"""Nearest-rank quantile ``params["q"]`` (ms) of the interval between two
stamps the program puts on each ticket, ``params["start"]`` and
``params["end"]`` (``Ticket`` properties on the ``time.perf_counter()``
clock), over the window's answered requests.  A program whose tickets lack
the stamps gives nothing."""
import math


def read(rec, params):
    dt = []
    for r in rec.window_requests:
        if r.status != "ok" or r.ticket is None:
            continue
        a = getattr(r.ticket, params["start"], math.nan)
        b = getattr(r.ticket, params["end"], math.nan)
        if math.isfinite(a) and math.isfinite(b):
            dt.append(b - a)
    if not dt:
        return None
    dt.sort()
    return 1e3 * dt[max(0, math.ceil(params["q"] * len(dt)) - 1)]
