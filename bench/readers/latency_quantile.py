"""End-to-end latency quantile (ms) over every request due in the window,
timed from the instant it was due to its ticket's completion stamp.  A
request refused, failed or never answered counts as missing: it takes the
longest latency the run could have measured (due to the collection
deadline).  Nearest-rank quantile, ``params["q"]`` in (0, 1]."""
import math


def read(rec, params):
    lat = sorted((r.done - r.due) if r.status == "ok" else
                 (rec.deadline - r.due) for r in rec.window_requests)
    if not lat:
        return None
    return 1e3 * lat[max(0, math.ceil(params["q"] * len(lat)) - 1)]
