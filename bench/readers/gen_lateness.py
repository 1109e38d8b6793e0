"""How late the load generator submitted (ms): submit instant minus due
instant, nearest-rank quantile ``params["q"]`` over the window's requests.
A starved generator shows here before it is read as a fast server."""
import math


def read(rec, params):
    late = sorted(r.submitted - r.due for r in rec.window_requests)
    if not late:
        return None
    return 1e3 * late[max(0, math.ceil(params["q"] * len(late)) - 1)]
