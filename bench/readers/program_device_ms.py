"""Mean device time (ms) of one execution of the jitted batch program:
module events on the device whose name matches ``params["program"]``."""


def read(rec, params):
    tr = rec.trace
    runs = tr.matching(tr.modules(), params["program"]) if tr else []
    if not runs:
        return None
    return 1e3 * sum(e.dur_ns for e in runs) * 1e-9 / len(runs)
