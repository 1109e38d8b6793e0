"""The whole program's share (%) of the int8 peak while it runs: integer
ops of the real (unpadded) samples the traced run served, over the device
time of the program's executions times the peak.  Ops are the algorithm's
(``rec.work``, the configuration's counts), so padding and recompute lower
the share."""


def read(rec, params):
    tr = rec.trace
    runs = tr.matching(tr.modules(), params["program"]) if tr else []
    samples = rec.session["requests"]
    if not runs or not samples:
        return None
    t = sum(e.dur_ns for e in runs) * 1e-9
    ops = samples * rec.work.ops_per_sample(rec.layers)
    return 100.0 * ops / (t * rec.peak["int8_ops_per_s"])
