"""A kernel family's share (%) of its roofline: the least time the chip
could take for the work the plan gives that family (``rec.work``, the
configuration's counts: useful outputs only, each real sample's int8 input
and output moved once, the int8 weights once per executed batch) over the
traced run's samples and batches, over the summed device time of the
family's events (op events matching ``params["pattern"]``).  The bound
(compute or memory) is reported on standard error."""
import sys


def read(rec, params):
    tr = rec.trace
    evs = tr.matching(tr.ops(), params["pattern"]) if tr else []
    samples, batches = rec.session["requests"], rec.session["batches"]
    if not evs or not samples:
        return None
    least, bound = rec.work.family_least_time_s(
        rec.layers, params["family"], samples, batches, rec.peak)
    t = sum(e.dur_ns for e in evs) * 1e-9
    print(f"{params['family']}: {len(evs)} kernel events, {t:.6f} s, "
          f"least time {least:.6f} s ({bound}-bound)", file=sys.stderr)
    return 100.0 * least / t
