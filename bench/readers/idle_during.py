"""Share (%) of the window in which the device ran no op while one of the
program's profiler spans named in ``params["spans"]`` was open on the host.

Device idle is what ``device_idle`` reads: the gaps between the op
intervals in the window, averaged over the devices.  The spans are merged
into one union, clipped to the window, and intersected with the gaps in one
sorted sweep, so overlapping spans count once.  Shares read with disjoint
span sets add up to at most ``device_idle``.  A trace without any such span
(a program that has none) gives nothing."""
from benchlib import trace as tracemod


def overlap_ns(a, b) -> float:
    """Total length of the intersection of two sorted lists of disjoint
    (start, end) intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(rec, params):
    tr = rec.trace
    if tr is None or not tr.devices:
        return None
    names = set(params["spans"])
    spans = [(e.start_ns, e.end_ns) for e in tr.host_events()
             if e.name in names]
    if not spans:
        return None
    held = tracemod.clip(tracemod.union(spans), tr.lo, tr.hi)
    idle = sum(overlap_ns(tracemod.gaps(tr.busy(d), tr.lo, tr.hi), held)
               for d in tr.devices) / len(tr.devices)
    return 100.0 * idle / (tr.hi - tr.lo)
