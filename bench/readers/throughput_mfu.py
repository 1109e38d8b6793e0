"""Served work's share (%) of the chip's int8 peak over the window:
completed requests per second x integer ops per sample (``rec.work``, the
configuration's counts) / peak."""


def read(rec, params):
    w0, w1 = rec.window
    done = sum(r.status == "ok" and w0 <= r.done <= w1 for r in rec.requests)
    if not done or rec.peak is None:
        return None
    rate = done / rec.seconds
    return 100.0 * rate * rec.work.ops_per_sample(rec.layers) / \
        rec.peak["int8_ops_per_s"]
