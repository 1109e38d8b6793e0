"""Requests completed inside the window, per second of the window."""


def read(rec, params):
    w0, w1 = rec.window
    done = sum(r.status == "ok" and w0 <= r.done <= w1 for r in rec.requests)
    return done / rec.seconds if done else None
