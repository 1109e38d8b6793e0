"""Share (%) of executed batch slots that were padding:
``padded / (requests + padded)`` from the session's counters."""


def read(rec, params):
    d = rec.session
    slots = d["requests"] + d["padded"]
    return 100.0 * d["padded"] / slots if slots else None
