"""Real requests per dispatch, from the differences of the session's own
counters (``SessionStats.requests`` / ``batches``) across the run."""


def read(rec, params):
    d = rec.session
    return d["requests"] / d["batches"] if d["batches"] else None
