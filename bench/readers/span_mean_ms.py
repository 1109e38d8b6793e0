"""Mean duration (ms) of the program's profiler spans named
``params["span"]`` that start inside the window."""


def read(rec, params):
    tr = rec.trace
    if tr is None:
        return None
    durs = [e.dur_ns for e in tr.host_events()
            if e.name == params["span"] and tr.lo <= e.start_ns < tr.hi]
    if not durs:
        return None
    return 1e-6 * sum(durs) / len(durs)
