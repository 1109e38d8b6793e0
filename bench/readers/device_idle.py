"""Share (%) of the window in which no op ran on the device: 1 minus the
union of the op intervals over the window, averaged over the devices."""


def read(rec, params):
    tr = rec.trace
    if tr is None or not tr.devices:
        return None
    return 100.0 * tr.idle_share()
