"""Set-up time (s): process start to the first timed request — weights
and inputs from the seed, calibration, plan load, the program's
quantization, compilation and warm-up of every bucket the cell uses, and
the traffic's lead-in."""


def read(rec, params):
    return rec.setup_s
