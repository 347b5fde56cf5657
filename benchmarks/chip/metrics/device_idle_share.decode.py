"""Device: share of the traced window in which no operation ran on the
device, mean over devices, in percent (``trace_reduce``)."""


def read(r):
    return None if r["trace"] is None else r["trace"]["idle_share"] * 100
