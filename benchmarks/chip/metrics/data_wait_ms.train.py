"""Input pipeline: milliseconds a step waits on ``next()`` of the
program's ``PrefetchIterator``, over the window (the harness's span)."""


def read(r):
    return r["spans"].get("data_wait", 0.0) / r["steps"] * 1e3
