"""Train step memory: ``peak_bytes_in_use`` of the fullest chip after the
window, in GiB."""


def read(r):
    return r["peak_bytes"] / 2 ** 30 if r["peak_bytes"] else None
