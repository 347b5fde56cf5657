"""Layer loop, decoding: device ms a step in the ``layer_scan`` scope, what
the loop over layers does outside the inner scopes (weight slices, carries).
Over the step's executions inside the traced window (``scopes.py``)."""

import scopes


def read(r):
    return scopes.scope_ms(r, "layer_scan")
