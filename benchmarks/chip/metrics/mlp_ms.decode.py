"""MLP, decoding: device ms a step in the ``mlp`` scope (the dense MLP and
its norm, its weights streamed from memory).
Over the step's executions inside the traced window (``scopes.py``)."""

import scopes


def read(r):
    return scopes.scope_ms(r, "mlp")
