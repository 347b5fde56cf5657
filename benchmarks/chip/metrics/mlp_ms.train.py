"""MLP, training: device ms a step in the ``mlp`` scope (the dense MLP and
its norm; forward, recomputed and backward).
Over the step's executions inside the traced window (``scopes.py``)."""

import scopes


def read(r):
    return scopes.scope_ms(r, "mlp")
