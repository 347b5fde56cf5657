"""Optimizer, training: device ms a step in the ``optimizer`` scope (global
norm, clip and Adam).
Over the step's executions inside the traced window (``scopes.py``)."""

import scopes


def read(r):
    return scopes.scope_ms(r, "optimizer")
