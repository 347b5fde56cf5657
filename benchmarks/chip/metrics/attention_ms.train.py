"""Attention, training: device ms a step in the ``attention`` scope (scores,
mask, softmax and weighted sum; forward, recomputed and backward).
Over the step's executions inside the traced window (``scopes.py``)."""

import scopes


def read(r):
    return scopes.scope_ms(r, "attention")
