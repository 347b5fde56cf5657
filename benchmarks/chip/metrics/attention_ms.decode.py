"""Attention, decoding: device ms a step in the ``attention`` scope (the read
of each layer's cache span, scores, softmax and weighted sum).
Over the step's executions inside the traced window (``scopes.py``)."""

import scopes


def read(r):
    return scopes.scope_ms(r, "attention")
