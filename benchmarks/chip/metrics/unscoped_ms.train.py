"""Train step: device ms a step in ops outside every named scope.
Over the step's executions inside the traced window (``scopes.py``)."""

import scopes


def read(r):
    return scopes.scope_ms(r, scopes.UNSCOPED)
