"""Head and loss, training: device ms a step in the ``head`` and ``loss``
scopes (final norm, ``lm_head``, logsumexp and NLL, and their backward).
Over the step's executions inside the traced window (``scopes.py``)."""

import scopes


def read(r):
    return scopes.scope_ms(r, "head", "loss")
