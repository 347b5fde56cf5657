"""jit'd public wrappers around the Pallas kernels.

The kernels compile for TPU. ``interpret=True`` runs them through the
Pallas interpreter instead (any backend; the tests use it on CPU).
"""

from __future__ import annotations

from functools import partial

import jax

from .flash_attention import flash_attention as _flash
from .rmsnorm import rmsnorm_pallas as _rmsnorm
from .ssd_scan import ssd_scan_pallas as _ssd


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128, block_k=128,
                    interpret=False):
    return _flash(q, k, v, causal=causal, window=window,
                  block_q=block_q, block_k=block_k, interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk=256, interpret=False):
    return _ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, w, *, eps=1e-5, block_rows=256, interpret=False):
    return _rmsnorm(x, w, eps=eps, block_rows=block_rows, interpret=interpret)
