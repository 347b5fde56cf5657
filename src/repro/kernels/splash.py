"""Causal GQA attention through JAX's splash-attention Pallas kernels.

The installed ``jax.experimental.pallas.ops.tpu.splash_attention`` brings
a flash forward kernel and its own dq and dk/dv backward kernels under a
custom VJP, with block skipping: a block whose mask is all false is never
computed, so the causal upper triangle costs nothing. Its multi-head form
takes fewer KV heads than query heads: query head ``h`` reads KV head
``h // (nh // nkv)`` (the grouping ``layers.attention`` uses), and the
dk/dv kernel sums each KV head's gradient over its group.

Precision is the caller's: operands stay in their dtype; scores, softmax
statistics and accumulators are float32 (the forward kernel also takes
its probability-value product in float32). The softmax scale
``hd ** -0.5`` is folded into q, since the kernel applies none.

The kernels annotate their calls with their block sizes
(``pallas_call(metadata=...)``), which XLA prints as a multi-line frontend
attribute of the custom call. The call's ``op_name`` then no longer
shares a line with its instruction, and readers of the compiled text that
go by line (the chip benchmark's scope reader) cannot file the kernels
under ``attention``. ``causal_gqa`` therefore runs the kernels, forward
and backward, from their jaxprs with that annotation dropped.
"""

from __future__ import annotations

from functools import partial

import jax
from jax.experimental.pallas.ops.tpu import splash_attention as sa
from jax.extend import core as jex

__all__ = ["fits", "block_sizes", "causal_gqa"]

LANES = 128        # the kernel's blocks and head dim are lane multiples
# Preferred blocks, from a sweep over 256, 512 and 1024 on TPU v5e at
# S 4096, head_dim 128 (PERF.md, Findings).
BLOCK = 1024
FWD_KV_COMPUTE = 512


def _block(S: int, size: int) -> int:
    """The largest of ``size``, halved down to ``LANES``, dividing ``S``."""
    while S % size:
        size //= 2
    return size


def fits(S: int, head_dim: int) -> bool:
    """Whether the kernel serves sequence length ``S`` and ``head_dim``."""
    return S > 0 and S % LANES == 0 and head_dim % LANES == 0


def block_sizes(S: int) -> sa.BlockSizes:
    """The forward, dq and dk/dv blocks for a sequence of ``S``."""
    b = _block(S, BLOCK)
    return sa.BlockSizes(block_q=b, block_kv=b,
                         block_kv_compute=_block(S, FWD_KV_COMPUTE),
                         block_q_dq=b, block_kv_dq=b,
                         block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b)


def causal_gqa(q: jax.Array, k: jax.Array, v: jax.Array, *,
               interpret: bool = False) -> jax.Array:
    """Causal attention. q: [B,S,nh,hd]; k,v: [B,S,nkv,hd]. Returns
    [B,S,nh,hd]. ``interpret=True`` runs the Pallas interpreter (any
    backend); otherwise the kernels compile for TPU."""
    _, S, nh, hd = q.shape
    if not fits(S, hd):
        raise ValueError(f"no splash blocks for S={S}, head_dim={hd}")
    mask = sa.MultiHeadMask([sa.CausalMask((S, S))] * nh)

    def kernel(*args):
        # built where it runs, so its mask arrays are constants of that trace
        return jax.vmap(sa.make_splash_mha_single_device(
            mask, block_sizes=block_sizes(S), interpret=interpret))(*args)

    heads_first = lambda t: t.transpose(0, 2, 1, 3)     # [B,S,n,hd] <-> [B,n,S,hd]
    out = _unannotated(kernel)(heads_first(q * hd ** -0.5), heads_first(k), heads_first(v))
    return heads_first(out)


def _unannotated(kernel):
    """``kernel`` with its own forward and backward, each run from its
    jaxpr with every Pallas call's ``metadata`` dropped."""
    @jax.custom_vjp
    def run(*args):
        return _from_jaxpr(kernel)(*args)

    def fwd(*args):
        return _from_jaxpr(partial(jax.vjp, kernel))(*args)

    def bwd(pullback, d_out):
        return _from_jaxpr(lambda f, g: f(g))(pullback, d_out)

    run.defvjp(fwd, bwd)
    return run


def _from_jaxpr(fun):
    """``fun`` evaluated from its jaxpr, Pallas calls unannotated."""
    def run(*args):
        closed, shapes = jax.make_jaxpr(fun, return_shape=True)(*args)
        flat = jex.jaxpr_as_fun(_drop_metadata(closed))(*jax.tree.leaves(args))
        return jax.tree.unflatten(jax.tree.structure(shapes), flat)
    return run


def _drop_metadata(x):
    """A jaxpr (or a parameter holding jaxprs) with ``metadata=None`` on
    every ``pallas_call`` in it."""
    if isinstance(x, jex.ClosedJaxpr):
        return x.replace(jaxpr=_drop_metadata(x.jaxpr))
    if isinstance(x, tuple):
        return tuple(_drop_metadata(a) for a in x)
    if not isinstance(x, jex.Jaxpr):
        return x
    eqns = []
    for eqn in x.eqns:
        if eqn.primitive.name == "pallas_call":
            params = dict(eqn.params, metadata=None)
        else:
            params = {name: _drop_metadata(p) for name, p in eqn.params.items()}
        eqns.append(eqn.replace(params=params))
    return x.replace(eqns=eqns)
