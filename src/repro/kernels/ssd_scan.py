"""Mamba2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

TPU-native adaptation (DESIGN.md §3): the SSD block decomposition maps
naturally onto the MXU — the intra-chunk term is a [Q,Q]x[Q,hp] masked
matmul and the inter-chunk term a rank-N state contraction. The grid is
(batch, head, chunk) with the chunk axis innermost-sequential; the
[hp, N] fp32 running state lives in VMEM scratch across grid steps (the
same carry pattern as flash attention's (m, l, acc)).

Layout for the TPU compiler: ``A`` sits whole in SMEM and each head reads
its scalar; ``dt`` enters as ``[B, nh, S, 1]`` so its block ends in
``(chunk, 1)`` and meets the (8, 128) tiling rule; the within-chunk
cumulative sum is a lower-triangular ones matmul.

Padding note: S is padded to a chunk multiple with dt = 0, which makes
padded tokens exact no-ops in the recurrence (decay 1, update 0), so no
tail masking is needed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_scr, *,
                chunk: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    A = a_ref[pl.program_id(1)]                      # this head's decay rate (SMEM)
    x = x_ref[0, 0].astype(jnp.float32)              # [Q, hp]
    dt = dt_ref[0, 0].astype(jnp.float32)            # [Q, 1]
    Bm = b_ref[0].astype(jnp.float32)                # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)                # [Q, N]

    # cumulative log decay as a lower-triangular ones matmul (Mosaic has
    # no cumsum); HIGHEST keeps the f32 sum exact enough to exponentiate
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = rows >= cols
    a = dt * A                                       # [Q, 1] log decay
    a_cs = jax.lax.dot(tri.astype(jnp.float32), a,
                       precision=jax.lax.Precision.HIGHEST)  # [Q, 1]
    a_total = jnp.sum(a)                             # scalar: whole-chunk decay

    # intra-chunk (attention form): y_i = sum_{j<=i} C_i.B_j exp(acs_i-acs_j) dt_j x_j
    acs_i = jnp.broadcast_to(a_cs, (chunk, chunk))   # [i, j] -> acs_i
    diff = acs_i - acs_i.T
    L = jnp.where(tri, jnp.exp(diff), 0.0)           # [Q, Q]
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # [Q, Q]
    y = jax.lax.dot(cb * L, x * dt)                  # [Q, hp]

    # inter-chunk: y_i += (C_i . h_prev) * exp(acs_i)
    state = state_scr[...]                           # [hp, N]
    y += jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ()))) * jnp.exp(a_cs)

    # state update: h <- exp(sum a) h + sum_j exp(acs_last-acs_j) dt_j x_j B_j^T
    w = jnp.exp(a_total - a_cs) * dt                 # [Q, 1]
    upd = jax.lax.dot_general(x, Bm * w, (((0,), (0,)), ((), ())))  # [hp, N]
    state_scr[...] = state * jnp.exp(a_total) + upd

    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_scan_pallas(
    x: jax.Array,      # [B, nh, S, hp]
    dt: jax.Array,     # [B, nh, S]   (already softplus-ed)
    A: jax.Array,      # [nh]         (negative)
    Bm: jax.Array,     # [B, S, N]    (shared across heads)
    Cm: jax.Array,     # [B, S, N]
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, nh, S, hp = x.shape
    N = Bm.shape[-1]
    S_pad = math.ceil(S / chunk) * chunk
    if S_pad != S:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, S_pad - S)))   # dt=0 => exact no-op
        Bm = jnp.pad(Bm, ((0, 0), (0, S_pad - S), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, S_pad - S), (0, 0)))
    nc = S_pad // chunk

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(B, nh, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),    # A: whole [nh] vector
            pl.BlockSpec((1, 1, chunk, hp), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, hp), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nh, S_pad, hp), x.dtype),
        scratch_shapes=[pltpu.VMEM((hp, N), jnp.float32)],
        interpret=interpret,
    )(A.astype(jnp.float32), x, dt[..., None], Bm, Cm)
    return out[:, :, :S]
