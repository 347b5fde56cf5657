"""Model-zoo primitives (pure JAX; Pallas fast paths live in repro.kernels).

Design notes:

* Attention is GQA-grouped (no KV repeat — grouped einsum keeps HLO bytes
  honest) with an optional query-chunk scan: memory O(S * q_chunk)
  instead of O(S^2), the XLA-level flash-attention pattern that keeps
  32k-token prefill compilable and is also the faithful cost model for
  the roofline. Sliding-window attention slices the KV span per chunk, so
  window archs (hymba) get the sub-quadratic compute they promise.
* MoE uses sort-free scatter dispatch with static capacity (GShard-style):
  deterministic shapes, expert-parallel shardable, dropped-token fraction
  reported by the router for tests.
* Mamba2 uses the SSD chunked block decomposition (intra-chunk attention
  form + inter-chunk state recurrence), matching kernels/ssd_ref.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "rmsnorm",
    "rope",
    "attention",
    "decode_attention",
    "mlp",
    "moe",
    "ssd_scan",
    "ssm_decode_step",
    "silu",
    "squared_relu",
]


def silu(x):
    return x * jax.nn.sigmoid(x)


def squared_relu(x):
    r = jax.nn.relu(x)
    return r * r


ACTIVATIONS = {"gated_silu": silu, "squared_relu": squared_relu, "gelu": jax.nn.gelu}


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps)).astype(dtype) * w.astype(dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0) -> jax.Array:
    """Rotary embedding. x: [..., S, n, hd]; positions: [..., S]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _attend(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array) -> jax.Array:
    """Grouped attention core. q: [B,Q,nkv,g,hd]; k,v: [B,S,nkv,hd];
    mask: [Q,S] boolean (True = attend)."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k) * scale
    scores = jnp.where(mask[None, None, None], scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    return jnp.einsum("bkgqs,bskh->bqkgh", probs, v)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 0,
) -> jax.Array:
    """Training/prefill attention.

    q: [B,S,nh,hd]; k,v: [B,S,nkv,hd]. Returns [B,S,nh,hd].
    ``q_chunk > 0`` scans over query chunks (O(S * chunk) memory);
    ``window > 0`` additionally slices KV to the live span per chunk.
    """
    B, S, nh, hd = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    qg = q.reshape(B, S, nkv, g, hd)

    def mask_for(q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
        m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
        if causal:
            m &= q_pos[:, None] >= k_pos[None, :]
        if window:
            m &= k_pos[None, :] > q_pos[:, None] - window
        return m

    if not q_chunk or S <= q_chunk:
        pos = jnp.arange(S)
        out = _attend(qg, k, v, mask_for(pos, pos))
        return out.reshape(B, S, nh, hd)

    assert S % q_chunk == 0, (S, q_chunk)
    n_chunks = S // q_chunk
    qc = qg.reshape(B, n_chunks, q_chunk, nkv, g, hd)

    if window:
        span = min(S, window + q_chunk)  # static KV slice per chunk

        def chunk_fn(_, inputs):
            idx, qi = inputs
            q0 = idx * q_chunk
            k0 = jnp.maximum(q0 + q_chunk - span, 0)
            ks = lax.dynamic_slice_in_dim(k, k0, span, axis=1)
            vs = lax.dynamic_slice_in_dim(v, k0, span, axis=1)
            # dynamic positions -> build mask from absolute indices
            q_pos = q0 + jnp.arange(q_chunk)
            k_pos = k0 + jnp.arange(span)
            m = q_pos[:, None] >= k_pos[None, :]
            m &= k_pos[None, :] > q_pos[:, None] - window
            return None, _attend(qi, ks, vs, m)
    else:
        def chunk_fn(_, inputs):
            idx, qi = inputs
            q0 = idx * q_chunk
            q_pos = q0 + jnp.arange(q_chunk)
            k_pos = jnp.arange(S)
            m = q_pos[:, None] >= k_pos[None, :] if causal else \
                jnp.ones((q_chunk, S), dtype=bool)
            return None, _attend(qi, k, v, m)

    idxs = jnp.arange(n_chunks)
    _, out = lax.scan(chunk_fn, None, (idxs, jnp.moveaxis(qc, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, nh, hd)
    return out


def decode_attention(
    q: jax.Array,          # [B, 1, nh, hd]
    k_cache: jax.Array,    # [B, S_max, nkv, hd]
    v_cache: jax.Array,
    cache_len: jax.Array,  # scalar: valid prefix length (new token included)
) -> jax.Array:
    B, Sq, nh, hd = q.shape
    nkv = k_cache.shape[2]
    g = nh // nkv
    qg = q.reshape(B, Sq, nkv, g, hd)
    S = k_cache.shape[1]
    valid = jnp.arange(S)[None, :] < cache_len  # [1, S]
    out = _attend(qg, k_cache, v_cache, jnp.broadcast_to(valid, (Sq, S)))
    return out.reshape(B, Sq, nh, hd)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp(x: jax.Array, params: Dict[str, jax.Array], kind: str,
        constrain=None) -> jax.Array:
    """Gated-SiLU (3 matmuls) / squared-ReLU / GELU (2 matmuls).
    ``constrain`` pins the d_ff-inner activations (Megatron TP hint)."""
    c = constrain or (lambda t: t)
    if kind == "gated_silu":
        return (c(silu(x @ params["wg"])) * c(x @ params["wi"])) @ params["wo"]
    act = ACTIVATIONS[kind]
    return c(act(x @ params["wi"])) @ params["wo"]


# ---------------------------------------------------------------------------
# MoE (scatter dispatch, static capacity)
# ---------------------------------------------------------------------------

def moe(
    x: jax.Array,                      # [T, H] flattened tokens
    params: Dict[str, jax.Array],      # router [H,E], wg/wi [E,H,F], wo [E,F,H]
    top_k: int,
    capacity_factor: float = 1.25,
    gated: bool = True,
    constrain=None,                    # fn([E,C,H]) -> [E,C,H]: EP sharding hook
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Returns (output [T,H], aux dict with load-balance stats)."""
    T, H = x.shape
    E = params["router"].shape[1]
    logits = (x.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # [T,E]
    gate_vals, expert_idx = lax.top_k(probs, top_k)            # [T,k]
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    C = int(max(1, capacity_factor * top_k * T / E))
    flat_e = expert_idx.reshape(-1)                            # [T*k]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)        # [T*k, E]
    pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)           # occupancy before me
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = pos < C                                             # capacity drop
    slot = flat_e * C + jnp.minimum(pos, C - 1)                # [T*k]

    x_rep = jnp.repeat(x, top_k, axis=0)                       # [T*k, H]
    buf = jnp.zeros((E * C, H), dtype=x.dtype)
    buf = buf.at[slot].add(jnp.where(keep[:, None], x_rep, 0))
    he = buf.reshape(E, C, H)
    if constrain is not None:          # expert-parallel: all-to-all emerges here
        he = constrain(he)

    if gated:
        inner = silu(jnp.einsum("ech,ehf->ecf", he, params["wg"])) * \
            jnp.einsum("ech,ehf->ecf", he, params["wi"])
    else:
        inner = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", he, params["wi"]))
    out_e = jnp.einsum("ecf,efh->ech", inner, params["wo"]).reshape(E * C, H)

    gathered = out_e[slot] * (keep[:, None] * gate_vals.reshape(-1)[:, None]).astype(x.dtype)
    out = gathered.reshape(T, top_k, H).sum(axis=1)

    aux = {
        "load": onehot.sum(axis=0),                            # tokens per expert
        "drop_fraction": 1.0 - keep.mean(),
        "router_entropy": -(probs * jnp.log(probs + 1e-9)).sum(-1).mean(),
    }
    return out, aux


def moe_ep(
    x: jax.Array,                      # [T, H] tokens (sharded over data axes)
    params: Dict[str, jax.Array],
    top_k: int,
    mesh,
    capacity_factor: float = 1.25,
    gated: bool = True,
    data_axes: Tuple = ("data",),
    expert_axis: str = "model",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Expert-parallel MoE via shard_map (the production path).

    Naive GSPMD partitioning of the scatter dispatch synthesizes one-hot
    matmuls costing 13-17x the useful FLOPs (measured — EXPERIMENTS.md
    §Perf iteration 6). Here every model-axis rank routes its (replicated)
    local tokens to ITS experts with plain dense scatter/gather, runs the
    local expert FFNs, and a single psum over the expert axis combines
    partial outputs. Experts are zero-padded to a multiple of the axis
    size (e.g. granite-moe's 40 -> 48 on a 16-way axis).
    """
    from jax.sharding import PartitionSpec as P

    H = x.shape[-1]
    E = params["router"].shape[-1]
    m = mesh.shape[expert_axis]
    E_pad = -(-E // m) * m
    pad_e = E_pad - E

    router = jnp.pad(params["router"], ((0, 0), (0, pad_e)))
    wg = jnp.pad(params["wg"], ((0, pad_e), (0, 0), (0, 0)))
    wi = jnp.pad(params["wi"], ((0, pad_e), (0, 0), (0, 0)))
    wo = jnp.pad(params["wo"], ((0, pad_e), (0, 0), (0, 0)))
    E_loc = E_pad // m

    def inner(x_l, router_r, wg_l, wi_l, wo_l):
        T_l = x_l.shape[0]
        r = jax.lax.axis_index(expert_axis)
        logits = (x_l.astype(jnp.float32) @ router_r.astype(jnp.float32))
        logits = jnp.where(jnp.arange(E_pad)[None, :] < E, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, top_k)       # [T_l, k]
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

        C = int(max(1, capacity_factor * top_k * T_l / E_pad))
        flat_e = expert_idx.reshape(-1)                           # [T_l*k]
        local = (flat_e >= r * E_loc) & (flat_e < (r + 1) * E_loc)
        le = jnp.where(local, flat_e - r * E_loc, E_loc)          # E_loc = trash
        onehot = jax.nn.one_hot(le, E_loc + 1, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(onehot, 0) - onehot,
                                  le[:, None], axis=1)[:, 0]
        keep = local & (pos < C)
        slot = jnp.where(keep, le * C + jnp.minimum(pos, C - 1), E_loc * C)

        x_rep = jnp.repeat(x_l, top_k, axis=0)
        buf = jnp.zeros((E_loc * C + 1, H), x_l.dtype)
        buf = buf.at[slot].add(jnp.where(keep[:, None], x_rep, 0))
        he = buf[:-1].reshape(E_loc, C, H)

        if gated:
            inner_act = silu(jnp.einsum("ech,ehf->ecf", he, wg_l)) * \
                jnp.einsum("ech,ehf->ecf", he, wi_l)
        else:
            inner_act = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", he, wi_l))
        out_e = jnp.einsum("ecf,efh->ech", inner_act, wo_l).reshape(E_loc * C, H)
        out_e = jnp.concatenate([out_e, jnp.zeros((1, H), out_e.dtype)])

        gathered = out_e[slot] * (keep[:, None] * gate_vals.reshape(-1)[:, None]
                                  ).astype(x_l.dtype)
        partial = gathered.reshape(T_l, top_k, H).sum(axis=1)
        out = jax.lax.psum(partial, expert_axis)                  # EP combine
        stat_axes = tuple(data_axes) + (expert_axis,)
        load = jax.lax.psum(onehot[:, :E_loc].sum(0), stat_axes)
        kept = jax.lax.psum(keep.astype(jnp.float32).sum(), stat_axes)
        total = jax.lax.psum(jnp.float32(T_l * top_k), stat_axes) / m
        drop = 1.0 - kept / total
        return out, load, drop

    t_spec = P(data_axes, None)
    e_spec = P(expert_axis, None, None)
    out, load, drop = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(t_spec, P(None, None), e_spec, e_spec, e_spec),
        out_specs=(t_spec, P(None), P()),
        check_vma=False,
    )(x, router, wg, wi, wo)
    aux = {"load": load.astype(jnp.float32),
           "drop_fraction": drop,
           "router_entropy": jnp.zeros((), jnp.float32)}
    return out, aux


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality, chunked)
# ---------------------------------------------------------------------------

def _segsum(a: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{j < m <= i} a[..., m]
    (lower-triangular cumulative log-decay)."""
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), dtype=bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_scan(
    x: jax.Array,        # [B, S, nh, hp]  (inner activations, headdim hp)
    dt: jax.Array,       # [B, S, nh]      (softplus-ed step size)
    A: jax.Array,        # [nh]            (negative decay rate)
    Bm: jax.Array,       # [B, S, N]       (input matrix, shared across heads)
    Cm: jax.Array,       # [B, S, N]       (output matrix)
    chunk: int = 256,
    initial_state: Optional[jax.Array] = None,   # [B, nh, hp, N]
    return_state: bool = False,
):
    """Chunked SSD forward (Mamba2 'state-space duality' algorithm [2405.21060]).

    h_t = exp(A dt_t) h_{t-1} + dt_t * x_t B_t^T ;  y_t = C_t h_t.
    Intra-chunk runs in attention form; inter-chunk is a state recurrence.
    """
    Bsz, S, nh, hp = x.shape
    N = Bm.shape[-1]
    if S % chunk:
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Sp = x.shape[1]
    nc = Sp // chunk

    f32 = jnp.float32
    xc = x.reshape(Bsz, nc, chunk, nh, hp).astype(f32)
    dtc = dt.reshape(Bsz, nc, chunk, nh).astype(f32)
    Bc = Bm.reshape(Bsz, nc, chunk, N).astype(f32)
    Cc = Cm.reshape(Bsz, nc, chunk, N).astype(f32)

    a = dtc * A.astype(f32)[None, None, None, :]        # [B,nc,Q,nh] log-decay
    a_h = jnp.moveaxis(a, -1, 2)                        # [B,nc,nh,Q]
    a_cs = jnp.cumsum(a_h, axis=-1)                     # within-chunk cumsum

    # 1) intra-chunk (attention form): scores[i,j] = C_i.B_j * exp(acs_i-acs_j) * dt_j
    L = jnp.exp(_segsum(a_h))                           # [B,nc,nh,Q,Q]
    cb = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)          # [B,nc,Q,Q]
    scores = cb[:, :, None] * L * jnp.moveaxis(dtc, -1, 2)[:, :, :, None, :]
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", scores, xc)

    # 2) chunk states: S_c = sum_j exp(acs_last - acs_j) * dt_j * B_j x_j^T
    decay_to_end = jnp.exp(a_cs[..., -1:] - a_cs)       # [B,nc,nh,Q]
    w = decay_to_end * jnp.moveaxis(dtc, -1, 2)         # [B,nc,nh,Q]
    states = jnp.einsum("bchj,bcjn,bcjhp->bchpn", w, Bc, xc)  # [B,nc,nh,hp,N]

    # 3) inter-chunk recurrence over chunk boundaries
    chunk_decay = jnp.exp(a_cs[..., -1])                # [B,nc,nh]
    init = jnp.zeros((Bsz, nh, hp, N), f32) if initial_state is None \
        else initial_state.astype(f32)

    def step(h, inp):
        dec, s = inp                                    # dec [B,nh], s [B,nh,hp,N]
        h_new = h * dec[..., None, None] + s
        return h_new, h                                  # emit state *entering* chunk

    (final_state, h_prevs) = lax.scan(
        step, init, (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0)))
    h_prev = jnp.moveaxis(h_prevs, 0, 1)                # [B,nc,nh,hp,N]

    # 4) inter-chunk output: y_i += (C_i . h_prev) * exp(acs_i)
    decay_from_start = jnp.exp(a_cs)                    # [B,nc,nh,Q]
    y_inter = jnp.einsum("bcin,bchpn,bchi->bcihp", Cc, h_prev, decay_from_start)

    y = (y_intra + y_inter).reshape(Bsz, Sp, nh, hp)[:, :S].astype(x.dtype)
    if return_state:
        return y, final_state
    return y


def ssm_decode_step(
    x: jax.Array,      # [B, nh, hp]
    dt: jax.Array,     # [B, nh]
    A: jax.Array,      # [nh]
    Bm: jax.Array,     # [B, N]
    Cm: jax.Array,     # [B, N]
    state: jax.Array,  # [B, nh, hp, N]
) -> Tuple[jax.Array, jax.Array]:
    """Single-token SSD recurrence (decode): O(1) per token."""
    f32 = jnp.float32
    dec = jnp.exp(dt.astype(f32) * A.astype(f32))                 # [B,nh]
    upd = jnp.einsum("bh,bhp,bn->bhpn", dt.astype(f32), x.astype(f32), Bm.astype(f32))
    new_state = state * dec[..., None, None] + upd
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(f32), new_state)
    return y.astype(x.dtype), new_state
