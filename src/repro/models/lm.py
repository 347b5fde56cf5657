"""Composable LM model zoo: one parameterised decoder/encoder covering all
10 assigned architectures (dense GQA, MoE, SSM, hybrid, encoder-only,
embeds-input backbones).

Params are plain pytrees with layer-stacked leaves ([L, ...]) consumed by
``lax.scan`` — the production pattern (MaxText-style) that keeps HLO size
O(1) in depth, bounds compile time, and gives the remat policy a single
boundary per layer.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from ..configs.base import ArchConfig
from .layers import (
    attention,
    decode_attention,
    mlp,
    moe,
    rmsnorm,
    rope,
    silu,
    ssd_scan,
    ssm_decode_step,
)

__all__ = ["RunCfg", "init_params", "forward", "loss_fn", "init_cache", "decode_step",
           "param_count"]


@dataclass(frozen=True)
class RunCfg:
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    q_chunk: int = 1024
    ssd_chunk: int = 256
    remat: bool = True
    scan_layers: bool = True
    capacity_factor: float = 1.25
    logits_fp32: bool = True
    # distribution (None = single-host semantics, constraints are no-ops)
    mesh: Any = None
    batch_axes: Any = ("data",)        # ("pod","data") on multi-pod meshes
    seq_shard: bool = False            # sequence-parallel residual stream
    expert_axis: Any = "model"         # MoE expert-parallel axis


def _cst(x: jax.Array, cfg: "RunCfg", spec_dims: Tuple) -> jax.Array:
    """with_sharding_constraint when a mesh is configured, else identity.
    Axes that don't divide the actual dim are dropped (e.g. 49155 vocab,
    batch-1 decode)."""
    if cfg.mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..parallel.sharding import fit_first
    spec = fit_first([P(*spec_dims)], tuple(x.shape), cfg.mesh)
    return lax.with_sharding_constraint(x, NamedSharding(cfg.mesh, spec))


def _residual_spec(cfg: "RunCfg") -> Tuple:
    return (cfg.batch_axes, "model" if cfg.seq_shard else None, None)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _dense(key, shape, dtype, scale=None):
    # fan-in is the input dim of the matrix; leading dims are the layer
    # stack ([L, H, F]) and experts ([L, E, H, F])
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = (1.0 / fan_in) ** 0.5 if scale is None else scale
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _attn_layer_params(arch: ArchConfig, key, L, dtype):
    H, nh, nkv, hd = arch.d_model, arch.n_heads, arch.n_kv, arch.head_dim
    ks = jax.random.split(key, 4)
    out_scale = (1.0 / (nh * hd)) ** 0.5 / (2 * arch.num_layers) ** 0.5
    return {
        "wq": _dense(ks[0], (L, H, nh * hd), dtype),
        "wk": _dense(ks[1], (L, H, nkv * hd), dtype),
        "wv": _dense(ks[2], (L, H, nkv * hd), dtype),
        "wo": _dense(ks[3], (L, nh * hd, H), dtype, scale=out_scale),
    }


def _mlp_layer_params(arch: ArchConfig, key, L, dtype):
    H, F = arch.d_model, arch.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "wi": _dense(ks[0], (L, H, F), dtype),
        "wo": _dense(ks[1], (L, F, H), dtype, scale=(1.0 / F) ** 0.5 / (2 * arch.num_layers) ** 0.5),
    }
    if arch.mlp == "gated_silu":
        p["wg"] = _dense(ks[2], (L, H, F), dtype)
    return p


def _moe_layer_params(arch: ArchConfig, key, L, dtype):
    H, E, F = arch.d_model, arch.n_experts, arch.d_ff_expert
    ks = jax.random.split(key, 4)
    return {
        "router": _dense(ks[0], (L, H, E), dtype, scale=0.02),
        "wg": _dense(ks[1], (L, E, H, F), dtype),
        "wi": _dense(ks[2], (L, E, H, F), dtype),
        "wo": _dense(ks[3], (L, E, F, H), dtype, scale=(1.0 / F) ** 0.5 / (2 * arch.num_layers) ** 0.5),
    }


def _ssm_layer_params(arch: ArchConfig, key, L, dtype):
    H, di, N = arch.d_model, arch.d_inner, arch.ssm_state
    nh = arch.ssm_n_heads
    conv_dim = di + 2 * N
    d_in_proj = 2 * di + 2 * N + nh
    ks = jax.random.split(key, 6)
    dt = jax.random.uniform(ks[4], (L, nh), jnp.float32, 1e-3, 1e-1)
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    return {
        "in_proj": _dense(ks[0], (L, H, d_in_proj), dtype),
        "conv_w": _dense(ks[1], (L, arch.conv_width, conv_dim), dtype, scale=0.3),
        "conv_b": jnp.zeros((L, conv_dim), dtype),
        "A_log": jnp.log(jax.random.uniform(ks[2], (L, nh), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((L, nh), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "ssm_norm": jnp.ones((L, di), dtype),
        "out_proj": _dense(ks[3], (L, di, H), dtype, scale=(1.0 / di) ** 0.5 / (2 * arch.num_layers) ** 0.5),
    }


def init_params(arch: ArchConfig, key: jax.Array, cfg: RunCfg = RunCfg()) -> Dict:
    L, H, V = arch.num_layers, arch.d_model, arch.vocab
    dtype = cfg.param_dtype
    keys = jax.random.split(key, 8)
    layers: Dict[str, Any] = {"norm1": jnp.ones((L, H), dtype)}
    if arch.block in ("attn", "hymba"):
        layers["attn"] = _attn_layer_params(arch, keys[0], L, dtype)
    if arch.block in ("ssm", "hymba"):
        layers["ssm"] = _ssm_layer_params(arch, keys[1], L, dtype)
    if arch.block in ("attn", "hymba") and (arch.d_ff or arch.n_experts):
        layers["norm2"] = jnp.ones((L, H), dtype)
        if arch.n_experts:
            layers["moe"] = _moe_layer_params(arch, keys[2], L, dtype)
        else:
            layers["mlp"] = _mlp_layer_params(arch, keys[3], L, dtype)

    params: Dict[str, Any] = {
        "layers": layers,
        "final_norm": jnp.ones((H,), dtype),
        "lm_head": _dense(keys[5], (H, V), dtype, scale=0.02),
    }
    if not arch.embeds_input:
        params["embed"] = _dense(keys[4], (V, H), dtype, scale=0.02)
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _run_attn(arch: ArchConfig, p, h: jax.Array, positions: jax.Array, cfg: RunCfg):
    B, S, H = h.shape
    nh, nkv, hd = arch.n_heads, arch.n_kv, arch.head_dim
    with jax.named_scope("attn_proj"):
        # Megatron-SP pattern: gather sequence, shard heads over "model" —
        # explicit hints so GSPMD never falls back to gathering whole weights
        h = _cst(h, cfg, (cfg.batch_axes, None, None))
        q = _cst(h @ p["wq"], cfg, (cfg.batch_axes, None, "model")).reshape(B, S, nh, hd)
        k = _cst(h @ p["wk"], cfg, (cfg.batch_axes, None, "model")).reshape(B, S, nkv, hd)
        v = _cst(h @ p["wv"], cfg, (cfg.batch_axes, None, "model")).reshape(B, S, nkv, hd)
        q, k = rope(q, positions), rope(k, positions)
        o = attention(q, k, v, causal=arch.causal, window=arch.window, q_chunk=cfg.q_chunk)
        return o.reshape(B, S, nh * hd) @ p["wo"]


def _run_ssm(arch: ArchConfig, p, h: jax.Array, cfg: RunCfg):
    with jax.named_scope("ssm"):
        B, S, H = h.shape
        di, N, nh = arch.d_inner, arch.ssm_state, arch.ssm_n_heads
        hp = arch.ssm_headdim
        proj = h @ p["in_proj"]
        z, xbc, dtr = jnp.split(proj, [di, 2 * di + 2 * N], axis=-1)
        # causal depthwise conv over (x, B, C)
        K = arch.conv_width
        padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(padded[:, k:k + S] * p["conv_w"][k] for k in range(K)) + p["conv_b"]
        xbc = silu(conv).astype(h.dtype)
        xs, Bm, Cm = jnp.split(xbc, [di, di + N], axis=-1)
        dt = jax.nn.softplus(dtr.astype(jnp.float32) + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        y = ssd_scan(xs.reshape(B, S, nh, hp), dt, A, Bm, Cm, chunk=cfg.ssd_chunk)
        y = y + p["D"].astype(y.dtype)[None, None, :, None] * xs.reshape(B, S, nh, hp)
        y = y.reshape(B, S, di)
        y = rmsnorm(y * silu(z), p["ssm_norm"])
        return y @ p["out_proj"]


def _run_ffn(arch: ArchConfig, lp, x: jax.Array, cfg: RunCfg):
    """MLP or MoE sublayer (with pre-norm), returns (delta, aux)."""
    if not (arch.d_ff or arch.n_experts):
        return jnp.zeros_like(x), _zero_aux(arch)
    B, S, H = x.shape
    if arch.n_experts:
        with jax.named_scope("moe"):
            h2 = rmsnorm(x, lp["norm2"])
            if cfg.mesh is not None:
                # shard_map expert parallelism (§Perf iter. 6): local dispatch
                # per expert rank + one psum combine — avoids GSPMD's one-hot-
                # matmul synthesis for cross-shard scatter (13-17x flops)
                from .layers import moe_ep
                h2 = _cst(h2, cfg, (cfg.batch_axes, None, None))
                out, aux = moe_ep(h2.reshape(B * S, H), lp["moe"], arch.top_k,
                                  cfg.mesh, cfg.capacity_factor,
                                  gated=arch.mlp == "gated_silu",
                                  data_axes=cfg.batch_axes,
                                  expert_axis=cfg.expert_axis)
            else:
                out, aux = moe(h2.reshape(B * S, H), lp["moe"], arch.top_k,
                               cfg.capacity_factor, gated=arch.mlp == "gated_silu")
            return out.reshape(B, S, H), {"moe_drop": aux["drop_fraction"],
                                          "moe_load_max": aux["load"].max().astype(jnp.float32)}
    with jax.named_scope("mlp"):
        h2 = _cst(rmsnorm(x, lp["norm2"]), cfg, (cfg.batch_axes, None, None))
        inner_cst = (lambda t: _cst(t, cfg, (cfg.batch_axes, None, "model"))) \
            if cfg.mesh is not None else None
        return mlp(h2, lp["mlp"], arch.mlp, constrain=inner_cst), _zero_aux(arch)


def _zero_aux(arch: ArchConfig):
    if arch.n_experts:
        return {"moe_drop": jnp.zeros((), jnp.float32),
                "moe_load_max": jnp.zeros((), jnp.float32)}
    return {}


def _block(arch: ArchConfig, cfg: RunCfg, x: jax.Array, lp, positions: jax.Array):
    h = rmsnorm(x, lp["norm1"])
    if arch.block == "attn":
        x = x + _run_attn(arch, lp["attn"], h, positions, cfg)
    elif arch.block == "ssm":
        x = x + _run_ssm(arch, lp["ssm"], h, cfg)
    else:  # hymba: parallel attn + mamba heads, fused mean
        a = _run_attn(arch, lp["attn"], h, positions, cfg)
        s = _run_ssm(arch, lp["ssm"], h, cfg)
        x = x + 0.5 * (a + s)
    delta, aux = _run_ffn(arch, lp, x, cfg)
    return _cst(x + delta, cfg, _residual_spec(cfg)), aux


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------

def forward(
    arch: ArchConfig,
    params: Dict,
    tokens: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,
    cfg: RunCfg = RunCfg(),
    logits_positions: str = "all",   # "all" | "last" (prefill: avoid B*S*V)
) -> Tuple[jax.Array, Dict]:
    """Returns (logits [B,S,V] or [B,1,V], aux). Input is ``tokens`` [B,S]
    for LM archs or ``embeds`` [B,S,H] for stub-frontend (vlm/audio) archs."""
    if arch.embeds_input:
        assert embeds is not None, f"{arch.name} takes precomputed embeddings"
        x = embeds.astype(cfg.compute_dtype)
    else:
        with jax.named_scope("embed"):
            x = params["embed"].astype(cfg.compute_dtype)[tokens]
    x = _cst(x, cfg, _residual_spec(cfg))
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    # cast BEFORE the layer scan: the FSDP all-gather inside each layer then
    # moves bf16, not fp32 — halves the dominant collective volume
    # (EXPERIMENTS.md §Perf iteration 2)
    cast = lambda t: jax.tree.map(lambda a: a.astype(cfg.compute_dtype)
                                  if a.dtype in (jnp.float32, jnp.bfloat16) and a.ndim > 1
                                  else a, t)

    def body(x, lp):
        return _block(arch, cfg, x, lp, positions)

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)

    with jax.named_scope("layer_scan"):
        layers = cast(params["layers"])
        if cfg.scan_layers:
            x, aux = lax.scan(body, x, layers)
            aux = jax.tree.map(jnp.mean, aux)
        else:
            aux = _zero_aux(arch)
            L = arch.num_layers
            for i in range(L):
                lp = jax.tree.map(lambda a: a[i], layers)
                x, aux_i = body(x, lp)
                aux = jax.tree.map(lambda a, b: a + b / L, aux, aux_i)

    with jax.named_scope("head"):
        if logits_positions == "last":
            x = x[:, -1:]                       # prefill: next-token logits only
        x = rmsnorm(x, params["final_norm"].astype(cfg.compute_dtype))
        logits = x @ params["lm_head"].astype(cfg.compute_dtype)
        logits = _cst(logits, cfg, (cfg.batch_axes, None, "model"))  # vocab-sharded
        if cfg.logits_fp32:
            logits = logits.astype(jnp.float32)
    return logits, aux


def loss_fn(
    arch: ArchConfig,
    params: Dict,
    batch: Dict[str, jax.Array],
    cfg: RunCfg = RunCfg(),
) -> Tuple[jax.Array, Dict]:
    """Next-token (or frame-label) cross entropy; batch keys:
    tokens|embeds, labels, and optional loss_mask."""
    logits, aux = forward(arch, params,
                          tokens=batch.get("tokens"),
                          embeds=batch.get("embeds"), cfg=cfg)
    labels = batch["labels"]
    with jax.named_scope("loss"):
        # logsumexp form: avoids materialising a second logits-sized
        # log_softmax buffer; the vocab reduction stays sharded under GSPMD
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = lse - ll
        mask = batch.get("loss_mask")
        if mask is None:
            loss = nll.mean()
        else:
            loss = (nll * mask).sum() / jnp.clip(mask.sum(), 1.0)
    metrics = {"loss": loss, **aux}
    if arch.n_experts:
        loss = loss + 0.0 * aux.get("moe_drop", 0.0)  # keep aux alive
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------

def init_cache(arch: ArchConfig, batch: int, max_len: int, cfg: RunCfg = RunCfg()) -> Dict:
    """KV / SSM state cache, layer-stacked for scan. Window archs keep a
    ring buffer of ``window`` positions; SSM archs a constant-size state."""
    L = arch.num_layers
    dtype = cfg.compute_dtype
    cache: Dict[str, jax.Array] = {}
    if arch.has_attention:
        span = min(arch.window, max_len) if arch.window else max_len
        kv_shape = (L, batch, span, arch.n_kv, arch.head_dim)
        cache["k"] = jnp.zeros(kv_shape, dtype)
        cache["v"] = jnp.zeros(kv_shape, dtype)
    if arch.block in ("ssm", "hymba"):
        conv_dim = arch.d_inner + 2 * arch.ssm_state
        cache["conv"] = jnp.zeros((L, batch, arch.conv_width - 1, conv_dim), dtype)
        cache["ssm"] = jnp.zeros(
            (L, batch, arch.ssm_n_heads, arch.ssm_headdim, arch.ssm_state), jnp.float32)
    return cache


def _row_major(tree):
    """Pins each array to its default row-major layout. The decode loop
    carries the K/V cache under this pin: left free, XLA lays the carry
    out for the attention read and copies the whole cache into and out
    of that layout on every step."""
    return jax.tree.map(
        lambda a: with_layout_constraint(a, Layout(tuple(range(a.ndim)))), tree)


def _decode_attn(arch: ArchConfig, p, h, kv, layer, pos, cfg):
    """Attention for one new token. ``kv`` is the layer-stacked K/V cache
    ``[L,B,span,nkv,hd]``: this step's k and v are written at ``layer``'s
    slot in place, and the layer reads back its own span."""
    B = h.shape[0]
    nh, nkv, hd = arch.n_heads, arch.n_kv, arch.head_dim
    with jax.named_scope("attn_proj"):
        q = (h @ p["wq"]).reshape(B, 1, nh, hd)
        k = (h @ p["wk"]).reshape(B, 1, nkv, hd)
        v = (h @ p["wv"]).reshape(B, 1, nkv, hd)
        posb = jnp.broadcast_to(pos[None, None], (B, 1))
        q, k = rope(q, posb), rope(k, posb)
        span = kv["k"].shape[2]
        slot = pos % span if arch.window else pos
        with jax.named_scope("kv_write"):
            kv = {"k": lax.dynamic_update_slice(kv["k"], k[None], (layer, 0, slot, 0, 0)),
                  "v": lax.dynamic_update_slice(kv["v"], v[None], (layer, 0, slot, 0, 0))}
        with jax.named_scope("attention"):
            k_cache, v_cache = (lax.dynamic_index_in_dim(kv[n], layer, keepdims=False)
                                for n in ("k", "v"))
        cache_len = jnp.minimum(pos + 1, span)
        o = decode_attention(q, k_cache, v_cache, cache_len)
        return o.reshape(B, 1, nh * hd) @ p["wo"], kv


def _decode_ssm(arch: ArchConfig, p, h, c, cfg):
    with jax.named_scope("ssm"):
        B = h.shape[0]
        di, N, nh, hp = arch.d_inner, arch.ssm_state, arch.ssm_n_heads, arch.ssm_headdim
        proj = (h @ p["in_proj"])[:, 0]                        # [B, d_in_proj]
        z, xbc, dtr = jnp.split(proj, [di, 2 * di + 2 * N], axis=-1)
        # streaming causal conv: state holds last K-1 inputs
        K = arch.conv_width
        hist = jnp.concatenate([c["conv"], xbc[:, None]], axis=1)   # [B,K,conv_dim]
        conv = (hist * p["conv_w"]).sum(axis=1) + p["conv_b"]
        new_conv_state = hist[:, 1:]
        xbc_a = silu(conv).astype(h.dtype)
        xs, Bm, Cm = jnp.split(xbc_a, [di, di + N], axis=-1)
        dt = jax.nn.softplus(dtr.astype(jnp.float32) + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        y, new_state = ssm_decode_step(xs.reshape(B, nh, hp), dt, A, Bm, Cm, c["ssm"])
        y = y + p["D"].astype(y.dtype)[None, :, None] * xs.reshape(B, nh, hp)
        y = y.reshape(B, 1, di)
        y = rmsnorm(y * silu(z)[:, None], p["ssm_norm"])
        return y @ p["out_proj"], {"conv": new_conv_state, "ssm": new_state}


def decode_step(
    arch: ArchConfig,
    params: Dict,
    cache: Dict,
    tokens: Optional[jax.Array] = None,     # [B] token ids
    embeds: Optional[jax.Array] = None,     # [B, H] for stub-frontend archs
    pos: jax.Array = None,                  # scalar int32: current position
    cfg: RunCfg = RunCfg(),
) -> Tuple[jax.Array, Dict]:
    """One autoregressive step: returns (logits [B,V], new cache)."""
    if arch.embeds_input:
        x = embeds[:, None].astype(cfg.compute_dtype)
    else:
        with jax.named_scope("embed"):
            x = params["embed"].astype(cfg.compute_dtype)[tokens][:, None]

    cast = lambda t: jax.tree.map(lambda a: a.astype(cfg.compute_dtype)
                                  if a.dtype in (jnp.float32, jnp.bfloat16) and a.ndim > 1
                                  else a, t)

    # The K/V cache is a loop carry written in place, one position a
    # layer; only the SSM states, rewritten whole each step, are outputs.
    kv = {n: cache[n] for n in ("k", "v") if n in cache}
    states = {n: cache[n] for n in ("conv", "ssm") if n in cache}

    def body(carry, scanned):
        x, kv = carry
        lp, c, layer = scanned
        lp = cast(lp)
        h = rmsnorm(x, lp["norm1"])
        new_c = {}
        if arch.block == "attn":
            o, kv = _decode_attn(arch, lp["attn"], h, kv, layer, pos, cfg)
            x = x + o
        elif arch.block == "ssm":
            o, new_c = _decode_ssm(arch, lp["ssm"], h, c, cfg)
            x = x + o
        else:
            a, kv = _decode_attn(arch, lp["attn"], h, kv, layer, pos, cfg)
            s, new_c = _decode_ssm(arch, lp["ssm"], h, c, cfg)
            x = x + 0.5 * (a + s)
        delta, _ = _run_ffn(arch, lp, x, cfg)
        return (x + delta, _row_major(kv)), new_c

    with jax.named_scope("layer_scan"):
        L = arch.num_layers
        if cfg.scan_layers:
            (x, kv), states = lax.scan(body, (x, kv),
                                       (params["layers"], states, jnp.arange(L)))
        else:
            new_states = []
            for i in range(L):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                c = jax.tree.map(lambda a: a[i], states)
                (x, kv), nc = body((x, kv), (lp, c, i))
                new_states.append(nc)
            states = jax.tree.map(lambda *xs: jnp.stack(xs), *new_states)
    with jax.named_scope("head"):
        x = rmsnorm(x, params["final_norm"].astype(cfg.compute_dtype))
        logits = (x @ params["lm_head"].astype(cfg.compute_dtype))[:, 0]
        return logits.astype(jnp.float32), {**kv, **states}
