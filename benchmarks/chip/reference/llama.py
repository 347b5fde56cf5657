"""Plain float32 reference of a llama-style decoder, its loss and AdamW.

Written from the published description of the architecture (pre-norm
RMSNorm, rotary positions, grouped-query causal attention, a gated-SiLU
MLP or a top-k mixture of gated-SiLU experts) and from the configuration
file's numbers; it imports nothing of the program under test. Leaves are
addressed by the same paths the benchmark's weight generator uses.

Granite's multipliers, as ``GraniteMoeForCausalLM`` of Hugging Face
transformers applies them (``modeling_granitemoe.py``; the model card of
ibm-granite/granite-3.0-3b-a800m-base), each applied only where the file
gives a value other than the plain one, so that a plain model's
arithmetic is exactly the llama decoder's:

- ``x_0 = embedding_multiplier * embed[tokens]``;
- attention scores ``q k^T * attention_multiplier`` (plain:
  ``/ sqrt(head_dim)``);
- each sublayer's output ``x + residual_multiplier * f(norm(x))``;
- logits ``norm(x_L) W_head / logits_scaling``;
- with ``tie_word_embeddings`` the head is the embedding transposed,
  ``W_head = embed^T``.

``prec`` selects the matrix products: ``"f32"`` is float32 at the highest
precision (the reference); ``"fp8"`` is the control, the precision below
the bfloat16 the configurations state, in the usual fp8 training recipe
(Micikevicius et al., "FP8 Formats for Deep Learning", arXiv:2209.05433):
each operand of a forward product is rounded to float8 e4m3 and each
gradient that enters a backward product to float8 e5m2, every tensor
under a per-tensor scale that maps its largest magnitude to the format's
largest value; products accumulate in float32 and are scaled back.

Departures, each a rule of the configuration as run and not of the
published model: with a ``capacity_factor`` a mixture-of-experts layer
keeps at most ``capacity_factor * k * T / E`` assignments per expert, in
token order, and drops the rest (without one it keeps every assignment,
as the published models route); weight decay applies to every stored array of rank 2
or more, which in the layer-stacked layout includes the per-layer norm
scales.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 1024        # query rows per attention block
LOSS_BLOCK = 1024     # tokens per block of the head and loss


def _scaled(x, dtype):
    """x over a per-tensor scale that maps its largest magnitude to the
    largest value of ``dtype``, rounded to ``dtype``; and the scale."""
    x = x.astype(jnp.float32)
    amax = lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32), scale


def _e4m3(x):
    """x rounded to scaled e4m3 (exact in bfloat16), and the scale; the
    gradient passes the rounding unchanged."""
    y = x.astype(jnp.float32)
    rounded, scale = _scaled(y, jnp.float8_e4m3fn)
    y = y / scale
    return (y + lax.stop_gradient(rounded - y)).astype(jnp.bfloat16), scale


@jax.custom_vjp
def _e5m2_gradient(y):
    """The identity, whose gradient is rounded to scaled e5m2."""
    return y


def _e5m2_fwd(y):
    return y, None


def _e5m2_bwd(_, g):
    rounded, scale = _scaled(g, jnp.float8_e5m2)
    return ((rounded * scale).astype(g.dtype),)


_e5m2_gradient.defvjp(_e5m2_fwd, _e5m2_bwd)


def einsum(spec: str, a, b, prec: str):
    if prec == "f32":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    (qa, sa), (qb, sb) = _e4m3(a), _e4m3(b)
    out = jnp.einsum(spec, qa, qb, preferred_element_type=jnp.float32)
    return _e5m2_gradient(out) * (sa * sb)


def dims(config: Dict) -> Dict:
    """The sizes and constants of the equations; a multiplier is None
    where the file gives the plain value or none."""
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(f"the reference has no hidden_act {config['hidden_act']!r}")
    h = config["hidden_size"]
    nh = config["num_attention_heads"]
    hd = config.get("head_dim") or h // nh

    def multiplier(key, plain):
        value = config.get(key, plain)
        return None if value == plain else value

    return dict(
        hidden=h, heads=nh, kv_heads=config["num_key_value_heads"], head_dim=hd,
        layers=config["num_hidden_layers"], vocab=config["vocab_size"],
        eps=config["rms_norm_eps"], theta=config["rope_theta"],
        experts=config.get("num_local_experts", 0),
        top_k=config.get("num_experts_per_tok", 0),
        capacity_factor=config.get("capacity_factor"),
        embedding_multiplier=multiplier("embedding_multiplier", 1.0),
        attention_multiplier=multiplier("attention_multiplier", hd ** -0.5),
        residual_multiplier=multiplier("residual_multiplier", 1.0),
        logits_scaling=multiplier("logits_scaling", 1.0),
        tied=config.get("tie_word_embeddings", False))


def scaled(x, multiplier):
    """``x * multiplier``; ``x`` itself where the multiplier is plain."""
    return x if multiplier is None else x * multiplier


def head_weights(leaf, dm):
    """The head ``[H, V]`` from ``leaf(path)``: the embedding transposed
    where the model ties them."""
    return leaf("['embed']").T if dm["tied"] else leaf("['lm_head']")


def logits_of(x, head, dm, prec):
    logits = einsum("...h,hv->...v", x, head, prec)
    return logits if dm["logits_scaling"] is None else logits / dm["logits_scaling"]


def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x: [B, S, n, d]; positions: [S]. Rotates (x[:d/2], x[d/2:]) pairs."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, prec, multiplier=None):
    """Causal grouped-query attention. q: [B,S,nh,d]; k, v: [B,S,nkv,d].
    Scores are scaled by ``multiplier``, or by 1/sqrt(d) where it is None."""
    B, S, nh, d = q.shape
    g = nh // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)

    @jax.checkpoint
    def block(q0, qb):
        s = einsum("bqhd,bkhd->bhqk", qb, k, prec)
        s = s / np.sqrt(d) if multiplier is None else s * multiplier
        qpos = q0 + jnp.arange(qb.shape[1])
        s = jnp.where(qpos[:, None] >= jnp.arange(S)[None, :], s, -jnp.inf)
        return einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, prec)

    n = max(1, S // Q_BLOCK)
    size = S // n
    return jnp.concatenate(
        [block(i * size, q[:, i * size:(i + 1) * size]) for i in range(n)], axis=1)


def mlp(x, p, prec):
    gate = einsum("...h,hf->...f", x, p["wg"], prec)
    up = einsum("...h,hf->...f", x, p["wi"], prec)
    return einsum("...f,fh->...h", jax.nn.silu(gate) * up, p["wo"], prec)


def moe(x, p, dm, prec):
    """Top-k mixture of gated-SiLU experts, with a capacity per expert
    where the configuration gives a ``capacity_factor``.

    x: [T, H]. Each expert is applied to every token and weighted by that
    token's kept gate for it (zero where it was not chosen or was
    dropped). Without a capacity factor the capacity is T, which no
    expert can exceed: a token chooses an expert at most once."""
    T = x.shape[0]
    E, k = dm["experts"], dm["top_k"]
    probs = jax.nn.softmax(einsum("th,he->te", x, p["router"], prec), axis=-1)
    gates, chosen = lax.top_k(probs, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    cf = dm["capacity_factor"]
    capacity = T if cf is None else int(max(1, cf * k * T / E))
    hit = chosen.reshape(-1)[:, None] == jnp.arange(E)[None, :]        # [T*k, E]
    rank = jnp.cumsum(hit, axis=0) - 1                # earlier picks of the expert
    kept = jnp.sum(jnp.where(hit, rank, 0), axis=-1) < capacity        # [T*k]
    weight = gates.reshape(-1) * kept
    combine = (hit * weight[:, None]).reshape(T, k, E).sum(axis=1)      # [T, E]

    @jax.checkpoint
    def expert(acc, e):
        pe = {n: p[n][e] for n in ("wg", "wi", "wo")}
        return acc + combine[:, e, None] * mlp(x, pe, prec), None

    out, _ = lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    return out


def block(x, lp, dm, prec):
    B, S, H = x.shape
    nh, nkv, d = dm["heads"], dm["kv_heads"], dm["head_dim"]
    pos = jnp.arange(S)
    h = rmsnorm(x, lp["norm1"], dm["eps"])
    a = lp["attn"]
    q = rope(einsum("bsh,hf->bsf", h, a["wq"], prec).reshape(B, S, nh, d), pos, dm["theta"])
    k = rope(einsum("bsh,hf->bsf", h, a["wk"], prec).reshape(B, S, nkv, d), pos, dm["theta"])
    v = einsum("bsh,hf->bsf", h, a["wv"], prec).reshape(B, S, nkv, d)
    o = attention(q, k, v, prec, dm["attention_multiplier"]).reshape(B, S, nh * d)
    r = dm["residual_multiplier"]
    x = x + scaled(einsum("bsf,fh->bsh", o, a["wo"], prec), r)
    h = rmsnorm(x, lp["norm2"], dm["eps"])
    if dm["experts"]:
        return x + scaled(moe(h.reshape(B * S, H), lp["moe"], dm, prec).reshape(B, S, H), r)
    return x + scaled(mlp(h, lp["mlp"], prec), r)


def nest(flat: Dict) -> Dict:
    """Nested dict of one layer from its flat ``['layers'][...]`` leaves."""
    out: Dict = {}
    for path, x in flat.items():
        names = path[len("['layers']"):].strip("[]'").split("']['")
        node = out
        for n in names[:-1]:
            node = node.setdefault(n, {})
        node[names[-1]] = x
    return out


def layer_params(params: Dict, layer: int) -> Dict:
    """The nested dict of one layer from flat stacked leaves."""
    return nest({p: x[layer] for p, x in params.items()
                 if p.startswith("['layers']")})


def hidden(params: Dict, tokens, dm, prec):
    """Final-norm hidden states [B, S, H] of token ids [B, S]."""
    x = scaled(params["['embed']"][tokens], dm["embedding_multiplier"])
    for layer in range(dm["layers"]):
        x = jax.checkpoint(partial(block, dm=dm, prec=prec))(
            x, layer_params(params, layer))
    return rmsnorm(x, params["['final_norm']"], dm["eps"])


def loss(params: Dict, tokens, labels, weights, dm, prec):
    """Mean next-token cross entropy over the tokens with weight 1."""
    x = hidden(params, tokens, dm, prec)
    H = x.shape[-1]
    x, labels, weights = x.reshape(-1, H), labels.reshape(-1), weights.reshape(-1)
    n = max(1, x.shape[0] // LOSS_BLOCK)
    size = x.shape[0] // n

    head = head_weights(params.__getitem__, dm)

    @jax.checkpoint
    def nll(xb, lb):
        logits = logits_of(xb, head, dm, prec)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]

    total = sum(jnp.sum(nll(x[i * size:(i + 1) * size], labels[i * size:(i + 1) * size])
                        * weights[i * size:(i + 1) * size]) for i in range(n))
    return total / jnp.sum(weights)


# ---------------------------------------------------------------------------
# AdamW, three steps, moments kept on the host between steps
# ---------------------------------------------------------------------------

def lr_at(opt: Dict, step: int) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_ratio`` of the peak."""
    warm, peak = opt["warmup_steps"], opt["peak_lr"]
    if step < warm:
        return peak * step / max(1, warm)
    prog = min(max((step - warm) / max(1, opt["decay_steps"] - warm), 0.0), 1.0)
    r = opt["min_lr_ratio"]
    return peak * (r + (1 - r) * 0.5 * (1 + np.cos(np.pi * prog)))


@partial(jax.jit, static_argnames=("decay",))
def _adam_leaf(p, g, m, v, lr, scale, c1, c2, b1, b2, eps, wd, decay):
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    d = (m / c1) / (jnp.sqrt(v / c2) + eps)
    if decay:
        d = d + wd * p
    return p - lr * d, m, v


def train(config: Dict, opt: Dict, params: Dict, batches, prec: str = "f32",
          row_weights: Optional[np.ndarray] = None) -> Dict:
    """Runs ``len(batches)`` AdamW steps from ``params`` (flat, float32,
    on the device; consumed). Returns the loss of each step, the first
    (clipped) gradient as host arrays, and the final params."""
    dm = dims(config)
    vg = jax.jit(jax.value_and_grad(partial(loss, dm=dm, prec=prec)))
    moments: Dict[str, tuple] = {}
    losses, first_grad = [], None
    for t, batch in enumerate(batches, start=1):
        tokens, labels = batch["tokens"][0], batch["labels"][0]
        w = np.ones(tokens.shape, np.float32) if row_weights is None else row_weights
        value, grads = vg(params, tokens, labels, w)
        losses.append(float(value))
        gnorm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values())))
        scale = min(1.0, opt["grad_clip"] / (gnorm + 1e-9)) if opt["grad_clip"] > 0 else 1.0
        if first_grad is None:
            first_grad = {p: np.asarray(g * scale) for p, g in grads.items()}
        lr = lr_at(opt, t)
        c1, c2 = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t
        last = t == len(batches)
        for path in list(params):
            p = params.pop(path)
            m, v = moments.get(path, (None, None))
            m = jnp.zeros_like(p) if m is None else jnp.asarray(m)
            v = jnp.zeros_like(p) if v is None else jnp.asarray(v)
            p, m, v = _adam_leaf(p, grads.pop(path), m, v, lr, scale, c1, c2,
                                 opt["b1"], opt["b2"], opt["eps"],
                                 opt["weight_decay"], decay=p.ndim >= 2)
            params[path] = p
            moments[path] = None if last else (np.asarray(m), np.asarray(v))
            del m, v
    return {"losses": losses, "first_grad": first_grad, "params": params}


# ---------------------------------------------------------------------------
# Serving: the forward pass layer by layer
# ---------------------------------------------------------------------------

def position_logits(config: Dict, make_layer, make_leaf, tokens: np.ndarray,
                    prec: str = "f32", rows: int = 4):
    """Yields, sequence by sequence, the logits ``[S, V]`` at every
    position of token ids ``[n, S]``. ``make_layer(layer)`` gives one
    layer's weights as a flat dict of float32 ``['layers'][...]`` leaves
    and ``make_leaf(path)`` one other leaf; one layer's weights are on the
    device at a time, applied to ``rows`` sequences at a time."""
    dm = dims(config)
    embed = make_leaf("['embed']")
    xs = [scaled(embed[jnp.asarray(tokens[i:i + rows])], dm["embedding_multiplier"])
          for i in range(0, len(tokens), rows)]
    del embed
    step = jax.jit(partial(block, dm=dm, prec=prec))
    for layer in range(dm["layers"]):
        w = nest(make_layer(layer))
        xs = [step(x, w) for x in xs]
    norm, head = make_leaf("['final_norm']"), head_weights(make_leaf, dm)
    for x in xs:
        for row in rmsnorm(x, norm, dm["eps"]):
            yield logits_of(row, head, dm, prec)
