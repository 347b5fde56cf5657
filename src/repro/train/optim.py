"""Optimizers (Adam / SGD, PALM Table II row 'Optimizer') with ZeRO-style
sharded state and configurable moment dtype.

The memory policy lever for nemotron-4-340b (DESIGN.md §6): moments can
be stored in bf16 (``moment_dtype``) while the update math runs in fp32
— params fp32 5.3 GB + m,v bf16 2x2.7 GB per chip at 256-way sharding.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["OptimizerCfg", "init_opt_state", "apply_optimizer", "lr_at"]


@dataclass(frozen=True)
class OptimizerCfg:
    name: str = "adam"                # "adam" | "sgd" (paper Table II)
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    decay_steps: int = 10_000
    min_lr_ratio: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    moment_dtype: Any = jnp.float32   # bf16 = the 340B memory policy


def lr_at(cfg: OptimizerCfg, step: jax.Array) -> jax.Array:
    """Linear warmup + cosine decay to min_lr_ratio."""
    step = step.astype(jnp.float32)
    warm = cfg.peak_lr * step / max(1, cfg.warmup_steps)
    prog = jnp.clip((step - cfg.warmup_steps) /
                    max(1, cfg.decay_steps - cfg.warmup_steps), 0.0, 1.0)
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < cfg.warmup_steps, warm, cfg.peak_lr * cos)


def init_opt_state(cfg: OptimizerCfg, params) -> Dict:
    if cfg.name == "sgd":
        return {"m": jax.tree.map(lambda p: jnp.zeros((), p.dtype), params),  # stubs
                "v": jax.tree.map(lambda p: jnp.zeros((), p.dtype), params),
                "step": jnp.zeros((), jnp.int32)}
    zeros = lambda p: jnp.zeros(p.shape, cfg.moment_dtype)
    return {"m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


def apply_optimizer(
    cfg: OptimizerCfg,
    params,
    grads,
    state: Dict,
) -> Tuple[Any, Dict, Dict]:
    """Returns (new_params, new_state, metrics). Math in fp32, storage at
    param/moment dtypes."""
    with jax.named_scope("optimizer"):
        step = state["step"] + 1
        lr = lr_at(cfg, step)

        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9)) if cfg.grad_clip > 0 else 1.0

        if cfg.name == "sgd":
            def upd(p, g):
                g32 = g.astype(jnp.float32) * scale
                return (p.astype(jnp.float32) - lr * g32).astype(p.dtype)
            new_params = jax.tree.map(upd, params, grads)
            new_state = {**state, "step": step}
            return new_params, new_state, {"lr": lr, "grad_norm": gnorm}

        b1, b2 = cfg.b1, cfg.b2
        c1 = 1.0 - b1 ** step.astype(jnp.float32)
        c2 = 1.0 - b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32) * scale
            m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
            v32 = b2 * v.astype(jnp.float32) + (1 - b2) * g32 * g32
            mhat = m32 / c1
            vhat = v32 / c2
            p32 = p.astype(jnp.float32)
            step_dir = mhat / (jnp.sqrt(vhat) + cfg.eps)
            if cfg.weight_decay > 0 and p.ndim >= 2:   # decay matrices only
                step_dir = step_dir + cfg.weight_decay * p32
            return ((p32 - lr * step_dir).astype(p.dtype),
                    m32.astype(cfg.moment_dtype), v32.astype(cfg.moment_dtype))

        out = jax.tree.map(upd, params, grads, state["m"], state["v"])
        new_params = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
        new_v = jax.tree.map(lambda t: t[2], out, is_leaf=lambda x: isinstance(x, tuple))
        new_state = {"m": new_m, "v": new_v, "step": step}
        return new_params, new_state, {"lr": lr, "grad_norm": gnorm}
