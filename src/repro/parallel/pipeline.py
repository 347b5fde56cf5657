"""Executable pipeline parallelism: GPipe schedule via shard_map + ppermute.

PALM *models* PP (core.scheduler); this module *runs* it on a mesh axis —
on the production mesh the natural choice is ``pp_axis="pod"`` (stages =
pods, Act/Grad Pass = inter-pod collective-permute), exactly the
traffic pattern the paper's Act/Grad Pass events describe.

Mechanics: S stages on the axis, G microbatches, T = G + S - 1 ticks.
Each tick every stage applies its layer block to the activation it holds,
then the ring ``ppermute`` shifts activations one stage forward. Autodiff
through the tick scan yields the interleaved backward schedule for free
(the MaxText pattern), so ``jax.grad`` of a pipelined loss just works.

The schedule's bubble fraction is (S-1)/(G+S-1) — asserted against
PALM's Eq. (1) in tests for the same (S, G).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["pipeline_apply", "make_pipeline_loss"]


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,           # pytree, leading dim S (sharded over axis)
    microbatches: jax.Array,     # [G, B, ...] (replicated; consumed by stage 0)
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run the GPipe pipeline; returns outputs [G, B, ...] (replicated)."""
    S = mesh.shape[axis]
    G = microbatches.shape[0]
    T = G + S - 1

    other_axes = [a for a in mesh.axis_names if a != axis]

    param_specs = jax.tree.map(lambda _: P(axis), stage_params)
    in_specs = (param_specs, P())
    out_specs = P()

    def body(params_local, mbs):
        s = lax.axis_index(axis)
        zero = jnp.zeros_like(mbs[0])
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(buf, t):
            mb_idx = jnp.clip(t, 0, G - 1)
            inp = jnp.where(s == 0,
                            lax.dynamic_index_in_dim(mbs, mb_idx, keepdims=False),
                            buf)
            local = jax.tree.map(lambda p: p[0], params_local)
            out = stage_fn(local, inp)
            nxt = lax.ppermute(out, axis, perm)
            # only the last stage's output is the pipeline output
            y = jnp.where(s == S - 1, out, jnp.zeros_like(out))
            y = lax.psum(y, axis)          # broadcast to all stages
            return nxt, y

        _, ys = lax.scan(tick, zero, jnp.arange(T))
        # microbatch g exits the last stage at tick g + S - 1
        return ys[S - 1:]

    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)
    return fn(stage_params, microbatches)


def make_pipeline_loss(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    loss_head: Callable[[jax.Array, jax.Array], jax.Array],
    mesh: Mesh,
    axis: str = "pod",
):
    """Pipelined loss: mean over microbatches of loss_head(pipeline(x), y).
    Differentiable end-to-end (grads flow through the ppermute ring)."""

    def loss_fn(stage_params, microbatches, labels):
        outs = pipeline_apply(stage_fn, stage_params, microbatches, mesh, axis)
        losses = jax.vmap(loss_head)(outs, labels)
        return losses.mean()

    return loss_fn
