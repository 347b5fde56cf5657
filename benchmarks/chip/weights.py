"""Seeded weights for a cell, made on the device by the benchmark.

The program under test and the plain reference both take their weights
from here, never from each other: every leaf is a function of the seed
and the leaf's path alone, so the reference can make one leaf, or one
layer of a leaf, on its own and get the same numbers the program got.

The pytree layout (names and shapes) is the program's parameter
interface; ``param_shapes`` reads it from the program's own
``init_params`` with ``jax.eval_shape``, so no values are taken.
"""

from __future__ import annotations

import zlib
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Shapes = Dict[str, Tuple[Tuple[int, ...], object]]   # path -> (shape, dtype)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size up to 64 bits."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _param_tree(arch, dtype):
    from repro.models.lm import RunCfg, init_params
    return jax.eval_shape(lambda k: init_params(arch, k, RunCfg(param_dtype=dtype)),
                          jax.random.PRNGKey(0))


def param_shapes(arch, dtype) -> Shapes:
    """Paths, shapes and dtypes of the program's parameter tree."""
    flat = jax.tree_util.tree_flatten_with_path(_param_tree(arch, dtype))[0]
    return {jax.tree_util.keystr(p): (tuple(x.shape), x.dtype) for p, x in flat}


def _scale(path: str, shape, num_layers: int) -> float:
    """Standard deviation of a leaf, by its name: unit scales for norms,
    0.02 for embedding, head and router, 1/sqrt(fan-in) for the other
    matrices, with output projections further cut by 1/sqrt(2 L)."""
    if "norm" in path:
        return 0.0
    if any(n in path for n in ("'embed'", "'lm_head'", "'router'")):
        return 0.02
    scale = shape[-2] ** -0.5
    if path.endswith("['wo']"):
        scale /= (2 * num_layers) ** 0.5
    return scale


def is_stacked(path: str) -> bool:
    return path.startswith("['layers']")


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw(key, path, shape, dtype, num_layers):
    scale = _scale(path, shape, num_layers)
    if scale == 0.0:
        return jnp.ones(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def make_layer(key, path: str, shape, dtype, layer: int, num_layers: int):
    """Layer ``layer`` of a layer-stacked leaf (``shape`` is the stacked
    shape)."""
    k = jax.random.fold_in(_leaf_key(key, path), layer)
    return _draw(k, path, shape[1:], dtype, num_layers)


def make_leaf(key, path: str, shape, dtype, num_layers: int):
    if is_stacked(path):
        # one layer at a time, so temporaries stay the size of one layer
        return lax.map(lambda l: make_layer(key, path, shape, dtype, l, num_layers),
                       jnp.arange(shape[0]))
    return _draw(_leaf_key(key, path), path, shape, dtype, num_layers)


def _unflatten(arch, dtype, leaves: Dict[str, jax.Array]):
    flat, treedef = jax.tree_util.tree_flatten_with_path(_param_tree(arch, dtype))
    return jax.tree_util.tree_unflatten(
        treedef, [leaves[jax.tree_util.keystr(p)] for p, _ in flat])


def generate(arch, key, dtype):
    """The whole parameter tree in ``dtype``, made in one jitted call."""
    shapes = param_shapes(arch, dtype)

    @jax.jit
    def make(k):
        return {path: make_leaf(k, path, shape, dt, arch.num_layers)
                for path, (shape, dt) in shapes.items()}

    return _unflatten(arch, dtype, make(key))


def flatten(tree) -> Dict[str, jax.Array]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): x for p, x in flat}


@partial(jax.jit, static_argnames=("path", "num_layers"))
def _change_norm(leaf, key, path: str, num_layers: int):
    start = make_leaf(key, path, leaf.shape, leaf.dtype, num_layers)
    d = leaf.astype(jnp.float32) - start.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(d * d))


def change_norms(flat: Dict[str, jax.Array], key, num_layers: int) -> Dict[str, float]:
    """Per leaf, the norm of (leaf - its seeded start), made leaf by leaf
    so that only one leaf's start is on the device at a time."""
    out = {path: _change_norm(x, key, path=path, num_layers=num_layers)
           for path, x in flat.items()}
    return {p: float(v) for p, v in out.items()}
