"""Compile the Pallas kernels and the serve step for a described TPU v5e
(no chip needed).

The TPU compiler is installed wherever libtpu is, and it compiles for a
topology that is described rather than attached. That catches what the
interpret-mode tests cannot: block shapes that break the (8, 128) tiling
rule, ops Mosaic cannot lower, VMEM overuse, and the TPU's own layout
choices, which can add whole-array copies. Shapes are the real widths of
the configs each kernel serves.
"""

import dataclasses
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.models.layers import attention
from repro.models.lm import RunCfg, init_cache, init_params
from repro.serving.serve import make_serve_step
from repro.train.step import TrainCfg, init_train_state, make_train_step

from chipbench import chipbench_paths  # noqa: F401  (puts the chip benchmark on sys.path)
import scopes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> an abstract argument on one v5e chip.
    The persistent compile cache is off meanwhile: a compile for a
    described chip is written but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("nh,nkv,hd,window", [
    (32, 4, 128, 0),        # yi-6b: GQA, full causal
    (25, 5, 64, 1024),      # hymba-1.5b: head dim 64, sliding window
], ids=["yi-6b", "hymba-1.5b"])
def test_flash_attention_compiles_for_v5e(shape, nh, nkv, hd, window):
    S = 2048
    _compile(partial(flash_attention, causal=True, window=window),
             shape((1, nh, S, hd)), shape((1, nkv, S, hd)),
             shape((1, nkv, S, hd)))


def _kernel_calls(compiled) -> int:
    """The step's ``tpu_custom_call``s, after checking that the chip
    benchmark's scope reader files each under ``attention``."""
    text = compiled.as_text()
    calls = [line.split(" = ", 1)[0].split()[-1].lstrip("%") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    found = scopes.op_scopes(text)
    assert all(found[name] == "attention" for name in calls), calls
    return len(calls)


@pytest.mark.parametrize("nh,nkv,hd,window,calls", [
    (32, 4, 128, 0, 3),     # yi-6b: the splash forward, dq and dkv kernels
    (25, 5, 64, 1024, 0),   # hymba-1.5b: window and head dim 64 keep the jnp path
], ids=["yi-6b", "hymba-1.5b"])
def test_attention_grad_runs_the_splash_kernels_on_v5e(shape, nh, nkv, hd, window, calls):
    """The kernel's engagement, fixed by shape: ``jax.grad`` of
    ``layers.attention`` at 4096 tokens."""
    S = 4096

    def loss(q, k, v):
        out = attention(q, k, v, causal=True, window=window, q_chunk=1024)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape((1, S, nh, hd)), shape((1, S, nkv, hd)), shape((1, S, nkv, hd))).compile()
    assert _kernel_calls(compiled) == calls


def test_yi_train_stage_step_runs_the_splash_kernels_on_v5e(shape):
    """The step ``yi-6b.train.s4k`` runs: one yi-6b layer with embedding
    and head, float32 weights and Adam, 1 x 4096 tokens."""
    arch = dataclasses.replace(get_config("yi-6b"), num_layers=1)
    cfg = TrainCfg()
    abstract = lambda tree: jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)
    params, opt_state = abstract(jax.eval_shape(
        lambda: init_train_state(arch, cfg, jax.random.PRNGKey(0))))
    batch = {name: shape((1, 1, 4096), jnp.int32) for name in ("tokens", "labels")}
    compiled = make_train_step(arch, cfg).lower(params, opt_state, batch).compile()
    assert _kernel_calls(compiled) == 3


@pytest.mark.parametrize("H", [4096, 1600], ids=["yi-6b", "hymba-1.5b"])
def test_rmsnorm_compiles_for_v5e(shape, H):
    _compile(rmsnorm_pallas, shape((4096, H)), shape((H,)))


def test_ssd_scan_compiles_for_v5e(shape):
    # mamba2-2.7b: d_inner 5120 / headdim 64 = 80 heads, state 128
    B, nh, S, hp, N = 1, 80, 2048, 64, 128
    _compile(partial(ssd_scan_pallas, chunk=256),
             shape((B, nh, S, hp)), shape((B, nh, S), jnp.float32),
             shape((nh,), jnp.float32), shape((B, S, N)), shape((B, S, N)))


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
def test_serve_step_updates_the_kv_cache_in_place_on_v5e(shape, scan):
    """yi-6b at full width, 2 layers, batch 16 over a 1280-position
    cache: the compiled serve step aliases the donated K/V cache to its
    output, makes no copy of it, and needs less scratch than one layer's
    K span."""
    arch = dataclasses.replace(get_config("yi-6b"), num_layers=2)
    cfg = RunCfg(param_dtype=jnp.bfloat16, scan_layers=scan)
    B, span = 16, 1280
    abstract = lambda tree: jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)
    params = abstract(jax.eval_shape(lambda: init_params(arch, jax.random.PRNGKey(0), cfg)))
    cache = abstract(jax.eval_shape(lambda: init_cache(arch, B, span, cfg)))
    compiled = make_serve_step(arch, cfg).lower(
        params, cache, shape((B,), jnp.int32), shape((), jnp.int32)).compile()
    hlo = compiled.as_text()
    n = len(jax.tree.leaves(params))
    assert f"{{2}}: ({n}, {{}}, may-alias), {{3}}: ({n + 1}, {{}}, may-alias)" in hlo
    full = re.escape(f"bf16[2,{B},{span},{arch.n_kv},{arch.head_dim}]")
    made = re.findall(rf"%([\w.-]+) = {full}\{{[^}}]*\}} ([\w-]+)\(", hlo)
    assert made and not [m for m in made if m[1] == "copy" or m[0].startswith("copy")]
    layer_k_bytes = B * span * arch.n_kv * arch.head_dim * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_k_bytes
