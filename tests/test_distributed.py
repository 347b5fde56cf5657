"""Distributed-runtime tests on an 8-device host mesh.

These run in a subprocess so the 8-device XLA_FLAGS override never leaks
into other tests (the suite must see 1 device)."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.train import scale_arch
    from repro.models import RunCfg, init_params
    from repro.train.optim import init_opt_state
    from repro.train.step import TrainCfg, make_train_step
    from repro.train.fault_tolerance import elastic_reshard
    from repro.parallel.compression import compressed_psum
    from repro.parallel.pipeline import pipeline_apply

    out = {}
    mesh = make_mesh((2, 4), ("data", "model"))

    # 1) sharded train step matches single-device numerics
    arch = scale_arch(get_config("yi-6b"), "tiny")
    cfg = TrainCfg(run=RunCfg(q_chunk=0, remat=False), num_microbatches=2)
    key = jax.random.PRNGKey(0)
    params = init_params(arch, key, cfg.run)
    opt = init_opt_state(cfg.opt, params)
    batch = {
        "tokens": jax.random.randint(key, (2, 4, 32), 0, arch.vocab),
        "labels": jax.random.randint(key, (2, 4, 32), 0, arch.vocab),
    }
    step_single = make_train_step(arch, cfg, mesh=None)
    p1, o1, m1 = step_single(params, opt, batch)
    step_sharded = make_train_step(arch, cfg, mesh)
    jitted = step_sharded.jit_with(
        jax.eval_shape(lambda: init_params(arch, key, cfg.run)), batch)
    params2 = init_params(arch, key, cfg.run)
    opt2 = init_opt_state(cfg.opt, params2)
    p2, o2, m2 = jitted(params2, opt2, batch)
    out["loss_single"] = float(m1["loss"])
    out["loss_sharded"] = float(m2["loss"])
    diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
               for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    out["param_max_diff"] = diff

    # 2) elastic reshard onto a smaller mesh
    small = make_mesh((2, 2), ("data", "model"))
    state = elastic_reshard({"params": p2, "opt_state": o2}, arch, small)
    d2 = max(float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))
             for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(state["params"])))
    out["reshard_diff"] = d2

    # 3) compressed psum ~= exact psum
    pod_mesh = make_mesh((8,), ("pod",))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 512))
    exact = shard_map(lambda v: jax.lax.psum(v, "pod"), mesh=pod_mesh,
                      in_specs=P("pod"), out_specs=P("pod"))(x)
    comp = shard_map(lambda v: compressed_psum(v, "pod"), mesh=pod_mesh,
                     in_specs=P("pod"), out_specs=P("pod"))(x)
    rel = float(jnp.linalg.norm(comp - exact) / jnp.linalg.norm(exact))
    out["psum_rel_err"] = rel

    # 4) shard_map GPipe pipeline == sequential stage application
    S, G, B, H = 4, 6, 2, 16
    stage_mesh = make_mesh((4,), ("pod",))
    ks = jax.random.split(jax.random.PRNGKey(2), S)
    stage_w = jnp.stack([jax.random.normal(k, (H, H)) / jnp.sqrt(H) for k in ks])
    mbs = jax.random.normal(jax.random.PRNGKey(3), (G, B, H))
    stage_fn = lambda w, x: jnp.tanh(x @ w)
    piped = pipeline_apply(stage_fn, stage_w, mbs, stage_mesh, axis="pod")
    ref = mbs
    for s in range(S):
        ref = jnp.tanh(ref @ stage_w[s])
    out["pipe_diff"] = float(jnp.max(jnp.abs(piped - ref)))

    # 5) pipeline is differentiable (grads flow through ppermute)
    def loss(w):
        y = pipeline_apply(stage_fn, w, mbs, stage_mesh, axis="pod")
        return jnp.sum(y ** 2)
    g = jax.grad(loss)(stage_w)
    out["pipe_grad_norm"] = float(jnp.linalg.norm(g))

    # 6) shard_map expert-parallel MoE == single-device MoE (drop-free)
    from repro.models.layers import moe, moe_ep
    T, Hm, E, F, kk = 256, 32, 10, 16, 4
    kmoe = jax.random.split(jax.random.PRNGKey(4), 5)
    mparams = {"router": jax.random.normal(kmoe[0], (Hm, E)) * 0.1,
               "wg": jax.random.normal(kmoe[1], (E, Hm, F)) * 0.1,
               "wi": jax.random.normal(kmoe[2], (E, Hm, F)) * 0.1,
               "wo": jax.random.normal(kmoe[3], (E, F, Hm)) * 0.1}
    xm = jax.random.normal(kmoe[4], (T, Hm))
    ref, _ = moe(xm, mparams, top_k=kk, capacity_factor=16.0)
    got, _ = jax.jit(lambda x, p: moe_ep(x, p, kk, mesh,
                                         capacity_factor=16.0))(xm, mparams)
    out["moe_ep_diff"] = float(jnp.max(jnp.abs(got - ref)))

    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def results():
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True,
                          env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                               "JAX_PLATFORMS": "cpu"},
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_step_matches_single_device(results):
    assert results["loss_single"] == pytest.approx(results["loss_sharded"], rel=1e-3)
    assert results["param_max_diff"] < 5e-2     # bf16 compute tolerance


def test_elastic_reshard_preserves_values(results):
    assert results["reshard_diff"] == 0.0


def test_compressed_psum_close_to_exact(results):
    assert results["psum_rel_err"] < 0.01


def test_pipeline_matches_sequential(results):
    assert results["pipe_diff"] < 1e-5


def test_pipeline_differentiable(results):
    assert results["pipe_grad_norm"] > 0


def test_moe_ep_matches_reference(results):
    assert results["moe_ep_diff"] < 1e-4
