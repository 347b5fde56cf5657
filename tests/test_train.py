"""Training substrate: optimizer math, data determinism, checkpointing,
end-to-end loss decrease."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.train import scale_arch, train_loop
from repro.models import RunCfg, init_params
from repro.train.checkpoint import (
    CheckpointManager,
    latest_step,
    restore_checkpoint,
    restore_latest,
    save_checkpoint,
)
from repro.train.data import DataCfg, PrefetchIterator, SyntheticDataset
from repro.train.optim import OptimizerCfg, apply_optimizer, init_opt_state, lr_at
from repro.train.step import TrainCfg, init_train_state, make_train_step
from proptools import given


# ----------------------------------------------------------------- optimizer

def test_adam_matches_reference_implementation():
    cfg = OptimizerCfg(peak_lr=1e-2, warmup_steps=0, decay_steps=100,
                       weight_decay=0.0, grad_clip=0.0, min_lr_ratio=1.0)
    params = {"w": jnp.array([[1.0, 2.0]])}
    grads = {"w": jnp.array([[0.1, -0.2]])}
    state = init_opt_state(cfg, params)
    new_params, new_state, _ = apply_optimizer(cfg, params, grads, state)
    # hand-computed Adam step 1: m=0.1g, v=0.05g^2, mhat=g, vhat=g^2
    g = np.array([[0.1, -0.2]])
    expected = np.array([[1.0, 2.0]]) - 1e-2 * g / (np.abs(g) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_params["w"]), expected, rtol=1e-5)


def test_grad_clip_scales_update():
    cfg = OptimizerCfg(peak_lr=1e-2, warmup_steps=0, grad_clip=0.1,
                       weight_decay=0.0, min_lr_ratio=1.0, name="sgd")
    params = {"w": jnp.ones((4,))}
    grads = {"w": jnp.full((4,), 10.0)}   # norm 20 >> clip 0.1
    state = init_opt_state(cfg, params)
    new_params, _, m = apply_optimizer(cfg, params, grads, state)
    delta = np.asarray(params["w"] - new_params["w"])
    assert np.linalg.norm(delta / 1e-2) == pytest.approx(0.1, rel=1e-4)


def test_lr_schedule_warmup_and_decay():
    cfg = OptimizerCfg(peak_lr=1.0, warmup_steps=10, decay_steps=110,
                       min_lr_ratio=0.1)
    assert float(lr_at(cfg, jnp.int32(5))) == pytest.approx(0.5)
    assert float(lr_at(cfg, jnp.int32(10))) == pytest.approx(1.0, rel=1e-3)
    assert float(lr_at(cfg, jnp.int32(110))) == pytest.approx(0.1, rel=1e-3)


def test_bf16_moments_policy():
    cfg = OptimizerCfg(moment_dtype=jnp.bfloat16)
    params = {"w": jnp.ones((8, 8))}
    state = init_opt_state(cfg, params)
    assert state["m"]["w"].dtype == jnp.bfloat16


# ----------------------------------------------------------------- data

def test_data_deterministic_and_resumable():
    arch = scale_arch(get_config("yi-6b"), "tiny")
    cfg = DataCfg(seq_len=16, global_batch=4, num_microbatches=2, seed=3)
    ds = SyntheticDataset(arch, cfg)
    b1 = ds.batch_at(7)
    b2 = ds.batch_at(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    it = PrefetchIterator(ds, start_step=7)
    b3 = next(it)
    it.close()
    np.testing.assert_array_equal(b1["tokens"], b3["tokens"])


def test_data_labels_are_shifted_tokens():
    arch = scale_arch(get_config("yi-6b"), "tiny")
    ds = SyntheticDataset(arch, DataCfg(seq_len=16, global_batch=2))
    b = ds.batch_at(0)
    np.testing.assert_array_equal(b["labels"][..., :-1], b["tokens"][..., 1:])


# ----------------------------------------------------------------- ckpt

def test_checkpoint_roundtrip(tmp_path):
    arch = scale_arch(get_config("yi-6b"), "tiny")
    cfg = TrainCfg(run=RunCfg(q_chunk=0, remat=False))
    params, opt_state = init_train_state(arch, cfg, jax.random.PRNGKey(0))
    save_checkpoint(tmp_path, 5, {"params": params, "opt_state": opt_state},
                    extra={"data_step": 5})
    assert latest_step(tmp_path) == 5
    state, extra = restore_checkpoint(tmp_path, 5,
                                      {"params": params, "opt_state": opt_state})
    assert extra["data_step"] == 5
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_prune_and_latest(tmp_path):
    tree = {"x": jnp.arange(4)}
    for step in (1, 2, 3, 4, 5):
        save_checkpoint(tmp_path, step, {"t": tree}, keep_last=2)
    assert latest_step(tmp_path) == 5
    got, state, _ = restore_latest(tmp_path, {"t": tree})
    assert got == 5


def test_checkpoint_manager_async(tmp_path):
    mgr = CheckpointManager(tmp_path, every_steps=2, keep_last=2)
    tree = {"x": jnp.arange(3)}
    assert not mgr.maybe_save(1, {"t": tree})
    assert mgr.maybe_save(2, {"t": tree})
    mgr.wait()
    assert latest_step(tmp_path) == 2


# ----------------------------------------------------------------- end2end

def test_train_loop_loss_decreases(tmp_path):
    arch = scale_arch(get_config("yi-6b"), "tiny")
    cfg = TrainCfg(run=RunCfg(q_chunk=0, remat=False),
                   opt=OptimizerCfg(peak_lr=1e-3, warmup_steps=5, decay_steps=40),
                   num_microbatches=2)
    data_cfg = DataCfg(seq_len=64, global_batch=8, num_microbatches=2)
    _, _, losses = train_loop(arch, cfg, data_cfg, steps=40, log_every=100,
                              log_fn=lambda *_: None)
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1


def test_train_restart_resumes_deterministically(tmp_path):
    arch = scale_arch(get_config("yi-6b"), "tiny")
    cfg = TrainCfg(run=RunCfg(q_chunk=0, remat=False),
                   opt=OptimizerCfg(peak_lr=1e-3, warmup_steps=2, decay_steps=20),
                   num_microbatches=1)
    data_cfg = DataCfg(seq_len=32, global_batch=4, num_microbatches=1)
    # continuous run
    _, _, losses_full = train_loop(arch, cfg, data_cfg, steps=12,
                                   log_every=100, log_fn=lambda *_: None)
    # interrupted run: 6 steps, checkpoint, then resume to 12
    ck = tmp_path / "ck"
    train_loop(arch, cfg, data_cfg, steps=6, ckpt_dir=ck, ckpt_every=3,
               log_every=100, log_fn=lambda *_: None)
    _, _, losses_resumed = train_loop(arch, cfg, data_cfg, steps=12,
                                      ckpt_dir=ck, ckpt_every=3,
                                      log_every=100, log_fn=lambda *_: None)
    # the resumed tail must match the continuous run's tail
    np.testing.assert_allclose(losses_resumed[-3:], losses_full[-3:],
                               rtol=2e-4, atol=2e-4)


def test_train_loop_writes_the_benchmark_spans(tmp_path):
    """Under the profiler, each step of ``train_loop`` is a ``train``
    step annotation holding the chip benchmark's three host spans."""
    from collections import Counter
    from jax.profiler import ProfileData
    arch = scale_arch(get_config("yi-6b"), "tiny")
    cfg = TrainCfg(run=RunCfg(q_chunk=0, remat=False))
    data_cfg = DataCfg(seq_len=16, global_batch=2, num_microbatches=1)
    with jax.profiler.trace(str(tmp_path)):
        train_loop(arch, cfg, data_cfg, steps=3, log_fn=lambda *_: None)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    profile = ProfileData.from_file(str(path))
    names = ("train", "data_wait", "dispatch", "loss_fetch")
    counts = Counter(e.name for plane in profile.planes if plane.name.startswith("/host:")
                     for line in plane.lines for e in line.events if e.name in names)
    assert counts == {name: 3 for name in names}
    steps = sorted(dict(e.stats)["step_num"] for plane in profile.planes
                   for line in plane.lines for e in line.events if e.name == "train")
    assert steps == [0, 1, 2]
