"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --scale tiny \
        --steps 200 --global-batch 32 --seq-len 256

``--scale tiny|small`` shrinks the selected architecture to a CPU-trainable
variant (same family/block structure); ``--scale full`` uses the exact
assigned config (for real pods). The loop wires together every substrate:
synthetic data pipeline with prefetch, checkpoint/restart, straggler
monitoring, and metrics logging.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..configs.base import ArchConfig
from ..models.lm import RunCfg
from ..train.checkpoint import CheckpointManager, restore_latest
from ..train.data import DataCfg, PrefetchIterator, SyntheticDataset
from ..train.fault_tolerance import StragglerMonitor
from ..train.optim import OptimizerCfg
from ..train.step import TrainCfg, init_train_state, make_train_step
from .compile_cache import enable_compile_cache

__all__ = ["scale_arch", "train_loop", "main"]


def scale_arch(arch: ArchConfig, scale: str) -> ArchConfig:
    """Family-preserving reductions for CPU-scale runs."""
    if scale == "full":
        return arch
    dims = {"tiny": (2, 128, 4, 256), "small": (4, 256, 8, 1024)}[scale]
    L, H, nh, V = dims
    nkv = max(1, min(arch.n_kv, nh // 2)) if arch.n_kv else 0
    return dataclasses.replace(
        arch, num_layers=L, d_model=H, n_heads=nh if arch.n_heads else 0,
        n_kv=nkv, head_dim=H // nh if arch.n_heads else 0,
        d_ff=2 * H if arch.d_ff else 0, vocab=min(arch.vocab, V),
        n_experts=min(arch.n_experts, 4) if arch.n_experts else 0,
        top_k=min(arch.top_k, 2) if arch.top_k else 0,
        d_ff_expert=H if arch.n_experts else 0,
        d_inner=2 * H if arch.block in ("ssm", "hymba") else 0,
        ssm_state=min(arch.ssm_state, 16) if arch.ssm_state else 0,
        ssm_headdim=32 if arch.block in ("ssm", "hymba") else 64,
        window=min(arch.window, 64) if arch.window else 0)


def train_loop(arch: ArchConfig, cfg: TrainCfg, data_cfg: DataCfg, steps: int,
               ckpt_dir=None, log_every: int = 10, ckpt_every: int = 50,
               seed: int = 0, log_fn=print):
    train_step = make_train_step(arch, cfg)
    params, opt_state = init_train_state(arch, cfg, jax.random.PRNGKey(seed))

    start_step = 0
    manager = None
    if ckpt_dir is not None:
        manager = CheckpointManager(ckpt_dir, every_steps=ckpt_every)
        like = {"params": params, "opt_state": opt_state}
        got, state, extra = restore_latest(ckpt_dir, like)
        if got is not None:
            params, opt_state = state["params"], state["opt_state"]
            start_step = extra.get("data_step", got)
            log_fn(f"[restore] resumed from step {got}")

    dataset = SyntheticDataset(arch, data_cfg)
    it = PrefetchIterator(dataset, start_step=start_step)
    monitor = StragglerMonitor()
    losses = []
    try:
        for step in range(start_step, steps):
            batch = next(it)
            t0 = time.time()
            params, opt_state, metrics = train_step(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            losses.append(loss)
            ev = monitor.record(step, dt)
            if ev:
                log_fn(f"[straggler] step {step}: {ev['ratio']:.1f}x median")
            if step % log_every == 0:
                log_fn(f"step {step}: loss={loss:.4f} "
                       f"lr={float(metrics['lr']):.2e} "
                       f"gnorm={float(metrics['grad_norm']):.3f} {dt:.2f}s")
            if manager is not None:
                manager.maybe_save(step + 1,
                                   {"params": params, "opt_state": opt_state},
                                   extra={"data_step": step + 1})
        if manager is not None:
            manager.wait()
    finally:
        it.close()
    return params, opt_state, losses


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--scale", default="tiny", choices=["tiny", "small", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    arch = scale_arch(get_config(args.arch), args.scale)
    cfg = TrainCfg(
        run=RunCfg(q_chunk=0, remat=False),
        opt=OptimizerCfg(peak_lr=args.lr, warmup_steps=20, decay_steps=args.steps),
        num_microbatches=args.microbatches)
    data_cfg = DataCfg(seq_len=args.seq_len, global_batch=args.global_batch,
                       num_microbatches=args.microbatches, seed=args.seed)
    _, _, losses = train_loop(arch, cfg, data_cfg, args.steps,
                              ckpt_dir=args.ckpt_dir, seed=args.seed)
    first = np.mean(losses[:10])
    last = np.mean(losses[-10:])
    print(f"done: loss {first:.3f} -> {last:.3f} "
          f"({'improved' if last < first else 'NOT improved'})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
