#!/usr/bin/env python3
"""Smoke run of the JAX training and serving path on a TPU.

    python3 chip_smoke.py               # one chip: train, then serve
    python3 chip_smoke.py --four-chips  # only the (data=2, model=2) mesh step

The model is yi-6b at its published width with the depth cut to 2 layers,
random weights and synthetic data from ``--seed``. Every phase goes through
the entry points a user calls: ``launch.train.train_loop``,
``serving.serve.make_prefill_step`` / ``make_serve_step`` /
``greedy_generate`` and, for four chips,
``train.step.make_train_step(arch, cfg, mesh).jit_with``. Each phase checks
its results and any failed check ends the run with an error.

Times printed here are one-off smoke readings, not benchmark numbers. The
last line of standard output is one JSON object naming the device. Without
a TPU the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from contextlib import contextmanager
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.train import train_loop  # noqa: E402
from repro.models.lm import RunCfg, init_cache, init_params  # noqa: E402
from repro.serving.serve import (  # noqa: E402
    greedy_generate,
    make_prefill_step,
    make_serve_step,
)
from repro.train.data import DataCfg, SyntheticDataset  # noqa: E402
from repro.train.step import TrainCfg, init_train_state, make_train_step  # noqa: E402

ARCH = "yi-6b"
LAYERS = 2            # depth cut; widths stay as published
BATCH, SEQ = 4, 1024  # global batch x sequence for training
STEPS = 5
PROMPTS, PROMPT_LEN, NEW_TOKENS = 4, 128, 32
# bf16 compute: logits are bf16 matmul outputs and the residual stream is
# bf16, so two paths over the same tokens agree to a few bf16 ulps of the
# largest logit (2**-8 relative each)
LOGIT_RTOL = 8 * 2.0 ** -8
# sharded vs one-device step on the same batch: only the reduction order
# differs, so one bf16 ulp (relative) bounds the gap
STEP_RTOL = 2.0 ** -8


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def require_tpu(count: int):
    devices = jax.devices()
    found = devices[0]
    if found.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{found.platform!r} ({found.device_kind}), no result")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX found "
                         f"{len(devices)}")
    return devices


def reduced_arch(layers: int = LAYERS):
    arch = get_config(ARCH)
    print(f"reduced: {ARCH} num_layers {arch.num_layers} -> {layers} "
          f"(d_model {arch.d_model}, heads {arch.n_heads}/{arch.n_kv}, "
          f"d_ff {arch.d_ff}, vocab {arch.vocab} as published)", flush=True)
    return dataclasses.replace(arch, num_layers=layers)


@contextmanager
def compile_log():
    """Yields a list that gathers ``(function, seconds, cache)`` for each
    backend compile while the block runs; ``cache`` is ``"hit"`` or
    ``"miss"`` in the persistent compile cache, or ``"off"``."""
    compiles = []
    cache = ["off"]

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache[0] = "hit"
        elif event == "/jax/compilation_cache/cache_misses":
            cache[0] = "miss"

    def on_duration(event, secs, fun_name="", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append((fun_name, secs, cache[0]))
            cache[0] = "off"

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield compiles
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _fmt_compile(compiles, fun_name: str) -> str:
    got = [f"{secs:.3f} s (persistent cache {cache})"
           for name, secs, cache in compiles if name == fun_name]
    return ", ".join(got) or "none"


def train_phase(arch, batch: int, seq: int, steps: int, seed: int):
    """``train_loop`` for a few steps; returns the trained params."""
    cfg = TrainCfg()
    data_cfg = DataCfg(seq_len=seq, global_batch=batch, num_microbatches=1,
                       seed=seed)
    print(f"[train] train_loop: {steps} steps, global batch {batch} x seq "
          f"{seq}, 1 microbatch, default TrainCfg", flush=True)
    with compile_log() as first:
        params, opt_state, losses = train_loop(
            arch, cfg, data_cfg, steps, log_every=1, seed=seed,
            log_fn=lambda m: print(f"[train] {m}", flush=True))
    print(f"[train] compile of train_step: {_fmt_compile(first, 'jit(train_step)')}",
          flush=True)

    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        fail(f"train losses not all finite: {losses}")
    ln_v = math.log(arch.vocab)
    print(f"[train] step-0 loss {losses[0]!r} vs ln(vocab) {ln_v!r}", flush=True)
    if abs(losses[0] - ln_v) > 1.0:
        fail(f"step-0 loss {losses[0]} is not within 1.0 of ln({arch.vocab})")

    # a second train step, built anew, must come from the persistent cache
    sds = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    batch0 = SyntheticDataset(arch, data_cfg).batch_at(0)
    with compile_log() as again:
        make_train_step(arch, cfg).lower(
            sds(params), sds(opt_state), sds(batch0)).compile()
    print(f"[train] compile of a rebuilt train_step: "
          f"{_fmt_compile(again, 'jit(train_step)')}", flush=True)

    stats = jax.devices()[0].memory_stats() or {}
    print(f"[train] peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    return params


def serve_phase(arch, params, batch: int, prompt_len: int, new_tokens: int,
                seed: int) -> None:
    """Prefill vs token-by-token decode on the same prompts, then greedy
    generation."""
    cfg = RunCfg()
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(0, arch.vocab, (batch, prompt_len)),
                          jnp.int32)

    prefill = make_prefill_step(arch, cfg)
    t0 = time.perf_counter()
    last = prefill(params, {"tokens": prompts})[:, -1]          # [B, V]
    last.block_until_ready()
    print(f"[serve] prefill {batch} x {prompt_len}: "
          f"{time.perf_counter() - t0:.3f} s incl. compile", flush=True)

    serve = make_serve_step(arch, cfg)
    cache = init_cache(arch, batch, prompt_len, cfg)
    columns = list(jnp.asarray(np.asarray(prompts).T))  # sliced before timing
    step_s = []
    for i, column in enumerate(columns):
        t0 = time.perf_counter()
        _, logits, cache = serve(params, cache, column, jnp.int32(i))
        logits.block_until_ready()
        step_s.append(time.perf_counter() - t0)
    diff = float(jnp.max(jnp.abs(last - logits)))
    tol = LOGIT_RTOL * float(jnp.max(jnp.abs(last)))
    print(f"[serve] prefill vs decode logits at position {prompt_len - 1}: "
          f"max |diff| {diff!r}, tolerance {tol!r} "
          f"({LOGIT_RTOL!r} x max |logit|)", flush=True)
    if not diff <= tol:
        fail(f"prefill and decode logits differ by {diff} > {tol}")
    steady = float(np.median(step_s[1:]))
    print(f"[serve] decode step (batch {batch}): first {step_s[0]:.3f} s incl. "
          f"compile, median {steady!r} s -> {batch / steady!r} tokens/s "
          f"(one-chip smoke time, not a benchmark number)", flush=True)

    t0 = time.perf_counter()
    out = np.asarray(greedy_generate(arch, params, prompts, new_tokens, cfg))
    gen_s = time.perf_counter() - t0
    print(f"[serve] greedy_generate {new_tokens} new tokens x {batch}: "
          f"{gen_s:.3f} s incl. compile and {prompt_len - 1} prompt steps "
          f"(one-chip smoke time)", flush=True)
    if out.shape != (batch, new_tokens):
        fail(f"generated shape {out.shape} != {(batch, new_tokens)}")
    if out.min() < 0 or out.max() >= arch.vocab:
        fail(f"generated ids outside [0, {arch.vocab}): "
             f"{out.min()}..{out.max()}")
    # the first generated token is an argmax of the prefill logits, up to
    # the same tolerance
    last = np.asarray(last)
    chosen = last[np.arange(batch), out[:, 0]]
    gap = float(np.max(last.max(axis=-1) - chosen))
    print(f"[serve] first generated token vs prefill argmax: logit gap "
          f"{gap!r}", flush=True)
    if not gap <= tol:
        fail(f"first generated token is {gap} below the prefill argmax")


def four_chip_phase(arch, batch: int, seq: int, seed: int) -> None:
    """One train step on a (data=2, model=2) mesh against the same step on
    one device."""
    cfg = TrainCfg()
    data_cfg = DataCfg(seq_len=seq, global_batch=batch, num_microbatches=1,
                       seed=seed)
    batch0 = SyntheticDataset(arch, data_cfg).batch_at(0)
    key = jax.random.PRNGKey(seed)

    # reference first; its arrays go before the sharded step, since device
    # 0 cannot hold both
    params, opt_state = init_train_state(arch, cfg, key)
    out = make_train_step(arch, cfg)(params, opt_state, batch0)
    ref_loss, ref_gn = float(out[2]["loss"]), float(out[2]["grad_norm"])
    del params, opt_state, out
    print(f"[4chip] one-device step: loss {ref_loss!r} grad_norm {ref_gn!r}",
          flush=True)

    mesh = make_mesh((2, 2), ("data", "model"))
    step = make_train_step(arch, cfg, mesh)
    shapes = jax.eval_shape(lambda k: init_params(arch, k, cfg.run), key)
    jitted = step.jit_with(shapes, batch0)
    p_sh, o_sh = step.planner.params(shapes), step.planner.opt_state(shapes)
    params, opt_state = jax.jit(
        lambda k: init_train_state(arch, cfg, k), out_shardings=(p_sh, o_sh))(key)
    t0 = time.perf_counter()
    params, opt_state, metrics = jitted(params, opt_state, batch0)
    loss, gn = float(metrics["loss"]), float(metrics["grad_norm"])
    print(f"[4chip] (data=2, model=2) step: loss {loss!r} grad_norm {gn!r} "
          f"({time.perf_counter() - t0:.3f} s incl. compile)", flush=True)
    for name, got, want in (("loss", loss, ref_loss), ("grad_norm", gn, ref_gn)):
        rel = abs(got - want) / abs(want)
        print(f"[4chip] {name} relative difference {rel!r} "
              f"(tolerance {STEP_RTOL!r})", flush=True)
        if not rel <= STEP_RTOL:
            fail(f"sharded {name} {got} vs one-device {want}")

    n_dev = len(mesh.devices.flat)
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        if len(leaf.sharding.device_set) != n_dev:
            fail(f"{name} lives on {len(leaf.sharding.device_set)} devices")
        is_matrix = leaf.ndim >= 2 and "norm" not in name
        shard = leaf.addressable_shards[0].data
        if is_matrix and shard.size * n_dev != leaf.size:
            fail(f"{name} {leaf.shape} is not split over {n_dev} devices "
                 f"(shard {shard.shape})")
    print(f"[4chip] every parameter spans {n_dev} devices; every weight "
          f"matrix is split {n_dev} ways", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (data=2, model=2) train step on four "
                         "chips against the one-device step")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu(4 if args.four_chips else 1)
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x {len(devices)}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    arch = reduced_arch()
    if args.four_chips:
        four_chip_phase(arch, BATCH, SEQ, args.seed)
    else:
        params = train_phase(arch, BATCH, SEQ, STEPS, args.seed)
        serve_phase(arch, params, PROMPTS, PROMPT_LEN, NEW_TOKENS, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
