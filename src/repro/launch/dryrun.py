"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all          # every live cell, subprocess-isolated

Per cell this produces (artifacts/dryrun/<cell>.json):

* the FULL production compile (scanned layers, real microbatching):
  ``memory_analysis()`` proves per-device fit; compile success proves the
  sharding config is coherent;
* trip-corrected roofline inputs: XLA's ``cost_analysis`` counts while
  bodies ONCE (verified), so FLOPs / bytes / collective-bytes are
  extrapolated from 4 (train) or 2 (serve) small UNROLLED probe compiles
  via the exact linear model  f(L, G) = a + bL + cG + dLG  — probes hold
  per-microbatch batch size constant, so shard shapes match the full run;
* MODEL_FLOPS (6·N_active·D for training) for the useful-compute ratio.
"""

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from ..configs import ARCHS, SHAPES, get_config, shape_applicable
from ..models.lm import RunCfg, init_cache, init_params, loss_fn
from ..parallel.sharding import ShardingPlanner
from ..serving.serve import make_prefill_step, make_serve_step
from ..train.optim import apply_optimizer, init_opt_state
from ..train.step import TrainCfg, make_train_step
from .hlo_analysis import collective_bytes
from .input_specs import decode_input_specs, prefill_input_specs, train_input_specs
from .mesh import make_production_mesh
from .presets import run_cfg_for, train_cfg_for

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


# ---------------------------------------------------------------------------
# probe steps (fully unrolled: no while loops => cost_analysis is exact)
# ---------------------------------------------------------------------------

def _probe_train_step(arch, cfg: TrainCfg, mesh, G: int):
    run = dataclasses.replace(
        cfg.run, scan_layers=False, mesh=mesh,
        batch_axes=("pod", "data") if "pod" in mesh.axis_names else ("data",))

    def step(params, opt_state, batch):
        def mb_loss(p, mb):
            return loss_fn(arch, p, mb, run)
        grads = jax.tree.map(lambda p: jnp.zeros(p.shape, cfg.grad_accum_dtype), params)
        loss = 0.0
        for g in range(G):
            mb = jax.tree.map(lambda t: t[g], batch)
            (l, _), gr = jax.value_and_grad(mb_loss, has_aux=True)(params, mb)
            grads = jax.tree.map(lambda a, b: a + b.astype(cfg.grad_accum_dtype), grads, gr)
            loss = loss + l / G
        grads = jax.tree.map(lambda g: g / G, grads)
        new_params, new_opt, _ = apply_optimizer(cfg.opt, params, grads, opt_state)
        return new_params, new_opt, loss

    return step


def _cost(compiled):
    ca = compiled.cost_analysis()
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": collective_bytes(compiled.as_text())}


def _lin2(f11, f21, f12, f22, L, G):
    """Exact interpolation of f(L,G)=a+bL+cG+dLG from (1,1),(2,1),(1,2),(2,2)."""
    d = f22 - f21 - f12 + f11
    b = (f21 - f11) - d
    c = (f12 - f11) - d
    a = f11 - b - c - d
    return a + b * L + c * G + d * L * G


def _lin1(f1, f2, L, L1=1, L2=2):
    """Linear in L from probes at (L1, L2). A negative slope means GSPMD
    chose different strategies for the two probes (partitioning noise) —
    fall back to proportional scaling of the larger probe (monotone)."""
    b = (f2 - f1) / (L2 - L1)
    if b < 0 or f1 < 0 or f2 < 0:
        return max(f1, f2) * L / L2
    return f1 + b * (L - L1)


_SERVE_PROBE_L = (2, 4)


def _extrapolate(probes, L, G=None):
    out = {}
    keys = ["flops", "bytes"]
    l1, l2 = _SERVE_PROBE_L
    for key in keys:
        if G is None:
            out[key] = _lin1(probes[(l1,)][key], probes[(l2,)][key], L, l1, l2)
        else:
            out[key] = _lin2(probes[(1, 1)][key], probes[(2, 1)][key],
                             probes[(1, 2)][key], probes[(2, 2)][key], L, G)
    coll = {}
    kinds = probes[next(iter(probes))]["coll"].keys()
    for k in kinds:
        if G is None:
            coll[k] = _lin1(probes[(l1,)]["coll"][k], probes[(l2,)]["coll"][k],
                            L, l1, l2)
        else:
            coll[k] = _lin2(probes[(1, 1)]["coll"][k], probes[(2, 1)]["coll"][k],
                            probes[(1, 2)]["coll"][k], probes[(2, 2)]["coll"][k], L, G)
    out["coll"] = coll
    return out


def _mem_stats(compiled):
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes")}


def _small(arch, L):
    return dataclasses.replace(arch, num_layers=L)


# ---------------------------------------------------------------------------
# per-cell runners
# ---------------------------------------------------------------------------

def run_train_cell(arch, shape, mesh, record):
    dp_total = 32 if "pod" in mesh.axis_names else 16
    cfg = train_cfg_for(arch, shape, dp_total)
    G = cfg.num_microbatches
    B_mb = shape.global_batch // G

    # --- full production compile (scan) ---
    t0 = time.time()
    params_s = jax.eval_shape(lambda: init_params(arch, jax.random.PRNGKey(0), cfg.run))
    opt_s = jax.eval_shape(lambda: init_opt_state(cfg.opt, params_s))
    batch_s = train_input_specs(arch, shape, G)
    ts = make_train_step(arch, cfg, mesh)
    compiled = ts.jit_with(params_s, batch_s).lower(params_s, opt_s, batch_s).compile()
    record["full"] = {"compile_s": round(time.time() - t0, 2),
                      "memory": _mem_stats(compiled),
                      "cost_scan_raw": _cost(compiled)}

    # --- probes (unrolled, small L, python-loop G) ---
    probes = {}
    for (l, g) in [(1, 1), (2, 1), (1, 2), (2, 2)]:
        a_l = _small(arch, l)
        p_s = jax.eval_shape(lambda: init_params(a_l, jax.random.PRNGKey(0), cfg.run))
        o_s = jax.eval_shape(lambda: init_opt_state(cfg.opt, p_s))
        b_s = jax.tree.map(
            lambda t: jax.ShapeDtypeStruct((g,) + t.shape[1:], t.dtype), batch_s)
        b_s = jax.tree.map(
            lambda t: jax.ShapeDtypeStruct((t.shape[0], B_mb) + t.shape[2:], t.dtype), b_s)
        step = _probe_train_step(a_l, cfg, mesh, g)
        pl_ = ShardingPlanner(mesh, a_l)
        b_sh = jax.tree.map(lambda leaf: pl_.batch(True, leaf.shape), b_s)
        jitted = jax.jit(step,
                         in_shardings=(pl_.params(p_s), pl_.opt_state(p_s), b_sh),
                         out_shardings=(pl_.params(p_s), pl_.opt_state(p_s), None))
        probes[(l, g)] = _cost(jitted.lower(p_s, o_s, b_s).compile())
    record["probes"] = {f"L{l}G{g}": v for (l, g), v in probes.items()}
    record["extrapolated"] = _extrapolate(probes, arch.num_layers, G)
    record["config"] = {"num_microbatches": G, "microbatch_size": B_mb,
                        "seq_shard": cfg.run.seq_shard,
                        "moment_dtype": str(cfg.opt.moment_dtype.__name__
                                            if hasattr(cfg.opt.moment_dtype, "__name__")
                                            else cfg.opt.moment_dtype)}


def run_prefill_cell(arch, shape, mesh, record):
    run = run_cfg_for(arch, shape)
    t0 = time.time()
    params_s = jax.eval_shape(lambda: init_params(arch, jax.random.PRNGKey(0), run))
    batch_s = prefill_input_specs(arch, shape)
    pf = make_prefill_step(arch, run, mesh)
    compiled = pf.jit_with(params_s, batch_s).lower(params_s, batch_s).compile()
    record["full"] = {"compile_s": round(time.time() - t0, 2),
                      "memory": _mem_stats(compiled),
                      "cost_scan_raw": _cost(compiled)}
    probes = {}
    for l in _SERVE_PROBE_L:
        a_l = _small(arch, l)
        r_l = dataclasses.replace(run, scan_layers=False)
        p_s = jax.eval_shape(lambda: init_params(a_l, jax.random.PRNGKey(0), r_l))
        pf_l = make_prefill_step(a_l, r_l, mesh)
        probes[(l,)] = _cost(pf_l.jit_with(p_s, batch_s).lower(p_s, batch_s).compile())
    record["probes"] = {f"L{l[0]}": v for l, v in probes.items()}
    record["extrapolated"] = _extrapolate(probes, arch.num_layers, None)
    record["config"] = {"q_chunk": run.q_chunk}


def run_decode_cell(arch, shape, mesh, record):
    run = run_cfg_for(arch, shape)
    t0 = time.time()
    params_s = jax.eval_shape(lambda: init_params(arch, jax.random.PRNGKey(0), run))
    cache_s, tok_s, pos_s = decode_input_specs(arch, shape, run)
    ss = make_serve_step(arch, run, mesh)
    compiled = ss.jit_with(params_s, cache_s).lower(params_s, cache_s, tok_s, pos_s).compile()
    record["full"] = {"compile_s": round(time.time() - t0, 2),
                      "memory": _mem_stats(compiled),
                      "cost_scan_raw": _cost(compiled)}
    probes = {}
    for l in _SERVE_PROBE_L:
        a_l = _small(arch, l)
        r_l = dataclasses.replace(run, scan_layers=False)
        p_s = jax.eval_shape(lambda: init_params(a_l, jax.random.PRNGKey(0), r_l))
        c_s, t_s, po_s = decode_input_specs(a_l, shape, r_l)
        ss_l = make_serve_step(a_l, r_l, mesh)
        probes[(l,)] = _cost(
            ss_l.jit_with(p_s, c_s).lower(p_s, c_s, t_s, po_s).compile())
    record["probes"] = {f"L{l[0]}": v for l, v in probes.items()}
    record["extrapolated"] = _extrapolate(probes, arch.num_layers, None)
    record["config"] = {"cache_len": shape.seq_len}


def palm_trace_record(arch_name: str, shape_name: str,
                      hardware: str = "tpu_v5e_4x4") -> dict:
    """Run the cell's workload through the PALM event simulator and return
    ``{"trace": <chrome traceEvents dict>, "summary": ..., "plan": ...}``.

    Training cells and serving cells (prefill/decode) emit the *same*
    columnar :class:`~repro.core.trace.Trace` schema, rendered through the
    same :func:`~repro.core.trace.chrome_trace` exporter the CLI's
    ``simulate --trace-out`` uses — so dry-run timelines are directly
    comparable with any other PALM timeline in one Perfetto view.
    """
    import math

    from ..api import Experiment, ParallelPlan, resolve_hardware
    from ..api.report import plan_to_dict
    from ..core.trace import chrome_trace

    arch = get_config(arch_name)
    shape = SHAPES[shape_name]
    hw = resolve_hardware(hardware)
    n = hw.num_devices
    train = shape.kind == "train"
    # simple feasible split: pipeline depth bounded by layer count, data
    # parallelism by the batch, tensor parallelism takes the remainder
    pp = min(4, arch.num_layers, n)
    while pp > 1 and n % pp:
        pp -= 1
    rest = n // pp
    dp = math.gcd(rest, shape.global_batch)
    tp = min(rest // dp, max(1, arch.n_heads))
    plan = ParallelPlan(pp=pp, dp=dp, tp=tp, microbatch=1,
                        global_batch=shape.global_batch, training=train)
    report = Experiment(
        arch=arch, hardware=hw, plan=plan,
        seq_len=shape.seq_len, global_batch=shape.global_batch,
        training=train, decode=shape.kind == "decode",
        collect_timeline=True,
    ).run()
    return {
        "hardware": hw.name,
        "plan": plan_to_dict(plan),
        "summary": report.trace_summary(),
        "throughput": report.throughput,
        "total_time": report.total_time,
        "trace": chrome_trace(report.trace,
                              label=f"{arch_name} {shape_name} (palm)"),
    }


def model_flops(arch, shape) -> float:
    N = arch.active_param_count()
    if shape.kind == "train":
        return 6.0 * N * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * N * shape.global_batch * shape.seq_len
    return 2.0 * N * shape.global_batch  # decode: one token per sequence


def run_cell(arch_name: str, shape_name: str, mesh_kind: str) -> dict:
    arch = get_config(arch_name)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(arch, shape)
    record = {"arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
              "kind": shape.kind, "applicable": ok, "skip_reason": reason,
              "chips": 512 if mesh_kind == "multi" else 256,
              "params": arch.param_count(),
              "active_params": arch.active_param_count(),
              "model_flops": model_flops(arch, shape)}
    if not ok:
        return record
    mesh = make_production_mesh(multi_pod=mesh_kind == "multi")
    with jax.default_device(jax.devices("cpu")[0]):
        if shape.kind == "train":
            run_train_cell(arch, shape, mesh, record)
        elif shape.kind == "prefill":
            run_prefill_cell(arch, shape, mesh, record)
        else:
            run_decode_cell(arch, shape, mesh, record)
    record["ok"] = True
    return record


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def all_cells():
    for arch_name in sorted(ARCHS):
        for shape_name in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
            for mesh_kind in ("single", "multi"):
                yield arch_name, shape_name, mesh_kind


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", type=str)
    ap.add_argument("--shape", type=str, choices=list(SHAPES))
    ap.add_argument("--mesh", type=str, default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", type=str, default=str(ARTIFACT_DIR))
    ap.add_argument("--palm-trace", action="store_true",
                    help="first write <cell>.palm_trace.json: the cell's "
                         "workload simulated by PALM, in the same "
                         "Chrome/Perfetto trace schema as `python -m repro "
                         "simulate --trace-out` (the trace itself needs no "
                         "XLA compile; combine with --trace-only to skip "
                         "the compile)")
    ap.add_argument("--trace-only", action="store_true",
                    help="with --palm-trace: stop after writing the trace")
    ap.add_argument("--palm-hardware", type=str, default="tpu_v5e_4x4",
                    help="hardware preset the --palm-trace simulation runs on")
    args = ap.parse_args(argv)

    # 512 host devices on the CPU backend; must precede its first use,
    # which locks the device count (the --all children inherit both)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.all:
        # subprocess isolation: one compile job per process (bounds memory,
        # isolates failures, makes the sweep resumable)
        failures = []
        for a, s, m in all_cells():
            path = out_dir / f"{a}__{s}__{m}.json"
            if path.exists() and not args.force:
                print(f"[skip cached] {path.name}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--mesh", m, "--out", str(out_dir)]
            if args.palm_trace:
                cmd += ["--palm-trace", "--palm-hardware", args.palm_hardware]
                if args.trace_only:
                    cmd.append("--trace-only")
            print(f"[run] {a} x {s} x {m}", flush=True)
            r = subprocess.run(cmd, cwd=str(Path(__file__).resolve().parents[2]))
            if r.returncode != 0:
                failures.append((a, s, m))
        print(f"done; {len(failures)} failures: {failures}")
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch and --shape required (or --all)"
    path = out_dir / f"{args.arch}__{args.shape}__{args.mesh}.json"
    if args.palm_trace:
        # event-simulated timeline for this cell (cheap: no XLA compile);
        # same schema as training/serving traces everywhere else
        tpath = out_dir / f"{args.arch}__{args.shape}.palm_trace.json"
        rec = palm_trace_record(args.arch, args.shape, args.palm_hardware)
        tpath.write_text(json.dumps(rec, indent=1))
        s = rec["summary"]
        print(f"[palm trace written to {tpath}: {s['events']} events, "
              f"bubble {s['bubble_fraction']:.1%}]")
        if args.trace_only:
            return 0
    t0 = time.time()
    try:
        record = run_cell(args.arch, args.shape, args.mesh)
    except Exception:
        record = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
                  "ok": False, "error": traceback.format_exc()}
        path.write_text(json.dumps(record, indent=1))
        print(record["error"], file=sys.stderr)
        return 1
    record["wall_s"] = round(time.time() - t0, 2)
    path.write_text(json.dumps(record, indent=1))
    status = "OK" if record.get("ok") else f"SKIP ({record.get('skip_reason')})"
    print(f"{args.arch} x {args.shape} x {args.mesh}: {status} "
          f"[{record['wall_s']}s]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
