#!/usr/bin/env python3
"""Chip benchmark: runs one cell of ``BENCHMARK.json`` on the TPU this
process finds, and prints one JSON line as the last line of its output.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

The cell names a configuration file (which names its reference module),
a traffic file (whose ``job`` picks ``jobs/<job>.py``) and a chip count;
the limits of its compared numbers are in ``limits/<cell>.json``. With
``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` the first seconds of the window are profiled and the line
carries the cell's per-layer metrics, each read by
``metrics/<metric>.py`` from the run's record, and a ``breakdown``: the
longest device ops, idle time by host span and device ms a step by named
scope. Off a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parents[1] / "src")]

import bench  # noqa: E402

def require_chips(count: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"run.py: needs a TPU; JAX found {devices[0].platform!r} "
                         f"({devices[0].device_kind}); no result")
    if len(devices) < count:
        raise SystemExit(f"run.py: the cell needs {count} TPU chips, JAX found "
                         f"{len(devices)}; no result")
    return devices


def enable_compile_cache(scoped: bool = False) -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` when set, else
    at the fixed ``<checkout>/.jax_cache``; every program is kept. With
    ``scoped`` a program's metadata is part of its key: the named scopes
    are metadata, and a cached executable of the same program could
    otherwise carry another tree's op names."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(bench.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", scoped)
    return path


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", BENCH_DIR / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def cell_metrics(spec: dict, cell: str, trace: bool) -> list:
    """The metrics this cell reports: its end-to-end metrics, or with
    ``trace`` the per-layer metrics whose cells include it."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in moved)]


def reduce_trace(profile, span_names, step_hlo) -> dict:
    """``trace_reduce.reduce`` of a traced window, with ``scopes`` (device
    ms a step by named scope; None without the step's HLO text)."""
    import scopes
    import trace_reduce
    reduced = trace_reduce.reduce(profile, span_names)
    if reduced is not None:
        reduced["scopes"] = scopes.reduce(profile, step_hlo) if step_hlo else None
    return reduced


def breakdown(reduced: dict) -> dict:
    """The traced window's longest device ops and its idle time by host
    span; device ms a step by scope, which add up to ``step_busy_ms``, the
    busy time of one of the ``scope_steps`` step executions they average."""
    out = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    by_scope = reduced["scopes"]
    if by_scope is not None:
        out.update(scopes=by_scope["ms"], step_busy_ms=by_scope["busy_ms"],
                   scope_steps=by_scope["steps"])
    return out


def run_job(spec: dict, cell_name: str, seed: int, seconds: float, trace: bool,
            process_start: float, devices):
    """Runs the cell's job; returns the cell, the job's ``Outcome`` and the
    record its per-layer readers read."""
    cell = bench.load_cell(cell_name, spec)
    job = importlib.import_module(f"jobs.{cell.traffic['job']}")
    spans = bench.Spans(trace=trace)
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    try:
        with bench.CompileLog() as compiles:
            out = job.run(cell, seed, seconds, spans, bench.WindowTrace(trace_dir),
                          process_start, compiles)
        record = dict(out.record, config=cell.config, traffic=cell.traffic, trace=None)
        if trace:
            import trace_reduce
            profile = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
            record["trace"] = reduce_trace(profile, spans.seconds.keys(), out.step_hlo)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    record["peaks"] = bench.load_peaks(devices[0].device_kind)
    return cell, out, record


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float, trace: bool,
             process_start: float, devices) -> dict:
    """Runs the cell's job and returns the result line (a dict)."""
    cell, out, record = run_job(spec, cell_name, seed, seconds, trace, process_start,
                                devices)
    metrics = {}
    for m in cell_metrics(spec, cell_name, trace):
        value = out.end_to_end.get(m["name"]) if not trace else reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct, checks = bench.judge(out.numbers, cell.limits)
    line = {"correct": correct, "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics,
            "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                       "count": len(devices), "memory_peak_bytes": out.memory_peak_bytes}}
    reduced = record["trace"]
    if reduced is not None:
        line["device"]["busy_s"] = reduced["busy_s"]
        line["device"]["window_s"] = reduced["window_s"]
        line["breakdown"] = breakdown(reduced)
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = bench.load_json(bench.ROOT / "BENCHMARK.json")
    chips = {w["name"]: w["chips"] for w in spec["workloads"]}
    if args.workload not in chips:
        raise SystemExit(f"run.py: no workload {args.workload!r}; no result")
    devices = require_chips(chips[args.workload])
    enable_compile_cache(bool(args.trace))
    line = run_cell(spec, args.workload, args.seed, args.seconds, bool(args.trace),
                    PROCESS_START, devices)
    for name, check in line["checks"].items():
        print(f"check {name}: {check['value']!r} (limit {check['limit']!r})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
