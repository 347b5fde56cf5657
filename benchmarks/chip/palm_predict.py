#!/usr/bin/env python3
"""PALM's predicted step time for the benchmark's training cells, on the
simulator's ``tpu_v5e`` preset, from the same configuration and traffic
files the chip benchmark reads. Runs on the host CPU only; a prediction,
never a measurement, and never part of a timed run.

    python3 benchmarks/chip/palm_predict.py [cell ...]

Prints one JSON line per cell: the predicted step seconds and the
tokens a second they imply. The preset's efficiencies are its own
constants, not fitted to any chip run.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parents[1] / "src")]

import bench  # noqa: E402


def predict(cell_name: str, spec: dict) -> dict:
    from repro.api import Experiment, ParallelPlan
    from repro.core.hardware import tpu_v5e_pod
    cell = bench.load_cell(cell_name, spec)
    t = cell.traffic
    if t["job"] != "train":
        raise ValueError(f"{cell_name}: PALM predicts training steps only")
    rows, cols = {1: (1, 1), 4: (2, 2)}[cell.chips]
    batch = t["global_batch"]
    report = Experiment(
        arch=bench.arch_of(cell.config), hardware=tpu_v5e_pod(rows, cols),
        plan=ParallelPlan(pp=1, dp=cell.chips, tp=1, microbatch=batch // cell.chips,
                          global_batch=batch),
        seq_len=t["seq_len"]).run()
    step_s = report.total_time
    return {"cell": cell_name, "hardware": f"tpu_v5e_{rows}x{cols}",
            "predicted_step_s": step_s,
            "predicted_tokens_per_s": batch * t["seq_len"] / step_s}


def main(argv=None) -> int:
    spec = bench.load_json(bench.ROOT / "BENCHMARK.json")
    names = (argv if argv is not None else sys.argv[1:]) or [
        w["name"] for w in spec["workloads"]
        if bench.load_cell(w["name"], spec).traffic["job"] == "train"]
    for name in names:
        print(json.dumps(predict(name, spec)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
