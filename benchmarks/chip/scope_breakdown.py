#!/usr/bin/env python3
"""Device time a step of a cell's step program, by the program's named
scopes, from a traced run on the TPU this process finds.

    python3 benchmarks/chip/scope_breakdown.py --workload <cell> --seed <n> \
        [--hlo-out <file>]

Runs the cell as ``run.py --trace 1`` does and prints one JSON line: the
traced window's busy time and idle time by host span, and
``scopes.reduce``'s device milliseconds a step by scope with the heaviest
instructions of each (``null`` for a program without scopes), from the
step's HLO text that the job hands over; ``--hlo-out`` writes that text.
Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run  # first: puts the program's sources on sys.path
import bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--hlo-out", default=None)
    args = ap.parse_args(argv)

    spec = bench.load_json(bench.ROOT / "BENCHMARK.json")
    devices = run.require_chips(bench.load_cell(args.workload, spec).chips)
    run.enable_compile_cache(scoped=True)
    _, out, record = run.run_job(spec, args.workload, args.seed, spec["run_seconds"], True,
                                 time.perf_counter(), devices)
    if args.hlo_out:
        with open(args.hlo_out, "w") as f:
            f.write(out.step_hlo)
    reduced = record["trace"]
    line = {"workload": args.workload, "seed": args.seed,
            "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                       "count": len(devices)},
            "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
            "idle_gaps": reduced["idle_gaps"], "scopes": reduced["scopes"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
