#!/usr/bin/env python3
"""Device time a step of a cell's step program, by the program's named
scopes, from a traced window on the TPU this process finds.

    python3 benchmarks/chip/scope_breakdown.py --workload <cell> --seed <n> \
        [--hlo-out <file>]

Builds the cell's job as ``run.py`` does (weights from the seed, the
compiled step, its feed; a decode cell's cache is left unfilled, which
changes no shape), warms the step up, profiles ``bench.TRACE_SECONDS`` of
steps and prints one JSON line: ``trace_reduce.reduce``'s busy time and
idle time by host span, and ``scopes.reduce``'s device milliseconds a step
by scope (``null`` for a program without scopes). ``--hlo-out`` writes
the step's compiled HLO text. No reference runs, so there is no
``correct``: this is a diagnosis, not a benchmark run. Needs a TPU.
"""

from __future__ import annotations

import argparse
import itertools
import json
import shutil
import sys
import tempfile

import jax
import numpy as np

import run  # first: puts the program's sources on sys.path
import bench
import scopes
import trace_reduce

WARM_STEPS = 3


def traced_train(cell, seed: int, spans, window) -> str:
    from jobs.train import Trainer
    trainer = Trainer(cell, seed, spans)
    for _ in range(WARM_STEPS):
        trainer.step()
    window.start()
    while window.active:
        trainer.step()
        window.tick()
    hlo = trainer.fn.lower(trainer.params, trainer.opt_state,
                           next(trainer.data)).compile().as_text()
    trainer.close()
    return hlo


def traced_decode(cell, seed: int, spans, window) -> str:
    from jobs.decode import Server
    server = Server(cell, seed, spans)
    served = np.zeros((server.batch, server.turn_tokens), np.int32)
    warm = itertools.count(1)
    server.turn(0, served, lambda: next(warm) >= WARM_STEPS)
    window.start()

    def on_step() -> bool:
        window.tick()
        return not window.active

    turn = 1
    while window.active:
        server.turn(turn, served, on_step)
        turn += 1
    tok = jax.device_put(server.openers[0])
    hlo = server.serve.lower(server.params, server.cache, tok,
                             server.positions[0]).compile().as_text()
    server.close()
    return hlo


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--hlo-out", default=None)
    args = ap.parse_args(argv)

    cell = bench.load_cell(args.workload)
    devices = run.require_chips(cell.chips)
    run.enable_compile_cache()
    # the scopes are metadata, which the cache's key leaves out by default:
    # a cached executable of the same program could carry stale op names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    traced = {"train": traced_train, "decode": traced_decode}[cell.traffic["job"]]
    spans = bench.Spans(trace=True)
    trace_dir = tempfile.mkdtemp(prefix="scope_breakdown_")
    try:
        hlo = traced(cell, args.seed, spans, bench.WindowTrace(trace_dir))
        if args.hlo_out:
            with open(args.hlo_out, "w") as f:
                f.write(hlo)
        profile = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        reduced = trace_reduce.reduce(profile, spans.seconds.keys())
        by_scope = scopes.reduce(profile, hlo)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    line = {"workload": args.workload, "seed": args.seed,
            "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                       "count": len(devices)},
            "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
            "idle_gaps": reduced["idle_gaps"], "scopes": by_scope}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
