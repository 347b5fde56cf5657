#!/usr/bin/env python3
"""Readings from which a cell's limits are set, at the cell's own size,
in one process on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,...,12 --control-seeds 1,2,3 [--turns 2]

For each seed it prints the compared numbers of the program as the
benchmark's set-up produces them (training: the checked steps; decode: a
cache fill and ``--turns`` whole turns at the cell's batch). For each
control seed it also prints the control's numbers, the float32 reference
against the reference computed in fp8 (``prec="fp8"`` of the reference
module that the configuration names) put in the program's place, and
those of faults planted in the reference: half of the batch left out of
the loss (training), or one served token of each session altered and half
of the sessions left undecoded (decode). With experts the fp8 reference
routes on its own, and its readings are taken both as the job would take
them from a program that reports those routes (the float32 reference held
to them, ``route_flip`` its own count) and against the float32 reference
routing on its own (``route_flip`` the share of the fp8 choices that the
float32 reference did not make). The one that stands where the program
stands counts as ``control``: held where the step reports its routes,
free where it does not; the other is printed as ``control_held`` or
``control_free`` and not used for limits. A routing fault holds one slot
of each token to an expert that the float32 reference did not choose.
Training lines also name the leaf that sets ``grad_diff``. Each line is
one JSON object. With ``--write-limits`` it then sets each number's limit
from these readings and writes ``limits/<cell>.json`` (see ``limits_of``).
Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parents[1] / "src")]

import numpy as np  # noqa: E402

import bench  # noqa: E402
import run as harness  # noqa: E402


def half_rows(traffic_: dict) -> np.ndarray:
    """Loss weights that leave out the second half of the batch (of the
    row's tokens where the batch is one row)."""
    b, s = traffic_["global_batch"], traffic_["seq_len"]
    w = np.ones((b, s), np.float32)
    if b > 1:
        w[b // 2:] = 0
    else:
        w[:, s // 2:] = 0
    return w


# Kinds printed for the record and not used for limits.
INFORMATIONAL = ("control_free", "control_held")


def train_readings(cell, seed: int, control: bool):
    from jobs import train
    trainer = train.Trainer(cell, seed, bench.Spans())
    checked = trainer.first_steps()
    trainer.close()
    del trainer
    numbers, ref = train.check(cell, seed, checked)
    yield "program", numbers, worst_leaf(checked, ref)
    if not control:
        return
    if "route_flip" in numbers:            # ref was held to the program's routes
        ref = train.reference(cell, seed)
    ctrl = train.reference(cell, seed, prec="fp8")
    free = train.compare(ctrl, ref)
    if cell.config.get("num_local_experts"):
        free["route_flip"] = max(map(not_chosen, ctrl["routes"], ref["routes"]))
        held, held_ref = train.check(cell, seed, ctrl)
        kinds = ("control", "control_free") if "route_flip" in numbers else \
            ("control_held", "control")
        yield kinds[0], held, worst_leaf(ctrl, held_ref)
        yield kinds[1], free, worst_leaf(ctrl, ref)
        wrong = [wrong_slot(r, cell.config["num_local_experts"], seed) for r in ref["routes"]]
        fault = train.reference(cell, seed, routes=wrong)
        numbers = dict(train.compare(fault, ref), route_flip=max(fault["route_flip"]))
        yield "route_fault", numbers, worst_leaf(fault, ref)
    else:
        yield "control", free, worst_leaf(ctrl, ref)
    half = train.reference(cell, seed, row_weights=half_rows(cell.traffic))
    yield "half_batch", train.compare(half, ref), worst_leaf(half, ref)


def not_chosen(routes: np.ndarray, own: np.ndarray) -> float:
    """The share of the (token, slot) choices of ``routes`` that are not
    among ``own``'s choices at that token; both ``[..., T, k]``."""
    return float(np.mean(np.all(routes[..., :, None] != own[..., None, :], axis=-1)))


def wrong_slot(routes: np.ndarray, experts: int, seed: int) -> np.ndarray:
    """``routes`` with the last slot of each token's choices replaced by a
    seeded expert that the token did not choose."""
    rng = np.random.default_rng([seed, 11])
    pick = (routes[..., -1] + rng.integers(1, experts, size=routes.shape[:-1])) % experts
    for _ in range(routes.shape[-1]):
        taken = np.any(routes == pick[..., None], axis=-1)
        pick = np.where(taken, (pick + 1) % experts, pick)
    out = routes.copy()
    out[..., -1] = pick
    return out


def worst_leaf(got: dict, ref: dict) -> dict:
    """The leaf that sets ``grad_diff``, with its reading."""
    from jobs import train
    norms = {p: float(np.linalg.norm(g.ravel())) for p, g in ref["grad"].items()}
    diffs = train.grad_diffs(got, ref, norms)
    leaf = max(diffs, key=diffs.get)
    return {"grad_diff": [leaf, diffs[leaf]]}


def decode_readings(cell, seed: int, control: bool, turns: int):
    from jobs import decode
    server = decode.Server(cell, seed, bench.Spans())
    filled = server.fill()
    done = []
    for j in range(turns):
        served = np.zeros((server.batch, server.turn_tokens), np.int32)
        server.turn(j, served, lambda: False)
        done.append(served)
    contexts, openers = server.contexts, server.openers
    server.close()
    del server
    seqs, chosen = decode.judged(contexts, openers, filled, done, seed)
    if not control:
        yield "program", decode.gap_numbers(decode.reference_gaps(cell, seed, seqs, [chosen])[0]), {}
        return
    ctrl = decode.reference_choice(cell, seed, seqs, "fp8")
    rng = np.random.default_rng([seed, 9])
    rows = np.arange(len(chosen))
    at = cell.traffic["context"] + rng.integers(0, cell.traffic["turn_tokens"], size=len(rows))
    altered = chosen.copy()      # one served token of each session altered
    altered[rows, at] = (chosen[rows, at] + 1 + rng.integers(0, 100, size=len(rows))) \
        % cell.config["vocab_size"]
    left_out = chosen.copy()     # half of the sessions never decoded
    left_out[len(rows) // 2:] = 0
    kinds = ["program", "control", "token_altered", "half_batch"]
    gaps = decode.reference_gaps(cell, seed, seqs, [chosen, ctrl, altered, left_out])
    for kind, g in zip(kinds, gaps):
        yield kind, decode.gap_numbers(g), {}


def limits_of(readings: list, job: str) -> dict:
    """Each number's limit from its two readings. The lower is the largest
    the program gave. The upper is the smallest the control gave, where
    that is at least three times the lower; in training also the smallest
    of each fault that reads ten times the lower or more, and 1 for the
    gradient and change gaps, which a state left unchanged reads, where
    that is three times the lower. A reading of 0 separates nothing and
    sets no upper. The limit lies 60% of the way from the lower to the
    upper. A number with no upper gets no limit. ``route_flip`` is held
    to this rule like the other numbers, where the program reads it."""
    out = {}
    for name in readings[0]["numbers"]:
        by_kind = {}
        for r in readings:
            if name in r["numbers"] and r["kind"] not in INFORMATIONAL:
                by_kind.setdefault(r["kind"], []).append(r["numbers"][name])
        lower = max(by_kind["program"])
        uppers = []
        control = min(by_kind.get("control", [0]))
        if control >= 3 * lower and control > 0:
            uppers.append(control)
        if job == "train":
            for kind, values in by_kind.items():
                if kind not in ("program", "control") and min(values) >= 10 * lower \
                        and min(values) > 0:
                    uppers.append(min(values))
            if name in ("grad_gap", "grad_diff", "change_gap") and 1.0 >= 3 * lower:
                uppers.append(1.0)
        upper = min(uppers) if uppers else None
        out[name] = {"lower": lower, "upper": upper,
                     "limit": None if upper is None else lower + 0.6 * (upper - lower)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--turns", type=int, default=2)
    ap.add_argument("--write-limits", action="store_true")
    args = ap.parse_args(argv)
    spec = bench.load_json(bench.ROOT / "BENCHMARK.json")
    cell = bench.load_cell(args.workload, spec)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    job = cell.traffic["job"]
    readings = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        if job == "train":
            got = train_readings(cell, seed, seed in controls)
        else:
            got = decode_readings(cell, seed, seed in controls, args.turns)
        for kind, numbers, detail in got:
            readings.append({"cell": cell.name, "seed": seed, "kind": kind,
                             "numbers": numbers, "worst": detail})
            print(json.dumps(readings[-1]), flush=True)
    derived = limits_of(readings, job)
    print(json.dumps({"cell": cell.name, "limits": derived}), flush=True)
    if args.write_limits:
        limits = {k: v["limit"] for k, v in derived.items() if v["limit"] is not None}
        with open(BENCH_DIR / "limits" / f"{cell.name}.json", "w") as f:
            json.dump(limits, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
