"""What every job of the chip benchmark shares: the cell's files, the
model built from its configuration file, host spans, compile events, and
the comparison that decides ``correct``."""

from __future__ import annotations

import json
import math
import time
from collections import defaultdict
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

import traffic

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
TRACE_SECONDS = 4.0      # length of the traced part of a --trace 1 window


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_peaks(kind: str) -> Dict:
    """The published peaks of one device kind; an unknown kind is an error."""
    table = load_json(BENCH_DIR / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table['devices'])}")
    return table["devices"][kind]


@dataclass
class Cell:
    name: str
    config: Dict
    traffic: Dict
    chips: int
    limits: Optional[Dict]


def load_cell(name: str, spec: Optional[Dict] = None, root: Path = ROOT) -> Cell:
    """A cell of ``BENCHMARK.json`` with its configuration, traffic and
    limits, each found by name."""
    spec = spec or load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    limits_path = BENCH_DIR / "limits" / f"{name}.json"
    return Cell(name=name, config=load_json(root / entry["file"]),
                traffic=traffic.load(w["traffic"]),
                chips=w["chips"],
                limits=load_json(limits_path) if limits_path.exists() else None)


def arch_of(config: Dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig
    if config.get("hidden_act") != "silu":
        raise ValueError(f"unsupported hidden_act {config.get('hidden_act')!r}")
    experts = config.get("num_local_experts", 0)
    h, nh = config["hidden_size"], config["num_attention_heads"]
    return ArchConfig(
        name=config["name"], family="moe" if experts else "dense",
        num_layers=config["num_hidden_layers"], d_model=h, n_heads=nh,
        n_kv=config["num_key_value_heads"], d_ff=config["intermediate_size"],
        vocab=config["vocab_size"], head_dim=config.get("head_dim") or h // nh,
        mlp="gated_silu", tie_embeddings=config["tie_word_embeddings"],
        n_experts=experts, top_k=config.get("num_experts_per_tok", 0),
        d_ff_expert=config["intermediate_size"] if experts else 0,
        source=config["source"])


class Spans:
    """Host spans by name: total seconds and count. With ``trace`` each
    span is also written into the profiler's trace, on the device's clock."""

    def __init__(self, trace: bool = False):
        self.trace = trace
        self.seconds: Dict[str, float] = defaultdict(float)
        self.count: Dict[str, int] = defaultdict(int)

    def reset(self):
        self.seconds.clear()
        self.count.clear()

    @contextmanager
    def __call__(self, name: str):
        ann = jax.profiler.TraceAnnotation(name) if self.trace else nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds[name] += time.perf_counter() - t0
        self.count[name] += 1


class CompileLog:
    """Counts backend compiles (and their seconds) while it is open."""

    def __init__(self):
        self.events: List[float] = []

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append(secs)

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


@dataclass
class Outcome:
    """What a job hands back to the harness."""
    end_to_end: Dict[str, float]          # metric name -> value
    attempted: int
    failed: int
    numbers: Dict[str, float]             # compared number -> reading
    record: Dict = field(default_factory=dict)   # what per-layer readers read
    memory_peak_bytes: int = 0


def judge(numbers: Dict[str, float], limits: Optional[Dict]) -> tuple:
    """``correct`` and the checks: every number that has a limit is finite
    and at or under it; a cell with no limits is not correct. A number
    with no limit is printed with a null limit and not compared."""
    limits = limits or {}
    checks = {name: {"value": value, "limit": limits.get(name)}
              for name, value in numbers.items()}
    ok = bool(limits) and all(
        name in numbers and math.isfinite(numbers[name]) and numbers[name] <= limit
        for name, limit in limits.items())
    return ok, checks


def gap(program: Dict[str, float], reference: Dict[str, float],
        keep: Optional[List[str]] = None) -> float:
    """Worst leaf's gap between two norms, over the larger of the
    reference's norm of that leaf and of the median leaf."""
    names = keep if keep is not None else list(reference)
    median = float(np.median([reference[n] for n in reference]))
    return max(abs(program[n] - reference[n]) / max(reference[n], median)
               for n in names)


def moving_leaves(reference_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's."""
    median = float(np.median(list(reference_grad.values())))
    return [n for n, g in reference_grad.items() if g >= 1e-3 * median]


class WindowTrace:
    """Profiles the first ``TRACE_SECONDS`` of the window into
    ``directory`` (nothing when it is None), marked by the host span the
    trace reduction reads as its window."""

    def __init__(self, directory: Optional[str]):
        self.directory = directory
        self.active = False

    def start(self):
        if self.directory is None:
            return
        jax.profiler.start_trace(self.directory)
        self.span = jax.profiler.TraceAnnotation("traced_window")
        self.span.__enter__()
        self.t0 = time.perf_counter()
        self.active = True

    def tick(self):
        if self.active and time.perf_counter() - self.t0 >= TRACE_SECONDS:
            self.stop()

    def stop(self):
        if not self.active:
            return
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
