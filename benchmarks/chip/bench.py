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


# Keys of a configuration file that say nothing about the model's
# equations: provenance, the dtypes the jobs choose, and run settings.
METADATA = frozenset({"name", "source", "paper", "reference", "published", "why_changed",
                      "assumed", "deployment", "dtype", "compute_dtype", "model_type",
                      "max_position_embeddings", "capacity_factor"})

# Published names of architecture keys -> ``ArchConfig`` field. Any other
# key reaches ``ArchConfig`` by its own name.
PUBLISHED_NAMES = {
    "hidden_size": "d_model", "num_hidden_layers": "num_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv",
    "intermediate_size": "d_ff", "vocab_size": "vocab", "hidden_act": "mlp",
    "tie_word_embeddings": "tie_embeddings", "sliding_window": "window",
    "num_local_experts": "n_experts", "num_experts_per_tok": "top_k",
    "state_size": "ssm_state", "mamba_d_state": "ssm_state",
    "conv_kernel": "conv_width", "mamba_d_conv": "conv_width",
    "mamba_d_head": "ssm_headdim", "expand": "d_inner", "mamba_expand": "d_inner",
}
ACTIVATIONS = {"silu": "gated_silu", "relu2": "squared_relu"}
# Values the program takes in another form than the file gives them.
CONVERT = {
    "hidden_act": lambda v, c: ACTIVATIONS[v],
    "sliding_window": lambda v, c: v or 0,      # null: no window
    "expand": lambda v, c: v * c["hidden_size"],
    "mamba_expand": lambda v, c: v * c["hidden_size"],
}


def head_dim(config: Dict) -> int:
    return config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"]


# Keys the program has no field for, each with the value at which the
# published model is the one the program runs (a function of the file).
PLAIN = {
    "rope_theta": lambda c: 10000.0,
    "rms_norm_eps": lambda c: 1e-5,
    "embedding_multiplier": lambda c: 1.0,
    "residual_multiplier": lambda c: 1.0,
    "logits_scaling": lambda c: 1.0,
    "attention_multiplier": lambda c: head_dim(c) ** -0.5,
}


def arch_of(config: Dict):
    """The program's ``ArchConfig`` for a configuration file. Every key is
    metadata (``METADATA``), reaches an ``ArchConfig`` field (by its
    published name in ``PUBLISHED_NAMES`` or by the field's own name), or
    has no field and holds the value that leaves the model as the program
    runs it (``PLAIN``); any other key, or value, stops the run."""
    from dataclasses import fields
    from repro.configs.base import ArchConfig
    names = {f.name for f in fields(ArchConfig)}
    experts = config.get("num_local_experts", 0)
    out = {"name": config["name"], "source": config["source"],
           "family": "moe" if experts else "dense",
           "d_ff_expert": config["intermediate_size"] if experts else 0}
    for key, value in config.items():
        field_name = PUBLISHED_NAMES.get(key, key)
        if key in METADATA:
            continue
        if key == "hidden_act" and value not in ACTIVATIONS:
            raise ValueError(f"hidden_act = {value!r}: the program has no such MLP")
        if field_name in names:
            out[field_name] = CONVERT.get(key, lambda v, c: v)(value, config)
        elif key in PLAIN and value != PLAIN[key](config):
            raise ValueError(f"{key} = {value!r}: the program has no {key}; it runs "
                             f"{PLAIN[key](config)!r}")
        elif key not in PLAIN:
            raise ValueError(f"{key}: neither metadata nor a key the program takes")
    arch = ArchConfig(**out)
    if arch.tie_embeddings:
        import jax.numpy as jnp
        import weights
        if "['lm_head']" in weights.param_shapes(arch, jnp.float32):
            raise ValueError("tie_word_embeddings = True: the program keeps a separate "
                             "lm_head")
    return arch


def run_cfg(config: Dict, **settings):
    """The program's ``RunCfg`` with ``settings`` and the file's run
    settings: its ``capacity_factor``, the same number the reference
    reads. An expert model without one routes without drops, which the
    program takes as ``capacity_factor=None`` once it declares it so."""
    import typing
    from repro.models.lm import RunCfg
    if "capacity_factor" in config:
        settings["capacity_factor"] = config["capacity_factor"]
    elif config.get("num_local_experts"):
        if type(None) not in typing.get_args(typing.get_type_hints(RunCfg)["capacity_factor"]):
            raise ValueError(f"{config['name']} routes without drops (no capacity_factor); "
                             "the program's RunCfg.capacity_factor takes no None")
        settings["capacity_factor"] = None
    return RunCfg(**settings)


def reference_of(config: Dict):
    """The reference module that the configuration's ``reference`` names,
    a path under the benchmark's directory."""
    import importlib.util
    import sys
    path = (BENCH_DIR / config["reference"]).resolve()
    if BENCH_DIR not in path.parents:
        raise ValueError(f"reference {config['reference']!r} lies outside {BENCH_DIR}")
    name = "reference_" + "_".join(path.relative_to(BENCH_DIR).with_suffix("").parts)
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        sys.modules[name] = module
    return sys.modules[name]


class Spans:
    """Host spans by name: total seconds. With ``trace`` each
    span is also written into the profiler's trace, on the device's clock."""

    def __init__(self, trace: bool = False):
        self.trace = trace
        self.seconds: Dict[str, float] = defaultdict(float)

    def reset(self):
        self.seconds.clear()

    @contextmanager
    def __call__(self, name: str):
        ann = jax.profiler.TraceAnnotation(name) if self.trace else nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds[name] += time.perf_counter() - t0


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
    step_hlo: Optional[str] = None        # the step's compiled HLO text, traced runs


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
