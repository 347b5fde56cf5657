"""Each job at a tiny size on the CPU against the float32 reference.

The limits are set at the tiny size by the rule ``calibrate.py`` applies at
the cell's size on the chip (program, control and planted faults over two
seeds). Then a sound run on another seed passes, the control fails, and
every fault a cell can have (state left unchanged, half of the batch left
out, a token altered where it is produced) fails through the harness.
A step that reports its expert choices (``moe_routes``) hands them to the
reference, and a wrong choice fails by ``route_flip``."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_paths import BENCH_DIR

import bench
import calibrate
import weights
from jobs import decode, train
from reference import llama

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)
TINY_MOE = dict(TINY, intermediate_size=32, num_local_experts=8, num_experts_per_tok=2,
                attention_multiplier=0.25)
# decode is judged by logit gaps, which scale with the hidden size: wide
# enough that a wrong token's gap is as large as in the full model
TINY_WIDE = dict(TINY, hidden_size=1024, intermediate_size=2048, num_attention_heads=8)
SEED = 2 ** 31 + 11


# cell -> (configuration, traffic, tiny traffic shapes). The granite
# training stage has its files and limits but no cell yet (its control
# does not separate on the chip); its tiny runs keep the expert path tested.
CELLS = {
    "yi-6b.train.s4k": ("yi-6b-train-stage", "train.s4k.b1",
                        dict(seq_len=64, global_batch=2)),
    "granite-moe.train.s1k": ("granite-moe-3b-a800m-train-stage", "train.s1k.b8",
                              dict(seq_len=64, global_batch=4)),
    "yi-6b.decode.b16.c1k": ("yi-6b", "decode.b16.c1k.t256",
                             dict(batch=4, context=16, turn_tokens=8)),
}


CALIBRATION_SEEDS = (1, 2)
_READINGS = {}


def readings(cell):
    """Program, control and fault readings at the tiny size, by kind."""
    if cell.name not in _READINGS:
        out = []
        for seed in CALIBRATION_SEEDS:
            if cell.traffic["job"] == "train":
                got = calibrate.train_readings(cell, seed, control=True)
            else:
                got = calibrate.decode_readings(cell, seed, control=True, turns=2)
            out += [{"kind": kind, "numbers": numbers} for kind, numbers, _ in got]
        _READINGS[cell.name] = out
    return _READINGS[cell.name]


def tiny(name, label=None):
    """The cell ``name`` at a tiny size, named ``label`` where given: its
    configuration with small widths and depth, its traffic with small
    shapes, no limits."""
    config, traffic_name, shapes = CELLS[name]
    config = bench.load_json(BENCH_DIR / "configs" / f"{config}.json")
    t = dict(bench.traffic.load(traffic_name), **shapes)
    shrink = (TINY_MOE if config.get("num_local_experts") else
              TINY_WIDE if t["job"] == "decode" else TINY)
    return bench.Cell(name=label or name, config=dict(config, **shrink), traffic=t,
                      chips=1, limits=None)


def tiny_cell(name, label=None):
    """``tiny(name, label)`` with limits set from readings at that size."""
    cell = tiny(name, label)
    derived = calibrate.limits_of(readings(cell), cell.traffic["job"])
    cell.limits = {k: v["limit"] for k, v in derived.items() if v["limit"] is not None}
    return cell


def run_job(cell, seconds=2.0):
    job = {"train": train, "decode": decode}[cell.traffic["job"]]
    with bench.CompileLog() as compiles:
        out = job.run(cell, SEED, seconds, bench.Spans(), bench.WindowTrace(None),
                      time.perf_counter(), compiles)
    return out, bench.judge(out.numbers, cell.limits)[0]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    cell = tiny_cell(name)
    out, correct = run_job(cell)
    assert correct, out.numbers
    assert out.attempted > 0 and out.failed == 0
    assert all(v > 0 for v in out.end_to_end.values())


TRAIN = [n for n in CELLS if "seq_len" in CELLS[n][2]]
DECODE = [n for n in CELLS if "batch" in CELLS[n][2]]


def faulty_train_step(monkeypatch, fault):
    from repro.train import step as step_mod
    real_make = step_mod.make_train_step

    def make(arch, cfg, mesh=None):
        real = real_make(arch, cfg, mesh)

        def step(params, opt_state, batch):
            copy = lambda t: jax.tree.map(jnp.copy, t)
            if fault == "unchanged":
                _, _, m = real(copy(params), copy(opt_state), batch)
                return params, opt_state, m
            half = {k: v[:, : v.shape[1] // 2] for k, v in batch.items()}
            return real(params, opt_state, half)

        return step

    monkeypatch.setattr(step_mod, "make_train_step", make)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
@pytest.mark.parametrize("name", TRAIN)
def test_train_fault_is_not_correct(monkeypatch, name, fault):
    cell = tiny_cell(name)           # limits from sound readings, before the fault
    faulty_train_step(monkeypatch, fault)
    out, correct = run_job(cell)
    assert not correct, out.numbers


def faulty_serve_step(monkeypatch, fault):
    from repro.serving import serve as serve_mod
    real_make = serve_mod.make_serve_step

    def make(arch, cfg, mesh=None):
        real = real_make(arch, cfg, mesh)
        calls = [0]

        def step(params, cache, tokens, pos):
            calls[0] += 1
            if fault == "unchanged":
                nxt, logits, _ = real(params, jax.tree.map(jnp.copy, cache), tokens, pos)
                return nxt, logits, cache
            nxt, logits, cache = real(params, cache, tokens, pos)
            if fault == "token_altered" and calls[0] % 3 == 0:
                nxt = nxt.at[1].set((nxt[1] + 1) % arch.vocab)
            if fault == "half_batch":
                nxt = nxt.at[nxt.shape[0] // 2:].set(0)
            return nxt, logits, cache

        return step

    monkeypatch.setattr(serve_mod, "make_serve_step", make)


@pytest.mark.parametrize("fault", ["unchanged", "token_altered", "half_batch"])
@pytest.mark.parametrize("name", DECODE)
def test_decode_fault_is_not_correct(monkeypatch, name, fault):
    cell = tiny_cell(name)           # limits from sound readings, before the fault
    faulty_serve_step(monkeypatch, fault)
    out, correct = run_job(cell)
    assert not correct, out.numbers


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = tiny_cell(name)
    assert cell.limits
    for r in readings(cell):
        if r["kind"] not in ("program",) + calibrate.INFORMATIONAL:
            assert not bench.judge(r["numbers"], cell.limits)[0], r


# ---------------------------------------------------------------------------
# Expert routes handed from the step to the reference
# ---------------------------------------------------------------------------

MOE = "granite-moe.train.s1k"


class ReportedRoutes:
    """Wraps the program's train step so that its metrics carry
    ``moe_routes``, as a program that reports its expert choices would:
    here the float32 reference's own choices, from its router on the
    step's parameters and tokens, as a stand-in for the program's. With
    ``fault`` "wrong_slot" one slot of each token names an expert it did
    not choose; with "collapsed" every slot names the token's first
    choice."""

    def __init__(self, monkeypatch, cell):
        from repro.train import step as step_mod
        self.fault = None
        real_make = step_mod.make_train_step
        dm = llama.dims(cell.config)
        experts = cell.config["num_local_experts"]
        own = jax.jit(lambda params, tokens: llama.hidden(params, tokens, dm, "f32")[1])

        def make(arch, cfg, mesh=None):
            real = real_make(arch, cfg, mesh)

            def step(params, opt_state, batch):
                routes = np.asarray(own(weights.flatten(params), batch["tokens"][0]))
                if self.fault == "wrong_slot":
                    routes = calibrate.wrong_slot(routes, experts, 0)
                elif self.fault == "collapsed":
                    routes = np.repeat(routes[..., :1], routes.shape[-1], axis=-1)
                params, opt_state, m = real(params, opt_state, batch)
                return params, opt_state, dict(m, moe_routes=jnp.asarray(routes))

            return step

        monkeypatch.setattr(step_mod, "make_train_step", make)


def routed_cell(monkeypatch):
    """The tiny expert cell with a step that reports its routes, and
    limits set from readings of that step, as ``calibrate.py`` sets them."""
    reported = ReportedRoutes(monkeypatch, tiny(MOE))
    return tiny_cell(MOE, MOE + ".routes"), reported


def test_routes_of_the_references_own_choices_read_as_free_routing(monkeypatch):
    plain = tiny_cell(MOE)
    free, _ = run_job(plain)
    ReportedRoutes(monkeypatch, plain)
    held, _ = run_job(plain)
    assert held.numbers.pop("route_flip") == 0
    assert held.numbers == free.numbers


def test_a_routing_fault_is_not_correct(monkeypatch):
    """Also: the control that counts (the fp8 reference's own routes,
    handed over as a program would hand them) and the planted faults of
    the readings fail the limits of a step that reports its routes."""
    cell, reported = routed_cell(monkeypatch)
    assert cell.limits["route_flip"] > 0
    kinds = {r["kind"] for r in readings(cell)}
    assert {"control", "control_free", "route_fault", "half_batch"} <= kinds
    for r in readings(cell):
        if r["kind"] in ("control", "route_fault", "half_batch"):
            assert not bench.judge(r["numbers"], cell.limits)[0], r
    reported.fault = "wrong_slot"
    out, correct = run_job(cell)
    assert not correct, out.numbers
    assert out.numbers["route_flip"] > cell.limits["route_flip"]


def test_half_batch_with_routes_fails_as_a_reading(monkeypatch):
    cell, _ = routed_cell(monkeypatch)
    faulty_train_step(monkeypatch, "half_batch")
    out, correct = run_job(cell)
    assert not correct, out.numbers
    assert out.numbers["route_flip"] == 1.0


def test_routes_that_name_an_expert_twice_are_not_correct(monkeypatch):
    """Every slot of a token on its first choice: each id is among the
    reference's top-k, so only the rule that a token names ``top_k``
    different experts catches it."""
    cell, reported = routed_cell(monkeypatch)
    reported.fault = "collapsed"
    out, correct = run_job(cell)
    assert not correct, out.numbers
    assert out.numbers["route_flip"] == 1.0
