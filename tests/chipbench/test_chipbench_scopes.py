"""Named scopes in the program's step programs, and their attribution in
a trace (``benchmarks/chip/scopes.py``): on compiled CPU programs, on
hand-made intervals and profiles, and on a small scoped trace recorded on
a TPU v5e (``benchmarks/chip/testdata/record_scoped_trace.py``)."""

import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from chipbench_paths import BENCH_DIR

import run
import scopes
import trace_reduce as tr

TRAIN = {"embed", "layer_scan", "attn_proj", "attention", "mlp", "head", "loss", "optimizer"}
DECODE = TRAIN - {"loss", "optimizer"} | {"kv_write"}
SCOPED = BENCH_DIR / "testdata" / "scoped_train5"


def compiled_scopes(arch_name: str, job: str) -> set:
    """The scopes in the op_name metadata of a tiny arch's compiled train
    or serve step."""
    from repro.configs import get_config
    from repro.launch.train import scale_arch
    from repro.models.lm import RunCfg, init_cache
    from repro.serving.serve import make_serve_step
    from repro.train.step import TrainCfg, init_train_state, make_train_step
    arch = scale_arch(get_config(arch_name), "tiny")
    cfg = TrainCfg(run=RunCfg(q_chunk=32))
    params, opt_state = jax.eval_shape(
        lambda: init_train_state(arch, cfg, jax.random.PRNGKey(0)))
    if job == "train":
        tokens = jax.ShapeDtypeStruct((1, 2, 64), jnp.int32)
        lowered = make_train_step(arch, cfg).lower(
            params, opt_state, {"tokens": tokens, "labels": tokens})
    else:
        cache = jax.eval_shape(lambda: init_cache(arch, 2, 64, cfg.run))
        lowered = make_serve_step(arch, cfg.run).lower(
            params, cache, jax.ShapeDtypeStruct((2,), jnp.int32), jnp.int32(3))
    return set(scopes.op_scopes(lowered.compile().as_text()).values()) - {scopes.UNSCOPED}


@pytest.mark.parametrize("arch_name, job, expected", [
    ("yi-6b", "train", TRAIN),
    ("yi-6b", "decode", DECODE),
    ("granite-moe-3b-a800m", "train", TRAIN - {"mlp"} | {"moe"}),
    ("mamba2-2.7b", "train", {"embed", "layer_scan", "ssm", "head", "loss", "optimizer"}),
])
def test_step_programs_carry_the_scopes(arch_name, job, expected):
    assert expected <= set(scopes.SCOPES)
    assert compiled_scopes(arch_name, job) == expected


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/jvp(layer_scan)/while/body/closed_call/attn_proj/attention/dot_general",
     "attention"),
    ("jit(train_step)/transpose(jvp(layer_scan))/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/mul", "mlp"),
    ("jit(train_step)/transpose(jvp(head))/dot_general", "head"),
    ("jit(train_step)/layer_scan/while/body/dynamic_slice", "layer_scan"),
    ("jit(serve_step)/layer_scan/while/body/attn_proj/kv_write/dynamic_update_slice",
     "kv_write"),
    ("jit(train_step)/optimizer/sqrt;jit(train_step)/loss/exp", "optimizer"),
    ("jit(train_step)/jvp()/iota", scopes.UNSCOPED),
    ("jit(train_step)/mlp_extra/mul", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
])
def test_scope_of_op_name(op_name, scope):
    assert scopes.scope_of(op_name) == scope


HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={()->f32[]}

%fused_computation (p: f32[]) -> f32[] {
  %p = f32[] parameter(0)
  ROOT %mul.4 = f32[] multiply(%p, %p), metadata={op_name="jit(step)/head/mul"}
}

ENTRY %main.9 () -> f32[] {
  %while.1 = (s32[]) while(%t), condition=%cond, body=%body, metadata={op_name="jit(step)/layer_scan/while" source_file="lm.py" source_line=3}
  %fusion.1 = f32[] fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(layer_scan))/while/body/attn_proj/attention/dot_general"}
  %fusion.2 = f32[] fusion(%b), kind=kOutput, calls=%f2, metadata={op_name="jit(step)/layer_scan/while/body/checkpoint/rematted_computation/mlp/mul"}
  ROOT %copy.3 = f32[] copy(%c)
}
"""


def test_op_scopes_of_hlo_text():
    assert scopes.module_name(HLO) == "jit_step"
    assert scopes.op_scopes(HLO) == {
        "p": scopes.UNSCOPED, "mul.4": "head", "while.1": "layer_scan",
        "fusion.1": "attention", "fusion.2": "mlp", "copy.3": scopes.UNSCOPED}
    assert scopes.instruction("%fusion.95 = bf16[4096]{0} fusion(%a), kind=kLoop") == "fusion.95"


def test_self_time_of_nested_intervals():
    """A while with body ops: each instant goes once, to the innermost op."""
    ops = [(0, 20), (1, 5), (2, 3), (7, 12), (12, 15), (30, 40), (35, 45)]
    assert scopes.self_times(ops) == [8, 3, 1, 5, 3, 5, 10]
    assert sum(scopes.self_times(ops)) == tr.total(tr.union(ops))
    assert scopes.self_times([(0, 4), (0, 10)]) == [4, 6]
    assert scopes.self_times([]) == []


def _profile(window, modules, ops):
    event = lambda name, a, b: SimpleNamespace(name=name, start_ns=a, duration_ns=b - a)
    line = lambda name, events: SimpleNamespace(name=name, events=[event(*e) for e in events])
    return SimpleNamespace(planes=[
        SimpleNamespace(name="/host:CPU", lines=[line("python", [(tr.WINDOW, *window)])]),
        SimpleNamespace(name="/device:TPU:0", lines=[line("XLA Modules", modules),
                                                     line("XLA Ops", ops)])])


def test_reduce_a_hand_made_trace():
    """Two whole executions count; one the window cuts does not; another
    program's ops and an instruction the text does not name are kept apart."""
    modules = [("jit_step(7)", 5, 25), ("jit_step(7)", 30, 50), ("jit_other(3)", 60, 70),
               ("jit_step(7)", 90, 110)]
    ops = [("%while.1 = (s32[]) while()", 5, 25), ("%fusion.1 = f32[] fusion()", 6, 10),
           ("%fusion.2 = f32[] fusion()", 12, 20), ("%copy.3 = f32[] copy()", 21, 22),
           ("%mystery.9 = f32[] copy()", 23, 24),
           ("%while.1 = (s32[]) while()", 30, 50), ("%fusion.1 = f32[] fusion()", 31, 35),
           ("%fusion.2 = f32[] fusion()", 36, 44), ("%copy.3 = f32[] copy()", 46, 47),
           ("%add.7 = f32[] add()", 60, 70), ("%fusion.1 = f32[] fusion()", 90, 105)]
    got = scopes.reduce(_profile((0, 100), modules, ops), HLO)
    ms = {k: v * 1e6 for k, v in got["ms"].items()}
    assert got["program"] == "jit_step" and got["steps"] == 2
    assert ms == pytest.approx({"mlp": 8, "layer_scan": 6.5, scopes.OTHER: 5, "attention": 4,
                                scopes.UNSCOPED: 1.5})
    assert list(ms) == sorted(ms, key=lambda k: -ms[k])
    assert got["busy_ms"] * 1e6 == pytest.approx(20)
    assert got["unmatched_ms"] * 1e6 == pytest.approx(0.5)
    top = {k: [[op, t * 1e6] for op, t in v] for k, v in got["top_ops"].items()}
    assert list(top) == [k for k in ms if k != scopes.OTHER]
    assert top["mlp"] == [["fusion.2", pytest.approx(8)]]
    assert top[scopes.UNSCOPED] == [["copy.3", pytest.approx(1)], ["mystery.9", pytest.approx(0.5)]]


def test_reduce_needs_scopes_and_a_whole_execution():
    modules = [("jit_step(7)", 5, 25)]
    ops = [("%fusion.1 = f32[] fusion()", 6, 10)]
    unscoped = re.sub(r'op_name="[^"]*"', 'op_name="jit(step)/mul"', HLO)
    assert scopes.reduce(_profile((0, 100), modules, ops), unscoped) is None
    assert scopes.reduce(_profile((0, 20), modules, ops), HLO) is None
    assert scopes.reduce(_profile((0, 100), modules, ops), HLO)["steps"] == 1


@pytest.fixture(scope="module")
def scoped():
    profile = tr.load(str(SCOPED.with_suffix(".xplane.pb")))
    return profile, scopes.reduce(profile, SCOPED.with_suffix(".hlo.txt").read_text())


def test_recorded_scopes_add_up_to_busy_time(scoped):
    _, got = scoped
    assert got["program"] == "jit_train_step" and got["steps"] == 4
    stepped = sum(v for k, v in got["ms"].items() if k != scopes.OTHER)
    assert stepped == pytest.approx(got["busy_ms"], rel=1e-6)
    assert got["unmatched_ms"] == 0
    for scope in ("attention", "mlp", "head", "optimizer"):
        assert got["ms"][scope] > 0, scope


def test_recorded_step_busy_time_is_inside_the_window(scoped):
    profile, got = scoped
    whole = tr.reduce(profile, ["dispatch", "loss_fetch"])
    assert got["busy_ms"] * got["steps"] <= whole["busy_s"] * 1e3 * (1 + 1e-9)


@pytest.fixture(scope="module")
def run_reduced():
    profile = tr.load(str(SCOPED.with_suffix(".xplane.pb")))
    return run.reduce_trace(profile, ["dispatch", "loss_fetch"],
                            SCOPED.with_suffix(".hlo.txt").read_text())


def test_breakdown_scopes_add_up_to_the_step_busy_time(run_reduced):
    got = run.breakdown(run_reduced)
    assert list(got) == ["device_ops", "idle_gaps", "scopes", "step_busy_ms", "scope_steps"]
    ms = got["scopes"]
    assert list(ms) == sorted(ms, key=lambda k: -ms[k])
    stepped = sum(v for k, v in ms.items() if k != scopes.OTHER)
    assert stepped == pytest.approx(got["step_busy_ms"], rel=1e-6)
    assert got["step_busy_ms"] * got["scope_steps"] <= run_reduced["busy_s"] * 1e3 * (1 + 1e-9)


def test_no_hlo_text_no_scopes():
    profile = tr.load(str(SCOPED.with_suffix(".xplane.pb")))
    reduced = run.reduce_trace(profile, ["dispatch", "loss_fetch"], None)
    assert reduced["scopes"] is None
    assert list(run.breakdown(reduced)) == ["device_ops", "idle_gaps"]


SCOPE_READERS = {
    "attention_ms.train": ["attention"], "mlp_ms.train": ["mlp"],
    "head_loss_ms.train": ["head", "loss"], "optimizer_ms.train": ["optimizer"],
    "unscoped_ms.train": [scopes.UNSCOPED], "attention_ms.decode": ["attention"],
    "mlp_ms.decode": ["mlp"], "layer_scan_ms.decode": ["layer_scan"],
    "unscoped_ms.decode": [scopes.UNSCOPED],
}


def reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(name, BENCH_DIR / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("name", SCOPE_READERS)
def test_scope_readers_read_only_scopes(name, run_reduced):
    read = reader(name)
    want = sum(run_reduced["scopes"]["ms"][s] for s in SCOPE_READERS[name])
    assert want > 0
    assert read({"trace": run_reduced}) == pytest.approx(want, rel=1e-12)
    assert read({"trace": None}) is None
    assert read({"trace": dict(run_reduced, scopes=None)}) is None
    assert read({"trace": dict(run_reduced, scopes={"ms": {"embed": 1.0}})}) is None


def test_reduce_keeps_its_readings_on_the_matmul_trace():
    """The scope attribution leaves ``trace_reduce.reduce`` as it was."""
    got = tr.reduce(tr.load(str(BENCH_DIR / "testdata" / "matmul20.xplane.pb")),
                    ["dispatch", "token_fetch"])
    assert got["busy_s"] == pytest.approx(0.002180621, rel=1e-9)
    assert got["window_s"] == pytest.approx(0.141466108, rel=1e-9)
    assert got["idle_share"] == pytest.approx(0.9845855588251569, rel=1e-9)
    assert got["exposed_collective_share"] == 0.0
    assert got["device_ops"][0][0].startswith("%fusion = bf16[2048,2048]")
    assert got["device_ops"][0][1] == pytest.approx(0.00172884, rel=1e-9)
    assert dict(got["idle_gaps"]) == pytest.approx(
        {"token_fetch": 0.134244028, "dispatch": 0.004373445, "(no span)": 0.000668014})
