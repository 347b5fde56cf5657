"""A configuration file carries its architecture: every key reaches the
program's ``ArchConfig`` or stops the run (``bench.arch_of``), its run
settings reach ``RunCfg`` (``bench.run_cfg``), and it names the reference
module that judges it (``bench.reference_of``). The llama reference's
Granite equations, tied head and routing without drops, on the CPU."""

import dataclasses
import json
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_paths import BENCH_DIR

import bench
import calibrate
import traffic
import weights
from reference import llama


def config(name):
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


CONFIGS = ["yi-6b", "yi-6b-train-stage", "granite-moe-3b-a800m-train-stage"]


def todays_fields(c):
    """The ``ArchConfig`` fields each configuration file has been run with."""
    experts = c.get("num_local_experts", 0)
    return dict(
        name=c["name"], family="moe" if experts else "dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
        mlp="gated_silu", tie_embeddings=c["tie_word_embeddings"], n_experts=experts,
        top_k=c.get("num_experts_per_tok", 0),
        d_ff_expert=c["intermediate_size"] if experts else 0, source=c["source"])


def todays_arch(c):
    from repro.configs.base import ArchConfig
    return ArchConfig(**todays_fields(c))


@pytest.mark.parametrize("name", CONFIGS)
def test_todays_configs_map_to_todays_arch(name):
    """The fields each file has been run with are as before; every other
    field holds the value it takes when it is not given, or the file's
    value under the field's own name (a field the program gains)."""
    c = config(name)
    arch, today = bench.arch_of(c), todays_arch(c)
    given = todays_fields(c)
    for f in dataclasses.fields(arch):
        value = getattr(arch, f.name)
        if f.name in given:
            assert value == getattr(today, f.name), f.name
        else:
            assert value == getattr(today, f.name) or value == c.get(f.name), f.name


def arch_without(monkeypatch, name):
    """A stand-in ``ArchConfig``: the program's fields less ``name``."""
    from repro.configs import base
    real = base.ArchConfig
    kept = [(f.name, f.type, dataclasses.field(default=f.default,
                                               default_factory=f.default_factory))
            for f in dataclasses.fields(real) if f.name != name]
    stand_in = dataclasses.make_dataclass("ArchConfig", kept, frozen=True,
                                          namespace={"__post_init__": real.__post_init__})
    monkeypatch.setattr(base, "ArchConfig", stand_in)


def shapes_with_head(monkeypatch, head: bool):
    """Stand-in parameter shapes: the program's, with or without a
    separate ``['lm_head']`` whatever the model ties."""
    real = weights.param_shapes

    def shapes(arch, dtype):
        out = {p: v for p, v in real(arch, dtype).items() if p != "['lm_head']"}
        if head:
            out["['lm_head']"] = ((arch.d_model, arch.vocab), dtype)
        return out

    monkeypatch.setattr(weights, "param_shapes", shapes)


@pytest.mark.parametrize("key, value", [
    ("frobnication_factor", 2.0),
    ("rope_theta", 5e6),
    ("rms_norm_eps", 1e-6),
    ("embedding_multiplier", 12.0),
    ("attention_multiplier", 1 / 64),
    ("residual_multiplier", 0.22),
    ("logits_scaling", 6.0),
    ("hidden_act", "gelu"),
    ("tie_word_embeddings", True),
])
def test_an_unknown_or_unhonoured_key_stops_with_its_name(monkeypatch, key, value):
    """A key with no field and a value other than the plain one stops the
    run, whatever fields the program has: each case runs against a
    program without the key's field. A tied head stops the run while the
    program's parameters keep a separate ``lm_head``."""
    if key == "tie_word_embeddings":
        shapes_with_head(monkeypatch, head=True)
    else:
        arch_without(monkeypatch, bench.PUBLISHED_NAMES.get(key, key))
    with pytest.raises(ValueError, match=key):
        bench.arch_of(dict(config("granite-moe-3b-a800m-train-stage"), **{key: value}))


def test_a_tied_head_passes_where_the_program_keeps_no_lm_head(monkeypatch):
    shapes_with_head(monkeypatch, head=False)
    c = dict(config("granite-moe-3b-a800m-train-stage"), tie_word_embeddings=True)
    assert bench.arch_of(c).tie_embeddings


GRANITE_KEYS = {"rms_norm_eps": 1e-6, "embedding_multiplier": 12.0,
                "attention_multiplier": 1 / 64, "residual_multiplier": 0.22,
                "logits_scaling": 6.0}


@pytest.mark.parametrize("key", GRANITE_KEYS)
def test_a_granite_key_reaches_the_programs_field(key):
    """Where the program has the key's field, the published value reaches it."""
    from repro.configs.base import ArchConfig
    if key not in {f.name for f in dataclasses.fields(ArchConfig)}:
        pytest.skip(f"the program's ArchConfig has no {key}")
    c = dict(config("granite-moe-3b-a800m-train-stage"), **{key: GRANITE_KEYS[key]})
    assert getattr(bench.arch_of(c), key) == GRANITE_KEYS[key]


def test_plain_values_and_program_fields_pass():
    c = dict(config("yi-6b"), attention_multiplier=128 ** -0.5, logits_scaling=1,
             rope_theta=10000, sliding_window=512, causal=True)
    arch = bench.arch_of(c)
    assert arch.window == 512 and arch.causal
    assert dataclasses.replace(arch, window=0) == todays_arch(c)
    relu2 = bench.arch_of(dict(c, hidden_act="relu2"))
    assert relu2.mlp == "squared_relu"


def test_a_field_the_program_gains_is_passed_by_its_name(monkeypatch):
    from repro.configs import base

    @dataclasses.dataclass(frozen=True)
    class Granite(base.ArchConfig):
        embedding_multiplier: float = 1.0

    monkeypatch.setattr(base, "ArchConfig", Granite)
    arch = bench.arch_of(dict(config("granite-moe-3b-a800m-train-stage"),
                              embedding_multiplier=12.0))
    assert arch.embedding_multiplier == 12.0


def test_run_settings_reach_the_program():
    assert bench.run_cfg(config("granite-moe-3b-a800m-train-stage")).capacity_factor == 1.25
    dense = bench.run_cfg(config("yi-6b"), param_dtype=jnp.bfloat16)
    assert dense.param_dtype == jnp.bfloat16


def test_routing_without_drops_needs_the_program_to_have_it(monkeypatch):
    """Against a program whose capacity factor is a plain float, a file
    without one stops the run; against one that takes None, None passes."""
    c = config("granite-moe-3b-a800m-train-stage")
    del c["capacity_factor"]
    from repro.models import lm

    @dataclasses.dataclass(frozen=True)
    class Dropping:
        capacity_factor: float = 1.25

    monkeypatch.setattr(lm, "RunCfg", Dropping)
    with pytest.raises(ValueError, match="routes without drops"):
        bench.run_cfg(c)

    @dataclasses.dataclass(frozen=True)
    class Dropless:
        capacity_factor: Optional[float] = 1.25

    monkeypatch.setattr(lm, "RunCfg", Dropless)
    assert bench.run_cfg(c).capacity_factor is None


def test_reference_is_the_module_the_file_names():
    for name in CONFIGS:
        module = bench.reference_of(config(name))
        assert module.__file__ == llama.__file__
        assert bench.reference_of(config(name)) is module
    with pytest.raises(ValueError, match="outside"):
        bench.reference_of({"reference": "../../src/repro/models/lm.py"})


# ---------------------------------------------------------------------------
# The reference's equations, at a tiny size on the CPU
# ---------------------------------------------------------------------------

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)
TINY_MOE = dict(TINY, intermediate_size=32, num_local_experts=8, num_experts_per_tok=2,
                attention_multiplier=0.25)
SEED = 2 ** 31 + 5


def tiny(name, **changes):
    return dict(config(name), **dict(TINY_MOE if "granite" in name else TINY, **changes))


def params_of(c):
    flat = weights.flatten(weights.generate(bench.arch_of(c), weights.seed_key(SEED),
                                            jnp.float32))
    return {k: np.asarray(v) for k, v in flat.items()}


def loss_and_grads(c, params):
    rows = traffic.train_rows(SEED, 0, c["vocab_size"], 32, 2)
    tokens, labels = rows["tokens"][0], rows["labels"][0]
    ones = np.ones(tokens.shape, np.float32)
    f = jax.jit(jax.value_and_grad(
        lambda p: llama.loss(p, tokens, labels, ones, llama.dims(c), "f32")[0]))
    value, grads = f({k: jnp.asarray(v) for k, v in params.items()})
    return float(value), {k: np.asarray(g) for k, g in grads.items()}


@pytest.mark.parametrize("name", ["yi-6b-train-stage", "granite-moe-3b-a800m-train-stage"])
def test_plain_multipliers_leave_the_loss_bit_for_bit(name):
    c = tiny(name)
    for key in ("embedding_multiplier", "residual_multiplier", "logits_scaling",
                "attention_multiplier"):
        c.pop(key, None)
    plain = dict(c, embedding_multiplier=1.0, residual_multiplier=1.0, logits_scaling=1.0,
                 attention_multiplier=16 ** -0.5)
    params = params_of(c)
    value, grads = loss_and_grads(c, params)
    value_plain, grads_plain = loss_and_grads(plain, params)
    assert value == value_plain
    assert all(np.array_equal(grads[k], grads_plain[k]) for k in grads)
    granite = dict(plain, embedding_multiplier=12.0, residual_multiplier=0.22,
                   attention_multiplier=1 / 16)
    assert loss_and_grads(granite, params)[0] != value


def test_a_tied_head_is_the_embedding_transposed():
    c = tiny("yi-6b-train-stage")
    params = params_of(c)
    tied = {k: v for k, v in params.items() if k != "['lm_head']"}
    untied = dict(tied, **{"['lm_head']": tied["['embed']"].T.copy()})
    value_tied, g_tied = loss_and_grads(dict(c, tie_word_embeddings=True), tied)
    value, g = loss_and_grads(c, untied)
    assert value_tied == pytest.approx(value, rel=1e-6)
    np.testing.assert_allclose(g_tied["['embed']"], g["['embed']"] + g["['lm_head']"].T,
                               rtol=1e-4, atol=1e-7)


def test_logits_scaling_divides_the_head():
    c = tiny("yi-6b-train-stage")
    params = params_of(c)
    scaled = dict(params, **{"['lm_head']": params["['lm_head']"] / 6.0})
    assert loss_and_grads(dict(c, logits_scaling=6.0), params)[0] == pytest.approx(
        loss_and_grads(c, scaled)[0], rel=1e-6)


def test_no_capacity_factor_keeps_every_assignment():
    c = tiny("granite-moe-3b-a800m-train-stage")
    dropless = {k: v for k, v in c.items() if k != "capacity_factor"}
    params = params_of(c)
    value, grads = loss_and_grads(dropless, params)
    value_all, grads_all = loss_and_grads(dict(c, capacity_factor=8 / 2), params)
    assert value == value_all
    assert all(np.array_equal(grads[k], grads_all[k]) for k in grads)
    assert loss_and_grads(c, params)[0] != value       # 1.25 drops some at this size


# The loss and a sha256 of the gradients (float32 bytes, leaves in sorted
# order) of the tiny expert model below, routing freely, as the reference
# computed them on the CPU before it could hold routes.
FREE_ROUTING_LOSS = 5.557982921600342
FREE_ROUTING_GRADS = "d37ca00e859a9fabc9484b08643a09a710c7df1da7c5aa23a1e566060855ed06"


def test_free_routing_keeps_todays_arithmetic():
    import hashlib
    c = tiny("granite-moe-3b-a800m-train-stage")
    value, grads = loss_and_grads(c, params_of(c))
    digest = hashlib.sha256()
    for k in sorted(grads):
        digest.update(k.encode())
        digest.update(np.ascontiguousarray(grads[k], np.float32).tobytes())
    assert value == FREE_ROUTING_LOSS
    assert digest.hexdigest() == FREE_ROUTING_GRADS


def test_held_routes_keep_its_own_choices_and_count_the_others():
    """Held to its own choices, by ids below 0 or by the ids themselves, the
    reference computes what it computes routing freely, bit for bit, and
    reads ``route_flip`` 0; with one slot of every token moved to an
    expert it did not choose, it reads 1/k."""
    c = tiny("granite-moe-3b-a800m-train-stage")
    params = {k: jnp.asarray(v) for k, v in params_of(c).items()}
    rows = traffic.train_rows(SEED, 0, c["vocab_size"], 32, 2)
    tokens, labels = rows["tokens"][0], rows["labels"][0]
    ones = np.ones(tokens.shape, np.float32)
    dm = llama.dims(c)
    held = jax.jit(jax.value_and_grad(
        lambda p, r: llama.loss(p, tokens, labels, ones, dm, "f32", routes=r), has_aux=True))
    (value, (own, flip)), grads = held(params, None)
    assert float(flip) == 0
    (value_own, (own_own, flip)), grads_own = held(params, -np.ones((2, 64, 2), np.int32))
    assert value_own == value and float(flip) == 0 and np.array_equal(own_own, own)
    assert all(np.array_equal(grads[k], grads_own[k]) for k in grads)
    (value_ids, (own_ids, flip)), grads_ids = held(params, own)
    assert value_ids == value and float(flip) == 0 and np.array_equal(own_ids, own)
    assert all(np.array_equal(grads[k], grads_ids[k]) for k in grads)
    moved = calibrate.wrong_slot(np.asarray(own), 8, SEED)
    assert not np.any(moved[..., -1:] == np.asarray(own), axis=-1).any()
    assert held(params, moved)[0][0] != value
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    layer = llama.layer_params(params, 0)["moe"]
    _, own0, flip = llama.moe(x, layer, dm, "f32", -np.ones((64, 2), np.int32))
    _, _, flip_moved = llama.moe(x, layer, dm, "f32",
                                 calibrate.wrong_slot(np.asarray(own0), 8, SEED))
    assert float(flip) == 0 and float(flip_moved) == 0.5
