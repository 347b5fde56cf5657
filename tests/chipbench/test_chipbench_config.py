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
import traffic
import weights
from reference import llama


def config(name):
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


CONFIGS = ["yi-6b", "yi-6b-train-stage", "granite-moe-3b-a800m-train-stage"]


def todays_arch(c):
    """The ``ArchConfig`` each configuration file has been run as."""
    from repro.configs.base import ArchConfig
    experts = c.get("num_local_experts", 0)
    return ArchConfig(
        name=c["name"], family="moe" if experts else "dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
        mlp="gated_silu", tie_embeddings=c["tie_word_embeddings"], n_experts=experts,
        top_k=c.get("num_experts_per_tok", 0),
        d_ff_expert=c["intermediate_size"] if experts else 0, source=c["source"])


@pytest.mark.parametrize("name", CONFIGS)
def test_todays_configs_map_to_todays_arch(name):
    assert bench.arch_of(config(name)) == todays_arch(config(name))


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
def test_an_unknown_or_unhonoured_key_stops_with_its_name(key, value):
    with pytest.raises(ValueError, match=key):
        bench.arch_of(dict(config("granite-moe-3b-a800m-train-stage"), **{key: value}))


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
    c = config("granite-moe-3b-a800m-train-stage")
    del c["capacity_factor"]
    with pytest.raises(ValueError, match="routes without drops"):
        bench.run_cfg(c)
    from repro.models import lm

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
        lambda p: llama.loss(p, tokens, labels, ones, llama.dims(c), "f32")))
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
