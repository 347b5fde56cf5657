"""Per-arch smoke tests (spec-required): every assigned architecture at a
REDUCED config runs one forward/train step on CPU with finite outputs and
correct shapes; decode matches teacher-forced forward (strong AR-cache
correctness check)."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, SHAPES, shape_applicable
from repro.launch.train import scale_arch
from repro.models import (
    RunCfg,
    decode_step,
    forward,
    init_cache,
    init_params,
    loss_fn,
)
from repro.serving.serve import make_serve_step

CFG = RunCfg(q_chunk=0, remat=False)
KEY = jax.random.PRNGKey(0)


def _batch(arch, B=2, S=32, key=KEY):
    if arch.embeds_input:
        return {"embeds": jax.random.normal(key, (B, S, arch.d_model)),
                "labels": jnp.zeros((B, S), jnp.int32)}
    return {"tokens": jax.random.randint(key, (B, S), 0, arch.vocab),
            "labels": jnp.zeros((B, S), jnp.int32)}


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_arch_smoke_forward_and_grad(name):
    arch = scale_arch(get_config(name), "tiny")
    params = init_params(arch, KEY, CFG)
    batch = _batch(arch)
    logits, aux = jax.jit(lambda p, b: forward(
        arch, p, tokens=b.get("tokens"), embeds=b.get("embeds"), cfg=CFG))(params, batch)
    assert logits.shape == (2, 32, arch.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    loss, metrics = loss_fn(arch, params, batch, CFG)
    assert jnp.isfinite(loss)
    grads = jax.grad(lambda p: loss_fn(arch, p, batch, CFG)[0])(params)
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("name,window", [
    *(pytest.param(n, 0, id=n) for n in ["yi-6b", "granite-moe-3b-a800m", "mamba2-2.7b",
                                          "hymba-1.5b", "llava-next-34b"]),
    pytest.param("hymba-1.5b", 5, id="hymba-1.5b-window5"),
    pytest.param("yi-6b", 5, id="yi-6b-window5"),
])
def test_decode_matches_teacher_forced_forward(name, window):
    """decode_step over a prompt must reproduce forward()'s next-token
    logits at every position (KV cache + SSM state correctness).
    ``window`` 0 is full attention; a window shorter than the 12-token
    prompt makes the ring buffer's slot wrap, so a stale entry would show.
    MoE capacity is batch-dependent, so use a drop-free capacity factor —
    with drops, decode-vs-forward divergence is expected MoE semantics."""
    arch = dataclasses.replace(scale_arch(get_config(name), "tiny"), window=window)
    cfg = dataclasses.replace(CFG, capacity_factor=8.0)
    params = init_params(arch, KEY, cfg)
    B, S = 2, 12
    if arch.embeds_input:
        embeds = jax.random.normal(KEY, (B, S, arch.d_model))
        ref_logits, _ = forward(arch, params, embeds=embeds, cfg=cfg)
    else:
        tokens = jax.random.randint(KEY, (B, S), 0, arch.vocab)
        ref_logits, _ = forward(arch, params, tokens=tokens, cfg=cfg)

    cache = init_cache(arch, B, S + 4, cfg)
    if window:
        assert cache["k"].shape[2] == window < S
    outs = []
    for t in range(S):
        if arch.embeds_input:
            lg, cache = decode_step(arch, params, cache, embeds=embeds[:, t],
                                    pos=jnp.int32(t), cfg=cfg)
        else:
            lg, cache = decode_step(arch, params, cache, tokens=tokens[:, t],
                                    pos=jnp.int32(t), cfg=cfg)
        outs.append(lg)
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec_logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               rtol=2e-2, atol=2e-2)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
def test_serve_step_writes_the_kv_cache_in_place(scan):
    """The serve step carries the layer-stacked K/V cache through the
    layers and writes one position a layer into it: no stacked scan
    output and no copy has the cache's shape, and the compiled step
    aliases the donated cache to its output. Two donated steps give the
    logits and cache of the undonated decode_step, and change the cache
    only at the two positions written."""
    arch = scale_arch(get_config("yi-6b"), "tiny")
    # an f32 cache: the CPU backend computes a bf16 update in f32 and copies
    # the carry for it; test_tpu_compile.py checks the bf16 cache on a v5e
    cfg = dataclasses.replace(CFG, scan_layers=scan, compute_dtype=jnp.float32)
    params = init_params(arch, KEY, cfg)
    B, span, pos = 3, 8, 5
    cache = {n: jax.random.normal(jax.random.PRNGKey(i + 1), a.shape, a.dtype)
             for i, (n, a) in enumerate(init_cache(arch, B, span, cfg).items())}
    shape = cache["k"].shape
    assert shape == (arch.num_layers, B, span, arch.n_kv, arch.head_dim)
    tokens = jax.random.randint(KEY, (2, B), 0, arch.vocab)
    serve = make_serve_step(arch, cfg)

    jaxpr = jax.make_jaxpr(serve)(params, cache, tokens[0], jnp.int32(pos)).jaxpr
    scans = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"]
    assert len(scans) == int(scan)
    assert all(v.aval.shape != shape
               for e in scans for v in e.outvars[e.params["num_carry"]:])
    makers = {e.primitive.name for e in _eqns(jaxpr)
              if any(getattr(v.aval, "shape", None) == shape for v in e.outvars)}
    assert makers <= {"dynamic_update_slice", "layout_constraint", "scan", "jit"}, makers

    hlo = serve.lower(params, cache, tokens[0], jnp.int32(pos)).compile().as_text()
    n = len(jax.tree.leaves(params))
    assert f"{{2}}: ({n}, {{}}, may-alias), {{3}}: ({n + 1}, {{}}, may-alias)" in hlo
    full = "f32[" + ",".join(map(str, shape)) + "]"
    assert not [line for line in hlo.splitlines() if full in line and " copy(" in line]

    undonated = jax.jit(partial(decode_step, arch, cfg=cfg))
    want, got = cache, jax.tree.map(jnp.copy, cache)
    for t in range(2):
        want_logits, want = undonated(params, want, tokens=tokens[t], pos=jnp.int32(pos + t))
        _, got_logits, got = serve(params, got, tokens[t], jnp.int32(pos + t))
        np.testing.assert_array_equal(np.asarray(got_logits), np.asarray(want_logits))
    for n in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got[n]), np.asarray(want[n]))
        changed = np.any(np.asarray(got[n] != cache[n]), axis=(0, 1, 3, 4))
        assert np.flatnonzero(changed).tolist() == [pos, pos + 1]


def test_hubert_encoder_no_decode():
    arch = get_config("hubert-xlarge")
    ok, reason = shape_applicable(arch, SHAPES["decode_32k"])
    assert not ok and "encoder" in reason


def test_long_500k_applicability():
    assert shape_applicable(get_config("mamba2-2.7b"), SHAPES["long_500k"])[0]
    assert shape_applicable(get_config("hymba-1.5b"), SHAPES["long_500k"])[0]
    for name in ("yi-6b", "nemotron-4-340b", "dbrx-132b", "llava-next-34b"):
        ok, reason = shape_applicable(get_config(name), SHAPES["long_500k"])
        assert not ok and "sub-quadratic" in reason


def test_param_count_estimates_match_init():
    for name in sorted(ARCHS):
        arch = scale_arch(get_config(name), "tiny")
        params = init_params(arch, KEY, CFG)
        actual = sum(x.size for x in jax.tree.leaves(params))
        est = arch.param_count()
        assert abs(actual - est) / actual < 0.12, (name, actual, est)


def test_moe_load_stats_exposed():
    arch = scale_arch(get_config("granite-moe-3b-a800m"), "tiny")
    params = init_params(arch, KEY, CFG)
    batch = _batch(arch)
    loss, metrics = loss_fn(arch, params, batch, CFG)
    assert "moe_drop" in metrics
    assert 0.0 <= float(metrics["moe_drop"]) <= 1.0


def test_sliding_window_matches_full_when_window_covers():
    arch = scale_arch(get_config("hymba-1.5b"), "tiny")
    big_window = dataclasses.replace(arch, window=64)   # covers S=32
    params = init_params(big_window, KEY, CFG)
    batch = _batch(big_window)
    lg_w, _ = forward(big_window, params, tokens=batch["tokens"], cfg=CFG)
    full = dataclasses.replace(big_window, window=0)
    lg_f, _ = forward(full, params, tokens=batch["tokens"], cfg=CFG)
    np.testing.assert_allclose(np.asarray(lg_w), np.asarray(lg_f),
                               rtol=1e-4, atol=1e-4)
