"""The splash flash-attention path of ``layers.attention``: its numbers
against the jnp path (Pallas interpret mode on the CPU), and the rule
that takes it (lowered for TPU, where it shows as a ``tpu_custom_call``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.kernels import splash
from repro.models.layers import attention

KEY = jax.random.PRNGKey(17)

# bf16: the two paths round scores and probabilities at different points
TOL = {jnp.bfloat16: dict(out=0.03, grad=0.02),
       jnp.float32: dict(out=2e-5, grad=1e-5)}


@pytest.mark.parametrize("nh,nkv", [(8, 2), (4, 4)], ids=["gqa4", "mha"])
@pytest.mark.parametrize("S", [256, 512])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_splash_matches_the_jnp_path(nh, nkv, S, dtype):
    hd = 128
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (1, S, nh, hd), dtype)
    k = jax.random.normal(ks[1], (1, S, nkv, hd), dtype)
    v = jax.random.normal(ks[2], (1, S, nkv, hd), dtype)
    do = jax.random.normal(ks[3], (1, S, nh, hd), dtype)
    kernel = lambda q, k, v: splash.causal_gqa(q, k, v, interpret=True)
    plain = lambda q, k, v: attention(q, k, v, causal=True, q_chunk=S // 2)
    out, vjp = jax.vjp(kernel, q, k, v)
    want, vjp_want = jax.vjp(plain, q, k, v)
    f32 = lambda a: np.asarray(a, np.float32)
    tol = TOL[dtype]
    assert out.dtype == dtype
    assert np.max(np.abs(f32(out) - f32(want))) <= tol["out"]
    for name, g, w in zip("qkv", vjp(do), vjp_want(do)):
        gap = np.linalg.norm(f32(g) - f32(w)) / np.linalg.norm(f32(w))
        assert gap <= tol["grad"], (name, gap)


def _kernel_calls(platform, S=256, nh=8, nkv=2, hd=128, **kw):
    """``tpu_custom_call``s in ``jax.grad`` of ``attention`` lowered for
    ``platform``."""
    def loss(q, k, v):
        return attention(q, k, v, **kw).astype(jnp.float32).sum()
    q = jax.ShapeDtypeStruct((1, S, nh, hd), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, S, nkv, hd), jnp.bfloat16)
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, kv, kv).lower(
        lowering_platforms=(platform,))
    return lowered.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("platform,kw,calls", [
    pytest.param("tpu", {}, 3, id="all-hold"),
    pytest.param("tpu", dict(S=384), 3, id="S384-128-blocks"),
    pytest.param("cpu", {}, 0, id="cpu"),
    pytest.param("tpu", dict(causal=False), 0, id="not-causal"),
    pytest.param("tpu", dict(window=64), 0, id="window"),
    pytest.param("tpu", dict(hd=64, nh=4, nkv=4), 0, id="head_dim-64"),
    pytest.param("tpu", dict(S=200), 0, id="odd-length"),
    pytest.param("tpu", "mesh", 0, id="mesh"),
])
def test_the_kernel_is_taken_only_where_every_condition_holds(platform, kw, calls):
    if kw == "mesh":
        kw = dict(mesh=Mesh(np.array(jax.devices()[:1]), ("data",)))
    assert _kernel_calls(platform, **kw) == calls


@pytest.mark.parametrize("S,want", [(128, 128), (384, 128), (512, 512), (1536, 512),
                                    (4096, 1024), (640, 128)])
def test_blocks_divide_the_sequence(S, want):
    blocks = splash.block_sizes(S)
    assert blocks.block_q == want
    sizes = (blocks.block_q, blocks.block_kv, blocks.block_kv_compute, blocks.block_q_dq,
             blocks.block_kv_dq, blocks.block_q_dkv, blocks.block_kv_dkv)
    assert all(S % b == 0 and b % splash.LANES == 0 for b in sizes)
    assert blocks.block_kv % blocks.block_kv_compute == 0
