"""Operation and byte counts of ``work.py`` against hand counts, and the
table of peaks."""

import json

import pytest

from chipbench_paths import BENCH_DIR

import bench
import work


def config(name):
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


def test_yi_train_flops_per_token_hand_count():
    # one layer: q and o 4096x4096 each, k and v 4096x512 each, three
    # 4096x11008 MLP matrices; the 4096x64000 head; attention 12 L n_h d S
    layer = 4096 * 4096 * 2 + 4096 * 512 * 2 + 3 * 4096 * 11008
    n = layer + 4096 * 64000
    assert n == 435_159_040
    want = 6 * n + 12 * 1 * 32 * 128 * 4096
    assert work.train_flops_per_token(config("yi-6b-train-stage"), 4096) == want


def test_granite_train_flops_count_routed_experts_only():
    # 8 layers: attention 1536x1536 (q, o) and 1536x512 (k, v); 8 of 40
    # experts of three 1536x512 matrices each, plus the 1536x40 router
    layer = 1536 * 1536 * 2 + 1536 * 512 * 2 + 8 * 3 * 1536 * 512 + 1536 * 40
    n = 8 * layer + 1536 * 49155
    assert n == 277_320_192
    want = 6 * n + 12 * 8 * 24 * 64 * 1024
    assert work.train_flops_per_token(
        config("granite-moe-3b-a800m-train-stage"), 1024) == want


def test_yi_decode_step_work_hand_count():
    # 32 layers; a turn of 256 from a 1024-token context attends to 1152.5
    # positions on average; bf16 weights and cache
    layer = 4096 * 4096 * 2 + 4096 * 512 * 2 + 3 * 4096 * 11008
    n = 32 * layer + 4096 * 64000
    live = 1024 + 257 / 2
    flops = 16 * (2 * n + 4 * 32 * 32 * 128 * live)
    weight_bytes = 2 * (n + 65 * 4096 + 16 * 4096)
    kv_bytes = 32 * 16 * 2 * 4 * 128 * 2 * (live + 1)
    got = work.decode_step_work(config("yi-6b"), 16, 1024, 256)
    assert got["flops"] == pytest.approx(flops, rel=1e-12)
    assert got["bytes"] == pytest.approx(weight_bytes + kv_bytes, rel=1e-12)


def test_peaks_known_kind():
    v5e = bench.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["ici_bits_per_s"] == 1600e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5"])
def test_peaks_unknown_kind_raises(kind):
    with pytest.raises(KeyError):
        bench.load_peaks(kind)
