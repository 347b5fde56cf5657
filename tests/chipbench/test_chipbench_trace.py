"""The trace reduction on a small trace recorded on a TPU v5e
(``benchmarks/chip/testdata/record_trace.py``), and its interval
arithmetic on hand-made cases."""

import numpy as np
import pytest

from chipbench_paths import BENCH_DIR

import trace_reduce as tr

TRACE = BENCH_DIR / "testdata" / "matmul20.xplane.pb"


@pytest.fixture(scope="module")
def profile():
    return tr.load(str(TRACE))


@pytest.fixture(scope="module")
def reduced(profile):
    return tr.reduce(profile, ["dispatch", "token_fetch"])


def test_finds_one_device_and_the_window(profile):
    assert [p.name for p in tr.device_planes(profile)] == ["/device:TPU:0"]
    windows = tr.host_spans(profile, [tr.WINDOW])
    assert len(windows) == 1
    spans = tr.host_spans(profile, ["dispatch", "token_fetch"])
    assert sum(1 for s in spans if s[0] == "dispatch") == 20
    _, lo, hi = windows[0]
    assert all(lo <= a <= b <= hi for _, a, b in spans)


def test_busy_union_matches_a_sampled_timeline(profile, reduced):
    """Busy time by interval union against a 10 ns sampled timeline."""
    _, lo, hi = tr.host_spans(profile, [tr.WINDOW])[0]
    ops = tr.op_events(tr.device_planes(profile)[0])
    grid = np.zeros(int((hi - lo) / 10) + 1, bool)
    for _, a, b in ops:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            grid[int((a - lo) / 10):int((b - lo) / 10)] = True
    assert reduced["busy_s"] == pytest.approx(grid.sum() * 10e-9, rel=1e-3)
    assert reduced["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["idle_share"] == pytest.approx(1 - reduced["busy_s"] / reduced["window_s"])


def test_idle_gaps_add_up_to_idle_time(reduced):
    idle = sum(s for _, s in reduced["idle_gaps"])
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert {n for n, _ in reduced["idle_gaps"]} <= {"dispatch", "token_fetch", "(no span)"}


def test_top_ops_are_the_step(reduced):
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= tr.TOP
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    assert sum(s for _, s in ops) <= reduced["busy_s"] * (1 + 1e-6) + 1e-9
    assert any("fusion" in n or "convolution" in n or "dot" in n for n, _ in ops)
    assert reduced["exposed_collective_share"] == 0.0


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.gaps([(1, 2), (4, 6)], 0, 7) == [(0, 1), (2, 4), (6, 7)]
    assert tr.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.total([(0, 3), (5, 9)]) == 7


def test_exposed_collective_time():
    """A collective that overlaps compute is hidden only where compute runs."""
    coll = tr.union([(0, 10), (20, 30)])
    other = tr.union([(5, 12), (22, 24)])
    assert tr.subtract(coll, other) == [(0, 5), (20, 22), (24, 30)]
    assert tr.total(tr.subtract(coll, other)) == 13
    assert tr.is_collective("all-reduce.3") and tr.is_collective("all-gather-start")
    assert not tr.is_collective("fusion.12")
