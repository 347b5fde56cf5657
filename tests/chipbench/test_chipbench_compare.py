"""The numbers that decide ``correct``, on hand-made readings: the
training gaps, the served-token gaps, and the judgement against limits."""

import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_paths  # noqa: F401  (puts the benchmark on sys.path)

import bench
from jobs import decode, train


def _train_reading(grad):
    return {"losses": [2.0, 1.5, 1.25], "grad": grad, "change": {"a": 1.0, "b": 2.0}}


def test_grad_diff_sees_a_turned_gradient_that_the_norm_gap_misses():
    rng = np.random.default_rng(0)
    ref = {"a": rng.normal(size=256).astype(np.float32),
           "b": rng.normal(size=64).astype(np.float32)}
    turned = {"a": ref["a"][::-1].copy(), "b": ref["b"].copy()}
    got = train.compare(_train_reading(turned), _train_reading(ref))
    assert got["grad_gap"] == pytest.approx(0.0, abs=1e-6)
    assert got["grad_diff"] > 0.5
    same = train.compare(_train_reading(ref), _train_reading(ref))
    assert same == {"loss_gap": 0.0, "grad_gap": 0.0, "grad_diff": 0.0, "change_gap": 0.0}


def test_grad_diff_of_a_state_left_unchanged_reads_one():
    ref = {"a": np.full(16, 3.0, np.float32), "b": np.full(16, 1.0, np.float32)}
    zero = {k: np.zeros_like(v) for k, v in ref.items()}
    assert train.compare(_train_reading(zero), _train_reading(ref))["grad_diff"] == 1.0


def test_gaps_of_each_choice_below_the_best():
    logits = jnp.array([[0.0, 2.0, 1.0], [3.0, 0.0, 2.5]])
    gaps = np.asarray(decode._gaps(logits, jnp.array([[1, 2], [0, 0]])))
    assert gaps.tolist() == [[0.0, 0.5], [2.0, 0.0]]
    assert decode.gap_numbers(gaps[:1]) == {"served_mean_gap": 0.25, "served_gap": 0.5}


def test_each_position_is_judged_by_the_choice_after_it():
    contexts = np.array([[1, 2, 3], [4, 5, 6]])
    filled = np.array([[2, 3, 9], [5, 6, 9]])
    served = [np.array([[10, 11], [12, 13]])]
    seqs, chosen = decode.judged(contexts, np.array([[7, 8]]), filled, served, seed=1)
    assert seqs.tolist() == [[1, 2, 3, 7, 10], [4, 5, 6, 8, 12]]
    assert chosen.tolist() == [[2, 3, 9, 10, 11], [5, 6, 9, 12, 13]]


def test_judge_compares_only_numbers_with_a_limit():
    ok, checks = bench.judge({"x": 0.5, "y": 9.0}, {"x": 1.0})
    assert ok and checks["y"] == {"value": 9.0, "limit": None}
    assert not bench.judge({"x": 1.5}, {"x": 1.0})[0]
    assert not bench.judge({"x": float("nan")}, {"x": 1.0})[0]
    assert not bench.judge({"x": 0.5}, None)[0]
