"""Each per-layer metric reader on a hand-made run record: the number it
gives, and nothing where there is nothing to read."""

import importlib.util

import pytest

from chipbench_paths import BENCH_DIR

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    spec = importlib.util.spec_from_file_location(name, BENCH_DIR / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


TRAIN = {"steps": 50, "window_s": 10.0, "tokens_per_s": 20480.0,
         "spans": {"data_wait": 0.05, "dispatch": 0.5, "loss_fetch": 9.0},
         "flops_per_token": 2.8e9, "chips": 1, "peak_bytes": 3 * 2 ** 30,
         "peaks": PEAKS,
         "trace": {"idle_share": 0.04}}
DECODE = {"steps": 500, "window_s": 10.0, "step_flops": 2e11, "step_bytes": 1.2e10,
          "chips": 1, "peaks": PEAKS, "trace": None}


def test_train_readers():
    assert reader("data_wait_ms.train")(TRAIN) == pytest.approx(1.0)
    assert reader("step_mfu.train")(TRAIN) == pytest.approx(2.8e9 * 20480 / 197e12 * 100)
    assert reader("peak_hbm_gib.train")(TRAIN) == pytest.approx(3.0)
    assert reader("device_idle_share.train")(TRAIN) == pytest.approx(4.0)


def test_decode_roofline_share_takes_the_binding_bound():
    # bytes bind: 1.2e10 / 819e9 = 14.65 ms against 20 ms a step
    assert reader("step_mfu.decode")(DECODE) == pytest.approx(1.2e10 / 819e9 / 0.02 * 100)
    flops_bound = dict(DECODE, step_flops=4e12, step_bytes=1e9)
    assert reader("step_mfu.decode")(flops_bound) == pytest.approx(4e12 / 197e12 / 0.02 * 100)


def test_idle_share_needs_a_trace():
    assert reader("device_idle_share.decode")(DECODE) is None
    assert reader("device_idle_share.decode")(dict(DECODE, trace={"idle_share": 0.1})) \
        == pytest.approx(10.0)
