#!/usr/bin/env python3
"""Records the small scoped chip trace the scope-attribution tests read.

    python3 benchmarks/chip/testdata/record_scoped_trace.py <out_dir>

Five steps of the program's ``make_train_step`` for a tiny yi-6b (2
layers, layer scan, remat, a two-chunk attention scan, Adam), each a
``dispatch`` and a ``loss_fetch`` span, with the benchmark's own window
tracer around them. Writes ``scoped_train5.xplane.pb`` and the step's
compiled HLO text ``scoped_train5.hlo.txt``; prints the device time a step
by scope. Needs a TPU.
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parents[1] / "src")]

import jax  # noqa: E402

import bench  # noqa: E402
import scopes  # noqa: E402
import trace_reduce  # noqa: E402

STEPS = 5


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_scoped_trace.py: needs a TPU")
    from repro.configs import get_config
    from repro.launch.train import scale_arch
    from repro.models.lm import RunCfg
    from repro.train.step import TrainCfg, init_train_state, make_train_step
    arch = scale_arch(get_config("yi-6b"), "tiny")
    cfg = TrainCfg(run=RunCfg(q_chunk=64))
    step = make_train_step(arch, cfg)
    params, opt_state = init_train_state(arch, cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 2, 129), 0, arch.vocab)
    batch = {"tokens": tokens[..., :-1], "labels": tokens[..., 1:]}
    for _ in range(2):
        params, opt_state, m = step(params, opt_state, batch)
        float(m["loss"])
    spans = bench.Spans(trace=True)
    tmp = tempfile.mkdtemp()
    tracer = bench.WindowTrace(tmp)
    tracer.start()
    for _ in range(STEPS):
        with spans("dispatch"):
            params, opt_state, m = step(params, opt_state, batch)
        with spans("loss_fetch"):
            float(m["loss"])
    tracer.stop()
    hlo = step.lower(params, opt_state, batch).compile().as_text()
    path = trace_reduce.find_xplane(tmp)
    print(json.dumps(scopes.reduce(trace_reduce.load(path), hlo)))
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out / f"scoped_train{STEPS}.xplane.pb")
    (out / f"scoped_train{STEPS}.hlo.txt").write_text(hlo)
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
