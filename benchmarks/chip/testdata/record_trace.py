#!/usr/bin/env python3
"""Records the small chip trace the trace-reduction tests read.

    python3 benchmarks/chip/testdata/record_trace.py <out_dir>

Twenty steps of a jitted matrix product, each a ``dispatch`` span and a
``token_fetch`` span, with the benchmark's own window tracer around them;
prints the planes and lines it finds. Needs a TPU.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import bench  # noqa: E402
import trace_reduce  # noqa: E402


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace.py: needs a TPU")
    step = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    np.asarray(step(x))
    spans = bench.Spans(trace=True)
    tmp = tempfile.mkdtemp()
    tracer = bench.WindowTrace(tmp)
    tracer.start()
    for _ in range(20):
        with spans("dispatch"):
            x = step(x)
        with spans("token_fetch"):
            np.asarray(x[0, :8])
    tracer.stop()
    path = trace_reduce.find_xplane(tmp)
    profile = trace_reduce.load(path)
    for plane in profile.planes:
        lines = [(line.name, sum(1 for _ in line.events)) for line in plane.lines]
        print(plane.name, lines)
    print(trace_reduce.reduce(profile, ["dispatch", "token_fetch"]))
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    shutil.copy(path, Path(out_dir) / "matmul20.xplane.pb")
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
