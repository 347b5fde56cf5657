"""The one traffic generator: every mix is a data file under ``traffic/``
read by these functions, and every input is a function of the seed.

Training rows follow the program's synthetic stream (a token walk
``t[i+1] = (31 t[i] + 7 + noise) mod V`` keyed by (seed, step)); it is
restated here so that the reference gets its rows from the benchmark and
not from the program's data pipeline.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> Dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


def train_rows(seed: int, step: int, vocab: int, seq_len: int, batch: int,
               microbatches: int = 1) -> Dict[str, np.ndarray]:
    """Tokens and next-token labels ``[microbatches, batch/microbatches,
    seq_len]`` for one step."""
    rng = np.random.Generator(np.random.Philox(key=seed, counter=step))
    shape = (microbatches, batch // microbatches, seq_len)
    start = rng.integers(0, vocab, size=shape[:2] + (1,))
    noise = (rng.random(size=shape) < 0.1).astype(np.int64)
    toks = np.zeros(shape, dtype=np.int64)
    toks[..., 0] = start[..., 0]
    for t in range(1, seq_len):
        toks[..., t] = (toks[..., t - 1] * 31 + 7 + noise[..., t]) % vocab
    labels = np.roll(toks, -1, axis=-1)
    labels[..., -1] = 0
    return {"tokens": toks.astype(np.int32), "labels": labels.astype(np.int32)}


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


def decode_contexts(seed: int, batch: int, context: int, vocab: int) -> np.ndarray:
    """Each session's cached context, ``[batch, context]``."""
    return _rng(seed, 1).integers(0, vocab, size=(batch, context), dtype=np.int32)


def turn_openers(seed: int, turns: int, batch: int, vocab: int) -> np.ndarray:
    """The token each session's turn starts from, ``[turns, batch]``."""
    return _rng(seed, 2).integers(0, vocab, size=(turns, batch), dtype=np.int32)


def sample_turns(seed: int, completed: int, n: int) -> np.ndarray:
    """For each of ``n`` sessions, which completed turn to check."""
    return _rng(seed, 4).integers(0, completed, size=n)
