"""Decode job: a closed loop over a static batch of sessions through the
program's jitted ``make_serve_step``.

Set-up makes the weights in the type they are served in and caches each
session's context by feeding it through the serve step, one position a
step, keeping the step's greedy choice at each position. The window then
runs turns: each session's turn starts from a seeded opener token at the
first position after the context, decodes greedily to the end of the
cache span, and the position goes back for the next turn. Each step ends
when the host holds that step's next tokens, as a server streaming them
would. Once the window has closed and the program's state is freed, one
finished turn of every session, drawn from the seed, is run through the
float32 reference with its context, and the program's choice at every
position (1024 from the fill, 256 served in the window) is judged by how
far its reference logit lies below the reference's best: the mean of
these gaps, and the widest.
"""

from __future__ import annotations

import gc
import time
from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

import traffic
import weights
from bench import Outcome, arch_of, reference_of, run_cfg

MAX_TURNS = 4096


class Server:
    """The compiled serve step with its weights, cache and sessions."""

    def __init__(self, cell, seed: int, spans):
        from repro.models.lm import init_cache
        from repro.serving.serve import make_serve_step
        t = cell.traffic
        self.arch = arch_of(cell.config)
        self.batch, self.context, self.turn_tokens = t["batch"], t["context"], t["turn_tokens"]
        span = self.context + self.turn_tokens
        cfg = run_cfg(cell.config, param_dtype=jnp.bfloat16)
        self.key = weights.seed_key(seed)
        self.serve = make_serve_step(self.arch, cfg)
        self.params = weights.generate(self.arch, self.key, jnp.bfloat16)
        self.cache = jax.jit(partial(init_cache, self.arch, self.batch, span, cfg))()
        self.contexts = traffic.decode_contexts(seed, self.batch, self.context, self.arch.vocab)
        self.openers = traffic.turn_openers(seed, MAX_TURNS, self.batch, self.arch.vocab)
        self.positions = [jnp.int32(p) for p in range(self.context, span)]
        self.spans = spans

    def fill(self) -> np.ndarray:
        """Caches every session's context through the serve step; returns
        the step's greedy choice at each position, ``[batch, context]``."""
        columns = [jax.device_put(c) for c in self.contexts.T]
        chosen = []
        for i, column in enumerate(columns):
            tok, _, self.cache = self.serve(self.params, self.cache, column, jnp.int32(i))
            chosen.append(tok)
        return np.stack(jax.device_get(chosen), axis=1)

    def turn(self, index: int, served: np.ndarray, on_step) -> int:
        """Runs turn ``index`` into ``served`` [batch, turn_tokens] until it
        ends or ``on_step`` returns True; returns the steps run."""
        with self.spans("turn_reset"):
            tok = jax.device_put(self.openers[index])
        for s, pos in enumerate(self.positions):
            with self.spans("dispatch"):
                tok, _, self.cache = self.serve(self.params, self.cache, tok, pos)
            with self.spans("token_fetch"):
                served[:, s] = np.asarray(tok)
            if on_step():
                return s + 1
        return self.turn_tokens

    def step_hlo(self) -> str:
        """The compiled step's HLO text, whose op names the trace's ops carry."""
        tok = jax.device_put(self.openers[0])
        return self.serve.lower(self.params, self.cache, tok,
                                self.positions[0]).compile().as_text()

    def close(self):
        self.params = self.cache = None
        gc.collect()


def _reference_weights(arch, key):
    shapes = weights.param_shapes(arch, jnp.bfloat16)
    stacked = [p for p in shapes if weights.is_stacked(p)]

    @partial(jax.jit, static_argnames=("path",))
    def layer_leaf(layer, path):
        return weights.make_layer(key, path, shapes[path][0], jnp.bfloat16, layer,
                                  arch.num_layers).astype(jnp.float32)

    @partial(jax.jit, static_argnames=("path",))
    def leaf(path):
        return weights.make_leaf(key, path, shapes[path][0], jnp.bfloat16,
                                 arch.num_layers).astype(jnp.float32)

    return (lambda layer: {p: layer_leaf(jnp.int32(layer), path=p) for p in stacked},
            lambda path: leaf(path=path))


def judged(contexts: np.ndarray, openers: np.ndarray, filled: np.ndarray,
           served_turns: List[np.ndarray], seed: int):
    """One finished turn of each session, drawn from the seed: the token
    sequences ``[batch, context + turn]`` (context, opener, all but the
    last served token) and the program's choice after each position (its
    fill choices, then the served tokens)."""
    turns = traffic.sample_turns(seed, len(served_turns), len(contexts))
    seqs, chosen = [], []
    for b, j in enumerate(turns):
        out = served_turns[j][b]
        seqs.append(np.concatenate([contexts[b], openers[j, b:b + 1], out[:-1]]))
        chosen.append(np.concatenate([filled[b], out]))
    return np.stack(seqs), np.stack(chosen)


@jax.jit
def _gaps(logits, chosen):
    """How far the logit of each chosen token ``[k, S]`` lies below the
    best at its position (logits ``[S, V]``)."""
    picked = jnp.take_along_axis(logits, chosen.T, axis=-1).T
    return jnp.max(logits, axis=-1)[None] - picked


def reference_gaps(cell, seed: int, seqs: np.ndarray, choices: List[np.ndarray]):
    """For each array of choices ``[n, S]``, the gap at every position in
    the configuration's float32 reference, ``[len(choices), n, S]``."""
    arch = arch_of(cell.config)
    make_layer, make_leaf = _reference_weights(arch, weights.seed_key(seed))
    rows = reference_of(cell.config).position_logits(cell.config, make_layer, make_leaf,
                                                     seqs)
    out = [np.asarray(_gaps(logits, jnp.asarray(np.stack([c[i] for c in choices]))))
           for i, logits in enumerate(rows)]
    return np.stack(out, axis=1)


def reference_choice(cell, seed: int, seqs: np.ndarray, prec: str) -> np.ndarray:
    """The token the reference in ``prec`` puts first at every position."""
    arch = arch_of(cell.config)
    make_layer, make_leaf = _reference_weights(arch, weights.seed_key(seed))
    rows = reference_of(cell.config).position_logits(cell.config, make_layer, make_leaf,
                                                     seqs, prec)
    return np.stack([np.asarray(jnp.argmax(logits, axis=-1)) for logits in rows])


def gap_numbers(gaps: np.ndarray) -> dict:
    """The mean and the widest of the gaps of the program's choices."""
    return {"served_mean_gap": float(np.mean(gaps)), "served_gap": float(np.max(gaps))}


def run(cell, seed: int, seconds: float, spans, window_trace, process_start: float,
        compiles) -> Outcome:
    from work import decode_step_work
    server = Server(cell, seed, spans)
    filled = server.fill()

    spans.reset()
    t_start = time.perf_counter()
    setup_s = t_start - process_start
    first_compile = len(compiles.events)
    marks = [t_start]          # when the host held each step's tokens
    state = {"done": False}

    def on_step() -> bool:
        now = time.perf_counter()
        marks.append(now)
        window_trace.tick()
        state["done"] = now - t_start >= seconds
        return state["done"]

    window_trace.start()
    served_turns: List[np.ndarray] = []
    turns_started = 0
    while not state["done"]:
        served = np.zeros((server.batch, server.turn_tokens), np.int32)
        ran = server.turn(turns_started, served, on_step)
        turns_started += 1
        if ran == server.turn_tokens:
            served_turns.append(served)
    window_s = time.perf_counter() - t_start
    window_trace.stop()
    if len(compiles.events) != first_compile:
        raise RuntimeError(f"{len(compiles.events) - first_compile} compiles in the window")
    if not served_turns:
        raise RuntimeError("the window finished no turn; lengthen --seconds")
    step_hlo = server.step_hlo() if window_trace.directory else None

    steps = len(marks) - 1
    step_p95_ms = float(np.percentile(np.diff(marks), 95) * 1e3)
    t = cell.traffic
    contexts, openers = server.contexts, server.openers
    server.close()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    del server
    gc.collect()

    seqs, chosen = judged(contexts, openers, filled, served_turns, seed)
    numbers = gap_numbers(reference_gaps(cell, seed, seqs, [chosen])[0])
    work = decode_step_work(cell.config, t["batch"], t["context"], t["turn_tokens"])
    return Outcome(
        end_to_end={"decode_tokens_per_s": steps * t["batch"] / window_s,
                    "decode_step_p95_ms": step_p95_ms, "setup_s": setup_s},
        attempted=turns_started * t["batch"], failed=0, numbers=numbers,
        memory_peak_bytes=peak, step_hlo=step_hlo,
        record={"steps": steps, "window_s": window_s, "spans": dict(spans.seconds),
                "step_flops": work["flops"], "step_bytes": work["bytes"],
                "chips": cell.chips, "peak_bytes": peak})
