"""Training job: the program's jitted ``make_train_step`` fed by its own
``PrefetchIterator(SyntheticDataset)``, each step ended by fetching the
loss, as ``launch.train.train_loop`` ends it.

Set-up builds one ``Trainer`` (weights from the seed, the compiled step,
Adam's state, the feed) and drives it through its first three steps,
reading the loss of each, the first gradient as Adam got it (its first
moment after one step, copied to the host, over ``1 - b1``) and each
leaf's change after the three. The window then drives the same ``Trainer`` on. Once the window has
closed and the program's state is freed, the float32 reference repeats the
three steps from the same seed.

Where the step's metrics carry ``moe_routes`` (int32 ``[num_layers,
tokens, top_k]``, the expert ids the step chose for its one microbatch's
tokens in row-major order), the checked steps keep them and the reference
is held to them: a near tie that the program breaks the other way then
moves no gradient, and ``route_flip``, the share of the program's choices
that are not among the reference's own top-k (worst step), joins the
compared numbers. Routes that do not fit the batch or the experts, or
that name an expert twice for one token, read ``route_flip`` 1.
"""

from __future__ import annotations

import gc
import time
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

import traffic
import weights
from bench import Outcome, arch_of, gap, moving_leaves, reference_of, run_cfg

CHECKED_STEPS = 3


class Trainer:
    """The compiled step with its state and feed."""

    def __init__(self, cell, seed: int, spans):
        from repro.train.data import DataCfg, PrefetchIterator, SyntheticDataset
        from repro.train.optim import OptimizerCfg, init_opt_state
        from repro.train.step import TrainCfg, make_train_step
        t = cell.traffic
        if t["microbatches"] != 1:
            raise ValueError("the reference follows one microbatch a step")
        self.arch = arch_of(cell.config)
        cfg = TrainCfg(run=run_cfg(cell.config), opt=OptimizerCfg(**t["optimizer"]),
                       num_microbatches=t["microbatches"])
        self.b1 = cfg.opt.b1
        self.key = weights.seed_key(seed)
        self.fn = make_train_step(self.arch, cfg)
        self.params = weights.generate(self.arch, self.key, jnp.float32)
        self.opt_state = jax.jit(partial(init_opt_state, cfg.opt))(self.params)
        self.data = PrefetchIterator(SyntheticDataset(self.arch, DataCfg(
            seq_len=t["seq_len"], global_batch=t["global_batch"],
            num_microbatches=t["microbatches"], seed=seed)))
        self.tokens_per_step = t["seq_len"] * t["global_batch"]
        self.spans = spans

    def step(self, routes: Optional[list] = None) -> float:
        """One step, and its loss once the host holds it; appends the
        step's ``moe_routes`` to ``routes`` where given and reported."""
        with self.spans("data_wait"):
            batch = next(self.data)
        with self.spans("dispatch"):
            self.params, self.opt_state, m = self.fn(self.params, self.opt_state, batch)
        with self.spans("loss_fetch"):
            loss = float(m["loss"])
        if routes is not None and "moe_routes" in m:
            routes.append(np.asarray(jax.device_get(m["moe_routes"])))
        return loss

    def first_steps(self) -> Dict:
        """The checked steps, through the window's own call and feed, with
        the expert ids each chose where the step reports them."""
        routes = []
        losses = [self.step(routes)]
        moment = jax.device_get(weights.flatten(self.opt_state["m"]))
        losses += [self.step(routes) for _ in range(CHECKED_STEPS - 1)]
        change = weights.change_norms(weights.flatten(self.params), self.key,
                                      self.arch.num_layers)
        out = {"losses": losses, "grad": moment, "grad_scale": 1 / (1 - self.b1),
               "change": change}
        if routes:
            out["routes"] = routes
        return out

    def step_hlo(self) -> str:
        """The compiled step's HLO text, whose op names the trace's ops carry."""
        return self.fn.lower(self.params, self.opt_state,
                             next(self.data)).compile().as_text()

    def close(self):
        self.data.close()
        self.params = self.opt_state = None
        gc.collect()


def reference(cell, seed: int, prec: str = "f32",
              row_weights: Optional[np.ndarray] = None, routes=None) -> Dict:
    """The readings of the checked steps from ``seed`` of the reference
    that the configuration names; with ``routes`` (one ``[L, T, k]`` array
    a step) its experts are held to them, and the readings also have its
    own choices (``routes``) and ``route_flip`` a step."""
    arch = arch_of(cell.config)
    t = cell.traffic
    key = weights.seed_key(seed)
    params = weights.flatten(weights.generate(arch, key, jnp.float32))
    batches = [traffic.train_rows(seed, s, arch.vocab, t["seq_len"],
                                  t["global_batch"], t["microbatches"])
               for s in range(CHECKED_STEPS)]
    out = reference_of(cell.config).train(cell.config, t["optimizer"], params, batches,
                                          prec, row_weights, routes)
    change = weights.change_norms(out.pop("params"), key, arch.num_layers)
    got = {"losses": out["losses"], "grad": out["first_grad"], "change": change}
    got.update({k: out[k] for k in ("routes", "route_flip") if k in out})
    return got


def route_shape(cell) -> tuple:
    """``[num_layers, tokens, top_k]`` of one step's expert ids."""
    c, t = cell.config, cell.traffic
    return (c["num_hidden_layers"], t["seq_len"] * t["global_batch"] // t["microbatches"],
            c["num_experts_per_tok"])


def routes_fit(routes, cell) -> bool:
    """Every checked step's ids have the step's shape, name an expert, and
    name ``top_k`` different experts for each token."""
    experts = cell.config.get("num_local_experts", 0)
    return len(routes) == CHECKED_STEPS and all(
        r.shape == route_shape(cell) and np.issubdtype(r.dtype, np.integer)
        and r.min() >= 0 and r.max() < experts
        and np.all(np.diff(np.sort(r, axis=-1), axis=-1) != 0) for r in routes)


def check(cell, seed: int, checked: Dict) -> tuple:
    """The compared numbers of the checked steps against the reference
    from ``seed``, and the reference's readings. Where the program reported
    its expert ids the reference is held to them and ``route_flip`` joins;
    ids that do not fit (``routes_fit``) read 1 and the reference routes
    freely."""
    routes = checked.get("routes")
    held = routes is not None and routes_fit(routes, cell)
    ref = reference(cell, seed, routes=routes if held else None)
    numbers = compare(checked, ref)
    if routes is not None:
        numbers["route_flip"] = max(ref["route_flip"]) if held else 1.0
    return numbers, ref


def compare(program: Dict, ref: Dict) -> Dict[str, float]:
    """The compared numbers: the worst step's loss gap (nats); the worst
    leaf's gap of the first gradient's norm, and the worst leaf's norm of
    the difference of the first gradients (``grad_diff``), each over the
    larger of the reference leaf's norm and the median leaf's; and the
    worst leaf's gap of the change's norm after the checked steps."""
    ref_norms = {p: float(np.linalg.norm(g.ravel())) for p, g in ref["grad"].items()}
    norms = {p: float(np.linalg.norm(g.ravel())) * program.get("grad_scale", 1.0)
             for p, g in program["grad"].items()}
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(program["losses"], ref["losses"])),
        "grad_gap": gap(norms, ref_norms),
        "grad_diff": max(grad_diffs(program, ref, ref_norms).values()),
        "change_gap": gap(program["change"], ref["change"], moving_leaves(ref_norms)),
    }


def grad_diffs(program: Dict, ref: Dict, ref_norms: Dict[str, float]) -> Dict[str, float]:
    """Per leaf, the norm of the difference of the first gradients over
    the larger of the reference leaf's norm and the median leaf's."""
    median = float(np.median(list(ref_norms.values())))
    scale = np.float32(program.get("grad_scale", 1.0))
    return {p: float(np.linalg.norm((program["grad"][p] * scale - g).ravel()))
            / max(ref_norms[p], median) for p, g in ref["grad"].items()}


def run(cell, seed: int, seconds: float, spans, window_trace, process_start: float,
        compiles) -> Outcome:
    from work import train_flops_per_token
    trainer = Trainer(cell, seed, spans)
    checked = trainer.first_steps()

    spans.reset()
    t_start = time.perf_counter()
    setup_s = t_start - process_start
    first_compile = len(compiles.events)
    window_trace.start()
    steps = 0
    while True:
        trainer.step()
        steps += 1
        window_trace.tick()
        if time.perf_counter() - t_start >= seconds:
            break
    window_s = time.perf_counter() - t_start
    window_trace.stop()
    if len(compiles.events) != first_compile:
        raise RuntimeError(f"{len(compiles.events) - first_compile} compiles in the window")
    step_hlo = trainer.step_hlo() if window_trace.directory else None

    tokens_per_s = steps * trainer.tokens_per_step / window_s
    t = cell.traffic
    trainer.close()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    del trainer
    gc.collect()

    numbers, _ = check(cell, seed, checked)
    return Outcome(
        end_to_end={"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        attempted=steps, failed=0, numbers=numbers, memory_peak_bytes=peak,
        step_hlo=step_hlo,
        record={"steps": steps, "window_s": window_s, "tokens_per_s": tokens_per_s,
                "spans": dict(spans.seconds),
                "flops_per_token": train_flops_per_token(cell.config, t["seq_len"]),
                "chips": cell.chips, "peak_bytes": peak})
