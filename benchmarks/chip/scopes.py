"""Device time of a step program by the program's named scopes.

The model, optimizer and serve step put ``jax.named_scope`` names at each
layer boundary (``SCOPES``). A scope reaches the compiled program only as
``metadata={op_name=...}`` on each HLO instruction; the profiler's
``XLA Ops`` events carry no metadata, only the instruction's text
(``%fusion.95 = bf16[...] fusion(...)``). So each op is mapped to a scope
through its instruction name in the compiled step's HLO text
(``jitted.lower(...).compile().as_text()``), and the ``XLA Modules`` line
says which program each op ran in.

Each instant of device time goes to the innermost op covering it (a
``while`` keeps only what its body ops leave free), so the scope times of
one device add up to its busy time. Times are per execution of the step
program, over its executions that lie wholly inside the traced window
(``trace_reduce.WINDOW``).
"""

from __future__ import annotations

import bisect
import heapq
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce

# The program's scope names, in the order of a step. ``router``,
# ``dispatch``, ``experts`` and ``combine`` are the expert layer's parts
# inside ``moe``; an op in one of them counts there and not in ``moe``.
SCOPES = ("embed", "layer_scan", "attn_proj", "attention", "kv_write", "mlp", "moe",
          "router", "dispatch", "experts", "combine", "ssm", "head", "loss", "optimizer")
UNSCOPED = "(unscoped)"
OTHER = "(other programs)"
TOP_OPS = 3                # instructions listed for each scope

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s+=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_MODULE = re.compile(r"^\s*HloModule\s+([^\s,]+)")


def scope_of(op_name: str) -> str:
    """The last component of an ``op_name`` path that is a known scope,
    with transformation wrappers (``jvp(x)``, ``transpose(jvp(x))``)
    stripped; of ``;``-joined names the first counts."""
    found = UNSCOPED
    for part in op_name.split(";")[0].split("/"):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        if part in SCOPES:
            found = part
    return found


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope, for every instruction of a compiled
    module's HLO text; an instruction with no metadata is unscoped. A
    fusion has its own metadata, which is its root's."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            name = _OP_NAME.search(line)
            out[m.group(1)] = scope_of(name.group(1)) if name else UNSCOPED
    return out


def module_name(hlo_text: str) -> str:
    """The module name as the trace's ``XLA Modules`` line shows it,
    without its fingerprint (``jit_train_step``)."""
    m = _MODULE.match(hlo_text)
    if not m:
        raise ValueError("not an HLO module's text")
    return m.group(1)


def instruction(event_name: str) -> str:
    """``%fusion.95 = bf16[...] fusion(...)`` -> ``fusion.95``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def module_events(plane) -> List[Tuple[str, float, float]]:
    """(module name, start ns, end ns) of every program execution on a
    device plane."""
    out = []
    for line in plane.lines:
        if line.name == "XLA Modules":
            out.extend((e.name.split("(", 1)[0], e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events)
    return sorted(out, key=lambda m: m[1])


def self_times(intervals: Sequence[Tuple[float, float]]) -> List[float]:
    """Per interval, the time in which it is the innermost interval
    covering a point: of those covering it, the one that started last,
    and of two that started together, the one that ends first. The
    results add up to the length of the union."""
    out = [0.0] * len(intervals)
    order = sorted(range(len(intervals)), key=lambda i: intervals[i][0])
    bounds = sorted({t for iv in intervals for t in iv})
    heap: List[Tuple[float, float, int]] = []
    j = 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while j < len(order) and intervals[order[j]][0] <= t0:
            i = order[j]
            heapq.heappush(heap, (-intervals[i][0], intervals[i][1], i))
            j += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)
        if heap:
            out[heap[0][2]] += t1 - t0
    return out


def reduce(profile, hlo_text: str) -> Optional[Dict]:
    """Device milliseconds a step of the step program whose compiled HLO
    text is ``hlo_text``, by scope (descending), with ``(unscoped)`` for
    its ops outside every scope and ``(other programs)`` for the ops of
    other programs in the window, over the step's executions wholly
    inside the window (``steps``, a mean over devices); ``top_ops``, the
    instructions that take the most of each scope. ``busy_ms`` is the
    union of the step's ops a step, which the scopes and ``(unscoped)``
    add up to; ``unmatched_ms`` the part of it whose instruction the text
    does not name. ``None`` when the trace has no window, device or
    execution, or the program no scope."""
    scopes = op_scopes(hlo_text)
    if not set(scopes.values()) & set(SCOPES):
        return None
    step = module_name(hlo_text)
    windows = trace_reduce.host_spans(profile, [trace_reduce.WINDOW])
    planes = trace_reduce.device_planes(profile)
    if not windows or not planes:
        return None
    _, lo, hi = windows[0]
    by_op: Dict[Tuple[str, Optional[str]], float] = defaultdict(float)
    busy = 0.0
    executions = 0
    for plane in planes:
        modules = [m for m in module_events(plane) if m[2] > lo and m[1] < hi]
        starts = [m[1] for m in modules]
        counted = {k for k, (name, a, b) in enumerate(modules)
                   if name == step and lo <= a and b <= hi}
        executions += len(counted)
        ops, keys = [], []
        for name, a, b in trace_reduce.op_events(plane):
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            k = bisect.bisect_right(starts, a) - 1
            inside = k >= 0 and a < modules[k][2]
            if inside and k in counted:
                op = instruction(name)
                keys.append((scopes.get(op, UNSCOPED), op))
            elif inside and modules[k][0] == step:
                continue             # an execution the window cuts
            else:
                keys.append((OTHER, None))
            ops.append((a, b))
        for key, t in zip(keys, self_times(ops)):
            by_op[key] += t
        busy += trace_reduce.total(trace_reduce.union(
            [iv for iv, (label, _) in zip(ops, keys) if label != OTHER]))
    if not executions:
        return None
    per_step = 1e-6 / executions
    ms: Dict[str, float] = defaultdict(float)
    top: Dict[str, List] = defaultdict(list)
    for (label, op), t in sorted(by_op.items(), key=lambda kv: -kv[1]):
        ms[label] += t * per_step
        if op is not None and len(top[label]) < TOP_OPS:
            top[label].append([op, t * per_step])
    order = sorted(ms, key=lambda label: -ms[label])
    unmatched = sum(t for (_, op), t in by_op.items() if op is not None and op not in scopes)
    return {"program": step, "steps": executions / len(planes), "busy_ms": busy * per_step,
            "unmatched_ms": unmatched * per_step, "ms": {label: ms[label] for label in order},
            "top_ops": {label: top[label] for label in order if top[label]}}


def scope_ms(record, *names: str) -> Optional[float]:
    """Device ms a step in the named scopes, from a run record's traced
    window; None where the record has no scopes or the step none of them."""
    reduced = (record.get("trace") or {}).get("scopes")
    if reduced is None or not set(names) & set(reduced["ms"]):
        return None
    return sum(reduced["ms"].get(n, 0.0) for n in names)
