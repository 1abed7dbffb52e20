"""The device scopes of the compiled TRAINING step in a traced run —
what the seven ``train_*_ms_per_step`` / ``train_step_named_pct``
readers share (PR 38).

The step names its own parts (``jax.named_scope`` in
``parallel/plan.py``, ``models/transformer.py``, ``nn/attention.py``;
``telemetry.tracer.DEVICE_SCOPES``), and the names reach the trace as
the components of an operation's ``op_name``.  Autodiff WRAPS the
component a transform was applied under, so the backward of
``step.forward/block.mlp/dot_general`` reads
``transpose(jvp(step.forward))/block.mlp/dot_general`` and a
rematerialised block
``transpose(jvp(step.forward))/jvp(step.forward)/checkpoint/rematted_computation/block.mlp/...``:
an operation belongs to scope ``s`` when ``s`` IS a component of its
path once the wrappers are taken off — never when it is only part of
one (``block.mlpx``).  ``_program_spans.scope_seconds`` looks for
``scope + "/"`` as a substring and so misses every wrapped form.

A reading is per step: chip 0's operation SELF time (nesting as
``trace_reduce.self_times`` does it) under the scope, forward and
backward together, summed over the executions of the step program that
lie WHOLE inside the traced window and divided by their number.  The
step program is the module with the most device time; an execution cut
by the window's edge is neither summed nor counted.  ONE pass over the
events serves all seven readers and is kept on ``ctx``.

None where the run has no trace, the trace no chip event, no whole
execution, or no operation under any of the scopes (a program from
before PR 38, a CPU rehearsal): never 0 for the wrong reason.
"""
from __future__ import annotations

import bisect
import re
from functools import lru_cache

from benchmark import trace_reduce
from benchmark.readers import _program_spans

#: the partition: every operation of a step goes to the first of these
#: on its path, or to ``UNNAMED``
STEP_SCOPES = ("step.cast_params", "step.forward", "step.loss",
               "step.grad_reduce", "step.update")
#: the model's scopes, nested in ``step.forward`` and its transpose
MODEL_SCOPES = ("lm.embed", "block.attention", "attention.core",
                "block.mlp", "lm.head")
SCOPES = STEP_SCOPES + MODEL_SCOPES
UNNAMED = "unnamed"
CHIP0 = "/device:TPU:0"

_WRAPPED = re.compile(r"^(?:[A-Za-z_][\w.\-]*\()+([^()]*)\)+$")


@lru_cache(maxsize=1 << 16)
def components(op_name: str) -> tuple:
    """The path's components with autodiff's and jit's wrappers taken
    off: ``jit(f)/transpose(jvp(step.forward))/block.mlp/dot`` ->
    ``("f", "step.forward", "block.mlp", "dot")``."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        out.append(m.group(1) if m else part)
    return tuple(out)


@lru_cache(maxsize=1 << 16)
def _booking(op_name: str) -> tuple:
    """(the scopes of ``SCOPES`` on the path, its place in the
    partition)."""
    parts = components(op_name)
    hits = frozenset(p for p in parts if p in SCOPES)
    first = next((p for p in parts if p in STEP_SCOPES), UNNAMED)
    return hits, first


def modules(pb: str) -> list:
    """[[name, start_ns, duration_ns]] of chip 0's ``XLA Modules`` line:
    one event per executed program."""
    from jax.profiler import ProfileData

    planes = ProfileData.from_file(pb).planes
    chip = next((p for p in planes if p.name == CHIP0), None) or next(
        (p for p in planes if trace_reduce._is_chip(p.name)), None)
    if chip is None:
        return []
    return [[e.name, int(e.start_ns), int(e.duration_ns)]
            for line in chip.lines if line.name == "XLA Modules"
            for e in line.events if e.duration_ns > 0]


def whole_executions(module_events, window) -> list:
    """[(start, end)] of the executions of the step program — the module
    with the most device time — that lie whole inside ``window``."""
    lo, hi = window
    by_name = {}
    for name, s, d in module_events:
        by_name.setdefault(re.sub(r"\(\d+\)$", "", name), []).append(
            (s, s + d))
    if not by_name:
        return []
    step = max(by_name.values(), key=lambda runs: sum(e - s
                                                      for s, e in runs))
    return sorted((s, e) for s, e in step if lo <= s and e <= hi)


def reduce(chip_events, module_events, window=None):
    """Events of chip 0 (``[name, start_ns, duration_ns, {"scope":
    op_name}]``) and its module events -> ``{"executions": n,
    "busy_ns": self ns inside them, "by_hits": {frozenset of scopes:
    ns}, "partition_ns": {step scope or "unnamed": ns}}``, or None."""
    if not chip_events or not module_events:
        return None
    if window is None:
        # whatever the chip's lines hold: a module starts a little
        # before its first operation
        lo = min(min(e[1] for e in chip_events),
                 min(s for _, s, _ in module_events))
        hi = max(max(e[1] + e[2] for e in chip_events),
                 max(s + d for _, s, d in module_events))
        window = (lo, hi)
    runs = whole_executions(module_events, window)
    if not runs:
        return None
    starts = [s for s, _ in runs]
    by_hits, partition, busy = {}, {}, 0
    for ev, self_ns, _ in trace_reduce.self_times(chip_events):
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i < 0 or ev[1] + ev[2] > runs[i][1]:
            continue            # in no whole execution
        hits, first = _booking(ev[3].get("scope") or "")
        by_hits[hits] = by_hits.get(hits, 0) + self_ns
        partition[first] = partition.get(first, 0) + self_ns
        busy += self_ns
    if not any(by_hits):
        return None             # nothing names a scope: not this program
    return {"executions": len(runs), "busy_ns": busy, "by_hits": by_hits,
            "partition_ns": partition}


def table(ctx):
    """``reduce`` of this run's trace, made once and kept on ``ctx``."""
    if hasattr(ctx, "_train_scopes"):
        return ctx._train_scopes
    ctx._train_scopes = None
    spans = _program_spans.load(ctx)
    if not spans or not spans["chip_events"]:
        return None
    try:
        mods = modules(trace_reduce.find_xplane(ctx.run["trace_path"]))
        ctx._train_scopes = reduce(spans["chip_events"], mods,
                                   ctx.run.get("trace_window"))
    except Exception as e:  # noqa: BLE001 — as ``_program_spans.load``:
        # a trace this cannot parse leaves seven metrics out of the
        # line, it does not take the others down
        say = getattr(ctx, "say", print)
        say(f"[train scopes] trace not readable: {type(e).__name__}: {e}")
    return ctx._train_scopes


def _under(t: dict, scopes) -> int:
    """Self ns of the operations under ANY of ``scopes`` (one that is
    under two of them counts once)."""
    want = set(scopes)
    return sum(ns for hits, ns in t["by_hits"].items() if hits & want)


def ms_per_step(ctx, *scopes):
    """Self milliseconds a step under any of ``scopes``; None where
    nothing is to be read, or nothing lies under them."""
    t = table(ctx)
    ns = _under(t, scopes) if t else 0
    return ns / 1e6 / t["executions"] if ns else None


def named_pct(ctx):
    """Share of a step's self time under any scope of ``SCOPES``."""
    t = table(ctx)
    if not t or not t["busy_ns"]:
        return None
    return 100.0 * _under(t, SCOPES) / t["busy_ns"]


def per_step_table(t: dict) -> dict:
    """For PERF.md and the tests: ms a step of every scope, of the
    partition, and of the whole."""
    n = t["executions"] * 1e6
    return {"executions": t["executions"], "busy_ms": t["busy_ns"] / n,
            "scope_ms": {s: _under(t, (s,)) / n for s in SCOPES},
            "partition_ms": {k: v / n
                             for k, v in sorted(t["partition_ns"].items())},
            "named_pct": 100.0 * _under(t, SCOPES) / t["busy_ns"]}


def save_slice(pb: str, out: str, executions: int = 3):
    """Cut a small recording out of a training trace for the tests: the
    module events and chip 0's operations of the first ``executions``
    whole executions of the step program, each operation as [short
    name, start_ns, duration_ns, index into ``scopes``], as gzipped
    JSON."""
    import gzip
    import json

    raw = _program_spans.extract(pb)
    mods = modules(pb)
    events = raw["chip_events"]
    lo = min(min(e[1] for e in events), min(s for _, s, _ in mods))
    hi = max(max(e[1] + e[2] for e in events),
             max(s + d for _, s, d in mods))
    runs = whole_executions(mods, (lo, hi))[:executions]
    t0, t1 = runs[0][0], runs[-1][1]
    scopes, rows = {}, []
    for name, s, d, st in events:
        if t0 <= s and s + d <= t1:
            rows.append([trace_reduce.split_hlo(name)[0], s - t0, d,
                         scopes.setdefault(st["scope"], len(scopes))])
    with gzip.open(out, "wt") as f:
        json.dump({"scopes": list(scopes), "events": rows,
                   "modules": [[n, s - t0, d] for n, s, d in mods
                               if t0 <= s and s + d <= t1]},
                  f, separators=(",", ":"))


def load_slice(path: str):
    """-> (chip_events, module_events) as ``reduce`` takes them."""
    import gzip
    import json

    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    scopes = raw["scopes"]
    return ([[n, s, d, {"scope": scopes[i]}] for n, s, d, i in raw["events"]],
            raw["modules"])
