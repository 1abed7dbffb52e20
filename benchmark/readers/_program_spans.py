"""The program's own spans in a traced run — what the readers of the
``program_span`` metrics added by PR 24 share.

Inside a profiler session every live span of the program's tracer
(``bigdl_tpu/telemetry/tracer.py``) also lands in the xplane as a
``bigdl.<name>`` event on its thread's line of ``/host:CPU``, on the
clock of the chips' ``XLA Ops`` lines, its ids (``step``, ``batch_id``,
...) as event stats.  ``load(ctx)`` parses the run's xplane once, keeps
those events with their stats (``trace_reduce.load`` drops them) and the
holes of chip 0's busy union, and caches the result on ``ctx``.

Two threads matter: the DRIVER (the host line that holds
``bigdl.train.iteration``) and the serving WORKER (the line that holds
``bigdl.serve.batch``).  Where the trace has no such line — a program
from before PR 24, a run without a trace — ``load`` gives None, every
reader returns None and the line leaves the metric out.  A span that
was open when the session started (``train.optimize``, the first
``serve.idle``) is not in the xplane; no reader depends on one.
"""
from __future__ import annotations

import bisect
import statistics

from benchmark import trace_reduce
from benchmark.readers import _xplane_opnames

PREFIX = "bigdl."
DRIVER_MARK = PREFIX + "train.iteration"
WORKER_MARK = PREFIX + "serve.batch"


class Line:
    """One host thread's ``bigdl.*`` events, by start:
    ``(name without the prefix, start_ns, end_ns, {stat: value})``."""

    def __init__(self, events):
        self.events = sorted(events, key=lambda e: (e[1], -e[2]))
        self._starts = [e[1] for e in self.events]

    def named(self, name: str) -> list:
        return [e for e in self.events if e[0] == name]

    def leaf_at(self, t: int):
        """The innermost event open at ``t`` (the one that began last),
        or None."""
        best = None
        for e in self.events[:bisect.bisect_right(self._starts, t)]:
            if e[2] > t and (best is None or e[1] >= best[1]):
                best = e
        return best


def load(ctx):
    """{"driver": Line|None, "worker": Line|None, "holes": [(s, e)],
    "idle_ns": int, "window": (lo, hi)} of this run's trace, or None
    where there is no trace or no ``bigdl.*`` line in it."""
    if hasattr(ctx, "_program_spans"):
        return ctx._program_spans
    ctx._program_spans = None
    path = (getattr(ctx, "run", None) or {}).get("trace_path")
    if not path:
        return None
    try:
        ctx._program_spans = build(
            extract(trace_reduce.find_xplane(path)),
            ctx.run.get("trace_window"))
    except FileNotFoundError:
        pass
    except Exception as e:  # noqa: BLE001 — a trace these readers cannot
        # parse leaves their metrics out of the line; it must not take
        # the run's other metrics down with it
        say = getattr(ctx, "say", print)
        say(f"[program spans] trace not readable: {type(e).__name__}: {e}")
    return ctx._program_spans


def extract(pb: str) -> dict:
    """The part of an xplane these readers use, as plain data:
    ``lines`` (per host thread, its ``bigdl.*`` events ``[name, start_ns,
    end_ns, {stat: value}]``), ``chip_ops`` (chip 0's ``XLA Ops``
    intervals), ``chip_events`` (the same events as ``[name, start_ns,
    duration_ns, {"scope": op_name}]``, the form ``trace_reduce.self_times``
    takes; the op_name — the ``jax.named_scope`` path — is the ``tf_op``
    stat of the event's metadata, see ``_xplane_opnames``) and
    ``bench_window``."""
    from jax.profiler import ProfileData

    lines, chip_events, bench_window = [], None, None
    try:
        scopes = _xplane_opnames.op_names(pb)
    except (ValueError, IndexError, UnicodeError):  # not the wire format
        scopes = {}                                  # expected: no scopes
    for plane in ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                mine = []
                for ev in line.events:
                    name = ev.name
                    if name.startswith(PREFIX):
                        s = int(ev.start_ns)
                        mine.append([name[len(PREFIX):], s,
                                     s + int(ev.duration_ns),
                                     {k: v for k, v in ev.stats
                                      if isinstance(v, (str, int, float))}])
                    elif name == "bench.window":
                        s = int(ev.start_ns)
                        bench_window = [s, s + int(ev.duration_ns)]
                if mine:
                    lines.append(mine)
        elif plane.name == "/device:TPU:0" or (
                chip_events is None and trace_reduce._is_chip(plane.name)):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chip_events = [
                        [e.name, int(e.start_ns), int(e.duration_ns),
                         {"scope": scopes.get(e.name, "")}]
                        for e in line.events if e.duration_ns > 0]
    chip_events = chip_events or []
    return {"lines": lines, "chip_events": chip_events,
            "chip_ops": [[s, s + d] for _, s, d, _ in chip_events],
            "bench_window": bench_window}


def build(raw: dict, window=None):
    """``extract``'s data -> what ``load`` returns."""
    lines = [Line(evs) for evs in raw["lines"]]
    if not lines:
        return None

    def line_with(mark):
        name = mark[len(PREFIX):]
        return next((ln for ln in lines if ln.named(name)), None)

    out = {"driver": line_with(DRIVER_MARK), "worker": line_with(WORKER_MARK),
           "lines": lines, "holes": [], "idle_ns": 0, "window": None,
           "chip_events": raw.get("chip_events") or []}
    chip_ops = raw["chip_ops"]
    if chip_ops:
        window = window or raw.get("bench_window")
        lo, hi = window if window else (min(s for s, _ in chip_ops),
                                        max(e for _, e in chip_ops))
        busy = trace_reduce._clip(
            trace_reduce._union([[s, e] for s, e in chip_ops]), lo, hi)
        holes = [(s, e) for s, e in trace_reduce._subtract([[lo, hi]], busy)
                 if e - s >= trace_reduce.SMALL_GAP_NS]
        out.update(holes=holes, idle_ns=sum(e - s for s, e in holes),
                   window=(lo, hi))
    return out


def save_slice(pb: str, out: str, seconds: float = 1.0):
    """Cut a small recording out of a trace for the tests: what
    ``extract`` keeps of the first ``seconds`` after the first device
    operation, as gzipped JSON."""
    import gzip
    import json

    raw = extract(pb)
    t0 = min(s for s, _ in raw["chip_ops"])
    t1 = t0 + int(seconds * 1e9)
    raw["chip_ops"] = [[s, e] for s, e in raw["chip_ops"] if s < t1]
    del raw["chip_events"]  # names and scopes: large, and no test reads them
    raw["lines"] = [[ev for ev in evs if t0 <= ev[1] < t1]
                    for evs in raw["lines"]]
    raw["lines"] = [evs for evs in raw["lines"] if evs]
    raw["bench_window"] = None
    with gzip.open(out, "wt") as f:
        json.dump(raw, f, separators=(",", ":"))


def load_slice(path: str) -> dict:
    import gzip
    import json

    with gzip.open(path, "rt") as f:
        return json.load(f)


def idle_by_span(spans: dict, which: str) -> dict:
    """Chip 0's idle nanoseconds (gaps of at least 50 us) by the leaf
    ``bigdl.*`` span of thread ``which`` open at each gap's midpoint;
    ``None`` keys what no span covers."""
    line, out = spans[which], {}
    for s, e in spans["holes"]:
        leaf = line.leaf_at((s + e) // 2) if line else None
        key = leaf[0] if leaf else None
        out[key] = out.get(key, 0) + (e - s)
    return out


def idle_attributed_pct(ctx, which: str):
    spans = load(ctx)
    if not spans or spans[which] is None or not spans["idle_ns"]:
        return None
    by = idle_by_span(spans, which)
    return 100.0 * (spans["idle_ns"] - by.get(None, 0)) / spans["idle_ns"]


def scope_seconds(ctx, scope: str):
    """Self seconds, inside the traced window, of chip 0's operations
    whose ``op_name`` lies under ``scope``; None where the trace holds
    no program span, no device event, or no event that names a scope at
    all (then the share would read 0 for the wrong reason).  The pass
    over the events is made once a run and kept beside the spans: every
    scope is a sum over its rows."""
    spans = load(ctx)
    if not spans or not spans["chip_events"] or not spans["window"]:
        return None
    if "scope_self_ns" not in spans:
        lo, hi = spans["window"]
        events = [e for e in spans["chip_events"] if lo <= e[1] < hi]
        spans["scope_self_ns"] = [
            (ev[3]["scope"] + "/", self_ns)
            for ev, self_ns, _ in trace_reduce.self_times(events)
        ] if any(e[3]["scope"] for e in events) else None
    rows = spans["scope_self_ns"]
    if rows is None:
        return None
    mark = scope + "/"
    return sum(self_ns for path, self_ns in rows if mark in path) / 1e9


def handoffs(line: Line, done: str, enqueued: str) -> list:
    """[(end of a ``done`` span, end of the next ``enqueued`` span)]:
    the host's stretch between a result arriving and the next program
    being on the device's queue."""
    nexts, out = line.named(enqueued), []
    for d in line.named(done):
        nxt = next((e for e in nexts if e[1] >= d[2]), None)
        if nxt is not None:
            out.append((d[2], nxt[2]))
    return out


def median_ms(values):
    return 1e-6 * statistics.median(values) if values else None


def ring():
    """The process tracer's ring (``default_tracer().spans()``), or None
    for a program that has none."""
    try:
        from bigdl_tpu.telemetry import default_tracer
    except ImportError:
        return None
    return default_tracer().spans()
