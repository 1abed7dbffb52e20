"""From a profiler trace (``*.xplane.pb``) to the numbers the readers
use — the benchmark's own reduction, so every PR computes the same
number in the same way.

What a TPU trace holds (looked at by hand on a v5e, jax 0.9.0): one
plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` carries one
event per executed HLO operation — its NAME is the instruction's whole
text, ``%fusion.35 = (shapes) fusion(operands), kind=...`` — nested
inside the event of a ``while`` that contains it, and whose line ``XLA
Modules`` carries one event per executed program
(``jit_local_step(<hash>)``); and ``/host:CPU`` with one line per host
thread, holding the runtime's spans, ``jax.profiler.TraceAnnotation``
spans and one event per Python call (``$file.py:line function``), all
on the same clock.

* busy: the UNION of the op intervals of a chip inside the window, so
  nested events are never counted twice; ``busy_s`` is the mean over
  chips, ``window_s`` the traced window (the ``bench.window`` host span
  where there is one, else first to last device op).
* operation seconds: SELF time — an event's duration minus the events
  nested in it — summed by operation, keyed ``<hlo name>:<signature>``.
* idle gaps: the holes of chip 0's busy union, each booked to the
  innermost host span open at its midpoint.
* exposed collective time: per chip, time in which a collective
  operation runs and no other operation does.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from functools import lru_cache

COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
              "collective-permute", "send", "recv")
CONTAINERS = ("while", "conditional", "call")
SMALL_GAP_NS = 50_000
SMALL_GAPS = "device:gaps_under_50_us_between_operations"


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return files[-1]


def load(path: str) -> list:
    """The trace as plain data: planes -> lines -> events
    ``[name, start_ns, duration_ns, {stat: value}]``."""
    from jax.profiler import ProfileData

    keep = ("hlo_op",)
    planes = []
    for plane in ProfileData.from_file(find_xplane(path)).planes:
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                stats = {}
                for k, v in ev.stats:
                    if k in keep and isinstance(v, (str, int, float)):
                        stats[k] = v
                events.append([ev.name, int(ev.start_ns),
                               int(ev.duration_ns), stats])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _is_chip(plane_name: str) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", plane_name) is not None


def _line(plane: dict, name: str):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


# The three parsers below are memoised on the event's name: a traced
# window holds some hundred thousand events of a few thousand distinct
# instructions, and the ``while`` of a decode scan carries every weight
# and cache as an operand — kilobytes of text that ``self_times`` asks
# about once for each of its children.
@lru_cache(maxsize=1 << 16)
def split_hlo(text: str):
    """``%fusion.35 = (shapes) fusion(...)`` -> (``fusion.35``, the rest).
    A name that is no instruction text comes back whole."""
    m = re.match(r"%?([\w.\-]+) = (.*)", text, re.S)
    return (m.group(1), m.group(2)) if m else (text, "")


@lru_cache(maxsize=1 << 16)
def signature(body: str, limit: int = 48) -> str:
    """A short, stable tag of an operation's shapes from its HLO text."""
    body = re.sub(r"\{[^{}]*\}", "", body)       # layouts
    return re.sub(r"[^A-Za-z0-9]+", "_", body)[:limit].strip("_")


@lru_cache(maxsize=1 << 16)
def base_name(name: str) -> str:
    """What an event does: the opcode of its instruction text
    (``%psum.3006 = f32[..] all-reduce(...)`` -> ``all-reduce``), else its
    name without the number (``fusion.123`` -> ``fusion``)."""
    hlo, body = split_hlo(name)
    m = re.search(r"(?<![\w\-])([a-z][a-z0-9\-]*)\(", body)
    return m.group(1) if m else re.sub(r"[.\d]+$", "", hlo)


def is_collective(name: str) -> bool:
    return base_name(name).startswith(COLLECTIVE)


def self_times(events) -> list:
    """[(event, self_ns, in_loop)] for events on one line: an event's
    duration minus the events nested in it (wholly inside an earlier,
    longer one); ``in_loop`` says whether some ancestor is a ``while``."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []   # stack of [event, end, child_ns, in_loop]

    def pop():
        ev, _, child_ns, in_loop = stack.pop()
        out.append((ev, ev[2] - child_ns, in_loop))

    for ev in order:
        s, e = ev[1], ev[1] + ev[2]
        # leave every span this event is not wholly inside (async
        # operations overlap their neighbours without containing them)
        while stack and (stack[-1][1] <= s or stack[-1][1] < e):
            pop()
        in_loop = bool(stack) and (stack[-1][3]
                                   or base_name(stack[-1][0][0]) == "while")
        if stack:
            stack[-1][2] += ev[2]
        stack.append([ev, e, 0, in_loop])
    while stack:
        pop()
    return out


def host_window(planes):
    """(start, end) of the ``bench.window`` host span, if one was
    recorded."""
    for plane in planes:
        if plane["name"].startswith("/host"):
            for line in plane["lines"]:
                for name, s, d, _ in line["events"]:
                    if name == "bench.window":
                        return s, s + d
    return None


def _program_files() -> set:
    """Base names of the program's and the benchmark's own Python files:
    a Python-call event ``$file.py:line function`` is the program's if
    its file is one of these."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = set()
    for top in ("bigdl_tpu", "benchmark"):
        for _, _, files in os.walk(os.path.join(root, top)):
            names.update(f for f in files if f.endswith(".py"))
    return names


class HostSpans:
    """The host's spans in three kinds, each as arrays for a quick
    "which are open at t": the benchmark's own annotations
    (``bench.*``), Python calls inside the program or the benchmark, and
    the runtime's spans."""

    def __init__(self, planes, program_files=None):
        import numpy as np

        files = _program_files() if program_files is None else program_files
        kinds = {"bench": [], "frame": [], "runtime": []}
        for plane in planes:
            if not plane["name"].startswith("/host"):
                continue
            for line in plane["lines"]:
                for name, s, d, _ in line["events"]:
                    if d <= 0 or name == "bench.window":
                        continue
                    if name.startswith("bench."):
                        kinds["bench"].append((s, s + d, name))
                    elif name.startswith("$"):
                        if name[1:].split(":", 1)[0] in files:
                            kinds["frame"].append((s, s + d, name[1:]))
                    else:
                        kinds["runtime"].append((s, s + d, name))
        self.kinds = {}
        for k, rows in kinds.items():
            self.kinds[k] = (np.array([r[0] for r in rows], np.int64),
                             np.array([r[1] for r in rows], np.int64),
                             [r[2] for r in rows])

    def innermost(self, kind: str, t: int):
        """Name of the span of ``kind`` open at ``t`` that began last."""
        import numpy as np

        starts, ends, names = self.kinds[kind]
        if not len(names):
            return None
        open_ = np.nonzero((starts <= t) & (ends > t))[0]
        if not len(open_):
            return None
        return names[int(open_[np.argmax(starts[open_])])]

    def blame(self, t: int) -> str:
        """``<bench annotation>|<program call or runtime span>`` at ``t``."""
        bench = self.innermost("bench", t) or "-"
        what = (self.innermost("frame", t) or self.innermost("runtime", t)
                or "no span open")
        return f"{bench}|{what}"


def reduce(planes: list, window=None, program_files=None) -> dict:
    chips = [p for p in planes if _is_chip(p["name"])]
    if not chips:
        raise ValueError("the trace holds no /device:TPU:<n> plane: "
                         f"{[p['name'] for p in planes]}")
    per_chip = [[e for e in _line(p, "XLA Ops") if e[2] > 0] for p in chips]
    # collectives issued asynchronously span start -> done on a line of
    # their own
    per_chip_async = [[e for e in _line(p, "Async XLA Ops") if e[2] > 0]
                      for p in chips]
    if window is None:
        window = host_window(planes)
    if window is None:
        lo = min(e[1] for ops in per_chip for e in ops)
        hi = max(e[1] + e[2] for ops in per_chip for e in ops)
    else:
        lo, hi = window
    window_ns = hi - lo

    busy, exposed = [], []
    for ops, asyncs in zip(per_chip, per_chip_async):
        every = _clip(_union([[e[1], e[1] + e[2]] for e in ops]), lo, hi)
        busy.append(_length(every))
        coll = _clip(_union([[e[1], e[1] + e[2]] for e in ops + asyncs
                             if is_collective(e[0])]), lo, hi)
        rest = _clip(_union([[e[1], e[1] + e[2]] for e in ops
                             if not is_collective(e[0])
                             and base_name(e[0]) not in CONTAINERS]), lo, hi)
        exposed.append(_length(_subtract(coll, rest)))

    # operation self time, chip 0 (every chip of a data mesh runs the
    # same program)
    ops0 = [e for e in per_chip[0] if lo <= e[1] < hi]
    table = defaultdict(lambda: {"seconds": 0.0, "count": 0})
    loop_runs = 0
    for ev, self_ns, in_loop in self_times(ops0):
        if base_name(ev[0]) == "while" and not in_loop:
            loop_runs += 1
        hlo, body = split_hlo(ev[0])
        row = table[f"{hlo}:{signature(body)}"]
        row["seconds"] += self_ns / 1e9
        row["count"] += 1
        row["in_loop"] = in_loop or base_name(ev[0]) == "while"
        row.setdefault("name", hlo)
        row.setdefault("long_name", body)
    device_ops = sorted(([k[:64], v["seconds"]] for k, v in table.items()),
                        key=lambda kv: -kv[1])

    # idle gaps of chip 0, booked to what the host was doing
    merged = _clip(_union([[e[1], e[1] + e[2]] for e in per_chip[0]]), lo, hi)
    holes = _subtract([[lo, hi]], merged)
    host = HostSpans(planes, program_files)
    gaps = defaultdict(float)
    for s, e in holes:
        if e - s < SMALL_GAP_NS:
            gaps[SMALL_GAPS] += (e - s) / 1e9
        else:
            gaps[host.blame((s + e) // 2)] += (e - s) / 1e9
    idle_gaps = sorted(([re.sub(r"[^A-Za-z0-9_.:|\-]+", "_", k)[:64], v]
                        for k, v in gaps.items()), key=lambda kv: -kv[1])

    modules = defaultdict(lambda: {"seconds": 0.0, "count": 0})
    for name, s, d, _ in _line(chips[0], "XLA Modules"):
        if lo <= s < hi:
            m = modules[re.sub(r"\(\d+\)$", "", name)]
            m["seconds"] += d / 1e9
            m["count"] += 1
    return {"chips": len(chips), "window_s": window_ns / 1e9,
            "busy_s": sum(busy) / len(busy) / 1e9,
            "busy_s_by_chip": [b / 1e9 for b in busy],
            "exposed_collective_s_by_chip": [x / 1e9 for x in exposed],
            "ops": dict(table), "modules": dict(modules),
            "loop_runs": loop_runs,
            "device_ops": device_ops, "idle_gaps": idle_gaps}


def reduce_file(path: str, window=None) -> dict:
    return reduce(load(path), window)


def save_slice(path: str, out: str, seconds: float = 0.5,
               min_host_ns: int = 20_000):
    """Cut a small recording out of a trace for the tests: the chips'
    ``XLA Ops`` / ``XLA Modules`` events and the host spans of at least
    ``min_host_ns`` that start in the first ``seconds`` after the first
    device operation; written as gzipped JSON in ``load``'s format."""
    import gzip
    import json

    planes = load(path)
    t0 = min(e[1] for p in planes if _is_chip(p["name"])
             for e in _line(p, "XLA Ops"))
    t1 = t0 + int(seconds * 1e9)
    kept = []
    for p in planes:
        chip, host = _is_chip(p["name"]), p["name"].startswith("/host")
        if not (chip or host):
            continue
        lines = []
        for ln in p["lines"]:
            if chip and ln["name"] not in ("XLA Ops", "XLA Modules"):
                continue
            ev = [[n, s, d, {}] for n, s, d, st in ln["events"]
                  if t0 <= s < t1 and (chip or d >= min_host_ns)]
            if ev:
                lines.append({"name": ln["name"], "events": ev})
        kept.append({"name": p["name"], "lines": lines})
    with gzip.open(out, "wt") as f:
        json.dump(kept, f, separators=(",", ":"))


def load_slice(path: str) -> list:
    import gzip
    import json

    with gzip.open(path, "rt") as f:
        return json.load(f)
