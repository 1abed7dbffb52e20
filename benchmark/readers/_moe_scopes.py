"""What the two roofline readers of the parallel expert block take from
a traced window — from the device events themselves, so that neither
depends on how completely the events of a loop's body were recorded:

* the decode scans are the ``while`` events that lie in no other
  ``while`` and hold operations under ``generate.decode_step`` (a step
  has small loops of its own, nested in the scan); a scan makes
  ``max_new - 1`` steps, so a step's time is the scans' DURATION over
  their steps — gaps and operations that name no scope included;
* the grouped products are the Mosaic custom calls under both
  ``generate.decode_step`` and ``moe.expert_matmul``: three a layer and
  step, so the steps that the trace HOLDS are counted from them, and a
  scope's self time is divided by that count;
* a step's time is read both ways — the scans' duration over their
  steps, and the self time of every operation under
  ``generate.decode_step`` over the steps the trace holds — and the
  LONGER reading is the step: the first leaves nothing of a step out,
  the second does not depend on the scans' own events; a trace that
  lost events shortens one or the other, and lengthens the second only
  as far as it lost grouped products more than the rest.

None where the trace names no scope (a program without the scopes, a
run without a trace) or holds no such event."""
import bisect

from benchmark import trace_reduce
from benchmark.readers import _program_spans

STEP = "generate.decode_step/"
PRODUCTS_PER_LAYER = 3  # gate, up, down
MOSAIC = 'custom_call_target="tpu_custom_call"'


def _events(ctx):
    """[(event, self ns)] of chip 0's operations in the window, the
    event as ``_program_spans.extract`` keeps it (name, start, duration,
    {"scope": op_name}); one pass, kept beside the spans."""
    spans = _program_spans.load(ctx)
    if not spans or not spans["chip_events"] or not spans["window"]:
        return None
    if "moe_events" not in spans:
        lo, hi = spans["window"]
        events = [e for e in spans["chip_events"] if lo <= e[1] < hi]
        spans["moe_events"] = [
            (ev, self_ns)
            for ev, self_ns, _ in trace_reduce.self_times(events)
        ] if any(e[3]["scope"] for e in events) else None
    return spans["moe_events"]


def _under(ev, *scopes) -> bool:
    path = ev[3]["scope"] + "/"
    return all(s in path for s in scopes)


def decode_scans(ctx):
    """[(start ns, end ns)] of the decode scans in the window: the
    ``while`` events that lie in no other ``while`` and hold an
    operation under ``generate.decode_step`` (a ``while`` event names no
    scope of its own on the v5e, so the step's small loops are told from
    the scan by lying inside it)."""
    rows = _events(ctx)
    if rows is None:
        return None
    starts = sorted(ev[1] for ev, _ in rows if _under(ev, STEP))
    loops = sorted(((ev[1], ev[1] + ev[2]) for ev, _ in rows
                    if trace_reduce.base_name(ev[0]) == "while"),
                   key=lambda se: (se[0], -se[1]))
    scans, outer_end = [], -1
    for s, e in loops:
        if s < outer_end:
            continue            # inside the loop before it
        outer_end = e
        i = bisect.bisect_left(starts, s)
        if i < len(starts) and starts[i] < e:
            scans.append((s, e))
    return scans


def _scope_step_seconds(ctx, layers: int, *scopes):
    """Self seconds a decode step of the operations under
    ``generate.decode_step`` and ``scopes``: their self time over the
    steps counted from the grouped products the trace holds."""
    rows = _events(ctx)
    if rows is None:
        return None
    products = sum(1 for ev, _ in rows if MOSAIC in ev[0]
                   and _under(ev, STEP, "moe.expert_matmul/"))
    if not products:
        return None
    steps = products / (PRODUCTS_PER_LAYER * layers)
    return sum(ns for ev, ns in rows if _under(ev, STEP, *scopes)) \
        / 1e9 / steps


def step_seconds(ctx, layers: int):
    """Device seconds of one decode step: the longer of the scans'
    duration over the steps they make and the self time of a step's
    operations over the steps the trace holds."""
    scans = decode_scans(ctx)
    steps = len(scans or ()) * (ctx.run["shapes"]["max_new"] - 1)
    readings = [sum(e - s for s, e in scans) / 1e9 / steps if steps
                else None, _scope_step_seconds(ctx, layers)]
    return max((r for r in readings if r), default=None)


def expert_matmul_step_seconds(ctx, layers: int):
    """Self seconds under ``moe.expert_matmul`` of one decode step."""
    return _scope_step_seconds(ctx, layers, "moe.expert_matmul/")


def mean_bucket_rows(ctx):
    """Rows of the mean dispatched bucket (real and padded), or None
    where no batch was dispatched in the window."""
    c = ctx.run["counters"]
    if not c.get("batches"):
        return None
    return (c["real_rows"] + c["padded_rows"]) / c["batches"]
