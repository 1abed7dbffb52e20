"""What the readers of the SmallThinker cell share: the cell's shapes as
``counts_smallthinker`` wants them, and a scope's self time a decode
step with the steps counted from the decode SCANS (``_moe_scopes.
decode_scans``: each makes ``max_new - 1`` steps and lies whole in the
window, whose end is the drain's) — not from Mosaic grouped products,
which a buffer that takes ``grouped_matmul``'s ``ragged`` arm has none
of.  On the v5e a ``ragged_dot`` is a custom call the compiler
names ``ragged-dot-*`` and gives no ``op_name`` of the program's (its
scope reads ``ragged-dot-none:``), so the grouped products of a step are
the operations INSIDE a scan that lie under ``moe.expert_matmul`` or
bear that name."""
import bisect

from benchmark import counts_smallthinker
from benchmark.readers import _moe_scopes


def shapes(ctx):
    """(dims, rows of the mean dispatched bucket, mean context of a
    decode step) or None where no batch was dispatched."""
    rows = _moe_scopes.mean_bucket_rows(ctx)
    if rows is None or ctx.peaks is None:
        return None
    sh = ctx.run["shapes"]
    return (counts_smallthinker.dims(ctx.config), rows,
            sh["prompt_len"] + sh["max_new"] / 2)


def steps(ctx) -> int:
    """Decode steps the window's scans make."""
    scans = _moe_scopes.decode_scans(ctx)
    return len(scans or ()) * (ctx.run["shapes"]["max_new"] - 1)


def scope_step_seconds(ctx, *scopes):
    """Self seconds a decode step of the operations under
    ``generate.decode_step`` and ``scopes``; None where the trace names
    no such operation or holds no scan."""
    n = steps(ctx)
    rows = _moe_scopes._events(ctx)
    if not n or rows is None:
        return None
    ns = sum(ns for ev, ns in rows
             if _moe_scopes._under(ev, _moe_scopes.STEP, *scopes))
    return ns / 1e9 / n if ns else None


def in_scan_rows(ctx):
    """[(event, self ns)] of the operations that start inside a decode
    scan (the scan's own event among them), or None where the trace
    holds no scan."""
    rows = _moe_scopes._events(ctx)
    scans = _moe_scopes.decode_scans(ctx)
    if not rows or not scans:
        return None
    starts = [s for s, _ in scans]
    out = []
    for ev, self_ns in rows:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < scans[i][1]:
            out.append((ev, self_ns))
    return out


def expert_matmul_step_seconds(ctx):
    """Self seconds a decode step of the experts' grouped products and
    the gate between them: the operations inside a decode scan that lie
    under ``moe.expert_matmul`` (the Mosaic arms, the gate) or are the
    compiler's own ``ragged-dot`` calls (the ``ragged`` arm, which names
    no scope)."""
    n, rows = steps(ctx), in_scan_rows(ctx)
    if not n or not rows:
        return None
    ns = sum(self_ns for ev, self_ns in rows
             if ev[0].startswith("%ragged-dot")
             or _moe_scopes._under(ev, _moe_scopes.STEP,
                                   "moe.expert_matmul/"))
    return ns / 1e9 / n if ns else None
