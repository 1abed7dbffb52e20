"""Share of chip 0's busy time the decode steps spend in the attention
sublayer: self time of operations under ``block.attention`` inside
``generate.decode_step`` (projections, rotation, the one-position cache
write, the attend over ring or cache, the output projection; every
layer) over the busy seconds of the traced window.  A program without
the scope gives nothing to read."""
from benchmark.readers import _moe_scopes

SCOPE = "block.attention/"


def read(ctx):
    rows = _moe_scopes._events(ctx)
    summary = getattr(ctx, "trace_summary", None)
    if not rows or not summary or summary["busy_s"] <= 0:
        return None
    ns = sum(ns for ev, ns in rows
             if _moe_scopes._under(ev, _moe_scopes.STEP, SCOPE))
    return 100.0 * ns / 1e9 / summary["busy_s"] if ns else None
