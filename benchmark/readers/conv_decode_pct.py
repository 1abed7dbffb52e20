"""Share of chip 0's busy time the decode steps spend in the gated short
convolution: self time of operations under ``block.conv`` inside
``generate.decode_step`` (the product that gives ``B``, ``C`` and ``u``,
the gate, the three-tap sum over the tail, the tail's shift and the
output projection, every conv layer) over the busy seconds of the traced
window.  A program without the ``block.conv`` scope gives nothing to
read."""
from benchmark.readers import _moe_scopes

SCOPE = "block.conv/"


def read(ctx):
    rows = _moe_scopes._events(ctx)
    summary = getattr(ctx, "trace_summary", None)
    if not rows or not summary or summary["busy_s"] <= 0:
        return None
    if not any(_moe_scopes._under(ev, SCOPE) for ev, _ in rows):
        return None
    ns = sum(ns for ev, ns in rows
             if _moe_scopes._under(ev, _moe_scopes.STEP, SCOPE))
    return 100.0 * ns / 1e9 / summary["busy_s"]
