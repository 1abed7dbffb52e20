"""Share of chip 0's busy time spent around the experts rather than in
them: self time of operations under ``moe.route`` (scores, top-k,
gates), ``moe.dispatch`` (sort, gather into the sorted buffer) and
``moe.combine`` (the weighted gather back), prefill and decode alike,
over the busy seconds of the traced window — the latency-bound part
beside the memory-bound one."""
from benchmark.readers import _program_spans


def read(ctx):
    summary = getattr(ctx, "trace_summary", None)
    parts = [_program_spans.scope_seconds(ctx, s)
             for s in ("moe.route", "moe.dispatch", "moe.combine")]
    if not summary or summary["busy_s"] <= 0 or not any(parts):
        return None
    return 100.0 * sum(p or 0.0 for p in parts) / summary["busy_s"]
