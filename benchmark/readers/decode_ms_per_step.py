"""Device time inside the decode loop (the ``lax.scan`` body) per scan
step: traced self seconds of operations nested in a ``while``, over
executions x (max_new - 1)."""
from benchmark.readers._common import loop_seconds


def read(ctx):
    s = ctx.trace_summary
    if not s:
        return None
    inside, _ = loop_seconds(s)
    steps = s.get("loop_runs", 0) * (ctx.run["shapes"]["max_new"] - 1)
    return 1e3 * inside / steps if steps and inside > 0 else None
