"""p95 over p50 of the whole-step times: how much the driver's loop
(feed, dispatch, loss fetch) disturbs an otherwise constant step."""
from benchmark.readers._common import percentile


def read(ctx):
    steps = ctx.run["spans"].get("step_s", [])
    if len(steps) < 10:
        return None
    return percentile(steps, 95) / percentile(steps, 50)
