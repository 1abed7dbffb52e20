"""Median seconds between whole-step boundaries of the training driver
(host clock at the optimizer's end-trigger call, which follows the loss
fetch), in milliseconds."""
from benchmark.readers._common import percentile


def read(ctx):
    p50 = percentile(ctx.run["spans"].get("step_s", []), 50)
    return None if p50 is None else 1e3 * p50
