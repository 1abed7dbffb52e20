"""Forward operations of the prompts (head at the last position only)
over the traced device time OUTSIDE the decode loop, over the peak."""
from benchmark.readers._common import loop_seconds, main_module


def read(ctx):
    s = ctx.trace_summary
    mod = main_module(s)
    if mod is None or ctx.peaks is None:
        return None
    _, outside = loop_seconds(s)
    if outside <= 0:
        return None
    sh = ctx.run["shapes"]
    flops = mod[2] * ctx.counts.forward_flops(
        ctx.config, sh["max_batch"], sh["prompt_len"], head_positions=1)
    return 100.0 * flops / outside / ctx.peaks["bf16_flops_per_s"]
