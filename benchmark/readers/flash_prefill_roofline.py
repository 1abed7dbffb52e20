"""The forward flash kernel of the prefill: least time for its operations
and bytes over its traced time.  At T 2048, head 128 the compute peak
binds."""
from benchmark.readers._common import flash_seconds, main_module


def read(ctx):
    s = ctx.trace_summary
    mod = main_module(s)
    if mod is None or ctx.peaks is None:
        return None
    m = ctx.counts.dims(ctx.config)
    sh = ctx.run["shapes"]
    seconds = flash_seconds(s, m["head_dim"])
    if seconds <= 0:
        return None
    c = ctx.counts.flash_call(sh["max_batch"] * m["heads"],
                              sh["prompt_len"], m["head_dim"])
    least = ctx.counts.roofline_seconds(c["fwd_flops"], c["fwd_bytes"],
                                        ctx.peaks)[0]
    return 100.0 * least * m["layers"] * mod[2] / seconds
