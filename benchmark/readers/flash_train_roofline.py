"""Forward + dKdV + dQ flash kernels: the least time the chip could take
for their operations and bytes (benchmark/counts.py) over their traced
time.  At T 1024, head 64, bf16 the compute peak binds."""
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
    c = ctx.counts.flash_call(sh["batch"] // sh["chips"] * m["heads"],
                              sh["seq"], m["head_dim"])
    least = (ctx.counts.roofline_seconds(c["fwd_flops"], c["fwd_bytes"],
                                         ctx.peaks)[0]
             + ctx.counts.roofline_seconds(c["bwd_flops"], c["bwd_bytes"],
                                           ctx.peaks)[0])
    return 100.0 * least * m["layers"] * mod[2] / seconds
