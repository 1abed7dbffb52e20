"""The grouped products of the experts in the decode steps of the
SmallThinker block: least time for ONE expert layer's operations and
bytes (``counts_smallthinker.expert_matmul_call`` for the mean
dispatched bucket, at whichever peak binds: 192 buffer rows over
``[64, 2560, 768]``) times the expert layers, over the traced self time
a step of the grouped products inside the decode scans
(``_st_scopes.expert_matmul_step_seconds``: what lies under
``moe.expert_matmul`` and the compiler's own ``ragged-dot`` calls, which
name no scope; steps counted from the scans).  A reading over 100 % is a wrong count, not a fast kernel."""
from benchmark import counts_smallthinker
from benchmark.readers import _st_scopes


def read(ctx):
    sh = _st_scopes.shapes(ctx)
    if sh is None:
        return None
    m, rows, _ = sh
    seconds = _st_scopes.expert_matmul_step_seconds(ctx)
    if not seconds:
        return None
    call = counts_smallthinker.expert_matmul_call(ctx.config, rows)
    least = ctx.counts.roofline_seconds(call["flops"], call["bytes"],
                                        ctx.peaks)[0]
    return 100.0 * least * m["expert_layers"] / seconds
