"""The grouped products of the held experts in the decode steps of the
hyper-connected latent expert block: least time for ONE expert layer's
operations and bytes (``counts_xing4_0.expert_matmul_call`` for the mean
dispatched bucket, at whichever peak binds) times the expert layers,
over the traced self time of a step's operations under
``moe.expert_matmul`` inside ``generate.decode_step`` — as
``glm_expert_matmul_roofline``, at this configuration's shapes (32 held
experts of width 1024).  A reading over 100 % is a wrong count, not a
fast kernel."""
from benchmark import counts_xing4_0
from benchmark.readers import _moe_scopes, _xing_scopes


def read(ctx):
    sh = _xing_scopes.shapes(ctx)
    if sh is None:
        return None
    m, rows, _ = sh
    seconds = _moe_scopes.expert_matmul_step_seconds(ctx, m["expert_layers"])
    if not seconds:
        return None
    call = counts_xing4_0.expert_matmul_call(ctx.config, rows)
    least = ctx.counts.roofline_seconds(call["flops"], call["bytes"],
                                        ctx.peaks)[0]
    return 100.0 * least * m["expert_layers"] / seconds
