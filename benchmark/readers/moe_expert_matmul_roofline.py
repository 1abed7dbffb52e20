"""The grouped products of the held experts in the decode steps: least
time for ONE layer's operations and bytes (``counts_command_a_plus
.expert_matmul_call`` for the mean dispatched bucket, at whichever peak
binds — the hit experts' weights once a layer and step) times the
layers, over the traced self time of a step's operations under
``moe.expert_matmul`` inside ``generate.decode_step``
(``_moe_scopes.expert_matmul_step_seconds``: the steps are counted from
the grouped products the trace holds, three a layer).  A reading over
100 % is a wrong count, not a fast kernel."""
from benchmark import counts_command_a_plus
from benchmark.readers import _moe_scopes


def read(ctx):
    layers = counts_command_a_plus.dims(ctx.config)["layers"]
    seconds = _moe_scopes.expert_matmul_step_seconds(ctx, layers)
    rows = _moe_scopes.mean_bucket_rows(ctx)
    if not seconds or ctx.peaks is None or rows is None:
        return None
    call = counts_command_a_plus.expert_matmul_call(ctx.config, rows)
    least = ctx.counts.roofline_seconds(call["flops"], call["bytes"],
                                        ctx.peaks)[0]
    return 100.0 * least * layers / seconds
