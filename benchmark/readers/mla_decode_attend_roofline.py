"""The absorbed attend of the decode steps: least time for ONE layer's
operations and bytes (``counts_glm4_moe_lite.attend_call`` for the mean
dispatched bucket at the step's mean context, at whichever peak binds —
the cached latent and shared key once a layer and step) times the
layers, over the traced self time of a step's operations under
``mla.attend`` inside ``generate.decode_step`` (the steps counted as
``_moe_scopes`` counts them).  A program that reads its whole static
cache reads more than the count: the extra is its loss.  A reading over
100 % is a wrong count, not a fast product."""
from benchmark import counts_glm4_moe_lite
from benchmark.readers import _mla_scopes, _moe_scopes


def read(ctx):
    sh = _mla_scopes.shapes(ctx)
    if sh is None:
        return None
    m, rows, context = sh
    seconds = _moe_scopes._scope_step_seconds(ctx, m["expert_layers"],
                                              "mla.attend/")
    if not seconds:
        return None
    call = counts_glm4_moe_lite.attend_call(ctx.config, rows, context)
    least = ctx.counts.roofline_seconds(call["flops"], call["bytes"],
                                        ctx.peaks)[0]
    return 100.0 * least * m["layers"] / seconds
