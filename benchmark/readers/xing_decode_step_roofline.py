"""Bytes one decode step of the hyper-connected latent expert block must
move (bf16 weights of the latent path, the dense FFN, routers, shared
and HIT held experts and the head once; the latent cache at the step's
mean context; the float32 logits once; and every sublayer's ``phi``
once — the four streams live inside the step and count nothing; for the
mean dispatched bucket:
``counts_xing4_0.decode_step_bytes``) at the chip's memory bandwidth,
over the traced time of a step (``_moe_scopes.step_seconds``: the decode
scans' own duration over the steps they make, or the self time of a
step's operations over the steps the trace holds, whichever is longer).
Memory binds: a step multiplies at most 256 rows by every weight it
reads."""
from benchmark import counts_xing4_0
from benchmark.readers import _moe_scopes, _xing_scopes


def read(ctx):
    sh = _xing_scopes.shapes(ctx)
    if sh is None:
        return None
    m, rows, context = sh
    seconds = _moe_scopes.step_seconds(ctx, m["expert_layers"])
    if not seconds:
        return None
    nbytes = counts_xing4_0.decode_step_bytes(ctx.config, rows, context)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / seconds
