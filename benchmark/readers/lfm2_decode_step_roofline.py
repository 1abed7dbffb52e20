"""Bytes one greedy decode step of the short-convolution expert block
must move (bf16: every conv layer's leaves and its tail read and
written, the ONE attention layer in four's leaves and its K/V at the
step's mean context, the dense FFN, routers, each HIT expert once, the
tied matrix once as the head and the rows the lookup gathers; no
logits; for the mean dispatched bucket:
``counts_lfm2_moe.decode_step_bytes``) at the chip's memory bandwidth,
over the traced time of a step (``_moe_scopes.step_seconds``: the decode
scans' own duration over the steps they make, or the self time of a
step's operations over the steps the trace holds, whichever is longer).
Memory binds: a step multiplies at most 256 rows by every weight it
reads."""
from benchmark import counts_lfm2_moe
from benchmark.readers import _lfm2_scopes, _moe_scopes


def read(ctx):
    sh = _lfm2_scopes.shapes(ctx)
    if sh is None:
        return None
    m, rows, context = sh
    seconds = _moe_scopes.step_seconds(ctx, m["expert_layers"])
    if not seconds:
        return None
    nbytes = counts_lfm2_moe.decode_step_bytes(ctx.config, rows, context)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / seconds
