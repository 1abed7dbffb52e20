"""Bytes one greedy decode step of the SmallThinker block must move
(bf16: the attention leaves, the K/V its attends read — three whole
rings and the global layer's written part at the step's mean context —
each HIT expert once, routers and norms, the head once and the rows the
lookup gathers; no logits; for the mean dispatched bucket:
``counts_smallthinker.decode_step_bytes``) at the chip's memory
bandwidth, over the traced time of a step (``_moe_scopes.step_seconds``:
the decode scans' own duration over the steps they make, or the self
time of a step's operations over the steps the trace holds, whichever
is longer).  Memory binds: a step multiplies at most 32 rows by every
weight it reads."""
from benchmark import counts_smallthinker
from benchmark.readers import _moe_scopes, _st_scopes


def read(ctx):
    sh = _st_scopes.shapes(ctx)
    if sh is None:
        return None
    m, rows, context = sh
    seconds = _moe_scopes.step_seconds(ctx, m["expert_layers"])
    if not seconds:
        return None
    nbytes = counts_smallthinker.decode_step_bytes(ctx.config, rows, context)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / seconds
