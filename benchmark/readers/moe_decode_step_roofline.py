"""Bytes one decode step of the parallel expert block must move (bf16
attention, router, shared-expert and head weights once; each HIT held
expert once; the K/V each kind of layer attends to, for the mean
dispatched bucket — ``counts_command_a_plus.decode_step_bytes``) at the
chip's memory bandwidth, over the traced time of a step
(``_moe_scopes.step_seconds``: the decode scans' own duration over the
steps they make, or the self time of a step's operations over the steps
the trace holds, whichever is longer — no part of a step is left out
whatever the trace holds of a scan's body).  Memory binds: a step
multiplies at most 128 rows by every weight it reads."""
from benchmark import counts_command_a_plus
from benchmark.readers import _moe_scopes


def read(ctx):
    seconds = _moe_scopes.step_seconds(
        ctx, counts_command_a_plus.dims(ctx.config)["layers"])
    rows = _moe_scopes.mean_bucket_rows(ctx)
    if not seconds or ctx.peaks is None or rows is None:
        return None
    sh = ctx.run["shapes"]
    nbytes = counts_command_a_plus.decode_step_bytes(
        ctx.config, rows, sh["prompt_len"] + sh["max_new"] / 2)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / seconds
