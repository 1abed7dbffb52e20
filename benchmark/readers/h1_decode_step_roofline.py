"""Bytes one decode step of the hybrid block must move (bf16 weights
once; the K/V of the positions it attends to; the SSM state and conv
tail read AND written, for the mean dispatched bucket —
``counts_falcon_h1.decode_step_bytes``) at the chip's memory bandwidth,
over the traced time of a step.  Memory binds: a step multiplies at
most 64 rows by every weight."""
from benchmark import counts_falcon_h1
from benchmark.readers import decode_ms_per_step


def read(ctx):
    ms = decode_ms_per_step.read(ctx)
    c = ctx.run["counters"]
    if ms is None or ctx.peaks is None or not c.get("batches"):
        return None
    sh = ctx.run["shapes"]
    rows = (c["real_rows"] + c["padded_rows"]) / c["batches"]
    nbytes = counts_falcon_h1.decode_step_bytes(
        ctx.config, rows, sh["prompt_len"] + sh["max_new"] / 2)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (ms / 1e3)
