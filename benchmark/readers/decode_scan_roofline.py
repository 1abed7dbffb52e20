"""Bytes one decode step must read (bf16 weights once, plus the K/V of
the positions it attends to, for the mean dispatched bucket) at the
chip's memory bandwidth, over the traced time of a step.  Memory binds:
a step multiplies at most 16 rows by every weight."""
from benchmark.readers import decode_ms_per_step


def read(ctx):
    ms = decode_ms_per_step.read(ctx)
    c = ctx.run["counters"]
    if ms is None or ctx.peaks is None or not c.get("batches"):
        return None
    sh = ctx.run["shapes"]
    rows = (c["real_rows"] + c["padded_rows"]) / c["batches"]
    nbytes = ctx.counts.decode_step_bytes(
        ctx.config, rows, sh["prompt_len"] + sh["max_new"] / 2)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (ms / 1e3)
