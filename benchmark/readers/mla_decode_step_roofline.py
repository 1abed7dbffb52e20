"""Bytes one decode step of the latent expert block must move (bf16
weights of the latent path, the dense FFN, routers, shared and HIT held
experts and the head once; the latent cache — ``kv_lora_rank + rope``
numbers a position and layer — at the step's mean context; the float32
logits once; for the mean dispatched bucket:
``counts_glm4_moe_lite.decode_step_bytes``) at the chip's memory
bandwidth, over the traced time of a step (``_moe_scopes.step_seconds``:
the decode scans' own duration over the steps they make, or the self
time of a step's operations over the steps the trace holds, whichever
is longer).  Memory binds: a step multiplies at most 256 rows by every
weight it reads."""
from benchmark import counts_glm4_moe_lite
from benchmark.readers import _mla_scopes, _moe_scopes


def read(ctx):
    sh = _mla_scopes.shapes(ctx)
    if sh is None:
        return None
    m, rows, context = sh
    seconds = _moe_scopes.step_seconds(ctx, m["expert_layers"])
    if not seconds:
        return None
    nbytes = counts_glm4_moe_lite.decode_step_bytes(ctx.config, rows, context)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / seconds
