"""The flash forward calls of the prompt pass under a window: the
operations of one group's pass through every layer
(``counts_smallthinker.prefill_attention_flops``: ``Q K^T`` and ``P V``
over the pairs the mask leaves — the last 4096 keys in three layers of
four, every key in the global one) for as many passes as the trace
holds, at the chip's bf16 peak, over the self time of the Mosaic calls
under ``block.attention`` inside ``generate.prefill``.  Compute binds
at 4608 positions and a head of 128.  The passes are counted from the
calls themselves, one a layer.  A reading over 100 % is a wrong count."""
from benchmark import counts_smallthinker
from benchmark.readers import _moe_scopes, _st_scopes

PREFILL = "generate.prefill/"
ATTENTION = "block.attention/"


def read(ctx):
    sh = _st_scopes.shapes(ctx)
    rows_ev = _moe_scopes._events(ctx)
    if sh is None or not rows_ev:
        return None
    m, rows, _ = sh
    calls = [ns for ev, ns in rows_ev if _moe_scopes.MOSAIC in ev[0]
             and _moe_scopes._under(ev, PREFILL, ATTENTION)]
    if not calls or sum(calls) <= 0:
        return None
    t = ctx.run["shapes"]["prompt_len"]
    group = counts_smallthinker.prefill_group_rows(int(round(rows)), t)
    flops = counts_smallthinker.prefill_attention_flops(ctx.config, group, t)
    passes = len(calls) / m["layers"]
    least = flops * passes / ctx.peaks["bf16_flops_per_s"]
    return 100.0 * least / (sum(calls) / 1e9)
