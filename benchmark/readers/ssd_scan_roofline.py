"""The chunked SSD scan of the prefill: least time for its operations
and bytes (``counts_falcon_h1.ssd_scan_call``, at whichever peak binds)
over the traced self time of the operations under the ``mixer.ssd_scan``
scope — one call a layer and executed batch, every batch a full bucket.
A reading over 100 % is a wrong count, not a fast scan."""
from benchmark import counts_falcon_h1
from benchmark.readers import _program_spans
from benchmark.readers._common import main_module


def read(ctx):
    mod = main_module(getattr(ctx, "trace_summary", None))
    seconds = _program_spans.scope_seconds(ctx, "mixer.ssd_scan")
    if mod is None or ctx.peaks is None or not seconds:
        return None
    sh = ctx.run["shapes"]
    c = counts_falcon_h1.ssd_scan_call(ctx.config, sh["max_batch"],
                                       sh["prompt_len"])
    least = ctx.counts.roofline_seconds(c["flops"], c["bytes"], ctx.peaks)[0]
    calls = counts_falcon_h1.dims(ctx.config)["layers"] * mod[2]
    return 100.0 * least * calls / seconds
