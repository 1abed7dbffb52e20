"""What the three readers of the short-convolution expert block share:
the cell's shapes as ``counts_lfm2_moe`` wants them.  A decode step's
time and a scope's self time a step come from ``_moe_scopes`` (the steps
the trace holds are counted from the grouped products of the EXPERT
layers, three a layer: a leading dense layer has none)."""
from benchmark import counts_lfm2_moe
from benchmark.readers import _moe_scopes


def shapes(ctx):
    """(dims, rows of the mean dispatched bucket, mean context of a
    decode step) or None where no batch was dispatched."""
    rows = _moe_scopes.mean_bucket_rows(ctx)
    if rows is None or ctx.peaks is None:
        return None
    sh = ctx.run["shapes"]
    return (counts_lfm2_moe.dims(ctx.config), rows,
            sh["prompt_len"] + sh["max_new"] / 2)
