"""What the readers of the hyper-connected latent expert block share:
the cell's shapes as ``counts_xing4_0`` wants them, and the self time a
decode step spends under the ``mhc.*`` scopes.  A step's time and a
scope's self time a step come from ``_moe_scopes`` (the steps the trace
holds are counted from the grouped products of the EXPERT layers, three
a layer: the leading dense layer has none)."""
from benchmark import counts_xing4_0
from benchmark.readers import _moe_scopes

MHC = "mhc."


def shapes(ctx):
    """(dims, rows of the mean dispatched bucket, mean context of a
    decode step) or None where no batch was dispatched."""
    rows = _moe_scopes.mean_bucket_rows(ctx)
    if rows is None or ctx.peaks is None:
        return None
    sh = ctx.run["shapes"]
    return (counts_xing4_0.dims(ctx.config), rows,
            sh["prompt_len"] + sh["max_new"] / 2)


def mhc_step_seconds(ctx, expert_layers: int):
    """Self seconds a decode step of the operations under ``mhc.coeffs``,
    ``mhc.sinkhorn``, ``mhc.pre`` and ``mhc.post`` inside
    ``generate.decode_step``, every sublayer; None where the program
    names no such scope."""
    return _moe_scopes._scope_step_seconds(ctx, expert_layers, MHC) or None
