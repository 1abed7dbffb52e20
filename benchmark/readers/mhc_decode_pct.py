"""Share of a decode step's device time spent in the hyper-connections:
self time a step of the operations under ``mhc.coeffs``,
``mhc.sinkhorn``, ``mhc.pre`` and ``mhc.post`` inside
``generate.decode_step`` (two sublayers a layer, every layer) over the
traced time of a step (``_moe_scopes.step_seconds``).  What they must
read from HBM is 0.2 % of the step's bytes
(``counts_xing4_0.decode_step_parts``: ``phi``) and what they touch 3.8
%; what this reads is the latency of some twenty small operations a
sublayer.  A program without the ``mhc.*`` scopes gives nothing to
read."""
from benchmark.readers import _moe_scopes, _xing_scopes


def read(ctx):
    sh = _xing_scopes.shapes(ctx)
    if sh is None:
        return None
    layers = sh[0]["expert_layers"]
    mhc = _xing_scopes.mhc_step_seconds(ctx, layers)
    step = _moe_scopes.step_seconds(ctx, layers)
    if not mhc or not step:
        return None
    return 100.0 * mhc / step
