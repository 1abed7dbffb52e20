"""Milliseconds of one training step that chip 0 spends under the device
scope ``block.attention`` — a block's first norm, the attention module
(projections, the attention itself, output projection) and the residual
add, forward and backward: operation self time over the step program's
whole executions in the traced window (``_train_scopes``)."""
from benchmark.readers import _train_scopes


def read(ctx):
    return _train_scopes.ms_per_step(ctx, "block.attention")
