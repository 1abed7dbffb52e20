"""Milliseconds of one training step under the device scope
``step.update``: every pass over the parameter tree after the gradients
are reduced — the gradient norm, the optimizer method's step, the
finiteness guard and its selects (``_train_scopes``)."""
from benchmark.readers import _train_scopes


def read(ctx):
    return _train_scopes.ms_per_step(ctx, "step.update")
