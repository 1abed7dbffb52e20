"""Milliseconds of one training step under the device scope
``step.grad_reduce`` — the gradients' collectives over the mesh and the
reduce of the loss and the buffers: chip 0's own time in them, hidden
behind other work or not.  Beside ``train_collective_exposed_pct`` it
says how much of the reduce the backward already hides
(``_train_scopes``)."""
from benchmark.readers import _train_scopes


def read(ctx):
    return _train_scopes.ms_per_step(ctx, "step.grad_reduce")
