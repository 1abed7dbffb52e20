"""Milliseconds of one training step under the device scopes ``lm.head``
(final norm, vocabulary head, ``log_softmax`` where it runs) and
``step.loss`` (the criterion), forward and backward (``_train_scopes``).
The optimizer's pass over the two vocabulary tables is not in it: that
books to ``step.update``."""
from benchmark.readers import _train_scopes


def read(ctx):
    return _train_scopes.ms_per_step(ctx, "lm.head", "step.loss")
