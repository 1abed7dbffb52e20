"""Milliseconds of one training step under the device scope
``attention.core`` — the attention itself inside ``MultiHeadAttention``,
forward and backward (the three flash kernels where ``seq_strategy`` is
``flash``), without projections and rotation: the kernels found by the
scope they run under, where ``flash_train_roofline`` finds them by the
shape of their operands (``_train_scopes``)."""
from benchmark.readers import _train_scopes


def read(ctx):
    return _train_scopes.ms_per_step(ctx, "attention.core")
