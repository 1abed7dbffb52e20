"""Milliseconds of one training step under the device scope ``block.mlp``
— a block's second norm, its FFN (dense, SwiGLU or experts) and the
residual add, forward and backward (``_train_scopes``)."""
from benchmark.readers import _train_scopes


def read(ctx):
    return _train_scopes.ms_per_step(ctx, "block.mlp")
