"""Share of a training step's device time that lies under ANY of the
ten device scopes of the compiled step (``_train_scopes.SCOPES``):
the coverage of the tracing itself, as ``train_idle_attributed_pct`` is
on the host side.  What is missing is what a fusion across a scope's
edge books to no scope at all."""
from benchmark.readers import _train_scopes


def read(ctx):
    return _train_scopes.named_pct(ctx)
