"""As ``train_idle_attributed_pct``, on the serving WORKER thread;
``serve.idle`` (no request in hand) counts as attributed."""
from benchmark.readers import _program_spans


def read(ctx):
    return _program_spans.idle_attributed_pct(ctx, "worker")
