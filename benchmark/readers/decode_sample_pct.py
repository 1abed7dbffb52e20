"""Share of chip 0's busy time spent sampling: self time of operations
whose HLO ``op_name`` lies under the ``generate.sample`` scope
(``jax.named_scope`` in ``models/generate.py``: both calls of
``_sample``, after the prefill and in every decode step) over the busy
seconds of the traced window.  The scope path of an operation is read
from the stat of its ``XLA Ops`` event that carries it."""
from benchmark.readers import _program_spans


def read(ctx):
    scoped = _program_spans.scope_seconds(ctx, "generate.sample")
    summary = getattr(ctx, "trace_summary", None)
    if scoped is None or not summary or summary["busy_s"] <= 0:
        return None
    return 100.0 * scoped / summary["busy_s"]
