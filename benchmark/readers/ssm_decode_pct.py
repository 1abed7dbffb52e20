"""Share of chip 0's busy time spent on the one-token update of the
recurrent state: self time of operations whose HLO ``op_name`` lies
under the ``mixer.ssm_step`` scope (``jax.named_scope`` in
``nn/mamba.py``: the conv tail's shift and the state's decay, update and
read-out, in every decode step of every hybrid layer) over the busy
seconds of the traced window — read as ``decode_sample_pct`` is, from
the one pass ``_program_spans.scope_seconds`` makes for every scope.
A program without that scope (no such block) gives nothing to read."""
from benchmark.readers import _program_spans


def read(ctx):
    scoped = _program_spans.scope_seconds(ctx, "mixer.ssm_step")
    summary = getattr(ctx, "trace_summary", None)
    if not scoped or not summary or summary["busy_s"] <= 0:
        return None
    return 100.0 * scoped / summary["busy_s"]
