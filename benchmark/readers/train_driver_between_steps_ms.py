"""Host time between two steps of the training driver: from the end of
``train.loss_fetch`` of step n (the device has just gone quiet) to the
end of ``train.dispatch`` of step n+1 (the next program is enqueued) —
bookkeeping, the end trigger, the data wait, batch placement and the
dispatch itself.  Median over the traced iterations, milliseconds."""
from benchmark.readers import _program_spans


def read(ctx):
    spans = _program_spans.load(ctx)
    if not spans or spans["driver"] is None:
        return None
    return _program_spans.median_ms(
        [hi - lo for lo, hi in _program_spans.handoffs(
            spans["driver"], "train.loss_fetch", "train.dispatch")])
