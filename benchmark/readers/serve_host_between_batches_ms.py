"""Host time between two batches on the worker thread: from the end of
``serve.fetch`` of batch b (its result is on the host) to the end of
``serve.dispatch`` of batch b+1 (the next program is enqueued), with
the time inside ``serve.idle`` (no request to run) taken out.  Median,
milliseconds."""
from benchmark.readers import _program_spans


def read(ctx):
    spans = _program_spans.load(ctx)
    if not spans or spans["worker"] is None:
        return None
    idles = spans["worker"].named("serve.idle")
    gaps = []
    for lo, hi in _program_spans.handoffs(spans["worker"], "serve.fetch",
                                          "serve.dispatch"):
        idle = sum(min(e, hi) - max(s, lo) for _, s, e, _ in idles
                   if min(e, hi) > max(s, lo))
        gaps.append(hi - lo - idle)
    return _program_spans.median_ms(gaps)
