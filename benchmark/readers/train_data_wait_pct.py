"""Share of the traced stretch (first traced ``train.iteration``'s start
to the last one's end) that the driver spent inside ``train.data_wait``
— the call into the feed: a hit on a full buffer costs microseconds, a
miss is a real stall."""
from benchmark.readers import _program_spans


def read(ctx):
    spans = _program_spans.load(ctx)
    if not spans or spans["driver"] is None:
        return None
    its = spans["driver"].named("train.iteration")
    lo, hi = its[0][1], max(e[2] for e in its)
    waited = sum(min(e, hi) - max(s, lo)
                 for _, s, e, _ in spans["driver"].named("train.data_wait")
                 if min(e, hi) > max(s, lo))
    return 100.0 * waited / (hi - lo) if hi > lo else None
