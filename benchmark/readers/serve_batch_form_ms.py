"""What forming a batch costs on the worker thread: the gather window
(``serve.gather``: first request in hand -> batch closed) plus
``serve.batch_form`` (stack, pad by repeating, host -> device) of the
batches that gather fed.  Median per gather, milliseconds."""
from benchmark.readers import _program_spans


def read(ctx):
    spans = _program_spans.load(ctx)
    if not spans or spans["worker"] is None:
        return None
    gathers = spans["worker"].named("serve.gather")
    forms = spans["worker"].named("serve.batch_form")
    if not gathers:
        return None
    totals = []
    for i, g in enumerate(gathers):
        until = gathers[i + 1][1] if i + 1 < len(gathers) else float("inf")
        totals.append((g[2] - g[1]) + sum(
            f[2] - f[1] for f in forms if g[2] <= f[1] < until))
    return _program_spans.median_ms(totals)
