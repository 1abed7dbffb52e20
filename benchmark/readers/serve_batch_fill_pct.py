"""Real rows over bucket rows of the batches dispatched in the window
(``ServingMetrics`` batch and padded-row counters)."""


def read(ctx):
    c = ctx.run["counters"]
    rows = c.get("real_rows", 0) + c.get("padded_rows", 0)
    return 100.0 * c["real_rows"] / rows if rows else None
