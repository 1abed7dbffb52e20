"""Share of the traced window in which a collective runs on a chip while
no other operation does, on the worst chip."""


def read(ctx):
    s = ctx.trace_summary
    if not s or s["chips"] < 2:
        return None
    return 100.0 * max(s["exposed_collective_s_by_chip"]) / s["window_s"]
