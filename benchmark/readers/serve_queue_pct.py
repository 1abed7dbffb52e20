"""Share of request latency spent waiting in the server's queue: the sum
of ``ServeResult.queued_s`` over the sum of ``ServeResult.latency_s``."""


def read(ctx):
    sp = ctx.run["spans"]
    total = sum(sp.get("server_latency_s", []))
    return 100.0 * sum(sp["queued_s"]) / total if total > 0 else None
