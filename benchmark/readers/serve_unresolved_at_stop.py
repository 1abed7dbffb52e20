"""Requests unresolved at the instant the open-loop generator stopped
sending: the batch in flight and what queued behind it.  Below the knee
it stays within two batches' worth; a server that has fallen behind its
offered load shows here before the tail says so."""


def read(ctx):
    return ctx.run["counters"].get("backlog_at_end")
