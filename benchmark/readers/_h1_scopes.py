"""Self seconds under several ``jax.named_scope`` names from ONE pass
over chip 0's operations — what ``ssm_decode_pct`` and
``ssd_scan_roofline`` share.

``_program_spans.scope_seconds`` gives the same number for one scope,
but each call walks every event through ``trace_reduce.self_times``,
which also works out whether an event lies in a ``while`` by parsing its
parent's instruction text; in this cell's decode loop that text is the
8.8 kB tuple of every weight and cache, and a pass over the 320 000
events of four batches of 64 took 38 s on the chip machine — twice that
for two scopes, of a run that has 360 s in all.  The nesting rule here
is ``self_times``'s, letter for letter (an event's duration less the
events wholly inside it); only the ``in_loop`` flag, which a scope's
share does not use, is left out.  ``benchmark/tests/test_falcon_h1.py``
holds the two against each other.
"""
from __future__ import annotations

from benchmark.readers import _program_spans

SCOPES = ("mixer.ssm_step", "mixer.ssd_scan")


def self_ns(events) -> list:
    """[(event, self_ns)] for ``[name, start_ns, duration_ns, ...]``
    events of one line, nested as ``trace_reduce.self_times`` nests
    them."""
    out, stack = [], []   # stack of [event, end, child_ns]
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        s, e = ev[1], ev[1] + ev[2]
        while stack and (stack[-1][1] <= s or stack[-1][1] < e):
            top = stack.pop()
            out.append((top[0], top[0][2] - top[2]))
        if stack:
            stack[-1][2] += ev[2]
        stack.append([ev, e, 0])
    while stack:
        top = stack.pop()
        out.append((top[0], top[0][2] - top[2]))
    return out


def scope_seconds(ctx) -> dict | None:
    """{scope: self seconds inside the traced window} for ``SCOPES``,
    worked out once a run and kept on ``ctx``; None where
    ``_program_spans.scope_seconds`` gives None (no trace, no program
    span, no device event, or no event that names a scope at all)."""
    if not hasattr(ctx, "_h1_scope_seconds"):
        ctx._h1_scope_seconds = None
        spans = _program_spans.load(ctx)
        if spans and spans["chip_events"] and spans["window"]:
            lo, hi = spans["window"]
            events = [e for e in spans["chip_events"] if lo <= e[1] < hi]
            if any(e[3]["scope"] for e in events):
                ctx._h1_scope_seconds = by_scope(events, SCOPES)
    return ctx._h1_scope_seconds


def by_scope(events, scopes) -> dict:
    total = dict.fromkeys(scopes, 0)
    for ev, ns in self_ns(events):
        path = ev[3]["scope"] + "/"
        for scope in scopes:
            if scope + "/" in path:
                total[scope] += ns
    return {s: ns / 1e9 for s, ns in total.items()}
