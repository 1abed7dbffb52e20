"""What the prompt pass is of a batch: share of chip 0's busy seconds in
the traced window that lie OUTSIDE the decode scans — the prompt pass
in its groups of rows, the cast, the first token — over the busy
seconds.  Busy inside a scan is the self time of the operations that
start within one (``_st_scopes.in_scan_rows``, the scan's own event
among them).  None where the trace holds no scan."""
from benchmark.readers import _st_scopes


def read(ctx):
    rows = _st_scopes.in_scan_rows(ctx)
    summary = getattr(ctx, "trace_summary", None)
    if not rows or not summary or summary["busy_s"] <= 0:
        return None
    inside = sum(ns for _, ns in rows) / 1e9
    return 100.0 * max(summary["busy_s"] - inside, 0.0) / summary["busy_s"]
