"""How late the open-loop generator handed requests over, 95th
percentile: a starved generator must not be read as a fast server."""
from benchmark.readers._common import percentile


def read(ctx):
    late = ctx.run["spans"].get("late_s", [])
    return 1e3 * percentile(late, 95) if len(late) else None
