"""Helpers several readers share.  A reader is ``read(ctx) -> float |
None``: ``ctx.run`` is the driver's account of the window (``spans``,
``counters``, ``end_to_end``, ``shapes``, ``memory_peak_bytes``),
``ctx.trace_summary`` the reduced device trace (None without one),
``ctx.config`` / ``ctx.traffic`` / ``ctx.peaks`` / ``ctx.counts`` the
cell's data and the benchmark's arithmetic.  A reader that finds
nothing to read returns None and the metric is left out of the line."""
from __future__ import annotations

import re


def percentile(values, q: float):
    import numpy as np

    return float(np.percentile(values, q)) if len(values) else None


def idle_pct(ctx):
    s = ctx.trace_summary
    if not s or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])


def hbm_peak_gb(ctx):
    peak = ctx.run.get("memory_peak_bytes")
    return peak / 1e9 if peak else None


def main_module(summary: dict):
    """The program that took most device time in the window: (name,
    seconds, executions)."""
    if not summary or not summary["modules"]:
        return None
    name, row = max(summary["modules"].items(),
                    key=lambda kv: kv[1]["seconds"])
    return name, row["seconds"], row["count"]


def is_flash(row: dict, head_dim: int) -> bool:
    """A Mosaic custom call over rank-3 [batch*heads, T, head_dim]
    operands: the flash kernels (the fused LayerNorm kernels are rank-2)."""
    text = row["long_name"]
    if 'custom_call_target="tpu_custom_call"' not in text:
        return False
    return re.search(r"\[\d+,\d+,%d\]" % head_dim, text) is not None


def flash_seconds(summary: dict, head_dim: int) -> float:
    return sum(r["seconds"] for r in summary["ops"].values()
               if is_flash(r, head_dim))


def loop_seconds(summary: dict):
    """(seconds inside ``while`` loops, seconds outside) of operation
    self time in the window."""
    inside = sum(r["seconds"] for r in summary["ops"].values()
                 if r.get("in_loop"))
    total = sum(r["seconds"] for r in summary["ops"].values())
    return inside, total - inside
