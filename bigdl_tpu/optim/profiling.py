"""Phase-split extraction from jax.profiler traces.

The reference attributes each iteration's wall time to named phases via
Spark accumulators ("computing time average", "aggregate gradient time"
— Metrics.scala:103-121, DistriOptimizer.scala:146-151).  On TPU the
whole iteration is ONE fused XLA program, so the honest split comes from
the profiler: trace the step's execution, classify device-side op events
into collective (gradient aggregation / weight exchange) vs compute, and
sum their durations.  ``DistriOptimizer`` does this on profiling
iterations, falling back to the collective-free probe when a trace
yields nothing parsable (e.g. an execution backend whose xplane has no
device lines).
"""
from __future__ import annotations

import glob
import logging
import os
import shutil
import tempfile
from typing import Callable, NamedTuple, Optional

log = logging.getLogger("bigdl_tpu")


class PhaseSplit(NamedTuple):
    """Device-time attribution of one profiled step.  A NamedTuple so
    every existing ``compute_s, collective_s = split`` unpacking keeps
    working while new callers (the telemetry tracer's compute/
    collective children) get named fields."""

    compute_s: float
    collective_s: float

    @property
    def total_s(self) -> float:
        return self.compute_s + self.collective_s

    @property
    def compute_fraction(self) -> float:
        return self.compute_s / max(self.total_s, 1e-12)

# Substrings identifying communication ops in XLA/xplane event names
# (TPU planes use HLO names: all-reduce.N, all-gather.N, ...; the CPU
# backend surfaces its thread rendezvous instead).
_COLLECTIVE_MARKS = (
    "all-reduce", "allreduce", "all-gather", "allgather",
    "reduce-scatter", "reducescatter", "all-to-all", "alltoall",
    "collective", "permute", "psum", "rendezvous", "wait:",
    "send", "recv",
)
# Host-side bookkeeping events that are neither compute nor collective.
# ThunkExecutor/ExecuteHelper span whole executables (counting them would
# double-count every op inside); "wait for completion" is idle time.
_SKIP_MARKS = (
    "threadpoollistener", "startregion", "stopregion", "parsearguments",
    "collectgarbage", "end:", "executehelper", "thunkexecutor",
    "d2d dispatch", "wait for complet",
)


def _classify(name: str) -> Optional[str]:
    n = name.lower()
    if any(m in n for m in _SKIP_MARKS):
        return None
    if any(m in n for m in _COLLECTIVE_MARKS):
        return "collective"
    return "compute"


def _device_lines(profile_data):
    """Yield lines holding device-side PER-OP execution events.

    TPU planes are named /device:TPU:N; only their "XLA Ops" line is
    per-op — "XLA Modules" carries one whole-executable event (compute
    AND collective time) and "Framework Ops"/"Steps" duplicate the op
    stream, all of which would double-count.  The CPU PJRT backend nests
    its executor threads under /host:CPU with tf_XLAPjRtCpuClient/...
    line names."""
    for plane in profile_data.planes:
        dev_plane = plane.name.startswith("/device:")
        for line in plane.lines:
            if dev_plane and "xla ops" in line.name.lower():
                yield line
            elif line.name.startswith("tf_XLA"):
                # CPU PJRT executor threads (tf_XLAPjRtCpuClient/...)
                yield line


def split_from_xplane(path: str) -> PhaseSplit:
    """Sum (compute_seconds, collective_seconds) over a trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    compute_ns = 0
    collective_ns = 0
    for line in _device_lines(pd):
        for ev in line.events:
            kind = _classify(ev.name)
            if kind == "compute":
                compute_ns += ev.duration_ns
            elif kind == "collective":
                collective_ns += ev.duration_ns
    return PhaseSplit(compute_ns / 1e9, collective_ns / 1e9)


def trace_phase_split(run: Callable[[], None]) -> Optional[PhaseSplit]:
    """Run ``run()`` under a jax.profiler trace; return the device-time
    (compute_s, collective_s) split, or None when the trace has no
    classifiable device events (caller falls back to the probe).

    ``run`` ALWAYS executes exactly once, and its exceptions propagate —
    the driver's failure-retry loop depends on seeing training errors.
    A failure of the profiling machinery itself costs the split, not
    the step: it is logged with its traceback and None is returned.

    The temp trace directory is removed on EVERY path — trace-start
    failure, a raising ``run``, an unparsable trace — via the
    enclosing try/finally."""
    import jax

    tmp = tempfile.mkdtemp(prefix="bigdl_phase_")
    ctx, started = None, False
    try:
        try:
            ctx = jax.profiler.trace(tmp)
            ctx.__enter__()
            started = True
        except Exception:
            log.warning("profiler trace did not start — step runs "
                        "untraced", exc_info=True)
        try:
            run()
        finally:
            if started:
                try:
                    ctx.__exit__(None, None, None)
                except Exception:
                    log.warning("profiler trace did not stop cleanly",
                                exc_info=True)
                    started = False
        if not started:
            return None
        try:
            files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                log.warning("profiler wrote no xplane under %s", tmp)
                return None
            split = split_from_xplane(files[0])
        except Exception:
            log.warning("profiler trace could not be parsed",
                        exc_info=True)
            return None
        if split.compute_s <= 0.0:
            log.warning("profiler trace has no device compute events")
            return None
        return split
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
