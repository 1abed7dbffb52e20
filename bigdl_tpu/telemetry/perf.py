"""Performance accounting from XLA's own cost model.

The telemetry spine (registry/tracer/goodput) accounts *time*; this
module accounts *work*: per-compiled-step FLOPs and bytes accessed
derived from XLA (``Lowered.cost_analysis()`` — the pre-optimization
HLO cost model, which counts the math as written, without remat or
fusion artifacts — or ``Compiled.cost_analysis()`` +
``memory_analysis()`` when the caller holds an AOT executable), plus
live HBM watermarks from ``device.memory_stats()`` polled at step
boundaries.  From those it publishes the MFU family as first-class
registry metrics and classifies every analyzed program against the
device roofline (compute-bound vs HBM-bound vs collective-bound,
peaks from :mod:`.device_info`).

Nothing here hand-codes a model's FLOPs: the numbers come from the
exact program the driver dispatches.  Every entry point degrades to a
no-op on failure — perf accounting must never take down a training
step (``memory_stats()`` returning None on CPU jaxlib is the normal
case, not an error).

jax is imported lazily inside functions: the registry/tracer side of
the spine stays importable before backend init.
"""
from __future__ import annotations

import logging
from typing import Dict, NamedTuple, Optional

from .device_info import DeviceSpec, current_device_spec
from .registry import MetricsRegistry, default_registry

log = logging.getLogger("bigdl_tpu")

__all__ = ["PerfAccountant", "StepCost", "classify_roofline",
           "cost_from_analysis"]

#: roofline verdicts (``unknown`` = not enough device/byte data)
ROOFLINE_BOUNDS = ("compute", "hbm", "collective", "unknown")


class StepCost(NamedTuple):
    """Static cost of one compiled program, from XLA's cost model."""

    flops: float
    bytes_accessed: float
    #: caller-supplied estimate (XLA's per-op byte counts do not
    #: attribute collective wire bytes); 0.0 = single-chip program
    collective_bytes: float = 0.0
    #: from Compiled.memory_analysis() when available, else None
    peak_bytes: Optional[float] = None
    argument_bytes: Optional[float] = None
    output_bytes: Optional[float] = None
    temp_bytes: Optional[float] = None
    #: "lowered" (pre-optimization HLO) or "compiled" (executable)
    source: str = "lowered"

    @property
    def arithmetic_intensity(self) -> Optional[float]:
        if not self.bytes_accessed:
            return None
        return self.flops / self.bytes_accessed


def cost_from_analysis(analysis, collective_bytes: float = 0.0,
                       memory=None, source: str = "lowered") -> StepCost:
    """Normalize a jax ``cost_analysis()`` result (dict, or a 1-list
    of dicts on older executables) + optional ``memory_analysis()``
    into a :class:`StepCost`."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    get = analysis.get if hasattr(analysis, "get") else lambda *_: 0.0
    kw = {}
    if memory is not None:
        arg = float(getattr(memory, "argument_size_in_bytes", 0))
        out = float(getattr(memory, "output_size_in_bytes", 0))
        tmp = float(getattr(memory, "temp_size_in_bytes", 0))
        kw = dict(argument_bytes=arg, output_bytes=out, temp_bytes=tmp,
                  peak_bytes=arg + out + tmp)
    return StepCost(
        flops=float(get("flops", 0.0) or 0.0),
        bytes_accessed=float(get("bytes accessed", 0.0) or 0.0),
        collective_bytes=max(0.0, float(collective_bytes or 0.0)),
        source=source, **kw)


def classify_roofline(cost: StepCost, spec: DeviceSpec) -> dict:
    """Which wall does this program lean on?

    Attainable-time comparison: ``flops/peak`` vs ``bytes/hbm_bw`` vs
    ``collective_bytes/ici_bw`` — the largest lower bound names the
    binding resource.  The compute-vs-HBM half is equivalent to
    comparing arithmetic intensity against the device ridge point
    (``peak_flops / hbm_bw``); stating it as times lets the collective
    leg join the same comparison.  Returns the classification plus the
    inputs it was made from, so reports can show their work.
    """
    ai = cost.arithmetic_intensity
    ridge = spec.ridge_flops_per_byte
    times = {}
    if spec.peak_flops_per_sec:
        times["compute"] = cost.flops / spec.peak_flops_per_sec
    if spec.hbm_bytes_per_sec and cost.bytes_accessed:
        times["hbm"] = cost.bytes_accessed / spec.hbm_bytes_per_sec
    if spec.ici_bytes_per_sec and cost.collective_bytes:
        times["collective"] = (cost.collective_bytes
                               / spec.ici_bytes_per_sec)
    bound = max(times, key=times.get) if times else "unknown"
    if "hbm" not in times and bound == "compute" and not cost.flops:
        bound = "unknown"
    return {
        "bound": bound,
        "arithmetic_intensity": ai,
        "ridge_flops_per_byte": ridge,
        "attainable_seconds": times,
        "nominal_device": spec.nominal,
    }


class PerfAccountant:
    """Derives work metrics for the programs a driver dispatches.

    One accountant per process side (training driver, bench worker,
    serving server).  ``analyze_jitted`` is called once per fresh
    program (the driver's ``first_step``); ``on_step`` once per
    dispatch.  Publishes into the registry:

    * ``bigdl_perf_flops_per_step`` / ``bigdl_perf_bytes_per_step`` /
      ``bigdl_perf_collective_bytes`` gauges, labeled by ``program``;
    * ``bigdl_perf_arithmetic_intensity`` gauge per program;
    * ``bigdl_perf_mfu`` gauge per program (rolling mean over the
      last observed step times) + ``bigdl_perf_model_flops_per_sec``;
    * ``bigdl_perf_flops_total`` counter — the cross-host foldable
      total (counters sum in the cluster merge);
    * ``bigdl_perf_hbm_{bytes_in_use,peak_bytes,limit_bytes}`` gauges
      from ``device.memory_stats()``, polled every
      ``memory_poll_every`` steps (backends without memory stats —
      CPU jaxlib — leave them untouched).
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 spec: Optional[DeviceSpec] = None,
                 memory_poll_every: int = 16):
        self.registry = registry if registry is not None \
            else default_registry()
        self._spec = spec
        self.memory_poll_every = max(1, int(memory_poll_every))
        self._programs: Dict[str, StepCost] = {}
        self._current: Optional[str] = None
        self._steps_seen = 0
        self._ema_flops_per_sec: Dict[str, float] = {}
        self.last_memory_stats: Optional[dict] = None
        r = self.registry
        self.flops_per_step = r.gauge(
            "bigdl_perf_flops_per_step",
            "XLA cost-model FLOPs of one compiled step",
            labels=("program",))
        self.bytes_per_step = r.gauge(
            "bigdl_perf_bytes_per_step",
            "XLA cost-model bytes accessed by one compiled step",
            labels=("program",))
        self.collective_bytes = r.gauge(
            "bigdl_perf_collective_bytes",
            "estimated collective wire bytes per step (sparse-transport "
            "leaves accounted as actual index+value bytes)",
            labels=("program",))
        self.sparse_bytes_saved = r.gauge(
            "bigdl_perf_sparse_bytes_saved",
            "collective wire bytes per step NOT moved because sparse "
            "gradient transport replaced the dense all-reduce",
            labels=("program",))
        self.sync_bytes_saved = r.gauge(
            "bigdl_perf_sync_bytes_saved",
            "collective wire bytes per step NOT moved because relaxed "
            "synchrony (periodic(k) local SGD) replaced the lockstep "
            "per-step reduction with amortized k-step averaging",
            labels=("program",))
        self.sparse_flops_skipped_gauge = r.gauge(
            "bigdl_perf_sparse_flops_skipped",
            "dense-equivalent MXU FLOPs per step NOT executed because "
            "block-sparse kernels skipped masked blocks (kernel-"
            "reported: XLA's cost model cannot see inside Pallas "
            "custom calls)",
            labels=("program",))
        #: kernel-reported sparse corrections per program — the
        #: uncorrected cost is retained so repeated reports replace,
        #: never compound
        self._sparse_flops: Dict[str, dict] = {}
        self._uncorrected: Dict[str, StepCost] = {}
        self.intensity = r.gauge(
            "bigdl_perf_arithmetic_intensity",
            "flops / bytes accessed of one compiled step",
            labels=("program",))
        self.mfu = r.gauge(
            "bigdl_perf_mfu",
            "model flops utilization vs the device peak "
            "(per analyzed program; rolling over recent steps)",
            labels=("program",))
        self.model_flops_per_sec = r.gauge(
            "bigdl_perf_model_flops_per_sec",
            "achieved model FLOP/s (per analyzed program)",
            labels=("program",))
        self.flops_total = r.counter(
            "bigdl_perf_flops_total",
            "cost-model FLOPs executed (sums across hosts)")
        self.hbm_in_use = r.gauge(
            "bigdl_perf_hbm_bytes_in_use",
            "device memory in use at the last poll")
        self.hbm_peak = r.gauge(
            "bigdl_perf_hbm_peak_bytes",
            "device memory high-watermark at the last poll")
        self.hbm_limit = r.gauge(
            "bigdl_perf_hbm_limit_bytes",
            "device memory capacity reported by the backend")

    # -- device ----------------------------------------------------------
    @property
    def spec(self) -> DeviceSpec:
        if self._spec is None:
            self._spec = current_device_spec()
        return self._spec

    # -- program analysis ------------------------------------------------
    def analyze_jitted(self, fn, *args, label: str = "train_step",
                       collective_bytes: float = 0.0,
                       sparse_bytes_saved: float = 0.0,
                       sync_bytes_saved: float = 0.0,
                       **kwargs) -> Optional[StepCost]:
        """Lower a jitted callable with the driver's concrete args and
        read XLA's cost model — no compile, no execution, no donation
        (lowering only traces avals), a few seconds of host work per
        fresh program.  Returns None (and logs at debug) on any
        failure: accounting never takes down the step loop."""
        try:
            lowered = fn.lower(*args, **kwargs)
            cost = cost_from_analysis(lowered.cost_analysis(),
                                      collective_bytes=collective_bytes,
                                      source="lowered")
        except Exception as e:
            log.debug("perf: cost analysis failed for %r: %s: %s",
                      label, type(e).__name__, e)
            return None
        return self.on_program(label, cost,
                               sparse_bytes_saved=sparse_bytes_saved,
                               sync_bytes_saved=sync_bytes_saved)

    def analyze_compiled(self, compiled, label: str = "train_step",
                         collective_bytes: float = 0.0
                         ) -> Optional[StepCost]:
        """Read an AOT executable's cost + memory analyses (the bench
        path, which already compiles ahead of time)."""
        try:
            memory = None
            try:
                memory = compiled.memory_analysis()
            except Exception:
                pass
            cost = cost_from_analysis(compiled.cost_analysis(),
                                      collective_bytes=collective_bytes,
                                      memory=memory, source="compiled")
        except Exception as e:
            log.debug("perf: compiled analysis failed for %r: %s: %s",
                      label, type(e).__name__, e)
            return None
        return self.on_program(label, cost)

    def on_program(self, label: str, cost: StepCost,
                   sparse_bytes_saved: float = 0.0,
                   sync_bytes_saved: float = 0.0) -> StepCost:
        """Install an analyzed program: publish its static gauges and
        make it the one ``on_step`` attributes work to."""
        label = str(label)
        self._programs[label] = cost
        # a fresh analysis supersedes any kernel-reported sparse
        # correction (the caller re-reports after re-analyzing)
        self._uncorrected.pop(label, None)
        self._sparse_flops.pop(label, None)
        self._current = label
        self.flops_per_step.labels(program=label).set(cost.flops)
        self.bytes_per_step.labels(program=label).set(
            cost.bytes_accessed)
        self.collective_bytes.labels(program=label).set(
            cost.collective_bytes)
        if sparse_bytes_saved:
            self.sparse_bytes_saved.labels(program=label).set(
                float(sparse_bytes_saved))
        if sync_bytes_saved:
            self.sync_bytes_saved.labels(program=label).set(
                float(sync_bytes_saved))
        if cost.arithmetic_intensity is not None:
            self.intensity.labels(program=label).set(
                cost.arithmetic_intensity)
        self.poll_memory_stats()
        return cost

    def report_sparse_flops(self, label: str, executed_flops: float,
                            dense_equiv_flops: float) -> Optional[StepCost]:
        """Kernel-reported effective-FLOPs correction for a program
        whose Pallas kernels SKIP work the cost model cannot see.

        XLA counts a Pallas call as a zero-FLOP custom call, so a
        block-sparse kernel's skipped blocks are invisible: without
        this correction a 2x wall-clock win at 50% density reads as an
        MFU regression.  The caller (driver/bench — it knows the mask)
        reports the kernel's ``executed`` FLOPs and the ``dense
        equivalent``; the program's accounted FLOPs become
        ``cost-model + executed`` (MFU/model_flops_per_sec rate on
        EXECUTED work), the dense equivalent is recorded alongside in
        the payload, and the difference lands in the
        ``bigdl_perf_sparse_flops_skipped`` gauge.  Repeated reports
        for one program replace (never compound) the correction."""
        label = str(label)
        executed = max(0.0, float(executed_flops))
        dense_eq = max(executed, float(dense_equiv_flops))
        base = self._uncorrected.get(label)
        if base is None:
            base = self._programs.get(label, StepCost(0.0, 0.0))
            self._uncorrected[label] = base
        skipped = dense_eq - executed
        corrected = base._replace(flops=base.flops + executed)
        self._programs[label] = corrected
        self._sparse_flops[label] = {
            "executed_flops": base.flops + executed,
            "dense_equivalent_flops": base.flops + dense_eq,
            "sparse_flops_skipped": skipped,
        }
        self.sparse_flops_skipped_gauge.labels(program=label).set(
            skipped)
        self.flops_per_step.labels(program=label).set(corrected.flops)
        if corrected.arithmetic_intensity is not None:
            self.intensity.labels(program=label).set(
                corrected.arithmetic_intensity)
        return corrected

    @property
    def current_cost(self) -> Optional[StepCost]:
        return self._programs.get(self._current) \
            if self._current else None

    @property
    def current_label(self) -> Optional[str]:
        return self._current

    # -- per-step accounting ---------------------------------------------
    def on_step(self, seconds: float, compiled: bool = False,
                label: Optional[str] = None):
        """One dispatch of the current (or named) analyzed program
        completed in ``seconds``.  Compile steps still count their
        FLOPs (the work ran) but are excluded from the MFU rate — a
        first-step wall is XLA build time, not math time."""
        label = label or self._current
        cost = self._programs.get(label) if label else None
        if cost is None:
            return
        self.flops_total.inc(cost.flops)
        seconds = float(seconds)
        if seconds > 0 and not compiled:
            rate = cost.flops / seconds
            # EMA over recent steps: one outlier step must not own the
            # published MFU, one gauge read must not require history
            prev = self._ema_flops_per_sec.get(label)
            rate = rate if prev is None else (0.8 * prev + 0.2 * rate)
            self._ema_flops_per_sec[label] = rate
            self.model_flops_per_sec.labels(program=label).set(rate)
            peak = self.spec.peak_flops_per_sec
            if peak:
                self.mfu.labels(program=label).set(rate / peak)
        self._steps_seen += 1
        if self._steps_seen % self.memory_poll_every == 0:
            self.poll_memory_stats()

    # -- HBM watermarks --------------------------------------------------
    def poll_memory_stats(self, device=None) -> Optional[dict]:
        """Read ``device.memory_stats()`` into the HBM gauges.  CPU
        jaxlib returns None (and some backends lack the method) — both
        degrade to a no-op returning None, never an exception."""
        try:
            if device is None:
                import jax

                device = jax.devices()[0]
            stats = getattr(device, "memory_stats", lambda: None)()
        except Exception as e:
            log.debug("perf: memory_stats unavailable: %s", e)
            return None
        if not stats:
            return None
        self.last_memory_stats = dict(stats)
        if "bytes_in_use" in stats:
            self.hbm_in_use.set(float(stats["bytes_in_use"]))
        if "peak_bytes_in_use" in stats:
            self.hbm_peak.set(float(stats["peak_bytes_in_use"]))
        if "bytes_limit" in stats:
            self.hbm_limit.set(float(stats["bytes_limit"]))
        return self.last_memory_stats

    # -- roofline + export -----------------------------------------------
    def roofline(self, label: Optional[str] = None) -> Optional[dict]:
        cost = self._programs.get(label or self._current or "")
        if cost is None:
            return None
        return classify_roofline(cost, self.spec)

    def span_args(self) -> dict:
        """Static work attributes for the current program — attached
        to every step span so Perfetto traces carry intensity
        annotations even in unprofiled runs."""
        cost = self.current_cost
        if cost is None:
            return {}
        out = {"flops": cost.flops, "bytes": cost.bytes_accessed}
        if cost.collective_bytes:
            out["collective_bytes"] = cost.collective_bytes
        if cost.arithmetic_intensity is not None:
            out["intensity"] = round(cost.arithmetic_intensity, 3)
        rf = self.roofline()
        if rf is not None:
            out["bound"] = rf["bound"]
        return out

    def payload(self) -> dict:
        """The ``perf`` section of the telemetry payload (what the
        cross-host merge folds and run_report renders)."""
        programs = {}
        for label, cost in self._programs.items():
            entry = dict(cost._asdict())
            entry["arithmetic_intensity"] = cost.arithmetic_intensity
            rf = classify_roofline(cost, self.spec)
            entry["bound"] = rf["bound"]
            # kernel-reported sparse correction: executed-basis flops
            # with the dense equivalent recorded alongside
            if label in self._sparse_flops:
                entry.update(self._sparse_flops[label])
            rate = self._ema_flops_per_sec.get(label)
            if rate is not None:
                entry["model_flops_per_sec"] = rate
                if self.spec.peak_flops_per_sec:
                    entry["mfu"] = rate / self.spec.peak_flops_per_sec
            programs[label] = entry
        out = {
            "device": self.spec.to_dict(),
            "flops_total": self.flops_total.value,
            "programs": programs,
        }
        if self.last_memory_stats is not None:
            out["hbm"] = {
                k: self.last_memory_stats[k]
                for k in ("bytes_in_use", "peak_bytes_in_use",
                          "bytes_limit")
                if k in self.last_memory_stats}
        return out
