"""Unified telemetry spine: metrics registry, structured step tracing,
Perfetto export, goodput accounting, cross-host aggregation.

The reproduction had grown four siloed observability fragments
(TensorBoard scalars, the xplane phase split, FlightRecorder journals,
ad-hoc serving/elastic counter bags); this package is the one spine
they hang off:

* :mod:`.registry`  — thread-safe Counter/Gauge/Histogram with label
  sets, JSON snapshots + Prometheus text export, injectable clock.
* :mod:`.tracer`    — nested spans with explicit categories into a
  bounded ring buffer, exported as Chrome-trace/Perfetto JSON.
* :mod:`.goodput`   — :class:`GoodputLedger` classifying every second
  of run wall clock (productive / compile / data-stall / checkpoint /
  recovery / idle).
* :mod:`.aggregate` — hosts publish snapshots over the elastic KV
  transport (incarnation-keyed); the leader merges a cluster view;
  snapshot directories feed ``tools/run_report.py``.
* :mod:`.slog`      — structured logging entry points (the library
  never calls ``logging.basicConfig`` at import time).

:class:`Telemetry` is the driver-facing bundle: ``Optimizer
.set_telemetry(Telemetry(...))`` wires all four optimizer mesh paths,
the serving path and the resilience hooks into the same registry,
tracer and ledger.
"""
from __future__ import annotations

import time
from typing import Optional

from .aggregate import (
    collect_snapshots, merge_alerts, merge_cluster, merge_incidents,
    merge_metrics, merge_timeline, publish_snapshot,
    read_snapshot_dir, write_snapshot,
)
from .device_info import DeviceSpec, device_spec, peak_flops_per_sec
from .events import (CHANGE_EVENT_KINDS, ChangeEvent, ChangeJournal,
                     default_journal, record_change,
                     reset_default_journal)
from .incidents import Incident, IncidentEngine, IncidentPolicy
from .goodput import GOODPUT_CATEGORIES, GoodputLedger
from .metric_names import METRIC_FAMILY_NAMES
from .perf import PerfAccountant, StepCost, classify_roofline
from .publish import BackgroundPublisher
from .registry import (
    Counter, Gauge, Histogram, MetricsRegistry, default_buckets,
    default_registry, reset_default_registry,
)
from .slo import (Alert, HealthVerdict, SloEngine, SloRule,
                  TrainingHealthMonitor, default_loop_rules,
                  default_serving_rules, default_training_rules,
                  ingest_deadman_rule)
from .slog import configure_logging, get_logger
from .timeseries import MetricRecorder
from .trace_context import (REQUEST_CATEGORIES, TRACE_KV_PREFIX,
                            TailSampler, TraceContext)
from .tracer import (CATEGORIES, STEP_CATEGORIES, Span, Tracer,
                     default_tracer, reset_default_tracer)

__all__ = [
    "Alert", "BackgroundPublisher", "CATEGORIES",
    "CHANGE_EVENT_KINDS", "GOODPUT_CATEGORIES",
    "ChangeEvent", "ChangeJournal", "Counter", "DeviceSpec",
    "Gauge", "HealthVerdict", "Histogram", "Incident",
    "IncidentEngine", "IncidentPolicy", "METRIC_FAMILY_NAMES",
    "MetricRecorder", "MetricsRegistry", "GoodputLedger",
    "PerfAccountant", "REQUEST_CATEGORIES", "STEP_CATEGORIES",
    "SloEngine", "SloRule",
    "Span", "StepCost", "TRACE_KV_PREFIX", "TailSampler",
    "Telemetry", "TraceContext", "Tracer", "TrainingHealthMonitor",
    "classify_roofline", "collect_snapshots", "configure_logging",
    "default_buckets", "default_journal", "default_loop_rules",
    "default_registry",
    "default_serving_rules", "default_tracer", "default_training_rules", "device_spec",
    "get_logger", "ingest_deadman_rule",
    "merge_alerts", "merge_cluster", "merge_incidents",
    "merge_metrics",
    "merge_timeline", "peak_flops_per_sec",
    "publish_snapshot", "read_snapshot_dir", "record_change",
    "reset_default_journal", "reset_default_registry",
    "reset_default_tracer", "write_snapshot",
]

#: log-spaced bounds sized for step/phase durations (100µs … ~100s)
STEP_BUCKETS = default_buckets(start=1e-4, factor=2.0, count=21)


class Telemetry:
    """The bundle the training/serving drivers speak to.

    Without arguments it adopts the process-wide default registry (so
    the resilience layer's counters land in the same snapshot), the
    process-wide default tracer (the one the driver loop, the plan
    engine, the prefetcher and the server record their in-place spans
    into — :func:`default_tracer`) and a fresh goodput ledger.  The
    hooks below feed histograms and the ledger; the spans themselves
    are opened where the work happens, not here.  ``trace_every``
    thins what the hooks still attach to those spans — the cost-model
    args and the profiled compute/collective children of every Nth
    step (1 = every step, the default; 0 = none).  ``snapshot_dir``
    makes :meth:`write_snapshot` drop ``<host>.json`` payloads for
    ``tools/run_report.py``.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 ledger: Optional[GoodputLedger] = None,
                 host: str = "local",
                 snapshot_dir: Optional[str] = None,
                 trace_every: int = 1,
                 perf: Optional[PerfAccountant] = None):
        self.registry = registry if registry is not None \
            else default_registry()
        self.tracer = tracer if tracer is not None else default_tracer()
        self.ledger = ledger or GoodputLedger()
        # XLA cost-model work accounting (telemetry/perf.py): built on
        # the same registry so the mfu family lands in one snapshot
        self.perf = perf if perf is not None \
            else PerfAccountant(registry=self.registry)
        self.host = str(host)
        self.snapshot_dir = snapshot_dir
        self.trace_every = max(0, int(trace_every))
        self.incarnation = 0
        self._steps_seen = 0
        self._recovery_t0: Optional[float] = None  # tracer clock
        #: optional online SLO engine (telemetry/slo.py) — a
        #: TrainingHealthMonitor built over this bundle registers
        #: itself here so payload() publishes the active-alert view
        self.slo = None
        #: optional incident engine (telemetry/incidents.py) —
        #: registered the same way so payload() publishes open/recent
        #: incident bundles alongside the alerts they explain
        self.incidents = None
        r = self.registry
        # bind the CONCRETE unlabeled series (family.labels()), not the
        # family wrapper: the per-step hooks below run inside the
        # driver loop, and the family->labels->child indirection was a
        # measurable slice of per-iteration idle at millisecond step
        # times (the child exposes the same observe/inc/value/sum API)
        self.steps = r.counter(
            "bigdl_train_steps_total", "compiled train steps run"
        ).labels()
        self.records = r.counter(
            "bigdl_train_records_total", "records trained").labels()
        self.step_seconds = r.histogram(
            "bigdl_train_step_seconds",
            "compiled step wall time (post-compile)",
            bounds=STEP_BUCKETS, window=1024).labels()
        self.compile_seconds = r.histogram(
            "bigdl_train_compile_seconds",
            "first-step wall time of each fresh program (XLA build)",
            bounds=STEP_BUCKETS).labels()
        self.data_wait_seconds = r.histogram(
            "bigdl_train_data_wait_seconds",
            "host wait on the input pipeline per iteration",
            bounds=STEP_BUCKETS, window=1024).labels()
        self.h2d_seconds = r.histogram(
            "bigdl_train_host_to_device_seconds",
            "host-to-device placement (infeed sharding) per iteration",
            bounds=STEP_BUCKETS).labels()
        self.checkpoint_seconds = r.histogram(
            "bigdl_checkpoint_write_seconds",
            "checkpoint write/dispatch wall time",
            bounds=STEP_BUCKETS).labels()
        self.checkpoint_blocked_seconds = r.histogram(
            "bigdl_checkpoint_blocked_seconds",
            "critical-path seconds blocked on checkpoint back-pressure "
            "(async writer queue full)",
            bounds=STEP_BUCKETS).labels()
        self.recoveries = r.counter(
            "bigdl_recovery_windows_total",
            "fault-to-first-productive-step recovery windows").labels()
        self.skipped_steps = r.counter(
            "bigdl_guard_skipped_steps_total",
            "steps skipped by the NaN/Inf gradient guard").labels()

    # -- driver hooks ----------------------------------------------------
    def _trace_due(self) -> bool:
        return (self.trace_every > 0
                and self._steps_seen % self.trace_every == 0)

    def on_attempt_begin(self):
        """Start of an optimize attempt: the run clock starts (first
        attempt only — the ledger is idempotent)."""
        self.ledger.start()

    def on_data_wait(self, seconds: float, step: Optional[int] = None):
        """Host time spent waiting on the input pipeline (the span is
        ``train.data_wait``, opened around the wait itself)."""
        seconds = max(0.0, float(seconds))
        self.data_wait_seconds.observe(seconds)
        self.ledger.add("data_stall", seconds)

    def on_host_to_device(self, seconds: float,
                          step: Optional[int] = None):
        """Host→device placement (infeed sharding) — ledgered as part
        of the data stall (the span is ``train.place_batch``)."""
        seconds = max(0.0, float(seconds))
        self.h2d_seconds.observe(seconds)
        self.ledger.add("data_stall", seconds)

    def on_step_commit(self, seconds: float, span=None):
        """A step's loss has arrived — the driver's commit, BEFORE the
        next step is enqueued.  What has to happen at the step's own
        boundary and while its ``train.iteration`` span is live: an
        open recovery window closes where this step BEGAN (the step's
        own ``seconds`` are attributed by :meth:`on_step`, not as
        recovery), and the cost model's static FLOPs/bytes/intensity go
        onto the live span, so a profiler session's event carries them.
        Cheap: a flag read and a dict update.  Idempotent — a later
        :meth:`on_step` for the same step finds both done."""
        seconds = max(0.0, float(seconds))
        if self.ledger.in_recovery:
            rec = self.ledger.recovery_end(exclude=seconds)
            t0, self._recovery_t0 = self._recovery_t0, None
            if rec and t0 is not None and self.trace_every > 0:
                self.tracer.record("recovery", "recovery", t0, rec)
        if isinstance(span, Span) and span.end is None \
                and self._trace_due():  # live, and not the disabled
            # tracer's null span
            span.set(**self.perf.span_args())

    def on_step(self, seconds: float, records: int = 0,
                step: Optional[int] = None, compiled: bool = False,
                phase_split=None, skipped: bool = False, span=None):
        """One compiled-step dispatch completed.  ``compiled=True``
        classifies it as compile time (the first step of every fresh
        program).  ``span`` is the caller's ``train.iteration`` span of
        that step — live, or closed already where the driver reports a
        step after the next one is enqueued and has called
        :meth:`on_step_commit` while it was live: ``phase_split`` (the
        optional :class:`~bigdl_tpu.optim.profiling.PhaseSplit` of a
        profiled step) becomes its compute / collective children, laid
        from the span's own start — estimates of device time, not clock
        truths."""
        seconds = max(0.0, float(seconds))
        self.on_step_commit(seconds, span)
        self.ledger.add("compile" if compiled else "productive", seconds)
        self.steps.inc()
        if records:
            self.records.inc(records)
        if skipped:
            self.skipped_steps.inc()
        (self.compile_seconds if compiled
         else self.step_seconds).observe(seconds)
        self.perf.on_step(seconds, compiled=compiled)
        if isinstance(span, Span) and phase_split is not None \
                and self._trace_due():
            compute_s, collective_s = phase_split
            self.tracer.record("compute", "compute", span.start,
                               compute_s, parent=span, step=step)
            self.tracer.record("collective", "collective",
                               span.start + compute_s,
                               collective_s, parent=span, step=step)
        self._steps_seen += 1

    def on_checkpoint(self, seconds: float, step: Optional[int] = None):
        """Checkpoint snapshot/write seconds on the critical path (the
        span is ``train.checkpoint``, opened around the call)."""
        seconds = max(0.0, float(seconds))
        self.checkpoint_seconds.observe(seconds)
        self.ledger.add("checkpoint", seconds)

    def on_checkpoint_blocked(self, seconds: float,
                              step: Optional[int] = None):
        """Critical-path back-pressure from the background checkpoint
        writer: the step boundary waited ``seconds`` for a previous
        async write to commit.  With async checkpointing this (plus
        the snapshot cost fed to :meth:`on_checkpoint`) is ALL the
        checkpoint time the ledger should ever see."""
        seconds = max(0.0, float(seconds))
        if seconds <= 0.0:
            return
        self.checkpoint_blocked_seconds.observe(seconds)
        self.ledger.add("checkpoint", seconds)

    def on_recovery_begin(self):
        """A fault was detected (retry rollback, membership change):
        wall clock is recovery until the next completed step."""
        if not self.ledger.in_recovery:
            self.recoveries.inc()
            self._recovery_t0 = self.tracer.clock()
        self.ledger.recovery_begin()

    # -- export ----------------------------------------------------------
    #: newest spans carried per published payload — enough for the
    #: cluster timeline's recent window without bloating KV puts
    SPAN_EXPORT_LIMIT = 512

    def payload(self, step: Optional[int] = None) -> dict:
        """The publishable telemetry payload (what lands on the KV
        transport and in snapshot directories).  ``spans`` (the newest
        :data:`SPAN_EXPORT_LIMIT`, with a mono/wall clock anchor) is
        what ``merge_timeline`` folds into the cluster-wide Perfetto
        view."""
        return {
            "host": self.host,
            "step": step,
            "incarnation": int(self.incarnation),
            "ts": time.time(),
            "goodput": self.ledger.snapshot(),
            "metrics": self.registry.snapshot()["metrics"],
            "span_totals": self.tracer.category_totals(),
            "spans": self.tracer.export_spans(self.SPAN_EXPORT_LIMIT),
            "clock_anchor": {"mono": self.tracer.clock(),
                             "wall": time.time()},
            "perf": self.perf.payload(),
            # active/recent SLO alerts (None without an engine) — the
            # cluster fold unions these into the run-report alert table
            "alerts": (self.slo.snapshot() if self.slo is not None
                       else None),
            # open/recent incident bundles (None without an engine) —
            # merge_incidents folds them cluster-wide like alerts
            "incidents": (self.incidents.snapshot()
                          if self.incidents is not None else None),
        }

    def write_snapshot(self, directory: Optional[str] = None,
                       step: Optional[int] = None) -> Optional[str]:
        """Drop ``<host>.json`` into ``directory`` (default: the
        configured ``snapshot_dir``); no-op without one."""
        directory = directory or self.snapshot_dir
        if directory is None:
            return None
        return write_snapshot(directory, self.host, self.payload(step))

    def to_summary(self, summary, step: int):
        """Write the goodput ledger + headline counters as scalar
        events (tags ``telemetry/<field>``) through a
        ``visualization.summary.Summary`` (e.g.
        :class:`~bigdl_tpu.visualization.TelemetrySummary`)."""
        snap = self.ledger.snapshot()
        summary.add_scalar("telemetry/goodput_fraction",
                           snap["productive_fraction"], step)
        summary.add_scalar("telemetry/accounted_fraction",
                           snap["accounted_fraction"], step)
        for cat, secs in snap["seconds"].items():
            summary.add_scalar(f"telemetry/{cat}_s", secs, step)
        summary.add_scalar("telemetry/steps_total", self.steps.value,
                           step)
        summary.add_scalar("telemetry/recovery_windows",
                           self.recoveries.value, step)
        return summary
