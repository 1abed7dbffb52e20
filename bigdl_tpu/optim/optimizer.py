"""Optimizer frontend + LocalOptimizer (reference optim/Optimizer.scala:42,
LocalOptimizer.scala:41).

The reference's LocalOptimizer clones the model per core and hand-merges
gradients (LocalOptimizer.scala:66-142); on TPU the whole iteration is
ONE jitted function — forward, loss, backward, optimizer update — and
batch parallelism is XLA vectorization.  The host loop owns only what
the reference driver owned: triggers, epochs, validation, checkpointing,
summaries, metrics.
"""
from __future__ import annotations

import logging
import os
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..dataset.sample import MiniBatch, SampleToMiniBatch
from ..nn.module import AbstractModule
from ..parallel.plan import FSDP_MIN_BYTES
from ..resilience.guards import LossSpikeDetector
from ..resilience.preemption import PreemptionHandler
from ..resilience.retry import LossSpikeError, RetryPolicy
from ..utils.engine import get_property
from ..utils.rng import next_jax_key, peek_jax_key
from .metrics import Metrics
from .optim_method import SGD, OptimMethod
from .trigger import Trigger
from .validation import ValidationMethod

# the library never configures root logging at import time (the
# print/basicConfig lint enforces it); applications and the package's
# own entry points opt in via telemetry.slog.configure_logging()
log = logging.getLogger("bigdl_tpu")


class Optimizer:
    """Fluent training config (reference Optimizer.scala fluent API +
    factory ``Optimizer(model=..., dataset=..., criterion=...)``:324)."""

    def __init__(self, model: AbstractModule, dataset, criterion,
                 batch_size: Optional[int] = None, end_trigger: Optional[Trigger] = None):
        from .trigger import max_epoch

        # Samples → MiniBatch conversion at the factory, like
        # Optimizer.apply (Optimizer.scala:330-335)
        if batch_size is not None and not _yields_minibatch(dataset):
            dataset = dataset.transform(SampleToMiniBatch(batch_size))
        self.batch_size = batch_size
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.optim_method: OptimMethod = SGD(learning_rate=1e-3)
        self.end_when: Trigger = end_trigger or max_epoch(1)
        self.checkpoint_path: Optional[str] = None
        self.checkpoint_trigger: Optional[Trigger] = None
        self.checkpoint_format = "pickle"
        self._orbax = None
        self.is_overwrite = False
        self.validation_trigger: Optional[Trigger] = None
        self.validation_dataset = None
        self.validation_methods: Sequence[ValidationMethod] = ()
        self.validation_output_seq_dim = "auto"
        self.train_summary = None
        self.validation_summary = None
        self.metrics = Metrics()
        # reference straggler knobs (Optimizer.scala:229-243) — wired to
        # the elastic straggler policy (resilience/elastic.py): they set
        # the skew threshold / eviction budget once set_elastic attaches
        # a coordinator; inert (with a warning) on single-host runs
        self.drop_percentage = 0.0
        self.max_drop_percentage = 0.0
        self._drop_warmup = 200
        self.compute_threshold_batchsize = 100
        # mixed precision: compute dtype for fwd/bwd; master weights,
        # gradients and the optimizer update stay float32 (the TPU-native
        # analogue of the reference's fp16 wire codec,
        # FP16CompressedTensor.scala:26 — on TPU the precision knob moves
        # from the wire to the MXU)
        self.compute_dtype = None
        # GPipe microbatch count for meshes with a 'pipe' axis (None:
        # the driver defaults to the pipe-axis size)
        self.pipeline_microbatch = None
        # unified sharding-plan engine (parallel/plan.py, ISSUE 8):
        # every mesh path compiles through ONE compile_step_with_plan
        # builder.  ``sharding_plan`` overrides the derived default
        # rule set; ``fsdp_min_bytes`` is the threshold of the FSDP
        # rule (on a data axis of more than one device a large leaf's
        # master, slots and update live on its data shard; only the
        # compute copy is gathered) — 1 MiB unless
        # bigdl.fsdp.minBytes says otherwise; 0 / None replicates.
        self.sharding_plan = None
        _fsdp = get_property("bigdl.fsdp.minBytes")
        self.fsdp_min_bytes = (FSDP_MIN_BYTES if _fsdp is None
                               else int(_fsdp or 0) or None)
        # sparse gradient transport row budget, as a fraction of a
        # table's rows (parallel/plan.py "Gradient transport";
        # bigdl.sparse.density property sets the default, 1/16) —
        # consumed by the derived plan; explicit plans carry their own
        _sd = get_property("bigdl.sparse.density")
        self.sparse_density = float(_sd) if _sd else None
        # relaxed-synchrony defaults for the derived plan's sparse-
        # table rules (parallel/plan.py "Synchrony"; bigdl.sync.period
        # / bigdl.sync.staleness properties set the defaults, None =
        # lockstep).  Dense rules opt in per rule via an explicit plan.
        _syp = get_property("bigdl.sync.period")
        self.sync_period = int(_syp) if _syp else None
        _sys = get_property("bigdl.sync.staleness")
        self.sync_staleness = int(_sys) if _sys else None
        # relaxed-synchrony checkpoint plumbing: the newest per-replica
        # snapshot (rides the trainState leg so resume is bitwise
        # across an averaging boundary), the restored one (consumed
        # once by the next _plan_loop), and the membership-change flag
        # that forces an averaging round instead of resuming divergence
        self._sync_snapshot = None
        self._sync_resume = None
        self._sync_force_average = False
        # how the last profiled iteration's phase split was measured:
        # "trace" (jax.profiler device events) or None (not profiled),
        # and that split (profiling.PhaseSplit, device seconds)
        self.phase_source = None
        self.phase_split = None
        # online-training slices (train_more / the continuous-learning
        # loop) call optimize() every few steps — rebuilding the plan
        # engine each call would re-trace the jitted step and bill the
        # run a compile per slice.  When opted in, the compiled engine
        # is cached per mesh identity and reused while model/plan
        # knobs are untouched (elastic runs re-derive per attempt and
        # never reuse).
        self.reuse_compiled_engine = False
        self._engine_cache = None
        self._engine_cache_hit = False  # (mesh_key, engine)
        # --- resilience (bigdl_tpu/resilience/) -----------------------
        # gradient anomaly guard: NaN/Inf steps are skipped in-program
        # (params/slots/buffers ride through intact) and counted
        self.gradient_guard = str(get_property(
            "bigdl.guard.gradients", "true")).lower() in ("1", "true",
                                                          "yes", "on")
        # loss-spike rollback: off unless configured (it needs a
        # checkpoint to roll back to)
        self.spike_detector: Optional[LossSpikeDetector] = None
        _spike_k = get_property("bigdl.guard.spikeK")
        if _spike_k:
            self.spike_detector = LossSpikeDetector(
                k=int(_spike_k),
                ratio=float(get_property("bigdl.guard.spikeRatio", 2.0)),
                warmup=int(get_property("bigdl.guard.spikeWarmup", 10)))
        # retry: exponential backoff + classification (compat aliases
        # bigdl.failure.retryTimes / retryTimeInterval honored inside)
        self.retry_policy = RetryPolicy.from_properties()
        # SIGTERM/SIGINT → checkpoint at the next step boundary + clean
        # resumable exit (off by default: installing signal handlers is
        # an application decision)
        self.handle_preemption = str(get_property(
            "bigdl.preemption.handleSignals", "false")).lower() in (
            "1", "true", "yes", "on")
        self._preemption: Optional[PreemptionHandler] = None
        # elastic multi-host coordination (resilience/elastic.py):
        # heartbeats, hung-collective watchdog, straggler eviction,
        # shrink-to-survivors recovery — off unless set_elastic attaches
        # a context
        self.elastic = None
        # step-fingerprint flight recorder (resilience/integrity.py):
        # off unless set_flight_recorder attaches one
        self.flight_recorder = None
        self.integrity_summary = None
        # unified telemetry spine (bigdl_tpu/telemetry): metrics
        # registry + structured tracer + goodput ledger — off unless
        # set_telemetry attaches one
        self.telemetry = None
        # online health verdicts (telemetry/slo.py): loss/step-time/
        # goodput/MFU SLO rules evaluated WHILE the run is live — off
        # unless set_health_monitor attaches a TrainingHealthMonitor
        self.health_monitor = None
        # --- async everything (docs/async.md) -------------------------
        # background snapshot-then-write checkpointing: serialize at
        # the step boundary (synchronous — bitwise-identical bytes),
        # hand the atomic crc32c write to a background writer thread.
        # On by default: only the I/O is deferred, so resume semantics
        # are unchanged (bigdl.checkpoint.async=false restores the
        # fully synchronous write)
        self.async_checkpoint = str(get_property(
            "bigdl.checkpoint.async", "true")).lower() in (
            "1", "true", "yes", "on")
        self._ckpt_writer = None  # lazy AsyncCheckpointWriter
        self._ckpt_queue_depth = 1
        # bounded prefetch-to-device infeed depth shared by every mesh
        # path (dataset/prefetch.py): 2 = double buffering (default),
        # 0 disables (synchronous fetch, every fetch a real stall)
        self.infeed_depth = int(get_property("bigdl.infeed.depth", 2))
        # input-pipeline resume cursor (records already trained in the
        # interrupted epoch) — set by resume_from_checkpoint when the
        # checkpoint carries train state, consumed once by the loop
        self._resume_cursor: Optional[int] = None
        self.skipped_steps = 0   # anomalous steps skipped by the guard
        self.rollbacks = 0       # checkpoint restores done by retry

    # -- fluent config (Optimizer.scala:98-243) -------------------------
    def set_optim_method(self, method: OptimMethod):
        self.optim_method = method
        return self

    def set_end_when(self, trigger: Trigger):
        self.end_when = trigger
        return self

    def set_validation(self, trigger: Trigger, dataset, v_methods,
                       batch_size: Optional[int] = None,
                       output_seq_dim="auto"):
        """``output_seq_dim`` is forwarded to the on-mesh eval forward
        when validation runs on a mesh with a ``seq`` axis: which dim of
        each output leaf carries the sequence (``"auto"`` probes and
        validates against the input seq dim; ``None`` declares the
        outputs seq-free, e.g. a pooled classifier head; an int names
        the dim explicitly).  Ignored on seq-free meshes."""
        if batch_size is not None and not _yields_minibatch(dataset):
            dataset = dataset.transform(SampleToMiniBatch(batch_size))
        self.validation_trigger = trigger
        self.validation_dataset = dataset
        self.validation_methods = list(v_methods)
        self.validation_output_seq_dim = output_seq_dim
        return self

    def set_checkpoint(self, path: str, trigger: Trigger,
                       format: str = "pickle"):
        """``format="pickle"`` (default) writes whole-module files
        (reference DistriOptimizer.scala:394-416 semantics);
        ``"orbax"`` writes sharded, ASYNC array checkpoints
        (utils/orbax_io.py) — on the sharded mesh paths the device-
        resident trees save without a host gather."""
        if format not in ("pickle", "orbax"):
            raise ValueError(f"checkpoint format {format!r} not in "
                             "('pickle', 'orbax')")
        # re-pointing at a new directory must not keep writing into the
        # old checkpointer's path
        self._orbax_close()
        self._orbax = None
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        self.checkpoint_format = format
        return self

    def overwrite_checkpoint(self):
        self.is_overwrite = True
        return self

    def set_train_summary(self, summary):
        self.train_summary = summary
        return self

    def set_validation_summary(self, summary):
        self.validation_summary = summary
        return self

    def set_compute_dtype(self, dtype):
        """Mixed-precision training: run forward/backward in ``dtype``
        (typically ``jnp.bfloat16``) while keeping float32 master weights
        and a float32 optimizer update.  Gradients arrive float32 through
        the cast's vjp.  Pass ``None`` to restore full precision."""
        self.compute_dtype = jnp.dtype(dtype) if dtype is not None else None
        return self

    def set_pipeline_microbatch(self, n: int):
        """GPipe microbatch count M for training over a mesh with a
        ``pipe`` axis (parallel/pipeline.py).  Larger M shrinks the
        pipeline bubble (``(S-1)/(M+S-1)``) at the cost of smaller
        per-microbatch matmuls; the per-device batch must be divisible
        by M.  Default: the pipe-axis size."""
        if int(n) < 1:
            raise ValueError(f"pipeline microbatch must be >= 1, got {n}")
        self.pipeline_microbatch = int(n)
        return self

    def set_sharding_plan(self, plan):
        """Install an explicit :class:`~bigdl_tpu.parallel.plan.Plan`
        (ordered regex rules mapping param-tree path names to
        PartitionSpecs).  ``None`` restores the derived default —
        module introspection plus the FSDP threshold rule at
        :meth:`set_fsdp`'s threshold (an explicit plan carries its own
        ``fsdp_min_bytes``).  The plan re-binds to the live mesh
        every attempt, so elastic shrink/regrow is one mesh+plan
        re-derivation."""
        self.sharding_plan = plan
        return self

    def set_fsdp(self, min_bytes: Optional[int] = FSDP_MIN_BYTES):
        """The threshold of FSDP-style parameter sharding, which is ON
        by default at 1 MiB wherever the mesh's ``data`` axis has more
        than one device: any dense, step-synchronous parameter of at
        least ``min_bytes`` that the plan would otherwise replicate
        over ``data`` is sharded over it instead (its minor dim
        where the shard is whole lane tiles, else the largest dim that
        divides) — master, optimizer slots and update on the shard; the
        compute-dtype copy gathered on use inside the step; the
        gradient upcast to the master dtype and reduce-scattered — so
        each parameter is updated on ONE shard, and parameters whose
        full tree does not fit one chip train anyway.  ``None`` (or 0)
        replicates: every device keeps and updates the whole tree after
        a gradient all-reduce.  (``bigdl.fsdp.minBytes`` property sets
        the default; 0 replicates.)"""
        self.fsdp_min_bytes = int(min_bytes) if min_bytes else None
        return self

    def set_sparse_density(self, density: Optional[float]):
        """Size the sparse gradient transport's per-step row budget:
        a ``transport="sparse"`` table ships ``ceil(rows * density)``
        ``(index, row)`` pairs per shard instead of its dense gradient,
        with automatic fallback to the dense all-reduce when the budget
        would not beat it — or when a batch overflows it (exact,
        in-program).  ``None`` restores the ``bigdl.sparse.density``
        property default (1/16).  See docs/distributed.md "Gradient
        transport"."""
        self.sparse_density = float(density) if density else None
        return self

    def set_sync_period(self, k: Optional[int]):
        """Default averaging period for the derived plan's RELAXABLE
        rules (data-replicated sparse tables — the Parallax hybrid:
        dense MLP rules stay lockstep): the table runs local SGD and
        every ``k``-th step its replicas (and momentum-style optimizer
        slots) all-reduce-average, cutting the per-step wire by ``k``.
        ``None`` restores the ``bigdl.sync.period`` property default
        (lockstep).  Dense leaves opt in per rule via
        ``set_sharding_plan`` with ``Rule(..., sync="periodic(k)")``.
        See docs/distributed.md "Synchrony"."""
        self.sync_period = int(k) if k else None
        return self

    def set_sync_staleness(self, s: Optional[int]):
        """Default staleness bound for the derived plan's sparse-table
        rules: lookups proceed against the local replica while the
        index+row exchange is in flight — peers' sparse updates apply
        up to ``s`` steps late (bounded staleness, enforced by the
        step-phase watermark).  ``None`` restores the
        ``bigdl.sync.staleness`` property default (lockstep).  See
        docs/distributed.md "Synchrony"."""
        self.sync_staleness = int(s) if s else None
        return self

    def set_drop_module_property(self, drop_percentage, max_drop_percentage,
                                 batch_size=100, warmup_iteration=200):
        """Straggler-drop knobs (reference Optimizer.scala:229-243) —
        no longer a no-op: under ``set_elastic`` they configure the
        straggler policy (``resilience.elastic.StragglerPolicy
        .from_drop_knobs``): ``drop_percentage`` sets the step-time skew
        threshold (``max(1.5, 1/drop_percentage)``× the cluster
        median), ``max_drop_percentage`` caps the eviction budget as a
        fraction of the gang, and ``warmup_iteration`` scales the
        patience before a vote.  A single-host run has no straggler to
        drop; ``optimize()`` warns instead of silently ignoring."""
        self.drop_percentage = float(drop_percentage)
        self.max_drop_percentage = float(max_drop_percentage)
        self.compute_threshold_batchsize = batch_size
        self._drop_warmup = int(warmup_iteration)
        if self.elastic is not None and self.drop_percentage > 0:
            self.elastic.configure_straggler_from_knobs(
                self.drop_percentage, self.max_drop_percentage,
                self._drop_warmup)
        return self

    # -- resilience config (bigdl_tpu/resilience/) ----------------------
    def set_gradient_guard(self, enabled: bool = True):
        """Enable/disable the in-program NaN/Inf gradient guard (on by
        default; ``bigdl.guard.gradients`` property sets the default).
        A guarded anomalous step is skipped — parameters, optimizer
        slots and buffers come out unchanged — and counted in
        ``skipped_steps`` and the train summary."""
        self.gradient_guard = bool(enabled)
        return self

    def set_loss_spike_guard(self, k: int = 3, ratio: float = 2.0,
                             warmup: int = 10):
        """Roll back to the last good checkpoint after ``k`` consecutive
        iterations whose loss exceeds ``ratio``× its running average
        (see resilience.guards.LossSpikeDetector).  Pass ``k=None`` to
        disable.  Needs ``set_checkpoint`` — without one the trigger
        only logs."""
        self.spike_detector = (None if k is None else
                               LossSpikeDetector(k=k, ratio=ratio,
                                                 warmup=warmup))
        return self

    def set_retry_policy(self, policy: RetryPolicy):
        """Replace the failure retry policy (default: built from the
        ``bigdl.failure.*`` properties)."""
        self.retry_policy = policy
        # keep the reference compat aliases (DistriOptimizer.max_retry/
        # retry_window) in sync: _with_retry lets a caller-mutated alias
        # win, so a stale snapshot of the DEFAULT policy must not
        # silently clobber an explicitly installed one
        if hasattr(self, "max_retry"):
            self.max_retry = policy.max_retries
        if hasattr(self, "retry_window"):
            self.retry_window = policy.window
        return self

    def set_async_checkpoint(self, enabled: bool = True,
                             queue_depth: int = 1):
        """Background snapshot-then-write checkpointing (on by
        default; ``bigdl.checkpoint.async`` property sets the
        default).  The checkpoint's bytes are serialized synchronously
        at the step boundary — so deterministic resume stays bitwise —
        and the atomic crc32c-verified write happens on a single
        background writer thread with back-pressure (``queue_depth``
        pending writes; a trigger arriving while the queue is full
        blocks, and that time is ledgered as ``checkpoint``).  The
        writer drains at loop exit, before every restore, and on
        preemption.  See docs/async.md."""
        self.async_checkpoint = bool(enabled)
        if self._ckpt_writer is not None \
                and self._ckpt_writer.queue_depth != int(queue_depth):
            self._ckpt_writer.close()
            self._ckpt_writer = None
        self._ckpt_queue_depth = max(1, int(queue_depth))
        return self

    def set_infeed_prefetch(self, depth: int = 2):
        """Bounded prefetch-to-device infeed depth for every mesh path
        (``bigdl.infeed.depth`` property sets the default, 2 = double
        buffering): a background thread overlaps batch N+1's host prep
        + ``device_put`` with the compiled step on batch N, and
        ``data_stall`` is ledgered only when the buffer was actually
        empty.  ``depth=0`` restores the synchronous fetch."""
        self.infeed_depth = max(0, int(depth))
        return self

    def set_preemption_handling(self, enabled: bool = True):
        """Install SIGTERM/SIGINT handlers for the duration of
        ``optimize()``: on signal, finish the in-flight step, write a
        checkpoint (when a checkpoint path is configured) and return
        cleanly — the next run resumes via ``resume_from_checkpoint``."""
        self.handle_preemption = bool(enabled)
        return self

    def set_flight_recorder(self, recorder):
        """Attach a step-fingerprint flight recorder
        (``resilience.integrity.FlightRecorder``): every iteration
        appends the loss's exact bit pattern, the global gradient norm
        and a crc32c of the batch bytes to its journal, plus a crc32c
        of the parameter tree at the recorder's ``param_crc_every``
        cadence (and whenever a checkpoint is written) — the evidence
        ``resilience.replay`` diffs to localize the first divergent
        step.  Pass ``None`` to detach."""
        self.flight_recorder = recorder
        return self

    def set_integrity_summary(self, summary):
        """Attach a ``visualization.IntegritySummary``: the flight
        recorder's journal length streams as ``FingerprintSteps`` and
        the elastic SDC-vote counters (``IntegrityVotes`` /
        ``IntegrityDisagreements`` / ``IntegrityEvictions``) land in
        the same ``<app>/integrity`` event stream."""
        self.integrity_summary = summary
        if self.elastic is not None:
            self.elastic.integrity_summary = summary
        return self

    def set_telemetry(self, telemetry):
        """Attach a :class:`bigdl_tpu.telemetry.Telemetry` bundle: the
        step loop then feeds the metrics registry (step/data-wait/
        checkpoint histograms, step/record counters), records
        categorized spans into the tracer (Chrome-trace/Perfetto
        export), and classifies run wall clock in the goodput ledger
        (productive/compile/data-stall/checkpoint/recovery/idle —
        docs/observability.md).  Pass ``None`` to detach."""
        self.telemetry = telemetry
        if self.elastic is not None:
            self.elastic.telemetry = telemetry
        return self

    def set_health_monitor(self, monitor):
        """Attach a :class:`bigdl_tpu.telemetry.TrainingHealthMonitor`:
        the step loop then feeds it per-iteration loss and step time,
        it evaluates the training SLO rule pack (loss-descent stall/
        divergence, step-time drift, goodput floor, MFU collapse) at
        its cadence, and :meth:`health_verdict` answers the live
        :class:`~bigdl_tpu.telemetry.HealthVerdict` — the watchdog
        hook the continuous-learning loop consults while serving.
        A monitor built without a telemetry bundle adopts this
        optimizer's at attach time.  Pass ``None`` to detach."""
        self.health_monitor = monitor
        if monitor is not None and monitor.telemetry is None \
                and self.telemetry is not None:
            monitor.telemetry = self.telemetry
            if getattr(self.telemetry, "slo", None) is None:
                self.telemetry.slo = monitor.engine
        return self

    def health_verdict(self):
        """The live training health verdict
        (:class:`~bigdl_tpu.telemetry.HealthVerdict`), or None when no
        monitor is attached."""
        return (self.health_monitor.verdict()
                if self.health_monitor is not None else None)

    def train_more(self, n_steps: int) -> AbstractModule:
        """Continue training for ``n_steps`` more iterations — the
        online-training slice the continuous-learning loop drives.
        The optim method's persisted state table carries ``neval`` /
        ``epoch`` across calls, so each slice resumes exactly where
        the last one stopped; this just extends the end trigger by
        ``n_steps`` completed iterations and re-enters ``optimize()``.
        Enables ``reuse_compiled_engine`` so back-to-back slices
        dispatch into the cached jitted step instead of paying a
        re-trace per slice."""
        from .trigger import max_iteration

        if n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        self.reuse_compiled_engine = True
        done = int(self.optim_method.state.get("neval", 1)) - 1
        self.set_end_when(max_iteration(done + int(n_steps)))
        return self.optimize()

    def _health_step(self, state, loss: float, seconds: float):
        """Per-iteration health feed (no-op without a monitor): the
        monitor samples at its own cadence and must never take down
        training."""
        hm = self.health_monitor
        if hm is None:
            return
        try:
            hm.on_step(state["neval"], loss, seconds)
        except Exception:
            log.debug("health monitor step failed", exc_info=True)

    def set_elastic(self, context):
        """Attach an elastic-cluster context
        (``resilience.elastic.ElasticContext``): the step loop then
        heartbeats every iteration, runs the compiled step under the
        hung-collective watchdog deadline, tracks per-host step-time
        skew, and on a membership change (host death, straggler
        eviction, rejoin) restores the last verified checkpoint and —
        on the data-parallel mesh path — rebuilds the mesh at the
        largest valid shard count for the survivors.  Pass ``None`` to
        detach."""
        self.elastic = context
        if context is not None:
            if self.integrity_summary is not None:
                context.integrity_summary = self.integrity_summary
            if self.telemetry is not None:
                context.telemetry = self.telemetry
            if self.batch_size is not None:
                context.attach(batch_size=self.batch_size)
            if self.drop_percentage > 0:
                context.configure_straggler_from_knobs(
                    self.drop_percentage, self.max_drop_percentage,
                    self._drop_warmup)
        return self

    # -- resilience plumbing shared by the drivers ----------------------
    def _warn_drop_knobs_if_inert(self):
        """Satellite of the straggler wiring: the reference knobs used
        to no-op silently; now they either configure the elastic policy
        or say loudly why they cannot."""
        if self.drop_percentage and self.elastic is None:
            log.warning(
                "straggler-drop knobs set (drop_percentage=%.2f, "
                "max_drop_percentage=%.2f) on a single-host run with no "
                "elastic coordinator — there is no straggler to drop; "
                "attach set_elastic(ElasticContext(...)) for multi-host "
                "straggler eviction", self.drop_percentage,
                self.max_drop_percentage)

    def _elastic_begin(self):
        """Start-of-attempt hook: adopt/rendezvous the current
        incarnation and reset the watchdog estimator."""
        if self.elastic is not None:
            self.elastic.begin_attempt()

    def _elastic_step_start(self, state):
        """Per-iteration hook before the batch fetch: heartbeat +
        membership/straggler/rejoin checks (may raise the retryable
        MembershipChangedError)."""
        if self.elastic is not None:
            self.elastic.on_step_start(state["neval"])

    def _elastic_dispatch(self, dispatch, state):
        """Run one compiled-step dispatch, under the watchdog deadline
        when elastic is attached (the watchdog blocks on the loss, so
        prefetch overlap is traded for hang coverage)."""
        if self.elastic is None:
            return dispatch()
        return self.elastic.run_step(dispatch, state["neval"])

    def _restore_latest(self):
        self.resume_from_checkpoint()

    # -- async checkpoint plumbing (resilience/async_checkpoint.py) -----
    def _checkpoint_writer(self):
        """The lazily-built background checkpoint writer (one per
        optimizer; recreated after close)."""
        from ..resilience.async_checkpoint import AsyncCheckpointWriter

        if self._ckpt_writer is None:
            self._ckpt_writer = AsyncCheckpointWriter(
                queue_depth=self._ckpt_queue_depth)
        return self._ckpt_writer

    def _drain_checkpoints(self, raise_errors: bool = True):
        """Barrier: every submitted checkpoint byte is committed (or
        its write error raised here, on the training thread).  Runs
        before any restore — a rollback must see the newest snapshot —
        and at preemption/loop exit.  The restore path passes
        ``raise_errors=False``: a failed background write there means
        the newest checkpoint is simply absent, which the verified
        walk-back restore already handles by design."""
        if self._ckpt_writer is not None:
            self._ckpt_writer.drain(raise_errors=raise_errors)

    def _close_ckpt_writer(self):
        if self._ckpt_writer is not None:
            self._ckpt_writer.close()
            self._ckpt_writer = None

    def _shutdown_async_writer(self):
        """Best-effort writer close on the way out of ``optimize()`` —
        never raises (an abnormal exit's original exception must not be
        masked); write failures already surfaced through the drain
        barriers on the normal path."""
        w, self._ckpt_writer = self._ckpt_writer, None
        if w is None:
            return
        try:
            w.close()
        except Exception:
            log.exception("async checkpoint writer close failed")

    def _make_feed(self, data_iter, epoch_size: int,
                   start_records: int = 0, transform=None):
        """Feed over one epoch of ``data_iter`` at the configured
        prefetch depth (dataset/prefetch.py); the driver closes it
        before shuffle/rollover and at loop exit.  The default
        transform is the host→device batch conversion."""
        from ..dataset.prefetch import make_feed

        return make_feed(data_iter, epoch_size=epoch_size,
                         start_records=start_records,
                         depth=self.infeed_depth,
                         transform=transform or _device_batch)

    # -- telemetry plumbing shared by the drivers -----------------------
    def _tm_attempt_begin(self):
        """Top of every optimize attempt: start the goodput run clock
        (idempotent — only the first attempt stamps it)."""
        if self.telemetry is not None:
            self.telemetry.on_attempt_begin()

    def _tm_step(self, state, train_time: float, data_time: float,
                 records: int, compiled: bool = False,
                 phase_split=None, skipped: bool = False, span=None):
        """One driver iteration for the telemetry spine: data-wait +
        step time into the registry histograms and goodput ledger,
        categorized spans into the tracer (``compiled=True`` marks the
        first step of a fresh program — mostly XLA build time;
        ``phase_split`` attributes a profiled step's device time to
        compute/collective children)."""
        tm = self.telemetry
        if tm is None:
            return
        step = state["neval"]
        if data_time > 0:
            tm.on_data_wait(data_time, step=step)
        tm.on_step(train_time, records=records, step=step,
                   compiled=compiled, phase_split=phase_split,
                   skipped=skipped, span=span)

    def _tm_commit(self, train_time: float, span):
        """A step's loss has arrived: what the telemetry spine does at
        the step's own boundary, while its ``train.iteration`` span is
        live (a recovery window closes; the static work attributes go
        onto the span).  The rest of the step's telling waits for
        ``train.report`` (:meth:`_tm_step`)."""
        if self.telemetry is not None:
            self.telemetry.on_step_commit(train_time, span)

    def _tm_finish(self, state):
        """End of a training loop: drop the host's snapshot file when a
        snapshot directory is configured (tools/run_report.py input)."""
        if self.telemetry is not None:
            self.telemetry.write_snapshot(step=state.get("neval"))

    def _tm_analyze(self, fn, *args, label: str = "train_step",
                    collective_bytes: float = 0.0,
                    sparse_bytes_saved: float = 0.0,
                    sync_bytes_saved: float = 0.0, **kwargs):
        """Feed the step program to the telemetry PerfAccountant: XLA
        cost-model FLOPs/bytes from lowering ``fn`` with the driver's
        concrete args (no compile, no execution — lowering only traces
        avals, so donated buffers are untouched).  Called once per
        fresh program, at the first dispatch of every mesh path;
        best-effort by contract — analysis failure never touches the
        step loop."""
        tm = self.telemetry
        if tm is None or fn is None:
            return
        tm.perf.analyze_jitted(fn, *args, label=label,
                               collective_bytes=collective_bytes,
                               sparse_bytes_saved=sparse_bytes_saved,
                               sync_bytes_saved=sync_bytes_saved,
                               **kwargs)

    # -- determinism + integrity plumbing (docs/determinism.md) ---------
    def _fault_host(self) -> str:
        """The host name the SDC fault injectors key off: the elastic
        identity on a cluster, ``"local"`` on a single-host run."""
        return self.elastic.host if self.elastic is not None else "local"

    def _maybe_corrupt_params(self, state, params):
        """Apply an armed ``flip_param_bits`` fault to the live params
        (the silent-data-corruption injection point: one mantissa bit,
        everything stays finite).  No-op when nothing is armed."""
        from ..resilience import faults

        if faults.check_param_corruption(self._fault_host(),
                                         state["neval"]):
            log.warning("fault injection: flipping a parameter bit at "
                        "iteration %d", state["neval"])
            params = faults.flip_tree_bits(params)
        return params

    def _record_fingerprint(self, state, loss, grad_norm, batch,
                            params_fn, skipped=False):
        """One flight-recorder entry for this iteration (no-op without
        a recorder): the step fingerprint, plus a parameter checksum
        at the recorder's cadence."""
        rec = self.flight_recorder
        if rec is None:
            return
        from ..resilience.integrity import batch_fingerprint, checksum_tree

        step = state["neval"]
        rec.record_step(
            step=step, epoch=state["epoch"], loss=loss,
            grad_norm=grad_norm,
            batch_id=batch_fingerprint(batch), skipped=skipped)
        if rec.wants_param_crc(step):
            rec.record_param(step, checksum_tree(params_fn()))
        if self.integrity_summary is not None:
            self.integrity_summary.add_scalar(
                "FingerprintSteps", rec.steps_recorded, step)

    def _record_checkpoint_param_crc(self, state, tree):
        """Parameter checksum at checkpoint cadence — ties every
        written checkpoint to a journal fingerprint, so replay can
        verify a checkpoint's params against the run that wrote it.
        ``tree`` may be a whole checkpoint tree (the orbax layouts:
        params under ``"params"``, or the pipeline's packed tree) or
        a bare param tree (the pickle path)."""
        if self.flight_recorder is None:
            return
        from ..resilience.integrity import checksum_tree

        if isinstance(tree, dict) and "params" in tree:
            tree = tree["params"]
        self.flight_recorder.record_param(state["neval"] - 1,
                                          checksum_tree(tree))

    def _integrity_step(self, state, params_fn):
        """Cross-host SDC vote at the elastic context's cadence: this
        host's parameter checksum against the gang's strict majority.
        Raises through to the retry loop (eviction/restore) on a
        flagged host; fatal IntegrityError without a quorum."""
        el = self.elastic
        if el is None or getattr(el, "integrity_cadence", 0) <= 0:
            return
        step = state["neval"]
        if step % el.integrity_cadence != 0:
            return
        from ..resilience.integrity import checksum_tree

        el.integrity_vote(step, checksum_tree(params_fn()))

    def _train_state_dict(self, state) -> dict:
        """The non-parameter half of total training state: the host RNG
        stream (per-step jax keys, shuffles) and the input pipeline's
        order/cursor — what turns "restore the params" into "resume on
        the exact next batch"."""
        from ..utils.rng import RNG

        out = {"version": 1,
               "rng": RNG().state_dict(),
               "dataset": self.dataset.state_dict(),
               "records_this_epoch": int(
                   state.get("records_this_epoch", 0))}
        if self._sync_snapshot is not None:
            # relaxed synchrony: the exact per-replica stacks + stale
            # pending buffers — what makes resume bitwise across an
            # averaging boundary (docs/distributed.md "Synchrony");
            # the step-phase counters ride optimMethod's state table
            out["sync"] = self._sync_snapshot
        return out

    def _apply_train_state(self, ts: dict):
        from ..utils.rng import RNG

        if not isinstance(ts, dict) or "rng" not in ts:
            return
        RNG().load_state_dict(ts["rng"])
        self.dataset.load_state_dict(ts.get("dataset") or {})
        self._resume_cursor = int(ts.get("records_this_epoch", 0))
        self._sync_resume = ts.get("sync")

    def _consume_resume_cursor(self, data_iter, epoch_size: int) -> int:
        """Fast-forward a fresh epoch iterator past the records the
        interrupted run already trained on (deterministic recomputation
        of the input pipeline — the order is restored state, so the
        skipped batches are bit-identical to the ones trained).
        Returns the restored records-this-epoch count."""
        cursor, self._resume_cursor = self._resume_cursor, None
        if not cursor:
            return 0
        if cursor >= epoch_size:
            log.warning("resume cursor %d >= epoch size %d — starting "
                        "the epoch from its first record", cursor,
                        epoch_size)
            return 0
        skipped = 0
        while skipped < cursor:
            skipped += next(data_iter).size()
        log.info("resumed input pipeline at record %d/%d of the "
                 "interrupted epoch", skipped, epoch_size)
        return skipped

    def _with_retry(self, fn):
        """Failure-retry loop shared by every driver (reference
        DistriOptimizer.scala:750-816, upgraded: exponential backoff +
        jitter between attempts, fatal errors never retried).  Without
        a checkpoint there is nothing to restore — first error raises,
        matching the reference loop — unless an elastic context is
        attached: membership changes and watchdog trips must still
        re-enter the attempt (with a fresh mesh) even when nothing is
        checkpointed."""
        if self.checkpoint_path is None and self.elastic is None:
            return fn()

        def on_retry(exc, attempt):
            self.rollbacks += 1
            if self.telemetry is not None:
                # everything until the next completed step is recovery
                self.telemetry.on_recovery_begin()
            if self.spike_detector is not None:
                self.spike_detector.reset()
            self._restore_latest()

        return self.retry_policy.run(fn, on_retry=on_retry)

    def _preemption_scope(self):
        """Context manager arming preemption handling for one run (a
        no-op context when disabled)."""
        import contextlib

        if not self.handle_preemption:
            self._preemption = None
            return contextlib.nullcontext()
        self._preemption = PreemptionHandler()
        return self._preemption

    def _preempted(self) -> bool:
        return self._preemption is not None and self._preemption.should_stop

    def _check_loss_anomaly(self, loss: float, skipped: bool):
        """Host-side per-iteration anomaly accounting: count guard
        skips, feed the spike detector, and raise the retryable
        LossSpikeError when it trips (the retry loop answers with a
        rollback to the last good checkpoint)."""
        if skipped:
            self.skipped_steps += 1
            log.warning("gradient anomaly (NaN/Inf) — step skipped "
                        "(%d total); params/slots unchanged",
                        self.skipped_steps)
            return
        if self.spike_detector is not None and \
                self.spike_detector.update(loss):
            if self.checkpoint_path is None:
                log.error("loss spike detected (loss %.6g) but no "
                          "checkpoint is configured — cannot roll back; "
                          "continuing", loss)
                return
            raise LossSpikeError(
                f"training loss diverged (loss {loss:.6g} after "
                f"{self.spike_detector.k} consecutive spikes) — rolling "
                "back to the last good checkpoint")

    def _write_pickle_checkpoint(self, state):
        """Atomic, checksummed model/optimMethod/trainState pickle
        checkpoint (tmp + fsync + rename, crc32c sidecars — the write
        side of the verified-restore contract in resilience.checkpoint).

        With ``async_checkpoint`` (the default) this is snapshot-then-
        write: the three legs are SERIALIZED here, synchronously at the
        step boundary (so the bytes — and therefore any later resume —
        are bit-identical to a synchronous write), and the atomic
        writes happen on the background writer thread.  Only the
        serialize cost and any writer back-pressure stay on the
        critical path (docs/async.md)."""
        from ..utils import file_io

        if self.checkpoint_path is None:
            return
        t_ck0 = time.time()
        n = state["neval"] - 1
        suffix = "" if self.is_overwrite else f".{n}"
        # the third leg of total state: host RNG stream + input-pipeline
        # order/cursor — what makes the resume land on the exact next
        # batch instead of restarting the epoch (docs/determinism.md)
        legs = (("model", self.model),
                ("optimMethod", self.optim_method),
                ("trainState", self._train_state_dict(state)))
        if not self.async_checkpoint:
            for name, obj in legs:
                file_io.save(obj,
                             file_io.join(self.checkpoint_path,
                                          f"{name}{suffix}"),
                             overwrite=True, atomic=True, checksum=True)
            self._record_checkpoint_param_crc(state,
                                              self.model.param_tree())
            if self.telemetry is not None:
                self.telemetry.on_checkpoint(time.time() - t_ck0, step=n)
            return
        files = tuple(
            (file_io.join(self.checkpoint_path, f"{name}{suffix}"),
             file_io.serialize(obj))
            for name, obj in legs)
        self._record_checkpoint_param_crc(state, self.model.param_tree())
        snap_s = time.time() - t_ck0
        blocked = self._checkpoint_writer().submit(n, files)
        if self.telemetry is not None:
            # the snapshot (serialize) cost is the checkpoint's real
            # critical-path tax; back-pressure is ledgered separately
            self.telemetry.on_checkpoint(snap_s, step=n)
            self.telemetry.on_checkpoint_blocked(blocked, step=n)

    # -- orbax sharded checkpoints (utils/orbax_io.py) -------------------
    @staticmethod
    def _orbax_tree(params, slots, buffers=None):
        """Checkpoint tree with empty subtrees dropped (orbax rejects
        leafless nodes)."""
        tree = {"params": params}
        if slots is not None and jax.tree_util.tree_leaves(slots):
            tree["slots"] = slots
        if buffers is not None and jax.tree_util.tree_leaves(buffers):
            tree["buffers"] = buffers
        return tree

    def _orbax_save(self, state, tree, kind: str):
        """Async-save ``tree`` as it is sharded (device arrays write
        their own shards; no host gather) plus a small pickle sidecar
        carrying the optimizer state table, the tree's abstract shapes
        (the restore skeleton) and ``kind`` ("model": params are the
        module tree; "packed": the pipeline's packed layout)."""
        import pickle

        from ..utils.orbax_io import ShardedCheckpointer, latest_step

        if self._orbax is None:
            self._orbax = ShardedCheckpointer(self.checkpoint_path)
        t_ck0 = time.time()
        n = state["neval"] - 1
        # retention safety: snapshot the newest COMMITTED step before
        # kicking off step n's async save — probing after the save
        # starts could see n's not-yet-committed directory as "latest"
        # and delete the actual last good checkpoint while n is still
        # in flight.  Drain the PREVIOUS async save first: probing
        # while it is still writing would miss it, and save(n)'s own
        # internal wait would then commit it right before retention
        # deletes it as not-in-keep.
        committed_before = None
        blocked = 0.0
        if self.is_overwrite:
            # draining the PREVIOUS async save is back-pressure, not
            # fresh checkpoint work — ledger it as such
            t_w0 = time.time()
            self._orbax.wait()
            blocked = time.time() - t_w0
            committed_before = latest_step(self._orbax.directory)
        self._orbax.save(n, tree)
        meta = {"kind": kind, "state": dict(state),
                "train_state": self._train_state_dict(state),
                "abstract": jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    tree)}
        # snapshot-then-write for the sidecar too: the bytes are fixed
        # here (meta holds host state + abstract shapes only); the file
        # write rides the background checkpoint writer.  FIFO order
        # keeps meta-N committed before any later checkpoint's legs,
        # and restore paths drain the writer first.
        meta_path = os.path.join(self._orbax.directory, f"meta-{n}.pkl")
        meta_bytes = pickle.dumps(meta)
        if self.async_checkpoint:
            blocked += self._checkpoint_writer().submit(
                n, fn=lambda: _write_plain(meta_path, meta_bytes))
        else:
            _write_plain(meta_path, meta_bytes)
        self._record_checkpoint_param_crc(state, tree)
        if self.is_overwrite:
            # bounded retention (the pickle path's overwrite analogue):
            # keep the in-flight step n AND the newest already-committed
            # step (crash safety while n's async save is still writing);
            # everything older deletes
            import shutil

            from ..utils.orbax_io import ShardedCheckpointer as SC

            keep = {n, committed_before
                    if committed_before is not None else n}
            for name in os.listdir(self._orbax.directory):
                if ".corrupt" in name:
                    continue  # quarantined evidence is never reclaimed
                for prefix, is_dir in ((SC.PREFIX, True), ("meta-", False),
                                       (SC.MANIFEST_PREFIX, False)):
                    if name.startswith(prefix):
                        tail = name[len(prefix):].split(".")[0]
                        if tail.isdigit() and int(tail) not in keep:
                            p = os.path.join(self._orbax.directory, name)
                            (shutil.rmtree if is_dir
                             else os.remove)(p)
        if self.telemetry is not None:
            # the async save's host-side dispatch cost; the shard
            # writes overlap the next steps by design.  Back-pressure
            # (waiting out the previous save) is its own ledger line.
            self.telemetry.on_checkpoint(
                max(0.0, time.time() - t_ck0 - blocked), step=n)
            self.telemetry.on_checkpoint_blocked(blocked, step=n)

    def _orbax_restore_into_model(self) -> bool:
        """Restore the newest orbax step host-side into the live
        model/optimizer (the resume path).  Returns False when no
        committed step exists."""
        import pickle

        from ..utils.orbax_io import (ShardedCheckpointer, _is_finalized,
                                      latest_step, quarantine_step,
                                      verify_step)

        if self.checkpoint_path is None:
            return False
        directory = os.path.abspath(self.checkpoint_path)

        def _older_than(n):
            # same commit-marker guard as latest_step: a torn step can
            # have a meta sidecar (written synchronously before the
            # async save finished) — never restore it
            older = [
                s for s in range(n)
                if os.path.isdir(os.path.join(
                    directory, f"{ShardedCheckpointer.PREFIX}{s}"))
                and _is_finalized(os.path.join(
                    directory, f"{ShardedCheckpointer.PREFIX}{s}"))]
            return max(older) if older else None

        n = latest_step(directory)
        meta = None
        while n is not None:
            # crc32c manifest check: a bit-flipped or truncated shard
            # is quarantined and restore walks back to the previous
            # good step (manifest-less legacy steps pass through)
            if verify_step(directory, n) is False:
                log.warning("orbax step %d failed crc32c verification — "
                            "quarantining and falling back", n)
                quarantine_step(directory, n)
                n = latest_step(directory)
                continue
            # a crash between the async step commit and the sidecar
            # write can leave a committed step without meta — fall back
            # to the newest step that has one
            try:
                with open(os.path.join(directory, f"meta-{n}.pkl"),
                          "rb") as f:
                    meta = pickle.load(f)
                break
            except FileNotFoundError:
                log.warning("orbax step %d has no meta sidecar "
                            "(interrupted save?) — falling back", n)
                n = _older_than(n)
            except (pickle.UnpicklingError, EOFError, OSError) as e:
                log.warning("orbax step %d has an unreadable meta "
                            "sidecar (%s) — quarantining and falling "
                            "back", n, e)
                quarantine_step(directory, n)
                n = latest_step(directory)
        if meta is None:
            return False
        if self._orbax is None:
            self._orbax = ShardedCheckpointer(directory)
        tree = self._orbax.restore(n, meta["abstract"], host=True)
        if meta["kind"] == "packed":
            from ..parallel.pipeline import unpack_params

            unpack_params(tree["params"], self.model)
        else:
            self.model.set_param_tree(tree["params"])
            if tree.get("buffers"):
                self.model.set_buffer_tree(tree["buffers"])
        self.optim_method._slots = tree.get("slots") or None
        self.optim_method.state.update(meta["state"])
        if meta.get("train_state"):
            self._apply_train_state(meta["train_state"])
        return True

    def _orbax_close(self):
        if self._orbax is not None:
            self._orbax.close()

    def resume_from_checkpoint(self, step: Optional[int] = None) -> bool:
        """Restore the newest checkpoint at ``checkpoint_path`` into the
        live model/optimizer — the manual-resume entry point (reference
        'manual via Module.load + OptimMethod.load'); the Distri retry
        loop calls it automatically on failure.  Returns False when
        there is nothing to restore.

        The restore is *total* when the checkpoint carries a
        ``trainState`` leg (written since the determinism work): the
        host RNG stream and the input pipeline's order + record cursor
        come back too, so the resumed run continues on the exact next
        batch (docs/determinism.md).  ``step`` pins the restore to the
        newest checkpoint at or below that step (the replay entry
        point's knob); optimMethod/trainState are always pinned to the
        step the model actually restored from, so the trio can never
        mix steps on a partially corrupt directory."""
        # a restore must see every checkpoint already triggered: commit
        # any in-flight background write first (a write that FAILED is
        # simply absent — the verified walk-back below handles that)
        self._drain_checkpoints(raise_errors=False)
        if self.checkpoint_format == "orbax":
            if step is not None:
                log.warning("resume_from_checkpoint(step=%s) is pickle-"
                            "format only; orbax restores the newest "
                            "verified step", step)
            return self._orbax_restore_into_model()
        from ..resilience.checkpoint import verify_and_load_latest

        restored_any = False
        restored, path = verify_and_load_latest(self.checkpoint_path,
                                                "model", max_step=step)
        pin = step
        if restored is not None:
            self.model.set_param_tree(restored.param_tree())
            self.model.set_buffer_tree(restored.buffer_tree())
            restored_any = True
            tail = path.rsplit(".", 1)[-1] if path else ""
            if tail.isdigit():
                pin = int(tail)
        om, _path = verify_and_load_latest(self.checkpoint_path,
                                           "optimMethod", max_step=pin)
        if om is not None:
            self.optim_method = om
            restored_any = True
        ts, _path = verify_and_load_latest(self.checkpoint_path,
                                           "trainState", max_step=pin)
        if ts is not None:
            self._apply_train_state(ts)
        return restored_any

    # ------------------------------------------------------------------
    # the unified plan driver (parallel/plan.py, ISSUE 8): ONE loop for
    # every mesh shape — the four hand-wired paths (Local + Distri
    # data/multi-axis/pipeline) collapsed into this single code path,
    # so elastic hooks, watchdog, integrity fingerprints, telemetry
    # spans, prefetch infeed and async checkpointing are threaded
    # through exactly once.
    # ------------------------------------------------------------------
    @staticmethod
    def _should(trigger, state) -> bool:
        return trigger is not None and trigger(state)

    def _report_validation(self, state, results):
        """Log + summarize validation results and update the trigger
        score — the one copy shared by every mesh shape."""
        for method, result in zip(self.validation_methods, results):
            log.info("%s is %s", method.format(), result)
            if self.validation_summary is not None:
                self.validation_summary.add_scalar(
                    method.format(), result.result()[0],
                    state["neval"] - 1)
            if method.format() in ("Top1Accuracy", "Top5Accuracy"):
                state["score"] = result.result()[0]

    def _plan_optimize(self, mesh) -> AbstractModule:
        """Retry wrapper around the unified loop.  With an elastic
        context the mesh (and therefore the plan) is re-derived PER
        ATTEMPT from the live membership — shrink/regrow on ANY mesh
        shape is one mesh+plan re-derivation, keeping the template's
        model/pipe axes (the old shrink silently degraded a multi-axis
        mesh to data-only)."""
        if self.elastic is not None:
            self.elastic.attach(n_devices=len(jax.devices()),
                                batch_size=self.batch_size,
                                mesh_template=mesh)
            first_attempt = [True]

            def attempt():
                self._elastic_begin()
                if not first_attempt[0]:
                    # a membership change (or any elastic re-entry)
                    # forces an immediate averaging round: no survivor
                    # carries unaveraged local-SGD divergence across an
                    # incarnation boundary (docs/elastic.md)
                    self._sync_force_average = True
                first_attempt[0] = False
                return self._plan_loop(self.elastic.current_mesh())

            return self._with_retry(attempt)
        return self._with_retry(lambda: self._plan_loop(mesh))

    def _plan_engine(self, mesh):
        """Compile the one step for this attempt's mesh.  With
        ``reuse_compiled_engine`` set (the online-training-slice path)
        the engine is cached per mesh identity so back-to-back
        ``optimize()`` calls dispatch straight into the already-jitted
        step instead of re-tracing."""
        key = None
        if self.reuse_compiled_engine and self.elastic is None:
            key = (tuple(mesh.devices.flatten().tolist()),
                   tuple(mesh.axis_names))
            if self._engine_cache is not None \
                    and self._engine_cache[0] == key:
                self._engine_cache_hit = True
                return self._engine_cache[1]
        self._engine_cache_hit = False
        n_seq = mesh.shape.get("seq", 1)
        engine = self._build_plan_engine(mesh, n_seq)
        if key is not None:
            self._engine_cache = (key, engine)
        return engine

    def _build_plan_engine(self, mesh, n_seq):
        from ..parallel.plan import compile_step_with_plan

        return compile_step_with_plan(
            self.model, self.criterion, self.optim_method, mesh,
            plan=self.sharding_plan,
            input_seq_dim=1 if n_seq > 1 else None,
            compute_dtype=self.compute_dtype, donate=True,
            guard=self.gradient_guard, with_gnorm=True,
            n_microbatch=self.pipeline_microbatch,
            fsdp_min_bytes=self.fsdp_min_bytes,
            sparse_density=self.sparse_density,
            sync_period=self.sync_period,
            sync_staleness=self.sync_staleness)

    def _registry(self):
        """The attached telemetry's registry, or the process's."""
        from ..telemetry.registry import default_registry

        return (self.telemetry.registry if self.telemetry is not None
                else default_registry())

    def _publish_plan_metrics(self, engine, params):
        """Addressable-param-bytes gauges: the FSDP acceptance
        measurement (per-device bytes ~ total/N under an FSDP plan)
        and a live view of what the plan actually placed where."""
        reg = self._registry()
        try:
            by_dev = engine.param_bytes_by_device(params)
            total = float(sum(
                int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                for a in jax.tree_util.tree_leaves(params)))
            if by_dev:
                reg.gauge(
                    "bigdl_plan_param_bytes_per_device",
                    "max addressable parameter bytes on one device "
                    "under the active sharding plan"
                ).set(float(max(by_dev.values())))
            reg.gauge(
                "bigdl_plan_param_bytes_total",
                "logical parameter bytes of the model"
            ).set(total)
            reg.gauge(
                "bigdl_plan_update_sharded_bytes",
                "parameter bytes whose optimizer update runs on one "
                "data shard (FSDP leaves)"
            ).set(float(engine.update_sharded_bytes))
        except Exception:  # accounting must never take down training
            log.debug("plan param-bytes accounting failed", exc_info=True)

    def _plan_loop(self, mesh) -> AbstractModule:
        """One entry of ``optimize()`` (one retry attempt), under the
        ``train.optimize`` span of the process tracer."""
        from ..telemetry.tracer import default_tracer

        tr = default_tracer()
        with tr.span("train.optimize", "other") as span:
            return self._plan_attempt(mesh, tr, span)

    def _plan_attempt(self, mesh, tr, optimize_span) -> AbstractModule:
        from ._sharding_utils import maskable, pad_batch, round_up
        from .optim_method import OptimMethod  # noqa: F401 (doc link)

        clock = tr.clock  # the one clock of every span site below
        self._tm_attempt_begin()
        model, optim = self.model, self.optim_method
        model.training()
        engine = self._plan_engine(mesh)
        optimize_span.set(engine_cache_hit=bool(
            getattr(self, "_engine_cache_hit", False)))
        # relaxed synchrony (parallel/plan.py "Synchrony"): restore
        # the exact per-replica stacks for bitwise resume — unless a
        # membership change forced an averaging round, in which case
        # every survivor re-seeds from the averaged checkpoint params
        sync_resume, self._sync_resume = self._sync_resume, None
        if self._sync_force_average:
            self._sync_force_average = False
            if engine.has_relaxed and sync_resume is not None:
                log.warning(
                    "relaxed synchrony: membership change — forcing an "
                    "averaging round; survivors re-seed their replica "
                    "stacks from the averaged checkpoint params")
                sync_resume = None
        params, slots, buffers = engine.init_state(
            sync_resume=sync_resume)
        sync_state = (engine.init_sync_state(sync_resume)
                      if engine.has_relaxed else None)
        sync_phases = None
        if engine.has_relaxed and engine.periodic_cadences:
            # step-phase counters, one per averaging cadence group —
            # checkpointed in optimMethod's state table so the
            # averaging schedule resumes exactly where it left off
            saved = self.optim_method.state.get("sync_phase")
            n_groups = len(engine.periodic_cadences)
            sync_phases = (list(saved)
                           if isinstance(saved, (list, tuple))
                           and len(saved) == n_groups
                           else [0] * n_groups)
        self._publish_plan_metrics(engine, params)
        pad_multiple = engine.pad_multiple
        n_seq = engine.n_seq
        multi_device = int(np.prod(mesh.devices.shape)) > 1

        state = optim.state
        state["epoch"] = state.get("epoch", 1)
        state["neval"] = state.get("neval", 1)
        state["epoch_finished"] = False
        epoch_size = _epoch_records(self.dataset)
        data_iter = self.dataset.data(train=True)
        # a total-state resume continues mid-epoch on the exact next
        # batch (the restored order makes the skipped prefix identical)
        records_this_epoch = self._consume_resume_cursor(data_iter,
                                                         epoch_size)
        wall_start = clock()

        profile_interval = int(get_property(
            "bigdl.metrics.profileInterval", 10))
        compute_ratio = None   # last measured compute/total split
        eval_cache = {}        # lazily built validation forward

        def seq_misfits(x):
            if n_seq <= 1:
                return []
            return [a.shape for a in jax.tree_util.tree_leaves(x)
                    if getattr(a, "ndim", 0) > 1
                    and a.shape[1] % n_seq != 0]

        def to_device(batch):
            # the feed's producer puts a whole batch straight at the
            # step's input sharding; one the driver has to pad or turn
            # away first goes to the default device, as on one chip
            if multi_device and batch.size() % pad_multiple == 0:
                x, y = batch.get_input(), batch.get_target()
                if not seq_misfits(x):
                    return engine.place_batch(x), engine.place_batch(y)
            return _device_batch(batch)

        # bounded prefetch-to-device infeed (dataset/prefetch.py):
        # batch N+1's host prep overlaps the compiled step on batch N;
        # data_time below is the REAL empty-buffer stall only
        feed = self._make_feed(data_iter, epoch_size, records_this_epoch,
                               transform=to_device)
        # first dispatch = XLA build (telemetry) — unless the engine
        # came out of the train_more cache, in which case there is no
        # build to attribute (goodput would book it as compile)
        first_step = not getattr(self, "_engine_cache_hit", False)
        # on that same cached re-entry (train_more slices) the first
        # feed.get() wait is the prefetch thread spinning up at the
        # slice boundary, not an empty-buffer stall — a real infeed
        # stall would keep showing on the following iterations
        warm_reentry = not first_step
        staged_steps = self._registry().counter(
            "bigdl_train_steps_staged_total",
            "train steps whose every input was ready before the "
            "previous step's loss arrived").labels()

        def stage_step():
            """Everything of a step that waits for no loss: its batch
            from the feed, padded, masked, checked and placed, as the
            arrays the compiled call takes, the call for them and the
            key the step will draw.  Run while the step before is on
            the device; what goes wrong here is kept and raised where
            that step would have begun."""
            nonlocal warm_reentry
            st = _StagedStep()
            try:
                with tr.span("train.stage", "other"):
                    with tr.span("train.data_wait", "data_wait") as sp:
                        item, st.stall_time = feed.get()
                        sp.set(hit=st.stall_time == 0.0)
                    if warm_reentry:
                        st.stall_time = 0.0
                        warm_reentry = False
                    batch, x, y = item
                    n_records = st.n_records = batch.size()
                    w = None
                    if n_records % pad_multiple != 0:
                        # trailing partial batch: pad whole records to the
                        # mesh multiple and train the real ones via the
                        # per-record weight mask — every record of an epoch
                        # trains exactly once at static shape, on EVERY
                        # mesh shape (reference DataSet.scala:255-288)
                        if not maskable(y, n_records):
                            raise ValueError(
                                "training got a trailing partial batch of "
                                f"{n_records} records but the targets are "
                                "not record-leading arrays for pad-and-"
                                "mask; size the dataset to a multiple of "
                                f"{pad_multiple}")
                        x, y, w = pad_batch(x, y, n_records,
                                            round_up(n_records, pad_multiple))
                        st.total_w = float(n_records)
                    bad = seq_misfits(x)
                    if bad:
                        raise ValueError(
                            f"sequence dim of inputs {bad} must be "
                            f"divisible by the mesh's seq-axis size "
                            f"{n_seq}; pad sequences to a multiple")
                    if multi_device:
                        # at the step's input sharding (h2d attributed
                        # separately from the data stall); what the feed
                        # has placed already comes back as it is
                        t_h2d0 = clock()
                        with tr.span("train.place_batch", "host_to_device"):
                            x = engine.place_batch(x)
                            y = engine.place_batch(y)
                            if w is not None:
                                w = engine.place_batch(w)
                        st.h2d_time = clock() - t_h2d0
                    st.call, st.x, st.y, st.w = engine.stage(x, y, w)
                    st.key = peek_jax_key()
            except Exception as e:  # noqa: BLE001 — raised by the loop
                st.error = e
            return st

        def report():
            """What only tells of a step — telemetry, health, metrics,
            the log line, the summary — with the step's own values.
            Owed from its loss until the next step is on the device,
            and paid before the loop returns or raises."""
            nonlocal owed, compute_ratio
            r, owed = owed, None
            if r is None:
                return
            with tr.span("train.report", "other", step=r["neval"]):
                st, at = r["staged"], {"neval": r["neval"]}
                if r["was_staged"]:
                    staged_steps.inc()
                if self.telemetry is not None and st.h2d_time > 0:
                    self.telemetry.on_host_to_device(st.h2d_time,
                                                     step=r["neval"])
                train_time, loss = r["train_time"], r["loss"]
                self._tm_step(at, train_time, st.stall_time, st.n_records,
                              compiled=r["compiled"],
                              phase_split=r["trace_split"],
                              skipped=r["skipped"], span=r["span"])
                self._health_step(at, loss, train_time)
                # metric-name contract (reference
                # DistriOptimizer.scala:146-151): profiled iterations
                # pin the compute/aggregate split from the trace; in
                # between, the last measured ratio attributes the fused
                # step's wall time
                if r["trace_split"] is not None:
                    c_s, agg_s = r["trace_split"]
                    compute_ratio = c_s / max(c_s + agg_s, 1e-12)
                    self.phase_source = "trace"
                    self.phase_split = r["trace_split"]
                if compute_ratio is not None:
                    self.metrics.add("computing time average",
                                     train_time * compute_ratio)
                    self.metrics.add("aggregate gradient time",
                                     train_time * (1.0 - compute_ratio))
                else:
                    self.metrics.add("computing time average", train_time)
                    self.metrics.add("aggregate gradient time", 0.0)
                infeed_time = st.stall_time + st.h2d_time
                self.metrics.add("get weights average", infeed_time)
                self.metrics.add("data fetch time", st.stall_time)
                rate = st.n_records / max(train_time + infeed_time, 1e-9)
                log.info(
                    "[Epoch %d %d/%d][Iteration %d][Wall Clock %.3fs] "
                    "Train %d in %.4f seconds. Throughput is %.1f "
                    "records/second. Loss is %.5f.",
                    r["epoch"], r["records"], epoch_size, r["neval"],
                    r["wall"], st.n_records, train_time + infeed_time,
                    rate, loss)
                if self.train_summary is not None:
                    self.train_summary.add_scalar("Loss", loss, r["neval"])
                    self.train_summary.add_scalar("Throughput", rate,
                                                  r["neval"])
                    if "LearningRate" in getattr(self.train_summary,
                                                 "triggers", {}):
                        self.train_summary.add_scalar(
                            "LearningRate", r["lr"], r["neval"])
                    if self.gradient_guard:
                        self.train_summary.add_scalar(
                            "SkippedSteps", float(r["skipped_steps"]),
                            r["neval"])

        # One order for every plan: loss n -> commit, triggers -> enqueue
        # n+1.  Between the loss and the enqueue stands only what can
        # stop training, reads or replaces the step's device state, or
        # changes an argument of the next step; step n+1's inputs are
        # staged while step n runs, and step n is reported once n+1 is
        # on the device.
        staged = None   # step n+1's inputs, made while step n runs
        owed = None     # step n's values, until report() has told them
        spent = None    # step n's donated state trees, until n+1 is enqueued
        try:
            running = not self.end_when(state)
            while running:
                with tr.span("train.iteration", "step",
                             step=state["neval"]) as it_span:
                    state["epoch_finished"] = False
                    self._elastic_step_start(state)
                    was_staged = staged is not None
                    st, staged = staged or stage_step(), None
                    if st.error is not None:
                        raise st.error
                    n_records, x, y = st.n_records, st.x, st.y
                    masked = st.w is not None
                    mask_kw = ({"w": st.w, "total_w": st.total_w}
                               if masked else {})

                    # profile past the compile iteration so timings are
                    # warm; single-device meshes skip (nothing to split)
                    profiled = (multi_device and profile_interval > 0
                                and state["neval"] > 1
                                and state["neval"] % profile_interval == 0
                                and not masked)

                    # relaxed synchrony: advance the step-phase counters
                    # and fire this iteration's averaging flags (host-side
                    # — the flags are traced args, so the program never
                    # recompiles; an elastic relax-before-evict verdict
                    # widens the effective period here)
                    sync_kw = {}
                    if engine.has_relaxed:
                        vals = [0] * engine.n_flags
                        if sync_phases is not None:
                            relax_f = (getattr(self.elastic,
                                               "sync_relax_factor",
                                               lambda: 1.0)()
                                       if self.elastic is not None else 1.0)
                            for gi, cad in enumerate(
                                    engine.periodic_cadences):
                                sync_phases[gi] += 1
                                eff = max(1, int(round(cad * relax_f)))
                                if sync_phases[gi] >= eff:
                                    vals[gi] = 1
                                    sync_phases[gi] = 0
                            state["sync_phase"] = list(sync_phases)
                        sync_kw = {"sync_flags": np.asarray(vals, np.int32),
                                   "sync_state": sync_state}

                    lr = optim.get_current_lr()
                    t0 = clock()
                    if first_step and not masked \
                            and self.telemetry is not None:
                        # XLA cost-model accounting for the exact program
                        # about to compile (inside the first step's timed
                        # window, ledgered as COMPILE; the constant key
                        # never consumes the checkpointed stream).  Wire
                        # bytes come from the PLAN now — tensor-parallel
                        # and FSDP traffic is counted per leaf, not assumed
                        # to be a data-parallel ring.
                        analyze_extra = ()
                        if engine.has_relaxed:
                            analyze_extra = (
                                jnp.zeros((engine.n_flags,), jnp.int32),
                                sync_state)
                        self._tm_analyze(
                            st.call, params, slots,
                            buffers, jnp.float32(lr), jax.random.PRNGKey(0),
                            x, y, *analyze_extra,
                            collective_bytes=engine.collective_bytes,
                            sparse_bytes_saved=engine.sparse_bytes_saved,
                            sync_bytes_saved=engine.sync_bytes_saved)

                    def dispatch():
                        # enqueue only: the step runs behind the return
                        with tr.span("train.dispatch",
                                     "compile" if first_step
                                     else "dispatch",
                                     compiled=first_step,
                                     staged=was_staged):
                            return engine.step(
                                params, slots, buffers, lr, x, y,
                                rng=next_jax_key(st.key), call=st.call,
                                **sync_kw, **mask_kw)

                    def while_it_runs():
                        # the device is busy with this step: tell of the
                        # one before, make the next one's inputs.  An
                        # epoch's last step stages nothing — the feed has
                        # met its budget, the shuffle waits for the loss
                        nonlocal staged, spent
                        spent = None
                        report()
                        if records_this_epoch + n_records < epoch_size:
                            staged = stage_step()

                    def fetch_loss(out):
                        # the device wait, and ONE fetch for all the
                        # driver reads of the step: the three copies are
                        # issued together, not each behind the wait for
                        # the one before
                        with tr.span("train.loss_fetch", "device_wait"):
                            loss_v, ok, gn = jax.device_get(
                                (out[0], out[4], out[5]))
                            return float(loss_v), bool(ok), float(gn)

                    trace_split = None
                    if profiled:
                        # phase split measured from the profiler trace of
                        # THIS step's execution: collective vs compute
                        # device time (reference Metrics.scala:103-121).
                        # The loss fetch (execution barrier) happens inside
                        # the trace so device events are captured.
                        from .profiling import trace_phase_split

                        step_out = []

                        def run_traced():
                            t_run = clock()
                            out = dispatch()
                            while_it_runs()
                            step_out.append((out, fetch_loss(out),
                                             clock() - t_run))
                        trace_split = trace_phase_split(run_traced)
                        out, fetched, train_time = step_out[0]
                    else:
                        out = self._elastic_dispatch(dispatch, state)
                        while_it_runs()
                        fetched = fetch_loss(out)
                        train_time = clock() - t0
                    loss, step_ok, gnorm = fetched
                    # commit and decide: the device has nothing queued
                    # until the next iteration's dispatch, so only what
                    # that dispatch depends on stands here
                    with tr.span("train.bookkeeping", "other"):
                        # the trees this step was given are donated: to
                        # let go of a thousand spent arrays takes
                        # milliseconds, so it waits for the next enqueue
                        spent = (params, slots, buffers)
                        _, params, slots, buffers = out[:4]
                        if engine.has_relaxed:
                            sync_state = out[6]
                        skipped = not step_ok
                        records_this_epoch += n_records
                        owed = {
                            "staged": st, "was_staged": was_staged,
                            "neval": state["neval"],
                            "epoch": state["epoch"],
                            "records": records_this_epoch, "loss": loss,
                            "train_time": train_time, "lr": lr,
                            "compiled": first_step, "skipped": skipped,
                            "trace_split": trace_split, "span": it_span,
                            "wall": clock() - wall_start,
                            "skipped_steps": self.skipped_steps + skipped}
                        first_step = False
                        self._tm_commit(train_time, it_span)
                        self._check_loss_anomaly(loss, skipped)
                        params = self._maybe_corrupt_params(state, params)
                        self._record_fingerprint(state, loss, gnorm,
                                                 (x, y), lambda: params,
                                                 skipped=skipped)
                        self._integrity_step(state, lambda: params)

                        state["records_this_epoch"] = records_this_epoch
                        state["loss"] = loss
                        state["neval"] += 1
                        optim.state = state

                        if records_this_epoch >= epoch_size:
                            state["epoch"] += 1
                            state["epoch_finished"] = True
                            records_this_epoch = 0
                            state["records_this_epoch"] = 0
                            # the producer met its epoch budget and is parked —
                            # the shuffle cannot race a fetch; reset re-arms
                            # the same producer thread on the fresh iterator
                            self.dataset.shuffle()
                            data_iter = self.dataset.data(train=True)
                            feed.reset(data_iter, epoch_size, 0)

                        # evaluate each trigger exactly once per iteration
                        # (stateful user triggers must not see a second call)
                        do_validate = self._should(self.validation_trigger, state)
                        do_checkpoint = self._should(self.checkpoint_trigger,
                                                     state)
                        if do_validate:
                            with tr.span("train.validation", "other"):
                                self._plan_validate(engine, state, params,
                                                    buffers, eval_cache)
                        if do_checkpoint or self._preempted():
                            with tr.span("train.checkpoint", "checkpoint"):
                                self._plan_checkpoint(engine, state, params,
                                                      slots, buffers,
                                                      sync_state)
                        if self._preempted():
                            self._drain_checkpoints()
                            log.warning("preemption requested — checkpointed at "
                                        "iteration %d; exiting resumable",
                                        state["neval"] - 1)
                            running = False
                    # the end trigger closes the iteration: it is handed
                    # the table with the step just done in it
                    running = running and not self.end_when(state)
                    if not running:
                        report()  # nothing follows to hide it behind
        finally:
            try:
                report()  # of the step an exception ended the loop at
            finally:
                feed.close()

        engine.sync_to_model(params, slots, buffers)
        model.evaluate()
        # drain-on-exit barrier: every triggered checkpoint is durable
        # (or its write error surfaces here, into the retry loop)
        self._drain_checkpoints()
        self._orbax_close()
        self._tm_finish(state)
        return model

    def _plan_checkpoint(self, engine, state, params, slots, buffers,
                         sync_state=None):
        if self.checkpoint_path is None:
            return
        if self.checkpoint_format == "orbax":
            # sharded async save straight from the device trees — no
            # host gather, no unpack (checkpoint_tree rejects relaxed-
            # synchrony state loudly: the replica stacks ride the
            # pickle trainState leg only)
            tree, kind = engine.checkpoint_tree(params, slots, buffers)
            self._orbax_save(state, tree, kind=kind)
            return
        if engine.has_relaxed:
            # snapshot the exact per-replica stacks + pending buffers
            # BEFORE the averaged sync_to_model write — the model leg
            # carries the replica mean, the trainState leg the truth
            self._sync_snapshot = engine.sync_snapshot(params, slots,
                                                       sync_state)
        else:
            self._sync_snapshot = None  # a swapped plan must not leak
        # host-gather for the whole-module pickle checkpoint
        # (model-sharded and FSDP leaves reassemble on fetch)
        engine.sync_to_model(params, slots, buffers)
        self._write_pickle_checkpoint(state)

    def _plan_validate(self, engine, state, params, buffers, cache):
        """On-mesh validation matched to the engine's layout: the
        pipeline eval schedule for packed params, the multi-axis eval
        forward when seq/model axes are live, and the shard_mapped
        data-axis eval (reference DistriValidator) otherwise — always
        with the device-resident params, never a host pull."""
        if self.validation_dataset is None or not self.validation_methods:
            return
        from .evaluator import evaluate_dataset

        # relaxed-synchrony replica stacks collapse to their mean for
        # validation (the local-SGD read-out; a no-op otherwise)
        params = engine.eval_params(params)
        mesh = engine.mesh
        if engine.kind == "packed":
            if cache.get("fwd") is None:
                from ..parallel.pipeline import make_pipeline_eval_forward

                pfwd = make_pipeline_eval_forward(
                    self.model, mesh, n_microbatch=engine.n_microbatch,
                    model_axis=engine.model_axis,
                    compute_dtype=self.compute_dtype)
                cache["fwd"] = lambda p, b, xx: pfwd(p, xx)
            results = evaluate_dataset(
                self.model, self.validation_dataset,
                self.validation_methods,
                batch_size=self.batch_size or 128, params=params,
                buffers=self.model.buffer_tree(), fwd=cache["fwd"],
                n_shard=engine.pad_multiple)
        elif engine.n_seq > 1 or engine.n_model > 1:
            if cache.get("fwd") is None:
                from ..parallel.spmd import make_eval_forward

                cache["fwd"] = make_eval_forward(
                    self.model, mesh,
                    input_seq_dim=1 if engine.n_seq > 1 else None,
                    compute_dtype=self.compute_dtype,
                    output_seq_dim=self.validation_output_seq_dim)
            n_seq = engine.n_seq
            if n_seq > 1:
                # cheap fast-fail probe on the first sample; ragged
                # LATER samples are caught by the except below
                probe = next(iter(
                    self.validation_dataset.data(train=False)), None)
                if probe is not None and not hasattr(probe, "size"):
                    arr = np.asarray(probe.feature)
                    if arr.ndim >= 1 and arr.shape[0] % n_seq != 0:
                        raise ValueError(
                            f"validation sequence length {arr.shape[0]} "
                            f"must be divisible by the mesh's seq-axis "
                            f"size {n_seq}; pad sequences to a multiple")
            try:
                results = evaluate_dataset(
                    self.model, self.validation_dataset,
                    self.validation_methods,
                    batch_size=self.batch_size or 128, params=params,
                    buffers=buffers, fwd=cache["fwd"],
                    n_shard=engine.n_data)
            except ValueError as e:
                if n_seq > 1 and "shard" in str(e).lower():
                    raise ValueError(
                        f"on-mesh validation failed to shard a batch "
                        f"over the seq axis (size {n_seq}) — every "
                        f"validation sequence length must be divisible "
                        f"by {n_seq}; pad sequences to a multiple "
                        f"(underlying error: {e})") from e
                raise
        else:
            # pure data mesh (FSDP params reshard transparently on
            # entry to the replicated-spec eval program)
            results = evaluate_dataset(
                self.model, self.validation_dataset,
                self.validation_methods,
                batch_size=self.batch_size or 128, mesh=mesh,
                params=params, buffers=buffers)
        self.model.training()
        self._report_validation(state, results)

    def optimize(self) -> AbstractModule:
        raise NotImplementedError


def _write_plain(path: str, data: bytes):
    """Plain local byte write (the orbax meta sidecar — its integrity
    story is the per-step shard manifest, not a crc sidecar)."""
    with open(path, "wb") as f:
        f.write(data)


def _yields_minibatch(dataset) -> bool:
    try:
        probe = next(iter(dataset.data(train=False)))
    except StopIteration:
        return False
    return isinstance(probe, MiniBatch)


def _epoch_records(dataset) -> int:
    """Records per epoch.  MiniBatch-DIRECT datasets (an in-memory list
    of prebuilt batches) count items, not records, in ``size()`` — sum
    their sizes, which is free because the batches already exist.  Every
    other dataset (including Sample streams wrapped by SampleToMiniBatch,
    whose ``size()`` is already the record count) keeps ``size()``: a
    counting pass through a transformed pipeline would read and decode
    the whole dataset before the first step."""
    from ..dataset.dataset import TransformedDataSet

    base = dataset
    while isinstance(base, TransformedDataSet):
        base = base.base
    items = getattr(base, "_data", None)
    if items and isinstance(items[0], MiniBatch):
        return sum(b.size() for b in items)
    return dataset.size()


def _resume_slots(optim, fresh_slots):
    """Reuse checkpointed optimizer slots when their pytree structure and
    leaf shapes match a fresh init; otherwise start clean."""
    saved = optim._slots
    if saved is None:
        return fresh_slots
    try:
        ok = all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda a, b: jnp.shape(a) == jnp.shape(b), saved, fresh_slots)))
    except ValueError:
        ok = False
    return saved if ok else fresh_slots


def _cast_floats(tree, dtype):
    """Cast every floating leaf of a pytree to ``dtype`` (ints pass)."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(jnp.result_type(a), jnp.floating) else a, tree)


def _restore_dtypes(tree, template):
    """Cast ``tree``'s leaves back to the dtypes of ``template`` — keeps
    BatchNorm running stats f32 under a bf16 compute pass."""
    return jax.tree_util.tree_map(
        lambda a, t: jnp.asarray(a, jnp.result_type(t)), tree, template)


class _StagedStep:
    """One step's inputs, made ahead of its enqueue (``stage_step`` of
    the plan driver): the placed arrays, the compiled call for them,
    the peeked key — or the error that making them met."""

    __slots__ = ("error", "n_records", "x", "y", "w", "total_w", "call",
                 "key", "stall_time", "h2d_time")

    def __init__(self):
        self.error = self.w = self.total_w = None
        self.stall_time = self.h2d_time = 0.0


def _device_batch(batch: MiniBatch):
    x = batch.get_input()
    y = batch.get_target()
    # inputs/targets are pytrees: arrays, tuples, or Table activities
    conv = lambda v: jax.tree_util.tree_map(jnp.asarray, v)
    return conv(x), conv(y)


class LocalOptimizer(Optimizer):
    """Single-host training driver (reference optim/LocalOptimizer.scala:41):
    the whole iteration is one jitted step on one chip (or all local chips
    via vectorized batch — the reference's per-core model clones collapse
    into the batch dimension, SURVEY §2.2 P2).

    Since ISSUE 8 this is the unified plan driver over a single-device
    mesh — the same ``compile_step_with_plan`` program every other mesh
    shape runs, with the size-1 data axis compiled away by XLA."""

    def optimize(self) -> AbstractModule:
        self._warn_drop_knobs_if_inert()
        try:
            with self._preemption_scope():
                from jax.sharding import Mesh

                mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
                return self._plan_optimize(mesh)
        finally:
            # commit any in-flight async save on abnormal exits —
            # background writer first, then the orbax checkpointer
            self._shutdown_async_writer()
            self._orbax_close()
