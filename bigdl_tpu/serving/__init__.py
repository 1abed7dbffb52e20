"""Hardened online serving subsystem.

The offline surface (``optim.Predictor`` walking a dataset,
``models.generate`` as a library call) serves nobody under live
traffic: the first bad request, stuck device, or queue pile-up takes
the whole process down.  This package is the serving-side counterpart
of :mod:`bigdl_tpu.resilience` — the same discipline (typed failure
classification, preemption hooks, deterministic fault injection,
verified checkpoints) applied to an in-process request path:

* :mod:`.server`  — :class:`InferenceServer`: bounded request queue +
  a worker thread that coalesces requests into **static bucket
  shapes** (continuous micro-batching through the same cached compiled
  eval forward the Predictor uses, and the KV-cache decode generator
  for token generation), so variable traffic never triggers a
  recompile.  SIGTERM (via :mod:`bigdl_tpu.resilience.preemption`)
  stops admission, finishes everything already admitted, and exits
  cleanly.
* :mod:`.status`  — the status taxonomy: every request resolves to a
  :class:`ServeResult` (``OK`` / ``DEADLINE_EXCEEDED`` / ``OVERLOADED``
  / ``UNAVAILABLE`` / ``INTERNAL_ERROR`` / ``CANCELLED``) — never a
  silent drop, never an unbounded wait.
* :mod:`.breaker` — :class:`CircuitBreaker` around the compiled step:
  consecutive failures (classified retryable vs fatal by
  :class:`bigdl_tpu.resilience.retry.RetryPolicy`) trip it open; while
  open the server rejects fast instead of crashing; a half-open probe
  admits one batch to test recovery.
* :mod:`.batcher` — :class:`MicroBatcher`: bucket ladder + tail
  padding (``optim._sharding_utils.pad_batch``) + compile accounting.
* :mod:`.swap`    — hot model swap: new params load through the
  crc32c-verified checkpoint path, pass a canary batch, and swap
  atomically between batches — rolling back if the canary fails.
* :mod:`.metrics` — per-request counters + latency quantiles
  (p50/p99) backed by the unified telemetry registry
  (:mod:`bigdl_tpu.telemetry` — Prometheus text export, mergeable
  histograms), exported through ``visualization.summary``.
* :mod:`.fleet` / :mod:`.router` — the replica fleet layer:
  :class:`ServingFleet` runs N replicas whose membership rides the
  elastic KV transport (heartbeats + health snapshots + incarnation
  numbers, exactly like training gangs) and rolls verified deploys
  one replica at a time with fleet-wide rollback;
  :class:`FleetRouter` dispatches least-loaded with deadline-budget
  failover retries, optional p99-derived hedging, and per-replica
  circuit breakers.

* :mod:`.kvpool` / :mod:`.pools` / :mod:`.autoscale` — the serving
  scale-out control plane: :class:`KVPagePool` pages the decode
  KV-cache (requests hold pages for the positions they actually fill,
  pool exhaustion sheds typed OVERLOADED), replicas advertise a
  prefill/decode/both **role** so the router can disaggregate the two
  phases into separately-sized pools (KV pages travel between them as
  crc-verified handoff blobs), and :class:`Autoscaler` scales each
  pool independently on the router's aggregated telemetry (p99, shed
  rate, queue depth, KV occupancy) with hysteresis, cooldowns, and
  drain-before-retire.  Every ``InferenceServer.start`` turns on the
  persistent compile cache (:mod:`bigdl_tpu.utils.compile_cache`) so
  cold autoscaled replicas skip per-bucket compiles.

Deterministic serving fault injectors (fail-next-N steps, injected
step latency, poisoned params, replica kill/partition) live with the
training injectors in :mod:`bigdl_tpu.resilience.faults`.
"""
from .autoscale import AutoscalePolicy, Autoscaler
from .batcher import MicroBatcher
from .breaker import CircuitBreaker
from .fleet import FleetQuorumError, ReplicaAgent, ServingFleet
from .health import FleetHealthMonitor, ReplicaHealthPolicy
from .kvpool import KVPagePool, PageLease, PoolExhausted
from .metrics import ServingMetrics
from .pools import HandoffCorrupt
from .request_trace import (ReplicaTraceSink, RequestTracer,
                            trace_attribution, trace_coverage)
from .router import FleetRouter
from .server import InferenceServer
from .sparse_fetch import FetchResult, SparseFetchClient
from .status import ServeFuture, ServeResult, Status
from .swap import load_verified_params

__all__ = [
    "AutoscalePolicy", "Autoscaler", "CircuitBreaker",
    "FleetHealthMonitor", "FleetQuorumError", "FleetRouter",
    "HandoffCorrupt",
    "FetchResult",
    "InferenceServer", "KVPagePool", "MicroBatcher", "PageLease",
    "PoolExhausted", "ReplicaAgent", "ReplicaHealthPolicy",
    "ReplicaTraceSink",
    "RequestTracer", "ServeFuture", "ServeResult",
    "ServingFleet", "ServingMetrics", "SparseFetchClient", "Status",
    "load_verified_params",
    "trace_attribution", "trace_coverage",
]
