"""In-process inference server — the hardened online request path.

One worker thread owns the device: it pulls admitted requests off a
bounded queue, coalesces them into static bucket shapes
(:class:`.batcher.MicroBatcher`), and dispatches ONE compiled program
per batch — classification through the same cached compiled eval
forward the Predictor uses (``optim.evaluator._cached_eval_fwd``,
shard_mapped when a mesh is given), token generation through the
KV-cache decode generator (``models.generate.cached_generate``).
Requests never touch the device individually and the device never
sees a shape it hasn't seen before — variable traffic changes *which
bucket* runs, not *what compiles*.

Request lifecycle (every path ends in a typed
:class:`~.status.ServeResult`; nothing hangs, nothing drops silently)::

    submit ──► admission ──► queue ──► batch ──► compiled step ──► OK
                  │            │         │            │
                  │ full       │ expired │ breaker    │ step raised
                  ▼            ▼         ▼ open       ▼
              OVERLOADED   DEADLINE_  UNAVAILABLE  INTERNAL_ERROR
              (shed)       EXCEEDED   (reject fast) (+ breaker count)

Failures at the step are classified retryable-vs-fatal by the
:class:`resilience.retry.RetryPolicy`; consecutive failures trip the
:class:`.breaker.CircuitBreaker` open (fatal ones immediately), a
half-open probe admits one request to test recovery, and while open
the server degrades to fast UNAVAILABLE rejections instead of
crashing.  SIGTERM (or ``resilience.preemption.request_preemption()``)
stops admission, finishes everything already admitted, and exits the
worker cleanly; a hard ``stop()`` resolves still-queued requests as
CANCELLED.  New params install atomically between batches via
:meth:`InferenceServer.swap_params` (crc32c-verified load + canary
batch + rollback — see :mod:`.swap`).
"""
from __future__ import annotations

import collections
import gc
import itertools
import logging
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..resilience import faults as _faults
from ..resilience.guards import tree_finite
from ..resilience.preemption import PreemptionHandler
from ..resilience.retry import RetryPolicy
from ..telemetry.tracer import default_tracer, profiler_session_live
from .batcher import MicroBatcher
from .breaker import OPEN, PROBE, REJECT, CircuitBreaker
from .metrics import ServingMetrics
from .status import Request, ServeFuture, ServeResult, Status
from .swap import SwapRejected, load_verified_params

log = logging.getLogger("bigdl_tpu")


class _BoundedQueue:
    """Deque + condition: reject-fast ``try_put``, front requeue for
    the breaker's half-open probe leftovers, and atomic drain."""

    def __init__(self, maxsize: int):
        self.maxsize = int(maxsize)
        self._d: collections.deque = collections.deque()
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)

    def try_put(self, item) -> bool:
        with self._lock:
            if len(self._d) >= self.maxsize:
                return False
            self._d.append(item)
            self._not_empty.notify()
            return True

    def put_front(self, items) -> None:
        """Requeue in original order ahead of newer arrivals (bound
        intentionally not enforced — these were already admitted)."""
        with self._lock:
            for item in reversed(list(items)):
                self._d.appendleft(item)
            self._not_empty.notify()

    def get(self, timeout: float):
        with self._lock:
            if not self._d:
                self._not_empty.wait(timeout)
            return self._d.popleft() if self._d else None

    def get_nowait(self):
        with self._lock:
            return self._d.popleft() if self._d else None

    def drain_all(self) -> list:
        with self._lock:
            items = list(self._d)
            self._d.clear()
            return items


def moe_counters(counts, tokens_per_layer: int) -> dict:
    """The ``serve.fetch`` arguments of a generate call that routed over
    experts, from its ``[layers, held]`` assignment counts — a row for
    each layer that HAS experts (a leading dense layer carries none):
    ``moe_assignments`` (total to the held experts), ``moe_tokens``
    (tokens routed over, counted once a layer) and
    ``moe_load_max_over_mean`` (a layer's busiest held expert over its
    mean one, the worst layer)."""
    counts = np.asarray(counts, np.int64)
    mean = np.maximum(counts.mean(axis=1), 1e-9)
    return {"moe_assignments": int(counts.sum()),
            "moe_tokens": int(tokens_per_layer * counts.shape[0]),
            "moe_load_max_over_mean": float((counts.max(axis=1)
                                             / mean).max())}


class InferenceServer:
    """See the module docstring for the full request lifecycle.

    Parameters
    ----------
    model : the module to serve.  Classification rides its cached
        compiled eval forward; ``submit_generate`` additionally
        requires a ``TransformerLM``.
    mesh : optional Mesh — the forward shard_maps over its data axis
        (bucket sizes are rounded to the axis size).
    max_batch : largest micro-batch (top of the bucket ladder).
    max_queue : admission bound; a full queue sheds with OVERLOADED.
    batch_window_s : how long the worker waits to coalesce more
        requests after the first one arrives.
    default_deadline_s : per-request deadline when ``submit`` gives
        none (``None`` = no deadline).
    breaker / policy / metrics : injectable for tests; defaults are a
        3-failure threshold breaker and ``RetryPolicy.from_properties``
        classification.
    generate_dtype : compute dtype for the generation path (e.g.
        ``jnp.bfloat16``); ``None`` serves in the params' dtype.
    """

    def __init__(self, model, mesh=None, max_batch: int = 32,
                 max_queue: int = 256, batch_window_s: float = 0.002,
                 default_deadline_s: Optional[float] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 policy: Optional[RetryPolicy] = None,
                 metrics: Optional[ServingMetrics] = None,
                 generate_dtype=None, name: Optional[str] = None,
                 kv_pool=None, role: str = "both",
                 kv_page_window: Optional[int] = None,
                 kv_page_globals: int = 1, trace_sink=None,
                 model_name: Optional[str] = None,
                 model_version: str = "v1"):
        from ..optim._sharding_utils import data_mesh
        from .pools import ROLES

        #: replica identity — the fleet layer names its servers so the
        #: per-replica fault injectors (``delay_replica`` et al.) can
        #: target one member; anonymous servers match only unscoped
        #: faults
        self.name = name
        self.model = model
        #: multi-tenant identity: which registered model (and version)
        #: this replica serves — advertised in the health snapshot so
        #: the FleetRouter's ModelRegistry routing dispatches on it.
        #: None = single-model fleet (pre-registry behavior unchanged)
        self.model_name = model_name
        self.model_version = str(model_version)
        #: paged KV arena (``serving.kvpool.KVPagePool``): when set,
        #: generation serves through the paged decode path — each
        #: request holds pages for the positions it actually fills
        #: instead of a static cache of prompt + max_new positions,
        #: and pool exhaustion sheds typed OVERLOADED
        self.kv_pool = kv_pool
        #: page-granular block mask for long paged decodes (the BLaST
        #: sparsity story on the serving path): attend only the first
        #: ``kv_page_globals`` anchor pages + the last
        #: ``kv_page_window`` pages; None = dense over the page table
        self.kv_page_window = kv_page_window
        self.kv_page_globals = int(kv_page_globals)
        if role not in ROLES:
            raise ValueError(f"role {role!r} not in {ROLES}")
        #: which generation phase(s) this replica serves — advertised
        #: in the health snapshot so the FleetRouter can route prefill
        #: and decode to separately-sized pools
        self.role = role
        #: distributed request tracing (serving.request_trace
        #: .ReplicaTraceSink): when set, traced requests' queue wait,
        #: batch formation, compiled-step execution, KV-page gathers
        #: and swap/canary windows record as children of the request's
        #: remote span and publish as trace fragments over the fleet
        #: KV transport.  None = nothing is published.
        self.trace_sink = trace_sink
        #: the process tracer: the worker's ``serve.*`` spans and every
        #: request's queue / batch-wait / execute records land in its
        #: ring, sink or no sink.  Its clock is the server's one clock
        #: (submission stamps, deadlines, spans).
        self._tracer = default_tracer()
        self._clock = self._tracer.clock
        self._request_ids = itertools.count(1)
        self._batch_ids = itertools.count(1)
        self._idle_span = None  # the worker's open ``serve.idle``
        #: static batches whose per-request ring records are still owed
        #: (see ``_flush_owed``)
        self._owed: list = []
        self._idle_since = 0.0
        self._idle_in_session = False
        if role != "both" and kv_pool is None:
            raise ValueError(
                f"role {role!r} requires a kv_pool (the prefill/"
                f"decode split moves KV pages between pools)")
        if kv_pool is not None and model_name is not None \
                and kv_pool.default_owner is None:
            # decoder-internal page allocs charge this model's tenant
            kv_pool.default_owner = model_name
        self.mesh = data_mesh(mesh)
        self._n_dev = self.mesh.shape["data"] if self.mesh is not None \
            else 1
        self.batcher = MicroBatcher(max_batch, multiple=self._n_dev)
        self.metrics = metrics or ServingMetrics()
        self.breaker = breaker or CircuitBreaker()
        self.policy = policy or RetryPolicy.from_properties(
            prefix="bigdl.serving")
        self.generate_dtype = generate_dtype
        #: (bucket, prompt length, max_new) -> cache_footprint of that
        #: generate program (span args)
        self._gen_footprints = {}
        #: threads that compile a generate ladder's programs beside
        #: each other (``_compile_ladder``); made with the first one
        self._compile_pool: Optional[ThreadPoolExecutor] = None
        self._queue = _BoundedQueue(max_queue)
        self._batch_window_s = float(batch_window_s)
        self._default_deadline_s = default_deadline_s
        self._poll_s = 0.02

        self._model_lock = threading.Lock()
        self._params = model.param_tree()
        self._buffers = model.buffer_tree()
        self._canary_x = None  # last good classify batch (padded)

        self._feature_shape = None  # pinned by the first classify submit
        self._worker: Optional[threading.Thread] = None
        self._started = False
        self._draining = False
        self._hard_stop = False
        self._drained = threading.Event()
        self._preemption: Optional[PreemptionHandler] = None
        self._fwd = None
        # classify buckets whose compiled-forward cost was already
        # analyzed (one XLA cost-model lowering per bucket, ever)
        self._costed_buckets: set = set()

    # ------------------------------------------------------------ lifecycle
    def start(self, install_signal_handler: bool = False
              ) -> "InferenceServer":
        """Compile-cache the eval forward and start the worker.
        ``install_signal_handler=True`` additionally routes SIGTERM/
        SIGINT to a graceful drain (main thread only; off the main
        thread the process-wide ``request_preemption()`` flag still
        drains — PreemptionHandler's degrade contract)."""
        if self._started:
            raise RuntimeError("server already started")
        from ..optim.evaluator import _cached_eval_fwd
        from ..utils.compile_cache import ensure_compile_cache

        # a cold autoscaled replica loads per-bucket executables from
        # the persistent compile cache instead of recompiling them
        ensure_compile_cache()
        self.model.evaluate()
        self._fwd = _cached_eval_fwd(self.model, self.mesh)
        # on_request flips readiness the instant the signal lands (the
        # worker would only notice at its next batch boundary)
        signals = None if install_signal_handler else ()
        self._preemption = PreemptionHandler(
            **({} if signals is None else {"signals": signals}),
            on_request=self._note_drain)
        self._preemption.__enter__()
        self._started = True
        self._draining = False
        self._hard_stop = False
        self._drained.clear()
        self._settle_heap()
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="bigdl-serving-worker")
        self._worker.start()
        return self

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown: stop admission, finish everything already
        admitted, then stop the worker.  Returns True when the worker
        exited within ``timeout``."""
        self._draining = True
        done = self._drained.wait(timeout) if self._worker else True
        if self._worker is not None:
            self._worker.join(timeout)
            done = done and not self._worker.is_alive()
        if self._preemption is not None:
            self._preemption.__exit__(None, None, None)
            self._preemption = None
        if self._compile_pool is not None:
            # what has not begun is dropped; a later call of that shape
            # compiles by itself
            self._compile_pool.shutdown(wait=False, cancel_futures=True)
            self._compile_pool = None
        gc.unfreeze()
        self._started = False
        return done

    @staticmethod
    def _settle_heap():
        """What is alive now is here to stay while the server runs —
        jax, the model, the programs compiled so far: 600 000 tracked
        objects on a serving process — so it is moved out of the
        collector's sight (``gc.freeze``; ``drain`` gives it back).  A
        full collection then walks what the serving loop made since, a
        few milliseconds, where it took 0.22 s with the GIL held: the
        worker needs the GIL at every batch boundary, and a collection
        that fell on one left the device idle that long (once a window
        of the benchmark's closed loop, PERF.md section 6 "PR 42").
        Called at ``start`` and after every batch that compiled."""
        gc.freeze()

    def stop(self, timeout: Optional[float] = None) -> bool:
        """Hard shutdown: still-queued requests resolve CANCELLED (the
        in-flight batch, if any, completes first — the device step is
        not interruptible)."""
        self._hard_stop = True
        return self.drain(timeout)

    # ------------------------------------------------------------ health
    def healthy(self) -> bool:
        """Liveness: the worker thread is running."""
        return bool(self._started and self._worker
                    and self._worker.is_alive())

    def ready(self) -> bool:
        """Readiness: accepting requests with headroom — started, not
        draining, breaker not open, queue below its bound."""
        return (self.healthy() and not self._draining
                and not self._should_drain()
                and self.breaker.state != OPEN
                and len(self._queue) < self._queue.maxsize)

    def health(self) -> dict:
        out = {
            "healthy": self.healthy(),
            "ready": self.ready(),
            "draining": bool(self._draining or self._should_drain()),
            "queue_depth": len(self._queue),
            "breaker": self.breaker.snapshot(),
            "role": self.role,
        }
        if self.model_name is not None:
            out["model"] = self.model_name
            out["model_version"] = self.model_version
        if self.kv_pool is not None:
            out["kv"] = self.kv_pool.stats()
        return out

    def compile_stats(self) -> dict:
        """Compile accounting for the static-shape contract: the jit
        cache of the shared eval forward may hold at most one entry per
        (bucket, feature-shape) ever dispatched."""
        cache_size = None
        if self._fwd is not None and hasattr(self._fwd, "_cache_size"):
            cache_size = int(self._fwd._cache_size())
        return {
            "jit_cache_size": cache_size,
            "buckets_dispatched":
                sorted(self.batcher.buckets_dispatched),
        }

    # ------------------------------------------------------------ admission
    def _admit(self, req: Request) -> ServeFuture:
        now = self._clock()
        req.request_id = next(self._request_ids)
        if not self._started or self._draining or self._should_drain():
            self._resolve(req, ServeResult(
                Status.UNAVAILABLE,
                error="server draining" if self._started
                else "server not started"))
            return req.future
        if req.expired(now):
            self._resolve(req, ServeResult(
                Status.DEADLINE_EXCEEDED, error="expired on arrival"))
            return req.future
        self.metrics.record_depth(len(self._queue))
        if not self._queue.try_put(req):
            # load shedding: reject fast, count it, never queue forever
            self._resolve(req, ServeResult(
                Status.OVERLOADED,
                error=f"queue full ({self._queue.maxsize})"))
        return req.future

    def _deadline(self, deadline_s: Optional[float],
                  now: float) -> Optional[float]:
        if deadline_s is None:
            deadline_s = self._default_deadline_s
        return None if deadline_s is None else now + float(deadline_s)

    def _fast_fail_expired(self, deadline: Optional[float],
                           now: float) -> Optional[ServeFuture]:
        """A request whose remaining budget is already <= 0 resolves
        DEADLINE_EXCEEDED right here — before admission, before the
        queue, before metrics see a depth sample.  The fleet router
        retries with the *remaining* deadline budget, so a dead budget
        arriving here is the common case under failover, and queueing
        it would waste a batch slot on an answer nobody is waiting
        for."""
        if deadline is None or deadline > now:
            return None
        fut = ServeFuture()
        result = ServeResult(Status.DEADLINE_EXCEEDED,
                             error="deadline budget exhausted before "
                                   "admission")
        self.metrics.record(result.status, 0.0, 0.0)
        fut._resolve(result)
        return fut

    @staticmethod
    def _parse_trace(trace):
        """Wire dict (or TraceContext) → TraceContext; malformed
        contexts degrade to untraced, never fail the request."""
        if trace is None:
            return None
        from ..telemetry.trace_context import TraceContext

        return TraceContext.from_wire(trace)

    def _trace(self, req: Request, name: str, category: str,
               start: float, duration: float, **args):
        """Record one request-phase span: into the process tracer's
        ring always (retroactive — the interval is only known once it
        is over), and into the fleet sink for a request that carries a
        trace context."""
        self._tracer.record(name, category, start, duration,
                            request_id=req.request_id, **args)
        if self.trace_sink is not None and req.trace is not None:
            self.trace_sink.record(req.trace, name, category, start,
                                   duration, **args)

    def submit(self, feature,
               deadline_s: Optional[float] = None,
               trace=None) -> ServeFuture:
        """One classification/regression request: ``feature`` is a
        single record (no batch dim); the result's ``output`` is the
        model's output row for it."""
        feature = np.asarray(feature)
        # shape-check at admission: one malformed request must fail ITS
        # caller synchronously, not poison whole batches (and trip the
        # breaker) once coalesced
        if self._feature_shape is None:
            self._feature_shape = feature.shape
        elif feature.shape != self._feature_shape:
            raise ValueError(
                f"feature shape {feature.shape} does not match this "
                f"server's pinned shape {self._feature_shape}")
        now = self._clock()
        deadline = self._deadline(deadline_s, now)
        fast = self._fast_fail_expired(deadline, now)
        if fast is not None:
            return fast
        return self._admit(Request(
            kind="classify", payload=feature,
            future=ServeFuture(), submitted_at=now, deadline=deadline,
            trace=self._parse_trace(trace)))

    def submit_generate(self, prompt_ids, max_new: int,
                        eos_id: Optional[int] = None,
                        pad_id: Optional[int] = None,
                        deadline_s: Optional[float] = None,
                        trace=None) -> ServeFuture:
        """One greedy-decode generation request; the result's
        ``output`` is the generated id row (``max_new`` tokens,
        eos-then-pad per ``models.generate``).  Requests are micro-
        batched with others sharing (prompt_len, max_new, eos, pad) —
        the compiled decode program's static signature."""
        prompt = np.asarray(prompt_ids, np.int32)
        if prompt.ndim != 1:
            raise ValueError(f"prompt_ids must be 1-D, got shape "
                             f"{prompt.shape}")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        now = self._clock()
        deadline = self._deadline(deadline_s, now)
        fast = self._fast_fail_expired(deadline, now)
        if fast is not None:
            return fast
        return self._admit(Request(
            kind="generate", payload=prompt, future=ServeFuture(),
            submitted_at=now, deadline=deadline,
            opts=(int(max_new), eos_id, pad_id),
            trace=self._parse_trace(trace)))

    def _require_pool(self, what: str):
        if self.kv_pool is None:
            raise RuntimeError(
                f"{what} requires a kv_pool (paged serving); this "
                f"server has none")

    def submit_prefill(self, prompt_ids,
                       deadline_s: Optional[float] = None,
                       trace=None) -> ServeFuture:
        """Prefill-only dispatch for the disaggregated path: run the
        prompt pass, produce the first token, and return a crc-sealed
        KV handoff blob (``result.output``) a decode-pool replica can
        continue from.  The prefill replica's pages are released as
        soon as the blob is exported — prefill holds pages only for
        the duration of the prompt pass."""
        self._require_pool("submit_prefill")
        prompt = np.asarray(prompt_ids, np.int32)
        if prompt.ndim != 1:
            raise ValueError(f"prompt_ids must be 1-D, got shape "
                             f"{prompt.shape}")
        now = self._clock()
        deadline = self._deadline(deadline_s, now)
        fast = self._fast_fail_expired(deadline, now)
        if fast is not None:
            return fast
        return self._admit(Request(
            kind="prefill", payload=prompt, future=ServeFuture(),
            submitted_at=now, deadline=deadline,
            trace=self._parse_trace(trace)))

    def submit_decode(self, handoff: bytes, max_new: int,
                      eos_id: Optional[int] = None,
                      pad_id: Optional[int] = None,
                      deadline_s: Optional[float] = None,
                      trace=None) -> ServeFuture:
        """Decode-only dispatch for the disaggregated path: verify
        ``handoff`` (crc32c + geometry), import its pages into this
        replica's pool, and stream the remaining ``max_new - 1``
        tokens (the first one was produced by prefill and rides the
        handoff).  The result's ``output`` holds those remaining
        tokens; a corrupt blob resolves INTERNAL_ERROR, a full pool
        sheds OVERLOADED."""
        self._require_pool("submit_decode")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        now = self._clock()
        deadline = self._deadline(deadline_s, now)
        fast = self._fast_fail_expired(deadline, now)
        if fast is not None:
            return fast
        ctx = self._parse_trace(trace)
        if ctx is None:
            # belt-and-braces: the context also rides the sealed blob
            # itself (handoff extras), so a decode dispatched outside
            # the router still joins its trace
            from .pools import peek_handoff_trace

            ctx = self._parse_trace(peek_handoff_trace(handoff))
        return self._admit(Request(
            kind="decode", payload=handoff, future=ServeFuture(),
            submitted_at=now, deadline=deadline,
            opts=(int(max_new), eos_id, pad_id), trace=ctx))

    # ------------------------------------------------------------ hot swap
    def swap_params(self, params: Any = None, path: Optional[str] = None,
                    buffers: Any = None,
                    outcome: str = "installed",
                    version: Optional[str] = None) -> bool:
        """Install new params atomically between batches.

        ``path`` loads through the crc32c-verified checkpoint path
        (:func:`.swap.load_verified_params`); corrupt files quarantine
        and the swap is refused.  Candidates then face a canary batch
        on the live compiled forward (the last good batch's input; a
        params-finiteness check before any traffic has flowed) — a
        canary that raises or emits non-finite outputs raises
        :class:`SwapRejected` and the server keeps serving the prior
        params.  Returns True on install.

        ``outcome`` names the success leg of the swap counter —
        ``"installed"`` for a deploy, ``"rolled_back"`` when a fleet
        rollback re-installs captured prior params (the rollback rides
        this exact verified canary path; only its accounting differs).
        """
        if (params is None) == (path is None):
            raise ValueError("pass exactly one of params/path")
        t_swap = self._clock()

        def note_swap(outcome: str):
            # traced requests overlapping this window see it as a
            # swap_window span in their stitched timeline
            if self.trace_sink is not None:
                self.trace_sink.record_swap_window(
                    t_swap, self._clock() - t_swap, outcome)

        try:
            if path is not None:
                params = load_verified_params(path)
            with self._model_lock:
                canary = self._canary_x
                bufs = buffers if buffers is not None else self._buffers
            # the canary rides the same injection point as live batches
            # (scoped by replica name), so a fleet test can fail ONE
            # replica's canary deterministically mid-rolling-deploy
            _faults.check_serving_fault(self.name)
            if canary is not None and self._fwd is not None:
                out = self._fwd(params, bufs, canary)
                if not bool(tree_finite(out)):
                    raise SwapRejected(
                        "canary batch produced non-finite outputs")
            elif not bool(tree_finite(params)):
                raise SwapRejected("candidate params are non-finite")
        except SwapRejected:
            self.metrics.record_swap(installed=False)
            note_swap("rejected")
            raise
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            self.metrics.record_swap(installed=False)
            note_swap("rejected")
            raise SwapRejected(f"canary batch failed "
                               f"({type(e).__name__}: {e})")
        with self._model_lock:
            self._params = params
            if buffers is not None:
                self._buffers = buffers
        if version is not None:
            # the advertised (model, version) pair tracks the install —
            # a rollback passes the prior version back in
            self.model_version = str(version)
        self.metrics.record_swap(outcome=outcome)
        note_swap(outcome)
        log.info("serving params hot-swapped%s%s",
                 f" from {path}" if path else "",
                 " (rollback)" if outcome == "rolled_back" else "")
        return True

    def current_params(self):
        """The (params, buffers) pair currently serving — what a fleet
        rollback re-installs on the already-swapped replicas when a
        later replica rejects the deploy."""
        with self._model_lock:
            return self._params, self._buffers

    # ------------------------------------------------------------ worker
    def _note_drain(self):
        self._draining = True

    def _should_drain(self) -> bool:
        return self._preemption is not None \
            and self._preemption.should_stop

    def _tenant_of(self, req: Request) -> Optional[str]:
        """The tenant a request's phase/latency samples attribute to:
        the trace's tenant when the router stamped one, else this
        replica's model (one model ≈ one tenant), else None (untagged
        single-model fleets pay no tenant series)."""
        tenant = getattr(req.trace, "tenant", None) \
            if req.trace is not None else None
        return tenant if tenant is not None else self.model_name

    def _resolve(self, req: Request, result: ServeResult):
        now = self._clock()
        result.latency_s = now - req.submitted_at
        self.metrics.record(result.status, result.latency_s,
                            result.queued_s,
                            tenant=self._tenant_of(req))
        if req.trace is not None:
            result.trace_id = req.trace.trace_id
            if result.status is not Status.OK:
                # typed failure span: the stitched trace shows WHAT
                # failed on WHICH replica, not just a missing interval
                self._trace(req, f"fail:{req.kind}", "error",
                            req.submitted_at, result.latency_s,
                            status=result.status.value,
                            error=(result.error or "")[:200])
            if self.trace_sink is not None:
                self.trace_sink.finish(req.trace)
        req.future._resolve(result)

    def _gather(self, limit: int) -> list:
        """Block briefly for the first request, then coalesce whatever
        arrives inside the batch window (continuous micro-batching:
        the window bounds added latency, the ladder bounds compiles)."""
        # a busy worker finds the next request waiting: no idle stretch
        first = self._queue.get_nowait() if self._idle_span is None \
            else None
        if first is None:
            if self._idle_span is None:
                # ``serve.idle``: no request in hand.  One span covers a
                # run of empty polls; it is renewed once a second, and
                # at the first poll after a profiler session started or
                # ended (a span that was open before the session is not
                # in its xplane, and the quiet stretch at its start
                # would go unexplained).
                self._idle_span = self._tracer.span("serve.idle", "idle")
                self._idle_since = self._clock()
                self._idle_in_session = profiler_session_live()
            first = self._queue.get(timeout=self._poll_s)
            if first is not None \
                    or self._clock() - self._idle_since >= 1.0 \
                    or profiler_session_live() != self._idle_in_session:
                self._close_idle()
            if first is None:
                self._flush_owed()
                return []
        with self._tracer.span("serve.gather", "batch") as sp:
            first.dequeued_at = self._clock()
            batch = [first]
            window_end = first.dequeued_at + self._batch_window_s
            while len(batch) < limit:
                remaining = window_end - self._clock()
                nxt = self._queue.get_nowait() if remaining <= 0 else \
                    self._queue.get(timeout=remaining)
                if nxt is None:
                    break
                nxt.dequeued_at = self._clock()
                batch.append(nxt)
            sp.set(n=len(batch))
        return batch

    def _close_idle(self):
        span, self._idle_span = self._idle_span, None
        if span is not None:
            span.__exit__(None, None, None)

    def _run(self):
        try:
            while True:
                if self._hard_stop:
                    break
                if self._draining or self._should_drain():
                    self._draining = True
                    if len(self._queue) == 0:
                        break
                batch = self._gather(self.batcher.max_batch)
                if not batch:
                    continue
                # expired-in-queue requests resolve typed, pre-device
                now = self._clock()
                live = []
                for r in batch:
                    if r.expired(now):
                        self._resolve(r, ServeResult(
                            Status.DEADLINE_EXCEEDED,
                            error="deadline expired in queue",
                            queued_s=now - r.submitted_at))
                    else:
                        live.append(r)
                if not live:
                    continue
                verdict = self.breaker.acquire()
                if verdict == REJECT:
                    for r in live:
                        self._resolve(r, ServeResult(
                            Status.UNAVAILABLE,
                            error="circuit breaker open"))
                    continue
                if verdict == PROBE and len(live) > 1:
                    # half-open admits ONE request; the rest requeue
                    # (ahead of newer arrivals) pending the verdict
                    self._queue.put_front(live[1:])
                    live = live[:1]
                for kind, group in self._group(live):
                    self._run_group(kind, group)
        finally:
            # hard stop (or a worker crash — nothing may hang): every
            # queued request resolves
            self._close_idle()
            self._flush_owed()
            leftover = self._queue.drain_all()
            for r in leftover:
                self._resolve(r, ServeResult(
                    Status.CANCELLED, error="server stopped"))
            self._drained.set()

    @staticmethod
    def _group(reqs):
        """Split a gathered batch into runnable groups: classify
        requests coalesce together; generate requests group by their
        compiled signature (prompt_len, opts); the paged kinds
        (prefill / decode) each form one group — they are driven
        per-request by the continuous paged loop, which interleaves
        them regardless of shape."""
        groups: dict = {}
        for r in reqs:
            if r.kind == "classify":
                key = ("classify",)
            elif r.kind in ("prefill", "decode"):
                key = (r.kind,)
            else:
                key = ("generate", r.payload.shape[0], r.opts)
            groups.setdefault(key, []).append(r)
        for key, group in groups.items():
            yield key[0], group

    def _run_group(self, kind: str, reqs: list):
        if kind in ("prefill", "decode") or (
                kind == "generate" and self.kv_pool is not None):
            return self._run_paged_group(kind, reqs)
        tr = self._tracer
        batch_id = next(self._batch_ids)
        with tr.span("serve.batch", "batch", batch_id=batch_id,
                     kind=kind, rows=len(reqs)) as batch_span:
            self._run_batch(kind, reqs, batch_id, batch_span)

    def _run_batch(self, kind: str, reqs: list, batch_id: int,
                   batch_span):
        """One static-shape batch on the worker thread, in four spans:
        ``serve.batch_form`` (stack, pad, host→device),
        ``serve.dispatch`` (the call into the compiled program
        returning — enqueue only), ``serve.fetch`` (device wait +
        device→host) and ``serve.resolve`` (slice, count, futures).
        Each request gets three retroactive records sharing its
        ``request_id`` and this ``batch_id``: ``admission_queue``
        (submitted → dequeued), ``batch_wait`` (dequeued → this batch
        began) and ``execute:<kind>`` (this batch began → done) —
        owed here, written by ``_flush_owed``."""
        tr = self._tracer
        t_batch = self._clock()
        queued = [t_batch - r.submitted_at for r in reqs]
        with self._model_lock:
            params, buffers = self._params, self._buffers
        try:
            _faults.check_serving_fault(self.name)
            if kind == "classify":
                with tr.span("serve.batch_form", "batch"):
                    new_sig = self.batcher.bucket_for(len(reqs)) \
                        not in self.batcher.buckets_dispatched
                    x, bucket = self.batcher.coalesce(
                        [r.payload for r in reqs])
                    xj = jnp.asarray(x)
                with tr.span("serve.dispatch",
                             "compile" if new_sig else "dispatch",
                             compiled=new_sig):
                    self._account_bucket_cost(bucket, params, buffers,
                                              xj)
                    out = self._fwd(params, buffers, xj)
                # host transfer doubles as the execution barrier —
                # device-side failures surface here, inside the try
                with tr.span("serve.fetch", "device_wait"):
                    self._flush_owed()
                    out_np = jax.tree_util.tree_map(np.asarray, out)
                with self._model_lock:
                    self._canary_x = xj  # freshest known-good canary
                if new_sig:
                    self._settle_heap()
            else:
                out_np, bucket = self._run_generate(params, reqs)
            batch_span.set(bucket=bucket)
            t_done = self._clock()
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            fatal = self.policy.classify(e) == "fatal"
            self.breaker.record_failure(fatal=fatal)
            err = f"{type(e).__name__}: {e}"
            log.warning("serving step failed (%s, %s): %s",
                        "fatal" if fatal else "retryable",
                        self.breaker.state, err)
            self._owe_records(kind, reqs, t_batch, None, batch_id, None)
            for r, q in zip(reqs, queued):
                self._resolve(r, ServeResult(
                    Status.INTERNAL_ERROR, error=err, queued_s=q))
            return
        with tr.span("serve.resolve", "other"):
            self.breaker.record_success()
            self.metrics.record_batch(len(reqs), bucket)
            self._owe_records(kind, reqs, t_batch, t_done, batch_id,
                              bucket)
            for i, (r, q) in enumerate(zip(reqs, queued)):
                self._resolve(r, ServeResult(
                    Status.OK, output=jax.tree_util.tree_map(
                        lambda a: a[i], out_np),
                    queued_s=q, bucket=bucket))

    @staticmethod
    def _request_records(req: Request, kind: str, t_batch: float,
                         t_done: Optional[float], batch_id: int,
                         bucket: Optional[int], rows: int):
        """The retroactive records of one request of a static batch:
        its wait in the admission queue until the worker took it, its
        wait in the worker's hands until the batch began (the gather
        window, and any group of the same gather that ran first) — the
        two sum to ``ServeResult.queued_s`` — and, for a batch that
        ran, ``execute:<kind>`` from there to done."""
        taken = min(max(req.dequeued_at, req.submitted_at), t_batch)
        yield ("admission_queue", "queue", req.submitted_at,
               taken - req.submitted_at, {"batch_id": batch_id})
        yield ("batch_wait", "batch", taken, t_batch - taken,
               {"batch_id": batch_id})
        if t_done is not None:
            yield (f"execute:{kind}", "execute", t_batch,
                   t_done - t_batch,
                   {"batch_id": batch_id, "bucket": bucket,
                    "batch": rows})

    def _owe_records(self, kind: str, reqs: list, t_batch: float,
                     t_done: Optional[float], batch_id: int,
                     bucket: Optional[int]):
        """Per-request records of a batch that is about to resolve.
        A request that carries a fleet trace context gets them into the
        sink now (its fragment is published when it resolves).  The
        ring's are OWED: writing three records a request between one
        batch's fetch and the next one's dispatch is host time the chip
        waits out and every queued request pays (0.15 ms a batch of 12
        on the v5e host — enough, summed over a busy stretch, to move
        which batch an arrival joins).  ``_flush_owed`` writes them
        once the device is busy again."""
        if self.trace_sink is not None:
            for r in reqs:
                if r.trace is not None:
                    for name, cat, start, dur, args in \
                            self._request_records(r, kind, t_batch,
                                                  t_done, batch_id,
                                                  bucket, len(reqs)):
                        self.trace_sink.record(r.trace, name, cat,
                                               start, dur, **args)
        if self._tracer.enabled:
            self._owed.append((kind, reqs, t_batch, t_done, batch_id,
                               bucket))

    def _flush_owed(self):
        """Write the owed per-request ring records (worker thread
        only).  Called where the worker has nothing better to do: at
        the start of ``serve.fetch`` (the next program is already
        running), on an empty poll, and when the worker exits — so the
        ring is at most one batch or one poll behind."""
        if not self._owed:
            return
        owed, self._owed = self._owed, []
        record = self._tracer.record
        for kind, reqs, t_batch, t_done, batch_id, bucket in owed:
            for r in reqs:
                for name, cat, start, dur, args in self._request_records(
                        r, kind, t_batch, t_done, batch_id, bucket,
                        len(reqs)):
                    record(name, cat, start, dur,
                           request_id=r.request_id, **args)

    def _account_bucket_cost(self, bucket: int, params, buffers, xj):
        """Per-bucket FLOP accounting: one XLA cost-model lowering the
        first time each classify bucket dispatches, installed into the
        metrics so `snapshot()` can report goodput-per-chip (served
        model-FLOP/s over the chip peak).  Best-effort: cost analysis
        failing must never fail the batch."""
        key = (int(bucket), tuple(xj.shape[1:]))
        if key in self._costed_buckets or self._fwd is None:
            return
        self._costed_buckets.add(key)
        try:
            from ..telemetry.perf import cost_from_analysis

            lowered = self._fwd.lower(params, buffers, xj)
            cost = cost_from_analysis(lowered.cost_analysis())
            if cost.flops > 0:
                self.metrics.record_bucket_cost(bucket, cost.flops)
        except Exception as e:  # non-lowerable fwd, analysis quirks
            log.debug("serving: bucket %d cost analysis skipped: %s",
                      bucket, e)

    # ------------------------------------------------------- paged decode
    def _import_handoff(self, decoder, blob):
        """Verify a KV handoff blob and materialize it as a live
        PagedSequence in THIS replica's pool (crc + geometry checked;
        pages leased here, scattered from the blob)."""
        from ..models.generate import PagedSequence
        from .pools import HandoffCorrupt, deserialize_handoff

        h = deserialize_handoff(blob)
        pool = self.kv_pool
        geometry = (h["layers"], h["num_kv_heads"], h["page_size"],
                    h["head_dim"])
        expect = (pool.layers, pool.num_kv_heads, pool.page_size,
                  pool.head_dim)
        if geometry != expect:
            raise HandoffCorrupt(
                f"handoff geometry {geometry} does not match this "
                f"pool {expect}")
        lease = pool.alloc(int(h["k_pages"].shape[0]))
        try:
            pool.write_pages(lease.pages, h["k_pages"], h["v_pages"])
        except BaseException:
            lease.release()
            raise
        return PagedSequence(lease, pos=int(h["pos"]),
                             last=int(h["first_token"]),
                             prompt_len=int(h["pos"]))

    def _run_paged_group(self, kind: str, reqs: list):
        """Continuous paged generation: one host loop interleaves
        every in-flight sequence a token at a time, so a long decode
        never blocks a short one and a kill/drain/deadline mid-stream
        resolves typed WITH its pages released.  Outcomes:

        * pool exhaustion (at start or on a mid-decode page
          extension) → typed OVERLOADED shed;
        * a corrupt handoff → INTERNAL_ERROR (refused before any K/V
          byte is trusted);
        * deadline mid-decode → DEADLINE_EXCEEDED;
        * hard stop mid-decode → CANCELLED;
        * everything else finishes OK with the unpaged path's exact
          eos-then-pad emission convention.
        """
        from ..models.generate import (_eos_pad, cached_paged_decoder)
        from .kvpool import PoolExhausted
        from .pools import HandoffCorrupt, serialize_handoff

        pool = self.kv_pool
        decoder = cached_paged_decoder(
            self.model, pool, compute_dtype=self.generate_dtype,
            page_window=self.kv_page_window,
            page_globals=self.kv_page_globals)
        with self._model_lock:
            params = self._params

        def fail(req, queued_s, exc, status=Status.INTERNAL_ERROR):
            fatal = self.policy.classify(exc) == "fatal"
            self.breaker.record_failure(fatal=fatal)
            err = f"{type(exc).__name__}: {exc}"
            log.warning("paged serving %s failed (%s, %s): %s",
                        req.kind, "fatal" if fatal else "retryable",
                        self.breaker.state, err)
            self._resolve(req, ServeResult(status, error=err,
                                           queued_s=queued_s))

        live = []
        for req in reqs:
            now = self._clock()
            queued_s = now - req.submitted_at
            self._trace(req, "admission_queue", "queue",
                        req.submitted_at, queued_s)
            try:
                _faults.check_serving_fault(self.name)
                if req.kind == "decode":
                    max_new, eos_id, pad_id = req.opts
                    eos, pad = map(int, _eos_pad(self.model, eos_id,
                                                 pad_id))
                    t_g = self._clock()
                    seq = self._import_handoff(decoder, req.payload)
                    self._trace(req, "kv_import", "kv_gather", t_g,
                                self._clock() - t_g,
                                pages=len(seq.lease.pages))
                    # the first token rode the handoff: this dispatch
                    # owes the remaining max_new - 1
                    entry = {
                        "req": req, "seq": seq, "toks": [],
                        "target": max_new - 1, "eos": eos, "pad": pad,
                        "queued_s": queued_s,
                        "done": eos > 0 and seq.last == eos,
                        "t_decode": self._clock(), "steps": 0,
                    }
                    live.append(entry)
                else:
                    t0 = self._clock()
                    seq = decoder.start(params, req.payload)
                    prefill_s = self._clock() - t0
                    self.metrics.record_phase("prefill", prefill_s,
                                              tenant=self._tenant_of(req))
                    self.metrics.record_ttft(
                        self._clock() - req.submitted_at,
                        tenant=self._tenant_of(req))
                    self._trace(req, "prefill", "prefill", t0,
                                prefill_s,
                                prompt_len=int(req.payload.shape[0]),
                                pages=len(seq.lease.pages))
                    if req.kind == "prefill":
                        t_g = self._clock()
                        k_pages, v_pages = pool.read_pages(
                            seq.lease.pages)
                        extras = None
                        if req.trace is not None:
                            from ..telemetry.trace_context import \
                                TRACE_WIRE_KEY

                            # the context rides the sealed blob: the
                            # decode replica joins the trace even when
                            # the dispatch path loses the kwarg
                            extras = {TRACE_WIRE_KEY:
                                      req.trace.to_wire()}
                        blob = serialize_handoff(
                            k_pages, v_pages, seq.last, seq.pos,
                            pool.page_size, extras=extras)
                        self._trace(req, "kv_export", "kv_gather", t_g,
                                    self._clock() - t_g,
                                    pages=len(seq.lease.pages),
                                    blob_bytes=len(blob))
                        seq.release()
                        self.breaker.record_success()
                        self.metrics.record_batch(1, 1)
                        self._resolve(req, ServeResult(
                            Status.OK, output=blob,
                            queued_s=queued_s, bucket=1))
                    else:  # paged full generate
                        max_new, eos_id, pad_id = req.opts
                        eos, pad = map(int, _eos_pad(
                            self.model, eos_id, pad_id))
                        live.append({
                            "req": req, "seq": seq,
                            "toks": [seq.last], "target": max_new,
                            "eos": eos, "pad": pad,
                            "queued_s": queued_s,
                            "done": eos > 0 and seq.last == eos,
                            "t_decode": self._clock(), "steps": 0,
                        })
            except PoolExhausted as e:
                # admission control, not failure: shed typed (the
                # breaker must not trip on a full pool)
                self._resolve(req, ServeResult(
                    Status.OVERLOADED, error=f"KV pool exhausted: {e}",
                    queued_s=queued_s))
            except (KeyboardInterrupt, SystemExit):
                raise
            except HandoffCorrupt as e:
                fail(req, queued_s, e)
            except Exception as e:
                fail(req, queued_s, e)
        self.metrics.set_kv_pool(pool.stats())

        def finish(entry):
            seq, req = entry["seq"], entry["req"]
            seq.release()
            decode_s = self._clock() - entry["t_decode"]
            self.metrics.record_phase("decode", decode_s,
                                      tenant=self._tenant_of(req))
            if entry["steps"]:
                self.metrics.record_tpot(decode_s / entry["steps"],
                                         tenant=self._tenant_of(req))
            self._trace(req, "decode", "decode", entry["t_decode"],
                        decode_s, steps=entry["steps"],
                        tokens=len(entry["toks"]))
            self.breaker.record_success()
            self.metrics.record_batch(1, 1)
            self._resolve(req, ServeResult(
                Status.OK,
                output=np.asarray(entry["toks"], np.int32),
                queued_s=entry["queued_s"], bucket=1))

        def abort(entry, result: ServeResult):
            entry["seq"].release()
            decode_s = self._clock() - entry["t_decode"]
            self._trace(entry["req"], "decode", "decode",
                        entry["t_decode"], decode_s,
                        steps=entry["steps"], aborted=True)
            result.queued_s = entry["queued_s"]
            self._resolve(entry["req"], result)

        # round-robin continuous decode: every live sequence advances
        # one token per round, so a long decode never starves a short
        # one and page pressure tracks actual lengths
        while live:
            if self._hard_stop:
                for entry in live:
                    abort(entry, ServeResult(
                        Status.CANCELLED,
                        error="server stopped mid-decode"))
                break
            nxt = []
            for entry in live:
                req, seq = entry["req"], entry["seq"]
                if len(entry["toks"]) >= entry["target"]:
                    finish(entry)
                    continue
                if entry["done"]:
                    # eos already emitted: pad-fill (the unpaged
                    # path's static-shape convention) without burning
                    # device steps
                    entry["toks"].extend(
                        [entry["pad"]]
                        * (entry["target"] - len(entry["toks"])))
                    nxt.append(entry)
                    continue
                if req.expired(self._clock()):
                    abort(entry, ServeResult(
                        Status.DEADLINE_EXCEEDED,
                        error="deadline expired mid-decode"))
                    continue
                try:
                    _faults.check_serving_fault(self.name)
                    tok = decoder.step(params, seq)
                except PoolExhausted as e:
                    abort(entry, ServeResult(
                        Status.OVERLOADED,
                        error=f"KV pool exhausted mid-decode: {e}"))
                    continue
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    entry["seq"].release()
                    fail(req, entry["queued_s"], e)
                    continue
                entry["steps"] += 1
                entry["toks"].append(tok)
                if entry["eos"] > 0 and tok == entry["eos"]:
                    entry["done"] = True
                nxt.append(entry)
            live = nxt
        self.metrics.set_kv_pool(pool.stats())

    def _compile_ladder(self, gen, params, sig):
        """The first batch of a (prompt length, ``max_new``) is the
        first of its ladder: callers that fill this bucket will fill
        the others, and each new one would stop the worker for a whole
        compile.  All of them start compiling now, beside each other
        on the compile pool — this batch's first, which ``gen`` then
        waits for; then those whose prompt pass goes in groups of rows
        (``generate.prefill_groups``), most groups first: a program is
        unrolled once a group, so these are the slowest to build or to
        load from the compile cache, and tracing and lowering are
        Python under one interpreter lock — what is asked for first is
        lowered first, and the slowest load has to start while the
        others are still lowered (SmallThinker's 32-row program of 4
        groups loads in 15-24 s: the warm ladder took 35.0 s smallest
        first and 28.3 s in this order before ``ops/grouped_prefill.py``,
        43.3 and 36.8-38.4 s with it; ``PERF.md`` §6 "PR 48"); then the
        rest, smallest first — so a ladder costs about its slowest
        program, not their sum (cold start of an autoscaled replica;
        the warm-up of a benchmark)."""
        from ..models.generate import prefill_groups

        _, bucket, prompt_len, max_new = sig
        ladder = self.batcher.ladder
        if self._compile_pool is None:
            self._compile_pool = ThreadPoolExecutor(
                max_workers=max(1, min(len(ladder),
                                       (os.cpu_count() or 2) - 1)),
                thread_name_prefix="bigdl-serving-compile")
        for b in [bucket] + sorted(
                (b for b in ladder if b != bucket),
                key=lambda b: -prefill_groups(b, prompt_len)):
            gen.compile_ahead(params, b, prompt_len, max_new,
                              self._compile_pool)

    def _run_generate(self, params, reqs):
        """One compiled decode program per (bucket, prompt_len,
        max_new): prompts stack along the batch dim and pad up to the
        bucket by repeating the last row (same ladder as classify, so
        generation traffic can't recompile per batch count either)."""
        from ..models.generate import cache_footprint, cached_generate

        tr = self._tracer
        max_new, eos_id, pad_id = reqs[0].opts
        with tr.span("serve.batch_form", "batch"):
            prompts = np.stack([r.payload for r in reqs])
            n = prompts.shape[0]
            bucket = self.batcher.bucket_for(n)
            if n < bucket:
                prompts = np.concatenate(
                    [prompts,
                     np.repeat(prompts[-1:], bucket - n, axis=0)],
                    axis=0)
            sig = ("gen", bucket, prompts.shape[1], max_new)
            new_sig = sig not in self.batcher.buckets_dispatched
            self.batcher.buckets_dispatched.add(sig)
            prompts_j = jnp.asarray(prompts, jnp.int32)
        # what this program holds on the device beside the weights, from
        # shapes: its cache is as long as prompt + max_new need, not as
        # the model's max_len (zero recurrent bytes for a model without
        # the state)
        holds = self._gen_footprints.get(sig[1:])
        if holds is None:
            holds = self._gen_footprints[sig[1:]] = cache_footprint(
                self.model, bucket, prompts.shape[1], max_new,
                compute_dtype=self.generate_dtype)
        # the call returning: eos/pad lookup, argument conversion and
        # the enqueue of ONE compiled program (a build on a new
        # signature); the decode itself runs behind it
        with tr.span("serve.dispatch",
                     "compile" if new_sig else "dispatch",
                     compiled=new_sig, **holds):
            gen = cached_generate(self.model,
                                  compute_dtype=self.generate_dtype)
            if new_sig and self.mesh is None:
                self._compile_ladder(gen, params, sig)
            ids, stats = gen(params, prompts_j, max_new, eos_id=eos_id,
                             pad_id=pad_id, return_stats=True)
        with tr.span("serve.fetch", "device_wait") as fetch:
            self._flush_owed()
            out = np.asarray(ids)[:, prompts.shape[1]:]  # generated tail
            if "moe_counts" in stats:
                # [layers, held] assignments to the held experts in this
                # call, fetched with the tokens
                fetch.set(**moe_counters(
                    np.asarray(stats["moe_counts"]),
                    bucket * (prompts.shape[1] + max_new - 1)))
            # whatever else the call's layers counted, one number each
            # (a hyper-connected model: how far from doubly stochastic
            # its worst residual map was), by its own name
            for name, v in stats.items():
                if not np.ndim(v):
                    fetch.set(**{name: float(v)})
        if new_sig:
            self._settle_heap()
        return out, bucket
