"""Structured step tracer — nested spans into a bounded ring buffer,
exported as Chrome-trace JSON (the format Perfetto / chrome://tracing
load directly).

Where the registry answers "how many / how long on average", the
tracer answers "what was the wall clock doing at second 83": every
driver iteration records a ``step`` span whose children attribute the
time to an explicit category — ``data_wait`` (input pipeline),
``host_to_device`` (infeed), ``compile`` (XLA build), ``compute`` /
``collective`` (the xplane phase split of a profiled step,
optim/profiling.py), ``checkpoint``, ``recovery``.  The buffer is a
ring: a week-long run keeps the most recent ``capacity`` spans instead
of growing without bound.

One tracer serves the process: :func:`default_tracer` (beside
``default_registry``) is what the plan driver loop, the plan engine,
the prefetcher, the serving worker and ``generate`` speak to directly
— no ``set_telemetry``, no sink.  Its ring is a flight recorder: on by
default, bounded, dumped with :meth:`Tracer.export_json` without
foresight.  ``Tracer.enabled = False`` is the one switch; a disabled
``span()`` takes no lock and allocates nothing.

**The profiler bridge.**  A live :meth:`Tracer.span` also enters
``jax.profiler.TraceAnnotation("bigdl." + name, **ids)``.  With no
profiler session that is one flag check; inside one
(``jax.profiler.trace`` / ``start_trace``) the span lands on its
thread's line of ``/host:CPU`` in the xplane, on the clock of the
``XLA Ops`` lines — joined to the device by construction.  ``jax`` is
never imported here: a process that has not imported it has no
session to join.

Spans nest two ways:

* :meth:`Tracer.span` — a context manager pushing onto a thread-local
  stack; children opened inside it are linked to it and cannot
  outlive it (closing the parent closes abandoned children).
* :meth:`Tracer.record` — retroactive insertion with explicit
  ``start``/``duration`` (and optionally an explicit ``parent``), for
  timings that are only known after the fact — e.g. the profiler's
  compute/collective split of a step that already ended.  Children
  recorded under a parent are clamped into the parent's interval, so
  the no-child-outlives-its-parent invariant holds for exports.
"""
from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from .trace_context import REQUEST_CATEGORIES

__all__ = ["CATEGORIES", "DEVICE_SCOPES", "PROGRAM_SPANS",
           "STEP_CATEGORIES", "Span",
           "Tracer", "default_tracer", "profiler_session_live",
           "reset_default_tracer"]

#: the training-side vocabulary — everything the goodput ledger can
#: attribute a second of wall clock to, plus the profiled split of
#: on-device time, plus the three things a host thread does around a
#: compiled program: ``dispatch`` (enqueue it), ``device_wait`` (block
#: on its result) and ``state_sync`` (move whole state trees between
#: module and device at the edges of ``optimize()``)
STEP_CATEGORIES = (
    "step", "data_wait", "host_to_device", "compile", "compute",
    "collective", "checkpoint", "recovery", "idle", "other",
    "dispatch", "device_wait", "state_sync",
)

#: the closed vocabulary of span categories: the training table above
#: plus the request-path table (ONE shared constant source —
#: ``telemetry.trace_context.REQUEST_CATEGORIES`` — so router, server
#: and tracer can never drift; a vocabulary lint enforces it)
CATEGORIES = STEP_CATEGORIES + REQUEST_CATEGORIES


#: the program's in-place spans — stable API (no file names, no line
#: numbers; docs/observability.md has where each is emitted and what it
#: bounds): name -> category.  A ``*.dispatch`` that builds a fresh
#: program is categorized ``compile`` instead.  A lint
#: (tests/test_program_spans.py) holds every literal span name under
#: these prefixes to this table.
PROGRAM_SPANS = {
    "train.optimize": "other",
    "plan.init_state": "state_sync",
    "plan.sync_to_model": "state_sync",
    "train.iteration": "step",
    "train.stage": "other",
    "train.data_wait": "data_wait",
    "train.place_batch": "host_to_device",
    "train.dispatch": "dispatch",
    "train.loss_fetch": "device_wait",
    "train.bookkeeping": "other",
    "train.validation": "other",
    "train.checkpoint": "checkpoint",
    "train.report": "other",
    "feed.produce": "other",
    "feed.blocked": "idle",
    "serve.idle": "idle",
    "serve.gather": "batch",
    "serve.batch": "batch",
    "serve.batch_form": "batch",
    "serve.dispatch": "dispatch",
    "serve.fetch": "device_wait",
    "serve.resolve": "other",
}


#: the device scopes (``jax.named_scope``: metadata on the HLO
#: operations, read from a device trace's ``op_name``) — stable API like
#: the spans above.  ``generate.*`` name the stretches of one compiled
#: generate call (``models/generate.py``); ``mixer.*`` the parts of a
#: hybrid block inside ``generate.prefill`` / ``generate.decode_step``
#: (``nn/mamba.py``): ``mixer.ssd_scan`` is the chunked scan of a whole
#: sequence, ``mixer.ssm_step`` the one-token update of conv tail and
#: state, ``mixer.attention`` the attention branch beside the mixer.
#: ``moe.*`` the parts of a dropless expert layer (``parallel/moe.py``):
#: ``moe.route`` scores, top-k and gates, ``moe.dispatch`` the sort and
#: the gather into the sorted buffer, ``moe.expert_matmul`` the grouped
#: products and the gate, ``moe.combine`` the weighted gather back,
#: ``moe.shared`` the shared experts; ``block.attention`` the attention
#: branch of a parallel block (``models/parallel_moe.py``) or of a
#: latent block (``models/latent_moe.py``).  ``mla.*`` the parts of
#: latent attention inside ``block.attention``: ``mla.q_proj`` the
#: query's two projections, norm and rotation, ``mla.kv_latent`` the
#: latent, its norm, the rotated shared key and the cache write,
#: ``mla.expand`` per-head K and V from the latent (prefill only),
#: ``mla.absorb`` the absorbed products of a decode step (``q_nope ->
#: q_lat`` and ``o_lat -> o``), ``mla.attend`` scores, softmax and the
#: weighted sum over the cached latent, ``mla.out_proj`` the output
#: projection.
#: ``mla.prefill_attend`` the plain causal attention of a whole sequence
#: on per-head K and V, whole scores (``nn/attention.py``): the prompt
#: pass of a latent layer whose value head is narrower than its query's
#: (the flash kernels take one head size).
#: ``mhc.*`` the parts of ONE sublayer's hyper-connection
#: (``nn/hyper_connection.py``), inside that sublayer's scope
#: (``block.attention``, or ``block.mlp`` for the FFN): ``mhc.coeffs``
#: the mean square over the streams, the one product with ``phi`` and
#: the two sigmoids, ``mhc.sinkhorn`` clip, ``exp``, the sweeps and the
#: error reading, ``mhc.pre`` the mixture a sublayer reads, ``mhc.post``
#: the write of its result to every stream.
#: ``block.conv`` the operator sublayer of a block WITHOUT attention
#: (``models/latent_moe.py``'s sequential block over
#: ``nn/short_conv.py``) — the twin of ``block.attention``, which the
#: same model's attention layers carry: norm, operator and residual in
#: the block's ``apply_fn``, the operator in a generate program — and
#: inside it ``conv.in_proj`` the one product that gives ``B``, ``C``
#: and ``u``, ``conv.short`` everything between the two products
#: (``B * u``, the ``kernel``-tap sum over the tail, the tail's shift,
#: ``C *``), ``conv.out_proj`` the output projection.
#: ``step.*`` the parts of one compiled TRAINING step
#: (``parallel/plan.py``'s local step; ``train.*`` stay host spans); the
#: backward has no scope of its own: autodiff wraps the outermost
#: component of the forward's path,
#: ``transpose(jvp(step.forward))/block.mlp/...``:
#: ``step.cast_params`` the cast of parameters and input to the compute
#: dtype and the FSDP gather,
#: ``step.forward`` the model's ``apply_fn`` (the model's scopes nest in it),
#: ``step.loss`` the criterion and the auxiliary term (masked or not),
#: ``step.grad_reduce`` the gradients' collectives over the mesh, the
#: stale exchange, and the reduce of the loss and the buffers,
#: ``step.update`` every pass over the parameter tree after the reduce:
#: the regulariser's gradient and the gradient scales where a model has
#: them, the global gradient norm, the optimizer method's step, the
#: finiteness guard (check, ``pmin``, selects) and the periodic
#: averaging round — ONE name, because the compiler fuses the
#: optimizer's arithmetic under the guard's selects: a name each read 0
#: for the optimizer and the whole stretch for the guard (v5e, PR 38).
#: In a ``TransformerLM`` (``models/transformer.py``):
#: ``lm.embed`` the token embedding and the learned positions,
#: ``block.mlp`` a block's second norm, its FFN (dense, SwiGLU or
#: experts) and the residual add — beside ``block.attention``, here the
#: first norm, the attention module and its add,
#: ``lm.head`` the final norm, the vocabulary head and its
#: ``log_softmax`` where that runs,
#: ``attention.core`` (``nn/attention.py``) the attention itself inside
#: ``MultiHeadAttention`` — the flash kernels, forward and backward, or
#: whichever arm ``seq_strategy`` picks; projections and rotation are
#: outside it.
DEVICE_SCOPES = (
    "generate.cast_params", "generate.prefill", "generate.prefill_group",
    "generate.decode_step", "generate.sample",
    "mixer.in_proj", "mixer.conv", "mixer.ssd_scan", "mixer.ssm_step",
    "mixer.gate_norm", "mixer.out_proj", "mixer.attention",
    "moe.route", "moe.dispatch", "moe.expert_matmul", "moe.combine",
    "moe.shared", "block.attention",
    "mla.q_proj", "mla.kv_latent", "mla.expand", "mla.absorb",
    "mla.attend", "mla.out_proj", "mla.prefill_attend",
    "mhc.coeffs", "mhc.sinkhorn", "mhc.pre", "mhc.post",
    "block.conv", "conv.in_proj", "conv.short", "conv.out_proj",
    "step.cast_params", "step.forward", "step.loss", "step.grad_reduce",
    "step.update",
    "lm.embed", "block.mlp", "lm.head", "attention.core",
    "attention.decode_attend",
)


def profiler_session_live() -> bool:
    """Whether a profiler session is recording right now.  A span
    opened before it started is not in its xplane; a long-lived one
    (the serving worker's ``serve.idle``) asks, and renews itself."""
    return _annotation_class() is not None


class Span:
    __slots__ = ("id", "name", "category", "start", "end", "tid",
                 "parent_id", "args", "_annotation")

    def __init__(self, id: int, name: str, category: str, start: float,
                 tid: int, parent_id: Optional[int],
                 args: Optional[dict]):
        self.id = id
        self.name = name
        self.category = category
        self.start = start
        self.end: Optional[float] = None
        self.tid = tid
        self.parent_id = parent_id
        self.args = args
        self._annotation = None  # the live profiler twin, if any

    @property
    def duration(self) -> float:
        return (self.end - self.start) if self.end is not None else 0.0

    def set(self, **args):
        """Attach what is only known once the span is open (``hit``,
        ``compiled``, ``n``): into the ring's args and, inside a
        profiler session, onto the xplane event's stats."""
        if self.args is None:
            self.args = args
        else:
            self.args.update(args)
        if self._annotation is not None:
            self._annotation.set_metadata(**args)

    def __repr__(self):
        return (f"Span({self.name!r}, cat={self.category!r}, "
                f"dur={self.duration:.6f}s)")

    def to_dict(self) -> dict:
        """JSON-serializable form — what trace fragments and telemetry
        payloads publish over the KV transport."""
        out = {"id": self.id, "name": self.name, "cat": self.category,
               "start": self.start, "dur": self.duration,
               "tid": self.tid}
        if self.parent_id is not None:
            out["parent_id"] = self.parent_id
        if self.args:
            out["args"] = dict(self.args)
        return out


class _SpanCtx:
    """Context manager for one open span (returned by Tracer.span)."""

    __slots__ = ("_tracer", "span")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self.span = span

    def __enter__(self) -> Span:
        return self.span

    def __exit__(self, exc_type, exc, tb):
        self._tracer._close(self.span)
        return False


class _NullSpan:
    """What a disabled tracer hands out: one shared object that is its
    own context manager and swallows :meth:`Span.set`."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set(self, **args):
        pass


_NULL_SPAN = _NullSpan()

#: ``jax.profiler.TraceAnnotation`` once jax is in the process
_TraceAnnotation = None


def _annotation_class():
    """The annotation class while a profiler session is live, else
    None.  Never imports jax: without it there is no session."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        _TraceAnnotation = jax.profiler.TraceAnnotation
    return _TraceAnnotation if _TraceAnnotation.is_enabled() else None


class Tracer:
    def __init__(self, capacity: int = 8192,
                 clock: Callable[[], float] = time.perf_counter,
                 enabled: bool = True):
        self.capacity = int(capacity)
        self._clock = clock
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._done: deque = deque(maxlen=self.capacity)
        self._local = threading.local()
        self._ids = itertools.count(1)  # next() is atomic under the GIL
        self.dropped = 0  # spans evicted from the ring

    # -- internals ------------------------------------------------------
    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _finish(self, span: Span):
        ann, span._annotation = span._annotation, None
        if ann is not None:
            ann.__exit__(None, None, None)
        with self._lock:
            if len(self._done) == self._done.maxlen:
                self.dropped += 1
            self._done.append(span)

    # -- recording ------------------------------------------------------
    def span(self, name: str, category: str = "other", **ids):
        """Open a nested span: ``with tracer.span("step", "step") as s``.
        Children opened on the same thread while it is open are linked
        to it.  ``ids`` (``step``, ``batch_id``, ``request_id``,
        ``bucket``) ride as args in the ring and as event stats in a
        profiler session."""
        if not self.enabled:
            return _NULL_SPAN
        _check_category(category)
        stack = self._stack()
        s = Span(next(self._ids), str(name), category, 0.0,
                 threading.get_ident(),
                 stack[-1].id if stack else None, ids or None)
        stack.append(s)
        ann = _annotation_class()
        if ann is not None:
            s._annotation = ann("bigdl." + s.name, **ids)
            s._annotation.__enter__()
        s.start = self._clock()
        return _SpanCtx(self, s)

    def _close(self, span: Span):
        now = self._clock()
        stack = self._stack()
        # close abandoned children first (an exception can unwind past
        # a child's __exit__ only through re-entrancy bugs; be safe)
        while stack and stack[-1] is not span:
            child = stack.pop()
            child.end = now
            self._finish(child)
        if stack and stack[-1] is span:
            stack.pop()
        span.end = now
        self._finish(span)

    def record(self, name: str, category: str, start: float,
               duration: float, parent: Optional[Span] = None,
               **args) -> Optional[Span]:
        """Retroactively insert a completed span.  With ``parent``, the
        interval is clamped into the parent's so no child outlives it
        (profiler-derived children are estimates, not clock truths)."""
        if not self.enabled:
            return None
        _check_category(category)
        start = float(start)
        end = start + max(0.0, float(duration))
        if parent is not None and parent.end is not None:
            start = min(max(start, parent.start), parent.end)
            end = min(max(end, start), parent.end)
        tid = threading.get_ident()
        s = Span(next(self._ids), str(name), category, start, tid,
                 parent.id if parent else None, args or None)
        s.end = end
        # one lock round trip — retroactive records run on serving hot
        # paths
        with self._lock:
            if len(self._done) == self._done.maxlen:
                self.dropped += 1
            self._done.append(s)
        return s

    @property
    def clock(self) -> Callable[[], float]:
        return self._clock

    # -- export ---------------------------------------------------------
    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._done)

    def clear(self):
        with self._lock:
            self._done.clear()

    def export_spans(self, limit: Optional[int] = None) -> List[dict]:
        """The newest ``limit`` completed spans as JSON-serializable
        dicts (all of them when ``limit`` is None) — what
        ``Telemetry.payload`` publishes for the cluster timeline."""
        spans = self.spans()
        if limit is not None and len(spans) > int(limit):
            spans = spans[-int(limit):]
        return [s.to_dict() for s in spans]

    def category_totals(self) -> Dict[str, float]:
        """Seconds per category, summed over completed spans.  A span
        counts its SELF time (its duration minus its direct children),
        so nested program spans — a ``train.iteration`` over its
        phases, a ``serve.batch`` over its four, a step over its
        profiled compute/collective children — never double-report."""
        spans = self.spans()
        child_sum: Dict[int, float] = {}
        for s in spans:
            if s.parent_id is not None:
                child_sum[s.parent_id] = (child_sum.get(s.parent_id, 0.0)
                                          + s.duration)
        out: Dict[str, float] = {}
        for s in spans:
            dur = max(0.0, s.duration - child_sum.get(s.id, 0.0))
            out[s.category] = out.get(s.category, 0.0) + dur
        return out

    def to_chrome_trace(self) -> dict:
        """Chrome-trace ("Trace Event Format") JSON dict — load it in
        Perfetto (ui.perfetto.dev) or chrome://tracing.  Complete
        ("ph":"X") events, microsecond timestamps."""
        pid = os.getpid()
        events = []
        for s in self.spans():
            ev = {
                "name": s.name, "cat": s.category, "ph": "X",
                "ts": s.start * 1e6, "dur": s.duration * 1e6,
                "pid": pid, "tid": s.tid,
            }
            args = dict(s.args or {})
            args["span_id"] = s.id
            if s.parent_id is not None:
                args["parent_id"] = s.parent_id
            ev["args"] = args
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_json(self, path: str):
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)


_CATEGORY_SET = frozenset(CATEGORIES)

_default: Optional[Tracer] = None
_default_lock = threading.Lock()


def default_tracer() -> Tracer:
    """The process-wide tracer.  The plan driver loop, the plan engine,
    the prefetcher, the serving worker and ``generate`` record into it
    unconditionally; a Telemetry facade built without an explicit
    tracer adopts it, so one export carries the whole process."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = Tracer()
    return _default


def reset_default_tracer() -> Tracer:
    """Swap in a fresh default tracer (tests isolate with this).
    Emitters look the tracer up at every entry, never at import."""
    global _default
    with _default_lock:
        _default = Tracer()
        return _default


def _check_category(category: str):
    if category not in _CATEGORY_SET:
        raise ValueError(f"unknown span category {category!r}; one of "
                         f"{CATEGORIES}")
