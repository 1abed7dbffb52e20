"""Bounded prefetch-to-device infeed — overlap batch N+1's host prep
with the compiled step on batch N.

Every optimizer mesh path used to either fetch synchronously (the
step waited on ``next(data_iter)`` + ``device_put`` every iteration)
or carry its own ad-hoc one-deep ``prefetch()`` closure inside the
driver loop.  This module is the one generalization: a
:class:`DevicePrefetcher` runs the fetch + host→device transfer on a
background producer thread into a bounded queue (default depth 2 —
double buffering), and the driver's ``get()`` measures *actual* stall
time — the seconds it really blocked on an empty buffer — which is the
only time the telemetry spine should ledger as ``data_stall``.
DeepSpark (arXiv:1602.08191) makes the same argument for overlapping
data movement with computation; INFEED_REHEARSAL.json measured the
decode pipeline at ~3x the consumption rate, so with any buffering the
steady-state stall is zero unless the pipeline is genuinely
data-bound.

Epoch semantics are preserved exactly: the producer stops once it has
fetched the epoch's record budget (never consuming past the epoch, so
rollover/shuffle/resume-cursor behavior is unchanged — the underlying
iterators shuffle from a clone, docs/determinism.md), and the driver
``reset()``-s the feed with the fresh iterator AFTER the shuffle — the one producer thread persists across epochs
(epochs can be two steps long; a thread spawn/join per epoch would be
its own stall).  By the time the driver reaches the rollover the
producer has exhausted its budget and is parked on the epoch
condition, so a fetch can never race the shuffle's index permutation.

Exceptions from the data pipeline (fault injectors, corrupt records,
``StopIteration`` from a finite iterator) are re-raised on the
training thread from ``get()``, exactly where a synchronous ``next``
would have raised them.

:class:`InlineFeed` is the same API without the thread (prefetch depth
0) — one driver code path serves both modes.
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Iterator, Optional

from ..telemetry.tracer import default_tracer

__all__ = ["DevicePrefetcher", "InlineFeed", "make_feed"]

_DONE = object()


def _count(name: str, help: str, n: float = 1.0):
    try:
        from ..telemetry import default_registry

        default_registry().counter(name, help).inc(n)
    except Exception:
        pass


class _Failure:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class InlineFeed:
    """Depth-0 feed: synchronous fetch with the same ``get()`` API —
    the whole fetch time is a real stall, reported as such."""

    def __init__(self, data_iter: Iterator,
                 transform: Optional[Callable] = None):
        self._it = data_iter
        self._transform = transform
        self._clock = default_tracer().clock

    def get(self):
        t0 = self._clock()
        batch = next(self._it)
        item = ((batch, *self._transform(batch)) if self._transform
                else (batch,))
        return item, self._clock() - t0

    def reset(self, data_iter: Iterator, epoch_size=None,
              start_records: int = 0):
        self._it = data_iter
        return self

    def close(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False


class DevicePrefetcher:
    """Background producer filling a bounded queue of device-ready
    batches.

    ``transform(batch)`` runs on the producer thread and returns the
    device-resident tuple (typically ``(x, y)`` via ``jnp.asarray`` —
    ``device_put`` dispatches asynchronously, so the transfer itself
    overlaps the running step too).  ``epoch_size``/``start_records``
    bound the producer to the current epoch: it stops *before*
    consuming a record past the budget, so an infinite epoch iterator
    is never over-read and the driver's rollover arithmetic is
    untouched.  One producer thread serves the feed's whole life;
    :meth:`reset` hands it the next epoch's iterator."""

    def __init__(self, data_iter: Iterator, *,
                 epoch_size: Optional[int] = None,
                 start_records: int = 0, depth: int = 2,
                 transform: Optional[Callable] = None,
                 name: str = "bigdl-infeed"):
        self.depth = max(1, int(depth))
        self._transform = transform
        self._tracer = default_tracer()
        self._q: queue.Queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._epoch = (data_iter, epoch_size, int(start_records))
        self._epoch_id = 0
        self.hits = 0     # get() served without blocking
        self.misses = 0   # get() blocked on an empty buffer (real stall)
        self.produced = 0
        self.epochs_fed = 0
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=name)
        self._thread.start()

    # -- producer --------------------------------------------------------
    def _run(self):
        served = -1
        while True:
            with self._cv:
                while self._epoch_id == served and not self._stop.is_set():
                    self._cv.wait()
                if self._stop.is_set():
                    return
                served = self._epoch_id
                it, budget, fetched = self._epoch
            self.epochs_fed += 1
            while not self._stop.is_set():
                if budget is not None and fetched >= budget:
                    break  # epoch budget met: park until reset
                try:
                    # next() + transform: the producer's own work
                    with self._tracer.span("feed.produce", "other"):
                        batch = next(it)
                        item = ((batch, *self._transform(batch))
                                if self._transform else (batch,))
                except BaseException as e:  # noqa: BLE001 — re-raised
                    # in get() on the training thread (StopIteration
                    # included: a finite iterator ending early surfaces
                    # exactly where a synchronous next() would have)
                    self._put(_Failure(e))
                    break
                size = getattr(batch, "size", None)
                if callable(size):
                    try:
                        fetched += int(size())
                    except TypeError:
                        fetched += 1
                else:
                    fetched += 1
                self.produced += 1
                if not self._put(item):
                    break

    def _put(self, item) -> bool:
        """Bounded put that stays responsive to close(): returns False
        when the feed was closed while waiting for queue room.  A wait
        on a FULL queue is the ``feed.blocked`` span: the proof that
        the driver never lacked data, not a cost."""
        try:
            self._q.put_nowait(item)
            return True
        except queue.Full:
            pass
        with self._tracer.span("feed.blocked", "idle"):
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
        return False

    # -- consumer --------------------------------------------------------
    def get(self):
        """Next ``(item, stall_seconds)``.  ``stall_seconds`` > 0 only
        when the buffer was actually empty — the honest ``data_stall``
        figure.  Re-raises any producer-side exception here, on the
        training thread."""
        clock = self._tracer.clock
        t0 = clock()
        try:
            item = self._q.get_nowait()
            stall = 0.0
            self.hits += 1
            _count("bigdl_infeed_buffer_hits_total",
                   "infeed get() served from a non-empty buffer")
        except queue.Empty:
            item = self._q.get()
            stall = clock() - t0
            self.misses += 1
            _count("bigdl_infeed_buffer_misses_total",
                   "infeed get() blocked on an empty buffer "
                   "(real data stall)")
        if isinstance(item, _Failure):
            raise item.exc
        return item, stall

    def reset(self, data_iter: Iterator,
              epoch_size: Optional[int] = None,
              start_records: int = 0):
        """Point the (persistent) producer at the next epoch's
        iterator.  The driver calls this AFTER consuming the previous
        epoch and AFTER the shuffle — at that point the producer has
        met its budget and is parked, so no fetch races the
        permutation."""
        with self._cv:
            self._epoch = (data_iter, epoch_size, int(start_records))
            self._epoch_id += 1
            self._cv.notify_all()
        return self

    def close(self, timeout: float = 10.0):
        """Stop the producer and join it — the barrier the driver runs
        at loop exit (and whenever the epoch contract below cannot be
        kept).  Idempotent."""
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        # unblock a producer stuck on a full queue
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        if self._thread.is_alive():
            self._thread.join(timeout=timeout)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False


def make_feed(data_iter: Iterator, *, epoch_size: Optional[int] = None,
              start_records: int = 0, depth: int = 2,
              transform: Optional[Callable] = None):
    """Feed factory the drivers use: ``depth >= 1`` builds the
    background :class:`DevicePrefetcher`; ``depth == 0`` the
    synchronous :class:`InlineFeed` (prefetch disabled)."""
    if int(depth) <= 0:
        return InlineFeed(data_iter, transform=transform)
    return DevicePrefetcher(data_iter, epoch_size=epoch_size,
                            start_records=start_records, depth=depth,
                            transform=transform)
