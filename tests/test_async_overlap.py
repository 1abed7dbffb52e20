"""Async-everything overlap engine specs (ISSUE 7): background
snapshot-then-write checkpointing (resilience/async_checkpoint.py),
the bounded prefetch-to-device infeed (dataset/prefetch.py), the
background publisher (telemetry/publish.py) with incarnation-keyed
staleness discard, the goodput plumbing that ledgers only REAL stalls
and checkpoint back-pressure — plus the acceptance e2es: bitwise
resume equivalence against an async-written checkpoint, the
crash-during-async-checkpoint chain (writer killed mid-write →
previous checkpoint survives → torn file quarantined → bitwise
resume), and a bounded-memory regression spec for the long-run RSS
audit (telemetry/elastic object counts plateau).
"""
import os
import queue
import threading
import time

import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import Sample, array
from bigdl_tpu.dataset.prefetch import (DevicePrefetcher, InlineFeed,
                                        make_feed)
from bigdl_tpu.optim import (SGD, LocalOptimizer, max_iteration,
                             several_iteration)
from bigdl_tpu.resilience import FlightRecorder, faults
from bigdl_tpu.resilience.async_checkpoint import (AsyncCheckpointError,
                                                   AsyncCheckpointWriter)
from bigdl_tpu.resilience.checkpoint import verify_file
from bigdl_tpu.telemetry import (BackgroundPublisher, MetricsRegistry,
                                 Telemetry)
from bigdl_tpu.utils import file_io
from bigdl_tpu.utils.rng import set_global_seed


@pytest.fixture(autouse=True)
def _reset_explicit_seed():
    from bigdl_tpu.utils import rng as rng_mod

    yield
    rng_mod._explicit_seed = None


def _regression_samples(n=256, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 4).astype(np.float32)
    w = np.array([[1.5], [-2.0], [0.5], [3.0]], np.float32)
    y = (x @ w + 0.7).astype(np.float32)
    return [Sample(x[i], y[i]) for i in range(n)]


def _regression_model():
    return nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))


def _step_records(path):
    from bigdl_tpu.resilience import load_journal

    return {r["step"]: r for r in load_journal(path)
            if r.get("kind") == "step"}


# ---------------------------------------------------------------------------
# AsyncCheckpointWriter unit specs
# ---------------------------------------------------------------------------

def test_writer_commits_bytes_with_crc_and_drains(tmp_path):
    w = AsyncCheckpointWriter()
    p1 = str(tmp_path / "model.5")
    p2 = str(tmp_path / "optimMethod.5")
    blocked = w.submit(5, [(p1, b"params-bytes"), (p2, b"slots-bytes")])
    assert blocked >= 0.0
    assert w.drain(timeout=10.0)
    assert open(p1, "rb").read() == b"params-bytes"
    assert open(p2, "rb").read() == b"slots-bytes"
    # torn-write protection's evidence: crc32c sidecars verify
    assert verify_file(p1) is True and verify_file(p2) is True
    assert w.writes == 1 and w.pending == 0
    w.close()


def test_writer_backpressure_blocks_and_reports_seconds(tmp_path):
    """Depth 1: a second submit while the first write is in flight
    blocks until it commits, and the blocked seconds are returned —
    the only checkpoint time left on the caller's critical path."""
    release = threading.Event()
    started = threading.Event()

    def slow_write():
        started.set()
        assert release.wait(10.0)

    w = AsyncCheckpointWriter(queue_depth=1)
    w.submit(1, fn=slow_write)
    assert started.wait(5.0)
    t = threading.Timer(0.25, release.set)
    t.start()
    blocked = w.submit(2, [(str(tmp_path / "model.2"), b"x")])
    t.cancel()
    assert blocked >= 0.15, f"submit returned without waiting ({blocked})"
    assert w.drain(timeout=10.0)
    assert w.blocked_seconds >= 0.15
    w.close()


def test_writer_jobs_commit_in_submission_order(tmp_path):
    """One writer thread => FIFO: step N's files can never land after
    step N+1's (the overwrite layout depends on this)."""
    w = AsyncCheckpointWriter(queue_depth=1)
    p = str(tmp_path / "model")
    for n in range(8):
        w.submit(n, [(p, b"step-%d" % n)])
    assert w.drain(timeout=10.0)
    assert open(p, "rb").read() == b"step-7"
    assert verify_file(p) is True
    assert w.writes == 8
    w.close()


def test_writer_error_surfaces_on_training_thread(tmp_path):
    """A background write failure is stored and re-raised at the next
    submit/drain — asynchrony must not eat checkpoint failures."""
    w = AsyncCheckpointWriter()
    with faults.io_faults(str(tmp_path / "model"), times=1):
        w.submit(3, [(str(tmp_path / "model.3"), b"x")])
        # wait for the background failure without consuming it
        assert w.drain(timeout=10.0, raise_errors=False)
        with pytest.raises(AsyncCheckpointError) as ei:
            w.submit(4, [(str(tmp_path / "model.4"), b"y")])
    assert "step 3" in str(ei.value)
    # the error was consumed; the writer keeps serving later jobs
    # (the raising submit queued nothing — resubmit like a retry would)
    w.submit(4, [(str(tmp_path / "model.4"), b"y")])
    assert w.drain(timeout=10.0)
    assert os.path.exists(tmp_path / "model.4")
    # the failed write left nothing under the final name (atomic tmp)
    assert not os.path.exists(tmp_path / "model.3")
    w.close()


# ---------------------------------------------------------------------------
# DevicePrefetcher / InlineFeed unit specs
# ---------------------------------------------------------------------------

class _FakeBatch:
    def __init__(self, i, n=4):
        self.i = i
        self.n = n

    def size(self):
        return self.n


def test_prefetcher_preserves_order_and_epoch_budget():
    """The producer never consumes past the epoch's record budget of
    an infinite iterator, and items arrive in order."""
    fetched = []

    def gen():
        i = 0
        while True:
            fetched.append(i)
            yield _FakeBatch(i)
            i += 1

    feed = DevicePrefetcher(gen(), epoch_size=16, depth=2)
    got = [feed.get()[0][0].i for _ in range(4)]  # 4 batches x 4 = 16
    assert got == [0, 1, 2, 3]
    time.sleep(0.1)  # producer must be parked, not over-reading
    assert len(fetched) == 4
    # reset re-arms the SAME producer thread on the next epoch
    t = feed._thread
    feed.reset(gen(), epoch_size=8, start_records=0)
    got2 = [feed.get()[0][0].i for _ in range(2)]
    assert got2 == [0, 1]
    assert feed._thread is t and t.is_alive()
    assert feed.epochs_fed == 2
    feed.close()
    assert not t.is_alive()


def test_prefetcher_stall_accounting_only_when_empty():
    """data_stall truth: a buffered batch costs ~0 stall; an empty
    buffer bills the real wait."""
    slow = threading.Event()

    def gen():
        i = 0
        while True:
            if i >= 2:
                slow.wait(0.3)  # batches after the second arrive late
            yield _FakeBatch(i)
            i += 1

    feed = DevicePrefetcher(gen(), epoch_size=16, depth=2)
    time.sleep(0.2)  # let the buffer fill
    _, stall1 = feed.get()
    assert stall1 == 0.0 and feed.hits == 1
    feed.get()
    _, stall3 = feed.get()  # producer is sleeping: real stall
    assert stall3 > 0.05 and feed.misses >= 1
    feed.close()


def test_prefetcher_reraises_pipeline_exceptions_in_consumer():
    fault = faults.ExceptionTransformer(
        fail_at=3, exc=lambda: OSError("injected pipeline failure"))
    data = array(_regression_samples()) >> fault
    it = data.data(train=True)
    feed = DevicePrefetcher(it, epoch_size=10_000, depth=2)
    with pytest.raises(OSError):
        for _ in range(64):
            feed.get()
    feed.close()


def test_prefetcher_transform_runs_on_producer_and_stopiteration():
    feed = DevicePrefetcher(iter([_FakeBatch(0)]), depth=2,
                            transform=lambda b: (b.i * 10,))
    (batch, tens), _ = feed.get()
    assert batch.i == 0 and tens == 0
    with pytest.raises(StopIteration):
        feed.get()  # finite iterator ends where next() would have
    feed.close()


def test_make_feed_depth_zero_is_inline():
    feed = make_feed(iter([_FakeBatch(1)]), depth=0,
                     transform=lambda b: (b.i,))
    assert isinstance(feed, InlineFeed)
    (b, i), stall = feed.get()
    assert b.i == 1 and i == 1 and stall > 0.0
    feed.close()


# ---------------------------------------------------------------------------
# BackgroundPublisher unit specs
# ---------------------------------------------------------------------------

def test_publisher_publishes_and_drains():
    seen = []
    p = BackgroundPublisher()
    for i in range(4):
        assert p.submit(lambda i=i: seen.append(i))
    assert p.drain(timeout=5.0)
    assert seen == [0, 1, 2, 3]
    assert p.published == 4
    p.close()
    assert p.submit(lambda: None) is False  # closed => caller degrades


def test_publisher_discards_stale_incarnation():
    inc = {"v": 3}
    gate = threading.Event()
    seen = []
    p = BackgroundPublisher(incarnation_of=lambda: inc["v"])
    p.submit(gate.wait)  # hold the thread so the next task queues
    p.submit(lambda: seen.append("stale"), incarnation=2)
    p.submit(lambda: seen.append("live"), incarnation=3)
    gate.set()
    assert p.drain(timeout=5.0)
    assert seen == ["live"]
    assert p.discarded_stale == 1
    p.close()


def test_publisher_coalesces_by_key_and_urgent_jumps_queue():
    gate = threading.Event()
    seen = []
    p = BackgroundPublisher()
    p.submit(gate.wait)
    p.submit(lambda: seen.append("tm-old"), key="tm")
    p.submit(lambda: seen.append("vote"), urgent=True)
    p.submit(lambda: seen.append("tm-new"), key="tm")  # replaces tm-old
    gate.set()
    assert p.drain(timeout=5.0)
    assert seen == ["vote", "tm-new"]
    assert p.coalesced == 1
    p.close()


def test_elastic_publish_rides_publisher_and_cluster_snapshot_drains():
    from bigdl_tpu.resilience import ElasticContext, ElasticCoordinator
    from bigdl_tpu.resilience.elastic import InMemoryKV

    kv = InMemoryKV()
    ctx = ElasticContext(ElasticCoordinator("host0", kv))
    ctx.telemetry = Telemetry(registry=MetricsRegistry(), host="host0")
    ctx.begin_attempt()
    ctx.telemetry.on_step(0.01, records=4, step=1)
    ctx.publish_telemetry(1)
    snap = ctx.cluster_snapshot()  # drains the publisher before collect
    assert snap["hosts"] == ["host0"]
    assert snap["goodput"]["seconds"]["productive"] > 0
    assert ctx._publisher is not None and ctx._publisher.published >= 1
    ctx.close()


# ---------------------------------------------------------------------------
# driver e2e: async checkpoint + prefetch through the Local loop
# ---------------------------------------------------------------------------

def _build_opt(data=None, fault=None, async_ckpt=True):
    set_global_seed(123)
    ds = data if data is not None else array(_regression_samples())
    if fault is not None:
        ds = ds >> fault
    opt = LocalOptimizer(_regression_model(), ds, nn.MSECriterion(),
                         batch_size=64)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_async_checkpoint(async_ckpt)
    return opt


def test_async_checkpoint_resume_bitwise_equals_sync(tmp_path):
    """The acceptance spec: a checkpoint written by the background
    writer restores a run that is BITWISE identical to one resumed
    from a synchronous checkpoint — the snapshot is taken at the same
    step boundary; only the I/O moved."""
    steps, ckpt_at = 10, 6

    def run(mode_dir, async_ckpt):
        opt = _build_opt(async_ckpt=async_ckpt)
        opt.set_end_when(max_iteration(steps))
        opt.set_checkpoint(str(tmp_path / mode_dir),
                           several_iteration(ckpt_at))
        with FlightRecorder(str(tmp_path / f"{mode_dir}.jsonl")) as rec:
            opt.set_flight_recorder(rec)
            opt.optimize()

    def resume(mode_dir):
        set_global_seed(999)  # trainState must overwrite it
        opt = LocalOptimizer(_regression_model(),
                             array(_regression_samples()),
                             nn.MSECriterion(), batch_size=64)
        opt.set_optim_method(SGD(learning_rate=0.1))
        opt.set_checkpoint(str(tmp_path / mode_dir),
                           several_iteration(ckpt_at))
        assert opt.resume_from_checkpoint() is True
        assert opt.optim_method.state["neval"] == ckpt_at + 1
        opt.set_end_when(max_iteration(steps))
        with FlightRecorder(
                str(tmp_path / f"{mode_dir}.resume.jsonl")) as rec:
            opt.set_flight_recorder(rec)
            opt.optimize()
        return _step_records(str(tmp_path / f"{mode_dir}.resume.jsonl"))

    run("sync", async_ckpt=False)
    run("async", async_ckpt=True)
    # both modes committed the same checkpoint files, crc-verified
    for leg in ("model", "optimMethod", "trainState"):
        sync_p = str(tmp_path / "sync" / f"{leg}.{ckpt_at}")
        async_p = str(tmp_path / "async" / f"{leg}.{ckpt_at}")
        assert verify_file(sync_p) is True
        assert verify_file(async_p) is True
        assert open(sync_p, "rb").read() == open(async_p, "rb").read(), \
            f"async-written {leg} bytes differ from sync-written"
    a = resume("sync")
    b = resume("async")
    assert set(a) == set(b) == set(range(ckpt_at + 1, steps + 1))
    for s in a:
        for field in ("batch_id", "loss_bits", "grad_norm_bits"):
            assert a[s][field] == b[s][field], \
                f"step {s} diverged on {field}"


def test_crash_during_async_checkpoint_previous_survives(tmp_path):
    """Satellite: kill the writer mid-write (io_faults injector).
    The failure surfaces on the training thread as a retryable
    AsyncCheckpointError, the retry loop restores the PREVIOUS
    crc32c-verified checkpoint (nothing torn sits under the failed
    step's name — atomic temp write), and the rerun completes with
    every checkpoint intact."""
    opt = _build_opt()
    opt.set_end_when(max_iteration(12))
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(4))
    # the step-8 model leg dies mid-write; the failure raises at the
    # next submit, the retry restores step 4 and reruns 5..12
    with faults.io_faults("model.8", times=1) as fault:
        opt.optimize()
    assert fault["remaining"] == 0, "injected write failure never fired"
    assert opt.rollbacks >= 1, \
        "async write failure must enter the retry machinery"
    # previous checkpoint survived; the rerun re-committed every step
    for n in (4, 8, 12):
        assert verify_file(str(tmp_path / "ckpt" / f"model.{n}")) is True
    # the walk-back resume lands on an intact step
    opt2 = _build_opt()
    opt2.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(4))
    assert opt2.resume_from_checkpoint() is True
    assert opt2.optim_method.state["neval"] == 13


def test_async_write_failure_raises_without_retry_budget(tmp_path):
    """Without a checkpoint to restore... there IS one here, but with
    retries exhausted the error is the caller's: a writer whose path
    keeps failing surfaces AsyncCheckpointError out of optimize()."""
    opt = _build_opt()
    opt.retry_policy.max_retries = 0
    opt.set_end_when(max_iteration(8))
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(4))
    with faults.io_faults("model.8", times=10):
        with pytest.raises(AsyncCheckpointError):
            opt.optimize()
    assert verify_file(str(tmp_path / "ckpt" / "model.4")) is True
    assert not os.path.exists(tmp_path / "ckpt" / "model.8")


def test_torn_async_checkpoint_quarantined_and_resume_bitwise(tmp_path):
    """Satellite e2e: truncate the newest async-written checkpoint
    (the simulated hard crash the atomic rename cannot cover) — the
    resume quarantines it, walks back to the previous verified step,
    and replays bitwise-identically to a sync-checkpoint run."""
    steps = 12

    # reference: uninterrupted sync-checkpoint run
    opt = _build_opt(async_ckpt=False)
    opt.set_end_when(max_iteration(steps))
    with FlightRecorder(str(tmp_path / "ref.jsonl")) as rec:
        opt.set_flight_recorder(rec)
        opt.optimize()

    # async run checkpointing every 4 steps, then tear the newest leg
    opt = _build_opt()
    opt.set_end_when(max_iteration(steps))
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(4))
    opt.optimize()
    newest = str(tmp_path / "ckpt" / "model.12")
    assert verify_file(newest) is True
    faults.truncate(newest, keep_fraction=0.3)
    assert verify_file(newest) is False

    # fresh process resumes: quarantine + walk back to step 8
    set_global_seed(999)
    opt2 = LocalOptimizer(_regression_model(),
                          array(_regression_samples()),
                          nn.MSECriterion(), batch_size=64)
    opt2.set_optim_method(SGD(learning_rate=0.1))
    opt2.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(4))
    assert opt2.resume_from_checkpoint() is True
    assert os.path.exists(newest + ".corrupt"), "torn file quarantined"
    assert opt2.optim_method.state["neval"] == 9
    opt2.set_end_when(max_iteration(steps))
    with FlightRecorder(str(tmp_path / "replay.jsonl")) as rec:
        opt2.set_flight_recorder(rec)
        opt2.optimize()

    ref = _step_records(str(tmp_path / "ref.jsonl"))
    rep = _step_records(str(tmp_path / "replay.jsonl"))
    assert set(rep) == set(range(9, steps + 1))
    for s in rep:
        for field in ("batch_id", "loss_bits", "grad_norm_bits"):
            assert ref[s][field] == rep[s][field], \
                f"step {s} diverged on {field}"


def test_goodput_ledger_checkpoint_near_zero_and_stall_honest(tmp_path):
    """The tentpole's measurable claim, in-process scale: with async
    checkpointing + the double-buffered infeed, the checkpoint
    category is a sliver of wall clock and data_stall only bills real
    empty-buffer waits (accounted stays ~1.0)."""
    opt = _build_opt(data=array(_regression_samples(n=2048)))
    opt.set_end_when(max_iteration(60))
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(10))
    tm = Telemetry(registry=MetricsRegistry())
    opt.set_telemetry(tm)
    opt.optimize()
    snap = tm.ledger.snapshot()
    assert snap["accounted_fraction"] >= 0.99
    secs = snap["seconds"]
    assert secs["checkpoint"] <= 0.10 * snap["wall_s"], \
        f"checkpoint still on the critical path: {secs}"
    # six checkpoints committed, crc-verified, by the background writer
    for n in (10, 20, 30, 40, 50, 60):
        assert verify_file(str(tmp_path / "ckpt" / f"model.{n}")) is True
    from bigdl_tpu.telemetry import default_registry

    # the infeed counters land in the process default registry (the
    # feed is driver plumbing, not per-run telemetry)
    hits = default_registry().get("bigdl_infeed_buffer_hits_total")
    assert hits is not None and hits.value > 0, \
        "prefetch buffer never served a batch"


def test_preemption_drains_writer_before_resumable_exit(tmp_path):
    """The drain-on-preemption barrier: the SIGTERM path's final
    checkpoint is durable before optimize() returns."""
    fault = faults.PreemptTransformer(at=150)
    opt = _build_opt(fault=fault)
    opt.set_end_when(max_iteration(10))
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(1000))
    opt.set_preemption_handling(True)
    opt.optimize()
    assert fault.fired
    stopped_at = opt.optim_method.state["neval"] - 1
    for leg in ("model", "optimMethod", "trainState"):
        p = str(tmp_path / "ckpt" / f"{leg}.{stopped_at}")
        assert verify_file(p) is True, f"{leg} not durable at exit"


# ---------------------------------------------------------------------------
# bounded-memory regression (the long-run RSS audit)
# ---------------------------------------------------------------------------

def test_longrun_memory_object_counts_plateau():
    """LONGRUN_SUMMARY.json measured 247→581 MB RSS over 150 min; the
    audit found the elastic per-step logs growing without bound and
    this spec keeps every per-step accumulator bounded: drive the
    telemetry spine + elastic context for 2N steps and assert the
    retained-object footprint at 2N matches N (a plateau, not a
    slope)."""
    from bigdl_tpu.resilience import ElasticContext, ElasticCoordinator
    from bigdl_tpu.resilience.elastic import InMemoryKV

    tm = Telemetry(registry=MetricsRegistry())
    ctx = ElasticContext(ElasticCoordinator("host0", InMemoryKV()))
    ctx.telemetry = tm
    ctx.begin_attempt()

    def footprint():
        return (len(tm.tracer.spans())
                + len(tm.step_seconds._samples)
                + len(tm.data_wait_seconds._samples)
                + len(ctx.step_log) + len(ctx.vote_log)
                + len(ctx.recoveries) + len(ctx.shard_history)
                + len(ctx.evicted_hosts) + len(ctx.sdc_detected_steps))

    def pump(n0, n):
        for i in range(n0, n0 + n):
            with tm.tracer.span("train.iteration", "step", step=i) as it:
                with tm.tracer.span("train.data_wait", "data_wait"):
                    tm.on_data_wait(1e-4, step=i)
                tm.on_step(1e-3, records=4, step=i, span=it)
            ctx.step_log.append((0, i, float(i), 1e-3))
            ctx.vote_log.append((i, 1e-4))

    n = 6000
    pump(0, n)
    at_n = footprint()
    pump(n, n)
    at_2n = footprint()
    assert at_2n <= at_n, \
        f"per-step telemetry/elastic state grew {at_n} -> {at_2n}"
    # and the bounds are real, not empty accumulators
    assert len(ctx.step_log) == ctx.step_log.maxlen
    assert len(tm.tracer.spans()) == tm.tracer.capacity
    ctx.close()
