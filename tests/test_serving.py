"""Hardened serving-path specs (bigdl_tpu/serving/): micro-batch
bucketing, deadline expiry, queue-full shedding, breaker
trip/half-open/recovery, SIGTERM drain, hot-swap canary rollback, and
the 200-request chaos e2e — all driven by the deterministic serving
fault injectors in resilience.faults, all on the CPU backend.
"""
import os
import signal
import threading
import time
from collections import Counter

import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.resilience import faults
from bigdl_tpu.resilience.retry import FatalTrainingError, RetryPolicy
from bigdl_tpu.serving import (CircuitBreaker, InferenceServer,
                               MicroBatcher, ServingMetrics, Status)
from bigdl_tpu.serving.batcher import bucket_ladder
from bigdl_tpu.serving.breaker import CLOSED, HALF_OPEN, OPEN
from bigdl_tpu.serving.swap import SwapRejected


def small_model():
    return nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 3),
                         nn.LogSoftMax())


def feat(rng):
    return rng.rand(4).astype(np.float32)


@pytest.fixture
def server():
    srv = InferenceServer(small_model(), max_batch=8, max_queue=32,
                          breaker=CircuitBreaker(failure_threshold=3,
                                                 reset_timeout=0.2))
    srv.start()
    yield srv
    srv.stop(timeout=10)


# ---------------------------------------------------------------------------
# batcher / breaker units
# ---------------------------------------------------------------------------

def test_bucket_ladder_and_coalesce():
    assert bucket_ladder(32) == [1, 2, 4, 8, 16, 32]
    assert bucket_ladder(20) == [1, 2, 4, 8, 16, 20]
    assert bucket_ladder(8, multiple=8) == [8]
    b = MicroBatcher(8)
    x, bucket = b.coalesce([np.full(3, i, np.float32) for i in range(5)])
    assert bucket == 8 and x.shape == (8, 3)
    # pad rows repeat the last record (numerically valid padding)
    np.testing.assert_array_equal(x[5], x[4])
    assert b.buckets_dispatched == {8}
    with pytest.raises(ValueError):
        b.bucket_for(9)


def test_breaker_trip_halfopen_recovery_cycle():
    clock = [0.0]
    br = CircuitBreaker(failure_threshold=2, reset_timeout=5.0,
                        clock=lambda: clock[0])
    assert br.acquire() == "admit"
    br.record_failure()
    assert br.state == CLOSED          # below threshold
    br.record_failure()
    assert br.state == OPEN and br.trips == 1
    assert br.acquire() == "reject"    # open: reject fast
    clock[0] = 6.0
    assert br.acquire() == "probe"     # timeout elapsed: one probe
    assert br.state == HALF_OPEN
    assert br.acquire() == "reject"    # only ONE probe at a time
    br.record_failure()                # probe failed -> re-open
    assert br.state == OPEN and br.trips == 2
    clock[0] = 12.0
    assert br.acquire() == "probe"
    br.record_success()                # probe succeeded -> closed
    assert br.state == CLOSED and br.recoveries == 1
    assert br.acquire() == "admit"


def test_breaker_fatal_trips_immediately():
    br = CircuitBreaker(failure_threshold=100, reset_timeout=5.0)
    br.record_failure(fatal=True)
    assert br.state == OPEN and br.trips == 1


# ---------------------------------------------------------------------------
# request path
# ---------------------------------------------------------------------------

def test_serves_and_matches_direct_forward(server):
    rng = np.random.RandomState(0)
    xs = [feat(rng) for _ in range(20)]
    res = [f.result(timeout=60)
           for f in [server.submit(x) for x in xs]]
    assert all(r.ok for r in res)
    model = server.model
    direct = np.asarray(model.forward(np.stack(xs)))
    np.testing.assert_allclose(np.stack([r.output for r in res]),
                               direct, atol=1e-6)
    assert all(r.latency_s >= r.queued_s >= 0 for r in res)
    assert server.metrics.snapshot()["served_ok"] == 20


def test_mismatched_feature_shape_rejected_at_admission(server):
    rng = np.random.RandomState(0)
    server.submit(feat(rng)).result(timeout=60)
    with pytest.raises(ValueError, match="pinned shape"):
        server.submit(rng.rand(5).astype(np.float32))


def test_deadline_expired_on_arrival_and_in_queue(server):
    rng = np.random.RandomState(0)
    # expired on arrival: typed rejection, no queue time burned
    r = server.submit(feat(rng), deadline_s=0.0).result(timeout=5)
    assert r.status is Status.DEADLINE_EXCEEDED
    # expires while queued behind an injected-slow batch
    with faults.serving_step_latency(0.3, times=2):
        server.submit(feat(rng))                     # occupies the step
        time.sleep(0.05)  # let the worker take it before the doomed one
        doomed = server.submit(feat(rng), deadline_s=0.05)
        assert doomed.result(timeout=30).status is Status.DEADLINE_EXCEEDED
    assert server.metrics.snapshot()["deadline_exceeded"] == 2


def test_expired_budget_fast_fails_without_queueing(server):
    """A request whose remaining budget is <= 0 (the fleet router's
    failover-retry case) resolves DEADLINE_EXCEEDED synchronously —
    it never occupies a queue slot or a batch slot."""
    rng = np.random.RandomState(0)
    for budget in (0.0, -1.0):
        fut = server.submit(feat(rng), deadline_s=budget)
        assert fut.done()                     # resolved before return
        r = fut.result(timeout=0)
        assert r.status is Status.DEADLINE_EXCEEDED
        assert "budget" in r.error
    snap = server.metrics.snapshot()
    assert snap["deadline_exceeded"] == 2
    assert snap["batches"] == 0               # nothing hit the device
    # queue-depth histogram saw no admission from the dead requests
    assert snap["queue_depth_max"] == 0


def test_expired_budget_fast_fails_generate_path():
    from bigdl_tpu.models.transformer import TransformerLM

    lm = TransformerLM(61, embed_dim=16, num_heads=2, num_layers=1,
                       max_len=32, output="logits")
    srv = InferenceServer(lm, max_batch=4)
    srv.start()
    try:
        rng = np.random.RandomState(0)
        prompt = rng.randint(1, 61, 6).astype(np.int32)
        fut = srv.submit_generate(prompt, max_new=4, deadline_s=-0.5)
        assert fut.done()
        assert fut.result(0).status is Status.DEADLINE_EXCEEDED
        assert srv.metrics.snapshot()["batches"] == 0
    finally:
        srv.stop(timeout=10)


@pytest.mark.parametrize("max_batch,max_queue,n", [(4, 4, 40), (8, 16, 48)])
def test_queue_full_sheds_with_typed_overloaded(max_batch, max_queue, n):
    srv = InferenceServer(small_model(), max_batch=max_batch,
                          max_queue=max_queue)
    srv.start()
    try:
        rng = np.random.RandomState(0)
        with faults.serving_step_latency(0.25, times=4):
            futs = [srv.submit(feat(rng)) for _ in range(n)]
            res = [f.result(timeout=60) for f in futs]
        by = Counter(r.status for r in res)
        assert by[Status.OVERLOADED] > 0       # shed, not queued forever
        assert by[Status.OK] > 0               # admitted ones served
        assert by[Status.OK] + by[Status.OVERLOADED] == n
        snap = srv.metrics.snapshot()
        assert snap["shed"] == by[Status.OVERLOADED]  # counted, not silent
        assert snap["shed_rate"] == pytest.approx(by[Status.OVERLOADED] / n)
        assert snap["total"] == n == snap["served_ok"] + snap["shed"] \
            + snap["deadline_exceeded"] + snap["internal_error"]
        assert srv.drain(timeout=30)
    finally:
        srv.stop(timeout=10)


def test_breaker_trips_degrades_and_recovers(server):
    rng = np.random.RandomState(0)
    # 3 consecutive failing batches trip the breaker (sequential
    # submits so each forms its own batch)
    with faults.serving_step_failures(times=3):
        for _ in range(3):
            r = server.submit(feat(rng)).result(timeout=30)
            assert r.status is Status.INTERNAL_ERROR
            assert "injected serving step failure" in r.error
    assert server.breaker.state == OPEN
    assert server.breaker.trips == 1
    # while open: fast typed rejection, no crash
    r = server.submit(feat(rng)).result(timeout=30)
    assert r.status is Status.UNAVAILABLE and "breaker" in r.error
    assert not server.ready() and server.healthy()
    # after the reset timeout the half-open probe admits one request
    # and its success closes the breaker
    time.sleep(server.breaker.reset_timeout + 0.05)
    r = server.submit(feat(rng)).result(timeout=30)
    assert r.status is Status.OK
    assert server.breaker.state == CLOSED
    assert server.breaker.recoveries == 1
    assert server.ready()


def test_fatal_error_trips_breaker_immediately(server):
    rng = np.random.RandomState(0)
    with faults.serving_step_failures(times=1,
                                      exc_type=FatalTrainingError):
        r = server.submit(feat(rng)).result(timeout=30)
    assert r.status is Status.INTERNAL_ERROR
    assert server.breaker.state == OPEN and server.breaker.trips == 1


def test_halfopen_probe_failure_reopens(server):
    rng = np.random.RandomState(0)
    with faults.serving_step_failures(times=4):
        for _ in range(3):
            server.submit(feat(rng)).result(timeout=30)
        assert server.breaker.state == OPEN
        time.sleep(server.breaker.reset_timeout + 0.05)
        r = server.submit(feat(rng)).result(timeout=30)  # probe fails
        assert r.status is Status.INTERNAL_ERROR
    assert server.breaker.state == OPEN and server.breaker.trips == 2


# ---------------------------------------------------------------------------
# drain / stop / preemption
# ---------------------------------------------------------------------------

def test_sigterm_drains_admitted_and_stops_admission():
    srv = InferenceServer(small_model(), max_batch=4, max_queue=64)
    srv.start(install_signal_handler=True)
    rng = np.random.RandomState(0)
    try:
        with faults.serving_step_latency(0.1, times=3):
            admitted = [srv.submit(feat(rng)) for _ in range(10)]
            os.kill(os.getpid(), signal.SIGTERM)
            time.sleep(0.02)  # let the handler run before the late submit
            late = srv.submit(feat(rng))
        # admission closed the moment the signal landed
        assert late.result(timeout=5).status is Status.UNAVAILABLE
        # ...but everything already admitted completes (drain finishes
        # in-flight work; nothing cancelled, nothing hung)
        res = [f.result(timeout=60) for f in admitted]
        assert all(r.ok for r in res)
        assert srv.drain(timeout=10)
        assert not srv.healthy()
    finally:
        srv.stop(timeout=10)


def test_hard_stop_cancels_queued_requests():
    srv = InferenceServer(small_model(), max_batch=2, max_queue=64)
    srv.start()
    rng = np.random.RandomState(0)
    with faults.serving_step_latency(0.3, times=2):
        futs = [srv.submit(feat(rng)) for _ in range(20)]
        assert srv.stop(timeout=30)
    res = [f.result(timeout=10) for f in futs]   # nobody hangs
    by = Counter(r.status for r in res)
    assert by[Status.CANCELLED] > 0
    assert set(by) <= {Status.OK, Status.CANCELLED}
    assert srv.metrics.snapshot()["cancelled"] == by[Status.CANCELLED]


def test_health_and_readiness_probes(server):
    assert server.healthy() and server.ready()
    h = server.health()
    assert h["healthy"] and h["ready"] and not h["draining"]
    assert h["breaker"]["state"] == CLOSED
    server.drain(timeout=10)
    assert not server.healthy()


# ---------------------------------------------------------------------------
# hot swap
# ---------------------------------------------------------------------------

def test_hot_swap_changes_outputs_atomically(server):
    rng = np.random.RandomState(0)
    x = feat(rng)
    before = server.submit(x).result(timeout=60).output
    twin = small_model()  # different init -> different params
    assert server.swap_params(params=twin.param_tree())
    after = server.submit(x).result(timeout=60).output
    np.testing.assert_allclose(
        after, np.asarray(twin.forward(x[None]))[0], atol=1e-6)
    assert not np.allclose(before, after)
    assert server.metrics.swaps == 1


def test_hot_swap_canary_rejects_poisoned_params(server):
    rng = np.random.RandomState(0)
    x = feat(rng)
    before = server.submit(x).result(timeout=60).output
    with pytest.raises(SwapRejected, match="non-finite"):
        server.swap_params(
            params=faults.poison_params(server.model.param_tree()))
    # rolled back: the old params still serve, traffic unaffected
    after = server.submit(x).result(timeout=60)
    assert after.ok
    np.testing.assert_allclose(after.output, before, atol=1e-6)
    assert server.metrics.swap_rollbacks == 1


def test_hot_swap_from_verified_checkpoint(tmp_path, server):
    from bigdl_tpu.utils import file_io

    rng = np.random.RandomState(0)
    x = feat(rng)
    server.submit(x).result(timeout=60)
    twin = small_model()
    good = str(tmp_path / "model.1")
    file_io.save(twin, good, atomic=True, checksum=True)
    assert server.swap_params(path=good)
    got = server.submit(x).result(timeout=60).output
    np.testing.assert_allclose(
        got, np.asarray(twin.forward(x[None]))[0], atol=1e-6)
    # corrupt checkpoint: crc32c refuses it, file quarantined, params keep
    bad = str(tmp_path / "model.2")
    file_io.save(twin, bad, atomic=True, checksum=True)
    faults.bit_flip(bad)
    with pytest.raises(SwapRejected, match="crc32c"):
        server.swap_params(path=bad)
    assert os.path.exists(bad + ".corrupt")
    assert server.submit(x).result(timeout=60).ok


# ---------------------------------------------------------------------------
# generation path
# ---------------------------------------------------------------------------

def test_generate_microbatch_matches_library_decode():
    from bigdl_tpu.models.generate import make_generate
    from bigdl_tpu.models.transformer import TransformerLM

    lm = TransformerLM(61, embed_dim=16, num_heads=2, num_layers=1,
                       max_len=32, output="logits")
    srv = InferenceServer(lm, max_batch=4, batch_window_s=0.05)
    srv.start()
    try:
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 61, 6).astype(np.int32)
                   for _ in range(5)]
        futs = [srv.submit_generate(p, max_new=4) for p in prompts]
        res = [f.result(timeout=180) for f in futs]
        assert all(r.ok for r in res)
        ref = np.asarray(make_generate(lm)(
            lm.param_tree(), np.stack(prompts), 4))[:, 6:]
        np.testing.assert_array_equal(
            np.stack([r.output for r in res]), ref)
        with pytest.raises(ValueError):
            srv.submit_generate(prompts[0][None], max_new=4)  # 2-D
        with pytest.raises(ValueError):
            srv.submit_generate(prompts[0], max_new=0)
    finally:
        srv.stop(timeout=10)


def test_a_new_generate_shape_compiles_its_whole_ladder_ahead():
    """The first batch of a (prompt length, max_new) starts the compile
    of every bucket of the ladder on the server's pool — tracing under
    one lock, XLA's compiles beside each other — and each bucket's
    first batch runs what was built for it: the tokens are the library
    decode's, whichever way the program was compiled."""
    from bigdl_tpu.models.generate import cached_generate, make_generate
    from bigdl_tpu.models.transformer import TransformerLM

    lm = TransformerLM(61, embed_dim=16, num_heads=2, num_layers=1,
                       max_len=32, output="logits")
    srv = InferenceServer(lm, max_batch=4, batch_window_s=0.05).start()
    try:
        rng = np.random.RandomState(1)
        first = rng.randint(1, 61, 6).astype(np.int32)
        assert srv.submit_generate(first, max_new=4).result(180).ok
        ahead = cached_generate(lm).ahead
        assert set(ahead) == {(1, 6, 4), (2, 6, 4), (4, 6, 4)}
        assert srv._compile_pool is not None
        prompts = [rng.randint(1, 61, 6).astype(np.int32)
                   for _ in range(4)]
        res = [f.result(timeout=180) for f in
               [srv.submit_generate(p, max_new=4) for p in prompts]]
        assert all(r.ok for r in res)
        assert all(f.done() and f.exception() is None
                   for f in ahead.values())
        np.testing.assert_array_equal(
            np.stack([r.output for r in res]),
            np.asarray(make_generate(lm)(lm.param_tree(),
                                         np.stack(prompts), 4))[:, 6:])
        # another max_new is another ladder
        assert srv.submit_generate(first, max_new=3).result(180).ok
        assert {(1, 6, 3), (2, 6, 3), (4, 6, 3)} <= set(ahead)
    finally:
        srv.stop(timeout=10)
    assert srv._compile_pool is None


def test_a_running_server_keeps_the_settled_heap_from_the_collector(
        monkeypatch):
    """``gc.freeze`` at ``start`` and after a batch that compiled — not
    after a warm one — so a full collection inside the serving loop
    walks what the loop made and not the process; ``stop`` gives the
    heap back."""
    import gc

    from bigdl_tpu.models.transformer import TransformerLM

    calls, real = [], InferenceServer._settle_heap
    monkeypatch.setattr(InferenceServer, "_settle_heap",
                        staticmethod(lambda: (calls.append(1), real())))
    gc.unfreeze()
    lm = TransformerLM(61, embed_dim=16, num_heads=2, num_layers=1,
                       max_len=32, output="logits")
    srv = InferenceServer(lm, max_batch=2, batch_window_s=0.05).start()
    try:
        assert len(calls) == 1
        assert gc.get_freeze_count() > 10_000   # jax and the model
        prompt = np.arange(1, 7).astype(np.int32)
        assert srv.submit_generate(prompt, max_new=3).result(180).ok
        assert len(calls) == 2                  # the batch compiled
        assert srv.submit_generate(prompt, max_new=3).result(180).ok
        assert len(calls) == 2                  # a warm batch
    finally:
        srv.stop(timeout=10)
    assert gc.get_freeze_count() == 0


def test_compile_ahead_is_the_program_the_call_would_build():
    """``generate.compile_ahead``: the executable serves greedy calls of
    its shape only; a sampled call, another shape and a pool shut down
    before the compile began all go the jitted way, same tokens."""
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from bigdl_tpu.models.generate import make_generate
    from bigdl_tpu.models.transformer import TransformerLM

    lm = TransformerLM(61, embed_dim=16, num_heads=2, num_layers=1,
                       max_len=32, output="logits")
    p = lm.param_tree()
    ids = np.random.RandomState(2).randint(1, 61, (2, 5)).astype(np.int32)
    want = np.asarray(make_generate(lm)(p, ids, 4))
    gen = make_generate(lm)
    with ThreadPoolExecutor(1) as pool:
        gen.compile_ahead(p, 2, 5, 4, pool)
        gen.compile_ahead(p, 2, 5, 4, pool)     # asked twice, built once
        assert list(gen.ahead) == [(2, 5, 4)]
        np.testing.assert_array_equal(np.asarray(gen(p, ids, 4)), want)
    assert gen.ahead[(2, 5, 4)].done()
    np.testing.assert_array_equal(np.asarray(gen(p, ids[:1], 4)), want[:1])
    assert gen(p, ids, 4, rng=jax.random.PRNGKey(0),
               temperature=0.7).shape == want.shape
    # a parameter tree of another dtype than the executable was lowered
    # for (a swap): the jitted way, and the executable is let go
    import jax.numpy as jnp

    p16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p)
    assert np.asarray(gen(p16, ids, 4)).shape == want.shape
    assert (2, 5, 4) not in gen.ahead
    # a pool shut down before the compile's turn (a server stopped
    # while its ladder was queued): the call compiles by itself
    gate, pool = threading.Event(), ThreadPoolExecutor(1)
    pool.submit(gate.wait)
    gen2 = make_generate(lm)
    gen2.compile_ahead(p, 2, 5, 4, pool)
    pool.shutdown(wait=False, cancel_futures=True)
    gate.set()
    assert gen2.ahead[(2, 5, 4)].cancelled()
    np.testing.assert_array_equal(np.asarray(gen2(p, ids, 4)), want)
    assert not gen2.ahead


def test_a_ladder_is_asked_for_this_batch_first_then_most_groups_first(
        monkeypatch):
    """``_compile_ladder``: programs are lowered one at a time in the
    order they are asked for, and one whose prompt pass goes in groups
    of rows takes longest to build or load — after the batch's own, the
    programs with the most groups are asked for first, so that they
    build while the others are still traced; the rest smallest first,
    as ever (PR 48)."""
    from bigdl_tpu.models import generate as G
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import InferenceServer

    class Gen:
        asked = []

        def compile_ahead(self, params, b, prompt_len, max_new, pool):
            self.asked.append((b, prompt_len, max_new))

    lm = TransformerLM(61, embed_dim=16, num_heads=2, num_layers=1,
                       max_len=32, output="logits")
    srv = InferenceServer(lm, max_batch=16)
    assert list(srv.batcher.ladder) == [1, 2, 4, 8, 16]

    def asked(first):
        Gen.asked = []
        srv._compile_ladder(Gen(), None, ("gen", first, 5, 4))
        assert all(shape[1:] == (5, 4) for shape in Gen.asked)
        return [shape[0] for shape in Gen.asked]

    # every prompt goes whole: this batch's, then smallest first
    assert asked(1) == [1, 2, 4, 8, 16] and asked(4) == [4, 1, 2, 8, 16]
    # 20 tokens a pass: 16 rows x 5 go in 4 groups, 8 rows in 2
    monkeypatch.setattr(G, "PREFILL_TOKENS", 20)
    assert [G.prefill_groups(b, 5) for b in (4, 8, 16)] == [1, 2, 4]
    assert asked(1) == [1, 16, 8, 2, 4] and asked(8) == [8, 16, 1, 2, 4]
    srv._compile_pool.shutdown()


@pytest.mark.parametrize("traffic,grouped", [
    ("closed128_p4608_n256_b32", [32, 16]),     # SmallThinker: 4 and 2
    ("closed256_p256_n128_b64", []),
    ("closed32_p2048_n8", []),
    ("closed512_p128_n128_b128", []),
    ("closed512_p128_n256_b256", []),
    ("closed512_p128_n256_b256_mhc", []),
    ("closed512_p128_n512_b256", []),
    ("closed64_p128_n96", []),
    ("open_p128_n96", []),
])
def test_the_ladder_of_each_served_cell(traffic, grouped):
    """The order each benchmark cell's ladder is asked for in, from the
    cell's own ``max_batch`` and prompt: only a prompt pass that goes in
    groups of rows moves a program ahead — SmallThinker's 32- and 16-row
    programs — and every other cell's ladder is asked for as before
    PR 48, this batch's bucket and then smallest first."""
    import json
    import os

    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import InferenceServer

    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                           "traffic", traffic + ".json")) as fh:
        cell = json.load(fh)
    asked = []

    class Gen:
        def compile_ahead(self, params, b, prompt_len, max_new, pool):
            asked.append(b)

    lm = TransformerLM(61, embed_dim=16, num_heads=2, num_layers=1,
                       max_len=32, output="logits")
    srv = InferenceServer(lm, max_batch=cell["max_batch"])
    ladder = list(srv.batcher.ladder)
    assert ladder[-1] == cell["max_batch"]
    srv._compile_ladder(Gen(), None, ("gen", 1, cell["prompt_len"],
                                      cell["max_new"]))
    assert asked == [1] + grouped + [b for b in ladder[1:]
                                     if b not in grouped]
    srv._compile_pool.shutdown()


# ---------------------------------------------------------------------------
# metrics export
# ---------------------------------------------------------------------------

def test_metrics_export_through_summary(tmp_path, server):
    from bigdl_tpu.visualization import ServingSummary
    from bigdl_tpu.visualization.summary import read_scalars

    rng = np.random.RandomState(0)
    [f.result(timeout=60) for f in
     [server.submit(feat(rng)) for _ in range(8)]]
    summary = ServingSummary(str(tmp_path), "app")
    server.metrics.to_summary(summary, step=1)
    summary.close()
    got = read_scalars(summary.log_dir, "serving/served_ok")
    assert got == [(1, 8.0)]
    p50 = read_scalars(summary.log_dir, "serving/latency_p50_s")
    assert p50 and p50[0][1] > 0


def test_metrics_quantiles_and_counts():
    m = ServingMetrics(window=100)
    for i in range(100):
        m.record(Status.OK, latency_s=(i + 1) / 100.0,
                 queued_s=0.001)
    m.record(Status.OVERLOADED)
    m.record(Status.DEADLINE_EXCEEDED)
    snap = m.snapshot()
    assert snap["served_ok"] == 100 and snap["total"] == 102
    assert 0.45 < snap["latency_p50_s"] < 0.56
    assert snap["latency_p99_s"] > 0.9
    assert snap["shed"] == 1 and snap["deadline_exceeded"] == 1


def test_metrics_swap_and_hedge_counters_in_prometheus():
    """The swap-outcome and hedge counters are registry-backed so the
    scraped exposition (and the fleet's cross-replica fold) carries
    them, not just python attributes."""
    m = ServingMetrics()
    m.record_swap(installed=True)
    m.record_swap(installed=False)
    m.record_swap(installed=False)
    m.record_hedge()                   # fired
    m.record_hedge(won=True)
    m.record_retry()
    assert m.swaps == 1 and m.swap_rollbacks == 2
    assert m.hedges_fired == 1 and m.hedges_won == 1
    assert m.retries == 1
    snap = m.snapshot()
    assert snap["swaps"] == 1 and snap["swap_rollbacks"] == 2
    assert snap["hedges_fired"] == 1 and snap["hedges_won"] == 1
    assert snap["retries"] == 1
    text = m.to_prometheus()
    assert 'bigdl_serving_swaps_total{outcome="installed"} 1.0' in text
    assert 'bigdl_serving_swaps_total{outcome="rejected"} 2.0' in text
    assert 'bigdl_serving_hedges_total{event="fired"} 1.0' in text
    assert 'bigdl_serving_hedges_total{event="won"} 1.0' in text
    assert "bigdl_serving_retries_total 1.0" in text


# ---------------------------------------------------------------------------
# the chaos e2e (acceptance): >=200 concurrent requests, injected step
# failures, a SIGTERM mid-flight — nothing hangs, the breaker trips AND
# recovers, drain completes all admitted work, and the batch path
# compiled at most once per bucket shape.
# ---------------------------------------------------------------------------

def test_e2e_200_concurrent_requests_chaos():
    import threading

    srv = InferenceServer(
        small_model(), max_batch=8, max_queue=512,
        breaker=CircuitBreaker(failure_threshold=2, reset_timeout=0.05))
    srv.start(install_signal_handler=True)
    rng = np.random.RandomState(0)
    N = 240
    futs = [None] * N
    errs = []

    def client(lo, hi, seed):
        r = np.random.RandomState(seed)
        try:
            for i in range(lo, hi):
                futs[i] = srv.submit(r.rand(4).astype(np.float32),
                                     deadline_s=30.0)
                time.sleep(0.002)  # spread the flood across the chaos
        except Exception as e:  # pragma: no cover - fail the test below
            errs.append(e)

    threads = [threading.Thread(target=client,
                                args=(k * 30, (k + 1) * 30, k))
               for k in range(N // 30)]
    for t in threads:
        t.start()
    time.sleep(0.05)  # let clean traffic flow first
    # mid-flood failure burst: consecutive failing batches trip the
    # 2-threshold breaker; once the injected budget is spent the
    # half-open probe succeeds and the breaker recovers — all while
    # the clients are still submitting
    with faults.serving_step_failures(times=3) as burst:
        # trickle traffic so the half-open probes have something to
        # test recovery on once the flood has flushed
        deadline = time.time() + 30
        while burst["fired"] < 3 and time.time() < deadline:
            srv.submit(feat(rng), deadline_s=5.0)
            time.sleep(0.01)
        assert burst["fired"] >= 3
        assert srv.breaker.trips >= 1
        deadline = time.time() + 30
        while srv.breaker.state != CLOSED and time.time() < deadline:
            srv.submit(feat(rng), deadline_s=5.0)
            time.sleep(0.01)
    for t in threads:
        t.join(timeout=60)
    assert not errs
    # everything admitted resolves (typed, never hung)
    res = [f.result(timeout=120) for f in futs]
    assert srv.breaker.state == CLOSED
    assert srv.breaker.recoveries >= 1
    late_ok = srv.submit(feat(rng)).result(timeout=30)
    assert late_ok.ok

    # SIGTERM with work still queued: admission stops, admitted work
    # completes, worker exits clean
    with faults.serving_step_latency(0.05, times=2):
        tail = [srv.submit(feat(rng)) for _ in range(20)]
        os.kill(os.getpid(), signal.SIGTERM)
    tail_res = [f.result(timeout=60) for f in tail]
    assert all(r.status in (Status.OK, Status.UNAVAILABLE)
               for r in tail_res)
    assert any(r.ok for r in tail_res)
    assert srv.drain(timeout=30)
    post = srv.submit(feat(rng)).result(timeout=5)
    assert post.status is Status.UNAVAILABLE

    # no silent outcomes: every one of the N requests is typed
    by = Counter(r.status for r in res)
    assert sum(by.values()) == N
    assert set(by) <= {Status.OK, Status.INTERNAL_ERROR,
                       Status.UNAVAILABLE, Status.OVERLOADED,
                       Status.DEADLINE_EXCEEDED}
    assert by[Status.OK] > 0
    assert by[Status.INTERNAL_ERROR] > 0      # the injected bursts

    # static-shape contract: at most one executable per dispatched
    # bucket (the jit cache saw only ladder shapes)
    stats = srv.compile_stats()
    assert stats["jit_cache_size"] is not None
    assert 0 < stats["jit_cache_size"] <= len(
        stats["buckets_dispatched"])
