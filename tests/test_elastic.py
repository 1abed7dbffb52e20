"""Elastic multi-host training specs (bigdl_tpu/resilience/elastic.py +
watchdog.py): KV transports, heartbeat/membership/incarnations,
straggler policy, hung-collective watchdog — and the end-to-end chaos
spec: a simulated 4-host cluster (one coordinator per fake host, 8
virtual CPU devices) driven through hang → straggler eviction → host
death → shrink-to-survivors → rejoin → regrow while the loss keeps
descending.  No spec ever waits on a dead collective: every wait is
bounded by a watchdog deadline, heartbeat timeout, or rendezvous
timeout.
"""
import contextlib
import os
import time

import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import Sample, array
from bigdl_tpu.optim import SGD, max_iteration, several_iteration
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.optim.optimizer import LocalOptimizer
from bigdl_tpu.resilience import (CollectiveWatchdog, ElasticContext,
                                  ElasticCoordinator, FileKV,
                                  HostKilledError, HungCollectiveError,
                                  InMemoryKV, MembershipChangedError,
                                  RetryPolicy, SimulatedHost,
                                  StepTimeEstimator, StragglerPolicy,
                                  classify_error, faults,
                                  largest_valid_shards)
from bigdl_tpu.visualization import ElasticSummary, TrainSummary


# ---------------------------------------------------------------------------
# KV transports
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["memory", "file"])
def test_kv_transport_contract(backend, tmp_path):
    kv = (InMemoryKV() if backend == "memory"
          else FileKV(str(tmp_path / "kv")))
    kv.put("hb/host0", "a")
    kv.put("hb/host1", "b")
    kv.put("inc", "c")
    assert kv.get("hb/host0") == "a"
    assert kv.get("missing") is None
    assert kv.keys("hb/") == ["hb/host0", "hb/host1"]
    assert kv.keys() == ["hb/host0", "hb/host1", "inc"]
    kv.put("hb/host0", "a2")  # overwrite
    assert kv.get("hb/host0") == "a2"
    kv.delete("hb/host0")
    assert kv.get("hb/host0") is None
    kv.delete("hb/host0")  # idempotent


def test_file_kv_atomic_and_slash_keys(tmp_path):
    kv = FileKV(str(tmp_path))
    kv.put("ack/3/host1", "1")
    assert kv.keys("ack/3/") == ["ack/3/host1"]
    # no partial tmp files leak into the key namespace
    kv.put("x", "y" * 10000)
    assert all(".tmp." not in k for k in kv.keys())


# ---------------------------------------------------------------------------
# heartbeats + membership
# ---------------------------------------------------------------------------

def test_heartbeat_liveness_with_fake_clock():
    t = [0.0]
    kv = InMemoryKV()
    c = ElasticCoordinator("host0", kv, heartbeat_timeout=1.0,
                           clock=lambda: t[0])
    peer = ElasticCoordinator("host1", kv, heartbeat_timeout=1.0,
                              clock=lambda: t[0])
    c.heartbeat(step=3, step_time=0.1)
    peer.heartbeat(step=2, step_time=0.2)
    assert c.alive() == {"host0", "host1"}
    t[0] = 0.9
    assert c.alive() == {"host0", "host1"}
    t[0] = 1.5  # host beats are now 1.5s old > 1.0s timeout
    assert c.alive() == set()
    c.heartbeat(step=4)
    assert c.alive() == {"host0"}
    assert c.leader_step("host0") == 4
    assert c.leader_step("nobody") == 0


def test_membership_bootstrap_propose_ack_rendezvous():
    kv = InMemoryKV()
    a = ElasticCoordinator("a", kv, heartbeat_timeout=1.0)
    b = ElasticCoordinator("b", kv, heartbeat_timeout=1.0)
    a.bootstrap(["a", "b", "c"])
    b.bootstrap(["x"])  # idempotent: existing incarnation wins
    assert a.membership() == (0, ("a", "b", "c"))

    n = a.propose(["a", "b"], reason="c died", expect=0)
    assert n == 1 and a.membership() == (1, ("a", "b"))
    # a acked its own proposal; b has not yet
    assert a.acked(1) == {"a"}
    # stale expectation loses the race
    assert b.propose(["b"], reason="late", expect=0) is None

    b.ack(1)
    got = a.rendezvous(1, ["a", "b"], timeout=1.0)
    assert got == {"a", "b"}
    # a bounded rendezvous returns the partial ack set, never blocks
    t0 = time.monotonic()
    got = a.rendezvous(1, ["a", "b", "ghost"], timeout=0.2)
    assert got == {"a", "b"}
    assert time.monotonic() - t0 < 2.0


def test_eviction_markers_roundtrip():
    kv = InMemoryKV()
    c = ElasticCoordinator("a", kv)
    c.evict("slow", "chronic straggler")
    assert c.evicted() == {"slow"}
    c.readmit("slow")
    assert c.evicted() == set()


# ---------------------------------------------------------------------------
# shard math
# ---------------------------------------------------------------------------

def test_largest_valid_shards():
    assert largest_valid_shards(4, batch_size=64) == 4
    assert largest_valid_shards(3, batch_size=64) == 2  # 64 % 3 != 0
    assert largest_valid_shards(2, batch_size=64) == 2
    assert largest_valid_shards(1, batch_size=64) == 1
    assert largest_valid_shards(5, batch_size=63) == 3
    assert largest_valid_shards(7, batch_size=64, n_devices=4) == 4
    assert largest_valid_shards(0) == 1  # degenerate: never 0 shards
    assert largest_valid_shards(4) == 4  # no batch constraint


def test_survivor_mesh_uses_first_n_devices():
    import jax

    from bigdl_tpu.parallel.spmd import survivor_mesh

    m = survivor_mesh(2)
    assert m.axis_names == ("data",)
    assert m.shape["data"] == 2
    assert list(np.ravel(m.devices)) == jax.devices()[:2]
    with pytest.raises(ValueError):
        survivor_mesh(len(jax.devices()) + 1)


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_step_time_estimator_median_resists_compile_spike():
    est = StepTimeEstimator(multiplier=4.0, floor=0.5, min_samples=3)
    assert est.deadline() is None       # warming up: no deadline yet
    est.observe(3.0)                    # the compile step
    est.observe(0.02)
    assert est.deadline() is None
    est.observe(0.02)
    # median of [3.0, .02, .02] is .02 — the spike does not stretch it
    assert est.deadline() == pytest.approx(0.5)
    est.observe(1.0)
    est.observe(1.0)
    assert est.deadline() == pytest.approx(4.0)  # genuine slowdown does
    est.reset()
    assert est.deadline() is None
    # the optional warmup cap bounds even the warming (compile) steps
    capped = StepTimeEstimator(min_samples=3, warmup_deadline=20.0)
    assert capped.deadline() == pytest.approx(20.0)


def test_watchdog_trips_and_is_retryable_unavailable():
    wd = CollectiveWatchdog(StepTimeEstimator(min_samples=1, floor=0.05,
                                              multiplier=1.0))
    assert wd.run(lambda cancel: "ok") == "ok"  # warmup ran inline
    t0 = time.monotonic()
    with pytest.raises(HungCollectiveError) as ei:
        wd.run(lambda cancel: cancel.wait(30))  # cooperative hang
    assert time.monotonic() - t0 < 5.0, "the watchdog must bound the wait"
    assert wd.trips == 1
    # the taxonomy contract: retryable, typed UNAVAILABLE
    assert classify_error(ei.value) == "retryable"
    assert ei.value.code == "UNAVAILABLE"
    assert classify_error(MembershipChangedError("x")) == "retryable"
    assert MembershipChangedError("x").code == "UNAVAILABLE"
    # a killed host, by contrast, is fatal for itself
    assert classify_error(HostKilledError("x")) == "fatal"


def test_watchdog_propagates_worker_errors():
    wd = CollectiveWatchdog(StepTimeEstimator(min_samples=1, floor=5.0))
    wd.estimator.observe(0.01)
    with pytest.raises(ZeroDivisionError):
        wd.run(lambda cancel: 1 // 0)
    assert wd.trips == 0


# ---------------------------------------------------------------------------
# straggler policy
# ---------------------------------------------------------------------------

def test_straggler_policy_warn_sustain_and_budget():
    t = [0.0]
    p = StragglerPolicy(skew_threshold=3.0, patience=2, eviction_budget=1,
                        sustain=1.0, clock=lambda: t[0])
    fast = {"a": 0.1, "b": 0.1, "c": 0.1}
    assert p.observe(fast) == {}
    slow = dict(fast, d=1.0)            # 10x the median
    warn = p.observe(slow)
    assert set(warn) == {"d"} and warn["d"] == pytest.approx(10.0)
    assert p.victim() is None           # patience 1/2
    t[0] = 0.5
    p.observe(slow)
    assert p.victim() is None           # patience met, sustain 0.5/1.0s
    t[0] = 1.2
    p.observe(slow)
    assert p.victim() == "d"
    assert p.victim(exclude=("d",)) is None  # never evict the excluded
    p.record_eviction("d")
    # budget spent: a second chronic host is warned about, never voted
    t[0] = 0.0
    slow2 = dict(fast, e=2.0)
    p.observe(slow2); t[0] = 5.0; p.observe(slow2)
    assert "e" in p.warnings
    assert p.victim() is None


def test_straggler_streak_resets_on_recovery():
    t = [0.0]
    p = StragglerPolicy(skew_threshold=3.0, patience=2, sustain=0.0,
                        clock=lambda: t[0])
    fast = {"a": 0.1, "b": 0.1, "c": 0.1}
    p.observe(dict(fast, d=1.0))
    p.observe(fast | {"d": 0.1})        # recovered: streak resets
    p.observe(dict(fast, d=1.0))
    assert p.victim() is None


def test_from_drop_knobs_mapping():
    p = StragglerPolicy.from_drop_knobs(0.25, 0.25, n_hosts=4,
                                        warmup_iteration=200, sustain=0.6)
    assert p.skew_threshold == pytest.approx(4.0)   # 1/0.25
    assert p.eviction_budget == 1                   # round(.25 * 4)
    assert p.patience == 2                          # 200 // 100
    assert p.sustain == pytest.approx(0.6)
    assert StragglerPolicy.from_drop_knobs(0.0, 0.0, 4) is None
    p2 = StragglerPolicy.from_drop_knobs(0.5, 0.5, n_hosts=8)
    assert p2.skew_threshold == pytest.approx(2.0)
    assert p2.eviction_budget == 4


def test_drop_knobs_warn_on_single_host_run(caplog):
    """Satellite: the reference knobs must not silently no-op — a
    single-host run without an elastic coordinator warns loudly."""
    import logging

    samples = [Sample(np.zeros(2, np.float32), 1.0) for _ in range(64)]
    opt = LocalOptimizer(nn.Sequential(nn.Linear(2, 2), nn.LogSoftMax()),
                         array(samples), nn.ClassNLLCriterion(),
                         batch_size=64)
    opt.set_drop_module_property(0.1, 0.2)
    opt.set_end_when(max_iteration(1))
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu"):
        opt.optimize()
    assert any("no straggler to drop" in r.message for r in caplog.records)


def test_drop_knobs_configure_elastic_policy():
    kv = InMemoryKV()
    coord = ElasticCoordinator("host0", kv, heartbeat_timeout=0.5)
    coord.bootstrap(["host0", "host1", "host2", "host3"])
    ctx = ElasticContext(coord)
    samples = [Sample(np.zeros(2, np.float32), 1.0) for _ in range(64)]
    opt = DistriOptimizer(nn.Sequential(nn.Linear(2, 2), nn.LogSoftMax()),
                          array(samples), nn.ClassNLLCriterion(),
                          batch_size=64)
    # both orders work: knobs-then-context and context-then-knobs
    opt.set_elastic(ctx)
    opt.set_drop_module_property(0.25, 0.5, warmup_iteration=300)
    ctx.begin_attempt()
    assert ctx.straggler is not None
    assert ctx.straggler.skew_threshold == pytest.approx(4.0)
    assert ctx.straggler.eviction_budget == 2       # round(.5 * 4)
    assert ctx.straggler.patience == 3              # 300 // 100


# ---------------------------------------------------------------------------
# elastic fault injectors
# ---------------------------------------------------------------------------

def test_kill_and_delay_injectors_fire_deterministically():
    with faults.kill_host("h2", at_step=5) as kill:
        faults.check_elastic_fault("h2", 4)          # too early
        faults.check_elastic_fault("h1", 5)          # wrong host
        assert kill["fired"] == 0
        with pytest.raises(HostKilledError):
            faults.check_elastic_fault("h2", 5)
        assert kill["fired"] == 1
        faults.check_elastic_fault("h2", 6)          # budget spent
    with faults.delay_host("h1", 0.05, at_step=2, times=2) as delay:
        t0 = time.monotonic()
        faults.check_elastic_fault("h1", 2)
        assert time.monotonic() - t0 >= 0.05
        faults.check_elastic_fault("h1", 3)
        faults.check_elastic_fault("h1", 4)          # budget spent: free
        assert delay["fired"] == 2
    faults.check_elastic_fault("h2", 99)             # nothing armed: no-op


def test_hang_injector_honors_watchdog_cancel():
    wd = CollectiveWatchdog(StepTimeEstimator(min_samples=1, floor=0.1,
                                              multiplier=1.0))
    wd.estimator.observe(0.02)
    dispatched = []
    with faults.hang_collective("h0", at_step=1, seconds=60) as hang:
        t0 = time.monotonic()
        with pytest.raises(HungCollectiveError):
            def body(cancel):
                faults.check_elastic_fault("h0", 1, cancel)
                dispatched.append(True)
            wd.run(body)
        assert time.monotonic() - t0 < 5.0
        assert hang["fired"] == 1
    # give the canceled worker a beat to unwind, then check it never
    # reached the dispatch (an abandoned attempt must not run the step)
    time.sleep(0.2)
    assert dispatched == []


# ---------------------------------------------------------------------------
# context membership transitions (no training loop)
# ---------------------------------------------------------------------------

def _ctx(kv, members, host="host0", timeout=0.5, **kw):
    coord = ElasticCoordinator(host, kv, heartbeat_timeout=timeout)
    coord.bootstrap(members)
    ctx = ElasticContext(coord, rendezvous_timeout=0.5,
                         regrow_after_steps=2, **kw)
    ctx.attach(n_devices=8, batch_size=64)
    return ctx


def test_context_detects_death_and_shrinks_then_regrows():
    kv = InMemoryKV()
    ctx = _ctx(kv, ["host0", "host1"], timeout=0.3)
    peer = ElasticCoordinator("host1", kv, heartbeat_timeout=0.3)
    ctx.begin_attempt()
    assert ctx.incarnation == 0
    assert ctx.current_mesh().shape["data"] == 2
    peer.heartbeat(step=1, step_time=0.01)
    ctx.on_step_start(1)  # both alive: no change

    time.sleep(0.4)       # host1's beat goes stale past the timeout
    with pytest.raises(MembershipChangedError):
        ctx.on_step_start(2)
    peer.ack(1)
    ctx.begin_attempt()
    assert ctx.incarnation == 1
    assert ctx.members == ("host0",)
    assert ctx.incarnation_changes == 1
    assert ctx.current_mesh().shape["data"] == 1

    # rejoin: a fresh beat with the rejoin flag regrows at the boundary
    peer.heartbeat(step=2, step_time=0.01, rejoin=True)
    ctx.on_step_start(3)
    with pytest.raises(MembershipChangedError) as ei:
        ctx.on_step_start(4)
    assert "rejoin" in str(ei.value)
    peer.ack(2)
    ctx.begin_attempt()
    assert ctx.members == ("host0", "host1")
    assert ctx.current_mesh().shape["data"] == 2


def test_context_bars_evicted_host_until_readmit():
    kv = InMemoryKV()
    ctx = _ctx(kv, ["host0", "host1"], timeout=5.0)
    peer = ElasticCoordinator("host1", kv, heartbeat_timeout=5.0)
    ctx.begin_attempt()
    ctx.coordinator.evict("host1", "chronic straggler")
    ctx.coordinator.propose(["host0"], "evicted straggler host1",
                            expect=0)
    with pytest.raises(MembershipChangedError):
        ctx.on_step_start(1)
    ctx.begin_attempt()
    assert ctx.members == ("host0",)
    # host1 keeps beating with rejoin=True but stays barred...
    for step in range(2, 6):
        peer.heartbeat(step=step, step_time=0.01, rejoin=True)
        ctx.on_step_start(step)
    # ...until the marker clears
    ctx.coordinator.readmit("host1")
    peer.heartbeat(step=6, step_time=0.01, rejoin=True)
    with pytest.raises(MembershipChangedError):
        ctx.on_step_start(6)


# ---------------------------------------------------------------------------
# the chaos e2e
# ---------------------------------------------------------------------------

def _regression_samples(n=256, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 4).astype(np.float32)
    w = np.array([[1.5], [-2.0], [0.5], [3.0]], np.float32)
    y = (x @ w + 0.7).astype(np.float32)
    return [Sample(x[i], y[i]) for i in range(n)]


@pytest.mark.parametrize("steps,hang_at,slow,die_at,back_at",
                         [(56, 8, 3.0, 26, 38), (20, None, None, 6, 14)])
def test_elastic_chaos_end_to_end(tmp_path, steps, hang_at, slow, die_at,
                                  back_at):
    """The acceptance spec: a simulated 4-host cluster (FileKV — the
    file/dir transport carries the real protocol), one coordinator per
    fake host, driven through

    * a hung collective on the driver host (step 8) — the watchdog
      classifies it retryable-UNAVAILABLE within its deadline,
    * one chronic straggler (host3, ~60x skew) — warned, then voted out
      within the drop knobs' budget,
    * a host death (host2 at step 20) — detected by heartbeat timeout,
      survivors shrink to the largest valid shard count,
    * rejoin of both (leader step 34) — regrow at the boundary,

    while training resumes each time from the verified checkpoint and
    the loss keeps descending across every incarnation boundary."""
    t_start = time.monotonic()
    kv = FileKV(str(tmp_path / "kv"))
    hosts = ["host0", "host1", "host2", "host3"]
    coord = ElasticCoordinator("host0", kv, heartbeat_timeout=0.3)
    coord.bootstrap(hosts)
    # schedule with clean windows between events: the hang (step 8)
    # resets the straggler sustain window, so the eviction lands around
    # step ~22; host2's death (leader step 26) and the rejoins (38) each
    # get their own incarnation rather than merging into one
    sims = [
        SimulatedHost("host1", kv, heartbeat_timeout=0.3),
        SimulatedHost("host2", kv, heartbeat_timeout=0.3,
                      die_at_leader_step=die_at,
                      rejoin_at_leader_step=back_at),
        SimulatedHost("host3", kv, heartbeat_timeout=0.3,
                      step_time=slow, readmit_at_leader_step=back_at),
    ]
    summary = ElasticSummary(str(tmp_path / "logs"), "chaos")
    ts = TrainSummary(str(tmp_path / "logs"), "chaos")
    ctx = ElasticContext(
        coord, summary=summary,
        watchdog=CollectiveWatchdog(StepTimeEstimator(
            floor=0.75, multiplier=4.0, min_samples=3,
            warmup_deadline=15.0)),
        rendezvous_timeout=3.0, regrow_after_steps=4)

    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = DistriOptimizer(model, array(_regression_samples()),
                          nn.MSECriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=0.3))
    opt.set_end_when(max_iteration(steps))
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(1))
    opt.set_retry_policy(RetryPolicy(max_retries=20, backoff_base=0.01,
                                     backoff_max=0.05))
    opt.set_drop_module_property(0.25, 0.25, warmup_iteration=200)
    opt.set_elastic(ctx)
    opt.set_train_summary(ts)

    # pace the driver to ~50ms/step (delay_host on the real host) so
    # heartbeat staleness and sustained-skew windows are meaningful
    hang_cm = faults.hang_collective("host0", at_step=hang_at, seconds=30) \
        if hang_at else contextlib.nullcontext({"fired": 0})
    with hang_cm as hang, \
         faults.delay_host("host0", 0.05, at_step=1) as pace:
        for s in sims:
            s.start()
        try:
            opt.optimize()
        finally:
            for s in sims:
                s.stop()
    elapsed = time.monotonic() - t_start
    assert elapsed < 120, f"chaos run must stay bounded, took {elapsed:.0f}s"
    assert hang["fired"] == bool(hang_at)
    assert pace["fired"] > 10

    # --- membership story ------------------------------------------------
    c = ctx.counters()
    assert c["incarnation_changes"] >= (3 if slow else 1), c
    assert c["watchdog_trips"] >= bool(hang_at), c
    assert c["evictions"] >= bool(slow), c
    assert ("host3" in c["evicted_hosts"]) == bool(slow), c
    assert "host2" not in c["evicted_hosts"], \
        "a dead host is the death path's business, not an eviction"
    assert c["recoveries_s"] and max(c["recoveries_s"]) < 30, c
    # shrink-to-survivors reached 2 shards; regrow restored 4
    assert min(c["shard_history"]) == 2, c
    assert c["shard_history"][0] == 4 and c["shard_history"][-1] == 4, c
    assert set(c["members"]) == set(hosts), "everyone back after regrow"

    # --- ElasticSummary reports the acceptance counters ------------------
    incs = summary.read_scalar("Incarnation")
    assert len({v for _, v in incs}) >= 2        # >= 1 incarnation change
    if slow:    # the small case has no hang and no straggler
        for tag in ("Evictions", "WatchdogTrips"):
            assert [v for _, v in summary.read_scalar(tag)][-1] >= 1
        assert summary.read_scalar("StragglerSkew")
    assert summary.read_scalar("RecoverySeconds")

    # --- the training contract -------------------------------------------
    assert opt.optim_method.state["neval"] - 1 == steps, "run must complete"
    losses = ts.read_scalar("Loss")
    first = np.mean([v for _, v in losses[:3]])
    last = np.mean([v for _, v in losses[-3:]])
    assert last < first, (first, last)
    # strictly decreasing ACROSS the incarnation boundaries: the loss
    # after the final recovery sits below the loss just before the
    # first membership change
    first_change_step = int(incs[1][0])
    before = [v for s, v in losses if s < first_change_step]
    assert losses[-1][1] < min(before[:3]), (before[:3], losses[-1])
    summary.close()
    ts.close()
