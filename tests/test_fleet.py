"""Serving-fleet specs (bigdl_tpu/serving/fleet.py + router.py):
replica membership over the elastic KV transport (heartbeats, health
snapshots, incarnation-bumped eject/readmit), health-aware failover
routing with deadline-budget retries and tail-latency hedging,
fleet-wide rolling verified deploys with quorum + rollback, and the
chaos e2e — a 4-replica fleet absorbing a replica kill and a poisoned
deploy mid-load with every request resolving typed.
"""
import json
import threading
import time
from collections import Counter

import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.resilience import faults
from bigdl_tpu.resilience.elastic import InMemoryKV
from bigdl_tpu.serving import (FleetQuorumError, ReplicaAgent,
                               ServingFleet, Status)
from bigdl_tpu.serving.router import read_health
from bigdl_tpu.serving.swap import SwapRejected


def small_model():
    return nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 3),
                         nn.LogSoftMax())


def feat(rng):
    return rng.rand(4).astype(np.float32)


def make_fleet(n=2, model=None, hedge=False, hedge_delay_s=0.02,
               heartbeat_timeout=0.4, pump_interval_s=None,
               clock=time.monotonic, ready_quorum=None,
               default_deadline_s=10.0, max_queue=64):
    return ServingFleet.build(
        model or small_model(), n_replicas=n,
        server_kw=dict(max_batch=8, max_queue=max_queue),
        heartbeat_timeout=heartbeat_timeout,
        pump_interval_s=pump_interval_s,
        ready_quorum=ready_quorum,
        clock=clock,
        router_kw=dict(default_deadline_s=default_deadline_s,
                       hedge=hedge, hedge_delay_s=hedge_delay_s,
                       clock=clock))


@pytest.fixture
def fleet():
    fl = make_fleet(n=2)
    fl.start()
    yield fl
    fl.stop(timeout=10)


# ---------------------------------------------------------------------------
# membership: heartbeats, health, eject, readmit
# ---------------------------------------------------------------------------

def test_agent_publishes_heartbeat_and_health_snapshot():
    kv = InMemoryKV()
    srv_model = small_model()
    from bigdl_tpu.serving import InferenceServer

    srv = InferenceServer(srv_model, name="rA", max_batch=4).start()
    try:
        agent = ReplicaAgent("rA", srv, kv)
        agent.coordinator.bootstrap(["rA"])
        agent.pump()
        beats = agent.coordinator.beats()
        assert "rA" in beats and beats["rA"]["step"] == 1
        h = read_health(kv, "rA")
        assert h["ready"] is True and h["healthy"] is True
        assert h["breaker_state"] == "closed"
        assert h["queue_depth"] == 0
        assert h["incarnation"] == 0
        assert "p99_s" in h and "ts" in h
    finally:
        srv.stop(timeout=10)


def test_missed_heartbeats_eject_then_rejoin_readmits():
    """Driven entirely on a fake clock: a silent replica ages out of
    the live set (incarnation bump, eviction marker — the training-gang
    death path), and its resumed beats re-admit it at the next pump."""
    t = [0.0]
    fl = make_fleet(n=3, heartbeat_timeout=2.0, pump_interval_s=0,
                    clock=lambda: t[0])
    fl.start()
    try:
        assert fl.router.members == ("r0", "r1", "r2")
        # r0 goes silent; the others keep beating past the timeout
        t[0] = 3.0
        fl.agents["r1"].pump()
        fl.agents["r2"].pump()
        fl.router.refresh()
        assert fl.router.members == ("r1", "r2")
        assert fl.router.ejections == 1
        n, members = fl.router.coordinator.membership()
        assert n == 1 and members == ("r1", "r2")
        # r0 comes back: fresh beat + ready health -> re-admitted
        fl.agents["r0"].pump()
        fl.router.refresh()
        assert fl.router.members == ("r0", "r1", "r2")
        assert fl.router.readmissions == 1
        assert fl.router.coordinator.membership()[0] == 2
    finally:
        fl.stop(timeout=10)


def test_partition_kv_ejects_and_heals():
    t = [0.0]
    fl = make_fleet(n=2, heartbeat_timeout=2.0, pump_interval_s=0,
                    clock=lambda: t[0])
    fl.start()
    try:
        with faults.partition_kv("r1"):
            t[0] = 3.0
            fl.pump_once()       # r1's pump is silenced by the fault
            assert fl.router.members == ("r0",)
        # healed: beats land again, ready -> readmit
        fl.pump_once()
        assert fl.router.members == ("r0", "r1")
        assert fl.router.readmissions == 1
    finally:
        fl.stop(timeout=10)


def test_breaker_open_ejects_and_recovery_readmits():
    fl = make_fleet(n=2, pump_interval_s=0)
    fl.start()
    try:
        fl.servers["r1"].breaker.record_failure(fatal=True)
        assert fl.servers["r1"].breaker.state == "open"
        fl.pump_once()
        assert fl.router.members == ("r0",)
        assert fl.router.ejections == 1
        fl.servers["r1"].breaker.record_success()
        fl.pump_once()
        assert fl.router.members == ("r0", "r1")
    finally:
        fl.stop(timeout=10)


def test_kill_replica_ejects_and_requests_keep_resolving():
    fl = make_fleet(n=3, heartbeat_timeout=0.3, pump_interval_s=0.05)
    fl.start()
    rng = np.random.RandomState(0)
    try:
        [f.result(60) for f in
         [fl.submit(feat(rng)) for _ in range(6)]]
        with faults.kill_replica("r1"):
            deadline = time.monotonic() + 15
            while "r1" in fl.router.members \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
        assert "r1" not in fl.router.members
        assert fl.router.ejections >= 1
        # the survivors carry the traffic; every request resolves typed
        res = [f.result(60) for f in
               [fl.submit(feat(rng)) for _ in range(12)]]
        assert all(r.ok for r in res)
        # a killed server never silently drops: its server-side queue
        # was resolved CANCELLED on stop (typed), never hung
        assert not fl.servers["r1"].healthy()
    finally:
        fl.stop(timeout=10)


# ---------------------------------------------------------------------------
# routing: failover retries, deadline budget, hedging
# ---------------------------------------------------------------------------

def test_routes_and_matches_direct_forward(fleet):
    rng = np.random.RandomState(0)
    xs = [feat(rng) for _ in range(12)]
    res = [f.result(60) for f in [fleet.submit(x) for x in xs]]
    assert all(r.ok for r in res)
    direct = np.asarray(fleet.servers["r0"].model.forward(np.stack(xs)))
    np.testing.assert_allclose(np.stack([r.output for r in res]),
                               direct, atol=1e-6)
    # both replicas took traffic (least-loaded spread under the
    # concurrent flood) or at least every request was dispatched
    snap = fleet.router.snapshot()
    assert snap["metrics"]["served_ok"] == 12


def test_failed_replica_retries_on_another_with_budget(fleet):
    rng = np.random.RandomState(0)
    [f.result(60) for f in [fleet.submit(feat(rng)) for _ in range(4)]]
    before_retries = fleet.router.metrics.retries
    # r0 fails its next step; the router must land the request on r1
    with faults.serving_step_failures(times=1, server="r0") as burst:
        res = [fleet.submit(feat(rng), deadline_s=10.0).result(60)
               for _ in range(6)]
        assert burst["fired"] == 1
    assert all(r.ok for r in res)
    assert fleet.router.metrics.retries >= before_retries + 1


def test_deadline_budget_exhausted_resolves_typed(fleet):
    rng = np.random.RandomState(0)
    [f.result(60) for f in [fleet.submit(feat(rng)) for _ in range(2)]]
    # every replica slow: the budget dies before anyone answers
    with faults.serving_step_latency(0.5, times=8):
        r = fleet.submit(feat(rng), deadline_s=0.15).result(30)
    assert r.status is Status.DEADLINE_EXCEEDED
    # and an already-dead budget resolves immediately, pre-dispatch
    t0 = time.monotonic()
    r = fleet.submit(feat(rng), deadline_s=-1.0).result(10)
    assert r.status is Status.DEADLINE_EXCEEDED
    assert time.monotonic() - t0 < 1.0


def test_no_ready_replica_degrades_typed():
    fl = make_fleet(n=2, pump_interval_s=0)
    fl.start()
    try:
        rng = np.random.RandomState(0)
        [f.result(60) for f in
         [fl.submit(feat(rng)) for _ in range(2)]]
        for srv in fl.servers.values():
            srv.drain(timeout=10)
        fl.pump_once()
        r = fl.submit(feat(rng)).result(30)
        assert r.status in (Status.UNAVAILABLE, Status.CANCELLED,
                            Status.INTERNAL_ERROR)
        assert r.error
    finally:
        fl.stop(timeout=10)


def test_hedge_fires_after_delay_and_hedge_wins():
    fl = make_fleet(n=2, hedge=True, hedge_delay_s=0.05)
    fl.start()
    rng = np.random.RandomState(0)
    try:
        # warm both replicas' compile caches first (no hedging noise:
        # delay far above the cold-compile walls)
        [f.result(60) for f in
         [fl.submit(feat(rng)) for _ in range(4)]]
        time.sleep(0.1)
        fired0 = fl.router.metrics.hedges_fired
        won0 = fl.router.metrics.hedges_won
        # r0 (the tie-break primary at zero load) goes slow: the hedge
        # fires at 50ms and r1's duplicate answer wins
        with faults.delay_replica("r0", 0.8, times=4):
            t0 = time.monotonic()
            r = fl.submit(feat(rng), deadline_s=10.0).result(30)
            took = time.monotonic() - t0
        assert r.ok
        assert took < 0.7        # the winner was the hedge, not r0
        assert fl.router.metrics.hedges_fired >= fired0 + 1
        assert fl.router.metrics.hedges_won >= won0 + 1
        # the loser's late answer is discarded, not double-counted:
        # exactly one fleet-level OK for that request
        assert fl.router.metrics.snapshot()["served_ok"] == 5
    finally:
        fl.stop(timeout=10)


def test_hedge_disabled_never_fires(fleet):
    rng = np.random.RandomState(0)
    with faults.serving_step_latency(0.1, times=2):
        r = fleet.submit(feat(rng)).result(30)
    assert r.ok
    assert fleet.router.metrics.hedges_fired == 0


# ---------------------------------------------------------------------------
# rolling verified deploys
# ---------------------------------------------------------------------------

def test_rolling_swap_installs_on_every_replica(fleet):
    rng = np.random.RandomState(0)
    x = feat(rng)
    [f.result(60) for f in [fleet.submit(x) for _ in range(4)]]
    twin = small_model()
    assert fleet.rolling_swap(params=twin.param_tree()) == 2
    assert fleet.deploys == 1
    want = np.asarray(twin.forward(x[None]))[0]
    for srv in fleet.servers.values():
        got = srv.submit(x).result(60)
        assert got.ok
        np.testing.assert_allclose(got.output, want, atol=1e-6)
        assert srv.metrics.swaps == 1


def test_rolling_swap_from_verified_checkpoint(tmp_path, fleet):
    from bigdl_tpu.utils import file_io

    rng = np.random.RandomState(0)
    x = feat(rng)
    [f.result(60) for f in [fleet.submit(x) for _ in range(2)]]
    twin = small_model()
    good = str(tmp_path / "model.1")
    file_io.save(twin, good, atomic=True, checksum=True)
    assert fleet.rolling_swap(path=good) == 2
    # corrupt artifact: the ONE verified load refuses it before any
    # replica is touched
    bad = str(tmp_path / "model.2")
    file_io.save(twin, bad, atomic=True, checksum=True)
    faults.bit_flip(bad)
    with pytest.raises(SwapRejected, match="crc32c"):
        fleet.rolling_swap(path=bad)
    for srv in fleet.servers.values():
        assert srv.metrics.swaps == 1          # nothing re-installed


def test_poisoned_deploy_rejected_fleetwide_nothing_served(fleet):
    rng = np.random.RandomState(0)
    x = feat(rng)
    before = fleet.submit(x).result(60).output
    with pytest.raises(SwapRejected, match="rolling deploy halted"):
        fleet.rolling_swap(params=faults.poison_params(
            fleet.servers["r0"].model.param_tree()))
    assert fleet.deploy_rollbacks == 1
    after = fleet.submit(x).result(60)
    assert after.ok
    np.testing.assert_allclose(after.output, before, atol=1e-6)
    for srv in fleet.servers.values():
        assert srv.metrics.swaps == 0
        # r0's canary rejected; later replicas were never touched


def test_midway_rejection_rolls_back_already_swapped():
    fl = make_fleet(n=3, pump_interval_s=0)
    fl.start()
    rng = np.random.RandomState(0)
    x = feat(rng)
    try:
        before = fl.submit(x).result(60).output
        twin = small_model()
        # r2's canary fails (injected): r0 + r1 already swapped and
        # must roll back to the prior params
        with faults.serving_step_failures(times=1, server="r2"):
            with pytest.raises(SwapRejected,
                               match="halted at r2.*2 already-swapped"):
                fl.rolling_swap(params=twin.param_tree())
        assert fl.deploy_rollbacks == 1
        res = [srv.submit(x).result(60)
               for srv in fl.servers.values()]
        for r in res:
            assert r.ok
            np.testing.assert_allclose(r.output, before, atol=1e-6)
    finally:
        fl.stop(timeout=10)


def test_alert_driven_rollback_rides_verified_path_and_accounts():
    """``rollback_last_deploy()`` (the continuous loop's burn-rate
    actuator) re-installs the captured prior params on every replica
    of the last roll through the same verified canary install path,
    each re-install recording ``outcome="rolled_back"``; a second call
    is a no-op — the rollback consumed the deploy."""
    fl = make_fleet(n=3, pump_interval_s=0)
    fl.start()
    rng = np.random.RandomState(0)
    x = feat(rng)
    try:
        before = fl.submit(x).result(60).output
        twin = small_model()
        assert fl.rolling_swap(params=twin.param_tree()) == 3
        assert fl.rollback_last_deploy() == 3
        assert fl.deploy_rollbacks == 1
        after = fl.submit(x).result(60)
        assert after.ok
        np.testing.assert_allclose(after.output, before, atol=1e-6)
        for srv in fl.servers.values():
            assert srv.metrics.swaps == 1
            assert srv.metrics.swaps_rolled_back == 1
        # consumed: a second watch trip has nothing left to undo
        assert fl.rollback_last_deploy() == 0
        assert fl.deploy_rollbacks == 1
    finally:
        fl.stop(timeout=10)


def test_quorum_guard_refuses_degraded_deploy():
    fl = make_fleet(n=4, ready_quorum=3, pump_interval_s=0)
    fl.start()
    rng = np.random.RandomState(0)
    x = feat(rng)
    try:
        before = fl.submit(x).result(60).output
        # two replicas down -> only 2 others ready < quorum 3
        fl.servers["r2"].stop(timeout=10)
        fl.servers["r3"].stop(timeout=10)
        with pytest.raises(FleetQuorumError, match="quorum"):
            fl.rolling_swap(params=small_model().param_tree())
        r = fl.submit(x).result(60)
        assert r.ok
        np.testing.assert_allclose(r.output, before, atol=1e-6)
    finally:
        fl.stop(timeout=10)


# ---------------------------------------------------------------------------
# fleet telemetry: merged registries, prometheus, run_report
# ---------------------------------------------------------------------------

def test_snapshot_merges_per_replica_registries(fleet):
    rng = np.random.RandomState(0)
    [f.result(60) for f in [fleet.submit(feat(rng)) for _ in range(10)]]
    snap = fleet.snapshot()
    per = snap["replicas"]
    total_ok = sum(p["served_ok"] for p in per.values())
    assert total_ok == 10
    merged = snap["metrics"]["bigdl_serving_requests_total"]
    ok_series = [s for s in merged["series"]
                 if s["labels"] == {"status": "ok"}]
    assert ok_series and ok_series[0]["value"] == 10
    assert snap["router"]["metrics"]["served_ok"] == 10
    assert snap["membership"]["members"] == ["r0", "r1"]
    assert "goodput_per_chip" in snap
    assert snap["goodput_per_chip"]["chips"] == 2


def test_prometheus_carries_swap_and_hedge_counters(fleet):
    rng = np.random.RandomState(0)
    [f.result(60) for f in [fleet.submit(feat(rng)) for _ in range(2)]]
    fleet.rolling_swap(params=small_model().param_tree())
    with pytest.raises(SwapRejected):
        fleet.rolling_swap(params=faults.poison_params(
            fleet.servers["r0"].model.param_tree()))
    fleet.router.metrics.record_hedge()
    fleet.router.metrics.record_hedge(won=True)
    text = fleet.to_prometheus()
    assert 'bigdl_serving_swaps_total{outcome="installed"} 1.0' in text
    assert 'bigdl_serving_swaps_total{outcome="rejected"} 1.0' in text
    assert 'bigdl_serving_hedges_total{event="fired"} 1.0' in text
    assert 'bigdl_serving_hedges_total{event="won"} 1.0' in text


def test_write_snapshots_renders_through_run_report(tmp_path, fleet,
                                                    capsys):
    import tools.run_report as run_report

    rng = np.random.RandomState(0)
    [f.result(60) for f in [fleet.submit(feat(rng)) for _ in range(6)]]
    paths = fleet.write_snapshots(str(tmp_path))
    assert len(paths) == 3                     # 2 replicas + router
    assert run_report.main([str(tmp_path), "--json"]) == 0
    merged = json.loads(capsys.readouterr().out)
    assert sorted(merged["hosts"]) == ["fleet-router", "r0", "r1"]
    fam = merged["metrics"]["bigdl_serving_requests_total"]
    ok = [s for s in fam["series"] if s["labels"] == {"status": "ok"}]
    assert ok and ok[0]["value"] == 6          # replicas only, no
    #                                           router double count
    assert "bigdl_serving_hedges_total" in merged["metrics"]


# ---------------------------------------------------------------------------
# chaos e2e (acceptance): 4-replica fleet under load absorbs a replica
# kill AND a poisoned rolling deploy mid-flight — every request
# resolves typed, nothing is ever served by poisoned params, p99 stays
# bounded across the failover.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N,gap_s,max_queue",    # 150 requests/s, queue 32
                         [(160, 0.004, 256), (96, 0.027, 32)])
def test_e2e_fleet_survives_replica_kill_and_poisoned_deploy(
        N, gap_s, max_queue):
    DEADLINE = 5.0
    fl = make_fleet(n=4, hedge=True, hedge_delay_s=0.05,
                    heartbeat_timeout=0.3, pump_interval_s=0.05,
                    default_deadline_s=DEADLINE, max_queue=max_queue)
    fl.start()
    futs = [None] * N
    errs = []

    def client(lo, hi, seed):
        r = np.random.RandomState(seed)
        try:
            for i in range(lo, hi):
                futs[i] = fl.submit(r.rand(4).astype(np.float32),
                                    deadline_s=DEADLINE)
                time.sleep(gap_s)
        except Exception as e:  # pragma: no cover - fail below
            errs.append(e)

    threads = [threading.Thread(target=client,
                                args=(k * N // 4, (k + 1) * N // 4, k))
               for k in range(4)]
    try:
        rng = np.random.RandomState(99)
        # warm the bucket ladder so mid-chaos latencies are not
        # compile walls
        [f.result(60) for f in
         [fl.submit(feat(rng)) for _ in range(8)]]
        for t in threads:
            t.start()
        time.sleep(0.08)                      # traffic flowing
        # chaos 1: kill a replica mid-load
        with faults.kill_replica("r1"):
            deadline = time.monotonic() + 15
            while "r1" in fl.router.members \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
        assert "r1" not in fl.router.members
        # chaos 2: poisoned rolling deploy mid-load — refused at the
        # first canary, fleet-wide, while requests keep flowing
        with pytest.raises(SwapRejected):
            fl.rolling_swap(params=faults.poison_params(
                fl.servers["r0"].model.param_tree()))
        for t in threads:
            t.join(timeout=60)
        assert not errs
        res = [f.result(timeout=120) for f in futs]

        # zero lost requests beyond the shed budget: every single one
        # resolves with a typed Status
        by = Counter(r.status for r in res)
        assert sum(by.values()) == N
        assert set(by) <= {Status.OK, Status.OVERLOADED,
                           Status.UNAVAILABLE, Status.DEADLINE_EXCEEDED,
                           Status.INTERNAL_ERROR, Status.CANCELLED}
        assert by[Status.OK] > N * 0.5

        # nothing was ever served by poisoned params: every OK output
        # is finite (poisoned params produce NaN outputs)
        for r in res:
            if r.ok:
                assert np.isfinite(np.asarray(r.output)).all()
        for srv in fl.servers.values():
            assert srv.metrics.swaps == 0      # nothing installed

        # p99 stays bounded across the failover (well under the
        # request deadline — failover routed around the dead replica
        # instead of letting requests age out)
        ok_lat = sorted(r.latency_s for r in res if r.ok)
        p99 = ok_lat[int(0.99 * (len(ok_lat) - 1))]
        assert p99 < DEADLINE

        # the fleet settled at 3 members and kept its goodput view
        assert fl.router.members == ("r0", "r2", "r3")
        snap = fl.snapshot()
        assert snap["membership"]["ejections"] >= 1
        assert fl.router.metrics.hedges_won <= fl.router.metrics.hedges_fired
        assert fl.goodput_per_chip()["model_flops_per_sec_per_chip"] > 0
    finally:
        fl.stop(timeout=15)
