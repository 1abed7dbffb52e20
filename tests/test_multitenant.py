"""Multi-tenant fleet specs (serving/registry.py + the tenant-aware
routing/admission across router, fleet, kvpool, slo):

* ModelRegistry lifecycle — replicas advertise (model, version) in
  their health snapshots, the router dispatches model-addressed
  requests over the advertising subset only, and an unregistered
  model resolves a typed NOT_FOUND at admission (no queue slot, no
  retry burn, never INTERNAL_ERROR).
* Per-tenant admission — weighted max-inflight quotas with weighted
  FAIR shedding: the over-quota tenant sheds typed ("tenant_quota")
  while under-quota tenants keep their full budget; only fleet-wide
  exhaustion sheds "global".  Per-tenant deadline budgets clamp.
* Tenant-scoped KV-page accounting — one owner's long decodes can
  never exhaust the shared arena for other owners.
* Tenant-scoped verified deploys — per-replica deploy locks (disjoint
  models roll concurrently, overlap is refused typed), a poisoned
  tenant-A artifact is rejected by the canary and never touches a
  replica serving model B.
* Per-tenant SLO packs fire and resolve independently.
* The chaos e2e: a 2-model fleet under a sustained tenant-A flood +
  poisoned tenant-A deploy + replica kill keeps tenant B's p99
  bounded, sheds zero tenant-B requests, resolves every request
  typed, and serves zero poisoned outputs for either tenant.
"""
import json
import threading
import time
from collections import Counter

import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.resilience import faults
from bigdl_tpu.serving import ServingFleet, Status
from bigdl_tpu.serving.kvpool import KVPagePool, PoolExhausted
from bigdl_tpu.serving.registry import (AdmissionController,
                                        ModelRegistry)
from bigdl_tpu.serving.swap import DeployInFlight, SwapRejected


def small_model():
    return nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 3),
                         nn.LogSoftMax())


def feat(rng):
    return rng.rand(4).astype(np.float32)


def multi_fleet(n=2, quotas=None, capacity=None, pump_interval_s=0.05,
                heartbeat_timeout=0.4, default_deadline_s=10.0,
                max_queue=64, deadline_budgets=None, **fleet_kw):
    return ServingFleet.build_multi(
        {"alpha": small_model(), "beta": small_model()},
        n_replicas_each=n,
        server_kw=dict(max_batch=8, max_queue=max_queue),
        quotas=quotas, admission_capacity=capacity,
        deadline_budgets=deadline_budgets,
        heartbeat_timeout=heartbeat_timeout,
        pump_interval_s=pump_interval_s,
        router_kw=dict(default_deadline_s=default_deadline_s),
        **fleet_kw)


# ---------------------------------------------------------------------------
# ModelRegistry
# ---------------------------------------------------------------------------

def test_registry_lifecycle_and_advertisers():
    reg = ModelRegistry()
    assert reg.register("alpha") == "v1"
    assert reg.register("beta", "b7") == "b7"
    assert reg.lookup("alpha") == "v1"
    assert reg.has("beta") and not reg.has("ghost")
    assert reg.lookup("ghost") is None
    # re-registration updates the advertised version in place
    assert reg.register("alpha", "v2") == "v2"
    assert reg.models() == {"alpha": "v2", "beta": "b7"}
    assert reg.unregister("beta") is True
    assert reg.unregister("beta") is False
    assert reg.lookup("beta") is None
    health = {"r0": {"model": "alpha"}, "r1": {"model": "beta"},
              "r2": {"model": "alpha"}, "r3": {}}
    assert ModelRegistry.advertisers("alpha", health) == ["r0", "r2"]
    assert ModelRegistry.advertisers("ghost", health) == []


def test_unregister_model_mid_flight_injector():
    """The armed injector makes the registry entry vanish at the next
    lookup — the deterministic mid-flight-vanish chaos hook."""
    reg = ModelRegistry()
    reg.register("alpha")
    with faults.unregister_model_mid_flight("alpha"):
        assert reg.lookup("alpha") is None     # fired + self-removed
    assert not reg.has("alpha")                # it really unregistered
    reg.register("alpha")                      # restore is explicit
    assert reg.lookup("alpha") == "v1"


# ---------------------------------------------------------------------------
# AdmissionController: weighted quotas, fair shed ordering, deadlines
# ---------------------------------------------------------------------------

def test_weighted_shed_ordering_quota_before_global():
    """The fairness contract: the over-quota tenant sheds typed
    ("tenant_quota") while the under-quota tenant keeps its FULL
    budget; "global" only ever fires on genuine fleet-wide
    exhaustion."""
    ac = AdmissionController(capacity=6, quotas={"a": 2.0, "b": 1.0})
    assert ac.budget("a") == 4 and ac.budget("b") == 2
    for _ in range(4):
        assert ac.try_admit("a") == (True, ac.ADMITTED)
    # a is at quota: shed typed, BEFORE b has lost anything
    assert ac.try_admit("a") == (False, ac.TENANT_QUOTA)
    # b still gets every one of its slots
    for _ in range(2):
        assert ac.try_admit("b") == (True, ac.ADMITTED)
    assert ac.try_admit("b") == (False, ac.TENANT_QUOTA)
    # fleet-wide exhaustion: an unknown (default-slot) tenant is
    # refused "global" — its own 1-slot budget was never the problem
    assert ac.budget("c") == 1
    assert ac.try_admit("c") == (False, ac.GLOBAL)
    # releasing an a-slot restores a (quota) and frees capacity (c)
    ac.release("a")
    assert ac.try_admit("c") == (True, ac.ADMITTED)
    snap = ac.snapshot()
    assert snap["total_inflight"] == 6 == snap["capacity"]
    assert snap["inflight"] == {"a": 3, "b": 2, "c": 1}


def test_tenant_deadline_budget_clamps():
    ac = AdmissionController(capacity=4,
                             deadline_budgets={"a": 0.5})
    assert ac.deadline_for("a", 2.0) == 0.5     # clamped to ceiling
    assert ac.deadline_for("a", 0.2) == 0.2     # tighter stays
    assert ac.deadline_for("a", None) == 0.5    # ceiling is default
    assert ac.deadline_for("b", 2.0) == 2.0     # unbudgeted passes
    assert ac.deadline_for("b", None) is None


# ---------------------------------------------------------------------------
# tenant-scoped KV-page accounting
# ---------------------------------------------------------------------------

def test_kv_owner_budget_isolates_arena():
    pool = KVPagePool(num_pages=8, layers=1, num_kv_heads=1,
                      page_size=4, head_dim=2)
    pool.set_owner_budget("a", 3)
    lease_a = pool.alloc(3, owner="a")
    assert pool.owner_held("a") == 3
    # a is at its budget: refused typed even with 5 pages free
    with pytest.raises(PoolExhausted, match="budget"):
        lease_a.extend(1)
    assert pool.free_pages == 5
    # b takes the arena a could not exhaust
    lease_b = pool.alloc(5, owner="b")
    assert pool.owner_held("b") == 5
    assert pool.stats()["by_owner"] == {"a": 3, "b": 5}
    lease_a.release()
    lease_b.release()
    assert pool.free_pages == 8                 # no leak
    assert pool.stats()["by_owner"] == {}
    assert pool.owner_held("a") == 0


def test_kv_default_owner_charges_unnamed_allocs():
    pool = KVPagePool(num_pages=4, layers=1, num_kv_heads=1,
                      page_size=4, head_dim=2)
    pool.default_owner = "alpha"
    lease = pool.alloc(2)                       # decoder-internal path
    assert pool.owner_held("alpha") == 2
    lease.release()
    assert pool.owner_held("alpha") == 0


# ---------------------------------------------------------------------------
# registry-aware routing + typed NOT_FOUND on the live fleet
# ---------------------------------------------------------------------------

def test_not_found_is_typed_nonretryable_and_burns_nothing():
    from bigdl_tpu.serving.router import RETRYABLE_STATUSES

    assert Status.NOT_FOUND not in RETRYABLE_STATUSES
    fl = multi_fleet(pump_interval_s=0)
    fl.start()
    try:
        rng = np.random.RandomState(0)
        r = fl.submit(feat(rng), model="ghost").result(10)
        assert r.status is Status.NOT_FOUND
        assert "ghost" in r.error
        # typed at admission: no replica saw it, no retry burned, no
        # admission slot consumed
        for srv in fl.servers.values():
            assert sum(srv.metrics.counts.values()) == 0
        assert fl.router.admission.inflight() == 0
        tenants = fl.router.metrics.tenants()
        assert tenants["ghost"]["requests"] == {"not_found": 1}
        assert tenants["ghost"]["sheds"] == {"not_found": 1}
    finally:
        fl.stop(timeout=10)


def test_router_dispatches_on_advertised_model_only():
    fl = multi_fleet(pump_interval_s=0)
    fl.start()
    try:
        fl.pump_once()
        # health snapshots advertise (model, version)
        h = fl.router.health_of("alpha-r0")
        assert h["model"] == "alpha" and h["model_version"] == "v1"
        rng = np.random.RandomState(1)
        res = [fl.submit(feat(rng), model="alpha").result(30)
               for _ in range(8)]
        assert all(r.status is Status.OK for r in res)
        served = {rid: srv.metrics.counts["ok"]
                  for rid, srv in fl.servers.items()}
        assert served["beta-r0"] == 0 and served["beta-r1"] == 0
        assert served["alpha-r0"] + served["alpha-r1"] == 8
    finally:
        fl.stop(timeout=10)


def test_unregistered_model_resolves_not_found_on_fleet():
    fl = multi_fleet(pump_interval_s=0)
    fl.start()
    try:
        rng = np.random.RandomState(2)
        assert fl.submit(feat(rng),
                         model="alpha").result(30).status is Status.OK
        fl.router.model_registry.unregister("alpha")
        r = fl.submit(feat(rng), model="alpha").result(10)
        assert r.status is Status.NOT_FOUND
        # beta is untouched by alpha's disappearance
        assert fl.submit(feat(rng),
                         model="beta").result(30).status is Status.OK
    finally:
        fl.stop(timeout=10)


def test_tenant_flood_injector_sheds_flooded_tenant_only():
    fl = multi_fleet(pump_interval_s=0)
    fl.start()
    try:
        rng = np.random.RandomState(3)
        with faults.tenant_flood("alpha", rps=10 ** 6):
            ra = fl.submit(feat(rng), model="alpha").result(10)
            rb = fl.submit(feat(rng), model="beta").result(30)
        assert ra.status is Status.OVERLOADED
        assert "tenant_quota" in ra.error
        assert rb.status is Status.OK
        tenants = fl.router.metrics.tenants()
        assert tenants["alpha"]["sheds"] == {"tenant_quota": 1}
        assert tenants["beta"]["shed_total"] == 0
    finally:
        fl.stop(timeout=10)


# ---------------------------------------------------------------------------
# tenant-scoped verified deploys: per-replica locks, canary, rollback
# ---------------------------------------------------------------------------

def test_model_scoped_swap_updates_only_that_tenant():
    fl = multi_fleet(pump_interval_s=0)
    fl.start()
    try:
        assert fl.rolling_swap(params=small_model().param_tree(),
                               model="alpha", version="v2") == 2
        for rid, srv in fl.servers.items():
            if rid.startswith("alpha"):
                assert srv.model_version == "v2"
                assert srv.metrics.swaps == 1
            else:
                assert srv.model_version == "v1"
                assert srv.metrics.swaps == 0
        assert fl.router.model_registry.lookup("alpha") == "v2"
        # rollback consumes the scoped capture and restores the
        # advertised version
        assert fl.rollback_last_deploy(model="alpha") == 2
        assert all(s.model_version == "v1"
                   for s in fl.servers.values())
        assert fl.router.model_registry.lookup("alpha") == "v1"
        assert fl.rollback_last_deploy(model="alpha") == 0
    finally:
        fl.stop(timeout=10)


def test_poisoned_tenant_deploy_never_touches_other_tenant():
    fl = multi_fleet(pump_interval_s=0)
    fl.start()
    try:
        rng = np.random.RandomState(4)
        with pytest.raises(SwapRejected):
            fl.rolling_swap(params=faults.poison_params(
                fl.servers["alpha-r0"].model.param_tree()),
                model="alpha", version="v2")
        # nothing installed anywhere; beta params and traffic intact
        for srv in fl.servers.values():
            assert srv.metrics.swaps == 0
        assert fl.router.model_registry.lookup("alpha") == "v1"
        r = fl.submit(feat(rng), model="beta").result(30)
        assert r.status is Status.OK
        assert np.isfinite(np.asarray(r.output)).all()
    finally:
        fl.stop(timeout=10)


def test_deploy_locks_serialize_overlap_only():
    """Disjoint tenants deploy concurrently; an overlapping replica
    set is refused typed before any replica is touched."""
    fl = multi_fleet(pump_interval_s=0)
    fl.start()
    try:
        with fl._deploy_table_lock:
            lk = fl._deploy_locks.setdefault("alpha-r0",
                                             threading.Lock())
        assert lk.acquire(blocking=False)
        try:
            with pytest.raises(DeployInFlight):
                fl.rolling_swap(params=small_model().param_tree(),
                                model="alpha")
            with pytest.raises(DeployInFlight):
                fl.rolling_swap(params=small_model().param_tree())
            # a disjoint model's deploy proceeds while alpha is held
            assert fl.rolling_swap(params=small_model().param_tree(),
                                   model="beta", version="v3") == 2
        finally:
            lk.release()
        assert fl.rolling_swap(params=small_model().param_tree(),
                               model="alpha", version="v2") == 2
    finally:
        fl.stop(timeout=10)


# ---------------------------------------------------------------------------
# per-tenant SLO rule packs
# ---------------------------------------------------------------------------

def test_per_tenant_slo_rules_fire_and_resolve_independently():
    from bigdl_tpu.telemetry import MetricRecorder, MetricsRegistry
    from bigdl_tpu.telemetry import metric_names as M
    from bigdl_tpu.telemetry.slo import (SloEngine,
                                         default_serving_rules)

    t = [0.0]
    rec = MetricRecorder(clock=lambda: t[0])
    eng = SloEngine(rec, registry=MetricsRegistry(),
                    clock=lambda: t[0])
    names = {}
    for tenant in ("alpha", "beta"):
        rules = default_serving_rules(
            "both", tenant=tenant, p99_high_s=0.5,
            for_intervals=1, resolve_intervals=1)
        for r in rules:
            eng.add_rule(r)
        names[tenant] = [r.name for r in rules]
    assert set(names["alpha"]).isdisjoint(names["beta"])
    assert f"serving/alpha:both/p99" in names["alpha"]

    def feed(tenant, p99, now):
        rec.observe(M.AUTOSCALE_POOL_P99_SECONDS, p99,
                    labels={"pool": f"{tenant}:both"}, now=now)

    # alpha breaches, beta healthy
    t[0] = 1.0
    feed("alpha", 2.0, t[0])
    feed("beta", 0.01, t[0])
    eng.evaluate(now=t[0])
    firing = {a["rule"] for a in eng.firing()}
    assert "serving/alpha:both/p99" in firing
    assert not any(n in firing for n in names["beta"])
    # alpha recovers while beta breaches: the packs move independently
    t[0] = 2.0
    feed("alpha", 0.01, t[0])
    feed("beta", 2.0, t[0])
    eng.evaluate(now=t[0])
    firing = {a["rule"] for a in eng.firing()}
    assert "serving/alpha:both/p99" not in firing
    assert "serving/beta:both/p99" in firing


# ---------------------------------------------------------------------------
# (model, phase) pools: the autoscaler's tenant-scoped sizing
# ---------------------------------------------------------------------------

def test_autoscaler_defaults_to_model_scoped_pools():
    from bigdl_tpu.serving.autoscale import Autoscaler
    from bigdl_tpu.serving.pools import split_pool

    assert split_pool("decode") == (None, "decode")
    assert split_pool("alpha:decode") == ("alpha", "decode")
    fl = multi_fleet(pump_interval_s=0)
    fl.start()
    try:
        asc = Autoscaler(fl, lambda rid, pool: None)
        assert asc.pools == ("alpha:both", "beta:both")
        assert asc.pool_size("alpha:both") == 2
        assert asc.pool_size("beta:both") == 2
        fl.pump_once()
        sig = asc.pool_signals("alpha:both")
        assert sig["replicas"] == 2
        # the scoped pool reads ONLY its own model's health
        assert set(asc._pool_health("alpha:both")) \
            == {"alpha-r0", "alpha-r1"}
    finally:
        fl.stop(timeout=10)


# ---------------------------------------------------------------------------
# fleet snapshot fold + run-report per-tenant view
# ---------------------------------------------------------------------------

def test_snapshot_and_run_report_carry_tenant_view(tmp_path, capsys):
    import tools.run_report as run_report

    fl = multi_fleet(pump_interval_s=0)
    fl.start()
    try:
        rng = np.random.RandomState(5)
        for _ in range(4):
            assert fl.submit(feat(rng),
                             model="alpha").result(30).ok
        for _ in range(2):
            assert fl.submit(feat(rng),
                             model="beta").result(30).ok
        snap = fl.snapshot()
        assert snap["tenants"]["alpha"]["served_ok"] == 4
        assert snap["tenants"]["beta"]["served_ok"] == 2
        assert snap["router"]["registry"] == {"alpha": "v1",
                                              "beta": "v1"}
        assert "bigdl_tenant_admission_total" in snap["metrics"]
        paths = fl.write_snapshots(str(tmp_path))
        assert len(paths) == 5                 # 4 replicas + router
        assert run_report.main([str(tmp_path), "--json"]) == 0
        merged = json.loads(capsys.readouterr().out)
        assert merged["tenants"]["alpha"]["served_ok"] == 4
        assert merged["tenants"]["beta"]["total"] == 2
    finally:
        fl.stop(timeout=10)


# ---------------------------------------------------------------------------
# chaos e2e (acceptance): noisy-neighbor isolation under flood + kill
# + poisoned deploy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,n_floods", [(60, 4), (30, 2)])
def test_e2e_two_tenant_fleet_isolates_noisy_neighbor(n, n_floods):
    DEADLINE = 5.0
    fl = multi_fleet(n=2, capacity=16, pump_interval_s=0.05,
                     heartbeat_timeout=0.3,
                     default_deadline_s=DEADLINE, max_queue=256)
    fl.start()
    rng = np.random.RandomState(7)
    try:
        # warm both models' compiled paths
        for m in ("alpha", "beta"):
            [f.result(60) for f in
             [fl.submit(feat(rng), model=m) for _ in range(8)]]

        def beta_closed_loop(n):
            lats = []
            r = np.random.RandomState(11)
            for _ in range(n):
                res = fl.submit(feat(r), model="beta").result(60)
                lats.append((res.status, res.latency_s,
                             res.output))
            return lats

        # tenant-B solo baseline
        solo = beta_closed_loop(n)
        solo_lat = sorted(l for _, l, _ in solo)
        solo_p99 = solo_lat[int(0.99 * (len(solo_lat) - 1))]

        # contended phase: sustained tenant-A flood (open loop, four
        # producers), a poisoned tenant-A deploy, and an alpha
        # replica kill — all while tenant B runs the same closed loop
        alpha_futs = []
        fut_lock = threading.Lock()
        stop = threading.Event()

        def alpha_flood(seed):
            r = np.random.RandomState(seed)
            while not stop.is_set():
                f = fl.submit(feat(r), model="alpha",
                              deadline_s=DEADLINE)
                with fut_lock:
                    alpha_futs.append(f)
                time.sleep(0.001)

        floods = [threading.Thread(target=alpha_flood, args=(s,))
                  for s in range(n_floods)]
        for th in floods:
            th.start()
        try:
            time.sleep(0.05)
            # poisoned tenant-A deploy: rejected by the first canary,
            # rolls back, never touches a model-B replica
            with pytest.raises(SwapRejected):
                fl.rolling_swap(params=faults.poison_params(
                    fl.servers["alpha-r0"].model.param_tree()),
                    model="alpha", version="v2")
            # kill one alpha replica mid-flood
            with faults.kill_replica("alpha-r0"):
                deadline = time.monotonic() + 15
                while "alpha-r0" in fl.router.members \
                        and time.monotonic() < deadline:
                    time.sleep(0.01)
            assert "alpha-r0" not in fl.router.members
            contended = beta_closed_loop(n)
        finally:
            stop.set()
            for th in floods:
                th.join(timeout=30)
        alpha_res = [f.result(timeout=120) for f in alpha_futs]

        # every request — both tenants — resolved typed
        by = Counter(r.status for r in alpha_res)
        assert set(by) <= {Status.OK, Status.OVERLOADED,
                           Status.UNAVAILABLE,
                           Status.DEADLINE_EXCEEDED, Status.CANCELLED}
        assert all(s is Status.OK for s, _, _ in contended)

        # bad_params_served == 0 for BOTH tenants: every OK output is
        # finite (poisoned params produce NaN outputs), and nothing
        # was ever installed
        for r in alpha_res:
            if r.ok:
                assert np.isfinite(np.asarray(r.output)).all()
        for _, _, out in contended:
            assert np.isfinite(np.asarray(out)).all()
        for srv in fl.servers.values():
            assert srv.metrics.swaps == 0
        # the rejected model-A deploy never reached a model-B replica
        assert all(s.model_version == "v1"
                   for rid, s in fl.servers.items()
                   if rid.startswith("beta"))

        # tenant B shed ZERO requests and its p99 stayed bounded
        tenants = fl.router.metrics.tenants()
        assert tenants["beta"]["shed_total"] == 0
        assert tenants["beta"]["total"] >= 2 * n
        con_lat = sorted(l for _, l, _ in contended)
        con_p99 = con_lat[int(0.99 * (len(con_lat) - 1))]
        # isolation bar: <= 1.25x the solo baseline (+50ms grace for
        # shared-CPU scheduler noise at millisecond latencies)
        # at the full size only: of 30 latencies the p99 is the worst
        assert n < 60 or con_p99 <= 1.25 * solo_p99 + 0.05, \
            f"tenant-B p99 {con_p99:.4f}s vs solo {solo_p99:.4f}s"

        # the flood DID make tenant A shed typed through its quota —
        # the fairness machinery was genuinely exercised
        assert tenants["alpha"]["sheds"].get("tenant_quota", 0) > 0 \
            or by[Status.OVERLOADED] > 0
    finally:
        fl.stop(timeout=15)
