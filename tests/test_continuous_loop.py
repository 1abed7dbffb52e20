"""Continuous-learning production loop specs (bigdl_tpu/loop/):
streaming ingest → online training slices → health-gated verified
hot-swaps into a live fleet → post-swap burn-rate watch with automatic
fleet-wide rollback.  The chaos e2e injects a poisoned candidate, a
loss-divergence burst, a replica kill, and a chronic straggler
mid-loop and requires every bad state to be caught by a gate or an
alert — never by a served bad parameter.  The steady-state spec is
the other half of the contract: a clean run must produce ZERO
rollbacks and zero false-positive loop alerts while the model
measurably improves across mid-run fleet-wide hot-swaps.
"""
import time

import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import Sample, array
from bigdl_tpu.loop import DEPLOY_OUTCOMES, ContinuousLoop
from bigdl_tpu.optim import SGD
from bigdl_tpu.optim.optimizer import LocalOptimizer
from bigdl_tpu.resilience import faults
from bigdl_tpu.serving import ServingFleet
from bigdl_tpu.telemetry import (MetricsRegistry, Telemetry,
                                 TrainingHealthMonitor,
                                 default_training_rules)
from bigdl_tpu.telemetry.goodput import GoodputLedger


class _TickLedger(GoodputLedger):
    """One tick for every section the optimizer reports, whatever the
    host's clock read: the ledger follows which sections were opened."""

    def add(self, category, seconds):
        super().add(category, 1.0)


class _World:
    """One continuous-learning rig: a regression optimizer with a
    divergence-only health monitor, a live fleet on a fake clock, and
    a ContinuousLoop wiring them.  ``step()`` is one interval: tick,
    advance the clock, drive router traffic, keep every result."""

    def __init__(self, n_replicas=3, init_samples=512, capacity=1024,
                 ingest_per_interval=8, batch_size=32,
                 divergence_ratio=4.0, heartbeat_timeout=5.0,
                 health=False, health_kw=None, requests_per_interval=2,
                 **loop_kw):
        self.rng = np.random.RandomState(0)
        self.w = self.rng.rand(8, 1).astype(np.float32)
        self.t = [0.0]
        self.ingest_per_interval = ingest_per_interval
        self.requests_per_interval = requests_per_interval
        self.results = []

        data = array(self.make_samples(init_samples))
        self.model = nn.Sequential(nn.Linear(8, 8), nn.Tanh(),
                                   nn.Linear(8, 1))
        self.opt = LocalOptimizer(self.model, data, nn.MSECriterion(),
                                  batch_size=batch_size)
        self.opt.set_optim_method(SGD(learning_rate=0.05))
        self.opt.set_telemetry(Telemetry(registry=MetricsRegistry()))
        # divergence-only rule subset: a toy run legitimately
        # plateaus (stall) and its wall clock is all compile
        # (goodput) without being sick — the established pattern
        self.monitor = TrainingHealthMonitor(
            rules=[r for r in default_training_rules(
                divergence_ratio=divergence_ratio)
                if r.name == "training/loss_divergence"],
            every_n_steps=2)
        self.opt.set_health_monitor(self.monitor)

        serve_model = nn.Sequential(nn.Linear(8, 8), nn.Tanh(),
                                    nn.Linear(8, 1))
        self.initial_params = serve_model.param_tree()
        fleet_kw = dict(health=health, health_kw=health_kw) \
            if health else {}
        self.fleet = ServingFleet.build(
            serve_model, n_replicas=n_replicas,
            server_kw=dict(max_batch=8, max_queue=64),
            heartbeat_timeout=heartbeat_timeout, pump_interval_s=0,
            clock=lambda: self.t[0],
            router_kw=dict(default_deadline_s=30.0,
                           clock=lambda: self.t[0]),
            **fleet_kw)
        self.fleet.start()
        self.loop = ContinuousLoop(
            self.opt, self.fleet, self._ingest,
            dataset_capacity=capacity, interval_s=1.0,
            clock=lambda: self.t[0], **loop_kw)

    def make_samples(self, n):
        xs = self.rng.rand(n, 8).astype(np.float32)
        return [Sample(xs[i], (xs[i] @ self.w).astype(np.float32))
                for i in range(n)]

    def _ingest(self):
        return self.make_samples(self.ingest_per_interval)

    def serve(self, n=None):
        n = self.requests_per_interval if n is None else n
        res = [f.result(60) for f in
               [self.fleet.submit(self.rng.rand(8).astype(np.float32))
                for _ in range(n)]]
        self.results.extend(res)
        return res

    def step(self, n=1, serve=None):
        for _ in range(n):
            self.loop.tick()
            self.t[0] += 1.0
            self.serve(serve)

    def stop(self):
        self.fleet.stop(timeout=10)

    def check_goodput(self):
        """The loop's goodput by count: one compile (in the warm-up
        baseline), nothing checkpointed or recovered.  The ratio reads how
        often the host starved the infeed thread, so it has no floor."""
        tm = self.opt.telemetry
        secs = tm.ledger.snapshot()["seconds"]
        assert tm.compile_seconds.count == 1
        assert secs["checkpoint"] == secs["recovery"] == 0.0
        assert 0.0 < self.loop.goodput() <= 1.0

    def served_matches_trained(self):
        """The fleet serves exactly the params of the last confirmed
        deploy (training has usually moved on a few slices since)."""
        assert self.loop.last_deployed_params is not None
        expect = nn.Sequential(nn.Linear(8, 8), nn.Tanh(),
                               nn.Linear(8, 1))
        expect.set_param_tree(self.loop.last_deployed_params)
        probe = self.rng.rand(8).astype(np.float32)
        direct = np.asarray(expect.forward(probe[None]))
        r = self.fleet.submit(probe).result(60)
        assert r.ok, r.status
        np.testing.assert_allclose(np.asarray(r.output), direct[0],
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# steady state: the model improves while serving, nothing false-fires
# ---------------------------------------------------------------------------

def test_steady_state_improves_while_serving_no_false_alarms():
    """200 clean intervals: loss descends across many mid-run
    fleet-wide hot-swaps, training books one compile and no recovery,
    and there are ZERO rollbacks and zero firing transitions from the
    loop's alert engine — a quiet pipeline must read quiet."""
    w = _World(deploy_every=5, watch_intervals=2, cooldown_intervals=2)
    try:
        w.step(200)
        snap = w.loop.snapshot()
        d = snap["deploys"]
        assert d.get("confirmed", 0) >= 10, d
        for bad in ("rolled_back", "rejected", "gated", "refused"):
            assert d.get(bad, 0) == 0, d
        # zero false-positive loop alerts over the whole run
        fired = [a for a in w.loop.engine.events if a.state == "firing"]
        assert fired == [], fired
        assert w.opt.health_verdict().healthy
        # the model measurably improved while serving: the swap-synced
        # fleet serves the trained params and loss fell by an order
        losses = w.loop.losses
        assert len(losses) >= 190
        assert np.mean(losses[-10:]) < 0.2 * np.mean(losses[:10]), (
            losses[:10], losses[-10:])
        assert snap["bad_params_served"] == 0
        w.check_goodput()
        assert all(r.ok for r in w.results)
        assert all(np.isfinite(np.asarray(r.output)).all()
                   for r in w.results)
        w.served_matches_trained()
        # deploy counter folded into the fleet snapshot for scrape
        fam = w.fleet.snapshot()["metrics"].get(
            "bigdl_loop_deploys_total")
        assert fam is not None
        got = {tuple(s["labels"].items()): s["value"]
               for s in fam["series"]}
        assert got[(("outcome", "confirmed"),)] == d["confirmed"]
    finally:
        w.stop()


def test_goodput_excludes_warmup_and_serving_idle():
    """The loop's goodput is a steady-state delta: before any tick it
    is None, and the first slice's XLA compile lands in the warmup
    baseline rather than being billed against training.  On a tick
    ledger and the synchronous feed (every fetch but a slice's first is
    a stall) k steps read k productive ticks and k - 1 stalls, no more."""
    w = _World(deploy_every=0)
    w.opt.telemetry.ledger = _TickLedger()
    w.opt.set_infeed_prefetch(0)
    try:
        assert w.loop.goodput() is None
        w.step(1)
        assert w.opt.telemetry.compile_seconds.count == 1
        assert w.loop.goodput() is None     # all of it is the baseline
        w.step(9)
        k = w.loop.steps_per_interval
        assert w.loop.goodput() == pytest.approx(k / (2 * k - 1))
    finally:
        w.stop()


# ---------------------------------------------------------------------------
# the four-fault chaos e2e
# ---------------------------------------------------------------------------

def test_chaos_every_bad_state_caught_never_served():
    """Poisoned candidate, loss-divergence burst, replica kill, and a
    chronic straggler injected mid-loop: the gate catches the
    divergence, the canary catches the poison, membership/health
    handle the infra faults — and not one bad parameter set is ever
    served, not one false rollback fires."""
    from bigdl_tpu.serving import ReplicaHealthPolicy

    w = _World(n_replicas=4, capacity=64, ingest_per_interval=16,
               init_samples=64, heartbeat_timeout=2.0,
               requests_per_interval=6, health=True,
               # p99_high must clear the cold-start compile latency
               # (~0.13s) that sits in every replica's exact window
               health_kw=dict(policy=ReplicaHealthPolicy(
                   p99_high_s=0.25, window_s=30.0, feed_dead_s=60.0,
                   for_intervals=2, resolve_intervals=2)),
               deploy_every=10, watch_intervals=2,
               cooldown_intervals=2)
    try:
        # phase 0 (i1-12): clean — first deploy lands and confirms
        w.step(12)
        assert w.loop.deploy_outcomes["confirmed"] >= 1
        w.served_matches_trained()

        # phase 1 (i13-20): poisoned candidate at the i20 boundary —
        # the training gate is happy (loss is fine), so the per-replica
        # canary must be what stops it
        w.step(7)
        with faults.poison_candidate(times=1):
            w.step(1)
        assert w.loop.deploy_outcomes["rejected"] >= 1
        # the poison never reached a served param
        assert w.loop.bad_params_served == 0
        w.step(2)          # cooldown drains
        assert all(r.ok for r in w.results[-8:])

        # phase 2 (i23-30): loss-divergence burst right before the
        # i30 boundary — with a 64-sample window and 16 samples per
        # interval of x12-scaled features, the monitor's frac-of-min
        # rule fires and the gate refuses the candidate
        w.step(5)                                   # i23-27 clean
        with faults.loop_loss_divergence(times=3, scale=12.0):
            w.step(3)                               # i28-30 poisoned
        assert w.loop.deploy_outcomes["gated"] >= 1, \
            dict(w.loop.deploy_outcomes)
        gated = [e for e in w.loop.events
                 if e["kind"] == "deploy" and e["state"] == "gated"]
        assert any("training/loss_divergence" in e.get("rules", ())
                   for e in gated), gated
        assert w.loop.bad_params_served == 0

        # phase 3 (i31-39): replica kill — ejection and failover are
        # membership's problem; the loop must NOT roll anything back
        rolled_before = w.loop.deploy_outcomes["rolled_back"]
        with faults.kill_replica("r1"):
            w.step(4)                               # i31-34
        assert "r1" not in w.fleet.router.members
        w.step(5)                                   # i35-39 settle
        assert w.loop.deploy_outcomes["rolled_back"] == rolled_before
        # divergence washed out of the bounded window: gate is open
        # again and the i40 deploy confirms mid-chaos
        assert w.opt.health_verdict().healthy
        w.step(3)                                   # i40-42
        assert w.loop.deploy_outcomes["confirmed"] >= 2
        w.served_matches_trained()

        # phase 4 (i43+): chronic straggler — r2 answers, slowly; the
        # per-replica health rule marks it degraded and routes around
        with faults.delay_replica("r2", 0.6):
            for _ in range(8):
                w.step(1)
                if "r2" in w.fleet.router.degraded:
                    break
        assert "r2" in w.fleet.router.degraded
        # recovery: r1 rejoins; quorum holds without r2, so the loop
        # keeps deploying through the degraded fleet
        w.fleet.restart_replica("r1")
        w.step(10)
        assert "r1" in w.fleet.router.members
        snap = w.loop.snapshot()
        assert snap["deploys"].get("confirmed", 0) >= 3, snap["deploys"]
        assert snap["deploys"].get("rolled_back", 0) == 0
        assert snap["bad_params_served"] == 0
        # every served output that resolved ok was finite — a bad
        # param never answered a request
        assert all(np.isfinite(np.asarray(r.output)).all()
                   for r in w.results if r.ok)
        w.served_matches_trained()
    finally:
        w.stop()


# ---------------------------------------------------------------------------
# post-swap burn-rate watch → automatic fleet-wide rollback
# ---------------------------------------------------------------------------

# (29, 10): two deploys land and confirm first; the one at 30 regresses
@pytest.mark.parametrize("clean,deploy_every", [(0, 8), (29, 10)])
def test_post_swap_burn_fires_automatic_fleet_rollback(clean, deploy_every):
    """A deploy that regresses under live traffic: serving errors
    spike inside the watch window, the loop's burn-rate rule fires,
    and the fleet is rolled back wholesale through the verified
    install path — then, once the burn resolves, the next deploy
    confirms (the loop recovers by itself)."""
    from bigdl_tpu.telemetry import default_loop_rules

    w = _World(deploy_every=deploy_every, watch_intervals=4,
               cooldown_intervals=2, requests_per_interval=8,
               rules=default_loop_rules(interval_s=1.0,
                                        serve_budget=0.02))
    try:
        w.step(clean)
        confirmed = w.loop.deploy_outcomes["confirmed"]
        if clean:       # the model improved while the fleet served
            assert confirmed >= 2
            assert np.mean(w.loop.losses[-4:]) < np.mean(w.loop.losses[:4])
            w.check_goodput()
        while w.loop.state != "watch":  # the deploy lands, watch armed
            served = w.fleet.servers["r0"].current_params()[0]
            w.step(1)
        assert w.loop.deploy_outcomes["confirmed"] == confirmed
        # regress under live traffic: a failure burst inside the watch
        # window.  Sequential submits keep the retry rotation
        # deterministic (2 requests x 3 attempts = 6 failures, under
        # every breaker's consecutive threshold), and the budget
        # exhausts before the rollback runs, so the rollback canaries
        # see a healthy step.
        with faults.serving_step_failures(times=6) as burst:
            for _ in range(8):
                w.results.append(w.fleet.submit(
                    w.rng.rand(8).astype(np.float32)).result(60))
        assert burst["fired"] == 6
        w.step(1)                       # i9: burn breach no.1
        w.step(1)                       # i10: breach no.2 -> rollback
        d = dict(w.loop.deploy_outcomes)
        assert d.get("rolled_back", 0) == 1, d
        assert w.loop.state == "cooldown"
        assert w.fleet.deploy_rollbacks == 1
        # the rollback rode the verified install path on EVERY replica
        for srv in w.fleet.servers.values():
            assert srv.metrics.swaps_rolled_back == 1
            assert srv.breaker.state == "closed"
        # and re-installed the pre-deploy params
        probe = w.rng.rand(8).astype(np.float32)
        r = w.fleet.submit(probe).result(60)
        assert r.ok
        expect = nn.Sequential(nn.Linear(8, 8), nn.Tanh(),
                               nn.Linear(8, 1))
        expect.set_param_tree(served)
        np.testing.assert_allclose(np.asarray(r.output),
                                   np.asarray(expect.forward(
                                       probe[None]))[0], atol=1e-5)
        assert w.loop.last_rollback_latency_s is not None \
            and w.loop.last_rollback_latency_s < 30.0
        ev = [e for e in w.loop.events if e["kind"] == "deploy"
              and e["state"] == "rolled_back"]
        assert ev and ev[-1]["rules"] == ["loop/serving_burn"]
        assert ev[-1]["replicas"] == 3
        # recovery: the burn resolves as the error burst ages out of
        # its windows, and the next boundary deploys + confirms
        w.step(14)
        d = dict(w.loop.deploy_outcomes)
        assert d.get("confirmed", 0) >= confirmed + 1, d
        assert d.get("rolled_back", 0) == 1, d
        assert w.loop.bad_params_served == 0
        w.served_matches_trained()
    finally:
        w.stop()


def test_rollback_consumed_second_watch_trip_is_noop():
    """The captured deploy set is consumed by the rollback: with
    nothing newer deployed, another alert-driven rollback re-installs
    nothing (returns 0) rather than double-rolling."""
    w = _World(deploy_every=4, watch_intervals=2, cooldown_intervals=1)
    try:
        w.step(4)
        assert w.loop.state == "watch"
        assert w.fleet.rollback_last_deploy() == 3
        assert w.fleet.rollback_last_deploy() == 0
        assert w.fleet.deploy_rollbacks == 1
    finally:
        w.stop()


# ---------------------------------------------------------------------------
# ingest dead-man: a stalled stream pages instead of idling silently
# ---------------------------------------------------------------------------

def test_ingest_deadman_fires_on_stall_and_resolves_on_resume():
    w = _World(deploy_every=0)
    try:
        w.step(3)                      # the stream HAS reported
        w.ingest_per_interval = 0      # ...and now stalls
        w.loop.ingest = lambda: None
        fired = []
        for _ in range(8):
            fired += [a for a in w.loop.tick()
                      if a.rule == "loop/ingest_deadman"
                      and a.state == "firing"]
            w.t[0] += 1.0
            if fired:
                break
        assert fired, "dead-man never fired on a stalled stream"
        assert fired[0].severity == "page"
        assert w.loop.engine.verdict().status == "critical"
        # resume: the next fresh batch feeds the series and resolves
        w.loop.ingest = lambda: w.make_samples(8)
        resolved = []
        for _ in range(4):
            resolved += [a for a in w.loop.tick()
                         if a.rule == "loop/ingest_deadman"
                         and a.state == "resolved"]
            w.t[0] += 1.0
            if resolved:
                break
        assert resolved, "dead-man did not resolve on resume"
    finally:
        w.stop()


# ---------------------------------------------------------------------------
# loop surface details
# ---------------------------------------------------------------------------

def test_loop_requires_streamable_dataset():
    class _NotStreamable:
        pass

    with pytest.raises(TypeError, match="in-memory base dataset"):
        ContinuousLoop._resolve_base_dataset(_NotStreamable())


def test_snapshot_shape_and_outcome_vocabulary():
    w = _World(deploy_every=2, watch_intervals=1,
               cooldown_intervals=1)
    try:
        w.step(4)
        snap = w.loop.snapshot()
        for key in ("intervals", "state", "deploys",
                    "bad_params_served", "goodput", "alerts",
                    "events", "ingested_batches", "last_loss"):
            assert key in snap, key
        assert set(snap["deploys"]) <= set(DEPLOY_OUTCOMES)
        assert snap["intervals"] == 4
        assert snap["ingested_batches"] == 4
    finally:
        w.stop()
