"""Online health engine specs (telemetry/timeseries.py + slo.py,
serving/health.py, the autoscaler's SLO signal source, the training
HealthVerdict hook): windowed reducers with counter-reset tolerance,
multi-window burn-rate interplay, firing→resolved lifecycles under an
injectable clock, the staleness gate (no fresh samples ⇒ no verdict),
the chaos e2e (shed ramp + loss divergence + MFU collapse + replica
kill each detected within 3 evaluation intervals, zero spurious
alerts on the steady control), decision-for-decision autoscaler
equivalence between raw thresholds and SLO verdicts, and per-replica
degradation marks feeding the router's eject/re-admit machinery."""
import time

import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.telemetry import (MetricRecorder, MetricsRegistry,
                                 SloEngine, SloRule,
                                 TrainingHealthMonitor,
                                 default_serving_rules,
                                 default_training_rules)
from bigdl_tpu.telemetry import metric_names as M


class Clock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def tick(self, dt=1.0):
        self.t += dt
        return self.t


# ---------------------------------------------------------------------------
# recorder: rings, reducers, staleness, counter-reset tolerance
# ---------------------------------------------------------------------------

def test_recorder_ring_is_bounded_and_windowed():
    clk = Clock()
    r = MetricRecorder(capacity=8, clock=clk)
    for i in range(50):
        clk.t = float(i)
        r.observe("bigdl_train_loss", float(i))
    s = r.series("bigdl_train_loss")
    assert len(s) == 8                       # bounded
    assert s.last() == (49.0, 49.0)
    # window selects by time
    assert r.reduce("bigdl_train_loss", "min", window_s=3.0,
                    now=49.0) == 46.0
    assert r.reduce("bigdl_train_loss", "mean", window_s=1.0,
                    now=49.0) == pytest.approx(48.5)


def test_counter_rate_tolerates_resets():
    """A counter that reset (process restart) must read as its own
    value since the reset, never a negative increment — the
    prometheus convention."""
    clk = Clock()
    r = MetricRecorder(clock=clk)
    for t, v in [(0, 0), (1, 10), (2, 20), (3, 5), (4, 15)]:
        clk.t = float(t)
        r.observe("bigdl_serving_requests_total", v, kind="counter")
    # increases: 10 + 10 + 5 (reset: the new value IS the increment)
    # + 10 = 35 over 4s
    assert r.reduce("bigdl_serving_requests_total", "delta",
                    window_s=100, now=4.0) == 35.0
    assert r.reduce("bigdl_serving_requests_total", "rate",
                    window_s=100, now=4.0) == pytest.approx(8.75)
    # a gauge with the same samples reduces literally
    for t, v in [(0, 0), (1, 10), (2, 20), (3, 5), (4, 15)]:
        r.observe("bigdl_perf_mfu", v, now=float(t))
    assert r.reduce("bigdl_perf_mfu", "delta", window_s=100,
                    now=4.0) == 15.0


def test_counter_window_includes_boundary_sample():
    """The sample just BEFORE the window anchors the increase — a
    counter window must not lose the increment across its left edge."""
    clk = Clock()
    r = MetricRecorder(clock=clk)
    for t, v in [(0, 100), (10, 200), (20, 300)]:
        clk.t = float(t)
        r.observe("bigdl_replica_requests_total", v, kind="counter")
    # window [12, 20]: only the t=20 sample is inside, but the t=10
    # sample anchors it: increase 100 over 10s
    assert r.reduce("bigdl_replica_requests_total", "rate",
                    window_s=8.0, now=20.0) == pytest.approx(10.0)


def test_recorder_staleness_age_and_slope_and_mad():
    clk = Clock()
    r = MetricRecorder(clock=clk)
    assert r.age("bigdl_train_loss") is None       # never fed
    for i in range(10):
        clk.t = float(i)
        r.observe("bigdl_train_loss", 10.0 - i)
    clk.t = 30.0
    assert r.age("bigdl_train_loss") == pytest.approx(21.0)
    assert not r.fresh("bigdl_train_loss", max_age_s=5.0)
    assert r.fresh("bigdl_train_loss", max_age_s=30.0)
    # robust slope of a clean descent
    assert r.reduce("bigdl_train_loss", "slope", window_s=100,
                    now=9.0) == pytest.approx(-1.0)
    # one outlier cannot fake a trend (Theil-Sen)
    r.observe("bigdl_train_loss", 100.0, now=9.5)
    slope = r.reduce("bigdl_train_loss", "slope", window_s=100,
                     now=9.5)
    assert slope < 0
    # MAD score: a flat series that jumps scores off the chart
    for i in range(8):
        r.observe("bigdl_train_step_time_seconds", 0.1,
                  now=float(i))
    r.observe("bigdl_train_step_time_seconds", 0.5, now=8.0)
    score = r.reduce("bigdl_train_step_time_seconds", "mad_score",
                     window_s=100, now=8.0)
    assert score == float("inf")


def test_recorder_samples_registry_and_merged_views():
    """sample() decomposes live histograms into count/sum/quantile
    series; sample_metrics() accepts the merged cluster dict — the
    cross-host merge rides the existing aggregate fold."""
    from bigdl_tpu.telemetry import merge_metrics

    clk = Clock()
    reg = MetricsRegistry()
    reg.counter("bigdl_serving_requests_total", labels=("status",)) \
        .labels(status="ok").inc(5)
    h = reg.histogram("bigdl_serving_latency_seconds", window=16)
    for v in (0.01, 0.02, 0.03):
        h.observe(v)
    r = MetricRecorder(registry=reg, clock=clk)
    r.sample()
    assert r.reduce("bigdl_serving_requests_total", "last",
                    labels={"status": "ok"}, window_s=10) == 5.0
    assert r.reduce("bigdl_serving_latency_seconds", "last",
                    field="count", window_s=10) == 3.0
    assert r.reduce("bigdl_serving_latency_seconds", "last",
                    field="p99", window_s=10) is not None
    # the merged two-host view: counters summed, recorder rides it
    snap = reg.snapshot()["metrics"]
    merged = merge_metrics([snap, snap])
    r2 = MetricRecorder(clock=clk)
    r2.sample_metrics(merged)
    assert r2.reduce("bigdl_serving_requests_total", "last",
                     labels={"status": "ok"}, window_s=10) == 10.0


# ---------------------------------------------------------------------------
# engine: lifecycle, staleness gate, burn-rate interplay
# ---------------------------------------------------------------------------

def _engine(rules, clk):
    r = MetricRecorder(clock=clk)
    return r, SloEngine(r, rules=rules, registry=MetricsRegistry(),
                        clock=clk)


def test_threshold_firing_resolved_lifecycle_and_counters():
    clk = Clock()
    rule = SloRule(name="serving/both/p99",
                   family=M.AUTOSCALE_POOL_P99_SECONDS,
                   labels={"pool": "both"}, kind="threshold",
                   reduce="last", op=">=", threshold=0.5,
                   window_s=10.0, for_intervals=2,
                   resolve_intervals=2)
    r, eng = _engine([rule], clk)

    def step(v, dt=1.0):
        clk.tick(dt)
        r.observe(M.AUTOSCALE_POOL_P99_SECONDS, v,
                  labels={"pool": "both"})
        return eng.evaluate()

    assert step(0.1) == []                        # healthy
    assert step(0.9) == []                        # breach 1: sustain
    fired = step(0.9)                             # breach 2: FIRING
    assert [a.state for a in fired] == ["firing"]
    assert fired[0].rule == "serving/both/p99"
    assert fired[0].severity == "page"
    assert eng.verdict().status == "critical"
    assert eng.active_alerts()[0]["rule"] == "serving/both/p99"
    assert step(0.9) == []                        # still firing: quiet
    assert step(0.1) == []                        # clear 1: sustain
    resolved = step(0.1)                          # clear 2: RESOLVED
    assert [a.state for a in resolved] == ["resolved"]
    assert eng.verdict().status == "ok"
    assert eng.verdict().healthy
    # transitions counted per state in the registry
    fam = eng.registry.get(M.ALERTS_TOTAL)
    counts = {s["labels"]["state"]: s["value"]
              for s in eng.registry.snapshot()["metrics"]
              [M.ALERTS_TOTAL]["series"]}
    assert counts == {"firing": 1.0, "resolved": 1.0}
    assert fam is not None
    assert eng.registry.get(M.ALERTS_ACTIVE).value == 0.0


def test_staleness_gate_freezes_state_no_verdict():
    """No fresh samples ⇒ no verdict: a stale series neither fires a
    healthy rule nor resolves a firing one — state freezes until the
    signal returns."""
    clk = Clock()
    rule = SloRule(name="serving/both/p99",
                   family=M.AUTOSCALE_POOL_P99_SECONDS,
                   kind="threshold", reduce="last", op=">=",
                   threshold=0.5, window_s=5.0, staleness_s=3.0,
                   for_intervals=1, resolve_intervals=1)
    r, eng = _engine([rule], clk)
    clk.tick()
    r.observe(M.AUTOSCALE_POOL_P99_SECONDS, 0.9)
    assert [a.state for a in eng.evaluate()] == ["firing"]
    # the feed dies; evaluations keep coming — the alert must neither
    # resolve (no evidence of recovery) nor re-fire
    for _ in range(5):
        clk.tick(2.0)
        assert eng.evaluate() == []
    assert eng.verdict().status == "critical"     # held, not resolved
    # signal returns healthy: resolves on the next evaluation
    r.observe(M.AUTOSCALE_POOL_P99_SECONDS, 0.1)
    assert [a.state for a in eng.evaluate()] == ["resolved"]


def test_burn_rate_fast_slow_window_interplay():
    """The SRE multi-window form: a short error blip burns the fast
    window but not the slow one — no page.  A sustained burn trips
    both — page.  Recovery clears the fast window first — prompt
    resolution."""
    clk = Clock()
    L = {"pool": "both"}
    rule = SloRule(name="serving/both/error_budget",
                   family=M.AUTOSCALE_POOL_SHED_TOTAL, labels=L,
                   total_family=M.AUTOSCALE_POOL_REQUESTS_TOTAL,
                   total_labels=L, kind="burn_rate", budget=0.05,
                   fast_window_s=10.0, slow_window_s=60.0,
                   burn_factor=2.0, for_intervals=1,
                   resolve_intervals=1)
    r, eng = _engine([rule], clk)
    shed = total = 0

    def step(bad, good, dt=1.0):
        nonlocal shed, total
        clk.tick(dt)
        shed += bad
        total += bad + good
        r.observe(M.AUTOSCALE_POOL_SHED_TOTAL, shed, labels=L,
                  kind="counter")
        r.observe(M.AUTOSCALE_POOL_REQUESTS_TOTAL, total, labels=L,
                  kind="counter")
        return eng.evaluate()

    # a minute of clean traffic fills the slow window
    for _ in range(60):
        assert step(0, 100) == []
    # short blip: 3s of 100% errors — the fast window burns hot but
    # the slow window (60s of mostly-clean traffic) stays under
    # factor: NO alert.  (3s*100 errors / ~60s*100 reqs) / 0.05 ≈ 1.0
    for _ in range(3):
        assert step(100, 0) == []
    assert eng.verdict().status == "ok"
    # recovery, then a SUSTAINED burn: both windows trip -> page
    for _ in range(20):
        step(0, 100)
    fired = []
    for _ in range(12):
        fired += step(100, 0)
    assert [a.state for a in fired] == ["firing"]
    assert eng.verdict().status == "critical"
    # recovery: the fast window clears within ~its own width even
    # though the slow window still remembers the burn
    resolved = []
    for _ in range(12):
        resolved += step(0, 100)
    assert [a.state for a in resolved] == ["resolved"]


def test_absent_rule_is_the_dead_man_switch():
    clk = Clock()
    rule = SloRule(name="replica/r1/health_feed",
                   family=M.REPLICA_P99_SECONDS,
                   labels={"replica": "r1"}, kind="absent",
                   window_s=3.0, for_intervals=1,
                   resolve_intervals=1)
    r, eng = _engine([rule], clk)
    # never reported: no verdict, never a boot-time page
    clk.tick(10.0)
    assert eng.evaluate() == []
    # reports, then goes silent past the window: fires
    r.observe(M.REPLICA_P99_SECONDS, 0.01, labels={"replica": "r1"})
    assert eng.evaluate() == []
    clk.tick(5.0)
    assert [a.state for a in eng.evaluate()] == ["firing"]
    # feed resumes: resolves
    r.observe(M.REPLICA_P99_SECONDS, 0.01, labels={"replica": "r1"})
    assert [a.state for a in eng.evaluate()] == ["resolved"]


def test_anomaly_rule_step_time_drift():
    clk = Clock()
    rule = SloRule(name="training/step_time_drift",
                   family=M.TRAIN_STEP_TIME_SECONDS, kind="anomaly",
                   score=6.0, direction="up", window_s=100.0,
                   for_intervals=2, resolve_intervals=2,
                   min_samples=8)
    r, eng = _engine([rule], clk)
    for i in range(16):
        clk.tick()
        r.observe(M.TRAIN_STEP_TIME_SECONDS,
                  0.100 + 0.001 * (i % 3))
        assert eng.evaluate() == []
    fired = []
    for _ in range(3):                        # drift: 4x step time
        clk.tick()
        r.observe(M.TRAIN_STEP_TIME_SECONDS, 0.4)
        fired += eng.evaluate()
    assert [a.state for a in fired] == ["firing"]


# ---------------------------------------------------------------------------
# the chaos e2e: every injected breach detected within 3 evaluation
# intervals, resolves after recovery, zero spurious alerts on steady
# ---------------------------------------------------------------------------

def _chaos_rules():
    rules = default_serving_rules(
        "both", p99_high_s=0.5, shed_high=0.05, error_budget=0.02,
        window_s=30.0, fast_window_s=15.0, slow_window_s=60.0,
        for_intervals=2, resolve_intervals=2)
    rules += default_training_rules(
        goodput_floor=0.5, loss_window_s=60.0,
        divergence_ratio=1.5, mfu_drop_frac=0.5, window_s=60.0,
        for_intervals=2, resolve_intervals=2)
    # the training pack's stall rule would legitimately fire on the
    # steady segment's flat-converged loss; the chaos spec exercises
    # divergence, so give stall a margin that tracks "descending"
    rules = [r for r in rules if r.name != "training/loss_stall"]
    rules.append(SloRule(
        name="replica/r1/health_feed", family=M.REPLICA_P99_SECONDS,
        labels={"replica": "r1"}, kind="absent", window_s=12.0,
        resolve_intervals=1,
        description="replica r1 health feed went silent"))
    return rules


class _ChaosHarness:
    """Scripted fleet+training signal generator over an injected
    clock: one tick = one evaluation interval (5s)."""

    INTERVAL = 5.0

    def __init__(self):
        self.clk = Clock()
        self.r = MetricRecorder(clock=self.clk)
        self.eng = SloEngine(self.r, rules=_chaos_rules(),
                             registry=MetricsRegistry(),
                             clock=self.clk)
        self.shed = self.total = 0
        self.loss = 4.0
        self.mfu = 0.5

    def tick(self, *, shed_frac=0.0, diverge=False, kill_replica=False,
             mfu=None):
        self.clk.tick(self.INTERVAL)
        L = {"pool": "both"}
        r = self.r
        n = 500
        bad = int(n * shed_frac)
        self.shed += bad
        self.total += n
        r.observe(M.AUTOSCALE_POOL_P99_SECONDS, 0.040, labels=L)
        r.observe(M.AUTOSCALE_POOL_SHED_RATE, shed_frac, labels=L)
        r.observe(M.AUTOSCALE_POOL_KV_OCCUPANCY, 0.3, labels=L)
        r.observe(M.AUTOSCALE_POOL_SHED_TOTAL, self.shed, labels=L,
                  kind="counter")
        r.observe(M.AUTOSCALE_POOL_REQUESTS_TOTAL, self.total,
                  labels=L, kind="counter")
        self.loss = self.loss * (1.8 if diverge else 0.98)
        r.observe(M.TRAIN_LOSS, self.loss)
        r.observe(M.TRAIN_STEP_TIME_SECONDS, 0.1)
        r.observe(M.GOODPUT_PRODUCTIVE_FRACTION, 0.97)
        if mfu is not None:
            self.mfu = mfu
        r.observe(M.PERF_MFU, self.mfu)
        if not kill_replica:
            r.observe(M.REPLICA_P99_SECONDS, 0.02,
                      labels={"replica": "r1"})
        return self.eng.evaluate()


def test_chaos_e2e_detects_each_breach_within_3_intervals():
    h = _ChaosHarness()
    # steady warmup: no alerts
    for _ in range(20):
        assert h.tick() == [], h.eng.active_alerts()

    def fire_within(n, **kw):
        for i in range(1, n + 1):
            alerts = h.tick(**kw)
            if any(a.state == "firing" for a in alerts):
                return i, [a.rule for a in alerts
                           if a.state == "firing"]
        raise AssertionError(
            f"no alert within {n} intervals for {kw}; "
            f"active={h.eng.active_alerts()}")

    def resolve_within(n, rules, **kw):
        resolved = []
        for _ in range(n):
            resolved += [a.rule for a in h.tick(**kw)
                         if a.state == "resolved"]
            if set(rules) <= set(resolved):
                return
        raise AssertionError(f"{rules} did not resolve; got "
                             f"{resolved}")

    # 1) injected shed ramp: 30% of traffic shed
    took, rules = fire_within(3, shed_frac=0.30)
    assert took <= 3 and "serving/both/shed_rate" in rules
    # keep shedding: the error-budget burn joins within the window
    for _ in range(4):
        h.tick(shed_frac=0.30)
    assert "serving/both/error_budget" in {
        a["rule"] for a in h.eng.active_alerts()}
    resolve_within(16, ["serving/both/shed_rate",
                        "serving/both/error_budget"])

    # 2) loss divergence
    took, rules = fire_within(3, diverge=True)
    assert took <= 3 and "training/loss_divergence" in rules
    # recovery: loss descends again and falls back under ratio x min
    for _ in range(30):
        h.tick()
        if not h.eng.firing(["training/loss_divergence"]):
            break
    assert not h.eng.firing(["training/loss_divergence"])

    # 3) MFU collapse: 0.5 -> 0.1
    took, rules = fire_within(3, mfu=0.1)
    assert took <= 3 and "training/mfu_collapse" in rules
    resolve_within(30, ["training/mfu_collapse"], mfu=0.5)

    # 4) replica kill: health feed goes silent
    took, rules = fire_within(3, kill_replica=True)
    assert took <= 3 and "replica/r1/health_feed" in rules
    resolve_within(3, ["replica/r1/health_feed"])

    # everything resolved; the engine is quiet again
    assert h.eng.verdict().status == "ok"


@pytest.mark.parametrize("intervals", [200, 60])
def test_chaos_steady_control_zero_false_positives(intervals):
    h = _ChaosHarness()
    alerts = []
    for _ in range(intervals):
        alerts += h.tick()
    assert alerts == []
    assert h.eng.verdict().status == "ok"
    snap = h.eng.snapshot()
    assert snap["active"] == [] and snap["verdict"] == "ok"


# ---------------------------------------------------------------------------
# training health monitor + the driver hook
# ---------------------------------------------------------------------------

def test_training_monitor_verdict_flips_on_divergence():
    clk = Clock()
    mon = TrainingHealthMonitor(
        rules=default_training_rules(for_intervals=2,
                                     resolve_intervals=2,
                                     loss_window_s=60.0),
        every_n_steps=1, registry=MetricsRegistry(),
        clock=clk)
    loss = 4.0
    for i in range(20):
        clk.tick()
        loss *= 0.95
        mon.on_step(i, loss, 0.1)
    assert mon.verdict().healthy
    for i in range(20, 26):
        clk.tick()
        loss *= 2.0
        mon.on_step(i, loss, 0.1)
    v = mon.verdict()
    assert v.status == "critical"
    assert "training/loss_divergence" in v.firing
    # NaN losses never poison the window (they are simply not fed)
    mon.on_step(26, float("nan"), 0.1)
    assert mon.recorder.reduce(M.TRAIN_LOSS, "last",
                               window_s=1e9) == loss


def test_optimizer_health_hook_feeds_monitor():
    """The driver hook: a LocalOptimizer with a monitor attached
    feeds it every iteration, the verdict is answerable live, and a
    healthy run reads ok."""
    from bigdl_tpu.dataset import Sample, array
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.telemetry import MetricsRegistry as MR, Telemetry

    rng = np.random.RandomState(0)
    x = rng.rand(64, 8).astype(np.float32)
    w = rng.rand(8, 1).astype(np.float32)
    y = (x @ w).astype(np.float32)
    data = array([Sample(x[i], y[i]) for i in range(64)])
    model = nn.Sequential(nn.Linear(8, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = LocalOptimizer(model, data, nn.MSECriterion(),
                         batch_size=32)
    opt.set_optim_method(SGD(learning_rate=0.05))
    opt.set_end_when(max_iteration(12))
    tm = Telemetry(registry=MR())
    opt.set_telemetry(tm)
    # divergence-only rules: a 12-step toy run may legitimately
    # plateau (stall) and its wall clock is all compile (goodput)
    # without being sick
    mon = TrainingHealthMonitor(
        rules=[r for r in default_training_rules()
               if r.name == "training/loss_divergence"],
        every_n_steps=2)
    opt.set_health_monitor(mon)
    assert mon.telemetry is tm                 # adopted at attach
    assert tm.slo is mon.engine                # payload publishes it
    opt.optimize()
    assert len(mon.recorder.series(M.TRAIN_LOSS)) >= 12
    v = opt.health_verdict()
    assert v is not None and v.healthy, v
    # the engine snapshot rides the telemetry payload for run_report
    payload = tm.payload(step=12)
    assert payload["alerts"]["verdict"] == "ok"


# ---------------------------------------------------------------------------
# autoscaler: SLO verdicts reproduce raw-threshold decisions
# ---------------------------------------------------------------------------

class _StubServer:
    def __init__(self, role):
        self.role = role


class _StubRouter:
    def __init__(self):
        from bigdl_tpu.serving.metrics import ServingMetrics

        self.metrics = ServingMetrics()
        self.health = {}

    def health_of(self, rid):
        return self.health.get(rid)


class _StubFleet:
    """Just enough fleet for the Autoscaler: scripted health
    snapshots, recorded add/remove calls."""

    def __init__(self, roles):
        self.servers = {rid: _StubServer(role)
                        for rid, role in roles.items()}
        self.router = _StubRouter()
        self.actions = []

    def add_replica(self, rid, server):
        self.servers[rid] = server
        self.actions.append(("add", rid))

    def remove_replica(self, rid, timeout=None, drain=True):
        self.servers.pop(rid, None)
        self.router.health.pop(rid, None)
        self.actions.append(("remove", rid))
        return True


def _scripted_rounds():
    """A ramp scenario: quiet -> p99 breach sustained -> recovery ->
    idle drain -> a noisy single-sample blip that must scale
    nothing."""
    quiet = {"ready": True, "role": "both", "p99_s": 0.02,
             "queue_depth": 0, "shed_total": 0, "requests_total": 0}
    rounds = []
    req = 0
    for spec in ([dict(p99=0.02, dreq=50)] * 3        # warm, quiet
                 + [dict(p99=2.0, dreq=200)] * 4      # sustained burn
                 + [dict(p99=0.02, dreq=50)] * 2      # recovered
                 + [dict(p99=0.01, dreq=50)] * 6      # idle-ish
                 + [dict(p99=3.0, dreq=200)]          # one noisy blip
                 + [dict(p99=0.01, dreq=50)] * 4):
        req += spec["dreq"]
        h = dict(quiet, p99_s=spec["p99"], requests_total=req)
        rounds.append(h)
    return rounds


def _drive(signal_source):
    from bigdl_tpu.serving.autoscale import AutoscalePolicy, Autoscaler

    clk = Clock()
    fleet = _StubFleet({"r0": "both"})
    policy = AutoscalePolicy(min_replicas=1, max_replicas=3,
                             p99_high_s=0.5, sustain=2,
                             p99_idle_s=0.05, idle_sustain=3,
                             cooldown_s=0.0,
                             idle_requests_delta=0)

    def factory(rid, role):
        return _StubServer(role)

    asc = Autoscaler(fleet, factory, policy=policy,
                     signal_source=signal_source, clock=clk)
    decisions = []
    for h in _scripted_rounds():
        clk.tick()
        # every CURRENT member reports the scripted health
        fleet.router.health = {rid: dict(h) for rid in fleet.servers}
        for d in asc.evaluate_once():
            decisions.append((d["pool"], d["direction"]))
    return asc, fleet, decisions


def test_autoscaler_slo_reproduces_raw_decisions():
    """Decision-for-decision: the SLO-verdict signal source must
    reproduce the raw-threshold path's scale-up/scale-down sequence
    on the same scripted ramp (the SERVING_r03 reproduction bar, in
    deterministic miniature)."""
    asc_raw, fleet_raw, raw = _drive("raw")
    asc_slo, fleet_slo, slo = _drive("slo")
    assert raw == slo
    assert fleet_raw.actions == fleet_slo.actions
    # the ramp actually exercised both directions
    assert ("both", "up") in raw and ("both", "down") in raw
    # ...and the SLO path additionally recorded every breach as a
    # structured alert transition
    assert asc_slo.slo_engine is not None
    states = [a["state"] for a in asc_slo.slo_engine.snapshot()
              ["recent"]]
    assert "firing" in states and "resolved" in states
    assert asc_raw.slo_engine is None


def test_autoscaler_slo_traffic_gate_is_staleness():
    """Over no fresh traffic a stale windowed p99 renders no verdict:
    the pool reads idle, never a breach — the raw activity gate,
    generalized through the recorder."""
    from bigdl_tpu.serving.autoscale import AutoscalePolicy, Autoscaler

    clk = Clock()
    fleet = _StubFleet({"r0": "both"})
    policy = AutoscalePolicy(min_replicas=1, max_replicas=3,
                             p99_high_s=0.5, sustain=1,
                             cooldown_s=0.0, idle_requests_delta=0)
    asc = Autoscaler(fleet, lambda rid, role: _StubServer(role),
                     policy=policy, signal_source="slo", clock=clk)
    # a stale-high p99 with NO fresh requests must scale nothing
    fleet.router.health = {"r0": {
        "ready": True, "role": "both", "p99_s": 9.9,
        "queue_depth": 0, "shed_total": 0, "requests_total": 0}}
    for _ in range(4):
        clk.tick()
        assert asc.evaluate_once() == []
    assert fleet.actions == []


# ---------------------------------------------------------------------------
# fleet integration: degradation marks ride the eject machinery
# ---------------------------------------------------------------------------

def _small_model():
    return nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 3),
                         nn.LogSoftMax())


def test_router_degraded_mark_ejects_and_clears():
    from bigdl_tpu.serving import ServingFleet

    fl = ServingFleet.build(_small_model(), n_replicas=3,
                            server_kw=dict(max_batch=8),
                            heartbeat_timeout=0.4,
                            pump_interval_s=0)   # pump by hand
    fl.start()
    try:
        assert set(fl.router.members) == {"r0", "r1", "r2"}
        fl.router.mark_degraded("r1", "replica/r1/p99")
        # unroutable immediately, ejected at the next refresh
        assert "r1" not in fl.router.live()
        fl.pump_once()
        assert "r1" not in fl.router.members
        assert fl.router.degraded == {"r1": "replica/r1/p99"}
        # still beating + ready, but NOT re-admitted while marked
        fl.pump_once()
        assert "r1" not in fl.router.members
        # requests keep resolving on the survivors
        rng = np.random.RandomState(0)
        res = fl.submit(rng.rand(4).astype(np.float32)).result(60)
        assert res.ok
        # mark clears: the normal returner path re-admits it
        fl.router.clear_degraded("r1")
        fl.pump_once()
        assert "r1" in fl.router.members
        assert "r1" in fl.router.live()
        assert fl.router.snapshot()["degraded"] == {}
    finally:
        fl.stop(10)


def test_fleet_health_monitor_marks_slow_replica_degraded():
    """The answering-but-answering-badly case: a replica whose
    published p99 breaches the per-replica rule is marked degraded,
    ejected, and re-admitted after its rule resolves."""
    from bigdl_tpu.serving import ReplicaHealthPolicy, ServingFleet

    fl = ServingFleet.build(
        _small_model(), n_replicas=3,
        server_kw=dict(max_batch=8),
        heartbeat_timeout=5.0, pump_interval_s=0,
        health=True,
        health_kw=dict(policy=ReplicaHealthPolicy(
            p99_high_s=0.5, window_s=30.0, feed_dead_s=30.0,
            for_intervals=2, resolve_intervals=2)))
    fl.start()
    try:
        mon = fl.health_monitor
        assert mon is not None
        # forge a slow replica: publish health with a breaching p99
        # (the monitor reads the router's health view)
        import json as _json

        from bigdl_tpu.serving.router import HEALTH_PREFIX

        def publish(rid, p99, ts):
            h = {"replica": rid, "ready": True, "healthy": True,
                 "draining": False, "queue_depth": 0,
                 "breaker_state": "closed", "role": "both",
                 "p99_s": p99, "served_ok": 100, "shed_total": 0,
                 "requests_total": 100, "ts": ts}
            fl.transport.put(HEALTH_PREFIX + rid, _json.dumps(h))

        # forge-publish, refresh the router's health cache, then let
        # the monitor evaluate — the agents' own pump would overwrite
        # the forged snapshots, so the rounds are driven by hand
        for i in range(3):
            for rid in ("r0", "r1", "r2"):
                publish(rid, 2.0 if rid == "r1" else 0.01,
                        ts=1000.0 + i)
            fl.router.refresh()
            mon.observe()
        assert "r1" in fl.router.degraded
        assert "r1" in mon.degraded()
        fl.router.refresh()               # the eject round
        assert "r1" not in fl.router.members
        snap = fl.snapshot()
        assert snap["health"]["degraded"]
        # alert counters folded into the fleet metrics view
        assert "bigdl_alerts_total" in snap["metrics"]
        # recovery: p99 back under threshold for resolve_intervals
        for i in range(3):
            for rid in ("r0", "r1", "r2"):
                publish(rid, 0.01, ts=2000.0 + i)
            fl.router.refresh()
            mon.observe()
        assert "r1" not in fl.router.degraded
        fl.pump_once()                    # returner path re-admits
        assert "r1" in fl.router.members
    finally:
        fl.stop(10)
