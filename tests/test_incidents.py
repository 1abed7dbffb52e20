"""Incident-engine specs (telemetry/events.py + incidents.py plus the
cluster folds and the observability satellites): the typed bounded
change journal (closed kind vocabulary, scope filtering, throttled
high-rate sites, since/until slicing), the incident lifecycle (open on
a fresh firing transition, flap-guard cooldown, black-box capture of
the breached + scope-correlated series over the pre-window, deflection
onset preceding the firing edge, post-window finalize), chaos-scored
suspect ranking (scope match outranks fleet-wide outranks scope
mismatch; ground-truth injections land on top), the
``merge_alerts`` duplicate-(rule, host) dedupe regression, the
``merge_incidents`` cluster fold, the payload/merge_cluster plumbing,
the runtime metric-name drift guard, and the trace_report
``_default`` tenant bucket."""
import pytest

from bigdl_tpu.telemetry import (ChangeJournal, IncidentEngine,
                                 IncidentPolicy, MetricRecorder,
                                 MetricsRegistry, SloEngine, SloRule,
                                 Telemetry, merge_alerts,
                                 merge_cluster, merge_incidents,
                                 record_change, reset_default_journal)
from bigdl_tpu.resilience import faults
from bigdl_tpu.telemetry import events, metric_names as M
from bigdl_tpu.telemetry.events import CHANGE_EVENT_KINDS, SCOPE_KEYS


class Clock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def tick(self, dt=1.0):
        self.t += dt
        return self.t


# ---------------------------------------------------------------------------
# change journal: vocabulary, scope, bounds, throttling, slicing
# ---------------------------------------------------------------------------

def test_journal_records_ordered_scoped_events():
    c = Clock(100.0)
    reg = MetricsRegistry()
    j = ChangeJournal(clock=c, registry=reg)
    e0 = j.record("deploy_started", "version=v2", source="fleet",
                  model="alpha", replica="r0")
    c.tick()
    # None scope values drop (optional model/tenant pass straight
    # through); keys outside SCOPE_KEYS drop too
    e1 = j.record("autoscale_up", pool="decode", tenant=None,
                  bogus="nope")
    assert (e0.seq, e1.seq) == (0, 1)
    assert e0.at == 100.0 and e1.at == 101.0
    assert e0.scope == {"model": "alpha", "replica": "r0"}
    assert e1.scope == {"pool": "decode"}
    assert not e0.ground_truth
    assert set(e0.scope) <= set(SCOPE_KEYS)
    counts = {s["labels"]["kind"]: s["value"]
              for s in reg.snapshot()["metrics"]
              [M.CHANGE_EVENTS_TOTAL]["series"]}
    assert counts == {"deploy_started": 1.0, "autoscale_up": 1.0}
    d = e0.to_dict()
    assert d["kind"] == "deploy_started" and d["seq"] == 0


def test_journal_rejects_unlisted_kind():
    j = ChangeJournal(registry=MetricsRegistry())
    with pytest.raises(ValueError, match="unknown change-event kind"):
        j.record("coffee_spilled")
    assert "deploy_started" in CHANGE_EVENT_KINDS


def test_journal_ring_is_bounded_but_counts_everything():
    j = ChangeJournal(capacity=4, clock=Clock(),
                      registry=MetricsRegistry())
    for i in range(10):
        j.record("membership_change", f"n={i}", now=float(i))
    assert len(j) == 4
    snap = j.snapshot()
    assert snap["recorded"] == 10 and snap["capacity"] == 4
    assert [e["detail"] for e in snap["events"]] == \
        ["n=6", "n=7", "n=8", "n=9"]


def test_journal_since_until_slicing_inclusive():
    j = ChangeJournal(registry=MetricsRegistry())
    for t in (1.0, 2.0, 3.0, 4.0):
        j.record("breaker_open", now=t, replica=f"r{int(t)}")
    ats = [e.at for e in j.events(since=2.0, until=3.0)]
    assert ats == [2.0, 3.0]
    assert [e.at for e in j.events(since=3.0)] == [3.0, 4.0]
    assert [e.at for e in j.events(until=1.0)] == [1.0]


def test_journal_throttles_high_rate_sites():
    c = Clock(0.0)
    j = ChangeJournal(clock=c, registry=MetricsRegistry())
    assert j.record_throttled("tenant_shed", key="a",
                              tenant="a") is not None
    # a flood inside the interval must not evict the deploy event
    # that explains it out of the bounded ring
    for _ in range(50):
        assert j.record_throttled("tenant_shed", key="a",
                                  tenant="a") is None
    # a different key is its own throttle bucket
    assert j.record_throttled("tenant_shed", key="b",
                              tenant="b") is not None
    c.tick(2.0)
    assert j.record_throttled("tenant_shed", key="a",
                              tenant="a") is not None
    assert len(j) == 3 and j.dropped == 50
    assert j.snapshot()["dropped_throttled"] == 50


def test_default_journal_record_change_and_reset_isolation():
    c = Clock(10.0)
    j = reset_default_journal(clock=c)
    try:
        record_change("model_registered", "version=1", model="m")
        record_change("tenant_shed", tenant="t",
                      throttle_key="t/quota")
        record_change("tenant_shed", tenant="t",
                      throttle_key="t/quota")   # throttled away
        assert [e.kind for e in j.events()] == \
            ["model_registered", "tenant_shed"]
        j2 = reset_default_journal()
        assert len(j2) == 0 and j2 is not j
    finally:
        reset_default_journal()


# ---------------------------------------------------------------------------
# incident lifecycle: open, capture, onset, finalize
# ---------------------------------------------------------------------------

def _wire(rules, pre_window_s=60.0, post_intervals=2, **policy_kw):
    c = Clock(1000.0)
    rec = MetricRecorder(clock=c)
    j = ChangeJournal(clock=c, registry=MetricsRegistry())
    eng = SloEngine(rec, rules=rules, registry=MetricsRegistry(),
                    clock=c)
    reg = MetricsRegistry()
    ie = IncidentEngine(
        rec, journal=j, engine=eng, registry=reg,
        policy=IncidentPolicy(pre_window_s=pre_window_s,
                              post_intervals=post_intervals,
                              **policy_kw),
        clock=c)
    return c, rec, j, eng, ie, reg


P99_RULE = [SloRule(name="replica/r1/p99",
                    family=M.REPLICA_P99_SECONDS,
                    labels={"replica": "r1"}, kind="threshold",
                    reduce="last", op=">=", threshold=1.0,
                    window_s=30.0, for_intervals=2,
                    resolve_intervals=2,
                    description="replica r1 p99 >= 1s")]


def test_incident_opens_on_firing_and_finalizes_after_post_window():
    c, rec, j, eng, ie, reg = _wire(P99_RULE)
    L = {"replica": "r1"}
    for _ in range(10):                       # healthy baseline
        rec.observe(M.REPLICA_P99_SECONDS, 0.05, labels=L)
        assert ie.observe(eng.evaluate()) == []
        c.tick(5.0)
    j.record("deploy_started", "version=v2", replica="r1",
             model="alpha")
    finalized = []
    rounds_after_open = 0
    for _ in range(8):
        rec.observe(M.REPLICA_P99_SECONDS, 2.5, labels=L)
        done = ie.observe(eng.evaluate())
        finalized.extend(done)
        if ie.opened_total:
            rounds_after_open += 1
        if finalized:
            break
        c.tick(5.0)
    assert len(finalized) == 1
    inc = finalized[0]
    # the post-window: opened, held open post_intervals observe
    # rounds, then finalized
    assert rounds_after_open == 3 and inc.status == "finalized"
    assert inc.rule == "replica/r1/p99" and inc.labels == L
    d = inc.to_dict()
    breached_keys = [k for k in d["series"]
                     if k.startswith(M.REPLICA_P99_SECONDS)]
    assert breached_keys, d["series"].keys()
    assert any(e["kind"] == "deploy_started" for e in d["events"])
    assert ie.opened_total == 1 and ie.open_incidents() == []
    snap = ie.snapshot()
    assert snap["opened"] == 1 and len(snap["recent"]) == 1
    assert snap["open"] == []
    counts = {s["labels"]["severity"]: s["value"]
              for s in reg.snapshot()["metrics"]
              [M.INCIDENTS_TOTAL]["series"]}
    assert counts == {"page": 1.0}


def test_cooldown_flap_guard_blocks_refire():
    c, rec, j, eng, ie, _ = _wire(P99_RULE, post_intervals=1,
                                  cooldown_s=10_000.0)
    L = {"replica": "r1"}

    def rounds(v, n):
        for _ in range(n):
            rec.observe(M.REPLICA_P99_SECONDS, v, labels=L)
            ie.observe(eng.evaluate())
            c.tick(5.0)

    rounds(0.05, 6)
    rounds(2.5, 4)          # fire -> open -> finalize
    assert ie.opened_total == 1
    rounds(0.05, 4)         # resolve
    rounds(2.5, 4)          # re-fires inside the cooldown window
    assert ie.opened_total == 1     # flap guard held
    assert len(ie.incidents()) == 1


def test_capture_freezes_correlated_series_inside_pre_window():
    c, rec, j, eng, ie, _ = _wire(P99_RULE, pre_window_s=20.0)
    breached = {"replica": "r1"}
    neighbor = {"replica": "r1", "pool": "decode"}
    stranger = {"replica": "r9"}
    for i in range(12):
        v = 0.05 if i < 8 else 2.5
        rec.observe(M.REPLICA_P99_SECONDS, v, labels=breached)
        rec.observe(M.REPLICA_QUEUE_DEPTH, float(i), labels=neighbor)
        rec.observe(M.REPLICA_QUEUE_DEPTH, 1.0, labels=stranger)
        done = ie.observe(eng.evaluate())
        if done:
            break
        c.tick(5.0)
    inc = done[0].to_dict()
    keys = list(inc["series"])
    # the breached series and the label-correlated neighbor are in the
    # black box; the unrelated replica is not
    assert any(M.REPLICA_P99_SECONDS in k for k in keys)
    assert any(M.REPLICA_QUEUE_DEPTH in k and "decode" in k
               for k in keys)
    assert not any("r9" in k for k in keys)
    # every frozen sample sits inside [breach - pre_window, breach]
    since = inc["opened_at"] - 20.0
    for samples in inc["series"].values():
        assert all(t >= since for t, _v in samples)


def test_onset_precedes_firing_edge():
    """for_intervals hysteresis means the true deflection PRECEDES the
    firing edge — alignment against onset is what separates cause from
    reaction."""
    c, rec, j, eng, ie, _ = _wire(P99_RULE)
    L = {"replica": "r1"}
    deflect_at = None
    done = []
    for i in range(16):
        v = 0.05 if i < 10 else 2.5
        if i == 10:
            deflect_at = c()
        rec.observe(M.REPLICA_P99_SECONDS, v, labels=L)
        done = ie.observe(eng.evaluate())
        if done:
            break
        c.tick(5.0)
    inc = done[0]
    assert inc.onset_at == deflect_at
    assert inc.onset_at < inc.opened_at


@pytest.mark.parametrize("healthy,injector",
                         [(10, None), (60, faults.kill_replica)])
def test_suspect_ranking_scope_beats_fleet_wide_beats_mismatch(
        healthy, injector, monkeypatch, request):
    c, rec, j, eng, ie, _ = _wire(P99_RULE)
    monkeypatch.setattr(events, "_default", j)
    L = {"replica": "r1"}
    for _ in range(healthy):
        rec.observe(M.REPLICA_P99_SECONDS, 0.05, labels=L)
        assert ie.observe(eng.evaluate()) == []
        c.tick(5.0)
    assert ie.opened_total == 0 and ie.open_incidents() == []
    # three candidate causes, same instant: a ground-truth chaos
    # injection on the breached replica, a fleet-wide membership
    # change, and an autoscale move on a DIFFERENT replica (shared
    # key, conflicting value -> ranked below fleet-wide)
    if injector:        # armed, never fired: there is no fleet here
        fault = injector("r1")
        fault.__enter__()
        request.addfinalizer(lambda: fault.__exit__(None, None, None))
    else:
        j.record("chaos_inject", "kind=kill", ground_truth=True,
                 replica="r1")
    j.record("membership_change", "incarnation=7")
    j.record("autoscale_up", "scale 2->3", replica="r9",
             pool="decode")
    done = []
    for _ in range(8):
        rec.observe(M.REPLICA_P99_SECONDS, 2.5, labels=L)
        done = ie.observe(eng.evaluate())
        if done:
            break
        c.tick(5.0)
    suspects = done[0].suspects
    kinds = [s["kind"] for s in suspects]
    assert kinds[0] == "chaos_inject" and suspects[0]["ground_truth"]
    assert kinds.index("membership_change") < \
        kinds.index("autoscale_up")
    scores = [s["score"] for s in suspects]
    assert scores == sorted(scores, reverse=True)
    assert [s["rank"] for s in suspects] == \
        list(range(1, len(suspects) + 1))


def test_trace_provider_is_captured_and_guarded():
    def provider(since, until):
        return [{"trace_id": "t1", "since": since, "until": until}]

    c, rec, j, eng, ie, _ = _wire(P99_RULE)
    ie.trace_provider = provider
    L = {"replica": "r1"}
    done = []
    for i in range(16):
        rec.observe(M.REPLICA_P99_SECONDS,
                    0.05 if i < 8 else 2.5, labels=L)
        done = ie.observe(eng.evaluate())
        if done:
            break
        c.tick(5.0)
    assert done[0].traces and done[0].traces[0]["trace_id"] == "t1"

    # a raising provider degrades to an empty capture, never a crash
    def boom(since, until):
        raise RuntimeError("sampler gone")

    c, rec, j, eng, ie, _ = _wire(P99_RULE, cooldown_s=0.0)
    ie.trace_provider = boom
    done = []
    for i in range(16):
        rec.observe(M.REPLICA_P99_SECONDS,
                    0.05 if i < 8 else 2.5, labels=L)
        done = ie.observe(eng.evaluate())
        if done:
            break
        c.tick(5.0)
    assert done and done[0].traces == []


def test_observe_accepts_alert_dicts_and_ignores_non_firing():
    c, rec, j, eng, ie, _ = _wire(P99_RULE)
    ie.observe([{"rule": "x/y", "state": "resolved", "at": c(),
                 "severity": "page", "labels": {}}])
    assert ie.opened_total == 0
    ie.observe([{"rule": "x/y", "state": "firing", "at": c(),
                 "severity": "ticket", "value": 9.0,
                 "labels": {"replica": "r1"}}])
    assert ie.opened_total == 1
    assert ie.open_incidents()[0].severity == "ticket"


# ---------------------------------------------------------------------------
# merge_alerts duplicate-(rule, host) union regression
# ---------------------------------------------------------------------------

def test_merge_alerts_dedupes_duplicate_rule_host_worst_wins():
    """A rule reported twice for one host (overlapping snapshot
    collections / re-published payloads) unions to ONE deterministic
    entry — severity page beats ticket, firing beats resolved at the
    same transition instant, and the fold is order-independent."""
    dup = {"alerts": {
        "active": [
            {"rule": "replica/r1/p99", "severity": "ticket",
             "since": 5.0, "labels": {"replica": "r1"}},
            {"rule": "replica/r1/p99", "severity": "page",
             "since": 9.0, "labels": {"replica": "r1"}},
        ],
        "recent": [
            {"rule": "replica/r1/p99", "state": "resolved", "at": 4.0},
            {"rule": "replica/r1/p99", "state": "firing", "at": 4.0},
            {"rule": "replica/r1/p99", "state": "firing", "at": 4.0},
        ]}}
    other = {"alerts": {
        "active": [{"rule": "replica/r1/p99", "severity": "ticket",
                    "since": 2.0}],
        "recent": [{"rule": "replica/r1/p99", "state": "firing",
                    "at": 2.0}]}}
    merged = merge_alerts({"h2": other, "h1": dup})
    assert merged["hosts"] == ["h1", "h2"]
    # one active entry per (rule, host); h1 kept the page
    assert [(a["host"], a["severity"]) for a in merged["active"]] == \
        [("h1", "page"), ("h2", "ticket")]
    # the three h1 recents collapsed to one, state firing won
    h1_recent = [a for a in merged["recent"] if a["host"] == "h1"]
    assert len(h1_recent) == 1
    assert h1_recent[0]["state"] == "firing"
    assert merged["totals"] == {"firing": 2}
    assert merged["verdict"] == "critical"
    # deterministic: recent ordered by (at, rule, host)
    assert [a["host"] for a in merged["recent"]] == ["h2", "h1"]


def test_merge_alerts_none_when_no_engine_snapshots():
    assert merge_alerts({"h1": {"metrics": {}}, "h2": {}}) is None


# ---------------------------------------------------------------------------
# merge_incidents cluster fold
# ---------------------------------------------------------------------------

def _inc(id_, status, opened_at, rule="r/p99"):
    return {"id": id_, "rule": rule, "severity": "page",
            "opened_at": opened_at, "status": status,
            "labels": {}, "suspects": [], "events": []}


def test_merge_incidents_host_stamps_dedupes_and_orders():
    p1 = {"incidents": {"open": [_inc("inc-0002", "open", 20.0)],
                        "recent": [_inc("inc-0001", "finalized", 5.0)],
                        "opened": 2}}
    p2 = {"incidents": {"open": [],
                        "recent": [_inc("inc-0001", "finalized", 9.0)],
                        "opened": 1}}
    merged = merge_incidents({"h1": p1, "h2": p2})
    assert merged["hosts"] == ["h1", "h2"] and merged["opened"] == 3
    # same incident id on two hosts is two rows (per-host engines)
    assert [(i["id"], i["host"]) for i in merged["recent"]] == \
        [("inc-0001", "h1"), ("inc-0001", "h2")]
    assert [(i["id"], i["host"]) for i in merged["open"]] == \
        [("inc-0002", "h1")]
    assert merge_incidents({"h": {"alerts": {}}}) is None


def test_merge_incidents_finalized_republish_supersedes_open():
    p = {"incidents": {
        "open": [_inc("inc-0001", "open", 5.0)],
        "recent": [_inc("inc-0001", "finalized", 5.0)],
        "opened": 1}}
    merged = merge_incidents({"h1": p})
    assert merged["open"] == []
    assert [i["status"] for i in merged["recent"]] == ["finalized"]


def test_payload_and_merge_cluster_carry_incidents():
    reg = MetricsRegistry()
    tel = Telemetry(registry=reg)
    assert tel.payload()["incidents"] is None
    rec = MetricRecorder(clock=Clock())
    tel.incidents = IncidentEngine(
        rec, journal=ChangeJournal(registry=MetricsRegistry()),
        registry=MetricsRegistry())
    snap = tel.payload()["incidents"]
    assert snap == {"open": [], "recent": [], "opened": 0}
    cluster = merge_cluster({"h1": tel.payload()})
    assert cluster["incidents"]["hosts"] == ["h1"]


# ---------------------------------------------------------------------------
# satellite: runtime metric-name drift guard
# ---------------------------------------------------------------------------

def test_runtime_registered_families_stay_in_shared_table():
    """The static lint (test_telemetry) catches literals; this guard
    catches the RUNTIME side — every family a live subsystem actually
    registers must be in metric_names.METRIC_FAMILY_NAMES, so a
    dynamically-built name can never drift out of the table."""
    from bigdl_tpu.serving.metrics import ServingMetrics
    from bigdl_tpu.telemetry.metric_names import METRIC_FAMILY_NAMES

    reg = MetricsRegistry()
    tel = Telemetry(registry=reg)            # training spine
    tel.payload()
    ServingMetrics(registry=reg)             # serving families
    rec = MetricRecorder(clock=Clock())
    SloEngine(rec, registry=reg)             # alert counters
    j = ChangeJournal(registry=reg)          # change-event counter
    j.record("deploy_started")
    IncidentEngine(rec, journal=j, registry=reg)  # incident counters
    registered = set(reg.snapshot()["metrics"])
    stray = {f for f in registered
             if f.startswith("bigdl_")} - set(METRIC_FAMILY_NAMES)
    assert not stray, (
        f"families registered at runtime but missing from "
        f"metric_names.METRIC_FAMILY_NAMES: {sorted(stray)}")


# ---------------------------------------------------------------------------
# satellite: trace_report per-tenant attribution _default bucket
# ---------------------------------------------------------------------------

def test_trace_report_buckets_untagged_traces_under_default(
        monkeypatch):
    """Traces with no tenant stamp (single-model fleets, spans
    predating multi-tenancy) land in the ``_default`` bucket — the
    per-tenant attribution must never silently drop wall seconds."""
    import bigdl_tpu.serving.request_trace as rt
    import tools.trace_report as trace_report

    def fake_attr(trace):
        return {"wall_s": trace["wall_s"],
                "tenant": trace.get("tenant"),
                "phases": {"compute": trace["wall_s"]},
                "compute_by_replica": {"r0": trace["wall_s"]},
                "coverage": 1.0, "critical_phase": "compute",
                "critical_replica": "r0"}

    monkeypatch.setattr(rt, "trace_attribution", fake_attr)
    report = trace_report.analyze({
        "t1": {"wall_s": 0.5, "tenant": "alpha"},
        "t2": {"wall_s": 0.25},                  # no tenant stamp
        "t3": {"wall_s": 0.125, "tenant": None},  # explicit None
    })
    tenants = report["tenants"]
    assert set(tenants) == {"alpha", "_default"}
    assert tenants["_default"]["traces"] == 2
    assert tenants["_default"]["wall_s"] == pytest.approx(0.375)
    total = sum(t["wall_s"] for t in tenants.values())
    assert total == pytest.approx(sum(r["wall_s"]
                                      for r in report["rows"]))
