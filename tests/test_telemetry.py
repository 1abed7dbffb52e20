"""Unified telemetry spine (bigdl_tpu/telemetry — docs/observability.md):
registry/tracer/goodput unit contracts, the driver wiring, cross-host
aggregation, and the 4-host chaos acceptance run whose merged cluster
snapshot must account for >=99% of wall clock with the recovery window
from a host eviction visible as a non-productive segment."""
import itertools
import json
import re
import threading
import time

import numpy as np
import pytest

from bigdl_tpu.telemetry import (
    GoodputLedger, MetricsRegistry, Telemetry, Tracer, collect_snapshots,
    merge_cluster, publish_snapshot, read_snapshot_dir,
)
from bigdl_tpu.telemetry.registry import Histogram, default_buckets
from bigdl_tpu.telemetry.report import render_report


def _fake_clock(start=0.0, tick=1.0):
    counter = itertools.count()
    return lambda: start + tick * next(counter)


# ---------------------------------------------------------------------------
# registry: counters, gauges, histograms
# ---------------------------------------------------------------------------

def test_counter_gauge_labels_and_snapshot_json():
    r = MetricsRegistry(clock=lambda: 42.0)
    c = r.counter("req_total", "requests", labels=("status",))
    c.labels(status="ok").inc()
    c.labels(status="ok").inc(2)
    c.labels(status="shed").inc()
    g = r.gauge("depth", "queue depth")
    g.set(7)
    snap = json.loads(json.dumps(r.snapshot()))  # JSON round-trips
    assert snap["ts"] == 42.0
    series = {tuple(s["labels"].items()): s["value"]
              for s in snap["metrics"]["req_total"]["series"]}
    assert series[(("status", "ok"),)] == 3.0
    assert series[(("status", "shed"),)] == 1.0
    assert snap["metrics"]["depth"]["series"][0]["value"] == 7.0


def test_counter_rejects_negative_and_reregistration_conflicts():
    r = MetricsRegistry()
    c = r.counter("a_total", "a")
    with pytest.raises(ValueError):
        c.inc(-1)
    assert r.counter("a_total", "a") is c  # get-or-create
    with pytest.raises(ValueError):
        r.gauge("a_total")  # kind conflict
    with pytest.raises(ValueError):
        r.counter("a_total", labels=("x",))  # label conflict


def test_histogram_window_quantiles_match_numpy_exactly():
    """The serving p50/p99 contract: with a sample window, quantiles
    reproduce numpy.percentile (linear interpolation) bit-for-bit."""
    h = Histogram(window=512)
    rng = np.random.RandomState(0)
    vals = rng.exponential(0.05, size=300).tolist()
    for v in vals:
        h.observe(v)
    for q in (0.0, 0.5, 0.9, 0.99, 1.0):
        assert h.quantile(q) == pytest.approx(
            float(np.percentile(vals, 100 * q)), abs=0, rel=0)


def test_histogram_bucket_quantile_without_window_is_sane():
    h = Histogram(bounds=default_buckets(1e-3, 2.0, 16))
    for v in [0.01] * 50 + [0.1] * 50:
        h.observe(v)
    p50 = h.quantile(0.5)
    assert 0.008 <= p50 <= 0.11
    assert h.quantile(1.0) == pytest.approx(0.1)
    assert h.quantile(0.0) >= 0.0


def test_histogram_merge_is_associative_and_checks_geometry():
    rng = np.random.RandomState(1)
    a, b, c = Histogram(), Histogram(), Histogram()
    for h, scale in ((a, 1.0), (b, 10.0), (c, 0.01)):
        for v in rng.rand(64) * scale:
            h.observe(v)
    left = a.merge(b).merge(c)
    right = a.merge(b.merge(c))
    assert left.buckets == right.buckets
    assert left.count == right.count == 192
    assert left.sum == pytest.approx(right.sum)
    assert left.min == right.min and left.max == right.max
    with pytest.raises(ValueError):
        a.merge(Histogram(bounds=(1.0, 2.0)))


def test_prometheus_text_roundtrips_through_minimal_parser():
    r = MetricsRegistry()
    r.counter("req_total", "total requests",
              labels=("status",)).labels(status="ok").inc(5)
    r.gauge("depth", "queue depth").set(3)
    h = r.histogram("lat_seconds", "latency",
                    bounds=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    text = r.to_prometheus()

    # minimal exposition-format parser: TYPE lines + samples
    types, samples = {}, {}
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            types[name] = kind
        elif not line.startswith("#"):
            m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
                         r"(\{[^}]*\})?\s+(\S+)$", line)
            assert m, f"unparsable sample line: {line!r}"
            name, labels, value = m.groups()
            samples[(name, labels or "")] = float(value)

    assert types == {"req_total": "counter", "depth": "gauge",
                     "lat_seconds": "histogram"}
    assert samples[("req_total", '{status="ok"}')] == 5.0
    assert samples[("depth", "")] == 3.0
    # histogram expands to CUMULATIVE buckets + sum/count
    assert samples[("lat_seconds_bucket", '{le="0.1"}')] == 1.0
    assert samples[("lat_seconds_bucket", '{le="1.0"}')] == 2.0
    assert samples[("lat_seconds_bucket", '{le="10.0"}')] == 3.0
    assert samples[("lat_seconds_bucket", '{le="+Inf"}')] == 4.0
    assert samples[("lat_seconds_count", "")] == 4.0
    assert samples[("lat_seconds_sum", "")] == pytest.approx(55.55)


def test_registry_thread_hammer_loses_nothing():
    r = MetricsRegistry()
    c = r.counter("hits_total")
    h = r.histogram("obs_seconds", window=64)
    n, threads = 2000, 8

    def work():
        for i in range(n):
            c.inc()
            h.observe(i * 1e-4)

    ts = [threading.Thread(target=work) for _ in range(threads)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert c.value == n * threads
    assert h.count == n * threads


# ---------------------------------------------------------------------------
# tracer: nesting, chrome trace export, ring bound
# ---------------------------------------------------------------------------

def test_tracer_nested_spans_and_chrome_trace_valid():
    tr = Tracer()
    with tr.span("step", "step", step=3) as outer:
        with tr.span("wait", "data_wait"):
            time.sleep(0.001)
        with tr.span("ckpt", "checkpoint"):
            pass
    # retroactive profiled children clamp into the parent
    tr.record("compute", "compute", outer.start, 1e9, parent=outer)

    spans = {s.name: s for s in tr.spans()}
    by_id = {s.id: s for s in tr.spans()}
    assert spans["wait"].parent_id == spans["step"].id
    assert spans["ckpt"].parent_id == spans["step"].id
    # no child outlives its parent
    for s in tr.spans():
        if s.parent_id is not None:
            parent = by_id[s.parent_id]
            assert s.start >= parent.start - 1e-9
            assert s.end <= parent.end + 1e-9

    blob = json.dumps(tr.to_chrome_trace())
    trace = json.loads(blob)  # the acceptance check: valid JSON
    events = trace["traceEvents"]
    assert {e["ph"] for e in events} == {"X"}
    for e in events:
        assert e["dur"] >= 0 and "pid" in e and "tid" in e
        assert e["cat"] in ("step", "data_wait", "checkpoint", "compute")


def test_tracer_ring_buffer_bounds_memory():
    tr = Tracer(capacity=16)
    for i in range(100):
        with tr.span(f"s{i}", "other"):
            pass
    assert len(tr.spans()) == 16
    assert tr.dropped == 100 - 16
    assert [s.name for s in tr.spans()][-1] == "s99"


def test_tracer_rejects_unknown_category_and_disabled_mode():
    tr = Tracer()
    with pytest.raises(ValueError):
        tr.span("x", "not-a-category")
    off = Tracer(enabled=False)
    with off.span("x", "step"):
        pass
    assert off.spans() == []
    assert off.record("y", "compute", 0.0, 1.0) is None


def test_tracer_category_totals_use_step_self_time():
    clock = _fake_clock()
    tr = Tracer(clock=clock)  # 0,1,2,... one tick per clock() call
    with tr.span("step", "step"):          # start=0
        with tr.span("wait", "data_wait"):  # start=1
            pass                            # end=2
    # step end=3 -> step dur 3, child dur 1 -> step SELF time 2
    totals = tr.category_totals()
    assert totals["data_wait"] == 1.0
    assert totals["step"] == 2.0


# ---------------------------------------------------------------------------
# goodput ledger
# ---------------------------------------------------------------------------

def test_goodput_ledger_attributes_and_derives_idle():
    t = {"now": 0.0}
    led = GoodputLedger(clock=lambda: t["now"])
    led.start()
    led.add("productive", 6.0)
    led.add("compile", 2.0)
    led.add("data_stall", 1.0)
    t["now"] = 10.0
    snap = led.snapshot()
    assert snap["wall_s"] == 10.0
    assert snap["seconds"]["idle"] == pytest.approx(1.0)
    assert snap["productive_fraction"] == pytest.approx(0.6)
    assert snap["accounted_fraction"] == 1.0
    with pytest.raises(ValueError):
        led.add("idle", 1.0)
    with pytest.raises(ValueError):
        led.add("nonsense", 1.0)


def test_goodput_recovery_window_and_merge():
    t = {"now": 0.0}
    led = GoodputLedger(clock=lambda: t["now"])
    led.start()
    led.add("productive", 2.0)
    t["now"] = 2.0
    led.recovery_begin()
    led.recovery_begin()  # idempotent: one window
    t["now"] = 5.0
    assert led.in_recovery
    assert led.recovery_end() == pytest.approx(3.0)
    assert led.recovery_windows == 1
    t["now"] = 6.0
    snap = led.snapshot()
    assert snap["seconds"]["recovery"] == pytest.approx(3.0)
    assert snap["seconds"]["idle"] == pytest.approx(1.0)

    merged = GoodputLedger.merge_snapshots([snap, snap])
    assert merged["hosts"] == 2
    assert merged["wall_s"] == pytest.approx(12.0)
    assert merged["seconds"]["recovery"] == pytest.approx(6.0)
    assert merged["accounted_fraction"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Telemetry facade + summaries + run report
# ---------------------------------------------------------------------------

def test_telemetry_facade_hooks_and_summary_export(tmp_path):
    from bigdl_tpu.visualization import TelemetrySummary
    from bigdl_tpu.visualization.summary import read_scalars

    tm = Telemetry(registry=MetricsRegistry(), host="hostA",
                   snapshot_dir=str(tmp_path / "snaps"))
    tm.on_attempt_begin()
    # the driver opens the spans where the work happens and hands the
    # hooks the live iteration span; the hooks place none of their own
    with tm.tracer.span("train.iteration", "step", step=1) as it:
        tm.on_step(0.5, records=32, step=1, compiled=True, span=it)
    tm.on_data_wait(0.01, step=2)
    with tm.tracer.span("train.iteration", "step", step=2) as it:
        tm.on_step(0.1, records=32, step=2, phase_split=(0.06, 0.03),
                   span=it)
    tm.on_checkpoint(0.02, step=2)
    tm.on_recovery_begin()
    time.sleep(0.02)  # a real (wall) recovery window...
    tm.on_step(0.0, records=32, step=3)  # ...closed where step 3 began

    assert tm.steps.value == 3
    assert tm.records.value == 96
    assert tm.step_seconds.count == 2  # the compile step lands apart
    assert tm.compile_seconds.count == 1
    spans = tm.tracer.spans()
    assert {s.category for s in spans} == {"step", "compute",
                                           "collective", "recovery"}
    # the profiled split hangs under the span it was handed, laid from
    # that span's own start — not at the hook's "now"
    step2 = next(s for s in spans if (s.args or {}).get("step") == 2
                 and s.category == "step")
    kids = [s for s in spans if s.parent_id == step2.id]
    assert [k.name for k in kids] == ["compute", "collective"]
    assert kids[0].start == step2.start
    # the recovery window starts where the fault was noted
    rec = next(s for s in spans if s.category == "recovery")
    assert 0.015 <= rec.duration <= 5.0
    assert rec.start >= step2.end

    summary = TelemetrySummary(str(tmp_path), "app")
    tm.to_summary(summary, step=3)
    summary.close()
    got = read_scalars(summary.log_dir, "telemetry/steps_total")
    assert got == [(3, 3.0)]
    assert read_scalars(summary.log_dir, "telemetry/goodput_fraction")

    path = tm.write_snapshot(step=3)
    payloads = read_snapshot_dir(str(tmp_path / "snaps"))
    assert path and "hostA" in payloads
    report = render_report(merge_cluster(payloads))
    assert "goodput" in report and "hostA" in report


def test_run_report_tool_renders_snapshot_dir(tmp_path, capsys):
    import importlib.util
    import os

    tm = Telemetry(registry=MetricsRegistry(), host="h0")
    tm.on_attempt_begin()
    tm.on_step(0.2, records=8, step=1)
    tm.write_snapshot(str(tmp_path), step=1)

    spec = importlib.util.spec_from_file_location(
        "run_report", os.path.join(os.path.dirname(__file__), "..",
                                   "tools", "run_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "run report" in out and "productive" in out
    assert mod.main([str(tmp_path / "empty")]) == 1


# ---------------------------------------------------------------------------
# serving p50/p99 regression: registry-backed quantiles == the old
# numpy-percentile-over-deque numbers on a fixed sample
# ---------------------------------------------------------------------------

def test_serving_metrics_quantiles_unchanged_on_fixed_sample():
    from bigdl_tpu.serving import ServingMetrics, Status

    rng = np.random.RandomState(7)
    lats = rng.exponential(0.02, size=500).tolist()
    m = ServingMetrics(window=8192)
    for v in lats:
        m.record(Status.OK, latency_s=v, queued_s=v / 4)
    m.record(Status.OVERLOADED)
    snap = m.snapshot()
    # the pre-registry implementation: np.percentile over the window
    assert snap["latency_p50_s"] == pytest.approx(
        float(np.percentile(lats, 50)), rel=0, abs=0)
    assert snap["latency_p99_s"] == pytest.approx(
        float(np.percentile(lats, 99)), rel=0, abs=0)
    assert snap["served_ok"] == 500 and snap["shed"] == 1
    assert snap["queued_mean_s"] == pytest.approx(
        float(np.mean([v / 4 for v in lats])))
    # the registry behind it exports Prometheus text
    assert "bigdl_serving_requests_total" in m.to_prometheus()


# ---------------------------------------------------------------------------
# cross-host aggregation over the elastic KV transport
# ---------------------------------------------------------------------------

def test_publish_collect_merge_is_incarnation_keyed():
    from bigdl_tpu.resilience import InMemoryKV

    kv = InMemoryKV()
    tms = {}
    for host in ("host0", "host1"):
        tm = Telemetry(registry=MetricsRegistry(), host=host)
        tm.on_attempt_begin()
        tm.on_step(0.1, records=4, step=1)
        tms[host] = tm
        publish_snapshot(kv, host, tm.payload(step=1), incarnation=0)
    # a NEWER incarnation must not see incarnation-0 payloads
    assert collect_snapshots(kv, incarnation=1) == {}
    got = collect_snapshots(kv, incarnation=0)
    assert set(got) == {"host0", "host1"}
    # membership restriction drops departed hosts' stale payloads
    only = collect_snapshots(kv, incarnation=0, members=("host0",))
    assert set(only) == {"host0"}

    cluster = merge_cluster(got)
    assert cluster["hosts"] == ["host0", "host1"]
    fam = cluster["metrics"]["bigdl_train_steps_total"]
    assert fam["series"][0]["value"] == 2.0  # counters summed
    hist = cluster["metrics"]["bigdl_train_step_seconds"]["series"][0]
    assert hist["count"] == 2  # histogram buckets added
    assert sum(hist["buckets"]) == 2
    # goodput host-seconds summed (wall here is fabricated/minuscule,
    # so the fraction is meaningless in this unit test — the chaos e2e
    # below asserts the >=99% accounting on a real run)
    assert cluster["goodput"]["seconds"]["productive"] == pytest.approx(
        0.2)
    skew = cluster["per_host_skew"]
    assert set(skew) == {"host0", "host1"}
    assert all(abs(rec["skew"] - 1.0) < 1e-6 for rec in skew.values())


# ---------------------------------------------------------------------------
# driver wiring: LocalOptimizer + DistriOptimizer feed the spine
# ---------------------------------------------------------------------------

def _regression_samples(n=256):
    from bigdl_tpu.dataset import Sample

    rng = np.random.RandomState(0)
    x = rng.rand(n, 4).astype(np.float32)
    w = np.array([[1.5], [-2.0], [0.5], [3.0]], np.float32)
    y = (x @ w + 0.7).astype(np.float32)
    return [Sample(x[i], y[i]) for i in range(n)]


@pytest.mark.parametrize("steps,batch,every", [(6, 64, 3), (120, 512, 30)])
def test_local_optimizer_feeds_telemetry(tmp_path, steps, batch, every):
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import array
    from bigdl_tpu.optim import SGD, max_iteration, several_iteration
    from bigdl_tpu.optim.optimizer import LocalOptimizer

    tm = Telemetry(registry=MetricsRegistry(), host="local",
                   snapshot_dir=str(tmp_path / "snaps"))
    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = LocalOptimizer(model, array(_regression_samples(4 * batch)),
                         nn.MSECriterion(), batch_size=batch)
    opt.set_optim_method(SGD(learning_rate=0.2))
    opt.set_end_when(max_iteration(steps))
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(every))
    opt.set_telemetry(tm)
    opt.optimize()

    assert tm.steps.value == steps
    assert tm.records.value == steps * batch
    assert tm.compile_seconds.count == 1    # first step = XLA build
    assert tm.step_seconds.count == steps - 1
    assert tm.checkpoint_seconds.count >= 1
    gp = tm.ledger.snapshot()
    assert gp["seconds"]["productive"] > 0
    assert gp["seconds"]["compile"] > 0
    assert gp["accounted_fraction"] >= 0.99
    # the tracer exported a parseable trace with step spans
    trace = json.loads(json.dumps(tm.tracer.to_chrome_trace()))
    names = [e["name"] for e in trace["traceEvents"]]
    assert names.count("train.iteration") == steps
    assert "train.checkpoint" in names
    compiled = [e["args"]["compiled"] for e in trace["traceEvents"]
                if e["name"] == "train.dispatch"]
    assert compiled == [True] + [False] * (steps - 1)
    # the end-of-run snapshot landed for tools/run_report.py
    assert "local" in read_snapshot_dir(str(tmp_path / "snaps"))


def test_distri_optimizer_feeds_telemetry_with_phase_split(tmp_path):
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import array
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer

    tm = Telemetry(registry=MetricsRegistry(), host="d0")
    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = DistriOptimizer(model, array(_regression_samples()),
                          nn.MSECriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=0.2))
    # the default bigdl.metrics.profileInterval=10 profiles iteration 10
    opt.set_end_when(max_iteration(12))
    opt.set_telemetry(tm)
    opt.optimize()
    assert tm.steps.value == 12
    assert tm.compile_seconds.count == 1
    # iteration 10 was profiled: the step span carries compute (+
    # collective when the trace classified any) children
    cats = {s.category for s in tm.tracer.spans()}
    if opt.phase_source == "trace":
        assert "compute" in cats
    assert tm.ledger.snapshot()["accounted_fraction"] >= 0.99


# ---------------------------------------------------------------------------
# the chaos acceptance: 4 simulated hosts, a host death mid-run, and a
# merged cluster snapshot that accounts for >=99% of wall clock with
# the recovery window visible as a non-productive segment
# ---------------------------------------------------------------------------

def test_chaos_cluster_snapshot_accounts_wall_clock(tmp_path):
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import array
    from bigdl_tpu.optim import SGD, max_iteration, several_iteration
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.resilience import (CollectiveWatchdog, ElasticContext,
                                      ElasticCoordinator, InMemoryKV,
                                      RetryPolicy, SimulatedHost,
                                      StepTimeEstimator, faults)

    kv = InMemoryKV()
    hosts = ["host0", "host1", "host2", "host3"]
    coord = ElasticCoordinator("host0", kv, heartbeat_timeout=0.3)
    coord.bootstrap(hosts)
    sims = [SimulatedHost(h, kv, heartbeat_timeout=0.3,
                          die_at_leader_step=(8 if h == "host2"
                                              else None))
            for h in hosts[1:]]
    tm = Telemetry(registry=MetricsRegistry(), host="host0",
                   snapshot_dir=str(tmp_path / "snaps"))
    ctx = ElasticContext(
        coord,
        watchdog=CollectiveWatchdog(StepTimeEstimator(
            floor=0.75, multiplier=4.0, min_samples=3)),
        rendezvous_timeout=3.0, regrow_after_steps=1000,
        telemetry_cadence=2)

    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = DistriOptimizer(model, array(_regression_samples()),
                          nn.MSECriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=0.3))
    opt.set_end_when(max_iteration(20))
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(1))
    opt.set_retry_policy(RetryPolicy(max_retries=20, backoff_base=0.01,
                                     backoff_max=0.05))
    opt.set_telemetry(tm)
    opt.set_elastic(ctx)
    assert ctx.telemetry is tm  # set_elastic picked the bundle up

    t0 = time.monotonic()
    with faults.delay_host("host0", 0.05, at_step=1):
        for s in sims:
            s.start()
        try:
            opt.optimize()
        finally:
            for s in sims:
                s.stop()
    elapsed = time.monotonic() - t0
    assert elapsed < 120

    # the run completed across the death, and recovery was ledgered
    assert opt.optim_method.state["neval"] - 1 == 20
    assert ctx.incarnation_changes >= 1
    gp = tm.ledger.snapshot()
    assert gp["seconds"]["recovery"] > 0, \
        "the eviction's recovery window must be a non-productive segment"
    assert tm.recoveries.value >= 1

    # the merged cluster snapshot: survivors' payloads, >=99% accounted
    cluster = ctx.cluster_snapshot()
    assert "host0" in cluster["hosts"]
    assert len(cluster["hosts"]) >= 2        # survivors published too
    assert "host2" not in cluster["hosts"]   # the dead host is gone
    assert cluster["goodput"]["accounted_fraction"] >= 0.99, cluster[
        "goodput"]
    assert cluster["goodput"]["seconds"]["recovery"] > 0
    assert 0 < cluster["goodput"]["productive_fraction"] <= 1.0
    # and it renders as the run report table
    report = render_report(cluster)
    assert "recovery" in report and "host0" in report

    # the cluster-wide Perfetto timeline: per-host published step
    # spans merged into ONE view (clock-aligned, skew-stamped), with
    # the recovery window appearing exactly as often as it happened —
    # and on the host that recovered, never duplicated by the merge
    tl = cluster["timeline"]
    assert tl is not None and "host0" in tl["hosts"]
    events = [e for e in tl["traceEvents"] if e.get("ph") == "X"]
    assert any(e["cat"] == "step" for e in events)
    host0_pid = next(
        e["pid"] for e in tl["traceEvents"]
        if e.get("ph") == "M" and e["args"].get("host") == "host0")
    recov = [e for e in events if e["cat"] == "recovery"]
    assert len(recov) == int(tm.recoveries.value) >= 1
    assert {e["pid"] for e in recov} == {host0_pid}
    # skew stamps ride the process metadata when step histograms
    # published (host_skew's source data)
    metas = [e for e in tl["traceEvents"] if e.get("ph") == "M"]
    assert any("step_time_skew" in e["args"] for e in metas)

    # rendered by the CLI: tools/run_report.py --timeline
    import tools.run_report as run_report

    out_path = str(tmp_path / "timeline.json")
    assert run_report.main([str(tmp_path / "snaps"),
                            "--timeline", out_path]) == 0
    with open(out_path) as f:
        written = json.load(f)
    assert any(e.get("cat") == "step"
               for e in written["traceEvents"])


# ---------------------------------------------------------------------------
# profiling satellite: typed PhaseSplit keeps tuple unpacking
# ---------------------------------------------------------------------------

def test_phase_split_is_typed_and_unpacks():
    from bigdl_tpu.optim.profiling import PhaseSplit

    split = PhaseSplit(0.06, 0.02)
    c, a = split  # the tuple contract every call site relies on
    assert (c, a) == (0.06, 0.02)
    assert split.compute_s == 0.06 and split.collective_s == 0.02
    assert split.total_s == pytest.approx(0.08)
    assert split.compute_fraction == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# resilience counters land in the process-wide default registry
# ---------------------------------------------------------------------------

def test_retry_and_watchdog_count_into_default_registry():
    from bigdl_tpu.resilience import (CollectiveWatchdog, RetryPolicy,
                                      StepTimeEstimator)
    from bigdl_tpu.resilience.watchdog import HungCollectiveError
    from bigdl_tpu.telemetry import default_registry

    r = default_registry()

    def val(name):
        fam = r.get(name)
        return fam.value if fam is not None else 0.0

    retries0 = val("bigdl_retry_attempts_total")
    trips0 = val("bigdl_watchdog_trips_total")

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    policy = RetryPolicy(max_retries=5, backoff_base=0.0, jitter=0.0)
    assert policy.run(flaky) == "ok"
    assert val("bigdl_retry_attempts_total") == retries0 + 2

    wd = CollectiveWatchdog(StepTimeEstimator(min_samples=1, floor=0.05))
    wd.estimator.observe(0.001)
    with pytest.raises(HungCollectiveError):
        wd.run(lambda cancel: time.sleep(5))
    assert val("bigdl_watchdog_trips_total") == trips0 + 1


# ---------------------------------------------------------------------------
# lint: every bigdl_* metric family name literal comes from ONE shared
# constant table (telemetry/metric_names.py) — a renamed family can
# never silently orphan an SLO rule
# ---------------------------------------------------------------------------

#: a quoted family-shaped literal: bigdl_ plus >= 2 more segments (the
#: bare package name "bigdl_tpu" and tempfile prefixes ending in "_"
#: are not family names and do not match)
_METRIC_LITERAL = re.compile(
    r"""["'](bigdl_[a-z0-9]+(?:_[a-z0-9]+)+)["']""")


def test_metric_family_names_come_from_shared_table():
    """Every ``"bigdl_*"`` metric-family string literal anywhere in
    bigdl_tpu/ must be a member of
    ``telemetry.metric_names.METRIC_FAMILY_NAMES`` — the span-category
    lint pattern applied to metric names.  Alert rules reference
    families through the same table, so the rule set and the
    registration sites can never drift apart."""
    import os

    from bigdl_tpu.telemetry.metric_names import METRIC_FAMILY_NAMES

    assert len(METRIC_FAMILY_NAMES) > 40    # the table is populated
    for name in METRIC_FAMILY_NAMES:
        assert _METRIC_LITERAL.match(f'"{name}"'), name

    pkg = os.path.join(os.path.dirname(__file__), "..", "bigdl_tpu")
    offenders = []
    for dirpath, _dirs, files in os.walk(pkg):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, pkg)
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    code = line.split("#", 1)[0]
                    for name in _METRIC_LITERAL.findall(code):
                        if name not in METRIC_FAMILY_NAMES:
                            offenders.append(
                                f"bigdl_tpu/{rel}:{lineno}: family "
                                f"{name!r} not in metric_names"
                                f".METRIC_FAMILY_NAMES: "
                                f"{line.strip()}")
    assert not offenders, (
        "metric family names outside the shared table (declare them "
        "in telemetry/metric_names.py):\n" + "\n".join(offenders))


# ---------------------------------------------------------------------------
# exemplars survive the cross-host merge (the fold used to drop them)
# ---------------------------------------------------------------------------

def test_exemplars_survive_cross_host_merge_roundtrip():
    """Two hosts' histograms with exemplars fold into one cluster
    series keeping the NEWEST exemplar per bucket, and the merged
    view round-trips through the OpenMetrics text exporter with the
    exemplar syntax intact."""
    from bigdl_tpu.telemetry.aggregate import (merge_metrics,
                                               metrics_to_prometheus)

    bounds = (0.1, 1.0)

    def host(trace_low, trace_high, ts):
        r = MetricsRegistry()
        h = r.histogram("bigdl_serving_latency_seconds", "lat",
                        bounds=bounds)
        h.observe(0.05, exemplar=trace_low)
        h.observe(0.5, exemplar=trace_high)
        snap = r.snapshot()["metrics"]
        # pin deterministic publish stamps (observe() stamps wall
        # clock; the merge keys on ts, so forge distinct ones)
        for series in snap["bigdl_serving_latency_seconds"]["series"]:
            for ex in series["exemplars"].values():
                ex["ts"] = ts
        return snap

    older = host("aaaa", "bbbb", ts=100.0)
    newer = host("cccc", "dddd", ts=200.0)
    merged = merge_metrics([older, newer])
    series = merged["bigdl_serving_latency_seconds"]["series"][0]
    # buckets added; the NEWEST exemplar won each bucket
    assert series["count"] == 4
    ex = series["exemplars"]
    assert ex["0"]["trace_id"] == "cccc"
    assert ex["1"]["trace_id"] == "dddd"
    # fold order must not matter (newest-wins is by stamp, not order)
    merged2 = merge_metrics([newer, older])
    assert merged2["bigdl_serving_latency_seconds"]["series"][0][
        "exemplars"] == ex
    # ...and the merged view exports OpenMetrics text with exemplars
    text = metrics_to_prometheus(merged)
    assert '# {trace_id="cccc"} 0.05' in text
    assert '# {trace_id="dddd"} 0.5' in text
    # a minimal parse recovers cumulative bucket counts from the
    # merged text (the round trip: registry -> snapshot -> merge ->
    # exposition)
    bucket_lines = [ln for ln in text.splitlines()
                    if ln.startswith(
                        "bigdl_serving_latency_seconds_bucket")]
    assert len(bucket_lines) == 3          # 2 bounds + +Inf
    counts = [int(ln.split(" # ")[0].rsplit(" ", 1)[1])
              for ln in bucket_lines]
    assert counts == [2, 4, 4]


def test_exemplar_merge_drops_on_geometry_drift():
    """Mismatched bucket geometry already drops the buckets — the
    exemplars (bucket-indexed) must go with them, never attach to the
    wrong ladder."""
    from bigdl_tpu.telemetry.aggregate import merge_metrics

    def host(bounds):
        r = MetricsRegistry()
        h = r.histogram("bigdl_serving_latency_seconds", "lat",
                        bounds=bounds)
        h.observe(0.05, exemplar="eeee")
        return r.snapshot()["metrics"]

    merged = merge_metrics([host((0.1, 1.0)), host((0.2, 2.0))])
    series = merged["bigdl_serving_latency_seconds"]["series"][0]
    assert "buckets" not in series
    assert "exemplars" not in series
    assert series["count"] == 2            # count/sum still honest
