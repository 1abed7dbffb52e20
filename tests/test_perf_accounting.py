"""Performance observatory specs (telemetry/perf.py + device_info.py).

Covers the ISSUE-6 acceptance surface: cost-analysis extraction on a
small jitted step (CPU backend), memory-stats degradation when the
backend lacks ``memory_stats()`` (CPU jaxlib returns None — must not
crash), roofline classification boundaries, driver/serving wiring, the
cross-host perf fold, and the derived-vs-analytic FLOP cross-checks
that replace the hand-coded constants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.telemetry.device_info import (CPU_SPEC, DeviceSpec,
                                             current_device_spec,
                                             device_spec,
                                             peak_flops_per_sec)
from bigdl_tpu.telemetry.perf import (PerfAccountant, StepCost,
                                      classify_roofline,
                                      cost_from_analysis)
from bigdl_tpu.telemetry.registry import MetricsRegistry


# ---------------------------------------------------------------------------
# device_info: the one peak table
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197e12), ("TPU v4", 275e12),
    ("weird accelerator", None), ("cpu", None)])
def test_device_table_lookup(kind, peak):
    assert peak_flops_per_sec(kind) == peak
    if kind == "cpu":   # the NOMINAL row: no honest peak claim
        assert device_spec(kind).nominal is True


def test_device_spec_ridge_point():
    spec = device_spec("TPU v5e")
    assert spec.peak_flops_per_sec == 197e12
    assert spec.hbm_bytes == 16 * 1024 ** 3
    # ridge = peak / hbm_bw ~ 240 flops/byte on v5e
    assert 200 < spec.ridge_flops_per_byte < 280
    # the live backend (CPU in tier-1) gets the nominal row
    live = current_device_spec()
    assert isinstance(live, DeviceSpec)
    assert live.nominal is True


def test_current_device_spec_raises_for_unknown_accelerator():
    """A live accelerator whose kind is not in DEVICE_SPECS has no
    honest denominator: it raises instead of taking the nominal CPU
    row — also when its kind merely mentions the host."""
    class Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    assert current_device_spec(Dev("tpu", "TPU v5 lite")).kind == "v5 lite"
    assert current_device_spec(Dev("cpu", "cpu")) is CPU_SPEC
    for dev in (Dev("tpu", "TPU v9x"), Dev("gpu", "NVIDIA H100"),
                Dev("tpu", "host-attached thing")):
        with pytest.raises(LookupError, match="DEVICE_SPECS"):
            current_device_spec(dev)


# ---------------------------------------------------------------------------
# cost extraction on a small jitted step
# ---------------------------------------------------------------------------

def test_cost_extraction_small_jitted_step():
    @jax.jit
    def step(w, x):
        return jnp.tanh(x @ w).sum()

    w = jnp.ones((64, 64), jnp.float32)
    x = jnp.ones((32, 64), jnp.float32)
    pa = PerfAccountant(registry=MetricsRegistry(), spec=CPU_SPEC)
    cost = pa.analyze_jitted(step, w, x, label="tiny")
    assert cost is not None
    # 32x64x64 matmul = 2*32*64*64 ~ 262k flops (+ tanh etc.)
    assert cost.flops > 2 * 32 * 64 * 64 * 0.9
    assert cost.bytes_accessed > 0
    assert cost.arithmetic_intensity > 0
    assert cost.source == "lowered"
    # static gauges published under the program label
    snap = pa.registry.snapshot()["metrics"]
    series = snap["bigdl_perf_flops_per_step"]["series"]
    assert series[0]["labels"] == {"program": "tiny"}
    assert series[0]["value"] == cost.flops
    # a step at a known wall time yields a non-zero mfu gauge
    pa.on_step(0.01)
    snap = pa.registry.snapshot()["metrics"]
    mfu = snap["bigdl_perf_mfu"]["series"][0]["value"]
    assert mfu == pytest.approx(
        cost.flops / 0.01 / CPU_SPEC.peak_flops_per_sec)
    assert snap["bigdl_perf_flops_total"]["series"][0]["value"] == \
        cost.flops


def test_analyze_compiled_carries_memory_analysis():
    @jax.jit
    def step(w, x):
        return (x @ w).sum()

    w = jnp.ones((64, 64), jnp.float32)
    x = jnp.ones((32, 64), jnp.float32)
    compiled = step.lower(w, x).compile()
    pa = PerfAccountant(registry=MetricsRegistry(), spec=CPU_SPEC)
    cost = pa.analyze_compiled(compiled, label="aot")
    assert cost is not None and cost.source == "compiled"
    assert cost.flops > 0
    # CompiledMemoryStats: argument bytes at least the two operands
    assert cost.argument_bytes >= w.nbytes + x.nbytes
    assert cost.peak_bytes is not None and cost.peak_bytes > 0


def test_analysis_failure_is_a_none_not_a_raise():
    pa = PerfAccountant(registry=MetricsRegistry(), spec=CPU_SPEC)
    assert pa.analyze_jitted(lambda x: x, 1.0, label="nope") is None
    assert pa.current_cost is None
    pa.on_step(0.5)  # no program installed: a silent no-op
    assert pa.flops_total.value == 0.0


# ---------------------------------------------------------------------------
# HBM watermark degradation (CPU jaxlib has no memory stats)
# ---------------------------------------------------------------------------

def test_memory_stats_none_on_cpu_does_not_crash():
    pa = PerfAccountant(registry=MetricsRegistry(), spec=CPU_SPEC)
    assert pa.poll_memory_stats() is None  # CPU jaxlib returns None
    snap = pa.registry.snapshot()["metrics"]
    # gauges exist but carry no series — nothing was ever set
    assert snap["bigdl_perf_hbm_peak_bytes"]["series"] == []
    assert pa.last_memory_stats is None


def test_memory_stats_gauges_from_a_reporting_device():
    class FakeDev:
        def memory_stats(self):
            return {"bytes_in_use": 1024, "peak_bytes_in_use": 4096,
                    "bytes_limit": 16 * 1024 ** 3}

    pa = PerfAccountant(registry=MetricsRegistry(), spec=CPU_SPEC)
    stats = pa.poll_memory_stats(device=FakeDev())
    assert stats["peak_bytes_in_use"] == 4096
    snap = pa.registry.snapshot()["metrics"]
    assert snap["bigdl_perf_hbm_peak_bytes"]["series"][0]["value"] \
        == 4096
    assert snap["bigdl_perf_hbm_bytes_in_use"]["series"][0]["value"] \
        == 1024
    # the payload carries the watermark for the cross-host fold
    assert pa.payload()["hbm"]["peak_bytes_in_use"] == 4096

    class RaisingDev:
        def memory_stats(self):
            raise RuntimeError("backend quirk")

    assert pa.poll_memory_stats(device=RaisingDev()) is None


# ---------------------------------------------------------------------------
# roofline classification boundaries
# ---------------------------------------------------------------------------

def test_roofline_boundaries():
    # synthetic chip: 100 F/s peak, 10 B/s HBM, 1 B/s ICI -> ridge 10
    spec = DeviceSpec("test", 100.0, 1000.0, 10.0, 1.0)
    assert spec.ridge_flops_per_byte == 10.0
    # AI 20 > ridge: compute-bound (compute 2.0s > hbm 1.0s)
    rf = classify_roofline(StepCost(flops=200.0, bytes_accessed=10.0),
                           spec)
    assert rf["bound"] == "compute"
    assert rf["arithmetic_intensity"] == 20.0
    # AI 0.5 < ridge: hbm-bound (hbm 10s > compute 0.5s)
    rf = classify_roofline(StepCost(flops=50.0, bytes_accessed=100.0),
                           spec)
    assert rf["bound"] == "hbm"
    # collective time dominates both: collective-bound
    rf = classify_roofline(
        StepCost(flops=50.0, bytes_accessed=100.0,
                 collective_bytes=50.0), spec)
    assert rf["bound"] == "collective"
    # no flops, no bytes: unknown
    rf = classify_roofline(StepCost(flops=0.0, bytes_accessed=0.0),
                           spec)
    assert rf["bound"] == "unknown"
    # exactly at the ridge the two times tie; either verdict is a
    # compute/hbm one, never collective/unknown
    rf = classify_roofline(StepCost(flops=100.0, bytes_accessed=10.0),
                           spec)
    assert rf["bound"] in ("compute", "hbm")


# ---------------------------------------------------------------------------
# driver wiring: Local + Distri-data publish the mfu family
# ---------------------------------------------------------------------------

def _fit_local(telemetry, steps=5):
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import Sample, array
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.optimizer import LocalOptimizer

    rng = np.random.RandomState(0)
    x = rng.rand(128, 8).astype(np.float32)
    w = rng.rand(8, 1).astype(np.float32)
    y = (x @ w).astype(np.float32)
    samples = [Sample(x[i], y[i]) for i in range(len(x))]
    model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                          nn.Linear(16, 1))
    opt = LocalOptimizer(model, array(samples), nn.MSECriterion(),
                         batch_size=32)
    opt.set_optim_method(SGD(learning_rate=0.05))
    opt.set_end_when(max_iteration(steps))
    opt.set_telemetry(telemetry)
    opt.optimize()


def test_local_optimizer_publishes_mfu_family():
    from bigdl_tpu.telemetry import Telemetry

    tm = Telemetry(registry=MetricsRegistry())
    _fit_local(tm)
    snap = tm.registry.snapshot()["metrics"]
    flops = snap["bigdl_perf_flops_per_step"]["series"][0]
    assert flops["labels"] == {"program": "train_step"}
    assert flops["value"] > 0
    assert snap["bigdl_perf_bytes_per_step"]["series"][0]["value"] > 0
    assert snap["bigdl_perf_mfu"]["series"][0]["value"] > 0
    assert snap["bigdl_perf_flops_total"]["series"][0]["value"] >= \
        5 * flops["value"] * 0.99
    # payload carries the perf section for the cross-host fold
    perf = tm.payload()["perf"]
    assert perf["programs"]["train_step"]["bound"] in (
        "compute", "hbm")
    assert perf["device"]["nominal"] is True


def test_step_spans_carry_static_work_attributes():
    """The small-fix satellite: every step span gets flops/bytes/
    intensity args from the cost model, profiler or not."""
    from bigdl_tpu.telemetry import Telemetry

    tm = Telemetry(registry=MetricsRegistry())
    _fit_local(tm)
    steps = [s for s in tm.tracer.spans() if s.category == "step"]
    assert steps, "no step spans recorded"
    for s in steps:
        assert s.args["flops"] > 0
        assert s.args["bytes"] > 0
        assert s.args["bound"] in ("compute", "hbm", "collective")
    # and the chrome-trace export carries them into Perfetto
    ev = [e for e in tm.tracer.to_chrome_trace()["traceEvents"]
          if e["cat"] == "step"]
    assert ev and ev[0]["args"]["flops"] > 0


def test_work_attributes_are_set_while_the_step_span_is_live(monkeypatch):
    """A step is reported after the NEXT one is enqueued, when its
    ``train.iteration`` span has closed; the static work attributes
    still go on at the step's commit — only a live span hands them to
    a profiler session's event — and a recovery window closes there
    too, not one dispatch later."""
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import Sample, array
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.telemetry import Telemetry
    from bigdl_tpu.telemetry.tracer import Span

    live = []
    real_set = Span.set

    def spy(self, **args):
        if "flops" in args:
            live.append((self.name, self.args["step"], self.end is None))
        return real_set(self, **args)

    monkeypatch.setattr(Span, "set", spy)
    tm = Telemetry(registry=MetricsRegistry())
    seen = []

    def end(state):  # called after step n's commit, before enqueue n+1
        seen.append((state["neval"] - 1, tm.ledger.in_recovery))
        if state["neval"] - 1 == 2:
            tm.on_recovery_begin()  # a fault noted after step 2
        return state["neval"] > 5

    rng = np.random.RandomState(0)
    x = rng.rand(256, 8).astype(np.float32)
    samples = [Sample(x[i], x[i, :1]) for i in range(len(x))]
    opt = LocalOptimizer(nn.Sequential(nn.Linear(8, 1)), array(samples),
                         nn.MSECriterion(), batch_size=32)
    opt.set_optim_method(SGD(learning_rate=0.05))
    opt.set_end_when(end)
    opt.set_telemetry(tm)
    opt.optimize()
    # (a run's last step is reported inside its own iteration: twice)
    assert sorted(set(live)) == [("train.iteration", n, True)
                                 for n in range(1, 6)]
    # open when step 3 began, closed by step 3's commit: the trigger
    # that follows that commit already finds the window shut
    assert seen == [(0, False), (1, False), (2, False), (3, False),
                    (4, False), (5, False)]
    assert tm.ledger.recovery_windows == 1 and not tm.ledger.in_recovery


def test_distri_data_path_publishes_collective_bytes():
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import Sample, array
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.telemetry import Telemetry

    rng = np.random.RandomState(0)
    x = rng.rand(128, 4).astype(np.float32)
    y = (x @ np.array([[1.0], [-2.0], [0.5], [3.0]],
                      np.float32)).astype(np.float32)
    samples = [Sample(x[i], y[i]) for i in range(len(x))]
    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = DistriOptimizer(model, array(samples), nn.MSECriterion(),
                          batch_size=64)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_iteration(4))
    tm = Telemetry(registry=MetricsRegistry())
    opt.set_telemetry(tm)
    opt.optimize()
    snap = tm.registry.snapshot()["metrics"]
    assert snap["bigdl_perf_flops_per_step"]["series"][0]["value"] > 0
    # the data-parallel wire estimate: 2(n-1)/n x param bytes > 0 on
    # the 8-virtual-device mesh
    coll = snap["bigdl_perf_collective_bytes"]["series"][0]["value"]
    assert coll > 0
    prog = tm.payload()["perf"]["programs"]["train_step"]
    assert prog["collective_bytes"] == coll


# ---------------------------------------------------------------------------
# serving: per-bucket FLOPs -> goodput-per-chip
# ---------------------------------------------------------------------------

def test_serving_reports_bucket_flops_and_goodput_per_chip():
    from bigdl_tpu import nn
    from bigdl_tpu.serving import InferenceServer

    model = nn.Sequential(nn.Linear(16, 32), nn.Tanh(),
                          nn.Linear(32, 4), nn.LogSoftMax())
    srv = InferenceServer(model, max_batch=8, max_queue=32)
    srv.start()
    try:
        rng = np.random.RandomState(0)
        futs = [srv.submit(rng.rand(16).astype(np.float32))
                for _ in range(12)]
        for f in futs:
            assert f.result(timeout=60).ok
    finally:
        srv.stop(timeout=30)
    snap = srv.metrics.snapshot()
    assert snap["flops_total"] > 0
    assert snap["model_flops_per_sec"] >= 0.0
    gpc = srv.metrics.goodput_per_chip()
    assert gpc["flops_total"] == snap["flops_total"]
    # nominal CPU peak -> an mfu figure exists once batches flowed
    # across a non-zero wall window; single-burst runs may have ~0
    # wall, in which case mfu is None by contract
    if gpc["wall_s"] > 0:
        assert gpc["mfu"] is None or gpc["mfu"] > 0


# ---------------------------------------------------------------------------
# derived vs analytic cross-checks (the constants leave the
# reporting path but must keep agreeing with it)
# ---------------------------------------------------------------------------

def _train_step(model, criterion, optim):
    """One jitted optimizer step: forward, backward, update."""
    def step(params, buffers, slots, lr, rng, x, y):
        def loss_fn(p):
            out, nb = model.apply_fn(p, buffers, x, True, rng)
            return criterion._loss(jnp.asarray(out, jnp.float32), y), nb

        (loss, nb), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return (loss, nb) + optim.step(grads, params, slots, lr)

    return jax.jit(step)


def test_resnet50_derived_flops_within_5pct_of_analytic():
    from bigdl_tpu import nn
    from bigdl_tpu.models.resnet import ResNet50
    from bigdl_tpu.optim import SGD

    B = 2
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(B, 3, 224, 224).astype(np.float32))
    y = jnp.asarray(rng.randint(1, 1001, B).astype(np.float32))
    model = ResNet50(1000)
    optim = SGD(learning_rate=0.01)
    params = model.param_tree()
    buffers = model.buffer_tree()
    slots = optim.init_state(params)
    one_step = _train_step(model, nn.ClassNLLCriterion(), optim)
    lowered = one_step.lower(params, buffers, slots, jnp.float32(0.01),
                             jax.random.PRNGKey(0), x, y)
    cost = cost_from_analysis(lowered.cost_analysis())
    # 4.09 GMAC forward an image, FMA = 2, backward twice the forward
    analytic = 2 * 4.09e9 * 3 * B
    assert cost.flops == pytest.approx(analytic, rel=0.05), (
        f"derived {cost.flops:.4g} vs analytic {analytic:.4g} — the "
        "FMA=2 train-step count drifted from the 2x4.09GMAC x3 "
        "convention")


def test_transformer_lm_derived_flops_within_5pct_of_6nd():
    from bigdl_tpu import nn
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.optim import SGD

    V, D, L, T, B = 1024, 128, 2, 256, 2
    model = TransformerLM(V, embed_dim=D, num_heads=2, num_layers=L,
                          max_len=T, seq_strategy="dense",
                          output="logits")
    crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(),
                                       True)
    active = sum(a.size for a in
                 jax.tree_util.tree_leaves(model.param_tree()))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(1, V, (B, T)).astype(np.float32))
    y = jnp.asarray(rng.randint(1, V + 1, (B, T)).astype(np.float32))
    optim = SGD(learning_rate=0.01)
    params = model.param_tree()
    buffers = model.buffer_tree()
    slots = optim.init_state(params)
    one_step = _train_step(model, crit, optim)
    lowered = one_step.lower(params, buffers, slots, jnp.float32(0.01),
                             jax.random.PRNGKey(0), x, y)
    cost = cost_from_analysis(lowered.cost_analysis())
    analytic_6nd = 6.0 * active * B * T
    assert cost.flops == pytest.approx(analytic_6nd, rel=0.05), (
        f"derived {cost.flops:.4g} vs 6ND {analytic_6nd:.4g}")


# ---------------------------------------------------------------------------
# cross-host fold + run report
# ---------------------------------------------------------------------------

def _payload(host, flops_total, wall, peak=100.0, hbm_peak=None):
    perf = {
        "device": {"kind": "test", "peak_flops_per_sec": peak,
                   "hbm_bytes": 1000.0, "hbm_bytes_per_sec": 10.0,
                   "ici_bytes_per_sec": 1.0, "nominal": False},
        "flops_total": flops_total,
        "programs": {"train_step": {
            "flops": 200.0, "bytes_accessed": 10.0,
            "collective_bytes": 0.0, "arithmetic_intensity": 20.0,
            "bound": "compute", "mfu": 0.5}},
    }
    if hbm_peak is not None:
        perf["hbm"] = {"peak_bytes_in_use": hbm_peak,
                       "bytes_limit": 4 * hbm_peak}
    return {"host": host, "incarnation": 0,
            "goodput": {"wall_s": wall,
                        "seconds": {"productive": wall},
                        "productive_fraction": 1.0,
                        "accounted_fraction": 1.0},
            "metrics": {}, "span_totals": {"step": wall},
            "perf": perf}


def test_merge_perf_cluster_mfu_and_report():
    from bigdl_tpu.telemetry.aggregate import merge_cluster, merge_perf
    from bigdl_tpu.telemetry.report import render_report

    payloads = {"host0": _payload("host0", 500.0, 10.0,
                                  hbm_peak=2048.0),
                "host1": _payload("host1", 300.0, 10.0,
                                  hbm_peak=1024.0)}
    perf = merge_perf(payloads)
    assert perf["flops_total"] == 800.0
    # (500+300) / (10*100 + 10*100) = 0.4
    assert perf["cluster_mfu"] == pytest.approx(0.4)
    assert perf["hbm_peak_bytes"] == 2048.0
    assert perf["programs"]["train_step"]["reporting_hosts"] == 2
    cluster = merge_cluster(payloads)
    assert cluster["perf"]["flops_total"] == 800.0
    text = render_report(cluster)
    assert "performance (XLA cost model)" in text
    assert "cluster MFU: 40.0%" in text
    assert "train_step" in text and "compute-bound" in text
    # hosts without perf payloads keep the section absent, not broken
    bare = {k: {kk: vv for kk, vv in v.items() if kk != "perf"}
            for k, v in payloads.items()}
    assert merge_perf(bare) is None
    assert "performance (XLA" not in render_report(merge_cluster(bare))
