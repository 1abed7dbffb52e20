"""Synchrony as a Plan dimension (ISSUE 15).

* sync vocabulary: unknown values, periodic/stale with fsdp, stale on
  dense transport, and relaxed rules under a pipe mesh all rejected
  loudly;
* ``sync="step"`` default compiles a program with bitwise parity to
  the pre-sync engine (data-only AND data x model) — relaxed synchrony
  is opt-in per rule, never a silent numerics change;
* ``periodic(k)`` local SGD: loss trajectory within rtol 2e-3 of
  lockstep on the 8-dev forced-host mesh, amortized collective-bytes
  accounting + the ``bigdl_perf_sync_bytes_saved`` gauge, bitwise
  deterministic resume across an averaging boundary (replica stacks +
  step-phase counter ride the checkpoint);
* ``stale(s)`` bounded-staleness sparse updates: loss descends and
  tracks lockstep, replica divergence stays bounded;
* elastic: a membership change forces an averaging round (shape-
  mismatched or force-flagged resume re-seeds from the mean), and the
  ``relax_before_evict`` straggler mode widens the effective averaging
  period before voting eviction — the chaos spec shows the relaxed
  path completing faster than the eviction path under an injected
  straggler.
"""
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu import nn
from bigdl_tpu.dataset import Sample
from bigdl_tpu.dataset.dataset import array
from bigdl_tpu.optim import SGD, max_iteration, several_iteration
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel.plan import (Plan, Rule, compile_step_with_plan,
                                     derive_plan, named_leaves)
from bigdl_tpu.utils.rng import RNG, set_global_seed


class _LossLog:
    def __init__(self):
        self.losses = []
        self.walls = []

    def add_scalar(self, name, value, step):
        if name == "Loss":
            self.losses.append(float(value))
            self.walls.append(time.monotonic())


# ---------------------------------------------------------------------------
# vocabulary + rejection specs
# ---------------------------------------------------------------------------

def test_unknown_sync_rejected():
    with pytest.raises(ValueError, match="unknown synchrony"):
        Plan([Rule(".*", P(), sync="eventually")])
    with pytest.raises(ValueError, match="period"):
        Plan([Rule(".*", P(), sync="periodic(0)")])
    with pytest.raises(ValueError, match="staleness"):
        Plan([Rule(".*", P(), transport="sparse", sync="stale(0)")])


def test_sync_fsdp_rejected():
    with pytest.raises(ValueError, match="fsdp"):
        Plan([Rule(".*", P("data"), fsdp=True, sync="periodic(4)")])


def test_stale_requires_sparse_transport():
    with pytest.raises(ValueError, match="SPARSE update path"):
        Plan([Rule(".*", P(), sync="stale(2)")])
    # sparse transport composes fine
    Plan([Rule(".*", P(), transport="sparse", sync="stale(2)")])


def test_sync_with_pipe_rejected_at_compile():
    from bigdl_tpu.models.transformer import TransformerLM

    RNG().set_seed(3)
    lm = TransformerLM(17, embed_dim=8, num_heads=2, num_layers=2,
                       max_len=8)
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "pipe"))
    plan = Plan([Rule(".*", P(), sync="periodic(4)")])
    with pytest.raises(NotImplementedError, match="pipeline"):
        compile_step_with_plan(lm, nn.ClassNLLCriterion(), SGD(), mesh,
                               plan=plan)


def test_sync_degrades_on_data_sharded_leaf(caplog):
    """A leaf sharded over the data axis has exactly one copy of each
    element — periodic/stale degrade to 'step' with a warning, and the
    table records the effective sync."""
    mesh = Mesh(np.array(jax.devices()), ("data",))
    tree = {"emb": np.zeros((64, 8), np.float32),
            "w": np.zeros((8, 2), np.float32)}
    plan = Plan([Rule("emb", P("data"), transport="sparse",
                      sync="stale(2)"),
                 Rule(".*", P(), sync="periodic(4)")], mesh=mesh)
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu"):
        table = plan.table(tree)
    assert table["emb"] == "(data) | sparse | step"
    assert table["w"] == "replicated | dense | periodic(4)"
    assert any("sharded over the data axis" in r.message
               for r in caplog.records)


def test_derive_stamps_embedding_rules():
    """The Parallax hybrid as two rule lines: dense MLP rules stay
    'step'; a replicated sparse table's rule defaults to stale(s)
    under the staleness knob (module-level ``staleness=`` wins over
    the global), periodic(k) under the period knob; row-sharded
    tables stay 'step'."""
    from bigdl_tpu.models.dlrm import DLRM
    from bigdl_tpu.nn.embedding import ShardedEmbedding

    RNG().set_seed(1)
    mesh = Mesh(np.array(jax.devices()), ("data",))
    model = DLRM(dense_dim=4, table_sizes=(512, 64), embed_dim=8,
                 shard_min_bytes=4096)
    t = derive_plan(model, mesh, sync_staleness=3).table(
        model.param_tree())
    assert t["1/weight"] == "(data) | sparse | step"      # row-sharded
    assert t["2/weight"] == "replicated | sparse | stale(3)"
    assert t["0/0/weight"] == "replicated | dense | step"  # dense MLP
    t2 = derive_plan(model, mesh, sync_period=8).table(
        model.param_tree())
    assert t2["2/weight"] == "replicated | sparse | periodic(8)"
    # module-level staleness override beats the global knob
    RNG().set_seed(1)
    emb = nn.Sequential(ShardedEmbedding(64, 8, axis_name=None,
                                         staleness=5),
                        nn.Sum(dimension=2), nn.Linear(8, 2))
    t3 = derive_plan(emb, mesh, sync_staleness=3).table(emb.param_tree())
    assert t3["0/weight"] == "replicated | sparse | stale(5)"


def test_orbax_rejected_with_periodic(tmp_path):
    mesh = Mesh(np.array(jax.devices()), ("data",))
    RNG().set_seed(2)
    model = nn.Sequential(nn.Linear(8, 4), nn.Tanh(), nn.Linear(4, 1))
    eng = compile_step_with_plan(
        model, nn.MSECriterion(), SGD(), mesh,
        plan=Plan([Rule(".*", P(), sync="periodic(2)")]))
    params, slots, buffers = eng.init_state()
    with pytest.raises(NotImplementedError, match="orbax"):
        eng.checkpoint_tree(params, slots, buffers)


# ---------------------------------------------------------------------------
# accounting: amortized wire + saved-bytes
# ---------------------------------------------------------------------------

def _tree_bytes(tree):
    return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
               for a in jax.tree_util.tree_leaves(tree))


def test_collective_bytes_amortized_under_periodic():
    tree = {"w": np.zeros((64, 32), np.float32)}
    mesh = Mesh(np.array(jax.devices()), ("data",))
    nb = _tree_bytes(tree)
    ring = 2.0 * 7 / 8 * nb
    step = Plan([Rule(".*", P())], mesh=mesh)
    per8 = Plan([Rule(".*", P(), sync="periodic(8)")], mesh=mesh)
    assert step.collective_bytes(tree) == pytest.approx(ring)
    # the averaging round's ring bytes divided by k — cheaper, not free
    assert per8.collective_bytes(tree) == pytest.approx(ring / 8)
    assert per8.sync_bytes_saved(tree) == pytest.approx(ring - ring / 8)
    assert step.sync_bytes_saved(tree) == 0.0
    # stale sparse leaves unchanged: the exchange still runs every step
    sp = dict(transport="sparse")
    stale = Plan([Rule(".*", P(), sync="stale(2)", **sp)], mesh=mesh)
    lock = Plan([Rule(".*", P(), **sp)], mesh=mesh)
    assert stale.collective_bytes(tree) == pytest.approx(
        lock.collective_bytes(tree))
    assert stale.sync_bytes_saved(tree) == 0.0


# ---------------------------------------------------------------------------
# sync="step" parity: the default compiles the exact pre-sync program
# ---------------------------------------------------------------------------

def _cls_samples(n=128, d=8, seed=0):
    rng = np.random.RandomState(seed)
    xs = rng.rand(n, d).astype(np.float32)
    ys = (1 + (xs.sum(1) > d / 2)).astype(np.float32)
    return [Sample(x, y) for x, y in zip(xs, ys)]


def _drive(model_fn, samples, criterion, plan=None, mesh=None, steps=6,
           lr=0.2, batch=32, seed=5, ckpt=None, resume=False,
           sync_period=None, momentum=0.0):
    set_global_seed(seed)
    model = model_fn()
    rec = _LossLog()
    kw = {"mesh": mesh} if mesh is not None else {}
    opt = DistriOptimizer(model, array(samples), criterion,
                          batch_size=batch, **kw)
    opt.set_optim_method(SGD(learning_rate=lr, momentum=momentum))
    opt.set_end_when(max_iteration(steps))
    opt.set_train_summary(rec)
    if plan is not None:
        opt.set_sharding_plan(plan)
    if sync_period is not None:
        opt.set_sync_period(sync_period)
    if ckpt:
        opt.set_checkpoint(ckpt, several_iteration(1))
    if resume:
        set_global_seed(999)  # trainState must overwrite it
        assert opt.resume_from_checkpoint() is True
    opt.optimize()
    return rec, model


def test_step_sync_bitwise_parity_with_default():
    """Stamping every derived rule sync='step' explicitly compiles the
    same program as the untouched default — loss streams and trained
    params are bit-identical, on data-only AND data x model meshes."""
    from bigdl_tpu.parallel.tensor_parallel import (ColumnParallelLinear,
                                                    RowParallelLinear)

    samples = _cls_samples()

    def mlp():
        return nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                             nn.Linear(16, 2), nn.LogSoftMax())

    def tp():
        return nn.Sequential(
            ColumnParallelLinear(8, 16, axis_name="model"), nn.Tanh(),
            RowParallelLinear(16, 2, axis_name="model"),
            nn.LogSoftMax())

    devs = np.array(jax.devices())
    cases = [(mlp, Mesh(devs, ("data",))),
             (tp, Mesh(devs.reshape(2, 4), ("data", "model")))]
    for model_fn, mesh in cases:
        set_global_seed(5)
        plan = derive_plan(model_fn(), mesh)
        stamped = Plan([r._replace(sync="step") for r in plan.rules])
        rec_a, m_a = _drive(model_fn, samples, nn.ClassNLLCriterion(),
                            mesh=mesh)
        rec_b, m_b = _drive(model_fn, samples, nn.ClassNLLCriterion(),
                            plan=stamped, mesh=mesh)
        assert rec_a.losses == rec_b.losses  # bitwise: float == float
        for a, b in zip(jax.tree_util.tree_leaves(m_a.param_tree()),
                        jax.tree_util.tree_leaves(m_b.param_tree())):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# periodic(k): local SGD within tolerance of lockstep, gauges, resume
# ---------------------------------------------------------------------------

def _reg_samples(n=512, d=8, seed=3):
    rng = np.random.RandomState(seed)
    xs = rng.rand(n, d).astype(np.float32)
    w = rng.randn(d, 1).astype(np.float32)
    ys = (xs @ w + 0.3).astype(np.float32)
    return [Sample(x, y) for x, y in zip(xs, ys)]


def _reg_model():
    return nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))


@pytest.mark.parametrize("period,steps,batch", [(4, 8, 256), (8, 6, 128)])
def test_periodic_loss_matches_lockstep_rtol(period, steps, batch):
    """periodic(k) local SGD tracks the lockstep trajectory within
    rtol 2e-3 on the 8-dev forced-host mesh, while the plan-derived
    collective-bytes gauge reports the AMORTIZED wire and the new
    sync-saved gauge publishes."""
    from bigdl_tpu.telemetry import MetricsRegistry, Telemetry

    samples = _reg_samples()

    def run(plan):
        set_global_seed(5)
        model = _reg_model()
        tm = Telemetry(registry=MetricsRegistry())
        rec = _LossLog()
        opt = DistriOptimizer(model, array(samples), nn.MSECriterion(),
                              batch_size=batch)
        opt.set_optim_method(SGD(learning_rate=0.01))
        opt.set_end_when(max_iteration(steps))
        opt.set_telemetry(tm)
        opt.set_train_summary(rec)
        if plan is not None:
            opt.set_sharding_plan(plan)
        opt.optimize()
        snap = tm.registry.snapshot()["metrics"]

        def gauge(name):
            series = (snap.get(name) or {}).get("series") or []
            return float(series[0]["value"]) if series else None

        return (rec.losses, gauge("bigdl_perf_collective_bytes"),
                gauge("bigdl_perf_sync_bytes_saved"))

    got, rel_bytes, saved = run(
        Plan([Rule(".*", P(), sync=f"periodic({period})")]))
    want, lock_bytes, lock_saved = run(None)
    assert len(got) == len(want) == steps
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)
    if batch == 256:    # four noisier batches an epoch do not, in six
        assert got[-1] < got[0]  # and the trajectory descends
    # the amortized accounting: periodic(k) reports ~1/k of lockstep
    # (the 1-element bias is a scalar rule — it stays lockstep and
    # contributes its full ring to both: (160 + k) / 161 of 1/k)
    assert rel_bytes == pytest.approx(lock_bytes / period,
                                      rel=0.01 * period)
    assert saved == pytest.approx(lock_bytes - rel_bytes)
    assert lock_saved is None  # lockstep never publishes the gauge


def test_periodic_resume_bitwise_across_averaging_boundary(tmp_path):
    """Interrupt at step k-1 (the worst case: maximal unaveraged
    divergence), resume, and the combined loss stream is BITWISE
    identical to the uninterrupted run — the replica stacks ride the
    trainState leg and the step-phase counter optimMethod's state."""
    samples = _cls_samples()
    plan = lambda: Plan([Rule(".*", P(), sync="periodic(4)")])

    def model():
        return nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                             nn.Linear(16, 2), nn.LogSoftMax())

    rec_a, _ = _drive(model, samples, nn.ClassNLLCriterion(),
                      plan=plan(), steps=8, lr=0.3, momentum=0.9)
    rec_b1, _ = _drive(model, samples, nn.ClassNLLCriterion(),
                       plan=plan(), steps=3, lr=0.3, momentum=0.9,
                       ckpt=str(tmp_path / "ckpt"))
    rec_b2, _ = _drive(model, samples, nn.ClassNLLCriterion(),
                       plan=plan(), steps=8, lr=0.3, momentum=0.9,
                       ckpt=str(tmp_path / "ckpt"), resume=True)
    got = rec_b1.losses + rec_b2.losses
    assert len(got) == 8
    assert got == rec_a.losses  # bitwise: float == float


def test_masked_trailing_batch_composes_with_periodic():
    """A dataset whose tail batch needs pad-and-mask still trains
    under a periodic plan (the masked program threads the sync args
    too) and every loss is finite."""
    samples = _cls_samples(n=120)  # 120 % 32 != 0: masked tail batch
    rec, _ = _drive(
        lambda: nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                              nn.Linear(16, 2), nn.LogSoftMax()),
        samples, nn.ClassNLLCriterion(),
        plan=Plan([Rule(".*", P(), sync="periodic(3)")]), steps=6,
        lr=0.1)
    assert len(rec.losses) == 6
    assert all(np.isfinite(v) for v in rec.losses)


# ---------------------------------------------------------------------------
# stale(s): bounded-staleness sparse updates
# ---------------------------------------------------------------------------

def test_stale_sparse_descends_and_tracks_lockstep():
    """stale(2) on a replicated sparse table: the loss descends,
    stays close to the lockstep trajectory, and the replica stacks'
    divergence stays bounded (the one-step-late application is within
    any declared bound)."""
    mesh = Mesh(np.array(jax.devices()), ("data",))
    rng = np.random.RandomState(0)
    idx = rng.choice([3, 7, 11, 19], (32, 4)) + 1
    xs = jnp.asarray(idx.astype(np.float32))
    ys = jnp.asarray(
        (1 + (idx.sum(1) > idx.sum(1).mean())).astype(np.float32))

    def drive(sync):
        RNG().set_seed(2)
        model = nn.Sequential(nn.LookupTable(64, 8),
                              nn.Sum(dimension=2), nn.Linear(8, 2),
                              nn.LogSoftMax())
        rules = [Rule(r"^0/weight$", P(), transport="sparse",
                      sync=sync),
                 Rule(".*", P())]
        eng = compile_step_with_plan(model, nn.ClassNLLCriterion(),
                                     SGD(learning_rate=0.05), mesh,
                                     plan=Plan(rules))
        params, slots, buffers = eng.init_state()
        ss = eng.init_sync_state()
        losses = []
        for i in range(10):
            kw = {}
            if eng.has_relaxed:
                kw = dict(sync_flags=np.zeros((eng.n_flags,), np.int32),
                          sync_state=ss)
            out = eng.step(params, slots, buffers, 0.05, xs, ys,
                           rng=jax.random.PRNGKey(i), **kw)
            loss, params, slots, buffers, ok, _ = out[:6]
            assert bool(ok)
            if eng.has_relaxed:
                ss = out[6]
            losses.append(float(loss))
        return losses, params, eng

    stale, params, eng = drive("stale(2)")
    lock, _, _ = drive("step")
    assert eng.stale_cadences == {"0/weight": 2}
    assert stale[-1] < stale[0]
    # tracks lockstep (staleness costs a little accuracy, bounded)
    np.testing.assert_allclose(stale, lock, rtol=0.05, atol=0.02)
    # replica divergence bounded: the stacks stay within one step's
    # worth of gradient of each other
    table = np.asarray(dict(named_leaves(
        jax.device_get(params)))["0/weight"])
    assert table.shape[0] == 8
    spread = np.abs(table - table.mean(axis=0)).max()
    assert 0 < spread < 0.05, spread


# ---------------------------------------------------------------------------
# elastic: forced averaging + relax-before-evict
# ---------------------------------------------------------------------------

def test_membership_change_forces_averaging_round():
    """A sync_resume whose stacks match is honored bitwise; a forced
    averaging round (what every elastic re-entry sets) discards it and
    every replica re-seeds from the averaged model params."""
    mesh = Mesh(np.array(jax.devices()), ("data",))
    RNG().set_seed(4)
    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    eng = compile_step_with_plan(
        model, nn.MSECriterion(), SGD(learning_rate=0.1), mesh,
        plan=Plan([Rule(".*", P(), sync="periodic(4)")]))
    params, slots, buffers = eng.init_state()
    # manufacture divergence, then snapshot it
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(32, 4).astype(np.float32))
    y = jnp.asarray(rng.rand(32, 1).astype(np.float32))
    out = eng.step(params, slots, buffers, 0.1, x, y,
                   sync_flags=np.zeros((1,), np.int32))
    params, slots = out[1], out[2]
    snap = eng.sync_snapshot(params, slots, None)
    w = snap["params"]["0/weight"]
    assert np.abs(w - w[0:1]).max() > 0  # replicas really diverged
    # matching resume: honored bitwise
    p2, s2, _ = eng.init_state(sync_resume=snap)
    w2 = np.asarray(dict(named_leaves(
        jax.device_get(p2)))["0/weight"])
    np.testing.assert_array_equal(w2, w)
    # forced averaging (the driver passes sync_resume=None after a
    # membership change): every replica seeds from the model's value
    eng.sync_to_model(params, slots, buffers)  # model := stack mean
    p3, _, _ = eng.init_state(sync_resume=None)
    w3 = np.asarray(dict(named_leaves(
        jax.device_get(p3)))["0/weight"])
    np.testing.assert_array_equal(w3, np.broadcast_to(
        w.mean(axis=0).astype(w.dtype), w.shape))
    # a shape-mismatched stack (elastic shrink changed n_data) is
    # discarded the same way instead of crashing
    bad = {"params": {"0/weight": w[:4]}, "slots": {}, "pending": {}}
    p4, _, _ = eng.init_state(sync_resume=bad)
    w4 = np.asarray(dict(named_leaves(
        jax.device_get(p4)))["0/weight"])
    np.testing.assert_array_equal(w4, w3)


def test_relax_before_evict_policy():
    """The straggler policy's relax mode: the first max_relax_rounds
    qualifying observations widen the period factor instead of naming
    a victim; the victim only falls out after the rounds are spent;
    recovery tightens the factor back."""
    from bigdl_tpu.resilience.elastic import StragglerPolicy

    pol = StragglerPolicy(skew_threshold=2.0, patience=2,
                          eviction_budget=1, relax_before_evict=True,
                          relax_factor=2.0, max_relax_rounds=2)
    slow = {"host0": 0.1, "host1": 0.1, "host2": 1.0}
    assert pol.period_factor == 1.0
    for _ in range(2):
        pol.observe(slow)
    assert pol.victim() is None          # round 1: relax, not evict
    assert pol.period_factor == 2.0
    for _ in range(2):
        pol.observe(slow)
    assert pol.victim() is None          # round 2: relax again
    assert pol.period_factor == 4.0
    for _ in range(2):
        pol.observe(slow)
    assert pol.victim() == "host2"       # rounds spent: last resort
    # recovery: every relaxed host back under threshold resets
    pol2 = StragglerPolicy(skew_threshold=2.0, patience=1,
                           relax_before_evict=True, relax_factor=2.0,
                           max_relax_rounds=2)
    pol2.observe(slow)
    assert pol2.victim() is None and pol2.period_factor == 2.0
    pol2.observe({"host0": 0.1, "host1": 0.1, "host2": 0.1})
    assert pol2.period_factor == 1.0


def test_relaxed_beats_eviction_under_straggler(tmp_path, monkeypatch):
    """The chaos spec: a 3-host gang with one chronic straggler.  The
    eviction path pays restore + mesh re-derivation + recompile; the
    relax_before_evict path widens the averaging period and keeps
    training — it completes the same step budget in less wall clock
    (the time-to-loss-target win the bench leg measures at scale),
    with zero evictions and the period factor visibly widened."""
    # the trace-profiled iteration's first xplane parse costs seconds
    # of pure measurement overhead and would land in whichever run
    # goes first — the judged walls run unprofiled (the DLRM bench
    # leg's rule)
    monkeypatch.setenv("BIGDL_METRICS_PROFILEINTERVAL", "0")
    from bigdl_tpu.resilience import (CollectiveWatchdog, ElasticContext,
                                      ElasticCoordinator, InMemoryKV,
                                      RetryPolicy, SimulatedHost,
                                      StepTimeEstimator)
    from bigdl_tpu.resilience.elastic import StragglerPolicy

    samples = _cls_samples(n=120, seed=7)

    def run(relax, tag):
        kv = InMemoryKV()
        hosts = ["host0", "host1", "host2"]
        coord = ElasticCoordinator("host0", kv, heartbeat_timeout=0.3)
        coord.bootstrap(hosts)
        sims = [SimulatedHost("host1", kv, heartbeat_timeout=0.3),
                SimulatedHost("host2", kv, heartbeat_timeout=0.3,
                              step_time=1.0)]  # chronic straggler
        pol = StragglerPolicy(skew_threshold=3.0, patience=2,
                              eviction_budget=1, sustain=0.0,
                              relax_before_evict=relax,
                              relax_factor=2.0, max_relax_rounds=8)
        ctx = ElasticContext(
            coord,
            watchdog=CollectiveWatchdog(StepTimeEstimator(
                floor=0.75, multiplier=4.0, min_samples=3,
                warmup_deadline=15.0)),
            straggler=pol, rendezvous_timeout=2.0,
            regrow_after_steps=1000)
        set_global_seed(7)
        model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                              nn.Linear(16, 2), nn.LogSoftMax())
        rec = _LossLog()
        opt = DistriOptimizer(model, array(samples),
                              nn.ClassNLLCriterion(), batch_size=12)
        opt.set_optim_method(SGD(learning_rate=0.2))
        opt.set_sharding_plan(
            Plan([Rule(".*", P(), sync="periodic(2)")]))
        opt.set_end_when(max_iteration(12))
        opt.set_checkpoint(str(tmp_path / f"ckpt_{tag}"),
                           several_iteration(1))
        opt.set_retry_policy(RetryPolicy(max_retries=10,
                                         backoff_base=0.01,
                                         backoff_max=0.05))
        opt.set_elastic(ctx)
        opt.set_train_summary(rec)
        for s in sims:
            s.start()
        try:
            opt.optimize()
        finally:
            for s in sims:
                s.stop()
        return rec, ctx, pol

    rec_rel, ctx_rel, pol_rel = run(True, "relax")
    rec_ev, ctx_ev, pol_ev = run(False, "evict")
    # compile-fair timing: the first run pays the process's XLA
    # compiles for the shared data=3 program, so the judged wall is
    # first-loss -> last-loss (the eviction path's restore + data=2
    # recompile lands inside its span; the relaxed path has neither)
    wall_rel = rec_rel.walls[-1] - rec_rel.walls[0]
    wall_ev = rec_ev.walls[-1] - rec_ev.walls[0]
    # the eviction path really evicted (and paid the re-derivation)
    assert ctx_ev.counters()["evictions"] >= 1
    assert ctx_ev.counters()["incarnation_changes"] >= 1
    # the relaxed path absorbed the skew without a single eviction
    assert ctx_rel.counters()["evictions"] == 0
    assert pol_rel.relax_rounds >= 1
    assert "host2" in pol_rel.relaxed_hosts
    # both descend; the relaxed run finishes the same budget faster
    assert rec_rel.losses[-1] < rec_rel.losses[0]
    assert rec_ev.losses[-1] < rec_ev.losses[0]
    assert len(rec_rel.losses) == 12
    assert wall_rel < wall_ev, (wall_rel, wall_ev)
    # time-to-loss-target: the relaxed run reaches the eviction run's
    # final loss no later than the eviction run did
    target = rec_ev.losses[-1]
    t_rel = next((w - rec_rel.walls[0]
                  for w, l in zip(rec_rel.walls, rec_rel.losses)
                  if l <= target), wall_rel)
    assert t_rel <= wall_ev, (t_rel, wall_ev)
