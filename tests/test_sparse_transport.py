"""Sparsity-aware gradient transport + sharded-embedding DLRM specs
(ISSUE 10).

* transport vocabulary: unknown transports and sparse+FSDP rules
  rejected loudly at plan construction; sparse-with-pipe compositions
  rejected loudly at derive/compile time;
* numerics: same seed, same Zipf batches — the sparse-transport loss
  trajectory matches the dense all-reduce run within the composed-mesh
  tolerance PR 8 established (rtol 2e-3), and the measured collective
  bytes (the plan-derived gauge) shrink;
* density-threshold crossover: the trace-time fallback engages when
  the budgeted sparse wire cannot beat the dense all-reduce, and the
  in-program runtime fallback keeps numerics exact when a batch
  overflows the row budget;
* ShardedEmbedding: the all_gather/psum_scatter index exchange equals
  a local gather, rows and slots shard over the bound axis;
* clickstream: seeded determinism + checkpointable pipeline state;
* DLRM deterministic resume: preempt/resume losses bitwise-identical;
* chaos (acceptance): host death mid-train with row-sharded tables —
  shrink re-derives mesh+plan, rows re-partition across survivors (no
  silent row loss: the final checkpoint restores bitwise-identical
  tables), loss descends across the incarnation boundary.
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu import nn
from bigdl_tpu.dataset import Sample, ZipfClickstream
from bigdl_tpu.dataset.dataset import array
from bigdl_tpu.models.dlrm import DLRM
from bigdl_tpu.optim import (SGD, LocalOptimizer, max_iteration,
                             several_iteration)
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel.plan import (Plan, Rule, compile_step_with_plan,
                                     derive_plan)
from bigdl_tpu.utils.rng import RNG, set_global_seed


class _LossLog:
    def __init__(self):
        self.losses = []

    def add_scalar(self, name, value, step):
        if name == "Loss":
            self.losses.append(float(value))


# ---------------------------------------------------------------------------
# transport vocabulary + rejection specs
# ---------------------------------------------------------------------------

def test_unknown_transport_rejected():
    with pytest.raises(ValueError, match="unknown gradient transport"):
        Plan([Rule(".*", P(), transport="gather")])


def test_sparse_fsdp_rule_rejected():
    with pytest.raises(ValueError, match="fsdp"):
        Plan([Rule(".*", P("data"), fsdp=True, transport="sparse")])


def test_table_carries_transport_column():
    mesh = Mesh(np.array(jax.devices()), ("data",))
    tree = {"emb": np.zeros((64, 8), np.float32),
            "w": np.zeros((8, 2), np.float32)}
    plan = Plan([Rule("emb", P(), transport="sparse"),
                 Rule(".*", P())], mesh=mesh)
    table = plan.table(tree)
    assert table["emb"] == "replicated | sparse | step"
    assert table["w"] == "replicated | dense | step"


def test_sparse_with_pipe_rejected_at_derive():
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "pipe"))
    RNG().set_seed(3)
    model = DLRM(dense_dim=4, table_sizes=(64,), embed_dim=8,
                 shard_min_bytes=1 << 30)
    with pytest.raises(NotImplementedError, match="pipeline"):
        derive_plan(model, mesh, pipe_axis="pipe", n_pipe=2)


def test_sparse_with_pipe_rejected_at_compile():
    """An EXPLICIT sparse plan on a pipe mesh is rejected by the
    builder itself (the derive path can't see user rules)."""
    from bigdl_tpu.models.transformer import TransformerLM

    RNG().set_seed(3)
    lm = TransformerLM(17, embed_dim=8, num_heads=2, num_layers=2,
                       max_len=8)
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "pipe"))
    plan = Plan([Rule(".*", P(), transport="sparse")])
    with pytest.raises(NotImplementedError, match="pipeline"):
        compile_step_with_plan(lm, nn.ClassNLLCriterion(), SGD(), mesh,
                               plan=plan)


# ---------------------------------------------------------------------------
# trace-time density-threshold fallback (decision recorded per leaf)
# ---------------------------------------------------------------------------

def _tiny_lookup_model():
    RNG().set_seed(2)
    return nn.Sequential(nn.LookupTable(64, 8), nn.Sum(dimension=2),
                         nn.Linear(8, 2), nn.LogSoftMax())


def test_transport_table_records_decisions():
    mesh = Mesh(np.array(jax.devices()), ("data",))
    model = _tiny_lookup_model()
    rules = [Rule(r"^0/weight$", P(), transport="sparse"),
             Rule(".*", P())]
    eng = compile_step_with_plan(
        model, nn.ClassNLLCriterion(), SGD(), mesh,
        plan=Plan(rules, sparse_density=1.0 / 16))
    assert eng.transport_table["0/weight"].startswith("sparse (row")
    assert eng.sparse_bytes_saved > 0
    # density 1.0: the budget is the whole table — the sparse wire
    # cannot beat the dense all-reduce, so the fallback engages at
    # trace time and is recorded
    eng2 = compile_step_with_plan(
        model, nn.ClassNLLCriterion(), SGD(), mesh,
        plan=Plan(rules, sparse_density=1.0))
    assert "density-threshold fallback" in eng2.transport_table[
        "0/weight"]
    assert eng2.sparse_bytes_saved == 0.0
    # and the accounting follows the decision
    assert eng.collective_bytes < eng2.collective_bytes


# ---------------------------------------------------------------------------
# numerics: sparse == dense, including the runtime overflow fallback
# ---------------------------------------------------------------------------

def _drive_lookup(transport_plan, xs, ys, steps=3, lr=0.5):
    model = _tiny_lookup_model()
    mesh = Mesh(np.array(jax.devices()), ("data",))
    eng = compile_step_with_plan(model, nn.ClassNLLCriterion(),
                                 SGD(learning_rate=lr), mesh,
                                 plan=transport_plan)
    params, slots, buffers = eng.init_state()
    losses = []
    for _ in range(steps):
        out = eng.step(params, slots, buffers, lr, xs, ys,
                       rng=jax.random.PRNGKey(0))
        loss, params, slots, buffers, ok, _ = out
        assert bool(ok)
        losses.append(float(loss))
    return losses, jax.device_get(params)


@pytest.mark.parametrize("overflow", [False, True])
def test_sparse_matches_dense_exactly_lookup(overflow):
    """Few-rows batch rides the sparse wire; a batch touching more
    rows than the budget (K=4 at density 1/16 on a 64-row table) hits
    the IN-PROGRAM dense fallback — numerics match the dense plan in
    both regimes, which is only possible if the fallback engaged."""
    rng = np.random.RandomState(0)
    if overflow:
        idx = rng.randint(1, 65, (16, 4))        # ~40 distinct rows >> K
    else:
        idx = rng.choice([3, 7, 11], (16, 4)) + 1  # 3 rows << K... per
        # shard each of the 8 shards sees 2 records -> <= 8 rows
    xs = jnp.asarray(idx.astype(np.float32))
    ys = jnp.asarray(rng.randint(1, 3, 16).astype(np.float32))
    sparse_plan = Plan([Rule(r"^0/weight$", P(), transport="sparse"),
                        Rule(".*", P())])
    dense_plan = Plan([Rule(".*", P())])
    got, p_got = _drive_lookup(sparse_plan, xs, ys)
    want, p_want = _drive_lookup(dense_plan, xs, ys)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    for a, b in zip(jax.tree_util.tree_leaves(p_got),
                    jax.tree_util.tree_leaves(p_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("table_sizes,dim,batch,shard_min,win", [
    ((1024, 256, 64), 8, 64, 16 * 1024, 3),
    ((2048, 512, 128), 16, 128, 64 * 1024, 5)])
def test_dlrm_sparse_matches_dense_loss_trajectory(table_sizes, dim, batch,
                                                   shard_min, win):
    """The satellite spec: same seed, same Zipf batches — the DLRM
    with row-sharded big tables + sparse-transport small tables tracks
    the replicate-everything dense-all-reduce run within the
    composed-mesh tolerance (rtol 2e-3), while the measured collective
    bytes (the plan gauge) shrink and the saved-bytes gauge
    publishes."""
    from bigdl_tpu.telemetry import MetricsRegistry, Telemetry

    def drive(plan):
        set_global_seed(11)
        model = DLRM(dense_dim=4, table_sizes=table_sizes, embed_dim=dim,
                     shard_min_bytes=shard_min)
        ds = ZipfClickstream(4 * batch, table_sizes, dense_dim=4)
        tm = Telemetry(registry=MetricsRegistry())
        rec = _LossLog()
        opt = DistriOptimizer(model, ds, nn.BCECriterion(),
                              batch_size=batch)
        opt.set_optim_method(SGD(learning_rate=0.5))
        opt.set_end_when(max_iteration(6))
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
                gauge("bigdl_perf_sparse_bytes_saved"), model)

    sparse_losses, sparse_bytes, saved, model = drive(None)
    assert model.sharded_tables == [0]  # the first table alone is over
    dense_losses, dense_bytes, _, _ = drive(Plan([Rule(".*", P())]))
    assert len(sparse_losses) == len(dense_losses) == 6
    np.testing.assert_allclose(sparse_losses, dense_losses, rtol=2e-3,
                               atol=2e-4)
    # the wire win the transport exists for, on the judged gauge
    assert sparse_bytes is not None and dense_bytes is not None
    assert sparse_bytes < dense_bytes / win
    assert saved and saved > 0


# ---------------------------------------------------------------------------
# ShardedEmbedding: exchange == gather; degraded replica still correct
# ---------------------------------------------------------------------------

def test_sharded_embedding_exchange_matches_local_gather():
    from bigdl_tpu.nn.embedding import ShardedEmbedding
    from jax import shard_map

    RNG().set_seed(4)
    emb = ShardedEmbedding(64, 8, axis_name="data")
    w = emb.param_tree()["weight"]
    mesh = Mesh(np.array(jax.devices()), ("data",))
    idx = np.random.RandomState(0).randint(1, 65, (16, 3)).astype(
        np.float32)

    def local(p, x):
        out, _ = emb.apply_fn(p, {}, x, False, None)
        return out

    fwd = jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=({"weight": P("data")}, P("data")),
        out_specs=P("data"), check_vma=False))
    got = np.asarray(fwd({"weight": w}, jnp.asarray(idx)))
    want = np.asarray(jnp.take(w, jnp.asarray(idx, jnp.int32) - 1,
                               axis=0))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # unbound: plain gather, same function
    out, _ = emb.apply_fn({"weight": w}, {}, jnp.asarray(idx), False,
                          None)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)


def test_sharded_embedding_degrades_to_replica_when_rows_dont_divide(
        caplog):
    """A 50-row table cannot shard 8 ways: the plan degrades it to a
    full replica with a warning — rows replicate, never drop — and the
    module detects the full table and gathers locally."""
    RNG().set_seed(4)
    model = DLRM(dense_dim=4, table_sizes=(50,), embed_dim=8,
                 shard_min_bytes=0)
    mesh = Mesh(np.array(jax.devices()), ("data",))
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu"):
        plan = derive_plan(model, mesh)
        table = plan.table(model.param_tree())
    assert table["1/weight"] == "replicated | sparse | step"
    assert any("does not divide" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# clickstream: seeded + checkpointable
# ---------------------------------------------------------------------------

def test_clickstream_deterministic_and_stateful():
    a = ZipfClickstream(64, (128, 32), dense_dim=4, seed=9)
    b = ZipfClickstream(64, (128, 32), dense_dim=4, seed=9)
    for sa, sb in zip(a.data(train=False), b.data(train=False)):
        np.testing.assert_array_equal(sa.feature[0], sb.feature[0])
        np.testing.assert_array_equal(sa.feature[1], sb.feature[1])
        np.testing.assert_array_equal(sa.label, sb.label)
    c = ZipfClickstream(64, (128, 32), dense_dim=4, seed=10)
    assert not np.array_equal(
        np.stack([s.feature[1] for s in a.data(train=False)]),
        np.stack([s.feature[1] for s in c.data(train=False)]))
    # labels are skewed Bernoulli, indices 1-based within vocab
    idx = np.stack([s.feature[1] for s in a.data(train=False)])
    assert idx.min() >= 1 and idx[:, 0].max() <= 128 \
        and idx[:, 1].max() <= 32
    # the epoch order is checkpointable pipeline state (the
    # LocalArrayDataSet contract every other dataset rides)
    a.shuffle()
    state = a.state_dict()
    order_after = [s.label.tobytes() for s in a.data(train=False)]
    d = ZipfClickstream(64, (128, 32), dense_dim=4, seed=9)
    d.load_state_dict(state)
    # data(train=False) iterates storage order; train=True follows the
    # index permutation — compare permutations directly
    np.testing.assert_array_equal(state["index"],
                                  d.state_dict()["index"])
    assert order_after  # sanity: the epoch yielded records


def test_dlrm_resume_bitwise(tmp_path):
    """Preempt-and-resume on the DLRM + clickstream pipeline: the
    resumed run's losses are BITWISE identical to the uninterrupted
    run — sharded-table state, RNG stream and the Zipf cursor all came
    back (the ISSUE 10 acceptance's resume leg)."""
    steps = 6
    table_sizes = (64, 16)

    def build():
        set_global_seed(123)
        model = DLRM(dense_dim=4, table_sizes=table_sizes, embed_dim=8,
                     shard_min_bytes=1024)
        ds = ZipfClickstream(128, table_sizes, dense_dim=4)
        opt = LocalOptimizer(model, ds, nn.BCECriterion(),
                             batch_size=32)
        opt.set_optim_method(SGD(learning_rate=0.2))
        return opt

    rec_a = _LossLog()
    opt = build()
    opt.set_end_when(max_iteration(steps))
    opt.set_train_summary(rec_a)
    opt.optimize()

    rec_b = _LossLog()
    opt = build()
    opt.set_end_when(max_iteration(3))
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(1))
    opt.set_train_summary(rec_b)
    opt.optimize()

    # the generated STREAM is constructional (np_stream mixes the
    # global seed): rebuild it under the original seed, then flip the
    # global seed — the checkpoint's trainState must overwrite it
    set_global_seed(123)
    ds2 = ZipfClickstream(128, table_sizes, dense_dim=4)
    set_global_seed(999)
    model2 = DLRM(dense_dim=4, table_sizes=table_sizes, embed_dim=8,
                  shard_min_bytes=1024)
    opt2 = LocalOptimizer(model2, ds2, nn.BCECriterion(), batch_size=32)
    opt2.set_optim_method(SGD(learning_rate=0.2))
    opt2.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(1))
    assert opt2.resume_from_checkpoint() is True
    rec_b2 = _LossLog()
    opt2.set_end_when(max_iteration(steps))
    opt2.set_train_summary(rec_b2)
    opt2.optimize()

    got = rec_b.losses + rec_b2.losses
    assert len(got) == steps
    assert got == rec_a.losses  # bitwise: float == float


# ---------------------------------------------------------------------------
# chaos: host death with row-sharded tables (the acceptance spec)
# ---------------------------------------------------------------------------

def test_host_death_repartitions_sharded_rows(tmp_path):
    """3-host gang training a DLRM whose big table row-shards over the
    data axis; host2 dies mid-run.  The shrink re-derives mesh+plan
    (data 3 -> 2: 48 rows go 16/shard -> 24/shard — re-partitioned,
    not dropped), loss keeps descending across the incarnation
    boundary, and the final checkpoint restores a bitwise-identical
    table into a fresh model (checksummed: no silent row loss)."""
    from bigdl_tpu.resilience import (CollectiveWatchdog, ElasticContext,
                                      ElasticCoordinator, InMemoryKV,
                                      RetryPolicy, SimulatedHost,
                                      StepTimeEstimator, faults)
    from bigdl_tpu.resilience.integrity import checksum_tree

    kv = InMemoryKV()
    hosts = ["host0", "host1", "host2"]
    coord = ElasticCoordinator("host0", kv, heartbeat_timeout=0.3)
    coord.bootstrap(hosts)
    sims = [SimulatedHost("host1", kv, heartbeat_timeout=0.3),
            SimulatedHost("host2", kv, heartbeat_timeout=0.3,
                          die_at_leader_step=6)]
    ctx = ElasticContext(
        coord,
        watchdog=CollectiveWatchdog(StepTimeEstimator(
            floor=0.75, multiplier=4.0, min_samples=3,
            warmup_deadline=15.0)),
        rendezvous_timeout=2.0, regrow_after_steps=100)

    meshes = []
    orig = ctx.current_mesh
    ctx.current_mesh = lambda: (meshes.append(orig()) or meshes[-1])

    table_sizes = (48, 12)
    set_global_seed(7)
    model = DLRM(dense_dim=4, table_sizes=table_sizes, embed_dim=8,
                 shard_min_bytes=1024)  # 48x8 f32 = 1.5 KiB: sharded
    assert model.sharded_tables == [0]
    ds = ZipfClickstream(144, table_sizes, dense_dim=4)

    rec = _LossLog()
    opt = DistriOptimizer(model, ds, nn.BCECriterion(), batch_size=12)
    opt.set_optim_method(SGD(learning_rate=0.3))
    opt.set_end_when(max_iteration(14))
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(1))
    opt.set_retry_policy(RetryPolicy(max_retries=10, backoff_base=0.01,
                                     backoff_max=0.05))
    opt.set_elastic(ctx)
    opt.set_train_summary(rec)

    for s in sims:
        s.start()
    try:
        # paced, or a fast host ends the run inside the heartbeat window
        with faults.delay_host("host0", 0.05, at_step=1):
            opt.optimize()
    finally:
        for s in sims:
            s.stop()

    assert opt.optim_method.state["neval"] - 1 == 14, "run must complete"
    assert ctx.counters()["incarnation_changes"] >= 1
    # the shrink really re-partitioned: data axis 3 -> 2
    assert len(meshes) >= 2
    assert meshes[0].shape["data"] == 3
    assert meshes[-1].shape["data"] == 2, dict(meshes[-1].shape)
    # loss descends across the incarnation boundary
    assert rec.losses[-1] < rec.losses[0]
    # no silent row loss: the final checkpoint restores the full table
    # bitwise into a fresh model (host-side reassembly of the sharded
    # rows round-trips), proven by checksum AND element equality
    set_global_seed(999)
    model2 = DLRM(dense_dim=4, table_sizes=table_sizes, embed_dim=8,
                  shard_min_bytes=1024)
    opt2 = DistriOptimizer(model2,
                           ZipfClickstream(144, table_sizes, dense_dim=4),
                           nn.BCECriterion(), batch_size=12)
    opt2.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(1))
    assert opt2.resume_from_checkpoint() is True
    assert checksum_tree(model2.param_tree()) == \
        checksum_tree(model.param_tree())
    for a, b in zip(jax.tree_util.tree_leaves(model.param_tree()),
                    jax.tree_util.tree_leaves(model2.param_tree())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
