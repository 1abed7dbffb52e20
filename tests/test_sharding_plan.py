"""Unified sharding-plan engine specs (ISSUE 8).

* golden plan tables: the derived regex rules applied to the ResNet-50,
  TransformerLM and Llama param trees snapshot to committed
  PartitionSpec tables (tests/fixtures/plan_*.json) — regenerate with
  ``BIGDL_REGEN_PLAN_GOLDENS=1 pytest tests/test_sharding_plan.py -k
  golden``;
* composed-mesh equivalence: data=2 x pipe=2 x model=2 on the 8
  forced-host CPU devices, loss trajectory matching the single-device
  run;
* FSDP: per-device addressable param bytes shrink ~1/N (telemetry
  registry gauges) and training matches plain data parallelism;
* elastic shrink on a multi-axis mesh re-derives a mesh/plan that
  KEEPS the model axis (the old shrink silently degraded to data-only);
* plan-derived collective-bytes accounting (the PerfAccountant gauge's
  new source) and the dropped-axis diagnosability warning.
"""
import json
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu import nn
from bigdl_tpu.dataset import Sample
from bigdl_tpu.dataset.dataset import array
from bigdl_tpu.optim import SGD, LocalOptimizer, max_iteration
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer, normalize_mesh
from bigdl_tpu.parallel.plan import (Plan, Rule, compile_step_with_plan,
                                     derive_plan, match_partition_rules,
                                     named_leaves)
from bigdl_tpu.utils.rng import RNG

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


# ---------------------------------------------------------------------------
# rule matching unit specs
# ---------------------------------------------------------------------------

def test_match_partition_rules_order_scalars_and_unmatched():
    tree = {"0": {"weight": np.zeros((8, 4), np.float32),
                  "bias": np.zeros((8,), np.float32)},
            "t": np.float32(0.0)}  # scalar: never partitioned
    rules = [Rule(r"0/weight", P("model", None)),
             Rule(r".*", P())]
    specs = match_partition_rules(rules, tree)
    assert specs["0"]["weight"] == P("model", None)
    assert specs["0"]["bias"] == P()
    assert specs["t"] == P()
    # first match wins: a later broader rule never overrides
    rules2 = [Rule(r"weight", P("model", None)),
              Rule(r"0/weight", P(None, "model")), Rule(r".*", P())]
    assert match_partition_rules(rules2, tree)["0"]["weight"] == \
        P("model", None)
    with pytest.raises(ValueError, match="no partition rule"):
        match_partition_rules([Rule(r"nothing", P())], tree)


def test_plan_degrades_missing_axes_with_warning(caplog):
    tree = {"w": np.zeros((8, 4), np.float32)}
    mesh = Mesh(np.array(jax.devices()), ("data",))
    plan = Plan([Rule(r"w", P("model", None)), Rule(r".*", P())],
                mesh=mesh)
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu"):
        specs = plan.param_specs(tree)
    assert specs["w"] == P(None, None)
    assert any("model" in r.message and "not in mesh" in r.message
               for r in caplog.records)


def test_resolve_axes_warns_on_dropped_bound_axis(caplog):
    """Satellite: a model BUILT for an axis the mesh lacks used to run
    silently un-parallelized — now the dropped axis is named."""
    from bigdl_tpu.parallel.spmd import _resolve_axes, bound_axes
    from bigdl_tpu.parallel.tensor_parallel import ColumnParallelLinear

    model = nn.Sequential(ColumnParallelLinear(4, 8, axis_name="model"),
                          nn.Tanh())
    mesh = Mesh(np.array(jax.devices()), ("data",))
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu"):
        d, s, m = _resolve_axes(mesh, "data", "seq", "model",
                                bound=bound_axes(model))
    assert (d, s, m) == ("data", None, None)
    assert any("'model'" in r.message for r in caplog.records), \
        [r.message for r in caplog.records]
    # an unbound default axis (seq here) drops silently — no spam
    assert not any("'seq'" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# collective-bytes accounting (the PerfAccountant satellite)
# ---------------------------------------------------------------------------

def _tree_bytes(tree):
    return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
               for a in jax.tree_util.tree_leaves(tree))


def test_collective_bytes_matches_data_ring_on_pure_dp():
    tree = {"w": np.zeros((64, 32), np.float32),
            "b": np.zeros((64,), np.float32)}
    mesh = Mesh(np.array(jax.devices()), ("data",))
    plan = Plan([Rule(r".*", P())], mesh=mesh)
    want = 2.0 * 7 / 8 * _tree_bytes(tree)
    assert plan.collective_bytes(tree) == pytest.approx(want)


def test_collective_bytes_counts_tp_and_fsdp():
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    w = np.zeros((64, 32), np.float32)          # 8192 bytes
    tree = {"tp": w, "fsdp": w, "repl": w}
    plan = Plan([Rule(r"tp", P("model", None)),
                 Rule(r"fsdp", P("data", None), fsdp=True),
                 Rule(r".*", P())], mesh=mesh)
    nb = float(w.nbytes)
    # tp: slice nb/4 all-reduced over data (R=2) -> 2*(1/2)*nb/4
    # fsdp: gather+scatter over data -> 2*(1/2)*nb, plus the slice
    #       (nb/2) all-reduced over model (R=4) -> 2*(3/4)*nb/2
    # repl: all-reduce over both axes (R=8) -> 2*(7/8)*nb
    want = (2 * 0.5 * nb / 4) + (2 * 0.5 * nb + 2 * 0.75 * nb / 2) \
        + (2 * 7 / 8 * nb)
    assert plan.collective_bytes(tree) == pytest.approx(want)


def test_engine_reports_plan_collective_bytes():
    """The driver's cost-model call now carries the PLAN's estimate —
    on a TP mesh it must be the sliced accounting, not the data ring."""
    from bigdl_tpu.parallel.tensor_parallel import (ColumnParallelLinear,
                                                    RowParallelLinear)

    RNG().set_seed(2)
    model = nn.Sequential(ColumnParallelLinear(8, 16, axis_name="model"),
                          nn.Tanh(),
                          RowParallelLinear(16, 2, axis_name="model"),
                          nn.LogSoftMax())
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    eng = compile_step_with_plan(model, nn.ClassNLLCriterion(), SGD(),
                                 mesh)
    plan_bytes = eng.plan.collective_bytes(model.param_tree())
    assert eng.collective_bytes == pytest.approx(plan_bytes)
    ring = 2.0 * 7 / 8 * _tree_bytes(model.param_tree())
    assert eng.collective_bytes < ring  # sliced TP traffic < full ring


# ---------------------------------------------------------------------------
# golden plan tables
# ---------------------------------------------------------------------------

def _golden_cases():
    """name -> (param tree, bound plan).  Architectures pinned by the
    committed fixtures; shapes (not weights) define the tables."""
    devs = np.array(jax.devices())
    cases = {}

    def resnet50():
        from bigdl_tpu.models.resnet import ResNet50

        RNG().set_seed(1)
        model = ResNet50(class_num=1000)
        mesh = Mesh(devs, ("data",))
        # 1 MiB threshold: the big 3x3 convs and the 2048x1000 FC shard
        # over data (FSDP); the small early convs/BN params replicate
        plan = derive_plan(model, mesh, fsdp_min_bytes=1 << 20)
        return model.param_tree(), plan

    def transformerlm():
        from bigdl_tpu.models.transformer import TransformerLM

        RNG().set_seed(1)
        lm = TransformerLM(32, embed_dim=16, num_heads=4, num_layers=2,
                           max_len=8, model_axis="model")
        mesh = Mesh(devs.reshape(2, 4), ("data", "model"))
        return lm.param_tree(), derive_plan(lm, mesh)

    def llama():
        torch = pytest.importorskip("torch")
        transformers = pytest.importorskip("transformers")
        from bigdl_tpu.interop import load_llama

        torch.manual_seed(0)
        cfg = transformers.LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=48,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=24,
            rms_norm_eps=1e-5, rope_theta=10000.0, attention_bias=False,
            tie_word_embeddings=False)
        lm = load_llama(transformers.LlamaForCausalLM(cfg).eval())
        mesh = Mesh(devs, ("data",))
        # low threshold: the embedding/head/MLP weights FSDP-shard, the
        # tiny norms replicate — the per-variable plan Parallax argues
        # for, visible in one table
        return lm.param_tree(), derive_plan(lm, mesh,
                                            fsdp_min_bytes=4096)

    def dlrm():
        from bigdl_tpu.models.dlrm import DLRM

        RNG().set_seed(1)
        # 4 KiB shard threshold: the 512-row table row-shards over
        # data, the 64-row table replicates — BOTH carry the sparse
        # transport column (the ISSUE 10 per-rule wire) AND the sync
        # column shows the full ISSUE 15 vocabulary in one committed
        # table: the replicated table defaults to stale(2) under the
        # staleness knob (row-sharded rows have one copy — they stay
        # "step"), and a user rule opts the bottom MLP into
        # periodic(4) local SGD
        model = DLRM(dense_dim=4, table_sizes=(512, 64), embed_dim=8,
                     shard_min_bytes=4096)
        mesh = Mesh(devs, ("data",))
        return model.param_tree(), derive_plan(
            model, mesh, sync_staleness=2,
            extra_rules=[Rule(r"^0/", P(), reason="user",
                              sync="periodic(4)")])

    cases["resnet50"] = resnet50
    cases["transformerlm"] = transformerlm
    cases["llama"] = llama
    cases["dlrm"] = dlrm
    return cases


@pytest.mark.parametrize("name", ["resnet50", "transformerlm", "llama",
                                  "dlrm"])
def test_golden_plan_tables(name):
    tree, plan = _golden_cases()[name]()
    table = plan.table(tree)
    path = os.path.join(FIXTURES, f"plan_{name}.json")
    if os.environ.get("BIGDL_REGEN_PLAN_GOLDENS"):
        with open(path, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
        pytest.skip(f"regenerated {path}")
    with open(path) as f:
        want = json.load(f)
    assert table == want


# ---------------------------------------------------------------------------
# composed-mesh equivalence: data=2 x pipe=2 x model=2 on 8 devices
# ---------------------------------------------------------------------------

class _LossLog:
    """Minimal train-summary: record the per-iteration loss stream."""

    def __init__(self):
        self.losses = []

    def add_scalar(self, name, value, step):
        if name == "Loss":
            self.losses.append(float(value))


def _lm_samples(v, t, n=16, seed=3):
    rng = np.random.RandomState(seed)
    seqs = rng.randint(1, v, (n, t + 1))
    return [Sample(s[:-1].astype(np.float32),
                   (s[1:] + 1).astype(np.float32)) for s in seqs]


def test_composed_2x2x2_matches_single_device_loss_trajectory():
    """data=2 x pipe=2 x model=2 composed on ONE mesh through the ONE
    builder; the loss trajectory matches the single-device dense run —
    the numeric contract the whole engine rests on."""
    from bigdl_tpu.models.transformer import TransformerLM

    V, T = 17, 8

    def build(model_axis):
        RNG().set_seed(6)
        return TransformerLM(V, embed_dim=8, num_heads=2, num_layers=2,
                             max_len=T, model_axis=model_axis)

    tp, dense = build("model"), build(None)
    for a, b in zip(jax.tree_util.tree_leaves(tp.param_tree()),
                    jax.tree_util.tree_leaves(dense.param_tree())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    crit = lambda: nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                               True)

    def drive(model, mesh, cls):
        RNG().set_seed(11)
        rec = _LossLog()
        kw = {"mesh": mesh} if mesh is not None else {}
        opt = cls(model, array(_lm_samples(V, T)), crit(), batch_size=8,
                  **kw)
        opt.set_optim_method(SGD(learning_rate=0.5))
        opt.set_end_when(max_iteration(6))
        opt.set_train_summary(rec)
        opt.optimize()
        return rec.losses

    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("data", "pipe", "model"))
    got = drive(tp, mesh, DistriOptimizer)
    want = drive(dense, None, LocalOptimizer)
    assert len(got) == len(want) == 6
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)
    # and the trajectory actually descends
    assert got[-1] < got[0]


# ---------------------------------------------------------------------------
# FSDP: params beyond one device's budget, measured ~1/N per device
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("steps", [3, 4])
def test_fsdp_trains_model_exceeding_one_device_budget(steps):
    from bigdl_tpu.telemetry import MetricsRegistry, Telemetry

    def build():
        RNG().set_seed(4)
        return nn.Sequential(nn.Linear(256, 512), nn.Tanh(),
                             nn.Linear(512, 512), nn.Tanh(),
                             nn.Linear(512, 2), nn.LogSoftMax())

    rng = np.random.RandomState(0)
    xs = rng.rand(64, 256).astype(np.float32)
    ys = (1 + (xs.sum(1) > 128)).astype(np.float32)
    samples = [Sample(x, y) for x, y in zip(xs, ys)]

    def drive(fsdp_min_bytes):
        model = build()
        tm = Telemetry(registry=MetricsRegistry())
        rec = _LossLog()
        opt = DistriOptimizer(model, array(samples),
                              nn.ClassNLLCriterion(), batch_size=64)
        opt.set_optim_method(SGD(learning_rate=0.1))
        opt.set_end_when(max_iteration(steps))
        opt.set_telemetry(tm)
        opt.set_train_summary(rec)
        # None: the replicated control (the 1 MiB default would shard
        # the 512 x 512 layer)
        opt.set_fsdp(fsdp_min_bytes)
        opt.optimize()
        assert rec.losses[-1] < rec.losses[0]
        snap = tm.registry.snapshot()["metrics"]
        per_dev = snap["bigdl_plan_param_bytes_per_device"]["series"][0][
            "value"]
        total = snap["bigdl_plan_param_bytes_total"]["series"][0]["value"]
        # the engagement gauge: master bytes updated on ONE data shard —
        # the two big weights when armed, nothing when replicated
        sharded = snap["bigdl_plan_update_sharded_bytes"]["series"][0][
            "value"]
        assert sharded == ((512 * 256 + 512 * 512) * 4
                           if fsdp_min_bytes else 0.0)
        return model, per_dev, total

    n = jax.device_count()
    assert n == 8
    model_fsdp, per_dev, total = drive(64 * 1024)
    # the full tree exceeds a pretend per-device budget of total/2;
    # FSDP brings the per-device footprint under it, at ~1/N
    budget = total / 2
    assert total > budget
    assert per_dev < budget
    assert per_dev == pytest.approx(total / n, rel=0.35)
    assert 0.10 <= per_dev / total <= 0.25

    # replicated control: every device holds the whole tree...
    model_dp, per_dev_dp, total_dp = drive(None)
    assert total_dp == total
    assert per_dev_dp == pytest.approx(total, rel=0.01)
    # ...and FSDP's math is plain data parallelism: same trained params
    for a, b in zip(jax.tree_util.tree_leaves(model_fsdp.param_tree()),
                    jax.tree_util.tree_leaves(model_dp.param_tree())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-6)


def test_fsdp_specs_shard_large_leaves_only():
    RNG().set_seed(4)
    model = nn.Sequential(nn.Linear(256, 512), nn.Tanh(),
                          nn.Linear(512, 2))
    mesh = Mesh(np.array(jax.devices()), ("data",))
    plan = derive_plan(model, mesh, fsdp_min_bytes=64 * 1024)
    table = plan.table(model.param_tree())
    assert "[fsdp]" in table["0/weight"]   # 512x256 f32 = 512 KiB
    assert "data" in table["0/weight"]
    assert table["0/bias"] == "replicated | dense | step"
    assert table["2/weight"] == "replicated | dense | step"  # 2x512 f32 = 4 KiB


# ---------------------------------------------------------------------------
# elastic shrink on a multi-axis mesh keeps the model axis
# ---------------------------------------------------------------------------

def test_survivor_mesh_template_keeps_non_data_axes():
    from bigdl_tpu.parallel.spmd import survivor_mesh

    tmpl = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("data", "model", "pipe"))
    m = survivor_mesh(1, template=tmpl)
    assert dict(m.shape) == {"data": 1, "model": 2, "pipe": 2}
    assert tuple(m.axis_names) == ("data", "model", "pipe")
    # no template: the historical data-only shape
    m2 = survivor_mesh(2)
    assert dict(m2.shape) == {"data": 2}
    with pytest.raises(ValueError):
        survivor_mesh(4, template=tmpl)  # 4*2*2 > 8 devices


def test_elastic_shrink_on_multi_axis_mesh_keeps_model_axis(tmp_path):
    """Chaos spec (8 forced-host devices): a 3-host gang training on a
    data x model template loses a host mid-run; the re-derived mesh
    shrinks the DATA axis only — tensor parallelism survives the
    shrink (the old shrink silently rebuilt data-only)."""
    from bigdl_tpu.optim import several_iteration
    from bigdl_tpu.parallel.tensor_parallel import (ColumnParallelLinear,
                                                    RowParallelLinear)
    from bigdl_tpu.resilience import faults
    from bigdl_tpu.resilience import (CollectiveWatchdog,
                                              ElasticContext,
                                              ElasticCoordinator,
                                              InMemoryKV, RetryPolicy,
                                              SimulatedHost,
                                              StepTimeEstimator)

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

    RNG().set_seed(7)
    model = nn.Sequential(ColumnParallelLinear(4, 8, axis_name="model"),
                          nn.Tanh(),
                          RowParallelLinear(8, 1, axis_name="model"))
    rng = np.random.RandomState(0)
    xs = rng.rand(120, 4).astype(np.float32)
    ys = (xs @ np.array([[1.5], [-2.0], [0.5], [3.0]], np.float32)
          + 0.7).astype(np.float32)
    samples = [Sample(x, y) for x, y in zip(xs, ys)]

    rec = _LossLog()
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    opt = DistriOptimizer(model, array(samples), nn.MSECriterion(),
                          batch_size=12, mesh=mesh)
    opt.set_optim_method(SGD(learning_rate=0.2))
    opt.set_end_when(max_iteration(14))
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(1))
    opt.set_retry_policy(RetryPolicy(max_retries=10, backoff_base=0.01,
                                     backoff_max=0.05))
    opt.set_elastic(ctx)
    opt.set_train_summary(rec)

    with faults.delay_host("host0", 0.05, at_step=1):
        for s in sims:
            s.start()
        try:
            opt.optimize()
        finally:
            for s in sims:
                s.stop()

    assert opt.optim_method.state["neval"] - 1 == 14, "run must complete"
    c = ctx.counters()
    assert c["incarnation_changes"] >= 1, c
    # EVERY derived mesh keeps the template's model axis; the shrink
    # shows up as a smaller data axis only
    assert len(meshes) >= 2
    for m in meshes:
        assert m.shape["model"] == 2, dict(m.shape)
    assert meshes[0].shape["data"] == 3
    assert meshes[-1].shape["data"] == 2, dict(meshes[-1].shape)
    # loss keeps descending across the shrink boundary
    assert rec.losses[-1] < rec.losses[0]


# ---------------------------------------------------------------------------
# routing sanity
# ---------------------------------------------------------------------------

def test_normalize_mesh_drops_size_one_axes():
    devs = np.array(jax.devices())
    m = normalize_mesh(Mesh(devs.reshape(8, 1, 1, 1),
                            ("data", "model", "seq", "pipe")))
    assert tuple(m.axis_names) == ("data",) and m.shape["data"] == 8
    m2 = normalize_mesh(Mesh(devs.reshape(2, 4), ("data", "model")))
    assert tuple(m2.axis_names) == ("data", "model")
    m3 = normalize_mesh(Mesh(devs[:1].reshape(1, 1), ("data", "pipe")))
    assert tuple(m3.axis_names) == ("data",) and m3.shape["data"] == 1


def test_seq_pipe_mesh_rejected():
    devs = np.array(jax.devices())
    opt = DistriOptimizer(
        nn.Sequential(nn.Linear(4, 4)), array(
            [Sample(np.zeros(4, np.float32), 1.0)] * 8),
        nn.MSECriterion(), batch_size=8,
        mesh=Mesh(devs.reshape(2, 2, 2), ("data", "seq", "pipe")))
    opt.set_end_when(max_iteration(1))
    with pytest.raises(ValueError, match="seq"):
        opt.optimize()


# ---------------------------------------------------------------------------
# the data-parallel step updates each parameter on ONE shard (ISSUE 44):
# by default, on a data axis of more than one device, a dense lockstep
# leaf of 1 MiB or more lives on its data shard — master, slots, reduced
# gradient, update — and only its compute-dtype copy is ever whole
# ---------------------------------------------------------------------------

def _big_mlp():
    """0/weight [1024, 512] and 2/weight [512, 1024] are 2 MiB of f32
    each (over the 1 MiB default), every other leaf is small."""
    RNG().set_seed(4)
    return nn.Sequential(nn.Linear(512, 1024), nn.Tanh(),
                         nn.Linear(1024, 512), nn.Tanh(),
                         nn.Linear(512, 4), nn.LogSoftMax())


def _big_lookup():
    """0/weight [40000, 8] f32 = 1.28 MB: a table over the threshold."""
    RNG().set_seed(2)
    return nn.Sequential(nn.LookupTable(40000, 8), nn.Sum(dimension=2),
                         nn.Linear(8, 4), nn.LogSoftMax())


def _data_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("data",))


def _mlp_batch(n=16, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.rand(n, 512).astype(np.float32),
            (1 + rng.randint(0, 4, n)).astype(np.float32))


def _assert_trees(got, want, **tol):
    """Leaf by leaf: equal, or close where a tolerance is given."""
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        if tol:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _lowered_collectives(eng, x, y):
    """[(op, operand dims, operand dtype)] of the lowered step's
    collectives, from the StableHLO text."""
    import re

    x, y = jnp.asarray(x), jnp.asarray(y)
    args = eng.init_state() + (np.float32(0.1), jax.random.PRNGKey(0), x, y)
    if eng.has_relaxed:
        args += (jnp.zeros((eng.n_flags,), jnp.int32),
                 eng.init_sync_state())
    text = eng.jitted_for(x, y, False).lower(*args).as_text()
    out = []
    for op in ("all_gather", "reduce_scatter", "all_reduce"):
        # an op with a reduction body carries its type after the body
        for m in re.finditer(r'"stablehlo\.%s"\(.*?: \(tensor<([^>]*)>\) -> '
                             % op, text, re.S):
            dims = m.group(1).split("x")
            out.append((op, tuple(int(d) for d in dims[:-1]), dims[-1]))
    return out


@pytest.mark.parametrize("shape,n,want", [
    # the minor dim where its shard is whole 128-lane tiles ...
    ((1024, 4096), 4, P(None, "data")),
    ((4096, 1024), 4, P(None, "data")),
    ((50257, 1024), 4, P(None, "data")),
    # ... else the largest dim that divides (the first of equals)
    ((4096, 1000), 4, P("data", None)),
    ((3, 3, 512, 512), 8, P(None, None, "data", None)),
    ((50257, 1022), 4, P()),            # nothing divides: replicated
])
def test_threshold_rule_reads_the_dim_off_the_shapes(shape, n, want):
    plan = Plan([Rule(".*", P())], mesh=_data_mesh(n),
                fsdp_min_bytes=1 << 20)
    got = plan.param_specs({"w": jax.ShapeDtypeStruct(shape, jnp.float32)})
    assert got["w"] == want


_SHARD_CASES = {
    # name: (model, n devices, plan or None, engine kwargs,
    #        {leaf: spec string it must carry},
    #        the lowered collectives over whole / shard of big leaves)
    "default": (
        _big_mlp, 4, None, {},
        {"0/weight": "(-, data)", "2/weight": "(-, data)",
         "4/weight": "replicated", "0/bias": "replicated"},
        [("all_gather", (512, 256), "bf16"),
         ("all_gather", (1024, 128), "bf16"),
         ("reduce_scatter", (512, 1024), "f32"),
         ("reduce_scatter", (1024, 512), "f32")]),
    "off": (
        _big_mlp, 4, None, {"fsdp_min_bytes": None},
        {"0/weight": "replicated", "2/weight": "replicated"},
        [("all_reduce", (512, 1024), "f32"),
         ("all_reduce", (1024, 512), "f32")]),
    "under_threshold": (
        _big_mlp, 4, None, {"fsdp_min_bytes": 4 << 20},
        {"0/weight": "replicated", "2/weight": "replicated"},
        [("all_reduce", (512, 1024), "f32"),
         ("all_reduce", (1024, 512), "f32")]),
    "one_device": (
        _big_mlp, 1, None, {},
        {"0/weight": "replicated", "2/weight": "replicated"}, []),
    "sparse_table": (
        _big_lookup, 4,
        lambda: Plan([Rule(r"^0/weight$", P(), transport="sparse"),
                      Rule(".*", P())], fsdp_min_bytes=1 << 20), {},
        {"0/weight": "replicated"}, None),
    "periodic": (
        _big_mlp, 4,
        lambda: Plan([Rule(r"^0/", P(), sync="periodic(4)"),
                      Rule(".*", P())], fsdp_min_bytes=1 << 20), {},
        {"0/weight": "replicated", "2/weight": "(-, data)"},
        [("all_gather", (512, 256), "bf16"),
         # the averaging round's pmean of the replica stack, in its cond
         ("all_reduce", (1, 1024, 512), "f32"),
         ("reduce_scatter", (512, 1024), "f32")]),
    # the explicitly armed rule takes the same path: compute-dtype
    # gather, MASTER-dtype scatter (never the gather's bf16 transpose)
    "armed_rule": (
        _big_mlp, 4,
        lambda: Plan([Rule(r"^0/weight$", P(None, "data"), fsdp=True),
                      Rule(".*", P())]), {},
        {"0/weight": "(-, data)", "2/weight": "replicated"},
        [("all_gather", (1024, 128), "bf16"),
         ("all_reduce", (512, 1024), "f32"),
         ("reduce_scatter", (1024, 512), "f32")]),
}


@pytest.mark.parametrize("case", sorted(_SHARD_CASES))
def test_default_layout_updates_each_large_leaf_on_one_shard(case):
    build, n, plan, kw, specs, want = _SHARD_CASES[case]
    model = build()
    if build is _big_lookup:
        rng = np.random.RandomState(0)
        x = (1 + rng.randint(0, 50, (16, 3))).astype(np.float32)
        y = (1 + rng.randint(0, 4, 16)).astype(np.float32)
    else:
        x, y = _mlp_batch()
    eng = compile_step_with_plan(
        model, nn.ClassNLLCriterion(), SGD(), _data_mesh(n),
        plan=plan() if plan else None, compute_dtype=jnp.bfloat16, **kw)
    table = eng.plan.table(model.param_tree())
    for leaf, spec in specs.items():
        assert table[leaf].split(" | ")[0] == spec, (leaf, table[leaf])
        assert ("[fsdp]" in table[leaf]) == ("data" in spec)
    p, s, _ = eng.init_state()
    for leaf, spec in specs.items():
        if "data" not in spec:
            continue
        arr = dict(named_leaves(p))[leaf]
        dim = spec.strip("()").split(", ").index("data")
        assert arr.sharding.spec[dim] == "data"
        assert arr.addressable_shards[0].data.shape[dim] \
            == arr.shape[dim] // n
    colls = _lowered_collectives(eng, x, y)
    big = sorted(c for c in colls if int(np.prod(c[1] or (1,))) >= 1 << 17)
    if want is None:
        # the sparse table keeps its replica and its index+value wire
        # (K = 40000 / 16 rows a shard): nothing gathers or scatters a
        # whole table
        assert sorted(c for c in colls if c[0] != "all_reduce") == [
            ("all_gather", (2500,), "i32"),
            ("all_gather", (2500, 8), "f32")], colls
        assert eng.update_sharded_bytes == 0.0
        return
    assert big == sorted(want), colls
    # (d) the accounting reads what was lowered: a gather's operand is
    # one shard (the wire carries the n - 1 others), a scatter's the
    # whole cotangent (the wire carries (n - 1) / n of it)
    nbytes = lambda c: float(np.prod(c[1])) * (2 if c[2] == "bf16" else 4)
    wire = sum(nbytes(c) * (n - 1 if c[0] == "all_gather" else (n - 1) / n)
               for c in big if c[0] != "all_reduce")
    leaves = named_leaves(model.param_tree())
    assert eng.update_sharded_bytes == sum(
        l.size * 4 for name, l in leaves if "[fsdp]" in table[name])
    ring = 2.0 * (n - 1) / n
    assert eng.collective_bytes == pytest.approx(wire + ring * sum(
        l.size * 4 / (4 if "periodic" in table[name] else 1)
        for name, l in leaves if "[fsdp]" not in table[name]))


@pytest.mark.parametrize("case", ["plain", "masked", "nan"])
def test_sharded_update_is_the_replicated_update(case):
    """Three Adam steps, bf16 compute, on a data mesh of 4: the default
    (sharded) layout against ``fsdp_min_bytes=None`` from the same seed.
    The same local cotangent, upcast, summed over chips in f32, through
    the same elementwise Adam: equal to the order of an f32 sum — on a
    full batch, on a trailing batch whose last rows are padding, and on
    a step whose gradient holds a NaN (every shard skips it)."""
    from bigdl_tpu.optim import Adam

    x, y = _mlp_batch()
    w = total_w = None
    if case == "masked":
        w = np.array([1.0] * 10 + [0.0] * 6, np.float32)
        total_w = 10.0
    runs = {}
    for name, kw in (("sharded", {}), ("replicated",
                                       {"fsdp_min_bytes": None})):
        model = _big_mlp()
        eng = compile_step_with_plan(
            model, nn.ClassNLLCriterion(), Adam(1e-3), _data_mesh(4),
            compute_dtype=jnp.bfloat16, **kw)
        assert eng.has_fsdp == (name == "sharded")
        p, s, b = eng.init_state()
        first = jax.device_get(p)
        log = []
        for i in range(3):
            xi = x.copy()
            poisoned = case == "nan" and i == 1
            if poisoned:
                xi[5, 7] = np.nan
                before = jax.device_get((p, s))
            loss, p, s, b, ok, gn = eng.step(p, s, b, 1e-3, xi, y, w=w,
                                             total_w=total_w)
            log.append((float(loss), float(gn), bool(ok)))
            if poisoned:
                # the skip left every shard of every leaf as it was
                assert not bool(ok)
                _assert_trees((p, s), before)
        runs[name] = (log, jax.device_get((p, s)))
        assert any(np.any(a != b_) for a, b_ in zip(
            jax.tree_util.tree_leaves(runs[name][1][0]),
            jax.tree_util.tree_leaves(first)))  # it trained
        eng.sync_to_model(p, s, b)
        # whole trees come back on the host, sharded or not
        _assert_trees(model.param_tree(), runs[name][1][0])
    (log_s, state_s), (log_r, state_r) = runs["sharded"], runs["replicated"]
    for (l1, g1, ok1), (l2, g2, ok2) in zip(log_s, log_r):
        assert ok1 == ok2
        if ok1:
            assert l1 == pytest.approx(l2, rel=1e-6)
            # sums of 0.5 M squares, in 4 parts or in 1
            assert g1 == pytest.approx(g2, rel=5e-4)
    _assert_trees(state_s, state_r, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("n_after", [2, 1])
def test_state_sharded_over_four_rebinds_to_a_smaller_mesh(n_after):
    """What an elastic shrink does each attempt (``_plan_loop`` on the
    live mesh): a new engine for the survivors' mesh takes its state
    from the host trees the four-way-sharded run wrote back.  Parameters
    and both Adam moments arrive identical, laid out for the new mesh,
    and the next step is the replicated twin's."""
    from bigdl_tpu.optim import Adam

    x, y = _mlp_batch()
    model, optim = _big_mlp(), Adam(1e-3)
    crit = nn.ClassNLLCriterion()
    eng4 = compile_step_with_plan(model, crit, optim, _data_mesh(4))
    p, s, b = eng4.init_state()
    for _ in range(2):
        _, p, s, b, _, _ = eng4.step(p, s, b, 1e-3, x, y)
    want = jax.device_get((p, s))
    eng4.sync_to_model(p, s, b)

    eng = compile_step_with_plan(model, crit, optim, _data_mesh(n_after))
    assert eng.has_fsdp == (n_after > 1)
    p2, s2, b2 = eng.init_state()
    _assert_trees((p2, s2), want)
    big = dict(named_leaves(p2))["0/weight"]
    assert big.addressable_shards[0].data.shape == (1024, 512 // n_after)
    m = dict(named_leaves(s2))["m/0/weight"]
    assert m.addressable_shards[0].data.shape == (1024, 512 // n_after)

    twin = compile_step_with_plan(model, crit, optim, _data_mesh(4),
                                  fsdp_min_bytes=None)
    loss, p2, s2, b2, _, _ = eng.step(p2, s2, b2, 1e-3, x, y)
    loss_t, pt, st, _, _, _ = twin.step(*twin.init_state(), 1e-3, x, y)
    assert float(loss) == pytest.approx(float(loss_t), rel=1e-6)
    _assert_trees((p2, s2), (pt, st), rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("fmt", ["pickle", "orbax"])
@pytest.mark.parametrize("written,read", [(1 << 20, None), (None, 1 << 20)])
def test_checkpoint_crosses_the_sharded_and_replicated_layouts(
        tmp_path, written, read, fmt):
    """A checkpoint holds whole trees on the host: one written by the
    sharded layout resumes replicated, and the other way round, onto the
    trajectory of a run that was never interrupted."""
    from bigdl_tpu.optim import Adam, several_iteration

    xs, ys = _mlp_batch(64, seed=1)
    samples = [Sample(a, b) for a, b in zip(xs, ys)]

    def build(fsdp):
        opt = DistriOptimizer(_big_mlp(), array(samples),
                              nn.ClassNLLCriterion(), batch_size=16,
                              mesh=_data_mesh(4))
        opt.set_optim_method(Adam(1e-3))
        opt.set_fsdp(fsdp)
        return opt

    whole = build(read)
    whole.set_end_when(max_iteration(5))
    whole.optimize()

    first = build(written)
    first.set_end_when(max_iteration(3))
    first.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(1),
                         format=fmt)
    first.optimize()

    second = build(read)
    second.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(1),
                          format=fmt)
    assert second.resume_from_checkpoint() is True
    second.set_end_when(max_iteration(5))
    second.optimize()
    assert second.optim_method.state["neval"] - 1 == 5
    _assert_trees((second.model.param_tree(), second.optim_method._slots),
                  (whole.model.param_tree(), whole.optim_method._slots),
                  rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# the threshold's one default, and what happens where it cannot apply
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("value,want", [
    (None, 1 << 20), ("", None), ("0", None), ("4096", 4096)])
def test_fsdp_threshold_property_and_default(monkeypatch, value, want):
    """``bigdl.fsdp.minBytes``: unset is the 1 MiB default, empty or 0
    replicates, a number is the threshold — and ``set_fsdp()``,
    ``derive_plan`` and ``compile_step_with_plan`` default to the same
    constant."""
    import inspect

    from bigdl_tpu.parallel.plan import FSDP_MIN_BYTES

    assert FSDP_MIN_BYTES == 1 << 20
    for fn, arg in ((DistriOptimizer.set_fsdp, "min_bytes"),
                    (derive_plan, "fsdp_min_bytes"),
                    (compile_step_with_plan, "fsdp_min_bytes")):
        assert inspect.signature(fn).parameters[arg].default \
            is FSDP_MIN_BYTES
    monkeypatch.delenv("BIGDL_FSDP_MINBYTES", raising=False)
    if value is not None:
        monkeypatch.setenv("BIGDL_FSDP_MINBYTES", value)
    opt = DistriOptimizer(nn.Linear(4, 2), array([Sample(
        np.zeros(4, np.float32), np.float32(1))]), nn.MSECriterion(),
        batch_size=1)
    assert opt.fsdp_min_bytes == want
    assert opt.set_fsdp().fsdp_min_bytes == FSDP_MIN_BYTES


def test_pipeline_layout_says_that_it_keeps_the_replicated_update(caplog):
    """A threshold on a data x pipe mesh is not applied (the packed
    stack is stage-sharded, never gathered on use): the derived plan
    says so in the log instead of dropping it silently."""
    from bigdl_tpu.models.transformer import TransformerLM

    RNG().set_seed(3)
    model = TransformerLM(17, embed_dim=8, num_heads=2, num_layers=2,
                          max_len=8)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "pipe"))
    with caplog.at_level(logging.INFO, logger="bigdl_tpu"):
        plan = derive_plan(model, mesh, pipe_axis="pipe", n_pipe=2,
                           fsdp_min_bytes=64)
    assert plan.fsdp_min_bytes is None
    assert "pipeline layout keeps the replicated update" in caplog.text
    assert "64 bytes" in caplog.text


def test_armed_rule_on_one_device_reads_no_sharded_update():
    """``Rule(fsdp=True)`` on a one-device data axis compiles the
    unsharded program, and the engagement gauge reads what was
    compiled: 0."""
    RNG().set_seed(5)
    model = nn.Sequential(nn.Linear(64, 64), nn.Tanh(), nn.Linear(64, 2))
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    plan = Plan([Rule(r".*weight$", P("data", None), fsdp=True),
                 Rule(".*", P())])
    eng = compile_step_with_plan(model, nn.MSECriterion(),
                                 SGD(learning_rate=0.1), mesh, plan=plan)
    assert not eng.has_fsdp
    assert eng.update_sharded_bytes == 0.0
