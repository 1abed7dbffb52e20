"""DistriOptimizer specs on the 8-virtual-device CPU mesh — the analogue
of the reference's Spark local-mode distributed tests
(optim/DistriOptimizerSpec.scala:32-60, SURVEY §4.3): tiny MLPs trained
through the FULL reduce-scatter → slice-update → all-gather path.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import Sample, array
from bigdl_tpu.optim import SGD, Adam, Top1Accuracy, max_epoch, max_iteration
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel.all_reduce import AllReduceParameter
from bigdl_tpu.utils.engine import Engine


def xor_samples(n=256, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 2).astype(np.float32)
    y = ((x[:, 0] > 0.5) ^ (x[:, 1] > 0.5)).astype(np.float32) + 1
    return [Sample(x[i], y[i]) for i in range(n)]


def xor_model():
    return nn.Sequential(nn.Linear(2, 32), nn.Tanh(), nn.Linear(32, 2),
                         nn.LogSoftMax())


def test_eight_devices_present():
    assert jax.device_count() == 8


def test_distri_sgd_converges():
    Engine.init()
    ds = array(xor_samples())
    model = xor_model()
    opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=1.0))
    opt.set_end_when(max_epoch(150))
    trained = opt.optimize()
    res = trained.evaluate(array(xor_samples(seed=1)), [Top1Accuracy()])
    acc = res[0][0].result()[0]
    assert acc > 0.9, f"distributed XOR accuracy {acc}"


def test_distri_matches_local_single_step():
    """Sharded update must equal the unsharded update (the reference
    checks DistriOptimizer against RefDistriOptimizer — SURVEY §4.4)."""
    from bigdl_tpu.optim import LocalOptimizer

    samples = xor_samples(n=64, seed=5)

    from bigdl_tpu.utils.rng import RNG

    RNG().set_seed(7)
    m1 = xor_model()
    RNG().set_seed(7)
    m2 = xor_model()
    w1, _ = m1.get_parameters()
    w2, _ = m2.get_parameters()
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2))

    ds1 = array(samples)
    lo = LocalOptimizer(m1, ds1, nn.ClassNLLCriterion(), batch_size=64)
    lo.set_optim_method(SGD(learning_rate=0.1))
    lo.set_end_when(max_iteration(3))
    lo.optimize()

    ds2 = array(samples)
    do = DistriOptimizer(m2, ds2, nn.ClassNLLCriterion(), batch_size=64)
    do.set_optim_method(SGD(learning_rate=0.1))
    do.set_end_when(max_iteration(3))
    do.optimize()

    w1, _ = m1.get_parameters()
    w2, _ = m2.get_parameters()
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=2e-4)


def test_distri_adam_with_sharded_state():
    """Adam slots live sharded per slice (ZeRO-1); must still converge."""
    ds = array(xor_samples())
    model = xor_model()
    opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(Adam(learning_rate=0.05))
    opt.set_end_when(max_epoch(15))
    trained = opt.optimize()
    res = trained.evaluate(array(xor_samples(seed=2)), [Top1Accuracy()])
    assert res[0][0].result()[0] > 0.85


def test_allreduce_parameter_semantics():
    """Codec/slicing parity unit (reference FP16ParameterSpec — SURVEY §4.6):
    reduce-scatter of per-shard grads + all-gather reproduces psum."""
    from functools import partial

    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("data",))
    params = {"w": jnp.arange(10, dtype=jnp.float32)}
    arp = AllReduceParameter(params, 8, compress="none")

    grads_global = np.random.RandomState(0).rand(8, 10).astype(np.float32)

    def f(g):
        gslice = arp.reduce_scatter_gradients({"w": g[0]})
        full = jax.lax.all_gather(gslice, "data", tiled=True)
        return full[None]

    out = shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"))(
        jnp.asarray(grads_global))
    got = np.asarray(out)[0][:10]
    np.testing.assert_allclose(got, grads_global.sum(0), rtol=1e-5)


def test_bf16_compression_close():
    """bf16 wire format ≈ fp32 within bf16 tolerance (reference fp16
    codec round-trip spec)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("data",))
    params = {"w": jnp.zeros(16)}
    arp = AllReduceParameter(params, 8, compress="bf16")
    grads_global = np.random.RandomState(1).randn(8, 16).astype(np.float32)

    def f(g):
        gslice = arp.reduce_scatter_gradients({"w": g[0]})
        return jax.lax.all_gather(gslice, "data", tiled=True)[None]

    out = np.asarray(shard_map(f, mesh=mesh, in_specs=P("data"),
                               out_specs=P("data"))(jnp.asarray(grads_global)))[0][:16]
    np.testing.assert_allclose(out, grads_global.sum(0), rtol=0.05, atol=0.05)


def test_checkpoint_retry_recovers(tmp_path):
    """Fault-injection: the driver retry loop reloads the latest
    checkpoint and resumes (reference ExceptionTest module driving
    DistriOptimizer.scala:750-816, SURVEY §4.5).  The failure is injected
    at the data plane — under XLA a module can only throw at trace time,
    so the host-visible fault surface is the input pipeline."""
    from bigdl_tpu.dataset import SampleToMiniBatch

    from bigdl_tpu.resilience.faults import ExceptionTransformer

    fault = ExceptionTransformer(fail_at=200)
    ds = array(xor_samples()) >> fault >> SampleToMiniBatch(64)
    model = xor_model()
    opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion())
    opt.set_optim_method(SGD(learning_rate=0.3))
    opt.set_end_when(max_iteration(10))
    from bigdl_tpu.optim import several_iteration

    opt.set_checkpoint(str(tmp_path), several_iteration(1))
    trained = opt.optimize()  # must ride through the injected failure
    assert fault.fired, "the injected fault never triggered"
    assert trained is model
    assert opt.optim_method.state["neval"] > 10


@pytest.mark.slow  # ~13s epoch sweep; the pad-and-mask contract
# stays budgeted via test_distri_multi_axis
# ::test_partial_batch_trains_on_three_axis_mesh
def test_partial_batches_train_all_records():
    """Dataset size % (batch, mesh) != 0: every record still trains
    (pad-and-mask), and the weights move under the trailing batch
    (reference trains every record per epoch, DataSet.scala:255-288)."""
    from bigdl_tpu.dataset import SampleToMiniBatch

    n = 70  # batch 64 -> trailing batch of 6, and 6 % 8 != 0
    ds = array(xor_samples(n=n))
    model = xor_model()
    opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_end_when(max_epoch(200))
    trained = opt.optimize()
    # 2 iterations per epoch: the trailing 6-record batch was trained,
    # not skipped
    assert opt.optim_method.state["neval"] - 1 == 2 * 200
    # fit on the training records themselves: proves the trailing batch
    # contributed gradients (70 samples are too few to test generalization)
    res = trained.evaluate(array(xor_samples(n=n)), [Top1Accuracy()])
    assert res[0][0].result()[0] > 0.85


def test_masked_trailing_batch_matches_full_gradient():
    """The masked step's update on a padded batch must equal the plain
    step's update on the same records run at an exactly-divisible size."""
    samples = xor_samples(n=8, seed=11)

    from bigdl_tpu.utils.rng import RNG

    RNG().set_seed(3)
    m1 = xor_model()
    RNG().set_seed(3)
    m2 = xor_model()

    # divisible path: all 8 records in one batch of 8
    o1 = DistriOptimizer(m1, array(samples), nn.ClassNLLCriterion(),
                         batch_size=8)
    o1.set_optim_method(SGD(learning_rate=0.1))
    o1.set_end_when(max_iteration(1))
    o1.optimize()

    # masked path: batch_size 16 -> single partial batch of 8? no — use
    # n=8 with batch 16 gives one batch of 8 (divisible). Force masking
    # with a 6-record tail: train 1 iteration on a 6-record dataset,
    # batch 16 -> batch of 6, 6 % 8 != 0 -> masked step.
    samples6 = samples[:6]
    RNG().set_seed(3)
    m3 = xor_model()
    RNG().set_seed(3)
    m4 = xor_model()
    o3 = DistriOptimizer(m3, array(samples6 + samples6[:2]),
                         nn.ClassNLLCriterion(), batch_size=8)
    o3.set_optim_method(SGD(learning_rate=0.1))
    o3.set_end_when(max_iteration(1))
    o3.optimize()  # 8 records divisible — reference update

    o4 = DistriOptimizer(m4, array(samples6), nn.ClassNLLCriterion(),
                         batch_size=8)
    o4.set_optim_method(SGD(learning_rate=0.1))
    o4.set_end_when(max_iteration(1))
    o4.optimize()  # 6 records -> padded to 8, masked

    # the masked 6-record mean gradient differs from the 8-record one,
    # but both must be finite and the masked one must not include the
    # padded rows: compare against a LocalOptimizer on the same 6
    from bigdl_tpu.optim import LocalOptimizer

    RNG().set_seed(3)
    m5 = xor_model()
    lo = LocalOptimizer(m5, array(samples6), nn.ClassNLLCriterion(),
                        batch_size=8)
    lo.set_optim_method(SGD(learning_rate=0.1))
    lo.set_end_when(max_iteration(1))
    lo.optimize()

    w4, _ = m4.get_parameters()
    w5, _ = m5.get_parameters()
    np.testing.assert_allclose(np.asarray(w4), np.asarray(w5), atol=2e-4)


def test_validation_runs_on_mesh_and_metrics_are_real():
    """The validation trigger must run a compiled sharded eval (no host
    param pull) and the Metrics phase breakdown must be measured, not
    hardcoded zero (reference Metrics.scala:103-121)."""
    import bigdl_tpu.optim.evaluator as ev
    from bigdl_tpu.optim import several_iteration

    ds = array(xor_samples(n=128))
    model = xor_model()
    opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_end_when(max_iteration(25))
    # validation dataset of 100 -> one 100-record eval batch, 100 % 8 != 0
    # -> exercises eval-side pad too
    opt.set_validation(several_iteration(10), array(xor_samples(n=100, seed=4)),
                       [Top1Accuracy()], batch_size=100)
    ev.last_eval_info.update({"sharded": False, "n_devices": 1})
    opt.optimize()
    assert ev.last_eval_info["sharded"] is True
    assert ev.last_eval_info["n_devices"] == 8
    summary = opt.metrics.summary()
    agg = opt.metrics.get("aggregate gradient time")
    # profiled at iterations 11 and 21 -> a real (non-zero) split exists
    assert agg is not None and agg > 0.0, summary
    # VERDICT r2 #6: the split must come from a jax.profiler trace of the
    # step's own execution (collective vs compute device events), with
    # the collective-free probe only as fallback
    assert opt.phase_source == "trace", opt.phase_source


def test_trace_phase_split_classifies_collectives():
    """Unit: the xplane classifier separates psum/rendezvous events from
    compute on the 8-device CPU backend."""
    import jax.numpy as jnp
    from jax import lax

    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from bigdl_tpu.optim.profiling import trace_phase_split

    mesh = Mesh(np.array(jax.devices()), ("data",))

    def step(x, w):
        return lax.psum(x @ w, "data")

    f = jax.jit(shard_map(step, mesh=mesh, in_specs=(P("data"), P()),
                          out_specs=P()))
    x = jnp.ones((8, 256, 256))
    w = jnp.ones((256, 256))
    jax.block_until_ready(f(x, w))  # compile outside the trace
    split = trace_phase_split(lambda: jax.block_until_ready(f(x, w)))
    assert split is not None
    compute_s, collective_s = split
    assert compute_s > 0.0 and collective_s > 0.0


def test_trace_phase_split_propagates_run_errors():
    """Training errors must escape the profiler wrapper — the driver's
    checkpoint-retry loop depends on them (DistriOptimizer.scala:750)."""
    from bigdl_tpu.optim.profiling import trace_phase_split

    class Boom(RuntimeError):
        pass

    def run():
        raise Boom("training failure")

    with pytest.raises(Boom):
        trace_phase_split(run)


def test_pytree_table_targets_pad_and_mask():
    """VERDICT r2 #7: multi-output/table-criterion models keep the
    every-record guarantee — a two-target model with a trailing partial
    batch (6 % 8 != 0) trains through the masked step, and matches a
    LocalOptimizer run on the same records."""
    from bigdl_tpu.dataset import array
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.optim import LocalOptimizer
    from bigdl_tpu.utils.rng import RNG
    from bigdl_tpu.utils.table import T

    rng = np.random.RandomState(5)

    def two_target_batches(n_full, tail):
        """Full batches of 8 plus one trailing batch of ``tail``."""
        batches = []
        for size in [8] * n_full + [tail]:
            x = rng.rand(size, 2).astype(np.float32)
            cls = ((x[:, 0] > 0.5) ^ (x[:, 1] > 0.5)).astype(np.float32) + 1
            reg = (x.sum(axis=1, keepdims=True) * 0.5).astype(np.float32)
            batches.append(MiniBatch(x, T(jnp.asarray(cls), jnp.asarray(reg))))
        return batches

    def two_head_model():
        return nn.ConcatTable(
            nn.Sequential(nn.Linear(2, 8), nn.Tanh(), nn.Linear(8, 2),
                          nn.LogSoftMax()),
            nn.Linear(2, 1))

    def two_head_criterion():
        return (nn.ParallelCriterion()
                .add(nn.ClassNLLCriterion(), 1.0)
                .add(nn.MSECriterion(), 0.5))

    rng = np.random.RandomState(5)
    batches = two_target_batches(2, 6)

    RNG().set_seed(9)
    m_dist = two_head_model()
    opt = DistriOptimizer(m_dist, array(batches), two_head_criterion())
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_iteration(3))
    opt.optimize()
    # all 3 batches trained, including the masked trailing 6-record one
    assert opt.optim_method.state["neval"] - 1 == 3

    rng = np.random.RandomState(5)
    batches = two_target_batches(2, 6)
    RNG().set_seed(9)
    m_local = two_head_model()
    lo = LocalOptimizer(m_local, array(batches), two_head_criterion())
    lo.set_optim_method(SGD(learning_rate=0.1))
    lo.set_end_when(max_iteration(3))
    lo.optimize()

    w_d, _ = m_dist.get_parameters()
    w_l, _ = m_local.get_parameters()
    # 5e-4: psum_scatter vs local-sum f32 accumulation order over 3 steps
    np.testing.assert_allclose(np.asarray(w_d), np.asarray(w_l), atol=5e-4)


# ---------------------------------------------------------------------------
# the order of a step (ISSUE 35), on the 8-device data mesh: the same cases
# as tests/test_local_optimizer.py, against the parent commit's account
# ---------------------------------------------------------------------------

import driver_order_scenarios as order  # noqa: E402


@pytest.fixture(scope="module")
def distri_order(tmp_path_factory):
    return (order.scenarios("distri", str(tmp_path_factory.mktemp("order"))),
            order.fixture()["distri"])


@pytest.mark.parametrize("case", order.CASES)
def test_distri_driver_order(distri_order, case):
    getattr(order, "check_" + case)(*distri_order)
