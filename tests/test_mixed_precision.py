"""Mixed-precision + buffer-donation driver specs (VERDICT r2 next #2).

The reference's precision knob is the fp16 wire codec
(parameters/FP16CompressedTensor.scala:26); on TPU the knob moves from
the wire to the MXU: ``set_compute_dtype(bf16)`` runs forward/backward
in bf16 against f32 master weights.  Donation is the HBM half of the
same fix: the jitted step updates parameters in place.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import Sample, array
from bigdl_tpu.optim import SGD, Adam, LocalOptimizer, Top1Accuracy, \
    max_epoch, max_iteration
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.utils.engine import Engine


def xor_samples(n=256, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 2).astype(np.float32)
    y = ((x[:, 0] > 0.5) ^ (x[:, 1] > 0.5)).astype(np.float32) + 1
    return [Sample(x[i], y[i]) for i in range(n)]


def xor_model():
    return nn.Sequential(nn.Linear(2, 32), nn.Tanh(), nn.Linear(32, 2),
                         nn.LogSoftMax())


def test_local_bf16_converges_with_f32_master_weights():
    ds = array(xor_samples())
    model = xor_model()
    opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=32)
    opt.set_optim_method(SGD(learning_rate=1.0))
    opt.set_compute_dtype(jnp.bfloat16)
    opt.set_end_when(max_epoch(150))
    trained = opt.optimize()

    # master weights stayed f32 end to end
    for leaf in jax.tree_util.tree_leaves(trained.param_tree()):
        assert leaf.dtype == jnp.float32
    res = trained.evaluate(array(xor_samples(seed=1)), [Top1Accuracy()])
    acc = res[0][0].result()[0]
    assert acc > 0.9, f"bf16 XOR accuracy {acc}"


@pytest.mark.slow
def test_distri_bf16_converges():
    Engine.init()
    ds = array(xor_samples())
    model = xor_model()
    opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=1.0))
    opt.set_compute_dtype(jnp.bfloat16)
    opt.set_end_when(max_epoch(120))
    trained = opt.optimize()
    for leaf in jax.tree_util.tree_leaves(trained.param_tree()):
        assert leaf.dtype == jnp.float32
    res = trained.evaluate(array(xor_samples(seed=1)), [Top1Accuracy()])
    acc = res[0][0].result()[0]
    assert acc > 0.85, f"distributed bf16 XOR accuracy {acc}"


def test_bf16_batchnorm_buffers_stay_f32():
    """Running stats must not silently degrade to bf16 accumulation."""
    rng = np.random.RandomState(3)
    samples = [Sample(rng.rand(8).astype(np.float32),
                      np.float32(1 + (i % 2))) for i in range(64)]
    model = nn.Sequential(nn.Linear(8, 16), nn.BatchNormalization(16),
                          nn.ReLU(), nn.Linear(16, 2), nn.LogSoftMax())
    opt = LocalOptimizer(model, array(samples), nn.ClassNLLCriterion(),
                         batch_size=16)
    opt.set_compute_dtype(jnp.bfloat16)
    opt.set_end_when(max_iteration(6))
    trained = opt.optimize()
    for leaf in jax.tree_util.tree_leaves(trained.buffer_tree()):
        assert leaf.dtype == jnp.float32


def _assert_step_donates_and_model_survives(opt, model, before, x, y):
    """The jitted step consumes the param/slot buffers it is HANDED
    (VERDICT r2 weak #1) — and those are the engine's own copies: the
    model's pre-training arrays stay alive, so a failed attempt leaves
    the model usable."""
    assert not any(a.is_deleted() for a in before), \
        "the train step donated the model's own arrays"
    engine = opt._engine_cache[1]
    params, slots, buffers = engine.init_state()
    handed = jax.tree_util.tree_leaves((params, slots))
    engine.step(params, slots, buffers, 0.01, x, y)
    assert all(a.is_deleted() for a in handed), \
        "the train step did not donate its input buffers"
    # and the model's post-training params are live + usable
    _ = model.forward(np.zeros((1, 2), np.float32))


def test_local_step_donates_buffers():
    samples = xor_samples(n=32)
    model = xor_model()
    before = jax.tree_util.tree_leaves(model.param_tree())
    opt = LocalOptimizer(model, array(samples), nn.ClassNLLCriterion(),
                         batch_size=32)
    opt.set_optim_method(Adam(learning_rate=0.01))
    opt.set_end_when(max_iteration(2))
    opt.reuse_compiled_engine = True
    opt.optimize()
    _assert_step_donates_and_model_survives(
        opt, model, before, np.stack([s.feature for s in samples]),
        np.stack([s.label for s in samples]))


def test_distri_step_donates_buffers():
    Engine.init()
    samples = xor_samples(n=64)
    model = xor_model()
    before = jax.tree_util.tree_leaves(model.param_tree())
    opt = DistriOptimizer(model, array(samples), nn.ClassNLLCriterion(),
                          batch_size=64)
    opt.set_end_when(max_iteration(2))
    opt.reuse_compiled_engine = True
    opt.optimize()
    _assert_step_donates_and_model_survives(
        opt, model, before, np.stack([s.feature for s in samples]),
        np.stack([s.label for s in samples]))
