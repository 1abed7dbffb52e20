"""End-to-end recovery specs for the resilience subsystem
(bigdl_tpu/resilience/): NaN-step skip, loss-spike rollback,
corrupt-checkpoint fallback (pickle + orbax), backoff retry schedule,
preemption checkpoint-resume, and ingest transient-I/O retry — all
driven by the deterministic injectors in resilience.faults.
"""
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import Sample, SampleToMiniBatch, array
from bigdl_tpu.optim import (SGD, LocalOptimizer, Top1Accuracy, max_epoch,
                             max_iteration, several_iteration)
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.resilience import (LossSpikeDetector, PreemptionHandler,
                                  RetryPolicy, classify_error, faults,
                                  tree_finite, verify_file, where_tree)
from bigdl_tpu.resilience.retry import FatalTrainingError, LossSpikeError


def xor_samples(n=256, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 2).astype(np.float32)
    y = ((x[:, 0] > 0.5) ^ (x[:, 1] > 0.5)).astype(np.float32) + 1
    return [Sample(x[i], y[i]) for i in range(n)]


def xor_model():
    return nn.Sequential(nn.Linear(2, 32), nn.Tanh(), nn.Linear(32, 2),
                         nn.LogSoftMax())


def tree_equal(a, b):
    leaves_a = jax.tree_util.tree_leaves(a)
    leaves_b = jax.tree_util.tree_leaves(b)
    return len(leaves_a) == len(leaves_b) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(leaves_a, leaves_b))


# ---------------------------------------------------------------------------
# guards (unit)
# ---------------------------------------------------------------------------

def test_tree_finite_and_where_tree():
    good = {"a": jnp.ones(3), "b": jnp.arange(4, dtype=jnp.int32)}
    bad = {"a": jnp.array([1.0, jnp.nan, 2.0]),
           "b": jnp.arange(4, dtype=jnp.int32)}
    assert bool(tree_finite(good))
    assert not bool(tree_finite(bad))
    assert not bool(tree_finite({"a": jnp.array([jnp.inf])}))
    # integer-only trees are vacuously finite
    assert bool(tree_finite({"i": jnp.arange(3)}))

    old = {"w": jnp.zeros(3)}
    new = {"w": jnp.ones(3)}
    picked = where_tree(jnp.bool_(False), new, old)
    assert np.array_equal(np.asarray(picked["w"]), np.zeros(3))
    picked = where_tree(jnp.bool_(True), new, old)
    assert np.array_equal(np.asarray(picked["w"]), np.ones(3))


def test_loss_spike_detector_k_consecutive():
    det = LossSpikeDetector(k=2, ratio=2.0, warmup=3)
    for _ in range(5):
        assert not det.update(1.0)  # warm EMA at 1.0
    assert not det.update(5.0)   # spike 1/2 — isolated is tolerated
    assert not det.update(1.0)   # recovery resets the streak
    assert not det.update(5.0)   # spike 1/2
    assert det.update(5.0)       # spike 2/2 — trip
    # NaN counts as a spike
    det.reset()
    for _ in range(5):
        det.update(1.0)
    assert not det.update(float("nan"))
    assert det.update(float("nan"))


# ---------------------------------------------------------------------------
# retry (unit)
# ---------------------------------------------------------------------------

def test_backoff_schedule_and_classification():
    sleeps = []
    p = RetryPolicy(max_retries=4, backoff_base=0.1, backoff_max=0.4,
                    jitter=0.0, sleep=sleeps.append)
    assert p.schedule(4) == pytest.approx([0.1, 0.2, 0.4, 0.4])

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 3:
            raise OSError("transient")
        return "ok"

    assert p.run(flaky) == "ok"
    assert sleeps == pytest.approx([0.1, 0.2, 0.4])

    # classification: programming/capacity errors are fatal, I/O and
    # loss spikes retryable, user interrupts always fatal
    assert classify_error(OSError("x")) == "retryable"
    assert classify_error(LossSpikeError("x")) == "retryable"
    assert classify_error(RuntimeError("injected failure")) == "retryable"
    assert classify_error(MemoryError()) == "fatal"
    assert classify_error(FatalTrainingError("x")) == "fatal"
    assert classify_error(KeyboardInterrupt()) == "fatal"


def test_fatal_errors_never_retried():
    sleeps = []
    p = RetryPolicy(max_retries=5, backoff_base=0.01, sleep=sleeps.append)
    with pytest.raises(MemoryError):
        p.run(lambda: (_ for _ in ()).throw(MemoryError()))
    assert sleeps == []


def test_jitter_is_deterministic_and_bounded():
    a = RetryPolicy(backoff_base=1.0, backoff_max=64.0, jitter=0.25, seed=7)
    b = RetryPolicy(backoff_base=1.0, backoff_max=64.0, jitter=0.25, seed=7)
    da = [a.delay(i) for i in range(1, 6)]
    db = [b.delay(i) for i in range(1, 6)]
    assert da == db  # same seed, same schedule
    for i, d in enumerate(da, start=1):
        base = min(1.0 * 2 ** (i - 1), 64.0)
        assert base * 0.75 <= d <= base * 1.25


def test_retry_budget_exhausts():
    sleeps = []
    p = RetryPolicy(max_retries=2, backoff_base=0.01, sleep=sleeps.append)
    with pytest.raises(OSError):
        p.run(lambda: (_ for _ in ()).throw(OSError("always")))
    assert len(sleeps) == 2  # two retries granted, then re-raise


# ---------------------------------------------------------------------------
# NaN gradient skip (e2e)
# ---------------------------------------------------------------------------

def test_nan_step_preserves_params_exact_local():
    """One all-NaN batch: the guarded step is a bit-exact no-op on
    params (the acceptance contract: an injected NaN gradient is
    skipped without corrupting params)."""
    bad = [Sample(np.full(2, np.nan, np.float32), 1.0) for _ in range(64)]
    model = xor_model()
    before = jax.tree_util.tree_map(np.asarray, model.param_tree())
    opt = LocalOptimizer(model, array(bad), nn.ClassNLLCriterion(),
                         batch_size=64)
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_end_when(max_iteration(1))
    opt.optimize()
    assert opt.skipped_steps == 1
    assert tree_equal(before, model.param_tree())


def test_nan_injection_skipped_and_converges_local():
    fault = faults.NaNInjector(at=65, n=64)  # exactly batch 2
    ds = array(xor_samples()) >> fault >> SampleToMiniBatch(64)
    model = xor_model()
    opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=1.0))
    opt.set_end_when(max_epoch(150))
    trained = opt.optimize()
    assert fault.fired == 64, "the NaN injection never triggered"
    assert opt.skipped_steps >= 1
    for leaf in jax.tree_util.tree_leaves(trained.param_tree()):
        assert np.isfinite(np.asarray(leaf)).all()
    res = trained.evaluate(array(xor_samples(seed=1)), [Top1Accuracy()])
    assert res[0][0].result()[0] > 0.85


@pytest.mark.slow
def test_nan_injection_skipped_distri():
    """Same contract through the shard_mapped reduce-scatter step: the
    skip predicate must agree across all 8 shards (pmin)."""
    fault = faults.NaNInjector(at=65, n=64)
    ds = array(xor_samples()) >> fault >> SampleToMiniBatch(64)
    model = xor_model()
    opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=1.0))
    opt.set_end_when(max_epoch(150))
    trained = opt.optimize()
    assert fault.fired == 64
    assert opt.skipped_steps >= 1
    for leaf in jax.tree_util.tree_leaves(trained.param_tree()):
        assert np.isfinite(np.asarray(leaf)).all()
    res = trained.evaluate(array(xor_samples(seed=1)), [Top1Accuracy()])
    assert res[0][0].result()[0] > 0.85


# ---------------------------------------------------------------------------
# loss-spike rollback (e2e)
# ---------------------------------------------------------------------------

def test_loss_spike_rollback_to_checkpoint(tmp_path):
    """K consecutive spiked batches trip the detector; the retry loop
    restores the last good checkpoint and training completes."""
    # linear model on XOR: loss plateaus ~0.69, and a 100x feature
    # scale blows the misclassified half's loss up by orders of
    # magnitude — a deterministic spike
    model = nn.Sequential(nn.Linear(2, 2), nn.LogSoftMax())
    fault = faults.ScaleInjector(at=257, n=128, scale=100.0)  # 2 batches
    ds = array(xor_samples()) >> fault >> SampleToMiniBatch(64)
    opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_iteration(12))
    opt.set_checkpoint(str(tmp_path), several_iteration(1))
    opt.set_loss_spike_guard(k=2, ratio=2.0, warmup=2)
    opt.set_retry_policy(RetryPolicy(max_retries=5, backoff_base=0.01))
    trained = opt.optimize()
    assert fault.fired == 128, "the spike injection never triggered"
    assert opt.rollbacks >= 1, "the spike never triggered a rollback"
    assert trained is model
    assert opt.optim_method.state["neval"] > 12


# ---------------------------------------------------------------------------
# corrupt-checkpoint fallback (e2e, both formats)
# ---------------------------------------------------------------------------

def _train_with_checkpoints(tmp_path, fmt="pickle", iters=4):
    model = xor_model()
    opt = LocalOptimizer(model, array(xor_samples()),
                         nn.ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_end_when(max_iteration(iters))
    opt.set_checkpoint(str(tmp_path), several_iteration(1), format=fmt)
    opt.optimize()
    return opt


def test_corrupt_pickle_checkpoint_falls_back(tmp_path):
    _train_with_checkpoints(tmp_path, "pickle")
    steps = sorted(int(f.split(".")[1]) for f in os.listdir(tmp_path)
                   if f.startswith("model."))
    newest, prev = steps[-1], steps[-2]
    faults.bit_flip(str(tmp_path / f"model.{newest}"))

    fresh = xor_model()
    opt2 = LocalOptimizer(fresh, array(xor_samples()),
                          nn.ClassNLLCriterion(), batch_size=64)
    opt2.set_checkpoint(str(tmp_path), several_iteration(1))
    assert opt2.resume_from_checkpoint() is True
    # the corrupt newest was quarantined, the previous good one loaded
    assert (tmp_path / f"model.{newest}.corrupt").exists()
    from bigdl_tpu.utils.file_io import load

    good = load(str(tmp_path / f"model.{prev}"))
    assert tree_equal(good.param_tree(), fresh.param_tree())


def test_truncated_pickle_checkpoint_falls_back(tmp_path):
    _train_with_checkpoints(tmp_path, "pickle")
    steps = sorted(int(f.split(".")[1]) for f in os.listdir(tmp_path)
                   if f.startswith("model."))
    newest, prev = steps[-1], steps[-2]
    faults.truncate(str(tmp_path / f"model.{newest}"), keep_fraction=0.5)

    fresh = xor_model()
    opt2 = LocalOptimizer(fresh, array(xor_samples()),
                          nn.ClassNLLCriterion(), batch_size=64)
    opt2.set_checkpoint(str(tmp_path), several_iteration(1))
    assert opt2.resume_from_checkpoint() is True
    assert (tmp_path / f"model.{newest}.corrupt").exists()
    from bigdl_tpu.utils.file_io import load

    good = load(str(tmp_path / f"model.{prev}"))
    assert tree_equal(good.param_tree(), fresh.param_tree())


def test_corrupt_orbax_checkpoint_falls_back(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    opt = _train_with_checkpoints(tmp_path, "orbax")
    saved_neval = opt.optim_method.state["neval"]
    steps = sorted(int(d.split("-")[1]) for d in os.listdir(tmp_path)
                   if d.startswith("ckpt-") and d.split("-")[1].isdigit())
    assert len(steps) >= 2
    newest = steps[-1]
    # flip a bit in the newest step's largest file (the array payload)
    step_dir = tmp_path / f"ckpt-{newest}"
    victim = max((p for p in step_dir.rglob("*") if p.is_file()),
                 key=lambda p: p.stat().st_size)
    faults.bit_flip(str(victim))

    fresh = xor_model()
    opt2 = LocalOptimizer(fresh, array(xor_samples()),
                          nn.ClassNLLCriterion(), batch_size=64)
    opt2.set_checkpoint(str(tmp_path), several_iteration(1),
                        format="orbax")
    assert opt2.resume_from_checkpoint() is True
    assert (tmp_path / f"ckpt-{newest}.corrupt").exists()
    # the state restored is the previous step's (saved at neval-1)
    assert opt2.optim_method.state["neval"] < saved_neval


def test_atomic_save_writes_verifiable_sidecar(tmp_path):
    from bigdl_tpu.utils import file_io

    p = str(tmp_path / "tree")
    file_io.save({"w": jnp.ones((4, 4))}, p, atomic=True, checksum=True)
    assert verify_file(p) is True
    faults.bit_flip(p)
    assert verify_file(p) is False


# ---------------------------------------------------------------------------
# mid-epoch exception retry converges like an uninjected run (e2e)
# ---------------------------------------------------------------------------

def test_injected_exception_retries_and_converges(tmp_path):
    def run(inject):
        from bigdl_tpu.utils.rng import RNG

        RNG().set_seed(1)
        np.random.seed(1)
        model = xor_model()
        ds = array(xor_samples())
        fault = None
        if inject:
            fault = faults.ExceptionTransformer(fail_at=300)
            ds = ds >> fault >> SampleToMiniBatch(64)
        opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(),
                             batch_size=64)
        opt.set_optim_method(SGD(learning_rate=1.0))
        opt.set_end_when(max_epoch(150))
        opt.set_checkpoint(str(tmp_path / ("inj" if inject else "clean")),
                           several_iteration(1))
        sleeps = []
        opt.set_retry_policy(RetryPolicy(max_retries=5, backoff_base=0.01,
                                         sleep=sleeps.append))
        opt.optimize()
        return opt, fault, sleeps, float(opt.optim_method.state["loss"])

    opt_i, fault, sleeps, loss_injected = run(inject=True)
    assert fault.fired, "the injected fault never triggered"
    assert opt_i.rollbacks >= 1
    assert len(sleeps) >= 1 and sleeps[0] > 0, \
        "retry must back off before restoring"
    _, _, _, loss_clean = run(inject=False)
    # the recovered run lands in the same basin as the clean one (the
    # post-rollback record order differs, so "same" is the basin, not
    # the bit pattern)
    assert loss_injected < 0.3, loss_injected
    assert abs(loss_injected - loss_clean) < 0.2, \
        (loss_injected, loss_clean)


def test_compile_and_oom_errors_are_fatal_and_surface_first(tmp_path):
    """A program the compiler refuses, or one that does not fit device
    memory, fails identically on every replay: the retry loop must
    raise it at once — not recompile it five times with backoff, and
    not bury it under a later error — and leave the model usable."""
    def xla(msg):
        return jax.errors.JaxRuntimeError(msg)

    for exc in (xla("RESOURCE_EXHAUSTED: XLA:TPU compile permanent "
                    "error. Ran out of memory in memory space hbm."),
                xla("RESOURCE_EXHAUSTED: Error allocating device buffer"),
                xla("INTERNAL: Mosaic failed to compile TPU kernel: x"),
                xla("INVALID_ARGUMENT: bad shape"),
                ValueError("The Pallas TPU lowering currently requires "
                           "that the last two dimensions ..."),
                TypeError("scan body function carry input and carry "
                          "output must have equal types")):
        assert classify_error(exc) == "fatal", exc
    for exc in (RuntimeError("injected failure"), OSError("disk"),
                xla("UNAVAILABLE: socket closed"),
                LossSpikeError("diverged")):
        assert classify_error(exc) == "retryable", exc

    model = xor_model()
    oom = faults.ExceptionTransformer(
        fail_at=300, exc=lambda: xla("RESOURCE_EXHAUSTED: out of HBM"))
    ds = array(xor_samples()) >> oom >> SampleToMiniBatch(64)
    opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=1.0))
    opt.set_end_when(max_epoch(150))
    opt.set_checkpoint(str(tmp_path / "ck"), several_iteration(1))
    sleeps = []
    opt.set_retry_policy(RetryPolicy(max_retries=5, backoff_base=0.01,
                                     sleep=sleeps.append))
    with pytest.raises(jax.errors.JaxRuntimeError,
                       match="RESOURCE_EXHAUSTED"):
        opt.optimize()
    assert oom.fired and opt.rollbacks == 0 and sleeps == []
    # steps ran and donated before the failure; the model's own arrays
    # were never the donated ones
    _ = model.forward(np.zeros((1, 2), np.float32))


# ---------------------------------------------------------------------------
# preemption: checkpoint at the step boundary, exit clean, resume
# ---------------------------------------------------------------------------

def test_sigterm_requests_graceful_stop():
    h = PreemptionHandler()
    with h:
        assert not h.should_stop
        os.kill(os.getpid(), signal.SIGTERM)
        # the handler runs at the next bytecode boundary
        for _ in range(100):
            if h.should_stop:
                break
        assert h.should_stop


def test_preemption_checkpoints_and_resumes(tmp_path):
    fault = faults.PreemptTransformer(at=150)  # fires in iteration 3
    ds = array(xor_samples()) >> fault >> SampleToMiniBatch(64)
    model = xor_model()
    opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=1.0))
    opt.set_end_when(max_iteration(600))
    # the trigger never fires on its own — only the preemption path
    # writes this checkpoint
    opt.set_checkpoint(str(tmp_path), several_iteration(1000))
    opt.set_preemption_handling(True)
    opt.optimize()
    assert fault.fired
    stopped_at = opt.optim_method.state["neval"]
    assert stopped_at < 600, "preemption should have stopped the run early"
    assert any(f.startswith("model.") for f in os.listdir(tmp_path))

    # fresh process analogue: new model/optimizer resume and finish
    fresh = xor_model()
    opt2 = LocalOptimizer(fresh, array(xor_samples()),
                          nn.ClassNLLCriterion(), batch_size=64)
    opt2.set_optim_method(SGD(learning_rate=1.0))
    opt2.set_checkpoint(str(tmp_path), several_iteration(1000))
    assert opt2.resume_from_checkpoint() is True
    assert opt2.optim_method.state["neval"] == stopped_at
    opt2.set_end_when(max_iteration(600))
    trained = opt2.optimize()
    assert opt2.optim_method.state["neval"] - 1 == 600
    res = trained.evaluate(array(xor_samples(seed=1)), [Top1Accuracy()])
    assert res[0][0].result()[0] > 0.85


# ---------------------------------------------------------------------------
# ingest transient-I/O retry
# ---------------------------------------------------------------------------

def _ingest_samples(n=20):
    return [Sample(np.full(4, i, np.float32), float(i % 2) + 1)
            for i in range(n)]


def test_ingest_transient_io_error_is_retried(tmp_path):
    from bigdl_tpu.dataset.ingest import SeqFileFolder, write_seq_files

    write_seq_files(_ingest_samples(), str(tmp_path), shard_size=8)
    with faults.io_faults(str(tmp_path), times=2) as entry:
        ds = SeqFileFolder(str(tmp_path))
        it = ds.data(train=False)
        got = [next(it) for _ in range(20)]
    assert len(got) == 20
    assert entry["remaining"] == 0, "the I/O faults never triggered"
    np.testing.assert_allclose(np.asarray(got[3].feature),
                               np.full(4, 3, np.float32))


def test_ingest_corrupt_record_is_not_retried(tmp_path):
    from bigdl_tpu.dataset.ingest import (CorruptRecordError, SeqFileFolder,
                                          write_seq_files)

    paths = write_seq_files(_ingest_samples(), str(tmp_path), shard_size=8)
    faults.bit_flip(paths[0])
    ds = SeqFileFolder(str(tmp_path))
    with pytest.raises(CorruptRecordError):
        list(ds.data(train=False))
