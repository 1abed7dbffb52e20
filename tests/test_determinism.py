"""Deterministic-resume + SDC-defense specs (bigdl_tpu/resilience/
integrity.py + replay.py and the total-train-state plumbing):
checkpointable RNG/pipeline state, atomic shard writes, the
step-fingerprint flight recorder, deterministic replay localization,
cross-host integrity votes — and two acceptance e2es: an interrupted+
resumed run bitwise identical to an uninterrupted one, and a simulated
4-host cluster that localizes and evicts a silently-corrupting host
while the loss keeps descending.  A lint spec greps the package for
module-level unseeded RNG calls so nondeterminism can't creep back in.
"""
import os
import re
import time

import jax
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import (Sample, SampleToMiniBatch, SeqFileFolder,
                               array, write_seq_files)
from bigdl_tpu.dataset.ingest import RecordFileWriter
from bigdl_tpu.optim import (SGD, LocalOptimizer, max_iteration,
                             several_iteration)
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.resilience import (ElasticContext, ElasticCoordinator,
                                  FlightRecorder, InMemoryKV,
                                  IntegrityError, MembershipChangedError,
                                  RetryPolicy, SilentDataCorruptionError,
                                  SimulatedHost, checksum_tree,
                                  diff_journals, faults, load_journal,
                                  majority_vote, replay)
from bigdl_tpu.utils.rng import (RNG, RandomGenerator, derive_seed,
                                 np_stream, set_global_seed)
from bigdl_tpu.visualization import IntegritySummary, TrainSummary


@pytest.fixture(autouse=True)
def _reset_explicit_seed():
    """set_global_seed flips module state the other suites must not
    inherit (derived streams re-key off the explicit seed)."""
    from bigdl_tpu.utils import rng as rng_mod

    yield
    rng_mod._explicit_seed = None


def _regression_samples(n=256, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 4).astype(np.float32)
    w = np.array([[1.5], [-2.0], [0.5], [3.0]], np.float32)
    y = (x @ w + 0.7).astype(np.float32)
    return [Sample(x[i], y[i]) for i in range(n)]


def _regression_model():
    return nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))


def _rng_state_equal(a, b):
    sa, sb = a["bit_generator"]["state"], b["bit_generator"]["state"]
    return (a["seed"] == b["seed"] and sa["pos"] == sb["pos"]
            and np.array_equal(sa["key"], sb["key"]))


# ---------------------------------------------------------------------------
# checkpointable RNG + pipeline state
# ---------------------------------------------------------------------------

def test_rng_state_roundtrip_mid_stream():
    g = RandomGenerator(7)
    g.uniform(0, 1, (13,))                   # advance the stream
    state = g.state_dict()
    expected = g.uniform(0, 1, (50,))
    # a generator seeded DIFFERENTLY continues the exact bit sequence
    # after load_state_dict: position included, not just the seed
    g2 = RandomGenerator(999).load_state_dict(state)
    assert np.array_equal(g2.uniform(0, 1, (50,)), expected)
    assert g2.get_seed() == 7


def test_global_seed_governs_derived_streams():
    # no explicit seed: the legacy fixed fallbacks, bit-for-bit
    assert np.array_equal(np_stream(10).rand(5),
                          np.random.RandomState(10).rand(5))
    set_global_seed(777)
    a = np_stream(10).rand(5)
    assert not np.array_equal(a, np.random.RandomState(10).rand(5))
    assert np.array_equal(a, np_stream(10).rand(5))  # reproducible
    # distinct sub-streams stay distinct under one global seed
    assert derive_seed(10) != derive_seed(11)
    set_global_seed(778)
    assert not np.array_equal(np_stream(10).rand(5), a)


def test_local_array_dataset_state_roundtrip():
    ds = array(_regression_samples(32))
    ds.shuffle()
    state = ds.state_dict()
    order = [np.asarray(s.feature).tobytes()
             for s, _ in zip(ds.data(train=True), range(32))]
    ds2 = array(_regression_samples(32))
    ds2.load_state_dict(state)
    order2 = [np.asarray(s.feature).tobytes()
              for s, _ in zip(ds2.data(train=True), range(32))]
    assert order == order2


def test_seqfilefolder_state_roundtrip_and_private_stream(tmp_path):
    write_seq_files(_regression_samples(24), str(tmp_path), shard_size=4)
    ds = SeqFileFolder(str(tmp_path), seed=3)
    host_state = RNG().state_dict()
    ds.shuffle()
    ds.shuffle()
    # shard shuffling draws from the per-dataset generator, NOT the
    # thread-local global RNG() — its stream must be untouched
    assert _rng_state_equal(RNG().state_dict(), host_state)
    state = ds.state_dict()
    seq = [np.asarray(s.feature).tobytes()
           for s, _ in zip(ds.data(train=True), range(48))]
    ds2 = SeqFileFolder(str(tmp_path), seed=99)
    ds2.load_state_dict(state)
    seq2 = [np.asarray(s.feature).tobytes()
            for s, _ in zip(ds2.data(train=True), range(48))]
    # 2 epochs worth: the restored order AND the restored shuffle-stream
    # position reproduce the record sequence across epoch boundaries
    assert seq == seq2
    # shard-count mismatch (dataset regenerated differently) is ignored,
    # not crashed on
    ds3 = SeqFileFolder(str(tmp_path), shard_index=0, shard_count=2)
    ds3.load_state_dict(state)


def test_seqfilefolder_iterator_does_not_mutate_dataset_state(tmp_path):
    write_seq_files(_regression_samples(16), str(tmp_path), shard_size=4)
    ds = SeqFileFolder(str(tmp_path), seed=3)
    before = ds.state_dict()
    for _, _ in zip(ds.data(train=True), range(40)):
        pass
    # the producer shuffles a CLONED generator: state captured at any
    # step boundary is exact regardless of prefetch depth
    after = ds.state_dict()
    assert after["order"] == before["order"]
    assert _rng_state_equal(after["rng"], before["rng"])


# ---------------------------------------------------------------------------
# atomic shard writes (file_io discipline for RecordFileWriter)
# ---------------------------------------------------------------------------

def test_record_writer_publishes_atomically(tmp_path):
    path = str(tmp_path / "shard-00000.records")
    w = RecordFileWriter(path)
    w.write(b"payload")
    # nothing visible before close: the bytes sit in a staging file the
    # shard listing ignores (it does not end in .records)
    assert not os.path.exists(path)
    assert all(not f.endswith(".records") for f in os.listdir(tmp_path))
    w.close()
    assert os.path.exists(path)
    w.close()  # idempotent
    with pytest.raises(ValueError):
        w.write(b"late")


def test_crash_mid_write_leaves_no_torn_shard(tmp_path):
    """The regression: the old writer opened <path> directly, so a
    crash mid-write left a torn shard whose intact prefix passed the
    CRC scan and silently shrank the dataset.  Now the crash leaves
    only a staging file that SeqFileFolder never lists."""
    samples = _regression_samples(8)
    write_seq_files(samples[:4], str(tmp_path), shard_size=4,
                    prefix="good")
    w = RecordFileWriter(str(tmp_path / "torn-00000.records"))
    from bigdl_tpu.dataset.ingest import _encode_sample

    w.write(_encode_sample(samples[4]))
    del w  # crash analogue: never closed, never published
    ds = SeqFileFolder(str(tmp_path))
    assert ds.size() == 4  # only the published shard, fully intact
    got = sum(1 for _ in ds.data(train=False))
    assert got == 4


def test_record_writer_abort_drops_staging(tmp_path):
    path = str(tmp_path / "shard-00000.records")
    w = RecordFileWriter(path)
    w.write(b"abc")
    w.abort()
    assert os.listdir(tmp_path) == []
    w.abort()  # idempotent


# ---------------------------------------------------------------------------
# flight recorder + journal diff
# ---------------------------------------------------------------------------

def test_flight_recorder_journal_and_torn_trailing_line(tmp_path):
    p = str(tmp_path / "j.jsonl")
    with FlightRecorder(p, param_crc_every=2) as rec:
        rec.record_step(1, 1, 0.5, grad_norm=2.0, batch_id="aa")
        assert not rec.wants_param_crc(1)
        rec.record_step(2, 1, 0.25, grad_norm=1.0, batch_id="bb",
                        skipped=True)
        assert rec.wants_param_crc(2)
        rec.record_param(2, "deadbeef")
    with pytest.raises(ValueError):
        rec.record_step(3, 1, 0.1)
    # crash analogue: a torn trailing line is skipped, the rest parses
    with open(p, "a") as f:
        f.write('{"kind": "step", "step": 3, "loss_bi')
    j = load_journal(p)
    assert [r["step"] for r in j] == [1, 2, 2]
    assert j[0]["loss_bits"] is not None and j[0]["grad_norm_bits"]
    assert j[1]["skipped"] is True
    assert j[2] == {"kind": "param", "step": 2, "param_crc": "deadbeef"}


def test_diff_journals_blame_order_and_alignment():
    a = [{"kind": "step", "step": 1, "batch_id": "x", "loss_bits": "l1"},
         {"kind": "step", "step": 2, "batch_id": "y", "loss_bits": "l2"},
         {"kind": "step", "step": 3, "batch_id": "z", "loss_bits": "l3"}]
    assert diff_journals(a, [dict(r) for r in a]) is None
    # replay starts mid-journal: only common steps are compared
    b = [dict(r) for r in a[1:]]
    assert diff_journals(a, b) is None
    # a batch_id mismatch outranks the loss mismatch at the same step
    b = [dict(r) for r in a]
    b[1].update(batch_id="WRONG", loss_bits="ALSO")
    d = diff_journals(a, b)
    assert (d["step"], d["field"]) == (2, "batch_id")
    # None fields (fused paths record no grad norm) never diverge
    b = [dict(r, grad_norm_bits=None) for r in a]
    a2 = [dict(r, grad_norm_bits="gg") for r in a]
    assert diff_journals(a2, b) is None


def test_majority_vote_contract():
    truth, corrupt = majority_vote(
        {"a": "x", "b": "x", "c": "y"}, ["a", "b", "c"])
    assert (truth, corrupt) == ("x", ["c"])
    truth, corrupt = majority_vote(
        {"a": "x", "b": "x", "c": "x"}, ["a", "b", "c"])
    assert corrupt == []
    # a 2-2 split has no ground truth
    with pytest.raises(IntegrityError):
        majority_vote({"a": "x", "b": "x", "c": "y", "d": "y"},
                      ["a", "b", "c", "d"])
    # silent hosts count AGAINST quorum: 2 agreeing of 4 is not truth
    with pytest.raises(IntegrityError):
        majority_vote({"a": "x", "b": "x"}, ["a", "b", "c", "d"])
    with pytest.raises(IntegrityError):
        majority_vote({}, ["a", "b"])


def test_flip_param_bits_is_finite_and_fingerprint_visible():
    import jax.numpy as jnp

    tree = {"w": jnp.ones((8, 8), jnp.float32),
            "b": jnp.zeros((8,), jnp.float32),
            "step": jnp.int32(3)}
    flipped = faults.flip_tree_bits(tree)
    leaves, fleaves = (jax.tree_util.tree_leaves(tree),
                       jax.tree_util.tree_leaves(flipped))
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(leaves, fleaves))
    # every value stays finite and plausibly sized: NaN/Inf guards and
    # loss-spike detectors ride straight past it
    for leaf in fleaves:
        a = np.asarray(leaf)
        assert np.isfinite(a).all()
        if np.issubdtype(a.dtype, np.floating):
            assert np.abs(a).max() < 2.0
    assert checksum_tree(tree) != checksum_tree(flipped)


# ---------------------------------------------------------------------------
# resume equivalence: interrupted+resumed == uninterrupted, bitwise
# ---------------------------------------------------------------------------

def _step_records(path):
    return {r["step"]: r for r in load_journal(path)
            if r.get("kind") == "step"}


def test_resume_equivalence_bitwise(tmp_path):
    """The acceptance spec: preempt a run mid-epoch, resume from the
    checkpoint in a fresh optimizer, and the batch-id sequence and the
    loss/grad-norm trajectories are BITWISE identical to an
    uninterrupted run — total state (params, slots, RNG stream,
    pipeline order + record cursor) came back."""
    steps = 10

    def build(fault=None):
        set_global_seed(123)
        model = _regression_model()
        ds = array(_regression_samples())
        if fault is not None:
            ds = ds >> fault
        opt = LocalOptimizer(model, ds, nn.MSECriterion(), batch_size=64)
        opt.set_optim_method(SGD(learning_rate=0.1))
        return opt

    # --- run A: uninterrupted --------------------------------------------
    opt = build()
    opt.set_end_when(max_iteration(steps))
    with FlightRecorder(str(tmp_path / "A.jsonl")) as rec:
        opt.set_flight_recorder(rec)
        opt.optimize()

    # --- run B: preempted mid-epoch at record 150 (iteration 3) ----------
    fault = faults.PreemptTransformer(at=150)
    opt = build(fault)
    opt.set_end_when(max_iteration(steps))
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(1000))
    opt.set_preemption_handling(True)
    with FlightRecorder(str(tmp_path / "B1.jsonl")) as rec:
        opt.set_flight_recorder(rec)
        opt.optimize()
    assert fault.fired
    stopped_at = opt.optim_method.state["neval"]
    assert 1 < stopped_at <= steps, "preemption must interrupt mid-run"

    # --- resume in a fresh "process": different global seed on purpose —
    # the checkpoint's trainState must overwrite it
    set_global_seed(999)
    model2 = _regression_model()
    opt2 = LocalOptimizer(model2, array(_regression_samples()),
                          nn.MSECriterion(), batch_size=64)
    opt2.set_optim_method(SGD(learning_rate=0.1))
    opt2.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(1000))
    assert opt2.resume_from_checkpoint() is True
    assert opt2.optim_method.state["neval"] == stopped_at
    opt2.set_end_when(max_iteration(steps))
    with FlightRecorder(str(tmp_path / "B2.jsonl")) as rec:
        opt2.set_flight_recorder(rec)
        opt2.optimize()
    assert opt2.optim_method.state["neval"] - 1 == steps

    # --- bitwise equivalence ---------------------------------------------
    a = _step_records(str(tmp_path / "A.jsonl"))
    b = dict(_step_records(str(tmp_path / "B1.jsonl")))
    b2 = _step_records(str(tmp_path / "B2.jsonl"))
    assert not set(b) & set(b2), "resume must not re-train a step"
    b.update(b2)
    assert set(a) == set(b) == set(range(1, steps + 1))
    for s in range(1, steps + 1):
        for field in ("batch_id", "loss_bits", "grad_norm_bits",
                      "epoch"):
            assert a[s][field] == b[s][field], \
                f"step {s} diverged on {field}: " \
                f"{a[s][field]} vs {b[s][field]}"
    assert diff_journals(sorted(a.values(), key=lambda r: r["step"]),
                         list(b.values())) is None


# ---------------------------------------------------------------------------
# replay: localize the first divergent step
# ---------------------------------------------------------------------------

def test_replay_localizes_first_divergent_step(tmp_path):
    """flip_param_bits perturbs one mantissa bit after step 7 — every
    value stays finite, the guards see nothing, the loss keeps looking
    plausible.  Replay from the step-4 checkpoint re-executes clean and
    the journal diff blames the first post-corruption step."""
    journal = str(tmp_path / "journal.jsonl")
    ckpt = str(tmp_path / "ckpt")

    def make_opt():
        set_global_seed(5)
        opt = LocalOptimizer(_regression_model(),
                             array(_regression_samples()),
                             nn.MSECriterion(), batch_size=64)
        opt.set_optim_method(SGD(learning_rate=0.1))
        return opt

    opt = make_opt()
    opt.set_checkpoint(ckpt, several_iteration(4))
    opt.set_end_when(max_iteration(12))
    rec = FlightRecorder(journal, param_crc_every=2)
    opt.set_flight_recorder(rec)
    with faults.flip_param_bits("local", at_step=7) as flip:
        opt.optimize()
    rec.close()
    assert flip["fired"] == 1

    report = replay(make_opt, ckpt, journal, from_step=4,
                    param_crc_every=2)
    d = report["divergence"]
    assert d is not None, "the corruption must be visible to replay"
    # the flip lands after step 7's fingerprint: step 8 is the first
    # record computed FROM corrupt state (param crc at the cadence, or
    # the loss bits — both derive from the flipped tree)
    assert d["step"] == 8, d
    assert d["field"] in ("loss_bits", "grad_norm_bits", "param_crc"), d
    assert report["steps_compared"] >= 8
    # the replayed journal is evidence too — and the original directory
    # was never written to (no new checkpoints)
    assert os.path.exists(report["replay_journal"])
    assert max(int(f.rsplit(".", 1)[1]) for f in os.listdir(ckpt)
               if f.startswith("model.")) == 12


def test_replay_verifies_a_clean_run_bit_for_bit(tmp_path):
    journal = str(tmp_path / "journal.jsonl")
    ckpt = str(tmp_path / "ckpt")

    def make_opt():
        set_global_seed(5)
        opt = LocalOptimizer(_regression_model(),
                             array(_regression_samples()),
                             nn.MSECriterion(), batch_size=64)
        opt.set_optim_method(SGD(learning_rate=0.1))
        return opt

    opt = make_opt()
    opt.set_checkpoint(ckpt, several_iteration(4))
    opt.set_end_when(max_iteration(10))
    with FlightRecorder(journal, param_crc_every=2) as rec:
        opt.set_flight_recorder(rec)
        opt.optimize()

    report = replay(make_opt, ckpt, journal, from_step=4,
                    param_crc_every=2)
    assert report["divergence"] is None
    assert report["steps_compared"] >= 6  # steps 5..10 replayed


# ---------------------------------------------------------------------------
# cross-host integrity votes
# ---------------------------------------------------------------------------

def _vote_ctx(kv, hosts, **kw):
    coord = ElasticCoordinator("host0", kv, heartbeat_timeout=5.0)
    coord.bootstrap(hosts)
    ctx = ElasticContext(coord, rendezvous_timeout=0.5,
                         integrity_cadence=1, integrity_timeout=0.3,
                         **kw)
    ctx.attach(n_devices=8, batch_size=64)
    ctx.begin_attempt()
    return ctx


def test_integrity_vote_flags_self_peer_and_quorum_loss():
    kv = InMemoryKV()
    hosts = ["host0", "host1", "host2", "host3"]
    ctx = _vote_ctx(kv, hosts)
    inc = ctx.incarnation

    # unanimous: no flag
    for h in hosts[1:]:
        kv.put(f"sdc/{inc}/1/{h}", "aaaa")
    ctx.integrity_vote(1, "aaaa")
    assert ctx.sdc_votes == 1 and ctx.sdc_disagreements == 0

    # the MAJORITY says this host's numbers are the wrong ones
    for h in hosts[1:]:
        kv.put(f"sdc/{inc}/3/{h}", "bbbb")
    with pytest.raises(SilentDataCorruptionError):
        ctx.integrity_vote(3, "aaaa")
    assert ctx.sdc_detected_steps == [3]

    # a corrupt PEER is evicted + proposed out (retryable membership
    # change — the same escalation path a dead host takes)
    kv.put(f"sdc/{inc}/5/host1", "aaaa")
    kv.put(f"sdc/{inc}/5/host2", "cccc")
    kv.put(f"sdc/{inc}/5/host3", "aaaa")
    with pytest.raises(MembershipChangedError) as ei:
        ctx.integrity_vote(5, "aaaa")
    assert "host2" in str(ei.value)
    assert ctx.sdc_evictions == 1
    assert "host2" in ctx.evicted_hosts
    assert ctx.coordinator.evicted() == {"host2"}


def test_integrity_vote_no_quorum_is_fatal():
    kv = InMemoryKV()
    hosts = ["host0", "host1", "host2", "host3"]
    ctx = _vote_ctx(kv, hosts)
    inc = ctx.incarnation
    # 2-2 split: no strict majority, no ground truth — fatal
    kv.put(f"sdc/{inc}/2/host1", "aaaa")
    kv.put(f"sdc/{inc}/2/host2", "bbbb")
    kv.put(f"sdc/{inc}/2/host3", "bbbb")
    with pytest.raises(IntegrityError):
        ctx.integrity_vote(2, "aaaa")
    # silent peers count against quorum too (bounded wait, then fatal)
    t0 = time.monotonic()
    kv.put(f"sdc/{inc}/4/host1", "aaaa")
    with pytest.raises(IntegrityError):
        ctx.integrity_vote(4, "aaaa")
    assert time.monotonic() - t0 < 5.0


# ---------------------------------------------------------------------------
# the SDC chaos e2e
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("steps,corrupt_at", [(30, 9), (20, 6)])
def test_sdc_chaos_end_to_end(tmp_path, steps, corrupt_at):
    """The acceptance spec: a simulated 4-host cluster trains with
    integrity votes every 4 steps; host2 starts publishing silently
    wrong checksums at step 9 (corrupt_gradient — finite, plausible,
    invisible to the NaN guards).  The next vote must localize it
    within the cadence window, evict it through the elastic path,
    restore from the verified checkpoint, and keep the loss
    descending on the survivors."""
    t_start = time.monotonic()
    kv = InMemoryKV()
    hosts = ["host0", "host1", "host2", "host3"]
    coord = ElasticCoordinator("host0", kv, heartbeat_timeout=0.3)
    coord.bootstrap(hosts)
    sims = [SimulatedHost(h, kv, heartbeat_timeout=0.3)
            for h in hosts[1:]]
    isummary = IntegritySummary(str(tmp_path / "logs"), "sdc")
    tsummary = TrainSummary(str(tmp_path / "logs"), "sdc")
    ctx = ElasticContext(coord, rendezvous_timeout=3.0,
                         regrow_after_steps=1000,
                         integrity_cadence=4)

    opt = DistriOptimizer(_regression_model(),
                          array(_regression_samples()),
                          nn.MSECriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=0.3))
    opt.set_end_when(max_iteration(steps))
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(1))
    opt.set_retry_policy(RetryPolicy(max_retries=20, backoff_base=0.01,
                                     backoff_max=0.05))
    opt.set_integrity_summary(isummary)
    opt.set_elastic(ctx)
    opt.set_train_summary(tsummary)

    with faults.corrupt_gradient("host2", at_step=corrupt_at) as fault, \
            faults.delay_host("host0", 0.05, at_step=1):
        for s in sims:
            s.start()
        try:
            opt.optimize()
        finally:
            for s in sims:
                s.stop()
    elapsed = time.monotonic() - t_start
    assert elapsed < 120, f"chaos run must stay bounded, took {elapsed:.0f}s"
    assert fault["fired"] >= 1

    # --- localization within the cadence window --------------------------
    assert ctx.sdc_detected_steps, "the vote never flagged the host"
    detected = ctx.sdc_detected_steps[0]
    assert 0 <= detected - corrupt_at <= ctx.integrity_cadence, detected
    assert ctx.evicted_hosts == ["host2"]
    assert ctx.sdc_evictions == 1
    assert ctx.incarnation_changes >= 1          # evict → shrink
    assert "host2" not in ctx.members
    assert set(ctx.members) == {"host0", "host1", "host3"}
    # post-eviction votes keep passing on the survivors
    assert ctx.sdc_votes > ctx.sdc_disagreements

    # --- the run completes and the loss keeps descending ------------------
    assert opt.optim_method.state["neval"] - 1 == steps, "run must complete"
    losses = tsummary.read_scalar("Loss")
    first = np.mean([v for _, v in losses[:3]])
    last = np.mean([v for _, v in losses[-3:]])
    assert last < first, (first, last)

    # --- IntegritySummary reports the counters ----------------------------
    votes = isummary.read_scalar("IntegrityVotes")
    assert votes and votes[-1][1] == ctx.sdc_votes
    assert [v for _, v in isummary.read_scalar(
        "IntegrityDisagreements")][-1] >= 1
    assert [v for _, v in isummary.read_scalar(
        "IntegrityEvictions")][-1] == 1
    isummary.close()
    tsummary.close()


# ---------------------------------------------------------------------------
# lint: unseeded module-level RNG calls must not creep back in
# ---------------------------------------------------------------------------

_NP_GLOBAL = re.compile(
    r"np\.random\.(rand|randn|randint|random|random_sample|choice|"
    r"shuffle|permutation|uniform|normal|standard_normal|seed)\s*\(")
_STDLIB_GLOBAL = re.compile(
    r"(?<![\w.])random\.(random|randint|randrange|choice|choices|"
    r"shuffle|sample|uniform|gauss|seed)\s*\(")


def test_no_unseeded_module_level_rng_in_package():
    """Every random draw in bigdl_tpu/ must come from utils.rng (the
    checkpointable, set_seed-governed streams) or an explicitly seeded
    local generator — the global numpy/stdlib state is invisible to
    trainState checkpoints, so one call silently breaks bitwise
    resume.  Fails with the offending file:line."""
    pkg = os.path.join(os.path.dirname(__file__), "..", "bigdl_tpu")
    offenders = []
    for dirpath, _dirs, files in os.walk(pkg):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    code = line.split("#", 1)[0]
                    if _NP_GLOBAL.search(code) or \
                            _STDLIB_GLOBAL.search(code):
                        rel = os.path.relpath(path, pkg)
                        offenders.append(
                            f"bigdl_tpu/{rel}:{lineno}: {line.strip()}")
    assert not offenders, (
        "unseeded module-level RNG calls (route through utils.rng — "
        "see docs/determinism.md):\n" + "\n".join(offenders))


# ---------------------------------------------------------------------------
# lint: library code speaks through the structured logger/tracer, not
# stdout, and never configures root logging at import time
# ---------------------------------------------------------------------------

_BARE_PRINT = re.compile(r"(?<![\w.])print\s*\(")
_MODULE_BASICCONFIG = re.compile(r"^logging\.basicConfig\s*\(")


def test_no_print_or_import_time_logging_config_in_library():
    """Library code must use the structured logger/tracer
    (telemetry.slog / telemetry.Tracer — docs/observability.md): a bare
    ``print(`` is invisible to every exporter and unfilterable by the
    embedding application, and a module-level ``logging.basicConfig``
    hijacks the application's logging the moment the package imports.
    ``bigdl_tpu/examples/`` is exempt from the print rule only — they
    are runnable scripts whose stdout IS their interface (several emit
    JSON lines the bench driver consumes).  Fails with file:line."""
    pkg = os.path.join(os.path.dirname(__file__), "..", "bigdl_tpu")
    offenders = []
    for dirpath, _dirs, files in os.walk(pkg):
        rel_dir = os.path.relpath(dirpath, pkg)
        is_example = rel_dir == "examples" or \
            rel_dir.startswith("examples" + os.sep)
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    code = line.split("#", 1)[0]
                    bad = _MODULE_BASICCONFIG.search(code) or (
                        not is_example and _BARE_PRINT.search(code))
                    if bad:
                        rel = os.path.relpath(path, pkg)
                        offenders.append(
                            f"bigdl_tpu/{rel}:{lineno}: {line.strip()}")
    assert not offenders, (
        "bare print( / import-time logging.basicConfig in library code "
        "(use telemetry.slog.get_logger / configure_logging — see "
        "docs/observability.md):\n" + "\n".join(offenders))


# ---------------------------------------------------------------------------
# lint: span categories + trace KV keys come from ONE constant table
# (telemetry.trace_context) — no stringly-typed drift between router,
# server, and tracer
# ---------------------------------------------------------------------------

#: literal category in Tracer.record(name, cat) / Tracer.span(name,
#: cat) / ReplicaTraceSink.record(ctx, name, cat) /
#: InferenceServer._trace(req, name, cat) call sites
_SPAN_CATEGORY_CALLS = (
    re.compile(r"\.(?:record|span)\(\s*f?\"[^\"]+\",\s*\"(\w+)\""),
    re.compile(r"\.(?:record|span)\(\s*[\w.\[\]\"']+,\s*f?\"[^\"]+\","
               r"\s*\"(\w+)\""),
    re.compile(r"_trace\(\s*[\w.\[\]\"']+,\s*f?\"[^\"]+\",\s*"
               r"\"(\w+)\""),
)


def test_span_categories_and_trace_keys_come_from_shared_table():
    """Every literal span category recorded anywhere in bigdl_tpu/
    must be a member of the one shared vocabulary
    (``telemetry.tracer.CATEGORIES``, which appends
    ``telemetry.trace_context.REQUEST_CATEGORIES``), and the trace KV
    key prefix literal ``"trc/"`` may exist ONLY in trace_context.py —
    router, server, and tracer can never drift on either."""
    from bigdl_tpu.telemetry.trace_context import (REQUEST_CATEGORIES,
                                                   TRACE_KV_PREFIX)
    from bigdl_tpu.telemetry.tracer import CATEGORIES, STEP_CATEGORIES

    # the table itself is coherent: one source, no duplicates
    assert set(REQUEST_CATEGORIES) <= set(CATEGORIES)
    assert set(STEP_CATEGORIES).isdisjoint(REQUEST_CATEGORIES)
    assert len(CATEGORIES) == len(set(CATEGORIES))
    assert TRACE_KV_PREFIX == "trc/"

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
                    for pat in _SPAN_CATEGORY_CALLS:
                        for cat in pat.findall(code):
                            if cat not in CATEGORIES:
                                offenders.append(
                                    f"bigdl_tpu/{rel}:{lineno}: "
                                    f"category {cat!r} not in the "
                                    f"shared table: {line.strip()}")
                    if '"trc/' in code and rel != os.path.join(
                            "telemetry", "trace_context.py"):
                        offenders.append(
                            f"bigdl_tpu/{rel}:{lineno}: literal trace "
                            f"KV prefix (use telemetry.trace_context"
                            f".TRACE_KV_PREFIX): {line.strip()}")
    assert not offenders, (
        "stringly-typed span categories / trace keys (the shared "
        "table lives in telemetry/trace_context.py):\n"
        + "\n".join(offenders))
