"""``correct`` comes out false when the timed path is broken underneath,
and when the reference computed in the next lower precision is put in
the program's place (the control).  These drive ``run.main`` in this
process at a toy size on the CPU: the look for a chip is skipped
(``--rehearse-cpu``), the rest of a run is the real one."""
import json
import os

import numpy as np
import pytest

from conftest import tiny_manifest


def run_main(overlay, cell, capsys, seconds=0.5):
    from benchmark import run

    rc = run.main(["--manifest", os.path.join(overlay, "BENCHMARK.json"),
                   "--rehearse-cpu", "--workload", cell, "--seed",
                   "3000000021", "--seconds", str(seconds), "--trace", "0"])
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return rc, json.loads(lines[-1]), "\n".join(lines)


def test_sound_run_is_correct(overlay, capsys):
    rc, obj, log = run_main(overlay, "tiny_serve_closed", capsys)
    assert rc == 0 and obj["correct"] is True, log


def test_a_token_altered_where_it_is_produced(overlay, capsys, monkeypatch):
    from bigdl_tpu.serving.server import InferenceServer

    real = InferenceServer._run_generate

    def broken(self, params, reqs):
        out, bucket = real(self, params, reqs)
        out = np.array(out)
        out[:, 1] = out[:, 1] % 100 + 1   # one served token of each reply
        return out, bucket

    monkeypatch.setattr(InferenceServer, "_run_generate", broken)
    rc, obj, log = run_main(overlay, "tiny_serve_closed", capsys)
    assert rc == 0 and obj["correct"] is False, log
    assert "widest gap" in log and "FAILED" in log


def test_a_token_altered_in_one_slot_of_a_full_bucket(overlay, capsys,
                                                      monkeypatch):
    """A cache-slot or padding mix-up wrongs one row of a batch, not
    every reply: the sample of requests compared has to be large enough
    to hold some from every slot (here 16 requests on buckets of 2; on
    the chip 96 on buckets of 16 and 48 on buckets of 8)."""
    from bigdl_tpu.serving.server import InferenceServer

    real = InferenceServer._run_generate
    hit = []

    def broken(self, params, reqs):
        out, bucket = real(self, params, reqs)
        out = np.array(out)
        if len(reqs) == bucket > 1:       # the last slot, full buckets only
            out[bucket - 1, 1] = out[bucket - 1, 1] % 100 + 1
            hit.append(bucket)
        return out, bucket

    monkeypatch.setattr(InferenceServer, "_run_generate", broken)
    rc, obj, log = run_main(overlay, "tiny_serve_closed", capsys)
    assert hit and set(hit) == {2}, hit
    assert rc == 0 and obj["correct"] is False, log
    assert "widest gap" in log and "FAILED" in log


def test_a_step_that_returns_its_state_unchanged(overlay, capsys, monkeypatch):
    from bigdl_tpu.optim import Adam

    monkeypatch.setattr(
        Adam, "step", lambda self, grads, params, state, lr: (
            params, dict(state, t=state["t"] + 1)))
    rc, obj, log = run_main(overlay, "tiny_train_1chip", capsys)
    assert rc == 0 and obj["correct"] is False, log
    assert "parameter change after three steps" in log


def test_part_of_the_batch_left_out(overlay, capsys, monkeypatch):
    """Half of every batch replaced by copies of the other half: the
    loss and the gradient the optimizer gets are another batch's."""
    from benchmark.drivers import train

    real = train.TokenStream.data

    def half(self, train):
        for mb in real(self, train):
            x, y = mb.get_input(), mb.get_target()
            n = x.shape[0] // 2
            x[n:], y[n:] = x[:n], y[:n]
            yield mb

    monkeypatch.setattr(train.TokenStream, "data", half)
    rc, obj, log = run_main(overlay, "tiny_train_1chip", capsys)
    assert rc == 0 and obj["correct"] is False, log


def test_control_fp8_reference_fails_serving(overlay):
    """The reference in fp8 in the program's place: the token it puts
    first lies further below the float32 best than the limit allows, on
    each of three seeds."""
    from benchmark import program
    from benchmark.reference import serve_check

    with open(os.path.join(overlay, "benchmark/configs/tiny-mistral.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(overlay, "benchmark/traffic/tiny_closed.json")) as f:
        lim = json.load(f)["limits"]
    ref = program.reference_for(cfg)
    for seed in (11, 12, 3000000013):
        r = np.random.RandomState(seed % 2 ** 32)
        prompts = r.randint(0, cfg["vocab_size"], size=(8, 16))
        served = r.randint(0, cfg["vocab_size"], size=(8, 16))
        out = serve_check.teacher_forced(ref, cfg, seed, prompts, served,
                                         control=True)
        rel = out["control_gap"] / out["spread"]
        assert (rel.max() > lim["served_gap_over_spread"]
                or rel.mean() > lim["served_mean_gap_over_spread"]), (
            seed, rel.max(), rel.mean())


def test_control_fp8_reference_fails_training(overlay):
    """The reference's own three steps in fp8 against the same in
    float32: the first gradient's worst leaf is further off than the
    real cell's limit allows, on each of three seeds."""
    from benchmark import program
    from benchmark.drivers.train import TokenStream, _worst_gap
    from benchmark.reference import train as ref_train

    with open(os.path.join(overlay, "benchmark/configs/tiny-gpt2.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(overlay, "benchmark/traffic/tiny_train_local.json")) as f:
        tr = json.load(f)
    ref = program.reference_for(cfg)
    for seed in (31, 32, 3000000033):
        stream = TokenStream(seed, tr["batch_per_chip"], tr["seq_len"],
                             cfg["vocab_size"], tr["cycle"])
        batches = [stream.ids(i) for i in range(3)]
        runs = {mode: ref_train.run_steps(ref, cfg, seed, batches,
                                          tr["adam"]["lr"], mode=mode)
                for mode in ("f32", "fp8")}
        gap, which = _worst_gap(runs["fp8"]["first_grad_norms"],
                                runs["f32"]["first_grad_norms"])
        assert gap > tr["limits"]["grad_norm_rel"], (seed, gap, which)
