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


def run_main(overlay, cell, capsys, seconds=0.5, seed=3000000021, extra=()):
    from benchmark import run

    rc = run.main(["--manifest", os.path.join(overlay, "BENCHMARK.json"),
                   "--rehearse-cpu", "--workload", cell, "--seed",
                   str(seed), "--seconds", str(seconds), "--trace", "0",
                   *extra])
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


@pytest.mark.parametrize("cell,full", [("tiny_serve_closed", 2),
                                       ("tiny_serve_sat", 4)])
def test_a_token_altered_in_one_slot_of_a_full_bucket(overlay, capsys,
                                                      monkeypatch, cell,
                                                      full):
    """A cache-slot or padding mix-up wrongs one row of a batch, not
    every reply: the sample of requests compared has to be large enough
    to hold some from every slot (here 16 requests on buckets of 2 or
    4; on the chip 96 on buckets of 16 and 48 on buckets of 8)."""
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
    rc, obj, log = run_main(overlay, cell, capsys)
    assert hit and max(hit) == full, hit   # smaller ones: the warm-up's
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


@pytest.mark.parametrize("cell,failing", [
    ("tiny_serve_closed", {"served_gap_widest", "served_gap_mean"}),
    ("tiny_serve_sat", {"served_gap_widest", "served_gap_mean"}),
    ("tiny_train_1chip", {"first_grad_worst_leaf"})])
def test_control_fp8_reference_is_not_correct(overlay, capsys, cell, failing):
    """``--control 1`` puts the reference in fp8 in the program's place
    and ITS readings through the harness's own comparison: the result
    line reads ``correct`` false on each of three seeds, with one of the
    cell's numbers over its limit, where the same seed without the
    control is correct."""
    for seed in (11, 12, 3000000013):
        rc, obj, log = run_main(overlay, cell, capsys, seed=seed,
                                extra=("--control", "1"))
        assert rc == 0 and obj["correct"] is False, log
        over = {k for k, c in obj["checks"].items()
                if not c["value"] <= c["limit"]}
        assert over and over <= failing | {"loss_step1_rel", "loss_step2_rel",
                                           "loss_step3_rel",
                                           "param_change_worst_leaf"}, over
        assert over & failing, over
        assert "[program] " in log and "[control] " in log
    rc, obj, log = run_main(overlay, cell, capsys, seed=11)
    assert rc == 0 and obj["correct"] is True, log
