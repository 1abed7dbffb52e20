"""The whole command, each driver, at a toy size: one last line with
exactly the contract's keys."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT, run_command

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
DEVICE = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.mark.parametrize("cell,e2e", [
    ("tiny_train_1chip", {"train_tokens_per_s_per_chip", "setup_s"}),
    ("tiny_train_dp4", {"train_tokens_per_s_per_chip", "setup_s"}),
    ("tiny_serve_open", {"serve_latency_p50_s", "serve_latency_p95_s",
                         "setup_s"}),   # below the knee: judged on tails
    ("tiny_serve_closed", {"serve_tokens_per_s", "serve_latency_p50_s",
                           "serve_latency_p95_s", "setup_s"}),
    ("tiny_serve_sat", {"serve_tokens_per_s", "serve_latency_p50_s",
                        "serve_latency_p95_s", "setup_s"}),
])
def test_end_to_end_line(overlay, cell, e2e):
    rc, obj, log = run_command(overlay, cell)
    assert rc == 0, log
    assert set(obj) - {"rehearsal"} == KEYS, obj
    assert obj["rehearsal"] is True          # a CPU run says what it is
    assert set(obj["device"]) == DEVICE
    assert obj["device"]["count"] == (4 if cell.endswith("dp4") else 1)
    assert obj["correct"] is True, log
    assert obj["failed"] == 0 and obj["attempted"] > 0
    assert set(obj["metrics"]) == e2e
    for m in obj["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # every number compared beside its limit, as the line's last key
    assert list(obj)[-1] == "checks" and len(obj["checks"]) >= 5
    for c in obj["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]


@pytest.mark.parametrize("cell,some", [
    ("tiny_train_1chip", {"train_step_p50_ms", "train_step_tail_ratio"}),
    ("tiny_serve_open", {"serve_queue_pct", "serve_batch_fill_pct",
                         "serve_generator_late_p95_ms",
                         "serve_unresolved_at_stop"}),
    ("tiny_serve_sat", {"serve_queue_pct", "serve_batch_fill_pct",
                        "serve_batch_form_ms"}),
])
def test_traced_line_reports_per_layer_metrics(overlay, cell, some):
    """On the CPU there is no chip plane to reduce, so only the readers
    of spans and counters find something; the rest are left out."""
    rc, obj, log = run_command(overlay, cell, trace=1)
    assert rc == 0, log
    assert some <= set(obj["metrics"]), obj["metrics"]
    assert "setup_s" not in obj["metrics"]
    with open(os.path.join(overlay, "BENCHMARK.json")) as f:
        names = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(obj["metrics"]) <= names


def test_same_seed_same_inputs(overlay):
    """Weights, batches and prompts come from --seed alone."""
    from benchmark.drivers.train import TokenStream
    from benchmark.reference import common, gpt2

    a = TokenStream(3000000019, 4, 16, 97, 8).ids(5)
    b = TokenStream(3000000019, 4, 16, 97, 8).ids(5)
    c = TokenStream(3000000020, 4, 16, 97, 8).ids(5)
    assert (a[0] == b[0]).all() and not (a[0] == c[0]).all()
    assert len({tuple(r) for r in a[0]}) == 4      # rows all differ
    cfg = {"n_embd": 8, "vocab_size": 11, "n_positions": 4, "n_layer": 2}
    make = lambda seed, stacked: common.make_params(
        gpt2.param_specs(cfg), 2, 0.02, seed, stacked)
    p, q, s = make(2**31 + 5, False), make(2**31 + 5, False), make(2**31 + 5, True)
    assert (p["wte"] == q["wte"]).all()
    assert (s["h.attn.wq"][1] == p["h.1.attn.wq"]).all()
    assert not (make(2**31 + 6, False)["wte"] == p["wte"]).all()


def test_no_accelerator_no_result(overlay):
    """Without --rehearse-cpu the command refuses a machine with no TPU:
    exit code other than 0 and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--manifest", os.path.join(overlay, "BENCHMARK.json"),
         "--workload", "tiny_train_1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=300)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_sweep_tool_reports_the_spread_of_repeated_windows(overlay, tmp_path):
    """``tools/sweep_open.py``: one warmed server, every window offered
    ``--repeat`` times on each ``--schedule-seeds``; the last lines give
    each schedule's run-to-run spread of p50 and p95."""
    out = str(tmp_path / "sweep.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark/tools/sweep_open.py"),
         "--manifest", os.path.join(overlay, "BENCHMARK.json"),
         "--rehearse-cpu", "--workload", "tiny_serve_open", "--rates", "40",
         "--seconds", "0.5", "--schedule-seeds", "7,8", "--repeat", "3",
         "--out", out],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert p.returncode == 0, p.stdout + p.stderr[-3000:]
    with open(out) as f:
        rows = json.load(f)
    assert [r["schedule_seed"] for r in rows[:6]] == [7, 7, 7, 8, 8, 8]
    assert all(r["failed"] == 0 and r["p95_s"] >= r["p50_s"] > 0
               for r in rows[:6])
    assert [(r["schedule_seed"], r["runs"]) for r in rows[6:]] == [(7, 3),
                                                                   (8, 3)]
    assert all(r["p95_s_spread"] >= 0 and len(r["backlog_at_end"]) == 3
               for r in rows[6:])
