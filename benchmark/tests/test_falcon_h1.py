"""The Falcon-H1 configuration's files, at toy size on the CPU: found by
name with no edit to a file that was there, ``build_model`` strict both
ways, a toy run ``correct`` and its ``--control 1`` twin not, the scalar
multiplier keys equal to the published lists, and ``counts_falcon_h1``
against hand arithmetic."""
import json
import os

import pytest

from conftest import ROOT, run_command, tiny_manifest
from test_broken_path import run_main

REAL_CELL, CELL = "falconh1_serve_decode_sat", "tiny_falconh1_sat"


@pytest.fixture()
def h1_overlay(tmp_path):
    """The toy overlay with the hybrid block's own files laid over it
    and its cell in the manifest under every metric the real cell lists
    (``falconh1/BENCHMARK.template.json`` names it as that cell's twin)."""
    dst = str(tmp_path / "overlay")
    m = tiny_manifest(dst, extra=("falconh1",))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    for group in ("end_to_end", "per_layer"):
        for metric, ours in zip(real[group], m[group]):
            assert (CELL in ours.get("workloads", ())) == (
                REAL_CELL in metric.get("workloads", ())), metric["name"]
    return dst


def _config(name="benchmark/configs/falcon-h1-34b-l4v4.json"):
    with open(os.path.join(ROOT, name)) as f:
        return json.load(f)


def test_the_cell_runs_correct_from_files_alone(h1_overlay):
    rc, obj, log = run_command(h1_overlay, CELL, trace=0)
    assert rc == 0 and obj["correct"], log
    assert obj["failed"] == 0 and obj["attempted"] > 0
    for name in ("serve_tokens_per_s", "serve_latency_p50_s",
                 "serve_latency_p95_s", "setup_s"):
        assert obj["metrics"][name]["value"] > 0, name
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "configs", "tiny-falcon-h1.json"))


def test_traced_run_reports_the_counters_it_can_read_on_a_cpu(h1_overlay):
    """No device trace on the CPU: the trace readers return nothing and
    do not raise; the counters' readers report."""
    rc, obj, log = run_command(h1_overlay, CELL, trace=1)
    assert rc == 0 and obj["correct"], log
    assert obj["metrics"]["serve_batch_fill_pct"]["value"] > 50
    assert "ssm_decode_pct" not in obj["metrics"] or \
        0 <= obj["metrics"]["ssm_decode_pct"]["value"] <= 100


def test_control_fp8_reference_is_not_correct(h1_overlay, capsys):
    for seed in (11, 12, 3000000013):
        rc, obj, log = run_main(h1_overlay, CELL, capsys, seed=seed,
                                extra=("--control", "1"))
        assert rc == 0 and obj["correct"] is False, log
        over = {k for k, c in obj["checks"].items()
                if not c["value"] <= c["limit"]}
        assert over and over <= {"served_gap_widest", "served_gap_mean"}
    rc, obj, log = run_main(h1_overlay, CELL, capsys, seed=11)
    assert rc == 0 and obj["correct"] is True, log


def test_one_slot_with_another_rows_state_is_not_correct(h1_overlay, capsys,
                                                         monkeypatch):
    """The recurrent state prefill hands to decode, wronged in the last
    slot of every full bucket of 4."""
    from bigdl_tpu import nn

    real = nn.Mamba2Mixer.sequence

    def broken(self, params, u, state=None):
        out, st = real(self, params, u, state)
        if u.shape[0] == 4:
            st = {k: v.at[3].set(v[0]) for k, v in st.items()}
        return out, st

    monkeypatch.setattr(nn.Mamba2Mixer, "sequence", broken)
    rc, obj, log = run_main(h1_overlay, CELL, capsys)
    assert rc == 0 and obj["correct"] is False, log
    assert "widest gap" in log and "FAILED" in log


@pytest.mark.parametrize("fault", ["left_out", "unknown_to_the_model",
                                   "unknown_to_the_reference"])
def test_build_model_is_strict_both_ways(h1_overlay, fault):
    from benchmark import program

    cfg = _config("benchmark/tests/falconh1/benchmark/configs/"
                  "tiny-falcon-h1.json")
    table = cfg["program"]["params"]["layers"]["hybrid"]
    if fault == "left_out":
        del table["mixer.A_log"]
    elif fault == "unknown_to_the_model":
        table["mixer.extra"] = ["6", "no_such_leaf"]
    else:
        table["attn.bq"] = ["1", "bq"]
    with pytest.raises((ValueError, KeyError)) as err:
        program.build_model(cfg, 3000000023,
                            ref=program.reference_for(cfg, h1_overlay))
    if fault == "left_out":
        assert "only in the reference ['h.0.mixer.A_log'" in str(err.value)


def test_the_configuration_holds_the_published_widths():
    cfg = _config()
    want = {"hidden_size": 5120, "num_attention_heads": 20,
            "num_key_value_heads": 4, "head_dim": 128,
            "intermediate_size": 21504, "mamba_n_heads": 32,
            "mamba_d_head": 128, "mamba_d_state": 256, "mamba_n_groups": 2,
            "mamba_d_conv": 4, "mamba_chunk_size": 128, "mamba_d_ssm": 4096}
    assert {k: cfg[k] for k in want} == want
    assert set(cfg["reduced"]) == {"num_hidden_layers", "vocab_size",
                                   "max_position_embeddings"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert cfg["vocab_size"] == 261120 // 4 == 510 * 128
    kw = cfg["program"]["kwargs"]
    assert (kw["embed_dim"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["mlp_dim"]) == (5120, 20, 4, 128, 21504)
    assert (kw["mamba_heads"], kw["mamba_head_dim"], kw["mamba_d_state"],
            kw["mamba_groups"], kw["mamba_d_conv"], kw["mamba_chunk"]) == (
        32, 128, 256, 2, 4, 128)
    assert (kw["vocab_size"], kw["num_layers"], kw["max_len"]) == (
        cfg["vocab_size"], cfg["num_hidden_layers"],
        cfg["max_position_embeddings"])


@pytest.mark.parametrize("name", [
    "benchmark/configs/falcon-h1-34b-l4v4.json",
    "benchmark/tests/falconh1/benchmark/configs/tiny-falcon-h1.json"])
def test_scalar_multiplier_keys_equal_the_published_lists(name):
    cfg = _config(name)
    assert [cfg[f"ssm_multiplier_{s}"] for s in ("z", "x", "B", "C", "dt")
            ] == cfg["ssm_multipliers"]
    assert [cfg["mlp_gate_multiplier"], cfg["mlp_down_multiplier"]
            ] == cfg["mlp_multipliers"]
    kw = cfg["program"]["kwargs"]
    assert kw["ssm_multipliers"] == cfg["ssm_multipliers"]
    assert kw["mlp_multipliers"] == cfg["mlp_multipliers"]


def test_counts_against_hand_arithmetic():
    from benchmark import counts_falcon_h1 as c

    cfg = _config()
    in_proj = 5120 * 9248                      # 4096 + 4096 + 2*2*256 + 32
    out_proj = 4096 * 5120
    attn = 2 * 5120 * 2560 + 2 * 5120 * 512    # 20 and 4 heads of 128
    mlp = 3 * 5120 * 21504
    assert (in_proj, out_proj, attn, mlp) == (
        47349760, 20971520, 31457280, 330301440)
    assert c.layer_matmul_params(cfg) == in_proj + out_proj + attn + mlp
    small = 2 * 5120 + 5 * 5120 + 3 * 32 + 4096
    assert small == 40032
    assert c.layer_params(cfg) == 430120032
    assert c.total_params(cfg) == 4 * 430120032 + 2 * 65280 * 5120 + 5120 \
        == 2388952448
    weights = 2 * (4 * 430080000 + 65280 * 5120)
    assert weights == 4109107200               # 4.11 GB a step
    s = c.state_bytes(cfg)
    assert s == {"ssm": 32 * 128 * 256 * 4, "conv": 3 * 5120 * 2,
                 "kv_per_position": 2 * 4 * 128 * 2}
    step = c.decode_step_bytes(cfg, 64, 320)
    assert step == weights + 4 * 64 * 320 * 2048 \
        + 4 * 64 * 2 * (4194304 + 30720)
    assert 2.14e9 < 4 * 64 * 2 * 4194304 < 2.16e9    # the state, 64 rows
    scan = c.ssd_scan_call(cfg, 64, 256)
    scores = 2 * 64 * 256 * 128 * 2 * 256 / 2
    apply = 2 * 64 * 256 * 128 * 4096 / 2
    states = 2 * 64 * 256 * 4096 * 256
    assert scan["flops"] == scores + apply + 2 * states
    assert scan["bytes"] == (64 * 256 * (4096 + 1024) * 2 + 64 * 256 * 32 * 4
                             + 64 * 256 * 4096 * 4 + 64 * 4096 * 256 * 4)
    # a ragged tail computes a whole chunk; bytes follow the real length
    assert c.ssd_scan_call(cfg, 64, 130)["flops"] == scan["flops"]
    assert c.ssd_scan_call(cfg, 64, 130)["bytes"] < scan["bytes"]


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """On a program without the scopes (the parent commit) or a run
    without a trace, the three readers leave their metric out and do not
    raise."""
    import importlib

    class Ctx:
        run = {"counters": {"batches": 0}, "shapes": {
            "prompt_len": 256, "max_new": 128, "max_batch": 64}}
        trace_summary = None
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
        config = _config()
        from benchmark import counts

    for name in ("h1_decode_step_roofline", "ssm_decode_pct",
                 "ssd_scan_roofline"):
        reader = importlib.import_module(f"benchmark.readers.{name}")
        assert reader.read(Ctx()) is None, name


def _scope_seconds_pr30(spans, scope):
    """``_program_spans.scope_seconds`` as it was up to PR 30: one pass
    through ``self_times`` for every scope asked."""
    from benchmark import trace_reduce

    lo, hi = spans["window"]
    events = [e for e in spans["chip_events"] if lo <= e[1] < hi]
    if not any(e[3]["scope"] for e in events):
        return None
    mark = scope + "/"
    return sum(self_ns for ev, self_ns, _ in trace_reduce.self_times(events)
               if mark in ev[3]["scope"] + "/") / 1e9


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_one_cached_pass_reads_every_scope_as_a_pass_of_its_own(seed):
    """``_program_spans.scope_seconds`` walks the events once a run and
    sums each scope from the rows it kept: on a line of nested, abutting
    and overlapping (asynchronous) events under a ``while`` whose text
    is kilobytes long it reads, for each scope, the nanoseconds a pass
    of its own read up to PR 30 — and the two readers take their numbers
    from it."""
    import random
    import types

    from benchmark import trace_reduce
    from benchmark.readers import (_program_spans, ssd_scan_roofline,
                                   ssm_decode_pct)

    rnd = random.Random(seed)
    paths = ["jit(_run)/generate.decode_step/mixer.ssm_step/mul",
             "jit(_run)/generate.decode_step/mixer.ssm_step/mixer.conv/add",
             "jit(_run)/generate.prefill/mixer.ssd_scan/dot_general",
             "jit(_run)/generate.decode_step/mixer.ssm_step_other/mul",
             "jit(_run)/generate.decode_step/dot_general", ""]
    operands = ", ".join(f"bf16[64,4,2560,{i}]" for i in range(400))
    events, t = [], 1000
    for b in range(3):
        for _ in range(5):      # prefill: flat, one async pair overlapping
            d = rnd.randint(50, 400)
            events.append([f"%fusion.{len(events)} = f32[8] fusion(%p)", t, d,
                           {"scope": paths[2] if rnd.random() < .5 else ""}])
            t += d + rnd.randint(0, 20)
        start = t
        inner = []
        for _ in range(40):     # the decode loop's body
            d = rnd.randint(10, 200)
            inner.append([f"%fusion.{len(events) + len(inner)} = f32[8] "
                          f"fusion(%q)", t, d, {"scope": rnd.choice(paths)}])
            if rnd.random() < .3:   # an async done reaching past its successor
                inner.append([f"%copy-done.{len(inner)} = f32[8] copy-done(%s)",
                              t + d // 2, d, {"scope": rnd.choice(paths)}])
            t += d + rnd.randint(0, 5)
        events.append([f"%while.{b} = (s32[], {operands}) while(%t), body=%b",
                       start, t - start, {"scope": "jit(_run)/while"}])
        events += inner
        t += 100
    lo, hi = 1200, t - 300     # the window cuts the first and last events
    spans = {"chip_events": events, "window": (lo, hi)}
    ctx = types.SimpleNamespace(
        _program_spans=spans,
        trace_summary={"busy_s": 1e-3, "modules": {
            "jit__run": {"seconds": 1.0, "count": 3}}},
        run={"shapes": {"prompt_len": 256, "max_new": 128, "max_batch": 64}},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        config=_config())
    from benchmark import counts
    ctx.counts = counts
    for scope in ("mixer.ssm_step", "mixer.ssd_scan", "generate.decode_step",
                  "generate.sample"):
        want = _scope_seconds_pr30(
            {"chip_events": events, "window": (lo, hi)}, scope)
        assert _program_spans.scope_seconds(ctx, scope) == want
        assert (want > 0) == (scope != "generate.sample")
    assert len(spans["scope_self_ns"]) == sum(lo <= e[1] < hi for e in events)
    assert ssm_decode_pct.read(ctx) == pytest.approx(
        100.0 * _program_spans.scope_seconds(ctx, "mixer.ssm_step") / 1e-3)
    assert ssd_scan_roofline.read(ctx) > 0
    # the memoised parsers say what the bare ones say
    for ev in events:
        assert trace_reduce.base_name(ev[0]) == \
            trace_reduce.base_name.__wrapped__(ev[0])
        assert trace_reduce.split_hlo(ev[0]) == \
            trace_reduce.split_hlo.__wrapped__(ev[0])
    # no event names a scope at all -> nothing to read
    bare = types.SimpleNamespace(_program_spans={
        "chip_events": [[e[0], e[1], e[2], {"scope": ""}] for e in events],
        "window": (lo, hi)})
    assert _program_spans.scope_seconds(bare, "mixer.ssm_step") is None
    assert _program_spans.scope_seconds(bare, "mixer.ssd_scan") is None
