"""The SmallThinker-21BA3B-Instruct configuration's files, at toy size on
the CPU: found by name with no edit to a file that was there, a toy run
of the cell's driver ``correct`` (a prompt of 19 over a window of 8) and
its ``--control 1`` twin not, a program whose router reads the experts'
input or whose ring forgets the window not ``correct``, the
configuration against its published widths, ``counts_smallthinker``
against hand arithmetic, the readers silent where there is nothing to
read and right on a written fragment."""
import json
import os
import types

import pytest

from conftest import ROOT, run_command, tiny_manifest
from test_broken_path import run_main

REAL_CELL, CELL = "smallthinker_serve_window_sat", "tiny_smallthinker_window_sat"
REAL = "benchmark/configs/smallthinker-21b-a3b-l4.json"
TINY = "benchmark/tests/smallthinker/benchmark/configs/tiny-smallthinker.json"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NEW = ("st_decode_step_roofline", "st_expert_matmul_roofline",
       "ring_decode_attend_roofline", "kv_decode_pct",
       "window_prefill_roofline", "prefill_busy_pct")


@pytest.fixture()
def st_overlay(tmp_path):
    dst = str(tmp_path / "overlay")
    m = tiny_manifest(dst, extra=("smallthinker",))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    for group in ("end_to_end", "per_layer"):
        for metric, ours in zip(real[group], m[group]):
            assert (CELL in ours.get("workloads", ())) == (
                REAL_CELL in metric.get("workloads", ())), metric["name"]
    return dst


def _config(name=REAL):
    with open(os.path.join(ROOT, name)) as f:
        return json.load(f)


def test_the_cell_runs_correct_from_files_alone(st_overlay):
    rc, obj, log = run_command(st_overlay, CELL, trace=0)
    assert rc == 0 and obj["correct"], log
    assert obj["failed"] == 0 and obj["attempted"] > 0
    for name in ("serve_tokens_per_s", "serve_latency_p50_s",
                 "serve_latency_p95_s", "setup_s"):
        assert obj["metrics"][name]["value"] > 0, name
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "configs", "tiny-smallthinker.json"))


def test_traced_run_reports_the_counters_it_can_read_on_a_cpu(st_overlay):
    """No device trace on the CPU: the trace readers return nothing and
    do not raise; the counters' readers report."""
    rc, obj, log = run_command(st_overlay, CELL, trace=1)
    assert rc == 0 and obj["correct"], log
    assert obj["metrics"]["serve_batch_fill_pct"]["value"] > 50
    assert obj["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert obj["metrics"]["setup_weight_draw_s"]["value"] > 0
    assert not set(NEW) & set(obj["metrics"])


def test_control_fp8_reference_is_not_correct(st_overlay, capsys):
    for seed in (11, 3000000013):
        rc, obj, log = run_main(st_overlay, CELL, capsys, seed=seed,
                                extra=("--control", "1"))
        assert rc == 0 and obj["correct"] is False, log
        over = {k for k, c in obj["checks"].items()
                if not c["value"] <= c["limit"]}
        assert over and over <= {"served_gap_widest", "served_gap_mean"}
    rc, obj, log = run_main(st_overlay, CELL, capsys, seed=11)
    assert rc == 0 and obj["correct"] is True, log


def test_a_program_whose_router_reads_the_experts_input_is_not_correct(
        st_overlay, capsys, monkeypatch):
    from bigdl_tpu.parallel.moe import DroplessMoE

    real = DroplessMoE.routed
    monkeypatch.setattr(
        DroplessMoE, "routed",
        lambda self, params, x2, batch=None, scores_from=None: real(
            self, params, x2, batch=batch))
    rc, obj, log = run_main(st_overlay, CELL, capsys, seed=11)
    assert rc == 0 and obj["correct"] is False, log


def test_a_prompt_pass_that_forgets_the_window_is_not_correct(
        st_overlay, capsys, monkeypatch):
    """The prompt pass without the window's mask: the last 11 queries
    of a 19-token prompt see keys a window of 8 never shows them, and
    what they wrote rides on through every decode step."""
    import importlib

    F = importlib.import_module("bigdl_tpu.ops.flash_attention")
    real = F.flash_attention
    monkeypatch.setattr(
        F, "flash_attention",
        lambda q, k, v, causal=False, window=None, **kw: real(
            q, k, v, causal=causal, **kw))
    rc, obj, log = run_main(st_overlay, CELL, capsys, seed=11)
    assert rc == 0 and obj["correct"] is False, log


def test_the_configuration_holds_the_published_widths():
    cfg = _config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    assert entry["file"] == REAL and entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "rope_layout", "sliding_window_layout"])
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
        "vocab_size": 151936}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 4
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] == [0, 1, 1, 1]
    assert cfg["rope_layout_text"] == cfg["sliding_window_layout_text"] \
        == "0111"
    assert (cfg["num_experts_held"], cfg["first_expert_held"]) == (64, 0)
    for key in ("rope_pairing", "router_input", "router_gates",
                "secondary_experts", "expert_gate", "initializer_range"):
        assert cfg["assumed"][key], key
    assert "four pipeline stages of 13 layers" in cfg["deployment"]
    kw = cfg["program"]["kwargs"]
    assert cfg["program"]["class"] == \
        "bigdl_tpu.models.latent_moe:PreRoutedMoELM"
    assert (kw["embed_dim"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["expert_dim"], kw["n_experts"], kw["top_k"],
            kw["held"], kw["vocab_size"], kw["window"], kw["rope_layout"],
            kw["window_layout"], kw["rope_theta"], kw["norm_eps"],
            kw["max_len"], kw["param_dtype"]) == (
        2560, 28, 4, 128, 768, 64, 6, [0, 64], 151936, 4096, [0, 1, 1, 1],
        [0, 1, 1, 1], 1500000, 1e-06, 16384, "bfloat16")
    # the toy twin names the same leaves
    tiny = _config(TINY)
    assert tiny["program"]["params"] == cfg["program"]["params"]


def test_the_traffic_is_the_issues():
    with open(os.path.join(ROOT, "benchmark/traffic/"
                           "closed128_p4608_n256_b32.json")) as f:
        tr = json.load(f)
    assert (tr["driver"], tr["clients"], tr["prompt_len"], tr["max_new"],
            tr["max_batch"], tr["max_queue"], tr["generate_dtype"]) == (
        "serve_closed", 128, 4608, 256, 32, 256, "bfloat16")
    assert (tr["prompt_len"] + tr["max_new"]) % 128 == 0     # no dead tail
    assert 0 < tr["limits"]["served_mean_gap_over_spread"] \
        < tr["limits"]["served_gap_over_spread"] < 1


def test_the_reference_and_the_counts_agree_on_the_parameters():
    import numpy as np

    from benchmark import counts_smallthinker as C
    from benchmark.reference import common, smallthinker as ref

    cfg = _config()
    specs = common.flat_specs(ref.param_specs(cfg), ref.n_layers(cfg))
    assert sum(int(np.prod(s)) for s, _ in specs.values()) \
        == C.total_params(cfg) == 2_372_426_240
    with pytest.raises(ValueError, match="sliding_window_layout_text"):
        ref.param_specs(dict(cfg, sliding_window_layout=[1, 1, 1, 1]))


def test_counts_against_hand_arithmetic():
    from benchmark import counts_smallthinker as C

    cfg = _config()
    attn = 2560 * 3584 * 2 + 2560 * 512 * 2
    expert = 3 * 2560 * 768
    assert C.attention_params(cfg) == attn == 20_971_520
    assert C.router_params(cfg) == 64 * 2560 == 163_840
    assert C.expert_params(cfg) == expert == 5_898_240
    assert C.layer_params(cfg) == attn + 163_840 + 64 * expert + 5120 \
        == 398_627_840
    assert C.total_params(cfg) == 4 * 398_627_840 + 2 * 151936 * 2560 + 2560 \
        == 2_372_426_240                       # 4.745 GB in bfloat16
    m = C.dims(cfg)
    assert (m["window_layers"], m["full_layers"], m["window"]) == (3, 1, 4096)
    # one cached position of one layer: K and V, 4 heads of 128, bfloat16
    assert C.kv_position_bytes(cfg) == 2 * 4 * 128 * 2 == 2048
    # the caches of the 32-row bucket as serve.dispatch reports them
    assert 3 * 32 * 4096 * 2048 == 805_306_368
    assert 32 * 4864 * 2048 == 318_767_104
    # a step's attends at the mean context 4736: three whole rings, the
    # global layer's written part
    assert C.decode_attend_bytes(cfg, 32, 4736) \
        == 805_306_368 + 32 * 4736 * 2048 == 1_115_684_864
    # under the window every layer reads what is written
    assert C.decode_attend_bytes(cfg, 32, 1000) == 4 * 32 * 1000 * 2048
    hit = 64 * (1 - (1 - 6 / 64) ** 32)
    assert C.experts_hit(cfg, 32) == pytest.approx(hit)
    assert 61.2 < hit < 61.4
    parts = C.decode_step_parts(cfg, 32, 4736)
    assert parts["attention_weights"] == 4 * attn * 2           # 168 MB
    assert parts["kv_cache"] == 1_115_684_864
    assert parts["experts_hit"] == pytest.approx(4 * hit * expert * 2)
    assert 2.88e9 < parts["experts_hit"] < 2.90e9               # 58 %
    assert parts["routers_and_norms"] == (4 * 163_840 + 9 * 2560) * 2
    assert parts["head"] == 151936 * 2560 * 2 == 777_912_320    # 16 %
    assert parts["embedding_rows"] == 32 * 2560 * 2
    total = C.decode_step_bytes(cfg, 32, 4736)
    assert total == pytest.approx(sum(parts.values()))
    assert 4.93e9 < total < 4.97e9          # 6.0 ms at 819 GB/s
    em = C.expert_matmul_call(cfg, 32)
    assert em["rows"] == 192 and em["flops"] == 6 * 192 * 2560 * 768
    assert em["bytes"] == pytest.approx(
        (hit * expert + 192 * (2 * 2560 + 3 * 768)) * 2, rel=1e-9)
    # the prompt pass: groups of 8 rows; the pairs the masks leave
    assert C.prefill_group_rows(32, 4608) == 8
    assert C.prefill_group_rows(8, 4608) == 8
    assert C.visible_pairs(4608) == 4608 * 4609 // 2 == 10_619_136
    assert C.visible_pairs(4608, 4096) == 4096 * 4097 // 2 + 512 * 4096 \
        == 10_487_808
    assert C.visible_pairs(100, 4096) == 5050
    flops = C.prefill_attention_flops(cfg, 8, 4608)
    assert flops == 4 * 8 * 28 * 128 * (3 * 10_487_808 + 10_619_136)
    assert 19.2e12 < 4 * flops < 19.4e12    # the whole bucket of 32 rows


def _ctx(**kw):
    from benchmark import counts

    base = dict(run={"counters": {"batches": 0}, "shapes": {
        "prompt_len": 4608, "max_new": 256, "max_batch": 32}},
        trace_summary=None, peaks=PEAKS, config=_config(), counts=counts)
    return types.SimpleNamespace(**{**base, **kw})


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """A run without a trace, or a program without the scopes (the
    parent's, under this PR's benchmark files): the six readers leave
    their metric out and do not raise."""
    import importlib

    for name in NEW:
        reader = importlib.import_module(f"benchmark.readers.{name}")
        assert reader.read(_ctx()) is None, name
    # a trace that names scopes, none of them these readers'
    ev = ["%fusion.1 = bf16[8] fusion(%a)", 1000, 500,
          {"scope": "jit(_run)/generate.sample/add"}]
    bare = _ctx(_program_spans={"chip_events": [ev], "window": (0, 10_000)},
                run={"counters": {"batches": 1, "real_rows": 32,
                                  "padded_rows": 0},
                     "shapes": {"prompt_len": 4608, "max_new": 256,
                                "max_batch": 32}},
                trace_summary={"busy_s": 1e-6})
    for name in NEW:
        reader = importlib.import_module(f"benchmark.readers.{name}")
        assert reader.read(bare) is None, name


def _traced_ctx():
    """One batch: a prompt pass of four groups — four flash calls of
    20 ms a group under ``block.attention`` and 600 ms of everything
    else — then one scan of 255 steps of 8 ms; a step holds four layers
    of 0.1 ms of projections and a 0.3 ms attend under
    ``block.attention`` and three 0.35 ms grouped products."""
    step_ns, proj_ns, attend_ns, gmm_ns, flash_ns, rest_ns = (
        8_000_000, 100_000, 300_000, 350_000, 20_000_000, 150_000_000)
    mosaic = 'custom-call(%x), custom_call_target="tpu_custom_call"'
    pre = "jit(_run)/generate.prefill/generate.prefill_group/"
    inside = "jit(_run)/while/body/generate.decode_step/"
    events, t = [], 1000
    for group in range(4):
        for layer in range(4):
            events.append([f"%flash.{layer} = bf16[224,4608,128] " + mosaic,
                           t, flash_ns,
                           {"scope": pre + "block.attention/flash"}])
            t += flash_ns
        events.append(["%fusion.7 = bf16[8,4608,2560] fusion(%q)", t,
                       rest_ns, {"scope": pre + "moe.expert_matmul/dot"}])
        t += rest_ns
    t_scan = t
    events.append(["%while.9 = (s32[]) while(%tuple)", t_scan, 255 * step_ns,
                   {"scope": ""}])
    for step in range(255):
        at = t_scan + step * step_ns
        for layer in range(4):
            events.append(["%fusion.1 = bf16[32,3584] fusion(%q)", at,
                           proj_ns, {"scope": inside + "block.attention/dot"}])
            at += proj_ns
            events.append(["%fusion.2 = f32[32,28,4096] fusion(%q)", at,
                           attend_ns,
                           {"scope": inside + "block.attention/"
                            "attention.decode_attend/dot"}])
            at += attend_ns
            for k in range(3):
                # the v5e's ragged_dot: a custom call without the
                # program's op_name
                events.append([f"%ragged-dot-none.{k} = bf16[192,768] "
                               + mosaic, at, gmm_ns,
                               {"scope": "ragged-dot-none:"}])
                at += gmm_ns
    prefill_s = 4 * (4 * flash_ns + rest_ns) / 1e9
    # the busy union holds the scan's own event: all 8 ms of a step
    busy = prefill_s + 255 * step_ns / 1e9
    return _ctx(_program_spans={"chip_events": events,
                                "window": (0, t_scan + 255 * step_ns + 1)},
                run={"shapes": {"prompt_len": 4608, "max_new": 256,
                                "max_batch": 32},
                     "counters": {"batches": 1, "real_rows": 32,
                                  "padded_rows": 0}},
                trace_summary={"busy_s": busy}), busy, prefill_s


def test_the_six_readers_on_a_written_fragment():
    from benchmark import counts_smallthinker as C
    from benchmark.readers import (kv_decode_pct, prefill_busy_pct,
                                   ring_decode_attend_roofline,
                                   st_decode_step_roofline,
                                   st_expert_matmul_roofline,
                                   window_prefill_roofline)

    (ctx, busy, prefill_s), cfg = _traced_ctx(), _config()
    # the step: 8 ms by the scan's own event over its 255 steps, against
    # 4 x (0.1 + 0.3 + 3 x 0.35) = 5.8 ms of named operations — the LONGER
    want = 100 * C.decode_step_bytes(cfg, 32, 4736) / 819e9 / 8e-3
    assert st_decode_step_roofline.read(ctx) == pytest.approx(want)
    assert 0 < want < 100
    em = C.expert_matmul_call(cfg, 32)
    least = max(em["flops"] / 197e12, em["bytes"] / 819e9)
    assert least == em["bytes"] / 819e9            # memory binds
    got = st_expert_matmul_roofline.read(ctx)
    assert got == pytest.approx(100 * 4 * least / (12 * 0.35e-3))
    assert 0 < got < 100
    got = ring_decode_attend_roofline.read(ctx)
    assert got == pytest.approx(
        100 * 1_115_684_864 / 819e9 / (4 * 0.3e-3))
    assert got > 100    # 0.3 ms a layer is faster than the rings can be read
    assert kv_decode_pct.read(ctx) == pytest.approx(
        100 * 255 * 4 * 0.4e-3 / busy)
    # busy outside the scan: the prompt pass
    assert prefill_busy_pct.read(ctx) == pytest.approx(
        100 * prefill_s / busy)
    flops = C.prefill_attention_flops(cfg, 8, 4608)
    assert window_prefill_roofline.read(ctx) == pytest.approx(
        100 * 4 * flops / 197e12 / (16 * 20e-3))
    assert 0 < window_prefill_roofline.read(ctx) < 100
