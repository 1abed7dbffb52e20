"""The GLM-4.7-Flash configuration's files, at toy size on the CPU:
found by name with no edit to a file that was there, ``build_model``
strict both ways over a dense layer and expert layers, a toy run
``correct`` and its ``--control 1`` twin not, the selection bias zeroed
in the program not ``correct``, the configuration against its published
widths, ``counts_glm4_moe_lite`` against hand arithmetic, the readers
silent where there is nothing to read and right on a written fragment."""
import json
import os
import types

import pytest

from conftest import ROOT, run_command, tiny_manifest
from test_broken_path import run_main

REAL_CELL, CELL = "glm47flash_serve_decode_sat", "tiny_glm47flash_sat"
REAL = "benchmark/configs/glm-4.7-flash-l5e16.json"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NEW = ("mla_decode_step_roofline", "mla_decode_attend_roofline",
       "mla_decode_pct", "glm_expert_matmul_roofline")


@pytest.fixture()
def glm_overlay(tmp_path):
    dst = str(tmp_path / "overlay")
    m = tiny_manifest(dst, extra=("glm47flash",))
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


def test_the_cell_runs_correct_from_files_alone(glm_overlay):
    rc, obj, log = run_command(glm_overlay, CELL, trace=0)
    assert rc == 0 and obj["correct"], log
    assert obj["failed"] == 0 and obj["attempted"] > 0
    for name in ("serve_tokens_per_s", "serve_latency_p50_s",
                 "serve_latency_p95_s", "setup_s"):
        assert obj["metrics"][name]["value"] > 0, name
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "configs", "tiny-glm-4.7-flash.json"))


def test_traced_run_reports_the_counters_it_can_read_on_a_cpu(glm_overlay):
    """No device trace on the CPU: the trace readers return nothing and
    do not raise; the counters' readers report."""
    rc, obj, log = run_command(glm_overlay, CELL, trace=1)
    assert rc == 0 and obj["correct"], log
    assert obj["metrics"]["serve_batch_fill_pct"]["value"] > 50
    assert obj["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert not set(NEW) & set(obj["metrics"])


def test_control_fp8_reference_is_not_correct(glm_overlay, capsys):
    for seed in (11, 3000000013):
        rc, obj, log = run_main(glm_overlay, CELL, capsys, seed=seed,
                                extra=("--control", "1"))
        assert rc == 0 and obj["correct"] is False, log
        over = {k for k, c in obj["checks"].items()
                if not c["value"] <= c["limit"]}
        assert over and over <= {"served_gap_widest", "served_gap_mean"}
    rc, obj, log = run_main(glm_overlay, CELL, capsys, seed=11)
    assert rc == 0 and obj["correct"] is True, log


def test_a_program_that_forgets_the_bias_is_not_correct(glm_overlay, capsys,
                                                        monkeypatch):
    """The selection made on the unbiased scores: other experts for most
    tokens, each a whole expert's part of the output."""
    from bigdl_tpu.parallel import moe

    real = moe.route_top_k
    monkeypatch.setattr(
        moe, "route_top_k",
        lambda x2, w, b, k, scoring="softmax", renormalize=True,
        select_bias=None, gate_scale=1.0: real(
            x2, w, b, k, scoring, renormalize, None, gate_scale))
    rc, obj, log = run_main(glm_overlay, CELL, capsys, seed=11)
    assert rc == 0 and obj["correct"] is False, log


@pytest.mark.parametrize("fault", ["missing", "extra"])
def test_build_model_is_strict_both_ways(glm_overlay, fault):
    from benchmark import program

    cfg = _config("benchmark/tests/glm47flash/benchmark/configs/"
                  "tiny-glm-4.7-flash.json")
    table = cfg["program"]["params"]
    if fault == "missing":
        del table["layers"]["moe"]["moe.bias"]
    else:
        table["top"]["dense.0.mlp.gate2"] = ["1", "3", "w_gate"]
    with pytest.raises(ValueError, match="disagree on the parameter tree"):
        program.build_model(cfg, 7)


def test_the_configuration_holds_the_published_widths():
    cfg = _config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    assert entry["file"] == REAL and entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "n_routed_experts", "num_nextn_predict_layers",
        "max_position_embeddings"])
    published = {"hidden_size": 2048, "num_attention_heads": 20,
                 "q_lora_rank": 768, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
                 "v_head_dim": 256, "intermediate_size": 10240,
                 "moe_intermediate_size": 1536, "num_experts_per_tok": 4,
                 "routed_scaling_factor": 1.8, "vocab_size": 154880,
                 "n_shared_experts": 1, "first_k_dense_replace": 1,
                 "rms_norm_eps": 1e-05, "rope_theta": 1000000,
                 "topk_method": "noaux_tc", "norm_topk_prob": True,
                 "n_group": 1, "topk_group": 1,
                 "tie_word_embeddings": False}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["n_routed_experts_published"], cfg["router_outputs"],
            cfg["n_routed_experts"], cfg["first_expert_held"]) == (64, 64,
                                                                   16, 0)
    assert cfg["num_hidden_layers"] == 5 and cfg["num_expert_layers"] == 4
    kw = cfg["program"]["kwargs"]
    assert cfg["program"]["kinds"] == ["dense"] + ["moe"] * 4
    assert (kw["embed_dim"], kw["num_heads"], kw["q_rank"], kw["kv_rank"],
            kw["nope_dim"], kw["rope_dim"], kw["v_dim"], kw["mlp_dim"],
            kw["expert_dim"], kw["n_experts"], kw["top_k"], kw["held"],
            kw["routed_scale"], kw["vocab_size"], kw["first_dense"],
            kw["num_layers"], kw["param_dtype"]) == (
        2048, 20, 768, 512, 192, 64, 256, 10240, 1536, 64, 4, [0, 16], 1.8,
        154880, 1, 5, "bfloat16")


def test_the_reference_and_the_counts_agree_on_the_parameters():
    import numpy as np

    from benchmark import counts_glm4_moe_lite as C
    from benchmark.reference import common, glm4_moe_lite as ref

    cfg = _config()
    specs = common.flat_specs(ref.param_specs(cfg), ref.n_layers(cfg))
    assert sum(int(np.prod(s)) for s, _ in specs.values()) \
        == C.total_params(cfg) == 1_448_374_784


def test_counts_against_hand_arithmetic():
    from benchmark import counts_glm4_moe_lite as C

    cfg = _config()
    attn = (2048 * 768 + 768 + 768 * 5120 + 2048 * 576 + 512 + 512 * 8960
            + 5120 * 2048)
    expert = 3 * 2048 * 1536
    assert C.attention_params(cfg) == attn == 21_759_232
    assert C.expert_params(cfg) == expert == 9_437_184
    assert C.router_params(cfg) == 64 * 2048 + 64 == 131_136
    assert C.expert_layer_params(cfg) == attn + 131_136 + 4096 + 17 * expert
    assert C.dense_layer_params(cfg) == attn + 4096 + 3 * 2048 * 10240
    assert C.total_params(cfg) == 1_448_374_784
    # the step of the full bucket against the WHOLE 640-position cache:
    # ISSUE 36's 3.36 GB, by part
    parts = C.decode_step_parts(cfg, 256, 640)
    assert parts["latent_cache"] == 5 * 256 * 640 * 576 * 2    # 943.7 MB
    assert parts["latent_weights"] == 5 * attn * 2
    assert abs(parts["experts_hit"] - 4 * 16 * expert * 2) < 1e3   # all hit
    assert parts["head"] == 154880 * 2048 * 2
    assert parts["logits"] == 256 * 154880 * 4
    total = C.decode_step_bytes(cfg, 256, 640)
    assert abs(total - 3.3647e9) < 1e6
    latent = parts["latent_cache"] + parts["latent_weights"]
    assert abs(latent / total - 0.345) < 0.002
    assert abs(parts["head"] / total - 0.1885) < 0.002
    # at the mean context of a step (128 + 512 / 2) the cache is 3/5 of it
    assert C.decode_step_bytes(cfg, 256, 384) == pytest.approx(
        total - 0.4 * parts["latent_cache"])
    # per-head K and V would be 17.8 times the latent cache
    assert 20 * (256 + 256) * 2 / C.latent_position_bytes(cfg) \
        == pytest.approx(17.78, abs=0.01)
    call = C.attend_call(cfg, 256, 640)
    assert call["flops"] == 2 * 256 * 20 * 640 * (576 + 512)    # 7.1 GFLOP
    assert call["bytes"] == (256 * 640 * 576 + 256 * 20 * 1088) * 2
    # what absorbing saves: expanding the cache costs 9.2 MFLOP a
    # position, 1.5 TFLOP a layer-step (7.6 ms at the MXU's peak; ISSUE
    # 36 wrote PFLOP); the absorbed products 2.3 GFLOP
    assert C.expand_flops_per_position(cfg) == 2 * 512 * 8960
    assert C.expand_flops_per_position(cfg) * 256 * 640 > 1.5e12
    assert C.absorb_flops(cfg, 256) == 2 * 256 * 20 * 512 * 448
    em = C.expert_matmul_call(cfg, 256)
    assert em["rows"] == 256 and em["flops"] == 6 * 256 * 2048 * 1536


def _ctx(**kw):
    from benchmark import counts

    base = dict(run={"counters": {"batches": 0}, "shapes": {
        "prompt_len": 128, "max_new": 512, "max_batch": 256}},
        trace_summary=None, peaks=PEAKS, config=_config(), counts=counts)
    return types.SimpleNamespace(**{**base, **kw})


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """A run without a trace, or a program without the scopes: the four
    readers leave their metric out and do not raise."""
    import importlib

    for name in NEW:
        reader = importlib.import_module(f"benchmark.readers.{name}")
        assert reader.read(_ctx()) is None, name
    # a trace of a program that names no ``mla.*`` scope
    ev = ["%fusion.1 = bf16[8] fusion(%a)", 1000, 500,
          {"scope": "jit(_run)/while/body/generate.decode_step/add"}]
    bare = _ctx(_program_spans={"chip_events": [ev], "window": (0, 10_000)},
                run={"counters": {"batches": 1, "real_rows": 256,
                                  "padded_rows": 0},
                     "shapes": {"prompt_len": 128, "max_new": 512,
                                "max_batch": 256}},
                trace_summary={"busy_s": 1e-6})
    for name in NEW:
        reader = importlib.import_module(f"benchmark.readers.{name}")
        assert reader.read(bare) is None, name


def _traced_ctx():
    """One scan of 511 steps of 7 ms: a step holds five layers of 0.4 ms
    under ``mla.attend`` and 0.3 ms of other attention work, and four
    expert layers of three 0.25 ms grouped products."""
    step_ns, attend_ns, other_ns, gmm_ns = 7_000_000, 400_000, 300_000, 250_000
    mosaic = ('bf16[1024,1536] custom-call(%x), '
              'custom_call_target="tpu_custom_call"')
    inside = "jit(_run)/while/body/generate.decode_step/"
    events, t = [], 1000
    events.append(["%while.9 = (s32[]) while(%tuple)", t, 511 * step_ns,
                   {"scope": ""}])
    for step in range(511):
        at = t + step * step_ns
        for layer in range(5):
            events.append([f"%fusion.{layer} = f32[256,20,640] fusion(%q)",
                           at, attend_ns, {"scope": inside +
                                           "block.attention/mla.attend/dot"}])
            at += attend_ns
            events.append([f"%fusion.1{layer} = bf16[256,768] fusion(%q)",
                           at, other_ns, {"scope": inside +
                                          "block.attention/mla.q_proj/dot"}])
            at += other_ns
            if layer:
                for k in range(3):
                    events.append([f"%gmm.{3 * layer + k} = " + mosaic, at,
                                   gmm_ns, {"scope": inside +
                                            "moe.expert_matmul/gmm"}])
                    at += gmm_ns
    busy = 511 * (5 * (attend_ns + other_ns) + 12 * gmm_ns) / 1e9
    return _ctx(_program_spans={"chip_events": events,
                                "window": (0, t + 511 * step_ns + 1)},
                run={"shapes": {"prompt_len": 128, "max_new": 512,
                                "max_batch": 256},
                     "counters": {"batches": 1, "real_rows": 256,
                                  "padded_rows": 0}},
                trace_summary={"busy_s": busy})


def test_the_four_readers_on_a_written_fragment():
    from benchmark import counts_glm4_moe_lite as C
    from benchmark.readers import (glm_expert_matmul_roofline,
                                   mla_decode_attend_roofline,
                                   mla_decode_pct, mla_decode_step_roofline)

    ctx, cfg = _traced_ctx(), _config()
    # the step: 7 ms by the scan's own event over its 511 steps, against
    # 5 x 0.7 + 12 x 0.25 = 6.5 ms of named operations — the LONGER holds
    busy_step = 5 * 0.7e-3 + 12 * 0.25e-3
    want = 100 * C.decode_step_bytes(cfg, 256, 384) / 819e9 / 7e-3
    assert mla_decode_step_roofline.read(ctx) == pytest.approx(want)
    assert 0 < want < 100
    call = C.attend_call(cfg, 256, 384)
    least = max(call["flops"] / 197e12, call["bytes"] / 819e9)
    assert least == call["bytes"] / 819e9          # memory binds
    assert mla_decode_attend_roofline.read(ctx) == pytest.approx(
        100 * 5 * least / (5 * 0.4e-3))
    assert mla_decode_pct.read(ctx) == pytest.approx(
        100 * 5 * 0.7e-3 / busy_step)
    em = C.expert_matmul_call(cfg, 256)
    least = max(em["flops"] / 197e12, em["bytes"] / 819e9)
    assert glm_expert_matmul_roofline.read(ctx) == pytest.approx(
        100 * 4 * least / (12 * 0.25e-3))
