"""The LFM2-24B-A2B configuration's files, at toy size on the CPU: found
by name with no edit to a file that was there, ``build_model`` strict
both ways over a dense conv layer, an attention layer and conv expert
layers and holding the stated dtypes, a toy run ``correct`` and its
``--control 1`` twin not, a program that loses its convolution tail or
forgets the per-head norms not ``correct``, the configuration against
its published widths, ``counts_lfm2_moe`` against hand arithmetic, the
readers silent where there is nothing to read and right on a written
fragment."""
import copy
import json
import os
import types

import pytest

from conftest import ROOT, run_command, tiny_manifest
from test_broken_path import run_main

REAL_CELL, CELL = "lfm2moe_serve_decode_sat", "tiny_lfm2moe_sat"
REAL = "benchmark/configs/lfm2-24b-a2b-l5.json"
TINY = "benchmark/tests/lfm2moe/benchmark/configs/tiny-lfm2-moe.json"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NEW = ("lfm2_decode_step_roofline", "conv_decode_pct",
       "lfm2_expert_matmul_roofline")


@pytest.fixture()
def lfm2_overlay(tmp_path):
    dst = str(tmp_path / "overlay")
    m = tiny_manifest(dst, extra=("lfm2moe",))
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


def test_the_cell_runs_correct_from_files_alone(lfm2_overlay):
    rc, obj, log = run_command(lfm2_overlay, CELL, trace=0)
    assert rc == 0 and obj["correct"], log
    assert obj["failed"] == 0 and obj["attempted"] > 0
    for name in ("serve_tokens_per_s", "serve_latency_p50_s",
                 "serve_latency_p95_s", "setup_s"):
        assert obj["metrics"][name]["value"] > 0, name
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "configs", "tiny-lfm2-moe.json"))


def test_traced_run_reports_the_counters_it_can_read_on_a_cpu(lfm2_overlay):
    """No device trace on the CPU: the trace readers return nothing and
    do not raise; the counters' readers report."""
    rc, obj, log = run_command(lfm2_overlay, CELL, trace=1)
    assert rc == 0 and obj["correct"], log
    assert obj["metrics"]["serve_batch_fill_pct"]["value"] > 50
    assert obj["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert obj["metrics"]["setup_weight_draw_s"]["value"] > 0
    assert not set(NEW) & set(obj["metrics"])


def test_control_fp8_reference_is_not_correct(lfm2_overlay, capsys):
    for seed in (11, 3000000013):
        rc, obj, log = run_main(lfm2_overlay, CELL, capsys, seed=seed,
                                extra=("--control", "1"))
        assert rc == 0 and obj["correct"] is False, log
        over = {k for k, c in obj["checks"].items()
                if not c["value"] <= c["limit"]}
        assert over and over <= {"served_gap_widest", "served_gap_mean"}
    rc, obj, log = run_main(lfm2_overlay, CELL, capsys, seed=11)
    assert rc == 0 and obj["correct"] is True, log


def test_a_program_that_loses_its_tail_is_not_correct(lfm2_overlay, capsys,
                                                      monkeypatch):
    """Every decode step convolving over zeros instead of the two gated
    values before it: with the benchmark's box filter two thirds of
    every conv layer's output."""
    import jax.numpy as jnp

    from bigdl_tpu.nn.short_conv import GatedShortConv

    real = GatedShortConv.step
    monkeypatch.setattr(
        GatedShortConv, "step", lambda self, params, x, state: real(
            self, params, x, {"conv": jnp.zeros_like(state["conv"])}))
    rc, obj, log = run_main(lfm2_overlay, CELL, capsys, seed=11)
    assert rc == 0 and obj["correct"] is False, log


def test_a_program_that_forgets_the_head_norms_is_not_correct(
        lfm2_overlay, capsys, monkeypatch):
    from bigdl_tpu.nn.attention import MultiHeadAttention

    monkeypatch.setattr(MultiHeadAttention, "normed_heads",
                        lambda self, params, q, k: (q, k))
    rc, obj, log = run_main(lfm2_overlay, CELL, capsys, seed=11)
    assert rc == 0 and obj["correct"] is False, log


@pytest.mark.parametrize("fault", ["missing", "extra"])
def test_build_model_is_strict_both_ways(fault):
    from benchmark import program

    cfg = _config(TINY)
    table = cfg["program"]["params"]
    if fault == "missing":
        del table["top"]["attn.0.attn.k_norm"]
    else:
        table["layers"]["conv_moe"]["conv.bias"] = ["1", "conv"]
    with pytest.raises(ValueError, match="disagree on the parameter tree"):
        program.build_model(cfg, 7)


def test_build_model_holds_the_stated_dtypes():
    import jax
    import jax.numpy as jnp

    from benchmark import program

    cfg = copy.deepcopy(_config(TINY))
    cfg["program"]["kwargs"]["param_dtype"] = "bfloat16"
    tree = program.build_model(cfg, 7).param_tree()
    assert sorted(tree) == [str(i) for i in range(7)]   # no head's entry
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        want = jnp.float32 if path[-1].key == "score_bias" else jnp.bfloat16
        assert leaf.dtype == want, path
    assert "score_bias" in tree["2"]["3"] and "conv" in tree["1"]["1"]
    assert "k" not in tree["1"]["1"] and "wq" in tree["2"]["1"]
    # the real file states the same for every leaf it names
    real = _config()
    assert real["program"]["kwargs"]["param_dtype"] == "bfloat16"
    assert (real["program"]["params"]["layers"]
            == cfg["program"]["params"]["layers"])
    assert (real["program"]["params"]["top"]
            == cfg["program"]["params"]["top"])


def test_the_configuration_holds_the_published_widths():
    cfg = _config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    assert entry["file"] == REAL and entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "max_position_embeddings"])
    published = {"hidden_size": 2048, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "intermediate_size": 11776,
                 "moe_intermediate_size": 1536, "num_experts": 64,
                 "num_experts_per_tok": 4, "conv_L_cache": 3,
                 "conv_bias": False, "vocab_size": 65536, "norm_eps": 1e-05,
                 "norm_topk_prob": True, "routed_scaling_factor": 1,
                 "use_expert_bias": True, "model_type": "lfm2_moe",
                 "rope_parameters": {"rope_theta": 1000000,
                                     "rope_type": "default"}}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_theta"] == cfg["rope_parameters"]["rope_theta"]
    assert (cfg["num_experts_held"], cfg["first_expert_held"]) == (64, 0)
    assert cfg["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                  "conv"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_attention_expert_layers"],
            cfg["num_conv_expert_layers"]) == (5, 1, 1, 3)
    for key in ("tied_embedding", "renorm_eps", "rope_pairing", "conv_filter",
                "expert_bias", "initializer_range", "layers_in_embed"):
        assert cfg["assumed"][key], key
    assert "v5e-8" in cfg["deployment"]
    kw = cfg["program"]["kwargs"]
    assert cfg["program"]["class"] == \
        "bigdl_tpu.models.latent_moe:ShortConvMoELM"
    assert (kw["embed_dim"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["mlp_dim"], kw["expert_dim"], kw["n_experts"],
            kw["top_k"], kw["held"], kw["routed_scale"], kw["renorm_eps"],
            kw["vocab_size"], kw["first_dense"], kw["conv_kernel"],
            kw["layer_types"], kw["rope_theta"], kw["param_dtype"]) == (
        2048, 32, 8, 64, 11776, 1536, 64, 4, [0, 64], 1.0, 1e-06, 65536, 1,
        3, cfg["layer_types"], 1000000, "bfloat16")


def test_the_reference_and_the_counts_agree_on_the_parameters():
    import numpy as np

    from benchmark import counts_lfm2_moe as C
    from benchmark.reference import common, lfm2_moe as ref

    cfg = _config()
    specs = common.flat_specs(ref.param_specs(cfg), ref.n_layers(cfg))
    assert sum(int(np.prod(s)) for s, _ in specs.values()) \
        == C.total_params(cfg) == 2_700_654_976
    # the reference serves the stack the configuration states, no other
    with pytest.raises(ValueError, match="not the stack"):
        ref.param_specs(dict(cfg, layer_types=["full_attention"] + ["conv"] * 4))


def test_counts_against_hand_arithmetic():
    from benchmark import counts_lfm2_moe as C

    cfg = _config()
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    expert = 3 * 2048 * 1536
    assert C.conv_params(cfg) == conv == 16_783_360
    assert C.attention_params(cfg) == attn == 10_485_888
    assert C.expert_params(cfg) == expert == 9_437_184
    assert C.router_params(cfg) == 64 * 2048 + 64 == 131_136
    assert C.layer_params(cfg, 0) == conv + 4096 + 3 * 2048 * 11776 \
        == 89_139_200
    assert C.layer_params(cfg, 1) == attn + 4096 + 131_136 + 64 * expert \
        == 614_600_896
    assert C.layer_params(cfg, 2) == C.layer_params(cfg, 4) \
        == conv + 4096 + 131_136 + 64 * expert == 620_898_368
    assert C.total_params(cfg) == 2_700_654_976      # 5.40 GB in bfloat16
    # the step of the full bucket at its mean context (128 + 256 / 2):
    # ISSUE 40's 5.55 GB, by part
    parts = C.decode_step_parts(cfg, 256, 256)
    assert parts["conv_weights"] == 4 * conv * 2                # 134 MB
    assert parts["conv_tails"] == 2 * 4 * 256 * 2 * 2048 * 2    # 16.8 MB
    assert C.tail_bytes(cfg, 256) == 256 * 4 * 2 * 2048 * 2     # 8.4 MB held
    assert parts["kv_cache"] == 256 * 256 * 2 * 8 * 64 * 2      # 134 MB
    assert parts["attention_weights"] == attn * 2
    assert abs(parts["experts_hit"] - 4 * 64 * expert * 2) < 1e3    # all hit
    assert parts["dense_ffn"] == 3 * 2048 * 11776 * 2
    assert parts["head"] == 65536 * 2048 * 2
    assert parts["embedding_rows"] == 256 * 2048 * 2
    assert "logits" not in parts
    total = C.decode_step_bytes(cfg, 256, 256)
    assert abs(total - 5.5534e9) < 1e6
    assert abs(total / 819e9 - 6.78e-3) < 1e-5
    assert abs(parts["experts_hit"] / total - 0.870) < 0.002
    # the whole 384-position cache is half as much K/V again
    assert C.decode_step_bytes(cfg, 256, 384) == pytest.approx(
        total + 0.5 * parts["kv_cache"])
    # per-head K/V in all five layers would be 1.0 GB at 384 positions;
    # the one layer that keeps it holds 201 MB
    assert 256 * 384 * C.kv_position_bytes(cfg) == 201_326_592
    # one token an expert on the mean leaves a third of them unhit
    assert C.experts_hit(cfg, 16) == pytest.approx(64 * (1 - (15 / 16) ** 16))
    em = C.expert_matmul_call(cfg, 256)
    assert em["rows"] == 1024 and em["flops"] == 6 * 1024 * 2048 * 1536
    assert em["bytes"] == pytest.approx(
        (64 * expert + 1024 * (2 * 2048 + 3 * 1536)) * 2, rel=1e-6)


def _ctx(**kw):
    from benchmark import counts

    base = dict(run={"counters": {"batches": 0}, "shapes": {
        "prompt_len": 128, "max_new": 256, "max_batch": 256}},
        trace_summary=None, peaks=PEAKS, config=_config(), counts=counts)
    return types.SimpleNamespace(**{**base, **kw})


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """A run without a trace, or a program without the scopes: the three
    readers leave their metric out and do not raise."""
    import importlib

    for name in NEW:
        reader = importlib.import_module(f"benchmark.readers.{name}")
        assert reader.read(_ctx()) is None, name
    # a trace of a program that names no ``block.conv`` scope and runs
    # no grouped product
    ev = ["%fusion.1 = bf16[8] fusion(%a)", 1000, 500,
          {"scope": "jit(_run)/while/body/generate.decode_step/add"}]
    bare = _ctx(_program_spans={"chip_events": [ev], "window": (0, 10_000)},
                run={"counters": {"batches": 1, "real_rows": 256,
                                  "padded_rows": 0},
                     "shapes": {"prompt_len": 128, "max_new": 256,
                                "max_batch": 256}},
                trace_summary={"busy_s": 1e-6})
    for name in ("conv_decode_pct", "lfm2_expert_matmul_roofline"):
        reader = importlib.import_module(f"benchmark.readers.{name}")
        assert reader.read(bare) is None, name


def _traced_ctx():
    """One scan of 255 steps of 10 ms: a step holds four conv layers of
    0.1 ms under ``conv.in_proj`` and 0.05 ms under ``conv.short``, one
    attention layer of 0.4 ms, and four expert layers of three 0.6 ms
    grouped products."""
    step_ns, proj_ns, short_ns, attn_ns, gmm_ns = (
        10_000_000, 100_000, 50_000, 400_000, 600_000)
    mosaic = ('bf16[1024,1536] custom-call(%x), '
              'custom_call_target="tpu_custom_call"')
    inside = "jit(_run)/while/body/generate.decode_step/"
    events, t = [], 1000
    events.append(["%while.9 = (s32[]) while(%tuple)", t, 255 * step_ns,
                   {"scope": ""}])
    for step in range(255):
        at = t + step * step_ns
        for layer, kind in enumerate(["conv", "attn", "conv", "conv",
                                      "conv"]):
            if kind == "conv":
                for name, ns in (("conv.in_proj/dot", proj_ns),
                                 ("conv.short/mul", short_ns)):
                    events.append([f"%fusion.{layer} = bf16[256,6144] "
                                   "fusion(%q)", at, ns,
                                   {"scope": inside + "block.conv/" + name}])
                    at += ns
            else:
                events.append(["%fusion.9 = f32[256,32,384] fusion(%q)", at,
                               attn_ns, {"scope": inside +
                                         "block.attention/dot"}])
                at += attn_ns
            if layer:
                for k in range(3):
                    events.append([f"%gmm.{3 * layer + k} = " + mosaic, at,
                                   gmm_ns, {"scope": inside +
                                            "moe.expert_matmul/gmm"}])
                    at += gmm_ns
    busy = 255 * (4 * (proj_ns + short_ns) + attn_ns + 12 * gmm_ns) / 1e9
    return _ctx(_program_spans={"chip_events": events,
                                "window": (0, t + 255 * step_ns + 1)},
                run={"shapes": {"prompt_len": 128, "max_new": 256,
                                "max_batch": 256},
                     "counters": {"batches": 1, "real_rows": 256,
                                  "padded_rows": 0}},
                trace_summary={"busy_s": busy})


def test_the_three_readers_on_a_written_fragment():
    from benchmark import counts_lfm2_moe as C
    from benchmark.readers import (conv_decode_pct,
                                   lfm2_decode_step_roofline,
                                   lfm2_expert_matmul_roofline)

    ctx, cfg = _traced_ctx(), _config()
    # the step: 10 ms by the scan's own event over its 255 steps, against
    # 4 x 0.15 + 0.4 + 12 x 0.6 = 8.2 ms of named operations — the LONGER
    busy_step = 4 * 0.15e-3 + 0.4e-3 + 12 * 0.6e-3
    want = 100 * C.decode_step_bytes(cfg, 256, 256) / 819e9 / 10e-3
    assert lfm2_decode_step_roofline.read(ctx) == pytest.approx(want)
    assert 0 < want < 100
    assert conv_decode_pct.read(ctx) == pytest.approx(
        100 * 4 * 0.15e-3 / busy_step)
    em = C.expert_matmul_call(cfg, 256)
    least = max(em["flops"] / 197e12, em["bytes"] / 819e9)
    assert least == em["bytes"] / 819e9            # memory binds
    got = lfm2_expert_matmul_roofline.read(ctx)
    assert got == pytest.approx(100 * 4 * least / (12 * 0.6e-3))
    assert 0 < got < 100
