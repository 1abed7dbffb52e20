"""The Xing4.0-29B-A4B configuration's files, at toy size on the CPU:
found by name with no edit to a file that was there, ``build_model``
strict both ways over a dense layer and expert layers with their
hyper-connections and holding the stated dtypes, a toy run ``correct``
and its ``--control 1`` twin not, a program that forgets ``H_post``'s
factor or the Sinkhorn sweeps, whose gates are 0, which sweeps once for
twenty times or transposes ``H_res`` (``tools/xing_faults.py``) not
``correct``, the
configuration against its published widths, ``counts_xing4_0`` against
hand arithmetic, the readers silent where there is nothing to read and
right on a written fragment."""
import copy
import json
import os
import types

import pytest

from conftest import ROOT, run_command, tiny_manifest
from test_broken_path import run_main

REAL_CELL, CELL = "xing4_serve_decode_sat", "tiny_xing4_sat"
REAL = "benchmark/configs/xing4.0-29b-a4b-l5e32v2.json"
TINY = "benchmark/tests/xing4_0/benchmark/configs/tiny-xing4.0.json"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NEW = ("xing_decode_step_roofline", "xing_expert_matmul_roofline",
       "mhc_decode_pct", "mhc_sinkhorn_err")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture()
def xing_overlay(tmp_path):
    dst = str(tmp_path / "overlay")
    m = tiny_manifest(dst, extra=("xing4_0",))
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


def test_the_manifest_names_the_cell_and_its_four_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = [w for w in m["workloads"] if w["name"] == REAL_CELL]
    assert cell == [dict(cell[0], config="xing4.0-29b-a4b-l5e32v2",
                         traffic="closed512_p128_n256_b256_mhc", chips=1)]
    assert m["workloads"][-1]["name"] == REAL_CELL      # appended, last
    assert [p["name"] for p in m["per_layer"][-4:]] == list(NEW)
    for p in m["per_layer"][-4:]:
        assert p["workloads"] == [REAL_CELL]
        assert p["moves"] == "serve_tokens_per_s"
        assert os.path.exists(os.path.join(ROOT, "benchmark", "readers",
                                           p["name"] + ".py"))
    listed = {p["name"] for p in m["per_layer"]
              if REAL_CELL in p.get("workloads", ())}
    # every metric LFM2's cell of the same traffic lists that is no
    # configuration's own, and the two of the latent attend whose
    # readers count from this file's keys as they stand
    assert {"decode_ms_per_step", "serve_batch_fill_pct", "serve_hbm_peak_gb",
            "moe_load_max_over_mean", "moe_routing_overhead_pct",
            "mla_decode_pct", "mla_decode_attend_roofline"} <= listed
    assert "mla_decode_step_roofline" not in listed     # counts no streams
    # the constructor draws nothing (``draw_weights`` false): no counter
    assert "setup_weight_draw_s" not in listed
    assert _config()["program"]["kwargs"]["draw_weights"] is False


def test_the_cell_runs_correct_from_files_alone(xing_overlay):
    rc, obj, log = run_command(xing_overlay, CELL, trace=0)
    assert rc == 0 and obj["correct"], log
    assert obj["failed"] == 0 and obj["attempted"] > 0
    for name in ("serve_tokens_per_s", "serve_latency_p50_s",
                 "serve_latency_p95_s", "setup_s"):
        assert obj["metrics"][name]["value"] > 0, name
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "configs", "tiny-xing4.0.json"))


def test_traced_run_reports_the_counters_it_can_read_on_a_cpu(xing_overlay):
    """No device trace on the CPU: the trace readers return nothing and
    do not raise; the counters' readers report — the Sinkhorn error
    among them."""
    rc, obj, log = run_command(xing_overlay, CELL, trace=1)
    assert rc == 0 and obj["correct"], log
    assert obj["metrics"]["serve_batch_fill_pct"]["value"] > 50
    assert obj["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert 0 < obj["metrics"]["mhc_sinkhorn_err"]["value"] < 1e-4
    assert set(NEW) & set(obj["metrics"]) == {"mhc_sinkhorn_err"}


def test_control_fp8_reference_is_not_correct(xing_overlay, capsys):
    for seed in (11, 3000000013):
        rc, obj, log = run_main(xing_overlay, CELL, capsys, seed=seed,
                                extra=("--control", "1"))
        assert rc == 0 and obj["correct"] is False, log
        over = {k for k, c in obj["checks"].items()
                if not c["value"] <= c["limit"]}
        assert over and over <= {"served_gap_widest", "served_gap_mean"}
    rc, obj, log = run_main(xing_overlay, CELL, capsys, seed=11)
    assert rc == 0 and obj["correct"] is True, log


def test_a_program_that_forgets_h_posts_factor_is_not_correct(
        xing_overlay, capsys, monkeypatch):
    """``H_post = sigmoid(.)`` for ``2 sigmoid(.)``: every sublayer's
    result written back at half its weight."""
    from bigdl_tpu.nn.hyper_connection import HyperConnection

    real = HyperConnection.coefficients
    monkeypatch.setattr(
        HyperConnection, "coefficients",
        lambda self, params, x: real(self, params, x)._replace(
            post=0.5 * real(self, params, x).post))
    rc, obj, log = run_main(xing_overlay, CELL, capsys, seed=11)
    assert rc == 0 and obj["correct"] is False, log


def test_a_program_whose_gates_are_zero_is_not_correct(xing_overlay, capsys,
                                                       monkeypatch):
    """The cell's seeding (gates of ones) and limits see the DYNAMIC
    part of the maps: with the gates 0 every token gets the same maps
    (``tools/xing_faults.py gates_zero``).  The other two faults of that
    tool need the published widths to be seen by greedy tokens — at the
    toy's 256 numbers a token the residual map's logits spread by 0.3
    and one sweep leaves it within the limits of twenty — and are shown
    there on the chip (PERF.md section 6 "PR 42"); what each does to the
    maps is the next test."""
    import jax

    from benchmark.tools import xing_faults
    from bigdl_tpu.nn import hyper_connection

    monkeypatch.setattr(hyper_connection, "_coefficients",
                        hyper_connection._coefficients)
    xing_faults.install("gates_zero")
    rc, obj, log = run_main(xing_overlay, CELL, capsys, seed=11)
    jax.clear_caches()
    assert rc == 0 and obj["correct"] is False, log


@pytest.mark.parametrize("fault", ["gates_zero", "one_sweep",
                                   "transposed"])
def test_each_fault_does_to_the_maps_what_it_says(monkeypatch, fault):
    """On a state whose maps' logits spread as at the published widths
    (``phi`` of std 0.02 over 14336 numbers: 2.4)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.tools import xing_faults
    from bigdl_tpu import nn
    from bigdl_tpu.nn import hyper_connection

    hc = nn.HyperConnection(3584, 4)
    params = {**hc.param_tree(), **{
        k: jnp.float32(1) for k in ("alpha_pre", "alpha_post",
                                    "alpha_res")}}
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 4, 3584))
    sound = hc.coefficients(params, x)
    monkeypatch.setattr(hyper_connection, "_coefficients",
                        hyper_connection._coefficients)
    xing_faults.install(fault)
    co = hc.coefficients(params, x)
    jax.clear_caches()
    moved = float(jnp.abs(co.res - sound.res).mean())
    assert moved > 0.03, moved      # entries are 0.25 on the mean
    if fault == "gates_zero":       # one map for every token
        assert float(jnp.abs(co.res - co.res[..., :1]).max()) < 1e-6
        assert float(jnp.abs(co.pre - co.pre[:, :1]).max()) < 1e-6
    elif fault == "one_sweep":      # rows no longer sum to 1
        assert float(co.err) > 10 * float(sound.err) > 0
    else:
        np.testing.assert_array_equal(np.asarray(co.res),
                                      np.swapaxes(np.asarray(sound.res),
                                                  0, 1))


def test_a_program_that_forgets_the_sweeps_is_not_correct(
        xing_overlay, capsys, monkeypatch):
    """``H_res`` left as ``exp`` of its logits, rows and columns summing
    to about 4: every sublayer multiplies the state.  (YaRN's softmax
    factor is a tier-1 control, ``tests/test_xing4_0.py``: at the toy's
    widths the attention is near uniform and greedy tokens do not see
    it.)"""
    import jax.numpy as jnp

    from bigdl_tpu.nn import hyper_connection

    import jax

    monkeypatch.setattr(
        hyper_connection, "sinkhorn_map",
        lambda x, iters, eps, lo, hi: jnp.exp(jnp.clip(x, lo, hi)))
    # the three functions are jitted once a shape: what an earlier test
    # traced holds the real sweeps, and what this one traces must not
    # outlive it
    jax.clear_caches()
    try:
        rc, obj, log = run_main(xing_overlay, CELL, capsys, seed=11)
    finally:
        jax.clear_caches()
    assert rc == 0 and obj["correct"] is False, log


@pytest.mark.parametrize("fault", ["missing", "extra"])
def test_build_model_is_strict_both_ways(fault):
    from benchmark import program

    cfg = _config(TINY)
    table = cfg["program"]["params"]
    if fault == "missing":
        del table["top"]["dense.0.ffn_hc.b_res"]
    else:
        table["layers"]["moe"]["attn_hc.gain"] = ["4", "gain"]
    with pytest.raises(ValueError, match="disagree on the parameter tree"):
        program.build_model(cfg, 7)


def test_the_leaf_table_maps_every_reference_leaf_to_a_program_leaf():
    """Shape for shape, for the REAL file too (from shapes alone: no
    weight of the real size is made)."""
    import jax

    from benchmark import program
    from benchmark.reference import common, xing4_0 as ref

    for name in (TINY, REAL):
        cfg = _config(name)
        specs = common.flat_specs(ref.param_specs(cfg), ref.n_layers(cfg))
        table = program.paths(cfg)
        assert set(table) == set(specs)
        assert len(set(table.values())) == len(table)
    cfg = copy.deepcopy(_config(TINY))
    cfg["program"]["kwargs"]["param_dtype"] = "bfloat16"
    tree = program.build_model(cfg, 7).param_tree()
    keep = ("score_bias", "alpha_pre", "alpha_post", "alpha_res", "b_pre",
            "b_post", "b_res")
    import jax.numpy as jnp
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        want = jnp.float32 if path[-1].key in keep else jnp.bfloat16
        assert leaf.dtype == want, path
    flat = program.from_tree(cfg, tree)
    specs = common.flat_specs(ref.param_specs(cfg), ref.n_layers(cfg))
    assert {k: tuple(v.shape) for k, v in flat.items()} == {
        k: tuple(s) for k, (s, _) in specs.items()}
    # the seeded gates: ones, so that the maps move from token to token
    for gate in ("alpha_pre", "alpha_post", "alpha_res"):
        assert float(flat[f"h.0.ffn_hc.{gate}"]) == 1.0
    # the real file states the same table for every leaf it names
    real = _config()
    assert real["program"]["kwargs"]["param_dtype"] == "bfloat16"
    assert (real["program"]["params"]["layers"]
            == cfg["program"]["params"]["layers"])
    assert (real["program"]["params"]["top"].keys()
            == cfg["program"]["params"]["top"].keys())


def test_the_configuration_holds_the_published_widths():
    cfg = _config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    reduced = ["num_hidden_layers", "first_k_dense_replace",
               "n_routed_experts", "vocab_size", "num_nextn_predict_layers",
               "max_position_embeddings"]
    assert entry["file"] == REAL and entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        reduced)
    if os.path.exists(CATALOG):     # the row the driver drew, key for key
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B"][0]
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in reduced:
                assert cfg[key] == value, key
        assert {k: row["config"][k] for k in reduced} == {
            "num_hidden_layers": cfg["num_hidden_layers_published"],
            "first_k_dense_replace": cfg["first_k_dense_replace_published"],
            "n_routed_experts": cfg["n_routed_experts_published"],
            "vocab_size": cfg["vocab_size_published"],
            "num_nextn_predict_layers": 1,
            "max_position_embeddings": 262144}
    published = {"hidden_size": 3584, "num_attention_heads": 32,
                 "q_lora_rank": 768, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "intermediate_size": 9216,
                 "moe_intermediate_size": 1024, "num_experts_per_tok": 4,
                 "n_shared_experts": 1, "routed_scaling_factor": 2,
                 "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
                 "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
                 "rms_norm_eps": 1e-06, "rope_theta": 10000,
                 "model_type": "xing4_0", "scoring_func": "sigmoid",
                 "topk_method": "noaux_tc", "norm_topk_prob": True}
    assert {k: cfg[k] for k in published} == published
    rs = cfg["rope_scaling"]
    assert rs == {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"}
    assert all(cfg[f"rope_scaling_{k}"] == v for k, v in rs.items()
               if k != "type")
    assert (cfg["n_routed_experts"], cfg["n_routed_experts_published"],
            cfg["router_outputs"], cfg["first_expert_held"]) == (32, 64, 64, 0)
    assert (cfg["vocab_size"], cfg["vocab_size_published"]) == (65536, 131072)
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_expert_layers"]) == (5, 1, 4)
    for key in ("hc_rms", "hc_sinkhorn", "hc_maps", "hc_streams",
                "hc_precision", "hc_seeded_values", "constructor", "yarn",
                "rope_pairing",
                "initializer_range", "score_bias"):
        assert cfg["assumed"][key], key
    assert "two chips" in cfg["deployment"] and "v5e-8" in cfg["deployment"]
    kw = cfg["program"]["kwargs"]
    assert cfg["program"]["class"] == \
        "bigdl_tpu.models.latent_moe:HyperLatentMoELM"
    assert (kw["embed_dim"], kw["num_heads"], kw["q_rank"], kw["kv_rank"],
            kw["nope_dim"], kw["rope_dim"], kw["v_dim"], kw["mlp_dim"],
            kw["expert_dim"], kw["n_experts"], kw["top_k"], kw["held"],
            kw["routed_scale"], kw["vocab_size"], kw["first_dense"],
            kw["num_layers"], kw["hc_mult"], kw["hc_sinkhorn_iters"],
            kw["hc_eps"], kw["h_res_clamp"], kw["norm_eps"],
            kw["rope_scaling"], kw["param_dtype"]) == (
        3584, 32, 768, 512, 128, 64, 128, 9216, 1024, 64, 4, [0, 32], 2.0,
        65536, 1, 5, 4, 20, 1e-06, [-30, 30], 1e-06, rs, "bfloat16")


def test_the_reference_and_the_counts_agree_on_the_parameters():
    import numpy as np

    from benchmark import counts_xing4_0 as C
    from benchmark.reference import common, xing4_0 as ref

    cfg = _config()
    specs = common.flat_specs(ref.param_specs(cfg), ref.n_layers(cfg))
    total = sum(int(np.prod(s)) for s, _ in specs.values())
    assert total == C.total_params(cfg) == 2_168_632_590
    assert abs(total - 2.17e9) < 0.01 * 2.17e9          # ISSUE 42's 2.17 B


def test_counts_against_hand_arithmetic():
    from benchmark import counts_xing4_0 as C

    cfg = _config()
    attn = (3584 * 768 + 768 + 768 * 32 * 192 + 3584 * 576 + 512
            + 512 * 32 * 256 + 32 * 128 * 3584)
    expert = 3 * 3584 * 1024
    hc = 24 * 4 * 3584 + 3 + 24
    assert C.attention_params(cfg) == attn == 28_411_136
    assert C.expert_params(cfg) == expert == 11_010_048
    assert C.router_params(cfg) == 64 * 3584 + 64
    assert C.hyper_connection_params(cfg) == hc == 344_091
    assert C.dense_layer_params(cfg) == attn + 2 * 3584 \
        + 3 * 3584 * 9216 + 2 * hc == 128_196_918
    assert C.expert_layer_params(cfg) == attn + 2 * 3584 + 64 * 3584 + 64 \
        + 33 * expert + 2 * hc == 392_667_510
    assert C.total_params(cfg) == 128_196_918 + 4 * 392_667_510 \
        + 2 * 65536 * 3584 + 3584                    # 4.34 GB in bfloat16
    # the step of the full bucket at its mean context (128 + 256 / 2)
    parts = C.decode_step_parts(cfg, 256, 256)
    assert parts["latent_weights"] == 5 * attn * 2
    assert parts["latent_cache"] == 5 * 256 * 256 * 576 * 2     # 377 MB
    assert abs(parts["experts_hit"] - 4 * 32 * expert * 2) < 1e3   # all hit
    assert parts["shared"] == 4 * expert * 2
    assert parts["dense_ffn"] == 3 * 3584 * 9216 * 2
    assert parts["head"] == 65536 * 3584 * 2
    assert parts["logits"] == 256 * 65536 * 4
    # a sublayer MUST read phi from HBM; the four streams it mixes may
    # stay in fast memory and are in no count
    call = C.hyper_connection_call(cfg, 256)
    assert call["bytes"] == 24 * 4 * 3584 * 2 == 688_128
    assert set(call) == {"flops", "bytes"}
    assert call["flops"] == 256 * (2 * 24 * 14336 + 2 * 14336
                                   + 2 * 20 * 3584 + 20 * 56)
    assert call["flops"] / 197e12 > call["bytes"] / 819e9   # 1.0 > 0.84 us
    assert parts["hyper_connections"] == 10 * call["bytes"]
    total = C.decode_step_bytes(cfg, 256, 256)
    assert abs(total - 4.3121e9) < 1e6
    assert abs(total / 819e9 - 5.265e-3) < 1e-5
    # the latent attend is GLM's count at 32 heads: what
    # mla_decode_attend_roofline reads from this file's keys
    from benchmark import counts_glm4_moe_lite as G
    at = G.attend_call(cfg, 256, 256)
    assert at["flops"] == 2 * 256 * 32 * 256 * (576 + 512)
    assert at["bytes"] == (256 * 256 * 576 + 256 * 32 * (576 + 512)) * 2
    em = C.expert_matmul_call(cfg, 256)
    assert em["rows"] == 512 and em["flops"] == 6 * 512 * 3584 * 1024
    assert em["bytes"] == pytest.approx(
        (32 * expert + 512 * (2 * 3584 + 3 * 1024)) * 2, rel=1e-6)


def _ctx(**kw):
    from benchmark import counts

    base = dict(run={"counters": {"batches": 0}, "shapes": {
        "prompt_len": 128, "max_new": 256, "max_batch": 256}},
        trace_summary=None, peaks=PEAKS, config=_config(), counts=counts)
    return types.SimpleNamespace(**{**base, **kw})


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """A run without a trace or a dispatched batch, or a program without
    the scopes (the parent commit's): the readers leave their metric out
    and do not raise."""
    import importlib

    for name in NEW[:3]:
        reader = importlib.import_module(f"benchmark.readers.{name}")
        assert reader.read(_ctx()) is None, name
    ev = ["%fusion.1 = bf16[8] fusion(%a)", 1000, 500,
          {"scope": "jit(_run)/while/body/generate.decode_step/add"}]
    bare = _ctx(_program_spans={"chip_events": [ev], "window": (0, 10_000)},
                run={"counters": {"batches": 1, "real_rows": 256,
                                  "padded_rows": 0},
                     "shapes": {"prompt_len": 128, "max_new": 256,
                                "max_batch": 256}},
                trace_summary={"busy_s": 1e-6})
    for name in NEW[1:3]:
        reader = importlib.import_module(f"benchmark.readers.{name}")
        assert reader.read(bare) is None, name


def test_the_counter_reader_takes_the_largest_of_the_window(monkeypatch):
    from benchmark.readers import _program_spans, mhc_sinkhorn_err

    span = lambda name, args: types.SimpleNamespace(name=name, args=args)
    monkeypatch.setattr(_program_spans, "ring", lambda: [
        span("serve.fetch", {"mhc_sinkhorn_err": 1.1e-6, "moe_tokens": 3}),
        span("serve.fetch", {"mhc_sinkhorn_err": 3.5e-6}),
        span("serve.dispatch", {"mhc_sinkhorn_err": 9.0}),
        span("serve.fetch", None)])
    assert mhc_sinkhorn_err.read(_ctx()) == 3.5e-6
    monkeypatch.setattr(_program_spans, "ring", lambda: [
        span("serve.fetch", {"moe_tokens": 3})])        # the parent's
    assert mhc_sinkhorn_err.read(_ctx()) is None
    monkeypatch.setattr(_program_spans, "ring", lambda: None)
    assert mhc_sinkhorn_err.read(_ctx()) is None


def _traced_ctx():
    """One scan of 255 steps of 8 ms: a step holds five layers of two
    sublayers, each with 20 us under ``mhc.coeffs``, 10 us under
    ``mhc.sinkhorn``, 15 us under ``mhc.pre`` and 25 us under
    ``mhc.post``, 0.2 ms of attention a layer, and four expert layers
    of three 0.35 ms grouped products."""
    step_ns, attn_ns, gmm_ns = 8_000_000, 200_000, 350_000
    mhc = (("mhc.coeffs/dot", 20_000), ("mhc.sinkhorn/div", 10_000),
           ("mhc.pre/mul", 15_000), ("mhc.post/add", 25_000))
    mosaic = ('bf16[512,1024] custom-call(%x), '
              'custom_call_target="tpu_custom_call"')
    inside = "jit(_run)/while/body/generate.decode_step/"
    events, t = [], 1000
    events.append(["%while.9 = (s32[]) while(%tuple)", t, 255 * step_ns,
                   {"scope": ""}])
    for step in range(255):
        at = t + step * step_ns
        for layer in range(5):
            for sub in ("block.attention/", "block.mlp/"):
                for name, ns in mhc:
                    events.append([f"%fusion.{layer} = f32[24,256] "
                                   "fusion(%q)", at, ns,
                                   {"scope": inside + sub + name}])
                    at += ns
                if sub == "block.attention/":
                    events.append(["%fusion.9 = f32[256,32,384] fusion(%q)",
                                   at, attn_ns,
                                   {"scope": inside + sub + "mla.attend/dot"}])
                    at += attn_ns
            if layer:
                for k in range(3):
                    events.append([f"%gmm.{3 * layer + k} = " + mosaic, at,
                                   gmm_ns, {"scope": inside +
                                            "moe.expert_matmul/gmm"}])
                    at += gmm_ns
    busy = 255 * (10 * 70_000 + 5 * attn_ns + 12 * gmm_ns) / 1e9
    return _ctx(_program_spans={"chip_events": events,
                                "window": (0, t + 255 * step_ns + 1)},
                run={"shapes": {"prompt_len": 128, "max_new": 256,
                                "max_batch": 256},
                     "counters": {"batches": 1, "real_rows": 256,
                                  "padded_rows": 0}},
                trace_summary={"busy_s": busy})


def test_the_three_trace_readers_on_a_written_fragment():
    from benchmark import counts_xing4_0 as C
    from benchmark.readers import (mhc_decode_pct, mla_decode_pct,
                                   xing_decode_step_roofline,
                                   xing_expert_matmul_roofline)

    ctx, cfg = _traced_ctx(), _config()
    # the step: 8 ms by the scan's own event over its 255 steps, against
    # 0.7 + 1.0 + 4.2 = 5.9 ms of named operations — the LONGER
    want = 100 * C.decode_step_bytes(cfg, 256, 256) / 819e9 / 8e-3
    assert xing_decode_step_roofline.read(ctx) == pytest.approx(want)
    assert 0 < want < 100
    # ten sublayers of 70 us under mhc.* in a step of 8 ms
    assert mhc_decode_pct.read(ctx) == pytest.approx(100 * 0.7e-3 / 8e-3)
    em = C.expert_matmul_call(cfg, 256)
    least = max(em["flops"] / 197e12, em["bytes"] / 819e9)
    assert least == em["bytes"] / 819e9            # memory binds
    got = xing_expert_matmul_roofline.read(ctx)
    assert got == pytest.approx(100 * 4 * least / (12 * 0.35e-3))
    assert 0 < got < 100
    # the accepted reader of the latent block's share reads this cell's
    # trace as it stands: everything under block.attention in a step —
    # here the attention sublayer's hyper-connection with the attend
    busy_step = 10 * 70e-6 + 5 * 0.2e-3 + 12 * 0.35e-3
    assert mla_decode_pct.read(ctx) == pytest.approx(
        100 * (5 * 70e-6 + 5 * 0.2e-3) / busy_step)
