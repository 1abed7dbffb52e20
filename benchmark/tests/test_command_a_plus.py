"""The Command A+ configuration's files, at toy size on the CPU: found
by name with no edit to a file that was there, ``build_model`` strict
both ways, a toy run ``correct`` and its ``--control 1`` twin not, a
ring slot wronged not ``correct``, the configuration against its
published widths, ``counts_command_a_plus`` against hand arithmetic, the
readers silent where there is nothing to read, and the ``longctx``
overlay's files and expected counters."""
import json
import os

import pytest

from conftest import HERE, ROOT, run_command, tiny_manifest
from test_broken_path import run_main

REAL_CELL, CELL = "commandaplus_serve_decode_sat", "tiny_commandaplus_sat"
TOY = "benchmark/tests/commandaplus/benchmark/configs/tiny-command-a-plus.json"


@pytest.fixture()
def cap_overlay(tmp_path):
    dst = str(tmp_path / "overlay")
    m = tiny_manifest(dst, extra=("commandaplus",))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    for group in ("end_to_end", "per_layer"):
        for metric, ours in zip(real[group], m[group]):
            assert (CELL in ours.get("workloads", ())) == (
                REAL_CELL in metric.get("workloads", ())), metric["name"]
    return dst


def _config(name="benchmark/configs/command-a-plus-l4e16v8.json"):
    with open(os.path.join(ROOT, name)) as f:
        return json.load(f)


def test_the_cell_runs_correct_from_files_alone(cap_overlay):
    rc, obj, log = run_command(cap_overlay, CELL, trace=0)
    assert rc == 0 and obj["correct"], log
    assert obj["failed"] == 0 and obj["attempted"] > 0
    for name in ("serve_tokens_per_s", "serve_latency_p50_s",
                 "serve_latency_p95_s", "setup_s"):
        assert obj["metrics"][name]["value"] > 0, name
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "configs", "tiny-command-a-plus.json"))


def test_traced_run_reports_the_counters_it_can_read_on_a_cpu(cap_overlay):
    """No device trace on the CPU: the trace readers return nothing and
    do not raise; the counters' readers report."""
    rc, obj, log = run_command(cap_overlay, CELL, trace=1)
    assert rc == 0 and obj["correct"], log
    assert obj["metrics"]["serve_batch_fill_pct"]["value"] > 50
    assert obj["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    for name in ("moe_decode_step_roofline", "moe_expert_matmul_roofline"):
        assert name not in obj["metrics"]


def test_control_fp8_reference_is_not_correct(cap_overlay, capsys):
    for seed in (11, 3000000013):
        rc, obj, log = run_main(cap_overlay, CELL, capsys, seed=seed,
                                extra=("--control", "1"))
        assert rc == 0 and obj["correct"] is False, log
        over = {k for k, c in obj["checks"].items()
                if not c["value"] <= c["limit"]}
        assert over and over <= {"served_gap_widest", "served_gap_mean"}
    rc, obj, log = run_main(cap_overlay, CELL, capsys, seed=11)
    assert rc == 0 and obj["correct"] is True, log


def test_a_ring_that_forgets_to_wrap_is_not_correct(cap_overlay, capsys,
                                                    monkeypatch):
    """The sliding layers' cache written at ``pos`` instead of ``pos mod``
    its length: ``dynamic_update_slice`` clamps, so every token past the
    window lands on the last slot."""
    import jax

    real = jax.lax.dynamic_update_slice

    def broken(arr, x, idx):
        if arr.ndim == 4 and arr.shape[2] == 8 and x.shape[2] == 1:
            idx = (idx[0], idx[1], arr.shape[2] - 1, idx[3])
        return real(arr, x, idx)

    monkeypatch.setattr(jax.lax, "dynamic_update_slice", broken)
    rc, obj, log = run_main(cap_overlay, CELL, capsys)
    assert rc == 0 and obj["correct"] is False, log
    assert "widest gap" in log and "FAILED" in log


@pytest.mark.parametrize("fault", ["left_out", "unknown_to_the_model",
                                   "unknown_to_the_reference"])
def test_build_model_is_strict_both_ways(cap_overlay, fault):
    from benchmark import program

    cfg = _config(TOY)
    table = cfg["program"]["params"]["layers"]["parallel"]
    if fault == "left_out":
        del table["moe.router"]
    elif fault == "unknown_to_the_model":
        table["moe.extra"] = ["2", "no_such_leaf"]
    else:
        table["attn.bq"] = ["1", "bq"]
    with pytest.raises((ValueError, KeyError)) as err:
        program.build_model(cfg, 3000000023,
                            ref=program.reference_for(cfg, cap_overlay))
    if fault == "left_out":
        assert "only in the reference ['h.0.moe.router'" in str(err.value)


def test_the_configuration_holds_the_published_widths():
    cfg = _config()
    want = {"hidden_size": 4096, "num_attention_heads": 128,
            "num_key_value_heads": 8, "head_dim": 128,
            "intermediate_size": 4096, "num_experts_per_tok": 8,
            "num_shared_experts": 4, "sliding_window": 4096,
            "layer_switch": 4, "rope_theta": 50000, "layer_norm_eps": 1e-05,
            "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
            "tie_word_embeddings": True, "use_parallel_block": True,
            "position_embedding_type": "rope_gptj", "logit_scale": 1}
    assert {k: cfg[k] for k in want} == want
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts",
                                   "vocab_size", "max_position_embeddings"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert (cfg["num_experts"], cfg["num_experts_published"],
            cfg["router_outputs"], cfg["first_expert_held"]) == (16, 128,
                                                                 128, 0)
    assert cfg["vocab_size"] == 262144 // 8
    assert len(cfg["layer_types"]) == 32            # kept as published
    kw = cfg["program"]["kwargs"]
    assert (kw["embed_dim"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["expert_dim"]) == (4096, 128, 8, 128, 4096)
    assert (kw["n_experts"], kw["top_k"], kw["n_shared"], kw["held"],
            kw["window"], kw["scoring"], kw["renormalize"]) == (
        128, 8, 4, [0, 16], 4096, "sigmoid", True)
    assert (kw["vocab_size"], kw["num_layers"], kw["max_len"]) == (
        cfg["vocab_size"], cfg["num_hidden_layers"],
        cfg["max_position_embeddings"])
    assert kw["param_dtype"] == "bfloat16"
    for key in ("shared_expert_combination_strategy", "intermediate_size",
                "router_precision", "initializer_range"):
        assert key in cfg["assumed"], key


def test_counts_against_hand_arithmetic():
    from benchmark import counts_command_a_plus as c

    cfg = _config()
    attn = 2 * 4096 * 16384 + 2 * 4096 * 1024          # 128 and 8 heads of 128
    expert = 3 * 4096 * 4096
    assert (attn, expert) == (142606336, 50331648)
    assert c.attention_params(cfg) == attn and c.expert_params(cfg) == expert
    layer = attn + 128 * 4096 + 20 * expert + 4096     # 16 held + 4 shared
    assert c.layer_params(cfg) == layer == 1149767680
    assert c.total_params(cfg) == 4 * layer + 32768 * 4096 + 4096 \
        == 4733292544                                  # 9.47 GB in bfloat16
    d = c.dims(cfg)
    assert (d["full_layers"], d["window_layers"]) == (1, 3)
    # 128 rows x 8 choices over 128 experts: every held expert is hit
    assert 15.99 < c.experts_hit(cfg, 128) < 16
    assert abs(c.experts_hit(cfg, 1) - 1.0) < 1e-9     # one row: 8/128 x 16
    assert c.kv_positions(cfg, 192) == {"full": 192.0, "window": 192.0}
    assert c.kv_positions(cfg, 4300) == {"full": 4300.0, "window": 4096.0}
    step = c.decode_step_bytes(cfg, 128, 192)
    hit = c.experts_hit(cfg, 128)
    weights = 2 * (4 * (attn + 128 * 4096 + 4096 + (4 + hit) * expert)
                   + 32768 * 4096)
    assert abs(step - (weights + 128 * 4096 * 4 * 192)) < 1
    assert 9.4e9 < weights < 9.5e9 and 6.4e9 < 2 * 4 * hit * expert < 6.5e9
    call = c.expert_matmul_call(cfg, 128)
    assert call["rows"] == 128 and call["flops"] == 6 * 128 * 4096 * 4096
    assert abs(call["bytes"] - 2 * (hit * expert + 128 * 5 * 4096)) < 1


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """On a program without the scopes or the counter (the parent
    commit) or a run without a trace, the four readers leave their
    metric out and do not raise."""
    import importlib

    from bigdl_tpu.telemetry import reset_default_tracer

    reset_default_tracer()      # the ring of this process: no serve.fetch

    class Ctx:
        run = {"counters": {"batches": 0}, "shapes": {
            "prompt_len": 128, "max_new": 128, "max_batch": 128}}
        trace_summary = None
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
        config = _config()
        from benchmark import counts

    for name in ("moe_decode_step_roofline", "moe_expert_matmul_roofline",
                 "moe_routing_overhead_pct", "moe_load_max_over_mean"):
        reader = importlib.import_module(f"benchmark.readers.{name}")
        assert reader.read(Ctx()) is None, name


def _traced_ctx(keep=lambda i: True, scan_share=1.0):
    """A window as ``_program_spans.load`` hands it: two scans of 127
    steps of 3 ms — a step is 4 layers of three 0.2 ms grouped products
    and a 0.05 ms gate under ``moe.expert_matmul`` and a small loop of
    its own under ``moe.dispatch`` — after a prefill that holds the same
    scopes outside ``generate.decode_step``.  ``keep(i)`` says which
    events of a scan's body the trace still holds, ``scan_share`` how
    much of its length a scan's own event still shows."""
    import types

    step_ns, gmm_ns, gate_ns = 3_000_000, 200_000, 50_000
    mosaic = ('bf16[1024,4096] custom-call(%x), '
              'custom_call_target="tpu_custom_call"')
    inside = "jit(_run)/while/body/generate.decode_step/"
    events, t, i = [], 1000, 0
    for scan in range(2):
        events.append(["%gmm.99 = " + mosaic, t, 9_000_000,
                       {"scope": "jit(_run)/generate.prefill/"
                                 "moe.expert_matmul/gmm"}])
        t += 10_000_000
        events.append(["%while.9 = (s32[]) while(%tuple)", t,
                       int(127 * step_ns * scan_share), {"scope": ""}])
        for step in range(127):
            at = t + step * step_ns
            body = [["%while.10 = (s32[]) while(%t)", at, 20_000,
                     {"scope": ""}],   # a while names no scope on the chip
                    ["%add.1 = s32[] add(%a, %b)", at + 1000, 5_000,
                     {"scope": inside + "moe.dispatch/while/body/add"}]]
            at += 100_000
            for layer in range(4):
                for k in range(3):
                    body.append([f"%gmm.{3 * layer + k} = " + mosaic, at,
                                 gmm_ns, {"scope": inside +
                                          "moe.expert_matmul/gmm"}])
                    at += gmm_ns
                body.append([f"%fusion.{layer} = bf16[8] fusion(%g)", at,
                             gate_ns, {"scope": inside +
                                       "moe.expert_matmul/mul"}])
                at += gate_ns
            for ev in body:
                if keep(i):
                    events.append(ev)
                i += 1
        t += 127 * step_ns + 50_000
    return types.SimpleNamespace(
        _program_spans={"chip_events": events, "window": (0, t)},
        run={"shapes": {"prompt_len": 128, "max_new": 128,
                        "max_batch": 128},
             "counters": {"batches": 2, "real_rows": 256, "padded_rows": 0}},
        config=_config(),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})


@pytest.mark.parametrize("keep", [lambda i: True, lambda i: i % 71 == 0,
                                  lambda i: i < 400],
                         ids=["whole", "one_in_71", "first_400"])
def test_roofline_readers_do_not_depend_on_how_much_of_a_body_is_held(keep):
    """The step's time is the scans' duration over their steps (or, if
    longer, the step's operations over the steps the trace holds) and
    the grouped products' time a step is counted from the products the
    trace holds: a trace that lost most of a scan's body reads the same
    step and, of what it holds, the same time a product — never a share
    of the roofline that grows with what was lost."""
    from benchmark import counts
    from benchmark.readers import (_moe_scopes, moe_decode_step_roofline,
                                   moe_expert_matmul_roofline)

    ctx = _traced_ctx(keep)
    ctx.counts = counts
    # the scans that hold a step's operation, never a step's small loop
    assert all(e - s == 127 * 3_000_000
               for s, e in _moe_scopes.decode_scans(ctx))
    assert _moe_scopes.step_seconds(ctx, 4) == pytest.approx(3e-3)
    whole = moe_decode_step_roofline.read(_with_counts(_traced_ctx()))
    assert moe_decode_step_roofline.read(ctx) == pytest.approx(whole)
    per_step = _moe_scopes.expert_matmul_step_seconds(ctx, 4)
    # 12 products of 0.2 ms and 4 gates of 0.05 ms; of a thinned trace
    # the gates and products are held in other proportions, so between
    # the products alone and products with every gate
    assert 12 * 0.2e-3 - 1e-9 <= per_step <= 12 * 0.25e-3 + 1e-9
    share = moe_expert_matmul_roofline.read(ctx)
    assert share is not None and share == pytest.approx(
        moe_expert_matmul_roofline.read(_with_counts(_traced_ctx())),
        rel=0.21)


def test_a_scan_event_cut_short_does_not_shorten_the_step():
    """Where the scans' own events show a hundredth of their length,
    the step is what its operations take over the steps the trace
    holds: 12 products, 4 gates and the small loop's body, 2.605 of the
    3 ms (a ``while`` event names no scope, so its own 15 us do not
    count)."""
    from benchmark.readers import _moe_scopes

    ctx = _traced_ctx(scan_share=0.01)
    assert _moe_scopes.step_seconds(ctx, 4) == pytest.approx(2.605e-3)


def _with_counts(ctx):
    from benchmark import counts

    ctx.counts = counts
    return ctx


def test_routing_overhead_sums_the_three_scopes_over_busy_seconds():
    import types

    from benchmark.readers import moe_routing_overhead_pct

    rows = [("jit(_run)/while/body/generate.decode_step/moe.expert_matmul/"
             "gmm/", 700),
            ("jit(_run)/while/body/generate.decode_step/moe.route/top_k/", 50),
            ("jit(_run)/generate.prefill/moe.dispatch/sort/", 30),
            ("jit(_run)/while/body/generate.decode_step/moe.combine/x/", 20)]
    ctx = types.SimpleNamespace(
        _program_spans={"chip_events": [1], "window": (0, 1),
                        "scope_self_ns": rows},
        trace_summary={"busy_s": 1e-5})
    assert moe_routing_overhead_pct.read(ctx) == pytest.approx(
        100.0 * 100e-9 / 1e-5)


def test_the_longctx_overlay_names_files_that_exist():
    """``benchmark/tests/longctx/`` (``--manifest``, not a cell): the
    cell's configuration under a prompt longer than the window; the
    counters a chip run has to read, from shapes."""
    from benchmark import run

    over = os.path.join(HERE, "longctx")
    with open(os.path.join(over, "BENCHMARK.json")) as f:
        m = json.load(f)
    (w,) = m["workloads"]
    assert w["config"] == "command-a-plus-l4e16v8" and w["chips"] == 1
    mix = run.load_json(run.find("traffic", w["traffic"], ".json", over))
    assert (mix["prompt_len"], mix["max_new"], mix["max_batch"],
            mix["clients"]) == (4224, 128, 8, 16)
    cfg = run.load_json(run.find("configs", w["config"], ".json", over))
    assert mix["prompt_len"] > cfg["sliding_window"]
    real = run.load_json(os.path.join(ROOT, "benchmark", "traffic",
                                      "closed512_p128_n128_b128.json"))
    assert mix["limits"] == real["limits"]
    per_pos = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    assert per_pos == 4096
    assert 3 * 8 * 4096 * per_pos == 402653184      # kv_cache_bytes_window
    assert 8 * 4352 * per_pos == 142606336          # kv_cache_bytes_full
    for x in m["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "readers",
                                           x["name"] + ".py")), x["name"]
