"""The pre-routed expert block (SmallThinker-21BA3B-Instruct,
``smallthinker``): a router that reads the block's INPUT — before the
first norm, before attention — a softmax over the chosen logits,
ReLU-gated experts that are ALL held, one position-free global layer
beside three rotated layers under a sliding window whose cache is a
ring, an untied head — and their path through the generator (the prompt
pass in groups of rows too) and the server, at toy widths on the CPU,
against the plain float32 reference in
``benchmark/reference/smallthinker.py`` (attention over the whole
sequence with the scores written out, every expert applied to every
token; no ring, no cache, no sort).  The toy configuration is the
benchmark's own fixture: one period ``[global, window, window,
window]``, a window of 8, 8 experts all held, 3 a token.

Tolerances.  Everything here is float32 on the CPU with matmuls at
HIGHEST, so the program and the reference differ by summation order
alone: 2e-5 of the largest logit.  The selection is discrete: a token
whose 3rd and 4th logits lie within that rounding would flip an expert
and move the output by a whole expert's part — seeds are fixed and no
such tie occurs at them (a flip would read 1e-1, not 1e-5).
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import program  # noqa: E402
from benchmark.reference import common  # noqa: E402
from benchmark.reference import smallthinker as ref  # noqa: E402
from bigdl_tpu import nn  # noqa: E402
from bigdl_tpu.models import generate as G  # noqa: E402
from bigdl_tpu.models.latent_moe import (PreRoutedMoELM,  # noqa: E402
                                         SequentialMoEBlock,
                                         SequentialMoELM)
from bigdl_tpu.parallel import moe as M  # noqa: E402

with open(os.path.join(ROOT, "benchmark/tests/smallthinker/benchmark/"
                       "configs/tiny-smallthinker.json")) as _f:
    CFG = json.load(_f)
VOCAB, LAYERS, D = CFG["vocab_size"], CFG["num_hidden_layers"], 32
WINDOW, E, K = (CFG["sliding_window_size"], CFG["moe_num_primary_experts"],
                CFG["moe_num_active_primary_experts"])
HKV, DH = CFG["num_key_value_heads"], CFG["head_dim"]
TOL = 2e-5      # of the largest value compared: see the module docstring


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _flat(seed=7):
    return dict(common.make_params(ref.param_specs(CFG), ref.n_layers(CFG),
                                   CFG["initializer_range"], seed))


def _model(flat=None, cls=PreRoutedMoELM, **kw):
    model = cls(**{**CFG["program"]["kwargs"], **kw})
    if flat is not None:
        model.set_param_tree(program.to_tree(CFG, flat))
    return model


def _layer(flat, i):
    prefix = f"h.{i}."
    return {k[len(prefix):]: v for k, v in flat.items()
            if k.startswith(prefix)}


def _ref_logits(flat, ids0, cfg=CFG):
    h = ref.embed(flat, ids0, cfg)
    for i in range(ref.n_layers(cfg)):
        h = ref.block(_layer(flat, i), h, cfg, "f32", layer=i)
    return ref.head(flat, h, cfg)


def _prompts(n, t, seed=0):
    return np.random.RandomState(seed).randint(
        1, VOCAB + 1, (n, t)).astype(np.int32)


def _err(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1.0)


def _close(got, want, tol=TOL):
    assert _err(got, want) <= tol, (_err(got, want), np.abs(want).max())


def _rel(got, want):
    """Of the largest value compared, however small (a layer's part of
    the residual is 1e-3 at the seeded weights' scale)."""
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() / np.abs(want).max()


def _machinery(model):
    first, count = G._check_model(model)
    return G._decode_machinery(model, first, count)


def _decode_logits(model, ids, T0):
    """Prefill ``ids[:, :T0]``, then one teacher-forced decode step a
    remaining token through the caches: logits [B, T - T0 + 1, V] at
    positions T0-1 .. T-1, and the caches."""
    prefill, decode_token, logits_last = _machinery(model)
    pc, T = model.param_tree(), ids.shape[1]
    h, caches = prefill(pc, ids[:, :T0], jnp.float32,
                        G._cache_len(model.max_len, T0, T - T0))
    out = [logits_last(pc, h)]
    for pos in range(T0, T):
        h, caches = decode_token(pc, ids[:, pos:pos + 1], caches,
                                 jnp.int32(pos))
        out.append(logits_last(pc, h))
    return jnp.stack(out, 1), caches


def _run_of(gen):
    """The jitted ``_run`` inside a ``make_generate`` closure."""
    return [c.cell_contents for c in gen.__closure__
            if hasattr(c.cell_contents, "lower")][0]


def _run_args(model, prompts, max_new):
    return (model.param_tree(), jnp.asarray(prompts), max_new,
            jax.random.PRNGKey(0), jnp.float32(0), 0, jnp.float32(1),
            jnp.int32(0), jnp.int32(0), True, False)


# -- (a) the forward pass --------------------------------------------------
def test_model_logits_are_the_references_with_a_window_shorter_than_the_sequence():
    flat = _flat()
    ids = _prompts(3, 29)                   # 29 positions over a window of 8
    model = _model(flat)
    assert [m.modules[1].window for m in model.modules[1:-2]] == [
        None, WINDOW, WINDOW, WINDOW]
    assert [m.modules[1].rope for m in model.modules[1:-2]] == [
        False, True, True, True]
    got, _ = model.apply_fn(model.param_tree(), model.buffer_tree(),
                            jnp.asarray(ids), False, None)
    _close(got, _ref_logits(flat, ids - 1))


@pytest.mark.parametrize("strategy", ["dense", "flash"])
def test_each_prefill_strategy_gives_the_references_logits(strategy):
    flat = _flat(11)
    ids = _prompts(2, 21, seed=3)
    model = _model(flat, seq_strategy=strategy)
    got, _ = model.apply_fn(model.param_tree(), model.buffer_tree(),
                            jnp.asarray(ids), False, None)
    _close(got, _ref_logits(flat, ids - 1))


# -- (b) through the caches: a ring that wraps -----------------------------
@pytest.mark.parametrize("T0", [5, 8, 11, 19])
def test_prefill_then_decode_across_the_ring_is_the_full_pass(T0):
    """A prompt shorter than, as long as and longer than the window of
    8, then enough steps that every ring slot is overwritten at least
    once; the logits of every generated position against the
    reference's full pass."""
    flat = _flat()
    T = T0 + 2 * WINDOW + 3
    ids = _prompts(2, T, seed=T0)
    model = _model(flat)
    got, caches = _decode_logits(model, jnp.asarray(ids), T0)
    want = _ref_logits(flat, ids - 1)[:, T0 - 1:]
    _close(got, want)
    # the global layer keeps every position, a window layer a ring
    assert caches[0]["k"].shape[2] == G._cache_len(model.max_len, T0, T - T0)
    assert all(c["k"].shape[2] == WINDOW for c in caches[1:])


def test_the_ring_holds_the_last_window_positions_each_at_pos_mod_window():
    flat = _flat()
    T0 = 19
    ids = _prompts(1, T0, seed=4)
    model = _model(flat)
    prefill, _, _ = _machinery(model)
    pc = model.param_tree()
    _, caches = prefill(pc, jnp.asarray(ids), jnp.float32, 64)
    # layer 1's keys of the whole prompt, as its attention makes them
    block = model.modules[2]
    h0, _ = model.modules[0].apply_fn(pc["0"], {}, jnp.asarray(ids), False,
                                      None)
    h1, _ = model.modules[1].apply_fn(pc["1"], model.modules[1].buffer_tree(),
                                      h0, False, None)
    n, _ = block.modules[0].apply_fn(pc["2"]["0"], {}, h1, False, None)
    _, k, _ = block.modules[1].heads(pc["2"]["1"], n)
    ring = np.asarray(caches[1]["k"])
    for pos in range(T0 - WINDOW, T0):
        _close(ring[:, :, pos % WINDOW], np.asarray(k)[:, :, pos])


# -- (c) the router's input ------------------------------------------------
def test_scores_taken_from_the_ffns_input_fail_the_comparison():
    """The same leaves in a model whose router reads what its experts
    read (``pre_routed`` off): another function."""
    flat = _flat()
    ids = _prompts(2, 21, seed=1)
    want = _ref_logits(flat, ids - 1)

    def build(pre_routed):
        mha = [m.modules[1] for m in _model().modules[1:-2]]
        kw = CFG["program"]["kwargs"]
        model = SequentialMoELM(
            VOCAB, D, [lambda m=m: m for m in mha],
            [lambda: M.DroplessMoE(D, kw["expert_dim"], E, top_k=K,
                                   scoring="softmax", activation="relu")]
            * LAYERS, max_len=kw["max_len"], norm_eps=kw["norm_eps"],
            output="logits", pre_routed=pre_routed)
        model.set_param_tree(program.to_tree(CFG, flat))
        return model.apply_fn(model.param_tree(), model.buffer_tree(),
                              jnp.asarray(ids), False, None)[0]

    _close(build(True), want)
    assert _err(build(False), want) > 100 * TOL     # a flipped expert's part


def test_a_softmax_over_the_chosen_logits_is_softmax_top_k_renormalised():
    x = jax.random.normal(jax.random.PRNGKey(3), (37, D))
    w = jax.random.normal(jax.random.PRNGKey(4), (E, D)) * 0.3
    gates, idx = M.route_top_k(x, w, None, K, "softmax", True)
    v, want_idx = jax.lax.top_k(jnp.dot(x, w.T), K)
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    assert np.abs(np.asarray(gates)
                  - np.asarray(jax.nn.softmax(v, -1))).max() <= 2e-7
    g_ref, i_ref = ref.select({"moe.router": w}, x, CFG)
    assert np.array_equal(np.asarray(idx), np.asarray(i_ref))
    assert np.abs(np.asarray(gates) - np.asarray(g_ref)).max() <= 2e-7


def test_the_gate_is_relu_and_silu_is_another_function():
    lp = _layer(_flat(), 1)
    m = jax.random.normal(jax.random.PRNGKey(5), (2, 7, D))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 7, D))
    want = ref.routed(lp, m, x, CFG).reshape(14, -1)
    p = {"router_w": lp["moe.router"],
         **{f"w_{n}": lp[f"moe.{n}"] for n in ("gate", "up", "down")}}

    def run(activation):
        moe = M.DroplessMoE(D, 16, E, top_k=K, scoring="softmax",
                            activation=activation)
        return moe.routed(p, m.reshape(14, -1),
                          scores_from=x.reshape(14, -1))[0]

    assert _rel(run("relu"), want) <= TOL
    assert _rel(run("silu"), want) > 0.1
    with pytest.raises(ValueError, match="activation"):
        M.DroplessMoE(D, 16, E, activation="gelu")


# -- (d) shares --------------------------------------------------------------
def test_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The benchmark's configuration holds every expert: the layer IS
    the uncut reference layer.  Four shares of 2 experts, each computing
    its own experts' part, sum to it (no shared expert to count once)."""
    lp = _layer(_flat(), 2)
    m = jax.random.normal(jax.random.PRNGKey(9), (2, 7, D))
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 7, D))
    want = ref.routed(lp, m, x, CFG).reshape(14, -1)

    def share(first, count):
        moe = M.DroplessMoE(D, 16, E, top_k=K, scoring="softmax",
                            held=(first, count), activation="relu")
        p = {"router_w": lp["moe.router"],
             **{f"w_{n}": lp[f"moe.{n}"][first:first + count]
                for n in ("gate", "up", "down")}}
        return moe.routed(p, m.reshape(14, -1),
                          scores_from=x.reshape(14, -1)), p

    (whole, sizes), _ = share(0, E)
    assert _rel(whole, want) <= TOL
    assert int(sizes.sum()) == 14 * K       # nothing routed elsewhere
    total = 0.0
    for first in range(0, E, E // 4):
        (y, _), p = share(first, E // 4)
        part = dict(CFG, num_experts_held=E // 4, first_expert_held=first)
        lp_k = dict(lp, **{f"moe.{n}": p[f"w_{n}"]
                           for n in ("gate", "up", "down")})
        assert _rel(y, ref.routed(lp_k, m, x, part).reshape(14, -1)) <= TOL
        total = total + y
    assert _rel(total, want) <= TOL


# -- (e) the prompt pass in groups of rows ---------------------------------
@pytest.mark.parametrize("batch,prompt,groups", [
    (32, 4608, 4), (16, 4608, 2), (8, 4608, 1), (1, 4608, 1),
    (8, 4224, 1), (256, 128, 1), (8, 2048, 1), (64, 256, 1),
    (256, 384, 2), (4, 70000, 4), (6, 40000, 6)])
def test_prefill_groups_follow_from_shapes(batch, prompt, groups):
    assert G.prefill_groups(batch, prompt) == groups
    from benchmark import counts_smallthinker as C

    assert C.prefill_group_rows(batch, prompt) == batch // groups
    assert C.PREFILL_TOKENS == G.PREFILL_TOKENS == 65536


def test_the_prompt_pass_in_row_groups_gives_the_one_pass_caches_and_logits(
        monkeypatch):
    flat = _flat()
    ids = jnp.asarray(_prompts(8, 19, seed=2))
    model = _model(flat)
    prefill, _, logits_last = _machinery(model)
    pc = model.param_tree()
    h, caches = prefill(pc, ids, jnp.float32, 64)
    monkeypatch.setattr(G, "PREFILL_TOKENS", 2 * 19)    # groups of 2 rows
    assert G.prefill_groups(8, 19) == 4
    h_g, caches_g = prefill(pc, ids, jnp.float32, 64)
    assert h_g.shape == (8, 1, D)           # the last position alone
    _close(logits_last(pc, h_g), logits_last(pc, h))
    for one, grouped in zip(caches, caches_g):
        assert sorted(one) == sorted(grouped)
        for name in one:
            assert one[name].shape == grouped[name].shape
            if name == "moe_counts":
                assert np.array_equal(np.asarray(one[name]),
                                      np.asarray(grouped[name]))
            else:
                _close(grouped[name], one[name])
    # ``whole`` keeps the one pass whatever the size
    h_w, _ = prefill(pc, ids, jnp.float32, 64, whole=True)
    assert h_w.shape == (8, 19, D)


def test_generate_in_row_groups_returns_the_one_pass_tokens_and_counts_them(
        monkeypatch):
    from bigdl_tpu.telemetry.registry import default_registry

    model = _model(_flat())
    prompts = _prompts(4, 19, seed=8)
    want, stats = G.make_generate(model)(model.param_tree(), prompts, 11,
                                         return_stats=True)
    counter = default_registry().get("bigdl_generate_prefill_groups_total")
    before = sum(c.value for _, c in counter.series())
    monkeypatch.setattr(G, "PREFILL_TOKENS", 19)        # one row a group
    gen = G.make_generate(model)
    got, stats_g = gen(model.param_tree(), prompts, 11, return_stats=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(stats_g["moe_counts"]),
                          np.asarray(stats["moe_counts"]))
    assert sum(c.value for _, c in counter.series()) - before == 4
    text = _run_of(gen).lower(*_run_args(model, prompts, 11)).as_text(
        debug_info=True)
    assert "generate.prefill/generate.prefill_group/block.attention" in text
    # one ``while`` a program: the decode scan (PERF.md section 6 "PR 32")
    assert text.count("stablehlo.while") == 1
    assert G.cache_footprint(model, 4, 19, 11)["prefill_groups"] == 4


def test_a_counter_without_a_batch_axis_is_carried_from_group_to_group(
        monkeypatch):
    """The hyper-connected block's ``mhc_err`` is one number a layer:
    the grouped prompt pass hands it on as a decode step does."""
    from bigdl_tpu.models.latent_moe import HyperLatentMoELM

    model = HyperLatentMoELM(
        vocab_size=50, embed_dim=16, num_heads=2, q_rank=8, kv_rank=8,
        nope_dim=4, rope_dim=4, v_dim=4, mlp_dim=24, expert_dim=12,
        num_layers=2, n_experts=4, top_k=2, max_len=32, hc_mult=2,
        hc_sinkhorn_iters=3, output="logits")
    prompts = np.random.RandomState(1).randint(1, 51, (4, 7)).astype(np.int32)
    want, stats = G.make_generate(model)(model.param_tree(), prompts, 3,
                                         return_stats=True)
    monkeypatch.setattr(G, "PREFILL_TOKENS", 2 * 7)
    got, stats_g = G.make_generate(model)(model.param_tree(), prompts, 3,
                                          return_stats=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert float(stats_g["mhc_sinkhorn_err"]) == pytest.approx(
        float(stats["mhc_sinkhorn_err"]), rel=1e-5)


# -- (f) the model's shape ---------------------------------------------------
def test_operators_by_layout_an_untied_head_and_held_dtypes():
    model = _model(param_dtype="bfloat16")
    assert isinstance(model, SequentialMoELM) and not model.tied_head
    assert model.rope_layout == model.window_layout == (0, 1, 1, 1)
    assert model.layer_kinds == ("moe",) * LAYERS
    tree = model.param_tree()
    assert sorted(tree) == [str(i) for i in range(LAYERS + 3)]
    assert all(leaf.dtype == jnp.bfloat16
               for leaf in jax.tree_util.tree_leaves(tree))
    for i in range(1, LAYERS + 1):
        block = model.modules[i]
        assert isinstance(block, SequentialMoEBlock) and block.pre_routed
        assert sorted(tree[str(i)]["1"]) == ["wk", "wo", "wq", "wv"]
        assert sorted(tree[str(i)]["3"]) == ["router_w", "w_down", "w_gate",
                                             "w_up"]
        assert block.moe.activation == "relu"
        assert (block.moe.scoring, block.moe.renormalize) == ("softmax", True)
    with pytest.raises(ValueError, match="rope_layout names"):
        _model(rope_layout=[0, 1])


def test_pre_routed_refuses_a_hyper_connection_and_a_dense_ffn():
    from bigdl_tpu.models.latent_moe import GatedFFN

    mha = nn.MultiHeadAttention(8, 2, causal=True, with_bias=False)
    moe = M.DroplessMoE(8, 12, 4)
    with pytest.raises(ValueError, match="pre_routed"):
        SequentialMoEBlock(mha, moe, 8, 1e-6, pre_routed=True,
                           hyper=lambda: nn.HyperConnection(8, 2))
    with pytest.raises(ValueError, match="pre_routed"):
        SequentialMoEBlock(mha, GatedFFN(8, 12), 8, 1e-6, pre_routed=True)


def test_build_model_holds_the_reference_to_the_tree():
    model = program.build_model(CFG, 7)
    tree, flat = model.param_tree(), _flat(7)
    assert np.array_equal(np.asarray(tree["2"]["3"]["router_w"]),
                          np.asarray(flat["h.1.moe.router"]))
    assert np.array_equal(np.asarray(tree[str(LAYERS + 2)]["weight"]),
                          np.asarray(flat["head"]))
    # embedding and head are two matrices
    assert not np.array_equal(np.asarray(tree["0"]["weight"]),
                              np.asarray(tree[str(LAYERS + 2)]["weight"]))
    with pytest.raises(ValueError, match="rope_layout_text"):
        ref.param_specs(dict(CFG, rope_layout_text="1111"))


def test_attention_reports_its_kv_by_kind_of_layer():
    full = nn.MultiHeadAttention(16, 4, causal=True, num_kv_heads=2)
    slide = nn.MultiHeadAttention(16, 4, causal=True, num_kv_heads=2,
                                  window=8)
    per_pos = 3 * 2 * 2 * 4 * 4         # rows, K and V, kv heads, Dh, f32
    fp = full.footprint(3, jnp.float32, 64)
    assert (fp["kv_cache_bytes"], fp["kv_cache_bytes_full"],
            fp["kv_cache_bytes_window"]) == (64 * per_pos, 64 * per_pos, 0)
    fp = slide.footprint(3, jnp.float32, 64)
    assert (fp["kv_cache_bytes"], fp["kv_cache_bytes_full"],
            fp["kv_cache_bytes_window"]) == (8 * per_pos, 0, 8 * per_pos)
    # by KIND: a window layer's cache shorter than its window is no ring
    # and still the window's bytes
    fp = slide.footprint(3, jnp.float32, 4)
    assert (fp["kv_cache_bytes_full"], fp["kv_cache_bytes_window"]) == (
        0, 4 * per_pos)


def test_cache_footprint_by_kind_of_layer():
    fp = G.cache_footprint(_model(), batch=3, prompt_len=19, max_new=11)
    per_pos = 3 * 2 * HKV * DH * 4
    assert fp["kv_cache_positions"] == 64
    assert fp["kv_cache_bytes_window"] == 3 * per_pos * WINDOW
    assert fp["kv_cache_bytes_full"] == per_pos * 64
    assert fp["kv_cache_bytes"] == (fp["kv_cache_bytes_window"]
                                    + fp["kv_cache_bytes_full"])
    assert fp["recurrent_state_bytes"] == 0 and fp["prefill_groups"] == 1
    assert (fp["kv_attend"], fp["grouped"]) == ("einsum", "ragged")


# -- (g) through the server --------------------------------------------------
def test_the_server_returns_make_generates_tokens_and_reports_the_caches():
    from bigdl_tpu.serving import InferenceServer
    from bigdl_tpu.telemetry import default_tracer

    model = _model(_flat())
    server = InferenceServer(model, max_batch=4,
                             generate_dtype=jnp.float32).start()
    try:
        prompts = _prompts(4, 19, seed=6)
        futs = [server.submit_generate(p, 11) for p in prompts]
        outs = [f.result(timeout=600) for f in futs]
    finally:
        server.stop(30)
    assert all(r.ok for r in outs)
    ids, stats = G.make_generate(model)(model.param_tree(), prompts, 11,
                                        return_stats=True)
    assert np.array_equal(np.stack([np.asarray(r.output) for r in outs]),
                          np.asarray(ids)[:, 19:])
    counts = np.asarray(stats["moe_counts"])
    assert counts.shape == (LAYERS, E) and counts.dtype == np.int32
    # every expert held: every assignment counted, prefill and decode
    assert np.all(counts.sum(1) == 4 * (19 + 10) * K)
    spans = default_tracer().spans()
    fetch = [s for s in spans if s.name == "serve.fetch"
             and s.args and "moe_tokens" in s.args]
    assert fetch
    for s in fetch:
        assert s.args["moe_assignments"] == s.args["moe_tokens"] * K
        assert s.args["moe_load_max_over_mean"] >= 1.0
    dispatch = [s for s in spans if s.name == "serve.dispatch"]
    assert dispatch
    for s in dispatch:
        per_pos = 2 * HKV * DH * 4
        bucket = s.args["kv_cache_bytes_full"] // (64 * per_pos)
        assert bucket in (1, 2, 4)
        assert s.args["kv_cache_bytes_full"] == bucket * 64 * per_pos
        assert s.args["kv_cache_bytes_window"] == 3 * bucket * WINDOW * per_pos
        assert s.args["kv_cache_positions"] == 64
        assert s.args["prefill_groups"] == 1
        assert s.args["kv_attend"] == "einsum"
        assert s.args["grouped"] == "ragged"
        assert "latent_cache_bytes" not in s.args


def test_generate_is_greedy_over_the_references_logits():
    flat = _flat()
    model = _model(flat)
    prompts = _prompts(2, 13, seed=9)
    ids = np.asarray(model.generate(prompts, max_new=12))
    want = _ref_logits(flat, ids[:, :-1] - 1)[:, 12:]
    served = np.take_along_axis(np.asarray(want), ids[:, 13:, None] - 1, -1)
    assert np.all(np.asarray(want).max(-1) - served[..., 0] <= 1e-4)


# -- (h) what cannot hold its state says so; int8; beams ---------------------
def test_the_paged_path_refuses_the_block_by_name():
    from bigdl_tpu.serving.kvpool import KVPagePool

    pool = KVPagePool(num_pages=8, page_size=4, layers=LAYERS,
                      num_kv_heads=HKV, head_dim=DH)
    with pytest.raises(TypeError, match="SequentialMoEBlock's state — its "
                                        "layers differ in what they see"):
        G.PagedDecoder(_model(), pool)


def test_an_int8_cache_serves_rings_and_the_global_layer():
    model = _model(_flat())
    prompts = _prompts(2, 13, seed=3)
    exact = np.asarray(model.generate(prompts, max_new=9))
    gen = G.make_generate(model, kv_dtype="int8")
    approx = np.asarray(gen(model.param_tree(), prompts, 9))
    assert approx.shape == exact.shape
    # the first generated token comes from the prompt's own attention
    assert np.array_equal(approx[:, :14], exact[:, :14])


def test_beam_of_one_equals_greedy():
    model = _model(_flat())
    prompts = _prompts(2, 11, seed=5)
    greedy = np.asarray(model.generate(prompts, max_new=10))
    ids, scores = G.make_beam_search(model)(model.param_tree(), prompts, 10,
                                            num_beams=1)
    assert np.array_equal(np.asarray(ids), greedy)
    assert np.all(np.isfinite(np.asarray(scores)))


# -- (i) training by autodiff ------------------------------------------------
def test_local_optimizer_takes_a_step_on_the_toy():
    """Plain autodiff through the window's mask, the rotation, the sort,
    the grouped products, the ReLU gate and the gather; every leaf moves
    — the router's through the gates alone (it reads the block's
    input)."""
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.optim import Adam, LocalOptimizer, max_iteration

    model = _model(output="log_probs", seq_strategy="dense")
    before = jax.tree_util.tree_map(np.asarray, model.param_tree())
    seq = (np.arange(17 * 8) % 7 + 1).reshape(8, 17).astype(np.float32)
    data = DataSet.array([Sample(s[:-1], s[1:]) for s in seq])
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True)

    def loss():
        out, _ = model.apply_fn(model.param_tree(), model.buffer_tree(),
                                jnp.asarray(seq[:, :-1]), False, None)
        return float(crit.forward(out, jnp.asarray(seq[:, 1:])))

    start = loss()
    opt = LocalOptimizer(model, data, crit, batch_size=8)
    opt.set_optim_method(Adam(3e-3)).set_end_when(max_iteration(2))
    opt.optimize()
    assert loss() < start
    after = model.param_tree()

    def moved(*path):
        a, b = after, before
        for k in path:
            a, b = a[k], b[k]
        return float(np.abs(np.asarray(a) - b).max())

    for path in (("0", "weight"), ("1", "1", "wq"), ("1", "3", "router_w"),
                 ("2", "1", "wk"), ("2", "3", "w_gate"), ("3", "3", "w_up"),
                 ("4", "3", "w_down"), ("4", "3", "router_w"),
                 (str(LAYERS + 1), "weight"), (str(LAYERS + 2), "weight")):
        assert moved(*path) > 0, path


# -- (j) scopes --------------------------------------------------------------
def test_scopes_of_one_generate_call():
    from bigdl_tpu.telemetry.tracer import DEVICE_SCOPES

    model = _model(_flat())
    gen = G.make_generate(model)
    prompts = _prompts(4, 19, seed=5)
    text = _run_of(gen).lower(*_run_args(model, prompts, 11)).as_text(
        debug_info=True)
    for scope in ("block.attention", "moe.route", "moe.dispatch",
                  "moe.expert_matmul", "moe.combine",
                  "attention.decode_attend", "generate.prefill_group"):
        assert scope in DEVICE_SCOPES, scope
    for inside in ("generate.prefill/block.attention",
                   "generate.prefill/moe.route",
                   "generate.decode_step/moe.route",
                   "generate.decode_step/moe.expert_matmul",
                   "generate.decode_step/block.attention/"
                   "attention.decode_attend"):
        assert inside in text, inside
    # the prompt went whole: no group scope; the router is no part of
    # the attention sublayer
    assert "generate.prefill_group" not in text
    assert "block.attention/moe.route" not in text
