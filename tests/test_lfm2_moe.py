"""The short-convolution expert block (LFM2-24B-A2B, ``lfm2_moe``): the
gated short convolution that keeps NO K/V, grouped-query attention with
per-head QK-norm in one layer of four, the bias-corrected sigmoid router
over experts that are ALL held, a tied head — and their path through the
generator and the server, at toy widths on the CPU, against the plain
float32 reference in ``benchmark/reference/lfm2_moe.py`` (the
convolution as a three-term sum over a zero-padded sequence, attention
over the whole sequence, every expert applied to every token; no tail,
no cache, no sort).  The toy configuration is the benchmark's own
fixture: a dense conv layer, an attention expert layer and three conv
expert layers, 16 experts all held, 4 a token.

Tolerances.  Everything here is float32 on the CPU with matmuls at
HIGHEST, so the program and the reference differ by summation order
alone: 2e-5 of the largest logit (logits are O(10); float32 carries 1e-7
a product and a few hundred products a sum).  The selection is discrete:
a token whose 4th and 5th biased scores lie within that rounding would
flip an expert and move the output by a whole expert's part — seeds are
fixed and no such tie occurs at them (a flip would read 1e-1, not 1e-5).
The conv filter is drawn at RANDOM here (the benchmark's is ones, which
``seeded_leaf`` can draw): a filter of ones cannot tell its taps apart.
"""
import copy
import hashlib
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
from benchmark.reference import lfm2_moe as ref  # noqa: E402
from bigdl_tpu import nn  # noqa: E402
from bigdl_tpu.models import generate as G  # noqa: E402
from bigdl_tpu.models.latent_moe import (GatedFFN, LogitHead,  # noqa: E402
                                         SequentialMoEBlock,
                                         SequentialMoELM, ShortConvMoELM)
from bigdl_tpu.parallel import moe as M  # noqa: E402

with open(os.path.join(ROOT, "benchmark/tests/lfm2moe/benchmark/configs/"
                       "tiny-lfm2-moe.json")) as _f:
    CFG = json.load(_f)
VOCAB, LAYERS, D = CFG["vocab_size"], CFG["num_hidden_layers"], 32
TYPES = CFG["layer_types"]
HKV, DH = CFG["num_key_value_heads"], D // CFG["num_attention_heads"]
TOL = 2e-5      # of the largest value compared: see the module docstring


def _attention_first(cfg=CFG):
    """The toy with its dense conv layer taken off: a stack that STARTS
    with attention, ``[full_attention, conv, conv]``."""
    cfg = copy.deepcopy(cfg)
    types = ["full_attention", "conv", "conv"]
    cfg.update(layer_types=types, num_hidden_layers=3, num_dense_layers=0,
               num_conv_expert_layers=2)
    cfg["program"]["kwargs"].update(layer_types=types, first_dense=0)
    table = cfg["program"]["params"]
    table["top"] = {
        name: (["1"] + path[1:] if name.startswith("attn.0.") else
               ["L+2"] + path[1:] if name == "norm" else path)
        for name, path in table["top"].items()
        if not name.startswith("dense.")}
    table["first_layer"] = 2
    return cfg


CFG_ATTN_FIRST = _attention_first()


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _flat(seed=7, cfg=CFG):
    """The reference's seeded leaves, the conv filters drawn at random."""
    flat = dict(common.make_params(ref.param_specs(cfg), ref.n_layers(cfg),
                                   cfg["initializer_range"], seed))
    for name, leaf in flat.items():
        if name.endswith("conv.w"):
            flat[name] = common.seeded_leaf(seed, name, leaf.shape, "normal",
                                            0.5)
    return flat


def _model(flat=None, cfg=CFG, **kw):
    model = ShortConvMoELM(**{**cfg["program"]["kwargs"], **kw})
    if flat is not None:
        model.set_param_tree(program.to_tree(cfg, flat))
    return model


def _layer(flat, prefix):
    return {k[len(prefix):]: v for k, v in flat.items()
            if k.startswith(prefix)}


def _ref_logits(flat, ids0, cfg=CFG):
    h = ref.embed(flat, ids0, cfg)
    for i in range(ref.n_layers(cfg)):
        h = ref.block(_layer(flat, f"h.{i}."), h, cfg, "f32")
    return ref.head(flat, h, cfg)


def _prompts(n, t, seed=0):
    return np.random.RandomState(seed).randint(
        1, VOCAB + 1, (n, t)).astype(np.int32)


def _err(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1.0)


def _close(got, want, tol=TOL):
    assert _err(got, want) <= tol, (_err(got, want), np.abs(want).max())


def _decode_logits(model, ids, T0, after_prefill=None):
    """Prefill ``ids[:, :T0]``, then one teacher-forced decode step a
    remaining token through the caches: logits [B, T - T0 + 1, V] at
    positions T0-1 .. T-1, and the caches.  ``after_prefill`` may
    replace the caches prefill left."""
    first, count = G._check_model(model)
    prefill, decode_token, logits_last = G._decode_machinery(model, first,
                                                             count)
    pc, T = model.param_tree(), ids.shape[1]
    h, caches = prefill(pc, ids[:, :T0], jnp.float32,
                        G._cache_len(model.max_len, T0, T - T0))
    if after_prefill is not None:
        caches = after_prefill(caches)
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


# -- (a) the operators against the reference -----------------------------
def test_the_gated_short_convolution_is_the_references():
    flat = _flat()
    lp = _layer(flat, "h.0.")
    conv = _model(flat).modules[3].modules[1]
    assert isinstance(conv, nn.GatedShortConv) and conv.kind == "short_conv"
    assert {k: v.shape for k, v in conv.param_tree().items()} == {
        "w_in": (3 * D, D), "conv": (3, D), "w_out": (D, D)}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, D))
    got, _ = conv.apply_fn(conv.param_tree(), {}, x, False, None)
    _close(got, ref.short_conv(lp, x, CFG))
    # the whole sequence in two pieces, the second from the first's tail
    head, state = conv.sequence(conv.param_tree(), x[:, :4])
    tail, state = conv.sequence(conv.param_tree(), x[:, 4:], state)
    _close(jnp.concatenate([head, tail], 1), got)
    assert state["conv"].shape == (2, 2, D)
    # a prompt shorter than the tail keeps the zeros before it
    _, short = conv.sequence(conv.param_tree(), x[:, :1])
    assert short["conv"].shape == (2, 2, D)
    assert not np.asarray(short["conv"][:, 0]).any()


def test_per_head_qk_norm_attention_is_the_references():
    flat = _flat()
    lp = _layer(flat, "attn.0.")
    mha = _model(flat).modules[2].modules[1]
    assert isinstance(mha, nn.MultiHeadAttention) and mha.qk_norm
    assert sorted(mha.param_tree()) == ["k_norm", "q_norm", "wk", "wo", "wq",
                                        "wv"]
    assert mha.param_tree()["q_norm"].shape == (DH,)
    # gains that are not ones, so that a gain left out would show
    p = dict(mha.param_tree(),
             q_norm=1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(1), (DH,)),
             k_norm=1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (DH,)))
    lp = dict(lp, **{"attn.q_norm": p["q_norm"], "attn.k_norm": p["k_norm"]})
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, D))
    got, _ = mha.apply_fn(p, {}, x, False, None)
    _close(got, ref.attention(lp, x, CFG))
    # off by default: no leaf, and nothing between projection and rotation
    plain = nn.MultiHeadAttention(D, 4, causal=True, with_bias=False,
                                  num_kv_heads=2, rope=True)
    assert sorted(plain.param_tree()) == ["wk", "wo", "wq", "wv"]


@pytest.mark.parametrize("cfg", [CFG, CFG_ATTN_FIRST],
                         ids=["conv_first", "attention_first"])
def test_model_logits_are_the_references(cfg):
    flat = _flat(cfg=cfg)
    model = _model(flat, cfg)
    ids = jnp.asarray(_prompts(3, 13))
    got, _ = model.apply_fn(model.param_tree(), model.buffer_tree(), ids,
                            False, None)
    assert got.dtype == jnp.float32
    _close(got, _ref_logits(flat, ids - 1, cfg))


# -- (b) prefill then decode through K/V and tails -----------------------
@pytest.mark.parametrize("cfg", [CFG, CFG_ATTN_FIRST],
                         ids=["conv_first", "attention_first"])
@pytest.mark.parametrize("T0", [1, 5, 19])
def test_prefill_then_decode_through_kv_and_tails(cfg, T0):
    """Prefill keeps the prompt's last two gated values a conv layer and
    the normed, rotated keys of the attention layer; every step after it
    reads them — compared on LOGITS, at every step, with the reference's
    full forward (a prompt of 1 is shorter than the tail)."""
    flat = _flat(seed=11, cfg=cfg)
    model = _model(flat, cfg)
    ids = jnp.asarray(_prompts(2, 30, seed=1))
    got, _ = _decode_logits(model, ids, T0)
    _close(got, _ref_logits(flat, ids - 1, cfg)[:, T0 - 1:])


def test_a_program_that_loses_its_tail_fails_the_comparison():
    flat = _flat(seed=11)
    model = _model(flat)
    ids = jnp.asarray(_prompts(2, 30, seed=1))
    want = _ref_logits(flat, ids - 1)[:, 18:]

    def zero_tails(caches):
        return [{k: jnp.zeros_like(v) if k == "conv" else v
                 for k, v in c.items()} for c in caches]

    got, _ = _decode_logits(model, ids, 19, after_prefill=zero_tails)
    # the prefill's own logits are sound; the first steps after it are not
    _close(got[:, 0], want[:, 0])
    assert _err(got[:, 1:3], want[:, 1:3]) > 1000 * TOL


def test_a_program_without_the_per_head_norms_fails_the_comparison():
    flat = _flat(seed=11)
    model = _model(flat)
    model.modules[2].modules[1].qk_norm = False
    ids = jnp.asarray(_prompts(2, 30, seed=1))
    got, _ = _decode_logits(model, ids, 19)
    assert _err(got, _ref_logits(flat, ids - 1)[:, 18:]) > 1000 * TOL


def test_generate_is_greedy_over_the_references_logits():
    flat = _flat(seed=11)
    model = _model(flat)
    prompts = _prompts(3, 19, seed=2)
    out = np.asarray(model.generate(prompts, max_new=11))
    lg = _ref_logits(flat, jnp.asarray(out[:, :-1]) - 1)
    best = np.asarray(jnp.argmax(lg, -1))[:, 18:] + 1
    assert np.array_equal(best, out[:, 19:])


# -- (c) what the cache holds --------------------------------------------
def test_a_conv_layer_keeps_its_tail_and_nothing_else():
    model = _model()
    ids = jnp.asarray(_prompts(2, 24, seed=1))
    _, caches = _decode_logits(model, ids, 19)
    T_cache, held = 64, CFG["num_experts_held"]     # min(max_len, 128)
    for kind, i, cache in zip(TYPES, range(LAYERS), caches):
        want = ({"conv": (2, 2, D)} if kind == "conv" else
                {"k": (2, HKV, T_cache, DH), "v": (2, HKV, T_cache, DH)})
        if i >= CFG["num_dense_layers"]:
            want["moe_counts"] = (2, held)
        assert {k: v.shape for k, v in cache.items()} == want, (i, kind)
    # what a layer keeps is what its operator says it keeps
    assert [sorted(set(jax.eval_shape(
        lambda b=b: b.state_init(1, jnp.float32, 8))) - set(b.counters))
            for b in model.modules[1:1 + LAYERS]] == [
        ["conv"], ["k", "v"], ["conv"], ["conv"], ["conv"]]
    foot = G.cache_footprint(model, 2, 19, 5)
    assert foot["kv_cache_positions"] == T_cache
    # K and V of the ONE attention layer; per-head K/V in all five layers
    # would be five times that
    assert foot["kv_cache_bytes"] == 2 * HKV * T_cache * DH * 2 * 4
    # four tails of two positions, whatever the context
    assert foot["recurrent_state_bytes"] == 4 * 2 * 2 * D * 4
    assert foot == G.cache_footprint(model, 2, 7, 17) | {}
    assert "latent_cache_bytes" not in foot
    # the tail is a state, not a function of the cache's length
    long = G.cache_footprint(model, 2, 19, 40)
    assert long["recurrent_state_bytes"] == foot["recurrent_state_bytes"]


def test_the_head_geometry_is_each_attention_layers_own():
    """No model-wide head geometry: the attention layer's state has its
    K/V heads and head size, a layer without attention has no heads to
    ask for and is never asked."""
    model = _model()
    blocks = model.modules[1:1 + LAYERS]
    assert not hasattr(blocks[0].modules[1], "num_heads")
    states = [jax.eval_shape(lambda b=b: b.state_init(3, jnp.float32, 64))
              for b in blocks]
    assert states[1]["k"].shape == states[1]["v"].shape == (3, HKV, 64, DH)
    assert blocks[1].modules[1].num_heads == 4
    assert all("k" not in s and "v" not in s for s in states[2:])


# -- (d) the router ------------------------------------------------------
def test_the_bias_chooses_and_never_weighs_at_epsilon_1e_6():
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (64, 32))
    w = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (16, 32))
    b = 0.3 * jax.random.normal(jax.random.fold_in(key, 2), (16,))
    s = jax.nn.sigmoid(x @ w.T)
    g0, i0 = M.route_top_k(x, w, None, 4, "sigmoid", True)
    g1, i1 = M.route_top_k(x, w, None, 4, "sigmoid", True, select_bias=b,
                           renorm_eps=1e-6)
    changed = np.mean([set(a) != set(c) for a, c in
                       zip(np.asarray(i0).tolist(), np.asarray(i1).tolist())])
    assert changed > 0.5                    # the bias moves the selection
    assert np.array_equal(np.asarray(i1),
                          np.asarray(jax.lax.top_k(s + b, 4)[1]))
    picked = jnp.take_along_axis(s, i1, -1)   # the UNBIASED scores
    want = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    assert np.array_equal(np.asarray(g1), np.asarray(want))
    # the epsilon is in the sum: the gates add up to just under 1 (scale 1)
    assert np.all(np.asarray(g1.sum(-1)) < 1.0)
    _close(g1.sum(-1), jnp.ones((64,)), 1e-5)
    # the reference selects and weighs alike
    cfg = dict(CFG, hidden_size=32)
    gr, ir = ref.select({"moe.router": w, "moe.bias": b}, x, cfg)
    assert np.array_equal(np.asarray(ir), np.asarray(i1))
    _close(gr, g1, 1e-6)
    # a layer built for this family carries the epsilon, GLM's does not
    moe = _model().modules[2].modules[3]
    assert (moe.renorm_eps, moe.routed_scale, moe.n_shared, moe.held) == (
        1e-6, 1.0, 0, (0, 16))
    assert M.DroplessMoE(8, 12, 6, score_bias=True).renorm_eps == 1e-20


def test_a_program_with_the_bias_zeroed_fails_the_comparison():
    flat = _flat(seed=11)
    ids = jnp.asarray(_prompts(3, 13, seed=3))
    want = _ref_logits(flat, ids - 1)
    zeroed = {k: (jnp.zeros_like(v) if k.endswith("moe.bias") else v)
              for k, v in flat.items()}
    model = _model(zeroed)
    got, _ = model.apply_fn(model.param_tree(), model.buffer_tree(), ids,
                            False, None)
    assert _err(got, want) > 1000 * TOL     # a whole expert's part


def test_the_full_held_layer_is_the_uncut_layer_and_four_shares_add_up():
    """The benchmark's configuration holds every expert: the layer IS
    the uncut reference layer.  Four shares of 4 experts, each computing
    its own experts' part, sum to it (no shared expert to count once)."""
    flat = _flat()
    lp = _layer(flat, "h.0.")
    n = jax.random.normal(jax.random.PRNGKey(9), (2, 7, D))
    want = ref.routed(lp, n, CFG).reshape(14, -1)
    x2 = n.reshape(14, -1)

    def share(first, count):
        moe = M.DroplessMoE(D, 24, 16, top_k=4, scoring="sigmoid",
                            held=(first, count), score_bias=True,
                            renorm_eps=1e-6)
        p = {"router_w": lp["moe.router"], "score_bias": lp["moe.bias"],
             **{f"w_{n_}": lp[f"moe.{n_}"][first:first + count]
                for n_ in ("gate", "up", "down")}}
        return moe.routed(p, x2), p

    (whole, sizes), _ = share(0, 16)
    _close(whole, want)
    assert int(sizes.sum()) == 14 * 4       # nothing routed elsewhere
    total = 0.0
    for k in range(4):
        (y, _), p = share(4 * k, 4)
        part = dict(CFG, num_experts_held=4, first_expert_held=4 * k)
        lp_k = dict(lp, **{f"moe.{n_}": p[f"w_{n_}"]
                           for n_ in ("gate", "up", "down")})
        _close(y, ref.routed(lp_k, n, part).reshape(14, -1))
        total = total + y
    _close(total, want)


# -- (e) the model's shape -----------------------------------------------
def test_operators_by_layer_a_tied_head_and_held_dtypes():
    model = _model(param_dtype="bfloat16")
    assert isinstance(model, SequentialMoELM)
    assert model.layer_types == tuple(TYPES)
    assert model.layer_kinds == ("dense",) + ("moe",) * 4
    blocks = model.modules[1:1 + LAYERS]
    for kind, block in zip(TYPES, blocks):
        want = nn.GatedShortConv if kind == "conv" else nn.MultiHeadAttention
        assert isinstance(block.modules[1], want)
    assert isinstance(blocks[0].modules[3], GatedFFN)
    assert all(isinstance(b.modules[3], M.DroplessMoE) for b in blocks[1:])
    assert [type(b) for b in blocks] == [SequentialMoEBlock] * LAYERS
    assert not blocks[0].is_moe and not blocks[0].counters
    assert blocks[1].is_moe and blocks[1].moe is blocks[1].modules[3]
    # the head owns no leaf and the tree does not name it
    head = model.modules[-1]
    assert isinstance(head, LogitHead) and head.tied and model.tied_head
    assert head.param_tree() == {}
    tree = model.param_tree()
    assert sorted(tree) == [str(i) for i in range(LAYERS + 2)]
    assert sorted(model.grad_tree()) == sorted(tree)
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        want = jnp.float32 if path[-1].key == "score_bias" else jnp.bfloat16
        assert leaf.dtype == want, path
    # given float32 leaves, the model holds them in bfloat16 — but the bias
    model.set_param_tree(program.to_tree(CFG, _flat()))
    assert model.param_tree()["2"]["3"]["score_bias"].dtype == jnp.float32
    assert model.param_tree()["3"]["1"]["conv"].dtype == jnp.bfloat16
    assert model.param_tree()["2"]["1"]["q_norm"].dtype == jnp.bfloat16
    # float32 logits from bfloat16 weights, through the embedding's matrix
    out, _ = model.apply_fn(model.param_tree(), model.buffer_tree(),
                            jnp.asarray(_prompts(1, 5)), False, None)
    assert out.dtype == jnp.float32 and out.shape == (1, 5, VOCAB)
    with pytest.raises(ValueError, match="layer_types"):
        _model(layer_types=["conv", "sliding"])


def test_build_model_holds_the_reference_to_the_tree():
    model = program.build_model(CFG, 7)
    flat = common.make_params(ref.param_specs(CFG), ref.n_layers(CFG),
                              CFG["initializer_range"], 7)
    tree = model.param_tree()
    assert np.array_equal(np.asarray(tree["0"]["weight"]),
                          np.asarray(flat["embed"]))
    assert np.array_equal(np.asarray(tree["2"]["1"]["wq"]),
                          np.asarray(flat["attn.0.attn.wq"]))
    assert np.array_equal(np.asarray(tree["4"]["1"]["w_in"]),
                          np.asarray(flat["h.1.conv.w_in"]))
    assert np.array_equal(np.asarray(tree["5"]["3"]["score_bias"]),
                          np.asarray(flat["h.2.moe.bias"]))
    # the benchmark's filter is ones: a box over three positions
    assert np.array_equal(np.asarray(tree["1"]["1"]["conv"]),
                          np.ones((3, D), np.float32))


# -- (f) through the server ----------------------------------------------
def test_the_server_reports_kv_tails_and_the_expert_counters():
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
    direct = np.asarray(model.generate(prompts, max_new=11))[:, 19:]
    assert np.array_equal(np.stack([np.asarray(r.output) for r in outs]),
                          direct)
    spans = default_tracer().spans()
    fetch = [s for s in spans if s.name == "serve.fetch"
             and s.args and "moe_tokens" in s.args]
    assert fetch
    expert_layers = LAYERS - CFG["num_dense_layers"]
    for s in fetch:
        # FOUR layers route; the dense first layer carries no count
        rows = s.args["moe_tokens"] // (expert_layers * (19 + 10))
        assert rows in (1, 2, 4)
        assert s.args["moe_tokens"] == rows * expert_layers * (19 + 10)
        # every expert is held: every assignment is counted
        assert s.args["moe_assignments"] == s.args["moe_tokens"] * 4
        assert s.args["moe_load_max_over_mean"] >= 1.0
    for s in spans:
        if s.name != "serve.dispatch":
            continue
        bucket = s.args["kv_cache_bytes"] // (2 * HKV * 64 * DH * 4)
        assert bucket in (1, 2, 4)
        assert s.args["kv_cache_bytes"] == bucket * 2 * HKV * 64 * DH * 4
        assert s.args["recurrent_state_bytes"] == bucket * 4 * 2 * D * 4
        assert "latent_cache_bytes" not in s.args


# -- (g) what cannot hold a tail says so; int8; beams --------------------
def test_the_paged_path_refuses_the_block_by_name():
    from bigdl_tpu.serving.kvpool import KVPagePool

    pool = KVPagePool(num_pages=8, page_size=4, layers=LAYERS,
                      num_kv_heads=HKV, head_dim=DH)
    with pytest.raises(TypeError, match="GatedShortConv keeps no K or V at "
                                        "all"):
        G.PagedDecoder(_model(), pool)


def test_an_int8_cache_quantises_the_attention_layers_kv_only():
    """``kv_dtype="int8"`` is an approximation of K and V: the one
    attention layer's cache is int8 with a scale a head and position, a
    conv layer's tail stays what it was — and the tokens stay the
    float32 program's where int8's rounding (1/127 of a head's largest
    value) does not reach the arg-max."""
    flat = _flat(seed=11)
    model = _model(flat)
    first, count = G._check_model(model)
    blocks = model.modules[first:first + count]
    for kind, block in zip(TYPES, blocks):
        cache = block.state_init(2, jnp.float32, 64, True)
        cache.pop("moe_counts", None)
        if kind == "conv":
            assert {k: v.dtype for k, v in cache.items()} == {
                "conv": jnp.float32}
        else:
            assert sorted(cache) == ["k", "k_scale", "v", "v_scale"]
            assert cache["k"].dtype == cache["v"].dtype == jnp.int8
    foot = G.cache_footprint(model, 2, 19, 5, kv_dtype="int8")
    assert foot["kv_cache_bytes"] == 2 * HKV * 64 * (DH + 4) * 2
    assert foot["recurrent_state_bytes"] == 4 * 2 * 2 * D * 4
    prompts = _prompts(3, 19, seed=2)
    exact = np.asarray(G.make_generate(model)(model.param_tree(), prompts, 9))
    lossy = np.asarray(G.make_generate(model, kv_dtype="int8")(
        model.param_tree(), prompts, 9))
    # the first token is the prefill's (full precision): exact
    assert np.array_equal(lossy[:, :20], exact[:, :20])
    assert np.mean(lossy == exact) > 0.8


def test_beam_of_one_equals_greedy():
    model = _model(_flat())
    prompts = _prompts(2, 12, seed=4)
    greedy = np.asarray(model.generate(prompts, max_new=7))
    beam, _ = G.make_beam_search(model)(model.param_tree(), prompts, 7,
                                        num_beams=1)
    assert np.array_equal(np.asarray(beam), greedy)
    wide, scores = G.make_beam_search(model)(model.param_tree(), prompts, 7,
                                             num_beams=3)
    assert np.asarray(wide).shape == greedy.shape
    assert np.all(np.isfinite(np.asarray(scores)))


# -- (h) training by autodiff --------------------------------------------
def test_local_optimizer_takes_a_step_on_the_toy():
    """Plain autodiff through the convolution, the per-head norms, the
    sort, the grouped products and the gather; every leaf moves but the
    selection bias (it chooses, it does not weigh), and the tied matrix
    takes the gradients of both its uses."""
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
    assert sorted(after) == sorted(before)

    def moved(*path):
        a, b = after, before
        for k in path:
            a, b = a[k], b[k]
        return float(np.abs(np.asarray(a) - b).max())

    for path in (("0", "weight"), ("1", "1", "w_in"), ("1", "1", "conv"),
                 ("1", "1", "w_out"), ("1", "3", "w_down"),
                 ("2", "1", "wq"), ("2", "1", "q_norm"), ("2", "1", "k_norm"),
                 ("2", "3", "router_w"), ("3", "1", "conv"),
                 ("5", "3", "w_gate"), (str(LAYERS + 1), "weight")):
        assert moved(*path) > 0, path
    assert moved("2", "3", "score_bias") == 0


# -- (i) what the other configurations share -----------------------------
def _toy_of(overlay):
    import glob

    path, = glob.glob(os.path.join(ROOT, "benchmark/tests", overlay,
                                   "benchmark/configs/*.json"))
    with open(path) as f:
        cfg = json.load(f)
    return program.model_class(cfg)(**cfg["program"]["kwargs"])


def _dense_toy():
    from bigdl_tpu.models.transformer import TransformerLM

    return TransformerLM(vocab_size=50, embed_dim=16, num_heads=4,
                         mlp_dim=32, num_layers=2, max_len=32)


@pytest.mark.parametrize("name,build,dtype,want", [
    ("glm47flash", lambda: _toy_of("glm47flash"), jnp.bfloat16,
     "3ae3f593efcd7f09"),
    ("commandaplus", lambda: _toy_of("commandaplus"), jnp.bfloat16,
     "795f2a2b425e56cd"),
    ("falconh1", lambda: _toy_of("falconh1"), jnp.bfloat16,
     "d01a1ad49d9797bc"),
    ("dense", _dense_toy, None, "aae8bbc5a2ebd898"),
])
def test_the_other_models_generate_programs_are_the_parents(name, build,
                                                            dtype, want):
    """The jaxpr of one generate call (2 rows, prompt 5, 3 new tokens;
    matmuls at HIGHEST, this file's fixture) of the other
    configurations' toy twins and of a dense model without QK-norm,
    hashed: the value each had at the parent commit (2e55945,
    before ``qk_norm``, ``renorm_eps``, the conv operator and the one
    sequential arm existed).  A change that means to alter one of those
    programs renews its hash here."""
    model = build()
    run = _run_of(G.make_generate(model, compute_dtype=dtype))
    args = _run_args(model, np.ones((2, 5), np.int32), 3)
    text = str(jax.make_jaxpr(lambda p, i: run(p, i, *args[2:]))(
        args[0], args[1]))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want, name


def test_the_new_defaults_change_no_other_router_or_attention():
    moe = M.DroplessMoE(8, 12, 6, top_k=2, scoring="sigmoid", n_shared=1,
                        held=(0, 3), score_bias=True, routed_scale=1.8)
    calls = []
    real = M.route_top_k
    try:
        M.route_top_k = lambda *a, **kw: calls.append((len(a), kw)) or real(
            *a, **kw)
        moe.routed(moe.param_tree(), jnp.ones((4, 8)))
    finally:
        M.route_top_k = real
    # the call GLM's layer always made: eight positionals, no keyword
    assert calls == [(8, {})]
    mha = nn.MultiHeadAttention(8, 2, with_bias=False)
    q = jnp.ones((1, 2, 3, 4))
    assert mha.normed_heads({}, q, q)[0] is q


# -- (j) scopes ----------------------------------------------------------
def test_scopes_and_counters_of_one_generate_call():
    from bigdl_tpu.telemetry.tracer import DEVICE_SCOPES

    model = _model(_flat())
    gen = G.make_generate(model)
    prompts = _prompts(4, 19, seed=5)
    text = _run_of(gen).lower(*_run_args(model, prompts, 11)).as_text(
        debug_info=True)
    for scope in ("block.conv", "conv.in_proj", "conv.short",
                  "conv.out_proj", "block.attention", "moe.route",
                  "moe.expert_matmul"):
        assert scope in DEVICE_SCOPES and scope in text, scope
    for inside in ("generate.prefill/block.conv/conv.in_proj",
                   "generate.decode_step/block.conv/conv.short",
                   "generate.decode_step/block.conv/conv.out_proj",
                   "generate.decode_step/block.attention"):
        assert inside in text, inside
    # a conv layer runs nothing under the attention's scope
    assert "block.attention/conv." not in text
    assert "block.conv/block.attention" not in text
    ids, stats = gen(model.param_tree(), prompts, 11, return_stats=True)
    counts = np.asarray(stats["moe_counts"])
    assert counts.shape == (4, 16) and counts.dtype == np.int32
    # four expert layers, every expert held: every assignment counted
    assert np.all(counts.sum(1) == 4 * (19 + 10) * 4)
