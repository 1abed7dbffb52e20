"""The latent expert block (GLM-4.7-Flash, ``glm4_moe_lite``): latent
attention with its compressed cache and absorbed decode path, the
bias-corrected sigmoid router, and their path through the generator and
the server, at toy widths on the CPU, against the plain float32 reference
in ``benchmark/reference/glm4_moe_lite.py`` (the EXPANDED attention at
every position, every held expert applied to every token; no cache, no
absorbed form, no sort).  The toy configuration is the benchmark's own
fixture: 16 experts of which 4 are held (experts 4..7), 4 a token, one
shared, a dense layer and two expert layers.

Tolerances.  Everything here is float32 on the CPU with matmuls at
HIGHEST, so the program and the reference differ by summation order —
and, in a decode step, by the ORDER OF TWO PRODUCTS (``(q W_uk) c``
against ``q (W_uk c)``), which is again rounding: 2e-5 of the largest
logit (logits are O(10); float32 carries 1e-7 a product and a few
hundred products a sum).  The selection is discrete: a token whose 4th
and 5th biased scores lie within that rounding would flip an expert and
move the output by a whole expert's part — seeds are fixed and no such
tie occurs at them (a flip would read 1e-1, not 1e-5; the test with the
bias zeroed shows what one reads).
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
from benchmark.reference import glm4_moe_lite as ref  # noqa: E402
from bigdl_tpu import nn  # noqa: E402
from bigdl_tpu.models import generate as G  # noqa: E402
from bigdl_tpu.models.latent_moe import (GatedFFN, LatentMoELM,  # noqa: E402
                                         LogitHead, SequentialMoEBlock)
from bigdl_tpu.parallel import moe as M  # noqa: E402

with open(os.path.join(ROOT, "benchmark/tests/glm47flash/benchmark/"
                       "configs/tiny-glm-4.7-flash.json")) as _f:
    CFG = json.load(_f)
VOCAB, LAYERS = CFG["vocab_size"], CFG["num_hidden_layers"]
EXPERT_LAYERS = ref.n_layers(CFG)
RANK, ROPE = CFG["kv_lora_rank"], CFG["qk_rope_head_dim"]
TOL = 2e-5      # of the largest value compared: see the module docstring


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _flat(seed=7, cfg=CFG):
    return dict(common.make_params(ref.param_specs(cfg), ref.n_layers(cfg),
                                   cfg["initializer_range"], seed))


def _model(flat=None, cfg=CFG, **kw):
    model = LatentMoELM(**{**cfg["program"]["kwargs"], **kw})
    if flat is not None:
        model.set_param_tree(program.to_tree(cfg, flat))
    return model


def _layer(flat, i):
    return {k.split(".", 2)[2]: v for k, v in flat.items()
            if k.startswith(f"h.{i}.")}


def _ref_logits(flat, ids0, cfg=CFG):
    h = ref.embed(flat, ids0, cfg)
    for i in range(ref.n_layers(cfg)):
        h = ref.block(_layer(flat, i), h, cfg, "f32")
    return ref.head(flat, h, cfg)


def _prompts(n, t, seed=0):
    return np.random.RandomState(seed).randint(
        1, VOCAB + 1, (n, t)).astype(np.int32)


def _close(got, want, tol=TOL):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0), (
        np.abs(got - want).max(), np.abs(want).max())


def _decode_logits(model, ids, T0):
    """Prefill ``ids[:, :T0]``, then one teacher-forced decode step a
    remaining token through the cache: logits [B, T - T0 + 1, V] at
    positions T0-1 .. T-1, and the caches."""
    first, count = G._check_model(model)
    prefill, decode_token, logits_last = G._decode_machinery(model, first,
                                                             count)
    pc, T = model.param_tree(), ids.shape[1]
    h, caches = prefill(pc, ids[:, :T0], jnp.float32,
                        G._cache_len(model.max_len, T0, T - T0))
    out = [logits_last(pc, h)]
    for pos in range(T0, T):
        h, caches = decode_token(pc, ids[:, pos:pos + 1], caches,
                                 jnp.int32(pos))
        out.append(logits_last(pc, h))
    return jnp.stack(out, 1), caches


# -- (a) the attention module against the reference ----------------------
def test_latent_attention_is_the_references_expanded_form():
    flat = _flat()
    lp = _layer(flat, 0)
    mla = _model(flat).modules[2].modules[1]
    assert isinstance(mla, nn.LatentAttention)
    assert sorted(mla.param_tree()) == ["kv_norm", "q_norm", "wkv_a",
                                        "wkv_b", "wo", "wq_a", "wq_b"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, CFG["hidden_size"]))
    got, _ = mla.apply_fn(mla.param_tree(), {}, x, False, None)
    _close(got, ref.attention(lp, x, CFG))


def test_model_logits_are_the_references():
    flat = _flat()
    model = _model(flat)
    ids = jnp.asarray(_prompts(3, 13))
    got, _ = model.apply_fn(model.param_tree(), model.buffer_tree(), ids,
                            False, None)
    assert got.dtype == jnp.float32
    _close(got, _ref_logits(flat, ids - 1))


# -- (b) absorbed equals expanded ----------------------------------------
@pytest.mark.parametrize("T0", [1, 5, 19])
def test_prefill_then_decode_through_the_latent_cache(T0):
    """Prefill expands the prompt's latent once; every step after it
    reads the latent cache through the absorbed products — compared on
    LOGITS, at every step, with the reference's full expanded forward."""
    flat = _flat(seed=11)
    model = _model(flat)
    ids = jnp.asarray(_prompts(2, 30, seed=1))
    got, _ = _decode_logits(model, ids, T0)
    _close(got, _ref_logits(flat, ids - 1)[:, T0 - 1:])


def test_generate_is_greedy_over_the_references_logits():
    flat = _flat(seed=11)
    model = _model(flat)
    prompts = _prompts(3, 19, seed=2)
    out = np.asarray(model.generate(prompts, max_new=11))
    lg = _ref_logits(flat, jnp.asarray(out[:, :-1]) - 1)
    best = np.asarray(jnp.argmax(lg, -1))[:, 18:] + 1
    assert np.array_equal(best, out[:, 19:])


def test_beam_of_one_equals_greedy():
    model = _model(_flat())
    prompts = _prompts(2, 12, seed=4)
    greedy = np.asarray(model.generate(prompts, max_new=7))
    beam, _ = G.make_beam_search(model)(model.param_tree(), prompts, 7,
                                        num_beams=1)
    assert np.array_equal(np.asarray(beam), greedy)


# -- (c) what the cache holds --------------------------------------------
def test_the_cache_holds_the_latent_and_one_rotated_key_a_position():
    model = _model()
    ids = jnp.asarray(_prompts(2, 24, seed=1))
    _, caches = _decode_logits(model, ids, 19)
    heads, T_cache = CFG["num_attention_heads"], 64   # min(max_len, 128)
    for i, cache in enumerate(caches):
        # the shared key with positions MINOR: ``rope`` is half a lane
        # tile, so [B, T, rope] would pad every position to twice its
        # bytes where a kernel reads it (ops/latent_attend.py)
        want = {"ckv": (2, T_cache, RANK), "kr": (2, ROPE, T_cache)}
        if i >= CFG["first_k_dense_replace"]:
            want["moe_counts"] = (2, CFG["n_routed_experts"])
        assert {k: v.shape for k, v in cache.items()} == want
        # no leaf has a head axis: nothing is kept by head
        assert all(a.ndim <= 3 for a in cache.values())
        assert heads not in cache["ckv"].shape[1:-1]
        # (the toy's rotated key is as wide as the toy has heads)
        assert cache["kr"].shape[1:] == (ROPE, T_cache)
    foot = G.cache_footprint(model, 2, 19, 5)
    assert foot["kv_cache_positions"] == T_cache
    # what a position HOLDS is rank + rope numbers, whatever the layout;
    # on the CPU a decode step attends by the plain einsums
    assert (foot["latent_attend"], foot["latent_attend_block"]) == (
        "einsum", 0)
    assert foot["latent_cache_bytes"] == LAYERS * 2 * T_cache * (RANK
                                                                 + ROPE) * 4
    assert foot["kv_cache_bytes"] == 0 and foot["recurrent_state_bytes"] == 0
    per_head = LAYERS * 2 * T_cache * heads * (
        CFG["qk_nope_head_dim"] + ROPE + CFG["v_head_dim"]) * 4
    assert per_head == foot["latent_cache_bytes"] * heads * 32 // 20


def test_a_decode_step_makes_nothing_by_head_and_position_but_the_scores():
    """The compiled-from jaxpr of ONE decode step: the only arrays with
    both the head axis and the cached-position axis are the scores
    [B, H, 1, T] — no per-head K or V of cached positions exists."""
    import re

    model = _model()
    first, count = G._check_model(model)
    _, decode_token, _ = G._decode_machinery(model, first, count)
    B, H, T = 3, CFG["num_attention_heads"], 64
    caches = [b.state_init(B, jnp.float32, T)
              for b in model.modules[first:first + count]]
    text = str(jax.make_jaxpr(
        lambda pc, tok, caches: decode_token(pc, tok, caches, jnp.int32(20))
    )(model.param_tree(), jnp.ones((B, 1), jnp.int32), caches))
    # rank 4 and up: the toy's rotated key [B, T, 4] is as wide as the
    # toy has heads
    by_head = {s for s in re.findall(r"\w+\[([\d,]+)\]", text)
               if {str(H), str(T)} <= set(s.split(",")[1:])
               and s.count(",") >= 3}
    assert by_head == {f"{B},{H},1,{T}"}, by_head


# -- (d) the router ------------------------------------------------------
def test_the_bias_chooses_and_never_weighs():
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (64, 32))
    w = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (16, 32))
    b = 0.3 * jax.random.normal(jax.random.fold_in(key, 2), (16,))
    s = jax.nn.sigmoid(x @ w.T)
    g0, i0 = M.route_top_k(x, w, None, 4, "sigmoid", True)
    g1, i1 = M.route_top_k(x, w, None, 4, "sigmoid", True, select_bias=b,
                           gate_scale=1.8)
    changed = np.mean([set(a) != set(c) for a, c in
                       zip(np.asarray(i0).tolist(), np.asarray(i1).tolist())])
    assert changed > 0.5                    # the bias moves the selection
    assert np.array_equal(np.asarray(i1),
                          np.asarray(jax.lax.top_k(s + b, 4)[1]))
    picked = jnp.take_along_axis(s, i1, -1)   # the UNBIASED scores
    _close(g1, 1.8 * picked / picked.sum(-1, keepdims=True), 1e-6)
    _close(g1.sum(-1), jnp.full((64,), 1.8), 1e-6)
    # a zero bias selects as no bias does, and the scale alone is 1.8
    g2, i2 = M.route_top_k(x, w, None, 4, "sigmoid", True,
                           select_bias=jnp.zeros(16), gate_scale=1.8)
    assert np.array_equal(np.asarray(i2), np.asarray(i0))
    _close(g2, 1.8 * g0, 1e-6)


def test_a_program_with_the_bias_zeroed_fails_the_comparison():
    flat = _flat(seed=11)
    ids = jnp.asarray(_prompts(3, 13, seed=3))
    want = _ref_logits(flat, ids - 1)
    zeroed = {k: (jnp.zeros_like(v) if k.endswith("moe.bias") else v)
              for k, v in flat.items()}
    model = _model(zeroed)
    got, _ = model.apply_fn(model.param_tree(), model.buffer_tree(), ids,
                            False, None)
    err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert err > 1000 * TOL, err            # a whole expert's part


# -- (e) the shares add up -----------------------------------------------
def test_the_shares_add_up_with_the_shared_expert_counted_once():
    """Four shares of 4 experts, each computing its own experts' part
    and the shared expert, sum — the shared expert counted once — to
    the uncut reference layer's FFN."""
    whole = dict(CFG, n_routed_experts=16, first_expert_held=0)
    flat = _flat(cfg=whole)
    lp = _layer(flat, 0)
    n = jax.random.normal(jax.random.PRNGKey(9), (2, 7, CFG["hidden_size"]))
    want = ref.routed(lp, n, whole) + ref.shared(lp, n, whole)
    x2 = n.reshape(14, -1)
    total, shared = 0.0, None
    for k in range(4):
        moe = M.DroplessMoE(32, 24, 16, top_k=4, scoring="sigmoid",
                            n_shared=1, held=(4 * k, 4), score_bias=True,
                            routed_scale=1.8)
        p = {"router_w": lp["moe.router"], "score_bias": lp["moe.bias"],
             "w_gate": lp["moe.gate"][4 * k:4 * k + 4],
             "w_up": lp["moe.up"][4 * k:4 * k + 4],
             "w_down": lp["moe.down"][4 * k:4 * k + 4],
             "shared_gate": lp["shared.gate"], "shared_up": lp["shared.up"],
             "shared_down": lp["shared.down"]}
        y, sizes = moe.routed(p, x2)
        shared = moe.shared(p, x2)
        total = total + y - shared
        # this share alone is the reference's share
        part = dict(whole, n_routed_experts=4, first_expert_held=4 * k)
        lp_k = dict(lp, **{f"moe.{n_}": p[f"w_{n_}"]
                           for n_ in ("gate", "up", "down")})
        _close(y - shared, ref.routed(lp_k, n, part).reshape(14, -1))
    _close(total + shared, want.reshape(14, -1))


# -- (f) the model's shape -----------------------------------------------
def test_dense_first_then_experts_an_untied_head_and_held_dtypes():
    model = _model(param_dtype="bfloat16")
    assert model.layer_kinds == ("dense", "moe", "moe")
    blocks = model.modules[1:1 + LAYERS]
    assert isinstance(blocks[0].modules[3], GatedFFN)
    assert all(isinstance(b.modules[3], M.DroplessMoE) for b in blocks[1:])
    assert [(type(b), type(b.modules[1])) for b in blocks] == [
        (SequentialMoEBlock, nn.LatentAttention)] * LAYERS
    assert not blocks[0].is_moe and not blocks[0].counters
    assert blocks[1].is_moe and blocks[1].moe is blocks[1].modules[3]
    head = model.modules[-1]
    assert isinstance(head, LogitHead)
    tree = model.param_tree()
    assert tree[str(LAYERS + 2)]["weight"].shape == (VOCAB, 32)
    assert tree[str(LAYERS + 2)]["weight"] is not tree["0"]["weight"]
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        want = jnp.float32 if path[-1].key == "score_bias" else jnp.bfloat16
        assert leaf.dtype == want, path
    # given float32 leaves, the model holds them in bfloat16 — but the bias
    model.set_param_tree(program.to_tree(CFG, _flat()))
    assert model.param_tree()["2"]["3"]["score_bias"].dtype == jnp.float32
    assert model.param_tree()["2"]["3"]["w_gate"].dtype == jnp.bfloat16
    # float32 logits from bfloat16 weights
    out, _ = model.apply_fn(model.param_tree(), model.buffer_tree(),
                            jnp.asarray(_prompts(1, 5)), False, None)
    assert out.dtype == jnp.float32


def test_the_generators_cast_leaves_the_bias_float32():
    model = _model(_flat())
    gen = G.make_generate(model, compute_dtype=jnp.bfloat16)
    run = [c.cell_contents for c in gen.__closure__
           if hasattr(c.cell_contents, "lower")][0]
    text = str(jax.make_jaxpr(
        lambda p, ids: run(p, ids, 3, jax.random.PRNGKey(0), jnp.float32(0),
                           0, jnp.float32(1), jnp.int32(0), jnp.int32(0),
                           True, False))(model.param_tree(),
                                         jnp.asarray(_prompts(2, 5))))
    # 16-wide float32 leaves: two latent norms' gains a layer (rounded,
    # as every other leaf) and the expert layers' biases (never)
    assert text.count("bf16[16] = convert_element_type") == 2 * LAYERS


# -- (g) through the server ----------------------------------------------
def test_the_server_reports_the_latent_cache_and_the_expert_counters():
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
    for s in fetch:
        # the EXPERT layers route; the dense first layer carries no count
        rows = s.args["moe_tokens"] // (EXPERT_LAYERS * (19 + 10))
        assert rows in (1, 2, 4)
        assert s.args["moe_tokens"] == rows * EXPERT_LAYERS * (19 + 10)
        assert 0 < s.args["moe_assignments"] <= s.args["moe_tokens"] * 4
        assert s.args["moe_load_max_over_mean"] >= 1.0
    dispatch = [s for s in spans if s.name == "serve.dispatch"][-1]
    bucket = dispatch.args["latent_cache_bytes"] // (
        LAYERS * 64 * (RANK + ROPE) * 4)
    assert bucket in (1, 2, 4)
    assert dispatch.args["kv_cache_bytes"] == 0
    # every dispatched batch says which arm of the absorbed attend its
    # program compiled (the einsums on the CPU) and the kernel's block
    for s in spans:
        if s.name == "serve.dispatch":
            assert s.args["latent_attend"] == "einsum"
            assert s.args["latent_attend_block"] == 0


def test_scopes_and_counters_of_one_generate_call():
    from bigdl_tpu.telemetry.tracer import DEVICE_SCOPES

    model = _model(_flat())
    gen = G.make_generate(model)
    run = [c.cell_contents for c in gen.__closure__
           if hasattr(c.cell_contents, "lower")][0]
    prompts = _prompts(4, 19, seed=5)
    text = run.lower(model.param_tree(), jnp.asarray(prompts), 11,
                     jax.random.PRNGKey(0), jnp.float32(0), 0,
                     jnp.float32(1), jnp.int32(0), jnp.int32(0), True,
                     False).as_text(debug_info=True)
    for scope in ("mla.q_proj", "mla.kv_latent", "mla.expand", "mla.absorb",
                  "mla.attend", "mla.out_proj", "block.attention",
                  "moe.route", "moe.expert_matmul", "moe.shared"):
        assert scope in DEVICE_SCOPES and scope in text, scope
    # the expansion is prefill's alone, the absorbed products a step's
    assert "generate.decode_step/block.attention/mla.expand" not in text
    assert "generate.prefill/block.attention/mla.absorb" not in text
    assert "generate.decode_step/block.attention/mla.absorb" in text
    ids, stats = gen(model.param_tree(), prompts, 11, return_stats=True)
    counts = np.asarray(stats["moe_counts"])
    assert counts.shape == (EXPERT_LAYERS, 4) and counts.dtype == np.int32
    assert np.all(counts.sum(1) <= 4 * (19 + 10) * 4)


# -- (h) what cannot hold the latent cache says so -----------------------
def test_the_paged_path_and_the_int8_cache_refuse_the_block_by_name():
    from bigdl_tpu.serving.kvpool import KVPagePool

    pool = KVPagePool(num_pages=8, page_size=4, layers=LAYERS,
                      num_kv_heads=4, head_dim=16)
    with pytest.raises(TypeError, match="LatentAttention keeps no K or V"):
        G.PagedDecoder(_model(), pool)
    with pytest.raises(TypeError, match="LatentAttention keeps no K or V"):
        G.make_generate(_model(), kv_dtype="int8")
    with pytest.raises(TypeError, match="LatentAttention keeps no K or V"):
        G.make_beam_search(_model(), kv_dtype="int8")


# -- (i) training by autodiff --------------------------------------------
def test_local_optimizer_takes_a_step_on_the_toy():
    """Plain autodiff through the expanded attention, the sort, the
    grouped products and the gather; every matrix moves, the selection
    bias has no gradient (it chooses, it does not weigh)."""
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

    for path in (("0", "weight"), ("1", "1", "wq_a"), ("1", "1", "wkv_b"),
                 ("1", "3", "w_down"), ("2", "1", "kv_norm"),
                 ("2", "3", "router_w"), ("2", "3", "shared_up"),
                 (str(LAYERS + 2), "weight")):
        assert moved(*path) > 0, path
    assert moved("2", "3", "score_bias") == 0


# -- (j) what the other configurations share -----------------------------
def test_the_routers_defaults_leave_the_other_routers_program_as_it_was():
    """``route_top_k`` without a bias or a scale is the computation it
    was: same jaxpr as the parent's body written out, and a
    ``DroplessMoE`` built as Command A+ builds it has no new leaf."""
    x = jnp.ones((6, 8), jnp.bfloat16)
    w = jnp.ones((5, 8), jnp.bfloat16)

    def parent(x2, router_w):
        with jax.named_scope("moe.route"):
            logits = jnp.dot(x2, router_w.T.astype(x2.dtype),
                             preferred_element_type=jnp.float32)
            scores = jax.nn.sigmoid(logits)
            gates, idx = jax.lax.top_k(scores, 2)
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        return gates, idx

    now = jax.make_jaxpr(lambda a, b: M.route_top_k(a, b, None, 2,
                                                    "sigmoid", True))(x, w)
    assert str(now) == str(jax.make_jaxpr(parent)(x, w))
    moe = M.DroplessMoE(8, 12, 6, top_k=2, scoring="sigmoid", n_shared=1,
                        held=(0, 3))
    assert "score_bias" not in moe.param_tree()
    assert (moe.score_bias, moe.routed_scale) == (False, 1.0)
