"""The parallel expert block (Command A+, ``cohere2_moe``), the dropless
dispatch and their path through the generator and the server, at toy
widths on the CPU, against the plain float32 reference in
``benchmark/reference/command_a_plus.py`` (every held expert applied to
every token and masked by the selection; no sort, no cache, no kernel).
The toy configuration is the benchmark's own fixture: 16 experts of
which 4 are held (experts 4..7), 4 a token, 2 shared, a window of 8, one
period of four layers.

Tolerances.  Everything here is float32 on the CPU with matmuls at
HIGHEST, so the program and the reference differ by summation order
only: 2e-5 of the largest logit (logits are O(10); float32 carries
1e-7 a product and a few hundred products a sum).  The selection is
discrete: a token whose 4th and 5th scores lie within that rounding
would flip an expert and move the output by a whole expert's part —
seeds are fixed and no such tie occurs at them (a flip would read
1e-1, not 1e-5).
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
from benchmark.reference import command_a_plus as ref  # noqa: E402
from benchmark.reference import common  # noqa: E402
from bigdl_tpu import nn  # noqa: E402
from bigdl_tpu.models import generate as G  # noqa: E402
from bigdl_tpu.models.parallel_moe import (ParallelMoEBlock,  # noqa: E402
                                           ParallelMoELM, layer_kinds)
from bigdl_tpu.nn.attention import rope_rotate  # noqa: E402
from bigdl_tpu.ops.flash_attention import (causal_schedule,  # noqa: E402
                                           flash_attention,
                                           windowed_attention)
from bigdl_tpu.parallel import moe as M  # noqa: E402

with open(os.path.join(ROOT, "benchmark/tests/commandaplus/benchmark/"
                       "configs/tiny-command-a-plus.json")) as _f:
    CFG = json.load(_f)
VOCAB, LAYERS, WINDOW = (CFG["vocab_size"], CFG["num_hidden_layers"],
                         CFG["sliding_window"])
TOL = 2e-5      # of the largest value compared: see the module docstring


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _flat(seed=7, cfg=CFG):
    return dict(common.make_params(ref.param_specs(cfg), LAYERS,
                                   cfg["initializer_range"], seed))


def _model(flat=None, cfg=CFG, **kw):
    model = ParallelMoELM(**{**cfg["program"]["kwargs"], **kw})
    if flat is not None:
        model.set_param_tree(program.to_tree(cfg, flat))
    return model


def _layer(flat, i):
    return {k.split(".", 2)[2]: v for k, v in flat.items()
            if k.startswith(f"h.{i}.")}


def _ref_logits(flat, ids0, cfg=CFG):
    h = ref.embed(flat, ids0, cfg)
    for i in range(LAYERS):
        h = ref.block(_layer(flat, i), h, cfg, "f32", layer=i)
    return ref.head(flat, h, cfg)


def _prompts(n, t, seed=0):
    return np.random.RandomState(seed).randint(
        1, VOCAB + 1, (n, t)).astype(np.int32)


def _close(got, want, tol=TOL):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0), (
        np.abs(got - want).max(), np.abs(want).max())


# -- the layer and the model against the reference -----------------------
@pytest.mark.parametrize("layer", [0, 3], ids=["sliding", "full"])
def test_block_is_the_references_layer(layer):
    """One block on 3 windows' worth of positions: LayerNorm without
    bias, windowed interleaved-RoPE attention or position-free full
    attention, the held experts' part and the shared mean, all on ONE
    normed input."""
    flat = _flat()
    model = _model(flat)
    x = jax.random.normal(jax.random.PRNGKey(layer), (2, 3 * WINDOW + 2,
                                                      CFG["hidden_size"]))
    block = model.modules[1 + layer]
    assert block.attention == ("full" if layer == 3 else "sliding")
    got, _ = block.apply_fn(model.param_tree()[str(1 + layer)],
                            block.buffer_tree(), x, False, None)
    _close(got, ref.block(_layer(flat, layer), x, CFG, "f32", layer=layer))


def test_model_logits_are_the_references():
    flat = _flat()
    model = _model(flat)
    ids = _prompts(3, 30)
    got, _ = model.apply_fn(model.param_tree(), model.buffer_tree(),
                            jnp.asarray(ids), False, None)
    _close(got, _ref_logits(flat, jnp.asarray(ids) - 1))


def test_the_shares_add_up():
    """16 experts in 4 shares of 4: the four routed parts plus the
    shared mean ONCE are the uncut reference's layer (all 16 held)."""
    whole = dict(CFG, num_experts=16, first_expert_held=0)
    rs = np.random.RandomState(3)
    d, f = CFG["hidden_size"], CFG["intermediate_size"]
    lp = {"moe.router": rs.normal(0, 0.3, (16, d)),
          "moe.gate": rs.normal(0, 0.3, (16, d, f)),
          "moe.up": rs.normal(0, 0.3, (16, d, f)),
          "moe.down": rs.normal(0, 0.3, (16, f, d)),
          "shared.gate": rs.normal(0, 0.3, (2, d, f)),
          "shared.up": rs.normal(0, 0.3, (2, d, f)),
          "shared.down": rs.normal(0, 0.3, (2, f, d))}
    lp = {k: jnp.asarray(v, jnp.float32) for k, v in lp.items()}
    n = jax.random.normal(jax.random.PRNGKey(0), (40, d))
    want = ref.routed(lp, n, whole) + ref.shared(lp, n, whole)
    total = 0.0
    for share in range(4):
        layer = M.DroplessMoE(d, f, 16, top_k=4, scoring="sigmoid",
                              n_shared=2, held=(4 * share, 4))
        cut = slice(4 * share, 4 * share + 4)
        params = {"router_w": lp["moe.router"],
                  "w_gate": lp["moe.gate"][cut], "w_up": lp["moe.up"][cut],
                  "w_down": lp["moe.down"][cut],
                  "shared_gate": lp["shared.gate"],
                  "shared_up": lp["shared.up"],
                  "shared_down": lp["shared.down"]}
        y, _ = layer.routed(params, n)
        total = total + y - layer.shared(params, n)     # the routed part
    _close(total + layer.shared(params, n), want)


def _rigged(target, d=16, f=24, n_experts=8, held=(2, 3), top_k=2):
    """A layer whose router sends every token to ``target`` first."""
    layer = M.DroplessMoE(d, f, n_experts, top_k=top_k, scoring="softmax",
                          held=held, init_std=0.3)
    p = dict(layer.param_tree())
    router = np.zeros((n_experts, d), np.float32)
    router[target] = 5.0                 # positive inputs -> a huge logit
    p["router_w"] = jnp.asarray(router)
    return layer, p


def test_nothing_is_dropped_when_every_token_goes_to_one_held_expert():
    layer, p = _rigged(target=3)            # held expert 1 of (2, 3, 4)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (64, 16))) + 0.1
    y, sizes = layer.routed(p, x)
    assert int(sizes[1]) == 64                      # all of them, none lost
    gates, idx = M.route_top_k(x, p["router_w"], None, 2, "softmax", True)
    want = 0.0
    for e in range(3):
        w = jnp.sum(jnp.where(idx == 2 + e, gates, 0.0), -1)
        h = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
        want = want + w[:, None] * (h @ p["w_down"][e])
    assert float(jnp.abs(want).min(axis=-1).max()) > 0
    _close(y, want)


def test_zeros_when_every_token_goes_elsewhere():
    layer, p = _rigged(target=7, top_k=1)   # expert 7 is not held
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (32, 16))) + 0.1
    y, sizes = layer.routed(p, x)
    assert int(sizes.sum()) == 0
    assert float(jnp.abs(y).max()) == 0.0


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
@pytest.mark.parametrize("renormalize", [True, False])
def test_scoring_and_renormalisation(scoring, renormalize):
    layer = M.DroplessMoE(16, 24, 8, top_k=3, scoring=scoring,
                          renormalize=renormalize, held=(0, 8),
                          init_std=0.3)
    p = layer.param_tree()
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 16))
    x2 = x.reshape(18, 16)
    logits = x2 @ p["router_w"].T
    s = jax.nn.sigmoid(logits) if scoring == "sigmoid" else \
        jax.nn.softmax(logits, -1)
    g, idx = jax.lax.top_k(s, 3)
    if renormalize:
        g = g / g.sum(-1, keepdims=True)
    want = 0.0
    for e in range(8):
        w = jnp.sum(jnp.where(idx == e, g, 0.0), -1)
        h = jax.nn.silu(x2 @ p["w_gate"][e]) * (x2 @ p["w_up"][e])
        want = want + w[:, None] * (h @ p["w_down"][e])
    got, _ = layer.apply_fn(p, {}, x, False, None)
    _close(got.reshape(18, 16), want)
    if renormalize:
        np.testing.assert_allclose(np.asarray(g.sum(-1)), 1.0, rtol=1e-6)


def test_a_long_token_list_is_dispatched_in_pieces(monkeypatch):
    layer = M.DroplessMoE(16, 24, 8, top_k=2, held=(0, 4), n_shared=1,
                          init_std=0.3)
    p = layer.param_tree()
    x = jax.random.normal(jax.random.PRNGKey(5), (48, 16))
    whole, sizes = layer.routed(p, x)
    monkeypatch.setattr(M, "MAX_DISPATCH_ROWS", 24)     # 4 pieces of 12
    for unrolled in (32, 0):            # a Python loop, then a lax.map
        monkeypatch.setattr(M, "MAX_UNROLLED_PIECES", unrolled)
        pieces, sizes2 = layer.routed(p, x)
        _close(pieces, whole, 1e-6)
        assert np.array_equal(np.asarray(sizes), np.asarray(sizes2))


# -- prefill, then decode through caches of two lengths ------------------
def _decode_logits(model, ids, T0):
    """Logits at positions T0-1 .. T-1 from the DECODE path: one prefill
    of the first T0 tokens, then a token at a time through the caches."""
    first, count = G._check_model(model)
    prefill, decode_token, logits_last = G._decode_machinery(model, first,
                                                             count)
    pc = model.param_tree()
    T = ids.shape[1]
    T_cache = G._cache_len(model.max_len, T0, T - T0 + 1)
    h, caches = prefill(pc, ids[:, :T0], jnp.float32, T_cache)
    out = [logits_last(pc, h)]
    for pos in range(T0, T):
        h, caches = decode_token(pc, ids[:, pos:pos + 1], caches,
                                 jnp.int32(pos))
        out.append(logits_last(pc, h))
    return jnp.stack(out, 1), caches


@pytest.mark.parametrize("T0", [5, 19], ids=["prompt_inside_the_window",
                                             "prompt_past_the_window"])
def test_prefill_then_decode_across_a_wrapped_window(T0):
    """Contexts of more than 3 windows: the sliding layers' ring of 8
    wraps three times, a prompt of 19 is already longer than it (prefill
    keeps its last 8 positions, each at its slot), and the full layer
    keeps everything — compared on LOGITS with the reference's full
    forward."""
    flat = _flat(seed=11)
    model = _model(flat)
    ids = jnp.asarray(_prompts(2, 3 * WINDOW + 5, seed=1))
    got, caches = _decode_logits(model, ids, T0)
    want = _ref_logits(flat, ids - 1)[:, T0 - 1:]
    _close(got, want)
    assert caches[0]["k"].shape[2] == WINDOW          # a ring
    assert caches[3]["k"].shape[2] > WINDOW           # the full layer


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


# -- the windowed flash forward ------------------------------------------
@pytest.mark.parametrize("T,window,tile,sub", [(256, 64, 128, 64),
                                               (512, 100, 128, 32),
                                               (512, 300, 256, 64),
                                               (256, 8, None, 64)])
def test_windowed_flash_forward_in_interpret_mode(T, window, tile, sub):
    """A prompt longer than the window through the kernel's walk against
    masked dense attention; 1e-6: float32 tiles, online softmax."""
    ks = jax.random.split(jax.random.PRNGKey(T + window), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, T, 32)) for kk in ks)
    got = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=tile, block_k=tile, sub_tile=sub,
                          window=window)
    _close(got, windowed_attention(q, k, v, window), 2e-6)
    sched = causal_schedule(T, T, tile or T, sub, True, window)
    plain = causal_schedule(T, T, tile or T, sub, True)
    assert sched["computed"] < plain["computed"]        # the walk skips


def test_a_window_of_the_whole_sequence_is_the_causal_schedule():
    for T, tile, sub in ((512, 256, 128), (1024, 512, 256)):
        assert causal_schedule(T, T, tile, sub, True, T) == \
            causal_schedule(T, T, tile, sub, True)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 128, 16))
    a = flash_attention(q, q, q, causal=True, interpret=True, window=128)
    b = flash_attention(q, q, q, causal=True, interpret=True)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_windowed_kernel_has_no_backward():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 128, 16))
    with pytest.raises(NotImplementedError, match="forward kernel only"):
        jax.grad(lambda x: flash_attention(
            x, q, q, causal=True, interpret=True, window=32).sum())(q)


# -- interleaved RoPE ----------------------------------------------------
def test_interleaved_rope_is_the_references_and_rotate_half_permuted():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 20, 16))
    pos = jnp.arange(20)
    got = rope_rotate(x, pos, 50000.0, interleaved=True)
    _close(got, ref._rope_interleaved(x, 50000.0), 1e-6)
    # column 2i -> i, 2i+1 -> i + D/2: rotate-half of the permuted head
    perm = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    half = rope_rotate(x[..., perm], pos, 50000.0)
    _close(got[..., perm], half, 1e-6)


# -- the pieces ----------------------------------------------------------
def test_the_head_is_the_embedding():
    model = _model(_flat())
    tree = model.param_tree()
    assert str(LAYERS + 2) not in tree and len(model.modules) == LAYERS + 3
    assert set(tree[str(LAYERS + 1)]) == {"weight"}      # no bias leaf
    ids = jnp.asarray(_prompts(1, 6))

    def logits(t):
        return model.apply_fn(t, model.buffer_tree(), ids, False, None)[0]

    bumped = jax.tree_util.tree_map(lambda a: a, tree)
    bumped["0"] = {"weight": tree["0"]["weight"] * 1.5}
    assert float(jnp.abs(logits(bumped) - logits(tree)).max()) > 1e-3
    grads = jax.grad(lambda t: logits(t).sum())(tree)
    assert set(grads) == set(tree)
    # a token the prompt never holds still gets a gradient: from the head
    unused = [i for i in range(VOCAB) if i + 1 not in np.asarray(ids)][0]
    assert float(jnp.abs(grads["0"]["weight"][unused]).max()) > 0


def test_layer_norm_without_a_bias_leaf():
    ln = nn.LayerNorm(8, eps=1e-5, with_bias=False)
    assert set(ln.param_tree()) == {"weight"}
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8)) * 3 + 1
    got, _ = ln.apply_fn({"weight": jnp.full((8,), 2.0)}, {}, x, False, None)
    _close(got, ref._ln(x, 2.0, 1e-5), 1e-6)
    assert set(nn.LayerNorm(8).param_tree()) == {"weight", "bias"}


def test_cache_footprint_by_kind_of_layer():
    model = _model()
    fp = G.cache_footprint(model, batch=3, prompt_len=19, max_new=11)
    kvh, hd, T = CFG["num_key_value_heads"], CFG["head_dim"], 64
    per_pos = 3 * 2 * kvh * hd * 4                  # rows, K and V, float32
    assert fp["kv_cache_positions"] == T
    assert fp["kv_cache_bytes_window"] == 3 * per_pos * WINDOW
    assert fp["kv_cache_bytes_full"] == 1 * per_pos * T
    assert fp["kv_cache_bytes"] == (fp["kv_cache_bytes_window"]
                                    + fp["kv_cache_bytes_full"])
    assert fp["recurrent_state_bytes"] == 0     # the counters are no state
    # the arm and plan of the step's grouped products (PR 43): the CPU's
    assert (fp["grouped"], fp["grouped_tiles"],
            fp["grouped_tiles_down"]) == ("ragged", "", "")
    # and of a piece of its prompt pass (PR 48)
    assert (fp["grouped_prefill"], fp["grouped_prefill_tiles"],
            fp["grouped_prefill_tiles_down"]) == ("ragged", "", "")
    # a context shorter than the window: every layer keeps all of it
    short = G.cache_footprint(_model(window=128), 3, 19, 11)
    assert short["kv_cache_bytes_window"] == 3 * per_pos * T


def test_layer_kinds_follow_the_published_interleave():
    assert layer_kinds(8) == ("sliding",) * 3 + ("full",) + \
        ("sliding",) * 3 + ("full",)
    assert layer_kinds(4, local_first=False)[0] == "full"
    with open(os.path.join(ROOT, "benchmark/configs/"
                           "command-a-plus-l4e16v8.json")) as f:
        real = json.load(f)
    want = ["full_attention" if ref.is_full(real, i) else "sliding_attention"
            for i in range(len(real["layer_types"]))]
    assert real["layer_types"] == want      # the scalars repeat the list
    assert ParallelMoEBlock(16, 2, 1, 8, 24, 4, 2, attention="sliding",
                            window=4).modules[1].rope_kind == "interleaved"
    assert ParallelMoEBlock(16, 2, 1, 8, 24, 4, 2).modules[1].rope is False


# -- tracing -------------------------------------------------------------
def test_scopes_counters_and_the_schedule_event():
    from bigdl_tpu.telemetry import default_tracer
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
    for scope in ("moe.route", "moe.dispatch", "moe.expert_matmul",
                  "moe.combine", "moe.shared", "block.attention"):
        assert scope in DEVICE_SCOPES and scope in text, scope
    events = [s for s in default_tracer().spans() if s.name == "moe.schedule"]
    assert {(e.args["tokens"], e.args["rows"], e.args["held"], e.args["k"])
            for e in events} >= {(4 * 19, 4 * 19 * 4, 4, 4), (4, 16, 4, 4)}
    # which arm and tile plan the grouped products compiled (PR 43): off
    # the TPU ``ragged_dot``, whose tiles are the compiler's own
    assert {(e.args["impl"], tuple(e.args["tiles"]), e.args["k_tiles"])
            for e in events} == {("ragged", (), 0)}
    ids, stats = gen(model.param_tree(), prompts, 11, return_stats=True)
    counts = np.asarray(stats["moe_counts"])
    assert counts.shape == (LAYERS, 4) and counts.dtype == np.int32
    tokens = 4 * (19 + 10)                      # a layer routes each once
    even = tokens * 4 * 4 / 16                  # tokens x k x held / all
    # the toy's noise: 116 tokens a layer, binomial sd 9 around 116
    assert np.all(np.abs(counts.sum(1) - even) < 4 * 9), counts.sum(1)
    assert gen(model.param_tree(), prompts, 11).shape == ids.shape


def test_the_server_reports_the_counters_and_the_cache_by_kind():
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
        rows = s.args["moe_tokens"] // (LAYERS * (19 + 10))
        assert rows in (1, 2, 4)
        assert 0 < s.args["moe_assignments"] <= s.args["moe_tokens"] * 4
        assert s.args["moe_load_max_over_mean"] >= 1.0
    dispatch = [s for s in spans if s.name == "serve.dispatch"][-1]
    assert dispatch.args["kv_cache_bytes_window"] > 0
    assert (dispatch.args["kv_cache_bytes_window"]
            + dispatch.args["kv_cache_bytes_full"]
            == dispatch.args["kv_cache_bytes"])
    # every generate batch says which grouped product it compiled
    assert (dispatch.args["grouped"], dispatch.args["grouped_tiles"],
            dispatch.args["grouped_tiles_down"]) == ("ragged", "", "")
    # ... in its steps, and in the pieces of its prompt pass (PR 48)
    assert (dispatch.args["grouped_prefill"],
            dispatch.args["grouped_prefill_tiles"],
            dispatch.args["grouped_prefill_tiles_down"]) == ("ragged", "",
                                                             "")


# -- the paths it shares -------------------------------------------------
def test_a_moe_ffn_decodes_through_the_dispatch_without_gathered_weights():
    """``MoEFFN.nodrop`` is a call of the dropless dispatch: no
    ``[N, D, H]`` of weights gathered per token, and the capacity-free
    mixture it always computed."""
    from bigdl_tpu.parallel.moe import MoEFFN

    moe = MoEFFN(16, 24, 4, top_k=2)
    p = moe.param_tree()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 16))
    got = moe.nodrop(p, x)
    x2 = x.reshape(10, 16)
    probs = jax.nn.softmax(x2 @ p["router_w"].T + p["router_b"], -1)
    g, idx = jax.lax.top_k(probs, 2)
    g = g / g.sum(-1, keepdims=True)
    want = 0.0
    for c in range(2):
        wi, wo = p["wi"][idx[:, c]], p["wo"][idx[:, c]]
        h = jax.nn.gelu(jnp.einsum("nd,ndh->nh", x2, wi)
                        + p["bi"][idx[:, c]])
        want = want + g[:, c, None] * (jnp.einsum("nh,nhd->nd", h, wo)
                                       + p["bo"][idx[:, c]])
    _close(got.reshape(10, 16), want)
    jaxpr = str(jax.make_jaxpr(lambda a: moe.nodrop(p, a))(x))
    assert "f32[10,16,24]" not in jaxpr and "f32[10,24,16]" not in jaxpr


def test_the_paged_path_refuses_the_block():
    from bigdl_tpu.serving.kvpool import KVPagePool

    pool = KVPagePool(num_pages=8, page_size=4, layers=LAYERS,
                      num_kv_heads=2, head_dim=8)
    with pytest.raises(TypeError, match="pages of ONE length"):
        G.PagedDecoder(_model(), pool)


def test_local_optimizer_takes_three_steps_on_the_toy():
    """Plain autodiff through the sort, the grouped products and the
    gather (``seq_strategy='dense'``: the windowed kernel is forward
    only); every leaf moves but the experts no token chose."""
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
    opt.set_optim_method(Adam(3e-3)).set_end_when(max_iteration(3))
    opt.optimize()
    assert loss() < start
    after = model.param_tree()
    assert float(np.abs(np.asarray(after["0"]["weight"])
                        - before["0"]["weight"]).max()) > 0
    assert float(np.abs(np.asarray(after["1"]["2"]["router_w"])
                        - before["1"]["2"]["router_w"]).max()) > 0
