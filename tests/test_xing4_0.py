"""The hyper-connected latent expert block (Xing4.0-29B-A4B, ``xing4_0``):
a residual of four streams mixed per token by manifold-constrained
hyper-connections around latent attention with YaRN and a value head
narrower than the query's, and the bias-corrected sigmoid router — at toy
widths on the CPU against the plain float32 reference in
``benchmark/reference/xing4_0.py`` (whole scores at every position, every
held expert applied to every token; no cache, no absorbed form, the maps
computed in the textbook ``[rows, n, n]`` layout).  The toy configuration
is the benchmark's own fixture
(``benchmark/tests/xing4_0/.../tiny-xing4.0.json``): hidden 64, 4 heads,
ranks 32 / 16, head 16 + 8 / 16, 8 experts of which 4 are held, 2 a token,
one shared, a dense layer and two expert layers, four streams, 20 sweeps,
YaRN factor 4 over 32 original positions.

Weights are the benchmark's seeded ones: every gate (``alpha_pre``,
``alpha_post``, ``alpha_res``) is 1, as the configuration's reference
seeds them (``_flat`` says it again, so that this file does not hang on
that choice): at the toy's widths gates of 1 give the maps' logits a
spread of 0.3, the maps move from token to token and the sweeps still
converge to 1e-6 (at the published widths the spread is 2.4 and twenty
sweeps leave a third of the tokens' maps 1e-4 from doubly stochastic:
the configuration's ``assumed.hc_seeded_values``).

Tolerances.  Everything is float32 on the CPU with matmuls at HIGHEST:
the program and the reference differ by summation order — the program
scales the product with ``phi`` by ``1 / rms`` AFTER it, runs the sweeps
rows-minor, and a decode step takes ``(q W_uk) c`` for ``q (W_uk c)`` —
which is rounding: 3e-5 of the largest logit (2e-5 is
``test_glm4_moe_lite``'s; the maps add twenty normalisations a sublayer
on top).  The selection is discrete; seeds are fixed and no tie occurs
at them (a flip reads 1e-1).  Each control under (d) has to move the
logits by at least 30 x that, so the tolerance is shown to see it.
"""
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
from benchmark.reference import xing4_0 as ref  # noqa: E402
from bigdl_tpu import nn  # noqa: E402
from bigdl_tpu.models import generate as G  # noqa: E402
from bigdl_tpu.models.latent_moe import (HyperLatentMoELM,  # noqa: E402
                                         LatentMoELM)
from bigdl_tpu.parallel import moe as M  # noqa: E402

with open(os.path.join(ROOT, "benchmark/tests/xing4_0/benchmark/configs/"
                       "tiny-xing4.0.json")) as _f:
    CFG = json.load(_f)
VOCAB, LAYERS = CFG["vocab_size"], CFG["num_hidden_layers"]
EXPERT_LAYERS, N, D = ref.n_layers(CFG), CFG["hc_mult"], CFG["hidden_size"]
TOL = 3e-5      # of the largest value compared: see the module docstring
SEEN = 30 * TOL  # what a control has to move the logits by


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _flat(seed=7, cfg=CFG):
    flat = dict(common.make_params(ref.param_specs(cfg), ref.n_layers(cfg),
                                   cfg["initializer_range"], seed))
    return {k: jnp.ones_like(v) if ".alpha_" in k else v
            for k, v in flat.items()}


def _model(flat=None, cfg=CFG, cls=HyperLatentMoELM, **kw):
    model = cls(**{**cfg["program"]["kwargs"], **kw})
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


def _moved(got, want) -> float:
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def _logits(model, ids):
    return model.apply_fn(model.param_tree(), model.buffer_tree(), ids,
                          False, None)[0]


def _decode_logits(model, ids, T0):
    """Prefill ``ids[:, :T0]``, then one teacher-forced decode step a
    remaining token through the cache: logits at positions T0-1 .. T-1,
    and the caches."""
    first, count = G._check_model(model)
    prefill, decode_token, logits_last = G._decode_machinery(model, first,
                                                             count)
    pc, T = model.param_tree(), ids.shape[1]
    h, caches = prefill(pc, ids[:, :T0], jnp.float32,
                        G._cache_len(model.max_len, T0, T - T0))
    assert h.shape == (ids.shape[0], T0, N, D)     # the streams, carried
    out = [logits_last(pc, h)]
    for pos in range(T0, T):
        h, caches = decode_token(pc, ids[:, pos:pos + 1], caches,
                                 jnp.int32(pos))
        assert h.shape == (ids.shape[0], 1, N, D)
        out.append(logits_last(pc, h))
    return jnp.stack(out, 1), caches


# -- (a) the module's three maps and both mixes ---------------------------
def _hc_and_leaves(flat, sub="attn_hc", child="4"):
    lp = _layer(flat, 0)
    block = _model(flat).modules[2]
    hc = block.hyper[0 if sub == "attn_hc" else 1]
    assert isinstance(hc, nn.HyperConnection)
    return hc, block.param_tree()[child], lp


@pytest.mark.parametrize("sub,child", [("attn_hc", "4"), ("ffn_hc", "5")])
def test_the_three_maps_and_both_mixes_are_the_references(sub, child):
    hc, hp, lp = _hc_and_leaves(_flat(), sub, child)
    assert sorted(hp) == ["alpha_post", "alpha_pre", "alpha_res", "b_post",
                          "b_pre", "b_res", "phi"]
    assert hp["phi"].shape == (2 * N + N * N, N * D)
    X = jax.random.normal(jax.random.PRNGKey(3), (2, 9, N, D))
    co = hc.coefficients(hp, X)
    pre, post, res = ref.coefficients(lp, sub, X, CFG)
    rows = 2 * 9
    # the program's maps are ROWS MINOR
    _close(co.pre.T.reshape(2, 9, N), pre, 1e-6)
    _close(co.post.T.reshape(2, 9, N), post, 1e-6)
    _close(jnp.moveaxis(co.res, -1, 0).reshape(2, 9, N, N), res, 1e-5)
    assert co.pre.shape == (N, rows) and co.res.shape == (N, N, rows)
    # doubly stochastic: rows and columns sum to 1, and the counter says so
    assert float(jnp.abs(res.sum(-1) - 1).max()) < 1e-5
    assert float(jnp.abs(res.sum(-2) - 1).max()) < 1e-5
    assert float(co.err) < 1e-5
    want_err = max(float(jnp.abs(co.res.sum(0) - 1).max()),
                   float(jnp.abs(co.res.sum(1) - 1).max()))
    assert float(co.err) == pytest.approx(want_err, abs=1e-7)
    # the maps MOVE with the token (gates of 1 here): not one matrix
    assert float(jnp.std(pre, axis=(0, 1)).min()) > 0.02
    assert float(jnp.std(res, axis=(0, 1)).min()) > 0.01
    # the two mixes
    u = hc.pre(co, X)
    _close(u, jnp.einsum("btn,btnc->btc", pre, X))
    y = jax.random.normal(jax.random.PRNGKey(4), (2, 9, D))
    _close(hc.post(co, X, y),
           jnp.einsum("btij,btjc->btic", res, X)
           + post[..., None] * y[..., None, :])
    # the whole sublayer as the reference writes it
    f = lambda n: jnp.tanh(n) * 0.5
    got = hc.post(co, X, f(ref._rms(u, lp["input_norm"],
                                    CFG["rms_norm_eps"])))
    _close(got, ref.sublayer(lp, sub, "input_norm", X, f, CFG))


def test_the_clamp_binds_when_forced():
    hc, hp, lp = _hc_and_leaves(_flat())
    X = jax.random.normal(jax.random.PRNGKey(5), (3, 4, N, D))
    forced = dict(hp, alpha_res=jnp.float32(400.0))
    lp_forced = dict(lp, **{"attn_hc.alpha_res": jnp.float32(400.0)})
    z = jnp.einsum("on,rn->or", hp["phi"], X.reshape(12, -1))[2 * N:]
    assert float(jnp.abs(400.0 * z).max()) > 100      # far past +-30
    got = hc.coefficients(forced, X)
    want = ref.coefficients(lp_forced, "attn_hc", X, CFG)[2]
    assert np.isfinite(np.asarray(got.res)).all()
    # unclamped, exp(400 r) overflows float32 and the map is NaN
    loose = ref.coefficients(lp_forced, "attn_hc", X,
                             dict(CFG, mhc_h_res_clamp_min=-1e9,
                                  mhc_h_res_clamp_max=1e9))[2]
    assert not np.isfinite(np.asarray(loose)).all()
    _close(jnp.moveaxis(got.res, -1, 0).reshape(3, 4, N, N), want, 1e-5)


@pytest.mark.parametrize("rows", [1, 16, 256, 300, 2 * 64 * 128])
def test_the_sinkhorn_kernel_is_the_plain_sweeps(rows):
    """``ops/sinkhorn.py`` interpreted on the CPU against its plain
    form, values and gradient (the kernel's backward is the plain
    form's): ``m * (1 / s)`` for ``m / s`` is one rounding more a
    normalisation, 1e-6 of entries of order 1; rows that are no
    multiple of 128 are padded and dropped, 16384 rows walk two grid
    blocks."""
    from bigdl_tpu.ops import sinkhorn as S

    x = 0.5 * jax.random.normal(jax.random.PRNGKey(rows), (N, N, rows))
    x = x.at[0, 0, 0].set(45.0).at[1, 2, rows - 1].set(-45.0)   # clamped
    args = (20, 1e-6, -30.0, 30.0)
    want = S.sinkhorn_reference(x, *args)
    got = S.sinkhorn_map(x, *args, interpret=True)
    assert got.shape == want.shape == (N, N, rows)
    _close(got, want, 1e-6)
    assert float(jnp.abs(got.sum(0) - 1).max()) < 1e-5
    loss = lambda f: (lambda x: jnp.sum(f(x) ** 2))
    _close(jax.grad(loss(lambda x: S.sinkhorn_map(x, *args,
                                                  interpret=True)))(x),
           jax.grad(loss(lambda x: S.sinkhorn_reference(x, *args)))(x), 1e-6)
    # off the TPU, and for a float64 oracle, the plain form itself
    assert np.array_equal(np.asarray(S.sinkhorn_map(x, *args)),
                          np.asarray(want))


# -- (b) the whole forward ---------------------------------------------------
def test_model_logits_are_the_references():
    flat = _flat()
    model = _model(flat)
    ids = jnp.asarray(_prompts(3, 40))          # past the 32 original positions
    got = _logits(model, ids)
    assert got.dtype == jnp.float32 and got.shape == (3, 40, VOCAB)
    _close(got, _ref_logits(flat, ids - 1))


def test_latent_attention_with_yarn_is_the_references():
    flat = _flat()
    mla = _model(flat).modules[2].modules[1]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, D))
    got, _ = mla.apply_fn(mla.param_tree(), {}, x, False, None)
    _close(got, ref.attention(_layer(flat, 0), x, CFG))
    _close(mla.inv_freq, ref.yarn_inv_freq(CFG), 1e-6)
    assert mla.softmax_scale == pytest.approx(ref.softmax_scale(CFG))
    assert mla.softmax_mult == pytest.approx(
        (0.1 * np.log(4.0) + 1.0) ** 2)
    # the published numbers: factor 64 over 4096, rope 64 -> pairs below
    # 10 keep their frequency, from 23 on take 1 / 64 of it; m = 1.4159
    inv, ms, mult = nn.attention.yarn_rotation(
        64, 10000.0, {"type": "yarn", "factor": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096})
    f = 10000.0 ** (-np.arange(32) / 32.0)
    assert np.allclose(inv[:11], f[:11]) and np.allclose(inv[23:],
                                                         f[23:] / 64)
    assert np.all(inv[11:23] < f[11:23]) and np.all(inv[11:23] > f[11:23]
                                                    / 64)
    assert ms == 1.0 and mult == pytest.approx(1.4159 ** 2, rel=1e-4)
    with pytest.raises(ValueError, match="only 'yarn'"):
        nn.LatentAttention(8, 2, 6, 6, 4, 2, 4,
                           rope_scaling={"type": "linear", "factor": 2})


# -- (c) prefill, then decoding through the cache ---------------------------
@pytest.mark.parametrize("B,T0", [(2, 19), (4, 30), (2, 1)])
def test_prefill_then_decode_through_the_cache(B, T0):
    """Logits at every served position against the reference's full
    forward, at two bucket sizes, over a context that crosses the 32
    original positions of YaRN; the cache is the latent's and nothing of
    the streams."""
    flat = _flat(seed=11)
    model = _model(flat)
    ids = jnp.asarray(_prompts(B, 41, seed=1))
    got, caches = _decode_logits(model, ids, T0)
    _close(got, _ref_logits(flat, ids - 1)[:, T0 - 1:])
    for i, cache in enumerate(caches):
        want = {"ckv": (B, 128, CFG["kv_lora_rank"]),
                "kr": (B, CFG["qk_rope_head_dim"], 128), "mhc_err": ()}
        if i >= CFG["first_k_dense_replace"]:
            want["moe_counts"] = (B, CFG["n_routed_experts"])
        assert {k: v.shape for k, v in cache.items()} == want
        assert 0 < float(cache["mhc_err"]) < 1e-4


def test_generate_is_greedy_over_the_references_logits():
    flat = _flat(seed=11)
    model = _model(flat)
    prompts = _prompts(3, 19, seed=2)
    out = np.asarray(model.generate(prompts, max_new=17))
    lg = _ref_logits(flat, jnp.asarray(out[:, :-1]) - 1)
    best = np.asarray(jnp.argmax(lg, -1))[:, 18:] + 1
    assert np.array_equal(best, out[:, 19:])
    foot = G.cache_footprint(model, 3, 19, 17)
    assert foot["recurrent_state_bytes"] == 0 and foot["kv_cache_bytes"] == 0
    assert foot["latent_cache_bytes"] == LAYERS * 3 * 128 * (
        CFG["kv_lora_rank"] + CFG["qk_rope_head_dim"]) * 4


# -- (d) the tolerance sees each of these -------------------------------------
def _control(flat=None, **kw):
    flat = flat or _flat()
    ids = jnp.asarray(_prompts(3, 40, seed=3))
    want = _ref_logits(_flat(), ids - 1)
    return _moved(_logits(_model(flat, **kw), ids), want)


def test_control_gates_of_zero():
    flat = {k: jnp.zeros_like(v) if ".alpha_" in k else v
            for k, v in _flat().items()}
    assert _control(flat) > SEEN


def test_control_one_sinkhorn_sweep_for_twenty():
    assert _control(hc_sinkhorn_iters=1) > SEEN
    assert _control(hc_sinkhorn_iters=20) <= TOL


def test_control_the_plain_residual():
    flat = {k: v for k, v in _flat().items() if "_hc." not in k}
    cfg = json.loads(json.dumps(CFG))
    table = cfg["program"]["params"]
    for group in (table["top"], table["layers"]["moe"]):
        for k in [k for k in group if "_hc." in k]:
            del group[k]
    kw = {k: v for k, v in cfg["program"]["kwargs"].items()
          if not k.startswith(("hc_", "h_res"))}
    plain = LatentMoELM(**kw)
    plain.set_param_tree(program.to_tree(cfg, flat))
    ids = jnp.asarray(_prompts(3, 40, seed=3))
    assert _moved(_logits(plain, ids), _ref_logits(_flat(), ids - 1)) > SEEN


def test_control_yarn_off():
    assert _control(rope_scaling=None) > SEEN


def test_control_the_softmax_scale_without_mscale_squared():
    rs = dict(CFG["rope_scaling"], mscale_all_dim=0, mscale=0)
    model = _model(_flat(), rope_scaling=rs)
    mla = model.modules[1].modules[1]
    # the frequencies are YaRN's still; only the scale lost its factor
    assert mla.softmax_mult == 1.0 and mla.rope_mscale == 1.0
    _close(mla.inv_freq, ref.yarn_inv_freq(CFG), 1e-6)
    ids = jnp.asarray(_prompts(3, 40, seed=3))
    assert _moved(_logits(model, ids), _ref_logits(_flat(), ids - 1)) > SEEN
    # ... in a decode step too: the absorbed attend reads the same scale
    got, _ = _decode_logits(model, ids[:2], 19)
    assert _moved(got, _ref_logits(_flat(), ids[:2] - 1)[:, 18:]) > SEEN


# -- (e) the shares add up -----------------------------------------------
def test_the_shares_add_up_with_the_shared_expert_counted_once():
    """``held=(0, 4)`` plus ``held=(4, 8)``, each computing its own
    experts' part and the shared expert, sum — the shared expert counted
    once — to the uncut reference layer's FFN, and so does the state the
    FFN's hyper-connection writes (it is linear in the result)."""
    whole = dict(CFG, n_routed_experts=8, first_expert_held=0)
    flat = _flat(cfg=whole)
    lp = _layer(flat, 0)
    n = jax.random.normal(jax.random.PRNGKey(9), (2, 7, D))
    want = ref.glm.routed(lp, n, whole) + ref.glm.shared(lp, n, whole)
    x2 = n.reshape(14, -1)
    total, shared = 0.0, None
    for k in range(2):
        moe = M.DroplessMoE(D, CFG["moe_intermediate_size"], 8, top_k=2,
                            scoring="sigmoid", n_shared=1, held=(4 * k, 4),
                            score_bias=True, routed_scale=2.0)
        p = {"router_w": lp["moe.router"], "score_bias": lp["moe.bias"],
             "w_gate": lp["moe.gate"][4 * k:4 * k + 4],
             "w_up": lp["moe.up"][4 * k:4 * k + 4],
             "w_down": lp["moe.down"][4 * k:4 * k + 4],
             "shared_gate": lp["shared.gate"], "shared_up": lp["shared.up"],
             "shared_down": lp["shared.down"]}
        y, _ = moe.routed(p, x2)
        shared = moe.shared(p, x2)
        total = total + y - shared
    _close(total + shared, want.reshape(14, -1))
    hc, hp, _ = _hc_and_leaves(flat, "ffn_hc", "5")
    X = jax.random.normal(jax.random.PRNGKey(10), (2, 7, N, D))
    co = hc.coefficients(hp, X)
    _close(hc.post(co, X, (total + shared).reshape(2, 7, D)),
           hc.post(co, X, want))


# -- (f) training by autodiff ---------------------------------------------
def test_local_optimizer_takes_a_step_through_the_sweeps():
    """Plain autodiff through the maps (sigmoids, ``exp``, twenty
    unrolled sweeps), the expanded attention and the expert layer: every
    hyper-connection leaf moves."""
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.optim import Adam, LocalOptimizer, max_iteration

    model = _model(_flat(), output="log_probs")
    before = jax.tree_util.tree_map(np.asarray, model.param_tree())
    seq = (np.arange(17 * 8) % 7 + 1).reshape(8, 17).astype(np.float32)
    data = DataSet.array([Sample(s[:-1], s[1:]) for s in seq])
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True)

    def loss():
        out = _logits(model, jnp.asarray(seq[:, :-1]))
        return float(crit.forward(out, jnp.asarray(seq[:, 1:])))

    start = loss()
    opt = LocalOptimizer(model, data, crit, batch_size=8)
    opt.set_optim_method(Adam(3e-3)).set_end_when(max_iteration(2))
    opt.optimize()
    assert loss() < start
    after = model.param_tree()
    for layer in ("1", "2"):
        for child in ("4", "5"):
            for leaf in ("phi", "alpha_pre", "alpha_post", "alpha_res",
                         "b_pre", "b_post", "b_res"):
                moved = np.abs(np.asarray(after[layer][child][leaf])
                               - before[layer][child][leaf]).max()
                assert moved > 0, (layer, child, leaf)
    assert np.abs(np.asarray(after["2"]["3"]["score_bias"])
                  - before["2"]["3"]["score_bias"]).max() == 0


# -- (g) scopes and the counter ------------------------------------------------
def _run_of(gen):
    return [c.cell_contents for c in gen.__closure__
            if hasattr(c.cell_contents, "lower")][0]


def test_scopes_of_the_lowered_program_and_the_counter():
    from bigdl_tpu.telemetry.tracer import DEVICE_SCOPES

    model = _model(_flat())
    gen = G.make_generate(model)
    prompts = _prompts(4, 19, seed=5)
    text = _run_of(gen).lower(
        model.param_tree(), jnp.asarray(prompts), 11, jax.random.PRNGKey(0),
        jnp.float32(0), 0, jnp.float32(1), jnp.int32(0), jnp.int32(0), True,
        False).as_text(debug_info=True)
    for scope in ("mhc.coeffs", "mhc.sinkhorn", "mhc.pre", "mhc.post",
                  "mla.prefill_attend", "mla.absorb", "mla.attend",
                  "moe.expert_matmul"):
        assert scope in DEVICE_SCOPES and scope in text, scope
    # the three functions are jitted ONCE for all sublayers (prefill's
    # shapes and the step's: two traces each, not one a sublayer), so in
    # the lowered module a part's scope is inside its function and the
    # sublayer's scope at the call
    import re
    for fn in ("_coefficients", "_pre", "_post"):
        assert 1 <= len(re.findall(rf"func\.func private @{fn}\w*\(",
                                   text)) <= 2, fn
    for stretch in ("generate.decode_step", "generate.prefill"):
        for sub in ("block.attention", "block.mlp"):
            for fn in ("_coefficients", "_pre", "_post"):
                assert f"{stretch}/{sub}/jit({fn})" in text, (stretch, sub,
                                                              fn)
    # ... and in the COMPILED program's op_name the whole path, which is
    # what the readers match: tests/test_tpu_compile.py
    # the plain prompt attention is prefill's; a step attends the latent
    assert "generate.prefill/block.attention/mla.prefill_attend" in text
    assert "generate.decode_step/block.attention/mla.prefill_attend" \
        not in text
    ids, stats = gen(model.param_tree(), prompts, 11, return_stats=True)
    assert sorted(stats) == ["mhc_sinkhorn_err", "moe_counts"]
    err = np.asarray(stats["mhc_sinkhorn_err"])
    assert err.shape == () and err.dtype == np.float32 and 0 < err < 1e-4
    assert np.asarray(stats["moe_counts"]).shape == (EXPERT_LAYERS, 4)


def test_the_server_reports_the_counter_with_the_tokens():
    from bigdl_tpu.serving import InferenceServer
    from bigdl_tpu.telemetry import default_tracer

    model = _model(_flat())
    server = InferenceServer(model, max_batch=4,
                             generate_dtype=jnp.float32).start()
    try:
        prompts = _prompts(4, 19, seed=6)
        outs = [f.result(timeout=600) for f in
                [server.submit_generate(p, 11) for p in prompts]]
    finally:
        server.stop(30)
    assert all(r.ok for r in outs)
    direct = np.asarray(model.generate(prompts, max_new=11))[:, 19:]
    assert np.array_equal(np.stack([np.asarray(r.output) for r in outs]),
                          direct)
    fetch = [s for s in default_tracer().spans() if s.name == "serve.fetch"
             and s.args and "mhc_sinkhorn_err" in s.args]
    assert fetch
    for s in fetch:
        assert 0 < s.args["mhc_sinkhorn_err"] < 1e-4
        assert s.args["moe_load_max_over_mean"] >= 1.0


def test_held_dtypes_and_the_generators_cast():
    model = _model(param_dtype="bfloat16")
    keep = ("score_bias",) + nn.hyper_connection.COEFFICIENT_LEAVES
    assert set(keep) == set(M.FLOAT32_LEAVES)
    for path, leaf in jax.tree_util.tree_leaves_with_path(model.param_tree()):
        want = jnp.float32 if path[-1].key in keep else jnp.bfloat16
        assert leaf.dtype == want, path
    model.set_param_tree(program.to_tree(CFG, _flat()))
    tree = model.param_tree()
    assert tree["2"]["4"]["phi"].dtype == jnp.bfloat16
    assert tree["2"]["4"]["alpha_res"].dtype == jnp.float32
    assert tree["2"]["5"]["b_res"].dtype == jnp.float32
    out = _logits(model, jnp.asarray(_prompts(1, 5)))
    assert out.dtype == jnp.float32 and np.isfinite(np.asarray(out)).all()
    # a bfloat16 generate call keeps the six float32 and is finite
    ids, stats = G.make_generate(_model(_flat()),
                                 compute_dtype=jnp.bfloat16)(
        _model(_flat()).param_tree(), _prompts(2, 9), 5, return_stats=True)
    assert 0 < float(stats["mhc_sinkhorn_err"]) < 1e-4


# -- (h) the plain block's program did not move ----------------------------------
# sha256 of ``lower(...).as_text()`` at the parent commit (PR 41,
# cff5e07), this toy, this jax: a block without hyper-connections
# compiles to the program it compiled to before the sequential arm of
# ``_block_step`` asked the block for its sublayers' input and result
PINNED_JAX = "0.9.0"
PLAIN_GENERATE = "30af820584504c75"
PLAIN_APPLY = "be81a650de2c1a42"


def _plain():
    return LatentMoELM(vocab_size=97, embed_dim=64, num_heads=4, q_rank=32,
                       kv_rank=16, nope_dim=16, rope_dim=8, v_dim=24,
                       mlp_dim=96, expert_dim=32, num_layers=3, n_experts=8,
                       top_k=2, first_dense=1, n_shared=1, routed_scale=1.8,
                       max_len=128, output="logits")


@pytest.mark.skipif(jax.__version__ != PINNED_JAX,
                    reason="the pinned text is this jax version's")
def test_a_plain_latent_model_lowers_to_the_program_it_lowered_to():
    with jax.default_matmul_precision("default"):
        m = _plain()
        p, ids = m.param_tree(), jnp.ones((2, 9), jnp.int32)
        g = G.make_generate(m)
        gen = jax.jit(lambda p, ids: g(p, ids, 5, return_stats=True)
                      ).lower(p, ids).as_text()
        fwd = jax.jit(lambda p, ids: m.apply_fn(p, m.buffer_tree(), ids,
                                                False, None)[0]
                      ).lower(p, ids).as_text()
    assert hashlib.sha256(gen.encode()).hexdigest()[:16] == PLAIN_GENERATE
    assert hashlib.sha256(fwd.encode()).hexdigest()[:16] == PLAIN_APPLY
    assert "mhc_err" not in gen and m.modules[1].hyper is None
    assert len(m.modules[1].modules) == 4


# -- (i) what cannot carry the streams says so ------------------------------------
def test_the_paged_decoder_and_beam_search_refuse_the_block_by_name():
    from bigdl_tpu.serving.kvpool import KVPagePool

    pool = KVPagePool(num_pages=8, page_size=4, layers=LAYERS,
                      num_kv_heads=4, head_dim=16)
    with pytest.raises(TypeError, match="HyperConnection makes the "
                                        "residual of SequentialMoEBlock 4 "
                                        "streams"):
        G.PagedDecoder(_model(), pool)
    with pytest.raises(TypeError, match="beam search gathers every cache "
                                        "leaf .* HyperConnection"):
        G.make_beam_search(_model())
    with pytest.raises(TypeError, match="LatentAttention keeps no K or V"):
        G.make_generate(_model(), kv_dtype="int8")
