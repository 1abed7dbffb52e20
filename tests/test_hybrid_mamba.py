"""The Mamba-2 mixer, the hybrid block and their path through the
generator and the server, at toy widths on the CPU, against the plain
float32 reference in ``benchmark/reference/falcon_h1.py`` (time-
sequential recurrence, no chunks, no cache).  The toy configuration is
the benchmark's own fixture; ``A_log`` / ``dt_bias`` are drawn from the
PUBLISHED ranges (A in [1, 16], dt in [1e-3, 1e-1]) wherever a state
carried across a chunk edge or from prefill into decode has to matter —
with the benchmark's zeros the state halves every token and a lost
carry hides after a few positions."""
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
from benchmark.reference import common, falcon_h1 as ref  # noqa: E402
from bigdl_tpu import nn  # noqa: E402
from bigdl_tpu.models.generate import make_generate  # noqa: E402
from bigdl_tpu.models.hybrid_mamba import HybridMambaLM  # noqa: E402
from bigdl_tpu.nn.mamba import ssd_chunked_scan, ssm_step  # noqa: E402

with open(os.path.join(ROOT, "benchmark/tests/falconh1/benchmark/configs/"
                       "tiny-falcon-h1.json")) as _f:
    CFG = json.load(_f)
VOCAB, LAYERS = CFG["vocab_size"], CFG["num_hidden_layers"]


def _flat_params(seed=7, published=True):
    """The reference's flat float32 parameters; with ``published`` the
    mixer's small leaves leave their benchmark constants for the ranges
    the family publishes."""
    flat = dict(common.make_params(ref.param_specs(CFG), LAYERS,
                                   CFG["initializer_range"], seed))
    if published:
        rs = np.random.RandomState(seed)
        H, conv = CFG["mamba_n_heads"], flat["h.0.mixer.conv_b"].shape[0]
        for i in range(LAYERS):
            dt = np.exp(rs.uniform(np.log(1e-3), np.log(1e-1), H))
            flat[f"h.{i}.mixer.dt_bias"] = jnp.asarray(
                dt + np.log(-np.expm1(-dt)), jnp.float32)
            flat[f"h.{i}.mixer.A_log"] = jnp.asarray(
                np.log(rs.uniform(1.0, 16.0, H)), jnp.float32)
            flat[f"h.{i}.mixer.D"] = jnp.asarray(rs.normal(1, 0.3, H),
                                                 jnp.float32)
            flat[f"h.{i}.mixer.conv_b"] = jnp.asarray(
                rs.normal(0, 0.1, conv), jnp.float32)
    return flat


def _model(flat=None, **kw):
    model = HybridMambaLM(**{**CFG["program"]["kwargs"], **kw})
    if flat is not None:
        model.set_param_tree(program.to_tree(CFG, flat))
    return model


def _ref_logits(flat, ids0):
    """The reference's full forward: 0-based ids [B, T] -> [B, T, V]."""
    h = ref.embed(flat, ids0, CFG)
    for i in range(LAYERS):
        lp = {k.split(".", 2)[2]: v for k, v in flat.items()
              if k.startswith(f"h.{i}.")}
        h = ref.block(lp, h, CFG)
    return ref.head(flat, h, CFG)


def _served_gap(flat, ids):
    """Teacher-forced: by how much the logit of every generated token
    lies below the reference's best at its position, over the logit
    spread there.  ``ids`` 1-based [B, T0 + n] with the prompt first."""
    ids0 = jnp.asarray(ids) - 1
    lg = _ref_logits(flat, ids0[:, :-1])
    best, low = lg.max(-1), lg.min(-1)
    got = jnp.take_along_axis(lg, ids0[:, 1:, None], -1)[..., 0]
    return np.asarray((best - got) / (best - low))


def _prompts(n, t, seed=0):
    return np.random.RandomState(seed).randint(
        1, VOCAB + 1, (n, t)).astype(np.int32)


# -- (a) the block's forward is the reference's --------------------------
@pytest.mark.parametrize("published", [False, True],
                         ids=["benchmark_init", "published_ranges"])
def test_forward_equals_reference(published):
    flat = _flat_params(published=published)
    model = _model(flat)
    ids = _prompts(3, 21)         # 2 chunks of 8 and a ragged tail of 5
    out, _ = model.apply_fn(model.param_tree(), model.buffer_tree(),
                            jnp.asarray(ids), False, None)
    want = _ref_logits(flat, jnp.asarray(ids) - 1)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5 * scale, rtol=2e-4)


# -- (b) chunked scan == sequential recurrence ---------------------------
def _scan_inputs(T, seed=1):
    rs = np.random.RandomState(seed)
    b, H, P, G, N = 2, 4, 16, 2, 16
    x = rs.normal(size=(b, T, H, P)).astype(np.float32)
    B = rs.normal(size=(b, T, G, N)).astype(np.float32)
    C = rs.normal(size=(b, T, G, N)).astype(np.float32)
    dt = np.exp(rs.uniform(np.log(1e-3), np.log(1e-1),
                           (b, T, H))).astype(np.float32)
    A = -rs.uniform(1.0, 16.0, H).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (x, dt, A, B, C))


def _sequential(x, dt, A, B, C, state=None):
    b, T, H, P = x.shape
    state = jnp.zeros((b, H, P, B.shape[-1])) if state is None else state
    ys = []
    for t in range(T):
        y, state = ssm_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], state)
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("T,chunk", [(8, 8), (24, 8), (29, 8), (3, 8),
                                     (70, 2)],
                         ids=["one_chunk", "three_chunks",
                              "three_chunks_and_tail", "under_a_chunk",
                              "rolled_carry_beyond_32_chunks"])
def test_chunked_scan_equals_sequential(T, chunk):
    x, dt, A, B, C = _scan_inputs(T)
    y, s = ssd_chunked_scan(x, dt, A, B, C, chunk=chunk)
    y_seq, s_seq = _sequential(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_seq),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_seq),
                               atol=2e-5, rtol=2e-5)


def test_chunked_scan_carries_a_given_state_and_hands_it_on():
    """A scan over the first 13 positions, then one that starts from its
    state, equals one scan over all 29 — and a lost carry does not: the
    state matters at these decays."""
    x, dt, A, B, C = _scan_inputs(29)
    cut = lambda a, lo, hi: a[:, lo:hi]
    y_all, s_all = ssd_chunked_scan(x, dt, A, B, C, chunk=8)
    _, s_mid = ssd_chunked_scan(cut(x, 0, 13), cut(dt, 0, 13), A,
                                cut(B, 0, 13), cut(C, 0, 13), chunk=8)
    y_b, s_b = ssd_chunked_scan(cut(x, 13, 29), cut(dt, 13, 29), A,
                                cut(B, 13, 29), cut(C, 13, 29), chunk=8,
                                state=s_mid)
    np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_all[:, 13:]),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(s_b), np.asarray(s_all),
                               atol=2e-5, rtol=2e-5)
    y_lost, _ = ssd_chunked_scan(cut(x, 13, 29), cut(dt, 13, 29), A,
                                 cut(B, 13, 29), cut(C, 13, 29), chunk=8)
    # sixteen positions on, the lost state is still a large part of y
    assert float(jnp.abs(y_lost[:, -1] - y_all[:, -1]).max()) > 0.1


# -- (c) prefill, then decode through the cache --------------------------
def test_generate_agrees_with_reference_by_teacher_forced_logits():
    flat = _flat_params()
    model = _model(flat)
    prompts = _prompts(3, 19)      # two chunks and a ragged tail
    ids = np.asarray(model.generate(prompts, max_new=12))
    assert ids.shape == (3, 31) and (ids[:, :19] == prompts).all()
    assert ids.min() >= 1 and ids.max() <= VOCAB
    gap = _served_gap(flat, ids)[:, 18:]
    assert gap.max() < 1e-4, gap.max()


def _serve(model, prompts, max_new, max_batch):
    from bigdl_tpu.serving import InferenceServer

    server = InferenceServer(model, max_batch=max_batch,
                             batch_window_s=0.2,
                             generate_dtype=jnp.dtype("float32")).start()
    try:
        futs = [server.submit_generate(p, max_new) for p in prompts]
        res = [f.result(timeout=300) for f in futs]
    finally:
        server.stop(30)
    assert all(r.ok for r in res), [r.error for r in res if not r.ok]
    return np.stack([np.asarray(r.output) for r in res]), res


def test_server_generate_agrees_with_reference():
    """Three requests in a bucket of four: the fourth row is padding
    and carries a state of its own."""
    flat = _flat_params()
    model = _model(flat)
    prompts = _prompts(3, 11, seed=5)
    out, res = _serve(model, prompts, max_new=9, max_batch=4)
    assert {r.bucket for r in res} <= {1, 2, 4}
    ids = np.concatenate([prompts, out], 1)
    gap = _served_gap(flat, ids)[:, 10:]
    assert gap.max() < 1e-4, gap.max()


# -- (d) rows of a bucket are independent --------------------------------
def test_rows_do_not_change_with_what_other_rows_hold():
    flat = _flat_params()
    model = _model(flat)
    prompts = _prompts(4, 10, seed=9)
    both = np.asarray(model.generate(prompts, max_new=8))
    for b in range(4):
        alone = np.asarray(model.generate(prompts[b:b + 1], max_new=8))
        np.testing.assert_array_equal(both[b], alone[0])
    # the same row beside other neighbours, padding (a repeated row) too
    other = np.concatenate([prompts[:1], _prompts(3, 10, seed=10)])
    np.testing.assert_array_equal(
        np.asarray(model.generate(other, max_new=8))[0], both[0])


# -- (e) the broken-path twin: one slot's state altered ------------------
@pytest.mark.parametrize("what", ["ssm", "conv"])
def test_one_slot_with_an_altered_state_fails_the_comparison(what,
                                                             monkeypatch):
    """The state prefill hands to decode is wronged in ONE row of the
    bucket (another row's state in its place): that row's tokens leave
    the reference, the other rows' do not.  At the benchmark's own
    initialisers (A = -1, dt = softplus of the projection), where the
    state is a large part of the mixer's output."""
    flat = _flat_params(published=False)
    model = _model(flat)
    prompts = _prompts(4, 19, seed=3)
    real = nn.Mamba2Mixer.sequence

    def broken(self, params, u, state=None):
        out, st = real(self, params, u, state)
        st = dict(st)
        st[what] = st[what].at[2].set(st[what][1])
        return out, st

    monkeypatch.setattr(nn.Mamba2Mixer, "sequence", broken)
    ids = np.asarray(make_generate(model)(model.param_tree(), prompts, 12))
    gap = _served_gap(flat, ids)[:, 18:]
    assert gap[[0, 1, 3]].max() < 1e-4
    assert gap[2].max() > 1e-2, gap[2].max()


# -- (f) parameters held in the dtype they are served in -----------------
def _lowered_run(model, gen, batch=2, t0=5, max_new=4):
    run = next(c.cell_contents for c in gen.__closure__
               if hasattr(c.cell_contents, "lower"))
    return run.lower(model.param_tree(), jnp.ones((batch, t0), jnp.int32),
                     max_new, jax.random.PRNGKey(0), jnp.float32(0), 0,
                     jnp.float32(1), jnp.int32(0), jnp.int32(0), True, False)


def test_bf16_held_parameters_are_accepted_cast_and_never_converted():
    import re

    model = _model(param_dtype="bfloat16")
    leaves = jax.tree_util.tree_leaves(model.param_tree())
    assert {str(a.dtype) for a in leaves} == {"bfloat16"}   # drawn so
    flat = _flat_params(published=False)
    model.set_param_tree(program.to_tree(CFG, flat))        # f32 in
    held = jax.tree_util.tree_leaves(model.param_tree())
    assert {str(a.dtype) for a in held} == {"bfloat16"}
    assert model.param_tree()["0"]["weight"].shape == (VOCAB, 64)
    # a leaf already in the held dtype is taken as it is: no second copy
    tree = model.param_tree()
    model.set_param_tree(tree)
    assert all(a is b for a, b in zip(
        jax.tree_util.tree_leaves(tree),
        jax.tree_util.tree_leaves(model.param_tree())))
    # the compiled generator converts no weight: the only parameters
    # that change dtype are the mixer's three [heads] vectors, which
    # compute in float32 whatever they are held in
    text = _lowered_run(model, make_generate(
        model, compute_dtype=jnp.bfloat16)).as_text()
    n_params = len(held)
    converted = re.findall(
        r"stablehlo\.convert %arg(\d+) : \(tensor<([0-9x]*)xbf16>\)", text)
    assert all(int(i) < n_params for i, _ in converted)
    assert {shape for _, shape in converted} <= {str(CFG["mamba_n_heads"])}
    # and it serves: tokens within bf16's rounding of the reference
    ids = np.asarray(model.generate(_prompts(2, 9), max_new=6,
                                    compute_dtype=jnp.bfloat16))
    assert _served_gap(flat, ids)[:, 8:].max() < 0.08


def test_f32_held_model_still_casts_inside_the_call():
    """The dense path's contract is unchanged: float32-held weights are
    cast to the compute dtype inside the program."""
    model = _model(_flat_params(published=False))
    text = _lowered_run(model, make_generate(
        model, compute_dtype=jnp.bfloat16)).as_text()
    assert "xf32>) -> tensor<101x64xbf16>" in text


# -- (g) gradient buffers appear on first use ----------------------------
def test_a_fresh_model_owns_no_gradient_buffer_until_one_is_asked_for():
    model = _model()
    mods = list(model.modules_iter())
    assert all(not m.grads for m in mods)
    model.param_tree(), model.n_parameters(), model.evaluate()
    model.generate(_prompts(1, 4), max_new=2)
    assert all(not m.grads for m in mods)           # serving asks for none
    grads = jax.tree_util.tree_leaves(model.grad_tree())
    params = jax.tree_util.tree_leaves(model.param_tree())
    assert [g.shape for g in grads] == [p.shape for p in params]
    assert all(float(jnp.abs(g).max()) == 0.0 for g in grads)
    lin = nn.Linear(3, 2)
    assert not lin.grads
    x = jnp.ones((4, 3))
    lin.forward(x)
    lin.backward(x, jnp.ones((4, 2)))               # backward makes them
    np.testing.assert_allclose(np.asarray(lin.grads["weight"]),
                               np.full((2, 3), 4.0))
    lin.zero_grad_parameters()
    assert float(jnp.abs(lin.grads["weight"]).max()) == 0.0
    fresh = nn.Linear(3, 2)
    fresh.zero_grad_parameters()                    # and so does zeroing
    assert set(fresh.grads) == {"weight", "bias"}
    w, g = nn.Linear(3, 2).parameters()             # and parameters()
    assert [a.shape for a in w] == [a.shape for a in g]


# -- (h) gradients by autodiff equal the reference's ---------------------
def test_gradients_equal_jax_grad_of_the_reference_loss():
    flat = _flat_params()
    model = _model(flat)
    ids = _prompts(2, 13, seed=2)
    x, y = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:]) - 1

    def ref_loss(p):
        return common.token_xent_sum(_ref_logits(p, x - 1), y) / y.size

    def model_loss(tree):
        logits, _ = model.apply_fn(tree, model.buffer_tree(), x, True, None)
        return common.token_xent_sum(logits, y) / y.size

    want = jax.grad(ref_loss)(flat)
    got = program.from_tree(CFG, jax.grad(model_loss)(model.param_tree()))
    assert set(got) == set(want)
    for name in sorted(want):
        w = np.asarray(want[name])
        np.testing.assert_allclose(
            np.asarray(got[name]), w, rtol=2e-3,
            atol=2e-4 * max(float(np.abs(w).max()), 1e-6), err_msg=name)


def test_local_optimizer_trains_the_toy_model():
    """A cyclic stream: the loss falls under LocalOptimizer with no
    hand-written backward anywhere."""
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.optim import Adam, LocalOptimizer, max_iteration

    model = _model(output="log_probs")
    seq = (np.arange(17 * 8) % 7 + 1).reshape(8, 17).astype(np.float32)
    data = DataSet.array([Sample(s[:-1], s[1:]) for s in seq])
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True)

    def loss():
        out, _ = model.apply_fn(model.param_tree(), model.buffer_tree(),
                                jnp.asarray(seq[:, :-1]), False, None)
        return float(crit.forward(out, jnp.asarray(seq[:, 1:])))

    before = loss()
    opt = LocalOptimizer(model, data, crit, batch_size=8)
    opt.set_optim_method(Adam(3e-3)).set_end_when(max_iteration(30))
    opt.optimize()
    assert loss() < 0.6 * before


# -- the paged path refuses the block, loudly ----------------------------
def test_paged_path_refuses_a_block_with_a_recurrent_state():
    from bigdl_tpu.models.generate import PagedDecoder
    from bigdl_tpu.serving.kvpool import KVPagePool

    model = _model()
    pool = KVPagePool(num_pages=8, page_size=4, layers=LAYERS,
                      num_kv_heads=2, head_dim=8)
    with pytest.raises(TypeError, match="recurrent state"):
        PagedDecoder(model, pool)


def test_beam_of_one_equals_greedy():
    from bigdl_tpu.models.generate import make_beam_search

    model = _model(_flat_params())
    prompts = _prompts(2, 9, seed=4)
    greedy = np.asarray(model.generate(prompts, max_new=6))
    beam, _ = make_beam_search(model)(model.param_tree(), prompts, 6,
                                      num_beams=1)
    np.testing.assert_array_equal(np.asarray(beam), greedy)


# -- tracing: device scopes and what a dispatched bucket holds -----------
def test_device_scopes_name_the_lowered_operations():
    from bigdl_tpu.telemetry.tracer import DEVICE_SCOPES

    model = _model()
    text = _lowered_run(model, make_generate(model)).as_text(debug_info=True)
    for scope in DEVICE_SCOPES:
        # this model's: ``moe.*`` / ``block.*`` are the parallel expert
        # block's (tests/test_command_a_plus.py)
        if scope.startswith(("generate.", "mixer.")) and scope not in (
                "generate.cast_params",         # f32 held: no cast
                "generate.prefill_group"):      # the prompt went whole
            assert scope + "/" in text or scope + '"' in text, scope
    # the chunked scan runs in the prefill, the one-token step in the loop
    assert "generate.prefill/mixer.ssd_scan" in text
    assert "generate.decode_step/mixer.ssm_step/mixer.conv" in text
    # the chunk carry is unrolled: the one `while` is the decode scan
    assert text.count("stablehlo.while") == 1


def test_dispatch_span_says_what_the_bucket_holds():
    from bigdl_tpu.models.generate import cache_footprint
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.telemetry import default_tracer

    flat = _flat_params()
    model = _model(flat)
    rec = LAYERS * 4 * (4 * 16 * 16 * 4 + 3 * 128 * 4)   # ssm + conv tail

    def kv(positions):                 # K, V [4, 2, positions, 8] f32
        return LAYERS * 2 * 4 * 2 * positions * 8 * 4

    # 11 + 3 positions of this model's 48: the whole table is the cache
    got = cache_footprint(model, 4, 11, 3, compute_dtype=jnp.float32)
    assert got == {"kv_cache_bytes": kv(48), "recurrent_state_bytes": rec,
                   "kv_cache_positions": 48,
                   # by kind of layer and the prompt pass's groups (PR 46)
                   "kv_cache_bytes_window": 0, "kv_cache_bytes_full": kv(48),
                   "prefill_groups": 1,
                   "kv_attend": "einsum", "kv_attend_block": 0}
    # the same call on a long table holds 128 positions, not 640, and
    # the recurrent state does not care
    long = _model(flat, max_len=640)
    got = cache_footprint(long, 4, 11, 3, compute_dtype=jnp.float32)
    assert got == {"kv_cache_bytes": kv(128), "recurrent_state_bytes": rec,
                   "kv_cache_positions": 128,
                   "kv_cache_bytes_window": 0, "kv_cache_bytes_full": kv(128),
                   "prefill_groups": 1,
                   "kv_attend": "einsum", "kv_attend_block": 0}
    dense = TransformerLM(23, embed_dim=16, num_heads=2, mlp_dim=32,
                          num_layers=2, max_len=24)
    assert cache_footprint(dense, 2, 5, 7)["recurrent_state_bytes"] == 0
    tracer = default_tracer()
    tracer.enabled = True
    for served, positions in ((model, 48), (long, 128)):
        before = len(tracer.spans())
        _serve(served, _prompts(4, 11, seed=6), max_new=3, max_batch=4)
        spans = [s for s in tracer.spans()[before:]
                 if s.name == "serve.dispatch"]
        assert spans and all(
            s.args["recurrent_state_bytes"] > 0
            and s.args["kv_cache_positions"] == positions for s in spans)
        full = [s for s in spans if s.args["kv_cache_bytes"] == kv(positions)]
        assert full and full[0].args["recurrent_state_bytes"] == rec


def test_a_long_table_generates_the_ids_of_a_short_one():
    """(PR 28) the hybrid block's K/V cache is as long as prompt +
    max_new need (128 of 640 here; the 48-position twin holds 48): the
    tokens are the same, from ``generate``, from the server and under
    the int8 cache, and they are the reference's."""
    flat = _flat_params()
    short, long = _model(flat), _model(flat, max_len=640)
    prompts = _prompts(3, 19)
    want = np.asarray(short.generate(prompts, max_new=12))
    got = np.asarray(long.generate(prompts, max_new=12))
    np.testing.assert_array_equal(got, want)
    assert _served_gap(flat, got)[:, 18:].max() < 1e-4
    out, _ = _serve(long, prompts, max_new=12, max_batch=4)
    np.testing.assert_array_equal(out, want[:, 19:])
    q8 = [np.asarray(make_generate(m, kv_dtype="int8")(
        m.param_tree(), prompts, 12)) for m in (short, long)]
    np.testing.assert_array_equal(q8[1], q8[0])
    np.testing.assert_array_equal(q8[1][:, :20], want[:, :20])
    text = _lowered_run(long, make_generate(long)).as_text()
    assert "x128x8xf32>" in text and "x640x" not in text


# -- the constructor draws on the device ---------------------------------
def test_device_draw_keeps_the_distributions_and_the_host_numbers():
    """Under ``device_draw`` the random initialisers give the same
    distributions from ``jax.random``; outside it a layer's weights are
    the host stream's historical numbers, whatever was drawn inside."""
    from bigdl_tpu.nn.initialization import (MsraFiller, RandomNormal,
                                             RandomUniform, Xavier,
                                             device_draw)
    from bigdl_tpu.utils.rng import RNG

    RNG().set_seed(7)
    before = np.asarray(nn.Linear(64, 32).param_tree()["weight"])
    RNG().set_seed(7)
    with device_draw():
        u = np.asarray(RandomUniform().init((256, 64)))
        lo_hi = np.asarray(RandomUniform(-3.0, 5.0).init((4096,)))
        n = np.asarray(RandomNormal(2.0, 0.5).init((8192,)))
        x = np.asarray(Xavier().init((64, 32)))
        m = np.asarray(MsraFiller(False).init((4096, 8)))
        with device_draw():          # nests
            pass
        inside = np.asarray(nn.Linear(64, 32).param_tree()["weight"])
    assert u.dtype == np.float32 and np.abs(u).max() <= 64 ** -0.5
    assert u.std() == pytest.approx(64 ** -0.5 / 3 ** 0.5, rel=0.05)
    assert -3.0 <= lo_hi.min() < -2.9 and 4.9 < lo_hi.max() <= 5.0
    assert n.mean() == pytest.approx(2.0, abs=0.03)
    assert n.std() == pytest.approx(0.5, rel=0.05)
    assert np.abs(x).max() <= (6.0 / 96) ** 0.5
    assert m.std() == pytest.approx((2.0 / 8) ** 0.5, rel=0.05)
    assert not np.array_equal(inside, before)
    RNG().set_seed(7)
    np.testing.assert_array_equal(
        np.asarray(nn.Linear(64, 32).param_tree()["weight"]), before)


def test_the_model_is_drawn_on_the_device_and_reset_draws_anew():
    from bigdl_tpu.utils.rng import RNG

    RNG().set_seed(3)
    a = _model(param_dtype="bfloat16")
    RNG().set_seed(3)
    b = _model(param_dtype="bfloat16")
    la, lb = (jax.tree_util.tree_leaves(m.param_tree()) for m in (a, b))
    assert all(x.dtype == jnp.bfloat16 for x in la
               if jnp.issubdtype(x.dtype, jnp.floating))
    for x, y in zip(la, lb):        # keyed from the host stream: repeatable
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
    head = np.asarray(la[-1], np.float32)
    assert head.std() > 0 and np.abs(head).max() <= CFG["hidden_size"] ** -0.5
    b.reset()
    assert not np.array_equal(
        np.asarray(jax.tree_util.tree_leaves(b.param_tree())[-1], np.float32),
        head)
