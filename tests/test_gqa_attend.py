"""The attend of a per-head K/V decode step (``ops/gqa_attend.py``,
PR 41): the Pallas kernel, interpreted on the CPU, against the plain
grouped-query einsums of ``gqa_attend_reference`` it replaces where a
layer's K and V are large — one pass over the cache, and only as far as
it is written.

Tolerances.  float32: the two forms differ by summation order and by
WHEN the softmax's sum divides (the einsums normalise the probabilities
before ``P V``, the kernel after), 1e-5 of the largest value.  bfloat16:
the einsums round the scores to 8 bits before the softmax (the kernel
keeps them float32), the probabilities are rounded at different scales,
and the result once more: 2 ** -5 of the largest value.
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
from bigdl_tpu.models import generate as G  # noqa: E402
from bigdl_tpu.ops import gqa_attend as A  # noqa: E402

BLOCK = A.BLOCK_POSITIONS
B, HKV, T = 2, 2, 256


def _operands(G_, Dh, dt, B=B, Hkv=HKV, T=T, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, Hkv * G_, 1, Dh), dt),
            jax.random.normal(ks[1], (B, Hkv, T, Dh), dt),
            jax.random.normal(ks[2], (B, Hkv, T, Dh), dt))


def _kernel_arm(q, k, v, pos):
    """The kernel arm, interpreted, on the reference's operands."""
    block = A.attend_plan(q.shape[0], k.shape[1], k.shape[2], q.shape[3],
                          k.dtype, interpret=True)
    return A._gqa_attend_kernel(q[:, :, 0], k, v, pos, block,
                                True)[:, :, None]


def _close(got, want, dt):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    tol = 1e-5 if dt == jnp.float32 else 2.0 ** -5
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0)


# the first position, a block's last slot, the next block's first slot,
# the cache's last slot
@pytest.mark.parametrize("pos", [0, BLOCK - 1, BLOCK, T - 1])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("Dh", [64, 128])
@pytest.mark.parametrize("G_", [1, 4, 5, 16])
def test_kernel_equals_the_einsums_and_reads_nothing_beyond_pos(G_, Dh, dt,
                                                                pos):
    """Every K slot beyond ``pos`` holds NaN and every V slot ``inf``: a
    block wholly beyond it is never fetched, and in the block ``pos``
    falls in the scores AND the V rows beyond it are masked, so not even
    ``0 * inf`` reaches the result."""
    q, k, v = _operands(G_, Dh, dt)
    want = A.gqa_attend_reference(q, k, v, jnp.int32(pos), HKV * G_, HKV,
                                  Dh)
    dead = (jnp.arange(T) > pos)[None, None, :, None]
    got = _kernel_arm(q, jnp.where(dead, jnp.nan, k),
                      jnp.where(dead, jnp.inf, v), jnp.int32(pos))
    assert got.shape == want.shape == (B, HKV * G_, 1, Dh)
    assert got.dtype == dt
    _close(got, want, dt)


def test_a_cache_no_block_divides_is_one_block():
    q, k, v = _operands(4, 8, jnp.float32, T=48, seed=1)
    assert A.attend_plan(B, HKV, 48, 8, jnp.float32, interpret=True) == 48
    for pos in (0, 17, 47):
        want = A.gqa_attend_reference(q, k, v, jnp.int32(pos), HKV * 4,
                                      HKV, 8)
        _close(_kernel_arm(q, k, v, jnp.int32(pos)), want, jnp.float32)


# -- the rule ------------------------------------------------------------
# the LFM2 cell's step: 256 rows, 8 K/V heads of 4 query heads, a cache
# of 384 positions of 64 numbers: 201 MB of K and V
LFM2 = dict(B=256, Hkv=8, T=384, Dh=64, dtype=jnp.bfloat16)


def _on_a_tpu(monkeypatch):
    monkeypatch.setattr(A, "use_kernel", lambda interpret: True)


def test_the_rule_takes_the_claimed_shape_on_a_tpu_only(monkeypatch):
    assert A.attend_plan(**LFM2) == 0           # the CPU takes the einsums
    _on_a_tpu(monkeypatch)
    assert A.attend_plan(**LFM2) == BLOCK


@pytest.mark.parametrize("change", [
    pytest.param(dict(Tq=2), id="more_than_one_query"),
    pytest.param(dict(window=384), id="a_ring"),
    pytest.param(dict(dtype=jnp.int8), id="int8_kv"),
    pytest.param(dict(T=360), id="no_block_divides_T"),
    pytest.param(dict(B=1), id="under_the_threshold"),
    pytest.param(dict(Dh=96), id="a_head_of_neither_width"),
])
def test_the_rule_keeps_the_einsums(monkeypatch, change):
    _on_a_tpu(monkeypatch)
    assert A.attend_plan(**LFM2) == BLOCK
    assert A.attend_plan(**{**LFM2, **change}) == 0


def test_the_rule_is_bytes_not_rows(monkeypatch):
    _on_a_tpu(monkeypatch)
    # a head of whole lane tiles: K and V of one row at 128 positions
    # are 2 * 8 * 128 * 128 * 2 bytes
    rows = A.KERNEL_MIN_CACHE_BYTES // (2 * 8 * 128 * 128 * 2)
    plan = lambda B, T, Dh=128: A.attend_plan(B, 8, T, Dh, jnp.bfloat16)
    assert plan(rows, 128) == BLOCK and plan(rows - 1, 128) == 0
    # a long cache engages one row
    assert plan(1, 128 * rows) == BLOCK
    # a head of 64 engages from a cache a tenth of that: under the
    # einsums its every write is a scatter (8 rows of the LFM2 bucket
    # ladder and up)
    assert [plan(B, 384, 64) for B in (1, 4, 8, 256)] == [0, 0, BLOCK, BLOCK]
    assert plan(8, 384) == 0
    # the interpreter takes any size
    assert A.attend_plan(1, 1, 128, 8, jnp.float32, interpret=True) == BLOCK


@pytest.mark.parametrize("B,Hkv,T,Dh,want", [
    # the serving cells' decode steps (PERF.md §4) and the arm the sweep
    # and the cells themselves showed faster (PERF.md §6 "PR 41")
    pytest.param(256, 8, 384, 64, BLOCK, id="lfm2moe_decode_sat"),
    pytest.param(128, 8, 256, 128, BLOCK, id="commandaplus_decode_sat"),
    pytest.param(64, 4, 384, 128, 0, id="falconh1_decode_sat"),
    pytest.param(8, 8, 256, 128, 0, id="mistral7b_decode"),
    pytest.param(16, 8, 256, 128, 0, id="mistral7b_decode_sat"),
    pytest.param(8, 8, 2176, 128, BLOCK, id="mistral7b_prefill"),
])
def test_the_arm_of_each_serving_cell(monkeypatch, B, Hkv, T, Dh, want):
    _on_a_tpu(monkeypatch)
    assert A.attend_plan(B, Hkv, T, Dh, jnp.bfloat16) == want


def test_rows_a_program_by_bytes():
    # a K block of at most 2 MiB, a head of 64 counted as the lane tile
    # it fills; a power of two that divides the batch
    assert A._rows_per_program(256, 8, BLOCK, 64, 2) == 8
    assert A._rows_per_program(256, 8, BLOCK, 128, 2) == 8
    assert A._rows_per_program(64, 4, BLOCK, 128, 2) == 16
    assert A._rows_per_program(24, 8, BLOCK, 128, 2) == 8
    assert A._rows_per_program(7, 8, BLOCK, 128, 2) == 1
    assert A._rows_per_program(256, 8, BLOCK, 128, 4) == 4


# -- end to end on the benchmark's toy configurations ---------------------
TOYS = {
    "gpt2": "benchmark/tests/tiny/benchmark/configs/tiny-gpt2.json",
    "mistral": "benchmark/tests/tiny/benchmark/configs/tiny-mistral.json",
    "falcon_h1": "benchmark/tests/falconh1/benchmark/configs/"
                 "tiny-falcon-h1.json",
    "command_a_plus": "benchmark/tests/commandaplus/benchmark/configs/"
                      "tiny-command-a-plus.json",
    "glm": "benchmark/tests/glm47flash/benchmark/configs/"
           "tiny-glm-4.7-flash.json",
    "lfm2": "benchmark/tests/lfm2moe/benchmark/configs/tiny-lfm2-moe.json",
}


def _toy(name, **over):
    with open(os.path.join(ROOT, TOYS[name])) as f:
        cfg = json.load(f)
    return cfg, program.model_class(cfg)(**{**cfg["program"]["kwargs"],
                                            **over})


def _generate_jaxpr(model, params, prompts, max_new, **make):
    """The jaxpr of the model's one compiled greedy generate program."""
    gen = G.make_generate(model, **make)
    run = [c.cell_contents for c in gen.__closure__
           if hasattr(c.cell_contents, "lower")][0]
    return str(jax.make_jaxpr(
        lambda p, ids: run(p, ids, max_new, jax.random.PRNGKey(0),
                           jnp.float32(0), 0, jnp.float32(1), jnp.int32(0),
                           jnp.int32(0), True, False))(params, prompts))


def _bf16_jaxpr(model):
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), model.param_tree())
    return _generate_jaxpr(model, shapes, jnp.ones((2, 9), jnp.int32), 7,
                           compute_dtype=jnp.bfloat16)


@pytest.mark.parametrize("name", list(TOYS))
def test_the_einsum_arm_is_the_program_it_was(monkeypatch, name):
    """Where the rule says 0 the decode step calls the reference as it
    did before there was a rule (``_decode_machinery._attend``: the
    parent's two calls, verbatim, after the kernel's branch): no kernel
    in the program, and the SAME program whether the rule said 0 for
    the backend or, on a TPU, for the toy's size."""
    _, model = _toy(name)
    got = _bf16_jaxpr(model)
    assert "pallas_call" not in got
    _on_a_tpu(monkeypatch)
    want = _bf16_jaxpr(model)
    assert hashlib.sha256(got.encode()).hexdigest() \
        == hashlib.sha256(want.encode()).hexdigest()


def _force_kernel(monkeypatch):
    """The kernel arm on the CPU: the op's own shape rule patched to say
    yes wherever the kernel may run — blocks of 32, so a toy's cache of
    64 positions is a walk of two — and its call interpreted."""
    real_plan, real = A.attend_plan, A._gqa_attend_kernel

    def plan(B, Hkv, T, Dh, dtype, Tq=1, window=None, interpret=False):
        return 32 if real_plan(B, Hkv, T, Dh, dtype, Tq, window, True) else 0

    monkeypatch.setattr(A, "attend_plan", plan)
    monkeypatch.setattr(
        A, "_gqa_attend_kernel",
        lambda *a: real(*a[:-1], True))     # the last is ``interpret``


@pytest.mark.parametrize("name", ["mistral", "lfm2", "falcon_h1"])
def test_greedy_tokens_of_the_kernel_arm_are_the_einsum_arms(monkeypatch,
                                                             name):
    with jax.default_matmul_precision("highest"):
        cfg, model = _toy(name, max_len=64)
        layers = sum(
            "k" in jax.eval_shape(lambda b=b: b.state_init(1, jnp.float32, 8))
            for b in model.modules[1:1 + G._check_model(model)[1]])
        # 25 + 11 tokens: the steps' positions cross from the walk's
        # first block of 32 into its second
        prompts = np.random.RandomState(2).randint(
            1, cfg["vocab_size"] + 1, (3, 25)).astype(np.int32)
        params = model.param_tree()
        plain = np.asarray(G.make_generate(model)(params, prompts, 11))
        foot = G.cache_footprint(model, 3, 25, 11)
        assert (foot["kv_attend"], foot["kv_attend_block"]) == ("einsum", 0)
        _force_kernel(monkeypatch)
        foot = G.cache_footprint(model, 3, 25, 11)
        assert foot["kv_cache_positions"] == 64
        assert (foot["kv_attend"], foot["kv_attend_block"]) == ("kernel", 32)
        text = _generate_jaxpr(model, params, jnp.asarray(prompts), 11)
        # one kernel a K/V layer in the decode step, none in prefill
        assert text.count("pallas_call") == layers > 0
        assert np.array_equal(
            np.asarray(G.make_generate(model)(params, prompts, 11)), plain)
        beam, _ = G.make_beam_search(model)(params, prompts, 11,
                                            num_beams=1)
        assert np.array_equal(np.asarray(beam), plain)


def test_a_ring_and_int8_keep_the_einsums_under_a_forced_kernel(monkeypatch):
    """Command A+'s toy has three sliding layers in four, window 8: their
    caches are rings and keep the einsums; the full layer takes the
    kernel.  Under ``kv_dtype="int8"`` no layer does."""
    _, model = _toy("command_a_plus")
    _force_kernel(monkeypatch)

    def kernels(**kw):
        return _generate_jaxpr(model, model.param_tree(),
                               jnp.ones((3, 25), jnp.int32), 11,
                               **kw).count("pallas_call")

    assert kernels() == 1
    assert G.cache_footprint(model, 3, 25, 11)["kv_attend"] == "kernel"
    assert kernels(kv_dtype="int8") == 0
    assert G.cache_footprint(model, 3, 25, 11,
                             kv_dtype="int8")["kv_attend"] == "einsum"


@pytest.mark.parametrize("forced", [False, True], ids=["einsum", "kernel"])
def test_dispatch_spans_say_which_arm_the_program_compiled(monkeypatch,
                                                           forced):
    from bigdl_tpu.serving import InferenceServer
    from bigdl_tpu.telemetry import default_tracer

    cfg, model = _toy("lfm2")
    if forced:
        _force_kernel(monkeypatch)
    server = InferenceServer(model, max_batch=4,
                             generate_dtype=jnp.float32).start()
    try:
        prompts = np.random.RandomState(6).randint(
            1, cfg["vocab_size"] + 1, (4, 19)).astype(np.int32)
        futs = [server.submit_generate(p, 11) for p in prompts]
        outs = [f.result(timeout=600) for f in futs]
    finally:
        server.stop(30)
    assert all(r.ok for r in outs)
    spans = [s for s in default_tracer().spans()
             if s.name == "serve.dispatch"]
    assert spans
    for s in spans:
        rows = s.args["kv_cache_bytes"] // (2 * 2 * 64 * 8 * 4)
        want = G.cache_footprint(model, rows, 19, 11,
                                 compute_dtype=jnp.float32)
        assert (s.args["kv_attend"], s.args["kv_attend_block"]) == (
            want["kv_attend"], want["kv_attend_block"]) == (
                ("kernel", 32) if forced else ("einsum", 0))
