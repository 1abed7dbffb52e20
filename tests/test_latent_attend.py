"""The absorbed attend of a latent decode step (``ops/latent_attend.py``,
PR 37): the Pallas kernel, interpreted on the CPU, against the plain
einsums it replaces where a layer's cache is large — one pass over the
cached latent, and only as far as it is written.

Tolerances.  float32: the two forms differ by summation order and by
WHEN the softmax's sum divides (the einsums normalise the probabilities
before ``P c_kv``, the kernel after), 1e-5 of the largest value.
bfloat16: the probabilities are rounded to 8 bits at different scales
(normalised against unnormalised), and the result once more: 2 ulp of
the largest value, 2 ** -6.
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
from bigdl_tpu.ops import latent_attend as L  # noqa: E402

RANK, ROPE, QK = 32, 8, 24      # toy widths; qk_dim only sets the scale
BLOCK = L.BLOCK_POSITIONS


def _operands(B, H, T, dt, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, H, 1, RANK), dt),
            jax.random.normal(ks[1], (B, H, 1, ROPE), dt),
            jax.random.normal(ks[2], (B, T, RANK), dt),
            jax.random.normal(ks[3], (B, ROPE, T), dt))


def _cases():
    """(T_cache, pos): the first position, a block's last slot, the next
    block's first slot, the cache's last slot."""
    for T in (128, 256, 640):
        for pos in sorted({0, BLOCK - 1, min(BLOCK, T - 1), T - 1}):
            yield pytest.param(T, pos, id=f"T{T}-pos{pos}")


@pytest.mark.parametrize("T,pos", list(_cases()))
@pytest.mark.parametrize("H", [20, 4])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_equals_the_einsums_and_reads_nothing_beyond_pos(dt, H, T,
                                                                pos):
    """Every slot beyond ``pos`` holds NaN: a block wholly beyond it is
    never fetched, and in the block ``pos`` falls in the scores AND the
    latent rows beyond it are masked, so not even ``0 * NaN`` reaches
    the result."""
    q_lat, q_rope, ckv, kr = _operands(4, H, T, dt)
    want = L.latent_attend_reference(q_lat, q_rope, ckv, kr, jnp.int32(pos),
                                     QK)
    dead = jnp.arange(T) > pos
    got = L.latent_attend(q_lat, q_rope,
                          jnp.where(dead[None, :, None], jnp.nan, ckv),
                          jnp.where(dead[None, None, :], jnp.nan, kr),
                          jnp.int32(pos), QK, interpret=True)
    assert got.shape == want.shape == (4, H, 1, RANK) and got.dtype == dt
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    tol = 1e-5 if dt == jnp.float32 else 2.0 ** -6
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0)


def test_a_cache_no_block_divides_is_one_block():
    q_lat, q_rope, ckv, kr = _operands(3, 4, 48, jnp.float32, seed=1)
    assert L.attend_plan(3, 48, RANK, ROPE, jnp.float32,
                         interpret=True) == 48
    for pos in (0, 17, 47):
        want = L.latent_attend_reference(q_lat, q_rope, ckv, kr,
                                         jnp.int32(pos), QK)
        got = L.latent_attend(q_lat, q_rope, ckv, kr, jnp.int32(pos), QK,
                              interpret=True)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-5


def test_the_arm_is_decided_by_shapes(monkeypatch):
    dt = jnp.bfloat16

    def plan(B, T, rank=512, **kw):
        return L.attend_plan(B, T, rank, 64, dt, **kw)

    # the CPU takes the einsums whatever the shapes
    assert plan(256, 640) == 0
    monkeypatch.setattr(L, "use_kernel", lambda interpret: True)
    # the cell's buckets: 8 rows and up hold a layer's cache of 5.9 MB
    # and more; 4 rows' 2.9 MB stay with the einsums
    assert [plan(B, 640) for B in (1, 2, 4, 8, 32, 256)] == [
        0, 0, 0, BLOCK, BLOCK, BLOCK]
    # a long cache engages a small bucket: the rule is bytes, not rows
    assert plan(1, 8192) == BLOCK
    # more than one query a row is the einsums' (prefill never asks)
    assert plan(256, 640, Tq=2) == 0
    # a cache that is no whole number of blocks (a max_len that cut it)
    # or a latent that is no whole number of lane tiles stays with the
    # einsums on a TPU; the interpreter takes either
    assert plan(256, 600) == 0 and plan(256, 640, rank=96) == 0
    assert plan(256, 600, interpret=True) == 600
    assert plan(2, 640, rank=96, interpret=True) == BLOCK
    # rows a program: a power of two that divides the batch, a ckv
    # block of at most 2 MiB
    assert L._rows_per_program(256, BLOCK, 512, 2) == 16
    assert L._rows_per_program(32, BLOCK, 512, 2) == 16
    assert L._rows_per_program(24, BLOCK, 512, 2) == 8
    assert L._rows_per_program(7, BLOCK, 512, 2) == 1
    assert L._rows_per_program(256, BLOCK, 512, 4) == 8


# -- end to end on the toy of tests/test_glm4_moe_lite.py -----------------
with open(os.path.join(ROOT, "benchmark/tests/glm47flash/benchmark/"
                       "configs/tiny-glm-4.7-flash.json")) as _f:
    GLM = json.load(_f)


def _force_kernel(monkeypatch):
    """The kernel arm on the CPU: the op's own shape rule patched to say
    yes — blocks of 32, so the toy's cache of 64 positions is a walk of
    two — and its call interpreted."""
    real = L._latent_attend_kernel
    monkeypatch.setattr(L, "attend_plan",
                        lambda B, T, rank, rope, dtype, Tq=1,
                        interpret=False: 32 if Tq == 1 else 0)
    monkeypatch.setattr(
        L, "_latent_attend_kernel",
        lambda *a, **kw: real(*a[:-1], True, **kw))   # last: ``interpret``


def test_greedy_tokens_of_the_kernel_arm_are_the_einsum_arms(monkeypatch):
    from bigdl_tpu.models.latent_moe import LatentMoELM

    with jax.default_matmul_precision("highest"):
        model = LatentMoELM(**GLM["program"]["kwargs"])
        # 25 + 11 tokens: the steps' positions cross from the walk's
        # first block of 32 into its second
        prompts = np.random.RandomState(2).randint(
            1, GLM["vocab_size"] + 1, (3, 25)).astype(np.int32)
        params = model.param_tree()
        plain = np.asarray(G.make_generate(model)(params, prompts, 11))
        assert G.cache_footprint(model, 3, 25, 11)["latent_attend"] \
            == "einsum"
        _force_kernel(monkeypatch)
        foot = G.cache_footprint(model, 3, 25, 11)
        assert foot["kv_cache_positions"] == 64
        assert (foot["latent_attend"], foot["latent_attend_block"]) == (
            "kernel", 32)
        gen = G.make_generate(model)
        run = [c.cell_contents for c in gen.__closure__
               if hasattr(c.cell_contents, "lower")][0]
        text = str(jax.make_jaxpr(
            lambda p, ids: run(p, ids, 11, jax.random.PRNGKey(0),
                               jnp.float32(0), 0, jnp.float32(1),
                               jnp.int32(0), jnp.int32(0), True, False))(
                                   params, jnp.asarray(prompts)))
        # one kernel a layer in the decode step, none in prefill
        assert text.count("pallas_call") == GLM["num_hidden_layers"]
        assert np.array_equal(np.asarray(gen(params, prompts, 11)), plain)
        beam, _ = G.make_beam_search(model)(params, prompts, 11, num_beams=1)
        assert np.array_equal(np.asarray(beam), plain)


# -- the programs that have no latent block did not move ------------------
def _generate_digest(cfg_path):
    with open(os.path.join(ROOT, cfg_path)) as f:
        cfg = json.load(f)
    model = program.model_class(cfg)(**cfg["program"]["kwargs"])
    gen = G.make_generate(model, compute_dtype=jnp.bfloat16)
    run = [c.cell_contents for c in gen.__closure__
           if hasattr(c.cell_contents, "lower")][0]
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), model.param_tree())
    text = str(jax.make_jaxpr(
        lambda p, ids: run(p, ids, 7, jax.random.PRNGKey(0), jnp.float32(0),
                           0, jnp.float32(1), jnp.int32(0), jnp.int32(0),
                           True, False))(
                               shapes, jnp.ones((2, 9), jnp.int32)))
    return hashlib.sha256(text.encode()).hexdigest(), text


@pytest.mark.parametrize("cfg_path", [
    pytest.param("benchmark/tests/tiny/benchmark/configs/tiny-mistral.json",
                 id="dense"),
    pytest.param("benchmark/tests/falconh1/benchmark/configs/"
                 "tiny-falcon-h1.json", id="hybrid_mamba"),
    pytest.param("benchmark/tests/commandaplus/benchmark/configs/"
                 "tiny-command-a-plus.json", id="command_a_plus"),
])
def test_programs_without_a_latent_block_never_reach_the_kernel(
        monkeypatch, cfg_path):
    """The generate program of a model with no latent block is the same
    jaxpr with ``ops.latent_attend`` importable and with its import
    poisoned and every function of it raising: nothing of the module is
    on their path, so nothing of this change can move them."""
    want, text = _generate_digest(cfg_path)
    assert "pallas_call" not in text
    for name in ("latent_attend", "attend_plan", "latent_attend_reference",
                 "_latent_attend_kernel"):
        monkeypatch.setattr(L, name, None)
    monkeypatch.setitem(sys.modules, "bigdl_tpu.ops.latent_attend", None)
    with pytest.raises(ImportError):
        from bigdl_tpu.ops.latent_attend import attend_plan  # noqa: F401
    got, _ = _generate_digest(cfg_path)
    assert got == want
