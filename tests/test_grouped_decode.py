"""The grouped product of a decode step's expert layer
(``ops/grouped_decode.py``, PR 43): the Pallas kernel, interpreted on
the CPU, against ``jax.lax.ragged_dot`` — the arm it replaces megablox's
``gmm`` and the compiler's ``ragged-dot`` with where
``parallel.moe.grouped_plan`` says so — on UNEVEN group sizes; and the
plan itself at the five serving cells' shapes.

Tolerances.  The kernel and ``ragged_dot`` both accumulate in float32
and round once; they differ by summation order: 1e-5 of the largest
value in float32, 2 ** -7 (a bfloat16 rounding step) in bfloat16.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from bigdl_tpu.ops import grouped_decode as GD
from bigdl_tpu.parallel import moe

# cell -> (embed D, expert F, rows of a full bucket's decode buffer)
CELLS = {"lfm2": (2048, 1536, 1024), "xing4": (3584, 1024, 1024),
         "glm": (2048, 1536, 1024), "commandaplus": (4096, 4096, 1024),
         "smallthinker": (2560, 768, 192)}


@pytest.fixture
def on_tpu(monkeypatch):
    """The TPU's branch of the plan (shapes alone decide the rest)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("product", ["up", "down"])
@pytest.mark.parametrize("cell", ["lfm2", "xing4", "glm", "smallthinker"])
def test_plan_at_the_serving_cells(on_tpu, cell, product):
    """An expert's WHOLE matrix is the tile — one k tile, so a hit
    expert's weights are one copy and the float32 sum is written once;
    all ``n`` columns, so the copy is contiguous — within 8 MB and
    inside the kernel's VMEM, at every bucket of the ladder that is
    whole products: of 128 rows, or of 64 where 128 does not divide the
    buffer (a 16-row bucket's 64; SmallThinker's 32 rows x 6 = 192)."""
    D, F, R = CELLS[cell]
    k, n = (D, F) if product == "up" else (F, D)
    for rows in (64, 128, 192, 256, 512, R, 2048):
        chunk = 64 if rows % 128 else 128
        impl, tiles = moe.grouped_plan(rows, k, n, jnp.bfloat16)
        assert (impl, tiles) == ("grouped_decode", (chunk, k, n))
        assert k * n * 2 <= GD.WHOLE_BYTES
        assert GD.vmem_bytes(rows, k, n, 2, chunk) <= GD.VMEM_BYTES


@pytest.mark.parametrize("R,k,n,dt,why", [
    (1024, 4096, 4096, jnp.bfloat16, "Command A+: an expert is 32 MB"),
    (1024, 2048, 1536, jnp.float32, "LFM2's shape in float32: 12 MB"),
    (2048, 512, 8192, jnp.bfloat16,
     "8 MB a matrix, but the output block twice is 64 MiB of VMEM"),
])
def test_shape_without_a_plan_keeps_todays_gmm_tiles(on_tpu, R, k, n, dt,
                                                     why):
    """A matrix that is no one tile by the rule of BYTES keeps PR 32's
    megablox tiles: 1024 where that divides, else 512."""
    assert moe.grouped_plan(R, k, n, dt) == (
        "gmm", (128, 1024 if k % 1024 == 0 else 512,
                1024 if n % 1024 == 0 else 512)), why


@pytest.mark.parametrize("R,k,n", [
    (32768, 2048, 1536),      # a prefill piece
    (1000, 2048, 1536),       # no whole row tiles
    (1024, 2048, 1300),       # a width that is no whole lane tiles
    (96, 2560, 768),          # SmallThinker's 16-row bucket: no whole
    (32, 2048, 1536),         # products of 64 rows; LFM2's 8-row one
])
def test_other_buffers_keep_ragged_dot(on_tpu, R, k, n):
    assert moe.grouped_plan(R, k, n, jnp.bfloat16) == ("ragged", None)


def test_off_the_tpu_the_plan_is_ragged_dot():
    assert moe.grouped_plan(1024, 2048, 1536, jnp.bfloat16) == ("ragged",
                                                                None)


# rows 256, eight groups unless said: what a router deals, and the edges
SIZES = {
    "uneven, groups straddle the 128-row tiles":
        [3, 50, 17, 41, 30, 9, 64, 42],
    "empty groups first, between and last": [0, 0, 70, 0, 0, 90, 1, 0],
    "one group holds every row": [0, 0, 0, 256, 0, 0, 0, 0],
    "one group longer than a product's chunk": [5, 200, 51, 0, 0, 0, 0, 0],
    "fewer rows than the buffer": [3, 5, 0, 7, 11, 2, 1, 4],
    "a single row": [0, 0, 0, 0, 0, 1, 0, 0],
    "no row at all": [0] * 8,
    "sixty-four groups of a few rows": list(np.random.default_rng(0)
                                            .multinomial(250, [1 / 64] * 64)),
}
# SmallThinker's decode buffer, 32 rows x 6 choices over 64 experts as
# its router deals them: the busiest at 4 x the mean of 3, some empty
SKEWED_192 = list(np.random.default_rng(47).permutation(
    [12, 9, 7] + [5] * 4 + [4] * 10 + [3] * 22 + [2] * 19 + [0] * 6))


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("chunk", [128, 32])
@pytest.mark.parametrize("case", list(SIZES))
def test_kernel_equals_ragged_dot(case, chunk, dt):
    """Only the rows under ``sum(sizes)`` are compared (the rest is
    undefined by contract); the rows past it hold NaN, which no defined
    row may pick up."""
    sizes = np.asarray(SIZES[case], np.int32)
    R, k, n, total = 256, 256, 384, int(sizes.sum())
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 2)
    xs = jax.random.normal(ks[0], (R, k), dt)
    xs = jnp.where(jnp.arange(R)[:, None] < total, xs, jnp.nan)
    w = jax.random.normal(ks[1], (len(sizes), k, n), dt) / k ** 0.5
    got = GD.grouped_decode(xs, w, jnp.asarray(sizes), chunk=chunk,
                            interpret=True)
    assert got.shape == (R, n) and got.dtype == dt
    want = lax.ragged_dot(xs, w, jnp.asarray(sizes))
    got, want = (np.asarray(a[:total], np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    tol = 1e-5 if dt == jnp.float32 else 2.0 ** -7
    assert np.abs(got - want).max(initial=0.0) <= tol * max(
        np.abs(want).max(initial=0.0), 1.0)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_equals_ragged_dot_at_192_rows_in_chunks_of_64(dt):
    """The SmallThinker step's buffer: 192 rows are no whole 128-row
    chunks, so the plan walks it in products of 64 — 64 groups, the
    busiest 12 rows (4 x the mean), six of them empty, the last chunk's
    rows shared by many groups."""
    sizes = np.asarray(SKEWED_192, np.int32)
    assert (len(sizes), sizes.sum(), sizes.max(), (sizes == 0).sum()) == (
        64, 192, 12, 6)
    R, k, n = 192, 256, 384
    assert GD.chunk_rows(R) == 64
    ks = jax.random.split(jax.random.PRNGKey(47), 2)
    xs = jax.random.normal(ks[0], (R, k), dt)
    w = jax.random.normal(ks[1], (len(sizes), k, n), dt) / k ** 0.5
    got = GD.grouped_decode(xs, w, jnp.asarray(sizes), chunk=64,
                            interpret=True)
    want = lax.ragged_dot(xs, w, jnp.asarray(sizes))
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    tol = 1e-5 if dt == jnp.float32 else 2.0 ** -7
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0)


@pytest.mark.parametrize("R,chunk", [(2048, 128), (1024, 128), (192, 64),
                                     (64, 64), (96, 0), (32, 0), (1000, 0)])
def test_rows_a_product_from_the_buffers_rows(R, chunk):
    assert GD.chunk_rows(R) == chunk
    assert GD.fits(R, 2560, 768, 2, chunk) == bool(chunk)


def test_kernel_refuses_a_buffer_without_whole_chunks():
    xs, w = jnp.zeros((200, 256)), jnp.zeros((2, 256, 128))
    with pytest.raises(ValueError, match="no whole chunks"):
        GD.grouped_decode(xs, w, jnp.array([3, 4], jnp.int32),
                          interpret=True)


def test_the_expert_layer_on_the_kernel_arm_and_its_schedule_event(
        monkeypatch):
    """``DroplessMoE`` through :func:`grouped_matmul` with the plan a
    TPU would give (the kernel interpreted): the same output as on
    ``ragged_dot``, and the dispatch's ``moe.schedule`` event names the
    arm, the first product's tiles and ONE k tile."""
    from bigdl_tpu.telemetry import default_tracer

    layer = moe.DroplessMoE(512, 512, 8, 4)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 512))   # 32 tokens
    params = layer.param_tree()
    want = layer.apply_fn(params, {}, x, False, None)[0]
    before = len([s for s in default_tracer().spans()
                  if s.name == "moe.schedule"])
    real = GD.grouped_decode
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(GD, "grouped_decode",
                        lambda *a, **kw: real(*a, interpret=True, **kw))
    got = layer.apply_fn(params, {}, x, False, None)[0]
    monkeypatch.undo()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    events = [s.args for s in default_tracer().spans()
              if s.name == "moe.schedule"][before:]
    assert [(e["tokens"], e["rows"], e["held"], e["k"], e["impl"],
             e["tiles"], e["k_tiles"]) for e in events] == [
        (32, 128, 8, 4, "grouped_decode", [128, 512, 512], 1)]
