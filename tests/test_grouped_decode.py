"""The grouped product of a decode step's expert layer
(``ops/grouped_decode.py``, PR 43): the Pallas kernel, interpreted on
the CPU, against ``jax.lax.ragged_dot`` — the arm it replaces megablox's
``gmm`` and the compiler's ``ragged-dot`` with where
``parallel.moe.grouped_plan`` says so — on UNEVEN group sizes; and the
plan itself at the five serving cells' shapes.  And the grouped product
of a PIECE OF A PROMPT PASS (``ops/grouped_prefill.py``, PR 48: the
plan's arm for buffers of more than 2048 rows) the same way: the kernel
interpreted against ``ragged_dot``, its visit lists against a count on
the host, the plan at the five cells' pieces.

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
from bigdl_tpu.ops import grouped_prefill as GP
from bigdl_tpu.parallel import moe

# cell -> (embed D, expert F, rows of a full bucket's decode buffer)
CELLS = {"lfm2": (2048, 1536, 1024), "xing4": (3584, 1024, 1024),
         "glm": (2048, 1536, 1024), "commandaplus": (4096, 4096, 1024),
         "smallthinker": (2560, 768, 192)}
# cell -> (embed D, expert F, rows of one piece of its prompt pass,
# whether an expert's matrix is one tile of ``ops/grouped_prefill.py``)
PIECES = {"smallthinker": (2560, 768, 27648, True),
          "lfm2": (2048, 1536, 32768, True),
          "glm": (2048, 1536, 32768, True),
          "xing4": (3584, 1024, 32768, False),
          "commandaplus": (4096, 4096, 32768, False)}


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


@pytest.mark.parametrize("product", ["up", "down"])
@pytest.mark.parametrize("cell", list(PIECES))
def test_plan_of_a_prompt_piece_at_the_serving_cells(on_tpu, cell, product):
    """A buffer of more than 2048 rows is a piece of a prompt pass: it
    takes ``ops/grouped_prefill.py`` — one k tile, the whole width, 128
    rows a visit — where an expert's matrix is ONE tile within the
    kernel's 16 MiB of VMEM (SmallThinker's 3.9 MB: 9.9 MiB a call;
    LFM2's and GLM's 6.3 MB: 15.8), and keeps ``ragged_dot`` where it
    is not (Xing4.0's 7.3 MB: 17.3 MiB up, 19.8 down; Command A+'s
    32 MB)."""
    D, F, R, whole = PIECES[cell]
    k, n = (D, F) if product == "up" else (F, D)
    assert GP.fits(R, k, n, 2) == whole
    assert (GP.vmem_bytes(k, n, 2) <= GP.VMEM_BYTES) == whole
    assert moe.grouped_plan(R, k, n, jnp.bfloat16) == (
        ("grouped_prefill", (128, k, n)) if whole else ("ragged", None))
    # the smallest piece a prompt is cut to takes the same arm
    assert moe.grouped_plan(2176, k, n, jnp.bfloat16)[0] == (
        "grouped_prefill" if whole else "ragged")


@pytest.mark.parametrize("R,k,n", [
    (32768, 4096, 4096),      # a prefill piece of Command A+: 32 MB
    (32768, 4096, 2048),      # half its matrix: 16 MB, twice over VMEM
    (32768, 1024, 3584),      # Xing4.0's down product: 19.8 MiB
    (27648 + 64, 2560, 768),  # a prefill piece of no whole row tiles
    (27648, 2560, 800),       # a width that is no whole lane tiles
    (1000, 2048, 1536),       # no whole row tiles
    (1024, 2048, 1300),       # a width that is no whole lane tiles
    (96, 2560, 768),          # SmallThinker's 16-row bucket: no whole
    (32, 2048, 1536),         # products of 64 rows; LFM2's 8-row one
])
def test_other_buffers_keep_ragged_dot(on_tpu, R, k, n):
    assert moe.grouped_plan(R, k, n, jnp.bfloat16) == ("ragged", None)


@pytest.mark.parametrize("R", [1024, 27648, 32768])
def test_off_the_tpu_the_plan_is_ragged_dot(R):
    for D, F in ((2048, 1536), (2560, 768)):
        assert moe.grouped_plan(R, D, F, jnp.bfloat16) == ("ragged", None)
        assert moe.grouped_plan(R, F, D, jnp.bfloat16) == ("ragged", None)


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


# -- ops/grouped_prefill.py: a piece of a prompt pass (PR 48) -------------
# rows 512 in tiles of 128, eight groups
PIECE_SIZES = {
    "uneven, empty groups, groups that straddle a row tile":
        [0, 130, 3, 0, 200, 60, 0, 17],
    "every group ends on a row tile": [128, 256, 0, 128, 0, 0, 0, 0],
    "one group holds every row": [0, 0, 0, 512, 0, 0, 0, 0],
    "the last tile shared by four groups": [100, 100, 100, 100, 100, 5, 5,
                                            2],
    "a single row": [0, 0, 1, 0, 0, 0, 0, 0],
    "a group over three row tiles, the buffer full": [0, 300, 0, 0, 212, 0,
                                                      0, 0],
    "every held group one row, then one large": [1, 1, 1, 1, 1, 1, 1, 400],
    "no row at all": [0] * 8,
}


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(PIECE_SIZES))
def test_prefill_kernel_equals_ragged_dot(case, dt):
    """The rows under ``sum(sizes)`` are compared; the rows past it hold
    NaN, which no defined row may pick up."""
    sizes = np.asarray(PIECE_SIZES[case], np.int32)
    R, k, n, total = 512, 256, 384, int(sizes.sum())
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 2)
    xs = jax.random.normal(ks[0], (R, k), dt)
    xs = jnp.where(jnp.arange(R)[:, None] < total, xs, jnp.nan)
    w = jax.random.normal(ks[1], (len(sizes), k, n), dt) / k ** 0.5
    got = GP.grouped_prefill(xs, w, jnp.asarray(sizes), interpret=True)
    assert got.shape == (R, n) and got.dtype == dt
    want = lax.ragged_dot(xs, w, jnp.asarray(sizes))
    got, want = (np.asarray(a[:total], np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    tol = 1e-5 if dt == jnp.float32 else 2.0 ** -7
    assert np.abs(got - want).max(initial=0.0) <= tol * max(
        np.abs(want).max(initial=0.0), 1.0)


@pytest.mark.parametrize("seed", range(4))
def test_visit_lists_are_the_row_tiles_each_group_spans(seed):
    """``visits`` against a count on the host: a group takes the row
    tiles it spans, in order; an empty group none.  The groups that
    have rows alternate between the two slots, and a group's first
    visit — no other — names the next group that has rows (-1 after
    the last)."""
    rng = np.random.default_rng(seed)
    for _ in range(25):
        G, tiles, tile = (int(rng.integers(1, 20)), int(rng.integers(1, 12)),
                          GP.ROW_TILE)
        R = tiles * tile
        sizes = rng.multinomial(int(rng.integers(0, R + 1)),
                                rng.dirichlet([0.5] * G)).astype("int32")
        offs, group, row_tile, slot, fetch, count = (
            np.asarray(a).tolist()
            for a in GP.visits(jnp.asarray(sizes), R))
        hit = [g for g in range(G) if sizes[g]]
        want, at = [], 0
        for g, s in enumerate(int(s) for s in sizes):
            spans = range(at // tile, (at + s - 1) // tile + 1) if s else ()
            want += [(t, g, hit.index(g) % 2,
                      -2 if t != spans[0] else
                      (hit + [-1])[hit.index(g) + 1]) for t in spans]
            at += s
        assert count == len(want) <= tiles + G - 1
        assert list(zip(row_tile, group, slot, fetch))[:count] == want
        assert offs == [0] + np.cumsum(sizes).tolist()


def test_the_prefill_kernels_module_does_not_hold_its_callers():
    """The jitted kernel is traced once a shape by whichever program
    calls it first, and a Mosaic module carries the Python stack of each
    operation — ten frames.  Lowered for the TPU, a program's text
    (kernel modules included, its compile-cache key's input) is the
    same whether it traced the kernel itself or another program, on
    another call path and under other scopes, did so before it."""
    args = (jax.ShapeDtypeStruct((512, 256), jnp.bfloat16),
            jax.ShapeDtypeStruct((8, 256, 384), jnp.bfloat16),
            jax.ShapeDtypeStruct((8,), jnp.int32))

    def program(x, w, s):
        with jax.named_scope("moe.expert_matmul"):
            return GP.grouped_prefill(x, w, s) * 2

    def another_program(x, w, s):
        def a_piece_of_a_group(x):
            with jax.named_scope("generate.prefill_group"):
                return GP.grouped_prefill(x, w, s)
        return a_piece_of_a_group(x)

    def lowered(*before):
        jax.clear_caches()
        for fn in before + (program,):
            text = jax.jit(fn).trace(*args).lower(
                lowering_platforms=("tpu",)).as_text()
        return text

    alone = lowered()
    assert "tpu_custom_call" in alone
    assert lowered(another_program) == alone


def test_prefill_kernel_refuses_a_buffer_without_whole_tiles():
    xs, w = jnp.zeros((200, 256)), jnp.zeros((2, 256, 128))
    with pytest.raises(ValueError, match="no whole tiles"):
        GP.grouped_prefill(xs, w, jnp.array([3, 4], jnp.int32),
                           interpret=True)


def test_a_prompt_piece_on_the_prefill_arm_and_its_schedule_event(
        monkeypatch):
    """``DroplessMoE`` over 640 tokens x 4 choices — a buffer of 2560
    rows, a piece of a prompt pass — with the plan a TPU would give (the
    kernel interpreted): the same output as on ``ragged_dot``; the
    ``moe.schedule`` event names the arm, its tiles and ONE k tile, and
    ``prefill_plan`` says the same in words."""
    from bigdl_tpu.telemetry import default_tracer

    layer = moe.DroplessMoE(256, 384, 8, 4)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 320, 256))
    params = layer.param_tree()
    want = layer.apply_fn(params, {}, x, False, None)[0]
    assert layer.prefill_plan(640, jnp.float32) == {
        "grouped_prefill": "ragged", "grouped_prefill_tiles": "",
        "grouped_prefill_tiles_down": ""}
    before = len([s for s in default_tracer().spans()
                  if s.name == "moe.schedule"])
    real = GP.grouped_prefill
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(GP, "grouped_prefill",
                        lambda *a, **kw: real(*a, interpret=True, **kw))
    assert layer.prefill_plan(640, jnp.float32) == {
        "grouped_prefill": "grouped_prefill",
        "grouped_prefill_tiles": "128x256x384",
        "grouped_prefill_tiles_down": "128x384x256"}
    got = layer.apply_fn(params, {}, x, False, None)[0]
    monkeypatch.undo()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    events = [s.args for s in default_tracer().spans()
              if s.name == "moe.schedule"][before:]
    assert [(e["tokens"], e["rows"], e["held"], e["k"], e["impl"],
             e["tiles"], e["k_tiles"]) for e in events] == [
        (640, 2560, 8, 4, "grouped_prefill", [128, 256, 384], 1)]


@pytest.mark.parametrize("tokens,k,held,pieces,each", [
    (36864, 6, 64, 8, 4608),    # SmallThinker: a group of 8 rows x 4608
    (32768, 4, 64, 4, 8192),    # LFM2: 256 rows x 128
    (16384, 8, 16, 4, 4096),    # Command A+: 128 rows x 128
    (32, 6, 64, 1, 32),         # a decode step
    (10, 2, 2, 1, 10),
])
def test_pieces_of_a_dispatch(tokens, k, held, pieces, each):
    """The arithmetic ``dropless_apply`` cuts a token list by, and
    ``prefill_plan`` reads: equal pieces of at most 32 768 rows."""
    assert moe.dispatch_pieces(tokens, k, held) == (pieces, each)
    assert each * min(k, held) <= moe.MAX_DISPATCH_ROWS
