"""The flash kernels COMPILED for a v5e that is described, not attached
(PR 30).  ``tests/test_tpu_lowering.py`` stops at the Pallas→Mosaic
lowering; here the TPU's own compiler runs, so what interpret mode and
the lowering cannot see is refused on the CPU host: a slice that is not
aligned to the tiling (the sub-tile walks slice the refs' rows, and the
lanes of the lse / delta rows), more VMEM than a kernel may use.  Shapes are the benchmark cells' own.  Nothing runs: a compile
that passes is not a chip run.

The topology is described inside a fixture of THIS file only — the
process that describes it holds libtpu until it exits, so no other test
file may do the same.  ``conftest.py`` keeps the persistent compile
cache off for the suite (such a compile could be written to it but not
read back without the chip).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bigdl_tpu.ops import gqa_attend
from bigdl_tpu.ops.flash_attention import _flash
from bigdl_tpu.ops.latent_attend import BLOCK_POSITIONS, _latent_attend_kernel


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _fwd(grid, sub):
    return lambda q, k, v: _flash(q, k, v, True, 0.125, False, grid, grid,
                                  sub)


def _fwd_bwd(grid, sub):
    return jax.grad(lambda q, k, v: jnp.sum(
        _fwd(grid, sub)(q, k, v).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))


@pytest.mark.parametrize("shape,fn,calls", [
    # gpt2m_train_*: 8 x 16 heads, T 1024, head 64 — one grid tile
    # walked in 512s, the carry never leaves the kernel's values; the
    # backward is ONE kernel (one key grid tile): 2 calls a layer
    pytest.param((8, 16, 1024, 64), _fwd_bwd(None, None), 2, id="train"),
    # the fused backward at wide heads (one tile of 512) and with dk/dv
    # resting in scratch between two query grid tiles
    pytest.param((4, 8, 512, 128), _fwd_bwd(None, None), 2,
                 id="train_head128"),
    pytest.param((2, 20, 512, 256), _fwd_bwd(None, None), 2,
                 id="train_head256"),
    # mistral7b_serve_prefill: 8 x 32 heads, T 2048, head 128 — 2 x 2
    # grid tiles, a schedule per tile offset, carry through scratch
    pytest.param((8, 32, 2048, 128), _fwd(None, None), 1, id="prefill"),
    # the backward over several grid tiles AND sub-tiles: dKdV and dQ
    pytest.param((1, 16, 2048, 64), _fwd_bwd(512, 128), 3, id="bwd_grid"),
    # the decode cells' prompt: one sub-tile or less
    pytest.param((16, 32, 128, 128), _fwd(None, None), 1, id="prompt"),
    # glm47flash_serve_decode_sat: latent attention expanded to per-head
    # K and V in prefill — 256 x 20 heads, prompt 128, head 256
    pytest.param((256, 20, 128, 256), _fwd(None, None), 1,
                 id="latent_prompt"),
])
def test_flash_kernels_compile_for_v5e(one_chip, shape, fn, calls):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(fn).lower(x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == calls


@pytest.mark.parametrize("rows", [256, 8])
def test_latent_attend_kernel_compiles_for_v5e(one_chip, rows):
    """glm47flash_serve_decode_sat's decode step: 20 heads (not a
    multiple of the sublane tile), a latent of 512 and a shared key of
    64 with positions minor, a cache of 640 — the full bucket and the
    smallest one the shape rule engages."""
    def S(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(
        lambda ql, qr, c, r, pos: _latent_attend_kernel(
            ql, qr, c, r, pos, 256, BLOCK_POSITIONS, False)).lower(
                S(rows, 20, 512), S(rows, 20, 64), S(rows, 640, 512),
                S(rows, 64, 640), S(dt=jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("B,H,Hkv,Dh,T", [
    # lfm2moe_serve_decode_sat: groups of 4 (a quarter of a sublane
    # tile), a head of 64
    pytest.param(256, 32, 8, 64, 384, id="lfm2_head64"),
    # commandaplus_serve_decode_sat: groups of 16, a head of 128
    pytest.param(128, 128, 8, 128, 256, id="commandaplus_head128"),
    # falconh1_serve_decode_sat: groups of 5
    pytest.param(64, 20, 4, 128, 384, id="falconh1_group5"),
])
def test_gqa_attend_kernel_compiles_for_v5e(one_chip, B, H, Hkv, Dh, T):
    """The decode attend of the cells with per-head K/V.  An ARGUMENT of
    ``[.., T, 64]`` lies positions-minor on the chip and the kernel
    reads it row-major, so here it is copied once; a head of whole lane
    tiles is not.  In the generate program the leaf is CARRIED row-major
    and nothing is copied: the test below."""
    def S(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(
        lambda q, k, v, pos: gqa_attend._gqa_attend_kernel(
            q, k, v, pos, gqa_attend.BLOCK_POSITIONS, False)).lower(
                S(B, H, Dh), S(B, Hkv, T, Dh), S(B, Hkv, T, Dh),
                S(dt=jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    copied = compiled.memory_analysis().temp_size_in_bytes \
        >= B * Hkv * T * Dh * 2
    assert copied == (Dh == 64)


def test_lfm2_decode_loop_carries_its_kv_row_major(one_chip, monkeypatch):
    """What the LFM2 cell's gain rests on (PERF.md §6 "PR 41"): because
    the kernel arm reads them, the compiled generate program carries
    the ``[256, 8, 384, 64]`` K and V leaves ROW-MAJOR — a step's
    one-position write is then 2048 rows and no scatter (0.03 ms a leaf
    where the einsum arm's positions-minor leaf takes 0.43) — and puts
    no copy of a leaf anywhere, the decode loop included.  The cell's
    own batch, heads and cache at toy depth and width of everything
    else (one conv layer, one attention layer with four small
    experts), the TPU's branches as on the chip."""
    import json
    import os
    import re

    from bigdl_tpu.models import generate as G
    from bigdl_tpu.models.latent_moe import ShortConvMoELM

    B, T0, new = 256, 128, 256
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "lfm2-24b-a2b-l5.json")) as f:
        kw = json.load(f)["program"]["kwargs"]
    model = ShortConvMoELM(**{
        **kw, "layer_types": ["conv", "full_attention"], "vocab_size": 256,
        "mlp_dim": 256, "n_experts": 4, "held": [0, 4], "top_k": 2,
        "expert_dim": 128, "max_len": T0 + new})
    Hkv, Dh = kw["num_kv_heads"], kw["head_dim"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert G.cache_footprint(model, B, T0, new)["kv_attend"] == "kernel"

    def S(shape=(), dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    gen = G.make_generate(model, compute_dtype=jnp.bfloat16)
    run = [c.cell_contents for c in gen.__closure__
           if hasattr(c.cell_contents, "lower")][0]
    params = jax.tree_util.tree_map(lambda a: S(a.shape, a.dtype),
                                    model.param_tree())
    text = run.lower(params, S((B, T0)), new, S((2,), jnp.uint32),
                     S(dt=jnp.float32), 0, S(dt=jnp.float32), S(), S(),
                     True, False).compile().as_text()
    # every instruction that yields a whole leaf: its layout, its opcode
    leaves = re.findall(
        rf"= bf16\[{B},{Hkv},{T0 + new},{Dh}\]\{{([\d,]+)[^ ]* ([\w\-]+)\(",
        text)
    written = [op for _, op in leaves if op == "dynamic-update-slice"]
    assert len(written) == 2                    # K and V, in the loop
    assert {layout for layout, _ in leaves} == {"3,2,1,0"}
    assert "copy" not in {op for _, op in leaves}


@pytest.mark.parametrize("rows", [1, 16, 256, 256 * 128])
def test_sinkhorn_kernel_compiles_at_the_serving_rows(one_chip, monkeypatch,
                                                      rows):
    """The residual map of a hyper-connected sublayer
    (``ops/sinkhorn.py``) at the rows of the Xing4.0 cell: a decode
    step of the smallest and the largest bucket, a bucket that is no
    lane tile, and the 32 768 tokens of the largest bucket's prompt —
    ONE Mosaic call each, twenty sweeps inside it."""
    from bigdl_tpu.ops.sinkhorn import sinkhorn_map

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = jax.ShapeDtypeStruct((4, 4, rows), jnp.float32, sharding=one_chip)
    text = jax.jit(lambda x: sinkhorn_map(x, 20, 1e-6, -30.0, 30.0)).lower(
        x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert " while(" not in text        # the sweeps are the kernel's loop


def test_xing4_decode_step_names_its_hyper_connections(one_chip, monkeypatch):
    """The compiled generate program of the Xing4.0 cell's shapes (its
    batch, widths, heads and streams; toy depth, vocabulary and experts;
    the TPU's branches as on the chip): every operation of a
    hyper-connection carries, in its ``op_name``, the stretch, the
    sublayer's scope and the part's — what ``benchmark/readers/
    mhc_decode_pct.py`` and ``mla_decode_pct.py`` match — with the one
    ``jit(...)`` of the function that is traced once for all sublayers
    between them; the sweeps are ONE Mosaic call a sublayer, and the
    prompt's attention is the plain one under its own scope."""
    import json
    import os
    import re

    from bigdl_tpu.models import generate as G
    from bigdl_tpu.models.latent_moe import HyperLatentMoELM

    B, T0, new = 256, 128, 256
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "xing4.0-29b-a4b-l5e32v2.json")) as f:
        kw = json.load(f)["program"]["kwargs"]
    model = HyperLatentMoELM(**{
        **kw, "num_layers": 2, "vocab_size": 256, "mlp_dim": 256,
        "n_experts": 4, "held": [0, 4], "top_k": 2, "expert_dim": 128,
        "max_len": T0 + new})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def S(shape=(), dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    gen = G.make_generate(model, compute_dtype=jnp.bfloat16)
    run = [c.cell_contents for c in gen.__closure__
           if hasattr(c.cell_contents, "lower")][0]
    params = jax.tree_util.tree_map(lambda a: S(a.shape, a.dtype),
                                    model.param_tree())
    text = run.lower(params, S((B, T0)), new, S((2,), jnp.uint32),
                     S(dt=jnp.float32), 0, S(dt=jnp.float32), S(), S(),
                     True, False).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for stretch in ("generate.decode_step", "generate.prefill"):
        for sub in ("block.attention", "block.mlp"):
            for fn, part in (("_coefficients", "mhc.coeffs"),
                             ("_coefficients", "mhc.sinkhorn"),
                             ("_pre", "mhc.pre"), ("_post", "mhc.post")):
                want = f"{stretch}/{sub}/jit({fn})/{part}/"
                assert any(want in n for n in names), want
    sweeps = [ln for ln in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in ln
              and "mhc.sinkhorn" in ln]
    assert len(sweeps) == 2 * 2 * 2     # layers x sublayers x (prefill, step)
    assert any("generate.prefill/block.attention/mla.prefill_attend/" in n
               for n in names)
    assert not any("generate.decode_step" in n and "mla.prefill_attend" in n
                   for n in names)


def test_smallthinker_32_row_bucket_fits_a_v5e_with_its_prompt_in_groups(
        one_chip, monkeypatch):
    """The SmallThinker cell's largest program — 32 rows, a prompt of
    4608 and 256 new tokens, every width, head count, the window and the
    whole vocabulary as published — compiled for the described chip with
    the TPU's branches taken: the prompt pass goes in 4 groups of 8 rows
    (147 456 tokens against ``PREFILL_TOKENS``), the program keeps ONE
    ``while`` and its arguments and temporaries stay under 13 GB.  Its
    grouped products are all Mosaic calls under ``moe.expert_matmul``
    (PR 48): twelve a decode step under ``generate.decode_step``
    (``ops/grouped_decode.py``), and OUTSIDE it the prompt pass's 4
    groups x 8 pieces of 27 648 rows x 4 layers x 3 products
    (``ops/grouped_prefill.py``) — no ``ragged-dot`` is left.  To
    spare this host 4.2 GB of zeros, 8 of the 64 experts a layer are
    held here (the router keeps its 64 outputs and 6 a token, so every
    buffer of the program has the cell's shape) and the 56 left out are
    added to the arguments by arithmetic."""
    import json
    import os
    import re

    from bigdl_tpu.models import generate as G
    from bigdl_tpu.models.latent_moe import PreRoutedMoELM

    B, T0, new, held = 32, 4608, 256, 8
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "smallthinker-21b-a3b-l4.json")) as f:
        kw = json.load(f)["program"]["kwargs"]
    model = PreRoutedMoELM(**{**kw, "held": [0, held],
                              "draw_weights": False})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fp = G.cache_footprint(model, B, T0, new, compute_dtype=jnp.bfloat16)
    assert (fp["kv_cache_bytes_window"], fp["kv_cache_bytes_full"],
            fp["kv_cache_positions"], fp["prefill_groups"]) == (
        805_306_368, 318_767_104, 4864, 4)
    assert (fp["grouped_prefill"], fp["grouped_prefill_tiles"],
            fp["grouped_prefill_tiles_down"]) == (
        "grouped_prefill", "128x2560x768", "128x768x2560")

    def S(shape=(), dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    gen = G.make_generate(model, compute_dtype=jnp.bfloat16)
    run = [c.cell_contents for c in gen.__closure__
           if hasattr(c.cell_contents, "lower")][0]
    params = jax.tree_util.tree_map(lambda a: S(a.shape, a.dtype),
                                    model.param_tree())
    compiled = run.lower(params, S((B, T0)), new, S((2,), jnp.uint32),
                         S(dt=jnp.float32), 0, S(dt=jnp.float32), S(), S(),
                         True, False).compile()
    mem = compiled.memory_analysis()
    left_out = (kw["n_experts"] - held) * len(kw["rope_layout"]) \
        * 3 * kw["embed_dim"] * kw["expert_dim"] * 2
    assert left_out == 2_642_411_520
    assert (mem.argument_size_in_bytes + left_out
            + mem.temp_size_in_bytes) < 13e9
    text = compiled.as_text()
    assert len(re.findall(r" while\(", text)) == 1
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "moe.expert_matmul" in line]
    step = [c for c in calls if "generate.decode_step" in c]
    assert (len(step), len(calls) - len(step)) == (12, 4 * 8 * 4 * 3)
    assert {re.search(r"= bf16\[(\d+),(\d+)\]", c).groups()
            for c in calls if c not in step} == {("27648", "768"),
                                                 ("27648", "2560")}
    assert "ragged-dot" not in text


def test_the_products_of_a_prompt_piece_share_one_set_of_visit_lists(
        one_chip):
    """``grouped_prefill`` makes its visit lists from the sizes inside
    the call, and a piece's gate, up and down products get the SAME
    sizes: compiled for the described v5e, the three calls of a gated
    expert leave ONE set of the lists' fusions in the program (the
    compiler merges equal operations of equal operands), so a prompt
    pass holds them once a piece, not once a product."""
    import re

    from bigdl_tpu.ops.grouped_prefill import grouped_prefill

    R, k, n, G = 2048, 256, 384, 8

    def one(x, wg, wu, wd, s):
        return grouped_prefill(x, wg, s)

    def three(x, wg, wu, wd, s):
        g, u = grouped_prefill(x, wg, s), grouped_prefill(x, wu, s)
        return grouped_prefill(jax.nn.relu(g) * u, wd, s)

    def S(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def entry(fn):
        text = jax.jit(fn).lower(
            S((R, k)), S((G, k, n)), S((G, k, n)), S((G, n, k)),
            S((G,), jnp.int32)).compile().as_text()
        body = text[text.index("ENTRY"):]
        return (body.count('custom_call_target="tpu_custom_call"'),
                len(re.findall(r" fusion\(", body)))

    (calls1, fusions1), (calls3, fusions3) = entry(one), entry(three)
    assert (calls1, calls3) == (1, 3)
    assert fusions3 == fusions1 + 1         # the gate's product alone


_TOY_4_OF_4 = {"vocab_size": 256, "mlp_dim": 256, "n_experts": 4,
               "held": [0, 4], "top_k": 4}


@pytest.mark.parametrize("config,cls,B,toy,up,down,pieces", [
    ("lfm2-24b-a2b-l5", "ShortConvMoELM", 256, _TOY_4_OF_4,
     "128x2048x1536", "128x1536x2048", 4),
    ("xing4.0-29b-a4b-l5e32v2", "HyperLatentMoELM", 256, _TOY_4_OF_4,
     "128x3584x1024", "128x1024x3584", 0),
    ("smallthinker-21b-a3b-l4", "PreRoutedMoELM", 32,
     {"vocab_size": 256, "n_experts": 8, "held": [0, 8]},
     "64x2560x768", "64x768x2560", 1),
])
def test_grouped_products_of_a_256_row_bucket_compile_for_v5e(
        one_chip, monkeypatch, config, cls, B, toy, up, down, pieces):
    """The largest bucket of the LFM2 and Xing4.0 cells (256 rows, four
    choices a token: a decode buffer of 1024 rows at the published
    embed and expert widths; four experts, toy vocabulary and dense
    FFN) and of the SmallThinker cell (32 rows, six choices: 192 rows,
    no whole 128-row chunks; eight experts) compiles under the plan of
    ``parallel.moe.grouped_plan`` — each expert's matrix ONE whole-depth
    tile of 3.9-7 MB, 11-31 MiB of VMEM a call, walked in products of
    128 rows or of 64 — and a decode step holds twelve Mosaic calls
    under ``moe.expert_matmul`` (three a layer, four expert layers):
    what the ``*_expert_matmul_roofline`` readers count a step's
    products by.  The prompt's 32 768 (24 576) tokens go in ``pieces``
    pieces of ``ops/grouped_prefill.py``'s calls where an expert's
    matrix is one tile of it (SmallThinker's 3.9 MB, LFM2's 6.3:
    ``128x<k>x<n>``, 10-16 MiB of VMEM a call), OUTSIDE
    ``generate.decode_step``, and no ``ragged-dot`` is left in the
    program; ``pieces`` 0 — Xing4.0's 7.3 MB, 19.8 MiB a call — says
    the prompt pass keeps ``ragged_dot``.  ``cache_footprint`` says
    which in ``grouped_prefill*``."""
    import json
    import os
    import re

    from bigdl_tpu.models import generate as G
    from bigdl_tpu.models import latent_moe

    T0, new = 128, 256
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", config + ".json")) as f:
        kw = {**json.load(f)["program"]["kwargs"], **toy,
              "max_len": T0 + new}
    model = getattr(latent_moe, cls)(**kw)
    rows = B * kw["top_k"]                 # a token's choices, all held
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    foot = G.cache_footprint(model, B, T0, new, compute_dtype=jnp.bfloat16)
    assert (foot["grouped"], foot["grouped_tiles"],
            foot["grouped_tiles_down"]) == ("grouped_decode", up, down)
    assert (foot["grouped_prefill"], foot["grouped_prefill_tiles"],
            foot["grouped_prefill_tiles_down"]) == ((
        "grouped_prefill", "128x" + up.split("x", 1)[1],
        "128x" + down.split("x", 1)[1]) if pieces else ("ragged", "", ""))

    def S(shape=(), dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    gen = G.make_generate(model, compute_dtype=jnp.bfloat16)
    run = [c.cell_contents for c in gen.__closure__
           if hasattr(c.cell_contents, "lower")][0]
    params = jax.tree_util.tree_map(lambda a: S(a.shape, a.dtype),
                                    model.param_tree())
    text = run.lower(params, S((B, T0)), new, S((2,), jnp.uint32),
                     S(dt=jnp.float32), 0, S(dt=jnp.float32), S(), S(),
                     True, False).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "moe.expert_matmul" in line]
    step = [c for c in calls if "generate.decode_step" in c]
    assert len(step) == 12
    assert {re.search(r"= bf16\[(\d+),(\d+)\]", c).groups()
            for c in step} == {(str(rows), up.split("x")[2]),
                               (str(rows), down.split("x")[2])}
    # the prompt pass's calls lie outside the decode step, three a
    # piece and expert layer, or it is ``ragged_dot``'s
    assert len(calls) - len(step) == pieces * 12
    assert ("ragged-dot" in text) == (not pieces)


def test_dp4_step_gathers_behind_the_forward_and_reduce_scatters(
        topo, monkeypatch):
    """The data-parallel training step of ``parallel/plan.py`` compiled
    for the described v5e:2x2's four chips (PERF.md §6 "PR 44"): two
    layers at gpt2-medium's widths, Adam, bf16 compute, the step the
    ``gpt2m_train_dp4`` cell times at toy depth.  Each of
    the 15 leaves over 1 MiB lives on its data shard: its bf16 copy is
    gathered ASYNCHRONOUSLY (an ``async-collective-start`` / ``-done``
    fusion pair with the forward's products between them, or an
    all-gather the compiler marked ``async_collective_name``), its f32
    cotangent goes through ONE reduce-scatter fusion, and no all-reduce
    over a leaf of 1 MiB or more is left — the parent's twelve combined
    synchronous ``psum``s of 1.6 GB are gone from the program."""
    import json
    import os
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bigdl_tpu import nn
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.parallel.plan import compile_step_with_plan

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "gpt2-medium.json")) as f:
        kw = json.load(f)["program"]["kwargs"]
    # the real vocabulary: 50257 divides by nothing, so the two tables
    # shard their MINOR dimension — and a smaller table's minor-dimension
    # scatter the compiler turns back into an all-reduce and a slice
    model = TransformerLM(**{**kw, "num_layers": 2})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices), ("data",))
    crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(), True)
    eng = compile_step_with_plan(model, crit, Adam(3e-4), mesh,
                                 compute_dtype=jnp.bfloat16, donate=True)
    host = model.param_tree()
    sharded = [name for name, row in eng.plan.table(host).items()
               if "[fsdp]" in row]
    assert len(sharded) == 2 * 6 + 3    # the block matrices, wte, wpe, head

    def S(a, spec):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    tree = jax.tree_util.tree_map
    x = S(jax.ShapeDtypeStruct((32, 1024), jnp.float32), P("data"))
    text = eng.jitted_for(x, x, False).lower(
        tree(S, host, eng.param_specs),
        tree(S, jax.eval_shape(eng.optim.init_state, host), eng.slot_specs),
        tree(S, model.buffer_tree(), eng.buffer_specs),
        S(jax.ShapeDtypeStruct((), jnp.float32), P()),
        S(jax.ShapeDtypeStruct((2,), jnp.uint32), P()), x, x,
    ).compile().as_text()
    entry = text[text.index("\nENTRY "):]
    starts = re.findall(r"%(async-collective-start[.\d]*) = ", entry)
    dones = re.findall(r"%(async-collective-done[.\d]*) = ", entry)
    marked = [ln for ln in entry.splitlines()
              if " all-gather(" in ln and "async_collective_name" in ln]
    plain = [ln for ln in entry.splitlines()
             if " all-gather(" in ln and "async_collective_name" not in ln]
    assert len(starts) == len(dones) and not plain
    assert len(starts) + len(marked) == len(sharded)
    assert len(starts) >= 2 * 6         # every block matrix's gather
    scatters = [ln for ln in entry.splitlines()
                if "AllReduceScatterFusion" in ln]
    assert len(scatters) == len(sharded)
    assert all(re.search(r"= f32\[", ln) for ln in scatters)
    # (a scatter's own fused computation holds an all-reduce: the entry's)
    for shape in re.findall(r"= \(?(\w+\[[\d,]*\])[^=]* all-reduce\(", entry):
        dims = [int(d) for d in re.findall(r"\d+", shape.split("[")[1])]
        assert int(np.prod(dims or [1])) * 4 < 1 << 20, shape
