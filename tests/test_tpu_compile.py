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

from bigdl_tpu.ops.flash_attention import _flash
from bigdl_tpu.ops.latent_attend import BLOCK_POSITIONS, _latent_attend_kernel


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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
