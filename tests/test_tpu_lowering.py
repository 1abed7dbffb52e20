"""Pallas kernels cross-lowered for the TPU on the CPU host.

``jax.jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs the
Pallas→Mosaic lowering with no chip: it catches the block-shape and
layout refusals (the 8x128 tiling rule) in seconds.  It does NOT compile
— Mosaic's own limits (VMEM) are met by the compiles for a described
v5e in ``tests/test_tpu_compile.py`` (the flash kernels) and on the
chip, which ``chip_smoke.py`` covers.  Shapes here are the smoke's: the flagship
TransformerLM's per-chip attention (B16 H8 T1024 D128 bf16) and its
LayerNorm rows (16384 x 1024), plus the long-context attention shapes.

Also pinned: on a TPU backend there is no second path — a kernel that
cannot run raises, it never rides a dense fallback.
"""
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.ops import (block_sparse_attention, block_sparse_matmul,
                           flash_attention, fused_layer_norm,
                           sliding_window_mask)
from bigdl_tpu.ops import _support
from bigdl_tpu.ops.block_sparse import BlockMask, _bs_attn, _bs_mm
from bigdl_tpu.ops.conv3x3_pallas import _conv3x3, conv3x3_s1_same
from bigdl_tpu.ops.flash_attention import _flash
from bigdl_tpu.ops.layer_norm import _fused_ln


def _lower_tpu(fn, *args) -> str:
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _grad_of_sum(fn):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
                    argnums=(0, 1, 2))


@pytest.mark.parametrize("shape,calls", [
    # several key grid tiles: forward, dK/dV and dQ
    ((16, 8, 1024, 128), 3),   # the smoke's train step (block 512)
    ((4, 8, 4096, 128), 3),    # long context (block 1024)
    ((2, 8, 8192, 128), 3),
    # one key grid tile: forward and the fused backward
    ((8, 16, 1024, 64), 2),    # the training cells (block 1024)
    ((16, 8, 512, 128), 2),    # wide heads at one tile of 512
    ((2, 20, 512, 256), 2),    # latent attention's expanded heads
])
def test_flash_fwd_and_bwd_lower_for_tpu(shape, calls):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    text = _lower_tpu(_grad_of_sum(
        lambda q, k, v: _flash(q, k, v, True, 0.088, False, None, None)),
        x, x, x)
    assert text.count("tpu_custom_call") == calls


def test_fused_layer_norm_lowers_for_tpu():
    x = jax.ShapeDtypeStruct((16384, 1024), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1024,), jnp.float32)
    text = _lower_tpu(lambda x, g, b: _fused_ln(x, g, b, 1e-5, False),
                      x, g, g)
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("block", [512, 128])
def test_block_sparse_attention_lowers_for_tpu(block):
    T = 4096
    mask = sliding_window_mask(T // block, T // block, window=2,
                               n_global=1, causal=True, block_q=block,
                               block_k=block)
    x = jax.ShapeDtypeStruct((2, 8, T, 128), jnp.bfloat16)
    text = _lower_tpu(_grad_of_sum(
        lambda q, k, v: _bs_attn(q, k, v, mask, True, 0.088, False)),
        x, x, x)
    assert text.count("tpu_custom_call") == 3


def test_block_sparse_matmul_lowers_for_tpu():
    import numpy as np

    mask = BlockMask(np.ones((8, 32), bool), 128, 128)
    x = jax.ShapeDtypeStruct((2048, 1024), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((1024, 4096), jnp.bfloat16)
    assert "tpu_custom_call" in _lower_tpu(
        lambda x, w: _bs_mm(x, w, mask, False), x, w)


# --------------------------------------------------------------------------
# what the TPU lowering refuses, and that nothing hides it
# --------------------------------------------------------------------------

@pytest.fixture
def as_if_on_tpu(monkeypatch):
    """``use_kernel`` sees a TPU backend; the process still runs on the
    CPU, where a non-interpreted pallas_call cannot execute — so any
    call that reaches a kernel raises, and a call that returns took a
    fallback."""
    monkeypatch.setattr(_support.jax, "default_backend", lambda: "tpu")


def test_sub_128_block_sparse_is_rejected_on_tpu(as_if_on_tpu):
    """Block 16 (the docs' old example): the lse tile (1, 1, 16) breaks
    the 128-lane tiling, so the TPU path raises a ValueError that says
    so — before the lowering, and never the masked dense path."""
    mask = sliding_window_mask(8, 8, window=2, causal=True, block_q=16,
                               block_k=16)
    x = jnp.zeros((1, 2, 128, 32), jnp.bfloat16)
    with pytest.raises(ValueError, match="multiple of 128"):
        block_sparse_attention(x, x, x, mask, causal=True)
    mm = BlockMask(jnp.ones((4, 4), bool), 16, 16)
    with pytest.raises(ValueError, match="multiple of 128"):
        block_sparse_matmul(jnp.zeros((8, 64), jnp.bfloat16),
                            jnp.zeros((64, 64), jnp.bfloat16), mm)
    # the raw kernel shows why: the lowering itself refuses the tile
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        _lower_tpu(lambda q, k, v: _bs_attn(q, k, v, mask, True, 0.25,
                                            False), x, x, x)


def test_sub_128_block_sparse_still_runs_interpreted():
    """The CPU tests' sizes keep working through the interpreter."""
    mask = sliding_window_mask(8, 8, window=2, causal=True, block_q=16,
                               block_k=16)
    x = jnp.ones((1, 2, 128, 32), jnp.float32)
    out = block_sparse_attention(x, x, x, mask, causal=True,
                                 interpret=True)
    assert out.shape == x.shape and bool(jnp.all(jnp.isfinite(out)))


@pytest.mark.parametrize("hw", [56, 28])
def test_conv3x3_pallas_is_refused_at_resnet_stages(hw):
    """ROADMAP D3 decides this kernel's fate; until then its refusal at
    the 56x56 / 28x28 ResNet stages (output tile 16*(W+2) rows is not a
    multiple of 8) is a loud lowering error, not a fallback."""
    x = jax.ShapeDtypeStruct((2, hw, hw, 64), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((3, 3, 64, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        _lower_tpu(lambda x, w: _conv3x3(x, w, False), x, w)


def test_no_dense_fallback_on_a_tpu_backend(as_if_on_tpu):
    """flash attention, fused LayerNorm and the 3x3 conv dispatch to
    their kernels on a TPU backend and let the kernel's error out."""
    q = jnp.zeros((1, 2, 128, 32), jnp.float32)
    with pytest.raises(Exception, match="[Ii]nterpret"):
        flash_attention(q, q, q, causal=True)
    with pytest.raises(Exception, match="[Ii]nterpret"):
        fused_layer_norm(jnp.zeros((8, 128)), jnp.ones((128,)),
                         jnp.zeros((128,)))
    with pytest.raises(Exception, match="[Ii]nterpret"):
        conv3x3_s1_same(jnp.zeros((1, 8, 8, 8)), jnp.zeros((3, 3, 8, 8)))
