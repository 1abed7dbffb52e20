"""Hand-written Pallas TPU kernel for the 3×3 stride-1 SAME conv — the
ResNet-50 workhorse shape (VERDICT r3 #1: attack the dominant conv cost
with a hand kernel, or prove the ceiling).

Strategy — flat-slab shifted-matmul, no im2col materialisation:

* the input is padded once in XLA to (B, H+2, W+2, C) and viewed flat
  as (B, (H+2)·(W+2), C);
* each grid step (b, h-tile) DMAs one contiguous
  ((th+2)·(W+2), C) row slab from HBM into a 2-D VMEM scratch — the
  ONLY input traffic; all nine taps read the same slab;
* in the row-major flat view, tap (dy, dx) is the CONTIGUOUS window
  ``slab[dy·(W+2)+dx : +th·(W+2)]`` — so compute is nine large 2-D MXU
  matmuls ``(th·(W+2), C) × (C, O)`` accumulated f32, rank-2
  throughout (Mosaic's sweet spot; no strided 3-D window reads).  The
  shift wraps across row boundaries only into each row's 2 padding
  columns, which the caller slices off after the kernel — kept output
  columns are exact.
* the kernel therefore emits (B, H·(W+2), O); the XLA-side
  ``reshape → [:, :, :W]`` costs one fused output pass.  The wrap
  columns are wasted MXU work and output bytes in ratio 2/(W+2):
  3.4 % at ResNet's W=56, 6.7 % at W=28, 12.5 % at W=14, and a
  material 22 % at W=7 — the price of keeping every matmul contiguous
  rank-2; the 7² layers are the least conv-bound, so the trade is
  taken knowingly.

Identical math to ``ops/conv_gemm`` but with the tiling pinned: the
slab never leaves VMEM, so the k² input re-reads that bound the
XLA-level decomposition cost nothing here.  DMA (≤ ~0.2 µs/slab) is
negligible next to the ~7 µs of tile FLOPs, so the simple
copy→wait→compute schedule suffices (no double buffering).

Backward is hybrid: dX is the same kernel with spatially-flipped,
transposed weights (a 3×3 s1 conv again); dW is nine huge-K matmuls
``(B·H·W, C)ᵀ × (B·H·W, O)`` left to XLA, where the MXU shape is
already ideal.  Falls back to ``conv_gemm`` off-TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ._support import pl, pltpu, use_kernel
from .conv_gemm import conv2d_gemm_nhwc


def _pick_th(h: int, target: int = 16) -> int:
    for th in range(min(target, h), 0, -1):
        if h % th == 0:
            return th
    return h


def _kernel(x_hbm, w_ref, o_ref, slab, sem, *, th, W, C, O):
    b = pl.program_id(0)
    i = pl.program_id(1)
    Wp = W + 2
    # one flat row slab: padded rows [i*th, i*th + th + 2) = contiguous
    # flat range [i*th*Wp, (i*th + th + 2)*Wp)
    cp = pltpu.make_async_copy(
        x_hbm.at[b, pl.ds(i * th * Wp, (th + 2) * Wp + 8)], slab, sem)
    cp.start()
    cp.wait()
    M = th * Wp
    acc = jnp.zeros((M, O), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            off = dy * Wp + dx
            acc = acc + lax.dot_general(
                slab[off:off + M, :], w_ref[dy, dx],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[0] = acc.astype(o_ref.dtype)


def _conv3x3_fwd(x, w, interpret):
    B, H, W, C = x.shape
    O = w.shape[-1]
    th = _pick_th(H)
    Wp = W + 2
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    # +8 flat rows so the last tile's largest tap window (off = 2·Wp+2)
    # stays in-bounds: off + th·Wp = (th+2)·Wp + 2 <= slab rows
    xf = jnp.pad(xp.reshape(B, (H + 2) * Wp, C), ((0, 0), (0, 8), (0, 0)))
    kernel = functools.partial(_kernel, th=th, W=W, C=C, O=O)
    out = pl.pallas_call(
        kernel,
        grid=(B, H // th),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),          # x stays in HBM
            pl.BlockSpec((3, 3, C, O), lambda b, i: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, th * Wp, O), lambda b, i: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, H * Wp, O), x.dtype),
        scratch_shapes=[
            pltpu.VMEM(((th + 2) * Wp + 8, C), x.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xf, w)
    # drop each row's 2 wrap-around columns (see module docstring)
    return out.reshape(B, H, Wp, O)[:, :, :W, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv3x3(x, w, interpret):
    return _conv3x3_fwd(x, w, interpret)


def _fwd_rule(x, w, interpret):
    return _conv3x3_fwd(x, w, interpret), (x, w)


def _bwd_rule(interpret, res, g):
    x, w = res
    # dX: conv of g with the spatially-flipped, in/out-transposed filter
    # (3×3 s1 SAME again — the same kernel)
    w_flip = jnp.transpose(w[::-1, ::-1], (0, 1, 3, 2))
    dx = _conv3x3_fwd(g.astype(x.dtype), w_flip.astype(x.dtype),
                      interpret)
    # dW: nine (C, O) matmuls with K = B·H·W — XLA's MXU sweet spot
    B, H, W, C = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    gf = g.reshape(B * H * W, -1)
    taps = []
    for dy in range(3):
        row = []
        for dxx in range(3):
            a = lax.slice(xp, (0, dy, dxx, 0), (B, dy + H, dxx + W, C))
            row.append(lax.dot_general(
                a.reshape(B * H * W, C), gf,
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        taps.append(jnp.stack(row))
    dw = jnp.stack(taps).astype(w.dtype)
    return dx, dw


_conv3x3.defvjp(_fwd_rule, _bwd_rule)


def conv3x3_s1_same(x, w, interpret: bool = False):
    """3×3 stride-1 SAME NHWC conv via the Pallas slab kernel.

    Args:
      x: [B, H, W, C];  w: [3, 3, C, O] (HWIO).
    Returns [B, H, W, O] in x.dtype (f32 accumulation).
    Off-TPU (without ``interpret``) delegates to ``conv2d_gemm_nhwc``;
    on a TPU the kernel compiles or the Mosaic error propagates.
    """
    assert w.shape[:2] == (3, 3), "conv3x3_s1_same is the 3×3 kernel"
    if use_kernel(interpret):
        return _conv3x3(x, w, interpret)
    return conv2d_gemm_nhwc(x, w, stride=(1, 1), padding=(1, 1))
