"""Flash attention — tiled online-softmax attention as Pallas TPU
kernels (the hot op the reference era lacked; replaces materializing the
(T, T) score matrix in HBM with running (max, denom, acc) statistics in
VMEM).

Design (pallas_guide.md patterns):
- forward: grid = (batch*heads, T/block_q, S/block_k); each program owns
  one (q tile, k tile) pair.  K/V blocks are *streamed* from HBM by the
  BlockSpec index_map — VMEM holds only one (block_k, d) K and V tile at
  a time, so sequence length is bounded by HBM, not VMEM.  Online
  softmax carries m (running row max), l (running denominator), acc
  (unnormalized output) in VMEM scratch across the innermost k grid
  dimension; the output AND the row log-sum-exp (the backward's softmax
  statistic) are written once on the final k step.
- backward: two Pallas kernels (FlashAttention-2 schedule).  Both
  recompute the probability tile from (q, k, lse) on the fly — no (T, S)
  array ever exists.  Scores are computed TRANSPOSED, (block_k rows ×
  block_q lanes), so the per-q-row lse/delta vectors broadcast along the
  sublane dimension without any in-kernel transpose:
    * dKdV kernel: grid (BH, S/block_k, T/block_q), dk/dv accumulate in
      VMEM scratch over the inner q sweep;
    * dQ kernel: grid (BH, T/block_q, S/block_k), dq accumulates over
      the inner k sweep.
- causal: blocks strictly above the diagonal are skipped via ``pl.when``
  in all three kernels (no wasted MXU work).
- matmuls run in the input dtype (bf16 stays bf16 on the MXU) with f32
  accumulation; probability tiles are cast back to the input dtype
  before the PV/dV/dK products — elementwise math stays f32.

The public ``flash_attention`` computes the jnp reference on non-TPU
backends (or with ``interpret=True`` runs the kernels in the Pallas
interpreter — used by tests).  On a TPU the kernels compile or the
error propagates; only a sequence length the kernels cannot tile
(neither a 128-multiple nor one 8-aligned block) takes the reference.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ._support import pl, pltpu, use_kernel

_LANES = 128  # VMEM scratch lane width (TPU-friendly minor dim)
_BIG_LSE = 1e30  # lse sentinel for fully-masked rows: exp(s - BIG) == 0


def _attention_reference(q, k, v, causal: bool, sm_scale: float):
    """Numerics oracle + short-sequence fallback — delegates to the
    canonical dense attention (parallel/ring_attention.py:170),
    pre-scaling q so a non-default sm_scale lands on the same path."""
    from ..parallel.ring_attention import attention as dense_attention

    d = q.shape[-1]
    return dense_attention(q * (sm_scale * math.sqrt(d)), k, v, causal)


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# Shared tile machinery — ONE implementation of the online-softmax
# (m, l, acc) accumulate and the FlashAttention-2 backward tile, used by
# both the dense-grid flash kernels below and the block-sparse kernels
# (ops/block_sparse.py), so the two can never drift numerically.
# --------------------------------------------------------------------------

def _tile_causal_mask(q_start, k_start, block_q: int, block_k: int,
                      transposed: bool = False):
    """Boolean causal mask for one score tile at absolute offsets —
    (bq, bk) for the forward layout, (bk, bq) for the backward's
    transposed layout.  Offsets may be traced scalars (block indices
    read from a scalar-prefetch table)."""
    if transposed:
        k_pos = k_start + lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
        q_pos = q_start + lax.broadcasted_iota(jnp.int32, (1, block_q), 1)
    else:
        q_pos = q_start + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        k_pos = k_start + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    return q_pos >= k_pos


def _init_softmax_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _online_softmax_tile(s, v, m_scr, l_scr, acc_scr):
    """Fold one (bq, bk) f32 score tile into the running (max, denom,
    unnormalized output) statistics — the online-softmax accumulate."""
    m = m_scr[...][:, :1]                             # (bq, 1)
    l = l_scr[...][:, :1]
    acc = acc_scr[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # guard fully-masked rows: exp(-inf - -inf) would be nan
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)
    scale = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * scale + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * scale + _dot(p.astype(v.dtype), v, ((1,), (0,)))
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
    acc_scr[...] = acc_new


def _finish_softmax_tile(o_ref, lse_ref, m_scr, l_scr, acc_scr):
    """Normalize the accumulated output and emit the row log-sum-exp
    (the backward's softmax statistic); fully-masked rows (l == 0)
    produce exactly zero output and the ``_BIG_LSE`` sentinel."""
    m = m_scr[...][:, :1]
    l = l_scr[...][:, :1]
    o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # lse as a ROW (1, bq): broadcast along sublanes in the backward
    lse = jnp.where(l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)),
                    _BIG_LSE)
    lse_ref[0] = lse[:, 0][None, :]


def _bwd_tile_terms(q, do, k, v, lse, delta, sm_scale, st_mask):
    """The FlashAttention-2 backward tile, transposed layout: recompute
    the (bk, bq) probability tile from (q, k, lse) and form dSᵀ from
    the saved delta rows.  Returns (pᵀ, dSᵀ)."""
    st = _dot(k, q, ((1,), (1,))) * sm_scale          # (bk, bq) f32
    if st_mask is not None:
        st = jnp.where(st_mask, st, -jnp.inf)
    pt = jnp.exp(st - lse)                            # (bk, bq)
    dpt = _dot(v, do, ((1,), (1,)))                   # (bk, bq)
    dst = pt * (dpt - delta)
    return pt, dst


def _accum_dkv_tile(q, do, k, v, lse, delta, sm_scale, st_mask,
                    dk_scr, dv_scr):
    pt, dst = _bwd_tile_terms(q, do, k, v, lse, delta, sm_scale, st_mask)
    dv_scr[...] += _dot(pt.astype(v.dtype), do, ((1,), (0,)))  # (bk, d)
    dk_scr[...] += _dot(dst.astype(q.dtype), q, ((1,), (0,))) * sm_scale


def _accum_dq_tile(q, do, k, v, lse, delta, sm_scale, st_mask, dq_scr):
    pt, dst = _bwd_tile_terms(q, do, k, v, lse, delta, sm_scale, st_mask)
    # dq += ds @ k — contract the bk (sublane) dim: no transpose
    dq_scr[...] += _dot(dst.astype(k.dtype), k, ((0,), (0,))) * sm_scale


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale: float, causal: bool, block_q: int, block_k: int,
                num_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        _init_softmax_scratch(m_scr, l_scr, acc_scr)

    def compute():
        q = q_ref[0]                                      # (block_q, d)
        k = k_ref[0]                                      # (block_k, d)
        v = v_ref[0]
        s = _dot(q, k, (((1,), (1,)))) * sm_scale         # (bq, bk) f32
        if causal:
            s = jnp.where(_tile_causal_mask(qi * block_q, ki * block_k,
                                            block_q, block_k),
                          s, -jnp.inf)
        _online_softmax_tile(s, v, m_scr, l_scr, acc_scr)

    if causal:
        # key blocks strictly above the diagonal contribute nothing
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        _finish_softmax_tile(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _flash_fwd(q, k, v, causal: bool, sm_scale: float, block_q: int,
               block_k: int, interpret: bool):
    B, H, T, D = q.shape
    S = k.shape[2]
    bq = min(block_q, T)
    bk = min(block_k, S)
    assert T % bq == 0 and S % bk == 0, (
        f"seq lens ({T}, {S}) must divide block sizes ({bq}, {bk}); "
        "pad sequences to a block multiple")
    qr = q.reshape(B * H, T, D)
    kr = k.reshape(B * H, S, D)
    vr = v.reshape(B * H, S, D)

    nk = S // bk
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=bq, block_k=bk, num_k_blocks=nk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, T // bq, nk),
        # bh/q-block programs are independent ("parallel" lets Mosaic
        # pipeline across them); the k sweep carries the online-softmax
        # accumulator and must stay sequential ("arbitrary")
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, i, j: (bh, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, i, j: (bh, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, i, j: (bh, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, i, j: (bh, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda bh, i, j: (bh, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running row max
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running denominator
            pltpu.VMEM((bq, D), jnp.float32),        # unnormalized output
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, T, D), lse


def _dkv_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                num_q_blocks: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def compute():
        # transposed scores: (bk rows, bq lanes) — lse/delta broadcast
        # along sublanes with no in-kernel transpose
        st_mask = _tile_causal_mask(qi * block_q, ki * block_k,
                                    block_q, block_k,
                                    transposed=True) if causal else None
        _accum_dkv_tile(q_ref[0], do_ref[0], k_ref[0], v_ref[0],
                        lse_ref[0], delta_ref[0], sm_scale, st_mask,
                        dk_scr, dv_scr)

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            compute()
    else:
        compute()

    @pl.when(qi == num_q_blocks - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *,
               sm_scale: float, causal: bool, block_q: int, block_k: int,
               num_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def compute():
        st_mask = _tile_causal_mask(qi * block_q, ki * block_k,
                                    block_q, block_k,
                                    transposed=True) if causal else None
        _accum_dq_tile(q_ref[0], do_ref[0], k_ref[0], v_ref[0],
                       lse_ref[0], delta_ref[0], sm_scale, st_mask,
                       dq_scr)

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, causal: bool, sm_scale: float,
               block_q: int, block_k: int, interpret: bool):
    B, H, T, D = q.shape
    S = k.shape[2]
    bq = min(block_q, T)
    bk = min(block_k, S)
    BH = B * H
    qr = q.reshape(BH, T, D)
    kr = k.reshape(BH, S, D)
    vr = v.reshape(BH, S, D)
    gr = g.reshape(BH, T, D).astype(q.dtype)
    # delta = rowsum(dO * O): one cheap fused elementwise+reduce in XLA
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(BH, 1, T)

    nq, nk = T // bq, S // bk
    row_specs = [
        pl.BlockSpec((1, bq, D), lambda bh, i, j: (bh, i, 0),
                     memory_space=pltpu.VMEM),   # q
        pl.BlockSpec((1, bq, D), lambda bh, i, j: (bh, i, 0),
                     memory_space=pltpu.VMEM),   # dO
        pl.BlockSpec((1, bk, D), lambda bh, i, j: (bh, j, 0),
                     memory_space=pltpu.VMEM),   # k
        pl.BlockSpec((1, bk, D), lambda bh, i, j: (bh, j, 0),
                     memory_space=pltpu.VMEM),   # v
        pl.BlockSpec((1, 1, bq), lambda bh, i, j: (bh, 0, i),
                     memory_space=pltpu.VMEM),   # lse
        pl.BlockSpec((1, 1, bq), lambda bh, i, j: (bh, 0, i),
                     memory_space=pltpu.VMEM),   # delta
    ]

    # --- dK/dV: grid over k blocks, sweep q blocks innermost ----------
    def swap(spec):  # same tensors, but grid dims are (bh, ki, qi)
        return pl.BlockSpec(
            spec.block_shape,
            lambda bh, kj, ij, _m=spec.index_map: _m(bh, ij, kj),
            memory_space=pltpu.VMEM)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, num_q_blocks=nq),
        grid=(BH, nk, nq),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        in_specs=[swap(s) for s in row_specs],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda bh, j, i: (bh, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, j, i: (bh, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
    )(qr, gr, kr, vr, lse, delta)

    # --- dQ: grid over q blocks, sweep k blocks innermost -------------
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, num_k_blocks=nk),
        grid=(BH, nq, nk),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        in_specs=row_specs,
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, i, j: (bh, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(qr, gr, kr, vr, lse, delta)

    return (dq.reshape(B, H, T, D), dk.reshape(B, H, S, D),
            dv.reshape(B, H, S, D))


def _pick_block(n: int, d: int = 64) -> int:
    """Largest 128-aligned block <= a measured target dividing n.

    Roofline: per q-block the kernel streams the whole K/V (4·S·D bytes
    bf16) from HBM while doing 4·bq·S·D MXU FLOPs → arithmetic
    intensity = bq FLOP/byte.  v5e ridge point = 197 TFLOP/s ÷
    ~820 GB/s ≈ 240 FLOP/byte, so bq ≥ 256 already keeps the sweep
    compute-bound — but the measured on-chip matrix (r4, v5e,
    docs/PERF.md) shows throughput keeps climbing past the ridge:
    block=1024 beats 512 at every swept point but one, fwd and fwd+bwd
    (T=8192 D=128 fwd+bwd 62.5 vs 40.7 TFLOP/s; T=4096 D=64 27.5 vs
    17.9; the exception is T=1024 D=128, where 512 edges 1024 by ~2%
    fwd+bwd and ~30% fwd — the whole-sequence block leaves too few
    programs to hide the pipeline at the short length, so wide heads at
    T<=1024 keep the 512 target).  Past the ridge the win comes from
    grid overhead: fewer, longer-running programs amortize
    prologue/epilogue and revisit the accumulators fewer times.  1024
    is the largest block Mosaic has been shown to take: fwd, dKdV and
    dQ at 1024², D=128, bf16 compile on a v5e under Mosaic's default
    VMEM limit and agree with the dense reference (``chip_smoke.py``,
    PR 21, jax 0.9.0) — the f32 score tile is 1024²·4 B = 4 MB; 2048²
    (16 MB) has never been tried.  Measured (v5e, r3): 512² runs the
    T=1024 grad 2.1× faster than 128²; short sequences use one whole
    block.  The timings quoted here date from 2026-07/08 and are older
    than this code."""
    target = 512 if (n <= 1024 and d >= 128) else 1024
    if n <= target:
        return n
    b = target
    while b >= 128:
        if n % b == 0:
            return b
        b //= 2
    return 128


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, interpret, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, causal, sm_scale,
                        block_q or _pick_block(q.shape[2], q.shape[3]),
                        block_k or _pick_block(k.shape[2], k.shape[3]),
                        interpret)
    return out


def _flash_fwd_rule(q, k, v, causal, sm_scale, interpret, block_q,
                    block_k):
    out, lse = _flash_fwd(q, k, v, causal, sm_scale,
                          block_q or _pick_block(q.shape[2], q.shape[3]),
                          block_k or _pick_block(k.shape[2], k.shape[3]),
                          interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, sm_scale, interpret, block_q, block_k, res,
                    g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g, causal, sm_scale,
                      block_q or _pick_block(q.shape[2], q.shape[3]),
                      block_k or _pick_block(k.shape[2], k.shape[3]),
                      interpret)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    interpret: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    """Attention over (B, H, T, D) tensors without materializing scores.

    Uses the Pallas kernels on TPU (or under ``interpret=True``); plain
    XLA attention elsewhere.  The kernel path takes sequence lengths
    that are 128-multiples, or short 8-aligned sequences that fit one
    block; any other length takes the dense reference (callers pad —
    the data layer's fixed-length contract already guarantees static
    shapes).
    ``block_q``/``block_k`` override the measured default (1024-target;
    see ``_pick_block``) — exposed for the on-hardware tuning sweeps.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    T, S = q.shape[2], k.shape[2]

    def blockable(n):  # one whole block (8-aligned) or a 128-multiple
        return (n % 128 == 0) or (n < 128 and n % 8 == 0)

    if use_kernel(interpret) and blockable(T) and blockable(S):
        return _flash(q, k, v, causal, sm_scale, interpret,
                      block_q, block_k)
    return _attention_reference(q, k, v, causal, sm_scale)
