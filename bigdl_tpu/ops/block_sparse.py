"""Block-sparse transformer kernels — BLaST (arxiv 2507.03117) on the
MXU: sparse attention and sparse MLP matmuls that *actually skip* the
zero blocks, for training and paged decode.

PR 10 proved the sparsity bet on the wire (sparse gradient transport
pays only when the zero rows are never shipped); these kernels are the
same bet one level down: structured sparsity pays only when the masked
blocks are never **read** and never **multiplied** — masking scores
after a dense matmul saves nothing.  The mechanism is the Pallas
scalar-prefetch grid (``pltpu.PrefetchScalarGridSpec``): a static
per-(q-block, k-block) :class:`BlockMask` is compiled at trace time
into per-row *active block index tables* that live in SMEM, and the
K/V BlockSpec index maps read the next block id from those tables —

* a masked block never appears in any table entry, so its HBM tile is
  **never DMA'd** and its score tile **never exists**;
* grid padding steps past a row's active count repeat the previous
  block index (the pipeline re-uses the resident tile — no fresh DMA)
  and a ``pl.when`` guard skips all compute (no MXU work);
* the online-softmax (m, l, acc) accumulate and the FlashAttention-2
  backward tile come verbatim from ``ops/flash_attention`` (the shared
  ``_online_softmax_tile`` / ``_accum_dkv_tile`` / ``_accum_dq_tile``
  helpers), so the sparse and dense-grid kernels can never drift
  numerically — an all-ones mask IS the flash kernel's schedule.

Accounting: XLA's cost model sees a Pallas call as an opaque zero-FLOP
custom call, so the skipped work is invisible to the roofline.
:func:`attention_work` / :func:`matmul_work` report the kernel's
*executed* FLOPs (derived from the same index tables the grid runs)
next to the dense equivalent; drivers feed them to
``PerfAccountant.report_sparse_flops`` so MFU is computed on executed
work and the win lands in ``bigdl_perf_sparse_flops_skipped`` instead
of reading as an MFU regression.

Off a TPU backend (without ``interpret``) both ops compute the
identical math densely with the mask applied elementwise — same
function, no skip.  On a TPU there is no dense fallback: a block size
Mosaic cannot tile (see :func:`_kernel_path`) raises, and a compile
error propagates.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ._support import pl, pltpu, use_kernel
from .flash_attention import (_BIG_LSE, _LANES, _accum_dkv_tile,
                              _accum_dq_tile, _dot, _finish_softmax_tile,
                              _init_softmax_scratch, _online_softmax_tile,
                              _tile_causal_mask)

__all__ = ["BlockMask", "block_sparse_attention", "block_sparse_matmul",
           "sliding_window_mask", "strided_mask", "magnitude_block_mask",
           "attention_work", "matmul_work", "pick_block_divisor"]


# --------------------------------------------------------------------------
# BlockMask: the static per-tile mask, hashable so jit retracing and the
# custom_vjp nondiff plumbing stay stable
# --------------------------------------------------------------------------

class BlockMask:
    """A static boolean grid over (row-block, col-block) tiles plus the
    block sizes it was built at.  Immutable and hashable (the bytes are
    the identity), so it can ride ``custom_vjp`` nondiff arguments and
    jit-cache keys without retracing per call."""

    __slots__ = ("mask", "block_q", "block_k", "_key")

    def __init__(self, mask, block_q: int, block_k: int):
        m = np.ascontiguousarray(np.asarray(mask), dtype=bool)
        if m.ndim != 2:
            raise ValueError(f"block mask must be 2-D, got shape {m.shape}")
        m.setflags(write=False)
        self.mask = m
        self.block_q = int(block_q)
        self.block_k = int(block_k)
        self._key = (m.shape, m.tobytes(), self.block_q, self.block_k)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, BlockMask) and self._key == other._key

    def __repr__(self):
        return (f"BlockMask({self.mask.shape[0]}x{self.mask.shape[1]} "
                f"blocks {self.block_q}x{self.block_k}, "
                f"density {self.density:.3f})")

    @property
    def nnz(self) -> int:
        return int(self.mask.sum())

    @property
    def density(self) -> float:
        """Active fraction of the FULL block grid (the dense basis)."""
        return float(self.mask.mean()) if self.mask.size else 0.0

    def transposed(self) -> "BlockMask":
        return BlockMask(self.mask.T, self.block_k, self.block_q)

    def pruned_causal(self) -> "BlockMask":
        """Drop blocks strictly above the causal diagonal (no element of
        the tile can legally attend) — the block-granular twin of the
        flash kernel's causal skip."""
        nq, nk = self.mask.shape
        qi = np.arange(nq)[:, None]
        kj = np.arange(nk)[None, :]
        legal = kj * self.block_k <= qi * self.block_q + self.block_q - 1
        return BlockMask(self.mask & legal, self.block_q, self.block_k)

    def elementwise(self) -> np.ndarray:
        """The mask expanded to element granularity [R, C] — what the
        dense fallback applies."""
        return np.repeat(np.repeat(self.mask, self.block_q, axis=0),
                         self.block_k, axis=1)


def pick_block_divisor(n: int, m: int, target: int) -> int:
    """Largest 8-aligned block <= ``target`` dividing both ``n`` and
    ``m`` (the mask-builder's block-size picker); falls back to the
    largest common divisor when nothing 8-aligned divides."""
    g = math.gcd(int(n), int(m))
    best = None
    for b in range(min(int(target), g), 0, -1):
        if g % b == 0:
            if b % 8 == 0:
                return b
            if best is None:
                best = b
    return best or 1


# --------------------------------------------------------------------------
# Mask builders
# --------------------------------------------------------------------------

def sliding_window_mask(nq: int, nk: int, window: int, n_global: int = 0,
                        causal: bool = True, block_q: int = 1,
                        block_k: int = 1) -> BlockMask:
    """Sliding-window + global-token pattern at BLOCK granularity:
    each q block attends its own and the previous ``window - 1`` k
    blocks, plus the first ``n_global`` k blocks (Longformer-style
    anchors).  Non-causal windows extend both directions."""
    if window < 1:
        raise ValueError(f"window must be >= 1 blocks, got {window}")
    qi = np.arange(nq)[:, None]
    kj = np.arange(nk)[None, :]
    if causal:
        m = (kj <= qi) & (kj > qi - window)
    else:
        m = np.abs(qi - kj) < window
    if n_global:
        g = kj < n_global
        if causal:
            g = g & (kj <= qi)
        m = m | g
    return BlockMask(m, block_q, block_k)


def strided_mask(nq: int, nk: int, stride: int, causal: bool = True,
                 block_q: int = 1, block_k: int = 1) -> BlockMask:
    """Local-diagonal + strided pattern: each q block attends its own
    k block and every ``stride``-th k block (the Sparse-Transformer
    fixed pattern at block granularity)."""
    if stride < 1:
        raise ValueError(f"stride must be >= 1, got {stride}")
    qi = np.arange(nq)[:, None]
    kj = np.arange(nk)[None, :]
    m = (qi == kj) | ((kj + 1) % stride == 0)
    if causal:
        m = m & (kj <= qi)
    return BlockMask(m, block_q, block_k)


def magnitude_block_mask(w, block_rows: int, block_cols: int,
                         density: float) -> BlockMask:
    """The BLaST-style magnitude-derived static mask: keep the top
    ``density`` fraction of tiles by L2 norm.  ``w`` is any 2-D array
    — MLP weights for the sparse-matmul story, or an averaged
    attention-score map for the pretraining mask derivation.  The kept
    count is exact (top-k, not a threshold), so the requested density
    is the delivered density up to one block."""
    if not 0.0 < density <= 1.0:
        raise ValueError(f"density must be in (0, 1], got {density}")
    a = np.asarray(jax.device_get(w), dtype=np.float64)
    if a.ndim != 2:
        raise ValueError(f"magnitude mask needs a 2-D array, got "
                         f"shape {a.shape}")
    R, C = a.shape
    if R % block_rows or C % block_cols:
        raise ValueError(
            f"shape {a.shape} not divisible by blocks "
            f"({block_rows}, {block_cols})")
    nr, nc = R // block_rows, C // block_cols
    norms = np.sqrt(
        (a.reshape(nr, block_rows, nc, block_cols) ** 2).sum((1, 3)))
    keep = max(1, int(round(density * nr * nc)))
    mask = np.zeros(nr * nc, dtype=bool)
    mask[np.argsort(-norms.ravel(), kind="stable")[:keep]] = True
    return BlockMask(mask.reshape(nr, nc), block_rows, block_cols)


# --------------------------------------------------------------------------
# Index tables: the compiled form of a BlockMask — what the scalar-
# prefetch grid actually sweeps.  Executed-work accounting derives from
# THESE (not from the mask directly), so the FLOP report and the grid
# can never disagree.
# --------------------------------------------------------------------------

def _index_tables(mask: np.ndarray):
    """Per-row active column indices, padded to the max row count by
    repeating the last active index (a repeated block index re-uses the
    already-resident VMEM tile: no fresh DMA), plus per-row counts.
    Rows with zero active blocks point every step at block 0 with
    count 0 — the kernel's ``pl.when`` guard skips all their work."""
    n_rows = mask.shape[0]
    counts = mask.sum(axis=1).astype(np.int32)
    L = max(1, int(counts.max()) if n_rows else 1)
    table = np.zeros((n_rows, L), np.int32)
    for i in range(n_rows):
        act = np.nonzero(mask[i])[0]
        if act.size:
            table[i, :act.size] = act
            table[i, act.size:] = act[-1]
    return table, counts, L


def attention_work(mask: BlockMask, batch: int, heads: int, head_dim: int,
                   causal: bool = False, train: bool = False) -> dict:
    """Kernel-reported effective FLOPs for one block-sparse attention
    dispatch: ``executed`` counts only the block pairs the grid's
    active tables visit (4·bq·bk·D FLOPs per pair: QKᵀ + PV, times
    3.5 for fwd+bwd — the FA-2 backward recomputes the tile and runs
    five matmuls); ``dense_equivalent`` is the full [T, S] grid the
    dense path would materialize.  Feed both to
    ``PerfAccountant.report_sparse_flops``."""
    m = mask.pruned_causal() if causal else mask
    _, counts, _ = _index_tables(m.mask)
    per_pair = 4.0 * mask.block_q * mask.block_k * head_dim
    factor = 3.5 if train else 1.0
    executed = factor * per_pair * float(counts.sum()) * batch * heads
    dense = factor * per_pair * float(mask.mask.size) * batch * heads
    # the flash kernel's causal schedule already skips above-diagonal
    # blocks: the wall-clock-comparable basis is the causal grid
    ones = BlockMask(np.ones_like(mask.mask), mask.block_q,
                     mask.block_k)
    flash_pairs = (ones.pruned_causal().nnz if causal
                   else ones.nnz)
    flash_eq = factor * per_pair * float(flash_pairs) * batch * heads
    return {
        "executed_flops": executed,
        "dense_equivalent_flops": dense,
        "flash_equivalent_flops": flash_eq,
        "sparse_flops_skipped": dense - executed,
        "executed_fraction": executed / dense if dense else 0.0,
        "executed_vs_flash_fraction": (executed / flash_eq
                                       if flash_eq else 0.0),
        "executed_block_pairs": int(counts.sum()),
        "dense_block_pairs": int(mask.mask.size),
    }


def matmul_work(mask: BlockMask, m_rows: int, train: bool = False) -> dict:
    """Effective FLOPs for one block-sparse matmul: 2·M·bk·bn per
    active weight tile (times 3 for fwd+bwd: dX rides the transposed
    sparse kernel, dW the masked dense)."""
    factor = 3.0 if train else 1.0
    per_tile = 2.0 * m_rows * mask.block_q * mask.block_k
    executed = factor * per_tile * mask.nnz
    dense = factor * per_tile * mask.mask.size
    return {
        "executed_flops": executed,
        "dense_equivalent_flops": dense,
        "sparse_flops_skipped": dense - executed,
        "executed_fraction": executed / dense if dense else 0.0,
    }


# --------------------------------------------------------------------------
# Block-sparse attention kernels
# --------------------------------------------------------------------------

def _bs_fwd_kernel(kmap_ref, nact_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr, *, sm_scale: float, causal: bool,
                   block_q: int, block_k: int, num_steps: int):
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_softmax_scratch(m_scr, l_scr, acc_scr)

    @pl.when(j < nact_ref[qi])
    def _compute():
        s = _dot(q_ref[0], k_ref[0], ((1,), (1,))) * sm_scale
        if causal:
            ki = kmap_ref[qi, j]
            s = jnp.where(_tile_causal_mask(qi * block_q, ki * block_k,
                                            block_q, block_k),
                          s, -jnp.inf)
        _online_softmax_tile(s, v_ref[0], m_scr, l_scr, acc_scr)

    @pl.when(j == num_steps - 1)
    def _finish():
        _finish_softmax_tile(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _bs_dkv_kernel(qmap_ref, nact_ref, q_ref, do_ref, k_ref, v_ref,
                   lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                   sm_scale: float, causal: bool, block_q: int,
                   block_k: int, num_steps: int):
    kj = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(j < nact_ref[kj])
    def _compute():
        qi = qmap_ref[kj, j]
        st_mask = _tile_causal_mask(qi * block_q, kj * block_k, block_q,
                                    block_k, transposed=True) \
            if causal else None
        _accum_dkv_tile(q_ref[0], do_ref[0], k_ref[0], v_ref[0],
                        lse_ref[0], delta_ref[0], sm_scale, st_mask,
                        dk_scr, dv_scr)

    @pl.when(j == num_steps - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bs_dq_kernel(kmap_ref, nact_ref, q_ref, do_ref, k_ref, v_ref,
                  lse_ref, delta_ref, dq_ref, dq_scr, *, sm_scale: float,
                  causal: bool, block_q: int, block_k: int,
                  num_steps: int):
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(j < nact_ref[qi])
    def _compute():
        ki = kmap_ref[qi, j]
        st_mask = _tile_causal_mask(qi * block_q, ki * block_k, block_q,
                                    block_k, transposed=True) \
            if causal else None
        _accum_dq_tile(q_ref[0], do_ref[0], k_ref[0], v_ref[0],
                       lse_ref[0], delta_ref[0], sm_scale, st_mask,
                       dq_scr)

    @pl.when(j == num_steps - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _runtime_mask(mask: BlockMask, causal: bool) -> BlockMask:
    """What the grid actually sweeps: the caller's mask with causally
    dead blocks pruned (the flash kernel's diagonal skip, applied at
    mask granularity so the tables never visit them)."""
    return mask.pruned_causal() if causal else mask


def _bs_fwd(q, k, v, mask: BlockMask, causal, sm_scale, interpret):
    B, H, T, D = q.shape
    S = k.shape[2]
    bq, bk = mask.block_q, mask.block_k
    nq, nk = T // bq, S // bk
    qr = q.reshape(B * H, T, D)
    kr = k.reshape(B * H, S, D)
    vr = v.reshape(B * H, S, D)
    table, counts, L = _index_tables(_runtime_mask(mask, causal).mask)
    kernel = functools.partial(_bs_fwd_kernel, sm_scale=sm_scale,
                               causal=causal, block_q=bq, block_k=bk,
                               num_steps=L)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * H, nq, L),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, i, j, km, na: (bh, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D),
                         lambda bh, i, j, km, na: (bh, km[i, j], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D),
                         lambda bh, i, j, km, na: (bh, km[i, j], 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, i, j, km, na: (bh, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda bh, i, j, km, na: (bh, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running row max
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running denominator
            pltpu.VMEM((bq, D), jnp.float32),        # unnormalized output
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(table), jnp.asarray(counts), qr, kr, vr)
    return out.reshape(B, H, T, D), lse


def _bs_bwd(q, k, v, o, lse, g, mask: BlockMask, causal, sm_scale,
            interpret):
    B, H, T, D = q.shape
    S = k.shape[2]
    bq, bk = mask.block_q, mask.block_k
    nq, nk = T // bq, S // bk
    BH = B * H
    qr = q.reshape(BH, T, D)
    kr = k.reshape(BH, S, D)
    vr = v.reshape(BH, S, D)
    gr = g.reshape(BH, T, D).astype(q.dtype)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(BH, 1, T)
    run = _runtime_mask(mask, causal).mask
    # dK/dV sweeps each k block's active q blocks; dQ the forward's sets
    q_table, q_counts, Lq = _index_tables(run.T)
    k_table, k_counts, Lk = _index_tables(run)

    def rows(spec_q):
        """(q, dO, k, v, lse, delta) BlockSpecs with the q-side index
        supplied by ``spec_q`` and the k-side by the grid row."""
        return [
            pl.BlockSpec((1, bq, D), lambda bh, i, j, km, na:
                         (bh, spec_q(i, j, km), 0),
                         memory_space=pltpu.VMEM),   # q
            pl.BlockSpec((1, bq, D), lambda bh, i, j, km, na:
                         (bh, spec_q(i, j, km), 0),
                         memory_space=pltpu.VMEM),   # dO
        ]

    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, nk, Lq),
        in_specs=rows(lambda i, j, km: km[i, j]) + [
            pl.BlockSpec((1, bk, D), lambda bh, i, j, km, na: (bh, i, 0),
                         memory_space=pltpu.VMEM),   # k
            pl.BlockSpec((1, bk, D), lambda bh, i, j, km, na: (bh, i, 0),
                         memory_space=pltpu.VMEM),   # v
            pl.BlockSpec((1, 1, bq),
                         lambda bh, i, j, km, na: (bh, 0, km[i, j]),
                         memory_space=pltpu.VMEM),   # lse
            pl.BlockSpec((1, 1, bq),
                         lambda bh, i, j, km, na: (bh, 0, km[i, j]),
                         memory_space=pltpu.VMEM),   # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda bh, i, j, km, na: (bh, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, i, j, km, na: (bh, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_bs_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, num_steps=Lq),
        grid_spec=dkv_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(q_table), jnp.asarray(q_counts), qr, gr, kr, vr, lse,
      delta)

    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, nq, Lk),
        in_specs=rows(lambda i, j, km: i) + [
            pl.BlockSpec((1, bk, D),
                         lambda bh, i, j, km, na: (bh, km[i, j], 0),
                         memory_space=pltpu.VMEM),   # k
            pl.BlockSpec((1, bk, D),
                         lambda bh, i, j, km, na: (bh, km[i, j], 0),
                         memory_space=pltpu.VMEM),   # v
            pl.BlockSpec((1, 1, bq), lambda bh, i, j, km, na: (bh, 0, i),
                         memory_space=pltpu.VMEM),   # lse
            pl.BlockSpec((1, 1, bq), lambda bh, i, j, km, na: (bh, 0, i),
                         memory_space=pltpu.VMEM),   # delta
        ],
        out_specs=pl.BlockSpec((1, bq, D),
                               lambda bh, i, j, km, na: (bh, i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(_bs_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, num_steps=Lk),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(k_table), jnp.asarray(k_counts), qr, gr, kr, vr, lse,
      delta)

    return (dq.reshape(B, H, T, D), dk.reshape(B, H, S, D),
            dv.reshape(B, H, S, D))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _bs_attn(q, k, v, mask, causal, sm_scale, interpret):
    out, _ = _bs_fwd(q, k, v, mask, causal, sm_scale, interpret)
    return out


def _bs_attn_fwd_rule(q, k, v, mask, causal, sm_scale, interpret):
    out, lse = _bs_fwd(q, k, v, mask, causal, sm_scale, interpret)
    return out, (q, k, v, out, lse)


def _bs_attn_bwd_rule(mask, causal, sm_scale, interpret, res, g):
    q, k, v, o, lse = res
    return _bs_bwd(q, k, v, o, lse, g, mask, causal, sm_scale, interpret)


_bs_attn.defvjp(_bs_attn_fwd_rule, _bs_attn_bwd_rule)


def _kernel_path(interpret: bool, *blocks, rows: int = 8) -> bool:
    """Whether a call takes the Pallas kernels, for ``(block, full_dim)``
    pairs (and the matmul's row count).

    Mosaic tiles the minor dimension on 128 lanes: the lse/delta rows
    are ``(1, 1, block)`` tiles of a ``(BH, 1, T)`` array and the
    matmul's activation tile is ``(bm, block)``, so on a TPU a block is
    a 128-multiple or spans its whole dimension — anything else RAISES
    here rather than reaching the lowering or, worse, the dense path.
    The interpreter (the CPU tests) also takes 8-aligned sub-128
    blocks; other sizes there, and every call off-TPU without
    ``interpret``, run the masked dense reference."""
    if not use_kernel(interpret):
        return False
    tiles = rows % 8 == 0 and all(
        b % 128 == 0
        or (b % 8 == 0 and (b == n or (interpret and b < 128)))
        for b, n in blocks)
    if tiles or interpret:
        return tiles
    raise ValueError(
        "block-sparse kernels on a TPU need blocks that are a multiple "
        "of 128 or span their whole dimension (8-aligned), and a row "
        f"count that is a multiple of 8; got (block, dim) = {blocks}, "
        f"rows = {rows}")


def _bs_attention_reference(q, k, v, mask: BlockMask, causal: bool,
                            sm_scale: float):
    """Dense fallback with the IDENTICAL function: scores masked
    elementwise by the block mask (+ causal), fully-masked rows emit
    exactly zero — the kernel's ``l == 0`` convention.  Scale handling
    matches ``flash_attention``'s dense path spec: ``sm_scale`` is
    applied to the raw scores, never folded twice."""
    elem = jnp.asarray(_runtime_mask(mask, causal).elementwise())
    s = (jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
         * sm_scale)
    T, S = s.shape[-2:]
    m = elem[None, None]
    if causal:
        m = m & (jnp.arange(T)[:, None] >= jnp.arange(S)[None, :])
    s = jnp.where(m, s, -jnp.inf)
    smax = jnp.max(s, axis=-1, keepdims=True)
    smax = jnp.where(jnp.isfinite(smax), smax, 0.0)
    e = jnp.where(jnp.isfinite(s), jnp.exp(s - smax), 0.0)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    p = e / jnp.maximum(denom, 1e-30)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


def block_sparse_attention(q, k, v, block_mask, causal: bool = False,
                           sm_scale: Optional[float] = None,
                           interpret: bool = False):
    """Attention over (B, H, T, D) tensors computing ONLY the block
    pairs the mask allows — masked blocks are skipped entirely on the
    kernel path (no HBM read, no MXU work, no score tile).

    ``block_mask`` is a :class:`BlockMask` (or a raw [nq, nk] bool
    array, block sizes inferred as T//nq, S//nk).  ``causal=True``
    additionally applies the element-level causal mask inside
    diagonal-crossing blocks and prunes above-diagonal blocks from the
    sweep (an all-ones causal mask therefore runs exactly the flash
    kernel's schedule).  Off-TPU (without ``interpret``) the identical
    math runs densely with the mask applied elementwise; on a TPU a
    block size Mosaic cannot tile raises (:func:`_kernel_path`)."""
    B, H, T, D = q.shape
    S = k.shape[2]
    if not isinstance(block_mask, BlockMask):
        m = np.asarray(block_mask)
        if T % m.shape[0] or S % m.shape[1]:
            raise ValueError(
                f"seq lens ({T}, {S}) not divisible by mask grid "
                f"{m.shape}")
        block_mask = BlockMask(m, T // m.shape[0], S // m.shape[1])
    nq, nk = block_mask.mask.shape
    if nq * block_mask.block_q != T or nk * block_mask.block_k != S:
        raise ValueError(
            f"mask grid {block_mask.mask.shape} x blocks "
            f"({block_mask.block_q}, {block_mask.block_k}) does not "
            f"tile seq lens ({T}, {S})")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)

    if _kernel_path(interpret, (block_mask.block_q, T),
                    (block_mask.block_k, S)):
        return _bs_attn(q, k, v, block_mask, causal, float(sm_scale),
                        interpret)
    return _bs_attention_reference(q, k, v, block_mask, causal,
                                   float(sm_scale))


# --------------------------------------------------------------------------
# Block-sparse matmul (the BLaST sparse-MLP kernel)
# --------------------------------------------------------------------------

def _bs_mm_kernel(kmap_ref, nact_ref, x_ref, w_ref, o_ref, acc_scr, *,
                  num_steps: int):
    n = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j < nact_ref[n])
    def _compute():
        acc_scr[...] += _dot(x_ref[...], w_ref[...], ((1,), (0,)))

    @pl.when(j == num_steps - 1)
    def _finish():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def _pick_m_block(m: int, target: int = 512) -> int:
    if m <= target:
        return m
    for b in range(target, 0, -1):
        if m % b == 0:
            return b
    return m


def _bs_mm_fwd(x2, w, mask: BlockMask, interpret):
    M, K = x2.shape
    N = w.shape[1]
    bk, bn = mask.block_q, mask.block_k
    nn = N // bn
    bm = _pick_m_block(M)
    # per-OUTPUT-column-block active k tiles: sweep columns of mask.T
    table, counts, L = _index_tables(mask.mask.T)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M // bm, nn, L),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, n, j, km, na: (i, km[n, j]),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bn), lambda i, n, j, km, na: (km[n, j], n),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, n, j, km, na: (i, n),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_bs_mm_kernel, num_steps=L),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x2.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(table), jnp.asarray(counts), x2, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _bs_mm(x2, w, mask, interpret):
    return _bs_mm_fwd(x2, w, mask, interpret)


def _bs_mm_fwd_rule(x2, w, mask, interpret):
    return _bs_mm_fwd(x2, w, mask, interpret), (x2, w)


def _bs_mm_bwd_rule(mask, interpret, res, g):
    x2, w = res
    # dX rides the transposed sparse kernel (wᵀ's live tiles are
    # mask.T's); dW is one huge-K dense matmul — XLA's MXU sweet spot
    # (the conv3x3 backward's split) — masked down to the live tiles,
    # whose complement holds structural zeros with no gradient.
    dx = _bs_mm_fwd(g, w.T.astype(g.dtype), mask.transposed(), interpret)
    dw = _dot(x2, g, ((0,), (0,)))
    dw = (dw * jnp.asarray(mask.elementwise(), dw.dtype)).astype(w.dtype)
    return dx.astype(x2.dtype), dw


_bs_mm.defvjp(_bs_mm_fwd_rule, _bs_mm_bwd_rule)


def block_sparse_matmul(x, w, block_mask, interpret: bool = False):
    """``x @ w`` where ``w`` [K, N] carries a static :class:`BlockMask`
    over its (K-block, N-block) tile grid — the BLaST sparsified-MLP
    weight layout.  Masked tiles are structural zeros: on the kernel
    path they are never read and never multiplied; the fallback (and
    the dW gradient) computes ``x @ (w·mask)`` — identical math.

    ``x`` may carry leading batch dims ([..., K]); returns [..., N]."""
    if not isinstance(block_mask, BlockMask):
        m = np.asarray(block_mask)
        K, N = w.shape
        if K % m.shape[0] or N % m.shape[1]:
            raise ValueError(
                f"weight shape {w.shape} not divisible by mask grid "
                f"{m.shape}")
        block_mask = BlockMask(m, K // m.shape[0], N // m.shape[1])
    K, N = w.shape
    if (block_mask.mask.shape[0] * block_mask.block_q != K
            or block_mask.mask.shape[1] * block_mask.block_k != N):
        raise ValueError(
            f"mask grid {block_mask.mask.shape} x blocks "
            f"({block_mask.block_q}, {block_mask.block_k}) does not "
            f"tile weight shape {w.shape}")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)

    if _kernel_path(interpret, (block_mask.block_q, K),
                    (block_mask.block_k, N), rows=x2.shape[0]):
        y = _bs_mm(x2, w, block_mask, interpret)
    else:
        y = x2 @ (w * jnp.asarray(block_mask.elementwise(), w.dtype))
    return y.reshape(*lead, N)
