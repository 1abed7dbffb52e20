"""Flash attention — tiled online-softmax attention as Pallas TPU
kernels (the hot op the reference era lacked; replaces materializing the
(T, T) score matrix in HBM with running (max, denom, acc) statistics in
VMEM).

Design (pallas_guide.md patterns):
- two tile sizes.  The GRID tile (``_pick_block``: 1024 where it fits)
  is what a program owns and what the BlockSpec index maps stream from
  HBM — VMEM holds one (block_k, d) K and V tile at a time, so sequence
  length is bounded by HBM, not VMEM.  Inside it every kernel walks
  SUB-TILES (``_pick_sub_tile``): the score tile that exists at one
  time is (sub_q, sub_k), and the running statistics of one sub-tile
  row are carried from sub-tile to sub-tile as values.  Grid steps are
  bought with the grid tile; skipped and unmasked work with the
  sub-tile.  The walk is unrolled — at most (grid tile / sub-tile)²
  bodies, whatever T: on the v5e a rolled ``lax.fori_loop`` over the
  same sub-tiles ran 1.5-3.3x slower (PERF.md §6 "PR 30").
- forward: grid = (batch*heads, T/block_q, S/block_k).  For each q
  sub-tile the k sub-tiles up to the diagonal fold into (m, l, acc);
  with one k grid tile the output and the row log-sum-exp (the
  backward's softmax statistic) are written straight from the carry,
  with several the carry rests in VMEM scratch between grid steps.
- backward (FlashAttention-2 schedule): the probability tile is
  recomputed from (q, k, lse) on the fly — no (T, S) array ever exists.
  Scores are computed TRANSPOSED, (sub_k rows × sub_q lanes), so the
  per-q-row lse/delta vectors broadcast along the sublane dimension
  without any in-kernel transpose.  How many kernels is a SHAPE:
    * the key axis is ONE grid tile (S <= the grid tile: training at
      T 1024): one fused kernel, grid (BH, T/block_q, 1).  For each k
      sub-tile it walks the q sub-tiles from the diagonal down; each
      (pᵀ, dSᵀ) is computed once and gives its share of dv, dk and dq —
      dk/dv are one k sub-tile's values, dq is held for the q grid tile
      across the walk and written once;
    * several key grid tiles: dq would have to accumulate across grid
      steps that are not consecutive, so two kernels, each recomputing
      the tile — dKdV, grid (BH, S/block_k, T/block_q): for each k
      sub-tile dk/dv accumulate over the q sub-tiles from the diagonal
      down; dQ, grid (BH, T/block_q, S/block_k): for each q sub-tile dq
      accumulates over the k sub-tiles up to the diagonal.
- causal: the walks stop at the diagonal (``_visible_k`` /
  ``_visible_q``; ``causal_schedule`` counts what they give): a
  sub-tile wholly above it is never touched, one wholly under it takes
  no mask at all, and only the sub-tiles the diagonal crosses build
  ``_tile_causal_mask``.  Where the diagonal crosses a grid tile is
  static per tile offset (``_tile_offsets``): ``pl.when`` picks that
  offset's schedule, the unmasked one for a grid tile wholly under the
  diagonal, and none for one above it.
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


def _attention_reference(q, k, v, causal: bool, sm_scale: float,
                         window: Optional[int] = None):
    """Numerics oracle + short-sequence fallback — delegates to the
    canonical dense attention (parallel/ring_attention.py:170),
    pre-scaling q so a non-default sm_scale lands on the same path.
    With a ``window`` the mask is written out here."""
    if window is not None:
        return windowed_attention(q, k, v, window, sm_scale)
    from ..parallel.ring_attention import attention as dense_attention

    d = q.shape[-1]
    return dense_attention(q * (sm_scale * math.sqrt(d)), k, v, causal)


def windowed_attention(q, k, v, window: int,
                       sm_scale: Optional[float] = None):
    """Causal attention in which query ``t`` sees keys ``t - window + 1
    .. t``, scores materialized: the oracle of ``flash_attention(...,
    window=)`` and its path off the TPU.  Softmax in at least float32."""
    T, S = q.shape[2], k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    # at least float32 (a float64 oracle keeps its precision)
    ct = jnp.promote_types(q.dtype, jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(ct) * sm_scale
    back = jnp.arange(T)[:, None] - jnp.arange(S)[None, :]
    s = jnp.where((back >= 0) & (back < window), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# Shared tile machinery — ONE implementation of the online-softmax
# (m, l, acc) update and the FlashAttention-2 backward tile, as functions
# of VALUES.  The dense-grid flash kernels below carry those values
# from sub-tile to sub-tile; the block-sparse kernels
# (ops/block_sparse.py) keep them in VMEM scratch between grid steps
# through the thin ``*_tile`` wrappers — so the two can never drift
# numerically.
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


def _tile_window_mask(q_start, k_start, block_q: int, block_k: int,
                      window: int):
    """Boolean mask of the keys at most ``window - 1`` positions behind
    their query, for one (bq, bk) score tile at absolute offsets — the
    sliding window's far edge (the near one is the causal mask)."""
    q_pos = q_start + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    k_pos = k_start + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    return q_pos - k_pos < window


def _softmax_init(rows: int, d: int):
    """(m, l, acc) of a row block that has seen no key yet."""
    return (jnp.full((rows, 1), -jnp.inf, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, d), jnp.float32))


def _softmax_update(s, v, m, l, acc):
    """Fold one (bq, bk) f32 score tile into the running (max, denom,
    unnormalized output) statistics — the online-softmax accumulate."""
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # guard fully-masked rows: exp(-inf - -inf) would be nan
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)
    scale = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * scale + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * scale + _dot(p.astype(v.dtype), v, ((1,), (0,)))
    return m_new, l_new, acc_new


def _softmax_finish(m, l, acc):
    """Normalized output and the row log-sum-exp (the backward's softmax
    statistic) as a ROW (1, bq): it broadcasts along sublanes in the
    backward.  Fully-masked rows (l == 0) give exactly zero output and
    the ``_BIG_LSE`` sentinel."""
    out = acc / jnp.maximum(l, 1e-30)
    lse = jnp.where(l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)),
                    _BIG_LSE)
    return out, lse[:, 0][None, :]


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


def _dkv_tile(q, do, k, v, lse, delta, sm_scale, st_mask):
    """One tile's (dk, dv) contribution, (bk, d) f32 each."""
    pt, dst = _bwd_tile_terms(q, do, k, v, lse, delta, sm_scale, st_mask)
    dv = _dot(pt.astype(v.dtype), do, ((1,), (0,)))
    dk = _dot(dst.astype(q.dtype), q, ((1,), (0,))) * sm_scale
    return dk, dv


def _dq_tile(q, do, k, v, lse, delta, sm_scale, st_mask):
    """One tile's dq contribution, (bq, d) f32: ds @ k contracts the bk
    (sublane) dim — no transpose."""
    _, dst = _bwd_tile_terms(q, do, k, v, lse, delta, sm_scale, st_mask)
    return _dot(dst.astype(k.dtype), k, ((0,), (0,))) * sm_scale


def _bwd_tile(q, do, k, v, lse, delta, sm_scale, st_mask):
    """One tile's (dq, dk, dv) contributions from ONE pass over its
    scores: what ``_dq_tile`` and ``_dkv_tile`` give, product for
    product, without the second Sᵀ, ``exp`` and dPᵀ."""
    pt, dst = _bwd_tile_terms(q, do, k, v, lse, delta, sm_scale, st_mask)
    dst = dst.astype(q.dtype)
    dv = _dot(pt.astype(v.dtype), do, ((1,), (0,)))
    dk = _dot(dst, q, ((1,), (0,))) * sm_scale
    dq = _dot(dst, k, ((0,), (0,))) * sm_scale
    return dq, dk, dv


# the same arithmetic over VMEM scratch, for kernels whose accumulators
# live across grid steps (ops/block_sparse.py; the dense kernels' carry
# between grid tiles)

def _init_softmax_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _online_softmax_tile(s, v, m_scr, l_scr, acc_scr):
    m, l, acc = _softmax_update(s, v, m_scr[...][:, :1], l_scr[...][:, :1],
                                acc_scr[...])
    m_scr[...] = jnp.broadcast_to(m, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l, l_scr.shape)
    acc_scr[...] = acc


def _finish_softmax_tile(o_ref, lse_ref, m_scr, l_scr, acc_scr):
    out, lse = _softmax_finish(m_scr[...][:, :1], l_scr[...][:, :1],
                               acc_scr[...])
    o_ref[0] = out.astype(o_ref.dtype)
    lse_ref[0] = lse


def _accum_dkv_tile(q, do, k, v, lse, delta, sm_scale, st_mask,
                    dk_scr, dv_scr):
    dk, dv = _dkv_tile(q, do, k, v, lse, delta, sm_scale, st_mask)
    dv_scr[...] += dv
    dk_scr[...] += dk


def _accum_dq_tile(q, do, k, v, lse, delta, sm_scale, st_mask, dq_scr):
    dq_scr[...] += _dq_tile(q, do, k, v, lse, delta, sm_scale, st_mask)


# --------------------------------------------------------------------------
# The causal schedule — ONE rule (a key position is seen by the query
# positions at or after it), in the two closed forms the kernels' walks
# need.  Everything here is a Python int: positions are RELATIVE to the
# grid tile's first key, and ``offset`` = the tile's first query minus
# its first key takes one of a few static values (0 where the grid tiles
# are square), so a kernel holds one unrolled schedule per value and the
# grid indices only choose among them.
# --------------------------------------------------------------------------

def _clip(x: int, hi: int) -> int:
    return min(max(x, 0), hi)


def _visible_k(q0: int, sub_q: int, sub_k: int, n: int):
    """For the query rows [q0, q0 + sub_q), of a grid tile's ``n`` key
    sub-tiles: ``(full, computed)`` — sub-tiles [0, full) lie wholly
    under the diagonal, [full, computed) are crossed by it, the rest
    wholly above it."""
    span = n * sub_k
    return (_clip(q0 + 1, span) // sub_k,
            _clip(q0 + sub_q - 1 + sub_k, span) // sub_k)


def _visible_q(k0: int, sub_k: int, q_base: int, sub_q: int, n: int):
    """For the key rows [k0, k0 + sub_k), of a grid tile's ``n`` query
    sub-tiles (the first at ``q_base``): ``(first, full)`` — sub-tiles
    [first, full) are crossed by the diagonal, [full, n) lie wholly
    under it, those before ``first`` wholly above it."""
    span = n * sub_q
    return (_clip(k0 - q_base, span) // sub_q,
            _clip(k0 + sub_k - 1 - q_base + sub_q - 1, span) // sub_q)


def _window_k(q0: int, sub_q: int, sub_k: int, n: int, window):
    """For the query rows [q0, q0 + sub_q) under a sliding ``window``
    (a query sees the keys at most ``window - 1`` behind it), of a grid
    tile's ``n`` key sub-tiles: ``(first, inside)`` — sub-tiles before
    ``first`` are wholly older than the window, [first, inside) are
    crossed by its edge, from ``inside`` on every key is inside it.
    ``window`` None: (0, 0), nothing skipped and nothing masked."""
    if window is None:
        return 0, 0
    span = n * sub_k
    return (_clip(q0 - window + 1, span) // sub_k,
            _clip(q0 + sub_q - window + sub_k - 1, span) // sub_k)


def _tile_offsets(T: int, S: int, block_q: int, block_k: int,
                  every: bool = False, window=None):
    """The values ``qi * block_q - ki * block_k`` takes on the grid
    tiles the diagonal crosses.  A tile whose offset is >= block_k - 1
    lies wholly under the diagonal; one at <= -block_q wholly above it:
    its schedule is empty, and only ``every`` lists it (for a kernel
    that must still write its zeros).  With a ``window``, also the
    offsets of the tiles under the diagonal that the window's edge
    crosses: one past ``window + block_k - 2`` is wholly older than the
    window (skipped), one up to ``window - block_q`` wholly inside it."""
    seen = {qi * block_q - ki * block_k
            for qi in range(T // block_q) for ki in range(S // block_k)}
    out = {o for o in seen if o < block_k - 1
           and (every or o > -block_q)}
    if window is not None:
        out |= {o for o in seen if o >= block_k - 1
                and window - block_q < o < window + block_k - 1}
    return tuple(sorted(out))


def causal_schedule(T: int, S: int, grid_tile, sub_tile,
                    causal: bool = True, window=None) -> dict:
    """Sub-tiles per head that one kernel computes, masks (of the
    computed: those the diagonal or the window's edge crosses) and
    skips, for (q, k) tile pairs ``grid_tile`` and ``sub_tile`` (an int
    means square) — counted with the bounds the kernels' walks run to.
    A ``window`` of ``T`` or more is the causal schedule."""
    bq, bk = (grid_tile,) * 2 if isinstance(grid_tile, int) else grid_tile
    sq, sk = (sub_tile,) * 2 if isinstance(sub_tile, int) else sub_tile
    total = (T // sq) * (S // sk)
    computed = masked = 0
    for qi in range(T // bq):
        for ki in range(S // bk):
            # a non-causal tile is a tile wholly under the diagonal
            offset = qi * bq - ki * bk if causal else bk
            for i in range(bq // sq):
                q0 = offset + i * sq
                full, comp = _visible_k(q0, sq, sk, bk // sk)
                first, inside = _window_k(q0, sq, sk, bk // sk, window)
                computed += max(comp - first, 0)
                masked += sum(1 for j in range(first, comp)
                              if j >= full or j < inside)
    return {"computed": computed, "masked": masked,
            "skipped": total - computed}


def _record_schedule(kernel: str, T, S, D, tiles, causal, window=None):
    """One ``flash.schedule`` event in the process tracer's ring per
    traced kernel build: the schedule is static, so it is recorded where
    it is made."""
    from ..telemetry.tracer import default_tracer

    bq, bk, sq, sk = tiles
    tr = default_tracer()
    extra = {} if window is None else {"window": window}
    tr.record("flash.schedule", "compile", tr.clock(), 0.0, kernel=kernel,
              T=T, S=S, head_dim=D, grid_tile=[bq, bk], sub_tile=[sq, sk],
              **extra,
              **causal_schedule(T, S, (bq, bk), (sq, sk), causal, window))


def _sub(i: int, size: int) -> slice:
    return slice(i * size, (i + 1) * size)


def _at_static_offset(body, offset, offsets, block_k: int, window=None,
                      block_q: int = 0):
    """Run ``body`` with this grid tile's offset as a Python int: one
    ``pl.when`` branch per offset the diagonal crosses a tile at, one
    (``body(block_k)``: nothing masked) for the tiles wholly under it;
    a tile wholly above it matches none and is skipped.  Under a
    ``window`` (the forward kernel only) ``offsets`` also holds the
    tiles its edge crosses, ``body`` takes the window as a second
    argument — None in the unmasked branch, which then stops at the
    last tile wholly inside the window — and a tile wholly older than
    the window matches none."""
    if window is None:
        if isinstance(offset, int):     # a grid of one tile; non-causal
            return body(offset)
        for static in offsets:
            pl.when(offset == static)(functools.partial(body, static))
        pl.when(offset >= block_k - 1)(functools.partial(body, block_k))
        return
    if isinstance(offset, int):
        return body(offset, window)
    for static in offsets:
        pl.when(offset == static)(functools.partial(body, static, window))
    pl.when((offset >= block_k - 1) & (offset <= window - block_q))(
        functools.partial(body, block_k, None))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                sub_q: int, sub_k: int, num_q_blocks: int,
                num_k_blocks: int, offsets, window=None):
    qi = pl.program_id(1) if num_q_blocks > 1 else 0
    ki = pl.program_id(2) if num_k_blocks > 1 else 0
    n_k = block_k // sub_k
    d = q_ref.shape[-1]
    # with one k grid tile the carry never leaves the kernel's values
    one_pass = num_k_blocks == 1
    if not one_pass:
        m_scr, l_scr, acc_scr = scratch

        @pl.when(ki == 0)
        def _init():
            _init_softmax_scratch(m_scr, l_scr, acc_scr)

    def q_sub_tile(i: int, offset: int, win):
        rows = _sub(i, sub_q)
        q = q_ref[0, rows, :]                             # (sub_q, d)
        q0 = offset + i * sub_q
        full, computed = _visible_k(q0, sub_q, sub_k, n_k)
        # a sliding window: sub-tiles wholly older than it are never
        # touched, those its edge crosses take its mask
        first, inside = _window_k(q0, sub_q, sub_k, n_k, win)
        carry = (_softmax_init(sub_q, d) if one_pass else
                 (m_scr[rows, :1], l_scr[rows, :1], acc_scr[rows, :]))
        for j in range(first, computed):
            cols = _sub(j, sub_k)
            s = _dot(q, k_ref[0, cols, :], ((1,), (1,))) * sm_scale
            if j >= full:       # the diagonal crosses this sub-tile
                s = jnp.where(_tile_causal_mask(q0, j * sub_k, sub_q, sub_k),
                              s, -jnp.inf)
            if j < inside:      # the window's edge crosses it
                s = jnp.where(_tile_window_mask(q0, j * sub_k, sub_q,
                                                sub_k, win), s, -jnp.inf)
            carry = _softmax_update(s, v_ref[0, cols, :], *carry)
        if one_pass:
            out, lse = _softmax_finish(*carry)
            o_ref[0, rows, :] = out.astype(o_ref.dtype)
            lse_ref[0, :, rows] = lse
        else:
            m, l, acc = carry
            m_scr[rows, :] = jnp.broadcast_to(m, (sub_q, _LANES))
            l_scr[rows, :] = jnp.broadcast_to(l, (sub_q, _LANES))
            acc_scr[rows, :] = acc

    def tile(offset: int, win=None):
        for i in range(block_q // sub_q):
            q_sub_tile(i, offset, win)

    # a non-causal tile is a tile wholly under the diagonal
    offset = qi * block_q - ki * block_k if causal else block_k
    _at_static_offset(tile, offset, offsets, block_k, window, block_q)

    if not one_pass:
        @pl.when(ki == num_k_blocks - 1)
        def _finish():
            _finish_softmax_tile(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _tiles(T: int, S: int, D: int, block_q, block_k, sub_tile,
           backward: bool = False):
    """(block_q, block_k, sub_q, sub_k): the grid tile and the sub-tile
    of each axis — chosen from the shape where the caller gives None.
    The ``backward`` of a key axis of one grid tile is the fused kernel,
    which has a sub-tile of its own."""
    bq = min(block_q or _pick_block(T, D), T)
    bk = min(block_k or _pick_block(S, D), S)
    assert T % bq == 0 and S % bk == 0, (
        f"seq lens ({T}, {S}) must divide block sizes ({bq}, {bk}); "
        "pad sequences to a block multiple")
    sub = sub_tile or _pick_sub_tile(T, S, D, backward and bk == S)
    sq, sk = (sub, sub) if isinstance(sub, int) else sub
    return bq, bk, _fit_sub_tile(sq, bq), _fit_sub_tile(sk, bk)


def _flash_fwd(q, k, v, causal: bool, sm_scale: float, block_q, block_k,
               sub_tile, interpret: bool, window=None):
    B, H, T, D = q.shape
    S = k.shape[2]
    tiles = bq, bk, sq, sk = _tiles(T, S, D, block_q, block_k, sub_tile)
    _record_schedule("fwd", T, S, D, tiles, causal, window)
    qr = q.reshape(B * H, T, D)
    kr = k.reshape(B * H, S, D)
    vr = v.reshape(B * H, S, D)

    nq, nk = T // bq, S // bk
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=bq, block_k=bk, sub_q=sq, sub_k=sk,
                               num_q_blocks=nq, num_k_blocks=nk,
                               offsets=_tile_offsets(T, S, bq, bk,
                                                     window=window),
                               **({} if window is None
                                  else {"window": window}))
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
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
        scratch_shapes=[] if nk == 1 else [
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running row max
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running denominator
            pltpu.VMEM((bq, D), jnp.float32),        # unnormalized output
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, T, D), lse


def _dkv_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *scratch,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                sub_q: int, sub_k: int, num_q_blocks: int,
                num_k_blocks: int, offsets):
    ki = pl.program_id(1) if num_k_blocks > 1 else 0
    qi = pl.program_id(2) if num_q_blocks > 1 else 0
    n_q = block_q // sub_q
    d = k_ref.shape[-1]
    one_pass = num_q_blocks == 1
    if not one_pass:
        dk_scr, dv_scr = scratch

        @pl.when(qi == 0)
        def _init():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

    def k_sub_tile(j: int, offset: int):
        rows = _sub(j, sub_k)
        k = k_ref[0, rows, :]
        v = v_ref[0, rows, :]
        first, full = _visible_q(j * sub_k, sub_k, offset, sub_q, n_q)
        if one_pass:
            dk = dv = jnp.zeros((sub_k, d), jnp.float32)
        else:
            dk, dv = dk_scr[rows, :], dv_scr[rows, :]
        for i in range(first, n_q):
            cols = _sub(i, sub_q)
            # transposed scores: (sub_k rows, sub_q lanes) — lse/delta
            # broadcast along sublanes with no in-kernel transpose
            st_mask = _tile_causal_mask(
                offset + i * sub_q, j * sub_k, sub_q, sub_k,
                transposed=True) if i < full else None
            ddk, ddv = _dkv_tile(q_ref[0, cols, :], do_ref[0, cols, :], k, v,
                                 lse_ref[0, :, cols], delta_ref[0, :, cols],
                                 sm_scale, st_mask)
            dk, dv = dk + ddk, dv + ddv
        if one_pass:
            dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
            dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)
        else:
            dk_scr[rows, :], dv_scr[rows, :] = dk, dv

    def tile(offset: int):
        for j in range(block_k // sub_k):
            k_sub_tile(j, offset)

    # a non-causal tile is a tile wholly under the diagonal
    offset = qi * block_q - ki * block_k if causal else block_k
    _at_static_offset(tile, offset, offsets, block_k)

    if not one_pass:
        @pl.when(qi == num_q_blocks - 1)
        def _finish():
            dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
               dq_ref, dq_scr,
               sm_scale: float, causal: bool, block_q: int, block_k: int,
               sub_q: int, sub_k: int, num_q_blocks: int,
               num_k_blocks: int, offsets):
    # several k grid tiles (one takes the fused kernel): dq rests in
    # scratch between them
    qi = pl.program_id(1) if num_q_blocks > 1 else 0
    ki = pl.program_id(2)
    n_k = block_k // sub_k

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def q_sub_tile(i: int, offset: int):
        rows = _sub(i, sub_q)
        q = q_ref[0, rows, :]
        do = do_ref[0, rows, :]
        lse = lse_ref[0, :, rows]
        delta = delta_ref[0, :, rows]
        q0 = offset + i * sub_q
        full, computed = _visible_k(q0, sub_q, sub_k, n_k)
        dq = dq_scr[rows, :]
        for j in range(computed):
            cols = _sub(j, sub_k)
            st_mask = _tile_causal_mask(
                q0, j * sub_k, sub_q, sub_k,
                transposed=True) if j >= full else None
            dq = dq + _dq_tile(q, do, k_ref[0, cols, :], v_ref[0, cols, :],
                               lse, delta, sm_scale, st_mask)
        dq_scr[rows, :] = dq

    def tile(offset: int):
        for i in range(block_q // sub_q):
            q_sub_tile(i, offset)

    # a non-causal tile is a tile wholly under the diagonal
    offset = qi * block_q - ki * block_k if causal else block_k
    _at_static_offset(tile, offset, offsets, block_k)

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_fused_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *scratch,
                      sm_scale: float, causal: bool, block_q: int,
                      block_k: int, sub_q: int, sub_k: int,
                      num_q_blocks: int, offsets):
    """The whole backward where the key axis is ONE grid tile: dKdV's
    walk (k sub-tile outer, the visible q sub-tiles inner), and from
    each tile's one (pᵀ, dSᵀ) also its share of dQ, which is held for
    the q grid tile across the walk and written once."""
    qi = pl.program_id(1) if num_q_blocks > 1 else 0
    n_q = block_q // sub_q
    d = k_ref.shape[-1]
    one_pass = num_q_blocks == 1
    if not one_pass:
        dk_scr, dv_scr = scratch

        @pl.when(qi == 0)
        def _init():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile(offset: int):
        dq = [jnp.zeros((sub_q, d), jnp.float32)] * n_q
        for j in range(block_k // sub_k):
            rows = _sub(j, sub_k)
            k = k_ref[0, rows, :]
            v = v_ref[0, rows, :]
            first, full = _visible_q(j * sub_k, sub_k, offset, sub_q, n_q)
            if one_pass:
                dk = dv = jnp.zeros((sub_k, d), jnp.float32)
            else:
                dk, dv = dk_scr[rows, :], dv_scr[rows, :]
            for i in range(first, n_q):
                cols = _sub(i, sub_q)
                st_mask = _tile_causal_mask(
                    offset + i * sub_q, j * sub_k, sub_q, sub_k,
                    transposed=True) if i < full else None
                ddq, ddk, ddv = _bwd_tile(
                    q_ref[0, cols, :], do_ref[0, cols, :], k, v,
                    lse_ref[0, :, cols], delta_ref[0, :, cols], sm_scale,
                    st_mask)
                dq[i], dk, dv = dq[i] + ddq, dk + ddk, dv + ddv
            if one_pass:
                dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
                dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)
            else:
                dk_scr[rows, :], dv_scr[rows, :] = dk, dv
        for i in range(n_q):
            dq_ref[0, _sub(i, sub_q), :] = dq[i].astype(dq_ref.dtype)

    # a non-causal tile is a tile wholly under the diagonal; with one k
    # grid tile none lies above it, so every q grid tile writes its dq
    offset = qi * block_q if causal else block_k
    _at_static_offset(tile, offset, offsets, block_k)

    if not one_pass:
        @pl.when(qi == num_q_blocks - 1)
        def _finish():
            dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, causal: bool, sm_scale: float,
               block_q, block_k, sub_tile, interpret: bool):
    B, H, T, D = q.shape
    S = k.shape[2]
    tiles = bq, bk, sq, sk = _tiles(T, S, D, block_q, block_k, sub_tile,
                                    backward=True)
    nq, nk = T // bq, S // bk
    # one k grid tile: nothing makes dq wait for a grid step that is not
    # the next one, so one kernel forms all three from each score tile
    fused = nk == 1
    _record_schedule("bwd_fused" if fused else "bwd", T, S, D, tiles, causal)
    BH = B * H
    qr = q.reshape(BH, T, D)
    kr = k.reshape(BH, S, D)
    vr = v.reshape(BH, S, D)
    gr = g.reshape(BH, T, D).astype(q.dtype)
    # delta = rowsum(dO * O): one cheap fused elementwise+reduce in XLA
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(BH, 1, T)

    static = dict(sm_scale=sm_scale, causal=causal, block_q=bq, block_k=bk,
                  sub_q=sq, sub_k=sk, num_q_blocks=nq)
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
    dq_spec, dk_spec, dv_spec = row_specs[0], row_specs[2], row_specs[3]
    dq_shape = jax.ShapeDtypeStruct((BH, T, D), q.dtype)
    dkv_shapes = [jax.ShapeDtypeStruct((BH, S, D), k.dtype),
                  jax.ShapeDtypeStruct((BH, S, D), v.dtype)]
    # dk/dv of a k grid tile rest in scratch between its q grid tiles
    dkv_scratch = [] if nq == 1 else [pltpu.VMEM((bk, D), jnp.float32),
                                      pltpu.VMEM((bk, D), jnp.float32)]

    if fused:
        # grid (BH, nq, 1); the q sweep carries dk/dv: sequential
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, **static,
                              offsets=_tile_offsets(T, S, bq, bk)),
            grid=(BH, nq, nk),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            in_specs=row_specs,
            out_specs=[dq_spec, dk_spec, dv_spec],
            out_shape=[dq_shape] + dkv_shapes,
            scratch_shapes=dkv_scratch,
            interpret=interpret,
        )(qr, gr, kr, vr, lse, delta)
        return (dq.reshape(B, H, T, D), dk.reshape(B, H, S, D),
                dv.reshape(B, H, S, D))

    static = dict(static, num_k_blocks=nk)

    # --- dK/dV: grid over k blocks, sweep q blocks innermost ----------
    def swap(spec):  # same tensors, but grid dims are (bh, ki, qi)
        return pl.BlockSpec(
            spec.block_shape,
            lambda bh, kj, ij, _m=spec.index_map: _m(bh, ij, kj),
            memory_space=pltpu.VMEM)

    # with one q grid tile the dKdV kernel writes straight from its
    # values, so it must visit the k tiles no query sees too (S > T)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **static, offsets=_tile_offsets(
            T, S, bq, bk, every=nq == 1)),
        grid=(BH, nk, nq),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        in_specs=[swap(s) for s in row_specs],
        out_specs=[swap(dk_spec), swap(dv_spec)],
        out_shape=dkv_shapes,
        scratch_shapes=dkv_scratch,
        interpret=interpret,
    )(qr, gr, kr, vr, lse, delta)

    # --- dQ: grid over q blocks, sweep k blocks innermost -------------
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static,
                          offsets=_tile_offsets(T, S, bq, bk)),
        grid=(BH, nq, nk),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        in_specs=row_specs,
        out_specs=dq_spec,
        out_shape=dq_shape,
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(qr, gr, kr, vr, lse, delta)

    return (dq.reshape(B, H, T, D), dk.reshape(B, H, S, D),
            dv.reshape(B, H, S, D))


def _pick_block(n: int, d: int = 64) -> int:
    """The GRID tile of one axis: the largest 128-aligned divisor of n
    up to 1024 (512 for wide heads at n <= 1024).  It buys grid steps:
    fewer, longer programs amortize prologue, K/V refetch and the
    accumulators' trip through scratch.  What is skipped and what goes
    unmasked is the sub-tile's business (``_pick_sub_tile``; PERF.md §6
    "PR 30" has both sweeps; the 2026-07 matrix is superseded)."""
    target = 512 if (n <= 1024 and d >= 128) else 1024
    if n <= target:
        return n
    b = target
    while b >= 128:
        if n % b == 0:
            return b
        b //= 2
    return 128


def _pick_sub_tile(T: int, S: int, d: int, fused_bwd: bool = False) -> int:
    """The SUB-TILE the kernels walk inside a grid tile, from the shape
    alone.  It buys skipped work (causal sub-tiles above the diagonal
    are never touched) against the per-sub-tile cost of the carry.  A
    sequence of one grid tile is walked in 512s by the forward, whose
    carry is rescaled at every sub-tile, and in 256s by the fused
    backward, whose accumulators are only added to (PERF.md §6
    "PR 39"); one of several grid tiles already skips by grid tile, and
    is walked grid tile by grid tile — there a sub-tile measured slower
    (PERF.md §6 "PR 30")."""
    del d
    if max(T, S) > 1024:
        return 1024
    return 256 if fused_bwd else 512


def _fit_sub_tile(sub: int, block: int) -> int:
    """The largest 128-aligned halving of ``sub`` that divides the grid
    tile; a tile under 128 is its own sub-tile."""
    if block <= sub or block < 128:
        return block
    while sub > 128 and block % sub:
        sub //= 2
    return sub


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, sm_scale, interpret, block_q, block_k,
           sub_tile=None, window=None):
    out, _ = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                        sub_tile, interpret, window)
    return out


def _flash_fwd_rule(q, k, v, causal, sm_scale, interpret, block_q,
                    block_k, sub_tile, window):
    out, lse = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                          sub_tile, interpret, window)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, sm_scale, interpret, block_q, block_k,
                    sub_tile, window, res, g):
    if window is not None:
        raise NotImplementedError(
            "flash_attention(window=) has a forward kernel only: the "
            "backward kernels walk the causal schedule.  Train a "
            "windowed layer with seq_strategy='dense'.")
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g, causal, sm_scale, block_q,
                      block_k, sub_tile, interpret)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    interpret: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    sub_tile: Optional[int] = None,
                    window: Optional[int] = None):
    """Attention over (B, H, T, D) tensors without materializing scores.

    Uses the Pallas kernels on TPU (or under ``interpret=True``); plain
    XLA attention elsewhere.  The kernel path takes sequence lengths
    that are 128-multiples, or short 8-aligned sequences that fit one
    block; any other length takes the dense reference (callers pad —
    the data layer's fixed-length contract already guarantees static
    shapes).
    ``block_q``/``block_k`` override the grid tile and ``sub_tile`` the
    sub-tile walked inside it; None means chosen from the shape
    (``_pick_block``, ``_pick_sub_tile``) — exposed for the on-hardware
    tuning sweeps.
    ``window`` (causal only): query ``t`` sees keys ``t - window + 1 ..
    t``.  The forward kernel's walk skips the sub-tiles wholly older
    than the window and masks those its edge crosses; a window of ``T``
    or more is the causal schedule, sub-tile for sub-tile.  Forward
    only: differentiating the windowed kernel raises.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    T, S = q.shape[2], k.shape[2]
    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"window={window} needs causal=True and a "
                             "length of at least 1")
        window = None if window >= T else int(window)

    def blockable(n):  # one whole block (8-aligned) or a 128-multiple
        return (n % 128 == 0) or (n < 128 and n % 8 == 0)

    if use_kernel(interpret) and blockable(T) and blockable(S):
        if window is None:
            return _flash(q, k, v, causal, sm_scale, interpret,
                          block_q, block_k, sub_tile)
        return _flash(q, k, v, causal, sm_scale, interpret,
                      block_q, block_k, sub_tile, window)
    return _attention_reference(q, k, v, causal, sm_scale, window)
