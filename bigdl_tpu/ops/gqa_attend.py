"""The attend of a per-head K/V decode step — scores, softmax and ``P V``
of ONE query token a row against the un-repeated K and V leaves — as one
Pallas TPU kernel that reads the cache once and only as far as it is
written.

What a decode step holds (``nn.MultiHeadAttention.step``): the
rotated query ``q [B, H, 1, Dh]``, the layer's cache ``k``, ``v`` ``[B,
Hkv, T, Dh]`` (``Hkv`` K/V heads, each shared by ``G = H / Hkv`` query
heads) and ONE position ``pos`` for the whole batch.  The plain form
(:func:`gqa_attend_reference`, which stays the reference) is two grouped
einsums over the whole cache with a float32 softmax between them: each
leaf streams through the MXU ``G`` query rows at a time, all ``T``
positions of it whatever ``pos``.

The kernel (the recipe of ``ops/latent_attend.py``, PERF.md §6 "PR 37";
pallas_guide.md, boom_attention_tricks.md §8-11):

- grid ``(B / rows, T / block)``, rows "parallel", positions
  "arbitrary": a program owns ``rows`` batch rows with all their K/V
  heads and walks the cached positions in blocks, the running maximum /
  sum / accumulator in VMEM scratch, so a block of K and of V is in VMEM
  once;
- ``pos`` is a prefetched scalar and the walk is RIGHT-ALIGNED in the
  grid: the last step holds the block ``pos`` falls in, the steps before
  the walk's first block hold block 0 and compute nothing.  The pipeline
  copies a block only when its index changes, so a block wholly beyond
  ``pos`` is never fetched, and the next program's first block is
  copied under this program's last products.  Only the last step builds
  a mask, and there the V rows beyond ``pos`` are zeroed too, so what a
  never-written slot holds cannot reach the output even as ``0 * x``;
- a step takes all its ``rows * Hkv`` (row, K/V head) pairs product by
  product (batched einsums the compiler unrolls; no rolled loop of
  rows).  The ``G`` query heads of a pair are the product's small side:
  they sit on the sublanes of one VMEM tile, padded there and not in
  HBM;
- arithmetic: scores accumulate in float32, the scale and the softmax
  are float32, the probabilities are cast to the cache's dtype before
  ``P V``, which accumulates in float32.

**The leaf's layout is the kernel's to decide, and at a head of 64 that
is what the kernel is FOR** (PERF.md §6 "PR 41").  A Mosaic operand is
row-major: positions second-minor, a position's ``Dh`` numbers along
the lanes.  Where only einsums read a ``[B, Hkv, T, 64]`` leaf the
compiler lays it out positions-MINOR (64 is half a lane tile; ``T`` is
whole ones), and the step's one-position write into that layout is a
scatter of ``B * Hkv * 64`` single numbers: 0.43 ms a leaf at 256 rows,
two thirds of the layer's time, where the attend itself is 0.2.  Asking
for the leaf row-major makes the compiler carry it so through the
decode loop: the write is 2048 rows of 128 bytes (0.03 ms), the kernel
reads each position's 64 numbers in a lane row of 128 — twice the
counted bytes, 0.46 ms a step — and the layer takes 0.61 ms where it
took 1.30.  Reading the positions-minor leaf as it lies (``[B, Hkv, Dh,
T]``, a free view) made the kernel itself twice as fast and left the
write what it was: that step was SLOWER than the einsums' by 0.1 ms,
and the arm is not kept.  A head of whole lane tiles is row-major under
either arm; there the kernel gains what the einsums lose to their form,
from a larger cache on.  ``cache_footprint`` counts the numbers a leaf
holds, not the lanes a head of 64 leaves empty: on the kernel arm such
a leaf takes twice its ``kv_cache_bytes`` on the device.

Which arm a program compiles is decided by SHAPES (:func:`attend_plan`),
under ``_support.use_kernel``'s rule — no argument, no environment
variable: the kernel where a layer's K and V are large enough for it to
win (the sweep of ``tools/gqa_attend_sweep.py``, PERF.md §6 "PR 41"),
the einsums everywhere else — small buckets, every backend but a TPU,
``Tq > 1``, a ring, int8 K/V.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ._support import pl, pltpu, use_kernel
from .flash_attention import _init_softmax_scratch

_LANES = 128
# positions a block: a lane tile of the scores' minor axis; a longer
# block reads further past ``pos`` (the mean over-read is half a block)
BLOCK_POSITIONS = 128
# bytes of one K (or V) block in VMEM, a row of a head padded to whole
# lane tiles (the pipeline holds two of each): decides how many batch
# rows a program owns
_BLOCK_BYTES = 2 << 20
# a layer's K and V (``2 * B * Hkv * T * Dh`` numbers) from which the
# kernel arm beats the einsum arm on a v5e (PERF.md §6 "PR 41").  A head
# of whole lane tiles: 134 MB (128 rows x 256 positions, 16 query heads
# a K/V head) 0.200 against 0.211 ms a call and + 0.9 % tokens/s in the
# program, 71 MB 0.113 against 0.119, 67 MB (64 rows of the same: this
# very size) 0.106-0.109 against 0.111-0.115; 50 MB and under (34 MB of
# the same shape too), level within a call's launch
KERNEL_MIN_CACHE_BYTES = 64 << 20
# a head of 64: the einsum arm's leaf lies positions-minor and every
# step's write into it is a scatter; write and attend together are twice
# as fast on the kernel arm from the smallest size measured, 8 rows x 384
# positions (0.023 against 0.040-0.048 ms).  (Einsums over a leaf the
# compiler is TOLD to carry row-major, which the program does not have,
# read 0.675 against this arm's 0.57 ms at 256 rows and are the faster
# at 64 rows and under: PERF.md §7 "after PR 40" g.)
KERNEL_MIN_CACHE_BYTES_HEAD_64 = 6 << 20


def gqa_attend_reference(q, k_cache, v_cache, pos, H, Hkv, Dh, k_pos=None,
                         window=None):
    """The plain form, any ``Tq``, and the kernel's reference: causal
    attention of Tq queries (absolute positions pos..pos+Tq-1) against
    a dense ``[B, Hkv, Tm, Dh]`` cache view.  GQA contracts the query
    groups against the UN-repeated cache — a repeat here would
    materialize H/Hkv copies of the whole cache every decode step,
    exactly the bandwidth GQA exists to save.  Shared by the static
    cache and the paged decode path (which passes a page-gathered
    view), so the two can never drift numerically.  ``k_pos`` [Tm] gives
    each cache slot's ABSOLUTE position when the view is not contiguous
    from 0 — the page-window path gathers only the live pages, so slot
    index and position diverge.  ``window`` adds the sliding window's
    far edge, ``k_pos > q_pos - window``, and masks a slot that holds no
    position yet (``k_pos < 0``: a ring that is not full)."""
    Tq, Tm = q.shape[2], k_cache.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.float32(Dh)).astype(q.dtype)
    qpos = pos + jnp.arange(Tq)
    if k_pos is None:
        k_pos = jnp.arange(Tm)
    mask = k_pos[None, :] <= qpos[:, None]            # [Tq, Tm]
    if window is not None:
        mask = (mask & (k_pos[None, :] > qpos[:, None] - window)
                & (k_pos[None, :] >= 0))
    if Hkv == H:
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k_cache) * scale
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype),
                          v_cache)
    B = q.shape[0]
    qg = q.reshape(B, Hkv, H // Hkv, Tq, Dh)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_cache) * scale
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", probs.astype(q.dtype),
                   v_cache)
    return o.reshape(B, H, Tq, Dh)


def _lane_width(Dh: int) -> int:
    """Lanes a position's ``Dh`` numbers fill in VMEM: whole tiles."""
    return -(-Dh // _LANES) * _LANES


def _rows_per_program(B: int, Hkv: int, block: int, Dh: int,
                      itemsize: int) -> int:
    """Batch rows one program owns: the largest power of two that
    divides ``B`` and keeps a K block within ``_BLOCK_BYTES``."""
    width = _lane_width(Dh)
    rows = 1
    while (B % (2 * rows) == 0 and
           2 * rows * Hkv * block * width * itemsize <= _BLOCK_BYTES):
        rows *= 2
    return rows


def attend_plan(B: int, Hkv: int, T: int, Dh: int, dtype, Tq: int = 1,
                window=None, interpret: bool = False) -> int:
    """Positions a block of the kernel arm for a decode step of ``B``
    rows and ``Hkv`` K/V heads against ``T`` cached positions of ``Dh``
    numbers stored in ``dtype`` — or 0: the einsum arm.  The ONE rule
    both the decode step (``nn.MultiHeadAttention.step``) and
    ``cache_footprint`` read.  The kernel takes one query a row against
    a cache that is contiguous from position 0 (no ``window``: a ring's
    slots are not positions) and held in a floating dtype (int8 K/V is
    dequantised by the einsums' operand read).  On a TPU the layer's K
    and V have to be large enough for the single pass to win, a whole
    number of blocks long (``_cache_len`` gives a multiple of 128 unless
    the model's ``max_len`` cuts it) and the head half a lane tile or
    whole ones (what was compiled and measured); the interpreter takes
    any size, a cache no block divides as one block."""
    dtype = jnp.dtype(dtype)
    if (Tq != 1 or window is not None or not use_kernel(interpret)
            or not jnp.issubdtype(dtype, jnp.floating)):
        return 0
    if interpret:
        return T if T % BLOCK_POSITIONS else BLOCK_POSITIONS
    if T % BLOCK_POSITIONS or (Dh != 64 and Dh % _LANES):
        return 0
    least = (KERNEL_MIN_CACHE_BYTES_HEAD_64 if Dh == 64
             else KERNEL_MIN_CACHE_BYTES)
    nbytes = 2 * B * Hkv * T * Dh * dtype.itemsize
    return BLOCK_POSITIONS if nbytes >= least else 0


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            block: int, n_blocks: int):
    step = pl.program_id(1)
    pos = pos_ref[0]
    # the walk ends at the block ``pos`` falls in, on the LAST grid step;
    # the steps before its first block compute nothing (``cache_at``)
    t = step - (n_blocks - 1 - pos // block)
    rows, Hkv, G, Dh = q_ref.shape
    pairs = rows * Hkv

    pl.when(step == 0)(functools.partial(_init_softmax_scratch, m_scr,
                                         l_scr, acc_scr))

    def walk(masked: bool):
        # every (row, K/V head) pair of the program at once, product by
        # product: the compiler unrolls the pairs, and the MXU goes from
        # one pair's product to the next's while the first's softmax is
        # taken.  Merging the two leading axes moves nothing.
        q = q_ref[...].reshape(pairs, G, Dh)
        k = k_ref[...].reshape(pairs, block, Dh)
        v = v_ref[...].reshape(pairs, block, Dh)
        s = jnp.einsum("ngd,nkd->ngk", q, k,
                       preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(jnp.float32(Dh))               # [pairs, G, block]
        if masked:
            at = t * block + lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(at <= pos, s, -jnp.inf)
            live = t * block + lax.broadcasted_iota(jnp.int32, v.shape,
                                                    1) <= pos
            v = jnp.where(live, v, jnp.zeros_like(v))
        m_old = m_scr[...]                              # [pairs, G, LANES]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new[:, :, :1])
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = alpha[:, :, :1] * acc_scr[...] + jnp.einsum(
            "ngk,nkd->ngd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    pl.when((t >= 0) & (step < n_blocks - 1))(functools.partial(walk, False))

    @pl.when(step == n_blocks - 1)
    def _finish():
        walk(True)
        o = acc_scr[...] / l_scr[...][:, :, :1]
        o_ref[...] = o.reshape(rows, Hkv, G, Dh).astype(o_ref.dtype)


def _gqa_attend_kernel(q, k_cache, v_cache, pos, block: int,
                       interpret: bool, rows=None):
    """The kernel arm on ``q [B, H, Dh]`` against ``k_cache``,
    ``v_cache`` ``[B, Hkv, T, Dh]`` -> ``[B, H, Dh]``; ``rows`` (batch
    rows a program; the sweep's lever) defaults to
    :func:`_rows_per_program`."""
    B, H, Dh = q.shape
    Hkv, T = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    itemsize = k_cache.dtype.itemsize
    rows = rows or _rows_per_program(B, Hkv, block, Dh, itemsize)
    n_blocks = T // block
    pairs = rows * Hkv
    width = _lane_width(Dh)
    # what the pipeline holds: K and V blocks twice each, the query and
    # the result (G padded to a sublane tile) twice, the scratch once;
    # the compiler's own limit (16 MiB on a v5e) unless that is not
    # enough
    held = (4 * pairs * block * width * itemsize
            + 4 * pairs * 16 * width * itemsize
            + pairs * 8 * (width + 2 * _LANES) * 4)

    def cache_at(b, step, pos_ref):
        """The walk is right-aligned in the grid: the last step holds the
        block ``pos`` is in, and the steps before the first block hold
        block 0 already — the pipeline copies a block only when its
        index changes, so nothing beyond ``pos``'s block is fetched, and
        the first block of the NEXT program is copied under this
        program's last product, not under an empty step."""
        at = jnp.maximum(step - (n_blocks - 1 - pos_ref[0] // block), 0)
        return (b, 0, at, 0)

    def by_row(b, step, pos_ref):
        return (b, 0, 0, 0)

    kv_block = (rows, Hkv, block, Dh)
    o = pl.pallas_call(
        functools.partial(_kernel, block=block, n_blocks=n_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // rows, n_blocks),
            in_specs=[
                pl.BlockSpec((rows, Hkv, G, Dh), by_row),
                pl.BlockSpec(kv_block, cache_at),
                pl.BlockSpec(kv_block, cache_at),
            ],
            out_specs=pl.BlockSpec((rows, Hkv, G, Dh), by_row),
            scratch_shapes=[
                pltpu.VMEM((pairs, G, _LANES), jnp.float32),  # running max
                pltpu.VMEM((pairs, G, _LANES), jnp.float32),  # running sum
                pltpu.VMEM((pairs, G, Dh), jnp.float32),      # P V so far
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        # rows are independent; the walk over positions carries the
        # online softmax and stays sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(16 << 20, 2 * held)),
        interpret=interpret,
    )(jnp.reshape(pos, (1,)).astype(jnp.int32),
      q.reshape(B, Hkv, G, Dh), k_cache, v_cache)
    return o.reshape(B, H, Dh)
