"""The absorbed attend of a latent decode step — scores, softmax and
``P c_kv`` of ONE token a row against the cached latent — as one Pallas
TPU kernel that reads the cache once and only as far as it is written.

What a decode step of latent attention holds (``models/generate.py``,
``_latent_attention``): the absorbed query ``q_lat [B, H, kv_rank]`` and
its rotated part ``q_rope [B, H, rope]``, the layer's cache ``ckv [B, T,
kv_rank]`` and ``kr [B, rope, T]`` (positions MINOR: ``rope`` is half a
lane tile, so ``[B, T, rope]`` would pad every position's 64 numbers to
128), and ONE position ``pos`` for the whole batch.  The plain form is
two einsums over the whole cache with a softmax between them
(:func:`latent_attend_reference`): the cache is read twice a layer and
step, all ``T`` positions of it whatever ``pos``.

The kernel (pallas_guide.md, boom_attention_tricks.md §8-11):

- grid ``(B / rows, T / block)``, rows "parallel", positions
  "arbitrary": a program owns ``rows`` batch rows and walks the cached
  positions in blocks with the running maximum / sum / accumulator in
  VMEM scratch, so each block of ``ckv`` is in VMEM once and serves both
  products;
- ``pos`` is a prefetched scalar, and the walk is RIGHT-ALIGNED in the
  grid: the last step holds the block ``pos`` falls in, the steps before
  the walk's first block hold block 0 and compute nothing.  The pipeline
  copies a block only when its index changes, so a block wholly beyond
  ``pos`` is never FETCHED; and because the idle steps come first, the
  copy of the next program's first block runs under this program's last
  products (idle steps at the end left it exposed: 0.215 against 0.192
  ms a call at 384 live positions, PERF.md §6 "PR 37").  Only the last
  step builds a mask, and there the latent rows beyond ``pos`` are
  zeroed too, so what a never-written slot holds cannot reach the output
  even as ``0 * x``;
- one side of every product is the ``H`` heads of a row (20 of the
  MXU's 128), so a layer's products take about as long as one read of
  its cache: the walk only pays because the pipeline copies block k+1
  while block k is multiplied, and because a step takes all its rows
  product by product (batched einsums, unrolled by the compiler): a
  rolled loop of rows, each row's score product, softmax and ``P c_kv``
  in a chain, ran twice as long (0.40 against 0.22 ms);
- arithmetic as the plain form: scores accumulate in float32, the scale
  and the softmax are float32, the probabilities are cast to the cache's
  dtype before ``P c_kv``, which accumulates in float32.

Which arm a program compiles is decided by SHAPES (:func:`attend_plan`),
under ``_support.use_kernel``'s rule — no argument, no environment
variable: the kernel where a layer's cache is large enough for the
single pass to win (the sweep of ``tools/latent_attend_sweep.py``,
PERF.md §6 "PR 37"), the einsums everywhere else — small buckets, every
backend but a TPU, ``Tq > 1``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ._support import pl, pltpu, use_kernel
from .flash_attention import _init_softmax_scratch

_LANES = 128
# positions a block: a lane tile of ``kr``'s minor axis; a longer block
# reads further past ``pos`` (the mean over-read is half a block)
BLOCK_POSITIONS = 128
# bytes of one ``ckv`` block in VMEM (double-buffered by the pipeline):
# decides how many rows a program owns
_BLOCK_BYTES = 2 << 20
# a layer's cache (``B * T * (kv_rank + rope)`` numbers) from which the
# kernel beats the einsums on a v5e: at 640 positions 8 rows (5.9 MB)
# take 0.020 ms against 0.030, 4 rows about what the einsums take and
# 1-2 rows the same (PERF.md §6 "PR 37")
KERNEL_MIN_CACHE_BYTES = 4 << 20


def latent_attend_reference(q_lat, q_rope, ckv, kr, pos, qk_dim: int,
                            scale_mult: float = 1.0):
    """The plain form, any ``Tq``: ``q_lat [B, H, Tq, kv_rank]``,
    ``q_rope [B, H, Tq, rope]`` at positions ``pos .. pos + Tq - 1``
    against ``ckv [B, T, kv_rank]`` / ``kr [B, rope, T]`` ->
    ``o_lat [B, H, Tq, kv_rank]``.  Two reads of the whole cache.  The
    scores are divided by ``sqrt(qk_dim)`` and, where a rotation scaling
    asks for it (YaRN's ``mscale^2``: ``LatentAttention.softmax_mult``),
    multiplied by ``scale_mult``."""
    dt = q_lat.dtype
    ct = jnp.promote_types(dt, jnp.float32)
    qpos = pos + jnp.arange(q_lat.shape[2])
    scores = (jnp.einsum("bhqc,bkc->bhqk", q_lat, ckv,
                         preferred_element_type=ct)
              + jnp.einsum("bhqr,brk->bhqk", q_rope, kr,
                           preferred_element_type=ct))
    scores = scores / jnp.sqrt(jnp.asarray(qk_dim, ct))
    if scale_mult != 1.0:
        scores = scores * jnp.asarray(scale_mult, ct)
    seen = jnp.arange(ckv.shape[1])[None, :] <= qpos[:, None]
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkc->bhqc", probs.astype(dt), ckv)


def _rows_per_program(B: int, block: int, width: int, itemsize: int) -> int:
    """Batch rows one program owns: the largest power of two that
    divides ``B`` and keeps a ``ckv`` block within ``_BLOCK_BYTES``."""
    rows = 1
    while (B % (2 * rows) == 0
           and 2 * rows * block * width * itemsize <= _BLOCK_BYTES):
        rows *= 2
    return rows


def attend_plan(B: int, T: int, kv_rank: int, rope: int, dtype,
                Tq: int = 1, interpret: bool = False) -> int:
    """Positions a block of the kernel arm for a decode step of ``B``
    rows against ``T`` cached positions of ``kv_rank + rope`` numbers in
    ``dtype`` — or 0: the einsum arm.  The ONE rule both
    ``models/generate.py`` and ``cache_footprint`` read.  On a TPU the
    layer's cache has to be large enough for the single pass to win, a
    whole number of blocks long (``_cache_len`` gives a multiple of 128
    unless the model's ``max_len`` cuts it) and its latent whole lane
    tiles wide (what was compiled and measured); the interpreter takes
    any size, a cache no block divides as one block."""
    if Tq != 1 or not use_kernel(interpret):
        return 0
    if interpret:
        return T if T % BLOCK_POSITIONS else BLOCK_POSITIONS
    nbytes = B * T * (kv_rank + rope) * jnp.dtype(dtype).itemsize
    if T % BLOCK_POSITIONS or kv_rank % _LANES \
            or nbytes < KERNEL_MIN_CACHE_BYTES:
        return 0
    return BLOCK_POSITIONS


def _kernel(pos_ref, ql_ref, qr_ref, c_ref, r_ref, o_ref, m_scr, l_scr,
            acc_scr, *, qk_dim: int, scale_mult: float, block: int,
            n_blocks: int):
    step = pl.program_id(1)
    pos = pos_ref[0]
    # the walk ends at the block ``pos`` falls in, on the LAST grid step;
    # the steps before its first block compute nothing (``cache_at``)
    t = step - (n_blocks - 1 - pos // block)

    pl.when(step == 0)(functools.partial(_init_softmax_scratch, m_scr,
                                         l_scr, acc_scr))

    def walk(masked: bool):
        # all rows of the program at once, product by product: the
        # compiler unrolls the rows, and the MXU goes from one row's
        # product to the next's while the first's softmax is taken
        c = c_ref[...]                                  # [rows, block, C]
        s = (jnp.einsum("bhc,bkc->bhk", ql_ref[...], c,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhr,brk->bhk", qr_ref[...], r_ref[...],
                          preferred_element_type=jnp.float32))
        s = s / jnp.sqrt(jnp.float32(qk_dim))           # [rows, H, block]
        if scale_mult != 1.0:
            s = s * jnp.float32(scale_mult)
        if masked:
            at = t * block + lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(at <= pos, s, -jnp.inf)
            live = t * block + lax.broadcasted_iota(
                jnp.int32, c.shape, 1) <= pos
            c = jnp.where(live, c, jnp.zeros_like(c))
        m_old = m_scr[...]                              # [rows, H, LANES]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new[:, :, :1])
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = alpha[:, :, :1] * acc_scr[...] + jnp.einsum(
            "bhk,bkc->bhc", p.astype(c.dtype), c,
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    pl.when((t >= 0) & (step < n_blocks - 1))(functools.partial(walk, False))

    @pl.when(step == n_blocks - 1)
    def _finish():
        walk(True)
        o_ref[...] = (acc_scr[...] / l_scr[...][:, :, :1]).astype(o_ref.dtype)


def _latent_attend_kernel(q_lat, q_rope, ckv, kr, pos, qk_dim: int,
                          block: int, interpret: bool, rows=None,
                          scale_mult: float = 1.0):
    """The kernel arm on ``q_lat [B, H, C]`` / ``q_rope [B, H, R]``;
    ``rows`` (batch rows a program; the sweep's lever) defaults to
    :func:`_rows_per_program`."""
    B, H, C = q_lat.shape
    R, T = kr.shape[1], kr.shape[2]
    rows = rows or _rows_per_program(B, block, C, ckv.dtype.itemsize)
    n_blocks = T // block
    # what the pipeline holds: every operand's block twice, the scratch
    # once; the compiler's own limit (16 MiB on a v5e) unless that is
    # not enough
    held = (2 * rows * ((block + 2 * H) * (C + R)) * ckv.dtype.itemsize
            + rows * H * (C + 2 * _LANES) * 4)

    def cache_at(minor):
        """Index map of a cache leaf whose position axis is the minor
        one (``kr``) or the second-minor (``ckv``).  The walk is right-aligned in the grid:
        the last step holds the block ``pos`` is in, and the steps
        before the first block hold block 0 already — the pipeline
        copies a block only when its index changes, so nothing beyond
        ``pos``'s block is fetched, and the first block of the NEXT
        program is copied under this program's last product, not under
        an empty step."""
        def index(b, step, pos_ref):
            at = jnp.maximum(step - (n_blocks - 1 - pos_ref[0] // block), 0)
            return (b, 0, at) if minor else (b, at, 0)
        return index

    def by_row(b, step, pos_ref):
        return (b, 0, 0)

    kernel = functools.partial(_kernel, qk_dim=qk_dim,
                               scale_mult=float(scale_mult), block=block,
                               n_blocks=n_blocks)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // rows, n_blocks),
            in_specs=[
                pl.BlockSpec((rows, H, C), by_row),
                pl.BlockSpec((rows, H, R), by_row),
                pl.BlockSpec((rows, block, C), cache_at(False)),
                pl.BlockSpec((rows, R, block), cache_at(True)),
            ],
            out_specs=pl.BlockSpec((rows, H, C), by_row),
            scratch_shapes=[
                pltpu.VMEM((rows, H, _LANES), jnp.float32),  # running max
                pltpu.VMEM((rows, H, _LANES), jnp.float32),  # running sum
                pltpu.VMEM((rows, H, C), jnp.float32),       # P c_kv so far
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, C), q_lat.dtype),
        # rows are independent; the walk over positions carries the
        # online softmax and stays sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(16 << 20, 2 * held)),
        interpret=interpret,
    )(jnp.reshape(pos, (1,)).astype(jnp.int32), q_lat, q_rope, ckv, kr)


def latent_attend(q_lat, q_rope, ckv, kr, pos, qk_dim: int,
                  interpret: bool = False, scale_mult: float = 1.0):
    """``o_lat [B, H, Tq, kv_rank]``: attention of the absorbed queries
    ``q_lat [B, H, Tq, kv_rank]`` / ``q_rope [B, H, Tq, rope]`` at
    positions ``pos ..`` on the cached latent ``ckv [B, T, kv_rank]``
    and shared rotated key ``kr [B, rope, T]``, positions ``0 .. pos +
    Tq - 1`` seen, the scores times ``scale_mult / sqrt(qk_dim)``.  The
    kernel where :func:`attend_plan` says so, the plain einsums
    otherwise."""
    B, H, Tq, C = q_lat.shape
    block = attend_plan(B, ckv.shape[1], C, kr.shape[1], ckv.dtype, Tq,
                        interpret)
    if not block:
        return latent_attend_reference(q_lat, q_rope, ckv, kr, pos, qk_dim,
                                       scale_mult)
    o = _latent_attend_kernel(q_lat[:, :, 0], q_rope[:, :, 0], ckv, kr, pos,
                              qk_dim, block, interpret,
                              scale_mult=scale_mult)
    return o[:, :, None]
