"""The grouped matrix product of a decode step's expert layer — row
``r`` of group ``g`` times ``w[g]`` — as one Pallas TPU kernel in which
every hit expert's matrix crosses HBM ONCE, as one contiguous copy of
3.9-8 MB.

What a decode step holds (``parallel/moe.py``, ``_dropless_piece``): the
sorted buffer ``xs [R, k]`` of at most 2048 rows, ``group_sizes [G]``
(rows an expert, a handful each) and the experts' ``w [G, k, n]``.  The
step is bound by the weights' bytes: a group's ``[k, n]`` matrix is
hundreds of times its rows.

The kernel:

- grid ``(groups that have rows,)`` — a prefetched list of their
  indices, its length the grid's size, so an expert no token chose
  costs neither a copy nor a step (a bucket of 8 rows hits a third of
  the experts Xing4.0 holds; a step that only names the block already
  held still cost 1.5-6 us on the chip): a step owns ONE group and its
  WHOLE matrix — the block ``(g, 0, 0)`` of ``w``, one copy a group BY
  CONSTRUCTION, contiguous
  in HBM, the whole depth at once so the float32 sum is complete when
  the product returns.  (A ``[k, tn < n]`` slab is ``k / 16`` strided
  pieces, and how fast those stream depends on where the array lies:
  Xing4.0's ``[3584, 512]`` of 1024 columns took 0.315 ms a call for
  one weight array and 0.392 for its twin, the whole ``[3584, 1024]``
  0.318 for both; Command A+'s ``[4096, 512]`` of 4096 read 92.1 % of
  bytes-once in two calls to the chip and 88.6 in a third.  PERF.md §6
  "PR 43".);
- the row buffer is in VMEM whole, and a group's rows are walked in
  chunks of ``chunk`` rows — :func:`chunk_rows` of the buffer: 128
  where that divides its rows, else 64 (SmallThinker's 192) — from a
  prefetched offset rounded DOWN to a sublane tile of the operand (16
  rows of bfloat16): the MXU takes as long over 16 rows as over 128 —
  the matrix passes through it either way — so ONE product covers any
  group of up to ``chunk - 15`` rows wherever it lies, and no group is
  visited twice for straddling a row tile, as the 128-row tiles of
  megablox's ``gmm`` make it;
- the product's rows of OTHER groups (before the group's first row,
  past its last) are masked at the write, into the ``[R, n]`` output
  that stays in VMEM until the last group; rows past
  ``sum(group_sizes)`` are never written and come out UNDEFINED
  (``grouped_matmul``'s contract);
- arithmetic: operands in the buffer's dtype, float32 accumulation over
  the whole depth, one cast at the write — on the chip, bit for bit
  what ``jax.lax.ragged_dot`` gives.

``tools/moe_grouped_sweep.py`` times it against ``gmm`` and
``ragged_dot`` at the five serving cells' shapes with uneven group
sizes; PERF.md §6 "PR 43" and "PR 47" have the tables.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ._support import pl, pltpu

# rows of one product, the first that divides the buffer's rows: a
# group of up to chunk - 15 rows is one product.  The chip measured
# chunks of 32 / 64 / 128 level while a matrix's copy hides the product
# (PERF.md §6 "PR 43"); 128 makes the fewest
CHUNKS = (128, 64)
# the largest [k, n] matrix the kernel takes as one tile (the pipeline
# holds two): SmallThinker's 3.9 MB, LFM2's and GLM's 6 MB and Xing4.0's
# 7 MB are; Command A+'s 32 MB are not, and keep ``gmm``
WHOLE_BYTES = 8 << 20
# what the kernel may hold in VMEM: half a v5e's 128 MiB, the rest is
# the compiler's for what it keeps there between operations (its own
# limit for a kernel is 16 MiB unless told)
VMEM_BYTES = 64 << 20


def chunk_rows(R: int) -> int:
    """Rows a product of a buffer of ``R`` rows: the first of ``CHUNKS``
    that divides ``R``; 0 where none does (no plan for such a buffer)."""
    return next((c for c in CHUNKS if R % c == 0), 0)


def vmem_bytes(R: int, k: int, n: int, itemsize: int, chunk: int) -> int:
    """What a call holds in VMEM: the matrix twice, the row buffer and
    the output (the pipeline allots two of each, copies one) and a
    chunk's float32 product."""
    return (2 * k * n * itemsize + 2 * R * k * itemsize
            + 2 * R * n * itemsize + chunk * n * 4)


def fits(R: int, k: int, n: int, itemsize: int, chunk: int) -> bool:
    """Whether the kernel takes ``[R, k] x [G, k, n]`` in products of
    ``chunk`` rows: whole chunks of rows, whole lane tiles of depth and
    of columns, the matrix one tile and the call within the kernel's
    VMEM."""
    return (chunk > 0 and R % chunk == 0 and k % 128 == 0 and n % 128 == 0
            and k * n * itemsize <= WHOLE_BYTES
            and vmem_bytes(R, k, n, itemsize, chunk) <= VMEM_BYTES)


def _kernel(offs_ref, hit_ref, xs_ref, w_ref, o_ref, *, chunk: int,
            align: int):
    g = hit_ref[pl.program_id(0)]
    start, end = offs_ref[g], offs_ref[g + 1]
    R = xs_ref.shape[0]
    base = start // align * align
    w = w_ref[...]

    def one(c, _):
        # the last chunk of the buffer is pulled back inside it: the
        # rows it repeats are written the same values again
        r0 = pl.multiple_of(jnp.minimum(base + c * chunk, R - chunk), align)
        rows = pl.ds(r0, chunk)
        y = jnp.dot(xs_ref[rows, :], w, preferred_element_type=jnp.float32)
        at = r0 + lax.broadcasted_iota(jnp.int32, y.shape, 0)
        mine = (at >= start) & (at < end)
        o_ref[rows, :] = jnp.where(mine, y.astype(o_ref.dtype),
                                   o_ref[rows, :])
        return _

    lax.fori_loop(0, pl.cdiv(end - base, chunk), one, None)


def grouped_decode(xs, w, group_sizes, *, chunk: int | None = None,
                   interpret: bool = False):
    """``xs [R, k]`` rows sorted by group, ``w [G, k, n]``,
    ``group_sizes [G]`` int32 -> ``[R, n]`` in ``xs``'s dtype: row ``r``
    of group ``g`` times ``w[g]``; rows past ``sum(group_sizes)``
    undefined.  ``R`` a multiple of ``chunk`` (None: :func:`chunk_rows`
    of ``R``; the sweep's lever), which is a multiple of the operand's
    sublane tile."""
    R, k = xs.shape
    G, _, n = w.shape
    align = max(8, 32 // xs.dtype.itemsize)
    if chunk is None:
        chunk = chunk_rows(R)
    if not chunk or R % chunk or chunk % align:
        raise ValueError(f"grouped_decode: {R} rows are no whole chunks of "
                         f"{chunk} rows in tiles of {align}")
    sizes = group_sizes.astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    # the groups that have rows, in order: hit[j] is the j-th of them
    # (a comparison of G x G numbers: no sort, no scatter)
    ids = jnp.arange(G, dtype=jnp.int32)
    place = jnp.where(sizes > 0, jnp.cumsum(sizes > 0) - 1, -1)
    hit = jnp.sum(jnp.where(place[:, None] == ids[None, :], ids[:, None], 0),
                  axis=0)

    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, align=align),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(jnp.sum(sizes > 0),),
            in_specs=[
                pl.BlockSpec((R, k), lambda i, offs, hit: (0, 0)),
                pl.BlockSpec((None, k, n),
                             lambda i, offs, hit: (hit[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((R, n), lambda i, offs, hit: (0, 0))),
        out_shape=jax.ShapeDtypeStruct((R, n), xs.dtype),
        # every group writes its rows of the one output block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
        name="grouped_decode",
    )(offs, hit, xs, w.astype(xs.dtype))
