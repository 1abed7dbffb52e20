"""The grouped matrix product of ONE PIECE of a prompt pass through the
expert layer — row ``r`` of group ``g`` times ``w[g]`` over tens of
thousands of sorted rows — as a Pallas TPU kernel with ONE k tile: an
expert's whole ``[k, n]`` matrix is the weight tile.

What a prompt piece holds (``parallel/moe.py``, ``_dropless_piece``): the
sorted buffer ``xs [R, k]`` of up to 32 768 rows, ``group_sizes [G]``
(hundreds of rows an expert) and the experts' ``w [G, k, n]``.  Unlike a
decode step's (``ops/grouped_decode.py``) it is bound by its operations:
every matrix is read for hundreds of rows.

The kernel:

- the grid is megablox's (``jax.experimental.pallas.ops.tpu.megablox``):
  one step a VISIT, a (row tile, group) pair that shares rows — a group
  takes the row tiles it spans, a tile that two groups share is visited
  by both and each writes its own rows of it.  With the whole depth in
  the weight tile the float32 sum is complete when the product returns:
  no accumulator, no k loop, one cast at the write;
- the weights stay in HBM and the kernel copies them itself, into two
  slots of VMEM: a group's matrix ONCE, however many row tiles the group
  spans, and the NEXT group's at this group's first visit, at low
  priority, so it arrives behind the visits instead of ahead of the
  next row tile.  (The pipeline of a ``BlockSpec`` asks for a block one
  grid step ahead: every new group's 3.9 MB waited 4.6 us in full on
  the chip where a visit lasts 2.7, and at the default priority the row
  tiles queued behind the copy.  PERF.md §6 "PR 48".);
- the visit lists come from the sizes by comparisons and sums of
  ``[visits, G]`` numbers: no sort, no scatter and NO LOOP — megablox's
  own ``make_group_metadata`` bins with ``jnp.histogram``, a ``while`` on
  the device, and a generate program keeps ONE ``while``, its decode
  scan (``benchmark/readers`` count scans and steps by it);
- rows of OTHER groups in a visited tile are kept at the write; a tile's
  rows past ``sum(group_sizes)`` are whatever VMEM held and come out
  UNDEFINED, as do the tiles past the last visit (``grouped_matmul``'s
  contract: the caller masks them);
- arithmetic: operands in the buffer's dtype, float32 accumulation over
  the whole depth, one cast — what ``jax.lax.ragged_dot`` gives.

``tools/moe_grouped_sweep.py --shapes smallthinker_prefill --arms
prefill,gmm,ragged`` times it against megablox's ``gmm`` under the same
tiles and ``ragged_dot``;
PERF.md §6 "PR 48" has the table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ._support import pl, pltpu

# rows of a visit's tile: a visit of 128 rows reads 92-94 % of the MXU's
# peak; one of 256 computes twice the rows for nothing where a group
# ends inside the tile, and the chip read 0.92 ms a call for 0.78 at
# SmallThinker's piece (PERF.md §6 "PR 48")
ROW_TILE = 128
# what the kernel may hold in VMEM: what a Mosaic kernel has without
# asking.  A matrix that is no one tile within it stays on
# ``ragged_dot``: Command A+'s 32 MB, and Xing4.0's 7.3 MB, whose down
# product asks for 19.8 MiB — given 32 MiB its piece read 40 % under
# ``ragged_dot``'s time and the cell + 1.3 % tokens/s, but its ladder of
# nine programs took 63 s to load warm for the parent's 56, over that
# cell's bound on set-up (PERF.md §6 "PR 48", §7)
VMEM_BYTES = 16 << 20


def vmem_bytes(k: int, n: int, itemsize: int) -> int:
    """What a call holds in VMEM: the matrix, the row tile and the
    output tile in the pipeline's two slots each, and the float32
    product with its cast."""
    return (2 * k * n * itemsize + 2 * ROW_TILE * (k + n) * itemsize
            + 2 * ROW_TILE * n * 4)


def fits(R: int, k: int, n: int, itemsize: int) -> bool:
    """Whether the kernel takes ``[R, k] x [G, k, n]``: whole row tiles,
    whole lane tiles of depth and of columns, the matrix ONE tile and
    the call within the kernel's VMEM."""
    return (R % ROW_TILE == 0 and k % 128 == 0 and n % 128 == 0
            and vmem_bytes(k, n, itemsize) <= VMEM_BYTES)


def visits(group_sizes, rows: int):
    """``(offs [G + 1], group [V], row_tile [V], slot [V], fetch [V],
    count)`` of the grid: visit ``v < count`` multiplies row tile
    ``row_tile[v]`` by group ``group[v]``'s matrix, which lies in slot
    ``slot[v]`` of the two; ``fetch[v]`` is -2 but at a group's FIRST
    visit, where it names the next group that has rows (-1: none), whose
    matrix is to be fetched into the other slot meanwhile.  ``V = rows
    // tile + G - 1`` bounds the count (every tile once, and once more
    for each group that starts inside one)."""
    tile = ROW_TILE
    sizes = group_sizes.astype(jnp.int32)
    G = sizes.shape[0]
    ids = jnp.arange(G, dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tile
    hit = sizes > 0
    spans = jnp.where(hit, (ends - 1) // tile - first + 1, 0)
    upto = jnp.cumsum(spans)                 # visits through group g
    v = jnp.arange(rows // tile + G - 1, dtype=jnp.int32)
    # the group of visit v: how many groups' visits all lie before it
    group = jnp.minimum(
        jnp.sum(upto[None, :] <= v[:, None], axis=1, dtype=jnp.int32), G - 1)
    nth = v - jnp.take(upto - spans, group)  # of its group's visits
    # the next group with rows after each (a comparison of G x G numbers)
    after = jnp.min(jnp.where(hit[None, :] & (ids[None, :] > ids[:, None]),
                              ids[None, :], G), axis=1)
    fetch = jnp.where(nth == 0,
                      jnp.take(jnp.where(after < G, after, -1), group), -2)
    slot = jnp.take((jnp.cumsum(hit) - 1) % 2, group).astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    row_tile = jnp.clip(jnp.take(first, group) + nth, 0, rows // tile - 1)
    return offs, group, row_tile, slot, fetch, upto[-1]


def _kernel(offs_ref, group_ref, tile_ref, slot_ref, fetch_ref, xs_ref,
            w_hbm, o_ref, held, sem):
    v = pl.program_id(0)
    g, s, nxt = group_ref[v], slot_ref[v], fetch_ref[v]

    def fetch(group, slot):
        return pltpu.make_async_copy(w_hbm.at[group], held.at[slot],
                                     sem.at[slot])

    @pl.when(v == 0)
    def _():
        fetch(g, s).start()

    @pl.when(nxt > -2)          # the group's first visit
    def _():
        fetch(g, s).wait()

        @pl.when(nxt >= 0)      # the other slot's group is done with
        def _():
            fetch(nxt, 1 - s).start(priority=1)

    y = jnp.dot(xs_ref[...], held[s], preferred_element_type=jnp.float32)
    at = tile_ref[v] * y.shape[0] + lax.broadcasted_iota(jnp.int32, y.shape,
                                                         0)
    mine = (at >= offs_ref[g]) & (at < offs_ref[g + 1])
    o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


# jitted by itself: a prompt pass unrolls hundreds of these calls (384 in
# SmallThinker's 32-row program) of TWO shapes, and a call of a jitted
# function is traced and lowered once a shape — 0.04 s a call otherwise,
# under the interpreter's lock while a ladder's buckets compile beside
# each other (PERF.md §6 "PR 48")
@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped_prefill(xs, w, group_sizes, *, interpret: bool = False):
    R, k = xs.shape
    n = w.shape[-1]
    tile = ROW_TILE
    if R % tile:
        raise ValueError(f"grouped_prefill: {R} rows are no whole tiles of "
                         f"{tile} rows")
    *lists, count = visits(group_sizes, R)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(count,),
            in_specs=[
                pl.BlockSpec((tile, k), lambda v, o, g, t, s, f: (t[v], 0)),
                pl.BlockSpec(memory_space=pl.ANY),      # fetched by hand
            ],
            out_specs=pl.BlockSpec((tile, n),
                                   lambda v, o, g, t, s, f: (t[v], 0)),
            scratch_shapes=[pltpu.VMEM((2, k, n), xs.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((R, n), xs.dtype),
        # a tile two groups share is written by both visits, in turn
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
        name="grouped_prefill",
    )(*lists, xs, w.astype(xs.dtype))


# what jax keeps of the Python stack in an operation's location
# (``jax_traceback_in_locations_limit``)
_LOCATION_FRAMES = 10


def _from_this_file(frames: int, fn, *args, **kwargs):
    """``fn(*args, **kwargs)``, called from ``frames`` frames of this
    line."""
    if frames:
        return _from_this_file(frames - 1, fn, *args, **kwargs)
    return fn(*args, **kwargs)


def grouped_prefill(xs, w, group_sizes, *, interpret: bool = False):
    """``xs [R, k]`` rows sorted by group, ``w [G, k, n]``,
    ``group_sizes [G]`` int32 -> ``[R, n]`` in ``xs``'s dtype: row ``r``
    of group ``g`` times ``w[g]``; rows past ``sum(group_sizes)``
    undefined.  ``R`` whole row tiles (``ROW_TILE``).

    The jitted function is traced once a shape A PROCESS, by whichever
    program calls it first, and a Mosaic kernel's module carries the
    Python stack each of its operations was traced under — the nearest
    ten frames, callers' included.  Buckets of one ladder are traced
    beside each other and share shapes, so which caller's stack went
    into every program's kernels, and with it the programs' compile-
    cache keys, would differ from run to run (Xing4.0's 64-, 128- and
    256-row programs compiled anew in every warm set-up, 82 s for the
    parent's 36: PERF.md §6 "PR 48").  The call is therefore made from
    ten frames of this file: no caller is within the stack's reach."""
    return _from_this_file(_LOCATION_FRAMES, _grouped_prefill, xs, w,
                           group_sizes, interpret=interpret)
