#!/usr/bin/env python3
"""The grouped matrix products of the dropless expert layer, alone, on
the chip, as a decode step issues them: ``--calls`` gated experts
(``gate`` and ``up`` ``[R, d] x [G, d, f]``, ``down`` ``[R, f] x [G, f,
d]``) back to back inside ONE jitted loop, each fed the one before it,
at the shapes the five serving cells with experts dispatch — and with
group sizes drawn as those cells route: UNEVEN (a multinomial over
popularities from a Dirichlet, the busiest group at 2-3 x the mean, as
``moe_load_max_over_mean`` reads), so that groups straddle the 128-row
tiles of megablox's ``gmm``.  (PR 32's sweep gave every group ``real //
held`` rows: every group started on a tile boundary, none straddled.)

    python tools/moe_grouped_sweep.py
        [--shapes lfm2,xing4,glm,commandaplus,smallthinker,
                  smallthinker_prefill,lfm2_prefill,glm_prefill,
                  xing4_prefill,commandaplus_prefill]
        [--arms gmm,decode,prefill,ragged] [--chunk 128,64]
        [--tiling today plan 128,k,today 64,k,plan]
        [--d .. --f .. --held .. --rows .. --real ..]

Arms: ``gmm`` (the Pallas grouped matmul that ships with jax) under each
``--tiling`` — ``today`` is what PR 32 shipped (``tm`` 128, ``tk`` and
``tn`` 1024 where that divides, else 512), ``plan`` ISSUE 43's Arm A
(one k tile and the widest column tile within 4 MB that divides ``n``:
``[2048, 768]``, ``[3584, 512]``, ...), ``tm,tk,tn`` explicit (``tk`` a
number or ``k`` for the whole depth, ``tn`` a number, ``n`` for the
whole width, ``today`` or ``plan``); ``decode`` (the repo's own
``ops/grouped_decode.py``: one grid step a group, its whole matrix one
tile) under each ``--chunk`` (rows a product); ``prefill`` (the repo's
own ``ops/grouped_prefill.py``: ``gmm``'s grid with ONE k tile, for a
piece of a prompt pass; its row tile is the module's, 128: 256 read
slower at every shape, PERF.md §6 "PR 48");
``ragged`` (``jax.lax.ragged_dot``).  An arm is skipped at a shape its
tiles do not divide, ``decode`` and ``prefill`` where the kernel does
not take the product (``grouped_decode.fits``: a matrix over its 8 MB,
rows that are no whole chunks, a buffer over its VMEM — a prompt piece;
``grouped_prefill.fits``: a matrix that is no one tile within its VMEM).
``--d`` ... give one shape of your own in place of ``--shapes``;
``--even`` deals every group ``real // held`` rows in place of a draw
(with whole row tiles a group, no group straddles a tile).

Chip only.  A row of the table is one product (``gate``: the gate and
up calls; ``down``) of one arm at one shape:

* ``ms_per_call`` (``ms_min``, ``ms_max``): from the DEVICE TRACE of the
  loop, the custom call's own events in the order a layer runs them
  (left out, ``calls_timed`` 0, where the trace holds another number of
  them than three a layer: ``calls_seen``);
* the kernel's grid, counted on the host from the sizes: ``visits``
  ((row tile, group) pairs of ``gmm``; groups hit), ``grid_steps``,
  ``products`` (MXU passes of a weight tile) and, for ``gmm``,
  ``read_over_needed`` — weight bytes the grid's block indices fetch
  over the hit experts' bytes: a straddling group's second visit
  re-fetches unless the block is the one held;
* ``bytes_once_pct``: the hit experts' weights and the real rows in and
  out ONCE at the chip's bandwidth (``benchmark/peaks.json``), over
  ``ms_per_call``; ``peak_flops_pct``: the real rows' operations at the
  chip's bf16 peak over the same (what bounds a prompt piece);
* ``loop_ms_per_layer``: the host clock around the whole loop over its
  layers — the three products with the sizes' bookkeeping and the gate
  between them, as the program pays them — and
  ``other_ops_ms_per_layer``, the traced self time of everything in the
  loop that is no product (``gmm`` builds its visit lists from the sizes
  with a dozen small operations a call);
* ``gate_err_vs_ragged``: the arm's largest difference from
  ``ragged_dot`` on the defined rows of one gate product (values of
  order 1).

PERF.md §6 "PR 43", "PR 47" and "PR 48" have the readings (§6 "PR 32"
the first sweep's).
"""
import argparse
import collections
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name -> (d, f, held, rows of the buffer, real rows): the decode step
# of a full bucket in each cell with experts (PERF.md §4)
SHAPES = {
    "lfm2": (2048, 1536, 64, 1024, 1024),
    "xing4": (3584, 1024, 32, 1024, 512),
    "glm": (2048, 1536, 16, 1024, 256),
    "commandaplus": (4096, 4096, 16, 1024, 128),
    # 32 rows x 6 choices: no whole 128-row chunks, products of 64
    "smallthinker": (2560, 768, 64, 192, 192),
    # one piece of a cell's prompt pass (SmallThinker's: 4608 tokens x 6
    # choices; the others': 8192 tokens x 4, Command A+'s 4096 x 8), the
    # real rows the share of the choices that name a held expert: over
    # the plan's 2048 rows, so ``decode`` skips them
    "smallthinker_prefill": (2560, 768, 64, 27648, 27648),
    "lfm2_prefill": (2048, 1536, 64, 32768, 32768),
    "glm_prefill": (2048, 1536, 16, 32768, 8192),
    "xing4_prefill": (3584, 1024, 32, 32768, 16384),
    "commandaplus_prefill": (4096, 4096, 16, 32768, 4096),
}


def draw_sizes(held: int, real: int, seed: int, even: bool = False):
    """Rows a group as a router deals them: popularities from a
    Dirichlet(4) (a coefficient of variation of one half), ``real``
    assignments drawn over them; ``even``: ``real // held`` each (with
    whole row tiles a group no group straddles one)."""
    import numpy as np

    if even:
        return np.full((held,), real // held, "int32")
    rng = np.random.default_rng(seed)
    return rng.multinomial(real, rng.dirichlet([4.0] * held)).astype("int32")


def gmm_grid(sizes, tiles, k: int, n: int):
    """``gmm``'s grid ``(n tiles, visits, k tiles)`` for these sizes: a
    visit is a (row tile, group) pair; a step copies its weight tile
    unless it is the block the step before it held."""
    tm, tk, tn = tiles
    visits, at = [], 0
    for g, s in enumerate(int(s) for s in sizes):
        if s:
            visits += [g] * (-(-(at + s) // tm) - at // tm)
        at += s
    tiles_k, tiles_n = k // tk, n // tn
    fetched = len(visits) * tiles_k if tiles_k > 1 else len(set(visits))
    return {"visits": len(visits),
            "grid_steps": tiles_n * len(visits) * tiles_k,
            "read_over_needed": fetched / (len(set(visits)) * tiles_k)}


def decode_grid(sizes, chunk: int, align: int = 16):
    """``grouped_decode``'s grid ``(groups that have rows,)``: a visit
    and a grid step each, a product one chunk of a group's rows from
    its offset rounded down to ``align``."""
    products, hit, at = 0, 0, 0
    for s in (int(s) for s in sizes):
        if s:
            hit += 1
            products += -(-(at + s - at // align * align) // chunk)
        at += s
    return {"visits": hit, "grid_steps": hit, "products": products}


def call_seconds(trace_dir, trace_reduce):
    """([seconds of each Mosaic / ragged call, in the order they ran],
    self seconds of every OTHER operation inside the loop, {name of such
    an operation: its self seconds}) of chip 0."""
    planes = trace_reduce.load(trace_dir)
    chip = next(p for p in planes if trace_reduce._is_chip(p["name"]))
    calls, other, names = [], 0.0, collections.Counter()
    for ev, self_ns, in_loop in trace_reduce.self_times(
            trace_reduce._line(chip, "XLA Ops")):
        # on the v5e a ``ragged_dot`` is a custom call the compiler names
        # ``ragged-dot-none.N`` (beside a ``ragged-dot-metadata`` of 3 us)
        # (the metadata call is a Mosaic custom call too: PR 48's first
        # sweep counted four calls a layer)
        head = ev[0].split(" = ")[0]
        if "metadata" not in head and ("tpu_custom_call" in ev[0]
                                       or "ragged-dot" in head):
            calls.append((ev[1], ev[2] / 1e9))
        elif in_loop:
            other += self_ns / 1e9
            names[head.lstrip("%").rstrip(".0123456789")] += self_ns / 1e9
    return [sec for _, sec in sorted(calls)], other, names


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(
        s for s in SHAPES if not s.endswith("_prefill")))
    for dim in ("d", "f", "held", "rows", "real"):
        ap.add_argument("--" + dim, type=int, default=0)
    ap.add_argument("--arms", default="gmm,decode")
    ap.add_argument("--tiling", nargs="*", default=["today", "plan"],
                    help="today | plan | tm,tk,tn with tk a number or k, "
                    "tn a number, today or plan (gmm)")
    ap.add_argument("--chunk", default="128", help="rows a product (decode)")
    ap.add_argument("--calls", type=int, default=24,
                    help="expert layers in one timed loop")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--even", action="store_true",
                    help="real // held rows a group in place of a draw")
    ap.add_argument("--out", default="chiprun_out/moe_grouped_sweep.json")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from benchmark import counts, trace_reduce
    from bigdl_tpu.ops import grouped_decode as GD
    from bigdl_tpu.ops import grouped_prefill as GP
    from bigdl_tpu.parallel.moe import _tiles_of

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peaks = counts.peaks_for(dev.device_kind, json.load(fh))
    dt = jnp.bfloat16
    shapes = ({"custom": (args.d, args.f, args.held, args.rows, args.real)}
              if args.d else {s: SHAPES[s] for s in args.shapes.split(",")})
    def variants(k, n, rows):
        """[(label, tiles, product fn, grid fn)] for [rows, k] x
        [G, k, n]; the product False for an arm that does not take it."""
        tn_plan = min(n, (4 << 20) // (2 * k)) // 128 * 128
        while n % tn_plan:
            tn_plan -= 128
        out = []
        for arm in args.arms.split(","):
            if arm == "ragged":
                out.append(("ragged", None, lax.ragged_dot, None))
            for t in args.tiling if arm == "gmm" else ():
                today = _tiles_of("gmm", rows, k, n)
                named = {"k": k, "n": n, "today": today[2], "plan": tn_plan}
                tiles = (today if t == "today" else
                         (today[0], k, tn_plan) if t == "plan" else
                         tuple(named.get(w) or int(w) for w in t.split(",")))
                ok = not (k % tiles[1] or n % tiles[2])
                out.append((
                    f"gmm {t}", tiles,
                    ok and (lambda x, w, s, tiles=tiles: gmm(
                        x, w, s, preferred_element_type=dt, tiling=tiles)),
                    lambda sizes, tiles=tiles: gmm_grid(sizes, tiles, k, n)))
            if arm == "prefill":
                tiles = (GP.ROW_TILE, k, n)
                out.append((
                    "prefill", tiles,
                    GP.fits(rows, k, n, 2) and GP.grouped_prefill,
                    lambda sizes, tiles=tiles: gmm_grid(sizes, tiles, k, n)))
            for c in (int(c) for c in args.chunk.split(",")
                      if arm == "decode"):
                out.append((
                    f"decode chunk {c}", (c, k, n),
                    GD.fits(rows, k, n, 2, c) and (
                        lambda x, w, s, c=c: GD.grouped_decode(
                            x, w, s, chunk=c)),
                    lambda sizes, c=c: decode_grid(sizes, c)))
        return out

    rows_out = []
    for name, (d, f, held, rows, real) in shapes.items():
        sizes_np = draw_sizes(held, real, args.seed, args.even)
        sizes = jnp.asarray(sizes_np)
        hit = int((sizes_np > 0).sum())
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        x = jax.random.normal(ks[0], (rows, d), dt)
        wg, wu = (jax.random.normal(kk, (held, d, f), dt) / d ** 0.5
                  for kk in ks[1:3])
        wd = jax.random.normal(ks[3], (held, f, d), dt) / f ** 0.5
        for (label, tg, up, grid_g), (_, td, down, grid_d) in zip(
                variants(d, f, rows), variants(f, d, rows)):
            head = {"shape": name, "d": d, "f": f, "held": held,
                    "rows": rows, "real_rows": real, "groups_hit": hit,
                    "max_over_mean": float(sizes_np.max() * held / real),
                    "arm": label}
            if not (up and down):
                rows_out.append(dict(
                    head, skipped="the tiles do not divide the products, "
                    "or the matrix is no one tile"))
                print(json.dumps(rows_out[-1]), flush=True)
                continue

            @jax.jit
            def loop(x, wg, wu, wd, sizes, up=up, down=down):
                def layer(i, x):
                    # the sizes are the step's own: nothing of their
                    # bookkeeping is hoisted out of the loop
                    s = sizes + (i < 0).astype(jnp.int32)
                    h = jax.nn.silu(up(x, wg, s)) * up(x, wu, s)
                    return down(h, wd, s)
                return lax.fori_loop(0, args.calls, layer, x)

            trace_dir = tempfile.mkdtemp(prefix="moe_sweep_")
            try:
                # the arm's gate product against the compiler's own, on
                # the rows that are defined
                head["gate_err_vs_ragged"] = float(jnp.max(jnp.abs(
                    (up(x, wg, sizes) - lax.ragged_dot(x, wg, sizes))
                    [:real].astype(jnp.float32))))
                jax.block_until_ready(loop(x, wg, wu, wd, sizes))
                walls = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    jax.block_until_ready(loop(x, wg, wu, wd, sizes))
                    walls.append(time.perf_counter() - t0)
                with jax.profiler.trace(trace_dir):
                    jax.block_until_ready(loop(x, wg, wu, wd, sizes))
                secs, other, names = call_seconds(trace_dir, trace_reduce)
            except Exception as e:  # noqa: BLE001 — a sweep reports
                rows_out.append(dict(
                    head, error=f"{type(e).__name__}: {str(e)[:300]}"))
                print(json.dumps(rows_out[-1]), flush=True)
                continue
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
            loop_ms = 1e3 * min(walls) / args.calls
            # a layer runs gate, up, down in that order
            whole = len(secs) == 3 * args.calls
            for product, k, n, tiles, grid, calls in (
                    ("gate", d, f, tg, grid_g,
                     [c for i, c in enumerate(secs) if i % 3 < 2]),
                    ("down", f, d, td, grid_d, secs[2::3])):
                row = dict(head, product=product, k=k, n=n, tiles=tiles,
                           loop_ms_per_layer=loop_ms,
                           other_ops_ms_per_layer=1e3 * other / args.calls,
                           calls_timed=len(calls) if whole else 0,
                           calls_seen=len(secs))
                if not whole:   # what the loop held instead
                    row["loop_ops_ms"] = {
                        nm: 1e3 * sec for nm, sec in names.most_common(6)}
                if whole:
                    ms = 1e3 * statistics.median(calls)
                    once = 2.0 * (hit * k * n + real * (k + n))
                    row.update(
                        ms_per_call=ms, ms_min=1e3 * min(calls),
                        ms_max=1e3 * max(calls), bytes_once_pct=100.0 * once
                        / peaks["hbm_bytes_per_s"] / (ms / 1e3),
                        peak_flops_pct=100.0 * 2.0 * real * k * n
                        / peaks["bf16_flops_per_s"] / (ms / 1e3))
                if grid:
                    row.update(grid(sizes_np))
                    if whole:
                        row["us_per_grid_step"] = 1e3 * ms / row["grid_steps"]
                rows_out.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump({"device": dev.device_kind, "seed": args.seed,
                   "rows": rows_out}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
