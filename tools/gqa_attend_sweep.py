#!/usr/bin/env python3
"""The attend of a per-head K/V decode step, alone, on the chip: the
plain grouped-query einsums of ``ops.gqa_attend.gqa_attend_reference`` (two
reads of the whole cache) against the Pallas kernel of
``bigdl_tpu/ops/gqa_attend.py`` (one read of the written part), at the
shapes the serving cells run — ONE query token a row against ``[B, Hkv,
T, Dh]`` K and V leaves — and at three live lengths each.

    python tools/gqa_attend_sweep.py [--shapes lfm2,...] [--rows 0,4,8,16,32]
                                     [--arms einsum,kernel,einsum_rowmajor]

Chip only.  One timed call is a jitted loop of ``--calls`` attends, each
fed the one before it (the result has the query's shape), so nothing is
hoisted and no dispatch lies between them; the time is the host clock
around it, ended by ``block_until_ready``.  By default (``--write 1``)
an attend is the step's own: the one-position write of the new key and
value first, so that a leaf's layout — which the arm's operands decide,
and which decides what that write costs — is counted with the arm that
asks for it; ``--write 0`` times the attend against fixed leaves.  The
roofline share is the SINGLE read of the live positions, ``B * Hkv *
live * Dh * 2 * itemsize`` bytes at the chip's bandwidth
(``benchmark/peaks.json``), over that time.  ``--rows`` lists the batch
rows a kernel program owns (0: the op's own choice), ``--batch`` rows in
place of a shape's own.  The arm ``einsum_rowmajor`` (a step only) is
the einsums over leaves the compiler is TOLD to carry row-major
(``with_layout_constraint`` after the write): what the kernel's operand
form does to a head of 64 by itself, without the kernel.  PERF.md §6
"PR 41" has the tables that the leaf's layout at a head of 64 and the
two thresholds of ``attend_plan`` were chosen from.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name -> (B, H, Hkv, Dh, T cache, live lengths): the decode step of
# each cell with per-head K/V (PERF.md §4); ``pos`` is live - 1
SHAPES = {
    "lfm2": (256, 32, 8, 64, 384, (129, 256, 384)),
    "commandaplus": (128, 128, 8, 128, 256, (129, 192, 256)),
    "falconh1": (64, 20, 4, 128, 384, (257, 320, 384)),
    "mistral_b8": (8, 32, 8, 128, 256, (129, 192, 256)),
    "mistral_b16": (16, 32, 8, 128, 256, (129, 192, 256)),
    "mistral_prefill": (8, 32, 8, 128, 2176, (2049, 2112, 2176)),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--arms", default="einsum,kernel")
    ap.add_argument("--rows", default="0")
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--write", type=int, default=1)
    ap.add_argument("--batch", default="0",
                    help="rows in place of each shape's own (0), a list")
    ap.add_argument("--out", default="chiprun_out/gqa_attend_sweep.json")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental.layout import Layout, with_layout_constraint

    from benchmark import counts
    from bigdl_tpu.ops.gqa_attend import gqa_attend_reference as _gqa_attend

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peaks = counts.peaks_for(dev.device_kind, json.load(fh))
    dt = jnp.bfloat16
    arms = args.arms.split(",")

    def looped(attend, G, rowmajor=False):
        """``--calls`` attends in one program, each on the last's result.
        With ``--write 1`` each is a step's own: the one-position write
        of a new key and value into the carried leaves, then the attend
        — the leaves' layout in the loop is the compiler's choice under
        the arm's demands, as it is in the generate program, or
        (``rowmajor``) row-major because it is told so."""
        def run(q, k, v, pos):
            if not args.write:
                return lax.fori_loop(0, args.calls,
                                     lambda _, q: attend(q, k, v, pos), q)

            def step(_, carry):
                q, k, v = carry
                new = q[:, ::G]                         # [B, Hkv, 1, Dh]
                k = lax.dynamic_update_slice(k, new, (0, 0, pos, 0))
                v = lax.dynamic_update_slice(v, -new, (0, 0, pos, 0))
                if rowmajor:
                    k, v = (with_layout_constraint(
                        a, Layout(major_to_minor=(0, 1, 2, 3)))
                        for a in (k, v))
                return attend(q, k, v, pos), k, v
            return lax.fori_loop(0, args.calls, step, (q, k, v))[0]
        return jax.jit(run)

    out = []
    for name, batch in ((n, int(b)) for n in args.shapes.split(",")
                        for b in args.batch.split(",")):
        B, H, Hkv, Dh, T, lives = SHAPES[name]
        B = batch or B
        ks = jax.random.split(jax.random.PRNGKey(B + T), 3)
        q = jax.random.normal(ks[0], (B, H, 1, Dh), dt)
        k = jax.random.normal(ks[1], (B, Hkv, T, Dh), dt)
        v = jax.random.normal(ks[2], (B, Hkv, T, Dh), dt)
        todo = []
        for impl in ("einsum", "einsum_rowmajor"):
            if impl in arms and (impl == "einsum" or args.write):
                todo.append((impl, 0, 0, lambda q, k, v, pos: _gqa_attend(
                    q, k, v, pos, H, Hkv, Dh)))
        if "kernel" in arms:
            from bigdl_tpu.ops import gqa_attend as A

            for rows in (int(r) for r in args.rows.split(",")):
                if rows and B % rows:
                    continue
                todo.append(("kernel", A.BLOCK_POSITIONS, rows,
                             lambda q, k, v, pos, rows=rows:
                             A._gqa_attend_kernel(
                                 q[:, :, 0], k, v, pos, A.BLOCK_POSITIONS,
                                 False, rows or None)[:, :, None]))
        for impl, block, rows, attend in todo:
            fn = looped(attend, H // Hkv, impl == "einsum_rowmajor")
            for live in lives:
                pos = jnp.int32(live - 1)
                nbytes = B * Hkv * live * Dh * 2 * jnp.dtype(dt).itemsize
                least = nbytes / peaks["hbm_bytes_per_s"]
                row = {"shape": name, "B": B, "H": H, "Hkv": Hkv, "Dh": Dh,
                       "T": T, "live": live, "impl": impl, "block": block,
                       "rows_a_program": rows, "write": args.write,
                       "kv_bytes": 2 * B * Hkv * T * Dh * 2}
                try:
                    jax.block_until_ready(fn(q, k, v, pos))
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(q, k, v, pos))
                    ms = 1e3 * (time.perf_counter() - t0) / args.calls
                    row.update(ms_per_call=ms,
                               roofline_pct=100.0 * least / (ms / 1e3))
                except Exception as e:  # noqa: BLE001 — a sweep reports
                    row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                out.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
