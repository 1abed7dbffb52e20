#!/usr/bin/env python3
"""The absorbed attend of a latent decode step, alone, on the chip: the
plain einsums (two reads of the whole cache) against the Pallas kernel
of ``bigdl_tpu/ops/latent_attend.py`` (one read of the written part), at
the widths of ``glm47flash_serve_decode_sat`` — 20 heads, a latent of
512 and a shared rotated key of 64, a cache of 640 positions — for every
bucket of the server's ladder (1 ... 256 rows) and a live length of 129,
384 and 640 positions.

    python tools/latent_attend_sweep.py [--rows 1,2,...] [--variants]

Chip only.  One timed call is a jitted loop of ``--calls`` attends, each
fed the one before it (``o_lat`` has ``q_lat``'s shape), so nothing is
hoisted and no dispatch lies between them; the time is the host clock
around it, ended by ``block_until_ready``.  The roofline share is
``counts_glm4_moe_lite.attend_call`` — the LIVE positions once, the
query in and the result out, at whichever peak binds — over that time:
the reading ``mla_decode_attend_roofline`` takes from a trace.
``--variants`` adds, for 32 and 256 rows, the kernel at other rows a
program and with the whole cache as one block (nothing skipped).
PERF.md §6 "PR 37" has the table that ``KERNEL_MIN_CACHE_BYTES`` and
``BLOCK_POSITIONS`` were chosen from.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

HEADS, RANK, ROPE, CACHE = 20, 512, 64, 640
QK_DIM = 192 + ROPE


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="1,2,4,8,16,32,64,128,256")
    ap.add_argument("--live", default="129,384,640")
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--variants", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax import lax

    from benchmark import counts, counts_glm4_moe_lite
    from bigdl_tpu.ops import latent_attend as L

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peaks = counts.peaks_for(dev.device_kind, json.load(fh))
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-4.7-flash-l5e16.json")) as fh:
        cfg = json.load(fh)
    dt = jnp.bfloat16

    def looped(attend):
        """``--calls`` attends in one program, each on the last's result."""
        def run(q_lat, q_rope, ckv, kr, pos):
            return lax.fori_loop(
                0, args.calls,
                lambda _, q: attend(q, q_rope, ckv, kr, pos), q_lat)
        return jax.jit(run)

    def einsum(q, q_rope, ckv, kr, pos):
        return L.latent_attend_reference(q[:, :, None], q_rope[:, :, None],
                                         ckv, kr, pos, QK_DIM)[:, :, 0]

    def kernel(block, rows=None):
        return lambda q, q_rope, ckv, kr, pos: L._latent_attend_kernel(
            q, q_rope, ckv, kr, pos, QK_DIM, block, False, rows)

    out = []
    for B in (int(b) for b in args.rows.split(",")):
        ks = jax.random.split(jax.random.PRNGKey(B), 4)
        q_lat = jax.random.normal(ks[0], (B, HEADS, RANK), dt)
        q_rope = jax.random.normal(ks[1], (B, HEADS, ROPE), dt)
        ckv = jax.random.normal(ks[2], (B, CACHE, RANK), dt)
        kr = jax.random.normal(ks[3], (B, ROPE, CACHE), dt)
        arms = [("einsum", 0, 0, einsum),
                ("kernel", L.BLOCK_POSITIONS, 0,
                 kernel(L.BLOCK_POSITIONS))]
        if args.variants and B in (32, 256):
            arms += [("kernel", block, rows, kernel(block, rows))
                     for block in (128, 640)      # a block divides the cache
                     for rows in (4, 8, 16, 32)
                     if rows * block <= 32 * 128 and B % rows == 0]
        for impl, block, rows, attend in arms:
            fn = looped(attend)
            for live in (int(n) for n in args.live.split(",")):
                pos = jnp.int32(live - 1)
                call = counts_glm4_moe_lite.attend_call(cfg, B, live)
                least, binds = counts.roofline_seconds(
                    call["flops"], call["bytes"], peaks)
                row = {"rows": B, "live": live, "impl": impl,
                       "block": block, "rows_a_program": rows}
                try:
                    jax.block_until_ready(fn(q_lat, q_rope, ckv, kr, pos))
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(q_lat, q_rope, ckv, kr, pos))
                    ms = 1e3 * (time.perf_counter() - t0) / args.calls
                    row.update(ms_per_call=ms, binds=binds,
                               roofline_pct=100.0 * least / (ms / 1e3))
                except Exception as e:  # noqa: BLE001 — a sweep reports
                    row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                out.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/latent_attend_sweep.json", "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
