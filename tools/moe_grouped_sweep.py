#!/usr/bin/env python3
"""The grouped matrix product of the dropless expert layer, alone, on
the chip: ``jax.lax.ragged_dot`` (the compiler's grouped kernel, rows in
tiles of 512) against the Pallas grouped matmul that ships with jax
(megablox ``gmm``, rows in tiles of 128), at the two shapes the
Command A+ cell dispatches — a decode step's buffer (1024 rows of which
128 are real, 16 held experts) and one piece of a prefill (32768 rows
of which 4096 are real).

    python tools/moe_grouped_sweep.py [--d 4096 --f 4096 --held 16]

Chip only.  Times are host-clock means over a loop of calls that ends in
``block_until_ready`` (the calls queue back to back, so the mean is the
device time of one call plus its dispatch); the roofline share is the
real rows' operations and the hit experts' bytes at whichever peak
binds, over that time.  PERF.md §6 "PR 32" has the readings.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--d", type=int, default=4096)
    ap.add_argument("--f", type=int, default=4096)
    ap.add_argument("--held", type=int, default=16)
    ap.add_argument("--calls", type=int, default=30)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from benchmark import counts
    from bigdl_tpu.parallel.moe import grouped_matmul

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "peaks.json")) as fh:
        peaks = counts.peaks_for(dev.device_kind, json.load(fh))
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (args.held, args.d, args.f), jnp.bfloat16)
    rows_out = []
    for rows, real in ((1024, 128), (1024, 1024), (32768, 4096),
                       (32768, 32768)):
        x = jax.random.normal(key, (rows, args.d), jnp.bfloat16)
        sizes = jnp.full((args.held,), real // args.held, jnp.int32)
        flops = 2.0 * real * args.d * args.f
        nbytes = 2.0 * (args.held * args.d * args.f
                        + real * (args.d + args.f))
        least, binds = counts.roofline_seconds(flops, nbytes, peaks)
        for impl in ("ragged", "gmm"):
            fn = jax.jit(lambda x, w, s, impl=impl:
                         grouped_matmul(x, w, s, impl))
            try:
                jax.block_until_ready(fn(x, w, sizes))
                t0 = time.perf_counter()
                for _ in range(args.calls):     # queued back to back;
                    out = fn(x, w, sizes)       # one result alive
                jax.block_until_ready(out)
                ms = 1e3 * (time.perf_counter() - t0) / args.calls
                row = {"rows": rows, "real_rows": real, "impl": impl,
                       "ms_per_call": ms, "binds": binds,
                       "roofline_pct": 100.0 * least / (ms / 1e3)}
            except Exception as e:  # noqa: BLE001 — a sweep reports
                row = {"rows": rows, "real_rows": real, "impl": impl,
                       "error": f"{type(e).__name__}: {str(e)[:200]}"}
            rows_out.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_grouped_sweep.json", "w") as fh:
        json.dump(rows_out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
