#!/usr/bin/env python3
"""The hyper-connections of a decode step alone — chip only: ten
sublayers' coefficients, Sinkhorn sweeps and both mixes (the sublayer
itself a stand-in: its input handed back) inside a ``lax.scan`` of
steps, with the sweeps in ONE kernel a sublayer (``ops/sinkhorn.py``,
the arm the program runs) against the plain form written out
(``sinkhorn_reference``) and ROLLED (a ``lax.fori_loop`` of one sweep,
written here: the program has no such arm), at the rows of the serving
buckets.  Prints, an arm and row count, the
seconds to compile and the microseconds a step (host clock around the
whole scan over its steps; a step here holds nothing else, so the
device's gaps are the arm's own).  PERF.md section 6 "PR 42".

    python tools/hyper_connection_sweep.py --rows 256,16 --steps 200
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\\n\\n")[0])
    ap.add_argument("--rows", default="256")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--embed", type=int, default=3584)
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--sublayers", type=int, default=10)
    ap.add_argument("--sweeps", type=int, default=20)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.nn import hyper_connection
    from bigdl_tpu.ops.sinkhorn import sinkhorn_map, sinkhorn_reference

    if jax.default_backend() != "tpu":
        print("hyper_connection_sweep: needs a TPU", file=sys.stderr)
        return 2
    def rolled_form(x, iters, eps, lo, hi):
        def sweep(_, m):
            m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
            return m / (jnp.sum(m, axis=0, keepdims=True) + eps)

        return jax.lax.fori_loop(0, iters, sweep,
                                 jnp.exp(jnp.clip(x, lo, hi)))

    arms = {"kernel": sinkhorn_map, "rolled": rolled_form,
            "written_out": sinkhorn_reference}
    out = []
    for rows in [int(r) for r in args.rows.split(",")]:
        x0 = jax.random.normal(jax.random.PRNGKey(0), (
            rows, 1, args.streams, args.embed), jnp.bfloat16)
        for arm, form in arms.items():
            # the arm: the program's own map, or a plain form in its
            # place (the functions are jitted a module's numbers, and
            # the arm is not one of them: the caches go)
            hyper_connection.sinkhorn_map = form
            jax.clear_caches()
            hcs = [nn.HyperConnection(args.embed, args.streams, args.sweeps)
                   for _ in range(args.sublayers)]
            params = [jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16) if a.ndim == 2
                and a.shape[0] > args.streams else a,
                {**hc.param_tree(), "alpha_pre": jnp.float32(1),
                 "alpha_post": jnp.float32(1)}) for hc in hcs]

            def step(x, _):
                err = jnp.float32(0)
                for hc, p in zip(hcs, params):
                    co = hc.coefficients(p, x)
                    u = hc.pre(co, x)
                    x = hc.post(co, x, u * 0.1)
                    err = jnp.maximum(err, co.err)
                return x, err

            run = jax.jit(lambda x: jax.lax.scan(step, x, None,
                                                 length=args.steps))
            t0 = time.perf_counter()
            compiled = run.lower(x0).compile()
            t_compile = time.perf_counter() - t0
            jax.block_until_ready(compiled(x0))
            t0 = time.perf_counter()
            for _ in range(3):
                jax.block_until_ready(compiled(x0))
            us = (time.perf_counter() - t0) / 3 / args.steps * 1e6
            row = {"rows": rows, "arm": arm,
                   "compile_s": round(t_compile, 2),
                   "us_per_step": round(us, 1),
                   "us_per_sublayer": round(us / args.sublayers, 2)}
            print(json.dumps(row), flush=True)
            out.append(row)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "hyper_connection_sweep.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
