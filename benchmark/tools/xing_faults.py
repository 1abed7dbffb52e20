#!/usr/bin/env python3
"""A cell of the hyper-connected block run with ONE fault in the
program's maps, through the harness's own comparison: each has to end
``correct: false``, or the cell's seeding and limits do not see the
mechanism the cell names.

    python3 benchmark/tools/xing_faults.py <fault> --workload \\
        xing4_serve_decode_sat --seed <n> --seconds 10 --trace 0

``gates_zero``: the three gates 0, so the maps are the same for every
token (``sigmoid(b)``, the Sinkhorn of ``exp(b_res)``).  ``one_sweep``:
one Sinkhorn sweep for the configured count.  ``transposed``: ``H_res``
takes stream ``i`` to stream ``j`` for ``j`` to ``i``.  The program's
own code is patched in this process only (``nn.hyper_connection``'s
one coefficient function); the reference is the cell's.  Never part of a
measured run.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

FAULTS = ("gates_zero", "one_sweep", "transposed")


def install(fault: str):
    """Put ``fault`` into ``nn.hyper_connection._coefficients``; returns
    the function it replaced (a test puts it back)."""
    import jax.numpy as jnp

    from bigdl_tpu.nn import hyper_connection as hc

    real = hc._coefficients

    def gates_zero(spec, params, x):
        return real(spec, {**params, **{
            k: jnp.zeros_like(params[k])
            for k in ("alpha_pre", "alpha_post", "alpha_res")}}, x)

    def one_sweep(spec, params, x):
        return real(spec._replace(iters=1), params, x)

    def transposed(spec, params, x):
        co = real(spec, params, x)
        return co._replace(res=jnp.swapaxes(co.res, 0, 1))

    hc._coefficients = {"gates_zero": gates_zero, "one_sweep": one_sweep,
                        "transposed": transposed}[fault]
    return real


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in FAULTS:
        print(f"usage: xing_faults.py {{{'|'.join(FAULTS)}}} "
              "[benchmark/run.py's arguments]", file=sys.stderr)
        return 2
    install(argv[0])
    from benchmark import run

    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main())
