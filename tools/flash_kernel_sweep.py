"""The flash kernels alone, on the chip: ms a call and share of the
roofline for the forward and the backward kernels apart — the fused
backward where the key axis is one grid tile (``bwd_ms``), dKdV and dQ
where it is several or the checkout predates the fused kernel — over
grid tile x sub-tile, at the two shapes the benchmark's cells run —
[128, 1024, 64] forward + backward (gpt2m training) and [256, 2048,
128] forward (mistral prefill).  PERF.md §6 "PR 30" and "PR 39" hold
the tables this printed.

Several applications are chained in ONE dispatch (each round's outputs
feed the next round's inputs), and the kernels' seconds are read from
the device trace of that dispatch, so the per-dispatch constant that
spoiled the 2026-07 matrix (docs/PERF.md) is not in them; the host
clock over the whole dispatch stands beside them as a check.

    python tools/flash_kernel_sweep.py --parent .bench_parent

``--parent <checkout> ...`` also times those checkouts' kernels over
the same tiles (trees from PR 30 on: the sub-tile is an argument).
Needs a TPU; through the chip tool only.  Writes ``chiprun_out/flash_kernel_sweep.json``.
"""
import argparse
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax
import jax.numpy as jnp

from benchmark import counts, trace_reduce

SHAPES = {  # name: (batch*heads, T, head_dim, with backward)
    "train_128x1024x64": (128, 1024, 64, True),
    "prefill_256x2048x128": (256, 2048, 128, False),
}


def _load_parent(checkout):
    path = os.path.join(checkout, "bigdl_tpu", "ops", "flash_attention.py")
    spec = importlib.util.spec_from_file_location(
        "bigdl_tpu.ops._parent_flash_attention", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _chain(fwd, bwd, rounds, with_bwd):
    """``rounds`` applications in one program, each fed by the last."""
    def run(q, k, v, g):
        for _ in range(rounds):
            o, lse = fwd(q, k, v)
            if with_bwd:
                dq, dk, dv = bwd(q, k, v, o, lse, g)
                q = q + 1e-3 * dq
                k = k + 1e-3 * dk
                v = v + 1e-3 * dv
            else:
                q = q + 1e-3 * o
        return q, k, v
    return jax.jit(run)


def _kind(hlo_text):
    """fwd / bwd / dkv / dq by the custom call's result: the forward
    also returns the f32 row statistic, the fused backward three
    tensors, dKdV two, dQ one."""
    result = hlo_text.split("custom-call(")[0]
    if "f32[" in result:
        return "fwd"
    return {3: "bwd", 2: "dkv", 1: "dq"}[result.count("bf16[")]


def _kernel_seconds(trace_dir):
    planes = trace_reduce.load(trace_dir)
    chip = next(p for p in planes if trace_reduce._is_chip(p["name"]))
    out = {}
    for name, _, dur, _ in trace_reduce._line(chip, "XLA Ops"):
        if "tpu_custom_call" in name:
            out.setdefault(_kind(name), []).append(dur / 1e9)
    return out


def measure(label, fwd, bwd, shape_name, rounds, peak):
    bh, t, d, with_bwd = SHAPES[shape_name]
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(kk, (1, bh, t, d), jnp.bfloat16) * 0.5
                  for kk in key)
    fn = _chain(fwd, bwd, rounds, with_bwd)
    jax.block_until_ready(fn(q, k, v, g))          # compile + warm
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(q, k, v, g))
        walls.append(time.perf_counter() - t0)
    trace_dir = tempfile.mkdtemp(prefix="flash_sweep_")
    try:
        with jax.profiler.trace(trace_dir):
            jax.block_until_ready(fn(q, k, v, g))
        secs = _kernel_seconds(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    need = counts.flash_call(bh, t, d)
    row = {"shape": shape_name, "variant": label, "rounds": rounds,
           "dispatch_ms_per_round": 1e3 * min(walls) / rounds}
    for kind, xs in sorted(secs.items()):
        assert len(xs) == rounds, (label, kind, len(xs))
        row[kind + "_ms"] = 1e3 * statistics.median(xs)
    row["fwd_roofline_pct"] = (100 * need["fwd_flops"] / peak
                               / (row["fwd_ms"] / 1e3))
    if with_bwd:
        # one fused kernel, or dKdV and dQ
        bwd_ms = row.setdefault("bwd_ms", row.get("dkv_ms", 0.0)
                                + row.get("dq_ms", 0.0))
        row["bwd_roofline_pct"] = (100 * need["bwd_flops"] / peak
                                   / (bwd_ms / 1e3))
        row["all_roofline_pct"] = (
            100 * (need["fwd_flops"] + need["bwd_flops"]) / peak
            / ((row["fwd_ms"] + bwd_ms) / 1e3))
    print(json.dumps(row), flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", nargs="*", default=[])
    ap.add_argument("--shape", nargs="*", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--grid", type=int, nargs="*", default=[512, 1024])
    ap.add_argument("--sub", nargs="*", default=["256", "512", "1024"],
                    help="sub-tiles: N, or QxK for a non-square one")
    ap.add_argument("--out", default="chiprun_out/flash_kernel_sweep.json")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("flash_kernel_sweep: needs a TPU (found %s)" % dev.platform)
    with open(os.path.join(os.path.dirname(counts.__file__),
                           "peaks.json")) as f:
        peak = counts.peaks_for(dev.device_kind,
                                json.load(f))["bf16_flops_per_s"]
    subs = [tuple(int(x) for x in s.split("x")) if "x" in s else int(s)
            for s in args.sub]
    rows = []
    # the package's attribute of that name is the function
    here = importlib.import_module("bigdl_tpu.ops.flash_attention")
    trees = [(os.path.basename(os.path.normpath(c)) + " ", _load_parent(c))
             for c in args.parent] + [("", here)]
    for shape_name in args.shape:
        bh, t, d, _ = SHAPES[shape_name]
        scale = 1.0 / d ** 0.5
        # a sub-tile over the grid tile is fitted down to it: the same row
        variants = [(None, None)] + [
            (g_, s) for g_ in args.grid for s in subs
            if max(s if isinstance(s, tuple) else (s,)) <= g_]
        for prefix, mod in trees:
            for grid, sub in variants:
                label = prefix + ("chosen from the shape" if grid is None
                                  else "grid %d sub %s" % (grid, sub))
                rows.append(measure(
                    label,
                    lambda q, k, v: mod._flash_fwd(
                        q, k, v, True, scale, grid, grid, sub, False),
                    lambda q, k, v, o, lse, g: mod._flash_bwd(
                        q, k, v, o, lse, g, True, scale, grid, grid, sub,
                        False),
                    shape_name, args.rounds, peak))
    from bigdl_tpu.telemetry.tracer import default_tracer
    events = [s.to_dict() for s in default_tracer().spans()
              if s.name == "flash.schedule"]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "rows": rows,
                   "flash_schedule_events": events}, f, indent=1)


if __name__ == "__main__":
    main()
