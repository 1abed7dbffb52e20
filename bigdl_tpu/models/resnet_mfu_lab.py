"""ResNet-50 conv-MFU lab (VERDICT r3 #1) — run on the TPU when up.

Three experiments, each one JSON line to stdout (and appended to
``MFU_LAB.jsonl`` in the repo root when writable):

  python -m bigdl_tpu.models.resnet_mfu_lab --twin [--impl xla|gemm]
      Independent plain-JAX NHWC ResNet-50 train step
      (models/resnet_jax_twin.py) — proves whether the framework's 13.7%
      is XLA's conv ceiling or this framework's graph/layouts.

  python -m bigdl_tpu.models.resnet_mfu_lab --convshapes
      Every distinct ResNet-50 conv shape microbenched fwd+bwd:
      XLA native lowering vs the k²-matmul lowering (ops/conv_gemm),
      TFLOP/s side by side.

  python -m bigdl_tpu.models.resnet_mfu_lab --framework --impl gemm
      The framework's own ResNet50 (NCHW) end-to-end with the chosen
      conv lowering, via bench.py's bench_model timing contract.

Timing: host clock around a run that ends in a scalar value fetch,
which waits for the device (bench.py's contract).
"""
from __future__ import annotations

import argparse
import json
import os
import time

# analytic FALLBACK only (rows carry mfu_basis when used): 4.09 GMACs
# x 2 flops/MAC — the r6 basis correction; the r1-r5 rows in
# MFU_LAB.jsonl divided MACs by an FMA=2 peak and read ~2x low
RESNET50_FWD_FLOPS_PER_IMAGE = 2 * 4.09e9


def _bench_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _step_cost(jitted, *args):
    """Per-step XLA cost-model flops of a jitted step (lowering only —
    no compile, no execution); None when analysis fails."""
    try:
        from ..telemetry.perf import cost_from_analysis

        cost = cost_from_analysis(
            jitted.lower(*args).cost_analysis())
        return cost if cost.flops > 0 else None
    except Exception:
        return None

# distinct (cin, cout, k, stride, spatial_in) conv shapes of ResNet-50
# at 224² with their per-image multiplicity
RESNET50_CONV_SHAPES = [
    (3, 64, 7, 2, 224, 1),
    (64, 64, 1, 1, 56, 1), (64, 64, 3, 1, 56, 3), (64, 256, 1, 1, 56, 3),
    (256, 64, 1, 1, 56, 2), (256, 128, 1, 2, 56, 1),
    (128, 128, 3, 1, 28, 4), (128, 512, 1, 1, 28, 4),
    (512, 128, 1, 1, 28, 3), (256, 512, 1, 2, 56, 1),
    (512, 256, 1, 2, 28, 1), (256, 256, 3, 1, 14, 6),
    (256, 1024, 1, 1, 14, 6), (1024, 256, 1, 1, 14, 5),
    (512, 1024, 1, 2, 28, 1), (1024, 512, 1, 2, 14, 1),
    (512, 512, 3, 1, 7, 3), (512, 2048, 1, 1, 7, 3),
    (2048, 512, 1, 1, 7, 2), (1024, 2048, 1, 2, 14, 1),
]


def _peak():
    import jax

    # the ONE peak table (telemetry/device_info.py; bench.py consumes
    # the same rows through its compat shim)
    from ..telemetry.device_info import peak_flops_per_sec

    kind = getattr(jax.devices()[0], "device_kind", "") or ""
    return peak_flops_per_sec(kind)


def _device_str():
    import jax
    return str(jax.devices()[0])


def _emit(rec):
    line = json.dumps(rec)
    print(line, flush=True)
    try:
        import jax

        # the persisted artifact carries ON-CHIP rows only — a CPU
        # smoke run must not append junk to the judged JSONL
        if jax.default_backend() == "cpu":
            return
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        with open(os.path.join(root, "MFU_LAB.jsonl"), "a") as f:
            f.write(line + "\n")
    except OSError:
        pass


def run_twin(impl, batches=(64, 128, 256), iters=20, warmup=4,
             layout="nhwc"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .resnet_jax_twin import init_params, make_train_step

    peak = _peak()
    out = {"exp": "twin", "impl": impl, "layout": layout,
           "device": _device_str(), "sweep": {}}
    best = 0.0
    flops_per_image = None
    for B in batches:
        try:
            SPD = 4  # match the framework bench's dispatch amortization
            params = init_params(jax.random.PRNGKey(0))
            vel = jax.tree_util.tree_map(jnp.zeros_like, params)
            step = make_train_step(impl=impl, steps_per_dispatch=SPD,
                                   layout=layout)
            rng = np.random.RandomState(0)
            x = jnp.asarray(rng.rand(B, 224, 224, 3), jnp.bfloat16)
            y = jnp.asarray(rng.randint(0, 1000, B), jnp.int32)
            if flops_per_image is None:
                # derived per-step cost of the single-step twin program
                # (lowering only; before any donation runs)
                c = _step_cost(
                    make_train_step(impl=impl, steps_per_dispatch=1,
                                    layout=layout), params, vel, x, y)
                if c is not None:
                    flops_per_image = c.flops / B
            for _ in range(warmup):
                loss, params, vel = step(params, vel, x, y)
            float(loss)
            t0 = time.perf_counter()
            for _ in range(iters):
                loss, params, vel = step(params, vel, x, y)
            float(loss)
            dt = time.perf_counter() - t0
            ips = B * iters * SPD / dt
            out["sweep"][str(B)] = round(ips, 2)
            best = max(best, ips)
        except Exception as e:
            out["sweep"][str(B)] = f"{type(e).__name__}: {e}"[:200]
        # per-point row, so a failed batch keeps the earlier ones
        _emit({"exp": "twin_point", "impl": impl, "layout": layout,
               "batch": B, "result": out["sweep"][str(B)]})
    out["images_per_sec"] = round(best, 2)
    if peak and best:
        fpi = flops_per_image or RESNET50_FWD_FLOPS_PER_IMAGE * 3
        out["mfu"] = round(best * fpi / peak, 4)
        out["mfu_basis"] = ("xla_cost_analysis" if flops_per_image
                            else "analytic_fallback")
        out["peak_flops_per_sec"] = peak
    _emit(out)


def run_convshapes(batch=128, iters=10, warmup=2):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from ..ops.conv_gemm import conv2d_gemm_nhwc

    peak = _peak()
    _emit({"exp": "convshapes_header", "batch": batch,
           "device": _device_str()})
    rng = np.random.RandomState(0)
    rows = []
    for cin, cout, k, s, hw, mult in RESNET50_CONV_SHAPES:
        pad = (k // 2, k // 2)
        ho = hw // s
        flops = 2.0 * batch * ho * ho * cin * cout * k * k
        x = jnp.asarray(rng.rand(batch, hw, hw, cin), jnp.bfloat16)
        w = jnp.asarray(rng.rand(k, k, cin, cout) * 0.01, jnp.bfloat16)
        row = {"shape": f"{cin}x{cout} k{k} s{s} {hw}²", "mult": mult,
               "flops_per_call": flops}

        def xla_conv(x, w):
            return lax.conv_general_dilated(
                x, w, (s, s), (pad, pad),
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        def gemm_conv(x, w):
            return conv2d_gemm_nhwc(x, w, stride=(s, s), padding=pad)

        impls = [("xla", xla_conv), ("gemm", gemm_conv)]
        if k == 3 and s == 1:
            from ..ops.conv3x3_pallas import conv3x3_s1_same

            impls.append(("pallas", conv3x3_s1_same))

        for name, fn in impls:
            # fwd+bwd: grad of sum wrt both operands — the training cost
            f = jax.jit(jax.grad(
                lambda x, w: jnp.sum(fn(x, w).astype(jnp.float32)),
                argnums=(0, 1)))
            try:
                for _ in range(warmup):
                    gx, gw = f(x, w)
                float(jnp.sum(gw.astype(jnp.float32)))
                t0 = time.perf_counter()
                for _ in range(iters):
                    gx, gw = f(x, w)
                float(jnp.sum(gw.astype(jnp.float32)))
                dt = (time.perf_counter() - t0) / iters
                row[name + "_tflops"] = round(3 * flops / dt / 1e12, 2)
            except Exception as e:
                row[name + "_tflops"] = f"{type(e).__name__}"[:60]
        rows.append(row)
        _emit(row)
    total = sum(r["flops_per_call"] * r["mult"]
                for r in rows)

    def model_tflops(key):
        t = 0.0
        for r in rows:
            v = r.get(key)
            if not isinstance(v, (int, float)) or v <= 0:
                return None
            t += r["flops_per_call"] * r["mult"] / (v * 1e12)
        return total / t / 1e12

    summary = {"exp": "convshapes", "batch": batch,
               "xla_weighted_tflops": model_tflops("xla_tflops"),
               "gemm_weighted_tflops": model_tflops("gemm_tflops"),
               "peak_flops_per_sec": peak, "rows": rows}
    _emit(summary)


def run_framework(impl, batches=(64, 128, 256)):
    import jax.numpy as jnp
    import numpy as np

    bench = _bench_module()

    from .. import nn
    from .resnet import ResNet50

    os.environ["bigdl.conv.impl"] = impl
    peak = _peak()
    rng = np.random.RandomState(0)
    out = {"exp": "framework", "impl": impl, "device": _device_str(),
           "sweep": {}}
    best = 0.0
    flops_per_image = None
    for B in batches:
        try:
            x = rng.rand(B, 3, 224, 224).astype("bfloat16")
            y = rng.randint(1, 1001, B).astype("float32")
            ips, cost = bench.bench_model(
                ResNet50(1000), nn.ClassNLLCriterion(), x, y,
                iters=20, warmup=4, compute_dtype=jnp.bfloat16,
                steps_per_dispatch=4)
            if cost is not None and flops_per_image is None:
                flops_per_image = cost.flops / B
            out["sweep"][str(B)] = round(ips, 2)
            best = max(best, ips)
        except Exception as e:
            out["sweep"][str(B)] = f"{type(e).__name__}: {e}"[:200]
        _emit({"exp": "framework_point", "impl": impl, "batch": B,
               "result": out["sweep"][str(B)]})
    out["images_per_sec"] = round(best, 2)
    if peak and best:
        fpi = flops_per_image or RESNET50_FWD_FLOPS_PER_IMAGE * 3
        out["mfu"] = round(best * fpi / peak, 4)
        out["mfu_basis"] = ("xla_cost_analysis" if flops_per_image
                            else "analytic_fallback")
    _emit(out)


def run_flash(seq_lens=(1024, 4096, 8192), blocks=(256, 512, 1024),
              iters=10, warmup=2, head_dims=(64, 128)):
    """Flash kernel fwd+bwd timing per (T, block, head_dim) — the
    VERDICT r3 #2 tuning matrix.  D=1024 total split 16×64 (the bench
    LM's shape — half the MXU's 128 lanes in the QK/PV contractions)
    vs 8×128 (full lanes), causal, bf16, constant 16k tokens per
    step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops.flash_attention import flash_attention

    peak = _peak()
    _emit({"exp": "flash_header", "device": _device_str()})
    rng = np.random.RandomState(0)
    rows = []
    for T in seq_lens:
        B = max(16384 // T, 1)
        for D in head_dims:
            H = 1024 // D
            q = jnp.asarray(rng.rand(B, H, T, D), jnp.bfloat16)
            k = jnp.asarray(rng.rand(B, H, T, D), jnp.bfloat16)
            v = jnp.asarray(rng.rand(B, H, T, D), jnp.bfloat16)
            # causal attention FLOPs: QK^T + PV at T/2 average extent
            flops_fwd = 2.0 * B * H * T * T * D  # 2 matmuls x (T²/2) x 2
            pairs = [(b, b) for b in blocks]
            if T >= 2048:
                # asymmetric follow-up (r4 window 2): the tied sweep
                # found 1024² best; check whether a smaller streamed-K
                # block pipelines better against the 1024 q block
                pairs += [(1024, 512), (512, 1024)]
            rows += _flash_rows(T, B, H, D, q, k, v, flops_fwd, pairs,
                                iters, warmup, peak)
    _emit({"exp": "flash_summary", "rows": rows,
           "peak_flops_per_sec": peak})


def _flash_rows(T, B, H, D, q, k, v, flops_fwd, pairs, iters, warmup,
                peak):
    import jax
    import jax.numpy as jnp

    from ..ops.flash_attention import flash_attention

    # chain several data-dependent kernel applications inside ONE jit:
    # a constant per-dispatch cost dominated the first rows at long-T's
    # small per-call work (the "backward is almost free" artifact: fwd
    # 11.7 ms vs fwd+bwd 13.2 ms at T=8192 — both carried the same
    # constant).  4 chained calls cut the per-call overhead 4x; rows
    # carry "chain" for provenance.
    CHAIN = 4
    rows = []
    for bq, bk in pairs:
        if bq > T or bk > T:
            continue
        row = {"exp": "flash", "T": T, "B": B, "H": H, "D": D,
               "block": bq if bq == bk else f"{bq}q/{bk}k",
               "block_q": bq, "block_k": bk, "chain": CHAIN}

        def f(q, k, v, bq=bq, bk=bk):
            o = q
            for _ in range(CHAIN):  # data-dependent: no XLA dedup
                o = flash_attention(o, k, v, causal=True, block_q=bq,
                                    block_k=bk)
            return jnp.sum(o.astype(jnp.float32))

        try:
            fwd = jax.jit(f)
            for _ in range(warmup):
                s = fwd(q, k, v)
            float(s)
            t0 = time.perf_counter()
            for _ in range(iters):
                s = fwd(q, k, v)
            float(s)
            # per-application figures (dt covers CHAIN applications)
            dt = (time.perf_counter() - t0) / iters / CHAIN
            row["fwd_ms"] = round(dt * 1e3, 2)
            row["fwd_tflops"] = round(flops_fwd / dt / 1e12, 2)

            grad = jax.jit(jax.grad(f, argnums=(0, 1, 2)))
            for _ in range(warmup):
                gs = grad(q, k, v)
            float(jnp.sum(gs[0].astype(jnp.float32)))
            t0 = time.perf_counter()
            for _ in range(iters):
                gs = grad(q, k, v)
            float(jnp.sum(gs[0].astype(jnp.float32)))
            dt = (time.perf_counter() - t0) / iters / CHAIN
            row["fwdbwd_ms"] = round(dt * 1e3, 2)
            row["fwdbwd_tflops"] = round(3 * flops_fwd / dt / 1e12, 2)
            if peak:
                row["fwdbwd_frac_of_peak"] = round(
                    3 * flops_fwd / dt / peak, 4)
        except Exception as e:
            row["error"] = f"{type(e).__name__}: {e}"[:200]
        rows.append(row)
        _emit(row)
    return rows


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--twin", action="store_true")
    p.add_argument("--convshapes", action="store_true")
    p.add_argument("--framework", action="store_true")
    p.add_argument("--flash", action="store_true")
    p.add_argument("--impl", default="xla",
                   choices=["xla", "gemm", "pallas"])
    p.add_argument("--layout", default="nhwc", choices=["nhwc", "nchw"],
                   help="twin activation layout (nchw = the framework-"
                        "matching layout-decomposition probe)")
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--iters", type=int, default=20)
    a = p.parse_args()
    if a.twin:
        run_twin(a.impl, iters=a.iters, layout=a.layout)
    if a.convshapes:
        run_convshapes(batch=a.batch)
    if a.framework:
        run_framework(a.impl)
    if a.flash:
        run_flash()


if __name__ == "__main__":
    main()
