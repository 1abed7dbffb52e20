"""Benchmark driver.

``python bench.py`` runs the DEVICE BATTERY: one process, on the
backend jax finds, printing ONE JSON line that names its device.  It
fails when it finds no accelerator — unless the CPU was asked for with
``JAX_PLATFORMS=cpu``, which runs a toy-size reference battery — and it
exits non-zero when any section raises: no probe child, no CPU worker
after a failed device worker, no salvaged or republished measurement.

Headline metric: **ResNet-50 ImageNet-shape training throughput
(images/sec/chip)** with an MFU figure — the BASELINE.json north-star
metric (train ResNet-50 end-to-end at >=45% MFU).  The reference's only
*published absolute* number is SimpleRNN 4.85 records/s on a Xeon node
(reference models/rnn/README.md:119-122), so ``vs_baseline`` is our
SimpleRNN records/s over 4.85; see ``vs_baseline_basis``.

The host-side legs keep their flags, one process each, every one pinned
to the CPU backend (they measure control planes, not the device):

    python bench.py --serving | --fleet | --trace | --disagg | --elastic
                    | --integrity | --telemetry | --sharding | --dlrm
                    | --sync | --slo | --loop | --blocksparse | --embed
                    | --tenant | --incident

MFU accounting: model FLOPs from XLA's cost model of the exact step
program (``cost_analysis()``; FMA = 2) over the chip's bf16 peak looked
up from ``device_kind`` (telemetry/device_info.py); the LM rows use the
6ND convention from the live parameter count, because Pallas custom
calls count zero in XLA's model.

Timing: host clock around a run of steps that ends in a scalar VALUE
FETCH of the final step's loss.  Fetching any output waits for that
step's whole executable, and the donated parameter chain orders every
step before it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REFERENCE_SIMPLE_RNN_RPS = 4.85  # reference models/rnn/README.md:122
VS_BASELINE_BASIS = (
    "SimpleRNN records/s over the reference's only published absolute "
    "(4.85 records/s, models/rnn/README.md:119-122); ResNet-50 has no "
    "published reference number"
)

# Analytic CROSS-CHECK constants (no longer on the reporting path —
# MFU is derived from XLA's cost model of the exact compiled step; a
# tier-1 test keeps derived-vs-analytic within 5%).  NOTE the r6
# correction: the widely-quoted "4.09 GFLOPs" for ResNet-50 at 224² is
# 4.09 G*MACs*; in the multiply-add=2 convention every MFU denominator
# uses (TPU peak specs count FMA as 2), the forward is 8.18 GFLOP per
# image.  Rounds 1-5 divided MACs by an FMA=2 peak, understating
# ResNet MFU ~2x (BENCH_r05's 0.135 is ~0.27 on the corrected basis).
RESNET50_FWD_MACS_PER_IMAGE = 4.09e9  # 224x224, standard count
RESNET50_FWD_FLOPS_PER_IMAGE = 2 * RESNET50_FWD_MACS_PER_IMAGE
TRAIN_FWD_MULTIPLIER = 3.0  # fwd + bwd(2x fwd)

# bf16 peak FLOP/s per chip — the one table lives in
# telemetry/device_info.py (with HBM capacity/bandwidth for the
# roofline); re-exported for existing callers.
from bigdl_tpu.telemetry.device_info import (  # noqa: E402,F401
    PEAK_FLOPS_TABLE, peak_flops_per_sec)

# --------------------------------------------------------------------------
# Device battery: the on-chip measurements (this process)
# --------------------------------------------------------------------------

def _train_step_fn(model, criterion, optim, compute_dtype=None):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.parallel.moe import aux_loss_term, collect_aux_paths

    # f32-accumulating criterions (fused xent) take bf16 logits directly
    upcast = not getattr(criterion, "accepts_low_precision", False)
    # MoE balance term rides the buffer thread (same read-back the
    # product drivers do) so the timed step is the real training program
    aux_paths = list(collect_aux_paths(model))

    def step(params, buffers, slots, lr, rng, x, y):
        def loss_fn(p):
            if compute_dtype is not None:
                p = jax.tree_util.tree_map(
                    lambda a: a.astype(compute_dtype), p)
                x_c = x.astype(compute_dtype)
            else:
                x_c = x
            out, nb = model.apply_fn(p, buffers, x_c, True, rng)
            if upcast:
                out = jnp.asarray(out, jnp.float32)
            loss = criterion._loss(out, y)
            if aux_paths:
                loss = loss + aux_loss_term(nb, aux_paths)
            return loss, nb

        # grads arrive f32: the internal bf16 cast's vjp restores the
        # master-weight dtype, so the update below stays full-precision
        (loss, nb), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        new_params, new_slots = optim.step(grads, params, slots, lr)
        return loss, new_params, nb, new_slots

    # donate params/buffers/slots — in-place updates, no HBM churn
    return step, jax.jit(step, donate_argnums=(0, 1, 2))


def bench_model(model, criterion, x, y, iters=20, warmup=3, lr=0.01,
                compute_dtype=None, steps_per_dispatch=1):
    """Returns ``(records_per_sec, cost)`` — ``cost`` is a
    :class:`bigdl_tpu.telemetry.perf.StepCost` for ONE training step
    (XLA cost-model FLOPs/bytes of the exact program timed, with its
    memory analysis) or None when the model reports no FLOPs.

    ``steps_per_dispatch > 1`` chains K train steps inside ONE jitted
    program (lax.fori_loop; the reference perf harness also repeats a
    fixed batch, DistriOptimizerPerf.scala:39-80), taking per-step host
    dispatch out of the timed loop."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from bigdl_tpu.optim import SGD

    optim = SGD(learning_rate=lr)
    params = model.param_tree()
    buffers = model.buffer_tree()
    slots = optim.init_state(params)
    inner, one_step = _train_step_fn(model, criterion, optim, compute_dtype)
    rng = jax.random.PRNGKey(0)
    lr_arr = jnp.float32(lr)
    x, y = jnp.asarray(x), jnp.asarray(y)

    K = max(int(steps_per_dispatch), 1)
    if K > 1:
        def multi(params, buffers, slots, lr, rng, x, y):
            def body(i, carry):
                p, b, s = carry
                _, p, b, s = inner(p, b, s, lr,
                                   jax.random.fold_in(rng, i), x, y)
                return (p, b, s)
            params, buffers, slots = lax.fori_loop(
                0, K - 1, body, (params, buffers, slots))
            return inner(params, buffers, slots, lr,
                         jax.random.fold_in(rng, K - 1), x, y)

        step = jax.jit(multi, donate_argnums=(0, 1, 2))
        iters = max(iters // K, 2)
    else:
        step = one_step

    # AOT-compile once; reuse the executable so cost_analysis sees the
    # exact program we time (and we never compile twice).
    from bigdl_tpu.telemetry.perf import cost_from_analysis

    run = step.lower(params, buffers, slots, lr_arr, rng, x, y).compile()

    # per-STEP cost from XLA's own model.  K>1 chains steps inside a
    # fori_loop whose body the cost analysis does not scale by trip
    # count, so the per-step figure comes from lowering the single-step
    # program instead (lowering traces only — no second compile).
    if K == 1:
        cost = cost_from_analysis(run.cost_analysis(),
                                  memory=run.memory_analysis(),
                                  source="compiled")
    else:
        cost = cost_from_analysis(
            one_step.lower(params, buffers, slots, lr_arr, rng, x,
                           y).cost_analysis(), source="lowered")
    if cost is not None and cost.flops <= 0:
        cost = None

    # Execution barrier: fetch the scalar loss value.  Fetching any
    # output waits for the final step's whole executable, and the
    # donated params chain orders every step before it.
    for _ in range(warmup):
        loss, params, buffers, slots = run(
            params, buffers, slots, lr_arr, rng, x, y)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, params, buffers, slots = run(
            params, buffers, slots, lr_arr, rng, x, y)
    float(loss)
    dt = time.perf_counter() - t0
    return x.shape[0] * iters * K / dt, cost


def _bench_resnet(batch, iters, warmup, compute_dtype, rng, spd=1,
                  stem="conv7", conv_impl=None):
    import jax.numpy as jnp
    from bigdl_tpu import nn
    from bigdl_tpu.models.resnet import ResNet50

    x = rng.rand(batch, 3, 224, 224).astype(
        "float32" if compute_dtype is None else str(jnp.dtype(compute_dtype)))
    y = rng.randint(1, 1001, batch).astype("float32")
    model = ResNet50(1000, stem=stem)
    if conv_impl:
        for m in model.modules_iter():
            if hasattr(m, "set_conv_impl"):
                m.set_conv_impl(conv_impl)
    ips, flops = bench_model(model,
                             nn.ClassNLLCriterion(), x, y,
                             iters=iters, warmup=warmup,
                             compute_dtype=compute_dtype,
                             steps_per_dispatch=spd)
    return ips, flops


def _bench_transformer_lm(rng, iters=16, spd=2, seq_len=1024, batch=16,
                          embed_dim=1024, num_heads=8, num_layers=8,
                          moe_experts=0, moe_aux_coef=0.0,
                          seq_strategy="flash", blocksparse=None):
    """Flagship LM: flash attention + fused xent, bf16.  Returns
    (tokens_per_sec, model_flops_per_sec_6nd, flops_per_sec_attn_incl,
    step_cost_or_None).  The 6ND figures are derived from the live
    param count (the standard LM MFU convention), the cost figure from
    XLA's model of the step program — note Pallas kernels (the flash
    path) are opaque custom calls the XLA cost model counts at zero
    flops, so the derived count under-reports attention math there;
    ``seq_strategy="dense"`` makes the two directly comparable (the
    tier-1 cross-check uses it).

    The 6ND convention counts NO attention-score FLOPs, which grow
    linearly in T and are real MXU work — the attention-inclusive rate
    adds 6·T·D·L per token (causal QK^T + PV, fwd×3) so long-context
    rows stop hiding kernel time (VERDICT r3 #2).

    ``moe_experts > 0`` benches the Switch-MoE variant; both FLOP rates
    then count ACTIVE params (top-1 routing: one expert's MLP per
    token), the standard MoE MFU convention."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu import nn
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.parallel.moe import MoEFFN

    V, D, L, T, B = 32000, embed_dim, num_layers, seq_len, batch
    # num_heads -> head_dim 128 = the MXU lane width: the r4 on-chip
    # flash matrix measured D=128 attention 1.22x faster than D=64 at
    # T=4096 (33.7 vs 27.5 TFLOP/s fwd+bwd, block 1024) with identical
    # d_model and parameter count.
    model = TransformerLM(V, embed_dim=D, num_heads=num_heads,
                          num_layers=L, max_len=T,
                          seq_strategy=seq_strategy,
                          output="logits", moe_experts=moe_experts,
                          moe_aux_coef=moe_aux_coef,
                          blocksparse=blocksparse)
    crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(), True)
    active = sum(a.size for a in jax.tree_util.tree_leaves(
        model.param_tree()))
    for m in model.modules_iter():
        # subtract the (E-1)/E inactive expert params, derived from the
        # constructed module's own leaves (never from a shape formula)
        if isinstance(m, MoEFFN) and m.n_experts > 1:
            ex = sum(m.params[k].size for k in ("wi", "bi", "wo", "bo"))
            active -= ex * (m.n_experts - 1) // m.n_experts
    x = rng.randint(1, V, (B, T)).astype("float32")
    y = rng.randint(1, V + 1, (B, T)).astype("float32")
    rps, cost = bench_model(model, crit, x, y, iters=iters, warmup=2,
                            compute_dtype=jnp.bfloat16,
                            steps_per_dispatch=spd)
    tokens_per_sec = rps * T
    attn_flops_per_token = 6.0 * T * D * L  # causal, train (fwd x3)
    return (tokens_per_sec, 6.0 * active * tokens_per_sec,
            (6.0 * active + attn_flops_per_token) * tokens_per_sec,
            cost)


def _bench_resnet_sweep(batches, iters, warmup, compute_dtype, rng, spd=1,
                        stem="conv7"):
    """Run every batch size and keep the best throughput (a pinned small
    batch under-utilizes the chip).  A batch that does not fit raises —
    size the list to the chip.  Returns (best_ips, cost, best_batch,
    sweep_dict)."""
    best = (None, None, None)
    sweep = {}
    for b in batches:
        ips, cost = _bench_resnet(b, iters, warmup, compute_dtype, rng,
                                  spd=spd, stem=stem)
        sweep[str(b)] = round(ips, 2)
        if best[0] is None or ips > best[0]:
            best = (ips, cost, b)
    return best[0], best[1], best[2], sweep


def run_device_battery() -> None:
    """The device sections, in this process, on the backend jax finds.
    Any section that raises ends the run non-zero; nothing is caught
    into an ``*_error`` field."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.models.rnn import SimpleRNN
    from bigdl_tpu.utils.compile_cache import ensure_compile_cache
    from bigdl_tpu.utils.rng import set_global_seed

    dev = jax.devices()[0]
    device_kind = getattr(dev, "device_kind", "") or str(dev)
    on_tpu = dev.platform != "cpu"
    if not on_tpu and os.environ.get("JAX_PLATFORMS", "").strip() != "cpu":
        raise SystemExit(
            "bench.py: jax found no accelerator (platform "
            f"{dev.platform!r}).  The device battery does not fall back; "
            "ask for the toy-size CPU reference battery with "
            "JAX_PLATFORMS=cpu")
    cache_dir = ensure_compile_cache()
    set_global_seed(42)
    rng = np.random.RandomState(0)
    t_start = time.time()
    peak = peak_flops_per_sec(device_kind) if on_tpu else None

    # XLA cost-model work accounting for the whole battery: per-
    # workload StepCosts land in one accountant (private registry) and
    # the payload rides the emitted line under "perf" — the telemetry
    # snapshot view of the bench (mfu family, roofline bounds, HBM
    # watermarks where the backend reports them)
    from bigdl_tpu.telemetry import MetricsRegistry
    from bigdl_tpu.telemetry.device_info import current_device_spec
    from bigdl_tpu.telemetry.perf import PerfAccountant

    pa = PerfAccountant(registry=MetricsRegistry(),
                        spec=current_device_spec(dev))

    def account(label, cost, seconds_per_step):
        if cost is not None and seconds_per_step > 0:
            pa.on_program(label, cost)
            pa.on_step(seconds_per_step)

    out = {
        "device": str(dev),
        "platform": dev.platform,
        "device_kind": device_kind,
        "tpu": bool(on_tpu),
        "n_devices": jax.device_count(),
        "jax_version": jax.__version__,
        "compile_cache_dir": cache_dir,
    }

    def done(section):
        print("[battery] %s done t=%.0fs" % (section,
                                             time.time() - t_start),
              file=sys.stderr, flush=True)

    # --- ResNet-50 ImageNet shapes: the north-star metric ---------------
    s2d_ips = s2d_cost = None
    if on_tpu:
        bf16_ips, bf16_cost, bf16_batch, sweep = _bench_resnet_sweep(
            (64, 128, 256), 20, 5, jnp.bfloat16, rng, spd=4)
        out["resnet50_bf16_batch_sweep"] = sweep
        out["resnet50_bf16_images_per_sec_per_chip"] = round(bf16_ips, 2)
        out["resnet50_bf16_batch"] = bf16_batch
        done("resnet50_bf16_sweep")
        f32_ips, _ = _bench_resnet(64, 10, 3, None, rng)
        out["resnet50_images_per_sec_per_chip"] = round(f32_ips, 2)
        out["resnet50_batch"] = 64
        done("resnet50_f32")
        # Space-to-depth stem: the SAME network function (exactness
        # pinned in tests/test_resnet_s2d.py) with the MXU-starved 7x7x3
        # stem conv rewritten as 4x4x12 — swept over the same batches as
        # the dense stem (a fair optimum-vs-optimum comparison; the
        # memory layouts differ, so their best batches can too) and
        # taken as headline when faster.
        s2d_ips, s2d_cost, s2d_batch, s2d_sweep = _bench_resnet_sweep(
            (64, 128, 256), 20, 5, jnp.bfloat16, rng, spd=4, stem="s2d")
        out["resnet50_s2d_batch_sweep"] = s2d_sweep
        out["resnet50_s2d_images_per_sec_per_chip"] = round(s2d_ips, 2)
        out["resnet50_s2d_batch"] = s2d_batch
        done("resnet50_s2d")
        head_ips, head_cost, head_batch = bf16_ips, bf16_cost, bf16_batch
    else:
        # CPU reference battery: compile time dominates; keep it tiny
        # but keep the 224^2 ImageNet shape so the unit stays honest.
        bf16_ips = None
        head_batch = 4
        head_ips, head_cost = _bench_resnet(head_batch, 2, 1, None, rng)
        out["resnet50_images_per_sec_per_chip"] = round(head_ips, 2)
        out["resnet50_batch"] = head_batch
        done("resnet50_cpu")

    out["resnet50_headline_stem"] = "conv7"
    if s2d_ips and s2d_ips > head_ips:
        head_ips, head_cost, head_batch = s2d_ips, s2d_cost, s2d_batch
        out["resnet50_headline_stem"] = "s2d"

    # alternative conv lowerings at the best batch (the NHWC layout and
    # the k²-matmul decomposition) — same optimum-vs-optimum contract
    # as the stem sweep: measure each, headline the fastest, record
    # which won.  conv_impl="pallas" is not in the list: its 3x3 kernel
    # is refused by the TPU lowering at the 56x56 and 28x28 stages
    # (tests/test_tpu_lowering.py pins that; ROADMAP D3 decides it).
    out["resnet50_headline_conv_impl"] = "xla"
    if on_tpu:
        for impl in ("xla_nhwc", "gemm"):
            alt_ips, alt_cost = _bench_resnet(
                bf16_batch, 12, 3, jnp.bfloat16, rng, spd=4,
                conv_impl=impl)
            out[f"resnet50_{impl}_images_per_sec_per_chip"] = round(
                alt_ips, 2)
            if alt_ips > head_ips:
                head_ips, head_cost, head_batch = (alt_ips, alt_cost,
                                                   bf16_batch)
                out["resnet50_headline_conv_impl"] = impl
        done("resnet50_conv_impls")

    # MFU from XLA's cost model of the exact compiled step — no
    # hand-coded FLOP constant on the reporting path (r6; the old
    # 4.09e9 "FLOPs" constant was MACs, understating MFU ~2x).  The
    # pre-optimization HLO count is the math as written: the analytic
    # figure rides along as a cross-check, and a tier-1 test holds the
    # two within 5% on CPU.
    analytic_fps = (RESNET50_FWD_FLOPS_PER_IMAGE
                    * TRAIN_FWD_MULTIPLIER * head_ips)
    if head_cost is not None:
        out["resnet50_flops_per_step"] = head_cost.flops
        out["resnet50_bytes_per_step"] = head_cost.bytes_accessed
        if head_cost.peak_bytes:
            out["resnet50_step_peak_bytes"] = head_cost.peak_bytes
        model_fps = head_cost.flops / head_batch * head_ips
        out["mfu_basis"] = (
            "xla_cost_analysis per-step flops (FMA=2) — corrected "
            "basis, ~2x the r1-r5 MACs-as-FLOPs analytic")
    else:
        # the chained-step rows cost the LOWERED single step, and on a
        # TPU under jax 0.9.0 that analysis reports no FLOPs (my chip
        # run, PR 21) — say which basis the figure stands on
        model_fps = analytic_fps
        out["mfu_basis"] = (
            "analytic 3 x 8.18 GFLOP/image (XLA's cost model reported "
            "no FLOPs for the lowered step on this backend)")
    account("resnet50_train_step", head_cost, head_batch / head_ips)
    out["resnet50_model_flops_per_sec"] = round(model_fps, 3)
    out["resnet50_analytic_flops_per_sec"] = round(analytic_fps, 3)
    out["mfu"] = round(model_fps / peak, 4) if peak else None
    out["peak_flops_per_sec"] = peak
    out["mfu_target"] = 0.45

    # --- TransformerLM: the flagship long-context model -----------------
    # (flash attention Pallas kernels + fused xent, bf16; MXU-bound —
    # shows the framework's MFU ceiling next to the conv-bound ResNet)
    if on_tpu:
        lm_tps, lm_fps, lm_fps_attn, lm_cost = _bench_transformer_lm(rng)
        out["transformerlm_tokens_per_sec"] = round(lm_tps, 1)
        out["transformerlm_model_flops_per_sec"] = round(lm_fps, 1)
        if lm_cost is not None:
            # flash Pallas kernels are opaque to the cost model
            # (counted 0 flops) — reported for the record, 6ND stays
            # the LM MFU basis (derived from the live param count, not
            # a hand-coded constant)
            out["transformerlm_flops_per_step"] = lm_cost.flops
        account("transformerlm_train_step", lm_cost,
                16 * 1024 / max(lm_tps, 1e-9))
        out["transformerlm_mfu"] = round(lm_fps / peak, 4)
        out["transformerlm_mfu_attn_incl"] = round(lm_fps_attn / peak, 4)
        done("transformerlm_T1024")
        # long-context: same model at T=4096 (dense attention OOMs here;
        # the flash kernels' O(T*block) memory is what makes it run)
        long_tps, long_fps, long_fps_attn, _ = _bench_transformer_lm(
            rng, iters=8, spd=2, seq_len=4096, batch=4)
        out["transformerlm_T4096_tokens_per_sec"] = round(long_tps, 1)
        out["transformerlm_T4096_mfu"] = round(long_fps / peak, 4)
        out["transformerlm_T4096_mfu_attn_incl"] = round(
            long_fps_attn / peak, 4)
        done("transformerlm_T4096")
        # block-sparse T4096 (BLaST kernels, ISSUE 12): the SAME model
        # with a sliding-window+global block mask covering ~58% of the
        # causal block grid — the leg the dense-vs-flash-vs-blocksparse
        # comparison hinges on.  Speedup is wall vs the flash leg; MFU
        # is on the EXECUTED-work basis (kernel-reported correction —
        # XLA's cost model cannot see Pallas-skipped blocks) with the
        # dense-equivalent recorded alongside.
        from bigdl_tpu.ops.block_sparse import (attention_work,
                                                sliding_window_mask)

        bs_cfg = {"window": 2, "globals": 1, "block": 512}
        bs_tps, bs_fps, bs_fps_attn, _ = _bench_transformer_lm(
            rng, iters=8, spd=2, seq_len=4096, batch=4,
            seq_strategy="blocksparse", blocksparse=bs_cfg)
        mask = sliding_window_mask(
            4096 // 512, 4096 // 512, bs_cfg["window"],
            n_global=bs_cfg["globals"], causal=True,
            block_q=512, block_k=512)
        work = attention_work(mask, 1, 1, 128, causal=True)
        dvf = work["executed_vs_flash_fraction"]
        bs_exec = bs_fps + dvf * (bs_fps_attn - bs_fps)
        out["transformerlm_blocksparse_T4096_tokens_per_sec"] = round(
            bs_tps, 1)
        out["transformerlm_blocksparse_mask_density"] = round(dvf, 4)
        out["transformerlm_blocksparse_config"] = (
            "sliding w%d+g%d block%d" % (
                bs_cfg["window"], bs_cfg["globals"], bs_cfg["block"]))
        out["transformerlm_blocksparse_T4096_speedup_x"] = round(
            bs_tps / long_tps, 3)
        out["transformerlm_blocksparse_T4096_mfu"] = round(
            bs_exec / peak, 4)
        out["transformerlm_blocksparse_T4096_mfu_dense_equiv"] = round(
            bs_fps_attn / peak, 4)
        done("transformerlm_blocksparse")
        # T=8192: where the block=1024 flash tuning pays the most
        # (r4 matrix: 62.5 vs 40.7 TFLOP/s fwd+bwd at D=128)
        l8_tps, l8_fps, l8_fps_attn, _ = _bench_transformer_lm(
            rng, iters=6, spd=2, seq_len=8192, batch=2)
        out["transformerlm_T8192_tokens_per_sec"] = round(l8_tps, 1)
        out["transformerlm_T8192_mfu"] = round(l8_fps / peak, 4)
        out["transformerlm_T8192_mfu_attn_incl"] = round(
            l8_fps_attn / peak, 4)
        done("transformerlm_T8192")

        # Switch-MoE LM (single-chip dense dispatch): the round-4
        # expert-parallel model family's one-chip throughput; MFU is
        # computed over ACTIVE params (top-1 routing: one expert's MLP
        # per token) as is standard for MoE
        m_tps, m_fps, _, _ = _bench_transformer_lm(
            rng, iters=8, spd=2, seq_len=1024, batch=16,
            embed_dim=512, num_heads=4, num_layers=4,
            moe_experts=8, moe_aux_coef=0.01)
        out["moe_transformerlm_tokens_per_sec"] = round(m_tps, 1)
        out["moe_transformerlm_experts"] = 8
        out["moe_transformerlm_active_param_mfu"] = round(m_fps / peak, 4)
        done("moe_transformerlm")

        # KV-cache decode throughput (round-4 generation path): batched
        # prefill + scan decode, the standard serving metric.  One
        # timing protocol (compile+barrier, reps, value-fetch barrier)
        # behind four rows: dense decode, GQA decode (llama-style,
        # 4x-smaller KV cache — decode is cache-bandwidth-bound, so
        # this row measures what grouped-query attention buys on THIS
        # chip), int8-cache decode, and prefill-only long-prompt
        # throughput (the flash prompt-only prefill; max_new=1).  Each
        # model drops before the next builds (two 130M-param models +
        # caches would double peak HBM).
        from bigdl_tpu.models.generate import make_generate
        from bigdl_tpu.models.transformer import TransformerLM

        set_global_seed(42)
        V, D, L, B, T0, NEW = 32000, 1024, 8, 8, 128, 128
        DEC_REPS = 3

        def timed_decode(prompt_len, max_new, kv_dtype=None, **lm_kw):
            """tokens/sec of (prefill + decode) at the shared timing
            protocol; tokens = generated for decode rows, prompt for
            the prefill row (max_new=1)."""
            glm = TransformerLM(V, embed_dim=D, num_heads=8,
                                num_layers=L,
                                max_len=prompt_len + max_new,
                                output="logits", **lm_kw)
            gen = make_generate(glm, compute_dtype=jnp.bfloat16,
                                kv_dtype=kv_dtype)
            gp = glm.param_tree()
            prompt = rng.randint(1, V, (B, prompt_len)).astype("int32")
            ids = gen(gp, prompt, max_new)
            _ = int(jax.device_get(ids)[0, -1])  # compile+barrier
            t0 = time.time()
            for _ in range(DEC_REPS):
                ids = gen(gp, prompt, max_new)
            _ = int(jax.device_get(ids)[0, -1])
            dt = time.time() - t0
            n_tok = max_new if max_new > 1 else prompt_len
            return round(B * n_tok * DEC_REPS / dt, 1)

        out["decode_tokens_per_sec"] = timed_decode(T0, NEW)
        out["decode_config"] = f"B{B} prompt{T0} new{NEW} D{D} L{L}"
        out["decode_gqa_tokens_per_sec"] = timed_decode(
            T0, NEW, norm="rms", mlp="swiglu", num_kv_heads=2, rope=True)
        out["decode_gqa_config"] = (
            f"B{B} prompt{T0} new{NEW} D{D} L{L} kv2/8 llama-style")
        # decode is cache-bandwidth-bound: the int8 cache halves the
        # bytes per step vs the bf16 cache (an approximation knob, off
        # by default)
        out["decode_int8kv_tokens_per_sec"] = timed_decode(
            T0, NEW, kv_dtype="int8")
        out["decode_int8kv_config"] = (
            f"B{B} prompt{T0} new{NEW} D{D} L{L} int8 cache")
        T0L = 1920
        out["prefill_tokens_per_sec"] = timed_decode(T0L, 1)
        # max_new=1: the timed region is prefill PLUS one decode step —
        # noted so the row reads honestly
        out["prefill_config"] = (f"B{B} prompt{T0L} D{D} L{L} "
                                 "(+1 decode step)")
        done("decode")
    else:
        # CPU reference leg for the second bench workload: a tiny
        # dense-attention TransformerLM, so a CPU-backend run reports
        # derived mfu-family metrics for BOTH bench workloads (dense
        # attention so the XLA cost model sees the attention math —
        # flash Pallas custom calls count zero flops)
        c_tps, _, _, c_cost = _bench_transformer_lm(
            rng, iters=2, spd=1, seq_len=128, batch=2,
            embed_dim=128, num_heads=2, num_layers=2,
            seq_strategy="dense")
        out["transformerlm_cpu_tokens_per_sec"] = round(c_tps, 1)
        if c_cost is not None:
            out["transformerlm_cpu_flops_per_step"] = c_cost.flops
        account("transformerlm_train_step", c_cost,
                2 * 128 / max(c_tps, 1e-9))
        done("transformerlm_cpu")

    # --- SimpleRNN: the reference's published workload (batch 12) -------
    V, H, T, B = 4001, 40, 25, 12
    seq = rng.randint(0, V, (B, T + 1))
    x_rnn = np.eye(V, dtype=np.float32)[seq[:, :-1]]
    y_rnn = (seq[:, 1:] + 1).astype(np.float32)
    rnn_crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True)
    # batch-12 steps are ~1 ms of compute, so per-step dispatch is a
    # large share of the wall: chain steps per dispatch, as for
    # ResNet/LM above (steps still run back-to-back on-device).
    # Whether the chaining still buys anything natively is ROADMAP S5.
    rnn_spd = 32 if on_tpu else 1
    rnn_rps, _ = bench_model(SimpleRNN(V, H, V), rnn_crit, x_rnn, y_rnn,
                             iters=64 if on_tpu else 10,
                             steps_per_dispatch=rnn_spd)
    out["simplernn_records_per_sec"] = round(rnn_rps, 2)
    out["simplernn_steps_per_dispatch"] = rnn_spd
    done("simplernn")

    # --- LeNet-5 MNIST shapes ------------------------------------------
    B_l = 256
    x_len = rng.rand(B_l, 784).astype(np.float32)
    y_len = rng.randint(1, 11, B_l).astype(np.float32)
    lenet_spd = 32 if on_tpu else 1
    lenet_ips, _ = bench_model(LeNet5(10), nn.ClassNLLCriterion(),
                               x_len, y_len, iters=64 if on_tpu else 10,
                               steps_per_dispatch=lenet_spd)
    out["lenet5_images_per_sec"] = round(lenet_ips, 2)
    out["lenet5_steps_per_dispatch"] = lenet_spd

    # the bench's telemetry-snapshot view: per-workload cost-model
    # flops/bytes, mfu, roofline bound, HBM watermarks if any
    out["perf"] = pa.payload()

    out.update({
        "metric": "ResNet-50 train throughput"
                  + (" (bf16)" if bf16_ips else " (f32)"),
        "value": round(head_ips, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(rnn_rps / REFERENCE_SIMPLE_RNN_RPS, 2),
        "vs_baseline_basis": VS_BASELINE_BASIS,
        "measured_at": _utc_now(),
    })
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Serving leg: open-loop load through the hardened InferenceServer
# --------------------------------------------------------------------------

SERVING_RESULT = "SERVING_r01.json"


def _serving_measurements(rate_rps: float = 800.0, duration_s: float = 4.0,
                          burst: int = 512, feature_dim: int = 64,
                          max_batch: int = 64, max_queue: int = 256):
    """Synthetic open-loop load through ``serving.InferenceServer``.

    Open loop: requests are submitted on a wall-clock schedule
    regardless of completions (the arrival process does not slow down
    when the server does — the regime where queues actually grow and
    shedding matters), then a queue-overflowing burst measures the
    admission-control path.  Returns the measurement dict; pure
    control-plane numbers, meaningful on any backend."""
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.serving import InferenceServer, Status

    model = nn.Sequential(nn.Linear(feature_dim, 128), nn.Tanh(),
                          nn.Linear(128, 10), nn.LogSoftMax())
    srv = InferenceServer(model, max_batch=max_batch, max_queue=max_queue,
                          default_deadline_s=5.0)
    srv.start()
    rng = np.random.RandomState(0)
    x = rng.rand(feature_dim).astype(np.float32)
    try:
        # warm the bucket ladder so steady-state numbers exclude compiles
        warm = [srv.submit(rng.rand(feature_dim).astype(np.float32))
                for _ in range(max_batch)]
        for f in warm:
            f.result(timeout=120)

        futs = []
        t0 = time.perf_counter()
        n = 0
        while True:
            elapsed = time.perf_counter() - t0
            if elapsed >= duration_s:
                break
            while n < int(elapsed * rate_rps):
                futs.append(srv.submit(x))
                n += 1
            time.sleep(0.0005)
        steady = [f.result(timeout=120) for f in futs]
        ok_lat = [r.latency_s for r in steady if r.ok]
        shed = sum(r.status is Status.OVERLOADED for r in steady)

        # the one quantile implementation (telemetry.Histogram — exact
        # over its sample window), not a third hand-rolled percentile
        from bigdl_tpu.telemetry import Histogram

        lat_hist = Histogram(window=max(1, len(ok_lat)))
        for v in ok_lat:
            lat_hist.observe(v)

        def pct(q):
            p = lat_hist.quantile(q)
            return round(p * 1e3, 3) if p is not None else None

        # burst: 2x the queue bound submitted as fast as possible —
        # admission control must shed the overflow fast and typed
        bfuts = [srv.submit(x) for _ in range(2 * max_queue if burst is None
                                              else burst)]
        bres = [f.result(timeout=120) for f in bfuts]
        bshed = sum(r.status is Status.OVERLOADED for r in bres)
        snap = srv.metrics.snapshot()
        return {
            "steady": {
                "target_rps": rate_rps,
                "offered": len(steady),
                "achieved_rps": round(len(steady) / duration_s, 1),
                "ok": sum(r.ok for r in steady),
                "shed": shed,
                "shed_rate": round(shed / len(steady), 4) if steady
                else 0.0,
                "latency_p50_ms": pct(0.50),
                "latency_p99_ms": pct(0.99),
            },
            "burst": {
                "offered": len(bres),
                "ok": sum(r.ok for r in bres),
                "shed": bshed,
                "shed_rate": round(bshed / len(bres), 4) if bres else 0.0,
            },
            "totals": {k: snap[k] for k in
                       ("total", "served_ok", "shed", "deadline_exceeded",
                        "internal_error", "batches", "queue_depth_max")},
            "breaker_trips": srv.breaker.trips,
            "buckets_dispatched": srv.compile_stats()["buckets_dispatched"],
            "max_batch": max_batch,
            "max_queue": max_queue,
            "drained_clean": srv.drain(timeout=60),
        }
    finally:
        srv.stop(timeout=30)


def run_serving_bench() -> None:
    """--serving mode: run the open-loop serving load on CPU (control-
    plane numbers), write SERVING_r01.json, print the one JSON line."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "serving", "backend": "cpu",
           "measured_at": _utc_now()}
    try:
        out.update(_serving_measurements())
        p99 = out["steady"]["latency_p99_ms"]
        out.update({
            "metric": "serving open-loop p99 latency",
            "value": p99 if p99 is not None else 0.0,
            "unit": "ms",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "serving open-loop p99 latency",
                    "value": 0.0, "unit": "ms"})
    try:
        with open(os.path.join(_here(), SERVING_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Fleet leg: open-loop Zipf load over a 4-replica serving fleet
# --------------------------------------------------------------------------

FLEET_RESULT = "SERVING_r02.json"


def _fleet_measurements(n_replicas: int = 4, rate_rps: float = 500.0,
                        duration_s: float = 2.5, feature_dim: int = 64,
                        max_batch: int = 32, max_queue: int = 128,
                        users: int = 128, zipf_a: float = 1.1,
                        deadline_s: float = 2.0):
    """Open-loop load with a Zipf-distributed request mix through the
    replica fleet (``serving.ServingFleet`` + ``FleetRouter``).

    Zipf mix: requests draw one of ``users`` distinct feature rows
    with rank-``zipf_a`` popularity — the heavy-skew traffic shape the
    BigDL lineage served in production.  Three passes: (1) steady
    un-hedged fleet (p50/p99, shed rate, goodput-per-chip), (2) the
    same load with tail-latency hedging enabled (hedged p99 + hedge
    counters), (3) a replica kill mid-load (recovery wall-clock =
    kill → ejected from the live set → first post-eject OK).  Pure
    control-plane numbers, meaningful on any backend."""
    import contextlib
    import threading

    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.resilience import faults
    from bigdl_tpu.serving import ServingFleet, Status
    from bigdl_tpu.telemetry import Histogram

    rng = np.random.RandomState(0)
    features = rng.rand(users, feature_dim).astype(np.float32)
    ranks = np.arange(1, users + 1, dtype=np.float64)
    probs = ranks ** -float(zipf_a)
    probs /= probs.sum()

    model = nn.Sequential(nn.Linear(feature_dim, 128), nn.Tanh(),
                          nn.Linear(128, 10), nn.LogSoftMax())

    def build(hedge):
        fleet = ServingFleet.build(
            model, n_replicas=n_replicas,
            server_kw=dict(max_batch=max_batch, max_queue=max_queue),
            heartbeat_timeout=0.4,
            router_kw=dict(default_deadline_s=deadline_s,
                           hedge=hedge))
        fleet.start()
        # warm every replica's bucket ladder so steady numbers
        # exclude compiles
        warm = [fleet.servers[rid].submit(features[i % users])
                for rid in fleet.servers for i in range(max_batch)]
        for f in warm:
            f.result(timeout=120)
        return fleet

    def open_loop(fleet, duration):
        mix = rng.choice(users, size=int(rate_rps * duration) + 64,
                         p=probs)
        futs = []
        t0 = time.perf_counter()
        n = 0
        while True:
            elapsed = time.perf_counter() - t0
            if elapsed >= duration:
                break
            while n < int(elapsed * rate_rps):
                futs.append(fleet.submit(features[mix[n % len(mix)]]))
                n += 1
            time.sleep(0.0005)
        return [f.result(timeout=120) for f in futs]

    def stats(results):
        ok_lat = [r.latency_s for r in results if r.ok]
        hist = Histogram(window=max(1, len(ok_lat)))
        for v in ok_lat:
            hist.observe(v)

        def pct(q):
            p = hist.quantile(q)
            return round(p * 1e3, 3) if p is not None else None

        shed = sum(r.status is Status.OVERLOADED for r in results)
        return {
            "offered": len(results),
            "ok": sum(r.ok for r in results),
            "shed": shed,
            "shed_rate": round(shed / len(results), 4) if results
            else 0.0,
            "latency_p50_ms": pct(0.50),
            "latency_p99_ms": pct(0.99),
        }

    out = {"n_replicas": n_replicas, "users": users,
           "zipf_a": zipf_a, "rate_rps": rate_rps,
           "deadline_s": deadline_s}

    # -- pass 0: distributed request tracing — overhead + coverage.
    # Runs FIRST: the overhead A/B needs the fresh process heap (the
    # open-loop passes below leave fleets' worth of garbage that
    # inflates gen2 GC scans exactly on the allocation-heavier traced
    # legs).
    out["trace"] = _fleet_trace_pass(features=features, users=users)
    out["trace_overhead_pct"] = out["trace"]["overhead_pct"]
    out["trace_p99_coverage"] = out["trace"]["p99_coverage"]

    # -- pass 1: steady un-hedged + replica kill mid-load ------------
    fleet = build(hedge=False)
    try:
        steady = open_loop(fleet, duration_s)
        out["steady"] = stats(steady)
        gpc = fleet.goodput_per_chip()
        out["goodput_per_chip_flops"] = round(
            gpc["model_flops_per_sec_per_chip"], 1)
        out["fleet_mfu"] = gpc["mfu"]

        # replica kill mid-load: keep offering traffic while r1 dies;
        # recovery = kill -> ejected from the live set -> first
        # post-eject OK probe
        kill = {"recovery_s": None, "ejected": False}

        def killer():
            t_kill = time.monotonic()
            deadline = t_kill + 30
            while "r1" in fleet.router.members \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            kill["ejected"] = "r1" not in fleet.router.members
            while time.monotonic() < deadline:
                probe = fleet.submit(features[0]).result(timeout=30)
                if probe.ok:
                    kill["recovery_s"] = round(
                        time.monotonic() - t_kill, 3)
                    return
                time.sleep(0.01)

        with contextlib.ExitStack() as stack:
            stack.enter_context(faults.kill_replica("r1"))
            kt = threading.Thread(target=killer)
            kt.start()
            during = open_loop(fleet, duration_s / 2)
            kt.join(timeout=60)
        out["kill"] = dict(kill, **stats(during))
        out["recovery_s"] = kill["recovery_s"]
        # every request resolved with a typed Status (zero lost
        # beyond the shed budget)
        out["all_resolved_typed"] = all(
            r.status is not None for r in steady + during)
    finally:
        fleet.stop(timeout=30)

    # -- pass 2: the same steady load, hedged ------------------------
    fleet = build(hedge=True)
    try:
        hedged = open_loop(fleet, duration_s)
        h = stats(hedged)
        h["hedges_fired"] = fleet.router.metrics.hedges_fired
        h["hedges_won"] = fleet.router.metrics.hedges_won
        out["hedged"] = h
    finally:
        fleet.stop(timeout=30)

    out["p99_ms"] = out["steady"]["latency_p99_ms"]
    out["hedged_p99_ms"] = out["hedged"]["latency_p99_ms"]
    out["shed_rate"] = out["steady"]["shed_rate"]
    return out


def _fleet_trace_pass(features, users,
                      serial_n: int = 200, repeats: int = 5):
    """The traced fleet pass: (1) tracing overhead — ONE fleet,
    alternating A/B legs with the RequestTracer detached/attached
    (between-process fleet noise on the 1-core box dwarfs the
    per-request cost; within one process back-to-back legs agree to
    ~µs), min-of-repeats closed-loop serial latency; (2) per-request
    trace coverage — an open-loop burst on the same fleet with the
    sampler budget opened wide, every kept request stitched
    cross-replica and its span-union coverage of the observed wall
    clock computed (the p99 cohort's mean is the ledger metric)."""
    from bigdl_tpu import nn
    from bigdl_tpu.serving import (ServingFleet, trace_attribution,
                                   trace_coverage)

    feature_dim = features.shape[1]
    model = nn.Sequential(nn.Linear(feature_dim, 128), nn.Tanh(),
                          nn.Linear(128, 10), nn.LogSoftMax())

    def serial_wall(fleet):
        t0 = time.perf_counter()
        for i in range(serial_n):
            fleet.submit(features[i % users]).result(timeout=120)
        return time.perf_counter() - t0

    out = {}
    # the overhead legs run the REALISTIC sampler (tail keeps trouble
    # + a bounded OK budget; dropped traces cost zero span records
    # router-side and never touch the transport under publish-on-keep)
    fleet = ServingFleet.build(
        model, n_replicas=2,
        server_kw=dict(max_batch=8, max_queue=128),
        heartbeat_timeout=0.4, tracing=True,
        trace_kw=dict(keep_per_s=20.0, burst=20.0),
        router_kw=dict(default_deadline_s=10.0))
    fleet.start()
    try:
        fleet.submit(features[0]).result(timeout=120)  # warm compiles
        tracer = fleet.router.tracing
        # pin the pre-existing heap (jax caches, compiled programs)
        # out of the collector: gen2 scans over it would tax the
        # allocation-heavier traced legs for garbage that is not theirs
        import gc
        import statistics

        gc.collect()
        gc.freeze()
        deltas, plains = [], []
        for rep in range(repeats):
            # alternate leg order per repeat: any monotonic drift of
            # the box (thermal / cgroup throttle) biases whichever
            # side always runs second — median of paired deltas over
            # both orders cancels it
            order = (False, True) if rep % 2 == 0 else (True, False)
            pair = {}
            for traced in order:
                fleet.router.tracing = tracer if traced else None
                pair[traced] = serial_wall(fleet)
            fleet.router.tracing = tracer
            deltas.append(pair[True] - pair[False])
            plains.append(pair[False])
        gc.unfreeze()
        # clamp at 0: a negative median is the noise floor, and a
        # negative frozen baseline would arm the "lower" sentinel
        # against pure jitter
        out["overhead_pct"] = round(max(
            0.0, statistics.median(deltas)
            / statistics.median(plains) * 100.0), 2)
        out["serial_n"] = serial_n
        # coverage burst: keep EVERYTHING from here on so every
        # request of the slab stitches
        from bigdl_tpu.telemetry.trace_context import TailSampler

        fleet.tracing.sampler = TailSampler(keep_per_s=1e6, burst=1e6)
        # coverage burst: a concurrent slab so batches coalesce like
        # live traffic, every request kept (budget opened wide above)
        futs = [fleet.submit(features[i % users])
                for i in range(200)]
        res = [f.result(timeout=120) for f in futs]
        kept = fleet.kept_traces()
        covers = []
        for k in kept:
            t = fleet.stitch_trace(k["trace_id"])
            if t is None:
                continue
            c = trace_coverage(t)
            if c is not None:
                covers.append((k["latency_s"], c, t))
        covers.sort()
        out["sampled"] = len(kept)
        out["stitched"] = len(covers)
        out["all_resolved_typed"] = all(
            r.status is not None for r in res)
        if covers:
            p99_idx = int(0.99 * (len(covers) - 1))
            cohort = covers[p99_idx:]
            out["p99_coverage"] = round(
                sum(c for _, c, _ in cohort) / len(cohort), 4)
            out["coverage_min"] = round(min(c for _, c, _ in covers),
                                        4)
            attr = trace_attribution(cohort[-1][2])
            out["p99_critical_phase"] = attr["critical_phase"]
        else:
            out["p99_coverage"] = None
        out["sampler"] = fleet.tracing.sampler.snapshot()
    finally:
        fleet.stop(timeout=30)
    return out


def run_fleet_bench() -> None:
    """--fleet mode: open-loop Zipf load over the 4-replica fleet on
    CPU (control-plane numbers), write SERVING_r02.json, print the one
    JSON line."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "fleet", "backend": "cpu",
           "measured_at": _utc_now()}
    try:
        out.update(_fleet_measurements())
        p99 = out["p99_ms"]
        out.update({
            "metric": "fleet open-loop p99 latency",
            "value": p99 if p99 is not None else 0.0,
            "unit": "ms",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "fleet open-loop p99 latency",
                    "value": 0.0, "unit": "ms"})
    try:
        with open(os.path.join(_here(), FLEET_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Trace chaos leg: hedged + retried + kill-mid-decode, every sampled
# request stitched cross-replica (the ISSUE 13 acceptance artifact)
# --------------------------------------------------------------------------

TRACE_RESULT = "TRACE_r01.json"


def _trace_chaos_measurements(vocab: int = 23, t_max: int = 32,
                              prompt_len: int = 5):
    """The distributed-tracing chaos bar: a 4-replica disaggregated
    fleet (2 prefill + 2 decode, tracing on, keep-everything sampler)
    absorbs hedged prefills, a retried prefill, and a decode replica
    killed mid-stream — then every sampled request's stitched
    cross-replica trace is checked for wall-clock coverage, the hedge
    winner/loser and the replayed decode attempt are located as
    labeled spans, and the p99 cohort's critical-path phase is named.
    """
    import numpy as np

    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.resilience import faults
    from bigdl_tpu.serving import ServingFleet, trace_coverage
    from bigdl_tpu.utils.rng import RNG

    RNG().set_seed(4)
    model = TransformerLM(vocab, embed_dim=16, num_heads=2,
                          mlp_dim=32, num_layers=1, max_len=t_max)
    fleet = ServingFleet.build(
        model, n_replicas=4,
        roles=("prefill", "prefill", "decode", "decode"),
        kv_pages=32, kv_page_size=4, server_kw=dict(max_batch=8),
        heartbeat_timeout=0.4, pump_interval_s=0.05,
        tracing=True, trace_kw=dict(keep_per_s=1e6, burst=1e6),
        router_kw=dict(default_deadline_s=60.0, disaggregate=True,
                       hedge=True, hedge_delay_s=0.05))
    fleet.start()
    out = {"n_replicas": 4,
           "roles": ["prefill", "prefill", "decode", "decode"]}
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, vocab + 1,
                           (prompt_len,)).astype(np.int32)
               for _ in range(4)]
    try:
        # warm every pool's compiled programs (hedge/kill must land on
        # decode work, not compile walls)
        for p in prompts[:2]:
            r = fleet.submit_generate(p, max_new=4).result(300)
            assert r.ok, (r.status, r.error)

        results = []
        # -- hedged: the primary prefill goes slow, the duplicate on
        # the other prefill replica wins; the loser's span must close
        # hedge_outcome=lost at discard
        with faults.delay_replica("r0", 0.4, times=2):
            results.append(
                fleet.submit_generate(prompts[0],
                                      max_new=6).result(300))
        # -- retried: one prefill step failure → retry on the other
        # prefill replica with the remaining budget
        with faults.serving_step_failures(times=1, server="r0"):
            results.append(
                fleet.submit_generate(prompts[1],
                                      max_new=6).result(300))
        # -- kill mid-decode: slow the decode pool, find the replica
        # actually streaming, kill it — the retained handoff replays
        # on the survivor inside the same trace
        killed = None
        with faults.serving_step_latency(0.05, times=1 << 10):
            fut = fleet.submit_generate(prompts[2], max_new=20)
            deadline = time.monotonic() + 10
            while killed is None and time.monotonic() < deadline:
                snap = fleet.router.snapshot()
                for rid in ("r2", "r3"):
                    if snap["inflight"].get(rid, 0) > 0:
                        killed = rid
                        break
                time.sleep(0.02)
            if killed is not None:
                with faults.kill_replica(killed):
                    k_deadline = time.monotonic() + 15
                    while fleet.servers[killed].healthy() \
                            and time.monotonic() < k_deadline:
                        time.sleep(0.02)
            results.append(fut.result(300))
        out["killed_replica"] = killed
        # -- background OK traffic for the p99 cohort
        for i in range(6):
            results.append(
                fleet.submit_generate(prompts[i % 4],
                                      max_new=4).result(300))

        out["offered"] = len(results)
        out["ok"] = sum(1 for r in results if r.ok)
        out["all_resolved_typed"] = all(
            r.status is not None for r in results)

        kept = fleet.kept_traces()
        stitched = {}
        covers = []
        for k in kept:
            t = fleet.stitch_trace(k["trace_id"])
            if t is None:
                continue
            stitched[k["trace_id"]] = t
            c = trace_coverage(t)
            if c is not None:
                covers.append(c)
        out["sampled"] = len(kept)
        out["stitched"] = len(stitched)
        out["coverage_min"] = round(min(covers), 4) if covers else None
        out["coverage_mean"] = round(sum(covers) / len(covers), 4) \
            if covers else None

        def spans(t, cat=None):
            return [e for e in t["traceEvents"]
                    if e.get("ph") == "X"
                    and (cat is None or e.get("cat") == cat)]

        # hedge winner + loser are distinct labeled spans in ONE trace
        hedge_ok = False
        for t in stitched.values():
            outcomes = {(e["args"].get("hedge_outcome"))
                        for e in spans(t, "attempt")}
            if {"won", "lost"} <= outcomes:
                hedge_ok = True
                break
        out["hedge_winner_loser_labeled"] = hedge_ok
        # the killed decode shows up as a failed attempt + the
        # replayed survivor attempt in the same stitched trace
        replay_ok = False
        for t in stitched.values():
            dec = [e for e in spans(t, "attempt")
                   if e["args"].get("kind") == "decode"]
            statuses = {e["args"].get("status") for e in dec}
            replicas = {e["args"].get("replica") for e in dec}
            if len(dec) >= 2 and len(replicas) >= 2 \
                    and "ok" in statuses \
                    and any(s not in ("ok", None) for s in statuses):
                replay_ok = True
                break
        out["replayed_decode_labeled"] = replay_ok

        from tools.trace_report import analyze

        report = analyze(stitched)
        out["p99_cohort"] = report["p99_cohort"]
        out["sampler"] = fleet.tracing.sampler.snapshot()
        # the artifact carries a few exemplar stitched traces: the
        # hedged one, the replayed one, and the slowest
        keep_ids = []
        for pred in (lambda t: {"won", "lost"} <= {
                         e["args"].get("hedge_outcome")
                         for e in spans(t, "attempt")},
                     lambda t: any(
                         e["args"].get("kind") == "decode"
                         and e["args"].get("status")
                         not in ("ok", None)
                         for e in spans(t, "attempt"))):
            for tid, t in stitched.items():
                if pred(t) and tid not in keep_ids:
                    keep_ids.append(tid)
                    break
        out["traces"] = {tid: stitched[tid] for tid in keep_ids[:4]}
    finally:
        fleet.stop(timeout=30)
    return out


def run_trace_bench() -> None:
    """--trace mode: the distributed-tracing chaos run on CPU, write
    TRACE_r01.json, print the one JSON line (traces themselves stay in
    the artifact, not on stdout)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "trace", "backend": "cpu",
           "measured_at": _utc_now()}
    try:
        out.update(_trace_chaos_measurements())
        out.update({
            "metric": "stitched trace coverage (min)",
            "value": out.get("coverage_min") or 0.0,
            "unit": "fraction",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "stitched trace coverage (min)",
                    "value": 0.0, "unit": "fraction"})
    try:
        with open(os.path.join(_here(), TRACE_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps({k: v for k, v in out.items()
                      if k != "traces"}), flush=True)


# --------------------------------------------------------------------------
# Disagg leg: paged KV + prefill/decode pools + telemetry autoscaling
# --------------------------------------------------------------------------

DISAGG_RESULT = "SERVING_r03.json"


def _disagg_measurements(phase_s: float = 2.5, low_rps: float = 2.0,
                         high_rps: float = 60.0, users: int = 24,
                         zipf_a: float = 1.1, prompt_len: int = 6,
                         max_new: int = 40, long_prompt: int = 8,
                         long_new: int = 24, t_max: int = 64,
                         page_size: int = 4, vocab: int = 31,
                         max_queue: int = 16,
                         eval_interval_s: float = 0.35,
                         cooldown_s: float = 1.2,
                         deadline_s: float = 10.0,
                         layers: int = 2):
    """The serving scale-out leg: paged KV-cache vs the static-bucket
    baseline at EQUAL arena bytes, a Zipf load ramp over a mixed
    prefill/decode fleet in three passes (static / paged / paged +
    autoscale).

    Proof obligations (the committed SERVING_r03.json):

    * at equal KV arena bytes the paged pool sustains ≥ 2x the
      concurrent long decodes the static ``T_max`` accounting admits,
      with every paged token stream EXACTLY the unpaged
      ``cached_generate`` stream;
    * under the ramp, each pool scales up on sustained p99/shed/queue
      breach and back down on idle (replica-count timeline), with
      cooldown respected and ≤ 1 scale direction flip per ramp phase,
      at a shed rate no worse than the fixed paged fleet's;
    * TTFT/TPOT p50/p99 per pass.  Pure control-plane numbers,
      meaningful on any backend."""
    import threading

    import numpy as np

    from bigdl_tpu.models.generate import cached_generate
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import (AutoscalePolicy, Autoscaler,
                                   InferenceServer, KVPagePool,
                                   ServingFleet, Status)
    from bigdl_tpu.telemetry import Histogram
    from bigdl_tpu.utils.rng import RNG

    def build_model():
        RNG().set_seed(11)
        return TransformerLM(vocab, embed_dim=16, num_heads=2,
                             mlp_dim=32, num_layers=layers,
                             max_len=t_max)

    model = build_model()
    params = model.param_tree()
    gen = cached_generate(model)
    rng = np.random.RandomState(0)
    prompts = rng.randint(1, vocab + 1,
                          (users, prompt_len)).astype(np.int32)
    ranks = np.arange(1, users + 1, dtype=np.float64)
    probs = ranks ** -float(zipf_a)
    probs /= probs.sum()
    num_pages = (2 * t_max) // page_size   # arena = TWO static buckets

    def ref_tail(prompt, n):
        return np.asarray(gen(params, prompt[None], n))[0,
                                                        len(prompt):]

    out = {"t_max": t_max, "page_size": page_size,
           "arena_positions": num_pages * page_size}

    # -- part A: paged-vs-static concurrency at equal arena bytes ----
    pool = KVPagePool.for_model(model, num_pages, page_size=page_size)
    out["arena_bytes"] = pool.arena_bytes()
    pages_per_long = pool.pages_for_tokens(long_prompt + long_new)
    #: the static-bucket accounting: every request pins a whole T_max
    #: window, so this arena admits exactly this many long decodes
    static_max = (num_pages * page_size) // t_max
    #: the paged accounting: requests pin only the pages they fill
    paged_target = num_pages // pages_per_long
    srv = InferenceServer(model, kv_pool=pool, max_batch=8,
                          batch_window_s=0.25).start()
    try:
        long_prompts = [rng.randint(1, vocab + 1,
                                    (long_prompt,)).astype(np.int32)
                        for _ in range(paged_target)]
        refs = [ref_tail(p, long_new) for p in long_prompts]
        futs = [srv.submit_generate(p, long_new)
                for p in long_prompts]
        res = [f.result(timeout=300) for f in futs]
        exact = all(r.ok and np.array_equal(r.output, refs[i])
                    for i, r in enumerate(res))
        paged_concurrent = pool.high_water // pages_per_long
    finally:
        srv.stop(timeout=30)
    out["concurrency"] = {
        "static_max_long_decodes": static_max,
        "paged_long_decodes_sustained": paged_concurrent,
        "paged_concurrency_x": round(paged_concurrent
                                     / max(1, static_max), 2),
        "paged_outputs_exact": bool(exact),
        "pages_per_long_decode": pages_per_long,
        "pool_leak_free": pool.free_pages == pool.num_pages,
    }

    # -- part B: the Zipf load ramp, three passes --------------------
    phases = ((("low", low_rps), ("high", high_rps),
               ("idle", 0.0)))

    def pct_ms(vals, q):
        if not vals:
            return None
        hist = Histogram(window=max(1, len(vals)))
        for v in vals:
            hist.observe(v)
        p = hist.quantile(q)
        return round(p * 1e3, 3) if p is not None else None

    def run_ramp(fleet, asc=None):
        per_phase, timeline, t0 = [], [], time.perf_counter()
        t0_mono = time.monotonic()   # the autoscaler's clock basis
        stop_ctl = threading.Event()

        def controller():
            while not stop_ctl.wait(eval_interval_s):
                if asc is not None:
                    try:
                        asc.evaluate_once()
                    except Exception:   # control must not kill load
                        pass
                counts = {"prefill": 0, "decode": 0, "both": 0}
                for s in list(fleet.servers.values()):
                    counts[getattr(s, "role", "both")] += 1
                timeline.append(dict(
                    t=round(time.perf_counter() - t0, 2), **counts))

        ctl = threading.Thread(target=controller, daemon=True)
        ctl.start()
        try:
            for name, rate in phases:
                futs, n = [], 0
                p0 = time.perf_counter()
                dur = phase_s if rate else 2 * phase_s
                while True:
                    elapsed = time.perf_counter() - p0
                    if elapsed >= dur:
                        break
                    while n < int(elapsed * rate):
                        i = int(rng.choice(users, p=probs))
                        futs.append(fleet.submit_generate(
                            prompts[i], max_new,
                            deadline_s=deadline_s))
                        n += 1
                    time.sleep(0.002)
                per_phase.append((name, futs))
        finally:
            done = [(name, [f.result(timeout=300) for f in futs])
                    for name, futs in per_phase]
            stop_ctl.set()
            ctl.join(timeout=10)
        stats = {}
        all_res = []
        for name, res in done:
            all_res.extend(res)
            ok_lat = [r.latency_s for r in res if r.ok]
            shed = sum(r.status is Status.OVERLOADED for r in res)
            stats[name] = {
                "offered": len(res), "ok": sum(r.ok for r in res),
                "shed": shed,
                "shed_rate": round(shed / len(res), 4) if res else 0.0,
                "latency_p50_ms": pct_ms(ok_lat, 0.50),
                "latency_p99_ms": pct_ms(ok_lat, 0.99),
            }
        offered = len(all_res)
        shed = sum(r.status is Status.OVERLOADED for r in all_res)
        stats["total"] = {
            "offered": offered,
            "ok": sum(r.ok for r in all_res),
            "shed": shed,
            "shed_rate": round(shed / offered, 4) if offered else 0.0,
            "all_resolved_typed": all(r.status is not None
                                      for r in all_res),
        }
        return stats, timeline, t0_mono

    def phase_metrics(fleet):
        """TTFT from the router (disagg records it at first-token),
        TPOT from the worst decode replica."""
        r = fleet.router.metrics.snapshot()
        tpots = [s.metrics.snapshot() for s in fleet.servers.values()
                 if getattr(s, "role", "both") in ("decode", "both")]

        def ms(v):
            return round(v * 1e3, 3) if v is not None else None

        def worst(key):
            vals = [t[key] for t in tpots if t[key] is not None]
            return ms(max(vals)) if vals else None

        return {"ttft_p50_ms": ms(r["ttft_p50_s"]),
                "ttft_p99_ms": ms(r["ttft_p99_s"]),
                "tpot_p50_ms": worst("tpot_p50_s"),
                "tpot_p99_ms": worst("tpot_p99_s")}

    def make_paged_fleet():
        # max_workers sized ABOVE the offered concurrency: the load
        # must reach the replicas (and their published signals), not
        # queue invisibly in the router's dispatch pool
        return ServingFleet.build(
            model, n_replicas=2, roles=("prefill", "decode"),
            kv_pages=num_pages, kv_page_size=page_size,
            server_kw=dict(max_batch=8, max_queue=max_queue),
            heartbeat_timeout=0.4, pump_interval_s=0.1,
            router_kw=dict(default_deadline_s=deadline_s,
                           disaggregate=True, max_workers=96))

    # pass 1: static-bucket baseline (unpaged, same replica count)
    fleet = ServingFleet.build(
        model, n_replicas=2,
        server_kw=dict(max_batch=8, max_queue=max_queue),
        heartbeat_timeout=0.4, pump_interval_s=0.1,
        router_kw=dict(default_deadline_s=deadline_s,
                       max_workers=96))
    fleet.start()
    try:
        warm = fleet.submit_generate(prompts[0], max_new)
        warm.result(timeout=300)
        stats, _, _ = run_ramp(fleet)
        lat = fleet.router.metrics.snapshot()

        def ms(v):
            return round(v * 1e3, 3) if v is not None else None

        # the unpaged path emits every token at once: its whole
        # latency IS its TTFT, and TPOT is unobservable
        out["static_pass"] = dict(
            stats, ttft_p50_ms=ms(lat["latency_p50_s"]),
            ttft_p99_ms=ms(lat["latency_p99_s"]),
            tpot_p50_ms=None, tpot_p99_ms=None)
    finally:
        fleet.stop(timeout=30)

    # pass 2: paged + disaggregated, fixed fleet
    fleet = make_paged_fleet()
    fleet.start()
    try:
        fleet.submit_generate(prompts[0], max_new).result(timeout=300)
        stats, _, _ = run_ramp(fleet)
        out["paged_pass"] = dict(stats, **phase_metrics(fleet))
    finally:
        fleet.stop(timeout=30)

    # pass 3: paged + autoscale
    fleet = make_paged_fleet()
    fleet.start()

    def factory(rid, role):
        return InferenceServer(
            model, name=rid, role=role, max_batch=8,
            max_queue=max_queue,
            kv_pool=KVPagePool.for_model(model, num_pages,
                                         page_size=page_size))

    asc = Autoscaler(fleet, factory, policy=AutoscalePolicy(
        min_replicas=1, max_replicas=3, p99_high_s=0.25,
        shed_high=0.01, queue_high=3, sustain=2,
        p99_idle_s=0.05, queue_idle=2, idle_sustain=2,
        cooldown_s=cooldown_s, idle_requests_delta=1,
        drain_timeout_s=10.0))
    try:
        fleet.submit_generate(prompts[0], max_new).result(timeout=300)
        stats, timeline, t0_mono = run_ramp(fleet, asc=asc)
        out["autoscale_pass"] = dict(stats, **phase_metrics(fleet))
        decode_counts = [t["decode"] for t in timeline]
        # ≤ 1 scale direction flip per ramp phase: map each decision
        # onto the ramp clock and walk phase boundaries (decisions
        # landing in the post-ramp drain tail count in the last phase)
        bounds, acc = [], 0.0
        for name, rate in phases:
            dur = phase_s if rate else 2 * phase_s
            bounds.append((name, acc, acc + dur))
            acc += dur
        rel = [(d["at"] - t0_mono, d["direction"])
               for d in asc.decisions]
        flips = {}
        for i, (name, lo, hi) in enumerate(bounds):
            last = i == len(bounds) - 1
            dirs = [direction for t, direction in rel
                    if lo <= t and (last or t < hi)]
            flips[name] = sum(1 for a, b in zip(dirs, dirs[1:])
                              if a != b)
        scaled_up = bool(decode_counts) \
            and max(decode_counts) > decode_counts[0]
        out["autoscale"] = {
            "timeline": timeline,
            "decisions": [
                {k: d[k] for k in ("pool", "direction", "replica",
                                   "reason")}
                for d in asc.decisions],
            "decode_replicas_min": min(decode_counts)
            if decode_counts else None,
            "decode_replicas_max": max(decode_counts)
            if decode_counts else None,
            "scaled_up": scaled_up,
            "scaled_back_down": scaled_up and decode_counts
            and decode_counts[-1] < max(decode_counts),
            "direction_flips_per_phase": flips,
            "max_flips_in_a_phase": max(flips.values())
            if flips else 0,
            "cooldown_s": cooldown_s,
        }
        out["autoscale"]["shed_rate_vs_fixed"] = {
            "fixed": out["paged_pass"]["total"]["shed_rate"],
            "autoscaled": stats["total"]["shed_rate"],
            "no_worse": stats["total"]["shed_rate"]
            <= out["paged_pass"]["total"]["shed_rate"] + 1e-9,
        }
    finally:
        fleet.stop(timeout=30)

    out["ttft_p99_ms"] = (out.get("paged_pass") or {}).get(
        "ttft_p99_ms")
    out["ttft_p50_ms"] = (out.get("paged_pass") or {}).get(
        "ttft_p50_ms")
    out["tpot_p99_ms"] = (out.get("paged_pass") or {}).get(
        "tpot_p99_ms")
    out["tpot_p50_ms"] = (out.get("paged_pass") or {}).get(
        "tpot_p50_ms")
    out["paged_concurrency_x"] = out["concurrency"][
        "paged_concurrency_x"]
    out["shed_rate"] = (out.get("autoscale_pass")
                        or {}).get("total", {}).get("shed_rate")
    return out


def run_disagg_bench() -> None:
    """--disagg mode: paged-vs-static + the three-pass Zipf ramp over
    a mixed prefill/decode fleet on CPU (control-plane numbers), write
    SERVING_r03.json, print the one JSON line."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "disagg", "backend": "cpu",
           "measured_at": _utc_now()}
    try:
        out.update(_disagg_measurements())
        p99 = out.get("ttft_p99_ms")
        out.update({
            "metric": "disaggregated serving TTFT p99",
            "value": p99 if p99 is not None else 0.0,
            "unit": "ms",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "disaggregated serving TTFT p99",
                    "value": 0.0, "unit": "ms"})
    try:
        with open(os.path.join(_here(), DISAGG_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Elastic leg: chaos run through the shrink-to-survivors coordinator
# --------------------------------------------------------------------------

ELASTIC_RESULT = "ELASTIC_r01.json"


def _elastic_measurements(max_steps: int = 36, die_at: int = 10,
                          rejoin_at: int = 24, n_hosts: int = 4,
                          batch: int = 64, pace_s: float = 0.05):
    """Simulated-cluster chaos leg: a 4-"host" gang (one coordinator per
    fake host, resilience.elastic.SimulatedHost) trains a small
    regression under DistriOptimizer with an injected host death at step
    ``die_at`` and a rejoin at ``rejoin_at``.  Measures steady-state
    steps/sec before the fault, the recovery wall-clock
    (fault detection -> first post-restore step), and the post-shrink
    throughput.  Control-plane numbers, meaningful on any backend."""
    import tempfile

    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import Sample, array
    from bigdl_tpu.optim import SGD, max_iteration, several_iteration
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.resilience import (CollectiveWatchdog, ElasticContext,
                                      ElasticCoordinator, InMemoryKV,
                                      RetryPolicy, SimulatedHost,
                                      StepTimeEstimator, faults)

    rng = np.random.RandomState(0)
    x = rng.rand(256, 4).astype(np.float32)
    w = np.array([[1.5], [-2.0], [0.5], [3.0]], np.float32)
    y = (x @ w + 0.7).astype(np.float32)
    samples = [Sample(x[i], y[i]) for i in range(len(x))]

    kv = InMemoryKV()
    hosts = [f"host{i}" for i in range(n_hosts)]
    coord = ElasticCoordinator("host0", kv, heartbeat_timeout=0.3)
    coord.bootstrap(hosts)
    sims = [SimulatedHost(h, kv, heartbeat_timeout=0.3,
                          die_at_leader_step=(die_at if h == "host2"
                                              else None),
                          rejoin_at_leader_step=(rejoin_at
                                                 if h == "host2" else None))
            for h in hosts[1:]]
    ctx = ElasticContext(
        coord,
        watchdog=CollectiveWatchdog(StepTimeEstimator(
            floor=0.75, multiplier=4.0, min_samples=3)),
        rendezvous_timeout=3.0, regrow_after_steps=4)

    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = DistriOptimizer(model, array(samples), nn.MSECriterion(),
                          batch_size=batch)
    opt.set_optim_method(SGD(learning_rate=0.3))
    opt.set_end_when(max_iteration(max_steps))
    ckpt = tempfile.mkdtemp(prefix="bench_elastic_")
    opt.set_checkpoint(ckpt, several_iteration(1))
    opt.set_retry_policy(RetryPolicy(max_retries=20, backoff_base=0.01,
                                     backoff_max=0.05))
    opt.set_elastic(ctx)

    t0 = time.monotonic()
    # pace the driver so heartbeat windows are meaningful on fast CPUs
    with faults.delay_host("host0", pace_s, at_step=1):
        for s in sims:
            s.start()
        try:
            opt.optimize()
        finally:
            for s in sims:
                s.stop()
    wall = time.monotonic() - t0

    def rate(entries):
        # median step time, excluding each incarnation's first (compile)
        # step; entries are (incarnation, step, t_end, dt)
        dts = sorted(dt for _, _, _, dt in entries[1:])
        if not dts:
            return None
        return round(1.0 / max(dts[len(dts) // 2], 1e-9), 2)

    log = ctx.step_log
    incs = [e[0] for e in log]
    before = [e for e in log if e[0] == incs[0]]
    shrunk = [e for e in log if e[0] != incs[0]]  # post-first-recovery
    return {
        "hosts": n_hosts,
        "steps": int(opt.optim_method.state["neval"] - 1),
        "wall_clock_s": round(wall, 2),
        "shards_before": ctx.shard_history[0] if ctx.shard_history else None,
        "shards_min": min(ctx.shard_history) if ctx.shard_history else None,
        "shards_after": (ctx.shard_history[-1]
                         if ctx.shard_history else None),
        "steps_per_sec_before_fault": rate(before),
        "steps_per_sec_after_shrink": rate(shrunk),
        "recovery_wall_clock_s": (round(ctx.recoveries[0], 3)
                                  if ctx.recoveries else None),
        "incarnations": ctx.incarnation_changes,
        "evictions": ctx.evictions,
        "watchdog_trips": ctx.watchdog.trips,
        "final_loss": round(float(opt.optim_method.state["loss"]), 5),
    }


def run_elastic_bench() -> None:
    """--elastic mode: run the chaos leg on the virtual-CPU topology,
    write ELASTIC_r01.json, print the one JSON line."""
    # the multi-shard simulation needs >1 device; same fallback idiom as
    # __graft_entry__.dryrun_multichip (set flags BEFORE backend init)
    import re

    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", "")).strip()
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "elastic", "backend": "cpu",
           "measured_at": _utc_now()}
    try:
        out.update(_elastic_measurements())
        rec = out.get("recovery_wall_clock_s")
        out.update({
            "metric": "elastic shrink-to-survivors recovery wall-clock",
            "value": rec if rec is not None else 0.0,
            "unit": "s",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "elastic shrink-to-survivors recovery "
                              "wall-clock",
                    "value": 0.0, "unit": "s"})
    try:
        with open(os.path.join(_here(), ELASTIC_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Integrity leg: fingerprint/vote overhead + SDC detection latency
# --------------------------------------------------------------------------

INTEGRITY_RESULT = "INTEGRITY_r01.json"


def _fingerprint_overhead(steps: int = 60, batch: int = 64,
                          param_crc_every: int = 4):
    """Wall-clock cost of the flight recorder at its default cadence:
    the same LocalOptimizer run twice (fresh model each time, so both
    passes pay one compile), bare vs. recording loss/grad-norm bits +
    batch crc every step and a param-tree crc every
    ``param_crc_every`` steps."""
    import tempfile

    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import Sample, array
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.resilience import FlightRecorder

    rng = np.random.RandomState(0)
    x = rng.rand(256, 16).astype(np.float32)
    w = rng.rand(16, 1).astype(np.float32)
    y = (x @ w + 0.3).astype(np.float32)
    samples = [Sample(x[i], y[i]) for i in range(len(x))]

    def run(recorder):
        model = nn.Sequential(nn.Linear(16, 32), nn.Tanh(),
                              nn.Linear(32, 1))
        opt = LocalOptimizer(model, array(samples), nn.MSECriterion(),
                             batch_size=batch)
        opt.set_optim_method(SGD(learning_rate=0.1))
        opt.set_end_when(max_iteration(steps))
        if recorder is not None:
            opt.set_flight_recorder(recorder)
        t0 = time.monotonic()
        opt.optimize()
        return time.monotonic() - t0

    bare = run(None)
    jpath = os.path.join(tempfile.mkdtemp(prefix="bench_integrity_"),
                         "journal.jsonl")
    with FlightRecorder(jpath, param_crc_every=param_crc_every) as rec:
        recorded = run(rec)
    pct = 100.0 * (recorded - bare) / max(bare, 1e-9)
    return {"fingerprint_steps": steps,
            "fingerprint_param_crc_every": param_crc_every,
            "bare_wall_s": round(bare, 3),
            "recorded_wall_s": round(recorded, 3),
            "fingerprint_overhead_pct": round(pct, 1)}


def _integrity_measurements(max_steps: int = 30, corrupt_at: int = 9,
                            cadence: int = 4, n_hosts: int = 4,
                            batch: int = 64, pace_s: float = 0.05):
    """SDC chaos leg: the elastic leg's 4-"host" simulated gang, but the
    injected fault is `corrupt_gradient` on host2 — from step
    ``corrupt_at`` its published integrity checksums are silently wrong.
    The cross-host vote at ``cadence`` must flag it, evict it, and the
    survivors keep training.  Measures the detection latency in steps
    (vote cadence bounds it), the vote wall-clock overhead %, and the
    flight-recorder overhead from :func:`_fingerprint_overhead`."""
    import tempfile

    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import Sample, array
    from bigdl_tpu.optim import SGD, max_iteration, several_iteration
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.resilience import (CollectiveWatchdog, ElasticContext,
                                      ElasticCoordinator, InMemoryKV,
                                      RetryPolicy, SimulatedHost,
                                      StepTimeEstimator, faults)

    rng = np.random.RandomState(0)
    x = rng.rand(256, 4).astype(np.float32)
    w = np.array([[1.5], [-2.0], [0.5], [3.0]], np.float32)
    y = (x @ w + 0.7).astype(np.float32)
    samples = [Sample(x[i], y[i]) for i in range(len(x))]

    kv = InMemoryKV()
    hosts = [f"host{i}" for i in range(n_hosts)]
    coord = ElasticCoordinator("host0", kv, heartbeat_timeout=0.3)
    coord.bootstrap(hosts)
    sims = [SimulatedHost(h, kv, heartbeat_timeout=0.3)
            for h in hosts[1:]]
    ctx = ElasticContext(
        coord,
        watchdog=CollectiveWatchdog(StepTimeEstimator(
            floor=0.75, multiplier=4.0, min_samples=3)),
        rendezvous_timeout=3.0, regrow_after_steps=1000,
        integrity_cadence=cadence)

    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = DistriOptimizer(model, array(samples), nn.MSECriterion(),
                          batch_size=batch)
    opt.set_optim_method(SGD(learning_rate=0.3))
    opt.set_end_when(max_iteration(max_steps))
    ckpt = tempfile.mkdtemp(prefix="bench_integrity_")
    opt.set_checkpoint(ckpt, several_iteration(1))
    opt.set_retry_policy(RetryPolicy(max_retries=20, backoff_base=0.01,
                                     backoff_max=0.05))
    opt.set_elastic(ctx)

    t0 = time.monotonic()
    with faults.corrupt_gradient("host2", at_step=corrupt_at), \
            faults.delay_host("host0", pace_s, at_step=1):
        for s in sims:
            s.start()
        try:
            opt.optimize()
        finally:
            for s in sims:
                s.stop()
    wall = time.monotonic() - t0

    detected = (ctx.sdc_detected_steps[0]
                if ctx.sdc_detected_steps else None)
    vote_wall = sum(dt for _, dt in ctx.vote_log)
    out = {
        "hosts": n_hosts,
        "steps": int(opt.optim_method.state["neval"] - 1),
        "wall_clock_s": round(wall, 2),
        "integrity_cadence": cadence,
        "sdc_injected_at": corrupt_at,
        "sdc_detected_at": detected,
        "sdc_detection_latency_steps": (None if detected is None
                                        else detected - corrupt_at),
        "sdc_votes": ctx.sdc_votes,
        "sdc_evictions": ctx.sdc_evictions,
        "evicted_hosts": list(ctx.evicted_hosts),
        "vote_overhead_pct": round(100.0 * vote_wall / max(wall, 1e-9),
                                   1),
        "final_loss": round(float(opt.optim_method.state["loss"]), 5),
    }
    out.update(_fingerprint_overhead())
    return out


def run_integrity_bench() -> None:
    """--integrity mode: run the SDC chaos leg + fingerprint overhead
    probe on the virtual-CPU topology, write INTEGRITY_r01.json, print
    the one JSON line."""
    import re

    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", "")).strip()
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "integrity", "backend": "cpu",
           "measured_at": _utc_now()}
    try:
        out.update(_integrity_measurements())
        lat = out.get("sdc_detection_latency_steps")
        out.update({
            "metric": "SDC detection latency at default vote cadence",
            "value": float(lat) if lat is not None else 0.0,
            "unit": "steps",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "SDC detection latency at default vote "
                              "cadence",
                    "value": 0.0, "unit": "steps"})
    try:
        with open(os.path.join(_here(), INTEGRITY_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Telemetry leg: tracer+registry overhead on the compiled step loop
# --------------------------------------------------------------------------

TELEMETRY_RESULT = "TELEMETRY_r01.json"


def _telemetry_measurements(steps: int = 300, batch: int = 512,
                            hidden: int = 128, repeats: int = 3,
                            goodput_steps: int = 1200,
                            goodput_hidden: int = 4096,
                            goodput_batch: int = 1024,
                            checkpoint_every: int = 150):
    """Cost of the full telemetry spine (registry histograms + goodput
    ledger + tracer spans at the default every-step cadence) on the
    compiled step loop: the same LocalOptimizer workload run
    alternately bare and with a Telemetry bundle attached (fresh model
    each pass, so every pass pays exactly one compile), overhead taken
    between the MIN walls over ``repeats`` alternating pairs (min, not
    mean: scheduler noise only ever adds time).  The defaults run
    enough post-compile steps that the steady-state loop dominates the
    one compile, so the delta measures the per-step tax, not compile
    jitter.  Plus per-op microbenches pinning the primitive costs the
    loop pays per step.

    The **goodput leg** then runs the overlap engine under realistic
    conditions — checkpointing ENABLED at ``checkpoint_every``, the
    default double-buffered infeed, async snapshot-then-write — for
    ``goodput_steps`` steps of a compute-bound model, and reports the
    ledger verbatim (including the one XLA compile): the judged
    ``goodput_productive_fraction`` (target >=0.95 vs the 0.303 the
    pre-overlap loop measured), ``data_stall_s`` (only real
    empty-buffer waits count) and ``checkpoint_blocked_s``."""
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import Sample, array
    from bigdl_tpu.optim import SGD, max_iteration, several_iteration
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.telemetry import MetricsRegistry, Telemetry, Tracer

    import numpy as np

    import logging

    rng = np.random.RandomState(0)
    x = rng.rand(1024, 16).astype(np.float32)
    w = rng.rand(16, 1).astype(np.float32)
    y = (x @ w + 0.3).astype(np.float32)
    samples = [Sample(x[i], y[i]) for i in range(len(x))]
    data = array(samples)

    # the per-iteration INFO line is console I/O, not training work —
    # it would dominate "idle" at these step times and measure the
    # bench harness instead of the loop (restored after the leg)
    bigdl_log = logging.getLogger("bigdl_tpu")
    prev_level = bigdl_log.level
    bigdl_log.setLevel(logging.WARNING)

    def run(telemetry, n_steps=steps, width=hidden, ckpt_dir=None):
        model = nn.Sequential(nn.Linear(16, width), nn.Tanh(),
                              nn.Linear(width, 1))
        opt = LocalOptimizer(model, data, nn.MSECriterion(),
                             batch_size=batch)
        opt.set_optim_method(SGD(learning_rate=0.01))
        opt.set_end_when(max_iteration(n_steps))
        if ckpt_dir is not None:
            opt.set_checkpoint(ckpt_dir,
                               several_iteration(checkpoint_every))
        if telemetry is not None:
            opt.set_telemetry(telemetry)
        t0 = time.monotonic()
        opt.optimize()
        return time.monotonic() - t0

    # --- goodput leg: checkpointing on, overlap engine judged --------
    # runs FIRST (before the overhead pairs): the judged fraction must
    # measure the loop, not collector pauses over the pairs' garbage
    import shutil
    import tempfile

    # realistic epoch length (32 steps at batch 512): two-step epochs
    # would measure the epoch-boundary cold buffer 1250 times instead
    # of the steady-state loop.  The goodput dataset is PRE-BATCHED
    # MiniBatches (the production infeed layout — record files decode
    # to batches ahead of time, INFEED_REHEARSAL.json): on this
    # container every host-side millisecond shares the single CPU core
    # with the "device" compute, so per-record stacking in the producer
    # would serialize against the step and misread as overhead of the
    # overlap engine itself
    from bigdl_tpu.dataset.sample import MiniBatch

    xg = rng.rand(16384, 16).astype(np.float32)
    yg = (xg @ w + 0.3).astype(np.float32)
    goodput_data = array(
        [MiniBatch(xg[i:i + goodput_batch], yg[i:i + goodput_batch])
         for i in range(0, len(xg), goodput_batch)])

    def run_goodput(telemetry, ckpt_dir):
        model = nn.Sequential(nn.Linear(16, goodput_hidden), nn.Tanh(),
                              nn.Linear(goodput_hidden, 1))
        opt = LocalOptimizer(model, goodput_data, nn.MSECriterion(),
                             batch_size=goodput_batch)
        opt.set_optim_method(SGD(learning_rate=0.01))
        opt.set_end_when(max_iteration(goodput_steps))
        opt.set_checkpoint(ckpt_dir,
                           several_iteration(checkpoint_every))
        opt.set_telemetry(telemetry)
        opt.optimize()

    ckpt_dir = tempfile.mkdtemp(prefix="bench_telemetry_ckpt_")
    tm_gp = Telemetry(registry=MetricsRegistry())
    try:
        run_goodput(tm_gp, ckpt_dir)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    gp_ck = tm_gp.ledger.snapshot()

    # --- overhead pairs: spine tax on the compiled step loop ---------
    bare_walls, tel_walls = [], []
    tm = None
    try:
        for _ in range(max(1, repeats)):
            bare_walls.append(run(None))
            tm = Telemetry(registry=MetricsRegistry())
            tel_walls.append(run(tm))
    finally:
        bigdl_log.setLevel(prev_level)
    bare, tel = min(bare_walls), min(tel_walls)
    pct = 100.0 * (tel - bare) / max(bare, 1e-9)

    # per-op costs: what one driver iteration actually pays
    reg = MetricsRegistry()
    hist = reg.histogram("bench_seconds", window=1024)
    cnt = reg.counter("bench_total")
    n = 50_000
    t0 = time.perf_counter()
    for i in range(n):
        hist.observe(i * 1e-6)
    observe_ns = (time.perf_counter() - t0) / n * 1e9
    t0 = time.perf_counter()
    for _ in range(n):
        cnt.inc()
    counter_ns = (time.perf_counter() - t0) / n * 1e9
    tr = Tracer(capacity=1024)
    t0 = time.perf_counter()
    for i in range(n):
        tr.record("step", "step", i * 1e-3, 1e-3)
    span_ns = (time.perf_counter() - t0) / n * 1e9

    secs = gp_ck.get("seconds") or {}
    wall = float(gp_ck.get("wall_s") or 0.0)
    return {
        "telemetry_steps": steps,
        "telemetry_batch": batch,
        "trace_every": 1,
        "bare_wall_s": round(bare, 3),
        "telemetry_wall_s": round(tel, 3),
        "overhead_pct": round(pct, 2),
        "histogram_observe_ns": round(observe_ns, 0),
        "counter_inc_ns": round(counter_ns, 0),
        "tracer_record_ns": round(span_ns, 0),
        # the judged goodput family comes from the checkpoint-enabled
        # goodput leg (overlap engine on; ledger reported verbatim,
        # compile included)
        "goodput_steps": goodput_steps,
        "goodput_hidden": goodput_hidden,
        "goodput_checkpoint_every": checkpoint_every,
        "goodput_wall_s": round(wall, 3),
        "goodput_accounted_fraction": round(
            float(gp_ck.get("accounted_fraction", 0.0)), 4),
        "goodput_productive_fraction": round(
            float(gp_ck.get("productive_fraction", 0.0)), 4),
        "goodput_checkpoint_fraction": round(
            float(secs.get("checkpoint", 0.0)) / wall if wall else 0.0,
            5),
        "data_stall_s": round(float(secs.get("data_stall", 0.0)), 4),
        "checkpoint_s": round(float(secs.get("checkpoint", 0.0)), 4),
        "checkpoint_blocked_s": round(float(
            tm_gp.checkpoint_blocked_seconds.sum), 4),
        "compile_s": round(float(secs.get("compile", 0.0)), 4),
        "idle_s": round(float(secs.get("idle", 0.0)), 4),
        "trace_events": len(tm.tracer.spans()) if tm is not None else 0,
    }


def run_telemetry_bench() -> None:
    """--telemetry mode: measure the spine's overhead on the compiled
    step loop (target <3% at the default every-step tracing cadence),
    write TELEMETRY_r01.json, print the one JSON line."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "telemetry", "backend": "cpu",
           "measured_at": _utc_now()}
    try:
        out.update(_telemetry_measurements())
        out.update({
            "metric": "telemetry spine overhead on the compiled "
                      "step loop",
            "value": out.get("overhead_pct", 0.0),
            "unit": "%",
            "target": "<3%",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "telemetry spine overhead on the "
                              "compiled step loop",
                    "value": 0.0, "unit": "%", "target": "<3%"})
    try:
        with open(os.path.join(_here(), TELEMETRY_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Sharding leg: the unified plan engine on a composed forced-host mesh
# --------------------------------------------------------------------------

SHARDING_RESULT = "SHARDING_r01.json"


def _sharding_measurements(composed_steps: int = 16, fsdp_steps: int = 10,
                           batch: int = 8):
    """The plan-engine leg (ISSUE 8), on 8 forced-host CPU devices:

    * **composed mesh** — a TransformerLM trained over data=2 x pipe=2
      x model=2 composed on ONE mesh through the one
      ``compile_step_with_plan`` builder (steps/sec post-compile, loss
      descending — the 3-D composition the four hand-wired paths could
      never express);
    * **FSDP** — a model whose replicated tree would occupy every
      device in full trains with data-axis param sharding instead;
      the judged number is the measured per-device addressable param
      fraction (~1/8 + replicated crumbs) from the telemetry registry.
    """
    import jax
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import Sample
    from bigdl_tpu.dataset.dataset import array
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.telemetry import MetricsRegistry, Telemetry
    from bigdl_tpu.utils.rng import RNG
    from jax.sharding import Mesh

    import logging

    if jax.device_count() < 8:
        raise RuntimeError(
            f"sharding leg needs 8 forced-host devices, have "
            f"{jax.device_count()}")
    bigdl_log = logging.getLogger("bigdl_tpu")
    prev_level = bigdl_log.level
    bigdl_log.setLevel(logging.WARNING)

    class _Losses:
        def __init__(self):
            self.values = []

        def add_scalar(self, name, value, step):
            if name == "Loss":
                self.values.append(float(value))

    def run(model, mesh, steps, data, criterion, lr, fsdp=None):
        tm = Telemetry(registry=MetricsRegistry())
        rec = _Losses()
        opt = DistriOptimizer(model, data, criterion, batch_size=batch,
                              mesh=mesh)
        opt.set_optim_method(SGD(learning_rate=lr))
        opt.set_end_when(max_iteration(steps))
        opt.set_telemetry(tm)
        opt.set_train_summary(rec)
        if fsdp:
            opt.set_fsdp(fsdp)
        t0 = time.monotonic()
        opt.optimize()
        wall = time.monotonic() - t0
        compile_s = float(tm.compile_seconds.sum)
        sps = (steps - 1) / max(wall - compile_s, 1e-9)
        snap = tm.registry.snapshot()["metrics"]

        def gauge(name):
            series = (snap.get(name) or {}).get("series") or []
            return float(series[0]["value"]) if series else None

        return {"wall_s": round(wall, 3), "compile_s": round(compile_s, 3),
                "steps_per_sec": round(sps, 3), "losses": rec.values,
                "param_bytes_per_device": gauge(
                    "bigdl_plan_param_bytes_per_device"),
                "param_bytes_total": gauge("bigdl_plan_param_bytes_total")}

    try:
        # --- composed data=2 x pipe=2 x model=2 ------------------------
        V, T = 17, 8
        RNG().set_seed(6)
        lm = TransformerLM(V, embed_dim=8, num_heads=2, num_layers=2,
                           max_len=T, model_axis="model")
        rng = np.random.RandomState(3)
        seqs = rng.randint(1, V, (32, T + 1))
        lm_data = array([Sample(s[:-1].astype(np.float32),
                                (s[1:] + 1).astype(np.float32))
                         for s in seqs])
        crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True)
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                    ("data", "pipe", "model"))
        composed = run(lm, mesh, composed_steps, lm_data, crit, lr=0.5)

        # --- FSDP on the full data mesh --------------------------------
        RNG().set_seed(4)
        mlp = nn.Sequential(nn.Linear(256, 512), nn.Tanh(),
                            nn.Linear(512, 512), nn.Tanh(),
                            nn.Linear(512, 2), nn.LogSoftMax())
        rng = np.random.RandomState(0)
        xs = rng.rand(64, 256).astype(np.float32)
        ys = (1 + (xs.sum(1) > 128)).astype(np.float32)
        mlp_data = array([Sample(x, y) for x, y in zip(xs, ys)])
        fsdp = run(mlp, None, fsdp_steps, mlp_data,
                   nn.ClassNLLCriterion(), lr=0.1, fsdp=64 * 1024)
    finally:
        bigdl_log.setLevel(prev_level)

    frac = None
    if fsdp["param_bytes_per_device"] and fsdp["param_bytes_total"]:
        frac = fsdp["param_bytes_per_device"] / fsdp["param_bytes_total"]
    cl = composed["losses"]
    return {
        "devices": 8,
        "composed_mesh": "data=2 x pipe=2 x model=2",
        "composed_steps": composed_steps,
        "composed_steps_per_sec": composed["steps_per_sec"],
        "composed_wall_s": composed["wall_s"],
        "composed_compile_s": composed["compile_s"],
        "composed_loss_first": round(cl[0], 5) if cl else None,
        "composed_loss_last": round(cl[-1], 5) if cl else None,
        "composed_loss_descending": bool(cl and cl[-1] < cl[0]),
        "fsdp_steps": fsdp_steps,
        "fsdp_steps_per_sec": fsdp["steps_per_sec"],
        "fsdp_param_bytes_per_device": fsdp["param_bytes_per_device"],
        "fsdp_param_bytes_total": fsdp["param_bytes_total"],
        "fsdp_param_bytes_frac": round(frac, 4) if frac else None,
        "fsdp_loss_descending": bool(
            fsdp["losses"] and fsdp["losses"][-1] < fsdp["losses"][0]),
    }


def run_sharding_bench() -> None:
    """--sharding mode: run the composed-mesh + FSDP plan-engine legs
    on 8 forced-host CPU devices, write SHARDING_r01.json, print the
    one JSON line."""
    # must run before first backend use: the host-platform device count
    # is an XLA client flag, not a jax config knob
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "sharding", "backend": "cpu",
           "forced_host_devices": 8, "measured_at": _utc_now()}
    try:
        out.update(_sharding_measurements())
        out.update({
            "metric": "composed-mesh (data x pipe x model) plan-engine "
                      "throughput",
            "value": out.get("composed_steps_per_sec", 0.0),
            "unit": "steps/sec",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "composed-mesh (data x pipe x model) "
                              "plan-engine throughput",
                    "value": 0.0, "unit": "steps/sec"})
    try:
        with open(os.path.join(_here(), SHARDING_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# DLRM leg: sharded-embedding recommendation workload, sparse vs dense
# gradient transport (ISSUE 10)
# --------------------------------------------------------------------------

DLRM_RESULT = "DLRM_r01.json"


def _dlrm_measurements(steps: int = 24, batch: int = 256,
                       table_sizes=(65536, 32768, 8192, 1024, 256),
                       embed_dim: int = 16, n_records: int = 2048,
                       zipf_exponent: float = 1.1,
                       shard_min_bytes: int = 512 * 1024,
                       lr: float = 0.5):
    """The sparsity-aware transport leg (ISSUE 10), on 8 forced-host
    CPU devices over a Zipf rank-``zipf_exponent`` clickstream:

    * **sparse pass** — the derived plan row-shards every table at or
      above ``shard_min_bytes`` over the data axis (total table bytes
      exceed the pretend per-device budget of total/2 — the FSDP-style
      proof) and ships the replicated tables' gradients as
      ``(row_indices, row_values)``;
    * **dense pass** — the SAME model under an explicit
      replicate-everything plan: every table's gradient rides the
      dense all-reduce (the transport the reference framework
      hard-wired).

    Judged numbers: measured collective bytes/step (the plan-derived
    ``bigdl_perf_collective_bytes`` gauge — sparse transport accounted
    as actual index+value bytes) with its reduction ratio, and
    steps/sec for both passes with the loss descending."""
    import jax
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import ZipfClickstream
    from bigdl_tpu.models.dlrm import DLRM
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.parallel.plan import Plan, Rule
    from bigdl_tpu.telemetry import MetricsRegistry, Telemetry
    from bigdl_tpu.utils.rng import RNG
    from jax.sharding import PartitionSpec as P

    import logging

    if jax.device_count() < 8:
        raise RuntimeError(
            f"dlrm leg needs 8 forced-host devices, have "
            f"{jax.device_count()}")
    bigdl_log = logging.getLogger("bigdl_tpu")
    prev_level = bigdl_log.level
    bigdl_log.setLevel(logging.WARNING)
    # the trace-profiled iteration parses an xplane dump whose size
    # scales with the program's op count — on the sparse program that
    # one iteration costs seconds of pure measurement overhead, so the
    # judged steps/sec comparison runs unprofiled on BOTH passes
    prev_profile = os.environ.get("BIGDL_METRICS_PROFILEINTERVAL")
    os.environ["BIGDL_METRICS_PROFILEINTERVAL"] = "0"

    class _Losses:
        def __init__(self):
            self.values = []

        def add_scalar(self, name, value, step):
            if name == "Loss":
                self.values.append(float(value))

    table_sizes = tuple(int(v) for v in table_sizes)
    table_bytes = sum(v * embed_dim * 4 for v in table_sizes)

    def run(plan):
        RNG().set_seed(7)
        model = DLRM(dense_dim=4, table_sizes=table_sizes,
                     embed_dim=embed_dim,
                     shard_min_bytes=shard_min_bytes)
        data = ZipfClickstream(n_records, table_sizes, dense_dim=4,
                               exponent=zipf_exponent)
        tm = Telemetry(registry=MetricsRegistry())
        rec = _Losses()
        opt = DistriOptimizer(model, data, nn.BCECriterion(),
                              batch_size=batch)
        opt.set_optim_method(SGD(learning_rate=lr))
        opt.set_end_when(max_iteration(steps))
        opt.set_telemetry(tm)
        opt.set_train_summary(rec)
        if plan is not None:
            opt.set_sharding_plan(plan)
        t0 = time.monotonic()
        opt.optimize()
        wall = time.monotonic() - t0
        compile_s = float(tm.compile_seconds.sum)
        sps = (steps - 1) / max(wall - compile_s, 1e-9)
        snap = tm.registry.snapshot()["metrics"]

        def gauge(name):
            series = (snap.get(name) or {}).get("series") or []
            return float(series[0]["value"]) if series else None

        return {"wall_s": round(wall, 3),
                "compile_s": round(compile_s, 3),
                "steps_per_sec": round(sps, 3), "losses": rec.values,
                "collective_bytes": gauge("bigdl_perf_collective_bytes"),
                "sparse_saved": gauge("bigdl_perf_sparse_bytes_saved"),
                "sharded_tables": list(model.sharded_tables)}

    try:
        sparse = run(None)  # derived plan: row sharding + sparse wire
        dense = run(Plan([Rule(".*", P())]))  # replicate-all, dense wire
    finally:
        bigdl_log.setLevel(prev_level)
        if prev_profile is None:
            os.environ.pop("BIGDL_METRICS_PROFILEINTERVAL", None)
        else:
            os.environ["BIGDL_METRICS_PROFILEINTERVAL"] = prev_profile

    ratio = None
    if sparse["collective_bytes"] and dense["collective_bytes"]:
        ratio = dense["collective_bytes"] / sparse["collective_bytes"]
    sl, dl = sparse["losses"], dense["losses"]
    return {
        "devices": 8,
        "mesh": "data=8",
        "zipf_exponent": zipf_exponent,
        "table_sizes": list(table_sizes),
        "embed_dim": embed_dim,
        "table_bytes_total": table_bytes,
        # the row-sharding forcing function: the full tables exceed a
        # pretend per-device budget of half their total (PR 8's
        # FSDP-style proof, applied to stateful tables)
        "per_device_table_budget_bytes": table_bytes // 2,
        "sharded_tables": sparse["sharded_tables"],
        "steps": steps, "batch": batch,
        "steps_per_sec": sparse["steps_per_sec"],
        "collective_bytes_per_step": sparse["collective_bytes"],
        "sparse_bytes_saved_per_step": sparse["sparse_saved"],
        "loss_first": round(sl[0], 5) if sl else None,
        "loss_last": round(sl[-1], 5) if sl else None,
        "loss_descending": bool(sl and sl[-1] < sl[0]),
        "dense_steps_per_sec": dense["steps_per_sec"],
        "dense_collective_bytes_per_step": dense["collective_bytes"],
        "dense_loss_descending": bool(dl and dl[-1] < dl[0]),
        "collective_bytes_reduction_x": (round(ratio, 2)
                                         if ratio else None),
        "sparse_compile_s": sparse["compile_s"],
        "dense_compile_s": dense["compile_s"],
    }


def run_dlrm_bench() -> None:
    """--dlrm mode: the sharded-embedding DLRM workload on 8 forced-
    host CPU devices — sparse vs dense gradient transport — writes
    DLRM_r01.json, prints the one JSON line."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "dlrm", "backend": "cpu",
           "forced_host_devices": 8, "measured_at": _utc_now()}
    try:
        out.update(_dlrm_measurements())
        out.update({
            "metric": "DLRM sparse-transport collective-bytes "
                      "reduction vs dense all-reduce",
            "value": out.get("collective_bytes_reduction_x") or 0.0,
            "unit": "x",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "DLRM sparse-transport collective-bytes "
                              "reduction vs dense all-reduce",
                    "value": 0.0, "unit": "x"})
    try:
        with open(os.path.join(_here(), DLRM_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Sync leg: relaxed synchrony — periodic averaging vs lockstep, and the
# relax-before-evict straggler story (ISSUE 15)
# --------------------------------------------------------------------------

SYNC_RESULT = "SYNC_r01.json"


def _sync_measurements(steps: int = 24, batch: int = 256,
                       n_records: int = 2048, period: int = 8,
                       straggler_steps: int = 14,
                       straggler: bool = True, lr: float = 0.1):
    """The relaxed-synchrony leg (ISSUE 15), on 8 forced-host CPU
    devices:

    * **lockstep vs periodic(k) pass** — the SAME MLP + seeded
      classification stream under the default lockstep plan and under
      ``Rule(".*", P(), sync=f"periodic({period})")``: judged
      steps/sec (post-compile) for both, plus the plan-derived
      ``bigdl_perf_collective_bytes`` gauge — periodic(k) must move
      >= 4x fewer collective bytes/step (accounting: the averaging
      ring / k), with loss descending in both passes;
    * **straggler pass** — a 3-host elastic gang with one chronic
      straggler (a simulated member publishing 1s step times), run
      twice: ``relax_before_evict`` (the averaging period widens, no
      eviction, training never stops) vs the eviction path (straggler
      voted out -> restore + mesh re-derivation + recompile).  Judged:
      wall clock first-loss -> last-loss for the same step budget and
      the time-to-loss-target advantage (relaxed reaches the eviction
      run's final loss in a fraction of its wall)."""
    import tempfile
    import jax
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import Sample
    from bigdl_tpu.dataset.dataset import array
    from bigdl_tpu.optim import SGD, max_iteration, several_iteration
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.parallel.plan import Plan, Rule
    from bigdl_tpu.telemetry import MetricsRegistry, Telemetry
    from bigdl_tpu.utils.rng import set_global_seed
    from jax.sharding import PartitionSpec as P

    import logging

    if jax.device_count() < 8:
        raise RuntimeError(
            f"sync leg needs 8 forced-host devices, have "
            f"{jax.device_count()}")
    bigdl_log = logging.getLogger("bigdl_tpu")
    prev_level = bigdl_log.level
    bigdl_log.setLevel(logging.ERROR)
    # the trace-profiled iteration's xplane parse costs seconds of
    # pure measurement overhead — every judged wall runs unprofiled
    prev_profile = os.environ.get("BIGDL_METRICS_PROFILEINTERVAL")
    os.environ["BIGDL_METRICS_PROFILEINTERVAL"] = "0"

    class _Losses:
        def __init__(self):
            self.values = []
            self.walls = []

        def add_scalar(self, name, value, step):
            if name == "Loss":
                self.values.append(float(value))
                self.walls.append(time.monotonic())

    rng = np.random.RandomState(3)
    xs = rng.rand(n_records, 64).astype(np.float32)
    ys = (1 + (xs.sum(1) > 32)).astype(np.float32)
    samples = [Sample(x, y) for x, y in zip(xs, ys)]

    def model_fn():
        return nn.Sequential(nn.Linear(64, 256), nn.Tanh(),
                             nn.Linear(256, 64), nn.Tanh(),
                             nn.Linear(64, 2), nn.LogSoftMax())

    def run(plan):
        set_global_seed(7)
        model = model_fn()
        tm = Telemetry(registry=MetricsRegistry())
        rec = _Losses()
        opt = DistriOptimizer(model, array(samples),
                              nn.ClassNLLCriterion(), batch_size=batch)
        opt.set_optim_method(SGD(learning_rate=lr))
        opt.set_end_when(max_iteration(steps))
        opt.set_telemetry(tm)
        opt.set_train_summary(rec)
        if plan is not None:
            opt.set_sharding_plan(plan)
        t0 = time.monotonic()
        opt.optimize()
        wall = time.monotonic() - t0
        compile_s = float(tm.compile_seconds.sum)
        sps = (steps - 1) / max(wall - compile_s, 1e-9)
        snap = tm.registry.snapshot()["metrics"]

        def gauge(name):
            series = (snap.get(name) or {}).get("series") or []
            return float(series[0]["value"]) if series else None

        return {"steps_per_sec": round(sps, 3), "losses": rec.values,
                "collective_bytes": gauge("bigdl_perf_collective_bytes"),
                "sync_saved": gauge("bigdl_perf_sync_bytes_saved")}

    def run_straggler(relax: bool):
        from bigdl_tpu.resilience import (CollectiveWatchdog,
                                          ElasticContext,
                                          ElasticCoordinator,
                                          InMemoryKV, RetryPolicy,
                                          SimulatedHost,
                                          StepTimeEstimator)
        from bigdl_tpu.resilience.elastic import StragglerPolicy

        kv = InMemoryKV()
        coord = ElasticCoordinator("host0", kv, heartbeat_timeout=0.3)
        coord.bootstrap(["host0", "host1", "host2"])
        sims = [SimulatedHost("host1", kv, heartbeat_timeout=0.3),
                SimulatedHost("host2", kv, heartbeat_timeout=0.3,
                              step_time=1.0)]
        pol = StragglerPolicy(skew_threshold=3.0, patience=2,
                              eviction_budget=1, sustain=0.0,
                              relax_before_evict=relax,
                              relax_factor=2.0, max_relax_rounds=8)
        ctx = ElasticContext(
            coord,
            watchdog=CollectiveWatchdog(StepTimeEstimator(
                floor=0.75, multiplier=4.0, min_samples=3,
                warmup_deadline=15.0)),
            straggler=pol, rendezvous_timeout=2.0,
            regrow_after_steps=10000)
        srng = np.random.RandomState(7)
        sxs = srng.rand(120, 8).astype(np.float32)
        sys_ = (1 + (sxs.sum(1) > 4)).astype(np.float32)
        ssamples = [Sample(x, y) for x, y in zip(sxs, sys_)]
        set_global_seed(7)
        model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                              nn.Linear(16, 2), nn.LogSoftMax())
        rec = _Losses()
        opt = DistriOptimizer(model, array(ssamples),
                              nn.ClassNLLCriterion(), batch_size=12)
        opt.set_optim_method(SGD(learning_rate=0.2))
        opt.set_sharding_plan(
            Plan([Rule(".*", P(), sync="periodic(2)")]))
        opt.set_end_when(max_iteration(straggler_steps))
        opt.set_checkpoint(tempfile.mkdtemp(prefix="sync_bench_"),
                           several_iteration(1))
        opt.set_retry_policy(RetryPolicy(max_retries=10,
                                         backoff_base=0.01,
                                         backoff_max=0.05))
        opt.set_elastic(ctx)
        opt.set_train_summary(rec)
        for s in sims:
            s.start()
        try:
            opt.optimize()
        finally:
            for s in sims:
                s.stop()
        return {"losses": rec.values, "walls": rec.walls,
                "evictions": ctx.counters()["evictions"],
                "incarnation_changes":
                    ctx.counters()["incarnation_changes"],
                "relax_rounds": pol.relax_rounds}

    try:
        lock = run(None)
        per = run(Plan([Rule(".*", P(),
                             sync=f"periodic({int(period)})")]))
        strag = None
        if straggler:
            rel = run_straggler(True)
            ev = run_straggler(False)
            span = lambda r: (r["walls"][-1] - r["walls"][0]
                              if len(r["walls"]) > 1 else 0.0)
            wall_rel, wall_ev = span(rel), span(ev)
            sps = lambda w: round((straggler_steps - 1)
                                  / max(w, 1e-9), 3)
            target = ev["losses"][-1] if ev["losses"] else None
            t_rel = wall_rel
            if target is not None:
                for w, l in zip(rel["walls"], rel["losses"]):
                    if l <= target:
                        t_rel = w - rel["walls"][0]
                        break
            strag = {
                "steps": straggler_steps,
                "relaxed_wall_s": round(wall_rel, 3),
                "evict_wall_s": round(wall_ev, 3),
                "relaxed_steps_per_sec": sps(wall_rel),
                "evict_steps_per_sec": sps(wall_ev),
                "relaxed_time_to_target_s": round(t_rel, 3),
                "loss_target": (round(target, 5)
                                if target is not None else None),
                "relaxed_evictions": rel["evictions"],
                "evict_evictions": ev["evictions"],
                "relax_rounds": rel["relax_rounds"],
                "relaxed_loss_descending": bool(
                    rel["losses"] and rel["losses"][-1]
                    < rel["losses"][0]),
                "evict_loss_descending": bool(
                    ev["losses"] and ev["losses"][-1] < ev["losses"][0]),
                # the judged multiple (the acceptance's "steps/sec
                # under an injected straggler vs the eviction path"):
                # same step budget, first-loss -> last-loss walls —
                # the eviction path's restore + mesh re-derivation +
                # recompile is inside its span, the relaxed path has
                # neither (time-to-target above is informational)
                "straggler_advantage_x": round(
                    wall_ev / max(wall_rel, 1e-9), 2),
            }
    finally:
        bigdl_log.setLevel(prev_level)
        if prev_profile is None:
            os.environ.pop("BIGDL_METRICS_PROFILEINTERVAL", None)
        else:
            os.environ["BIGDL_METRICS_PROFILEINTERVAL"] = prev_profile

    ll, pl = lock["losses"], per["losses"]
    ratio = None
    if lock["collective_bytes"] and per["collective_bytes"]:
        ratio = lock["collective_bytes"] / per["collective_bytes"]
    out = {
        "devices": 8,
        "mesh": "data=8",
        "period": int(period),
        "steps": steps, "batch": batch,
        "lockstep_steps_per_sec": lock["steps_per_sec"],
        "periodic_steps_per_sec": per["steps_per_sec"],
        "lockstep_collective_bytes_per_step": lock["collective_bytes"],
        "periodic_collective_bytes_per_step": per["collective_bytes"],
        "sync_bytes_saved_per_step": per["sync_saved"],
        "collective_bytes_reduction_x": (round(ratio, 2)
                                         if ratio else None),
        "lockstep_loss_descending": bool(ll and ll[-1] < ll[0]),
        "periodic_loss_descending": bool(pl and pl[-1] < pl[0]),
        "loss_first": round(pl[0], 5) if pl else None,
        "loss_last": round(pl[-1], 5) if pl else None,
        # the forced-host simulation runs all 8 "devices" on ONE core
        # pool, so local SGD's per-replica optimizer work serializes
        # and periodic steps/sec reads BELOW lockstep here — on real
        # multi-host silicon each replica's work is its own chip's.
        # The judged wins are the deterministic amortized wire (the
        # reduction ratio above) and the straggler pass's wall clock.
        "note": "periodic steps/sec on forced-host CPU serializes "
                "per-replica work; wire + straggler walls are the "
                "judged numbers",
    }
    if strag is not None:
        out["straggler"] = strag
        out["straggler_advantage_x"] = strag["straggler_advantage_x"]
    return out


def run_sync_bench() -> None:
    """--sync mode: relaxed synchrony on 8 forced-host CPU devices —
    lockstep vs periodic(8) wire + throughput, and the straggler
    relax-vs-evict chaos pass — writes SYNC_r01.json, prints the one
    JSON line."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "sync", "backend": "cpu",
           "forced_host_devices": 8, "measured_at": _utc_now()}
    try:
        out.update(_sync_measurements())
        out.update({
            "metric": "periodic(8) collective-bytes reduction vs "
                      "lockstep",
            "value": out.get("collective_bytes_reduction_x") or 0.0,
            "unit": "x",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "periodic(8) collective-bytes reduction "
                              "vs lockstep",
                    "value": 0.0, "unit": "x"})
    try:
        with open(os.path.join(_here(), SYNC_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Block-sparse kernel leg: BLaST skip accounting + parity (ISSUE 12)
# --------------------------------------------------------------------------

BLOCKSPARSE_RESULT = "BLOCKSPARSE_r01.json"


def _blocksparse_measurements(seq_len: int = 4096, head_dim: int = 64,
                              heads: int = 1, batch: int = 1,
                              block: int = 512,
                              densities=(1.0, 0.5, 0.25)):
    """The block-sparse kernel lab (ISSUE 12): on TPU the kernels run
    for real and ``speedup_x`` is the measured wall ratio vs the flash
    kernel at the 50% magnitude mask; off-TPU they run in the Pallas
    interpreter and ``speedup_x`` is the kernel-reported executed-work
    reduction (the accounting the MFU correction rides — a count,
    not a speed).  Either way the leg proves:

    * full-mask parity at a NON-default sm_scale: block-sparse ==
      flash == dense (the reference-fallback scale-bug class);
    * executed work ∝ mask density (within 10%) across a magnitude-
      mask sweep — the index tables the grid runs are the accounting;
    * the ``bigdl_perf_sparse_flops_skipped`` gauge lands in the
      PerfAccountant payload (the roofline-report view).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops.block_sparse import (BlockMask, attention_work,
                                            block_sparse_attention,
                                            block_sparse_matmul,
                                            magnitude_block_mask,
                                            matmul_work)
    from bigdl_tpu.ops.flash_attention import (_attention_reference,
                                               flash_attention)
    from bigdl_tpu.telemetry import MetricsRegistry
    from bigdl_tpu.telemetry.perf import PerfAccountant, StepCost

    rng = np.random.RandomState(0)
    B, H, T, D = batch, heads, seq_len, head_dim
    nb = T // block
    if T % block:
        raise ValueError(f"seq_len {T} not divisible by block {block}")
    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu
    q, k, v = [jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)
                           * 0.5) for _ in range(3)]

    # -- full-mask parity at a non-default sm_scale ---------------------
    sm = 0.5 / float(np.sqrt(D))
    full = BlockMask(np.ones((nb, nb), bool), block, block)
    ref = np.asarray(_attention_reference(q, k, v, True, sm))
    fl = np.asarray(flash_attention(q, k, v, causal=True, sm_scale=sm,
                                    interpret=interpret))
    bs_full = np.asarray(block_sparse_attention(
        q, k, v, full, causal=True, sm_scale=sm, interpret=interpret))
    tol = 2e-3 * max(1.0, float(np.abs(ref).max()))
    full_mask_parity = bool(
        np.abs(bs_full - fl).max() < tol
        and np.abs(bs_full - ref).max() < tol)

    # -- executed work ∝ density (magnitude-mask sweep, non-causal) -----
    score_map = rng.randn(nb, nb)
    sweep = []
    within = True
    for d in densities:
        m = magnitude_block_mask(score_map, 1, 1, d)
        m = BlockMask(m.mask, block, block)
        w = attention_work(m, B, H, D, causal=False)
        frac = w["executed_fraction"]
        sweep.append({"density": round(float(d), 4),
                      "executed_fraction": round(frac, 4)})
        if abs(frac - d) > 0.10 * max(d, 1e-9):
            within = False

    # -- the judged 50% mask: walls + the accounting correction ---------
    mask50 = BlockMask(magnitude_block_mask(score_map, 1, 1, 0.5).mask,
                       block, block)
    work50 = attention_work(mask50, B, H, D, causal=False)

    def timed(fn, reps=2):
        fn().block_until_ready()          # warmup/compile
        t0 = time.monotonic()
        for _ in range(reps):
            r = fn()
        r.block_until_ready()
        return (time.monotonic() - t0) / reps

    wall_flash = timed(lambda: flash_attention(
        q, k, v, causal=False, interpret=interpret))
    wall_bs = timed(lambda: block_sparse_attention(
        q, k, v, mask50, causal=False, interpret=interpret))
    wall_speedup = wall_flash / max(wall_bs, 1e-9)
    work_reduction = (work50["dense_equivalent_flops"]
                      / max(work50["executed_flops"], 1e-9))

    # -- sparse MLP matmul: parity + work --------------------------------
    wm = jnp.asarray(rng.randn(2 * block, 2 * block).astype(np.float32)
                     * 0.1)
    xm = jnp.asarray(rng.randn(64, 2 * block).astype(np.float32))
    mlp_mask = magnitude_block_mask(wm, block, block, 0.5)
    ym = np.asarray(block_sparse_matmul(xm, wm, mlp_mask,
                                        interpret=interpret))
    ym_ref = np.asarray(xm @ (wm * jnp.asarray(mlp_mask.elementwise(),
                                               wm.dtype)))
    mlp_parity = bool(np.abs(ym - ym_ref).max()
                      < 1e-3 * max(1.0, float(np.abs(ym_ref).max())))
    mlp_w = matmul_work(mlp_mask, 64)

    # -- the PerfAccountant correction loop (gauge + payload) -----------
    pa = PerfAccountant(registry=MetricsRegistry())
    pa.on_program("blocksparse_attention",
                  StepCost(flops=0.0, bytes_accessed=float(
                      3 * B * H * T * D * 4)))
    pa.report_sparse_flops("blocksparse_attention",
                           work50["executed_flops"],
                           work50["dense_equivalent_flops"])
    entry = pa.payload()["programs"]["blocksparse_attention"]
    snap = pa.registry.snapshot()["metrics"]
    gauge = (snap.get("bigdl_perf_sparse_flops_skipped") or {}).get(
        "series") or []
    gauge_val = float(gauge[0]["value"]) if gauge else None

    return {
        "backend": "tpu" if on_tpu else "cpu",
        "mode": "kernel" if on_tpu else "interpret",
        "seq_len": T, "head_dim": D, "block": block, "n_blocks": nb,
        "full_mask_parity": full_mask_parity,
        "scale_parity_sm_scale": sm,
        "density_sweep": sweep,
        "accounting_within_10pct": within,
        "mask_density": 0.5,
        "executed_flops": work50["executed_flops"],
        "dense_equiv_flops": work50["dense_equivalent_flops"],
        "sparse_flops_skipped": work50["sparse_flops_skipped"],
        "work_reduction_x": round(work_reduction, 3),
        "wall_flash_s": round(wall_flash, 4),
        "wall_blocksparse_s": round(wall_bs, 4),
        "wall_speedup_x": round(wall_speedup, 3),
        # the judged multiple: measured wall on TPU; the deterministic
        # executed-work reduction under the interpreter (the
        # acceptance's TPU-unreachable basis)
        "speedup_x": round(wall_speedup if on_tpu
                           else work_reduction, 3),
        "speedup_basis": ("tpu_wall" if on_tpu
                          else "interpret_work_reduction"),
        "mlp_parity": mlp_parity,
        "mlp_work_reduction_x": round(
            mlp_w["dense_equivalent_flops"]
            / max(mlp_w["executed_flops"], 1e-9), 3),
        "accountant_payload_has_skip": bool(
            entry.get("sparse_flops_skipped") ==
            work50["sparse_flops_skipped"]),
        "sparse_flops_gauge": gauge_val,
    }


def run_blocksparse_bench() -> None:
    """--blocksparse mode: the BLaST kernel lab on CPU (interpreter +
    accounting proof; the on-chip wall comparison lives in the TPU
    worker's ``transformerlm_blocksparse_T4096`` rows) — writes
    BLOCKSPARSE_r01.json, prints the one JSON line."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "blocksparse", "measured_at": _utc_now()}
    try:
        out.update(_blocksparse_measurements())
        out.update({
            "metric": "block-sparse attention speedup at 50%% density "
                      "(%s)" % out.get("speedup_basis"),
            "value": out.get("speedup_x") or 0.0,
            "unit": "x",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "block-sparse attention speedup at 50% "
                              "density", "value": 0.0, "unit": "x"})
    try:
        with open(os.path.join(_here(), BLOCKSPARSE_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# SLO leg: the online health engine — detection latency, false
# positives, recorder+engine overhead
# --------------------------------------------------------------------------

SLO_RESULT = "SLO_r01.json"


def _slo_chaos_scenarios(eval_interval_s: float = 5.0,
                         steady_intervals: int = 200):
    """Deterministic chaos harness under an injected clock: scripted
    fleet+training signal streams drive the default rule packs
    through four injected breaches — shed ramp, loss divergence, MFU
    collapse, replica kill — plus a steady control run.  Returns
    per-scenario detection/resolution interval counts and the steady
    pass's false-positive count (the acceptance bar: every breach
    detected within 3 evaluation intervals, zero spurious alerts)."""
    from bigdl_tpu.telemetry import (MetricRecorder, MetricsRegistry,
                                     SloEngine, SloRule,
                                     default_serving_rules,
                                     default_training_rules)
    from bigdl_tpu.telemetry import metric_names as M

    def build():
        clk = {"t": 0.0}
        rec = MetricRecorder(clock=lambda: clk["t"])
        rules = default_serving_rules(
            "both", p99_high_s=0.5, shed_high=0.05,
            error_budget=0.02, window_s=30.0, fast_window_s=15.0,
            slow_window_s=60.0, for_intervals=2, resolve_intervals=2)
        rules += [r for r in default_training_rules(
            goodput_floor=0.5, loss_window_s=60.0,
            divergence_ratio=1.5, mfu_drop_frac=0.5, window_s=60.0,
            for_intervals=2, resolve_intervals=2)
            # the stall rule legitimately fires on a converged flat
            # loss; the chaos scenarios exercise divergence
            if r.name != "training/loss_stall"]
        rules.append(SloRule(
            name="replica/r1/health_feed",
            family=M.REPLICA_P99_SECONDS, labels={"replica": "r1"},
            kind="absent", window_s=2 * eval_interval_s + 1.0,
            resolve_intervals=1,
            description="replica r1 health feed went silent"))
        eng = SloEngine(rec, rules=rules,
                        registry=MetricsRegistry(),
                        clock=lambda: clk["t"])
        state = {"clk": clk, "rec": rec, "eng": eng, "shed": 0,
                 "total": 0, "loss": 4.0, "mfu": 0.5}
        return state

    def tick(st, shed_frac=0.0, diverge=False, kill_replica=False,
             mfu=None):
        st["clk"]["t"] += eval_interval_s
        rec, L = st["rec"], {"pool": "both"}
        n = 500
        st["shed"] += int(n * shed_frac)
        st["total"] += n
        rec.observe(M.AUTOSCALE_POOL_P99_SECONDS, 0.040, labels=L)
        rec.observe(M.AUTOSCALE_POOL_SHED_RATE, shed_frac, labels=L)
        rec.observe(M.AUTOSCALE_POOL_KV_OCCUPANCY, 0.3, labels=L)
        rec.observe(M.AUTOSCALE_POOL_SHED_TOTAL, st["shed"],
                    labels=L, kind="counter")
        rec.observe(M.AUTOSCALE_POOL_REQUESTS_TOTAL, st["total"],
                    labels=L, kind="counter")
        st["loss"] *= 1.8 if diverge else 0.98
        rec.observe(M.TRAIN_LOSS, st["loss"])
        rec.observe(M.TRAIN_STEP_TIME_SECONDS, 0.1)
        rec.observe(M.GOODPUT_PRODUCTIVE_FRACTION, 0.97)
        if mfu is not None:
            st["mfu"] = mfu
        rec.observe(M.PERF_MFU, st["mfu"])
        if not kill_replica:
            rec.observe(M.REPLICA_P99_SECONDS, 0.02,
                        labels={"replica": "r1"})
        return st["eng"].evaluate()

    # --- steady control: full-length run, zero alerts expected -------
    st = build()
    false_positives = 0
    for _ in range(steady_intervals):
        false_positives += len(tick(st))

    # --- injected breaches, one scenario run -------------------------
    st = build()
    for _ in range(20):                       # warmup, steady
        false_positives += len(tick(st))
    scenarios = {}

    def run_scenario(name, expect_rule, breach_kw, recover_kw,
                     max_detect=3, max_resolve=40):
        detect = None
        for i in range(1, max_detect + 1):
            fired = [a.rule for a in tick(st, **breach_kw)
                     if a.state == "firing"]
            if expect_rule in fired:
                detect = i
                break
        # hold the breach a few more intervals (the burn-rate rule
        # joins during the shed ramp hold)
        for _ in range(4):
            tick(st, **breach_kw)
        resolve = None
        for i in range(1, max_resolve + 1):
            tick(st, **recover_kw)
            if not st["eng"].firing():
                resolve = i
                break
        scenarios[name] = {
            "detected_in_intervals": detect,
            "resolved_in_intervals": resolve,
            "expected_rule": expect_rule,
        }

    run_scenario("shed_ramp", "serving/both/shed_rate",
                 dict(shed_frac=0.30), dict())
    run_scenario("loss_divergence", "training/loss_divergence",
                 dict(diverge=True), dict())
    run_scenario("mfu_collapse", "training/mfu_collapse",
                 dict(mfu=0.1), dict(mfu=0.5))
    run_scenario("replica_kill", "replica/r1/health_feed",
                 dict(kill_replica=True), dict())

    detects = [s["detected_in_intervals"] for s in scenarios.values()]
    resolves = [s["resolved_in_intervals"] for s in scenarios.values()]
    return {
        "eval_interval_s": eval_interval_s,
        "steady_intervals": steady_intervals,
        "scenarios": scenarios,
        "all_detected": all(d is not None for d in detects),
        "all_resolved": all(r is not None for r in resolves),
        "max_detection_intervals": (max(detects)
                                    if all(d is not None
                                           for d in detects)
                                    else None),
        "detection_latency_s": (max(detects) * eval_interval_s
                                if all(d is not None
                                       for d in detects) else None),
        "false_positives": false_positives,
    }


def _slo_measurements(eval_interval_s: float = 5.0,
                      steady_intervals: int = 200,
                      overhead_steps: int = 600,
                      overhead_batch: int = 512,
                      overhead_hidden: int = 128,
                      overhead_repeats: int = 3,
                      monitor_every: int = 32):
    """The online-health-engine leg: (1) deterministic chaos
    scenarios under an injected clock (detection latency on an
    injected shed ramp / loss divergence / MFU collapse / replica
    kill, false positives on a steady control), (2) recorder+engine
    overhead on the SAME compiled step loop the telemetry leg
    measures — telemetry-only vs telemetry+TrainingHealthMonitor at
    the ``monitor_every``-step evaluation cadence, min-of-repeats
    walls — and (3) per-op primitive costs."""
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import Sample, array
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.telemetry import (MetricRecorder, MetricsRegistry,
                                     SloEngine, Telemetry,
                                     TrainingHealthMonitor,
                                     default_training_rules)
    from bigdl_tpu.telemetry import metric_names as M

    import logging

    import numpy as np

    out = _slo_chaos_scenarios(eval_interval_s=eval_interval_s,
                               steady_intervals=steady_intervals)

    # --- overhead vs the telemetry leg's instrumented loop -----------
    rng = np.random.RandomState(0)
    x = rng.rand(1024, 16).astype(np.float32)
    w = rng.rand(16, 1).astype(np.float32)
    y = (x @ w + 0.3).astype(np.float32)
    data = array([Sample(x[i], y[i]) for i in range(len(x))])
    bigdl_log = logging.getLogger("bigdl_tpu")
    prev_level = bigdl_log.level
    bigdl_log.setLevel(logging.WARNING)

    def run(with_monitor: bool) -> float:
        model = nn.Sequential(nn.Linear(16, overhead_hidden),
                              nn.Tanh(),
                              nn.Linear(overhead_hidden, 1))
        opt = LocalOptimizer(model, data, nn.MSECriterion(),
                             batch_size=overhead_batch)
        opt.set_optim_method(SGD(learning_rate=0.01))
        opt.set_end_when(max_iteration(overhead_steps))
        opt.set_telemetry(Telemetry(registry=MetricsRegistry()))
        if with_monitor:
            opt.set_health_monitor(TrainingHealthMonitor(
                rules=default_training_rules(),
                every_n_steps=monitor_every))
        t0 = time.monotonic()
        opt.optimize()
        return time.monotonic() - t0

    tel_walls, mon_walls = [], []
    try:
        for _ in range(max(1, overhead_repeats)):
            tel_walls.append(run(False))
            mon_walls.append(run(True))
    finally:
        bigdl_log.setLevel(prev_level)
    tel, mon = min(tel_walls), min(mon_walls)
    # informational only: on this 1-core container the A/B wall noise
    # (±10-25% scheduler jitter) swamps the ~20µs/step signal even
    # under min-of-repeats, so the JUDGED overhead below is the
    # directly measured amortized per-step monitor cost over the
    # loop's measured step time — stable run to run, and what the tax
    # actually is
    wall_overhead_pct = 100.0 * (mon - tel) / max(tel, 1e-9)
    step_s = tel / max(1, overhead_steps)

    # --- per-op primitive costs + the judged amortized tax -----------
    rec = MetricRecorder()
    n = 50_000
    t0 = time.perf_counter()
    for i in range(n):
        # descending feed: the engine loops below must measure
        # evaluation cost, not fire the stall rule
        rec.observe(M.TRAIN_LOSS, float(n - i))
    observe_ns = (time.perf_counter() - t0) / n * 1e9
    eng = SloEngine(rec, rules=default_training_rules(),
                    registry=MetricsRegistry())
    n_eval = 2_000
    t0 = time.perf_counter()
    for _ in range(n_eval):
        eng.evaluate()
    evaluate_us = (time.perf_counter() - t0) / n_eval * 1e6
    # amortized monitor cost per driver iteration, rings at steady
    # state (full windows — the honest worst case for the reducers)
    amon = TrainingHealthMonitor(rules=default_training_rules(),
                                 every_n_steps=monitor_every,
                                 registry=MetricsRegistry())
    prev = bigdl_log.level
    bigdl_log.setLevel(logging.ERROR)   # transitions are console I/O
    try:
        for i in range(2_000):          # fill the rings
            amon.on_step(i, 4.0 * 0.999 ** i, step_s)
        n_mon = 20_000
        t0 = time.perf_counter()
        for i in range(n_mon):
            amon.on_step(i, 3.0, step_s)
        monitor_step_us = (time.perf_counter() - t0) / n_mon * 1e6
    finally:
        bigdl_log.setLevel(prev)
    overhead_pct = 100.0 * (monitor_step_us * 1e-6) / max(step_s,
                                                          1e-9)

    out.update({
        "overhead_steps": overhead_steps,
        "monitor_every_n_steps": monitor_every,
        "telemetry_wall_s": round(tel, 3),
        "monitored_wall_s": round(mon, 3),
        "wall_overhead_pct": round(wall_overhead_pct, 2),
        "step_ms": round(step_s * 1e3, 3),
        "monitor_step_us": round(monitor_step_us, 1),
        "overhead_pct": round(overhead_pct, 2),
        "recorder_observe_ns": round(observe_ns, 0),
        "engine_evaluate_us": round(evaluate_us, 1),
    })
    return out


def run_slo_bench() -> None:
    """--slo mode: the online health engine — chaos detection
    latency + false positives under an injected clock, recorder+
    engine overhead on the instrumented step loop — writes
    SLO_r01.json, prints the one JSON line."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "slo", "backend": "cpu",
           "measured_at": _utc_now()}
    try:
        out.update(_slo_measurements())
        out.update({
            "metric": "SLO detection latency on injected breaches",
            "value": out.get("detection_latency_s") or 0.0,
            "unit": "s",
            "target": "<= 3 evaluation intervals, 0 false positives, "
                      "<= 1% overhead",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "SLO detection latency on injected "
                              "breaches",
                    "value": 0.0, "unit": "s"})
    try:
        with open(os.path.join(_here(), SLO_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Continuous-learning loop bench (--loop): online train → verified
# hot-swap → serve, burn-rate rollback under a regressed deploy
# --------------------------------------------------------------------------

LOOP_RESULT = "LOOP_r01.json"


def _loop_measurements(intervals: int = 30,
                       steps_per_interval: int = 4,
                       n_replicas: int = 3,
                       requests_per_interval: int = 8):
    """The continuous-learning production loop end to end on a fake
    clock: (1) a clean run — the model must measurably improve while
    the fleet serves and confirmed hot-swaps land, with the training
    slices' goodput (productive fraction of attributed wall) as the
    headline; (2) a regressed deploy under live traffic — the
    post-swap burn-rate watch fires and the fleet-wide verified
    rollback's wall is the latency number; (3) the audit invariant —
    a non-finite param tree never answered a request."""
    import logging

    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import Sample, array
    from bigdl_tpu.loop import ContinuousLoop
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.resilience import faults
    from bigdl_tpu.serving import ServingFleet
    from bigdl_tpu.telemetry import (MetricsRegistry, Telemetry,
                                     TrainingHealthMonitor,
                                     default_loop_rules,
                                     default_training_rules)

    bigdl_log = logging.getLogger("bigdl_tpu")
    prev_level = bigdl_log.level
    bigdl_log.setLevel(logging.ERROR)

    rng = np.random.RandomState(0)
    w = rng.rand(8, 1).astype(np.float32)

    def make_samples(n):
        xs = rng.rand(n, 8).astype(np.float32)
        return [Sample(xs[i], (xs[i] @ w).astype(np.float32))
                for i in range(n)]

    model = nn.Sequential(nn.Linear(8, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = LocalOptimizer(model, array(make_samples(512)),
                         nn.MSECriterion(), batch_size=32)
    opt.set_optim_method(SGD(learning_rate=0.05))
    opt.set_telemetry(Telemetry(registry=MetricsRegistry()))
    opt.set_health_monitor(TrainingHealthMonitor(
        rules=[r for r in default_training_rules(divergence_ratio=4.0)
               if r.name == "training/loss_divergence"],
        every_n_steps=2))

    t = [0.0]
    fl = ServingFleet.build(
        nn.Sequential(nn.Linear(8, 8), nn.Tanh(), nn.Linear(8, 1)),
        n_replicas=n_replicas,
        server_kw=dict(max_batch=8, max_queue=64),
        heartbeat_timeout=5.0, pump_interval_s=0,
        clock=lambda: t[0],
        router_kw=dict(default_deadline_s=30.0, clock=lambda: t[0]))
    fl.start()

    loop = ContinuousLoop(
        opt, fl, lambda: make_samples(16),
        steps_per_interval=steps_per_interval, deploy_every=5,
        watch_intervals=4, cooldown_intervals=2,
        dataset_capacity=1024,
        rules=default_loop_rules(interval_s=1.0, serve_budget=0.02),
        interval_s=1.0, clock=lambda: t[0])

    def step(n):
        for _ in range(n):
            loop.tick()
            t[0] += 1.0
            for f in [fl.submit(rng.rand(8).astype(np.float32))
                      for _ in range(requests_per_interval)]:
                f.result(60)

    try:
        # --- clean run: improve while serving, confirmed hot-swaps ---
        step(intervals)
        snap = loop.snapshot()
        confirmed = snap["deploys"].get("confirmed", 0)
        losses = list(loop.losses)
        loss_first = float(np.mean(losses[:steps_per_interval]))
        loss_last = float(np.mean(losses[-steps_per_interval:]))

        # --- regressed deploy: burn fires, verified fleet rollback ---
        while loop.state != "watch":
            step(1)
        with faults.serving_step_failures(times=6):
            for _ in range(requests_per_interval):
                fl.submit(rng.rand(8).astype(np.float32)).result(60)
        step(2)
        rolled_back = loop.deploy_outcomes["rolled_back"]
        rollback_latency_s = loop.last_rollback_latency_s
        return {
            "intervals": intervals,
            "steps_per_interval": steps_per_interval,
            "n_replicas": n_replicas,
            "confirmed_deploys": confirmed,
            "loss_first": round(loss_first, 4),
            "loss_last": round(loss_last, 4),
            "loss_improvement_x": round(
                loss_first / max(loss_last, 1e-9), 1),
            "goodput": (None if snap["goodput"] is None
                        else round(snap["goodput"], 4)),
            "rollbacks_fired": rolled_back,
            "rollback_latency_s": (
                None if rollback_latency_s is None
                else round(rollback_latency_s, 4)),
            "bad_params_served": loop.bad_params_served,
        }
    finally:
        bigdl_log.setLevel(prev_level)
        fl.stop(timeout=10)


def run_loop_bench() -> None:
    """--loop mode: the continuous-learning production loop — goodput
    while serving + confirmed hot-swaps on a clean run, burn-rate
    rollback latency on a regressed deploy, bad-params-served audit —
    writes LOOP_r01.json, prints the one JSON line."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "loop", "backend": "cpu",
           "measured_at": _utc_now()}
    try:
        out.update(_loop_measurements())
        out.update({
            "metric": "continuous-loop goodput while serving",
            "value": out.get("goodput") or 0.0,
            "unit": "fraction",
            "target": ">= 0.97 goodput, 0 bad params served, "
                      "rollback through the verified install path",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "continuous-loop goodput while serving",
                    "value": 0.0, "unit": "fraction"})
    try:
        with open(os.path.join(_here(), LOOP_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Embedding-store bench (--embed): parameter-server-scale table — live
# 1-host re-partition wall-clock, Zipf hot-row cache hit rate, and the
# bad-rows-served audit under a corrupted migration shard
# --------------------------------------------------------------------------

EMBED_RESULT = "EMBED_r01.json"


def _embed_measurements(n_rows: int = 100_000, dim: int = 16,
                        block_rows: int = 1024,
                        update_rounds: int = 40,
                        zipf_lookups: int = 400,
                        zipf_batch: int = 32):
    """The parameter-server embedding store end to end (ISSUE 18):

    (1) a 3-host table takes Zipf-skewed sparse updates and writes its
    repartition-barrier checkpoints; (2) one host is removed — the
    survivors' live re-partition wall-clock is the headline, and the
    moved-row fraction must sit near 1/N (consistent assignment, never
    a reshuffle); (3) a joiner regrows the gang WITH one migration
    shard corrupted in flight — detection + checkpointed-leg recovery
    are counted; (4) a Zipf lookup stream through the serving-side
    SparseFetchClient measures the hot-row cache hit rate and the
    must-stay-zero bad-rows-served audit."""
    import tempfile
    import time as _time

    import numpy as np

    from bigdl_tpu.nn import EmbeddingStore, table_checksum
    from bigdl_tpu.resilience import faults
    from bigdl_tpu.resilience.elastic import InMemoryKV
    from bigdl_tpu.serving import SparseFetchClient

    hosts = ["emb-0", "emb-1", "emb-2"]
    kv = InMemoryKV()
    rng = np.random.RandomState(0)
    with tempfile.TemporaryDirectory() as tmp:
        stores = {h: EmbeddingStore("bench_emb", n_rows, dim, h, hosts,
                                    kv=kv, block_rows=block_rows,
                                    seed=11, checkpoint_dir=tmp)
                  for h in hosts}

        def route(row):
            return stores[hosts[0]].owner_of_row(row)

        for _ in range(update_rounds):
            rows = np.minimum(rng.zipf(1.3, size=zipf_batch) - 1,
                              n_rows - 1)
            by_owner = {}
            for r in rows:
                by_owner.setdefault(route(int(r)), []).append(int(r))
            for owner, rs in by_owner.items():
                legs = stores.get(owner)
                if legs is not None:
                    legs.apply_updates(
                        rs, rng.standard_normal(
                            (len(rs), dim)).astype(np.float32))
        for s in stores.values():
            s.checkpoint()
        before = table_checksum(list(stores.values()))

        # -- 1-host shrink: the live re-partition wall-clock ----------
        survivors = {h: stores[h] for h in hosts[:-1]}
        t0 = _time.monotonic()
        moved = 0
        for leg in survivors.values():
            stats = leg.repartition(hosts[:-1], dead=[hosts[-1]])
            moved += stats["moved_rows"]
        migration_s = _time.monotonic() - t0
        rows_moved_frac = moved / float(n_rows)
        shrink_equal = (
            table_checksum(list(survivors.values())) == before)

        # -- regrow with one corrupted shard in flight ----------------
        joiner = EmbeddingStore("bench_emb", n_rows, dim, "emb-3",
                                hosts[:-1], kv=kv,
                                block_rows=block_rows, seed=11,
                                checkpoint_dir=tmp)
        grown = sorted(hosts[:-1] + ["emb-3"])
        with faults.corrupt_migration_shard("bench_emb", times=1) as f:
            for leg in survivors.values():
                leg.repartition(grown)
            joiner.repartition(grown)
            corrupt_fired = f["fired"]
        legs = list(survivors.values()) + [joiner]
        regrow_equal = table_checksum(legs) == before

        # -- Zipf lookup stream through the serving fetch -------------
        client = SparseFetchClient({s.host: s for s in legs},
                                   cache_capacity=4096)
        for _ in range(zipf_lookups):
            rows = np.minimum(rng.zipf(1.3, size=zipf_batch) - 1,
                              n_rows - 1)
            client.fetch([int(r) for r in rows])
        snap = client.health_snapshot()

        return {
            "n_rows": n_rows,
            "dim": dim,
            "n_hosts": len(hosts),
            "migration_s": round(migration_s, 4),
            "rows_moved_frac": round(rows_moved_frac, 4),
            "bitwise_equal_after_shrink": shrink_equal,
            "bitwise_equal_after_regrow": regrow_equal,
            "corrupt_shards_injected": corrupt_fired,
            "corrupt_shards_detected":
                joiner.migration_corrupt_detected,
            "recovered_from_checkpoint": sum(
                s.recovered_from_checkpoint for s in legs),
            "cache_hit_rate": round(snap["cache"]["hit_rate"], 4),
            "bad_rows_served": snap["bad_rows_served"],
            "rows_served": snap["rows_served"],
            "table_version": snap["table_version"],
        }


def run_embed_bench() -> None:
    """--embed mode: parameter-server-scale embedding store — 1-host
    re-partition wall-clock + moved-row fraction, corrupt-shard
    recovery, Zipf cache hit rate, bad-rows-served audit — writes
    EMBED_r01.json, prints the one JSON line."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "embed", "backend": "cpu",
           "measured_at": _utc_now()}
    try:
        out.update(_embed_measurements())
        out.update({
            "metric": "1-host live re-partition wall-clock",
            "value": out.get("migration_s") or 0.0,
            "unit": "s",
            "target": "rows_moved_frac <= 1.5/N, bitwise-equal table "
                      "across the membership boundary, 0 bad rows "
                      "served",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "1-host live re-partition wall-clock",
                    "value": 0.0, "unit": "s"})
    try:
        with open(os.path.join(_here(), EMBED_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Multi-tenant fleet bench (--tenant): noisy-neighbor isolation — the
# victim tenant's p99 under an aggressor flood + poisoned aggressor
# deploy vs its solo baseline, victim shed rate, bad-params audit
# --------------------------------------------------------------------------

TENANT_RESULT = "TENANT_r01.json"


def _tenant_measurements(n_replicas_each: int = 2,
                         solo_requests: int = 60,
                         contended_requests: int = 60,
                         flood_threads: int = 4,
                         deadline_s: float = 5.0):
    """The multi-tenant fleet end to end (ISSUE 19): a 2-model fleet
    (registry + per-tenant weighted admission) serves tenant B a
    closed-loop stream twice — once solo (the baseline), once while
    tenant A floods the fleet open-loop from ``flood_threads``
    producers AND ships a poisoned deploy that the canary must reject
    without touching a model-B replica.  Emits:

    * ``isolation_p99_ratio`` — contended-over-solo tenant-B p99 (the
      noisy-neighbor headline; 1.0 is perfect isolation);
    * ``victim_shed_rate`` — tenant-B sheds over tenant-B requests, a
      must-stay-zero: fair admission may never bill A's flood to B;
    * ``bad_params_served`` — non-finite OK outputs across BOTH
      tenants plus any replica that installed the rejected artifact, a
      must-stay-zero;
    * aggressor-side accounting (typed shed rate through A's quota)
      proving the fairness machinery was genuinely exercised.
    """
    import threading
    import time as _time

    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.resilience import faults
    from bigdl_tpu.serving import ServingFleet, Status
    from bigdl_tpu.serving.swap import SwapRejected

    def small_model():
        return nn.Sequential(nn.Linear(4, 8), nn.Tanh(),
                             nn.Linear(8, 3), nn.LogSoftMax())

    fl = ServingFleet.build_multi(
        {"alpha": small_model(), "beta": small_model()},
        n_replicas_each=n_replicas_each,
        server_kw=dict(max_batch=8, max_queue=256),
        admission_capacity=8 * n_replicas_each,
        heartbeat_timeout=0.4, pump_interval_s=0.05,
        router_kw=dict(default_deadline_s=deadline_s))
    fl.start()
    rng = np.random.RandomState(0)
    try:
        for m in ("alpha", "beta"):            # warm compiled paths
            [f.result(60) for f in
             [fl.submit(rng.rand(4).astype(np.float32), model=m)
              for _ in range(8)]]

        def beta_closed_loop(n):
            out = []
            r = np.random.RandomState(11)
            for _ in range(n):
                res = fl.submit(r.rand(4).astype(np.float32),
                                model="beta").result(60)
                out.append(res)
            return out

        def p99(results):
            lat = sorted(r.latency_s for r in results)
            return lat[int(0.99 * (len(lat) - 1))]

        solo = beta_closed_loop(solo_requests)
        solo_p99 = p99(solo)

        alpha_futs = []
        fut_lock = threading.Lock()
        stop = threading.Event()

        def alpha_flood(seed):
            r = np.random.RandomState(seed)
            while not stop.is_set():
                f = fl.submit(r.rand(4).astype(np.float32),
                              model="alpha", deadline_s=deadline_s)
                with fut_lock:
                    alpha_futs.append(f)
                _time.sleep(0.001)

        floods = [threading.Thread(target=alpha_flood, args=(s,))
                  for s in range(flood_threads)]
        for th in floods:
            th.start()
        poisoned_rejected = False
        try:
            _time.sleep(0.05)
            try:
                fl.rolling_swap(params=faults.poison_params(
                    fl.servers["alpha-r0"].model.param_tree()),
                    model="alpha", version="v2")
            except SwapRejected:
                poisoned_rejected = True
            contended = beta_closed_loop(contended_requests)
        finally:
            stop.set()
            for th in floods:
                th.join(timeout=30)
        alpha_res = [f.result(timeout=120) for f in alpha_futs]

        bad_params = sum(
            1 for r in list(alpha_res) + solo + contended
            if r.ok and not np.isfinite(np.asarray(r.output)).all())
        bad_params += sum(s.metrics.swaps
                          for s in fl.servers.values())
        tenants = fl.router.metrics.tenants()
        beta_t = tenants.get("beta") or {}
        alpha_t = tenants.get("alpha") or {}
        contended_p99 = p99(contended)
        return {
            "n_replicas_each": n_replicas_each,
            "solo_p99_ms": round(solo_p99 * 1e3, 3),
            "contended_p99_ms": round(contended_p99 * 1e3, 3),
            "isolation_p99_ratio": round(
                contended_p99 / solo_p99, 4) if solo_p99 > 0 else None,
            "victim_requests": int(beta_t.get("total") or 0),
            "victim_shed_rate": round(
                float(beta_t.get("shed_total") or 0)
                / max(1, int(beta_t.get("total") or 0)), 6),
            "aggressor_requests": int(alpha_t.get("total") or 0),
            "aggressor_shed_rate": round(
                float(alpha_t.get("shed_total") or 0)
                / max(1, int(alpha_t.get("total") or 0)), 4),
            "aggressor_quota_sheds": int(
                (alpha_t.get("sheds") or {}).get("tenant_quota", 0)),
            "poisoned_deploy_rejected": poisoned_rejected,
            "bad_params_served": int(bad_params),
            "all_typed": all(
                r.status in (Status.OK, Status.OVERLOADED,
                             Status.UNAVAILABLE,
                             Status.DEADLINE_EXCEEDED,
                             Status.CANCELLED)
                for r in alpha_res),
        }
    finally:
        fl.stop(timeout=15)


def run_tenant_bench() -> None:
    """--tenant mode: the multi-tenant noisy-neighbor pass — victim
    p99 ratio under an aggressor flood + poisoned aggressor deploy,
    victim shed rate, bad-params audit — writes TENANT_r01.json,
    prints the one JSON line."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "tenant", "backend": "cpu",
           "measured_at": _utc_now()}
    try:
        out.update(_tenant_measurements())
        out.update({
            "metric": "victim-tenant p99 ratio under aggressor flood",
            "value": out.get("isolation_p99_ratio") or 0.0,
            "unit": "x",
            "target": "ratio <= 1.25x solo, victim sheds 0, rejected "
                      "deploy installs nowhere, 0 bad params served",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric":
                    "victim-tenant p99 ratio under aggressor flood",
                    "value": 0.0, "unit": "x"})
    try:
        with open(os.path.join(_here(), TENANT_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Incident engine bench (--incident): chaos-scored causal attribution —
# five injected fault classes judged top-1 against the ground-truth
# chaos journal, clean-control false incidents, capture latency, and
# the amortized per-pump-round observe/journal tax
# --------------------------------------------------------------------------

INCIDENT_RESULT = "INCIDENT_r01.json"


def _incident_scenarios(eval_interval_s: float = 5.0,
                        steady_intervals: int = 200):
    """Deterministic attribution harness under an injected clock: five
    fault classes — replica kill, poisoned deploy, tenant flood,
    straggler delay, KV-pool exhaustion — each armed through the REAL
    chaos injectors (``resilience/faults.py`` journals ``chaos_inject``
    with ``ground_truth=True`` into the default change journal, pinned
    to the fake clock) while scripted metric streams breach an SLO rule
    and open an incident.  Benign distractor events (autoscale moves,
    confirmed deploys elsewhere, membership churn — including one
    landing AFTER the injection) are journaled around every arm, so
    top-1 blame is a genuine ranking problem, not a last-event grab.
    A full-length steady control run counts false incidents (the
    must-stay-zero)."""
    from bigdl_tpu.resilience import faults
    from bigdl_tpu.telemetry import (IncidentEngine, IncidentPolicy,
                                     MetricRecorder, MetricsRegistry,
                                     SloEngine, SloRule,
                                     reset_default_journal)
    from bigdl_tpu.telemetry import metric_names as M

    def build(rules):
        clk = {"t": 1000.0}
        rec = MetricRecorder(clock=lambda: clk["t"])
        jr = reset_default_journal(clock=lambda: clk["t"])
        eng = SloEngine(rec, rules=rules, registry=MetricsRegistry(),
                        clock=lambda: clk["t"])
        ie = IncidentEngine(
            rec, journal=jr, engine=eng, registry=MetricsRegistry(),
            policy=IncidentPolicy(
                pre_window_s=12 * eval_interval_s, post_intervals=2),
            clock=lambda: clk["t"])
        return {"clk": clk, "rec": rec, "jr": jr, "eng": eng,
                "ie": ie}

    def distractors(jr):
        # production-style (ground_truth=False) noise: scoped moves on
        # OTHER replicas/models and one fleet-wide membership change
        jr.record("autoscale_up", "scale decode 2->3",
                  source="serving.autoscale", pool="decode",
                  replica="r9")
        jr.record("deploy_confirmed", "version=v7 replicas=2",
                  source="serving.fleet", model="beta")
        jr.record("membership_change", "incarnation=4 reason=join",
                  source="resilience.elastic", host="host-2")

    scenarios = {}
    caps = []
    hits = 0

    def run_scenario(name, rules, feed, breach_feed, injector,
                     max_intervals=24):
        nonlocal hits
        st = build(rules)
        clk, rec, eng, ie, jr = (st["clk"], st["rec"], st["eng"],
                                 st["ie"], st["jr"])
        finalized = []

        def tick(breached):
            clk["t"] += eval_interval_s
            (breach_feed if breached else feed)(st)
            finalized.extend(ie.observe(eng.evaluate()))

        for _ in range(6):
            tick(False)
        distractors(jr)                # noise well before the fault
        for _ in range(4):
            tick(False)
        detect = None
        with injector():
            # late noise the proximity term must rank below the cause
            jr.record("autoscale_down", "scale decode 3->2",
                      source="serving.autoscale", pool="decode",
                      replica="r9")
            for i in range(1, max_intervals + 1):
                tick(True)
                if detect is None and ie.opened_total:
                    detect = i
                if finalized:
                    break
        inc = finalized[0].to_dict() if finalized else None
        top = ((inc or {}).get("suspects") or [{}])[0]
        hit = bool(top.get("ground_truth"))
        hits += int(hit)
        if inc is not None:
            caps.append(inc["capture_latency_s"])
        scenarios[name] = {
            "rule": rules[0].name,
            "detected_in_intervals": detect,
            "finalized": inc is not None,
            "top1_kind": top.get("kind"),
            "top1_scope": top.get("scope"),
            "top1_ground_truth": hit,
            "incident": inc,
        }

    L = {"replica": "r1"}

    def healthy_replica(st):
        st["rec"].observe(M.REPLICA_P99_SECONDS, 0.05, labels=L)
        st["rec"].observe(M.REPLICA_QUEUE_DEPTH, 2.0, labels=L)

    def silent_replica(st):
        # the kill: the feed stops, the absent rule trips
        st["rec"].observe(M.REPLICA_QUEUE_DEPTH, 2.0,
                          labels={"replica": "r9"})

    run_scenario(
        "replica_kill",
        [SloRule(name="replica/r1/health_feed",
                 family=M.REPLICA_P99_SECONDS, labels=L,
                 kind="absent",
                 window_s=2 * eval_interval_s + 1.0,
                 resolve_intervals=1,
                 description="replica r1 health feed went silent")],
        healthy_replica, silent_replica,
        lambda: faults.kill_replica("r1"))

    def steady_loss(st):
        st["rec"].observe(M.TRAIN_LOSS, st.setdefault("loss", 1.0))

    def diverging_loss(st):
        st["loss"] = st.setdefault("loss", 1.0) * 1.9
        st["rec"].observe(M.TRAIN_LOSS, st["loss"])

    def poisoned_deploy():
        # the loop ships the poisoned candidate: the (non-GT)
        # deploy_started the pipeline itself journals rides along
        ctx = faults.poison_candidate()
        from bigdl_tpu.telemetry.events import record_change
        record_change("deploy_started", "version=v8",
                      source="loop.continuous", model="alpha")
        return ctx

    run_scenario(
        "poisoned_deploy",
        [SloRule(name="training/loss_divergence",
                 family=M.TRAIN_LOSS, kind="threshold",
                 reduce="last", op=">=", threshold=3.0,
                 window_s=12 * eval_interval_s, for_intervals=2,
                 resolve_intervals=2,
                 description="training loss diverging")],
        steady_loss, diverging_loss, poisoned_deploy)

    TA = {"tenant": "alpha"}

    def calm_tenant(st):
        st["rec"].observe(M.AUTOSCALE_POOL_SHED_RATE, 0.0, labels=TA)

    def shedding_tenant(st):
        st["rec"].observe(M.AUTOSCALE_POOL_SHED_RATE, 0.5, labels=TA)

    run_scenario(
        "tenant_flood",
        [SloRule(name="tenant/alpha/shed_rate",
                 family=M.AUTOSCALE_POOL_SHED_RATE, labels=TA,
                 kind="threshold", reduce="last", op=">=",
                 threshold=0.2, window_s=6 * eval_interval_s,
                 for_intervals=2, resolve_intervals=2,
                 description="tenant alpha shedding")],
        calm_tenant, shedding_tenant,
        lambda: faults.tenant_flood("alpha", rps=64))

    R7 = {"replica": "r7"}

    def fast_replica(st):
        st["rec"].observe(M.REPLICA_P99_SECONDS, 0.05, labels=R7)

    def straggling_replica(st):
        st["rec"].observe(M.REPLICA_P99_SECONDS, 2.5, labels=R7)

    run_scenario(
        "straggler_delay",
        [SloRule(name="replica/r7/p99",
                 family=M.REPLICA_P99_SECONDS, labels=R7,
                 kind="threshold", reduce="last", op=">=",
                 threshold=1.0, window_s=6 * eval_interval_s,
                 for_intervals=2, resolve_intervals=2,
                 description="replica r7 p99 >= 1s")],
        fast_replica, straggling_replica,
        lambda: faults.delay_replica("r7", 0.4))

    R3 = {"replica": "r3"}

    def roomy_kv(st):
        st["rec"].observe(M.AUTOSCALE_POOL_KV_OCCUPANCY, 0.4,
                          labels=R3)

    def exhausted_kv(st):
        # partitioned from the fleet KV transport, its pages never
        # free: occupancy pins at the ceiling
        st["rec"].observe(M.AUTOSCALE_POOL_KV_OCCUPANCY, 0.99,
                          labels=R3)

    run_scenario(
        "kv_exhaustion",
        [SloRule(name="replica/r3/kv_occupancy",
                 family=M.AUTOSCALE_POOL_KV_OCCUPANCY, labels=R3,
                 kind="threshold", reduce="last", op=">=",
                 threshold=0.95, window_s=6 * eval_interval_s,
                 for_intervals=2, resolve_intervals=2,
                 description="replica r3 KV pool exhausted")],
        roomy_kv, exhausted_kv,
        lambda: faults.partition_kv("r3"))

    # --- steady control: full-length run, zero incidents expected ----
    st = build([SloRule(name="replica/r1/p99",
                        family=M.REPLICA_P99_SECONDS, labels=L,
                        kind="threshold", reduce="last", op=">=",
                        threshold=1.0,
                        window_s=6 * eval_interval_s,
                        for_intervals=2, resolve_intervals=2,
                        description="replica r1 p99 >= 1s"),
                SloRule(name="tenant/alpha/shed_rate",
                        family=M.AUTOSCALE_POOL_SHED_RATE, labels=TA,
                        kind="threshold", reduce="last", op=">=",
                        threshold=0.2,
                        window_s=6 * eval_interval_s,
                        for_intervals=2, resolve_intervals=2,
                        description="tenant alpha shedding")])
    for i in range(steady_intervals):
        st["clk"]["t"] += eval_interval_s
        healthy_replica(st)
        calm_tenant(st)
        if i % 20 == 0:       # routine churn must not open incidents
            distractors(st["jr"])
        st["ie"].observe(st["eng"].evaluate())
    false_incidents = st["ie"].opened_total

    reset_default_journal()   # unpin the fake clock
    detects = [s["detected_in_intervals"]
               for s in scenarios.values()]
    return {
        "eval_interval_s": eval_interval_s,
        "steady_intervals": steady_intervals,
        "scenarios": scenarios,
        "attribution_top1": hits,
        "attribution_total": len(scenarios),
        "attribution_top1_frac": round(hits / len(scenarios), 4),
        "all_finalized": all(s["finalized"]
                             for s in scenarios.values()),
        "max_detection_intervals": (max(detects)
                                    if all(d is not None
                                           for d in detects)
                                    else None),
        "capture_latency_s": (round(max(caps), 6) if caps else None),
        "false_incidents": int(false_incidents),
    }


def _incident_measurements(eval_interval_s: float = 5.0,
                           steady_intervals: int = 200,
                           pump_interval_s: float = 0.05):
    """The incident-engine leg: (1) the deterministic five-fault
    attribution harness + clean control, (2) the amortized tax an idle
    incident engine adds to each fleet pump round — one
    ``IncidentEngine.observe`` on the round's (empty) transitions plus
    one journal write, judged against the ``pump_interval_s`` cadence
    the engine actually rides (the ``FleetHealthMonitor`` chain)."""
    from bigdl_tpu.telemetry import (ChangeJournal, IncidentEngine,
                                     MetricRecorder, MetricsRegistry,
                                     SloEngine,
                                     default_training_rules)
    from bigdl_tpu.telemetry import metric_names as M

    out = _incident_scenarios(eval_interval_s=eval_interval_s,
                              steady_intervals=steady_intervals)

    # --- amortized per-round tax -------------------------------------
    jr = ChangeJournal(registry=MetricsRegistry())
    n = 50_000
    t0 = time.perf_counter()
    for i in range(n):
        jr.record("autoscale_up", "scale 2->3", pool="decode",
                  replica=f"r{i & 7}")
    record_ns = (time.perf_counter() - t0) / n * 1e9
    rec = MetricRecorder()
    eng = SloEngine(rec, rules=default_training_rules(),
                    registry=MetricsRegistry())
    ie = IncidentEngine(rec, journal=jr, engine=eng,
                        registry=MetricsRegistry())
    for i in range(2_000):     # fill the rings, engine steady
        rec.observe(M.TRAIN_LOSS, float(4_000 - i))
    n_obs = 20_000
    t0 = time.perf_counter()
    for _ in range(n_obs):
        ie.observe(())
    observe_us = (time.perf_counter() - t0) / n_obs * 1e6
    # one idle observe + one journal write per pump round — the
    # honest steady-state tax at the cadence the engine rides
    round_us = observe_us + record_ns * 1e-3
    overhead_pct = 100.0 * (round_us * 1e-6) / pump_interval_s

    out.update({
        "pump_interval_s": pump_interval_s,
        "journal_record_ns": round(record_ns, 0),
        "incident_observe_us": round(observe_us, 2),
        "overhead_pct": round(overhead_pct, 4),
    })
    return out


def run_incident_bench() -> None:
    """--incident mode: the incident-engine pass — top-1 causal
    attribution on five injected fault classes, clean-control false
    incidents, capture latency, amortized observe tax — writes
    INCIDENT_r01.json, prints the one JSON line."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = {"bench": "incident", "backend": "cpu",
           "measured_at": _utc_now()}
    try:
        out.update(_incident_measurements())
        out.update({
            "metric": "top-1 causal attribution on injected faults",
            "value": out.get("attribution_top1_frac") or 0.0,
            "unit": "frac",
            "target": ">= 4/5 top-1 vs ground truth, 0 false "
                      "incidents over the clean control, < 2% "
                      "observe overhead",
        })
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        out.update({"metric": "top-1 causal attribution on injected "
                              "faults",
                    "value": 0.0, "unit": "frac"})
    try:
        with open(os.path.join(_here(), INCIDENT_RESULT), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# The flat record tools/perf_sentinel.py compares against
# PERF_BASELINE.json (bench.py itself writes no ledger file)
# --------------------------------------------------------------------------

LEDGER_SCHEMA = 1

#: the schema-stable field set every ledger record carries (absent
#: measurements are explicit nulls, never missing keys — the sentinel
#: and any trend tooling can rely on the shape).  tools/perf_sentinel.py
#: checks a subset of these against PERF_BASELINE.json.
LEDGER_FIELDS = (
    "tpu", "stale", "backend", "device_kind", "metric", "value", "unit",
    "mfu", "mfu_basis", "resnet50_flops_per_step",
    "transformerlm_mfu", "transformerlm_T4096_mfu",
    "transformerlm_cpu_tokens_per_sec",
    "simplernn_records_per_sec", "lenet5_images_per_sec",
    "decode_tokens_per_sec", "prefill_tokens_per_sec",
    "serving_p99_ms", "serving_p50_ms",
    "fleet_p99_ms", "fleet_hedged_p99_ms", "fleet_shed_rate",
    "fleet_goodput_per_chip", "fleet_recovery_s",
    "trace_overhead_pct", "trace_p99_coverage",
    "disagg_ttft_p99_ms", "disagg_tpot_p99_ms",
    "disagg_paged_concurrency_x", "disagg_shed_rate",
    "elastic_recovery_s",
    "sdc_detection_latency_steps", "telemetry_overhead_pct",
    "goodput_productive_fraction", "goodput_accounted_fraction",
    "goodput_checkpoint_fraction", "data_stall_s",
    "checkpoint_blocked_s",
    "sharding_composed_steps_per_sec", "sharding_fsdp_param_bytes_frac",
    "dlrm_steps_per_sec", "dlrm_collective_bytes_per_step",
    "sync_periodic_steps_per_sec", "sync_bytes_per_step",
    "sync_straggler_advantage_x",
    "slo_detection_latency_s", "slo_false_positives",
    "slo_overhead_pct",
    "loop_goodput", "loop_rollback_latency_s",
    "loop_bad_params_served",
    "blocksparse_t4096_mfu", "blocksparse_speedup_x",
    "embed_migration_s", "embed_cache_hit_rate",
    "embed_bad_rows_served",
    "tenant_isolation_p99_ratio", "tenant_victim_shed_rate",
    "tenant_bad_params_served",
    "incident_attribution_top1", "incident_false_positives",
    "incident_capture_latency_s", "incident_overhead_pct",
    "vs_baseline",
)


def ledger_record(result: dict) -> dict:
    """Flatten one bench emit into the schema-stable ledger record."""
    flat = dict(result)
    flat["backend"] = "tpu" if result.get("tpu") else "cpu"
    serving = result.get("serving") or {}
    flat["serving_p99_ms"] = serving.get("p99_ms")
    flat["serving_p50_ms"] = serving.get("p50_ms")
    # the fleet leg (ISSUE 9): shed rate may only fall, goodput-per-
    # chip may only rise — tools/perf_sentinel.py guards the direction
    fleet = result.get("fleet") or {}
    flat["fleet_p99_ms"] = fleet.get("p99_ms")
    flat["fleet_hedged_p99_ms"] = fleet.get("hedged_p99_ms")
    flat["fleet_shed_rate"] = fleet.get("shed_rate")
    flat["fleet_goodput_per_chip"] = fleet.get("goodput_per_chip_flops")
    flat["fleet_recovery_s"] = fleet.get("recovery_s")
    # the distributed-tracing pass (ISSUE 13): traced-vs-untraced
    # overhead may only fall (abs floor absorbs scheduler jitter) and
    # the p99 cohort's stitched coverage may only rise — a fall means
    # replicas silently stopped publishing their fragments
    flat["trace_overhead_pct"] = fleet.get("trace_overhead_pct")
    flat["trace_p99_coverage"] = fleet.get("trace_p99_coverage")
    # the disagg leg (ISSUE 11): TTFT/TPOT may only fall, the paged
    # concurrency multiple may only rise, shed under the ramp may only
    # fall — tools/perf_sentinel.py guards the direction
    disagg = result.get("disagg") or {}
    flat["disagg_ttft_p99_ms"] = disagg.get("ttft_p99_ms")
    flat["disagg_tpot_p99_ms"] = disagg.get("tpot_p99_ms")
    flat["disagg_paged_concurrency_x"] = disagg.get(
        "paged_concurrency_x")
    flat["disagg_shed_rate"] = disagg.get("shed_rate")
    elastic = result.get("elastic") or {}
    flat["elastic_recovery_s"] = elastic.get("recovery_wall_clock_s")
    integrity = result.get("integrity") or {}
    flat["sdc_detection_latency_steps"] = integrity.get(
        "sdc_detection_latency_steps")
    telemetry = result.get("telemetry") or {}
    flat["telemetry_overhead_pct"] = telemetry.get("overhead_pct")
    # the goodput family (async-everything overlap engine, ISSUE 7):
    # productive fraction may only rise; stall/blocked seconds may
    # only fall — tools/perf_sentinel.py guards the direction
    for key in ("goodput_productive_fraction",
                "goodput_accounted_fraction",
                "goodput_checkpoint_fraction", "data_stall_s",
                "checkpoint_blocked_s"):
        flat[key] = telemetry.get(key)
    # the sharding-plan engine leg (ISSUE 8): composed-mesh throughput
    # may only rise; the FSDP per-device param fraction may only fall
    sharding = result.get("sharding") or {}
    flat["sharding_composed_steps_per_sec"] = sharding.get(
        "composed_steps_per_sec")
    flat["sharding_fsdp_param_bytes_frac"] = sharding.get(
        "fsdp_param_bytes_frac")
    # the DLRM sparse-transport leg (ISSUE 10): steps/sec may only
    # rise; measured collective bytes/step may only fall — the wire
    # win sparse transport exists for must never silently erode
    dlrm = result.get("dlrm") or {}
    flat["dlrm_steps_per_sec"] = dlrm.get("steps_per_sec")
    flat["dlrm_collective_bytes_per_step"] = dlrm.get(
        "collective_bytes_per_step")
    # the relaxed-synchrony leg (ISSUE 15): periodic(8) throughput may
    # only rise; its amortized collective bytes/step is a deterministic
    # plan/accounting property and may only fall — relaxed synchrony
    # must never silently stop paying; the straggler advantage (relax-
    # before-evict vs the eviction path on time-to-loss-target) may
    # only rise, with an absolute floor absorbing 1-core wall noise
    syncleg = result.get("sync") or {}
    flat["sync_periodic_steps_per_sec"] = syncleg.get(
        "periodic_steps_per_sec")
    flat["sync_bytes_per_step"] = syncleg.get(
        "periodic_collective_bytes_per_step")
    flat["sync_straggler_advantage_x"] = syncleg.get(
        "straggler_advantage_x")
    # the online health engine (ISSUE 14): detection latency may only
    # fall, the steady control's false-positive count must stay ZERO,
    # and the recorder+engine overhead may only fall — the online SLO
    # layer must never get slower to notice or noisier to trust
    slo = result.get("slo") or {}
    flat["slo_detection_latency_s"] = slo.get("detection_latency_s")
    flat["slo_false_positives"] = slo.get("false_positives")
    flat["slo_overhead_pct"] = slo.get("overhead_pct")
    # the continuous-learning loop (ISSUE 17): goodput while serving
    # may only rise, burn-rate rollback latency may only fall, and
    # bad-params-served is a must-stay-zero invariant — a serve of an
    # unverified param tree is never a regression to tolerate
    loop = result.get("loop") or {}
    flat["loop_goodput"] = loop.get("goodput")
    flat["loop_rollback_latency_s"] = loop.get("rollback_latency_s")
    flat["loop_bad_params_served"] = loop.get("bad_params_served")
    # the block-sparse kernel family (ISSUE 12): the T4096 MFU rides
    # the device battery's executed-basis row; the speedup multiple
    # prefers the battery's measured wall ratio and otherwise takes the
    # CPU leg's deterministic executed-work reduction (a count)
    bs = result.get("blocksparse") or {}
    flat["blocksparse_t4096_mfu"] = flat.get(
        "transformerlm_blocksparse_T4096_mfu")
    flat["blocksparse_speedup_x"] = (
        flat.get("transformerlm_blocksparse_T4096_speedup_x")
        or bs.get("speedup_x"))
    # the embedding-store leg (ISSUE 18): 1-host re-partition wall may
    # only fall, the Zipf hot-row cache hit rate may only rise, and
    # bad-rows-served is a must-stay-zero invariant — a row served at
    # a retired table version is never a regression to tolerate
    embed = result.get("embed") or {}
    flat["embed_migration_s"] = embed.get("migration_s")
    flat["embed_cache_hit_rate"] = embed.get("cache_hit_rate")
    flat["embed_bad_rows_served"] = embed.get("bad_rows_served")
    # the multi-tenant leg (ISSUE 19): the victim tenant's p99 ratio
    # under an aggressor flood may only fall (abs floor absorbs
    # scheduler jitter), and the victim shed rate + bad-params audit
    # are must-stay-zero invariants — a victim request billed to the
    # aggressor's flood is never a regression to tolerate
    tenant = result.get("tenant") or {}
    flat["tenant_isolation_p99_ratio"] = tenant.get(
        "isolation_p99_ratio")
    flat["tenant_victim_shed_rate"] = tenant.get("victim_shed_rate")
    flat["tenant_bad_params_served"] = tenant.get("bad_params_served")
    # the incident-engine leg (ISSUE 20): top-1 attribution vs the
    # ground-truth chaos journal may only rise, the clean control's
    # false-incident count must stay ZERO, and capture latency +
    # amortized observe overhead may only fall — blame that gets
    # vaguer, noisier or slower to freeze is never a regression to
    # tolerate
    incident = result.get("incident") or {}
    flat["incident_attribution_top1"] = incident.get(
        "attribution_top1_frac")
    flat["incident_false_positives"] = incident.get("false_incidents")
    flat["incident_capture_latency_s"] = incident.get(
        "capture_latency_s")
    flat["incident_overhead_pct"] = incident.get("overhead_pct")
    rec = {"schema": LEDGER_SCHEMA,
           "ts": result.get("measured_at") or _utc_now(),
           "recorded_at": _utc_now()}
    for key in LEDGER_FIELDS:
        rec[key] = flat.get(key)
    return rec


def _utc_now() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


def _here() -> str:
    return os.path.dirname(os.path.abspath(__file__))


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    legs = {
        "serving": run_serving_bench, "fleet": run_fleet_bench,
        "trace": run_trace_bench, "disagg": run_disagg_bench,
        "elastic": run_elastic_bench, "integrity": run_integrity_bench,
        "telemetry": run_telemetry_bench, "sharding": run_sharding_bench,
        "dlrm": run_dlrm_bench, "sync": run_sync_bench,
        "slo": run_slo_bench, "loop": run_loop_bench,
        "blocksparse": run_blocksparse_bench, "embed": run_embed_bench,
        "tenant": run_tenant_bench, "incident": run_incident_bench,
    }
    group = p.add_mutually_exclusive_group()
    for name in legs:
        group.add_argument("--" + name, dest="leg", action="store_const",
                           const=name, help=f"host-side {name} leg (CPU)")
    a = p.parse_args()
    if a.leg:
        legs[a.leg]()
    else:
        run_device_battery()
