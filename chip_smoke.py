#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

Drives the normal entry points once, in ONE process, at the full width
of the model the repo has the most history on (TransformerLM V 32000,
D 1024, 8 heads of 128, 8 layers, T 1024, bf16, batch 16 per chip,
random weights and a learnable token stream, both from a seed):

  kernels     flash attention fwd + grads and fused LayerNorm against
              the dense references, at the model's per-chip shapes
  train       ``LocalOptimizer(...).optimize()`` with a checkpoint and a
              validation pass; the loss must fall, the checkpoint must
              pass its crc32c check, the compiled step must hold Mosaic
              custom calls
  serve       ``InferenceServer(model).start()`` and generate requests
              of several prompt lengths; every result OK, ``max_new``
              tokens, each the (near-)argmax of a dense teacher-forced
              forward
  train x4    ``DistriOptimizer`` over a four-chip data mesh, when the
              host has four chips: placement, per-device memory, and a
              profiler-traced step with collective time

Every phase is fatal.  Without an accelerator the script exits non-zero
and prints no result; ``--rehearse-cpu`` runs a toy-size rehearsal of the
same control flow on the CPU and says so.  The last line of stdout is
one JSON object naming the device as jax reports it.

    python chip_smoke.py                 # on a machine with a TPU
    python chip_smoke.py --rehearse-cpu  # toy size, CPU, not a chip run
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import sys
import tempfile
import time

import numpy as np

FULL = dict(vocab=32000, embed=1024, heads=8, layers=8, seq=1024,
            batch_per_chip=16, cycle=512, steps=8, steps_mesh=12,
            prompt_lens=(96, 200, 384), max_new=16,
            # (B, H, T, D): the train step's own attention shape (block
            # 512) and the long-context one (block 1024, the VMEM edge)
            flash_shapes=((2, 8, 1024, 128), (1, 8, 4096, 128)),
            ln_shape=(16384, 1024))
TOY = dict(vocab=128, embed=64, heads=2, layers=1, seq=64,
           batch_per_chip=4, cycle=16, steps=8, steps_mesh=12,
           prompt_lens=(5, 16), max_new=4,
           flash_shapes=((1, 2, 64, 32),), ln_shape=(64, 64))
SEED = 20260926
LR = 3e-4


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def make_samples(n: int, cfg: dict, seed: int):
    """A learnable stream from a seed: each row walks a cycle of
    ``cfg['cycle']`` tokens from a random start; target = next token."""
    from bigdl_tpu.dataset.sample import Sample

    r = np.random.RandomState(seed)
    t = np.arange(cfg["seq"] + 1)
    out = []
    for s in r.randint(0, cfg["cycle"], n):
        seq = ((s + t) % cfg["cycle"] + 1).astype(np.float32)
        out.append(Sample(seq[:-1], seq[1:]))
    return out


def build_model(cfg: dict):
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils.rng import set_global_seed

    set_global_seed(SEED)
    return TransformerLM(cfg["vocab"], embed_dim=cfg["embed"],
                         num_heads=cfg["heads"], num_layers=cfg["layers"],
                         max_len=cfg["seq"], seq_strategy="flash",
                         output="logits")


# --------------------------------------------------------------------------
# phase: kernels against their dense references
# --------------------------------------------------------------------------

def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-6))


def phase_kernels(cfg: dict) -> None:
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops import flash_attention, fused_layer_norm
    from bigdl_tpu.parallel.ring_attention import attention

    for shape in cfg["flash_shapes"]:
        ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
        q, k, v, w = (jax.random.normal(kk, shape, jnp.bfloat16)
                      for kk in ks)

        def run(fn):
            f = jax.jit(jax.value_and_grad(
                lambda q, k, v: jnp.sum(
                    fn(q, k, v).astype(jnp.float32) * w), (0, 1, 2)))
            t0 = time.perf_counter()
            out = jax.block_until_ready(f(q, k, v))
            t1 = time.perf_counter()
            jax.block_until_ready(f(q, k, v))
            return out, t1 - t0, time.perf_counter() - t1

        (lk, gk), first_s, again_s = run(
            lambda q, k, v: flash_attention(q, k, v, causal=True))
        (lr, gr), _, _ = run(lambda q, k, v: attention(q, k, v, True))
        errs = [abs(float(lk) - float(lr)) / max(abs(float(lr)), 1e-6)]
        errs += [_rel_err(a, b) for a, b in zip(gk, gr)]
        say(f"[kernels] flash {shape} bf16 causal: first call "
            f"{first_s:.2f}s, second {again_s:.4f}s, rel err vs dense "
            f"(loss, dq, dk, dv) = "
            + ", ".join(f"{e:.2e}" for e in errs))
        check(all(np.isfinite(errs)) and max(errs) < 4e-2,
              f"flash attention {shape} disagrees with the dense "
              f"reference: {errs}")

    rows, feat = cfg["ln_shape"]
    kx, kg, kb = jax.random.split(jax.random.PRNGKey(SEED + 1), 3)
    x = jax.random.normal(kx, (rows, feat), jnp.bfloat16)
    g = 1.0 + 0.1 * jax.random.normal(kg, (feat,), jnp.float32)
    b = 0.1 * jax.random.normal(kb, (feat,), jnp.float32)
    got = jax.block_until_ready(jax.jit(fused_layer_norm)(x, g, b))
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    want = (xf - mean) * jax.lax.rsqrt(
        ((xf - mean) ** 2).mean(-1, keepdims=True) + 1e-5) * g + b
    err = _rel_err(got, want)
    say(f"[kernels] fused LayerNorm {(rows, feat)} bf16: rel err vs jnp "
        f"= {err:.2e}")
    check(np.isfinite(err) and err < 2e-2,
          f"fused LayerNorm disagrees with the jnp reference: {err}")


# --------------------------------------------------------------------------
# phase: train through the product driver (one chip, or a data mesh)
# --------------------------------------------------------------------------

def phase_train(cfg: dict, mesh, on_tpu: bool):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.dataset.dataset import array
    from bigdl_tpu.optim import (Adam, DistriOptimizer, LocalOptimizer, Loss,
                                 max_iteration, several_iteration)
    from bigdl_tpu.resilience.checkpoint import verify_file
    from bigdl_tpu.utils import file_io
    from bigdl_tpu.visualization.summary import (TrainSummary,
                                                 ValidationSummary)

    n_chips = 1 if mesh is None else int(mesh.devices.size)
    tag = "train" if mesh is None else f"train x{n_chips}"
    steps = cfg["steps"] if mesh is None else cfg["steps_mesh"]
    batch = cfg["batch_per_chip"] * n_chips
    model = build_model(cfg)
    crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(), True)
    train_samples = make_samples(batch * steps, cfg, SEED + 2)
    val_batch = max(n_chips, 4)
    val_samples = make_samples(2 * val_batch, cfg, SEED + 3)
    # The checkpoint goes to the product's in-process ``memory://``
    # store (utils/file_io.py): at full width its legs are single files
    # of 0.67 GB and 1.3 GB, and a chip machine may refuse files that
    # large (the driver's did: EFBIG on model.5).  Snapshot, serialize,
    # background writer and crc32c sidecars are the same code as on disk.
    ckpt = f"memory://chip_smoke/{os.getpid()}/{tag.replace(' ', '_')}"

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if mesh is None:
            opt = LocalOptimizer(model, array(train_samples), crit,
                                 batch_size=batch)
        else:
            opt = DistriOptimizer(model, array(train_samples), crit,
                                  batch_size=batch, mesh=mesh)
        opt.set_optim_method(Adam(LR))
        opt.set_compute_dtype(jnp.bfloat16)
        opt.set_end_when(max_iteration(steps))
        # each trigger fires exactly once, mid-run
        opt.set_checkpoint(ckpt, several_iteration(steps - 3))
        opt.set_validation(several_iteration(steps - 2),
                           array(val_samples), [Loss(crit)],
                           batch_size=val_batch)
        train_summary = TrainSummary(tmp, "smoke")
        val_summary = ValidationSummary(tmp, "smoke")
        opt.set_train_summary(train_summary)
        opt.set_validation_summary(val_summary)
        # keep the compiled engine so the step that RAN can be inspected
        opt.reuse_compiled_engine = True

        t0 = time.perf_counter()
        opt.optimize()
        wall = time.perf_counter() - t0

        losses = [v for _, v in train_summary.read_scalar("Loss")]
        # the driver's own host clock per iteration; it ends in the
        # loss fetch, which waits for the step
        secs = [batch / max(v, 1e-9)
                for _, v in train_summary.read_scalar("Throughput")]
        val = val_summary.read_scalar("Loss")
        train_summary.close()
        val_summary.close()
    ckpts = sorted(file_io.listdir(ckpt)) if file_io.isdir(ckpt) else []
    legs = [f for f in ckpts if not f.startswith(".")]
    verified = {f: verify_file(file_io.join(ckpt, f)) for f in legs}
    if ckpts:  # give the host memory back before the next phase
        file_io.filesystem_for(ckpt).fs.rm(ckpt, recursive=True)

    check(len(losses) == steps, f"{tag}: {len(losses)} of {steps} steps "
          "reached the train summary")
    steady = float(np.median(secs[1:]))
    say(f"[{tag}] {steps} steps, global batch {batch} x T {cfg['seq']}, "
        f"optimize() wall {wall:.1f}s; first step {secs[0]:.2f}s "
        f"(compile ~{max(secs[0] - steady, 0.0):.2f}s), steady step "
        f"median {steady:.4f}s (min {min(secs[1:]):.4f}s, max "
        f"{max(secs[1:]):.4f}s; checkpoint, validation and any traced "
        "step are among them)")
    say(f"[{tag}] losses: " + " ".join(f"{v:.4f}" for v in losses))
    say(f"[{tag}] validation Loss at iteration "
        + ", ".join(f"{s}: {v:.4f}" for s, v in val)
        + f"; checkpoint in {ckpt}: {ckpts}, crc32c verified {verified}")
    check(all(np.isfinite(losses)), f"{tag}: non-finite loss {losses}")
    check(losses[-1] < losses[0], f"{tag}: loss did not fall "
          f"({losses[0]:.4f} -> {losses[-1]:.4f})")
    check(len(val) == 1 and np.isfinite(val[0][1]),
          f"{tag}: validation did not run exactly once: {val}")
    n_ck = steps - 3
    check(legs == [f"model.{n_ck}", f"optimMethod.{n_ck}",
                   f"trainState.{n_ck}"] and all(verified.values()),
          f"{tag}: the step-{n_ck} checkpoint is missing a leg or fails "
          f"its crc32c sidecar: {ckpts}, {verified}")
    check(opt.rollbacks == 0, f"{tag}: the retry loop rolled back "
          f"{opt.rollbacks} time(s)")

    # -- the step that ran: Mosaic calls, placement, memory --------------
    engine = opt._engine_cache[1]
    params, slots, buffers = engine.init_state()
    x = np.stack([s.feature for s in train_samples[:batch]])
    y = np.stack([s.label for s in train_samples[:batch]])
    if n_chips > 1:
        x, y = engine.place_batch(x), engine.place_batch(y)
    t0 = time.perf_counter()
    hlo = engine.jitted_for(x, y, False).lower(
        params, slots, buffers, jnp.float32(LR), jax.random.PRNGKey(0),
        x, y).compile().as_text()
    n_mosaic = hlo.count('custom_call_target="tpu_custom_call"')
    say(f"[{tag}] compiled train step: {n_mosaic} Mosaic custom calls "
        f"(tpu_custom_call), {len(hlo.splitlines())} HLO lines; "
        f"re-lower + compile for inspection {time.perf_counter() - t0:.2f}s")
    if on_tpu:
        check(n_mosaic > 0, f"{tag}: no Mosaic custom call in the "
              "compiled train step — the Pallas kernels are not on the "
              "path")

    if n_chips > 1:
        leaves = jax.tree_util.tree_leaves(params)
        dev_counts = {len(a.sharding.device_set) for a in leaves}
        shard_shapes = [tuple(s.data.shape) for s in x.addressable_shards]
        by_dev = engine.param_bytes_by_device(params)
        say(f"[{tag}] params on {sorted(dev_counts)} devices per leaf; "
            f"batch on {len(x.sharding.device_set)} devices, shards "
            f"{shard_shapes}; param bytes by device {by_dev}")
        check(dev_counts == {n_chips}, f"{tag}: params not on all "
              f"{n_chips} devices: {dev_counts}")
        check(len(x.sharding.device_set) == n_chips
              and shard_shapes == [(cfg["batch_per_chip"], cfg["seq"])]
              * n_chips, f"{tag}: batch not split in {n_chips}: "
              f"{shard_shapes}")
        check(len(by_dev) == n_chips and min(by_dev.values()) > 0,
              f"{tag}: parameter bytes missing on a device: {by_dev}")
        split = opt.phase_split
        say(f"[{tag}] traced step (every tenth): phase_source="
            f"{opt.phase_source!r}, split={split} (device seconds "
            "summed over the mesh)")
        check(split is not None,
              f"{tag}: the profiler-traced step produced no phase split "
              "(see the warnings above)")
        check(split.collective_s > 0.0 and split.compute_s > 0.0,
              f"{tag}: traced split has no collective time: {split}")
    devices = jax.devices()[:n_chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    say(f"[{tag}] peak_bytes_in_use per device: {peaks}")
    if on_tpu:
        check(all(p for p in peaks), f"{tag}: a device reports no peak "
              f"memory: {peaks}")
    del params, slots, buffers, engine, opt
    gc.collect()
    return model


# --------------------------------------------------------------------------
# phase: serve through InferenceServer
# --------------------------------------------------------------------------

def phase_serve(cfg: dict, model) -> None:
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.serving import InferenceServer

    r = np.random.RandomState(SEED + 4)
    t = np.arange(max(cfg["prompt_lens"]))
    prompts = [((s + t[:n]) % cfg["cycle"] + 1).astype(np.int32)
               for s, n in zip(r.randint(0, cfg["cycle"],
                                         len(cfg["prompt_lens"])),
                               cfg["prompt_lens"])]
    max_new = cfg["max_new"]
    srv = InferenceServer(model, generate_dtype=jnp.bfloat16).start()
    try:
        rounds = []
        for _ in range(2):  # first round compiles, second is steady
            futs = [srv.submit_generate(p, max_new) for p in prompts]
            rounds.append([f.result(timeout=900) for f in futs])
    finally:
        srv.stop(60)

    params, buffers = model.param_tree(), model.buffer_tree()
    fwd = jax.jit(lambda p, ids: model.apply_fn(p, buffers, ids, False,
                                                None)[0])
    for p, cold, warm in zip(prompts, *rounds):
        for res in (cold, warm):
            check(res.ok, f"serve: prompt {len(p)} resolved "
                  f"{res.status.name}: {res.error}")
        out = np.asarray(warm.output).reshape(-1)
        check(out.shape == (max_new,), f"serve: prompt {len(p)} returned "
              f"{out.shape} tokens, want {max_new}")
        check(np.array_equal(out, np.asarray(cold.output).reshape(-1)),
              f"serve: prompt {len(p)} decoded differently on repeat")
        # teacher forcing: a dense f32 forward over prompt + output must
        # rank each emitted token first (or within bf16 noise of first)
        ids = np.concatenate([p, out])[None].astype(np.float32)
        logits = np.asarray(fwd(params, ids),
                            np.float32)[0, len(p) - 1:-1]
        chosen = logits[np.arange(max_new), out - 1]
        gap = logits.max(-1) - chosen
        spread = logits.max(-1) - logits.min(-1)
        exact = int(np.sum(out - 1 == logits.argmax(-1)))
        say(f"[serve] prompt {len(p):4d} -> {max_new} tokens OK; first "
            f"request {cold.latency_s:.2f}s (compiles), repeat "
            f"{warm.latency_s:.4f}s; teacher-forced dense forward agrees "
            f"on {exact}/{max_new} argmaxes, worst gap "
            f"{float(gap.max()):.4f} of logit spread "
            f"{float(spread.mean()):.2f}; tokens {out.tolist()}")
        check(bool(np.all(gap <= 0.02 * spread + 1e-3)),
              f"serve: prompt {len(p)} emitted a token the dense forward "
              f"does not rank first: gaps {gap.tolist()}")


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy-size rehearsal of the control flow on the "
                         "CPU backend; NOT a chip run")
    args = ap.parse_args(argv)

    import logging

    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(levelname)s %(message)s")
    import jax
    import jaxlib
    from jax.sharding import Mesh

    from bigdl_tpu import native
    from bigdl_tpu.telemetry.device_info import current_device_spec
    from bigdl_tpu.utils.compile_cache import (CACHE_DIR_ENV,
                                               ensure_compile_cache)

    cache_dir = ensure_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        print(f"chip_smoke: no TPU (jax {jax.__version__} found platform "
              f"{dev.platform!r}, device_kind {dev.device_kind!r}); this "
              "is a chip check — pass --rehearse-cpu for a toy-size CPU "
              "rehearsal", file=sys.stderr)
        return 1
    if on_tpu and args.rehearse_cpu:
        print("chip_smoke: --rehearse-cpu asked for on a TPU backend; "
              "launch it with JAX_PLATFORMS=cpu", file=sys.stderr)
        return 1
    say(f"[env] jax {jax.__version__}, jaxlib {jaxlib.__version__}; "
        f"platform {dev.platform}, device_kind {dev.device_kind!r}, "
        f"{len(devices)} device(s)")
    say(f"[env] compile cache: {cache_dir} ("
        + (f"placed by {CACHE_DIR_ENV}, nothing set in code"
           if os.environ.get(CACHE_DIR_ENV) else "the fixed in-checkout "
           "default") + f"); native host runtime loaded: "
        f"{native.available()}; file size limit (RLIMIT_FSIZE, -1 = "
        f"none): {resource.getrlimit(resource.RLIMIT_FSIZE)}")
    cfg = TOY if args.rehearse_cpu else FULL
    if args.rehearse_cpu:
        say("[env] CPU REHEARSAL at toy size — control flow only, no "
            "Mosaic kernels, nothing here is a device reading")
    spec = current_device_spec(dev)
    say(f"[env] device spec row {spec.kind!r}: peak "
        f"{spec.peak_flops_per_sec:.3g} FLOP/s, HBM {spec.hbm_bytes} B at "
        f"{spec.hbm_bytes_per_sec:.3g} B/s, nominal={spec.nominal}")

    hits = {"hits": 0, "misses": 0}

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            hits["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            hits["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    t_start = time.perf_counter()
    phase_kernels(cfg)
    model = phase_train(cfg, None, on_tpu)
    phase_serve(cfg, model)
    del model
    gc.collect()
    if len(devices) >= 4:
        phase_train(cfg, Mesh(np.array(devices[:4]), ("data",)), on_tpu)
    else:
        say(f"[train x4] skipped: this host has {len(devices)} device(s); "
            "the four-chip phase needs four")
    say(f"[env] persistent compile cache: {hits['hits']} hit(s), "
        f"{hits['misses']} miss(es) this run; all phases passed in "
        f"{time.perf_counter() - t_start:.1f}s")
    result = {"ok": True, "device": {"platform": dev.platform,
                                     "kind": dev.device_kind,
                                     "count": len(devices)}}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
