"""Perf harness (reference models/utils/LocalOptimizerPerf.scala and
DistriOptimizerPerf.scala:32 — SURVEY §2.5 'Perf harness').

Times the full train step (forward + backward + update) of the zoo's
ImageNet workloads on constant/random input, logging per-iteration
wall time and average records/second, matching the reference's
measured quantity (DistriOptimizer.scala:295-297 log line).

Usage:
    python -m bigdl_tpu.models.perf -m inception_v1 -b 32 -i 10
    python -m bigdl_tpu.models.perf -m resnet50 --distributed  # data-parallel
"""
from __future__ import annotations

import argparse
import logging
import time

import numpy as np

log = logging.getLogger("bigdl_tpu")


MODELS = ("inception_v1", "inception_v2", "vgg16", "vgg19", "resnet50",
          "lenet5")


def build_model(name: str, class_num: int = 1000):
    from . import inception, lenet, resnet, vgg

    name = name.lower()
    if name == "inception_v1":
        return inception.Inception_v1(class_num), (3, 224, 224)
    if name == "inception_v2":
        return inception.Inception_v2(class_num), (3, 224, 224)
    if name == "vgg16":
        return vgg.Vgg16(class_num), (3, 224, 224)
    if name == "vgg19":
        return vgg.Vgg19(class_num), (3, 224, 224)
    if name == "resnet50":
        return resnet.ResNet50(class_num), (3, 224, 224)
    if name == "lenet5":
        return lenet.LeNet5(10), (1, 28, 28)
    raise ValueError(f"model must be one of {MODELS}")


def performance(model_name: str, batch_size: int, iterations: int,
                input_data: str = "random", warmup: int = 2,
                distributed: bool = False, dtype: str = "float32"):
    import jax
    import jax.numpy as jnp

    from .. import nn
    from ..optim.optim_method import SGD

    model, shape = build_model(model_name)
    criterion = nn.ClassNLLCriterion()
    optim = SGD(learning_rate=0.01)

    rng = np.random.RandomState(1)
    host_x = (np.full((batch_size,) + shape, 0.01, np.float32)
              if input_data == "constant"
              else rng.rand(batch_size, *shape).astype(np.float32))
    cdtype = jnp.bfloat16 if dtype == "bfloat16" else None
    x = jnp.asarray(host_x, cdtype or jnp.float32)
    y = jnp.ones((batch_size,), jnp.float32)

    params, buffers = model.param_tree(), model.buffer_tree()
    slots = optim.init_state(params)

    def step(p, b, s, xx, yy):
        def loss_fn(pp):
            if cdtype is not None:
                # bf16 compute / f32 master weights: grads arrive f32
                # through the cast's vjp (same scheme as the drivers'
                # set_compute_dtype)
                pp_c = jax.tree_util.tree_map(
                    lambda a: a.astype(cdtype)
                    if a.dtype == jnp.float32 else a, pp)
            else:
                pp_c = pp
            out, nb = model.apply_fn(pp_c, b, xx, True,
                                     jax.random.PRNGKey(0))
            return criterion._loss(jnp.asarray(out, jnp.float32), yy), nb

        (loss, nb), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
        new_p, new_s = optim.step(grads, p, s, 0.01)
        return loss, new_p, nb, new_s

    if distributed and jax.device_count() > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()), ("data",))
        xs = NamedSharding(mesh, P("data"))
        rep = NamedSharding(mesh, P())
        x = jax.device_put(x, xs)
        y = jax.device_put(y, xs)
        params = jax.device_put(params, rep)
        step = jax.jit(step, in_shardings=(rep, rep, rep, xs, xs),
                       out_shardings=(rep, rep, rep, rep),
                       donate_argnums=(0, 1, 2))
    else:
        step = jax.jit(step, donate_argnums=(0, 1, 2))

    for _ in range(warmup):
        loss, params, buffers, slots = step(params, buffers, slots, x, y)
    float(loss)  # value fetch = execution barrier (docs/PERF.md)

    times = []
    for i in range(iterations):
        t0 = time.perf_counter()
        loss, params, buffers, slots = step(params, buffers, slots, x, y)
        loss_v = float(loss)  # value fetch = execution barrier
        dt = time.perf_counter() - t0
        times.append(dt)
        log.info(
            "Iteration %d %s batch %d: %.1f ms, throughput %.2f "
            "records/second, loss %.4f", i + 1, model_name, batch_size,
            dt * 1000, batch_size / dt, loss_v)
    avg = float(np.mean(times))
    log.info(
        "Average throughput is %.2f records/second (avg iteration "
        "%.1f ms over %d runs)", batch_size / avg, avg * 1000,
        iterations)
    return batch_size / avg


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="bigdl_tpu perf harness (reference *OptimizerPerf)")
    parser.add_argument("-m", "--model", default="inception_v1",
                        choices=MODELS)
    parser.add_argument("-b", "--batchSize", type=int, default=32)
    parser.add_argument("-i", "--iteration", type=int, default=10)
    parser.add_argument("-d", "--inputdata", default="random",
                        choices=("constant", "random"))
    parser.add_argument("--distributed", action="store_true",
                        help="data-parallel over all visible devices")
    parser.add_argument("--dtype", default="float32",
                        choices=("float32", "bfloat16"))
    parser.add_argument("--conv-impl", default=None,
                        choices=("xla", "xla_nhwc", "gemm", "pallas"),
                        help="conv lowering (bigdl.conv.impl property)")
    args = parser.parse_args(argv)
    if args.conv_impl:
        import os

        os.environ["bigdl.conv.impl"] = args.conv_impl
    from ..utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    performance(args.model, args.batchSize, args.iteration, args.inputdata,
                distributed=args.distributed, dtype=args.dtype)


if __name__ == "__main__":
    main()
