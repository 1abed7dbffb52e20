"""Independent plain-JAX ResNet-50 twin — the conv-MFU ceiling probe.

VERDICT r3 #1: the 13.7 % ResNet-50 MFU claim ("XLA's conv lowering is
the ceiling") needs an INDEPENDENT implementation on the same chip to
rule out this framework's layouts/graph as the cause.  This file is
that twin: no framework modules, no Torch-semantics facade, no NCHW
heritage — raw jax functions, NHWC activations (TPU-native layout),
HWIO weights, bf16 compute with f32 master weights, fused-by-XLA
BN+ReLU, one jitted donated train step.  If THIS lands at the same MFU,
the ceiling is XLA's conv lowering, not the framework.

``conv_impl="gemm"`` swaps every conv for the k²-matmul lowering
(ops/conv_gemm.py) to test whether reformulating conv as MXU-shaped
matmuls beats the native lowering end-to-end.

The lab that ran it on hardware left in PR 29; its verdicts are in
docs/PERF.md, and ``tests/test_conv_gemm.py`` keeps it running.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.conv_gemm import conv2d_gemm_nhwc

# ResNet-50 stage plan: (blocks, mid_channels, stride of first block)
STAGES = ((3, 64, 1), (4, 128, 2), (6, 256, 2), (3, 512, 2))


def _conv(x, w, stride, padding, impl, layout="nhwc"):
    if layout == "nchw":
        # the layout-decomposition probe: identical math, activations
        # flowing NCHW like the framework — isolates how much of the
        # twin-vs-framework gap is logical layout vs facade
        if padding == "SAME":
            pads = "SAME"
        else:
            pads = ((padding[0], padding[0]), (padding[1], padding[1]))
        return lax.conv_general_dilated(
            x, w, (stride, stride), pads,
            dimension_numbers=("NCHW", "HWIO", "NCHW"),
            preferred_element_type=jnp.float32 if x.dtype == jnp.float32
            else None)
    if (impl == "pallas" and w.shape[:2] == (3, 3) and stride == 1
            and padding == (1, 1)):
        from ..ops.conv3x3_pallas import conv3x3_s1_same

        return conv3x3_s1_same(x, w)
    if impl == "pallas":
        impl = "xla"  # non-3×3/s1 shapes keep the native lowering
    if impl == "gemm":
        return conv2d_gemm_nhwc(x, w, stride=(stride, stride),
                                padding=padding)
    if padding == "SAME":
        pads = "SAME"
    else:
        pads = ((padding[0], padding[0]), (padding[1], padding[1]))
    return lax.conv_general_dilated(
        x, w, (stride, stride), pads,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32 if x.dtype == jnp.float32
        else None)


def _bn(x, p, training, eps=1e-5, layout="nhwc"):
    red = (0, 1, 2) if layout == "nhwc" else (0, 2, 3)
    shp = (1, -1, 1, 1) if layout == "nchw" else (-1,)
    if training:
        mean = jnp.mean(x, axis=red)
        var = jnp.var(x, axis=red)
    else:
        mean, var = p["mean"], p["var"]
    inv = lax.rsqrt(var.astype(jnp.float32) + eps).astype(x.dtype)
    return ((x - mean.reshape(shp)) * inv.reshape(shp)
            * p["gamma"].reshape(shp) + p["beta"].reshape(shp))


def _init_conv(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return (jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
            * np.sqrt(2.0 / fan_in))


def _init_bn(c):
    return {"gamma": jnp.ones((c,), jnp.float32),
            "beta": jnp.zeros((c,), jnp.float32),
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32)}


def init_params(key, num_classes=1000):
    keys = iter(jax.random.split(key, 64))
    p = {"stem": {"w": _init_conv(next(keys), 7, 7, 3, 64),
                  "bn": _init_bn(64)}}
    cin = 64
    for si, (blocks, mid, _) in enumerate(STAGES):
        stage = []
        for bi in range(blocks):
            blk = {"w1": _init_conv(next(keys), 1, 1, cin, mid),
                   "bn1": _init_bn(mid),
                   "w2": _init_conv(next(keys), 3, 3, mid, mid),
                   "bn2": _init_bn(mid),
                   "w3": _init_conv(next(keys), 1, 1, mid, mid * 4),
                   "bn3": _init_bn(mid * 4)}
            if bi == 0:
                blk["wd"] = _init_conv(next(keys), 1, 1, cin, mid * 4)
                blk["bnd"] = _init_bn(mid * 4)
            stage.append(blk)
            cin = mid * 4
        p[f"stage{si}"] = stage
    k = next(keys)
    p["fc"] = {"w": jax.random.normal(k, (cin, num_classes), jnp.float32)
               * np.sqrt(1.0 / cin),
               "b": jnp.zeros((num_classes,), jnp.float32)}
    return p


def _bottleneck(x, blk, stride, training, impl, layout="nhwc"):
    y = _conv(x, blk["w1"], 1, (0, 0), impl, layout)
    y = jax.nn.relu(_bn(y, blk["bn1"], training, layout=layout))
    y = _conv(y, blk["w2"], stride, (1, 1), impl, layout)
    y = jax.nn.relu(_bn(y, blk["bn2"], training, layout=layout))
    y = _conv(y, blk["w3"], 1, (0, 0), impl, layout)
    y = _bn(y, blk["bn3"], training, layout=layout)
    if "wd" in blk:
        x = _bn(_conv(x, blk["wd"], stride, (0, 0), impl, layout),
                blk["bnd"], training, layout=layout)
    return jax.nn.relu(y + x)


def forward(params, x, training=True, impl="xla", layout="nhwc"):
    """x: [B, 224, 224, 3] NHWC → logits [B, classes].  ``layout=
    "nchw"`` transposes once at entry and flows NCHW throughout (the
    layout-decomposition probe)."""
    if layout == "nchw":
        x = x.transpose(0, 3, 1, 2)
        pool_win, pool_str = (1, 1, 3, 3), (1, 1, 2, 2)
        pool_pad = ((0, 0), (0, 0), (1, 1), (1, 1))
        spatial = (2, 3)
    else:
        pool_win, pool_str = (1, 3, 3, 1), (1, 2, 2, 1)
        pool_pad = ((0, 0), (1, 1), (1, 1), (0, 0))
        spatial = (1, 2)
    y = _conv(x, params["stem"]["w"].astype(x.dtype), 2, (3, 3), impl,
              layout)
    y = jax.nn.relu(_bn(y, params["stem"]["bn"], training, layout=layout))
    y = lax.reduce_window(y, -jnp.inf, lax.max, pool_win, pool_str,
                          pool_pad)
    for si, (blocks, _, stride) in enumerate(STAGES):
        for bi in range(blocks):
            blk = params[f"stage{si}"][bi]
            y = _bottleneck(y, blk, stride if bi == 0 else 1, training,
                            impl, layout)
    y = jnp.mean(y, axis=spatial)
    return jnp.dot(y, params["fc"]["w"].astype(y.dtype)) + params["fc"]["b"]


def make_train_step(impl="xla", compute_dtype=jnp.bfloat16, lr=0.1,
                    momentum=0.9, steps_per_dispatch=1, layout="nhwc"):
    """One jitted donated SGD-momentum step on f32 master weights
    (``steps_per_dispatch > 1`` chains K steps per program)."""

    def cast(tree, dt):
        return jax.tree_util.tree_map(
            lambda a: a.astype(dt)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    def loss_fn(params, x, y):
        p_c = cast(params, compute_dtype) if compute_dtype else params
        logits = forward(p_c, x.astype(compute_dtype or x.dtype),
                         training=True, impl=impl, layout=layout)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(
            jnp.take_along_axis(logp, y[:, None], axis=1))

    def one(params, vel, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        vel = jax.tree_util.tree_map(lambda v, g: momentum * v + g,
                                     vel, grads)
        params = jax.tree_util.tree_map(lambda p, v: p - lr * v,
                                        params, vel)
        return loss, params, vel

    if steps_per_dispatch <= 1:
        return partial(jax.jit, donate_argnums=(0, 1))(one)

    # chain K steps in ONE program (same fixed batch, like the
    # framework bench's steps_per_dispatch=4): the twin-vs-framework
    # ceiling comparison must carry the same per-dispatch amortization
    # on both sides
    @partial(jax.jit, donate_argnums=(0, 1))
    def multi(params, vel, x, y):
        def body(i, carry):
            p, v = carry
            _, p, v = one(p, v, x, y)
            return (p, v)
        params, vel = jax.lax.fori_loop(
            0, steps_per_dispatch - 1, body, (params, vel))
        return one(params, vel, x, y)

    return multi
