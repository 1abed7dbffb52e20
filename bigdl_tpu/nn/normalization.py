"""Normalization layers (reference BatchNormalization.scala:50,
SpatialBatchNormalization, SpatialCrossMapLRN, Normalize, L1Penalty,
Spatial{Subtractive,Divisive,Contrastive}Normalization).

Running statistics live in the module's *buffer* pytree and are threaded
functionally through ``apply_fn`` — the TPU answer to the reference's
mutable ``runningMean``/``runningVar`` (BatchNormalization.scala:50,
``copyStatus``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .initialization import ONE_D, Ones, RandomUniform, Zeros
from .module import TensorModule


class BatchNormalization(TensorModule):
    """BN over (N, D) — feature dim 2 (reference nn/BatchNormalization.scala:50)."""

    _feature_axis = 1  # axis of C in the input

    def __init__(self, n_output: int, eps: float = 1e-5, momentum: float = 0.1,
                 affine: bool = True):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.momentum = momentum
        self.affine = affine
        self.reset()

    def reset(self):
        if self.affine:
            w_init = self._init_methods.get("weight", (RandomUniform(0.0, 1.0), None))[0]
            b_init = self._init_methods.get("bias", (Zeros(), None))[0]
            self._register_param("weight", w_init.init((self.n_output,), ONE_D))
            self._register_param("bias", b_init.init((self.n_output,), ONE_D))
        self._register_buffer("running_mean", jnp.zeros((self.n_output,)))
        self._register_buffer("running_var", jnp.ones((self.n_output,)))
        return self

    def _reduce_axes(self, x):
        return tuple(i for i in range(x.ndim) if i != self._feature_axis)

    def _bshape(self, x):
        shape = [1] * x.ndim
        shape[self._feature_axis] = self.n_output
        return tuple(shape)

    def _apply(self, params, buffers, x, training, rng):
        axes = self._reduce_axes(x)
        bshape = self._bshape(x)
        if training:
            mean = jnp.mean(x, axis=axes)
            var = jnp.mean(jnp.square(x - mean.reshape(bshape)), axis=axes)
            n = int(np.prod([x.shape[i] for i in axes]))
            unbiased = var * n / max(n - 1, 1)
            new_buffers = {
                "running_mean": (1 - self.momentum) * buffers["running_mean"]
                + self.momentum * mean,
                "running_var": (1 - self.momentum) * buffers["running_var"]
                + self.momentum * unbiased,
            }
        else:
            mean, var = buffers["running_mean"], buffers["running_var"]
            new_buffers = buffers
        inv = lax.rsqrt(var + self.eps).reshape(bshape)
        y = (x - mean.reshape(bshape)) * inv
        if self.affine:
            y = y * params["weight"].reshape(bshape) + params["bias"].reshape(bshape)
        return y, new_buffers


class SpatialBatchNormalization(BatchNormalization):
    """BN over NCHW, per-channel (reference nn/SpatialBatchNormalization.scala)."""


class SpatialCrossMapLRN(TensorModule):
    """AlexNet-style local response normalization across channels
    (reference nn/SpatialCrossMapLRN.scala)."""

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75,
                 k: float = 1.0):
        super().__init__()
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k

    def _apply(self, params, buffers, x, training, rng):
        sq = jnp.square(x)  # (N, C, H, W)
        half = (self.size - 1) // 2
        # sum over channel window via reduce_window on the C axis
        sums = lax.reduce_window(
            sq, 0.0, lax.add, (1, self.size, 1, 1), (1, 1, 1, 1),
            [(0, 0), (half, self.size - 1 - half), (0, 0), (0, 0)])
        denom = jnp.power(self.k + sums * self.alpha / self.size, self.beta)
        return x / denom, buffers


class Normalize(TensorModule):
    """Lp-normalize rows (reference nn/Normalize.scala)."""

    def __init__(self, p: float = 2.0, eps: float = 1e-10):
        super().__init__()
        self.p, self.eps = p, eps

    def _apply(self, params, buffers, x, training, rng):
        if self.p == float("inf"):
            norm = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        else:
            norm = jnp.sum(jnp.abs(x) ** self.p, axis=-1, keepdims=True) ** (1.0 / self.p)
        return x / (norm + self.eps), buffers


class L1Penalty(TensorModule):
    """Identity forward that adds an L1 term to the loss gradient
    (reference nn/L1Penalty.scala) — custom_vjp adds sign(x)*scale to grads."""

    def __init__(self, l1weight: float, size_average: bool = False,
                 provide_output: bool = True):
        super().__init__()
        self.l1weight = l1weight
        self.size_average = size_average
        self.loss = 0.0

    def _apply(self, params, buffers, x, training, rng):
        if not training:
            return x, buffers
        l1w, avg = self.l1weight, self.size_average

        @jax.custom_vjp
        def pen(v):
            return v

        def bwd(res, g):
            (v,) = res
            scale = l1w / v.size if avg else l1w
            return (g + scale * jnp.sign(v),)

        pen.defvjp(lambda v: (v, (v,)), bwd)
        return pen(x), buffers


def _gaussian_kernel_2d(kernel):
    k = np.asarray(kernel, np.float32)
    if k.ndim == 1:
        k = np.outer(k, k)
    return k / k.sum()


class SpatialSubtractiveNormalization(TensorModule):
    """Subtract local weighted mean (reference
    nn/SpatialSubtractiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None):
        super().__init__()
        self.n_input_plane = n_input_plane
        if kernel is None:
            kernel = np.ones((9, 9), np.float32)
        self.kernel = _gaussian_kernel_2d(np.asarray(kernel))

    def _local_mean(self, x):
        kh, kw = self.kernel.shape
        # kernel in the INPUT's dtype (lax conv requires matching
        # dtypes; f64 inputs from the gradient checker included)
        k = jnp.asarray(self.kernel, x.dtype)
        w = k.reshape(1, 1, kh, kw)
        w = jnp.tile(w, (1, x.shape[1], 1, 1)) / x.shape[1]
        pad = [(kh // 2, (kh - 1) // 2), (kw // 2, (kw - 1) // 2)]
        mean = lax.conv_general_dilated(
            x, w, (1, 1), pad, dimension_numbers=("NCHW", "OIHW", "NCHW"))
        # edge coefficient correction: convolve a ones image
        ones = jnp.ones_like(x[:1, :1])
        coef = lax.conv_general_dilated(
            ones, k.reshape(1, 1, kh, kw), (1, 1), pad,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return mean / coef

    def _apply(self, params, buffers, x, training, rng):
        squeeze = False
        if x.ndim == 3:
            x = x[None]
            squeeze = True
        y = x - self._local_mean(x)
        if squeeze:
            y = y[0]
        return y, buffers


class SpatialDivisiveNormalization(TensorModule):
    """Divide by local weighted std (reference
    nn/SpatialDivisiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float = 1e-4, thresval: float = 1e-4):
        super().__init__()
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.threshold, self.thresval = threshold, thresval

    def _apply(self, params, buffers, x, training, rng):
        squeeze = False
        if x.ndim == 3:
            x = x[None]
            squeeze = True
        local_sq = self.sub._local_mean(jnp.square(x))
        std = jnp.sqrt(jnp.maximum(local_sq, 0.0))
        mean_std = jnp.mean(std, axis=(1, 2, 3), keepdims=True)
        adj = jnp.maximum(std, mean_std)
        adj = jnp.where(adj < self.threshold, self.thresval, adj)
        y = x / adj
        if squeeze:
            y = y[0]
        return y, buffers


class SpatialContrastiveNormalization(TensorModule):
    """Subtractive then divisive (reference
    nn/SpatialContrastiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float = 1e-4, thresval: float = 1e-4):
        super().__init__()
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.div = SpatialDivisiveNormalization(n_input_plane, kernel,
                                                threshold, thresval)

    def _apply(self, params, buffers, x, training, rng):
        y, _ = self.sub._apply({}, {}, x, training, rng)
        y, _ = self.div._apply({}, {}, y, training, rng)
        return y, buffers


class LayerNorm(TensorModule):
    """Layer normalization over the last dimension.

    No reference counterpart (the reference predates transformers) —
    required by the TPU rebuild's attention/transformer stack.  Unlike
    BatchNormalization it keeps no running statistics, so it is fully
    shard-oblivious: under sequence/tensor parallelism each device
    normalises its local activations independently.
    """

    def __init__(self, n_output: int, eps: float = 1e-5, affine: bool = True,
                 with_bias: bool = True):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.affine = affine
        # ``with_bias=False``: a gain and no bias LEAF (the Cohere
        # family's norm) — not a bias held at zero
        self.with_bias = bool(with_bias)
        self.reset()

    def reset(self):
        if self.affine:
            w_init = self._init_methods.get("weight", (Ones(), None))[0]
            self._register_param("weight", w_init.init((self.n_output,), ONE_D))
        if self.affine and getattr(self, "with_bias", True):
            b_init = self._init_methods.get("bias", (Zeros(), None))[0]
            self._register_param("bias", b_init.init((self.n_output,), ONE_D))
        return self

    def _apply(self, params, buffers, x, training, rng):
        if self.affine and not getattr(self, "with_bias", True):
            # statistics in at least float32 and the cast back BEFORE
            # the gain, as RMSNorm does
            ct = jnp.promote_types(x.dtype, jnp.float32)
            xc = x.astype(ct)
            xc = xc - xc.mean(axis=-1, keepdims=True)
            var = (xc * xc).mean(axis=-1, keepdims=True)
            y = (xc * lax.rsqrt(var + self.eps)).astype(x.dtype)
            return y * params["weight"].astype(x.dtype), buffers
        if self.affine:
            # Pallas single-pass kernel on TPU, jnp fallback elsewhere
            from ..ops import fused_layer_norm

            return fused_layer_norm(x, params["weight"], params["bias"],
                                    self.eps), buffers
        mean = x.mean(axis=-1, keepdims=True)
        var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
        return (x - mean) * lax.rsqrt(var + self.eps), buffers


def rms_normed(x, weight, eps: float):
    """``RMSNorm``'s arithmetic on a bare gain (a norm inside another
    module, as latent attention's two): at-LEAST float32 statistics
    (bf16 upcasts, f64 oracles keep their precision) — the HF convention
    for low-precision inputs — cast back to ``x``'s dtype BEFORE the gain
    multiplies."""
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = (xf * lax.rsqrt(var + eps)).astype(x.dtype)
    return normed * weight.astype(x.dtype)


class RMSNorm(TensorModule):
    """Root-mean-square normalization over the last dimension (the
    Llama-family norm): ``x * rsqrt(mean(x²) + eps) * weight`` — no
    mean subtraction, no bias.

    No reference counterpart (the reference predates transformers).
    Matches the HF Llama convention for low-precision inputs: the
    variance is computed in at-LEAST float32 (bf16/f16 upcast; float64
    keeps float64 — the gradient-sweep oracles need the precision),
    and the normalized activations cast back to the input dtype BEFORE
    the weight multiply."""

    def __init__(self, n_output: int, eps: float = 1e-6):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.reset()

    def reset(self):
        w_init = self._init_methods.get("weight", (Ones(), None))[0]
        self._register_param("weight", w_init.init((self.n_output,),
                                                   ONE_D))
        return self

    def _apply(self, params, buffers, x, training, rng):
        return rms_normed(x, params["weight"], self.eps), buffers


class ImageNormalize(TensorModule):
    """Device-side image normalization + layout move.

    Pairs with ``MTLabeledImgToBatch(..., device_normalize=True)``: the
    host batch path becomes a pure uint8 memcpy (stack only) and THIS
    module — placed first in the model — does cast → (x-mean)/std →
    NHWC→NCHW on the accelerator, where XLA fuses all of it into the
    stem conv's input read.  The normalize that cost the reference a
    host thread pool (dataset/image/MTLabeledBGRImgToBatch.scala:46)
    costs ~nothing on-device; on a starved host (1 core feeding a
    2000+ img/s chip) this is the difference between infeed-bound and
    compute-bound (docs/PERF.md round-4 infeed rehearsal).

    ``from_layout``: "NHWC" (the memcpy batch layout) transposes to the
    framework's NCHW; "NCHW" normalizes in place.
    """

    def __init__(self, mean, std, from_layout: str = "NHWC"):
        super().__init__()
        if from_layout not in ("NHWC", "NCHW"):
            raise ValueError(f"from_layout {from_layout!r}")
        self.mean = tuple(float(m) for m in np.atleast_1d(mean))
        self.std = tuple(float(s) for s in np.atleast_1d(std))
        self.from_layout = from_layout

    def _apply(self, params, buffers, x, training, rng):
        squeeze = x.ndim == 3
        if squeeze:
            x = x[None]
        # uint8 infeed casts up to f32; float inputs keep their dtype
        # (f64 under the gradient checker must not quantize)
        dt = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.float32
        mean = jnp.asarray(self.mean, dt)
        std = jnp.asarray(self.std, dt)
        x = x.astype(dt)
        if self.from_layout == "NHWC":
            x = (x - mean) / std          # broadcast over trailing C
            x = jnp.transpose(x, (0, 3, 1, 2))
        else:
            x = (x - mean[:, None, None]) / std[:, None, None]
        if squeeze:
            x = x[0]
        return x, buffers
