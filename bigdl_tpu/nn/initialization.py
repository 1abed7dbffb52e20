"""Initialization methods (reference nn/InitializationMethod.scala).

Host-side numpy draws from the seeded MT generator, converted to jax
arrays — init happens once at construction, so it stays off-device.
"""
from __future__ import annotations

import contextlib
import functools
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.metric_names import INIT_DRAW_SECONDS_TOTAL
from ..telemetry.registry import default_registry
from ..utils.rng import RNG

_draw = threading.local()


@contextlib.contextmanager
def device_draw():
    """Inside this block the random initialisers draw ON THE DEVICE
    (``jax.random`` with the device's own bit generator, seeded from the
    host generator's stream) instead of with the host's Mersenne
    Twister: the same distributions, other numbers.  For a model of billions of parameters whose host draw
    costs a minute of every start (``HybridMambaLM`` builds under it);
    everything else keeps the host stream and its historical numbers."""
    before = getattr(_draw, "on", False)
    _draw.on = True
    try:
        yield
    finally:
        _draw.on = before


@contextlib.contextmanager
def no_draw():
    """Inside this block the random initialisers draw NOTHING: a leaf
    is allocated as zeros.  For a model built to be GIVEN its weights (a
    checkpoint; a benchmark's seeded leaves): where nothing is cached a
    model's fifteen shapes cost 40 s of small compilations for numbers
    that are dropped unread (v5e, PR 42)."""
    before = getattr(_draw, "skip", False)
    _draw.skip = True
    try:
        yield
    finally:
        _draw.skip = before


@functools.partial(jax.jit, static_argnames=("shape", "normal"))
def _device_random(key, a, b, shape, normal):
    """One program a shape and kind (the bounds are arguments): drawn
    operation by operation, a model's dozen shapes cost half a minute of
    small compilations where nothing is cached."""
    if normal:
        return a + b * jax.random.normal(key, shape, jnp.float32)
    return jax.random.uniform(key, shape, jnp.float32, a, b)


def _device_key():
    """A key of the device's own bit generator, seeded from the host
    generator's stream: where nothing is cached the twelve shapes of a
    ``HybridMambaLM`` compile in 15 s with it, in 34 s with threefry
    (v5e, jax 0.9.0)."""
    return jax.random.key(int(RNG().random_int(0, 2**31 - 1)), impl="rbg")


def _drawn(normal: bool, a, b, shape):
    """One leaf, drawn where ``device_draw`` says and BOOKED there: the
    host seconds of the call, as a counter family of the default
    registry labelled ``where``.  On the host that is the draw itself;
    on the device it is what the host spends building and enqueuing the
    draw — the compile or the cache load of a shape's program included,
    which is most of it where nothing is cached — while the draw itself
    runs behind it.  Two clock reads a leaf; no span per leaf in the
    ring."""
    if getattr(_draw, "skip", False):       # ``no_draw``: nothing booked
        return jnp.zeros(tuple(shape), jnp.float32)
    t0 = time.perf_counter()
    if getattr(_draw, "on", False):
        where = "device"
        out = _device_random(_device_key(), a, b, tuple(shape), normal)
    else:
        where = "host"
        gen = RNG().normal if normal else RNG().uniform
        out = jnp.asarray(gen(a, b, shape), jnp.float32)
    default_registry().counter(
        INIT_DRAW_SECONDS_TOTAL,
        "host seconds the initialisers spent drawing weights",
        labels=("where",)).labels(where=where).inc(time.perf_counter() - t0)
    return out


def _uniform(lo, hi, shape):
    return _drawn(False, lo, hi, shape)


def _normal(mean, std, shape):
    return _drawn(True, mean, std, shape)


class VariableFormat:
    """Describes which dims are fan-in/fan-out (reference VariableFormat)."""

    def __init__(self, name="Default"):
        self.name = name

    def fans(self, shape):
        if self.name == "ONE_D":
            return shape[0], shape[0]
        if self.name == "IN_OUT":       # (out, in) linear weight
            fan_out, fan_in = shape[0], int(np.prod(shape[1:]))
            return fan_in, fan_out
        if self.name == "OUT_IN":
            fan_in, fan_out = shape[0], int(np.prod(shape[1:]))
            return fan_out, fan_in
        if self.name == "OUT_IN_KW_KH":  # conv weight (out, in, kh, kw)
            receptive = int(np.prod(shape[2:]))
            return shape[1] * receptive, shape[0] * receptive
        if self.name == "IN_OUT_KW_KH":
            receptive = int(np.prod(shape[2:]))
            return shape[0] * receptive, shape[1] * receptive
        n = int(np.prod(shape))
        d0 = shape[0] if shape else 1
        return n // d0 if d0 else 1, d0


ONE_D = VariableFormat("ONE_D")
IN_OUT = VariableFormat("IN_OUT")
OUT_IN = VariableFormat("OUT_IN")
OUT_IN_KW_KH = VariableFormat("OUT_IN_KW_KH")
IN_OUT_KW_KH = VariableFormat("IN_OUT_KW_KH")
DEFAULT_FORMAT = VariableFormat("Default")


class InitializationMethod:
    def init(self, shape, fmt: VariableFormat = DEFAULT_FORMAT):
        raise NotImplementedError


class Zeros(InitializationMethod):
    def init(self, shape, fmt=DEFAULT_FORMAT):
        return jnp.zeros(shape, jnp.float32)


class Ones(InitializationMethod):
    def init(self, shape, fmt=DEFAULT_FORMAT):
        return jnp.ones(shape, jnp.float32)


class ConstInitMethod(InitializationMethod):
    def __init__(self, value):
        self.value = value

    def init(self, shape, fmt=DEFAULT_FORMAT):
        return jnp.full(shape, self.value, jnp.float32)


class RandomUniform(InitializationMethod):
    """U(lower, upper); no-arg variant scales by 1/sqrt(fan_in) like the
    reference's default torch init."""

    def __init__(self, lower=None, upper=None):
        self.lower, self.upper = lower, upper

    def init(self, shape, fmt=DEFAULT_FORMAT):
        if self.lower is None:
            fan_in, _ = fmt.fans(shape)
            stdv = 1.0 / math.sqrt(max(fan_in, 1))
            lo, hi = -stdv, stdv
        else:
            lo, hi = self.lower, self.upper
        return _uniform(lo, hi, shape)


class RandomNormal(InitializationMethod):
    def __init__(self, mean=0.0, stdv=1.0):
        self.mean, self.stdv = mean, stdv

    def init(self, shape, fmt=DEFAULT_FORMAT):
        return _normal(self.mean, self.stdv, shape)


class Xavier(InitializationMethod):
    """Glorot uniform (reference InitializationMethod.scala Xavier)."""

    def init(self, shape, fmt=DEFAULT_FORMAT):
        fan_in, fan_out = fmt.fans(shape)
        stdv = math.sqrt(6.0 / (fan_in + fan_out))
        return _uniform(-stdv, stdv, shape)


class MsraFiller(InitializationMethod):
    """He init (reference MsraFiller)."""

    def __init__(self, variance_norm_average=True):
        self.avg = variance_norm_average

    def init(self, shape, fmt=DEFAULT_FORMAT):
        fan_in, fan_out = fmt.fans(shape)
        n = (fan_in + fan_out) / 2.0 if self.avg else fan_in
        std = math.sqrt(2.0 / max(n, 1))
        return _normal(0.0, std, shape)


class BilinearFiller(InitializationMethod):
    """Bilinear upsampling kernel for deconv (reference BilinearFiller)."""

    def init(self, shape, fmt=DEFAULT_FORMAT):
        assert len(shape) >= 2
        kh, kw = shape[-2], shape[-1]
        f = math.ceil(kw / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        w = np.zeros(shape, np.float32)
        flat = w.reshape(-1, kh * kw)
        for i in range(kh * kw):
            x, y = i % kw, i // kw
            val = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
            flat[:, i] = val
        return jnp.asarray(flat.reshape(shape))
