"""Shared Pallas gating for the ops package."""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl  # noqa: F401
from jax.experimental.pallas import tpu as pltpu  # noqa: F401


def use_kernel(interpret: bool) -> bool:
    """Kernel path on a TPU backend or when explicitly interpreting; the
    jnp reference on every other backend (CPU tests exercise the kernels
    with ``interpret=True``).  On a TPU there is no second path: a
    kernel compiles or its error propagates."""
    return interpret or jax.default_backend() == "tpu"
