"""What the two plain references share: seeded weights, a matrix
multiply whose precision is stated, causal attention written out.

Nothing here imports the program.  Everything is float32 ``jax.numpy``;
``mm`` runs at ``Precision.HIGHEST`` because a TPU otherwise multiplies
float32 in bfloat16 passes.  ``mode="fp8"`` is the CONTROL, not a
feature: both operands and the result of every product are rounded to
8-bit floats (e4m3, one scale per row) — the nearest step below the
bfloat16 the configurations state, and the one a later PR would be
tempted by — so that the comparison which decides ``correct`` can be
shown to fail it.  Normalisations, the softmax and the residual sums
stay float32 there, as fp8 recipes keep them.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@partial(jax.jit, static_argnums=(2, 3, 4))
def _seeded(seed_pair, crc, shape, kind: str, std: float):
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    lo, hi = seed_pair
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return std * jax.random.normal(jax.random.fold_in(key, crc), shape,
                                   jnp.float32)


def seeded_leaf(seed: int, name: str, shape, kind: str, std: float):
    """ONE parameter, float32, on the device.  Its values depend on the
    seed and its own name only (``h.<i>.<name>`` for layer ``i``), so a
    caller that can hold one leaf at a time — ``program.build_model``
    casting each to the dtype the model holds it in, ``serve_check``
    going layer by layer — reads the numbers ``make_params`` gives.  One
    program serves every leaf of a shape and kind: the name goes in as
    a number."""
    seed = int(seed)
    pair = (np.uint32(seed & 0x7FFFFFFF), np.uint32(seed >> 31))
    crc = np.uint32(zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return _seeded(pair, crc, tuple(shape), kind, float(std))


def flat_specs(specs: dict, n_layers: int) -> dict:
    """{flat name: (shape, kind)}: the top-level leaves, then layer by
    layer ``h.<i>.<name>``."""
    out = dict(specs["top"])
    for i in range(n_layers):
        out.update({f"h.{i}.{n}": sk for n, sk in specs["layer"].items()})
    return out


def make_params(specs: dict, n_layers: int, std: float, seed: int,
                stacked: bool = False) -> dict:
    """Every parameter, float32, on the device, leaf by leaf from the
    seed (``seeded_leaf``): the stacked form (the reference scans over
    it) and the per-layer form hold the same numbers.  For a caller
    that wants the whole set at once; one that cannot afford 4 bytes a
    parameter walks ``flat_specs`` itself."""
    if not stacked:
        return {name: seeded_leaf(seed, name, shape, kind, std) for name,
                (shape, kind) in flat_specs(specs, n_layers).items()}
    out = {n: seeded_leaf(seed, n, shape, kind, std)
           for n, (shape, kind) in specs["top"].items()}
    for n, (shape, kind) in specs["layer"].items():
        out[f"h.{n}"] = jnp.stack([
            seeded_leaf(seed, f"h.{i}.{n}", shape, kind, std)
            for i in range(n_layers)])
    return out


def hashable(cfg: dict):
    """A configuration's scalar settings as a jit-static key."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))
                        or v is None))


def _fp8(a):
    """Round to float8 e4m3 (3 mantissa bits), one absmax scale per row
    (last axis) so the row fits the format's range; the gradient passes
    straight through."""
    s = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 448.0 + 1e-30
    q = (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return a + jax.lax.stop_gradient(q - a)


def _low(a, mode: str):
    if mode == "fp8":
        return _fp8(a)
    if mode != "f32":
        raise ValueError(f"unknown precision mode {mode!r}")
    return a


def mm(x, w, mode: str = "f32"):
    """x [..., in] times w [out, in] -> [..., out], float32 HIGHEST."""
    y = jnp.einsum("...i,oi->...o", _low(x, mode), _low(w, mode),
                   precision=HIGHEST)
    return _low(y, mode)


def causal_attention(q, k, v, mode: str = "f32"):
    """q [B,H,T,D], k/v [B,Hkv,T,D] -> [B,H,T,D]; scores written out."""
    B, H, T, D = q.shape
    group = H // k.shape[1]
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    q, k, v = _low(q, mode), _low(k, mode), _low(v, mode)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST)
    s = _low(s, mode) / jnp.sqrt(jnp.float32(D))
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = _low(jax.nn.softmax(s, axis=-1), mode)
    return _low(jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=HIGHEST),
                mode)


def split_heads(x, heads: int):
    B, T, _ = x.shape
    return x.reshape(B, T, heads, -1).transpose(0, 2, 1, 3)


def merge_heads(x):
    B, H, T, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, T, H * D)


def token_xent_sum(logits, targets):
    """Sum over tokens of -log softmax(logits)[target]; targets 0-based."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum(lse - picked)
