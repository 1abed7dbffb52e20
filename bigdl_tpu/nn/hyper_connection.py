"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections, arXiv:2409.19606): the residual of a block is not one
vector a token but ``n`` STREAMS ``X [n, C]``, and every sublayer ``f``
reads a learned mixture of them and writes its result back to all of
them through three small maps computed, per token, from the state
itself:

    x~      = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)     all n C numbers, no gain
    [p|q|r] = x~ phi                                       n | n | n^2 numbers
    H_pre   = sigmoid(a_pre p + b_pre)                     [n]
    H_post  = 2 sigmoid(a_post q + b_post)                 [n]
    M       = exp(clip(a_res mat(r) + b_res, lo, hi))      [n, n]
    ``sinkhorn_iters`` times:  M <- M / (rowsum M + eps);  M <- M / (colsum M + eps)
    H_res   = M                                            doubly stochastic
    u       = sum_i H_pre[i] X[i]                          the sublayer's input
    X'[i]   = sum_j H_res[i, j] X[j] + H_post[i] f(norm u)

One module a sublayer.  Three pure functions — :meth:`coefficients`,
:meth:`pre`, :meth:`post` — are what a block's ``apply_fn`` and the
generation builder both call, so a cached decode step and the training
forward run the same code.

How it is laid out for the chip.  The three projections are ONE product,
and the scale ``1 / rms`` commutes with it, so the product reads the
streams as they are held (no normalised copy is made) and the float32
result is scaled: ``phi`` is stored ``[n + n + n^2, n C]`` ([out, in] as
every matrix of this repository: the long axis minor, so its 24 rows are
not padded to a lane tile each) and the product gives ``[24, rows]`` —
ROWS MINOR: the Sinkhorn sweeps then run on ``[n, n, rows]`` with the
rows of a batch along the lanes, a handful of vector registers for 256
rows, where ``[rows, n, n]`` would hold one 4 x 4 matrix a register
tile.  On a TPU the sweeps are ONE kernel a sublayer
(``ops/sinkhorn.py``: in XLA every normalisation is a reduction, which
ends a fusion — 40 kernels a sublayer written out, a nested ``while`` a
sublayer rolled; ``tools/hyper_connection_sweep.py`` times all three);
elsewhere, and in a backward pass, the plain form written out.

The coefficient path is float32 whatever the streams are held in (as a
router's scores are): the mean square, the product's accumulation, the
sigmoids, ``exp`` and the sweeps; ``a_*`` and ``b_*`` stay float32
leaves (``FLOAT32_LEAVES``).  The mixes accumulate in float32 and give
the streams' dtype.

Device scopes (``telemetry.tracer.DEVICE_SCOPES``): ``mhc.coeffs`` (mean
square, the product, the sigmoids), ``mhc.sinkhorn`` (clip, ``exp``,
the sweeps and the error reading), ``mhc.pre``, ``mhc.post`` — opened
here; the block that owns the module puts them inside the sublayer's own
scope (``models/latent_moe.py``: ``block.attention`` or ``block.mlp``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.sinkhorn import sinkhorn_map
from .initialization import IN_OUT, RandomNormal
from .module import TensorModule

#: the leaves that stay float32 where the model holds or computes in a
#: lower precision (all of them are in ``nn.module.FLOAT32_LEAVES``)
COEFFICIENT_LEAVES = ("alpha_pre", "alpha_post", "alpha_res",
                      "b_pre", "b_post", "b_res")


class Coefficients(NamedTuple):
    """The three maps of one sublayer for ``rows`` tokens, float32, ROWS
    MINOR: ``pre`` / ``post`` ``[n, rows]``, ``res`` ``[n, n, rows]``
    (``res[i, j]`` takes stream ``j`` to stream ``i``), and ``err``, the
    largest ``|rowsum(res) - 1|`` or ``|colsum(res) - 1|`` over the
    rows (a scalar)."""
    pre: jax.Array
    post: jax.Array
    res: jax.Array
    err: jax.Array


class HyperConnection(TensorModule):
    """The hyper-connection of ONE sublayer over a state ``[..., n,
    embed]``.  Leaves: ``phi`` ``[n + n + n^2, n * embed]`` (rows: the
    ``n`` of ``H_pre``, the ``n`` of ``H_post``, then ``H_res`` row by
    row), drawn ``normal(0, init_std)``; ``alpha_pre`` / ``alpha_post``
    / ``alpha_res`` scalars (drawn 0.01, the papers' small gate) and
    ``b_pre`` / ``b_post`` ``[n]``, ``b_res`` ``[n, n]`` (zeros but
    ``b_res``'s diagonal, 1: the streams start out mostly kept), float32
    all six.  It has no ``apply_fn`` of its own: a block calls the three
    functions around its sublayer."""

    kind = "hyper_connection"

    def __init__(self, embed_dim: int, n_streams: int = 4,
                 sinkhorn_iters: int = 20, eps: float = 1e-6,
                 norm_eps: float = 1e-6, clamp=(-30.0, 30.0),
                 init_std: float = 0.02):
        super().__init__()
        if n_streams < 1:
            raise ValueError(f"n_streams {n_streams}: a state has at "
                             "least one stream")
        self.embed_dim, self.n_streams = int(embed_dim), int(n_streams)
        self.sinkhorn_iters, self.eps = int(sinkhorn_iters), float(eps)
        self.norm_eps = float(norm_eps)
        self.clamp = (float(clamp[0]), float(clamp[1]))
        self.init_std = float(init_std)
        self.reset()

    def reset(self):
        init = self._init_methods.get(
            "weight", (RandomNormal(0.0, self.init_std), None))[0]
        n = self.n_streams
        self._register_param(
            "phi", init.init((n + n + n * n, n * self.embed_dim), IN_OUT))
        for name in ("alpha_pre", "alpha_post", "alpha_res"):
            self._register_param(name, jnp.full((), 0.01, jnp.float32))
        self._register_param("b_pre", jnp.zeros((n,), jnp.float32))
        self._register_param("b_post", jnp.zeros((n,), jnp.float32))
        self._register_param("b_res", jnp.eye(n, dtype=jnp.float32))
        return self

    def _apply(self, params, buffers, x, training, rng):
        raise TypeError("HyperConnection wraps a sublayer: a block calls "
                        "coefficients / pre / post around it")

    # -- the state's two ends --------------------------------------------
    def replicate(self, h):
        """``[..., embed]`` -> the initial state ``[..., n, embed]``:
        every stream the embedding."""
        return jnp.broadcast_to(h[..., None, :],
                                h.shape[:-1] + (self.n_streams,
                                                h.shape[-1]))

    @staticmethod
    def reduce(x):
        """The state ``[..., n, embed]`` -> ``[..., embed]``: the sum of
        the streams, float32 accumulation."""
        ct = jnp.promote_types(x.dtype, jnp.float32)
        return jnp.sum(x.astype(ct), axis=-2).astype(x.dtype)

    # -- the three functions ------------------------------------------------
    @property
    def _spec(self) -> "_Spec":
        return _Spec(self.n_streams, self.embed_dim, self.sinkhorn_iters,
                     self.eps, self.norm_eps, self.clamp)

    def coefficients(self, params, x) -> Coefficients:
        """The maps of every token of ``x [..., n, embed]``."""
        return _coefficients(self._spec, params, x)

    def pre(self, co: Coefficients, x):
        """The sublayer's input ``[..., embed]``: ``sum_i H_pre[i]
        X[i]``."""
        return _pre(self._spec, co, x)

    def post(self, co: Coefficients, x, y):
        """The next state ``[..., n, embed]``: ``X'[i] = sum_j H_res[i,
        j] X[j] + H_post[i] y`` with ``y [..., embed]`` the sublayer's
        result."""
        return _post(self._spec, co, x, y)


class _Spec(NamedTuple):
    """What the three functions read of a module — hashable, so that
    they are jitted ONCE for all the sublayers of a model that share it:
    a generate program of five layers calls each ten times in prefill
    and ten in a step, and traces it twice."""
    n: int
    embed: int
    iters: int
    eps: float
    norm_eps: float
    clamp: tuple


def _streams(spec: _Spec, x):
    """``x [..., n, C]`` -> flat ``[rows, n C]`` and its streams ``n x
    [rows, C]``."""
    flat = x.reshape(-1, spec.n * spec.embed)
    return flat, [flat[:, i * spec.embed:(i + 1) * spec.embed]
                  for i in range(spec.n)]


@functools.partial(jax.jit, static_argnums=0)
def _coefficients(spec: _Spec, params, x) -> Coefficients:
    n = spec.n
    f32 = jnp.promote_types(x.dtype, jnp.float32)
    with jax.named_scope("mhc.coeffs"):
        flat, _ = _streams(spec, x)
        xf = flat.astype(f32)
        inv = lax.rsqrt(jnp.mean(xf * xf, axis=-1) + spec.norm_eps)
        # [n + n + n^2, rows]: phi [out, in] times the state as held,
        # float32 accumulation; 1 / rms scales the result
        z = jnp.einsum("on,rn->or", params["phi"].astype(x.dtype), flat,
                       preferred_element_type=f32) * inv[None, :]
        pre = jax.nn.sigmoid(params["alpha_pre"] * z[:n]
                             + params["b_pre"][:, None])
        post = 2.0 * jax.nn.sigmoid(params["alpha_post"] * z[n:2 * n]
                                    + params["b_post"][:, None])
    with jax.named_scope("mhc.sinkhorn"):
        logits = (params["alpha_res"] * z[2 * n:].reshape(n, n, -1)
                  + params["b_res"][:, :, None])
        # one kernel on a TPU, the plain sweeps elsewhere
        res = sinkhorn_map(logits, spec.iters, spec.eps, *spec.clamp)
        err = jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0)),
                          jnp.max(jnp.abs(jnp.sum(res, axis=0) - 1.0)))
    return Coefficients(pre, post, res, err.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=0)
def _pre(spec: _Spec, co: Coefficients, x):
    with jax.named_scope("mhc.pre"):
        _, streams = _streams(spec, x)
        w = co.pre.T                                        # [rows, n]
        u = sum(w[:, i, None] * s.astype(w.dtype)
                for i, s in enumerate(streams))
        return u.astype(x.dtype).reshape(x.shape[:-2] + x.shape[-1:])


@functools.partial(jax.jit, static_argnums=0)
def _post(spec: _Spec, co: Coefficients, x, y):
    n = spec.n
    with jax.named_scope("mhc.post"):
        _, streams = _streams(spec, x)
        res = jnp.moveaxis(co.res, -1, 0)                   # [rows, n, n]
        w = co.post.T                                       # [rows, n]
        ct = w.dtype
        streams = [s.astype(ct) for s in streams]
        yf = y.reshape(-1, spec.embed).astype(ct)
        out = [sum(res[:, i, j, None] * streams[j] for j in range(n))
               + w[:, i, None] * yf for i in range(n)]
        return jnp.concatenate(out, axis=-1).astype(x.dtype).reshape(x.shape)
