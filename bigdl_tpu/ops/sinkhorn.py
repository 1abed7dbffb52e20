"""The residual map of a hyper-connection — ``exp`` of the clamped
logits made doubly stochastic by Sinkhorn sweeps — as one Pallas TPU
kernel a sublayer.

What a hyper-connected sublayer holds when it gets here
(``nn/hyper_connection.py``): the ``n x n`` logits of every token,
float32, ROWS MINOR — ``[n, n, rows]``, a batch's rows along the lanes.
The plain form (:func:`sinkhorn_reference`) is ``iters`` sweeps, each a
row normalisation and a column normalisation: in XLA every one of them
is a reduction, and a reduction ends a fusion — 40 kernels a sublayer
with the sweeps written out, a nested ``while`` a sublayer with them
rolled, where the arithmetic of all twenty sweeps over 256 rows is a few
thousand vector operations (compile time and step time of both: PERF.md
section 6 "PR 42").

The kernel keeps the ``n^2`` entries of a block of rows as ``n^2``
arrays ``[rows / 128, 128]`` — entry ``(i, j)`` of every row, whole
vector registers — so that a row sum is ``n - 1`` additions and a
normalisation one reciprocal and ``n`` products, all elementwise; the
sweeps are a loop INSIDE the kernel (a scalar loop on the core, no
launch between sweeps).  Grid: blocks of rows, "parallel".  Arithmetic
as the plain form but for ``m * (1 / s)`` in place of ``m / s`` (one
rounding more a normalisation: 1e-7 relative).

Which arm runs is ``_support.use_kernel``'s rule: the kernel on a TPU
(or interpreted, for the tests), the plain form elsewhere.  The map is
differentiable either way: the kernel's backward is the plain form's
(``jax.custom_vjp``: the sweeps recomputed and differentiated by
autodiff — a training step pays the plain form once, in its backward).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ._support import pl, pltpu, use_kernel

_LANES = 128
# sublane rows (of 128 rows each) a grid step holds of every entry: 64 x
# 128 rows x 16 entries x 4 bytes = 512 KiB in, as much out
_BLOCK_SUBLANES = 64


def sinkhorn_reference(x, iters: int, eps: float, lo: float, hi: float):
    """The plain form on ``x [n, n, rows]``: ``exp(clip(x, lo, hi))``,
    then ``iters`` times each row (axis 1 runs over a row's columns)
    divided by its sum + ``eps`` and each column by its, the sweeps
    written out."""
    m = jnp.exp(jnp.clip(x, lo, hi))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def _kernel(x_ref, o_ref, *, n: int, iters: int, eps: float, lo: float,
            hi: float):
    def sweep(_, e):
        e = [list(e[i * n:(i + 1) * n]) for i in range(n)]
        for i in range(n):                              # rows
            inv = 1.0 / (sum(e[i][1:], e[i][0]) + eps)
            e[i] = [v * inv for v in e[i]]
        for j in range(n):                              # columns
            col = [e[i][j] for i in range(n)]
            inv = 1.0 / (sum(col[1:], col[0]) + eps)
            for i in range(n):
                e[i][j] = e[i][j] * inv
        return tuple(v for row in e for v in row)

    e = tuple(jnp.exp(jnp.clip(x_ref[k], lo, hi)) for k in range(n * n))
    e = lax.fori_loop(0, iters, sweep, e)
    for k in range(n * n):
        o_ref[k] = e[k]


def _sinkhorn_kernel(x, iters: int, eps: float, lo: float, hi: float,
                     interpret: bool):
    """The kernel arm on ``x [n, n, rows]`` float32."""
    n, rows = x.shape[0], x.shape[-1]
    padded = -(-rows // _LANES) * _LANES
    x2 = x.reshape(n * n, rows)
    if padded != rows:          # rows beyond the batch: zeros in, dropped
        x2 = jnp.pad(x2, ((0, 0), (0, padded - rows)))
    sub = padded // _LANES
    # a block's sublane count is a multiple of 8 or the whole axis
    block = _BLOCK_SUBLANES if sub % _BLOCK_SUBLANES == 0 else sub
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, iters=iters, eps=eps, lo=lo, hi=hi),
        grid=(sub // block,),
        in_specs=[pl.BlockSpec((n * n, block, _LANES),
                               lambda b: (0, b, 0))],
        out_specs=pl.BlockSpec((n * n, block, _LANES), lambda b: (0, b, 0)),
        out_shape=jax.ShapeDtypeStruct((n * n, sub, _LANES), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2.reshape(n * n, sub, _LANES))
    return out.reshape(n * n, padded)[:, :rows].reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _sinkhorn_map(x, iters, eps, lo, hi, interpret):
    return _sinkhorn_kernel(x, iters, eps, lo, hi, interpret)


def _fwd(x, iters, eps, lo, hi, interpret):
    return _sinkhorn_kernel(x, iters, eps, lo, hi, interpret), x


def _bwd(iters, eps, lo, hi, interpret, x, g):
    return jax.vjp(lambda x: sinkhorn_reference(x, iters, eps, lo, hi),
                   x)[1](g)


_sinkhorn_map.defvjp(_fwd, _bwd)


def sinkhorn_map(x, iters: int, eps: float, lo: float, hi: float,
                 interpret: bool = False):
    """``H_res [n, n, rows]`` from the logits ``x [n, n, rows]``:
    ``exp(clip(x, lo, hi))`` after ``iters`` Sinkhorn sweeps with
    ``eps`` in every sum.  The kernel for float32 logits where
    ``_support.use_kernel`` says so, the plain form otherwise (every
    other backend; float64 oracles)."""
    if x.dtype != jnp.float32 or not use_kernel(interpret):
        return sinkhorn_reference(x, iters, eps, lo, hi)
    return _sinkhorn_map(x, int(iters), float(eps), float(lo), float(hi),
                         bool(interpret))
