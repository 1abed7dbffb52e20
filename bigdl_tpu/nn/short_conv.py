"""The gated short convolution (Liquid's LFM2 operator, ``lfm2`` /
``lfm2_moe``): a layer that mixes positions WITHOUT attention and keeps
no K or V —

    (B, C, u) = split_3(w_in x)            each [T, D]
    z   = B * u
    c_t = sum_{k < K} conv[k] * z_{t - (K-1) + k}    depthwise, causal,
                                                      z zero before 0
    out = w_out (C * c)

no bias anywhere.  What a decoder carries from one token to the next is
the last ``K - 1`` values of ``z`` per channel: ``(K - 1) * D`` numbers a
row, whatever the context.  The convolution is ``nn.mamba.causal_conv``
(the same depthwise filter with a tail a Mamba-2 mixer runs over its
``x B C``), not a copy of it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .initialization import IN_OUT, RandomNormal
from .mamba import causal_conv
from .module import TensorModule


class GatedShortConv(TensorModule):
    """The operator over [batch, seq, embed].  Leaves: ``w_in``
    [3 * embed, embed] (rows ``B``, then ``C``, then ``u``), ``conv``
    [kernel, embed] (``conv[kernel - 1]`` multiplies the current
    position), ``w_out`` [embed, embed]; drawn ``normal(0, init_std)``
    unless an init method is set.

    ``apply_fn`` is the whole-sequence form (plain jax: differentiable
    by autodiff).  ``state_init`` / ``sequence`` / ``step`` are
    ``Mamba2Mixer``'s contract, the decode-state protocol of an
    operator without positions (``nn/attention.py``): the layer's WHOLE
    state is its tail, ``{"conv": [batch, kernel - 1, embed]}`` in the
    decoder's dtype (not K/V: under an int8 cache too), whatever the
    context — no ``k``, no ``v``, no position.  The prompt keeps its
    last values, a decode step reads them, writes one output from
    ``kernel`` values and shifts."""

    kind = "short_conv"

    def __init__(self, embed_dim: int, kernel: int = 3,
                 init_std: float = 0.02):
        super().__init__()
        if kernel < 2:
            raise ValueError(f"kernel {kernel} keeps no tail: a short "
                             "convolution spans at least two positions")
        self.embed_dim, self.kernel = int(embed_dim), int(kernel)
        self.init_std = float(init_std)
        self.reset()

    def reset(self):
        init = self._init_methods.get(
            "weight", (RandomNormal(0.0, self.init_std), None))[0]
        D, K = self.embed_dim, self.kernel
        self._register_param("w_in", init.init((3 * D, D), IN_OUT))
        self._register_param("conv", init.init((K, D), IN_OUT))
        self._register_param("w_out", init.init((D, D), IN_OUT))
        return self

    @property
    def state_doc(self) -> str:
        return ("keeps no K or V at all — its whole state is a convolution "
                f"tail of {self.kernel - 1} positions a row")

    def sequence(self, params, x, state=None):
        """[b, T, D] and the state before it (None: zeros, the start of
        a sequence) -> (out [b, T, D], state after the LAST token)."""
        dt = x.dtype
        tail = None if state is None else state["conv"]
        with jax.named_scope("conv.in_proj"):
            b, c, u = jnp.split(jnp.dot(x, params["w_in"].T.astype(dt)), 3,
                                axis=-1)
        with jax.named_scope("conv.short"):
            y, tail = causal_conv(b * u, params["conv"], jnp.zeros((), dt),
                                  tail)
            y = c * y
        with jax.named_scope("conv.out_proj"):
            return jnp.dot(y, params["w_out"].T.astype(dt)), {"conv": tail}

    def step(self, params, x, state):
        """[b, 1, D] and the carried state -> (out [b, 1, D], state):
        the same three taps, over the tail and the one new value."""
        return self.sequence(params, x, state)

    def state_init(self, batch: int, dtype):
        """The carried state before any token."""
        return {"conv": jnp.zeros((batch, self.kernel - 1, self.embed_dim),
                                  dtype)}

    def _apply(self, params, buffers, x, training, rng):
        return self.sequence(params, x)[0], buffers
