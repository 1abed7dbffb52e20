"""Selective state-space layers: the Mamba-2 mixer and the hybrid block
that runs it BESIDE grouped-query attention (Falcon-H1's layer).

No reference counterpart (the reference's sequence story ends at
LSTM/GRU).  The mixer is the SSD form of Mamba-2 (Dao & Gu 2024): per
head ``h`` with a scalar decay,

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t      S: [P, N], float32
    y_t = S_t C_t + D x_t

A whole sequence is computed in chunks (:func:`ssd_chunked_scan`): within
a chunk the decay-masked ``C B^T`` product and two matmuls, between
chunks the state is carried — plain ``jnp.einsum`` the compiler places
on the MXU, no kernel.  One token is :func:`ssm_step`.  The state, the
decays and ``A_log`` / ``dt_bias`` / ``D`` compute in float32 whatever
dtype the parameters are held in.

Device scopes (``jax.named_scope``, metadata only; listed in
``telemetry.tracer.DEVICE_SCOPES``): ``mixer.in_proj``, ``mixer.conv``,
``mixer.ssd_scan`` (a whole sequence), ``mixer.ssm_step`` (one token:
conv tail and state update), ``mixer.gate_norm``, ``mixer.out_proj``.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.rng import next_jax_key
from .attention import MultiHeadAttention, advance, footprint
from .initialization import RandomNormal, device_draw
from .linear import Linear
from .module import Container, TensorModule
from .normalization import RMSNorm

#: the carry between chunks is a Python loop up to this many chunks (a
#: handful of elementwise operations each) and a ``lax.scan`` beyond: a
#: rolled loop is a ``while`` in the device trace, which the decode
#: readers take for the decode scan
_UNROLL_CHUNKS = 32


def _wide(dtype):
    """At-LEAST float32 (the repo's convention for statistics and
    states: bf16 upcasts, the float64 gradient oracles keep theirs)."""
    return jnp.promote_types(dtype, jnp.float32)


def _dot(x, w):
    """``x @ w.T`` in ``w``'s dtype, accumulated in float32 on the MXU
    (float64 oracles: never downcast), as ``nn.Linear`` does."""
    x = x.astype(w.dtype)
    if jnp.dtype(w.dtype).itemsize < 8:
        return jnp.dot(x, w.T,
                       preferred_element_type=jnp.float32).astype(w.dtype)
    return jnp.dot(x, w.T)


def ssd_chunked_scan(x, dt, A, B, C, chunk: int, state=None):
    """The SSD recurrence over a whole sequence, in chunks.

    ``x`` [b, T, H, P] inputs per head, ``dt`` [b, T, H] float32 step
    sizes (after softplus), ``A`` [H] float32 (negative), ``B`` / ``C``
    [b, T, G, N] with head ``h`` using group ``h // (H // G)``;
    ``state`` [b, H, P, N] float32 or None (zeros).  Returns ``(y
    [b, T, H, P] float32, state after the last token [b, H, P, N]
    float32)``.  ``T`` need not be a multiple of ``chunk``: the tail is
    padded with ``dt = 0``, which neither decays nor feeds the state.

    Matmul operands stay in ``x``'s dtype with float32 accumulation;
    decays are float32 throughout.
    """
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    Q = int(chunk)
    nc = -(-T // Q)
    pad = nc * Q - T
    if pad:
        x, B, C = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (x, B, C))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    f32 = _wide(x.dtype)
    dt = dt.astype(f32)
    x = x.reshape(b, nc, Q, H, P)
    B = B.reshape(b, nc, Q, G, N)
    C = C.reshape(b, nc, Q, G, N)
    dt = dt.reshape(b, nc, Q, H)
    a = dt * A.astype(f32)                        # log decay of each step
    cs = jnp.cumsum(a, axis=2)                    # [b, c, Q, H], inclusive
    xdt = (x.astype(f32) * dt[..., None]).astype(x.dtype)

    # within a chunk: y_l += sum_{j<=l} (C_l . B_j) exp(cs_l - cs_j) xdt_j
    scores = jnp.einsum("bclgn,bcjgn->bcglj", C, B,
                        preferred_element_type=f32)
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]      # [b,c,l,j,H]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(causal[None, None, :, :, None], seg,
                              -jnp.inf))
    decay = decay.transpose(0, 1, 4, 2, 3)                 # [b,c,H,l,j]
    w = (jnp.repeat(scores, H // G, axis=2) * decay).astype(x.dtype)
    y = jnp.einsum("bchlj,bcjhp->bclhp", w, xdt,
                   preferred_element_type=f32)

    # what each chunk adds to the state at its own end, and its decay
    to_end = jnp.exp(cs[:, :, -1:, :] - cs)                # [b,c,Q,H]
    Bh = jnp.repeat(B, H // G, axis=3)                     # [b,c,Q,H,N]
    adds = jnp.einsum("bcjhp,bcjhn->bchpn",
                      (xdt.astype(f32) * to_end[..., None]).astype(x.dtype),
                      Bh, preferred_element_type=f32)
    chunk_decay = jnp.exp(cs[:, :, -1, :])                 # [b,c,H]

    # between chunks: the carry
    if state is None:
        state = jnp.zeros((b, H, P, N), f32)

    state = state.astype(f32)
    if nc <= _UNROLL_CHUNKS:
        entering = []
        for c in range(nc):
            entering.append(state)
            state = chunk_decay[:, c, :, None, None] * state + adds[:, c]
        s_in = jnp.stack(entering, axis=1)                 # [b,c,H,P,N]
    else:
        def carry(s, inp):
            add, dec = inp
            return dec[:, :, None, None] * s + add, s

        state, s_in = lax.scan(
            carry, state, (adds.transpose(1, 0, 2, 3, 4),
                           chunk_decay.transpose(1, 0, 2)))
        s_in = s_in.transpose(1, 0, 2, 3, 4)

    # the entering state read out at every position of the chunk
    Ch = jnp.repeat(C, H // G, axis=3)                     # [b,c,Q,H,N]
    y_off = jnp.einsum("bclhn,bchpn->bclhp", Ch, s_in.astype(x.dtype),
                       preferred_element_type=f32)
    y = y + y_off * jnp.exp(cs)[..., None]
    return y.reshape(b, nc * Q, H, P)[:, :T], state


def ssm_step(x, dt, A, B, C, state):
    """One token: ``x`` [b, H, P], ``dt`` [b, H] float32, ``B`` / ``C``
    [b, G, N], ``state`` [b, H, P, N] float32 -> ``(y [b, H, P]
    float32, new state)``.  Elementwise and one reduction over the
    state, all float32 — one pass over the state's bytes."""
    f32 = _wide(x.dtype)
    H, G = x.shape[1], B.shape[1]
    dt = dt.astype(f32)
    Bh = jnp.repeat(B.astype(f32), H // G, axis=1)          # [b, H, N]
    Ch = jnp.repeat(C.astype(f32), H // G, axis=1)
    decay = jnp.exp(dt * A.astype(f32))[:, :, None, None]
    xdt = x.astype(f32) * dt[:, :, None]
    state = decay * state + xdt[..., None] * Bh[:, :, None, :]
    return jnp.sum(state * Ch[:, :, None, :], axis=-1), state


def causal_conv(x, w, bias, tail=None):
    """Depthwise causal convolution over time: ``x`` [b, T, C], ``w``
    [K, C] (``w[K-1]`` multiplies the current step), ``bias`` [C];
    ``tail`` [b, K-1, C] holds the inputs before ``x`` (zeros if None).
    Returns ``(y [b, T, C], the last K-1 inputs [b, K-1, C])``."""
    K, T = w.shape[0], x.shape[1]
    if tail is None:
        tail = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = bias.astype(x.dtype)
    for k in range(K):
        y = y + xp[:, k:k + T] * w[k].astype(x.dtype)
    return y, xp[:, T:]


class Mamba2Mixer(TensorModule):
    """The Mamba-2 mixer over [batch, seq, embed]: ``in_proj`` ->
    (gate ``z`` | ``x B C`` | ``dt``), depthwise causal conv and SiLU on
    ``x B C``, the SSD recurrence, ``rms(y * silu(z))`` in ``n_groups``
    groups (the gated norm, gate first), ``out_proj``.

    ``multipliers`` are the five per-slice scales of ``in_proj``'s
    output (z, x, B, C, dt); ``in_multiplier`` scales its input — the
    family's muP constants, 1 by default.  Parameters: ``in_proj``
    [d_ssm + conv_dim + heads, embed], ``conv_w`` [d_conv, conv_dim],
    ``conv_b``, ``dt_bias`` / ``A_log`` / ``D`` [heads], ``norm``
    [d_ssm], ``out_proj`` [embed, d_ssm], with ``conv_dim = d_ssm +
    2 * n_groups * d_state``.
    """

    def __init__(self, embed_dim: int, n_heads: int, head_dim: int,
                 d_state: int, n_groups: int = 1, d_conv: int = 4,
                 chunk_size: int = 128, norm_eps: float = 1e-5,
                 in_multiplier: float = 1.0,
                 multipliers: Sequence[float] = (1.0,) * 5):
        super().__init__()
        if n_heads % n_groups:
            raise ValueError(f"n_heads {n_heads} not divisible by "
                             f"n_groups {n_groups}")
        if len(multipliers) != 5:
            raise ValueError("multipliers are five: z, x, B, C, dt")
        self.embed_dim = embed_dim
        self.n_heads, self.head_dim = n_heads, head_dim
        self.d_state, self.n_groups = d_state, n_groups
        self.d_conv, self.chunk_size = d_conv, chunk_size
        self.norm_eps = norm_eps
        self.d_ssm = n_heads * head_dim
        self.conv_dim = self.d_ssm + 2 * n_groups * d_state
        self.in_multiplier = float(in_multiplier)
        self.multipliers = tuple(float(m) for m in multipliers)
        self.reset()

    def reset(self):
        H, E = self.n_heads, self.embed_dim
        d_in = self.d_ssm + self.conv_dim + H

        def normal(shape, std):
            with device_draw():
                return RandomNormal(0.0, std).init(shape)

        self._register_param("in_proj", normal((d_in, E), E ** -0.5))
        self._register_param("conv_w", normal((self.d_conv, self.conv_dim),
                                              self.d_conv ** -0.5))
        self._register_param("conv_b", jnp.zeros((self.conv_dim,)))
        # the published initialisers: dt log-uniform in [1e-3, 1e-1]
        # through the inverse softplus, A uniform in [1, 16]
        dt = jnp.exp(jax.random.uniform(
            next_jax_key(), (H,), minval=jnp.log(1e-3),
            maxval=jnp.log(1e-1)))
        self._register_param("dt_bias", dt + jnp.log(-jnp.expm1(-dt)))
        self._register_param("A_log", jnp.log(jax.random.uniform(
            next_jax_key(), (H,), minval=1.0, maxval=16.0)))
        self._register_param("D", jnp.ones((H,)))
        self._register_param("norm", jnp.ones((self.d_ssm,)))
        self._register_param("out_proj", normal((E, self.d_ssm),
                                                self.d_ssm ** -0.5))
        return self

    # -- the pieces, shared by the whole-sequence and one-token forms --
    def _project(self, params, u):
        """``in_proj`` with its multipliers -> (z, xBC, dt_raw)."""
        with jax.named_scope("mixer.in_proj"):
            w = params["in_proj"]
            p = _dot(scaled(u.astype(w.dtype), self.in_multiplier), w)
            if any(m != 1.0 for m in self.multipliers):
                gn = self.n_groups * self.d_state
                sizes = (self.d_ssm, self.d_ssm, gn, gn, self.n_heads)
                mup = jnp.concatenate([jnp.full((n,), m, p.dtype) for n, m
                                       in zip(sizes, self.multipliers)])
                p = p * mup
            return jnp.split(p, [self.d_ssm, self.d_ssm + self.conv_dim],
                             axis=-1)

    def _split_xbc(self, xbc):
        gn = self.n_groups * self.d_state
        lead = xbc.shape[:-1]
        xs, Bm, Cm = jnp.split(xbc, [self.d_ssm, self.d_ssm + gn], axis=-1)
        return (xs.reshape(*lead, self.n_heads, self.head_dim),
                Bm.reshape(*lead, self.n_groups, self.d_state),
                Cm.reshape(*lead, self.n_groups, self.d_state))

    def _dt_A(self, params, dt_raw):
        f32 = _wide(dt_raw.dtype)
        dt = jax.nn.softplus(dt_raw.astype(f32)
                             + params["dt_bias"].astype(f32))
        return dt, -jnp.exp(params["A_log"].astype(f32))

    def _gate_out(self, params, y, xs, z):
        """``D`` skip, the gated grouped RMSNorm and ``out_proj``; ``y``
        float32 [..., H, P]."""
        with jax.named_scope("mixer.gate_norm"):
            f32 = y.dtype
            y = y + params["D"].astype(f32)[:, None] * xs.astype(f32)
            lead = y.shape[:-2]
            y = y.reshape(*lead, self.d_ssm) * jax.nn.silu(z.astype(f32))
            g = y.reshape(*lead, self.n_groups, self.d_ssm // self.n_groups)
            g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + self.norm_eps)
            y = g.reshape(*lead, self.d_ssm).astype(z.dtype) \
                * params["norm"].astype(z.dtype)
        with jax.named_scope("mixer.out_proj"):
            return _dot(y, params["out_proj"])

    # -- whole sequence -------------------------------------------------
    def sequence(self, params, u, state=None):
        """[b, T, E] -> (out [b, T, E], state) where ``state`` is what a
        decoder carries on from the LAST token: ``{"ssm": [b, H, P, N]
        float32, "conv": [b, d_conv - 1, conv_dim]}``."""
        z, xbc, dt_raw = self._project(params, u)
        with jax.named_scope("mixer.conv"):
            xbc, tail = causal_conv(xbc, params["conv_w"], params["conv_b"],
                                    None if state is None else state["conv"])
            xs, Bm, Cm = self._split_xbc(jax.nn.silu(xbc))
        with jax.named_scope("mixer.ssd_scan"):
            dt, A = self._dt_A(params, dt_raw)
            y, ssm = ssd_chunked_scan(
                xs, dt, A, Bm, Cm, self.chunk_size,
                None if state is None else state["ssm"])
        return self._gate_out(params, y, xs, z), {"ssm": ssm, "conv": tail}

    # -- one token ------------------------------------------------------
    def step(self, params, u, state):
        """[b, 1, E] and the carried state -> (out [b, 1, E], state)."""
        z, xbc, dt_raw = self._project(params, u)
        with jax.named_scope("mixer.ssm_step"):
            with jax.named_scope("mixer.conv"):
                xbc, tail = causal_conv(xbc, params["conv_w"],
                                        params["conv_b"], state["conv"])
                xs, Bm, Cm = self._split_xbc(jax.nn.silu(xbc))
            dt, A = self._dt_A(params, dt_raw)
            y, ssm = ssm_step(xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                              state["ssm"])
        out = self._gate_out(params, y[:, None], xs, z)
        return out, {"ssm": ssm, "conv": tail}

    def state_init(self, batch: int, dtype):
        """The carried state before any token."""
        return {"ssm": jnp.zeros((batch, self.n_heads, self.head_dim,
                                  self.d_state), jnp.float32),
                "conv": jnp.zeros((batch, self.d_conv - 1, self.conv_dim),
                                  dtype)}

    def _apply(self, params, buffers, x, training, rng):
        return self.sequence(params, x)[0], buffers


class HybridMambaBlock(Container):
    """Falcon-H1's layer: a Mamba-2 mixer and grouped-query attention
    read the SAME normed input and their outputs are summed (not
    stacked), then a SwiGLU MLP:

        u = rms(x);  x = x + mixer(u) * ssm_out + attn(u * attn_in) * attn_out
        f = rms(x);  x = x + down(up(f) * silu(gate(f) * mlp_gate)) * mlp_down

    Children (the first six are a llama-dialect ``TransformerBlock``'s):
    ``0`` input norm, ``1`` attention, ``2`` pre-MLP norm, ``3`` gate,
    ``4`` up, ``5`` down, ``6`` the mixer.  Every multiplier defaults
    to 1.

    A decoder keeps, beside the attention's K/V, the mixer's SSM state
    ``[B, heads, head, N]`` (float32) and conv tail ``[B, d_conv - 1,
    channels]`` in ONE dict a layer (``state_init`` / ``advance``, the
    decode-state protocol of ``nn/attention.py``): the prompt runs the
    chunked scan from an empty state and hands the state after its last
    token to the decode steps, which advance it one token each.
    """

    is_moe = False
    state_doc = ("it carries a recurrent state (SSM state and conv tail) "
                 "beside its K/V")
    mlp_kind = "swiglu"

    def __init__(self, embed_dim: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, mlp_dim: int, mamba_heads: int,
                 mamba_head_dim: int, mamba_d_state: int,
                 mamba_groups: int = 1, mamba_d_conv: int = 4,
                 mamba_chunk: int = 128, rope_theta: float = 10000.0,
                 norm_eps: float = 1e-5, seq_strategy: str = "dense",
                 attention_in_multiplier: float = 1.0,
                 attention_out_multiplier: float = 1.0,
                 key_multiplier: float = 1.0,
                 ssm_in_multiplier: float = 1.0,
                 ssm_out_multiplier: float = 1.0,
                 ssm_multipliers: Sequence[float] = (1.0,) * 5,
                 mlp_multipliers: Sequence[float] = (1.0, 1.0)):
        super().__init__(
            RMSNorm(embed_dim, eps=norm_eps),
            MultiHeadAttention(embed_dim, num_heads, causal=True,
                               with_bias=False, seq_strategy=seq_strategy,
                               num_kv_heads=num_kv_heads, rope=True,
                               rope_theta=rope_theta, head_dim=head_dim,
                               key_multiplier=key_multiplier),
            RMSNorm(embed_dim, eps=norm_eps),
            Linear(embed_dim, mlp_dim, with_bias=False),
            Linear(embed_dim, mlp_dim, with_bias=False),
            Linear(mlp_dim, embed_dim, with_bias=False),
            Mamba2Mixer(embed_dim, mamba_heads, mamba_head_dim,
                        mamba_d_state, n_groups=mamba_groups,
                        d_conv=mamba_d_conv, chunk_size=mamba_chunk,
                        norm_eps=norm_eps,
                        in_multiplier=ssm_in_multiplier,
                        multipliers=ssm_multipliers))
        self.attention_in_multiplier = float(attention_in_multiplier)
        self.attention_out_multiplier = float(attention_out_multiplier)
        self.ssm_out_multiplier = float(ssm_out_multiplier)
        self.mlp_multipliers = tuple(float(m) for m in mlp_multipliers)

    @property
    def mixer(self) -> Mamba2Mixer:
        return self.modules[6]

    def mix(self, h, attn_out, mixer_out):
        """The residual sum of the two branches, each at its scale."""
        return (h + scaled(attn_out, self.attention_out_multiplier)
                + scaled(mixer_out, self.ssm_out_multiplier))

    def apply_fn(self, params, buffers, x, training, rng):
        def run(i, v):
            return self.modules[i].apply_fn(params[str(i)], buffers[str(i)],
                                            v, training, None)[0]

        u = run(0, x)
        with jax.named_scope("mixer.attention"):
            a = run(1, scaled(u, self.attention_in_multiplier))
        x = self.mix(x, a, run(6, u))
        f = run(2, x)
        gm, dm = self.mlp_multipliers
        g = jax.nn.silu(scaled(run(3, f), gm)) * run(4, f)
        return x + scaled(run(5, g), dm), buffers

    # -- decode: the state between tokens, and Tq tokens against it ------
    def state_init(self, batch: int, dtype, length: int, int8: bool = False):
        return {**self.modules[1].state_init(batch, dtype, length, int8),
                **self.mixer.state_init(batch, dtype)}

    def footprint(self, batch: int, dtype, length: int, int8: bool = False):
        return {**self.modules[1].footprint(batch, dtype, length, int8),
                **footprint(self.mixer, batch, dtype, length, int8)}

    def advance(self, params, h, state, pos):
        """The mixer reads the same normed input as the attention: the
        prompt runs the chunked scan from an empty state and keeps the
        state after its last token, a decode step advances it."""
        def run(i, v):
            return self.modules[i].apply_fn(params[str(i)], {}, v, False,
                                            None)[0]

        u = run(0, h)
        with jax.named_scope("mixer.attention"):
            a, state = advance(
                self.modules[1], params["1"],
                scaled(u, self.attention_in_multiplier), state, pos)
        m, carried = advance(self.mixer, params["6"], u, state, pos)
        h = self.mix(h, a, m)
        f = run(2, h)
        gm, dm = self.mlp_multipliers
        g, up = run(3, f), run(4, f)
        f = run(5, jax.nn.silu(scaled(g, gm)) * up)
        return h + scaled(f, dm), {**state, **carried}


def scaled(v, m: float):
    """``v * m``; nothing at all for ``m == 1`` so that a block without
    multipliers lowers to the program it always did."""
    return v if m == 1.0 else v * m
