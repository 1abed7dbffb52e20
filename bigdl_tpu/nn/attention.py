"""Attention layers — the TPU rebuild's first-class long-context stack.

The reference has no attention (it predates transformers; SURVEY §5.7),
so these layers have no reference counterpart to cite — they exist
because the TPU framework makes long-context and sequence parallelism
first-class.  The compute lives in ``parallel/ring_attention.py``; these
modules wrap it in the standard layer protocol.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..parallel.ring_attention import (attention, blockwise_attention,
                                       ring_attention, ulysses_attention)
from .initialization import IN_OUT, ONE_D, RandomNormal, Xavier, Zeros
from .module import TensorModule
from .normalization import rms_normed

SEQ_STRATEGIES = ("dense", "flash", "block", "ring", "ulysses",
                  "blocksparse")

#: block-sparse mask patterns the layer can build (ops/block_sparse.py)
SPARSE_PATTERNS = ("sliding", "strided")


ROPE_KINDS = ("half", "interleaved")


def rope_kind(rope):
    """``True`` / ``"half"`` / ``"interleaved"`` / falsy -> the kind, or
    None for a layer without positions."""
    kind = "half" if rope is True else (rope or None)
    if kind is not None and kind not in ROPE_KINDS:
        raise ValueError(f"rope {rope!r} not in {ROPE_KINDS}, True or None")
    return kind


def yarn_rotation(rope_dim: int, theta: float, scaling: dict) -> tuple:
    """YaRN as DeepSeek-V3's published modelling code computes it from a
    configuration's ``rope_scaling`` (``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``mscale``, ``mscale_all_dim``): ``(inv_freq [rope_dim / 2] float32,
    what multiplies cos and sin, what multiplies the softmax scale)``.
    Dimension pair ``i`` keeps its frequency ``f_i = theta^(-2i / d)``
    below ``low``, takes ``f_i / factor`` above ``high`` and a linear
    blend between; ``m(s) = 0.1 s ln(factor) + 1``."""
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind != "yarn":
        raise ValueError(f"rope_scaling of type {kind!r}: only 'yarn' is "
                         "built")
    d, factor = int(rope_dim), float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def boundary(rotations: float) -> float:
        return (d * np.log(orig / (rotations * 2 * np.pi))
                / (2 * np.log(theta)))

    low = max(np.floor(boundary(float(scaling.get("beta_fast", 32)))), 0)
    high = min(np.ceil(boundary(float(scaling.get("beta_slow", 1)))), d - 1)
    if low == high:
        high += 0.001                       # the published guard
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    inv_freq = (f / factor) * ramp + f * (1.0 - ramp)

    def m(s) -> float:
        return 0.1 * float(s) * np.log(factor) + 1.0 if factor > 1 else 1.0

    all_dim = scaling.get("mscale_all_dim", 0)
    return (inv_freq.astype(np.float32),
            float(m(scaling.get("mscale", 1)) / m(all_dim)),
            float(m(all_dim) ** 2) if all_dim else 1.0)


def rope_rotate(x, pos, theta: float = 10000.0, interleaved: bool = False,
                inv_freq=None, mscale: float = 1.0):
    """Rotary position embedding over ``x`` [B, H, T, D] at absolute
    positions ``pos`` [T]: HF Llama's rotate-half convention (dim ``i``
    pairs with ``i + D/2``), or with ``interleaved`` GPT-J's (dim ``2i``
    pairs with ``2i + 1``) — the same rotation under a permutation of
    the head's columns.  ``inv_freq`` [D/2] stands in for ``theta``'s
    own frequencies where a scaling has blended them
    (:func:`yarn_rotation`), and ``mscale`` multiplies cos and sin."""
    D = x.shape[-1]
    # like RMSNorm: float64 oracles keep their precision, low-precision
    # inputs still get at least float32 tables
    ct = jnp.promote_types(x.dtype, jnp.float32)
    if inv_freq is None:
        inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=ct) / D))
    else:
        inv = jnp.asarray(inv_freq, ct)
    ang = pos.astype(ct)[:, None] * inv[None, :]            # [T, D/2]
    if interleaved:
        cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)           # [T, D]
        sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)
        # the partner of an even lane is its right neighbour (negated),
        # of an odd lane its left one: ONE product with a constant
        # [D, D] matrix of 0 / +1 / -1 (exact in any dtype: one nonzero
        # term a sum) — no [D/2, 2] reshape of the minor dimension, and
        # none of the lane-shifted copies of x two ``roll``s cost (four
        # arrays of q's size at a prompt of thousands of positions)
        swap = np.zeros((D, D), np.float32)
        even = np.arange(0, D, 2)
        swap[even + 1, even] = -1.0          # rot[2i]   = -x[2i + 1]
        swap[even, even + 1] = 1.0           # rot[2i+1] =  x[2i]
        rot = jnp.einsum("...d,de->...e", x, jnp.asarray(swap, x.dtype),
                         precision=lax.Precision.HIGHEST)
    else:
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)  # [T, D]
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
        x1, x2 = x[..., :D // 2], x[..., D // 2:]
        rot = jnp.concatenate([-x2, x1], -1)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    return (x * cos[None, None].astype(x.dtype)
            + rot * sin[None, None].astype(x.dtype))


class MultiHeadAttention(TensorModule):
    """Multi-head self-attention over [batch, seq, embed].

    ``seq_strategy`` picks how the sequence dimension is handled:
      * ``"dense"``  — one [T, T] matmul (short sequences)
      * ``"flash"``  — Pallas online-softmax kernel (ops/flash_attention;
        jnp fallback off-TPU), scores never materialized
      * ``"block"``  — single-device flash-style blockwise attention
      * ``"ring"``   — ring context parallelism; REQUIRES running inside
        shard_map with the sequence sharded over ``seq_axis``
      * ``"ulysses"`` — all-to-all sequence parallelism (same requirement)
      * ``"blocksparse"`` — BLaST block-sparse Pallas kernel
        (ops/block_sparse.py): only the block pairs a static mask
        allows are ever read or multiplied.  The mask is built from
        ``sparse_pattern`` at ``sparse_block`` granularity (default
        ``block_size``): ``"sliding"`` = ``sparse_window`` blocks back
        plus ``sparse_globals`` anchor blocks (Longformer-style);
        ``"strided"`` = own block + every ``sparse_stride``-th block.
        Masks are cached per (T, S); off-TPU the identical math runs
        densely with the mask applied elementwise.  On a TPU the block
        must be a multiple of 128 or span the whole sequence — a
        smaller block raises (ops/block_sparse.py ``_kernel_path``).
    """

    def __init__(self, embed_dim: int, num_heads: int,
                 causal: bool = False, with_bias: bool = True,
                 seq_strategy: str = "dense", seq_axis: str = "seq",
                 block_size: int = 512,
                 num_kv_heads: "int | None" = None,
                 rope: bool = False, rope_theta: float = 10000.0,
                 sparse_pattern: str = "sliding",
                 sparse_window: int = 2, sparse_globals: int = 1,
                 sparse_stride: int = 4,
                 sparse_block: "int | None" = None,
                 head_dim: "int | None" = None,
                 key_multiplier: float = 1.0,
                 window: "int | None" = None,
                 qk_norm: bool = False, norm_eps: float = 1e-6):
        super().__init__()
        assert head_dim or embed_dim % num_heads == 0, \
            "embed_dim % num_heads != 0"
        if seq_strategy not in SEQ_STRATEGIES:
            raise ValueError(f"seq_strategy {seq_strategy!r} not in "
                             f"{SEQ_STRATEGIES}")
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        # heads need not tile the width: ``head_dim`` given, q and the
        # output projection are [heads * head_dim] wide
        self.head_dim = int(head_dim or embed_dim // num_heads)
        # a constant scale on the keys (a muP multiplier); 1 = none
        self.key_multiplier = float(key_multiplier)
        # RMSNorm over the head_dim numbers of EACH query and key head
        # (one gain vector for all heads), before the rotation
        self.qk_norm, self.norm_eps = bool(qk_norm), float(norm_eps)
        self.causal = causal
        self.with_bias = with_bias
        self.seq_strategy = seq_strategy
        self.seq_axis = seq_axis
        self.block_size = block_size
        # grouped-query attention: kv projections carry num_kv_heads
        # heads (each shared by num_heads/num_kv_heads query groups)
        self.num_kv_heads = int(num_kv_heads or num_heads)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads {num_heads} not divisible by num_kv_heads "
                f"{self.num_kv_heads}")
        if sparse_pattern not in SPARSE_PATTERNS:
            raise ValueError(f"sparse_pattern {sparse_pattern!r} not in "
                             f"{SPARSE_PATTERNS}")
        self.sparse_pattern = sparse_pattern
        self.sparse_window = int(sparse_window)
        self.sparse_globals = int(sparse_globals)
        self.sparse_stride = int(sparse_stride)
        self.sparse_block = sparse_block
        self._sparse_masks = {}   # (T, S) -> BlockMask (static, hashable)
        # ``rope``: True / "half" (rotate-half), "interleaved" (GPT-J
        # pairs), or falsy — a layer with no positions at all
        self.rope_kind = rope_kind(rope)
        self.rope = self.rope_kind is not None
        self.rope_theta = float(rope_theta)
        # sliding window: query t sees keys t - window + 1 .. t
        self.window = int(window) if window else None
        if self.window and not (causal and seq_strategy in ("dense",
                                                             "flash")):
            raise ValueError(
                "window composes with causal dense/flash attention only "
                f"(got causal={causal}, seq_strategy={seq_strategy!r})")
        if self.rope and seq_strategy in ("ring", "ulysses"):
            # the rotation needs GLOBAL positions, which the module
            # cannot know inside a seq-sharded shard_map region
            raise ValueError(
                "rope composes with dense/flash/block attention; "
                "ring/ulysses sequence parallelism would rotate at "
                "shard-local positions")
        self.reset()

    def reset(self):
        w_init = self._init_methods.get("weight", (Xavier(), None))[0]
        b_init = self._init_methods.get("bias", (Zeros(), None))[0]
        E = self.embed_dim
        qd = self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        for name, shape in (("wq", (qd, E)), ("wk", (kv, E)),
                            ("wv", (kv, E)), ("wo", (E, qd))):
            self._register_param(name, w_init.init(shape, IN_OUT))
        if self.with_bias:
            for name, n in (("bq", qd), ("bk", kv), ("bv", kv), ("bo", E)):
                self._register_param(name, b_init.init((n,), ONE_D))
        if getattr(self, "qk_norm", False):
            for name in ("q_norm", "k_norm"):
                self._register_param(name, jnp.ones((self.head_dim,),
                                                    jnp.float32))
        return self

    def normed_heads(self, params, q, k):
        """``q`` / ``k`` [B, heads, T, head_dim] through the per-head
        RMSNorms (``qk_norm``); as they came without them.  Shared with
        the cached decoder, which keeps the normed, rotated keys."""
        if not getattr(self, "qk_norm", False):
            return q, k
        return (rms_normed(q, params["q_norm"], self.norm_eps),
                rms_normed(k, params["k_norm"], self.norm_eps))

    def _split(self, x, heads=None):
        B, T, _ = x.shape
        h = heads or self.num_heads
        return x.reshape(B, T, h, self.head_dim).transpose(0, 2, 1, 3)

    def block_mask(self, T, S):
        """The layer's static :class:`~bigdl_tpu.ops.BlockMask` for a
        (T, S) attention — built once per shape and cached (hashable,
        so jit never retraces on reuse).  Public so benches and the
        perf accountant can derive the executed-work correction from
        the EXACT mask the layer runs."""
        key = (int(T), int(S))
        if key not in self._sparse_masks:
            from ..ops.block_sparse import (pick_block_divisor,
                                            sliding_window_mask,
                                            strided_mask)

            target = self.sparse_block or self.block_size
            b = pick_block_divisor(T, S, target)
            nq, nk = T // b, S // b
            if self.sparse_pattern == "strided":
                m = strided_mask(nq, nk, self.sparse_stride,
                                 causal=self.causal, block_q=b,
                                 block_k=b)
            else:
                m = sliding_window_mask(nq, nk, self.sparse_window,
                                        n_global=self.sparse_globals,
                                        causal=self.causal, block_q=b,
                                        block_k=b)
            self._sparse_masks[key] = m
        return self._sparse_masks[key]

    def _attend(self, q, k, v):
        if self.seq_strategy == "blocksparse":
            from ..ops.block_sparse import block_sparse_attention

            return block_sparse_attention(
                q, k, v, self.block_mask(q.shape[2], k.shape[2]),
                causal=self.causal)
        if self.seq_strategy == "ring":
            return ring_attention(q, k, v, axis_name=self.seq_axis,
                                  causal=self.causal)
        if self.seq_strategy == "ulysses":
            return ulysses_attention(q, k, v, axis_name=self.seq_axis,
                                     causal=self.causal,
                                     block_size=self.block_size)
        if self.seq_strategy == "block":
            return blockwise_attention(q, k, v, block_size=self.block_size,
                                       causal=self.causal)
        window = getattr(self, "window", None)
        if self.seq_strategy == "flash":
            from ..ops import flash_attention

            return flash_attention(q, k, v, causal=self.causal,
                                   window=window)
        if window:
            from ..ops.flash_attention import windowed_attention

            return windowed_attention(q, k, v, window)
        return attention(q, k, v, causal=self.causal)

    def _apply(self, params, buffers, x, training, rng):
        def proj(x, w, b):
            y = jnp.dot(x, w.T)
            return y + params[b] if self.with_bias else y

        q = self._split(proj(x, params["wq"], "bq"))
        k = self._split(proj(x, params["wk"], "bk"), self.num_kv_heads)
        v = self._split(proj(x, params["wv"], "bv"), self.num_kv_heads)
        if getattr(self, "key_multiplier", 1.0) != 1.0:
            k = k * self.key_multiplier
        q, k = self.normed_heads(params, q, k)
        if self.rope:
            pos = jnp.arange(q.shape[2])
            il = getattr(self, "rope_kind", "half") == "interleaved"
            q = rope_rotate(q, pos, self.rope_theta, interleaved=il)
            k = rope_rotate(k, pos, self.rope_theta, interleaved=il)
        if self.num_kv_heads != self.num_heads:
            group = self.num_heads // self.num_kv_heads
            k = jnp.repeat(k, group, axis=1)
            v = jnp.repeat(v, group, axis=1)
        # device scope (``telemetry.tracer.DEVICE_SCOPES``): the
        # attention itself, whichever arm; projections and rotation
        # stay outside it
        with jax.named_scope("attention.core"):
            o = self._attend(q, k, v)
        B, H, T, D = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(B, T, H * D)
        return proj(o, params["wo"], "bo"), buffers


class LatentAttention(TensorModule):
    """Multi-head LATENT attention (DeepSeek-V2's MLA; GLM-4.7-Flash's
    ``glm4_moe_lite``) over [batch, seq, embed]: queries and keys/values
    go through low-rank bottlenecks, and the rotated part of the key is
    ONE vector a position shared by all heads.

        c_q  = RMSNorm_q(x wq_a)                    [q_rank]
        q    = c_q wq_b  -> [H, nope + rope] = q_nope | q_rope
        c, k_r = x wkv_a -> [kv_rank] | [rope]
        c_kv = RMSNorm_kv(c);  k_rope = RoPE(k_r);  q_rope = RoPE(q_rope)
        k_nope | v = c_kv wkv_b -> [H, nope] | [H, v_dim]
        scores = (q_nope k_nope + q_rope k_rope) / sqrt(nope + rope)

    Seven leaves, all [out, in] but the two gains: ``wq_a``, ``q_norm``,
    ``wq_b``, ``wkv_a``, ``kv_norm``, ``wkv_b`` (per head ``nope`` rows
    of keys, then ``v_dim`` rows of values), ``wo``.  Rotation is by
    halves over the ``rope`` dims at ``rope_theta``.

    ``apply_fn`` is the EXPANDED full-sequence form (per-head K and V
    made from the latent, then causal flash or dense attention) — the
    training and prefill form, differentiable by autodiff.  What a
    decode step keeps and reads is ``c_kv`` and the rotated ``k_rope``
    (``kv_rank + rope`` numbers a position), with ``wkv_b`` absorbed
    into the query and the output: ``models/generate.py``."""

    kind = "latent"

    def __init__(self, embed_dim: int, num_heads: int, q_rank: int,
                 kv_rank: int, nope_dim: int, rope_dim: int, v_dim: int,
                 rope_theta: float = 10000.0, norm_eps: float = 1e-6,
                 seq_strategy: str = "dense",
                 init_std: "float | None" = None,
                 rope_scaling: "dict | None" = None):
        super().__init__()
        # matrices drawn normal(0, init_std); None: Xavier, as the
        # other attention draws (an init method set later wins)
        self.init_std = init_std
        if seq_strategy not in ("dense", "flash"):
            raise ValueError(f"seq_strategy {seq_strategy!r} not in "
                             "('dense', 'flash')")
        if seq_strategy == "flash" and v_dim != nope_dim + rope_dim:
            raise ValueError("the flash kernels take one head size: "
                             f"v_dim {v_dim} != {nope_dim} + {rope_dim}")
        if rope_dim % 2:
            raise ValueError(f"rope_dim {rope_dim} is odd")
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.qk_dim = nope_dim + rope_dim
        self.rope_theta, self.norm_eps = float(rope_theta), float(norm_eps)
        self.seq_strategy = seq_strategy
        # no scaling: the rotation's own frequencies, cos and sin as
        # they are, the scale 1 / sqrt(qk_dim)
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.inv_freq, self.rope_mscale, self.softmax_mult = (
            yarn_rotation(rope_dim, self.rope_theta, self.rope_scaling)
            if self.rope_scaling else (None, 1.0, 1.0))
        self.reset()

    @property
    def softmax_scale(self) -> float:
        """What multiplies this layer's scores before the softmax."""
        return self.softmax_mult / float(np.sqrt(self.qk_dim))

    def rotate(self, x, pos):
        """``x`` [B, H, T, rope] rotated at ``pos`` [T]."""
        return rope_rotate(x, pos, self.rope_theta, inv_freq=self.inv_freq,
                           mscale=self.rope_mscale)

    def reset(self):
        default = (Xavier() if self.init_std is None
                   else RandomNormal(0.0, float(self.init_std)))
        w_init = self._init_methods.get("weight", (default, None))[0]
        E, H = self.embed_dim, self.num_heads
        for name, shape in (
                ("wq_a", (self.q_rank, E)),
                ("wq_b", (H * self.qk_dim, self.q_rank)),
                ("wkv_a", (self.kv_rank + self.rope_dim, E)),
                ("wkv_b", (H * (self.nope_dim + self.v_dim), self.kv_rank)),
                ("wo", (E, H * self.v_dim))):
            self._register_param(name, w_init.init(shape, IN_OUT))
        for name, n in (("q_norm", self.q_rank), ("kv_norm", self.kv_rank)):
            self._register_param(name, jnp.ones((n,), jnp.float32))
        return self

    # -- the pieces the cached decoder shares with ``apply_fn`` ---------
    def queries(self, params, x, pos):
        """(q_nope [B, H, T, nope], q_rope [B, H, T, rope] rotated at
        ``pos`` [T])."""
        B, T, _ = x.shape
        cq = rms_normed(jnp.dot(x, params["wq_a"].T), params["q_norm"],
                        self.norm_eps)
        q = jnp.dot(cq, params["wq_b"].T).reshape(
            B, T, self.num_heads, self.qk_dim).transpose(0, 2, 1, 3)
        return (q[..., :self.nope_dim],
                self.rotate(q[..., self.nope_dim:], pos))

    def latent(self, params, x, pos):
        """What the cache keeps of ``x`` at ``pos`` [T]: (c_kv [B, T,
        kv_rank] normed, k_rope [B, T, rope] rotated)."""
        ckr = jnp.dot(x, params["wkv_a"].T)
        ckv = rms_normed(ckr[..., :self.kv_rank], params["kv_norm"],
                         self.norm_eps)
        kr = self.rotate(ckr[:, None, :, self.kv_rank:], pos)[:, 0]
        return ckv, kr

    def up_weights(self, params):
        """``wkv_b`` by head: (W_uk [H, nope, kv_rank], W_uv [H, v_dim,
        kv_rank])."""
        w = params["wkv_b"].reshape(self.num_heads,
                                    self.nope_dim + self.v_dim, self.kv_rank)
        return w[:, :self.nope_dim], w[:, self.nope_dim:]

    def expand(self, params, ckv, kr):
        """Per-head (k [B, H, T, nope + rope], v [B, H, T, v_dim]) from
        the latent — the full-sequence form only."""
        B, T, _ = ckv.shape
        H = self.num_heads
        kv = jnp.dot(ckv, params["wkv_b"].T).reshape(
            B, T, H, self.nope_dim + self.v_dim).transpose(0, 2, 1, 3)
        k = jnp.concatenate(
            [kv[..., :self.nope_dim],
             jnp.broadcast_to(kr[:, None], (B, H, T, self.rope_dim))], -1)
        return k, kv[..., self.nope_dim:]

    def attend_full(self, q_nope, q_rope, k, v, flash=None):
        """Causal attention of the whole sequence on per-head K and V at
        ``softmax_scale``: the flash kernels where ``flash`` says so
        (None: ``seq_strategy``), else plain attention with whole scores
        — the only form for a value head narrower than the query's —
        under the device scope ``mla.prefill_attend``."""
        q = jnp.concatenate([q_nope, q_rope], -1)
        scaled = self.softmax_mult != 1.0
        if self.seq_strategy == "flash" if flash is None else flash:
            from ..ops import flash_attention

            return flash_attention(
                q, k, v, causal=True,
                sm_scale=self.softmax_scale if scaled else None)
        # where this pass stands in for the flash kernels (a narrower
        # value head) its scores are float32 as theirs are
        narrow = v.shape[-1] != q.shape[-1]
        with jax.named_scope("mla.prefill_attend"):
            return attention(q, k, v, causal=True,
                             scale=self.softmax_scale if scaled else None,
                             score_dtype=jnp.promote_types(
                                 q.dtype, jnp.float32) if narrow else None)

    def out_proj(self, params, o):
        B, H, T, D = o.shape
        return jnp.dot(o.transpose(0, 2, 1, 3).reshape(B, T, H * D),
                       params["wo"].T)

    def _apply(self, params, buffers, x, training, rng):
        pos = jnp.arange(x.shape[1])
        q_nope, q_rope = self.queries(params, x, pos)
        k, v = self.expand(params, *self.latent(params, x, pos))
        return self.out_proj(params, self.attend_full(q_nope, q_rope, k,
                                                      v)), buffers
