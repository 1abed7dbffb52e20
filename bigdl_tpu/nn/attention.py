"""Attention layers — the TPU rebuild's first-class long-context stack.

The reference has no attention (it predates transformers; SURVEY §5.7),
so these layers have no reference counterpart to cite — they exist
because the TPU framework makes long-context and sequence parallelism
first-class.  The compute lives in ``parallel/ring_attention.py``; these
modules wrap it in the standard layer protocol.

**What a layer keeps between tokens lives with the layer.**  A
token-mixing operator a decoder can carry (``models/generate.py``)
answers three calls, as ``nn.Mamba2Mixer`` and ``nn.GatedShortConv`` do:

* ``state_init(batch, dtype)`` — its state before any token, a dict of
  arrays;
* ``sequence(params, x, state=None)`` — a sequence after ``state``
  (None: from its start) -> ``(out, the leaves it wrote)``;
* ``step(params, x, state)`` — one token -> ``(out, the leaves it
  wrote)``.

An operator whose state has a POSITION axis (``keeps_positions``: the
two attentions here) is told more: ``state_init(batch, dtype, length,
int8=False)`` leaves room for the calling program's ``length`` positions
— as int8 where it can be held so; one that cannot raises ``TypeError``
— ``sequence(params, x, state)`` writes the prompt into that fresh
state, and ``step(params, x, state, pos)`` is told the token's absolute
position.  An operator MAY also say ``footprint(batch, dtype, length,
int8)`` (its bytes by kind and the arm its step compiles, for
``cache_footprint``; unsaid, all it keeps is ``recurrent_state_bytes``)
and ``state_doc`` (what it keeps, in words, for whoever cannot hold
it).  A block around it answers
``state_init`` likewise and ``advance(params, h, state, pos)`` — Tq
tokens at ``pos`` (the Python ``0``: the whole prompt) against its state
— with ``footprint``, ``prefill_plan(tokens, dtype)`` (the arm its
prompt pass compiles, in words), ``counters`` (leaf -> the statistic a
call returns of it) and ``state_doc`` where it has any: ``nn.mamba``,
``models/transformer.py``, ``models/parallel_moe.py``,
``models/latent_moe.py``.
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


# -- the decode-state protocol: what the blocks ask of an operator ---------
def from_start(pos) -> bool:
    """``pos`` is the Python ``0``: the whole prompt, not a step."""
    return isinstance(pos, int) and pos == 0


def fresh_state(op, batch: int, dtype, length: int, int8: bool = False):
    """``op``'s state before any token, for a program of ``length``
    positions; a state without positions is no K/V and stays in
    ``dtype`` whatever ``int8`` says."""
    if getattr(op, "keeps_positions", False):
        return op.state_init(batch, dtype, length, int8)
    return op.state_init(batch, dtype)


def advance(op, params, x, state, pos):
    """``op`` over Tq tokens at ``pos`` against ``state``: ``sequence``
    for the whole prompt (into the fresh state, where it has room to
    fill), ``step`` after it."""
    if getattr(op, "keeps_positions", False):
        return (op.sequence(params, x, state) if from_start(pos)
                else op.step(params, x, state, pos))
    return (op.sequence(params, x) if from_start(pos)
            else op.step(params, x, state))


def state_bytes(shapes) -> int:
    return sum(a.size * a.dtype.itemsize for a in shapes.values())


def footprint(op, batch: int, dtype, length: int, int8: bool = False):
    """``op.footprint(...)``, or for an operator that says nothing all
    of its state as ``recurrent_state_bytes``."""
    if hasattr(op, "footprint"):
        return op.footprint(batch, dtype, length, int8)
    return {"recurrent_state_bytes": state_bytes(jax.eval_shape(
        lambda: fresh_state(op, batch, dtype, length, int8)))}


def _proj(x, params, w, b, with_bias):
    y = jnp.dot(x, params[w].T)
    return y + params[b] if with_bias else y


def _quant(x):
    """absmax int8 over the head dim: x ≈ q * s, q int8,
    s [β..., 1] float32."""
    s_ = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                 keepdims=True) / 127.0 + 1e-12
    q_ = jnp.round(x.astype(jnp.float32) / s_).astype(jnp.int8)
    return q_, s_


def _ring_put(arr, x, pos):
    """``x`` [B, Hkv, Tq, ·] at positions pos.. into ``arr``, whose
    time axis may be SHORTER than the positions the program spans
    (a sliding layer's ring): a token goes to slot ``pos mod`` the
    ring's length; of a prompt longer than the ring the last ring's
    worth is kept, each position at its slot."""
    ring, Tq = arr.shape[2], x.shape[2]
    if isinstance(pos, int):                # prefill, from 0
        if Tq <= ring:
            return lax.dynamic_update_slice(arr, x, (0, 0, pos, 0))
        return jnp.roll(x[:, :, Tq - ring:], (Tq - ring) % ring, axis=2)
    return lax.dynamic_update_slice(arr, x, (0, 0, pos % ring, 0))


def _cache_write(cache, k, v, pos, ringed, int8):
    put = _ring_put if ringed else (
        lambda arr, x, pos: lax.dynamic_update_slice(arr, x,
                                                     (0, 0, pos, 0)))
    new = dict(cache)
    for name, x in (("k", k), ("v", v)):
        if int8:
            x, scale = _quant(x)
            new[name + "_scale"] = put(cache[name + "_scale"], scale,
                                       pos)
        new[name] = put(cache[name], x, pos)
    return new


def _cache_kv(cache, dt, int8):
    """(k, v) dense views of the cache — for int8 the convert+
    scale is elementwise and fuses into the attention dot's
    operand read (the int8 bytes are what HBM streams)."""
    if int8:
        return (cache["k"].astype(dt) * cache["k_scale"].astype(dt),
                cache["v"].astype(dt) * cache["v_scale"].astype(dt))
    return cache["k"], cache["v"]


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

    What a DECODER keeps of the layer (``state_init`` / ``sequence`` /
    ``step``) is its K and V by K/V head, ``[B, Hkv, T, Dh]`` — the KV
    head count, smaller than the query's under GQA — normed and rotated
    as they are attended, because cached decode attention is another
    computation than the full-sequence forward.  ``T`` is the calling
    program's length; a layer with a sliding ``window`` keeps ``min(T,
    window)`` positions, written at ``pos mod`` that length once it is
    full (a ring) and read with each slot's absolute position and the
    lower bound ``k_pos > q_pos - window``.  Under ``int8`` K and V are
    int8 with a float32 scale per (batch, head, position) — absmax
    rounding over the head dim: decode is cache-bandwidth-bound, so
    halving (vs bf16) the bytes read per step buys throughput; lossy by
    construction, and the prompt's own attention stays full-precision.
    A step's attend is chosen by shapes alone
    (``ops.gqa_attend.attend_plan``): where the cache is large, on a
    TPU, ONE Pallas kernel walks it in blocks of 128 positions up to the
    block the step's position falls in; everywhere else — small
    buckets, every other backend, a ring, int8 storage — the plain
    einsums (``ops.gqa_attend.gqa_attend_reference``) read the whole
    static cache twice.
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

    def _rep(self, kv):
        """The Hkv K/V heads repeated to the H query heads (GQA) — on
        whole-sequence tensors only; a decode step keeps the cache
        un-repeated (the grouped einsums, the kernel)."""
        if self.num_kv_heads == self.num_heads:
            return kv
        return jnp.repeat(kv, self.num_heads // self.num_kv_heads, axis=1)

    def heads(self, params, x, pos=None):
        """(q [B, H, T, Dh], k, v [B, Hkv, T, Dh]) of ``x`` [B, T, E]:
        the projections, the key multiplier, the per-head norms, then
        the rotation at ABSOLUTE positions — ``pos .. pos + T - 1``
        (None: from 0), or ``pos`` [T] itself — what a cache holds is
        normed, rotated keys."""
        q = self._split(_proj(x, params, "wq", "bq", self.with_bias))
        k = self._split(_proj(x, params, "wk", "bk", self.with_bias),
                        self.num_kv_heads)
        v = self._split(_proj(x, params, "wv", "bv", self.with_bias),
                        self.num_kv_heads)
        if getattr(self, "key_multiplier", 1.0) != 1.0:
            k = k * self.key_multiplier
        q, k = self.normed_heads(params, q, k)
        if self.rope:
            at = pos
            if pos is None or jnp.ndim(pos) != 1:
                at = jnp.arange(q.shape[2])
                at = at if pos is None else pos + at
            il = getattr(self, "rope_kind", "half") == "interleaved"
            q = rope_rotate(q, at, self.rope_theta, interleaved=il)
            k = rope_rotate(k, at, self.rope_theta, interleaved=il)
        return q, k, v

    def merged(self, params, o):
        """``o`` [B, H, T, Dh] through the output projection."""
        B, H, T, D = o.shape
        return _proj(o.transpose(0, 2, 1, 3).reshape(B, T, H * D), params,
                     "wo", "bo", self.with_bias)

    # -- what a decoder keeps of the layer, and a token against it ------
    keeps_positions = True

    def state_init(self, batch: int, dtype, length: int, int8: bool = False):
        """K and V ``[batch, Hkv, min(length, window), Dh]`` in
        ``dtype``, or int8 with ``k_scale`` / ``v_scale`` beside them."""
        kv = (batch, self.num_kv_heads,
              min(length, getattr(self, "window", None) or length),
              self.head_dim)
        if int8:
            return {"k": jnp.zeros(kv, jnp.int8),
                    "k_scale": jnp.zeros(kv[:3] + (1,), jnp.float32),
                    "v": jnp.zeros(kv, jnp.int8),
                    "v_scale": jnp.zeros(kv[:3] + (1,), jnp.float32)}
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}

    def footprint(self, batch: int, dtype, length: int, int8: bool = False):
        """``kv_cache_bytes`` as allocated — again by the layer's KIND,
        ``kv_cache_bytes_window`` (it has a ``window``, whether or not
        its cache is a ring at this length) or ``kv_cache_bytes_full``
        — and the arm a step of this layer compiles: ``kv_attend``
        ``("kernel" | "einsum", positions a block of the kernel's
        walk)`` — the rule the step reads."""
        from ..ops.gqa_attend import attend_plan

        shapes = jax.eval_shape(
            lambda: self.state_init(batch, dtype, length, int8))
        k = shapes["k"]
        block = attend_plan(batch, k.shape[1], k.shape[2], k.shape[3],
                            k.dtype, 1, self._ring(k))
        held = state_bytes(shapes)
        sliding = bool(getattr(self, "window", None))
        return {"kv_cache_bytes": held,
                "kv_cache_bytes_window": held * sliding,
                "kv_cache_bytes_full": held * (not sliding),
                "kv_attend": ("kernel" if block else "einsum", block)}

    def _ring(self, k):
        """The layer's window where its cache ``k`` is a ring: a sliding
        layer's cache as long as its window; a shorter one holds every
        position of its program, all inside the window: a plain cache."""
        window = getattr(self, "window", None)
        return window if window and k.shape[2] == window else None

    def _decode_attend(self, q, k_cache, v_cache, pos, window, int8):
        from ..ops.gqa_attend import (_gqa_attend_kernel, attend_plan,
                                      gqa_attend_reference)

        # one kernel pass over the written part of the cache where the
        # shapes say it wins, the plain einsums over the whole of it
        # otherwise (a ring, int8 storage, ``Tq > 1``, a small cache,
        # every backend but a TPU)
        H, Hkv, Dh = self.num_heads, self.num_kv_heads, self.head_dim
        block = attend_plan(q.shape[0], Hkv, k_cache.shape[2], Dh,
                            jnp.int8 if int8 else k_cache.dtype,
                            q.shape[2], window)
        with jax.named_scope("attention.decode_attend"):
            if block:
                return _gqa_attend_kernel(q[:, :, 0], k_cache, v_cache, pos,
                                          block, False)[:, :, None]
            if window is None:
                return gqa_attend_reference(q, k_cache, v_cache, pos, H,
                                            Hkv, Dh)
            # a ring: slot s holds the latest position <= pos that is s
            # mod the ring's length (negative: none yet)
            ring = k_cache.shape[2]
            k_pos = pos - (pos - jnp.arange(ring)) % ring
            return gqa_attend_reference(q, k_cache, v_cache, pos, H, Hkv,
                                        Dh, k_pos=k_pos, window=window)

    def sequence(self, params, x, state):
        """The whole prompt ``x`` [B, T0, E] in one causal pass that
        fills ``state`` (``state_init``'s) at ``[0, T0)``: the prompt
        attends its own full-precision k/v by the flash kernels
        (O(T0·block) memory on a TPU, the same dense causal attention
        elsewhere), never the ``[T]`` cache — slots past the prompt are
        outside the causal horizon anyway — so the first generated
        token is bit-exact under int8 too."""
        return self.step(params, x, state, 0)

    def step(self, params, x, state, pos):
        """Tq tokens at ``pos`` against ``state``: written, then
        attended; -> (the output projection's result, the state)."""
        int8 = "k_scale" in state
        q, k, v = self.heads(params, x, pos)
        window = self._ring(state["k"])
        state = _cache_write(state, k, v, pos, bool(window), int8)
        if from_start(pos):
            from ..ops.flash_attention import flash_attention

            o = flash_attention(q, self._rep(k), self._rep(v), causal=True,
                                window=getattr(self, "window", None))
        else:
            o = self._decode_attend(q, *_cache_kv(state, q.dtype, int8),
                                    pos, window, int8)
        return self.merged(params, o), state

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
        q, k, v = self.heads(params, x)
        k, v = self._rep(k), self._rep(v)
        # device scope (``telemetry.tracer.DEVICE_SCOPES``): the
        # attention itself, whichever arm; projections and rotation
        # stay outside it
        with jax.named_scope("attention.core"):
            o = self._attend(q, k, v)
        return self.merged(params, o), buffers


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
    training and prefill form, differentiable by autodiff.

    What a DECODER keeps (``state_init`` / ``sequence`` / ``step``) is
    ``ckv`` ``[B, T, kv_rank]`` (the normed latent) and ``kr`` ``[B,
    rope, T]`` (the rotated key all heads share; positions minor, so
    that no position's ``rope`` numbers are padded to a lane tile and
    the attend's kernel reads what the leaf holds) — no leaf has a head
    axis, and a position holds ``kv_rank + rope`` numbers.  The prompt
    EXPANDS its latent to per-head K and V once and runs causal (flash)
    attention at the full head size.  A decode step never expands the
    cache: the key half of ``wkv_b`` is absorbed into the query (``q_lat
    = q_nope W_uk``), scores and the weighted sum are taken on the
    latent itself, and the value half is applied to the ONE resulting
    latent a head (``o = o_lat W_uv``) — algebraically the same,
    ``kv_rank + rope`` numbers a cached position read instead of ``heads
    * (qk + v)`` made; the only array with both a head and a
    cached-position axis is the scores.  That attend has two arms,
    chosen by shapes alone (``ops.latent_attend.attend_plan``): where
    the cache is large, on a TPU, ONE Pallas kernel walks it in blocks
    up to the block the step's position falls in; everywhere else the
    plain einsums read the whole static cache twice."""

    state_doc = ("keeps no K or V by head — its cache is the latent and "
                 "one rotated key a position")

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

    # -- what a decoder keeps of the layer, and a token against it ------
    keeps_positions = True

    def state_init(self, batch: int, dtype, length: int, int8: bool = False):
        if int8:
            raise TypeError(
                'kv_dtype="int8" holds K and V by head as int8 with a '
                f"scale a head and {type(self).__name__} {self.state_doc}: "
                "decode this model through generate() / submit_generate() "
                "with the default cache")
        return {"ckv": jnp.zeros((batch, length, self.kv_rank), dtype),
                "kr": jnp.zeros((batch, self.rope_dim, length), dtype)}

    def footprint(self, batch: int, dtype, length: int, int8: bool = False):
        """``latent_cache_bytes`` as allocated, and the arm of the
        absorbed attend a step compiles (``latent_attend``: ``("kernel"
        | "einsum", positions a block)``) — the rule the step reads."""
        from ..ops.latent_attend import attend_plan

        block = attend_plan(batch, length, self.kv_rank, self.rope_dim,
                            dtype)
        return {"latent_cache_bytes": state_bytes(jax.eval_shape(
                    lambda: self.state_init(batch, dtype, length, int8))),
                "latent_attend": ("kernel" if block else "einsum", block)}

    def sequence(self, params, x, state):
        return self.step(params, x, state, 0)

    def step(self, params, x, state, pos):
        """Tq tokens at ``pos`` against ``state``: the whole prompt
        (``pos`` the Python 0) expands and attends itself, a decode step
        absorbs and attends the latent."""
        qpos = pos + jnp.arange(x.shape[1])
        with jax.named_scope("mla.q_proj"):
            q_nope, q_rope = self.queries(params, x, qpos)
        with jax.named_scope("mla.kv_latent"):
            ckv, kr = self.latent(params, x, qpos)
            state = {**state,
                     "ckv": lax.dynamic_update_slice(
                         state["ckv"], ckv.astype(state["ckv"].dtype),
                         (0, pos, 0)),
                     "kr": lax.dynamic_update_slice(
                         state["kr"],
                         kr.astype(state["kr"].dtype).transpose(0, 2, 1),
                         (0, 0, pos))}
        if from_start(pos):
            with jax.named_scope("mla.expand"):
                k, v = self.expand(params, ckv, kr)
            # the flash kernels take one head size; a narrower value
            # head goes the plain way, whole scores
            o = self.attend_full(q_nope, q_rope, k, v,
                                 flash=v.shape[-1] == self.qk_dim)
        else:
            w_uk, w_uv = self.up_weights(params)
            dt = q_nope.dtype
            with jax.named_scope("mla.absorb"):
                q_lat = jnp.einsum("bhqn,hnc->bhqc", q_nope,
                                   w_uk.astype(dt))
            with jax.named_scope("mla.attend"):
                from ..ops.latent_attend import latent_attend

                o_lat = latent_attend(q_lat, q_rope, state["ckv"],
                                      state["kr"], pos, self.qk_dim,
                                      scale_mult=self.softmax_mult)
            with jax.named_scope("mla.absorb"):
                o = jnp.einsum("bhqc,hvc->bhqv", o_lat, w_uv.astype(dt))
        with jax.named_scope("mla.out_proj"):
            return self.out_proj(params, o), state

    def _apply(self, params, buffers, x, training, rng):
        pos = jnp.arange(x.shape[1])
        q_nope, q_rope = self.queries(params, x, pos)
        k, v = self.expand(params, *self.latent(params, x, pos))
        return self.out_proj(params, self.attend_full(q_nope, q_rope, k,
                                                      v)), buffers
