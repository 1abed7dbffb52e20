"""Autoregressive generation for TransformerLM, HybridMambaLM,
ParallelMoELM and SequentialMoELM — KV-cache decode, with a recurrent
state beside the K/V where a block has one, a cache of each layer's own
length where layers differ in what they see, a LATENT cache (no K or V
by head) where a block's attention is latent, and NO cache but a
two-position tail where a block's operator is a short convolution.

The reference predates autoregressive LMs entirely (its sequence story
is Recurrent/TimeDistributed, SURVEY §5.7), so this is a TPU-native
extension: one jitted program containing a **batched prefill** (the
whole prompt in one causal pass that fills the per-layer KV caches —
MXU-sized matmuls, not a token loop) followed by a ``lax.scan`` over
decode steps at static shapes, with the caches (``[B, Hkv, T_cache,
Dh]`` — the KV head count, smaller than the query's under GQA)
updated in place via ``lax.dynamic_update_slice``.  No Python-level
loop over tokens, no recompilation per length.  ``T_cache`` is what
THIS program can use — prompt + ``max_new``, both static, rounded up
to a multiple of 128 and never past ``max_len`` (:func:`_cache_len`):
a decode step of the plain form reads its whole cache, so a cache as
long as the model's positional table would make every step pay for
positions no call of this program can ever write.

TWO decode attends are chosen by shapes alone, each by the
``attend_plan`` of its op, with no argument, flag or model name: the
attend on per-head K/V (``ops/gqa_attend.py``) and the absorbed attend
on a latent cache (``ops/latent_attend.py``, below).  Where a layer's
cache is large, on a TPU, ONE Pallas kernel walks it in blocks of 128
positions up to the block the step's position falls in — each block
read once for scores, softmax and the weighted sum, nothing beyond it
fetched.  Everywhere else — small buckets, every other backend,
``Tq > 1``, and for per-head K/V a ring or int8 storage — the plain
einsums (:func:`_gqa_attend`, the one plain form of the per-head attend
and the kernel's reference) read the whole static cache twice.

A hybrid block (``nn.HybridMambaBlock``) keeps, beside its K/V, the
Mamba-2 mixer's SSM state ``[B, heads, head, N]`` (float32) and conv
tail ``[B, d_conv - 1, channels]`` in the same per-layer cache dict:
prefill runs the chunked scan and hands the state after the last
prompt token to the decode scan, which advances it one token a step.
The paged path keeps K/V pages only and refuses such a block.

A block may see a sliding WINDOW (``MultiHeadAttention.window``): its
K/V cache is then ``min(T_cache, window)`` positions long, written at
``pos mod`` that length once it is full, and read with each slot's
absolute position and the lower bound ``k_pos > q_pos - window``; a
block without one keeps ``T_cache`` positions.  Rotation (rotate-half,
interleaved, or none) and window are read per block.  A parallel block
(``models/parallel_moe.py``) runs attention and its expert layer on ONE
normed input; its cache also carries ``moe_counts`` ``[B, held]``, the
assignments each held expert took from each row, which a generate call
returns beside the tokens on request (``return_stats=True``).

A block whose attention is LATENT (``nn.LatentAttention``;
``models/latent_moe.py``) keeps ``ckv`` ``[B, T_cache, kv_rank]`` and
``kr`` ``[B, rope, T_cache]`` — the normed latent and the one rotated
key all heads share, the key with positions minor (``rope`` is half a
lane tile) — and has TWO attention paths: prefill expands the prompt's
latent to per-head K and V once and runs causal (flash) attention; a
decode step absorbs ``wkv_b`` into the query and the output and attends
on the latent itself, so that nothing with both a head and a
cached-position axis exists but the scores.  That attend has two arms,
chosen by shapes alone (``ops/latent_attend.py``, ``attend_plan``):
where a layer's cache is large, on a TPU, ONE Pallas kernel walks the
cache in blocks up to the block the step's position falls in — each
block read once for scores, softmax and ``P c_kv``, nothing beyond it
fetched; everywhere else the plain einsums read the whole static cache
twice.

A block whose operator is a gated SHORT CONVOLUTION
(``nn.GatedShortConv``; ``models/latent_moe.py``'s ``ShortConvMoELM``)
has no attention at all: its whole state is ``conv`` ``[B, kernel - 1,
D]``, the last values of its gated input, whatever the context — no
``k``, no ``v``, no position.  Prefill keeps the tail of the prompt, a
decode step reads it, writes one output from ``kernel`` values and
shifts.  The head geometry of such a model is its first attention
layer's.  What a block is made of is decided in one place
(:func:`_block_kind`).

A block whose RESIDUAL is a hyper-connection (``nn.HyperConnection``;
``models/latent_moe.py``'s ``HyperLatentMoELM``) carries ``n`` streams a
token: ``prefill`` and ``decode_token`` hand ``[B, Tq, n, D]`` from
layer to layer, each sublayer reading the mixture and writing the
result its maps give (``models.latent_moe.sublayer_input`` /
``sublayer_result``: the sequential arm asks them, for the plain
residual too), and ``logits_last`` sums the streams.  The caches are
what the operator's are; beside ``moe_counts`` such a layer's cache
carries ``mhc_err``, the call's largest distance of a residual map from
doubly stochastic.  Beam search and the paged decoder refuse the block
by name.

Built from the model's OWN parameter tree and modules (the
parallel/pipeline.py pattern): LN/MLP sublayers run through their
module ``apply_fn``; attention re-derives the q/k/v/o projections from
the MultiHeadAttention parameter names (wq/wk/wv/wo + biases) because
cached decode attention is a different computation from the module's
full-sequence forward.  ONE machinery (``_decode_machinery``) backs
both the sampling decoder and beam search, and greedy decode is pinned
against the full dense forward by a teacher-forcing oracle in
tests/test_generate.py, which keeps the implementations from drifting.

MoE models decode through a capacity-FREE gather dispatch (each token
simply uses its argmax expert): at inference nothing should be
dropped — training-time capacity drops are a static-shape batching
artifact, not part of the learned function.  The teacher-forcing
equivalence with the training forward therefore holds whenever the
training forward's capacity does not bind.

Sampling: ``temperature=0`` → greedy argmax; ``temperature>0`` →
categorical over ``logits/temperature`` (optionally within ``top_k``
and/or the ``top_p`` nucleus) and REQUIRES an explicit ``rng`` key — a
silent fixed-seed default would return the identical "sample" every
call.  The sampler is chosen on the HOST, at the call, from those
numbers: the compiled program holds only what was asked for (greedy:
one ``argmax``, no key consumed; the sort / cumsum / scatter of the
nucleus only when ``0 < top_p < 1``), while the VALUES of
``temperature`` and ``top_p`` stay traced, so a new temperature
compiles nothing.  ``eos_id`` stops a row (sampling) or finishes a
beam (beam search) early at static shapes, emitting ``pad_id`` from
then on — hf.generate's convention.  Beam decode:
:func:`make_beam_search`.
"""
from __future__ import annotations

import threading
import weakref
from concurrent.futures import CancelledError
from contextlib import nullcontext
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..nn.mamba import scaled as _scaled
from .latent_moe import sublayer_input, sublayer_result

# compiled generators per model instance (weak: dies with the model),
# keyed by build config.  NOT stored on the module itself — a jitted
# closure attribute would break the pickle-based checkpoint verbs.
_GEN_CACHE = weakref.WeakKeyDictionary()
# tracing and lowering a generate program run the model's Python: one
# at a time, whichever thread asks (``make_generate.compile_ahead``)
_LOWER_LOCK = threading.Lock()


def _check_model(model):
    from .hybrid_mamba import HybridMambaLM
    from .latent_moe import SequentialMoELM
    from .parallel_moe import ParallelMoELM
    from .transformer import TransformerLM

    if not isinstance(model, (TransformerLM, HybridMambaLM, ParallelMoELM,
                              SequentialMoELM)):
        raise TypeError(
            f"generation supports TransformerLM, HybridMambaLM, "
            f"ParallelMoELM and SequentialMoELM (LatentMoELM, "
            f"ShortConvMoELM, HyperLatentMoELM; got {type(model).__name__})")
    # seq_strategy (dense/flash/ring/ulysses) changes only HOW training
    # attention is computed — the parameter tree is strategy-independent,
    # so a ring/Ulysses-trained model decodes through the same cached
    # single-shard attention as a dense one (pinned against a dense twin
    # built from the same params in tests/test_generate.py)
    return 1, len(model.modules) - 3


def _block_kind(block) -> tuple:
    """What a block is made of — decided HERE and nowhere else:
    ``(form, operator, experts)``.

    * ``form``: ``"hybrid"`` (``nn.HybridMambaBlock``: a recurrent state
      beside its attention), ``"parallel"``
      (``models.parallel_moe.ParallelMoEBlock``: attention and the
      expert layer read one normed input) or ``"sequential"`` (the
      operator, then the FFN on a second norm);
    * ``operator``: ``"latent"`` (``nn.LatentAttention``: the cache
      holds the latent and the shared rotated key), ``"conv"``
      (``nn.GatedShortConv``: no attention; the cache holds the
      convolution's tail and nothing else) or ``"kv"`` (per-head K and
      V);
    * ``experts``: the block's ``DroplessMoE`` (its cache carries
      ``moe_counts``), or None."""
    form = {"hybrid_mamba": "hybrid", "parallel_moe": "parallel"}.get(
        getattr(block, "kind", None), "sequential")
    operator = {"latent": "latent", "short_conv": "conv"}.get(
        getattr(block.modules[1], "kind", None), "kv")
    experts = (block.moe if form == "parallel"
               or getattr(block, "ffn_kind", None) == "moe" else None)
    return form, operator, experts


def _is_hybrid(block) -> bool:
    return _block_kind(block)[0] == "hybrid"


def _is_parallel(block) -> bool:
    return _block_kind(block)[0] == "parallel"


def _is_latent(block) -> bool:
    return _block_kind(block)[1] == "latent"


def _is_conv(block) -> bool:
    return _block_kind(block)[1] == "conv"


def _head_geometry(blocks) -> tuple:
    """``(heads, K/V heads, head size)`` of the model's per-head K/V,
    from the first block that keeps one: a block without attention has
    no heads to ask for, and a latent block keeps nothing by head (its
    head count is returned for a model of latent blocks alone, with no
    head size — such a model uses none)."""
    by_kind = {}
    for b in blocks:
        by_kind.setdefault(_block_kind(b)[1], b.modules[1])
    mha = by_kind.get("kv", by_kind.get("latent"))
    H = getattr(mha, "num_heads", None)
    return H, getattr(mha, "num_kv_heads", H), getattr(mha, "head_dim", None)


def _window_of(block):
    """The block's sliding window in positions, or None."""
    return getattr(block.modules[1], "window", None)


def _refuse_recurrent(model, first, count, what: str):
    """The paged path keeps K/V pages only: a block with a recurrent
    state has nowhere to put it there, so it is refused, not decoded
    without its state.  Its pages are all of one length and its block
    step is the sequential one, so a block with a window or a parallel
    expert layer is refused too, not decoded as another model."""
    blocks = model.modules[first:first + count]
    _refuse_streams(blocks, what,
                    "carries one residual vector [B, 1, D] a token")
    _refuse_latent(blocks, what, "K/V pages [Hkv, page, Dh]")
    conv = [b.modules[1] for b in blocks if _is_conv(b)]
    if conv:
        raise TypeError(
            f"{what} pages K/V only and {type(conv[0]).__name__} keeps no "
            f"K or V at all — its whole state is a convolution tail of "
            f"{conv[0].kernel - 1} positions a row: decode this model "
            f"through generate() / submit_generate(), whose static cache "
            f"holds a tail where a layer has one")
    if any(_is_parallel(b) or _window_of(b) for b in blocks):
        raise TypeError(
            f"{what} keeps pages of ONE length for every layer and runs "
            f"the sequential block: {type(model).__name__}'s layers "
            f"differ in what they see (a window beside full attention) "
            f"and run attention and experts on one norm.  Decode this "
            f"model through generate() / submit_generate(), whose "
            f"static cache is sized per layer")
    if any(_is_hybrid(b) for b in model.modules[first:first + count]):
        raise TypeError(
            f"{what} pages K/V only and {type(model).__name__}'s blocks "
            f"carry a recurrent state (SSM state and conv tail) beside "
            f"it: decode this model through generate() / "
            f"submit_generate(), whose static cache holds both")


def _refuse_streams(blocks, what: str, why: str):
    """A hyper-connected block's state between layers is ``n`` streams a
    token and its cache carries a counter with no batch axis: a decoder
    built around one residual vector and batch-major cache leaves
    refuses it, by name."""
    hyper = [b for b in blocks if getattr(b, "streams", 0)]
    if hyper:
        raise TypeError(
            f"{what} {why} and {type(hyper[0].hyper[0]).__name__} makes "
            f"the residual of {type(hyper[0]).__name__} "
            f"{hyper[0].streams} streams a token: decode this model "
            f"through generate() / submit_generate()")


def _refuse_latent(blocks, what: str, holds: str):
    """A latent block's cache is the latent and ONE rotated key a
    position, no K or V by head: a store made for those has no place
    for it."""
    latent = [b.modules[1] for b in blocks if _is_latent(b)]
    if latent:
        raise TypeError(
            f"{what} holds {holds} and {type(latent[0]).__name__} keeps "
            f"no K or V by head — its cache is the latent and one rotated "
            f"key a position: decode this model through generate() / "
            f"submit_generate() with the default cache")


def _check_len(model, max_len):
    """Validate the decode window against the positional table: the
    cached path embeds positions by ``lax.dynamic_slice_in_dim`` on
    ``pc['pos']``, whose clamped start would silently REUSE the last
    positions past ``model.max_len`` — wrong embeddings, so refuse
    loudly instead."""
    T_max = int(max_len or model.max_len)
    if T_max > model.max_len:
        raise ValueError(
            f"max_len {T_max} exceeds the model's positional table "
            f"({model.max_len}); the decode window cannot outgrow "
            f"the positions the model was built with")
    return T_max


def _eos_pad(model, eos_id, pad_id):
    """Normalize the shared eos/pad convention for BOTH decoders:
    ``eos_id=None`` disables early stop (sentinel 0 — ids are 1-based);
    ``pad_id`` defaults to the eos itself.  Out-of-vocabulary ids are
    rejected loudly — the beam decoder builds a one-hot pad row over
    [1, V], where a bad pad would silently annihilate finished beams
    instead of freezing them."""
    for name, v in (("eos_id", eos_id), ("pad_id", pad_id)):
        if v is not None and not 1 <= int(v) <= model.vocab_size:
            raise ValueError(
                f"{name}={v} outside the 1-based vocabulary "
                f"[1, {model.vocab_size}]")
    eos = int(eos_id or 0)
    pad = int(pad_id) if pad_id is not None else eos
    return jnp.int32(eos), jnp.int32(pad)


def _cast_params(p, compute_dtype):
    """The parameter tree a generator computes on: every floating leaf
    in ``compute_dtype`` (None: as held) but the leaves that stay
    float32 whatever the model computes in (``FLOAT32_LEAVES``: a
    router's selection bias)."""
    from ..nn.module import FLOAT32_LEAVES, hold_floats

    return hold_floats(p, compute_dtype, keep=FLOAT32_LEAVES)


def _proj(x, params, w, b, with_bias):
    y = jnp.dot(x, params[w].T)
    return y + params[b] if with_bias else y


# capacity-bind capture: while a list is installed on this thread,
# every _moe_ffn_nodrop call appends the fraction of its tokens that
# the TRAINING dispatch's static capacity would have dropped (trace-
# time side channel for capacity_bind_report; absent during normal
# decode).  Thread-LOCAL so a concurrent trace of another model's
# generator cannot interleave its fractions into this report.

_BIND_TLS = threading.local()


def _moe_ffn_nodrop(moe, params, x):
    """Capacity-free top-k advance of a ``MoEFFN`` for decode, through
    the dropless dispatch of ``parallel/moe.py``: assignments sorted by
    expert, one grouped product per projection (each expert's weights
    read at most once a call, none gathered per token), mixed by the
    (top-1 raw / top-k renormalized) gates.  [B, Tq, D] -> [B, Tq, D]."""
    from ..parallel.moe import (dropless_apply, grouped_matmul,
                                route_top_k, row_experts)

    B, Tq, D = x.shape
    K = getattr(moe, "top_k", 1)
    x2 = x.reshape(B * Tq, D)
    gk, idxk = route_top_k(x2, params["router_w"], params["router_b"], K,
                           "softmax", renormalize=K > 1)
    if getattr(_BIND_TLS, "capture", None) is not None:
        # the training dispatch's keep rule, via the module's own
        # shared helper so the two can never drift (capacity from THIS
        # batch's token count; choice-ordered stream like _route) —
        # the fraction is over all N·K routing assignments
        kept, counts = 0.0, None
        for c in range(K):
            oh = jax.nn.one_hot(idxk[:, c], moe.n_experts,
                                dtype=jnp.float32)
            _, keep, counts = moe.keep_mask(oh, counts)
            kept = kept + jnp.sum(keep.astype(jnp.float32))
        _BIND_TLS.capture.append(1.0 - kept / (B * Tq * K))

    def gelu_experts(xs, sizes):
        e = row_experts(sizes, xs.shape[0])
        h = jax.nn.gelu(grouped_matmul(xs, params["wi"], sizes)
                        + params["bi"][e].astype(xs.dtype))
        return (grouped_matmul(h, params["wo"], sizes)
                + params["bo"][e].astype(xs.dtype))

    y, _ = dropless_apply(x2, idxk, gk, (0, moe.n_experts), gelu_experts)
    return y.reshape(B, Tq, D)


def _gqa_attend(q, k_cache, v_cache, pos, H, Hkv, Dh, k_pos=None,
                window=None):
    """Causal attention of Tq queries (absolute positions
    pos..pos+Tq-1) against a dense ``[B, Hkv, Tm, Dh]`` cache view.
    GQA contracts the query groups against the UN-repeated cache — a
    repeat here would materialize H/Hkv copies of the whole cache
    every decode step, exactly the bandwidth GQA exists to save.
    Shared by the dense-cache machinery and the paged decode path (the
    paged path passes a page-gathered view), so the two can never
    drift numerically.  ``k_pos`` [Tm] gives each cache slot's
    ABSOLUTE position when the view is not contiguous from 0 — the
    page-window path gathers only the live pages, so slot index and
    position diverge.  ``window`` adds the sliding window's far edge,
    ``k_pos > q_pos - window``, and masks a slot that holds no position
    yet (``k_pos < 0``: a ring that is not full)."""
    Tq, Tm = q.shape[2], k_cache.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.float32(Dh)).astype(q.dtype)
    qpos = pos + jnp.arange(Tq)
    if k_pos is None:
        k_pos = jnp.arange(Tm)
    mask = k_pos[None, :] <= qpos[:, None]            # [Tq, Tm]
    if window is not None:
        mask = (mask & (k_pos[None, :] > qpos[:, None] - window)
                & (k_pos[None, :] >= 0))
    if Hkv == H:
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k_cache) * scale
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype),
                          v_cache)
    B = q.shape[0]
    qg = q.reshape(B, Hkv, H // Hkv, Tq, Dh)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_cache) * scale
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", probs.astype(q.dtype),
                   v_cache)
    return o.reshape(B, H, Tq, Dh)


def _ffn(block, bp, ln2):
    """The block's MLP (gelu / swiglu / one gated module / capacity-free
    MoE) on its normed input."""
    kind = getattr(block, "mlp_kind",
                   "moe" if block.is_moe else "gelu")
    if kind == "moe":
        ffn = _moe_ffn_nodrop(block.modules[3], bp["3"], ln2)
    elif kind == "gated":       # the whole SwiGLU is child 3 (GatedFFN)
        ffn, _ = block.modules[3].apply_fn(bp["3"], {}, ln2, False, None)
    elif kind == "swiglu":
        # a hybrid block's two muP constants; (1, 1) multiplies nothing
        gm, dm = getattr(block, "mlp_multipliers", (1.0, 1.0))
        g, _ = block.modules[3].apply_fn(bp["3"], {}, ln2, False,
                                         None)
        u, _ = block.modules[4].apply_fn(bp["4"], {}, ln2, False,
                                         None)
        ffn, _ = block.modules[5].apply_fn(
            bp["5"], {}, jax.nn.silu(_scaled(g, gm)) * u, False, None)
        ffn = _scaled(ffn, dm)
    else:
        mid, _ = block.modules[3].apply_fn(bp["3"], {}, ln2, False,
                                           None)
        out, _ = block.modules[4].apply_fn(bp["4"], {},
                                           jax.nn.gelu(mid), False,
                                           None)
        ffn = out
    return ffn


def _ffn_sublayer(block, bp, h):
    """ln2 + :func:`_ffn` with the residual add — the post-attention
    half of a block with the plain residual, shared by the dense-cache
    and paged machineries."""
    ln2, _ = block.modules[2].apply_fn(bp["2"], {}, h, False, None)
    return h + _ffn(block, bp, ln2)


def _cache_len(T_max, T0, max_new):
    """Positions the static K/V cache of one generate program holds:
    ``T0 + max_new`` rounded up to a multiple of 128 (the cache's
    length is the lane axis of the decode step's score tile), never
    past ``T_max``.  Both lengths are static in the program, so no
    position beyond them is ever written or attended; ``T_max`` still
    bounds what a caller may ask for."""
    if T0 + max_new > T_max:
        raise ValueError(
            f"prompt {T0} + max_new {max_new} exceeds max_len {T_max}")
    return min(T_max, -(-(T0 + max_new) // 128) * 128)


def _cache_init(block, B, T_cache, dt, kv_int8=False):
    """One layer's state for ``B`` rows, a dict: K and V ``[B, Hkv,
    T_cache, Dh]`` (int8 with ``k_scale`` / ``v_scale`` beside them
    under ``kv_int8``) and, for a hybrid block, the mixer's ``ssm``
    state and ``conv`` tail beside those.  ``T_cache`` is the calling
    program's :func:`_cache_len`, not the model's ``max_len``: every
    reader of the cache takes its length from its shape.  A block with
    a sliding window keeps ``min(T_cache, window)`` positions (a ring);
    a block with an expert layer adds ``moe_counts`` ``[B, held]``
    int32.  A LATENT block keeps no K or V: ``ckv`` ``[B, T_cache,
    kv_rank]`` (the normed latent) and ``kr`` ``[B, rope, T_cache]``
    (the rotated key all heads share; positions minor, so that no
    position's ``rope`` numbers are padded to a lane tile and the
    attend's kernel reads what the leaf holds) — no leaf has a head
    axis, and a position holds ``kv_rank + rope`` numbers.  A block
    whose operator is a SHORT CONVOLUTION keeps ``conv`` ``[B, kernel -
    1, D]`` and nothing else: no ``k``, no ``v``, under ``kv_int8``
    too (the tail is not K/V and stays in ``dt``)."""
    form, operator, experts = _block_kind(block)
    mha = block.modules[1]
    if operator == "conv":
        cache = mha.state_init(B, dt)
    elif operator == "latent":
        cache = {"ckv": jnp.zeros((B, T_cache, mha.kv_rank), dt),
                 "kr": jnp.zeros((B, mha.rope_dim, T_cache), dt)}
    else:
        Hkv = getattr(mha, "num_kv_heads", mha.num_heads)
        kv = (B, Hkv, min(T_cache, _window_of(block) or T_cache),
              mha.head_dim)
        if kv_int8:
            cache = {"k": jnp.zeros(kv, jnp.int8),
                     "k_scale": jnp.zeros(kv[:3] + (1,), jnp.float32),
                     "v": jnp.zeros(kv, jnp.int8),
                     "v_scale": jnp.zeros(kv[:3] + (1,), jnp.float32)}
        else:
            cache = {"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt)}
    if form == "hybrid":
        cache.update(block.mixer.state_init(B, dt))
    if experts is not None:
        cache["moe_counts"] = jnp.zeros((B, experts.held[1]), jnp.int32)
    if getattr(block, "streams", 0):   # a counter too: one number a layer
        cache["mhc_err"] = jnp.zeros((), jnp.float32)
    return cache


def cache_footprint(model, batch: int, prompt_len: int, max_new: int,
                    compute_dtype=None, max_len: Optional[int] = None,
                    kv_dtype: Optional[str] = None) -> dict:
    """State one generate call of ``batch`` rows, ``prompt_len`` prompt
    tokens and ``max_new`` answer tokens holds on the device, from
    shapes alone: ``kv_cache_positions`` (how long that program's
    static cache is, :func:`_cache_len`, against the model's
    ``max_len``), ``kv_cache_bytes`` (the K/V of every layer THAT KEEPS
    ONE at that length: what is allocated, and what every decode step
    reads) and ``recurrent_state_bytes`` (SSM state and conv tail — a
    short-convolution layer's whole state; zero for a model without
    them).  Where some layer has a sliding window, K/V
    is also given by KIND of layer: ``kv_cache_bytes_window`` (layers
    that keep ``min(positions, window)``) and ``kv_cache_bytes_full``.
    A model with latent attention also gives ``latent_cache_bytes``
    (the latent and the shared rotated key of every position, as
    allocated; its ``kv_cache_bytes`` is 0) and which arm of the
    absorbed attend this program's decode step compiled:
    ``latent_attend`` (``"kernel"`` or ``"einsum"``) and
    ``latent_attend_block`` (positions a block of the kernel's walk; 0
    for the einsums) — ``ops.latent_attend.attend_plan``, the rule the
    step itself reads.  A model with per-head K/V gives the same of its
    decode attend: ``kv_attend`` and ``kv_attend_block``, from
    ``ops.gqa_attend.attend_plan`` (the layers that are no ring; a ring
    keeps the einsums).  A model with experts gives the arm and the
    tile plan of the grouped products in its decode step (a buffer of
    ``batch * min(top_k, held)`` rows): ``grouped`` (``"ragged"``,
    ``"grouped_decode"`` or ``"gmm"``), ``grouped_tiles`` (``"<rows a
    product>x<tk>x<tn>"`` of the gate and up products; empty for
    ``ragged``) and ``grouped_tiles_down`` — ``parallel.moe.
    grouped_plan``, the rule ``grouped_matmul`` itself reads."""
    first, count = _check_model(model)
    T_cache = _cache_len(_check_len(model, max_len), int(prompt_len),
                         int(max_new))
    dt = jnp.dtype(compute_dtype or jax.tree_util.tree_leaves(
        model.param_tree())[0].dtype)
    out = {"kv_cache_bytes": 0, "recurrent_state_bytes": 0,
           "kv_cache_positions": T_cache}
    blocks = model.modules[first:first + count]
    by_kind = {"kv_cache_bytes_window": 0, "kv_cache_bytes_full": 0}
    if any(_is_latent(b) for b in blocks):
        out["latent_cache_bytes"] = 0
    plain = None    # what a K/V layer that is no ring stores
    for block in blocks:
        shapes = jax.eval_shape(partial(_cache_init, block, int(batch),
                                        T_cache, dt, _kv_int8(kv_dtype)))
        for name, a in shapes.items():
            nbytes = a.size * a.dtype.itemsize
            if name == "k" and a.shape[2] != _window_of(block):
                plain = a.dtype
            if name in ("k", "v", "k_scale", "v_scale"):
                out["kv_cache_bytes"] += nbytes
                by_kind["kv_cache_bytes_window" if _window_of(block)
                        else "kv_cache_bytes_full"] += nbytes
            elif name in ("ckv", "kr"):
                out["latent_cache_bytes"] += nbytes
            elif name not in ("moe_counts", "mhc_err"):   # counters
                out["recurrent_state_bytes"] += nbytes
    if any(_window_of(b) for b in blocks):
        out.update(by_kind)
    if "latent_cache_bytes" in out:
        from ..ops.latent_attend import attend_plan

        mla = next(b.modules[1] for b in blocks if _is_latent(b))
        block = attend_plan(int(batch), T_cache, mla.kv_rank,
                            mla.rope_dim, dt)
        out.update(latent_attend="kernel" if block else "einsum",
                   latent_attend_block=block)
    if out["kv_cache_bytes"]:
        from ..ops.gqa_attend import attend_plan

        # every layer that is not a ring keeps T_cache positions and
        # attends alike; a ring keeps the einsums whatever its size
        _, Hkv, Dh = _head_geometry(blocks)
        plan = 0 if plain is None else attend_plan(
            int(batch), Hkv, T_cache, Dh, plain)
        out.update(kv_attend="kernel" if plan else "einsum",
                   kv_attend_block=plan)
    experts = next((e for e in (_block_kind(b)[2] for b in blocks)
                    if e is not None), None)
    if experts is not None:
        from ..parallel.moe import grouped_plan

        # a decode step's buffer: one token a row, its choices among
        # the held experts
        rows = int(batch) * min(experts.top_k, experts.held[1])
        D, F = experts.embed_dim, experts.hidden_dim
        impl, up = grouped_plan(rows, D, F, dt)
        down = grouped_plan(rows, F, D, dt)[1]
        # as text: these ride on ``serve.dispatch`` into a profiler
        # session, whose event metadata is split at commas
        out.update(grouped=impl,
                   grouped_tiles="x".join(map(str, up or ())),
                   grouped_tiles_down="x".join(map(str, down or ())))
    return out


def _decode_machinery(model, first, count, kv_int8=False):
    """The cached-attention forward shared by the sampling decoder and
    beam search — built once per generator from the model structure.
    Every function takes the (already cast) param tree ``pc``
    explicitly; ``prefill`` is told how long a cache to allocate, and
    everything after it takes that length from the cache's shape.

    ``kv_int8`` stores the caches as int8 with a float32 scale per
    (batch, head, position) — absmax rounding over the head dim.
    Decode is cache-bandwidth-bound, so halving (vs bf16) the bytes
    read per step buys throughput; the prompt's own prefill attention
    stays full-precision (only post-prefill decode steps read the
    quantized cache).  Lossy by construction — an approximation knob,
    off by default.  It quantises K and V and nothing else: a layer
    without attention keeps its convolution tail as it is."""
    blocks = model.modules[first:first + count]
    ln_f = model.modules[first + count]
    head = model.modules[first + count + 1]
    embed = model.modules[0]
    if kv_int8:
        _refuse_latent(blocks, 'kv_dtype="int8"',
                       "K and V by head as int8 with a scale a head")
    # per-head K/V geometry, GQA's smaller K/V head count included (a
    # latent block has none and uses none; a block without attention
    # is passed over)
    H, Hkv, Dh = _head_geometry(blocks)
    use_rope = getattr(model, "use_rope", False)
    tied = getattr(model, "tied_head", False)
    # streams of the state between layers: all blocks alike (0: plain)
    n_streams = getattr(blocks[0], "streams", 0)

    def _rope_of(mha):
        """(kind, theta) of ONE block's rotation — "half",
        "interleaved", or None for a layer without positions; a model
        that rotates (``use_rope``) and whose layers do not say how
        rotates by halves."""
        kind = getattr(mha, "rope_kind", "half") if use_rope else None
        return kind, getattr(mha, "rope_theta", 10000.0)

    def _split(x, B, h=H):
        return x.reshape(B, -1, h, Dh).transpose(0, 2, 1, 3)

    def _rep(kv):
        """Broadcast the Hkv kv heads to the H query heads (GQA) — only
        used on the prompt-length prefill tensors; the decode hot loop
        keeps the cache un-repeated via the grouped einsum below."""
        if Hkv == H:
            return kv
        return jnp.repeat(kv, H // Hkv, axis=1)

    def _attend(q, k_cache, v_cache, pos, window=None):
        from ..ops.gqa_attend import _gqa_attend_kernel, attend_plan

        # one kernel pass over the written part of the cache where the
        # shapes say it wins, ``_gqa_attend`` over the whole of it
        # otherwise (a ring, int8 storage, ``Tq > 1``, a small cache,
        # every backend but a TPU)
        block = attend_plan(q.shape[0], Hkv, k_cache.shape[2], Dh,
                            jnp.int8 if kv_int8 else k_cache.dtype,
                            q.shape[2], window)
        with jax.named_scope("attention.decode_attend"):
            if block:
                return _gqa_attend_kernel(q[:, :, 0], k_cache, v_cache, pos,
                                          block, False)[:, :, None]
            if window is None:
                return _gqa_attend(q, k_cache, v_cache, pos, H, Hkv, Dh)
            # a ring: slot s holds the latest position <= pos that is s
            # mod the ring's length (negative: none yet)
            ring = k_cache.shape[2]
            k_pos = pos - (pos - jnp.arange(ring)) % ring
            return _gqa_attend(q, k_cache, v_cache, pos, H, Hkv, Dh,
                               k_pos=k_pos, window=window)

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

    def _cache_write(cache, k, v, pos, ringed=False):
        put = _ring_put if ringed else (
            lambda arr, x, pos: lax.dynamic_update_slice(arr, x,
                                                         (0, 0, pos, 0)))
        new = dict(cache)
        for name, x in (("k", k), ("v", v)):
            if kv_int8:
                x, scale = _quant(x)
                new[name + "_scale"] = put(cache[name + "_scale"], scale,
                                           pos)
            new[name] = put(cache[name], x, pos)
        return new

    def _cache_kv(cache, dt):
        """(k, v) dense views of the cache — for int8 the convert+
        scale is elementwise and fuses into the attention dot's
        operand read (the int8 bytes are what HBM streams)."""
        if kv_int8:
            return (cache["k"].astype(dt) * cache["k_scale"].astype(dt),
                    cache["v"].astype(dt) * cache["v_scale"].astype(dt))
        return cache["k"], cache["v"]

    def _latent_attention(mla, ap, ln1, cache, pos):
        """Latent attention of Tq tokens at ``pos`` against the cache of
        ``ckv`` / ``kr``.  Prefill EXPANDS the prompt's latent to
        per-head K and V once and runs causal (flash) attention at the
        full head size.  A decode step never expands the cache: the
        key half of ``wkv_b`` is absorbed into the query (``q_lat =
        q_nope W_uk``), scores and the weighted sum are taken on the
        latent itself, and the value half is applied to the ONE
        resulting latent a head (``o = o_lat W_uv``) — algebraically the
        same, ``kv_rank + rope`` numbers a cached position read instead
        of ``heads * (qk + v)`` made.  The only array with both a head
        and a cached-position axis is the scores."""
        Tq = ln1.shape[1]
        qpos = pos + jnp.arange(Tq)
        with jax.named_scope("mla.q_proj"):
            q_nope, q_rope = mla.queries(ap, ln1, qpos)
        with jax.named_scope("mla.kv_latent"):
            ckv, kr = mla.latent(ap, ln1, qpos)
            cache = {**cache,
                     "ckv": lax.dynamic_update_slice(
                         cache["ckv"], ckv.astype(cache["ckv"].dtype),
                         (0, pos, 0)),
                     "kr": lax.dynamic_update_slice(
                         cache["kr"],
                         kr.astype(cache["kr"].dtype).transpose(0, 2, 1),
                         (0, 0, pos))}
        if isinstance(pos, int) and pos == 0:
            with jax.named_scope("mla.expand"):
                k, v = mla.expand(ap, ckv, kr)
            # the flash kernels take one head size; a narrower value
            # head goes the plain way, whole scores
            o = mla.attend_full(q_nope, q_rope, k, v,
                                flash=v.shape[-1] == mla.qk_dim)
        else:
            w_uk, w_uv = mla.up_weights(ap)
            dt = q_nope.dtype
            with jax.named_scope("mla.absorb"):
                q_lat = jnp.einsum("bhqn,hnc->bhqc", q_nope,
                                   w_uk.astype(dt))
            with jax.named_scope("mla.attend"):
                # one pass over the written part of the cache where the
                # shapes say the kernel wins, the plain einsums over the
                # whole of it otherwise (``ops.latent_attend.attend_plan``)
                from ..ops.latent_attend import latent_attend

                o_lat = latent_attend(q_lat, q_rope, cache["ckv"],
                                      cache["kr"], pos, mla.qk_dim,
                                      scale_mult=mla.softmax_mult)
            with jax.named_scope("mla.absorb"):
                o = jnp.einsum("bhqc,hvc->bhqv", o_lat, w_uv.astype(dt))
        with jax.named_scope("mla.out_proj"):
            return mla.out_proj(ap, o), cache

    def _attention(block, ap, ln1, cache, pos):
        """Cached attention of one block on Tq tokens at ``pos``;
        returns (the output projection's result, cache)."""
        mha = block.modules[1]
        if _is_latent(block):
            return _latent_attention(mha, ap, ln1, cache, pos)
        B = ln1.shape[0]
        q = _split(_proj(ln1, ap, "wq", "bq", mha.with_bias), B)
        k = _split(_proj(ln1, ap, "wk", "bk", mha.with_bias), B, Hkv)
        v = _split(_proj(ln1, ap, "wv", "bv", mha.with_bias), B, Hkv)
        k = _scaled(k, getattr(mha, "key_multiplier", 1.0))
        # per-head QK-norm where the module has it, BEFORE the rotation:
        # the cache holds normed, rotated keys
        q, k = mha.normed_heads(ap, q, k)
        rope, rope_theta = _rope_of(mha)
        if rope:
            # rotate at ABSOLUTE positions; the cache stores rotated
            # keys (the standard KV-cache convention for RoPE)
            from ..nn.attention import rope_rotate

            qpos = pos + jnp.arange(q.shape[2])
            il = rope == "interleaved"
            q = rope_rotate(q, qpos, rope_theta, interleaved=il)
            k = rope_rotate(k, qpos, rope_theta, interleaved=il)
        window = _window_of(block)
        # a sliding layer's cache as long as its window is a ring; a
        # shorter one holds every position of this program, all of them
        # inside the window: a plain cache
        ringed = bool(window) and cache["k"].shape[2] == window
        cache = _cache_write(cache, k, v, pos, ringed)
        if isinstance(pos, int) and pos == 0:
            # the whole prefill (ANY prompt length — a 1-token prompt
            # rides flash_attention's dense fallback) attends the
            # full-precision k/v, so the first generated token is
            # bit-exact even under kv_int8
            # prefill: causal attention over the PROMPT only — cache
            # slots past the prompt are outside the causal horizon
            # anyway, so scoring the whole [T_cache] cache (the _attend
            # path) wastes T_cache/T0 of the work and materializes the
            # full score tile.  The flash kernels make this
            # O(T0·block) memory on TPU; off-TPU (and at non-blockable
            # T0) flash_attention falls back to the same dense causal
            # attention, so numerics stay pinned by the greedy
            # teacher-forcing oracle either way.
            from ..ops.flash_attention import flash_attention

            o = flash_attention(q, _rep(k), _rep(v), causal=True,
                                window=window)
        else:
            o = _attend(q, *_cache_kv(cache, q.dtype), pos,
                        window if ringed else None)
        o = o.transpose(0, 2, 1, 3).reshape(B, o.shape[2], H * Dh)
        return _proj(o, ap, "wo", "bo", mha.with_bias), cache

    def _operator(block, operator, ap, ln1, cache, pos):
        """The token-mixing operator of one sequential block on Tq
        tokens at ``pos``: cached attention, or the short convolution
        over its tail (prefill keeps the prompt's last values, a step
        shifts one in)."""
        if operator != "conv":
            return _attention(block, ap, ln1, cache, pos)
        conv = block.modules[1]
        if isinstance(pos, int) and pos == 0:
            a, state = conv.sequence(ap, ln1)
        else:
            a, state = conv.step(ap, ln1, cache)
        return a, {**cache, **state}

    def _block_step(block, bp, h, cache, pos):
        """One block on Tq tokens (prefill: Tq=T0 at pos 0; decode:
        Tq=1) against its cache; returns (h, cache).  A hybrid block's
        mixer reads the same normed input as its attention: prefill
        runs the chunked scan from an empty state and keeps the state
        after the last prompt token, a decode step advances it."""
        form, operator, experts = _block_kind(block)
        if form == "sequential":
            # ONE arm for every operator (per-head K/V, latent, short
            # convolution), every FFN and both residuals: the block says
            # what a sublayer reads of the state and how its result goes
            # back (``h + y``, or a hyper-connection's maps over the
            # streams ``[B, Tq, n, D]``); a block that names its
            # operator's device scope gets it
            ln1, co = sublayer_input(block, bp, 0, h)
            with getattr(block, "operator_scope", nullcontext)():
                a, cache = _operator(block, operator, bp["1"], ln1, cache,
                                     pos)
            h = sublayer_result(block, 0, h, a, co)
            ln2, co2 = sublayer_input(block, bp, 1, h)
            if experts is None:
                h = sublayer_result(block, 1, h, _ffn(block, bp, ln2), co2)
            else:
                B, Tq, D = ln2.shape
                m, counts = experts.routed(bp["3"], ln2.reshape(B * Tq, D),
                                           batch=B)
                h = sublayer_result(block, 1, h, m.reshape(B, Tq, D), co2)
                cache = {**cache,
                         "moe_counts": cache["moe_counts"] + counts}
            if co is not None:
                # the counter of the call: how far from doubly stochastic
                # the worst residual map of either sublayer was
                cache = {**cache, "mhc_err": jnp.maximum(
                    cache["mhc_err"], jnp.maximum(co.err, co2.err))}
            return h, cache
        ln1, _ = block.modules[0].apply_fn(bp["0"], {}, h, False, None)
        if form == "parallel":
            # attention and the expert layer read the SAME normed input
            with jax.named_scope("block.attention"):
                a, cache = _attention(block, bp["1"], ln1, cache, pos)
            B, Tq, D = ln1.shape
            m, counts = block.moe.routed(bp["2"], ln1.reshape(B * Tq, D),
                                         batch=B)
            return (h + a + m.reshape(B, Tq, D),
                    {**cache, "moe_counts": cache["moe_counts"] + counts})
        with jax.named_scope("mixer.attention"):
            a, cache = _attention(
                block, bp["1"],
                _scaled(ln1, block.attention_in_multiplier), cache, pos)
        mixer = block.mixer
        if isinstance(pos, int) and pos == 0:
            m, state = mixer.sequence(bp["6"], ln1)
        else:
            m, state = mixer.step(bp["6"], ln1, cache)
        return (_ffn_sublayer(block, bp, block.mix(h, a, m)),
                {**cache, **state})

    def _embed_at(pc, tok, pos, Tq):
        h, _ = embed.apply_fn(pc["0"], {}, tok, False, None)
        h = _scaled(h, getattr(model, "embedding_multiplier", 1.0))
        if use_rope:  # positions live in the per-layer q/k rotation
            return h
        return h + lax.dynamic_slice_in_dim(pc["pos"], pos, Tq)

    def _state_of(h):
        """The state the first layer takes: the embedding, or where the
        blocks carry streams every stream the embedding."""
        return blocks[0].hyper[0].replicate(h) if n_streams else h

    def prefill(pc, prompt, dt, T_cache):
        """The whole prompt in one causal pass; returns (h [B,T0,D] —
        [B,T0,n,D] where the blocks carry ``n`` streams — and caches) of
        ``T_cache`` positions with [0, T0) filled."""
        B, T0 = prompt.shape
        h = _state_of(_embed_at(pc, prompt, 0, T0))
        caches = []
        for bi, block in enumerate(blocks):
            cache = _cache_init(block, B, T_cache, dt, kv_int8)
            h, cache = _block_step(block, pc[str(first + bi)], h,
                                   cache, 0)
            caches.append(cache)
        return h, caches

    def decode_token(pc, tok, caches, pos):
        """One token [B, 1] at absolute position ``pos``; returns
        (h [B,1,D], new_caches)."""
        h = _state_of(_embed_at(pc, tok, pos, 1))
        new_caches = []
        for bi, block in enumerate(blocks):
            h, cache = _block_step(block, pc[str(first + bi)], h,
                                   caches[bi], pos)
            new_caches.append(cache)
        return h, new_caches

    def logits_last(pc, h):
        """Head on the LAST position of h only -> [B, V] f32; the
        streams of a hyper-connected state are summed first."""
        h = h[:, -1:]
        if n_streams:
            h = blocks[0].hyper[0].reduce(h)
        h, _ = ln_f.apply_fn(pc[str(first + count)], {}, h, False, None)
        # a tied head owns no leaf: it is handed the embedding's
        h, _ = head.apply_fn(pc["0" if tied else str(first + count + 1)],
                             {}, h, False, None)
        h = _scaled(h, getattr(model, "lm_head_multiplier", 1.0))
        h = _scaled(h, getattr(model, "logit_scale", 1.0))
        return h[:, 0, :].astype(jnp.float32)

    return prefill, decode_token, logits_last


def _kv_int8(kv_dtype):
    if kv_dtype in (None, "int8"):
        return kv_dtype == "int8"
    raise ValueError(f"kv_dtype {kv_dtype!r} not in (None, 'int8')")


def make_generate(model, max_len: Optional[int] = None,
                  compute_dtype=None, kv_dtype: Optional[str] = None):
    """Build ``generate(params, prompt_ids, max_new, rng=None,
    temperature=0.0, top_k=0, top_p=1.0) -> [B, prompt+max_new] ids``.

    ``params`` is ``model.param_tree()`` (1-based token ids, like the
    training path).  ``max_len`` bounds prompt+generated (default: the
    model's positional table length); it does NOT set the length of
    the K/V cache, which each program allocates for its own prompt +
    ``max_new`` (:func:`_cache_len`), so a short call on a long-context
    model reads a short cache at every step.  One compiled program per
    (prompt_shape, max_new, top_k, greedy, nucleus), where ``greedy =
    not temperature > 0`` and ``nucleus = 0 < top_p < 1`` are read on
    the host from the call's own numbers (a greedy call ignores
    ``top_k`` / ``top_p``: one program); the decode loop itself is a
    scan — no per-token dispatch.  ``return_stats=True`` returns
    ``(ids, stats)``: for a model with dropless expert layers ``stats``
    holds ``moe_counts`` ``[expert layers, held]`` int32, the
    assignments each held expert took in the call (fetched with the
    tokens; a dense layer among them has no row); for a model whose
    residual is a hyper-connection ``mhc_sinkhorn_err``, the largest
    ``|rowsum - 1|`` or ``|colsum - 1|`` of a residual map the call
    computed (a float32 scalar); for any other model it is empty.

    ``generate.compile_ahead(params, batch, prompt_len, max_new,
    executor)`` starts the compile of the GREEDY program of that shape
    on ``executor`` (a ``concurrent.futures`` pool) and returns at once;
    the first greedy call of the shape waits for it and runs what it
    built.  A server that is going to need a whole ladder of batch sizes
    compiles them beside each other so (``serving/server.py``): tracing
    and lowering are Python and take their turn under one lock, XLA's
    compile of one program runs while the next is lowered.
    """
    first, count = _check_model(model)
    T_max = _check_len(model, max_len)
    prefill, decode_token, logits_last = _decode_machinery(
        model, first, count, kv_int8=_kv_int8(kv_dtype))
    counted = any(_block_kind(b)[2] is not None or getattr(b, "streams", 0)
                  for b in model.modules[first:first + count])

    # device scopes (``jax.named_scope``): metadata on the HLO
    # operations only — ``generate.cast_params`` / ``.prefill`` /
    # ``.decode_step`` / ``.sample`` name what a device trace shows as
    # fusion.NNN; the computation is the same with or without them
    @jax.named_scope("generate.sample")
    def _sample(logits, temperature, top_k, top_p, key, greedy, nucleus):
        # ``top_k``, ``greedy`` and ``nucleus`` are Python values: the
        # program holds only the arm the call asked for
        if greedy:
            return jnp.argmax(logits, axis=-1)
        if top_k:
            kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        scaled = logits / jnp.maximum(temperature, 1e-6)
        if nucleus:
            # drop tokens outside the smallest set whose prob mass
            # reaches top_p (computed at the sampling temperature)
            probs = jax.nn.softmax(scaled, axis=-1)
            order = jnp.argsort(-probs, axis=-1)
            csum = jnp.cumsum(jnp.take_along_axis(probs, order, -1),
                              axis=-1)
            # keep ranks whose PRECEDING mass < top_p (always keeps
            # rank 0)
            keep_sorted = jnp.concatenate(
                [jnp.zeros_like(csum[:, :1]), csum[:, :-1]],
                axis=-1) < top_p
            keep = jnp.zeros_like(keep_sorted).at[
                jnp.arange(logits.shape[0])[:, None], order].set(
                    keep_sorted)
            scaled = jnp.where(keep, scaled, -jnp.inf)
        return jax.random.categorical(key, scaled, axis=-1)

    @partial(jax.jit, static_argnums=(2, 5, 9, 10))
    def _run(p, prompt, max_new, key, temperature, top_k, top_p,
             eos, pad, greedy, nucleus):
        with jax.named_scope("generate.cast_params"):
            pc = _cast_params(p, compute_dtype)
        B, T0 = prompt.shape
        T_cache = _cache_len(T_max, T0, max_new)
        dt = (compute_dtype
              or jax.tree_util.tree_leaves(pc)[0].dtype)

        def next_token(logits, key):
            """(key, 1-based ids): a sampled step consumes one split of
            the key, a greedy step none."""
            sub = None
            if not greedy:
                key, sub = jax.random.split(key)
            return key, _sample(logits, temperature, top_k, top_p, sub,
                                greedy, nucleus) + 1

        with jax.named_scope("generate.prefill"):
            h, caches = prefill(pc, prompt, dt, T_cache)
            logits = logits_last(pc, h)
        key, nxt = next_token(logits, key)
        # eos==0 disables early stop (ids are 1-based, 0 never matches).
        # Static shapes throughout: finished rows keep decoding but
        # emit `pad` (the hf.generate convention) — the work is bounded
        # by max_new either way.
        done = (nxt == eos) & (eos > 0)
        ids = jnp.zeros((B, T0 + max_new), prompt.dtype)
        ids = lax.dynamic_update_slice(ids, prompt, (0, 0))
        ids = lax.dynamic_update_slice(ids, nxt[:, None].astype(
            ids.dtype), (0, T0))

        def one_token(carry, _):
            caches, ids, pos, key, done = carry
            with jax.named_scope("generate.decode_step"):
                tok = lax.dynamic_slice(ids, (0, pos), (B, 1))
                h, new_caches = decode_token(pc, tok, caches, pos)
                logits = logits_last(pc, h)
            key, nxt = next_token(logits, key)
            nxt = jnp.where(done, pad, nxt)
            done = done | ((nxt == eos) & (eos > 0))
            ids = lax.dynamic_update_slice(
                ids, nxt[:, None].astype(ids.dtype), (0, pos + 1))
            return (new_caches, ids, pos + 1, key, done), None

        if max_new > 1:
            (caches, ids, _, _, _), _ = lax.scan(
                one_token, (caches, ids, T0, key, done), None,
                length=max_new - 1)
        if not counted:
            return ids
        stats = {}
        counts = [jnp.sum(c["moe_counts"], axis=0)
                  for c in caches if "moe_counts" in c]
        if counts:
            # [layers, held]: the assignments each held expert took in
            # this call, prefill and every decode step, all rows
            stats["moe_counts"] = jnp.stack(counts)
        errs = [c["mhc_err"] for c in caches if "mhc_err" in c]
        if errs:
            stats["mhc_sinkhorn_err"] = jnp.max(jnp.stack(errs))
        return ids, stats

    # greedy programs compiled ahead of their first call:
    # (batch, prompt_len, max_new) -> Future of the executable
    ahead = {}

    def _compile(params, batch, prompt_len, max_new):
        prompt = jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)
        with _LOWER_LOCK:
            lowered = _run.lower(
                params, prompt, max_new, jax.random.PRNGKey(0),
                jnp.float32(0.0), 0, jnp.float32(1.0), jnp.int32(0),
                jnp.int32(0), True, False)
        return lowered.compile()

    def compile_ahead(params, batch: int, prompt_len: int, max_new: int,
                      executor):
        shape = (int(batch), int(prompt_len), int(max_new))
        if shape not in ahead:
            ahead[shape] = executor.submit(_compile, params, *shape)

    def _compiled_ahead(shape):
        """The executable ``compile_ahead`` built for ``shape``, waited
        for; None where none was asked for or its pool was shut down
        before its turn (the call then compiles by itself)."""
        fut = ahead.get(shape)
        if fut is None:
            return None
        try:
            return fut.result()
        except CancelledError:
            del ahead[shape]
            return None

    def generate(params, prompt_ids, max_new: int, rng=None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, eos_id: Optional[int] = None,
                 pad_id: Optional[int] = None, return_stats: bool = False):
        if temperature > 0 and rng is None:
            raise ValueError(
                "temperature > 0 requires an explicit rng key "
                "(jax.random.PRNGKey) — a fixed default would return "
                "the identical sample every call")
        key = rng if rng is not None else jax.random.PRNGKey(0)
        eos, pad = _eos_pad(model, eos_id, pad_id)
        # the sampler is chosen HERE, from the call's own numbers; their
        # values stay traced, so a new temperature compiles nothing.  A
        # greedy call ignores top_k / top_p and shares one program
        greedy = not temperature > 0
        nucleus = bool(not greedy and 0 < top_p < 1)
        prompt = jnp.asarray(prompt_ids, jnp.int32)
        shape = prompt.shape + (int(max_new),)
        program = (_compiled_ahead(shape)
                   if greedy and rng is None else None)
        if program is not None:
            try:
                # the static arguments are part of the executable
                out = program(params, prompt, key,
                              jnp.float32(temperature), jnp.float32(top_p),
                              eos, pad)
            except TypeError:
                # an executable serves the parameter tree it was
                # lowered for: one swapped in at another dtype or
                # placement goes the jitted way from here on
                del ahead[shape]
                program = None
        if program is None:
            out = _run(params, prompt, int(max_new), key,
                       jnp.float32(temperature),
                       0 if greedy else int(top_k), jnp.float32(top_p),
                       eos, pad, greedy, nucleus)
        ids, stats = out if counted else (out, {})
        return (ids, stats) if return_stats else ids

    generate.compile_ahead = compile_ahead
    generate.ahead = ahead
    return generate


def make_beam_search(model, max_len: Optional[int] = None,
                     compute_dtype=None, kv_dtype: Optional[str] = None):
    """Build ``beam_search(params, prompt_ids, max_new, num_beams=4,
    eos_id=None, pad_id=None) -> (ids [B, prompt+max_new], scores [B])``.

    Beam decode at static shapes: each step expands every beam over the
    vocabulary and keeps the top ``num_beams`` by cumulative
    log-probability, gathering the KV caches along the beam dim to
    follow their parents.  ``scores`` are total log-probs.  With
    ``eos_id``, a beam that emits eos FINISHES: its score freezes and
    its only continuation is ``pad_id`` (default the eos) at zero cost,
    so finished beams compete with live ones at full width — the
    returned best may be a finished beam.  No length penalty is applied
    (scores are raw sums; with eos enabled, shorter finished beams
    naturally carry fewer negative terms — the standard caveat).

    When ``num_beams`` exceeds the vocabulary, the surplus first-step
    beams start dead (-inf) and are claimed by real expansions at later
    depths, so ``num_beams=1`` reduces to greedy and with enough beams
    to hold every prefix it IS exhaustive search (the oracle test pins
    that, with and without eos).  Shares :func:`_decode_machinery` with
    the sampling decoder."""
    first, count = _check_model(model)
    T_max = _check_len(model, max_len)
    _refuse_streams(model.modules[first:first + count], "beam search",
                    "gathers every cache leaf along the beam axis")
    prefill, decode_token, logits_last = _decode_machinery(
        model, first, count, kv_int8=_kv_int8(kv_dtype))

    @partial(jax.jit, static_argnums=(2, 3))
    def _run(p, prompt, max_new, kk, eos, pad):
        pc = _cast_params(p, compute_dtype)
        B, T0 = prompt.shape
        T_cache = _cache_len(T_max, T0, max_new)
        dt = (compute_dtype
              or jax.tree_util.tree_leaves(pc)[0].dtype)

        h, caches = prefill(pc, prompt, dt, T_cache)
        logp0 = jax.nn.log_softmax(logits_last(pc, h), axis=-1)  # [B, V]
        V = logp0.shape[-1]
        # the first expansion has only V candidates: surplus beams
        # start dead (-inf) and get claimed at later depths, keeping
        # the beam width (and every shape) at kk throughout
        k0 = min(kk, V)
        scores, first_tok = jax.lax.top_k(logp0, k0)      # [B, k0]
        if k0 < kk:
            scores = jnp.concatenate(
                [scores, jnp.full((B, kk - k0), -jnp.inf,
                                  scores.dtype)], axis=1)
            first_tok = jnp.concatenate(
                [first_tok, jnp.zeros((B, kk - k0), first_tok.dtype)],
                axis=1)
        done = ((first_tok + 1) == eos) & (eos > 0)       # [B, kk]
        ids = jnp.zeros((B, kk, T0 + max_new), prompt.dtype)
        ids = ids.at[:, :, :T0].set(prompt[:, None, :])
        ids = ids.at[:, :, T0].set((first_tok + 1).astype(ids.dtype))
        # caches replicate per beam: [B, ...] -> [B*kk, ...]
        # (tree_map: the per-layer cache is an arbitrary pytree — the
        # int8 variant carries quantized values + scales)
        caches = jax.tree_util.tree_map(
            lambda a: jnp.repeat(a, kk, axis=0), caches)
        # a finished beam's one legal continuation: pad at zero cost
        pad_row = jnp.where(jnp.arange(V) == pad - 1, 0.0, -jnp.inf)

        def step(carry, off):
            caches, ids, scores, done = carry
            pos = T0 + off
            tok = jax.vmap(
                lambda row: lax.dynamic_slice(row, (pos,), (1,)))(
                    ids.reshape(B * kk, -1))
            h, new_caches = decode_token(pc, tok, caches, pos)
            logp = jax.nn.log_softmax(logits_last(pc, h), axis=-1)
            logp = jnp.where(done[:, :, None], pad_row[None, None],
                             logp.reshape(B, kk, V))
            cand = scores[:, :, None] + logp
            scores, idx = jax.lax.top_k(cand.reshape(B, kk * V), kk)
            parent = idx // V                             # [B, kk]
            tok_next = (idx % V) + 1
            done = (jnp.take_along_axis(done, parent, axis=1)
                    | ((tok_next == eos) & (eos > 0)))
            # beams follow their parents: reorder ids and caches
            ids = jnp.take_along_axis(ids, parent[:, :, None], axis=1)
            ids = jax.vmap(
                lambda row, t: lax.dynamic_update_slice(row, t, (pos + 1,))
            )(ids.reshape(B * kk, -1),
              tok_next.astype(ids.dtype).reshape(B * kk, 1)).reshape(
                  B, kk, -1)
            gather = (parent + jnp.arange(B)[:, None] * kk).reshape(-1)
            new_caches = jax.tree_util.tree_map(
                lambda a: a[gather], new_caches)
            return (new_caches, ids, scores, done), None

        if max_new > 1:
            (caches, ids, scores, done), _ = lax.scan(
                step, (caches, ids, scores, done), jnp.arange(max_new - 1))
        best = jnp.argmax(scores, axis=-1)                # [B]
        out = jnp.take_along_axis(ids, best[:, None, None], axis=1)[:, 0]
        return out, jnp.take_along_axis(scores, best[:, None],
                                        axis=1)[:, 0]

    def beam_search(params, prompt_ids, max_new: int, num_beams: int = 4,
                    eos_id: Optional[int] = None,
                    pad_id: Optional[int] = None):
        if num_beams < 1:
            raise ValueError(f"num_beams must be >= 1, got {num_beams}")
        eos, pad = _eos_pad(model, eos_id, pad_id)
        return _run(params, jnp.asarray(prompt_ids, jnp.int32),
                    int(max_new), int(num_beams), eos, pad)

    return beam_search


# --------------------------------------------------------------------------
# Paged decode: page-table KV through a shared KVPagePool arena
# --------------------------------------------------------------------------

def _paged_machinery(model, first, count, page_size, page_window=None,
                     page_globals: int = 1):
    """The paged twin of :func:`_decode_machinery`: K/V live in a
    shared ``[num_pages, layers, Hkv, page_size, Dh]`` arena and each
    request addresses its positions through a page table ``pt`` (page
    ids, bucket-padded).  Attention gathers the request's pages into a
    dense view and runs the SAME :func:`_gqa_attend` the unpaged path
    runs — masked positions contribute exactly zero, so the paged
    token stream is the unpaged stream (pinned in
    tests/test_kvpool.py).

    ``page_window`` turns on the page-granular block mask (the BLaST
    sparsity story on the serving path): each decode step gathers and
    attends ONLY the first ``page_globals`` anchor pages plus the last
    ``page_window`` pages — dead pages are never gathered, so a long
    decode's per-token attention cost stops growing with total length.
    Prefill applies the same page-window rule through the block-sparse
    kernel (``ops/block_sparse``; masked dense off-TPU — identical
    math).  A window wide enough to cover the whole bucket is EXACTLY
    the dense paged path (parity pinned in tests/test_kvpool.py).

    Shapes are static per (prompt_len, page_bucket): ``pos`` and
    ``pt`` are traced values, so page-table REUSE never recompiles —
    one decode program per page-count bucket, ever.
    """
    blocks = model.modules[first:first + count]
    ln_f = model.modules[first + count]
    head = model.modules[first + count + 1]
    embed = model.modules[0]
    mha0 = blocks[0].modules[1]
    H, Dh = mha0.num_heads, mha0.head_dim
    Hkv = getattr(mha0, "num_kv_heads", H)
    use_rope = getattr(model, "use_rope", False)
    rope_theta = getattr(mha0, "rope_theta", 10000.0)

    def _split(x, B, h=H):
        return x.reshape(B, -1, h, Dh).transpose(0, 2, 1, 3)

    def _rep(kv):
        if Hkv == H:
            return kv
        return jnp.repeat(kv, H // Hkv, axis=1)

    def _embed_at(pc, tok, pos, Tq):
        h, _ = embed.apply_fn(pc["0"], {}, tok, False, None)
        if use_rope:
            return h
        return h + lax.dynamic_slice_in_dim(pc["pos"], pos, Tq)

    def _qkv(block, ap, ln1, pos_ids):
        mha = block.modules[1]
        B = ln1.shape[0]
        q = _split(_proj(ln1, ap, "wq", "bq", mha.with_bias), B)
        k = _split(_proj(ln1, ap, "wk", "bk", mha.with_bias), B, Hkv)
        v = _split(_proj(ln1, ap, "wv", "bv", mha.with_bias), B, Hkv)
        q, k = mha.normed_heads(ap, q, k)
        if use_rope:
            from ..nn.attention import rope_rotate

            q = rope_rotate(q, pos_ids, rope_theta)
            k = rope_rotate(k, pos_ids, rope_theta)
        return q, k, v

    def logits_last(pc, h):
        h = h[:, -1:, :]
        h, _ = ln_f.apply_fn(pc[str(first + count)], {}, h, False, None)
        h, _ = head.apply_fn(pc[str(first + count + 1)], {}, h, False,
                             None)
        return h[:, 0, :].astype(jnp.float32)

    def _prefill_attend(q, k, v, T0):
        """Prompt self-attention: full causal flash, or the page-window
        block mask through the block-sparse kernel when the window is
        configured and actually binds (fewer pages than the prompt
        holds)."""
        from ..ops.flash_attention import flash_attention

        n_pages = -(-T0 // page_size)
        if page_window is None or n_pages <= page_window + page_globals \
                or T0 % page_size:
            # non-page-multiple prompts keep the dense causal pass: the
            # ragged tail page cannot be expressed at block granularity
            return flash_attention(q, _rep(k), _rep(v), causal=True)
        from ..ops.block_sparse import (block_sparse_attention,
                                        sliding_window_mask)

        mask = sliding_window_mask(n_pages, n_pages, page_window,
                                   n_global=page_globals, causal=True,
                                   block_q=page_size, block_k=page_size)
        return block_sparse_attention(q, _rep(k), _rep(v), mask,
                                      causal=True)

    def prefill(pc, prompt, pt, arena_k, arena_v):
        """The whole prompt in one causal pass (the flash path the
        dense machinery uses — first-token numerics identical), K/V
        scattered into the request's pages.  ``prompt`` is [1, T0]."""
        B, T0 = prompt.shape
        n_pages = -(-T0 // page_size)          # static: T0 is static
        h = _embed_at(pc, prompt, 0, T0)
        for bi, block in enumerate(blocks):
            bp = pc[str(first + bi)]
            ln1, _ = block.modules[0].apply_fn(bp["0"], {}, h, False,
                                               None)
            q, k, v = _qkv(block, bp["1"], ln1, jnp.arange(T0))

            def paged_view(x):  # [1, Hkv, T0, Dh] -> [n, Hkv, ps, Dh]
                xp = jnp.pad(
                    x[0], ((0, 0), (0, n_pages * page_size - T0),
                           (0, 0)))
                return xp.reshape(Hkv, n_pages, page_size,
                                  Dh).transpose(1, 0, 2, 3)

            arena_k = arena_k.at[pt[:n_pages], bi].set(
                paged_view(k).astype(arena_k.dtype))
            arena_v = arena_v.at[pt[:n_pages], bi].set(
                paged_view(v).astype(arena_v.dtype))
            o = _prefill_attend(q, k, v, T0)
            o = o.transpose(0, 2, 1, 3).reshape(B, T0, H * Dh)
            h = h + _proj(o, bp["1"], "wo", "bo",
                          block.modules[1].with_bias)
            h = _ffn_sublayer(block, bp, h)
        return logits_last(pc, h), arena_k, arena_v

    def _page_view(arena, pages, bi, dt):
        """Gather ``pages`` (page-id vector) of layer ``bi`` into a
        dense [1, Hkv, len*page_size, Dh] cache view."""
        n = pages.shape[0]
        return arena[pages, bi].transpose(1, 0, 2, 3).reshape(
            Hkv, n * page_size, Dh)[None].astype(dt)

    def decode(pc, tok, pos, pt, arena_k, arena_v):
        """One token [1, 1] at traced absolute position ``pos``: write
        its K/V into page ``pt[pos // page_size]`` slot ``pos %
        page_size``, attend over the gathered page view.  With a
        ``page_window``, only the anchor + window pages are gathered —
        the page-granular block mask: dead pages cost no gather, no
        bytes, no score columns."""
        P = pt.shape[0]
        windowed = page_window is not None \
            and P > page_window + page_globals
        h = _embed_at(pc, tok, pos, 1)
        for bi, block in enumerate(blocks):
            bp = pc[str(first + bi)]
            ln1, _ = block.modules[0].apply_fn(bp["0"], {}, h, False,
                                               None)
            q, k, v = _qkv(block, bp["1"], ln1, pos + jnp.arange(1))
            page = pt[pos // page_size]
            slot = pos % page_size
            arena_k = arena_k.at[page, bi, :, slot, :].set(
                k[0, :, 0, :].astype(arena_k.dtype))
            arena_v = arena_v.at[page, bi, :, slot, :].set(
                v[0, :, 0, :].astype(arena_v.dtype))
            if windowed:
                # sparse page mask: gather the G anchor pages + the W
                # pages ending at the current one.  ``start`` clamps to
                # G so anchors never duplicate; not-yet-written window
                # slots carry k_pos > pos and mask to exactly zero.
                G, W = page_globals, page_window
                cur = pos // page_size
                start = jnp.maximum(cur - (W - 1), G)
                live = jnp.concatenate(
                    [pt[:G], lax.dynamic_slice(pt, (start,), (W,))])
                page_ids = jnp.concatenate(
                    [jnp.arange(G), start + jnp.arange(W)])
                k_pos = (page_ids[:, None] * page_size
                         + jnp.arange(page_size)[None, :]).reshape(-1)
                kc = _page_view(arena_k, live, bi, q.dtype)
                vc = _page_view(arena_v, live, bi, q.dtype)
                o = _gqa_attend(q, kc, vc, pos, H, Hkv, Dh,
                                k_pos=k_pos)
            else:
                # gather THIS request's pages into a dense
                # [1, Hkv, T, Dh] view (T = bucket * page_size);
                # positions past ``pos`` (padding pages, other
                # requests' bytes) are causally masked to exactly zero
                # weight inside _gqa_attend
                kc = _page_view(arena_k, pt, bi, q.dtype)
                vc = _page_view(arena_v, pt, bi, q.dtype)
                o = _gqa_attend(q, kc, vc, pos, H, Hkv, Dh)
            o = o.transpose(0, 2, 1, 3).reshape(1, 1, H * Dh)
            h = h + _proj(o, bp["1"], "wo", "bo",
                          block.modules[1].with_bias)
            h = _ffn_sublayer(block, bp, h)
        return logits_last(pc, h), arena_k, arena_v

    return prefill, decode


# jitted paged programs per model instance, keyed by (page_size,
# compute_dtype): shared across every pool with that geometry so a
# second pool (a scaled-up replica) never recompiles
_PAGED_FN_CACHE = weakref.WeakKeyDictionary()


def _paged_fns(model, first, count, page_size, compute_dtype,
               page_window=None, page_globals=1):
    from ..optim.optimizer import _cast_floats

    slot = _PAGED_FN_CACHE.setdefault(model, {})
    key = (int(page_size), compute_dtype,
           None if page_window is None else int(page_window),
           int(page_globals))
    if key not in slot:
        prefill, decode = _paged_machinery(model, first, count,
                                           page_size,
                                           page_window=page_window,
                                           page_globals=page_globals)
        cast = (lambda p: _cast_floats(p, compute_dtype)) \
            if compute_dtype else (lambda p: p)

        @jax.jit
        def _prefill(p, prompt, pt, ak, av):
            logits, ak, av = prefill(cast(p), prompt, pt, ak, av)
            return jnp.argmax(logits, axis=-1)[0] + 1, ak, av

        @jax.jit
        def _decode(p, tok, pos, pt, ak, av):
            logits, ak, av = decode(cast(p), tok, pos, pt, ak, av)
            return jnp.argmax(logits, axis=-1)[0] + 1, ak, av

        slot[key] = (_prefill, _decode)
    return slot[key]


class PagedSequence:
    """Host-side state of one in-flight paged decode: the page lease,
    the next write position, and the last emitted (1-based) token."""

    __slots__ = ("lease", "pos", "last", "prompt_len")

    def __init__(self, lease, pos: int, last: int, prompt_len: int):
        self.lease = lease
        self.pos = int(pos)
        self.last = int(last)
        self.prompt_len = int(prompt_len)

    def release(self):
        self.lease.release()


class PagedDecoder:
    """Per-request paged greedy decode against a shared
    :class:`~bigdl_tpu.serving.kvpool.KVPagePool`.

    ``start`` leases pages for the prompt, prefills them, and returns
    the first generated token inside a :class:`PagedSequence`;
    ``step`` advances one token, extending the lease (one page at a
    time) as the decode crosses page boundaries — a failed extension
    raises :class:`~bigdl_tpu.serving.kvpool.PoolExhausted` and the
    caller sheds typed.  Greedy only (the serving path's contract; a
    per-request sampling RNG would defeat page-table compile reuse).

    Compile accounting: ONE jitted prefill per (prompt_len,
    page_bucket) and ONE jitted decode per page bucket — ``pos`` and
    the page table are traced, so steps and page-table reuse never
    recompile.  ``compile_stats()`` exposes both jit cache sizes for
    the tests that pin this.
    """

    def __init__(self, model, pool, compute_dtype=None,
                 max_len: Optional[int] = None,
                 page_window: Optional[int] = None,
                 page_globals: int = 1):
        from ..optim.optimizer import _cast_floats

        if page_window is not None and page_window < 1:
            raise ValueError(f"page_window must be >= 1 pages, got "
                             f"{page_window}")
        first, count = _check_model(model)
        _refuse_recurrent(model, first, count, "PagedDecoder (KVPagePool)")
        mha0 = model.modules[first].modules[1]
        Hkv = getattr(mha0, "num_kv_heads", mha0.num_heads)
        if (pool.layers, pool.num_kv_heads, pool.head_dim) != \
                (count, Hkv, mha0.head_dim):
            raise ValueError(
                f"pool geometry (layers={pool.layers}, "
                f"Hkv={pool.num_kv_heads}, Dh={pool.head_dim}) does "
                f"not match the model (layers={count}, Hkv={Hkv}, "
                f"Dh={mha0.head_dim})")
        self.model = model
        self.pool = pool
        #: decode window cap: the positional table AND the arena both
        #: bound how long any one request may grow
        self.T_max = min(_check_len(model, max_len),
                         pool.max_positions)
        self.max_pages = pool.pages_for_tokens(self.T_max)
        # the jitted programs depend only on (model, page_size,
        # compute_dtype, page window) — NOT on which pool's arena they
        # run against — so every same-geometry pool (each autoscaled
        # replica gets its own) shares one compile, and a cold
        # scale-up pays zero paged compiles on an already-warm host
        self.page_window = page_window
        self.page_globals = int(page_globals)
        self._prefill_fn, self._decode_fn = _paged_fns(
            model, first, count, pool.page_size, compute_dtype,
            page_window=page_window, page_globals=page_globals)

    # ------------------------------------------------------------------
    def _padded_table(self, lease):
        from ..serving.kvpool import page_bucket_for

        bucket = page_bucket_for(len(lease.pages), self.max_pages)
        pt = lease.pages + [0] * (bucket - len(lease.pages))
        return jnp.asarray(pt, jnp.int32)

    def start(self, params, prompt_ids) -> PagedSequence:
        """Prefill one 1-D prompt into freshly leased pages; the
        returned sequence's ``last`` is the first generated token.
        Raises ``PoolExhausted`` (shed typed upstream) when the pool
        cannot back the prompt."""
        prompt = jnp.asarray(prompt_ids, jnp.int32)
        if prompt.ndim != 1:
            raise ValueError(f"prompt_ids must be 1-D, got shape "
                             f"{prompt.shape}")
        T0 = int(prompt.shape[0])
        if T0 + 1 > self.T_max:
            raise ValueError(
                f"prompt {T0} leaves no decode room in max_len "
                f"{self.T_max}")
        lease = self.pool.alloc(self.pool.pages_for_tokens(T0))
        try:
            pt = self._padded_table(lease)
            with self.pool.arena_lock:
                ak, av = self.pool.arena
                tok, ak, av = self._prefill_fn(params, prompt[None],
                                               pt, ak, av)
                self.pool.set_arena(ak, av)
            return PagedSequence(lease, pos=T0, last=int(tok),
                                 prompt_len=T0)
        except BaseException:
            lease.release()
            raise

    def step(self, params, seq: PagedSequence) -> int:
        """Advance one greedy token (writes the previous token's K/V
        at ``seq.pos``).  May raise ``PoolExhausted`` on a failed page
        extension — the sequence's pages stay held so the caller can
        resolve it typed before releasing."""
        if seq.lease.released:
            raise RuntimeError("sequence already released")
        if seq.pos + 1 > self.T_max:
            raise ValueError(f"decode window exhausted at pos "
                             f"{seq.pos} (max_len {self.T_max})")
        need = seq.pos // self.pool.page_size + 1
        if need > len(seq.lease.pages):
            seq.lease.extend(need - len(seq.lease.pages))
        pt = self._padded_table(seq.lease)
        tok = jnp.asarray([[seq.last]], jnp.int32)
        with self.pool.arena_lock:
            ak, av = self.pool.arena
            nxt, ak, av = self._decode_fn(params, tok,
                                          jnp.int32(seq.pos), pt, ak,
                                          av)
            self.pool.set_arena(ak, av)
        seq.pos += 1
        seq.last = int(nxt)
        return seq.last

    def compile_stats(self) -> dict:
        """Jit cache sizes — the static-shape contract: decode entries
        ≤ page buckets used, prefill entries ≤ distinct (prompt_len,
        bucket) pairs."""
        return {
            "prefill_cache_size": int(self._prefill_fn._cache_size()),
            "decode_cache_size": int(self._decode_fn._cache_size()),
        }


# compiled paged decoders per model instance (the _GEN_CACHE pattern);
# the inner key carries the pool's identity — a pool swap (new arena
# geometry) must rebuild the decoder
_PAGED_CACHE = weakref.WeakKeyDictionary()


def cached_paged_decoder(model, pool, compute_dtype=None,
                         max_len: Optional[int] = None,
                         page_window: Optional[int] = None,
                         page_globals: int = 1) -> PagedDecoder:
    cfg = (id(pool), compute_dtype, max_len or model.max_len,
           page_window, int(page_globals))
    slot = _PAGED_CACHE.setdefault(model, {})
    if cfg not in slot:
        slot[cfg] = PagedDecoder(model, pool,
                                 compute_dtype=compute_dtype,
                                 max_len=max_len,
                                 page_window=page_window,
                                 page_globals=page_globals)
    return slot[cfg]


# compiled capacity replays per model instance (the _GEN_CACHE
# pattern): the report is meant to run on EVERY batch a generator
# produces, so the prefill replay must not recompile per call
_BIND_CACHE = weakref.WeakKeyDictionary()


def capacity_bind_report(model, params, ids):
    """How far MoE decode diverges from the trained function: per MoE
    block, the fraction of ``ids``'s ROUTING ASSIGNMENTS (``N·top_k``
    of them — for top-1 that is simply the tokens) that the TRAINING
    dispatch's static capacity (``parallel/moe.py`` ``_route``:
    ``C = ceil(f·N/E)`` at this batch's token count, choice-ordered
    stream) would have DROPPED.  Decode itself
    routes capacity-free — a trained model whose capacity binds decodes
    through a different function than it was trained on, and this is the
    measurement of how often (weak-#8 contract: run it on real routed
    batches, e.g. the sequences a generator just produced).

    Teacher-forcing replay through the decode machinery (capacity-free
    MoE advance, so the hidden states are exactly the decode path's).
    The capacity rule applied is the DENSE dispatch's global convention
    (one cumsum over all ``B·T`` tokens, ``C = ceil(f·N/E)``).  A model
    trained under expert parallelism budgeted per (shard, expert) pair
    instead (``C_local = ceil(f·N_local/E)``, moe.py module docstring),
    which can only drop MORE when a hot expert's load concentrates on
    one shard — so for sharded-trained models this report is a lower
    bound (and the training-time shard composition of a batch isn't
    reconstructible at decode time anyway).

    Returns ``{block_index: fraction}`` over the model's MoE blocks plus
    ``"overall"`` (their mean); ``{}`` for a dense model."""
    first, count = _check_model(model)
    blocks = model.modules[first:first + count]
    moe_idx = [first + bi for bi, b in enumerate(blocks) if b.is_moe]
    if not moe_idx:
        return {}
    ids = jnp.asarray(ids, jnp.int32)
    T = int(ids.shape[1])
    if T > model.max_len:
        raise ValueError(f"sequence length {T} exceeds max_len "
                         f"{model.max_len}")

    slot = _BIND_CACHE.setdefault(model, {})
    if T not in slot:
        prefill, _, _ = _decode_machinery(model, first, count)

        @jax.jit
        def _replay(p, toks):
            _BIND_TLS.capture = []
            try:
                dt = jax.tree_util.tree_leaves(p)[0].dtype
                prefill(p, toks, dt, T)
                fracs = list(_BIND_TLS.capture)
            finally:
                _BIND_TLS.capture = None
            return jnp.stack(fracs)

        slot[T] = _replay
    fracs = [float(f) for f in slot[T](params, ids)]
    report = dict(zip(moe_idx, fracs))
    report["overall"] = sum(fracs) / len(fracs)
    return report


def cached_generate(model, compute_dtype=None, kv_dtype=None,
                    max_len: Optional[int] = None):
    """The per-model compiled generator (built once per
    (max_len, compute_dtype, kv_dtype) config, weakly cached).
    ``max_len`` bounds the decode window below the model's positional
    table (``_check_len`` validates it) — a serving config can cap
    per-request work without rebuilding the model."""
    cfg = (max_len or model.max_len, compute_dtype, kv_dtype)
    slot = _GEN_CACHE.setdefault(model, {})
    if cfg not in slot:
        slot[cfg] = make_generate(model, max_len=max_len,
                                  compute_dtype=compute_dtype,
                                  kv_dtype=kv_dtype)
    return slot[cfg]
