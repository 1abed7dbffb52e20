"""Autoregressive generation for the causal LMs of this package
(:class:`CausalLM`: ``TransformerLM``, ``HybridMambaLM``,
``ParallelMoELM``, ``SequentialMoELM`` and its families) — cached decode
over whatever state each layer keeps between tokens.

The reference predates autoregressive LMs entirely (its sequence story
is Recurrent/TimeDistributed, SURVEY §5.7), so this is a TPU-native
extension: one jitted program containing a **batched prefill** (the
whole prompt in one causal pass that fills the per-layer caches —
MXU-sized matmuls, not a token loop) followed by a ``lax.scan`` over
decode steps at static shapes, with the caches updated in place.  No
Python-level loop over tokens, no recompilation per length.

**This file knows what is the same for every model, and asks the layers
for the rest.**  Here: how long the cache of one program is
(:func:`_cache_len` — prompt + ``max_new``, both static, rounded up to a
multiple of 128 and never past ``max_len``: a decode step of the plain
form reads its whole cache, so a cache as long as the model's positional
table would make every step pay for positions no call of this program
can ever write), the embedding, the loop over blocks, the head, sampling,
the scan, beam search, the paged store and the compile ladder.  With the
layer (the decode-state protocol, ``nn/attention.py``): what a block
keeps between tokens (``block.state_init``) and how Tq tokens at ``pos``
advance it (``block.advance``) — per-head K/V, plain, a ring of a window
or int8 (``nn.MultiHeadAttention``); a latent cache with an absorbed
step (``nn.LatentAttention``); a recurrent state beside the K/V
(``nn.HybridMambaBlock``); a convolution tail (``nn.GatedShortConv``);
expert counts (``models/parallel_moe.py``); ``n`` residual streams a
token with a counter (``models/latent_moe.py``).  A new operator brings
a module under ``nn/`` and no line of this file (``docs/serving.md``,
"what a new architecture brings to be served").  ONE machinery
(``_decode_machinery``) backs both the sampling decoder and beam search,
and greedy decode is pinned against the full dense forward by a
teacher-forcing oracle in tests/test_generate.py, which keeps the
implementations from drifting.

Who cannot hold a layer's state says so once, from the protocol: beam
search gathers every leaf along the beam axis and refuses a state with a
leaf that has none; ``kv_dtype="int8"`` asks each operator to hold its
state so (K/V can; a latent cache refuses; a state that is no K/V stays
as it is); the paged decoder keeps K/V pages of one length and refuses a
block whose state is anything else.

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
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..nn.mamba import scaled as _scaled
from ..telemetry.registry import default_registry

# compiled generators per model instance (weak: dies with the model),
# keyed by build config.  NOT stored on the module itself — a jitted
# closure attribute would break the pickle-based checkpoint verbs.
_GEN_CACHE = weakref.WeakKeyDictionary()
# tracing and lowering a generate program run the model's Python: one
# at a time, whichever thread asks (``make_generate.compile_ahead``)
_LOWER_LOCK = threading.Lock()


class CausalLM:
    """What generation serves, mixed into a ``Container`` of
    ``TransformerLM``'s child layout: ``0`` the embedding, ``1..L``
    blocks that answer the decode-state protocol (``nn/attention.py``),
    ``L+1`` the final norm, ``L+2`` the head.  What the two ends apply
    beside their modules is read here, each at its neutral default: a
    table of learned positions unless ``use_rope``, a head that is handed
    the embedding's leaf where ``tied_head``, and three constant
    scales."""

    use_rope = False
    tied_head = False
    embedding_multiplier = 1.0
    lm_head_multiplier = 1.0
    logit_scale = 1.0

    def generate(self, prompt_ids, max_new: int, rng=None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, compute_dtype=None,
                 eos_id=None, pad_id=None):
        """Autoregressive decode through each layer's own cache:
        prefill + ``lax.scan`` decode at static shapes.
        ``temperature=0`` is greedy (pinned against the dense forward by
        teacher forcing); ``>0`` samples, optionally within ``top_k``
        and/or the ``top_p`` nucleus; the compiled program holds only
        the sampler the call asked for, and a new ``temperature`` or
        ``top_p`` value compiles nothing.  ``eos_id`` stops a row early
        (it keeps emitting ``pad_id``, default the eos itself —
        hf.generate's convention, at static shapes).  The compiled
        generator is cached per (max_len, compute_dtype)."""
        return cached_generate(self, compute_dtype)(
            self.param_tree(), prompt_ids, max_new, rng=rng,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_id=eos_id, pad_id=pad_id)


def _check_model(model):
    if not isinstance(model, CausalLM):
        raise TypeError(
            f"generation supports the CausalLM containers (TransformerLM, "
            f"HybridMambaLM, ParallelMoELM, SequentialMoELM and its "
            f"families; got {type(model).__name__})")
    # seq_strategy (dense/flash/ring/ulysses) changes only HOW training
    # attention is computed — the parameter tree is strategy-independent,
    # so a ring/Ulysses-trained model decodes through the same cached
    # single-shard attention as a dense one (pinned against a dense twin
    # built from the same params in tests/test_generate.py)
    return 1, len(model.modules) - 3


def _refusal(what: str, block):
    """The one way a decoder says it cannot hold a block's state: by the
    block's class and in the block's own words (``state_doc``)."""
    return TypeError(
        f"{what} and cannot hold {type(block).__name__}'s state — "
        f"{getattr(block, 'state_doc', 'it keeps one of its own')}: decode "
        f"this model through generate() / submit_generate()")


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



#: tokens one pass of the prompt may hold: a bucket's prompt of more
#: goes through the blocks in groups of rows (``prefill_groups``)
PREFILL_TOKENS = 65536


def prefill_groups(batch: int, prompt_len: int) -> int:
    """Groups of rows a prompt of ``batch`` x ``prompt_len`` tokens goes
    through the blocks in: 1 within ``PREFILL_TOKENS``, else ``batch`` /
    the largest power of two of rows that divides the batch and holds
    at most that many tokens (one row a group where a single row is
    longer: what grows with ONE row's length is not cut here).  From
    shapes alone — the rule the program reads, and ``cache_footprint``."""
    if batch * prompt_len <= PREFILL_TOKENS:
        return 1
    rows = 1
    while (batch % (2 * rows) == 0
           and 2 * rows * prompt_len <= PREFILL_TOKENS):
        rows *= 2
    return batch // rows


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


def cache_footprint(model, batch: int, prompt_len: int, max_new: int,
                    compute_dtype=None, max_len: Optional[int] = None,
                    kv_dtype: Optional[str] = None) -> dict:
    """State one generate call of ``batch`` rows, ``prompt_len`` prompt
    tokens and ``max_new`` answer tokens holds on the device, from
    shapes alone: ``kv_cache_positions`` (how long that program's
    static cache is, :func:`_cache_len`, against the model's
    ``max_len``) and what each layer says it keeps at that length
    (``block.footprint``), summed by kind: ``kv_cache_bytes`` (the K/V
    of every layer THAT KEEPS ONE: what is allocated, and what every
    decode step reads; also by kind of layer, ``kv_cache_bytes_window``
    and ``kv_cache_bytes_full`` — the attention operator's own split,
    ``MultiHeadAttention.footprint``: it has a window or it has not),
    ``latent_cache_bytes`` (a latent layer's; its ``kv_cache_bytes`` is
    0) and ``recurrent_state_bytes`` (SSM state and conv tail — a
    short-convolution layer's whole state; zero for a model without
    them).  Beside the bytes, which arm this program's decode step
    compiled: ``kv_attend`` / ``latent_attend`` (``"kernel"`` or
    ``"einsum"``) with ``kv_attend_block`` / ``latent_attend_block``
    (positions a block of the kernel's walk; 0 for the einsums) — of
    the layers that take the kernel, where some do (a ring keeps the
    einsums) — and, for a model with experts, ``grouped``,
    ``grouped_tiles`` and ``grouped_tiles_down``
    (``DroplessMoE.decode_plan``) and the same three for a piece of the
    prompt pass, ``grouped_prefill*`` (a block's ``prefill_plan`` of the
    tokens one group of rows brings).  ``prefill_groups``: the groups of
    rows the prompt pass goes in (:func:`prefill_groups`; 1: whole).
    Each is the rule the program itself reads."""
    first, count = _check_model(model)
    T_cache = _cache_len(_check_len(model, max_len), int(prompt_len),
                         int(max_new))
    dt = jnp.dtype(compute_dtype or jax.tree_util.tree_leaves(
        model.param_tree())[0].dtype)
    groups = prefill_groups(int(batch), int(prompt_len))
    out = {"kv_cache_bytes": 0, "recurrent_state_bytes": 0,
           "kv_cache_positions": T_cache, "prefill_groups": groups}
    for block in model.modules[first:first + count]:
        if hasattr(block, "prefill_plan"):
            for name, v in block.prefill_plan(
                    int(batch) // groups * int(prompt_len), dt).items():
                out.setdefault(name, v)
        for name, v in block.footprint(int(batch), dt, T_cache,
                                       _kv_int8(kv_dtype)).items():
            if isinstance(v, int):                  # bytes of a kind
                out[name] = out.get(name, 0) + v
            elif not isinstance(v, tuple):          # a plan, in words
                out.setdefault(name, v)
            elif v[1] or name not in out:           # (arm, its block)
                out[name], out[name + "_block"] = v
    return out


def _ends(model, first, count):
    """The two ends every decoder shares: ``embed_at(pc, tok, pos, Tq)``
    (the state the first block takes) and ``logits_last(pc, h)``."""
    blocks = model.modules[first:first + count]
    embed, ln_f, head = (model.modules[i]
                         for i in (0, first + count, first + count + 1))
    # a block whose state between layers is not the one residual vector
    # says how the embedding becomes it and what the final norm takes
    spread = getattr(blocks[0], "replicate", lambda h: h)
    gather = getattr(blocks[0], "reduce", lambda h: h)

    def embed_at(pc, tok, pos, Tq):
        h, _ = embed.apply_fn(pc["0"], {}, tok, False, None)
        h = _scaled(h, model.embedding_multiplier)
        if model.use_rope:  # positions live in the per-layer q/k rotation
            return spread(h)
        return spread(h + lax.dynamic_slice_in_dim(pc["pos"], pos, Tq))

    def logits_last(pc, h):
        """Head on the LAST position of h only -> [B, V] f32."""
        h = gather(h[:, -1:])
        h, _ = ln_f.apply_fn(pc[str(first + count)], {}, h, False, None)
        # a tied head owns no leaf: it is handed the embedding's
        h, _ = head.apply_fn(
            pc["0" if model.tied_head else str(first + count + 1)], {}, h,
            False, None)
        h = _scaled(h, model.lm_head_multiplier)
        h = _scaled(h, model.logit_scale)
        return h[:, 0, :].astype(jnp.float32)

    return embed_at, logits_last


def _leaves_without_rows(block, dtype, length, int8=False) -> set:
    """The leaves of ``block``'s decode state that have no batch axis (a
    counter a layer): those whose shape two batch sizes do not tell
    apart."""
    one, two = (jax.eval_shape(lambda b=b: block.state_init(
        b, dtype, length, int8)) for b in (1, 2))
    return {leaf for leaf in one if one[leaf].shape == two[leaf].shape}


def _decode_machinery(model, first, count, kv_int8=False):
    """The cached forward shared by the sampling decoder and beam search
    — built once per generator from the model structure.  Every
    function takes the (already cast) param tree ``pc`` explicitly;
    ``prefill`` is told how long a cache to allocate, and everything
    after it takes that length from the cache's shape.  ``kv_int8``
    asks every layer to hold its state as int8 (an approximation knob,
    off by default): whoever cannot says so here, at the build."""
    blocks = model.modules[first:first + count]
    if kv_int8:
        jax.eval_shape(lambda: [b.state_init(1, jnp.float32, 128, True)
                                for b in blocks])
    embed_at, logits_last = _ends(model, first, count)

    def prefill_rows(pc, prompt, dt, T_cache, carried=None):
        """``prompt``'s rows in one causal pass; ``carried``: a layer's
        leaves that have no batch axis, as the rows before left them."""
        B, T0 = prompt.shape
        h = embed_at(pc, prompt, 0, T0)
        caches = []
        for bi, block in enumerate(blocks):
            cache = block.state_init(B, dt, T_cache, kv_int8)
            if carried:
                cache.update(carried[bi])
            h, cache = block.advance(pc[str(first + bi)], h, cache, 0)
            caches.append(cache)
        return h, caches

    def prefill(pc, prompt, dt, T_cache, whole=False):
        """The whole prompt in causal passes; returns (h [B,T0,D] — or
        whatever state the blocks hand each other; of the LAST position
        alone where the rows went in groups — and caches) of
        ``T_cache`` positions with [0, T0) filled.  A prompt of more
        than ``PREFILL_TOKENS`` tokens goes through the blocks in
        groups of rows (:func:`prefill_groups`; a Python loop, so that
        a generate program keeps ONE ``while``, its decode scan): what
        a pass holds beside the weights — q, the K and V repeated to
        the query heads, the dispatch buffers — grows with its tokens.
        Each group's caches land in the batch's by rows; a leaf without
        a batch axis (a counter a layer) is carried from group to
        group as it is from step to step.  ``whole``: one pass whatever
        its size (``capacity_bind_report`` asks what a capacity sized
        for ALL the batch's tokens would drop)."""
        B, T0 = prompt.shape
        groups = 1 if whole else prefill_groups(B, T0)
        if groups == 1:
            return prefill_rows(pc, prompt, dt, T_cache)
        g = B // groups
        shared = [_leaves_without_rows(block, dt, T_cache, kv_int8)
                  for block in blocks]
        parts, last, carried = [], [], None
        for lo in range(0, B, g):
            with jax.named_scope("generate.prefill_group"):
                h, caches = prefill_rows(pc, prompt[lo:lo + g], dt, T_cache,
                                         carried)
            last.append(h[:, -1:])
            parts.append(caches)
            carried = [{n: c[n] for n in names}
                       for c, names in zip(caches, shared)]
        caches = [{n: (parts[-1][bi][n] if n in shared[bi] else
                       jnp.concatenate([p[bi][n] for p in parts]))
                   for n in parts[0][bi]} for bi in range(len(blocks))]
        return jnp.concatenate(last), caches

    def decode_token(pc, tok, caches, pos):
        """One token [B, 1] at absolute position ``pos``; returns
        (h [B,1,D], new_caches)."""
        h = embed_at(pc, tok, pos, 1)
        new_caches = []
        for bi, block in enumerate(blocks):
            h, cache = block.advance(pc[str(first + bi)], h, caches[bi],
                                     pos)
            new_caches.append(cache)
        return h, new_caches

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
    ``(ids, stats)``, the counters the blocks keep in their state
    (``block.counters``): for a model with dropless expert layers
    ``moe_counts`` ``[expert layers, held]`` int32, the assignments each
    held expert took in the call (fetched with the tokens; a dense layer
    among them has no row); for a model whose residual is a
    hyper-connection ``mhc_sinkhorn_err``, the largest ``|rowsum - 1|``
    or ``|colsum - 1|`` of a residual map the call computed (a float32
    scalar); for any other model it is empty.

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
    blocks = model.modules[first:first + count]
    counted = any(getattr(b, "counters", None) for b in blocks)

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
        # what the layers counted in this call, prefill and every decode
        # step: a counter with a batch axis is summed over the rows and
        # stacked [layers, ...]; a scalar a layer becomes the call's
        # largest
        found = {}
        for block, cache in zip(blocks, caches):
            for leaf, name in getattr(block, "counters", {}).items():
                c = cache[leaf]
                found.setdefault(name, []).append(
                    jnp.sum(c, axis=0) if c.ndim else c)
        return ids, {
            name: jnp.stack(cs) if cs[0].ndim else jnp.max(jnp.stack(cs))
            for name, cs in sorted(found.items(),
                                   key=lambda kv: -kv[1][0].ndim)}

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
        default_registry().counter(
            "bigdl_generate_prefill_groups_total",
            "groups of rows that generate calls' prompt passes went "
            "through the blocks in (1 a call whose prompt went whole)"
        ).inc(prefill_groups(*prompt.shape))
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
    the sampling decoder; it follows a beam by gathering every leaf of
    the caches along the beam axis, so a block whose state has a leaf
    without a batch axis is refused."""
    first, count = _check_model(model)
    T_max = _check_len(model, max_len)
    for block in model.modules[first:first + count]:
        if _leaves_without_rows(block, jnp.float32, 128):
            raise _refusal("beam search gathers every cache leaf along the "
                           "beam axis", block)
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
    """The paged twin of :func:`_decode_machinery`: the SAME embedding,
    head and blocks on another store.  K/V live in a shared
    ``[num_pages, layers, Hkv, page_size, Dh]`` arena and each request
    addresses its positions through a page table ``pt`` (page ids,
    bucket-padded): a block makes its heads and runs its FFN as in the
    static path (``block.attention_sublayer`` / ``ffn_sublayer``) and is
    handed the attention itself — the arena write, the gather of the
    request's pages into a dense view and the SAME
    ``gqa_attend_reference`` the unpaged path runs — masked positions
    contribute exactly zero, so the paged token stream is the unpaged
    stream (pinned in tests/test_kvpool.py).

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
    from ..ops.gqa_attend import gqa_attend_reference

    blocks = model.modules[first:first + count]
    embed_at, logits_last = _ends(model, first, count)

    def _rep(kv, H):
        Hkv = kv.shape[1]
        return kv if Hkv == H else jnp.repeat(kv, H // Hkv, axis=1)

    def _prefill_attend(q, k, v, T0):
        """Prompt self-attention: full causal flash, or the page-window
        block mask through the block-sparse kernel when the window is
        configured and actually binds (fewer pages than the prompt
        holds)."""
        from ..ops.flash_attention import flash_attention

        n_pages, H = -(-T0 // page_size), q.shape[1]
        if page_window is None or n_pages <= page_window + page_globals \
                or T0 % page_size:
            # non-page-multiple prompts keep the dense causal pass: the
            # ragged tail page cannot be expressed at block granularity
            return flash_attention(q, _rep(k, H), _rep(v, H), causal=True)
        from ..ops.block_sparse import (block_sparse_attention,
                                        sliding_window_mask)

        mask = sliding_window_mask(n_pages, n_pages, page_window,
                                   n_global=page_globals, causal=True,
                                   block_q=page_size, block_k=page_size)
        return block_sparse_attention(q, _rep(k, H), _rep(v, H), mask,
                                      causal=True)

    def _through(pc, h, pos, attend_of):
        """``h`` through every block, layer ``bi``'s attention being
        ``attend_of(bi)``."""
        for bi, block in enumerate(blocks):
            bp = pc[str(first + bi)]
            h = block.ffn_sublayer(
                bp, block.attention_sublayer(bp, h, pos, attend_of(bi)))
        return logits_last(pc, h)

    def prefill(pc, prompt, pt, arena_k, arena_v):
        """The whole prompt in one causal pass (the flash path the
        dense machinery uses — first-token numerics identical), K/V
        scattered into the request's pages.  ``prompt`` is [1, T0]."""
        T0 = prompt.shape[1]
        n_pages = -(-T0 // page_size)          # static: T0 is static
        arena = [arena_k, arena_v]

        def paged_view(x):  # [1, Hkv, T0, Dh] -> [n, Hkv, ps, Dh]
            Hkv, Dh = x.shape[1], x.shape[3]
            xp = jnp.pad(x[0], ((0, 0), (0, n_pages * page_size - T0),
                                (0, 0)))
            return xp.reshape(Hkv, n_pages, page_size,
                              Dh).transpose(1, 0, 2, 3)

        def attend_of(bi):
            def attend(q, k, v):
                for i, x in enumerate((k, v)):
                    arena[i] = arena[i].at[pt[:n_pages], bi].set(
                        paged_view(x).astype(arena[i].dtype))
                return _prefill_attend(q, k, v, T0)
            return attend

        logits = _through(pc, embed_at(pc, prompt, 0, T0), None, attend_of)
        return (logits, *arena)

    def _page_view(arena, pages, bi, dt):
        """Gather ``pages`` (page-id vector) of layer ``bi`` into a
        dense [1, Hkv, len*page_size, Dh] cache view."""
        n, Hkv, Dh = pages.shape[0], arena.shape[2], arena.shape[4]
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
        arena = [arena_k, arena_v]

        def attend_of(bi):
            def attend(q, k, v):
                H, Hkv, Dh = q.shape[1], k.shape[1], q.shape[3]
                page = pt[pos // page_size]
                slot = pos % page_size
                for i, x in enumerate((k, v)):
                    arena[i] = arena[i].at[page, bi, :, slot, :].set(
                        x[0, :, 0, :].astype(arena[i].dtype))
                if not windowed:
                    # gather THIS request's pages into a dense
                    # [1, Hkv, T, Dh] view (T = bucket * page_size);
                    # positions past ``pos`` (padding pages, other
                    # requests' bytes) are causally masked to exactly
                    # zero weight inside the attend
                    kc, vc = (_page_view(a, pt, bi, q.dtype) for a in arena)
                    return gqa_attend_reference(q, kc, vc, pos, H, Hkv, Dh)
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
                kc, vc = (_page_view(a, live, bi, q.dtype) for a in arena)
                return gqa_attend_reference(q, kc, vc, pos, H, Hkv, Dh,
                                            k_pos=k_pos)
            return attend

        logits = _through(pc, embed_at(pc, tok, pos, 1), pos, attend_of)
        return (logits, *arena)

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
        for block in model.modules[first:first + count]:
            # pages hold K and V of every position and nothing else: a
            # block is served if that is ALL its state and it runs its
            # attention on a store handed in
            kv = jax.eval_shape(lambda: block.state_init(
                1, jnp.float32, model.max_len))
            if (set(kv) != {"k", "v"} or kv["k"].shape[2] != model.max_len
                    or not hasattr(block, "attention_sublayer")):
                raise _refusal(
                    "PagedDecoder (KVPagePool) keeps K and V in pages of "
                    "ONE length for every layer, under the plain residual,",
                    block)
        _, Hkv, _, Dh = kv["k"].shape
        if (pool.layers, pool.num_kv_heads, pool.head_dim) != \
                (count, Hkv, Dh):
            raise ValueError(
                f"pool geometry (layers={pool.layers}, "
                f"Hkv={pool.num_kv_heads}, Dh={pool.head_dim}) does "
                f"not match the model (layers={count}, Hkv={Hkv}, "
                f"Dh={Dh})")
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
    from ..parallel.moe import BIND_TLS

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
            BIND_TLS.capture = []
            try:
                dt = jax.tree_util.tree_leaves(p)[0].dtype
                prefill(p, toks, dt, T, whole=True)
                fracs = list(BIND_TLS.capture)
            finally:
                BIND_TLS.capture = None
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
