"""SequentialMoELM — a causal LM of SEQUENTIAL pre-norm blocks whose
token-mixing OPERATOR is given per layer and whose FFN is a dense SwiGLU
in the first layers and a mixture of experts after them.  RMSNorm
throughout and no bias anywhere.  The block's RESIDUAL is one of two
kinds.  Plain, one vector a token:

    h = x + Op_i(RMSNorm_1(x))
    y = h + FFN_i(RMSNorm_2(h))

or a hyper-connection (``nn/hyper_connection.py``; ``hyper`` given):
the state is ``n`` streams a token, ``X [n, C]``, and each sublayer
``f`` (the operator, the FFN) has a module of its own that computes
three maps from ``X`` and mixes the streams around it:

    u  = sum_i H_pre[i] X[i];   y = f(RMSNorm(u))
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

with the embedding repeated into the ``n`` streams before the first
block and the streams summed before the final norm.

``Op_i`` is whatever module the layer's factory makes:
:class:`~bigdl_tpu.nn.attention.LatentAttention` (MLA),
:class:`~bigdl_tpu.nn.attention.MultiHeadAttention` (grouped-query, with
per-head QK-norm where asked) or
:class:`~bigdl_tpu.nn.short_conv.GatedShortConv` (no attention at all).
``FFN_i`` is :class:`GatedFFN` (``down(silu(gate n) * up n)``) or
:class:`~bigdl_tpu.parallel.moe.DroplessMoE`: sigmoid scores over ALL
experts, a per-expert correction bias that enters the selection only
(``score_bias``), the ``top_k`` chosen scores renormalised and times
``routed_scale``, SwiGLU experts of which this model may hold a SHARE
(``held``), ``n_shared`` shared experts added.  The head gives float32
logits: a matrix of its own, or (``tied_head``) the embedding's.

Three published families are constructors of it, each building the
operator and FFN lists from its configuration's own numbers:

* :class:`LatentMoELM` — Zhipu's ``glm4_moe_lite`` (GLM-4.7-Flash;
  DeepSeek-V2/V3's block): latent attention in every layer, an untied
  head.
* :class:`ShortConvMoELM` — Liquid's ``lfm2_moe`` (LFM2-24B-A2B): the
  operator follows a published list (``layer_types``: ``"conv"`` or
  ``"full_attention"``), attention is grouped-query with RMSNorm over
  each query and key head, no shared expert, a tied head.
* :class:`HyperLatentMoELM` — XingChen's ``xing4_0``
  (Xing4.0-29B-A4B): ``glm4_moe_lite``'s layers — latent attention,
  here with YaRN and a value head narrower than the query's, the same
  router — around a residual of ``hc_mult`` streams mixed per token by
  manifold-constrained hyper-connections, an untied head.
* :class:`PreRoutedMoELM` — PowerInfer's ``smallthinker``
  (SmallThinker-21BA3B-Instruct): grouped-query attention that is
  position-free and global in one layer of a published layout and
  rotated under a sliding window in the others, an expert layer in
  EVERY block whose router reads the block's INPUT — before the first
  norm, before attention (``pre_routed``) — a softmax over the chosen
  logits, ReLU-gated experts, an untied head.

A ``Container`` with ``TransformerLM``'s child layout — ``0`` the
embedding, ``1..L`` the blocks (children ``0`` RMSNorm, ``1`` the
operator, ``2`` RMSNorm, ``3`` the FFN and, where the residual is a
hyper-connection, ``4`` the operator's and ``5`` the FFN's), ``L+1``
the final RMSNorm, ``L+2`` the head — so the generation builder, the
server and the optimizers take it as they take the dense model.  What ``generate``
keeps a layer is what its operator keeps (``state_init`` / ``sequence``
/ ``step``: the decode-state protocol of ``nn/attention.py``): the
latent ``c_kv`` and the rotated shared key of every position and nothing
by head; per-head K and V; or, for a short convolution, the last
``kernel - 1`` values of its gated input and NOTHING that grows with the
context — and ANY operator that answers the three calls decodes, with
no edit to the generation builder.  A layer with experts adds
``moe_counts`` ``[B, held]``, a hyper-connected one ``mhc_err``, the
call's largest distance of a residual map from doubly stochastic
(``mhc_sinkhorn_err`` of ``return_stats=True``); its state between
layers is ``[B, Tq, n, D]``, and beam search and the paged decoder
refuse it.  ``param_dtype`` and the device draw as in ``HybridMambaLM``;
the selection bias stays float32.
"""
from __future__ import annotations

from contextlib import nullcontext
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.attention import advance, footprint, fresh_state
from ..nn.hyper_connection import HyperConnection
from ..nn.initialization import (IN_OUT, RandomNormal, device_draw,
                                 no_draw)
from ..nn.module import (FLOAT32_LEAVES, Container, TensorModule,
                         hold_floats)
from ..parallel.moe import DroplessMoE
from .generate import CausalLM
from .parallel_moe import TiedHeadTrees


def _held_in(module, dtype):
    """``module`` with its floating leaves cast to ``dtype`` as soon as
    it exists (the selection bias stays float32): child by child, so
    neither a block nor the model is ever whole in float32."""
    module.set_param_tree(hold_floats(module.param_tree(), dtype,
                                      keep=FLOAT32_LEAVES))
    return module


class GatedFFN(TensorModule):
    """A dense SwiGLU MLP without biases: ``w_down(silu(w_gate x) *
    w_up x)``, leaves [out, in]."""

    def __init__(self, embed_dim: int, hidden_dim: int,
                 init_std: float = 0.02):
        super().__init__()
        self.embed_dim, self.hidden_dim = embed_dim, hidden_dim
        self.init_std = float(init_std)
        self.reset()

    def reset(self):
        init = self._init_methods.get(
            "weight", (RandomNormal(0.0, self.init_std), None))[0]
        D, F = self.embed_dim, self.hidden_dim
        for name, shape in (("w_gate", (F, D)), ("w_up", (F, D)),
                            ("w_down", (D, F))):
            self._register_param(name, init.init(shape, IN_OUT))
        return self

    def _apply(self, params, buffers, x, training, rng):
        dt = x.dtype
        g = jnp.dot(x, params["w_gate"].T.astype(dt))
        u = jnp.dot(x, params["w_up"].T.astype(dt))
        return jnp.dot(jax.nn.silu(g) * u,
                       params["w_down"].T.astype(dt)), buffers


class LogitHead(TensorModule):
    """An output projection ``[vocab, embed]`` whose logits are float32
    whatever dtype the matrix is held in (the product accumulates there
    and is not rounded back).  ``tied``: it owns no leaf; whoever
    applies it hands it the embedding's (``{"weight": [vocab,
    embed]}``)."""

    def __init__(self, embed_dim: int, vocab_size: int,
                 init_std: float = 0.02, tied: bool = False):
        super().__init__()
        self.embed_dim, self.vocab_size = embed_dim, vocab_size
        self.init_std, self.tied = float(init_std), bool(tied)
        self.reset()

    def reset(self):
        if getattr(self, "tied", False):
            return self
        init = self._init_methods.get(
            "weight", (RandomNormal(0.0, self.init_std), None))[0]
        self._register_param(
            "weight", init.init((self.vocab_size, self.embed_dim), IN_OUT))
        return self

    def _apply(self, params, buffers, x, training, rng):
        w = params["weight"]
        ct = jnp.promote_types(w.dtype, jnp.float32)
        return jnp.dot(x.astype(w.dtype), w.T,
                       preferred_element_type=ct), buffers


# -- a sequential block's residual, plain or hyper-connected ---------------
def _sublayer_scope(block, i: int):
    """The device scope of sublayer ``i``'s hyper-connection: the
    block's own operator scope, or ``block.mlp``."""
    return (block.operator_scope() if i == 0
            else jax.named_scope("block.mlp"))


def sublayer_input(block, params, i: int, state):
    """What sublayer ``i`` (0 the operator, 1 the FFN) of a block with
    children norm, operator, norm, FFN reads of ``state``, through the
    sublayer's own norm (child ``2 i``): ``(normed input [..., embed],
    coefficients)``.  A plain block — a :class:`SequentialMoEBlock`
    without ``hyper``, an ``nn.TransformerBlock`` — reads the state
    itself and has no coefficients (None); a hyper-connected one
    (``block.hyper``: children ``4`` and ``5``) the mixture its maps
    give."""
    co = None
    if getattr(block, "hyper", None):
        hc = block.hyper[i]
        with _sublayer_scope(block, i):
            co = hc.coefficients(params[str(4 + i)], state)
            state = hc.pre(co, state)
    x, _ = block.modules[2 * i].apply_fn(params[str(2 * i)], {}, state,
                                         False, None)
    return x, co


def sublayer_result(block, i: int, state, y, co):
    """The state after sublayer ``i`` gave ``y``: ``state + y``, or the
    hyper-connection's write to every stream."""
    if co is None:
        return state + y
    with _sublayer_scope(block, i):
        return block.hyper[i].post(co, state, y)


class SequentialMoEBlock(Container):
    """``h = x + Op(norm_1 x); y = h + FFN(norm_2 h)``.  Children:
    ``0`` RMSNorm, ``1`` the operator (latent or grouped-query
    attention, a gated short convolution, or whatever answers
    ``state_init`` / ``sequence`` / ``step``), ``2`` RMSNorm, ``3`` the
    FFN (``ffn_kind``: ``"dense"`` a :class:`GatedFFN`, ``"moe"`` a
    ``DroplessMoE``).

    ``hyper`` (a zero-argument factory of a ``HyperConnection``) makes the
    residual a hyper-connection: children ``4`` (the operator's) and
    ``5`` (the FFN's) follow, ``x`` and ``y`` are ``[B, T, n, embed]``,
    and each sublayer reads and writes the streams through its module's
    maps (:func:`sublayer_input` / :func:`sublayer_result`, which
    ``apply_fn`` and ``advance`` both call, for the plain residual
    too).  Without it the block is what it was, program and parameter
    tree.

    ``pre_routed``: the expert layer's ROUTER multiplies the block's
    input ``x`` — un-normed, before the operator — while its experts
    read ``norm_2 h`` as ever: ``x`` rides across the operator sublayer
    to ``DroplessMoE.routed(..., scores_from=x)``, in ``apply_fn`` and
    ``advance`` alike."""

    def __init__(self, operator, ffn, embed_dim: int, norm_eps: float,
                 param_dtype: Optional[str] = None,
                 hyper: Optional[Callable] = None,
                 pre_routed: bool = False):
        children = [nn.RMSNorm(embed_dim, eps=norm_eps), operator,
                    nn.RMSNorm(embed_dim, eps=norm_eps), ffn]
        if hyper is not None:
            children += [hyper(), hyper()]
        super().__init__(*(_held_in(m, param_dtype) for m in children))
        #: the two hyper-connections (operator's, FFN's), or None
        self.hyper = tuple(self.modules[4:6]) if hyper is not None else None
        self.ffn_kind = "moe" if isinstance(ffn, DroplessMoE) else "dense"
        self.is_moe = self.ffn_kind == "moe"
        #: the router reads the block's INPUT, not the experts'
        self.pre_routed = bool(pre_routed)
        if self.pre_routed and (self.hyper or not self.is_moe):
            raise ValueError(
                "pre_routed: the router of an expert layer reads the "
                "block's input under the plain residual (no published "
                "model routes ahead of a hyper-connection, and a dense "
                "FFN has no router)")

    @property
    def moe(self) -> DroplessMoE:
        return self.modules[3]

    def operator_scope(self):
        """The device scope of the operator sublayer: ``block.conv``
        where no attention runs, ``block.attention`` elsewhere."""
        if getattr(self.modules[1], "kind", None) == "short_conv":
            return jax.named_scope("block.conv")
        return jax.named_scope("block.attention")

    @property
    def streams(self) -> int:
        """Streams of the state the block carries: its
        hyper-connections' ``n``, or 0 for the plain residual (one
        vector a token)."""
        return self.hyper[0].n_streams if self.hyper else 0

    def apply_fn(self, params, buffers, x, training, rng):
        block_input = x
        for i in (0, 1):        # the operator, then the FFN
            n, co = sublayer_input(self, params, i, x)
            if i == 1 and getattr(self, "pre_routed", False):
                D = n.shape[-1]
                y, _ = self.moe.routed(
                    params["3"], n.reshape(-1, D),
                    scores_from=block_input.reshape(-1, D))
                y = y.reshape(n.shape)
            else:
                with self.operator_scope() if i == 0 else nullcontext():
                    y, _ = self.modules[2 * i + 1].apply_fn(
                        params[str(2 * i + 1)], buffers[str(2 * i + 1)], n,
                        training, None)
            x = sublayer_result(self, i, x, y, co)
        return x, buffers

    # -- decode: the state between tokens, and Tq tokens against it ------
    @property
    def counters(self) -> dict:
        """Leaf of the decode state -> the statistic a call returns."""
        return {**({"moe_counts": "moe_counts"} if self.is_moe else {}),
                **({"mhc_err": "mhc_sinkhorn_err"} if self.hyper else {})}

    @property
    def state_doc(self) -> str:
        if self.hyper:
            return (f"{type(self.hyper[0]).__name__} makes the residual of "
                    f"{type(self).__name__} {self.streams} streams a token, "
                    "with a counter a layer that has no batch axis")
        if getattr(self, "pre_routed", False):
            return ("its layers differ in what they see (a window's ring "
                    "beside a position-free full layer) and its router "
                    "reads the block's input, whose counts it keeps beside "
                    "the K/V")
        op = self.modules[1]
        return (f"{type(op).__name__} "
                + getattr(op, "state_doc", "keeps a state of its own"))

    def replicate(self, h):
        """The state the first layer takes of the embedding ``h``."""
        return self.hyper[0].replicate(h) if self.hyper else h

    def reduce(self, h):
        """What the final norm takes of the last layer's state."""
        return self.hyper[0].reduce(h) if self.hyper else h

    def state_init(self, batch: int, dtype, length: int, int8: bool = False):
        state = fresh_state(self.modules[1], batch, dtype, length, int8)
        if self.is_moe:
            state["moe_counts"] = jnp.zeros((batch, self.moe.held[1]),
                                            jnp.int32)
        if self.hyper:          # a counter too: one number a layer
            state["mhc_err"] = jnp.zeros((), jnp.float32)
        return state

    def footprint(self, batch: int, dtype, length: int, int8: bool = False):
        return {**footprint(self.modules[1], batch, dtype, length, int8),
                **(self.moe.decode_plan(batch, dtype) if self.is_moe
                   else {})}

    def prefill_plan(self, tokens: int, dtype):
        """The experts' arm over a prompt pass of ``tokens`` tokens."""
        return self.moe.prefill_plan(tokens, dtype) if self.is_moe else {}

    def advance(self, params, h, state, pos):
        """ONE form for every operator, every FFN and both residuals:
        what a sublayer reads of ``h`` and how its result goes back are
        :func:`sublayer_input` / :func:`sublayer_result`'s."""
        block_input = h
        x, co = sublayer_input(self, params, 0, h)
        with self.operator_scope():
            a, wrote = advance(self.modules[1], params["1"], x, state, pos)
        state = {**state, **wrote}
        h = sublayer_result(self, 0, h, a, co)
        x, co2 = sublayer_input(self, params, 1, h)
        if self.is_moe:
            B, Tq, D = x.shape
            y, counts = self.moe.routed(
                params["3"], x.reshape(B * Tq, D), batch=B,
                scores_from=(block_input.reshape(B * Tq, D)
                             if getattr(self, "pre_routed", False)
                             else None))
            h = sublayer_result(self, 1, h, y.reshape(B, Tq, D), co2)
            state["moe_counts"] = state["moe_counts"] + counts
        else:
            y, _ = self.modules[3].apply_fn(params["3"], {}, x, False, None)
            h = sublayer_result(self, 1, h, y, co2)
        if co is not None:
            # the counter of the call: how far from doubly stochastic
            # the worst residual map of either sublayer was
            state["mhc_err"] = jnp.maximum(
                state["mhc_err"], jnp.maximum(co.err, co2.err))
        return h, state


#: the name the block had while latent attention was its only operator
LatentMoEBlock = SequentialMoEBlock


class SequentialMoELM(TiedHeadTrees, CausalLM, Container):
    """Decoder-only causal LM over 1-based token ids [batch, seq].

    ``operators`` and ``ffns`` are one zero-argument FACTORY a layer
    each: a layer's modules are made inside the device draw and cast to
    ``param_dtype`` child by child, so neither a block nor the model is
    ever whole in float32.  ``pre_routed``: every block's router reads
    the block's input (:class:`SequentialMoEBlock`).  ``hyper`` (a
    factory of a ``HyperConnection``) gives EVERY block a
    hyper-connected residual: the embedding is
    repeated into the streams and they are summed before the final
    norm.  ``draw_weights=False`` builds the model WITHOUT drawing: every
    matrix zeros, for a caller that sets the weights next (a checkpoint,
    seeded leaves) and should not pay the draw's programs first."""

    def __init__(self, vocab_size: int, embed_dim: int,
                 operators: Sequence[Callable], ffns: Sequence[Callable],
                 tied_head: bool = False, max_len: int = 2048,
                 norm_eps: float = 1e-5, output: str = "log_probs",
                 init_std: float = 0.02, param_dtype: Optional[str] = None,
                 hyper: Optional[Callable] = None,
                 draw_weights: bool = True, pre_routed: bool = False):
        if output not in ("log_probs", "logits"):
            raise ValueError(f"output {output!r} not in (log_probs, logits)")
        if len(operators) != len(ffns):
            raise ValueError(f"{len(operators)} operators for {len(ffns)} "
                             "FFNs: a layer has one of each")
        super().__init__()
        self._output_mode = output
        self.vocab_size = vocab_size
        self.embed_dim = embed_dim
        self.max_len = max_len
        self.use_rope = True            # no position table to add
        self.tied_head = bool(tied_head)
        self.param_dtype = (jnp.dtype(param_dtype).name if param_dtype
                            else None)
        with device_draw(), (nullcontext() if draw_weights else no_draw()):
            embed = nn.LookupTable(vocab_size, embed_dim)
            embed.set_init_method(RandomNormal(0.0, init_std))
            embed.reset()
            self.add(_held_in(embed, self.param_dtype))
            for make_operator, make_ffn in zip(operators, ffns):
                self.add(SequentialMoEBlock(make_operator(), make_ffn(),
                                            embed_dim, norm_eps,
                                            self.param_dtype, hyper,
                                            pre_routed))
            self.add(_held_in(nn.RMSNorm(embed_dim, eps=norm_eps),
                              self.param_dtype))
            self.add(_held_in(LogitHead(embed_dim, vocab_size, init_std,
                                        tied=self.tied_head),
                              self.param_dtype))
        blocks = self.modules[1:-2]
        #: "dense" or "moe" a layer
        self.layer_kinds = tuple(b.ffn_kind for b in blocks)

    def set_param_tree(self, tree):
        super().set_param_tree(hold_floats(tree, self.param_dtype,
                                           keep=FLOAT32_LEAVES))

    def reset(self):
        with device_draw():
            super().reset()
        self.set_param_tree(self.param_tree())
        return self

    def apply_fn(self, params, buffers, x, training, rng):
        h, last = x, len(self.modules) - 1
        hyper = self.modules[1].hyper
        for i, m in enumerate(self.modules):
            # a tied head owns no leaf: it is handed the embedding's
            tied = self.tied_head and i == last
            if hyper and i == last - 1:     # before the final norm
                h = hyper[0].reduce(h)
            h, _ = m.apply_fn(params["0" if tied else str(i)],
                              {} if tied else buffers[str(i)], h, training,
                              None)
            if hyper and i == 0:            # after the embedding
                h = hyper[0].replicate(h)
        if self._output_mode == "logits":
            return h, buffers
        return jax.nn.log_softmax(h, axis=-1), buffers


def _ffn_factories(num_layers: int, first_dense: int, embed_dim: int,
                   mlp_dim: int, init_std: float, experts: Callable) -> list:
    """A dense SwiGLU of ``mlp_dim`` for the first ``first_dense``
    layers, what ``experts`` makes after them."""
    if not 0 <= first_dense <= num_layers:
        raise ValueError(f"first_dense {first_dense} not in "
                         f"[0, num_layers={num_layers}]")
    return ([lambda: GatedFFN(embed_dim, mlp_dim, init_std)] * first_dense
            + [experts] * (num_layers - first_dense))


class LatentMoELM(SequentialMoELM):
    """``glm4_moe_lite``: latent attention in every layer, a dense FFN
    in the first ``first_dense`` layers and experts (beside ``n_shared``
    shared ones) after them, an untied head."""

    def __init__(self, vocab_size: int, embed_dim: int, num_heads: int,
                 q_rank: int, kv_rank: int, nope_dim: int, rope_dim: int,
                 v_dim: int, mlp_dim: int, expert_dim: int, num_layers: int,
                 n_experts: int, top_k: int, first_dense: int = 1,
                 n_shared: int = 1, held: Optional[Sequence[int]] = None,
                 routed_scale: float = 1.0, renormalize: bool = True,
                 max_len: int = 2048, rope_theta: float = 10000.0,
                 norm_eps: float = 1e-5, seq_strategy: str = "dense",
                 output: str = "log_probs", init_std: float = 0.02,
                 param_dtype: Optional[str] = None,
                 rope_scaling: Optional[dict] = None,
                 hyper: Optional[Callable] = None,
                 draw_weights: bool = True):
        def attention():
            return nn.LatentAttention(
                embed_dim, num_heads, q_rank, kv_rank, nope_dim, rope_dim,
                v_dim, rope_theta=rope_theta, norm_eps=norm_eps,
                seq_strategy=seq_strategy, init_std=init_std,
                rope_scaling=rope_scaling)

        def experts():
            return DroplessMoE(
                embed_dim, expert_dim, n_experts, top_k=top_k,
                scoring="sigmoid", renormalize=renormalize,
                n_shared=n_shared,
                held=tuple(held) if held is not None else None,
                init_std=init_std, score_bias=True,
                routed_scale=routed_scale)

        super().__init__(
            vocab_size, embed_dim, [attention] * num_layers,
            _ffn_factories(num_layers, first_dense, embed_dim, mlp_dim,
                           init_std, experts),
            max_len=max_len, norm_eps=norm_eps, output=output,
            init_std=init_std, param_dtype=param_dtype, hyper=hyper,
            draw_weights=draw_weights)


class HyperLatentMoELM(LatentMoELM):
    """``xing4_0``: :class:`LatentMoELM`'s layers (``rope_scaling``: the
    configuration's YaRN object) around a residual of ``hc_mult``
    streams — every sublayer wrapped in a hyper-connection whose
    residual map takes ``hc_sinkhorn_iters`` Sinkhorn sweeps with
    ``hc_eps`` in each sum and its logits clamped to ``h_res_clamp``;
    the RMS over the streams uses the model's ``norm_eps``."""

    def __init__(self, vocab_size: int, embed_dim: int, *args,
                 hc_mult: int = 4, hc_sinkhorn_iters: int = 20,
                 hc_eps: float = 1e-6, h_res_clamp=(-30.0, 30.0),
                 norm_eps: float = 1e-5, init_std: float = 0.02, **kwargs):
        def hyper():
            return HyperConnection(
                embed_dim, hc_mult, hc_sinkhorn_iters, hc_eps, norm_eps,
                h_res_clamp, init_std)

        super().__init__(vocab_size, embed_dim, *args, hyper=hyper,
                         norm_eps=norm_eps, init_std=init_std, **kwargs)
        self.hc_mult = int(hc_mult)


#: ``layer_types`` of a ``lfm2_moe`` configuration
OPERATOR_TYPES = ("conv", "full_attention")


class ShortConvMoELM(SequentialMoELM):
    """``lfm2_moe``: ``layer_types[i]`` says whether layer ``i``'s
    operator is the gated short convolution (``"conv"``; kernel
    ``conv_kernel``) or grouped-query attention with per-head QK-norm
    and rotation by halves (``"full_attention"``); a dense FFN in the
    first ``first_dense`` layers, experts without a shared one after
    them (the chosen scores renormalised with ``renorm_eps`` in the
    sum); embedding and head are one matrix."""

    def __init__(self, vocab_size: int, embed_dim: int, num_heads: int,
                 num_kv_heads: int, head_dim: int, mlp_dim: int,
                 expert_dim: int, layer_types: Sequence[str],
                 n_experts: int, top_k: int, first_dense: int = 2,
                 conv_kernel: int = 3,
                 held: Optional[Sequence[int]] = None,
                 routed_scale: float = 1.0, renormalize: bool = True,
                 renorm_eps: float = 1e-6, max_len: int = 2048,
                 rope_theta: float = 1000000.0, norm_eps: float = 1e-5,
                 seq_strategy: str = "dense", output: str = "log_probs",
                 init_std: float = 0.02, param_dtype: Optional[str] = None):
        unknown = sorted(set(layer_types) - set(OPERATOR_TYPES))
        if unknown:
            raise ValueError(f"layer_types {unknown} not in {OPERATOR_TYPES}")

        def attention():
            mha = nn.MultiHeadAttention(
                embed_dim, num_heads, causal=True, with_bias=False,
                seq_strategy=seq_strategy, num_kv_heads=num_kv_heads,
                head_dim=head_dim, rope=True, rope_theta=rope_theta,
                qk_norm=True, norm_eps=norm_eps)
            mha.set_init_method(RandomNormal(0.0, init_std))
            return mha.reset()

        def conv():
            return nn.GatedShortConv(embed_dim, conv_kernel, init_std)

        def experts():
            return DroplessMoE(
                embed_dim, expert_dim, n_experts, top_k=top_k,
                scoring="sigmoid", renormalize=renormalize,
                held=tuple(held) if held is not None else None,
                init_std=init_std, score_bias=True,
                routed_scale=routed_scale, renorm_eps=renorm_eps)

        super().__init__(
            vocab_size, embed_dim,
            [conv if t == "conv" else attention for t in layer_types],
            _ffn_factories(len(layer_types), first_dense, embed_dim,
                           mlp_dim, init_std, experts),
            tied_head=True, max_len=max_len, norm_eps=norm_eps,
            output=output, init_std=init_std, param_dtype=param_dtype)
        self.layer_types = tuple(layer_types)


class PreRoutedMoELM(SequentialMoELM):
    """``smallthinker``: layer ``i``'s operator is grouped-query
    attention without biases or QK-norm, rotated by halves where
    ``rope_layout[i]`` and seeing the last ``window`` keys where
    ``window_layout[i]`` (a layer with neither is global and has NO
    positions at all); EVERY block's FFN is an expert layer whose
    router reads the block's input (``pre_routed``) — a softmax over
    the ``top_k`` chosen logits, which ``scoring="softmax"`` with
    ``renormalize`` is term for term — over ReLU-gated experts, no
    shared expert, no selection bias; an untied head."""

    def __init__(self, vocab_size: int, embed_dim: int, num_heads: int,
                 num_kv_heads: int, head_dim: int, expert_dim: int,
                 rope_layout: Sequence[int], window_layout: Sequence[int],
                 window: int, n_experts: int, top_k: int,
                 held: Optional[Sequence[int]] = None, max_len: int = 2048,
                 rope_theta: float = 1500000.0, norm_eps: float = 1e-6,
                 seq_strategy: str = "dense", output: str = "log_probs",
                 init_std: float = 0.02, param_dtype: Optional[str] = None,
                 draw_weights: bool = True):
        if len(rope_layout) != len(window_layout):
            raise ValueError(f"rope_layout names {len(rope_layout)} layers, "
                             f"window_layout {len(window_layout)}")

        def attention(rotated, windowed):
            def make():
                mha = nn.MultiHeadAttention(
                    embed_dim, num_heads, causal=True, with_bias=False,
                    seq_strategy=seq_strategy, num_kv_heads=num_kv_heads,
                    head_dim=head_dim, rope=True if rotated else None,
                    rope_theta=rope_theta,
                    window=window if windowed else None)
                mha.set_init_method(RandomNormal(0.0, init_std))
                return mha.reset()
            return make

        def experts():
            return DroplessMoE(
                embed_dim, expert_dim, n_experts, top_k=top_k,
                scoring="softmax", renormalize=True,
                held=tuple(held) if held is not None else None,
                init_std=init_std, activation="relu")

        super().__init__(
            vocab_size, embed_dim,
            [attention(r, w) for r, w in zip(rope_layout, window_layout)],
            [experts] * len(rope_layout), max_len=max_len,
            norm_eps=norm_eps, output=output, init_std=init_std,
            param_dtype=param_dtype, draw_weights=draw_weights,
            pre_routed=True)
        self.rope_layout = tuple(int(r) for r in rope_layout)
        self.window_layout = tuple(int(w) for w in window_layout)
        self.window = int(window)
