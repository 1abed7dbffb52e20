"""SequentialMoELM — a causal LM of SEQUENTIAL pre-norm blocks whose
token-mixing OPERATOR is given per layer and whose FFN is a dense SwiGLU
in the first layers and a mixture of experts after them.  RMSNorm
throughout and no bias anywhere:

    h = x + Op_i(RMSNorm_1(x))
    y = h + FFN_i(RMSNorm_2(h))

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

Two published families are constructors of it, each building the
operator and FFN lists from its configuration's own numbers:

* :class:`LatentMoELM` — Zhipu's ``glm4_moe_lite`` (GLM-4.7-Flash;
  DeepSeek-V2/V3's block): latent attention in every layer, an untied
  head.
* :class:`ShortConvMoELM` — Liquid's ``lfm2_moe`` (LFM2-24B-A2B): the
  operator follows a published list (``layer_types``: ``"conv"`` or
  ``"full_attention"``), attention is grouped-query with RMSNorm over
  each query and key head, no shared expert, a tied head.

A ``Container`` with ``TransformerLM``'s child layout — ``0`` the
embedding, ``1..L`` the blocks (children ``0`` RMSNorm, ``1`` the
operator, ``2`` RMSNorm, ``3`` the FFN), ``L+1`` the final RMSNorm,
``L+2`` the head — so the generation builder, the server and the
optimizers take it as they take the dense model.  What ``generate``
keeps a layer depends on its operator (``models/generate.py``): the
latent ``c_kv`` and the rotated shared key of every position and nothing
by head; per-head K and V; or, for a short convolution, the last
``kernel - 1`` values of its gated input and NOTHING that grows with the
context.  ``param_dtype`` and the device draw as in ``HybridMambaLM``;
the selection bias stays float32.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.initialization import IN_OUT, RandomNormal, device_draw
from ..nn.module import Container, TensorModule, hold_floats
from ..parallel.moe import FLOAT32_LEAVES, DroplessMoE
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


class SequentialMoEBlock(Container):
    """``h = x + Op(norm_1 x); y = h + FFN(norm_2 h)``.  Children, in
    the order the generation builder relies on: ``0`` RMSNorm, ``1`` the
    operator (latent or grouped-query attention, or a gated short
    convolution), ``2`` RMSNorm, ``3`` the FFN (``ffn_kind``:
    ``"dense"`` a :class:`GatedFFN`, ``"moe"`` a ``DroplessMoE``)."""

    kind = "sequential_moe"

    def __init__(self, operator, ffn, embed_dim: int, norm_eps: float,
                 param_dtype: Optional[str] = None):
        super().__init__(
            *(_held_in(m, param_dtype)
              for m in (nn.RMSNorm(embed_dim, eps=norm_eps), operator,
                        nn.RMSNorm(embed_dim, eps=norm_eps), ffn)))
        self.ffn_kind = "moe" if isinstance(ffn, DroplessMoE) else "dense"
        self.is_moe = self.ffn_kind == "moe"
        if not self.is_moe:
            self.mlp_kind = "gated"     # generate._ffn_sublayer's arm

    @property
    def moe(self) -> DroplessMoE:
        return self.modules[3]

    def operator_scope(self):
        """The device scope of the operator sublayer: ``block.conv``
        where no attention runs, ``block.attention`` elsewhere."""
        if getattr(self.modules[1], "kind", None) == "short_conv":
            return jax.named_scope("block.conv")
        return jax.named_scope("block.attention")

    def apply_fn(self, params, buffers, x, training, rng):
        def run(i, v):
            return self.modules[i].apply_fn(params[str(i)], buffers[str(i)],
                                            v, training, None)[0]

        with self.operator_scope():
            h = x + run(1, run(0, x))
        return h + run(3, run(2, h)), buffers


#: the name the block had while latent attention was its only operator
LatentMoEBlock = SequentialMoEBlock


class SequentialMoELM(TiedHeadTrees, Container):
    """Decoder-only causal LM over 1-based token ids [batch, seq].

    ``operators`` and ``ffns`` are one zero-argument FACTORY a layer
    each: a layer's modules are made inside the device draw and cast to
    ``param_dtype`` child by child, so neither a block nor the model is
    ever whole in float32."""

    def __init__(self, vocab_size: int, embed_dim: int,
                 operators: Sequence[Callable], ffns: Sequence[Callable],
                 tied_head: bool = False, max_len: int = 2048,
                 norm_eps: float = 1e-5, output: str = "log_probs",
                 init_std: float = 0.02, param_dtype: Optional[str] = None):
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
        with device_draw():
            embed = nn.LookupTable(vocab_size, embed_dim)
            embed.set_init_method(RandomNormal(0.0, init_std))
            embed.reset()
            self.add(_held_in(embed, self.param_dtype))
            for make_operator, make_ffn in zip(operators, ffns):
                self.add(SequentialMoEBlock(make_operator(), make_ffn(),
                                            embed_dim, norm_eps,
                                            self.param_dtype))
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

    def generate(self, prompt_ids, max_new: int, rng=None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, compute_dtype=None,
                 eos_id=None, pad_id=None):
        """Autoregressive decode (``TransformerLM.generate``'s
        contract) through each layer's own cache: a latent layer's
        prefill expands it to per-head K and V once and a decode step
        never does; a short convolution carries its tail."""
        from .generate import cached_generate

        return cached_generate(self, compute_dtype)(
            self.param_tree(), prompt_ids, max_new, rng=rng,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_id=eos_id, pad_id=pad_id)

    def apply_fn(self, params, buffers, x, training, rng):
        h, last = x, len(self.modules) - 1
        for i, m in enumerate(self.modules):
            # a tied head owns no leaf: it is handed the embedding's
            tied = self.tied_head and i == last
            h, _ = m.apply_fn(params["0" if tied else str(i)],
                              {} if tied else buffers[str(i)], h, training,
                              None)
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
                 param_dtype: Optional[str] = None):
        def attention():
            return nn.LatentAttention(
                embed_dim, num_heads, q_rank, kv_rank, nope_dim, rope_dim,
                v_dim, rope_theta=rope_theta, norm_eps=norm_eps,
                seq_strategy=seq_strategy, init_std=init_std)

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
            init_std=init_std, param_dtype=param_dtype)


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
