"""ParallelMoELM — a causal LM of PARALLEL blocks whose FFN is a
mixture of experts (Cohere's ``cohere2_moe`` architecture: Command A+).

One block, with ``n = LN(x)`` — ONE LayerNorm without a bias leaf —

    y = x + Attn_l(n) + MoE(n)

attention and the expert layer read the same normed input and both land
on the residual.  ``Attn_l`` is grouped-query attention whose KIND
depends on the layer: a ``"sliding"`` layer sees the last ``window``
positions and rotates q and k by INTERLEAVED RoPE (pairs ``(2i, 2i+1)``),
a ``"full"`` layer is causal over everything and has NO positions at
all.  ``MoE`` is :class:`~bigdl_tpu.parallel.moe.DroplessMoE`: sigmoid
or softmax scores over ALL experts, the ``top_k`` largest renormalised,
SwiGLU experts of which this model may hold a SHARE (``held``), the mean
of ``n_shared`` shared experts added.  Embedding and head are one
matrix.

A ``Container`` with ``TransformerLM``'s child layout — ``0`` the
embedding, ``1..L`` the blocks (children ``0`` norm, ``1`` attention,
``2`` the expert layer), ``L+1`` the final LayerNorm, ``L+2`` a head
that owns no leaf and READS the embedding's — so the generation
builder, the server and the optimizers take it as they take the dense
model.  ``generate`` keeps a K/V cache per layer whose length depends on
the layer's kind (``nn.MultiHeadAttention.state_init``): ``min(T_cache,
window)`` positions, written round-robin, for a sliding layer; beside
it ``moe_counts`` ``[B, held]``, the assignments each held expert took
from each row, which a generate call returns beside the tokens on
request (``return_stats=True``).  ``param_dtype`` and the device draw
as in ``HybridMambaLM``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.attention import advance
from ..nn.initialization import RandomNormal, device_draw
from ..nn.module import Container, TensorModule, hold_floats
from ..parallel.moe import DroplessMoE
from .generate import CausalLM

LAYER_KINDS = ("sliding", "full")


def _held_in(module, dtype):
    """``module`` with its floating leaves cast to ``dtype`` as soon as
    it exists: child by child, so neither a block nor the model is ever
    whole in float32."""
    module.set_param_tree(hold_floats(module.param_tree(), dtype))
    return module


def layer_kinds(num_layers: int, layer_switch: int = 4,
                local_first: bool = True) -> tuple:
    """The published interleave: periods of ``layer_switch`` layers, one
    of them ``"full"`` — the last of a period under ``local_first``
    (``order_of_interleaved_layers: local_attn_first``), else the
    first."""
    full_at = layer_switch - 1 if local_first else 0
    return tuple("full" if i % layer_switch == full_at else "sliding"
                 for i in range(num_layers))


class TiedHead(TensorModule):
    """The output projection of a model whose head IS its embedding: it
    owns no leaf; whoever applies it hands it the embedding's
    (``{"weight": [vocab, embed]}``)."""

    def _apply(self, params, buffers, x, training, rng):
        return jnp.dot(x, params["weight"].T.astype(x.dtype)), buffers


class TiedHeadTrees:
    """Mixed into a ``Container`` whose LAST child is a head that owns
    no leaf where ``self.tied_head`` holds: the head's (empty) entry is
    left out of the parameter-shaped trees, so a tree names exactly the
    leaves a checkpoint or a reference has, and is accepted back with
    or without it."""

    tied_head = True

    def _leafed(self, tree):
        if not self.tied_head:
            return tree
        return {k: v for k, v in tree.items()
                if k != str(len(self.modules) - 1)}

    def _with_head(self, tree):
        if not self.tied_head:
            return tree
        return {**tree, str(len(self.modules) - 1): {}}

    def param_tree(self):
        return self._leafed(super().param_tree())

    def grad_tree(self):
        return self._leafed(super().grad_tree())

    def gradient_scale_tree(self):
        return self._leafed(super().gradient_scale_tree())

    def set_grad_tree(self, tree):
        super().set_grad_tree(self._with_head(tree))

    def set_param_tree(self, tree):
        super().set_param_tree(self._with_head(tree))


class ParallelMoEBlock(Container):
    """``x + Attn(LN(x)) + MoE(LN(x))``.  Children: ``0`` the LayerNorm
    (no bias), ``1`` attention, ``2`` the expert layer."""

    is_moe = True
    #: leaf of the decode state -> the statistic a call returns of it
    counters = {"moe_counts": "moe_counts"}
    state_doc = ("its layers differ in what they see (a window beside full "
                 "attention) and run attention and experts on one norm, "
                 "whose counts it keeps beside the K/V")

    def __init__(self, embed_dim: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, expert_dim: int, n_experts: int, top_k: int,
                 attention: str = "full", window: Optional[int] = None,
                 rope_theta: float = 10000.0, norm_eps: float = 1e-5,
                 scoring: str = "sigmoid", renormalize: bool = True,
                 n_shared: int = 0, held: Optional[tuple] = None,
                 seq_strategy: str = "dense", init_std: float = 0.02,
                 param_dtype: Optional[str] = None):
        if attention not in LAYER_KINDS:
            raise ValueError(f"attention {attention!r} not in {LAYER_KINDS}")
        sliding = attention == "sliding"
        if sliding and not window:
            raise ValueError("a sliding layer needs its window")

        mha = nn.MultiHeadAttention(
            embed_dim, num_heads, causal=True, with_bias=False,
            seq_strategy=seq_strategy, num_kv_heads=num_kv_heads,
            head_dim=head_dim, rope="interleaved" if sliding else None,
            rope_theta=rope_theta, window=window if sliding else None)
        mha.set_init_method(RandomNormal(0.0, init_std))
        mha.reset()
        super().__init__(
            _held_in(nn.LayerNorm(embed_dim, eps=norm_eps, with_bias=False),
                     param_dtype),
            _held_in(mha, param_dtype),
            _held_in(DroplessMoE(embed_dim, expert_dim, n_experts,
                                 top_k=top_k, scoring=scoring,
                                 renormalize=renormalize, n_shared=n_shared,
                                 held=held, init_std=init_std),
                     param_dtype))
        self.attention = attention

    @property
    def moe(self) -> DroplessMoE:
        return self.modules[2]

    def apply_fn(self, params, buffers, x, training, rng):
        def run(i, v):
            return self.modules[i].apply_fn(params[str(i)], buffers[str(i)],
                                            v, training, None)[0]

        n = run(0, x)
        with jax.named_scope("block.attention"):
            a = run(1, n)
        return x + a + run(2, n), buffers

    # -- decode: the state between tokens, and Tq tokens against it ------
    def state_init(self, batch: int, dtype, length: int, int8: bool = False):
        return {**self.modules[1].state_init(batch, dtype, length, int8),
                "moe_counts": jnp.zeros((batch, self.moe.held[1]),
                                        jnp.int32)}

    def footprint(self, batch: int, dtype, length: int, int8: bool = False):
        """The attention's — its K/V also by KIND of layer, which the
        operator itself says (``MultiHeadAttention.footprint``: a
        sliding layer keeps ``min(positions, window)``) — and the
        experts'."""
        return {**self.modules[1].footprint(batch, dtype, length, int8),
                **self.moe.decode_plan(batch, dtype)}

    def prefill_plan(self, tokens: int, dtype):
        """The experts' arm over a prompt pass of ``tokens`` tokens."""
        return self.moe.prefill_plan(tokens, dtype)

    def advance(self, params, h, state, pos):
        """Attention and the expert layer read the SAME normed input."""
        n, _ = self.modules[0].apply_fn(params["0"], {}, h, False, None)
        with jax.named_scope("block.attention"):
            a, state = advance(self.modules[1], params["1"], n, state, pos)
        B, Tq, D = n.shape
        m, counts = self.moe.routed(params["2"], n.reshape(B * Tq, D),
                                    batch=B)
        return (h + a + m.reshape(B, Tq, D),
                {**state, "moe_counts": state["moe_counts"] + counts})


class ParallelMoELM(TiedHeadTrees, CausalLM, Container):
    """Decoder-only causal LM over 1-based token ids [batch, seq]."""

    def __init__(self, vocab_size: int, embed_dim: int, num_heads: int,
                 num_kv_heads: int, head_dim: int, expert_dim: int,
                 num_layers: int, n_experts: int, top_k: int,
                 n_shared: int = 0, held: Optional[Sequence[int]] = None,
                 scoring: str = "sigmoid", renormalize: bool = True,
                 window: int = 4096, layer_switch: int = 4,
                 local_first: bool = True, max_len: int = 2048, rope_theta: float = 10000.0,
                 norm_eps: float = 1e-5, logit_scale: float = 1.0,
                 seq_strategy: str = "dense", output: str = "log_probs",
                 init_std: float = 0.02,
                 param_dtype: Optional[str] = None):
        if output not in ("log_probs", "logits"):
            raise ValueError(f"output {output!r} not in (log_probs, logits)")
        super().__init__()
        self._output_mode = output
        self.vocab_size = vocab_size
        self.embed_dim = embed_dim
        self.max_len = max_len
        self.use_rope = True            # no position table to add
        self.logit_scale = float(logit_scale)
        self.param_dtype = (jnp.dtype(param_dtype).name if param_dtype
                            else None)
        kinds = self.layer_types = layer_kinds(num_layers, layer_switch,
                                               local_first)

        with device_draw():
            embed = nn.LookupTable(vocab_size, embed_dim)
            embed.set_init_method(RandomNormal(0.0, init_std))
            embed.reset()
            self.add(_held_in(embed, self.param_dtype))
            for kind in kinds:
                self.add(ParallelMoEBlock(
                    embed_dim, num_heads, num_kv_heads, head_dim,
                    expert_dim, n_experts, top_k, attention=kind,
                    window=window, rope_theta=rope_theta,
                    norm_eps=norm_eps, scoring=scoring,
                    renormalize=renormalize, n_shared=n_shared,
                    held=tuple(held) if held is not None else None,
                    seq_strategy=seq_strategy, init_std=init_std,
                    param_dtype=self.param_dtype))
            self.add(_held_in(nn.LayerNorm(embed_dim, eps=norm_eps,
                                           with_bias=False),
                              self.param_dtype))
            self.add(TiedHead())

    def set_param_tree(self, tree):
        super().set_param_tree(hold_floats(tree, self.param_dtype))

    def reset(self):
        with device_draw():
            super().reset()
        self.set_param_tree(self.param_tree())
        return self

    def apply_fn(self, params, buffers, x, training, rng):
        n = len(self.modules)
        h, _ = self.modules[0].apply_fn(params["0"], buffers["0"], x,
                                        training, None)
        for i in range(1, n - 1):
            h, _ = self.modules[i].apply_fn(params[str(i)], buffers[str(i)],
                                            h, training, None)
        h, _ = self.modules[n - 1].apply_fn(params["0"], {}, h, training,
                                            None)
        if self.logit_scale != 1.0:
            h = h * self.logit_scale
        if self._output_mode == "logits":
            return h, buffers
        return jax.nn.log_softmax(h, axis=-1), buffers
