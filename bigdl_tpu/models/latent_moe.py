"""LatentMoELM — a causal LM of SEQUENTIAL pre-norm blocks whose
attention is latent (MLA) and whose FFN is a dense SwiGLU in the first
layers and a mixture of experts after them (Zhipu's ``glm4_moe_lite``
architecture: GLM-4.7-Flash; DeepSeek-V2/V3's block).

One block, RMSNorm throughout and no bias anywhere:

    h = x + Attn(RMSNorm_1(x))
    y = h + FFN_i(RMSNorm_2(h))

``Attn`` is :class:`~bigdl_tpu.nn.attention.LatentAttention`.  ``FFN_i``
is :class:`GatedFFN` (``down(silu(gate n) * up n)`` at ``mlp_dim``) for
the first ``first_dense`` layers and
:class:`~bigdl_tpu.parallel.moe.DroplessMoE` after them: sigmoid scores
over ALL experts, a per-expert correction bias that enters the selection
only (``score_bias``), the ``top_k`` chosen scores renormalised and
times ``routed_scale``, SwiGLU experts of which this model may hold a
SHARE (``held``), ``n_shared`` shared experts added.  The head is a
matrix of its own and gives float32 logits.

A ``Container`` with ``TransformerLM``'s child layout — ``0`` the
embedding, ``1..L`` the blocks (children ``0`` RMSNorm, ``1`` attention,
``2`` RMSNorm, ``3`` the FFN), ``L+1`` the final RMSNorm, ``L+2`` the
head — so the generation builder, the server and the optimizers take it
as they take the dense model.  ``generate`` keeps, a layer, the latent
``c_kv`` and the rotated shared key ``k_rope`` of every position and
nothing by head (``models/generate.py``).  ``param_dtype`` and the
device draw as in ``HybridMambaLM``; the selection bias stays float32.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.initialization import IN_OUT, RandomNormal, device_draw
from ..nn.module import Container, TensorModule, hold_floats
from ..parallel.moe import FLOAT32_LEAVES, DroplessMoE


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
    """An untied output projection ``[vocab, embed]`` whose logits are
    float32 whatever dtype the matrix is held in (the product
    accumulates there and is not rounded back)."""

    def __init__(self, embed_dim: int, vocab_size: int,
                 init_std: float = 0.02):
        super().__init__()
        self.embed_dim, self.vocab_size = embed_dim, vocab_size
        self.init_std = float(init_std)
        self.reset()

    def reset(self):
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


class LatentMoEBlock(Container):
    """``h = x + Attn(norm_1 x); y = h + FFN(norm_2 h)``.  Children, in
    the order the generation builder relies on: ``0`` RMSNorm, ``1``
    latent attention, ``2`` RMSNorm, ``3`` the FFN (``ffn``:
    ``"dense"`` a :class:`GatedFFN`, ``"moe"`` a ``DroplessMoE``)."""

    kind = "latent_moe"

    def __init__(self, attention: nn.LatentAttention, ffn, embed_dim: int,
                 norm_eps: float, param_dtype: Optional[str] = None):
        super().__init__(
            *(_held_in(m, param_dtype)
              for m in (nn.RMSNorm(embed_dim, eps=norm_eps), attention,
                        nn.RMSNorm(embed_dim, eps=norm_eps), ffn)))
        self.ffn_kind = "moe" if isinstance(ffn, DroplessMoE) else "dense"
        self.is_moe = self.ffn_kind == "moe"

    @property
    def moe(self) -> DroplessMoE:
        return self.modules[3]

    def apply_fn(self, params, buffers, x, training, rng):
        def run(i, v):
            return self.modules[i].apply_fn(params[str(i)], buffers[str(i)],
                                            v, training, None)[0]

        with jax.named_scope("block.attention"):
            h = x + run(1, run(0, x))
        return h + run(3, run(2, h)), buffers


class LatentMoELM(Container):
    """Decoder-only causal LM over 1-based token ids [batch, seq]."""

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
        if output not in ("log_probs", "logits"):
            raise ValueError(f"output {output!r} not in (log_probs, logits)")
        if not 0 <= first_dense <= num_layers:
            raise ValueError(f"first_dense {first_dense} not in "
                             f"[0, num_layers={num_layers}]")
        super().__init__()
        self._output_mode = output
        self.vocab_size = vocab_size
        self.embed_dim = embed_dim
        self.max_len = max_len
        self.use_rope = True            # no position table to add
        self.param_dtype = (jnp.dtype(param_dtype).name if param_dtype
                            else None)
        self.layer_kinds = tuple("dense" if i < first_dense else "moe"
                                 for i in range(num_layers))
        with device_draw():
            embed = nn.LookupTable(vocab_size, embed_dim)
            embed.set_init_method(RandomNormal(0.0, init_std))
            embed.reset()
            self.add(_held_in(embed, self.param_dtype))
            for kind in self.layer_kinds:
                attention = nn.LatentAttention(
                    embed_dim, num_heads, q_rank, kv_rank, nope_dim,
                    rope_dim, v_dim, rope_theta=rope_theta,
                    norm_eps=norm_eps, seq_strategy=seq_strategy,
                    init_std=init_std)
                ffn = GatedFFN(embed_dim, mlp_dim, init_std) \
                    if kind == "dense" else DroplessMoE(
                        embed_dim, expert_dim, n_experts, top_k=top_k,
                        scoring="sigmoid", renormalize=renormalize,
                        n_shared=n_shared,
                        held=tuple(held) if held is not None else None,
                        init_std=init_std, score_bias=True,
                        routed_scale=routed_scale)
                self.add(LatentMoEBlock(attention, ffn, embed_dim, norm_eps,
                                        self.param_dtype))
            self.add(_held_in(nn.RMSNorm(embed_dim, eps=norm_eps),
                              self.param_dtype))
            self.add(_held_in(LogitHead(embed_dim, vocab_size, init_std),
                              self.param_dtype))

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
        contract) through the latent cache: prefill expands it to
        per-head K and V once, a decode step never does."""
        from .generate import cached_generate

        return cached_generate(self, compute_dtype)(
            self.param_tree(), prompt_ids, max_new, rng=rng,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_id=eos_id, pad_id=pad_id)

    def apply_fn(self, params, buffers, x, training, rng):
        h = x
        for i, m in enumerate(self.modules):
            h, _ = m.apply_fn(params[str(i)], buffers[str(i)], h, training,
                              None)
        if self._output_mode == "logits":
            return h, buffers
        return jax.nn.log_softmax(h, axis=-1), buffers
