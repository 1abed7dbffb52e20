"""HybridMambaLM — a causal LM whose every block runs a Mamba-2 mixer
beside grouped-query attention (``nn.HybridMambaBlock``; Falcon-H1's
architecture, with the family's muP multipliers as constructor
arguments).

A ``Container`` with ``TransformerLM``'s child layout — ``0`` the
embedding, ``1..L`` the blocks, ``L+1`` the final RMSNorm, ``L+2`` the
untied head — so the generation builder, the server and the optimizers
take it as they take the dense model: ``apply_fn`` is plain
differentiable jax (no hand-written backward), ``generate`` decodes
through a K/V cache AND a recurrent state per layer
(``nn.HybridMambaBlock.advance``).  Rotary positions only: no position
table.

``param_dtype`` is the dtype the model HOLDS its floating parameters in
(a served bfloat16 model costs 2 bytes a parameter): the constructor
draws in it (on the device: ``nn.initialization.device_draw``), ``set_param_tree`` casts each incoming leaf to it, and a
generator whose compute dtype equals it casts nothing.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.initialization import device_draw
from ..nn.mamba import scaled
from ..nn.module import Container, hold_floats
from .generate import CausalLM


class HybridMambaLM(CausalLM, Container):
    """Decoder-only causal LM over 1-based token ids [batch, seq]."""

    def __init__(self, vocab_size: int, embed_dim: int, num_heads: int,
                 num_kv_heads: int, head_dim: int, mlp_dim: int,
                 num_layers: int, mamba_heads: int, mamba_head_dim: int,
                 mamba_d_state: int, mamba_groups: int = 1,
                 mamba_d_conv: int = 4, mamba_chunk: int = 128,
                 max_len: int = 2048, rope_theta: float = 10000.0,
                 norm_eps: float = 1e-5, seq_strategy: str = "dense",
                 output: str = "log_probs",
                 param_dtype: Optional[str] = None,
                 embedding_multiplier: float = 1.0,
                 lm_head_multiplier: float = 1.0,
                 attention_in_multiplier: float = 1.0,
                 attention_out_multiplier: float = 1.0,
                 key_multiplier: float = 1.0,
                 ssm_in_multiplier: float = 1.0,
                 ssm_out_multiplier: float = 1.0,
                 ssm_multipliers: Sequence[float] = (1.0,) * 5,
                 mlp_multipliers: Sequence[float] = (1.0, 1.0)):
        if output not in ("log_probs", "logits"):
            raise ValueError(f"output {output!r} not in (log_probs, logits)")
        super().__init__()
        self._output_mode = output
        self.vocab_size = vocab_size
        self.embed_dim = embed_dim
        self.max_len = max_len
        self.use_rope = True
        self.param_dtype = (jnp.dtype(param_dtype).name if param_dtype
                            else None)
        self.embedding_multiplier = float(embedding_multiplier)
        self.lm_head_multiplier = float(lm_head_multiplier)

        def held(module):
            # each child draws float32; it is cast as soon as it exists,
            # so the constructor never holds a second copy of the model
            module.set_param_tree(hold_floats(module.param_tree(),
                                              self.param_dtype))
            return module

        # drawn on the device, as the mixer draws: 2.4 B weights from
        # the host generator cost 47-61 s of every start on the v5e's host
        with device_draw():
            self.add(held(nn.LookupTable(vocab_size, embed_dim)))
            for _ in range(num_layers):
                self.add(held(nn.HybridMambaBlock(
                    embed_dim, num_heads, num_kv_heads, head_dim, mlp_dim,
                    mamba_heads, mamba_head_dim, mamba_d_state,
                    mamba_groups=mamba_groups, mamba_d_conv=mamba_d_conv,
                    mamba_chunk=mamba_chunk, rope_theta=rope_theta,
                    norm_eps=norm_eps, seq_strategy=seq_strategy,
                    attention_in_multiplier=attention_in_multiplier,
                    attention_out_multiplier=attention_out_multiplier,
                    key_multiplier=key_multiplier,
                    ssm_in_multiplier=ssm_in_multiplier,
                    ssm_out_multiplier=ssm_out_multiplier,
                    ssm_multipliers=ssm_multipliers,
                    mlp_multipliers=mlp_multipliers)))
            self.add(held(nn.RMSNorm(embed_dim, eps=norm_eps)))
            self.add(held(nn.Linear(embed_dim, vocab_size, with_bias=False)))

    def set_param_tree(self, tree):
        super().set_param_tree(hold_floats(tree, self.param_dtype))

    def reset(self):
        with device_draw():
            super().reset()
        self.set_param_tree(self.param_tree())
        return self

    def apply_fn(self, params, buffers, x, training, rng):
        h, _ = self.modules[0].apply_fn(params["0"], buffers["0"], x,
                                        training, None)
        h = scaled(h, self.embedding_multiplier)
        for i, m in enumerate(self.modules[1:], start=1):
            h, _ = m.apply_fn(params[str(i)], buffers[str(i)], h, training,
                              None)
        h = scaled(h, self.lm_head_multiplier)
        if self._output_mode == "logits":
            return h, buffers
        return jax.nn.log_softmax(h, axis=-1), buffers
