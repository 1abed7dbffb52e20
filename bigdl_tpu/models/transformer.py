"""TransformerLM — the TPU rebuild's flagship long-context model.

The reference's sequence models stop at LSTM/GRU (SURVEY §5.7); this is
the forward-looking model family that exercises every parallel axis the
framework makes first-class:

* data parallelism   — batch dim over the ``data`` mesh axis
* sequence/context   — ring (or Ulysses) attention over a ``seq`` axis
* tensor parallelism — Megatron column/row split of the MLP over a
  ``model`` axis (one psum per block)

Built entirely from framework layers (LookupTable, LayerNorm,
MultiHeadAttention, Column/RowParallelLinear), so the same model object
runs eagerly on one chip or inside shard_map over a 3-D mesh.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import nn
from ..nn.attention import advance
from ..nn.module import Container
from ..parallel.tensor_parallel import ColumnParallelLinear, RowParallelLinear
from ..utils.rng import next_jax_key
from .generate import CausalLM


def _norm_factory(norm: str, norm_eps):
    """One policy for both the blocks and the final norm: the norm
    class and its eps default (rms 1e-6 / ln 1e-5, HF's conventions)."""
    eps = norm_eps if norm_eps is not None else (
        1e-6 if norm == "rms" else 1e-5)
    if norm == "rms":
        return lambda d: nn.RMSNorm(d, eps=eps)
    return lambda d: nn.LayerNorm(d, eps=eps)


class TransformerBlock(Container):
    """Pre-norm residual block: x + MHA(LN(x)); x + MLP(LN(x)).

    ``moe_experts > 0`` swaps the dense MLP for a Switch-style
    mixture-of-experts FFN (parallel/moe.py) — expert-parallel over
    ``moe_axis`` when set (the token-sharding mesh axis), dense
    otherwise.  Dropped-over-capacity tokens ride the residual.

    A decoder keeps the attention's K/V and nothing else (``state_init``
    / ``advance``: the decode-state protocol of ``nn/attention.py``),
    LN/MLP sublayers run through their module ``apply_fn``, and an MoE
    FFN decodes capacity-FREE (``MoEFFN.nodrop``: at inference nothing
    should be dropped — training-time capacity drops are a static-shape
    batching artifact, not part of the learned function)."""

    def __init__(self, embed_dim: int, num_heads: int, mlp_dim: int,
                 causal: bool = True, seq_strategy: str = "dense",
                 seq_axis: str = "seq", model_axis: Optional[str] = None,
                 moe_experts: int = 0, moe_axis: Optional[str] = None,
                 moe_capacity_factor: float = 1.25,
                 moe_aux_coef: float = 0.0, moe_top_k: int = 1,
                 dropout: float = 0.0, norm: str = "ln",
                 mlp: str = "gelu", num_kv_heads: Optional[int] = None,
                 rope: bool = False, rope_theta: float = 10000.0,
                 attn_bias: Optional[bool] = None,
                 mlp_bias: Optional[bool] = None,
                 norm_eps: Optional[float] = None,
                 blocksparse: Optional[dict] = None):
        if norm not in ("ln", "rms"):
            raise ValueError(f"norm {norm!r} not in ('ln', 'rms')")
        if mlp not in ("gelu", "swiglu"):
            raise ValueError(f"mlp {mlp!r} not in ('gelu', 'swiglu')")
        if mlp == "swiglu" and moe_experts:
            raise ValueError("moe_experts uses gelu expert MLPs; "
                             "mlp='swiglu' does not compose with MoE")
        Norm = _norm_factory(norm, norm_eps)
        # llama convention: bias-free attention (and swiglu) projections
        with_bias = (attn_bias if attn_bias is not None
                     else not (rope or norm == "rms"))
        # block-sparse attention config (seq_strategy="blocksparse"):
        # pattern/window/globals/stride/block forwarded to the MHA's
        # mask builder (ops/block_sparse.py)
        bs = dict(blocksparse or {})
        mods = [
            Norm(embed_dim),
            nn.MultiHeadAttention(embed_dim, num_heads, causal=causal,
                                  seq_strategy=seq_strategy,
                                  seq_axis=seq_axis,
                                  num_kv_heads=num_kv_heads,
                                  rope=rope, rope_theta=rope_theta,
                                  with_bias=with_bias,
                                  sparse_pattern=bs.get("pattern",
                                                        "sliding"),
                                  sparse_window=bs.get("window", 2),
                                  sparse_globals=bs.get("globals", 1),
                                  sparse_stride=bs.get("stride", 4),
                                  sparse_block=bs.get("block")),
            Norm(embed_dim),
        ]
        if moe_experts:
            if model_axis is not None:
                raise ValueError(
                    "moe_experts replaces the Column/RowParallel MLP — "
                    "tensor parallelism of the FFN would be silently "
                    "dropped; pass model_axis=None with MoE")
            from ..parallel.moe import MoEFFN

            mods.append(MoEFFN(embed_dim, mlp_dim, moe_experts,
                               capacity_factor=moe_capacity_factor,
                               axis_name=moe_axis,
                               aux_loss_coef=moe_aux_coef,
                               top_k=moe_top_k,
                               # under sequence parallelism the tokens
                               # are seq-sharded too: aux routing stats
                               # must pmean over that axis as well
                               stat_axes=((seq_axis,) if seq_strategy
                                          in ("ring", "ulysses")
                                          and seq_axis else ())))
        elif mlp == "swiglu":
            # Megatron mapping: gate/up are column-split, down row-split
            # (bias independent of the attention's: HF llama separates
            # attention_bias from mlp_bias)
            mb = mlp_bias if mlp_bias is not None else with_bias
            mods += [ColumnParallelLinear(embed_dim, mlp_dim,
                                          with_bias=mb,
                                          axis_name=model_axis),
                     ColumnParallelLinear(embed_dim, mlp_dim,
                                          with_bias=mb,
                                          axis_name=model_axis),
                     RowParallelLinear(mlp_dim, embed_dim,
                                       with_bias=mb,
                                       axis_name=model_axis)]
        else:
            mods += [ColumnParallelLinear(embed_dim, mlp_dim,
                                          axis_name=model_axis),
                     RowParallelLinear(mlp_dim, embed_dim,
                                       axis_name=model_axis)]
        super().__init__(*mods)
        self.is_moe = bool(moe_experts)
        self.mlp_kind = "moe" if moe_experts else mlp
        # residual dropout applied FUNCTIONALLY (no extra modules, so
        # the block structure the pipeline/generation builders rely on
        # is unchanged); train-time only, keyed off the step rng the
        # drivers already decorrelate per batch shard
        self.dropout = float(dropout)

    def _drop(self, v, key, training):
        if self.dropout <= 0.0 or not training or key is None:
            return v
        keep = 1.0 - self.dropout
        mask = jax.random.bernoulli(key, keep, v.shape)
        return jnp.where(mask, v / keep, 0).astype(v.dtype)

    # -- decode: the state between tokens, and Tq tokens against it ------
    def state_init(self, batch: int, dtype, length: int, int8: bool = False):
        return self.modules[1].state_init(batch, dtype, length, int8)

    def footprint(self, batch: int, dtype, length: int, int8: bool = False):
        return self.modules[1].footprint(batch, dtype, length, int8)

    def _run(self, params, i, v):
        return self.modules[i].apply_fn(params[str(i)], {}, v, False,
                                        None)[0]

    def ffn_sublayer(self, params, h):
        """``h + MLP(norm_2 h)``: gelu, swiglu, or the capacity-free
        mixture of experts."""
        x = self._run(params, 2, h)
        if self.is_moe:
            return h + self.modules[3].nodrop(params["3"], x)
        if getattr(self, "mlp_kind", None) == "swiglu":
            g, u = self._run(params, 3, x), self._run(params, 4, x)
            return h + self._run(params, 5, jax.nn.silu(g) * u)
        return h + self._run(params, 4,
                             jax.nn.gelu(self._run(params, 3, x)))

    def attention_sublayer(self, params, h, pos, attend):
        """``h + Attn(norm_1 h)`` with the attention ITSELF handed in —
        ``attend(q, k, v) -> o [B, H, Tq, Dh]`` on the heads this layer
        makes at ``pos`` (None: from 0), wherever it keeps them: what a
        decoder with a store of its own (the paged one) runs."""
        mha, x = self.modules[1], self._run(params, 0, h)
        at = jnp.arange(x.shape[1])
        q, k, v = mha.heads(params["1"], x, at if pos is None else pos + at)
        return h + mha.merged(params["1"], attend(q, k, v))

    def advance(self, params, h, state, pos):
        a, state = advance(self.modules[1], params["1"],
                           self._run(params, 0, h), state, pos)
        return self.ffn_sublayer(params, h + a), state

    def apply_fn(self, params, buffers, x, training, rng):
        def sub(i):
            return jax.random.fold_in(rng, i) if rng is not None else None

        # device scopes (``telemetry.tracer.DEVICE_SCOPES``; metadata
        # only): the two residual branches, each with its norm and add
        nb = dict(buffers)
        with jax.named_scope("block.attention"):
            h, nb["0"] = self.modules[0].apply_fn(
                params["0"], buffers["0"], x, training, sub(0))
            h, nb["1"] = self.modules[1].apply_fn(
                params["1"], buffers["1"], h, training, sub(1))
            x = x + self._drop(h, sub(10), training)
        with jax.named_scope("block.mlp"):
            h, nb["2"] = self.modules[2].apply_fn(
                params["2"], buffers["2"], x, training, sub(2))
            if getattr(self, "mlp_kind", None) == "swiglu":
                # llama MLP: down(silu(gate(x)) * up(x))
                g, nb["3"] = self.modules[3].apply_fn(
                    params["3"], buffers["3"], h, training, sub(3))
                u, nb["4"] = self.modules[4].apply_fn(
                    params["4"], buffers["4"], h, training, sub(4))
                h, nb["5"] = self.modules[5].apply_fn(
                    params["5"], buffers["5"], jax.nn.silu(g) * u,
                    training, sub(5))
            else:
                h, nb["3"] = self.modules[3].apply_fn(
                    params["3"], buffers["3"], h, training, sub(3))
                if not self.is_moe:
                    # dense MLP: gelu between the column/row pair; the
                    # MoE FFN applies its own gelu between the expert
                    # matmuls
                    h = jax.nn.gelu(h)
                    h, nb["4"] = self.modules[4].apply_fn(
                        params["4"], buffers["4"], h, training, sub(4))
            return x + self._drop(h, sub(11), training), nb


class TransformerLM(CausalLM, Container):
    """Decoder-only causal LM over 1-based token ids [batch, seq].

    Output is log-probs [batch, seq, vocab] — feed
    ``TimeDistributedCriterion(ClassNLLCriterion())`` like SimpleRNN.
    Under sequence parallelism the learned positional table is sliced at
    each device's global offset (``lax.axis_index(seq_axis)``).
    """

    def __init__(self, vocab_size: int, embed_dim: int = 256,
                 num_heads: int = 8, mlp_dim: Optional[int] = None,
                 num_layers: int = 4, max_len: int = 2048,
                 causal: bool = True, seq_strategy: str = "dense",
                 seq_axis: str = "seq", model_axis: Optional[str] = None,
                 remat: bool = False, output: str = "log_probs",
                 moe_experts: int = 0, moe_axis: Optional[str] = None,
                 moe_capacity_factor: float = 1.25,
                 moe_aux_coef: float = 0.0, moe_top_k: int = 1,
                 dropout: float = 0.0, norm: str = "ln",
                 mlp: str = "gelu", num_kv_heads: Optional[int] = None,
                 rope: bool = False, rope_theta: float = 10000.0,
                 attn_bias: Optional[bool] = None,
                 mlp_bias: Optional[bool] = None,
                 head_bias: bool = True,
                 norm_eps: Optional[float] = None,
                 blocksparse: Optional[dict] = None):
        if output not in ("log_probs", "logits"):
            raise ValueError(f"output {output!r} not in (log_probs, logits)")
        mlp_dim = mlp_dim or 4 * embed_dim
        # "logits" skips the final log_softmax: pair with the fused
        # CrossEntropyCriterion so the [B,T,V] log-prob tensor is never
        # materialised (the vocab head is HBM-bound at LM scale).
        # NOT ``self.output`` — AbstractModule uses that name for the
        # cached forward activation (module.py), which would clobber it.
        self._output_mode = output
        self.vocab_size = vocab_size
        self.embed_dim = embed_dim
        self.max_len = max_len
        self.seq_axis = seq_axis
        self.seq_strategy = seq_strategy
        self.remat = remat
        # rope models carry no learned positional table — positions
        # live in the per-layer q/k rotation
        self.use_rope = bool(rope)
        blocks = [TransformerBlock(embed_dim, num_heads, mlp_dim, causal,
                                   seq_strategy, seq_axis, model_axis,
                                   moe_experts=moe_experts,
                                   moe_axis=moe_axis,
                                   moe_capacity_factor=moe_capacity_factor,
                                   moe_aux_coef=moe_aux_coef,
                                   moe_top_k=moe_top_k,
                                   dropout=dropout, norm=norm, mlp=mlp,
                                   num_kv_heads=num_kv_heads, rope=rope,
                                   rope_theta=rope_theta,
                                   attn_bias=attn_bias,
                                   mlp_bias=mlp_bias,
                                   norm_eps=norm_eps,
                                   blocksparse=blocksparse)
                  for _ in range(num_layers)]
        Norm = _norm_factory(norm, norm_eps)
        super().__init__(
            nn.LookupTable(vocab_size, embed_dim),
            *blocks,
            Norm(embed_dim),
            nn.Linear(embed_dim, vocab_size, with_bias=head_bias),
        )
        self._reset_pos()

    def _reset_pos(self):
        if getattr(self, 'use_rope', False):
            return
        self._register_param(
            "pos", 0.02 * jax.random.normal(
                next_jax_key(), (self.max_len, self.embed_dim)))

    def reset(self):
        super().reset()
        self._reset_pos()
        return self

    # own params ("pos") + children keyed by index, like Container
    # (rope models carry no positional table at all)
    def param_tree(self):
        tree = super().param_tree()
        if not getattr(self, 'use_rope', False):
            tree["pos"] = self.params["pos"]
        return tree

    def set_param_tree(self, tree):
        tree = dict(tree)
        if not getattr(self, 'use_rope', False):
            self.params["pos"] = tree.pop("pos")
        super().set_param_tree(tree)

    def grad_tree(self):
        tree = super().grad_tree()
        if not getattr(self, 'use_rope', False):
            tree["pos"] = self._grad("pos")
        return tree

    def set_grad_tree(self, tree):
        tree = dict(tree)
        if not getattr(self, 'use_rope', False):
            self.grads["pos"] = tree.pop("pos")
        super().set_grad_tree(tree)

    def gradient_scale_tree(self):
        tree = super().gradient_scale_tree()
        if not getattr(self, 'use_rope', False):
            tree["pos"] = self.scale_w
        return tree

    def _positions(self, pos_table, T):
        if self.seq_strategy in ("ring", "ulysses"):
            n = lax.psum(1, self.seq_axis)  # concrete under shard_map
            total = n * T if isinstance(n, int) else T
            off = lax.axis_index(self.seq_axis) * T
        else:
            total, off = T, 0
        if total > self.max_len:
            # dynamic_slice would silently clamp → duplicated rows
            raise ValueError(f"sequence length {total} exceeds "
                             f"max_len {self.max_len}")
        return lax.dynamic_slice_in_dim(pos_table, off, T)

    def apply_fn(self, params, buffers, x, training, rng):
        embed = self.modules[0]
        with jax.named_scope("lm.embed"):
            h, eb = embed.apply_fn(params["0"], buffers["0"], x, training,
                                   jax.random.fold_in(rng, 0)
                                   if rng is not None else None)
            if not getattr(self, 'use_rope', False):  # rope positions live in the q/k rotation
                h = h + self._positions(params["pos"], h.shape[1])
        new_buffers = dict(buffers)

        def child(i, h):
            m = self.modules[i]
            sub = jax.random.fold_in(rng, i) if rng is not None else None
            if self.remat and isinstance(m, TransformerBlock):
                # rematerialize each block's activations in the backward
                # pass — HBM for FLOPs (jax.checkpoint; SURVEY north-star
                # memory recipe).  training/sub close over; params/
                # buffers/h are the differentiated residuals.
                apply = jax.checkpoint(
                    lambda p, b, h_, _m=m, _s=sub: _m.apply_fn(
                        p, b, h_, training, _s))
                return apply(params[str(i)], buffers[str(i)], h)
            return m.apply_fn(params[str(i)], buffers[str(i)], h,
                              training, sub)

        head = len(self.modules) - 2  # the final norm, then the head
        for i in range(1, head):
            h, new_buffers[str(i)] = child(i, h)
        with jax.named_scope("lm.head"):
            for i in (head, head + 1):
                h, new_buffers[str(i)] = child(i, h)
            if self._output_mode != "logits":
                h = jax.nn.log_softmax(h, axis=-1)
        new_buffers["0"] = eb
        return h, new_buffers
