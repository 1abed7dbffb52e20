"""The LFM2-24B-A2B block as published (``lfm2_moe``;
``LiquidAI/LFM2-24B-A2B`` ``config.json``): a SEQUENTIAL pre-norm block
whose token-mixing operator is a gated SHORT CONVOLUTION in three layers
of four and grouped-query attention with per-head QK-norm in the fourth
(``layer_types``), and whose FFN is a dense SwiGLU in the first
``num_dense_layers`` layers and a mixture of experts chosen by
bias-corrected sigmoid scores after them.  RMSNorm (eps ``norm_eps``)
throughout, no bias anywhere (``conv_bias`` false); ``x`` is [T, d].

    h = x + Op_i(RMSNorm_op(x));   y = h + FFN_i(RMSNorm_ffn(h))

    Conv(n), K = conv_L_cache:
      (B, C, u) = split_3(w_in n)                   each [T, d]
      z   = B * u
      c_t = sum_{k < K} w[k] * z_{t-(K-1)+k}        depthwise, causal; z is
                                                    zero before the sequence,
                                                    w[K-1] on the current one
      out = w_out (C * c)

    Attn(n), H heads on Hkv K/V heads of d / H, scale 1 / sqrt(d / H):
      q = wq n -> [H, Dh];  k = wk n -> [Hkv, Dh];  v = wv n -> [Hkv, Dh]
      q = RMSNorm_q(q);  k = RMSNorm_k(k)           over the Dh numbers of
                                                    EACH head, one gain
                                                    vector for all heads
      q, k = RoPE(q), RoPE(k)                       theta rope_theta, halves
      causal softmax;  out = wo concat_h(P v)

    FFN_dense(n) = down(silu(gate n) * up n)        width intermediate_size
    FFN_moe(n):  s = sigmoid(W_r n)  float32, ALL num_experts outputs
                 idx = top-k(s + b)                 b: the expert bias, in
                                                    the SELECTION only
                 g = s[idx] / (sum s[idx] + 1e-6) * routed_scaling_factor
                 y = sum_{e in idx, e held here} g_e E_e(n)
                 E: SwiGLU at moe_intermediate_size; no shared expert

The head is the embedding matrix transposed, after a final RMSNorm (the
family calls it ``embedding_norm``; it is applied AFTER the last layer).

Plain on purpose: float32, every product at HIGHEST, the convolution as
the K-term sum over a zero-padded sequence (never a tail, never a
cache), attention over the whole sequence, every held expert applied to
EVERY token through a ``lax.scan`` over the stacked leaves and masked by
the selection.  Weights are [out, in], but the conv filter ``conv.w``
[K, d] and the experts': ``moe.gate`` / ``moe.up`` [expert, in, out] and
``moe.down`` [expert, hidden, out], the layout the program holds them in.

How the layers reach the harness.  The harness seeds ONE set of leaves a
layer (``specs["layer"]``), and this model's layers are of three kinds.
The stacks this file serves are, in order, ``num_dense_layers`` conv
layers with a dense FFN, ``num_attention_expert_layers`` attention
layers with experts, then ``num_conv_expert_layers`` conv layers with
experts — published layers 1-5 are such a stack (1, 1, 3), and
``param_specs`` refuses a ``layer_types`` that is not.  The first two
groups are part of ``embed``: their leaves are top-level
(``dense.<j>.<leaf>``, ``attn.<j>.<leaf>``), ``embed`` is the lookup
followed by those blocks, and ``n_layers`` counts the conv EXPERT layers
that ``block`` serves (one program for all of them).  ``embed`` has no
precision mode: under the fp8 control the dense conv layer and the
attention layer stay float32 and the conv expert layers and the head are
rounded.

Departures and assumptions (also under ``assumed`` in the configuration):

* The experts held are ``num_experts_held`` of ``num_experts`` from
  ``first_expert_held`` on; the configuration in the benchmark holds ALL
  of them, and a share is what the CPU tests add up.
* Embedding and head are one matrix, and the renormalisation has 1e-6
  in its sum (the family's code; the catalog's ``config`` has no key for
  either).
* Rotation by halves (dim ``i`` pairs with ``i + Dh/2``) over the whole
  head, ``rope_theta`` the scalar copy of ``rope_parameters``'.
* The router's scores, the bias and the gates are float32 in every mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .command_a_plus import _weighted_experts
from .common import causal_attention, merge_heads, mm, split_heads
from .glm4_moe_lite import _rms, _rope_halves

RENORM_EPS = 1e-6


def _head_dim(cfg: dict) -> int:
    return int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])


def conv_specs(cfg: dict) -> dict:
    d, K = cfg["hidden_size"], cfg["conv_L_cache"]
    return {"input_norm": ((d,), "ones"), "post_norm": ((d,), "ones"),
            "conv.w_in": ((3 * d, d), "normal"),
            # ones: ``seeded_leaf`` knows normal, ones and zeros, and a
            # filter drawn normal(0, 0.02) would make the tail invisible
            "conv.w": ((K, d), "ones"),
            "conv.w_out": ((d, d), "normal")}


def attention_specs(cfg: dict) -> dict:
    d, H, Hkv, Dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], _head_dim(cfg))
    return {"input_norm": ((d,), "ones"), "post_norm": ((d,), "ones"),
            "attn.wq": ((H * Dh, d), "normal"),
            "attn.wk": ((Hkv * Dh, d), "normal"),
            "attn.wv": ((Hkv * Dh, d), "normal"),
            "attn.wo": ((d, H * Dh), "normal"),
            "attn.q_norm": ((Dh,), "ones"), "attn.k_norm": ((Dh,), "ones")}


def dense_ffn_specs(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return {"mlp.gate": ((f, d), "normal"), "mlp.up": ((f, d), "normal"),
            "mlp.down": ((d, f), "normal")}


def expert_specs(cfg: dict) -> dict:
    d, f, E, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                  cfg["num_experts"], cfg["num_experts_held"])
    return {"moe.router": ((E, d), "normal"), "moe.bias": ((E,), "normal"),
            "moe.gate": ((e, d, f), "normal"), "moe.up": ((e, d, f), "normal"),
            "moe.down": ((e, f, d), "normal")}


def _stack(cfg: dict) -> tuple:
    """(dense conv, attention expert, conv expert) layers of the stack."""
    return (int(cfg["num_dense_layers"]),
            int(cfg["num_attention_expert_layers"]),
            int(cfg["num_conv_expert_layers"]))


def param_specs(cfg: dict) -> dict:
    a, b, c = _stack(cfg)
    want = ["conv"] * a + ["full_attention"] * b + ["conv"] * c
    if list(cfg["layer_types"]) != want:
        raise ValueError(f"layer_types {cfg['layer_types']} is not the stack "
                         f"this reference serves: {want}")
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    top = {"embed": ((v, d), "normal"), "norm": ((d,), "ones")}
    for j in range(a):
        top.update({f"dense.{j}.{n}": sk for n, sk in
                    {**conv_specs(cfg), **dense_ffn_specs(cfg)}.items()})
    for j in range(b):
        top.update({f"attn.{j}.{n}": sk for n, sk in
                    {**attention_specs(cfg), **expert_specs(cfg)}.items()})
    return {"top": top, "layer": {**conv_specs(cfg), **expert_specs(cfg)}}


def n_layers(cfg: dict) -> int:
    """The conv EXPERT layers: what ``block`` serves (module docstring)."""
    return int(cfg["num_conv_expert_layers"])


def short_conv(lp: dict, n, cfg: dict, mode: str = "f32"):
    """The whole sequence at once: the K-term sum over ``z`` padded with
    K - 1 zeros in front."""
    K, T = int(cfg["conv_L_cache"]), n.shape[1]
    b, c, u = jnp.split(mm(n, lp["conv.w_in"], mode), 3, axis=-1)
    z = jnp.pad(b * u, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(lp["conv.w"][k] * z[:, k:k + T] for k in range(K))
    return mm(c * y, lp["conv.w_out"], mode)


def attention(lp: dict, n, cfg: dict, mode: str = "f32"):
    H, Hkv, eps = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["norm_eps"])
    theta = float(cfg["rope_theta"])
    q = _rms(split_heads(mm(n, lp["attn.wq"], mode), H), lp["attn.q_norm"],
             eps)
    k = _rms(split_heads(mm(n, lp["attn.wk"], mode), Hkv), lp["attn.k_norm"],
             eps)
    v = split_heads(mm(n, lp["attn.wv"], mode), Hkv)
    o = causal_attention(_rope_halves(q, theta), _rope_halves(k, theta), v,
                         mode)
    return mm(merge_heads(o), lp["attn.wo"], mode)


def select(lp: dict, n, cfg: dict):
    """(gates [..., k] float32, experts [..., k]) over ALL router
    outputs — float32 at HIGHEST in every mode.  The bias chooses; the
    gates are the unbiased scores of the chosen."""
    s = jax.nn.sigmoid(mm(n, lp["moe.router"], "f32"))
    _, idx = jax.lax.top_k(s + lp["moe.bias"], int(cfg["num_experts_per_tok"]))
    g = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        g = g / (jnp.sum(g, -1, keepdims=True) + RENORM_EPS)
    return g * cfg["routed_scaling_factor"], idx


def routed(lp: dict, n, cfg: dict, mode: str = "f32"):
    """The HELD experts' part of the mixture: each applied to EVERY
    token, weighted by the token's gate for it (zero where the token did
    not choose it)."""
    g, idx = select(lp, n, cfg)
    held = (int(cfg["first_expert_held"])
            + jnp.arange(int(cfg["num_experts_held"])))
    weights = jnp.sum(
        jnp.where(idx[None] == held.reshape((-1,) + (1,) * idx.ndim),
                  g[None], 0.0), -1)                     # [held, ...tokens]
    return _weighted_experts(n, lp["moe.gate"], lp["moe.up"], lp["moe.down"],
                             weights, mode)


def dense_ffn(lp: dict, n, cfg: dict, mode: str = "f32"):
    act = jax.nn.silu(mm(n, lp["mlp.gate"], mode)) * mm(n, lp["mlp.up"], mode)
    return mm(act, lp["mlp.down"], mode)


def layer(lp: dict, h, cfg: dict, operator, ffn, mode: str = "f32"):
    """One block: ``operator`` is :func:`short_conv` or
    :func:`attention`, ``ffn`` :func:`dense_ffn` or :func:`routed`."""
    eps = cfg["norm_eps"]
    h = h + operator(lp, _rms(h, lp["input_norm"], eps), cfg, mode)
    return h + ffn(lp, _rms(h, lp["post_norm"], eps), cfg, mode)


def _sub(p: dict, prefix: str) -> dict:
    return {k[len(prefix):]: p[k] for k in list(p) if k.startswith(prefix)}


def embed(p: dict, ids, cfg: dict):
    """The lookup, then the dense conv layers and the attention expert
    layers (module docstring)."""
    a, b, _ = _stack(cfg)
    h = p["embed"][ids]
    for j in range(a):
        h = layer(_sub(p, f"dense.{j}."), h, cfg, short_conv, dense_ffn)
    for j in range(b):
        h = layer(_sub(p, f"attn.{j}."), h, cfg, attention, routed)
    return h


def block(lp: dict, h, cfg: dict, mode: str = "f32"):
    """One conv EXPERT layer."""
    return layer(lp, h, cfg, short_conv, routed, mode)


def head(p: dict, h, cfg: dict, mode: str = "f32"):
    return mm(_rms(h, p["norm"], cfg["norm_eps"]), p["embed"], mode)
