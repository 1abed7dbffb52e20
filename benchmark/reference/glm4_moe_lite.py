"""The GLM-4.7-Flash block as published (``glm4_moe_lite``;
``zai-org/GLM-4.7-Flash`` ``config.json``): a SEQUENTIAL pre-norm block
whose attention is latent (MLA) and whose FFN is a dense SwiGLU in the
first ``first_k_dense_replace`` layers and a mixture of experts chosen by
bias-corrected sigmoid scores after them.  RMSNorm (eps
``rms_norm_eps``) throughout, no bias anywhere; ``x`` is [T, d].

    h = x + Attn(RMSNorm_1(x));   y = h + FFN_i(RMSNorm_2(h))

    Attn(n), H heads, scale 1 / sqrt(qk_nope_head_dim + qk_rope_head_dim):
      c_q  = RMSNorm_q(wq_a n)                      [q_lora_rank]
      q    = wq_b c_q  -> [H, nope + rope] = q_nope | q_rope
      c, k_r = wkv_a n -> [kv_lora_rank] | [rope]
      c_kv = RMSNorm_kv(c);  k_rope = RoPE(k_r)     ONE vector a position
      q_rope = RoPE(q_rope)                         theta rope_theta, halves
      k_nope | v = wkv_b c_kv -> [H, nope] | [H, v_head_dim]   per head
      scores = q_nope.k_nope + q_rope.k_rope;  causal softmax;  o = P v
      out  = wo concat_h(o)

    FFN_dense(n) = down(silu(gate n) * up n)        width intermediate_size
    FFN_moe(n):  s = sigmoid(W_r n)  float32, ALL router outputs
                 idx = top-k(s + b)                 b: the correction bias,
                                                    in the SELECTION only
                 g = s[idx] / (sum s[idx] + 1e-20) * routed_scaling_factor
                 y = sum_{e in idx, e held here} g_e E_e(n) + S(n)
                 E, S: SwiGLU at moe_intermediate_size; S the shared expert(s)

The head is a matrix of its own after a final RMSNorm.

Plain on purpose: float32, every product at HIGHEST, the EXPANDED
attention at every position (per-head K and V made from the latent for
the whole sequence — never the absorbed form a decode step uses, never
a cache), every held expert applied to EVERY token and masked by the
selection.  Weights are [out, in], but the experts': ``moe.gate`` /
``moe.up`` [expert, in, out] and ``moe.down`` [expert, hidden, out]
(likewise ``shared.*``), the layout the program holds them in.

How the layers reach the harness.  The harness seeds ONE set of leaves a
layer (``specs["layer"]``), and this model's layers are of two kinds.
So the leading dense layers are part of ``embed``: their leaves are
top-level (``dense.<j>.<leaf>``), ``embed`` is the lookup followed by
those blocks, and ``n_layers`` counts the EXPERT layers that ``block``
serves (one program for all of them; layer ``i`` here is the model's
layer ``first_k_dense_replace + i``).  ``embed`` has no precision mode:
under the fp8 control the dense layers stay float32 and the expert
layers and the head are rounded.

Departures and assumptions (also under ``assumed`` in the configuration):

* A SHARE of the experts: the configuration holds ``n_routed_experts`` of
  the published ``n_routed_experts_published``, from
  ``first_expert_held`` on.  The router keeps all its outputs and its
  experts per token; what the absent experts would add is left out, here
  as in the program.  The shared expert is whole on every chip.
* Rotation by halves (dim ``i`` pairs with ``i + rope/2``) over all
  ``qk_rope_head_dim`` dims (``partial_rotary_factor`` 1).
* ``n_group`` 1, ``topk_group`` 1: no group step in the selection.
* The router's scores, the bias and the gates are float32 in every mode.
* ``num_nextn_predict_layers`` 0: the multi-token-prediction module is no
  part of the forward pass that yields the next token's logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .command_a_plus import _weighted_experts
from .common import causal_attention, merge_heads, mm, split_heads


def _attn_specs(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return {"input_norm": ((d,), "ones"), "post_norm": ((d,), "ones"),
            "attn.wq_a": ((qr, d), "normal"), "attn.q_norm": ((qr,), "ones"),
            "attn.wq_b": ((H * (nope + rope), qr), "normal"),
            "attn.wkv_a": ((kvr + rope, d), "normal"),
            "attn.kv_norm": ((kvr,), "ones"),
            "attn.wkv_b": ((H * (nope + vd), kvr), "normal"),
            "attn.wo": ((d, H * vd), "normal")}


def dense_specs(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return {**_attn_specs(cfg), "mlp.gate": ((f, d), "normal"),
            "mlp.up": ((f, d), "normal"), "mlp.down": ((d, f), "normal")}


def param_specs(cfg: dict) -> dict:
    d, v, f = (cfg["hidden_size"], cfg["vocab_size"],
               cfg["moe_intermediate_size"])
    e, s = cfg["n_routed_experts"], cfg["n_shared_experts"]
    top = {"embed": ((v, d), "normal"), "norm": ((d,), "ones"),
           "head": ((v, d), "normal")}
    for j in range(int(cfg["first_k_dense_replace"])):
        top.update({f"dense.{j}.{n}": sk
                    for n, sk in dense_specs(cfg).items()})
    return {
        "top": top,
        "layer": {
            **_attn_specs(cfg),
            "moe.router": ((cfg["n_routed_experts_published"], d), "normal"),
            "moe.bias": ((cfg["n_routed_experts_published"],), "normal"),
            "moe.gate": ((e, d, f), "normal"), "moe.up": ((e, d, f), "normal"),
            "moe.down": ((e, f, d), "normal"),
            "shared.gate": ((s, d, f), "normal"),
            "shared.up": ((s, d, f), "normal"),
            "shared.down": ((s, f, d), "normal"),
        }}


def n_layers(cfg: dict) -> int:
    """The EXPERT layers: what ``block`` serves (module docstring)."""
    return int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope_halves(x, theta):
    """x [B,H,T,D] rotated at positions 0..T-1; dim i pairs with i+D/2."""
    D, T = x.shape[-1], x.shape[2]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]   # [T, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(lp: dict, n, cfg: dict, mode: str = "f32"):
    """The expanded form: per-head K and V for every position."""
    H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    kvr, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    theta = float(cfg["rope_theta"])
    cq = _rms(mm(n, lp["attn.wq_a"], mode), lp["attn.q_norm"], eps)
    q = split_heads(mm(cq, lp["attn.wq_b"], mode), H)       # [B,H,T,nope+rope]
    q = jnp.concatenate([q[..., :nope], _rope_halves(q[..., nope:], theta)],
                        -1)
    ckr = mm(n, lp["attn.wkv_a"], mode)
    ckv = _rms(ckr[..., :kvr], lp["attn.kv_norm"], eps)
    k_rope = _rope_halves(ckr[:, None, :, kvr:], theta)      # [B,1,T,rope]
    kv = split_heads(mm(ckv, lp["attn.wkv_b"], mode), H)     # [B,H,T,nope+v]
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(
            k_rope, kv.shape[:3] + (k_rope.shape[-1],))], -1)
    o = causal_attention(q, k, kv[..., nope:], mode)
    return mm(merge_heads(o), lp["attn.wo"], mode)


def select(lp: dict, n, cfg: dict):
    """(gates [..., k] float32, experts [..., k]) over ALL router
    outputs — float32 at HIGHEST in every mode.  The bias chooses; the
    gates are the unbiased scores of the chosen."""
    s = jax.nn.sigmoid(mm(n, lp["moe.router"], "f32"))
    _, idx = jax.lax.top_k(s + lp["moe.bias"], int(cfg["num_experts_per_tok"]))
    g = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    return g * cfg["routed_scaling_factor"], idx


def routed(lp: dict, n, cfg: dict, mode: str = "f32"):
    """The HELD experts' part of the mixture: each applied to EVERY
    token, weighted by the token's gate for it (zero where the token did
    not choose it)."""
    g, idx = select(lp, n, cfg)
    held = (int(cfg["first_expert_held"])
            + jnp.arange(int(cfg["n_routed_experts"])))
    weights = jnp.sum(
        jnp.where(idx[None] == held.reshape((-1,) + (1,) * idx.ndim),
                  g[None], 0.0), -1)                     # [held, ...tokens]
    return _weighted_experts(n, lp["moe.gate"], lp["moe.up"], lp["moe.down"],
                             weights, mode)


def shared(lp: dict, n, cfg: dict, mode: str = "f32"):
    """The shared expert(s), added whole."""
    S = int(cfg["n_shared_experts"])
    return _weighted_experts(n, lp["shared.gate"], lp["shared.up"],
                             lp["shared.down"],
                             jnp.ones((S,) + n.shape[:-1]), mode)


def dense_block(lp: dict, h, cfg: dict, mode: str = "f32"):
    eps = cfg["rms_norm_eps"]
    h = h + attention(lp, _rms(h, lp["input_norm"], eps), cfg, mode)
    n = _rms(h, lp["post_norm"], eps)
    act = jax.nn.silu(mm(n, lp["mlp.gate"], mode)) * mm(n, lp["mlp.up"], mode)
    return h + mm(act, lp["mlp.down"], mode)


def embed(p: dict, ids, cfg: dict):
    """The lookup, then the leading dense layers (module docstring)."""
    h = p["embed"][ids]
    for j in range(int(cfg["first_k_dense_replace"])):
        h = dense_block({n: p[f"dense.{j}.{n}"] for n in dense_specs(cfg)},
                        h, cfg)
    return h


def block(lp: dict, h, cfg: dict, mode: str = "f32"):
    """One EXPERT layer."""
    eps = cfg["rms_norm_eps"]
    h = h + attention(lp, _rms(h, lp["input_norm"], eps), cfg, mode)
    n = _rms(h, lp["post_norm"], eps)
    return h + routed(lp, n, cfg, mode) + shared(lp, n, cfg, mode)


def head(p: dict, h, cfg: dict, mode: str = "f32"):
    return mm(_rms(h, p["norm"], cfg["rms_norm_eps"]), p["head"], mode)
