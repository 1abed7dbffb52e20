"""The Command A+ block as published (``cohere2_moe``;
``CohereLabs/command-a-plus-05-2026`` ``config.json``): a PARALLEL block
— attention and a mixture of experts read ONE LayerNorm (no bias) and
both land on the residual — whose attention is of two kinds by layer and
whose experts are chosen by sigmoid scores.

    n   = LN(x; input_norm)                      eps layer_norm_eps, no bias
    q   = wq n;  k = wk n;  v = wv n             128 heads on 8 K/V heads of 128
    sliding layer:  q, k rotated by INTERLEAVED RoPE (pairs (2i, 2i+1), theta
                    rope_theta, all head_dim dims); query t sees keys
                    t - sliding_window + 1 .. t
    full layer:     NO positions; causal over everything
    a   = wo attention(q, k, v)                  scale 1 / sqrt(head_dim)
    s   = sigmoid(W_r n)                         ALL router outputs, float32
    S   = the num_experts_per_tok largest;  g_e = s_e / sum_{j in S} s_j
    m   = sum_{e in S, e held here} g_e E_e(n)   E(n) = down(silu(gate n) * up n)
    sh  = mean over the shared experts of E_s(n)
    x   = x + a + m + sh

Layers go in periods of ``layer_switch``: under ``local_attn_first`` the
LAST of a period is the full one.  The head is the embedding
(``tie_word_embeddings``), after a final LayerNorm, times ``logit_scale``.

Plain on purpose: float32, every product at HIGHEST, every held expert
applied to EVERY token and masked by the selection — no sort, no
grouped product, no cache, no kernel.  Weights are [out, in], but the
experts': ``moe.gate`` / ``moe.up`` are [expert, in, out] and
``moe.down`` [expert, hidden, out] (likewise ``shared.*``), the layout
the program holds them in (the harness hands the program the
reference's leaves as they are).

Departures and assumptions (also under ``assumed`` in the configuration):

* A SHARE of the experts: the configuration holds ``num_experts`` of the
  published ``num_experts_published``, from ``first_expert_held`` on.
  The router keeps all its outputs and its experts per token; what the
  absent experts would add is left out, here as in the program, and that
  partial result goes on to the next layer.
* "average": the MEAN of the shared experts is ADDED to the routed sum.
* ``intermediate_size`` is one expert's width (routed and shared).
* The router's scores are float32 in every mode: under the fp8 control
  the products around it are rounded, the scoring is not (as the norms,
  the softmax and the residual sums are not).
* ``common.hashable`` hands a reference scalars only: the kind of a layer
  comes from ``layer`` and the scalars ``layer_switch`` /
  ``order_of_interleaved_layers``, which repeat ``layer_types``.
* ``first_k_dense_replace`` 0: no leading dense layer; the
  ``prefix_dense_*`` keys are inert.  The vision tower is not in the
  language model's config and is left out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import HIGHEST, _low, merge_heads, mm, split_heads


def param_specs(cfg: dict) -> dict:
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    e, s = cfg["num_experts"], cfg["num_shared_experts"]
    return {
        "top": {"embed": ((v, d), "normal"), "norm": ((d,), "ones")},
        "layer": {
            "input_norm": ((d,), "ones"),
            "attn.wq": ((q, d), "normal"), "attn.wk": ((kv, d), "normal"),
            "attn.wv": ((kv, d), "normal"), "attn.wo": ((d, q), "normal"),
            "moe.router": ((cfg["num_experts_published"], d), "normal"),
            "moe.gate": ((e, d, f), "normal"), "moe.up": ((e, d, f), "normal"),
            "moe.down": ((e, f, d), "normal"),
            "shared.gate": ((s, d, f), "normal"),
            "shared.up": ((s, d, f), "normal"),
            "shared.down": ((s, f, d), "normal"),
        }}


def n_layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def is_full(cfg: dict, layer: int) -> bool:
    """Whether ``layer`` is a ``full_attention`` layer."""
    period = int(cfg["layer_switch"])
    first = cfg["order_of_interleaved_layers"] == "local_attn_first"
    return layer % period == (period - 1 if first else 0)


def _ln(x, g, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope_interleaved(x, theta):
    """x [B,H,T,D] rotated at positions 0..T-1; dim 2i pairs with 2i+1."""
    B, H, T, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]   # [T, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xp = x.reshape(B, H, T, D // 2, 2)
    a, b = xp[..., 0], xp[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(B, H, T, D)


def _attend(q, k, v, window, mode):
    """q [B,H,T,D], k/v [B,Hkv,T,D]; scores written out; query t sees
    keys max(0, t - window + 1) .. t (``window`` None: 0 .. t).  One
    K/V head's group of query heads at a time (``lax.map``), so that a
    context of thousands of positions keeps [B, H/Hkv, T, T] of scores
    and not [B, H, T, T]."""
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    back = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    seen = back >= 0
    if window is not None:
        seen &= back < window

    def group(qkv):
        qg, kg, vg = qkv                # [B, H/Hkv, T, D], [B, T, D] x 2
        qg, kg, vg = _low(qg, mode), _low(kg, mode), _low(vg, mode)
        s = jnp.einsum("bgqd,bkd->bgqk", qg, kg, precision=HIGHEST)
        s = _low(s, mode) / jnp.sqrt(jnp.float32(D))
        s = jnp.where(seen[None, None], s, -jnp.inf)
        p = _low(jax.nn.softmax(s, axis=-1), mode)
        return _low(jnp.einsum("bgqk,bkd->bgqd", p, vg, precision=HIGHEST),
                    mode)

    qg = q.reshape(B, Hkv, H // Hkv, T, D).swapaxes(0, 1)
    o = jax.lax.map(group, (qg, k.swapaxes(0, 1), v.swapaxes(0, 1)))
    return o.swapaxes(0, 1).reshape(B, H, T, D)


def attention(lp: dict, n, cfg: dict, layer: int, mode: str = "f32"):
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = split_heads(mm(n, lp["attn.wq"], mode), H)
    k = split_heads(mm(n, lp["attn.wk"], mode), Hkv)
    v = split_heads(mm(n, lp["attn.wv"], mode), Hkv)
    window = None
    if not is_full(cfg, layer):
        theta = float(cfg["rope_theta"])
        q, k = _rope_interleaved(q, theta), _rope_interleaved(k, theta)
        window = int(cfg["sliding_window"])
    return mm(merge_heads(_attend(q, k, v, window, mode)), lp["attn.wo"],
              mode)


def _expert(n, gate, up, down, mode):
    """One SwiGLU expert; ``gate`` / ``up`` [in, hidden], ``down``
    [hidden, out]."""
    h = jax.nn.silu(mm(n, gate.T, mode)) * mm(n, up.T, mode)
    return mm(h, down.T, mode)


def select(lp: dict, n, cfg: dict):
    """(gates [..., k] float32, experts [..., k]) over ALL router
    outputs — float32 at HIGHEST in every mode."""
    s = jax.nn.sigmoid(mm(n, lp["moe.router"], "f32"))
    g, idx = jax.lax.top_k(s, int(cfg["num_experts_per_tok"]))
    if cfg["norm_topk_prob"]:
        g = g / jnp.sum(g, -1, keepdims=True)
    return g, idx


def _weighted_experts(n, gate, up, down, weights, mode):
    """``sum_e weights[e] * E_e(n)`` over the stacked experts, one after
    another (``lax.scan``: the compiled program holds ONE expert's body,
    whatever their number — unrolled, twenty experts at HIGHEST took two
    minutes to compile on the chip)."""
    def one(y, ew):
        g, u, d, w = ew
        return y + w[..., None] * _expert(n, g, u, d, mode), None

    return jax.lax.scan(one, jnp.zeros_like(n), (gate, up, down, weights))[0]


def routed(lp: dict, n, cfg: dict, mode: str = "f32"):
    """The HELD experts' part of the mixture: each applied to EVERY
    token, weighted by the token's gate for it (zero where the token did
    not choose it)."""
    g, idx = select(lp, n, cfg)
    held = int(cfg["first_expert_held"]) + jnp.arange(int(cfg["num_experts"]))
    weights = jnp.sum(jnp.where(idx[None] == held.reshape((-1,) + (1,) * idx.ndim),
                                g[None], 0.0), -1)       # [held, ...tokens]
    return _weighted_experts(n, lp["moe.gate"], lp["moe.up"], lp["moe.down"],
                             weights, mode)


def shared(lp: dict, n, cfg: dict, mode: str = "f32"):
    """The mean of the shared experts — separate experts here."""
    S = int(cfg["num_shared_experts"])
    return _weighted_experts(n, lp["shared.gate"], lp["shared.up"],
                             lp["shared.down"],
                             jnp.full((S,) + n.shape[:-1], 1.0 / S), mode)


def embed(p: dict, ids, cfg: dict):
    return p["embed"][ids]


def block(lp: dict, h, cfg: dict, mode: str = "f32", layer: int = 0):
    n = _ln(h, lp["input_norm"], cfg["layer_norm_eps"])
    return (h + attention(lp, n, cfg, layer, mode)
            + routed(lp, n, cfg, mode) + shared(lp, n, cfg, mode))


def head(p: dict, h, cfg: dict, mode: str = "f32"):
    return mm(_ln(h, p["norm"], cfg["layer_norm_eps"]), p["embed"],
              mode) * cfg["logit_scale"]
