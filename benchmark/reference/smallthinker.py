"""The SmallThinker block as published (``smallthinker``;
``PowerInfer/SmallThinker-21BA3B-Instruct`` ``config.json``,
arXiv:2507.20984): a SEQUENTIAL pre-norm block of grouped-query
attention and a mixture of small ReLU-gated experts whose ROUTER reads
the block's input — before the first norm, before attention.  RMSNorm
(eps ``rms_norm_eps``) throughout, no bias anywhere, no QK-norm; ``x_t``
is position ``t`` of layer ``l``'s input.

    r_t = W_r x_t                                  [E] router logits, float32,
                                                   from the block's INPUT
    n_t = RMSNorm_1(x_t)
    q_t = W_q n_t [H, d];  k_t = W_k n_t [G, d];  v_t = W_v n_t [G, d]
    if rope_layout[l]:  q_t, k_t rotated at t      by halves: pairs (i, i + d/2),
                                                   f_i = theta^(-2i/d); no scaling
    S_l(t) = {s <= t}                              sliding_window_layout[l] == 0:
                                                   global, and NO positions
           = {s : t - W < s <= t}                  else: the last W keys
    a_t = W_o concat_h softmax_{s in S_l(t)}(q_t[h] . k_s[g(h)] / sqrt(d)) v_s[g(h)]
                                                   g(h) = h // (H / G)
    h_t = x_t + a_t
    m_t = RMSNorm_2(h_t)
    (v, idx) = top_K(r_t);  g = softmax(v)         over the K chosen logits
    y_t = h_t + sum_k g_k W_down[idx_k](relu(W_gate[idx_k] m_t) * W_up[idx_k] m_t)

Embedding lookup in front, one RMSNorm after the last layer, a head of
its own (``tie_word_embeddings`` false), float32 logits.
``norm_topk_prob`` true changes nothing under
``moe_primary_router_apply_softmax`` true: the softmax of the chosen
logits already sums to 1.

Plain on purpose: float32, every product at HIGHEST, attention over the
whole sequence with the scores written out — a block of queries at a
time (``QUERY_BLOCK``), so that a sequence of thousands of positions
keeps ``[B, H, block, T]`` of scores and not ``[B, H, T, T]``; no ring,
no cache, no kernel; every held expert applied to EVERY token through a
``lax.scan`` over the stacked leaves and masked by the selection.
Weights are [out, in], but the experts': ``moe.gate`` / ``moe.up``
[expert, in, out] and ``moe.down`` [expert, hidden, out], the layout
the program holds them in.

Departures and assumptions (also under ``assumed`` in the configuration):

* Rotation by halves over the whole head (the family's code; the
  config has no key for the pairing).
* The router reads the UN-NORMED block input (``described_as``: "router
  placed before attention"; the published modelling file hands the
  decoder layer's input to the router before ``input_layernorm``).
* ``described_as``'s "secondary experts" have no key in this
  checkpoint's ``config`` and are absent.
* ``common.hashable`` hands a reference scalars and strings only: the
  kind of a layer comes from ``layer`` and the two layouts as TEXT
  (``rope_layout_text`` / ``sliding_window_layout_text``, one digit a
  layer, repeated down the stack), which ``param_specs`` holds to the
  published lists.
* The experts held are ``num_experts_held`` of
  ``moe_num_primary_experts`` from ``first_expert_held`` on (default:
  all of them, which is what the benchmark's configuration holds); what
  an absent expert would add is left out.
* The router's logits and the gates are float32 in every mode: under
  the fp8 control the products around them are rounded, the selection
  is not (as the norms, the softmax and the residual sums are not).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import HIGHEST, _low, merge_heads, mm, split_heads
from .glm4_moe_lite import _rms, _rope_halves

#: queries a block of the written-out attention
QUERY_BLOCK = 256


def _held(cfg: dict) -> tuple:
    return (int(cfg.get("first_expert_held", 0)),
            int(cfg.get("num_experts_held", cfg["moe_num_primary_experts"])))


def param_specs(cfg: dict) -> dict:
    for key in ("rope_layout", "sliding_window_layout"):
        text = "".join(str(int(v)) for v in cfg[key])
        if cfg[key + "_text"] != text:
            raise ValueError(f"{key}_text {cfg[key + '_text']!r} is not "
                             f"{key} {cfg[key]}")
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["moe_ffn_hidden_size"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    e = _held(cfg)[1]
    return {
        "top": {"embed": ((v, d), "normal"), "norm": ((d,), "ones"),
                "head": ((v, d), "normal")},
        "layer": {
            "input_norm": ((d,), "ones"), "post_norm": ((d,), "ones"),
            "attn.wq": ((q, d), "normal"), "attn.wk": ((kv, d), "normal"),
            "attn.wv": ((kv, d), "normal"), "attn.wo": ((d, q), "normal"),
            "moe.router": ((cfg["moe_num_primary_experts"], d), "normal"),
            "moe.gate": ((e, d, f), "normal"), "moe.up": ((e, d, f), "normal"),
            "moe.down": ((e, f, d), "normal"),
        }}


def n_layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def _flag(cfg: dict, key: str, layer: int) -> bool:
    """``key``'s entry for ``layer``: the list where the configuration
    is whole, its text where the harness handed scalars only."""
    layout = cfg.get(key) or cfg[key + "_text"]
    return bool(int(layout[layer % len(layout)]))


def _attend(q, k, v, window, mode):
    """q [B,H,T,D], k/v [B,G,T,D]; query t sees keys max(0, t - window
    + 1) .. t (``window`` None: 0 .. t).  Scores written out for
    ``QUERY_BLOCK`` queries at a time against every key."""
    B, H, T, D = q.shape
    G = k.shape[1]
    bq = min(T, QUERY_BLOCK)
    blocks = -(-T // bq)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, blocks * bq - T), (0, 0)))
    q = q.reshape(B, G, H // G, blocks, bq, D)
    k, v = _low(k, mode), _low(v, mode)
    keys = jnp.arange(T)

    def block(args):
        qb, first = args                        # [B, G, H/G, bq, D]
        back = (first + jnp.arange(bq))[:, None] - keys[None, :]
        seen = back >= 0
        if window is not None:
            seen &= back < window
        s = jnp.einsum("bghqd,bgkd->bghqk", _low(qb, mode), k,
                       precision=HIGHEST)
        s = _low(s, mode) / jnp.sqrt(jnp.float32(D))
        s = jnp.where(seen, s, -jnp.inf)
        p = _low(jax.nn.softmax(s, axis=-1), mode)
        return _low(jnp.einsum("bghqk,bgkd->bghqd", p, v,
                               precision=HIGHEST), mode)

    o = jax.lax.map(block, (jnp.moveaxis(q, 3, 0), jnp.arange(blocks) * bq))
    return jnp.moveaxis(o, 0, 3).reshape(B, H, blocks * bq, D)[:, :, :T]


def attention(lp: dict, n, cfg: dict, layer: int, mode: str = "f32"):
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = split_heads(mm(n, lp["attn.wq"], mode), H)
    k = split_heads(mm(n, lp["attn.wk"], mode), G)
    v = split_heads(mm(n, lp["attn.wv"], mode), G)
    if _flag(cfg, "rope_layout", layer):
        theta = float(cfg["rope_theta"])
        q, k = _rope_halves(q, theta), _rope_halves(k, theta)
    window = (int(cfg["sliding_window_size"])
              if _flag(cfg, "sliding_window_layout", layer) else None)
    return mm(merge_heads(_attend(q, k, v, window, mode)), lp["attn.wo"],
              mode)


def select(lp: dict, x, cfg: dict):
    """(gates [..., K] float32, experts [..., K]) from the block's INPUT
    ``x``: the K largest router logits and a softmax over THEM —
    float32 at HIGHEST in every mode."""
    r = mm(x, lp["moe.router"], "f32")
    v, idx = jax.lax.top_k(r, int(cfg["moe_num_active_primary_experts"]))
    return jax.nn.softmax(v, axis=-1), idx


def routed(lp: dict, m, x, cfg: dict, mode: str = "f32"):
    """The HELD experts' part of the mixture for the normed state ``m``
    under the gates ``x`` (the block's input) gives: each expert applied
    to EVERY token, one after another (``lax.scan`` over the stacked
    leaves: the compiled program holds ONE expert's body), weighted by
    the token's gate for it (zero where the token did not choose it)."""
    g, idx = select(lp, x, cfg)
    first, count = _held(cfg)
    held = first + jnp.arange(count)
    weights = jnp.sum(
        jnp.where(idx[None] == held.reshape((-1,) + (1,) * idx.ndim),
                  g[None], 0.0), -1)                     # [held, ...tokens]

    def one(y, ew):
        gate, up, down, w = ew      # [in, hidden] x 2, [hidden, out]
        h = jax.nn.relu(mm(m, gate.T, mode)) * mm(m, up.T, mode)
        return y + w[..., None] * mm(h, down.T, mode), None

    return jax.lax.scan(one, jnp.zeros_like(m),
                        (lp["moe.gate"], lp["moe.up"], lp["moe.down"],
                         weights))[0]


def embed(p: dict, ids, cfg: dict):
    return p["embed"][ids]


def block(lp: dict, x, cfg: dict, mode: str = "f32", layer: int = 0):
    eps = cfg["rms_norm_eps"]
    h = x + attention(lp, _rms(x, lp["input_norm"], eps), cfg, layer, mode)
    return h + routed(lp, _rms(h, lp["post_norm"], eps), x, cfg, mode)


def head(p: dict, h, cfg: dict, mode: str = "f32"):
    return mm(_rms(h, p["norm"], cfg["rms_norm_eps"]), p["head"], mode)
