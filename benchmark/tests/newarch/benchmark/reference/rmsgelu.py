"""A block neither reference of the benchmark describes, as a later PR
would drop it in: RMSNorm gains, bias-free grouped-query attention with
rotary positions, a two-matrix tanh-GELU MLP (with biases), no position
table, final RMSNorm, untied head.  Weights are [out, in].  ``block``
names a ``layer`` argument, so the comparison tells it which layer it
is computing — what a stack of several kinds of layer needs."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import causal_attention, merge_heads, mm, split_heads


def param_specs(cfg: dict) -> dict:
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    return {
        "top": {"embed": ((v, d), "normal"), "norm": ((d,), "ones"),
                "lm_head": ((v, d), "normal")},
        "layer": {
            "input_norm": ((d,), "ones"),
            "attn.wq": ((d, d), "normal"), "attn.wk": ((kv, d), "normal"),
            "attn.wv": ((kv, d), "normal"), "attn.wo": ((d, d), "normal"),
            "post_norm": ((d,), "ones"),
            "mlp.w_fc": ((f, d), "normal"), "mlp.b_fc": ((f,), "zeros"),
            "mlp.w_proj": ((d, f), "normal"), "mlp.b_proj": ((d,), "zeros"),
        }}


def n_layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    T, D = x.shape[2], x.shape[3]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def embed(p: dict, ids, cfg: dict):
    return p["embed"][ids]


def block(lp: dict, h, cfg: dict, mode: str = "f32", layer: int = 0):
    assert 0 <= layer < n_layers(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a = _rms(h, lp["input_norm"], eps)
    q = _rope(split_heads(mm(a, lp["attn.wq"], mode), H), theta)
    k = _rope(split_heads(mm(a, lp["attn.wk"], mode), Hkv), theta)
    v = split_heads(mm(a, lp["attn.wv"], mode), Hkv)
    o = merge_heads(causal_attention(q, k, v, mode))
    h = h + mm(o, lp["attn.wo"], mode)
    a = _rms(h, lp["post_norm"], eps)
    a = jax.nn.gelu(mm(a, lp["mlp.w_fc"], mode) + lp["mlp.b_fc"],
                    approximate=True)
    return h + mm(a, lp["mlp.w_proj"], mode) + lp["mlp.b_proj"]


def head(p: dict, h, cfg: dict, mode: str = "f32"):
    return mm(_rms(h, p["norm"], cfg["rms_norm_eps"]), p["lm_head"], mode)
