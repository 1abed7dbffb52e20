"""GPT-2 as published (Radford et al. 2019; ``modeling_gpt2``): learned
positions, pre-LayerNorm blocks, biased projections, tanh-GELU MLP of
four times the width, final LayerNorm.  Departure, written in the
configuration file: the output head is its own matrix (no tying), with
no bias.  Weights are [out, in]."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import causal_attention, merge_heads, mm, split_heads


def param_specs(cfg: dict) -> dict:
    d, v, t = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    f = 4 * d
    return {
        "top": {"wte": ((v, d), "normal"), "wpe": ((t, d), "normal"),
                "ln_f.g": ((d,), "ones"), "ln_f.b": ((d,), "zeros"),
                "lm_head": ((v, d), "normal")},
        "layer": {
            "ln_1.g": ((d,), "ones"), "ln_1.b": ((d,), "zeros"),
            "attn.wq": ((d, d), "normal"), "attn.bq": ((d,), "zeros"),
            "attn.wk": ((d, d), "normal"), "attn.bk": ((d,), "zeros"),
            "attn.wv": ((d, d), "normal"), "attn.bv": ((d,), "zeros"),
            "attn.wo": ((d, d), "normal"), "attn.bo": ((d,), "zeros"),
            "ln_2.g": ((d,), "ones"), "ln_2.b": ((d,), "zeros"),
            "mlp.w_fc": ((f, d), "normal"), "mlp.b_fc": ((f,), "zeros"),
            "mlp.w_proj": ((d, f), "normal"), "mlp.b_proj": ((d,), "zeros"),
        }}


def n_layers(cfg: dict) -> int:
    return int(cfg["n_layer"])


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def embed(p: dict, ids, cfg: dict):
    return p["wte"][ids] + p["wpe"][: ids.shape[1]][None]


def block(lp: dict, h, cfg: dict, mode: str = "f32"):
    eps, heads = cfg["layer_norm_epsilon"], cfg["n_head"]
    a = _ln(h, lp["ln_1.g"], lp["ln_1.b"], eps)
    q = split_heads(mm(a, lp["attn.wq"], mode) + lp["attn.bq"], heads)
    k = split_heads(mm(a, lp["attn.wk"], mode) + lp["attn.bk"], heads)
    v = split_heads(mm(a, lp["attn.wv"], mode) + lp["attn.bv"], heads)
    o = merge_heads(causal_attention(q, k, v, mode))
    h = h + mm(o, lp["attn.wo"], mode) + lp["attn.bo"]
    a = _ln(h, lp["ln_2.g"], lp["ln_2.b"], eps)
    a = _gelu_new(mm(a, lp["mlp.w_fc"], mode) + lp["mlp.b_fc"])
    return h + mm(a, lp["mlp.w_proj"], mode) + lp["mlp.b_proj"]


def head(p: dict, h, cfg: dict, mode: str = "f32"):
    h = _ln(h, p["ln_f.g"], p["ln_f.b"], cfg["layer_norm_epsilon"])
    return mm(h, p["lm_head"], mode)
