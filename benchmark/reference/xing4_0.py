"""The Xing4.0-29B-A4B block as published (``xing4_0``;
``XingChen-AGI/Xing4.0-29B-A4B`` ``config.json``): GLM-4.7-Flash's /
DeepSeek-V3's layers — latent attention (here with YaRN and a value head
narrower than the query's), a dense SwiGLU in the first
``first_k_dense_replace`` layers and bias-corrected sigmoid-routed
experts after them — around a residual that is not one vector a token
but ``n = hc_mult`` STREAMS, mixed per token by manifold-constrained
hyper-connections (mHC, arXiv:2512.24880).  The state of a token is ``X
[n, C]``; ``X_0`` is the embedding row repeated ``n`` times.  Each layer
is two sublayers ``f`` (attention behind ``input_norm``, the FFN behind
``post_norm``), EACH with hyper-connection leaves of its own — ``phi``
``[n + n + n^2, n C]`` (stored [out, in]: ``x~ phi`` is ``mm(x~, phi)``),
scalars ``alpha_pre / alpha_post / alpha_res``, ``b_pre`` / ``b_post``
``[n]``, ``b_res`` ``[n, n]``:

    x~      = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)   all n C numbers, no gain
    [p|q|r] = x~ phi
    H_pre   = sigmoid(alpha_pre p + b_pre)                    [n]
    H_post  = 2 sigmoid(alpha_post q + b_post)                [n]
    M       = exp(clip(alpha_res mat(r) + b_res,
                       mhc_h_res_clamp_min, mhc_h_res_clamp_max))     [n, n]
    hc_sinkhorn_iters times:
        M <- M / (rowsum(M) + hc_eps);  M <- M / (colsum(M) + hc_eps)
    H_res   = M
    u       = sum_i H_pre[i] X[i]
    y       = f(RMSNorm(u))
    X'[i]   = sum_j H_res[i, j] X[j] + H_post[i] y

After the last layer ``h = sum_i X[i]``, then the final RMSNorm and the
head (a matrix of its own).

    Attn(n): ``glm4_moe_lite``'s (that file's docstring) at this
      configuration's sizes, with YaRN as DeepSeek-V3's modelling code
      computes it from ``rope_scaling``: for pair i < rope / 2,
        f_i = theta^(-2i / rope),  g_i = f_i / factor
        low  = floor(rope ln(orig / (beta_fast 2 pi)) / (2 ln theta))
        high = ceil (rope ln(orig / (beta_slow 2 pi)) / (2 ln theta))
               both clipped to [0, rope - 1]
        ramp_i = clip((i - low) / (high - low), 0, 1)
        inv_freq_i = g_i ramp_i + f_i (1 - ramp_i)
      cos and sin times m(mscale) / m(mscale_all_dim), and the softmax
      scale (nope + rope)^(-1/2) m(mscale_all_dim)^2, with
      m(s) = 0.1 s ln(factor) + 1.  Rotation by halves.
    FFN: ``glm4_moe_lite``'s dense SwiGLU and its mixture (sigmoid
      scores over ALL router outputs, the correction bias in the
      selection only, the chosen renormalised and times
      ``routed_scaling_factor``, the HELD experts' part and the shared
      expert).

Plain on purpose: float32, every product at HIGHEST, the expanded
attention at every position, whole scores, no cache, no kernels; the
state between layers is ``[B, T, n, C]``; ``embed`` replicates and
``head`` reduces.  The coefficient path (``x~``, the product with
``phi``, sigmoids, ``exp``, the sweeps) and the two mixes are float32 in
EVERY mode, as the router's scores are: under the fp8 control the
sublayers' products are rounded and the hyper-connections are not.

How the layers reach the harness: as ``glm4_moe_lite``'s do — the
leading dense layers are part of ``embed`` (top-level leaves
``dense.<j>.<leaf>``), ``n_layers`` counts the expert layers.  The
scalar keys ``rope_scaling_<key>`` repeat the ``rope_scaling`` object
(``reference.common.hashable`` hands a reference scalars only).

Departures and assumptions are listed under ``assumed`` in the
configuration.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import glm4_moe_lite as glm
from .common import HIGHEST, _low, merge_heads, mm, split_heads

_rms = glm._rms
n_layers = glm.n_layers


def _hc_specs(cfg: dict, sub: str) -> dict:
    n, d = int(cfg["hc_mult"]), int(cfg["hidden_size"])
    return {f"{sub}.phi": ((2 * n + n * n, n * d), "normal"),
            f"{sub}.alpha_pre": ((), "ones"),
            f"{sub}.alpha_post": ((), "ones"),
            f"{sub}.alpha_res": ((), "ones"),
            f"{sub}.b_pre": ((n,), "normal"),
            f"{sub}.b_post": ((n,), "normal"),
            f"{sub}.b_res": ((n, n), "normal")}


def _layer_hc_specs(cfg: dict) -> dict:
    return {**_hc_specs(cfg, "attn_hc"), **_hc_specs(cfg, "ffn_hc")}


def dense_specs(cfg: dict) -> dict:
    return {**glm.dense_specs(cfg), **_layer_hc_specs(cfg)}


def param_specs(cfg: dict) -> dict:
    specs = glm.param_specs(cfg)
    for j in range(int(cfg["first_k_dense_replace"])):
        specs["top"].update({f"dense.{j}.{n}": sk
                             for n, sk in _layer_hc_specs(cfg).items()})
    specs["layer"].update(_layer_hc_specs(cfg))
    return specs


# -- the hyper-connection ------------------------------------------------
def coefficients(lp: dict, sub: str, X, cfg: dict):
    """(H_pre [..., n], H_post [..., n], H_res [..., n, n]) of every
    token of ``X [..., n, C]`` — float32 at HIGHEST in every mode."""
    n = int(cfg["hc_mult"])
    flat = X.reshape(X.shape[:-2] + (-1,))
    xt = flat / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True)
                         + cfg["rms_norm_eps"])
    z = mm(xt, lp[f"{sub}.phi"], "f32")
    pre = jax.nn.sigmoid(lp[f"{sub}.alpha_pre"] * z[..., :n]
                         + lp[f"{sub}.b_pre"])
    post = 2.0 * jax.nn.sigmoid(lp[f"{sub}.alpha_post"] * z[..., n:2 * n]
                                + lp[f"{sub}.b_post"])
    r = z[..., 2 * n:].reshape(z.shape[:-1] + (n, n))
    m = jnp.exp(jnp.clip(lp[f"{sub}.alpha_res"] * r + lp[f"{sub}.b_res"],
                         cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"]))
    for _ in range(int(cfg["hc_sinkhorn_iters"])):
        m = m / (jnp.sum(m, -1, keepdims=True) + cfg["hc_eps"])
        m = m / (jnp.sum(m, -2, keepdims=True) + cfg["hc_eps"])
    return pre, post, m


def sublayer(lp: dict, sub: str, norm: str, X, f, cfg: dict):
    """One hyper-connected sublayer ``f`` over the state ``X``."""
    pre, post, res = coefficients(lp, sub, X, cfg)
    u = jnp.einsum("...n,...nc->...c", pre, X, precision=HIGHEST)
    y = f(_rms(u, lp[norm], cfg["rms_norm_eps"]))
    return (jnp.einsum("...ij,...jc->...ic", res, X, precision=HIGHEST)
            + post[..., None] * y[..., None, :])


# -- latent attention with YaRN --------------------------------------------
def _yarn_m(factor: float, s: float) -> float:
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg: dict):
    """``inv_freq [rope / 2]`` of the blended rotation."""
    d, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    factor = float(cfg["rope_scaling_factor"])
    orig = float(cfg["rope_scaling_original_max_position_embeddings"])

    def boundary(rotations: float) -> float:
        return (d * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(boundary(cfg["rope_scaling_beta_fast"])), 0)
    high = min(math.ceil(boundary(cfg["rope_scaling_beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / d)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (f / factor) * ramp + f * (1.0 - ramp)


def _rope_halves(x, cfg: dict):
    """x [B,H,T,D] rotated at positions 0..T-1; dim i pairs with i+D/2."""
    D, T = x.shape[-1], x.shape[2]
    factor = float(cfg["rope_scaling_factor"])
    ms = (_yarn_m(factor, cfg["rope_scaling_mscale"])
          / _yarn_m(factor, cfg["rope_scaling_mscale_all_dim"]))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * yarn_inv_freq(cfg)[None]
    cos, sin = jnp.cos(ang) * ms, jnp.sin(ang) * ms
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def softmax_scale(cfg: dict) -> float:
    m = _yarn_m(float(cfg["rope_scaling_factor"]),
                cfg["rope_scaling_mscale_all_dim"])
    return ((cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
            * (m * m if cfg["rope_scaling_mscale_all_dim"] else 1.0))


def attention(lp: dict, n, cfg: dict, mode: str = "f32"):
    """The expanded form: per-head K and V for every position, whole
    scores, a value head of ``v_head_dim``."""
    H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    kvr, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    cq = _rms(mm(n, lp["attn.wq_a"], mode), lp["attn.q_norm"], eps)
    q = split_heads(mm(cq, lp["attn.wq_b"], mode), H)       # [B,H,T,nope+rope]
    q = jnp.concatenate([q[..., :nope], _rope_halves(q[..., nope:], cfg)], -1)
    ckr = mm(n, lp["attn.wkv_a"], mode)
    ckv = _rms(ckr[..., :kvr], lp["attn.kv_norm"], eps)
    k_rope = _rope_halves(ckr[:, None, :, kvr:], cfg)        # [B,1,T,rope]
    kv = split_heads(mm(ckv, lp["attn.wkv_b"], mode), H)     # [B,H,T,nope+v]
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(
            k_rope, kv.shape[:3] + (k_rope.shape[-1],))], -1)
    v = kv[..., nope:]
    T = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", _low(q, mode), _low(k, mode),
                   precision=HIGHEST)
    s = _low(s, mode) * softmax_scale(cfg)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    p = _low(jax.nn.softmax(s, axis=-1), mode)
    o = _low(jnp.einsum("bhqk,bhkd->bhqd", p, _low(v, mode),
                        precision=HIGHEST), mode)
    return mm(merge_heads(o), lp["attn.wo"], mode)


# -- the layers -------------------------------------------------------------
def _dense_ffn(lp: dict, n, mode: str):
    act = jax.nn.silu(mm(n, lp["mlp.gate"], mode)) * mm(n, lp["mlp.up"], mode)
    return mm(act, lp["mlp.down"], mode)


def _layer(lp: dict, X, ffn, cfg: dict, mode: str):
    X = sublayer(lp, "attn_hc", "input_norm", X,
                 lambda n: attention(lp, n, cfg, mode), cfg)
    return sublayer(lp, "ffn_hc", "post_norm", X, ffn, cfg)


def dense_block(lp: dict, X, cfg: dict, mode: str = "f32"):
    return _layer(lp, X, lambda n: _dense_ffn(lp, n, mode), cfg, mode)


def embed(p: dict, ids, cfg: dict):
    """The lookup repeated into the ``hc_mult`` streams, then the
    leading dense layers (module docstring)."""
    h = p["embed"][ids]
    X = jnp.broadcast_to(h[..., None, :],
                         h.shape[:-1] + (int(cfg["hc_mult"]), h.shape[-1]))
    for j in range(int(cfg["first_k_dense_replace"])):
        X = dense_block({n: p[f"dense.{j}.{n}"] for n in dense_specs(cfg)},
                        X, cfg)
    return X


def block(lp: dict, X, cfg: dict, mode: str = "f32"):
    """One EXPERT layer over the state ``[B, T, n, C]``."""
    return _layer(
        lp, X, lambda n: (glm.routed(lp, n, cfg, mode)
                          + glm.shared(lp, n, cfg, mode)), cfg, mode)


def head(p: dict, X, cfg: dict, mode: str = "f32"):
    """The streams summed, the final norm, the head."""
    return mm(_rms(jnp.sum(X, -2), p["norm"], cfg["rms_norm_eps"]),
              p["head"], mode)
