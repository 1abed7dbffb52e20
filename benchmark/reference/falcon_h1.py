"""The Falcon-H1 block as published (``transformers``
``modeling_falcon_h1.py``; TII 2025): in every layer a Mamba-2 mixer and
grouped-query attention read the SAME RMS-normed input and their outputs
are summed into the residual, then a SwiGLU MLP; the family's muP
multipliers scale the embedding, the head, the attention's input, keys
and output, the mixer's input, five slices of its projection and its
output, and the MLP's gate and output.  Weights are [out, in].

Plain on purpose: float32, every product through ``common.mm`` at
HIGHEST (so the fp8 control reaches them), the recurrence as ONE
``lax.scan`` over time — no chunks, no cache, no batching tricks.  The
recurrence itself, the norms, the softmax and the residual sums stay
float32 under the control, as fp8 recipes keep them.

    u   = rms(x; input_norm)
    p   = in_proj(u * ssm_in_multiplier) * mup      mup over [z|x|B|C|dt]
    z, xBC, dt_raw = split(p, [d_ssm, d_ssm + 2 G N, heads])
    xBC = silu(causal depthwise conv1d(xBC; width d_conv, bias))
    xs, Bm, Cm = split(xBC)        xs [heads, P]; Bm, Cm [G, N]
    dt  = softplus(dt_raw + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t xs_t (x) Bm_t        head h, group h // (heads / G)
    y_t = S_t Cm_t + D xs_t
    y   = grouped_rms(y * silu(z); G groups, weight norm)
    m   = out_proj(y) * ssm_out_multiplier
    q   = wq(u * attention_in_multiplier); k = wk(same) * key_multiplier; v = wv(same)
    a   = wo(causal attention(rope(q), rope(k), v)) * attention_out_multiplier
    x   = x + m + a
    f   = rms(x; pre_ff_norm)
    x   = x + down(up(f) * silu(gate(f) * mlp_gate_multiplier)) * mlp_down_multiplier

Departures and assumptions (also under ``assumed`` in the configuration):

* ``common.hashable`` hands a reference the configuration's SCALAR keys
  only, so the published lists ``ssm_multipliers`` / ``mlp_multipliers``
  are read from the scalar keys ``ssm_multiplier_z/_x/_B/_C/_dt`` and
  ``mlp_gate_multiplier`` / ``mlp_down_multiplier`` (equal to the lists,
  entry for entry; a test holds them so).
* ``make_params`` knows three initialisers.  ``A_log``, ``dt_bias`` and
  the conv bias are ``zeros`` (so ``A`` = -1 and ``dt`` = softplus of the
  projection; published: A uniform in [1, 16], dt log-uniform in
  [1e-3, 1e-1]); ``D`` and the norm gains ``ones``; every matrix
  ``normal(0, initializer_range)``.  The conv weight is ``ones`` (a box
  filter over the last d_conv steps; published: torch's default,
  uniform in +-1/sqrt(d_conv) = +-0.5): drawn ``normal(0, 0.02)`` it
  would leave B and C at 1e-3, the state's share of the mixer's output
  at 5e-5, and no comparison could tell a wrong state from a right one.
  ``conv_w`` is stored [d_conv, channels] (published [channels, 1,
  d_conv]); ``conv_w[d_conv - 1]`` multiplies the current step.
* The gated norm is the published ``norm_before_gate = false`` form:
  gate first, then RMS over each of the G groups of d_ssm / G channels.
* The published module also multiplies ``dt`` by nothing further and
  clamps it to ``time_step_limit`` = (0, inf): no clamp here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import causal_attention, merge_heads, mm, split_heads


def _sizes(cfg):
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    d_ssm = cfg["mamba_d_ssm"]
    assert d_ssm == H * P, (d_ssm, H, P)
    return H, P, G, N, d_ssm, d_ssm + 2 * G * N


def param_specs(cfg: dict) -> dict:
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    H, _, _, _, d_ssm, conv = _sizes(cfg)
    return {
        "top": {"embed": ((v, d), "normal"), "norm": ((d,), "ones"),
                "lm_head": ((v, d), "normal")},
        "layer": {
            "input_norm": ((d,), "ones"),
            "attn.wq": ((q, d), "normal"), "attn.wk": ((kv, d), "normal"),
            "attn.wv": ((kv, d), "normal"), "attn.wo": ((d, q), "normal"),
            "mixer.in_proj": ((d_ssm + conv + H, d), "normal"),
            "mixer.conv_w": ((cfg["mamba_d_conv"], conv), "ones"),
            "mixer.conv_b": ((conv,), "zeros"),
            "mixer.dt_bias": ((H,), "zeros"),
            "mixer.A_log": ((H,), "zeros"),
            "mixer.D": ((H,), "ones"),
            "mixer.norm": ((d_ssm,), "ones"),
            "mixer.out_proj": ((d, d_ssm), "normal"),
            "pre_ff_norm": ((d,), "ones"),
            "mlp.gate": ((f, d), "normal"), "mlp.up": ((f, d), "normal"),
            "mlp.down": ((d, f), "normal"),
        }}


def n_layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [B,H,T,D] rotated at positions 0..T-1, rotate-half convention."""
    T, D = x.shape[2], x.shape[3]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def embed(p: dict, ids, cfg: dict):
    return p["embed"][ids] * cfg["embedding_multiplier"]


def mixer(lp: dict, u, cfg: dict, mode: str = "f32"):
    """The Mamba-2 mixer on the normed input u [B, T, d] -> [B, T, d]."""
    H, P, G, N, d_ssm, conv = _sizes(cfg)
    B_, T, _ = u.shape
    mup = jnp.concatenate([
        jnp.full((n,), cfg[f"ssm_multiplier_{s}"], jnp.float32)
        for s, n in (("z", d_ssm), ("x", d_ssm), ("B", G * N), ("C", G * N),
                     ("dt", H))])
    p = mm(u * cfg["ssm_in_multiplier"], lp["mixer.in_proj"], mode) * mup
    z, xbc, dt_raw = jnp.split(p, [d_ssm, d_ssm + conv], -1)
    K = cfg["mamba_d_conv"]
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = lp["mixer.conv_b"] + sum(xp[:, k:k + T] * lp["mixer.conv_w"][k]
                                   for k in range(K))
    xs, Bm, Cm = jnp.split(jax.nn.silu(xbc), [d_ssm, d_ssm + G * N], -1)
    xs = xs.reshape(B_, T, H, P)
    Bm = jnp.repeat(Bm.reshape(B_, T, G, N), H // G, 2)     # per head
    Cm = jnp.repeat(Cm.reshape(B_, T, G, N), H // G, 2)
    dt = jax.nn.softplus(dt_raw + lp["mixer.dt_bias"])      # [B, T, H]
    A = -jnp.exp(lp["mixer.A_log"])

    def step(S, t):
        x_t, b_t, c_t, dt_t = t
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return S, jnp.sum(S * c_t[:, :, None, :], -1)

    _, y = jax.lax.scan(step, jnp.zeros((B_, H, P, N), jnp.float32),
                        tuple(a.swapaxes(0, 1) for a in (xs, Bm, Cm, dt)))
    y = y.swapaxes(0, 1) + lp["mixer.D"][:, None] * xs      # [B, T, H, P]
    y = y.reshape(B_, T, d_ssm) * jax.nn.silu(z)
    y = _rms(y.reshape(B_, T, G, d_ssm // G), 1.0, cfg["rms_norm_eps"])
    y = y.reshape(B_, T, d_ssm) * lp["mixer.norm"]
    return mm(y, lp["mixer.out_proj"], mode) * cfg["ssm_out_multiplier"]


def attention(lp: dict, u, cfg: dict, mode: str = "f32"):
    theta = float(cfg["rope_theta"])    # published as an integer, 1e11
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a = u * cfg["attention_in_multiplier"]
    q = _rope(split_heads(mm(a, lp["attn.wq"], mode), H), theta)
    k = _rope(split_heads(mm(a, lp["attn.wk"], mode)
                          * cfg["key_multiplier"], Hkv), theta)
    v = split_heads(mm(a, lp["attn.wv"], mode), Hkv)
    o = merge_heads(causal_attention(q, k, v, mode))
    return mm(o, lp["attn.wo"], mode) * cfg["attention_out_multiplier"]


def block(lp: dict, h, cfg: dict, mode: str = "f32"):
    eps = cfg["rms_norm_eps"]
    u = _rms(h, lp["input_norm"], eps)
    h = h + mixer(lp, u, cfg, mode) + attention(lp, u, cfg, mode)
    f = _rms(h, lp["pre_ff_norm"], eps)
    g = jax.nn.silu(mm(f, lp["mlp.gate"], mode) * cfg["mlp_gate_multiplier"])
    return h + mm(g * mm(f, lp["mlp.up"], mode), lp["mlp.down"],
                  mode) * cfg["mlp_down_multiplier"]


def head(p: dict, h, cfg: dict, mode: str = "f32"):
    return mm(_rms(h, p["norm"], cfg["rms_norm_eps"]), p["lm_head"],
              mode) * cfg["lm_head_multiplier"]
