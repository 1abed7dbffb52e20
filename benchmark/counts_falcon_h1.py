"""Operations and bytes of the Falcon-H1 block from shapes — the
arithmetic of the ``falcon-h1-*`` configurations, kept beside
``counts.py`` and under its conventions (a multiply-add is 2
operations; only what the algorithm requires counts; bytes are the
tensors a call must read and write once, at the dtype they are stored
in).  ``counts.py`` is not fed these configurations: its llama branch
would take the keys and count a different model.

A layer holds, beside grouped-query attention and the SwiGLU MLP, a
Mamba-2 mixer: ``in_proj`` d -> (d_ssm gate | d_ssm x | G N B | G N C |
heads dt), a depthwise conv of width ``d_conv`` over the middle three,
the recurrent state ``[heads, head, N]`` in float32, a gated norm over
d_ssm, ``out_proj`` d_ssm -> d.
"""
from __future__ import annotations

STATE_ITEMSIZE = 4   # the SSM state is float32 whatever the weights are


def dims(cfg: dict) -> dict:
    """Sizes by one set of names, from the configuration's own keys."""
    heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    mh, mp = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    g, n = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    d_ssm = int(cfg["mamba_d_ssm"])
    if d_ssm != mh * mp:
        raise ValueError(f"mamba_d_ssm {d_ssm} != heads {mh} x head {mp}")
    return {"d": int(cfg["hidden_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "heads": heads, "kv_heads": kv, "head_dim": int(cfg["head_dim"]),
            "ffn": int(cfg["intermediate_size"]),
            "vocab": int(cfg["vocab_size"]),
            "m_heads": mh, "m_head": mp, "groups": g, "state": n,
            "d_ssm": d_ssm, "conv_dim": d_ssm + 2 * g * n,
            "d_conv": int(cfg["mamba_d_conv"]),
            "chunk": int(cfg["mamba_chunk_size"])}


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one block that a token is multiplied by: ``in_proj``,
    ``out_proj``, the four attention projections, the three MLP
    matrices."""
    m = dims(cfg)
    in_proj = m["d"] * (m["d_ssm"] + m["conv_dim"] + m["m_heads"])
    out_proj = m["d_ssm"] * m["d"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    attn = 2 * m["d"] * q + 2 * m["d"] * kv
    return in_proj + out_proj + attn + 3 * m["d"] * m["ffn"]


def layer_params(cfg: dict) -> int:
    """Every stored parameter of one block: the matrices, two RMSNorm
    gains, the conv's weight and bias, ``dt_bias`` / ``A_log`` / ``D``
    and the gated norm's gain."""
    m = dims(cfg)
    small = (2 * m["d"] + (m["d_conv"] + 1) * m["conv_dim"]
             + 3 * m["m_heads"] + m["d_ssm"])
    return layer_matmul_params(cfg) + small


def matmul_params(cfg: dict) -> int:
    """Every weight a token is multiplied by: the blocks and the untied
    head (the embedding is a look-up)."""
    m = dims(cfg)
    return m["layers"] * layer_matmul_params(cfg) + m["d"] * m["vocab"]


def total_params(cfg: dict) -> int:
    """Every stored parameter: embedding, blocks, final norm, head."""
    m = dims(cfg)
    return (m["layers"] * layer_params(cfg) + 2 * m["vocab"] * m["d"]
            + m["d"])


def state_bytes(cfg: dict, itemsize: int = 2) -> dict:
    """Bytes of what ONE row keeps in ONE layer between two tokens: the
    SSM state (float32) and the conv tail (``d_conv - 1`` steps at the
    activations' ``itemsize``), and of one cached position's K and V."""
    m = dims(cfg)
    return {"ssm": m["m_heads"] * m["m_head"] * m["state"] * STATE_ITEMSIZE,
            "conv": (m["d_conv"] - 1) * m["conv_dim"] * itemsize,
            "kv_per_position": 2 * m["kv_heads"] * m["head_dim"] * itemsize}


def decode_step_bytes(cfg: dict, batch: float, cache_len: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step must move: every block weight and the head
    once (bf16), the K and V of the ``cache_len`` positions the step
    attends to, and the SSM state and conv tail READ AND WRITTEN once,
    for ``batch`` rows.  A program that reads its whole static cache
    reads more; the extra is its loss, not the algorithm's need."""
    m, s = dims(cfg), state_bytes(cfg, itemsize)
    weights = matmul_params(cfg) * itemsize
    kv = m["layers"] * batch * cache_len * s["kv_per_position"]
    recurrent = m["layers"] * batch * 2 * (s["ssm"] + s["conv"])
    return float(weights + kv + recurrent)


def ssd_scan_call(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """Operations and bytes of ONE chunked scan (one layer, one prefill
    of ``batch`` x ``seq``) in chunks of ``mamba_chunk_size``; a ragged
    tail computes a whole chunk.  Operations: within a chunk the causal
    half of C B^T per group and of its product with x per head; per
    chunk the state it adds and the read-out of the state it enters
    with (two [Q x head x N] matmuls a head).  Bytes: x, B, C (at
    ``itemsize``) and dt (float32) in; y and the final state out, both
    float32 as the program stores them."""
    m = dims(cfg)
    q = m["chunk"]
    t = -(-seq // q) * q
    hp = m["m_heads"] * m["m_head"]
    scores = 2.0 * batch * t * q * m["groups"] * m["state"] * 0.5
    apply = 2.0 * batch * t * q * hp * 0.5
    states = 2.0 * batch * t * hp * m["state"]
    flops = scores + apply + 2 * states
    gn = m["groups"] * m["state"]
    nbytes = (batch * seq * (hp + 2 * gn) * itemsize          # x, B, C
              + batch * seq * m["m_heads"] * 4                # dt
              + batch * seq * hp * 4                          # y
              + batch * hp * m["state"] * STATE_ITEMSIZE)     # final state
    return {"flops": flops, "bytes": float(nbytes)}
