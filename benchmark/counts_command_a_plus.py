"""Operations and bytes of the Command A+ parallel block from shapes —
the arithmetic of the ``command-a-plus-*`` configurations, kept beside
``counts.py`` and under its conventions (a multiply-add is 2
operations; only what the algorithm requires counts; bytes are the
tensors a call must read and write once, at the dtype they are stored
in).  ``counts.py`` is not fed these configurations: its llama branch
would take the keys and count a dense model.

A layer holds one LayerNorm gain, grouped-query attention, a router
over ALL published experts, ``num_experts`` HELD routed SwiGLU experts
(this chip's share) and ``num_shared_experts`` shared ones of the same
width.  Layers go in periods of ``layer_switch``: one full-attention
layer, the others see ``sliding_window`` positions.  The head is the
embedding.
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    """Sizes by one set of names, from the configuration's own keys."""
    layers, period = int(cfg["num_hidden_layers"]), int(cfg["layer_switch"])
    full_at = (period - 1 if cfg["order_of_interleaved_layers"]
               == "local_attn_first" else 0)
    full = sum(1 for i in range(layers) if i % period == full_at)
    return {"d": int(cfg["hidden_size"]), "layers": layers,
            "full_layers": full, "window_layers": layers - full,
            "window": int(cfg["sliding_window"]),
            "heads": int(cfg["num_attention_heads"]),
            "kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": int(cfg["head_dim"]),
            "ffn": int(cfg["intermediate_size"]),
            "vocab": int(cfg["vocab_size"]),
            "held": int(cfg["num_experts"]),
            "experts": int(cfg["num_experts_published"]),
            "top_k": int(cfg["num_experts_per_tok"]),
            "shared": int(cfg["num_shared_experts"])}


def attention_params(cfg: dict) -> int:
    m = dims(cfg)
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return 2 * m["d"] * q + 2 * m["d"] * kv


def expert_params(cfg: dict) -> int:
    """One SwiGLU expert: gate, up, down."""
    m = dims(cfg)
    return 3 * m["d"] * m["ffn"]


def layer_params(cfg: dict) -> int:
    """Every stored parameter of one block at this chip's share."""
    m = dims(cfg)
    return (attention_params(cfg) + m["experts"] * m["d"]
            + (m["held"] + m["shared"]) * expert_params(cfg) + m["d"])


def total_params(cfg: dict) -> int:
    """Every stored parameter: the tied embedding's rows held here, the
    blocks, the final norm's gain."""
    m = dims(cfg)
    return m["layers"] * layer_params(cfg) + m["vocab"] * m["d"] + m["d"]


def experts_hit(cfg: dict, batch: float) -> float:
    """Held experts that at least one of ``batch`` tokens chooses, in
    expectation under even routing (seeded weights route evenly): a
    held expert's weights count only in the steps that hit it."""
    m = dims(cfg)
    return m["held"] * (1.0 - (1.0 - m["top_k"] / m["experts"]) ** batch)


def kv_positions(cfg: dict, positions: float) -> dict:
    """Positions of K/V a token at ``positions`` attends to, by kind of
    layer: all of them in a full layer, at most the window in a sliding
    one."""
    m = dims(cfg)
    return {"full": float(positions),
            "window": float(min(positions, m["window"]))}


def decode_step_bytes(cfg: dict, batch: float, positions: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step must move for ``batch`` rows at a mean
    context of ``positions``: attention, router and shared-expert
    weights and the head (the embedding) once, each HIT held expert
    once, and the K and V each kind of layer attends to.  A program
    that reads its whole static cache, or an expert no token chose,
    reads more; the extra is its loss, not the algorithm's need."""
    m = dims(cfg)
    per_layer = (attention_params(cfg) + m["experts"] * m["d"] + m["d"]
                 + (m["shared"] + experts_hit(cfg, batch))
                 * expert_params(cfg))
    weights = (m["layers"] * per_layer + m["vocab"] * m["d"]) * itemsize
    seen = kv_positions(cfg, positions)
    per_pos = 2 * m["kv_heads"] * m["head_dim"] * itemsize
    kv = batch * per_pos * (m["full_layers"] * seen["full"]
                            + m["window_layers"] * seen["window"])
    return float(weights + kv)


def expert_matmul_call(cfg: dict, tokens: float, itemsize: int = 2) -> dict:
    """Operations and bytes of ONE layer's three grouped products (and
    the gate between them) for ``tokens`` tokens routed over: the rows
    that land on held experts, ``tokens x top_k x held / experts`` under
    even routing, times the three matrices of an expert; bytes: the hit
    experts' weights once, the rows in, the hidden twice out and once
    in, the rows out."""
    m = dims(cfg)
    rows = tokens * m["top_k"] * m["held"] / m["experts"]
    flops = 3 * 2.0 * rows * m["d"] * m["ffn"]
    nbytes = (experts_hit(cfg, tokens) * expert_params(cfg)
              + rows * (2 * m["d"] + 3 * m["ffn"])) * itemsize
    return {"flops": flops, "bytes": float(nbytes), "rows": rows}
