"""Operations and bytes of the SmallThinker block from shapes — the
arithmetic of the ``smallthinker-*`` configurations, kept beside
``counts.py`` and under its conventions (a multiply-add is 2 operations;
only what the algorithm requires counts; bytes are the tensors a call
must read and write once, at the dtype they are stored in).  No other
counts file is fed these configurations: none knows a layer whose cache
is a ring beside one whose cache grows.

A layer holds two RMSNorm gains, grouped-query attention (``d -> heads x
Dh``, twice ``d -> kv heads x Dh``, ``heads x Dh -> d``; no bias, no
per-head norm), a router over ALL ``moe_num_primary_experts`` and
``num_experts_held`` HELD ReLU-gated experts of ``moe_ffn_hidden_size``
(all of them in the benchmark's configuration); no dense FFN, no shared
expert.  Embedding and head are TWO matrices.  What a layer keeps
between decode steps: K and V, ``2 x kv heads x Dh`` numbers a position
— of every position where ``sliding_window_layout`` is 0, of the last
``sliding_window_size`` where it is 1 (a ring: a step reads ALL of it
once it is full).
"""
from __future__ import annotations

#: tokens one pass of the prompt holds (``generate.PREFILL_TOKENS``: the
#: program's rule, repeated here because a count imports no program)
PREFILL_TOKENS = 65536


def dims(cfg: dict) -> dict:
    """Sizes by one set of names, from the configuration's own keys."""
    layers = int(cfg["num_hidden_layers"])
    layout = list(cfg["sliding_window_layout"])
    windowed = sum(int(layout[i % len(layout)]) for i in range(layers))
    experts = int(cfg["moe_num_primary_experts"])
    return {"d": int(cfg["hidden_size"]), "layers": layers,
            "expert_layers": layers, "window_layers": windowed,
            "full_layers": layers - windowed,
            "window": int(cfg["sliding_window_size"]),
            "heads": int(cfg["num_attention_heads"]),
            "kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": int(cfg["head_dim"]),
            "expert_ffn": int(cfg["moe_ffn_hidden_size"]),
            "vocab": int(cfg["vocab_size"]), "experts": experts,
            "held": int(cfg.get("num_experts_held", experts)),
            "top_k": int(cfg["moe_num_active_primary_experts"])}


def attention_params(cfg: dict) -> int:
    """The four projections."""
    m = dims(cfg)
    return (2 * m["d"] * m["heads"] * m["head_dim"]
            + 2 * m["d"] * m["kv_heads"] * m["head_dim"])


def expert_params(cfg: dict) -> int:
    """One gated expert: gate, up, down."""
    m = dims(cfg)
    return 3 * m["d"] * m["expert_ffn"]


def router_params(cfg: dict) -> int:
    m = dims(cfg)
    return m["experts"] * m["d"]


def layer_params(cfg: dict) -> int:
    """Every stored parameter of one layer (all layers alike)."""
    m = dims(cfg)
    return (attention_params(cfg) + router_params(cfg)
            + m["held"] * expert_params(cfg) + 2 * m["d"])


def total_params(cfg: dict) -> int:
    """The layers, the embedding AND the head, the final norm's gain."""
    m = dims(cfg)
    return (m["layers"] * layer_params(cfg) + 2 * m["vocab"] * m["d"]
            + m["d"])


def experts_hit(cfg: dict, batch: float) -> float:
    """Held experts that at least one of ``batch`` tokens chooses, in
    expectation under even routing."""
    m = dims(cfg)
    return m["held"] * (1.0 - (1.0 - m["top_k"] / m["experts"]) ** batch)


def kv_position_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One cached position of ONE layer: K and V."""
    m = dims(cfg)
    return 2 * m["kv_heads"] * m["head_dim"] * itemsize


def decode_attend_bytes(cfg: dict, batch: float, positions: float,
                        itemsize: int = 2) -> float:
    """K and V the attends of ONE decode step must read for ``batch``
    rows at a context of ``positions``: a window layer the last
    ``min(positions, window)`` positions — its whole ring once the
    context has passed the window — a full layer every position
    written."""
    m = dims(cfg)
    per = batch * kv_position_bytes(cfg, itemsize)
    return (m["window_layers"] * per * min(positions, m["window"])
            + m["full_layers"] * per * positions)


def decode_step_parts(cfg: dict, batch: float, positions: float,
                      itemsize: int = 2) -> dict:
    """Bytes one greedy decode step must move for ``batch`` rows at a
    context of ``positions``, by part: the attention leaves, the K/V the
    attends read (:func:`decode_attend_bytes`), each HIT held expert
    once, routers and norm gains, the head once and the ``batch`` rows
    of the embedding the lookup gathers.  No logits: a greedy step needs
    the arg-max and not the row of logits.  A program that reads an
    expert no token chose, or its cache twice, reads more; the extra is
    its loss, not the algorithm's need."""
    m = dims(cfg)
    return {
        "attention_weights": m["layers"] * attention_params(cfg) * itemsize,
        "kv_cache": decode_attend_bytes(cfg, batch, positions, itemsize),
        "experts_hit": (m["expert_layers"] * experts_hit(cfg, batch)
                        * expert_params(cfg) * itemsize),
        "routers_and_norms": ((m["expert_layers"] * router_params(cfg)
                               + (2 * m["layers"] + 1) * m["d"])
                              * itemsize),
        "head": m["vocab"] * m["d"] * itemsize,
        "embedding_rows": batch * m["d"] * itemsize}


def decode_step_bytes(cfg: dict, batch: float, positions: float,
                      itemsize: int = 2) -> float:
    return float(sum(decode_step_parts(cfg, batch, positions,
                                       itemsize).values()))


def expert_matmul_call(cfg: dict, tokens: float, itemsize: int = 2) -> dict:
    """Operations and bytes of ONE expert layer's three grouped products
    (and the gate between them) for ``tokens`` tokens routed over: the
    rows that land on held experts, ``tokens x top_k x held / experts``
    under even routing, times the three matrices of an expert; bytes:
    the hit experts' weights once, the rows in, the hidden twice out and
    once in, the rows out."""
    m = dims(cfg)
    rows = tokens * m["top_k"] * m["held"] / m["experts"]
    flops = 3 * 2.0 * rows * m["d"] * m["expert_ffn"]
    nbytes = (experts_hit(cfg, tokens) * expert_params(cfg)
              + rows * (2 * m["d"] + 3 * m["expert_ffn"])) * itemsize
    return {"flops": flops, "bytes": float(nbytes), "rows": rows}


def prefill_group_rows(batch: int, prompt_len: int) -> int:
    """Rows of one group of the prompt pass: all of them within
    ``PREFILL_TOKENS``, else the largest power of two that divides the
    batch and holds at most that many tokens."""
    if batch * prompt_len <= PREFILL_TOKENS:
        return batch
    rows = 1
    while (batch % (2 * rows) == 0
           and 2 * rows * prompt_len <= PREFILL_TOKENS):
        rows *= 2
    return rows


def visible_pairs(t: int, window=None) -> int:
    """(query, key) pairs one causal head of ``t`` positions scores:
    query ``q`` sees ``min(q + 1, window)`` keys."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def prefill_attention_flops(cfg: dict, rows: int, t: int) -> float:
    """Operations of the flash forward calls of ONE pass of ``rows``
    rows of ``t`` positions through every layer: ``S = Q K^T`` and ``O =
    P V`` over the pairs the mask leaves (the window's in a window
    layer), every query head."""
    m = dims(cfg)
    pairs = (m["window_layers"] * visible_pairs(t, m["window"])
             + m["full_layers"] * visible_pairs(t))
    return 2 * 2.0 * rows * m["heads"] * pairs * m["head_dim"]
