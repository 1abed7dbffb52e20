"""Operations and bytes of the LFM2-24B-A2B block from shapes — the
arithmetic of the ``lfm2-24b-a2b-*`` configurations, kept beside
``counts.py`` and under its conventions (a multiply-add is 2
operations; only what the algorithm requires counts; bytes are the
tensors a call must read and write once, at the dtype they are stored
in).  No other counts file is fed these configurations: none knows a
layer without attention.

A layer holds two RMSNorm gains and ONE operator: the gated short
convolution (``d -> 3d``, a depthwise filter of ``conv_L_cache`` taps,
``d -> d``) where ``layer_types`` says ``conv``, grouped-query attention
(``d -> heads x Dh``, twice ``d -> kv heads x Dh``, ``heads x Dh -> d``
and the two per-head norms' gains of ``Dh``) where it says
``full_attention``.  Its FFN is a dense SwiGLU of ``intermediate_size``
in the first ``num_dense_layers`` layers and, after them, a router over
ALL ``num_experts`` with its bias and ``num_experts_held`` HELD routed
SwiGLU experts (all of them in the benchmark's configuration), no shared
expert.  Embedding and head are ONE matrix.  What a layer keeps between
decode steps: an attention layer K and V, ``2 x kv heads x Dh`` numbers
a position; a conv layer ``(conv_L_cache - 1) x d`` numbers whatever the
context.
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    """Sizes by one set of names, from the configuration's own keys."""
    types, dense = list(cfg["layer_types"]), int(cfg["num_dense_layers"])
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"d": d, "layers": len(types), "dense_layers": dense,
            "expert_layers": len(types) - dense,
            "conv_layers": types.count("conv"),
            "attention_layers": types.count("full_attention"),
            "heads": heads, "kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": d // heads, "taps": int(cfg["conv_L_cache"]),
            "ffn": int(cfg["intermediate_size"]),
            "expert_ffn": int(cfg["moe_intermediate_size"]),
            "vocab": int(cfg["vocab_size"]),
            "held": int(cfg["num_experts_held"]),
            "experts": int(cfg["num_experts"]),
            "top_k": int(cfg["num_experts_per_tok"])}


def conv_params(cfg: dict) -> int:
    """The three leaves of the gated short convolution."""
    m = dims(cfg)
    return 3 * m["d"] * m["d"] + m["taps"] * m["d"] + m["d"] * m["d"]


def attention_params(cfg: dict) -> int:
    """Four projections and the two per-head norms' gains."""
    m = dims(cfg)
    return (2 * m["d"] * m["heads"] * m["head_dim"]
            + 2 * m["d"] * m["kv_heads"] * m["head_dim"] + 2 * m["head_dim"])


def expert_params(cfg: dict) -> int:
    """One SwiGLU expert: gate, up, down."""
    m = dims(cfg)
    return 3 * m["d"] * m["expert_ffn"]


def router_params(cfg: dict) -> int:
    """The router over all experts and its bias."""
    m = dims(cfg)
    return m["experts"] * m["d"] + m["experts"]


def ffn_params(cfg: dict, layer: int) -> int:
    m = dims(cfg)
    if layer < m["dense_layers"]:
        return 3 * m["d"] * m["ffn"]
    return router_params(cfg) + m["held"] * expert_params(cfg)


def layer_params(cfg: dict, layer: int) -> int:
    """Every stored parameter of layer ``layer`` of the configuration."""
    op = (conv_params(cfg) if cfg["layer_types"][layer] == "conv"
          else attention_params(cfg))
    return op + 2 * dims(cfg)["d"] + ffn_params(cfg, layer)


def total_params(cfg: dict) -> int:
    """Every stored parameter: the layers, the ONE matrix that is
    embedding and head, the final norm's gain."""
    m = dims(cfg)
    return (sum(layer_params(cfg, i) for i in range(m["layers"]))
            + m["vocab"] * m["d"] + m["d"])


def experts_hit(cfg: dict, batch: float) -> float:
    """Held experts that at least one of ``batch`` tokens chooses, in
    expectation under even routing: a held expert's weights count only
    in the steps that hit it."""
    m = dims(cfg)
    return m["held"] * (1.0 - (1.0 - m["top_k"] / m["experts"]) ** batch)


def kv_position_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One cached position of ONE attention layer: K and V."""
    m = dims(cfg)
    return 2 * m["kv_heads"] * m["head_dim"] * itemsize


def tail_bytes(cfg: dict, batch: float, itemsize: int = 2) -> float:
    """The tails of every conv layer for ``batch`` rows, held once."""
    m = dims(cfg)
    return m["conv_layers"] * batch * (m["taps"] - 1) * m["d"] * itemsize


def decode_step_parts(cfg: dict, batch: float, positions: float,
                      itemsize: int = 2) -> dict:
    """Bytes one greedy decode step must move for ``batch`` rows at a
    context of ``positions``, by part: every conv layer's three leaves
    and its tail READ AND WRITTEN, the attention layers' leaves and
    their K/V at that context, each HIT held expert once, the dense
    layers' FFN, routers and norm gains, the ONE tied matrix once as the
    head and the ``batch`` rows of it the lookup gathers.  No logits: a
    greedy step needs the arg-max and not the row of logits (a program
    that writes them moves more).  A program that reads its whole
    static cache, or an expert no token chose, reads more; the extra is
    its loss, not the algorithm's need."""
    m = dims(cfg)
    return {
        "conv_weights": m["conv_layers"] * conv_params(cfg) * itemsize,
        "conv_tails": 2 * tail_bytes(cfg, batch, itemsize),
        "attention_weights": (m["attention_layers"] * attention_params(cfg)
                              * itemsize),
        "kv_cache": (m["attention_layers"] * batch * positions
                     * kv_position_bytes(cfg, itemsize)),
        "experts_hit": (m["expert_layers"] * experts_hit(cfg, batch)
                        * expert_params(cfg) * itemsize),
        "dense_ffn": m["dense_layers"] * 3 * m["d"] * m["ffn"] * itemsize,
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
