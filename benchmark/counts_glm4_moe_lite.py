"""Operations and bytes of the GLM-4.7-Flash block from shapes — the
arithmetic of the ``glm-4.7-flash-*`` configurations, kept beside
``counts.py`` and under its conventions (a multiply-add is 2
operations; only what the algorithm requires counts; bytes are the
tensors a call must read and write once, at the dtype they are stored
in).  ``counts.py`` and ``counts_command_a_plus.py`` are not fed these
configurations: neither knows a latent cache or a leading dense layer.

A layer holds two RMSNorm gains and latent attention (seven leaves:
``d -> q_lora_rank -> heads x (nope + rope)``, ``d -> kv_lora_rank +
rope``, ``kv_lora_rank -> heads x (nope + v)``, ``heads x v -> d`` and
the two latent norms' gains); its FFN is a dense SwiGLU of
``intermediate_size`` in the first ``first_k_dense_replace`` layers and,
after them, a router over ALL published experts with its correction
bias, ``n_routed_experts`` HELD routed SwiGLU experts (this chip's
share) and ``n_shared_experts`` shared ones of the same width.  The head
is a matrix of its own; logits are float32.  A cached position of a
layer is ``kv_lora_rank + qk_rope_head_dim`` numbers, whatever the
number of heads.
"""
from __future__ import annotations

LOGIT_ITEMSIZE = 4   # logits are float32 whatever the weights are


def dims(cfg: dict) -> dict:
    """Sizes by one set of names, from the configuration's own keys."""
    layers, dense = (int(cfg["num_hidden_layers"]),
                     int(cfg["first_k_dense_replace"]))
    return {"d": int(cfg["hidden_size"]), "layers": layers,
            "dense_layers": dense, "expert_layers": layers - dense,
            "heads": int(cfg["num_attention_heads"]),
            "q_rank": int(cfg["q_lora_rank"]),
            "kv_rank": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "v": int(cfg["v_head_dim"]),
            "ffn": int(cfg["intermediate_size"]),
            "expert_ffn": int(cfg["moe_intermediate_size"]),
            "vocab": int(cfg["vocab_size"]),
            "held": int(cfg["n_routed_experts"]),
            "experts": int(cfg["n_routed_experts_published"]),
            "top_k": int(cfg["num_experts_per_tok"]),
            "shared": int(cfg["n_shared_experts"])}


def attention_params(cfg: dict) -> int:
    """The seven leaves of latent attention."""
    m = dims(cfg)
    return (m["d"] * m["q_rank"] + m["q_rank"]
            + m["q_rank"] * m["heads"] * (m["nope"] + m["rope"])
            + m["d"] * (m["kv_rank"] + m["rope"]) + m["kv_rank"]
            + m["kv_rank"] * m["heads"] * (m["nope"] + m["v"])
            + m["heads"] * m["v"] * m["d"])


def expert_params(cfg: dict) -> int:
    """One SwiGLU expert: gate, up, down."""
    m = dims(cfg)
    return 3 * m["d"] * m["expert_ffn"]


def router_params(cfg: dict) -> int:
    """The router over all published experts and its correction bias."""
    m = dims(cfg)
    return m["experts"] * m["d"] + m["experts"]


def dense_layer_params(cfg: dict) -> int:
    m = dims(cfg)
    return attention_params(cfg) + 2 * m["d"] + 3 * m["d"] * m["ffn"]


def expert_layer_params(cfg: dict) -> int:
    """Every stored parameter of one expert layer at this chip's share."""
    m = dims(cfg)
    return (attention_params(cfg) + 2 * m["d"] + router_params(cfg)
            + (m["held"] + m["shared"]) * expert_params(cfg))


def total_params(cfg: dict) -> int:
    """Every stored parameter: embedding, the dense and the expert
    layers, the final norm's gain, the head."""
    m = dims(cfg)
    return (m["dense_layers"] * dense_layer_params(cfg)
            + m["expert_layers"] * expert_layer_params(cfg)
            + 2 * m["vocab"] * m["d"] + m["d"])


def experts_hit(cfg: dict, batch: float) -> float:
    """Held experts that at least one of ``batch`` tokens chooses, in
    expectation under even routing: a held expert's weights count only
    in the steps that hit it."""
    m = dims(cfg)
    return m["held"] * (1.0 - (1.0 - m["top_k"] / m["experts"]) ** batch)


def latent_position_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One cached position of one layer: the latent and the ONE rotated
    key all heads share."""
    m = dims(cfg)
    return (m["kv_rank"] + m["rope"]) * itemsize


def decode_step_parts(cfg: dict, batch: float, positions: float,
                      itemsize: int = 2) -> dict:
    """Bytes one decode step must move for ``batch`` rows at a context
    of ``positions``, by part: the latent path (attention weights of
    every layer and the latent cache the step attends to), each HIT held
    expert once, the shared experts, the dense layers' FFN, routers and
    norm gains, the head once, and the float32 logits written once.  A
    program that reads its whole static cache, or an expert no token
    chose, reads more; the extra is its loss, not the algorithm's
    need."""
    m = dims(cfg)
    return {
        "latent_weights": m["layers"] * attention_params(cfg) * itemsize,
        "latent_cache": (m["layers"] * batch * positions
                         * latent_position_bytes(cfg, itemsize)),
        "experts_hit": (m["expert_layers"] * experts_hit(cfg, batch)
                        * expert_params(cfg) * itemsize),
        "shared": (m["expert_layers"] * m["shared"] * expert_params(cfg)
                   * itemsize),
        "dense_ffn": m["dense_layers"] * 3 * m["d"] * m["ffn"] * itemsize,
        "routers_and_norms": ((m["expert_layers"] * router_params(cfg)
                               + (2 * m["layers"] + 1) * m["d"])
                              * itemsize),
        "head": m["vocab"] * m["d"] * itemsize,
        "logits": batch * m["vocab"] * LOGIT_ITEMSIZE}


def decode_step_bytes(cfg: dict, batch: float, positions: float,
                      itemsize: int = 2) -> float:
    return float(sum(decode_step_parts(cfg, batch, positions,
                                       itemsize).values()))


def attend_call(cfg: dict, batch: float, positions: float,
                itemsize: int = 2) -> dict:
    """Operations and bytes of ONE layer's absorbed attend in one decode
    step (scores against the latent and the shared rotated key, softmax,
    the weighted sum of the latent) for ``batch`` rows at a context of
    ``positions``; bytes: the cached positions once, the absorbed query
    in and the latent result out."""
    m = dims(cfg)
    per_pos = m["kv_rank"] + m["rope"]
    flops = 2.0 * batch * m["heads"] * positions * (per_pos + m["kv_rank"])
    nbytes = (batch * positions * per_pos
              + batch * m["heads"] * (per_pos + m["kv_rank"])) * itemsize
    return {"flops": flops, "bytes": float(nbytes)}


def absorb_flops(cfg: dict, batch: float) -> float:
    """Operations of one layer's two absorbed products in one step:
    ``q_nope -> q_lat`` and ``o_lat -> o``."""
    m = dims(cfg)
    return 2.0 * batch * m["heads"] * m["kv_rank"] * (m["nope"] + m["v"])


def expand_flops_per_position(cfg: dict) -> float:
    """Operations the expansion of ONE cached position to per-head K
    and V costs — what a decode step that expanded its cache would pay
    for every cached position, layer and step."""
    m = dims(cfg)
    return 2.0 * m["kv_rank"] * m["heads"] * (m["nope"] + m["v"])


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
