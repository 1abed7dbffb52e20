"""Operations and bytes of the Xing4.0-29B-A4B block from shapes — the
arithmetic of the ``xing4.0-29b-a4b-*`` configurations, under
``counts.py``'s conventions (a multiply-add is 2 operations; only what
the algorithm requires counts; bytes are the tensors a call must read
and write once, at the dtype they are stored in).

The layers are ``glm4_moe_lite``'s (latent attention, a leading dense
SwiGLU, a router over all published experts with its correction bias,
the HELD routed experts and the shared ones, an untied head, float32
logits) and ``counts_glm4_moe_lite`` counts them from this
configuration's keys as they stand; what this file adds is the
residual: every sublayer (two a layer) has a hyper-connection over ``n =
hc_mult`` streams — ``phi`` ``[2 n + n^2, n d]``, three gates, ``2 n +
n^2`` biases.  A decode step's mixes read and write, a sublayer, the
state ``X [rows, n, d]`` and the sublayer's result ``y [rows, d]`` — but
the state is made and used inside ONE step, 7.3 MB at 256 rows, and a
chip with 128 MiB of fast memory need never send it to HBM (the v5e's
compiler does keep it there, ``S(1)`` in the compiled program; counted
as HBM traffic it made a share read 115 %, PERF.md section 6 "PR 42").
What a sublayer MUST read from HBM is ``phi``: that is its ``bytes`` in
a step's total, and no share of a roofline is taken of the
hyper-connections alone (no counter says where the state lies).
"""
from __future__ import annotations

from benchmark import counts_glm4_moe_lite as glm

attention_params = glm.attention_params
expert_params = glm.expert_params
router_params = glm.router_params
experts_hit = glm.experts_hit
attend_call = glm.attend_call
expert_matmul_call = glm.expert_matmul_call


def dims(cfg: dict) -> dict:
    """``counts_glm4_moe_lite.dims`` and the streams."""
    n = int(cfg["hc_mult"])
    return {**glm.dims(cfg), "streams": n, "maps": 2 * n + n * n}


def hyper_connection_params(cfg: dict) -> int:
    """The leaves of ONE sublayer's hyper-connection: ``phi``, three
    gates, the biases."""
    m = dims(cfg)
    return m["maps"] * m["streams"] * m["d"] + 3 + m["maps"]


def dense_layer_params(cfg: dict) -> int:
    return glm.dense_layer_params(cfg) + 2 * hyper_connection_params(cfg)


def expert_layer_params(cfg: dict) -> int:
    return glm.expert_layer_params(cfg) + 2 * hyper_connection_params(cfg)


def total_params(cfg: dict) -> int:
    m = dims(cfg)
    return (glm.total_params(cfg)
            + 2 * m["layers"] * hyper_connection_params(cfg))


def hyper_connection_call(cfg: dict, batch: float, itemsize: int = 2) -> dict:
    """Operations and bytes of ONE sublayer's hyper-connection in one
    decode step of ``batch`` rows: the product with ``phi``, the two
    mixes (``n`` and ``n^2 + n`` multiply-adds a number of ``d``) and,
    a row, the sweeps' ``2 n^2`` divisions and ``2 n (n - 1)`` additions
    each; ``bytes``: ``phi`` once (what must come from HBM: the module
    docstring)."""
    m = dims(cfg)
    n, d = m["streams"], m["d"]
    sweeps = int(cfg["hc_sinkhorn_iters"]) * (2 * n * n + 2 * n * (n - 1))
    flops = batch * (2.0 * m["maps"] * n * d + 2.0 * n * d
                     + 2.0 * (n * n + n) * d + sweeps)
    phi = m["maps"] * n * d * itemsize
    return {"flops": flops, "bytes": float(phi)}


def decode_step_parts(cfg: dict, batch: float, positions: float,
                      itemsize: int = 2) -> dict:
    """``counts_glm4_moe_lite.decode_step_parts`` and the
    hyper-connections of every sublayer."""
    m = dims(cfg)
    return {**glm.decode_step_parts(cfg, batch, positions, itemsize),
            "hyper_connections": 2 * m["layers"] * hyper_connection_call(
                cfg, batch, itemsize)["bytes"]}


def decode_step_bytes(cfg: dict, batch: float, positions: float,
                      itemsize: int = 2) -> float:
    return float(sum(decode_step_parts(cfg, batch, positions,
                                       itemsize).values()))
