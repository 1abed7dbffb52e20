"""Operations and bytes from shapes — the benchmark's own arithmetic.

Kept with the benchmark so that no PR that claims a gain can change what
a token or a kernel call is taken to cost.  Conventions:

* a multiply-add is 2 operations;
* only operations the algorithm REQUIRES count: a causal attention needs
  half of the T x T score tile, recomputation under ``jax.checkpoint``
  or inside a flash backward beyond the one score recompute the
  algorithm is defined by does not count;
* bytes are the tensors a call must read and write once, at the dtype
  they are stored in (bf16 = 2) — no re-reads for tiling.

``dims(config)`` maps either dialect's published keys (GPT-2's ``n_*``,
the llama family's ``hidden_size`` ...) to one small dict, so every
function below serves both configurations the benchmark has.  It is not
made to guess a third dialect: a new architecture (a mixer, a router, a
latent projection) brings its arithmetic — its own ``dims``, step bytes
and kernel operations — in a new file beside this one, which its own
readers import; this file and the readers that use it stay as they are.
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    """Sizes by one set of names, from a configuration file's own keys."""
    if "n_embd" in cfg:  # GPT-2 dialect
        d = int(cfg["n_embd"])
        heads = int(cfg["n_head"])
        return {"d": d, "layers": int(cfg["n_layer"]), "heads": heads,
                "kv_heads": heads, "head_dim": d // heads,
                "ffn": int(cfg.get("n_inner") or 4 * d), "ffn_mats": 2,
                "vocab": int(cfg["vocab_size"]),
                "pos_rows": int(cfg["n_positions"])}
    d = int(cfg["hidden_size"])  # llama dialect
    heads = int(cfg["num_attention_heads"])
    return {"d": d, "layers": int(cfg["num_hidden_layers"]), "heads": heads,
            "kv_heads": int(cfg.get("num_key_value_heads", heads)),
            "head_dim": int(cfg.get("head_dim") or d // heads),
            "ffn": int(cfg["intermediate_size"]), "ffn_mats": 3,
            "vocab": int(cfg["vocab_size"]), "pos_rows": 0}


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one block that a token is multiplied by."""
    m = dims(cfg)
    q_out = m["heads"] * m["head_dim"]
    kv_out = m["kv_heads"] * m["head_dim"]
    attn = m["d"] * q_out + 2 * m["d"] * kv_out + q_out * m["d"]
    return attn + m["ffn_mats"] * m["d"] * m["ffn"]


def matmul_params(cfg: dict) -> int:
    """N of "6ND": every weight a token is multiplied by — the blocks and
    the (untied) output head; the embedding and position tables are
    look-ups and do not count."""
    m = dims(cfg)
    return m["layers"] * layer_matmul_params(cfg) + m["d"] * m["vocab"]


def total_params(cfg: dict, head_bias: bool = False) -> int:
    """Every stored parameter (for memory, not for operations)."""
    m = dims(cfg)
    gpt2 = m["ffn_mats"] == 2
    per_layer = layer_matmul_params(cfg)
    if gpt2:  # biases and two LayerNorms (gain + bias)
        per_layer += 4 * m["d"] + m["ffn"] + m["d"] + 4 * m["d"]
    else:     # two RMSNorm gains, no biases
        per_layer += 2 * m["d"]
    n = m["layers"] * per_layer + m["vocab"] * m["d"]      # embedding
    n += m["pos_rows"] * m["d"]
    n += m["d"] * m["vocab"] + (m["vocab"] if head_bias else 0)  # head
    n += 2 * m["d"] if gpt2 else m["d"]                    # final norm
    return n


def attention_flops_fwd(cfg: dict, batch: int, t_q: int, t_kv: int,
                        causal: bool = True) -> float:
    """QK^T and PV of every layer for ``batch`` sequences: 2 matmuls x 2
    ops x t_q x t_kv x head_dim per head; a causal square needs half."""
    m = dims(cfg)
    full = 4.0 * batch * m["heads"] * t_q * t_kv * m["head_dim"]
    if causal and t_q == t_kv:
        full *= 0.5
    return full * m["layers"]


def forward_flops(cfg: dict, batch: int, seq: int,
                  head_positions: int | None = None) -> float:
    """One causal forward over ``batch`` x ``seq`` tokens; the head is
    applied at ``head_positions`` positions per sequence (all by
    default; a prefill that feeds a decoder needs only the last)."""
    m = dims(cfg)
    hp = seq if head_positions is None else head_positions
    body = 2.0 * m["layers"] * layer_matmul_params(cfg) * batch * seq
    head = 2.0 * m["d"] * m["vocab"] * batch * hp
    return body + head + attention_flops_fwd(cfg, batch, seq, seq)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of one token in a causal sequence of ``seq``:
    6 N plus three times the forward attention."""
    return 3.0 * forward_flops(cfg, 1, seq) / seq


def flash_call(batch_heads: int, t: int, d: int, causal: bool = True,
               itemsize: int = 2) -> dict:
    """Operations and bytes of the three flash kernels for one attention
    over [batch_heads, t, d] (square).  fwd: S = QK^T, O = PV.  bwd: the
    one score recompute the algorithm is defined by, dV, dP, dQ, dK —
    counted once although the dKdV and dQ kernels each recompute S and
    dP.  Bytes: fwd reads Q, K, V and writes O (+ the f32 row
    statistic); bwd reads Q, K, V, O, dO (+ statistic) and writes dQ,
    dK, dV."""
    half = 0.5 if causal else 1.0
    mat = 2.0 * batch_heads * t * t * d * half   # one T x T x d matmul
    tensor = batch_heads * t * d * itemsize
    stat = batch_heads * t * 4
    return {"fwd_flops": 2 * mat, "bwd_flops": 5 * mat,
            "fwd_bytes": 4 * tensor + stat,
            "bwd_bytes": 8 * tensor + 2 * stat}


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds the chip could take, which peak binds)."""
    tc = flops / peaks["bf16_flops_per_s"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "memory")


def decode_step_bytes(cfg: dict, batch: int, cache_len: int,
                      itemsize: int = 2) -> float:
    """Bytes one decode step must read: every block weight and the head
    once (bf16), plus the K and V of the ``cache_len`` positions the
    step attends to, for ``batch`` rows.  A program that reads its whole
    static cache reads more than this; the extra is its loss, not the
    algorithm's need."""
    m = dims(cfg)
    weights = matmul_params(cfg) * itemsize
    cache = (2 * m["layers"] * batch * m["kv_heads"] * cache_len
             * m["head_dim"] * itemsize)
    return float(weights + cache)


def peaks_for(device_kind: str, table: dict) -> dict:
    """The peaks row of a device; an unknown device is an error."""
    kind = device_kind.lower()
    for row in table["rows"]:
        if row["match"] in kind:
            return row
    raise KeyError(f"device_kind {device_kind!r} matches no row of "
                   "benchmark/peaks.json — add its published peaks "
                   "with their source; there is no default")
