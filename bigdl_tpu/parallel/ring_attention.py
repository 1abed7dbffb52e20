"""Long-context attention parallelism: blockwise, ring, and Ulysses.

The reference framework predates attention entirely (SURVEY §5.7 — its
long-sequence story is `Recurrent` unrolling + padded batching), so this
module is the forward-looking extension the TPU rebuild makes
first-class: the sequence dimension becomes a mesh axis and attention is
computed over it without ever materialising the full [T, T] score
matrix or the full sequence on one chip.

Three strategies, one math:

* ``blockwise_attention`` — single-device flash-style attention: an
  online-softmax ``lax.scan`` over key/value blocks.  O(T) memory in the
  sequence; the inner block matmuls are MXU-shaped.
* ``ring_attention`` — sequence (context) parallelism: every device
  holds one sequence shard of Q/K/V; K/V chunks rotate around the mesh
  axis ring via ``lax.ppermute`` (one ICI hop per step) while each
  device folds the visiting chunk into its online-softmax accumulator.
  Compute overlaps communication; memory per chip is O(T / n_devices).
* ``ulysses_attention`` — all-to-all sequence parallelism: two
  ``lax.all_to_all`` collectives re-shard [seq → heads] so every device
  runs *full-sequence* attention for a head subset, then re-shard back.
  Cheaper collectives than ring when heads ≥ devices.

All ``*_attention`` functions take [batch, heads, seq, head_dim] and
return the same shape.  The ring/Ulysses variants must run inside
``shard_map`` over a mesh axis that shards the ``seq`` dimension.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

NEG_INF = -1e30


def _online_block(q, k, v, bias, m, l, o):
    """Fold one K/V block into the (m, l, o) online-softmax accumulator.

    q: [B, H, Tq, D]; k, v: [B, H, Tk, D]; bias: [Tq, Tk] additive mask
    (0 or NEG_INF); m, l: [B, H, Tq]; o: [B, H, Tq, D].
    """
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m_new = jnp.maximum(m, s.max(axis=-1))
    # all-masked rows keep m == NEG_INF; corrections stay finite
    p = jnp.exp(s - m_new[..., None])
    correction = jnp.exp(m - m_new)
    l_new = l * correction + p.sum(axis=-1)
    # PV on the MXU in the input dtype (an f32 matmul runs at a fraction
    # of bf16 rate); the o accumulator itself stays f32
    o_new = o * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def _finish(m, l, o, dtype):
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (o / l_safe[..., None]).astype(dtype)


def _causal_bias(q_pos, k_pos):
    return jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0, NEG_INF)


def blockwise_attention(q, k, v, block_size: int = 512,
                        causal: bool = False):
    """Flash-style attention on one device via ``lax.scan`` over K/V
    blocks.  Never builds the [T, T] matrix; O(T·block) working set."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    block = min(block_size, Tk)
    n_blocks = -(-Tk // block)
    pad = n_blocks * block - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(B, H, n_blocks, block, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, n_blocks, block, D).transpose(2, 0, 1, 3, 4)

    q_pos = jnp.arange(Tq)

    def body(carry, blk):
        m, l, o = carry
        kblk, vblk, idx = blk
        k_pos = idx * block + jnp.arange(block)
        bias = jnp.where(k_pos[None, :] < Tk, 0.0, NEG_INF)
        if causal:
            bias = bias + _causal_bias(q_pos, k_pos)
        m, l, o = _online_block(q, kblk, vblk, bias, m, l, o)
        return (m, l, o), None

    init = (jnp.full((B, H, Tq), NEG_INF, jnp.float32),
            jnp.zeros((B, H, Tq), jnp.float32),
            jnp.zeros((B, H, Tq, D), jnp.float32))
    (m, l, o), _ = lax.scan(body, init, (kb, vb, jnp.arange(n_blocks)))
    return _finish(m, l, o, q.dtype)


def ring_attention(q, k, v, axis_name: str = "seq", causal: bool = False):
    """Ring (context-parallel) attention.  Call inside ``shard_map`` with
    the sequence dimension sharded over ``axis_name``.

    Each of the n devices starts with its own K/V chunk; every step folds
    the resident chunk into the accumulator and passes it to the next
    device on the ring (``ppermute`` — a single ICI hop, overlapped with
    the block compute by XLA).  After n steps every Q shard has seen the
    full sequence.
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    q_pos = my * Tq + jnp.arange(Tq)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, step):
        m, l, o, k_cur, v_cur = carry
        # after `step` rotations we hold the chunk born on device my - step
        src = (my - step) % n
        k_pos = src * Tk + jnp.arange(Tk)
        bias = _causal_bias(q_pos, k_pos) if causal else None
        m, l, o = _online_block(q, k_cur, v_cur, bias, m, l, o)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m, l, o, k_nxt, v_nxt), None

    init = (jnp.full((B, H, Tq), NEG_INF, jnp.float32),
            jnp.zeros((B, H, Tq), jnp.float32),
            jnp.zeros((B, H, Tq, D), jnp.float32),
            k, v)
    (m, l, o, _, _), _ = lax.scan(body, init, jnp.arange(n))
    return _finish(m, l, o, q.dtype)


def ulysses_attention(q, k, v, axis_name: str = "seq",
                      causal: bool = False, block_size: int = 512):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style).

    Input is seq-sharded [B, H, T/n, D].  ``all_to_all`` re-shards to
    head-sharded [B, H/n, T, D], full-sequence blockwise attention runs
    locally, and a second ``all_to_all`` restores seq sharding.
    Requires H % n == 0.
    """
    n = lax.psum(1, axis_name)  # concrete under shard_map
    if isinstance(n, int) and q.shape[1] % n:
        raise ValueError(
            f"ulysses_attention needs num_heads ({q.shape[1]}) divisible "
            f"by the '{axis_name}' axis size ({n}); use strategy='ring'")

    def seq_to_heads(x):
        # [B, H, t, D] -> [B, H/n, T, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qf, kf, vf = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    of = blockwise_attention(qf, kf, vf, block_size=block_size,
                             causal=causal)
    return heads_to_seq(of)


def attention(q, k, v, causal: bool = False, scale=None,
              score_dtype=None):
    """Dense reference attention (materialises [T, T]); oracle for tests
    and the fast path for short sequences where one matmul wins.
    ``scale`` multiplies the scores (None: ``1 / sqrt(head size)``); the
    value head may be narrower than the query's.  ``score_dtype`` holds
    the scores in another dtype than the inputs' (float32: as the flash
    kernels hold theirs — for a caller whose whole-score pass stands in
    for one).

    Scores stay in the INPUT dtype (bf16 under mixed precision — an f32
    [B,H,T,T] tensor is pure HBM burn, measured 25% of the whole dense
    grad on a v5e); only the softmax normalisation accumulates f32,
    which preserves the max-subtracted exp's accuracy.
    """
    sdt = q.dtype if score_dtype is None else score_dtype
    scale = jnp.asarray(1.0 / np.sqrt(q.shape[-1]) if scale is None
                        else scale, sdt)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=score_dtype) * scale
    if causal:
        Tq, Tk = s.shape[-2:]
        s = jnp.where(jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :],
                      s, jnp.asarray(NEG_INF, s.dtype))
    # softmax normalisation accumulates f32 (f64 inputs — the gradient
    # checker's precision — keep f64 end-to-end)
    acc = jnp.float64 if s.dtype == jnp.float64 else jnp.float32
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp((s - m).astype(acc))
    p = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def make_ring_attention_sharded(mesh, axis_name: str = "seq",
                                causal: bool = False,
                                strategy: str = "ring"):
    """shard_map-wrapped sequence-parallel attention over ``mesh``.

    Returns f(q, k, v) on GLOBAL [B, H, T, D] arrays; the seq dim is
    sharded over ``axis_name`` and each device runs the ring/Ulysses
    local program.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, axis_name, None)
    fn = ring_attention if strategy == "ring" else ulysses_attention

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec, check_vma=False)
    def sharded(q, k, v):
        return fn(q, k, v, axis_name=axis_name, causal=causal)

    return sharded
