"""Sequence/context parallelism tests on the 8-device virtual CPU mesh
(the analogue of the reference's Spark local[4] distributed tests,
SURVEY §4.3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.parallel.ring_attention import (
    attention, blockwise_attention, make_ring_attention_sharded)

B, H, T, D = 2, 4, 64, 8


def _qkv(seed=0, heads=H):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, heads, T, D).astype(np.float32))
    return mk(), mk(), mk()


def _seq_mesh():
    return Mesh(np.array(jax.devices()[:8]), ("seq",))


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_dense(causal):
    q, k, v = _qkv()
    dense = attention(q, k, v, causal=causal)
    block = blockwise_attention(q, k, v, block_size=16, causal=causal)
    np.testing.assert_allclose(np.asarray(block), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)


def test_blockwise_ragged_tail():
    q, k, v = _qkv(1)
    # block size that does not divide T exercises the padded-tail mask
    block = blockwise_attention(q, k, v, block_size=24, causal=True)
    dense = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(block), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_sharded_matches_dense(strategy, causal):
    mesh = _seq_mesh()
    # Ulysses re-shards seq→heads, so heads must divide the axis size
    q, k, v = _qkv(2, heads=8)
    fn = make_ring_attention_sharded(mesh, causal=causal, strategy=strategy)
    sharded = jax.jit(fn)(q, k, v)
    dense = attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)


def test_ring_grads_match_dense():
    mesh = _seq_mesh()
    q, k, v = _qkv(3)
    fn = make_ring_attention_sharded(mesh, causal=True, strategy="ring")

    def loss_ring(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gr, gd in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                   atol=1e-4, rtol=1e-4)


def test_mha_layer_forward_backward():
    from bigdl_tpu import nn

    layer = nn.MultiHeadAttention(32, 4, causal=True)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 32),
                    dtype=jnp.float32)
    out = layer.forward(x)
    assert out.shape == (2, 16, 32)
    gi = layer.backward(x, jnp.ones_like(out))
    assert gi.shape == x.shape
    # blockwise strategy computes the same layer output
    layer.seq_strategy = "block"
    out_blk = layer.forward(x)
    np.testing.assert_allclose(np.asarray(out_blk), np.asarray(out),
                               atol=1e-5, rtol=1e-5)


def test_mha_ring_inside_shard_map_matches_dense():
    from bigdl_tpu import nn

    mesh = _seq_mesh()
    dense_layer = nn.MultiHeadAttention(32, 8, causal=True)
    ring_layer = nn.MultiHeadAttention(32, 8, causal=True,
                                       seq_strategy="ring", seq_axis="seq")
    ring_layer.set_param_tree(dense_layer.param_tree())
    x = jnp.asarray(np.random.RandomState(1).randn(2, 64, 32),
                    dtype=jnp.float32)

    from functools import partial

    from jax import shard_map

    params = ring_layer.param_tree()

    @jax.jit
    @partial(shard_map, mesh=mesh,
             in_specs=(P(), P(None, "seq", None)),
             out_specs=P(None, "seq", None), check_vma=False)
    def fwd(p, x):
        return ring_layer.apply_fn(p, {}, x, False, None)[0]

    out_ring = fwd(params, x)
    out_dense = dense_layer.apply_fn(params, {}, x, False, None)[0]
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_dense),
                               atol=1e-4, rtol=1e-4)
