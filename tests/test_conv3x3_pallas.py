"""Pallas 3×3 stride-1 conv kernel (ops/conv3x3_pallas) — exactness vs
lax.conv in interpret mode, forward and backward (VERDICT r3 #1 hand
kernel)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from bigdl_tpu.ops.conv3x3_pallas import conv3x3_s1_same

R = np.random.RandomState(5)


def _ref(x, w):
    return lax.conv_general_dilated(
        x, w, (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


@pytest.mark.parametrize("B,H,W,C,O", [
    (1, 8, 8, 8, 8),      # th == H single tile
    (2, 12, 10, 8, 16),   # th < H: multiple row slabs
])
def test_pallas_conv3x3_forward_matches_lax(B, H, W, C, O):
    x = jnp.asarray(R.randn(B, H, W, C), jnp.float32)
    w = jnp.asarray(R.randn(3, 3, C, O) * 0.1, jnp.float32)
    got = conv3x3_s1_same(x, w, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_ref(x, w)),
                               rtol=1e-4, atol=1e-4)


def test_pallas_conv3x3_grads_match_lax():
    x = jnp.asarray(R.randn(1, 8, 8, 8), jnp.float32)
    w = jnp.asarray(R.randn(3, 3, 8, 8) * 0.1, jnp.float32)

    def loss_k(x, w):
        return jnp.sum(conv3x3_s1_same(x, w, interpret=True) ** 2)

    def loss_r(x, w):
        return jnp.sum(_ref(x, w) ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1))(x, w)
    gr = jax.grad(loss_r, argnums=(0, 1))(x, w)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_fallback_path_off_tpu_matches_lax():
    # without interpret on CPU the public API must route to conv_gemm
    x = jnp.asarray(R.randn(2, 6, 6, 4), jnp.float32)
    w = jnp.asarray(R.randn(3, 3, 4, 4) * 0.1, jnp.float32)
    got = conv3x3_s1_same(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_ref(x, w)),
                               rtol=1e-5, atol=1e-5)


def test_framework_conv_impl_pallas_matches_xla():
    from bigdl_tpu import nn

    m = nn.SpatialConvolution(4, 8, 3, 3, 1, 1, 1, 1)
    x = jnp.asarray(R.randn(2, 4, 10, 10), jnp.float32)
    want = np.asarray(m.forward(x))
    m.set_conv_impl("pallas")  # CPU: routes through the gemm fallback
    got = np.asarray(m.forward(x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # a non-matching shape under impl=pallas keeps the native lowering
    m2 = nn.SpatialConvolution(4, 8, 5, 5, 2, 2, 2, 2)
    w2 = np.asarray(m2.forward(x))
    m2.set_conv_impl("pallas")
    np.testing.assert_allclose(np.asarray(m2.forward(x)), w2,
                               rtol=1e-6, atol=1e-6)


def test_twin_pallas_impl_matches_xla():
    from bigdl_tpu.models.resnet_jax_twin import forward, init_params

    params = init_params(jax.random.PRNGKey(2), num_classes=10)
    x = jnp.asarray(R.rand(1, 64, 64, 3), jnp.float32)
    a = np.asarray(forward(params, x, training=False, impl="xla"))
    b = np.asarray(forward(params, x, training=False, impl="pallas"))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
