"""Pallas kernel tests — run in interpreter mode on the CPU test
topology (pallas_guide.md: interpret=True), oracled against plain jnp."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.ops import flash_attention, fused_layer_norm
from bigdl_tpu.ops.flash_attention import _attention_reference
from bigdl_tpu.ops.layer_norm import _layer_norm_reference


class TestFlashAttention:
    def _qkv(self, B=2, H=2, T=128, D=32, seed=0):
        rng = np.random.RandomState(seed)
        return [jnp.asarray(rng.randn(B, H, T, D).astype(np.float32) * 0.5)
                for _ in range(3)]

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = self._qkv()
        ref = _attention_reference(q, k, v, causal, 1 / np.sqrt(q.shape[-1]))
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_multi_key_blocks(self):
        # T=256 → 2 key blocks: exercises the online-softmax rescale
        q, k, v = self._qkv(T=256, seed=1)
        ref = _attention_reference(q, k, v, True, 1 / np.sqrt(q.shape[-1]))
        out = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_grad_matches_reference(self):
        q, k, v = self._qkv(T=128, seed=2)

        def loss_flash(q_, k_, v_):
            return jnp.sum(flash_attention(q_, k_, v_, causal=True,
                                           interpret=True) ** 2)

        def loss_ref(q_, k_, v_):
            return jnp.sum(_attention_reference(
                q_, k_, v_, True, 1 / np.sqrt(q.shape[-1])) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grad_multi_block_backward(self, causal):
        # T=640 → backward block=512, 2 K/V blocks with 384 pad: exercises
        # the blockwise two-pass backward's rescale + pad masking
        q, k, v = self._qkv(B=1, H=2, T=640, seed=4)

        def loss_flash(q_, k_, v_):
            return jnp.sum(flash_attention(q_, k_, v_, causal=causal,
                                           interpret=True) ** 2)

        def loss_ref(q_, k_, v_):
            return jnp.sum(_attention_reference(
                q_, k_, v_, causal, 1 / np.sqrt(q.shape[-1])) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    def test_causal_cross_attention_t_gt_s(self):
        # T=256 queries over S=128 keys: n_blocks must clamp to S//bk
        rng = np.random.RandomState(8)
        q = jnp.asarray(rng.randn(1, 2, 256, 32).astype(np.float32) * 0.5)
        k = jnp.asarray(rng.randn(1, 2, 128, 32).astype(np.float32) * 0.5)
        v = jnp.asarray(rng.randn(1, 2, 128, 32).astype(np.float32) * 0.5)
        ref = _attention_reference(q, k, v, True, 1 / np.sqrt(32))
        out = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_cpu_fallback_path(self):
        # odd seq len → wrapper silently uses the XLA reference
        q, k, v = self._qkv(T=60)
        out = flash_attention(q, k, v, causal=False)
        ref = _attention_reference(q, k, v, False, 1 / np.sqrt(q.shape[-1]))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5)

    def test_jit_compiles(self):
        q, k, v = self._qkv(T=128)
        f = jax.jit(lambda a, b, c: flash_attention(a, b, c, causal=True,
                                                    interpret=True))
        out = f(q, k, v)
        assert out.shape == q.shape


class TestFusedLayerNorm:
    def test_uneven_rows_use_divisor_blocks(self):
        from bigdl_tpu.ops.layer_norm import _ln_fwd

        rng = np.random.RandomState(9)
        x = jnp.asarray(rng.randn(36, 64).astype(np.float32))  # 36 % 256 != 0
        gamma = jnp.asarray(np.ones(64, np.float32))
        beta = jnp.asarray(np.zeros(64, np.float32))
        out = _ln_fwd(x, gamma, beta, 1e-5, True, block_rows=16)
        ref = _layer_norm_reference(x, gamma, beta, 1e-5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_matches_reference(self):
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(4, 9, 128).astype(np.float32))
        gamma = jnp.asarray(rng.randn(128).astype(np.float32))
        beta = jnp.asarray(rng.randn(128).astype(np.float32))
        out = fused_layer_norm(x, gamma, beta, interpret=True)
        ref = _layer_norm_reference(x, gamma, beta, 1e-5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_grad_matches_reference(self):
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(8, 64).astype(np.float32))
        gamma = jnp.asarray(np.ones(64, np.float32))
        beta = jnp.asarray(np.zeros(64, np.float32))
        gf = jax.grad(lambda x_: jnp.sum(
            fused_layer_norm(x_, gamma, beta, interpret=True) ** 2))(x)
        gr = jax.grad(lambda x_: jnp.sum(
            _layer_norm_reference(x_, gamma, beta, 1e-5) ** 2))(x)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-3, atol=1e-4)

    def test_layer_module_uses_fused(self):
        from bigdl_tpu import nn

        rng = np.random.RandomState(5)
        ln = nn.LayerNorm(32)
        x = rng.randn(4, 32).astype(np.float32)
        out = np.asarray(ln.forward(x))
        np.testing.assert_allclose(out.mean(axis=-1), 0.0, atol=1e-5)
        np.testing.assert_allclose(out.std(axis=-1), 1.0, atol=1e-2)


class TestFlashInAttentionLayer:
    def test_mha_flash_strategy(self):
        from bigdl_tpu import nn

        rng = np.random.RandomState(6)
        x = rng.randn(2, 128, 32).astype(np.float32)
        mha_flash = nn.MultiHeadAttention(32, 4, causal=True,
                                          seq_strategy="flash")
        mha_dense = nn.MultiHeadAttention(32, 4, causal=True,
                                          seq_strategy="dense")
        mha_dense.set_param_tree(mha_flash.param_tree())
        np.testing.assert_allclose(np.asarray(mha_flash.forward(x)),
                                   np.asarray(mha_dense.forward(x)),
                                   rtol=1e-4, atol=1e-5)


class TestPickBlock:
    """Pin the measured block-target rule (r4 on-chip matrix,
    docs/PERF.md's flash rows): target 1024 everywhere except wide heads
    (D>=128) at short sequences (T<=1024), where 512 measured faster."""

    def test_long_sequences_target_1024(self):
        from bigdl_tpu.ops.flash_attention import _pick_block

        assert _pick_block(4096, 64) == 1024
        assert _pick_block(4096, 128) == 1024
        assert _pick_block(8192, 128) == 1024

    def test_short_wide_heads_keep_512(self):
        from bigdl_tpu.ops.flash_attention import _pick_block

        assert _pick_block(1024, 128) == 512
        assert _pick_block(1024, 64) == 1024  # narrow heads: 1024 won

    def test_short_sequences_whole_block(self):
        from bigdl_tpu.ops.flash_attention import _pick_block

        assert _pick_block(256, 64) == 256
        assert _pick_block(384, 128) == 384

    def test_non_divisible_falls_to_divisor(self):
        from bigdl_tpu.ops.flash_attention import _pick_block

        # 1536 = 1024 + 512: largest pow2-halved divisor <= target
        assert _pick_block(1536, 64) == 512
