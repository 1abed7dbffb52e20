"""Block-sparse kernel specs (ops/block_sparse.py, BLaST — ISSUE 12).

The contract, in order of importance: an all-ones mask IS the flash
kernel (same shared tile machinery, same schedule — bitwise-class
parity, fwd and grads, causal and not, GQA head counts); a masked
block's contribution is EXACTLY zero (NaN-poisoned masked K/V tiles
never touch the output — the proof the blocks are skipped, not
masked-after); the three attention paths can never diverge on
``sm_scale`` handling (the reference-fallback scale-bug class); and
the executed-work accounting the MFU correction rides is derived from
the same index tables the grid runs."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.block_sparse import (BlockMask, attention_work,
                                        block_sparse_attention,
                                        block_sparse_matmul,
                                        magnitude_block_mask, matmul_work,
                                        pick_block_divisor,
                                        sliding_window_mask, strided_mask)
from bigdl_tpu.ops.flash_attention import (_attention_reference,
                                           flash_attention)


def _qkv(B=2, H=2, T=128, D=32, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(B, H, T, D).astype(np.float32) * 0.5)
            for _ in range(3)]


def _full(T, block):
    return BlockMask(np.ones((T // block, T // block), bool), block, block)


class TestFullMaskParity:
    """All-ones mask == flash == dense, fwd + grads."""

    @pytest.mark.parametrize("causal,T,block,sm", [
        (False, 128, 32, 1.0), (True, 128, 32, 1.0), (True, 256, 64, 0.5)])
    def test_forward_three_way(self, causal, T, block, sm):
        q, k, v = _qkv(T=T)
        sm /= np.sqrt(q.shape[-1])
        ref = _attention_reference(q, k, v, causal, sm)
        fl = flash_attention(q, k, v, causal=causal, sm_scale=sm,
                             interpret=True)
        bs = block_sparse_attention(q, k, v, _full(T, block),
                                    causal=causal, sm_scale=sm,
                                    interpret=True)
        # bitwise-class vs flash: identical shared tile machinery,
        # identical block visit order
        np.testing.assert_allclose(np.asarray(bs), np.asarray(fl),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(bs), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_three_way(self, causal):
        q, k, v = _qkv(T=128, seed=2)
        mask = _full(128, 32)

        def loss(fn):
            return jax.grad(lambda a, b, c: jnp.sum(fn(a, b, c) ** 2),
                            argnums=(0, 1, 2))(q, k, v)

        gb = loss(lambda a, b, c: block_sparse_attention(
            a, b, c, mask, causal=causal, interpret=True))
        gf = loss(lambda a, b, c: flash_attention(
            a, b, c, causal=causal, interpret=True))
        gr = loss(lambda a, b, c: _attention_reference(
            a, b, c, causal, 1 / np.sqrt(q.shape[-1])))
        for a, b in zip(gb, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)
        for a, b in zip(gb, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    def test_gqa_head_counts_through_layer(self):
        """GQA (kv heads < query heads) through MultiHeadAttention:
        blocksparse with full causal coverage == dense strategy."""
        from bigdl_tpu import nn

        rng = np.random.RandomState(6)
        x = rng.randn(2, 128, 32).astype(np.float32)
        sp = nn.MultiHeadAttention(32, 4, causal=True,
                                   seq_strategy="blocksparse",
                                   num_kv_heads=2, sparse_window=8,
                                   sparse_globals=0, block_size=32)
        de = nn.MultiHeadAttention(32, 4, causal=True,
                                   seq_strategy="dense", num_kv_heads=2)
        de.set_param_tree(sp.param_tree())
        np.testing.assert_allclose(np.asarray(sp.forward(x)),
                                   np.asarray(de.forward(x)),
                                   rtol=1e-4, atol=1e-5)

    def test_non_default_sm_scale_parity(self):
        """The reference-fallback scale-bug class: a NON-default
        sm_scale must land identically on all three paths (flash's
        ``_attention_reference`` pre-multiplies q by sm_scale·sqrt(d)
        to undo the dense path's internal scaling — this spec pins
        that the kernels and both fallbacks agree)."""
        q, k, v = _qkv(T=128, seed=3)
        sm = 0.37
        ref = _attention_reference(q, k, v, True, sm)
        fl = flash_attention(q, k, v, causal=True, sm_scale=sm,
                             interpret=True)
        bs = block_sparse_attention(q, k, v, _full(128, 32), causal=True,
                                    sm_scale=sm, interpret=True)
        # and the off-kernel dense fallbacks of both wrappers
        fl_fb = flash_attention(q[:, :, :60], k[:, :, :60], v[:, :, :60],
                                causal=True, sm_scale=sm)
        ref_fb = _attention_reference(q[:, :, :60], k[:, :, :60],
                                      v[:, :, :60], True, sm)
        bs_fb = block_sparse_attention(q, k, v, _full(128, 32),
                                       causal=True, sm_scale=sm)
        np.testing.assert_allclose(np.asarray(fl), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(bs), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(bs_fb), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(fl_fb), np.asarray(ref_fb),
                                   rtol=1e-5, atol=1e-6)


class TestSparseMasks:
    def test_matches_masked_dense_reference(self):
        from bigdl_tpu.ops.block_sparse import _bs_attention_reference

        q, k, v = _qkv(seed=4)
        mask = sliding_window_mask(4, 4, window=2, n_global=1,
                                   causal=True, block_q=32, block_k=32)
        out = block_sparse_attention(q, k, v, mask, causal=True,
                                     interpret=True)
        ref = _bs_attention_reference(q, k, v, mask, True,
                                      1 / np.sqrt(q.shape[-1]))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_masked_blocks_nan_poisoned_output_finite_and_unchanged(self):
        """THE skip proof: poison every K/V position no unmasked block
        pair can read with NaN — if masked tiles were loaded and
        multiplied-then-masked, NaN would propagate; skipped tiles
        leave the output bit-identical to the clean run.  Grads too."""
        q, k, v = _qkv(seed=5)
        m = np.eye(4, dtype=bool)
        m[:, 0] = True                   # global anchor block
        m[2, 2] = False                  # k block 2 now fully dead
        mask = BlockMask(m, 32, 32)
        clean = block_sparse_attention(q, k, v, mask, causal=True,
                                       interpret=True)
        elem = mask.pruned_causal().elementwise()
        dead = ~elem.any(axis=0)        # k positions NO q block reads
        assert dead.any(), "pattern too dense to prove anything"
        kp = np.asarray(k).copy()
        vp = np.asarray(v).copy()
        kp[:, :, dead, :] = np.nan
        vp[:, :, dead, :] = np.nan
        kp, vp = jnp.asarray(kp), jnp.asarray(vp)
        poisoned = block_sparse_attention(q, kp, vp, mask, causal=True,
                                          interpret=True)
        assert bool(jnp.isfinite(poisoned).all())
        np.testing.assert_array_equal(np.asarray(poisoned),
                                      np.asarray(clean))
        g = jax.grad(lambda a: jnp.sum(block_sparse_attention(
            a, kp, vp, mask, causal=True, interpret=True) ** 2))(q)
        assert bool(jnp.isfinite(g).all())

    def test_fully_masked_row_emits_zero(self):
        q, k, v = _qkv(B=1, H=1, seed=7)
        m = np.ones((4, 4), bool)
        m[2, :] = False                  # q blocks 64..95 attend nothing
        out = block_sparse_attention(q, k, v, BlockMask(m, 32, 32),
                                     causal=False, interpret=True)
        out = np.asarray(out)
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(out[:, :, 64:96], 0.0)
        assert np.abs(out[:, :, :64]).max() > 0

    def test_builders_and_divisor(self):
        m = sliding_window_mask(8, 8, window=2, n_global=1, causal=True)
        # row 5: globals {0} + window {4, 5}
        np.testing.assert_array_equal(np.nonzero(m.mask[5])[0], [0, 4, 5])
        s = strided_mask(8, 8, stride=4, causal=True)
        np.testing.assert_array_equal(np.nonzero(s.mask[5])[0], [3, 5])
        assert not m.transposed().mask[1, 5] and m.mask[5, 1] == \
            m.transposed().mask[1, 5]
        assert pick_block_divisor(4096, 4096, 512) == 512
        assert pick_block_divisor(96, 96, 512) == 96
        assert pick_block_divisor(96, 64, 512) == 32
        mag = magnitude_block_mask(np.random.RandomState(0).randn(8, 8),
                                   1, 1, 0.5)
        assert mag.nnz == 32

    def test_accounting_rides_the_grid_tables(self):
        """Executed-work ∝ density, derived from the SAME index tables
        the kernel grid sweeps — the MFU-correction basis."""
        mask = sliding_window_mask(8, 8, window=2, n_global=1,
                                   causal=True, block_q=32, block_k=32)
        w = attention_work(mask, batch=2, heads=2, head_dim=32,
                           causal=True)
        assert w["executed_block_pairs"] == mask.pruned_causal().nnz
        assert w["sparse_flops_skipped"] == pytest.approx(
            w["dense_equivalent_flops"] - w["executed_flops"])
        full = attention_work(_full(256, 32), 2, 2, 32, causal=False)
        assert full["executed_fraction"] == 1.0
        assert full["sparse_flops_skipped"] == 0.0
        half = magnitude_block_mask(
            np.random.RandomState(1).randn(8, 8), 1, 1, 0.5)
        hw = attention_work(BlockMask(half.mask, 32, 32), 1, 1, 32)
        assert hw["executed_fraction"] == pytest.approx(0.5)


class TestBlockSparseMatmul:
    def test_matches_masked_dense_fwd_and_grads(self):
        rng = np.random.RandomState(8)
        x = jnp.asarray(rng.randn(16, 128).astype(np.float32))
        w = jnp.asarray(rng.randn(128, 64).astype(np.float32) * 0.3)
        mask = magnitude_block_mask(w, 32, 32, 0.5)
        elem = jnp.asarray(mask.elementwise(), w.dtype)
        y = block_sparse_matmul(x, w, mask, interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ (w * elem)),
                                   rtol=1e-4, atol=1e-4)
        gx, gw = jax.grad(lambda a, b: jnp.sum(block_sparse_matmul(
            a, b, mask, interpret=True) ** 2), argnums=(0, 1))(x, w)
        rx, rw = jax.grad(lambda a, b: jnp.sum((a @ (b * elem)) ** 2),
                          argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                   rtol=1e-3, atol=1e-3)
        # structural zeros get NO gradient
        np.testing.assert_array_equal(
            np.asarray(gw)[~np.asarray(mask.elementwise())], 0.0)

    def test_masked_weight_blocks_nan_poisoned(self):
        rng = np.random.RandomState(9)
        x = jnp.asarray(rng.randn(8, 64).astype(np.float32))
        w = rng.randn(64, 64).astype(np.float32)
        mask = magnitude_block_mask(w, 32, 32, 0.5)
        clean = block_sparse_matmul(x, jnp.asarray(w), mask,
                                    interpret=True)
        wp = w.copy()
        wp[~mask.elementwise()] = np.nan
        poisoned = block_sparse_matmul(x, jnp.asarray(wp), mask,
                                       interpret=True)
        assert bool(jnp.isfinite(poisoned).all())
        np.testing.assert_array_equal(np.asarray(poisoned),
                                      np.asarray(clean))

    def test_batched_leading_dims_and_work(self):
        rng = np.random.RandomState(10)
        x = jnp.asarray(rng.randn(2, 8, 64).astype(np.float32))
        w = jnp.asarray(rng.randn(64, 64).astype(np.float32))
        mask = magnitude_block_mask(w, 32, 32, 0.25)
        y = block_sparse_matmul(x, w, mask, interpret=True)
        assert y.shape == (2, 8, 64)
        mw = matmul_work(mask, 16)
        assert mw["executed_fraction"] == pytest.approx(0.25)


class TestAccountantCorrection:
    def test_report_sparse_flops_gauge_payload_and_mfu_basis(self):
        """The kernel-reported correction: MFU on executed work, dense
        equivalent alongside, skip in the gauge — the speedup must
        never read as an MFU regression."""
        from bigdl_tpu.telemetry import MetricsRegistry
        from bigdl_tpu.telemetry.device_info import CPU_SPEC
        from bigdl_tpu.telemetry.perf import PerfAccountant, StepCost

        pa = PerfAccountant(registry=MetricsRegistry(), spec=CPU_SPEC)
        pa.on_program("bs_step", StepCost(flops=100.0,
                                          bytes_accessed=10.0))
        pa.report_sparse_flops("bs_step", executed_flops=50.0,
                               dense_equiv_flops=100.0)
        entry = pa.payload()["programs"]["bs_step"]
        assert entry["flops"] == 150.0          # cost-model + executed
        assert entry["executed_flops"] == 150.0
        assert entry["dense_equivalent_flops"] == 200.0
        assert entry["sparse_flops_skipped"] == 50.0
        snap = pa.registry.snapshot()["metrics"]
        series = snap["bigdl_perf_sparse_flops_skipped"]["series"]
        assert series[0]["value"] == 50.0
        # repeated reports REPLACE (never compound)
        pa.report_sparse_flops("bs_step", 80.0, 100.0)
        entry = pa.payload()["programs"]["bs_step"]
        assert entry["flops"] == 180.0
        assert entry["sparse_flops_skipped"] == 20.0
        # MFU rate is computed on the corrected (executed) flops
        pa.on_step(1.0)
        snap = pa.registry.snapshot()["metrics"]
        rate = snap["bigdl_perf_model_flops_per_sec"]["series"][0]["value"]
        assert rate == pytest.approx(180.0)

    def test_fresh_analysis_supersedes_correction(self):
        from bigdl_tpu.telemetry import MetricsRegistry
        from bigdl_tpu.telemetry.perf import PerfAccountant, StepCost

        pa = PerfAccountant(registry=MetricsRegistry())
        pa.on_program("p", StepCost(flops=10.0, bytes_accessed=1.0))
        pa.report_sparse_flops("p", 5.0, 10.0)
        pa.on_program("p", StepCost(flops=20.0, bytes_accessed=1.0))
        entry = pa.payload()["programs"]["p"]
        assert entry["flops"] == 20.0
        assert "sparse_flops_skipped" not in entry
