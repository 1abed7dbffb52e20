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


def _out_and_grads(fn, q, k, v):
    """(out, dq, dk, dv), the loss a sum of squares."""
    def loss(a, b, c):
        out = fn(a, b, c)
        return jnp.sum(out ** 2), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out,) + grads


def _dense_pair(fn_flash, fn_ref, q, k, v):
    return _out_and_grads(fn_flash, q, k, v), _out_and_grads(fn_ref, q, k, v)


class TestFlashSubTiles:
    """PR 30: inside a grid tile the three kernels walk sub-tiles up to
    the diagonal, and mask only those it crosses."""

    def _qkv(self, T, S, D, seed):
        rng = np.random.RandomState(seed)
        return (jnp.asarray(rng.randn(1, 1, T, D).astype(np.float32) * 0.5),
                jnp.asarray(rng.randn(1, 1, S, D).astype(np.float32) * 0.5),
                jnp.asarray(rng.randn(1, 1, S, D).astype(np.float32) * 0.5))

    def _check(self, T, S, head, causal, seed, **tiles):
        q, k, v = self._qkv(T, S, head, seed)
        got, ref = _dense_pair(
            lambda a, b, c: flash_attention(a, b, c, causal=causal,
                                            interpret=True, **tiles),
            lambda a, b, c: _attention_reference(
                a, b, c, causal, 1 / np.sqrt(head)), q, k, v)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                                   rtol=1e-4, atol=1e-5)
        for a, b in zip(got[1:], ref[1:]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    @pytest.mark.parametrize("head", [64, 128])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("grid,sub", [(256, 128), (512, 128),
                                          (512, 256), (512, 512),
                                          (1024, 512)])
    def test_forward_and_gradients_match_dense(self, grid, sub, causal,
                                               head):
        # one grid tile: nothing goes through scratch, and the backward
        # is the fused kernel (1024 walked in 512s: the training cells)
        self._check(grid, grid, head, causal, seed=grid + sub + head,
                    block_q=grid, block_k=grid, sub_tile=sub)

    @pytest.mark.parametrize("head", [64, 128])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("T,S", [(512, 512), (512, 256), (768, 512),
                                     (768, 256), (256, 512)])
    def test_several_grid_tiles(self, T, S, causal, head):
        # grid tile 256, sub-tile 128: the carry rests in scratch between
        # grid steps and ``pl.when`` picks each tile's schedule by its
        # offset from the diagonal; T > S is causal cross-attention.
        # S = 256 is ONE key tile under two or three query tiles: the
        # fused backward, dk/dv resting in scratch between them
        self._check(T, S, head, causal, seed=T + S + head, block_q=256,
                    block_k=256, sub_tile=128)

    @pytest.mark.parametrize("sub", [128, (256, 128), (128, 256)])
    def test_one_grid_tile_static_schedule(self, sub):
        # T = S = one grid tile: no grid index chooses a schedule,
        # nothing goes through scratch
        q, k, v = self._qkv(512, 512, 64, seed=11)
        got, ref = _dense_pair(
            lambda a, b, c: flash_attention(a, b, c, causal=True,
                                            interpret=True, sub_tile=sub),
            lambda a, b, c: _attention_reference(a, b, c, True, 0.125),
            q, k, v)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    @pytest.mark.parametrize("T,S,tiles,kernel", [
        # the training cells' shape, tiles from the shape: 1024 in 512s
        (1024, 1024, {}, "bwd_fused"),
        (512, 512, {}, "bwd_fused"),
        # S > T and T > S with one key grid tile
        (256, 512, dict(block_q=256, block_k=512, sub_tile=128),
         "bwd_fused"),
        (1024, 512, dict(block_q=512, block_k=512, sub_tile=256),
         "bwd_fused"),
        # several key grid tiles keep the dKdV and dQ kernels
        (512, 512, dict(block_q=256, block_k=256, sub_tile=128), "bwd"),
        (256, 512, dict(block_q=256, block_k=256, sub_tile=128), "bwd"),
    ])
    def test_backward_is_chosen_by_the_key_grid_tiles(self, T, S, tiles,
                                                      kernel):
        """One kernel where the key axis is one grid tile, two where it
        is several — the operands' shapes alone choose, and both answer
        the dense gradients."""
        from bigdl_tpu.telemetry import default_tracer

        self._check(T, S, 64, True, seed=T + S, **tiles)
        assert [s.args["kernel"] for s in default_tracer().spans()
                if s.name == "flash.schedule"] == ["fwd", kernel]

    def test_two_kernel_backward_over_many_heads(self):
        # (1, 16, 2048, 64) at grid 512: 4 x 4 grid tiles a head
        rng = np.random.RandomState(16)
        q, k, v = (jnp.asarray(rng.randn(1, 16, 2048, 64).astype(np.float32)
                               * 0.5) for _ in range(3))
        got, ref = _dense_pair(
            lambda a, b, c: flash_attention(a, b, c, causal=True,
                                            interpret=True, block_q=512,
                                            block_k=512),
            lambda a, b, c: _attention_reference(a, b, c, True, 0.125),
            q, k, v)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    @pytest.mark.parametrize("shape,grid,want", [
        ((8, 16, 1024, 64), None, "bwd_fused"),     # the training cells
        ((1, 16, 2048, 64), 512, "bwd"),
        ((1, 16, 2048, 64), None, "bwd"),           # 2 x 2 tiles of 1024
        ((4, 8, 512, 128), None, "bwd_fused"),      # wide heads, one tile
        ((4, 8, 1024, 128), None, "bwd"),           # ... grid 512: two
    ])
    def test_traced_backward_records_which_it_built(self, shape, grid,
                                                    want):
        """The counter: tracing ``_flash_bwd`` (nothing runs) records ONE
        ``flash.schedule`` event that names the backward it built."""
        from bigdl_tpu.ops.flash_attention import (_flash_bwd, _tiles,
                                                   causal_schedule)
        from bigdl_tpu.telemetry import default_tracer

        B, H, T, D = shape
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        lse = jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32)
        jax.eval_shape(
            lambda q, k, v, o, l, g: _flash_bwd(
                q, k, v, o, l, g, True, 0.125, grid, grid, None, True),
            x, x, x, x, lse, x)
        event, = [s for s in default_tracer().spans()
                  if s.name == "flash.schedule"]
        bq, bk, sq, sk = _tiles(T, T, D, grid, grid, None, backward=True)
        assert event.args == {
            "kernel": want, "T": T, "S": T, "head_dim": D,
            "grid_tile": [bq, bk], "sub_tile": [sq, sk],
            **causal_schedule(T, T, (bq, bk), (sq, sk), True)}
        assert (want == "bwd_fused") == (T == bk)

    @pytest.mark.parametrize("block_k", [512, 256])
    def test_kernels_skip_and_do_not_compute_then_mask(self, block_k):
        """Causal with S > T: no query may see the K/V rows at
        positions >= T.  They hold NaN: a kernel that skips their
        sub-tiles never reads them; one that computes and then masks
        gives NaN through 0 x NaN in the PV product.  block_k 512: the
        sub-tile walk stops short inside one grid tile; 256: the second
        k grid tile is skipped whole."""
        T, S = 256, 512
        q, k, v = self._qkv(T, S, 64, seed=12)
        poison = jnp.arange(S)[None, None, :, None] >= T
        kp = jnp.where(poison, jnp.nan, k)
        vp = jnp.where(poison, jnp.nan, v)

        def flash(a, b, c):
            return flash_attention(a, b, c, causal=True, interpret=True,
                                   block_q=256, block_k=block_k,
                                   sub_tile=128)

        out, dq, dk, dv = _out_and_grads(flash, q, kp, vp)
        ref = _out_and_grads(
            lambda a, b, c: _attention_reference(a, b, c, True, 0.125),
            q, k[:, :, :T], v[:, :, :T])
        for a, b in zip((out, dq, dk[:, :, :T], dv[:, :, :T]), ref):
            assert np.isfinite(np.asarray(a)).all()
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)
        # the keys nobody sees get exactly zero gradient
        assert not np.asarray(dk[:, :, T:]).any()
        assert not np.asarray(dv[:, :, T:]).any()

    def test_schedule_event_in_the_tracers_ring(self):
        from bigdl_tpu.telemetry import default_tracer

        q, k, v = self._qkv(512, 512, 64, seed=13)
        jax.grad(lambda a: jnp.sum(flash_attention(
            a, k, v, causal=True, interpret=True, block_q=512,
            block_k=512, sub_tile=128)))(q)
        events = [s for s in default_tracer().spans()
                  if s.name == "flash.schedule"]
        assert [e.args["kernel"] for e in events] == ["fwd", "bwd_fused"]
        for e in events:
            assert e.category == "compile" and e.duration == 0.0
            assert e.args == {
                "kernel": e.args["kernel"], "T": 512, "S": 512,
                "head_dim": 64, "grid_tile": [512, 512],
                "sub_tile": [128, 128], "computed": 10, "masked": 4,
                "skipped": 6}


class TestCausalSchedule:
    """``causal_schedule`` counts with the bounds the kernels' loops run
    to (``_visible_k`` for the forward and dQ, ``_visible_q`` for
    dKdV)."""

    @pytest.mark.parametrize("T,grid,sub,want", [
        (1024, 1024, 256, (10, 4, 6)),     # the training cells, of 16
        (1024, 1024, 128, (36, 8, 28)),
        (2048, 1024, 256, (36, 8, 28)),    # the prefill cell, of 64
        (1024, 1024, 1024, (1, 1, 0)),     # the parent's schedule
        (128, 128, 256, (1, 1, 0)),        # the decode cells' prompts
    ])
    def test_closed_form(self, T, grid, sub, want):
        from bigdl_tpu.ops.flash_attention import _tiles, causal_schedule

        bq, bk, sq, sk = _tiles(T, T, 64, grid, grid, sub)
        got = causal_schedule(T, T, (bq, bk), (sq, sk), True)
        assert (got["computed"], got["masked"], got["skipped"]) == want
        # n sub-tiles a side: n(n+1)/2 on or under the diagonal, n on it
        n = T // sq
        assert want[:2] == (n * (n + 1) // 2, n)
        assert causal_schedule(T, T, (bq, bk), (sq, sk), False) == {
            "computed": n * n, "masked": 0, "skipped": 0}

    @pytest.mark.parametrize("T,S,bq,bk,sq,sk", [
        (1024, 1024, 512, 512, 128, 128),
        (1024, 512, 512, 256, 256, 128),    # T > S
        (512, 1024, 256, 512, 128, 256),    # S > T
        (768, 768, 384, 768, 128, 128),
        (1024, 1024, 1024, 512, 512, 128),
    ])
    def test_against_every_position(self, T, S, bq, bk, sq, sk):
        from bigdl_tpu.ops.flash_attention import (_visible_q,
                                                   causal_schedule)

        see = np.arange(T)[:, None] >= np.arange(S)[None, :]
        tiles = see.reshape(T // sq, sq, S // sk, sk)
        some = tiles.any(axis=(1, 3))
        every = tiles.all(axis=(1, 3))
        got = causal_schedule(T, S, (bq, bk), (sq, sk), True)
        assert got == {"computed": int(some.sum()),
                       "masked": int((some & ~every).sum()),
                       "skipped": int((~some).sum())}
        # the dKdV kernel's bounds, per key sub-tile, give the same sets
        for j in range(S // sk):
            first, full = _visible_q(j * sk, sk, 0, sq, T // sq)
            assert list(some[:, j]) == [i >= first
                                        for i in range(T // sq)]
            assert list(every[:, j]) == [i >= full for i in range(T // sq)]


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


class TestPickSubTile:
    """The sub-tile comes from the shape alone (``_pick_sub_tile``,
    fitted to the grid tile of each axis); ``block_q`` / ``block_k`` /
    ``sub_tile`` only override it for sweeps."""

    @pytest.mark.parametrize("T,S,d,want", [
        (1024, 1024, 64, (1024, 1024, 512, 512)),   # gpt2m training cells
        (2048, 2048, 128, (1024, 1024, 1024, 1024)),  # mistral prefill:
        (4096, 4096, 64, (1024, 1024, 1024, 1024)),   # grid tile by grid tile
        (1024, 1024, 128, (512, 512, 512, 512)),    # wide heads: grid 512
        (128, 128, 128, (128, 128, 128, 128)),      # decode cells' prompts:
        (256, 256, 128, (256, 256, 256, 256)),      # one sub-tile, or less
        (64, 64, 32, (64, 64, 64, 64)),
        (640, 640, 64, (640, 640, 128, 128)),       # 512 and 256 do not fit
        (1536, 512, 64, (512, 512, 512, 512)),
    ])
    def test_tiles_from_the_shape(self, T, S, d, want):
        from bigdl_tpu.ops.flash_attention import _tiles

        assert _tiles(T, S, d, None, None, None) == want

    @pytest.mark.parametrize("T,S,d,want", [
        # one key grid tile, the fused kernel: walked in 256s
        (1024, 1024, 64, (1024, 1024, 256, 256)),   # gpt2m training cells
        (512, 512, 128, (512, 512, 256, 256)),
        (2048, 1024, 64, (1024, 1024, 1024, 1024)),  # ... but not past 1024
        (128, 128, 64, (128, 128, 128, 128)),
        # several key grid tiles, dKdV and dQ: the forward's sub-tile
        (1024, 1024, 128, (512, 512, 512, 512)),
        (2048, 2048, 64, (1024, 1024, 1024, 1024)),
    ])
    def test_backward_tiles_from_the_shape(self, T, S, d, want):
        from bigdl_tpu.ops.flash_attention import _tiles

        assert _tiles(T, S, d, None, None, None, backward=True) == want
        # an override is an override for every kernel
        assert _tiles(T, S, d, None, None, 128, backward=True) == _tiles(
            T, S, d, None, None, 128)

    def test_overrides_are_fitted_to_the_grid_tile(self):
        from bigdl_tpu.ops.flash_attention import _tiles

        assert _tiles(1024, 1024, 64, 512, 512, 256) == (512, 512, 256, 256)
        assert _tiles(1024, 1024, 64, None, None, (256, 128)) == (
            1024, 1024, 256, 128)
        # a sub-tile wider than the grid tile is the grid tile
        assert _tiles(1024, 1024, 64, 256, 256, 512) == (256, 256, 256, 256)

