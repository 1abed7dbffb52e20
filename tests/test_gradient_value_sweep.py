"""Gradient VALUE oracles across the whole registry (VERDICT r3 #3).

The reference cross-validates every layer's hand-written backward
against Torch7 (torch/TH.scala:33-43, 122 specs) plus perturbation
sweeps (GradientChecker.scala).  Here every backward is one ``jax.vjp``
of the pure apply, so a single systematic primitive covers the registry:
for EVERY concrete layer and criterion, the public ``backward`` is
checked against float64 central differences of the public ``forward``
(directional derivatives along fixed random directions — each assertion
pins the full gradient's projection, input grads AND accumulated
parameter grads).

Layers whose backward is BY DESIGN not the forward's derivative
(GradientReversal, L1Penalty — custom_vjp side-band gradients, like the
reference modules they mirror) are asserted against their analytic spec
instead.  The only registry names excluded are ops with no
differentiable surface at all; a meta-test pins coverage >= 90%.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64

from bigdl_tpu import nn
from bigdl_tpu.models.latent_moe import LatentMoEBlock
from bigdl_tpu.models.parallel_moe import ParallelMoEBlock
from bigdl_tpu.parallel.moe import DroplessMoE
from bigdl_tpu.utils.table import T, Table

EPS = 1e-6
RTOL = 5e-4
ATOL = 1e-6


def _f64(tree):
    def cast(a):
        a = jnp.asarray(a)
        if jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(jnp.float64)
        return a
    return jax.tree_util.tree_map(cast, tree)


def _is_float(a):
    return jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)


def _proj(go_leaves, y):
    tot = 0.0
    for g, l in zip(go_leaves, jax.tree_util.tree_leaves(y)):
        if g is not None:
            tot += float(jnp.vdot(g, jnp.asarray(l, jnp.float64)))
    return tot


def check_module(mod, inp, diff=None, check_params=True, eps=EPS,
                 rtol=RTOL, atol=ATOL, seed=0, train=False):
    """Public-API gradient check: ``backward``'s grad-input and the
    accumulated parameter grads vs float64 central differences of
    ``forward``, projected on fixed random directions."""
    with enable_x64():
        if train:
            mod.training()
        else:
            mod.evaluate()
        mod.set_param_tree(_f64(mod.param_tree()))
        mod.set_buffer_tree(_f64(mod.buffer_tree()))
        x = _f64(inp)
        rng = np.random.RandomState(seed)

        y0 = mod.forward(x)
        # go carries each output leaf's OWN dtype (a module may emit
        # f32 regardless of input dtype, e.g. a stored Const value)
        go_leaves = [jnp.asarray(rng.standard_normal(np.asarray(l).shape),
                                 jnp.asarray(l).dtype)
                     if _is_float(l) else None
                     for l in jax.tree_util.tree_leaves(y0)]
        go = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(y0),
            [g if g is not None else jnp.zeros(np.asarray(l).shape)
             for g, l in zip(go_leaves, jax.tree_util.tree_leaves(y0))])

        mod.set_grad_tree(jax.tree_util.tree_map(
            lambda a: jnp.zeros(np.asarray(a).shape, jnp.float64),
            mod.grad_tree()))
        gi = mod.backward(x, go)

        x_leaves, xdef = jax.tree_util.tree_flatten(x)
        gi_leaves = jax.tree_util.tree_leaves(gi)
        assert len(gi_leaves) == len(x_leaves), \
            "grad-input tree does not match input tree"
        d_idx = (list(diff) if diff is not None
                 else [i for i, l in enumerate(x_leaves) if _is_float(l)])

        def fwd_proj(leaves):
            return _proj(go_leaves,
                         mod.forward(jax.tree_util.tree_unflatten(xdef,
                                                                  leaves)))

        for trial in range(2):
            vs = {i: jnp.asarray(rng.standard_normal(
                np.asarray(x_leaves[i]).shape)) for i in d_idx}
            if not vs:
                break
            plus = fwd_proj([l + eps * vs[i] if i in vs else l
                             for i, l in enumerate(x_leaves)])
            minus = fwd_proj([l - eps * vs[i] if i in vs else l
                              for i, l in enumerate(x_leaves)])
            numeric = (plus - minus) / (2 * eps)
            analytic = sum(float(jnp.vdot(jnp.asarray(gi_leaves[i],
                                                      jnp.float64), vs[i]))
                           for i in d_idx)
            assert np.isclose(numeric, analytic, rtol=rtol, atol=atol), \
                (f"{type(mod).__name__} INPUT grad trial {trial}: "
                 f"numeric {numeric} != analytic {analytic}")

        params = mod.param_tree()
        p_leaves, pdef = jax.tree_util.tree_flatten(params)
        if check_params and p_leaves:
            gp_leaves = jax.tree_util.tree_leaves(mod.grad_tree())
            for trial in range(2):
                vs = [jnp.asarray(rng.standard_normal(
                    np.asarray(l).shape)) for l in p_leaves]

                def at(sign):
                    mod.set_param_tree(jax.tree_util.tree_unflatten(
                        pdef, [l + sign * eps * v
                               for l, v in zip(p_leaves, vs)]))
                    val = _proj(go_leaves, mod.forward(x))
                    return val

                numeric = (at(+1) - at(-1)) / (2 * eps)
                mod.set_param_tree(jax.tree_util.tree_unflatten(pdef,
                                                                p_leaves))
                analytic = sum(float(jnp.vdot(jnp.asarray(g, jnp.float64),
                                              v))
                               for g, v in zip(gp_leaves, vs))
                assert np.isclose(numeric, analytic, rtol=rtol, atol=atol), \
                    (f"{type(mod).__name__} PARAM grad trial {trial}: "
                     f"numeric {numeric} != analytic {analytic}")


def check_criterion(crit, inp, target, eps=EPS, rtol=RTOL, atol=ATOL,
                    seed=0, diff=None):
    """d(loss)/d(input) from the public ``backward`` vs float64 central
    differences of the public ``forward`` (targets never differentiated,
    as in the reference's criterion specs)."""
    with enable_x64():
        x, t = _f64(inp), _f64(target)
        rng = np.random.RandomState(seed)
        gi = crit.backward(x, t)
        x_leaves, xdef = jax.tree_util.tree_flatten(x)
        gi_leaves = jax.tree_util.tree_leaves(gi)
        d_idx = (list(diff) if diff is not None
                 else [i for i, l in enumerate(x_leaves) if _is_float(l)])
        for trial in range(2):
            vs = {i: jnp.asarray(rng.standard_normal(
                np.asarray(x_leaves[i]).shape)) for i in d_idx}
            plus = float(crit.forward(jax.tree_util.tree_unflatten(
                xdef, [l + eps * vs[i] if i in vs else l
                       for i, l in enumerate(x_leaves)]), t))
            minus = float(crit.forward(jax.tree_util.tree_unflatten(
                xdef, [l - eps * vs[i] if i in vs else l
                       for i, l in enumerate(x_leaves)]), t))
            numeric = (plus - minus) / (2 * eps)
            analytic = sum(float(jnp.vdot(jnp.asarray(gi_leaves[i],
                                                      jnp.float64), vs[i]))
                           for i in d_idx)
            assert np.isclose(numeric, analytic, rtol=rtol, atol=atol), \
                (f"{type(crit).__name__} trial {trial}: numeric {numeric} "
                 f"!= analytic {analytic}")


# --------------------------------------------------------------------------
# fixed inputs (f32 here; the checker upcasts)
# --------------------------------------------------------------------------
R = np.random.RandomState(7)
X = R.randn(3, 6).astype(np.float32)
X2 = R.randn(3, 6).astype(np.float32)
XP = (R.rand(3, 6) + 0.2).astype(np.float32)       # strictly positive
X3 = R.randn(2, 5, 6).astype(np.float32)           # (B, T, F) sequences
X4 = R.randn(2, 3, 8, 8).astype(np.float32)        # NCHW images
X134 = R.randn(3, 1, 4).astype(np.float32)
X234 = R.randn(2, 3, 4).astype(np.float32)
X8 = R.randn(2, 5, 8).astype(np.float32)
X8S = R.randn(2, 5, 3, 8).astype(np.float32)        # (B, T, streams, C)
X5D = R.randn(1, 2, 4, 6, 6).astype(np.float32)    # NCDHW
XC = R.randn(2, 3, 3, 8, 8).astype(np.float32)     # (B, T, C, H, W)

_CONN = np.array([[1, 1], [2, 2], [3, 3]], np.float32)
_TREE = np.stack([np.array([[2, 3, -1], [0, 0, 1], [4, 5, 0],
                            [0, 0, 2], [0, 0, 3], [-1, -1, 0]],
                           np.float32)] * 2)
_XTREE = R.randn(2, 3, 4).astype(np.float32)

# name -> (module factory, input factory, kwargs for check_module)
def _moving(hc):
    """A hyper-connection whose maps move with the token: gates of 1
    (the module draws the papers' 0.01)."""
    tree = hc.param_tree()
    hc.set_param_tree({**tree, **{k: jnp.ones_like(tree[k]) for k in
                                  ("alpha_pre", "alpha_post", "alpha_res")}})
    return hc


MODULE_CASES = {
    "Abs": (lambda: nn.Abs(), lambda: XP, {}),
    "Add": (lambda: nn.Add(6), lambda: X, {}),
    "AddConstant": (lambda: nn.AddConstant(2.5), lambda: X, {}),
    "BatchNormalization": (lambda: nn.BatchNormalization(6),
                           lambda: X, {}),
    "BiRecurrent": (lambda: nn.BiRecurrent().add(nn.GRU(6, 4)),
                    lambda: X3, {}),
    "Bilinear": (lambda: nn.Bilinear(5, 4, 3),
                 lambda: T(R.randn(3, 5).astype(np.float32),
                           R.randn(3, 4).astype(np.float32)), {}),
    "BinaryTreeLSTM": (lambda: nn.BinaryTreeLSTM(4, 3),
                       lambda: T(_XTREE, _TREE), {"diff": [0]}),
    "Bottle": (lambda: nn.Bottle(nn.Linear(6, 4), 2, 2), lambda: X3, {}),
    "CAdd": (lambda: nn.CAdd([6]), lambda: X, {}),
    "CAddTable": (lambda: nn.CAddTable(), lambda: T(X, X2), {}),
    "CDivTable": (lambda: nn.CDivTable(), lambda: T(XP, XP + 0.5), {}),
    "CMaxTable": (lambda: nn.CMaxTable(), lambda: T(X, X2), {}),
    "CMinTable": (lambda: nn.CMinTable(), lambda: T(X, X2), {}),
    "CMul": (lambda: nn.CMul([6]), lambda: X, {}),
    "CMulTable": (lambda: nn.CMulTable(), lambda: T(X, X2), {}),
    "CSubTable": (lambda: nn.CSubTable(), lambda: T(X, X2), {}),
    "Clamp": (lambda: nn.Clamp(-0.5, 0.5), lambda: X, {}),
    "Concat": (lambda: nn.Concat(2, nn.Linear(6, 4), nn.Linear(6, 3)),
               lambda: X, {}),
    "ConcatTable": (lambda: nn.ConcatTable(nn.Linear(6, 4), nn.Tanh()),
                    lambda: X, {}),
    "Const": (lambda: nn.Const(np.ones((3, 2), np.float32)),
              lambda: X, {}),
    "Contiguous": (lambda: nn.Contiguous(), lambda: X, {}),
    "ConvLSTMPeephole": (
        lambda: nn.Recurrent().add(nn.ConvLSTMPeephole(3, 4, 3, 3)),
        lambda: XC, {}),
    "Cosine": (lambda: nn.Cosine(6, 4), lambda: X, {}),
    "CosineDistance": (lambda: nn.CosineDistance(), lambda: T(X, X2), {}),
    "DotProduct": (lambda: nn.DotProduct(), lambda: T(X, X2), {}),
    "Dropout": (lambda: nn.Dropout(0.5), lambda: X, {}),  # eval: identity
    "ELU": (lambda: nn.ELU(), lambda: X, {}),
    "Echo": (lambda: nn.Echo(), lambda: X, {}),
    "Euclidean": (lambda: nn.Euclidean(6, 3), lambda: X, {}),
    "Exp": (lambda: nn.Exp(), lambda: X, {}),
    "FlattenTable": (lambda: nn.FlattenTable(),
                     lambda: T(X, T(X2, XP)), {}),
    "GRU": (lambda: nn.Recurrent().add(nn.GRU(6, 4)), lambda: X3, {}),
    "Graph": (None, None, None),  # dedicated test below
    "HardShrink": (lambda: nn.HardShrink(0.5), lambda: X, {}),
    "HardTanh": (lambda: nn.HardTanh(), lambda: X, {}),
    "Identity": (lambda: nn.Identity(), lambda: X, {}),
    "ImageNormalize": (lambda: nn.ImageNormalize((0.4, 0.5, 0.6),
                                                 (0.2, 0.25, 0.3)),
                       lambda: R.randn(2, 6, 6, 3).astype(np.float32),
                       {}),
    "Index": (lambda: nn.Index(1),
              lambda: T(X, np.array([2.0, 1.0], np.float32)),
              {"diff": [0]}),
    "InferReshape": (lambda: nn.InferReshape([4, 6]), lambda: X234, {}),
    "JoinTable": (lambda: nn.JoinTable(2, 2), lambda: T(X, X2), {}),
    "LSTM": (lambda: nn.Recurrent().add(nn.LSTM(6, 4)), lambda: X3, {}),
    "LSTMPeephole": (lambda: nn.Recurrent().add(nn.LSTMPeephole(6, 4)),
                     lambda: X3, {}),
    "LayerNorm": (lambda: nn.LayerNorm(6), lambda: X, {}),
    "RMSNorm": (lambda: nn.RMSNorm(6), lambda: X, {}),
    "LeakyReLU": (lambda: nn.LeakyReLU(0.1), lambda: X, {}),
    "Linear": (lambda: nn.Linear(6, 4), lambda: X, {}),
    "Log": (lambda: nn.Log(), lambda: XP, {}),
    "LogSigmoid": (lambda: nn.LogSigmoid(), lambda: X, {}),
    "LogSoftMax": (lambda: nn.LogSoftMax(), lambda: X, {}),
    "LookupTable": (lambda: nn.LookupTable(10, 4),
                    lambda: np.array([[1., 3.], [2., 9.]], np.float32),
                    {"diff": []}),
    # unbound (eager) path: the local gather — the bound index-exchange
    # path is pinned in tests/test_sparse_transport.py
    "ShardedEmbedding": (lambda: nn.ShardedEmbedding(10, 4),
                         lambda: np.array([[1., 3.], [2., 9.]],
                                          np.float32),
                         {"diff": []}),
    "MM": (lambda: nn.MM(),
           lambda: T(R.randn(2, 3, 4).astype(np.float32),
                     R.randn(2, 4, 5).astype(np.float32)), {}),
    "MV": (lambda: nn.MV(),
           lambda: T(R.randn(2, 4, 5).astype(np.float32),
                     R.randn(2, 5).astype(np.float32)), {}),
    "MapTable": (lambda: nn.MapTable(nn.Linear(6, 4)),
                 lambda: T(X, X2), {}),
    "MaskedSelect": (lambda: nn.MaskedSelect(),
                     lambda: T(X, (X2 > 0).astype(np.float32)),
                     {"diff": [0]}),
    "Max": (lambda: nn.Max(2), lambda: X, {}),
    "Mean": (lambda: nn.Mean(2), lambda: X, {}),
    "Min": (lambda: nn.Min(2), lambda: X, {}),
    "MixtureTable": (lambda: nn.MixtureTable(),
                     lambda: T((R.rand(3, 2) + 0.1).astype(np.float32),
                               T(X, X2)), {}),
    "Mul": (lambda: nn.Mul(), lambda: X, {}),
    "MulConstant": (lambda: nn.MulConstant(2.5), lambda: X, {}),
    "MultiHeadAttention": (lambda: nn.MultiHeadAttention(8, 2),
                           lambda: X8, {}),
    "Mamba2Mixer": (lambda: nn.Mamba2Mixer(8, n_heads=2, head_dim=4,
                                           d_state=4, n_groups=2,
                                           chunk_size=2),
                    lambda: X8, {}),
    "HybridMambaBlock": (lambda: nn.HybridMambaBlock(
        8, num_heads=2, num_kv_heads=1, head_dim=4, mlp_dim=12,
        mamba_heads=2, mamba_head_dim=4, mamba_d_state=4, mamba_groups=2,
        mamba_chunk=2, key_multiplier=0.5, ssm_out_multiplier=0.7,
        mlp_multipliers=(0.8, 0.6)), lambda: X8, {}),
    # the dropless expert layer (a share of the experts, sigmoid scores,
    # a shared expert) and the parallel block around it: the selection
    # is piecewise constant, so central differences see the gates',
    # the experts' and the attention's derivatives
    "DroplessMoE": (lambda: DroplessMoE(8, 12, 6, top_k=2,
                                        scoring="sigmoid", n_shared=1,
                                        held=(1, 4), init_std=0.3),
                    lambda: X8, {}),
    "ParallelMoEBlock": (lambda: ParallelMoEBlock(
        8, num_heads=2, num_kv_heads=1, head_dim=4, expert_dim=12,
        n_experts=6, top_k=2, attention="sliding", window=3, n_shared=1,
        held=(0, 3), init_std=0.3), lambda: X8, {}),
    # latent attention (the expanded form: two low-rank paths, a shared
    # rotated key) and the sequential block around it with the
    # bias-corrected router (the bias chooses: its gradient is zero)
    "LatentAttention": (lambda: nn.LatentAttention(
        8, 2, q_rank=6, kv_rank=6, nope_dim=4, rope_dim=2, v_dim=6),
        lambda: X8, {}),
    "LatentMoEBlock": (lambda: LatentMoEBlock(
        nn.LatentAttention(8, 2, q_rank=6, kv_rank=6, nope_dim=4,
                           rope_dim=2, v_dim=6),
        DroplessMoE(8, 12, 6, top_k=2, scoring="sigmoid", n_shared=1,
                    held=(0, 3), init_std=0.3, score_bias=True,
                    routed_scale=1.8), 8, 1e-5), lambda: X8, {}),
    # the gated short convolution (no attention: three taps over the
    # gated input), per-head QK-norm before the rotation, and the
    # sequential block over each of them
    "GatedShortConv": (lambda: nn.GatedShortConv(8, 3, init_std=0.3),
                       lambda: X8, {}),
    "MultiHeadAttentionQKNorm": (lambda: nn.MultiHeadAttention(
        8, 2, causal=True, with_bias=False, num_kv_heads=1, head_dim=4,
        rope=True, qk_norm=True, norm_eps=1e-5), lambda: X8, {}),
    "ShortConvMoEBlock": (lambda: LatentMoEBlock(
        nn.GatedShortConv(8, 3, init_std=0.3),
        DroplessMoE(8, 12, 6, top_k=2, scoring="sigmoid", init_std=0.3,
                    score_bias=True, renorm_eps=1e-6), 8, 1e-5),
        lambda: X8, {}),
    # the pre-routed block: the router reads the block's INPUT, a
    # softmax over the chosen logits, a ReLU gate, attention under a
    # sliding window of 3
    "PreRoutedMoEBlock": (lambda: LatentMoEBlock(
        nn.MultiHeadAttention(8, 2, causal=True, with_bias=False,
                              num_kv_heads=1, head_dim=4, rope=True,
                              window=3),
        DroplessMoE(8, 12, 6, top_k=2, scoring="softmax", init_std=0.3,
                    activation="relu"), 8, 1e-6, pre_routed=True),
        lambda: X8, {}),
    # a hyper-connected residual (three streams a token) around latent
    # attention with YaRN and the expert layer: autodiff through the
    # sigmoids, ``exp`` and the unrolled Sinkhorn sweeps of both
    # sublayers' maps (``HyperConnection`` has no forward of its own: a
    # block calls its three functions)
    "HyperConnection": (lambda: LatentMoEBlock(
        nn.LatentAttention(8, 2, q_rank=6, kv_rank=6, nope_dim=4,
                           rope_dim=2, v_dim=4, rope_scaling={
                               "type": "yarn", "factor": 4,
                               "original_max_position_embeddings": 2,
                               "mscale": 1, "mscale_all_dim": 1}),
        DroplessMoE(8, 12, 6, top_k=2, scoring="sigmoid", n_shared=1,
                    held=(0, 3), init_std=0.3, score_bias=True,
                    routed_scale=2.0), 8, 1e-6,
        hyper=lambda: _moving(nn.HyperConnection(
            8, 3, sinkhorn_iters=5, init_std=0.3))),
        lambda: X8S, {}),
    "Narrow": (lambda: nn.Narrow(2, 2, 3), lambda: X, {}),
    "NarrowTable": (lambda: nn.NarrowTable(1, 2),
                    lambda: T(X, X2, XP), {}),
    "Normalize": (lambda: nn.Normalize(2.0), lambda: X, {}),
    "PReLU": (lambda: nn.PReLU(), lambda: X, {}),
    "Pack": (lambda: nn.Pack(2), lambda: T(X, X2), {}),
    "Padding": (lambda: nn.Padding(2, 2, 2), lambda: X, {}),
    "PairwiseDistance": (lambda: nn.PairwiseDistance(),
                         lambda: T(X, X2), {}),
    "ParallelTable": (lambda: nn.ParallelTable(nn.Linear(6, 4),
                                               nn.Tanh()),
                      lambda: T(X, X2), {}),
    "Power": (lambda: nn.Power(2.0, 1.5, 0.1), lambda: XP, {}),
    "RReLU": (lambda: nn.RReLU(), lambda: X, {}),  # eval: fixed slope
    "ReLU": (lambda: nn.ReLU(), lambda: X, {}),
    "ReLU6": (lambda: nn.ReLU6(), lambda: X, {}),
    "Recurrent": (lambda: nn.Recurrent().add(nn.RnnCell(6, 4)),
                  lambda: X3, {}),
    "Replicate": (lambda: nn.Replicate(3, 2), lambda: X, {}),
    "Reshape": (lambda: nn.Reshape([12]), lambda: X234, {}),
    "Reverse": (lambda: nn.Reverse(2), lambda: X, {}),
    "RnnCell": (lambda: nn.Recurrent().add(nn.RnnCell(6, 4)),
                lambda: X3, {}),
    "RoiPooling": (lambda: nn.RoiPooling(3, 3, 1.0),
                   lambda: T(R.rand(1, 4, 16, 16).astype(np.float32),
                             np.array([[0, 0, 0, 7, 7],
                                       [0, 4, 4, 15, 15]], np.float32)),
                   {"diff": [0]}),
    "Scale": (lambda: nn.Scale([1, 6]), lambda: X, {}),
    "Select": (lambda: nn.Select(2, 3), lambda: X, {}),
    "SelectTable": (lambda: nn.SelectTable(2), lambda: T(X, X2), {}),
    "Sequential": (lambda: nn.Sequential(nn.Linear(6, 4), nn.Tanh()),
                   lambda: X, {}),
    "Sigmoid": (lambda: nn.Sigmoid(), lambda: X, {}),
    "SoftMax": (lambda: nn.SoftMax(), lambda: X, {}),
    "SoftMin": (lambda: nn.SoftMin(), lambda: X, {}),
    "SoftPlus": (lambda: nn.SoftPlus(), lambda: X, {}),
    "SoftShrink": (lambda: nn.SoftShrink(0.5), lambda: X, {}),
    "SoftSign": (lambda: nn.SoftSign(), lambda: X, {}),
    "SpatialAveragePooling": (lambda: nn.SpatialAveragePooling(2, 2, 2, 2),
                              lambda: X4, {}),
    "SpatialBatchNormalization": (
        lambda: nn.SpatialBatchNormalization(3), lambda: X4, {}),
    "SpatialContrastiveNormalization": (
        lambda: nn.SpatialContrastiveNormalization(3), lambda: X4,
        {"rtol": 2e-3}),
    "SpatialConvolution": (lambda: nn.SpatialConvolution(3, 4, 3, 3),
                           lambda: X4, {}),
    "SpatialConvolutionMap": (
        lambda: nn.SpatialConvolutionMap(_CONN, 3, 3), lambda: X4, {}),
    "SpatialCrossMapLRN": (lambda: nn.SpatialCrossMapLRN(), lambda: X4,
                           {"rtol": 2e-3}),
    "SpatialDilatedConvolution": (
        lambda: nn.SpatialDilatedConvolution(3, 4, 3, 3,
                                             dilation_w=2, dilation_h=2),
        lambda: X4, {}),
    "SpatialDivisiveNormalization": (
        lambda: nn.SpatialDivisiveNormalization(3), lambda: X4,
        {"rtol": 2e-3}),
    "SpatialFullConvolution": (
        lambda: nn.SpatialFullConvolution(3, 4, 3, 3, 2, 2), lambda: X4,
        {}),
    "SpatialMaxPooling": (lambda: nn.SpatialMaxPooling(2, 2, 2, 2),
                          lambda: X4, {}),
    "SpatialShareConvolution": (
        lambda: nn.SpatialShareConvolution(3, 4, 3, 3), lambda: X4, {}),
    "SpatialSubtractiveNormalization": (
        lambda: nn.SpatialSubtractiveNormalization(3), lambda: X4, {}),
    "SpatialZeroPadding": (lambda: nn.SpatialZeroPadding(1, 1, 1, 1),
                           lambda: X4, {}),
    "SplitAndSelect": (lambda: nn.SplitAndSelect(2, 1, 2), lambda: X, {}),
    "SplitTable": (lambda: nn.SplitTable(2), lambda: X3, {}),
    "Sqrt": (lambda: nn.Sqrt(), lambda: XP, {}),
    "Square": (lambda: nn.Square(), lambda: X, {}),
    "Squeeze": (lambda: nn.Squeeze(2), lambda: X134, {}),
    "StrideSlice": (lambda: nn.StrideSlice([(2, 1, 4, 1)]), lambda: X, {}),
    "Sum": (lambda: nn.Sum(2), lambda: X, {}),
    "Tanh": (lambda: nn.Tanh(), lambda: X, {}),
    "TanhShrink": (lambda: nn.TanhShrink(), lambda: X, {}),
    "TemporalConvolution": (lambda: nn.TemporalConvolution(6, 4, 2),
                            lambda: X3, {}),
    "Threshold": (lambda: nn.Threshold(0.2, -1.0), lambda: X, {}),
    "TimeDistributed": (lambda: nn.TimeDistributed(nn.Linear(6, 4)),
                        lambda: X3, {}),
    "Transpose": (lambda: nn.Transpose([(2, 3)]), lambda: X3, {}),
    "TreeLSTM": (lambda: nn.TreeLSTM(4, 3),
                 lambda: T(_XTREE, _TREE), {"diff": [0]}),
    "Unsqueeze": (lambda: nn.Unsqueeze(2), lambda: X, {}),
    "View": (lambda: nn.View(12), lambda: X234, {}),
    "VolumetricConvolution": (
        lambda: nn.VolumetricConvolution(2, 3, 2, 2, 2), lambda: X5D, {}),
    "VolumetricMaxPooling": (lambda: nn.VolumetricMaxPooling(2, 2, 2),
                             lambda: X5D, {}),
}

# backward deliberately differs from the forward derivative (custom_vjp
# side-band gradients, mirroring the reference modules) — asserted
# against the analytic spec in dedicated tests below
SPEC_CHECKED = {
    "GradientReversal": "backward = -lambda * gradOutput by design "
                        "(nn/GradientReversal.scala)",
    "L1Penalty": "backward adds l1 * sign(x) to gradOutput by design "
                 "(nn/L1Penalty.scala)",
}

# no differentiable surface at all
SKIPPED_MODULES = {
    "Fill": "output is a constant fill of a SHAPE input (integer "
            "semantics); no gradient surface",
    "Shape": "emits the input's shape as integers; no gradient surface",
}

ABSTRACT = {"AbstractModule", "TensorModule", "Container", "Cell",
            "Graph"}  # Graph: checked by its dedicated case below


@pytest.mark.parametrize("name", sorted(MODULE_CASES))
def test_module_gradient_values(name):
    make, inp, kw = MODULE_CASES[name]
    if make is None:
        pytest.skip("dedicated test below")
    check_module(make(), inp(), **kw)


def test_graph_gradient_values():
    inp = nn.Input()
    h = nn.Linear(6, 6)(inp)
    h = nn.Tanh()(h)
    add = nn.CAddTable()(h, inp)
    out = nn.ReLU()(add)
    check_module(nn.Graph([inp], [out]), X)


def test_gradient_reversal_matches_spec():
    m = nn.GradientReversal(0.7)
    go = jnp.asarray(R.randn(3, 6).astype(np.float32))
    gi = m.backward(jnp.asarray(X), go)
    np.testing.assert_allclose(np.asarray(gi), -0.7 * np.asarray(go),
                               atol=1e-6)


def test_l1penalty_matches_spec():
    m = nn.L1Penalty(0.3)
    m.training()
    x = jnp.asarray(X)
    go = jnp.asarray(R.randn(3, 6).astype(np.float32))
    m.forward(x)
    gi = m.backward(x, go)
    np.testing.assert_allclose(
        np.asarray(gi), np.asarray(go) + 0.3 * np.sign(np.asarray(X)),
        atol=1e-6)


# --------------------------------------------------------------------------
# criterions
# --------------------------------------------------------------------------
_LOGP = np.log(np.abs(R.rand(3, 5)).astype(np.float32)
               / np.abs(R.rand(3, 5) + 1).astype(np.float32).sum())
_LOGITS = R.randn(3, 5).astype(np.float32)
_LABELS = np.array([2., 5., 1.], np.float32)
_PROBS = (R.rand(3, 5).astype(np.float32) * 0.8 + 0.1)
_BIN = (R.rand(3, 5) > 0.5).astype(np.float32)
_PM1 = np.array([1., -1., 1.], np.float32)

CRITERION_CASES = {
    "AbsCriterion": (lambda: nn.AbsCriterion(), lambda: (X, X2), {}),
    "BCECriterion": (lambda: nn.BCECriterion(),
                     lambda: (_PROBS, _BIN), {}),
    "ClassNLLCriterion": (lambda: nn.ClassNLLCriterion(),
                          lambda: (_LOGP, _LABELS), {}),
    "ClassSimplexCriterion": (lambda: nn.ClassSimplexCriterion(5),
                              lambda: (_LOGITS, _LABELS), {}),
    "CosineDistanceCriterion": (lambda: nn.CosineDistanceCriterion(),
                                lambda: (X, X2), {}),
    "CosineEmbeddingCriterion": (
        lambda: nn.CosineEmbeddingCriterion(0.2),
        lambda: (T(X, X2), _PM1), {}),
    "CrossEntropyCriterion": (lambda: nn.CrossEntropyCriterion(),
                              lambda: (_LOGITS, _LABELS), {}),
    "DiceCoefficientCriterion": (lambda: nn.DiceCoefficientCriterion(),
                                 lambda: (_PROBS, _BIN), {}),
    "DistKLDivCriterion": (lambda: nn.DistKLDivCriterion(),
                           lambda: (_LOGP, _PROBS), {}),
    "HingeEmbeddingCriterion": (
        lambda: nn.HingeEmbeddingCriterion(2.0),
        lambda: (np.abs(X[:, 0]) + 0.3, _PM1), {}),
    "L1Cost": (lambda: nn.L1Cost(), lambda: (XP, XP), {}),
    "L1HingeEmbeddingCriterion": (
        lambda: nn.L1HingeEmbeddingCriterion(5.0),
        lambda: (T(X[0], X2[0]), np.float32(-1.0)), {}),
    "MSECriterion": (lambda: nn.MSECriterion(), lambda: (X, X2), {}),
    "MarginCriterion": (lambda: nn.MarginCriterion(),
                        lambda: (X[:, 0] * 0.4, _PM1), {}),
    "MarginRankingCriterion": (
        lambda: nn.MarginRankingCriterion(0.7),
        lambda: (T(X[:, 0], X2[:, 0]), _PM1), {}),
    "MultiCriterion": (
        lambda: nn.MultiCriterion().add(nn.MSECriterion(), 0.5)
        .add(nn.AbsCriterion(), 2.0),
        lambda: (X, X2), {}),
    "MultiLabelMarginCriterion": (
        lambda: nn.MultiLabelMarginCriterion(),
        lambda: (_LOGITS, np.array([[2, 4, 0, 0, 0], [1, 0, 0, 0, 0],
                                    [3, 5, 1, 0, 0]], np.float32)), {}),
    "MultiLabelSoftMarginCriterion": (
        lambda: nn.MultiLabelSoftMarginCriterion(),
        lambda: (_LOGITS, _BIN), {}),
    "MultiMarginCriterion": (lambda: nn.MultiMarginCriterion(),
                             lambda: (_LOGITS, _LABELS), {}),
    "ParallelCriterion": (
        lambda: nn.ParallelCriterion().add(nn.MSECriterion(), 0.5)
        .add(nn.ClassNLLCriterion(), 1.0),
        lambda: (T(X, _LOGP), T(X2, _LABELS)), {}),
    "SmoothL1Criterion": (lambda: nn.SmoothL1Criterion(),
                          lambda: (X, X2), {}),
    "SmoothL1CriterionWithWeights": (
        lambda: nn.SmoothL1CriterionWithWeights(sigma=1.0, num=3),
        lambda: (X, T(X2, np.ones_like(X), np.ones_like(X))), {}),
    "SoftMarginCriterion": (
        lambda: nn.SoftMarginCriterion(),
        lambda: (X, (2 * (R.rand(3, 6) > 0.5) - 1).astype(np.float32)),
        {}),
    "SoftmaxWithCriterion": (
        lambda: nn.SoftmaxWithCriterion(),
        lambda: (R.randn(2, 5, 3, 3).astype(np.float32),
                 R.randint(1, 6, (2, 1, 3, 3)).astype(np.float32)), {}),
    "TimeDistributedCriterion": (
        lambda: nn.TimeDistributedCriterion(nn.MSECriterion(), True),
        lambda: (X3, R.randn(2, 5, 6).astype(np.float32)), {}),
}


@pytest.mark.parametrize("name", sorted(CRITERION_CASES))
def test_criterion_gradient_values(name):
    make, io, kw = CRITERION_CASES[name]
    x, t = io()
    check_criterion(make(), x, t, **kw)


# --------------------------------------------------------------------------
# coverage meta-test: the registry is value-checked, not spot-checked
# --------------------------------------------------------------------------

def _concrete(base, abstract):
    import inspect
    out = []
    for n in dir(nn):
        c = getattr(nn, n)
        if (inspect.isclass(c) and issubclass(c, base)
                and n not in abstract):
            out.append(n)
    return out


def test_registry_gradient_coverage_at_least_90pct():
    from bigdl_tpu.nn.criterion import AbstractCriterion
    from bigdl_tpu.nn.module import AbstractModule

    mods = [n for n in _concrete(AbstractModule, ABSTRACT | {"Input"})
            if not issubclass(getattr(nn, n), AbstractCriterion)]
    mods.append("Graph")
    covered = set(MODULE_CASES) | set(SPEC_CHECKED)
    unaccounted = set(mods) - covered - set(SKIPPED_MODULES)
    assert not unaccounted, f"modules with no gradient case: {unaccounted}"
    assert len(covered & set(mods)) / len(mods) >= 0.90

    crits = _concrete(AbstractCriterion, {"AbstractCriterion"})
    missing = set(crits) - set(CRITERION_CASES)
    assert not missing, f"criterions with no gradient case: {missing}"
