"""MoEFFN (parallel/moe.py): Switch-style top-1 routing with static
capacity, dense dispatch vs a per-token oracle, expert-parallel
all_to_all path pinned by exact equivalence with the dense path, and
the spmd train step's expert gradient-reduction rule pinned against a
single-device twin.  Beyond reference parity (SURVEY §2.2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from bigdl_tpu import nn
from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.optim import SGD
from bigdl_tpu.parallel.moe import MoEFFN
from bigdl_tpu.utils.rng import RNG

D, H, E = 8, 16, 4


def _moe(axis_name=None, capacity_factor=8.0, n_experts=E):
    RNG().set_seed(3)
    return MoEFFN(D, H, n_experts, capacity_factor=capacity_factor,
                  axis_name=axis_name)


def _tokens(b, t, seed=0):
    return np.random.RandomState(seed).randn(b, t, D).astype(np.float32)


def test_dense_matches_per_token_oracle():
    """Generous capacity: every token goes through exactly its argmax
    expert scaled by the softmax gate."""
    moe = _moe()
    p = moe.param_tree()
    x = _tokens(2, 6)
    out, _ = moe.apply_fn(p, moe.buffer_tree(), jnp.asarray(x), False,
                          None)
    x2d = x.reshape(-1, D)
    logits = x2d @ np.asarray(p["router_w"]).T + np.asarray(p["router_b"])
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.empty_like(x2d)
    for n in range(x2d.shape[0]):
        e = int(np.argmax(probs[n]))
        h = x2d[n] @ np.asarray(p["wi"])[e] + np.asarray(p["bi"])[e]
        h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
        y = h @ np.asarray(p["wo"])[e] + np.asarray(p["bo"])[e]
        want[n] = probs[n, e] * y
    np.testing.assert_allclose(np.asarray(out).reshape(-1, D), want,
                               atol=2e-5)


def test_top2_matches_per_token_oracle():
    """GShard top-2 with generous capacity: every token is the
    renormalized-gate mixture of its two highest-probability experts."""
    RNG().set_seed(3)
    moe = MoEFFN(D, H, E, capacity_factor=8.0, top_k=2)
    p = moe.param_tree()
    x = _tokens(2, 6, seed=9)
    out, _ = moe.apply_fn(p, moe.buffer_tree(), jnp.asarray(x), False,
                          None)
    x2d = x.reshape(-1, D)
    logits = x2d @ np.asarray(p["router_w"]).T + np.asarray(p["router_b"])
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.empty_like(x2d)
    for n in range(x2d.shape[0]):
        top2 = np.argsort(-probs[n])[:2]
        g = probs[n, top2] / probs[n, top2].sum()
        y = np.zeros(D, np.float32)
        for gi, e in zip(g, top2):
            h = x2d[n] @ np.asarray(p["wi"])[e] + np.asarray(p["bi"])[e]
            h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
            y += gi * (h @ np.asarray(p["wo"])[e] + np.asarray(p["bo"])[e])
        want[n] = y
    np.testing.assert_allclose(np.asarray(out).reshape(-1, D), want,
                               atol=2e-5)


def test_top2_expert_parallel_matches_dense():
    """The all_to_all dispatch computes the same top-2 function as the
    dense path — the [E, C] buffer shapes are routing-order-independent
    so the existing wire needs no change."""
    from jax import shard_map

    from bigdl_tpu.parallel.spmd import param_specs

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    RNG().set_seed(3)
    moe = MoEFFN(D, H, E, capacity_factor=8.0, top_k=2,
                 axis_name="data")
    RNG().set_seed(3)
    dense = MoEFFN(D, H, E, capacity_factor=8.0, top_k=2)
    p = moe.param_tree()
    x = _tokens(8, 4, seed=2)
    want, _ = dense.apply_fn(p, dense.buffer_tree(), jnp.asarray(x),
                             False, None)
    pspecs = param_specs(moe, "model")

    def local(pp, xx):
        out, _ = moe.apply_fn(pp, moe.buffer_tree(), xx, False, None)
        return out

    fwd = jax.jit(shard_map(local, mesh=mesh,
                            in_specs=(pspecs, P("data")),
                            out_specs=P("data"), check_vma=False))
    np.testing.assert_allclose(np.asarray(fwd(p, jnp.asarray(x))),
                               np.asarray(want), atol=2e-5)


def test_top2_capacity_drops_second_choices_first():
    """Choice-ordered capacity (GShard): with identical tokens and
    C=1, the expert's single slot goes to the FIRST token's first
    choice; every second choice queues behind all first choices and
    drops.  Output: token 0 keeps only its top-1 contribution (with
    top-2-renormalized gate), later tokens zero."""
    RNG().set_seed(3)
    moe = MoEFFN(D, H, 2, capacity_factor=1e-6, top_k=2)  # C = 1
    p = moe.param_tree()
    x = np.tile(_tokens(1, 1, seed=4), (1, 4, 1))  # 4 identical tokens
    out, _ = moe.apply_fn(p, moe.buffer_tree(), jnp.asarray(x), False,
                          None)
    out = np.asarray(out)[0]
    # token 0: first choice kept; its second choice queues behind the
    # OTHER tokens' first choices for that expert... with E=2 and all
    # tokens identical, expert A gets all 4 first choices (slot -> tok
    # 0), expert B all 4 second choices (slot -> tok 0's second choice)
    x2d = x.reshape(-1, D)
    logits = x2d[0] @ np.asarray(p["router_w"]).T + np.asarray(
        p["router_b"])
    probs = np.exp(logits - logits.max())
    probs /= probs.sum()
    top2 = np.argsort(-probs)[:2]
    g = probs[top2] / probs[top2].sum()
    want0 = np.zeros(D, np.float32)
    for gi, e in zip(g, top2):
        h = x2d[0] @ np.asarray(p["wi"])[e] + np.asarray(p["bi"])[e]
        h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
        want0 += gi * (h @ np.asarray(p["wo"])[e] + np.asarray(
            p["bo"])[e])
    np.testing.assert_allclose(out[0], want0, atol=2e-5)
    np.testing.assert_allclose(out[1:], 0.0, atol=1e-7)


def test_top2_lm_greedy_decode_matches_dense_forward():
    """A top-2 MoE TransformerLM decodes (capacity-free top-2 gather)
    exactly like its own training forward under loose capacity."""
    from bigdl_tpu.models.generate import make_generate

    RNG().set_seed(13)
    lm = TransformerLM(17, embed_dim=D, num_heads=2, mlp_dim=H,
                       num_layers=2, max_len=16, moe_experts=E,
                       moe_capacity_factor=8.0, moe_top_k=2)
    gen = make_generate(lm)
    prompt = np.random.RandomState(5).randint(
        1, 18, (2, 4)).astype(np.int32)
    ids = np.asarray(gen(lm.param_tree(), prompt, max_new=6))
    out, _ = lm.apply_fn(lm.param_tree(), lm.buffer_tree(),
                         jnp.asarray(ids), False, None)
    pred = 1 + np.argmax(np.asarray(out), axis=-1)
    np.testing.assert_array_equal(ids[:, 4:], pred[:, 3:-1])


def test_capacity_drops_pass_through_as_zero():
    """capacity_factor small enough that only the first token per expert
    fits: later same-expert tokens contribute exactly zero (the block's
    residual carries them)."""
    moe = _moe(capacity_factor=1e-6, n_experts=2)  # C = 1
    p = moe.param_tree()
    x = np.tile(_tokens(1, 1, seed=4), (1, 5, 1))  # 5 identical tokens
    out, _ = moe.apply_fn(p, moe.buffer_tree(), jnp.asarray(x), False,
                          None)
    out = np.asarray(out)[0]
    assert np.abs(out[0]).max() > 1e-4          # first token served
    np.testing.assert_allclose(out[1:], 0.0, atol=1e-7)  # rest dropped


def test_expert_parallel_matches_dense():
    """The all_to_all dispatch over 4 shards computes the same function
    as the dense path (capacity generous on both sides)."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    moe = _moe(axis_name="data", capacity_factor=4.0)
    dense = _moe(axis_name=None, capacity_factor=4.0)
    p = moe.param_tree()
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(dense.param_tree())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    x = _tokens(8, 4, seed=1)
    want, _ = dense.apply_fn(p, dense.buffer_tree(), jnp.asarray(x),
                             False, None)

    from bigdl_tpu.parallel.spmd import param_specs

    pspecs = param_specs(moe, "model")
    from jax import shard_map

    def local(pp, xx):
        out, _ = moe.apply_fn(pp, moe.buffer_tree(), xx, False, None)
        return out

    fwd = jax.jit(shard_map(local, mesh=mesh,
                            in_specs=(pspecs, P("data")),
                            out_specs=P("data"), check_vma=False))
    got = fwd(p, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


def _lm(moe_axis, seed=11):
    RNG().set_seed(seed)
    return TransformerLM(17, embed_dim=D, num_heads=2, mlp_dim=H,
                         num_layers=2, max_len=6, moe_experts=E,
                         moe_axis=moe_axis, moe_capacity_factor=4.0)


def _lm_batch(n, seed=0):
    r = np.random.RandomState(seed)
    return (r.randint(1, 18, (n, 6)).astype(np.int32),
            r.randint(1, 18, (n, 6)).astype(np.float32))


@pytest.mark.slow  # ~12s twin; the masked variant below pins the
# same expert grad-reduction rule in the budgeted run
def test_spmd_train_step_expert_grads_match_dense_twin():
    """spmd.make_train_step over a data mesh with expert-sharded MoE
    stacks: loss and updated params (router AND expert weights) must
    match a single-device dense twin — pins the expert grad-reduction
    rule (all_to_all transpose sum, /n_data, no pmean)."""
    from bigdl_tpu.parallel.spmd import make_train_step

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
    lr = 0.2

    dense = _lm(None)
    ep = _lm("data")
    params0 = dense.param_tree()
    for a, b in zip(jax.tree_util.tree_leaves(params0),
                    jax.tree_util.tree_leaves(ep.param_tree())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    x, y = _lm_batch(8, seed=2)

    def dense_step(model):
        p = model.param_tree()
        sgd = SGD(learning_rate=lr)
        slots = sgd.init_state(p)

        def loss_fn(pp):
            out, _ = model.apply_fn(pp, model.buffer_tree(),
                                    jnp.asarray(x), True, None)
            return crit._loss(out, jnp.asarray(y))

        loss, grads = jax.value_and_grad(loss_fn)(p)
        p, _ = sgd.step(grads, p, slots, lr)
        return float(loss), p

    loss_ref, params_ref = dense_step(dense)

    sgd = SGD(learning_rate=lr)
    step = make_train_step(ep, crit, sgd, mesh)
    params = ep.param_tree()
    slots = sgd.init_state(params)
    loss, params, slots, _ = step(params, slots, ep.buffer_tree(), lr,
                                  x, y)
    assert abs(float(loss) - loss_ref) < 2e-5
    flat = dict(jax.tree_util.tree_leaves_with_path(params_ref))
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            jax.device_get(params)):
        np.testing.assert_allclose(np.asarray(leaf),
                                   np.asarray(flat[path]), atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


def test_spmd_masked_expert_step_matches_dense_twin():
    """Trailing partial batch on the EP mesh: pad-and-mask trains
    exactly the real records (expert grads take the no-correction
    masked rule)."""
    from bigdl_tpu.parallel.spmd import make_train_step

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
    lr = 0.2
    x, y = _lm_batch(5, seed=7)

    dense = _lm(None)

    def loss_fn(pp):
        out, _ = dense.apply_fn(pp, dense.buffer_tree(), jnp.asarray(x),
                                True, None)
        return crit._loss(out, jnp.asarray(y))

    p0 = dense.param_tree()
    loss_ref, grads_ref = jax.value_and_grad(loss_fn)(p0)
    sgd = SGD(learning_rate=lr)
    params_ref, _ = sgd.step(grads_ref, p0, sgd.init_state(p0), lr)

    ep = _lm("data")
    sgd2 = SGD(learning_rate=lr)
    step = make_train_step(ep, crit, sgd2, mesh)
    pad = 8 - 5
    xp = np.concatenate([x, np.ones((pad, 6), x.dtype)])
    yp = np.concatenate([y, np.ones((pad, 6), y.dtype)])
    w = np.array([1.0] * 5 + [0.0] * pad, np.float32)
    params = ep.param_tree()
    slots = sgd2.init_state(params)
    loss, params, slots, _ = step(params, slots, ep.buffer_tree(), lr,
                                  xp, yp, w=w, total_w=5.0)
    assert abs(float(loss) - float(loss_ref)) < 2e-5
    flat = dict(jax.tree_util.tree_leaves_with_path(params_ref))
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            jax.device_get(params)):
        np.testing.assert_allclose(np.asarray(leaf),
                                   np.asarray(flat[path]), atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


def test_distri_optimizer_routes_ep_model():
    """The product driver sends a bound-MoE model through the SPMD path
    even on a pure-data mesh (the AllReduceParameter plane cannot hold
    sharded expert stacks)."""
    from bigdl_tpu.dataset.dataset import array
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.optim import max_iteration
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    lm = _lm("data")
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
    batches = [MiniBatch(*_lm_batch(8, seed=s)) for s in (0, 1)]
    opt = DistriOptimizer(lm, array(batches), crit, mesh=mesh)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_iteration(2))
    opt.optimize()
    assert np.isfinite(opt.optim_method.state["loss"])


def test_aux_loss_value_matches_hand_formula():
    """Switch aux = E * sum_e f_e * P_e over the pre-capacity top-1
    assignment, written to the aux_loss buffer."""
    RNG().set_seed(3)
    moe = MoEFFN(D, H, E, capacity_factor=8.0, aux_loss_coef=0.5)
    p = moe.param_tree()
    x = _tokens(2, 6, seed=8)
    _, nb = moe.apply_fn(p, moe.buffer_tree(), jnp.asarray(x), True, None)
    x2d = x.reshape(-1, D)
    logits = x2d @ np.asarray(p["router_w"]).T + np.asarray(p["router_b"])
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    onehot = np.eye(E)[probs.argmax(-1)]
    want = E * float(np.sum(onehot.mean(0) * probs.mean(0)))
    np.testing.assert_allclose(float(nb["aux_loss"]), want, atol=1e-5)


def test_aux_loss_enters_the_spmd_step_loss():
    """With identical params/inputs, the step loss with coef c exceeds
    the coef-0 loss by exactly c * sum-of-layer-aux (and the router
    receives a different gradient)."""
    from bigdl_tpu.parallel.moe import aux_loss_term, collect_aux_paths
    from bigdl_tpu.parallel.spmd import make_train_step

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
    x, y = _lm_batch(4, seed=3)

    def run(coef):
        RNG().set_seed(11)
        lm = TransformerLM(17, embed_dim=D, num_heads=2, mlp_dim=H,
                           num_layers=2, max_len=6, moe_experts=E,
                           moe_axis="data", moe_capacity_factor=4.0,
                           moe_aux_coef=coef)
        sgd = SGD(learning_rate=0.1)
        step = make_train_step(lm, crit, sgd, mesh)
        params = lm.param_tree()
        loss, new_p, _, nb = step(params, sgd.init_state(params),
                                  lm.buffer_tree(), 0.1, x, y)
        return lm, float(loss), jax.device_get(new_p), nb

    lm0, loss0, p0, _ = run(0.0)
    lm1, loss1, p1, nb1 = run(0.5)
    aux_total = float(aux_loss_term(jax.device_get(nb1),
                                    list(collect_aux_paths(lm1)))) / 0.5
    assert aux_total > 0
    np.testing.assert_allclose(loss1 - loss0, 0.5 * aux_total, atol=1e-5)
    # the balance term reshapes the router update
    r0 = np.asarray(p0["1"]["3"]["router_w"])
    r1 = np.asarray(p1["1"]["3"]["router_w"])
    assert np.abs(r0 - r1).max() > 1e-7


@pytest.mark.slow  # ~7s; the aux value/step wiring stays budgeted
# via test_aux_loss_value_matches_hand_formula +
# test_aux_loss_enters_the_spmd_step_loss
def test_aux_loss_ep_matches_dense_twin_multi_shard():
    """The EP aux term uses GLOBAL routing statistics (pmean'd over the
    axis), so loss AND params after one step match the dense twin
    exactly on a 4-shard mesh with aux enabled."""
    from bigdl_tpu.parallel.moe import aux_loss_term, collect_aux_paths
    from bigdl_tpu.parallel.spmd import make_train_step

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
    lr, coef = 0.2, 0.3
    x, y = _lm_batch(8, seed=6)

    def build(axis):
        RNG().set_seed(13)
        return TransformerLM(17, embed_dim=D, num_heads=2, mlp_dim=H,
                             num_layers=2, max_len=6, moe_experts=E,
                             moe_axis=axis, moe_capacity_factor=4.0,
                             moe_aux_coef=coef)

    dense = build(None)

    def loss_fn(pp):
        out, nb = dense.apply_fn(pp, dense.buffer_tree(), jnp.asarray(x),
                                 True, None)
        return (crit._loss(out, jnp.asarray(y))
                + aux_loss_term(nb, list(collect_aux_paths(dense))))

    p0 = dense.param_tree()
    loss_ref, grads_ref = jax.value_and_grad(loss_fn)(p0)
    sgd = SGD(learning_rate=lr)
    params_ref, _ = sgd.step(grads_ref, p0, sgd.init_state(p0), lr)

    ep = build("data")
    sgd2 = SGD(learning_rate=lr)
    step = make_train_step(ep, crit, sgd2, mesh)
    params = ep.param_tree()
    loss, params, _, _ = step(params, sgd2.init_state(params),
                              ep.buffer_tree(), lr, x, y)
    assert abs(float(loss) - float(loss_ref)) < 2e-5
    flat = dict(jax.tree_util.tree_leaves_with_path(params_ref))
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            jax.device_get(params)):
        np.testing.assert_allclose(np.asarray(leaf),
                                   np.asarray(flat[path]), atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


def test_aux_loss_local_optimizer_smoke():
    from bigdl_tpu.dataset.dataset import array
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.optim import max_iteration
    from bigdl_tpu.optim.optimizer import LocalOptimizer

    RNG().set_seed(5)
    lm = TransformerLM(17, embed_dim=D, num_heads=2, mlp_dim=H,
                       num_layers=2, max_len=6, moe_experts=E,
                       moe_aux_coef=0.01)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
    opt = LocalOptimizer(lm, array([MiniBatch(*_lm_batch(8, seed=s))
                                    for s in (0, 1)]), crit)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_iteration(2))
    opt.optimize()
    assert np.isfinite(opt.optim_method.state["loss"])


def _long_lm(moe_axis, seq_strategy="dense", seed=17, aux=0.3):
    RNG().set_seed(seed)
    return TransformerLM(17, embed_dim=D, num_heads=2, mlp_dim=H,
                         num_layers=2, max_len=8, moe_experts=E,
                         moe_axis=moe_axis, moe_capacity_factor=8.0,
                         moe_aux_coef=aux, seq_strategy=seq_strategy)


@pytest.mark.slow  # ~9s twin; the masked variant below pins the
# same EP x SP rule plus the tail-batch mask in the budgeted run
def test_moe_seq_parallel_matches_dense_twin():
    """EP x SP (long-context MoE): ring attention over the seq axis +
    expert dispatch over the data axis; loss and every updated param
    must match the dense single-device twin (incl. the aux term, whose
    statistics pmean over BOTH axes)."""
    from bigdl_tpu.parallel.moe import aux_loss_term, collect_aux_paths
    from bigdl_tpu.parallel.spmd import make_train_step

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "seq"))
    # sizeAverage=True: the seq-axis pmean convention needs a time-MEAN
    # criterion (a time-sum would halve per shard)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True)
    lr = 0.2
    r = np.random.RandomState(5)
    x = r.randint(1, 18, (4, 8)).astype(np.int32)
    y = r.randint(1, 18, (4, 8)).astype(np.float32)

    dense = _long_lm(None)

    def loss_fn(pp):
        out, nb = dense.apply_fn(pp, dense.buffer_tree(), jnp.asarray(x),
                                 True, None)
        return (crit._loss(out, jnp.asarray(y))
                + aux_loss_term(nb, list(collect_aux_paths(dense))))

    p0 = dense.param_tree()
    loss_ref, grads_ref = jax.value_and_grad(loss_fn)(p0)
    sgd = SGD(learning_rate=lr)
    params_ref, _ = sgd.step(grads_ref, p0, sgd.init_state(p0), lr)

    ep = _long_lm("data", seq_strategy="ring")
    for a, b in zip(jax.tree_util.tree_leaves(p0),
                    jax.tree_util.tree_leaves(ep.param_tree())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    sgd2 = SGD(learning_rate=lr)
    step = make_train_step(ep, crit, sgd2, mesh)
    params = ep.param_tree()
    loss, params, _, _ = step(params, sgd2.init_state(params),
                              ep.buffer_tree(), lr, x, y)
    assert abs(float(loss) - float(loss_ref)) < 2e-5
    flat = dict(jax.tree_util.tree_leaves_with_path(params_ref))
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            jax.device_get(params)):
        np.testing.assert_allclose(np.asarray(leaf),
                                   np.asarray(flat[path]), atol=3e-5,
                                   err_msg=jax.tree_util.keystr(path))


def test_moe_seq_parallel_masked_matches_dense_twin():
    """EP x SP with a trailing partial batch: pad-and-mask trains
    exactly the real records (expert grads take pmean(seq), no data
    correction)."""
    from bigdl_tpu.parallel.moe import aux_loss_term, collect_aux_paths
    from bigdl_tpu.parallel.spmd import make_train_step

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "seq"))
    # sizeAverage=True: the seq-axis pmean convention needs a time-MEAN
    # criterion (a time-sum would halve per shard)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True)
    lr = 0.2
    r = np.random.RandomState(6)
    x = r.randint(1, 18, (3, 8)).astype(np.int32)
    y = r.randint(1, 18, (3, 8)).astype(np.float32)

    dense = _long_lm(None, aux=0.0)

    def loss_fn(pp):
        out, _ = dense.apply_fn(pp, dense.buffer_tree(), jnp.asarray(x),
                                True, None)
        return crit._loss(out, jnp.asarray(y))

    p0 = dense.param_tree()
    loss_ref, grads_ref = jax.value_and_grad(loss_fn)(p0)
    sgd = SGD(learning_rate=lr)
    params_ref, _ = sgd.step(grads_ref, p0, sgd.init_state(p0), lr)

    ep = _long_lm("data", seq_strategy="ring", aux=0.0)
    sgd2 = SGD(learning_rate=lr)
    step = make_train_step(ep, crit, sgd2, mesh)
    pad = 4 - 3
    xp = np.concatenate([x, np.ones((pad, 8), x.dtype)])
    yp = np.concatenate([y, np.ones((pad, 8), y.dtype)])
    w = np.array([1.0] * 3 + [0.0] * pad, np.float32)
    params = ep.param_tree()
    loss, params, _, _ = step(params, sgd2.init_state(params),
                              ep.buffer_tree(), lr, xp, yp, w=w,
                              total_w=3.0)
    assert abs(float(loss) - float(loss_ref)) < 2e-5
    flat = dict(jax.tree_util.tree_leaves_with_path(params_ref))
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            jax.device_get(params)):
        np.testing.assert_allclose(np.asarray(leaf),
                                   np.asarray(flat[path]), atol=3e-5,
                                   err_msg=jax.tree_util.keystr(path))


def test_predictor_handles_ep_model():
    """The standalone sharded Predictor shards the expert stacks over
    the data axis (a replicated spec would feed full [E,...] weights to
    the bound all_to_all); outputs match the dense local twin."""
    from bigdl_tpu.dataset.dataset import array
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.optim.predictor import LocalPredictor, Predictor

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    ep = _lm("data")
    dense = _lm(None)
    x, _ = _lm_batch(8, seed=4)
    samples = [Sample(r, np.float32(1)) for r in x]
    got = Predictor(ep, mesh).predict(array(samples), batch_size=4)
    want = LocalPredictor(dense).predict(array(samples), batch_size=4)
    assert len(got) == len(want) == 8
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5)


def test_block_rejects_moe_plus_model_axis():
    with pytest.raises(ValueError, match="model_axis=None"):
        TransformerLM(17, embed_dim=D, num_heads=2, mlp_dim=H,
                      num_layers=2, max_len=6, moe_experts=4,
                      model_axis="model")


def test_moe_guards():
    from bigdl_tpu.parallel.spmd import make_train_step

    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
    # bound axis missing from the mesh
    mesh1 = Mesh(np.array(jax.devices()[:4]), ("data",))
    with pytest.raises(ValueError, match="does not have"):
        make_train_step(_lm("expert"), crit, SGD(), mesh1)
    # MoE on a seq mesh without seq-aware routing stats rejected
    mesh2 = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                 ("data", "seq"))
    with pytest.raises(ValueError, match="stat_axes"):
        make_train_step(_lm("data"), crit, SGD(), mesh2)
    # experts must divide the axis
    mesh3 = Mesh(np.array(jax.devices()[:8]), ("data",))
    RNG().set_seed(1)
    lm3 = TransformerLM(17, embed_dim=D, num_heads=2, mlp_dim=H,
                        num_layers=2, max_len=6, moe_experts=6,
                        moe_axis="data")
    with pytest.raises(ValueError, match="not divisible"):
        make_train_step(lm3, crit, SGD(), mesh3)
    # pipeline + bound MoE rejected
    from bigdl_tpu.parallel.pipeline import make_pipeline_train_step

    mesh4 = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                 ("data", "pipe"))
    with pytest.raises(ValueError, match="expert"):
        make_pipeline_train_step(_lm("data"), crit, SGD(), mesh4,
                                 n_microbatch=2)
