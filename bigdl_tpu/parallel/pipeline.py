"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

The fourth parallel axis of the rebuild (alongside ``data``/``seq``/
``model`` in parallel/spmd.py).  The reference has no pipeline story —
its only strategy is synchronous data parallelism (SURVEY §2.2) — so
this is a forward-looking extension shaped by how the hardware wants
it: a repeated-block region — the transformer blocks of a
:class:`~bigdl_tpu.models.transformer.TransformerLM`, or the maximal
identical-block run of ANY :class:`~bigdl_tpu.nn.Sequential` (wrap the
repeated unit in its own ``Sequential``) — is stacked into one
leading-``L`` pytree,
sharded over the ``pipe`` axis (each stage owns ``L/S`` layers AND
their optimizer state), and the microbatched GPipe schedule is a
``lax.scan`` over ``M + S - 1`` ticks whose inter-stage hop is a single
``ppermute`` riding the ICI.  JAX AD differentiates straight through
the scan + ppermute, so the backward pipeline (reverse schedule,
reverse permutation) is derived, not hand-written.

Layout of one tick (S stages, M microbatches):

    stage 0 feeds microbatch ``t`` into the ring; every stage applies
    its local layer stack (an inner ``lax.scan`` over ``L/S`` blocks);
    stage S-1 banks finished microbatch ``t-(S-1)``; ``ppermute``
    shifts activations one stage right.  Bubble fraction is the
    textbook ``(S-1)/(M+S-1)``.

Embedding/positions and the LN+head tail run replicated on every pipe
shard (their FLOPs are negligible next to the block stack; replication
buys zero extra collectives).  Gradient reduction follows the same
convention as spmd.py's model axis: pipe-sharded leaves see the
``S×`` cotangent amplification of the replicated-loss psum and are
divided by ``S``; replicated leaves are pmean'd over (data, pipe).

Composes with the ``data`` axis (batch sharding) and — via
``model_axis`` — with Megatron tensor parallelism inside each stage:
the stacked Column/Row weights shard over BOTH pipe (layer dim) and
model (feature dim), giving 3-D data × pipe × model parallelism.  A
``seq`` axis inside the pipelined region is out of scope (rejected
loudly) — use spmd.make_train_step for sequence-parallel meshes.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


# per-iteration bookkeeping/timing/state attributes that legitimately
# differ between otherwise identical modules (or flip when a module has
# run eagerly) — never part of the identity
_SIG_SKIP = frozenset(("name", "is_training", "forward_time",
                       "backward_time", "output", "grad_input"))
# attributes whose content is captured elsewhere in the block signature:
# children recurse via ``kids``; param/grad/buffer arrays are compared
# by treedef + leaf shape in _block_run
_SIG_STRUCTURAL = frozenset(("modules", "params", "grads", "buffers"))


def _sig_marker(v):
    """Conservative signature entry for a non-simple attribute value.

    Named module-level callables (functions, classes, bound activations
    like ``jnp.tanh``) compare by qualified name — two blocks built with
    the same default share it.  Everything else (closures, partials,
    arrays, dicts, arbitrary objects) compares by OBJECT IDENTITY:
    separately-constructed values refuse to match, so config-divergent
    blocks can never silently stack — the scan falls back to per-block
    execution instead of applying the first block's config to all."""
    if callable(v):
        mod = getattr(v, "__module__", None)
        qn = getattr(v, "__qualname__", None)
        if mod is not None and qn is not None and "<locals>" not in qn:
            return ("callable", mod, qn)
    return (type(v).__name__, id(v))


def _module_sig(m):
    """Recursive identity of a module for run detection: class name,
    every simple (int/float/bool/str/tuple) PUBLIC attribute, and the
    children's signatures.  The param treedef + leaf shapes alone are
    BLIND to non-parameter config — two Dropout(0.1)/Dropout(0.5)
    blocks, or two convs whose stride differs but whose weight shapes
    coincide, are structurally identical yet compute different
    functions, and the stacked stage scan would silently apply the
    first block's config to every layer."""
    cfg = []
    for k, v in sorted(vars(m).items()):
        if k.startswith("_") or k in _SIG_SKIP or k in _SIG_STRUCTURAL:
            continue
        if isinstance(v, (int, float, bool, str, bytes, type(None))):
            cfg.append((k, v))
        elif (isinstance(v, (tuple, list)) and
              all(isinstance(e, (int, float, bool, str, type(None)))
                  for e in v)):
            cfg.append((k, tuple(v)))
        else:
            # non-simple config (callable, array, dict, object): a
            # conservative marker so divergent blocks never stack
            cfg.append((k, _sig_marker(v)))
    kids = tuple(_module_sig(c) for c in getattr(m, "modules", ()))
    return (type(m).__name__, tuple(cfg), kids)


def _block_run(model):
    """Locate the maximal run of identical PARAMETERIZED blocks in
    ``model.modules`` (same param treedef + leaf shapes + recursive
    config signature).  Parameterless runs (e.g. repeated activations)
    are never candidates — there is nothing to shard over the pipe
    axis, and letting them win would shadow an equally long
    parameterized run.  Returns (first_index, count)."""
    sig, has_params = [], []
    for m in model.modules:
        t = m.param_tree()
        leaves, treedef = jax.tree_util.tree_flatten(t)
        sig.append((treedef, tuple(getattr(a, "shape", ()) for a in leaves),
                    _module_sig(m)))
        has_params.append(bool(leaves))
    best = (0, 0)
    i = 0
    while i < len(sig):
        j = i + 1
        while j < len(sig) and sig[j] == sig[i]:
            j += 1
        if has_params[i] and j - i > best[1]:
            best = (i, j - i)
        i = j
    return best


def _is_lm(model):
    from ..models.transformer import TransformerLM

    return isinstance(model, TransformerLM)


def _check_layout(model):
    """Validate the pipelined layout; return (first, count) of the
    pipelined block run.  Shared by pack/unpack and the step builders.

    Two shapes are accepted: a :class:`TransformerLM` ([embed,
    blocks..., ln, head] — the blocks ride the pipe, embed/ln/head
    replicate), or ANY :class:`~bigdl_tpu.nn.Sequential` whose middle is
    a maximal run of structurally identical parameterized blocks (same
    treedef + leaf shapes + class) — head/tail modules around the run
    replicate the same way.  Users pipeline a custom stack by wrapping
    the repeated unit in its own ``Sequential`` so consecutive units
    compare equal."""
    from ..nn.containers import Sequential

    if _is_lm(model):
        first, count = _block_run(model)
        if first != 1 or count != len(model.modules) - 3:
            raise ValueError(
                "TransformerLM blocks do not form one identical run "
                f"(found run at {first} len {count}, expected 1 len "
                f"{len(model.modules) - 3}): either the [embed, "
                "blocks..., ln, head] layout changed, or per-layer "
                "CONFIG diverged (e.g. one block's dropout rate edited "
                "post-construction) — pipelined blocks must be "
                "config-identical because one stacked stage function "
                "runs every layer")
        return first, count
    if not isinstance(model, Sequential):
        raise TypeError(
            "pipeline parallelism supports TransformerLM or a "
            "Sequential whose middle is a run of structurally identical "
            f"blocks (got {type(model).__name__})")
    first, count = _block_run(model)
    if count < 2:
        raise ValueError(
            "no pipelined region: the Sequential needs a run of >= 2 "
            "structurally identical parameterized blocks (wrap the "
            "repeated unit in its own Sequential so consecutive units "
            "compare equal)")
    return first, count


def _check_model(model, n_pipe, model_axis=None):
    from .tensor_parallel import ColumnParallelLinear, RowParallelLinear

    first, count = _check_layout(model)
    if getattr(model, "seq_strategy", None) in ("ring", "ulysses"):
        raise ValueError(
            "pipeline parallelism composes with data/model axes only; "
            f"seq_strategy {model.seq_strategy!r} needs a bound seq axis "
            "— use parallel.spmd.make_train_step for seq meshes")
    from .moe import MoEFFN

    bound = 0
    for m in model.modules_iter():
        if (isinstance(m, (ColumnParallelLinear, RowParallelLinear))
                and m.axis_name):
            if m.axis_name != model_axis:
                raise ValueError(
                    f"{type(m).__name__} is bound to mesh axis "
                    f"{m.axis_name!r} but the pipeline builder was given "
                    f"model_axis={model_axis!r}; pass model_axis="
                    f"{m.axis_name!r} to compose pipeline with tensor "
                    "parallelism, or build with model_axis=None")
            bound += 1
        if isinstance(m, MoEFFN) and m.axis_name:
            raise ValueError(
                "pipeline parallelism does not compose with expert "
                "parallelism yet: MoEFFN is bound to mesh axis "
                f"{m.axis_name!r} (build with moe_axis=None for dense "
                "MoE inside the pipeline)")
    if model_axis is not None and bound == 0:
        raise ValueError(
            f"pipeline builder was given model_axis={model_axis!r} but "
            "no Column/RowParallelLinear in the model is bound to it — "
            "the >1 model mesh axis would be pure replication (half the "
            f"devices doing redundant work); build the TransformerLM "
            f"with model_axis={model_axis!r}, or use a mesh whose model "
            "axis is 1")
    if count % n_pipe != 0:
        raise ValueError(
            f"num_layers {count} not divisible by pipe-axis size {n_pipe}")
    if jax.tree_util.tree_leaves(model.buffer_tree()):
        raise ValueError(
            "pipelined model must be buffer-free — the pipeline does not "
            "thread the buffer pytree (BatchNorm running stats, or an "
            "MoE aux_loss buffer: pass moe_aux_coef=0 for pipelined MoE)")
    return first, count


def pack_params(model, n_pipe: int, model_axis=None):
    """Model param tree → pipeline tree: the L block subtrees stacked
    into leading-``L`` leaves (sharded P('pipe') over stages), the rest
    verbatim.  TransformerLM keeps its named layout (embed/pos/ln/head
    — checkpoint compatibility); a generic Sequential packs the modules
    around the run as ``pre``/``post`` keyed by absolute module index.
    Inverse: :func:`unpack_params`."""
    first, count = _check_model(model, n_pipe, model_axis)
    t = model.param_tree()
    blocks = [t[str(i)] for i in range(first, first + count)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    if _is_lm(model):
        packed = {"embed": t["0"], "blocks": stacked,
                  "ln": t[str(first + count)],
                  "head": t[str(first + count + 1)]}
        if "pos" in t:  # rope models carry no positional table
            packed["pos"] = t["pos"]
        return packed
    return {"pre": {str(i): t[str(i)] for i in range(first)},
            "blocks": stacked,
            "post": {str(i): t[str(i)]
                     for i in range(first + count, len(model.modules))}}


def unpack_params(packed, model):
    """Write a pipeline param tree back into ``model`` (checkpointing /
    ``get_parameters`` interop).  Validates that the model's block count
    matches the packed stack — JAX's clamping gather would otherwise
    silently duplicate the last layer into any extras."""
    first, count = _check_layout(model)
    stacked_l = jax.tree_util.tree_leaves(packed["blocks"])
    if stacked_l and stacked_l[0].shape[0] != count:
        raise ValueError(
            f"packed tree carries {stacked_l[0].shape[0]} block layers "
            f"but the model has {count}")
    if _is_lm(model):
        tree = {"0": packed["embed"],
                str(first + count): packed["ln"],
                str(first + count + 1): packed["head"]}
        if "pos" in packed:
            tree["pos"] = packed["pos"]
    else:
        tree = dict(packed["pre"])
        tree.update(packed["post"])
    for i in range(count):
        tree[str(first + i)] = jax.tree_util.tree_map(
            lambda a, _i=i: a[_i], packed["blocks"])
    model.set_param_tree(tree)
    return model


def param_specs(packed, pipe_axis: str = "pipe", block=None,
                model_axis=None):
    """PartitionSpec tree for a packed pipeline tree: stacked block
    leaves shard their leading (layer) dim over ``pipe``; with
    ``block``/``model_axis`` given, each leaf's single-block tensor-
    parallel spec (spmd.param_specs) is appended after the pipe dim —
    Column/Row weights shard over BOTH axes.  Everything else
    replicates."""
    if block is not None and model_axis is not None:
        from .spmd import param_specs as _block_specs

        bspec = _block_specs(block, model_axis)
        blocks = jax.tree_util.tree_map(
            lambda s: P(pipe_axis, *s), bspec,
            is_leaf=lambda s: isinstance(s, P))
    else:
        blocks = jax.tree_util.tree_map(lambda _: P(pipe_axis),
                                        packed["blocks"])
    repl = lambda sub: jax.tree_util.tree_map(lambda _: P(), sub)
    if "embed" in packed:
        specs = {"embed": repl(packed["embed"]), "blocks": blocks,
                 "ln": repl(packed["ln"]), "head": repl(packed["head"])}
        if "pos" in packed:
            specs["pos"] = P()
        return specs
    return {"pre": repl(packed["pre"]), "blocks": blocks,
            "post": repl(packed["post"])}


def _make_local_forward(model, first, count, S, M, pipe_axis,
                        compute_dtype, remat):
    """The pipelined local forward shared by the train and eval builders
    (one implementation so their schedules can never diverge —
    spmd.py's ``_cast_fwd`` rule).

    Returns ``local_fwd(packed_master, x, training, rng, upcast) -> out``
    for use INSIDE shard_map: the bf16 cast happens within, so its vjp
    returns f32 master-weight gradients on the train path."""
    from ..optim.optimizer import _cast_floats

    Lp = count // S
    block = model.modules[first]
    block_bufs = block.buffer_tree()
    perm = [(i, i + 1) for i in range(S - 1)]

    def stage_fn(blocks_local, act, rng, training):
        def body(h, xs):
            lp, li = xs
            key = (jax.random.fold_in(rng, li)
                   if rng is not None else None)
            h, _ = block.apply_fn(lp, block_bufs, h, training, key)
            return h, None

        act, _ = lax.scan(body, act, (blocks_local, jnp.arange(Lp)))
        return act

    if remat:
        stage_fn = jax.checkpoint(stage_fn, static_argnums=(3,))

    def run_pipe(blocks_p, h, training, rng):
        """The GPipe schedule on pre-computed activations ``h`` [B,...]:
        microbatch split, the (M+S-1)-tick scan with the ppermute ring,
        and the last-stage bank broadcast — ONE implementation behind
        both model layouts so the schedules can never diverge."""
        B = h.shape[0]
        if B % M:
            raise ValueError(
                f"local batch {B} not divisible by n_microbatch {M}")
        mb = B // M
        hmb = h.reshape((M, mb) + h.shape[1:])
        stage = lax.axis_index(pipe_axis)

        def tick(carry, t):
            act, store = carry
            feed = lax.dynamic_index_in_dim(
                hmb, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            act_in = jnp.where(stage == 0, feed, act)
            # key unique per (tick, stage); stage_fn folds the local
            # layer index on top — no two (tick, layer) reuse a key
            key = (jax.random.fold_in(jax.random.fold_in(rng, t), stage)
                   if rng is not None else None)
            act_out = stage_fn(blocks_p, act_in, key, training)
            slot = t - (S - 1)
            upd = lax.dynamic_update_index_in_dim(
                store, act_out, jnp.clip(slot, 0, M - 1), 0)
            store = jnp.where((stage == S - 1) & (slot >= 0), upd, store)
            act = lax.ppermute(act_out, pipe_axis, perm)
            return (act, store), None

        (_, store), _ = lax.scan(tick, (jnp.zeros_like(hmb[0]),
                                        jnp.zeros_like(hmb)),
                                 jnp.arange(M + S - 1))
        # only the last stage banked real outputs; broadcast them to
        # every pipe shard (the psum transpose is where the S× cotangent
        # amplification that the train path's reduce_grad divides out
        # comes from)
        store = lax.psum(
            jnp.where(stage == S - 1, store, jnp.zeros_like(store)),
            pipe_axis)
        return store.reshape((B,) + store.shape[2:])

    if _is_lm(model):
        embed = model.modules[0]
        ln = model.modules[first + count]
        head = model.modules[first + count + 1]

        def local_fwd(packed, x, training, rng, upcast):
            pc = (_cast_floats(packed, compute_dtype)
                  if compute_dtype is not None else packed)
            xc = (_cast_floats(x, compute_dtype)
                  if compute_dtype is not None else x)
            h, _ = embed.apply_fn(pc["embed"], embed.buffer_tree(), xc,
                                  training, None)
            if not getattr(model, "use_rope", False):
                h = h + model._positions(pc["pos"], h.shape[1])
            h = run_pipe(pc["blocks"], h, training, rng)
            h, _ = ln.apply_fn(pc["ln"], ln.buffer_tree(), h, training,
                               None)
            h, _ = head.apply_fn(pc["head"], head.buffer_tree(), h,
                                 training, None)
            if model._output_mode == "log_probs":
                h = jax.nn.log_softmax(h, axis=-1)
            if compute_dtype is not None and upcast:
                h = _cast_floats(h, jnp.float32)
            return h

        return local_fwd

    pre = list(enumerate(model.modules[:first]))
    post = [(first + count + i, m)
            for i, m in enumerate(model.modules[first + count:])]

    def _edge(mods, pc_sub, h, training, rng):
        for i, m in mods:
            key = (jax.random.fold_in(rng, i)
                   if rng is not None else None)
            h, _ = m.apply_fn(pc_sub[str(i)], m.buffer_tree(), h,
                              training, key)
        return h

    def local_fwd(packed, x, training, rng, upcast):
        pc = (_cast_floats(packed, compute_dtype)
              if compute_dtype is not None else packed)
        xc = (_cast_floats(x, compute_dtype)
              if compute_dtype is not None else x)
        # edge-module keys fold the absolute module index; the pipe
        # region's keys fold (tick, stage, layer) — disjoint by use
        h = _edge(pre, pc["pre"], xc, training,
                  jax.random.fold_in(rng, 2**31 - 1) if rng is not None
                  else None)
        # shape-preservation check at trace time: the ring's where/
        # ppermute need block(out) shaped exactly like block(in), and
        # the raw XLA mismatch error would not name the real cause
        lp0 = jax.tree_util.tree_map(lambda a: a[0], pc["blocks"])
        sd = jax.eval_shape(
            lambda p, a: block.apply_fn(p, block_bufs, a, False,
                                        None)[0], lp0, h)
        if sd.shape != h.shape or sd.dtype != h.dtype:
            raise ValueError(
                f"pipelined blocks must be shape/dtype-preserving: "
                f"block maps {h.shape}/{h.dtype} -> {sd.shape}/"
                f"{sd.dtype}")
        h = run_pipe(pc["blocks"], h, training, rng)
        h = _edge(post, pc["post"], h, training,
                  jax.random.fold_in(rng, 2**31 - 2) if rng is not None
                  else None)
        if compute_dtype is not None and upcast:
            h = _cast_floats(h, jnp.float32)
        return h

    return local_fwd


def make_pipeline_train_step(model, criterion, optim, mesh,
                             n_microbatch: int,
                             data_axis: Optional[str] = "data",
                             pipe_axis: str = "pipe",
                             model_axis: Optional[str] = None,
                             compute_dtype=None, donate: bool = False,
                             remat: Optional[bool] = None):
    """Build the jitted data x pipe train step.

    Compatibility entry point: the implementation is the unified
    sharding-plan engine (``parallel.plan.compile_step_with_plan``,
    ISSUE 8) with the guard/grad-norm extras off, so the compiled
    program matches what this builder historically produced.

    Returns ``step(packed_params, slots, lr, x, y, rng=None) ->
    (loss, packed_params, slots)`` with ``.param_specs`` /
    ``.slot_specs`` / ``.pack`` / ``.unpack`` attached.  ``slots`` come
    from ``optim.init_state(packed_params)`` — stage-owned layers keep
    stage-owned optimizer state.

    ``remat`` — rematerialize each tick's stage computation in the
    backward pass.  Default ``None`` inherits ``model.remat``.
    """
    if pipe_axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {pipe_axis!r} axis")
    if model_axis is not None and model_axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {model_axis!r} axis")
    from .plan import compile_step_with_plan

    eng = compile_step_with_plan(
        model, criterion, optim, mesh, data_axis=data_axis,
        seq_axis=None, model_axis=model_axis, pipe_axis=pipe_axis,
        n_microbatch=n_microbatch, compute_dtype=compute_dtype,
        donate=donate, remat=remat, guard=False, with_gnorm=False)
    buffers = model.buffer_tree()  # validated empty by _check_model

    def step(packed, slots, lr, x, y, rng=None, w=None, total_w=None):
        loss, packed, slots, _buf, _ok, _gn = eng.step(
            packed, slots, buffers, lr, x, y, rng=rng, w=w,
            total_w=total_w)
        return loss, packed, slots

    S = eng.n_pipe
    step.param_specs = eng.param_specs
    step.slot_specs = eng.slot_specs
    step.n_stages = S
    step.n_microbatch = eng.n_microbatch
    step.pack = lambda: pack_params(model, S, model_axis)
    step.unpack = lambda packed: unpack_params(packed, model)
    # underlying jit object (by masked variant) for the telemetry
    # PerfAccountant's cost-model lowering
    step.jitted_for = lambda masked: eng.jitted_for(None, None, masked)
    step.engine = eng
    return step


def make_pipeline_eval_forward(model, mesh, n_microbatch: int,
                               data_axis: Optional[str] = "data",
                               pipe_axis: str = "pipe",
                               model_axis: Optional[str] = None,
                               compute_dtype=None):
    """Compiled pipelined forward for validation/inference over the same
    mesh/specs as :func:`make_pipeline_train_step` (reuses its sharded
    params and the SAME schedule implementation).  Returns
    ``fwd(packed_params, x) -> out`` with the batch dim sharded over
    ``data``."""
    if pipe_axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {pipe_axis!r} axis")
    data_axis = data_axis if data_axis in mesh.axis_names else None
    if model_axis is not None and model_axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {model_axis!r} axis")
    S = mesh.shape[pipe_axis]
    M = int(n_microbatch)
    first, count = _check_model(model, S, model_axis)
    local_fwd = _make_local_forward(model, first, count, S, M, pipe_axis,
                                    compute_dtype, remat=False)
    pspecs = param_specs(pack_params(model, S, model_axis), pipe_axis,
                         block=model.modules[first], model_axis=model_axis)

    def local_eval(packed, x):
        return local_fwd(packed, x, False, None, True)

    in_batch = P(data_axis) if data_axis else P()
    sharded = shard_map(local_eval, mesh=mesh, in_specs=(pspecs, in_batch),
                        out_specs=in_batch, check_vma=False)
    jitted = jax.jit(sharded)

    def fwd(packed, x):
        n_data = mesh.shape[data_axis] if data_axis else 1
        if x.shape[0] % (n_data * M):
            raise ValueError(
                f"batch {x.shape[0]} must be divisible by data-axis × "
                f"n_microbatch = {n_data} × {M} = {n_data * M}")
        return jitted(packed, jnp.asarray(x))

    fwd.param_specs = pspecs
    return fwd
