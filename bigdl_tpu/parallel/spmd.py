"""SPMD train-step builder over a multi-axis device mesh.

Composes the framework's parallel axes into ONE compiled program
(SURVEY §7.6 — the whole reference iteration, two Spark jobs + block
manager traffic, becomes a single XLA executable):

* ``data``  axis — batch sharding; gradients pmean'd across it (the
  rebuild of AllReduceParameter's reduce-scatter/all-gather, here left
  to XLA's collective scheduling)
* ``seq``   axis — sequence/context parallelism; models whose attention
  uses ``seq_strategy="ring"|"ulysses"`` compute across it with
  ppermute/all_to_all (parallel/ring_attention.py)
* ``model`` axis — Megatron tensor parallelism; Column/RowParallelLinear
  weights are sharded by ``param_specs`` and the row psum closes each
  block

``make_train_step`` returns a jitted function
``(params, slots, lr, x, y) -> (loss, params, slots)`` whose arrays stay
device-resident and sharded between steps.
"""
from __future__ import annotations

import logging

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

log = logging.getLogger("bigdl_tpu")


def param_specs(module, model_axis: str = "model"):
    """PartitionSpec pytree matching ``module.param_tree()``.

    Column/RowParallelLinear weights shard over ``model_axis``;
    ``MoEFFN`` expert stacks shard their leading expert dim over the
    layer's own ``axis_name`` (expert parallelism rides the token-
    sharding axis, router weights replicated); every other parameter is
    replicated.
    """
    from ..nn.embedding import ShardedEmbedding
    from ..nn.module import Container
    from .moe import MoEFFN
    from .tensor_parallel import ColumnParallelLinear, RowParallelLinear

    tree = module.param_tree()
    if isinstance(module, ShardedEmbedding) and module.axis_name:
        # rows (and their optimizer slots) partition over the bound
        # axis; the lookup is an index exchange under shard_map
        return {"weight": P(module.axis_name)}
    if isinstance(module, ColumnParallelLinear) and module.axis_name:
        specs = {"weight": P(model_axis, None)}
        if "bias" in tree:
            specs["bias"] = P(model_axis)
        return specs
    if isinstance(module, RowParallelLinear) and module.axis_name:
        specs = {"weight": P(None, model_axis)}
        if "bias" in tree:
            specs["bias"] = P()
        return specs
    if isinstance(module, MoEFFN) and module.axis_name:
        ax = module.axis_name
        return {"router_w": P(), "router_b": P(),
                "wi": P(ax), "bi": P(ax), "wo": P(ax), "bo": P(ax)}
    if isinstance(module, Container):
        specs = {str(i): param_specs(m, model_axis)
                 for i, m in enumerate(module.modules)}
        for k in tree:  # module-own params (e.g. TransformerLM "pos")
            if k not in specs:
                specs[k] = P()
        return specs
    return jax.tree_util.tree_map(lambda _: P(), tree)


def survivor_mesh(n_shards: int, devices=None, template=None):
    """Shrink-to-survivors rebuild mesh (resilience/elastic.py).

    Without a ``template``: a data-only mesh over the first
    ``n_shards`` devices (the historical shape).  With a ``template``
    mesh the non-data axes are KEPT at their template sizes and only
    the data axis resizes to ``n_shards`` — a shrink on a
    data x model [x pipe] mesh re-derives a mesh (and therefore a
    sharding plan) that still tensor/pipeline-parallelizes instead of
    silently degrading to data-only (ISSUE 8).  Devices beyond
    ``n_shards x prod(other axes)`` idle until regrow."""
    devs = list(devices if devices is not None else jax.devices())
    n = int(n_shards)
    from jax.sharding import Mesh

    from ..telemetry.registry import default_registry

    default_registry().counter(
        "bigdl_mesh_rebuilds_total",
        "survivor-mesh rebuilds (elastic shrink/regrow re-entries)"
    ).inc()
    if template is None:
        if n < 1 or n > len(devs):
            raise ValueError(
                f"survivor mesh needs 1..{len(devs)} shards, got {n}")
        return Mesh(np.array(devs[:n]), ("data",))
    names = tuple(template.axis_names)
    sizes = [int(template.shape[a]) for a in names]
    if "data" not in names:
        names = ("data",) + names
        sizes = [1] + sizes
    sizes[names.index("data")] = n
    need = int(np.prod(sizes))
    if n < 1 or need > len(devs):
        raise ValueError(
            f"survivor mesh {dict(zip(names, sizes))} needs {need} "
            f"devices, have {len(devs)}")
    return Mesh(np.array(devs[:need]).reshape(sizes), names)


def bound_axes(model) -> frozenset:
    """Mesh axis names the model's modules are BUILT for (bound TP
    layers, expert-parallel MoE, a ring/ulysses sequence strategy) —
    the axes whose silent absence from a mesh is a misconfiguration
    worth warning about, not a default quietly dropped."""
    from ..nn.embedding import ShardedEmbedding
    from .moe import MoEFFN
    from .tensor_parallel import ColumnParallelLinear, RowParallelLinear

    bound = set()
    for m in model.modules_iter():
        if isinstance(m, (ColumnParallelLinear, RowParallelLinear)) \
                and m.axis_name:
            bound.add(m.axis_name)
        if isinstance(m, (MoEFFN, ShardedEmbedding)) and m.axis_name:
            bound.add(m.axis_name)
    if getattr(model, "seq_strategy", None) in ("ring", "ulysses"):
        bound.add(getattr(model, "seq_axis", "seq"))
    return frozenset(bound)


def _resolve_axes(mesh, data_axis, seq_axis, model_axis,
                  bound=frozenset()):
    """Keep only the axes the mesh actually has.  A dropped axis that
    the model is BOUND to (``bound`` — see :func:`bound_axes`) is named
    in a structured-log warning: a misconfigured mesh used to run
    quietly un-parallelized, which is undiagnosable from the outside."""
    axes = set(mesh.axis_names)
    for axis in (data_axis, seq_axis, model_axis):
        if axis is not None and axis not in axes and axis in bound:
            log.warning(
                "mesh %s lacks axis %r which this model is built for — "
                "the axis is dropped and its layers run replicated/"
                "degraded; pass a mesh with a %r axis or rebuild the "
                "model without it", tuple(mesh.axis_names), axis, axis)
    return (data_axis if data_axis in axes else None,
            seq_axis if seq_axis in axes else None,
            model_axis if model_axis in axes else None)


def _check_moe(model, mesh, data_axis, seq_axis):
    """Expert-parallel constraints, validated loudly at build time:
    every bound ``MoEFFN`` must ride the mesh's token-sharding (data)
    axis; on a >1 seq mesh the layer must carry the seq axis in
    ``stat_axes`` so its aux-loss routing statistics stay global."""
    from .moe import MoEFFN

    moe = [m for m in model.modules_iter()
           if isinstance(m, MoEFFN) and m.axis_name]
    if not moe:
        return
    for m in moe:
        if m.axis_name not in mesh.axis_names:
            raise ValueError(
                f"MoEFFN is bound to mesh axis {m.axis_name!r} which the "
                f"mesh {mesh.axis_names} does not have; build with "
                "axis_name=None for dense (single-shard) MoE")
        if m.axis_name != data_axis:
            raise ValueError(
                f"expert parallelism rides the token-sharding axis: "
                f"MoEFFN.axis_name {m.axis_name!r} must equal the data "
                f"axis {data_axis!r}")
        if mesh.shape[m.axis_name] > 1 and m.n_experts % mesh.shape[
                m.axis_name] != 0:
            raise ValueError(
                f"n_experts {m.n_experts} not divisible by the "
                f"{m.axis_name!r} axis size {mesh.shape[m.axis_name]}")
        if (seq_axis is not None and mesh.shape[seq_axis] > 1
                and seq_axis not in m.stat_axes):
            raise ValueError(
                f"MoE on a >1 {seq_axis!r} mesh needs the seq axis in "
                f"MoEFFN.stat_axes (got {m.stat_axes}) so the aux-loss "
                "routing statistics stay global — TransformerLM wires "
                "this automatically when built with a seq strategy")


def _in_spec_fn(data_axis, seq_axis, input_seq_dim):
    """Rank → PartitionSpec: batch dim on ``data``, the sequence dim
    (when present and the leaf has one) on ``seq``, rest replicated.
    Shared by the train and eval builders so their layouts can never
    diverge (eval reuses the train step's sharded params)."""
    def in_spec(ndim):
        parts = [data_axis]
        if input_seq_dim is not None and seq_axis and ndim > input_seq_dim:
            parts += [None] * (input_seq_dim - 1) + [seq_axis]
        parts = parts[:ndim] + [None] * (ndim - len(parts))
        return P(*parts)

    return in_spec


def _io_spec_fn(in_spec):
    return lambda tree: jax.tree_util.tree_map(
        lambda a: in_spec(getattr(a, "ndim", 0)), tree)


def _cast_fwd(model, compute_dtype, upcast_out=True):
    """Forward with the bf16-compute/f32-master cast scheme applied
    (shared by the train loss_fn and the eval forward)."""
    from ..optim.optimizer import _cast_floats, _restore_dtypes

    def run(params, buf, x, training, rng):
        p_c, x_c = params, x
        if compute_dtype is not None:
            p_c = _cast_floats(params, compute_dtype)
            x_c = _cast_floats(x, compute_dtype)
        out, nb = model.apply_fn(p_c, buf, x_c, training, rng)
        if compute_dtype is not None:
            if upcast_out:
                out = _cast_floats(out, jnp.float32)
            nb = _restore_dtypes(nb, buf)
        return out, nb

    return run


def slot_specs(slots, pspecs):
    """Optimizer-state specs: subtrees shaped like the param tree inherit
    the param specs (momentum/Adam moments shard with their params);
    scalar leaves (step counters) replicate.  Recurses through dicts AND
    NamedTuples (optax states like ScaleByAdamState)."""
    ptreedef = jax.tree_util.tree_structure(pspecs)

    def rec(s):
        if jax.tree_util.tree_structure(s) == ptreedef:
            return pspecs
        if isinstance(s, dict):
            return {k: rec(v) for k, v in s.items()}
        if isinstance(s, tuple) and hasattr(s, "_fields"):
            return type(s)(*(rec(v) for v in s))
        if isinstance(s, (tuple, list)):
            return type(s)(rec(v) for v in s)
        return P()

    return rec(slots)


def make_train_step(model, criterion, optim, mesh,
                    data_axis: Optional[str] = "data",
                    seq_axis: Optional[str] = "seq",
                    model_axis: Optional[str] = "model",
                    input_seq_dim: Optional[int] = 1,
                    compute_dtype=None, donate: bool = False):
    """Build the jitted SPMD train step over ``mesh``.

    Compatibility entry point: the implementation is the unified
    sharding-plan engine (``parallel.plan.compile_step_with_plan``,
    ISSUE 8) with the guard/grad-norm extras off, so the compiled
    program matches what this builder historically produced.  Returns
    ``step(params, slots, buf, lr, x, y, rng=None, w=None,
    total_w=None) -> (loss, params, slots, buffers)`` with
    ``.param_specs`` / ``.slot_specs`` / ``.input_spec`` /
    ``.jitted_for`` attached.

    ``input_seq_dim`` — which dim of x/y is the sequence (None: inputs
    are not sequence-sharded).  Axes not present in the mesh are
    dropped (with a warning when the model is built for them).
    ``compute_dtype`` — bf16 compute / f32 master weights.
    ``donate=True`` donates params/slots/buffers to the step — no
    old+new copies in HBM; the caller must rebind them each call.
    """
    from .plan import compile_step_with_plan

    eng = compile_step_with_plan(
        model, criterion, optim, mesh, data_axis=data_axis,
        seq_axis=seq_axis, model_axis=model_axis,
        input_seq_dim=input_seq_dim, compute_dtype=compute_dtype,
        donate=donate, guard=False, with_gnorm=False)

    def step(params, slots, buf, lr, x, y, rng=None, w=None,
             total_w=None):
        loss, params, slots, buf, _ok, _gn = eng.step(
            params, slots, buf, lr, x, y, rng=rng, w=w, total_w=total_w)
        return loss, params, slots, buf

    step.param_specs = eng.param_specs
    step.slot_specs = eng.slot_specs
    step.input_spec = eng.input_spec
    # the underlying jit object for a given batch signature — lets the
    # telemetry PerfAccountant lower the exact program for cost-model
    # FLOP/byte accounting without a second jit cache
    step.jitted_for = eng.jitted_for
    step.engine = eng
    return step


_AUTO = "auto"


def make_eval_forward(model, mesh, data_axis: Optional[str] = "data",
                      seq_axis: Optional[str] = "seq",
                      model_axis: Optional[str] = "model",
                      input_seq_dim: Optional[int] = 1,
                      compute_dtype=None, output_seq_dim=_AUTO):
    """Compiled forward over the same multi-axis mesh/specs as
    :func:`make_train_step` — validation/inference for models whose
    eager forward needs bound mesh axes (ring attention, RowParallel
    psum).  Batch dim shards over ``data``.

    ``output_seq_dim`` — which dim of each output leaf is the sequence
    dim (sharded over ``seq`` on reassembly).  The default ``"auto"``
    uses ``input_seq_dim`` and VALIDATES it against the probed local
    output shapes: a rank>=2 output whose dim-1 extent is not the local
    sequence extent (e.g. a pooled (B, C) classifier head) raises
    instead of silently reassembling a wrong result.  Pass an explicit
    int to override, or ``None`` for outputs with no sequence dim
    (replicated across the seq axis — the model must reduce over it
    internally).  Returns ``fwd(params, buffers, x) -> out`` with out
    gathered per-call semantics (fetching the result reassembles the
    full array)."""
    data_axis, seq_axis, model_axis = _resolve_axes(
        mesh, data_axis, seq_axis, model_axis)
    _check_moe(model, mesh, data_axis, seq_axis)

    pspecs = param_specs(model, model_axis or "model")
    buffers = model.buffer_tree()
    bspecs = jax.tree_util.tree_map(lambda _: P(), buffers)
    in_spec = _in_spec_fn(data_axis, seq_axis, input_seq_dim)
    io_spec = _io_spec_fn(in_spec)
    cast_fwd = _cast_fwd(model, compute_dtype)

    def local_fwd(params, buf, x):
        out, _ = cast_fwd(params, buf, x, False, None)
        return out

    _cache = {}
    _shapes = {}  # input treedef/shapes -> local output shape tree

    def _probe_out_shapes(params, buf, x):
        """LOCAL output shapes via a minimal shard_map whose outputs are
        shape vectors only (an eager/eval_shape trace would hit the same
        unbound-axis problem the whole helper exists to avoid).  Probes
        on the smallest batch (one record per data shard) so the extra
        compile is cheap."""
        n_data = mesh.shape[data_axis] if data_axis else 1
        tiny = jax.tree_util.tree_map(
            lambda a: a[:n_data] if getattr(a, "ndim", 0) >= 1 else a, x)

        def shape_fn(p, b, xx):
            out = local_fwd(p, b, xx)
            return jax.tree_util.tree_map(
                lambda o: jnp.asarray(o.shape, jnp.int32), out)

        probe = shard_map(shape_fn, mesh=mesh,
                          in_specs=(pspecs, bspecs, io_spec(tiny)),
                          out_specs=P(), check_vma=False)
        shape_tree = jax.jit(probe)(params, buf, tiny)
        return jax.tree_util.tree_map(
            lambda s: tuple(int(v) for v in np.asarray(s)), shape_tree,
            is_leaf=lambda s: hasattr(s, "shape"))

    def _check_out_seq(local_shapes, x):
        """auto mode: a rank>=2 output leaf is about to have its dim
        ``input_seq_dim`` sharded over ``seq`` on reassembly — verify
        that dim's local extent IS the local sequence extent."""
        n_seq = mesh.shape[seq_axis]
        seq_exts = {a.shape[input_seq_dim]
                    for a in jax.tree_util.tree_leaves(x)
                    if getattr(a, "ndim", 0) > input_seq_dim}
        expect = {e // n_seq for e in seq_exts}
        for shp in jax.tree_util.tree_leaves(
                local_shapes, is_leaf=lambda s: isinstance(s, tuple)):
            if (len(shp) > input_seq_dim
                    and shp[input_seq_dim] not in expect):
                raise ValueError(
                    f"make_eval_forward: output leaf with local shape "
                    f"{shp} does not carry the sequence dim at dim "
                    f"{input_seq_dim} (local seq extent(s) "
                    f"{sorted(expect)}); reassembling it over the "
                    f"'{seq_axis}' axis would be wrong (e.g. a pooled "
                    "(B, C) head).  Pass output_seq_dim=None if the "
                    "output has no sequence dim (the model must reduce "
                    "over the seq axis internally), or an explicit "
                    "output_seq_dim int.")

    osd = output_seq_dim
    # equality, not identity: callers pass the plain string "auto"
    # (e.g. Optimizer.set_validation's default) and interning is not a
    # contract
    out_seq_dim = (input_seq_dim
                   if isinstance(osd, str) and osd == _AUTO else osd)
    out_spec_fn = (in_spec if out_seq_dim == input_seq_dim
                   else _in_spec_fn(data_axis, seq_axis, out_seq_dim))

    def fwd(params, buf, x):
        x = jax.tree_util.tree_map(jnp.asarray, x)
        treedef = jax.tree_util.tree_structure(x)
        # keyed by full input SHAPES (not just ranks): the seq-dim
        # validation below compares probed local extents against THIS
        # input's sequence length, so shapes probed for one length must
        # never be reused for another (a (B, 8) and a (B, 16) batch have
        # equal ranks but different local extents)
        key = treedef, tuple(a.shape
                             for a in jax.tree_util.tree_leaves(x))
        if key not in _cache:
            if key not in _shapes:
                _shapes[key] = _probe_out_shapes(params, buf, x)
            local_shapes = _shapes[key]
            if (isinstance(osd, str) and osd == _AUTO and seq_axis
                    and input_seq_dim is not None):
                _check_out_seq(local_shapes, x)
            out_specs = jax.tree_util.tree_map(
                lambda shp: out_spec_fn(len(shp)), local_shapes,
                is_leaf=lambda s: isinstance(s, tuple))
            sharded = shard_map(local_fwd, mesh=mesh,
                                in_specs=(pspecs, bspecs, io_spec(x)),
                                out_specs=out_specs, check_vma=False)
            _cache[key] = jax.jit(sharded)
        return _cache[key](params, buf, x)

    fwd.param_specs = pspecs
    return fwd
