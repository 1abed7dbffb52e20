"""Evaluator (reference optim/Evaluator.scala:37, Validator.scala,
LocalValidator.scala, DistriValidator.scala:35).

Batches run through ONE jitted eval forward; with a mesh, the forward is
a shard_mapped program over the ``data`` axis so validation runs
on-cluster exactly like the reference's DistriValidator
(DistriValidator.scala:35, DistriOptimizer.scala:568-640) — params stay
device-resident (no host pull) and batches are padded to the mesh
multiple at static shape (metrics see only the real records).
ValidationResults reduce as monoids (the reference's driver-side reduce
of per-partition results).
"""
from __future__ import annotations

import weakref
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..dataset.sample import MiniBatch, SampleToMiniBatch
from .validation import ValidationMethod, ValidationResult

from ._sharding_utils import data_mesh as _data_mesh, pad_batch, round_up

#: observability hook for tests/metrics: how the last eval ran
last_eval_info = {"sharded": False, "n_devices": 1, "batches": 0}


_EVAL_FWD_CACHE = weakref.WeakKeyDictionary()  # model -> {mesh: jitted fwd}


def _cached_eval_fwd(model, mesh: Optional[Mesh]):
    """One compiled eval forward per (model, mesh) — validation triggers
    mid-training reuse the executable instead of re-jitting.  Held in a
    weak side table (not on the model) so models stay picklable."""
    cache = _EVAL_FWD_CACHE.setdefault(model, {})
    if mesh in cache:
        return cache[mesh]

    def fwd_local(p, b, x):
        out, _ = model.apply_fn(p, b, x, False, None)
        return out

    if mesh is not None:
        # an expert-parallel model's MoE stacks must arrive sharded
        # over the data axis (the bound all_to_all expects E/n local
        # experts); everything else replicates as before
        from ..parallel.moe import MoEFFN

        if any(isinstance(m, MoEFFN) and m.axis_name
               for m in model.modules_iter()):
            from ..parallel.spmd import _check_moe, param_specs

            _check_moe(model, mesh, "data", None)
            # model_axis=None: this mesh is data-only, so any bound TP
            # layer degrades to replicated specs (matching its forward's
            # unbound-axis NameError degrade) instead of referencing a
            # nonexistent 'model' axis
            pspec = param_specs(model, None)
        else:
            pspec = P()
        # unchecked like every training step the plan engine builds:
        # a model the trainer accepts must also validate
        fwd = jax.jit(shard_map(fwd_local, mesh=mesh,
                                in_specs=(pspec, P(), P("data")),
                                out_specs=P("data"), check_vma=False))
    else:
        fwd = jax.jit(fwd_local)
    cache[mesh] = fwd
    return fwd


def evaluate_dataset(model, dataset, v_methods: Sequence[ValidationMethod],
                     batch_size: int = 128, mesh: Optional[Mesh] = None,
                     params=None, buffers=None, fwd=None,
                     n_shard: Optional[int] = None) -> List[ValidationResult]:
    """Shared eval loop; dataset may yield Samples or MiniBatches.

    ``mesh``: run the forward as a compiled shard_map over the data axis.
    ``params``/``buffers``: device-resident trees to evaluate with (skips
    the host pull from ``model`` — used by DistriOptimizer's validation
    trigger mid-training).
    ``fwd``: override the compiled forward with a custom
    ``(params, buffers, x) -> out`` (the multi-axis driver passes
    parallel.spmd.make_eval_forward); ``n_shard`` is the batch-dim
    padding multiple for that forward.
    """
    model.evaluate()
    if params is None:
        params = model.param_tree()
    if buffers is None:
        buffers = model.buffer_tree()

    if fwd is not None:
        n_dev = n_shard or 1
        mesh = None
    else:
        mesh = _data_mesh(mesh)
        n_dev = mesh.shape["data"] if mesh is not None else 1
        fwd = _cached_eval_fwd(model, mesh)

    last_eval_info.update({"sharded": mesh is not None or n_dev > 1,
                           "n_devices": n_dev,
                           "batches": 0})

    it = dataset.data(train=False)
    results = [None] * len(v_methods)
    batcher = SampleToMiniBatch(batch_size)

    def batches():
        pending = []
        for item in it:
            if isinstance(item, MiniBatch):
                yield item
            else:
                pending.append(item)
                if len(pending) == batch_size:
                    yield batcher.make(pending)
                    pending = []
        if pending:
            yield batcher.make(pending)

    for batch in batches():
        x = batch.get_input()
        y = batch.get_target()
        size = batch.size()
        x = jnp.asarray(x) if not isinstance(x, (list, tuple)) else \
            type(x)(jnp.asarray(v) for v in x)
        padded = size % n_dev != 0
        if padded:  # static-shape contract over the mesh
            x, y, _ = pad_batch(x, y, size, round_up(size, n_dev))
        out = fwd(params, buffers, x)
        if padded:
            # slice the RECORD axis of every output/target leaf (models
            # may emit tuples/Tables)
            out = jax.tree_util.tree_map(lambda a: a[:size], out)
            y = jax.tree_util.tree_map(lambda a: a[:size], y)
        last_eval_info["batches"] += 1
        for i, m in enumerate(v_methods):
            r = m(out, y)
            results[i] = r if results[i] is None else results[i] + r
    return [r for r in results if r is not None]


class Evaluator:
    """reference optim/Evaluator.scala:37 — model.evaluate(dataset, methods)."""

    def __init__(self, model):
        self.model = model

    def test(self, dataset, v_methods, batch_size: int = 128):
        results = evaluate_dataset(self.model, dataset, v_methods, batch_size)
        return list(zip(results, [m.format() for m in v_methods]))


class LocalValidator(Evaluator):
    """reference optim/LocalValidator.scala:37"""


class DistriValidator(Evaluator):
    """reference optim/DistriValidator.scala:35 — validation as a
    compiled, mesh-sharded program (EveryBatch sharding over the data
    axis; no host parameter pull)."""

    def __init__(self, model, mesh: Optional[Mesh] = None):
        super().__init__(model)
        if mesh is None:
            from ..utils.engine import Engine

            mesh = Engine.create_mesh()
        self.mesh = _data_mesh(mesh)

    def test(self, dataset, v_methods, batch_size: int = 128):
        results = evaluate_dataset(self.model, dataset, v_methods,
                                   batch_size, mesh=self.mesh)
        return list(zip(results, [m.format() for m in v_methods]))
