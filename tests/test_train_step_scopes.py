"""Device scopes of the compiled training step (ISSUE 38): the step
names its own parts — ``step.*`` in ``parallel/plan.py``'s local step,
``lm.*`` / ``block.*`` in ``models/transformer.py``, ``attention.core``
in ``nn/attention.py`` — as ``jax.named_scope`` metadata on the lowered
operations; the names are ``telemetry.tracer.DEVICE_SCOPES``, and a lint
holds every ``jax.named_scope`` literal in the package to that table.
The weight draw is counted where it happens.  Names and counts only."""
import ast
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from bigdl_tpu import nn
from bigdl_tpu.telemetry.tracer import DEVICE_SCOPES

HERE = os.path.dirname(os.path.abspath(__file__))

STEP_SCOPES = ("step.cast_params", "step.forward", "step.loss",
               "step.grad_reduce", "step.update")
NAMED = STEP_SCOPES + ("lm.embed", "block.attention", "block.mlp",
                       "lm.head", "attention.core")


def _engine(devices=1, remat=False, **kw):
    """The plan engine's compiled step of a tiny ``TransformerLM``: one
    device (a plain jit) or a data mesh (``shard_map``)."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.parallel.plan import compile_step_with_plan
    from bigdl_tpu.utils.rng import RNG

    RNG().set_seed(4)
    model = TransformerLM(23, embed_dim=16, num_heads=2, mlp_dim=32,
                          num_layers=2, max_len=24, output="logits",
                          remat=remat)
    crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(), True)
    mesh = Mesh(np.array(jax.devices()[:devices]), ("data",))
    return compile_step_with_plan(model, crit, Adam(1e-2), mesh, **kw)


def _op_names(form):
    """The ``op_name`` paths of the lowered step: ``single``,
    ``shard_map`` (two devices), ``remat`` (one device, every block
    under ``jax.checkpoint``)."""
    eng = _engine(2 if form == "shard_map" else 1, form == "remat",
                  compute_dtype=jnp.bfloat16)
    params, slots, buffers = eng.init_state()
    x = jnp.ones((4, 8), jnp.float32)
    text = eng.jitted_for(x, x, False).lower(
        params, slots, buffers, np.float32(1e-2), jax.random.PRNGKey(0),
        x, x).as_text(debug_info=True)
    return sorted(set(re.findall(r'loc\("([^"]+)"', text)))


def _has(path, scope):
    """``scope`` is a whole component of ``path``, wrapped or not."""
    return re.search(r"(?:^|[/(])" + re.escape(scope) + r"(?:$|[/)])",
                     path) is not None


@pytest.mark.parametrize("form", ["single", "shard_map", "remat"])
def test_step_scopes_name_the_lowered_operations(form):
    paths = _op_names(form)
    for scope in NAMED:
        assert scope in DEVICE_SCOPES
        if scope == "step.grad_reduce" and form != "shard_map":
            continue        # one device: nothing to reduce, no operation
        assert any(_has(p, scope) for p in paths), scope
    # the backward has no scope of its own: autodiff wraps the forward's
    for scope in ("block.mlp", "attention.core", "lm.head", "lm.embed"):
        # (a nested jit's own function is lowered once, under a path
        # of its own that starts at the innermost transform)
        under = [p for p in paths if _has(p, scope)
                 and p.startswith(("jit(", "jvp(", "transpose("))]
        assert any("transpose(" in p for p in under), scope
        assert any("transpose(" not in p for p in under), scope
        assert all(_has(p, "step.forward") for p in under), scope
    # the model's scopes nest as the readers take them to
    core = [p for p in paths if _has(p, "attention.core")]
    assert core and all(_has(p, "block.attention") for p in core)
    assert not any(_has(p, "block.mlp") for p in core)
    if form == "remat":
        assert any("checkpoint" in p and "rematted_computation" in p
                   and _has(p, "block.mlp") for p in paths)
    if form == "shard_map":
        # the gradients' reduce, and every other collective, is named
        coll = [p for p in paths if "/" in p
                and p.rsplit("/", 1)[-1] in ("psum", "pmin", "pmax",
                                             "all_gather", "psum_scatter")]
        assert any(_has(p, "step.grad_reduce") and p.endswith("psum")
                   for p in coll)
        assert all(any(_has(p, s) for s in STEP_SCOPES) for p in coll), coll


def test_scopes_change_no_number():
    """Metadata only: the step's results are those of the same step
    traced with ``jax.named_scope`` a no-op."""
    import contextlib
    from unittest import mock

    def three_steps():
        eng = _engine(2)
        params, slots, buffers = eng.init_state()
        ids = np.random.RandomState(0).randint(1, 24, (4, 9))
        x, y = (jnp.asarray(ids[:, :-1], jnp.float32),
                jnp.asarray(ids[:, 1:], jnp.float32))
        losses = []
        for _ in range(3):
            loss, params, slots, buffers, ok, gn = eng.step(
                params, slots, buffers, 1e-2, x, y,
                rng=jax.random.PRNGKey(0))
            losses.append((float(loss), float(gn), bool(ok)))
        return losses, jax.device_get(params)

    named = three_steps()
    with mock.patch.object(jax, "named_scope",
                           lambda name: contextlib.nullcontext()):
        plain = three_steps()
    assert named[0] == plain[0]
    for a, b in zip(jax.tree_util.tree_leaves(named[1]),
                    jax.tree_util.tree_leaves(plain[1])):
        np.testing.assert_array_equal(a, b)


def _scope_literals(tree):
    """(line, name) of every string literal handed to
    ``jax.named_scope`` — as a call or as a decorator's call."""
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "named_scope"):
            arg = node.args[0] if node.args else None
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                yield node.lineno, arg.value
            else:
                yield node.lineno, None


def test_device_scope_lint_covers_every_named_scope():
    """The device-side twin of
    ``test_category_lint_covers_the_program_span_names``: every name
    handed to ``jax.named_scope`` anywhere in ``bigdl_tpu/`` is a
    literal of ``DEVICE_SCOPES``, and every entry of the table is
    emitted somewhere."""
    assert len(DEVICE_SCOPES) == len(set(DEVICE_SCOPES)) == 43
    pkg = os.path.join(HERE, "..", "bigdl_tpu")
    seen, offenders = set(), []
    for path in glob.glob(os.path.join(pkg, "**", "*.py"), recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read())
        for line, name in _scope_literals(tree):
            where = f"{os.path.relpath(path, pkg)}:{line}"
            if name is None:
                offenders.append(f"{where}: a scope that is no literal")
            elif name not in DEVICE_SCOPES:
                offenders.append(f"{where}: {name!r} not in DEVICE_SCOPES")
            else:
                seen.add(name)
    assert not offenders, "\n".join(offenders)
    assert seen == set(DEVICE_SCOPES)


@pytest.mark.parametrize("where", ["host", "device"])
def test_the_weight_draw_is_counted_where_it_happens(where):
    import contextlib

    from bigdl_tpu.nn.initialization import (RandomNormal, Xavier, Zeros,
                                             device_draw)
    from bigdl_tpu.telemetry import default_tracer
    from bigdl_tpu.telemetry.metric_names import (INIT_DRAW_SECONDS_TOTAL,
                                                  METRIC_FAMILY_NAMES)
    from bigdl_tpu.telemetry.registry import reset_default_registry

    assert INIT_DRAW_SECONDS_TOTAL in METRIC_FAMILY_NAMES
    reg = reset_default_registry()
    before = len(default_tracer().spans())
    with (device_draw() if where == "device" else contextlib.nullcontext()):
        Zeros().init((100,))            # no draw: nothing booked
        assert reg.get(INIT_DRAW_SECONDS_TOTAL) is None
        a = Xavier().init((6, 5))
        b = RandomNormal(0.0, 0.02).init((7,))
    assert a.shape == (6, 5) and b.shape == (7,)
    seconds = dict((labels["where"], c.value) for labels, c in
                   reg.get(INIT_DRAW_SECONDS_TOTAL).series())
    assert set(seconds) == {where} and seconds[where] > 0
    assert len(default_tracer().spans()) == before   # no span per leaf
    reset_default_registry()
