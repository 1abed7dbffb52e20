"""The eight per-layer metrics PR 38 added: seven read the device scopes
of the compiled training step (``readers/_train_scopes.py``), one the
weight draw's counters.  The helper on synthetic rows; the seven on a
slice of a real ``gpt2m_train_1chip`` trace recorded on the chip; all
eight against a program that emits none of it, and on a CPU
rehearsal."""
import importlib
import os

import pytest

from conftest import HERE, run_command

from benchmark.readers import _train_scopes as ts

SCOPE_READERS = ("train_attention_ms_per_step",
                 "train_attention_core_ms_per_step",
                 "train_mlp_ms_per_step", "train_head_loss_ms_per_step",
                 "train_update_ms_per_step",
                 "train_grad_reduce_ms_per_step", "train_step_named_pct")
RECORDED = os.path.join(HERE, "recorded", "v5e_train_scopes_slice.json.gz")


class Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def read(name, ctx):
    return importlib.import_module(f"benchmark.readers.{name}").read(ctx)


@pytest.mark.parametrize("path,inside,outside", [
    ("jit(local_step)/step.forward/block.mlp/dot_general",
     {"step.forward", "block.mlp"}, {"block.attention", "step.loss"}),
    ("jit(local_step)/jvp(step.forward)/block.mlp/dot_general",
     {"step.forward", "block.mlp"}, set()),
    ("jit(f)/transpose(jvp(step.forward))/block.attention/attention.core/x",
     {"step.forward", "block.attention", "attention.core"}, {"block.mlp"}),
    ("jit(f)/transpose(jvp(step.forward))/jvp(step.forward)/checkpoint/"
     "rematted_computation/block.mlp/tanh",
     {"step.forward", "block.mlp"}, set()),
    # a scope that is only PART of a component is not on the path
    ("jit(f)/step.forward/block.mlpx/dot", {"step.forward"}, {"block.mlp"}),
    ("jit(f)/jvp(xstep.forward)/myblock.mlp/dot", set(),
     {"step.forward", "block.mlp"}),
    ("jit(f)/step.forwarding/lm.headless", set(), set(ts.SCOPES)),
    ("", set(), set(ts.SCOPES)),
])
def test_a_scope_is_a_whole_component_wrapped_or_not(path, inside, outside):
    got = set(ts.components(path))
    assert inside <= got and not outside & got
    hits, first = ts._booking(path)
    assert hits == inside
    assert first == ("step.forward" if "step.forward" in inside
                     else ts.UNNAMED)


def _rows(t0, scopes):
    """One execution's operations, 10 ns each, back to back from t0."""
    return [[f"%op.{i} = f32[] add()", t0 + 10 * i, 10, {"scope": s}]
            for i, s in enumerate(scopes)]


STEP = ["jit(s)/jvp(step.cast_params)/convert",
        "jit(s)/jvp(step.forward)/lm.embed/gather",
        "jit(s)/jvp(step.forward)/block.attention/dot",
        "jit(s)/jvp(step.forward)/block.attention/attention.core/call",
        "jit(s)/jvp(step.forward)/block.mlp/dot",
        "jit(s)/jvp(step.forward)/lm.head/dot",
        "jit(s)/jvp(step.loss)/exp",
        "jit(s)/transpose(jvp(step.forward))/block.mlp/dot",
        "jit(s)/transpose(jvp(step.forward))/block.attention/attention.core/c",
        "jit(s)/step.grad_reduce/psum",
        "jit(s)/step.update/dot",
        "jit(s)/step.update/mul",
        "jit(s)/step.update/jit(_where)/select_n",
        "jit(s)/copy"]                      # under no scope at all


def test_whole_executions_only_enter_sum_and_count():
    """Three executions of 140 ns; the window cuts the first (it starts
    before) and the last (it ends after): one is summed, one counted.
    A smaller program beside them is not the step."""
    n = 10 * len(STEP)
    events = _rows(0, STEP) + _rows(1000, STEP) + _rows(2000, STEP)
    mods = [["jit_s(1)", 0, n], ["jit_s(1)", 1000, n], ["jit_s(1)", 2000, n],
            ["jit_key(2)", 900, 5]]
    events.append(["%k = u32[] rng()", 900, 5, {"scope": "jit(key)/rng"}])
    t = ts.reduce(events, mods, window=(5, 2000 + n - 5))
    assert t["executions"] == 1 and t["busy_ns"] == n
    whole = ts.reduce(events, mods)            # the chip's own extent
    assert whole["executions"] == 3 and whole["busy_ns"] == 3 * n
    for table in (t, whole):
        per = ts.per_step_table(table)
        assert per["busy_ms"] == pytest.approx(n / 1e6)
        assert per["scope_ms"]["block.mlp"] == pytest.approx(20e-6)
        assert per["scope_ms"]["attention.core"] == pytest.approx(20e-6)
        assert per["scope_ms"]["block.attention"] == pytest.approx(30e-6)
        assert per["scope_ms"]["step.forward"] == pytest.approx(70e-6)
        # the partition adds up to the busy time exactly
        assert sum(table["partition_ns"].values()) == table["busy_ns"]
        assert per["partition_ms"][ts.UNNAMED] == pytest.approx(10e-6)
        assert per["named_pct"] == pytest.approx(100 * 13 / 14)
    ctx = Ctx(_train_scopes=t)
    assert read("train_head_loss_ms_per_step", ctx) == pytest.approx(20e-6)
    assert read("train_update_ms_per_step", ctx) == pytest.approx(30e-6)
    assert read("train_grad_reduce_ms_per_step", ctx) == pytest.approx(10e-6)
    assert read("train_step_named_pct", ctx) == pytest.approx(100 * 13 / 14)


def test_nested_events_count_their_self_time_once():
    events = [["%while.1 = () while()", 0, 100, {"scope": "jit(s)/step.update/while"}],
              ["%a = f32[] add()", 10, 30, {"scope": "jit(s)/step.update/while/body/add"}],
              ["%b = f32[] add()", 50, 20, {"scope": "jit(s)/step.loss/exp"}]]
    t = ts.reduce(events, [["jit_s(1)", 0, 100]])
    assert t["busy_ns"] == 100
    assert t["partition_ns"] == {"step.update": 80, "step.loss": 20}


def test_nothing_to_read_is_none():
    plain = ["jit(s)/jvp()/dot_general", "jit(s)/transpose(jvp())/mul", ""]
    mods = [["jit_s(1)", 0, 30]]
    assert ts.reduce(_rows(0, plain), mods) is None      # no scope named
    assert ts.reduce([], mods) is None                   # no chip event
    assert ts.reduce(_rows(0, STEP), []) is None         # no module line
    assert ts.reduce(_rows(0, STEP), [["jit_s(1)", 0, 140]],
                     window=(1, 100)) is None            # no whole execution
    # a scope the program has, with nothing under it on this chip
    t = ts.reduce(_rows(0, ["jit(s)/step.update/mul"]), [["jit_s(1)", 0, 10]])
    assert read("train_grad_reduce_ms_per_step", Ctx(_train_scopes=t)) is None
    assert read("train_update_ms_per_step", Ctx(_train_scopes=t)) \
        == pytest.approx(10e-6)


@pytest.mark.parametrize("name", SCOPE_READERS + ("setup_weight_draw_s",))
def test_the_parent_commits_program_reads_as_none(tmp_path, name):
    """No trace; a trace directory with no xplane; an xplane with no
    chip plane and no program span; a registry without the counter:
    None, never an exception."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.telemetry.registry import reset_default_registry

    reset_default_registry()
    assert read(name, Ctx(run={}, trace_summary=None)) is None
    assert read(name, Ctx(run={"trace_path": str(tmp_path)},
                          trace_summary=None)) is None
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            float(jnp.ones(4).sum())
    assert read(name, Ctx(run={"trace_path": str(tmp_path)},
                          trace_summary=None)) is None


def test_the_draw_counter_sums_host_and_device():
    from bigdl_tpu.telemetry.registry import (default_registry,
                                              reset_default_registry)

    reset_default_registry()
    fam = default_registry().counter("bigdl_init_draw_seconds_total", "",
                                     labels=("where",))
    fam.labels(where="host").inc(1.5)
    fam.labels(where="device").inc(0.25)
    assert read("setup_weight_draw_s", Ctx(run={})) == pytest.approx(1.75)
    reset_default_registry()


@pytest.mark.parametrize("cell", ["tiny_train_1chip", "tiny_train_dp4",
                                  "tiny_serve_closed"])
def test_on_a_cpu_rehearsal(overlay, cell):
    """No chip plane on the CPU: the seven scope readers have nothing
    to read; the constructor's draw was counted all the same."""
    rc, obj, log = run_command(overlay, cell, trace=1)
    assert rc == 0, log
    got = obj["metrics"]
    assert not set(SCOPE_READERS) & set(got), sorted(got)
    assert got["setup_weight_draw_s"]["value"] > 0, log[-2000:]


# what PERF.md section 5 states for ``gpt2m_train_1chip`` (my chip run,
# PR 38, traced seed 3800001102: 24 whole executions), ms a step; the
# slice holds the first three of that run's executions
STATED = {
    "busy_ms": 168.207,
    "readers": {"train_attention_ms_per_step": 71.908,
                "train_attention_core_ms_per_step": 35.614,
                "train_mlp_ms_per_step": 54.816,
                "train_head_loss_ms_per_step": 18.760,
                "train_update_ms_per_step": 16.011,
                "train_step_named_pct": 96.894},
    "partition_ms": {"step.cast_params": 0.629, "step.forward": 145.133,
                     "step.loss": 1.210, "step.grad_reduce": 0.0,
                     "step.update": 16.011, "unnamed": 5.224},
}


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recording")
def test_the_seven_readers_on_a_recorded_v5e_slice():
    events, mods = ts.load_slice(RECORDED)
    t = ts.reduce(events, mods)
    assert t["executions"] == 3
    assert sum(t["partition_ns"].values()) == t["busy_ns"]
    ctx = Ctx(_train_scopes=t)
    got = {name: read(name, ctx) for name in SCOPE_READERS}
    # one chip: no collective under step.grad_reduce
    assert got.pop("train_grad_reduce_ms_per_step") is None
    assert set(got) == set(STATED["readers"])
    for name, want in STATED["readers"].items():
        assert got[name] == pytest.approx(want, rel=0.01), name
    per = ts.per_step_table(t)
    assert per["busy_ms"] == pytest.approx(STATED["busy_ms"], rel=0.005)
    for scope, want in STATED["partition_ms"].items():
        assert per["partition_ms"].get(scope, 0.0) == pytest.approx(
            want, rel=0.02, abs=0.02), scope
