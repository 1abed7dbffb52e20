"""The eight per-layer metrics PR 24 added, read from the program's own
spans: on a CPU rehearsal each reader gives a value or None and never
raises; against a program that emits no spans each gives None; and
``train_idle_attributed_pct`` reads a recorded v5e slice that holds
``bigdl.*`` spans."""
import importlib
import os

import pytest

from conftest import HERE, run_command

NEW = ("train_driver_between_steps_ms", "train_data_wait_pct",
       "train_idle_attributed_pct", "train_state_roundtrip_s",
       "serve_batch_form_ms", "serve_host_between_batches_ms",
       "serve_idle_attributed_pct", "decode_sample_pct")


@pytest.mark.parametrize("cell,values,nothing_to_read", [
    ("tiny_train_1chip",
     {"train_driver_between_steps_ms", "train_data_wait_pct",
      "train_state_roundtrip_s"},
     {"train_idle_attributed_pct"}),          # no chip plane on the CPU
    ("tiny_train_dp4",
     {"train_driver_between_steps_ms", "train_data_wait_pct",
      "train_state_roundtrip_s"},
     {"train_idle_attributed_pct"}),
    ("tiny_serve_open",
     {"serve_batch_form_ms", "serve_host_between_batches_ms"},
     {"serve_idle_attributed_pct", "decode_sample_pct"}),
    ("tiny_serve_closed",
     {"serve_batch_form_ms", "serve_host_between_batches_ms"},
     {"serve_idle_attributed_pct", "decode_sample_pct"}),
])
def test_readers_on_a_cpu_rehearsal(overlay, cell, values, nothing_to_read):
    rc, obj, log = run_command(overlay, cell, trace=1)
    assert rc == 0, log
    got = set(obj["metrics"])
    assert values <= got, (sorted(got), log[-2000:])
    assert not nothing_to_read & got
    for name in values:
        assert obj["metrics"][name]["value"] >= 0
    if "train_data_wait_pct" in values:
        assert obj["metrics"]["train_data_wait_pct"]["value"] <= 100


class Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)


@pytest.mark.parametrize("name", NEW)
def test_no_trace_or_no_spans_reads_as_none(tmp_path, name, monkeypatch):
    """What the parent commit's program gives these readers: a run with
    no trace, a trace directory with no xplane, an xplane with no
    ``bigdl.*`` event, a tracer ring with no ``plan.*`` span — None,
    never an exception."""
    import jax
    import jax.numpy as jnp

    from benchmark.readers import _program_spans

    read = importlib.import_module(f"benchmark.readers.{name}").read
    monkeypatch.setattr(_program_spans, "ring", lambda: None)
    assert read(Ctx(run={}, trace_summary=None)) is None
    assert read(Ctx(run={"trace_path": str(tmp_path)},
                    trace_summary=None)) is None
    with jax.profiler.trace(str(tmp_path)):     # no program span inside
        with jax.profiler.TraceAnnotation("bench.window"):
            float(jnp.ones(4).sum())
    assert read(Ctx(run={"trace_path": str(tmp_path)},
                    trace_summary=None)) is None
    monkeypatch.setattr(_program_spans, "ring", lambda: [])
    assert read(Ctx(run={}, trace_summary=None)) is None


def test_idle_attribution_on_a_recorded_v5e_slice():
    """``recorded/v5e_train_spans_slice.json.gz``: the first second of a
    traced ``gpt2m_train_1chip`` run of PR 24 — chip 0's operation
    intervals and the program's ``bigdl.*`` host events."""
    from benchmark.readers import _program_spans as ps

    raw = ps.load_slice(os.path.join(HERE, "recorded",
                                     "v5e_train_spans_slice.json.gz"))
    spans = ps.build(raw)
    assert spans["driver"] is not None and spans["worker"] is None
    its = spans["driver"].named("train.iteration")
    assert len(its) >= 3 and [e[3]["step"] for e in its] == sorted(
        e[3]["step"] for e in its)
    assert spans["idle_ns"] > 0 and spans["holes"]
    by = ps.idle_by_span(spans, "driver")
    assert sum(by.values()) == spans["idle_ns"]
    # the gaps between steps fall into the driver's own spans, by name
    named = {k for k in by if k}
    assert named <= {"train.iteration", "train.data_wait",
                     "train.place_batch", "train.dispatch",
                     "train.loss_fetch", "train.bookkeeping",
                     "train.checkpoint", "train.validation"}
    pct = 100.0 * (spans["idle_ns"] - by.get(None, 0)) / spans["idle_ns"]
    assert 50.0 <= pct <= 100.0
    ctx = Ctx(_program_spans=spans)
    from benchmark.readers import train_idle_attributed_pct

    assert train_idle_attributed_pct.read(ctx) == pytest.approx(pct)
    # a gap's midpoint inside nothing is booked to None, not dropped
    spans["holes"].append((0, 10**6))
    spans["idle_ns"] += 10**6
    assert ps.idle_by_span(spans, "driver")[None] >= 10**6


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _ld(field, payload):      # a length-delimited field
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _vi(field, value):        # a varint field
    return _varint(field << 3) + _varint(value)


def test_op_names_from_the_xplane_wire_format(tmp_path):
    """``tf_op`` sits on the event METADATA, which ProfileData does not
    hand out: a hand-encoded XSpace with one device plane — a stat-name
    table, two event metadata (one by str_value, one by ref_value), a
    line to be skipped — reads back as {instruction text: op_name}."""
    from benchmark.readers import _xplane_opnames as x

    stat_meta = lambda sid, name: _ld(5, _vi(1, sid) + _ld(
        2, _vi(1, sid) + _ld(2, name.encode())))
    ev_meta = lambda eid, name, stats: _ld(4, _vi(1, eid) + _ld(
        2, _vi(1, eid) + _ld(2, name.encode()) + b"".join(
            _ld(5, s) for s in stats)))
    ref = "jit(_run)/while/body/generate.sample/sort"
    plane = (_vi(1, 7) + _ld(2, b"/device:TPU:0")
             + _ld(3, _vi(1, 1) + _ld(2, b"XLA Ops") + b"\x00" * 64)
             + stat_meta(11, "flops") + stat_meta(12, "tf_op")
             + stat_meta(13, ref)
             + ev_meta(1, "%fusion.1 = f32[8] fusion()", [
                 _vi(1, 11) + _vi(3, 2048),
                 _vi(1, 12) + _ld(5, b"jit(_run)/generate.prefill/dot")])
             + ev_meta(2, "%sort.4 = f32[8] sort()", [
                 _vi(1, 12) + _vi(7, 13)])
             + ev_meta(3, "%copy.9 = f32[8] copy()", [
                 _vi(1, 11) + _varint(11 << 3 | 1) + b"\x00" * 8]))
    other = _ld(2, b"/host:CPU") + ev_meta(1, "x", [
        _vi(1, 12) + _ld(5, b"not/this/plane")])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_ld(1, other) + _ld(1, plane))
    assert x.op_names(str(path)) == {
        "%fusion.1 = f32[8] fusion()": "jit(_run)/generate.prefill/dot",
        "%sort.4 = f32[8] sort()": ref}
    assert x.op_names(str(path), "/device:TPU:3") == {}
