"""Program spans (ISSUE 24): the plan driver loop, the plan engine, the
prefetcher, the serving worker and ``generate`` record where the work
happens, into ONE process tracer whose live spans also land in a
profiler session's xplane.  No wall-clock thresholds: every assertion
is about names, order, nesting and sums of the spans themselves."""
import ast
import glob
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.telemetry import (CATEGORIES, MetricsRegistry, Telemetry,
                                 Tracer, default_tracer,
                                 reset_default_tracer)
from bigdl_tpu.telemetry.tracer import PROGRAM_SPANS

HERE = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# the mechanism
# ---------------------------------------------------------------------------

def test_default_tracer_is_process_wide_and_telemetry_adopts_it():
    tr = default_tracer()
    assert default_tracer() is tr
    assert Telemetry(registry=MetricsRegistry()).tracer is tr
    own = Tracer()
    assert Telemetry(registry=MetricsRegistry(), tracer=own).tracer is own
    # on by default, bounded: a flight recorder nobody has to arm
    assert tr.enabled and tr.capacity == 8192
    fresh = reset_default_tracer()
    assert fresh is not tr and default_tracer() is fresh


class _NoLock:
    def __enter__(self):
        raise AssertionError("a disabled tracer took its lock")

    def __exit__(self, *a):
        return False


def test_disabled_tracer_takes_no_lock_and_keeps_nothing():
    tr = Tracer(enabled=False)
    tr._lock = _NoLock()
    a = tr.span("train.iteration", "step", step=1)
    b = tr.span("train.dispatch", "dispatch")
    assert a is b  # ONE shared object: nothing allocated per span
    with a as s:
        s.set(compiled=True)  # swallowed
    assert tr.record("admission_queue", "queue", 0.0, 1.0) is None
    assert not tr._done and not tr._stack()
    # the one switch works both ways, on a live tracer
    tr._lock = threading.Lock()
    tr.enabled = True
    with tr.span("train.iteration", "step"):
        pass
    assert [s.name for s in tr.spans()] == ["train.iteration"]


def test_without_a_profiler_session_the_ring_alone_records():
    tr = default_tracer()
    with tr.span("train.iteration", "step", step=7) as it:
        assert it._annotation is None  # no session: one flag check
        with tr.span("train.dispatch", "dispatch") as d:
            d.set(compiled=False)
    got = {s.name: s for s in tr.spans()}
    assert got["train.iteration"].args == {"step": 7}
    assert got["train.dispatch"].args == {"compiled": False}
    assert got["train.dispatch"].parent_id == got["train.iteration"].id


def _host_events(trace_dir):
    """{line index: [(event name, start, end, {stat: value})]} of the
    xplane's host plane (one line per thread; their names may collide)."""
    from jax.profiler import ProfileData

    pb = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True))[-1]
    out = {}
    for plane in ProfileData.from_file(pb).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            out[len(out)] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats)) for e in line.events]
    return out


def test_bridge_puts_live_spans_on_the_drivers_line_of_the_xplane(tmp_path):
    tr = default_tracer()
    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((8, 8))
    float(f(x))
    seen = []

    def other_thread():
        with tr.span("feed.produce", "other"):
            seen.append(threading.get_ident())

    with jax.profiler.trace(str(tmp_path)):
        for step in (1, 2):
            with tr.span("train.iteration", "step", step=step):
                with tr.span("train.dispatch", "dispatch") as d:
                    y = f(x)
                    d.set(compiled=False)
                with tr.span("train.loss_fetch", "device_wait"):
                    float(y)
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    lines = _host_events(str(tmp_path))
    driver = [n for n, evs in lines.items()
              if any(e[0] == "bigdl.train.iteration" for e in evs)]
    assert len(driver) == 1, sorted(lines)
    evs = lines[driver[0]]
    its = [e for e in evs if e[0] == "bigdl.train.iteration"]
    assert [e[3]["step"] for e in its] == [1, 2]
    # children on the same line, inside their parent, on its clock
    for name in ("bigdl.train.dispatch", "bigdl.train.loss_fetch"):
        kids = [e for e in evs if e[0] == name]
        assert len(kids) == 2
        for kid, it in zip(kids, its):
            assert it[1] <= kid[1] and kid[2] <= it[2]
    # what was only known once the span was open rides as a stat too
    assert [e[3]["compiled"] for e in evs
            if e[0] == "bigdl.train.dispatch"] == [0, 0]
    # another thread's span lands on another line
    producer = [n for n, es in lines.items()
                if any(e[0] == "bigdl.feed.produce" for e in es)]
    assert producer and producer != driver
    # and the ring holds the same spans, session or not
    names = [s.name for s in tr.spans()]
    assert names.count("train.iteration") == 2
    assert all(s._annotation is None for s in tr.spans())


def test_category_totals_count_self_time_for_any_parent():
    t = iter([0.0, 1.0, 2.0, 4.0, 5.0, 9.0])
    tr = Tracer(clock=lambda: next(t))
    with tr.span("serve.batch", "batch"):          # 0 .. 9
        with tr.span("serve.dispatch", "dispatch"):  # 1 .. 2
            pass
        with tr.span("serve.fetch", "device_wait"):  # 4 .. 5
            pass
    assert tr.category_totals() == {"batch": 7.0, "dispatch": 1.0,
                                    "device_wait": 1.0}


# ---------------------------------------------------------------------------
# the training driver
# ---------------------------------------------------------------------------

def _regression(n=256):
    from bigdl_tpu.dataset import Sample

    rng = np.random.RandomState(0)
    x = rng.rand(n, 4).astype(np.float32)
    y = (x @ np.array([[1.5], [-2.0], [0.5], [3.0]], np.float32) + 0.7)
    return [Sample(x[i], y[i].astype(np.float32)) for i in range(n)]


def _local_optimizer(steps, **kw):
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import array
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.optimizer import LocalOptimizer

    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = LocalOptimizer(model, array(_regression()), nn.MSECriterion(),
                         batch_size=32, **kw)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_iteration(steps))
    return opt


def _children(spans, parent):
    return [s for s in spans if s.parent_id == parent.id]


def test_plain_local_optimizer_records_its_span_tree():
    """No set_telemetry, no sink: a plain optimizer is traced."""
    opt = _local_optimizer(8)
    opt.optimize()
    spans = default_tracer().spans()
    driver = threading.get_ident()
    root = [s for s in spans if s.name == "train.optimize"]
    assert len(root) == 1 and root[0].args["engine_cache_hit"] is False
    its = [s for s in spans if s.name == "train.iteration"]
    assert [s.args["step"] for s in its] == list(range(1, 9))
    assert all(s.parent_id == root[0].id and s.tid == driver for s in its)
    # the order of an iteration (ISSUE 35): the dispatch comes first —
    # its inputs were staged while the step before ran; behind it, while
    # the device is busy, the report of the step before and the staging
    # of the step after (its data wait inside); then the loss and the
    # short commit.  An entry's first step stages itself, and the eighth
    # batch is the epoch's last: nothing is staged beside it, and as the
    # run ends there its own report closes its iteration
    report, stage = "train.report", "train.stage"
    tail = ["train.loss_fetch", "train.bookkeeping"]
    want = ([[stage, "train.dispatch", stage] + tail]
            + [["train.dispatch", report, stage] + tail] * 6
            + [["train.dispatch", report] + tail + [report]])
    covered = total = 0.0
    for it, names in zip(its, want):
        kids = sorted(_children(spans, it), key=lambda s: s.start)
        assert [k.name for k in kids] == names
        # in place: inside the parent, one after another
        edges = [it.start]
        for k in kids:
            edges += [k.start, k.end]
        edges.append(it.end)
        assert edges == sorted(edges)
        covered += sum(k.duration for k in kids)
        total += it.duration
        for k in kids:  # a staging holds its data wait, in place
            if k.name == stage:
                inner = _children(spans, k)
                assert [c.name for c in inner] == ["train.data_wait"]
                assert k.start <= inner[0].start <= inner[0].end <= k.end
    # the span tree accounts for the driver's time: staging included
    assert covered >= 0.95 * total
    disp = [s for s in spans if s.name == "train.dispatch"]
    assert [s.args["compiled"] for s in disp] == [True] + [False] * 7
    assert [s.args["staged"] for s in disp] == [False] + [True] * 7
    assert disp[0].category == "compile"
    assert {s.category for s in disp[1:]} == {"dispatch"}
    waits = [s for s in spans if s.name == "train.data_wait"]
    assert all(isinstance(s.args["hit"], bool) for s in waits)
    # only table names, each under its table category
    for s in spans:
        assert s.name in PROGRAM_SPANS, s.name
        assert s.category in (PROGRAM_SPANS[s.name], "compile")


def _order_optimizer(kind, steps):
    """Four batches of 64 an epoch, on one device or the 8-device mesh."""
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import array
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.optim.optimizer import LocalOptimizer

    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    cls = LocalOptimizer if kind == "local" else DistriOptimizer
    opt = cls(model, array(_regression()), nn.MSECriterion(), batch_size=64)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_iteration(steps))
    return opt


@pytest.mark.parametrize("kind", ["local", "distri"])
def test_between_a_loss_and_the_next_dispatch_stands_only_the_decision(kind):
    """ISSUE 35: the feed, the placement and the reporting leave the
    gap in which the device waits for the driver — read off the ring."""
    from bigdl_tpu.optim import max_iteration
    from bigdl_tpu.telemetry import default_registry

    counter = "bigdl_train_steps_staged_total"
    fam = default_registry().get(counter)
    before = fam.labels().value if fam else 0.0
    opt = _order_optimizer(kind, 7)
    opt.reuse_compiled_engine = True
    opt.optimize()
    opt.set_end_when(max_iteration(10))
    opt.optimize()  # a second entry: it begins its epoch again
    spans = sorted((s for s in default_tracer().spans()
                    if s.tid == threading.get_ident()),
                   key=lambda s: s.start)
    entries = [s for s in spans if s.name == "train.optimize"]
    assert len(entries) == 2
    kept_out = {"train.stage", "train.data_wait", "train.place_batch",
                "train.report"}
    gaps = 0
    for entry in entries:
        inside = [s for s in spans
                  if entry.start <= s.start and s.end <= entry.end]
        dispatches = [s for s in inside if s.name == "train.dispatch"]
        for fetch in (s for s in inside if s.name == "train.loss_fetch"):
            nxt = next((d for d in dispatches if d.start >= fetch.end), None)
            if nxt is None:
                continue  # the entry's last step
            between = [s.name for s in inside
                       if fetch.end <= s.start < nxt.start]
            if nxt.args["staged"]:
                assert not kept_out & set(between), between
                gaps += 1
            # what does stand there: the commit, and what it decides
            assert "train.bookkeeping" in between
    disp = [s for s in spans if s.name == "train.dispatch"]
    # unstaged: an entry's first step (1, 8) and an epoch's first (5)
    assert [s.args["staged"] for s in disp] == [
        False, True, True, True, False, True, True, False, True, True]
    assert gaps == 7
    assert default_registry().get(counter).labels().value - before == 7
    if kind == "distri":  # staged with the rest, not left in the gap
        assert len([s for s in spans
                    if s.name == "train.place_batch"]) >= 10
    # every step is reported once, the last of an entry before it returns
    reports = [s for s in spans if s.name == "train.report"]
    assert [s.args["step"] for s in reports] == list(range(1, 11))
    assert entries[0].start < reports[6].end <= entries[0].end


@pytest.mark.parametrize("where", ["end", "validation"])
def test_the_last_step_is_reported_when_a_trigger_raises(where):
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import array
    from bigdl_tpu.optim import Loss

    class Boom(Exception):
        pass

    def trigger(state):
        if state["neval"] > 3:  # with the third step's loss in the table
            raise Boom
        return False

    opt = _local_optimizer(8)
    if where == "end":
        opt.set_end_when(trigger)
    else:  # inside the commit, where the step's state is still its own
        opt.set_validation(trigger, array(_regression(64)),
                           [Loss(nn.MSECriterion())], batch_size=32)
    with pytest.raises(Boom):
        opt.optimize()
    spans = default_tracer().spans()
    assert [s.args["step"] for s in spans
            if s.name == "train.report"] == [1, 2, 3]
    assert len([s for s in spans if s.name == "train.dispatch"]) == 3


def test_plan_engine_state_spans_bracket_the_loop():
    opt = _local_optimizer(3)
    opt.reuse_compiled_engine = True
    opt.optimize()
    from bigdl_tpu.optim import max_iteration

    opt.set_end_when(max_iteration(5))
    opt.optimize()  # re-entry: the cached engine, no new build
    spans = default_tracer().spans()
    roots = [s for s in spans if s.name == "train.optimize"]
    assert [r.args["engine_cache_hit"] for r in roots] == [False, True]
    for root in roots:
        kids = sorted(_children(spans, root), key=lambda s: s.start)
        names = [k.name for k in kids]
        assert names[0] == "plan.init_state"
        assert names[-1] == "plan.sync_to_model"
        assert set(names[1:-1]) == {"train.iteration"}
        assert kids[0].category == kids[-1].category == "state_sync"
    disp = [s for s in spans if s.name == "train.dispatch"]
    assert [s.args["compiled"] for s in disp] == [True] + [False] * 4


def test_checkpoint_and_validation_keep_their_own_child_spans(tmp_path):
    from bigdl_tpu.optim import several_iteration

    opt = _local_optimizer(4)
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(2))
    opt.optimize()
    spans = default_tracer().spans()
    by_id = {s.id: s for s in spans}
    cks = [s for s in spans if s.name == "train.checkpoint"]
    assert len(cks) == 2
    for ck in cks:
        book = by_id[ck.parent_id]
        assert book.name == "train.bookkeeping"
        assert book.start <= ck.start and ck.end <= book.end
        assert by_id[book.parent_id].args["step"] in (2, 4)


def test_a_disabled_tracer_leaves_a_traced_multi_device_run_working():
    """The null span has no clock readings: the driver, the telemetry
    hooks (profiled split included, iteration 10) and the feed must not
    ask it for any."""
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import array
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer

    default_tracer().enabled = False
    tm = Telemetry(registry=MetricsRegistry())
    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = DistriOptimizer(model, array(_regression()), nn.MSECriterion(),
                          batch_size=64)
    opt.set_optim_method(SGD(learning_rate=0.2))
    opt.set_end_when(max_iteration(12))
    opt.set_telemetry(tm)
    opt.optimize()
    assert tm.steps.value == 12 and tm.h2d_seconds.count == 12
    assert default_tracer().spans() == []


def test_feed_spans_are_the_producer_threads():
    from bigdl_tpu.dataset.prefetch import DevicePrefetcher

    gate = threading.Event()

    def batches():
        for i in range(6):
            yield i
        gate.wait(10)

    feed = DevicePrefetcher(batches(), depth=1)
    try:
        # depth 1, six batches, nobody consuming: the producer makes
        # two and then sits on a full queue
        got = [feed.get()[0][0] for _ in range(3)]
        assert got == [0, 1, 2]
    finally:
        gate.set()
        feed.close()
    spans = default_tracer().spans()
    produce = [s for s in spans if s.name == "feed.produce"]
    blocked = [s for s in spans if s.name == "feed.blocked"]
    assert len(produce) >= 3 and blocked
    tids = {s.tid for s in produce + blocked}
    assert len(tids) == 1 and threading.get_ident() not in tids
    assert {s.category for s in blocked} == {"idle"}


def test_telemetry_hooks_place_no_span_of_their_own():
    tm = Telemetry(registry=MetricsRegistry())
    tm.on_data_wait(0.5, step=1)
    tm.on_host_to_device(0.25, step=1)
    tm.on_step(1.0, records=8, step=1)
    tm.on_checkpoint(0.1, step=1)
    tm.on_checkpoint_blocked(0.1, step=1)
    assert tm.tracer.spans() == []
    # their histogram and ledger halves stay
    assert tm.data_wait_seconds.count == 1 and tm.steps.value == 1
    assert tm.ledger.snapshot()["seconds"]["data_stall"] == 0.75


# ---------------------------------------------------------------------------
# the serving worker
# ---------------------------------------------------------------------------

@pytest.fixture()
def served():
    """A tiny LM behind a plain server (no sink, no trace context):
    two waves of generate requests -> (results, spans)."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import InferenceServer
    from bigdl_tpu.utils.rng import RNG

    RNG().set_seed(2)
    lm = TransformerLM(61, embed_dim=16, num_heads=2, num_layers=1,
                       max_len=32, output="logits")
    srv = InferenceServer(lm, max_batch=4, batch_window_s=0.05).start()
    rng = np.random.RandomState(0)
    results = []
    try:
        for wave in range(2):
            futs = [srv.submit_generate(
                rng.randint(1, 62, 6).astype(np.int32), max_new=4)
                for _ in range(4)]
            results += [f.result(timeout=120) for f in futs]
    finally:
        srv.stop(timeout=10)
    return results, default_tracer().spans()


def test_every_ok_request_has_its_three_records(served):
    results, spans = served
    assert len(results) == 8 and all(r.ok for r in results)
    batches = {s.args["batch_id"]: s for s in spans
               if s.name == "serve.batch"}
    by_req = {}
    for s in spans:
        if s.args and "request_id" in s.args:
            by_req.setdefault(s.args["request_id"], []).append(s)
    assert len(by_req) == 8
    latency = sorted(r.latency_s for r in results)
    queued = sorted(r.queued_s for r in results)
    sums, waits = [], []
    for rid, recs in by_req.items():
        recs.sort(key=lambda s: s.start)
        assert [s.name for s in recs] == [
            "admission_queue", "batch_wait", "execute:generate"]
        assert [s.category for s in recs] == ["queue", "batch", "execute"]
        # submitted -> dequeued -> batch began -> done, without a hole
        assert recs[0].end == pytest.approx(recs[1].start, abs=1e-9)
        assert recs[1].end == pytest.approx(recs[2].start, abs=1e-9)
        bid = {s.args["batch_id"] for s in recs}
        assert len(bid) == 1 and bid <= set(batches)
        batch = batches[bid.pop()]
        assert batch.start <= recs[2].start and recs[2].end <= batch.end
        sums.append(sum(s.duration for s in recs))
        waits.append(recs[0].duration + recs[1].duration)
    # request ids are the server's own, so pair by rank: the three
    # records never exceed the latency the caller was told ...
    for got, want in zip(sorted(sums), latency):
        assert got <= want + 1e-9
    # ... and the two waits ARE ServeResult.queued_s
    for got, want in zip(sorted(waits), queued):
        assert got == pytest.approx(want, abs=1e-3)


def test_worker_spans_nest_under_their_batch(served):
    _, spans = served
    batches = sorted((s for s in spans if s.name == "serve.batch"),
                     key=lambda s: s.start)
    assert batches and sum(b.args["rows"] for b in batches) == 8
    worker = {b.tid for b in batches}
    assert len(worker) == 1 and threading.get_ident() not in worker
    seen_compiled = []
    for b in batches:
        assert b.args["kind"] == "generate" and b.args["bucket"] >= \
            b.args["rows"]
        kids = sorted(_children(spans, b), key=lambda s: s.start)
        assert [k.name for k in kids] == [
            "serve.batch_form", "serve.dispatch", "serve.fetch",
            "serve.resolve"]
        edges = [b.start]
        for k in kids:
            edges += [k.start, k.end]
        edges.append(b.end)
        assert edges == sorted(edges)
        seen_compiled.append((b.args["bucket"], kids[1].args["compiled"],
                              kids[1].category))
    # a signature builds once: compile the first time, dispatch after
    first = {}
    for bucket, compiled, cat in seen_compiled:
        assert compiled is (bucket not in first)
        assert cat == ("compile" if compiled else "dispatch")
        first[bucket] = True
    gathers = [s for s in spans if s.name == "serve.gather"]
    assert sum(g.args["n"] for g in gathers) == 8
    assert any(s.name == "serve.idle" and s.category == "idle"
               for s in spans)
    assert {s.tid for s in gathers} == worker


def test_an_idle_worker_shows_in_a_session_that_started_after_it(tmp_path):
    """The worker's ``serve.idle`` was open before the session began,
    so it is not in the xplane; the worker notices the session at its
    next poll and renews the span, and the quiet stretch is explained."""
    import time

    from bigdl_tpu import nn
    from bigdl_tpu.serving import InferenceServer

    srv = InferenceServer(nn.Sequential(nn.Linear(4, 2)), max_batch=2)
    srv.start()
    try:
        time.sleep(0.1)  # several empty polls, no session
        with jax.profiler.trace(str(tmp_path)):
            time.sleep(0.3)  # ~15 polls of 20 ms, then a request ends
            #                  the idle stretch inside the session
            assert srv.submit(np.ones(4, np.float32)).result(120).ok
    finally:
        srv.stop(timeout=10)
    idle = [e for evs in _host_events(str(tmp_path)).values() for e in evs
            if e[0] == "bigdl.serve.idle"]
    assert idle, "no serve.idle in a session that began on an idle worker"


def test_sink_and_ring_both_get_a_traced_requests_records():
    """``_trace`` writes to the process tracer always and to the fleet
    sink when the request carries a context: the stitched fragment
    keeps the two names it always had."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import InferenceServer
    from bigdl_tpu.serving.request_trace import ReplicaTraceSink
    from bigdl_tpu.telemetry import TraceContext

    lm = TransformerLM(61, embed_dim=16, num_heads=2, num_layers=1,
                       max_len=32, output="logits")
    sink = ReplicaTraceSink("r0", eager_publish=False)
    srv = InferenceServer(lm, max_batch=2, trace_sink=sink).start()
    try:
        ctx = TraceContext.mint()
        r = srv.submit_generate(np.arange(1, 7, dtype=np.int32), max_new=2,
                                trace=ctx).result(timeout=120)
        assert r.ok
    finally:
        srv.stop(timeout=10)
        sink.close()
    frag = sink.fragment(ctx.trace_id)
    names = {sp["name"] for sp in frag["spans"]}
    assert {"admission_queue", "execute:generate"} <= names
    ring = {s.name for s in default_tracer().spans()}
    assert {"admission_queue", "batch_wait", "execute:generate",
            "serve.batch"} <= ring


def _stub_request(rid, trace=None):
    from bigdl_tpu.serving.status import Request, ServeFuture

    return Request("generate", None, ServeFuture(), submitted_at=1.0,
                   trace=trace, request_id=rid, dequeued_at=1.25)


def test_per_request_ring_records_are_owed_until_the_flush():
    """Between one batch's fetch and the next one's dispatch the chip
    waits on the worker, so the three records a request are not written
    there: ``_owe_records`` keeps the batch, ``_flush_owed`` writes
    them (a failed batch owes its two waits only)."""
    from bigdl_tpu import nn
    from bigdl_tpu.serving import InferenceServer

    srv = InferenceServer(nn.Sequential(nn.Linear(4, 2)), max_batch=2)
    reqs = [_stub_request(7), _stub_request(8)]
    srv._owe_records("generate", reqs, 1.5, 2.5, batch_id=3, bucket=2)
    srv._owe_records("generate", [_stub_request(9)], 2.6, None,
                     batch_id=4, bucket=None)
    assert default_tracer().spans() == [] and len(srv._owed) == 2
    srv._flush_owed()
    assert srv._owed == []
    got = [(s.name, s.args["request_id"], s.args["batch_id"],
            round(s.start, 6), round(s.duration, 6))
           for s in default_tracer().spans()]
    assert got == [
        ("admission_queue", 7, 3, 1.0, 0.25), ("batch_wait", 7, 3, 1.25, 0.25),
        ("execute:generate", 7, 3, 1.5, 1.0),
        ("admission_queue", 8, 3, 1.0, 0.25), ("batch_wait", 8, 3, 1.25, 0.25),
        ("execute:generate", 8, 3, 1.5, 1.0),
        ("admission_queue", 9, 4, 1.0, 0.25), ("batch_wait", 9, 4, 1.25, 1.35)]
    srv._flush_owed()  # nothing owed: nothing written
    assert len(default_tracer().spans()) == 8
    # a disabled ring owes nothing (no request is kept alive for it)
    default_tracer().enabled = False
    srv._owe_records("generate", reqs, 1.5, 2.5, batch_id=5, bucket=2)
    assert srv._owed == []


def test_a_traced_requests_sink_records_are_not_owed():
    """The fleet fragment is published when the request resolves, so a
    request with a trace context gets its records into the sink at
    once; only the ring's wait for the flush."""
    from bigdl_tpu import nn
    from bigdl_tpu.serving import InferenceServer
    from bigdl_tpu.serving.request_trace import ReplicaTraceSink
    from bigdl_tpu.telemetry import TraceContext

    sink = ReplicaTraceSink("r0", eager_publish=False)
    srv = InferenceServer(nn.Sequential(nn.Linear(4, 2)), max_batch=2,
                          trace_sink=sink)
    ctx = TraceContext.mint()
    srv._owe_records("generate", [_stub_request(1, trace=ctx),
                                  _stub_request(2)], 1.5, 2.5, 1, 2)
    assert default_tracer().spans() == []
    sink.finish(ctx)
    names = [sp["name"] for sp in sink.fragment(ctx.trace_id)["spans"]]
    sink.close()
    assert sorted(names) == ["admission_queue", "batch_wait",
                             "execute:generate"]
    srv._flush_owed()
    assert len(default_tracer().spans()) == 6


def test_an_idle_poll_pays_what_the_worker_owes():
    """With no next batch to hide behind, the records of the last one
    are written at the worker's first empty poll — after the
    ``serve.idle`` that follows the batch was opened, not inside
    ``serve.resolve`` — so a dump of a quiet server misses nothing."""
    import time

    from bigdl_tpu import nn
    from bigdl_tpu.serving import InferenceServer

    srv = InferenceServer(nn.Sequential(nn.Linear(4, 2)), max_batch=2)
    srv.start()
    try:
        assert srv.submit(np.ones(4, np.float32)).result(120).ok
        deadline = time.monotonic() + 60
        while not any(s.name == "execute:classify"
                      for s in default_tracer().spans()):
            assert time.monotonic() < deadline, "records never written"
            time.sleep(0.01)
    finally:
        srv.stop(timeout=10)
    spans = default_tracer().spans()
    resolve = next(s for s in spans if s.name == "serve.resolve")
    idle_after = min(s.id for s in spans
                     if s.name == "serve.idle" and s.id > resolve.id)
    records = [s for s in spans if s.args and "request_id" in s.args]
    assert [s.name for s in records] == [
        "admission_queue", "batch_wait", "execute:classify"]
    assert all(s.id > idle_after for s in records)


# ---------------------------------------------------------------------------
# generate: device scopes are metadata only
# ---------------------------------------------------------------------------

def _tiny_lm():
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils.rng import RNG

    RNG().set_seed(4)
    return TransformerLM(23, embed_dim=16, num_heads=2, mlp_dim=32,
                         num_layers=2, max_len=24)


def _gen_cases(model):
    from bigdl_tpu.models.generate import make_generate

    prompt = np.random.RandomState(0).randint(1, 24, (2, 5)).astype(
        np.int32)
    p = model.param_tree()
    return {
        "greedy": lambda: make_generate(model)(p, prompt, max_new=7),
        "sampled": lambda: make_generate(model)(
            p, prompt, max_new=7, rng=jax.random.PRNGKey(3),
            temperature=0.8, top_k=5, top_p=0.9),
        "greedy_bf16": lambda: make_generate(
            model, compute_dtype=jnp.bfloat16)(p, prompt, max_new=7),
    }


@pytest.mark.parametrize("case", ["greedy", "sampled", "greedy_bf16"])
def test_generate_ids_are_bitwise_the_parent_commits(case):
    """tests/fixtures/generate_pr23_ids.json holds what the parent
    commit (PR 23, no scopes) gave for the same model, prompt and key."""
    with open(os.path.join(HERE, "fixtures",
                           "generate_pr23_ids.json")) as f:
        want = json.load(f)[case]
    got = np.asarray(_gen_cases(_tiny_lm())[case]())
    np.testing.assert_array_equal(got, np.asarray(want))


@pytest.mark.parametrize("temperature,top_p,greedy,nucleus", [
    (0.0, 1.0, True, False), (0.8, 0.9, False, True)],
    ids=["greedy", "nucleus"])
def test_generate_scopes_name_the_lowered_operations(
        temperature, top_p, greedy, nucleus):
    from bigdl_tpu.models.generate import make_generate

    model = _tiny_lm()
    gen = make_generate(model, compute_dtype=jnp.bfloat16)
    run = next(c.cell_contents for c in gen.__closure__
               if hasattr(c.cell_contents, "lower"))
    prompt = jnp.ones((2, 5), jnp.int32)
    text = run.lower(model.param_tree(), prompt, 4, jax.random.PRNGKey(0),
                     jnp.float32(temperature), 0, jnp.float32(top_p),
                     jnp.int32(0), jnp.int32(0), greedy,
                     nucleus).as_text(debug_info=True)
    for scope in ("generate.cast_params", "generate.prefill",
                  "generate.decode_step", "generate.sample"):
        assert scope in text, scope
    # both calls of _sample: the one after the prefill and the one in
    # the scan body
    assert "generate.sample/" in text or 'generate.sample"' in text
    assert text.count("while/body") > 0 or "while" in text


# ---------------------------------------------------------------------------
# lint: span names are the table's, categories the shared vocabulary's
# ---------------------------------------------------------------------------

_PREFIXES = ("train.", "plan.", "feed.", "serve.")


def _literals(node):
    """String constants an expression can evaluate to (both arms of a
    conditional)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, ast.IfExp):
        return _literals(node.body) + _literals(node.orelse)
    return []


def test_category_lint_covers_the_program_span_names():
    """Every ``.span("<prefix>...", <category>)`` call in bigdl_tpu/
    names a span of ``PROGRAM_SPANS`` under its table category (or
    ``compile``, for a dispatch that builds) — the regex lint of
    test_determinism.py reads single-line calls only; this one reads
    the syntax tree, so the multi-line and conditional ones count."""
    assert set(PROGRAM_SPANS.values()) <= set(CATEGORIES)
    pkg = os.path.join(HERE, "..", "bigdl_tpu")
    seen, offenders = set(), []
    for path in glob.glob(os.path.join(pkg, "**", "*.py"), recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "span" and node.args):
                continue
            names = _literals(node.args[0])
            if not names or not names[0].startswith(_PREFIXES):
                continue
            where = f"{os.path.relpath(path, pkg)}:{node.lineno}"
            cats = _literals(node.args[1]) if len(node.args) > 1 else []
            for name in names:
                seen.add(name)
                if name not in PROGRAM_SPANS:
                    offenders.append(f"{where}: {name!r} not in "
                                     "PROGRAM_SPANS")
                elif not cats or not set(cats) <= {PROGRAM_SPANS[name],
                                                   "compile"}:
                    offenders.append(f"{where}: {name!r} under {cats}, "
                                     f"table says {PROGRAM_SPANS[name]!r}")
    assert not offenders, "\n".join(offenders)
    # and nothing in the table is a name no code emits
    assert seen == set(PROGRAM_SPANS)
