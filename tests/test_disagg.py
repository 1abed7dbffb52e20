"""Disaggregated serving + autoscaling specs (serving/pools.py,
router.py, autoscale.py): prefill and decode route
to their own role pools with the KV handoff riding crc-verified blobs
between them, a decode replica killed mid-stream retries on a
survivor within the remaining deadline budget with its pages freed,
decode-phase hedges are suppressed (and counted) by default, and the
autoscaler scales each pool on sustained signal breaches with
hysteresis + cooldown + drain-before-retire."""
import time

import numpy as np
import pytest

from bigdl_tpu import nn  # noqa: F401 — registry
from bigdl_tpu.models.generate import cached_generate
from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.resilience import faults
from bigdl_tpu.serving import (AutoscalePolicy, Autoscaler,
                               InferenceServer, KVPagePool,
                               ServingFleet, Status)
from bigdl_tpu.utils.rng import RNG

VOCAB, TMAX = 23, 32

#: one model for the whole module (1 layer, seed-deterministic
#: params): the paged decode programs are shared per (model,
#: page_size) across pools, so every fleet in this file reuses one
#: set of compiles
_MODELS = {}


def _model(**kw):
    key = tuple(sorted(kw.items()))
    if key not in _MODELS:
        RNG().set_seed(4)
        _MODELS[key] = TransformerLM(VOCAB, embed_dim=16, num_heads=2,
                                     mlp_dim=32, num_layers=1,
                                     max_len=TMAX, **kw)
    return _MODELS[key]


def _fleet(model, roles, deadline_s=30.0, hedge=False, **router_kw):
    router_kw.setdefault("disaggregate", True)
    return ServingFleet.build(
        model, n_replicas=len(roles), roles=roles,
        kv_pages=32, kv_page_size=4, server_kw=dict(max_batch=8),
        heartbeat_timeout=0.4, pump_interval_s=0.05,
        router_kw=dict(default_deadline_s=deadline_s, hedge=hedge,
                       **router_kw))


def _ref(model, prompt, max_new):
    gen = cached_generate(model)
    return np.asarray(gen(model.param_tree(), prompt[None],
                          max_new))[0, len(prompt):]


# ---------------------------------------------------------------------------
# disaggregated routing
# ---------------------------------------------------------------------------

# (4, 12): long decodes, four pages each of a window's eight
@pytest.mark.parametrize("prompt_len,max_new", [(5, 8), (4, 12)])
def test_disagg_generate_matches_reference_and_routes_by_role(
        prompt_len, max_new):
    model = _model()
    fl = _fleet(model, ("prefill", "decode", "decode"))
    fl.start()
    try:
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, VOCAB + 1,
                               (prompt_len,)).astype(np.int32)
                   for _ in range(4)]
        # all four in flight at once, each still exactly the unpaged one
        futs = [fl.submit_generate(p, max_new=max_new) for p in prompts]
        for p, fut in zip(prompts, futs):
            res = fut.result(120)
            assert res.ok, (res.status, res.error)
            np.testing.assert_array_equal(res.output,
                                          _ref(model, p, max_new))
        snap = fl.router.snapshot()
        assert snap["pools"]["prefill"] == ["r0"]
        assert snap["pools"]["decode"] == ["r1", "r2"]
        # phase dispatches landed in their own pools: r0 saw only
        # prefill work, decode work went to r1/r2
        assert fl.servers["r0"].metrics.counts["ok"] >= 4
        decode_ok = (fl.servers["r1"].metrics.counts["ok"]
                     + fl.servers["r2"].metrics.counts["ok"])
        assert decode_ok >= 4
        # the router recorded fleet-level TTFT (prefill landed before
        # the decode phase began)
        assert fl.router.metrics.snapshot()["ttft_p99_s"] is not None
    finally:
        fl.stop(15)
    for srv in fl.servers.values():
        assert srv.kv_pool.free_pages == srv.kv_pool.num_pages


def test_prefill_pool_gone_degrades_typed():
    model = _model()
    fl = _fleet(model, ("prefill", "decode"))
    fl.start()
    try:
        rng = np.random.RandomState(1)
        prompt = rng.randint(1, VOCAB + 1, (5,)).astype(np.int32)
        assert fl.submit_generate(prompt, max_new=4).result(120).ok
        fl.servers["r0"].drain(timeout=10)   # the only prefill replica
        fl.pump_once()
        res = fl.submit_generate(prompt, max_new=4,
                                 deadline_s=2.0).result(60)
        assert res.status in (Status.UNAVAILABLE,
                              Status.DEADLINE_EXCEEDED)
        assert res.error
    finally:
        fl.stop(15)


def test_decode_kill_mid_stream_retries_on_survivor():
    """The chaos bar: a decode-pool member dies mid-stream — its pages
    come back, the decode replays on the surviving decode replica from
    the retained handoff within the remaining budget, and the final
    stream is still exactly the reference."""
    model = _model()
    fl = _fleet(model, ("prefill", "decode", "decode"),
                deadline_s=60.0)
    fl.start()
    try:
        rng = np.random.RandomState(2)
        prompt = rng.randint(1, VOCAB + 1, (5,)).astype(np.int32)
        # warm both decode replicas (and the prefill) so the kill hits
        # decode work, not compiles
        assert fl.submit_generate(prompt, max_new=3).result(120).ok
        assert fl.submit_generate(prompt, max_new=3).result(120).ok

        killed_pool = fl.servers["r1"].kv_pool
        with faults.delay_replica("r1", 0.05, times=1 << 10):
            fut = fl.submit_generate(prompt, max_new=24)
            time.sleep(0.2)          # decode underway somewhere
            with faults.kill_replica("r1"):
                deadline = time.monotonic() + 15
                while fl.servers["r1"].healthy() \
                        and time.monotonic() < deadline:
                    time.sleep(0.02)
            res = fut.result(120)
        # r1 may or may not have been the chosen decode replica; in
        # either case the request resolves OK with the exact stream
        assert res.ok, (res.status, res.error)
        np.testing.assert_array_equal(res.output,
                                      _ref(model, prompt, 24))
        # the killed replica's pages were freed on cancel
        assert killed_pool.free_pages == killed_pool.num_pages
        # and every later request keeps resolving on the survivor
        res2 = fl.submit_generate(prompt, max_new=6).result(120)
        assert res2.ok
        np.testing.assert_array_equal(res2.output,
                                      _ref(model, prompt, 6))
    finally:
        fl.stop(15)


def test_decode_hedge_suppressed_by_default_and_counted():
    model = _model()
    fl = _fleet(model, ("prefill", "decode", "decode"), hedge=True,
                hedge_delay_s=0.02)
    fl.start()
    try:
        rng = np.random.RandomState(3)
        prompt = rng.randint(1, VOCAB + 1, (5,)).astype(np.int32)
        assert fl.submit_generate(prompt, max_new=4).result(120).ok
        suppressed0 = fl.router.metrics.hedges_suppressed
        # decode made slow: the hedge timer fires but the decode-phase
        # duplicate is refused and counted
        with faults.serving_step_latency(0.08, times=1 << 10):
            res = fl.submit_generate(prompt, max_new=6).result(120)
        assert res.ok
        assert fl.router.metrics.hedges_suppressed > suppressed0
    finally:
        fl.stop(15)


def test_hedge_decode_knob_enables_decode_hedging():
    model = _model()
    fl = _fleet(model, ("prefill", "decode", "decode"), hedge=True,
                hedge_delay_s=0.02, hedge_decode=True)
    fl.start()
    try:
        rng = np.random.RandomState(4)
        prompt = rng.randint(1, VOCAB + 1, (5,)).astype(np.int32)
        assert fl.submit_generate(prompt, max_new=4).result(120).ok
        before = fl.router.metrics.hedges_suppressed
        with faults.serving_step_latency(0.08, times=1 << 10):
            res = fl.submit_generate(prompt, max_new=6).result(120)
        assert res.ok
        # nothing suppressed: with the knob on, slow decodes hedge
        assert fl.router.metrics.hedges_suppressed == before
    finally:
        fl.stop(15)


def test_phase_metrics_in_fleet_snapshot_and_prometheus():
    model = _model()
    fl = _fleet(model, ("prefill", "decode"))
    fl.start()
    try:
        rng = np.random.RandomState(5)
        prompt = rng.randint(1, VOCAB + 1, (5,)).astype(np.int32)
        assert fl.submit_generate(prompt, max_new=6).result(120).ok
        pre = fl.servers["r0"].metrics.snapshot()
        dec = fl.servers["r1"].metrics.snapshot()
        assert pre["ttft_p99_s"] is not None       # prefill phase ran
        assert pre["prefill_p99_s"] is not None
        assert dec["tpot_p99_s"] is not None       # decode phase ran
        assert dec["decode_p99_s"] is not None
        assert dec["kv_pages_total"] == 32
        snap = fl.snapshot()
        merged = snap["metrics"]
        assert "bigdl_serving_phase_seconds" in merged
        phases = {s["labels"].get("phase")
                  for s in merged["bigdl_serving_phase_seconds"]
                  ["series"]}
        assert {"prefill", "decode"} <= phases
        text = fl.to_prometheus()
        assert "bigdl_serving_ttft_seconds" in text
        assert "bigdl_serving_tpot_seconds" in text
        assert "bigdl_serving_kv_pages_free" in text
    finally:
        fl.stop(15)


# ---------------------------------------------------------------------------
# autoscaler
# ---------------------------------------------------------------------------

def _factory(model):
    def make(rid, role):
        pool = KVPagePool.for_model(model, 32, page_size=4)
        return InferenceServer(model, name=rid, kv_pool=pool,
                               role=role, max_batch=8)
    return make


def test_autoscaler_sustained_breach_scales_up_with_hysteresis():
    model = _model()
    fl = _fleet(model, ("prefill", "decode"))
    fl.start()
    try:
        rng = np.random.RandomState(6)
        prompt = rng.randint(1, VOCAB + 1, (5,)).astype(np.int32)
        assert fl.submit_generate(prompt, max_new=4).result(120).ok
        fl.pump_once()
        asc = Autoscaler(fl, _factory(model),
                         policy=AutoscalePolicy(
                             min_replicas=1, max_replicas=3,
                             p99_high_s=1e-9, sustain=2,
                             cooldown_s=1000.0))
        assert asc.pools == ("decode", "prefill")
        # breach must SUSTAIN: the first evaluation acts on nothing
        assert asc.evaluate_once() == []
        taken = asc.evaluate_once()
        assert {d["direction"] for d in taken} == {"up"}
        assert asc.replica_counts() == {"decode": 2, "prefill": 2}
        # cooldown: still breaching, but no second action inside it
        assert asc.evaluate_once() == []
        assert asc.evaluate_once() == []
        # decisions are counted per pool/direction in the fleet view
        snap = fl.snapshot()
        fam = snap["metrics"]["bigdl_autoscale_decisions_total"]
        ups = {s["labels"]["pool"]: s["value"]
               for s in fam["series"] if s["labels"]["direction"] == "up"}
        assert ups == {"decode": 1.0, "prefill": 1.0}
        # the scaled-up fleet still serves exactly
        res = fl.submit_generate(prompt, max_new=6).result(120)
        assert res.ok
        np.testing.assert_array_equal(res.output,
                                      _ref(model, prompt, 6))
    finally:
        fl.stop(15)


def test_autoscaler_idle_scales_down_with_drain_and_bounds():
    model = _model()
    fl = _fleet(model, ("prefill", "decode", "decode"))
    fl.start()
    try:
        rng = np.random.RandomState(7)
        prompt = rng.randint(1, VOCAB + 1, (5,)).astype(np.int32)
        assert fl.submit_generate(prompt, max_new=4).result(120).ok
        fl.pump_once()
        asc = Autoscaler(fl, _factory(model),
                         policy=AutoscalePolicy(
                             min_replicas=1, max_replicas=3,
                             p99_high_s=1e9, queue_high=1 << 30,
                             p99_idle_s=1e9, idle_sustain=2,
                             cooldown_s=0.0))
        assert asc.evaluate_once() == []          # idle streak 1
        taken = asc.evaluate_once()               # idle streak 2: act
        downs = [d for d in taken if d["direction"] == "down"]
        assert downs
        # LIFO retire: r2 (newest decode) went first, drained
        assert any(d["replica"] == "r2" for d in downs)
        assert "r2" not in fl.servers
        assert "r2" not in fl.router.members
        # bounds: pools never fall below min_replicas
        for _ in range(6):
            asc.evaluate_once()
        assert asc.pool_size("decode") >= 1
        assert asc.pool_size("prefill") >= 1
        # the shrunken fleet still serves
        res = fl.submit_generate(prompt, max_new=6).result(120)
        assert res.ok
    finally:
        fl.stop(15)


def test_autoscaler_no_flap_under_alternating_noise():
    """One noisy breach sample between idle samples must produce NO
    action: hysteresis absorbs it."""
    model = _model()
    fl = _fleet(model, ("prefill", "decode"))
    fl.start()
    try:
        fl.pump_once()
        asc = Autoscaler(fl, _factory(model),
                         policy=AutoscalePolicy(
                             min_replicas=1, max_replicas=3,
                             p99_high_s=0.5, sustain=2,
                             p99_idle_s=1e-12, idle_sustain=2,
                             cooldown_s=0.0))
        st = asc._state["decode"]
        for i in range(6):
            # alternate: fake a breach streak reset by injecting
            # alternating signals through the real evaluator
            st.breach_streak = 1 if i % 2 == 0 else 0
            st.idle_streak = 1 if i % 2 == 1 else 0
            before = len(asc.decisions)
            asc.evaluate_once()
        # idle_sustain=2 could legitimately fire on consecutive idle
        # reads; what must NEVER happen is an up/down alternation
        dirs = [d["direction"] for d in asc.decisions]
        flips = sum(1 for a, b in zip(dirs, dirs[1:]) if a != b)
        assert flips <= 1
    finally:
        fl.stop(15)
