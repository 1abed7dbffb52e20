"""bench.py contract guards — the device battery prints ONE JSON line
from the backend it finds and never falls back; the cheap pieces and
every host-side leg's measurement function are unit-tested here at
small sizes."""
import subprocess
import sys

import numpy as np


def _bench():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(__file__), "..", "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_peak_flops_lookup():
    bench = _bench()
    assert bench.peak_flops_per_sec("TPU v5 lite") == 197e12
    assert bench.peak_flops_per_sec("TPU v4") == 275e12
    assert bench.peak_flops_per_sec("weird accelerator") is None


def test_bench_model_runs_and_counts_steps():
    bench = _bench()
    from bigdl_tpu import nn
    from bigdl_tpu.models.lenet import LeNet5

    rng = np.random.RandomState(0)
    x = rng.rand(32, 784).astype(np.float32)
    y = rng.randint(1, 11, 32).astype(np.float32)
    r1, c1 = bench.bench_model(LeNet5(10), nn.ClassNLLCriterion(), x, y,
                               iters=4, warmup=1)
    assert r1 > 0
    # XLA cost-model StepCost of the exact timed program (AOT path
    # carries the memory analysis too)
    assert c1 is not None and c1.flops > 0 and c1.bytes_accessed > 0
    # K-step chaining path compiles and reports records*K throughput;
    # per-step cost now comes from lowering the SINGLE-step program
    # (the r5 "unrecoverable from a loop" limitation is gone)
    r2, c2 = bench.bench_model(LeNet5(10), nn.ClassNLLCriterion(), x, y,
                               iters=4, warmup=1, steps_per_dispatch=2)
    assert r2 > 0
    assert c2 is not None and c2.flops > 0
    # same per-step math either way — the compiled (post-optimization)
    # count runs a little above the as-written lowered count (layout
    # rewrites), ~10% on LeNet; same order, not same op set
    assert abs(c2.flops - c1.flops) / c1.flops < 0.2


def test_device_battery_refuses_to_fall_back():
    """No accelerator and no ``JAX_PLATFORMS=cpu``: the device battery
    exits non-zero and prints no JSON line — no CPU worker steps in."""
    import os

    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run([sys.executable, "bench.py"], capture_output=True,
                         text=True, timeout=240, cwd=".", env=env)
    assert out.returncode != 0, out.stdout
    assert "no accelerator" in out.stderr
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]


def test_bench_import_touches_no_backend():
    """Importing bench.py (what every leg's parent process does) must
    not initialise a jax backend: one process per chip."""
    code = ("import importlib.util, jax;"
            "s = importlib.util.spec_from_file_location('b', 'bench.py');"
            "m = importlib.util.module_from_spec(s); s.loader.exec_module(m);"
            "import bigdl_tpu;"
            "from jax._src import xla_bridge;"
            "assert not xla_bridge._backends, xla_bridge._backends")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=".")
    assert out.returncode == 0, out.stderr


def test_serving_measurements_contract():
    """The serving leg's measurement dict carries the judged fields
    (p50/p99 + shed rates + typed totals) and drains clean — run tiny
    in-process so tier-1 stays fast; the full leg is `--serving`."""
    bench = _bench()
    out = bench._serving_measurements(rate_rps=200.0, duration_s=0.5,
                                      burst=48, max_batch=8,
                                      max_queue=16)
    assert out["steady"]["offered"] > 0
    assert out["steady"]["ok"] > 0
    assert out["steady"]["latency_p50_ms"] is not None
    assert out["steady"]["latency_p99_ms"] >= out["steady"][
        "latency_p50_ms"]
    # the burst (3x the queue bound) must shed typed, not queue forever
    assert out["burst"]["shed"] > 0
    assert out["burst"]["ok"] + out["burst"]["shed"] == out["burst"][
        "offered"]
    assert out["drained_clean"] is True
    t = out["totals"]
    assert t["total"] == t["served_ok"] + t["shed"] \
        + t["deadline_exceeded"] + t["internal_error"]


def test_fleet_measurements_contract():
    """The fleet leg's measurement dict carries the judged fields
    (p99 with/without hedging, shed rate, goodput-per-chip, replica-
    kill recovery seconds, every request typed) — run tiny in-process
    so tier-1 stays fast; the full leg is `--fleet` and its one JSON
    line lands in SERVING_r02.json."""
    bench = _bench()
    out = bench._fleet_measurements(rate_rps=150.0, duration_s=0.6,
                                    users=32, max_batch=8,
                                    max_queue=32)
    assert out["n_replicas"] == 4
    assert out["steady"]["offered"] > 0
    assert out["steady"]["ok"] > 0
    assert out["p99_ms"] is not None
    assert out["p99_ms"] >= out["steady"]["latency_p50_ms"]
    assert out["hedged"]["offered"] > 0
    assert out["hedged_p99_ms"] is not None
    assert out["hedged"]["hedges_fired"] >= 0
    assert out["hedged"]["hedges_won"] <= out["hedged"]["hedges_fired"]
    assert 0.0 <= out["shed_rate"] <= 1.0
    # the killed replica was ejected and the fleet recovered, bounded
    assert out["kill"]["ejected"] is True
    assert out["recovery_s"] is not None
    assert 0 < out["recovery_s"] < 30
    # zero requests lost beyond the shed budget: everything typed
    assert out["all_resolved_typed"] is True
    # goodput-per-chip is measured (XLA cost model works on CPU too)
    assert out["goodput_per_chip_flops"] > 0
    # and the record flattens into the schema-stable ledger fields
    rec = bench.ledger_record({"fleet": {
        "p99_ms": out["p99_ms"], "hedged_p99_ms": out["hedged_p99_ms"],
        "shed_rate": out["shed_rate"],
        "goodput_per_chip_flops": out["goodput_per_chip_flops"],
        "recovery_s": out["recovery_s"]}})
    assert rec["fleet_p99_ms"] == out["p99_ms"]
    assert rec["fleet_shed_rate"] == out["shed_rate"]
    assert rec["fleet_goodput_per_chip"] == \
        out["goodput_per_chip_flops"]
    assert rec["fleet_recovery_s"] == out["recovery_s"]
    for key in bench.LEDGER_FIELDS:
        assert key in rec


def test_disagg_measurements_contract():
    """The disagg leg's measurement dict carries the judged fields
    (paged-vs-static concurrency multiple with exact outputs, TTFT/
    TPOT percentiles, the autoscaler timeline/decisions with flip
    accounting, shed no worse than the fixed fleet) — run tiny
    in-process so tier-1 stays fast; the full leg is `--disagg` and
    its one JSON line lands in SERVING_r03.json."""
    bench = _bench()
    out = bench._disagg_measurements(
        phase_s=0.5, low_rps=2.0, high_rps=8.0, users=8,
        max_new=4, long_prompt=4, long_new=12, t_max=32,
        page_size=4, eval_interval_s=0.2, cooldown_s=0.4,
        deadline_s=20.0, layers=1)
    # paged-vs-static at equal arena bytes: >= 2x concurrent long
    # decodes, every stream exactly the unpaged reference, no leaks
    c = out["concurrency"]
    assert c["static_max_long_decodes"] >= 1
    assert c["paged_concurrency_x"] >= 2.0
    assert c["paged_outputs_exact"] is True
    assert c["pool_leak_free"] is True
    # every pass resolves everything typed
    for key in ("static_pass", "paged_pass", "autoscale_pass"):
        assert out[key]["total"]["all_resolved_typed"] is True
        assert out[key]["total"]["offered"] > 0
    # per-phase serving metrics measured on the paged passes
    assert out["paged_pass"]["ttft_p99_ms"] is not None
    assert out["paged_pass"]["tpot_p99_ms"] is not None
    assert out["static_pass"]["tpot_p99_ms"] is None  # unobservable
    # the autoscaler proof fields exist and respect the no-flap bar
    a = out["autoscale"]
    assert a["timeline"], "no replica-count timeline"
    assert a["max_flips_in_a_phase"] <= 1
    assert a["shed_rate_vs_fixed"]["no_worse"] is True
    assert isinstance(a["decisions"], list)
    # and the record flattens into the schema-stable ledger fields
    rec = bench.ledger_record({"disagg": {
        "ttft_p99_ms": out["ttft_p99_ms"],
        "tpot_p99_ms": out["tpot_p99_ms"],
        "paged_concurrency_x": out["paged_concurrency_x"],
        "shed_rate": out["shed_rate"]}})
    assert rec["disagg_ttft_p99_ms"] == out["ttft_p99_ms"]
    assert rec["disagg_paged_concurrency_x"] == \
        out["paged_concurrency_x"]
    assert rec["disagg_shed_rate"] == out["shed_rate"]
    for key in bench.LEDGER_FIELDS:
        assert key in rec


def test_elastic_measurements_contract():
    """The elastic chaos leg's measurement dict carries the judged
    fields (steps/sec before the fault, recovery wall-clock after the
    injected host death, post-shrink throughput) — run small in-process
    so tier-1 stays fast; the full leg is `--elastic` and its one JSON
    line lands in ELASTIC_r01.json."""
    bench = _bench()
    out = bench._elastic_measurements(max_steps=20, die_at=6,
                                      rejoin_at=14, pace_s=0.05)
    assert out["hosts"] == 4
    assert out["steps"] == 20                      # the run completes
    assert out["steps_per_sec_before_fault"] > 0
    assert out["steps_per_sec_after_shrink"] > 0
    assert out["recovery_wall_clock_s"] > 0        # death -> resumed
    assert out["recovery_wall_clock_s"] < 30       # ...bounded
    assert out["incarnations"] >= 1
    assert out["shards_min"] < out["shards_before"]  # it really shrank
    # the regression target starts at ~8.0 loss; 20 steps with replayed
    # recoveries land well below it (descent, not a tight absolute)
    assert out["final_loss"] < 5.0
    assert out["wall_clock_s"] < 120


def test_integrity_measurements_contract():
    """The integrity chaos leg's measurement dict carries the judged
    fields (SDC detection latency in steps at the vote cadence, vote +
    fingerprint overhead %, who was evicted) — run small in-process so
    tier-1 stays fast; the full leg is `--integrity` and its one JSON
    line lands in INTEGRITY_r01.json."""
    bench = _bench()
    out = bench._integrity_measurements(max_steps=20, corrupt_at=6,
                                        cadence=4, pace_s=0.05)
    assert out["hosts"] == 4
    assert out["steps"] == 20                       # the run completes
    assert out["sdc_injected_at"] == 6
    # the next vote after corruption flags the host: latency is bounded
    # by the cadence window
    assert out["sdc_detected_at"] is not None
    assert 0 <= out["sdc_detection_latency_steps"] <= out[
        "integrity_cadence"]
    assert out["evicted_hosts"] == ["host2"]
    assert out["sdc_evictions"] == 1
    assert out["sdc_votes"] >= 2                    # voting continued
    assert 0.0 <= out["vote_overhead_pct"] < 100.0
    # fingerprint overhead is a measured wall-clock delta: tiny and
    # noisy on CPU, but the probe itself must produce both passes
    assert out["bare_wall_s"] > 0 and out["recorded_wall_s"] > 0
    assert isinstance(out["fingerprint_overhead_pct"], float)
    assert out["final_loss"] < 5.0                  # loss kept descending
    assert out["wall_clock_s"] < 120


def test_telemetry_measurements_contract():
    """The telemetry leg's measurement dict carries the judged fields
    (overhead % of the telemetry spine vs a bare step loop at the
    default every-step tracing cadence, per-op primitive costs, and
    the goodput ledger accounting for the instrumented run) — run
    small in-process so tier-1 stays fast; the full leg is
    `--telemetry` and its one JSON line lands in TELEMETRY_r01.json."""
    bench = _bench()
    # small in-process scale everywhere — including the goodput leg,
    # which at its full defaults (1200 steps x hidden 4096) costs ~60s
    # of tier-1 for no extra schema coverage; the judged numbers come
    # from the full `--telemetry` leg
    out = bench._telemetry_measurements(steps=12, batch=256, repeats=1,
                                        goodput_steps=120,
                                        goodput_hidden=512,
                                        goodput_batch=512,
                                        checkpoint_every=30)
    assert out["bare_wall_s"] > 0 and out["telemetry_wall_s"] > 0
    assert isinstance(out["overhead_pct"], float)
    # the acceptance target is <3% on the full leg's longer loop; the
    # tiny in-process run only guards against a rogue order-of-
    # magnitude regression (wall noise dominates at this scale — a
    # single 0.2s scheduler hiccup on the ~1s walls reads as ~20%)
    assert out["overhead_pct"] < 50.0, out
    # primitive costs: each driver iteration pays a handful of these,
    # so µs-scale per op keeps the per-step tax far under 3% of any
    # real step time
    assert 0 < out["histogram_observe_ns"] < 1e5
    assert 0 < out["counter_inc_ns"] < 1e5
    assert 0 < out["tracer_record_ns"] < 1e5
    # the instrumented run's ledger accounted for its wall clock
    assert out["goodput_accounted_fraction"] >= 0.99
    assert out["trace_events"] > 0


def test_sharding_measurements_contract():
    """The sharding-plan leg's measurement dict carries the judged
    fields (composed data x pipe x model steps/sec with the loss
    descending, and the FSDP per-device addressable param fraction
    ~1/8) — run small in-process on the suite's 8 forced-host devices;
    the full leg is `--sharding` and its one JSON line lands in
    SHARDING_r01.json."""
    bench = _bench()
    out = bench._sharding_measurements(composed_steps=6, fsdp_steps=4)
    assert out["devices"] == 8
    assert out["composed_mesh"] == "data=2 x pipe=2 x model=2"
    assert out["composed_steps_per_sec"] > 0
    assert out["composed_loss_descending"] is True, out
    assert out["fsdp_steps_per_sec"] > 0
    assert out["fsdp_loss_descending"] is True, out
    # FSDP: per-device addressable bytes ~ total/8 plus replicated
    # crumbs (biases, the tiny head) — far under a full replica
    assert 0.10 <= out["fsdp_param_bytes_frac"] <= 0.25, out
    # and the record flattens into the schema-stable ledger fields
    rec = bench.ledger_record({"sharding": {
        "composed_steps_per_sec": out["composed_steps_per_sec"],
        "fsdp_param_bytes_frac": out["fsdp_param_bytes_frac"]}})
    assert rec["sharding_composed_steps_per_sec"] == \
        out["composed_steps_per_sec"]
    assert rec["sharding_fsdp_param_bytes_frac"] == \
        out["fsdp_param_bytes_frac"]
    for key in bench.LEDGER_FIELDS:
        assert key in rec


def test_dlrm_measurements_contract():
    """The DLRM sparse-transport leg's measurement dict carries the
    judged fields (measured collective bytes/step for the sparse and
    dense passes with the reduction ratio, steps/sec for both, loss
    trajectories descending-capable) — run small in-process on the
    suite's 8 forced-host devices; the full leg is `--dlrm` and its
    one JSON line lands in DLRM_r01.json."""
    bench = _bench()
    out = bench._dlrm_measurements(steps=6, batch=128,
                                   table_sizes=(2048, 512, 128),
                                   embed_dim=16, n_records=512,
                                   shard_min_bytes=64 * 1024)
    assert out["devices"] == 8
    assert out["mesh"] == "data=8"
    assert out["zipf_exponent"] == 1.1
    assert out["sharded_tables"] == [0]   # 2048x16 f32 = 128 KiB
    # the full tables exceed the pretend per-device budget (total/2):
    # row sharding is forced, not optional
    assert out["table_bytes_total"] > out["per_device_table_budget_bytes"]
    assert out["steps_per_sec"] > 0
    assert out["dense_steps_per_sec"] > 0
    # the wire win: measured collective bytes/step shrink well past the
    # acceptance bar even at this tiny scale (the full leg commits ~190x)
    assert out["collective_bytes_per_step"] > 0
    assert out["dense_collective_bytes_per_step"] > \
        5 * out["collective_bytes_per_step"]
    assert out["collective_bytes_reduction_x"] > 5
    assert out["sparse_bytes_saved_per_step"] > 0
    assert out["loss_first"] is not None and out["loss_last"] is not None
    # and the record flattens into the schema-stable ledger fields
    rec = bench.ledger_record({"dlrm": {
        "steps_per_sec": out["steps_per_sec"],
        "collective_bytes_per_step": out["collective_bytes_per_step"]}})
    assert rec["dlrm_steps_per_sec"] == out["steps_per_sec"]
    assert rec["dlrm_collective_bytes_per_step"] == \
        out["collective_bytes_per_step"]
    for key in bench.LEDGER_FIELDS:
        assert key in rec


def test_sync_measurements_contract():
    """The sync leg's measurement dict carries the judged fields
    (lockstep vs periodic(k) steps/sec, the amortized collective-bytes
    gauge with its reduction ratio >= the 4x bar — a deterministic
    accounting property even at tiny scale — and both passes' loss
    trajectories) — run small in-process WITHOUT the straggler pass
    (two elastic gangs cost tier-1 seconds the full `--sync` leg
    already spends); the full leg lands in SYNC_r01.json."""
    bench = _bench()
    out = bench._sync_measurements(steps=6, batch=128, n_records=512,
                                   period=8, straggler=False)
    assert out["devices"] == 8
    assert out["mesh"] == "data=8"
    assert out["period"] == 8
    assert out["lockstep_steps_per_sec"] > 0
    assert out["periodic_steps_per_sec"] > 0
    # the wire win: amortized averaging bytes / k, deterministic
    assert out["periodic_collective_bytes_per_step"] > 0
    assert out["lockstep_collective_bytes_per_step"] > \
        4 * out["periodic_collective_bytes_per_step"]
    assert out["collective_bytes_reduction_x"] > 4
    assert out["sync_bytes_saved_per_step"] > 0
    assert out["loss_first"] is not None and out["loss_last"] is not None
    assert "straggler" not in out  # skipped in the tiny pass
    # and the record flattens into the schema-stable ledger fields
    rec = bench.ledger_record({"sync": {
        "periodic_steps_per_sec": out["periodic_steps_per_sec"],
        "periodic_collective_bytes_per_step":
            out["periodic_collective_bytes_per_step"],
        "straggler_advantage_x": 2.0}})
    assert rec["sync_periodic_steps_per_sec"] == \
        out["periodic_steps_per_sec"]
    assert rec["sync_bytes_per_step"] == \
        out["periodic_collective_bytes_per_step"]
    assert rec["sync_straggler_advantage_x"] == 2.0
    for key in bench.LEDGER_FIELDS:
        assert key in rec


def test_blocksparse_measurements_contract():
    """The block-sparse kernel leg's measurement dict carries the
    judged fields (full-mask parity at a non-default sm_scale, the
    executed-work-∝-density accounting sweep, the 50%-mask work
    reduction, the sparse-FLOPs gauge round trip) — run tiny
    in-process so tier-1 stays fast; the full leg is `--blocksparse`
    and its one JSON line lands in BLOCKSPARSE_r01.json."""
    bench = _bench()
    out = bench._blocksparse_measurements(seq_len=256, head_dim=32,
                                          block=64,
                                          densities=(1.0, 0.5))
    assert out["full_mask_parity"] is True
    assert out["mlp_parity"] is True
    assert out["accounting_within_10pct"] is True, out["density_sweep"]
    for row in out["density_sweep"]:
        assert abs(row["executed_fraction"] - row["density"]) \
            <= 0.10 * row["density"]
    # the 50% magnitude mask halves the executed work exactly — a
    # deterministic count
    assert out["work_reduction_x"] == 2.0
    assert out["sparse_flops_skipped"] > 0
    assert out["sparse_flops_gauge"] == out["sparse_flops_skipped"]
    assert out["accountant_payload_has_skip"] is True
    assert out["speedup_basis"] == "interpret_work_reduction"
    # and the record flattens into the schema-stable ledger fields
    rec = bench.ledger_record({"blocksparse": {
        "speedup_x": out["speedup_x"]}})
    assert rec["blocksparse_speedup_x"] == out["speedup_x"]
    assert rec["blocksparse_t4096_mfu"] is None
    # the device battery's wall ratio takes precedence over the leg
    rec2 = bench.ledger_record({
        "transformerlm_blocksparse_T4096_speedup_x": 1.7,
        "transformerlm_blocksparse_T4096_mfu": 0.56,
        "blocksparse": {"speedup_x": out["speedup_x"]}})
    assert rec2["blocksparse_speedup_x"] == 1.7
    assert rec2["blocksparse_t4096_mfu"] == 0.56
    for key in bench.LEDGER_FIELDS:
        assert key in rec


def test_slo_measurements_contract():
    """The SLO leg's measurement dict carries the judged fields
    (per-scenario detection/resolution intervals under the injected
    clock, steady-pass false positives, recorder+engine overhead and
    per-op costs) — the chaos part runs in-process at full scale
    (injected clock: cheap), the overhead loop tiny; the full leg is
    `--slo` and its one JSON line lands in SLO_r01.json."""
    bench = _bench()
    out = bench._slo_measurements(overhead_steps=12,
                                  overhead_batch=256,
                                  overhead_repeats=1,
                                  steady_intervals=60)
    # the acceptance bar: every injected breach (shed ramp, loss
    # divergence, MFU collapse, replica kill) detected within 3
    # evaluation intervals and resolved after recovery
    assert set(out["scenarios"]) == {"shed_ramp", "loss_divergence",
                                     "mfu_collapse", "replica_kill"}
    for name, s in out["scenarios"].items():
        assert s["detected_in_intervals"] is not None, (name, s)
        assert s["detected_in_intervals"] <= 3, (name, s)
        assert s["resolved_in_intervals"] is not None, (name, s)
    assert out["all_detected"] is True
    assert out["all_resolved"] is True
    assert out["max_detection_intervals"] <= 3
    assert out["detection_latency_s"] == \
        out["max_detection_intervals"] * out["eval_interval_s"]
    # zero spurious alerts on the steady control run
    assert out["false_positives"] == 0
    # overhead: the judged number is the amortized per-step monitor
    # cost over the loop's measured step time (the A/B wall delta is
    # informational — 1-core scheduler noise swamps it); the <=1% bar
    # is judged on the full leg's longer loop, the tiny in-process run
    # only guards against a rogue order-of-magnitude regression
    assert isinstance(out["overhead_pct"], float)
    assert out["overhead_pct"] < 50.0, out
    assert out["monitor_step_us"] > 0
    assert out["step_ms"] > 0
    assert isinstance(out["wall_overhead_pct"], float)
    assert 0 < out["recorder_observe_ns"] < 1e5
    assert 0 < out["engine_evaluate_us"] < 1e5
    # and the record flattens into the schema-stable ledger fields
    rec = bench.ledger_record({"slo": {
        "detection_latency_s": out["detection_latency_s"],
        "false_positives": out["false_positives"],
        "overhead_pct": out["overhead_pct"]}})
    assert rec["slo_detection_latency_s"] == \
        out["detection_latency_s"]
    assert rec["slo_false_positives"] == 0
    assert rec["slo_overhead_pct"] == out["overhead_pct"]
    for key in bench.LEDGER_FIELDS:
        assert key in rec


def test_loop_measurements_contract():
    """The continuous-loop leg's measurement dict carries the judged
    fields: goodput while serving (>= 0.97 with confirmed hot-swaps
    landing and the loss descending), burn-rate rollback latency on a
    regressed deploy, and the bad-params-served audit (must be 0) —
    a short in-process run; the full leg is `--loop` and its one JSON
    line lands in LOOP_r01.json."""
    bench = _bench()
    out = bench._loop_measurements(intervals=20,
                                   requests_per_interval=8)
    # the model improved while the fleet served, across hot-swaps
    assert out["confirmed_deploys"] >= 2
    assert out["loss_last"] < out["loss_first"]
    assert out["goodput"] is not None and out["goodput"] >= 0.97
    # the regressed deploy was rolled back by the burn-rate watch,
    # through the verified install path, and quickly
    assert out["rollbacks_fired"] == 1
    assert out["rollback_latency_s"] is not None
    assert out["rollback_latency_s"] < 30.0
    # the audit invariant: a bad param tree never answered a request
    assert out["bad_params_served"] == 0
    # and the record flattens into the schema-stable ledger fields
    rec = bench.ledger_record({"loop": {
        "goodput": out["goodput"],
        "rollback_latency_s": out["rollback_latency_s"],
        "bad_params_served": out["bad_params_served"]}})
    assert rec["loop_goodput"] == out["goodput"]
    assert rec["loop_rollback_latency_s"] == out["rollback_latency_s"]
    assert rec["loop_bad_params_served"] == 0
    for key in bench.LEDGER_FIELDS:
        assert key in rec


def test_embed_measurements_contract():
    """The embedding-store leg's measurement dict carries the judged
    fields: 1-host live re-partition wall-clock with the moved-row
    fraction near 1/N, bitwise-equal tables across both membership
    boundaries, corrupt-shard detection + checkpointed-leg recovery,
    Zipf cache hit rate, and the bad-rows-served audit (must be 0) —
    a small in-process run; the full leg is `--embed` and its one
    JSON line lands in EMBED_r01.json."""
    bench = _bench()
    out = bench._embed_measurements(n_rows=8192, block_rows=256,
                                    update_rounds=10,
                                    zipf_lookups=60)
    # consistent assignment: a 1-host delta moves ~1/N, never more
    # than the 1.5/N acceptance bar
    assert 0.0 < out["rows_moved_frac"] <= 1.5 / out["n_hosts"]
    assert out["migration_s"] is not None and out["migration_s"] >= 0
    # the table is bitwise identical across both boundaries, even
    # with one migration shard corrupted in flight
    assert out["bitwise_equal_after_shrink"] is True
    assert out["bitwise_equal_after_regrow"] is True
    assert out["corrupt_shards_injected"] == 1
    assert out["corrupt_shards_detected"] >= 1
    assert out["recovered_from_checkpoint"] >= 1
    # the Zipf skew pays at the cache, and the audit invariant holds
    assert out["cache_hit_rate"] > 0.4
    assert out["bad_rows_served"] == 0
    assert out["rows_served"] > 0
    # and the record flattens into the schema-stable ledger fields
    rec = bench.ledger_record({"embed": {
        "migration_s": out["migration_s"],
        "cache_hit_rate": out["cache_hit_rate"],
        "bad_rows_served": out["bad_rows_served"]}})
    assert rec["embed_migration_s"] == out["migration_s"]
    assert rec["embed_cache_hit_rate"] == out["cache_hit_rate"]
    assert rec["embed_bad_rows_served"] == 0
    for key in bench.LEDGER_FIELDS:
        assert key in rec


def test_tenant_measurements_contract():
    """The multi-tenant leg's measurement dict carries the judged
    fields: the victim tenant's contended-over-solo p99 ratio, the
    must-stay-zero victim shed rate (fair admission never bills the
    aggressor's flood to the victim), the rejected poisoned deploy,
    and the bad-params audit across BOTH tenants — a small in-process
    run; the full leg is `--tenant` and its one JSON line lands in
    TENANT_r01.json."""
    bench = _bench()
    out = bench._tenant_measurements(solo_requests=30,
                                     contended_requests=30,
                                     flood_threads=2)
    assert out["solo_p99_ms"] > 0
    assert out["contended_p99_ms"] > 0
    assert out["isolation_p99_ratio"] > 0
    # the victim shed NOTHING while the aggressor flooded open-loop
    assert out["victim_requests"] >= 30
    assert out["victim_shed_rate"] == 0.0
    assert out["aggressor_requests"] > 0
    # the poisoned aggressor deploy was rejected by the canary and
    # nothing non-finite was ever served to either tenant
    assert out["poisoned_deploy_rejected"] is True
    assert out["bad_params_served"] == 0
    assert out["all_typed"] is True
    # and the record flattens into the schema-stable ledger fields
    rec = bench.ledger_record({"tenant": {
        "isolation_p99_ratio": out["isolation_p99_ratio"],
        "victim_shed_rate": out["victim_shed_rate"],
        "bad_params_served": out["bad_params_served"]}})
    assert rec["tenant_isolation_p99_ratio"] \
        == out["isolation_p99_ratio"]
    assert rec["tenant_victim_shed_rate"] == 0.0
    assert rec["tenant_bad_params_served"] == 0
    for key in bench.LEDGER_FIELDS:
        assert key in rec


def test_incident_measurements_contract():
    """The incident leg's measurement dict carries the judged fields:
    top-1 attribution vs the ground-truth chaos journal across all
    five fault classes, the must-stay-zero clean-control false-
    incident count, capture latency, and the amortized per-pump-round
    observe tax — a small in-process run; the full leg is `--incident`
    and its one JSON line lands in INCIDENT_r01.json."""
    bench = _bench()
    out = bench._incident_measurements(steady_intervals=60)
    assert out["attribution_total"] == 5
    assert set(out["scenarios"]) == {
        "replica_kill", "poisoned_deploy", "tenant_flood",
        "straggler_delay", "kv_exhaustion"}
    # every injected fault finalized an incident whose top-1 suspect
    # is the ground-truth chaos injection (acceptance: >= 4 of 5; the
    # deterministic harness lands all 5)
    assert out["all_finalized"] is True
    assert out["attribution_top1"] >= 4
    assert out["attribution_top1_frac"] >= 0.8
    # zero incidents opened over the clean control
    assert out["false_incidents"] == 0
    assert out["capture_latency_s"] is not None
    assert out["capture_latency_s"] < 0.5
    assert out["overhead_pct"] < 2.0
    # and the record flattens into the schema-stable ledger fields
    rec = bench.ledger_record({"incident": {
        "attribution_top1_frac": out["attribution_top1_frac"],
        "false_incidents": out["false_incidents"],
        "capture_latency_s": out["capture_latency_s"],
        "overhead_pct": out["overhead_pct"]}})
    assert rec["incident_attribution_top1"] \
        == out["attribution_top1_frac"]
    assert rec["incident_false_positives"] == 0
    assert rec["incident_capture_latency_s"] \
        == out["capture_latency_s"]
    assert rec["incident_overhead_pct"] == out["overhead_pct"]
    for key in bench.LEDGER_FIELDS:
        assert key in rec
