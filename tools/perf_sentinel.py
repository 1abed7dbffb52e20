#!/usr/bin/env python
"""Perf regression sentinel — diff the newest record of a bench ledger
against the committed baseline, fail loudly on regression.

A ledger is a JSONL file of ``bench.ledger_record(...)`` rows (see
``bench.LEDGER_FIELDS``) kept by whoever runs the benchmark; ``--ledger``
names it (there is no default: ``bench.py`` writes none, and the ledger
file at the repo root belongs to the round driver, not to this tool).
The newest record is compared metric by metric against
``PERF_BASELINE.json`` under that metric's own tolerance and direction
— throughput regressing 20% fails, latency regressing 20% fails, a
throughput *improvement* never does.  Exit is non-zero on any
regression.

Comparability guard: a record measured on a different backend than the
baseline (cpu vs tpu) is skipped with exit 0 and a notice — a CPU run
must not read as a 100x regression of a chip number.

Usage:
    python tools/perf_sentinel.py --check --ledger F [--baseline F]
    python tools/perf_sentinel.py --update-baseline --ledger F [--note TEXT]
    python tools/perf_sentinel.py --show --ledger F

Exit codes: 0 pass/skip, 1 regression, 2 usage or unreadable inputs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(REPO, "PERF_BASELINE.json")

#: metric -> (direction, relative tolerance[, absolute floor]).
#: "higher" = bigger is better (throughput, MFU, goodput fraction);
#: "lower" = smaller is better (latency, overhead, stall seconds).
#: Tolerance is the allowed relative regression before the sentinel
#: fails; the optional absolute floor passes any regression whose
#: absolute delta stays under it — without it, a metric whose baseline
#: is ~0 (e.g. ``checkpoint_blocked_s`` after the async-checkpoint
#: work) would fail on any nonzero jitter.  A baseline file may
#: override per metric.
DEFAULT_TOLERANCES = {
    "value": ("higher", 0.10),
    "mfu": ("higher", 0.10),
    "transformerlm_mfu": ("higher", 0.10),
    "transformerlm_T4096_mfu": ("higher", 0.10),
    "transformerlm_cpu_tokens_per_sec": ("higher", 0.50),
    "simplernn_records_per_sec": ("higher", 0.30),
    "lenet5_images_per_sec": ("higher", 0.30),
    "decode_tokens_per_sec": ("higher", 0.15),
    "prefill_tokens_per_sec": ("higher", 0.15),
    "serving_p99_ms": ("lower", 0.50),
    # serving-fleet leg (ISSUE 9): shed rate may only fall and
    # goodput-per-chip may only rise; latency/recovery on the 1-core
    # CI box is noisy, so tolerances are wide with absolute floors
    # absorbing jitter around small values
    "fleet_shed_rate": ("lower", 0.50, 0.02),
    "fleet_goodput_per_chip": ("higher", 0.60),
    "fleet_p99_ms": ("lower", 0.75, 5.0),
    "fleet_recovery_s": ("lower", 1.00, 0.5),
    # distributed request tracing (ISSUE 13): traced-vs-untraced
    # overhead may only fall (the 0.5-percentage-point absolute floor
    # absorbs 1-core scheduler jitter around the small baseline) and
    # the p99 cohort's stitched wall-clock coverage may only rise — a
    # falling coverage means replica fragments silently stopped
    # publishing or stitching
    "trace_overhead_pct": ("lower", 1.00, 0.5),
    "trace_p99_coverage": ("higher", 0.05),
    # disaggregated serving leg (ISSUE 11): TTFT/TPOT on the 1-core CI
    # box are scheduler-noisy (wide tolerances, absolute floors); the
    # paged concurrency multiple is a deterministic arena-accounting
    # property — a fall means paging silently stopped paying — and
    # shed under the ramp may only fall
    "disagg_ttft_p99_ms": ("lower", 2.00, 250.0),
    "disagg_tpot_p99_ms": ("lower", 2.00, 100.0),
    "disagg_paged_concurrency_x": ("higher", 0.0),
    "disagg_shed_rate": ("lower", 0.50, 0.02),
    "elastic_recovery_s": ("lower", 1.00),
    "telemetry_overhead_pct": ("lower", 2.00),
    # async-everything goodput family (ISSUE 7): the productive
    # fraction may only rise; stall/blocked seconds may only fall
    # (small absolute floors absorb scheduler jitter around ~0)
    "goodput_productive_fraction": ("higher", 0.05),
    "goodput_accounted_fraction": ("higher", 0.02),
    "goodput_checkpoint_fraction": ("lower", 0.50, 0.01),
    "data_stall_s": ("lower", 0.50, 0.50),
    "checkpoint_blocked_s": ("lower", 0.50, 0.25),
    # sharding-plan engine (ISSUE 8): composed-mesh steps/sec on the
    # forced-host CPU leg is noisy (single core, 3-D collectives), so
    # the tolerance is wide; the FSDP per-device param fraction is a
    # deterministic layout property — a rise means param sharding
    # silently stopped sharding
    "sharding_composed_steps_per_sec": ("higher", 0.50),
    "sharding_fsdp_param_bytes_frac": ("lower", 0.25),
    # DLRM sparse gradient transport (ISSUE 10): steps/sec on the
    # forced-host CPU leg is noisy (wide tolerance); the measured
    # collective bytes/step is a deterministic plan/accounting property
    # — a rise means the sparse wire silently stopped engaging
    "dlrm_steps_per_sec": ("higher", 0.50),
    "dlrm_collective_bytes_per_step": ("lower", 0.25),
    # relaxed synchrony (ISSUE 15): periodic(8) throughput on the
    # forced-host CPU leg is noisy (wide tolerance); its amortized
    # collective bytes/step is a deterministic plan/accounting
    # property — a rise means relaxed synchrony silently stopped
    # paying; the straggler advantage (relax-before-evict vs the
    # eviction path on time-to-loss-target) may only fall within the
    # wide tolerance + absolute floor that absorb 1-core wall noise
    # around the restore/recompile cost it measures
    "sync_periodic_steps_per_sec": ("higher", 0.50),
    "sync_bytes_per_step": ("lower", 0.25),
    "sync_straggler_advantage_x": ("higher", 0.75, 0.5),
    # online health engine (ISSUE 14): detection latency on the
    # injected breaches is deterministic (injected clock) and may
    # only fall (one-interval abs floor absorbs a rule-pack retune);
    # the steady control's false-positive count must stay ZERO (any
    # rise fails — a noisy health engine is worse than none); the
    # recorder+engine overhead on the instrumented step loop may only
    # fall (1-percentage-point abs floor absorbs 1-core scheduler
    # jitter around the small baseline)
    "slo_detection_latency_s": ("lower", 0.50, 5.0),
    "slo_false_positives": ("lower", 0.0),
    "slo_overhead_pct": ("lower", 1.00, 1.0),
    # continuous-learning loop (ISSUE 17): goodput while serving may
    # only rise (2-point abs floor absorbs 1-core scheduler jitter
    # near the 1.0 ceiling); burn-rate rollback latency may only fall
    # (wide tolerance + abs floor — the wall of a few verified
    # re-installs is tiny and jittery); bad-params-served must stay
    # ZERO — serving an unverified param tree is never a regression
    # to tolerate
    "loop_goodput": ("higher", 0.05, 0.02),
    "loop_rollback_latency_s": ("lower", 1.00, 0.5),
    "loop_bad_params_served": ("lower", 0.0),
    # block-sparse kernels (ISSUE 12): the T4096 executed-basis MFU
    # may only rise (null until a chip run measures it); the speedup
    # multiple is the measured wall ratio on TPU and the deterministic
    # executed-work reduction on the CPU leg — either way a fall means
    # the kernels silently stopped skipping
    "blocksparse_t4096_mfu": ("higher", 0.10),
    "blocksparse_speedup_x": ("higher", 0.25, 0.2),
    # parameter-server embedding store (ISSUE 18): the 1-host live
    # re-partition wall may only fall (wide tolerance + abs floor —
    # the wall of a ~100k-row in-process migration is tiny and
    # jittery); the Zipf hot-row cache hit rate may only fall so far
    # (abs floor absorbs stream-order noise); bad-rows-served must
    # stay ZERO — a row served at a retired table version is never a
    # regression to tolerate
    "embed_migration_s": ("lower", 1.00, 0.5),
    "embed_cache_hit_rate": ("higher", 0.10, 0.02),
    "embed_bad_rows_served": ("lower", 0.0),
    # multi-tenant fleet (ISSUE 19): the victim tenant's contended-
    # over-solo p99 ratio may only fall (wide tolerance + abs floor —
    # at millisecond solo latencies on the shared-CPU CI box the
    # flood's scheduler pressure dominates the ratio's noise); the
    # victim shed rate must stay ZERO (fair admission may never bill
    # the aggressor's flood to the victim's budget) and bad-params-
    # served must stay ZERO — a poisoned deploy that installs, or a
    # non-finite output served to EITHER tenant, is never a
    # regression to tolerate
    "tenant_isolation_p99_ratio": ("lower", 1.00, 3.0),
    "tenant_victim_shed_rate": ("lower", 0.0),
    "tenant_bad_params_served": ("lower", 0.0),
    # incident engine (ISSUE 20): top-1 causal attribution against
    # the ground-truth chaos journal may only rise (zero tolerance —
    # the five-fault harness is deterministic); the clean control's
    # false-incident count must stay ZERO (an incident opened on a
    # healthy fleet poisons trust in every real one); capture latency
    # and the amortized per-pump-round observe tax may only fall
    # (wide tolerance + abs floors absorb shared-CPU perf_counter
    # jitter on sub-millisecond walls)
    "incident_attribution_top1": ("higher", 0.0),
    "incident_false_positives": ("lower", 0.0),
    "incident_capture_latency_s": ("lower", 1.00, 0.5),
    "incident_overhead_pct": ("lower", 1.00, 1.0),
}


def read_latest_record(path: str) -> Optional[dict]:
    """Newest parseable record in the ledger (last valid JSON line)."""
    try:
        with open(path) as f:
            lines = [ln.strip() for ln in f if ln.strip()]
    except OSError:
        return None
    for ln in reversed(lines):
        try:
            rec = json.loads(ln)
        except ValueError:
            continue
        if isinstance(rec, dict):
            return rec
    return None


def read_baseline(path: str) -> Optional[dict]:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    return data if isinstance(data, dict) and "record" in data else None


def compare(record: dict, baseline: dict) -> dict:
    """Pure comparison (tested directly): returns
    ``{"status": "pass"|"fail"|"skipped", "checks": [...], ...}``."""
    base_rec = baseline.get("record") or {}
    tolerances = dict(DEFAULT_TOLERANCES)
    for name, spec in (baseline.get("tolerances") or {}).items():
        tolerances[name] = (spec.get("direction", "higher"),
                            float(spec.get("rel_tol", 0.10)),
                            float(spec.get("abs_tol", 0.0)))
    if record.get("backend") != base_rec.get("backend"):
        return {
            "status": "skipped",
            "reason": "backend mismatch: record %r vs baseline %r — "
                      "not comparable" % (record.get("backend"),
                                          base_rec.get("backend")),
            "checks": [],
        }
    checks = []
    failures = 0
    for name, spec in sorted(tolerances.items()):
        direction, tol = spec[0], spec[1]
        abs_tol = spec[2] if len(spec) > 2 else 0.0
        base = base_rec.get(name)
        cur = record.get(name)
        if base is None or not isinstance(base, (int, float)):
            continue  # baseline never measured it: nothing to guard
        check = {"metric": name, "baseline": base, "current": cur,
                 "direction": direction, "rel_tol": tol}
        if abs_tol:
            check["abs_tol"] = abs_tol
        if cur is None or not isinstance(cur, (int, float)):
            # a guarded metric VANISHING is a regression (a broken
            # bench section must not read as a pass)
            check.update(status="fail", reason="missing from record")
            failures += 1
        else:
            if base == 0:
                delta = 0.0 if cur == 0 else float("inf")
            else:
                delta = (cur - base) / abs(base)
            regression = -delta if direction == "higher" else delta
            # absolute worsening, signed toward "worse" for the metric's
            # direction — what the abs floor is compared against
            worse_abs = (base - cur) if direction == "higher" \
                else (cur - base)
            check["delta"] = (round(delta, 4)
                              if delta != float("inf") else "inf")
            if regression > tol and worse_abs > abs_tol:
                check.update(status="fail",
                             reason="%s regressed %.1f%% (tol %.0f%%)"
                                    % (name, min(100 * regression,
                                                 9999.0),
                                       100 * tol))
                failures += 1
            else:
                check["status"] = "pass"
        checks.append(check)
    return {"status": "fail" if failures else "pass",
            "failures": failures, "checks": checks}


def _utc_now() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


def make_baseline(record: dict, note: str = "") -> dict:
    return {
        "schema": 1,
        "frozen_at": _utc_now(),
        "note": note,
        "tolerances": {
            name: dict({"direction": spec[0], "rel_tol": spec[1]},
                       **({"abs_tol": spec[2]} if len(spec) > 2
                          else {}))
            for name, spec in sorted(DEFAULT_TOLERANCES.items())},
        "record": record,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--ledger", required=True,
                   help="JSONL of bench.ledger_record rows")
    p.add_argument("--baseline", default=DEFAULT_BASELINE)
    mode = p.add_mutually_exclusive_group(required=True)
    mode.add_argument("--check", action="store_true",
                      help="compare latest ledger record vs baseline; "
                           "exit 1 on regression")
    mode.add_argument("--update-baseline", action="store_true",
                      help="freeze the latest ledger record as the "
                           "new baseline")
    mode.add_argument("--show", action="store_true",
                      help="print the latest record and baseline")
    p.add_argument("--note", default="",
                   help="provenance note for --update-baseline")
    p.add_argument("--json", action="store_true",
                   help="machine-readable --check output")
    args = p.parse_args(argv)

    record = read_latest_record(args.ledger)
    if record is None:
        print("perf-sentinel: no readable record in %s" % args.ledger,
              file=sys.stderr)
        return 2

    if args.update_baseline:
        baseline = make_baseline(record, note=args.note)
        with open(args.baseline, "w") as f:
            json.dump(baseline, f, indent=1)
            f.write("\n")
        print("perf-sentinel: baseline frozen from record ts=%s -> %s"
              % (record.get("ts"), args.baseline))
        return 0

    baseline = read_baseline(args.baseline)
    if args.show:
        print(json.dumps({"record": record, "baseline": baseline},
                         indent=1))
        return 0

    if baseline is None:
        print("perf-sentinel: no baseline at %s (freeze one with "
              "--update-baseline)" % args.baseline, file=sys.stderr)
        return 2

    result = compare(record, baseline)
    if args.json:
        print(json.dumps(result, indent=1))
    else:
        if result["status"] == "skipped":
            print("perf-sentinel: SKIPPED — %s" % result["reason"])
        else:
            for c in result["checks"]:
                mark = "FAIL" if c["status"] == "fail" else " ok "
                base = c["baseline"]
                print("[%s] %-34s base=%-12g cur=%-12s %s" % (
                    mark, c["metric"], base,
                    ("%g" % c["current"]) if isinstance(
                        c.get("current"), (int, float)) else "missing",
                    c.get("reason", "")))
            print("perf-sentinel: %s (%d checked, %d failed)"
                  % (result["status"].upper(), len(result["checks"]),
                     result.get("failures", 0)))
    return 1 if result["status"] == "fail" else 0


if __name__ == "__main__":
    sys.exit(main())
