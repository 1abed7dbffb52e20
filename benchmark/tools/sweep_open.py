#!/usr/bin/env python3
"""The one sweep that finds the open-loop cell's knee: ONE server, warmed
once, offered the traffic file's schedule at each of several rates for
``--seconds`` each.  For every rate it prints what was offered and
completed, the backlog when sending stopped, client-side p50/p95 from
the due time, and how late the generator ran.  The knee is the highest
rate whose backlog stays bounded and whose completed rate follows the
offered rate; the cell's ``rate_rps`` is four fifths of it, stored as a
number in the traffic file.  Not part of a benchmark run.

    python3 benchmark/tools/sweep_open.py --workload <cell> --rates 6,8,10,11,12,13,14 --seconds 20

With ``--repeat n`` every window is offered ``n`` times, and with
``--schedule-seeds a,b,c`` on each of those schedules in place of the
traffic file's own; the last lines then give, for each rate and
schedule, the run-to-run spread (inter-quartile / median) of p50 and
p95 — how a schedule or a window length is tried before a bound is
widened.  These are windows of ONE process: a screening, not the two
sets of separate runs a bound is set from.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=3000000101)
    ap.add_argument("--schedule-seeds", default="",
                    help="comma list; default: the traffic file's own")
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy-size rehearsal on the CPU: never a measurement")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "sweep_open.json"))
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from benchmark import run as harness
    from benchmark.drivers import serve_open
    from benchmark.drivers.serve_common import Harness

    overlay = os.path.dirname(os.path.abspath(args.manifest))
    manifest = harness.load_json(args.manifest)
    cell = {w["name"]: w for w in manifest["workloads"]}[args.workload]
    traffic = harness.load_json(harness.find("traffic", cell["traffic"],
                                             ".json", overlay))
    ctx = harness.Context(
        cell=cell, traffic=traffic, seed=args.seed, chips=1, overlay=overlay,
        config=harness.load_json(harness.find("configs", cell["config"],
                                              ".json", overlay)),
        devices=jax.devices()[:1], clock=harness.SetupClock(T_PROCESS),
        say=harness.say, checks=harness.Checks(),
        compiles=harness.CompileCounter(jax), t_process=T_PROCESS)
    if not args.rehearse_cpu:
        harness.use_compile_cache(jax)
        if jax.devices()[0].platform != "tpu":
            print("sweep_open: no TPU", file=sys.stderr)
            return 2
    h = Harness(ctx)
    h.warm()
    seeds = [int(x) for x in args.schedule_seeds.split(",") if x] \
        or [traffic["schedule_seed"]]
    rows = []
    for rate, sseed, _ in [(float(r), x, i) for r in args.rates.split(",")
                           for x in seeds for i in range(args.repeat)]:
        tr = dict(traffic, rate_rps=rate, schedule_seed=sseed)
        due = serve_open.schedule(tr, args.seconds)
        sent, t0, backlog = serve_open.offer(h, due, h.prompts(len(due)))
        ok = [s for s in sent if s.result is not None and s.result.ok]
        lat = np.array([s.t_done - s.t_due for s in ok])
        late = np.array([s.t_sent - s.t_due for s in sent])
        t_last = max(s.t_done for s in ok)
        row = {"rate_offered_rps": len(due) / args.seconds,
               "nominal_rps": rate, "schedule_seed": sseed,
               "seconds": args.seconds,
               "rate_completed_rps": len(ok) / (t_last - t0),
               "failed": len(sent) - len(ok),
               "backlog_at_end": backlog,
               "drain_s": t_last - t0 - args.seconds,
               "p50_s": float(np.percentile(lat, 50)),
               "p95_s": float(np.percentile(lat, 95)),
               "late_p95_ms": float(1e3 * np.percentile(late, 95))}
        rows.append(row)
        print(json.dumps(row), flush=True)
    h.server.stop(60)
    if args.repeat >= 2:
        def spread(v):
            q = statistics.quantiles(v, n=4)
            return (q[2] - q[0]) / statistics.median(v)
        for key in sorted({(r["nominal_rps"], r["schedule_seed"])
                           for r in rows}):
            runs = [r for r in rows
                    if (r["nominal_rps"], r["schedule_seed"]) == key]
            out = {"nominal_rps": key[0], "schedule_seed": key[1],
                   "seconds": args.seconds, "runs": len(runs)}
            for m in ("p50_s", "p95_s"):
                v = [r[m] for r in runs]
                out[m + "_median"] = statistics.median(v)
                out[m + "_spread"] = spread(v)
            out["backlog_at_end"] = [r["backlog_at_end"] for r in runs]
            rows.append(out)
            print(json.dumps(out), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
