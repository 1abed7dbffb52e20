#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data.  ``BENCHMARK.json`` names the cell's
configuration and traffic mix and the metrics; the configuration is
``benchmark/configs/<config>.json``, the mix is
``benchmark/traffic/<traffic>.json`` and names its driver
(``benchmark/drivers/<driver>.py``); every per-layer metric is read by
``benchmark/readers/<metric>.py``.  Nothing in this file lists cells,
configurations, mixes or metrics.

A run makes its weights and inputs from ``--seed``, warms every shape
the cell dispatches (set-up), measures for ``--seconds``, checks what
the timed path produced against the plain reference, and prints ONE
JSON object as the last line of stdout; its last key, ``checks``, holds
every number compared beside its limit, and the same are the last lines
of stderr.  ``--trace 0`` reports the
cell's end-to-end metrics with the profiler off; ``--trace 1`` reports
its per-layer metrics from a profiled stretch of the same window.
Without a TPU (or with fewer chips than the cell asks for) it exits 2
and prints no result.
"""
import time

T_PROCESS = time.perf_counter()  # before any heavy import: set-up starts here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Every number compared, printed beside its limit; ``ok`` is the
    conjunction.  ``key`` is the short name the number goes by in the
    result line's ``checks`` and in the last lines of standard error."""

    def __init__(self):
        self.ok = True
        self.rows = []

    def le(self, key: str, name: str, value: float, limit: float,
           why: str = ""):
        good = bool(value == value and value <= limit)  # NaN fails
        self.ok &= good
        self.rows.append((key, float(value), float(limit), good))
        say(f"[check] {name}: {value:.6g} (limit <= {limit:.6g}) "
            f"{'ok' if good else 'FAILED'}{' — ' + why if why else ''}")
        return good

    def true(self, key: str, name: str, cond: bool, detail: str = ""):
        """A condition: reported as 0 (held) or 1 (broken), limit 0."""
        self.ok &= bool(cond)
        self.rows.append((key, float(not cond), 0.0, bool(cond)))
        say(f"[check] {name}: {'ok' if cond else 'FAILED'}"
            f"{' — ' + detail if detail else ''}")
        return bool(cond)


class SetupClock:
    """Where set-up goes: named stretches from process start."""

    def __init__(self, t0: float):
        self.t0, self.last, self.parts = t0, t0, []

    def mark(self, name: str):
        now = time.perf_counter()
        self.parts.append((name, now - self.last))
        self.last = now

    def line(self) -> str:
        return ", ".join(f"{n} {s:.2f}s" for n, s in self.parts)


class Context:
    """What a driver and the readers are handed."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def window_seconds(self) -> float:
        """A traced run profiles a short stretch of the window and reads
        per-layer numbers; its rates are not reported."""
        if self.trace:
            return min(self.seconds, self.traffic["trace_seconds"])
        return self.seconds

    def start_trace(self):
        import shutil

        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)

    def stop_trace(self):
        import jax

        jax.profiler.stop_trace()

    def peak_bytes(self) -> int:
        """``peak_bytes_in_use`` of the fullest chip so far — read it
        after the window and before the reference runs."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks)) if peaks else 0


def find(kind: str, name: str, ext: str, overlay: str) -> str:
    """``benchmark/<kind>/<name><ext>`` — beside the manifest first (a
    test's or a later PR's own files), then in this directory."""
    for base in (os.path.join(overlay, "benchmark"), HERE):
        path = os.path.join(base, kind, name + ext)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(
        f"no {kind}/{name}{ext} under {overlay}/benchmark or {HERE}")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_py(kind: str, name: str, overlay: str):
    path = find(kind, name, ".py", overlay)
    if os.path.dirname(os.path.dirname(path)) == HERE:
        return importlib.import_module(f"benchmark.{kind}.{name}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_overlay_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(manifest: dict, group: str, cell: str, reported=None):
    """Metrics of ``group`` that this cell reports: those that list it,
    and those that list no cells (then every cell that reports the
    end-to-end metric they move)."""
    out = []
    for m in manifest[group]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or reported is None \
                or m["moves"] in reported:
            out.append(m)
    return out


def use_compile_cache(jax):
    """jax's persistent cache at one fixed place: where
    JAX_COMPILATION_CACHE_DIR says, else ``.jax_cache/`` in the
    checkout — the program's own default, so its
    ``ensure_compile_cache()`` changes nothing.  Small programs are
    cached too: every run is a new process, and what is not cached is
    compiled again in every one of them."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compilations (cache misses and hits alike load or
    build a program) through jax's monitoring events; the window must
    see none."""

    def __init__(self, jax):
        self.backend_compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def _ev(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def programs(self) -> int:
        """Programs built or loaded from the cache so far."""
        return self.backend_compiles + self.cache_hits


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="for tests and sweeps: another BENCHMARK.json; "
                         "files beside it under benchmark/ are found first")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy-size rehearsal of the control flow on the "
                         "CPU backend; NOT a chip run, never a measurement")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="for setting limits, never in a benchmark run: "
                         "the reference at the next lower precision (fp8 "
                         "e4m3) stands in the program's place and ITS "
                         "readings go through the comparison, so the run "
                         "has to end with correct false; the program's "
                         "own readings are printed beside them")
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    overlay = os.path.dirname(os.path.abspath(args.manifest))
    manifest = load_json(args.manifest)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"run.py: no workload {args.workload!r} in {args.manifest} "
              f"(have {sorted(cells)})", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config = load_json(find("configs", cell["config"], ".json", overlay))
    traffic = load_json(find("traffic", cell["traffic"], ".json", overlay))
    for key, val in traffic.get("properties", {}).items():
        os.environ[key.replace(".", "_").upper()] = str(val)

    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell["chips"] > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={cell['chips']}")
    import jax

    # a rehearsal keeps no cache: CPU programs are not what a chip run loads
    cache_dir = None if args.rehearse_cpu else use_compile_cache(jax)
    devices = jax.devices()
    dev = devices[0]
    if args.rehearse_cpu:
        say("[env] CPU REHEARSAL — control flow only; nothing printed "
            "below is a device reading")
    elif dev.platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: cell {cell['name']!r} needs {cell['chips']} TPU "
              f"chip(s); jax {jax.__version__} found {len(devices)} x "
              f"{dev.platform!r} ({dev.device_kind!r})", file=sys.stderr)
        return 2
    from benchmark import counts

    peaks = None
    if dev.platform == "tpu":
        peaks = counts.peaks_for(dev.device_kind,
                                 load_json(os.path.join(HERE, "peaks.json")))
    clock = SetupClock(T_PROCESS)
    clock.mark("import and backend start")
    say(f"[env] jax {jax.__version__}; {len(devices)} x {dev.platform} "
        f"{dev.device_kind!r}; cell {cell['name']} = {cell['config']} x "
        f"{cell['traffic']} on {cell['chips']} chip(s); seed {args.seed}, "
        f"window {args.seconds:g}s, trace {args.trace}; compile cache "
        f"{cache_dir}")

    ctx = Context(cell=cell, config=config, traffic=traffic, seed=args.seed,
                  seconds=float(args.seconds), trace=bool(args.trace),
                  chips=int(cell["chips"]), devices=devices[:cell["chips"]],
                  root=ROOT, overlay=overlay, t_process=T_PROCESS,
                  clock=clock, say=say, checks=Checks(),
                  compiles=CompileCounter(jax),
                  peaks=peaks, counts=counts, rehearsal=args.rehearse_cpu,
                  control=bool(args.control),
                  trace_dir=os.path.join(ROOT, ".bench_trace"))
    driver = load_py("drivers", traffic["driver"], overlay)
    out = driver.run(ctx)  # -> dict: attempted, failed, end_to_end, spans...
    say(f"[setup] {out['end_to_end']['setup_s']:.2f}s = " + clock.line())

    e2e = cell_metrics(manifest, "end_to_end", cell["name"])
    if args.trace:
        ctx.run = out
        ctx.trace_summary = None
        if out.get("trace_path"):
            from benchmark import trace_reduce

            try:
                ctx.trace_summary = trace_reduce.reduce_file(
                    out["trace_path"], out.get("trace_window"))
            except ValueError:
                if not args.rehearse_cpu:  # a chip run must hold a chip's plane
                    raise
        reported = {m["name"] for m in e2e if m["name"] in out["end_to_end"]}
        metrics = {}
        for m in cell_metrics(manifest, "per_layer", cell["name"], reported):
            value = load_py("readers", m["name"], overlay).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(out["end_to_end"][m["name"]]),
                               "unit": m["unit"]}
                   for m in e2e if m["name"] in out["end_to_end"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(cell["chips"]),
              "memory_peak_bytes": int(out.get("memory_peak_bytes") or 0)}
    result = {"correct": bool(ctx.checks.ok), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if args.trace and ctx.trace_summary is not None:
        device["busy_s"] = ctx.trace_summary["busy_s"]
        device["window_s"] = ctx.trace_summary["window_s"]
        result["breakdown"] = {
            "device_ops": ctx.trace_summary["device_ops"][:10],
            "idle_gaps": ctx.trace_summary["idle_gaps"][:10]}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    # every number compared beside its limit: last in the result line,
    # and as the last lines of standard error
    result["checks"] = {key: {"value": value, "limit": limit}
                        for key, value, limit, _ in ctx.checks.rows}
    print(json.dumps(result), flush=True)
    for key, value, limit, good in ctx.checks.rows:
        print(f"[check] {key}: {value:.6g} (limit <= {limit:.6g}) "
              f"{'ok' if good else 'FAILED'}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
