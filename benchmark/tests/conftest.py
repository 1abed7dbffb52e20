"""``JAX_PLATFORMS=cpu pytest benchmark/tests -q`` — by hand, not part of
tier-1.  Every test drives the real command (or ``run.main``) at a toy
size on the CPU backend; nothing here is a measurement."""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TEMPLATE = "BENCHMARK.template.json"


def twin_dirs(tests_dir: str = HERE) -> dict:
    """directory -> its ``BENCHMARK.template.json``: toy ``configs`` and
    ``workloads``, each workload naming under ``twin_of`` the real cell
    it is the toy twin of.  A listing, so a PR that adds a cell brings
    its twin as a directory of files and edits nothing here."""
    out = {}
    for name in sorted(os.listdir(tests_dir)):
        path = os.path.join(tests_dir, name, TEMPLATE)
        if os.path.exists(path):
            with open(path) as f:
                out[name] = json.load(f)
    return out


def twins(tests_dir: str = HERE) -> dict:
    """real cell -> (directory, toy workload entry) of every twin."""
    return {w["twin_of"]: (d, w)
            for d, part in twin_dirs(tests_dir).items()
            for w in part["workloads"] if "twin_of" in w}


def tiny_manifest(dst: str, extra=()) -> dict:
    """Copy the toy configurations and mixes of ``tiny/`` (and of the
    ``extra`` twin directories, laid over it) to ``dst`` and write a
    manifest there that pairs them with the REAL metric lists; a real
    cell with no toy twin among them is left out."""
    parts = twin_dirs()
    tiny, rename = dict(parts["tiny"], configs=[], workloads=[]), {}
    for d in ("tiny",) + tuple(extra):
        shutil.copytree(os.path.join(HERE, d), dst, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns(TEMPLATE))
        tiny["configs"] += parts[d]["configs"]
        for w in parts[d]["workloads"]:
            w = dict(w)
            rename[w.pop("twin_of")] = w["name"]
            tiny["workloads"].append(w)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    for group in ("end_to_end", "per_layer"):
        tiny[group] = [
            dict(m, workloads=[rename[w] for w in m["workloads"]
                               if w in rename])
            if "workloads" in m else dict(m) for m in real[group]]
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(tiny, f, indent=1)
    return tiny


@pytest.fixture()
def overlay(tmp_path):
    dst = str(tmp_path / "overlay")
    tiny_manifest(dst)
    return dst


def run_command(overlay: str, cell: str, seed: int = 3000000019,
                trace: int = 0, seconds: float = 1.0, extra=()):
    """The whole command in a child process; returns (rc, last-line
    object or None, stdout)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--manifest", os.path.join(overlay, "BENCHMARK.json"),
         "--rehearse-cpu", "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), *extra],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    obj = None
    if p.returncode == 0 and lines:
        obj = json.loads(lines[-1])
    return p.returncode, obj, p.stdout + p.stderr[-3000:]
