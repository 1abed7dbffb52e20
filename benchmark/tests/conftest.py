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

RENAME = {"gpt2m_train_1chip": "tiny_train_1chip",
          "gpt2m_train_dp4": "tiny_train_dp4",
          "mistral7b_serve_decode": "tiny_serve_open",
          "mistral7b_serve_prefill": "tiny_serve_closed",
          "mistral7b_serve_decode_sat": "tiny_serve_sat"}


def tiny_manifest(dst: str) -> dict:
    """Copy the toy configurations and mixes to ``dst`` and write a
    manifest there that pairs them with the REAL metric lists; a real
    cell with no toy twin (one a later PR adds) is left out."""
    shutil.copytree(os.path.join(HERE, "tiny"), dst, dirs_exist_ok=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(dst, "BENCHMARK.template.json")) as f:
        tiny = json.load(f)
    for group in ("end_to_end", "per_layer"):
        tiny[group] = [
            dict(m, workloads=[RENAME[w] for w in m["workloads"]
                               if w in RENAME])
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
