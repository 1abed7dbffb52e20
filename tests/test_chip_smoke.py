"""chip_smoke.py off the chip, and the compile-cache rule it shares
with every entry point.

The chip run itself is the chip tool's business; here: the toy-size CPU
rehearsal walks the same control flow and names its device, the default
invocation refuses a CPU, and ``ensure_compile_cache`` leaves jax alone
when ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

from bigdl_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, **env_over):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.update(env_over)
    return subprocess.run([sys.executable, SMOKE] + args,
                          capture_output=True, text=True, timeout=600,
                          cwd=REPO, env=env)


def test_cpu_rehearsal_passes_and_names_its_device(tmp_path):
    out = _run(["--rehearse-cpu"], tmp_path,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert out.returncode == 0, out.stdout + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result == {"ok": True, "rehearsal": True,
                      "device": {"platform": "cpu", "kind": "cpu",
                                 "count": 4}}
    text = out.stdout
    assert "CPU REHEARSAL" in text
    # the variable placed the cache: the script says so and set nothing
    assert "placed by JAX_COMPILATION_CACHE_DIR" in text
    # every phase ran, the four-device one included
    for tag in ("[kernels]", "[train]", "[serve]", "[train x4]"):
        assert tag in text, text
    assert "phase_source='trace'" in text
    # the checkpoint rides the in-process store (a chip machine may cap
    # file sizes) and every leg passes its crc32c sidecar
    assert "checkpoint in memory://chip_smoke/" in text
    assert "'model.5': True" in text and "'model.9': True" in text


def test_default_invocation_refuses_a_cpu(tmp_path):
    out = _run([], tmp_path)
    assert out.returncode not in (0, None)
    assert out.stdout.strip() == ""       # no result, no JSON line
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("module",
                         ["bigdl_tpu", "chip_smoke", "benchmark.run"])
def test_import_touches_no_backend(module):
    """One process per chip: an importer can still hand it to a child."""
    code = (f"import jax, {module};"
            "from jax._src import xla_bridge;"
            "assert not xla_bridge._backends, xla_bridge._backends")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stderr


def test_cache_helper_leaves_jax_alone_when_placed_from_outside(
        monkeypatch, tmp_path):
    placed = str(tmp_path / "outside")
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, placed)
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert compile_cache.ensure_compile_cache() == placed
    assert calls == []


def test_cache_helper_picks_the_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    prior = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.ensure_compile_cache()
        assert got == compile_cache.DEFAULT_CACHE_DIR
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        # idempotent: a second entry point changes nothing
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: calls.append(a))
        assert compile_cache.ensure_compile_cache() == got
        assert calls == []
    finally:
        monkeypatch.undo()
        jax.config.update("jax_compilation_cache_dir", prior)
    # the path is git-ignored
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
