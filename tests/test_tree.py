"""The checkout's own promises (also without ``.git``): no file too large to
copy to the chip, documented commands exist, one v5e peak in two tables."""
import fnmatch
import glob
import importlib.util
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, ".gitignore")) as _f:
    _IGNORED = _f.read().split()
#: `python x/y.py`, `python -m a.b`, `[dir/]tools/name.ext`
_COMMAND = re.compile(r"python3? (?:-m ([\w.]+)|([\w./-]+\.py))"
                      r"|(?<![\w/.-])((?:[\w.-]+/)*tools/[\w-]+\.\w+)")
#: docs/PERF.md is history (ROADMAP D9): it names the tools of its rounds
_DOCS = [p for p in ["README.md", "PERF.md", ".claude/skills/verify/SKILL.md"]
         + sorted(glob.glob("docs/*.md", root_dir=REPO))
         if p != "docs/PERF.md" and os.path.exists(os.path.join(REPO, p))]


def _commands(doc):
    with open(os.path.join(REPO, doc)) as f:
        return {m.groups() for m in _COMMAND.finditer(f.read())}


def test_no_file_over_1_mib():
    big = []
    for dirpath, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d != ".git" and d + "/" not in _IGNORED]
        big += [os.path.join(dirpath, n) for n in files
                if not any(fnmatch.fnmatch(n, p) for p in _IGNORED)
                and os.path.getsize(os.path.join(dirpath, n)) > 1 << 20]
    assert not big, f"over 1 MiB, and copied to every chip run: {big}"


@pytest.mark.parametrize("doc", [d for d in _DOCS if _commands(d)])
def test_documented_commands_exist(doc):
    missing = []
    for module, script, tool in _commands(doc):
        path = os.path.join(REPO, module.replace(".", "/") if module
                            else script or tool)
        if not (os.path.exists(path) or os.path.exists(path + ".py")
                # or installed: pytest
                or module and importlib.util.find_spec(module.split(".")[0])):
            missing.append(module or script or tool)
    assert not missing, f"{doc} teaches commands that are gone: {missing}"


def test_one_v5e_peak():
    """Two tables hold the v5e's peaks (PERF.md §7): equal until one can
    go.  Not the interconnect: 400e9 bytes/s here, 1600e9 bits/s there."""
    from bigdl_tpu.telemetry.device_info import device_spec

    spec = device_spec("TPU v5 lite")
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        row = next(r for r in json.load(f)["rows"]
                   if r["match"] == "v5 lite")
    assert (spec.peak_flops_per_sec, spec.hbm_bytes,
            spec.hbm_bytes_per_sec) == (
        row["bf16_flops_per_s"], row["hbm_bytes"], row["hbm_bytes_per_s"])


#: what ``models/generate.py`` and ``serving/server.py`` may not name: a
#: block's or an operator's kind, a cache leaf of ONE architecture, a
#: block's private child index — a layer's decode state lives with the
#: layer (``nn/attention.py``, the decode-state protocol), and a new
#: architecture edits neither file
_KINDS = {"hybrid_mamba", "parallel_moe", "sequential_moe", "latent",
          "short_conv", "hybrid", "parallel", "sequential", "gated",
          "swiglu", "gelu"}
_LEAVES = {"ckv", "kr", "ssm", "conv", "mhc_err", "mhc_sinkhorn_err"}


@pytest.mark.parametrize("path", ["bigdl_tpu/models/generate.py",
                                  "bigdl_tpu/serving/server.py"])
def test_the_decoders_ask_the_layers_and_never_look(path):
    import ast

    with open(os.path.join(REPO, path)) as f:
        src = f.read()
    tree = ast.parse(src)
    named = {n.value for n in ast.walk(tree)
             if isinstance(n, ast.Constant) and isinstance(n.value, str)}
    assert not named & (_KINDS | _LEAVES), sorted(named & (_KINDS | _LEAVES))
    assert not {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)
                } & {"mlp_kind", "ffn_kind", "is_hybrid", "hyper", "mixer"}
    assert not re.findall(r'\bbp\["\d"\]|\.modules\[\d', src)
    if path.endswith("generate.py"):
        # of models/ it imports nothing but the base the containers share
        local = {a.name for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom) and (
                     n.level == 1 or (n.module or "").startswith("models"))
                 for a in n.names}
        assert local <= {"CausalLM"}, local
