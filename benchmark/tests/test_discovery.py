"""A configuration, a traffic mix, a per-layer reader — and a whole new
architecture: class, parameter table and plain reference — dropped into
the directories are found by name: one new entry each in BENCHMARK.json,
no edit to ``run.py``, ``program.py`` or any file that was there."""
import hashlib
import json
import os
import shutil

import pytest

from conftest import HERE, ROOT, run_command


def _snapshot(top: str) -> dict:
    """relative path -> digest of every file under ``top``."""
    out = {}
    for d, _, files in os.walk(top):
        for name in files:
            path = os.path.join(d, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha1(
                    f.read()).hexdigest()
    return out


def test_new_config_mix_and_reader_are_found(overlay):
    bench = os.path.join(overlay, "benchmark")
    with open(os.path.join(bench, "configs", "tiny-mistral.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny-mistral-wide"          # a new configuration ...
    cfg["intermediate_size"] = 96
    cfg["program"]["kwargs"]["mlp_dim"] = 96
    with open(os.path.join(bench, "configs", "tiny-mistral-wide.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "tiny_closed.json")) as f:
        mix = json.load(f)
    mix["clients"], mix["max_new"] = 4, 3      # ... a new mix ...
    with open(os.path.join(bench, "traffic", "tiny_closed4.json"), "w") as f:
        json.dump(mix, f)
    os.makedirs(os.path.join(bench, "readers"))
    with open(os.path.join(bench, "readers", "serve_requests_ok.py"), "w") as f:
        f.write("def read(ctx):\n"        # ... and a new counter's reader
                "    return ctx.run['counters']['requests_ok']\n")
    path = os.path.join(overlay, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "tiny-mistral-wide", "source": "fixture",
                         "file": "benchmark/configs/tiny-mistral-wide.json",
                         "reduced": [], "why": "fixture"})
    m["workloads"].append({"name": "tiny_new_cell",
                           "config": "tiny-mistral-wide",
                           "traffic": "tiny_closed4", "chips": 1,
                           "why": "fixture"})
    for metric in m["end_to_end"]:
        if metric["name"].startswith("serve_"):
            metric["workloads"].append("tiny_new_cell")
    m["per_layer"].append({"name": "serve_requests_ok", "unit": "requests",
                           "better": "higher", "source": "program_counter",
                           "layer": "serving", "moves": "serve_tokens_per_s",
                           "workloads": ["tiny_new_cell"]})
    with open(path, "w") as f:
        json.dump(m, f)

    rc, obj, log = run_command(overlay, "tiny_new_cell", trace=0)
    assert rc == 0 and obj["correct"], log
    assert obj["metrics"]["serve_tokens_per_s"]["value"] > 0
    rc, obj, log = run_command(overlay, "tiny_new_cell", trace=1)
    assert rc == 0, log
    assert obj["metrics"]["serve_requests_ok"]["value"] == obj["attempted"]
    assert obj["metrics"]["serve_requests_ok"]["unit"] == "requests"
    # the old metrics that list the old cells only are not reported here
    assert "serve_queue_pct" not in obj["metrics"]


def _add_newarch(overlay):
    """Lay the new architecture's own files over the overlay — nothing
    that was there is edited — and add its cell to the manifest."""
    shutil.copytree(os.path.join(HERE, "newarch"), overlay, dirs_exist_ok=True)
    path = os.path.join(overlay, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "tiny-rmsgelu", "source": "fixture",
                         "file": "benchmark/configs/tiny-rmsgelu.json",
                         "reduced": [], "why": "fixture"})
    m["workloads"].append({"name": "tiny_newarch_cell",
                           "config": "tiny-rmsgelu", "traffic": "tiny_closed",
                           "chips": 1, "why": "fixture"})
    for metric in m["end_to_end"]:
        if metric["name"].startswith("serve_"):
            metric["workloads"].append("tiny_newarch_cell")
    with open(path, "w") as f:
        json.dump(m, f)
    with open(os.path.join(overlay, "benchmark/configs/tiny-rmsgelu.json")) as f:
        return json.load(f)


def _as_a_later_prs_cell(tmp_path) -> str:
    """A copy of the checkout's benchmark to which a later PR ADDS the
    new architecture as a real cell — configuration, reference, mix,
    manifest entries — and its toy twin as a directory of files under
    ``benchmark/tests/``; no file that was there is edited."""
    import pytest

    import test_manifest

    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _snapshot(root)
    bench, new = os.path.join(root, "benchmark"), os.path.join(HERE, "newarch")
    twin = os.path.join(bench, "tests", "rmsgelu_twin")
    shutil.copytree(new, twin)
    shutil.copy(os.path.join(new, "benchmark/reference/rmsgelu.py"),
                os.path.join(bench, "reference"))
    with open(os.path.join(new, "benchmark/configs/tiny-rmsgelu.json")) as f:
        cfg = json.load(f)
    cfg.update(name="rmsgelu-1b", reduced={}, assumed={}, deployment="fixture")
    with open(os.path.join(bench, "configs", "rmsgelu-1b.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(bench, "traffic", "closed64_p128_n96.json"),
                os.path.join(bench, "traffic", "closed64_rmsgelu.json"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        m = json.load(f)
    m["configs"].append({"name": "rmsgelu-1b", "source": cfg["source"],
                         "file": "benchmark/configs/rmsgelu-1b.json",
                         "reduced": [], "why": "fixture"})
    m["workloads"].append({"name": "rmsgelu_serve_sat", "config": "rmsgelu-1b",
                           "traffic": "closed64_rmsgelu", "chips": 1,
                           "why": "fixture"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    with pytest.raises(AssertionError, match="toy twin"):   # not yet
        test_manifest.named_files_are_there(root)
    with open(os.path.join(twin, "BENCHMARK.template.json"), "w") as f:
        json.dump({"configs": [{"name": "tiny-rmsgelu", "source": "fixture",
                                "file": "benchmark/configs/tiny-rmsgelu.json",
                                "reduced": [], "why": "fixture"}],
                   "workloads": [{"name": "tiny_rmsgelu_sat",
                                  "config": "tiny-rmsgelu",
                                  "traffic": "tiny_closed_sat", "chips": 1,
                                  "why": "fixture",
                                  "twin_of": "rmsgelu_serve_sat"}]}, f)
    after = _snapshot(root)
    assert {k: after[k] for k in before if k != "BENCHMARK.json"} == {
        k: v for k, v in before.items() if k != "BENCHMARK.json"}
    return root


def test_new_architecture_is_found(overlay, tmp_path):
    """A block neither old table described (RMSNorm gains, bias-free
    rotary attention, a two-matrix GELU MLP, no position table): its
    configuration carries the class and the parameter table, its plain
    reference lies beside the manifest, and the cell runs ``correct``.
    Brought as a real cell with its toy twin as files, it passes
    ``test_manifest.py`` with no edit to a file that was there."""
    import test_manifest

    root = _as_a_later_prs_cell(tmp_path)
    test_manifest.named_files_are_there(root)
    test_manifest.paths_are_the_recorded_ones(root)
    before = _snapshot(overlay)
    _add_newarch(overlay)
    assert {k: v for k, v in _snapshot(overlay).items()
            if k in before and k != "BENCHMARK.json"} == {
        k: v for k, v in before.items() if k != "BENCHMARK.json"}
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "reference", "rmsgelu.py"))
    rc, obj, log = run_command(overlay, "tiny_newarch_cell", trace=0)
    assert rc == 0 and obj["correct"], log
    assert obj["failed"] == 0 and obj["attempted"] > 0
    assert obj["metrics"]["serve_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["left_out", "unknown_to_the_model",
                                   "unknown_to_the_reference"])
def test_a_wrong_parameter_table_is_an_error(overlay, fault):
    """Strict both ways: a parameter the table leaves out, or one it
    names that the model (or the reference) lacks, fails in
    ``build_model`` with both trees in the message."""
    from benchmark import program

    cfg = _add_newarch(overlay)
    block = cfg["program"]["params"]["layers"]["block"]
    if fault == "left_out":
        del block["attn.wo"]
    elif fault == "unknown_to_the_model":
        block["mlp.b_fc"] = ["3", "no_such_leaf"]
    else:
        block["attn.bq"] = ["1", "bq"]
    with pytest.raises(ValueError) as err:
        program.build_model(cfg, 3000000023,
                            ref=program.reference_for(cfg, overlay))
    text = str(err.value)
    assert "program {" in text and "vs reference {" in text
    if fault == "left_out":
        assert "only in the reference ['h.0.attn.wo'" in text
    if fault == "unknown_to_the_reference":
        assert "only in program.params ['h.0.attn.bq'" in text


def test_layers_of_several_kinds():
    """``kinds`` as a period: layer ``i`` takes the table of kind
    ``kinds[i % len]``; ``L`` and ``L+n`` count from the depth the
    table's ``depth`` key names."""
    from benchmark import program

    cfg = {"num_hidden_layers": 5,
           "program": {"params": {
               "depth": "num_hidden_layers",
               "top": {"embed": ["0", "weight"], "norm": ["L+1", "weight"],
                       "last": ["L"]},
               "first_layer": 1, "kinds": ["mix", "mix", "attn"],
               "layers": {"mix": {"in_proj": ["0", "w"]},
                          "attn": {"wq": ["1", "wq"], "wk": ["1", "wk"]}}}}}
    got = program.paths(cfg)
    assert got["norm"] == ("6", "weight") and got["last"] == ("5",)
    assert [k for k in got if k.startswith("h.")] == [
        "h.0.in_proj", "h.1.in_proj", "h.2.wq", "h.2.wk", "h.3.in_proj",
        "h.4.in_proj"]
    assert got["h.2.wk"] == ("3", "1", "wk") and got["h.4.in_proj"] == ("5", "0", "w")
