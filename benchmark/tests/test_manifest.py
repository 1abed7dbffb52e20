"""``BENCHMARK.json`` against the contract it is read by, and against the
files it names: every cell's configuration, traffic mix and driver, and
every per-layer metric's reader, are found by name."""
import json
import os
import re

from conftest import HERE, ROOT, twins

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = {"hidden_size", "intermediate_size", "n_embd", "n_inner",
          "num_experts_per_tok", "d_model", "d_ff"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_shape_of_the_manifest():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmark"] and 1 <= m["run_seconds"] <= 51
    assert all(one_line(w) for w in m["command"]) and len(m["command"]) <= 32
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith("benchmark/") and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) or k in WIDTHS
                       for k in c["reduced"])         # a width is never cut
    cells = m["workloads"]
    assert 1 <= len(cells) <= 24
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and one_line(w["why"])
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    assert {w["config"] for w in cells} == {c["name"] for c in m["configs"]}
    names = [x["name"] for g in ("configs", "workloads") for x in m[g]]
    metric_names = [x["name"] for g in ("end_to_end", "per_layer") for x in m[g]]
    assert len(set(names)) == len(names)
    assert len(set(metric_names)) == len(metric_names)


def test_metrics():
    m = manifest()
    cell_names = {w["name"] for w in m["workloads"]}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher") and 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["source"] in SOURCES and one_line(x["layer"])
        assert x["moves"] in e2e
        if x["name"].endswith("_roofline"):
            assert x["unit"] == "%"
        for cell in x.get("workloads", cell_names):
            assert cell in cell_names
            assert cell in e2e[x["moves"]].get("workloads", cell_names), (
                x["name"], cell)
    for cell in cell_names:   # setup_s, one more end-to-end, one per-layer
        assert any(cell in x.get("workloads", cell_names)
                   for x in m["end_to_end"] if x["name"] != "setup_s")
        assert any(cell in x.get("workloads", cell_names) for x in m["per_layer"])


def named_files_are_there(root=ROOT):
    """``root``: the checkout (``test_discovery`` hands a copy to which
    a later PR's files were added)."""
    m = manifest(root)
    bench = os.path.join(root, "benchmark")
    for c in m["configs"]:
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])     # every cut, with its reason
        assert "assumed" in cfg and "deployment" in cfg
        assert os.path.exists(os.path.join(bench, "reference", cfg["reference"] + ".py"))
    from benchmark import run

    tests = os.path.join(bench, "tests")
    twin = twins(tests)
    for w in m["workloads"]:
        mix = run.load_json(run.find("traffic", w["traffic"], ".json", root))
        assert hasattr(run.load_py("drivers", mix["driver"], root), "run")
        assert "limits" in mix
        assert w["name"] in twin, "every real cell has a toy twin"
        d, toy = twin[w["name"]]    # its files: its own, or tiny/'s below it
        for kind, key in (("configs", "config"), ("traffic", "traffic")):
            assert any(os.path.exists(os.path.join(
                tests, base, "benchmark", kind, toy[key] + ".json"))
                for base in (d, "tiny")), (w["name"], toy[key])
    for x in m["per_layer"]:
        assert os.path.exists(os.path.join(bench, "readers", x["name"] + ".py")), x["name"]


def test_every_named_file_is_there():
    named_files_are_there()
    open_mix = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "open_p128_n96.json")))
    assert abs(open_mix["rate_rps"] - 0.8 * open_mix["knee_rps"]) < 1e-9  # stored, from the sweep


def paths_are_the_recorded_ones(root=ROOT):
    from benchmark import program

    with open(os.path.join(HERE, "recorded", "paths_pr25.json")) as f:
        recorded = json.load(f)
    m = manifest(root)
    assert set(recorded) <= {c["name"] for c in m["configs"]}
    for c in m["configs"]:
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["program"]["class"].count(":") == 1
        if c["name"] in recorded:
            got = program.paths(cfg)
            assert [(k, list(v)) for k, v in got.items()] == list(
                recorded[c["name"]].items()), c["name"]


def test_parameter_paths_are_the_recorded_ones():
    """The tables in the configuration files of the two configurations
    PR 25 had give, entry for entry and in the same order, the maps
    ``program.py`` held in code up to then: weights, programs and
    compile-cache keys are that parent's.  A configuration added since
    has no recorded map and only has to name its class."""
    paths_are_the_recorded_ones()


def test_the_saturated_cell_and_its_twin():
    """``mistral7b_serve_decode_sat`` is the decode cell's shapes and
    limits under a closed loop of four batches' worth of callers; its
    toy twin keeps that ratio."""
    bench = os.path.join(ROOT, "benchmark", "traffic")
    sat = json.load(open(os.path.join(bench, "closed64_p128_n96.json")))
    dec = json.load(open(os.path.join(bench, "open_p128_n96.json")))
    for key in ("prompt_len", "max_new", "max_batch", "generate_dtype",
                "check_requests", "reference_rows", "limits"):
        assert sat[key] == dec[key], key
    assert sat["driver"] == "serve_closed"
    assert sat["clients"] == 4 * sat["max_batch"] == 64
    twin = json.load(open(os.path.join(
        HERE, "tiny", "benchmark", "traffic", "tiny_closed_sat.json")))
    assert twin["driver"] == "serve_closed"
    assert twin["clients"] == 4 * twin["max_batch"]
