"""A configuration, a traffic mix and a per-layer reader dropped into
the directories are found by name: one new entry each in BENCHMARK.json,
no edit to ``run.py`` or to any file that was there."""
import json
import os

from conftest import run_command


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
