#!/usr/bin/env python3
"""The generate program each SERVING cell dispatches, compiled at its
real configuration on the device this runs on, as digests — for holding
a refactor of the decode path against its parent ON THE CHIP, where the
TPU arms (``gqa_attend``, ``latent_attend``, ``grouped_decode`` / ``gmm``,
``sinkhorn``) are chosen and ``tools/program_fingerprint.py`` (the CPU's
branches) cannot see them.  For every cell of ``BENCHMARK.json`` whose
traffic is served: the model of its configuration (weights drawn and
dropped: the program is lowered on shapes), the greedy program of its
``max_batch`` x ``prompt_len`` bucket with ``max_new`` steps, and of the
compiled HLO

* ``text``: sha256 with the module's tables of files, functions and
  stack frames, every ``metadata={...}`` (source file and line,
  ``op_name``) and every Mosaic payload (``backend_config`` of a
  ``tpu_custom_call``: it carries the Python traceback of the kernel's
  call, file and line) taken out — what the device runs;
* ``scopes``: sha256 of the sorted multiset of ``op_name``;
* ``kernels``: the ``tpu_custom_call`` lines it holds, counted.

    python tools/decode_program_text.py [<checkout>] [--small | --cut]
        [--topology v5e:2x2] [--workloads a,b]
        [--out chiprun_out/decode_program_text.json]

Run it on the parent's checkout (``.bench_parent``) and on this one in
ONE chip call and compare the files: equal digests are programs a change
did not move.  Without a chip, ``--topology v5e:2x2`` compiles for that
DESCRIBED chip, the TPU's branches taken as on it (as
``tests/test_tpu_compile.py`` does), and ``--cut`` keeps each cell's
rows, cache, widths and heads but one layer of each kind, 1024 words and
eight held experts, so that the sandbox never holds a model.
``--small`` cuts every configuration to toy widths (the fingerprint's),
for a rehearsal of the command itself.  Nothing it prints is a
measurement.
"""
import argparse
import hashlib
import importlib
import json
import os
import re
import sys

_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")
#: FileNames / FunctionNames / FileLocations / StackFrames, up to the
#: blank line that ends each
_TABLES = re.compile(r"^(?:FileNames|FunctionNames|FileLocations|"
                     r"StackFrames)\n(?:.+\n)*", re.M)
#: --cut: depth, vocabulary and held experts; every width stays
CUT = {"vocab_size": 1024, "num_layers": 2, "layer_switch": 2,
       "layer_types": ["conv", "full_attention"], "held": [0, 8]}
_PAYLOAD = re.compile(r'(custom_call_target="tpu_custom_call".*?)'
                      r'backend_config=.*$', re.M)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("checkout", nargs="?", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".."))
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--cut", action="store_true")
    ap.add_argument("--topology", default="")
    ap.add_argument("--workloads", default="")
    ap.add_argument("--out", default="chiprun_out/decode_program_text.json")
    ap.add_argument("--texts", default="", help="directory for the bare "
                    "texts themselves, to diff where digests differ")
    args = ap.parse_args()
    root, out = os.path.abspath(args.checkout), os.path.abspath(args.out)
    args.texts = args.texts and os.path.abspath(args.texts)
    sys.path.insert(0, root)
    os.chdir(root)
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.generate import make_generate
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from program_fingerprint import _OP_NAME, small

    def sha(text):
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    with open("BENCHMARK.json") as f:
        manifest = json.load(f)
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    wanted = set(filter(None, args.workloads.split(",")))
    where = {}
    if args.topology:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=args.topology)
        where = {"sharding": SingleDeviceSharding(topo.devices[0])}
        jax.default_backend = lambda: "tpu"     # the chip's branches
    found = {"device": (f"described {args.topology}" if args.topology
                        else jax.devices()[0].device_kind)}

    def S(shape=(), dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, **where)
    models = {}     # configuration -> (model without weights, shapes)
    for w in manifest["workloads"]:
        with open(f"benchmark/traffic/{w['traffic']}.json") as f:
            traffic = json.load(f)
        if (not traffic["driver"].startswith("serve")
                or wanted and w["name"] not in wanted):
            continue
        if w["config"] not in models:
            with open(files[w["config"]]) as f:
                program = json.load(f)["program"]
            mod, cls = program["class"].split(":")
            kwargs = program["kwargs"]
            if args.small:
                kwargs = small(kwargs)
            elif args.cut:
                kwargs = {k: CUT.get(k, v) for k, v in kwargs.items()}
            model = getattr(importlib.import_module(mod), cls)(**kwargs)
            params = jax.tree_util.tree_map(lambda a: S(a.shape, a.dtype),
                                            model.param_tree())
            model.set_param_tree(jax.tree_util.tree_map(
                lambda a: jnp.zeros((0,), a.dtype), params))
            models[w["config"]] = model, params
        model, params = models[w["config"]]
        batch, prompt, new = (traffic["max_batch"], traffic["prompt_len"],
                              traffic["max_new"])
        if args.small:
            batch, prompt, new = 8, 40, 9
        gen = make_generate(model,
                            compute_dtype=jnp.dtype(traffic["generate_dtype"]))
        run = [c.cell_contents for c in gen.__closure__
               if hasattr(c.cell_contents, "lower")][0]
        text = run.lower(
            params, S((batch, prompt)), new, S((2,), jnp.uint32),
            S(dt=jnp.float32), 0, S(dt=jnp.float32), S(), S(), True,
            False).compile().as_text()
        bare = _TABLES.sub("", _METADATA.sub("", _PAYLOAD.sub(r"\1", text)))
        found[w["name"]] = {
            "bucket": [batch, prompt, new], "text": sha(bare),
            "scopes": sha("\n".join(sorted(_OP_NAME.findall(text)))),
            "kernels": len(_PAYLOAD.findall(text))}
        print(w["name"], found[w["name"]], flush=True)
        if args.texts:
            os.makedirs(os.path.abspath(args.texts), exist_ok=True)
            with open(os.path.join(os.path.abspath(args.texts),
                                   w["name"] + ".txt"), "w") as f:
                f.write(bare)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(found, f, indent=1, sort_keys=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
