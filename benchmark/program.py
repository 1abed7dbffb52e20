"""The system under test, built from a configuration file.

The only place the benchmark touches the program's model, and it knows
no architecture: the configuration's own ``program`` object names the
constructor (``class``, ``"package.module:Name"``), its ``kwargs``, and
``params`` — the relabelling between the reference's flat parameter
names and the program's parameter tree:

    "params": {
      "depth":  "num_hidden_layers",
      "top":    {"embed": ["0", "weight"], "norm": ["L+1", "weight"], ...},
      "first_layer": 1,
      "kinds":  "block",
      "layers": {"block": {"attn.wq": ["1", "wq"], ...}}
    }

``depth`` names the configuration's key that holds the number of
layers.  ``top`` maps each top-level name to its path in the tree;
``layers`` does the same for each KIND of layer, below that layer's own
child, which is child ``first_layer + i`` for layer ``i``.  ``kinds``
says which kind each layer is: one name for all, or a list that is
repeated down the stack (a whole list or one period of it).  A path
element ``L`` or ``L+n`` counts from the number of layers, so one table
serves every depth.  The weights are the BENCHMARK's
(``reference.common.seeded_leaf``): the program is given them, the
reference makes its own from the same seed.
"""
from __future__ import annotations

import importlib
import importlib.util
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
_REL = re.compile(r"^L(?:\+(\d+))?$")


def reference_for(cfg: dict, overlay: str | None = None):
    """``reference/<name>.py`` — beside the manifest first (a test's or
    a later PR's own file), then in this directory, as ``run.find``
    looks for a driver or a reader."""
    name = cfg["reference"]
    path = overlay and os.path.join(os.path.abspath(overlay), "benchmark",
                                    "reference", name + ".py")
    if not path or path.startswith(HERE + os.sep) or not os.path.exists(path):
        return importlib.import_module(f"benchmark.reference.{name}")
    # under the package's own name, so that its relative imports
    # (``from .common import mm``) find the benchmark's; loaded once
    modname = f"benchmark.reference.overlay_{name}"
    if getattr(sys.modules.get(modname), "__file__", None) != path:
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[modname] = mod
    return sys.modules[modname]


def _element(el: str, n_layers: int) -> str:
    m = _REL.match(el)
    return str(n_layers + int(m.group(1) or 0)) if m else el


def paths(cfg: dict) -> dict:
    """Every flat reference name -> its path in the program's tree."""
    table = cfg["program"]["params"]
    n = int(cfg[table["depth"]])
    kinds = table["kinds"]
    if isinstance(kinds, str):
        kinds = [kinds]
    out = {name: tuple(_element(el, n) for el in path)
           for name, path in table["top"].items()}
    for i in range(n):
        child = str(table["first_layer"] + i)
        for name, sub in table["layers"][kinds[i % len(kinds)]].items():
            out[f"h.{i}.{name}"] = (child,) + tuple(sub)
    return out


def _nest(table: dict, flat: dict) -> dict:
    tree: dict = {}
    for name, path in table.items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = flat[name]
    return tree


def to_tree(cfg: dict, flat: dict) -> dict:
    return _nest(paths(cfg), flat)


def from_tree(cfg: dict, tree: dict) -> dict:
    flat = {}
    for name, path in paths(cfg).items():
        node = tree
        for key in path:
            node = node[key]
        flat[name] = node
    return flat


def model_class(cfg: dict):
    module, _, name = cfg["program"]["class"].partition(":")
    return getattr(importlib.import_module(module), name)


def build_model(cfg: dict, seed: int, clock=None, ref=None):
    """The program's model, holding the benchmark's seeded weights;
    ``ref`` is the configuration's reference where the caller has found
    it already (``reference_for`` with the manifest's directory).

    The constructor draws its own initial weights first (the program's
    behaviour; they are dropped before ours go in).  Ours go in leaf by
    leaf: seeded in float32, cast to the dtype the constructor's own
    tree holds that leaf in, the float32 array dropped before the next
    is made — set-up costs the bytes the configuration states plus ONE
    float32 leaf, never a float32 copy of the model.  The tree
    structures must match exactly — a parameter the reference does not
    know, or the other way round, is an error, not a default."""
    import jax

    from .reference import common

    ref = ref or reference_for(cfg)
    model = model_class(cfg)(**cfg["program"]["kwargs"])
    own = model.param_tree()
    if clock is not None:
        jax.block_until_ready(own)
        clock.mark("model constructor (draws its own weights)")
    shapes = jax.tree_util.tree_map(lambda a: tuple(a.shape), own)
    dtypes = jax.tree_util.tree_map(lambda a: a.dtype, own)
    # let go of the constructor's arrays before ours are made, so that
    # set-up never holds two copies of the weights
    model.set_param_tree(jax.tree_util.tree_map(
        lambda a: jax.numpy.zeros((0,), a.dtype), own))
    del own
    specs = common.flat_specs(ref.param_specs(cfg), ref.n_layers(cfg))
    table = paths(cfg)
    known = {k: p for k, p in table.items() if k in specs}
    ours = _nest(known, {k: tuple(shape) for k, (shape, _) in specs.items()})
    if ours != shapes or set(table) != set(specs):
        raise ValueError("the configuration's reference and the "
                         "program's model disagree on the parameter "
                         f"tree: program {shapes} vs reference {ours}; "
                         "names only in program.params "
                         f"{sorted(set(table) - set(specs))}, only in the "
                         f"reference {sorted(set(specs) - set(table))}")
    held = from_tree(cfg, dtypes)
    flat = {}
    for name, (shape, kind) in specs.items():
        leaf = common.seeded_leaf(seed, name, shape, kind,
                                  cfg["initializer_range"])
        # wait for the cast before the float32 array goes: the next
        # leaf's program must not be given memory this one still holds
        flat[name] = jax.block_until_ready(leaf.astype(held[name]))
        del leaf
    model.set_param_tree(_nest(table, flat))
    if clock is not None:
        clock.mark("seeded weights, leaf by leaf in the dtype held")
    return model
