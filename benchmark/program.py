"""The system under test, built from a configuration file.

The only place the benchmark touches the program's classes: the model
constructor (``program.kwargs`` of the configuration), the relabelling
between the references' flat parameter names and the module tree
(``program.layout``), and the seeded weights handed over.  The weights
are the BENCHMARK's (``reference.common.make_params``): the program is
given them, the reference makes its own from the same seed.
"""
from __future__ import annotations

import importlib


def reference_for(cfg: dict):
    return importlib.import_module(f"benchmark.reference.{cfg['reference']}")


# flat reference name -> path in one TransformerBlock's param tree
_BLOCK = {
    "gpt2": {"ln_1.g": ("0", "weight"), "ln_1.b": ("0", "bias"),
             "attn.wq": ("1", "wq"), "attn.bq": ("1", "bq"),
             "attn.wk": ("1", "wk"), "attn.bk": ("1", "bk"),
             "attn.wv": ("1", "wv"), "attn.bv": ("1", "bv"),
             "attn.wo": ("1", "wo"), "attn.bo": ("1", "bo"),
             "ln_2.g": ("2", "weight"), "ln_2.b": ("2", "bias"),
             "mlp.w_fc": ("3", "weight"), "mlp.b_fc": ("3", "bias"),
             "mlp.w_proj": ("4", "weight"), "mlp.b_proj": ("4", "bias")},
    "llama": {"input_norm": ("0", "weight"),
              "attn.wq": ("1", "wq"), "attn.wk": ("1", "wk"),
              "attn.wv": ("1", "wv"), "attn.wo": ("1", "wo"),
              "post_norm": ("2", "weight"), "mlp.gate": ("3", "weight"),
              "mlp.up": ("4", "weight"), "mlp.down": ("5", "weight")},
}


def _top(layout: str, n_layers: int) -> dict:
    """flat top-level name -> path in the TransformerLM param tree
    (children keyed by index: 0 embedding, 1..L blocks, L+1 final norm,
    L+2 head; GPT-2's position table is the model's own ``pos``)."""
    nf, hd = str(n_layers + 1), str(n_layers + 2)
    if layout == "gpt2":
        return {"wte": ("0", "weight"), "wpe": ("pos",),
                "ln_f.g": (nf, "weight"), "ln_f.b": (nf, "bias"),
                "lm_head": (hd, "weight")}
    return {"embed": ("0", "weight"), "norm": (nf, "weight"),
            "lm_head": (hd, "weight")}


def paths(cfg: dict) -> dict:
    """Every flat reference name -> its path in the program's tree."""
    layout = cfg["program"]["layout"]
    n = reference_for(cfg).n_layers(cfg)
    out = dict(_top(layout, n))
    for i in range(n):
        for name, sub in _BLOCK[layout].items():
            out[f"h.{i}.{name}"] = (str(i + 1),) + sub
    return out


def to_tree(cfg: dict, flat: dict) -> dict:
    tree: dict = {}
    for name, path in paths(cfg).items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = flat[name]
    return tree


def from_tree(cfg: dict, tree: dict) -> dict:
    flat = {}
    for name, path in paths(cfg).items():
        node = tree
        for key in path:
            node = node[key]
        flat[name] = node
    return flat


def build_model(cfg: dict, seed: int, clock=None):
    """The program's model, holding the benchmark's seeded weights.

    The constructor draws its own initial weights first (the program's
    behaviour; they are dropped leaf by leaf as ours go in).  The tree
    structures must match exactly — a parameter the reference does not
    know, or the other way round, is an error, not a default."""
    import jax

    from bigdl_tpu.models.transformer import TransformerLM

    from .reference import common

    ref = reference_for(cfg)
    model = TransformerLM(**cfg["program"]["kwargs"])
    own = model.param_tree()
    if clock is not None:
        jax.block_until_ready(own)
        clock.mark("model constructor (draws its own weights)")
    shapes = jax.tree_util.tree_map(lambda a: tuple(a.shape), own)
    # let go of the constructor's arrays before ours are made, so that
    # set-up never holds two copies of the weights
    model.set_param_tree(jax.tree_util.tree_map(
        lambda a: jax.numpy.zeros((0,), a.dtype), own))
    del own
    flat = common.make_params(ref.param_specs(cfg), ref.n_layers(cfg),
                              cfg["initializer_range"], seed)
    tree = to_tree(cfg, flat)
    ours = jax.tree_util.tree_map(lambda a: tuple(a.shape), tree)
    if ours != shapes:
        raise ValueError("the configuration's reference and the "
                         "program's model disagree on the parameter "
                         f"tree: program {shapes} vs reference {ours}")
    model.set_param_tree(tree)
    if clock is not None:
        jax.block_until_ready(tree)
        clock.mark("seeded weights, one jitted call")
    return model
