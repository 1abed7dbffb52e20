"""A configuration's weights cost what the configuration states: seeded
leaf by leaf (``common.seeded_leaf``), cast to the dtype the model holds
each leaf in before the next is made (``program.build_model``), and in
the check one float32 layer at a time (``serve_check.teacher_forced``).
The one-call and whole-set forms the harness had up to PR 30 are kept
HERE as the recorded forms: the numbers may not move, bit for bit."""
import gc
import inspect
import json
import os
import weakref
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HERE

from benchmark import program
from benchmark.reference import common, falcon_h1, gpt2, mistral, serve_check

TOYS = {"gpt2": (gpt2, "tiny/benchmark/configs/tiny-gpt2.json"),
        "mistral": (mistral, "tiny/benchmark/configs/tiny-mistral.json"),
        "falcon_h1": (falcon_h1,
                      "falconh1/benchmark/configs/tiny-falcon-h1.json")}


def toy(name):
    ref, path = TOYS[name]
    with open(os.path.join(HERE, path)) as f:
        return ref, json.load(f)


# -- the recorded forms (benchmark/reference/common.py and
# serve_check.py at PR 30, letter for letter) --------------------------
def _leaf_pr30(key, name, shape, kind, std):
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return std * jax.random.normal(k, shape, jnp.float32)


@partial(jax.jit, static_argnums=(0, 1, 2, 4))
def _make_pr30(spec_items, n_layers, std, seed_pair, stacked):
    lo, hi = seed_pair
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    top, layer = spec_items
    out = {n: _leaf_pr30(key, n, shape, kind, std) for n, shape, kind in top}
    for n, shape, kind in layer:
        per = [_leaf_pr30(key, f"h.{i}.{n}", shape, kind, std)
               for i in range(n_layers)]
        if stacked:
            out[f"h.{n}"] = jnp.stack(per)
        else:
            out.update({f"h.{i}.{n}": a for i, a in enumerate(per)})
    return out


def make_params_pr30(specs, n_layers, std, seed, stacked=False):
    items = tuple(tuple((n, tuple(s), k) for n, (s, k) in specs[g].items())
                  for g in ("top", "layer"))
    seed = int(seed)
    pair = (jnp.uint32(seed & 0x7FFFFFFF), jnp.uint32(seed >> 31))
    return _make_pr30(items, int(n_layers), float(std), pair, bool(stacked))


@partial(jax.jit, static_argnums=(0, 3, 4))
def _logits_pr30(ref_cfg, params, ids, head_from, mode):
    ref, cfg_items = ref_cfg
    cfg = dict(cfg_items)
    indexed = "layer" in inspect.signature(ref.block).parameters
    h = ref.embed(params, ids, cfg)
    for i in range(ref.n_layers(cfg)):
        lp = {k.split(".", 2)[2]: v for k, v in params.items()
              if k.startswith(f"h.{i}.")}
        h = (ref.block(lp, h, cfg, mode, layer=i) if indexed
             else ref.block(lp, h, cfg, mode))
    return ref.head(params, h[:, head_from:], cfg, mode)


def teacher_forced_pr30(ref, cfg, seed, prompts0, served0, control, rows):
    params = make_params_pr30(ref.param_specs(cfg), ref.n_layers(cfg),
                              cfg["initializer_range"], seed)
    key, t0 = (ref, common.hashable(cfg)), prompts0.shape[1]
    ids = np.concatenate([prompts0, served0[:, :-1]], 1).astype(np.int32)
    outs = []
    for lo in range(0, len(ids), rows):
        blk = slice(lo, lo + rows)
        pad = rows - len(ids[blk])
        x = np.concatenate([ids[blk], ids[:pad]]) if pad else ids[blk]
        s = served0[blk].astype(np.int32)
        s = np.concatenate([s, served0[:pad].astype(np.int32)]) if pad else s
        lg = _logits_pr30(key, params, jnp.asarray(x), t0 - 1, "f32")
        best = jnp.max(lg, -1)
        below = lambda tok: best - jnp.take_along_axis(
            lg, tok[..., None], -1)[..., 0]
        o = {"gap": below(jnp.asarray(s)), "spread": best - jnp.min(lg, -1),
             "agree": jnp.argmax(lg, -1) == jnp.asarray(s)}
        if control:
            o["control_gap"] = below(jnp.argmax(_logits_pr30(
                key, params, jnp.asarray(x), t0 - 1, "fp8"), -1))
        outs.append({k: np.asarray(v)[: rows - pad] for k, v in o.items()})
    return {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}


def bits(a):
    return np.asarray(a).view(np.uint32)


# -- (a) every leaf, bit for bit ---------------------------------------
@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("name", sorted(TOYS))
def test_leaf_by_leaf_is_the_one_call_form_bit_for_bit(name, stacked):
    ref, cfg = toy(name)
    specs, n, std = ref.param_specs(cfg), ref.n_layers(cfg), cfg[
        "initializer_range"]
    for seed in (7, 3000000019):        # the second needs the high word
        want = make_params_pr30(specs, n, std, seed, stacked)
        got = common.make_params(specs, n, std, seed, stacked)
        assert set(got) == set(want)
        for k in want:
            assert got[k].dtype == want[k].dtype == jnp.float32
            assert got[k].shape == want[k].shape, k
            assert np.array_equal(bits(got[k]), bits(want[k])), k
    flat = common.flat_specs(specs, n)
    assert list(flat)[:len(specs["top"])] == list(specs["top"])
    one = next(k for k, (_, kind) in flat.items()
               if k.startswith("h.1.") and kind == "normal")
    assert np.array_equal(
        bits(common.seeded_leaf(7, one, *flat[one], std)),
        bits(make_params_pr30(specs, n, std, 7)[one]))


# -- (b) the check, layer at a time ------------------------------------
@pytest.mark.parametrize("name", ["mistral", "falcon_h1"])
def test_teacher_forced_layer_at_a_time_is_the_whole_set_form(name):
    ref, cfg = toy(name)
    rng = np.random.RandomState(5)
    prompts0 = rng.randint(0, cfg["vocab_size"], size=(6, 11))
    served0 = rng.randint(0, cfg["vocab_size"], size=(6, 5))
    want = teacher_forced_pr30(ref, cfg, 3000000019, prompts0, served0,
                               True, 4)
    got = serve_check.teacher_forced(ref, cfg, 3000000019, prompts0,
                                     served0, control=True, rows=4)
    assert set(got) == set(want) == {"gap", "spread", "agree",
                                     "control_gap"}
    assert np.array_equal(got["agree"], want["agree"])
    scale = float(want["spread"].mean())
    for k in ("gap", "spread", "control_gap"):
        assert got[k].shape == (6, 5)
        # float32 rounding of a logit, against the logits' own spread
        assert np.abs(got[k] - want[k]).max() <= 2e-6 * scale, k
    assert (want["control_gap"] > 0).any()       # the control differs
    plain = serve_check.teacher_forced(ref, cfg, 3000000019, prompts0,
                                       served0, rows=4)
    assert set(plain) == {"gap", "spread", "agree"}
    assert np.array_equal(plain["gap"], got["gap"])


def test_embed_and_head_are_given_the_leaves_they_read():
    """Tied or not, a top-level leaf is seeded for the one that reads
    it, found by tracing: GPT-2's ``embed`` reads two tables, its
    ``head`` three other leaves."""
    ref, cfg = toy("gpt2")
    top, std = ref.param_specs(cfg)["top"], cfg["initializer_range"]
    ids = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    got = serve_check._top_leaves(lambda p, x: ref.embed(p, x, cfg), top,
                                  7, std, ids)
    assert set(got) == {"wte", "wpe"}
    h = jax.ShapeDtypeStruct((2, 4, cfg["n_embd"]), jnp.float32)
    got = serve_check._top_leaves(lambda p, x: ref.head(p, x, cfg, "f32"),
                                  top, 7, std, h)
    assert set(got) == {"ln_f.g", "ln_f.b", "lm_head"}


# -- (c), (d) what build_model holds -----------------------------------
def test_build_model_never_holds_two_float32_leaves(monkeypatch):
    """A model that holds bfloat16: when a leaf is seeded, every float32
    leaf seeded before it is gone."""
    ref, cfg = toy("falcon_h1")
    cfg["program"]["kwargs"]["param_dtype"] = "bfloat16"
    real, seeded, alive_at_most = common.seeded_leaf, [], []

    def counting(seed, name, shape, kind, std):
        gc.collect()
        alive_at_most.append(sum(r() is not None for r in seeded))
        leaf = real(seed, name, shape, kind, std)
        assert leaf.dtype == jnp.float32
        seeded.append(weakref.ref(leaf))
        return leaf

    monkeypatch.setattr(common, "seeded_leaf", counting)
    model = program.build_model(cfg, 3000000019, ref=ref)
    n = len(program.paths(cfg))
    assert len(seeded) == n and max(alive_at_most) == 0
    gc.collect()
    assert not any(r() is not None for r in seeded)
    leaves = jax.tree_util.tree_leaves(model.param_tree())
    assert len(leaves) == n and {a.dtype.name for a in leaves} == {
        "bfloat16"}


@pytest.mark.parametrize("name,dtype", [("falcon_h1", "bfloat16"),
                                        ("falcon_h1", None),
                                        ("mistral", None), ("gpt2", None)])
def test_build_model_leaves_the_stated_dtype_and_the_seeded_numbers(name,
                                                                    dtype):
    """The dtype the configuration states (``param_dtype`` of
    ``HybridMambaLM``; float32 for ``TransformerLM`` and where nothing
    is stated) is what ``param_tree()`` holds, and every leaf is the
    seeded float32 leaf cast to it."""
    ref, cfg = toy(name)
    if dtype:
        cfg["program"]["kwargs"]["param_dtype"] = dtype
    model = program.build_model(cfg, 11, ref=ref)
    got = program.from_tree(cfg, model.param_tree())
    want = make_params_pr30(ref.param_specs(cfg), ref.n_layers(cfg),
                            cfg["initializer_range"], 11)
    assert set(got) == set(want)
    for k, a in got.items():
        assert a.dtype == jnp.dtype(dtype or "float32"), k
        assert np.array_equal(np.asarray(a), np.asarray(
            want[k].astype(a.dtype))), k


def test_the_room_overlay_is_the_falcon_cell_with_the_whole_vocabulary():
    """``benchmark/tests/room/`` — not a cell, the overlay PR 31 ran on
    the chip (``--manifest``): ``falcon-h1-34b-l4v4`` with ``vocab_size``
    261 120 and nothing else changed, under the real cell's traffic and
    metric lists.  4.394 B parameters: 8.79 GB held in bfloat16, 26.4 GB
    at 6 bytes a parameter, and the largest float32 leaf 5.35 GB."""
    from conftest import ROOT

    from benchmark import counts_falcon_h1

    room = os.path.join(HERE, "room")
    with open(os.path.join(room, "BENCHMARK.json")) as f:
        m = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    (cell,), (entry,) = m["workloads"], m["configs"]
    real_cell = [w for w in real["workloads"]
                 if w["name"] == "falconh1_serve_decode_sat"][0]
    assert (cell["traffic"], cell["chips"]) == (real_cell["traffic"], 1)
    for group in ("end_to_end", "per_layer"):
        want = [x["name"] for x in real[group]
                if real_cell["name"] in x.get("workloads", [real_cell["name"]])]
        assert [x["name"] for x in m[group]] == want
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark/configs/"
                                 "falcon-h1-34b-l4v4.json")) as f:
        cut = json.load(f)
    assert cfg["vocab_size"] == 261120 == 4 * cut["vocab_size"]
    assert set(cfg["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "max_position_embeddings"}
    cut["program"]["kwargs"]["vocab_size"] = cut["vocab_size"] = 261120
    for key in set(cfg) | set(cut):
        if key not in ("name", "reduced", "deployment"):
            assert cfg[key] == cut[key], key
    n = counts_falcon_h1.total_params(cfg)
    assert n == 4 * 430120032 + 2 * 261120 * 5120 + 5120 == 4394354048
    assert 8.78e9 < 2 * n < 8.80e9 and 26.3e9 < 6 * n < 26.4e9
    assert 4 * 261120 * 5120 == 5347737600
