"""TensorFlow interop example (reference example/tensorflow/Load.scala +
Save.scala + model.py): save a trained model as a frozen GraphDef a TF
user can read, and load a frozen TF graph as a framework model.

Usage:
    # save a zoo model as model.pb, reload it, compare forwards
    JAX_PLATFORMS=cpu python -m bigdl_tpu.examples.tensorflow_load_save

    # load an existing frozen graph
    JAX_PLATFORMS=cpu python -m bigdl_tpu.examples.tensorflow_load_save \
        --load graph.pb --inputs input --outputs prob
"""
from __future__ import annotations

import argparse
import os
import tempfile

import numpy as np


def save_then_load(model=None, input_shape=(1, 784), sample_batch=4):
    """reference Save.scala: module.saveTF; Load.scala: Module.loadTF."""
    import jax.numpy as jnp

    from ..interop.tensorflow import TensorflowLoader, TensorflowSaver
    from ..models.lenet import LeNet5

    if model is None:
        model = LeNet5(10)
    model.evaluate()

    path = os.path.join(tempfile.mkdtemp(prefix="bigdl_tf_"), "model.pb")
    out_name = TensorflowSaver.save(model, list(input_shape), path)
    print(f"saved frozen GraphDef: {path} (output node {out_name!r})")

    loaded = TensorflowLoader.build(TensorflowLoader.parse(path),
                                    ["input"], [out_name])
    loaded.evaluate()

    x = np.random.RandomState(0).rand(
        sample_batch, *input_shape[1:]).astype(np.float32)
    orig = np.asarray(model.forward(jnp.asarray(x)))
    back = np.asarray(loaded.forward(jnp.asarray(x)))
    err = float(np.abs(orig - back).max())
    print(f"round-trip max |Δforward| = {err:.2e}")
    return loaded, err


def load_graph(path: str, inputs, outputs):
    """reference Load.scala: Module.loadTF(graphFile, inputs, outputs)."""
    from ..interop.tensorflow import TensorflowLoader

    model = TensorflowLoader.load(path, list(inputs), list(outputs))
    model.evaluate()
    print(f"loaded {path}: {len(model.modules)} modules")
    return model


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--load", help="frozen .pb to load instead of the demo")
    p.add_argument("--inputs", default="input")
    p.add_argument("--outputs", default="output")
    a = p.parse_args(argv)
    if a.load:
        load_graph(a.load, a.inputs.split(","), a.outputs.split(","))
    else:
        _, err = save_then_load()
        assert err < 1e-4
        print("PASS")


if __name__ == "__main__":
    main()
