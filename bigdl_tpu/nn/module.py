"""AbstractModule — the layer protocol, rebuilt TPU-first.

Reference contract (nn/abstractnn/AbstractModule.scala:54): mutable
modules with explicit ``updateOutput`` / ``updateGradInput`` /
``accGradParameters``, ``parameters()`` returning (weights, gradWeights),
``getParameters()`` returning flattened views, containers composing
children, timing counters on forward/backward.

TPU-first redesign (SURVEY §7.1): every module's compute is ONE pure
function

    apply_fn(params, buffers, input, training, rng) -> (output, new_buffers)

where ``params``/``buffers`` are pytrees of jax arrays.  The Torch-style
mutable API (``forward``/``backward``/``zero_grad_parameters``) is a thin
eager shell over this pure core: ``backward`` is derived from ``jax.vjp``
of the pure apply — there are no hand-written backward passes anywhere in
the framework, XLA differentiates and fuses.  Optimizers never call the
eager shell; they trace ``apply_fn`` of the whole model into a single
jitted (and, distributed, shard_mapped) train step.

``Activity`` = jax array | Table | list/tuple of activities (pytree).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.rng import next_jax_key
from ..utils.table import Table
from .initialization import DEFAULT_FORMAT, InitializationMethod

Activity = Any  # jax array | Table | nested list/tuple


def to_array(x):
    """Unwrap Tensor facade / numpy into raw jax arrays at the API boundary."""
    from ..tensor.tensor import Tensor

    if isinstance(x, Tensor):
        return x.data
    if isinstance(x, (list, tuple)):
        return type(x)(to_array(v) for v in x)
    if isinstance(x, Table):
        out = Table()
        for k, v in x.items():
            out[k] = to_array(v)
        return out
    if isinstance(x, (np.ndarray, float, int)):
        return jnp.asarray(x)
    return x


#: leaves that stay float32 where a model holds or computes in a lower
#: precision (``hold_floats`` with ``keep=FLOAT32_LEAVES``; the
#: generator's cast): a router's selection bias is compared with float32
#: scores, and a hyper-connection's gates and biases enter float32
#: sigmoids and ``exp``
FLOAT32_LEAVES = ("score_bias", "alpha_pre", "alpha_post", "alpha_res",
                  "b_pre", "b_post", "b_res")


def hold_floats(tree, dtype, keep=()):
    """``tree`` with every floating leaf in ``dtype`` — one cast per
    leaf that is not there yet, none for one that is, so the tree and
    its copy never both exist whole.  ``dtype`` None: ``tree`` itself.
    A leaf whose own name (the last key of its path) is in ``keep``
    stays as it is."""
    if dtype is None:
        return tree
    dtype = jnp.dtype(dtype)

    def held(path, a):
        if (not jnp.issubdtype(jnp.result_type(a), jnp.floating)
                or a.dtype == dtype
                or (keep and getattr(path[-1], "key", None) in keep)):
            return a
        return a.astype(dtype)

    return jax.tree_util.tree_map_with_path(held, tree)


class AbstractModule:
    """Base layer.  Subclasses define ``_build()`` (register params) and
    ``_apply(params, buffers, input, training, rng) -> (output, new_buffers)``.

    Stateless layers only override ``_apply`` and ignore buffers.
    """

    def __init__(self):
        self.params: Dict[str, jax.Array] = {}
        self.grads: Dict[str, jax.Array] = {}
        self.buffers: Dict[str, jax.Array] = {}
        self.output: Activity = None
        self.grad_input: Activity = None
        self.is_training = True
        self.name: Optional[str] = None
        self.forward_time = 0.0
        self.backward_time = 0.0
        self.scale_w = 1.0
        self.scale_b = 1.0
        self._init_methods: Dict[str, Tuple[InitializationMethod, Any]] = {}
        self._last_rng = None
        self._node = None  # lazily-created graph node (see Graph container)

    # ------------------------------------------------------------------
    # functional core
    # ------------------------------------------------------------------
    def _apply(self, params, buffers, inp, training: bool, rng):
        raise NotImplementedError(
            f"{type(self).__name__} must implement _apply")

    def apply_fn(self, params, buffers, inp, training: bool = True, rng=None):
        """The pure forward.  Containers override to route children."""
        return self._apply(params, buffers, inp, training, rng)

    # ------------------------------------------------------------------
    # parameter / buffer pytrees
    # ------------------------------------------------------------------
    def param_tree(self):
        return dict(self.params)

    def set_param_tree(self, tree):
        self.params = dict(tree)

    def grad_tree(self):
        """The gradient buffers, made on first use: a module owns none
        until something asks (this, ``zero_grad_parameters``, a
        backward) — a model that is only served, or trained through a
        plan engine, never pays for them."""
        for name in self.params:
            self._grad(name)
        return dict(self.grads)

    def _grad(self, name: str) -> jax.Array:
        """One gradient buffer, zeros like its parameter when first
        asked for."""
        if name not in self.grads:
            self.grads[name] = jnp.zeros_like(self.params[name])
        return self.grads[name]

    def set_grad_tree(self, tree):
        self.grads = dict(tree)

    def buffer_tree(self):
        return dict(self.buffers)

    def set_buffer_tree(self, tree):
        self.buffers = dict(tree)

    def _register_param(self, name: str, value: jax.Array):
        self.params[name] = value
        # a buffer from an earlier draw is stale; the next one that is
        # asked for is zeros like the new value (``grad_tree``)
        self.grads.pop(name, None)

    def _register_buffer(self, name: str, value: jax.Array):
        self.buffers[name] = value

    # ------------------------------------------------------------------
    # Torch-style eager API (AbstractModule.scala:213-268)
    # ------------------------------------------------------------------
    def update_output(self, inp: Activity) -> Activity:
        inp = to_array(inp)
        if self._last_rng is None:
            self._last_rng = next_jax_key()
        out, new_buf = self.apply_fn(self.param_tree(), self.buffer_tree(),
                                     inp, self.is_training, self._last_rng)
        self.set_buffer_tree(new_buf)
        self.output = out
        return out

    def forward(self, inp: Activity) -> Activity:
        t0 = time.time()
        self._last_rng = next_jax_key()
        out = self.update_output(inp)
        self.forward_time += time.time() - t0
        return out

    def __call__(self, *args):
        """``layer(x)`` → eager forward; ``layer(node)`` / ``layer([n1, n2])``
        → graph wiring (reference ``inputs(...)``, AbstractModule.scala:539)."""
        from .graph import ModuleNode

        if len(args) == 1 and isinstance(args[0], ModuleNode):
            return self.inputs(args[0])
        if (len(args) >= 1 and isinstance(args[0], (list, tuple))
                and args[0] and all(isinstance(a, ModuleNode) for a in args[0])):
            return self.inputs(*args[0])
        if len(args) > 1 and all(isinstance(a, ModuleNode) for a in args):
            return self.inputs(*args)
        if len(args) == 1:
            return self.forward(args[0])
        return self.forward(list(args))

    def inputs(self, *nodes):
        from .graph import ModuleNode

        node = ModuleNode(self)
        for n in nodes:
            n.add_edge(node)
        return node

    def _vjp(self, inp: Activity):
        inp = to_array(inp)
        ptree = self.param_tree()
        btree = self.buffer_tree()
        rng = self._last_rng if self._last_rng is not None else next_jax_key()

        def f(p, x):
            return self.apply_fn(p, btree, x, self.is_training, rng)[0]

        return jax.vjp(f, ptree, inp)

    def update_grad_input(self, inp: Activity, grad_output: Activity) -> Activity:
        _, vjp = self._vjp(inp)
        _, gi = vjp(to_array(grad_output))
        self.grad_input = gi
        return gi

    def acc_grad_parameters(self, inp: Activity, grad_output: Activity):
        _, vjp = self._vjp(inp)
        gp, _ = vjp(to_array(grad_output))
        self._accumulate(gp)

    def backward(self, inp: Activity, grad_output: Activity) -> Activity:
        """One vjp computes both gradInput and parameter gradients —
        mirrors the reference's fused ``backward`` (AbstractModule.scala:231)."""
        t0 = time.time()
        _, vjp = self._vjp(inp)
        gp, gi = vjp(to_array(grad_output))
        self._accumulate(gp)
        self.grad_input = gi
        self.backward_time += time.time() - t0
        return gi

    def _accumulate(self, grad_param_tree):
        cur = self.grad_tree()
        scaled = jax.tree_util.tree_map(
            lambda g, s: g * s if s != 1.0 else g,
            grad_param_tree, self.gradient_scale_tree())
        new = jax.tree_util.tree_map(lambda a, b: a + b, cur, scaled)
        self.set_grad_tree(new)

    def gradient_scale_tree(self):
        """Per-leaf gradient scale factors — the reference's
        setScaleW/setScaleB applied in accGradParameters
        (AbstractModule.scala:70-101).  Same structure as param_tree;
        derived from it path-wise so modules with custom param_tree
        layouts stay consistent."""
        def scale_of(path, _leaf):
            key = str(getattr(path[-1], "key", "")) if path else ""
            return self.scale_b if "bias" in key else self.scale_w

        return jax.tree_util.tree_map_with_path(scale_of, self.param_tree())

    # ------------------------------------------------------------------
    # parameter surface (AbstractModule.scala:284-310)
    # ------------------------------------------------------------------
    def parameters(self) -> Tuple[List[jax.Array], List[jax.Array]]:
        """(weights, gradWeights) as flat lists over the module tree."""
        p_leaves = jax.tree_util.tree_leaves(self.param_tree())
        g_leaves = jax.tree_util.tree_leaves(self.grad_tree())
        return p_leaves, g_leaves

    def get_weights(self) -> List[np.ndarray]:
        """Weights as numpy arrays, in ``parameters()`` order (reference
        pyspark Layer.get_weights, nn/layer.py:308)."""
        return [np.asarray(p) for p in
                jax.tree_util.tree_leaves(self.param_tree())]

    def set_weights(self, weights):
        """Assign weights from a list of arrays in ``parameters()`` order
        (reference pyspark Layer.set_weights, nn/layer.py:263)."""
        tree = self.param_tree()
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if len(weights) != len(leaves):
            raise ValueError(
                f"expected {len(leaves)} weight arrays, got {len(weights)}")
        new_leaves = []
        for cur, w in zip(leaves, weights):
            w = jnp.asarray(w, cur.dtype)
            if w.shape != cur.shape:
                raise ValueError(
                    f"weight shape {w.shape} != expected {cur.shape}")
            new_leaves.append(w)
        self.set_param_tree(jax.tree_util.tree_unflatten(treedef,
                                                         new_leaves))
        return self

    def update_parameters(self, learning_rate: float):
        """Debug-only in-place SGD step from the eager grads (reference
        pyspark Layer.update_parameters, nn/layer.py:201: 'for debug
        only, please use optimizer.optimize() in production')."""
        self.set_param_tree(jax.tree_util.tree_map(
            lambda p, g: p - learning_rate * g,
            self.param_tree(), self.grad_tree()))
        return self

    def test(self, dataset, batch_size: int = 128, v_methods=None):
        """Model-quality benchmark (reference pyspark Layer.test →
        modelTest): ``evaluate(dataset, v_methods, batch_size)`` with the
        pyspark argument order."""
        if not v_methods:
            raise ValueError(
                "test() needs at least one ValidationMethod (e.g. "
                "[Top1Accuracy()]) — an empty list would run the full "
                "eval forward and return no metrics")
        return self.evaluate(dataset, v_methods, batch_size)

    def get_parameters(self) -> Tuple[jax.Array, jax.Array]:
        """Flattened (weight, grad) pair (reference Module.flatten:80).

        On TPU there is no aliased flat storage — this returns 1-D
        concatenations; ``set_flat_parameters`` writes back.
        """
        from jax.flatten_util import ravel_pytree

        flat_w, _ = ravel_pytree(self.param_tree())
        flat_g, _ = ravel_pytree(self.grad_tree())
        if flat_w.size == 0:
            return jnp.zeros((0,)), jnp.zeros((0,))
        return flat_w, flat_g

    def set_flat_parameters(self, flat_w):
        from jax.flatten_util import ravel_pytree

        _, unravel = ravel_pytree(self.param_tree())
        self.set_param_tree(unravel(jnp.asarray(flat_w)))
        return self

    def n_parameters(self) -> int:
        return sum(int(p.size) for p in jax.tree_util.tree_leaves(self.param_tree()))

    def zero_grad_parameters(self):
        self.set_grad_tree(jax.tree_util.tree_map(jnp.zeros_like, self.grad_tree()))
        return self

    # ------------------------------------------------------------------
    # mode / naming / reset (AbstractModule.scala:317-380)
    # ------------------------------------------------------------------
    def training(self):
        self.is_training = True
        return self

    def evaluate(self, *args, **kwargs):
        """No-arg: switch to eval mode.  With a dataset: distributed eval
        (reference AbstractModule.evaluate:571) — routed to Evaluator."""
        if not args:
            self.is_training = False
            return self
        from ..optim.evaluator import Evaluator

        return Evaluator(self).test(*args, **kwargs)

    def set_name(self, name: str):
        self.name = name
        return self

    def get_name(self) -> str:
        return self.name or type(self).__name__

    def set_init_method(self, weight_init: Optional[InitializationMethod] = None,
                        bias_init: Optional[InitializationMethod] = None):
        if weight_init is not None:
            self._init_methods["weight"] = (weight_init, DEFAULT_FORMAT)
        if bias_init is not None:
            self._init_methods["bias"] = (bias_init, DEFAULT_FORMAT)
        self.reset()
        return self

    def set_scale_w(self, w):
        self.scale_w = w
        return self

    def set_scale_b(self, b):
        self.scale_b = b
        return self

    def reset(self):
        """Re-draw parameters (subclasses with params override)."""
        return self

    # ------------------------------------------------------------------
    # traversal / timing (Container.getTimes analogue)
    # ------------------------------------------------------------------
    def modules_iter(self):
        yield self

    def get_times(self):
        return [(m.get_name(), m.forward_time, m.backward_time)
                for m in self.modules_iter()]

    def reset_times(self):
        for m in self.modules_iter():
            m.forward_time = 0.0
            m.backward_time = 0.0
        return self

    def find_module(self, name: str):
        for m in self.modules_iter():
            if m.get_name() == name:
                return m
        return None

    # ------------------------------------------------------------------
    # clone / save / predict
    # ------------------------------------------------------------------
    def clone_module(self) -> "AbstractModule":
        import copy

        return copy.deepcopy(self)

    def save(self, path: str, overwrite: bool = False):
        from ..utils.file_io import save as _save

        _save(self, path, overwrite)
        return self

    def save_torch(self, path: str, overwrite: bool = False):
        """Write this module as a Torch7 ``.t7`` file (reference
        AbstractModule.saveTorch:390 → TorchFile.save)."""
        from ..utils import torch_file

        torch_file.save(self, path, overwrite)
        return self

    def save_caffe(self, prototxt_path: str, model_path: str,
                   use_v2: bool = True, overwrite: bool = False):
        """Write this module as Caffe prototxt+caffemodel (reference
        AbstractModule.saveCaffe, AbstractModule.scala:398)."""
        from ..interop.caffe import CaffePersister

        CaffePersister.persist(prototxt_path, model_path, self,
                               use_v2=use_v2, overwrite=overwrite)
        return self

    def save_tf(self, input_shape, path: str, **kwargs):
        """Write this module as a frozen TF GraphDef (reference
        AbstractModule.saveTF, AbstractModule.scala:405)."""
        from ..interop.tensorflow import TensorflowSaver

        TensorflowSaver.save(self, input_shape, path, **kwargs)
        return self

    def save_weights(self, path: str, overwrite: bool = False):
        from ..utils.file_io import save as _save

        _save(self.param_tree(), path, overwrite)
        return self

    def load_weights(self, path: str):
        from ..utils.file_io import load as _load

        tree = _load(path)
        self.set_param_tree(jax.tree_util.tree_map(jnp.asarray, tree))
        return self

    def predict(self, dataset, batch_size: int = 32, mesh=None):
        """Distributed when given a mesh (reference Predictor.scala:34
        broadcasts + forwards per partition; here a compiled shard_map)."""
        from ..optim.predictor import Predictor

        return Predictor(self, mesh=mesh).predict(dataset, batch_size)

    def predict_class(self, dataset, batch_size: int = 32, mesh=None):
        from ..optim.predictor import Predictor

        return Predictor(self, mesh=mesh).predict_class(dataset, batch_size)

    # -- pickling: jax arrays travel as numpy (checkpoint format seam) ---
    def __getstate__(self):
        state = dict(self.__dict__)
        for key in ("params", "grads", "buffers"):
            state[key] = jax.tree_util.tree_map(
                lambda x: np.asarray(x) if isinstance(x, jax.Array) else x,
                state[key])
        state["output"] = None
        state["grad_input"] = None
        state["_last_rng"] = None
        state["_node"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        for key in ("params", "grads", "buffers"):
            setattr(self, key, jax.tree_util.tree_map(
                lambda x: jnp.asarray(x) if isinstance(x, np.ndarray) else x,
                getattr(self, key)))

    def __repr__(self):
        return f"{self.get_name()}"


class TensorModule(AbstractModule):
    """Module whose input and output are single tensors (reference
    abstractnn/TensorModule.scala:43)."""


class Container(AbstractModule):
    """Base container (reference nn/Container.scala:40)."""

    def __init__(self, *modules):
        super().__init__()
        self.modules: List[AbstractModule] = list(modules)

    def add(self, module: AbstractModule):
        self.modules.append(module)
        return self

    def __len__(self):
        return len(self.modules)

    def __getitem__(self, i: int) -> AbstractModule:
        return self.modules[i]

    def get(self, i: int) -> AbstractModule:
        """1-based accessor for API parity."""
        return self.modules[i - 1]

    # compose children's pytrees keyed by index
    def param_tree(self):
        return {str(i): m.param_tree() for i, m in enumerate(self.modules)}

    def set_param_tree(self, tree):
        for i, m in enumerate(self.modules):
            m.set_param_tree(tree[str(i)])

    def grad_tree(self):
        return {str(i): m.grad_tree() for i, m in enumerate(self.modules)}

    def set_grad_tree(self, tree):
        for i, m in enumerate(self.modules):
            m.set_grad_tree(tree[str(i)])

    def buffer_tree(self):
        return {str(i): m.buffer_tree() for i, m in enumerate(self.modules)}

    def gradient_scale_tree(self):
        return {str(i): m.gradient_scale_tree()
                for i, m in enumerate(self.modules)}

    def set_buffer_tree(self, tree):
        for i, m in enumerate(self.modules):
            m.set_buffer_tree(tree[str(i)])

    def modules_iter(self):
        yield self
        for m in self.modules:
            yield from m.modules_iter()

    def training(self):
        super().training()
        for m in self.modules:
            m.training()
        return self

    def evaluate(self, *args, **kwargs):
        if args:
            return super().evaluate(*args, **kwargs)
        super().evaluate()
        for m in self.modules:
            m.evaluate()
        return self

    def reset(self):
        for m in self.modules:
            m.reset()
        return self

    def __repr__(self):
        inner = "\n".join(
            "  " + repr(m).replace("\n", "\n  ") for m in self.modules)
        return f"{self.get_name()} {{\n{inner}\n}}"
