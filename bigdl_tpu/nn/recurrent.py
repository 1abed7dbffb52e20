"""Recurrent stack (reference nn/Cell.scala:43, Recurrent.scala:32,
RnnCell, LSTM.scala:50, LSTMPeephole, GRU.scala:54, ConvLSTMPeephole,
BiRecurrent, TimeDistributed).

TPU-first redesign: the reference clones the cell per timestep with
shared weight storage (Recurrent.scala:88-125); here the time dimension
is a ``lax.scan`` over ONE cell apply — weight sharing is the scan
carrying the same params, and XLA unrolls/pipelines it.  The reference's
``preTopology`` trick (hoisting the time-independent input projection
out of the per-step loop, Cell.scala:64-75) is preserved: cells expose
``pre_apply`` which runs batched over the whole sequence as one big MXU
matmul before the scan.

Layout: batch-first ``(N, T, F)`` like the reference's batch mode.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..utils.table import Table
from .initialization import ONE_D, RandomUniform
from .module import AbstractModule, TensorModule


class Cell(AbstractModule):
    """Recurrent cell protocol (reference nn/Cell.scala:43).

    Subclasses implement:
      - ``init_hidden(batch_size)`` → hidden pytree
      - ``pre_apply(params, x)``    → time-independent projection of the
        whole (N, T, F) sequence (preTopology); default identity
      - ``cell_apply(params, pre_t, hidden)`` → (out_t, new_hidden)
    """

    def __init__(self):
        super().__init__()

    def init_hidden(self, batch_size: int):
        raise NotImplementedError

    def pre_apply(self, params, x):
        return x

    def cell_apply(self, params, pre_t, hidden):
        raise NotImplementedError

    def _apply(self, params, buffers, inp, training, rng):
        """Single-step eager use: input Table(x_t, hidden) → Table(out, hidden)."""
        x_t, hidden = inp[1], inp[2]
        pre_t = self.pre_apply(params, x_t[:, None, :])[:, 0]
        out, new_hidden = self.cell_apply(params, pre_t, hidden)
        return Table(out, new_hidden), buffers


def _uniform_init(module, name, shape, stdv):
    init = module._init_methods.get(name, (RandomUniform(-stdv, stdv), None))[0]
    module._register_param(name, init.init(shape, ONE_D))


class RnnCell(Cell):
    """Vanilla RNN: h' = act(W x + U h + b) (reference nn/RnnCell.scala)."""

    def __init__(self, input_size: int, hidden_size: int, activation=None):
        super().__init__()
        self.input_size, self.hidden_size = input_size, hidden_size
        self.activation = activation if activation is not None else jnp.tanh
        self.reset()

    def reset(self):
        stdv = 1.0 / math.sqrt(self.hidden_size)
        _uniform_init(self, "i2h", (self.hidden_size, self.input_size), stdv)
        _uniform_init(self, "h2h", (self.hidden_size, self.hidden_size), stdv)
        _uniform_init(self, "bias", (self.hidden_size,), stdv)
        return self

    def init_hidden(self, batch_size):
        return jnp.zeros((batch_size, self.hidden_size))

    def pre_apply(self, params, x):
        # (N, T, F) @ (F, H) — one MXU matmul for the whole sequence
        return jnp.einsum("ntf,hf->nth", x, params["i2h"]) + params["bias"]

    def cell_apply(self, params, pre_t, h):
        act = self.activation
        h_new = act(pre_t + jnp.dot(h, params["h2h"].T))
        return h_new, h_new


class LSTM(Cell):
    """LSTM cell (reference nn/LSTM.scala:50).  Gate order i, f, z(g), o."""

    def __init__(self, input_size: int, hidden_size: int,
                 p: float = 0.0, w_regularizer=None, u_regularizer=None,
                 b_regularizer=None):
        super().__init__()
        self.input_size, self.hidden_size = input_size, hidden_size
        self.p = p
        self.reset()

    def reset(self):
        H, F = self.hidden_size, self.input_size
        stdv = 1.0 / math.sqrt(H)
        _uniform_init(self, "i2h", (4 * H, F), stdv)
        _uniform_init(self, "h2h", (4 * H, H), stdv)
        _uniform_init(self, "bias", (4 * H,), stdv)
        return self

    def init_hidden(self, batch_size):
        H = self.hidden_size
        return Table(jnp.zeros((batch_size, H)), jnp.zeros((batch_size, H)))

    def pre_apply(self, params, x):
        return jnp.einsum("ntf,gf->ntg", x, params["i2h"]) + params["bias"]

    def cell_apply(self, params, pre_t, hidden):
        h, c = hidden[1], hidden[2]
        H = self.hidden_size
        gates = pre_t + jnp.dot(h, params["h2h"].T)
        i = jax.nn.sigmoid(gates[:, 0:H])
        f = jax.nn.sigmoid(gates[:, H:2 * H])
        z = jnp.tanh(gates[:, 2 * H:3 * H])
        o = jax.nn.sigmoid(gates[:, 3 * H:4 * H])
        c_new = f * c + i * z
        h_new = o * jnp.tanh(c_new)
        return h_new, Table(h_new, c_new)


class LSTMPeephole(Cell):
    """LSTM with peephole connections (reference nn/LSTMPeephole.scala)."""

    def __init__(self, input_size: int, hidden_size: int, p: float = 0.0):
        super().__init__()
        self.input_size, self.hidden_size = input_size, hidden_size
        self.reset()

    def reset(self):
        H, F = self.hidden_size, self.input_size
        stdv = 1.0 / math.sqrt(H)
        _uniform_init(self, "i2h", (4 * H, F), stdv)
        _uniform_init(self, "h2h", (4 * H, H), stdv)
        _uniform_init(self, "bias", (4 * H,), stdv)
        _uniform_init(self, "peep_i", (H,), stdv)
        _uniform_init(self, "peep_f", (H,), stdv)
        _uniform_init(self, "peep_o", (H,), stdv)
        return self

    def init_hidden(self, batch_size):
        H = self.hidden_size
        return Table(jnp.zeros((batch_size, H)), jnp.zeros((batch_size, H)))

    def pre_apply(self, params, x):
        return jnp.einsum("ntf,gf->ntg", x, params["i2h"]) + params["bias"]

    def cell_apply(self, params, pre_t, hidden):
        h, c = hidden[1], hidden[2]
        H = self.hidden_size
        gates = pre_t + jnp.dot(h, params["h2h"].T)
        i = jax.nn.sigmoid(gates[:, 0:H] + params["peep_i"] * c)
        f = jax.nn.sigmoid(gates[:, H:2 * H] + params["peep_f"] * c)
        z = jnp.tanh(gates[:, 2 * H:3 * H])
        c_new = f * c + i * z
        o = jax.nn.sigmoid(gates[:, 3 * H:4 * H] + params["peep_o"] * c_new)
        h_new = o * jnp.tanh(c_new)
        return h_new, Table(h_new, c_new)


class GRU(Cell):
    """GRU cell (reference nn/GRU.scala:54).  Gate order r, z, n."""

    def __init__(self, input_size: int, hidden_size: int, p: float = 0.0):
        super().__init__()
        self.input_size, self.hidden_size = input_size, hidden_size
        self.reset()

    def reset(self):
        H, F = self.hidden_size, self.input_size
        stdv = 1.0 / math.sqrt(H)
        _uniform_init(self, "i2h", (3 * H, F), stdv)
        _uniform_init(self, "h2h", (3 * H, H), stdv)
        _uniform_init(self, "bias", (3 * H,), stdv)
        return self

    def init_hidden(self, batch_size):
        return jnp.zeros((batch_size, self.hidden_size))

    def pre_apply(self, params, x):
        return jnp.einsum("ntf,gf->ntg", x, params["i2h"]) + params["bias"]

    def cell_apply(self, params, pre_t, h):
        H = self.hidden_size
        hh = jnp.dot(h, params["h2h"].T)
        r = jax.nn.sigmoid(pre_t[:, 0:H] + hh[:, 0:H])
        z = jax.nn.sigmoid(pre_t[:, H:2 * H] + hh[:, H:2 * H])
        n = jnp.tanh(pre_t[:, 2 * H:3 * H] + r * hh[:, 2 * H:3 * H])
        h_new = (1 - z) * n + z * h
        return h_new, h_new


class ConvLSTMPeephole(Cell):
    """Convolutional LSTM with peepholes (reference nn/ConvLSTMPeephole.scala).
    State maps are (N, C, H, W); gates via 2-D convs."""

    def __init__(self, input_size: int, output_size: int, kernel_i: int,
                 kernel_c: int, stride: int = 1, with_peephole: bool = True):
        super().__init__()
        self.input_size, self.output_size = input_size, output_size
        self.kernel_i, self.kernel_c = kernel_i, kernel_c
        self.with_peephole = with_peephole
        self._spatial = None  # lazily known from input
        self.reset()

    def reset(self):
        C_in, C_out = self.input_size, self.output_size
        ki, kc = self.kernel_i, self.kernel_c
        stdv = 1.0 / math.sqrt(C_out * kc * kc)
        _uniform_init(self, "wi", (4 * C_out, C_in, ki, ki), stdv)
        _uniform_init(self, "wh", (4 * C_out, C_out, kc, kc), stdv)
        _uniform_init(self, "bias", (4 * C_out,), stdv)
        if self.with_peephole:
            _uniform_init(self, "peep_i", (C_out,), stdv)
            _uniform_init(self, "peep_f", (C_out,), stdv)
            _uniform_init(self, "peep_o", (C_out,), stdv)
        return self

    def init_hidden(self, batch_size, spatial=None):
        spatial = spatial or self._spatial
        h = jnp.zeros((batch_size, self.output_size) + spatial)
        return Table(h, h)

    def _conv(self, x, w):
        from jax import lax

        k = w.shape[-1]
        pad = k // 2
        return lax.conv_general_dilated(
            x, w, (1, 1), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    def cell_apply(self, params, x_t, hidden):
        h, c = hidden[1], hidden[2]
        C = self.output_size
        gates = (self._conv(x_t, params["wi"]) + self._conv(h, params["wh"])
                 + params["bias"][None, :, None, None])
        gi = gates[:, 0:C]
        gf = gates[:, C:2 * C]
        gz = gates[:, 2 * C:3 * C]
        go = gates[:, 3 * C:4 * C]
        if self.with_peephole:
            gi = gi + params["peep_i"][None, :, None, None] * c
            gf = gf + params["peep_f"][None, :, None, None] * c
        i = jax.nn.sigmoid(gi)
        f = jax.nn.sigmoid(gf)
        z = jnp.tanh(gz)
        c_new = f * c + i * z
        if self.with_peephole:
            go = go + params["peep_o"][None, :, None, None] * c_new
        o = jax.nn.sigmoid(go)
        h_new = o * jnp.tanh(c_new)
        return h_new, Table(h_new, c_new)


class Recurrent(AbstractModule):
    """Sequence container: scan the cell over time (reference
    nn/Recurrent.scala:32).  Input (N, T, F) → output (N, T, H)."""

    def __init__(self, cell: Optional[Cell] = None, reverse: bool = False):
        super().__init__()
        self.cell = cell
        self.reverse = reverse

    def add(self, cell: Cell):
        self.cell = cell
        return self

    # param/buffer plumbing delegates to the cell
    def param_tree(self):
        return {"cell": self.cell.param_tree()}

    def set_param_tree(self, tree):
        self.cell.set_param_tree(tree["cell"])

    def grad_tree(self):
        return {"cell": self.cell.grad_tree()}

    def set_grad_tree(self, tree):
        self.cell.set_grad_tree(tree["cell"])

    def buffer_tree(self):
        return {"cell": self.cell.buffer_tree()}

    def set_buffer_tree(self, tree):
        self.cell.set_buffer_tree(tree["cell"])

    def gradient_scale_tree(self):
        return {"cell": self.cell.gradient_scale_tree()}

    def modules_iter(self):
        yield self
        yield from self.cell.modules_iter()

    def reset(self):
        self.cell.reset()
        return self

    def apply_fn(self, params, buffers, x, training=True, rng=None):
        cell, cp = self.cell, params["cell"]
        n = x.shape[0]
        if isinstance(cell, ConvLSTMPeephole):
            cell._spatial = x.shape[3:]
            hidden0 = cell.init_hidden(n, x.shape[3:])
            pre = x
        else:
            hidden0 = cell.init_hidden(n)
            pre = cell.pre_apply(cp, x)
        if self.reverse:
            pre = jnp.flip(pre, axis=1)
        # (N, T, ...) → (T, N, ...) for scan
        pre_t = jnp.moveaxis(pre, 1, 0)

        # inside a shard_map that checks varying axes the carry must
        # enter the scan with the type it leaves with: the zero state
        # starts unvarying, the step's output varies like the inputs
        # and the weights
        vma = frozenset().union(*(
            jax.typeof(a).vma
            for a in jax.tree_util.tree_leaves((pre_t, cp))))
        if vma:
            hidden0 = jax.tree_util.tree_map(
                lambda h: jax.lax.pcast(h, tuple(vma), to="varying"),
                hidden0)

        def step(hidden, p_t):
            out, new_hidden = cell.cell_apply(cp, p_t, hidden)
            return new_hidden, out

        _, outs = jax.lax.scan(step, hidden0, pre_t)
        outs = jnp.moveaxis(outs, 0, 1)
        if self.reverse:
            outs = jnp.flip(outs, axis=1)
        return outs, buffers


class BiRecurrent(AbstractModule):
    """Bidirectional recurrent (reference nn/BiRecurrent.scala): forward +
    reversed scans, merged (default elementwise add, custom merge module
    supported)."""

    def __init__(self, merge: Optional[AbstractModule] = None):
        super().__init__()
        self.fwd: Optional[Recurrent] = None
        self.bwd: Optional[Recurrent] = None
        self.merge = merge

    def add(self, cell: Cell):
        import copy

        self.fwd = Recurrent(cell)
        self.bwd = Recurrent(copy.deepcopy(cell).reset(), reverse=True)
        return self

    def param_tree(self):
        t = {"fwd": self.fwd.param_tree(), "bwd": self.bwd.param_tree()}
        if self.merge is not None:
            t["merge"] = self.merge.param_tree()
        return t

    def set_param_tree(self, tree):
        self.fwd.set_param_tree(tree["fwd"])
        self.bwd.set_param_tree(tree["bwd"])
        if self.merge is not None:
            self.merge.set_param_tree(tree["merge"])

    def gradient_scale_tree(self):
        t = {"fwd": self.fwd.gradient_scale_tree(),
             "bwd": self.bwd.gradient_scale_tree()}
        if self.merge is not None:
            t["merge"] = self.merge.gradient_scale_tree()
        return t

    def grad_tree(self):
        t = {"fwd": self.fwd.grad_tree(), "bwd": self.bwd.grad_tree()}
        if self.merge is not None:
            t["merge"] = self.merge.grad_tree()
        return t

    def set_grad_tree(self, tree):
        self.fwd.set_grad_tree(tree["fwd"])
        self.bwd.set_grad_tree(tree["bwd"])
        if self.merge is not None:
            self.merge.set_grad_tree(tree["merge"])

    def buffer_tree(self):
        return {"fwd": self.fwd.buffer_tree(), "bwd": self.bwd.buffer_tree()}

    def set_buffer_tree(self, tree):
        self.fwd.set_buffer_tree(tree["fwd"])
        self.bwd.set_buffer_tree(tree["bwd"])

    def modules_iter(self):
        yield self
        yield from self.fwd.modules_iter()
        yield from self.bwd.modules_iter()

    def apply_fn(self, params, buffers, x, training=True, rng=None):
        fo, _ = self.fwd.apply_fn(params["fwd"], buffers["fwd"], x, training, rng)
        bo, _ = self.bwd.apply_fn(params["bwd"], buffers["bwd"], x, training, rng)
        if self.merge is None:
            return fo + bo, buffers
        out, _ = self.merge.apply_fn(params["merge"], {}, Table(fo, bo),
                                     training, rng)
        return out, buffers


class TimeDistributed(AbstractModule):
    """Apply a module at every timestep (reference nn/TimeDistributed.scala):
    fold T into the batch dim — one big batched apply, no loop."""

    def __init__(self, module: AbstractModule):
        super().__init__()
        self.module = module

    def param_tree(self):
        return {"m": self.module.param_tree()}

    def set_param_tree(self, tree):
        self.module.set_param_tree(tree["m"])

    def gradient_scale_tree(self):
        return {"m": self.module.gradient_scale_tree()}

    def grad_tree(self):
        return {"m": self.module.grad_tree()}

    def set_grad_tree(self, tree):
        self.module.set_grad_tree(tree["m"])

    def buffer_tree(self):
        return {"m": self.module.buffer_tree()}

    def set_buffer_tree(self, tree):
        self.module.set_buffer_tree(tree["m"])

    def modules_iter(self):
        yield self
        yield from self.module.modules_iter()

    def apply_fn(self, params, buffers, x, training=True, rng=None):
        n, t = x.shape[0], x.shape[1]
        flat = x.reshape((n * t,) + x.shape[2:])
        out, nb = self.module.apply_fn(params["m"], buffers["m"], flat,
                                       training, rng)
        return out.reshape((n, t) + out.shape[1:]), {"m": nb}
