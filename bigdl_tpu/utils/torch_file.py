"""Torch7 ``.t7`` binary codec — read/write tensors, tables and modules.

Parity target: reference utils/TorchFile.scala:67 (load:79, save:95).
The wire format is Torch7's public serialization format (little-endian):

    object   := int32 type-tag, payload
    tags     :  0=nil  1=number  2=string  3=table  4=torch-object  5=boolean
    number   := float64
    string   := int32 len, bytes
    boolean  := int32 (1 = true)
    table    := int32 index-id, [memo] int32 size, size x (key obj, value obj)
    torch    := int32 index-id, [memo] version string ("V 1"), class string,
                class-specific payload
    tensor   := int32 ndim, int64 sizes[ndim], int64 strides[ndim],
                int64 storageOffset (1-based), storage object
    storage  := int64 count, raw elements

Tensors surface as numpy arrays (float32/float64/int64 by torch class);
tables as :class:`~bigdl_tpu.utils.table.Table` (integer-valued number
keys become int keys, mirroring readTable, TorchFile.scala:753-771);
known ``nn.*`` classes as bigdl_tpu modules (readModule dispatch,
TorchFile.scala:205-260).  Unknown torch classes load as a Table with
``__torch_class__`` set so callers can post-process.
"""
from __future__ import annotations

import os
import struct
from typing import Any, BinaryIO, Dict, Optional

import numpy as np

from .table import Table

try:
    import jax.numpy as jnp
except ImportError:  # pragma: no cover - jax is a hard dep of the package
    jnp = None

TYPE_NIL = 0
TYPE_NUMBER = 1
TYPE_STRING = 2
TYPE_TABLE = 3
TYPE_TORCH = 4
TYPE_BOOLEAN = 5
TYPE_FUNCTION = 6
LEGACY_TYPE_RECUR_FUNCTION = 7
TYPE_RECUR_FUNCTION = 8

VERSION = "V 1"

_TENSOR_CLASSES = {
    "torch.FloatTensor": np.float32,
    "torch.DoubleTensor": np.float64,
    "torch.LongTensor": np.int64,
    "torch.IntTensor": np.int32,
    "torch.ByteTensor": np.uint8,
    "torch.CudaTensor": np.float32,
    "torch.CudaDoubleTensor": np.float64,
    "torch.CudaLongTensor": np.int64,
}
_STORAGE_CLASSES = {
    "torch.FloatStorage": np.float32,
    "torch.DoubleStorage": np.float64,
    "torch.LongStorage": np.int64,
    "torch.IntStorage": np.int32,
    "torch.ByteStorage": np.uint8,
    "torch.CudaStorage": np.float32,
    "torch.CudaDoubleStorage": np.float64,
    "torch.CudaLongStorage": np.int64,
}
_DTYPE_TO_TENSOR_CLASS = {
    np.dtype(np.float32): ("torch.FloatTensor", "torch.FloatStorage"),
    np.dtype(np.float64): ("torch.DoubleTensor", "torch.DoubleStorage"),
    np.dtype(np.int64): ("torch.LongTensor", "torch.LongStorage"),
}


class _Reader:
    def __init__(self, f: BinaryIO):
        self.f = f
        self.memo: Dict[int, Any] = {}

    def _unpack(self, fmt: str):
        size = struct.calcsize(fmt)
        return struct.unpack(fmt, self.f.read(size))[0]

    def read_int(self) -> int:
        return self._unpack("<i")

    def read_long(self) -> int:
        return self._unpack("<q")

    def read_double(self) -> float:
        return self._unpack("<d")

    def read_string(self) -> str:
        n = self.read_int()
        return self.f.read(n).decode("utf-8", errors="replace")

    def read_object(self) -> Any:
        tag = self.read_int()
        if tag == TYPE_NIL:
            return None
        if tag == TYPE_NUMBER:
            return self.read_double()
        if tag == TYPE_STRING:
            return self.read_string()
        if tag == TYPE_BOOLEAN:
            return self.read_int() == 1
        if tag == TYPE_TABLE:
            idx = self.read_int()
            if idx in self.memo:
                return self.memo[idx]
            result = Table()
            self.memo[idx] = result
            n = self.read_int()
            for _ in range(n):
                key = self.read_object()
                value = self.read_object()
                if isinstance(key, float) and key == int(key):
                    key = int(key)
                result[key] = value
            return result
        if tag == TYPE_TORCH:
            idx = self.read_int()
            if idx in self.memo:
                return self.memo[idx]
            version = self.read_string()
            if version.startswith("V "):
                class_name = self.read_string()
            else:  # legacy: no version header, the string IS the class
                class_name = version
            result = self._read_torch(class_name)
            self.memo[idx] = result
            return result
        raise NotImplementedError(f".t7 type tag {tag} not supported")

    def _read_torch(self, class_name: str) -> Any:
        if class_name in _TENSOR_CLASSES:
            return self._read_tensor()
        if class_name in _STORAGE_CLASSES:
            return self._read_storage(_STORAGE_CLASSES[class_name])
        elements = self.read_object()
        return _table_to_module(class_name, elements)

    def _read_tensor(self) -> Optional[np.ndarray]:
        ndim = self.read_int()
        sizes = [self.read_long() for _ in range(ndim)]
        strides = [self.read_long() for _ in range(ndim)]
        offset = self.read_long()  # 1-based
        storage = self.read_object()
        if storage is None:
            return None
        flat = np.asarray(storage)
        if ndim == 0:
            return flat[:0]
        return np.lib.stride_tricks.as_strided(
            flat[offset - 1:],
            shape=sizes,
            strides=[s * flat.itemsize for s in strides]).copy()

    def _read_storage(self, dtype) -> np.ndarray:
        n = self.read_long()
        return np.frombuffer(self.f.read(n * np.dtype(dtype).itemsize),
                             dtype=dtype).copy()


class _Writer:
    def __init__(self, f: BinaryIO):
        self.f = f
        self.memo: Dict[int, int] = {}  # id(obj) -> index
        self.next_index = 1
        self._keepalive = []

    def write_int(self, v: int):
        self.f.write(struct.pack("<i", v))

    def write_long(self, v: int):
        self.f.write(struct.pack("<q", v))

    def write_double(self, v: float):
        self.f.write(struct.pack("<d", v))

    def write_string(self, s: str):
        b = s.encode("utf-8")
        self.write_int(len(b))
        self.f.write(b)

    def _memoize(self, obj) -> Optional[int]:
        """Return existing index or assign a new one (None ⇒ first visit)."""
        key = id(obj)
        if key in self.memo:
            return self.memo[key]
        self.memo[key] = self.next_index
        self._keepalive.append(obj)
        self.next_index += 1
        return None

    def write_object(self, obj: Any):
        from ..nn.module import AbstractModule

        if obj is None:
            self.write_int(TYPE_NIL)
        elif isinstance(obj, bool):
            self.write_int(TYPE_BOOLEAN)
            self.write_int(1 if obj else 0)
        elif isinstance(obj, (int, float)):
            self.write_int(TYPE_NUMBER)
            self.write_double(float(obj))
        elif isinstance(obj, str):
            self.write_int(TYPE_STRING)
            self.write_string(obj)
        elif isinstance(obj, np.ndarray):
            self._write_tensor(obj)
        elif isinstance(obj, AbstractModule):
            self._write_module(obj)
        elif isinstance(obj, Table):
            self._write_table(obj)
        elif isinstance(obj, dict):
            t = Table()
            for k, v in obj.items():
                t[k] = v
            self._write_table(t)
        elif isinstance(obj, (list, tuple)):
            t = Table()
            for i, v in enumerate(obj):
                t[i + 1] = v
            self._write_table(t)
        else:
            try:  # jax arrays and anything array-like
                self._write_tensor(np.asarray(obj))
            except Exception:
                raise TypeError(f"cannot serialize {type(obj)} to .t7")

    def _write_table(self, table: Table):
        self.write_int(TYPE_TABLE)
        idx = self._memoize(table)
        if idx is not None:
            self.write_int(idx)
            return
        self.write_int(self.memo[id(table)])
        items = list(table.items())
        self.write_int(len(items))
        for k, v in items:
            self.write_object(float(k) if isinstance(k, int) else k)
            self.write_object(v)

    def _write_tensor(self, arr: np.ndarray):
        if arr.dtype == np.int32:
            arr = arr.astype(np.int64)
        if arr.dtype not in _DTYPE_TO_TENSOR_CLASS:
            arr = arr.astype(np.float32)
        tcls, scls = _DTYPE_TO_TENSOR_CLASS[arr.dtype]
        self.write_int(TYPE_TORCH)
        idx = self._memoize(arr)
        if idx is not None:
            self.write_int(idx)
            return
        self.write_int(self.memo[id(arr)])
        self.write_string(VERSION)
        self.write_string(tcls)
        arr = np.ascontiguousarray(arr)
        self.write_int(arr.ndim)
        for s in arr.shape:
            self.write_long(s)
        # contiguous strides in elements
        stride = 1
        strides = []
        for s in reversed(arr.shape):
            strides.append(stride)
            stride *= s
        for s in reversed(strides):
            self.write_long(s)
        self.write_long(1)  # storageOffset, 1-based
        # storage sub-object
        self.write_int(TYPE_TORCH)
        self.write_int(self.next_index)
        self.next_index += 1
        self.write_string(VERSION)
        self.write_string(scls)
        self.write_long(arr.size)
        self.f.write(arr.tobytes())

    def _write_module(self, module):
        class_name, elements = _module_to_table(module)
        self.write_int(TYPE_TORCH)
        idx = self._memoize(module)
        if idx is not None:
            self.write_int(idx)
            return
        self.write_int(self.memo[id(module)])
        self.write_string(VERSION)
        self.write_string(class_name)
        self.write_object(elements)


# ---------------------------------------------------------------------------
# module <-> element-table adapters (readModule / write<Layer> parity,
# TorchFile.scala:205-260, 263-300, 449-593)
# ---------------------------------------------------------------------------

def _np(x) -> Optional[np.ndarray]:
    return None if x is None else np.asarray(x)


def _module_to_table(module):
    """Return (torch class name, element Table) for a bigdl_tpu module."""
    from .. import nn

    t = Table()
    t["train"] = module.is_training
    p = module.params

    if isinstance(module, nn.Sequential):
        mods = Table()
        for i, m in enumerate(module.modules):
            mods[i + 1] = m
        t["modules"] = mods
        return "nn.Sequential", t
    if isinstance(module, nn.Concat):
        mods = Table()
        for i, m in enumerate(module.modules):
            mods[i + 1] = m
        t["modules"] = mods
        t["dimension"] = float(module.dimension)
        return "nn.Concat", t
    if isinstance(module, nn.ConcatTable):
        mods = Table()
        for i, m in enumerate(module.modules):
            mods[i + 1] = m
        t["modules"] = mods
        return "nn.ConcatTable", t
    if isinstance(module, nn.Linear):
        t["weight"] = _np(p.get("weight"))
        t["bias"] = _np(p.get("bias"))
        grads = module.grad_tree()
        t["gradWeight"] = _np(grads.get("weight"))
        t["gradBias"] = _np(grads.get("bias"))
        return "nn.Linear", t
    if isinstance(module, nn.SpatialConvolution):
        t["nInputPlane"] = float(module.n_input_plane)
        t["nOutputPlane"] = float(module.n_output_plane)
        t["kW"] = float(module.kernel_w)
        t["kH"] = float(module.kernel_h)
        t["dW"] = float(module.stride_w)
        t["dH"] = float(module.stride_h)
        t["padW"] = float(module.pad_w)
        t["padH"] = float(module.pad_h)
        w = _np(p.get("weight"))
        if w is not None:  # OIHW -> torch MM layout (O, I*kH*kW)
            t["weight"] = w.reshape(w.shape[0], -1)
        t["bias"] = _np(p.get("bias"))
        return "nn.SpatialConvolutionMM", t
    if isinstance(module, nn.SpatialMaxPooling):
        t["kW"], t["kH"] = float(module.kw), float(module.kh)
        t["dW"], t["dH"] = float(module.dw), float(module.dh)
        t["padW"], t["padH"] = float(module.pad_w), float(module.pad_h)
        t["ceil_mode"] = module.ceil_mode
        return "nn.SpatialMaxPooling", t
    if isinstance(module, nn.SpatialAveragePooling):
        t["kW"], t["kH"] = float(module.kw), float(module.kh)
        t["dW"], t["dH"] = float(module.dw), float(module.dh)
        t["padW"], t["padH"] = float(module.pad_w), float(module.pad_h)
        t["ceil_mode"] = module.ceil_mode
        t["count_include_pad"] = module.count_include_pad
        t["divide"] = module.divide
        return "nn.SpatialAveragePooling", t
    if isinstance(module, (nn.SpatialBatchNormalization, nn.BatchNormalization)):
        t["nOutput"] = float(module.n_output)
        t["eps"] = float(module.eps)
        t["momentum"] = float(module.momentum)
        t["affine"] = module.affine
        t["weight"] = _np(p.get("weight"))
        t["bias"] = _np(p.get("bias"))
        t["running_mean"] = _np(module.buffers.get("running_mean"))
        t["running_var"] = _np(module.buffers.get("running_var"))
        name = ("nn.SpatialBatchNormalization"
                if isinstance(module, nn.SpatialBatchNormalization)
                else "nn.BatchNormalization")
        return name, t
    if isinstance(module, nn.ReLU):
        t["inplace"] = bool(getattr(module, "inplace", False))
        t["threshold"] = 0.0
        t["val"] = 0.0
        return "nn.ReLU", t
    if isinstance(module, nn.Threshold):
        t["threshold"] = float(module.th)
        t["val"] = float(module.v)
        t["inplace"] = bool(getattr(module, "inplace", False))
        return "nn.Threshold", t
    if isinstance(module, nn.Tanh):
        return "nn.Tanh", t
    if isinstance(module, nn.Sigmoid):
        return "nn.Sigmoid", t
    if isinstance(module, nn.LogSoftMax):
        return "nn.LogSoftMax", t
    if isinstance(module, nn.SoftMax):
        return "nn.SoftMax", t
    if isinstance(module, nn.Dropout):
        t["p"] = float(module.p)
        return "nn.Dropout", t
    if isinstance(module, nn.View):
        t["size"] = np.asarray(module.sizes, dtype=np.int64)
        t["numElements"] = float(int(np.prod(module.sizes)))
        return "nn.View", t
    if isinstance(module, nn.Reshape):
        t["size"] = np.asarray(module.size, dtype=np.int64)
        t["batchMode"] = module.batch_mode  # None = auto (Option.empty)
        return "nn.Reshape", t
    if isinstance(module, nn.CAddTable):
        t["inplace"] = bool(getattr(module, "inplace", False))
        return "nn.CAddTable", t
    if isinstance(module, nn.SpatialZeroPadding):
        l, r, tp, b = module.pads
        t["pad_l"], t["pad_r"] = float(l), float(r)
        t["pad_t"], t["pad_b"] = float(tp), float(b)
        return "nn.SpatialZeroPadding", t
    if isinstance(module, nn.SpatialCrossMapLRN):
        t["size"] = float(module.size)
        t["alpha"] = float(module.alpha)
        t["beta"] = float(module.beta)
        t["k"] = float(module.k)
        return "nn.SpatialCrossMapLRN", t
    raise NotImplementedError(
        f"t7 write of {type(module).__name__} is not supported "
        "(reference TorchFile.scala writeObject has the same closed set)")


def _table_to_module(class_name: str, elements):
    """Build a bigdl_tpu module from a torch element table; unknown
    classes return the Table annotated with ``__torch_class__``."""
    from .. import nn

    e = elements if isinstance(elements, Table) else Table()

    def num(key, default=None):
        v = e.get(key, default)
        return None if v is None else int(v)

    def _set(mod, **named):
        for our_name, value in named.items():
            if value is None:
                continue
            arr = np.asarray(value, dtype=np.float32)
            if our_name in mod.params:
                if arr.shape != mod.params[our_name].shape:
                    arr = arr.reshape(mod.params[our_name].shape)
                mod.params[our_name] = jnp.asarray(arr)
        return mod

    def _submodules(container):
        mods = e.get("modules")
        if mods is not None:
            for i in sorted(k for k in mods.keys() if isinstance(k, int)):
                container.add(mods[i])
        return container

    if class_name == "nn.Sequential":
        return _submodules(nn.Sequential())
    if class_name == "nn.Concat":
        return _submodules(nn.Concat(num("dimension", 1)))
    if class_name == "nn.ConcatTable":
        return _submodules(nn.ConcatTable())
    if class_name == "nn.Linear":
        w = e.get("weight")
        mod = nn.Linear(int(w.shape[1]), int(w.shape[0]),
                        with_bias=e.get("bias") is not None)
        return _set(mod, weight=w, bias=e.get("bias"))
    if class_name in ("nn.SpatialConvolution", "nn.SpatialConvolutionMM"):
        mod = nn.SpatialConvolution(
            num("nInputPlane"), num("nOutputPlane"),
            num("kW"), num("kH"), num("dW", 1), num("dH", 1),
            num("padW", 0), num("padH", 0),
            n_group=num("groups", 1) or 1,
            with_bias=e.get("bias") is not None)
        return _set(mod, weight=e.get("weight"), bias=e.get("bias"))
    if class_name == "nn.SpatialMaxPooling":
        mod = nn.SpatialMaxPooling(num("kW"), num("kH"), num("dW"),
                                   num("dH"), num("padW", 0), num("padH", 0))
        if e.get("ceil_mode", False):
            mod.ceil()
        return mod
    if class_name == "nn.SpatialAveragePooling":
        mod = nn.SpatialAveragePooling(
            num("kW"), num("kH"), num("dW", 1), num("dH", 1),
            num("padW", 0), num("padH", 0),
            ceil_mode=bool(e.get("ceil_mode", False)),
            count_include_pad=bool(e.get("count_include_pad", True)),
            divide=bool(e.get("divide", True)))
        return mod
    if class_name in ("nn.BatchNormalization", "nn.SpatialBatchNormalization"):
        cls = (nn.SpatialBatchNormalization
               if class_name == "nn.SpatialBatchNormalization"
               else nn.BatchNormalization)
        n = num("nOutput") or int(np.asarray(e.get("running_mean")).shape[0])
        mod = cls(n, eps=float(e.get("eps", 1e-5)),
                  momentum=float(e.get("momentum", 0.1)),
                  affine=e.get("weight") is not None)
        _set(mod, weight=e.get("weight"), bias=e.get("bias"))
        if e.get("running_mean") is not None:
            mod.buffers["running_mean"] = jnp.asarray(
                np.asarray(e["running_mean"], np.float32))
        if e.get("running_var") is not None:
            mod.buffers["running_var"] = jnp.asarray(
                np.asarray(e["running_var"], np.float32))
        return mod
    if class_name == "nn.ReLU":
        return nn.ReLU(bool(e.get("inplace", False)))
    if class_name == "nn.Threshold":
        return nn.Threshold(float(e.get("threshold", 1e-6)),
                            float(e.get("val", 0.0)),
                            bool(e.get("inplace", False)))
    if class_name == "nn.Tanh":
        return nn.Tanh()
    if class_name == "nn.Sigmoid":
        return nn.Sigmoid()
    if class_name == "nn.LogSoftMax":
        return nn.LogSoftMax()
    if class_name == "nn.SoftMax":
        return nn.SoftMax()
    if class_name == "nn.Dropout":
        return nn.Dropout(float(e.get("p", 0.5)))
    if class_name == "nn.View":
        return nn.View(*[int(v) for v in np.asarray(e.get("size")).ravel()])
    if class_name == "nn.Reshape":
        bm = e.get("batchMode")
        return nn.Reshape([int(v) for v in np.asarray(e.get("size")).ravel()],
                          batch_mode=bm if isinstance(bm, bool) else None)
    if class_name == "nn.CAddTable":
        return nn.CAddTable(bool(e.get("inplace", False)))
    if class_name == "nn.SpatialZeroPadding":
        return nn.SpatialZeroPadding(num("pad_l"), num("pad_r"),
                                     num("pad_t"), num("pad_b"))
    if class_name == "nn.SpatialCrossMapLRN":
        return nn.SpatialCrossMapLRN(num("size", 5), float(e.get("alpha", 1.0)),
                                     float(e.get("beta", 0.75)),
                                     float(e.get("k", 1.0)))
    # unknown torch class: hand the raw table back, annotated
    e["__torch_class__"] = class_name
    return e


# ---------------------------------------------------------------------------
# public API (TorchFile.load:79 / save:95 parity)
# ---------------------------------------------------------------------------

def load(path: str) -> Any:
    with open(path, "rb") as f:
        return _Reader(f).read_object()


def save(obj: Any, path: str, overwrite: bool = False) -> None:
    if os.path.exists(path) and not overwrite:
        raise FileExistsError(path)
    with open(path, "wb") as f:
        _Writer(f).write_object(obj)
