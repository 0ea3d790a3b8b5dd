"""mx.library — load external operator libraries (reference
python/mxnet/library.py load() + the custom-op trampoline,
src/operator/custom/custom.cc; extension ABI in src/ext_api.h, the role of
reference include/mxnet/lib_api.h).

Loaded ops become callables taking/returning NDArrays. On TPU they execute
as HOST callbacks inside the XLA program (``jax.pure_callback``): the op
composes with jit/hybridize/vmap-free code, streams device→host→device,
and — when the library exports a backward — participates in autograd via
``jax.custom_vjp``.
"""
from __future__ import annotations

import ctypes
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as onp

from .base import MXNetError, logger
from .ndarray import NDArray, apply_multi, asarray

__all__ = ["load", "loaded_libraries"]

_ABI_VERSION = 1
_MAX_NDIM = 8

_DTYPE_TO_CODE = {"float32": 0, "float64": 1, "float16": 2,
                  "int32": 4, "int64": 5, "int8": 6, "uint8": 7}
_CODE_TO_DTYPE = {v: k for k, v in _DTYPE_TO_CODE.items()}


def _cpu_device():
    """CPU device for callback execution; None when CPU is already the
    default backend (no transfer needed)."""
    try:
        if jax.devices()[0].platform == "cpu":
            return None
        return jax.local_devices(backend="cpu")[0]
    except Exception:
        return None


class _ExtTensor(ctypes.Structure):
    _fields_ = [("data", ctypes.c_void_p),
                ("shape", ctypes.c_int64 * _MAX_NDIM),
                ("ndim", ctypes.c_int32),
                ("dtype", ctypes.c_int32)]


def _desc_from_array(arr: onp.ndarray) -> _ExtTensor:
    t = _ExtTensor()
    arr = onp.ascontiguousarray(arr)
    t.data = arr.ctypes.data_as(ctypes.c_void_p)
    for i, s in enumerate(arr.shape):
        t.shape[i] = s
    t.ndim = arr.ndim
    key = str(arr.dtype)
    if key not in _DTYPE_TO_CODE:
        raise MXNetError(f"extension ops do not support dtype {key}")
    t.dtype = _DTYPE_TO_CODE[key]
    return t, arr  # keep the (possibly copied) array alive


def _desc_from_spec(shape, dtype) -> _ExtTensor:
    t = _ExtTensor()
    for i, s in enumerate(shape):
        t.shape[i] = s
    t.ndim = len(shape)
    t.dtype = _DTYPE_TO_CODE[str(onp.dtype(dtype))]
    return t


def _spec_of(t: _ExtTensor):
    shape = tuple(t.shape[i] for i in range(t.ndim))
    return shape, onp.dtype(_CODE_TO_DTYPE[t.dtype])


class ExtensionOp:
    """One operator exported by an extension library."""

    def __init__(self, lib: "ExtensionLibrary", name: str):
        self._lib = lib
        self.name = name
        n_in, n_out = ctypes.c_int(), ctypes.c_int()
        lib._check(lib._h.MXTExtOpArity(name.encode(), ctypes.byref(n_in),
                                        ctypes.byref(n_out)),
                   f"{name}: arity")
        self.n_in, self.n_out = n_in.value, n_out.value
        self.has_backward = bool(
            getattr(lib._h, "MXTExtOpHasBackward", None)
            and lib._h.MXTExtOpHasBackward(name.encode()))
        self._fn = self._build()

    # ----------------------------------------------------------- internals
    def _infer(self, in_specs) -> List[Tuple[Tuple[int, ...], onp.dtype]]:
        ins = (_ExtTensor * self.n_in)(
            *[_desc_from_spec(s, d) for s, d in in_specs])
        outs = (_ExtTensor * self.n_out)()
        self._lib._check(
            self._lib._h.MXTExtOpInferShape(self.name.encode(), ins,
                                            self.n_in, outs, self.n_out),
            f"{self.name}: infer_shape")
        return [_spec_of(outs[i]) for i in range(self.n_out)]

    def _run_host(self, entry, host_ins, out_specs):
        """Invoke a C entry point on host numpy buffers."""
        keep = []
        descs = []
        for a in host_ins:
            d, arr = _desc_from_array(onp.asarray(a))
            descs.append(d)
            keep.append(arr)
        ins = (_ExtTensor * len(descs))(*descs)
        host_outs = [onp.empty(s, d) for s, d in out_specs]
        out_descs = []
        for a in host_outs:
            d, arr = _desc_from_array(a)
            out_descs.append(d)
            keep.append(arr)
        outs = (_ExtTensor * len(out_descs))(*out_descs)
        self._lib._check(entry(self.name.encode(), ins, len(descs),
                               outs, len(out_descs)),
                         f"{self.name}: execute")
        # _desc_from_array may have copied for contiguity; read back via
        # the kept arrays backing the descriptors
        return tuple(keep[len(host_ins):])

    def _build(self):
        op = self

        def forward_host(*host_ins):
            specs = [(a.shape, a.dtype) for a in host_ins]
            out_specs = op._infer(specs)
            return op._run_host(op._lib._h.MXTExtOpForward, host_ins,
                                out_specs)

        def call(*vals):
            out_specs = op._infer([(v.shape, v.dtype) for v in vals])
            result_shape = tuple(
                jax.ShapeDtypeStruct(s, d) for s, d in out_specs)
            # Route the callback through the CPU backend: a backend
            # without host send/recv support can't bind callbacks on
            # device-committed operands. Outside
            # an accelerator jit these are explicit transfers; inside one
            # they require the backend to support host callbacks.
            cpu = _cpu_device()
            if cpu is not None:
                back = [getattr(v, "device", None) for v in vals]
                vals = tuple(jax.device_put(v, cpu) for v in vals)
                outs = jax.pure_callback(forward_host, result_shape, *vals,
                                         vmap_method="sequential")
                dst = next((d for d in back if d is not None), None)
                if dst is not None and dst != cpu:
                    outs = tuple(jax.device_put(o, dst) for o in outs)
                return outs
            return jax.pure_callback(forward_host, result_shape, *vals,
                                     vmap_method="sequential")

        if not self.has_backward:
            return call

        @jax.custom_vjp
        def fn(*vals):
            return call(*vals)

        def fwd(*vals):
            outs = call(*vals)
            return outs, (vals, outs)

        def bwd(res, gs):
            vals, outs = res
            in_specs = [(v.shape, onp.dtype(str(v.dtype))) for v in vals]

            def backward_host(*host_args):
                return op._run_host(op._lib._h.MXTExtOpBackward,
                                    host_args, in_specs)

            result_shape = tuple(jax.ShapeDtypeStruct(s, d)
                                 for s, d in in_specs)
            args = tuple(gs) + vals + outs
            cpu = _cpu_device()
            if cpu is not None:
                back = [getattr(v, "device", None) for v in vals]
                args = tuple(jax.device_put(a, cpu) for a in args)
                grads = jax.pure_callback(
                    backward_host, result_shape, *args,
                    vmap_method="sequential")
                dst = next((d for d in back if d is not None), None)
                if dst is not None and dst != cpu:
                    grads = tuple(jax.device_put(g, dst) for g in grads)
                return tuple(grads)
            grads = jax.pure_callback(
                backward_host, result_shape, *args,
                vmap_method="sequential")
            return tuple(grads)

        fn.defvjp(fwd, bwd)
        return fn

    # -------------------------------------------------------------- call
    def __call__(self, *inputs):
        if len(inputs) != self.n_in:
            raise MXNetError(
                f"{self.name} expects {self.n_in} inputs, got {len(inputs)}")
        nds = [x if isinstance(x, NDArray) else asarray(x) for x in inputs]
        out = apply_multi(self._fn, nds, name=f"ext::{self.name}")
        if self.n_out == 1 and isinstance(out, tuple):
            return out[0]
        return out

    def __repr__(self):
        return (f"ExtensionOp({self.name}, n_in={self.n_in}, "
                f"n_out={self.n_out}, backward={self.has_backward})")


class ExtensionLibrary:
    def __init__(self, path: str):
        self.path = path
        try:
            self._h = ctypes.CDLL(path)
        except OSError as e:
            raise MXNetError(f"cannot load extension {path}: {e}")
        for sym in ("MXTExtABIVersion", "MXTExtOpCount", "MXTExtOpName",
                    "MXTExtOpArity", "MXTExtOpInferShape",
                    "MXTExtOpForward"):
            if not hasattr(self._h, sym):
                raise MXNetError(f"{path}: missing required symbol {sym}")
        self._h.MXTExtOpName.restype = ctypes.c_char_p
        ver = self._h.MXTExtABIVersion()
        if ver != _ABI_VERSION:
            raise MXNetError(
                f"{path}: extension ABI {ver} != framework ABI {_ABI_VERSION}")
        self.ops: Dict[str, ExtensionOp] = {}
        for i in range(self._h.MXTExtOpCount()):
            name = self._h.MXTExtOpName(i).decode()
            self.ops[name] = ExtensionOp(self, name)
            setattr(self, name, self.ops[name])
        logger.info("loaded extension %s: ops %s", path, sorted(self.ops))

    def _check(self, ret: int, what: str):
        if ret != 0:
            raise MXNetError(f"extension {self.path}: {what} failed")

    def __repr__(self):
        return f"ExtensionLibrary({self.path}, ops={sorted(self.ops)})"


_LOADED: Dict[str, ExtensionLibrary] = {}


def load(path: str, verbose: bool = True) -> ExtensionLibrary:
    """Load an extension library (reference mx.library.load): returns a
    handle whose attributes are the exported ops; ops are also registered
    into ``mxnet_tpu.npx`` under their exported names."""
    if path in _LOADED:
        return _LOADED[path]
    lib = ExtensionLibrary(path)
    _LOADED[path] = lib
    from . import numpy_extension as npx
    for name, op in lib.ops.items():
        if hasattr(npx, name):
            logger.warning("extension op %r shadows an existing npx "
                           "attribute; keeping the builtin", name)
            continue
        setattr(npx, name, op)
    return lib


def loaded_libraries() -> List[str]:
    return sorted(_LOADED)
