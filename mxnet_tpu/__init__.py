"""mxnet_tpu — a TPU-native deep-learning framework with the capabilities of
Apache MXNet 2.x (the reference), re-architected for JAX/XLA/PJRT.

Public surface mirrors the reference package layout
(reference python/mxnet/__init__.py): ``mx.np``/``mx.npx`` numpy frontend,
``mx.nd`` legacy alias, ``mx.gluon`` (Block/HybridBlock/Trainer),
``mx.autograd``, ``mx.optimizer``, ``mx.initializer``, ``mx.kv`` KVStore,
``mx.profiler``, devices (``mx.cpu()``/``mx.tpu()``/``mx.gpu()``), plus the
TPU-first additions: ``mx.parallel`` (mesh/sharding/collectives) and Pallas
kernels under ``mx.ops``.
"""
from __future__ import annotations

__version__ = "0.1.0"

import os as _os

import jax as _jax

# The reference supports int64/float64 arrays end-to-end (INT64 tensor build
# flag, reference CMakeLists.txt:352); enable JAX x64 so those dtypes exist.
# Creation defaults stay float32 (reference numpy-frontend default dtype).
_jax.config.update("jax_enable_x64", True)

# JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR is set
# JAX reads it itself; otherwise a fixed directory inside the checkout
# (git-ignored). The path is part of the cache key, so it must not move.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))

# Crash diagnostics: dump python stack traces on SIGSEGV/SIGABRT/fatal
# signals (reference USE_SIGNAL_HANDLER stack traces, src/initialize.cc).
# Honors the reference env-var name; default on like the release builds.
if _os.environ.get("MXNET_USE_SIGNAL_HANDLER", "1") not in ("0", "false"):
    import faulthandler as _faulthandler
    try:
        _faulthandler.enable()
    except Exception:
        pass

# Fork safety (reference src/initialize.cc:73 pthread_atfork handlers):
# a forked child must not reuse the parent's PJRT handles/engine threads.
# DataLoader workers obey a numpy-only contract; this hook additionally
# clears the native-core handle so the child lazily reopens it.
def _afterfork_child():
    try:
        from .src import nativelib as _nl
        _nl._LIB = None
        _nl._TRIED = False
    except Exception:
        pass


if hasattr(_os, "register_at_fork"):
    _os.register_at_fork(after_in_child=_afterfork_child)

# Multi-process bootstrap must precede XLA backend init, so when this
# process was spawned by tools/launch.py (DMLC env protocol present) the
# jax.distributed rendezvous happens at import time (reference
# kvstore_server.py import-time role)
if int(_os.environ.get("DMLC_NUM_WORKER", "0") or 0) > 1:
    from .kvstore import bootstrap as _bootstrap
    _bootstrap.init_from_env()

from . import base
from .base import MXNetError
from . import device as _device_mod
from .device import Device, Context, cpu, tpu, gpu, cpu_pinned, num_gpus, num_tpus, \
    current_device
from .ndarray import NDArray, waitall
from . import numpy as np
from . import numpy_extension as npx
from . import autograd
from . import _random as random_state
from . import serialization
from .serialization import save, load

# stateful random seed at top level (reference mx.random.seed)
from . import numpy as _np_mod


class _RandomNamespace:
    """mx.random — stateful global RNG (reference python/mxnet/random.py).
    Accepts the np spelling (``size=``, keyword or third positional) AND
    the legacy mx.random spelling (``shape=``)."""
    seed = staticmethod(_np_mod.random.seed)

    @staticmethod
    def _size(kwargs):
        if "shape" in kwargs:
            kwargs = dict(kwargs)
            kwargs["size"] = kwargs.pop("shape")
        return kwargs

    @staticmethod
    def uniform(low=0.0, high=1.0, *args, **kwargs):
        return _np_mod.random.uniform(low, high, *args,
                                      **_RandomNamespace._size(kwargs))

    @staticmethod
    def normal(loc=0.0, scale=1.0, *args, **kwargs):
        return _np_mod.random.normal(loc, scale, *args,
                                     **_RandomNamespace._size(kwargs))

    @staticmethod
    def randint(low, high=None, *args, **kwargs):
        return _np_mod.random.randint(low, high, *args,
                                      **_RandomNamespace._size(kwargs))


random = _RandomNamespace()

# Lazy imports to avoid import cycles; populated on attribute access.
_LAZY = {
    "gluon": ".gluon",
    "optimizer": ".optimizer",
    "initializer": ".initializer",
    "init": ".initializer",
    "lr_scheduler": ".lr_scheduler",
    "kv": ".kvstore",
    "kvstore": ".kvstore",
    "metrics": ".metrics",
    "parallel": ".parallel",
    "pipeline": ".pipeline",
    "ops": ".ops",
    "profiler": ".profiler",
    "runtime": ".runtime",
    "serve": ".serve",
    "aot": ".aot",
    "amp": ".amp",
    "io": ".io",
    "recordio": ".io.recordio",
    "image": ".image",
    "nd": ".nd",
    "observability": ".observability",
    "tune": ".tune",
    "sparse": ".sparse",
    "engine": ".engine",
    "util": ".util",
    "test_utils": ".test_utils",
    "metric": ".gluon.metric",
    "onnx": ".onnx",
    "contrib": ".contrib",
    "visualization": ".visualization",
    "viz": ".visualization",
    "library": ".library",
    "checkpoint": ".checkpoint",
    "benchmark": ".benchmark",
    "sym": ".symbol",
    "symbol": ".symbol",
    "operator": ".operator",
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(_LAZY[name], __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_LAZY))
