"""Build/runtime feature introspection (reference include/mxnet/libinfo.h,
src/libinfo.cc, python/mxnet/runtime.py — mx.runtime.Features)."""
from __future__ import annotations

from collections import OrderedDict

import jax

from .device import on_tpu

__all__ = ["Feature", "Features", "feature_list"]


class Feature:
    def __init__(self, name: str, enabled: bool):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _detect() -> "OrderedDict[str, Feature]":
    feats = OrderedDict()

    def add(name, enabled):
        feats[name] = Feature(name, bool(enabled))

    tpu = on_tpu()
    add("TPU", tpu)
    add("CPU", True)
    add("CUDA", False)           # reference flag names kept for parity
    add("CUDNN", False)
    add("NCCL", False)
    add("XLA", True)
    add("PALLAS", tpu)
    add("BF16", True)
    add("INT64_TENSOR_SIZE", jax.config.jax_enable_x64)
    add("DIST", True)            # jax.distributed collectives available
    try:
        from .src import nativelib
        add("NATIVE_CORE", nativelib.available())
    except Exception:
        add("NATIVE_CORE", False)
    add("OPENCV", _has("cv2"))
    add("PIL", _has("PIL"))
    add("SIGNAL_HANDLER", True)
    return feats


def _has(mod: str) -> bool:
    import importlib.util
    return importlib.util.find_spec(mod) is not None


class Features:
    """Reference mx.runtime.Features: mapping of feature name -> Feature."""

    def __init__(self):
        self._feats = _detect()

    def __getitem__(self, name: str) -> Feature:
        return self._feats[name.upper()]

    def __contains__(self, name):
        return name.upper() in self._feats

    def keys(self):
        return self._feats.keys()

    def values(self):
        return self._feats.values()

    def items(self):
        return self._feats.items()

    def is_enabled(self, name: str) -> bool:
        return self._feats[name.upper()].enabled

    def __repr__(self):
        return ", ".join(repr(f) for f in self._feats.values())


def feature_list():
    return list(Features().values())
