"""Device mesh construction and sharding-spec helpers.

The mental model is the scaling-book recipe: pick a mesh, annotate shardings
with PartitionSpecs, let XLA insert collectives. Axis names are conventional:
'dp' (data), 'tp' (tensor), 'sp' (sequence), 'ep' (expert), 'pp' (pipeline).
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional, Sequence

import numpy as onp

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..base import MXNetError

__all__ = ["P", "make_mesh", "local_mesh", "current_mesh", "set_default_mesh",
           "use_mesh",
           "named_sharding", "replicated", "shard_map"]

P = PartitionSpec


def shard_map(f, mesh, in_specs, out_specs, **kwargs):
    """``jax.shard_map`` with the mesh positional — the one spelling every
    manual mapping in the package uses."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


_DEFAULT_MESH: Optional[Mesh] = None


def make_mesh(axes: Dict[str, int], devices: Optional[Sequence] = None) -> Mesh:
    """Build a named mesh, e.g. ``make_mesh({'dp': 2, 'tp': 4})``.

    Axis order follows the dict (outermost first). The product must equal the
    device count. ICI-heavy axes (tp/sp) should be innermost so their
    collectives ride the fastest links — the caller controls this via
    ordering.
    """
    devices = list(devices if devices is not None else jax.devices())
    total = 1
    for v in axes.values():
        total *= v
    if total != len(devices):
        raise MXNetError(
            f"mesh {axes} needs {total} devices, have {len(devices)}")
    arr = onp.array(devices).reshape(tuple(axes.values()))
    return Mesh(arr, tuple(axes.keys()))


def local_mesh(axis_name: str = "dp") -> Mesh:
    """One-axis mesh over all local devices."""
    devs = jax.devices()
    return Mesh(onp.array(devs), (axis_name,))


def set_default_mesh(mesh: Optional[Mesh]):
    global _DEFAULT_MESH
    _DEFAULT_MESH = mesh


def current_mesh() -> Optional[Mesh]:
    return _DEFAULT_MESH


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Make ``mesh`` the current mesh while a sharded program is traced
    (``None`` leaves the current one in place). Code that cannot be
    partitioned by GSPMD — a Pallas kernel — asks :func:`current_mesh`
    and maps itself over the mesh by hand."""
    global _DEFAULT_MESH
    if mesh is None:
        yield
        return
    prev, _DEFAULT_MESH = _DEFAULT_MESH, mesh
    try:
        yield
    finally:
        _DEFAULT_MESH = prev


def named_sharding(mesh: Mesh, spec: Optional[PartitionSpec]) -> NamedSharding:
    return NamedSharding(mesh, spec if spec is not None else P())


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
