"""Device / Context abstraction over JAX/PJRT devices.

Role of the reference's ``Context`` (python/mxnet/context.py, device.py):
``mx.cpu()`` / ``mx.gpu(i)`` select where NDArrays live and where ops run.
TPU-native redesign: devices are PJRT devices; ``mx.tpu(i)`` is first-class,
``mx.gpu(i)`` is an accelerator alias kept for API compatibility (it resolves
to the i-th non-CPU PJRT device). A thread-local default-device stack mirrors
``with mx.Device(...):`` semantics.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from .base import MXNetError

__all__ = [
    "Device", "Context", "cpu", "tpu", "gpu", "cpu_pinned", "current_device",
    "num_gpus", "num_tpus", "default_backend", "on_tpu",
]


_ACCEL_TYPES = ("tpu", "gpu", "cuda", "rocm")


def _jax_devices(kind: str):
    devs = jax.devices()
    if kind == "cpu":
        cpus = [d for d in devs if d.platform == "cpu"]
        if cpus:
            return cpus
        try:
            return jax.devices("cpu")
        except RuntimeError:
            return []
    return [d for d in devs if d.platform in _ACCEL_TYPES]


def default_backend() -> str:
    """Platform name of the default JAX backend ('tpu', 'cpu', ...)."""
    return jax.default_backend()


def on_tpu() -> bool:
    """True iff the default JAX backend is a TPU — the one predicate every
    kernel and layout choice in the package asks. Where it says yes the
    Pallas kernel runs and a compile error propagates; where it says no the
    plain-XLA reference runs and says so (launch kind ``reference``)."""
    return jax.default_backend() == "tpu"


class Device:
    """A compute device. ``device_type`` in {'cpu', 'tpu', 'gpu', 'cpu_pinned'}.

    'gpu' is accepted for reference API compatibility and resolves to the
    accelerator list (on a TPU machine, the TPU chips).
    """

    _thread_local = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Device):
            device_id = device_type.device_id
            device_type = device_type.device_type
        device_type = device_type.lower()
        if device_type not in ("cpu", "tpu", "gpu", "cpu_pinned", "cpu_shared"):
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- mapping to PJRT ---------------------------------------------------
    @property
    def jax_device(self):
        """The PJRT device this names. An accelerator ``Device`` names the
        ``device_id``-th accelerator and nothing else: it raises when there
        is none or the id is out of range. The one exception is a process
        pinned to the CPU on purpose (``JAX_PLATFORMS=cpu``, how the tests
        run on the virtual mesh): there ``mx.gpu(i)``/``mx.tpu(i)`` name
        the i-th virtual CPU device."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            pool = _jax_devices("cpu")
        else:
            pool = _jax_devices("accel")
            if not pool:
                if jax.config.jax_platforms != "cpu":
                    raise MXNetError(
                        f"{self}: JAX found no accelerator (default backend "
                        f"{jax.default_backend()!r}). Only a process pinned "
                        "to the CPU with JAX_PLATFORMS=cpu (jax.config."
                        "jax_platforms == 'cpu') lets an accelerator Device "
                        "name a CPU device")
                pool = _jax_devices("cpu")
        if not 0 <= self.device_id < len(pool):
            raise MXNetError(
                f"{self}: device_id {self.device_id} out of range, "
                f"{len(pool)} such device(s) present")
        return pool[self.device_id]

    # -- identity ----------------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Device)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    # -- scoping -----------------------------------------------------------
    def __enter__(self):
        stack = getattr(Device._thread_local, "stack", None)
        if stack is None:
            stack = Device._thread_local.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        Device._thread_local.stack.pop()
        return False

    @classmethod
    def current(cls) -> "Device":
        stack = getattr(cls._thread_local, "stack", None)
        if stack:
            return stack[-1]
        return _default_device()


#: Back-compat alias (reference python/mxnet/context.py)
Context = Device


def _default_device() -> Device:
    return Device("tpu", 0) if _jax_devices("accel") else Device("cpu", 0)


def current_device() -> Device:
    return Device.current()


def cpu(device_id: int = 0) -> Device:
    return Device("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Device:
    return Device("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Device:
    return Device("tpu", device_id)


def gpu(device_id: int = 0) -> Device:
    """Accelerator alias for reference compatibility; resolves to TPU here."""
    return Device("gpu", device_id)


def num_gpus() -> int:
    return len(_jax_devices("accel"))


def num_tpus() -> int:
    return len(_jax_devices("accel"))
