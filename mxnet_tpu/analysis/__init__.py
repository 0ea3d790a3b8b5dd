"""Static analysis + runtime guards for TPU-hazard invariants.

Two complementary halves (see README "Static analysis & runtime guards"):

- :mod:`.linter` — mxlint, the AST linter behind ``tools/mxlint.py``:
  rules MX001 (host sync in traced/hot code), MX002 (recompile hazard),
  MX003 (tracer leak), MX004 (numpy-alias hazard), MX005 (lock
  discipline), with inline suppressions and a committed baseline. The
  Pallas kernel family rules MX101 (DMA lifecycle), MX102 (memory-space
  discipline), and MX103 (static VMEM budget vs the ``fusable_*``
  runtime gates) live in :mod:`.kernels` and fire through the same
  pipeline on files containing a ``pallas_call`` site; the
  ``mxnet_*`` telemetry-contract drift check lives in
  :mod:`.metrics_contract` (``tools/mxlint.py --metrics``).
- :mod:`.guards` — the same invariants enforced at runtime:
  ``no_sync()`` / ``no_recompile()`` context managers, the
  ``AliasSentinel`` write-protector for in-flight host buffers, and the
  ``LockOrderWitness`` acquisition-graph recorder (``MXNET_DEBUG_GUARDS=1``
  wires these into DevicePrefetcher, the serve engine, and the
  checkpoint writer).
"""
from . import guards
from .guards import (AliasSentinel, GuardViolation, HostSyncError,
                     LockOrderError, LockOrderWitness, RecompileError,
                     WitnessLock, check_lock_order, debug_guards_enabled,
                     disable_debug, enable_debug,
                     make_lock, no_recompile, no_sync, reset_lock_witness,
                     witness)

# the linter is tooling: every runtime subsystem imports this package for
# guards.make_lock/AliasSentinel, so the ~1k-line AST-rule module loads
# lazily (PEP 562) and only tools/tests pay for it. Same deal for the
# Pallas kernel analyzer (MX1xx) and the telemetry-contract checker.
_LINTER_ATTRS = ("linter", "RULES", "Finding", "lint_file", "lint_paths",
                 "lint_source", "find_cycles")
_KERNEL_ATTRS = ("kernels", "analyze_source", "analyze_file")
_METRICS_ATTRS = ("metrics_contract", "check_metrics_contract")


def __getattr__(name):
    # importlib, not `from . import`: the fromlist path probes the
    # package attribute first, which would re-enter this hook
    import importlib
    if name in _LINTER_ATTRS:
        mod = importlib.import_module(".linter", __name__)
        return mod if name == "linter" else getattr(mod, name)
    if name in _KERNEL_ATTRS:
        mod = importlib.import_module(".kernels", __name__)
        return mod if name == "kernels" else getattr(mod, name)
    if name in _METRICS_ATTRS:
        mod = importlib.import_module(".metrics_contract", __name__)
        return mod if name == "metrics_contract" else getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "guards", "linter",
    "AliasSentinel", "GuardViolation", "HostSyncError", "LockOrderError",
    "LockOrderWitness", "RecompileError", "WitnessLock",
    "check_lock_order", "debug_guards_enabled", "disable_debug",
    "enable_debug", "make_lock", "no_recompile",
    "no_sync", "reset_lock_witness", "witness",
    "RULES", "Finding", "lint_file", "lint_paths", "lint_source",
    "find_cycles",
    "kernels", "analyze_source", "analyze_file",
    "metrics_contract", "check_metrics_contract",
]
