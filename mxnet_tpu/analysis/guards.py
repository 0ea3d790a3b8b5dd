"""Runtime guards enforcing the mxlint invariants dynamically.

The linter (:mod:`mxnet_tpu.analysis.linter`) finds hazard *patterns*;
these guards catch the *events*: a host sync inside a window that must
stay async, a recompilation after warmup, a host buffer mutated while a
dispatch may still be reading it, and lock acquisitions whose order could
deadlock. Each guard either raises (test/CI mode) or counts through the
existing telemetry (``mxnet_guard_violations_total{guard=...}``) so
production can observe without crashing.

- :func:`no_sync` — context manager; any device→host sync through the
  framework funnel (``NDArray.asnumpy/item/wait_to_read``,
  ``jax.block_until_ready``, ``jax.device_get``) inside the block raises
  :class:`HostSyncError` (``action="raise"``) or counts
  (``action="count"``). On real device backends jax's transfer guard is
  armed as well; on CPU, transfers are zero-copy and only the funnel
  fires — which is exactly the funnel all mxnet_tpu hot paths use.
- :func:`no_recompile` — context manager; proves a window added zero
  trace builds by diffing ``mxnet_recompilations_total`` (optionally
  restricted to a ``block`` label prefix, e.g. ``"serve"`` or
  ``"TrainStep"``). Temporarily enables metrics collection if needed.
- :class:`AliasSentinel` — flips ``writeable=False`` on host numpy
  buffers while a dispatch that may zero-copy-alias them is in flight;
  any mutation raises ``ValueError`` at the *write site* (the PR-4 serve
  corruption, caught at dispatch time instead of as wrong tokens).
- :class:`LockOrderWitness` / :func:`make_lock` — named lock wrappers
  that record the per-thread acquisition graph across the threaded
  subsystems (serve engine, checkpoint writer, prefetcher, metrics);
  :func:`check_lock_order` fails tests on inversions/cycles, and
  acquiring a lock this thread already holds raises immediately instead
  of deadlocking.

Debug wiring: ``MXNET_DEBUG_GUARDS=1`` (or :func:`enable_debug`) makes
``make_lock`` return witness locks and turns on the alias sentinel inside
``DevicePrefetcher``.
The disabled path is a plain ``threading.Lock`` and ``None`` sentinels —
zero overhead in production.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, List, Optional, Tuple

from ..base import MXNetError, get_env

__all__ = [
    "GuardViolation", "HostSyncError", "RecompileError", "LockOrderError",
    "no_sync", "no_recompile", "AliasSentinel",
    "LockOrderWitness", "WitnessLock", "make_lock", "witness",
    "check_lock_order", "reset_lock_witness",
    "debug_guards_enabled", "enable_debug", "disable_debug",
]


class GuardViolation(MXNetError):
    """Base class for runtime-guard violations."""


class HostSyncError(GuardViolation):
    """A device->host sync happened inside a no_sync() window."""


class RecompileError(GuardViolation):
    """A trace build happened inside a no_recompile() window."""


class LockOrderError(GuardViolation):
    """Lock acquisition order is cyclic (or a lock was re-acquired)."""


# ---------------------------------------------------------------------------
# debug-guard switch (MXNET_DEBUG_GUARDS)
# ---------------------------------------------------------------------------

_DEBUG = bool(get_env(
    "MXNET_DEBUG_GUARDS", False, dtype=bool,
    doc="enable runtime hazard guards: witness locks, alias sentinels on "
        "prefetcher staging buffers"))


def debug_guards_enabled() -> bool:
    return _DEBUG


def enable_debug():
    """Turn on debug guards for objects constructed from now on."""
    global _DEBUG
    _DEBUG = True


def disable_debug():
    global _DEBUG
    _DEBUG = False


def _count_violation(guard: str, n: int = 1):
    from .. import metrics as _metrics
    if _metrics.ENABLED:
        _metrics.GUARD_VIOLATIONS.labels(guard=guard).inc(n)
    # every counted violation also lands in the flight recorder (and
    # triggers a rate-limited dump): a dynamically broken invariant is
    # exactly when the last-N-events context is worth a file
    try:
        from ..observability import recorder as _recorder
        _recorder.RECORDER.record_violation(guard, n)
    except Exception:   # pragma: no cover - observability never crashes us
        pass


# ---------------------------------------------------------------------------
# no_sync
# ---------------------------------------------------------------------------

class _GuardState:
    """Mutable result handle yielded by the guard context managers."""

    __slots__ = ("action", "violations", "detail")

    def __init__(self, action: str):
        self.action = action
        self.violations = 0
        self.detail: List[str] = []


_tls = threading.local()
_patch_lock = threading.Lock()
_patched = False


def _sync_states() -> List[_GuardState]:
    return getattr(_tls, "no_sync", [])


def _on_sync(what: str):
    states = _sync_states()
    if not states:
        return
    for st in states:
        st.violations += 1
        st.detail.append(what)
    _count_violation("no_sync", 1)
    if any(st.action == "raise" for st in states):
        raise HostSyncError(
            f"host sync {what} inside a no_sync() window — this stalls "
            "the dispatch pipeline (move the read outside the window, or "
            "use copy_to_host_async + a later force)")


def _install_sync_patches():
    """Wrap the framework's sync funnel once, process-wide. The wrappers
    are pass-through (one thread-local read) while no guard is active."""
    global _patched
    with _patch_lock:
        if _patched:
            return
        import jax
        from ..ndarray import NDArray

        def wrap_method(cls, name):
            orig = getattr(cls, name)

            def wrapper(self, *a, **kw):
                _on_sync(f".{name}()")
                return orig(self, *a, **kw)

            wrapper.__name__ = name
            wrapper.__wrapped__ = orig
            setattr(cls, name, wrapper)

        def wrap_func(mod, name):
            orig = getattr(mod, name)

            def wrapper(*a, **kw):
                _on_sync(f"jax.{name}()")
                return orig(*a, **kw)

            wrapper.__name__ = name
            wrapper.__wrapped__ = orig
            setattr(mod, name, wrapper)

        for m in ("asnumpy", "item", "wait_to_read"):
            wrap_method(NDArray, m)
        for f in ("block_until_ready", "device_get"):
            wrap_func(jax, f)
        _patched = True


@contextlib.contextmanager
def no_sync(action: str = "raise"):
    """Assert no device->host sync happens in this block (this thread).

    ``action="raise"``: the first sync raises :class:`HostSyncError` at
    the sync site. ``action="count"``: syncs increment the yielded
    state's ``.violations`` and ``mxnet_guard_violations_total
    {guard="no_sync"}``. Yields the :class:`_GuardState`."""
    if action not in ("raise", "count"):
        raise MXNetError(f"no_sync: unknown action {action!r}")
    _install_sync_patches()
    st = _GuardState(action)
    stack = getattr(_tls, "no_sync", None)
    if stack is None:
        stack = _tls.no_sync = []
    stack.append(st)
    guard_cm = None
    if action == "raise":
        # best-effort backstop for raw jax arrays on real device backends
        # (on CPU, D2H is zero-copy and the transfer guard stays silent)
        try:
            import jax
            guard_cm = jax.transfer_guard_device_to_host("disallow")
            guard_cm.__enter__()
        except Exception:
            guard_cm = None
    try:
        yield st
    finally:
        if guard_cm is not None:
            guard_cm.__exit__(None, None, None)
        stack.remove(st)


# ---------------------------------------------------------------------------
# no_recompile
# ---------------------------------------------------------------------------

def _recompile_counts(prefix: Optional[str]) -> Dict[Tuple[str, ...], float]:
    from .. import metrics as _metrics
    out: Dict[Tuple[str, ...], float] = {}
    for labelvalues, child in _metrics.RECOMPILATIONS.children():
        labels = dict(zip(_metrics.RECOMPILATIONS.labelnames, labelvalues))
        if prefix is not None and not labels.get("block", "").startswith(
                prefix):
            continue
        out[labelvalues] = child.value
    return out


@contextlib.contextmanager
def no_recompile(block: Optional[str] = None, action: str = "raise"):
    """Assert the block added ZERO trace builds (process-wide — background
    engine/prefetcher threads count too, which is the point).

    ``block`` restricts to ``mxnet_recompilations_total`` children whose
    ``block`` label starts with the prefix (e.g. ``"serve"``,
    ``"TrainStep"``); None watches every block. Metrics collection is
    enabled for the duration if it was off. The yielded state carries
    ``.violations`` (new trace builds) and ``.detail``."""
    if action not in ("raise", "count"):
        raise MXNetError(f"no_recompile: unknown action {action!r}")
    from .. import metrics as _metrics
    was_enabled = _metrics.enabled()
    if not was_enabled:
        _metrics.enable()
    before = _recompile_counts(block)
    st = _GuardState(action)
    body_raised = False
    try:
        yield st
    except BaseException:
        body_raised = True
        raise
    finally:
        after = _recompile_counts(block)
        grown = []
        for key, val in after.items():
            delta = val - before.get(key, 0.0)
            if delta > 0:
                labels = dict(zip(_metrics.RECOMPILATIONS.labelnames, key))
                grown.append(f"{labels} +{int(delta)}")
        if grown:
            st.violations = len(grown)
            st.detail = grown
            # count BEFORE restoring the metrics switch, so the telemetry
            # lands even when this guard was what enabled collection
            _count_violation("no_recompile", len(grown))
        if not was_enabled:
            _metrics.disable()
        # never mask the body's own exception with the guard's
        if grown and action == "raise" and not body_raised:
            scope = f" (block prefix {block!r})" if block else ""
            raise RecompileError(
                f"trace builds inside a no_recompile() window{scope}: "
                + "; ".join(grown) + " — an input signature "
                "(shape/dtype/static arg) is unstable, or warmup "
                "missed a bucket")


# ---------------------------------------------------------------------------
# alias sentinel
# ---------------------------------------------------------------------------

def _numpy_leaves(tree) -> List[Any]:
    import numpy as onp
    from ..ndarray import NDArray
    out: List[Any] = []

    def walk(x):
        if isinstance(x, (tuple, list)):
            for e in x:
                walk(e)
        elif isinstance(x, dict):
            for e in x.values():
                walk(e)
        elif isinstance(x, NDArray):
            walk(x._data)
        elif isinstance(x, onp.ndarray):
            out.append(x)

    walk(tree)
    return out


class AliasSentinel:
    """Write-protects host numpy buffers while a dispatch that may
    zero-copy-alias them is in flight.

    ``seal(*trees)`` flips ``writeable=False`` on every numpy leaf (a
    later mutation raises ``ValueError`` at the write site);
    ``release(*trees)`` restores the original flag. ``inflight`` scopes a
    seal to a block. Sealing a read-only view does not protect its base —
    seal the owning buffer. Thread-compatible: seal/release pairs are
    keyed by buffer identity."""

    def __init__(self):
        self._sealed: Dict[int, Tuple[Any, bool]] = {}
        self._lock = threading.Lock()

    def seal(self, *trees) -> int:
        n = 0
        with self._lock:
            for arr in [leaf for t in trees for leaf in _numpy_leaves(t)]:
                key = id(arr)
                if key in self._sealed:
                    continue
                self._sealed[key] = (arr, bool(arr.flags.writeable))
                try:
                    arr.flags.writeable = False
                except ValueError:
                    # e.g. a view of a buffer we don't own: best effort
                    del self._sealed[key]
                    continue
                n += 1
        return n

    def release(self, *trees) -> int:
        n = 0
        with self._lock:
            for arr in [leaf for t in trees for leaf in _numpy_leaves(t)]:
                entry = self._sealed.pop(id(arr), None)
                if entry is None:
                    continue
                arr.flags.writeable = entry[1]
                n += 1
        return n

    def release_all(self):
        with self._lock:
            for arr, writeable in self._sealed.values():
                try:
                    arr.flags.writeable = writeable
                except ValueError:
                    pass
            self._sealed.clear()

    @property
    def sealed_count(self) -> int:
        return len(self._sealed)

    @contextlib.contextmanager
    def inflight(self, *trees):
        """Seal for the duration of a dispatch window."""
        self.seal(*trees)
        try:
            yield self
        finally:
            self.release(*trees)


# ---------------------------------------------------------------------------
# lock-order witness
# ---------------------------------------------------------------------------

class LockOrderWitness:
    """Records the cross-thread lock-acquisition graph. Nodes are lock
    *names* (role-level: every serve engine's ``_lock`` is one node), an
    edge a→b means some thread acquired b while holding a. An edge pair
    {a→b, b→a} — or any longer cycle — is a potential deadlock;
    :meth:`check` raises with the witness sites."""

    def __init__(self):
        self._mu = threading.Lock()          # plain: never witnessed
        self._tls = threading.local()
        # (a, b) -> "thread=... first seen in ..." witness description
        self._edges: Dict[Tuple[str, str], str] = {}
        # every lock name ever acquired (coverage assertion for tests)
        self._nodes: set = set()

    # ------------------------------------------------------------- hooks
    def _held(self) -> List["WitnessLock"]:
        held = getattr(self._tls, "held", None)
        if held is None:
            held = self._tls.held = []
        return held

    def note_acquiring(self, lock: "WitnessLock"):
        held = self._held()
        for h in held:
            if h is lock:
                raise LockOrderError(
                    f"thread {threading.current_thread().name!r} "
                    f"re-acquiring non-reentrant lock {lock.name!r} it "
                    "already holds — this would deadlock")

    def note_acquired(self, lock: "WitnessLock"):
        held = self._held()
        tname = threading.current_thread().name
        with self._mu:
            self._nodes.add(lock.name)
            for h in held:
                if h.name == lock.name:
                    continue
                self._edges.setdefault(
                    (h.name, lock.name),
                    f"thread {tname!r} acquired {lock.name!r} while "
                    f"holding {h.name!r}")
        held.append(lock)

    def note_released(self, lock: "WitnessLock"):
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i] is lock:
                del held[i]
                return

    # ----------------------------------------------------------- queries
    def edges(self) -> Dict[Tuple[str, str], str]:
        with self._mu:
            return dict(self._edges)

    def nodes(self) -> set:
        """Every lock name the witness has seen acquired."""
        with self._mu:
            return set(self._nodes)

    def cycles(self) -> List[List[str]]:
        # lazy: the linter module stays out of production processes that
        # only ever take/release witness locks
        from .linter import find_cycles
        return find_cycles(self.edges())

    def check(self):
        """Raise :class:`LockOrderError` when the recorded acquisition
        graph contains a cycle (counts a violation in telemetry too)."""
        cycles = self.cycles()
        if not cycles:
            return
        edges = self.edges()
        _count_violation("lock_order", len(cycles))
        lines = []
        for cyc in cycles:
            lines.append(" -> ".join(cyc))
            for a, b in zip(cyc, cyc[1:]):
                if (a, b) in edges:
                    lines.append(f"  {edges[(a, b)]}")
        raise LockOrderError(
            "cyclic lock acquisition order across threads (potential "
            "deadlock):\n" + "\n".join(lines))

    def reset(self):
        with self._mu:
            self._edges.clear()
            self._nodes.clear()


class WitnessLock:
    """A named ``threading.Lock`` that reports acquisitions to the
    process witness. Drop-in for ``threading.Lock()`` — also works as the
    lock behind a ``threading.Condition``."""

    def __init__(self, name: str, witness: Optional[LockOrderWitness] = None):
        self.name = name
        self._lock = threading.Lock()
        self._witness = witness or _WITNESS

    # Condition() probes ownership via acquire(0); keep full signature
    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if blocking:
            self._witness.note_acquiring(self)
        got = self._lock.acquire(blocking, timeout)
        if got:
            self._witness.note_acquired(self)
        return got

    def release(self):
        self._lock.release()
        self._witness.note_released(self)

    def locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()


_WITNESS = LockOrderWitness()


def witness() -> LockOrderWitness:
    """The process-wide lock-order witness."""
    return _WITNESS


def check_lock_order():
    _WITNESS.check()


def reset_lock_witness():
    _WITNESS.reset()


def make_lock(name: str):
    """Factory the threaded subsystems use for their locks: a plain
    ``threading.Lock`` normally, a :class:`WitnessLock` feeding the
    lock-order witness when debug guards are enabled."""
    if _DEBUG:
        return WitnessLock(name)
    return threading.Lock()
