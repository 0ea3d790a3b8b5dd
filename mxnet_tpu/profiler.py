"""Profiler: chrome-trace JSON + aggregate stats + device memory stats.

Reference: src/profiler/ (2,836 LoC — Profiler class profiler.h:263,
chrome://tracing JSON profiler.h:87, aggregate stats aggregate_stats.cc,
GPU memory profiler storage_profiler.cc) + python/mxnet/profiler.py.

TPU redesign: two cooperating layers —
1. the frontend scope profiler here (ops, python scopes, custom tasks/
   counters/markers) emitting chrome-trace JSON and aggregate tables;
2. XLA/PJRT device tracing via ``jax.profiler`` (TensorBoard/perfetto) for
   on-chip timing, started/stopped by the same set_state calls.
Memory stats come from PJRT ``memory_stats()`` (the storage-profiler role).
"""
from __future__ import annotations

import json
import threading
import time
from typing import Any, Dict, List, Optional

import jax

from .base import MXNetError, get_env, logger

__all__ = [
    "set_config", "set_state", "state", "dump", "dumps", "pause", "resume",
    "Task", "Frame", "Counter", "Marker", "scope", "record_span",
    "device_memory_stats", "counter_event", "dropped_events",
]

_LOCK = threading.Lock()
_CONFIG = {
    "filename": get_env("MXNET_PROFILER_FILENAME", "profile.json",
                        doc="chrome-trace output path"),
    "profile_all": False,
    "profile_imperative": True,
    # reference set_config compatibility keys (profiler.cc params): the
    # executor/API layers here all funnel through the same event stream,
    # so these act as accepted no-op filters
    "profile_symbolic": True,
    "profile_api": True,
    "profile_memory": True,
    "continuous_dump": False,
    "aggregate_stats": True,
    # request-trace spans bridged from observability.trace (cat="trace");
    # on by default so one profile carries kernels, steps AND requests
    "profile_trace": True,
    "use_xla_profiler": False,
    "xla_logdir": "/tmp/mxtpu_xla_trace",
    # event cap: beyond this the buffer stops growing and a dropped-events
    # counter ticks (unbounded _EVENTS growth was the r6 memory pathology)
    "max_events": get_env("MXNET_PROFILER_MAX_EVENTS", 1_000_000,
                          doc="chrome-trace in-memory event cap; events "
                              "beyond it are counted as dropped"),
}
_STATE = {"running": False, "paused": False, "xla_running": False}
# fast-path flag consulted by runtime hot paths (_tape.invoke, CachedOp,
# TrainStep, DataLoader) — True only while running and not paused
ACTIVE = False
_EVENTS: List[Dict[str, Any]] = []
# name -> [count, total_us, min_us, max_us]: running aggregates, O(1)
# memory per name (a full duration list grew without bound on long runs).
# Events dropped by the trace cap STILL aggregate — the table stays
# complete even when the trace is truncated.
_AGG: Dict[str, List[float]] = {}
_START_TS: Optional[float] = None
_DROPPED = 0


def dropped_events() -> int:
    """Events discarded by the ``max_events`` cap over the process
    lifetime — monotone, so its metrics mirror
    (mxnet_profiler_dropped_events_total) is a valid Prometheus counter
    (a reset would make rate()/increase() fabricate spikes)."""
    return _DROPPED


def _append_locked(ev: Dict[str, Any]) -> bool:
    """Append one event honoring the cap; caller holds _LOCK. Returns
    False when the event was dropped."""
    global _DROPPED
    if len(_EVENTS) >= _CONFIG["max_events"]:
        _DROPPED += 1
        return False
    _EVENTS.append(ev)
    return True


def set_config(**kwargs):
    """Reference profiler.set_config."""
    unknown = set(kwargs) - set(_CONFIG)
    if unknown:
        raise MXNetError(f"profiler.set_config: unknown keys {sorted(unknown)}")
    _CONFIG.update(kwargs)


def set_state(state_name: str = "stop", profile_process: str = "worker"):
    """'run' | 'stop' (reference profiler.set_state)."""
    global _START_TS
    global ACTIVE
    if state_name == "run":
        _STATE["running"] = True
        _STATE["paused"] = False
        ACTIVE = True
        _START_TS = time.perf_counter()
        if _CONFIG["use_xla_profiler"] and not _STATE["xla_running"]:
            try:
                jax.profiler.start_trace(_CONFIG["xla_logdir"])
                _STATE["xla_running"] = True
            except Exception as e:
                logger.warning("XLA profiler unavailable: %s", e)
    elif state_name == "stop":
        _STATE["running"] = False
        ACTIVE = False
        if _STATE["xla_running"]:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            _STATE["xla_running"] = False
    else:
        raise MXNetError(f"bad profiler state {state_name!r}")


def state() -> str:
    return "run" if _STATE["running"] else "stop"


def pause(profile_process: str = "worker"):
    global ACTIVE
    _STATE["paused"] = True
    ACTIVE = False


def resume(profile_process: str = "worker"):
    global ACTIVE
    _STATE["paused"] = False
    ACTIVE = _STATE["running"]


def _active() -> bool:
    return ACTIVE


# categories that can be disabled via set_config while the profiler runs
_CATEGORY_GATE = {"operation": "profile_imperative",
                  "trace": "profile_trace"}


def record_span(name: str, cat: str, t0: float, t1: float, args=None):
    """Record one completed span; runtime hook entry point (the role of the
    reference engine feeding profiler.h:263 from PushAsync opr names)."""
    if not ACTIVE or _START_TS is None:
        return
    gate = _CATEGORY_GATE.get(cat)
    if gate and not _CONFIG[gate]:
        return
    _emit(name, cat, (t0 - _START_TS) * 1e6, (t1 - t0) * 1e6, args)


def _emit(name: str, cat: str, ts_us: float, dur_us: float, args=None):
    if ts_us < 0:
        # a span whose t0 predates set_state("run") would carry a negative
        # ts, which trace viewers reject; clamp to the profile origin and
        # keep the end point where it was
        dur_us = max(dur_us + ts_us, 0.0)
        ts_us = 0.0
    with _LOCK:
        _append_locked({
            "name": name, "cat": cat, "ph": "X", "ts": ts_us, "dur": dur_us,
            "pid": 0, "tid": threading.get_ident() % 100000,
            "args": args or {},
        })
        if _CONFIG["aggregate_stats"]:
            agg = _AGG.get(name)
            if agg is None:
                _AGG[name] = [1, dur_us, dur_us, dur_us]
            else:
                agg[0] += 1
                agg[1] += dur_us
                if dur_us < agg[2]:
                    agg[2] = dur_us
                if dur_us > agg[3]:
                    agg[3] = dur_us


class scope(jax.profiler.TraceAnnotation):
    """Time a python scope: THE span helper of the runtime (CachedOp,
    TrainStep, DataLoader, StepTimeline phases and the serving engine's
    tick all time through this). One pair of clock reads per boundary
    feeds three sinks:

    - a ``jax.profiler.TraceAnnotation`` (TraceMe) of the same name, so the
      span sits on the clock of the device trace whenever a JAX trace is
      being taken — and is a no-op otherwise: the JAX profiler, not a flag
      of ours, decides whether it is recorded. ``attrs`` (and ``set()``,
      for what is known only inside the span) become its metadata;
    - one chrome-trace slice while this module's profiler is ACTIVE;
    - ``hist.observe(seconds)`` where a registry histogram is given.

    After exit ``seconds`` holds the elapsed time and ``t1`` the closing
    ``perf_counter`` stamp (for a caller that stamps on). Exception-safe: a
    failing body still records its span. About 2 us a span with nothing
    recording (PERF.md section 3)."""

    __slots__ = ("name", "cat", "args", "seconds", "t1", "_hist", "_t0")

    def __init__(self, name: str, cat: str = "operation", hist=None,
                 **attrs):
        super().__init__(name, **attrs)
        self.name = name
        self.cat = cat
        self.args = attrs
        self._hist = hist

    def set(self, **attrs):
        """Attributes known only inside the span (call between enter and
        exit)."""
        self.set_metadata(**attrs)
        self.args.update(attrs)

    def __enter__(self):
        super().__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = t1 = time.perf_counter()
        self.seconds = t1 - self._t0
        super().__exit__(*exc)
        if self._hist is not None:
            self._hist.observe(self.seconds)
        if ACTIVE:
            record_span(self.name, self.cat, self._t0, t1, self.args)
        return False


class Task:
    """Reference profiler.Task/Frame domain objects."""

    _cat = "task"

    def __init__(self, domain: Optional[str] = None, name: str = "task"):
        self.name = f"{domain}::{name}" if domain else name
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self):
        if self._t0 is not None and _active() and _START_TS is not None:
            t1 = time.perf_counter()
            _emit(self.name, self._cat, (self._t0 - _START_TS) * 1e6,
                  (t1 - self._t0) * 1e6)
            self._t0 = None


class Frame(Task):
    _cat = "frame"


class Counter:
    """Reference profiler.Counter."""

    def __init__(self, domain: Optional[str] = None, name: str = "counter",
                 value: int = 0):
        self.name = f"{domain}::{name}" if domain else name
        self.value = value

    def set_value(self, value: int):
        self.value = value
        self._record()

    def increment(self, delta: int = 1):
        self.value += delta
        self._record()

    def decrement(self, delta: int = 1):
        self.value -= delta
        self._record()

    def _record(self):
        counter_event(self.name, self.value)


class Marker:
    """Instant event (reference profiler.Marker)."""

    def __init__(self, domain: Optional[str] = None, name: str = "marker"):
        self.name = f"{domain}::{name}" if domain else name

    def mark(self, scope_name: str = "process"):
        if _active() and _START_TS is not None:
            # same pid/tid/cat fields as _emit: viewers lane instant events
            # by (pid, tid) and events without them group badly
            with _LOCK:
                _append_locked({
                    "name": self.name, "cat": "marker", "ph": "i",
                    "ts": max((time.perf_counter() - _START_TS) * 1e6, 0.0),
                    "pid": 0, "tid": threading.get_ident() % 100000,
                    "s": "p",
                })


def counter_event(name: str, value) -> None:
    """Append a chrome-trace 'C' (counter) event if the profiler is ACTIVE.
    Shared entry point for profiler.Counter and the metrics-registry bridge
    (metrics updates show as live curves on the span timeline)."""
    if _active() and _START_TS is not None:
        with _LOCK:
            _append_locked({
                "name": name, "cat": "counter", "ph": "C",
                "ts": max((time.perf_counter() - _START_TS) * 1e6, 0.0),
                "pid": 0, "tid": threading.get_ident() % 100000,
                "args": {"value": value},
            })


def dump(finished: Optional[bool] = None, profile_process: str = "worker"):
    """Write chrome-trace JSON (reference profiler.dump).

    Honors ``finished``/``continuous_dump``: a finished dump flushes —
    events are written once and cleared, so repeated dumps never re-write
    a duplicated, ever-growing buffer. An unfinished dump writes the
    cumulative trace so far and keeps accumulating (periodic-snapshot
    mode, reference profiler.cc continuous_dump). When ``finished`` is
    not given it defaults to ``not continuous_dump``, so plain ``dump()``
    follows the configured mode."""
    if finished is None:
        finished = not _CONFIG["continuous_dump"]
    with _LOCK:
        payload = {"traceEvents": list(_EVENTS), "displayTimeUnit": "ms"}
        if _DROPPED:
            # cumulative process-lifetime count (see dropped_events)
            payload["otherData"] = {"droppedEvents": _DROPPED}
        if finished:
            _EVENTS.clear()
    with open(_CONFIG["filename"], "w") as f:
        json.dump(payload, f)
    return _CONFIG["filename"]


def dumps(reset: bool = False, format: str = "table") -> str:
    """Aggregate stats table (reference profiler.dumps / aggregate_stats.cc)."""
    with _LOCK:
        rows = []
        for name, (n, total, mn, mx) in sorted(_AGG.items()):
            rows.append((name, n, total, mn, mx, total / n))
        if reset:
            _AGG.clear()
    if format == "json":
        return json.dumps([
            {"name": r[0], "count": r[1], "total_us": r[2], "min_us": r[3],
             "max_us": r[4], "avg_us": r[5]} for r in rows])
    lines = [f"{'Name':<40} {'Count':>8} {'Total(us)':>12} {'Min':>10} "
             f"{'Max':>10} {'Avg':>10}"]
    for name, n, total, mn, mx, avg in rows:
        lines.append(f"{name:<40} {n:>8} {total:>12.1f} {mn:>10.1f} "
                     f"{mx:>10.1f} {avg:>10.1f}")
    return "\n".join(lines)


def device_memory_stats(device_id: int = 0) -> Dict[str, int]:
    """HBM stats from PJRT (reference storage_profiler GPU memory profiler)."""
    devs = jax.devices()
    if device_id >= len(devs):
        raise MXNetError(f"no device {device_id}")
    stats = devs[device_id].memory_stats() or {}
    return dict(stats)
