"""mxperf: always-on compile-time cost attribution + live roofline gauges.

The perf arc (ROOFLINE.md, BENCH rounds) was won by hand-built ledgers —
one-off scripts reaching into private ``TrainStep._jitted`` state after
the fact. This module makes the ledger a runtime service, the way
``metrics``/``trace`` made counting and tracing one: every executable
the runtime builds (CachedOp traces, the fused TrainStep single/multi/
ZeRO programs, the serve bucket ladder) deposits its XLA-reported cost
at COMPILE time into one process-wide :class:`CostLedger`, and the live
step-time telemetry turns those static costs into roofline verdicts.

Three layers:

- **Cost ledger** (:func:`capture_build`, :data:`LEDGER`): at every
  executable build site, record ``lowered.cost_analysis()`` (FLOPs, HBM
  bytes accessed — XLA's own numbers, the same source bench.py's MFU
  uses), the decode kernel-launch tally taken at trace time
  (``ops/int8_gemv.count_launches``), and — once a compiled object
  exists — ``compiled.memory_analysis()`` peak bytes. Keyed by the same
  block/bucket labels the metrics registry already uses
  (``train_step``, ``cachedop_<Block>``, ``serve_decode:b<bucket>``).
  Capture happens at compile time ONLY: steady-state calls never touch
  the ledger, so the ``no_recompile()`` guard sees nothing.
- **Gauges**: every entry publishes
  ``mxnet_executable_{flops,hbm_bytes,peak_bytes}{block=key}``; the
  metrics collection callback derives ``mxnet_mfu{path}`` and
  ``mxnet_hbm_util_fraction{path}`` by combining ledger costs with the
  most recent step wall time each hot loop reports via
  :func:`note_step` (TrainStep observation, serve decode/prefill
  ticks). :func:`summary` adds the compute/bandwidth/overhead regime
  classification ROOFLINE.md used to establish by hand.
- **Exports**: :func:`dump` (JSON document — the ``/perf`` view on the
  serving HTTP frontend and the router), :func:`summary` (per-path
  roofline verdicts), and ``tools/mxperf.py`` (offline CLI: top-N
  instructions by HBM bytes via :mod:`~mxnet_tpu.observability.hlo`,
  regime verdicts, ledger JSON).

Capture is gated by :func:`enable` / ``MXNET_PERF`` (the same opt-in
pattern as ``trace``): capturing one entry re-traces the executable's
function to reach the lowered stage, which roughly doubles a cold
serve-ladder warmup — affordable for bench rounds, serving replicas
and the perf CI check (which all enable it), not a tax every
metrics-enabled unit test should pay. The disabled fast path is one
module-bool check per BUILD — and builds are rare by definition — so
an idle ledger costs zero on every hot path. ``bench.py``,
``tools/serve_loadgen.py`` and ``tools/serve_router.py`` enable it
alongside metrics, which is what makes attribution *always on* where
it matters: every perf round and every serving replica.

Cost model caveat (same as bench.py): XLA's cost analysis cannot see
inside Pallas custom calls, so FLOPs of fused-kernel paths (flash
attention, fused decode) are under-counted there; the launch tally
records that those kernels exist, and bench.py keeps the analytic
convention for headline MFU. Peak FLOP/s and HBM GB/s default to the
v5e numbers off-TPU so CPU CI exercises the same arithmetic bench.py
reports.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence

from ..base import MXNetError, get_env

__all__ = [
    "CostLedger", "LEDGER", "enable", "disable", "active", "reset",
    "capture_build", "note_step", "complete_all", "dump", "summary",
    "refresh_gauges", "chip_peak_flops", "chip_hbm_bandwidth",
    "classify_regime",
]

# explicit capture switch (enable() / MXNET_PERF); one module-bool read
# is the whole disabled-path cost
ENABLED = False

# bf16 MXU peak FLOP/s and nominal HBM bytes/s, keyed by the
# ``device_kind`` JAX reports (Google Cloud TPU documentation, system
# architecture pages) — the ONE definition (bench.py's _chip_peak
# delegates here) so the offline MFU and the live gauge can never
# disagree on the denominator. A device that is not here is an error,
# not a default: a roofline against another chip's peak is no number.
PEAKS = {
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}


_CHIP_GEN: Optional[str] = None


def _device_kind() -> str:
    """``device_kind`` of the first device. Memoized — it cannot change
    within a process, and the first detection touches jax.devices(),
    which must not run per step on the note path (or at all in processes
    like the router that never create a PJRT client — see LEDGER guards
    there)."""
    global _CHIP_GEN
    if _CHIP_GEN is None:
        import jax
        _CHIP_GEN = jax.devices()[0].device_kind
    return _CHIP_GEN


def _chip_gen() -> str:
    """The attached chip's key into :data:`PEAKS`; raises for a device
    kind with no entry (the CPU included)."""
    kind = _device_kind()
    if kind not in PEAKS:
        raise MXNetError(
            f"no peak FLOP/s or HBM bandwidth on record for device kind "
            f"{kind!r}: MFU and roofline numbers exist only for "
            f"{sorted(PEAKS)}")
    return kind


def chip_peak_flops() -> float:
    """Peak bf16 FLOP/s of the attached chip (the MFU denominator)."""
    return PEAKS[_chip_gen()][0]


def chip_hbm_bandwidth() -> float:
    """Nominal HBM bytes/s of the attached chip (the bandwidth-util
    denominator)."""
    return PEAKS[_chip_gen()][1]


def classify_regime(flops: float, hbm_bytes: float, dt: float,
                    peak: Optional[float] = None,
                    bw: Optional[float] = None) -> str:
    """Compute/bandwidth/overhead verdict for one executable at one
    measured wall time — the ROOFLINE.md methodology as a function:
    compare ``dt`` against the MXU-time and HBM-time lower bounds; if
    the binding (larger) floor explains >= 50% of the measured time the
    regime is that floor's name, otherwise the step is dominated by
    work neither floor models (launch overhead, dispatch, unfused
    glue) — ``overhead``, the regime PR 6 collapsed for decode."""
    peak = chip_peak_flops() if peak is None else peak
    bw = chip_hbm_bandwidth() if bw is None else bw
    t_c = flops / peak if peak > 0 else 0.0
    t_b = hbm_bytes / bw if bw > 0 else 0.0
    floor = max(t_c, t_b)
    if dt <= 0 or floor <= 0:
        return "unknown"
    if floor / dt >= 0.5:
        return "compute" if t_c >= t_b else "bandwidth"
    return "overhead"


class CostEntry:
    """Compile-time cost record of one executable."""

    __slots__ = ("key", "label", "flops", "hbm_bytes", "transcendentals",
                 "peak_bytes", "memory", "launches", "meta", "t_captured",
                 "_jitted", "_example_args")

    def __init__(self, key: str, label: str):
        self.key = key
        self.label = label
        self.flops = 0.0
        self.hbm_bytes = 0.0
        self.transcendentals = 0.0
        self.peak_bytes = 0.0
        self.memory: Dict[str, float] = {}
        self.launches: Dict[str, int] = {}
        self.meta: Dict[str, Any] = {}
        self.t_captured = 0.0
        # kept so complete() can compile for memory_analysis on demand;
        # the build-site caches hold the same objects alive anyway
        self._jitted = None
        self._example_args: Optional[Sequence] = None

    def to_dict(self) -> Dict[str, Any]:
        return {
            "key": self.key, "label": self.label,
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "transcendentals": self.transcendentals,
            "peak_bytes": self.peak_bytes,
            "memory": dict(self.memory),
            "launches": dict(self.launches),
            "meta": dict(self.meta),
            "t_captured": self.t_captured,
        }


def _cost_dict(obj) -> Dict[str, float]:
    """Flatten jax's cost_analysis return (dict, or list/tuple of one)
    into a plain dict; {} on any failure — the ledger degrades, never
    raises into a build."""
    try:
        ca = obj.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    return dict(ca) if ca else {}


def _memory_dict(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
        out[field] = float(getattr(ma, field, 0) or 0)
    return out


def _abstractify(args):
    """Shape/dtype/sharding skeleton of an example-args tree: entries
    must not pin batch/param device buffers while they wait for an
    on-demand complete() (lowering accepts ShapeDtypeStructs)."""
    import jax

    def leaf(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=getattr(a, "sharding",
                                                         None))
        return a

    return jax.tree.map(leaf, args)


def _peak_bytes(memory: Dict[str, float]) -> float:
    """Peak device bytes one execution holds at once: arguments +
    outputs + XLA temp scratch, minus donated/aliased buffers (counted
    once, not twice)."""
    if not memory:
        return 0.0
    return (memory.get("argument_size_in_bytes", 0.0)
            + memory.get("output_size_in_bytes", 0.0)
            + memory.get("temp_size_in_bytes", 0.0)
            - memory.get("alias_size_in_bytes", 0.0))


class CostLedger:
    """Bounded, process-wide map of executable key -> :class:`CostEntry`.

    Writes happen at executable-build time only; reads (gauges, dumps,
    the ``/perf`` views) are lock-snapshot cheap. Overflow evicts the
    oldest entry — a serving process that churns signatures keeps the
    recent ladder, which is the one being executed."""

    def __init__(self, max_entries: int = 256):
        self.max_entries = int(max_entries)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, CostEntry]" = OrderedDict()
        self._notes: Dict[str, Dict[str, Any]] = {}
        self._evicted = 0

    # ------------------------------------------------------------ record
    def record(self, label: str, *, lowered=None, compiled=None,
               jitted=None, example_args: Optional[Sequence] = None,
               launches: Optional[Dict[str, int]] = None,
               key: Optional[str] = None,
               meta: Optional[Dict[str, Any]] = None) -> Optional[CostEntry]:
        """Deposit one executable's compile-time costs. Never raises:
        a cost-analysis failure records an empty entry rather than
        failing the build that called us."""
        key = key or label
        entry = CostEntry(key, label)
        entry.t_captured = time.time()
        if meta:
            entry.meta.update(meta)
        if launches:
            entry.launches = {k: int(v) for k, v in launches.items()}
        ca = _cost_dict(compiled) if compiled is not None else {}
        if not ca and lowered is not None:
            # deserialized AOT executables can refuse cost_analysis —
            # the lowered stage still reports the same program's costs
            ca = _cost_dict(lowered)
        entry.flops = float(ca.get("flops", 0.0) or 0.0)
        entry.hbm_bytes = float(ca.get("bytes accessed", 0.0) or 0.0)
        entry.transcendentals = float(ca.get("transcendentals", 0.0) or 0.0)
        if compiled is not None:
            entry.memory = _memory_dict(compiled)
            entry.peak_bytes = _peak_bytes(entry.memory)
        elif jitted is not None and example_args is not None:
            entry._jitted = jitted
            try:
                entry._example_args = _abstractify(example_args)
            except Exception:
                entry._example_args = None
        with self._lock:
            self._entries.pop(key, None)
            self._entries[key] = entry
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                self._evicted += 1
        _publish_entry(entry)
        return entry

    def note_step(self, path: str, dt: float, *, key: Optional[str] = None,
                  work: float = 1.0):
        """Record the most recent wall time of one executed step on
        ``path`` (and which ledger ``key`` it ran, for bucketed paths).
        This is the live half of the roofline: MFU/bandwidth gauges
        divide the keyed entry's static cost by this dt. The gauges for
        THIS path refresh here too (one entry lookup + float math), so
        a reader never sees a stale/unset mfu between collections."""
        with self._lock:
            self._notes[path] = {"dt": float(dt), "key": key or path,
                                 "work": float(work), "t": time.time()}
        entry = self.get(key or path)
        # no roofline gauge for a device without peaks on record (the
        # CPU): the note and the ledger entry stay, the MFU does not exist
        if entry is not None and dt > 0 and _device_kind() in PEAKS:
            _publish_roofline(path,
                              entry.flops * work / dt / chip_peak_flops(),
                              entry.hbm_bytes * work / dt
                              / chip_hbm_bandwidth())

    # ---------------------------------------------------------- complete
    def complete(self, key: str) -> Optional[CostEntry]:
        """Fill memory/peak stats for one entry by compiling its stored
        (jitted, example_args) pair. On-demand only (mxperf CLI, the
        perf CI check, full dumps): compiling costs real time, so the
        build-site capture never does it eagerly."""
        with self._lock:
            entry = self._entries.get(key)
        if entry is None or entry.memory:
            return entry
        jitted, args = entry._jitted, entry._example_args
        if jitted is None or args is None:
            return entry
        try:
            compiled = jitted.lower(*args).compile()
        except Exception:
            return entry
        entry.memory = _memory_dict(compiled)
        entry.peak_bytes = _peak_bytes(entry.memory)
        ca = _cost_dict(compiled)
        if ca.get("flops"):
            entry.flops = float(ca["flops"])
        if ca.get("bytes accessed"):
            entry.hbm_bytes = float(ca["bytes accessed"])
        entry._jitted = None
        entry._example_args = None
        _publish_entry(entry)
        return entry

    def complete_all(self):
        with self._lock:
            keys = list(self._entries)
        for k in keys:
            self.complete(k)

    # ------------------------------------------------------------- reads
    def entries(self) -> List[CostEntry]:
        with self._lock:
            return list(self._entries.values())

    def get(self, key: str) -> Optional[CostEntry]:
        with self._lock:
            return self._entries.get(key)

    def notes(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {k: dict(v) for k, v in self._notes.items()}

    def summary(self) -> Dict[str, Dict[str, Any]]:
        """Per-path roofline verdicts: for every path that has reported
        a live step time, combine it with the keyed entry's static cost
        into MFU, HBM bandwidth utilization, the floor times, and the
        regime classification."""
        notes = self.notes()
        out: Dict[str, Dict[str, Any]] = {}
        if not notes:
            # nothing ran: return before chip detection so an idle
            # process (the router) never touches jax.devices()
            return out
        if _device_kind() not in PEAKS:
            return out      # no peaks on record: no roofline to report
        peak = chip_peak_flops()
        bw = chip_hbm_bandwidth()
        for path, note in notes.items():
            entry = self.get(note["key"])
            if entry is None:
                continue
            dt = note["dt"]
            flops = entry.flops * note["work"]
            hbm = entry.hbm_bytes * note["work"]
            mfu = flops / dt / peak if dt > 0 else 0.0
            hbm_util = hbm / dt / bw if dt > 0 else 0.0
            out[path] = {
                "key": entry.key,
                "dt_s": dt,
                "flops": flops,
                "hbm_bytes": hbm,
                # 10 digits: a CPU-CI toy step's MFU (~1e-7 of a v5e
                # peak) must not round to a dead-zero gauge
                "mfu": round(mfu, 10),
                "hbm_util_fraction": round(hbm_util, 10),
                "mxu_floor_s": flops / peak,
                "hbm_floor_s": hbm / bw,
                "regime": classify_regime(flops, hbm, dt, peak, bw),
                "launches": dict(entry.launches),
            }
        return out

    def dump(self) -> Dict[str, Any]:
        """The machine-readable ledger document (the ``/perf`` payload
        and the mxperf CLI JSON)."""
        peak, bw = PEAKS.get(_device_kind(), (None, None))
        return {
            "chip": _device_kind(),
            "peak_flops": peak,
            "hbm_bandwidth": bw,
            "entries": [e.to_dict() for e in self.entries()],
            "roofline": self.summary(),
            "evicted": self._evicted,
        }

    def reset(self):
        with self._lock:
            self._entries.clear()
            self._notes.clear()
            self._evicted = 0


LEDGER = CostLedger()


def enable():
    """Turn ledger capture on (build sites start depositing costs)."""
    global ENABLED
    ENABLED = True


def disable():
    global ENABLED
    ENABLED = False


def active() -> bool:
    """Capture is live (build sites consult this once per build)."""
    return ENABLED


def reset():
    LEDGER.reset()


# ---------------------------------------------------------------------------
# build-site integration
# ---------------------------------------------------------------------------

def capture_build(label: str, jitted=None, example_args=None, *,
                  lowered=None, compiled=None,
                  launches: Optional[Dict[str, int]] = None,
                  key: Optional[str] = None,
                  meta: Optional[Dict[str, Any]] = None):
    """The one call every executable build site makes. No-op while
    capture is inactive; otherwise lowers ``jitted`` at ``example_args``
    (under the decode-launch tally, so launch sites recorded at trace
    time land in the entry) unless the caller already holds a lowered/
    compiled stage. Swallows every failure — attribution must never
    break a build."""
    if not active():
        return None
    try:
        if lowered is None and compiled is None and jitted is not None:
            from ..ops import int8_gemv as _gemv
            with _gemv.count_launches() as tally:
                lowered = jitted.lower(*example_args)
            if launches is None and tally:
                launches = dict(tally)
        return LEDGER.record(label, lowered=lowered, compiled=compiled,
                             jitted=jitted, example_args=example_args,
                             launches=launches, key=key, meta=meta)
    except Exception:
        return None


def note_step(path: str, dt: float, *, key: Optional[str] = None,
              work: float = 1.0):
    """Hot-loop step-time note (gate on metrics.ENABLED at the call
    site; this is two dict writes under a lock)."""
    LEDGER.note_step(path, dt, key=key, work=work)


def complete_all():
    LEDGER.complete_all()


def dump() -> Dict[str, Any]:
    return LEDGER.dump()


def summary() -> Dict[str, Dict[str, Any]]:
    return LEDGER.summary()


# ---------------------------------------------------------------------------
# gauge publication (metrics registry integration)
# ---------------------------------------------------------------------------

def _publish_entry(entry: CostEntry):
    """Set the per-executable gauges for one entry. Uses the direct
    child write (collection-callback semantics): the ledger is already
    gated by active(), and the gauges must reflect the ledger even when
    capture was forced on with the registry disabled."""
    try:
        from .. import metrics as _metrics
        _metrics.EXEC_FLOPS._child((entry.key,))._set_direct(entry.flops)
        _metrics.EXEC_HBM_BYTES._child((entry.key,))._set_direct(
            entry.hbm_bytes)
        _metrics.EXEC_PEAK_BYTES._child((entry.key,))._set_direct(
            entry.peak_bytes)
    except Exception:
        pass


def _publish_roofline(path: str, mfu: float, hbm_util: float):
    try:
        from .. import metrics as _metrics
        _metrics.MFU._child((path,))._set_direct(mfu)
        _metrics.HBM_UTIL._child((path,))._set_direct(hbm_util)
    except Exception:
        pass


def refresh_gauges():
    """Derive the live roofline gauges from the ledger + step notes —
    runs at every metrics collection (expose/dumps), so a scrape always
    reads a current MFU (entries recorded AFTER their path's last note
    land here)."""
    for path, roof in LEDGER.summary().items():
        _publish_roofline(path, roof["mfu"], roof["hbm_util_fraction"])


if get_env("MXNET_PERF", False, dtype=bool,
           doc="enable cost-ledger capture at import"):
    enable()
