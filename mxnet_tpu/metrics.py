"""Runtime telemetry: process-wide metrics registry + exposition.

The observability substrate the runtime reports through (the role the
TensorFlow system paper gives its built-in runtime tracing/metrics: every
placement/scheduling decision needs numbers). Three instrument kinds —
:class:`Counter`, :class:`Gauge`, :class:`Histogram` — with Prometheus-style
label support, collected in one process-wide :class:`MetricsRegistry` and
exposed three ways:

- ``expose()``       -> Prometheus text exposition format (scrapeable)
- ``dumps("json")``  -> machine-readable JSON (bench.py / CI regression)
- live 'C' counter events bridged into the chrome trace while the profiler
  is ACTIVE (one timeline for spans AND metric evolution)

Collection is OFF by default (``MXNET_METRICS`` env var or ``enable()``).
The disabled fast path is a single module-attribute bool check — no lock is
taken and no label child is allocated, so instrumented hot paths
(``_tape.invoke``, ``CachedOp.__call__``, ``TrainStep``, ``DataLoader``)
stay near-free when telemetry is idle.

Wired-in instruments (the metrics catalog; see README "Observability"):

- ``mxnet_op_dispatch_total{op}`` / ``mxnet_op_dispatch_seconds`` —
  eager op dispatches through the ``_tape.invoke`` funnel
- ``mxnet_cachedop_cache_hits_total{block}`` /
  ``mxnet_recompilations_total{block,kind}`` — trace-cache hits vs.
  (re)compilations in CachedOp and TrainStep; every ``kind="retrace"`` also
  warn-logs the shape/dtype signature that caused it
- ``mxnet_step_time_seconds{path}`` / ``mxnet_examples_total{path}`` /
  ``mxnet_examples_per_sec{path}`` — train-step latency + throughput
  (``path`` ∈ trainer | train_step | train_step_multi)
- ``mxnet_dataloader_batch_seconds`` / ``mxnet_dataloader_wait_seconds`` /
  ``mxnet_dataloader_batches_total`` — batch assembly latency and
  consumer-side queue wait
- ``mxnet_collective_calls_total{op}`` / ``mxnet_collective_bytes_total{op}``
  — collectives staged at trace time (parallel.collectives) and executed by
  the kvstore comm engine
- ``mxnet_kvstore_calls_total{api}`` / ``mxnet_kvstore_bytes_total{api}``
- ``mxnet_hbm_bytes_in_use{device}`` / ``mxnet_hbm_peak_bytes{device}`` —
  PJRT ``memory_stats()`` sampled at collection time; peak is a
  high-watermark (monotone max)
- ``mxnet_profiler_dropped_events_total`` — spans dropped by the profiler
  event cap
- ``mxnet_aot_cache_{hits,misses,errors,evictions}_total`` /
  ``mxnet_aot_cache_bytes`` / ``mxnet_aot_{load,compile}_seconds`` /
  ``mxnet_aot_warmup_seconds{path}`` — the persistent AOT compile cache
  (mxnet_tpu/aot): disk hits replace XLA compiles on warm starts
- ``mxnet_executable_{flops,hbm_bytes,peak_bytes}{block}`` /
  ``mxnet_mfu{path}`` / ``mxnet_hbm_util_fraction{path}`` — the
  compile-time cost ledger (observability/perf): XLA cost/memory
  analysis per executable, and the live roofline derived from it plus
  the most recent step wall times
- ``mxnet_input_wait_seconds{path}`` / ``mxnet_pipeline_depth{path}`` /
  ``mxnet_checkpoint_stall_seconds`` / ``mxnet_serve_host_sync_seconds``
  — the async execution pipeline (mxnet_tpu/pipeline, TrainStep in-flight
  window, async CheckpointManager saves, serve decode lookahead): each
  family proves one host↔device overlap is real
- ``mxnet_health_*`` — on-device numeric health telemetry
  (observability/health): per-step nonfinite counts + global norms off
  the fused step's health vector, the z-score detector state, anomaly/
  skipped-step counters and the sampled per-layer-group stats
- ``mxnet_amp_scale`` / ``mxnet_amp_skipped_steps_total`` /
  ``mxnet_amp_scale_adjustments_total{direction}`` — the dynamic AMP
  loss scaler (amp/loss_scaler)
"""
from __future__ import annotations

import bisect
import json
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax.monitoring

from . import profiler as _profiler
from .analysis import guards as _guards
from .base import MXNetError, get_env

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "enable", "disable", "enabled", "reset", "expose", "dumps",
    "get_sample_value", "register_collect_callback", "record_io",
    "backend_compiles",
]

# fast-path flag consulted by runtime hot paths; True only after enable().
# Reading one module attribute is the whole disabled-path cost.
ENABLED = False

DEFAULT_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)


class _Noop:
    """Shared do-nothing child returned by ``labels()`` while disabled:
    keeps instrumented call sites allocation- and lock-free when idle."""

    __slots__ = ()

    def inc(self, amount=1.0):
        pass

    def dec(self, amount=1.0):
        pass

    def set(self, value):
        pass

    def observe(self, value):
        pass


_NOOP = _Noop()


def _label_str(labelnames: Sequence[str], labelvalues: Sequence[str]) -> str:
    if not labelnames:
        return ""
    return "{" + ",".join(f'{k}="{_escape(v)}"'
                          for k, v in zip(labelnames, labelvalues)) + "}"


def _escape(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


class _CounterChild:
    __slots__ = ("_family", "_labelvalues", "_lock", "_value", "_trace_name")

    def __init__(self, family, labelvalues):
        self._family = family
        self._labelvalues = labelvalues
        self._lock = threading.Lock()
        self._value = 0.0
        # precomputed: the chrome-trace bridge must cost nothing beyond the
        # ACTIVE check on the per-op enabled path
        self._trace_name = family.name + _label_str(family.labelnames,
                                                    labelvalues)

    @property
    def value(self) -> float:
        return self._value

    def inc(self, amount: float = 1.0):
        if not ENABLED:
            return
        if amount < 0:
            raise MXNetError(f"counter {self._family.name}: inc by {amount} < 0")
        with self._lock:
            self._value += amount
            v = self._value
        if _profiler.ACTIVE:
            _profiler.counter_event(self._trace_name, v)

    def _set_direct(self, value: float):
        """Collection-callback path: write an externally-sourced monotone
        value, bypassing the ENABLED gate (collection is explicit)."""
        with self._lock:
            self._value = float(value)


class _GaugeChild:
    __slots__ = ("_family", "_labelvalues", "_lock", "_value", "_trace_name")

    def __init__(self, family, labelvalues):
        self._family = family
        self._labelvalues = labelvalues
        self._lock = threading.Lock()
        self._value = 0.0
        self._trace_name = family.name + _label_str(family.labelnames,
                                                    labelvalues)

    @property
    def value(self) -> float:
        return self._value

    def set(self, value: float):
        if not ENABLED:
            return
        with self._lock:
            self._value = float(value)
        if _profiler.ACTIVE:
            _profiler.counter_event(self._trace_name, float(value))

    def inc(self, amount: float = 1.0):
        if not ENABLED:
            return
        with self._lock:
            self._value += amount

    def dec(self, amount: float = 1.0):
        self.inc(-amount)

    def _set_direct(self, value: float):
        with self._lock:
            self._value = float(value)


class _HistogramChild:
    __slots__ = ("_family", "_labelvalues", "_lock", "_counts", "_sum",
                 "_count")

    def __init__(self, family, labelvalues):
        self._family = family
        self._labelvalues = labelvalues
        self._lock = threading.Lock()
        self._counts = [0] * (len(family.buckets) + 1)  # last = +Inf
        self._sum = 0.0
        self._count = 0

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def observe(self, value: float):
        if not ENABLED:
            return
        i = bisect.bisect_left(self._family.buckets, value)
        with self._lock:
            self._counts[i] += 1
            self._sum += value
            self._count += 1

    def snapshot(self):
        """(cumulative bucket counts incl. +Inf, sum, count) — consistent
        under the child lock."""
        with self._lock:
            counts = list(self._counts)
            s, c = self._sum, self._count
        cum, acc = [], 0
        for n in counts:
            acc += n
            cum.append(acc)
        return cum, s, c


class _MetricFamily:
    """One named metric; holds label children (or a single unlabeled child,
    created eagerly so the enabled path never allocates either).

    Constructing a family whose name is already registered (same type and
    labels) returns THE REGISTERED INSTANCE — a re-executed notebook cell
    gets the live metric back instead of a silent orphan whose updates
    never reach expose()."""

    typ = "untyped"
    _child_cls: type = _CounterChild

    def __new__(cls, name: str, help: str = "", labels: Sequence[str] = (),
                registry: Optional["MetricsRegistry"] = None, **kwargs):
        reg = registry if registry is not None else REGISTRY
        existing = reg.get(name)
        if existing is not None:
            if (type(existing) is not cls
                    or existing.labelnames != tuple(labels)):
                raise MXNetError(
                    f"metric {name} already registered with a different "
                    "type/label set")
            return existing
        return super().__new__(cls)

    def __init__(self, name: str, help: str = "", labels: Sequence[str] = (),
                 registry: Optional["MetricsRegistry"] = None, **kwargs):
        if getattr(self, "_initialized", False):
            return  # deduplicated: __new__ returned the live instance
        self._initialized = True
        self.name = name
        self.help = help
        self.labelnames = tuple(labels)
        # witnessed under MXNET_DEBUG_GUARDS (family locks nest inside the
        # registry lock during collection); child locks stay plain — they
        # are leaf locks on the per-op hot path
        self._lock = _guards.make_lock("metrics._MetricFamily._lock")
        self._children: "OrderedDict[Tuple[str, ...], Any]" = OrderedDict()
        self._unlabeled = None
        if not self.labelnames:
            self._unlabeled = self._make_child(())
            self._children[()] = self._unlabeled
        if registry is None:
            registry = REGISTRY
        registry.register(self)

    def _make_child(self, labelvalues):
        return self._child_cls(self, labelvalues)

    def _child(self, labelvalues: Tuple[str, ...]):
        """Always-create child lookup (collection callbacks and the enabled
        ``labels()`` path)."""
        child = self._children.get(labelvalues)
        if child is None:
            with self._lock:
                child = self._children.get(labelvalues)
                if child is None:
                    child = self._make_child(labelvalues)
                    self._children[labelvalues] = child
        return child

    def labels(self, **kv):
        if not ENABLED:
            return _NOOP
        try:
            key = tuple(str(kv[k]) for k in self.labelnames)
        except KeyError as e:
            raise MXNetError(
                f"metric {self.name}: missing label {e.args[0]!r} "
                f"(declared: {list(self.labelnames)})")
        if len(kv) != len(self.labelnames):
            extra = set(kv) - set(self.labelnames)
            raise MXNetError(f"metric {self.name}: unknown labels {sorted(extra)}")
        return self._child(key)

    def children(self) -> List[Tuple[Tuple[str, ...], Any]]:
        with self._lock:
            return list(self._children.items())

    def reset(self):
        with self._lock:
            if self.labelnames:
                self._children.clear()
            else:
                self._unlabeled = self._make_child(())
                self._children[()] = self._unlabeled

    # unlabeled conveniences: forward to the single child
    def _only(self):
        if self.labelnames:
            raise MXNetError(
                f"metric {self.name} has labels {list(self.labelnames)}; "
                "use .labels(...)")
        return self._unlabeled


class Counter(_MetricFamily):
    """Monotonically-increasing count (Prometheus counter)."""

    typ = "counter"
    _child_cls = _CounterChild

    def inc(self, amount: float = 1.0):
        if not ENABLED:
            return
        self._only().inc(amount)


class Gauge(_MetricFamily):
    """Point-in-time value that can go up and down (Prometheus gauge)."""

    typ = "gauge"
    _child_cls = _GaugeChild

    def set(self, value: float):
        if not ENABLED:
            return
        self._only().set(value)

    def inc(self, amount: float = 1.0):
        if not ENABLED:
            return
        self._only().inc(amount)

    def dec(self, amount: float = 1.0):
        if not ENABLED:
            return
        self._only().dec(amount)


class Histogram(_MetricFamily):
    """Cumulative-bucket distribution (Prometheus histogram)."""

    typ = "histogram"
    _child_cls = _HistogramChild

    def __init__(self, name, help="", labels=(), registry=None,
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        new_buckets = tuple(sorted(float(b) for b in buckets))
        if getattr(self, "_initialized", False):
            # deduplicated: different boundaries cannot merge into the
            # live children — fail loudly like a type/label mismatch does
            if new_buckets != self.buckets:
                raise MXNetError(
                    f"histogram {name} already registered with buckets "
                    f"{self.buckets}; cannot re-register with {new_buckets}")
            return
        self.buckets = new_buckets
        super().__init__(name, help, labels, registry)

    def observe(self, value: float):
        if not ENABLED:
            return
        self._only().observe(value)


class MetricsRegistry:
    """Process-wide named-metric registry with pluggable collection
    callbacks (sampled sources like PJRT memory stats)."""

    def __init__(self):
        self._lock = _guards.make_lock("metrics.MetricsRegistry._lock")
        self._metrics: "OrderedDict[str, _MetricFamily]" = OrderedDict()
        self._callbacks: List[Callable[[], None]] = []

    def register(self, metric: _MetricFamily) -> _MetricFamily:
        with self._lock:
            existing = self._metrics.get(metric.name)
            if existing is not None:
                if (type(existing) is not type(metric)
                        or existing.labelnames != metric.labelnames):
                    raise MXNetError(
                        f"metric {metric.name} already registered with a "
                        "different type/label set")
                return existing
            self._metrics[metric.name] = metric
            return metric

    def get(self, name: str) -> Optional[_MetricFamily]:
        with self._lock:
            return self._metrics.get(name)

    def register_callback(self, fn: Callable[[], None]):
        """``fn()`` runs at every collection (expose/dumps) to refresh
        sampled metrics; exceptions are swallowed (telemetry never takes
        the workload down)."""
        with self._lock:
            self._callbacks.append(fn)
        return fn

    def _run_callbacks(self):
        with self._lock:
            cbs = list(self._callbacks)
        for fn in cbs:
            try:
                fn()
            except Exception:
                pass

    def families(self) -> List[_MetricFamily]:
        self._run_callbacks()
        with self._lock:
            return list(self._metrics.values())

    def reset(self):
        with self._lock:
            fams = list(self._metrics.values())
        for f in fams:
            f.reset()

    # ------------------------------------------------------------ exposition
    def expose(self) -> str:
        """Prometheus text exposition format (version 0.0.4)."""
        lines: List[str] = []
        for fam in self.families():
            if fam.help:
                lines.append(f"# HELP {fam.name} {_escape(fam.help)}")
            lines.append(f"# TYPE {fam.name} {fam.typ}")
            for labelvalues, child in fam.children():
                ls = _label_str(fam.labelnames, labelvalues)
                if fam.typ == "histogram":
                    cum, s, c = child.snapshot()
                    for bound, n in zip(list(fam.buckets) + ["+Inf"], cum):
                        le = bound if bound == "+Inf" else repr(float(bound))
                        blabels = list(zip(fam.labelnames, labelvalues)) + \
                            [("le", str(le))]
                        bl = "{" + ",".join(
                            f'{k}="{_escape(v)}"' for k, v in blabels) + "}"
                        lines.append(f"{fam.name}_bucket{bl} {n}")
                    lines.append(f"{fam.name}_sum{ls} {_fmt(s)}")
                    lines.append(f"{fam.name}_count{ls} {c}")
                else:
                    lines.append(f"{fam.name}{ls} {_fmt(child.value)}")
        return "\n".join(lines) + "\n"

    def dumps(self, format: str = "json") -> str:
        """Machine-readable dump: ``format='json'`` (bench/CI) or a human
        ``'table'``."""
        if format == "json":
            doc: Dict[str, Any] = {}
            for fam in self.families():
                samples = []
                for labelvalues, child in fam.children():
                    labels = dict(zip(fam.labelnames, labelvalues))
                    if fam.typ == "histogram":
                        cum, s, c = child.snapshot()
                        samples.append({
                            "labels": labels, "count": c, "sum": s,
                            "buckets": {str(b): n for b, n in zip(
                                list(fam.buckets) + ["+Inf"], cum)},
                        })
                    else:
                        samples.append({"labels": labels,
                                        "value": child.value})
                doc[fam.name] = {"type": fam.typ, "help": fam.help,
                                 "samples": samples}
            return json.dumps(doc)
        if format == "table":
            rows = []
            for fam in self.families():
                for labelvalues, child in fam.children():
                    ls = _label_str(fam.labelnames, labelvalues)
                    if fam.typ == "histogram":
                        _, s, c = child.snapshot()
                        val = f"count={c} sum={_fmt(s)}"
                    else:
                        val = _fmt(child.value)
                    rows.append((fam.name + ls, fam.typ, val))
            w = max([len(r[0]) for r in rows], default=20)
            lines = [f"{'Metric':<{w}}  {'Type':<9}  Value"]
            lines += [f"{n:<{w}}  {t:<9}  {v}" for n, t, v in rows]
            return "\n".join(lines)
        raise MXNetError(f"metrics.dumps: unknown format {format!r}")

    def get_sample_value(self, name: str,
                         labels: Optional[Dict[str, str]] = None):
        """Read one sample by exposition name (histograms via ``_count`` /
        ``_sum`` suffixes). ``labels=None`` sums over all children — handy
        for 'total across ops' assertions. Returns None if absent."""
        base, field = name, "value"
        fam = self.get(name)
        if fam is None:
            for suffix in ("_count", "_sum"):
                if name.endswith(suffix):
                    fam = self.get(name[:-len(suffix)])
                    if fam is not None:
                        base, field = name[:-len(suffix)], suffix[1:]
                    break
        if fam is None:
            return None
        total, hit = 0.0, False
        for labelvalues, child in fam.children():
            if labels is not None:
                child_labels = dict(zip(fam.labelnames, labelvalues))
                if any(child_labels.get(k) != str(v)
                       for k, v in labels.items()):
                    continue
            hit = True
            if fam.typ == "histogram":
                _, s, c = child.snapshot()
                total += c if field == "count" else s
            else:
                total += child.value
        return total if hit else None


def _fmt(v: float) -> str:
    # Prometheus text format supports non-finite samples; int(v) on them
    # would raise and take the whole scrape down
    if v != v:
        return "NaN"
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


REGISTRY = MetricsRegistry()


def enable():
    """Turn collection on (hot paths start recording)."""
    global ENABLED
    ENABLED = True


def disable():
    global ENABLED
    ENABLED = False


def enabled() -> bool:
    return ENABLED


def reset():
    """Zero every metric (keep registrations); test/CI isolation."""
    REGISTRY.reset()


def expose() -> str:
    return REGISTRY.expose()


def dumps(format: str = "json") -> str:
    return REGISTRY.dumps(format)


def get_sample_value(name: str, labels: Optional[Dict[str, str]] = None):
    return REGISTRY.get_sample_value(name, labels)


def register_collect_callback(fn: Callable[[], None]):
    return REGISTRY.register_callback(fn)


def record_io(calls: "Counter", bytes_counter: "Counter", nbytes: float,
              **labels):
    """Shared call+payload-bytes update for the I/O-shaped instrument
    pairs (collective and kvstore telemetry): one place owns the
    'count the call, count the bytes if any' semantics. Callers compute
    ``nbytes`` from their own array flavor (traced avals, jax arrays,
    NDArrays) and should gate on ENABLED before doing that work."""
    if not ENABLED:
        return
    calls.labels(**labels).inc()
    if nbytes:
        bytes_counter.labels(**labels).inc(nbytes)


# ---------------------------------------------------------------------------
# The wired-in instrument catalog (one definition site; runtime modules
# import these attributes — see module docstring for semantics)
# ---------------------------------------------------------------------------

OP_DISPATCH = Counter(
    "mxnet_op_dispatch_total",
    "Eager op dispatches through the _tape.invoke funnel", labels=("op",))
OP_LATENCY = Histogram(
    "mxnet_op_dispatch_seconds",
    "Host-side dispatch latency of eager ops (includes any sync wait)")
CACHE_HITS = Counter(
    "mxnet_cachedop_cache_hits_total",
    "CachedOp trace-cache hits (no recompilation)", labels=("block",))
RECOMPILATIONS = Counter(
    "mxnet_recompilations_total",
    "XLA trace builds: kind=initial first trace, kind=retrace a new "
    "shape/dtype/mode signature forced recompilation (also warn-logged)",
    labels=("block", "kind"))
STEP_TIME = Histogram(
    "mxnet_step_time_seconds",
    "Train-step wall time per call (host-side; async dispatch). "
    "path=train_step/train_step_multi cover the full fused step; "
    "path=trainer covers ONLY allreduce+update (fwd/bwd run outside "
    "Trainer.step)", labels=("path",))
EXAMPLES = Counter(
    "mxnet_examples_total", "Examples processed by train steps",
    labels=("path",))
EXAMPLES_PER_SEC = Gauge(
    "mxnet_examples_per_sec",
    "Throughput of the most recent FUSED train step (TrainStep paths "
    "only: Trainer.step excludes fwd/bwd, so no gauge there)",
    labels=("path",))
DATA_BATCH_LATENCY = Histogram(
    "mxnet_dataloader_batch_seconds",
    "DataLoader batch assembly latency (sample fetch + batchify)")
DATA_QUEUE_WAIT = Histogram(
    "mxnet_dataloader_wait_seconds",
    "Consumer-side wait for the next prefetched batch (queue-wait)")
DATA_BATCHES = Counter(
    "mxnet_dataloader_batches_total", "Batches produced by DataLoader")
COLLECTIVE_CALLS = Counter(
    "mxnet_collective_calls_total",
    "Collective ops staged (trace time) or executed (kvstore comm)",
    labels=("op",))
COLLECTIVE_BYTES = Counter(
    "mxnet_collective_bytes_total",
    "Payload bytes of collective ops (per-process local stripe)",
    labels=("op",))
KVSTORE_CALLS = Counter(
    "mxnet_kvstore_calls_total", "KVStore API calls", labels=("api",))
KVSTORE_BYTES = Counter(
    "mxnet_kvstore_bytes_total", "Bytes moved through KVStore APIs",
    labels=("api",))
HBM_BYTES_IN_USE = Gauge(
    "mxnet_hbm_bytes_in_use",
    "Device memory in use (PJRT memory_stats, sampled at collection; 0 "
    "when the backend reports no stats)", labels=("device",))
HBM_PEAK_BYTES = Gauge(
    "mxnet_hbm_peak_bytes",
    "High-watermark of device memory in use (monotone max of samples)",
    labels=("device",))
PROFILER_DROPPED = Counter(
    "mxnet_profiler_dropped_events_total",
    "Chrome-trace events dropped by the profiler event cap "
    "(MXNET_PROFILER_MAX_EVENTS)")
# --- ZeRO sharded weight update (parallel/train + gluon/trainer) -----------
ZERO_SHARDS = Gauge(
    "mxnet_zero_shards",
    "dp-way shard count of the ZeRO weight update (TrainStep zero=1|2 "
    "over the 'dp' mesh axis, or Trainer zero over kvstore workers); "
    "unset/0 means replicated updates")
ZERO_STATE_BYTES = Gauge(
    "mxnet_zero_opt_state_bytes",
    "Optimizer-state bytes: scope=per_replica is what ONE replica "
    "actually holds (shard-shape sum over the live shardings), "
    "scope=replicated_equiv is what it WOULD hold unsharded — the ratio "
    "is the ZeRO HBM saving (~dp x)", labels=("scope",))
ZERO_RESIDUAL = Gauge(
    "mxnet_zero_residual_l2",
    "Error-feedback residual L2 per diff-param slot for quantized ZeRO "
    "collectives (refreshed by TrainStep.zero_residual_norms(): reading "
    "it costs a device sync, so it is on-demand, not per-step)",
    labels=("slot",))

# --- elastic pod training (mxnet_tpu/parallel/elastic) ----------------------
ELASTIC_HEARTBEATS = Counter(
    "mxnet_elastic_heartbeats_total",
    "Heartbeat exchanges on the bootstrap channel (dir=sent is this "
    "worker's own beats, dir=seen is peer stamps observed by the "
    "monitor)", labels=("dir",))
ELASTIC_PEER_AGE = Gauge(
    "mxnet_elastic_heartbeat_age_seconds",
    "Seconds since each peer's most recent heartbeat, as of the last "
    "monitor poll (compared against the configured timeout window)",
    labels=("peer",))
ELASTIC_PEER_LOST = Counter(
    "mxnet_elastic_peer_lost_total",
    "Peers declared dead by the detector (reason=heartbeat is the "
    "missed-beat window, reason=watchdog a stalled-collective "
    "wall-time bound)", labels=("reason",))
ELASTIC_SUPPRESSED = Counter(
    "mxnet_elastic_false_positives_suppressed_total",
    "Late-but-alive peers whose heartbeat recovered before the "
    "consecutive-miss threshold declared them dead (nonzero under a "
    "too-tight window: widen timeout_s / miss_polls before it flaps)")
ELASTIC_WATCHDOG_STALLS = Counter(
    "mxnet_elastic_watchdog_stalls_total",
    "Armed dispatch/collective windows that exceeded the watchdog "
    "wall-time bound (a dead peer usually manifests HERE first on the "
    "survivors: their next collective hangs)", labels=("op",))
ELASTIC_EPOCH = Gauge(
    "mxnet_elastic_epoch",
    "Membership epoch of the elastic mesh (bumped by the coordinator "
    "on every re-form; workers at different epochs never exchange)")
ELASTIC_WORLD = Gauge(
    "mxnet_elastic_world_size",
    "Current dp width of the elastic mesh (shrinks when a host is "
    "lost; the run continues at the surviving width)")
ELASTIC_REFORMS = Counter(
    "mxnet_elastic_reforms_total",
    "Mesh re-forms completed: survivors agreed on membership, rebuilt "
    "the TrainStep/ZeRO executables and resumed from the latest async "
    "sharded checkpoint at the new width")
ELASTIC_PHASE_SECONDS = Histogram(
    "mxnet_elastic_phase_seconds",
    "Wall time of each recovery phase (phase=detect is kill-to-"
    "declaration latency, phase=reform mesh+executable rebuild — AOT-"
    "warm when cached — phase=restore the checkpoint reshard+load)",
    labels=("phase",),
    buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
             120.0, 300.0))

# --- observability layer (mxnet_tpu/observability) --------------------------
STEP_PHASE = Histogram(
    "mxnet_step_phase_seconds",
    "Per-step training phase durations (phase=input_wait|h2d|dispatch|"
    "loss_sync|checkpoint_stall|allreduce|update): the step timeline "
    "TrainStep/Trainer record through observability.trace.StepTimeline",
    labels=("path", "phase"))
STEP_OVERLAP = Gauge(
    "mxnet_step_overlap_fraction",
    "1 - blocked/wall per training step: the fraction of step wall time "
    "the host was NOT blocked waiting on data or the device — how much "
    "of the dispatch/collective window (incl. the ZeRO param all-gather) "
    "actually overlapped compute", labels=("path",))
TRACE_SPANS = Counter(
    "mxnet_trace_spans_total",
    "Spans recorded into the process trace store")
TRACE_DROPPED = Counter(
    "mxnet_trace_spans_dropped_total",
    "Spans/events dropped by the trace-store caps (mirrors "
    "trace.dropped_trace_events; nonzero means /trace output is "
    "truncated)")
FLIGHT_DUMPS = Counter(
    "mxnet_flight_recorder_dumps_total",
    "Flight-recorder dumps by trigger (reason=engine_exception|"
    "guard_violation|preemption_storm|sigterm|manual)",
    labels=("reason",))
SLO_TARGET = Gauge(
    "mxnet_slo_target_seconds",
    "Configured latency SLO target at the tracked objective quantile "
    "(slo=ttft|intertoken)", labels=("slo",))
SLO_P99 = Gauge(
    "mxnet_slo_p99_seconds",
    "Fleet p99 latency estimate from the merged replica histograms "
    "(linear interpolation inside the owning bucket)", labels=("slo",))
SLO_VIOLATIONS = Counter(
    "mxnet_slo_violations_total",
    "Requests observed over the SLO target (cumulative, from the merged "
    "histogram buckets; monotone across replica restarts)",
    labels=("slo",))
SLO_BURN = Gauge(
    "mxnet_slo_error_budget_burn",
    "Error-budget burn rate: observed violation fraction / allowed "
    "fraction (1 - objective); > 1 means the budget is being spent "
    "faster than it accrues", labels=("slo",))

# --- cost ledger + live roofline (observability/perf) -----------------------
EXEC_FLOPS = Gauge(
    "mxnet_executable_flops",
    "XLA cost-analysis FLOPs of one compiled executable, captured at "
    "build time by the cost ledger (block = ledger key: train_step[, "
    "_multi], cachedop_<Block>, serve_<fn>:b<bucket>)", labels=("block",))
EXEC_HBM_BYTES = Gauge(
    "mxnet_executable_hbm_bytes",
    "XLA cost-analysis 'bytes accessed' of one compiled executable "
    "(HBM traffic per execution, fusion interiors excluded by XLA)",
    labels=("block",))
EXEC_PEAK_BYTES = Gauge(
    "mxnet_executable_peak_bytes",
    "Peak device bytes one execution holds at once (memory_analysis: "
    "arguments + outputs + temp scratch - donated aliases); 0 until "
    "the entry is completed against a compiled executable",
    labels=("block",))
MFU = Gauge(
    "mxnet_mfu",
    "Live model-FLOPs utilization per path: ledger FLOPs of the "
    "executable the path last ran / its most recent wall time / chip "
    "peak (path = train_step|train_step_multi|serve_decode|"
    "serve_prefill). XLA-visible FLOPs only — Pallas custom calls are "
    "invisible, same caveat as bench.py's mfu_xla_visible",
    labels=("path",))
HBM_UTIL = Gauge(
    "mxnet_hbm_util_fraction",
    "Live HBM bandwidth utilization per path: ledger bytes accessed / "
    "most recent step wall time / nominal chip bandwidth",
    labels=("path",))

GUARD_VIOLATIONS = Counter(
    "mxnet_guard_violations_total",
    "Runtime-guard violations observed in count mode (analysis.guards: "
    "guard=no_sync|no_recompile|lock_order) — nonzero in production "
    "means an invariant the linter enforces statically was broken "
    "dynamically", labels=("guard",))

# --- async execution pipeline (mxnet_tpu/pipeline + windowed TrainStep) -----
INPUT_WAIT = Histogram(
    "mxnet_input_wait_seconds",
    "Consumer-side wait for the next device-staged batch "
    "(DevicePrefetcher); near-zero means the input pipeline keeps the "
    "device fed, large means the step is input-bound", labels=("path",))
PIPELINE_DEPTH = Gauge(
    "mxnet_pipeline_depth",
    "Live pipeline occupancy: staged batches ready in the prefetcher "
    "(path=prefetch_*) or dispatched-but-unforced steps in the TrainStep "
    "in-flight window (path=train_step)", labels=("path",))
CKPT_STALL = Histogram(
    "mxnet_checkpoint_stall_seconds",
    "Training-thread blocking time inside CheckpointManager.save: the "
    "D2H snapshot for async saves, the full write for blocking ones",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0, 30.0, 60.0))
SERVE_HOST_SYNC = Histogram(
    "mxnet_serve_host_sync_seconds",
    "Engine-loop blocking host reads (token D2H sync); with decode "
    "lookahead the read overlaps the next step's compute, so this is "
    "the residual un-overlapped host time")

# --- serving engine (mxnet_tpu/serve) ---------------------------------------
SERVE_REQUESTS = Counter(
    "mxnet_serve_requests_total",
    "Serving requests by terminal status (ok/timeout/cancelled/rejected/"
    "shutdown/error)", labels=("status",))
SERVE_QUEUE_DEPTH = Gauge(
    "mxnet_serve_queue_depth", "Requests waiting for a decode slot")
SERVE_QUEUE_WAIT = Histogram(
    "mxnet_serve_queue_wait_seconds",
    "Submit-to-slot-admission wait (admission control latency)")
SERVE_TTFT = Histogram(
    "mxnet_serve_ttft_seconds",
    "Time to first token: submit -> prefill sampled token0")
SERVE_INTERTOKEN = Histogram(
    "mxnet_serve_intertoken_seconds",
    "Per-token decode latency (one continuous-batching step)")
SERVE_REQUEST_SECONDS = Histogram(
    "mxnet_serve_request_seconds", "End-to-end request latency")
SERVE_TOKENS = Counter(
    "mxnet_serve_tokens_total", "Tokens generated by the serving engine")
SERVE_TOKENS_PER_SEC = Gauge(
    "mxnet_serve_tokens_per_sec",
    "Decode throughput of the most recent engine step (active slots / "
    "step wall time)")
SERVE_SLOTS_IN_USE = Gauge(
    "mxnet_serve_slots_in_use", "KV-cache slots currently decoding")
SERVE_SLOT_OCCUPANCY = Gauge(
    "mxnet_serve_slot_occupancy",
    "Fraction of the slot pool in use (continuous-batching efficiency)")
SERVE_PREFILL_SECONDS = Histogram(
    "mxnet_serve_prefill_seconds",
    "Prefill latency per admitted request (bucketed prompt forward + "
    "slot cache insert)")
SERVE_STEP_SECONDS = Histogram(
    "mxnet_serve_decode_step_seconds",
    "Wall time of one batched decode step (all active slots advance one "
    "token)")
SERVE_COMPILES = Counter(
    "mxnet_serve_compiles_total",
    "Shape-bucket executables built by the serving engine (fn=prefill|"
    "decode). Flat after warmup = steady state hits only cached "
    "executables.", labels=("fn",))
SERVE_ROUNDTRIPS = Counter(
    "mxnet_serve_host_roundtrips_total",
    "Blocking host reads per engine dispatch (path=prefill|decode). With "
    "multi-token decode one decode round-trip covers K tokens, so "
    "round-trips/token << 1 is the overlap win the loadgen reports",
    labels=("path",))
DECODE_LAUNCHES = Counter(
    "mxnet_decode_launches_total",
    "Decode kernel-launch SITES recorded at trace time (kind=gemv|"
    "gemv_int4|fused_head|reference|spec_verify): one increment per "
    "launch the compiled step will issue per execution "
    "(ops/int8_gemv.count_launches tallies one trace). A kernel's kind "
    "is counted only where the Pallas kernel itself runs; reference "
    "marks a site where the plain-XLA reference ran in its place "
    "(off-TPU); spec_verify marks a speculative verify executable's "
    "trace",
    labels=("kind",))

# --- self-speculative decoding (serve engine speculate=K) --------------------
SPEC_DRAFTED = Counter(
    "mxnet_spec_drafted_tokens_total",
    "Draft tokens proposed by the self-speculative (prompt-lookup) "
    "decode path, per verify round: speculate-1 per live row")
SPEC_ACCEPTED = Counter(
    "mxnet_spec_accepted_tokens_total",
    "Draft tokens the exact verify step accepted (the emitted token "
    "equals the draft). accepted + rejected == drafted")
SPEC_REJECTED = Counter(
    "mxnet_spec_rejected_tokens_total",
    "Draft tokens the exact verify step rejected (replaced by the true "
    "token; everything after the first rejection in a round is "
    "discarded unverified and counts rejected too)")
SPEC_ROUNDS = Counter(
    "mxnet_spec_rounds_total",
    "Speculative verify dispatches (each covers every live slot; one "
    "host round-trip emits 1..K tokens per row)")
SPEC_ACCEPTANCE = Gauge(
    "mxnet_spec_acceptance_rate",
    "Running draft acceptance rate: accepted_tokens / drafted_tokens "
    "over the engine's lifetime (1.0 = every draft right — repetitive/"
    "structured traffic; near 0 = speculation pays for nothing but "
    "still emits >= 1 true token per round)")

# --- grammar-constrained decoding (serve/grammar + engine grammar mode) ------
GRAMMAR_SESSIONS = Counter(
    "mxnet_grammar_sessions_total",
    "Requests admitted with a grammar constraint attached (each decodes "
    "through the token-mask automaton; schema-conformant output by "
    "construction)")
GRAMMAR_MASK_CACHE_HITS = Counter(
    "mxnet_grammar_mask_cache_hits_total",
    "Compiled-automaton cache hits (tier=memory|disk): the "
    "content-addressed mask cache served the grammar without a "
    "recompile — steady-state structured traffic should be all hits",
    labels=("tier",))
GRAMMAR_MASK_CACHE_MISSES = Counter(
    "mxnet_grammar_mask_cache_misses_total",
    "Grammar compilations that missed every cache tier and paid the "
    "regex->DFA->token-automaton build (mxnet_grammar_compile_seconds)")
GRAMMAR_REJECTED = Counter(
    "mxnet_grammar_rejected_tokens_total",
    "Speculative draft tokens the grammar forbade (rewritten to a legal "
    "token before the verify — a grammar rejection is exactly a "
    "mismatch rejection under the token-identical contract)")
GRAMMAR_COMPILE_SECONDS = Histogram(
    "mxnet_grammar_compile_seconds",
    "Wall seconds to compile one grammar to its token-mask automaton "
    "(cache misses only; hits cost two dict lookups)",
    buckets=(0.001, 0.005, 0.02, 0.1, 0.5, 2.0, 10.0))

# --- paged KV serving (mxnet_tpu/serve/paging + paged engine) ----------------
SERVE_PAGE_POOL = Gauge(
    "mxnet_serve_page_pool_pages",
    "Leasable KV pages in the pool (paged engine HBM budget; excludes "
    "the sink page)")
SERVE_PAGE_IN_USE = Gauge(
    "mxnet_serve_page_in_use",
    "KV pages currently leased (slot block tables + prefix-cache pins): "
    "requests now cost their ACTUAL length in HBM, not max_len")
SERVE_PAGE_LEASES = Counter(
    "mxnet_serve_page_leases_total",
    "Pages leased on demand as decode positions advance (frees are "
    "implicit at retire/eviction: in_use is the live balance)")
SERVE_PAGE_COW = Counter(
    "mxnet_serve_page_cow_forks_total",
    "Copy-on-write forks: a slot wrote into a page shared with the "
    "prefix cache or another slot, so the page was copied first")
SERVE_PAGE_FOLDS = Counter(
    "mxnet_serve_page_folds_total",
    "Pages that went back to the pool while their request lived: a model "
    "whose cache folds finished a window, and the window's pages were "
    "replaced by one page of summaries")
SERVE_WINDOW_PAGES_RECYCLED = Counter(
    "mxnet_serve_window_pages_recycled_total",
    "Pages of a windowed kind that went back to the pool while their "
    "request lived: they lay wholly behind its sliding window")
SERVE_MOE_ASSIGNMENTS = Counter(
    "mxnet_serve_moe_assignments_total",
    "Token-to-expert assignments the served programs routed (where=all), "
    "and those among them to experts this engine's model holds "
    "(where=here)", labels=("where",))
SERVE_PAGE_PREEMPTIONS = Counter(
    "mxnet_serve_page_preemptions_total",
    "Slots preempted on pool exhaustion (released + requeued; resumed "
    "exactly via the stateless per-request sampling streams)")
SERVE_PREFIX_HITS = Counter(
    "mxnet_serve_page_prefix_hits_total",
    "Admissions that mapped cached shared-prefix pages instead of "
    "re-prefilling them")
SERVE_PREFIX_MISSES = Counter(
    "mxnet_serve_page_prefix_misses_total",
    "Admissions with no cached prefix (full prefill)")
SERVE_PREFIX_TOKENS_SAVED = Counter(
    "mxnet_serve_page_prefix_tokens_saved_total",
    "Prompt tokens whose prefill was skipped via prefix-cache page "
    "mapping (bytes saved = tokens x per-token KV bytes, reported by "
    "the engine stats)")
SERVE_PREFIX_BYTES_SAVED = Counter(
    "mxnet_serve_page_prefix_bytes_saved_total",
    "HBM write traffic avoided by prefix-cache hits (tokens_saved x "
    "per-token KV row bytes)")
SERVE_PREFIX_COLLISIONS = Counter(
    "mxnet_serve_page_prefix_collisions_total",
    "Prefix-cache key collisions detected by token comparison (the "
    "match walk stops; the span is prefilled normally)")
SERVE_PREFILL_CHUNKS = Counter(
    "mxnet_serve_page_prefill_chunks_total",
    "Chunked-prefill chunks dispatched (long prompts split into "
    "page-sized chunks interleaved with decode steps, bounding TTFT "
    "p99 for in-flight requests)")

# --- self-managing fleet (mxnet_tpu/serve/fleet + registry) ------------------
FLEET_REPLICAS = Gauge(
    "mxnet_fleet_replicas",
    "Fleet size as the autoscale controller sees it (state=healthy: in "
    "the dispatch rotation; state=retiring: drained, waiting for "
    "in-flight work to finish before the process stops)",
    labels=("state",))
FLEET_SCALE_EVENTS = Counter(
    "mxnet_fleet_scale_events_total",
    "Autoscale decisions acted on (direction=up|down, reason=load|"
    "slo_burn|min_floor) — every replica the controller spawned or "
    "drained is visible here", labels=("direction", "reason"))
FLEET_SUPPRESSED = Counter(
    "mxnet_fleet_decisions_suppressed_total",
    "Scale decisions the controller wanted but suppressed (why="
    "hysteresis: pressure not sustained for the required consecutive "
    "ticks; cooldown: a recent scale event is still settling; at_max/"
    "at_min: the replica-count bounds; no_owned_replica: nothing the "
    "spawner may drain) — the flap-damping at work",
    labels=("direction", "why"))
FLEET_PRESSURE = Gauge(
    "mxnet_fleet_pressure",
    "The controller's fused load signal: mean healthy-replica load "
    "(slot/page pressure + queue backlog off /healthz), the scale-up/"
    "down thresholds compare against this")
FLEET_TICKS = Counter(
    "mxnet_fleet_controller_ticks_total",
    "Autoscale control-loop observations (decisions or not)")
FLEET_SPAWN_SECONDS = Histogram(
    "mxnet_fleet_spawn_seconds",
    "Wall time to spawn one replica and see it healthy (AOT-prewarmed "
    "spawn keeps this to IO + dispatch)",
    buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
             300.0))
FLEET_DRAIN_SECONDS = Histogram(
    "mxnet_fleet_drain_seconds",
    "Wall time from a controller-initiated drain to the replica being "
    "idle (in-flight requests finished)",
    buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0))
FLEET_TENANT_DISPATCH = Counter(
    "mxnet_fleet_tenant_dispatch_total",
    "Requests dispatched per tenant after WFQ admission (the fairness "
    "arithmetic: over a saturated period, per-tenant shares track the "
    "configured weights)", labels=("tenant",))
FLEET_TENANT_INFLIGHT = Gauge(
    "mxnet_fleet_tenant_inflight",
    "Requests a tenant has in flight past admission (bounded by the "
    "tenant's max_inflight quota)", labels=("tenant",))
FLEET_TENANT_WAIT = Histogram(
    "mxnet_fleet_tenant_queue_wait_seconds",
    "WFQ admission wait per tenant (a bursting tenant queues HERE "
    "instead of starving everyone else's slots)", labels=("tenant",))
FLEET_TENANT_REJECTED = Counter(
    "mxnet_fleet_tenant_rejected_total",
    "Requests rejected at tenant admission (quota/WFQ wait exceeded "
    "its timeout — surfaces as 429 backpressure)", labels=("tenant",))

# --- live weight refresh (mxnet_tpu/serve/registry + engine swap) ------------
SERVE_WEIGHT_VERSION = Gauge(
    "mxnet_serve_weight_version",
    "Checkpoint version the engine's captured params currently serve "
    "(flips between decode ticks on a hot swap; 0 = construction-time "
    "weights, never published)", labels=("model",))
SERVE_WEIGHT_SWAPS = Counter(
    "mxnet_serve_weight_swaps_total",
    "Live weight swaps applied between decode ticks (no restart, no "
    "recompile — shapes unchanged means the same executables)",
    labels=("model",))

# --- multi-replica router (mxnet_tpu/serve/router) ---------------------------
ROUTER_DISPATCH = Counter(
    "mxnet_router_dispatch_total",
    "Requests dispatched per replica (least-loaded choice over healthz "
    "slot/page occupancy)", labels=("backend",))
ROUTER_EJECTS = Counter(
    "mxnet_router_ejects_total",
    "Replica ejections by cause: reason=poll_fail (healthz/transport "
    "failure), 5xx (replica-side dispatch failure), draining (graceful "
    "drain, incl. drain-bounced requests)",
    labels=("backend", "reason"))
ROUTER_REJOINS = Counter(
    "mxnet_router_rejoins_total",
    "Ejected replicas re-admitted after healthz recovered",
    labels=("backend",))
ROUTER_RETRIES = Counter(
    "mxnet_router_retries_total",
    "Requests re-dispatched to another replica after a dispatch failure")
ROUTER_REBALANCES = Counter(
    "mxnet_router_rebalances_total",
    "Dispatches where the least-loaded choice moved off the previously "
    "preferred replica (load-signal-driven rebalancing)")
ROUTER_HEALTHY = Gauge(
    "mxnet_router_backends_healthy",
    "Replicas currently in the dispatch rotation")

# --- cache-aware fleet (mxnet_tpu/serve/cachefleet + router affinity) --------
CACHE_AFFINITY_DISPATCH = Counter(
    "mxnet_cache_affinity_dispatch_total",
    "Prefix-affinity dispatch outcomes: outcome=hit (a replica's "
    "advertised prefix summary matched the prompt and won), "
    "load_bounded (a cache holder matched but exceeded the affinity "
    "load bound — least-loaded dispatch took over, the never-starve-"
    "a-cold-replica half of the contract), cold (no advertised root "
    "matched anywhere — plain least-loaded dispatch)",
    labels=("outcome",))
CACHE_AFFINITY_HIT_TOKENS = Counter(
    "mxnet_cache_affinity_hit_tokens_total",
    "Prompt tokens the affinity winner advertised as already cached at "
    "dispatch time (the router-side expectation; the replica's own "
    "mxnet_serve_page_prefix_tokens_saved_total records what the "
    "admission actually mapped)")
CACHE_ADVERT_ROOTS = Gauge(
    "mxnet_cache_advert_roots",
    "Prefix-cache roots this replica currently advertises via /healthz "
    "(bounded by the serve_prefix_advert knob — the O(N) health-poll "
    "payload contract)")
MIGRATE_PAGES_SENT = Counter(
    "mxnet_migrate_pages_sent_total",
    "KV pages exported for cross-replica migration (preemption rescue, "
    "prefill->decode tier streaming, fleet defrag). Balance invariant: "
    "sent == received + verify_failures")
MIGRATE_PAGES_RECEIVED = Counter(
    "mxnet_migrate_pages_received_total",
    "KV pages imported after chain-hash verification and published into "
    "the receiving replica's prefix cache")
MIGRATE_VERIFY_FAILURES = Counter(
    "mxnet_migrate_verify_failures_total",
    "Migrated pages REJECTED on receipt: the recomputed chain hash of "
    "the accompanying tokens did not match the sender's (corruption or "
    "a codec bug — the page is dropped, the receiver re-prefills)")
MIGRATE_RESCUES = Counter(
    "mxnet_migrate_rescues_total",
    "OutOfPages preemption rescues: outcome=resumed (the victim's pages "
    "shipped to another replica and the request resumed there token-"
    "exactly), failed (no capacity/transport error — the request "
    "requeued locally, the pre-mxcache behavior)",
    labels=("outcome",))
FLEET_TIER_REPLICAS = Gauge(
    "mxnet_fleet_tier_replicas",
    "Replicas per disaggregated serving tier (tier=prefill|decode|"
    "mixed) as the tier's controller sees them (state=healthy|retiring)",
    labels=("tier", "state"))
FLEET_TIER_SCALE_EVENTS = Counter(
    "mxnet_fleet_tier_scale_events_total",
    "Per-tier autoscale decisions acted on: each tier scales off its "
    "OWN SLO-burn signal (prefill on ttft, decode on intertoken) with "
    "per-tier min/max bounds — the disaggregation argument made "
    "visible", labels=("tier", "direction", "reason"))

# --- persistent AOT compile cache (mxnet_tpu/aot) ----------------------------
AOT_HITS = Counter(
    "mxnet_aot_cache_hits_total",
    "AOT disk-cache hits: an XLA executable was deserialized instead of "
    "compiled (block=cachedop_*|train_step*|serve_*)", labels=("block",))
AOT_MISSES = Counter(
    "mxnet_aot_cache_misses_total",
    "AOT disk-cache misses: a fresh XLA compile (stored for the next "
    "process unless unserializable)", labels=("block",))
AOT_ERRORS = Counter(
    "mxnet_aot_cache_errors_total",
    "AOT cache degradations, all non-fatal (kind=corrupt|deserialize|"
    "serialize|lower|signature_mismatch); every one falls back to a "
    "fresh compile", labels=("kind",))
AOT_EVICTIONS = Counter(
    "mxnet_aot_cache_evictions_total",
    "Entries evicted by the MXNET_AOT_CACHE_BYTES LRU cap")
AOT_BYTES = Gauge(
    "mxnet_aot_cache_bytes",
    "Total bytes of the persistent AOT cache directory (sampled on "
    "writes)")
AOT_LOAD_SECONDS = Histogram(
    "mxnet_aot_load_seconds",
    "Wall time to deserialize one cached executable (the warm-start "
    "cost that replaces an XLA compile)")
AOT_COMPILE_SECONDS = Histogram(
    "mxnet_aot_compile_seconds",
    "Wall time of XLA compiles on the AOT-cache miss path (the cold-"
    "start cost a warm cache removes)")
AOT_WARMUP_SECONDS = Histogram(
    "mxnet_aot_warmup_seconds",
    "End-to-end warmup wall time per path (path=serve covers the whole "
    "InferenceEngine bucket ladder) — the headline cold- vs warm-start "
    "number", labels=("path",),
    buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
             120.0, 300.0))

# -- autotuning (mxnet_tpu/tune): the tuned-config cache + search -----------
TUNE_TRIALS = Counter(
    "mxnet_tune_trials_total",
    "Configurations measured by the mxtune search (one per measure() "
    "call, workload = decode|ladder|prefill|synthetic|custom)",
    labels=("workload",))
TUNE_CACHE_HITS = Counter(
    "mxnet_tune_cache_hits_total",
    "Tuned-config cache hits: a consulting site's content-address "
    "matched a stored config (site=global|serve; whether each knob "
    "APPLIES still depends on resolution — explicit args and env "
    "outrank it, see mxnet_tune_active_config)", labels=("site",))
TUNE_CACHE_MISSES = Counter(
    "mxnet_tune_cache_misses_total",
    "Tuned-config cache misses: no (valid) entry for the site's key — "
    "the hand-picked defaults apply, bitwise", labels=("site",))
TUNE_CACHE_ERRORS = Counter(
    "mxnet_tune_cache_errors_total",
    "Tuned-config cache degradations (kind=corrupt): the entry was "
    "evicted and the site fell back to defaults", labels=("kind",))
TUNE_ACTIVE = Gauge(
    "mxnet_tune_active_config",
    "Value of one tuned knob actively overriding its hand-picked "
    "default (absent = the default applies)", labels=("site", "knob"))

# --- numeric health telemetry (observability/health + amp/loss_scaler) ------
HEALTH_NONFINITE = Gauge(
    "mxnet_health_nonfinite",
    "Nonfinite (NaN/Inf) element counts from the most recently read "
    "on-device health vector (what=grads|params|loss; params counts "
    "the PRE-update values, so a param-born NaN classifies apart from "
    "a grad-born one)", labels=("what",))
HEALTH_NORM = Gauge(
    "mxnet_health_norm",
    "Global fp32 L2 norms from the fused step's health vector "
    "(which=grad the rescaled gradients, which=update the applied "
    "param delta, which=param the post-update parameters) — read on "
    "the lazy-loss window's deferred schedule, never a fresh sync",
    labels=("which",))
HEALTH_LOSS = Gauge(
    "mxnet_health_loss",
    "Most recently read step loss off the health vector (the z-score "
    "detector's input signal, on the same deferred schedule)")
HEALTH_ZSCORE = Gauge(
    "mxnet_health_zscore",
    "Rolling-window z-score of the last observation per detector "
    "signal (signal=loss|grad_norm); the anomaly threshold lives in "
    "HealthConfig.zscore", labels=("signal",))
HEALTH_ANOMALIES = Counter(
    "mxnet_health_anomalies_total",
    "Numeric anomalies declared by the health monitor (kind=nonfinite "
    "a hard NaN/Inf trigger, kind=loss_spike|grad_explosion a rolling "
    "z-score breach); every one also emits a reason=numeric_anomaly "
    "flight-recorder dump", labels=("kind",))
HEALTH_SKIPPED = Counter(
    "mxnet_health_skipped_steps_total",
    "Steps whose update was dropped bitwise ON DEVICE by the "
    "on_anomaly='skip' policy (a nonfinite grad/param/loss selected "
    "the old params+state, the AMP-scaler skip semantics)")
HEALTH_LAST_ANOMALY_STEP = Gauge(
    "mxnet_health_last_anomaly_step",
    "Step index of the most recent numeric anomaly (0 = none yet); "
    "checkpoints at or after this step are tainted until the monitor "
    "is reset by a last-healthy restore")
HEALTH_LAYER_MAXABS = Gauge(
    "mxnet_health_layer_maxabs",
    "Sampled per-layer-group max-abs of the parameters (one separate "
    "cached executable every HealthConfig.sample_every steps; 0 = "
    "sampling off)", labels=("group",))
HEALTH_LAYER_RMS = Gauge(
    "mxnet_health_layer_rms",
    "Sampled per-layer-group RMS of the parameters (same cadence and "
    "executable as mxnet_health_layer_maxabs)", labels=("group",))

AMP_SCALE = Gauge(
    "mxnet_amp_scale",
    "Current dynamic loss scale of the AMP LossScaler (fp16 training; "
    "halves on overflow, doubles after scale_window clean steps)")
AMP_SKIPPED = Counter(
    "mxnet_amp_skipped_steps_total",
    "Optimizer steps skipped by the AMP scaler's overflow check "
    "(grads carried inf/nan at the current scale; params and state "
    "were left untouched)")
AMP_SCALE_ADJUSTMENTS = Counter(
    "mxnet_amp_scale_adjustments_total",
    "Dynamic loss-scale changes (direction=down an overflow halved it, "
    "direction=up a full clean scale_window doubled it)",
    labels=("direction",))


@register_collect_callback
def _sample_device_memory():
    """HBM gauges from PJRT memory_stats() (storage-profiler role): sampled
    at every collection so dumps always carry a current value; the peak
    gauge keeps the high-watermark across samples."""
    try:
        import jax
        devs = jax.devices()
    except Exception:
        return
    for d in devs:
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        label = f"{d.platform}:{d.id}"
        in_use = float(stats.get("bytes_in_use", 0) or 0)
        peak = float(stats.get("peak_bytes_in_use", in_use) or in_use)
        HBM_BYTES_IN_USE._child((label,))._set_direct(in_use)
        pk = HBM_PEAK_BYTES._child((label,))
        pk._set_direct(max(pk.value, peak, in_use))


# --- compilations, wherever they come from ----------------------------------
# JAX wraps every backend compile, a load from the persistent cache
# included, in one duration event; a load reports its retrieval time first,
# on the same thread, which is how the two are told apart.
_JAX_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_JAX_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_compiles = 0
_COMPILES_LOCK = threading.Lock()
_compile_tls = threading.local()


def backend_compiles() -> int:
    """Executables the XLA backend built or loaded from JAX's persistent
    cache since import, whoever asked (eager one-off programs too): both
    hold the calling thread. Counted with or without a registry; flat
    across a measured window = nothing compiled there."""
    return _compiles


def _on_jax_duration(event: str, seconds: float, **kwargs):
    if event == _JAX_CACHE_LOAD_EVENT:
        _compile_tls.cached = True
        return
    if event != _JAX_COMPILE_EVENT:
        return
    global _compiles
    cached = getattr(_compile_tls, "cached", False)
    _compile_tls.cached = False
    with _COMPILES_LOCK:
        _compiles += 1
    fun = str(kwargs.get("fun_name", ""))
    from .observability import recorder as _recorder
    _recorder.RECORDER.record("compile", fun, seconds=seconds, cached=cached)
    # an instant span where the compilation ENDED, carrying its seconds:
    # a traced window shows each one on the thread it held
    with _profiler.scope("mx.compile", "compile", fun=fun,
                         seconds=round(seconds, 6), cached=cached):
        pass


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


@register_collect_callback
def _sample_profiler_dropped():
    PROFILER_DROPPED._child(())._set_direct(float(_profiler.dropped_events()))


@register_collect_callback
def _sample_perf_gauges():
    # lazy import (same contract as the trace-counter callback): derive
    # mxnet_mfu / mxnet_hbm_util_fraction from the cost ledger + the
    # most recent step-time notes at every collection
    try:
        from .observability import perf as _perf
    except Exception:
        return
    _perf.refresh_gauges()


@register_collect_callback
def _sample_trace_counters():
    # lazy import: observability imports metrics at module top; this
    # callback only runs at collection time, after both modules exist
    try:
        from .observability import trace as _trace
    except Exception:
        return
    TRACE_DROPPED._child(())._set_direct(float(_trace.dropped_trace_events()))
    TRACE_SPANS._child(())._set_direct(float(_trace.STORE.added()))


if get_env("MXNET_METRICS", False, dtype=bool,
           doc="enable the runtime metrics registry at import"):
    enable()
