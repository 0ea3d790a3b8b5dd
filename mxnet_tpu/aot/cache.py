"""Persistent ahead-of-time compile cache: XLA executables on disk.

Every process restart — a preempted worker resuming from a checkpoint, a
serving replica rolling out, a CI bench run — pays full retrace + XLA
compile for every CachedOp / TrainStep / serve-bucket executable, even
though the telemetry layer proves the compiled artifacts are byte-identical
run to run. TensorFlow (PAPERS 1605.08695) and the Julia-to-TPU compiler
(PAPERS 1810.09868) both treat AOT compilation artifacts as first-class
persistent objects; this module does the same for the jitted executables
the runtime builds.

Design:

- **Content-addressed.** An entry's key is a SHA-256 fingerprint of the
  lowered StableHLO text + the abstract input signature (shape/dtype/
  sharding of every argument) + jax/jaxlib versions + backend platform,
  device kind and device count + the cache format version. Parameter
  VALUES are runtime inputs, so one cached executable serves any weights
  of the same architecture — a prewarmed cache works for checkpoints it
  has never seen.
- **Corruption-safe.** Entries are written atomically (tmp + rename into
  place) with a versioned header and a payload checksum; a truncated,
  garbage, or stale-format entry is treated as a miss (and deleted), never
  an exception on the load path. A failed executable deserialization falls
  back to a fresh compile the same way.
- **Bounded.** ``MXNET_AOT_CACHE_BYTES`` caps the directory; least-
  recently-used entries (mtime, refreshed on every hit) are evicted on
  insert.
- **Graceful degradation.** Executables that refuse serialization
  (host callbacks, exotic shardings) get a signature-only stub entry so
  later processes skip the doomed serialize attempt and go straight to
  compile — the cache never makes a cold start slower than no cache.

The process-wide cache is configured by ``MXNET_AOT_CACHE_DIR`` (unset =
disabled, like jax's own persistent compilation cache) or programmatically
via :func:`enable`. ``compile_cached`` is the one integration point used
by CachedOp, TrainStep and the serving engine's bucket ladder.

**Trust model.** Entry payloads are unpickled at load time; the payload
checksum defends against CORRUPTION (torn writes, bit rot), not
TAMPERING — it lives in the same file an attacker would rewrite. Treat
the cache directory with exactly the trust you give checkpoint/params
files: writable only by the training/serving identity, and when shipping
caches between CI jobs or to replicas, transport them through the same
authenticated artifact store as model weights. Never point
``MXNET_AOT_CACHE_DIR`` at a world-writable or untrusted directory.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import struct
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from .. import metrics as _metrics
from ..analysis import guards as _guards
from ..base import MXNetError, get_env, logger

__all__ = [
    "AotCache", "get_cache", "enable", "disable", "compile_cached",
    "fingerprint", "FORMAT_VERSION", "KIND_EXECUTABLE", "KIND_SIGNATURE",
]

# bump when the entry layout or fingerprint recipe changes: old entries
# become clean misses, not crashes
FORMAT_VERSION = 1
_MAGIC = b"MXAOT\x01"
KIND_EXECUTABLE = "executable"
KIND_SIGNATURE = "signature-only"

_DEFAULT_MAX_BYTES = 1 << 30  # 1 GiB


def _backend_id() -> Dict[str, Any]:
    """Backend/topology part of the fingerprint: an executable compiled
    for one platform/chip/mesh size must never load on another."""
    try:
        devs = jax.devices()
        d0 = devs[0]
        return {"platform": d0.platform,
                "device_kind": d0.device_kind,
                "num_devices": len(devs),
                "process_index": getattr(d0, "process_index", 0)}
    except Exception:
        return {"platform": "unknown", "device_kind": "unknown",
                "num_devices": 0, "process_index": 0}


def _aval_sig(x) -> str:
    """Stable string for one abstract value, including its sharding (a
    GSPMD-partitioned program is a different executable than the
    single-device one for the same shapes)."""
    shape = tuple(getattr(x, "shape", ()))
    dtype = str(getattr(x, "dtype", "?"))
    sh = getattr(x, "sharding", None)
    return f"{shape}:{dtype}:{sh}"


def fingerprint(lowered, extra: Any = None) -> str:
    """Content-address a ``jax.stages.Lowered``: SHA-256 over the lowered
    StableHLO text, the flat input avals, jax/jaxlib versions, backend and
    topology, the cache format version, and any caller ``extra`` (e.g.
    donation flags that do not show in the module text)."""
    import jaxlib

    h = hashlib.sha256()
    h.update(lowered.as_text().encode())
    try:
        in_avals = jax.tree_util.tree_leaves(lowered.in_avals)
    except Exception:
        in_avals = []
    parts = {
        "avals": [_aval_sig(a) for a in in_avals],
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": _backend_id(),
        "format": FORMAT_VERSION,
        "extra": repr(extra) if extra is not None else None,
    }
    h.update(json.dumps(parts, sort_keys=True).encode())
    return h.hexdigest()


class AotCache:
    """Content-addressed directory of serialized XLA executables.

    One file per entry: ``<dir>/<key[:2]>/<key>.aot`` laid out as
    ``MAGIC | u32 header_len | header JSON | payload``. The header carries
    the format version, entry kind, label, payload checksum and sizes; the
    payload is the pickled ``jax.experimental.serialize_executable``
    triple (or empty for signature-only stubs).
    """

    def __init__(self, path: str, max_bytes: Optional[int] = None):
        self.path = os.path.abspath(os.path.expanduser(path))
        if max_bytes is None:
            max_bytes = get_env("MXNET_AOT_CACHE_BYTES", _DEFAULT_MAX_BYTES,
                                dtype=int,
                                doc="LRU size cap (bytes) of the persistent "
                                    "AOT compile cache")
        self.max_bytes = int(max_bytes)
        self._lock = _guards.make_lock("aot.AotCache._lock")
        # keys read or written by THIS process (feeds manifests/prewarm)
        self.touched: List[Dict[str, Any]] = []
        os.makedirs(self.path, exist_ok=True)

    # ------------------------------------------------------------ layout
    def _entry_path(self, key: str) -> str:
        return os.path.join(self.path, key[:2], key + ".aot")

    def _iter_entry_files(self):
        for root, _dirs, files in os.walk(self.path):
            for f in files:
                if f.endswith(".aot"):
                    yield os.path.join(root, f)

    # ------------------------------------------------------------- store
    def put(self, key: str, payload: bytes, kind: str = KIND_EXECUTABLE,
            label: str = "", meta: Optional[Dict[str, Any]] = None):
        """Atomically write one entry (tmp + rename: a crashed writer can
        never leave a half-entry under the final name), then enforce the
        LRU byte cap."""
        header = {
            "format": FORMAT_VERSION,
            "kind": kind,
            "key": key,
            "label": label,
            "payload_bytes": len(payload),
            "payload_sha256": hashlib.sha256(payload).hexdigest(),
            "created": time.time(),
        }
        if meta:
            header["meta"] = meta
        hjson = json.dumps(header, sort_keys=True).encode()
        path = self._entry_path(key)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                   prefix=".tmp-", suffix=".aot")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(_MAGIC)
                f.write(struct.pack("<I", len(hjson)))
                f.write(hjson)
                f.write(payload)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        self._note_touched(key, label, kind, len(payload))
        total = self._enforce_cap(keep=key)
        self._observe_bytes(total)

    # -------------------------------------------------------------- load
    def get(self, key: str) -> Optional[Tuple[Dict[str, Any], bytes]]:
        """Load one entry; returns ``(header, payload)`` or None. Any
        corruption — bad magic, unparseable or stale-version header,
        truncated or checksum-failing payload — deletes the entry and
        reads as a miss (the caller recompiles; serving never crashes on
        a bad cache file)."""
        path = self._entry_path(key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return None
        header = self._parse(blob)
        if header is None:
            _metrics.AOT_ERRORS.labels(kind="corrupt").inc()
            logger.warning("aot: corrupt/stale cache entry %s (evicting)",
                           os.path.basename(path))
            try:
                os.unlink(path)
            except OSError:
                pass
            return None
        hdr, payload = header
        now = time.time()
        try:
            os.utime(path, (now, now))  # LRU recency
        except OSError:
            pass
        self._note_touched(key, hdr.get("label", ""), hdr.get("kind", "?"),
                           len(payload))
        return hdr, payload

    @staticmethod
    def _parse(blob: bytes):
        if len(blob) < len(_MAGIC) + 4 or not blob.startswith(_MAGIC):
            return None
        off = len(_MAGIC)
        (hlen,) = struct.unpack_from("<I", blob, off)
        off += 4
        if off + hlen > len(blob):
            return None
        try:
            hdr = json.loads(blob[off:off + hlen].decode())
        except (UnicodeDecodeError, ValueError):
            return None
        if not isinstance(hdr, dict) or hdr.get("format") != FORMAT_VERSION:
            return None
        payload = blob[off + hlen:]
        if len(payload) != hdr.get("payload_bytes", -1):
            return None
        if hashlib.sha256(payload).hexdigest() != hdr.get("payload_sha256"):
            return None
        return hdr, payload

    # --------------------------------------------------------------- mgmt
    def entries(self) -> List[Dict[str, Any]]:
        """Headers of every valid entry (invalid files are skipped, not
        raised on — this is the admin/inspection path)."""
        out = []
        for path in self._iter_entry_files():
            try:
                with open(path, "rb") as f:
                    parsed = self._parse(f.read())
            except OSError:
                continue
            if parsed is not None:
                out.append(parsed[0])
        return out

    def total_bytes(self) -> int:
        n = 0
        for path in self._iter_entry_files():
            try:
                n += os.path.getsize(path)
            except OSError:
                pass
        return n

    def contains(self, key: str) -> bool:
        return os.path.exists(self._entry_path(key))

    def clear(self):
        for path in self._iter_entry_files():
            try:
                os.unlink(path)
            except OSError:
                pass
        self._observe_bytes()

    def _enforce_cap(self, keep: Optional[str] = None) -> int:
        """Evict least-recently-used entries until under ``max_bytes``;
        returns the remaining directory byte total (one walk serves both
        the cap and the bytes gauge — put() must not be O(entries^2) in
        directory scans over a prewarm). ``keep`` protects the entry just
        written (evicting the newest member to honor a cap it alone
        exceeds would thrash)."""
        # lock-free on purpose (mxlint MX005): the directory walk and the
        # unlinks are disk I/O, and holding the cache lock across them
        # stalled every concurrent hit/miss. Concurrent eviction is safe:
        # the walk is advisory, unlink errors are swallowed (another
        # thread/process may have evicted first), and the byte totals
        # only feed the gauge.
        files = []
        total = 0
        for path in self._iter_entry_files():
            try:
                st = os.stat(path)
            except OSError:
                continue
            files.append((st.st_mtime, st.st_size, path))
            total += st.st_size
        if self.max_bytes <= 0 or total <= self.max_bytes:
            return total
        keep_path = self._entry_path(keep) if keep else None
        for _mtime, size, path in sorted(files):
            if total <= self.max_bytes:
                break
            if path == keep_path:
                continue
            try:
                os.unlink(path)
                total -= size
                _metrics.AOT_EVICTIONS.inc()
            except OSError:
                pass
        return total

    def _observe_bytes(self, total: Optional[int] = None):
        if _metrics.ENABLED:
            _metrics.AOT_BYTES.set(float(
                self.total_bytes() if total is None else total))

    def _note_touched(self, key: str, label: str, kind: str, nbytes: int):
        with self._lock:
            self.touched.append({"key": key, "label": label, "kind": kind,
                                 "payload_bytes": nbytes})


# ---------------------------------------------------------------------------
# process-wide cache handle
# ---------------------------------------------------------------------------

_CACHE: Optional[AotCache] = None
_CACHE_INIT = False
_CACHE_LOCK = threading.Lock()


def get_cache() -> Optional[AotCache]:
    """The process-wide cache, or None when disabled. First call reads
    ``MXNET_AOT_CACHE_DIR`` (unset/empty = disabled)."""
    global _CACHE, _CACHE_INIT
    with _CACHE_LOCK:
        if not _CACHE_INIT:
            _CACHE_INIT = True
            path = get_env("MXNET_AOT_CACHE_DIR", "",
                           doc="directory of the persistent AOT compile "
                               "cache (empty = disabled)")
            if path:
                try:
                    _CACHE = AotCache(path)
                except OSError as e:
                    logger.warning("aot: cannot open cache dir %r (%s); "
                                   "cache disabled", path, e)
                    _CACHE = None
        return _CACHE


def enable(path: str, max_bytes: Optional[int] = None) -> AotCache:
    """Programmatically enable the persistent cache at ``path``."""
    global _CACHE, _CACHE_INIT
    with _CACHE_LOCK:
        _CACHE = AotCache(path, max_bytes=max_bytes)
        _CACHE_INIT = True
        return _CACHE


def disable():
    global _CACHE, _CACHE_INIT
    with _CACHE_LOCK:
        _CACHE = None
        _CACHE_INIT = True


# ---------------------------------------------------------------------------
# the integration point: load-or-compile one jitted signature
# ---------------------------------------------------------------------------

class _AotExecutable:
    """Callable wrapper around an AOT ``jax.stages.Compiled``.

    Two escape hatches keep it exactly as capable as the jit it wraps:

    - **Tracer args** (autograd's backward replays the recorded fn under
      ``jax.vjp``; a Compiled cannot be traced) delegate to the original
      jitted function — which inlines into the surrounding trace — and
      the compiled fast path stays armed for eager calls.
    - **Aval mismatch** (an autocast wrapper changed a dtype, or a call
      arrives with shardings the executable was not lowered for — jax
      raises TypeError for the former, ValueError for the latter) falls
      back to jit permanently rather than fail the step.
    """

    __slots__ = ("_compiled", "_jitted", "__name__", "from_cache")

    def __init__(self, compiled, jitted, name: str, from_cache: bool):
        self._compiled = compiled
        self._jitted = jitted
        self.__name__ = name
        self.from_cache = from_cache

    def __call__(self, *args):
        if self._compiled is None:
            return self._jitted(*args)
        if any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(args)):
            return self._jitted(*args)
        try:
            return self._compiled(*args)
        except (TypeError, ValueError) as e:
            logger.warning("aot: %s signature mismatch vs cached "
                           "executable (%s); falling back to jit",
                           self.__name__, e)
            _metrics.AOT_ERRORS.labels(kind="signature_mismatch").inc()
            self._compiled = None
            return self._jitted(*args)


def _execution_devices(example_args):
    """Devices, in assignment order, that a program lowered for
    ``example_args`` runs on: the mesh of a NamedSharding argument, else
    the one device the arguments sit on, else the default device."""
    from jax.sharding import NamedSharding
    single = None
    for leaf in jax.tree.leaves(example_args):
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding):
            return list(sh.mesh.devices.flat)
        if sh is not None and single is None:
            single = list(sh.device_set)
    return single or [jax.devices()[0]]


#: compiles that jax's own persistent compilation cache answered in this
#: thread (the event is recorded in the compiling thread)
_JAX_HITS = threading.local()
_JAX_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_jax_cache_lock = threading.Lock()
_listening = False


def _on_jax_event(event: str, **_kwargs) -> None:
    if event == _JAX_HIT_EVENT:
        _JAX_HITS.n = getattr(_JAX_HITS, "n", 0) + 1


def _compile_to_store(jitted, example_args, lowered):
    """``lowered.compile()`` for an entry about to be persisted. Where jax's
    own persistent compilation cache answers the compile, the executable it
    loaded serializes without its code on XLA:CPU: the entry restores, then
    fails at its first call with ``NOT_FOUND: ... Function ... not found``.
    Such a program is compiled once more with that cache off (the jitted
    function's own cache cleared, since it keeps the loaded executable)."""
    global _listening
    with _jax_cache_lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_jax_event)
            _listening = True
    before = getattr(_JAX_HITS, "n", 0)
    compiled = lowered.compile()
    if getattr(_JAX_HITS, "n", 0) == before:
        return compiled
    from jax.experimental.compilation_cache import compilation_cache as _jcc
    with _jax_cache_lock:
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        _jcc.reset_cache()
        try:
            if hasattr(jitted, "clear_cache"):
                jitted.clear_cache()
            return jitted.lower(*example_args).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            _jcc.reset_cache()


def compile_cached(jitted, example_args: Sequence, label: str,
                   extra: Any = None):
    """Compile ``jitted`` for ``example_args`` through the persistent
    cache.

    With the cache disabled this returns ``jitted`` unchanged — the exact
    pre-AOT behavior (jit traces and compiles lazily on first call).

    With a cache: lower (tracing is cheap and also yields the
    content-address), then either deserialize a previously stored
    executable (hit: XLA compile skipped entirely) or compile and persist
    it (miss). Executables that cannot serialize leave a signature-only
    stub so the NEXT process skips the serialize attempt too. Any cache
    failure degrades to a fresh in-process compile.

    ``example_args`` may be concrete arrays or ShapeDtypeStructs —
    anything ``jitted.lower`` accepts. ``extra`` folds caller context that
    is not visible in the lowered module text (donation flags, static
    config) into the fingerprint.
    """
    cache = get_cache()
    if cache is None:
        return jitted
    from jax.experimental import serialize_executable as _se

    name = getattr(jitted, "__name__", label) or label
    try:
        from ..ops.int8_gemv import count_launches
        with count_launches() as launch_tally:
            lowered = jitted.lower(*example_args)
        key = fingerprint(lowered, extra=extra)
    except Exception as e:
        # lowering failed in a way plain jit would surface on first call
        # anyway; don't let the cache path own that error
        logger.warning("aot: lower failed for %s (%s); using jit", label, e)
        _metrics.AOT_ERRORS.labels(kind="lower").inc()
        return jitted

    def _ledger(compiled=None):
        # cost-ledger capture from the lowering this path already holds
        # (build-site callers skip their own capture when the AOT cache
        # is on); bucket/steps context from ``extra`` keys the entry the
        # same way the non-AOT sites do
        from ..observability import perf as _perf
        pkey, meta = label, None
        if isinstance(extra, dict):
            meta = dict(extra)
            if "bucket" in extra:
                pkey = f"{label}:b{extra['bucket']}"
        _perf.capture_build(label, lowered=lowered, compiled=compiled,
                            launches=dict(launch_tally) or None,
                            key=pkey, meta=meta)

    entry = cache.get(key)
    if entry is not None:
        hdr, payload = entry
        if hdr.get("kind") == KIND_EXECUTABLE:
            t0 = time.perf_counter()
            try:
                triple = pickle.loads(payload)
                # load onto the devices this program's arguments live
                # on; left to itself the loader takes every local device
                compiled = _se.deserialize_and_load(
                    *triple, execution_devices=_execution_devices(
                        example_args))
                _metrics.AOT_HITS.labels(block=label).inc()
                _metrics.AOT_LOAD_SECONDS.observe(time.perf_counter() - t0)
                _ledger(compiled)
                return _AotExecutable(compiled, jitted, name,
                                      from_cache=True)
            except Exception as e:
                # stale pickle/PJRT mismatch etc: evict + recompile below
                logger.warning("aot: deserialize failed for %s (%s); "
                               "recompiling", label, e)
                _metrics.AOT_ERRORS.labels(kind="deserialize").inc()
                try:
                    os.unlink(cache._entry_path(key))
                except OSError:
                    pass
        else:
            # known-unserializable signature: still a compile (so a miss),
            # but the doomed serialize attempt is skipped
            _metrics.AOT_MISSES.labels(block=label).inc()
            t0 = time.perf_counter()
            compiled = lowered.compile()
            _metrics.AOT_COMPILE_SECONDS.observe(time.perf_counter() - t0)
            _ledger(compiled)
            return _AotExecutable(compiled, jitted, name, from_cache=False)

    _metrics.AOT_MISSES.labels(block=label).inc()
    t0 = time.perf_counter()
    compiled = _compile_to_store(jitted, example_args, lowered)
    _metrics.AOT_COMPILE_SECONDS.observe(time.perf_counter() - t0)
    _ledger(compiled)
    try:
        payload = pickle.dumps(_se.serialize(compiled))
        cache.put(key, payload, kind=KIND_EXECUTABLE, label=label)
    except Exception as e:
        logger.warning("aot: executable for %s is not serializable (%s); "
                       "caching trace signature only", label, e)
        _metrics.AOT_ERRORS.labels(kind="serialize").inc()
        try:
            cache.put(key, b"", kind=KIND_SIGNATURE, label=label,
                      meta={"reason": str(e)[:200]})
        except OSError:
            pass
    return _AotExecutable(compiled, jitted, name, from_cache=False)
