"""The tuned-config layer: the knobs the runtime used to hand-pick.

Every value here was a hard-coded constant somewhere in the tree —
``_GEMV_MAX_M = 64`` in ops/int8_gemv.py, ``DEFAULT_BLOCK = 128`` in
kvstore/quant.py, the serve engine's page size / multi-token K / prefill
chunk / prompt-bucket ladder, the fused-GEMV output-channel block. This
module gives each one a name, a default (the exact current constant), an
env override, and a consult path into the content-addressed tuned-config
cache (:mod:`.cache`), so tools/mxtune.py's measured winners apply
without hand-editing magic numbers.

Resolution order at every consulting site, strongest first:

1. an **explicit caller argument** (never second-guessed),
2. the **env override** ``MXNET_TUNE_<KNOB>`` (operator escape hatch),
3. the **tuned config** whose content-address matches the site's
   workload context (see :func:`cache.config_key`) — a key mismatch is
   not an error, it is the design: a config tuned for other shapes or
   another backend silently does not apply,
4. the **hand-picked default** — with no cache, no activation and no env
   set, every site resolves to exactly the constant it used to hard-code
   (the bitwise-parity contract, pinned by tests/test_tune.py).

Lookups are memoized per key (including negative results), so the consult
path after the first resolution is one dict read — config resolution
happens at build/trace time anyway, never in a steady-state step, which
is what keeps serving ``no_recompile()``-clean with the layer active.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, Optional

from ..base import get_env, logger
from . import cache as _cache

__all__ = [
    "KNOBS", "knob_default", "get_knob", "resolve", "lookup", "activate",
    "deactivate_all", "invalidate", "serve_context", "GLOBAL_SITE",
    "SERVE_SITE",
]

#: site name for process-global knobs (context-free: the key varies only
#: with backend + versions)
GLOBAL_SITE = "global"
#: site name the serving engine consults (context = model dims + pool
#: geometry, see :func:`serve_context`)
SERVE_SITE = "serve"

# knob name -> (site, hand-picked default, regime tags, validator, doc).
# Defaults are literal copies of the constants they replace; the tests
# pin them against the original definitions so they cannot drift apart.
# A default of 0 means "derived" (the site computes the legacy value).
# ``valid`` guards the values a STORED config may carry: a semantically
# broken value (a non-pow2 min bucket, an odd quant block) is dropped
# at lookup — serving degrades to the default instead of crashing in a
# constructor. Explicit caller arguments are deliberately NOT run
# through it (the site's own validation owns the error message).
KNOBS: Dict[str, Dict[str, Any]] = {
    "gemv_max_m": {
        "site": GLOBAL_SITE, "default": 64, "tags": ("bandwidth",),
        "valid": lambda v: v >= 0,
        "doc": "row threshold routing decode-shaped matmuls onto the "
               "weight-only int8 GEMV kernel (ops/int8_gemv._GEMV_MAX_M)"},
    "quant_block": {
        "site": GLOBAL_SITE, "default": 128, "tags": ("bandwidth",),
        "valid": lambda v: v >= 2 and v % 2 == 0,
        "doc": "values per fp32 scale in the block-scaled collective "
               "codecs (kvstore/quant.DEFAULT_BLOCK)"},
    "gemv_int4_block": {
        "site": GLOBAL_SITE, "default": 128, "tags": ("bandwidth",),
        "valid": lambda v: v >= 2 and v % 2 == 0,
        "doc": "values per fp32 scale in the int4 weight-only decode "
               "lane (contrib/quantization bits=4); shares the "
               "kvstore/quant.py block-scaled codec, so the same "
               "even->=2 constraint"},
    "serve_page_size": {
        "site": SERVE_SITE, "default": 16, "tags": ("geometry",),
        "valid": lambda v: v >= 1,
        "doc": "tokens per KV page in the paged serving engine"},
    "serve_multi_token": {
        "site": SERVE_SITE, "default": 1, "tags": ("overhead",),
        "valid": lambda v: v >= 1,
        "doc": "tokens per decode dispatch (the on-device multi-token "
               "loop's K)"},
    "serve_prefill_chunk": {
        "site": SERVE_SITE, "default": 0, "tags": ("overhead",
                                                   "geometry"),
        "valid": lambda v: v >= 0,
        "doc": "tokens per chunked-prefill tick; 0 = one page (the "
               "engine's legacy derivation)"},
    "serve_min_prompt_bucket": {
        "site": SERVE_SITE, "default": 8, "tags": ("geometry",),
        "valid": lambda v: v >= 1 and v & (v - 1) == 0,
        "doc": "smallest prompt-length bucket of the prefill ladder"},
    "serve_bucket_growth": {
        "site": SERVE_SITE, "default": 2, "tags": ("geometry",),
        "valid": lambda v: 2 <= v <= 8,
        "doc": "geometric growth factor of the prompt-bucket ladder "
               "(2 = the legacy power-of-two ladder)"},
    "serve_speculate": {
        "site": SERVE_SITE, "default": 0, "tags": ("overhead",),
        "valid": lambda v: v == 0 or 2 <= v <= 64,
        "doc": "self-speculative spec batch K: tokens per verify "
               "dispatch (current token + K-1 drafts); 0 = off"},
    "serve_spec_draft": {
        "site": SERVE_SITE, "default": 0, "tags": ("overhead",),
        "valid": lambda v: 0 <= v <= 63,
        "doc": "draft tokens proposed per speculative round; 0 = the "
               "full verify width (speculate - 1)"},
    "serve_spec_lookup": {
        "site": SERVE_SITE, "default": 4, "tags": ("overhead",),
        "valid": lambda v: 1 <= v <= 64,
        "doc": "max n-gram length the prompt-lookup draft source "
               "matches against the request's token history"},
    "serve_prefix_advert": {
        "site": SERVE_SITE, "default": 8, "tags": ("overhead",),
        "valid": lambda v: v >= 0,
        "doc": "prefix-cache roots advertised via /healthz for the "
               "router's affinity scoring (top-N by refcount; 0 = no "
               "advert — fleet health polls stay O(N) regardless of "
               "pool size)"},
    "serve_grammar_mask_cache": {
        "site": SERVE_SITE, "default": 64, "tags": ("overhead",),
        "valid": lambda v: v >= 1,
        "doc": "compiled token-mask automata held in the in-memory "
               "content-addressed grammar cache (LRU entries; "
               "serve/grammar.compile_grammar)"},
    "serve_grammar_max_states": {
        "site": SERVE_SITE, "default": 64, "tags": ("geometry",),
        "valid": lambda v: 2 <= v <= 4096,
        "doc": "automaton state AND token-class cap: the per-slot "
               "device table is [max_states, max_states] int32, one "
               "fixed aval for every grammar (the zero-recompile "
               "contract); grammars past the cap fail compile loudly"},
}

# key -> tuned knob dict ({} = resolved miss); memoized so the consult
# path is one dict read after first resolution
_ACTIVE: Dict[str, Dict[str, int]] = {}
_LOCK = threading.Lock()


def knob_default(name: str) -> int:
    return KNOBS[name]["default"]


def _env_override(name: str) -> Optional[int]:
    v = get_env(f"MXNET_TUNE_{name.upper()}", None, dtype=int,
                doc=f"override the tuned/default value of the {name!r} "
                    f"knob: {KNOBS[name]['doc']}")
    if v is None:
        return None
    if not KNOBS[name]["valid"](int(v)):
        # same contract as stored configs (and get_env's own bad-parse
        # path): a semantically invalid override warns and is ignored
        # rather than reaching a kernel/constructor with no guard
        logger.warning("tune: ignoring invalid MXNET_TUNE_%s=%r",
                       name.upper(), v)
        return None
    return int(v)


def _publish_knob(name: str, value: int):
    """mxnet_tune_active_config{site,knob} for one knob that actually
    WON resolution — called from :func:`resolve`/:func:`get_knob` when
    the tuned value is what the site will run with, never from a bare
    lookup (a stored config outranked by an explicit argument or env
    must not report as active)."""
    try:
        from .. import metrics as _metrics
        if _metrics.ENABLED:
            _metrics.TUNE_ACTIVE.labels(site=KNOBS[name]["site"],
                                        knob=name).set(float(value))
    except Exception:
        pass


def lookup(site: str, context: Optional[Dict[str, Any]] = None
           ) -> Dict[str, int]:
    """Tuned knobs for one (site, context), or {} — the defaults apply.

    First call per key consults the cache (hit/miss counters tick there);
    the validated knob dict — or the miss — is memoized until
    :func:`invalidate`. Unknown, non-integer, or validator-failing knobs
    in a stored payload are dropped with a warning rather than applied
    blind (a newer tuner may know knobs this build does not)."""
    cache = _cache.get_cache()
    with _LOCK:
        nothing_tuned = cache is None and not _ACTIVE
    if nothing_tuned:
        # disabled fast path: no content key is computed, so a consult
        # with tuning off never reaches config_key's backend
        # fingerprint — which would initialize the jax platform before
        # a script's own jax.config/XLA_FLAGS override took effect
        return {}
    key = _cache.config_key(site, context)
    with _LOCK:
        if key in _ACTIVE:
            return dict(_ACTIVE[key])
    knobs: Dict[str, int] = {}
    if cache is not None:
        doc = cache.get(key, site=site)
        if doc is not None:
            raw = doc.get("payload", {}).get("knobs", {})
            for k, v in (raw.items() if isinstance(raw, dict) else ()):
                if k in KNOBS and KNOBS[k]["site"] == site \
                        and isinstance(v, int) and not isinstance(v, bool) \
                        and KNOBS[k]["valid"](v):
                    knobs[k] = v
                else:
                    logger.warning("tune: ignoring unknown/ill-typed/"
                                   "invalid knob %r=%r in config %s",
                                   k, v, key[:12])
    with _LOCK:
        _ACTIVE.setdefault(key, knobs)
        knobs = dict(_ACTIVE[key])
    return knobs


def get_knob(name: str, context: Optional[Dict[str, Any]] = None) -> int:
    """Resolve one knob: env override > tuned config > default."""
    env = _env_override(name)
    if env is not None:
        return env
    tuned = lookup(KNOBS[name]["site"], context).get(name)
    if tuned is None:
        return knob_default(name)
    _publish_knob(name, tuned)
    return tuned


def resolve(name: str, explicit: Optional[int],
            tuned: Dict[str, int]) -> int:
    """Consulting-site helper for sites that did one :func:`lookup` for
    several knobs: explicit caller argument > env override > ``tuned``
    > hand-picked default."""
    if explicit is not None:
        return int(explicit)
    env = _env_override(name)
    if env is not None:
        return env
    if name in tuned:
        _publish_knob(name, int(tuned[name]))
        return int(tuned[name])
    return knob_default(name)


def activate(site: str, knobs: Dict[str, int],
             context: Optional[Dict[str, Any]] = None) -> str:
    """Programmatic in-process activation (what mxtune does after a
    search, and what tests use): binds ``knobs`` to the (site, context)
    key without touching disk. Returns the key. The active-config
    gauges appear when a consult actually APPLIES a knob, not here —
    binding is not application (an explicit argument or env can still
    outrank every bound knob)."""
    clean = {k: int(v) for k, v in knobs.items()
             if k in KNOBS and KNOBS[k]["site"] == site
             and KNOBS[k]["valid"](int(v))}
    key = _cache.config_key(site, context)
    with _LOCK:
        _ACTIVE[key] = clean
    return key


def deactivate_all():
    """Drop every activation and memoized lookup (tests; also the path
    to pick up a config written to the cache later in-process)."""
    invalidate()


def invalidate():
    """Forget memoized lookups so the next consult re-reads the cache.
    The active-config gauges clear with them — "absent = the default
    applies" must hold after an eviction/deactivation, not report a
    config that no longer resolves; live configs republish on their
    next lookup."""
    with _LOCK:
        _ACTIVE.clear()
    try:
        from .. import metrics as _metrics
        _metrics.TUNE_ACTIVE.reset()
    except Exception:
        pass


def serve_context(model, max_batch_size: int, max_len: int
                  ) -> Dict[str, Any]:
    """The serving engine's workload context — the aval-shaping facts a
    serve-site tuned config is only valid for. mxtune builds the same
    dict from the same model, so the tuner's winners key-match the
    engines that should consult them (and nothing else)."""
    cfg = getattr(model, "cfg", None)
    return {
        "model": type(model).__name__,
        "hidden": int(getattr(cfg, "hidden_size", 0) or 0),
        "layers": int(getattr(cfg, "num_layers", 0) or 0),
        "heads": int(getattr(cfg, "num_heads", 0) or 0),
        "vocab": int(getattr(cfg, "vocab_size", 0) or 0),
        "max_batch_size": int(max_batch_size),
        "max_len": int(max_len),
    }
