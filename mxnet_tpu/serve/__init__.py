"""mx.serve — production inference serving on the KV-cache decode protocol.

Continuous batching (requests join/leave the batch per step), shape-
bucketed executables (zero recompiles after warmup), admission control
(bounded queue, deadlines, cancellation, graceful drain), full telemetry,
and a stdlib HTTP frontend. See ``engine.py`` for the architecture.

The engine's one cache layout is paged KV, on every backend: it leases
fixed-size cache pages per slot on demand (`paging.py` PagePool ledger)
with copy-on-write shared-prefix caching and chunked prefill; fused
block decode composes with it (the kernel addresses KV through the
block table in-kernel). Self-speculative decoding (``speculate=K``,
`speculate.py` prompt-lookup drafts + exact verify) trades one T=K
forward per host round-trip for 1..K token-exact tokens; the
multi-replica `router.py` fans traffic over N engine replicas with
least-loaded model-aware dispatch and healthz-based eject/rejoin.

The fleet manages itself (`fleet.py` + `registry.py`): an autoscale
controller turns load pressure + SLO error-budget burn into replica
count (hysteresis/cooldown-damped, graceful drains), a ModelRegistry
serves N models off one replica with TenantScheduler WFQ + quotas at
router dispatch, and live weight refresh hot-swaps published checkpoint
versions between decode ticks — no restart, no recompile.

Decoding is grammar-constrainable (`grammar.py`, "mxgrammar"): a JSON
schema or regex compiles to an alphabet-compressed token automaton whose
per-state masks fold into the fused sampling path — completions conform
BY CONSTRUCTION, the per-slot automaton state advances as data (zero
steady-state recompiles), and speculative drafts are pre-constrained so
acceptance never drops on conformant traffic. The HTTP frontend streams
tokens as Server-Sent Events (``stream: true``) and scores sequences in
one prefill-shaped forward (``POST /score``); the router proxies both
with exactly-once failover semantics.

The fleet is cache-aware (`cachefleet.py`, "mxcache"): the router's
prefix-affinity dispatch routes each prompt to the replica already
holding its longest cached prefix (``Router(affinity=True)``),
prefill and decode run as separately-scaled tiers streaming KV pages
over the kvstore wire (PrefillDecodePipeline + TieredFleetController),
and OutOfPages preemptions migrate the victim's pages to the
least-loaded peer and resume there token-exactly
(install_preempt_rescue).

Quickstart::

    import mxnet_tpu as mx
    from mxnet_tpu.serve import InferenceEngine, HTTPFrontend, Router

    engine = InferenceEngine(model, max_batch_size=8, max_len=256,
                             page_size=16)
    engine.start(); engine.warmup()
    res = engine.generate([1, 2, 3], max_new_tokens=16)   # in-process
    HTTPFrontend(engine, port=8000).start()               # or over HTTP
    router = Router(["http://h1:8000", "http://h2:8000"]).start()
"""
from .bucketing import bucket_for, bucket_ladder, next_pow2
from .cachefleet import (PrefillDecodePipeline, TieredFleetController,
                         install_preempt_rescue, migrate_prefix)
from .engine import (InferenceEngine, RequestHandle, ServeResult,
                     QueueFullError, EngineClosedError,
                     STATUS_OK, STATUS_TIMEOUT, STATUS_CANCELLED,
                     STATUS_SHUTDOWN, STATUS_ERROR)
from .fleet import (AutoscalePolicy, FleetController, InProcessSpawner,
                    SubprocessSpawner)
from .grammar import (TokenGrammar, clear_grammar_cache, compile_grammar,
                      schema_regex)
from .http import HTTPFrontend, serve_forever
from .paging import OutOfPages, PagePool, pages_for, prefix_key
from .speculate import constrain_draft, draft_from_history
from .registry import (ModelRegistry, QuotaExceededError, TenantPolicy,
                       TenantScheduler, WeightRefresher,
                       latest_weight_version, publish_from_checkpoint,
                       publish_weights, read_weights, snapshot_params,
                       weight_versions)
from .router import NoBackendError, Router, RouterFrontend

__all__ = [
    "InferenceEngine", "RequestHandle", "ServeResult",
    "QueueFullError", "EngineClosedError",
    "STATUS_OK", "STATUS_TIMEOUT", "STATUS_CANCELLED", "STATUS_SHUTDOWN",
    "STATUS_ERROR",
    "HTTPFrontend", "serve_forever",
    "PagePool", "OutOfPages", "pages_for", "prefix_key",
    "PrefillDecodePipeline", "TieredFleetController",
    "install_preempt_rescue", "migrate_prefix",
    "draft_from_history", "constrain_draft",
    "TokenGrammar", "compile_grammar", "schema_regex",
    "clear_grammar_cache",
    "Router", "RouterFrontend", "NoBackendError",
    "ModelRegistry", "WeightRefresher",
    "publish_weights", "publish_from_checkpoint", "read_weights",
    "snapshot_params", "latest_weight_version", "weight_versions",
    "TenantPolicy", "TenantScheduler", "QuotaExceededError",
    "AutoscalePolicy", "FleetController", "InProcessSpawner",
    "SubprocessSpawner",
    "bucket_for", "bucket_ladder", "next_pow2",
]
