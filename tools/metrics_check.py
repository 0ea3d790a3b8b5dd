"""CI telemetry check: run a tiny train loop, then validate that the
Prometheus exposition parses and the required runtime metrics exist.

Fast tier-1 guard for the observability substrate: if an instrument is
renamed, un-wired, or the exposition format breaks, this trips before any
dashboard or bench regression harness silently reads nothing.

Usage::

    JAX_PLATFORMS=cpu python tools/metrics_check.py

Prints one JSON line and exits non-zero on failure. ``run_check()`` is
importable for the in-process pytest wiring (tests/test_telemetry.py).
"""
from __future__ import annotations

import json
import os
import re
import sys

# metric families every build must expose after one tiny train loop
REQUIRED_METRICS = (
    "mxnet_op_dispatch_total",
    "mxnet_op_dispatch_seconds",
    "mxnet_recompilations_total",
    "mxnet_step_time_seconds",
    "mxnet_examples_total",
    "mxnet_dataloader_batch_seconds",
    "mxnet_hbm_bytes_in_use",
    "mxnet_profiler_dropped_events_total",
)

# families the async execution pipeline must expose after one pipelined
# train loop + async checkpoint save (run_pipeline_check)
REQUIRED_PIPELINE_METRICS = (
    "mxnet_input_wait_seconds",
    "mxnet_pipeline_depth",
    "mxnet_checkpoint_stall_seconds",
    "mxnet_serve_host_sync_seconds",
)

# families the fused/multi-token decode path must expose after one engine
# round (run_decode_check)
REQUIRED_DECODE_METRICS = (
    "mxnet_decode_launches_total",
    "mxnet_serve_host_roundtrips_total",
)

# families the self-speculative decode path must expose after one
# draft-verify serving round (run_spec_check)
REQUIRED_SPEC_METRICS = (
    "mxnet_spec_drafted_tokens_total",
    "mxnet_spec_accepted_tokens_total",
    "mxnet_spec_rejected_tokens_total",
    "mxnet_spec_rounds_total",
    "mxnet_spec_acceptance_rate",
)

# families the grammar-constrained decode path must expose after one
# constrained serving round + a mask-cache round-trip (run_grammar_check)
REQUIRED_GRAMMAR_METRICS = (
    "mxnet_grammar_sessions_total",
    "mxnet_grammar_mask_cache_hits_total",
    "mxnet_grammar_mask_cache_misses_total",
    "mxnet_grammar_rejected_tokens_total",
    "mxnet_grammar_compile_seconds",
)

# families the paged KV engine must expose after one shared-prefix
# serving round (run_paging_check)
REQUIRED_PAGING_METRICS = (
    "mxnet_serve_page_pool_pages",
    "mxnet_serve_page_in_use",
    "mxnet_serve_page_leases_total",
    "mxnet_serve_page_cow_forks_total",
    "mxnet_serve_page_folds_total",
    "mxnet_serve_window_pages_recycled_total",
    "mxnet_serve_moe_assignments_total",
    "mxnet_serve_page_preemptions_total",
    "mxnet_serve_page_prefix_hits_total",
    "mxnet_serve_page_prefix_misses_total",
    "mxnet_serve_page_prefix_tokens_saved_total",
    "mxnet_serve_page_prefix_bytes_saved_total",
    "mxnet_serve_page_prefix_collisions_total",
    "mxnet_serve_page_prefill_chunks_total",
)

# families the multi-replica router must expose after one routed round
# with a drain (run_paging_check)
REQUIRED_ROUTER_METRICS = (
    "mxnet_router_dispatch_total",
    "mxnet_router_ejects_total",
    "mxnet_router_rejoins_total",
    "mxnet_router_retries_total",
    "mxnet_router_rebalances_total",
    "mxnet_router_backends_healthy",
)

# families the self-managing fleet must expose after one controller
# round (scale up + down), a saturated WFQ window, and a live weight
# swap (run_fleet_check)
REQUIRED_FLEET_METRICS = (
    "mxnet_fleet_replicas",
    "mxnet_fleet_scale_events_total",
    "mxnet_fleet_decisions_suppressed_total",
    "mxnet_fleet_pressure",
    "mxnet_fleet_controller_ticks_total",
    "mxnet_fleet_spawn_seconds",
    "mxnet_fleet_tenant_dispatch_total",
    "mxnet_fleet_tenant_inflight",
    "mxnet_fleet_tenant_queue_wait_seconds",
    "mxnet_fleet_tenant_rejected_total",
    "mxnet_serve_weight_version",
    "mxnet_serve_weight_swaps_total",
)

# families the cache-aware fleet must expose after one affinity-routed
# round + a page-migration round-trip + a tiered scale decision
# (run_cache_check)
REQUIRED_CACHE_METRICS = (
    "mxnet_cache_affinity_dispatch_total",
    "mxnet_cache_affinity_hit_tokens_total",
    "mxnet_cache_advert_roots",
    "mxnet_migrate_pages_sent_total",
    "mxnet_migrate_pages_received_total",
    "mxnet_migrate_verify_failures_total",
    "mxnet_fleet_tier_replicas",
    "mxnet_fleet_tier_scale_events_total",
)

# families the ZeRO sharded weight update must expose after a few
# compressed zero=2 steps (run_zero_check)
REQUIRED_ZERO_METRICS = (
    "mxnet_zero_shards",
    "mxnet_zero_opt_state_bytes",
    "mxnet_zero_residual_l2",
    "mxnet_collective_calls_total",
    "mxnet_collective_bytes_total",
)

# families the observability layer must expose after one traced serving
# round + a flight-recorder dump (run_trace_check)
REQUIRED_TRACE_METRICS = (
    "mxnet_trace_spans_total",
    "mxnet_trace_spans_dropped_total",
    "mxnet_flight_recorder_dumps_total",
    "mxnet_step_phase_seconds",
    "mxnet_step_overlap_fraction",
    "mxnet_slo_target_seconds",
    "mxnet_slo_p99_seconds",
    "mxnet_slo_violations_total",
    "mxnet_slo_error_budget_burn",
)

# the span names one complete request tree must contain (paged engine:
# chunked prefill makes the prefill_chunk spans deterministic)
REQUIRED_REQUEST_SPANS = (
    "serve.request", "serve.queue", "serve.prefill",
    "serve.prefill_chunk", "serve.decode_chunk",
)

# families the cost ledger + live roofline must expose after one jitted
# train step and one serve bucket-ladder warmup (run_perf_check)
REQUIRED_PERF_METRICS = (
    "mxnet_executable_flops",
    "mxnet_executable_hbm_bytes",
    "mxnet_executable_peak_bytes",
    "mxnet_mfu",
    "mxnet_hbm_util_fraction",
)

# families the elastic runtime must expose after one simulated
# kill-a-worker drill (run_elastic_check)
REQUIRED_ELASTIC_METRICS = (
    "mxnet_elastic_heartbeats_total",
    "mxnet_elastic_heartbeat_age_seconds",
    "mxnet_elastic_peer_lost_total",
    "mxnet_elastic_epoch",
    "mxnet_elastic_world_size",
    "mxnet_elastic_reforms_total",
    "mxnet_elastic_phase_seconds",
    "mxnet_flight_recorder_dumps_total",
)

# families the autotuning layer must expose after one search + one
# cache round-trip + one corrupt-entry fallback (run_tune_check)
REQUIRED_TUNE_METRICS = (
    "mxnet_tune_trials_total",
    "mxnet_tune_cache_hits_total",
    "mxnet_tune_cache_misses_total",
    "mxnet_tune_cache_errors_total",
    "mxnet_tune_active_config",
)

# families the numeric-health telemetry must expose after a short
# health-on train loop with one poisoned batch plus a few AMP scaler
# calibration rounds (run_health_check)
REQUIRED_HEALTH_METRICS = (
    "mxnet_health_nonfinite",
    "mxnet_health_norm",
    "mxnet_health_loss",
    "mxnet_health_zscore",
    "mxnet_health_anomalies_total",
    "mxnet_health_last_anomaly_step",
    "mxnet_health_layer_maxabs",
    "mxnet_health_layer_rms",
    "mxnet_amp_scale",
    "mxnet_amp_skipped_steps_total",
    "mxnet_amp_scale_adjustments_total",
)

# families the persistent AOT compile cache must expose after one
# store-then-restore cycle (run_aot_check)
REQUIRED_AOT_METRICS = (
    "mxnet_aot_cache_hits_total",
    "mxnet_aot_cache_misses_total",
    "mxnet_aot_cache_errors_total",
    "mxnet_aot_cache_bytes",
    "mxnet_aot_load_seconds",
    "mxnet_aot_compile_seconds",
    "mxnet_aot_warmup_seconds",
)

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'              # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'  # first label
    r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'  # more labels
    r' (-?(?:[0-9.e+-]+|\+Inf|-Inf|NaN))$')
_TYPE_RE = re.compile(r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
                      r"(counter|gauge|histogram|summary|untyped)$")
_HELP_RE = re.compile(r"^# HELP ([a-zA-Z_:][a-zA-Z0-9_:]*) .*$")


def parse_exposition(text: str):
    """Strict-enough parser for the Prometheus text format: every line must
    be blank, # HELP, # TYPE, or a sample whose name resolves to a declared
    family (histograms via _bucket/_sum/_count). Returns
    {family: {"type": t, "samples": n}}; raises ValueError on any bad line."""
    families = {}

    def family_of(name: str):
        if name in families:
            return name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in families:
                return name[:-len(suffix)]
        return None

    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        m = _TYPE_RE.match(line)
        if m:
            families[m.group(1)] = {"type": m.group(2), "samples": 0}
            continue
        if _HELP_RE.match(line):
            continue
        if line.startswith("#"):
            raise ValueError(f"line {lineno}: bad comment line {line!r}")
        m = _SAMPLE_RE.match(line)
        if m is None:
            raise ValueError(f"line {lineno}: unparseable sample {line!r}")
        fam = family_of(m.group(1))
        if fam is None:
            raise ValueError(
                f"line {lineno}: sample {m.group(1)!r} has no # TYPE")
        float(m.group(3).replace("+Inf", "inf").replace("-Inf", "-inf"))
        families[fam]["samples"] += 1
    return families


def run_check():
    """Tiny hybridized train loop under enabled metrics, then validate the
    exposition. Returns a summary dict; raises on any failure."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, metrics, np
    from mxnet_tpu.gluon import Trainer, nn
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    from mxnet_tpu.gluon.loss import L2Loss

    was_enabled = metrics.enabled()
    metrics.reset()
    metrics.enable()
    try:
        net = nn.HybridSequential()
        net.add(nn.Dense(8, in_units=4), nn.Dense(2))
        net.initialize()
        net.hybridize()
        trainer = Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1})
        loss_fn = L2Loss()
        rng = onp.random.RandomState(0)
        ds = ArrayDataset(np.array(rng.rand(8, 4).astype("float32")),
                          np.array(rng.rand(8, 2).astype("float32")))
        for x, y in DataLoader(ds, batch_size=4):
            with autograd.record():
                loss = loss_fn(net(x), y).mean()
            loss.backward()
            trainer.step(4)
        # shape change: must register as one more recompilation
        x2 = np.array(rng.rand(2, 4).astype("float32"))
        net(x2)

        text = metrics.expose()
        families = parse_exposition(text)
        missing = [m for m in REQUIRED_METRICS if m not in families]
        if missing:
            raise AssertionError(f"missing required metrics: {missing}")
        empty = [m for m in REQUIRED_METRICS
                 if families[m]["samples"] == 0
                 and families[m]["type"] != "counter"]
        if empty:
            raise AssertionError(f"required metrics have no samples: {empty}")
        doc = json.loads(metrics.dumps(format="json"))
        recompiles = metrics.get_sample_value("mxnet_recompilations_total")
        if not recompiles:
            raise AssertionError("no recompilation events recorded")
        retraces = metrics.get_sample_value(
            "mxnet_recompilations_total", {"kind": "retrace"})
        if not retraces:
            raise AssertionError("shape change did not record a retrace")
        steps = metrics.get_sample_value("mxnet_step_time_seconds_count",
                                         {"path": "trainer"})
        if steps != 2:
            raise AssertionError(f"expected 2 trainer steps, saw {steps}")
        mx.waitall()
        return {
            "ok": True,
            "families": len(families),
            "exposition_bytes": len(text),
            "json_metrics": len(doc),
            "recompilations": recompiles,
            "retraces": retraces,
            "trainer_steps": steps,
        }
    finally:
        if not was_enabled:
            metrics.disable()


def run_perf_check(chip=None):
    """One jitted train step + one serve bucket-ladder warmup under the
    cost ledger (observability/perf), then validate: every executable
    class built here has a ledger entry (TrainStep, every prefill/decode
    bucket), the ``mxnet_executable_*`` gauges expose its XLA costs, the
    live ``mxnet_mfu{path=train_step}`` gauge equals the ledger-FLOPs /
    last-step-time / chip-peak arithmetic bench.py's offline ``_mfu``
    uses (same flops source, same denominator), steady-state steps
    compile nothing under the ``no_recompile()`` guard (ledger capture
    is compile-time only), and the JSON dump/exposition parse. Returns
    a summary dict; raises on any failure.

    ``chip`` names the ``perf.PEAKS`` entry both sides of the arithmetic
    check divide by, for a run on a device that has no peaks on record
    (the CPU this CI tool usually runs on — perf itself refuses to invent
    them). The check is of agreement between gauge and ledger, not of a
    speed."""
    import time as _time

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import metrics, np, parallel
    from mxnet_tpu.analysis import guards
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import L2Loss
    from mxnet_tpu.models import GPTModel
    from mxnet_tpu.models.gpt import GPTConfig
    from mxnet_tpu.observability import perf
    from mxnet_tpu.serve import InferenceEngine
    from mxnet_tpu.serve.bucketing import bucket_ladder

    was_enabled = metrics.enabled()
    was_perf = perf.active()
    kind_was = perf._CHIP_GEN
    metrics.reset()
    metrics.enable()
    perf.reset()
    perf.enable()
    if chip is not None:
        perf._CHIP_GEN = chip
    try:
        # --- train: tiny fused TrainStep (compile = ledger capture) ---
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, in_units=16), nn.Dense(4))
        net.initialize()
        rng = onp.random.RandomState(0)
        x = np.array(rng.rand(8, 16).astype("float32"))
        y = np.array(rng.rand(8, 4).astype("float32"))
        step = parallel.TrainStep(
            net, L2Loss(), mx.optimizer.SGD(learning_rate=0.1),
            example_inputs=[x])
        step(x, y).item()              # compile + capture
        t0 = _time.perf_counter()
        with guards.no_recompile():    # capture happens at compile ONLY
            for _ in range(3):
                step(x, y).item()
        wall_3 = _time.perf_counter() - t0

        entry = perf.LEDGER.get("train_step")
        if entry is None or entry.flops <= 0 or entry.hbm_bytes <= 0:
            raise AssertionError(
                f"train_step ledger entry missing/empty: "
                f"{entry and entry.to_dict()}")
        ca = step.cost_analysis() or {}
        if abs(entry.flops - float(ca.get("flops", 0.0))) > \
                0.05 * max(entry.flops, 1.0):
            raise AssertionError(
                f"ledger flops {entry.flops} disagree with "
                f"cost_analysis {ca.get('flops')}")

        # --- serve: tiny GPT bucket ladder (one entry per bucket) ---
        # the SMALLEST model/ladder that still exercises per-bucket
        # ledger keys (2 prefill + 1 decode buckets, and the page copy,
        # extract and inject; one page, so no chunk program): every extra
        # bucket is a compile + capture lowering on the tier-1 clock
        net2 = GPTModel(GPTConfig(
            vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
            max_position_embeddings=32, dropout=0.0))
        net2.initialize()
        # two pages: the request below forks its prompt's page, which the
        # prefix cache shares, at its first decode write; the default pool
        # of this geometry is that one page and would preempt instead
        eng = InferenceEngine(net2, max_batch_size=1, max_len=16,
                              num_pages=2)
        eng.warmup()
        # enumerate via the engine's RESOLVED knobs (min bucket/growth
        # may come from MXNET_TUNE_* env or a tuned config — recomputing
        # at the defaults would false-fail the check under operator env)
        expect = ([f"serve_prefill:b{pb}"
                   for pb in bucket_ladder(eng.min_prompt_bucket,
                                           eng._chunk, eng._growth)]
                  + ([f"serve_chunk:b{eng._chunk}"]
                     if eng._chunk < eng.L else [])
                  + ["serve_copy:b0", "serve_extract:b0",
                     "serve_inject:b0"]
                  + [f"serve_decode:b{sb}"
                     for sb in bucket_ladder(1, eng.S)])
        missing_entries = [k for k in expect if perf.LEDGER.get(k) is None]
        if missing_entries:
            raise AssertionError(
                f"serve ladder entries missing from the cost ledger: "
                f"{missing_entries}")
        eng.start()
        try:
            res = eng.submit(rng.randint(1, 63, size=6).astype(onp.int32),
                             3).result(120)
        finally:
            eng.shutdown()
        if res.status != "ok":
            raise AssertionError(f"perf-check request failed: {res}")

        # --- memory stats on demand; peak gauge must go nonzero.
        # complete() one entry, not complete_all(): each completion is a
        # real XLA compile and the tier-1 budget pays for it ---
        perf.LEDGER.complete("train_step")
        peak_b = metrics.get_sample_value("mxnet_executable_peak_bytes",
                                          {"block": "train_step"})
        if not peak_b:
            raise AssertionError(
                "mxnet_executable_peak_bytes{block=train_step} is zero "
                "after complete_all()")

        # --- exposition + gauge arithmetic ---
        text = metrics.expose()
        families = parse_exposition(text)
        missing = [m for m in REQUIRED_PERF_METRICS if m not in families]
        if missing:
            raise AssertionError(f"missing perf metrics: {missing}")
        g_flops = metrics.get_sample_value("mxnet_executable_flops",
                                           {"block": "train_step"})
        if g_flops != entry.flops:
            raise AssertionError(
                f"flops gauge {g_flops} != ledger {entry.flops}")
        live = metrics.get_sample_value("mxnet_mfu",
                                        {"path": "train_step"})
        roof = perf.summary().get("train_step")
        if roof is None or not live:
            raise AssertionError(
                f"no live train_step roofline (gauge={live}, "
                f"summary={roof})")
        offline = entry.flops / roof["dt_s"] / perf.chip_peak_flops()
        if abs(live - offline) / offline > 0.10:
            raise AssertionError(
                f"live mfu {live} disagrees with the offline "
                f"flops/dt/peak arithmetic {offline} by > 10%")
        # sanity-bound the note's dt against an independent wall clock
        # (unit errors — ms vs s, per-N vs per-step — explode this
        # ratio; scheduler noise does not reach 25x on 3 steps)
        if not (wall_3 / 3 / 25 <= roof["dt_s"] <= wall_3 * 25):
            raise AssertionError(
                f"step-note dt {roof['dt_s']} implausible vs measured "
                f"{wall_3 / 3} s/step")
        decode_roof = perf.summary().get("serve_decode")
        if decode_roof is None or decode_roof["regime"] == "unknown":
            raise AssertionError(
                f"no serve_decode roofline verdict: {decode_roof}")
        doc = perf.dump()
        if not doc["entries"] or "roofline" not in doc:
            raise AssertionError("perf.dump() missing entries/roofline")
        mx.waitall()
        return {"ok": True,
                "ledger_entries": len(doc["entries"]),
                "train_flops": entry.flops,
                "train_peak_bytes": peak_b,
                "mfu_live": live,
                "mfu_offline": offline,
                "serve_buckets": len(expect),
                "decode_regime": decode_roof["regime"]}
    finally:
        perf._CHIP_GEN = kind_was
        if not was_perf:
            perf.disable()
        perf.reset()
        if not was_enabled:
            metrics.disable()


def run_tune_check():
    """One mxtune search on the deterministic synthetic surface plus one
    tuned-config cache round-trip (store -> consult hit -> corrupt ->
    self-evict to defaults), then validate the ``mxnet_tune_*``
    families: trial counts per workload, cache hits/misses, the corrupt-
    entry error counter, and the active-config gauges reflecting the
    applied knobs. Pure python — no jax program is built. Returns a
    summary dict; raises on any failure."""
    import argparse
    import importlib.util
    import shutil
    import tempfile

    from mxnet_tpu import metrics, tune

    was_enabled = metrics.enabled()
    prev_cache = tune.get_cache()
    metrics.reset()
    metrics.enable()
    tune.deactivate_all()
    tmpdir = tempfile.mkdtemp(prefix="mxnet-tune-check-")
    try:
        cache = tune.enable(tmpdir)

        # --- search: the mxtune CLI's OWN synthetic workload (imported,
        # not re-implemented — the check and the CLI surface must not
        # drift apart), optimum K=4 / chunk=32 ---
        spec = importlib.util.spec_from_file_location(
            "mxtune", os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "mxtune.py"))
        mxtune = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mxtune)
        measure, space, defaults, _ctx, _site = \
            mxtune.synthetic_workload(argparse.Namespace(seed=0))
        report = tune.search(measure, space, defaults,
                             seed=0, workload="synthetic")
        if report["best"] != {"serve_multi_token": 4,
                              "serve_prefill_chunk": 32}:
            raise AssertionError(
                f"synthetic search missed the optimum: {report['best']}")
        trials = metrics.get_sample_value("mxnet_tune_trials_total",
                                          {"workload": "synthetic"})
        if trials != len(report["trials"]):
            raise AssertionError(
                f"trial counter {trials} != trials run "
                f"{len(report['trials'])}")

        # --- cache round-trip: store the winner, consult it back ---
        ctx = {"workload": "tune-check"}
        key = tune.config_key(tune.SERVE_SITE, ctx)
        cache.put(key, tune.SERVE_SITE,
                  {"knobs": report["best"], "context": ctx}, label="check")
        tune.invalidate()
        knobs = tune.lookup(tune.SERVE_SITE, ctx)
        if knobs != report["best"]:
            raise AssertionError(f"cache round-trip mismatch: {knobs}")
        hits = metrics.get_sample_value("mxnet_tune_cache_hits_total",
                                        {"site": "serve"})
        if not hits:
            raise AssertionError("consult hit did not count")
        # the active-config gauge appears on APPLICATION (a resolution
        # returning the tuned value), not on the bare lookup above
        if tune.get_knob("serve_multi_token", ctx) != 4:
            raise AssertionError("tuned knob did not resolve")
        active_k = metrics.get_sample_value(
            "mxnet_tune_active_config",
            {"site": "serve", "knob": "serve_multi_token"})
        if active_k != 4.0:
            raise AssertionError(
                f"active-config gauge reads {active_k}, want 4.0")

        # --- key mismatch is a miss; defaults apply ---
        tune.invalidate()
        other = tune.lookup(tune.SERVE_SITE, {"workload": "elsewhere"})
        if other != {}:
            raise AssertionError(f"key mismatch leaked a config: {other}")
        misses = metrics.get_sample_value("mxnet_tune_cache_misses_total",
                                          {"site": "serve"})
        if not misses:
            raise AssertionError("key-mismatch miss did not count")

        # --- corruption self-evicts to defaults ---
        with open(cache._entry_path(key), "w") as f:
            f.write("{ not json")
        tune.invalidate()
        if tune.lookup(tune.SERVE_SITE, ctx) != {}:
            raise AssertionError("corrupt entry did not fall back to "
                                 "defaults")
        errors = metrics.get_sample_value("mxnet_tune_cache_errors_total",
                                          {"kind": "corrupt"})
        if not errors:
            raise AssertionError("corrupt entry did not count an error")
        if os.path.exists(cache._entry_path(key)):
            raise AssertionError("corrupt entry was not evicted")

        text = metrics.expose()
        families = parse_exposition(text)
        missing = [m for m in REQUIRED_TUNE_METRICS if m not in families]
        if missing:
            raise AssertionError(f"missing tune metrics: {missing}")
        return {"ok": True,
                "trials": trials,
                "best": report["best"],
                "improvement": report["improvement"],
                "cache_hits": hits,
                "cache_misses": misses,
                "corrupt_evictions": errors}
    finally:
        if prev_cache is not None:
            tune.enable(prev_cache.path)
        else:
            tune.disable()
        tune.deactivate_all()
        if not was_enabled:
            metrics.disable()
        shutil.rmtree(tmpdir, ignore_errors=True)


def run_aot_check():
    """One store-then-restore cycle through the persistent AOT cache in a
    temp dir, then validate the ``mxnet_aot_*`` families: a miss + store
    on the first compile, a hit on the rebuild, non-zero cache bytes, and
    a parseable exposition. Returns a summary dict; raises on failure."""
    import shutil
    import tempfile

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import aot, metrics, np
    from mxnet_tpu.gluon import nn

    was_enabled = metrics.enabled()
    prev_cache = aot.get_cache()
    metrics.reset()
    metrics.enable()
    tmpdir = tempfile.mkdtemp(prefix="mxnet-aot-check-")
    try:
        aot.enable(tmpdir)

        def build():
            mx.random.seed(0)
            net = nn.HybridSequential()
            net.add(nn.Dense(8, in_units=4), nn.Dense(2))
            net.initialize()
            net.hybridize()
            return net

        x = np.array(onp.random.RandomState(0).rand(4, 4)
                     .astype("float32"))
        y1 = build()(x).asnumpy()
        y2 = build()(x).asnumpy()  # fresh CachedOp -> disk restore
        if not (y1 == y2).all():
            raise AssertionError("AOT-restored executable diverged from "
                                 "fresh compile")

        text = metrics.expose()
        families = parse_exposition(text)
        missing = [m for m in REQUIRED_AOT_METRICS if m not in families]
        if missing:
            raise AssertionError(f"missing AOT metrics: {missing}")
        hits = metrics.get_sample_value("mxnet_aot_cache_hits_total")
        misses = metrics.get_sample_value("mxnet_aot_cache_misses_total")
        nbytes = metrics.get_sample_value("mxnet_aot_cache_bytes")
        if not misses:
            raise AssertionError("first compile did not record an AOT miss")
        if not hits:
            raise AssertionError("rebuild did not record an AOT hit")
        if not nbytes:
            raise AssertionError("AOT cache bytes gauge is zero after a "
                                 "store")
        mx.waitall()
        return {"ok": True, "aot_hits": hits, "aot_misses": misses,
                "aot_cache_bytes": nbytes}
    finally:
        if prev_cache is not None:
            aot.enable(prev_cache.path, max_bytes=prev_cache.max_bytes)
        else:
            aot.disable()
        if not was_enabled:
            metrics.disable()
        shutil.rmtree(tmpdir, ignore_errors=True)


def run_pipeline_check():
    """One pipelined train loop (DevicePrefetcher + TrainStep in-flight
    window) bitwise-checked against the synchronous loop, plus an async
    CheckpointManager save, then validate the pipeline metric families.
    Returns a summary dict; raises on any failure."""
    import shutil
    import tempfile

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import metrics, np, parallel
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    from mxnet_tpu.gluon.loss import L2Loss

    was_enabled = metrics.enabled()
    metrics.reset()
    metrics.enable()
    tmpdir = tempfile.mkdtemp(prefix="mxnet-pipeline-check-")
    try:
        rng = onp.random.RandomState(0)
        X = rng.rand(16, 4).astype("float32")
        Y = rng.rand(16, 2).astype("float32")

        def run(pipelined):
            mx.random.seed(0)
            net = nn.HybridSequential()
            net.add(nn.Dense(8, in_units=4), nn.Dense(2))
            net.initialize()
            step = parallel.TrainStep(
                net, L2Loss(), mx.optimizer.SGD(learning_rate=0.1),
                example_inputs=[np.array(X[:4])],
                block_every=2 if pipelined else None)
            loader = DataLoader(ArrayDataset(np.array(X), np.array(Y)),
                                batch_size=4)
            losses = []
            if pipelined:
                for x, y in loader.as_device_iterator(depth=2):
                    losses.append(step.step(x, y))
                step.drain()
            else:
                for x, y in loader:
                    loss = step(x, y)
                    loss.item()          # the per-step sync being removed
                    losses.append(loss)
            return ([loss.asnumpy() for loss in losses],
                    [onp.asarray(v) for v in step.model.values()], net)

        sync_l, sync_p, _ = run(False)
        pipe_l, pipe_p, net = run(True)
        if not all((a == b).all() for a, b in zip(sync_l, pipe_l)):
            raise AssertionError("pipelined loop losses diverged from the "
                                 "synchronous loop")
        if not all((a == b).all() for a, b in zip(sync_p, pipe_p)):
            raise AssertionError("pipelined loop params diverged from the "
                                 "synchronous loop")

        mgr = CheckpointManager(tmpdir, net=net)
        mgr.save(0, blocking=False)
        mgr.wait()
        if mgr.latest() != 0:
            raise AssertionError("async checkpoint save did not land")

        text = metrics.expose()
        families = parse_exposition(text)
        missing = [m for m in REQUIRED_PIPELINE_METRICS
                   if m not in families]
        if missing:
            raise AssertionError(f"missing pipeline metrics: {missing}")
        waits = metrics.get_sample_value("mxnet_input_wait_seconds_count")
        if not waits:
            raise AssertionError("DevicePrefetcher recorded no input waits")
        stalls = metrics.get_sample_value(
            "mxnet_checkpoint_stall_seconds_count")
        if not stalls:
            raise AssertionError("async save recorded no checkpoint stall")
        mx.waitall()
        return {"ok": True, "input_waits": waits, "ckpt_stalls": stalls,
                "bitwise_parity": True}
    finally:
        if not was_enabled:
            metrics.disable()
        shutil.rmtree(tmpdir, ignore_errors=True)


def run_decode_check():
    """Two multi-token serving rounds on tiny quantized GPTs (int8, then
    int4 weights), then validate the decode metric families: launch
    sites recorded at trace time (mxnet_decode_launches_total — off-TPU
    every GEMV and head site runs its XLA reference and says so with
    kind=reference; on a TPU the same sites count gemv / gemv_int4 /
    fused_head) and host round-trips strictly fewer than decode tokens
    (the K-tokens-per-round-trip overlap). Returns a summary dict;
    raises on failure."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import metrics, np
    from mxnet_tpu.contrib.quantization import quantize_net
    from mxnet_tpu.device import on_tpu
    from mxnet_tpu.models import GPTModel
    from mxnet_tpu.models.gpt import GPTConfig
    from mxnet_tpu.serve import InferenceEngine

    was_enabled = metrics.enabled()
    metrics.reset()
    metrics.enable()

    def mk_net(bits=8):
        mx.random.seed(0)
        net = GPTModel(GPTConfig(vocab_size=256, hidden_size=128,
                                 num_layers=2, num_heads=4,
                                 max_position_embeddings=64, dropout=0.0))
        net.initialize()
        net(np.array(onp.zeros((1, 4), "int32")))
        quantize_net(net, calib_mode="none", bits=bits)
        return net

    def serve(net, **engine_kw):
        rng = onp.random.RandomState(0)
        prompts = [rng.randint(1, 250, size=rng.randint(3, 9))
                   .astype(onp.int32) for _ in range(4)]
        eng = InferenceEngine(net, max_batch_size=2, multi_token=K,
                              **engine_kw).start()
        try:
            results = [h.result(300) for h in
                       [eng.submit(p, 5 + i) for i, p in
                        enumerate(prompts)]]
        finally:
            eng.shutdown()
        if not all(r.status == "ok" for r in results):
            raise AssertionError(
                f"decode check requests failed: "
                f"{[(r.status, r.error) for r in results]}")
        return len(prompts)

    def sites(kind):
        return metrics.get_sample_value("mxnet_decode_launches_total",
                                        {"kind": kind}) or 0

    try:
        K = 3
        n_prompts = serve(mk_net(), max_len=32)
        serve(mk_net(bits=4), max_len=32)

        text = metrics.expose()
        families = parse_exposition(text)
        missing = [m for m in REQUIRED_DECODE_METRICS if m not in families]
        if missing:
            raise AssertionError(f"missing decode metrics: {missing}")
        kernel_sites = {k: sites(k)
                        for k in ("gemv", "gemv_int4", "fused_head")}
        reference = sites("reference")
        if on_tpu():
            # (the packed-int4 head has no kernel: it counts reference)
            if not all(kernel_sites.values()):
                raise AssertionError(
                    "on a TPU the decode sites run their kernels: "
                    f"{kernel_sites}, reference={reference}")
        elif any(kernel_sites.values()) or not reference:
            raise AssertionError(
                "off-TPU every decode site runs its XLA reference and "
                f"must count as kind=reference: {kernel_sites}, "
                f"reference={reference}")
        rts = metrics.get_sample_value("mxnet_serve_host_roundtrips_total",
                                       {"path": "decode"}) or 0
        toks = metrics.get_sample_value("mxnet_serve_tokens_total") or 0
        # tok0s come from prefill; 2 rounds x n_prompts requests
        decode_toks = toks - 2 * n_prompts
        if not rts:
            raise AssertionError("no decode host round-trips recorded")
        if rts >= decode_toks:
            raise AssertionError(
                f"multi-token overlap invisible: {rts} round-trips for "
                f"{decode_toks} decode tokens")
        return {"ok": True, "multi_token": K,
                "kernel_sites": kernel_sites, "reference_sites": reference,
                "decode_roundtrips": rts, "decode_tokens": decode_toks}
    finally:
        if not was_enabled:
            metrics.disable()


def run_spec_check():
    """One self-speculative paged serving round (speculate=K draft-
    verify) on a tiny GPT over repetitive traffic, then validate the
    ``mxnet_spec_*`` families: drafted/accepted/rejected token counters
    that balance exactly (accepted + rejected == drafted), a round
    counter, and the acceptance-rate gauge whose value IS
    accepted/drafted — plus the token-exactness spot check against a
    speculate=0 engine (speculation must never change output). Returns
    a summary dict; raises on failure."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import metrics, np
    from mxnet_tpu.models import GPTModel
    from mxnet_tpu.models.gpt import GPTConfig
    from mxnet_tpu.serve import InferenceEngine

    was_enabled = metrics.enabled()
    metrics.reset()
    metrics.enable()
    try:
        K = 4
        mx.random.seed(0)
        net = GPTModel(GPTConfig(vocab_size=128, hidden_size=32,
                                 num_layers=2, num_heads=2,
                                 max_position_embeddings=128, dropout=0.0))
        net.initialize()
        net(np.array(onp.zeros((1, 4), "int32")))
        rng = onp.random.RandomState(0)
        boiler = int(rng.randint(1, 120))
        prompts = [onp.asarray([boiler] * 8 + [int(rng.randint(1, 120))],
                               onp.int32) for _ in range(4)]

        def serve(spec):
            # explicit speculate (even 0): the token-exactness check
            # must compare against a REALLY non-speculative baseline
            # even when a tuned serve_speculate winner is active
            eng = InferenceEngine(net, max_batch_size=2, max_len=64,
                                  page_size=8,
                                  speculate=spec).start()
            try:
                return [list(eng.generate(p, 12).generated_ids)
                        for p in prompts]
            finally:
                eng.shutdown()

        spec_out = serve(K)
        base_out = serve(0)
        if spec_out != base_out:
            raise AssertionError(
                "speculative output diverged from speculate=0 (the "
                "token-exactness contract)")

        text = metrics.expose()
        families = parse_exposition(text)
        missing = [m for m in REQUIRED_SPEC_METRICS if m not in families]
        if missing:
            raise AssertionError(f"missing spec metrics: {missing}")
        drafted = metrics.get_sample_value(
            "mxnet_spec_drafted_tokens_total") or 0
        accepted = metrics.get_sample_value(
            "mxnet_spec_accepted_tokens_total") or 0
        rejected = metrics.get_sample_value(
            "mxnet_spec_rejected_tokens_total") or 0
        rounds = metrics.get_sample_value("mxnet_spec_rounds_total") or 0
        rate = metrics.get_sample_value("mxnet_spec_acceptance_rate")
        if not drafted or not rounds:
            raise AssertionError(
                f"no speculative activity recorded (drafted={drafted}, "
                f"rounds={rounds})")
        if accepted + rejected != drafted:
            raise AssertionError(
                f"spec counters do not balance: accepted={accepted} + "
                f"rejected={rejected} != drafted={drafted}")
        if rate is None or abs(rate - accepted / drafted) > 1e-6:
            raise AssertionError(
                f"acceptance-rate gauge {rate} != accepted/drafted "
                f"{accepted / drafted}")
        return {"ok": True, "speculate": K, "rounds": rounds,
                "drafted": drafted, "accepted": accepted,
                "acceptance_rate": rate}
    finally:
        if not was_enabled:
            metrics.disable()


def run_grammar_check():
    """One grammar-constrained serving round (speculate=K so the lookup
    drafts run through the pre-constrain rewrite) plus a mask-cache
    round-trip through both tiers, then validate the ``mxnet_grammar_*``
    families: a session counted per constrained request, exactly one
    compile miss (with its compile-seconds sample) and memory-/disk-tier
    hits for the same schema, grammar-dead draft tokens counted as
    rejections, and the conformance spot check — every completion
    matches the schema BY CONSTRUCTION. Returns a summary dict; raises
    on any failure."""
    import shutil
    import tempfile

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import metrics
    from mxnet_tpu.models import GPTModel
    from mxnet_tpu.models.gpt import GPTConfig
    from mxnet_tpu.serve import (InferenceEngine, clear_grammar_cache,
                                 compile_grammar)

    schema = {"type": "object",
              "properties": {"ok": {"type": "boolean"},
                             "mode": {"enum": ["fast", "safe"]}}}
    was_enabled = metrics.enabled()
    prev_dir = os.environ.get("MXNET_GRAMMAR_CACHE_DIR")
    tmpdir = tempfile.mkdtemp(prefix="mxnet-grammar-check-")
    metrics.reset()
    metrics.enable()
    clear_grammar_cache()
    os.environ["MXNET_GRAMMAR_CACHE_DIR"] = tmpdir
    try:
        mx.random.seed(0)
        net = GPTModel(GPTConfig(vocab_size=128, hidden_size=32,
                                 num_layers=2, num_heads=2,
                                 max_position_embeddings=128,
                                 dropout=0.0))
        net.initialize()
        rng = onp.random.RandomState(0)
        # 'A' (65) is dead at every automaton state of this schema, so
        # the repeat-last lookup drafts are guaranteed to hit the
        # pre-constrain rewrite (= grammar rejections) at least once
        prompts = [onp.asarray([65] * 6 + [int(rng.randint(1, 120))],
                               onp.int32) for _ in range(3)]
        eng = InferenceEngine(net, max_batch_size=2, max_len=64,
                              page_size=8, speculate=4,
                              grammar=True).start()
        try:
            results = [eng.generate(p, 40, grammar=schema,
                                    eos_token_id=0, seed=i)
                       for i, p in enumerate(prompts)]
        finally:
            eng.shutdown()
        gram = compile_grammar(schema, 128)   # memory hit: engine cached
        bad = [r for r in results if r.status != "ok"
               or not gram.matches(r.generated_ids, eos_token_id=0)]
        if bad:
            raise AssertionError(
                f"constrained completions nonconformant: "
                f"{[(r.status, list(r.generated_ids)) for r in bad]}")

        # disk tier: drop the memory layer; the same key must restore
        # from MXNET_GRAMMAR_CACHE_DIR without paying a recompile
        clear_grammar_cache()
        if compile_grammar(schema, 128).key != gram.key:
            raise AssertionError("disk restore changed the grammar key")

        text = metrics.expose()
        families = parse_exposition(text)
        missing = [m for m in REQUIRED_GRAMMAR_METRICS
                   if m not in families]
        if missing:
            raise AssertionError(f"missing grammar metrics: {missing}")
        sessions = metrics.get_sample_value(
            "mxnet_grammar_sessions_total") or 0
        if sessions != len(prompts):
            raise AssertionError(
                f"{sessions} grammar sessions for {len(prompts)} "
                f"constrained requests")
        misses = metrics.get_sample_value(
            "mxnet_grammar_mask_cache_misses_total") or 0
        compiles = metrics.get_sample_value(
            "mxnet_grammar_compile_seconds_count") or 0
        if misses != 1 or compiles != 1:
            raise AssertionError(
                f"one schema must compile exactly once: misses={misses}, "
                f"compile samples={compiles}")
        mem_hits = metrics.get_sample_value(
            "mxnet_grammar_mask_cache_hits_total",
            {"tier": "memory"}) or 0
        disk_hits = metrics.get_sample_value(
            "mxnet_grammar_mask_cache_hits_total", {"tier": "disk"}) or 0
        if not mem_hits or not disk_hits:
            raise AssertionError(
                f"cache tiers not exercised (memory={mem_hits}, "
                f"disk={disk_hits})")
        rejected = metrics.get_sample_value(
            "mxnet_grammar_rejected_tokens_total") or 0
        if not rejected:
            raise AssertionError(
                "grammar-dead lookup drafts recorded no rejections")
        mx.waitall()
        return {"ok": True, "sessions": int(sessions),
                "cache_misses": int(misses),
                "memory_hits": int(mem_hits),
                "disk_hits": int(disk_hits),
                "rejected_tokens": int(rejected),
                "conformant": len(results)}
    finally:
        if prev_dir is None:
            os.environ.pop("MXNET_GRAMMAR_CACHE_DIR", None)
        else:
            os.environ["MXNET_GRAMMAR_CACHE_DIR"] = prev_dir
        clear_grammar_cache()
        if not was_enabled:
            metrics.disable()
        shutil.rmtree(tmpdir, ignore_errors=True)


def run_zero_check():
    """A few ZeRO-2 steps with int8-quantized param all-gather on the
    virtual dp mesh, then validate the ``mxnet_zero_*`` exposition:
    shard-count and optimizer-state gauges (per-replica ~dp x smaller
    than replicated), collective call/byte counters for the
    reduce-scatter and quantized all-gather, wire bytes >= 3x below the
    fp32 reduce-scatter of the same tensors, and finite error-feedback
    residual gauges. Returns a summary dict; raises on any failure."""
    import numpy as onp

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import metrics, np, parallel
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.parallel import P

    was_enabled = metrics.enabled()
    metrics.reset()
    metrics.enable()
    try:
        dp = min(8, len(jax.devices()))
        mesh = parallel.make_mesh({"dp": dp}, devices=jax.devices()[:dp])
        rng = onp.random.RandomState(0)
        X = rng.randn(2 * dp, 16).astype("float32")
        Y = rng.randint(0, 4, 2 * dp).astype("int32")
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(128, activation="relu"), nn.Dense(4))
        net.initialize(mx.init.Xavier())
        step = parallel.TrainStep(
            net, SoftmaxCrossEntropyLoss(),
            mx.optimizer.Adam(learning_rate=1e-2),
            example_inputs=[np.array(X)], mesh=mesh,
            data_spec=P("dp"), label_spec=P("dp"), zero=2,
            compression_params={"type": "int8"})
        losses = [float(step(np.array(X), np.array(Y)).item())
                  for _ in range(3)]
        if not all(onp.isfinite(losses)):
            raise AssertionError(f"non-finite zero losses {losses}")
        residuals = step.zero_residual_norms()
        per_replica, replicated = step.zero_state_bytes()

        text = metrics.expose()
        families = parse_exposition(text)
        missing = [m for m in REQUIRED_ZERO_METRICS if m not in families]
        if missing:
            raise AssertionError(f"missing zero metrics: {missing}")
        shards = metrics.get_sample_value("mxnet_zero_shards")
        if shards != dp:
            raise AssertionError(f"mxnet_zero_shards={shards}, want {dp}")
        g_per = metrics.get_sample_value("mxnet_zero_opt_state_bytes",
                                         {"scope": "per_replica"})
        g_tot = metrics.get_sample_value("mxnet_zero_opt_state_bytes",
                                         {"scope": "replicated_equiv"})
        if not g_per or not g_tot or g_tot < g_per * (dp - 1):
            raise AssertionError(
                f"opt-state gauges do not show the ~dp x shrink: "
                f"per_replica={g_per}, replicated_equiv={g_tot}, dp={dp}")
        rs = metrics.get_sample_value("mxnet_collective_bytes_total",
                                      {"op": "zero_reduce_scatter"}) or 0
        agq = metrics.get_sample_value("mxnet_collective_bytes_total",
                                       {"op": "zero_allgather_q"}) or 0
        if not rs or not agq:
            raise AssertionError(
                f"zero collective byte counters missing "
                f"(reduce_scatter={rs}, allgather_q={agq})")
        # the fp32 reduce-scatter moves the SAME tensors the quantized
        # all-gather ships — the >= 3x wire saving reads straight off
        # the two counters (int8 + fp32 block scales ~= 3.9x)
        if rs / agq < 3.0:
            raise AssertionError(
                f"quantized all-gather saves only {rs / agq:.2f}x over "
                "fp32 (want >= 3x)")
        if not residuals or not all(
                onp.isfinite(v) for v in residuals.values()):
            raise AssertionError(f"bad residual norms {residuals}")
        n_res = sum(
            1 for _ in metrics.REGISTRY.get(
                "mxnet_zero_residual_l2").children())
        if n_res != len(residuals):
            raise AssertionError(
                f"{n_res} residual gauges for {len(residuals)} slots")
        mx.waitall()
        return {"ok": True, "dp": dp, "losses": losses,
                "opt_state_bytes_per_replica": per_replica,
                "opt_state_bytes_replicated": replicated,
                "wire_saving_x": rs / agq,
                "residual_slots": len(residuals)}
    finally:
        if not was_enabled:
            metrics.disable()


def run_health_check():
    """Drive the mxhealth stack in-process — a health-on TrainStep for
    a few clean steps (gauges + sampled layer stats), one NaN-poisoned
    batch (a declared nonfinite anomaly + a reason=numeric_anomaly
    flight-recorder dump), and an AMP LossScaler through one overflow
    and one clean doubling window — then validate every
    ``mxnet_health_*`` / ``mxnet_amp_*`` family in the exposition.
    Returns a summary dict; raises on any failure."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import metrics, np, parallel
    from mxnet_tpu.amp.loss_scaler import LossScaler
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import L2Loss
    from mxnet_tpu.observability import health as _health
    from mxnet_tpu.observability import recorder as _recorder

    was_enabled = metrics.enabled()
    metrics.reset()
    metrics.enable()
    _recorder.RECORDER.reset()
    try:
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, in_units=4), nn.Dense(2))
        net.initialize()
        rng = onp.random.RandomState(0)
        X = rng.rand(4, 4).astype("float32")
        step = parallel.TrainStep(
            net, L2Loss(), mx.optimizer.SGD(learning_rate=0.1),
            example_inputs=[np.array(X)], block_every=2, health=True,
            health_config=_health.HealthConfig(sample_every=2))
        for i in range(4):
            step(rng.rand(4, 4).astype("float32"),
                 rng.rand(4, 2).astype("float32"))
        step(onp.full((4, 4), onp.nan, dtype="float32"),
             rng.rand(4, 2).astype("float32"))
        step.drain()

        text = metrics.expose()
        families = parse_exposition(text)
        missing = [m for m in REQUIRED_HEALTH_METRICS if m not in families]
        if missing:
            raise AssertionError(f"missing health metrics: {missing}")
        anomalies = metrics.get_sample_value(
            "mxnet_health_anomalies_total", {"kind": "nonfinite"}) or 0
        if anomalies < 1:
            raise AssertionError("poisoned batch declared no "
                                 "kind=nonfinite anomaly")
        last = metrics.get_sample_value("mxnet_health_last_anomaly_step")
        if not last:
            raise AssertionError("mxnet_health_last_anomaly_step unset")
        bad_grads = metrics.get_sample_value(
            "mxnet_health_nonfinite", {"what": "grads"}) or 0
        if bad_grads < 1:
            raise AssertionError("nonfinite grad count did not surface")
        for fam in ("mxnet_health_layer_maxabs", "mxnet_health_layer_rms"):
            if families[fam]["samples"] < 2:
                raise AssertionError(f"{fam}: expected a sample per "
                                     "layer group")
        dump = _recorder.RECORDER.last_dump()
        if not (dump and os.path.exists(dump)):
            raise AssertionError("anomaly produced no recorder dump")
        with open(dump) as f:
            if json.load(f)["reason"] != "numeric_anomaly":
                raise AssertionError("dump reason != numeric_anomaly")

        # AMP scaler calibration trace: one overflow (skip + halving),
        # then a full clean window (doubling back)
        scaler = LossScaler(init_scale=8.0, scale_window=2)
        scaler.update_scale(True)
        scaler.update_scale(False)
        scaler.update_scale(False)
        if metrics.get_sample_value("mxnet_amp_scale") != 8.0:
            raise AssertionError("amp scale gauge did not track "
                                 "halve-then-double")
        if metrics.get_sample_value(
                "mxnet_amp_skipped_steps_total") != 1:
            raise AssertionError("overflow skip was not counted")
        for direction in ("down", "up"):
            if metrics.get_sample_value(
                    "mxnet_amp_scale_adjustments_total",
                    {"direction": direction}) != 1:
                raise AssertionError(
                    f"missing direction={direction} scale adjustment")
        mx.waitall()
        return {"ok": True, "anomalies": anomalies,
                "last_anomaly_step": last,
                "nonfinite_grads": bad_grads, "dump": dump}
    finally:
        if not was_enabled:
            metrics.disable()


def run_elastic_check():
    """One simulated kill-a-worker drill (the SAME drill
    ``tools/mxchaos.py::run_sim_drill`` ships — one implementation, two
    consumers: dp=4 -> 3 ElasticTrainer over the virtual mesh with
    zero=2 + async sharded checkpoints + a cold-restart bitwise-parity
    control), then validate the ``mxnet_elastic_*`` exposition:
    heartbeat send/age families, exactly one peer lost over the
    heartbeat window with its detect/reform/restore phase samples, the
    epoch/world gauges at the re-formed values, and a flight-recorder
    dump on ``reason=peer_lost`` whose ring carries the fault ->
    detection -> resume event chain. Returns a summary dict; raises on
    any failure."""
    import importlib.util
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import metrics
    from mxnet_tpu.observability import recorder as _recorder

    spec = importlib.util.spec_from_file_location(
        "mxchaos", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "mxchaos.py"))
    mxchaos = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mxchaos)

    was_enabled = metrics.enabled()
    metrics.reset()
    metrics.enable()
    _recorder.RECORDER.reset()
    workdir = tempfile.mkdtemp(prefix="mxnet-elastic-check-")

    try:
        hb_timeout = 0.24   # run_sim_drill derives timeout = 6 * pace
        out = mxchaos.run_sim_drill(dp=4, steps=14, period=3,
                                    plan_spec="kill@4:rank=2",
                                    pace_s=hb_timeout / 6,
                                    workdir=workdir, publish=False)

        if not out["ok"] or out["reforms"] != 1 or out["final_dp"] != 3:
            raise AssertionError(f"drill did not re-form at dp=3: {out}")
        if not out.get("bitwise_parity"):
            raise AssertionError(
                f"resumed losses diverged from the cold restart: {out}")
        text = metrics.expose()
        families = parse_exposition(text)
        missing = [m for m in REQUIRED_ELASTIC_METRICS
                   if m not in families]
        if missing:
            raise AssertionError(f"missing elastic metrics: {missing}")
        lost = metrics.get_sample_value("mxnet_elastic_peer_lost_total",
                                        {"reason": "heartbeat"}) or 0
        if lost < 1:
            raise AssertionError("no mxnet_elastic_peer_lost_total"
                                 "{reason=heartbeat} sample")
        epoch = metrics.get_sample_value("mxnet_elastic_epoch")
        world = metrics.get_sample_value("mxnet_elastic_world_size")
        reforms = metrics.get_sample_value("mxnet_elastic_reforms_total")
        if epoch != 1 or world != 3 or reforms != 1:
            raise AssertionError(
                f"re-form gauges wrong: epoch={epoch}, world={world}, "
                f"reforms={reforms}")
        hb_sent = metrics.get_sample_value(
            "mxnet_elastic_heartbeats_total", {"dir": "sent"}) or 0
        if hb_sent < 10:
            raise AssertionError(f"only {hb_sent} heartbeats sent")
        for phase in ("detect", "reform", "restore"):
            c = metrics.get_sample_value(
                "mxnet_elastic_phase_seconds_count", {"phase": phase})
            if not c:
                raise AssertionError(f"no {phase} phase sample")
        detect = next(e for e in out["events"]
                      if e["event"] == "peer_lost")
        if not (0 <= detect["latency_s"] <= 10 * hb_timeout):
            raise AssertionError(
                f"detect latency {detect['latency_s']} outside the "
                f"window (timeout {hb_timeout})")
        dump = _recorder.RECORDER.last_dump()
        if not dump or not os.path.exists(dump):
            raise AssertionError("no flight-recorder dump on peer loss")
        with open(dump) as f:
            doc = json.load(f)
        if doc.get("reason") != "peer_lost":
            raise AssertionError(
                f"dump reason {doc.get('reason')!r} != 'peer_lost'")
        dumped = {e.get("name") for e in doc.get("events", [])}
        if not {"fault_kill", "peer_lost"} <= dumped:
            raise AssertionError(
                f"dump missing fault/detection events: {sorted(dumped)}")
        ring = {e.get("name")
                for e in _recorder.RECORDER.snapshot()}
        if not {"elastic_resume", "checkpoint_restore"} <= ring:
            raise AssertionError(
                f"recorder ring missing resume events: {sorted(ring)}")
        dumps = metrics.get_sample_value(
            "mxnet_flight_recorder_dumps_total", {"reason": "peer_lost"})
        if not dumps:
            raise AssertionError("peer_lost dump not counted")
        mx.waitall()
        return {"ok": True, "peer_lost": int(lost),
                "detect_latency_s": round(detect["latency_s"], 4),
                "resume_step": out["resume_steps"][0],
                "final_dp": out["final_dp"], "epoch": int(epoch),
                "reforms": int(reforms), "hb_sent": int(hb_sent),
                "dump_path": dump}
    finally:
        if not was_enabled:
            metrics.disable()


def run_paging_check():
    """One paged serving round with shared-prefix + long-prompt traffic,
    then a 2-replica in-process router round with a drain, validating the
    ``mxnet_serve_page_*`` and ``mxnet_router_*`` families: prefix-cache
    hits and bytes saved > 0, chunked-prefill chunks > 0, page leases
    balanced by releases (in_use returns to the cache-only pin count),
    per-replica dispatches > 0 and the drain recorded as an eject.
    Returns a summary dict; raises on any failure."""
    import threading

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import metrics, np
    from mxnet_tpu.models import GPTModel
    from mxnet_tpu.models.gpt import GPTConfig
    from mxnet_tpu.serve import HTTPFrontend, InferenceEngine, Router

    was_enabled = metrics.enabled()
    metrics.reset()
    metrics.enable()
    try:
        def build():
            mx.random.seed(0)
            net = GPTModel(GPTConfig(
                vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                max_position_embeddings=128, dropout=0.0))
            net.initialize()
            return net

        rng = onp.random.RandomState(0)
        shared = rng.randint(1, 63, size=20).astype(onp.int32)
        prompts = ([onp.concatenate([shared, rng.randint(1, 63, size=3 + i)
                                     .astype(onp.int32)])
                    for i in range(4)]
                   + [rng.randint(1, 63, size=40).astype(onp.int32)])

        # --- paged engine: prefix reuse + chunked prefill + COW ---
        eng = InferenceEngine(build(), max_batch_size=2, max_len=64,
                              page_size=8).start()
        try:
            for i, p in enumerate(prompts):   # sequential: prefixes publish
                res = eng.submit(p, 6, seed=i).result(300)
                if res.status != "ok":
                    raise AssertionError(f"paged request failed: {res}")
            pstats = eng.stats()["pages"]
        finally:
            eng.shutdown()

        text = metrics.expose()
        families = parse_exposition(text)
        missing = [m for m in REQUIRED_PAGING_METRICS if m not in families]
        if missing:
            raise AssertionError(f"missing paging metrics: {missing}")
        hits = metrics.get_sample_value(
            "mxnet_serve_page_prefix_hits_total") or 0
        saved = metrics.get_sample_value(
            "mxnet_serve_page_prefix_bytes_saved_total") or 0
        chunks = metrics.get_sample_value(
            "mxnet_serve_page_prefill_chunks_total") or 0
        cows = metrics.get_sample_value(
            "mxnet_serve_page_cow_forks_total") or 0
        if not hits or not saved:
            raise AssertionError(
                f"shared-prefix traffic recorded no prefix-cache reuse "
                f"(hits={hits}, bytes_saved={saved})")
        if not chunks:
            raise AssertionError("long prompt recorded no prefill chunks")
        if not cows:
            raise AssertionError("prefix reuse recorded no COW forks")
        in_use = metrics.get_sample_value("mxnet_serve_page_in_use")
        if in_use != pstats["pages_cached_only"]:
            raise AssertionError(
                f"page leak: {in_use} pages in use after drain, but only "
                f"{pstats['pages_cached_only']} prefix-cache pins remain")

        # --- 2-replica router: least-loaded dispatch + drain eject ---
        engines = [InferenceEngine(build(), max_batch_size=1, max_len=32,
                                   page_size=8).start()
                   for _ in range(2)]
        fronts = [HTTPFrontend(e, port=0).start() for e in engines]
        router = Router([f.url for f in fronts],
                        health_interval=0.1).start()
        try:
            # concurrent dispatches so the in-flight term spreads the
            # choice across replicas (exercises the rebalance counter)
            errs = []

            def fire(i):
                doc = router.generate({
                    "input_ids": [int(t) for t in prompts[i % 4]],
                    "max_new_tokens": 4, "seed": i})
                if doc.get("status") != "ok":
                    errs.append(doc)

            threads = [threading.Thread(target=fire, args=(i,))
                       for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errs:
                raise AssertionError(f"routed requests failed: {errs}")
            router.drain(fronts[0].url)
            rstats = router.stats()
        finally:
            router.stop()
            for f in fronts:
                f.stop()
            for e in engines:
                e.shutdown()

        families = parse_exposition(metrics.expose())
        missing = [m for m in REQUIRED_ROUTER_METRICS if m not in families]
        if missing:
            raise AssertionError(f"missing router metrics: {missing}")
        dispatched = sum(
            metrics.get_sample_value("mxnet_router_dispatch_total",
                                     {"backend": f.url}) or 0
            for f in fronts)
        if dispatched < 6:
            raise AssertionError(
                f"router recorded {dispatched} dispatches for 6 requests")
        ejects = metrics.get_sample_value(
            "mxnet_router_ejects_total", {"backend": fronts[0].url}) or 0
        if not ejects:
            raise AssertionError("drain did not record an ejection")
        mx.waitall()
        return {"ok": True, "prefix_hits": hits, "prefix_bytes_saved": saved,
                "prefill_chunks": chunks, "cow_forks": cows,
                "router_dispatches": dispatched, "router_ejects": ejects,
                "router_rebalances": rstats["rebalances"]}
    finally:
        if not was_enabled:
            metrics.disable()


def run_fleet_check():
    """One self-managing-fleet round validating the ``mxnet_fleet_*``
    and weight-refresh families: (a) the autoscale controller scales a
    fake-replica fleet up under load and back down under slack — every
    decision (and every hysteresis-suppressed one) counted; (b) tenant
    WFQ fairness arithmetic — dispatch shares track 3:1 weights over a
    saturated window, and a quota'd tenant's overflow is rejected; (c) a
    live weight swap on a real engine flips the weight-version gauge and
    changes greedy outputs with zero engine restarts. Returns a summary
    dict; raises on any failure."""
    import json as _json
    import threading
    import time as _time
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    import mxnet_tpu as mx
    from mxnet_tpu import metrics
    from mxnet_tpu.models import GPTModel
    from mxnet_tpu.models.gpt import GPTConfig
    from mxnet_tpu.serve import (AutoscalePolicy, FleetController,
                                 InferenceEngine, Router, TenantPolicy,
                                 TenantScheduler, QuotaExceededError,
                                 publish_weights, snapshot_params)

    was_enabled = metrics.enabled()
    metrics.reset()
    metrics.enable()
    try:
        # --- (a) controller decisions over fake replicas ---
        class _Fake:
            """Stdlib replica stub with a settable load scalar."""

            def __init__(self):
                state = {"load": 0.0, "draining": False}

                class H(BaseHTTPRequestHandler):
                    def log_message(self, *a):
                        pass

                    def _json(self, code, doc):
                        body = _json.dumps(doc).encode()
                        self.send_response(code)
                        self.send_header("Content-Type",
                                         "application/json")
                        self.send_header("Content-Length",
                                         str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)

                    def do_GET(self):
                        self._json(200, {
                            "ok": not state["draining"],
                            "draining": state["draining"],
                            "load": state["load"], "slots": 2,
                            "slots_in_use": 0, "queue_depth": 0,
                            "models": {"m": 0}})

                    def do_POST(self):
                        self.rfile.read(int(
                            self.headers.get("Content-Length", 0)))
                        if self.path == "/drain":
                            state["draining"] = True
                            self._json(200, {"ok": True,
                                             "draining": True})
                        else:
                            self._json(404, {"error": "nope"})
                self.state = state
                self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
                self.httpd.daemon_threads = True
                threading.Thread(target=self.httpd.serve_forever,
                                 daemon=True).start()
                self.url = (f"http://127.0.0.1:"
                            f"{self.httpd.server_address[1]}")

            def close(self):
                self.httpd.shutdown()
                self.httpd.server_close()

        class _FakeSpawner:
            def __init__(self):
                self.fakes = {}

            def spawn(self):
                f = _Fake()
                self.fakes[f.url] = f
                return f.url

            def stop(self, url):
                self.fakes.pop(url).close()

            def urls(self):
                return list(self.fakes)

        spawner = _FakeSpawner()
        first = spawner.spawn()
        router = Router([first], health_interval=0.05).start()
        policy = AutoscalePolicy(scale_up_load=0.7, scale_down_load=0.2,
                                 up_after=2, down_after=2, cooldown_s=0.0,
                                 min_replicas=1, max_replicas=2,
                                 drain_grace_s=5.0, refresh_slo=False)
        ctl = FleetController(router, spawner, policy=policy)
        try:
            deadline = _time.monotonic() + 30
            # the first probe must land before ticking: an early tick
            # would see healthy=0 and take the min_floor recovery path,
            # putting the fleet at max before the load-reason assertions
            while (router.stats()["healthy"] < 1
                   and _time.monotonic() < deadline):
                _time.sleep(0.02)
            spawner.fakes[first].state["load"] = 1.5   # sustained pressure
            up_event = down_event = None
            while _time.monotonic() < deadline and up_event is None:
                _time.sleep(0.1)                       # let polls land
                up_event = ctl.tick()
            if not up_event or up_event["direction"] != "up":
                raise AssertionError(
                    f"controller never scaled up: {ctl.stats()}")
            for f in spawner.fakes.values():
                f.state["load"] = 0.0                  # sustained slack
            while _time.monotonic() < deadline and down_event is None:
                _time.sleep(0.1)
                down_event = ctl.tick()
            if not down_event or down_event["direction"] != "down":
                raise AssertionError(
                    f"controller never scaled down: {ctl.stats()}")
            while ctl.stats()["retiring"]:
                if _time.monotonic() > deadline:
                    raise AssertionError(
                        f"drained replica never retired: {ctl.stats()}")
                _time.sleep(0.1)
                ctl.tick()
        finally:
            ctl.stop()
            router.stop()
            for url in spawner.urls():
                spawner.stop(url)
        ups = metrics.get_sample_value(
            "mxnet_fleet_scale_events_total",
            {"direction": "up", "reason": "load"}) or 0
        downs = metrics.get_sample_value(
            "mxnet_fleet_scale_events_total",
            {"direction": "down", "reason": "load"}) or 0
        suppressed = metrics.get_sample_value(
            "mxnet_fleet_decisions_suppressed_total",
            {"direction": "up", "why": "hysteresis"}) or 0
        if not ups or not downs:
            raise AssertionError(
                f"scale decisions not counted (up={ups}, down={downs})")
        if not suppressed:
            raise AssertionError(
                "hysteresis never suppressed a decision (up_after=2 "
                "means the first pressure tick must be suppressed)")

        # --- (b) WFQ fairness arithmetic + quota rejection ---
        sched = TenantScheduler({"a": TenantPolicy(weight=3.0),
                                 "b": TenantPolicy(weight=1.0)},
                                capacity_fn=lambda: 2)
        counts = {"a": 0, "b": 0}
        lock = threading.Lock()
        stop = threading.Event()

        def worker(tenant):
            while not stop.is_set():
                sched.acquire(tenant)
                _time.sleep(0.002)
                with lock:
                    counts[tenant] += 1
                sched.release(tenant)

        workers = [threading.Thread(target=worker, args=(t,))
                   for t in ("a", "b") for _ in range(4)]
        for w in workers:
            w.start()
        _time.sleep(0.6)
        with lock:
            mid = dict(counts)
        stop.set()
        for w in workers:
            w.join()
        ratio = mid["a"] / max(1, mid["b"])
        if not 2.0 < ratio < 4.5:
            raise AssertionError(
                f"WFQ shares off 3:1 weights: {mid} (ratio {ratio:.2f})")
        quota = TenantScheduler({"q": TenantPolicy(max_inflight=1)})
        quota.acquire("q")
        try:
            quota.acquire("q", timeout=0.05)
            raise AssertionError("quota admission never timed out")
        except QuotaExceededError:
            pass
        quota.release("q")
        rejected = metrics.get_sample_value(
            "mxnet_fleet_tenant_rejected_total", {"tenant": "q"}) or 0
        if not rejected:
            raise AssertionError("quota rejection not counted")

        # --- (c) live weight swap flips the version gauge ---
        def build(seed):
            mx.random.seed(seed)
            net = GPTModel(GPTConfig(
                vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                max_position_embeddings=128, dropout=0.0))
            net.initialize()
            return net

        import tempfile
        eng = InferenceEngine(build(0), max_batch_size=2, max_len=64,
                              name="m").start()
        try:
            before = eng.generate([1, 2, 3], 6).generated_ids
            wdir = tempfile.mkdtemp(prefix="mxnet_fleet_check_")
            version = publish_weights(wdir, snapshot_params(build(1)))
            eng.swap_weights_from(wdir)
            after = eng.generate([1, 2, 3], 6).generated_ids
        finally:
            eng.shutdown()
        gauge = metrics.get_sample_value("mxnet_serve_weight_version",
                                         {"model": "m"})
        swaps = metrics.get_sample_value("mxnet_serve_weight_swaps_total",
                                         {"model": "m"}) or 0
        if gauge != version or not swaps:
            raise AssertionError(
                f"weight-version gauge did not flip on swap "
                f"(gauge={gauge}, published={version}, swaps={swaps})")
        if before == after:
            raise AssertionError(
                "weight swap did not change greedy outputs")

        families = parse_exposition(metrics.expose())
        missing = [m for m in REQUIRED_FLEET_METRICS if m not in families]
        if missing:
            raise AssertionError(f"missing fleet metrics: {missing}")
        mx.waitall()
        return {"ok": True, "scale_ups": ups, "scale_downs": downs,
                "suppressed_hysteresis": suppressed,
                "wfq_counts": mid, "wfq_ratio": round(ratio, 2),
                "quota_rejected": rejected,
                "weight_version": gauge, "weight_swaps": swaps}
    finally:
        if not was_enabled:
            metrics.disable()


def run_cache_check():
    """One cache-aware-fleet round validating the ``mxnet_cache_*`` and
    ``mxnet_migrate_*`` families plus the tier gauges: (a) a replica's
    bounded prefix-summary advert reaches /healthz and the router's
    affinity dispatch converts it into a hit (cold + hit outcomes and
    hit-tokens counted); (b) a KV page migration round-trips between two
    engines token-exactly, a deliberately corrupted page is REJECTED by
    the chain-hash verify (counted, never injected), and the balance
    invariant ``sent == received + verify_failures`` holds exactly;
    (c) a tier-scoped controller's scale decision lands in the
    ``mxnet_fleet_tier_*`` metrics. Returns a summary dict; raises on
    any failure."""
    import copy
    import json as _json
    import threading
    import time as _time
    import urllib.request
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import metrics
    from mxnet_tpu.models import GPTModel
    from mxnet_tpu.models.gpt import GPTConfig
    from mxnet_tpu.serve import (AutoscalePolicy, FleetController,
                                 HTTPFrontend, InferenceEngine, Router)

    was_enabled = metrics.enabled()
    metrics.reset()
    metrics.enable()
    try:
        def build():
            mx.random.seed(0)
            net = GPTModel(GPTConfig(
                vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                max_position_embeddings=128, dropout=0.0))
            net.initialize()
            return net

        rng = onp.random.RandomState(0)
        prefix = rng.randint(1, 63, size=24).astype(onp.int32)

        # --- (a) bounded advert -> affinity hit at the router ---
        engines = [InferenceEngine(build(), max_batch_size=2, max_len=64,
                                   page_size=8,
                                   prefix_advert=4).start()
                   for _ in range(2)]
        fronts = [HTTPFrontend(e, port=0).start() for e in engines]
        router = Router([f.url for f in fronts], health_interval=0.05,
                        affinity=True).start()
        try:
            def fire(seed):
                body = rng.randint(1, 63, size=5).astype(onp.int32)
                doc = router.generate({
                    "input_ids": [int(t) for t in prefix] +
                                 [int(t) for t in body],
                    "max_new_tokens": 4, "seed": seed})
                if doc.get("status") != "ok":
                    raise AssertionError(f"routed request failed: {doc}")

            fire(0)                       # cold: nobody advertises yet
            deadline = _time.monotonic() + 30
            while (not any(b.get("prefix_roots")
                           for b in router.stats()["backends"].values())
                   and _time.monotonic() < deadline):
                _time.sleep(0.02)         # let the advert poll land
            fire(1)                       # same prefix: affinity hit
            for f in fronts:              # the advert is BOUNDED
                with urllib.request.urlopen(f.url + "/healthz",
                                            timeout=5) as r:
                    hdoc = _json.loads(r.read())
                roots = hdoc.get("prefix_summary", {}).get("roots", ())
                if len(roots) > 4:
                    raise AssertionError(
                        f"advert exceeds prefix_advert=4: {len(roots)}")

            # --- (b) migration round-trip + corrupted-page verify ---
            # (reusing the live pair — engine builds dominate this
            # check's runtime; a fresh 33-token prompt keeps the
            # migration family disjoint from the affinity prefix)
            src, dst = engines
            prompt = [int(t) for t in rng.randint(1, 63, size=33)]
            ra = src.generate(prompt, 4, seed=7)
            if ra.status != "ok":
                raise AssertionError(f"source request failed: {ra}")
            bad = copy.deepcopy(src.export_pages(prompt))
            bad["pages"][0]["key"] ^= 1          # corrupt one chain hash
            res_bad = dst.import_pages(bad)
            if not res_bad["verify_failures"]:
                raise AssertionError(
                    f"corrupted page passed verification: {res_bad}")
            good = src.export_pages(prompt)
            res_good = dst.import_pages(good)
            if not res_good["received"]:
                raise AssertionError(f"clean import landed 0: {res_good}")
            rb = dst.generate(prompt, 4, seed=7)
            if list(rb.generated_ids) != list(ra.generated_ids):
                raise AssertionError(
                    f"migrated resume diverged: {list(rb.generated_ids)} "
                    f"vs {list(ra.generated_ids)}")
        finally:
            router.stop()
            for f in fronts:
                f.stop()
            for e in engines:
                e.shutdown()
        cold = metrics.get_sample_value(
            "mxnet_cache_affinity_dispatch_total",
            {"outcome": "cold"}) or 0
        hit = metrics.get_sample_value(
            "mxnet_cache_affinity_dispatch_total",
            {"outcome": "hit"}) or 0
        hit_tokens = metrics.get_sample_value(
            "mxnet_cache_affinity_hit_tokens_total") or 0
        if not cold or not hit:
            raise AssertionError(
                f"affinity outcomes not counted (cold={cold}, hit={hit})")
        if hit_tokens < 16:
            raise AssertionError(
                f"affinity hit mapped only {hit_tokens} prompt tokens "
                f"(24-token shared prefix should match >= 2 pages)")
        sent = metrics.get_sample_value(
            "mxnet_migrate_pages_sent_total") or 0
        received = metrics.get_sample_value(
            "mxnet_migrate_pages_received_total") or 0
        failures = metrics.get_sample_value(
            "mxnet_migrate_verify_failures_total") or 0
        if not sent or not failures:
            raise AssertionError(
                f"migration not counted (sent={sent}, vf={failures})")
        if sent != received + failures:
            raise AssertionError(
                f"page balance broken: sent={sent} != received="
                f"{received} + verify_failures={failures}")

        # --- (c) tier-scoped scale decision in mxnet_fleet_tier_* ---
        class _Fake:
            """Stdlib replica stub advertising a serving tier."""

            def __init__(self):
                state = {"load": 0.0}

                class H(BaseHTTPRequestHandler):
                    def log_message(self, *a):
                        pass

                    def do_GET(self):
                        body = _json.dumps({
                            "ok": True, "draining": False,
                            "load": state["load"], "slots": 2,
                            "slots_in_use": 0, "queue_depth": 0,
                            "tier": "prefill"}).encode()
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "application/json")
                        self.send_header("Content-Length",
                                         str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                self.state = state
                self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
                self.httpd.daemon_threads = True
                threading.Thread(target=self.httpd.serve_forever,
                                 daemon=True).start()
                self.url = (f"http://127.0.0.1:"
                            f"{self.httpd.server_address[1]}")

            def close(self):
                self.httpd.shutdown()
                self.httpd.server_close()

        class _FakeSpawner:
            def __init__(self):
                self.fakes = {}

            def spawn(self):
                f = _Fake()
                self.fakes[f.url] = f
                return f.url

            def stop(self, url):
                self.fakes.pop(url).close()

            def urls(self):
                return list(self.fakes)

        spawner = _FakeSpawner()
        first = spawner.spawn()
        router = Router([first], health_interval=0.05).start()
        policy = AutoscalePolicy(scale_up_load=0.7, scale_down_load=0.2,
                                 up_after=2, down_after=2, cooldown_s=0.0,
                                 min_replicas=1, max_replicas=2,
                                 drain_grace_s=5.0, refresh_slo=False,
                                 slo_names=("ttft",))
        ctl = FleetController(router, spawner, policy=policy,
                              tier="prefill")
        try:
            deadline = _time.monotonic() + 30
            while (router.stats()["healthy"] < 1
                   and _time.monotonic() < deadline):
                _time.sleep(0.02)
            spawner.fakes[first].state["load"] = 1.5
            up_event = None
            while _time.monotonic() < deadline and up_event is None:
                _time.sleep(0.1)
                up_event = ctl.tick()
            if not up_event or up_event["direction"] != "up":
                raise AssertionError(
                    f"tiered controller never scaled up: {ctl.stats()}")
            if up_event.get("tier") != "prefill":
                raise AssertionError(
                    f"scale event lost its tier: {up_event}")
        finally:
            ctl.stop()
            router.stop()
            for url in spawner.urls():
                spawner.stop(url)
        tier_ups = metrics.get_sample_value(
            "mxnet_fleet_tier_scale_events_total",
            {"tier": "prefill", "direction": "up", "reason": "load"}) or 0
        tier_replicas = metrics.get_sample_value(
            "mxnet_fleet_tier_replicas",
            {"tier": "prefill", "state": "healthy"}) or 0
        if not tier_ups:
            raise AssertionError("tier scale-up not counted in "
                                 "mxnet_fleet_tier_scale_events_total")
        if not tier_replicas:
            raise AssertionError("mxnet_fleet_tier_replicas gauge empty")

        families = parse_exposition(metrics.expose())
        missing = [m for m in REQUIRED_CACHE_METRICS if m not in families]
        if missing:
            raise AssertionError(f"missing cache metrics: {missing}")
        mx.waitall()
        return {"ok": True, "affinity_cold": cold, "affinity_hits": hit,
                "affinity_hit_tokens": hit_tokens,
                "pages_sent": sent, "pages_received": received,
                "verify_failures": failures,
                "tier_scale_ups": tier_ups,
                "tier_replicas": tier_replicas}
    finally:
        if not was_enabled:
            metrics.disable()


def run_trace_check():
    """One traced serving round on the paged engine, then validate the
    observability layer end to end: the request's span tree is complete
    (queue → chunked prefill → decode chunks → retire, all under ONE
    trace id — the client-supplied traceparent's id), the fleet
    aggregation merges registries correctly (counters sum, histogram
    buckets merge, per-backend labels survive, the rendered exposition
    re-parses), and a flight-recorder dump is well-formed JSON. Returns
    a summary dict; raises on any failure."""
    import json as _json

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import metrics
    from mxnet_tpu.models import GPTModel
    from mxnet_tpu.models.gpt import GPTConfig
    from mxnet_tpu.observability import aggregate, recorder, trace
    from mxnet_tpu.serve import InferenceEngine

    was_enabled = metrics.enabled()
    was_traced = trace.enabled()
    metrics.reset()
    metrics.enable()
    trace.enable()
    trace.reset()
    try:
        mx.random.seed(0)
        net = GPTModel(GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
            max_position_embeddings=128, dropout=0.0))
        net.initialize()
        rng = onp.random.RandomState(0)
        # long prompt -> chunked prefill (page_size=8 chunks)
        prompt = rng.randint(1, 63, size=40).astype(onp.int32)
        client_trace = "11" * 16
        tp = f"00-{client_trace}-{'22' * 8}-01"
        eng = InferenceEngine(net, max_batch_size=2, max_len=64,
                              page_size=8).start()
        try:
            res = eng.submit(prompt, 6, traceparent=tp).result(300)
        finally:
            eng.shutdown()
        if res.status != "ok":
            raise AssertionError(f"traced request failed: {res}")

        # --- span-tree completeness, under the propagated trace id ---
        if res.trace_id != client_trace:
            raise AssertionError(
                f"traceparent not honored: result trace id {res.trace_id} "
                f"!= client {client_trace}")
        doc = trace.export(res.trace_id)
        if doc is None:
            raise AssertionError("trace not exportable by id")
        names = {s["name"] for s in doc["spans"]}
        missing_spans = [n for n in REQUIRED_REQUEST_SPANS
                        if n not in names]
        if missing_spans:
            raise AssertionError(
                f"span tree incomplete: missing {missing_spans} "
                f"(have {sorted(names)})")
        if any(s["trace_id"] != res.trace_id for s in doc["spans"]):
            raise AssertionError("span tree mixes trace ids")
        roots = [s for s in doc["spans"] if s["name"] == "serve.request"]
        if len(roots) != 1 or roots[0]["status"] != "ok":
            raise AssertionError(f"bad request root span: {roots}")
        if not any(e["name"] == "retire"
                   for e in roots[0]["events"]):
            raise AssertionError("root span missing the retire event")
        open_spans = [s for s in doc["spans"] if s["t1"] is None]
        if open_spans:
            raise AssertionError(
                f"unclosed spans in a retired trace: "
                f"{[s['name'] for s in open_spans]}")

        # --- aggregated-registry merge correctness ---
        local = _json.loads(metrics.dumps("json"))
        tokens_one = metrics.get_sample_value("mxnet_serve_tokens_total")
        merged = aggregate.aggregate({"r1": local, "r2": local})
        tok = merged["mxnet_serve_tokens_total"]
        fleet = [s for s in tok["samples"]
                 if "backend" not in s["labels"]]
        per_b = [s for s in tok["samples"] if "backend" in s["labels"]]
        if len(fleet) != 1 or fleet[0]["value"] != 2 * tokens_one:
            raise AssertionError(
                f"counter merge wrong: {fleet} (one replica counted "
                f"{tokens_one})")
        if {s["labels"]["backend"] for s in per_b} != {"r1", "r2"}:
            raise AssertionError("per-backend labels missing from merge")
        ttft = [s for s in merged["mxnet_serve_ttft_seconds"]["samples"]
                if "backend" not in s["labels"]][0]
        one = local["mxnet_serve_ttft_seconds"]["samples"][0]
        if ttft["count"] != 2 * one["count"] or any(
                ttft["buckets"][b] != 2 * n
                for b, n in one["buckets"].items()):
            raise AssertionError("histogram bucket merge wrong")
        rendered = aggregate.render_prometheus(merged)
        families = parse_exposition(rendered)
        if "mxnet_serve_ttft_seconds" not in families:
            raise AssertionError("rendered fleet exposition lost families")

        # --- SLO tracker over the merged registries ---
        slo = aggregate.SLOTracker({"ttft": 60.0, "intertoken": 60.0})
        slo_out = slo.update(merged)
        if not slo_out or slo_out["ttft"]["violations"] != 0:
            raise AssertionError(f"trivial SLO shows violations: {slo_out}")
        tight = aggregate.SLOTracker({"ttft": 0.0})
        tight_out = tight.update(merged)
        if tight_out["ttft"]["violations"] <= 0 \
                or tight_out["ttft"]["burn"] <= 1.0:
            raise AssertionError(
                f"impossible SLO did not burn budget: {tight_out}")

        # --- flight-recorder dump well-formedness ---
        recorder.RECORDER.record("event", "trace_check")
        path = recorder.dump("manual", force=True)
        if not path:
            raise AssertionError("flight recorder dump failed")
        with open(path) as f:
            dumped = _json.load(f)
        for key in ("reason", "time", "pid", "events"):
            if key not in dumped:
                raise AssertionError(f"dump missing {key!r}: {path}")
        if not any(e.get("name") == "trace_check"
                   for e in dumped["events"]):
            raise AssertionError("dump lost the recorded event")

        text = metrics.expose()
        families = parse_exposition(text)
        missing = [m for m in REQUIRED_TRACE_METRICS if m not in families]
        if missing:
            raise AssertionError(f"missing trace metrics: {missing}")
        mx.waitall()
        return {"ok": True, "trace_id": res.trace_id,
                "spans": len(doc["spans"]),
                "span_names": sorted(names),
                "fleet_tokens": fleet[0]["value"],
                "slo_burn_tight": tight_out["ttft"]["burn"],
                "recorder_dump": path,
                "recorder_events": len(dumped["events"])}
    finally:
        if not was_traced:
            trace.disable()
        if not was_enabled:
            metrics.disable()


def main() -> int:
    try:
        summary = run_check()
        summary["pipeline"] = run_pipeline_check()
        from mxnet_tpu.observability import perf
        summary["perf"] = run_perf_check(
            chip=None if perf._device_kind() in perf.PEAKS
            else "TPU v5 lite")
        summary["tune"] = run_tune_check()
        summary["aot"] = run_aot_check()
        summary["decode"] = run_decode_check()
        summary["spec"] = run_spec_check()
        summary["grammar"] = run_grammar_check()
        summary["paging"] = run_paging_check()
        summary["fleet"] = run_fleet_check()
        summary["cache"] = run_cache_check()
        summary["zero"] = run_zero_check()
        summary["trace"] = run_trace_check()
        summary["elastic"] = run_elastic_check()
        summary["health"] = run_health_check()
    except Exception as e:
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"}))
        return 1
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # the zero check wants a multi-device dp mesh (it degrades to the
        # real device count, but 8 virtual CPU devices is the CI shape)
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8"
                                   ).strip()
    # runnable from anywhere: the repo root is one level up
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
