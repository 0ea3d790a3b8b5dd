"""Runtime telemetry layer (metrics registry + wired instruments).

The acceptance contract: after a 3-step hybridized train loop,
``metrics.dumps(format="json")`` reports ≥1 recompilation event, a
step-time histogram with count==3, op dispatch counters, and an HBM gauge;
changing the input shape mid-loop increments the recompile counter and
warn-logs the new signature. Plus: the disabled fast path takes no lock
and allocates no label children, the Prometheus exposition parses, and
tools/metrics_check.py (the tier-1 CI guard) passes in-process.
"""
import importlib.util
import json
import logging
import os

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, metrics, np, profiler
from mxnet_tpu.gluon import Trainer, nn
from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
from mxnet_tpu.gluon.loss import L2Loss

_TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")


def _load_metrics_check():
    spec = importlib.util.spec_from_file_location(
        "metrics_check", os.path.join(_TOOLS, "metrics_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def fresh_metrics():
    was = metrics.enabled()
    metrics.reset()
    metrics.enable()
    yield
    if not was:
        metrics.disable()
    metrics.reset()


def _tiny_net():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.Dense(2))
    net.initialize()
    net.hybridize()
    return net


def test_train_loop_acceptance(fresh_metrics, caplog):
    net = _tiny_net()
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    loss_fn = L2Loss()
    rng = onp.random.RandomState(0)
    x = np.array(rng.rand(4, 4).astype("float32"))
    y = np.array(rng.rand(4, 2).astype("float32"))
    for _ in range(3):
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(4)

    doc = json.loads(metrics.dumps(format="json"))
    # ≥1 recompilation event (the initial trace counts, kind="initial")
    rec = doc["mxnet_recompilations_total"]["samples"]
    assert sum(s["value"] for s in rec) >= 1
    # step-time histogram: count == 3 on the trainer path
    st = [s for s in doc["mxnet_step_time_seconds"]["samples"]
          if s["labels"].get("path") == "trainer"]
    assert len(st) == 1 and st[0]["count"] == 3
    assert st[0]["sum"] > 0
    # op dispatch counters flowed through the _tape.invoke funnel
    ops = doc["mxnet_op_dispatch_total"]["samples"]
    assert sum(s["value"] for s in ops) > 0
    assert all(s["labels"]["op"] for s in ops)
    # HBM gauge sampled (0 on CPU backends without memory_stats, but present)
    hbm = doc["mxnet_hbm_bytes_in_use"]["samples"]
    assert hbm and all("device" in s["labels"] for s in hbm)
    # examples throughput
    assert metrics.get_sample_value("mxnet_examples_total",
                                    {"path": "trainer"}) == 12

    # shape change mid-loop: retrace counter ticks, warning names the sig
    before = metrics.get_sample_value("mxnet_recompilations_total",
                                      {"kind": "retrace"}) or 0
    x2 = np.array(rng.rand(2, 4).astype("float32"))
    y2 = np.array(rng.rand(2, 2).astype("float32"))
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu"):
        with autograd.record():
            loss = loss_fn(net(x2), y2).mean()
        loss.backward()
        trainer.step(2)
    after = metrics.get_sample_value("mxnet_recompilations_total",
                                     {"kind": "retrace"})
    assert after >= before + 1
    warnings = [r.getMessage() for r in caplog.records
                if "recompilation" in r.getMessage()]
    assert any("(2, 4)" in w for w in warnings), warnings


def test_trainstep_records_step_metrics(fresh_metrics):
    from mxnet_tpu import parallel
    net = _tiny_net()
    rng = onp.random.RandomState(0)
    x = np.array(rng.rand(4, 4).astype("float32"))
    y = np.array(rng.rand(4, 2).astype("float32"))
    step = parallel.TrainStep(net, L2Loss(),
                              mx.optimizer.SGD(learning_rate=0.1),
                              example_inputs=[x])
    for _ in range(2):
        step(x, y)
    assert metrics.get_sample_value("mxnet_step_time_seconds_count",
                                    {"path": "train_step"}) == 2
    assert metrics.get_sample_value("mxnet_examples_total",
                                    {"path": "train_step"}) == 8
    assert metrics.get_sample_value("mxnet_recompilations_total",
                                    {"block": "TrainStep"}) >= 1
    assert (metrics.get_sample_value("mxnet_examples_per_sec",
                                     {"path": "train_step"}) or 0) > 0


def test_trainstep_alternating_shapes_not_recompiles(fresh_metrics):
    """jax.jit caches every seen signature: A/B/A/B batches compile twice
    total, so the retrace counter must read 1 — not one per alternation."""
    from mxnet_tpu import parallel
    net = _tiny_net()
    rng = onp.random.RandomState(0)
    xa = np.array(rng.rand(4, 4).astype("float32"))
    ya = np.array(rng.rand(4, 2).astype("float32"))
    xb = np.array(rng.rand(2, 4).astype("float32"))
    yb = np.array(rng.rand(2, 2).astype("float32"))
    step = parallel.TrainStep(net, L2Loss(),
                              mx.optimizer.SGD(learning_rate=0.1),
                              example_inputs=[xa])
    for _ in range(3):
        step(xa, ya)
        step(xb, yb)
    assert metrics.get_sample_value(
        "mxnet_recompilations_total",
        {"block": "TrainStep", "kind": "retrace"}) == 1
    assert metrics.get_sample_value(
        "mxnet_recompilations_total",
        {"block": "TrainStep", "kind": "initial"}) == 1


def test_trainstep_multi_step_compile_counted(fresh_metrics):
    """run(steps=N) compiles its own multi-step executable: a new N is a
    real compile event; repeating a known N is not."""
    from mxnet_tpu import parallel
    net = _tiny_net()
    rng = onp.random.RandomState(0)
    x = np.array(rng.rand(4, 4).astype("float32"))
    y = np.array(rng.rand(4, 2).astype("float32"))
    step = parallel.TrainStep(net, L2Loss(),
                              mx.optimizer.SGD(learning_rate=0.1),
                              example_inputs=[x])
    step(x, y)  # initial: (sig, single-step)
    before = metrics.get_sample_value(
        "mxnet_recompilations_total",
        {"block": "TrainStep", "kind": "retrace"}) or 0
    step.run(x, y, steps=2)  # same sig, NEW multi-step executable
    mid = metrics.get_sample_value(
        "mxnet_recompilations_total",
        {"block": "TrainStep", "kind": "retrace"})
    assert mid == before + 1
    step.run(x, y, steps=2)  # cached executable: no compile, no count
    assert metrics.get_sample_value(
        "mxnet_recompilations_total",
        {"block": "TrainStep", "kind": "retrace"}) == mid


def test_family_dedup_returns_live_instance():
    """Re-constructing a registered family (re-executed notebook cell)
    must hand back the live instance, not a silent orphan."""
    was = metrics.enabled()
    metrics.enable()
    reg = metrics.MetricsRegistry()
    try:
        c1 = metrics.Counter("t_dup_total", "x", registry=reg)
        c1.inc(2)
        c2 = metrics.Counter("t_dup_total", "other help", registry=reg)
        assert c2 is c1
        c2.inc(1)
        assert reg.get_sample_value("t_dup_total") == 3
        with pytest.raises(mx.MXNetError):
            metrics.Gauge("t_dup_total", registry=reg)  # type mismatch
        with pytest.raises(mx.MXNetError):
            metrics.Counter("t_dup_total", labels=("a",), registry=reg)
    finally:
        if not was:
            metrics.disable()


def test_cachedop_hits_vs_recompiles(fresh_metrics):
    net = _tiny_net()
    x = np.array(onp.random.RandomState(0).rand(4, 4).astype("float32"))
    net(x)
    net(x)
    net(x)
    hits = metrics.get_sample_value("mxnet_cachedop_cache_hits_total")
    initial = metrics.get_sample_value("mxnet_recompilations_total",
                                       {"kind": "initial"})
    assert initial == 1
    assert hits == 2


def test_dataloader_metrics(fresh_metrics):
    rng = onp.random.RandomState(0)
    ds = ArrayDataset(np.array(rng.rand(8, 3).astype("float32")))
    n = 0
    for _ in DataLoader(ds, batch_size=4):
        n += 1
    assert n == 2
    assert metrics.get_sample_value("mxnet_dataloader_batches_total") == 2
    assert metrics.get_sample_value(
        "mxnet_dataloader_batch_seconds_count") == 2
    # prefetching path exercises the queue-wait histogram
    for _ in DataLoader(ds, batch_size=4, num_workers=2):
        pass
    assert metrics.get_sample_value(
        "mxnet_dataloader_wait_seconds_count") >= 2


def test_collective_counters_at_trace_time(fresh_metrics):
    from mxnet_tpu import parallel
    from mxnet_tpu.parallel import collectives as coll
    shard_map = getattr(jax, "shard_map", None)
    if shard_map is None:
        from jax.experimental.shard_map import shard_map
    mesh = parallel.make_mesh({"x": 8})
    before = metrics.get_sample_value("mxnet_collective_calls_total",
                                      {"op": "allreduce"}) or 0

    fn = shard_map(lambda v: coll.allreduce(v, "x"), mesh=mesh,
                   in_specs=parallel.P("x"), out_specs=parallel.P())
    out = fn(jnp.arange(8.0, dtype=jnp.float32))
    onp.testing.assert_allclose(onp.asarray(out), 28.0)
    after = metrics.get_sample_value("mxnet_collective_calls_total",
                                     {"op": "allreduce"})
    assert after == before + 1
    # bytes = the traced operand (8 x f32 = 32 bytes per shard-local view)
    assert (metrics.get_sample_value("mxnet_collective_bytes_total",
                                     {"op": "allreduce"}) or 0) > 0


def test_disabled_fast_path_no_lock_no_alloc():
    """When nothing is enabled the instruments must not lock or allocate:
    labels() hands back the shared no-op child and value cells are never
    touched (the near-zero-cost-when-idle contract)."""
    was = metrics.enabled()
    metrics.disable()

    class _ForbiddenLock:
        def __enter__(self):
            raise AssertionError("metric lock acquired on the disabled path")

        def __exit__(self, *exc):
            return False

    reg = metrics.MetricsRegistry()
    try:
        labeled = metrics.Counter("t_disabled_total", "t", labels=("a",),
                                  registry=reg)
        assert labeled.labels(a="1") is metrics._NOOP
        assert labeled.children() == []  # no child allocated

        gauge = metrics.Gauge("t_disabled_gauge", "t", registry=reg)
        hist = metrics.Histogram("t_disabled_hist", "t", registry=reg)
        counter = metrics.Counter("t_disabled_plain_total", "t", registry=reg)
        for fam in (gauge, hist, counter):
            fam._unlabeled._lock = _ForbiddenLock()
        counter.inc()
        gauge.set(5.0)
        gauge.inc()
        gauge.dec()
        hist.observe(0.25)
        assert counter._unlabeled.value == 0
        assert gauge._unlabeled.value == 0
        assert hist._unlabeled.count == 0
    finally:
        if was:
            metrics.enable()


def test_prometheus_exposition_parses(fresh_metrics):
    mc = _load_metrics_check()
    x = np.array(onp.random.RandomState(0).rand(4, 4).astype("float32"))
    (x + x).asnumpy()
    text = metrics.expose()
    families = mc.parse_exposition(text)
    assert "mxnet_op_dispatch_total" in families
    assert families["mxnet_op_dispatch_seconds"]["type"] == "histogram"
    # histogram exposition carries _bucket/_sum/_count sample lines
    assert "mxnet_op_dispatch_seconds_bucket{" in text
    assert "mxnet_op_dispatch_seconds_count " in text
    # label escaping survives a round trip
    metrics.OP_DISPATCH.labels(op='weird"op\\name').inc()
    mc.parse_exposition(metrics.expose())


def test_metrics_check_tool_inprocess(fresh_metrics):
    mc = _load_metrics_check()
    summary = mc.run_check()
    assert summary["ok"]
    assert summary["recompilations"] >= 1
    assert summary["retraces"] >= 1
    assert summary["trainer_steps"] == 2


def test_pipeline_check_tool_inprocess(fresh_metrics):
    """CI guard for the async-pipeline metric families: pipelined loop
    bitwise-parity + DevicePrefetcher input waits + async checkpoint
    stall, validated through the exposition parser."""
    mc = _load_metrics_check()
    summary = mc.run_pipeline_check()
    assert summary["ok"]
    assert summary["bitwise_parity"]
    assert summary["input_waits"] >= 4
    assert summary["ckpt_stalls"] >= 1


def test_decode_check_tool_inprocess(fresh_metrics):
    """CI guard for the multi-token decode metric families: launch sites
    recorded at trace time (off-TPU all under kind=reference, none under
    a kernel's kind), round-trips << decode tokens."""
    mc = _load_metrics_check()
    summary = mc.run_decode_check()
    assert summary["ok"]
    assert summary["reference_sites"] >= 2 * (4 * 2 + 1)
    assert not any(summary["kernel_sites"].values())
    assert summary["decode_roundtrips"] < summary["decode_tokens"]


def test_spec_check_tool_inprocess(fresh_metrics):
    """CI guard for the self-speculative decode metric families: the
    drafted/accepted/rejected counters balance, the acceptance-rate
    gauge is exactly accepted/drafted, and speculation is token-exact
    vs the speculate=0 engine."""
    mc = _load_metrics_check()
    summary = mc.run_spec_check()
    assert summary["ok"]
    assert summary["rounds"] >= 1
    assert summary["drafted"] > 0
    assert 0.0 <= summary["acceptance_rate"] <= 1.0


def test_grammar_check_tool_inprocess(fresh_metrics):
    """CI guard for the grammar-constrained decode metric families: one
    session per constrained request, exactly one compile miss with its
    compile-seconds sample, memory- and disk-tier mask-cache hits for
    the same schema, grammar-dead drafts counted as rejections, and
    every completion schema-conformant by construction."""
    mc = _load_metrics_check()
    summary = mc.run_grammar_check()
    assert summary["ok"]
    assert summary["sessions"] == summary["conformant"] == 3
    assert summary["cache_misses"] == 1
    assert summary["memory_hits"] >= 1
    assert summary["disk_hits"] >= 1
    assert summary["rejected_tokens"] >= 1


def test_perf_check_tool_inprocess(fresh_metrics):
    """CI guard for the cost ledger + live roofline: every executable
    class built in the check (TrainStep, each serve prefill/decode
    bucket) lands in the ledger with XLA costs on the
    mxnet_executable_* gauges, the live mxnet_mfu gauge matches the
    offline flops/dt/peak arithmetic, steady-state steps stay silent
    under no_recompile(), and a regime verdict exists for decode.

    The check is of arithmetic agreement (live gauge vs ledger FLOPs /
    dt / peak), so the test names the chip whose peaks both sides divide
    by: the CPU it runs on has none on record, and perf refuses to
    invent them."""
    mc = _load_metrics_check()
    summary = mc.run_perf_check(chip="TPU v5 lite")
    assert summary["ok"]
    assert summary["train_flops"] > 0
    assert summary["train_peak_bytes"] > 0
    assert summary["serve_buckets"] >= 3
    assert summary["ledger_entries"] >= 1 + summary["serve_buckets"]
    # live gauge vs offline recompute: the 10% acceptance bound (the
    # check itself asserts it too; this pins the summary fields)
    assert abs(summary["mfu_live"] - summary["mfu_offline"]) \
        <= 0.1 * summary["mfu_offline"]
    assert summary["decode_regime"] in ("compute", "bandwidth",
                                        "overhead")


def test_tune_check_tool_inprocess(fresh_metrics):
    """CI guard for the autotuning metric families: the synthetic-surface
    search converges and counts every trial, the tuned-config cache
    round-trips with hit/miss counters and the active-config gauge, and
    a corrupted entry self-evicts to defaults with the error counted."""
    mc = _load_metrics_check()
    summary = mc.run_tune_check()
    assert summary["ok"]
    assert summary["best"] == {"serve_multi_token": 4,
                               "serve_prefill_chunk": 32}
    assert summary["trials"] >= 7
    assert summary["improvement"] > 0.5
    assert summary["cache_hits"] >= 1
    assert summary["cache_misses"] >= 1
    assert summary["corrupt_evictions"] >= 1


def test_zero_check_tool_inprocess(fresh_metrics):
    """CI guard for the ZeRO metric families: shard/opt-state gauges show
    the ~dp x per-replica shrink, the reduce-scatter vs quantized
    all-gather byte counters show the >= 3x wire saving, and the
    error-feedback residual gauges expose one finite sample per slot."""
    mc = _load_metrics_check()
    summary = mc.run_zero_check()
    assert summary["ok"]
    assert summary["dp"] == 8
    assert summary["opt_state_bytes_replicated"] >= \
        7 * summary["opt_state_bytes_per_replica"]
    assert summary["wire_saving_x"] >= 3.0
    assert summary["residual_slots"] == 4


def test_paging_check_tool_inprocess(fresh_metrics):
    """CI guard for the paged-KV + router metric families: prefix-cache
    hits/bytes saved, chunked-prefill chunks, COW forks, lease/release
    balance, per-replica dispatches and the drain-driven eject."""
    mc = _load_metrics_check()
    summary = mc.run_paging_check()
    assert summary["ok"]
    assert summary["prefix_hits"] >= 1
    assert summary["prefix_bytes_saved"] > 0
    assert summary["prefill_chunks"] >= 1
    assert summary["cow_forks"] >= 1
    assert summary["router_dispatches"] >= 6
    assert summary["router_ejects"] >= 1


def test_fleet_check_tool_inprocess(fresh_metrics):
    """CI guard for the self-managing fleet families: the autoscale
    controller's up/down decisions (and hysteresis suppressions) land on
    mxnet_fleet_scale_events_total, WFQ dispatch shares track the 3:1
    tenant weights over a saturated window with quota overflow rejected,
    and a live weight swap flips mxnet_serve_weight_version while
    changing greedy outputs."""
    mc = _load_metrics_check()
    summary = mc.run_fleet_check()
    assert summary["ok"]
    assert summary["scale_ups"] >= 1
    assert summary["scale_downs"] >= 1
    assert summary["suppressed_hysteresis"] >= 1
    assert 2.0 < summary["wfq_ratio"] < 4.5
    assert summary["quota_rejected"] >= 1
    assert summary["weight_version"] == 1
    assert summary["weight_swaps"] >= 1


def test_cache_check_tool_inprocess(fresh_metrics):
    """CI guard for the cache-aware fleet families: a bounded prefix
    advert reaches /healthz and converts into an affinity hit at the
    router (cold + hit outcomes, hit-tokens), a KV page migration
    round-trips token-exactly with a corrupted page REJECTED by the
    chain-hash verify, the sent == received + verify_failures balance
    holds exactly, and a tier-scoped scale decision lands on
    mxnet_fleet_tier_*."""
    mc = _load_metrics_check()
    summary = mc.run_cache_check()
    assert summary["ok"]
    assert summary["affinity_cold"] >= 1
    assert summary["affinity_hits"] >= 1
    assert summary["affinity_hit_tokens"] >= 16
    assert summary["verify_failures"] >= 1
    assert summary["pages_sent"] == (summary["pages_received"]
                                     + summary["verify_failures"])
    assert summary["tier_scale_ups"] >= 1
    assert summary["tier_replicas"] >= 1


def test_trace_check_tool_inprocess(fresh_metrics):
    """CI guard for the observability layer: one traced serving round
    yields a complete span tree under the client's traceparent id, the
    fleet aggregation merges counters/histograms with per-backend
    labels and re-renders parseable exposition, the SLO tracker burns
    budget on an impossible target, and a flight-recorder dump is
    well-formed."""
    mc = _load_metrics_check()
    summary = mc.run_trace_check()
    assert summary["ok"]
    assert summary["trace_id"] == "11" * 16
    assert set(mc.REQUIRED_REQUEST_SPANS) <= set(summary["span_names"])
    assert summary["slo_burn_tight"] > 1.0
    assert summary["recorder_events"] >= 1
    assert os.path.exists(summary["recorder_dump"])


def test_elastic_check_tool_inprocess(fresh_metrics):
    """CI guard for the elastic metric families: one simulated
    kill-a-worker drill (dp=4 -> 3) exposes heartbeat send/age samples,
    exactly one peer_lost over the heartbeat window with detect/reform/
    restore phase histograms, the epoch/world gauges at the re-formed
    values, and a flight-recorder dump on reason=peer_lost."""
    mc = _load_metrics_check()
    summary = mc.run_elastic_check()
    assert summary["ok"]
    assert summary["peer_lost"] == 1
    assert summary["final_dp"] == 3 and summary["epoch"] == 1
    assert summary["reforms"] == 1
    assert summary["hb_sent"] >= 10
    assert 0 <= summary["detect_latency_s"] <= 5.0
    assert os.path.exists(summary["dump_path"])


def test_health_check_tool_inprocess(fresh_metrics):
    """CI guard for the mxhealth metric families: a health-on TrainStep
    over clean steps plus one NaN-poisoned batch exposes every
    mxnet_health_* family (one kind=nonfinite anomaly, nonzero nonfinite
    grad count, a reason=numeric_anomaly dump) and the AMP LossScaler's
    calibration rounds expose the mxnet_amp_* families."""
    mc = _load_metrics_check()
    summary = mc.run_health_check()
    assert summary["ok"]
    assert summary["anomalies"] == 1
    assert summary["nonfinite_grads"] > 0
    assert summary["last_anomaly_step"] >= 1
    assert os.path.exists(summary["dump"])


def test_counter_bridges_into_chrome_trace(fresh_metrics):
    """Metric updates appear as live 'C' events on the profiler timeline
    while it is ACTIVE, with viewer-required pid/tid/cat fields."""
    profiler._EVENTS.clear()
    profiler.set_state("run")
    try:
        x = np.array(onp.random.RandomState(0).rand(2, 2).astype("float32"))
        (x * 2).asnumpy()
    finally:
        profiler.set_state("stop")
    counters = [e for e in profiler._EVENTS if e["ph"] == "C"]
    assert counters, "no counter events bridged into the trace"
    for e in counters:
        assert "tid" in e and "cat" in e and "pid" in e
    assert any(e["name"].startswith("mxnet_op_dispatch_total") for e in counters)
    profiler._EVENTS.clear()


def test_nonfinite_values_expose_without_crashing(fresh_metrics):
    """Prometheus text format supports +Inf/-Inf/NaN; the scrape path must
    render them instead of dying on int() (telemetry never takes the
    workload down)."""
    reg = metrics.MetricsRegistry()
    g = metrics.Gauge("t_inf_gauge", "t", registry=reg)
    g.set(float("inf"))
    h = metrics.Histogram("t_inf_hist", "t", registry=reg)
    h.observe(float("nan"))
    text = reg.expose()
    assert "t_inf_gauge +Inf" in text
    assert "NaN" in text
    reg.dumps(format="table")  # must not raise either
    g.set(float("-inf"))
    assert "t_inf_gauge -Inf" in reg.expose()


def test_histogram_bucket_mismatch_raises():
    reg = metrics.MetricsRegistry()
    metrics.Histogram("t_bkt_hist", "t", registry=reg, buckets=(0.1, 1.0))
    h2 = metrics.Histogram("t_bkt_hist", "t", registry=reg,
                           buckets=(1.0, 0.1))  # same set, order-free
    assert h2.buckets == (0.1, 1.0)
    with pytest.raises(mx.MXNetError):
        metrics.Histogram("t_bkt_hist", "t", registry=reg,
                          buckets=(10.0, 100.0))


def test_registry_reset_and_table(fresh_metrics):
    metrics.OP_DISPATCH.labels(op="x").inc(3)
    assert metrics.get_sample_value("mxnet_op_dispatch_total",
                                    {"op": "x"}) == 3
    table = metrics.dumps(format="table")
    assert "mxnet_op_dispatch_total" in table
    metrics.reset()
    assert metrics.get_sample_value("mxnet_op_dispatch_total",
                                    {"op": "x"}) is None
    with pytest.raises(mx.MXNetError):
        metrics.dumps(format="xml")
