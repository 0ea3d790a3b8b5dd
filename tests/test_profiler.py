"""Profiler wiring: the runtime actually records events (reference feeds the
profiler from engine dispatch, src/profiler/profiler.h:263; here the hooks
are _tape.invoke, CachedOp, TrainStep, DataLoader)."""
import json
import os
import tempfile

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import np, autograd, profiler
from mxnet_tpu.gluon import nn, Trainer
from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
from mxnet_tpu.gluon.loss import L2Loss


def _categories(events):
    return {e.get("cat") for e in events if "cat" in e}


def test_runtime_records_events():
    profiler._EVENTS.clear()
    profiler._AGG.clear()
    profiler.set_state("run")
    try:
        # eager ops -> 'operation' events
        net = nn.HybridSequential()
        net.add(nn.Dense(8, in_units=4), nn.Dense(2))
        net.initialize()
        x = np.array(onp.random.RandomState(0).randn(4, 4).astype("float32"))
        y = np.array(onp.random.RandomState(1).randn(4, 2).astype("float32"))
        trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
        with autograd.record():
            loss = L2Loss()(net(x), y).mean()
        loss.backward()
        trainer.step(1)

        # hybridized -> 'cached_op' events
        net.hybridize()
        net(x)
        net(x)

        # TrainStep -> 'train' events
        from mxnet_tpu import parallel
        step = parallel.TrainStep(net, L2Loss(),
                                  mx.optimizer.SGD(learning_rate=0.1),
                                  example_inputs=[x])
        step(x, y)

        # DataLoader -> 'data' events
        ds = ArrayDataset(np.array(onp.random.rand(8, 3).astype("float32")))
        for _ in DataLoader(ds, batch_size=4):
            pass
    finally:
        profiler.set_state("stop")

    cats = _categories(profiler._EVENTS)
    assert "operation" in cats
    assert "cached_op" in cats
    assert "train" in cats
    assert "data" in cats
    names = {e["name"] for e in profiler._EVENTS}
    assert "mx.train.step" in names
    assert any(n.startswith("CachedOp::") for n in names)

    # aggregate table has rows
    table = profiler.dumps()
    assert "mx.train.step" in table

    # chrome trace round trip
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "trace.json")
        profiler.set_config(filename=path)
        profiler.dump()
        with open(path) as f:
            payload = json.load(f)
    assert len(payload["traceEvents"]) > 0


def test_profiler_off_records_nothing():
    profiler._EVENTS.clear()
    assert profiler.state() == "stop"
    x = np.array(onp.random.rand(4, 4).astype("float32"))
    (x + x).asnumpy()
    assert profiler._EVENTS == []


def test_pause_resume():
    profiler._EVENTS.clear()
    profiler.set_state("run")
    try:
        profiler.pause()
        x = np.array(onp.random.rand(2, 2).astype("float32"))
        (x * 2).asnumpy()
        n_paused = len(profiler._EVENTS)
        profiler.resume()
        (x * 2).asnumpy()
        assert len(profiler._EVENTS) > n_paused or n_paused == 0
    finally:
        profiler.set_state("stop")


def test_dump_honors_finished_and_continuous():
    """dump(finished=True) flushes (no duplicated ever-growing buffer);
    continuous_dump keeps accumulating for periodic snapshots."""
    profiler._EVENTS.clear()
    prev_name = profiler._CONFIG["filename"]
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "trace.json")
        profiler.set_config(filename=path, continuous_dump=False)
        profiler.set_state("run")
        try:
            with profiler.scope("span_a", "custom"):
                pass
            profiler.dump()  # finished=True: flush + clear
            with open(path) as f:
                first = json.load(f)["traceEvents"]
            assert [e["name"] for e in first] == ["span_a"]
            assert profiler._EVENTS == []
            with profiler.scope("span_b", "custom"):
                pass
            profiler.dump()
            with open(path) as f:
                second = json.load(f)["traceEvents"]
            # no duplication of span_a in the second dump
            assert [e["name"] for e in second] == ["span_b"]

            # continuous mode: plain dump() follows the config — cumulative
            # snapshots, nothing cleared
            profiler.set_config(continuous_dump=True)
            with profiler.scope("span_c", "custom"):
                pass
            profiler.dump()
            with profiler.scope("span_d", "custom"):
                pass
            profiler.dump()
            with open(path) as f:
                snap = [e["name"] for e in json.load(f)["traceEvents"]]
            assert snap == ["span_c", "span_d"]
        finally:
            profiler.set_state("stop")
            profiler.set_config(filename=prev_name, continuous_dump=False)
            profiler._EVENTS.clear()


def test_event_cap_and_dropped_counter():
    profiler._EVENTS.clear()
    prev_cap = profiler._CONFIG["max_events"]
    d0 = profiler.dropped_events()
    profiler.set_config(max_events=3)
    profiler.set_state("run")
    try:
        for i in range(10):
            with profiler.scope(f"s{i}", "custom"):
                pass
    finally:
        profiler.set_state("stop")
        profiler.set_config(max_events=prev_cap)
    assert len(profiler._EVENTS) == 3
    assert profiler.dropped_events() == d0 + 7
    # a finished dump reports the cumulative drop count; the counter is
    # MONOTONE (a valid Prometheus counter) so the dump must not reset it
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "trace.json")
        prev_name = profiler._CONFIG["filename"]
        profiler.set_config(filename=path)
        profiler.dump()
        profiler.set_config(filename=prev_name)
        with open(path) as f:
            payload = json.load(f)
    assert payload["otherData"]["droppedEvents"] == d0 + 7
    assert profiler.dropped_events() == d0 + 7
    assert profiler._EVENTS == []


def test_counter_marker_events_have_tid_and_cat():
    """Chrome-trace conformance: 'C' and 'i' events carry the same pid/tid
    (and a cat) as 'X' spans so viewers lane them correctly."""
    profiler._EVENTS.clear()
    profiler.set_state("run")
    try:
        c = profiler.Counter(name="conf_c")
        c.increment(2)
        profiler.Marker(name="conf_m").mark()
    finally:
        profiler.set_state("stop")
    by_ph = {e["ph"]: e for e in profiler._EVENTS}
    for ph in ("C", "i"):
        ev = by_ph[ph]
        assert "tid" in ev and "cat" in ev and ev["pid"] == 0
        assert ev["ts"] >= 0
    profiler._EVENTS.clear()


def test_record_span_negative_ts_clamped():
    """A span whose t0 predates set_state('run') must clamp ts to 0 (not
    emit a viewer-invalid negative timestamp)."""
    import time as _time
    profiler._EVENTS.clear()
    t_before = _time.perf_counter()
    profiler.set_state("run")
    try:
        profiler.record_span("early", "custom", t_before - 0.5,
                             _time.perf_counter())
    finally:
        profiler.set_state("stop")
    ev = [e for e in profiler._EVENTS if e["name"] == "early"][0]
    assert ev["ts"] == 0.0
    assert ev["dur"] >= 0.0
    profiler._EVENTS.clear()


def test_dumps_json_format():
    profiler._EVENTS.clear()
    profiler._AGG.clear()
    profiler.set_state("run")
    try:
        with profiler.scope("agg_span", "custom"):
            pass
    finally:
        profiler.set_state("stop")
    rows = json.loads(profiler.dumps(format="json"))
    row = [r for r in rows if r["name"] == "agg_span"][0]
    assert row["count"] == 1
    assert set(row) == {"name", "count", "total_us", "min_us", "max_us",
                        "avg_us"}
    assert row["min_us"] <= row["avg_us"] <= row["max_us"]
    profiler._EVENTS.clear()
    profiler._AGG.clear()


def test_device_memory_stats_cpu_backend():
    """PJRT memory_stats on the CPU backend: returns a dict (possibly
    empty — CPU reports no stats) and never raises."""
    stats = profiler.device_memory_stats()
    assert isinstance(stats, dict)
    import pytest as _pytest
    from mxnet_tpu.base import MXNetError
    with _pytest.raises(MXNetError):
        profiler.device_memory_stats(device_id=10**6)


def test_scope_and_markers():
    profiler._EVENTS.clear()
    profiler.set_state("run")
    try:
        with profiler.scope("my_region", "custom"):
            pass
        t = profiler.Task(name="t1")
        t.start()
        t.stop()
        c = profiler.Counter(name="c1")
        c.increment(3)
        profiler.Marker(name="m1").mark()
    finally:
        profiler.set_state("stop")
    names = {e["name"] for e in profiler._EVENTS}
    assert {"my_region", "t1", "c1", "m1"} <= names
