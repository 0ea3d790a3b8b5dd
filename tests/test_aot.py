"""Persistent AOT compile cache (mxnet_tpu/aot): disk round-trips must be
bitwise-identical to fresh compiles, corruption must degrade to recompile
(never crash), and a warm serve warmup must beat cold by the restore
margin."""
import json
import os
import struct
import time

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import aot, metrics, np
from mxnet_tpu.aot import cache as aot_cache_mod
from mxnet_tpu.gluon import nn


@pytest.fixture
def aot_dir(tmp_path):
    """Fresh enabled cache per test; disabled again afterwards so the rest
    of the suite keeps the exact pre-AOT compile behavior."""
    cache = aot.enable(str(tmp_path / "aot"))
    yield cache
    aot.disable()


@pytest.fixture
def metrics_on():
    was = metrics.enabled()
    metrics.reset()
    metrics.enable()
    yield
    if not was:
        metrics.disable()
    metrics.reset()


def _hits(label=None):
    labels = {"block": label} if label else None
    return metrics.get_sample_value("mxnet_aot_cache_hits_total",
                                    labels) or 0


def _misses(label=None):
    labels = {"block": label} if label else None
    return metrics.get_sample_value("mxnet_aot_cache_misses_total",
                                    labels) or 0


def _errors(kind=None):
    labels = {"kind": kind} if kind else None
    return metrics.get_sample_value("mxnet_aot_cache_errors_total",
                                    labels) or 0


# ------------------------------------------------------------------ cache
def test_entry_roundtrip_atomic_layout(aot_dir):
    payload = b"x" * 1000
    key = "ab" + "0" * 62
    aot_dir.put(key, payload, label="t", meta={"note": "hi"})
    hdr, got = aot_dir.get(key)
    assert got == payload
    assert hdr["label"] == "t" and hdr["kind"] == aot.KIND_EXECUTABLE
    assert hdr["meta"]["note"] == "hi"
    # sharded layout + no tmp litter from the atomic write
    path = aot_dir._entry_path(key)
    assert os.path.exists(path) and "/ab/" in path
    assert not [f for f in os.listdir(os.path.dirname(path))
                if f.startswith(".tmp-")]
    assert aot_dir.contains(key) and not aot_dir.contains("ff" + "0" * 62)
    assert aot_dir.total_bytes() > len(payload)


def test_corrupt_entries_read_as_miss_and_evict(aot_dir, metrics_on):
    key = "cd" + "1" * 62
    aot_dir.put(key, b"payload-bytes", label="t")
    path = aot_dir._entry_path(key)

    # truncated payload
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:-4])
    assert aot_dir.get(key) is None
    assert not os.path.exists(path)  # evicted, not left to fail again

    # garbage magic
    aot_dir.put(key, b"payload-bytes", label="t")
    with open(path, "wb") as f:
        f.write(b"NOTMAGIC" + blob[8:])
    assert aot_dir.get(key) is None

    # flipped payload byte (checksum)
    aot_dir.put(key, b"payload-bytes", label="t")
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:-1] + bytes([blob[-1] ^ 0xFF]))
    assert aot_dir.get(key) is None
    assert _errors("corrupt") >= 3


def test_stale_format_version_reads_as_miss(aot_dir, monkeypatch):
    key = "ef" + "2" * 62
    monkeypatch.setattr(aot_cache_mod, "FORMAT_VERSION", 999)
    aot_dir.put(key, b"old-format-payload", label="t")
    monkeypatch.undo()
    assert aot_dir.get(key) is None  # versioned header -> clean miss


def test_lru_cap_evicts_oldest(tmp_path):
    # each entry is ~1.3 KB (payload + header); cap fits three, not four
    cache = aot.AotCache(str(tmp_path / "lru"), max_bytes=4500)
    for i, key in enumerate(["aa" + str(i) * 62 for i in range(3)]):
        cache.put(key, bytes(1000), label=f"e{i}")
        time.sleep(0.02)  # distinct mtimes for LRU ordering
    # touching entry 0 makes entry 1 the LRU victim of the next insert
    assert cache.get("aa" + "0" * 62) is not None
    time.sleep(0.02)
    cache.put("bb" + "9" * 62, bytes(1000), label="new")
    assert cache.contains("aa" + "0" * 62)
    assert not cache.contains("aa" + "1" * 62)
    assert cache.contains("bb" + "9" * 62)


def test_fingerprint_content_addressing():
    f = jax.jit(lambda x: x * 2 + 1)
    g = jax.jit(lambda x: x * 3 + 1)
    a32 = jax.ShapeDtypeStruct((4,), jnp.float32)
    a64 = jax.ShapeDtypeStruct((8,), jnp.float32)
    k1 = aot.fingerprint(f.lower(a32))
    assert k1 == aot.fingerprint(f.lower(a32))  # deterministic
    assert k1 != aot.fingerprint(f.lower(a64))  # shape in the address
    assert k1 != aot.fingerprint(g.lower(a32))  # program in the address
    assert k1 != aot.fingerprint(f.lower(a32), extra={"donate": True})


def test_compile_cached_noop_without_cache():
    aot.disable()
    jitted = jax.jit(lambda x: x + 1)
    assert aot.compile_cached(jitted, (jnp.ones(3),), label="t") is jitted


def test_unserializable_executable_leaves_signature_stub(
        aot_dir, metrics_on, monkeypatch):
    from jax.experimental import serialize_executable as se

    def boom(compiled):
        raise ValueError("not serializable")

    monkeypatch.setattr(se, "serialize", boom)
    jitted = jax.jit(lambda x: x * 2)
    fn = aot.compile_cached(jitted, (jnp.ones(3),), label="stub")
    assert float(fn(jnp.ones(3))[0]) == 2.0
    assert _errors("serialize") == 1
    entries = aot_dir.entries()
    assert len(entries) == 1 and entries[0]["kind"] == aot.KIND_SIGNATURE
    monkeypatch.undo()
    # the stub is honored: compile again, no second serialize attempt is
    # recorded as an error and the entry stays a stub (miss, not a crash)
    fn2 = aot.compile_cached(jax.jit(lambda x: x * 2), (jnp.ones(3),),
                             label="stub")
    assert float(fn2(jnp.ones(3))[0]) == 2.0
    assert _errors("serialize") == 1
    assert _misses("stub") == 2
    assert aot_dir.entries()[0]["kind"] == aot.KIND_SIGNATURE


def test_entry_stored_after_a_jax_cache_hit_restores_and_runs(tmp_path):
    """An AOT miss that jax's own persistent compilation cache answers
    still stores an executable that restores and runs: XLA:CPU serializes
    an executable that jax's cache loaded without its code."""
    from jax.experimental.compilation_cache import compilation_cache as jcc

    def jitted():                 # a new function each time: no shared trace
        def f(p, x):
            h = jnp.tanh(p)
            return jnp.where(x[:, None] > 0, h + 1.0, h * 2.0).sum(0)
        return jax.jit(f)

    args = (jnp.ones((16, 8)), jnp.arange(16) - 3)
    want = onp.asarray(jitted()(*args))
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    jcc.reset_cache()
    jax.config.update(keys[0], str(tmp_path / "jax"))
    jax.config.update(keys[1], 0)
    try:
        aot.enable(str(tmp_path / "first"))
        aot.compile_cached(jitted(), args, label="t")    # fills jax's cache
        aot.enable(str(tmp_path / "second"))
        aot.compile_cached(jitted(), args, label="t")    # jax's cache answers
        fn = aot.compile_cached(jitted(), args, label="t")
        assert fn.from_cache
        assert onp.array_equal(onp.asarray(fn(*args)), want)
    finally:
        aot.disable()
        for k, v in saved.items():
            jax.config.update(k, v)
        jcc.reset_cache()


# ------------------------------------------------------------- integration
def _dense_net():
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.Dense(2))
    net.initialize()
    net.hybridize()
    return net


def test_cachedop_roundtrip_bitwise(aot_dir, metrics_on):
    x = np.array(onp.random.RandomState(0).rand(4, 4).astype("float32"))
    y1 = _dense_net()(x).asnumpy()          # cold: compile + store
    assert _misses("cachedop_HybridSequential") == 1
    y2 = _dense_net()(x).asnumpy()          # fresh CachedOp: disk restore
    assert _hits("cachedop_HybridSequential") == 1
    assert (y1 == y2).all()                  # bitwise, not allclose
    kinds = {e["kind"] for e in aot_dir.entries()}
    assert kinds == {aot.KIND_EXECUTABLE}


def test_cachedop_backward_through_restored_executable(aot_dir, metrics_on):
    """autograd's backward replays the recorded fn under jax.vjp with
    TRACER args, which a restored Compiled cannot run — the wrapper must
    delegate tracer calls to the traceable jit WITHOUT burning the
    compiled fast path or logging a bogus signature mismatch
    (regression: training through an AOT-restored CachedOp)."""
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.loss import L2Loss
    rng = onp.random.RandomState(0)
    x = np.array(rng.rand(4, 4).astype("float32"))
    y = np.array(rng.rand(4, 2).astype("float32"))

    def grads(net):
        with autograd.record():
            loss = L2Loss()(net(x), y).mean()
        loss.backward()
        return [p.grad().asnumpy() for p in net.collect_params().values()]

    g_cold = grads(_dense_net())             # compile + store
    g_warm = grads(_dense_net())             # restored executable
    assert _hits("cachedop_HybridSequential") >= 1
    for a, b in zip(g_cold, g_warm):
        assert (a == b).all()
    assert _errors("signature_mismatch") == 0


def test_cachedop_corrupt_cache_recompiles(aot_dir, metrics_on):
    x = np.array(onp.random.RandomState(0).rand(4, 4).astype("float32"))
    y1 = _dense_net()(x).asnumpy()
    for e in aot_dir.entries():              # corrupt every stored entry
        path = aot_dir._entry_path(e["key"])
        with open(path, "r+b") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.truncate(max(size - 16, 1))
    y2 = _dense_net()(x).asnumpy()           # falls back to fresh compile
    assert (y1 == y2).all()
    assert _errors("corrupt") >= 1
    assert _hits() == 0


def _train_step():
    from mxnet_tpu.gluon.loss import L2Loss
    from mxnet_tpu.parallel import TrainStep
    net = _dense_net()
    x0 = np.array(onp.ones((4, 4), onp.float32))
    return TrainStep(net, L2Loss(), mx.optimizer.SGD(learning_rate=0.1),
                     example_inputs=[x0])


def test_trainstep_roundtrip_bitwise(aot_dir, metrics_on):
    rng = onp.random.RandomState(0)
    x = np.array(rng.rand(4, 4).astype("float32"))
    y = np.array(rng.rand(4, 2).astype("float32"))
    s1 = _train_step()
    cold = [s1(x, y).item(), s1(x, y).item(),
            s1.run(x, y, steps=3).item()]
    assert _misses("train_step") == 1 and _misses("train_step_multi") == 1
    s2 = _train_step()                       # fresh process path
    warm = [s2(x, y).item(), s2(x, y).item(),
            s2.run(x, y, steps=3).item()]
    assert _hits("train_step") == 1 and _hits("train_step_multi") == 1
    assert cold == warm                      # bitwise across the restore


def _tiny_engine():
    from mxnet_tpu.models import GPTModel
    from mxnet_tpu.models.gpt import GPTConfig
    from mxnet_tpu.serve import InferenceEngine
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=32, hidden_size=16, num_layers=2,
                             num_heads=2, max_position_embeddings=64,
                             dropout=0.0))
    net.initialize()
    return InferenceEngine(net, max_batch_size=2, max_len=16,
                           min_prompt_bucket=4)


def test_serve_bucket_roundtrip_bitwise(aot_dir, metrics_on):
    e1 = _tiny_engine().warmup()
    assert _misses() > 0 and _hits() == 0
    e2 = _tiny_engine().warmup()             # whole ladder from disk
    assert _hits() >= 1
    with e1:
        r1 = e1.generate([1, 2, 3], 6, temperature=0.7, top_k=4,
                         seed=11).generated_ids
    with e2:
        r2 = e2.generate([1, 2, 3], 6, temperature=0.7, top_k=4,
                         seed=11).generated_ids
    assert r1 == r2                          # restored executables sample
    assert e2.last_warmup_s is not None      # identically


@pytest.mark.slow
def test_serve_warm_warmup_speedup(aot_dir, metrics_on):
    """Warm warmup must be a PURE RESTORE of the whole bucket ladder.

    Deterministic assertions carry the test: the warm warmups record AOT
    hits and ZERO new misses/compiles (every executable came off disk).
    The wall-clock ratio is only a loose sanity bound — this box's timing
    jitter made the old >=3x assertion flaky under CI load (the real
    3.4-4.4x acceptance number is measured and recorded in BENCH json by
    bench.py's aot round, where the run owns the machine)."""
    import sys

    from mxnet_tpu.serve import InferenceEngine

    # the literal loadgen-harness model (shared definition)
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        from serve_loadgen import DEFAULTS, default_model
    finally:
        sys.path.pop(0)

    def engine():
        return InferenceEngine(default_model(),
                               max_batch_size=DEFAULTS["max_batch_size"],
                               max_len=DEFAULTS["max_len"])

    cold = engine().warmup().last_warmup_s
    assert _hits() == 0
    misses_cold = _misses()
    compiles_cold = metrics.get_sample_value(
        "mxnet_aot_compile_seconds_count") or 0
    warm = min(engine().warmup().last_warmup_s,
               engine().warmup().last_warmup_s)
    # every ladder entry restored from disk: hits grew, misses did not,
    # and the AOT layer recorded no new XLA compiles
    assert _hits() >= 1
    assert _misses() == misses_cold
    assert (metrics.get_sample_value(
        "mxnet_aot_compile_seconds_count") or 0) == compiles_cold
    # loose wall-clock sanity only (deserialize beats compile, with slack
    # for CPU CI noise); min-of-two warms already damps scheduler jitter
    assert cold / warm >= 1.2, (cold, warm)
    # warmup-time histogram carries the cold AND warm observations
    n = metrics.get_sample_value("mxnet_aot_warmup_seconds_count",
                                 {"path": "serve"})
    assert n == 3


# --------------------------------------------------------------- manifest
def test_manifest_roundtrip_and_verify(aot_dir, tmp_path):
    aot_dir.put("aa" + "0" * 62, b"one", label="serve_prefill")
    aot_dir.put("bb" + "1" * 62, b"two", label="serve_decode")
    path = str(tmp_path / "m.json")
    aot.write_manifest(path, "gpt-test", {"hidden": 16},
                       aot_dir.touched + aot_dir.touched)  # dupes collapse
    doc = aot.read_manifest(path)
    assert doc["model"] == "gpt-test" and len(doc["entries"]) == 2
    res = aot.verify_manifest(doc, aot_dir)
    assert res["ok"] and len(res["present"]) == 2
    os.unlink(aot_dir._entry_path("bb" + "1" * 62))
    res = aot.verify_manifest(doc, aot_dir)
    assert not res["ok"] and res["missing"] == ["bb" + "1" * 62]
    # versioned: future manifests fail loudly, not subtly
    doc_raw = json.load(open(path))
    doc_raw["version"] = 99
    with open(path, "w") as f:
        json.dump(doc_raw, f)
    with pytest.raises(mx.MXNetError, match="version"):
        aot.read_manifest(path)


def test_metrics_check_aot_families():
    """CI wiring: tools/metrics_check.run_aot_check validates the whole
    mxnet_aot_* exposition after one store-then-restore cycle."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import metrics_check
    finally:
        sys.path.pop(0)
    out = metrics_check.run_aot_check()
    assert out["ok"] and out["aot_hits"] >= 1 and out["aot_misses"] >= 1
