"""mxtune (mxnet_tpu/tune): the tuned-config layer, the content-
addressed config cache, and the noise-aware search.

The acceptance contract: with no tuned config present every consulting
site resolves to exactly the constant it used to hard-code (bitwise
parity with the hand-picked path); a corrupt entry self-evicts to
defaults; a key mismatch falls back to defaults; the search converges on
a deterministic synthetic cost surface with a schedule that is
reproducible given its seed; and the mxtune CLI's geometry workload
finds a >= 10% win over the defaults and persists it.

The cache/search tests are pure python (no jax program is ever built);
the parity tests that need a model import jax inside the test body.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from mxnet_tpu import tune
from mxnet_tpu.tune import Param, cache as tune_cache, config as tune_config

REPO = os.path.join(os.path.dirname(__file__), "..")
_TOOLS = os.path.join(REPO, "tools")


def _load_mxtune():
    spec = importlib.util.spec_from_file_location(
        "mxtune", os.path.join(_TOOLS, "mxtune.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def tune_dir(tmp_path):
    """A fresh enabled config cache; restores the prior process state."""
    prev = tune.get_cache()
    cache = tune.enable(str(tmp_path / "tuned"))
    yield cache
    if prev is not None:
        tune.enable(prev.path)
    else:
        tune.disable()
    tune.deactivate_all()


@pytest.fixture
def no_tune():
    """No cache, no activations — the hand-picked-defaults world."""
    prev = tune.get_cache()
    tune.disable()
    tune.deactivate_all()
    yield
    if prev is not None:
        tune.enable(prev.path)
    tune.deactivate_all()


# ============================================================ key discipline
def test_config_key_stable_and_context_sensitive(no_tune):
    ctx = {"model": "GPTModel", "hidden": 32, "max_len": 96}
    k1 = tune.config_key("serve", ctx)
    k2 = tune.config_key("serve", dict(reversed(list(ctx.items()))))
    assert k1 == k2, "dict ordering must not fork the key"
    assert tune.config_key("serve", {**ctx, "hidden": 64}) != k1
    assert tune.config_key("global", ctx) != k1
    assert len(k1) == 64  # sha256 hex


def test_cache_round_trip(tune_dir):
    key = tune.config_key("serve", {"a": 1})
    payload = {"knobs": {"serve_multi_token": 4}, "context": {"a": 1}}
    tune_dir.put(key, "serve", payload, label="t")
    doc = tune_dir.get(key, site="serve")
    assert doc["payload"] == payload
    assert doc["site"] == "serve" and doc["label"] == "t"
    assert tune_dir.contains(key)
    assert [e["key"] for e in tune_dir.entries()] == [key]


def test_cache_corruption_self_evicts_to_defaults(tune_dir):
    ctx = {"w": "corrupt-case"}
    key = tune.config_key("serve", ctx)
    tune_dir.put(key, "serve", {"knobs": {"serve_multi_token": 8},
                                "context": ctx})
    tune.invalidate()
    assert tune.lookup("serve", ctx) == {"serve_multi_token": 8}

    for garbage in ("{ not json", "", json.dumps({"format": "wrong"})):
        tune_dir.put(key, "serve", {"knobs": {"serve_multi_token": 8},
                                    "context": ctx})
        with open(tune_dir._entry_path(key), "w") as f:
            f.write(garbage)
        tune.invalidate()
        assert tune.lookup("serve", ctx) == {}, garbage
        assert not os.path.exists(tune_dir._entry_path(key))
    # checksum mismatch (payload edited in place) is corruption too
    tune_dir.put(key, "serve", {"knobs": {"serve_multi_token": 8},
                                "context": ctx})
    with open(tune_dir._entry_path(key)) as f:
        doc = json.load(f)
    doc["payload"]["knobs"]["serve_multi_token"] = 2
    with open(tune_dir._entry_path(key), "w") as f:
        json.dump(doc, f)
    tune.invalidate()
    assert tune.lookup("serve", ctx) == {}
    # and the resolving knob is back to its hand-picked default
    assert tune.get_knob("serve_multi_token", ctx) == 1


def test_key_mismatch_falls_back_to_defaults(tune_dir):
    ctx_a = {"model": "GPTModel", "hidden": 32}
    tune_dir.put(tune.config_key("serve", ctx_a), "serve",
                 {"knobs": {"serve_min_prompt_bucket": 2},
                  "context": ctx_a})
    tune.invalidate()
    # a different context (other dims / other model) resolves nothing
    assert tune.lookup("serve", {"model": "GPTModel", "hidden": 64}) == {}
    assert tune.get_knob("serve_min_prompt_bucket",
                         {"model": "GPTModel", "hidden": 64}) == 8


def test_unknown_knobs_in_payload_dropped(tune_dir):
    ctx = {"w": "unknown-knob"}
    key = tune.config_key("serve", ctx)
    tune_dir.put(key, "serve",
                 {"knobs": {"serve_multi_token": 2, "from_the_future": 7,
                            "gemv_max_m": 32,      # wrong site
                            "serve_page_size": "big",       # ill-typed
                            "serve_min_prompt_bucket": 3,   # not pow2
                            "serve_bucket_growth": 99},     # out of range
                  "context": ctx})
    tune.invalidate()
    # everything unknown / wrong-site / ill-typed / semantically invalid
    # is dropped — a bad stored value degrades to the default instead of
    # crashing an engine constructor
    assert tune.lookup("serve", ctx) == {"serve_multi_token": 2}


def test_defaults_pin_the_hand_picked_constants(no_tune):
    """The tuned-config defaults ARE the constants they replaced — the
    two definitions must never drift apart."""
    from mxnet_tpu.kvstore.quant import DEFAULT_BLOCK, default_block
    from mxnet_tpu.ops.int8_gemv import _GEMV_MAX_M, gemv_max_m
    assert tune.knob_default("gemv_max_m") == _GEMV_MAX_M == gemv_max_m()
    assert tune.knob_default("quant_block") == DEFAULT_BLOCK \
        == default_block()
    assert tune.knob_default("serve_min_prompt_bucket") == 8
    assert tune.knob_default("serve_bucket_growth") == 2
    assert tune.knob_default("serve_page_size") == 16
    assert tune.knob_default("serve_multi_token") == 1
    assert tune.knob_default("gemv_int4_block") == 128


def test_int4_block_knob_validator(no_tune, monkeypatch):
    """Invalid env/stored values for the int4 block knob degrade to the
    default: non-positive and odd blocks are rejected."""
    for env, bad, good, default in (
            ("MXNET_TUNE_GEMV_INT4_BLOCK", ("0", "-128", "127"), "64",
             128),):
        knob = env[len("MXNET_TUNE_"):].lower()
        for v in bad:
            monkeypatch.setenv(env, v)
            assert tune.get_knob(knob) == default, (knob, v)
        monkeypatch.setenv(env, good)
        assert tune.get_knob(knob) == int(good)
        monkeypatch.delenv(env)


def test_env_override_beats_tuned_and_default(tune_dir, monkeypatch):
    ctx = {"w": "env-case"}
    tune_dir.put(tune.config_key("global", ctx), "global",
                 {"knobs": {"gemv_max_m": 16}, "context": ctx})
    tune.invalidate()
    assert tune.get_knob("gemv_max_m", ctx) == 16
    monkeypatch.setenv("MXNET_TUNE_GEMV_MAX_M", "128")
    assert tune.get_knob("gemv_max_m", ctx) == 128
    monkeypatch.delenv("MXNET_TUNE_GEMV_MAX_M")
    assert tune.get_knob("gemv_max_m", ctx) == 16


def test_resolve_precedence(no_tune):
    tuned = {"serve_multi_token": 4}
    assert tune_config.resolve("serve_multi_token", 2, tuned) == 2
    assert tune_config.resolve("serve_multi_token", None, tuned) == 4
    assert tune_config.resolve("serve_multi_token", None, {}) == 1


# ============================================================ tune manifests
def test_tune_manifest_round_trip_and_verify(tune_dir, tmp_path):
    ctx = {"w": "manifest"}
    key = tune.config_key("serve", ctx)
    tune_dir.put(key, "serve", {"knobs": {"serve_multi_token": 4},
                                "context": ctx}, label="mxtune:decode")
    mpath = str(tmp_path / "t.tune-manifest.json")
    tune.write_tune_manifest(mpath, "t", tune_dir.touched)
    manifest = tune.read_tune_manifest(mpath)
    assert [e["key"] for e in manifest["entries"]] == [key]
    res = tune.verify_tune_manifest(manifest, tune_dir)
    assert res["ok"] and res["present"] == [key]

    # a re-tuned (different-payload) entry reads as stale
    tune_dir.put(key, "serve", {"knobs": {"serve_multi_token": 8},
                                "context": ctx})
    res = tune.verify_tune_manifest(manifest, tune_dir)
    assert not res["ok"] and res["stale"] == [key]

    # a deleted entry reads as missing
    os.unlink(tune_dir._entry_path(key))
    res = tune.verify_tune_manifest(manifest, tune_dir)
    assert not res["ok"] and res["missing"] == [key]


def test_tune_manifest_dedup_keeps_last_touch(tune_dir, tmp_path):
    """A read-then-rewrite (the mxtune merge path) touches one key twice
    with different checksums; the manifest must record the LAST (what is
    on disk), or every merged winner would ship as stale."""
    ctx = {"w": "merge"}
    key = tune.config_key("serve", ctx)
    tune_dir.put(key, "serve", {"knobs": {"serve_multi_token": 4},
                                "context": ctx})
    tune_dir.get(key, site="serve")          # read: touches the old sha
    tune_dir.put(key, "serve",               # merge rewrite: new sha
                 {"knobs": {"serve_multi_token": 4,
                            "serve_min_prompt_bucket": 2},
                  "context": ctx})
    mpath = str(tmp_path / "m.tune-manifest.json")
    tune.write_tune_manifest(mpath, "m", tune_dir.touched)
    res = tune.verify_tune_manifest(tune.read_tune_manifest(mpath),
                                    tune_dir)
    assert res["ok"], res


# ================================================================= search
def _surface(cfg):
    """Separable, deterministic, optimum at (a=4, b=64)."""
    return {"values": [100.0 - 5.0 * (cfg["a"] - 4) ** 2
                       - 5.0 * ((cfg["b"] - 64) / 16.0) ** 2],
            "regime": "overhead"}


_SPACE = {"a": Param([1, 2, 4, 8], tags=("overhead",)),
          "b": Param([16, 32, 64, 128], tags=("geometry",))}


def test_search_converges_and_is_deterministic(no_tune):
    r1 = tune.search(_surface, _SPACE, {"a": 1, "b": 16}, seed=3)
    r2 = tune.search(_surface, _SPACE, {"a": 1, "b": 16}, seed=3)
    assert r1["best"] == {"a": 4, "b": 64}
    assert [t["config"] for t in r1["trials"]] == \
        [t["config"] for t in r2["trials"]], "schedule must be seeded"
    assert r1["improvement"] > 0.5
    # a different seed may reorder but must reach the same optimum
    assert tune.search(_surface, _SPACE, {"a": 1, "b": 16},
                       seed=11)["best"] == {"a": 4, "b": 64}


def test_search_noise_cannot_crown_a_winner(no_tune):
    """A candidate inside the incumbent's measured spread never wins;
    a win beyond every spread does (the bench_gate tolerance math)."""
    wins, delta = tune.judge([103.0, 97.0, 100.0], [100.0, 95.0, 99.0])
    assert not wins and abs(delta) < 0.02   # 1% gain, ~6-8% spreads
    wins, delta = tune.judge([150.0, 148.0, 152.0], [100.0, 95.0, 99.0])
    assert wins and delta > 0.4
    # deterministic objectives (no spread) are gated by the floor alone
    assert tune.judge([104.0], [100.0], floor=0.05) == (False, 0.04)
    assert tune.judge([106.0], [100.0], floor=0.05)[0]


def test_search_regime_steers_knob_order(no_tune):
    """With an overhead regime verdict, the overhead-tagged knob is
    swept before the geometry-tagged one regardless of the shuffle."""
    for seed in range(6):
        r = tune.search(_surface, _SPACE, {"a": 1, "b": 16}, seed=seed)
        default = r["trials"][0]["config"]
        first_a = next(i for i, t in enumerate(r["trials"][1:])
                       if t["config"]["a"] != default["a"])
        first_b = next(i for i, t in enumerate(r["trials"][1:])
                       if t["config"]["b"] != default["b"])
        assert first_a < first_b, \
            f"seed {seed}: overhead knob swept at {first_a}, " \
            f"geometry at {first_b}"


def test_search_respects_max_trials(no_tune):
    r = tune.search(_surface, _SPACE, {"a": 1, "b": 16}, seed=0,
                    max_trials=3)
    assert len(r["trials"]) == 3


# ===================================================== the mxtune CLI (jax-free path)
def test_mxtune_ladder_finds_10pct_and_persists(tmp_path, no_tune):
    """The acceptance workload: deterministic given the seed, >= 10% on
    the tuner's own objective, winner in the content-addressed cache."""
    mxtune = _load_mxtune()
    cache_dir = str(tmp_path / "tuned")
    outs = [mxtune.run(_ladder_args(mxtune, cache_dir)) for _ in range(2)]
    assert outs[0]["best"]["config"] == outs[1]["best"]["config"]
    assert outs[0]["default"]["objective"] == outs[1]["default"]["objective"]
    assert outs[0]["improvement"] >= 0.10
    assert outs[0]["committed"]["key"] == outs[1]["committed"]["key"]
    key = outs[0]["committed"]["key"]
    doc = tune.ConfigCache(cache_dir).get(key, site="serve")
    assert doc is not None
    assert doc["payload"]["knobs"] == outs[0]["best"]["config"]
    assert doc["payload"]["objective"]["improvement"] >= 0.10
    assert os.path.exists(outs[0]["committed"]["manifest"])


def _ladder_args(mxtune, cache_dir):
    import argparse
    return argparse.Namespace(
        workload="ladder", seed=0, repeats=3, floor=0.05, passes=2,
        max_trials=None, cache_dir=cache_dir, manifest=None, name="t",
        requests=2048, mix="short", compile_cost_tokens=256,
        vocab=mxtune.MODEL_DIMS["vocab"], hidden=mxtune.MODEL_DIMS["hidden"],
        layers=mxtune.MODEL_DIMS["layers"], heads=mxtune.MODEL_DIMS["heads"],
        max_batch_size=4, max_len=96, trial_log=False, quiet=True)


def test_mxtune_cli_subprocess_ladder(tmp_path):
    """The CLI end to end, no jax assumed on the search path."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "mxtune.py"),
         "--workload", "ladder", "--cache-dir",
         str(tmp_path / "tuned"), "--quiet"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["improvement"] >= 0.10
    assert out["committed"] is not None


def test_mxtune_context_matches_engine_context(no_tune):
    """The hand-assembled CLI context must equal what a real engine
    builds for the same dims — or winners would never key-match."""
    jax = pytest.importorskip("jax")  # noqa: F841
    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTConfig, GPTModel
    mxtune = _load_mxtune()
    args = _ladder_args(mxtune, None)
    mx.random.seed(0)
    net = GPTModel(GPTConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_heads=args.heads,
        max_position_embeddings=2 * args.max_len, dropout=0.0))
    net.initialize()
    assert mxtune._serve_context(args) == tune.serve_context(
        net, args.max_batch_size, args.max_len)


# =============================================== consulting-site parity (jax)
def test_bucketing_growth2_is_the_legacy_pow2_ladder(no_tune):
    from mxnet_tpu.serve.bucketing import bucket_for, bucket_ladder, \
        next_pow2
    for lo, hi in ((8, 48), (1, 16), (4, 256), (8, 8)):
        assert bucket_ladder(lo, hi, 2) == bucket_ladder(lo, hi)
        for n in range(1, hi + 1):
            assert bucket_for(n, lo, hi, 2) == \
                min(max(next_pow2(n), lo), hi), (n, lo, hi)
    assert bucket_ladder(8, 96, 3) == [8, 24, 72, 96]
    assert bucket_for(25, 8, 96, 3) == 72


def test_engine_defaults_bitwise_without_tuned_config(no_tune):
    """With no tuned config, the knob-resolving constructor lands on
    exactly the legacy hand-picked values (the parity acceptance)."""
    jax = pytest.importorskip("jax")  # noqa: F841
    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTConfig, GPTModel
    from mxnet_tpu.serve import InferenceEngine
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                             num_heads=2, max_position_embeddings=64,
                             dropout=0.0))
    net.initialize()
    eng = InferenceEngine(net, max_batch_size=2, max_len=32)
    explicit = InferenceEngine(net, max_batch_size=2, max_len=32,
                               min_prompt_bucket=8, multi_token=1,
                               page_size=16, bucket_growth=2)
    assert (eng.K, eng.min_prompt_bucket, eng._growth, eng.page_size) == \
        (explicit.K, explicit.min_prompt_bucket, explicit._growth,
         explicit.page_size) == (1, 8, 2, 16)


def test_engine_consults_tuned_config_and_explicit_wins(tune_dir):
    jax = pytest.importorskip("jax")  # noqa: F841
    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTConfig, GPTModel
    from mxnet_tpu.serve import InferenceEngine
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                             num_heads=2, max_position_embeddings=64,
                             dropout=0.0))
    net.initialize()
    ctx = tune.serve_context(net, 2, 32)
    tune_dir.put(tune.config_key("serve", ctx), "serve",
                 {"knobs": {"serve_multi_token": 2,
                            "serve_min_prompt_bucket": 4},
                  "context": ctx})
    tune.invalidate()
    eng = InferenceEngine(net, max_batch_size=2, max_len=32)
    assert eng.K == 2 and eng.min_prompt_bucket == 4
    # explicit arguments always beat the tuned config
    eng2 = InferenceEngine(net, max_batch_size=2, max_len=32,
                           multi_token=1)
    assert eng2.K == 1 and eng2.min_prompt_bucket == 4
    # a different engine geometry (other key): defaults, bitwise
    eng3 = InferenceEngine(net, max_batch_size=4, max_len=32)
    assert eng3.K == 1 and eng3.min_prompt_bucket == 8


def test_gemv_routing_consults_tuned_threshold(no_tune):
    """QuantizedDense's GEMV-vs-MXU routing reads gemv_max_m() at trace
    time: the tuned value flips the path, deactivation restores it."""
    jax = pytest.importorskip("jax")  # noqa: F841
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import np
    from mxnet_tpu.contrib.quantization import quantize_net
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.ops.int8_gemv import count_launches

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=8))
    net.initialize()
    x = np.array(onp.random.RandomState(0).rand(4, 8).astype("float32"))
    net(x)
    quantize_net(net, calib_mode="none")

    def gemv_launches():
        with count_launches() as tally:
            net(np.array(onp.random.RandomState(1).rand(4, 8)
                         .astype("float32"))).wait_to_read()
        # off-TPU the GEMV site runs its XLA reference and says so
        return tally.get("reference", 0)

    net.hybridize(active=False)  # re-trace every call for the tally
    assert gemv_launches() == 1          # 4 rows <= default 64: GEMV path
    tune.activate("global", {"gemv_max_m": 0})
    assert gemv_launches() == 0          # threshold 0: int8 MXU path
    tune.deactivate_all()
    assert gemv_launches() == 1          # defaults restored


def test_global_winner_commits_under_the_context_runtime_consults(
        tune_dir):
    """The runtime consults GLOBAL_SITE context-free
    (ops/int8_gemv.gemv_max_m passes no context), so a persisted global
    winner must live under the empty-context key — the mxtune gemv
    workload's commit context is pinned to match."""
    mxtune = _load_mxtune()
    import argparse
    _m, _s, _d, ctx, site = mxtune.gemv_workload(
        argparse.Namespace(seed=0, repeats=1, vocab=64, hidden=16,
                           layers=1, heads=2, max_batch_size=2,
                           max_len=32))
    assert site == "global" and ctx == {}
    key = tune.config_key(site, ctx)
    tune_dir.put(key, site, {"knobs": {"gemv_max_m": 256},
                             "context": ctx})
    tune.invalidate()
    from mxnet_tpu.ops.int8_gemv import gemv_max_m
    assert gemv_max_m() == 256   # the runtime's context-free consult


def test_active_gauge_tracks_application_not_binding(tune_dir):
    """mxnet_tune_active_config appears when a knob APPLIES (resolution
    returns the tuned value), not when a config merely binds or its
    lookup is outranked; invalidate clears it."""
    from mxnet_tpu import metrics
    was = metrics.enabled()
    metrics.reset()
    metrics.enable()
    try:
        labels = {"site": "serve", "knob": "serve_multi_token"}
        tune.activate("serve", {"serve_multi_token": 4}, {"w": "g"})
        assert metrics.get_sample_value("mxnet_tune_active_config",
                                        labels) is None  # bound, unused
        assert tune_config.resolve("serve_multi_token", 2,
                                   tune.lookup("serve", {"w": "g"})) == 2
        assert metrics.get_sample_value("mxnet_tune_active_config",
                                        labels) is None  # outranked
        assert tune.get_knob("serve_multi_token", {"w": "g"}) == 4
        assert metrics.get_sample_value("mxnet_tune_active_config",
                                        labels) == 4.0   # applied
        tune.invalidate()
        assert metrics.get_sample_value("mxnet_tune_active_config",
                                        labels) is None  # cleared
    finally:
        if not was:
            metrics.disable()
        metrics.reset()


def test_quant_block_default_consults_layer(no_tune):
    from mxnet_tpu.kvstore import BlockQuantCompression
    assert BlockQuantCompression("int8").block == 128
    tune.activate("global", {"quant_block": 64})
    try:
        assert BlockQuantCompression("int8").block == 64
        # explicit block beats the tuned one
        assert BlockQuantCompression("int8", block=256).block == 256
    finally:
        tune.deactivate_all()
    assert BlockQuantCompression("int8").block == 128
