"""Self-managing fleet tier-1 coverage (mxnet_tpu/serve/{fleet,registry}):

- weight publishing: atomic versioned publish/read round trip, partial
  publishes invisible, checkpoint-directory adaptation
- live weight refresh: swap validation (shape/name mismatches rejected
  before anything is staged), swap parity vs a fresh engine on the new
  weights under ``no_recompile()``, a mid-flight swap that changes
  outputs WITHOUT dropping the in-flight stream, and the pull-side
  :class:`WeightRefresher`
- multi-model serving: one HTTP frontend serving N registry entries
  (``model`` key routing, 503 for unknown models so a router fails
  over), router model-aware dispatch over advertised model maps
- tenant fair share: WFQ ordering (a backlogged tenant's next request
  loses to a lighter tenant despite arriving first), quota blocking +
  release, 429 surfacing through the router frontend
- autoscale controller: load-driven scale up, cooldown suppression,
  slack-driven scale down with graceful retirement, min-floor recovery
  when the last replica dies — all over stdlib fake replicas, so the
  control-loop tests are engine-free and cheap
- drain-replay churn (the PR-7 drain-bounce contract under
  controller-driven cycles): repeated drains + respawns mid-traffic
  never duplicate or drop a stream
"""
import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import metrics
from mxnet_tpu.analysis import guards
from mxnet_tpu.base import MXNetError
from mxnet_tpu.checkpoint import CheckpointManager
from mxnet_tpu.models import GPTModel
from mxnet_tpu.models.gpt import GPTConfig
from mxnet_tpu.serve import (AutoscalePolicy, FleetController,
                             HTTPFrontend, InferenceEngine,
                             InProcessSpawner, ModelRegistry,
                             NoBackendError, QuotaExceededError, Router,
                             TenantPolicy, TenantScheduler,
                             WeightRefresher, latest_weight_version,
                             publish_from_checkpoint, publish_weights,
                             read_weights, snapshot_params,
                             weight_versions)


@pytest.fixture
def fresh_metrics():
    was = metrics.enabled()
    metrics.reset()
    metrics.enable()
    yield
    if not was:
        metrics.disable()
    metrics.reset()


def _build_net(seed=0):
    mx.random.seed(seed)
    net = GPTModel(GPTConfig(vocab_size=32, hidden_size=32, num_layers=2,
                             num_heads=2, max_position_embeddings=128,
                             dropout=0.0))
    net.initialize()
    return net


@pytest.fixture(scope="module")
def net_a():
    return _build_net(0)


@pytest.fixture(scope="module")
def net_b():
    return _build_net(1)


PROMPT = [1, 2, 3, 4, 5]


# ---------------------------------------------------------------- publishing
def test_publish_read_roundtrip(tmp_path, net_a):
    d = str(tmp_path / "w")
    params = snapshot_params(net_a)
    v1 = publish_weights(d, params)
    assert v1 == 1 and latest_weight_version(d) == 1
    # a second publish auto-increments; keep_last prunes the oldest
    v2 = publish_weights(d, params, keep_last=1)
    assert v2 == 2 and weight_versions(d) == [2]
    got_v, got, manifest = read_weights(d)
    assert got_v == 2 and manifest["version"] == 2
    for name, arr in params.items():
        assert got[name].shape == arr.shape
        assert got[name].dtype == arr.dtype
        assert onp.array_equal(got[name], arr)
    # explicit versions must be positive (0 = never-published sentinel)
    with pytest.raises(MXNetError):
        publish_weights(d, params, version=0)


def test_partial_publish_invisible(tmp_path, net_a):
    """A publish missing its DONE sentinel (crashed mid-write) must be
    invisible to readers — the atomicity half of the protocol."""
    d = tmp_path / "w"
    publish_weights(str(d), snapshot_params(net_a))
    partial = d / "weights-v0000000007"
    partial.mkdir()
    (partial / "params.npz").write_bytes(b"garbage")
    assert weight_versions(str(d)) == [1]
    with pytest.raises(MXNetError):
        read_weights(str(d), 7)


def test_publish_from_checkpoint(tmp_path, net_a):
    """The train->serve bridge: a CheckpointManager step directory
    publishes as a weight version whose params match the live net."""
    ckpt = str(tmp_path / "ckpt")
    mgr = CheckpointManager(ckpt, net=net_a, period=1)
    mgr.save(3)
    pub = str(tmp_path / "pub")
    v = publish_from_checkpoint(mgr._step_dir(3), pub)
    assert v == 1
    _, got, manifest = read_weights(pub)
    assert manifest["meta"]["source_checkpoint"].startswith("step-")
    want = snapshot_params(net_a)
    assert set(got) == set(want)
    for name in want:
        assert onp.allclose(onp.asarray(got[name], onp.float32),
                            onp.asarray(want[name], onp.float32))


def test_checkpoint_auto_publish_bridges_to_engine(tmp_path, net_a,
                                                   net_b):
    """CheckpointManager(publish_weights_dir=...) mirrors every save
    into the serving publish layout, and a refresher-equipped engine
    hot-swaps to it — a deploy IS the checkpoint save."""
    pub = str(tmp_path / "pub")
    mgr = CheckpointManager(str(tmp_path / "ckpt"), net=net_b, period=1,
                            publish_weights_dir=pub)
    mgr.save(0)
    assert latest_weight_version(pub) == 1
    _, manifest = read_weights(pub)[1:]
    assert manifest["meta"]["step"] == 0
    eng = InferenceEngine(net_a, max_batch_size=2, max_len=64)
    assert WeightRefresher(eng, pub).check() == 1
    assert eng.weight_version == 1
    _, pub_params, _ = read_weights(pub)
    for name, val in zip(eng._param_names, eng._values):
        assert onp.allclose(onp.asarray(val, onp.float32),
                            onp.asarray(pub_params[name], onp.float32))


# ----------------------------------------------------------- live swap
def test_swap_validation_rejects_before_staging(net_a, net_b):
    eng = InferenceEngine(net_a, max_batch_size=2, max_len=64)
    params = snapshot_params(net_b)
    # missing param
    broken = dict(params)
    broken.pop(next(iter(broken)))
    with pytest.raises(MXNetError, match="missing"):
        eng.swap_weights(broken)
    # unknown name
    extra = dict(params)
    extra["not_a_param"] = onp.zeros(3, onp.float32)
    with pytest.raises(MXNetError, match="unknown"):
        eng.swap_weights(extra)
    # shape mismatch = would-be recompile: rejected
    wrong = dict(params)
    first = next(iter(wrong))
    wrong[first] = onp.zeros(
        tuple(s + 1 for s in wrong[first].shape), wrong[first].dtype)
    with pytest.raises(MXNetError, match="shape mismatch"):
        eng.swap_weights(wrong)
    assert eng.weight_version == 0      # nothing staged, nothing applied


def test_live_swap_parity_no_recompile(tmp_path, net_a, net_b,
                                       fresh_metrics):
    """The deploy contract: swap changes outputs exactly to what a fresh
    engine on the new weights produces, with ZERO recompiles, and the
    weight-version gauge flips."""
    eng = InferenceEngine(net_a, max_batch_size=2, max_len=64,
                          name="gpt-main").start()
    try:
        before = eng.generate(PROMPT, 8).generated_ids
        d = str(tmp_path / "w")
        publish_weights(d, snapshot_params(net_b))
        with guards.no_recompile():
            got = eng.swap_weights_from(d)
            after = eng.generate(PROMPT, 8).generated_ids
        assert got == 1 and eng.weight_version == 1
        assert after != before
        assert metrics.get_sample_value(
            "mxnet_serve_weight_version", {"model": "gpt-main"}) == 1
        assert metrics.get_sample_value(
            "mxnet_serve_weight_swaps_total", {"model": "gpt-main"}) == 1
    finally:
        eng.shutdown()
    ref = InferenceEngine(net_b, max_batch_size=2, max_len=64).start()
    try:
        assert ref.generate(PROMPT, 8).generated_ids == after
    finally:
        ref.shutdown()


def test_swap_remakes_the_embedding_table(net_a, net_b):
    """Behind the parameters the engine holds the embedding's table in rows
    of whole lane tiles (32 wide here: padded to 128), which every program
    gathers from: a swap makes it again from the weights it takes."""
    eng = InferenceEngine(net_a, max_batch_size=2, max_len=64)
    assert len(eng._values) == len(eng._param_names) + 1

    def table_of(net):
        wte = onp.asarray(net.wte.weight.data()._data)
        return onp.pad(wte, ((0, 0), (0, 128 - wte.shape[1])))

    onp.testing.assert_array_equal(onp.asarray(eng._values[-1]),
                                   table_of(net_a))
    eng.swap_weights(snapshot_params(net_b))
    assert len(eng._values) == len(eng._param_names) + 1
    onp.testing.assert_array_equal(onp.asarray(eng._values[-1]),
                                   table_of(net_b))


def test_swap_mid_flight_keeps_stream(net_a, net_b):
    """The zero-downtime half: a swap while a stream decodes completes
    that stream (full token budget, no drop) — tokens after the swap
    simply sample from the new weights."""
    eng = InferenceEngine(net_a, max_batch_size=2, max_len=128).start()
    eng._step_delay = 0.01          # stretch the stream across the swap
    try:
        h = eng.submit(PROMPT, 60)
        deadline = time.monotonic() + 30
        while not h.first_token_t and time.monotonic() < deadline:
            time.sleep(0.005)       # in flight before we swap
        v = eng.swap_weights(snapshot_params(net_b))
        res = h.result(120)
        assert v == 1 and eng.weight_version == 1
        assert res.status == "ok"
        assert len(res.generated_ids) == 60
        # the engine keeps serving, on the new weights
        eng._step_delay = 0.0
        after = eng.generate(PROMPT, 8).generated_ids
    finally:
        eng.shutdown()
    ref = InferenceEngine(net_b, max_batch_size=2, max_len=64).start()
    try:
        assert ref.generate(PROMPT, 8).generated_ids == after
    finally:
        ref.shutdown()


def test_weight_refresher_pull(tmp_path, net_a, net_b):
    """The pull half: a refresher check() is a no-op until a NEWER
    version lands, then swaps once."""
    d = str(tmp_path / "w")
    eng = InferenceEngine(net_a, max_batch_size=2, max_len=64)
    r = WeightRefresher(eng, d, interval=0.05)
    assert r.check() is None            # nothing published yet
    publish_weights(d, snapshot_params(net_b))
    assert r.check() == 1
    assert eng.weight_version == 1
    assert r.check() is None            # already current


# ------------------------------------------------------------ multi-model
def test_registry_multi_model_http(net_a, net_b, tmp_path):
    reg = ModelRegistry()
    reg.add("alpha", InferenceEngine(net_a, max_batch_size=2, max_len=64))
    reg.add("beta", InferenceEngine(net_b, max_batch_size=2, max_len=64))
    with pytest.raises(MXNetError):
        reg.add("alpha", None)          # duplicate name
    reg.start()
    fe = HTTPFrontend(reg, port=0).start()

    def post(path, doc):
        req = urllib.request.Request(
            fe.url + path, data=json.dumps(doc).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=120) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            with e:
                return e.code, json.loads(e.read())

    try:
        gen = {"input_ids": PROMPT, "max_new_tokens": 6}
        _, a = post("/generate", {**gen, "model": "alpha"})
        _, b = post("/generate", {**gen, "model": "beta"})
        _, default = post("/generate", gen)       # first entry = default
        assert a["generated_ids"] != b["generated_ids"]
        assert default["generated_ids"] == a["generated_ids"]
        code, doc = post("/generate", {**gen, "model": "nope"})
        assert code == 503 and "nope" in doc["error"]
        with urllib.request.urlopen(fe.url + "/healthz", timeout=10) as r:
            hz = json.loads(r.read())
        assert hz["models"] == {"alpha": 0, "beta": 0}
        assert hz["slots"] == 4
        # push deploy into ONE entry: beta's weights into alpha
        d = str(tmp_path / "w")
        publish_weights(d, snapshot_params(net_b))
        code, doc = post("/weights", {"dir": d, "model": "alpha"})
        assert code == 200 and doc["version"] == 1
        _, a2 = post("/generate", {**gen, "model": "alpha"})
        assert a2["generated_ids"] == b["generated_ids"]
        with urllib.request.urlopen(fe.url + "/models", timeout=10) as r:
            models = json.loads(r.read())["models"]
        assert models["alpha"]["weight_version"] == 1
        assert models["beta"]["weight_version"] == 0
    finally:
        fe.stop()
        reg.shutdown()


# --------------------------------------------------- fake-replica helpers
class FakeReplica:
    """Stdlib replica stub: settable load/models, counts polls, serves
    trivial /generate, honors /drain."""

    def __init__(self, models=None, load=0.0, generate_status=200):
        state = self.state = {
            "load": load, "draining": False, "polls": 0,
            "models": models, "generate_status": generate_status,
            "generated": []}

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _json(self, code, doc):
                body = json.dumps(doc).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                state["polls"] += 1
                doc = {"ok": not state["draining"],
                       "draining": state["draining"],
                       "load": state["load"], "slots": 2,
                       "slots_in_use": 0, "queue_depth": 0}
                if state["models"] is not None:
                    doc["models"] = state["models"]
                self._json(200, doc)

            def do_POST(self):
                payload = json.loads(self.rfile.read(
                    int(self.headers.get("Content-Length", 0))) or b"{}")
                if self.path == "/drain":
                    state["draining"] = True
                    self._json(200, {"ok": True, "draining": True})
                    return
                state["generated"].append(payload)
                code = state["generate_status"]
                if code != 200:
                    self._json(code, {"error": "injected"})
                else:
                    self._json(200, {"status": "ok", "output_ids": [1],
                                     "generated_ids": [1]})

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.httpd.daemon_threads = True
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


# ------------------------------------------------------------ router layer
def test_router_model_aware_dispatch(fresh_metrics):
    """Dispatch only considers replicas that ADVERTISE the requested
    model; replicas without a models map (pre-registry) stay eligible
    for everything; an unserved model raises NoBackendError."""
    ra = FakeReplica(models={"a": 0})
    rb = FakeReplica(models={"b": 3})
    legacy = FakeReplica(models=None, load=5.0)   # eligible but last pick
    router = Router([ra.url, rb.url, legacy.url],
                    health_interval=30.0).start()
    try:
        deadline = time.monotonic() + 10
        while (router.stats()["healthy"] < 3
               and time.monotonic() < deadline):
            time.sleep(0.02)
        doc = router.generate({"input_ids": [1], "max_new_tokens": 1,
                               "model": "a"})
        assert doc["status"] == "ok"
        assert ra.state["generated"] and not rb.state["generated"]
        router.generate({"input_ids": [1], "max_new_tokens": 1,
                         "model": "b"})
        assert rb.state["generated"]
        # an unadvertised model falls through to the legacy wildcard
        # replica (back-compat) ...
        doc = router.generate({"input_ids": [1], "max_new_tokens": 1,
                               "model": "c"})
        assert doc["status"] == "ok" and legacy.state["generated"]
        # ... and with no wildcard in the fleet it raises
        router.remove_backend(legacy.url)
        with pytest.raises(NoBackendError, match="model 'c'"):
            router.generate({"input_ids": [1], "max_new_tokens": 1,
                             "model": "c"})
        # the advertised weight versions surface in router stats
        assert router.stats()["backends"][rb.url]["models"] == {"b": 3}
    finally:
        router.stop()
        for f in (ra, rb, legacy):
            f.close()


def test_router_poll_backoff_on_failure(fresh_metrics):
    """Satellite: failed polls back off exponentially per replica (up to
    the cap) instead of hammering a struggling replica at the fixed
    cadence; a healthy replica keeps backoff 0."""
    alive = FakeReplica()
    dead = FakeReplica()
    dead_url = dead.url
    dead.close()                        # nothing listens there anymore
    router = Router([alive.url, dead_url], health_interval=0.05,
                    health_backoff=2.0, health_backoff_max=0.4).start()
    try:
        time.sleep(1.2)                 # several poll generations
        st = router.stats()["backends"]
        assert st[alive.url]["poll_backoff"] == 0.0
        # the dead replica's cadence reached the cap (0.05 -> 0.1 ->
        # 0.2 -> 0.4), so over 1.2s it saw far fewer probes than 24
        assert st[dead_url]["poll_backoff"] == pytest.approx(0.4)
        polls_alive = alive.state["polls"]
        assert polls_alive >= 10        # healthy cadence kept up
    finally:
        router.stop()
        alive.close()


def test_tenant_wfq_ordering_and_quota(fresh_metrics):
    """Deterministic WFQ: the released capacity goes to the tenant with
    less virtual time (weight-4 tenant accrues 0.25/dispatch vs 1.0)
    even though the heavier tenant's waiter arrived FIRST; quotas block
    past max_inflight and surface QuotaExceededError on timeout."""
    sched = TenantScheduler({"a": TenantPolicy(weight=1.0),
                             "b": TenantPolicy(weight=4.0)},
                            capacity_fn=lambda: 2)
    sched.acquire("a")                  # a.vtime = 1.0, capacity 1/2
    sched.acquire("b")                  # b.vtime = 1 (floor) + 0.25
    order = []
    evts = {name: threading.Event() for name in ("a2", "a3", "b2")}

    def waiter(tag, tenant):
        sched.acquire(tenant)
        order.append(tag)
        evts[tag].set()

    # enqueue order: a2, a3, b2 — all blocked on capacity
    threads = []
    for tag, tenant in (("a2", "a"), ("a3", "a"), ("b2", "b")):
        t = threading.Thread(target=waiter, args=(tag, tenant),
                             daemon=True)
        t.start()
        threads.append(t)
        time.sleep(0.05)                # deterministic FIFO seq order
    sched.release("a")                  # a2 (1.0) beats b2 (1.25)
    assert evts["a2"].wait(5)
    sched.release("b")                  # a3 (now 2.0) loses to b2 (1.25)
    assert evts["b2"].wait(5)           # beats a3 despite arriving later
    sched.release("a")
    assert evts["a3"].wait(5)
    for t in threads:
        t.join(5)
    assert order == ["a2", "b2", "a3"]
    for tenant in ("a", "b"):
        sched.release(tenant)

    quota = TenantScheduler({"q": TenantPolicy(max_inflight=1)})
    quota.acquire("q")
    with pytest.raises(QuotaExceededError):
        quota.acquire("q", timeout=0.05)
    quota.release("q")
    quota.acquire("q")                  # released quota admits again
    quota.release("q")


def test_router_tenant_quota_429(fresh_metrics):
    """A tenant over quota gets 429 backpressure via the router API
    while other tenants keep dispatching."""
    slow = FakeReplica()
    router = Router([slow.url], health_interval=30.0,
                    tenants={"burst": TenantPolicy(max_inflight=1)},
                    tenant_timeout=0.1).start()
    try:
        deadline = time.monotonic() + 10
        while (not router.stats()["healthy"]
               and time.monotonic() < deadline):
            time.sleep(0.02)
        # hold the tenant's single admission slot
        router._tenants.acquire("burst")
        with pytest.raises(QuotaExceededError):
            router.generate({"input_ids": [1], "max_new_tokens": 1,
                             "tenant": "burst"})
        # a different tenant is untouched by burst's quota
        doc = router.generate({"input_ids": [1], "max_new_tokens": 1,
                               "tenant": "calm"})
        assert doc["status"] == "ok"
        router._tenants.release("burst")
        assert (metrics.get_sample_value(
            "mxnet_fleet_tenant_rejected_total",
            {"tenant": "burst"}) or 0) >= 1
    finally:
        router.stop()
        slow.close()


# ------------------------------------------------------------ controller
class FakeSpawner:
    def __init__(self, **replica_kwargs):
        self.fakes = {}
        self.kwargs = replica_kwargs

    def spawn(self):
        f = FakeReplica(**self.kwargs)
        self.fakes[f.url] = f
        return f.url

    def stop(self, url):
        self.fakes.pop(url).close()

    def urls(self):
        return list(self.fakes)


def _wait_probe(router, n, timeout=10):
    deadline = time.monotonic() + timeout
    while (router.stats()["healthy"] < n
           and time.monotonic() < deadline):
        time.sleep(0.02)


def _wait_loads(router, value, timeout=10):
    """Block until the router's polled view shows ``value`` load on
    every healthy backend (the fakes' state changes are only visible
    after a poll — ticking before that is timing-dependent)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        st = router.stats()["backends"]
        if st and all(abs(b["load"] - value) < 1e-9
                      for b in st.values() if b["healthy"]):
            return
        time.sleep(0.02)
    raise AssertionError(f"router never saw load={value}: {st}")


def test_controller_scale_cycle_with_cooldown(fresh_metrics):
    """Load -> (hysteresis) -> scale up -> cooldown suppresses the next
    wish -> slack -> scale down with graceful retirement. Engine-free:
    decisions drive fake replicas."""
    spawner = FakeSpawner()
    first = spawner.spawn()
    router = Router([first], health_interval=0.05).start()
    policy = AutoscalePolicy(scale_up_load=0.7, scale_down_load=0.2,
                             up_after=2, down_after=2, cooldown_s=120.0,
                             min_replicas=1, max_replicas=3,
                             drain_grace_s=5.0, refresh_slo=False)
    ctl = FleetController(router, spawner, policy=policy)
    try:
        _wait_probe(router, 1)
        spawner.fakes[first].state["load"] = 1.5
        _wait_loads(router, 1.5)
        assert ctl.tick() is None          # streak 1 < up_after
        assert ctl.tick() is not None      # streak 2 -> scale up
        assert len(spawner.urls()) == 2
        _wait_probe(router, 2)
        # still hot, streak satisfied again — but the cooldown gate holds
        for f in spawner.fakes.values():
            f.state["load"] = 1.5
        _wait_loads(router, 1.5)
        deadline = time.monotonic() + 10
        while (metrics.get_sample_value(
                "mxnet_fleet_decisions_suppressed_total",
                {"direction": "up", "why": "cooldown"}) or 0) < 1:
            assert ctl.tick() is None      # cooldown: no event may fire
            assert time.monotonic() < deadline
        # slack: kill the cooldown, scale back down to the floor
        ctl._last_event_t = -1e9
        for f in spawner.fakes.values():
            f.state["load"] = 0.0
        _wait_loads(router, 0.0)
        assert ctl.tick() is None
        ev = ctl.tick()
        assert ev is not None and ev["direction"] == "down"
        deadline = time.monotonic() + 10
        while ctl.stats()["retiring"] and time.monotonic() < deadline:
            time.sleep(0.05)
            ctl.tick()
        assert not ctl.stats()["retiring"]
        assert len(spawner.urls()) == 1
        assert len(router.stats()["backends"]) == 1
        ups = metrics.get_sample_value(
            "mxnet_fleet_scale_events_total",
            {"direction": "up", "reason": "load"})
        downs = metrics.get_sample_value(
            "mxnet_fleet_scale_events_total",
            {"direction": "down", "reason": "load"})
        assert ups == 1 and downs == 1
    finally:
        ctl.stop()
        router.stop()
        for url in spawner.urls():
            spawner.stop(url)


def test_controller_min_floor_recovery(fresh_metrics):
    """The emergency path: when the fleet drops below min_replicas the
    controller spawns immediately — no hysteresis, no cooldown."""
    spawner = FakeSpawner()
    first = spawner.spawn()
    router = Router([first], health_interval=0.05).start()
    policy = AutoscalePolicy(min_replicas=1, max_replicas=2,
                             cooldown_s=1e9, refresh_slo=False,
                             drain_grace_s=5.0)
    ctl = FleetController(router, spawner, policy=policy)
    try:
        _wait_probe(router, 1)
        spawner.fakes[first].close()       # the only replica dies
        deadline = time.monotonic() + 10
        while (router.stats()["healthy"] and
               time.monotonic() < deadline):
            time.sleep(0.02)               # health loop notices the loss
        ev = ctl.tick()
        assert ev is not None and ev["reason"] == "min_floor"
        assert (metrics.get_sample_value(
            "mxnet_fleet_scale_events_total",
            {"direction": "up", "reason": "min_floor"}) or 0) >= 1
        _wait_probe(router, 1)
        assert router.stats()["healthy"] >= 1
    finally:
        ctl.stop()
        router.stop()
        for url in spawner.urls():
            try:
                spawner.stop(url)
            except Exception:
                pass


# ------------------------------------------------------ drain-replay churn
def _churn_reference(net, prompts, max_new):
    eng = InferenceEngine(net, max_batch_size=4, max_len=64).start()
    try:
        return [eng.generate(p, max_new, seed=i).generated_ids
                for i, p in enumerate(prompts)]
    finally:
        eng.shutdown()


def test_drain_replay_churn_under_scaledown(net_a):
    """Satellite: controller-style drain cycles while requests are in
    flight never duplicate or drop a stream — every request completes
    exactly once with the greedy-deterministic output, surviving
    repeated drain -> respawn -> remove cycles (the PR-7 drain-bounce
    idempotency contract, extended to controller-driven churn)."""
    prompts = [[1 + (i % 7), 2, 3 + (i % 5)] for i in range(10)]
    max_new = 12
    expect = _churn_reference(net_a, prompts, max_new)

    spawner = InProcessSpawner(
        lambda: InferenceEngine(net_a, max_batch_size=4, max_len=64))
    urls = [spawner.spawn(), spawner.spawn()]
    router = Router(urls, health_interval=0.05).start()
    results = [None] * len(prompts)
    errors = []

    def client(i):
        try:
            doc = router.generate({"input_ids": prompts[i],
                                   "max_new_tokens": max_new,
                                   "seed": i})
            results[i] = doc
        except Exception as e:
            errors.append((i, repr(e)))

    try:
        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        # two controller-style scale-down/up cycles mid-traffic: drain
        # (in-flight work finishes or bounces -> idempotent replay),
        # stop, remove, respawn, add
        for _ in range(2):
            victim = spawner.urls()[0]
            router.drain(victim)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                try:
                    with urllib.request.urlopen(victim + "/healthz",
                                                timeout=2) as r:
                        doc = json.loads(r.read())
                except urllib.error.HTTPError as e:
                    with e:
                        doc = json.loads(e.read())
                except Exception:
                    break
                if not doc.get("slots_in_use"):
                    break
                time.sleep(0.05)
            spawner.stop(victim)
            router.remove_backend(victim)
            router.add_backend(spawner.spawn())
        for t in threads:
            t.join(120)
        assert not errors, errors
        for i, doc in enumerate(results):
            assert doc is not None and doc["status"] == "ok", (i, doc)
            assert doc["generated_ids"] == expect[i], (
                f"stream {i} diverged after drain churn")
    finally:
        router.stop()
        spawner.stop_all()
