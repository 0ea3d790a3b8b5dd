"""Cache-aware fleet — mxcache (mxnet_tpu/serve/cachefleet + router
prefix-affinity + KV page migration).

The tier-1 contracts of the cache-aware fleet:

- adverts: a paged replica's /healthz prefix summary is BOUNDED by the
  ``serve_prefix_advert`` knob, and a malformed summary is treated as
  absent (cache miss), never as an eject;
- affinity dispatch: the router routes a prompt to the replica already
  holding its longest cached prefix, token-identically to a single
  replica, and a drain-bounced replay RE-SCORES against the surviving
  rotation (no duplicate, no dropped tokens);
- migration: KV pages round-trip between replicas bitwise (chain-hash
  verified; a corrupted page is dropped and counted, never injected),
  preemption rescue resumes the victim token-exactly on a peer, and the
  prefill->decode pipeline streams pages with bitwise-identical output;
- steady state stays ``no_recompile()``-clean with affinity + migration
  on (the migration executables are part of the warmup ladder).

Engine builds dominate this file's runtime, so the oracle engine
(``ref_eng``), the two-replica ``pair``, and its ``fleet`` wrapper are
module-scoped and shared; tests keep to DISTINCT prefix families (the
hundreds digit of the prompt seed) so cached pages never leak across
assertions. The drain-bounce end-to-end test builds its own fleet — it
destroys a replica.
"""
import copy
import json
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import metrics
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import GPTModel
from mxnet_tpu.models.gpt import GPTConfig
from mxnet_tpu.serve import (HTTPFrontend, InferenceEngine,
                             PrefillDecodePipeline, Router,
                             install_preempt_rescue, migrate_prefix,
                             prefix_key)
from mxnet_tpu.serve.router import NoBackendError, _Backend


@pytest.fixture(scope="module")
def gpt_model():
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=32, hidden_size=32, num_layers=2,
                             num_heads=2, max_position_embeddings=128,
                             dropout=0.0))
    net.initialize()
    return net


@pytest.fixture(scope="module")
def ref_eng(gpt_model):
    """Single-replica oracle: every request served one at a time on one
    amply-sized engine — what any fleet dispatch must reproduce bitwise
    (stateless sampling: seed + position, never which replica)."""
    eng = InferenceEngine(gpt_model, max_batch_size=4, max_len=64,
                          page_size=8, num_pages=96).start()
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def pair(gpt_model):
    """Two identical paged replicas; prefix_advert wide enough that no
    test's root falls off the bounded summary mid-module. The pair is
    TIERED (prefill/decode) — a tier label only constrains tier-TARGETED
    dispatch, so the untiered affinity/migration tests are unaffected
    while the tier tests ride the same engines."""
    engines = [InferenceEngine(gpt_model, max_batch_size=2, max_len=64,
                               page_size=8, num_pages=64,
                               prefix_advert=32, tier=t).start()
               for t in ("prefill", "decode")]
    yield engines
    for e in engines:
        e.shutdown()


@pytest.fixture(scope="module")
def fleet(pair):
    fronts = [HTTPFrontend(e, port=0).start() for e in pair]
    router = Router([f.url for f in fronts], health_interval=0.05,
                    affinity=True).start()
    yield pair, fronts, router
    router.stop()
    for f in fronts:
        f.stop()


@pytest.fixture
def fresh_metrics():
    was = metrics.enabled()
    metrics.reset()
    metrics.enable()
    yield
    if not was:
        metrics.disable()
    metrics.reset()


def _prompt(seed, prefix_len=16, body_len=5, vocab=30):
    """One shared-prefix prompt: the prefix depends only on ``seed``'s
    hundreds digit, so seeds 100..199 share a prefix, 200..299 another."""
    pre = onp.random.RandomState(seed // 100).randint(
        1, vocab, size=prefix_len)
    body = onp.random.RandomState(seed).randint(1, vocab, size=body_len)
    return [int(t) for t in pre] + [int(t) for t in body]


def _reference(eng, prompts, max_new, seeds, temperature=0.0):
    outs = []
    for p, s in zip(prompts, seeds):
        r = eng.generate(p, max_new, temperature=temperature, seed=s)
        assert r.status == "ok"
        outs.append(list(r.generated_ids))
    return outs


def _wait_root(router, prompt, timeout=30.0):
    """Block until the ROUTER's view of some backend's advert holds a
    root matching ``prompt`` (so the next same-prefix dispatch can score
    an affinity hit); returns that backend's url."""
    deadline = time.monotonic() + timeout
    keys = {}
    while time.monotonic() < deadline:
        for url, b in router._backends.items():
            for key, ln in (b.prefix_summary or ()):
                if ln <= len(prompt):
                    if ln not in keys:
                        keys[ln] = prefix_key(prompt[:ln])
                    if keys[ln] == key:
                        return url
        time.sleep(0.02)
    raise AssertionError("prefix advert never reached the router")


# ------------------------------------------------------------ adverts
def test_prefix_advert_bounded_by_knob(gpt_model):
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=64,
                          page_size=8, prefix_advert=2).start()
    try:
        for s in (100, 200, 300):     # three distinct 16-token prefixes
            assert eng.generate(_prompt(s), 2, seed=s).status == "ok"
        summary = eng.stats()["prefix_summary"]
        assert summary["page_size"] == 8
        assert 1 <= len(summary["roots"]) <= 2     # top-N, not all roots
        for key, ln, refs in summary["roots"]:
            assert ln > 0 and refs >= 1
        # top_n <= 0 disables the advert at the pool level (what the
        # prefix_advert=0 knob plumbs through)
        assert eng._pages.prefix_summary(0) == []
    finally:
        eng.shutdown()
    with pytest.raises(MXNetError, match="prefix_advert"):
        InferenceEngine(gpt_model, max_len=64, page_size=8,
                        prefix_advert=-1)


def test_malformed_advert_treated_as_absent_not_eject():
    """A replica whose /healthz carries a garbage prefix summary keeps
    serving (summary read as absent -> plain least-loaded dispatch);
    ejecting on a malformed advert would turn a telemetry bug into an
    outage."""
    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = json.dumps({
                "ok": True, "draining": False, "load": 0.0,
                "slots": 2, "slots_in_use": 0, "queue_depth": 0,
                "prefix_summary": {"page_size": "WAT",
                                   "roots": [["x", "y"], [1], "junk"]},
            }).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    router = Router([url], health_interval=0.05, affinity=True).start()
    try:
        deadline = time.monotonic() + 30
        while (router.stats()["healthy"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.02)
        st = router.stats()
        assert st["healthy"] == 1
        assert st["backends"][url]["prefix_roots"] == 0
    finally:
        router.stop()
        httpd.shutdown()
        httpd.server_close()


# ------------------------------------------------------------ affinity
def test_drain_bounce_replay_rescores_against_survivors():
    """THE replay regression: when the affinity winner leaves the
    rotation, a retried request must re-score against the survivors —
    picking the next-best cache holder, never the departed replica."""
    router = Router(["http://a:1", "http://b:1"],
                    health_interval=3600)          # never started/polled
    a = _Backend("http://a:1"); a.healthy = True
    b = _Backend("http://b:1"); b.healthy = True
    prompt = _prompt(100)
    # both replicas hold the prefix; a advertises the longer root
    a.prefix_summary = [(prefix_key(prompt[:16]), 16)]
    b.prefix_summary = [(prefix_key(prompt[:8]), 8)]
    router._backends = {a.url: a, b.url: b}

    memo = {}
    first = router._pick(set(), prompt=prompt, memo=memo)
    assert first.url == a.url                      # longest root wins
    # a bounced the request (drain mid-stream): the replay excludes it
    # and the SAME memo re-scores the survivors
    retry = router._pick({a.url}, prompt=prompt, memo=memo)
    assert retry.url == b.url                      # next-best holder
    with pytest.raises(NoBackendError):
        router._pick({a.url, b.url}, prompt=prompt, memo=memo)


# ------------------------------------------------------------ migration
def test_page_migration_round_trip_token_exact(pair, fresh_metrics):
    """Sampled (T>0) continuation after a page migration is bitwise
    equal to the source replica's — stateless sampling + exact pages —
    and a corrupted page is dropped + counted, with the sent ==
    received + verify_failures balance holding exactly."""
    src, dst = pair
    prompt = _prompt(400, body_len=9)              # 25 tokens, 3 pages
    ra = src.generate(prompt, 6, temperature=0.8, seed=9)
    assert ra.status == "ok"

    bad = copy.deepcopy(src.export_pages(prompt))
    bad["pages"][0]["key"] ^= 1                    # corrupt a chain hash
    res = dst.import_pages(bad)
    assert res["verify_failures"] == 1
    assert res["received"] == len(bad["pages"]) - 1

    summary = migrate_prefix(src, dst, prompt)     # clean transfer
    assert summary["received"] >= 1

    rb = dst.generate(prompt, 6, temperature=0.8, seed=9)
    assert rb.status == "ok"
    assert list(rb.generated_ids) == list(ra.generated_ids)
    assert dst.stats()["pages"]["prefix_hits"] >= 1

    sent = metrics.get_sample_value("mxnet_migrate_pages_sent_total") or 0
    received = metrics.get_sample_value(
        "mxnet_migrate_pages_received_total") or 0
    failures = metrics.get_sample_value(
        "mxnet_migrate_verify_failures_total") or 0
    assert sent and sent == received + failures


def test_cache_http_endpoints_round_trip(fleet):
    """/cache/export -> /cache/import over real frontends (the
    kvstore-wire codec end to end), then the receiver serves the prompt
    off the imported pages token-exactly."""
    (src, dst), (fs, fd), _router = fleet
    prompt = _prompt(500, body_len=9)              # 25 tokens, 3 pages
    ra = src.generate(prompt, 4, seed=3)
    assert ra.status == "ok"
    summary = migrate_prefix(fs.url, fd.url, prompt)   # URL -> URL
    assert summary["received"] == 3
    rb = dst.generate(prompt, 4, seed=3)
    assert list(rb.generated_ids) == list(ra.generated_ids)


def test_affinity_fleet_token_exact(fleet, ref_eng, fresh_metrics):
    """2 tenants x 3 shared-prefix requests over the 2-replica affinity
    fleet: outputs bitwise-identical to the single-replica reference,
    with at least one dispatch converted into an affinity hit."""
    _engines, _fronts, router = fleet
    seeds = [600, 700, 601, 701, 602, 702]
    prompts = [_prompt(s) for s in seeds]
    ref = _reference(ref_eng, prompts, 4, seeds)

    outs, seen = [], set()
    for p, s in zip(prompts, seeds):
        if s // 100 in seen:
            # the family's advert must be router-visible before its
            # next request, or the duel measures poll latency
            _wait_root(router, p)
        seen.add(s // 100)
        doc = router.generate({"input_ids": p, "max_new_tokens": 4,
                               "seed": s})
        assert doc["status"] == "ok"
        outs.append(list(doc["generated_ids"]))
    assert outs == ref
    hits = metrics.get_sample_value("mxnet_cache_affinity_dispatch_total",
                                    {"outcome": "hit"}) or 0
    assert hits >= 1
    assert (metrics.get_sample_value(
        "mxnet_cache_affinity_hit_tokens_total") or 0) >= 8


def test_preempt_rescue_resumes_token_exact(gpt_model, ref_eng,
                                            fresh_metrics):
    """OutOfPages preemption under a starved pool ships the victim's
    pages to the peer and resumes there: every output bitwise equal to
    the unconstrained reference, rescues counted."""
    seeds = [5, 6, 7]
    prompts = [_prompt(s, prefix_len=0, body_len=10 + s) for s in seeds]
    ref = _reference(ref_eng, prompts, 8, seeds, temperature=0.7)

    victim = InferenceEngine(gpt_model, max_batch_size=3, max_len=32,
                             page_size=8, num_pages=5,
                             prefix_cache=False).start()
    peer = InferenceEngine(gpt_model, max_batch_size=3, max_len=32,
                           page_size=8, num_pages=16,
                           prefix_cache=False).start()
    install_preempt_rescue(victim, [peer])
    try:
        handles = [victim.submit(p, 8, temperature=0.7, seed=s)
                   for p, s in zip(prompts, seeds)]
        outs = [h.result(300) for h in handles]
        assert all(r.status == "ok" for r in outs)
        assert [list(r.generated_ids) for r in outs] == ref
        assert victim.stats()["preemptions"] >= 1
    finally:
        victim.shutdown()
        peer.shutdown()
    rescued = metrics.get_sample_value("mxnet_migrate_rescues_total",
                                       {"outcome": "resumed"}) or 0
    assert rescued >= 1


def test_prefill_decode_tiers(fleet, ref_eng):
    """Disaggregated tiers, one fleet: (a) the pipeline prefills on the
    prefill replica, streams the pages, decodes on the decode replica —
    output bitwise equal to one replica doing both; (b) tier-targeted
    router dispatch lands only on the matching tier, and a missing tier
    is a named NoBackendError."""
    (pre, dec), _fronts, router = fleet
    seeds = [1000, 1001]
    prompts = [_prompt(s, body_len=9) for s in seeds]
    ref = _reference(ref_eng, prompts, 6, seeds)

    pipe = PrefillDecodePipeline([pre], [dec])
    hits_before = dec.stats()["pages"]["prefix_hits"]
    for p, s, want in zip(prompts, seeds, ref):
        doc = pipe.generate({"input_ids": p, "max_new_tokens": 6,
                             "seed": s})
        assert doc["status"] == "ok"
        assert list(doc["generated_ids"]) == want
    assert pipe.stats()["pages_streamed"] >= 2
    assert dec.stats()["pages"]["prefix_hits"] >= hits_before + 1

    deadline = time.monotonic() + 30
    while (any(b["tier"] is None
               for b in router.stats()["backends"].values())
           and time.monotonic() < deadline):
        time.sleep(0.02)
    pre_before = pre.stats()["submitted"]
    dec_before = dec.stats()["submitted"]
    doc = router.generate({"input_ids": _prompt(1100),
                           "max_new_tokens": 2, "seed": 0},
                          tier="decode")
    assert doc["status"] == "ok"
    assert dec.stats()["submitted"] == dec_before + 1
    assert pre.stats()["submitted"] == pre_before
    with pytest.raises(NoBackendError, match="batch-tier"):
        router.generate({"input_ids": _prompt(1100),
                         "max_new_tokens": 2}, tier="batch")


# ------------------------------------------------------------ tiers
def test_slo_names_scopes_the_burn_signal():
    """Each tier scales off its OWN SLO: a prefill controller watching
    ("ttft",) must not see the decode tier's intertoken burn."""
    from mxnet_tpu.serve import AutoscalePolicy, FleetController

    class _SLO:
        last = {"ttft": {"burn": 4.0}, "intertoken": {"burn": 9.0}}

    class _FakeRouter:
        _slo = _SLO()

    class _NoSpawner:
        def urls(self):
            return []

    def ctl(names):
        return FleetController(
            _FakeRouter(), _NoSpawner(),
            policy=AutoscalePolicy(slo_names=names, refresh_slo=False))

    assert ctl(("ttft",)).slo_burn() == 4.0
    assert ctl(("intertoken",)).slo_burn() == 9.0
    assert ctl(None).slo_burn() == 9.0             # unscoped = worst


# ------------------------------------------------------------ steady state
def test_steady_state_no_recompile_with_affinity_and_migration(gpt_model,
                                                               pair):
    """The mxcache acceptance guard: shared-prefix traffic + a page
    import + a migrated-prefix continuation after warmup compile
    NOTHING (the migration executables are in the warmup ladder)."""
    from mxnet_tpu.analysis import guards

    peer = pair[0]
    migrated = _prompt(900, body_len=9)            # 25 tokens, 3 pages
    ra = peer.generate(migrated, 4, seed=11)
    doc = peer.export_pages(migrated)

    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32,
                          page_size=8).start()
    try:
        eng.warmup()
        with guards.no_recompile(block="serve"):
            for s in (100, 101, 102):          # affinity-shaped traffic
                assert eng.generate(_prompt(s), 4, seed=s).status == "ok"
            res = eng.import_pages(doc)        # migration mid-serving
            assert res["received"] >= 1
            rb = eng.generate(migrated, 4, seed=11)
        assert list(rb.generated_ids) == list(ra.generated_ids)
    finally:
        eng.shutdown()


# -------------------------------------------------- drain bounce (LAST:
# this test DRAINS a replica of the shared pair, so every other fleet
# test must already have run)
def test_drain_bounce_end_to_end_no_duplicate_tokens(fleet, ref_eng):
    """Drain the affinity winner before its next request: the replay
    lands on the survivor with the output still bitwise-exact (exactly
    once — a double-dispatch would show up as a second submit)."""
    engines, fronts, router = fleet
    seeds = [800, 801]
    prompts = [_prompt(s) for s in seeds]
    ref = _reference(ref_eng, prompts, 4, seeds)
    before = [e.stats()["submitted"] for e in engines]

    doc = router.generate({"input_ids": prompts[0],
                           "max_new_tokens": 4, "seed": seeds[0]})
    assert doc["status"] == "ok"
    assert list(doc["generated_ids"]) == ref[0]
    # drain whichever replica now advertises THIS family's prefix
    winner_url = _wait_root(router, prompts[1])
    winner = next(i for i, f in enumerate(fronts) if f.url == winner_url)
    urllib.request.urlopen(urllib.request.Request(
        fronts[winner].url + "/drain", data=b"{}",
        headers={"Content-Type": "application/json"}), timeout=10)
    # same prefix again: dispatched to the (possibly still-listed)
    # winner, bounced, and replayed against the survivor
    doc = router.generate({"input_ids": prompts[1],
                           "max_new_tokens": 4, "seed": seeds[1]})
    assert doc["status"] == "ok"
    assert list(doc["generated_ids"]) == ref[1]
    total = sum(e.stats()["submitted"] - b
                for e, b in zip(engines, before))
    assert total == 2                              # no duplicate dispatch
