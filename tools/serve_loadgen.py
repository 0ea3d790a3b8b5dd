#!/usr/bin/env python
"""Closed-loop load generator for the serving engine (mxnet_tpu/serve).

``--concurrency`` worker threads each submit ``--requests`` requests
back-to-back (closed loop: a worker's next request starts when its
previous one completes) with mixed prompt lengths, then the tool prints
p50/p99 time-to-first-token, p50/p99 end-to-end latency, and aggregate
generated tokens/sec, plus the engine's compile/recompile counters so a
run doubles as a shape-bucketing check.

Default target is an in-process engine over a randomly-initialized tiny
GPT (no checkpoint needed — serving mechanics, not model quality, are
under test). ``--url`` points the same closed loop at a running HTTP
frontend instead.

``--compare-sequential`` also runs the identical request set through the
one-request-at-a-time ``generate()`` baseline (best of two passes, so the
baseline gets its warm-cache chance) and prints the batched speedup —
the acceptance demo: mixed-length traffic forces the per-request
compiled loop to pay a compile per novel shape, while the engine's
bucketed executables amortize across the whole mix.

Examples::

    JAX_PLATFORMS=cpu python tools/serve_loadgen.py
    JAX_PLATFORMS=cpu python tools/serve_loadgen.py \
        --concurrency 16 --requests 4 --compare-sequential
    python tools/serve_loadgen.py --url http://127.0.0.1:8000

    # fused multi-token decode: K tokens per host round-trip; the report
    # prints round-trips per generated token (~1/K)
    JAX_PLATFORMS=cpu python tools/serve_loadgen.py --multi-token 4

    # self-speculative decoding on repetitive/structured traffic
    # (templated JSON-ish prompts: boilerplate runs + key/value slots):
    # latency-bound interactive streams, K-1 drafts from each request's
    # own history verified in one dispatch; --spec-compare reruns the
    # identical traffic with --speculate 0 and prints the tok/s duel +
    # acceptance rate (the >=1.5x acceptance scenario)
    JAX_PLATFORMS=cpu python tools/serve_loadgen.py \
        --structured --speculate 6 --concurrency 1 --requests 8 \
        --max-new-tokens 80 --spec-compare

    # cold- vs warm-start through the persistent AOT compile cache
    JAX_PLATFORMS=cpu python tools/serve_loadgen.py \
        --aot-cache-dir /tmp/aot --aot-compare

    # 64-way concurrency on the pool bytes of 16 slots x max_len (the
    # >=4x requests/HBM acceptance): short mixed traffic, report
    # includes in-flight peak per pool GB
    JAX_PLATFORMS=cpu python tools/serve_loadgen.py \
        --max-batch-size 64 --num-pages 128 --prompt-max 12 \
        --max-new-tokens 12 --concurrency 64 --requests 2

    # grammar-constrained structured traffic: every completion must match
    # the JSON schema (validated per completion — the summary prints the
    # conformance count); --grammar-compare duels constrained vs
    # unconstrained tok/s + spec acceptance on identical traffic
    JAX_PLATFORMS=cpu python tools/serve_loadgen.py --structured \
        --speculate 4 --grammar \
        '{"type":"object","properties":{"ok":{"type":"boolean"}}}' \
        --grammar-compare

    # shared system-prompt traffic: every request carries the same
    # 24-token prefix; --prefix-compare reruns with the prefix cache off
    # and prints the mean-TTFT delta
    JAX_PLATFORMS=cpu python tools/serve_loadgen.py \
        --shared-prefix 24 --prefix-compare

    # mixed long-prompt traffic: 25% of prompts near max_len exercise
    # chunked prefill (bounded TTFT p99 for the short requests in flight)
    JAX_PLATFORMS=cpu python tools/serve_loadgen.py \
        --long-prompt-mix 0.25

    # self-managing fleet under step traffic: OPEN-loop ramp-hold-drop
    # arrivals against an in-process router + autoscale controller; the
    # summary records every scale event, SLO burn, and asserts zero
    # failed requests while the fleet scales fleet-min -> N -> fleet-min
    JAX_PLATFORMS=cpu python tools/serve_loadgen.py \
        --traffic-pattern step --fleet-min 2 --fleet-max 4 \
        --step-low-rps 2 --step-high-rps 25 --phase-s 6

    # two-tenant mixed load through the same fleet: tenant weights 3:1
    # with a quota on the bursty tenant; per-tenant p50/p99 in the
    # summary prove the starved tenant's tail stays bounded
    JAX_PLATFORMS=cpu python tools/serve_loadgen.py \
        --traffic-pattern step --fleet-min 2 --fleet-max 4 \
        --tenant-mix interactive:3,batch:1 --tenant-quota batch:4
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def pct(values, q):
    if not values:
        return float("nan")
    vals = sorted(values)
    i = min(int(round(q / 100.0 * (len(vals) - 1))), len(vals) - 1)
    return vals[i]


# the loadgen harness defaults — the SHARED definition of "the loadgen
# model": bench.py (aot warm-start) and tests/test_aot.py build exactly
# this via default_model(), so the acceptance numbers measure the same
# program this harness serves
DEFAULTS = dict(vocab=256, hidden=64, layers=2, heads=4,
                max_batch_size=16, max_len=128, seed=0)


def default_model(seed=DEFAULTS["seed"], vocab=DEFAULTS["vocab"],
                  hidden=DEFAULTS["hidden"], layers=DEFAULTS["layers"],
                  heads=DEFAULTS["heads"], max_len=DEFAULTS["max_len"]):
    import mxnet_tpu as mx
    from mxnet_tpu.models import GPTModel
    from mxnet_tpu.models.gpt import GPTConfig
    mx.random.seed(seed)
    net = GPTModel(GPTConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_heads=heads, max_position_embeddings=max(2 * max_len, 64),
        dropout=0.0))
    net.initialize()
    return net


def build_model(args):
    net = default_model(seed=args.seed, vocab=args.vocab,
                        hidden=args.hidden, layers=args.layers,
                        heads=args.heads, max_len=args.max_len)
    bits = getattr(args, "bits", None)
    if bits:
        # weight-only int8/int4 decode (GEMV kernels + the fused head)
        from mxnet_tpu.contrib.quantization import quantize_net
        quantize_net(net, calib_mode="none", bits=bits)
    return net


def _headroom(args):
    """Per-request cache-row headroom past the final token: K-1 for
    multi-token, speculate-1 for draft-verify rounds (mutually
    exclusive)."""
    return max(args.multi_token, args.speculate or 1) - 1


def structured_prompts(n, vocab, seed=0, boiler_run=16, n_keys=3,
                       max_tokens=None):
    """Templated JSON-ish prompts: boilerplate runs (the structural
    indent/quote tokens that dominate machine-generated text) around a
    few fixed "key" tokens with per-request "values" — the repetitive
    traffic self-speculation drafts well on. THE shared definition of
    the structured scenario: `--structured` here, `bench_spec_decode`,
    and mxtune's `spec` workload all build exactly this traffic, so the
    acceptance/speedup numbers measure one shape."""
    import numpy as onp
    rng = onp.random.RandomState(seed)
    boiler = int(rng.randint(1, vocab - 1))
    keys = rng.randint(1, vocab - 1, size=n_keys)
    prompts = []
    for i in range(n):
        body = []
        for k in keys:
            body.extend([boiler] * boiler_run)
            body.append(int(k))
            body.append(int(rng.randint(1, vocab - 1)))
        if max_tokens is not None:
            body = body[:max_tokens]
        prompts.append(onp.asarray(body, onp.int32))
    return prompts


def make_prompts(args):
    import numpy as onp
    rng = onp.random.RandomState(args.seed)
    n = args.concurrency * args.requests
    # the longest prompt a request may carry and still fit its budget
    hard_max = args.max_len - args.max_new_tokens - _headroom(args)
    if args.structured:
        return structured_prompts(n, args.vocab, seed=args.seed,
                                  max_tokens=hard_max)
    shared = (rng.randint(1, args.vocab - 1, size=args.shared_prefix)
              .astype(onp.int32) if args.shared_prefix else
              onp.zeros(0, onp.int32))
    long_len = max(args.prompt_max + 1, hard_max - len(shared))
    prompts = []
    for i in range(n):
        if args.long_prompt_mix and rng.rand() < args.long_prompt_mix:
            size = long_len
        else:
            size = rng.randint(args.prompt_min, args.prompt_max + 1)
        size = max(1, min(size, hard_max - len(shared)))
        body = rng.randint(1, args.vocab - 1, size=size).astype(onp.int32)
        prompts.append(onp.concatenate([shared, body]))
    return prompts


def make_tenant_prompts(args):
    """Fleet-affinity traffic: each worker is a "tenant" whose requests
    all carry the SAME ``--shared-prefix``-token preamble (system
    prompt), distinct across workers — the fleet-scale shape where
    prefix-affinity routing wins: a tenant's prefix is cached on ONE
    replica, and prefix-blind dispatch scatters its requests away from
    it."""
    import numpy as onp
    rng = onp.random.RandomState(args.seed)
    hard_max = args.max_len - args.max_new_tokens - _headroom(args)
    prompts = []
    for w in range(args.concurrency):
        prefix = rng.randint(1, args.vocab - 1,
                             size=args.shared_prefix).astype(onp.int32)
        for r in range(args.requests):
            size = rng.randint(args.prompt_min, args.prompt_max + 1)
            size = max(1, min(size, hard_max - len(prefix)))
            body = rng.randint(1, args.vocab - 1,
                               size=size).astype(onp.int32)
            prompts.append(onp.concatenate([prefix, body]))
    return prompts


def parse_grammar_arg(spec):
    """``--grammar`` accepts a JSON-schema document (a JSON object) or a
    raw regex string — the same two sources ``compile_grammar`` takes."""
    try:
        doc = json.loads(spec)
    except ValueError:
        return spec
    return doc if isinstance(doc, dict) else spec


def engine_kwargs(args, prefix_cache=True, speculate=None, grammar=None):
    """Engine options shared by the serve and compare passes.
    ``speculate`` overrides args.speculate (the --spec-compare baseline
    pass forces 0); ``grammar=False`` builds a PLAIN engine for the
    --grammar-compare baseline (the constrained pass's executables take
    mask operands, so a fair tok/s duel needs the ungated program)."""
    spec = args.speculate if speculate is None else speculate
    gram = (getattr(args, "grammar", None) is not None
            if grammar is None else grammar)
    # speculate passed EXPLICITLY even at 0: an activated tuned
    # serve_speculate winner must never silently re-enable speculation
    # in a measurement baseline (explicit args outrank the tune layer)
    kw = dict(max_batch_size=args.max_batch_size, max_len=args.max_len,
              multi_token=args.multi_token, speculate=spec,
              grammar=gram, page_size=args.page_size,
              num_pages=args.num_pages, prefill_chunk=args.prefill_chunk,
              prefix_cache=prefix_cache and not args.no_prefix_cache)
    if spec and args.spec_lookup is not None:
        kw["spec_lookup"] = args.spec_lookup
    return kw


def run_inprocess(args, prompts, prefix_cache=True, speculate=None,
                  grammar=None):
    from mxnet_tpu import aot, metrics
    from mxnet_tpu.models import generate
    from mxnet_tpu.observability import perf as obs_perf
    from mxnet_tpu.observability import trace as obs_trace
    from mxnet_tpu.serve import InferenceEngine, compile_grammar
    from mxnet_tpu import np as mnp

    # constrained pass: the compiled automaton doubles as the per-
    # completion conformance validator (grammar=False = the
    # --grammar-compare unconstrained baseline)
    gsrc = (parse_grammar_arg(args.grammar)
            if grammar is not False and args.grammar is not None else None)
    gram = compile_grammar(gsrc, args.vocab) if gsrc is not None else None

    metrics.enable()
    # the cost ledger captures every bucket executable at warmup so the
    # summary can print the decode MFU/regime verdict
    obs_perf.enable()
    if not args.no_trace:
        # tracing on by default in the loadgen: the report's p99-tail
        # exemplars hand you the exact trace ids to pull. Size the store
        # to the whole run so the slowest (often OLDEST) requests'
        # traces are not LRU-evicted before the summary prints them.
        obs_trace.enable(max_traces=max(256, 2 * len(prompts)))

    def _counter(name):
        doc = json.loads(metrics.dumps("json"))
        return sum(s["value"]
                   for s in doc.get(name, {}).get("samples", []))

    # snapshot the process-global counters so a compare pass (this fn
    # runs TWICE under --prefix-compare/--aot-compare) prints ITS deltas,
    # not the cumulative totals of both runs
    base = {n: _counter(n) for n in (
        "mxnet_serve_page_prefill_chunks_total",
        "mxnet_serve_compiles_total",
        "mxnet_serve_host_roundtrips_total",
        "mxnet_serve_tokens_total")}
    if args.aot_cache_dir:
        cache = aot.enable(args.aot_cache_dir)
        print(f"AOT cache: {cache.path} "
              f"({len(cache.entries())} entries, {cache.total_bytes()} B)")
        if args.aot_compare:
            # the cold-start acceptance number: full ladder XLA-compiled
            # against an empty dir vs deserialized from the warm one
            cache.clear()
            cold = InferenceEngine(
                build_model(args), max_batch_size=args.max_batch_size,
                max_len=args.max_len).warmup().last_warmup_s
            warm = InferenceEngine(
                build_model(args), max_batch_size=args.max_batch_size,
                max_len=args.max_len).warmup().last_warmup_s
            print(f"AOT cold warmup: {cold:.2f}s, warm warmup: {warm:.2f}s "
                  f"-> {cold / warm:.2f}x faster cold-start")
    net = build_model(args)
    eng = InferenceEngine(net, max_queue_depth=max(64, len(prompts)),
                          **engine_kwargs(args, prefix_cache, speculate,
                                          grammar=gram is not None))
    eng.start()
    t0 = time.perf_counter()
    eng.warmup()
    print(f"warmup: {time.perf_counter() - t0:.2f}s, "
          f"buckets {eng.stats()['compiled_buckets']}")
    if args.aot_cache_dir:
        hits = metrics.get_sample_value("mxnet_aot_cache_hits_total") or 0
        misses = metrics.get_sample_value(
            "mxnet_aot_cache_misses_total") or 0
        print(f"AOT cache: {hits:.0f} hits / {misses:.0f} misses")

    records = []
    conform = {"ok": 0, "bad": 0}
    lock = threading.Lock()

    def worker(w):
        for r in range(args.requests):
            p = prompts[w * args.requests + r]
            extra = {}
            if gram is not None:
                extra = {"grammar": gram,
                         "eos_token_id": args.eos_token_id}
            res = eng.generate(p, args.max_new_tokens,
                               temperature=args.temperature,
                               top_k=args.top_k, top_p=args.top_p,
                               seed=w * 1000 + r, **extra)
            with lock:
                records.append((res.status, res.ttft_s, res.latency_s,
                                len(res.generated_ids), res.trace_id))
                if gram is not None:
                    # per-completion schema validation: the automaton
                    # replays the emitted tokens — the by-construction
                    # claim, checked from the outside
                    valid = gram.matches(res.generated_ids,
                                         eos_token_id=args.eos_token_id)
                    conform["ok" if valid else "bad"] += 1

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(args.concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    summary = report(records, wall)

    if gram is not None:
        total = conform["ok"] + conform["bad"]
        summary["grammar_conformant"] = conform["ok"]
        summary["grammar_total"] = total
        rej = (_counter("mxnet_grammar_rejected_tokens_total"))
        print(f"  grammar: {conform['ok']}/{total} completions "
              f"schema-conformant (validated per completion), "
              f"{rej:.0f} draft tokens rewritten by the automaton")
        if conform["bad"]:
            print("  GRAMMAR CONFORMANCE FAILURES — the by-construction "
                  "guarantee is broken")

    # HBM efficiency: how many concurrent requests one GB of KV pool
    # carried. num_pages defaults to max_batch_size * max_len tokens (a
    # contiguous cache's byte footprint), so this is the apples-to-apples
    # >=4x number.
    st = eng.stats()
    kv_gb = st["kv_bytes"] / 1e9
    layout = "%d pages x %d" % (st["pages"]["pages"], st["page_size"])
    # numerator is the concurrency the engine actually sustained
    # (max_active), not the requested --concurrency: an admission-gated
    # run must not overstate the >=4x acceptance number
    print(f"  KV pool: {st['kv_bytes'] / 1e6:.1f} MB ({layout}) "
          f"-> {st['max_active'] / kv_gb:.0f} concurrent requests/HBM-GB "
          f"(peak {st['max_active']} in flight of {args.concurrency} "
          f"offered)")
    p = st["pages"]
    chunks = (_counter("mxnet_serve_page_prefill_chunks_total")
              - base["mxnet_serve_page_prefill_chunks_total"])
    print(f"  pages: {p['leases']} leased, {p['cow_forks']} COW forks, "
          f"{st['preemptions']} preemptions, "
          f"{chunks:.0f} prefill chunks")
    print(f"  prefix cache: {p['prefix_hits']} hits / "
          f"{p['prefix_misses']} misses, "
          f"{p['prefix_tokens_saved']} prompt tokens not re-prefilled")

    compiles = (_counter("mxnet_serve_compiles_total")
                - base["mxnet_serve_compiles_total"])
    print(f"bucket executables compiled (incl. warmup): {compiles:.0f}; "
          "rerun traffic compiles ZERO more (steady state)")

    # the multi-token overlap, visible from the client side: host
    # round-trips (blocking D2H reads) per generated token — ~1 at K=1,
    # ~1/K with the on-device multi-token loop
    rt = (_counter("mxnet_serve_host_roundtrips_total")
          - base["mxnet_serve_host_roundtrips_total"])
    toks = (_counter("mxnet_serve_tokens_total")
            - base["mxnet_serve_tokens_total"])
    if toks:
        print(f"host round-trips: {rt:.0f} for {toks:.0f} generated tokens "
              f"-> {rt / toks:.3f} round-trips/token "
              f"(multi_token={args.multi_token})")

    spec = st.get("spec")
    if spec:
        rate = spec["acceptance_rate"]
        print(f"speculative decode (K={st['speculate']}): "
              f"{spec['rounds']} verify rounds, {spec['accepted']} of "
              f"{spec['drafted']} drafts accepted "
              f"(acceptance {rate if rate is None else round(rate, 3)}); "
              "output is token-exact vs --speculate 0")
        summary["spec_acceptance"] = rate
    summary["tokens_per_sec"] = (summary["tokens"] / summary["wall"]
                                 if summary["wall"] else float("nan"))

    # the live roofline verdict for the decode path (cost ledger +
    # most recent step note — the line ROOFLINE.md used to need a
    # hand-built script for; per-executable detail: /perf, mxperf.py)
    for path in ("serve_decode", "serve_prefill"):
        roof = obs_perf.summary().get(path)
        if roof:
            print(f"  {path} roofline: MFU {roof['mfu']:.5f}, HBM util "
                  f"{roof['hbm_util_fraction']:.5f} -> "
                  f"{roof['regime']}-bound ({roof['key']})")

    if args.compare_sequential:
        seq = float("inf")
        for _ in range(2):  # warm pass: give the per-request cache a chance
            t0 = time.perf_counter()
            for p in prompts:
                generate(net, mnp.array(p[None, :]), args.max_new_tokens,
                         temperature=args.temperature, top_k=args.top_k,
                         top_p=args.top_p)
            seq = min(seq, time.perf_counter() - t0)
        ntok = sum(r[3] for r in records)
        print(f"sequential generate() baseline (best of 2): {seq:.3f}s "
              f"({ntok / seq:.0f} tok/s)")
        print(f"batched speedup: {seq / wall:.2f}x")
    eng.shutdown()
    return summary


def run_http(args, prompts):
    records = []
    lock = threading.Lock()

    def worker(w):
        for r in range(args.requests):
            p = prompts[w * args.requests + r]
            body = json.dumps({
                "input_ids": [int(t) for t in p],
                "max_new_tokens": args.max_new_tokens,
                "temperature": args.temperature, "top_k": args.top_k,
                "top_p": args.top_p, "seed": w * 1000 + r,
            }).encode()
            req = urllib.request.Request(
                args.url.rstrip("/") + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            doc = json.loads(urllib.request.urlopen(req, timeout=600).read())
            dt = time.perf_counter() - t0
            with lock:
                records.append((doc["status"], doc.get("ttft_s"), dt,
                                len(doc.get("generated_ids", [])),
                                doc.get("trace_id")))

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(args.concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    report(records, time.perf_counter() - t0)


def report(records, wall):
    ok = [r for r in records if r[0] == "ok"]
    bad = [r for r in records if r[0] != "ok"]
    ttfts = [r[1] for r in ok if r[1] is not None]
    lats = [r[2] for r in ok]
    ntok = sum(r[3] for r in records)
    print(f"requests: {len(records)} ({len(ok)} ok, {len(bad)} not-ok) "
          f"in {wall:.3f}s")
    print(f"  TTFT    p50 {pct(ttfts, 50) * 1e3:8.1f} ms   "
          f"p99 {pct(ttfts, 99) * 1e3:8.1f} ms")
    print(f"  latency p50 {pct(lats, 50) * 1e3:8.1f} ms   "
          f"p99 {pct(lats, 99) * 1e3:8.1f} ms")
    print(f"  throughput: {ntok / wall:.0f} generated tokens/s")
    # p99-tail exemplars: the slowest requests' trace ids, so a slow run
    # hands you the exact span trees to pull from /trace/{id}. ALL
    # traced records qualify — timeouts/errors carry span trees too and
    # are exactly the tail worth pulling
    traced = sorted((r for r in records if len(r) > 4 and r[4]),
                    key=lambda r: r[2], reverse=True)
    exemplars = []
    if traced:
        p99_lat = pct([r[2] for r in traced], 99)
        tail = [r for r in traced if r[2] >= p99_lat] or traced[:1]
        exemplars = [{"trace_id": r[4], "latency_s": r[2],
                      "ttft_s": r[1]} for r in tail[:3]]
        print("  slowest requests (p99 tail — pull via /trace/{id}):")
        for e in exemplars:
            ttft_ms = (e["ttft_s"] or 0) * 1e3
            print(f"    latency {e['latency_s'] * 1e3:8.1f} ms   "
                  f"ttft {ttft_ms:8.1f} ms   trace {e['trace_id']}")
    return {"ok": len(ok), "wall": wall,
            "ttft_mean": sum(ttfts) / len(ttfts) if ttfts else float("nan"),
            "ttft_p99": pct(ttfts, 99), "tokens": ntok,
            "slow_exemplars": exemplars}


def parse_mix(spec):
    """'name:weight,name:weight' -> {name: float weight}."""
    out = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition(":")
        out[name.strip()] = float(w) if w else 1.0
    return out


def run_step_fleet(args, prompts):
    """Open-loop ramp-hold-drop traffic against an in-process
    SELF-MANAGING fleet: ``--fleet-min`` replicas to start, an autoscale
    controller that spawns/drains on load + SLO burn, optional
    multi-tenant WFQ admission. The summary records every scale event,
    the SLO error-budget burn, per-tenant latency percentiles, and the
    acceptance line: fleet-min -> peak -> fleet-min with zero failed
    requests."""
    import numpy as onp

    from mxnet_tpu import metrics
    from mxnet_tpu.serve import (AutoscalePolicy, FleetController,
                                 InferenceEngine, InProcessSpawner,
                                 Router, TenantPolicy)

    metrics.enable()
    mix = parse_mix(args.tenant_mix)
    quotas = {k: int(v) for k, v in parse_mix(args.tenant_quota).items()}
    tenants = {name: TenantPolicy(weight=w, max_inflight=quotas.get(name))
               for name, w in mix.items()} or None
    for q in quotas:
        if mix and q not in mix:
            raise SystemExit(f"--tenant-quota {q!r} not in --tenant-mix")

    def build():
        return InferenceEngine(build_model(args),
                               max_queue_depth=max(64, len(prompts)),
                               **engine_kwargs(args))

    spawner = InProcessSpawner(build)
    urls = [spawner.spawn() for _ in range(args.fleet_min)]
    slo = {k: v for k, v in (("ttft", args.slo_ttft),
                             ("intertoken", args.slo_intertoken))
           if v is not None}
    router = Router(urls, health_interval=0.2, slo_targets=slo or None,
                    tenants=tenants).start()
    policy = AutoscalePolicy(
        scale_up_load=args.scale_up_load,
        scale_down_load=args.scale_down_load,
        up_after=2, down_after=4, cooldown_s=args.cooldown_s,
        min_replicas=args.fleet_min, max_replicas=args.fleet_max,
        drain_grace_s=60.0)
    ctl = FleetController(router, spawner, policy=policy,
                          interval=0.25).start()

    # deterministic open-loop schedule: evenly spaced arrivals per phase
    phases = [("ramp", args.step_low_rps), ("hold", args.step_high_rps),
              ("drop", args.step_low_rps)]
    arrivals = []
    t = 0.0
    rng = onp.random.RandomState(args.seed)
    names = sorted(mix) or [None]
    weights = onp.array([mix[n] for n in sorted(mix)]) if mix else None
    probs = weights / weights.sum() if mix else None
    for phase, rps in phases:
        n = max(1, int(round(rps * args.phase_s)))
        for i in range(n):
            tenant = (names[rng.choice(len(names), p=probs)]
                      if mix else None)
            arrivals.append((t + (i + 0.5) * args.phase_s / n, phase,
                             tenant))
        t += args.phase_s

    records, lock = [], threading.Lock()
    peak = {"healthy": len(urls)}

    def fire(idx, phase, tenant):
        p = prompts[idx % len(prompts)]
        payload = {"input_ids": [int(x) for x in p],
                   "max_new_tokens": args.max_new_tokens,
                   "temperature": args.temperature, "top_k": args.top_k,
                   "top_p": args.top_p, "seed": idx}
        if tenant is not None:
            payload["tenant"] = tenant
        t0 = time.perf_counter()
        try:
            doc = router.generate(payload)
            status, ttft = doc.get("status"), doc.get("ttft_s")
        except Exception as e:
            status, ttft, doc = f"error:{type(e).__name__}", None, {}
        with lock:
            records.append((status, ttft, time.perf_counter() - t0,
                            len(doc.get("generated_ids", []) or []),
                            doc.get("trace_id"), phase, tenant))

    print(f"step traffic: {len(arrivals)} requests over "
          f"{t:.0f}s ({' -> '.join(f'{p}@{r}rps' for p, r in phases)}), "
          f"fleet {args.fleet_min}..{args.fleet_max}"
          + (f", tenants {mix} quotas {quotas}" if mix else ""))
    t_start = time.perf_counter()
    threads = []
    for idx, (offset, phase, tenant) in enumerate(arrivals):
        delay = t_start + offset - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=fire, args=(idx, phase, tenant))
        th.start()
        threads.append(th)
        peak["healthy"] = max(peak["healthy"], router.stats()["healthy"])
    for th in threads:
        th.join()
    wall = time.perf_counter() - t_start
    # let the controller scale back down to the floor
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 45:
        st = router.stats()
        peak["healthy"] = max(peak["healthy"], st["healthy"])
        if st["healthy"] <= args.fleet_min and not ctl.stats()["retiring"]:
            break
        time.sleep(0.25)

    summary = report([r[:5] for r in records], wall)
    final = router.stats()
    events = ctl.stats()["events"]
    ups = [e for e in events if e["direction"] == "up"]
    downs = [e for e in events if e["direction"] == "down"]
    bad = [r for r in records if r[0] != "ok"]
    print(f"  fleet: {args.fleet_min} -> peak {peak['healthy']} -> "
          f"{final['healthy']} replicas ({len(ups)} scale-ups, "
          f"{len(downs)} scale-downs, "
          f"{len(bad)} failed requests)")
    for e in events:
        print(f"    scale {e['direction']:4s} reason={e['reason']:8s} "
              f"replicas={e['replicas']} pressure={e['pressure']:.2f} "
              f"burn={e['burn']:.2f}")
    slo_st = final.get("slo", {}).get("last", {})
    for name, d in slo_st.items():
        print(f"  SLO {name}: p99 {d['p99'] * 1e3:.1f} ms vs target "
              f"{d['target'] * 1e3:.0f} ms, burn {d['burn']:.3f} "
              f"({'OK' if d['burn'] <= 1.0 else 'BURNING'})")
    if mix:
        by_tenant = {}
        for r in records:
            by_tenant.setdefault(r[6], []).append(r)
        print("  per-tenant isolation (mixed load):")
        for name in sorted(by_tenant):
            rs = by_tenant[name]
            lats = [r[2] for r in rs if r[0] == "ok"]
            print(f"    {name:12s} {len(rs):4d} reqs  "
                  f"latency p50 {pct(lats, 50) * 1e3:8.1f} ms  "
                  f"p99 {pct(lats, 99) * 1e3:8.1f} ms  "
                  f"(weight {mix[name]}, quota {quotas.get(name)})")
    summary.update({"failed": len(bad), "peak_replicas": peak["healthy"],
                    "scale_ups": len(ups), "scale_downs": len(downs),
                    "events": events, "slo": slo_st})
    ctl.stop()
    router.stop()
    spawner.stop_all()
    if bad:
        print(f"FAILED REQUESTS: {bad[:5]}")
    return summary


def affinity_reference(args, prompts):
    """The bitwise token-exactness oracle for the fleet duel: every
    request replayed one at a time on ONE replica. Stateless sampling
    (seed + position, not RNG state) means any replica — including one
    resuming a migrated request — must produce these exact tokens."""
    from mxnet_tpu.serve import InferenceEngine
    eng = InferenceEngine(build_model(args),
                          max_queue_depth=max(64, len(prompts)),
                          **engine_kwargs(args))
    eng.start()
    eng.warmup()
    ref = []
    for idx, p in enumerate(prompts):
        res = eng.generate(p, args.max_new_tokens,
                           temperature=args.temperature,
                           top_k=args.top_k, top_p=args.top_p, seed=idx)
        ref.append(tuple(int(t) for t in res.generated_ids))
    eng.shutdown()
    return ref


def run_affinity_fleet(args, prompts, reference, affinity=True):
    """Closed-loop tenant traffic (worker w = tenant w, all of w's
    requests share prefix_w) against a FIXED fleet of --fleet-replicas
    paged replicas behind the router, with prefix-affinity dispatch on
    or off. The summary carries mean/p99 TTFT, the affinity outcome
    counters, and the token-divergence count vs the single-replica
    reference (the acceptance number is ZERO either way)."""
    from mxnet_tpu import metrics
    from mxnet_tpu.serve import InferenceEngine, InProcessSpawner, Router

    metrics.enable()
    names = ("mxnet_cache_affinity_dispatch_total",
             "mxnet_cache_affinity_hit_tokens_total",
             "mxnet_serve_compiles_total",
             "mxnet_serve_page_prefix_tokens_saved_total",
             "mxnet_serve_page_prefill_chunks_total")

    def _counter(name, labels=None):
        if labels is not None:
            return metrics.get_sample_value(name, labels) or 0
        doc = json.loads(metrics.dumps("json"))
        return sum(s["value"]
                   for s in doc.get(name, {}).get("samples", []))

    # process-global counters; this fn runs twice under the duel
    base = {n: _counter(n) for n in names}
    outcome_base = {o: _counter(names[0], {"outcome": o})
                    for o in ("hit", "load_bounded", "cold")}

    def build():
        kw = engine_kwargs(args)
        # each replica caches several tenants' prefixes, each spanning
        # multiple page-boundary roots — advertise enough of them that
        # no tenant's root falls off the bounded summary mid-duel
        kw["prefix_advert"] = max(32, 4 * args.concurrency)
        return InferenceEngine(build_model(args),
                               max_queue_depth=max(64, len(prompts)),
                               **kw)

    # warmup at spawn: the duel measures dispatch quality, not compiles
    spawner = InProcessSpawner(build, warmup=True)
    urls = [spawner.spawn() for _ in range(args.fleet_replicas)]
    # fast health polls: adverts refresh between a tenant's requests,
    # so request 2..N see the root request 1 published
    router = Router(urls, health_interval=0.1, affinity=affinity).start()

    records, lock = [], threading.Lock()
    tokens = {}

    # a shared SHUFFLED job queue, not a worker per tenant: a real
    # frontend doesn't hold a connection per tenant, so without this,
    # synchronized closed loops + least-loaded's URL tie-break give the
    # BLIND baseline accidental tenant stickiness and the duel measures
    # nothing. Shuffling also spaces a tenant's requests out past the
    # health-poll interval, so its advert is live by request 2.
    import numpy as onp
    jobs = list(range(len(prompts)))
    onp.random.RandomState(args.seed + 1).shuffle(jobs)

    def worker():
        while True:
            with lock:
                if not jobs:
                    return
                idx = jobs.pop()
            p = prompts[idx]
            payload = {"input_ids": [int(x) for x in p],
                       "max_new_tokens": args.max_new_tokens,
                       "temperature": args.temperature,
                       "top_k": args.top_k, "top_p": args.top_p,
                       "seed": idx}
            t0 = time.perf_counter()
            try:
                doc = router.generate(payload)
                status, ttft = doc.get("status"), doc.get("ttft_s")
            except Exception as e:
                status, ttft, doc = f"error:{type(e).__name__}", None, {}
            with lock:
                records.append((status, ttft, time.perf_counter() - t0,
                                len(doc.get("generated_ids", []) or []),
                                doc.get("trace_id")))
                tokens[idx] = tuple(doc.get("generated_ids") or ())

    nworkers = args.fleet_workers or args.fleet_replicas
    mode = "prefix-affinity" if affinity else "prefix-blind"
    print(f"fleet duel [{mode}]: {args.fleet_replicas} replicas, "
          f"{nworkers} workers, {args.concurrency} tenants x "
          f"{args.requests} requests (shuffled), "
          f"{args.shared_prefix}-token tenant prefixes")
    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker)
               for _ in range(nworkers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    summary = report(records, wall)
    # raw per-request TTFTs: bench_prefix_affinity records their spread
    summary["ttfts"] = sorted(r[1] for r in records
                              if r[0] == "ok" and r[1] is not None)

    diverged = [i for i, ref in enumerate(reference)
                if tokens.get(i) != ref]
    summary["token_divergence"] = len(diverged)
    outcomes = {o: _counter(names[0], {"outcome": o}) - outcome_base[o]
                for o in outcome_base}
    hit_toks = (_counter(names[1]) - base[names[1]])
    compiles = (_counter(names[2]) - base[names[2]])
    summary.update({"affinity_outcomes": outcomes,
                    "affinity_hit_tokens": hit_toks})
    saved = _counter(names[3]) - base[names[3]]
    chunks = _counter(names[4]) - base[names[4]]
    summary["prefix_tokens_saved"] = saved
    print(f"  dispatch outcomes: {outcomes['hit']:.0f} affinity hits / "
          f"{outcomes['load_bounded']:.0f} load-bounded / "
          f"{outcomes['cold']:.0f} cold; "
          f"{hit_toks:.0f} prompt tokens routed onto cached pages")
    print(f"  replica prefix caches: {saved:.0f} prompt tokens not "
          f"re-prefilled, {chunks:.0f} prefill chunks")
    print(f"  token divergence: {len(diverged)} of {len(reference)} "
          f"requests (bitwise vs single-replica reference)"
          + (f" DIVERGED: {diverged[:8]}" if diverged else ""))
    print(f"  bucket executables compiled (incl. {len(urls)} warmups): "
          f"{compiles:.0f}")
    router.stop()
    spawner.stop_all()
    return summary


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--url", default=None,
                    help="target a running HTTP frontend instead of an "
                         "in-process engine")
    ap.add_argument("--concurrency", type=int, default=16)
    ap.add_argument("--requests", type=int, default=4,
                    help="requests per worker (closed loop)")
    ap.add_argument("--prompt-min", type=int, default=4)
    ap.add_argument("--prompt-max", type=int, default=24)
    ap.add_argument("--max-new-tokens", type=int, default=48)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--max-batch-size", type=int, default=None,
                    help="slots per engine (default 16; 4 in step mode "
                         "so per-replica saturation — the scale-up "
                         "signal — is reachable at laptop-scale rates)")
    ap.add_argument("--max-len", type=int, default=DEFAULTS["max_len"])
    ap.add_argument("--vocab", type=int, default=DEFAULTS["vocab"])
    ap.add_argument("--hidden", type=int, default=DEFAULTS["hidden"])
    ap.add_argument("--layers", type=int, default=DEFAULTS["layers"])
    ap.add_argument("--heads", type=int, default=DEFAULTS["heads"])
    ap.add_argument("--seed", type=int, default=DEFAULTS["seed"])
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page (default: the engine's, 16 "
                         "unless tuned)")
    ap.add_argument("--num-pages", "--pool-pages", type=int, default=None,
                    dest="num_pages", metavar="N",
                    help="page-pool size; default max_batch_size * "
                         "max_len / page_size. --pool-pages is an "
                         "alias")
    ap.add_argument("--bits", type=int, default=None, choices=(4, 8),
                    help="weight-only quantize the model: 8 = int8 "
                         "tables, 4 = packed int4 nibble tables "
                         "dequantized in-kernel")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="tokens per chunked-prefill step (default one "
                         "page)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable shared-prefix page reuse")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend the SAME N-token system prompt to every "
                         "request (prefix-cache traffic)")
    ap.add_argument("--prefix-compare", action="store_true",
                    help="rerun the identical traffic with the prefix "
                         "cache disabled and print the mean-TTFT delta")
    ap.add_argument("--fleet", action="store_true",
                    help="closed-loop TENANT traffic (worker w's requests "
                         "all share prefix_w) against a fixed in-process "
                         "fleet behind the prefix-affinity router; needs "
                         "--shared-prefix N")
    ap.add_argument("--fleet-replicas", type=int, default=4,
                    help="--fleet: replica count (fixed, no autoscaler)")
    ap.add_argument("--fleet-workers", type=int, default=None,
                    help="--fleet: closed-loop workers draining the "
                         "shared job queue (default: one per replica; "
                         "lower it to measure prefill cost with queue "
                         "wait out of the TTFT)")
    ap.add_argument("--prefix-affinity-compare", action="store_true",
                    help="--fleet: rerun the identical traffic with "
                         "prefix-BLIND (least-loaded) dispatch and print "
                         "the mean-TTFT duel; both passes are checked "
                         "bitwise against a single-replica reference")
    ap.add_argument("--long-prompt-mix", type=float, default=0.0,
                    metavar="FRAC",
                    help="fraction of prompts stretched to near max_len "
                         "(chunked-prefill traffic)")
    ap.add_argument("--multi-token", type=int, default=1, metavar="K",
                    help="emit K tokens per decode dispatch (on-device "
                         "lax.while_loop); the report includes host "
                         "round-trips per generated token")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="self-speculative decoding: verify K-1 tokens "
                         "drafted from each request's own history per "
                         "dispatch (token-exact vs --speculate 0; the "
                         "report adds acceptance rate)")
    ap.add_argument("--spec-lookup", type=int, default=None, metavar="N",
                    help="max n-gram the prompt-lookup draft source "
                         "matches (default: the engine/tuned default)")
    ap.add_argument("--structured", action="store_true",
                    help="templated JSON-ish prompts (boilerplate runs "
                         "+ key/value slots) — the repetitive traffic "
                         "speculation drafts well on")
    ap.add_argument("--spec-compare", action="store_true",
                    help="rerun the identical traffic with --speculate 0 "
                         "and print the decode tok/s duel + acceptance")
    ap.add_argument("--grammar", default=None, metavar="SCHEMA",
                    help="grammar-constrain every completion: a JSON "
                         "schema document or a regex string (compiled to "
                         "the token automaton; every completion is "
                         "validated against it and the summary prints "
                         "the conformance count)")
    ap.add_argument("--grammar-compare", action="store_true",
                    help="rerun the identical traffic UNCONSTRAINED on a "
                         "plain engine and print the tok/s duel + spec "
                         "acceptance under both (the <10%% constrained-"
                         "decode cost claim)")
    ap.add_argument("--eos-token-id", type=int, default=0,
                    help="EOS token for grammar requests (the automaton "
                         "requires one to terminate on)")
    ap.add_argument("--no-trace", action="store_true",
                    help="in-process mode: disable request tracing (on by "
                         "default so the summary can print p99-tail "
                         "trace-id exemplars). With --url the SERVER's "
                         "tracing config decides whether responses carry "
                         "trace ids")
    ap.add_argument("--compare-sequential", action="store_true",
                    help="also time the one-request-at-a-time generate() "
                         "baseline and print the batched speedup")
    ap.add_argument("--aot-cache-dir", default=None,
                    help="enable the persistent AOT compile cache at this "
                         "directory (warm-starts the bucket ladder)")
    ap.add_argument("--aot-compare", action="store_true",
                    help="with --aot-cache-dir: clear the cache, time a "
                         "cold warmup, then a warm one, and print the "
                         "cold-start speedup before serving traffic")
    ap.add_argument("--traffic-pattern", choices=("closed", "step"),
                    default="closed",
                    help="closed: --concurrency workers back-to-back "
                         "(default); step: OPEN-loop ramp-hold-drop "
                         "arrivals against an in-process self-managing "
                         "fleet (autoscaler + router), summary records "
                         "scale events + SLO burn")
    ap.add_argument("--step-low-rps", type=float, default=1.0,
                    help="step pattern: arrival rate of the ramp/drop "
                         "phases")
    ap.add_argument("--step-high-rps", type=float, default=5.0,
                    help="step pattern: arrival rate of the hold phase "
                         "(default sized to saturate the 2-replica floor "
                         "of 4-slot CPU engines but stay under the "
                         "4-replica ceiling, so the backlog drains)")
    ap.add_argument("--phase-s", type=float, default=8.0,
                    help="step pattern: seconds per phase (3 phases)")
    ap.add_argument("--fleet-min", type=int, default=2,
                    help="step pattern: replicas at the floor (the fleet "
                         "scales fleet-min -> N -> fleet-min)")
    ap.add_argument("--fleet-max", type=int, default=4,
                    help="step pattern: autoscaler replica ceiling")
    ap.add_argument("--scale-up-load", type=float, default=0.7)
    ap.add_argument("--scale-down-load", type=float, default=0.25)
    ap.add_argument("--cooldown-s", type=float, default=2.0,
                    help="autoscaler cooldown after any scale event")
    ap.add_argument("--slo-ttft", type=float, default=15.0, metavar="S",
                    help="step pattern: p99 TTFT SLO target (burn "
                         "reported in the summary; also a scale-up "
                         "signal). Default is CPU-tiny-model scale: "
                         "the scaled fleet meets it, so the summary "
                         "shows BOUNDED burn; tighten it to watch "
                         "slo_burn-reason scale-ups fire")
    ap.add_argument("--slo-intertoken", type=float, default=2.0,
                    metavar="S")
    ap.add_argument("--tenant-mix", default=None, metavar="N:W,N:W",
                    help="step pattern: tenant traffic mix AND WFQ "
                         "weights (e.g. interactive:3,batch:1); per-"
                         "tenant p50/p99 reported")
    ap.add_argument("--tenant-quota", default=None, metavar="N:Q,N:Q",
                    help="per-tenant max in-flight admission quotas")
    args = ap.parse_args()
    if args.speculate and args.multi_token > 1:
        ap.error("--speculate and --multi-token are mutually exclusive "
                 "(both own the decode dispatch)")
    if args.grammar_compare and args.grammar is None:
        ap.error("--grammar-compare needs --grammar SCHEMA")
    if args.grammar is not None and args.multi_token > 1:
        ap.error("--grammar needs --multi-token 1 (use --speculate K for "
                 "multi-token grammar decoding)")
    if args.grammar is not None and args.url:
        ap.error("--grammar drives an in-process engine (no --url)")
    hard_max = args.max_len - args.max_new_tokens - _headroom(args)
    if args.shared_prefix and args.shared_prefix >= hard_max:
        ap.error(f"--shared-prefix {args.shared_prefix} leaves no room for "
                 f"a prompt body: max_len - max_new_tokens - (K-1) = "
                 f"{hard_max} tokens of budget")
    if args.spec_compare and not args.speculate:
        ap.error("--spec-compare needs --speculate K")
    if args.max_batch_size is None:
        args.max_batch_size = (4 if args.traffic_pattern == "step"
                               else DEFAULTS["max_batch_size"])
    if args.prefix_affinity_compare and not args.fleet:
        ap.error("--prefix-affinity-compare needs --fleet")
    if args.fleet:
        if args.url or args.traffic_pattern == "step":
            ap.error("--fleet drives its own fixed in-process fleet "
                     "(no --url / --traffic-pattern step)")
        if not args.shared_prefix:
            ap.error("--fleet needs --shared-prefix N "
                     "(per-tenant prefixes are what affinity routes on)")
        prompts = make_tenant_prompts(args)
        ref = affinity_reference(args, prompts)
        witha = run_affinity_fleet(args, prompts, ref, affinity=True)
        if args.prefix_affinity_compare:
            print("\n--- same traffic, prefix-blind dispatch ---")
            blind = run_affinity_fleet(args, prompts, ref, affinity=False)
            print(f"\nprefix affinity mean TTFT: "
                  f"{witha['ttft_mean'] * 1e3:.1f} ms vs "
                  f"{blind['ttft_mean'] * 1e3:.1f} ms blind -> "
                  f"{blind['ttft_mean'] / witha['ttft_mean']:.2f}x faster "
                  f"first token at {args.fleet_replicas} replicas "
                  f"(p99 {witha['ttft_p99'] * 1e3:.1f} vs "
                  f"{blind['ttft_p99'] * 1e3:.1f} ms; token divergence "
                  f"{witha['token_divergence']} + "
                  f"{blind['token_divergence']} of "
                  f"2x{len(prompts)} vs the single-replica reference)")
        return
    prompts = make_prompts(args)
    if args.traffic_pattern == "step":
        if args.url:
            ap.error("--traffic-pattern step drives its own in-process "
                     "fleet (no --url)")
        run_step_fleet(args, prompts)
        return
    if args.tenant_mix or args.tenant_quota:
        ap.error("--tenant-mix/--tenant-quota need --traffic-pattern step")
    if args.url:
        run_http(args, prompts)
        return
    if args.prefix_compare and not args.shared_prefix:
        ap.error("--prefix-compare needs --shared-prefix N")
    withc = run_inprocess(args, prompts)
    if args.prefix_compare:
        print("\n--- same traffic, prefix cache OFF ---")
        without = run_inprocess(args, prompts, prefix_cache=False)
        print(f"\nprefix cache mean TTFT: {withc['ttft_mean'] * 1e3:.1f} ms "
              f"vs {without['ttft_mean'] * 1e3:.1f} ms without "
              f"-> {without['ttft_mean'] / withc['ttft_mean']:.2f}x faster "
              f"first token on shared-prefix traffic")
    if args.spec_compare:
        print("\n--- same traffic, --speculate 0 ---")
        base = run_inprocess(args, prompts, speculate=0)
        print(f"\nspeculative decode: {withc['tokens_per_sec']:.0f} tok/s "
              f"(K={args.speculate}, acceptance "
              f"{withc.get('spec_acceptance')}) vs "
              f"{base['tokens_per_sec']:.0f} tok/s without "
              f"-> {withc['tokens_per_sec'] / base['tokens_per_sec']:.2f}x "
              "on this traffic (token-exact either way)")
    if args.grammar_compare:
        print("\n--- same traffic, unconstrained (plain engine) ---")
        free = run_inprocess(args, prompts, grammar=False)
        cost = (1.0 - withc["tokens_per_sec"] / free["tokens_per_sec"]) \
            * 100.0
        print(f"\ngrammar-constrained decode: "
              f"{withc['tokens_per_sec']:.0f} tok/s "
              f"({withc.get('grammar_conformant')}/"
              f"{withc.get('grammar_total')} conformant) vs "
              f"{free['tokens_per_sec']:.0f} tok/s unconstrained "
              f"-> {cost:.1f}% throughput cost"
              + (f"; spec acceptance {withc.get('spec_acceptance')} "
                 f"constrained vs {free.get('spec_acceptance')} free"
                 if args.speculate else ""))


if __name__ == "__main__":
    main()
