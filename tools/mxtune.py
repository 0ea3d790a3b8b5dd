#!/usr/bin/env python
"""mxtune: measurement-driven search over the knobs we used to hand-pick.

The search half of the autotuner (mxnet_tpu/tune): sweeps the knobs the
runtime hard-coded until this PR, scoring each trial by measurement
(plus the live ``mxnet_mfu`` gauge and the mxperf compute/bandwidth/
overhead regime verdict, which steers knob order) and judging winners
with bench_gate's noise-aware tolerance math so jitter cannot crown a
false winner. Winners persist in the content-addressed config cache
(``MXNET_TUNE_CACHE_DIR`` / ``--cache-dir``) under the same key
discipline as the AOT cache, and a tune manifest indexes them so they
ship with AOT manifests (``tools/aot_prewarm.py --verify`` checks
both).

Workloads::

    ladder     serve prompt-bucket geometry (min bucket x growth) over a
               seeded request mix — pure geometry arithmetic, no jax,
               fully deterministic given --seed
    decode     multi-token K on a tiny GPT through the real serving
               engine (the overhead-bound regime: fewer host round-trips
               per token) — measured wall time, CPU-visible win
    prefill    chunked-prefill tokens/tick x page size on the paged
               engine with long prompts — measured wall time
    gemv       the GLOBAL-site `gemv_max_m` routing threshold on
               quantized decode (CPU evidence; the TPU-representative
               sweep rides the bench round)
    synthetic  a deterministic analytic surface over real knob names
               (CI/self-test: exercises search + cache end to end in
               milliseconds)

Knob coverage note: the measured CPU workloads produce winners for the
serve-site knobs and `gemv_max_m`. `quant_block` is resolved by the
same layer (env-overridable, stored-config capable) but has no
CPU-measurable objective — the collective wire only exists across
chips.

Examples::

    JAX_PLATFORMS=cpu python tools/mxtune.py --workload ladder \
        --cache-dir /tmp/tuned
    JAX_PLATFORMS=cpu python tools/mxtune.py --workload decode \
        --cache-dir /tmp/tuned --repeats 3

Prints one JSON line; exits non-zero on failure. The trial SCHEDULE is
deterministic given --seed; ladder/synthetic results are fully
deterministic (their objectives are arithmetic).

Runs WITHOUT jax for --workload ladder/synthetic: jax is imported only
inside the measured-engine workloads.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

SITE_SERVE = "serve"

#: tiny-GPT dims shared by every engine workload (and the context the
#: committed winner is keyed on — a real engine over the same dims
#: key-matches it)
MODEL_DIMS = {"vocab": 128, "hidden": 32, "layers": 2, "heads": 2}


def _serve_context(args) -> dict:
    """The same dict tune.config.serve_context builds for a GPTModel of
    these dims — hand-assembled so the geometry workloads never import
    jax. Pinned against the real builder by tests/test_tune.py."""
    return {"model": "GPTModel", "hidden": args.hidden,
            "layers": args.layers, "heads": args.heads,
            "vocab": args.vocab, "max_batch_size": args.max_batch_size,
            "max_len": args.max_len}


# ---------------------------------------------------------------------------
# workload: ladder (geometry, deterministic, jax-free)
# ---------------------------------------------------------------------------

def _request_mix(seed: int, n: int, max_len: int, mix: str = "short"):
    """Seeded prompt-length mix. ``short`` = classification/embedding-
    style traffic dominated by 2-6 token prompts — the geometry the
    pow2-from-8 default ladder pads worst (every 3-token prompt pays 8).
    ``chat`` = a broader band where the default ladder is near-optimal
    (the tuner confirming a hand-picked value is also a result)."""
    import random as _random
    rng = _random.Random(seed)
    lengths = []
    for _ in range(n):
        r = rng.random()
        if mix == "short":
            if r < 0.80:
                lengths.append(rng.randint(2, 6))
            elif r < 0.95:
                lengths.append(rng.randint(8, max(9, max_len // 4)))
            else:
                lengths.append(rng.randint(max(2, max_len // 4), max_len))
        else:
            if r < 0.70:
                lengths.append(rng.randint(2, 16))
            elif r < 0.90:
                lengths.append(rng.randint(16, max(17, max_len // 4)))
            else:
                lengths.append(rng.randint(max(2, max_len // 4), max_len))
    return lengths


def ladder_workload(args):
    """(measure, space, defaults, context): prompt-ladder geometry.

    Objective (higher-better): useful prompt tokens / (padded prompt
    tokens + amortized compile cost), where every request pads to its
    ladder bucket and every bucket in the ladder costs
    ``--compile-cost-tokens`` token-equivalents to compile — the real
    tradeoff the ladder encodes (padding waste vs executable count).
    Pure arithmetic over mxnet_tpu/serve/bucketing, so the objective is
    exactly reproducible and the improvement is the tuner's own
    number."""
    from mxnet_tpu.serve.bucketing import bucket_for, bucket_ladder
    from mxnet_tpu.tune import Param

    lengths = _request_mix(args.seed, args.requests, args.max_len,
                           args.mix)
    useful = float(sum(lengths))
    compile_cost = float(args.compile_cost_tokens)

    def measure(cfg):
        lo, g = cfg["serve_min_prompt_bucket"], cfg["serve_bucket_growth"]
        padded = float(sum(bucket_for(p, lo, args.max_len, g)
                           for p in lengths))
        ladder = bucket_ladder(lo, args.max_len, g)
        value = useful / (padded + compile_cost * len(ladder))
        return {"values": [value], "regime": "geometry",
                "buckets": len(ladder),
                "padding_waste": round((padded - useful) / useful, 4)}

    space = {
        "serve_min_prompt_bucket": Param([1, 2, 4, 8, 16],
                                         tags=("geometry",)),
        "serve_bucket_growth": Param([2, 3, 4], tags=("geometry",)),
    }
    defaults = {"serve_min_prompt_bucket": 8, "serve_bucket_growth": 2}
    return measure, space, defaults, _serve_context(args), SITE_SERVE


# ---------------------------------------------------------------------------
# workloads: decode / prefill (measured through the real engine)
# ---------------------------------------------------------------------------

def _build_model(args):
    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTConfig, GPTModel
    mx.random.seed(args.seed)
    cfg = GPTConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                    num_layers=args.layers, num_heads=args.heads,
                    max_position_embeddings=2 * args.max_len, dropout=0.0)
    net = GPTModel(cfg)
    net.initialize()
    return net


def _engine_rounds(args, engine_kwargs, prompts, max_new):
    """Shared engine harness: one warm (untimed, compiles) round, then
    ``--repeats`` timed rounds. Returns per-round wall times plus the
    mxperf regime/mfu read off the live gauges after the last round."""
    import numpy as onp

    from mxnet_tpu import metrics
    from mxnet_tpu.observability import perf
    from mxnet_tpu.serve import InferenceEngine

    net = _build_model(args)
    # every knob pinned explicitly: a trial measures exactly its config,
    # never a previously committed tuned config the engine would
    # otherwise consult (explicit args outrank the tune layer)
    kwargs = {"min_prompt_bucket": 8, "multi_token": 1, "page_size": 16,
              "bucket_growth": 2, "prefill_chunk": 16, "speculate": 0}
    kwargs.update(engine_kwargs)
    eng = InferenceEngine(net, max_batch_size=args.max_batch_size,
                          max_len=args.max_len,
                          max_queue_depth=4 * len(prompts),
                          **kwargs).start()
    try:
        def round_():
            futs = [eng.submit(onp.asarray(p, onp.int32), max_new)
                    for p in prompts]
            for f in futs:
                r = f.result(300)
                if r.status != "ok":
                    raise RuntimeError(f"mxtune request failed: {r}")

        round_()                       # warm: compiles + first dispatches
        times = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            round_()
            times.append(time.perf_counter() - t0)
        roof = perf.summary().get("serve_decode") or {}
        mfu = metrics.get_sample_value("mxnet_mfu",
                                       {"path": "serve_decode"})
        return times, roof.get("regime"), mfu
    finally:
        eng.shutdown()


def decode_workload(args):
    """(measure, space, defaults, context): on-device multi-token K.

    The overhead-bound decode regime's launch-count knob: K tokens per
    decode dispatch = 1/K host round-trips per token, which is exactly
    what a CPU box can measure (the dispatch overhead IS the cost).
    Objective: generated tokens/s, median of --repeats rounds."""
    from mxnet_tpu import metrics
    from mxnet_tpu.observability import perf
    from mxnet_tpu.tune import Param

    metrics.enable()
    perf.enable()
    import random as _random
    rng = _random.Random(args.seed)
    B, P, NEW = args.max_batch_size, 8, 24
    prompts = [[rng.randrange(1, args.vocab) for _ in range(P)]
               for _ in range(B)]

    def measure(cfg):
        times, regime, mfu = _engine_rounds(
            args, {"multi_token": cfg["serve_multi_token"]}, prompts, NEW)
        return {"values": [B * NEW / t for t in times],
                "regime": regime or "overhead", "mfu_live": mfu,
                "times_s": [round(t, 4) for t in times]}

    space = {"serve_multi_token": Param([1, 2, 4, 8], tags=("overhead",))}
    defaults = {"serve_multi_token": 1}
    return measure, space, defaults, _serve_context(args), SITE_SERVE


def spec_workload(args):
    """(measure, space, defaults, context): self-speculative verify
    width × lookup window on structured SINGLE-STREAM traffic (one
    request in flight — the latency-bound regime speculation targets;
    a saturated batch would honestly crown speculate=0, which is the
    point of measuring). Output is token-exact at every config, so the
    objective is pure latency: generated tokens/s, median of
    --repeats rounds."""
    from mxnet_tpu import metrics
    from mxnet_tpu.observability import perf
    from mxnet_tpu.serve import InferenceEngine
    from mxnet_tpu.tune import Param

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from serve_loadgen import structured_prompts
    finally:
        sys.path.pop(0)

    metrics.enable()
    perf.enable()
    NEW = 32
    # THE shared structured-traffic definition (tools/serve_loadgen.py):
    # the tuner measures the same shape --spec-compare and
    # bench_spec_decode report on
    prompts = structured_prompts(6, args.vocab, seed=args.seed)

    def measure(cfg):
        net = _build_model(args)
        spec = cfg["serve_speculate"]
        # every knob pinned explicitly (incl. speculate=0): a previously
        # committed winner must never leak into a trial's measurement
        kw = {"min_prompt_bucket": 8, "multi_token": 1, "speculate": spec}
        if spec:
            kw["spec_lookup"] = cfg["serve_spec_lookup"]
        eng = InferenceEngine(net, max_batch_size=2,
                              max_len=args.max_len, **kw).start()
        try:
            ntok = None

            def round_():
                total = 0
                for p in prompts:         # ONE request in flight at a time
                    r = eng.generate(p, NEW)
                    if r.status != "ok":
                        raise RuntimeError(f"mxtune request failed: {r}")
                    total += len(r.generated_ids)
                return total

            ntok = round_()               # warm: compiles + first rounds
            times = []
            for _ in range(args.repeats):
                t0 = time.perf_counter()
                ntok = round_()
                times.append(time.perf_counter() - t0)
        finally:
            eng.shutdown()
        roof = perf.summary().get("serve_decode") or {}
        return {"values": [ntok / t for t in times],
                "regime": roof.get("regime") or "overhead",
                "times_s": [round(t, 4) for t in times]}

    space = {
        "serve_speculate": Param([0, 3, 4, 6, 8], tags=("overhead",)),
        "serve_spec_lookup": Param([2, 4, 8], tags=("overhead",)),
    }
    defaults = {"serve_speculate": 0, "serve_spec_lookup": 4}
    return measure, space, defaults, _serve_context(args), SITE_SERVE


def prefill_workload(args):
    """(measure, space, defaults, context): chunked-prefill geometry on
    the paged engine. Long prompts prefill one chunk per engine tick;
    small chunks pay one host tick per chunk (overhead), big chunks
    monopolize ticks (TTFT) — the tuner balances it on measured wall
    time of a long-prompt round. Objective: prompt+decode tokens/s."""
    from mxnet_tpu import metrics
    from mxnet_tpu.observability import perf
    from mxnet_tpu.tune import Param

    metrics.enable()
    perf.enable()
    import random as _random
    rng = _random.Random(args.seed)
    B, NEW = args.max_batch_size, 8
    P = args.max_len // 2
    prompts = [[rng.randrange(1, args.vocab) for _ in range(P)]
               for _ in range(B)]

    def measure(cfg):
        times, regime, mfu = _engine_rounds(
            args, {"page_size": cfg["serve_page_size"],
                   "prefill_chunk": cfg["serve_prefill_chunk"]},
            prompts, NEW)
        return {"values": [B * (P + NEW) / t for t in times],
                "regime": regime or "overhead", "mfu_live": mfu,
                "times_s": [round(t, 4) for t in times]}

    space = {
        "serve_prefill_chunk": Param([8, 16, 32, 64],
                                     tags=("overhead", "geometry")),
        "serve_page_size": Param([8, 16, 32], tags=("geometry",)),
    }
    defaults = {"serve_prefill_chunk": 16, "serve_page_size": 16}
    return measure, space, defaults, _serve_context(args), SITE_SERVE


# ---------------------------------------------------------------------------
# workload: gemv (global-site routing threshold)
# ---------------------------------------------------------------------------

def gemv_workload(args):
    """(measure, space, defaults, context, site): the GEMV-vs-MXU
    routing threshold (`gemv_max_m`, GLOBAL site) measured on quantized
    tiny-GPT decode through ``models.generate``.

    `gemv_max_m` is read at trace time inside the quantized forward, so
    each trial activates its candidate in-process, rebuilds the
    quantized model fresh (new traces), measures, and deactivates — the
    one knob with no explicit-argument channel to pin. On the CPU box
    the two routes are real but not TPU-representative (dequant-f32
    matmul vs int8 dot); treat CPU winners as evidence for the CPU
    serving path only — the TPU sweep rides the bench round, where the
    weight-stream-vs-MXU tradeoff this knob encodes actually exists."""
    import numpy as onp

    from mxnet_tpu import metrics, np, tune
    from mxnet_tpu.observability import perf
    from mxnet_tpu.tune import Param

    metrics.enable()
    perf.enable()
    B, P, NEW = args.max_batch_size, 8, 24

    def measure(cfg):
        import mxnet_tpu as mx
        from mxnet_tpu.contrib.quantization import quantize_net
        from mxnet_tpu.models import generate
        tune.activate(tune.GLOBAL_SITE,
                      {"gemv_max_m": cfg["gemv_max_m"]})
        try:
            net = _build_model(args)
            rng = onp.random.RandomState(args.seed)
            calib = [np.array(rng.randint(0, args.vocab, (B, P))
                              .astype(onp.int32))]
            quantize_net(net, calib_mode="naive", calib_data=calib)
            prompt = np.array(rng.randint(1, args.vocab, (B, P))
                              .astype(onp.int32))
            generate(net, prompt, NEW, use_cache=True).asnumpy()  # warm
            times = []
            for _ in range(args.repeats):
                fresh = np.array(rng.randint(1, args.vocab, (B, P))
                                 .astype(onp.int32))
                t0 = time.perf_counter()
                generate(net, fresh, NEW, use_cache=True).asnumpy()
                times.append(time.perf_counter() - t0)
            mx.waitall()
        finally:
            tune.deactivate_all()
        return {"values": [B * NEW / t for t in times],
                "regime": "bandwidth",
                "times_s": [round(t, 4) for t in times]}

    space = {"gemv_max_m": Param([0, 8, 64, 256], tags=("bandwidth",))}
    defaults = {"gemv_max_m": 64}
    # GLOBAL site is consulted context-FREE by the runtime
    # (ops/int8_gemv.gemv_max_m passes no context), so the winner must
    # commit under the empty context or it would never key-match
    return measure, space, defaults, {}, "global"


# ---------------------------------------------------------------------------
# workload: synthetic (deterministic analytic surface; CI/self-test)
# ---------------------------------------------------------------------------

def synthetic_workload(args):
    """A known-optimum analytic surface over real knob names (optimum:
    K=4, chunk=32): exercises search + judgment + persistence without
    measuring anything. Deterministic, jax-free, milliseconds."""
    from mxnet_tpu.tune import Param

    def measure(cfg):
        k, c = cfg["serve_multi_token"], cfg["serve_prefill_chunk"]
        value = 100.0 - 5.0 * (k - 4) ** 2 - 5.0 * ((c - 32) / 8.0) ** 2
        return {"values": [value], "regime": "overhead"}

    space = {
        "serve_multi_token": Param([1, 2, 4, 8], tags=("overhead",)),
        "serve_prefill_chunk": Param([8, 16, 32, 64],
                                     tags=("overhead", "geometry")),
    }
    defaults = {"serve_multi_token": 1, "serve_prefill_chunk": 16}
    return measure, space, defaults, {"workload": "synthetic"}, SITE_SERVE


WORKLOADS = {
    "ladder": ladder_workload,
    "decode": decode_workload,
    "spec": spec_workload,
    "prefill": prefill_workload,
    "gemv": gemv_workload,
    "synthetic": synthetic_workload,
}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run(args) -> dict:
    from mxnet_tpu import tune

    measure, space, defaults, context, site = WORKLOADS[args.workload](args)
    if args.workload in ("decode", "prefill", "gemv"):
        # one discarded measurement: the process's first engine pays
        # lazy imports + allocator/thread-pool warmup that would bias
        # the default trial low and fake an improvement for whatever
        # config happens to run later
        measure(dict(defaults))
    report = tune.search(
        measure, space, defaults, seed=args.seed, floor=args.floor,
        passes=args.passes, max_trials=args.max_trials,
        workload=args.workload,
        log=(None if args.quiet else
             lambda m: print(f"mxtune[{args.workload}] {m}",
                             file=sys.stderr)))

    out = {
        "ok": True,
        "workload": args.workload,
        "seed": args.seed,
        "trials": len(report["trials"]),
        "default": report["default_trial"],
        "best": report["best_trial"],
        "improvement": report["improvement"],
        "regime": report["best_trial"].get("regime"),
    }

    committed = None
    if args.cache_dir and report["best"] != report["default_trial"]["config"]:
        cache = tune.enable(args.cache_dir)
        key = tune.config_key(site, context)
        # one config per (site, context): a new workload's winners MERGE
        # into the existing entry (ladder's geometry + decode's K live
        # together), knob collisions going to the newest measurement
        prior = cache.get(key, site=site)
        knobs = {}
        history = []
        if prior is not None:
            prior_payload = prior.get("payload", {})
            knobs.update(prior_payload.get("knobs", {}))
            history = list(prior_payload.get("history", []))
            if prior_payload.get("objective"):
                history.append(prior_payload["objective"])
        knobs.update(report["best"])
        payload = {
            "knobs": knobs,
            "context": context,
            "objective": {
                "workload": args.workload,
                "seed": args.seed,
                "default": report["default_trial"]["objective"],
                "best": report["best_trial"]["objective"],
                "improvement": report["improvement"],
                "regime": report["best_trial"].get("regime"),
            },
            "history": history,
        }
        cache.put(key, site, payload,
                  label=f"mxtune:{args.workload}")
        manifest = args.manifest or os.path.join(
            args.cache_dir, f"{args.name}.tune-manifest.json")
        tune.write_tune_manifest(manifest, args.name, cache.touched)
        committed = {"key": key, "cache_dir": args.cache_dir,
                     "manifest": manifest}
        # drop memoized lookups so THIS process's engines see the winner
        tune.invalidate()
    out["committed"] = committed
    if args.trial_log:
        out["trial_log"] = report["trials"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="mxtune",
        description="autotuning search over kernel/quantization/serving "
                    "parameters (winners -> content-addressed config "
                    "cache)")
    ap.add_argument("--workload", choices=sorted(WORKLOADS),
                    default="ladder")
    ap.add_argument("--seed", type=int, default=0,
                    help="search-schedule seed (ladder/synthetic results "
                         "are fully deterministic given it)")
    ap.add_argument("--repeats", type=int, default=5,
                    help="timed rounds per measured trial (median "
                         "decides, spread feeds the tolerance: a win "
                         "smaller than the observed per-trial spread is "
                         "never crowned)")
    ap.add_argument("--floor", type=float, default=0.05,
                    help="minimum relative gain that can dethrone an "
                         "incumbent (bench_gate's floor)")
    ap.add_argument("--passes", type=int, default=2,
                    help="coordinate-descent passes over the knob set")
    ap.add_argument("--max-trials", type=int, default=None)
    ap.add_argument("--cache-dir",
                    default=os.environ.get("MXNET_TUNE_CACHE_DIR") or None,
                    help="persist the winner here (default "
                         "$MXNET_TUNE_CACHE_DIR; omit to dry-run)")
    ap.add_argument("--manifest", default=None,
                    help="tune-manifest path (default "
                         "<cache-dir>/<name>.tune-manifest.json)")
    ap.add_argument("--name", default="mxtune",
                    help="name recorded in the tune manifest")
    ap.add_argument("--requests", type=int, default=2048,
                    help="ladder workload: requests in the seeded mix")
    ap.add_argument("--mix", choices=("short", "chat"), default="short",
                    help="ladder workload: prompt-length distribution")
    ap.add_argument("--compile-cost-tokens", type=int, default=256,
                    help="ladder workload: token-equivalents one ladder "
                         "bucket costs to compile (amortization weight)")
    ap.add_argument("--vocab", type=int, default=MODEL_DIMS["vocab"])
    ap.add_argument("--hidden", type=int, default=MODEL_DIMS["hidden"])
    ap.add_argument("--layers", type=int, default=MODEL_DIMS["layers"])
    ap.add_argument("--heads", type=int, default=MODEL_DIMS["heads"])
    ap.add_argument("--max-batch-size", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--trial-log", action="store_true",
                    help="include every trial in the JSON line")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    try:
        out = run(args)
    except Exception as e:
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"}))
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
