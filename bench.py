"""Headline benchmark: ResNet-50 training throughput + MFU, single chip.

Baseline (BASELINE.md): reference ResNet-50 training fp32 bs=128 on 1x V100 =
363.69 img/s (reference docs perf.md:253). Same model family, same batch
size, measured on one TPU chip with the fully-fused TrainStep
(forward+backward+SGD in one XLA executable). Also measured: the bf16 AMP
variant (the native TPU dtype) and a BERT-base fine-tune step through the
same fused path — BASELINE.json config 3.

MFU = achieved FLOP/s ÷ chip peak, with achieved FLOPs taken from XLA's own
cost analysis of the compiled step executable (not a hand model count). Peak
is the bf16 MXU rate for the chip generation (v5e: 197 TFLOP/s); fp32 MFU is
reported against the same bf16 peak, which understates fp32 efficiency but
keeps one honest denominator.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
Extras include the cost-ledger roofline section ("perf": per-path MFU /
HBM-util / regime from observability/perf, live-gauge vs offline MFU
cross-check as gpt2_mfu_live) and the advisory vs_prev deltas; the
exit-status regression GATE over the committed BENCH_r*.json history is
tools/bench_gate.py.
"""
from __future__ import annotations

import json
import os
import time

import numpy as onp

BASELINE_IMGS_PER_SEC = 363.69  # reference fp32 bs=128 training (perf.md:253)
BATCH = 128
# 60 on-device steps per dispatch: the fixed per-dispatch cost is a small
# share of the window, so the number measures the chip
STEPS = 60

def _chip_peak() -> float:
    """Peak bf16 FLOP/s of the attached chip (the MFU denominator):
    delegates to observability.perf's single PEAKS table + chip
    detection, so the offline MFU here and the live mxnet_mfu gauge can
    never disagree on the denominator. Imported lazily: bench_gate.py
    imports THIS module on jax-free boxes for the metric table."""
    from mxnet_tpu.observability.perf import chip_peak_flops
    return chip_peak_flops()


def _trial_times(fn, trials: int = 5):
    """All trial wall times. Throughput is computed from the min, but every
    trial is recorded so cross-round deltas can be judged against the
    observed variance."""
    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        fn().item()
        times.append(time.perf_counter() - t0)
    return times


def _stats(times):
    s = sorted(times)
    return {"min_s": round(s[0], 4), "median_s": round(s[len(s) // 2], 4),
            "max_s": round(s[-1], 4), "trials": len(s),
            # per-trial record + relative spread: min-of-N rewards the
            # wider distribution, so duel verdicts are arbitrated on
            # medians with the spread in evidence
            "trials_s": [round(t, 4) for t in times],
            "spread_pct": round(100.0 * (s[-1] - s[0]) / s[0], 1)}


def _best_dt(fn, trials: int = 5):
    return min(_trial_times(fn, trials))


def _mfu(step, work_per_run: float, dt: float):
    """MFU from XLA's cost analysis of the compiled step; None if the
    backend can't report flops."""
    try:
        ca = step.cost_analysis()
        flops = float(ca.get("flops", 0.0)) if ca else 0.0
    except Exception:
        return None
    if flops <= 0:
        return None
    return round(flops * work_per_run / dt / _chip_peak(), 4)


def bench_resnet50(dtype: str):
    import mxnet_tpu as mx
    from mxnet_tpu import np, parallel, amp
    from mxnet_tpu.gluon.model_zoo import get_model
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    mx.random.seed(0)
    # NHWC = TPU-native layout (channels on the vector lanes): measured
    # ~1.5x over NCHW on the full train step (resnet.py docstring). The
    # model is numerically identical (tests/test_gluon.py NHWC parity).
    net = get_model("resnet50_v1", classes=1000, layout="NHWC")
    net.initialize(mx.init.Xavier())

    rng = onp.random.RandomState(0)
    images = np.array(rng.rand(BATCH, 224, 224, 3).astype(onp.float32))
    labels = np.array(rng.randint(0, 1000, BATCH).astype(onp.int32))
    if dtype == "bfloat16":
        # deferred params record the dtype; TrainStep's eval_shape pass
        # materializes them FLOP-free
        amp.convert_hybrid_block(net, "bfloat16")
        images = images.astype("bfloat16")

    step = parallel.TrainStep(
        net, SoftmaxCrossEntropyLoss(),
        mx.optimizer.SGD(learning_rate=0.05, momentum=0.9),
        example_inputs=[images])

    # run() loops STEPS updates on device in ONE executable: each dispatch
    # through PJRT costs host time, so python-loop timing measures
    # dispatch, not the chip (first call compiles = warmup)
    step.run(images, labels, steps=STEPS).item()
    times = _trial_times(lambda: step.run(images, labels, steps=STEPS))
    dt = min(times)

    imgs_per_sec = BATCH * STEPS / dt
    out = {"imgs_per_sec": round(imgs_per_sec, 2), "timing": _stats(times)}
    mfu = _mfu(step, STEPS, dt)
    if mfu is not None:
        out["mfu"] = mfu
    return out


def bench_bert_base_ft():
    """BERT-base fine-tune throughput via the fused TrainStep
    (BASELINE.json config 3 role): forward+backward+Adam in one XLA
    executable, STEPS iterations looped on device."""
    import mxnet_tpu as mx
    from mxnet_tpu import np, parallel
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.models.bert import BertConfig, BertForSequenceClassification

    from mxnet_tpu import amp
    B, T = 32, 128
    N = 20
    mx.random.seed(0)
    cfg = BertConfig()  # also feeds the analytic-FLOPs formula below
    net = BertForSequenceClassification(cfg, num_classes=2)
    net.initialize()
    # bf16 params/compute — the TPU-native fine-tune configuration (norm
    # params and statistics stay fp32 via the amp name filter)
    amp.convert_hybrid_block(net, "bfloat16")

    rng = onp.random.RandomState(0)
    ids = np.array(rng.randint(0, 30522, (B, T)).astype(onp.int32))
    types = np.array(onp.zeros((B, T), dtype=onp.int32))
    labels = np.array(rng.randint(0, 2, B).astype(onp.int32))
    step = parallel.TrainStep(
        net, SoftmaxCrossEntropyLoss(),
        mx.optimizer.Adam(learning_rate=2e-5),
        example_inputs=[ids, types])

    step.run((ids, types), labels, steps=N).item()
    times = _trial_times(lambda: step.run((ids, types), labels, steps=N))
    dt = min(times)
    out = {"examples_per_sec": round(B * N / dt, 2), "timing": _stats(times)}
    # Same analytic-FLOPs convention as GPT-2 (VERDICT r4 weak #5: one
    # convention everywhere — XLA cost analysis can't see Pallas custom
    # calls and would silently under-count). Per layer fwd: 24*B*T*D^2
    # matmuls (QKV+out+4D FFN) + 4*B*T^2*D bidirectional attention; pooler
    # + classifier are 2*B*D^2-ish (included); embeddings are gathers
    # (~0 FLOPs). Training = 3x forward.
    L, D = cfg.num_layers, cfg.hidden_size
    analytic = 3 * (L * (24 * B * T * D * D + 4 * B * T * T * D)
                    + 2 * B * D * D + 2 * B * D * 2)
    out["mfu"] = round(analytic * N / dt / _chip_peak(), 4)
    out["mfu_xla_visible"] = _mfu(step, N, dt)
    return out


def bench_gpt2_train():
    """GPT-2-small causal-LM pretraining step, bf16, fused TrainStep.run —
    the transformer (MXU-dominated) headline: tokens/s + MFU."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import np, parallel
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.models.gpt import GPTConfig, GPTModel

    B, T = 16, 1024
    N = 10
    mx.random.seed(0)
    cfg = GPTConfig(dropout=0.0, dtype=jnp.bfloat16)
    net = GPTModel(cfg)
    net.initialize()
    rng = onp.random.RandomState(0)
    ids = np.array(rng.randint(0, cfg.vocab_size, (B, T)).astype(onp.int32))
    labels = np.array(rng.randint(0, cfg.vocab_size, (B, T))
                      .astype(onp.int32))
    step = parallel.TrainStep(
        net, SoftmaxCrossEntropyLoss(),
        mx.optimizer.Adam(learning_rate=1e-4), example_inputs=[ids])
    step.run(ids, labels, steps=N).item()
    times = _trial_times(lambda: step.run(ids, labels, steps=N))
    dt = min(times)
    out = {"tokens_per_sec": round(B * T * N / dt, 1), "timing": _stats(times)}
    # Pallas flash-attention kernels are invisible to XLA cost analysis, so
    # use the analytic model-FLOPs count (PaLM-appendix convention, causal
    # attention at T^2/2 — the kernel skips masked blocks): fwd per layer =
    # 24*B*T*D^2 matmul + 2*B*T^2*D attention; + 2*B*T*D*V LM head; bwd = 2x.
    L, D, V = cfg.num_layers, cfg.hidden_size, cfg.vocab_size
    analytic = 3 * (L * (24 * B * T * D * D + 2 * B * T * T * D)
                    + 2 * B * T * D * V)
    out["mfu"] = round(analytic * N / dt / _chip_peak(), 4)
    out["mfu_xla_visible"] = _mfu(step, N, dt)
    return out


def _decode_trials(net, B, P, NEW, vocab, rng, trials=6, **gen_kw):
    """Shared decode-duel harness: compile once, time ``trials`` fresh-
    prompt runs, report min-based AND median-based tok/s (min-of-N
    rewards the wider spread, so int8-vs-bf16 verdicts are arbitrated on
    the medians) plus per-trial spread."""
    from mxnet_tpu import np
    from mxnet_tpu.models import generate

    prompt = np.array(rng.randint(0, vocab, (B, P)).astype(onp.int32))
    generate(net, prompt, NEW, use_cache=True, **gen_kw) \
        .wait_to_read()  # compile
    times = []
    for _ in range(trials):  # decode trials are short; 6 tightens min-of-N
        # fresh prompt per trial, so no trial repeats another's inputs
        fresh = np.array(rng.randint(0, vocab, (B, P)).astype(onp.int32))
        t0 = time.perf_counter()
        # .asnumpy() = real device->host fetch: the decode has run
        generate(net, fresh, NEW, use_cache=True, **gen_kw).asnumpy()
        times.append(time.perf_counter() - t0)
    stats = _stats(times)
    med = sorted(times)[len(times) // 2]
    return {"tokens_per_sec": round(B * NEW / min(times), 1),
            "tokens_per_sec_median": round(B * NEW / med, 1),
            "timing": stats}


def bench_gpt2_decode():
    """GPT-2-small autoregressive decode throughput (KV-cache incremental
    decode, whole loop one executable): generated tokens/s."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTConfig, GPTModel

    B, P, NEW = 8, 32, 128
    mx.random.seed(0)
    cfg = GPTConfig(dropout=0.0, dtype=jnp.bfloat16)
    net = GPTModel(cfg)
    net.initialize()
    rng = onp.random.RandomState(0)
    return _decode_trials(net, B, P, NEW, cfg.vocab_size, rng)


def bench_gpt2_decode_int8():
    """GPT-2-small decode with int8 QKV/FFN matmuls (quantize_net swaps the
    transformer Dense layers; per-out-channel scales, int8xint8->int32 on
    the MXU) — compare against the bf16 decode number."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import np
    from mxnet_tpu.contrib.quantization import quantize_net
    from mxnet_tpu.models.gpt import GPTConfig, GPTModel

    B, P, NEW = 8, 32, 128
    mx.random.seed(0)
    cfg = GPTConfig(dropout=0.0, dtype=jnp.bfloat16)
    net = GPTModel(cfg)
    net.initialize()
    rng = onp.random.RandomState(0)
    calib = [np.array(rng.randint(0, cfg.vocab_size, (B, P))
                      .astype(onp.int32)) for _ in range(2)]
    quantize_net(net, calib_mode="naive", calib_data=calib)
    return _decode_trials(net, B, P, NEW, cfg.vocab_size, rng)


def bench_gpt2_decode_fused(multi_token: int = 8):
    """GPT-2-small decode through the multi-token path: int8 weight-only
    quantization (GEMV kernels) + the on-device multi-token loop with
    fused LM-head sampling. Also records the static kernel launches per
    decode step via the trace-time tally."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import np
    from mxnet_tpu.contrib.quantization import quantize_net
    from mxnet_tpu.models.gpt import GPTConfig, GPTModel
    from mxnet_tpu.ops.int8_gemv import count_launches
    from mxnet_tpu.serve import InferenceEngine

    B, P, NEW = 8, 32, 128
    mx.random.seed(0)
    cfg = GPTConfig(dropout=0.0, dtype=jnp.bfloat16)
    net = GPTModel(cfg)
    net.initialize()
    rng = onp.random.RandomState(0)
    calib = [np.array(rng.randint(0, cfg.vocab_size, (B, P))
                      .astype(onp.int32)) for _ in range(2)]
    quantize_net(net, calib_mode="naive", calib_data=calib)
    out = _decode_trials(net, B, P, NEW, cfg.vocab_size, rng,
                         multi_token=multi_token)
    out["multi_token"] = multi_token
    # launches/step of one engine decode-step executable: trace-time
    # tally, no execution needed
    eng = InferenceEngine(net, max_batch_size=B, max_len=P + NEW + 8,
                          multi_token=multi_token)
    with count_launches() as tally:
        eng._build_step(B).lower(*eng._example_args("decode", B))
    out["launches_per_step"] = {k: int(v) for k, v in sorted(tally.items())}
    return out


def bench_int4_decode(multi_token: int = 8):
    """int4 weight-only decode: GPT-2-small with ``quantize_net(bits=4)``
    packed-nibble tables (packed stream -> in-VMEM block-scaled dequant
    -> bf16 MXU GEMV) through the multi-token path. The launch tally of
    one engine decode step rides along (the gemv_int4 kind)."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.contrib.quantization import quantize_net
    from mxnet_tpu.models.gpt import GPTConfig, GPTModel
    from mxnet_tpu.ops.int8_gemv import count_launches
    from mxnet_tpu.serve import InferenceEngine

    B, P, NEW = 8, 32, 128
    mx.random.seed(0)
    cfg = GPTConfig(dropout=0.0, dtype=jnp.bfloat16)
    net = GPTModel(cfg)
    net.initialize()
    rng = onp.random.RandomState(0)
    # weight-only int4: no activation scales anywhere on the decode path
    # (the packed lane dequantizes weights; activations stay bf16), so
    # skip the calibration forward entirely
    quantize_net(net, calib_mode="none", bits=4)
    out = _decode_trials(net, B, P, NEW, cfg.vocab_size, rng,
                         multi_token=multi_token)
    out["multi_token"] = multi_token
    eng = InferenceEngine(net, max_batch_size=B, max_len=P + NEW + 8,
                          multi_token=multi_token)
    with count_launches() as tally:
        eng._build_step(B).lower(*eng._example_args("decode", B))
    if "gemv_int4" not in tally:
        raise AssertionError(
            f"int4 step recorded no gemv_int4 launch kind ({dict(tally)})"
            " — the run would measure another path")
    out["launches_per_step"] = {k: int(v) for k, v in sorted(tally.items())}
    return out


def bench_spec_decode(speculate: int = 6, trials: int = 5):
    """Self-speculative decode duel (ISSUE 15): the loadgen harness's
    repetitive/structured traffic (templated JSON-ish prompts) served by
    a paged engine with ``speculate=K`` draft-verify rounds vs the
    identical engine at ``speculate=0`` — token-exact by construction
    (the verify recomputes exactly the non-speculative stream), so the
    duel measures pure latency. Single interactive stream: speculation
    targets the latency-bound low-concurrency regime — a saturated batch
    already amortizes dispatch overhead across slots (see README).
    Median-of-N with per-trial spread, bench_gate-judgeable."""
    import sys

    from mxnet_tpu.serve import InferenceEngine

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        from serve_loadgen import default_model, structured_prompts
    finally:
        sys.path.pop(0)

    NEW = 80
    # clipped so prompt + NEW + the K-1 speculative headroom fits the
    # engine's max_len for every K in the duel
    prompts = structured_prompts(8, 256, seed=0,
                                 max_tokens=128 - NEW - 8)
    net = default_model()

    def sweep(spec):
        # explicit speculate (even 0): a tuned serve_speculate winner
        # must not silently re-enable speculation in the baseline sweep
        eng = InferenceEngine(net, max_batch_size=2, max_len=128,
                              page_size=16,
                              speculate=spec).start()
        eng.warmup()
        times, outs = [], None
        try:
            for t in range(trials + 1):       # first sweep = warm discard
                t0 = time.perf_counter()
                # ONE request in flight at a time: the interactive
                # latency-bound stream speculation targets (a saturated
                # batch amortizes dispatch overhead across slots and
                # pays the full T-wide verify compute instead)
                res = [eng.generate(p, NEW, seed=0) for p in prompts]
                dt = time.perf_counter() - t0
                assert all(r.status == "ok" for r in res)
                outs = sorted(tuple(r.generated_ids) for r in res)
                if t:
                    times.append(dt)
            ntok = sum(len(o) for o in outs)      # tokens per sweep
            st = eng.stats()
        finally:
            eng.shutdown()
        med = sorted(times)[len(times) // 2]
        return {"tokens_per_sec_median": round(ntok / med, 1),
                "timing": _stats(times), "outs": outs,
                "spec": st.get("spec")}

    spec = sweep(speculate)
    base = sweep(0)
    if spec["outs"] != base["outs"]:
        raise AssertionError("speculative output diverged from the "
                             "non-speculative stream (token-exactness "
                             "contract broken)")
    acc = (spec["spec"] or {}).get("acceptance_rate")
    return {
        "speculate": speculate,
        "tokens_per_sec_median": spec["tokens_per_sec_median"],
        "baseline_tokens_per_sec_median": base["tokens_per_sec_median"],
        "speedup": round(spec["tokens_per_sec_median"]
                         / base["tokens_per_sec_median"], 3),
        "acceptance_rate": acc,
        "timing": spec["timing"],
        "baseline_timing": base["timing"],
    }


def bench_grammar_decode(speculate: int = 4, trials: int = 5):
    """Grammar-constrained decode duel (ISSUE 18): the loadgen's
    structured traffic served by a paged speculative engine with the
    token-mask automaton in the sampling path vs the identical plain
    engine — two measurements in one bench.

    The COST half uses the pass-through grammar ``.*`` (every byte token
    legal in every state): the constrained stream must match the free
    stream token for token, so the duel isolates the mask machinery
    (table gathers + masked sampling + host automaton ledger) from any
    traffic difference, and ``grammar_vs_free_cost_pct`` is the <10%
    acceptance number. Spec acceptance is asserted >= the unconstrained
    baseline (a pre-constrained draft can only gain accepts).

    The CONFORMANCE half serves a real JSON schema and asserts EVERY
    completion replays through the automaton (``matches``) — the
    by-construction guarantee, checked from the outside before any
    number is reported."""
    import sys

    from mxnet_tpu.serve import InferenceEngine, compile_grammar

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        from serve_loadgen import default_model, structured_prompts
    finally:
        sys.path.pop(0)

    NEW = 64
    EOS = 0
    prompts = structured_prompts(8, 256, seed=0,
                                 max_tokens=128 - NEW - 8)
    net = default_model()

    def sweep(grammar):
        eng = InferenceEngine(net, max_batch_size=2, max_len=128,
                              page_size=16,
                              speculate=speculate,
                              grammar=grammar is not None).start()
        eng.warmup()
        extra = {"eos_token_id": EOS}
        if grammar is not None:
            extra["grammar"] = grammar
        times, outs = [], None
        try:
            for t in range(trials + 1):       # first sweep = warm discard
                t0 = time.perf_counter()
                res = [eng.generate(p, NEW, seed=0, **extra)
                       for p in prompts]
                dt = time.perf_counter() - t0
                assert all(r.status == "ok" for r in res)
                outs = [tuple(r.generated_ids) for r in res]
                if t:
                    times.append(dt)
            ntok = sum(len(o) for o in outs)
            st = eng.stats()
        finally:
            eng.shutdown()
        med = sorted(times)[len(times) // 2]
        return {"tokens_per_sec_median": round(ntok / med, 1),
                "timing": _stats(times), "outs": outs,
                "spec": st.get("spec")}

    free = sweep(None)
    cons = sweep(".*")
    if cons["outs"] != free["outs"]:
        raise AssertionError(
            "the pass-through grammar changed the token stream — the "
            "mask is not identity on an all-permissive automaton; no "
            "cost number reported")
    acc_free = (free["spec"] or {}).get("acceptance_rate") or 0.0
    acc_cons = (cons["spec"] or {}).get("acceptance_rate") or 0.0
    if acc_cons + 1e-9 < acc_free:
        raise AssertionError(
            f"constrained spec acceptance {acc_cons} dropped below the "
            f"unconstrained baseline {acc_free} on conformant traffic")

    # conformance half: a real schema, every completion replayed through
    # the automaton before anything is reported. BOUNDED productions
    # only (booleans/enums): an unbounded integer lets a greedy model
    # emit digits past the token budget — legal at every step but
    # truncated, which the replay would flag (see README)
    schema = {"type": "object", "properties": {
        "ok": {"type": "boolean"},
        "mode": {"enum": ["fast", "safe", "off"]},
        "n": {"enum": [0, 1, 2]}}}
    g = compile_grammar(schema, 256)
    eng = InferenceEngine(net, max_batch_size=2, max_len=128,
                          page_size=16, speculate=speculate,
                          grammar=True).start()
    eng.warmup()
    try:
        bad = []
        for i, p in enumerate(prompts):
            res = eng.generate(p, NEW, seed=i, grammar=g,
                               eos_token_id=EOS)
            assert res.status == "ok", res.status
            if not g.matches(res.generated_ids, eos_token_id=EOS):
                bad.append(i)
    finally:
        eng.shutdown()
    if bad:
        raise AssertionError(
            f"{len(bad)} of {len(prompts)} schema-constrained "
            f"completions failed conformance replay ({bad}) — the "
            "by-construction guarantee is broken")

    cost = (1.0 - cons["tokens_per_sec_median"]
            / free["tokens_per_sec_median"]) * 100.0
    return {
        "speculate": speculate,
        "tokens_per_sec_median": cons["tokens_per_sec_median"],
        "free_tokens_per_sec_median": free["tokens_per_sec_median"],
        "cost_pct": round(cost, 2),
        "acceptance_rate": acc_cons,
        "free_acceptance_rate": acc_free,
        "conformant": len(prompts),
        "timing": cons["timing"],
        "free_timing": free["timing"],
    }


def bench_prefix_affinity(replicas: int = 4):
    """Cache-aware fleet duel (ISSUE 17): 16 tenants' shared-prefix
    traffic (240-token per-tenant system prompts, shuffled job queue)
    against ``replicas`` paged engine replicas behind the router, with
    prefix-affinity dispatch ON vs prefix-BLIND (least-loaded) dispatch
    on the identical request set. Single-token probes: TTFT is the
    whole measurement, and token 1 depends on the full prefix KV, so
    the bitwise divergence check still proves the cached pages are the
    right pages. Both passes are asserted ZERO-divergent against a
    single-replica sequential reference before any speedup is reported
    — the duel can never trade tokens for latency. The acceptance
    number is mean-TTFT blind/affinity >= 2x at 4 replicas."""
    import argparse
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        import serve_loadgen as lg
    finally:
        sys.path.pop(0)

    args = argparse.Namespace(
        seed=0, vocab=256, hidden=256, layers=4, heads=8,
        max_len=256, max_new_tokens=1, temperature=0.0, top_k=0,
        top_p=1.0, concurrency=16, requests=5, shared_prefix=240,
        prompt_min=1, prompt_max=8, multi_token=1, speculate=0,
        spec_lookup=None, max_batch_size=16, page_size=16,
        num_pages=320, prefill_chunk=None, no_prefix_cache=False,
        fleet_replicas=replicas, fleet_workers=2)
    prompts = lg.make_tenant_prompts(args)
    ref = lg.affinity_reference(args, prompts)
    aff = lg.run_affinity_fleet(args, prompts, ref, affinity=True)
    blind = lg.run_affinity_fleet(args, prompts, ref, affinity=False)
    if aff["token_divergence"] or blind["token_divergence"]:
        raise AssertionError(
            "fleet dispatch diverged from the single-replica reference "
            f"(affinity {aff['token_divergence']}, blind "
            f"{blind['token_divergence']} of {len(prompts)}) — the "
            "token-exactness contract is broken; no speedup reported")
    return {
        "replicas": replicas,
        "speedup": round(blind["ttft_mean"] / aff["ttft_mean"], 3),
        "ttft_mean_ms": round(aff["ttft_mean"] * 1e3, 2),
        "blind_ttft_mean_ms": round(blind["ttft_mean"] * 1e3, 2),
        "outcomes": aff["affinity_outcomes"],
        "hit_tokens": aff["affinity_hit_tokens"],
        "timing": _stats(aff["ttfts"]),
        "blind_timing": _stats(blind["ttfts"]),
    }


def bench_aot_warmstart():
    """Cold- vs warm-start compile time through the persistent AOT cache
    (mxnet_tpu/aot): time the serving engine's full bucket-ladder warmup
    against an empty cache dir (every executable XLA-compiles) and again
    from fresh engines over the now-populated dir (every executable
    deserializes). The speedup is the restart-cost number the trajectory
    must not regress."""
    import shutil
    import sys
    import tempfile

    from mxnet_tpu import aot
    from mxnet_tpu.serve import InferenceEngine

    # the SHARED loadgen-model definition (tools/serve_loadgen.py), so
    # this measures exactly the harness the README numbers quote
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        from serve_loadgen import DEFAULTS, default_model
    finally:
        sys.path.pop(0)

    def build_engine():
        return InferenceEngine(default_model(),
                               max_batch_size=DEFAULTS["max_batch_size"],
                               max_len=DEFAULTS["max_len"])

    tmpdir = tempfile.mkdtemp(prefix="mxnet-aot-bench-")
    prev_cache = aot.get_cache()
    try:
        cache = aot.enable(tmpdir)
        cold = build_engine().warmup().last_warmup_s
        warm_times = [build_engine().warmup().last_warmup_s
                      for _ in range(2)]
        warm = min(warm_times)
        return {
            "cold_warmup_s": round(cold, 3),
            "warm_warmup_s": round(warm, 3),
            "speedup": round(cold / warm, 2),
            "cache_bytes": cache.total_bytes(),
            "timing": _stats(warm_times),
        }
    finally:
        if prev_cache is not None:
            aot.enable(prev_cache.path, max_bytes=prev_cache.max_bytes)
        else:
            aot.disable()
        shutil.rmtree(tmpdir, ignore_errors=True)


def bench_zero_overlap(steps: int = 24):
    """ZeRO-2 param all-gather vs next-step forward overlap — the
    hardware-side verification the ROADMAP has carried since PR 8.
    Runs the fused ``TrainStep(zero=2, block_every=4)`` over the full
    dp mesh with WINDOWED dispatch (``step()``: no per-step host sync),
    then reads ``mxnet_step_overlap_fraction{path=train_step}`` — the
    PR-9 step-timeline gauge, 1 − host-blocked/wall. The all-gather
    window lives inside the dispatch phase, so a fraction near 1.0
    means the collective pipelines behind compute instead of
    serializing the step loop; on real ICI this is the number that
    decides whether ZeRO's wire traffic is free."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import metrics as _metrics, np, parallel
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.parallel import P

    dp = len(jax.devices())
    mesh = parallel.make_mesh({"dp": dp})
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(1024, activation="relu"),
            nn.Dense(1024, activation="relu"), nn.Dense(16))
    net.initialize(mx.init.Xavier())
    rng = onp.random.RandomState(0)
    X = np.array(rng.randn(8 * dp, 256).astype("float32"))
    Y = np.array(rng.randint(0, 16, 8 * dp).astype("int32"))
    step = parallel.TrainStep(
        net, SoftmaxCrossEntropyLoss(),
        mx.optimizer.Adam(learning_rate=1e-3), example_inputs=[X],
        mesh=mesh, data_spec=P("dp"), label_spec=P("dp"), zero=2,
        block_every=4)
    step(X, Y).item()   # compile; the gauge needs a finished window
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        step.step(X, Y)
        times.append(time.perf_counter() - t0)
    step.drain()
    overlap = _metrics.get_sample_value("mxnet_step_overlap_fraction",
                                        {"path": "train_step"})
    return {"overlap_fraction": None if overlap is None
            else round(float(overlap), 4),
            "dp": dp, "timing": _stats(times)}


def bench_health_overhead(window: int = 4, trials: int = 6):
    """mxhealth duel (ISSUE 16): the fused health vector's step cost —
    ``TrainStep(health=True)`` vs an identical health-off step on the
    same net and data, both WINDOWED (``step()`` + ``drain()``, no
    per-step host sync: the health read rides the lazy-loss deferred
    schedule, so any overhead measured here is the fused on-device
    reductions themselves, never a sync). Interleaved median-of-N
    windows per the duel convention; acceptance is <= 1% on-device
    (CPU numbers are advisory — a tiny step is dispatch-dominated
    there, which inflates the relative cost of anything)."""
    import mxnet_tpu as mx
    from mxnet_tpu import np, parallel
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    rng = onp.random.RandomState(0)
    X = np.array(rng.randn(64, 256).astype("float32"))
    Y = np.array(rng.randint(0, 16, 64).astype("int32"))

    def build(health):
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(512, activation="relu"),
                nn.Dense(512, activation="relu"), nn.Dense(16))
        net.initialize(mx.init.Xavier())
        return parallel.TrainStep(
            net, SoftmaxCrossEntropyLoss(),
            mx.optimizer.Adam(learning_rate=1e-3), example_inputs=[X],
            block_every=window, health=health)

    off, on = build(False), build(True)

    def window_time(step):
        t0 = time.perf_counter()
        for _ in range(window):
            step.step(X, Y)
        step.drain()
        return time.perf_counter() - t0

    for step in (off, on):
        step(X, Y).item()   # compile
        window_time(step)   # settle caches, unmeasured
    toff, ton = [], []
    for _ in range(trials):
        toff.append(window_time(off))
        ton.append(window_time(on))
    soff, son = _stats(toff), _stats(ton)
    overhead = ((son["median_s"] - soff["median_s"])
                / soff["median_s"] * 100)
    return {"overhead_pct": round(overhead, 2), "timing": son,
            "off_timing": soff, "steps_per_window": window,
            "trials": trials}


def bench_tuned_vs_default():
    """mxtune duel (ISSUE 14): the autotuner's decode winner vs the
    hand-picked defaults on the tuner's own objective (engine decode
    tokens/s on the shared tiny-GPT workload of tools/mxtune.py).
    Runs the real search (noise-aware judge, regime-steered order),
    then re-measures BOTH configs fresh for the duel so the recorded
    speedup is never the search's own selection bias — median-of-N with
    per-trial spread per the PR-6 duel convention. On the CPU box this
    exercises the overhead-dominated knobs (multi-token K); the TPU-side
    kernel-shape wins ride the next bench round behind bench_gate."""
    import argparse
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        import mxtune
    finally:
        sys.path.pop(0)
    from mxnet_tpu import tune

    args = argparse.Namespace(seed=0, repeats=5,
                              vocab=mxtune.MODEL_DIMS["vocab"],
                              hidden=mxtune.MODEL_DIMS["hidden"],
                              layers=mxtune.MODEL_DIMS["layers"],
                              heads=mxtune.MODEL_DIMS["heads"],
                              max_batch_size=4, max_len=96)
    measure, space, defaults, _ctx, _site = mxtune.decode_workload(args)
    measure(dict(defaults))        # discarded process warmup
    report = tune.search(measure, space, defaults, seed=args.seed,
                         workload="decode")
    best = report["best"]
    dres = measure(dict(defaults))
    tres = measure(dict(best))
    # the tuner's own median convention — the duel must judge by the
    # same statistic that crowned the winner
    from mxnet_tpu.tune.search import median as _tmedian
    dmed = _tmedian(dres["values"])
    tmed = _tmedian(tres["values"])
    return {
        "tuned_knobs": best,
        "default_tokens_per_sec_median": round(dmed, 1),
        "tuned_tokens_per_sec_median": round(tmed, 1),
        "speedup": round(tmed / dmed, 3) if dmed > 0 else None,
        "search_improvement": report["improvement"],
        "search_trials": len(report["trials"]),
        "regime": tres.get("regime"),
        "timing": _stats(tres["times_s"]),
        "default_timing": _stats(dres["times_s"]),
    }


def bench_input_pipeline():
    """Input-bound training scenario (ISSUE 4 acceptance): a throttled
    synthetic loader — per-batch host delay calibrated to one device step,
    the balanced producer/consumer case — feeds the fused TrainStep with
    and without the async pipeline (DevicePrefetcher staging batch k+1 on
    a background thread + the bounded in-flight window replacing the
    per-step ``float(loss)`` sync). Ideal overlap is 2.0x; the recorded
    speedup is how much of it the pipeline actually delivers."""
    import mxnet_tpu as mx
    from mxnet_tpu import np, parallel
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.pipeline import DevicePrefetcher

    B, D, N = 64, 256, 30
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(512, activation="relu"),
            nn.Dense(512, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    rng = onp.random.RandomState(0)
    Xs = rng.rand(N, B, D).astype(onp.float32)
    Ys = rng.randint(0, 10, (N, B)).astype(onp.int32)
    step = parallel.TrainStep(net, SoftmaxCrossEntropyLoss(),
                              mx.optimizer.SGD(learning_rate=0.01),
                              example_inputs=[np.array(Xs[0])],
                              block_every=4)
    # calibrate the device step time (first call compiles = warmup)
    step(np.array(Xs[0]), np.array(Ys[0])).item()
    t0 = time.perf_counter()
    for i in range(5):
        step(np.array(Xs[i]), np.array(Ys[i])).item()
    delay = max((time.perf_counter() - t0) / 5, 0.002)

    def loader():
        for i in range(N):
            time.sleep(delay)            # the throttled host producer
            yield Xs[i], Ys[i]

    def run(prefetch: bool) -> float:
        t0 = time.perf_counter()
        if prefetch:
            for x, y in DevicePrefetcher(loader(), depth=2):
                step.step(x, y)
            step.drain()
        else:
            for x, y in loader():
                step(x, y).item()        # the per-step sync being removed
        return time.perf_counter() - t0

    # interleave so shared-box contention hits both modes alike
    base, pre = [], []
    for _ in range(3):
        base.append(run(False))
        pre.append(run(True))
    return {
        "no_prefetch_examples_per_sec": round(N * B / min(base), 1),
        "prefetch_examples_per_sec": round(N * B / min(pre), 1),
        "speedup": round(min(base) / min(pre), 2),
        "producer_delay_s": round(delay, 5),
        "timing": _stats(pre),
    }


# metric key -> timing-stats key recorded alongside it (spread source for
# the regression tripwire)
_METRIC_TIMING = {
    "value": "timing",
    "mfu": "timing",
    "bf16_imgs_per_sec": "bf16_timing",
    "bf16_mfu": "bf16_timing",
    "bert_base_ft_examples_per_sec": "bert_timing",
    "bert_mfu": "bert_timing",
    "gpt2_train_tokens_per_sec": "gpt2_timing",
    "gpt2_mfu": "gpt2_timing",
    "gpt2_decode_tokens_per_sec": "gpt2_decode_timing",
    "gpt2_decode_int8_tokens_per_sec": "gpt2_decode_int8_timing",
    # median-arbitrated duel metrics (min-of-N rewards the wider spread)
    "gpt2_decode_tokens_per_sec_median": "gpt2_decode_timing",
    "gpt2_decode_int8_tokens_per_sec_median": "gpt2_decode_int8_timing",
    "gpt2_decode_fused_tokens_per_sec": "gpt2_decode_fused_timing",
    "gpt2_decode_fused_tokens_per_sec_median": "gpt2_decode_fused_timing",
    # warm-start restore speedup (higher is better; spread from the warm
    # warmup trials)
    "aot_warmstart_speedup": "aot_timing",
    # input-bound overlap speedup (higher is better; 2.0 is the ideal for
    # the balanced producer/consumer calibration)
    "pipeline_input_bound_speedup": "pipeline_timing",
    # mxtune duel (bench_tuned_vs_default): the tuner's decode winner vs
    # the hand-picked defaults, both re-measured fresh after the search;
    # spread for both keys comes from the tuned side's trials
    "tuned_decode_tokens_per_sec_median": "tuned_decode_timing",
    "tuned_vs_default_speedup": "tuned_decode_timing",
    # int4 weight-only decode (bench_int4_decode): packed nibble stream
    "int4_decode_tokens_per_sec": "int4_decode_timing",
    "int4_decode_tokens_per_sec_median": "int4_decode_timing",
    # self-speculative decode duel (bench_spec_decode): structured
    # single-stream traffic, token-exact spec vs non-spec engines
    "spec_decode_tokens_per_sec_median": "spec_decode_timing",
    "spec_vs_baseline_speedup": "spec_decode_timing",
    # grammar-constrained decode (bench_grammar_decode): pass-through
    # automaton vs plain engine on identical token streams; the
    # lower-is-better cost_pct companion is deliberately NOT here
    "grammar_tokens_per_sec_median": "grammar_decode_timing",
}


def _load_prev_round():
    """Latest committed BENCH_r*.json; returns ``(round_number,
    parsed_metrics)`` or ``(None, None)``.

    BENCH_r*.json driver schema (what the CI driver archives per round,
    and what this function + tools/bench_gate.py consume)::

        {
          "n":      <round number>,
          "cmd":    <shell command the driver ran>,
          "rc":     <its exit status>,
          "tail":   <last stdout/stderr text, incl. the bench line>,
          "parsed": <THE JSON LINE main() printed, parsed>   # <- consumed
        }

    Only ``parsed`` is read (a bare parsed line with no wrapper is
    accepted for hand-built files); every metric key inside it follows
    the ``_METRIC_TIMING`` table — a throughput/MFU scalar plus the
    ``_stats`` timing dict (``min_s``/``median_s``/``max_s``/
    ``trials_s``/``spread_pct``) recorded next to it, which is what
    makes cross-round deltas judgeable against observed noise. Missing
    files, malformed JSON and a non-dict ``parsed`` all read as "no
    previous round".

    ``zero_overlap_fraction`` (bench_zero_overlap) is the exception to
    the table: a 0..1 gauge (the ZeRO all-gather-vs-forward overlap
    read off ``mxnet_step_overlap_fraction``), recorded with its dp
    width + step timing but deliberately NOT in ``_METRIC_TIMING`` —
    it is evidence for the roofline ledger, not a throughput to gate
    on (the gate's spread math assumes higher-is-better scalars with
    per-trial timings).

    The mxtune duel (bench_tuned_vs_default) records
    ``tuned_decode_tokens_per_sec_median`` + ``tuned_vs_default_speedup``
    (both gate-tracked against ``tuned_decode_timing``'s spread) plus
    the untracked evidence keys ``tuned_decode_knobs`` (the winning
    config), ``tuned_decode_default_tokens_per_sec_median`` and
    ``tuned_decode_default_timing`` — the duel re-measures BOTH configs
    fresh after the search, so the committed speedup is measurement,
    not selection bias.

    The int4 weight-only run (bench_int4_decode) records
    ``int4_decode_tokens_per_sec``/``int4_decode_tokens_per_sec_median``
    (gate-tracked against ``int4_decode_timing``'s spread) plus the
    untracked evidence keys ``int4_decode_multi_token`` and
    ``int4_decode_launches_per_step`` (must contain the ``gemv_int4``
    launch kind or the run raises).

    The self-speculative duel (bench_spec_decode) records
    ``spec_decode_tokens_per_sec_median`` + ``spec_vs_baseline_speedup``
    (gate-tracked against ``spec_decode_timing``'s spread) plus the
    untracked evidence keys ``spec_decode_acceptance_rate`` (draft
    acceptance on the structured traffic — a 0..1 gauge, workload
    evidence like ``zero_overlap_fraction``, not a throughput),
    ``spec_decode_baseline_tokens_per_sec_median`` and
    ``spec_decode_baseline_timing``; both engines serve the IDENTICAL
    request set and the duel asserts token-exact output before
    reporting, so the speedup can never trade content for speed.

    The grammar duel (bench_grammar_decode) records
    ``grammar_tokens_per_sec_median`` (gate-tracked against
    ``grammar_decode_timing``'s spread) plus the untracked evidence keys
    ``grammar_vs_free_cost_pct`` (the <10% constrained-decode cost —
    lower-is-better, so like ``health_overhead_pct`` it stays out of
    ``_METRIC_TIMING``), ``grammar_free_tokens_per_sec_median``/
    ``grammar_free_timing``, ``grammar_acceptance_rate``/
    ``grammar_free_acceptance_rate`` (0..1 gauges) and
    ``grammar_conformant``. The duel's hard gates are its own asserts:
    the pass-through automaton must leave the token stream bitwise
    unchanged, constrained spec acceptance must not drop below the free
    baseline, and every schema-constrained completion must replay
    through the automaton — any failure raises and the round records no
    grammar numbers at all.

    The cache-aware fleet duel (bench_prefix_affinity) records
    ``prefix_affinity_ttft_speedup`` — mean TTFT of prefix-BLIND
    dispatch over prefix-affinity dispatch on identical 16-tenant
    shared-prefix traffic at 4 replicas (>= 2x is ISSUE 17's
    acceptance) — with the evidence keys
    ``prefix_affinity_ttft_mean_ms``/``prefix_affinity_blind_ttft_mean_
    ms``, ``prefix_affinity_outcomes`` (hit/load_bounded/cold dispatch
    counts) and ``prefix_affinity_timing``/``prefix_affinity_blind_
    timing``. The timing dicts hold the PER-REQUEST TTFT distribution
    of each pass (one duel per round — rerunning the whole fleet N
    times is not worth the wall clock), whose cold-vs-hit bimodality
    makes ``spread_pct`` huge, so like ``health_overhead_pct`` the
    speedup is deliberately NOT in ``_METRIC_TIMING`` — the hard gate
    is the duel's own ZERO-token-divergence assert (it raises, and the
    round records no speedup at all, if any fleet token differs from
    the single-replica reference).

    The mxhealth duel (bench_health_overhead) records
    ``health_overhead_pct`` — the fused health vector's windowed step
    cost, ``(median_on - median_off) / median_off * 100`` — with the
    evidence keys ``health_on_timing``/``health_off_timing``. Like
    ``zero_overlap_fraction`` it is deliberately NOT in
    ``_METRIC_TIMING``: it is lower-is-better and the gate's spread
    math assumes higher-is-better throughputs (the <= 1% on-device
    acceptance is ISSUE 16's, judged per round against the recorded
    spreads)."""
    import glob
    import re
    best = None
    here = os.path.dirname(os.path.abspath(__file__))
    for f in glob.glob(os.path.join(here, "BENCH_r*.json")):
        m = re.search(r"BENCH_r0*(\d+)\.json$", f)
        if m and (best is None or int(m.group(1)) > best[0]):
            best = (int(m.group(1)), f)
    if best is None:
        return None, None
    try:
        with open(best[1]) as fh:
            doc = json.load(fh)
        parsed = doc.get("parsed", doc)
        return (best[0], parsed) if isinstance(parsed, dict) else (None, None)
    except Exception:
        return None, None


def _rel_spread(stats) -> float:
    """Per-trial relative spread ``(max - min) / min`` from a timing-stats
    dict; 0.0 for anything malformed (missing keys, a non-dict, a zero/
    negative min) — absent spread means "assume only the floor", never a
    crash in the compare path."""
    try:
        lo, hi = stats["min_s"], stats["max_s"]
        if not isinstance(lo, (int, float)) or not isinstance(
                hi, (int, float)) or lo <= 0:
            return 0.0
        return (hi - lo) / lo
    except Exception:
        return 0.0


def compare_vs_prev(line: dict, prev: dict, floor: float = 0.05):
    """Regression tripwire: per-metric relative deltas vs the previous
    round, flagging drops larger than the recorded per-trial spread of
    EITHER round (a drop inside the observed spread is noise, beyond it is
    a regression).
    ``floor`` is the minimum spread assumed when none was recorded.

    Pure and total: a missing/non-dict ``prev``, metrics new in this
    round (no prev value), metrics retired since the prev round, boolean
    or non-numeric values, and zero/malformed timing spreads all skip
    cleanly rather than KeyError — bench extras must never lose the
    headline line. Advisory only; the exit-status gate over the full
    history is tools/bench_gate.py."""
    deltas, regressions = {}, []
    if not isinstance(prev, dict):
        return deltas, regressions
    for key, val in line.items():
        if key not in _METRIC_TIMING or not isinstance(val, (int, float)) \
                or isinstance(val, bool):
            continue
        pv = prev.get(key)
        if not isinstance(pv, (int, float)) or isinstance(pv, bool) \
                or pv <= 0:
            continue
        delta = (val - pv) / pv
        deltas[key] = round(delta, 4)
        tol = max(_rel_spread(line.get(_METRIC_TIMING[key], {})),
                  _rel_spread(prev.get(_METRIC_TIMING[key], {})), floor)
        if delta < -tol:  # all tracked metrics are higher-is-better
            regressions.append(key)
    return deltas, regressions


def main():
    import sys
    import traceback
    from mxnet_tpu import metrics as _metrics
    # telemetry rides along: recompile counts / step histograms / HBM peak
    # in the same JSON line the driver archives, so perf rounds are
    # regressable on compile behavior too, not just throughput. The timed
    # loops are single step.run dispatches (device-bound), so the per-op
    # counter cost is noise — but the regime IS marked in the output so
    # rounds benched with telemetry off are not compared blind (the first
    # telemetry-on round vs a telemetry-off baseline).
    _metrics.enable()
    # the cost ledger rides with every bench round: each executable this
    # process builds deposits its XLA cost at compile time, and the live
    # mxnet_mfu / regime verdicts land in the "perf" section below
    from mxnet_tpu.observability import perf as _perf
    _perf.enable()
    fp32 = bench_resnet50("float32")
    line = {
        "metric": "resnet50_train_fp32_bs128_imgs_per_sec",
        "value": fp32["imgs_per_sec"],
        "unit": "img/s",
        "vs_baseline": round(fp32["imgs_per_sec"] / BASELINE_IMGS_PER_SEC, 3),
        "mfu": fp32.get("mfu"),
        "timing": fp32.get("timing"),
    }
    # extras must never lose the headline metric
    try:
        bf16 = bench_resnet50("bfloat16")
        line["bf16_imgs_per_sec"] = bf16["imgs_per_sec"]
        line["bf16_mfu"] = bf16.get("mfu")
        line["bf16_timing"] = bf16.get("timing")
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        bert = bench_bert_base_ft()
        line["bert_base_ft_examples_per_sec"] = bert["examples_per_sec"]
        if "mfu" in bert:
            line["bert_mfu"] = bert["mfu"]
        line["bert_timing"] = bert.get("timing")
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        gpt = bench_gpt2_train()
        line["gpt2_train_tokens_per_sec"] = gpt["tokens_per_sec"]
        if "mfu" in gpt:
            line["gpt2_mfu"] = gpt["mfu"]
        line["gpt2_mfu_xla_visible"] = gpt.get("mfu_xla_visible")
        line["gpt2_timing"] = gpt.get("timing")
        # the live-gauge acceptance: mxnet_mfu{path=train_step_multi}
        # right after the GPT-2 bench must agree with the offline
        # _mfu (same XLA-visible flops; dt = last vs min-of-trials
        # dispatch, so agreement is bounded by the recorded spread)
        roof = _perf.summary().get("train_step_multi")
        if roof:
            line["gpt2_mfu_live"] = roof["mfu"]
            line["gpt2_regime"] = roof["regime"]
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        dec = bench_gpt2_decode()
        line["gpt2_decode_tokens_per_sec"] = dec["tokens_per_sec"]
        line["gpt2_decode_tokens_per_sec_median"] = \
            dec["tokens_per_sec_median"]
        line["gpt2_decode_timing"] = dec.get("timing")
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        dec8 = bench_gpt2_decode_int8()
        line["gpt2_decode_int8_tokens_per_sec"] = dec8["tokens_per_sec"]
        line["gpt2_decode_int8_tokens_per_sec_median"] = \
            dec8["tokens_per_sec_median"]
        line["gpt2_decode_int8_timing"] = dec8.get("timing")
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        decf = bench_gpt2_decode_fused()
        line["gpt2_decode_fused_tokens_per_sec"] = decf["tokens_per_sec"]
        line["gpt2_decode_fused_tokens_per_sec_median"] = \
            decf["tokens_per_sec_median"]
        line["gpt2_decode_fused_timing"] = decf.get("timing")
        line["gpt2_decode_fused_multi_token"] = decf.get("multi_token")
        line["gpt2_decode_launches_per_step"] = \
            decf.get("launches_per_step")
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        dec4 = bench_int4_decode()
        line["int4_decode_tokens_per_sec"] = dec4["tokens_per_sec"]
        line["int4_decode_tokens_per_sec_median"] = \
            dec4["tokens_per_sec_median"]
        line["int4_decode_multi_token"] = dec4["multi_token"]
        line["int4_decode_launches_per_step"] = dec4["launches_per_step"]
        line["int4_decode_timing"] = dec4["timing"]
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        specd = bench_spec_decode()
        line["spec_decode_tokens_per_sec_median"] = \
            specd["tokens_per_sec_median"]
        line["spec_decode_baseline_tokens_per_sec_median"] = \
            specd["baseline_tokens_per_sec_median"]
        line["spec_vs_baseline_speedup"] = specd["speedup"]
        line["spec_decode_acceptance_rate"] = specd["acceptance_rate"]
        line["spec_decode_speculate"] = specd["speculate"]
        line["spec_decode_timing"] = specd["timing"]
        line["spec_decode_baseline_timing"] = specd["baseline_timing"]
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        gram = bench_grammar_decode()
        line["grammar_tokens_per_sec_median"] = \
            gram["tokens_per_sec_median"]
        line["grammar_free_tokens_per_sec_median"] = \
            gram["free_tokens_per_sec_median"]
        line["grammar_vs_free_cost_pct"] = gram["cost_pct"]
        line["grammar_acceptance_rate"] = gram["acceptance_rate"]
        line["grammar_free_acceptance_rate"] = \
            gram["free_acceptance_rate"]
        line["grammar_conformant"] = gram["conformant"]
        line["grammar_decode_timing"] = gram["timing"]
        line["grammar_free_timing"] = gram["free_timing"]
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        paf = bench_prefix_affinity()
        line["prefix_affinity_ttft_speedup"] = paf["speedup"]
        line["prefix_affinity_ttft_mean_ms"] = paf["ttft_mean_ms"]
        line["prefix_affinity_blind_ttft_mean_ms"] = \
            paf["blind_ttft_mean_ms"]
        line["prefix_affinity_outcomes"] = paf["outcomes"]
        line["prefix_affinity_replicas"] = paf["replicas"]
        line["prefix_affinity_timing"] = paf["timing"]
        line["prefix_affinity_blind_timing"] = paf["blind_timing"]
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        duel = bench_tuned_vs_default()
        line["tuned_vs_default_speedup"] = duel["speedup"]
        line["tuned_decode_tokens_per_sec_median"] = \
            duel["tuned_tokens_per_sec_median"]
        line["tuned_decode_default_tokens_per_sec_median"] = \
            duel["default_tokens_per_sec_median"]
        line["tuned_decode_knobs"] = duel["tuned_knobs"]
        line["tuned_decode_regime"] = duel["regime"]
        line["tuned_decode_timing"] = duel["timing"]
        line["tuned_decode_default_timing"] = duel["default_timing"]
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        pipe = bench_input_pipeline()
        line["pipeline_input_bound_speedup"] = pipe["speedup"]
        line["pipeline_prefetch_examples_per_sec"] = \
            pipe["prefetch_examples_per_sec"]
        line["pipeline_no_prefetch_examples_per_sec"] = \
            pipe["no_prefetch_examples_per_sec"]
        line["pipeline_timing"] = pipe.get("timing")
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        zov = bench_zero_overlap()
        line["zero_overlap_fraction"] = zov["overlap_fraction"]
        line["zero_overlap_dp"] = zov["dp"]
        line["zero_overlap_timing"] = zov["timing"]
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        ho = bench_health_overhead()
        line["health_overhead_pct"] = ho["overhead_pct"]
        line["health_on_timing"] = ho["timing"]
        line["health_off_timing"] = ho["off_timing"]
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        aotws = bench_aot_warmstart()
        line["aot_cold_warmup_s"] = aotws["cold_warmup_s"]
        line["aot_warm_warmup_s"] = aotws["warm_warmup_s"]
        line["aot_warmstart_speedup"] = aotws["speedup"]
        line["aot_cache_bytes"] = aotws["cache_bytes"]
        line["aot_timing"] = aotws.get("timing")
    except Exception:
        traceback.print_exc(file=sys.stderr)
    prev_round, prev = _load_prev_round()
    if prev:
        deltas, regressions = compare_vs_prev(line, prev)
        line["vs_prev_round"] = prev_round
        line["vs_prev"] = deltas
        if regressions:
            line["regressions"] = regressions
    try:
        # the round's roofline verdicts (cost ledger + live step notes):
        # per-path MFU / HBM-util / regime, the numbers ROOFLINE.md used
        # to assemble by hand (tools/mxperf.py prints the full ledger)
        line["perf"] = {
            "roofline": _perf.summary(),
            "ledger_entries": len(_perf.LEDGER.entries()),
        }
    except Exception:
        traceback.print_exc(file=sys.stderr)
    try:
        doc = json.loads(_metrics.dumps(format="json"))
        line["telemetry"] = {
            "enabled_during_bench": True,
            "recompilations": _metrics.get_sample_value(
                "mxnet_recompilations_total"),
            "retraces": _metrics.get_sample_value(
                "mxnet_recompilations_total", {"kind": "retrace"}) or 0,
            "op_dispatches": _metrics.get_sample_value(
                "mxnet_op_dispatch_total"),
            "steps": _metrics.get_sample_value(
                "mxnet_step_time_seconds_count"),
            "hbm_peak_bytes": max(
                (s["value"]
                 for s in doc["mxnet_hbm_peak_bytes"]["samples"]),
                default=0.0),
        }
    except Exception:
        traceback.print_exc(file=sys.stderr)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
