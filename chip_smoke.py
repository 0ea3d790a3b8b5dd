"""First proof that the system starts on the chip: GPT-2-small trains and
serves in ONE process through the entry points a user calls.

    python chip_smoke.py            # one TPU chip, published width
    python chip_smoke.py --chips 4  # data-parallel TrainStep on four chips
    python chip_smoke.py --tiny     # rehearsal: same code at GPT_TINY, on
                                    # whatever platform is present

Every line printed is one JSON object. The first names the software and the
device, each phase prints one line, and the last line is the contract line
``{"ok": true, "device": {...}}``. Any failed check raises: the process exits
non-zero with the reason on stderr and prints no contract line. The default
mode refuses to run without a TPU; ``--tiny`` never runs at full width.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time

import jax            # importing JAX does not take the chip; the package
import numpy as onp   # (mxnet_tpu) is imported only past the TPU check

#: bf16 losses of the same global batch, one device vs a dp mesh: the
#: cross-replica gradient sum reassociates, and three Adam steps at
#: lr 1e-4 keep the drift far inside one percent
DP_LOSS_RTOL = 1e-2

PROMPT_LENS = (17, 64, 150, 300)
NEW_TOKENS = 32
#: The int8 phase keeps prompt + new tokens within the 64 rows that route a
#: quantized Dense onto the weight-only GEMV (``gemv_max_m``): past it a
#: whole-prompt prefill quantizes activations too, which is other
#: arithmetic than the engine's 16-token chunks, not a rounding of it.
INT8_PROMPT_LENS = (9, 17, 25, 32)

#: Greedy tokens of the engine and of models.generate are compared for
#: identity. In bf16 the two reach the same logits over different reductions
#: (paged attention over max_len columns, chunked prefill, against a
#: contiguous cache of prompt+new columns), so a near-tie between the top
#: two of 50,257 random-weight logits can fall either way, and the
#: sequences part from there. A parting is accepted only as such a tie:
#: teacher-forced through the plain forward, every token the engine chose
#: must be within this many bf16 ulps (2**-8 relative) of that position's
#: largest logit. Anything wider is a wrong token and fails the run.
BF16_TIE_ULPS = 8


def emit(**fields):
    print(json.dumps(fields), flush=True)


def check(cond, reason: str):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {reason}")


def device_fields():
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def launch_tally():
    """{kind: count} of mxnet_decode_launches_total as it stands."""
    from mxnet_tpu import metrics
    return {lv[0]: int(child.value)
            for lv, child in metrics.DECODE_LAUNCHES.children()
            if child.value}


def tally_delta(before, after):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v - before.get(k, 0)}


def build_net(cfg, seed):
    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTModel
    mx.random.seed(seed)
    net = GPTModel(cfg)
    net.initialize()
    return net


def timed_runs(step, ids, labels, steps, calls=3):
    """``calls`` blocking ``step.run`` calls: (losses, seconds)."""
    losses, secs = [], []
    for _ in range(calls):
        t0 = time.perf_counter()
        loss = float(step.run(ids, labels, steps=steps).item())
        secs.append(time.perf_counter() - t0)
        losses.append(loss)
    return losses, secs


def check_losses(losses, what):
    check(all(math.isfinite(v) for v in losses),
          f"{what}: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"{what}: loss did not fall over three calls: {losses}")


def make_batch(cfg, B, T, seed):
    from mxnet_tpu import np
    rng = onp.random.RandomState(seed)
    ids = np.array(rng.randint(0, cfg.vocab_size, (B, T)).astype(onp.int32))
    labels = np.array(rng.randint(0, cfg.vocab_size, (B, T))
                      .astype(onp.int32))
    return ids, labels


def make_step(net, ids, **kw):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    return parallel.TrainStep(
        net, SoftmaxCrossEntropyLoss(),
        mx.optimizer.Adam(learning_rate=1e-4), example_inputs=[ids], **kw)


def on_devices(arrays, devices, what):
    got = set()
    for a in arrays:
        got |= set(a.devices())
    check(got == set(devices),
          f"{what} live on {sorted(map(str, got))}, "
          f"expected {sorted(map(str, devices))}")


# ------------------------------------------------------------------ phases
def phase_train(cfg, B, T, steps, seed, full):
    net = build_net(cfg, seed)
    ids, labels = make_batch(cfg, B, T, seed)
    step = make_step(net, ids)
    losses, secs = timed_runs(step, ids, labels, steps)
    check_losses(losses, "train")
    dev = jax.devices()[0]
    on_devices([p.data()._data for p in net.collect_params().values()],
               [dev], "train parameters")
    pallas = "tpu_custom_call" in step.compiled().as_text()
    if full:
        check(pallas, "the compiled TrainStep holds no tpu_custom_call: "
                      "attention took _fallback, not the Pallas flash "
                      "kernel")
    run_s = min(secs[1:])
    emit(phase="train", platform=dev.platform, batch=B, seq=T,
         steps_per_call=steps, losses=losses, first_call_s=secs[0],
         compile_s=max(secs[0] - run_s, 0.0), run_s=run_s,
         tpu_custom_call=pallas, launches={})


def reference_tokens(net, prompts):
    from mxnet_tpu import np
    from mxnet_tpu.models import generate
    out = []
    for p in prompts:
        full = generate(net, np.array(p[None, :]), NEW_TOKENS,
                        use_cache=True).asnumpy()
        out.append([int(t) for t in full[0, len(p):]])
    return out


def compare_tokens(net, prompt, got, want, name):
    """``"identical"``, or the bf16 tie the two greedy sequences parted at
    (see BF16_TIE_ULPS); exits on anything else."""
    from mxnet_tpu import np
    check(len(got) == len(want) == NEW_TOKENS,
          f"{name}: {len(got)} tokens generated, expected {NEW_TOKENS}")
    if got == want:
        return "identical"
    first = next(i for i, (a, b) in enumerate(zip(got, want)) if a != b)
    ids = onp.concatenate([prompt, onp.asarray(got, onp.int32)])[None, :]
    logits = net(np.array(ids)).asnumpy().astype(onp.float32)[0]
    worst = 0.0
    for i, tok in enumerate(got):
        row = logits[len(prompt) - 1 + i]
        ulps = float(row.max() - row[tok]) / (2.0 ** -8 * float(
            onp.abs(row).max()))
        worst = max(worst, ulps)
        check(ulps <= BF16_TIE_ULPS,
              f"{name}: {len(prompt)}-token prompt: token {i} ({tok}) is "
              f"{ulps:.1f} bf16 ulps under the largest logit — not a tie; "
              f"engine {got} != models.generate {want}")
    return {"parted_at": first, "worst_gap_bf16_ulps": round(worst, 2)}


def phase_serve(net, prompts, want, name, max_len, full, **eng_kw):
    from mxnet_tpu.serve import InferenceEngine
    dev = jax.devices()[0]
    before = launch_tally()
    eng = InferenceEngine(net, max_batch_size=8, max_len=max_len, **eng_kw)
    paged = bool(eng.stats()["paged"])
    if full:
        check(paged, f"{name}: the engine's default layout on a TPU is "
                     "not paged")
    on_devices(eng._pools, [dev], f"{name} KV pool")
    on_devices(eng._values, [dev], f"{name} engine parameters")
    t0 = time.perf_counter()
    eng.warmup()
    compile_s = time.perf_counter() - t0
    eng.start()
    try:
        t0 = time.perf_counter()
        handles = [eng.submit(p, NEW_TOKENS) for p in prompts]
        results = [h.result() for h in handles]
        run_s = time.perf_counter() - t0
    finally:
        eng.shutdown(drain=False)
    verdicts = []
    for r, p, w in zip(results, prompts, want):
        check(r.ok, f"{name}: request of {len(p)} tokens ended "
                    f"{r.status}: {r.error}")
        verdicts.append(compare_tokens(net, p, list(r.generated_ids), w,
                                       name))
    tally = tally_delta(before, launch_tally())
    emit(phase=name, platform=dev.platform, paged=paged,
         prompt_lens=[len(p) for p in prompts], new_tokens=NEW_TOKENS,
         token_parity=all(v == "identical" for v in verdicts),
         tokens=verdicts, compile_s=compile_s, run_s=run_s, launches=tally)
    return tally


def phase_dp(cfg, B, T, steps, seed, chips):
    """The same TrainStep on one device and on a dp mesh over ``chips``
    devices, zero=0 and zero=1, same global batch and seed."""
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu import parallel
    devs = jax.devices()[:chips]
    check(len(devs) == chips,
          f"--chips {chips} needs {chips} devices, JAX reports "
          f"{len(jax.devices())}")
    ids, labels = make_batch(cfg, B, T, seed)

    net = build_net(cfg, seed)
    base, secs = timed_runs(make_step(net, ids), ids, labels, steps)
    check_losses(base, "one-device")
    emit(phase="train_one_device", platform=devs[0].platform, batch=B,
         seq=T, steps_per_call=steps, losses=base, first_call_s=secs[0],
         run_s=min(secs[1:]))

    mesh = parallel.make_mesh({"dp": chips}, devices=devs)
    for zero in (0, 1):
        net = build_net(cfg, seed)
        step = make_step(net, ids, mesh=mesh, data_spec=P("dp"),
                         label_spec=P("dp"), zero=zero)
        losses, secs = timed_runs(step, ids, labels, steps)
        check_losses(losses, f"dp zero={zero}")
        for a, b in zip(base, losses):
            check(abs(a - b) <= DP_LOSS_RTOL * abs(a),
                  f"dp zero={zero}: losses {losses} differ from the "
                  f"one-device {base} by more than {DP_LOSS_RTOL:g}")
        placed = step._place((ids._data,), P("dp"))[0]
        batch_devs = {s.device for s in placed.addressable_shards}
        check(batch_devs == set(devs) and all(
            s.data.shape[0] == B // chips
            for s in placed.addressable_shards),
            f"dp zero={zero}: batch shards sit on "
            f"{sorted(map(str, batch_devs))}")
        state_devs = None
        if zero:
            slot = next(iter(step._zero_meta))
            n_pad = step._zero_meta[slot][1]
            leaf = next(x for x in jax.tree.leaves(step._opt_states[slot])
                        if getattr(x, "shape", None) == (n_pad,))
            shards = leaf.addressable_shards
            state_devs = {s.device for s in shards}
            check(state_devs == set(devs) and all(
                s.data.shape[0] == n_pad // chips for s in shards),
                f"zero=1: optimizer-state shards sit on "
                f"{sorted(map(str, state_devs))}")
        emit(phase=f"train_dp{chips}_zero{zero}",
             platform=devs[0].platform, batch=B, seq=T,
             steps_per_call=steps, losses=losses, loss_rtol=DP_LOSS_RTOL,
             first_call_s=secs[0], run_s=min(secs[1:]),
             batch_shard_devices=sorted(d.id for d in batch_devs),
             state_shard_devices=(sorted(d.id for d in state_devs)
                                  if state_devs else None))


# -------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal at GPT_TINY on whatever platform is "
                         "present")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the data-parallel TrainStep phase and "
                         "the one-device run it is compared with")
    args = ap.parse_args(argv)

    if not args.tiny:
        check(jax.default_backend() == "tpu"
              and jax.devices()[0].platform == "tpu",
              f"no TPU: JAX's default backend is "
              f"{jax.default_backend()!r}; the default mode runs only on "
              "a TPU (--tiny is the CPU rehearsal)")

    import jax.numpy as jnp
    from mxnet_tpu import metrics
    from mxnet_tpu.models.gpt import GPT2_SMALL, GPT_TINY
    from mxnet_tpu.src import nativelib

    metrics.enable()
    emit(jax=jax.__version__, backend=jax.default_backend(),
         device_kind=jax.devices()[0].device_kind,
         device_count=len(jax.devices()),
         compile_cache_dir=jax.config.jax_compilation_cache_dir,
         native_core=nativelib.available(), tiny=args.tiny,
         chips=args.chips)

    full = not args.tiny
    seed = 0
    if full:
        cfg = dataclasses.replace(GPT2_SMALL, dropout=0.0,
                                  dtype=jnp.bfloat16)
        B, T, steps, max_len = 16, 1024, 2, 1024
        prompt_lens = PROMPT_LENS
    else:
        cfg = dataclasses.replace(GPT_TINY, dropout=0.0)
        B, T, steps, max_len = 8, 64, 2, 128
        prompt_lens = (17, 33, 60, 88)

    if args.chips > 1:
        phase_dp(cfg, B, T, steps, seed, args.chips)
    else:
        phase_train(cfg, B, T, steps, seed, full)
        net = build_net(cfg, seed + 1)
        rng = onp.random.RandomState(seed + 1)

        def make_prompts(lens):
            return [rng.randint(0, cfg.vocab_size, (n,)).astype(onp.int32)
                    for n in lens]

        prompts = make_prompts(prompt_lens)
        want = reference_tokens(net, prompts)
        phase_serve(net, prompts, want, "serve", max_len, full)
        # what is left of the fused decode family after the v5e compiler
        # had its say: the int8 GEMV and the fused LM-head sampler, under
        # the on-device multi-token loop
        from mxnet_tpu.contrib.quantization import quantize_net
        quantize_net(net, calib_mode="none")
        prompts = make_prompts(INT8_PROMPT_LENS)
        want = reference_tokens(net, prompts)
        tally = phase_serve(net, prompts, want, "serve_int8",
                            max_len, full, multi_token=8)
        if full:
            check(tally.get("gemv") and tally.get("fused_head")
                  and not tally.get("reference"),
                  f"serve_int8: the int8 GEMV and fused-head kernels did "
                  f"not both run on the TPU: {tally}")

    emit(ok=True, device=device_fields())
    return 0


if __name__ == "__main__":
    sys.exit(main())
