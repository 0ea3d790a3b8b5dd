"""Readings that the limits of `correct` are set from, many seeds in one
process, on the chip, at the cell's own size. Not part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --controls 3 \
        --out chiprun_out/cal.jsonl [--seconds 12]

For each seed: the program's readings against the reference's (the *lower*
reading of every number compared). For the first ``--controls`` seeds also the
control — the reference put in the program's place and computed in the next
precision down (fp8 for a bfloat16 configuration) — and, for a training cell,
the fault planted in the reference put in the program's place: half of the
batch left out (the mean taken over the rest). Every reading goes through
the run's own comparison (``compare`` of the traffic module, then
``run.judge`` against the cell's limits), so each line says what `correct`
a run with those numbers would have printed. One JSON line per reading.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys
import time

import run as harness   # sets up the ``mxbench`` alias


def _ctx(args, seed):
    import jax
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell, spec, cfg = harness.find_cell(bench, args.workload, harness.BENCH)
    devices = jax.devices()[:int(cell["chips"])]
    peaks = harness.load_json(os.path.join(harness.BENCH, "peaks.json"))[
        devices[0].device_kind]
    builder = importlib.import_module(f"mxbench.models.{cfg['builder']}")
    traffic = importlib.import_module(
        f"mxbench.traffic.{spec['traffic']['kind']}")
    return traffic, {"cell": cell, "spec": spec, "cfg": cfg,
                     "builder": builder, "seed": seed, "devices": devices,
                     "chips": int(cell["chips"]), "peaks": peaks,
                     "note": lambda **kw: None, "rehearsal": False,
                     "sample_engine": False, "t_start": time.perf_counter()}


def emit(out, limits=None, **row):
    if limits is not None:
        row["correct"], row["numbers"], row["notes"] = harness.judge(
            row["numbers"], limits)
    line = json.dumps(row)
    print(line[:600], flush=True)
    out.write(line + "\n")
    out.flush()


def train(args, out):
    import jax.numpy as jnp
    from mxbench.traffic import train_steps as ts
    for k, seed in enumerate(args.seeds):
        traffic, ctx = _ctx(args, seed)
        spec, cfg, builder = ctx["spec"], ctx["cfg"], ctx["builder"]
        t0 = time.perf_counter()
        state = ts.setup(ctx)
        t1 = time.perf_counter()
        ts.release(state)
        gc.collect()
        tr, opt = spec["traffic"], spec["optimizer"]
        tokens = ts.make_batches(cfg, seed, int(tr["batches"]),
                                 int(tr["global_batch"]), int(tr["seq"]))
        batches = [(tokens[i, :, :-1], tokens[i, :, 1:])
                   for i in range(ts.CHECK_STEPS)]
        params = builder.reference_weights(cfg, seed)
        want = ts.reference_readings(builder, params, batches, cfg, opt, spec)
        t2 = time.perf_counter()
        parts = state["parts"]
        nums = ts.compare(state["got"], want, parts)
        limits = spec["limits"]
        emit(out, limits, kind="program", seed=seed, numbers=nums,
             losses=state["got"]["losses"], ref_losses=want["losses"],
             setup_s=t1 - t0, reference_s=t2 - t1)
        if k >= args.controls:
            continue

        as_got = lambda r: ts.as_got(r, parts)
        ctl = ts.reference_readings(builder, params, batches, cfg, opt, spec,
                                    fake=jnp.float8_e4m3fn)
        emit(out, limits, kind="control_fp8", seed=seed,
             numbers=ts.compare(as_got(ctl), want, parts))
        half = lambda b: (b[0][: b[0].shape[0] // 2],
                          b[1][: b[1].shape[0] // 2])
        flt = ts.reference_readings(builder, params, batches, cfg, opt, spec,
                                    transform=half)
        emit(out, limits, kind="fault_half_batch", seed=seed,
             numbers=ts.compare(as_got(flt), want, parts))
        del params, want
        gc.collect()


def serve(args, out, traffic=None, state=None):
    """``state``: an engine already set up with the first seed's weights
    (``bench/sweep.py`` may have used it first)."""
    import jax.numpy as jnp
    from mxbench.traffic import serve_common as sc
    if state is None:
        traffic, ctx = _ctx(args, args.seeds[0])
        state = traffic.setup(ctx)
    ctx = state["ctx"]
    builder, cfg, spec = ctx["builder"], ctx["cfg"], ctx["spec"]
    engine = state["engine"]
    samples = []
    for k, seed in enumerate(args.seeds):
        if k:
            ctx["seed"] = seed
            engine.swap_weights(dict(builder.program_weights(cfg, seed)))
        facts = traffic.window(state, args.seconds)
        facts["setup_s"] = 0.0
        facts = traffic.after_window(state, facts)
        samples.append((seed, state.pop("sample"),
                        state.pop("length_mismatch")))
        emit(out, kind="window", seed=seed, attempted=facts["attempted"],
             failed=facts["failed"], done=facts["requests_done"],
             checked_tokens=facts["checked_tokens"],
             **{k: facts[k] for k in ("ttft_p50_ms", "ttft_p90_ms",
                                      "itl_p50_ms", "itl_p95_ms", "drain_s")})
    traffic.release(state)
    del engine
    gc.collect()
    controls = (("control_fp8", jnp.float8_e4m3fn), ("control_int8", "int8"))
    for k, (seed, sample, mismatch) in enumerate(samples):
        t0 = time.perf_counter()
        nums = sc.compare(builder, cfg, spec, seed, sample, mismatch)
        emit(out, spec["limits"], kind="program", seed=seed, numbers=nums,
             reference_s=time.perf_counter() - t0)
        for name, fake in controls if k < args.controls else ():
            emit(out, spec["limits"], kind=name, seed=seed,
                 numbers=sc.compare(builder, cfg, spec, seed, sample, 0,
                                    fake=fake))
        gc.collect()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        raise harness.Refuse("calibration readings come from the chip")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(harness.ROOT, ".jax_cache"))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    _, spec, _ = harness.find_cell(bench, args.workload, harness.BENCH)
    with open(args.out, "a") as out:
        (train if spec["traffic"]["kind"] == "train_steps" else serve)(
            args, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
