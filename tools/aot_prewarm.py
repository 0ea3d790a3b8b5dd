#!/usr/bin/env python
"""Pre-populate a persistent AOT compile cache for a named model/config.

Compilation off the serving path: run this in CI (or on a build host with
the same backend/topology as the fleet), archive the cache directory plus
the manifest it writes, and every serving replica / preempted-and-resumed
trainer that starts with ``MXNET_AOT_CACHE_DIR`` pointed at the restored
directory warm-starts from disk — cold-start measured in seconds of IO,
not minutes of XLA.

The cache is content-addressed on the lowered program, NOT on parameter
values, so a prewarmed cache built from a randomly-initialized model of
the right config serves real checkpoints unchanged.

Examples::

    # build the serve-bucket ladder (+ train step) for a tiny GPT
    JAX_PLATFORMS=cpu python tools/aot_prewarm.py \
        --model gpt --cache-dir /tmp/aot --manifest /tmp/aot/gpt.manifest.json

    # verify a shipped cache before taking traffic
    JAX_PLATFORMS=cpu python tools/aot_prewarm.py \
        --cache-dir /tmp/aot --verify /tmp/aot/gpt.manifest.json

``--verify`` also validates shipped tuned-config manifests (mxtune
winners: key present, format/version current, payload checksum intact)
found in the cache dir or named via ``--tune-manifest`` — tuned configs
ship alongside AOT manifests, and a stale one fails the preflight the
same way a missing executable does.

Prints one JSON line; exits non-zero on failure (including --verify with
missing entries).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def build_model(args):
    import mxnet_tpu as mx
    mx.random.seed(args.seed)
    if args.model == "gpt":
        from mxnet_tpu.models.gpt import GPTConfig, GPTModel
        cfg = GPTConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                        num_layers=args.layers, num_heads=args.heads,
                        max_position_embeddings=max(2 * args.max_len, 64),
                        dropout=0.0)
        net = GPTModel(cfg)
    elif args.model == "llama":
        from mxnet_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                          intermediate_size=2 * args.hidden,
                          num_layers=args.layers, num_heads=args.heads,
                          max_position_embeddings=max(2 * args.max_len, 64))
        net = LlamaForCausalLM(cfg)
    else:
        raise SystemExit(f"unknown --model {args.model!r}")
    net.initialize()
    config = {k: v for k, v in vars(cfg).items()
              if isinstance(v, (int, float, str, bool))}
    config.update(model=args.model, max_batch_size=args.max_batch_size,
                  max_len=args.max_len, train_batch=args.train_batch)
    return net, config


def prewarm(args) -> dict:
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import aot, metrics, np
    from mxnet_tpu.serve import InferenceEngine

    metrics.enable()
    cache = aot.enable(args.cache_dir)
    net, config = build_model(args)

    t0 = time.perf_counter()
    eng = InferenceEngine(net, max_batch_size=args.max_batch_size,
                          max_len=args.max_len, page_size=args.page_size)
    eng.warmup()
    serve_s = eng.last_warmup_s

    train_s = None
    if args.train_batch:
        # the preemption-resume path: the fused train step for one batch
        # signature rides in the same cache/manifest
        from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
        from mxnet_tpu.parallel import TrainStep
        rng = onp.random.RandomState(args.seed)
        B, T = args.train_batch, min(args.max_len, 32)
        ids = np.array(rng.randint(0, args.vocab, (B, T)).astype(onp.int32))
        labels = np.array(rng.randint(0, args.vocab, (B, T))
                          .astype(onp.int32))
        t1 = time.perf_counter()
        step = TrainStep(net, SoftmaxCrossEntropyLoss(),
                         mx.optimizer.Adam(learning_rate=1e-4),
                         example_inputs=[ids])
        step(ids, labels).item()
        train_s = round(time.perf_counter() - t1, 3)

    name = args.name or f"{args.model}-h{args.hidden}l{args.layers}"
    manifest_path = args.manifest or os.path.join(
        args.cache_dir, f"{name}.manifest.json")
    aot.write_manifest(manifest_path, name, config, cache.touched)
    return {
        "ok": True,
        "model": name,
        "cache_dir": args.cache_dir,
        "manifest": manifest_path,
        "entries": len({e["key"] for e in cache.touched}),
        "cache_bytes": cache.total_bytes(),
        "serve_warmup_s": round(serve_s, 3) if serve_s else None,
        "train_step_s": train_s,
        "total_s": round(time.perf_counter() - t0, 3),
        "aot_hits": metrics.get_sample_value("mxnet_aot_cache_hits_total"),
        "aot_misses": metrics.get_sample_value(
            "mxnet_aot_cache_misses_total"),
    }


def verify(args) -> dict:
    from mxnet_tpu import aot
    cache = aot.AotCache(args.cache_dir)
    manifest = aot.read_manifest(args.verify)
    res = aot.verify_manifest(manifest, cache)
    out = {
        "ok": res["ok"],
        "model": manifest.get("model"),
        "manifest": args.verify,
        "present": len(res["present"]),
        "missing": len(res["missing"]),
        "missing_keys": res["missing"][:8],
    }
    tuned = verify_tuned(args)
    if tuned is not None:
        out["tuned"] = tuned
        out["ok"] = out["ok"] and tuned["ok"]
    return out


def verify_tuned(args) -> dict:
    """Validate shipped tuned-config manifests alongside the executables:
    every entry key present in the config cache, format/version current,
    payload checksum matching what the manifest recorded — a stale tuned
    config ships as loudly as a stale executable. Manifests come from
    ``--tune-manifest`` or are discovered as ``*.tune-manifest.json`` in
    the cache dir (mxtune writes them there); returns None when there is
    nothing to check."""
    import glob

    from mxnet_tpu import tune

    paths = list(args.tune_manifest or [])
    if not paths:
        paths = sorted(glob.glob(os.path.join(args.cache_dir,
                                              "*.tune-manifest.json")))
    if not paths:
        return None
    cache = tune.ConfigCache(args.cache_dir)
    ok = True
    present = missing = stale = 0
    reports = []
    for path in paths:
        try:
            manifest = tune.read_tune_manifest(path)
        except Exception as e:
            ok = False
            reports.append({"manifest": path, "ok": False,
                            "error": f"{type(e).__name__}: {e}"})
            continue
        res = tune.verify_tune_manifest(manifest, cache)
        ok = ok and res["ok"]
        present += len(res["present"])
        missing += len(res["missing"])
        stale += len(res["stale"])
        reports.append({"manifest": path, "name": manifest.get("name"),
                        "ok": res["ok"],
                        "present": len(res["present"]),
                        "missing_keys": res["missing"][:8],
                        "stale_keys": res["stale"][:8]})
    return {"ok": ok, "manifests": reports, "present": present,
            "missing": missing, "stale": stale}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cache-dir", required=True,
                    help="AOT cache directory to populate (or verify)")
    ap.add_argument("--manifest", default=None,
                    help="manifest output path (default: "
                         "<cache-dir>/<name>.manifest.json)")
    ap.add_argument("--verify", default=None, metavar="MANIFEST",
                    help="verify an existing cache against MANIFEST "
                         "instead of prewarming (also validates tuned-"
                         "config manifests found in the cache dir)")
    ap.add_argument("--tune-manifest", action="append", default=None,
                    metavar="TUNE_MANIFEST",
                    help="tuned-config manifest(s) to validate with "
                         "--verify (default: every *.tune-manifest.json "
                         "in the cache dir)")
    ap.add_argument("--model", choices=("gpt", "llama"), default="gpt")
    ap.add_argument("--name", default=None,
                    help="model name recorded in the manifest")
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--max-batch-size", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--train-batch", type=int, default=0,
                    help="also prewarm the fused TrainStep for this batch "
                         "size (0 = serving ladder only)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    try:
        out = verify(args) if args.verify else prewarm(args)
    except Exception as e:
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"}))
        return 1
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
