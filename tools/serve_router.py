#!/usr/bin/env python
"""Multi-replica serving frontend: route traffic over N engine replicas.

Three modes:

- ``--backends URL,URL,...`` — route over replicas that are already
  running (each an ``HTTPFrontend``; any host). The router frontend
  serves ``/generate`` (least-loaded dispatch + failover), ``/healthz``
  (fleet aggregate), ``/drain`` (``{"backend": url}`` — graceful rolling
  restart), ``/metrics``.
- ``--spawn N`` — ALSO launch N replica subprocesses of this script on
  ports ``--replica-base-port..+N-1`` (the tiny loadgen model; serving
  mechanics, not model quality). With ``--aot-cache-dir`` every replica
  starts with ``MXNET_AOT_CACHE_DIR`` pointed at the shared prewarmed
  cache, so a replica (re)start deserializes the whole bucket ladder
  from disk instead of paying a compile storm — the
  manifest-prewarmed-rollout story (tools/aot_prewarm.py builds and
  ``--prewarm-manifest`` preflights the cache before any replica boots).
- ``--replica`` (internal) — run ONE engine + HTTPFrontend on ``--port``.

Self-managing fleet: ``--autoscale`` runs the fleet controller in the
router process — sustained load or SLO error-budget burn spawns another
replica subprocess (same argv as --spawn, AOT-prewarmed when
--aot-cache-dir is set), sustained slack drains the least-loaded one
(in-flight requests finish; bounced requests replay on the survivors).
``--weights-dir`` makes every replica poll for published weight versions
(mxnet_tpu.serve.registry.publish_weights) and hot-swap between decode
ticks: a deploy is a checkpoint publish, not a restart.

Examples::

    # 2 local replicas + router, AOT-prewarmed rollout
    JAX_PLATFORMS=cpu python tools/aot_prewarm.py --cache-dir /tmp/aot \
        --max-batch-size 16 --max-len 128
    JAX_PLATFORMS=cpu python tools/serve_router.py --spawn 2 \
        --aot-cache-dir /tmp/aot --port 8080

    # route over an existing fleet
    python tools/serve_router.py \
        --backends http://h1:8000,http://h2:8000 --port 8080

    # drain one replica for a rolling restart
    curl -XPOST localhost:8080/drain \
        -d '{"backend": "http://h1:8000"}'

    # self-managing fleet: 2-replica floor, autoscale to 6 on load/SLO
    # burn, live weight refresh off a published checkpoint directory
    JAX_PLATFORMS=cpu python tools/serve_router.py --spawn 2 \
        --autoscale --max-replicas 6 --slo-ttft-p99 0.5 \
        --weights-dir /ckpt/published --port 8080

The router process does no jax computation, so it never initializes a
PJRT device client — colocating it on a TPU host costs no accelerator
(the import itself does pull jax into the process).
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def run_replica(args):
    """One serving replica: tiny loadgen model + engine + HTTPFrontend
    (blocking). ``MXNET_AOT_CACHE_DIR`` in the environment warm-starts
    the whole bucket ladder from the shared prewarmed cache. With
    ``--weights-dir`` the replica polls for published weight versions
    (serve/registry.py layout) and hot-swaps between decode ticks — the
    pull half of live weight refresh (``POST /weights`` is the push)."""
    from serve_loadgen import default_model

    from mxnet_tpu import metrics
    from mxnet_tpu.observability import perf, recorder, trace
    from mxnet_tpu.serve import InferenceEngine, WeightRefresher
    from mxnet_tpu.serve.http import serve_forever

    metrics.enable()
    trace.enable()              # /trace/{id} works out of the box
    perf.enable()               # /perf cost ledger captures the ladder
    recorder.install_sigterm()  # flight-recorder dump on shutdown
    net = default_model(max_len=args.max_len)
    eng = InferenceEngine(
        net, max_batch_size=args.max_batch_size, max_len=args.max_len,
        page_size=args.page_size)
    eng.start()
    if args.weights_dir:
        WeightRefresher(eng, args.weights_dir,
                        interval=args.weights_poll_s).start()
    t0 = time.perf_counter()
    eng.warmup()
    print(json.dumps({"replica": args.port,
                      "warmup_s": round(time.perf_counter() - t0, 3),
                      "aot_hits": metrics.get_sample_value(
                          "mxnet_aot_cache_hits_total")}), flush=True)
    serve_forever(eng, host=args.host, port=args.port)


def wait_healthy(url: str, timeout: float) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(url + "/healthz", timeout=2) as r:
                if json.loads(r.read()).get("ok"):
                    return True
        except Exception:
            pass
        time.sleep(0.25)
    return False


def replica_argv(args, port: int):
    """The command line for ONE replica subprocess on ``port`` — shared
    by the boot-time --spawn fleet and the autoscale controller's
    SubprocessSpawner (a scaled-up replica is configured identically)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--replica",
           "--host", args.host, "--port", str(port),
           "--max-batch-size", str(args.max_batch_size),
           "--max-len", str(args.max_len),
           "--page-size", str(args.page_size)]
    if args.weights_dir:
        cmd += ["--weights-dir", args.weights_dir,
                "--weights-poll-s", str(args.weights_poll_s)]
    return cmd


def replica_env(args):
    env = dict(os.environ)
    if args.aot_cache_dir:
        env["MXNET_AOT_CACHE_DIR"] = args.aot_cache_dir
    return env


def spawn_replicas(args):
    """Launch N replica subprocesses; returns (procs, urls)."""
    env = replica_env(args)
    procs, urls = [], []
    for i in range(args.spawn):
        port = args.replica_base_port + i
        procs.append(subprocess.Popen(replica_argv(args, port), env=env))
        urls.append(f"http://{args.host}:{port}")
    return procs, urls


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backends", default=None,
                    help="comma-separated replica URLs to route over")
    ap.add_argument("--spawn", type=int, default=0, metavar="N",
                    help="also launch N replica subprocesses locally")
    ap.add_argument("--replica", action="store_true",
                    help="internal: run one replica (engine + HTTP)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080,
                    help="router (or --replica) port")
    ap.add_argument("--replica-base-port", type=int, default=8100)
    ap.add_argument("--max-batch-size", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--aot-cache-dir", default=None,
                    help="shared prewarmed AOT cache for spawned replicas "
                         "(replica restart = seconds of IO, not a compile "
                         "storm)")
    ap.add_argument("--prewarm-manifest", default=None, metavar="MANIFEST",
                    help="with --aot-cache-dir: verify the cache against "
                         "this manifest before booting any replica")
    ap.add_argument("--health-interval", type=float, default=1.0)
    ap.add_argument("--boot-timeout", type=float, default=300.0)
    ap.add_argument("--slo-ttft-p99", type=float, default=None,
                    metavar="SECONDS",
                    help="arm the fleet SLO tracker: p99 TTFT target "
                         "(mxnet_slo_* on the router /metrics)")
    ap.add_argument("--slo-intertoken-p99", type=float, default=None,
                    metavar="SECONDS",
                    help="p99 inter-token latency target")
    ap.add_argument("--slo-objective", type=float, default=0.99,
                    help="SLO quantile (default 0.99)")
    ap.add_argument("--weights-dir", default=None,
                    help="replicas poll this directory for published "
                         "weight versions (serve/registry.py layout) and "
                         "hot-swap between decode ticks — a deploy is a "
                         "checkpoint publish, not a restart")
    ap.add_argument("--weights-poll-s", type=float, default=5.0)
    ap.add_argument("--autoscale", action="store_true",
                    help="run the fleet autoscale controller: spawn "
                         "replica subprocesses on sustained load/SLO "
                         "burn, drain the least-loaded on sustained "
                         "slack (scale events in mxnet_fleet_*)")
    ap.add_argument("--min-replicas", type=int, default=None,
                    help="autoscale floor (default: the --spawn count)")
    ap.add_argument("--max-replicas", type=int, default=8)
    ap.add_argument("--scale-up-load", type=float, default=0.75)
    ap.add_argument("--scale-down-load", type=float, default=0.25)
    ap.add_argument("--scale-cooldown-s", type=float, default=10.0)
    ap.add_argument("--autoscale-interval", type=float, default=1.0)
    args = ap.parse_args()

    if args.replica:
        run_replica(args)
        return 0

    if args.prewarm_manifest:
        # preflight the shipped cache: a missing entry would silently
        # recompile on every replica — fail loudly instead
        from mxnet_tpu import aot
        cache = aot.AotCache(args.aot_cache_dir)
        res = aot.verify_manifest(aot.read_manifest(args.prewarm_manifest),
                                  cache)
        print(json.dumps({"prewarm_verify": res["ok"],
                          "present": len(res["present"]),
                          "missing": len(res["missing"])}), flush=True)
        if not res["ok"]:
            return 1

    procs = []
    urls = [u for u in (args.backends or "").split(",") if u]
    if args.spawn:
        procs, spawned = spawn_replicas(args)
        urls += spawned
    if not urls:
        print(json.dumps({"ok": False,
                          "error": "need --backends and/or --spawn"}))
        return 1
    for u in urls:
        if not wait_healthy(u, args.boot_timeout):
            print(json.dumps({"ok": False,
                              "error": f"replica {u} never became healthy"}))
            for p in procs:
                p.terminate()
            return 1

    # the router never runs jax computation — the imports below pull
    # jax into the process but initialize no device client
    from mxnet_tpu import metrics
    from mxnet_tpu.observability import recorder, trace
    from mxnet_tpu.serve.router import Router, RouterFrontend

    metrics.enable()
    trace.enable()              # router.dispatch spans + /trace merge
    recorder.install_sigterm()
    slo = {}
    if args.slo_ttft_p99:
        slo["ttft"] = args.slo_ttft_p99
    if args.slo_intertoken_p99:
        slo["intertoken"] = args.slo_intertoken_p99
    router = Router(urls, health_interval=args.health_interval,
                    slo_targets=slo or None,
                    slo_objective=args.slo_objective).start()
    controller = None
    if args.autoscale:
        from mxnet_tpu.serve import (AutoscalePolicy, FleetController,
                                     SubprocessSpawner)
        spawner = SubprocessSpawner(
            lambda port: replica_argv(args, port), host=args.host,
            # scale-ups get ports past the boot-time --spawn block
            base_port=args.replica_base_port + max(args.spawn, 0),
            env=replica_env(args), boot_timeout=args.boot_timeout)
        policy = AutoscalePolicy(
            scale_up_load=args.scale_up_load,
            scale_down_load=args.scale_down_load,
            cooldown_s=args.scale_cooldown_s,
            min_replicas=(args.min_replicas if args.min_replicas
                          is not None else max(1, args.spawn)),
            max_replicas=args.max_replicas)
        controller = FleetController(router, spawner, policy=policy,
                                     interval=args.autoscale_interval)
        controller.start()
    frontend = RouterFrontend(router, host=args.host, port=args.port)
    print(json.dumps({"ok": True, "router": f"http://{args.host}:{args.port}",
                      "backends": urls,
                      "autoscale": bool(controller)}), flush=True)

    def _stop(signum, frame):
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _stop)
    try:
        frontend._httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        # cleanup must not be interruptible by a late/second signal
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        frontend._httpd.server_close()
        if controller is not None:
            controller.stop()
            controller.spawner.stop_all()
        router.stop()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()
    return 0


if __name__ == "__main__":
    sys.exit(main())
