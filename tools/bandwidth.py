#!/usr/bin/env python
"""Collective bandwidth measurement (role of the reference
tools/bandwidth/measure.py, which times kvstore push/pull against
`theoretical` NIC limits).

TPU version: times XLA all-reduce / all-gather / reduce-scatter over a
mesh axis across message sizes and prints achieved algorithmic GB/s
(bus bandwidth uses the 2(n-1)/n ring factor for all-reduce).

Usage:
  python tools/bandwidth.py                 # 8 virtual CPU devices
  python tools/bandwidth.py --devices 4
  MXTPU_TEST_TPU=1 python tools/bandwidth.py   # real chips if available
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def kvstore_mode(args):
    """Compare the r2-era eager per-gradient allgather+host-sum against the
    compiled batched allreduce (kvstore/comm.py) on a multi-process group.
    Run under the launcher:

      python tools/launch.py -n 2 python tools/bandwidth.py --mode kvstore

    (auto-spawns the launcher when DMLC_NUM_WORKER is unset)."""
    import subprocess
    if not os.environ.get("DMLC_NUM_WORKER"):
        cmd = [sys.executable, os.path.join(REPO, "tools", "launch.py"),
               "-n", str(args.workers), sys.executable,
               os.path.abspath(__file__), "--mode", "kvstore",
               "--iters", str(args.iters)]
        sys.exit(subprocess.call(cmd))

    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import np

    kv = mx.kv.create("dist_sync")
    r = kv.rank
    rng = onp.random.RandomState(0)
    # a ResNet-50-ish gradient set: a few big conv tensors + the long tail
    # of small ones (the real model has 161 tensors,106 of them BN vectors)
    shapes = [(512, 512, 3, 3), (2048, 1024), (1024, 512)] + \
             [(256, 128)] * 8 + [(512,)] * 50 + [(256,)] * 50 + [(64,)] * 50
    grads = [np.array(rng.randn(*s).astype("float32")) for s in shapes]
    nbytes = sum(int(onp.prod(s)) * 4 for s in shapes)

    def eager_once():
        from jax.experimental import multihost_utils
        for g in grads:
            gathered = multihost_utils.process_allgather(g._data)
            g._set_data(jnp.sum(gathered, axis=0))

    def compiled_once():
        kv.allreduce_grads(grads)

    def timed(fn):
        fn()  # warm
        t0 = time.perf_counter()
        for _ in range(args.iters):
            fn()
        mx.waitall()
        return (time.perf_counter() - t0) / args.iters

    t_comp = timed(compiled_once)
    t_eager = timed(eager_once)
    if r == 0:
        out = {"kvstore_allreduce": {
            "payload_mib": round(nbytes / (1 << 20), 2),
            "eager_ms": round(t_eager * 1e3, 2),
            "compiled_ms": round(t_comp * 1e3, 2),
            "speedup": round(t_eager / t_comp, 2)}}
        print(json.dumps(out), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--mode", type=str, default="collectives",
                    choices=["collectives", "kvstore"])
    ap.add_argument("--sizes", type=str,
                    default="1,4,16,64,256")  # MiB per device
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--collective", type=str, default="all",
                    choices=["all", "allreduce", "allgather",
                             "reducescatter"])
    args = ap.parse_args()
    if args.mode == "kvstore":
        return kvstore_mode(args)

    if not os.environ.get("MXTPU_TEST_TPU"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}").strip()
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu import parallel

    n = min(args.devices, len(jax.devices()))
    mesh = parallel.make_mesh({"x": n}, devices=jax.devices()[:n])
    print(f"# devices: {n} ({jax.devices()[0].platform}/"
          f"{jax.devices()[0].device_kind})")

    def timed(fn, x):
        onp.asarray(jax.block_until_ready(fn(x)))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(x)
        jax.block_until_ready(out)
        onp.asarray(out.ravel()[0])  # force a device->host fetch
        return (time.perf_counter() - t0) / args.iters

    col_defs = {
        "allreduce": (lambda v: jax.lax.psum(v, "x"),
                      lambda b: 2 * (n - 1) / n * b),
        "allgather": (lambda v: jax.lax.all_gather(v, "x"),
                      lambda b: (n - 1) / n * b * n),
        "reducescatter": (lambda v: jax.lax.psum_scatter(v, "x"),
                          lambda b: (n - 1) / n * b),
    }
    wanted = (list(col_defs) if args.collective == "all"
              else [args.collective])

    rows = []
    for name in wanted:
        body, bus_bytes = col_defs[name]
        from mxnet_tpu.parallel.mesh import shard_map as _shard_map
        fn = jax.jit(_shard_map(  # mxlint: disable=MX002 -- one wrapper
            # per collective kind (<=3, not per hot-loop iteration),
            # reused across every size in the inner timing loop
            body, mesh=mesh, in_specs=P("x"), out_specs=P("x")))
        for mib in (float(s) for s in args.sizes.split(",")):
            per_dev = int(mib * (1 << 20) / 4)
            x = jnp.ones((n * per_dev,), jnp.float32)
            dt = timed(fn, x)
            total_bytes = n * per_dev * 4
            gbs = bus_bytes(total_bytes) / dt / 1e9
            rows.append({"collective": name, "mib_per_dev": mib,
                         "ms": round(dt * 1e3, 3),
                         "bus_gb_s": round(gbs, 2)})
            print(f"{name:>14} {mib:7.1f} MiB/dev  {dt*1e3:9.3f} ms  "
                  f"{gbs:9.2f} GB/s")
    print(json.dumps({"bandwidth": rows}))


if __name__ == "__main__":
    main()
