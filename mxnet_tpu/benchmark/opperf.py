"""opperf: micro-benchmark individual operators across shapes/dtypes
(reference benchmark/opperf/opperf.py run_performance_test).

TPU notes: timings separate compile (first call) from steady state; the
steady-state loop chains ``iters`` applications inside ONE jitted call so
per-dispatch latency (PJRT round trips) doesn't drown
sub-millisecond ops — the same amortization TrainStep.run uses.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import MXNetError
from ..ndarray import NDArray

__all__ = ["run_performance_test", "nd_op"]


def nd_op(name: str) -> Callable:
    """Resolve an operator by name from np/npx (reference get op by str)."""
    from .. import np as np_mod
    from .. import numpy_extension as npx
    for mod in (npx, np_mod):
        fn = getattr(mod, name, None)
        if fn is not None:
            return fn
    raise MXNetError(f"unknown op {name!r}")


def _time_op(fn, args, kwargs, warmup: int, iters: int,
             run_backward: bool = False):
    raw = [a._data if isinstance(a, NDArray) else a for a in args]

    def fwd(*vals):
        out = fn(*[NDArray(v) if hasattr(v, "dtype") else v for v in vals],
                 **kwargs)
        first = out[0] if isinstance(out, (tuple, list)) else out
        return first._data if isinstance(first, NDArray) else first

    if run_backward:
        grad_fn = jax.grad(lambda *vals: jnp.sum(fwd(*vals))
                           .astype(jnp.float32), argnums=tuple(
                               i for i, v in enumerate(raw)
                               if jnp.issubdtype(jnp.asarray(v).dtype,
                                                 jnp.floating)))

        def once(*vals):
            gs = grad_fn(*vals)
            return sum(jnp.sum(g) for g in gs)
    else:
        once = fwd

    # chained steady-state program: out feeds a cheap dependency so XLA
    # cannot elide iterations
    def chained(*vals):
        acc = jnp.float32(0)
        for _ in range(iters):
            y = once(*vals)
            acc = acc + jnp.sum(y).astype(jnp.float32)
        return acc

    jfn = jax.jit(chained)
    t0 = time.perf_counter()
    onp.asarray(jfn(*raw))          # includes compile
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(max(warmup, 1)):
        t0 = time.perf_counter()
        onp.asarray(jfn(*raw))
        best = min(best, time.perf_counter() - t0)
    return compile_s, best / iters


def run_performance_test(ops, inputs: List[Dict], run_backward: bool = False,
                         dtype: str = "float32", warmup: int = 3,
                         runs: int = 10) -> List[Dict]:
    """Benchmark each op over each input config (reference
    opperf.py run_performance_test signature role).

    ``ops``: callable / op name / list thereof. ``inputs``: list of dicts;
    array-valued entries are given as shape tuples under keys the op takes
    positionally in order (key order preserved). Returns result dicts with
    avg_time_ms (steady state) and compile_ms.
    """
    if not isinstance(ops, (list, tuple)):
        ops = [ops]
    results = []
    rng = onp.random.RandomState(0)
    for op in ops:
        fn = nd_op(op) if isinstance(op, str) else op
        name = op if isinstance(op, str) else getattr(op, "__name__", "op")
        for cfg in inputs:
            args = []
            kwargs = {}
            for k, v in cfg.items():
                if isinstance(v, tuple) and all(
                        isinstance(d, int) for d in v):
                    args.append(NDArray(
                        rng.randn(*v).astype(dtype)))
                else:
                    kwargs[k] = v
            compile_s, per_iter = _time_op(fn, args, kwargs, warmup, runs,
                                           run_backward=run_backward)
            results.append({
                "operator": name, "inputs": dict(cfg),
                "avg_time_ms": round(per_iter * 1e3, 4),
                "compile_ms": round(compile_s * 1e3, 1),
                "backward": bool(run_backward),
            })
    return results
