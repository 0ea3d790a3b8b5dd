"""Micro-probes: what does XLA:TPU fuse around convs/matmuls?

Each probe runs the op in a fori_loop whose input is loop-carried (the
previous iteration's output feeds a cheap elementwise update of x), so XLA
cannot hoist the body. The carried update costs the same ~2 passes over x in
every variant; compare variants, not absolutes.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as onp

B, H, W, C_IN, C_OUT = 128, 56, 56, 256, 64
STEPS = 50


def conv1x1(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.bfloat16)


def loop(body, x, *args):
    """body(x, *args) -> pytree; first leaf's first element feeds the carry."""
    @jax.jit
    def run(xv, *a):
        def f(i, carry):
            r = body(carry, *a)
            first = jax.tree.leaves(r)[0]
            eps = (first.astype(jnp.float32).sum() * 1e-12).astype(jnp.bfloat16)
            return carry * jnp.bfloat16(0.9999) + eps
        out = jax.lax.fori_loop(0, STEPS, f, xv)
        return out.ravel()[0]

    run(x, *args).item()
    ts = []
    for t in range(5):
        # fresh input each trial, so no trial repeats another's inputs
        xt = x * jnp.bfloat16(1.0 + 0.001 * (t + 1))
        _ = xt.ravel()[0].item()
        t0 = time.perf_counter()
        run(xt, *args).item()
        ts.append(time.perf_counter() - t0)
    return min(ts) / STEPS * 1000


def main():
    rng = onp.random.RandomState(0)
    x = jnp.asarray(rng.rand(B, H, W, C_IN).astype("float32"), jnp.bfloat16)
    w = jnp.asarray(rng.rand(1, 1, C_IN, C_OUT).astype("float32"),
                    jnp.bfloat16) * 0.01
    scale = jnp.asarray(rng.rand(C_IN).astype("float32"), jnp.bfloat16)
    shift = jnp.asarray(rng.rand(C_IN).astype("float32"), jnp.bfloat16)

    r = {}
    r["carry_only"] = loop(lambda xs: xs, x)
    r["conv_alone"] = loop(lambda xs, wv: conv1x1(xs, wv), x, w)
    r["conv_with_prologue"] = loop(
        lambda xs, sv, bv, wv: conv1x1(
            jnp.maximum(xs * sv + bv, 0), wv), x, scale, shift, w)

    def conv_stats(xs, wv):
        y = conv1x1(xs, wv)
        s = jnp.sum(y, axis=(0, 1, 2), dtype=jnp.float32)
        s2 = jnp.sum(jnp.square(y.astype(jnp.float32)), axis=(0, 1, 2))
        return y, s, s2

    r["conv_plus_stats"] = loop(conv_stats, x, w)

    def stats_only(xs):
        s = jnp.sum(xs, axis=(0, 1, 2), dtype=jnp.float32)
        s2 = jnp.sum(jnp.square(xs.astype(jnp.float32)), axis=(0, 1, 2))
        return s, s2

    r["stats_only"] = loop(stats_only, x)
    r["apply_relu_only"] = loop(
        lambda xs, sv, bv: jnp.maximum(xs * sv + bv, 0), x, scale, shift)

    def bn_train_fwd(xs, g, b):
        m = jnp.mean(xs, axis=(0, 1, 2), dtype=jnp.float32)
        v = jnp.mean(jnp.square(xs.astype(jnp.float32)), axis=(0, 1, 2)) \
            - jnp.square(m)
        inv = jax.lax.rsqrt(v + 1e-5)
        sc = (g.astype(jnp.float32) * inv).astype(xs.dtype)
        sh = (-m * inv * g.astype(jnp.float32)).astype(xs.dtype)
        return jnp.maximum(xs * sc + sh, 0)

    r["bn_relu_train_fwd"] = loop(bn_train_fwd, x, scale, shift)

    xm = x.reshape(-1, C_IN)
    wm = w.reshape(C_IN, C_OUT)
    r["matmul_form"] = loop(
        lambda xs, wv: (xs.reshape(-1, C_IN) @ wv).reshape(B, H, W, C_OUT),
        x, wm)

    for k, v in r.items():
        print(f"{k}: {v:.3f} ms")
    nbytes = B * H * W * C_IN * 2
    print(f"one pass over x at 819GB/s: {nbytes/819e9*1000:.3f} ms "
          f"({nbytes/1e6:.0f} MB)")


if __name__ == "__main__":
    main()
