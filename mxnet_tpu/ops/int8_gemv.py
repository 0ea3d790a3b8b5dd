"""Weight-only int8 GEMV/GEMM Pallas kernel for decode-shaped matmuls.

Single-token decode is weight-bandwidth-bound: each step reads every Dense
weight once while the activation is a few rows. The round-4 int8 path
(activation-quantized int8 x int8 -> int32 on the MXU,
contrib/quantization.py) LOST to bf16 at decode — profiling shows its
matmul fusions cost 27 ms vs bf16's 17 ms per 128 generated tokens: the
per-step activation round/clip and XLA's int8 GEMV emitter eat the entire
halved-weight-bytes advantage.

This kernel keeps the advantage and drops the overhead: weights stream from
HBM as int8 (half the bytes of bf16), are dequantized in VMEM right before
an MXU dot in the activation's dtype, with per-output-channel scales folded
into the f32 accumulator output. Activations are NOT quantized — weight-only
int8 is also strictly more accurate than the activation-quantized path.

Used by contrib.quantization.QuantizedDense for row counts <= _GEMV_MAX_M;
large-M shapes (training/prefill) keep the int8 x int8 MXU path where the
2x int8 MXU rate wins. Off-TPU the jnp fallback computes the identical
dequantized matmul (parity-testable on CPU).

No reference counterpart: the reference's quantized decode runs cuDNN/oneDNN
int8 GEMMs (src/operator/quantization/); this design is TPU-first.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp

from ..device import on_tpu

__all__ = ["int8_weight_matmul", "int4_weight_matmul", "count_launches",
           "record_launch", "gemv_max_m"]

_BN = 512          # output-channel block per grid cell
# hand-picked row threshold: above this the int8 MXU path wins. This is
# the DEFAULT of the tuned-config layer's `gemv_max_m` knob — routing
# sites consult gemv_max_m() below, never this constant directly, so a
# measured winner (or MXNET_TUNE_GEMV_MAX_M) applies without editing it.
_GEMV_MAX_M = 64


def gemv_max_m() -> int:
    """The GEMV-vs-MXU routing threshold: env override
    (``MXNET_TUNE_GEMV_MAX_M``) > tuned config > ``_GEMV_MAX_M``.
    Resolved at trace time by the routing sites (QuantizedDense, the
    tied LM heads), so the python comparison never reaches a compiled
    step."""
    from ..tune import config as _tune
    return _tune.get_knob("gemv_max_m")

# ---------------------------------------------------------------------------
# Kernel-launch accounting. Decode is overhead-bound: the unit of cost is
# the LAUNCH, so the decode kernels self-report their launch sites, under
# the kernel's kind where the kernel runs and under ``reference`` where the
# plain-XLA reference runs in its place (off-TPU). record_launch fires once
# per python call — under jit that is once per TRACE, so a tally taken
# around a trace (count_launches) measures the static launches-per-step of
# the compiled executable. The cumulative mxnet_decode_launches_total
# counter has the same trace-time semantics.
# ---------------------------------------------------------------------------
_TALLY = threading.local()


@contextlib.contextmanager
def count_launches():
    """Tally decode-kernel launch sites recorded on this thread (e.g. around
    ``jax.jit(step).lower(...)``): yields {kind: count}."""
    prev = getattr(_TALLY, "d", None)
    d: dict = {}
    _TALLY.d = d
    try:
        yield d
    finally:
        _TALLY.d = prev


def record_launch(kind: str):
    """Record one decode-kernel launch site (called at trace time)."""
    d = getattr(_TALLY, "d", None)
    if d is not None:
        d[kind] = d.get(kind, 0) + 1
    from .. import metrics as _metrics
    if _metrics.ENABLED:
        _metrics.DECODE_LAUNCHES.labels(kind=kind).inc()


def _pad_to(x, mult: int, axis: int):
    size = x.shape[axis]
    rem = size % mult
    if rem == 0:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad), size


def int8_weight_matmul(x, w_q, w_scale):
    """x: (M, K) float; w_q: (N, K) int8; w_scale: (N,) f32 per-out-channel.
    Returns (M, N) f32 = x @ (w_q * w_scale).T with dequantization fused
    into the weight stream (Pallas on TPU, plain jnp elsewhere)."""
    M, K = x.shape
    N = w_q.shape[0]
    use_kernel = on_tpu()
    record_launch("gemv" if use_kernel else "reference")
    if not use_kernel:
        wf = w_q.astype(jnp.float32) * w_scale[:, None]
        return (x.astype(jnp.float32) @ wf.T)

    from jax.experimental import pallas as pl

    if x.dtype == jnp.float32:
        # bf16 feeds the MXU at full rate; weight-only quantization keeps
        # the model's own activation precision decisions elsewhere
        x = x.astype(jnp.bfloat16)
    xp, _ = _pad_to(x, 8, 0)
    Mp = xp.shape[0]
    bn = _gemv_bn(N)
    wp, _ = _pad_to(w_q, bn, 0)
    sp, _ = _pad_to(w_scale, bn, 0)
    Np = wp.shape[0]
    sp = sp.reshape(1, Np)  # (1, Np): lane-dim blocks keep Mosaic tiling happy

    def kernel(x_ref, w_ref, s_ref, o_ref):
        xb = x_ref[...]                      # (Mp, K) storage dtype
        wb = w_ref[...]                      # (bn, K) int8
        sb = s_ref[...]                      # (1, bn) f32
        acc = jax.lax.dot_general(
            xb, wb.astype(xb.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # (Mp, bn)
        o_ref[...] = acc * sb

    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
            grid=(Np // bn,),
            in_specs=[
                pl.BlockSpec((Mp, K), lambda j: (0, 0)),
                pl.BlockSpec((bn, K), lambda j: (j, 0)),
                pl.BlockSpec((1, bn), lambda j: (0, j)),
            ],
            out_specs=pl.BlockSpec((Mp, bn), lambda j: (0, j)),
        )(xp, wp, sp)
    return out[:M, :N]


def _gemv_bn(N: int) -> int:
    """Output-channel block of both GEMV kernels: one that divides N
    exactly where possible (transformer dims are 384- or 512-friendly —
    padding 768 -> 1024 wasted a third of the stream); for vocab-sized
    heads a large block amortizes per-grid-cell overhead and the padding
    waste is marginal (<2%)."""
    if N > 4096:
        return 2048
    for cand in (512, 384, 256, 128):
        if N % cand == 0:
            return cand
    return min(_BN, N)


def int4_weight_matmul(x, w_p, w_scale, interpret: bool = False):
    """int4 weight-only GEMV: ``x`` (M, K) float; ``w_p`` (N, K/2) uint8
    — two offset-binary nibbles per byte, EXACTLY the
    ``kvstore/quant.pack_codes(bits=4)`` wire layout; ``w_scale``
    (N, K/block) f32 block scales (``quantize_blocks``). Returns (M, N)
    f32 = ``x @ dequant(w).T``.

    The packed nibble stream halves the int8 lane's weight bytes where
    decode is weight-bandwidth-bound; the kernel unpacks + block-scales
    in VMEM right before a bf16 MXU dot (f32 accumulate — same input
    rounding as the int8 lane). The off-TPU fallback dequantizes through
    the codec's own ``unpack_codes`` / ``dequantize_blocks`` in full f32
    — dequant-exactness vs kvstore/quant.py holds by construction, and
    it is the bitwise contract fused-vs-unfused parity tests run
    against; kernel-vs-fallback parity is to bf16 input rounding."""
    M = x.shape[0]
    N, K2 = w_p.shape
    K = 2 * K2
    nsb = w_scale.shape[1]
    block = K // nsb
    use_kernel = interpret or on_tpu()
    record_launch("gemv_int4" if use_kernel else "reference")
    if not use_kernel:
        from ..kvstore.quant import dequantize_blocks, unpack_codes
        codes = unpack_codes(w_p.reshape(-1), 4)
        wf = dequantize_blocks(codes, w_scale.reshape(-1),
                               block).reshape(N, K)
        return x.astype(jnp.float32) @ wf.T

    from jax.experimental import pallas as pl

    if x.dtype == jnp.float32:
        x = x.astype(jnp.bfloat16)
    xp, _ = _pad_to(x, 8, 0)
    Mp = xp.shape[0]
    # byte j holds codes 2j (lo nibble) and 2j+1 (hi): split the few
    # activation rows into even and odd columns out here, so the kernel
    # contracts each nibble plane as it lies and never interleaves lanes
    # (Mosaic does not finish compiling the in-kernel interleave)
    x_lo, x_hi = xp[:, 0::2], xp[:, 1::2]
    bn = _gemv_bn(N)
    wp, _ = _pad_to(w_p, bn, 0)
    sp, _ = _pad_to(w_scale, bn, 0)          # pad scales 0 -> exact zeros
    Np = wp.shape[0]
    half = block // 2                        # byte columns per scale block

    def kernel(xl_ref, xh_ref, w_ref, s_ref, o_ref):
        w32 = w_ref[...].astype(jnp.int32)   # (bn, K/2) nibble pairs
        sb = s_ref[...]                      # (bn, nsb) block scales
        col = jax.lax.broadcasted_iota(jnp.int32, (bn, K2), 1) // half
        scale = jnp.zeros((bn, K2), jnp.float32)
        for b in range(nsb):
            scale = jnp.where(col == b, sb[:, b:b + 1], scale)

        def plane(codes, x_ref):
            wf = (codes - 8).astype(jnp.float32) * scale
            xb = x_ref[...]
            return jax.lax.dot_general(
                xb, wf.astype(xb.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        o_ref[...] = plane(w32 & 0xF, xl_ref) + plane(w32 >> 4, xh_ref)

    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
            grid=(Np // bn,),
            in_specs=[
                pl.BlockSpec((Mp, K2), lambda j: (0, 0)),
                pl.BlockSpec((Mp, K2), lambda j: (0, 0)),
                pl.BlockSpec((bn, K2), lambda j: (j, 0)),
                pl.BlockSpec((bn, nsb), lambda j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((Mp, bn), lambda j: (0, j)),
            interpret=interpret,
        )(x_lo, x_hi, wp, sp)
    return out[:M, :N]
