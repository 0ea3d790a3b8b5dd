"""Tied LM-head GEMV fused with token selection, for the decode step.

:func:`fused_lm_head_sample` runs the tied-head GEMV, temperature /
top-k / top-p and token selection in one step. On TPU the greedy and
pure-temperature rows stream the int8 table once through a Pallas kernel
with a running (Gumbel-)argmax in the reduction epilogue — no [B, V]
materialization, no full-vocab sort; rows with top-k/top-p filters take
the XLA path under ``lax.cond`` (``filter_logits`` needs the whole row
of logits for its thresholds), and so does a packed-int4 table. Off-TPU
the reference matches ``models.generation.sample_tokens`` bitwise.

Vocab padding: ``contrib.quantization._quantize_tied_lm_head`` pads the
int8 table's vocab dim to a 128-lane multiple (50257 -> 50304) so the
reduction tiles land on lane boundaries without a remainder branch; the
pad lanes are masked to -inf before any sampling and sliced off before
any logits consumer (the slice is free — XLA folds it into the layout).

TPU-side determinism note: the kernel draws its Gumbel noise from a
stateless hash of (request fold_in key bits, absolute vocab lane), so
sampled tokens are deterministic per (seed, counter) and independent of
batch composition — but follow a different stream than host
``jax.random.categorical``; greedy rows are exactly identical.

The block-level decode kernels that once lived here (one launch per
transformer block, contiguous / VMEM-paged / DMA-paged) were refused by
the v5e compiler and are gone; CHANGES.md (PR 23) records the messages.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..device import on_tpu
from .int8_gemv import record_launch

__all__ = ["fused_lm_head_sample", "VOCAB_LANE", "pad_vocab"]

# lane width the vocab dim is padded to (50257 -> 50304)
VOCAB_LANE = 128


def pad_vocab(n: int) -> int:
    """Smallest multiple of VOCAB_LANE >= n."""
    return -(-int(n) // VOCAB_LANE) * VOCAB_LANE


def _deq_matmul(x2d, w_q, w_scale):
    """The exact off-TPU math of ops.int8_gemv.int8_weight_matmul /
    int4_weight_matmul (keep in lockstep: the bitwise fused-vs-unfused
    parity contract depends on it). A uint8 ``w_q`` is the packed-nibble
    int4 lane — (N, K/2) codes with (N, K/block) block scales —
    dequantized through the kvstore/quant.py codec itself, so
    dequant-exactness vs the wire format holds by construction."""
    if w_q.dtype == jnp.uint8:
        from ..kvstore.quant import dequantize_blocks, unpack_codes
        N = w_q.shape[0]
        K = 2 * w_q.shape[1]
        block = K // w_scale.shape[1]
        codes = unpack_codes(w_q.reshape(-1), 4)
        wf = dequantize_blocks(codes, w_scale.reshape(-1),
                               block).reshape(N, K)
        return x2d.astype(jnp.float32) @ wf.T
    wf = w_q.astype(jnp.float32) * w_scale[:, None]
    return x2d.astype(jnp.float32) @ wf.T


# ---------------------------------------------------------------------------
# fused LM-head sampling
# ---------------------------------------------------------------------------

def _hash_uniform(keys_u32, lanes_i32):
    """Stateless per-(request key, absolute lane) uniform in (0, 1):
    murmur3-finalizer mix of the fold_in key bits with the lane index.
    Independent of the row's position in the batch, so a request's
    sample stream survives continuous-batching slot moves — the same
    determinism contract the host fold_in streams give."""
    z = lanes_i32.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
    z = z ^ keys_u32
    z = z ^ (z >> 16)
    z = z * jnp.uint32(0x7FEB352D)
    z = z ^ (z >> 15)
    z = z * jnp.uint32(0x846CA68B)
    z = z ^ (z >> 16)
    # 24 mantissa-safe bits -> (0, 1); +0.5 keeps it strictly positive
    return ((z >> 8).astype(jnp.int32).astype(jnp.float32) + 0.5) \
        * (1.0 / (1 << 24))


def _head_kernel(h, w_q, w_scale, vocab, temps, keybits, out_dtype=None,
                 mask=None, interpret=False):
    """Streamed tied-head GEMV with the token selection fused into the
    reduction epilogue: per vocab block, dequantize + dot, scale by 1/T,
    add Gumbel noise for sampling rows (T>0), mask pad lanes to -inf, and
    keep a running (value, index) argmax. Greedy rows (T==0) skip the
    noise, so they are exactly argmax(logits). The [B, Vp] logits are
    never materialized.

    ``mask`` (optional bool [B, Vp], True = allowed) streams alongside
    the vocab blocks: grammar-forbidden lanes drop to -inf BEFORE the
    running Gumbel-argmax reduction, so constrained selection costs one
    extra where() per block — never a materialized [B, V] filter.

    ``w_q`` is the int8 table ([Vp, D]) with per-row ``w_scale`` [Vp]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, D = h.shape
    Vp = w_q.shape[0]
    # largest candidate dividing Vp: GPT-2's padded 50304 = 131 x 384
    # (the 128 floor always divides — pad_vocab guarantees it)
    bnv = next(c for c in (2048, 1024, 512, 384, 256, VOCAB_LANE)
               if Vp % c == 0)
    nb = Vp // bnv
    has_mask = mask is not None

    def kernel(h_ref, w_ref, s_ref, t_ref, kb_ref, *refs):
        if has_mask:
            m_ref, o_ref, best_v, best_i = refs
        else:
            o_ref, best_v, best_i = refs
            m_ref = None
        g = pl.program_id(0)

        @pl.when(g == 0)
        def _init():
            best_v[...] = jnp.full((B, 1), -jnp.inf, jnp.float32)
            best_i[...] = jnp.zeros((B, 1), jnp.int32)

        wf = w_ref[...].astype(jnp.float32) * s_ref[...].T
        acc = jax.lax.dot_general(
            h_ref[...].astype(jnp.float32), wf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [B, bnv]
        if out_dtype is not None and out_dtype != jnp.float32:
            # the unfused head casts logits to the activation dtype before
            # sampling; round through it here too, so greedy tie-breaks
            # match the K=1 path token-for-token (bf16 models)
            acc = acc.astype(out_dtype).astype(jnp.float32)
        t = t_ref[...]                                          # [B, 1]
        z = acc / jnp.where(t > 0, t, 1.0)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (B, bnv), 1) + g * bnv
        # Gumbel-argmax sampling for T>0 rows, noise from the stateless
        # per-(key, lane) hash (no [B, V] materialization, no sort)
        u = _hash_uniform(kb_ref[...].astype(jnp.uint32), lanes)
        gumbel = -jnp.log(-jnp.log(u))
        z = jnp.where(t > 0, z + gumbel, z)
        if m_ref is not None:
            # grammar mask folds in before the streamed argmax reduction
            z = jnp.where(m_ref[...], z, -jnp.inf)
        # pad lanes (>= vocab) can never win
        z = jnp.where(lanes < vocab, z, -jnp.inf)
        m = jnp.max(z, axis=-1, keepdims=True)
        idx = jnp.min(jnp.where(z == m, lanes, jnp.int32(2 ** 30)),
                      axis=-1, keepdims=True)
        better = m > best_v[...]
        best_v[...] = jnp.where(better, m, best_v[...])
        best_i[...] = jnp.where(better, idx, best_i[...])

        @pl.when(g == nb - 1)
        def _emit():
            o_ref[...] = best_i[...]

    in_specs = [
        pl.BlockSpec((B, D), lambda j: (0, 0)),
        pl.BlockSpec((bnv, D), lambda j: (j, 0)),
        pl.BlockSpec((1, bnv), lambda j: (0, j)),
        pl.BlockSpec((B, 1), lambda j: (0, 0)),                  # temps
        pl.BlockSpec((B, 1), lambda j: (0, 0)),                  # key bits
    ]
    operands = [h, w_q, w_scale.reshape(1, Vp), temps.reshape(B, 1),
                keybits.reshape(B, 1)]
    if has_mask:
        in_specs.append(pl.BlockSpec((B, bnv), lambda j: (0, j)))
        operands.append(mask)
    # 64-bit types off: the package enables x64, and Mosaic refuses the
    # int64 a Python int then becomes in an index map
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid=(nb,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((B, 1), lambda j: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
            scratch_shapes=[
                pltpu.VMEM((B, 1), jnp.float32),
                pltpu.VMEM((B, 1), jnp.int32),
            ],
            interpret=interpret,
        )(*operands)
    return out.reshape(B)


def fused_lm_head_sample(h, w_q, w_scale, vocab, keys, temps, topks, topps,
                         out_dtype=None, mask=None):
    """Tied-head GEMV + sampling for one decode step's last-position
    hidden state ``h`` [B, D]. ``(w_q, w_scale)`` is the vocab-padded
    int8 table; ``vocab`` the true vocab size (pad lanes are masked).

    On TPU, batches with no top-k/top-p filtering stream the table once
    through :func:`_head_kernel` (greedy exact; sampled rows draw
    kernel-side Gumbel noise). Filtered batches — and every off-TPU call
    — compute the same sliced logits the unfused head emits and route
    through ``sample_tokens``, so fused-vs-unfused parity is bitwise
    where the tests run.

    ``mask`` (optional bool [B, vocab], True = allowed) constrains the
    selection: the streamed kernel folds it in before its Gumbel-argmax
    reduction (pad lanes stay masked), the XLA path forwards it to
    ``sample_tokens`` — same legality contract on every backend."""
    from ..models.generation import sample_tokens
    # the streamed kernel takes the int8 table on a TPU; a packed-int4
    # table and every off-TPU call take the XLA reference
    use_kernel = on_tpu() and w_q.dtype == jnp.int8
    record_launch("fused_head" if use_kernel else "reference")
    B = h.shape[0]
    temps = jnp.reshape(jnp.asarray(temps, jnp.float32), (-1,))
    temps = jnp.broadcast_to(temps, (B,))

    def xla_sample():
        logits = _deq_matmul(h, w_q, w_scale)[:, :vocab]
        if out_dtype is not None:
            # the unfused head casts logits to the activation dtype; keep
            # the same op so greedy parity stays bitwise
            logits = logits.astype(out_dtype)
        return sample_tokens(logits, keys, temps, topks, topps, mask=mask)

    if not use_kernel:
        return xla_sample()

    topks_a = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(topks, jnp.int32), (-1,)), (B,))
    topps_a = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(topps, jnp.float32), (-1,)), (B,))
    unfiltered = jnp.all((topks_a <= 0) & (topps_a >= 1.0))
    kd = jax.random.key_data(keys).reshape(B, -1).astype(jnp.uint32)
    keybits = kd[:, 0] if kd.shape[1] == 1 else kd[:, -2] ^ kd[:, -1]
    Vp = w_q.shape[0]
    mask_p = None
    if mask is not None:
        mask_p = jnp.zeros((B, Vp), bool).at[:, :vocab].set(mask)

    def fused():
        return _head_kernel(h, w_q, w_scale, vocab, temps, keybits,
                            out_dtype=out_dtype, mask=mask_p)

    return jax.lax.cond(unfiltered, fused, xla_sample)
