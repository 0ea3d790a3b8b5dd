"""Fused attention: Pallas TPU kernel with XLA fallback.

Replaces the reference's fused transformer matmuls
(`_contrib_interleaved_matmul_selfatt_{qk,valatt}`,
reference src/operator/contrib/transformer.cc:675,723) with a real
flash-attention kernel: blockwise online-softmax so the (T,S) score matrix
never materializes in HBM — O(T) memory, MXU-sized (128-multiple) tiles
streamed through VMEM.

Shape generality (round 5): ANY sequence length >= _MIN_KERNEL_LEN (256)
runs the Pallas kernels. Inputs are zero-padded to adaptive block multiples
(512→256→128, whichever wastes least), the kernels mask padded kv columns
by position, and causal attention supports T != S with end-aligned
semantics (query i attends to keys j <= i + S - T — the decode convention,
matching the jnp reference's ``tril(..., k=S-T)``). Head dims are padded to
the next MXU lane width (64/128/256). Shapes below _MIN_KERNEL_LEN (where
the kernels are grid-overhead-bound — measured slower than XLA fusions at
BERT's T=128) take `_xla_attention` einsums on TPU; very long non-kernel
shapes take *chunked* online-softmax — no path materializes an O(T·S) f32
score matrix at scale.

Forward is a Pallas kernel on TPU; the default backward is ONE fused Pallas
kernel producing dq/dk/dv in a single sweep, recomputing p = exp(s − lse)
blockwise from the saved log-sum-exp under ``jax.custom_vjp`` (a two-kernel
dq; dk+dv variant remains for sequences too long for the fused kernel's
VMEM budget). On CPU (tests) the math runs in plain jnp — identical
semantics, so correctness is testable on the virtual mesh.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..device import on_tpu

__all__ = ["flash_attention", "flash_attention_bthd", "attention"]

# Adaptive query/kv block candidates, largest first (v5e sweep: 512/512
# beats 128/128 by ~1.6x on fwd+bwd at T=1024 — fewer grid cells amortize
# per-cell cost). For a given length the candidate minimizing padded length
# wins; ties go to the larger block.
_BLOCKS = (512, 256, 128)
# Threshold below which the XLA einsum/chunked fallback is used even on
# TPU. Below it the score matrix is small and the Pallas kernel is
# grid-overhead-bound: at BERT's (B=32,H=12,T=128) the kernel's 384 tiny
# grid cells measured 5.9 ms/step vs XLA fused einsums, and decode has T=1
# (dispatch-dominated either way).
_MIN_KERNEL_LEN = 256


def _choose_block(length: int):
    """(block, padded_length) minimizing padding; ties prefer larger blocks."""
    best = None
    for b in _BLOCKS:
        padded = -(-length // b) * b
        if best is None or padded < best[1]:
            best = (b, padded)
    return best


def _pad_head_dim(d: int) -> int:
    for cand in (64, 128, 256):
        if d <= cand:
            return cand
    raise ValueError(f"head dim {d} > 256 has no Pallas path")


def _pad4(x, t_to: int, d_to: int):
    """Zero-pad (B, H, T, D) on the trailing two dims (no-op when aligned)."""
    T, D = x.shape[2], x.shape[3]
    if t_to == T and d_to == D:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, t_to - T), (0, d_to - D)))


def _dot_f32(a, b):
    """MXU dot: keep bf16 inputs (full MXU rate), accumulate in f32 —
    an .astype(f32) before the dot would force the slow multi-pass f32
    MXU path (measured ~2x on the fwd kernel)."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a @ b.T without materializing the transpose (contract on dim 1)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a.T @ b without materializing the transpose (contract on dim 0)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


# Above this score-matrix size the XLA einsum path gives way to the chunked
# online-softmax path (≈1 MB f32 per (b,h) head). One constant shared by
# every routing site so the BHTD and BHTD-transposed entries can't drift.
_XLA_PATH_MAX_SCORE_ELEMS = 2048 * 128


def _jnp_reference(q, k, v, causal: bool, scale: float):
    """Plain-jnp semantics oracle (CPU tests / tiny shapes). O(T·S) memory —
    only reached when T·S is small or off-TPU; long sequences use
    _chunked_reference. Causal T>S keyless rows are 0 (all paths agree)."""
    T, S = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, S), dtype=bool), k=S - T)
        s = jnp.where(mask[None, None], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    if causal and T > S:
        o = o * (jnp.arange(T)[:, None] >= T - S)
    return o.astype(q.dtype)


def _online_block(q, k, v, m, l, acc, scale, mask=None, acc_scale=None):
    """One blockwise-attention accumulation step (flash-attention math).
    ``mask=False`` entries contribute p = 0 even when the whole block is
    masked (m stuck at finfo.min would otherwise make p = exp(0) = 1).
    ``acc_scale``: optional per-element multiplier applied to p ONLY in the
    value accumulation (not the normalizer) — dropout on NORMALIZED probs,
    i.e. dropout(softmax(s)) @ V, expressed blockwise. Shared by
    _chunked_reference here and ring attention (parallel/attention.py)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, jnp.finfo(s.dtype).min)
    m_chunk = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_chunk)
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    pa = p if acc_scale is None else p * acc_scale
    acc_new = acc * corr + jnp.einsum("bhqk,bhkd->bhqd", pa, v)
    return m_new, l_new, acc_new


def _chunked_reference(q, k, v, causal: bool, scale: float, block: int = 512,
                       key_mask=None, dropout=None):
    """Online-softmax over kv chunks via lax.scan (reverse-differentiable):
    O(T·block) live memory — the fallback for shapes that skip the kernel,
    so no path materializes a full (T,S) f32 score matrix at scale. KV stays
    in storage dtype; each chunk is sliced and cast inside the scan body so
    live upcasts are O(block), not O(S). Rows with no valid key (causal
    T > S, or fully key-masked) return 0 — NaN-free, unlike a softmax over
    all-masked scores. ``key_mask``: optional (B, S) 1/0 padding mask.
    ``dropout``: optional (key, rate) attention-prob dropout — bits come
    from the position-indexed generator (numpy_extension._keep_bits_at), so
    each chunk draws exactly its slice of the (B,H,T,S) mask and the
    O(T·block) memory bound HOLDS under dropout (the einsum path's
    materialize-then-drop is only for small T)."""
    B, H, T, D = q.shape
    S = k.shape[2]
    bs = min(block, S)
    nb = -(-S // bs)
    Sp = nb * bs
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    if key_mask is not None and key_mask.shape[-1] != Sp:
        key_mask = jnp.pad(key_mask, ((0, 0), (0, Sp - key_mask.shape[-1])))
    dtype = jnp.promote_types(q.dtype, jnp.float32)
    qf = q.astype(dtype)
    q_pos = jnp.arange(T)[:, None]
    offset = S - T  # end-aligned causal (matches tril(..., k=S-T))

    def body(carry, j):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, j * bs, bs, axis=2).astype(dtype)
        vb = jax.lax.dynamic_slice_in_dim(v, j * bs, bs, axis=2).astype(dtype)
        kv_pos = j * bs + jnp.arange(bs)[None, :]
        valid = kv_pos < S
        if causal:
            valid = valid & (q_pos + offset >= kv_pos)
        valid = valid[None, None]
        if key_mask is not None:
            kmb = jax.lax.dynamic_slice_in_dim(key_mask, j * bs, bs, axis=1)
            valid = valid & (kmb > 0)[:, None, None, :]
        acc_scale = None
        if dropout is not None:
            import os
            dkey, rate = dropout
            if os.environ.get("MXTPU_DROPOUT_RNG") == "threefry":
                keep = jax.random.bernoulli(jax.random.fold_in(dkey, j),
                                            1.0 - rate, (B, H, T, bs))
            else:
                from ..numpy_extension import _keep_bits_at
                ii = jax.lax.broadcasted_iota
                # two 32-bit words, not one flat index: a flat
                # B·H·T·Sp int32 wraps at 2^32 in the long-context
                # regime and ALIASES dropout masks across (b,h) /
                # distant chunks. (b,h,t) is the high word, the key
                # position the low word — the pair is exact for any
                # B·H·T < 2^31 and S < 2^31.
                bht = (ii(jnp.int32, (B, H, T, bs), 0) * H
                       + ii(jnp.int32, (B, H, T, bs), 1)) * T \
                    + ii(jnp.int32, (B, H, T, bs), 2)
                spos = j * bs + ii(jnp.int32, (B, H, T, bs), 3)
                keep = _keep_bits_at(dkey, spos, 1.0 - rate, idx_hi=bht)
            acc_scale = jnp.where(keep, 1.0 / (1.0 - rate), 0.0) \
                .astype(dtype)
        m, l, acc = _online_block(qf, kb, vb, m, l, acc, scale, valid,
                                  acc_scale)
        return (m, l, acc), None

    m0 = jnp.full((B, H, T, 1), jnp.finfo(dtype).min, dtype=dtype)
    l0 = jnp.zeros((B, H, T, 1), dtype=dtype)
    acc0 = jnp.zeros((B, H, T, D), dtype=dtype)
    (_, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), jnp.arange(nb))
    return (acc / jnp.maximum(l, jnp.finfo(dtype).tiny)).astype(q.dtype)


def _dropout_keep(key, shape, rate: float):
    """Keep-multiplier for attention-prob dropout: counter-based bits by
    default; MXTPU_DROPOUT_RNG=threefry switches to jax.random.bernoulli —
    the SAME escape hatch npx.dropout honors, so an RNG A/B experiment
    flips every dropout site in the model at once."""
    import os
    if os.environ.get("MXTPU_DROPOUT_RNG") == "threefry":
        keep = jax.random.bernoulli(key, 1.0 - rate, shape)
    else:
        from ..numpy_extension import _cheap_keep_mask
        keep = _cheap_keep_mask(key, shape, 1.0 - rate)
    return jnp.where(keep, 1.0 / (1.0 - rate), 0.0)


def _xla_attention(q, k, v, causal: bool, scale: float,
                   layout: str = "bhtd", key_mask=None, dropout=None):
    """Small-T attention as plain XLA einsums in the STORAGE dtype (bf16
    feeds the MXU at full rate; scores/softmax accumulate in f32 via
    preferred_element_type). At T < _MIN_KERNEL_LEN the (T,S) matrix is KBs
    and XLA's fusion beats the Pallas kernel's per-grid-cell overhead.
    Rows with no visible key — causal T>S, or fully key-masked — are 0 on
    EVERY path (einsum, chunked, kernel). ``layout`` is "bhtd" or "bthd" —
    one implementation for both entries so the semantics can't drift.

    ``key_mask``: optional (B, S) 1/0 padding mask — masked keys get a
    -1e30 score bias (the BERT convention). ``dropout``: optional
    (key, rate) applied to the normalized probabilities (the reference
    convention; at this path's small T the probs are materialized anyway)."""
    if layout == "bhtd":
        T, S = q.shape[2], k.shape[2]
        qk, pv = "bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd"
        row = jnp.arange(T)[:, None]            # broadcasts over (..., T, D)
    else:
        T, S = q.shape[1], k.shape[1]
        qk, pv = "bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd"
        row = jnp.arange(T)[:, None, None]      # broadcasts over (T, H, D)
    s = jnp.einsum(qk, q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, S), dtype=bool), k=S - T)
        s = jnp.where(mask[None, None], s, jnp.finfo(jnp.float32).min)
    if key_mask is not None:
        bias = (1.0 - key_mask[:, None, None, :].astype(jnp.float32)) * -1e30
        s = s + bias
    p = jax.nn.softmax(s, axis=-1)
    if dropout is not None:
        dkey, rate = dropout
        p = p * _dropout_keep(dkey, p.shape, rate)
    o = jnp.einsum(pv, p.astype(q.dtype), v,
                   preferred_element_type=jnp.float32)
    if causal and T > S:
        o = o * (row >= T - S)
    if key_mask is not None:
        # fully-masked rows: softmax over all -1e30 is uniform garbage;
        # zero them so short (einsum) and long (chunked) sequences agree
        has_key = (jnp.sum(key_mask, axis=-1) > 0)[:, None, None, None]
        o = o * has_key
    return o.astype(q.dtype)


def _fallback(q, k, v, causal: bool, scale: float):
    T, S = q.shape[2], k.shape[2]
    if T * S <= _XLA_PATH_MAX_SCORE_ELEMS:
        if on_tpu():
            return _xla_attention(q, k, v, causal, scale)
        return _jnp_reference(q, k, v, causal, scale)
    return _chunked_reference(q, k, v, causal, scale)


def _pallas_forward(q, k, v, causal: bool, scale: float,
                    with_lse: bool = False):
    from jax.experimental import pallas as pl

    B, H, T, D = q.shape
    S = k.shape[2]
    bq, Tp = _choose_block(T)
    bk, Sp = _choose_block(S)
    Dp = _pad_head_dim(D)
    qp = _pad4(q, Tp, Dp)
    kp = _pad4(k, Sp, Dp)
    vp = _pad4(v, Sp, Dp)
    kv_pad = Sp != S
    offset = S - T  # end-aligned causal; _use_pallas rejects causal T > S
    grid = (B, H, Tp // bq)
    nkv = -(-S // bk)  # blocks fully past S are never visited

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref):
        qi = pl.program_id(2)
        qb = q_ref[0, 0]  # (bq, Dp) — storage dtype feeds the MXU directly
        m = jnp.full((bq, 1), jnp.finfo(jnp.float32).min, jnp.float32)
        l = jnp.zeros((bq, 1), jnp.float32)
        acc = jnp.zeros((bq, Dp), jnp.float32)

        def body(j, carry):
            m, l, acc = carry
            kb = k_ref[0, 0, pl.dslice(j * bk, bk), :]
            vb = v_ref[0, 0, pl.dslice(j * bk, bk), :]
            s = _dot_nt(qb, kb) * scale  # (bq, bk) f32 accum
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            if causal:
                q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                           (bq, bk), 0)
                s = jnp.where(q_pos + offset >= k_pos, s,
                              jnp.finfo(jnp.float32).min)
            if kv_pad:
                s = jnp.where(k_pos < S, s, jnp.finfo(jnp.float32).min)
            m_chunk = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_chunk)
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * corr + _dot_f32(p.astype(vb.dtype), vb)
            return m_new, l_new, acc_new

        upper = jnp.int32(nkv)
        if causal:
            # skip fully-masked kv blocks: last key for this q block is
            # (qi+1)*bq - 1 + offset (int32 math: x64 promotion recurses
            # inside pallas traces)
            upper = jnp.minimum(
                upper,
                jax.lax.div((qi + jnp.int32(1)) * jnp.int32(bq)
                            + jnp.int32(offset + bk - 1), jnp.int32(bk)))
            upper = jnp.maximum(upper, jnp.int32(0))
        m, l, acc = jax.lax.fori_loop(jnp.int32(0), upper, body, (m, l, acc))
        l = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
        # log-sum-exp residual for the backward kernels (flash bwd needs
        # p = exp(s - lse) recomputed per block, never the (T,S) matrix).
        # Padded query rows get lse = 0, NOT m+log(l) ≈ -3.4e38: the
        # backward computes p = exp(0 - lse) for their zero q rows and a
        # huge negative lse would make p = inf (then inf·0 = NaN in ds)
        lse_val = m + jnp.log(l)
        if Tp != T:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            lse_val = jnp.where(qpos < T, lse_val, 0.0)
        lse_ref[0, 0] = lse_val

    # native 4D blocks: no (B*H, T, D) reshape — XLA was inserting real
    # copies around the custom calls for the relayout (~9 ms/step on the
    # GPT-2 bench before this)
    with jax.enable_x64(False):
        out, lse = pl.pallas_call(
            kernel,
            out_shape=[jax.ShapeDtypeStruct((B, H, Tp, Dp), q.dtype),
                       jax.ShapeDtypeStruct((B, H, Tp, 1), jnp.float32)],
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, Dp), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, Sp, Dp), lambda b, h, i: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, Sp, Dp), lambda b, h, i: (b, h, 0, 0)),
            ],
            out_specs=[pl.BlockSpec((1, 1, bq, Dp),
                                    lambda b, h, i: (b, h, i, 0)),
                       pl.BlockSpec((1, 1, bq, 1),
                                    lambda b, h, i: (b, h, i, 0))],
        )(qp, kp, vp)
    out = out[:, :, :T, :D]
    if with_lse:
        # RAW (B, H, Tp, 1) f32, straight from the kernel: a reshape/slice
        # round-trip here made XLA relayout it before the backward kernel —
        # 12 × 0.22 ms of copies on the GPT-2 step
        return out, lse
    return out


def _pallas_backward(q, k, v, o, lse, do, causal: bool, scale: float):
    """Flash-attention backward, ONE Pallas kernel computing dq, dk and dv
    in a single sweep over (q-block, kv-block) pairs — p = exp(s − lse) and
    ds are recomputed ONCE per pair (the r4 two-kernel design computed them
    twice; at D=64 the kernels are VPU-bound on exactly those elementwise
    passes, so this halves the backward's bottleneck — measured 25.8 →
    ~13 ms on the GPT-2 step). dq accumulates across kv grid cells in a
    VMEM-resident f32 block: its out index map is invariant over the
    innermost (kv) grid dim, so Mosaic keeps the buffer live and writes HBM
    once per (b,h) row. The (T,S) score matrix never exists in HBM.

    Padding correctness: q/k/v/o/do are zero-padded, lse zero-padded. Padded
    kv columns are masked by position (p = 0). Padded *query* rows need no
    mask: their do rows are zero, so dv += pᵀ·do and ds = p·(do·vᵀ − Σdo·o)
    both vanish identically, and their dq rows are sliced away.

    Falls back to the two-kernel design when the full-T dq block would not
    fit VMEM (very long sequences)."""
    from jax.experimental import pallas as pl

    B, H, T, D = q.shape
    S = k.shape[2]
    bq, Tp = _choose_block(T)
    bk, Sp = _choose_block(S)
    Dp = _pad_head_dim(D)
    offset = S - T
    kv_pad = Sp != S
    nkv = -(-S // bk)
    nq = -(-T // bq)

    qp = _pad4(q, Tp, Dp)
    kp = _pad4(k, Sp, Dp)
    vp = _pad4(v, Sp, Dp)
    op = _pad4(o, Tp, Dp)
    dop = _pad4(do, Tp, Dp)
    # lse arrives RAW from the forward kernel: (B, H, Tp, 1) f32, padded
    # rows already sanitized to 0 there (p = exp(0-0) = 1 is harmless since
    # the matching do rows are zero). delta = Σ_d do·o is computed INSIDE
    # the kernels from o — the separate XLA reduce produced a (B,H,T,1)
    # tensor whose relayout copy cost 12 × 0.22 ms on the GPT-2 step.
    lser = lse

    neg_inf = jnp.finfo(jnp.float32).min

    def dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref):
        qi = pl.program_id(2)
        qb = q_ref[0, 0]
        dob = do_ref[0, 0]
        lseb = lse_ref[0, 0]       # (bq, 1)
        dlb = jnp.sum(dob.astype(jnp.float32) * o_ref[0, 0].astype(jnp.float32),
                      axis=-1, keepdims=True)
        acc = jnp.zeros((bq, Dp), jnp.float32)

        def body(j, acc):
            kb = k_ref[0, 0, pl.dslice(j * bk, bk), :]
            vb = v_ref[0, 0, pl.dslice(j * bk, bk), :]
            s = _dot_nt(qb, kb) * scale
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            if causal:
                q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                           (bq, bk), 0)
                s = jnp.where(q_pos + offset >= k_pos, s, neg_inf)
            if kv_pad:
                s = jnp.where(k_pos < S, s, neg_inf)
            p = jnp.exp(s - lseb)
            dp = _dot_nt(dob, vb)
            ds = p * (dp - dlb) * scale
            return acc + _dot_f32(ds.astype(kb.dtype), kb)

        upper = jnp.int32(nkv)
        if causal:
            upper = jnp.minimum(
                upper,
                jax.lax.div((qi + jnp.int32(1)) * jnp.int32(bq)
                            + jnp.int32(offset + bk - 1), jnp.int32(bk)))
            upper = jnp.maximum(upper, jnp.int32(0))
        acc = jax.lax.fori_loop(jnp.int32(0), upper, body, acc)
        dq_ref[0, 0] = acc.astype(dq_ref.dtype)

    def dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                   dk_ref, dv_ref):
        kj = pl.program_id(2)
        kb = k_ref[0, 0]   # (bk, Dp)
        vb = v_ref[0, 0]
        dk = jnp.zeros((bk, Dp), jnp.float32)
        dv = jnp.zeros((bk, Dp), jnp.float32)

        def body(i, carry):
            dk, dv = carry
            qb = q_ref[0, 0, pl.dslice(i * bq, bq), :]
            dob = do_ref[0, 0, pl.dslice(i * bq, bq), :]
            lseb = lse_ref[0, 0, pl.dslice(i * bq, bq), :]   # (bq, 1)
            ob = o_ref[0, 0, pl.dslice(i * bq, bq), :]
            dlb = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                          axis=-1, keepdims=True)
            s = _dot_nt(qb, kb) * scale
            k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            if causal:
                q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                          (bq, bk), 0)
                s = jnp.where(q_pos + offset >= k_pos, s, neg_inf)
            if kv_pad:
                s = jnp.where(k_pos < S, s, neg_inf)
            p = jnp.exp(s - lseb)          # (bq, bk)
            pb = p.astype(dob.dtype)
            dv = dv + _dot_tn(pb, dob)
            dp = _dot_nt(dob, vb)
            ds = p * (dp - dlb) * scale
            dk = dk + _dot_tn(ds.astype(qb.dtype), qb)
            return dk, dv

        lower = jnp.int32(0)
        if causal:
            # first query that can see this kv block: q >= kj*bk - offset
            lower = jnp.maximum(
                lower, jax.lax.div(kj * jnp.int32(bk) - jnp.int32(offset),
                                   jnp.int32(bq)))
            lower = jnp.minimum(lower, jnp.int32(nq))
        dk, dv = jax.lax.fori_loop(lower, jnp.int32(nq), body, (dk, dv))
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv.astype(dv_ref.dtype)

    def fused_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                     dk_ref, dv_ref, dq_ref):
        """dk/dv for this kv block + dq contributions for every q block —
        one p/ds computation per (i, j) pair."""
        kj = pl.program_id(2)

        @pl.when(kj == 0)
        def _init():  # dq persists in VMEM across the kv grid cells
            dq_ref[0, 0] = jnp.zeros((Tp, Dp), jnp.float32)

        kb = k_ref[0, 0]   # (bk, Dp)
        vb = v_ref[0, 0]
        dk = jnp.zeros((bk, Dp), jnp.float32)
        dv = jnp.zeros((bk, Dp), jnp.float32)

        def body(i, carry):
            dk, dv = carry
            qb = q_ref[0, 0, pl.dslice(i * bq, bq), :]
            dob = do_ref[0, 0, pl.dslice(i * bq, bq), :]
            lseb = lse_ref[0, 0, pl.dslice(i * bq, bq), :]   # (bq, 1)
            ob = o_ref[0, 0, pl.dslice(i * bq, bq), :]
            dlb = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                          axis=-1, keepdims=True)
            s = _dot_nt(qb, kb) * scale
            k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            if causal:
                q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                          (bq, bk), 0)
                s = jnp.where(q_pos + offset >= k_pos, s, neg_inf)
            if kv_pad:
                s = jnp.where(k_pos < S, s, neg_inf)
            p = jnp.exp(s - lseb)          # (bq, bk)
            pb = p.astype(dob.dtype)
            dv = dv + _dot_tn(pb, dob)
            dp = _dot_nt(dob, vb)
            ds = p * (dp - dlb) * scale
            dsb = ds.astype(qb.dtype)
            dk = dk + _dot_tn(dsb, qb)
            cur = dq_ref[0, 0, pl.dslice(i * bq, bq), :]
            # mxlint: disable=MX003 -- Pallas output ref: in-kernel
            # accumulation IS the mechanism, not a leak
            dq_ref[0, 0, pl.dslice(i * bq, bq), :] = cur + _dot_f32(dsb, kb)
            return dk, dv

        lower = jnp.int32(0)
        if causal:
            lower = jnp.maximum(
                lower, jax.lax.div(kj * jnp.int32(bk) - jnp.int32(offset),
                                   jnp.int32(bq)))
            lower = jnp.minimum(lower, jnp.int32(nq))
        dk, dv = jax.lax.fori_loop(lower, jnp.int32(nq), body, (dk, dv))
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv.astype(dv_ref.dtype)

    # q + do + o (storage dtype) + f32 dq + lse all live per cell; keep a
    # conservative VMEM budget before falling back to the two-kernel sweep
    fused_vmem = Tp * (4 * Dp + 3 * Dp * q.dtype.itemsize + 4) \
        + 2 * bk * Dp * k.dtype.itemsize
    use_fused = fused_vmem <= 6 * 1024 * 1024
    assert lse.shape == (B, H, Tp, 1), lse.shape
    # The two-kernel sweep holds whole-sequence blocks, double-buffered:
    # dkv keeps q, do, o (Tp x Dp each) and lse, whose (Tp, 1) f32 block
    # pads to 128 lanes in VMEM; dq keeps k and v. That passes the
    # compiler's default scoped-VMEM limit (16 MiB) already at T=16k, so
    # these two calls ask for what they need.
    from jax.experimental.pallas import tpu as pltpu
    isz = q.dtype.itemsize
    two_kernel_vmem = 2 * max(3 * Tp * Dp * isz + Tp * 128 * 4,
                              2 * Sp * Dp * isz) + (8 << 20)
    two_kernel_params = pltpu.CompilerParams(
        vmem_limit_bytes=two_kernel_vmem)

    with jax.enable_x64(False):
        if use_fused:
            dk, dv, dqf = pl.pallas_call(
                fused_kernel,
                out_shape=[jax.ShapeDtypeStruct((B, H, Sp, Dp), k.dtype),
                           jax.ShapeDtypeStruct((B, H, Sp, Dp), v.dtype),
                           jax.ShapeDtypeStruct((B, H, Tp, Dp), jnp.float32)],
                grid=(B, H, Sp // bk),
                in_specs=[
                    pl.BlockSpec((1, 1, Tp, Dp), lambda b, h, j: (b, h, 0, 0)),
                    pl.BlockSpec((1, 1, bk, Dp), lambda b, h, j: (b, h, j, 0)),
                    pl.BlockSpec((1, 1, bk, Dp), lambda b, h, j: (b, h, j, 0)),
                    pl.BlockSpec((1, 1, Tp, Dp), lambda b, h, j: (b, h, 0, 0)),
                    pl.BlockSpec((1, 1, Tp, Dp), lambda b, h, j: (b, h, 0, 0)),
                    pl.BlockSpec((1, 1, Tp, 1), lambda b, h, j: (b, h, 0, 0)),
                ],
                out_specs=[pl.BlockSpec((1, 1, bk, Dp),
                                        lambda b, h, j: (b, h, j, 0)),
                           pl.BlockSpec((1, 1, bk, Dp),
                                        lambda b, h, j: (b, h, j, 0)),
                           pl.BlockSpec((1, 1, Tp, Dp),
                                        lambda b, h, j: (b, h, 0, 0))],
            )(qp, kp, vp, dop, op, lser)
            dq = dqf.astype(q.dtype)
        else:
            dq = pl.pallas_call(
                dq_kernel,
                out_shape=jax.ShapeDtypeStruct((B, H, Tp, Dp), q.dtype),
                grid=(B, H, Tp // bq),
                in_specs=[
                    pl.BlockSpec((1, 1, bq, Dp), lambda b, h, i: (b, h, i, 0)),
                    pl.BlockSpec((1, 1, Sp, Dp), lambda b, h, i: (b, h, 0, 0)),
                    pl.BlockSpec((1, 1, Sp, Dp), lambda b, h, i: (b, h, 0, 0)),
                    pl.BlockSpec((1, 1, bq, Dp), lambda b, h, i: (b, h, i, 0)),
                    pl.BlockSpec((1, 1, bq, Dp), lambda b, h, i: (b, h, i, 0)),
                    pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
                ],
                out_specs=pl.BlockSpec((1, 1, bq, Dp),
                                       lambda b, h, i: (b, h, i, 0)),
                compiler_params=two_kernel_params,
            )(qp, kp, vp, dop, op, lser)
            dk, dv = pl.pallas_call(
                dkv_kernel,
                out_shape=[jax.ShapeDtypeStruct((B, H, Sp, Dp), k.dtype),
                           jax.ShapeDtypeStruct((B, H, Sp, Dp), v.dtype)],
                grid=(B, H, Sp // bk),
                in_specs=[
                    pl.BlockSpec((1, 1, Tp, Dp), lambda b, h, j: (b, h, 0, 0)),
                    pl.BlockSpec((1, 1, bk, Dp), lambda b, h, j: (b, h, j, 0)),
                    pl.BlockSpec((1, 1, bk, Dp), lambda b, h, j: (b, h, j, 0)),
                    pl.BlockSpec((1, 1, Tp, Dp), lambda b, h, j: (b, h, 0, 0)),
                    pl.BlockSpec((1, 1, Tp, Dp), lambda b, h, j: (b, h, 0, 0)),
                    pl.BlockSpec((1, 1, Tp, 1), lambda b, h, j: (b, h, 0, 0)),
                ],
                out_specs=[pl.BlockSpec((1, 1, bk, Dp),
                                        lambda b, h, j: (b, h, j, 0)),
                           pl.BlockSpec((1, 1, bk, Dp),
                                        lambda b, h, j: (b, h, j, 0))],
                compiler_params=two_kernel_params,
            )(qp, kp, vp, dop, op, lser)
    dq = dq[:, :, :T, :D]
    dk = dk[:, :, :S, :D]
    dv = dv[:, :, :S, :D]
    return dq, dk, dv


def _use_pallas(q, k, causal: bool) -> bool:
    """Kernel eligibility. With pad-to-block generality this is nearly
    always true on TPU; the exceptions are explicit, not alignment traps:
    tiny T/S (dispatch-bound, e.g. single-token decode — chunked fallback is
    exact and O(T·S) is KBs), head dim > 256 (no MXU tiling), causal with
    more queries than keys (ill-posed rows), exotic dtypes."""
    if not on_tpu():
        return False
    B, H, T, D = q.shape
    S = k.shape[2]
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if D > 256:
        return False
    if causal and T > S:
        return False
    # the forward kernel keeps whole-sequence k and v blocks in VMEM,
    # double-buffered, under the compiler's default 16 MiB scoped limit
    if 4 * _choose_block(S)[1] * _pad_head_dim(D) * q.dtype.itemsize \
            > 12 * 1024 * 1024:
        return False
    return T >= _MIN_KERNEL_LEN and S >= _MIN_KERNEL_LEN


def _over_mesh(kernel, *arrays):
    """Run ``kernel`` (a Pallas forward or backward over (B, H, ., .)
    arrays) on the current mesh. GSPMD cannot partition a Mosaic kernel
    ("Mosaic kernels cannot be automatically partitioned"), so under a mesh
    the call is mapped by hand: the batch over 'dp', every other axis
    replicated. Attention is independent per (batch, head), so each shard
    is the whole computation for its rows."""
    from ..parallel.mesh import P, current_mesh, shard_map
    mesh = current_mesh()
    if mesh is None or dict(mesh.shape).get("dp", 1) == 1:
        return kernel(*arrays)
    spec = P("dp")
    return shard_map(kernel, mesh, in_specs=(spec,) * len(arrays),
                     out_specs=spec, check_vma=False)(*arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = False, scale: Optional[float] = None):
    """Fused scaled-dot-product attention. q/k/v: (B, H, T, D).

    Pallas kernel on TPU (any T/S via pad-to-block); chunked online-softmax
    fallback elsewhere. Causal with T != S is end-aligned (decode
    convention); causal query rows with no visible key (T > S) return 0 on
    every path. GQA: call with kv heads already repeated (see models.llama)."""
    s = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if _use_pallas(q, k, causal):
        return _over_mesh(
            lambda q, k, v: _pallas_forward(q, k, v, causal, s), q, k, v)
    return _fallback(q, k, v, causal, s)


def _fwd(q, k, v, causal, scale):
    s = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if _use_pallas(q, k, causal):
        o, lse = _over_mesh(
            lambda q, k, v: _pallas_forward(q, k, v, causal, s,
                                            with_lse=True), q, k, v)
        return o, (q, k, v, o, lse)
    return _fallback(q, k, v, causal, s), (q, k, v, None, None)


def _bwd(causal, scale, res, g):
    q, k, v, o, lse = res
    s = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if o is not None:
        return _over_mesh(
            lambda q, k, v, o, lse, g: _pallas_backward(
                q, k, v, o, lse, g, causal, s), q, k, v, o, lse, g)

    def ref(q, k, v):
        return _fallback(q, k, v, causal, s)

    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)


def flash_attention_bthd(q, k, v, causal: bool = False,
                         scale: Optional[float] = None, key_mask=None,
                         dropout=None):
    """(B, T, H, D)-layout attention entry — the layout projections produce.
    On the XLA path the einsums contract directly in BTHD, so the six
    per-layer (B,T,H,D)<->(B,H,T,D) transposes ("data formatting" in the
    profile, ~1.4 ms/step on BERT-base) never exist; the Pallas kernel path
    transposes around the kernel (its blocks are (T,D) tiles).

    ``key_mask``: optional (B, S) 1/0 padding mask. ``dropout``: optional
    (key, rate) attention-prob dropout. Either routes off the Pallas kernel
    (no mask/RNG inputs there): small T takes the einsum path, long T takes
    the chunked path — which draws its dropout bits per chunk from the
    position-indexed generator, so the O(T·block) memory bound holds even
    when training with dropout."""
    B, T, H, D = q.shape
    S = k.shape[1]
    s = scale if scale is not None else 1.0 / (D ** 0.5)
    bhtd = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    if key_mask is None and dropout is None \
            and _use_pallas(bhtd(q), bhtd(k), causal):
        return bhtd(flash_attention(bhtd(q), bhtd(k), bhtd(v), causal, s))
    if T * S > _XLA_PATH_MAX_SCORE_ELEMS:
        return bhtd(_chunked_reference(bhtd(q), bhtd(k), bhtd(v), causal, s,
                                       key_mask=key_mask, dropout=dropout))
    return _xla_attention(q, k, v, causal, s, layout="bthd",
                          key_mask=key_mask, dropout=dropout)


def attention(q, k, v, causal: bool = False, scale: Optional[float] = None):
    """NDArray-level fused attention op (frontend entry)."""
    from ..ndarray import invoke_jnp
    return invoke_jnp(
        lambda a, b, c: flash_attention(a, b, c, causal, scale), (q, k, v), {},
        name="flash_attention")
