"""Linear attention with a per-head decay (Lightning Attention-2,
arXiv:2401.04658) over a recurrent state: the chunked scan that prefills and,
at one token, the update that decodes.

    S_t = lam_h * S_{t-1} + k_t^T v_t          (state [hd, hd] a head, float32)
    o_t = q_t S_t                              (the caller scales q)

There is no normaliser and no softmax: what a token reads of the past is the
state, ``hd * hd`` float32 values a head whatever the depth.

The scan walks the ``T`` new positions in blocks of ``LINEAR_BLOCK``. Inside a
block the products are the masked quadratic form ``((Q K^T) * D) V`` with
``D[i, j] = lam ** (i - j)`` for ``j <= i``; what the blocks before it gave
arrives through the state, ``lam ** (i + 1) * q_i S``; and the state moves on by
``lam ** n S + sum_j lam ** (n - 1 - j) k_j^T v_j``. ``n`` is the number of
the block's positions that are real: a row whose chunk is padded (a bucketed
last chunk of a prompt) carries ``valid`` real positions, and the padding
neither decays the state nor adds to it. At ``T = 1`` the block is one
position and the same code is the decode update.

The state's own products (``q S`` and ``k^T v``) run at float32 with
``precision="highest"``: a TPU otherwise rounds a float32 operand to bfloat16,
and the state is the whole memory of the sequence. The quadratic form inside a
block takes its operands in the type they come in (bfloat16 when served) and
accumulates in float32, as attention does.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["LINEAR_BLOCK", "decay_slopes", "lightning_attention",
           "lightning_attention_slots"]

#: positions a block of the scan holds: one MXU tile of scores on the v5e
LINEAR_BLOCK = 128

_HI = jax.lax.Precision.HIGHEST


def decay_slopes(heads: int, layer: int, layers: int):
    """The decay rates of one layer, ``[heads]`` float32, by Lightning
    Attention-2's rule: ALiBi's geometric slopes ``2 ** (-8 (h + 1) / H)``
    scaled by ``1 - layer / (layers - 1) + 1e-5``, so that deeper layers
    forget more slowly. A position's weight falls by ``exp(-slope)`` a step."""
    start = 2.0 ** (-(2.0 ** -(math.log2(heads) - 3)))
    base = jnp.asarray([start ** (h + 1) for h in range(heads)], jnp.float32)
    return base * jnp.float32(1.0 - layer / max(layers - 1, 1) + 1e-5)


@jax.named_scope("mx.linear_attn")
def lightning_attention(q, k, v, state, slopes, valid):
    """``q, k, v`` [B, H, T, hd] (``q`` already scaled), ``state`` [B, H, hd,
    hd] float32 as the positions before these left it, ``slopes`` [H],
    ``valid`` [B] real positions of the T. Returns ``(o [B, H, T, hd] in q's
    type, new state)``."""
    B, H, T, hd = q.shape
    c = min(T, LINEAR_BLOCK)
    pad = -T % c
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for x in (q, k, v))
    n_blocks = (T + pad) // c
    blocks = lambda x: x.reshape(B, H, n_blocks, c, hd).transpose(2, 0, 1, 3, 4)
    slopes = slopes.astype(jnp.float32)
    i = jnp.arange(c, dtype=jnp.float32)
    # D[h, i, j] = lam_h ** (i - j) below the diagonal, 0 above it
    gap = i[:, None] - i[None, :]
    intra = jnp.where(gap >= 0,
                      jnp.exp(-slopes[:, None, None] * jnp.maximum(gap, 0)),
                      0.0)                                        # [H, c, c]
    carry_in = jnp.exp(-slopes[:, None] * (i + 1.0))              # [H, c]
    valid = jnp.asarray(valid, jnp.int32)

    def block(S, inputs):
        qb, kb, vb, b0 = inputs                                   # [B,H,c,hd]
        n = jnp.clip(valid - b0, 0, c)                            # [B]
        s = jnp.einsum("bhid,bhjd->bhij", qb, kb,
                       preferred_element_type=jnp.float32) * intra
        o = jnp.einsum("bhij,bhjd->bhid", s.astype(vb.dtype), vb,
                       preferred_element_type=jnp.float32)
        with jax.named_scope("mx.state_update"):
            o = o + jnp.einsum("bhid,bhde->bhie", qb.astype(jnp.float32), S,
                               precision=_HI) * carry_in[None, :, :, None]
            # weight of position j in the state the block leaves:
            # lam ** (n - 1 - j) for the n real positions, 0 for the padding
            nf = n.astype(jnp.float32)[:, None, None]             # [B,1,1]
            left = nf - 1.0 - i[None, None, :]                    # [B,1,c]
            w = jnp.where(left >= 0,
                          jnp.exp(-slopes[None, :, None]
                                  * jnp.maximum(left, 0.0)), 0.0)  # [B,H,c]
            kw = kb.astype(jnp.float32) * w[..., None]
            S = S * jnp.exp(-slopes[None, :] * nf[:, :, 0])[..., None, None] \
                + jnp.einsum("bhjd,bhje->bhde", kw, vb.astype(jnp.float32),
                             precision=_HI)
        return S, o.astype(q.dtype)

    starts = jnp.arange(n_blocks, dtype=jnp.int32) * c
    state, o = jax.lax.scan(block, state.astype(jnp.float32),
                            (blocks(q), blocks(k), blocks(v), starts))
    o = o.transpose(1, 2, 0, 3, 4).reshape(B, H, n_blocks * c, hd)
    return o[:, :, :T], state


def lightning_attention_slots(q, k, v, state_pool, slots, pos, slopes, valid):
    """:func:`lightning_attention` over a pool of per-slot states
    ``[slots + 1, H, hd, hd]`` (the last is the sink, where rows that serve
    no request point): row ``b`` continues the state of ``slots[b]``, or
    starts from zero where its positions start at ``pos[b] == 0`` (a slot
    taken over from a retired request needs no clearing pass). Returns
    ``(o, new pool)``."""
    with jax.named_scope("mx.linear_attn"), jax.named_scope("mx.state_update"):
        state = jnp.where((jnp.asarray(pos) == 0)[:, None, None, None], 0.0,
                          state_pool[slots])
    o, state = lightning_attention(q, k, v, state, slopes, valid)
    with jax.named_scope("mx.linear_attn"), jax.named_scope("mx.state_update"):
        state_pool = state_pool.at[slots].set(state.astype(state_pool.dtype))
    return o, state_pool
