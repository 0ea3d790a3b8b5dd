"""The paged read of a prefill chunk or bucket as one Pallas TPU kernel.

``models/llama._walk_pages`` reads a row's pages a block of ``KV_BLOCK``
keys at a time under a running softmax. In XLA that loop carries its float32
state, the size of the whole chunk's queries (Command A+: 64 MiB), through
HBM in every trip, and the trip is paid in that state, not in the 0.5 MiB of
keys and values it reads (PERF.md section 6, PR 37). Here the state stays in
VMEM for the whole walk:

- one grid cell a (row, KV head, tile of ``Tq`` query positions); the
  ``rep`` query heads that share the KV head are stacked with the tile's
  positions, so a product has ``rep x Tq`` rows and a block's bytes serve
  all of them;
- the block table, each row's first column and the cells' trip bounds are
  prefetched scalars; a block is DMA'd page by page out of the pools as they
  are stored (``[pages + 1, page_size, G * hd]``: head g is the lane slice
  ``[g * hd, (g + 1) * hd)``, whole tiles since ``hd % 128 == 0``), two
  buffers deep;
- a tile walks from the block of the earliest column its first query sees
  (under a window; else 0) to that of the deepest its last query sees, so
  the chunk's causal triangle is not computed and masked, and only a block
  that crosses a query's edge is masked at all.

The arithmetic is the head-split form's as the chip makes it: scores are
products of ``q`` and ``k`` as stored, summed in float32 and then scaled;
the softmax weights enter the second product in the values' dtype; masked
scores are ``-inf``, and under a window a row whose maximum is still
``-inf`` does not subtract it from itself. A row that sees no key, an
inactive one among them (its table starts at the sink: it walks nothing),
reads zeros.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_prefill_attention", "tile_rows", "trip_keys"]

# rows (query heads of a KV head x positions) of one cell's products: q is
# 1 MiB of bf16 at hd 128, the accumulator 2 MiB, the running max and sum 2
# MiB each (lane-replicated). Command A+'s chunk read as fast at 2,048 and
# 8,192 (PERF.md section 6, PR 37)
ROWS = 4096
LANES = 128
# score elements (rows x keys) of one trip, 4 MiB of float32: a trip takes
# as many blocks as stay within it. On the v5e Command A+'s 4,096 rows walked
# 128, 256 and 512 keys a trip at 49-56, 71-84 and 59-71 % of the peak, at
# their deepest; EvaByte's 1,024 rows 128, 256, 512 and 1,024 at 32, 42, 47
# and 45 % (PERF.md section 6, PR 37). A constant, not a knob.
SCORES = 1 << 20
# and at most 1,024 keys (EvaByte's best): a shorter bucket's rows would
# otherwise walk the whole table in one trip, most of it past their depth
MAX_KEYS = 1024


def tile_rows(rep: int, T: int) -> int:
    """Query positions a cell takes: a power of two (the mask finds a row's
    position as ``row & (Tq - 1)``), at least 16 (a bfloat16 tile of rows),
    no more than ``T`` needs, and ``rep x Tq`` within ``ROWS``."""
    tq = 16
    while 2 * tq * rep <= ROWS and tq < T:
        tq *= 2
    return tq


def trip_keys(M: int, block: int, L: int) -> int:
    """Keys a trip walks for ``M`` rows over a table of ``L`` columns: whole
    blocks, doubled while the scores stay within ``SCORES``, the trip within
    ``MAX_KEYS`` and shorter than the table."""
    bk = block
    while 2 * bk * M <= SCORES and 2 * bk <= MAX_KEYS and bk < L:
        bk *= 2
    return bk


def _vmem_bytes(M: int, hd: int, bk: int, isz: int) -> int:
    """What one cell holds in VMEM: q and the output, double-buffered, in
    the served dtype; the float32 accumulator, max and sum; two buffers of
    K and V; a block's scores, weights and mask."""
    return (4 * M * hd * isz + M * hd * 4 + 2 * M * LANES * 4
            + 4 * bk * hd * isz + 4 * M * bk * 4)


def _lanes(x, n: int):
    """A lane-replicated ``[M, 128]`` value widened to ``n`` lanes."""
    return x if n == LANES else jnp.concatenate([x] * (n // LANES), axis=1)


def _kernel(table_ref, bounds_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, m_ref, l_ref, acc_ref, *, rep, Tq, nT, hd,
            ps, bpt, npages, L, window):
    b, g, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bk = bpt * ps
    M = rep * Tq
    cell = (b * nT + j) * 2
    lo, hi = bounds_ref[cell], bounds_ref[cell + 1]
    pos = pos_ref[b]
    lane0 = pl.multiple_of(g * hd, LANES)

    def dma(c, slot, start):
        """Start, or wait for, the DMAs of trip ``c`` into buffer ``slot``:
        its pages, one head's lanes of each, for K and for V (a loop, so
        that a trip of many pages traces as one)."""
        def page(i, carry):
            at = table_ref[b * npages + c * bpt + i]
            row = pl.multiple_of(i * ps, ps)
            for pool, buf, kv in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                cp = pltpu.make_async_copy(
                    pool.at[at, :, pl.ds(lane0, hd)],
                    buf.at[slot, pl.ds(row, ps), :], sems.at[slot, kv])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        jax.lax.fori_loop(0, bpt, page, 0)

    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    pl.when(lo < hi)(lambda: dma(lo, 0, True))

    # the columns the tile's first query sees at the deepest, and its last
    # (or pad) query's first under a window: a block inside both is unmasked
    t0 = j * Tq
    shallow = jnp.minimum(pos + t0, L - 1)
    late = pos + t0 + Tq - window if window is not None else 0
    scale = 1.0 / math.sqrt(hd)

    def trip(c, slot, masked):
        k = kbuf[slot]
        v = vbuf[slot]
        q = q_ref[0, 0].reshape(M, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            row = jax.lax.broadcasted_iota(jnp.int32, (M, bk), 0)
            col = c * bk + jax.lax.broadcasted_iota(jnp.int32, (M, bk), 1)
            qcol = pos + t0 + (row & (Tq - 1))
            vis = col <= jnp.minimum(qcol, L - 1)
            if window is not None:
                vis = vis & (col > qcol - window)
            s = jnp.where(vis, s, -jnp.inf)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        ref = (jnp.where(m_new == -jnp.inf, 0.0, m_new)
               if window is not None else m_new)
        p = jnp.exp(s - _lanes(ref, bk))
        alpha = jnp.exp(m_prev - ref)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * _lanes(alpha, hd) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    def body(c, carry):
        slot = (c - lo) % 2
        pl.when(c + 1 < hi)(lambda: dma(c + 1, 1 - slot, True))
        dma(c, slot, False)
        edge = c * bk + bk - 1 > shallow
        if window is not None:
            edge = edge | (c * bk < late)
        pl.when(edge)(lambda: trip(c, slot, True))
        pl.when(jnp.logical_not(edge))(lambda: trip(c, slot, False))
        return carry

    jax.lax.fori_loop(lo, hi, body, 0)
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)               # a row that saw no key
    o_ref[0, 0] = (acc_ref[...] / _lanes(l, hd)).reshape(
        rep, Tq, hd).astype(o_ref.dtype)


def _bounds(pos, active, L, T, Tq, nT, bk, window):
    """``[B * nT * 2]``: each cell's first trip and the trip past its last.
    A tile ends at the trip of the deepest column its last query sees and,
    under a window, starts at that of the earliest its first query sees; so
    no tile of an active row walks before the XLA loop's first block or past
    its trip count. An inactive row walks nothing."""
    j = jnp.arange(nT, dtype=jnp.int32)[None, :]
    pos = pos[:, None]
    deepest = jnp.minimum(pos + jnp.minimum(j * Tq + Tq, T) - 1, L - 1)
    hi = jnp.where(active[:, None], deepest // bk + 1, 0)
    if window is None:
        lo = jnp.zeros_like(hi)
    else:
        lo = jnp.minimum(
            jnp.maximum(pos + j * Tq - (window - 1), 0) // bk, hi)
    return jnp.stack([lo, hi], axis=-1).reshape(-1)


def paged_prefill_attention(qh, k_pages, v_pages, table, pos, L, active,
                            block, rep, window=None, interpret=False):
    """The read of ``models/llama._walk_pages`` in one kernel: query row t
    of batch row b, at column ``pos[b] + t``, attends the row's columns up
    to that one (under ``window``, the last ``window`` of them), and never
    past column ``L - 1``. ``table`` ``[B, pages]`` is the block table
    padded with sink pages to whole blocks of ``block`` keys; ``active``
    ``[B]`` is false for a row whose table starts at the sink. Returns
    ``[B, H, T, hd]`` in ``qh``'s dtype. ``interpret`` runs Pallas' TPU
    interpreter (the tests, off the chip)."""
    B, H, T, hd = qh.shape
    G, ps = H // rep, k_pages.shape[1]
    Tq = tile_rows(rep, T)
    nT = -(-T // Tq)
    M = rep * Tq
    # a trip walks whole blocks; the table ends in sink pages up to a trip
    bk = trip_keys(M, block, L)
    bpt = bk // ps
    table = jnp.pad(table, ((0, 0), (0, -table.shape[1] % bpt)),
                    constant_values=k_pages.shape[0] - 1)
    npages = table.shape[1]
    q = qh.astype(k_pages.dtype)
    if nT * Tq != T:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, nT * Tq - T), (0, 0)))
    q = q.reshape(B, G, rep, nT * Tq, hd)
    # 64-bit types off: the package enables x64, and Mosaic refuses the
    # int64 a Python int then becomes in an index map or a bound
    with jax.enable_x64(False):
        bounds = _bounds(jnp.asarray(pos, jnp.int32), active, L, T, Tq,
                         nT, bk, window).astype(jnp.int32)
        spec = pl.BlockSpec((1, 1, rep, Tq, hd),
                            lambda b, g, j, *_: (b, g, 0, j, 0))
        kernel = functools.partial(
            _kernel, rep=rep, Tq=Tq, nT=nT, hd=hd, ps=ps, bpt=bpt,
            npages=npages, L=L, window=window)
        isz = jnp.dtype(k_pages.dtype).itemsize
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(B, G, nT),
                in_specs=[spec, pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=spec,
                scratch_shapes=[
                    pltpu.VMEM((2, bk, hd), k_pages.dtype),
                    pltpu.VMEM((2, bk, hd), v_pages.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.VMEM((M, LANES), jnp.float32),
                    pltpu.VMEM((M, LANES), jnp.float32),
                    pltpu.VMEM((M, hd), jnp.float32),
                ]),
            out_shape=jax.ShapeDtypeStruct(q.shape, qh.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel"),
                vmem_limit_bytes=_vmem_bytes(M, hd, bk, isz) + (8 << 20)),
            interpret=(pltpu.InterpretParams() if interpret else False),
            name="paged_prefill_attention",
        )(table.reshape(-1).astype(jnp.int32), bounds,
          jnp.asarray(pos, jnp.int32), q, k_pages, v_pages)
    return out.reshape(B, H, nT * Tq, hd)[:, :, :T]
