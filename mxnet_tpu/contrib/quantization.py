"""Post-training INT8 quantization (reference python/mxnet/contrib/
quantization.py quantize_net; quantized kernels in
src/operator/quantization/).

TPU-native design: the reference rewrites the symbolic graph, inserting
quantize/dequantize nodes and replacing ops with int8 kernels
(quantize_graph_pass.cc:286). Here eligible layers (Dense, 2-D Conv) are
replaced by quantized wrapper blocks whose forward quantizes the activation
symmetrically to int8, runs the contraction on the MXU as int8×int8→int32
(``preferred_element_type=int32``), and rescales — per-output-channel weight
scales, per-tensor activation scale. Under ``hybridize()`` the whole
quantized forward compiles into one XLA executable, so the quantize /
matmul / rescale chain fuses.

Calibration:
- ``calib_mode='naive'``  — per-layer absolute-max of activations over the
  calibration set (reference _LayerOutputMinMaxCollector role).
- ``calib_mode='entropy'`` — KL-divergence-optimal clipping threshold from
  a 2048-bin histogram (reference _LayerHistogramCollector /
  get_optimal_threshold role).
- ``calib_mode='none'``   — dynamic quantization: the activation scale is
  computed in-graph per batch (an XLA reduction; static shapes, so it fuses
  cleanly — a TPU-friendly default the reference lacks).
"""
from __future__ import annotations

import re
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import MXNetError, logger
from ..gluon.block import HybridBlock
from ..gluon.nn import AvgPool2D, Conv2D, Dense, MaxPool2D
from ..ndarray import NDArray, invoke_jnp

__all__ = ["quantize_net", "quantize", "dequantize",
           "optimal_kl_threshold"]

_QMAX = 127.0  # symmetric int8
# row threshold below which QuantizedDense takes the weight-only
# dequant-GEMV kernel instead of the int8 MXU path: resolved through the
# tuned-config layer at trace time (ops/int8_gemv.gemv_max_m; the
# hand-picked _GEMV_MAX_M stays the default)
from ..ops.int8_gemv import gemv_max_m  # noqa: E402


def quantize(data, min_range, max_range, out_dtype: str = "int8"):
    """Quantize a float tensor given calibrated range (reference
    _contrib_quantize op). Symmetric: scale = max(|min|,|max|)/127."""
    if out_dtype not in ("int8", "auto"):
        raise MXNetError(f"unsupported quantized dtype {out_dtype!r} "
                         "(TPU build is symmetric int8)")
    amax = max(abs(float(min_range)), abs(float(max_range)))
    scale = amax / _QMAX if amax > 0 else 1.0

    def fn(x):
        q = jnp.clip(jnp.round(x / scale), -_QMAX, _QMAX).astype(jnp.int8)
        return q

    q = invoke_jnp(fn, (data,), {}, name="quantize")
    return q, NDArray(jnp.float32(-amax)), NDArray(jnp.float32(amax))


def dequantize(data, min_range, max_range):
    """Reference _contrib_dequantize op."""
    amax = max(abs(float(min_range.item() if isinstance(min_range, NDArray)
                         else min_range)),
               abs(float(max_range.item() if isinstance(max_range, NDArray)
                         else max_range)))
    scale = amax / _QMAX if amax > 0 else 1.0
    return invoke_jnp(lambda q: q.astype(jnp.float32) * scale, (data,), {},
                      name="dequantize")


def optimal_kl_threshold(hist: onp.ndarray, edges: onp.ndarray,
                         num_quantized_bins: int = 255) -> float:
    """KL-divergence-minimizing clip threshold over an |x| histogram
    (role of reference _LayerHistogramCollector.get_optimal_threshold).

    For each candidate threshold (right edge of bin ``i``): P = the first
    ``i`` bins with the outlier mass collapsed into bin i-1; Q = P re-binned
    to ``num_quantized_bins`` levels then expanded back, zero where the
    source bin was empty. Returns the edge minimizing KL(P||Q). ``edges``
    are the RIGHT edges of the bins (len(edges) == len(hist))."""
    hist = hist.astype(onp.float64)
    n = len(hist)
    if n <= num_quantized_bins or hist.sum() == 0:
        return float(edges[-1])
    eps = 1e-10
    best_kl, best_i = onp.inf, n
    for i in range(num_quantized_bins, n + 1, 4):
        p = hist[:i].copy()
        p[-1] += hist[i:].sum()
        src = hist[:i]
        qbin = onp.arange(i) * num_quantized_bins // i   # source → level
        level_mass = onp.bincount(qbin, weights=src,
                                  minlength=num_quantized_bins)
        nz = src > 0
        level_nz = onp.bincount(qbin, weights=nz.astype(onp.float64),
                                minlength=num_quantized_bins)
        q = onp.where(nz, level_mass[qbin] / onp.maximum(level_nz[qbin], 1),
                      0.0)
        psum, qsum = p.sum(), q.sum()
        if psum == 0 or qsum == 0:
            continue
        # smooth both so KL stays finite and sparse histograms don't
        # produce spurious zero divergence at the smallest threshold
        p = p / psum + eps
        q = q / qsum + eps
        p /= p.sum()
        q /= q.sum()
        kl = float(onp.sum(p * onp.log(p / q)))
        if kl < best_kl:
            best_kl, best_i = kl, i
    return float(edges[best_i - 1])


def _apply_act(y, act):
    if act is None:
        return y
    if act == "relu":
        return jax.nn.relu(y)
    if act == "sigmoid":
        return jax.nn.sigmoid(y)
    if act == "tanh":
        return jnp.tanh(y)
    if act == "softrelu":
        return jax.nn.softplus(y)
    if act == "softsign":
        return jax.nn.soft_sign(y)
    raise MXNetError(f"unsupported activation {act!r} in quantized layer")


class _Calibrator:
    """Per-layer activation-range observer."""

    NUM_BINS = 2048

    def __init__(self):
        self.amax = 0.0
        self.hist = None
        self.edges = None

    def observe(self, x: onp.ndarray):
        amax = float(onp.max(onp.abs(x))) if x.size else 0.0
        if self.hist is None:
            self.amax = amax
        else:
            self.amax = max(self.amax, amax)
        h, edges = onp.histogram(onp.abs(x), bins=self.NUM_BINS,
                                 range=(0, max(self.amax, 1e-8)))
        if self.edges is not None and self.edges[-1] == edges[-1]:
            self.hist += h
        else:
            # range grew: re-bin the old histogram into the new edges
            if self.hist is not None:
                centers = (self.edges[:-1] + self.edges[1:]) / 2
                idx = onp.clip(onp.searchsorted(edges, centers) - 1,
                               0, self.NUM_BINS - 1)
                nh = onp.zeros(self.NUM_BINS)
                onp.add.at(nh, idx, self.hist)
                h = h + nh
            self.hist = h
            self.edges = edges
            return
        if self.hist is None:
            self.hist, self.edges = h, edges

    def threshold(self, mode: str) -> float:
        if mode == "entropy" and self.hist is not None:
            return optimal_kl_threshold(self.hist, self.edges[1:])
        return self.amax


class _QuantizedLayer(HybridBlock):
    """Base for quantized wrappers: observe → freeze lifecycle."""

    def __init__(self, inner, bits: int = 8):
        super().__init__()
        self.inner = inner          # original fp layer (owns the params)
        self._bits = bits           # weight codec width (8, or 4 for Dense)
        self._mode = "dynamic"      # dynamic | observe | frozen
        self._calib = _Calibrator()
        self._act_scale: Optional[float] = None

    def begin_observe(self):
        self._mode = "observe"

    def freeze(self, calib_mode: str):
        if self._mode == "observe" and calib_mode in ("naive", "entropy"):
            amax = self._calib.threshold(calib_mode)
            self._act_scale = (amax / _QMAX) if amax > 0 else 1.0
        self._mode = "frozen" if self._act_scale is not None else "dynamic"
        self._quantize_weight()

    def _quantize_weight(self):
        raise NotImplementedError

    def _input_qscale(self, x):
        """Traced activation scale: calibrated constant when frozen, an
        in-graph abs-max reduction when dynamic."""
        if self._act_scale is not None:
            return jnp.float32(self._act_scale)
        amax = jnp.max(jnp.abs(x))
        return jnp.where(amax > 0, amax / _QMAX, 1.0).astype(jnp.float32)

    def __call__(self, *args):
        if self._mode == "observe":
            x = args[0]
            self._calib.observe(x.asnumpy() if isinstance(x, NDArray)
                                else onp.asarray(x))
            return self.inner(*args)
        return super().__call__(*args)


class QuantizedDense(_QuantizedLayer):
    """int8 (or 4-bit block-scaled) FullyConnected (reference
    quantized_fully_connected.cc role).

    ``bits=4`` stores the kvstore/quant.py wire format — packed
    offset-binary nibbles (``_w_q`` uint8 [N, K/2]) with per-block f32
    scales (``_w_scale`` [N, K/block]) — so the decode GEMV streams half
    the int8 lane's weight bytes and dequant-exactness vs the codec
    holds by construction. Layers whose input dim is odd cannot pack
    nibble pairs and silently keep int8 (the dtype of ``_w_q`` is the
    dispatch everywhere downstream)."""

    def _int4_block(self, K: int) -> int:
        # the tuned `gemv_int4_block` knob when it tiles K exactly, else
        # one block per row (blocks must never straddle rows: a row is
        # one output channel's reduction)
        from ..tune.config import get_knob
        block = get_knob("gemv_int4_block")
        return block if K % block == 0 else K

    def _quantize_weight(self):
        w = self.inner.weight.data()._data  # (units, in)
        N, K = w.shape
        if self._bits == 4 and K % 2 == 0:
            from ..kvstore.quant import pack_codes, quantize_blocks
            block = self._int4_block(K)
            codes, scales = quantize_blocks(
                w.astype(jnp.float32).reshape(-1), 4, block)
            self._w_scale = scales.reshape(N, K // block)
            self._w_q = pack_codes(codes, 4).reshape(N, K // 2)
            return
        w_amax = jnp.maximum(jnp.max(jnp.abs(w), axis=1), 1e-8)
        self._w_scale = (w_amax / _QMAX).astype(jnp.float32)   # per out-ch
        self._w_q = jnp.clip(jnp.round(w / self._w_scale[:, None]),
                             -_QMAX, _QMAX).astype(jnp.int8)

    def forward(self, x):
        inner = self.inner
        w_q, w_scale = self._w_q, self._w_scale
        bias = None if inner.bias is None else inner.bias.data()
        flatten = inner._flatten
        act = inner._activation
        arrays = [x] + ([bias] if bias is not None else [])

        def fn(xv, *rest):
            if flatten:
                xv = xv.reshape(xv.shape[0], -1)
            rows = 1
            for d in xv.shape[:-1]:
                rows *= int(d)
            int4 = w_q.dtype == jnp.uint8
            if rows <= gemv_max_m():
                # decode regime: weight-bandwidth-bound. Stream int8 (or
                # packed int4 nibble) weights, dequantize in VMEM, bf16
                # MXU dot — no activation quantization (ops/int8_gemv.py;
                # the act-quantized path measured SLOWER than bf16 here)
                if int4:
                    from ..ops.int8_gemv import int4_weight_matmul
                    y = int4_weight_matmul(xv.reshape(rows, xv.shape[-1]),
                                           w_q, w_scale)
                else:
                    from ..ops.int8_gemv import int8_weight_matmul
                    y = int8_weight_matmul(xv.reshape(rows, xv.shape[-1]),
                                           w_q, w_scale)
                y = y.reshape(xv.shape[:-1] + (w_q.shape[0],))
            elif int4:
                # large-M int4 stays weight-only: dequantize through the
                # codec and run the f32 matmul (no int4 MXU lane exists;
                # the activation-quantized path is an int8-only win)
                from ..kvstore.quant import dequantize_blocks, unpack_codes
                N, K2 = w_q.shape
                block = 2 * K2 // w_scale.shape[1]
                wf = dequantize_blocks(
                    unpack_codes(w_q.reshape(-1), 4),
                    w_scale.reshape(-1), block).reshape(N, 2 * K2)
                y = jax.lax.dot_general(
                    xv.astype(jnp.float32), wf,
                    (((xv.ndim - 1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            else:
                s_x = self._input_qscale(xv)
                x_q = jnp.clip(jnp.round(xv / s_x), -_QMAX, _QMAX) \
                    .astype(jnp.int8)
                y = jax.lax.dot_general(
                    x_q, w_q, (((x_q.ndim - 1,), (1,)), ((), ())),
                    preferred_element_type=jnp.int32)
                y = y.astype(jnp.float32) * (s_x * w_scale)
            if rest:
                y = y + rest[0]
            return _apply_act(y, act)

        from ..ndarray import apply_multi
        return apply_multi(fn, arrays, name="quantized_dense")


class QuantizedConv2D(_QuantizedLayer):
    """int8 2-D Convolution (reference quantized_conv.cc role). NCHW/OIHW."""

    def _quantize_weight(self):
        w = self.inner.weight.data()._data  # (O, I/g, KH, KW)
        w_amax = jnp.maximum(jnp.max(jnp.abs(w), axis=(1, 2, 3)), 1e-8)
        self._w_scale = (w_amax / _QMAX).astype(jnp.float32)
        self._w_q = jnp.clip(
            jnp.round(w / self._w_scale[:, None, None, None]),
            -_QMAX, _QMAX).astype(jnp.int8)

    def forward(self, x):
        inner = self.inner
        w_q, w_scale = self._w_q, self._w_scale
        bias = None if inner.bias is None else inner.bias.data()
        strides, padding = inner._strides, inner._padding
        dilation, groups = inner._dilation, inner._groups
        act = inner._activation
        arrays = [x] + ([bias] if bias is not None else [])

        def fn(xv, *rest):
            s_x = self._input_qscale(xv)
            x_q = jnp.clip(jnp.round(xv / s_x), -_QMAX, _QMAX) \
                .astype(jnp.int8)
            pad = [(p, p) for p in padding]
            y = jax.lax.conv_general_dilated(
                x_q, w_q, strides, pad, rhs_dilation=dilation,
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                feature_group_count=groups,
                preferred_element_type=jnp.int32)
            y = y.astype(jnp.float32) * (s_x * w_scale)[None, :, None, None]
            if rest:
                y = y + rest[0][None, :, None, None]
            return _apply_act(y, act)

        from ..ndarray import apply_multi
        return apply_multi(fn, arrays, name="quantized_conv2d")


class QuantizedPooling(HybridBlock):
    """Pooling kept in the int8 domain (reference quantize_graph_pass.cc:286
    keeps Pooling/Concat inside the quantized subgraph instead of
    dequantize→pool→requantize). Max pooling commutes with the symmetric
    scale, so pooling the int8 codes is numerically identical to fp pooling;
    average pooling accumulates the codes in int32 and applies the count in
    the dequantize scale (the reference's quantized_pooling semantics)."""

    def __init__(self, inner):
        super().__init__()
        self.inner = inner

    def forward(self, x):
        inner = self.inner
        kernel = inner._size
        strides = inner._strides
        padding = inner._padding
        is_max = inner._type == "max"
        include_pad = getattr(inner, "_count_include_pad", True)

        def fn(xv):
            amax = jnp.max(jnp.abs(xv))
            s = jnp.where(amax > 0, amax / _QMAX, 1.0).astype(jnp.float32)
            q = jnp.clip(jnp.round(xv / s), -_QMAX, _QMAX).astype(jnp.int8)
            window = (1, 1) + tuple(kernel)
            strd = (1, 1) + tuple(strides)
            pad = ((0, 0), (0, 0)) + tuple((p, p) for p in padding)
            if is_max:
                y = jax.lax.reduce_window(
                    q, jnp.int8(-128), jax.lax.max, window, strd, pad)
                return y.astype(jnp.float32) * s
            acc = jax.lax.reduce_window(
                q.astype(jnp.int32), jnp.int32(0), jax.lax.add, window,
                strd, pad)
            if include_pad or all(p == 0 for p in padding):
                count = float(onp.prod(kernel))
                return acc.astype(jnp.float32) * (s / count)
            # count_include_pad=False: same in-bounds divisor as the float
            # avg path (shared helper — semantics cannot diverge)
            from ..numpy_extension import _inbounds_count
            return acc.astype(jnp.float32) * s \
                / _inbounds_count(xv, window, strd, pad)

        from ..ndarray import apply_multi
        return apply_multi(fn, [x], name="quantized_pooling")


def _eligible(block, name: str, mode: str, exclude: List[str],
              exclude_match: List[str]) -> bool:
    if name in exclude:
        return False
    if any(re.search(pat, name) for pat in exclude_match):
        return False
    if isinstance(block, Dense):
        return block.weight._var is not None
    if isinstance(block, Conv2D) and not block._transpose:
        if block.weight._var is None:
            return False
        if mode == "smart" and block.weight.shape[1] < 8:
            # first conv over RGB: int8 gains nothing, accuracy cost is
            # outsized (reference quantize_mode='smart' exclusion role)
            return False
        return True
    return False


def _walk_replace(parent, mode, exclude, exclude_match, prefix="",
                  replaced=None, bits=8):
    if replaced is None:
        replaced = []
    prev_quantized = False
    for name, child in list(parent._children.items()):
        path = f"{prefix}{name}"
        if _eligible(child, path, mode, exclude, exclude_match):
            if isinstance(child, Dense):
                # only Dense has a 4-bit lane; Conv keeps the int8 MXU path
                q = QuantizedDense(child, bits=bits)
            else:
                q = QuantizedConv2D(child)
            setattr(parent, name, q)
            replaced.append(q)
            prev_quantized = True
        elif (prev_quantized
              and isinstance(child, (MaxPool2D, AvgPool2D))
              and not child._global and not child._ceil_mode):
            # pooling stays in the int8 domain between quantized layers
            # (reference quantize_graph_pass.cc:286); no calibration state,
            # so it is not added to `replaced`
            setattr(parent, name, QuantizedPooling(child))
            # an int8 pool passes the quantized domain through
        else:
            _walk_replace(child, mode, exclude, exclude_match,
                          prefix=f"{path}.", replaced=replaced, bits=bits)
            prev_quantized = False
    return replaced


def quantize_net(network, quantized_dtype: str = "auto",
                 quantize_mode: str = "smart",
                 exclude_layers: Optional[List[str]] = None,
                 exclude_layers_match: Optional[List[str]] = None,
                 calib_data=None, data_shapes=None,
                 calib_mode: str = "none", num_calib_batches: Optional[int] = None,
                 device=None, ctx=None, logger_=None,
                 quantize_tied_head: Optional[bool] = None, bits: int = 8):
    """Quantize a (forward-run) HybridBlock in place and return it
    (reference contrib.quantization.quantize_net, quantization.py:92).

    ``calib_mode='naive'|'entropy'`` require ``calib_data`` (a DataLoader or
    iterable of batches); ``'none'`` uses per-batch dynamic scales computed
    in-graph. Parameters must be initialized with known shapes (run one
    forward first).

    ``quantize_tied_head``: weight-only int8 for a tied LM head (GPT-style
    ``wte``). ``None`` (default) quantizes it unless the embedding is
    excluded via ``exclude_layers``/``exclude_layers_match`` — an exclusion
    means 'keep this layer full precision', and the tied head reads the
    SAME table, so it must honor it; True/False force either way.

    ``bits``: weight codec width for Dense layers and the tied head — 8
    (default) or 4. ``bits=4`` stores the kvstore/quant.py block-scaled
    nibble wire format (packed uint8 codes + per-block f32 scales; the
    ``gemv_int4_block`` knob sets the scale granularity) and decodes
    stream through ops/int8_gemv.int4_weight_matmul and the fused
    kernels' int4 lane; odd-input-dim Dense layers and Conv layers keep
    int8."""
    if quantized_dtype not in ("auto", "int8"):
        raise MXNetError(
            f"quantized_dtype={quantized_dtype!r}: the TPU build quantizes "
            "symmetric int8 (MXU int8×int8→int32); 'uint8' is not supported")
    if quantize_mode not in ("smart", "full"):
        raise MXNetError(f"unknown quantize_mode {quantize_mode!r}")
    if bits not in (4, 8):
        raise MXNetError(f"bits={bits!r}: supported weight codec widths "
                         "are 8 (int8) and 4 (packed block-scaled nibbles)")
    # a previously-compiled CachedOp would bypass the quantized wrappers
    # during calibration (stale executable); drop caches + deactivate
    network.hybridize(active=False)
    replaced = _walk_replace(network, quantize_mode,
                             list(exclude_layers or []),
                             list(exclude_layers_match or []), bits=bits)
    if not replaced:
        logger.warning("quantize_net: no quantizable layers found "
                       "(initialize + run a forward pass first?)")
        return network
    if calib_mode in ("naive", "entropy"):
        if calib_data is None:
            raise MXNetError(f"calib_data required for calib_mode={calib_mode!r}")
        for q in replaced:
            q.begin_observe()
        n = 0
        for batch in calib_data:
            data = batch[0] if isinstance(batch, (tuple, list)) else batch
            network(data)
            n += 1
            if num_calib_batches is not None and n >= num_calib_batches:
                break
        if n == 0:
            raise MXNetError("calib_data yielded no batches")
    elif calib_mode != "none":
        raise MXNetError(f"unknown calib_mode {calib_mode!r}")
    for q in replaced:
        q.freeze(calib_mode)
    if quantize_tied_head is None:
        # auto: the tied head shares the embedding table, so excluding the
        # embedding by name (or pattern) must keep the head fp too — for
        # every tied-embedding spelling (GPT 'wte', Llama
        # 'model.embed_tokens')
        excl = list(exclude_layers or [])
        exclm = list(exclude_layers_match or [])
        tied_names = ("wte", "model.embed_tokens", "embed_tokens")
        quantize_tied_head = not any(
            n in excl or any(re.search(p, n) for p in exclm)
            for n in tied_names)
    if quantize_tied_head:
        _quantize_tied_lm_head(network, bits=bits)
    network.hybridize()
    return network


def _quantize_tied_lm_head(network, bits: int = 8):
    """Weight-only int8 (or 4-bit block-scaled) for a tied LM head
    (GPT-style ``wte``, or a tie_embeddings Llama's ``model.embed_tokens``):
    the decode logits matmul reads the full (V, D) table every step —
    77 MB bf16 for GPT-2 — and halving (int8) or quartering (int4) that
    stream is the single biggest quantized decode win.

    The vocab dim is padded to a 128-lane multiple (50257 -> 50304) ONCE
    here, so the GEMV reduction tiles land on lane boundaries with no
    remainder branch; consumers slice logits back to ``vocab`` (free) or
    mask the pad lanes to -inf before sampling (ops/fused_block_gemv).
    Stores ``(table, scales, vocab)`` on the network — int8: [Vp, D] int8
    with per-row scales [Vp]; bits=4 (even D): [Vp, D/2] packed uint8
    nibbles with [Vp, D/block] block scales, padded rows quantized as
    exact zero blocks (codes 0, scale 1.0) so pad lanes stay zero. The
    model's forward dispatches on the table dtype at decode row counts.
    The embedding LOOKUP keeps the original table (exact)."""
    from ..ops.fused_block_gemv import pad_vocab
    wte = getattr(network, "wte", None)
    if wte is None or not hasattr(wte, "weight"):
        model = getattr(network, "model", None)
        wte = getattr(model, "embed_tokens", None)
        if (wte is None or not hasattr(wte, "weight")
                or getattr(network, "lm_head", 0) is not None):
            return                  # untied head: nothing reads the table
    w = wte.weight.data()._data  # (V, D)
    V, D = w.shape
    Vp = pad_vocab(V)
    if bits == 4 and D % 2 == 0:
        from ..kvstore.quant import pack_codes, quantize_blocks
        from ..tune.config import get_knob
        block = get_knob("gemv_int4_block")
        if D % block:
            block = D
        # pad FIRST: zero rows quantize to all-zero blocks (scale 1.0),
        # so pad lanes dequantize to exact zeros like the int8 pad
        wp = jnp.pad(w.astype(jnp.float32), ((0, Vp - V), (0, 0)))
        codes, scales = quantize_blocks(wp.reshape(-1), 4, block)
        network._q_lm_head = (pack_codes(codes, 4).reshape(Vp, D // 2),
                              scales.reshape(Vp, D // block), V)
        return
    amax = jnp.maximum(jnp.max(jnp.abs(w.astype(jnp.float32)), axis=1), 1e-8)
    scale = (amax / _QMAX).astype(jnp.float32)
    w_q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale[:, None]),
                   -_QMAX, _QMAX).astype(jnp.int8)
    if Vp != V:
        w_q = jnp.pad(w_q, ((0, Vp - V), (0, 0)))
        scale = jnp.pad(scale, (0, Vp - V), constant_values=1.0)
    network._q_lm_head = (w_q, scale, V)
