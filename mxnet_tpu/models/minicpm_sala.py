"""MiniCPM-SALA decoder LM (Gluon blocks): two kinds of layer in one stack.

``mixer_types`` names each layer's token mixer, in order:

- ``"minicpm4"``: block-sparse softmax attention (InfLLM v2; ``ops/
  sparse_attention.py``): grouped-query heads over few key/value heads, no
  position embedding, RMS-normed queries and keys, a sigmoid output gate. It
  owns paged pools: K and V pages and the compressed keys the selection
  scores.
- ``"lightning-attn"``: linear attention with a per-head decay (Lightning
  Attention-2; ``ops/linear_attention.py``): as many key/value heads as query
  heads, rotary positions on the normed queries and keys, an RMS norm over
  the heads' joined output and a sigmoid output gate. It owns no page: its
  memory is one ``[heads, hd, hd]`` float32 state a serving slot.

Around both: pre-RMSNorm residual blocks with MiniCPM's muP scalings (the
embedding times ``scale_emb``, every residual branch times ``scale_depth /
sqrt(mup_denominator)``, the final hidden state divided by ``hidden_size /
dim_model_base`` before an output head of its own) and llama's SwiGLU MLP.

**The cache protocol is the paged one only.** ``cache_spec_paged`` lists the
sparse layers' pools, ``cache_spec_state(slots)`` the lightning layers'
per-slot states, and ``forward_cached_paged(ids, pos, block_table, slots,
valid, *page_pools, *state_pools)`` takes with the block table each row's slot
(the last slot of a state pool is the sink, for rows that serve no request)
and how many of its ``T`` positions are real (padding must not move a state).
``serve.InferenceEngine`` reads those three methods and needs no argument of
its own for this model. ``forward`` is the same layers over pools made for the
call.

Device operations carry the scopes ``mx.embed``, ``mx.attn`` (around a whole
mixer; inside it ``mx.linear_attn`` > ``mx.state_update`` or ``mx.sparse_attn``
> ``mx.sparse_select``, ``mx.kv_compress``), ``mx.mlp``, ``mx.lm_head``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ndarray import NDArray, invoke_jnp
from ..ops import linear_attention as _linear
from ..ops import sparse_attention as _sparse
from ..ops.sparse_attention import SparseConfig
from .llama import LlamaMLP, _decode_positions, _rms, _rope

__all__ = ["MiniCPMSALAConfig", "MiniCPMSALAForCausalLM", "SALA_TINY",
           "SPARSE", "LIGHTNING"]

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


@dataclasses.dataclass
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    mixer_types: Tuple[str, ...] = (SPARSE,) + (LIGHTNING,) * 3
    #: the published index of ``mixer_types[0]`` and the published depth:
    #: a lightning layer's decay follows its place in the whole model
    first_layer: int = 0
    published_layers: int = 32
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    mup_denominator: int = 32
    dim_model_base: int = 256
    max_position_embeddings: int = 524288
    sparse: SparseConfig = SparseConfig()
    dtype: object = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.mup_denominator)


SALA_TINY = MiniCPMSALAConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_heads=4,
    num_kv_heads=2, head_dim=16, lightning_heads=4, lightning_head_dim=16,
    mixer_types=(SPARSE, LIGHTNING, LIGHTNING, SPARSE), first_layer=2,
    published_layers=8, mup_denominator=8, dim_model_base=32,
    max_position_embeddings=4096,
    sparse=SparseConfig(block=4, kernel=2, stride=1, init_blocks=1,
                        window=8, topk=5, dense_len=24),
    dtype=jnp.float32)


def _dense(units, in_units, dtype):
    return nn.Dense(units, use_bias=False, flatten=False, in_units=in_units,
                    dtype=dtype)


def _gain(name, size, dtype):
    return Parameter(name, shape=(size,), dtype=dtype, init="ones")


def _heads(x, n, hd):
    B, T, _ = x.shape
    return x.reshape(B, T, n, hd).transpose(0, 2, 1, 3)


def _norm(x, w, eps):
    return _rms(x, w.astype(x.dtype), eps)


class SALALightningMixer(HybridBlock):
    def __init__(self, cfg: MiniCPMSALAConfig, layer: int):
        super().__init__()
        self.cfg = cfg
        D, H, hd = cfg.hidden_size, cfg.lightning_heads, cfg.lightning_head_dim
        self.q_proj = _dense(H * hd, D, cfg.dtype)
        self.k_proj = _dense(H * hd, D, cfg.dtype)
        self.v_proj = _dense(H * hd, D, cfg.dtype)
        self.g_proj = _dense(H * hd, D, cfg.dtype)
        self.o_proj = _dense(D, H * hd, cfg.dtype)
        self.q_norm = _gain("q_norm", hd, cfg.dtype)
        self.k_norm = _gain("k_norm", hd, cfg.dtype)
        self.o_norm = _gain("o_norm", H * hd, cfg.dtype)
        self._layer = cfg.first_layer + layer

    def forward_state(self, x, pos, slots, valid, state):
        cfg = self.cfg
        H, hd = cfg.lightning_heads, cfg.lightning_head_dim
        B, T, _ = x.shape
        slopes = _linear.decay_slopes(H, self._layer, cfg.published_layers)

        def fn(q, k, v, g, qn, kn, on, posv, slotv, validv, pool):
            posv = jnp.broadcast_to(jnp.asarray(posv, jnp.int32), (B,))
            positions = _decode_positions(posv, T)
            q = _rope(_norm(_heads(q, H, hd), qn, cfg.rms_eps), positions,
                      cfg.rope_theta)
            k = _rope(_norm(_heads(k, H, hd), kn, cfg.rms_eps), positions,
                      cfg.rope_theta)
            q = (q.astype(jnp.float32) / math.sqrt(hd)).astype(q.dtype)
            o, pool = _linear.lightning_attention_slots(
                q, k, _heads(v, H, hd), pool, slotv, posv, slopes, validv)
            o = o.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
            o = _norm(o, on, cfg.rms_eps) * jax.nn.sigmoid(
                g.astype(jnp.float32)).astype(o.dtype)
            return o, pool

        o, state = invoke_jnp(
            fn, (self.q_proj(x), self.k_proj(x), self.v_proj(x),
                 self.g_proj(x), self.q_norm.data(), self.k_norm.data(),
                 self.o_norm.data(), pos, slots, valid, state), {},
            name="sala_lightning")
        return self.o_proj(o), state


class SALASparseMixer(HybridBlock):
    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        self.cfg = cfg
        D, H, G, hd = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                       cfg.head_dim)
        self.q_proj = _dense(H * hd, D, cfg.dtype)
        self.k_proj = _dense(G * hd, D, cfg.dtype)
        self.v_proj = _dense(G * hd, D, cfg.dtype)
        self.g_proj = _dense(H * hd, D, cfg.dtype)
        self.o_proj = _dense(D, H * hd, cfg.dtype)
        self.q_norm = _gain("q_norm", hd, cfg.dtype)
        self.k_norm = _gain("k_norm", hd, cfg.dtype)

    def forward_paged(self, x, pos, block_table, valid, k_pages, v_pages,
                      kc_pages):
        cfg = self.cfg
        H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        B, T, _ = x.shape

        def fn(q, k, v, g, qn, kn, posv, bt, validv, kp, vp, kcp):
            q = _norm(_heads(q, H, hd), qn, cfg.rms_eps)
            k = _norm(_heads(k, G, hd), kn, cfg.rms_eps)
            posv = jnp.broadcast_to(jnp.asarray(posv, jnp.int32), (B,))
            o, kp, vp, kcp = _sparse.sparse_paged_attention(
                q, k, _heads(v, G, hd), kp, vp, kcp, bt, posv, validv,
                rep=H // G, sc=cfg.sparse)
            o = o.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
            o = o * jax.nn.sigmoid(g.astype(jnp.float32)).astype(o.dtype)
            return o, kp, vp, kcp

        o, kp, vp, kcp = invoke_jnp(
            fn, (self.q_proj(x), self.k_proj(x), self.v_proj(x),
                 self.g_proj(x), self.q_norm.data(), self.k_norm.data(),
                 pos, block_table, valid, k_pages, v_pages, kc_pages), {},
            name="sala_sparse")
        return self.o_proj(o), kp, vp, kcp


class SALADecoderLayer(HybridBlock):
    def __init__(self, cfg: MiniCPMSALAConfig, layer: int):
        super().__init__()
        self.kind = cfg.mixer_types[layer]
        if self.kind not in (SPARSE, LIGHTNING):
            raise MXNetError(f"MiniCPM-SALA: unknown mixer {self.kind!r}")
        self._scale = cfg.residual_scale
        self.input_layernorm = nn.RMSNorm(
            epsilon=cfg.rms_eps, in_channels=cfg.hidden_size, dtype=cfg.dtype)
        self.self_attn = (SALASparseMixer(cfg) if self.kind == SPARSE
                          else SALALightningMixer(cfg, layer))
        self.post_attention_layernorm = nn.RMSNorm(
            epsilon=cfg.rms_eps, in_channels=cfg.hidden_size, dtype=cfg.dtype)
        self.mlp = LlamaMLP(cfg)

    def forward_cached(self, x, pos, block_table, slots, valid, caches):
        """``caches``: (K, V, compressed-key pages) or (state pool,)."""
        with jax.named_scope("mx.attn"):
            h = self.input_layernorm(x)
            if self.kind == SPARSE:
                y, *caches = self.self_attn.forward_paged(
                    h, pos, block_table, valid, *caches)
            else:
                y, *caches = self.self_attn.forward_state(
                    h, pos, slots, valid, *caches)
            x = x + y * self._scale
        with jax.named_scope("mx.mlp"):
            x = x + self.mlp(self.post_attention_layernorm(x)) * self._scale
        return x, caches


class MiniCPMSALAModel(HybridBlock):
    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         dtype=cfg.dtype)
        self.layers = nn.HybridSequential()
        for i in range(cfg.num_layers):
            self.layers.add(SALADecoderLayer(cfg, i))
        self.norm = nn.RMSNorm(epsilon=cfg.rms_eps,
                               in_channels=cfg.hidden_size, dtype=cfg.dtype)

    def forward_cached_paged(self, input_ids, pos, block_table, slots, valid,
                             *caches):
        cfg = self.cfg
        n_sparse = cfg.mixer_types.count(SPARSE)
        pages, states = list(caches[:3 * n_sparse]), list(caches[3 * n_sparse:])
        with jax.named_scope("mx.embed"):
            x = self.embed_tokens(input_ids) * cfg.scale_emb
        new_pages, new_states = [], []
        for layer in self.layers._children.values():
            if layer.kind == SPARSE:
                x, out = layer.forward_cached(x, pos, block_table, slots,
                                              valid, pages[:3])
                pages, new_pages = pages[3:], new_pages + out
            else:
                x, out = layer.forward_cached(x, pos, block_table, slots,
                                              valid, states[:1])
                states, new_states = states[1:], new_states + out
        return (self.norm(x), *new_pages, *new_states)


class MiniCPMSALAForCausalLM(HybridBlock):
    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        if cfg.num_heads % cfg.num_kv_heads:
            raise MXNetError("MiniCPM-SALA: query heads must be a multiple "
                             "of the key/value heads")
        self.cfg = cfg
        self.model = MiniCPMSALAModel(cfg)
        self.lm_head = _dense(cfg.vocab_size, cfg.hidden_size, cfg.dtype)

    # ------------------------------------------------------ cache protocol
    def cache_spec_paged(self, num_pages: int, page_size: int):
        """[(shape, dtype)] of the sparse layers' pools, three a layer in
        layer order: K and V pages ``[num_pages, kv heads, page, hd]`` and
        the compressed keys ``[num_pages, kv heads, page // stride, hd]``.
        A page is a selection block, so ``page_size`` is not free."""
        cfg = self.cfg
        if page_size != cfg.sparse.block:
            raise MXNetError(
                f"MiniCPM-SALA selects whole pages: page_size must be the "
                f"sparse block, {cfg.sparse.block}, not {page_size}")
        G, hd = cfg.num_kv_heads, cfg.head_dim
        kv = ((num_pages, G, page_size, hd), cfg.dtype)
        kc = ((num_pages, G, _sparse.compressed_per_page(cfg.sparse), hd),
              cfg.dtype)
        return [kv, kv, kc] * cfg.mixer_types.count(SPARSE)

    def cache_spec_state(self, slots: int):
        """[(shape, dtype)] of the lightning layers' recurrent states, one a
        layer in layer order: ``[slots, heads, hd, hd]`` float32. The caller
        passes one slot more than it serves: the last is the sink."""
        cfg = self.cfg
        shape = (slots, cfg.lightning_heads, cfg.lightning_head_dim,
                 cfg.lightning_head_dim)
        return [(shape, jnp.float32)] * cfg.mixer_types.count(LIGHTNING)

    def blocks_read(self, pos: int, T: int):
        """``(read, live)`` blocks of one sparse layer for a row's ``T`` new
        positions from ``pos`` (``ops.sparse_attention.blocks_read``)."""
        return _sparse.blocks_read(self.cfg.sparse, pos, T)

    @jax.named_scope("mx.lm_head")
    def _logits(self, h):
        cfg = self.cfg
        return self.lm_head(h * (cfg.dim_model_base / cfg.hidden_size))

    def forward_cached_paged(self, input_ids, pos, block_table, slots, valid,
                             *caches):
        h, *new_caches = self.model.forward_cached_paged(
            input_ids, pos, block_table, slots, valid, *caches)
        return (self._logits(h), *new_caches)

    def forward(self, input_ids):
        """The whole sequence at once, over pools made for the call."""
        B, T = input_ids.shape
        ps = self.cfg.sparse.block
        n = -(-T // ps)
        caches = [NDArray(jnp.zeros(s, d)) for s, d in
                  self.cache_spec_paged(B * n + 1, ps)
                  + self.cache_spec_state(B + 1)]
        table = jnp.arange(B * n, dtype=jnp.int32).reshape(B, n)
        out = self.forward_cached_paged(
            input_ids, NDArray(jnp.zeros(B, jnp.int32)), NDArray(table),
            NDArray(jnp.arange(B, dtype=jnp.int32)),
            NDArray(jnp.full(B, T, jnp.int32)), *caches)
        return out[0]
