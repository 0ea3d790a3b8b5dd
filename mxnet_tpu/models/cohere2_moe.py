"""Cohere2-MoE decoder LM (Gluon blocks): Command A+'s language model.

Identical *parallel* blocks: one bias-free LayerNorm (mean and variance, a
gain, no offset) feeds both branches, attention and experts, and both are
added to the residual, ``y = x + attn(h) + moe(h)``. Attention is Cohere2's:
grouped-query heads (``num_heads`` over ``num_kv_heads``), no bias, no q/k
norm, and two kinds of layer by ``layer_types``: a *sliding* layer rotates
queries and keys (interleaved pairs, lanes ``2i`` and ``2i + 1``:
``rope_gptj``) and a query at ``i`` sees the keys ``i - window < j <= i``; a
*full* layer has no positional embedding at all and sees ``j <= i``. The
expert branch routes every token over ``num_experts`` experts by a sigmoid
score, keeps ``num_experts_per_tok`` and normalises their scores over the kept
ones; ``num_shared_experts`` experts see every token and their outputs are
averaged and added to the routed sum. The embedding is tied to the head; the
logits are float32, times ``logit_scale``.

**The experts held here.** ``experts_held = (first, count)`` says which of the
``num_experts`` routed experts this model holds (a chip's share under expert
parallelism: ``ops/moe.py``). The router keeps its ``num_experts`` outputs and
its ``k``; the layer adds its held experts' part and leaves the others' out.
``(0, num_experts)`` is the whole layer.

``forward(ids)`` is the definition over a whole sequence, no cache. **The
cache is paged, in two kinds.** A full layer's K and V pools are what
``models/llama._paged_attention`` lays out, ``[pages + 1, page_size, kv_heads *
hd]``, and hold every position. A sliding layer's pools are of the same shape
but of a kind of their own, with their own page ids and their own table: what
lies behind a request's window is given back to the pool while the request
lives (``serve/paging.PagePool.slide``), and the paged read takes ``window``.
The protocol here is

- ``cache_window()``: the window, which tells the serving engine that this
  model's pools are of two kinds (the engine takes no argument for it);
- ``cache_spec_paged(num_pages, page_size)``: the pools in layer order, a K
  and a V pool a layer; ``num_pages`` may be a pair ``(full, windowed)``;
  ``cache_kinds()`` says which kind each pool is of (0 full, 1 windowed);
- ``expert_counts()``: ``(layers, held, experts a token)``; ``[layers,
  held]`` is the shape of one more small int32 array that
  ``forward_cached_paged`` returns behind the pools: the tokens each held
  expert received in each layer, real tokens only;
- ``forward_cached_paged(ids, pos, block_table, valid, *pools)``:
  ``block_table`` is ``[B, 2, max_pages]``, the full kind's table and the
  windowed kind's; ``valid`` [B] how many of each row's ``T`` positions are
  real (padding is routed to no expert). A row whose full table starts at
  the sink serves no request.

Device operations carry the scopes ``mx.embed``, ``mx.attn`` (inside it the
shared ``mx.paged_attention`` > ``mx.kv_write``, ``mx.kv_walk``), ``mx.moe`` >
``mx.moe_route``, ``mx.moe_experts``, ``mx.moe_shared``, and ``mx.lm_head``.
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
from ..ndarray import invoke_jnp
from ..ops import moe as _moe
from .llama import _decode_positions, _paged_attention, walk_form

__all__ = ["Cohere2MoEConfig", "Cohere2MoEForCausalLM", "COHERE2_MOE_TINY"]

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class Cohere2MoEConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096          # one expert's width, shared alike
    num_layers: int = 32
    num_heads: int = 128
    num_kv_heads: int = 8
    head_dim: int = 128
    num_experts: int = 128                 # the router's outputs
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    experts_held: Tuple[int, int] = (0, 128)
    layer_types: Tuple[str, ...] = ()      # default: period 4, sliding first
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    max_position_embeddings: int = 200000
    dtype: object = jnp.bfloat16

    def __post_init__(self):
        if not self.layer_types:
            self.layer_types = tuple(
                FULL if i % 4 == 3 else SLIDING
                for i in range(self.num_layers))
        self.layer_types = tuple(self.layer_types)
        self.experts_held = tuple(int(x) for x in self.experts_held)


COHERE2_MOE_TINY = Cohere2MoEConfig(
    vocab_size=256, hidden_size=64, intermediate_size=32, num_layers=4,
    num_heads=8, num_kv_heads=2, head_dim=16, num_experts=16,
    num_experts_per_tok=4, num_shared_experts=2, experts_held=(0, 16),
    sliding_window=24, max_position_embeddings=512, dtype=jnp.float32)


def _dense(units, in_units, dtype):
    return nn.Dense(units, use_bias=False, flatten=False, in_units=in_units,
                    dtype=dtype)


def _heads(x, n, hd):
    B, T, _ = x.shape
    return x.reshape(B, T, n, hd).transpose(0, 2, 1, 3)


def _rope_pairs(x, positions, theta: float):
    """Rotary embedding, interleaved-pairs convention (``rope_gptj``): the
    pair of lanes ``(2i, 2i + 1)`` turns by ``t * theta ** (-2 i / D)``; f32
    math. ``positions`` is [T] or [B, T]. The lanes stay where they are: the
    partner of each lane comes by a roll, not by a reshape to pairs."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.asarray(positions).astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)
    if ang.ndim == 2:
        cos, sin = cos[None, None], sin[None, None]     # [1, 1, T, D]
    else:
        cos, sin = cos[:, None], sin[:, None]           # [B, 1, T, D]
    xf = x.astype(jnp.float32)
    even = jnp.arange(D) % 2 == 0
    partner = jnp.where(even, -jnp.roll(xf, -1, axis=-1),
                        jnp.roll(xf, 1, axis=-1))
    return (xf * cos + partner * sin).astype(x.dtype)


def _dense_attention(q, k, v, rep: int, window):
    """Causal attention over a whole sequence, no cache: ``q`` [B, H, T, hd],
    ``k``, ``v`` [B, G, T, hd]; with ``window`` the sliding mask."""
    B, H, T, hd = q.shape
    G = H // rep
    qf = q.reshape(B, G, rep, T, hd).astype(jnp.float32) / math.sqrt(hd)
    s = jnp.einsum("bgrtd,bgjd->bgrtj", qf, k.astype(jnp.float32))
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (j > i - window)
    a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bgrtj,bgjd->bgrtd", a, v.astype(jnp.float32))
    return o.reshape(B, H, T, hd).astype(q.dtype)


class Cohere2LayerNorm(HybridBlock):
    """LayerNorm with a gain and no offset; float32 statistics."""

    def __init__(self, size: int, eps: float, dtype):
        super().__init__()
        self._eps = eps
        self.weight = Parameter("weight", shape=(size,), dtype=dtype,
                                init="ones")

    def forward(self, x):
        def fn(xv, w):
            xf = xv.astype(jnp.float32)
            mean = jnp.mean(xf, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
            return ((xf - mean) * jax.lax.rsqrt(var + self._eps)
                    * w.astype(jnp.float32)).astype(xv.dtype)

        return invoke_jnp(fn, (x, self.weight.data()), {},
                          name="cohere_norm")


class Cohere2Attention(HybridBlock):
    def __init__(self, cfg: Cohere2MoEConfig, kind: str):
        super().__init__()
        self.cfg = cfg
        self.window = cfg.sliding_window if kind == SLIDING else None
        D, H, G, hd = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                       cfg.head_dim)
        self.q_proj = _dense(H * hd, D, cfg.dtype)
        self.k_proj = _dense(G * hd, D, cfg.dtype)
        self.v_proj = _dense(G * hd, D, cfg.dtype)
        self.o_proj = _dense(D, H * hd, cfg.dtype)

    def _qkv(self, q, k, v, positions):
        """The projections as heads, a sliding layer's queries and keys
        turned to their positions (a full layer has no positions)."""
        cfg = self.cfg
        q = _heads(q, cfg.num_heads, cfg.head_dim)
        k = _heads(k, cfg.num_kv_heads, cfg.head_dim)
        v = _heads(v, cfg.num_kv_heads, cfg.head_dim)
        if self.window is not None:
            q = _rope_pairs(q, positions, cfg.rope_theta)
            k = _rope_pairs(k, positions, cfg.rope_theta)
        return q, k, v

    def forward(self, x):
        cfg = self.cfg
        B, T, _ = x.shape
        rep = cfg.num_heads // cfg.num_kv_heads

        def fn(q, k, v):
            q, k, v = self._qkv(q, k, v, jnp.arange(T))
            o = _dense_attention(q, k, v, rep, self.window)
            return o.transpose(0, 2, 1, 3).reshape(B, T, -1)

        o = invoke_jnp(fn, (self.q_proj(x), self.k_proj(x), self.v_proj(x)),
                       {}, name="cohere_attention")
        return self.o_proj(o)

    def forward_paged(self, x, pos, table, k_pages, v_pages):
        """``table`` [B, max_pages]: this layer's kind's."""
        cfg = self.cfg
        B, T, _ = x.shape
        rep = cfg.num_heads // cfg.num_kv_heads

        def fn(q, k, v, posv, bt, kp, vp):
            q, k, v = self._qkv(q, k, v, _decode_positions(posv, T))
            o, kp, vp = _paged_attention(q, k, v, kp, vp, bt, posv, rep,
                                         window=self.window)
            return o.transpose(0, 2, 1, 3).reshape(B, T, -1), kp, vp

        o, kp, vp = invoke_jnp(
            fn, (self.q_proj(x), self.k_proj(x), self.v_proj(x), pos, table,
                 k_pages, v_pages), {}, name="cohere_attention_paged")
        return self.o_proj(o), kp, vp


class Cohere2Experts(HybridBlock):
    """The expert branch: the held routed experts' part (``ops/moe.py``) and
    the shared experts, averaged. The shared experts are one gated MLP of
    ``num_shared_experts`` widths side by side (a sum over experts is a sum
    over the columns of one wider product), divided by their number."""

    def __init__(self, cfg: Cohere2MoEConfig):
        super().__init__()
        self.cfg = cfg
        D, F, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
        n, S = cfg.experts_held[1], cfg.num_shared_experts
        self.router = Parameter("router", shape=(D, E), dtype=cfg.dtype,
                                init="zeros")
        self.gate = Parameter("gate", shape=(n, D, F), dtype=cfg.dtype,
                              init="zeros")
        self.up = Parameter("up", shape=(n, D, F), dtype=cfg.dtype,
                            init="zeros")
        self.down = Parameter("down", shape=(n, F, D), dtype=cfg.dtype,
                              init="zeros")
        self.shared_gate_proj = _dense(S * F, D, cfg.dtype)
        self.shared_up_proj = _dense(S * F, D, cfg.dtype)
        self.shared_down_proj = _dense(D, S * F, cfg.dtype)

    def forward(self, h, valid=None):
        """``h`` [B, T, D]; ``valid`` [B, T] bool or None. Returns ``(m [B,
        T, D], tokens [held] int32)``."""
        cfg = self.cfg
        B, T, D = h.shape

        def routed(hv, wr, wg, wu, wd, *mask):
            y, tokens = _moe.routed_experts(
                hv.reshape(B * T, D), wr, wg, wu, wd,
                held=cfg.experts_held, k=cfg.num_experts_per_tok,
                score="sigmoid", normalize=True,
                valid=mask[0].reshape(B * T) if mask else None)
            return y.reshape(B, T, D), tokens

        with jax.named_scope("mx.moe"):
            y, tokens = invoke_jnp(
                routed, (h, self.router.data(), self.gate.data(),
                         self.up.data(), self.down.data())
                + (() if valid is None else (valid,)), {},
                name="cohere_routed_experts")
            with jax.named_scope("mx.moe_shared"):
                g = invoke_jnp(
                    lambda a, b: (jax.nn.silu(a.astype(jnp.float32))
                                  * b.astype(jnp.float32)).astype(a.dtype),
                    (self.shared_gate_proj(h), self.shared_up_proj(h)), {},
                    name="cohere_shared_gate")
                shared = self.shared_down_proj(g)
                m = invoke_jnp(
                    lambda r, s: (r.astype(jnp.float32) + s.astype(
                        jnp.float32) / cfg.num_shared_experts).astype(r.dtype),
                    (y, shared), {}, name="cohere_moe_combine")
        return m, tokens


def _residual(x, a, m):
    """``x + a + m``, added in float32 and rounded once."""
    return invoke_jnp(
        lambda xv, av, mv: (xv.astype(jnp.float32) + av.astype(jnp.float32)
                            + mv.astype(jnp.float32)).astype(xv.dtype),
        (x, a, m), {}, name="cohere_residual")


class Cohere2DecoderLayer(HybridBlock):
    def __init__(self, cfg: Cohere2MoEConfig, kind: str):
        super().__init__()
        self.input_layernorm = Cohere2LayerNorm(cfg.hidden_size,
                                                cfg.layer_norm_eps, cfg.dtype)
        self.self_attn = Cohere2Attention(cfg, kind)
        self.mlp = Cohere2Experts(cfg)

    def forward(self, x):
        h = self.input_layernorm(x)
        with jax.named_scope("mx.attn"):
            a = self.self_attn(h)
        m, _ = self.mlp(h)
        return _residual(x, a, m)

    def forward_cached_paged(self, x, pos, table, valid, k_pages, v_pages):
        h = self.input_layernorm(x)
        with jax.named_scope("mx.attn"):
            a, k_pages, v_pages = self.self_attn.forward_paged(
                h, pos, table, k_pages, v_pages)
        m, tokens = self.mlp(h, valid)
        return _residual(x, a, m), k_pages, v_pages, tokens


class Cohere2MoEModel(HybridBlock):
    def __init__(self, cfg: Cohere2MoEConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         dtype=cfg.dtype)
        self.layers = nn.HybridSequential()
        for kind in cfg.layer_types:
            self.layers.add(Cohere2DecoderLayer(cfg, kind))
        self.norm = Cohere2LayerNorm(cfg.hidden_size, cfg.layer_norm_eps,
                                     cfg.dtype)

    def forward(self, input_ids):
        with jax.named_scope("mx.embed"):
            x = self.embed_tokens(input_ids)
        for layer in self.layers._children.values():
            x = layer(x)
        return self.norm(x)

    def cache_kinds(self):
        """The kind of each pool, in ``cache_spec_paged``'s order: 0 the
        full layers', 1 the sliding layers'."""
        return [int(kind == SLIDING) for kind in self.cfg.layer_types
                for _ in range(2)]

    def forward_cached_paged(self, input_ids, pos, block_table, valid,
                             *caches):
        cfg = self.cfg
        T = input_ids.shape[1]
        with jax.named_scope("mx.embed"):
            x = self.embed_tokens(input_ids)
        # the two kinds' tables; a row is real where the full kind's table
        # starts at a leased page, a position where it is under ``valid``
        sink = caches[self.cache_kinds().index(0)].shape[0] - 1
        *tables, real = invoke_jnp(
            lambda bt, nv: (bt[:, 0], bt[:, 1],
                            (jnp.arange(T)[None, :] < nv[:, None])
                            & (bt[:, 0, :1] != sink)),
            (block_table, valid), {}, name="cohere_tables")
        new, counts = [], []
        for i, (kind, layer) in enumerate(zip(
                cfg.layer_types, self.layers._children.values())):
            x, kp, vp, tokens = layer.forward_cached_paged(
                x, pos, tables[kind == SLIDING], real, *caches[2 * i:2 * i + 2])
            new += [kp, vp]
            counts.append(tokens)
        counts = invoke_jnp(lambda *c: jnp.stack(c), tuple(counts), {},
                            name="cohere_expert_counts")
        return (self.norm(x), *new, counts)


class Cohere2MoEForCausalLM(HybridBlock):
    def __init__(self, cfg: Cohere2MoEConfig):
        super().__init__()
        if len(cfg.layer_types) != cfg.num_layers:
            raise MXNetError(
                f"Cohere2MoE: {len(cfg.layer_types)} layer_types for "
                f"{cfg.num_layers} layers")
        bad = set(cfg.layer_types) - {SLIDING, FULL}
        if bad:
            raise MXNetError(f"Cohere2MoE: unknown layer types {sorted(bad)}")
        first, count = cfg.experts_held
        if not (0 <= first and count >= 1
                and first + count <= cfg.num_experts):
            raise MXNetError(
                f"Cohere2MoE: experts_held {cfg.experts_held} is no range "
                f"of the router's {cfg.num_experts} experts")
        if cfg.num_heads % cfg.num_kv_heads:
            raise MXNetError("Cohere2MoE: num_heads must be a multiple of "
                             "num_kv_heads")
        if FULL not in cfg.layer_types:
            raise MXNetError("Cohere2MoE: no full_attention layer (the full "
                             "kind's table says which rows serve a request)")
        self.cfg = cfg
        self.model = Cohere2MoEModel(cfg)

    @jax.named_scope("mx.lm_head")
    def _logits(self, h):
        """Float32 logits of the tied head, times ``logit_scale``."""
        scale = self.cfg.logit_scale
        return invoke_jnp(
            lambda hv, w: jnp.einsum(
                "btd,vd->btv", hv, w,
                preferred_element_type=jnp.float32) * scale,
            (h, self.model.embed_tokens.weight.data()), {},
            name="cohere_lm_head")

    def forward(self, input_ids):
        return self._logits(self.model(input_ids))

    # ------------------------------------------------------ cache protocol
    def cache_window(self) -> int:
        """The sliding layers' window: their pools are a kind of their own
        (module docstring)."""
        return self.cfg.sliding_window

    def cache_kinds(self):
        return self.model.cache_kinds()

    def cache_spec_paged(self, num_pages, page_size: int):
        """[(shape, dtype)] in layer order, a K and a V pool a layer:
        ``[pages, page_size, kv_heads * hd]``; ``num_pages`` a number, or
        ``(full, windowed)`` pages for the two kinds."""
        cfg = self.cfg
        pages = (num_pages if isinstance(num_pages, (tuple, list))
                 else (num_pages, num_pages))
        return [((int(pages[kind]), page_size,
                  cfg.num_kv_heads * cfg.head_dim), cfg.dtype)
                for kind in self.cache_kinds()]

    def expert_counts(self):
        """``(layers, held, experts a token)``: the shape of the counts that
        ``forward_cached_paged`` returns behind the pools, and how many
        assignments a token makes in a layer."""
        cfg = self.cfg
        return (cfg.num_layers, cfg.experts_held[1], cfg.num_experts_per_tok)

    def walk_form(self, T: int) -> str:
        return walk_form(self.cfg.num_heads, T, self.cfg.head_dim)

    def forward_cached_paged(self, input_ids, pos, block_table, valid,
                             *caches):
        h, *new = self.model.forward_cached_paged(
            input_ids, pos, block_table, valid, *caches)
        return (self._logits(h), *new)
