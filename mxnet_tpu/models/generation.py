"""Autoregressive text generation for the causal-LM families.

No reference analogue (the reference predates LLM serving); designed
TPU-first: the whole decode loop is ONE compiled executable
(``lax.fori_loop`` over a fixed-size token buffer), so shapes stay static
and there is exactly one dispatch per ``generate`` call regardless of
length.

Two decode strategies:

- **KV-cache incremental decode** (default when the model exposes the
  ``cache_spec``/``forward_cached`` protocol — Llama and GPT families):
  one prefill forward fills [B, H, L, hd] K/V caches, then each new token
  is a single-token forward attending against the cache — O(L) work per
  step. Caches are ``fori_loop`` carries, so XLA keeps them on-device and
  updates them in place (``dynamic_update_slice`` aliasing).
- **cache-free** fallback: each step re-runs the model over the full
  padded buffer and reads the logits at the current position — correct
  for causal models and needed for stacked/pipeline decoders.

Supports greedy decoding, temperature sampling, top-k filtering, and
nucleus (top-p) filtering.

The cached-decode body is factored into reusable pieces —
:func:`decode_step` (one incremental forward through the cache protocol)
and :func:`filter_logits`/:func:`sample_tokens` (top-k/top-p/temperature
selection that accepts static scalars OR per-row arrays) — which the
serving engine (``mxnet_tpu/serve``) drives directly for continuous
batching.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as onp

from ..analysis import guards as _guards
from ..base import MXNetError
from ..ndarray import NDArray, invoke_jnp
from ..parallel.functional import functionalize

__all__ = ["generate", "clear_cache", "decode_step", "decode_multi_tokens",
           "embed_operand", "filter_logits", "sample_tokens",
           "spec_verify_tokens"]

# Bounded LRU cache of compiled decode loops (jit is keyed on function
# identity; without this every generate() call would recompile). Entries
# strongly reference their model (the traced closure needs it), so the
# cache is LRU-bounded and clearable rather than weak. Guarded by a lock:
# server threads call generate() concurrently (serve/http.py handlers).
_DECODE_CACHE: "OrderedDict" = OrderedDict()
_DECODE_CACHE_LIMIT = 8
_DECODE_CACHE_LOCK = _guards.make_lock("generation._DECODE_CACHE_LOCK")


def clear_cache():
    """Drop all cached decode executables (and their model references)."""
    with _DECODE_CACHE_LOCK:
        _DECODE_CACHE.clear()


def _can_cache(model) -> bool:
    """True if the model exposes the KV-cache protocol (cache_spec +
    forward_cached) and its current config supports it."""
    if not (hasattr(model, "cache_spec") and hasattr(model, "forward_cached")):
        return False
    try:
        model.cache_spec(1, 8)
    except MXNetError:
        # the documented unsupported-config signal (MoE / pp / sp configs);
        # anything else is a real bug in cache_spec and must propagate
        # (ADVICE r2 #3)
        return False
    return True


def _validate_sampling(temperature, top_k, top_p):
    """Shared sampling-argument validation (generate() and the serving
    engine's submit())."""
    if not temperature >= 0:          # NaN-proof: 'NaN < 0' is also False
        raise MXNetError(f"temperature must be >= 0, got {temperature}")
    if int(top_k) != top_k or top_k < 0:
        raise MXNetError(f"top_k must be a non-negative integer (0 disables "
                         f"top-k filtering), got {top_k}")
    if not 0.0 < top_p <= 1.0:
        raise MXNetError(f"top_p must be in (0, 1], got {top_p}")


def _check_mask_live(mask):
    """All-masked rows are a caller bug (an automaton dead end the
    coaccessible trim should have made impossible): with a CONCRETE mask
    raise a diagnosable error instead of letting the -inf argmax silently
    emit token 0. Traced masks skip the check (no host sync inside an
    executable — the serving engine guards its masks host-side)."""
    if isinstance(mask, jax.core.Tracer):
        return
    m = jnp.asarray(mask)
    live = jax.device_get(m.any(axis=-1))
    if not bool(live.all()):
        import numpy as onp
        rows = onp.nonzero(~onp.atleast_1d(live))[0].tolist()
        raise MXNetError(
            f"grammar mask allows NO token for row(s) {rows[:8]} — the "
            "automaton reached a dead end (every vocab token and EOS "
            "forbidden); this indicates a grammar whose token table "
            "cannot spell the remaining language, or a corrupted "
            "automaton state")


# bits of the threshold that one pass of _greatest_key settles: 2**bits - 1
# pivots a pass, 32 / bits passes. Timed on the chip (PERF.md section 6,
# PR 33): 2 bits are quicker by a third at 2-4 rows, 4 bits half the device
# operations a program (the benchmark's traced window keeps a fixed number)
_SEARCH_BITS = 4


def _order_key(x):
    """int32 keys that compare as the float32 ``x`` do (-0.0 beside 0.0)."""
    i = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


def _greatest_key(key, measure, target):
    """Per row of ``key`` [B, V], the greatest int32 ``t`` for which
    ``measure(key >= t) >= target`` ([B, 1]): ``measure`` reduces a
    [B, P, V] mask over V and must not grow as the mask shrinks, so the
    answer is one of the row's keys and is built from its top bit down,
    ``_SEARCH_BITS`` bits a pass. No sort: a pass is one masked reduction
    over the row, and a row's answer depends on that row alone."""
    bits = _SEARCH_BITS
    sign = jnp.uint32(0x80000000)
    rows = key.shape[0]
    if rows == 1:
        # a TPU tiles [1, P, V] worse than [2, P, V] (a third slower, and
        # one row is a final prefill and most decode steps): the row twice
        key = jnp.concatenate([key, key])
    # [passes, P]: the digits 1 .. 2**bits - 1 at each pass's place
    places = (onp.arange(1, 2 ** bits, dtype=onp.uint32)[None, :]
              << onp.arange(32 - bits, -1, -bits, dtype=onp.uint32)[:, None])

    def one_pass(i, t):
        cand = t | jnp.asarray(places)[i][None, :]                  # [B, P]
        # built as unsigned from the top bit down, compared as signed
        at = jax.lax.bitcast_convert_type(cand ^ sign, jnp.int32)
        ok = measure(key[:, None, :] >= at[:, :, None]) >= target   # [B, P]
        # ok falls from True to False along P: the last True is the digit
        return jnp.max(jnp.where(ok, cand, t), axis=-1, keepdims=True)

    t = jax.lax.fori_loop(0, 32 // bits, one_pass,
                          jnp.zeros((key.shape[0], 1), jnp.uint32))
    return jax.lax.bitcast_convert_type(t ^ sign, jnp.int32)[:rows]


def _where_any(asked, search, rows):
    """``search()`` ([rows, 1] int32 keys) if any row asks for it, else
    zeros nothing reads: a skip for the whole batch, which leaves the
    arithmetic of a row that asks as it is."""
    need = jnp.any(asked)
    if not isinstance(need, jax.core.Tracer):   # python scalars: generate()
        return search() if bool(need) else jnp.zeros((rows, 1), jnp.int32)
    return jax.lax.cond(need, search,
                        lambda: jnp.zeros((rows, 1), jnp.int32))


@jax.named_scope("mx.sample")
def filter_logits(scaled, top_k, top_p, mask=None):
    """Top-k then nucleus (top-p) filtering of [B, V] logits: filtered-out
    entries become -inf. ``top_k``/``top_p`` accept python scalars (static,
    baked into the trace — generate()) or int32/float32 arrays of shape
    [B] (per-row dynamic — the serving engine's heterogeneous batches).
    ``top_k <= 0`` and ``top_p >= 1`` disable the respective filter.

    ``mask`` (optional bool [B, V] or [V], True = allowed) applies a
    grammar constraint BEFORE the filters, so top-k counts and the
    nucleus mass are computed over the legal tokens only — a constrained
    row can never end up with every survivor masked out.

    The kept set needs no order. Top-k keeps a token iff fewer than ``k``
    tokens are strictly greater (ties with the k-th kept); the nucleus,
    over the softmax of what top-k left, keeps a token iff the mass of the
    tokens strictly greater than it is under ``top_p`` (the argmax and ties
    at the edge kept). Each is ``logit >= threshold``, and each threshold
    is the greatest value that still has ``k`` tokens, or ``top_p`` of the
    mass, at or above it: :func:`_greatest_key` finds it by masked
    reductions over the row, where a sort of the vocabulary was a tenth of
    a decode step (and ``lax.top_k`` is the same sort on a TPU)."""
    if mask is not None:
        _check_mask_live(mask)
        scaled = jnp.where(mask, scaled, -jnp.inf)
    B, V = scaled.shape
    top_k = jnp.reshape(jnp.asarray(top_k, jnp.int32), (-1, 1))     # [B|1, 1]
    top_p = jnp.reshape(jnp.asarray(top_p, jnp.float32), (-1, 1))
    key = _order_key(scaled)
    kth = _where_any(
        top_k > 0,
        lambda: _greatest_key(
            key, lambda ge: jnp.sum(ge, axis=-1, dtype=jnp.int32),
            jnp.clip(top_k, 1, V)), B)
    keep_k = (top_k <= 0) | (key >= kth)

    def nucleus():
        # over the post-top-k distribution, unnormalised: mass >= top_p * all
        mass = jnp.where(keep_k, jnp.exp(
            scaled - jnp.max(scaled, axis=-1, keepdims=True)), 0.0)  # [B, V]
        return _greatest_key(
            key, lambda ge: jnp.sum(
                jnp.where(ge, mass[:, None, :], 0.0), axis=-1),
            top_p * jnp.sum(mass, axis=-1, keepdims=True))

    thr = _where_any(top_p < 1.0, nucleus, B)
    keep = keep_k & ((top_p >= 1.0) | (key >= thr))
    return jnp.where(keep, scaled, -jnp.inf)


@jax.named_scope("mx.sample")
def sample_tokens(logits, keys, temperature, top_k, top_p, mask=None):
    """Batched next-token selection from [B, V] logits with PER-ROW
    sampling parameters: rows with ``temperature == 0`` are greedy, the
    rest are temperature/top-k/top-p sampled with their own PRNG key.
    ``keys`` is a [B] typed PRNG-key array. The serving engine's decode
    step uses this so one executable serves any mix of requests.

    ``mask`` (optional bool [B, V], True = allowed) constrains BOTH
    paths: the greedy argmax runs over the masked logits (never a silent
    raw argmax past the grammar) and the sampling path masks before
    scaling/filtering, so top_k >= V and top_p = 1.0 still only ever
    select legal tokens."""
    logits = logits.astype(jnp.float32)
    if mask is not None:
        _check_mask_live(mask)
        logits = jnp.where(mask, logits, -jnp.inf)
    t = jnp.reshape(jnp.asarray(temperature, jnp.float32), (-1, 1))
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.where(t > 0, t, 1.0)
    # a greedy row asks for no filter: its sample is not read, and a batch
    # of such rows skips filter_logits' search whole
    top_k = jnp.where(t > 0, jnp.reshape(jnp.asarray(top_k, jnp.int32),
                                         (-1, 1)), 0)
    top_p = jnp.where(t > 0, jnp.reshape(jnp.asarray(top_p, jnp.float32),
                                         (-1, 1)), 1.0)
    filt = filter_logits(scaled, top_k, top_p)
    sampled = jax.vmap(jax.random.categorical)(keys, filt).astype(jnp.int32)
    return jnp.where(jnp.reshape(t > 0, (-1,)), sampled, greedy_tok)


def spec_verify_tokens(logits, inputs, temps, topks, topps, seeds, counters,
                       masks=None):
    """Exact self-speculative verification of one drafted batch.

    ``logits`` [B, T, V] is the verify forward's output over the inputs
    ``[t0, d_1, ..., d_{T-1}]`` (the current token followed by T-1 draft
    tokens); ``inputs`` is that same [B, T] matrix. Column j's logits are
    bitwise-identical to what the sequential one-token-at-a-time decode
    would compute at that position (the same T-invariance the chunked-
    prefill parity contract rests on), and the per-row sampling streams
    are STATELESS (``fold_in(key(seed), counter + j)``) — so
    ``toks[:, j] = sample_tokens(logits[:, j], key_j, ...)`` is EXACTLY
    the token the non-speculative path would emit at counter
    ``counters + j``, greedy or sampled. Acceptance is therefore plain
    equality against the draft: the emitted sequence can never differ
    from the non-speculative path, which makes the scheme token-exact
    (the degenerate-but-exact form of rejection sampling — the draft
    distribution puts mass 1 on the looked-up token, and a mismatch
    rejects it in favor of the true sample).

    Returns ``(toks [B, T] int32, acc [B] int32)``: ``toks[:, :acc]``
    are the row's valid tokens this round — the accepted draft prefix
    plus the one correction/bonus token — so ``acc`` is in [1, T].

    The T per-position selections run as ONE flattened [B*T, V]
    ``sample_tokens`` call (one search, one categorical sweep instead of
    T): every op in the selection chain is row-wise, so the packing is
    bitwise-invisible — the parity contract survives the batching.

    ``masks`` (optional bool [B, T, V]) applies a per-position grammar
    constraint: column j's selection masks with ``masks[:, j]`` — the
    exact mask the non-speculative constrained path would apply at that
    position (the engine walks the automaton along the draft), so a
    grammar-forbidden draft token is rejected precisely as a mismatched
    token is and constraints × speculation stay token-identical."""
    B, T, V = logits.shape
    cgrid = counters[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    keys = _fold_keys(jnp.repeat(seeds, T), cgrid.reshape(-1))
    toks = sample_tokens(logits.reshape(B * T, V), keys,
                         jnp.repeat(temps, T), jnp.repeat(topks, T),
                         jnp.repeat(topps, T),
                         mask=(None if masks is None
                               else masks.reshape(B * T, V))).reshape(B, T)
    if T == 1:
        return toks, jnp.ones((B,), jnp.int32)
    match = toks[:, :-1] == inputs[:, 1:]                      # [B, T-1]
    # leading-True run length = accepted drafts; +1 for the correction/
    # bonus token every round emits
    lead = jnp.cumprod(match.astype(jnp.int32), axis=1)
    return toks, (1 + jnp.sum(lead, axis=1)).astype(jnp.int32)


def embed_operand(fm, param_vals):
    """``(table [vocab, lanes],)``: the embedding's table with its rows
    padded to whole tiles of 128 lanes, where the model names its table
    (``embed_table()``) and its width is not a multiple of 128; else ``()``.
    The serving engine makes it once for each set of weights it takes and
    holds it behind them; :func:`decode_step` hands it to the cached forward
    as its last operand, and the embedding gathers its rows from it
    (:func:`embed_lookup`).

    Why: a TPU holds a ``[vocab, hidden]`` table whose width is not whole
    lane tiles (GPT-2 XL: 1,600 is 12.5) with the vocabulary minor-most,
    whatever reads it. That is how the head's product wants it, tied or
    not, but the embedding's gather wants rows, and relays all of
    2 * vocab * hidden bytes out in every program, a tenth of a decode
    step. With rows of whole tiles the chip keeps the table as declared and
    gathers from it where it lies; at a lane multiple it does so anyway,
    and no second table is held."""
    named = getattr(fm.block, "embed_table", lambda: None)()
    if named is None:
        return ()
    table = param_vals[next(i for i, p in enumerate(fm.params) if p is named)]
    pad = -table.shape[1] % 128
    return (jnp.pad(table, ((0, 0), (0, pad))),) if pad else ()


def embed_lookup(embedding, input_ids, table=None):
    """``embedding(input_ids)``, its rows gathered from the padded ``table``
    of :func:`embed_operand` where a serving program brings one."""
    if table is None:
        return embedding(input_ids)
    width = embedding.weight.shape[1]
    return invoke_jnp(
        lambda t, i: jnp.take(t, i.astype(jnp.int32), axis=0)[..., :width],
        (table, input_ids), {}, name="embedding")


def _apply_cached(fm, param_vals, method, inputs, caches):
    """One cached forward: the model's parameters from the front of
    ``param_vals``; what the engine holds behind them
    (:func:`embed_operand`) goes to the forward behind the caches."""
    n = len(fm.params)
    out, _aux = fm.apply(list(param_vals[:n]), *inputs, *caches,
                         *param_vals[n:], seed=0, training=False,
                         method=method)
    return out[0], tuple(out[1:])


def decode_step(fm, param_vals, tokens, pos, caches, block_table=None,
                rows=None):
    """One incremental forward through the KV-cache protocol: attend
    ``tokens`` [B, T] at offset(s) ``pos`` (scalar, or [B] for per-row
    offsets — continuous batching) against ``caches``. Returns
    ``(logits [B, T, V], new_caches)``. Traceable; the single step both
    generate()'s fori_loop body and the serving engine drive.

    With ``block_table`` [B, max_pages] the step routes through the
    model's ``forward_cached_paged`` entry point instead: ``caches`` are
    then the shared page pools and every row addresses its KV rows
    through its table (serve/paging). A model that keeps recurrent state
    beside its pages (``cache_spec_state``) also takes ``rows``: each row's
    slot in the state pools and how many of its T positions are real."""
    if block_table is None:
        return _apply_cached(fm, param_vals, "forward_cached",
                             (tokens, pos), caches)
    return _apply_cached(fm, param_vals, "forward_cached_paged",
                         (tokens, pos, block_table) + tuple(rows or ()),
                         caches)


def decode_step_hidden(fm, param_vals, tokens, pos, caches,
                       block_table=None):
    """Like :func:`decode_step` but through the model's
    ``forward_cached_hidden`` (or ``forward_cached_paged_hidden``) entry
    point: returns the final hidden state [B, T, D] instead of logits, so
    the fused LM-head sampling kernel (ops/fused_block_gemv.
    fused_lm_head_sample) can fold the head GEMV into token selection
    without materializing [B, V] logits."""
    if block_table is None:
        return _apply_cached(fm, param_vals, "forward_cached_hidden",
                             (tokens, pos), caches)
    return _apply_cached(fm, param_vals, "forward_cached_paged_hidden",
                         (tokens, pos, block_table), caches)


def _fold_keys(seeds, counters):
    """[B] typed keys: fold_in(key(per-row seed), per-row counter) — the
    stateless stream that makes device-side sampling reproduce the host
    engine's per-request sampling exactly."""
    return jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.key(s), c)
    )(seeds, counters)


def decode_multi_tokens(fm, param_vals, tokens, pos, caches, num_tokens,
                        temps, topks, topps, seeds, counters,
                        eos_ids=None, remaining=None, done=None,
                        fill_eos=False, head=None, block_table=None):
    """Emit up to ``num_tokens`` (K, static) tokens in ONE dispatch with
    DEVICE-SIDE sampling: a ``lax.while_loop`` whose body is one
    incremental forward + per-row ``fold_in(key(seed), counter + j)``
    sampling, feeding each sampled token straight back in. This is the
    multi-token decode loop that collapses K host round-trips into one
    (ROADMAP item 2); the serving engine surfaces the K-token vector per
    dispatch and scans it for EOS/deadline on the host.

    - ``tokens`` [B]: the previous token per row; ``pos`` scalar or [B].
    - ``temps/topks/topps/seeds/counters`` [B]: per-row sampling state
      (data, not trace constants — one executable serves any mix).
    - ``eos_ids`` [B] int32 (-1 = no eos): a row that emits its eos is
      DONE; when every row is done the loop exits early (``steps`` < K).
    - ``remaining`` [B]: token budget per row; a row is done once it
      emitted that many (its later in-flight samples are speculative and
      discarded by the caller).
    - ``done`` [B] bool: initial done mask (rows already finished).
    - ``fill_eos``: generate() semantics — done rows keep emitting eos
      and the loop always runs the full K (no early exit), so the output
      buffer is completely filled.
    - ``head``: optional ``(w_q [Vp, D] int8, scales [Vp], vocab)`` — use
      ``forward_cached_hidden`` + the fused LM-head sampler instead of
      materializing logits.

    Returns ``(toks [B, K] int32, last [B] int32, steps int32 scalar,
    done [B] bool, new_caches)``; columns >= ``steps`` of ``toks`` are
    unwritten (zeros)."""
    B = tokens.shape[0]
    K = int(num_tokens)
    temps = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(temps, jnp.float32), (-1,)), (B,))
    topks = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(topks, jnp.int32), (-1,)), (B,))
    topps = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(topps, jnp.float32), (-1,)), (B,))
    seeds = jnp.asarray(seeds, jnp.uint32)
    counters = jnp.asarray(counters, jnp.int32)
    eos_vec = (jnp.full((B,), -1, jnp.int32) if eos_ids is None
               else jnp.broadcast_to(jnp.asarray(eos_ids, jnp.int32), (B,)))
    rem = None if remaining is None else jnp.asarray(remaining, jnp.int32)
    done0 = (jnp.zeros((B,), bool) if done is None
             else jnp.asarray(done, bool))
    pos = jnp.asarray(pos, jnp.int32)

    def step_state(tok, posj, caches):
        if head is None:
            logits, caches = decode_step(fm, param_vals, tok[:, None],
                                         posj, caches,
                                         block_table=block_table)
            return logits[:, -1], caches
        hidden, caches = decode_step_hidden(fm, param_vals, tok[:, None],
                                            posj, caches,
                                            block_table=block_table)
        return hidden[:, -1], caches

    def sample(state, keys):
        if head is None:
            return sample_tokens(state, keys, temps, topks, topps)
        from ..ops.fused_block_gemv import fused_lm_head_sample
        w_q, scale, vocab = head
        return fused_lm_head_sample(state, w_q, scale, vocab, keys, temps,
                                    topks, topps, out_dtype=state.dtype)

    def body(carry):
        j, tok, dn, out, caches = carry
        state, caches = step_state(tok, pos + j, caches)
        keys = _fold_keys(seeds, counters + j)
        nxt = sample(state, keys)
        if fill_eos:
            # generate() semantics: after eos a row keeps emitting eos
            nxt = jnp.where(dn & (eos_vec >= 0), eos_vec, nxt)
        newly = nxt == eos_vec
        if rem is not None:
            newly = newly | (j + 1 >= rem)
        out = jax.lax.dynamic_update_slice(out, nxt[:, None],
                                           (jnp.int32(0), j))
        return (j + jnp.int32(1), nxt, dn | newly, out, caches)

    def cond(carry):
        j = carry[0]
        if fill_eos:
            return j < K
        return (j < K) & ~jnp.all(carry[2])

    init = (jnp.int32(0), jnp.asarray(tokens, jnp.int32), done0,
            jnp.zeros((B, K), jnp.int32), caches)
    steps, last, done_out, out, caches = jax.lax.while_loop(cond, body, init)
    return out, last, steps, done_out, caches


def _record_compile(model):
    """Telemetry for a new decode-loop compilation (metrics are no-ops
    while collection is disabled). kind follows CachedOp semantics:
    'initial' for the model's first decode trace, 'retrace' afterwards."""
    from .. import metrics as _metrics
    if not _metrics.ENABLED:
        return
    with _DECODE_CACHE_LOCK:
        seen = any(k[0] == id(model) for k in _DECODE_CACHE)
    _metrics.RECOMPILATIONS.labels(
        block="generate", kind="retrace" if seen else "initial").inc()


def _row_seeds(seed: int, B: int):
    """Per-row uint32 seeds for generate()'s multi-token fold_in streams
    (deterministic in ``seed``; distinct per batch row)."""
    import numpy as onp
    base = onp.uint32((int(seed) * 0x9E3779B1) & 0xFFFFFFFF)
    return (base + onp.arange(B, dtype=onp.uint32)) & onp.uint32(0xFFFFFFFF)


def generate(model, input_ids, max_new_tokens: int,
             eos_token_id: Optional[int] = None,
             temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
             seed: int = 0, use_cache: Optional[bool] = None,
             multi_token: int = 1):
    """Generate ``max_new_tokens`` continuations of ``input_ids`` [B, P].

    ``temperature==0`` is greedy; otherwise softmax sampling at the given
    temperature, optionally restricted to the ``top_k`` highest logits
    and/or the nucleus of tokens whose cumulative probability reaches
    ``top_p``. After ``eos_token_id`` is emitted, a sequence keeps
    emitting eos (simple static-shape semantics).
    Returns [B, P + max_new_tokens].

    ``use_cache`` selects KV-cache incremental decode (prefill once, then
    one single-token step per new token — O(L) attention per step instead
    of a full O(L²) re-forward). Default: on whenever the model exposes
    the cache protocol (``cache_spec``/``forward_cached``); the cache-free
    path re-runs the full padded forward each step. Both run the whole
    decode loop as ONE compiled executable (``lax.fori_loop``).

    ``multi_token`` > 1 routes the cached decode loop through the fused
    whole-step path (:func:`decode_multi_tokens`): K tokens per loop
    iteration with device-side sampling and, when the model carries an
    int8 tied head, the fused LM-head sampler. Greedy output is
    bitwise-identical to ``multi_token=1``; sampled output follows the
    serving engine's per-row ``fold_in`` streams instead of the
    split-chain stream, so it differs from ``multi_token=1`` (but is
    deterministic in ``seed`` and matches the engine's fused path).
    """
    if max_new_tokens <= 0:
        raise MXNetError("max_new_tokens must be positive")
    _validate_sampling(temperature, top_k, top_p)
    multi_token = int(multi_token)
    if multi_token < 1:
        raise MXNetError("multi_token must be >= 1")
    ids = input_ids if isinstance(input_ids, NDArray) else NDArray(input_ids)
    B, P = ids.shape
    L = P + max_new_tokens
    max_pos = getattr(getattr(model, "cfg", None),
                      "max_position_embeddings", None)
    if max_pos is not None and L > max_pos:
        raise MXNetError(
            f"generate: prompt ({P}) + max_new_tokens ({max_new_tokens}) "
            f"= {L} exceeds the model's max_position_embeddings "
            f"({max_pos})")
    if use_cache is None:
        use_cache = _can_cache(model)
    elif use_cache and not _can_cache(model):
        raise MXNetError(
            "use_cache=True but the model does not expose the KV-cache "
            "protocol (cache_spec/forward_cached), or its config (stacked/"
            "pipeline decoder) does not support it")
    if multi_token > 1 and not use_cache:
        raise MXNetError(
            "multi_token > 1 requires KV-cache decode (the fused "
            "whole-step path drives the cache protocol)")

    padded = jnp.zeros((B, L), jnp.int32).at[:, :P].set(
        ids._data.astype(jnp.int32))
    greedy = temperature == 0.0
    cache_key = (id(model), B, P, max_new_tokens, greedy,
                 float(temperature), int(top_k), float(top_p), eos_token_id,
                 use_cache, multi_token)
    carrier = (jax.random.key(seed) if multi_token == 1
               else _row_seeds(seed, B))
    with _DECODE_CACHE_LOCK:
        cached = _DECODE_CACHE.get(cache_key)
        if cached is not None:
            _DECODE_CACHE.move_to_end(cache_key)    # LRU: refresh on hit
    if cached is not None:
        fm, jitted = cached
        values = tuple(fm.values())
        out = jitted(values, padded, carrier)
        return NDArray(out)

    _record_compile(model)
    fm = functionalize(model, NDArray(padded), training=False)
    values = tuple(fm.values())

    def select(step_logits, key, done):
        """Next token from [B, V] logits (greedy or temperature/top-k/p)."""
        step_logits = step_logits.astype(jnp.float32)
        if greedy:
            nxt = jnp.argmax(step_logits, axis=-1)
        else:
            scaled = step_logits / temperature
            if top_k > 0 or top_p < 1.0:
                scaled = filter_logits(scaled, int(top_k), float(top_p))
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, scaled, axis=-1)
        nxt = nxt.astype(jnp.int32)
        if eos_token_id is not None:
            nxt = jnp.where(done, jnp.int32(eos_token_id), nxt)
            done = done | (nxt == eos_token_id)
        return nxt, key, done

    def decode_nocache(param_vals, buf, key):
        def body(i, carry):
            buf, key, done = carry
            out, _aux = fm.apply(list(param_vals), buf, seed=0,
                                 training=False)
            logits = out[0] if isinstance(out, (tuple, list)) else out
            pos = P + i - 1
            step_logits = jax.lax.dynamic_index_in_dim(
                logits, pos, axis=1, keepdims=False)      # [B, V]
            nxt, key, done = select(step_logits, key, done)
            buf = jax.lax.dynamic_update_index_in_dim(
                buf, nxt, pos + 1, axis=1)
            return (buf, key, done)

        done0 = jnp.zeros((B,), bool)
        buf, _, _ = jax.lax.fori_loop(0, max_new_tokens, body,
                                      (buf, key, done0))
        return buf

    def decode_cached(param_vals, buf, key):
        caches = tuple(jnp.zeros(s, d) for s, d in model.cache_spec(B, L))
        # prefill: one forward over the prompt fills cache rows [0, P)
        logits, caches = decode_step(fm, param_vals, buf[:, :P],
                                     jnp.int32(0), caches)
        done0 = jnp.zeros((B,), bool)
        nxt, key, done = select(logits[:, -1], key, done0)
        buf = jax.lax.dynamic_update_index_in_dim(buf, nxt, P, axis=1)

        def body(i, carry):
            buf, caches, key, done = carry
            pos = P + i
            x = jax.lax.dynamic_slice(buf, (0, pos), (B, 1))
            logits, caches = decode_step(fm, param_vals, x, pos, caches)
            nxt, key, done = select(logits[:, 0], key, done)
            buf = jax.lax.dynamic_update_index_in_dim(buf, nxt, pos + 1,
                                                      axis=1)
            return (buf, caches, key, done)

        buf, _, _, _ = jax.lax.fori_loop(0, max_new_tokens - 1, body,
                                         (buf, caches, key, done))
        return buf

    # python scalars, resolved OUTSIDE the traced fns below (mxlint MX001:
    # int()/float() inside a jitted fn read as host syncs)
    _topk_i, _topp_f = int(top_k), float(top_p)
    _eos_i = -1 if eos_token_id is None else int(eos_token_id)

    def decode_cached_multi(param_vals, buf, seeds_vec):
        """Cached decode through the fused whole-step path: K tokens per
        loop iteration via decode_multi_tokens (device-side sampling,
        fused LM head when the model carries an int8 tied table). The
        token buffer and caches are padded to whole chunks; the tail is
        sliced off at the end."""
        K = multi_token
        chunks = -(-(max_new_tokens - 1) // K) if max_new_tokens > 1 else 0
        Lbuf = P + 1 + chunks * K
        head = model.head_weights() \
            if (hasattr(model, "head_weights")
                and hasattr(model, "forward_cached_hidden")) else None
        caches = tuple(jnp.zeros(s, d)
                       for s, d in model.cache_spec(B, Lbuf))
        buf = jnp.zeros((B, Lbuf), jnp.int32) \
            .at[:, :L].set(buf)
        temps_v = jnp.full((B,), temperature, jnp.float32)
        topks_v = jnp.full((B,), _topk_i, jnp.int32)
        topps_v = jnp.full((B,), _topp_f, jnp.float32)
        eos_vec = jnp.full((B,), _eos_i, jnp.int32)
        # prefill + token0 (counter 0 of every row's fold_in stream)
        if head is None:
            logits, caches = decode_step(fm, param_vals, buf[:, :P],
                                         jnp.int32(0), caches)
            state0 = logits[:, -1]
        else:
            hidden, caches = decode_step_hidden(fm, param_vals, buf[:, :P],
                                                jnp.int32(0), caches)
            state0 = hidden[:, -1]
        keys0 = _fold_keys(seeds_vec, jnp.zeros((B,), jnp.int32))
        if head is None:
            tok0 = sample_tokens(state0, keys0, temps_v, topks_v, topps_v)
        else:
            from ..ops.fused_block_gemv import fused_lm_head_sample
            tok0 = fused_lm_head_sample(state0, head[0], head[1], head[2],
                                        keys0, temps_v, topks_v, topps_v,
                                        out_dtype=state0.dtype)
        buf = jax.lax.dynamic_update_index_in_dim(buf, tok0, P, axis=1)
        done0 = tok0 == eos_vec

        def chunk(c, carry):
            buf, caches, tok, done = carry
            toks, last, _, done, caches = decode_multi_tokens(
                fm, param_vals, tok, jnp.int32(P) + c * K, caches, K,
                temps_v, topks_v, topps_v, seeds_vec,
                jnp.full((B,), 1, jnp.int32) + c * K,
                eos_ids=eos_vec, done=done, fill_eos=True, head=head)
            buf = jax.lax.dynamic_update_slice(
                buf, toks, (jnp.int32(0), jnp.int32(P + 1) + c * K))
            return (buf, caches, last, done)

        buf, _, _, _ = jax.lax.fori_loop(0, chunks, chunk,
                                         (buf, caches, tok0, done0))
        return buf[:, :L]

    if multi_token > 1:
        jitted = jax.jit(decode_cached_multi)
    else:
        jitted = jax.jit(decode_cached if use_cache else decode_nocache)
    with _DECODE_CACHE_LOCK:
        raced = _DECODE_CACHE.get(cache_key)
        if raced is not None:
            # another thread compiled the same key first — keep its entry
            # (and its traced fm) so both callers share one executable
            fm, jitted = raced
            values = tuple(fm.values())
            _DECODE_CACHE.move_to_end(cache_key)
        else:
            while len(_DECODE_CACHE) >= _DECODE_CACHE_LIMIT:
                _DECODE_CACHE.popitem(last=False)   # evict least-recent
            _DECODE_CACHE[cache_key] = (fm, jitted)
    out = jitted(values, padded, carrier)
    return NDArray(out)
