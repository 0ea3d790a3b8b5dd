"""Continuous-batching inference engine over the KV-cache decode protocol.

The serving layer the ROADMAP north star asks for ("serve heavy traffic"):
one model + one slot-based KV-cache pool + ONE compiled decode-step
executable per shape bucket, amortized across every concurrent request
(the TensorFlow-serving argument, PAPERS 1605.08695: throughput comes from
keeping a single static-shape executable hot, not from per-request graphs).

Architecture (vLLM-style continuous batching, TPU-static shapes):

- **Slots.** The engine owns ``max_batch_size`` slots: rows of the decode
  batch, each with a block table into the page pools (below). A request
  occupies one slot from prefill to completion; finished slots are
  refilled from the queue *mid-flight* — the batch never drains to refill.
- **Prefill** runs per-request at batch 1 over a power-of-two
  prompt-length bucket (right-padded; pad rows are masked/overwritten so
  they never contaminate attention), writes the slot's pages, and samples
  token0 (time-to-first-token).
- **Decode** advances ALL active slots one token per step with a single
  executable: per-slot positions (models accept vector ``pos``), per-slot
  sampling params (temperature/top-k/top-p as data, not trace constants)
  and per-slot ``fold_in(key(seed), n)`` PRNG — so one executable serves
  any request mix, deterministically per request. The batch dimension is
  bucketed to the power-of-two active-slot prefix.
- **Decode lookahead** (``lookahead=True``, default): the loop dispatches
  decode step N+1 — feeding step N's *device-resident* token vector
  straight back in — before host-reading step N's tokens, so the D2H sync
  (started early with ``copy_to_host_async``) overlaps the next step's
  compute instead of serializing with it. This attacks inter-token
  latency directly: the host read was the one per-token round trip left.
  Retires and slot refills are detected one step late (the read that
  notices EOS lands after step N+1 was dispatched); the boundary is
  handled by draining the pipeline — the speculative step's tokens for
  retired slots are discarded and its cache writes are overwritten by the
  next prefill — so EOS semantics and greedy output are token-for-token
  identical to the synchronous engine (tier-1 parity tests).
- **Admission control.** Bounded FIFO queue (``QueueFullError``
  backpressure), per-request deadlines (expired requests complete with
  whatever tokens they have — partial output), cancellation, and graceful
  shutdown that drains in-flight slots.
- **Paged KV, the engine's one cache layout**, on every backend: one
  pooled cache of fixed-size pages (``model.cache_spec_paged``) plus a
  host-side :class:`~mxnet_tpu.serve.paging.PagePool` ledger. Slots lease
  pages on demand as their decode position advances — a request costs
  its ACTUAL length in HBM, not a reserved ``max_len`` region. On top of
  paging: (a) a copy-on-write shared-prefix cache (repeated system
  prompts map their cached prefix pages instead of re-prefilling; a
  write into a shared page forks it first), (b) chunked prefill (long
  prompts split into ``prefill_chunk``-token chunks interleaved with
  decode steps, so one long prompt no longer stalls every in-flight
  request's next token),
  and (c) preemption (pool exhaustion releases + requeues the youngest
  slot; the stateless per-request sampling streams make the resume
  exact). The contiguous cache (``cache_spec``/``forward_cached``) is
  what ``models.generate`` decodes through, and the reference the
  parity tests hold the engine to: greedy decode is token-identical to
  it (tests/test_serve_paging.py). Here only ``score()`` traces one.
- **Self-speculative decoding** (``speculate=K``): decode proceeds in
  draft-verify rounds — K-1 tokens drafted from the request's own token
  history (n-gram prompt lookup, serve/speculate.py; no draft model),
  verified in ONE batched forward. The verify recomputes EXACTLY the
  token the non-speculative path would emit at each position (same
  bitwise logits by the chunked-prefill T-invariance contract, where the
  verify's paged read takes the step's form: ``heads x K`` within
  ``models/llama.WALK_LANES``; same stateless ``fold_in`` sampling
  keys), so acceptance is plain equality
  and output is token-identical to ``speculate=0`` — greedy AND
  sampled. Each round is one host round-trip for 1..K true tokens;
  acceptance/rounds ride ``mxnet_spec_*``. Composes with fused
  decode, prefix COW and chunked prefill.
- **Telemetry.** queue wait / TTFT / inter-token / step latency
  histograms, slot-occupancy + tokens/sec gauges, per-bucket compile
  counters, and the ``mxnet_serve_page_*`` family (pages in use, prefix
  hits/tokens/bytes saved, COW forks, prefill chunks, preemptions).
  ``mxnet_serve_compiles_total`` /
  ``mxnet_recompilations_total{block=serve_*}`` stay zero after warmup —
  the shape-bucketing contract (block tables and chunk shapes are
  data/static, never novel avals). A heartbeat thread (``_Heartbeat``)
  tells a process that stopped from a chip that was slow.

Single-host, single-device engine; params are captured at construction
(weight updates require a new engine). The pools are rows of
``[pages, page_size, kv_heads * head_dim]`` (``model.cache_spec_paged``)
and every program that writes them is given them: prefill, chunk,
step, verify, copy and inject donate the pools, so a program updates the
few rows it writes in the buffer where they lie and returns that buffer
(``stats()["pool_bytes_in_place"]`` against ``kv_bytes``, and
``stats()["pool_copies"]``, 0 where no program copies a pool into another
layout and back, which an aliased pool can still be). Only the engine
loop touches ``self._pools``: an array read from another thread may have
been donated since, so page exports and imports run at a tick boundary.
A step reads only the blocks of pages that its deepest row has reached
(``stats()``: ``kv_walk_blocks`` of ``kv_table_blocks``) and multiplies each
as the pool stores it (``kv_walk_blocks_on_lanes``: the blocks of the
dispatches whose program took that form, ``models/llama.walk_form``); a
chunk on a TPU reads them in one kernel (``kv_walk_blocks_in_kernel``); and
searches for a top-k or nucleus threshold only where one of its rows asks
for one (``sample_rows_filtered`` of ``sample_rows``).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import queue as _qmod
import re
import threading
import time
import warnings
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as onp

from .. import metrics as _metrics
from .. import profiler as _profiler
from ..analysis import guards as _guards
from ..base import MXNetError, logger
from ..models import generation as _gen
from ..models import llama as _llama
from ..observability import perf as _perf
from ..observability import recorder as _recorder
from ..observability import trace as _trace
from ..ndarray import NDArray
from ..parallel.functional import functionalize
from . import grammar as _grammar
from .bucketing import bucket_for, bucket_ladder
from .paging import (FlatPages, OutOfPages, PagePool, WindowedPages,
                     pages_for, prefix_key)

__all__ = ["InferenceEngine", "RequestHandle", "ServeResult",
           "QueueFullError", "EngineClosedError",
           "STATUS_OK", "STATUS_TIMEOUT", "STATUS_CANCELLED",
           "STATUS_SHUTDOWN", "STATUS_ERROR"]

STATUS_OK = "ok"
STATUS_TIMEOUT = "timeout"
STATUS_CANCELLED = "cancelled"
STATUS_SHUTDOWN = "shutdown"
STATUS_ERROR = "error"


class QueueFullError(MXNetError):
    """Admission control: the request queue is at max_queue_depth."""


class EngineClosedError(MXNetError):
    """The engine is shut down (or shutting down) and not accepting work."""


@dataclasses.dataclass
class ServeResult:
    """Terminal outcome of a request. ``generated_ids`` holds whatever was
    produced by completion/deadline/cancel — partial output is real
    output."""
    status: str
    prompt_ids: List[int]
    generated_ids: List[int]
    queue_wait_s: Optional[float] = None
    ttft_s: Optional[float] = None
    latency_s: float = 0.0
    error: Optional[str] = None
    #: trace id of the request's span tree — the /trace/{id} key. Set
    #: only when tracing is ENABLED on this process (a propagated
    #: traceparent then supplies the id; with tracing off the header is
    #: ignored and this stays None)
    trace_id: Optional[str] = None

    @property
    def output_ids(self) -> List[int]:
        return list(self.prompt_ids) + list(self.generated_ids)

    @property
    def ok(self) -> bool:
        return self.status == STATUS_OK


class RequestHandle:
    """Future-like view of a submitted request."""

    def __init__(self, prompt_ids, max_new_tokens, temperature, top_k, top_p,
                 eos_token_id, seed, deadline):
        self.prompt_ids = prompt_ids
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos_token_id = eos_token_id
        self.seed = seed
        self.deadline = deadline
        self.submit_t = time.perf_counter()
        self.admit_t: Optional[float] = None
        self.first_token_t: Optional[float] = None
        # tokens generated before a preemption (resume)
        self._resume: Optional[List[int]] = None
        # request span tree (observability.trace): root + currently-open
        # phase spans; None while tracing is disabled (the per-token
        # overhead contract is one is-None check per slot per step)
        self._trace = None
        self._span_queue = None
        self._span_prefill = None
        self._event = threading.Event()
        self._result: Optional[ServeResult] = None
        self._cancelled = False
        self._status = "queued"
        #: compiled token-mask automaton constraining this request's
        #: generated tokens (grammar.TokenGrammar; None = unconstrained)
        self.grammar = None
        # streaming: engine-side token feed (submit(stream=True)). The
        # engine thread puts ("token", id) per emitted token and
        # ("done", ServeResult) at completion; consumers (the SSE
        # frontend) drain with Queue.get(timeout=...) for heartbeats.
        self._events: Optional["_qmod.Queue"] = None

    @property
    def status(self) -> str:
        return self._status

    @property
    def trace_id(self) -> Optional[str]:
        return self._trace.trace_id if self._trace is not None else None

    def done(self) -> bool:
        return self._event.is_set()

    def cancel(self) -> bool:
        """Request cancellation. Queued requests are dropped before
        admission; in-flight requests stop at the next step boundary and
        complete with partial output (status 'cancelled'). Returns False
        if the request already finished."""
        if self._event.is_set():
            return False
        self._cancelled = True
        return True

    def result(self, timeout: Optional[float] = None) -> ServeResult:
        """Block until the request reaches a terminal status."""
        if not self._event.wait(timeout):
            raise MXNetError("RequestHandle.result: timed out waiting for "
                             "completion (request still in flight)")
        return self._result

    # engine-side completion
    def _complete(self, result: ServeResult):
        self._result = result
        self._status = result.status
        if self._events is not None:
            self._events.put(("done", result))
        self._event.set()

    # engine-side per-token streaming feed
    def _emit(self, tok: int):
        if self._events is not None:
            self._events.put(("token", int(tok)))


@dataclasses.dataclass
class _Slot:
    req: RequestHandle
    generated: List[int]
    t_admit: float
    t_last: float


@dataclasses.dataclass
class _Prefill:
    """Chunked-prefill progress for one slot. ``ids`` is the full
    token sequence to prefill (prompt, plus already-generated tokens when
    resuming a preempted request); ``cursor`` is the next position to
    write (starts past the mapped prefix-cache pages); ``counter0`` is
    the sampling-stream counter for the token the final chunk emits
    (``len(resumed tokens)`` — 0 for a fresh request)."""
    ids: List[int]
    cursor: int
    counter0: int
    t0: float


@dataclasses.dataclass
class _PendingStep:
    """One dispatched-but-unread decode step (the lookahead window).
    ``slots`` snapshots (index, slot object) pairs at dispatch time so the
    read side can skip rows whose slot was retired/refilled in between
    (identity check — a refilled index holds a different _Slot).
    Multi-token steps (K > 1) additionally carry the [sb, K] token matrix
    and the device step count (< K only when every row finished early);
    ``nxt`` is then the LAST token column, the lookahead feedback vector."""
    nxt: Any                               # device [sb] int32 token vector
    sb: int
    slots: List[Tuple[int, "_Slot"]]
    t0: float
    toks: Any = None                       # device [sb, K] (K > 1 only)
    steps: Any = None                      # device scalar: executed substeps
    # grammar engines: device [sb] automaton-state vector AFTER this
    # step's token — the lookahead feedback twin of ``nxt`` (the host
    # ledger stays authoritative; it re-advances at the read)
    gstate: Any = None
    # a model that counts its experts' tokens: device [layers, held] int32,
    # read where ``nxt`` is
    counts: Any = None
    # the dispatched program, as the device trace names it (``_program``)
    program: str = ""


def _jit_named(fn, name: str, donate_argnums=()):
    """``jax.jit(fn)`` under the name of what it is: the device trace's
    program line then reads ``jit_step_b16(...)``, ``jit_prefill_b32(...)``,
    ``jit_chunk_c128(...)``. ``donate_argnums``: the pools, for a program
    that writes them."""
    fn.__name__ = name
    return jax.jit(fn, donate_argnums=donate_argnums)


def _program(fn) -> str:
    """The name of a serving executable as the device trace's modules line
    shows it (``jit_step_b8``): a jit and the AOT cache's wrapper of one
    both carry the traced function's ``__name__``."""
    return f"jit_{fn.__name__}"


def _compiled(fn, args):
    """The executable that the first call of ``fn`` would have made (the
    jit keeps it), or None where the backend gives none."""
    try:
        return (getattr(fn, "_compiled", None)
                or fn.lower(*args).compile())
    except Exception:
        return None


def _alias_bytes(compiled) -> int:
    """Bytes of its arguments that a program compiles to updates where they
    lie (``memory_analysis().alias_size_in_bytes``; 0 where the backend
    gives no analysis)."""
    try:
        return int(compiled.memory_analysis().alias_size_in_bytes)
    except Exception:
        return 0


# one instruction of an optimized HLO text: name, result, opcode, operand
_INSTRUCTION = re.compile(
    r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(%?([\w.\-]*)")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\](?:\{([^}]*)\})?")


def _pool_copies(compiled, pools) -> int:
    """``count_pool_copies`` over one executable's text (0 where the backend
    gives none). Reading a program's text back costs 0.1-0.2 s on the v5e
    host for a 12-layer program, more for deeper ones, so the engine reads
    the first program of each kind only."""
    try:
        text = compiled.as_text()
    except Exception:
        return 0
    return count_pool_copies(text, [p.shape for p in pools])


def _laid_out(array):
    """``(dtype, dims, layout)`` of an HLO array as a copy sees it: the
    memory space (``S(1)``) left out of the layout."""
    dtype, dims, layout = array
    return dtype, dims, re.sub(r"S\(\d+\)", "", layout)


def count_pool_copies(hlo_text: str, shapes) -> int:
    """Instructions of an optimized HLO text that copy a whole pool into
    another shape or layout: a ``copy`` or ``copy-start`` (a ``copy-done``
    ends the latter) whose result holds as many elements as a pool of one
    of ``shapes`` and is laid out otherwise than its operand: the pool
    relaid out, or viewed flat for a scatter. An aliased pool can still be
    copied so, written there and copied back, which
    ``alias_size_in_bytes`` counts as in place. A copy that keeps the
    layout and changes only the memory space (the compiler staging a small
    pool nearer for the reads that follow) is not counted."""
    sizes = {math.prod(s) for s in shapes}
    copies = []
    for line in hlo_text.splitlines():
        if " copy" not in line:
            continue
        m = _INSTRUCTION.match(line)
        if not m or m.group(3) not in ("copy", "copy-start"):
            continue
        out = _ARRAY.findall(m.group(2))[:1]
        if out and math.prod(int(d) for d in out[0][1].split(",") if d) \
                in sizes:
            copies.append((_laid_out(out[0]), m.group(4)))
    operands = {name for _, name in copies}
    defined = {}
    for line in hlo_text.splitlines() if operands else ():
        name = line.split(" = ", 1)[0].strip().replace("ROOT ", "")
        if name.lstrip("%") in operands:
            m = _INSTRUCTION.match(line)
            out = _ARRAY.findall(m.group(2))[:1] if m else []
            if out:
                defined[name.lstrip("%")] = _laid_out(out[0])
    return sum(1 for laid, name in copies if defined.get(name) != laid)


#: a tick whose wall time passes this is written to the flight recorder
#: and the log with its child spans and the compilations since the tick
#: before: the operator's view of a stall with no trace running
_SLOW_TICK_S = 1.0
#: a wait for the device longer than this is listed, with the program it
#: waited for, in its tick's slow-tick record
_SLOW_WAIT_S = 0.1
#: the heartbeat's period, and the lateness of a beat that is a host pause:
#: a beat that took over twice its period, so that every heartbeat span of
#: over 100 ms has an event beside it that says what held it
_HEARTBEAT_S = 0.05
_PAUSE_S = 0.05
#: beats the heartbeat keeps for the slow-tick record (12.8 s of them)
_BEATS_KEPT = 256


class _TickSpan(_profiler.scope):
    """A child span of the engine's current tick (``mx.serve.<name>``):
    the span helper, stamped with the tick's number, adding its seconds to
    the tick's per-child record that a slow tick reports. While it is open
    the engine's ``_in_span`` names it: what a late heartbeat reports."""

    __slots__ = ("_engine", "_outer")

    def __init__(self, engine: "InferenceEngine", name: str, hist=None,
                 **attrs):
        super().__init__(f"mx.serve.{name}", "serve", hist,
                         tick=engine._tick_no, **attrs)
        self._engine = engine

    def __enter__(self):
        eng = self._engine
        self._outer, eng._in_span = eng._in_span, self.name
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        eng = self._engine
        eng._in_span = self._outer
        children = eng._tick_children
        children[self.name] = children.get(self.name, 0.0) + self.seconds
        return False


def _gc_collections() -> int:
    return sum(s["collections"] for s in gc.get_stats())


class _Heartbeat:
    """The engine's own pulse: a daemon thread that waits ``_HEARTBEAT_S``
    at a time inside an ``mx.serve.heartbeat`` span. A beat much longer than
    that is a time in which this thread could not run: another held the
    interpreter's lock, or the machine did not run the process. A beat late
    by more than ``_PAUSE_S`` writes a ``serve.host_pause`` event to the
    flight recorder and a warning:
    ``late_s``; ``cpu_s``, the process's CPU seconds over the beat (well
    above 0: a thread ran and held the interpreter; near 0: the process did
    not run); ``gc``, collections over the beat; the engine's ``tick`` and
    the child span its thread was in when the beat woke (``span``).
    ``pauses`` and ``pause_max_s`` count them for ``stats()``. Each beat's
    reading of the process's CPU clock also serves the slow-tick record,
    so that no tick reads it (a system call, which cost a tick 18 us on a
    v5e host)."""

    def __init__(self, engine: "InferenceEngine"):
        self._engine = engine
        self._halt = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name="mxnet-serve-heartbeat",
                                        daemon=True)
        # (start, end, CPU seconds at the start) of the last beats, and
        # (start, CPU seconds) of the one running
        self._beats: "deque[Tuple[float, float, float]]" = deque(
            maxlen=_BEATS_KEPT)
        self._beat = (time.perf_counter(), time.process_time())
        self.pauses = 0
        self.pause_max_s = 0.0

    def start(self):
        self._thread.start()

    def stop(self):
        self._halt.set()

    def join(self, timeout: Optional[float] = None):
        if self._thread.is_alive():
            self._thread.join(timeout)

    def _run(self):
        while not self._halt.is_set():
            cpu0, gc0 = time.process_time(), _gc_collections()
            with _profiler.scope("mx.serve.heartbeat", "serve") as beat:
                self._beat = (time.perf_counter(), cpu0)
                self._halt.wait(_HEARTBEAT_S)
            self._beats.append((self._beat[0], beat.t1, cpu0))
            late = beat.seconds - _HEARTBEAT_S
            if late > _PAUSE_S:
                self._pause(late, time.process_time() - cpu0,
                            _gc_collections() - gc0)

    def _pause(self, late: float, cpu_s: float, gcs: int):
        eng = self._engine
        self.pauses += 1
        self.pause_max_s = max(self.pause_max_s, late)
        fields = dict(late_s=round(late, 6), cpu_s=round(cpu_s, 6), gc=gcs,
                      tick=eng._tick_no, span=eng._in_span)
        _recorder.RECORDER.record("event", "serve.host_pause", **fields)
        logger.warning("serve: host pause: %s", fields)

    def tick_record(self, t0: float, t1: float) -> Tuple[float, float]:
        """For a tick over ``[t0, t1]`` (``perf_counter``): ``(the longest
        lateness of a beat that overlapped it, the beat still running
        included; the process's CPU seconds since the last beat that began
        by t0, so over the tick and at most a beat before it)``."""
        running = self._beat
        beats = list(self._beats) + [(running[0], None, running[1])]
        worst = time.perf_counter() - running[0] - _HEARTBEAT_S
        cpu0 = beats[0][2]
        for b0, b1, c0 in beats:
            if b0 <= t0:
                cpu0 = c0
            if b1 is not None and b0 < t1 and t0 < b1:
                worst = max(worst, b1 - b0 - _HEARTBEAT_S)
        return max(worst, 0.0), time.process_time() - cpu0


class InferenceEngine:
    """Continuous-batching serving engine for a causal LM that speaks the
    paged KV protocol (``cache_spec_paged``/``forward_cached_paged`` — GPT
    and Llama families, including stacked-scan decoders).

    **Recurrent state beside the pages.** A model whose layers keep a
    per-request state of fixed size (linear attention, a convolution) next
    to, or in place of, paged keys and values says so with one method more
    than the paged protocol, and the engine takes no argument for it:

    - ``cache_spec_paged(num_pages, page_size)`` lists only the pools that
      have a page axis (the engine infers that axis from them, as before);
    - ``cache_spec_state(slots)`` lists ``[(shape, dtype)]`` of the state
      pools, each indexed by slot on an axis the model knows. The engine
      asks for ``max_batch_size + 1`` slots (the last is the *sink*, for
      rows of a decode bucket that serve no request), allocates them behind
      the page pools and hands all pools to every program, in that order;
    - ``forward_cached_paged(ids, pos, block_table, slots, valid, *pools)``
      takes, after the block table, each row's slot id ``[B]`` and how many
      of its ``T`` positions are real ``[B]`` (a prompt's last chunk is
      padded to a bucket, and padding must not move a state). A row whose
      ``pos`` is 0 starts from a zero state: admission needs no clearing
      program and compiles nothing. The model may leave out
      ``cache_spec``/``forward_cached`` (then ``score()`` raises);
    - optionally ``blocks_read(pos, T) -> (read, live)`` for a model that
      selects among its pages: the engine puts ``sel``/``of`` on its two
      dispatch spans and sums them in ``stats()``.

    Preemption requeues and prefills again from position 0, which rebuilds
    the state. ``stats()`` gains ``state_bytes``, ``sparse_blocks_read`` and
    ``sparse_blocks_live``. **Refused with state, each with an
    ``MXNetError`` that says why** (carrying snapshots of a state is not
    built): ``prefix_cache=True`` (shared pages carry a prefix's keys and
    values, not the state it left; pass ``prefix_cache=False``),
    ``speculate`` and ``multi_token > 1`` (a rejected or surplus position
    has already moved the state), and page migration
    (COW ``copy``, ``export_pages``/``import_pages``, the preemption-rescue
    hook: a request's pages alone do not resume it).

    **A cache that folds.** A model whose attention keeps a finished window
    of positions only as one page of chunk summaries (EvaByte's EVA
    attention) says so with one method more than the paged protocol, and the
    engine takes no argument for it:

    - ``cache_fold(page_size)`` returns the arithmetic of its table
      (``ops.eva_attention.FoldedPages``: ``window``, ``window_pages``,
      ``column(pos)``, ``entries(depth)``, ``peak(depth)``,
      ``ends_window(depth)``; every other model's is ``FlatPages``). The page
      pool counts a request's need with it: in pages held, not in positions;
    - ``forward_cached_paged(ids, pos, block_table, valid, *pools)`` takes,
      after the block table, how many of each row's ``T`` positions are real
      ``[B]`` (padding must not end a window), and ``pos`` is the row's TRUE
      position. A dispatch that brings a row to the end of a window is handed
      a table whose entry behind the window's pages is a fresh page: the
      program writes the window's summaries there, and once it is dispatched
      the host replaces the window's entries by that page and gives the
      window's pages back to the pool (``PagePool.fold``), while the request
      lives and under the lookahead (the host knows every row's next position,
      and the donated pools order the programs on the device);
    - ``prefill_chunk`` must divide the window, so that a chunk lies in one.

    For such a model ``max_len`` is still a request's most positions, but a
    request of ``max_len`` holds only ``pages.max_pages`` pages (EvaByte at
    32,768 positions: 32 of 128 rows, not 256), ``num_pages`` defaults to
    that many for every slot, ``stats()["kv_bytes"]`` is the pools' bytes as
    ever, and ``pages["pages_in_use"]`` counts folded pages once. ``stats()``
    gains ``windows_folded``, ``pages_folded`` (pages given back by folds)
    and ``pages_held`` / ``pages_unfolded`` (what the dispatched rows'
    tables held, of what their depths would hold unfolded; the two dispatch
    spans carry the same as ``held`` / ``depth_pages``, and ``folded``).
    Preemption requeues and prefills again from 0, which rebuilds the
    summaries. **Refused with folding, each with an ``MXNetError`` that says
    why**: ``prefix_cache=True``, COW ``copy`` and page migration
    (``export_pages`` / ``import_pages``, the preemption-rescue hook): a
    shared or shipped page is keyed by the tokens of one page, and a folded
    page stands for a window of 2,048; ``speculate`` and ``multi_token > 1``:
    a position written past the accepted or the finished one may have ended
    a window, and a fold cannot be taken back.

    **Pools of two kinds, and experts that count.** A model that mixes
    layers which read every position with layers which read only the last
    ``window`` says so with one method more than the paged protocol:

    - ``cache_window()`` returns the window. The full layers' pools keep
      the page ids, the table and the ledger described above; the windowed
      layers' pools are a *kind* of their own, with their own page ids, a
      second :class:`~mxnet_tpu.serve.paging.PagePool` (``layout`` a
      ``WindowedPages``) and a second table. ``cache_spec_paged`` takes
      ``(full, windowed)`` page counts, ``cache_kinds()`` says which kind
      each pool is of, and ``forward_cached_paged(ids, pos, block_table,
      valid, *pools)`` takes both tables as ``[B, 2, max_pages]`` and, as a
      folding model does, how many of each row's ``T`` positions are real;
    - before each dispatch the host gives back every page of the windowed
      kind that lies wholly behind the row's window (``PagePool.slide``) and
      leases both kinds at the back, so a request at any depth holds at most
      ``ceil((window + prefill_chunk) / page_size) + 1`` windowed pages.
      Need, ``OutOfPages``, preemption and release count both kinds; the
      second pool holds that bound for every slot, so it is the full kind
      that runs out first;
    - a model whose layers route tokens to experts it holds a share of has
      ``expert_counts() -> (layers, held, experts a token)``, and its
      ``forward_cached_paged``
      returns one more small int32 array behind the pools: the tokens each
      held expert received. The step, chunk and prefill programs hand it on,
      and the engine reads it where it reads the sampled tokens (no wait of
      its own): a step's go onto its ``mx.serve.emit`` span (``moe_hit`` of
      ``moe_held`` experts that received a token, ``moe_here`` of
      ``moe_routed`` assignments), every program's into ``stats()``.

    ``stats()`` gains ``window_pages_held`` / ``window_pages_unwindowed``
    (what the dispatched rows held of the windowed kind, of what their
    depths would hold unwindowed; ``wheld`` / ``wdepth_pages`` on the two
    dispatch spans, and ``wwalk``: blocks a windowed layer's read visits,
    beside ``walk``), ``window_pages_recycled``, ``moe_assignments``,
    ``moe_assignments_here`` and ``moe_expert_tokens_max``. **Refused with a
    windowed kind, each with an ``MXNetError`` that says why**:
    ``prefix_cache=True``, COW ``copy`` and page migration (no request keeps
    the pages of its prefix), ``speculate`` and ``multi_token > 1`` (a
    position written past the accepted one may lie where the host has
    already given the page behind the window to another request).

    Parameters
    ----------
    model : initialized causal LM block
    max_batch_size : slot-pool size (concurrent in-flight requests)
    max_len : per-slot capacity in positions; prompt + new tokens must fit
    max_queue_depth : admission-control bound; ``submit`` raises
        :class:`QueueFullError` beyond it
    min_prompt_bucket : smallest prompt-length bucket (power of two)
    lookahead : dispatch decode step N+1 (device tokens fed straight back
        in) before host-reading step N's tokens, overlapping the D2H sync
        with compute; output is token-identical to ``lookahead=False``
        (retire/refill is delayed one step — see module docstring)
    multi_token : emit K tokens per decode dispatch via the on-device
        ``lax.while_loop`` (models/generation.decode_multi_tokens): the
        per-token host round-trip becomes one round-trip per K tokens.
        EOS/deadline/refill are
        detected by scanning the returned K-vector; speculative tokens
        past a row's EOS/budget are discarded, so output is
        token-for-token identical to ``multi_token=1`` — with one scoped
        exception: on TPU with an int8 tied head, temperature-only
        batches (no top-k/top-p) sample inside the fused head kernel
        from a per-request stateless-hash stream that is deterministic
        in (seed, counter) but differs from the K=1 host categorical
        stream (ops/fused_block_gemv module docstring); greedy and
        filtered sampling are exactly identical everywhere. Requires
        ``prompt + max_new_tokens + (K-1) <= max_len`` per request (the
        device may run up to K-1 speculative cache writes past a row's
        budget). When the model carries an int8 tied LM head
        (quantize_net), sampling fuses into the head GEMV
        (ops/fused_block_gemv.fused_lm_head_sample).
    paged : vestigial. The engine has one cache layout, pages (module
        docstring); ``None`` and ``True`` mean that, ``False`` raises
        (``models.generate`` is the contiguous reference). Kept while
        the benchmark's rehearsal cells pass it (``ROADMAP.md`` D13).
    page_size : tokens per KV page; ``max_len`` must be a multiple of it
    num_pages : leasable pages in the pool. Default
        ``max_batch_size * max_len / page_size``: every slot can reach
        ``max_len`` at once; requests shorter than that leave pages for
        the prefix cache.
    prefix_cache : publish/match shared prompt prefixes
    prefill_chunk : tokens per prefill chunk. Prompts
        longer than this are prefilled one chunk per engine tick,
        interleaved with decode steps. Default = one page; pass
        ``max_len`` to disable chunking.
    bucket_growth : geometric growth factor of the prompt-bucket ladder
        (default 2 = the legacy power-of-two ladder).
    speculate : self-speculative decoding — K > 0 replaces the per-token
        decode step with draft-verify rounds: K-1 tokens drafted from
        the request's OWN token history (n-gram prompt lookup — no
        draft model), verified in ONE batched forward whose per-column
        sampling recomputes EXACTLY the token the non-speculative path
        would emit (the stateless fold_in streams make the check plain
        equality), so output is token-identical to ``speculate=0`` for
        greedy AND sampled requests — speculation changes latency,
        never content. Each round is one host round-trip emitting 1..K
        true tokens; acceptance rides ``mxnet_spec_*``. Composes with
        fused decode, COW prefix sharing and chunked prefill;
        mutually exclusive with ``multi_token > 1`` (both own the
        decode dispatch). Wrong drafts cost only the (overlapped)
        verify compute: repetitive/structured traffic accepts most
        drafts, free-form sampled prose accepts few — see the README
        section for when to turn it on.
    spec_draft : draft tokens proposed per round (default 0 = the full
        verify width, ``speculate - 1``).
    spec_lookup : max n-gram length the prompt-lookup draft source
        matches (default 4).
    grammar : enable grammar-constrained decoding (serve/grammar.py):
        ``submit(..., grammar=...)`` compiles a regex/JSON-schema into a
        token-mask automaton whose per-slot state advances as DATA, and
        every prefill/decode/verify dispatch folds the allowed-token
        mask into sampling — output is schema-conformant BY
        CONSTRUCTION. Construction-time because the automaton tables
        ride the dispatches, changing executable signatures; the table
        shape is fixed by ``serve_grammar_max_states`` (one aval for
        every grammar — zero steady-state recompiles). Unconstrained
        requests on a grammar engine carry identity tables and batch
        with constrained ones. Mutually exclusive with
        ``multi_token > 1``; composes with speculation
        (drafts are pre-constrained host-side, the verify masks every
        draft position) and streaming.

    The knob-shaped parameters (``min_prompt_bucket``, ``multi_token``,
    ``page_size``, ``prefill_chunk``, ``bucket_growth``, ``speculate``,
    ``spec_draft``, ``spec_lookup``) default to
    ``None`` = *consult the tuned-config layer* (mxnet_tpu/tune): an
    mxtune winner whose content-address matches this engine's workload
    context (model dims + pool geometry + backend) applies; otherwise
    the hand-picked defaults (8 / 1 / 16 / one page / 2 / 0 / 0 / 4)
    do, bitwise.
    Explicit arguments always win, and resolution happens once, here —
    steady-state serving never consults anything (the
    ``no_recompile()``-clean contract is untouched).
    """

    #: labels (``_get_compiled``) of the programs that write the pools:
    #: they are given them, and return them last
    _POOL_WRITERS = ("prefill", "chunk", "decode", "spec", "copy", "inject")

    def __init__(self, model, max_batch_size: int = 8, max_len: int = 256,
                 max_queue_depth: int = 64,
                 min_prompt_bucket: Optional[int] = None,
                 lookahead: bool = True, multi_token: Optional[int] = None,
                 paged: Optional[bool] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None, prefix_cache: bool = True,
                 prefill_chunk: Optional[int] = None,
                 bucket_growth: Optional[int] = None,
                 speculate: Optional[int] = None,
                 spec_draft: Optional[int] = None,
                 spec_lookup: Optional[int] = None,
                 name: str = "default",
                 tier: Optional[str] = None,
                 prefix_advert: Optional[int] = None,
                 grammar: bool = False):
        if max_batch_size < 1:
            raise MXNetError("max_batch_size must be >= 1")
        if max_len < 2:
            raise MXNetError("max_len must be >= 2")
        if paged is False:
            raise MXNetError(
                "paged must be None or True: the engine has one cache "
                "layout, pages, on every backend; the contiguous cache is "
                "models.generate's, the reference the parity tests hold "
                "the engine to")
        # tuned-config consult: one lookup keyed on this engine's
        # workload context; every knob left None resolves env > tuned >
        # hand-picked default (tune/config.py resolution contract)
        from ..tune import config as _tuneconf
        _tctx = _tuneconf.serve_context(model, max_batch_size, max_len)
        _tuned = _tuneconf.lookup(_tuneconf.SERVE_SITE, _tctx)

        min_prompt_bucket = _tuneconf.resolve(
            "serve_min_prompt_bucket", min_prompt_bucket, _tuned)
        # explicitness captured BEFORE resolution: the multi_token ×
        # speculate conflict below must know which side the caller
        # actually chose (a resolved tuned value looks explicit after)
        mt_explicit = multi_token is not None
        multi_token = _tuneconf.resolve(
            "serve_multi_token", multi_token, _tuned)
        page_explicit = page_size is not None
        page_size = _tuneconf.resolve("serve_page_size", page_size, _tuned)
        if not page_explicit and max_len % page_size:
            # a tuned/env page size measured at another max_len must not
            # brick a default-constructed engine (the PR-13 contract); an
            # explicit one that does not divide is PagePool's error
            fallback = _tuneconf.knob_default("serve_page_size")
            if fallback != page_size:
                warnings.warn(
                    f"serve: tuned serve_page_size={page_size} does not "
                    f"divide max_len={max_len}; using the default "
                    f"{fallback} — re-tune page size for this geometry or "
                    "pass page_size explicitly")
                page_size = fallback
        self._growth = _tuneconf.resolve(
            "serve_bucket_growth", bucket_growth, _tuned)
        if self._growth < 2:
            # tuned/env values are range-validated upstream (2..8), so
            # only an explicit caller value can land here — fail loudly
            # like every sibling knob instead of silently clamping
            raise MXNetError("bucket_growth must be >= 2")
        if prefill_chunk is None:
            # serve_prefill_chunk's 0 default = the engine's legacy
            # derivation (one page), applied below;
            # an EXPLICIT 0 is not collapsed — it still fails the >= 1
            # validation loudly
            prefill_chunk = _tuneconf.resolve(
                "serve_prefill_chunk", None, _tuned) or None
        spec_explicit = speculate is not None
        speculate = _tuneconf.resolve("serve_speculate", speculate, _tuned)
        spec_draft = _tuneconf.resolve("serve_spec_draft", spec_draft,
                                       _tuned)
        spec_lookup = _tuneconf.resolve("serve_spec_lookup", spec_lookup,
                                        _tuned)
        prefix_advert = _tuneconf.resolve("serve_prefix_advert",
                                          prefix_advert, _tuned)
        if prefix_advert < 0:
            raise MXNetError("prefix_advert must be >= 0 (0 = no advert)")
        #: prefix-cache roots advertised via stats()/healthz (the
        #: router's affinity-scoring input; bounded so fleet health
        #: polls stay O(N))
        self._prefix_advert = int(prefix_advert)
        #: disaggregated-fleet tier this replica serves in (``prefill``/
        #: ``decode``/None = mixed) — advertised via stats()/healthz for
        #: tier-aware dispatch and per-tier autoscaling
        self.tier = str(tier) if tier else None
        if multi_token < 1:
            raise MXNetError("multi_token must be >= 1")
        if multi_token >= max_len:
            raise MXNetError("multi_token must be < max_len")
        if speculate < 0 or speculate == 1:
            raise MXNetError("speculate must be 0 (off) or >= 2 (the "
                             "verify width: current token + drafts)")
        if speculate >= max_len:
            raise MXNetError("speculate must be < max_len")
        if speculate and multi_token > 1:
            # mutually exclusive: both own the decode dispatch (the
            # verify step IS a multi-token dispatch). Two EXPLICIT
            # arguments are a caller error; a conflict involving
            # env/tuned values must degrade with a warning instead —
            # merged mxtune winners (a decode multi_token winner + a
            # spec winner in one cache entry) must never brick a
            # default-constructed engine (the PR-13 contract)
            if spec_explicit and mt_explicit:
                raise MXNetError(
                    "speculate and multi_token > 1 are mutually "
                    "exclusive: both own the decode dispatch (the "
                    "verify step IS a multi-token dispatch — up to K "
                    "tokens per round-trip)")
            if spec_explicit:
                warnings.warn(
                    f"serve: tuned/env multi_token={multi_token} "
                    f"conflicts with explicit speculate={speculate}; "
                    "running multi_token=1 (they are mutually "
                    "exclusive)")
                multi_token = 1
            else:
                warnings.warn(
                    f"serve: tuned/env serve_speculate={speculate} "
                    f"conflicts with multi_token={multi_token}; "
                    "disabling speculation (they are mutually "
                    "exclusive — pass speculate explicitly to prefer "
                    "it)")
                speculate = 0
        if spec_draft < 0:
            raise MXNetError("spec_draft must be >= 0 (0 = full width)")
        if spec_lookup < 1:
            raise MXNetError("spec_lookup must be >= 1")
        if min_prompt_bucket < 1 or min_prompt_bucket & (min_prompt_bucket - 1):
            raise MXNetError("min_prompt_bucket must be a power of two")
        # a model with recurrent state beside its pages adds
        # cache_spec_state, and forward_cached_paged takes each row's
        # slot: class docstring
        self._stateful = hasattr(model, "cache_spec_state")
        if not (self._stateful or hasattr(model, "cache_fold")
                or hasattr(model, "cache_window")
                or _gen._can_cache(model)):
            raise MXNetError(
                "InferenceEngine requires the KV-cache decode protocol "
                "(cache_spec/forward_cached) and a config that supports it")
        if not (hasattr(model, "cache_spec_paged")
                and hasattr(model, "forward_cached_paged")):
            raise MXNetError(
                "InferenceEngine requires the paged KV protocol "
                "(cache_spec_paged/forward_cached_paged)")
        max_pos = getattr(getattr(model, "cfg", None),
                          "max_position_embeddings", None)
        if max_pos is not None and max_len > max_pos:
            raise MXNetError(
                f"max_len ({max_len}) exceeds the model's "
                f"max_position_embeddings ({max_pos})")
        self.model = model
        self.S = int(max_batch_size)
        self.L = int(max_len)
        self.K = int(multi_token)
        # self-speculative decoding: spec = verify width (0 = off),
        # _n_draft = drafts proposed per round, _spec_lookup = n-gram
        # window of the prompt-lookup draft source
        self.spec = int(speculate)
        self._n_draft = (min(int(spec_draft) or self.spec - 1,
                             self.spec - 1) if self.spec else 0)
        self._spec_lookup = int(spec_lookup)
        # per-tick cache-row advance bound: multi-token and speculative
        # dispatches may write up to _adv rows past a row's final token
        # (speculative writes are masked until overwritten) — the
        # admission headroom and page-lease horizon
        self._adv = max(self.K, self.spec or 1)
        self._vocab = getattr(getattr(model, "cfg", None), "vocab_size", None)
        # grammar-constrained decoding is a CONSTRUCTION-time gate: the
        # automaton tables ride every prefill/decode/verify dispatch as
        # data, which changes the executable SIGNATURES — an engine
        # built without grammar=True compiles byte-identical programs
        # to pre-grammar builds (the tier-1 parity contract), and a
        # grammar engine serves constrained and unconstrained requests
        # mixed in one batch (unconstrained slots carry identity tables)
        self._grammar = bool(grammar)
        if self._grammar:
            if self._vocab is None:
                raise MXNetError(
                    "grammar=True requires a model config with "
                    "vocab_size (the token-mask automaton is built over "
                    "the vocabulary)")
            if self.K > 1:
                raise MXNetError(
                    "grammar=True and multi_token > 1 are mutually "
                    "exclusive: the on-device multi-token loop cannot "
                    "advance the automaton between substeps — use "
                    "speculate=K for multi-token grammar decoding (the "
                    "verify masks every draft position)")
            self._gmax = int(_tuneconf.resolve(
                "serve_grammar_max_states", None, _tuned))
        self.max_queue_depth = int(max_queue_depth)
        self.min_prompt_bucket = min(int(min_prompt_bucket), self.L)
        # fused LM-head sampling: engages when the model exposes the int8
        # tied-head table + the hidden-state protocol (multi-token path)
        self._head_pack = None
        if self.K > 1 and hasattr(model, "head_weights") \
                and hasattr(model, "forward_cached_hidden"):
            self._head_pack = model.head_weights()

        # pure functional view; params captured once — but swappable:
        # swap_weights() replaces the whole captured tuple between decode
        # ticks (same shapes/dtypes => same avals => same executables)
        self.name = str(name)
        self._fm = functionalize(
            model, NDArray(onp.zeros((1, self.min_prompt_bucket), onp.int32)),
            training=False)
        self._values = self._with_embed(self._fm.values())
        # canonical publish naming: collect_params names where available
        # (what snapshot_params/publish_weights write), functional
        # structural names as the fallback
        id2name = {}
        collect = getattr(model, "collect_params", None)
        if collect is not None:
            try:
                id2name = {id(p): n for n, p in collect().items()}
            except Exception:
                id2name = {}
        self._param_names: List[str] = [
            id2name.get(id(p), n) for n, p in self._fm.param_items]
        #: version of the weights currently serving (0 = construction-
        #: time weights, never published); flips between decode ticks on
        #: a hot swap
        self.weight_version = 0
        self._weight_swaps = 0
        # staged live weight swaps: {"values", "version", "evt", "ok"}
        # records guarded by self._lock, applied by the engine loop at
        # the next tick boundary ("ok" flips only on a REAL apply — a
        # crash-path discard wakes the waiter without it, so
        # swap_weights can fail honestly instead of reporting a deploy
        # that never happened)
        self._swaps: List[Dict[str, Any]] = []
        # staged cross-replica page imports, same tick-boundary contract
        # as weight swaps: the engine loop owns self._pools, so imports
        # land between ticks (import_pages stages + waits)
        self._page_ops: List[Dict[str, Any]] = []
        # preemption-rescue hook (serve/cachefleet installs it):
        # called as hook(engine, req, wire_doc) -> bool from _preempt,
        # True = the hook took ownership of the request (it resumes on
        # another replica); False/raise = requeue locally as before
        self._migrate_hook = None

        # the contiguous cache of one sequence: what score() traces
        # privately (a model with state may have none; score() then raises)
        self._spec1 = (model.cache_spec(1, self.L)
                       if hasattr(model, "cache_spec") else [])

        if self._stateful:
            self._refuse_with_state(prefix_cache, speculate, multi_token)
        self.page_size = int(page_size)
        # a model whose cache folds says so with cache_fold (class
        # docstring): the table's arithmetic, which the pool counts with
        self._fold = (model.cache_fold(self.page_size)
                      if hasattr(model, "cache_fold") else None)
        if self._fold is not None:
            self._refuse_with_fold(prefix_cache, speculate, multi_token)
        # a model whose pools are of two kinds says so with cache_window
        # (class docstring); like a folding one it is told how many of a
        # row's positions are real
        self._window = (int(model.cache_window())
                        if hasattr(model, "cache_window") else None)
        self._takes_valid = not (self._fold is None and self._window is None)
        if self._window is not None:
            self._refuse_with_window(prefix_cache, speculate, multi_token)
        #: a request is nothing but its pages, a page nothing but its
        #: positions' rows: what page sharing and migration rest on
        self._pages_portable = not (self._stateful or self._fold
                                    or self._window)
        layout = self._fold or FlatPages(self.page_size)
        if num_pages is None:
            num_pages = self.S * layout.peak(self.L)
        self._pages = PagePool(num_pages, self.page_size, self.L, self.S,
                               prefix_cache=prefix_cache, layout=layout)
        self.maxp = self._pages.max_pages
        if prefill_chunk is None:
            prefill_chunk = self.page_size
        self._chunk = min(int(prefill_chunk), self.L)
        if self._chunk < 1:
            raise MXNetError("prefill_chunk must be >= 1")
        # the windowed kind's ledger: its own page ids and table, a slot's
        # pages dereferenced behind its window and leased in front
        self._wpages: Optional[PagePool] = None
        if self._window is not None:
            wlayout = WindowedPages(self.page_size, self._window, self._chunk)
            # the most one request holds, for every slot
            self._wpages = PagePool(self.S * wlayout.need(self.L),
                                    self.page_size, self.L, self.S,
                                    prefix_cache=False, layout=wlayout)
        # what the dispatched rows held of the windowed kind, of what their
        # depths would hold unwindowed, and the blocks a windowed layer's
        # read visited (stats(): window_pages_held / _unwindowed,
        # window_walk_blocks)
        self._wheld = 0
        self._wdepth = 0
        self._wwalked = 0
        # a model that counts its experts' tokens (class docstring): the
        # sums of stats(), and the counts of chunks not read yet
        self._moe = (tuple(model.expert_counts())
                     if hasattr(model, "expert_counts") else None)
        # assignments a token makes over all layers
        self._moe_a_token = self._moe[0] * self._moe[2] if self._moe else 0
        self._moe_routed = 0
        self._moe_tokens = (onp.zeros(self._moe[:2], onp.int64)
                            if self._moe else None)
        self._moe_unread: List[Any] = []
        # the paged read walks the table a block at a time, as far as
        # the deepest row reaches: what a dispatch walks and what the
        # table holds are summed here (stats(): kv_walk_blocks /
        # kv_table_blocks, the share of a max_len read still done), and
        # of the walked, those a program multiplied as the pool stores them
        # and those it read in one kernel (kv_walk_blocks_on_lanes /
        # _in_kernel: the model's walk_form says which programs do, where
        # its layers read through the shared walk)
        self._kv_block = _llama.kv_block(self.page_size, self.maxp)
        self._walk_form = getattr(model, "walk_form", None)
        self._kv_walked = 0
        self._kv_on_lanes = 0
        self._kv_in_kernel = 0
        self._kv_tabled = 0
        # folding: pages the dispatched rows' tables held, of the pages
        # their depths would hold unfolded (stats(): pages_held /
        # pages_unfolded), and the windows the open tick's dispatches ended
        self._held = 0
        self._unfolded = 0
        self._tick_folded = 0
        # rows whose token a program selects, and those among them whose
        # selection runs filter_logits' search (stats(): sample_rows /
        # sample_rows_filtered)
        self._sample_rows = 0
        self._sample_filtered = 0
        # page-axis inference by diffing cache_spec_paged(1)/(2) (per-layer
        # pools: axis 0; stacked scan pools [layers, pages, ...]: 1)
        sp1 = model.cache_spec_paged(1, self.page_size)
        sp2 = model.cache_spec_paged(2, self.page_size)
        self._paxes: List[int] = []
        for (s1, _), (s2, _) in zip(sp1, sp2):
            diffs = [i for i, (a, b) in enumerate(zip(s1, s2)) if a != b]
            if len(diffs) != 1:
                raise MXNetError(
                    f"cannot infer page axis from cache_spec_paged "
                    f"shapes {s1} vs {s2}")
            self._paxes.append(diffs[0])
        # device pools carry one extra SINK page (index num_pages):
        # unleased block-table entries point at it, so pad/empty-row
        # writes land harmlessly and masked reads of unleased
        # territory contribute exact zeros
        pool_spec = model.cache_spec_paged(
            num_pages + 1 if self._wpages is None
            else (num_pages + 1, self._wpages.num_pages + 1), self.page_size)
        # per-slot recurrent state, a second kind of pool behind the
        # page pools: indexed by slot, no page axis, one slot more than
        # the engine serves (the sink, for rows that serve no request)
        state_spec = (model.cache_spec_state(self.S + 1)
                      if self._stateful else [])
        self._pools: Tuple[jax.Array, ...] = tuple(
            jnp.zeros(s, d) for s, d in list(pool_spec) + state_spec)
        self._state_bytes = sum(
            int(onp.prod(s)) * onp.dtype(d).itemsize
            for s, d in state_spec)
        # blocks of one sparse layer's cache that the dispatched
        # programs read, of those live (stats(): sparse_blocks_read /
        # sparse_blocks_live), where the model selects among its pages
        self._blocks_read = getattr(model, "blocks_read", None)
        self._sel_read = 0
        self._sel_live = 0
        self._tok_bytes = sum(
            int(onp.prod(s)) * onp.dtype(d).itemsize
            // (s[ax] * self.page_size)
            for (s, d), ax in zip(pool_spec, self._paxes))
        if self._fold is not None and self._fold.window % self._chunk:
            raise MXNetError(
                f"prefill_chunk ({self._chunk}) must divide the model's "
                f"window ({self._fold.window}): a chunk lies in one window, "
                "whose end folds it")
        self._chunks_per_tick = 1
        self._prefills: Dict[int, _Prefill] = {}
        self._active = onp.zeros(self.S, bool)
        self._preempted = 0
        self._chunk_fns: Dict[int, Any] = {}
        self._copy_fns: Dict[int, Any] = {}
        # cross-replica page migration executables (extract = one
        # page out of every pool, inject = one shipped page in)
        self._extract_fns: Dict[int, Any] = {}
        self._inject_fns: Dict[int, Any] = {}

        # what the pools take on the device, which pads a row to whole
        # tiles (a TPU holds GPT-2 XL's rows of 1,600 lanes as 1,664): the
        # bytes that memory_analysis() counts, so that
        # stats()["pool_bytes_in_place"] can equal this
        self._kv_bytes = sum(int(p.on_device_size_in_bytes())
                             for p in self._pools)

        # host-side per-slot state (mutated only by the engine thread)
        self._slots: List[Optional[_Slot]] = [None] * self.S
        self._tokens = onp.zeros(self.S, onp.int32)
        self._pos = onp.zeros(self.S, onp.int32)
        self._temps = onp.zeros(self.S, onp.float32)
        self._topks = onp.zeros(self.S, onp.int32)
        self._topps = onp.ones(self.S, onp.float32)
        self._seeds = onp.zeros(self.S, onp.uint32)
        self._counters = onp.zeros(self.S, onp.int32)
        # multi-token decode: per-slot eos id (-1 = none) + token budget,
        # flowing to the device as DATA (no shape/K-ladder recompiles)
        self._eos = onp.full(self.S, -1, onp.int32)
        self._remaining = onp.zeros(self.S, onp.int32)
        # grammar engines: per-slot automaton tables (fixed
        # [gmax, gmax] aval for EVERY grammar — the zero-recompile
        # contract) + the per-slot automaton state, advancing as DATA
        # like pos. The [S, ...] tables are re-uploaded to the device
        # only when a slot's grammar changes (_gdirty, flipped at
        # admission/retire); steady-state decode passes the SAME device
        # buffers every dispatch. Unoccupied/unconstrained slots carry
        # identity tables (every token allowed, always accepting).
        if self._grammar:
            icls, inxt, iacc = _grammar.identity_tables(
                int(self._vocab), self._gmax, self._gmax)
            self._gcls = onp.tile(icls[None, :], (self.S, 1))
            self._gnxt = onp.tile(inxt[None, :, :], (self.S, 1, 1))
            self._gacc = onp.tile(iacc[None, :], (self.S, 1))
            self._gstate = onp.zeros(self.S, onp.int32)
            self._gram: List[Optional[_grammar.TokenGrammar]] = \
                [None] * self.S
            self._gdirty = True
            self._gdev: Optional[Tuple[Any, Any, Any]] = None
        # decode lookahead: at most one dispatched-but-unread step
        self._lookahead = bool(lookahead)
        self._pending: Optional[_PendingStep] = None
        # shape-bucketed executables (bucket key -> jitted fn)
        self._prefill_fns: Dict[int, Any] = {}
        self._step_fns: Dict[int, Any] = {}
        self._spec_fns: Dict[int, Any] = {}
        # stats()["pool_bytes_in_place"]: None until a program that
        # writes the pools is built
        self._in_place: Optional[int] = None
        # stats()["pool_copies"]: instructions that copy a whole pool into
        # another layout (count_pool_copies), in the first program built
        # of each kind that writes the pools
        self._pool_copies = 0
        self._copies_read: set = set()
        # batched scoring (teacher-forced logprobs): its own bucket
        # ladder over the prompt geometry — warmed by warmup_score()
        self._score_fns: Dict[int, Any] = {}
        # self-speculative accounting (engine thread only): the running
        # acceptance-rate gauge divides these
        self._spec_rounds = 0
        self._spec_drafted = 0
        self._spec_accepted = 0

        self._queue: "deque[RequestHandle]" = deque()
        # witness-wrapped under MXNET_DEBUG_GUARDS (lock-order recording
        # across the engine/checkpoint/prefetcher threads); plain
        # threading.Lock otherwise
        self._lock = _guards.make_lock("serve.InferenceEngine._lock")
        self._cond = threading.Condition(self._lock)
        # bucket-executable builds may race (warmup on the caller thread vs
        # lazy compiles on the engine thread); the lock keeps the compile
        # counters exact — they back the zero-recompile contract
        self._compile_lock = _guards.make_lock(
            "serve.InferenceEngine._compile_lock")
        self._running = False
        self._closed = False
        self._draining = False
        self._abort_inflight = False
        self._thread: Optional[threading.Thread] = None
        # fault injection for tests: per-step sleep to make deadlines and
        # backpressure deterministic on fast hosts
        self._step_delay = 0.0
        # the engine thread's tick: its sequence number (carried by every
        # span under it), the open mx.serve.tick span, seconds per child
        # span, and the compile count when the previous tick closed; the
        # child span open now and the tick's waits of over _SLOW_WAIT_S
        self._tick_no = 0
        self._tick_span: Optional[_profiler.scope] = None
        self._tick_children: Dict[str, float] = {}
        self._compiles_seen = _metrics.backend_compiles()
        self._in_span: Optional[str] = None
        self._tick_waits: List[list] = []
        self._heartbeat = _Heartbeat(self)

        # counters for stats()
        self._submitted = 0
        self._completed: Dict[str, int] = {}
        self._max_active = 0
        self.last_warmup_s: Optional[float] = None

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "InferenceEngine":
        """Launch the background continuous-batching loop."""
        with self._lock:
            if self._closed:
                raise EngineClosedError("engine already shut down")
            if self._running:
                return self
            self._running = True
        self._thread = threading.Thread(target=self._loop,
                                        name="mxnet-serve-engine",
                                        daemon=True)
        self._heartbeat.start()
        self._thread.start()
        return self

    def begin_drain(self):
        """Start a graceful drain WITHOUT blocking: stop admitting new
        work immediately (submits raise :class:`EngineClosedError`, so a
        router fails over), let in-flight slots decode to completion on
        the engine loop, and complete still-queued requests with status
        'shutdown'. The HTTP ``/drain`` endpoint calls this from its
        handler thread; ``shutdown(drain=True)`` is this plus a join."""
        _recorder.RECORDER.record("event", "engine_drain_begin")
        self.shutdown(drain=True, timeout=0.0)

    def shutdown(self, drain: bool = True, timeout: Optional[float] = None):
        """Stop the engine. ``drain=True`` finishes in-flight slots
        (queued requests complete with status 'shutdown'); ``drain=False``
        aborts in-flight requests too, completing them with partial
        output. ``timeout=0.0`` returns without waiting for the loop
        (``begin_drain``)."""
        with self._cond:
            self._closed = True
            self._draining = drain
            was_running = self._running
            if was_running:
                self._running = False
                self._abort_inflight = not drain
                self._cond.notify_all()
            else:
                # loop already stopped (or never started): flush leftovers
                # OUTSIDE the lock (_finish_unstarted re-acquires it)
                flushed = list(self._queue)
                self._queue.clear()
        if not was_running:
            for req in flushed:
                self._finish_unstarted(req, STATUS_SHUTDOWN)
            if self._thread is not None and self._thread.is_alive():
                # a begin_drain() already stopped admissions without
                # waiting: this call upgrades it (drain=False flips the
                # still-draining loop to abort) and performs the join
                if not drain:
                    with self._cond:
                        self._abort_inflight = True
                        self._cond.notify_all()
                self._thread.join(timeout)
                if self._thread.is_alive():
                    return
            self._heartbeat.join(timeout)
            self._apply_swaps()  # loop is dead: unblock swap waiters
            self._apply_page_ops()
            return
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                return            # begin_drain: the loop finishes async
        self._heartbeat.join(timeout)   # the loop stopped it on its way out
        self._apply_swaps()      # loop is dead: unblock swap waiters
        self._apply_page_ops()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown(drain=True)

    # ------------------------------------------------------------ submission
    def submit(self, input_ids, max_new_tokens: int,
               eos_token_id: Optional[int] = None, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0, seed: int = 0,
               timeout_s: Optional[float] = None,
               traceparent: Optional[str] = None,
               resume: Optional[Sequence[int]] = None,
               grammar=None, stream: bool = False) -> RequestHandle:
        """Enqueue one request (a single sequence of token ids). Returns a
        :class:`RequestHandle`; admission control may raise
        :class:`QueueFullError` (backpressure) or
        :class:`EngineClosedError`. ``traceparent`` (a W3C header value,
        typically injected by the HTTP frontend/router) parents the
        request's span tree so one trace id follows the request across
        processes; with tracing disabled it is ignored.

        ``resume`` (internal — the cross-replica migration path) stashes
        already-generated tokens so this engine CONTINUES the stream
        instead of starting it: admission re-prefills
        ``prompt + resume`` and decoding picks up at sampling counter
        ``len(resume)`` — the stateless ``fold_in(seed, counter)``
        streams make the continuation bit-exact with the replica the
        request migrated away from (the same mechanism as a local
        preemption resume).

        ``grammar`` constrains every generated token to a compiled
        token-mask automaton (serve/grammar.py): a regex string, a
        restricted JSON-schema dict, or a pre-compiled
        :class:`~mxnet_tpu.serve.grammar.TokenGrammar`. Requires an
        engine built with ``grammar=True`` and an ``eos_token_id``
        (accept states with no continuation terminate by EOS — the
        coaccessible-trimmed automaton guarantees every reachable state
        either continues or accepts, so the mask is never empty).

        ``stream=True`` feeds per-token events into
        ``handle._events`` (("token", id) per emitted token, ("done",
        ServeResult) at completion) — the SSE frontend's source."""
        prompt = self._as_prompt(input_ids)
        if self._vocab is not None and any(
                t < 0 or t >= self._vocab for t in prompt):
            # the embedding gather would silently CLAMP out-of-range ids —
            # a public endpoint must reject, not serve garbage
            raise MXNetError(
                f"input_ids contain tokens outside [0, {self._vocab})")
        if max_new_tokens <= 0:
            raise MXNetError("max_new_tokens must be positive")
        _gen._validate_sampling(temperature, top_k, top_p)
        if len(prompt) + max_new_tokens + (self._adv - 1) > self.L:
            headroom = (f" + multi_token/speculate headroom "
                        f"({self._adv - 1})" if self._adv > 1 else "")
            raise MXNetError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens})"
                f"{headroom} exceeds the engine's max_len ({self.L})")
        g = None
        if grammar is not None:
            if not self._grammar:
                raise MXNetError(
                    "this engine was built without grammar support — "
                    "construct it with grammar=True (the automaton "
                    "tables change the decode executable signatures, so "
                    "the gate is construction-time)")
            if eos_token_id is None:
                raise MXNetError(
                    "grammar-constrained requests require eos_token_id: "
                    "an accept state with no continuation can only "
                    "terminate by emitting EOS")
            if isinstance(grammar, _grammar.TokenGrammar):
                g = grammar
                if g.vocab != int(self._vocab):
                    raise MXNetError(
                        f"grammar was compiled for vocab={g.vocab}, "
                        f"engine vocab is {self._vocab}")
                if g.n_states > self._gmax or g.n_classes > self._gmax:
                    raise MXNetError(
                        f"grammar ({g.n_states} states, {g.n_classes} "
                        f"classes) exceeds this engine's "
                        f"serve_grammar_max_states={self._gmax} tables")
            else:
                g = _grammar.compile_grammar(grammar, int(self._vocab),
                                             max_states=self._gmax)
            _metrics.GRAMMAR_SESSIONS.inc()
        deadline = (time.perf_counter() + timeout_s
                    if timeout_s is not None else None)
        req = RequestHandle(prompt, int(max_new_tokens), float(temperature),
                            int(top_k), float(top_p), eos_token_id, int(seed),
                            deadline)
        req.grammar = g
        if stream:
            req._events = _qmod.Queue()
        if resume is not None:
            req._resume = [int(t) for t in resume]
        t_wall = time.time()
        with self._cond:
            if self._closed or not self._running:
                raise EngineClosedError(
                    "engine is not running (call start(), or it was shut "
                    "down)")
            if len(self._queue) >= self.max_queue_depth:
                _metrics.SERVE_REQUESTS.labels(status="rejected").inc()
                raise QueueFullError(
                    f"request queue full (max_queue_depth="
                    f"{self.max_queue_depth}); retry with backoff")
            if _trace.ENABLED:
                # spans open only for ADMITTED requests: a backpressure
                # burst of rejects must not churn real in-flight traces
                # out of the bounded store (t0 backdated to arrival)
                req._trace = _trace.start_span(
                    "serve.request", parent=traceparent, t0=t_wall,
                    prompt_tokens=len(prompt),
                    max_new_tokens=int(max_new_tokens))
                req._span_queue = req._trace.child("serve.queue",
                                                   t0=t_wall)
            self._queue.append(req)
            self._submitted += 1
            _metrics.SERVE_QUEUE_DEPTH.set(len(self._queue))
            self._cond.notify_all()
        return req

    def generate(self, input_ids, max_new_tokens: int,
                 **kwargs) -> ServeResult:
        """Synchronous convenience: submit + wait."""
        return self.submit(input_ids, max_new_tokens, **kwargs).result()

    # ------------------------------------------------------------ scoring
    def warmup_score(self):
        """Compile the scoring bucket ladder (``score()``'s analogue of
        ``warmup()``) — call it before entering a ``no_recompile()``
        steady state that serves ``/score`` traffic. Kept out of
        ``warmup()`` so engines that never score pay nothing."""
        for pb in bucket_ladder(self.min_prompt_bucket, self.L,
                                self._growth):
            fn = self._get_score(pb)
            jax.block_until_ready(fn(*self._example_args("score", pb)))
        return self

    def score(self, input_ids) -> Dict[str, Any]:
        """Teacher-forced scoring: per-token log-probabilities of
        ``input_ids[1:]`` given their prefixes, riding the prompt bucket
        ladder in ONE forward (no decode loop, no slot, no queue — and
        no KV pool traffic, so it runs from any thread concurrently with
        serving; the weight read is one atomic tuple load). Returns
        ``{"tokens", "logprob", "token_logprobs"}``."""
        if not self._spec1:
            raise MXNetError(
                "score() runs on fresh contiguous caches "
                "(cache_spec/forward_cached), which this model does not "
                "have")
        prompt = self._as_prompt(input_ids)
        if len(prompt) < 2:
            raise MXNetError(
                "score requires at least 2 tokens (the first token has "
                "no conditional to score)")
        if self._vocab is not None and any(
                t < 0 or t >= self._vocab for t in prompt):
            raise MXNetError(
                f"input_ids contain tokens outside [0, {self._vocab})")
        if len(prompt) > self.L:
            raise MXNetError(
                f"score sequence ({len(prompt)}) exceeds the engine's "
                f"max_len ({self.L})")
        pb = bucket_for(len(prompt), self.min_prompt_bucket, self.L,
                        self._growth)
        fn = self._get_score(pb)
        ids = onp.zeros((1, pb), onp.int32)
        ids[0, :len(prompt)] = prompt
        lp = onp.asarray(fn(self._values, ids, onp.int32(len(prompt))))
        _metrics.SERVE_ROUNDTRIPS.labels(path="score").inc()
        toklp = [float(x) for x in lp[:len(prompt) - 1]]
        return {"tokens": len(prompt) - 1,
                "logprob": float(sum(toklp)),
                "token_logprobs": toklp}

    # ------------------------------------------------------- weight refresh
    def swap_weights(self, named_params: Dict[str, Any],
                     version: Optional[int] = None,
                     timeout: float = 60.0) -> int:
        """Hot-swap the engine's captured params to a new weight set —
        zero downtime, zero recompiles: the new arrays must match the
        live shapes exactly (validated BEFORE anything is staged), so
        every bucket executable keeps serving unchanged and in-flight
        streams keep decoding straight across the swap (their KV pages
        were written by the old weights; tokens from the next tick on
        sample from the new ones).

        ``named_params`` maps param name → array (the publish naming:
        ``collect_params`` names, what ``registry.publish_weights`` /
        ``snapshot_params`` produce). Missing params, extra names and
        shape mismatches all raise without touching the engine. The swap
        is staged and applied by the engine loop at the next tick
        boundary (old buffers drop their last reference there — the
        engine-side analogue of donation); with the loop not running it
        applies inline. Returns the version now serving."""
        if version is None:
            version = self.weight_version + 1
        version = int(version)
        if self._head_pack is not None:
            # fused head sampling bakes the packed int8 tied-head table
            # into the jitted executables as trace
            # constants, NOT as swappable arguments — a values-only swap
            # would silently sample through the OLD head. Refuse rather
            # than serve inconsistent generations.
            raise MXNetError(
                "swap_weights: this engine serves fused int8 decode "
                "(packed weights are baked into the executables); live "
                "refresh needs the unfused path — build a new engine "
                "for quantized fused-decode deploys")
        missing = [n for n in self._param_names if n not in named_params]
        if missing:
            raise MXNetError(
                f"swap_weights: missing {len(missing)} params (first: "
                f"{missing[:3]}); expected the publish naming "
                "(collect_params)")
        extra = set(named_params) - set(self._param_names)
        if extra:
            raise MXNetError(
                f"swap_weights: {len(extra)} unknown params (first: "
                f"{sorted(extra)[:3]}) — wrong model?")
        from ..checkpoint import _coerce_dtype
        new_values = []
        for name, cur in zip(self._param_names, self._values):
            arr = named_params[name]
            if hasattr(arr, "_data"):        # NDArray
                arr = arr._data
            arr = onp.asarray(arr) if not isinstance(arr, jax.Array) else arr
            if tuple(arr.shape) != tuple(cur.shape):
                raise MXNetError(
                    f"swap_weights: shape mismatch for {name!r}: "
                    f"{tuple(arr.shape)} vs live {tuple(cur.shape)} — "
                    "changed shapes need a new engine (and a recompile)")
            if isinstance(arr, onp.ndarray):
                arr = _coerce_dtype(arr, cur.dtype)
            # cast to the LIVE dtype: the aval (and so the executable)
            # is defined by what the engine serves, not what the trainer
            # published
            new_values.append(jnp.asarray(arr, dtype=cur.dtype))
        new_values = self._with_embed(new_values)
        rec = {"values": new_values, "version": version,
               "evt": threading.Event(), "ok": False}
        with self._cond:
            # gate on the loop THREAD being alive, not _running: during
            # a drain the loop keeps decoding in-flight slots with
            # _running already False — an inline apply from this thread
            # would change weights mid-iteration, the exact mixed-weights
            # hazard the tick-boundary staging exists to prevent
            alive = self._thread is not None and self._thread.is_alive()
            if alive:
                self._swaps.append(rec)
                self._cond.notify_all()
        if not alive:
            # no loop to race: apply inline
            self._values = new_values
            self._note_swap(version)
            return version
        if not rec["evt"].wait(timeout):
            raise MXNetError(
                f"swap_weights: engine loop did not apply the swap "
                f"within {timeout}s")
        if not rec["ok"]:
            raise MXNetError(
                "swap_weights: the engine loop went down before "
                f"applying v{version}; still serving "
                f"v{self.weight_version}")
        return version

    def _with_embed(self, params):
        """What every program takes as ``values``: the model's parameters
        and, behind them, the embedding's table in rows of whole lane tiles
        where its width is none (made here, once for each set of weights;
        models/generation.embed_operand says why)."""
        params = tuple(params)
        return params + _gen.embed_operand(self._fm, params)

    def swap_weights_from(self, directory: str,
                          version: Optional[int] = None) -> int:
        """Load a published weight version (``registry.publish_weights``
        layout; default latest) and hot-swap to it. The ``POST
        /weights`` deploy path."""
        from .registry import read_weights
        version, params, _manifest = read_weights(directory, version)
        return self.swap_weights(params, version=version)

    def _note_swap(self, version: int):
        self.weight_version = version
        self._weight_swaps += 1
        _metrics.SERVE_WEIGHT_VERSION.labels(model=self.name).set(version)
        _metrics.SERVE_WEIGHT_SWAPS.labels(model=self.name).inc()
        _recorder.RECORDER.record("event", "serve.weight_swap",
                                  model=self.name, version=version)

    def _apply_swaps(self):
        """Engine-loop side: adopt the newest staged weight set at a
        tick boundary. Intermediate versions staged in the same window
        are superseded (monotone versions — serving an already-replaced
        set would be wrong, not just wasteful); their waiters still
        succeed (a newer deploy landed)."""
        with self._lock:
            swaps, self._swaps = self._swaps, []
        if not swaps:
            return
        self._values = swaps[-1]["values"]
        self._note_swap(swaps[-1]["version"])
        for rec in swaps:
            rec["ok"] = True
            rec["evt"].set()

    @staticmethod
    def _refuse_with_state(prefix_cache, speculate, multi_token):
        """What a model with recurrent state cannot be served with yet,
        each with its reason (class docstring)."""
        if prefix_cache:
            raise MXNetError(
                "prefix_cache=True cannot serve a model with recurrent "
                "state: shared pages give a request its prefix's keys and "
                "values but not the state the prefix left, and no snapshot "
                "of a state is kept per page; pass prefix_cache=False")
        if speculate:
            raise MXNetError(
                "speculate cannot serve a model with recurrent state: a "
                "rejected draft has already moved the state, and a state "
                "has no stale rows for the causal mask to hide")
        if multi_token > 1:
            raise MXNetError(
                "multi_token > 1 cannot serve a model with recurrent "
                "state: the device loop writes past a finished row's "
                "budget, which a page forgives and a state does not")

    @staticmethod
    def _refuse_with_fold(prefix_cache, speculate, multi_token):
        """What a model whose cache folds cannot be served with, each with
        its reason (class docstring)."""
        if prefix_cache:
            raise MXNetError(
                "prefix_cache=True cannot serve a model whose cache folds: "
                "a shared page is keyed by the tokens of one page, and a "
                "folded page stands for a whole window of them (and a COW "
                "copy of a window's page would have to be folded again); "
                "pass prefix_cache=False")
        if speculate:
            raise MXNetError(
                "speculate cannot serve a model whose cache folds: a "
                "rejected draft may have ended a window, and a fold cannot "
                "be taken back (the window's pages are already summarised "
                "and given away)")
        if multi_token > 1:
            raise MXNetError(
                "multi_token > 1 cannot serve a model whose cache folds: "
                "the device loop writes past a finished row's budget and "
                "would end windows the host has leased no summary page for")

    @staticmethod
    def _refuse_with_window(prefix_cache, speculate, multi_token):
        """What a model with a windowed kind of pool cannot be served with,
        each with its reason (class docstring)."""
        if prefix_cache:
            raise MXNetError(
                "prefix_cache=True cannot serve a model with a windowed "
                "kind of pool: the pages behind a request's sliding window "
                "are given back while it lives, so no request keeps the "
                "pages of its prefix for another to map (and a COW copy "
                "would have to follow two tables); pass prefix_cache=False")
        if speculate:
            raise MXNetError(
                "speculate cannot serve a model with a windowed kind of "
                "pool: the pages behind the window are given back by the "
                "position the host counts, and a rejected draft would have "
                "moved that position past pages it still needs")
        if multi_token > 1:
            raise MXNetError(
                "multi_token > 1 cannot serve a model with a windowed kind "
                "of pool: the device loop advances positions the host has "
                "not slid the window for, and writes past a finished row's "
                "budget into pages it no longer holds")

    # ------------------------------------------------- page migration
    def _refuse_migration(self):
        if self._window is not None:
            raise MXNetError(
                "page migration (copy / extract / inject) cannot move a "
                "request of a model with a windowed kind of pool: a request "
                "holds pages of two kinds under two tables, those behind "
                "its window already given back, and a shipped page is "
                "verified as one page of one table")
        if self._stateful:
            raise MXNetError(
                "page migration (copy / extract / inject) cannot move a "
                "request of a model with recurrent state: its pages hold "
                "the sparse layers' keys and values only, and a snapshot "
                "of the per-slot state is not carried")
        if self._fold is not None:
            raise MXNetError(
                "page migration (copy / extract / inject) cannot move a "
                "request of a model whose cache folds: a shipped page is "
                "verified by the hash of one page of tokens, and a folded "
                "page stands for a whole window of them")

    def _export_entries(self, toks: List[int], phys_pages: Sequence[int]
                        ) -> dict:
        """Extract the given physical pages (page ``i`` covering tokens
        ``[i*page_size, (i+1)*page_size)`` of ``toks``) and wrap them as
        the migration wire doc: each page rides with the chain hash of
        the token prefix it completes, verified on receipt."""
        from ..kvstore.comm import encode_kv_pages
        extract = self._get_extract()
        ps = self.page_size
        entries = []
        for i, phys in enumerate(phys_pages):
            ln = (i + 1) * ps
            payload = [onp.asarray(a) for a in
                       extract(self._pools, onp.int32(int(phys)))]
            entries.append((ln, prefix_key(toks[:ln]), payload))
        if entries:
            _metrics.MIGRATE_PAGES_SENT.inc(len(entries))
            _recorder.RECORDER.record(
                "event", "serve.page_export", reason="page_migration",
                pages=len(entries), tokens=entries[-1][0])
        return encode_kv_pages(toks[:len(phys_pages) * ps], entries)

    def export_pages(self, input_ids, timeout: float = 60.0) -> dict:
        """Export the FULL cached pages of the longest prefix-cache
        match of ``input_ids`` as a migration wire doc
        (kvstore/comm.encode_kv_pages): exact page payloads, each with
        its chain hash. The partial tail page never ships — the
        receiving replica re-prefills it (token-exact either way).
        Pages are read live; call on an engine whose pool is not under
        allocation pressure (the prefill tier streams right after its
        prefill published the pages, when every exported page is pinned
        by its cache entry). Runs at a tick boundary of the engine loop,
        like :meth:`import_pages`."""
        self._refuse_migration()
        toks = self._as_prompt(input_ids)

        def export():
            pages, matched = self._pages.match_prefix(toks, count=False)
            full = min(matched // self.page_size, len(pages))
            return self._export_entries(toks,
                                        [int(p) for p in pages[:full]])

        return self._on_loop(export, timeout, "page export")

    def _export_slot_pages(self, s: int, toks: List[int]) -> dict:
        """Preempt-time capture (engine thread): the victim slot's
        leased FULL pages, straight off its block table — prompt AND
        generated-token pages, before release() frees them."""
        table = self._pages.table(s)
        full = len(toks) // self.page_size
        phys = []
        for i in range(full):
            p = int(table[i])
            if p == self._pages.sink:
                break
            phys.append(p)
        return self._export_entries(toks, phys)

    def import_pages(self, doc: dict, timeout: float = 60.0) -> dict:
        """Adopt migrated KV pages into this engine's prefix cache.

        Each shipped page is verified on receipt — the chain hash of the
        accompanying tokens is recomputed and the payload's aval checked
        against this engine's pool spec; failures are dropped and
        counted (``mxnet_migrate_verify_failures_total``), never
        injected. Verified pages are published as prefix-cache entries
        and their payloads written into freshly leased physical pages,
        so the migrated request's (or any sharing request's) admission
        maps them instead of re-prefilling. Runs at a tick boundary of
        the engine loop (the loop owns the pools); on a stopped engine
        it applies inline. Returns ``{"received", "adopted",
        "verify_failures", ...}``."""
        self._refuse_migration()
        from ..kvstore.comm import decode_kv_pages
        tokens, pages = decode_kv_pages(doc)
        return self._on_loop(
            lambda: self._apply_page_import(tokens, pages), timeout,
            "page import")

    def _on_loop(self, work, timeout: Optional[float], what: str):
        """Run ``work()`` where the pools may be touched, and return what
        it returns: staged for the next tick boundary of a running engine
        loop (only the loop owns ``self._pools``; each program that writes
        them is given them, so a pool read from another thread may be a
        deleted array by the time it is used), inline on a stopped
        engine or on the loop's own thread."""
        rec: Dict[str, Any] = {"work": work, "evt": threading.Event(),
                               "result": None, "error": None}
        with self._cond:
            staged = (self._running
                      and threading.current_thread() is not self._thread)
            if staged:
                self._page_ops.append(rec)
                self._cond.notify_all()
        if not staged:
            return work()
        if not rec["evt"].wait(timeout):
            raise MXNetError(f"{what} timed out waiting for a tick "
                             "boundary")
        if rec["error"]:
            raise MXNetError(rec["error"])
        return rec["result"]

    def _apply_page_ops(self):
        """Engine-loop side: run what :meth:`_on_loop` staged, between
        ticks."""
        with self._lock:
            ops, self._page_ops = self._page_ops, []
        failed = None
        for rec in ops:
            try:
                rec["result"] = rec["work"]()
            except Exception as e:
                rec["error"] = str(e)
                failed = failed or e
            finally:
                rec["evt"].set()
        if failed is not None:
            self._pools_lost(failed)

    def _fail_page_ops(self):
        """Crash/shutdown path: wake the waiters with the failure."""
        with self._lock:
            ops, self._page_ops = self._page_ops, []
        for rec in ops:
            rec["error"] = rec["error"] or "engine stopped before the " \
                                           "staged work ran"
            rec["evt"].set()

    def _pools_lost(self, e: Exception):
        """After a dispatch failed. A program that writes the pools is
        given them, so one that failed after it took them has left
        ``self._pools`` deleted arrays, and every later program would
        fail on them: raise instead, and the loop's backstop fails every
        outstanding request with this reason and closes the engine."""
        if any(p.is_deleted() for p in self._pools):
            raise MXNetError(
                "serve: the KV pools were donated to a program that "
                f"failed ({e!r}); the engine cannot serve on")

    def _apply_page_import(self, tokens, pages) -> Dict[str, Any]:
        tokens = [int(t) for t in tokens]
        spec = self._page_payload_spec()
        verified: Dict[int, Any] = {}
        failures = 0
        for ln, key, payload in pages:
            ok = (0 < ln <= len(tokens) and ln % self.page_size == 0
                  and prefix_key(tokens[:ln]) == int(key)
                  and len(payload) == len(spec)
                  and all(tuple(a.shape) == tuple(z.shape)
                          and onp.dtype(a.dtype) == z.dtype
                          for a, z in zip(payload, spec)))
            if ok:
                verified[int(ln)] = tuple(payload)
            else:
                failures += 1
        if failures:
            _metrics.MIGRATE_VERIFY_FAILURES.inc(failures)
        if verified:
            _metrics.MIGRATE_PAGES_RECEIVED.inc(len(verified))
        adopted = 0
        reason = None
        if verified:
            try:
                fresh = self._pages.adopt_prefix(tokens,
                                                 sorted(verified))
            except OutOfPages as e:
                fresh, reason = [], str(e)
            inject = self._get_inject()
            for ln, page in fresh:
                self._pools = inject(self._pools, verified[ln],
                                     onp.int32(int(page)))
                adopted += 1
        if verified or failures:
            _recorder.RECORDER.record(
                "event", "serve.page_import", reason="page_migration",
                received=len(verified), adopted=adopted,
                verify_failures=failures)
        return {"received": len(verified), "adopted": adopted,
                "verify_failures": failures,
                "skipped_cached": len(verified) - adopted,
                "out_of_pages": reason}

    @staticmethod
    def _as_prompt(input_ids) -> List[int]:
        if isinstance(input_ids, NDArray):
            input_ids = input_ids.asnumpy()
        arr = onp.asarray(input_ids)
        if arr.ndim == 2 and arr.shape[0] == 1:
            arr = arr[0]
        if arr.ndim != 1 or arr.size == 0:
            raise MXNetError(
                "submit expects one non-empty token sequence (shape [P] "
                f"or [1, P]), got shape {arr.shape}")
        return [int(t) for t in arr]

    # ------------------------------------------------------------ warmup
    def warmup(self):
        """Compile the whole shape-bucket ladder (prefill per prompt
        bucket, decode per batch bucket) so serving traffic hits only
        cached executables. Idempotent; call before taking traffic.

        With the persistent AOT cache enabled (``MXNET_AOT_CACHE_DIR`` or
        ``aot.enable``), every ladder executable a previous process
        compiled is deserialized from disk instead — the cold-start
        warmup measured in ``mxnet_aot_warmup_seconds{path=serve}`` drops
        to IO + dispatch.

        The programs are given the pools they write (donation), so
        each example runs on the live pools and the engine keeps what it
        returns: the examples' tables are all-sink and their slot the sink
        slot, so no page and no state of a request is written. On a
        running engine the whole ladder runs at a tick boundary of the
        loop, which owns the pools."""
        self._on_loop(self._warmup, None, "warmup")
        return self

    def _warmup(self):
        t0 = time.perf_counter()
        for pb in bucket_ladder(self.min_prompt_bucket, self._chunk,
                                self._growth):
            self._warm(self._get_prefill(pb), "prefill", pb)
        if self._chunk < self.L:
            self._warm(self._get_chunk(), "chunk", self._chunk)
        if self._pages.prefix_cache_enabled:
            self._warm(self._get_copy(), "copy", 0)
        if self._pages_portable:
            # migration executables: warmed so a first preemption rescue
            # or tier page-stream inside steady-state serving hits cached
            # code (the no_recompile() contract with migration enabled).
            # The inject example writes zeros into the SINK page.
            self._warm(self._get_extract(), "extract", 0)
            self._warm(self._get_inject(), "inject", 0)
        for sb in bucket_ladder(1, self.S):
            # speculative engines decode exclusively through the verify
            # executables — warm those; plain engines warm the step fns
            if self.spec:
                self._warm(self._get_spec(sb), "spec", sb)
            else:
                self._warm(self._get_step(sb), "decode", sb)
        self.last_warmup_s = time.perf_counter() - t0
        from .. import aot as _aot
        if _aot.get_cache() is not None:
            # mxnet_aot_* families belong to the persistent cache; a
            # cache-less warmup must not feed cold/warm dashboards
            _metrics.AOT_WARMUP_SECONDS.labels(path="serve").observe(
                self.last_warmup_s)

    def _warm(self, fn, label: str, bucket: int):
        """Run one program on its example arguments and wait for it. The
        pools are the last thing a program returns that writes them (all
        of it, for a chunk, a copy and an inject): it was given
        them, so from here on these are the engine's."""
        out = fn(*self._example_args(label, bucket))
        if label in self._POOL_WRITERS:
            whole = ("copy", "inject") if self._moe else \
                ("chunk", "copy", "inject")
            self._pools = out if label in whole else out[-1]
        jax.block_until_ready(out)

    def _example_args(self, label: str, bucket: int):
        """Representative arguments for one bucket executable — what
        warmup calls, and what the AOT cache lowers/fingerprints (runtime
        calls differ only in values, never avals). Example tables
        are all-sink, so warmup's writes land in the sink page of the
        live pools. Grammar example operands are identity-safe: all-zero
        ``nxt`` tables mean every transition lands in state 0 and is
        allowed, and ``geos=-1`` keeps EOS out of the mask — warmup
        never samples through an empty mask."""
        def gram_args(rows: int, states: int):
            if not self._grammar:
                return ()
            V, G = int(self._vocab), self._gmax
            return (onp.zeros((rows, V), onp.int32),
                    onp.zeros((rows, G, G), onp.int32),
                    onp.ones((rows, G), bool),
                    (onp.zeros((states, self.spec), onp.int32)
                     if label == "spec" else onp.zeros(states, onp.int32)),
                    onp.full(states, -1, onp.int32))

        if label == "score":
            return (self._values, onp.zeros((1, bucket), onp.int32),
                    onp.int32(2))
        sink_tbl = lambda rows: self._tables([], rows)   # noqa: E731
        if label == "spec":
            return (self._values, self._pools,
                    onp.zeros((bucket, self.spec), onp.int32),
                    onp.zeros(bucket, onp.int32), sink_tbl(bucket)) \
                + gram_args(self.S, bucket) + (
                    onp.zeros(bucket, onp.float32),
                    onp.zeros(bucket, onp.int32),
                    onp.ones(bucket, onp.float32),
                    onp.zeros(bucket, onp.uint32),
                    onp.zeros(bucket, onp.int32))
        # with state every row names its slot: the examples' is the sink
        sink_slot = lambda rows: self._slot_rows(  # noqa: E731
            [self.S] * rows)
        if label == "prefill":
            return (self._values, self._pools,
                    onp.zeros((1, bucket), onp.int32), onp.int32(1),
                    onp.int32(0), sink_tbl(1)) + sink_slot(1) \
                + gram_args(1, 1) + (
                    onp.zeros(1, onp.float32), onp.zeros(1, onp.int32),
                    onp.ones(1, onp.float32), onp.zeros(1, onp.uint32),
                    onp.zeros(1, onp.int32))
        if label == "chunk":
            return (self._values, self._pools,
                    onp.zeros((1, bucket), onp.int32), onp.int32(0),
                    sink_tbl(1)) + sink_slot(1)
        if label == "copy":
            return (self._pools, onp.int32(0), onp.int32(0))
        if label == "extract":
            return (self._pools, onp.int32(0))
        if label == "inject":
            return (self._pools, self._page_payload_spec(),
                    onp.int32(self._pages.sink))
        args = (self._values, self._pools,
                onp.zeros(bucket, onp.int32),
                onp.zeros(bucket, onp.int32), sink_tbl(bucket)) + \
            sink_slot(bucket) + gram_args(self.S, bucket) + (
                onp.zeros(bucket, onp.float32),
                onp.zeros(bucket, onp.int32),
                onp.ones(bucket, onp.float32),
                onp.zeros(bucket, onp.uint32),
                onp.zeros(bucket, onp.int32))
        if self.K > 1:
            args = args + (onp.full(bucket, -1, onp.int32),
                           onp.ones(bucket, onp.int32))
        return args

    # ------------------------------------------------------------ executables
    def _get_compiled(self, cache: Dict[int, Any], bucket: int, builder,
                      label: str):
        with self._compile_lock:
            fn = cache.get(bucket)
            if fn is None:
                kind = "initial" if not cache else "retrace"
                _metrics.SERVE_COMPILES.labels(fn=label).inc()
                _metrics.RECOMPILATIONS.labels(block=f"serve_{label}",
                                               kind=kind).inc()
                fn = builder(bucket)
                args = self._example_args(label, bucket)
                from .. import aot as _aot
                if _aot.get_cache() is not None:
                    fn = _aot.compile_cached(
                        fn, args, label=f"serve_{label}",
                        extra={"bucket": bucket, "slots": self.S,
                               "max_len": self.L})
                else:
                    # cost-ledger capture at build time (with the AOT
                    # cache on, compile_cached records the same entry
                    # from the lowering it already holds)
                    _perf.capture_build(
                        f"serve_{label}", fn, args,
                        key=f"serve_{label}:b{bucket}",
                        meta={"bucket": bucket, "slots": self.S,
                              "max_len": self.L, "multi_token": self.K})
                if label in self._POOL_WRITERS:
                    compiled = _compiled(fn, args)
                    alias = _alias_bytes(compiled)
                    self._in_place = (alias if self._in_place is None
                                      else min(self._in_place, alias))
                    if compiled is not None and label not in self._copies_read:
                        self._copies_read.add(label)
                        self._pool_copies += _pool_copies(compiled,
                                                          self._pools)
                cache[bucket] = fn
            else:
                _metrics.CACHE_HITS.labels(block=f"serve_{label}").inc()
        return fn

    def _get_prefill(self, pb: int):
        return self._get_compiled(self._prefill_fns, pb,
                                  self._build_prefill, "prefill")

    def _get_step(self, sb: int):
        return self._get_compiled(self._step_fns, sb, self._build_step,
                                  "decode")

    def _get_spec(self, sb: int):
        return self._get_compiled(self._spec_fns, sb,
                                  self._build_step_spec, "spec")

    def _get_chunk(self):
        return self._get_compiled(self._chunk_fns, self._chunk,
                                  self._build_chunk, "chunk")

    def _get_copy(self):
        return self._get_compiled(self._copy_fns, 0, self._build_copy,
                                  "copy")

    def _get_extract(self):
        return self._get_compiled(self._extract_fns, 0,
                                  self._build_extract, "extract")

    def _get_inject(self):
        return self._get_compiled(self._inject_fns, 0, self._build_inject,
                                  "inject")

    def _get_score(self, pb: int):
        return self._get_compiled(self._score_fns, pb, self._build_score,
                                  "score")

    def _gram_dev(self):
        """Device copies of the [S, ...] grammar tables, re-uploaded
        only when a slot's grammar changed since the last dispatch —
        steady-state decode hands the SAME buffers to every step."""
        if self._gdirty:
            self._gdev = (jax.device_put(self._gcls),
                          jax.device_put(self._gnxt),
                          jax.device_put(self._gacc))
            self._gdirty = False
        return self._gdev

    def _page_payload_spec(self) -> Tuple[onp.ndarray, ...]:
        """Zero payload with the aval every shipped page must match:
        per pool entry, the pool's shape with the page axis collapsed
        to 1. Import verification compares against this (an aval
        mismatch would retrace — a violation of the zero-recompile
        contract — so it is rejected as a verify failure instead)."""
        return tuple(
            onp.zeros(tuple(1 if i == ax else d
                            for i, d in enumerate(p.shape)),
                      onp.dtype(p.dtype))
            for p, ax in zip(self._pools, self._paxes))

    def _slot_keys(self, seeds, counters):
        """Per-slot PRNG: fold_in(key(request seed), tokens generated) —
        stateless, so a request's sample stream is independent of batch
        composition and step scheduling. Shares generation._fold_keys so
        the engine's K=1 stream and the device multi-token loop can never
        diverge (the cross-K sampling-parity contract)."""
        return _gen._fold_keys(seeds, counters)

    def _build_step_spec(self, sb: int):
        """Self-speculative verify step: ONE forward over the [sb, spec]
        input matrix (current token + spec-1 drafts per row, written at
        per-row positions ``pos..pos+spec-1``), then the exact per-column
        verification (models/generation.spec_verify_tokens). Returns
        ``(toks [sb, spec], acc [sb], pools)``: ``toks[s, :acc[s]]`` are
        the row's tokens this round — bitwise the tokens the
        non-speculative engine would emit, greedy or sampled (the
        stateless fold_in streams make the verify recompute exact).
        Rejected drafts leave stale cache rows past the accepted point;
        the causal mask hides them until the next rounds overwrite them
        (the multi-token speculative-row contract). One kind=spec_verify
        launch site marks the trace next to the underlying GEMV/fused
        tallies."""
        from ..ops.int8_gemv import record_launch
        fm = self._fm
        grammar = self._grammar

        def _vmasks(rest):
            """Unpack grammar-gated trailing args; per-draft-position
            verify masks from the host-walked ``gstates [sb, T]`` (the
            drafts were pre-constrained by speculate.constrain_draft, so
            every position's automaton state is well-defined)."""
            if not grammar:
                return None, rest
            (gcls, gnxt, gacc, gstates, geos), rest = rest[:5], rest[5:]
            masks = _grammar.grammar_mask_multi(
                jax.lax.slice_in_dim(gcls, 0, sb, axis=0),
                jax.lax.slice_in_dim(gnxt, 0, sb, axis=0),
                jax.lax.slice_in_dim(gacc, 0, sb, axis=0),
                gstates, geos)
            return masks, rest

        def step(values, pools, inputs, pos, tables, *rest):
            record_launch("spec_verify")
            masks, rest = _vmasks(rest)
            temps, topks, topps, seeds, counters = rest
            logits, new_pools = _gen.decode_step(
                fm, values, inputs, pos, pools, block_table=tables)
            toks, acc = _gen.spec_verify_tokens(
                logits, inputs, temps, topks, topps, seeds, counters,
                masks=masks)
            return toks, acc, new_pools

        return jax.jit(step, donate_argnums=1)

    def _build_prefill(self, pb: int):
        """Prefill: attend ``ids`` at offset ``start`` through the
        slot's block table (the final/only chunk — samples token0 at
        counter ``counter0`` so preempted requests resume mid-stream)."""
        fm = self._fm
        grammar = self._grammar
        stateful, counted = self._stateful, self._takes_valid
        moe = self._moe is not None

        def prefill(values, pools, ids, true_len, start, table, *rest):
            rows = None
            if stateful:
                # the row's slot, and how many of the bucket's positions
                # are the prompt's: padding must not move a state
                rows, rest = (rest[0], jnp.reshape(true_len, (1,))), rest[1:]
            elif counted:
                # ... nor end a window, nor be routed to an expert
                rows = (jnp.reshape(true_len, (1,)),)
            if grammar:
                (gcls, gnxt, gacc, gstate, geos,
                 temps, topks, topps, seeds, counter0) = rest
            else:
                temps, topks, topps, seeds, counter0 = rest
            logits, new_pools = _gen.decode_step(fm, values, ids, start,
                                                 pools, block_table=table,
                                                 rows=rows)
            last = jax.lax.dynamic_index_in_dim(
                logits, true_len - 1, axis=1, keepdims=False)   # [1, V]
            keys = self._slot_keys(seeds, counter0)
            mask = (_grammar.grammar_mask(gcls, gnxt, gacc, gstate, geos)
                    if grammar else None)
            tok0 = _gen.sample_tokens(last, keys, temps, topks, topps,
                                      mask=mask)
            if moe:
                return tok0[0], new_pools[-1], new_pools[:-1]
            return tok0[0], new_pools

        return _jit_named(prefill, f"prefill_b{pb}", donate_argnums=1)

    def _build_chunk(self, cs: int):
        """A middle prefill chunk: KV-page writes only (XLA dead-code-
        eliminates the LM head — the chunk's logits are never used)."""
        fm = self._fm
        counted, moe = self._takes_valid, self._moe is not None

        def chunk(values, pools, ids, start, table, *slot):
            real = (jnp.full(1, cs, jnp.int32),)
            rows = slot + real if slot else real if counted else None
            _logits, new_pools = _gen.decode_step(fm, values, ids, start,
                                                  pools, block_table=table,
                                                  rows=rows)
            if moe:
                return new_pools[-1], new_pools[:-1]
            return new_pools

        return _jit_named(chunk, f"chunk_c{cs}", donate_argnums=1)

    def _build_step(self, sb: int):
        """Decode step over the shared page pools: every row addresses
        its KV rows through its block-table row (inactive rows:
        all-sink)."""
        fm, K, head = self._fm, self.K, self._head_pack

        if K > 1:
            def step(values, pools, tokens, pos, tables, temps, topks,
                     topps, seeds, counters, eos_ids, remaining):
                toks, last, steps, _done, new_pools = \
                    _gen.decode_multi_tokens(
                        fm, values, tokens, pos, pools, K, temps, topks,
                        topps, seeds, counters, eos_ids=eos_ids,
                        remaining=remaining, done=remaining <= 0,
                        head=head, block_table=tables)
                return toks, last, steps, new_pools

            return _jit_named(step, f"step_b{sb}", donate_argnums=1)

        grammar = self._grammar
        stateful, counted = self._stateful, self._takes_valid
        moe = self._moe is not None

        def step(values, pools, tokens, pos, tables, *rest):
            rows = None
            if stateful:
                rows, rest = (rest[0], jnp.ones(sb, jnp.int32)), rest[1:]
            elif counted:
                rows = (jnp.ones(sb, jnp.int32),)
            if grammar:
                (gcls, gnxt, gacc, gstate, geos,
                 temps, topks, topps, seeds, counters) = rest
                gcls = jax.lax.slice_in_dim(gcls, 0, sb, axis=0)
                gnxt = jax.lax.slice_in_dim(gnxt, 0, sb, axis=0)
                gacc = jax.lax.slice_in_dim(gacc, 0, sb, axis=0)
            else:
                temps, topks, topps, seeds, counters = rest
            logits, new_pools = _gen.decode_step(fm, values,
                                                 tokens[:, None], pos,
                                                 pools, block_table=tables,
                                                 rows=rows)
            keys = self._slot_keys(seeds, counters)
            mask = (_grammar.grammar_mask(gcls, gnxt, gacc, gstate, geos)
                    if grammar else None)
            nxt = _gen.sample_tokens(logits[:, -1], keys, temps, topks,
                                     topps, mask=mask)
            if moe:
                new_pools, counts = new_pools[:-1], new_pools[-1]
            if grammar:
                ngs = _grammar.grammar_advance(gcls, gnxt, gstate, nxt,
                                               geos)
                return nxt, ngs, new_pools
            if moe:
                return nxt, counts, new_pools
            return nxt, new_pools

        return _jit_named(step, f"step_b{sb}", donate_argnums=1)

    def _build_copy(self, _bucket: int):
        """Copy one physical page (COW fork: src's rows into the freshly
        leased dst) across every pool entry, along each entry's page
        axis."""
        paxes = self._paxes

        def copy(pools, src, dst):
            out = []
            for p, ax in zip(pools, paxes):
                page = jax.lax.dynamic_slice_in_dim(p, src, 1, axis=ax)
                out.append(jax.lax.dynamic_update_slice_in_dim(
                    p, page, dst, axis=ax))
            return tuple(out)

        return jax.jit(copy, donate_argnums=0)

    def _build_extract(self, _bucket: int):
        """Slice one physical page out of every pool entry (the export
        half of cross-replica page migration)."""
        paxes = self._paxes

        def extract(pools, src):
            return tuple(jax.lax.dynamic_slice_in_dim(p, src, 1, axis=ax)
                         for p, ax in zip(pools, paxes))

        return jax.jit(extract)

    def _build_inject(self, _bucket: int):
        """Write one shipped page (a per-pool tuple of 1-page slices)
        into physical page ``dst`` of every pool entry (the import half
        of cross-replica page migration)."""
        paxes = self._paxes

        def inject(pools, payload, dst):
            return tuple(jax.lax.dynamic_update_slice_in_dim(
                p, q, dst, axis=ax)
                for p, q, ax in zip(pools, payload, paxes))

        return jax.jit(inject, donate_argnums=0)

    def _build_score(self, pb: int):
        """Batched scoring executable: teacher-forced per-token
        log-probabilities of ``ids[0, 1:true_len]`` — ONE prefill-shaped
        forward over the prompt bucket ladder, no decode loop. Runs on
        FRESH length-L contiguous caches traced in: the serving pools are
        never read or written, so scoring is safe from any thread,
        concurrent with decode."""
        fm, spec1 = self._fm, self._spec1

        def score(values, ids, true_len):
            caches = tuple(jnp.zeros(s, d) for s, d in spec1)
            logits, _caches = _gen.decode_step(fm, values, ids,
                                               jnp.int32(0), caches)
            lp = jax.nn.log_softmax(logits[0].astype(jnp.float32),
                                    axis=-1)                     # [pb, V]
            tgt = jnp.roll(ids[0], -1)                           # [pb]
            tok_lp = jnp.take_along_axis(
                lp, tgt[:, None].astype(jnp.int32), axis=1)[:, 0]
            idx = jnp.arange(ids.shape[1])
            # position i scores token i+1; pad rows and the last real
            # token (nothing follows it) contribute exactly zero
            return jnp.where(idx < true_len - 1, tok_lp, 0.0)

        return jax.jit(score)

    # ------------------------------------------------------------ engine loop
    def _loop(self):
        try:
            self._loop_inner()
            # a swap staged between the last tick's apply and the drain
            # exit still lands (this is the engine thread — no race)
            self._apply_swaps()
            self._apply_page_ops()
        except Exception as e:  # pragma: no cover - defensive backstop
            # an unguarded failure must not leave a zombie engine that
            # accepts submits no step will ever serve: fail everything
            # outstanding and close
            try:
                warnings.warn(f"serve: engine loop crashed: {e!r}")
            except Exception:
                pass
            # the flight-recorder moment: dump the last-N-events ring
            # (admissions, retires, preemptions, spans) with the crash
            # attached, BEFORE the cleanup below mutates engine state
            _recorder.RECORDER.record(
                "error", "engine_loop_crash", error=repr(e),
                slots_active=sum(1 for s in self._slots if s is not None))
            _recorder.RECORDER.dump("engine_exception", force=True)
            with self._cond:
                self._running = False
                self._closed = True
                queued = list(self._queue)
                self._queue.clear()
                swaps, self._swaps = self._swaps, []
            for rec in swaps:
                # discard WITHOUT ok: the waiter must see the failure,
                # not record a deploy that never happened
                rec["evt"].set()
            self._fail_page_ops()
            pending, self._pending = self._pending, None
            if pending is not None:
                try:
                    # salvage the already-computed lookahead tokens before
                    # failing the slots
                    self._process_step(pending)
                except Exception:
                    pass
            for req in queued:
                try:
                    self._finish_unstarted(req, STATUS_ERROR, error=str(e))
                except Exception:
                    req._complete(ServeResult(
                        status=STATUS_ERROR, prompt_ids=req.prompt_ids,
                        generated_ids=[], error=str(e)))
            for s in range(self.S):
                if self._slots[s] is not None:
                    try:
                        self._retire(s, STATUS_ERROR, error=str(e))
                    except Exception:
                        self._slots[s].req._complete(ServeResult(
                            status=STATUS_ERROR,
                            prompt_ids=self._slots[s].req.prompt_ids,
                            generated_ids=list(self._slots[s].generated),
                            error=str(e)))
                        self._slots[s] = None
        finally:
            self._heartbeat.stop()

    def _idle(self) -> bool:
        return (self._running and not self._queue and not any(self._slots)
                and not self._swaps and not self._page_ops)

    def _span(self, name: str, hist=None, **attrs) -> _TickSpan:
        return _TickSpan(self, name, hist, **attrs)

    def _chip_idle(self) -> int:
        """1 if every program the engine dispatched has finished on the
        chip, which then idles until the next one arrives; else 0. Every
        serving program returns the pools, so a pool belongs to the newest
        one. Never waits."""
        return int(self._pools[0].is_ready())

    def _sync(self, name: str, program: str, t0: float, out, fetch,
              **attrs):
        """Wait for the program ``program`` dispatched at ``t0``
        (``perf_counter``) and read its outputs home: ``(fetch(), the
        instant the wait ended)``. The ``mx.serve.<name>`` span
        (``program``, ``age_us``: how long ago it was dispatched) holds the
        wait for its output ``out`` to be ready (the outputs of one
        execution are ready together), which is the chip finishing
        everything queued up to and including that program, and then
        ``mx.serve.fetch``, the copy home and the conversion. A wait of over
        ``_SLOW_WAIT_S`` goes into the tick's slow-tick record."""
        age = time.perf_counter() - t0
        with self._span(name, _metrics.SERVE_HOST_SYNC, program=program,
                        age_us=int(age * 1e6), **attrs) as sync:
            out.block_until_ready()
            with self._span("fetch") as home:
                value = fetch()
        if sync.seconds > _SLOW_WAIT_S:
            self._tick_waits.append(
                [name, program, round(age * 1e3, 3), round(sync.seconds, 6),
                 round(home.seconds, 6)])
        return value, sync.t1

    def _note_walk(self, span: _profiler.scope, positions, T: int,
                   substeps: int = 1):
        """Tell a dispatch span how many blocks of the block table the
        program it dispatches walks in each layer (``walk``: the columns
        that the deepest of its rows, each at its ``positions`` entry,
        reaches with ``T`` new positions, in blocks, once per substep of the
        multi-token loop) out of how many the table holds (``of``), and add
        both to the sums that ``stats()`` reports. A folded table's columns
        are the model's (``cache_fold().column``). ``form`` is the form the
        program's paged read was traced in (``models/llama.walk_form``)."""
        blk = self._kv_block
        column = self._pages.layout.column
        walk = sum(-(-max(column(min(p + j + T, self.L) - 1) + 1
                          for p in positions) // blk)
                   for j in range(substeps))
        of = substeps * -(-self.maxp * self.page_size // blk)
        span.set(walk=walk, of=of)
        self._kv_walked += walk
        self._kv_tabled += of
        if self._window is not None:
            # a windowed layer's read starts at the block of the earliest
            # column any of the rows' first queries still sees
            wwalk = sum(
                -(-min(max(p + j + T for p in positions), self.L) // blk)
                - max(min(p + j for p in positions) - self._window + 1, 0)
                // blk for j in range(substeps))
            span.set(wwalk=wwalk)
            self._wwalked += wwalk
        if self._walk_form is not None:
            form = self._walk_form(T)
            span.set(form=form)
            if form == "lanes":
                self._kv_on_lanes += walk
            elif form == "kernel":
                self._kv_in_kernel += walk

    def _fold_ended(self, span: _profiler.scope, rows):
        """After the dispatch of ``rows``, ``(slot, depth it brought the
        slot to)``: tell the span what their tables held when it ran
        (``held``) of the pages their depths would hold unfolded
        (``depth_pages``), and fold the table of every row whose depth ended
        a window: the program was handed the window's pages and the page for
        their summaries, and from here on the window's pages are anyone's
        (``folded``: how many did). Nothing, for a model that does not
        fold."""
        fold = self._fold
        if fold is None:
            return
        held = unfolded = folded = 0
        for s, depth in rows:
            held += fold.entries(depth)
            unfolded += pages_for(depth, self.page_size)
            if fold.ends_window(depth):
                self._pages.fold(s)
                folded += 1
        span.set(held=held, depth_pages=unfolded, folded=folded)
        self._held += held
        self._unfolded += unfolded
        self._tick_folded += folded

    def _note_window(self, span: _profiler.scope, rows):
        """After the dispatch of ``rows``, ``(slot, depth it brought the
        slot to)``: tell the span what their tables held of the windowed
        kind when it ran (``wheld``) of the pages their depths would hold
        unwindowed (``wdepth_pages``), and add both to the sums of
        ``stats()``. Nothing, for a model without such a kind."""
        if self._wpages is None:
            return
        held = sum(self._wpages.held(s) for s, _ in rows)
        depth = sum(pages_for(d, self.page_size) for _, d in rows)
        span.set(wheld=held, wdepth_pages=depth)
        self._wheld += held
        self._wdepth += depth

    def _note_experts(self, counts, routed: int,
                      span: Optional[_profiler.scope] = None):
        """Add one program's expert counts (``[layers, held]`` tokens
        received, read from the device) and its ``routed`` assignments to
        the sums of ``stats()``; a step's go onto its ``emit`` span too:
        ``moe_hit`` of ``moe_held`` held experts received a token, ``moe_here``
        of ``moe_routed`` assignments went to a held expert."""
        counts = onp.asarray(counts, onp.int64)
        here = int(counts.sum())
        self._moe_tokens += counts
        self._moe_routed += routed
        _metrics.SERVE_MOE_ASSIGNMENTS.labels(where="all").inc(routed)
        _metrics.SERVE_MOE_ASSIGNMENTS.labels(where="here").inc(here)
        if span is not None:
            span.set(moe_hit=int((counts > 0).sum()), moe_held=counts.size,
                     moe_here=here, moe_routed=routed)

    def _read_chunk_experts(self):
        """The counts of the chunks and prefills dispatched before the
        tokens just read, in their order: what the device has finished is
        read, and nothing is waited for (a chunk dispatched behind the step
        just read is still running, and is read with a later step)."""
        while self._moe_unread and self._moe_unread[0][0].is_ready():
            self._note_experts(*self._moe_unread.pop(0))

    def _lease(self, s: int, start: int, end: int):
        """Lease what the dispatch that writes slot ``s``'s positions
        ``[start, end)`` needs, of every kind: the full kind to ``end``; the
        windowed kind slid to ``start`` first (what lies wholly behind that
        position's window goes back to the pool) and then leased to ``end``.
        Raises :class:`OutOfPages` from either pool."""
        self._pages.lease(s, end)
        if self._wpages is not None:
            self._wpages.slide(s, start)
            self._wpages.lease(s, end)

    def _release(self, s: int):
        self._pages.release(s)
        if self._wpages is not None:
            self._wpages.release(s)

    def _note_sample(self, span: _profiler.scope, rows):
        """Tell a dispatch span how many of the ``(temperature, top_k,
        top_p)`` rows it selects a token for need filter_logits' search
        (``filtered``: sampled, under a top-k or a nucleus; a program none
        of whose rows does skips the search), and add rows and filtered to
        the sums of ``stats()``."""
        filtered = sum(1 for t, k, p in rows if t > 0 and (k > 0 or p < 1.0))
        span.set(filtered=filtered)
        self._sample_rows += len(rows)
        self._sample_filtered += filtered

    def _note_select(self, span: _profiler.scope, rows):
        """Where the model selects among its pages (``blocks_read``): tell
        a dispatch span how many blocks of one sparse layer's cache the
        program reads for its rows' ``(pos, T)`` (``sel``: a decoding row
        its selection, a chunk every live block once) of how many those
        rows hold (``of``), and add both to the sums of ``stats()``."""
        if self._blocks_read is None:
            return
        sel = of = 0
        for pos, T in rows:
            read, live = self._blocks_read(min(pos, self.L - T), T)
            sel, of = sel + read, of + live
        span.set(sel=sel, of=of)
        self._sel_read += sel
        self._sel_live += of

    def _slot_rows(self, slots) -> Tuple[onp.ndarray, ...]:
        """The rows' slot ids as one more argument of a program, for
        a model with per-slot state; nothing for any other."""
        return (onp.asarray(slots, onp.int32),) if self._stateful else ()

    def _loop_inner(self):
        while True:
            # live weight refresh lands BETWEEN ticks: everything below
            # (admissions, prefills, the decode dispatch) sees one
            # consistent weight set per iteration
            self._apply_swaps()
            # migrated KV pages land at the same boundary, for the same
            # reason: the loop owns self._pools
            self._apply_page_ops()
            with self._cond:
                while self._idle():
                    # a staged weight swap wakes the idle loop too: the
                    # next iteration's tick boundary applies it. One span
                    # a wait: a profiler that starts or stops while the
                    # engine sleeps loses a tenth of a second of it
                    with _profiler.scope("mx.serve.idle", "serve"):
                        self._cond.wait(0.1)
            self._tick_no += 1
            self._tick_children.clear()
            self._tick_waits.clear()
            self._tick_folded = 0
            with _profiler.scope(
                    "mx.serve.tick", "serve", tick=self._tick_no,
                    queued=len(self._queue),
                    prefilling=len(self._prefills)) as tick:
                self._tick_span = tick
                more = self._tick()
            self._close_tick(tick)
            if not more:
                break

    def _close_tick(self, tick: _profiler.scope):
        """A tick of over ``_SLOW_TICK_S`` goes to the flight recorder and
        the log: its child spans' seconds (``fetch`` is inside the two
        syncs), ``waits`` (``[span, program, age_ms, seconds, fetch_s]`` of
        each wait of over ``_SLOW_WAIT_S``: the wait less ``fetch_s`` is the
        chip finishing up to that program, ``fetch_s`` the copy home),
        ``cpu_s`` (the process's CPU seconds over the tick, from the
        heartbeat's reading at most a beat before it) and
        ``heartbeat_late_s`` (the longest lateness of a heartbeat that
        overlapped it: the process stopped, not the chip)."""
        compiles = _metrics.backend_compiles()
        if tick.seconds > _SLOW_TICK_S:
            late, cpu_s = self._heartbeat.tick_record(
                tick.t1 - tick.seconds, tick.t1)
            fields = dict(
                tick=self._tick_no, seconds=round(tick.seconds, 6),
                rows=tick.args.get("rows", 0), sb=tick.args.get("sb", 0),
                children={k: round(v, 6)
                          for k, v in self._tick_children.items()},
                waits=list(self._tick_waits),
                cpu_s=round(cpu_s, 6), heartbeat_late_s=round(late, 6),
                folded=self._tick_folded,
                compiles=compiles - self._compiles_seen)
            _recorder.RECORDER.record("event", "serve.slow_tick", **fields)
            logger.warning("serve: slow tick: %s", fields)
        self._compiles_seen = compiles

    def _tick(self) -> bool:
        """One iteration of the loop that has work: admit, prefill, one
        decode step. False once a stopping engine has nothing left."""
        admits: List[Tuple[int, RequestHandle]] = []
        dead: List[Tuple[RequestHandle, str]] = []
        # opened before the lock is taken: a long admit span is the lock,
        # whether this thread waited for it or held it
        with self._span("admit") as admit, self._cond:
            stopping = not self._running
            if stopping:
                for req in self._queue:
                    dead.append((req, STATUS_SHUTDOWN))
                self._queue.clear()
            else:
                now = time.perf_counter()
                # purge dead entries ANYWHERE in the queue: a live head
                # blocked on a full slot pool must not delay cancelled/
                # expired completions (or their queue-depth credit)
                # behind it
                kept: "deque[RequestHandle]" = deque()
                for req in self._queue:
                    if req._cancelled:
                        dead.append((req, STATUS_CANCELLED))
                    elif (req.deadline is not None
                          and now > req.deadline):
                        dead.append((req, STATUS_TIMEOUT))
                    else:
                        kept.append(req)
                self._queue = kept
                while self._queue:
                    s = self._free_slot()
                    if s is None:
                        break
                    if not self._fits(self._queue[0]):
                        # not enough pages even after reclaiming the
                        # whole prefix cache: admitting would only
                        # preempt-thrash — wait for retires (FIFO
                        # order preserved)
                        break
                    head = self._queue.popleft()
                    if head.admit_t is None:
                        # re-admission after a preemption keeps the
                        # ORIGINAL queue wait
                        head.admit_t = now
                    head._status = "running"
                    self._slots[s] = _Slot(
                        head, list(getattr(head, "_resume", ()) or ()),
                        now, now)
                    admits.append((s, head))
                _metrics.SERVE_QUEUE_DEPTH.set(len(self._queue))
            admit.set(admitted=len(admits))
        for req, status in dead:
            self._finish_unstarted(req, status)
        if self._pending is not None and stopping:
            # drain the lookahead step so its token reads and retires
            # land before the abort below. An admission only starts a
            # PREFILL (the decode set is untouched until the final
            # chunk): the step tick's own set check handles activation.
            self._process_step(self._pending)
            self._pending = None
        if stopping and self._abort_inflight:
            for s in range(self.S):
                if self._slots[s] is not None:
                    self._retire(s, STATUS_SHUTDOWN)
        for s, req in admits:
            self._admit(s, req)
        self._advance_prefills(stopping)
        if any(self._slots):
            self._step_tick()
            if self._step_delay:
                time.sleep(self._step_delay)
        elif stopping:
            return False
        self._observe_occupancy()
        return True

    def _free_slot(self) -> Optional[int]:
        for s in range(self.S):
            if self._slots[s] is None:
                return s
        return None

    def _observe_occupancy(self):
        n = sum(1 for s in self._slots if s is not None)
        self._max_active = max(self._max_active, n)
        _metrics.SERVE_SLOTS_IN_USE.set(n)
        _metrics.SERVE_SLOT_OCCUPANCY.set(n / self.S)

    # ------------------------------------------------------------ prefill
    def _fits(self, req: RequestHandle) -> bool:
        """Conservative admission gate: the pool (free + reclaimable
        prefix-cache pages) can hold the request's prompt plus its first
        decode writes. Prefix-cache hits only reduce the real need."""
        resume = getattr(req, "_resume", None) or ()
        tokens = min(len(req.prompt_ids) + len(resume) + self._adv, self.L)
        need = self._pages.layout.peak(tokens)
        if self._wpages is not None and \
                self._wpages.free_pages() < self._wpages.layout.need(tokens):
            return False
        return (self._pages.free_pages()
                + self._pages.cached_pages()) >= need

    def _admit(self, s: int, req: RequestHandle):
        """Register an admitted request's prefill (``_advance_prefills``
        dispatches the chunks): map the longest cached prefix into the
        slot's block table, then set the chunk cursor past it."""
        first_admission = req._resume is None
        resume = list(req._resume or ())
        ids = list(req.prompt_ids) + resume
        t0 = time.perf_counter()
        if first_admission:
            # a preempted request (even one that never emitted token0,
            # _resume == []) must not re-observe a queue wait inflated by
            # its prefill time
            _metrics.SERVE_QUEUE_WAIT.observe(t0 - req.submit_t)
        _recorder.RECORDER.record("event", "serve.admit", slot=s,
                                  prompt_tokens=len(ids),
                                  resumed=not first_admission)
        if req._trace is not None:
            if req._span_queue is not None:
                req._span_queue.set("tick", self._tick_no)
                req._span_queue.end()
                req._span_queue = None
            req._span_prefill = req._trace.child(
                "serve.prefill", slot=s, resumed=not first_admission,
                tick=self._tick_no)
            if not first_admission:
                req._trace.event("resume", tokens=len(resume))
        pages, matched = self._pages.match_prefix(ids)
        if matched:
            self._pages.map_prefix(s, pages, matched)
            _metrics.SERVE_PREFIX_BYTES_SAVED.inc(matched * self._tok_bytes)
            if req._span_prefill is not None:
                req._span_prefill.event("prefix_cache_hit", tokens=matched)
        if self._grammar:
            self._install_grammar(s, req)
        self._prefills[s] = _Prefill(ids=ids, cursor=matched,
                                     counter0=len(resume), t0=t0)

    def _advance_prefills(self, unlimited: bool):
        """Dispatch prefill chunks for slots mid-prefill. With decode
        traffic in flight, at most ``_chunks_per_tick`` chunks run per
        tick — the chunked-prefill TTFT contract: a long prompt costs
        every OTHER request one chunk of added inter-token latency per
        tick, never its whole prefill. With nothing decoding (or during
        a drain) chunks run back-to-back."""
        if not self._prefills:
            return
        budget = (len(self._prefills)
                  if unlimited or not self._active.any()
                  else self._chunks_per_tick)
        pending = []
        while budget > 0 and self._prefills:
            progressed = False
            for s in list(self._prefills):
                if budget <= 0:
                    break
                with self._span("prefill_dispatch", slot=s) as span:
                    rec = self._prefill_step(s, span)
                if rec is not None:
                    pending.append(rec)
                progressed = True
                budget -= 1
            if not progressed:
                break
        # a burst of finishing prefills pipelines: every token0 dispatch
        # is already in flight (D2H started at dispatch), so the host
        # syncs below overlap the remaining device work instead of
        # serializing dispatch->sync per slot
        for rec in pending:
            self._prefill_finalize(*rec)

    def _fork_range(self, s: int, start: int, end: int) -> int:
        """Copy-on-write: fork every shared page the slot is about to
        write in token range [start, end) — the ledger swaps in a fresh
        page, the device copies the rows (first-divergent-token
        semantics for prefix-cache consumers). Returns forks performed."""
        n = 0
        for ti, _src in self._pages.writable(s, start, end):
            src, dst = self._pages.fork(s, ti)
            self._pools = self._get_copy()(
                self._pools, onp.int32(src), onp.int32(dst))
            n += 1
        if n and self._slots[s] is not None:
            req = self._slots[s].req
            if req._trace is not None:
                req._trace.event("cow_fork", pages=n)
        return n

    def _table_row(self, s: int) -> onp.ndarray:
        """[1, max_pages] snapshot of the slot's block table ([1, 2,
        max_pages] with a windowed kind: the full kind's and its own)."""
        if self._wpages is not None:
            return onp.stack([self._pages.table(s),
                              self._wpages.table(s)])[None]
        return self._pages.table(s)[None, :].copy()

    def _prefill_step(self, s: int, span: _profiler.scope):
        """Advance one slot's prefill by ONE chunk (``span``, the caller's
        open ``prefill_dispatch``, is told which). A middle chunk only
        writes KV pages (returns None); the final chunk (bucketed
        remainder) also samples token0 — its host sync is DEFERRED: the
        returned ``(s, pf, req, slot, tok0_dev, program, t0)`` record is
        finalized by the caller after every chunk of the tick has
        dispatched. The span is told the ``program`` dispatched and whether
        the chip sat ``idle`` when the dispatch began."""
        idle = self._chip_idle()
        pf = self._prefills[s]
        slot = self._slots[s]
        req = slot.req
        now = time.perf_counter()
        if req._cancelled:
            self._retire(s, STATUS_CANCELLED)
            return
        if req.deadline is not None and now > req.deadline:
            self._retire(s, STATUS_TIMEOUT)
            return
        P = len(pf.ids)
        end = min(pf.cursor + self._chunk, P)
        span.set(start=pf.cursor, end=end, final=end == P)
        try:
            self._lease(s, pf.cursor, end)
            # the fork can ALSO exhaust the pool (lease satisfied from
            # already-held pages, but a shared prefix tail needs a fresh
            # page to fork into) — same yield-and-requeue path
            self._fork_range(s, pf.cursor, end)
        except OutOfPages:
            # mid-prefill exhaustion: yield — release and requeue at the
            # front; the admission gate readmits once pages free up
            self._preempt(s)
            return
        try:
            if end < P:
                t0w = time.time()
                fn = self._get_chunk()
                ids = onp.zeros((1, self._chunk), onp.int32)
                ids[0, :] = pf.ids[pf.cursor:end]
                self._note_walk(span, [pf.cursor], self._chunk)
                self._note_select(span, [(pf.cursor, self._chunk)])
                span.set(program=_program(fn), idle=idle)
                pools = fn(self._values, self._pools, ids,
                           onp.int32(pf.cursor), self._table_row(s),
                           *self._slot_rows([s]))
                if self._moe:
                    counts, pools = pools
                    self._moe_unread.append(
                        (counts, (end - pf.cursor) * self._moe_a_token))
                self._pools = pools
                self._fold_ended(span, [(s, end)])
                self._note_window(span, [(s, end)])
                if req._span_prefill is not None:
                    ch = req._span_prefill.child(
                        "serve.prefill_chunk", t0=t0w,
                        start=pf.cursor, end=end)
                    ch.end()
                pf.cursor = end
                _metrics.SERVE_PREFILL_CHUNKS.inc()
                return
            # final chunk: bucketed remainder + token0 sampling
            t0w = time.time()
            t0 = time.perf_counter()
            rest = P - pf.cursor
            pb = bucket_for(rest, self.min_prompt_bucket, self._chunk,
                            self._growth)
            fn = self._get_prefill(pb)
            ids = onp.zeros((1, pb), onp.int32)
            ids[0, :rest] = pf.ids[pf.cursor:]
            self._note_walk(span, [pf.cursor], pb)
            self._note_select(span, [(pf.cursor, pb)])
            self._note_sample(span, [(req.temperature, req.top_k, req.top_p)])
            program = _program(fn)
            span.set(program=program, idle=idle)
            gargs = ()
            if self._grammar:
                gargs = (self._gcls[s:s + 1].copy(),
                         self._gnxt[s:s + 1].copy(),
                         self._gacc[s:s + 1].copy(),
                         self._gstate[s:s + 1].copy(),
                         onp.array([-1 if req.eos_token_id is None
                                    else req.eos_token_id], onp.int32))
            tok0, *counts, pools = fn(
                self._values, self._pools, ids, onp.int32(rest),
                onp.int32(pf.cursor), self._table_row(s),
                *self._slot_rows([s]), *gargs,
                onp.array([req.temperature], onp.float32),
                onp.array([req.top_k], onp.int32),
                onp.array([req.top_p], onp.float32),
                onp.array([req.seed & 0xFFFFFFFF], onp.uint32),
                onp.array([pf.counter0], onp.int32))
            self._pools = pools
            if counts:
                self._moe_unread.append(
                    (counts[0], rest * self._moe_a_token))
            self._fold_ended(span, [(s, P)])
            self._note_window(span, [(s, P)])
            if req._span_prefill is not None:
                ch = req._span_prefill.child(
                    "serve.prefill_chunk", t0=t0w, start=pf.cursor, end=P,
                    final=True)
                ch.end()
            try:
                tok0.copy_to_host_async()   # start the D2H early
            except Exception:
                pass
        except Exception as e:  # pragma: no cover - defensive
            warnings.warn(f"serve: prefill failed: {e!r}")
            self._retire(s, STATUS_ERROR, error=str(e))
            self._pools_lost(e)
            return None
        # the whole prompt's KV is live (on the device stream): publish it
        # for future prefix reuse BEFORE decode writes dirty the tail page
        # (the insert pins the pages; the slot's own next write forks the
        # shared tail), and deregister the prefill so a same-tick budget
        # round cannot re-step this slot while its token0 is in flight
        self._pages.insert_prefix(s, pf.ids)
        del self._prefills[s]
        return (s, pf, req, slot, tok0, program, t0)

    def _prefill_finalize(self, s: int, pf: "_Prefill",
                          req: RequestHandle, slot: "_Slot", tok0_dev,
                          program: str, t0: float):
        """Host-sync one deferred final-chunk token0 (of ``program``,
        dispatched at ``t0``) and activate the slot for decode."""
        try:
            tok0, now = self._sync("prefill_sync", program, t0, tok0_dev,
                                   lambda: int(tok0_dev), slot=s)
        except Exception as e:  # pragma: no cover - defensive
            warnings.warn(f"serve: prefill failed: {e!r}")
            # the prefix was published at dispatch, before the device
            # program proved itself — don't let a failed prefill leave
            # suspect KV pages matchable by future prompts
            self._pages.clear_prefix_cache()
            self._retire(s, STATUS_ERROR, error=str(e))
            return
        self._read_chunk_experts()
        _metrics.SERVE_ROUNDTRIPS.labels(path="prefill").inc()
        _metrics.SERVE_PREFILL_SECONDS.observe(now - pf.t0)
        if _metrics.ENABLED:
            # the final chunk's bucket (pf.cursor stops at the last
            # chunk boundary); the note's dt spans the whole chunked
            # admission, so prefill MFU reads per-admission
            pb = bucket_for(max(1, len(pf.ids) - pf.cursor),
                            self.min_prompt_bucket, self._chunk,
                            self._growth)
            _perf.note_step("serve_prefill", now - pf.t0,
                            key=f"serve_prefill:b{pb}")
        if req.first_token_t is None:
            req.first_token_t = now
            _metrics.SERVE_TTFT.observe(now - req.submit_t)
        _metrics.SERVE_TOKENS.inc()
        if req._span_prefill is not None:
            req._span_prefill.set("ttft_s", round(now - req.submit_t, 6))
            req._span_prefill.end()
            req._span_prefill = None
        g = pf.counter0                     # resumed tokens already emitted
        self._pos[s] = len(pf.ids)
        self._counters[s] = g + 1
        self._temps[s] = req.temperature
        self._topks[s] = req.top_k
        self._topps[s] = req.top_p
        self._seeds[s] = req.seed & 0xFFFFFFFF
        self._eos[s] = -1 if req.eos_token_id is None else req.eos_token_id
        self._remaining[s] = req.max_new_tokens - g - 1
        self._tokens[s] = tok0
        if self._grammar:
            self._advance_gstate(s, tok0)
        self._active[s] = True
        slot.generated.append(tok0)
        req._emit(tok0)
        slot.t_last = now
        self._check_finished(s, now)
        self._observe_occupancy()

    def _preempt(self, s: int):
        """Release a slot's pages and requeue its request at the FRONT of
        the queue with its generated tokens stashed for resume. The
        stateless ``fold_in(key(seed), counter)`` sampling streams make
        the resume exact: re-prefilling ``prompt + generated`` and
        continuing at counter ``len(generated)`` reproduces the token
        sequence bit-for-bit."""
        slot = self._slots[s]
        req = slot.req
        req._resume = list(slot.generated)
        doc = None
        if self._migrate_hook is not None and self._pages_portable:
            # capture the victim's leased pages BEFORE release() frees
            # them — this is the engine thread, so the pools are stable
            try:
                doc = self._export_slot_pages(
                    s, list(req.prompt_ids) + req._resume)
            except Exception as e:
                warnings.warn(f"serve: preempt-rescue export failed, "
                              f"requeueing locally: {e!r}")
                doc = None
        self._slots[s] = None
        self._active[s] = False
        self._prefills.pop(s, None)
        self._release(s)
        self._reset_slot_state(s)
        self._preempted += 1
        _metrics.SERVE_PAGE_PREEMPTIONS.inc()
        _recorder.RECORDER.record_preemption(
            slot=s, generated=len(req._resume))
        if req._trace is not None:
            if req._span_prefill is not None:
                req._span_prefill.end(status="preempted")
                req._span_prefill = None
            req._trace.event("preempt", generated=len(req._resume))
            # the request goes back to waiting for pages/slots: a fresh
            # queue span covers the re-admission wait
            req._span_queue = req._trace.child("serve.queue", requeued=True)
        if doc is not None:
            # preemption-rescue: hand the victim (tokens + its already-
            # computed pages) to the migration hook. True = the hook owns
            # the request now — it resumes on another replica and pipes
            # the result back into this handle; do NOT requeue.
            try:
                if self._migrate_hook(self, req, doc):
                    return
            except Exception as e:
                warnings.warn(f"serve: preempt-rescue hook failed, "
                              f"requeueing locally: {e!r}")
        req._status = "queued"
        with self._lock:
            # requeue-front may transiently exceed max_queue_depth —
            # preemption must never DROP an admitted request
            self._queue.appendleft(req)
            _metrics.SERVE_QUEUE_DEPTH.set(len(self._queue))

    # ------------------------------------------------------------ decode
    def _decoding(self) -> List[Tuple[int, "_Slot"]]:
        """(slot index, slot) for every decode-active slot, in row order.
        Mid-prefill slots are excluded — their decode rows are all-sink."""
        return [(s, self._slots[s]) for s in range(self.S)
                if self._active[s] and self._slots[s] is not None]

    @staticmethod
    def _same_rows(a: List[Tuple[int, "_Slot"]],
                   b: List[Tuple[int, "_Slot"]]) -> bool:
        return (len(a) == len(b)
                and all(x[0] == y[0] and x[1] is y[1]
                        for x, y in zip(a, b)))

    def _lease_decode(self):
        """Fork shared pages and lease growth for this tick's decode
        writes (each active row writes token positions
        ``[pos, pos + _adv)`` — K for multi-token, the verify width for
        speculative rounds). Pool exhaustion preempts the youngest slot
        (prefilling or decoding) and retries — the oldest admitted work
        always makes progress. Returns the number preempted."""
        preempted = 0
        while True:
            try:
                for s in range(self.S):
                    if self._active[s]:
                        p = int(self._pos[s])
                        self._fork_range(s, p, p + self._adv)
                        self._lease(s, p, min(p + self._adv, self.L))
                return preempted
            except OutOfPages:
                # youngest by ORIGINAL admission time (req.admit_t survives
                # preemption; _Slot.t_admit resets on re-admission, which
                # would make a resumed request look newest and thrash
                # through repeated preempt/re-prefill cycles)
                victim = max(
                    (s for s in range(self.S) if self._slots[s] is not None),
                    key=lambda s: self._slots[s].req.admit_t)
                self._preempt(victim)
                preempted += 1

    def _step_tick(self):
        """Advance decode one tick. Synchronous mode dispatches one step
        and reads it. Lookahead mode dispatches step N+1 — feeding step
        N's device token vector straight back in — BEFORE reading step N,
        so the host sync overlaps the next step's compute; a retire at
        the read drains the speculative step (its rows for dead slots are
        discarded) so the loop can shrink/refill before re-dispatching.
        Speculative mode (speculate=K) replaces the per-token step with
        draft-verify rounds.

        The decode batch spans the slot-index prefix up to the highest
        ACTIVE slot; inactive rows in the bucket carry all-sink block
        tables (their writes land in the sink page, their sampled tokens
        are discarded). The lookahead token vector is fed back only while
        the active row set is unchanged — activation (a prefill
        finishing), preemption and retires all force a drain first."""
        if self.spec:
            self._step_tick_spec()
            return
        prev, self._pending = self._pending, None
        with self._span("lease") as lease:    # may preempt (changes the set)
            lease.set(preempted=self._lease_decode())
        cur = self._decoding()
        if not cur:
            if prev is not None:
                self._process_step(prev)
            return
        sb = bucket_for(cur[-1][0] + 1, 1, self.S)
        if prev is not None and not (prev.sb == sb
                                     and self._same_rows(prev.slots, cur)):
            retired = self._process_step(prev)
            prev = None
            if retired:
                cur = self._decoding()
                if not cur:
                    return
                sb = bucket_for(cur[-1][0] + 1, 1, self.S)
        self._tick_span.set(rows=len(cur), sb=sb)
        with self._span("decode_dispatch", sb=sb, rows=len(cur)) as disp:
            idle = self._chip_idle()
            self._note_walk(disp, [int(self._pos[s]) for s, _ in cur],
                            1, self.K)
            self._note_select(disp, [(int(self._pos[s]), 1) for s, _ in cur])
            self._note_sample(disp, [
                (float(self._temps[s]), int(self._topks[s]),
                 float(self._topps[s])) for s, _ in cur])
            rec = self._dispatch_step(prev, cur, sb)
            if rec is not None:
                disp.set(program=rec.program, idle=idle)
                # the host clocks already stand behind the dispatched step
                self._fold_ended(disp, [(s, int(self._pos[s]))
                                        for s, _ in cur])
                self._note_window(disp, [(s, int(self._pos[s]))
                                         for s, _ in cur])
        if rec is None:
            return
        if prev is not None:
            retired = self._process_step(prev)
            if retired:
                self._process_step(rec)
                rec = None
        if self._lookahead:
            self._pending = rec
        elif rec is not None:
            self._process_step(rec)

    def _tables(self, cur: List[Tuple[int, "_Slot"]], sb: int
                ) -> onp.ndarray:
        """[sb, max_pages] snapshot of the decoding rows' block tables
        for one dispatch (a fresh array — nothing for jit arg conversion
        to alias); a row that decodes nothing points every logical page
        at the sink."""
        tables = onp.full((sb, self.maxp), self._pages.sink, onp.int32)
        for s, _ in cur:
            tables[s] = self._pages.table(s)
        if self._wpages is None:
            return tables
        # with a windowed kind [sb, 2, max_pages]: each row's two tables
        wtables = onp.full((sb, self.maxp), self._wpages.sink, onp.int32)
        for s, _ in cur:
            wtables[s] = self._wpages.table(s)
        return onp.stack([tables, wtables], axis=1)

    def _dispatch_step(self, prev: Optional[_PendingStep],
                       cur: List[Tuple[int, "_Slot"]], sb: int
                       ) -> Optional[_PendingStep]:
        """Dispatch one decode step over slot rows [0, sb) without
        waiting for it. ``prev`` (lookahead) feeds the previous step's
        device-resident output tokens back in; None reads the host token
        array. Advances the host pos/counter clocks to match the
        dispatched step. On dispatch failure, first processes ``prev`` —
        its tokens were already computed and must not be lost (a request
        finishing there completes OK, not error) — then retires the
        remaining slots and returns None."""
        t0 = time.perf_counter()
        tables = self._tables(cur, sb)
        # SNAPSHOT the host arrays (.copy()): with a step left in flight,
        # jit arg conversion can still be reading these buffers when the
        # loop mutates them (pos/counter advance below, retire resets,
        # token writes at process time)
        if prev is not None:
            tokens = prev.nxt
        else:
            tokens = self._tokens[:sb].copy()
        fn = self._get_step(sb)
        # with state: row r serves slot r, a row that decodes nothing the sink
        slot_rows = self._slot_rows(
            [r if self._active[r] else self.S for r in range(sb)])
        try:
            ngs, counts = None, []
            if self.K > 1:
                toks, nxt, steps, pools = fn(
                    self._values, self._pools,
                    tokens, self._pos[:sb].copy(), tables,
                    self._temps[:sb].copy(), self._topks[:sb].copy(),
                    self._topps[:sb].copy(), self._seeds[:sb].copy(),
                    self._counters[:sb].copy(), self._eos[:sb].copy(),
                    self._remaining[:sb].copy())
            elif self._grammar:
                toks = steps = None
                gcls_d, gnxt_d, gacc_d = self._gram_dev()
                gstate = (prev.gstate if prev is not None
                          else self._gstate[:sb].copy())
                nxt, ngs, pools = fn(
                    self._values, self._pools,
                    tokens, self._pos[:sb].copy(), tables, *slot_rows,
                    gcls_d, gnxt_d, gacc_d, gstate,
                    self._eos[:sb].copy(),
                    self._temps[:sb].copy(), self._topks[:sb].copy(),
                    self._topps[:sb].copy(), self._seeds[:sb].copy(),
                    self._counters[:sb].copy())
            else:
                toks = steps = None
                nxt, *counts, pools = fn(
                    self._values, self._pools,
                    tokens, self._pos[:sb].copy(), tables, *slot_rows,
                    self._temps[:sb].copy(), self._topks[:sb].copy(),
                    self._topps[:sb].copy(), self._seeds[:sb].copy(),
                    self._counters[:sb].copy())
            self._pools = pools
        except Exception as e:  # pragma: no cover - defensive
            warnings.warn(f"serve: decode step failed: {e!r}")
            if prev is not None:
                self._process_step(prev)
            for s in range(self.S):
                if self._slots[s] is not None:
                    self._retire(s, STATUS_ERROR, error=str(e))
            self._pools_lost(e)
            return None
        rec = _PendingStep(nxt=nxt, sb=sb, t0=t0, toks=toks, steps=steps,
                           gstate=ngs, slots=cur,
                           counts=counts[0] if self._moe and counts else None,
                           program=_program(fn))
        # the dispatched program owns its snapshot of this tick's
        # pos/counters; advance the host clocks now so the NEXT dispatch
        # — possibly before this one is read — sees post-step values.
        # K > 1 advances by K: the device runs K substeps whenever ANY
        # row is live (the early exit fires only with every row done, and
        # then every slot retires at the read and its clocks reset).
        for s, _ in cur:
            self._pos[s] += self.K
            self._counters[s] += self.K
            self._remaining[s] -= self.K
        try:
            for dev in (rec.toks, rec.steps, nxt, rec.counts):
                if dev is not None:
                    dev.copy_to_host_async()   # start the D2H early
        except Exception:
            pass
        return rec

    # ------------------------------------------------------ speculative decode
    def _step_tick_spec(self):
        """One self-speculative draft-verify round over every live slot.
        Drafts come from each request's OWN token
        history (serve/speculate.draft_from_history — n-gram prompt
        lookup, no draft model); ONE dispatch verifies all of them and
        emits 1..K true tokens per row. Rounds are synchronous by
        construction: the next round's drafts depend on the tokens this
        round accepts, so there is no pending step to overlap — the K
        tokens per host round-trip ARE the overlap win."""
        from . import speculate as _spec
        with self._span("lease") as lease:    # may preempt
            lease.set(preempted=self._lease_decode())
        cur = self._decoding()
        if not cur:
            return
        sb = bucket_for(cur[-1][0] + 1, 1, self.S)
        T = self.spec
        t0 = time.perf_counter()
        # fresh arrays per dispatch (nothing for jit arg conversion to
        # alias); inactive bucket rows verify zeros against zeros at the
        # sink rows and are discarded at the read
        inputs = onp.zeros((sb, T), onp.int32)
        gstates = (onp.zeros((sb, T), onp.int32) if self._grammar
                   else None)
        for s, slot in cur:
            hist = list(slot.req.prompt_ids) + list(slot.generated)
            inputs[s, 0] = self._tokens[s]
            draft = _spec.draft_from_history(
                hist, self._n_draft, self._spec_lookup) \
                + [int(self._tokens[s])] * (T - 1 - self._n_draft)
            if self._grammar:
                g = self._gram[s]
                q0 = int(self._gstate[s])
                if g is not None:
                    # rewrite grammar-dead draft tokens to legal ones
                    # (a forbidden draft would be rejected by the
                    # masked verify anyway — rewriting only ever GAINS
                    # acceptance) and record the per-position automaton
                    # states the verify masks are gathered from
                    draft, states, rej = _spec.constrain_draft(
                        draft, g, q0)
                    if rej:
                        _metrics.GRAMMAR_REJECTED.inc(rej)
                    gstates[s, :] = states[:T]
                else:
                    gstates[s, :] = q0
            inputs[s, 1:] = draft
        fn = self._get_spec(sb)
        try:
            args = (self._values, self._pools, inputs,
                    self._pos[:sb].copy(), self._tables(cur, sb))
            if self._grammar:
                gcls_d, gnxt_d, gacc_d = self._gram_dev()
                args = args + (gcls_d, gnxt_d, gacc_d, gstates,
                               self._eos[:sb].copy())
            toks, acc, pools = fn(
                *args,
                self._temps[:sb].copy(), self._topks[:sb].copy(),
                self._topps[:sb].copy(), self._seeds[:sb].copy(),
                self._counters[:sb].copy())
            self._pools = pools
        except Exception as e:  # pragma: no cover - defensive
            warnings.warn(f"serve: speculative decode step failed: {e!r}")
            for s in range(self.S):
                if self._slots[s] is not None:
                    self._retire(s, STATUS_ERROR, error=str(e))
            self._pools_lost(e)
            return
        try:
            for dev in (toks, acc):
                dev.copy_to_host_async()      # start the D2H early
        except Exception:
            pass
        self._process_step_spec(cur, toks, acc, t0, sb, _program(fn))

    def _process_step_spec(self, cur, toks_dev, acc_dev, t0: float,
                           sb: int, program: str):
        """Host-read one verify round and apply it: per row, append the
        ``acc`` valid tokens in order (accepted draft prefix + the one
        correction/bonus token), advancing the pos/counter/remaining
        clocks per APPENDED token — acceptance is data, so the clocks
        move at the read, not the dispatch. EOS/budget/deadline scanning
        stops a row early exactly like the multi-token K-vector scan."""
        try:
            (toks, acc), now = self._sync(            # [sb, T], [sb]
                "decode_sync", program, t0, toks_dev,
                lambda: (onp.asarray(toks_dev), onp.asarray(acc_dev)), sb=sb)
        except Exception as e:  # pragma: no cover - defensive
            warnings.warn(f"serve: speculative decode step failed: {e!r}")
            for s, slot in cur:
                if self._slots[s] is slot:
                    self._retire(s, STATUS_ERROR, error=str(e))
            return
        now_wall = time.time()
        chunk_t0w = now_wall - (now - t0)
        _metrics.SERVE_ROUNDTRIPS.labels(path="decode").inc()
        drafted = rejected = 0
        appended = 0
        for s, slot in cur:
            if self._slots[s] is not slot:    # pragma: no cover - invariant
                continue
            e = int(acc[s])                           # 1..T valid tokens
            drafted += self.spec - 1
            rejected += self.spec - e                 # unaccepted drafts
            per_tok = (now - slot.t_last) / e
            row_tokens = 0
            for j in range(e):
                tok = int(toks[s, j])
                slot.generated.append(tok)
                slot.req._emit(tok)
                _metrics.SERVE_INTERTOKEN.observe(per_tok)
                slot.t_last = now
                self._tokens[s] = tok
                if self._grammar:
                    self._advance_gstate(s, tok)
                # clocks advance per appended token: the token's cache
                # row is live (pos), its sampling counter consumed
                self._pos[s] += 1
                self._counters[s] += 1
                self._remaining[s] -= 1
                appended += 1
                row_tokens += 1
                self._check_finished(s, now)
                if self._slots[s] is not slot:
                    break                  # rest of the round: discard
            if slot.req._trace is not None and row_tokens:
                ch = slot.req._trace.child("serve.decode_chunk",
                                           t0=chunk_t0w,
                                           tokens=row_tokens,
                                           speculative=True,
                                           tick=self._tick_no)
                ch.end(t1=now_wall)
        self._spec_rounds += 1
        self._spec_drafted += drafted
        self._spec_accepted += drafted - rejected
        _metrics.SPEC_ROUNDS.inc()
        if drafted:
            _metrics.SPEC_DRAFTED.inc(drafted)
            _metrics.SPEC_REJECTED.inc(rejected)
            _metrics.SPEC_ACCEPTED.inc(drafted - rejected)
        if self._spec_drafted:
            _metrics.SPEC_ACCEPTANCE.set(
                self._spec_accepted / self._spec_drafted)
        dt = now - t0
        _metrics.SERVE_STEP_SECONDS.observe(dt)
        _metrics.SERVE_TOKENS.inc(appended)
        if _metrics.ENABLED and dt > 0:
            _metrics.SERVE_TOKENS_PER_SEC.set(appended / dt)
            # work=1: unlike the multi-token while_loop (body counted
            # once, scaled by K), the verify executable's cost analysis
            # already covers all spec positions — one trace, one forward
            _perf.note_step("serve_decode", dt,
                            key=f"serve_spec:b{sb}", work=1.0)

    def _process_step(self, rec: _PendingStep) -> bool:
        """Host-read one dispatched step and apply it: append tokens,
        update the host token array, retire finished slots. Rows whose
        slot was retired since dispatch are discarded (identity check).
        Multi-token steps scan each row's K-vector in order, stopping at
        the row's EOS/budget/deadline — tokens past the stop are the
        speculative rows the parity contract discards. Returns True when
        any slot retired."""
        try:
            (toks, steps, counts), now = self._sync(
                "decode_sync", rec.program, rec.t0, rec.nxt,
                lambda: self._fetch_step(rec), sb=rec.sb)
        except Exception as e:  # pragma: no cover - defensive
            warnings.warn(f"serve: decode step failed: {e!r}")
            for s, slot in rec.slots:
                if self._slots[s] is slot:
                    self._retire(s, STATUS_ERROR, error=str(e))
            return True
        with self._span("emit") as emit:
            if counts is not None:
                # the step's own counts onto the span; the chunks' that ran
                # before it into the sums
                self._read_chunk_experts()
                self._note_experts(
                    counts, len(rec.slots) * self._moe_a_token, emit)
            retired, appended = self._apply_step(rec, toks, steps, now)
            emit.set(tokens=appended, retired=int(retired))
        return retired

    @staticmethod
    def _fetch_step(rec: _PendingStep):
        """A finished step's outputs on the host: ``(tokens [sb, K],
        substeps run, expert counts or None)``."""
        if rec.toks is not None:
            toks = onp.asarray(rec.toks)
            steps = int(rec.steps)
        else:
            toks = onp.asarray(rec.nxt)[:, None]
            steps = 1
        counts = None if rec.counts is None else onp.asarray(rec.counts)
        return toks, steps, counts

    def _apply_step(self, rec: _PendingStep, toks, steps: int,
                    now: float) -> Tuple[bool, int]:
        """The host's part of a read step: ``(any slot retired, tokens
        appended)``."""
        now_wall = time.time()
        # the dispatch stamp is perf_counter-based; shift it onto the
        # wall clock for the trace spans
        chunk_t0w = now_wall - (now - rec.t0)
        _metrics.SERVE_ROUNDTRIPS.labels(path="decode").inc()
        live = [(s, slot) for s, slot in rec.slots
                if self._slots[s] is slot]
        retired = False
        appended = 0
        for s, slot in live:
            # amortize the block's wall time over its K tokens: observing
            # (now - t_last) per token would record one full interval +
            # K-1 zeros and collapse the histogram's percentiles
            per_tok = (now - slot.t_last) / steps
            row_tokens = 0
            for j in range(steps):
                tok = int(toks[s, j])
                slot.generated.append(tok)
                slot.req._emit(tok)
                _metrics.SERVE_INTERTOKEN.observe(per_tok)
                slot.t_last = now
                self._tokens[s] = tok
                if self._grammar:
                    self._advance_gstate(s, tok)
                appended += 1
                row_tokens += 1
                self._check_finished(s, now)
                if self._slots[s] is not slot:
                    retired = True
                    break                  # rest of the K-vector: discard
            if slot.req._trace is not None and row_tokens:
                # one span per dispatched decode chunk per request
                # (dispatch -> host read; K tokens ride one chunk)
                ch = slot.req._trace.child("serve.decode_chunk",
                                           t0=chunk_t0w, tokens=row_tokens,
                                           tick=self._tick_no)
                ch.end(t1=now_wall)
        # dispatch-to-read wall time: under lookahead consecutive spans
        # overlap by design (the read waits on compute that ran behind
        # the NEXT dispatch), so this reads as per-token latency, not
        # exclusive device time
        dt = now - rec.t0
        _metrics.SERVE_STEP_SECONDS.observe(dt)
        _metrics.SERVE_TOKENS.inc(appended)
        if _metrics.ENABLED and dt > 0:
            _metrics.SERVE_TOKENS_PER_SEC.set(appended / dt)
            # live roofline: this dispatch ran the b<sb> decode
            # executable; mxnet_mfu{path=serve_decode} divides its
            # ledger cost by this wall time at the next collection.
            # work=K: XLA cost analysis counts the multi-token
            # while_loop body once, so scale to the K substeps one
            # dispatch runs (early exit only fires when all rows are
            # done, i.e. at most once per request tail)
            _perf.note_step("serve_decode", dt,
                            key=f"serve_decode:b{rec.sb}",
                            work=float(self.K))
        return retired, appended

    def _check_finished(self, s: int, now: float):
        slot = self._slots[s]
        req = slot.req
        # completion first: a request whose final token landed in the same
        # step its deadline (or cancel) raced is COMPLETE, not timed out
        if (req.eos_token_id is not None
                and slot.generated[-1] == req.eos_token_id):
            self._retire(s, STATUS_OK)
        elif len(slot.generated) >= req.max_new_tokens:
            self._retire(s, STATUS_OK)
        elif req._cancelled:
            self._retire(s, STATUS_CANCELLED)
        elif req.deadline is not None and now > req.deadline:
            self._retire(s, STATUS_TIMEOUT)

    # ------------------------------------------------------------ grammar
    def _install_grammar(self, s: int, req: RequestHandle):
        """Write the request's automaton into the slot's rows of the
        [S, ...] device-bound tables and seed the slot's automaton state
        (walking any resumed tokens, so preemption/migration resume
        keeps the constraint exact). Unconstrained requests install
        identity tables — constrained and free traffic mix in one
        batch."""
        g = req.grammar
        if g is None:
            cls_row, nxt_row, acc_row = _grammar.identity_tables(
                int(self._vocab), self._gmax, self._gmax)
        else:
            cls_row, nxt_row, acc_row = g.padded_tables(self._gmax,
                                                        self._gmax)
        self._gram[s] = g
        self._gcls[s] = cls_row
        self._gnxt[s] = nxt_row
        self._gacc[s] = acc_row
        q = 0
        if g is not None:
            for tok in (req._resume or ()):
                nq = g.advance(q, int(tok))
                if nq < 0:
                    break   # defensive: keep the last live state
                q = nq
        self._gstate[s] = q
        self._gdirty = True

    def _advance_gstate(self, s: int, tok: int):
        """Advance the slot's HOST automaton state past one emitted
        token — the authoritative ledger (device-returned states are
        only the lookahead feedback; every read re-syncs from here).
        EOS parks the state (the slot is about to retire); a forbidden
        token cannot be emitted by construction (the mask), so a
        negative advance is a defensive park, never silent corruption."""
        g = self._gram[s]
        if g is None:
            return
        if tok == int(self._eos[s]):
            return
        nq = g.advance(int(self._gstate[s]), int(tok))
        if nq >= 0:
            self._gstate[s] = nq
        else:  # pragma: no cover - mask invariant violated
            warnings.warn(
                f"serve: grammar automaton rejected emitted token {tok} "
                f"in state {int(self._gstate[s])} (slot {s}) — the "
                "device mask and host ledger diverged; parking the state")

    # ------------------------------------------------------------ completion
    def _reset_slot_state(self, s: int):
        self._tokens[s] = 0
        self._pos[s] = 0
        self._temps[s] = 0.0
        self._topks[s] = 0
        self._topps[s] = 1.0
        self._seeds[s] = 0
        self._counters[s] = 0
        self._eos[s] = -1
        self._remaining[s] = 0
        if self._grammar and self._gram[s] is not None:
            # back to identity so a stale constrained row can never
            # empty-mask a discarded bucket row
            icls, inxt, iacc = _grammar.identity_tables(
                int(self._vocab), self._gmax, self._gmax)
            self._gcls[s] = icls
            self._gnxt[s] = inxt
            self._gacc[s] = iacc
            self._gram[s] = None
            self._gstate[s] = 0
            self._gdirty = True

    def _retire(self, s: int, status: str, error: Optional[str] = None):
        with self._lock:
            slot = self._slots[s]
            self._slots[s] = None
            self._completed[status] = self._completed.get(status, 0) + 1
        self._active[s] = False
        self._prefills.pop(s, None)
        # shared pages survive under their prefix-cache/other-slot refs
        self._release(s)
        self._reset_slot_state(s)
        req = slot.req
        now = time.perf_counter()
        res = ServeResult(
            status=status, prompt_ids=req.prompt_ids,
            generated_ids=list(slot.generated),
            queue_wait_s=(req.admit_t - req.submit_t
                          if req.admit_t is not None else None),
            ttft_s=(req.first_token_t - req.submit_t
                    if req.first_token_t is not None else None),
            latency_s=now - req.submit_t, error=error,
            trace_id=req.trace_id)
        _metrics.SERVE_REQUESTS.labels(status=status).inc()
        _metrics.SERVE_REQUEST_SECONDS.observe(res.latency_s)
        # always-on ring: one event per request lifecycle end — with
        # tracing off this is the request history a crash dump carries
        _recorder.RECORDER.record(
            "event", "serve.retire", slot=s, status=status,
            generated=len(res.generated_ids),
            **({"error": error or ""} if status == STATUS_ERROR else {}))
        if req._trace is not None:
            for open_span in (req._span_queue, req._span_prefill):
                if open_span is not None:
                    open_span.end(status=status)
            req._span_queue = req._span_prefill = None
            req._trace.event("retire", status=status,
                             generated=len(res.generated_ids))
            req._trace.set("generated_tokens", len(res.generated_ids))
            req._trace.end(status=status)
        req._complete(res)

    def _finish_unstarted(self, req: RequestHandle, status: str,
                          error: Optional[str] = None):
        """Complete a request that never reached (or never finished)
        prefill: no generated tokens — except a preempted-then-expired
        request, which keeps the tokens it generated before preemption
        (partial output is real output)."""
        res = ServeResult(status=status, prompt_ids=req.prompt_ids,
                          generated_ids=list(req._resume or ()),
                          latency_s=time.perf_counter() - req.submit_t,
                          error=error, trace_id=req.trace_id)
        with self._lock:
            self._completed[status] = self._completed.get(status, 0) + 1
        _metrics.SERVE_REQUESTS.labels(status=status).inc()
        _metrics.SERVE_REQUEST_SECONDS.observe(res.latency_s)
        _recorder.RECORDER.record("event", "serve.retire", status=status,
                                  generated=len(res.generated_ids),
                                  admitted=False)
        if req._trace is not None:
            if req._span_queue is not None:
                req._span_queue.end(status=status)
                req._span_queue = None
            req._trace.event("retire", status=status,
                             generated=len(res.generated_ids))
            req._trace.end(status=status)
        req._complete(res)

    # ------------------------------------------------------------ stats
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            queue_depth = len(self._queue)
            in_use = sum(1 for s in self._slots if s is not None)
            completed = dict(self._completed)
        with self._compile_lock:
            buckets = {"prefill": sorted(self._prefill_fns),
                       "decode": sorted(self._step_fns)}
            if self.spec:
                buckets["spec"] = sorted(self._spec_fns)
        out = {
            "running": self._running,
            "draining": self._draining,
            "name": self.name,
            "weight_version": self.weight_version,
            "weight_swaps": self._weight_swaps,
            "lookahead": self._lookahead,
            "multi_token": self.K,
            "speculate": self.spec,
            "slots": self.S,
            "slots_in_use": in_use,
            "max_active": self._max_active,
            "queue_depth": queue_depth,
            "submitted": self._submitted,
            "completed": completed,
            "compiled_buckets": buckets,
            "max_len": self.L,
            "last_warmup_s": self.last_warmup_s,
            "paged": True,    # the one layout; readers: http.py, bench/
            "grammar": self._grammar,
            "tier": self.tier,
            # the engine's KV HBM footprint (loadgen's requests/HBM-GB
            # denominator)
            "kv_bytes": self._kv_bytes,
            # of those, the bytes that every program built so far that
            # writes the pools updates where they lie: the least
            # memory_analysis().alias_size_in_bytes among them. Equal to
            # kv_bytes when every program aliases every pool; 0 where the
            # backend gives no analysis, and before the first program is
            # built
            "pool_bytes_in_place": self._in_place or 0,
            # what that cannot see: copies of an aliased pool into another
            # layout and back, in the first program of each kind among them
            # (count_pool_copies). 0 when those write and read the pools as
            # they lie
            "pool_copies": self._pool_copies,
        }
        if self.spec:
            out["spec"] = {
                "rounds": self._spec_rounds,
                "drafted": self._spec_drafted,
                "accepted": self._spec_accepted,
                "acceptance_rate": round(
                    self._spec_accepted / self._spec_drafted, 4)
                if self._spec_drafted else None,
            }
        # the router's least-loaded signal: worst of slot and page
        # pressure, plus queue backlog (0 = idle, 1 ≈ saturated, > 1 =
        # queueing)
        load = in_use / self.S
        pstats = self._pages.stats()
        out["page_size"] = self.page_size
        out["pages"] = pstats
        out["prefilling"] = len(self._prefills)
        out["preemptions"] = self._preempted
        # heartbeats late by over _PAUSE_S: the interpreter held, or the
        # process not run
        out["host_pauses"] = self._heartbeat.pauses
        out["host_pause_max_s"] = round(self._heartbeat.pause_max_s, 6)
        out["kv_walk_blocks"] = self._kv_walked
        out["kv_walk_blocks_on_lanes"] = self._kv_on_lanes
        out["kv_walk_blocks_in_kernel"] = self._kv_in_kernel
        out["kv_table_blocks"] = self._kv_tabled
        out["sample_rows"] = self._sample_rows
        out["sample_rows_filtered"] = self._sample_filtered
        out["state_bytes"] = self._state_bytes
        out["sparse_blocks_read"] = self._sel_read
        out["sparse_blocks_live"] = self._sel_live
        out["windows_folded"] = pstats["windows_folded"]
        out["pages_folded"] = pstats["pages_folded"]
        out["pages_held"] = self._held
        out["pages_unfolded"] = self._unfolded
        if self._wpages is not None:
            wstats = self._wpages.stats()
            out["window_pages"] = wstats
            out["window_pages_held"] = self._wheld
            out["window_pages_unwindowed"] = self._wdepth
            out["window_pages_recycled"] = wstats["pages_recycled"]
            out["window_walk_blocks"] = self._wwalked
            load = max(load, wstats["pages_in_use"] / wstats["pages"])
        if self._moe is not None:
            here = int(self._moe_tokens.sum())
            out["moe_assignments"] = self._moe_routed
            out["moe_assignments_here"] = here
            # the busiest held expert's share of the assignments here
            out["moe_expert_tokens_max"] = (
                round(float(self._moe_tokens.max()) / here, 4)
                if here else None)
        # bounded prefix-cache advert for the router's affinity
        # scoring: top-N chained-hash roots by refcount (the
        # serve_prefix_advert knob caps N; 0 disables the advert)
        roots = self._pages.prefix_summary(self._prefix_advert)
        out["prefix_summary"] = {"page_size": self.page_size,
                                 "roots": roots}
        _metrics.CACHE_ADVERT_ROOTS.set(len(roots))
        # cache-only pins are reclaimable on demand (the admission
        # gate already treats them as free) — a cache-warm idle
        # replica must NOT advertise a saturated pool to the router
        held = pstats["pages_in_use"] - pstats["pages_cached_only"]
        load = max(load, held / pstats["pages"])
        out["load"] = round(
            load + queue_depth / max(self.max_queue_depth, 1), 4)
        return out
