"""Paged KV-cache pool: fixed-size pages, per-slot block tables, shared
prefixes.

The HBM-side rebuild ROADMAP item 1 asks for (the vLLM argument,
PAPERS.md arXiv:2309.06180 lineage): the contiguous slot pool reserves
``max_len`` KV rows per slot whether a request uses them or not, so HBM —
not compute — caps concurrency. This module keeps the *host-side ledger*
of a pool of fixed-size pages instead:

- **Pages.** The device carries one pooled cache of ``num_pages + 1``
  physical pages per cache_spec entry (``cache_spec_paged``); page
  ``num_pages`` is the *sink* — unleased block-table entries point at it,
  so padded/speculative writes land somewhere harmless and masked reads
  of unleased territory see garbage that contributes exact zeros
  (see models/llama._paged_attention).
- **Block tables.** Each slot owns a ``[max_pages]`` int32 row mapping
  logical page ``i`` (token positions ``[i*page_size, (i+1)*page_size)``)
  to a physical page. Slots lease pages on demand as their decode
  position advances and release them at retire — a request's HBM
  footprint is its *actual* length, which is what buys the >=4x
  concurrency on the same pool bytes.
- **Shared prefixes (copy-on-write).** Completed prefills publish their
  prompt pages into a chained-hash prefix cache (page ``i`` keyed by the
  hash of tokens ``[0, i*page_size + chunk_len)`` — the chain makes a
  match at page ``i`` imply, inductively, a verified match of the whole
  prefix). A new request maps matching pages into its table instead of
  re-prefilling them; pages are refcounted, and any write into a page
  with refs > 1 must first *fork* it (``writable`` names the pages, the
  engine copies them on-device) — first divergent token semantics.
  Hash collisions are detected by token comparison and simply stop the
  match walk (fall back to prefilling from there).
- **Eviction & preemption.** Allocation failure first evicts LRU prefix
  entries (cache-only refs free their pages); if the pool is still
  exhausted the *engine* preempts a slot (release + requeue) — the
  stateless per-request ``fold_in(seed, counter)`` sampling streams make
  a preempted request exactly resumable by re-prefilling
  ``prompt + generated`` (see engine._preempt).

- **Folding.** A model whose cache folds (``cache_fold``: EVA attention
  keeps a finished window of positions as one page of chunk summaries)
  hands the pool the arithmetic of its table (``layout``: an
  ``ops.eva_attention.FoldedPages`` where every other model's is
  :class:`FlatPages`). A slot's need is then counted in the
  pages it *holds*, not in its positions: ``max_pages`` is the widest table
  a ``max_len`` request ever has, ``lease`` covers the dispatch that brings
  the slot to a depth (the page a window's summaries go to included, where
  the depth ends a window), and :meth:`PagePool.fold` shrinks the table
  while the slot lives: the window's entries are replaced by that one page
  and their pages go back to the pool. Sharing is position by position
  (a prefix entry is keyed by the tokens of one page), so a folding pool
  has no prefix cache.

- **A windowed kind.** A model that mixes layers which read every position
  with layers which read only the last ``window`` (``cache_window``) has
  pools of two *kinds*, each with page ids and a ledger of its own: the full
  layers' :class:`PagePool` as above, and a second one for the windowed
  layers whose ``layout`` is a :class:`WindowedPages`. Its table is indexed
  by logical page like any other (the paged read and its writes need no
  other arithmetic, and the read's blocks stay aligned at column 0: a ring
  indexed modulo its length would need both changed, for a table of a few
  hundred int32 a slot), but a slot's pages are *dereferenced at the front
  and leased at the back*: before the dispatch whose first new position is
  ``pos``, :meth:`PagePool.slide` gives back every page that lies wholly
  behind that position's window (its entry points at the sink again), so a
  request at any depth holds at most ``layout.held_bound`` pages of this
  kind. Sharing is refused as for a folding pool: a page that one request
  has slid past is another's to overwrite.

Pure host bookkeeping (numpy + stdlib): device page copies/gathers live
in the models' paged attention and the engine's executables.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as onp

from .. import metrics as _metrics
from ..analysis import guards as _guards
from ..base import MXNetError

__all__ = ["PagePool", "FlatPages", "WindowedPages", "OutOfPages",
           "pages_for", "prefix_key"]


class OutOfPages(MXNetError):
    """The page pool cannot satisfy a lease even after evicting every
    reclaimable prefix-cache entry (the engine's preemption trigger)."""


def pages_for(tokens: int, page_size: int) -> int:
    """Pages needed to hold ``tokens`` KV rows (ceil division)."""
    return -(-int(tokens) // int(page_size))


class FlatPages:
    """The arithmetic of a table that never shrinks: an entry for every
    ``page_size`` positions, each position in its own column. What a pool
    counts with unless the model's cache folds (module docstring)."""
    window_pages = 0

    def __init__(self, page_size: int):
        self.page_size = int(page_size)

    def column(self, pos):
        return pos

    def entries(self, depth: int) -> int:
        return pages_for(depth, self.page_size)

    peak = entries


class WindowedPages(FlatPages):
    """The arithmetic of a windowed kind's table (module docstring): an entry
    for every ``page_size`` positions as :class:`FlatPages` has, of which a
    slot keeps only those its next dispatch can still read. A dispatch of at
    most ``chunk`` new positions from ``pos`` reads the columns ``pos -
    window + 1 .. pos + chunk - 1``: ``held_bound`` pages at the most."""

    def __init__(self, page_size: int, window: int, chunk: int):
        super().__init__(page_size)
        self.window = int(window)
        self.held_bound = pages_for(self.window + int(chunk),
                                    self.page_size) + 1

    def first_live(self, pos: int) -> int:
        """The first table entry that a dispatch whose first new position is
        ``pos`` still reads."""
        return max(int(pos) - self.window + 1, 0) // self.page_size

    def need(self, tokens: int) -> int:
        """Pages a request of ``tokens`` positions holds at the most."""
        return min(self.entries(tokens), self.held_bound)


def prefix_key(tokens: Sequence[int]) -> int:
    """The chain key of a token prefix — :meth:`PagePool._hash` exposed
    for cross-process use: the router hashes request prompts with the
    SAME discipline the replicas advertise their cached roots under
    (prefix-affinity scoring), and migration receipts recompute it to
    verify shipped pages."""
    return PagePool._hash(tuple(int(t) for t in tokens))


@dataclasses.dataclass
class _PrefixEntry:
    """One cached page of a published prompt prefix.

    ``prefix_len`` is the total token length this entry's chain covers
    (``page_index * page_size + len(chunk)``); ``chunk`` holds the tokens
    stored in this page slice for collision verification."""
    page: int
    page_index: int
    chunk: Tuple[int, ...]
    prefix_len: int


class PagePool:
    """Host-side ledger for a fixed-size-page KV pool.

    Parameters
    ----------
    num_pages : leasable physical pages (the device pools carry one extra
        sink page at index ``num_pages``)
    page_size : tokens per page
    max_len : per-request capacity in positions; must be a page multiple:
        the read walks the block table in blocks of whole pages
        (models/llama._paged_attention)
    slots : block-table rows (the engine's ``max_batch_size``)
    prefix_cache : publish/match shared prompt prefixes
    layout : the table's arithmetic (``column``, ``entries``, ``peak``,
        ``window_pages``): :class:`FlatPages` unless the model's cache folds
        (module docstring). ``max_pages`` (the table's width, and the least
        ``num_pages`` may be) is the most pages a ``max_len`` request ever
        holds: folded, far fewer than ``max_len / page_size``
    """

    def __init__(self, num_pages: int, page_size: int, max_len: int,
                 slots: int, prefix_cache: bool = True, layout=None):
        if page_size < 1:
            raise MXNetError("page_size must be >= 1")
        if num_pages < 1:
            raise MXNetError("num_pages must be >= 1")
        if max_len % page_size:
            raise MXNetError(
                f"max_len ({max_len}) must be a multiple of page_size "
                f"({page_size}): the paged read walks whole pages")
        self.layout = layout or FlatPages(page_size)
        if self.layout.window_pages and prefix_cache:
            raise MXNetError(
                "a folding pool shares no prefix: a cached page is keyed by "
                "the tokens of one page, and a folded page stands for a "
                "whole window of them; pass prefix_cache=False")
        windowed = isinstance(self.layout, WindowedPages)
        if windowed and prefix_cache:
            raise MXNetError(
                "a windowed pool shares no prefix: the pages behind a "
                "request's window are given back while it lives, so no "
                "request keeps a prefix's pages; pass prefix_cache=False")
        self.page_size = int(page_size)
        self.max_pages = self.layout.peak(max_len)
        # the most pages one request ever holds: the table's width, unless
        # the kind is windowed
        most = self.layout.need(max_len) if windowed else self.max_pages
        if num_pages < most:
            raise MXNetError(
                f"page pool ({num_pages} pages x {page_size}) cannot hold "
                f"even one max_len ({max_len}) request, which holds up to "
                f"{most} pages")
        self.num_pages = int(num_pages)
        self.slots = int(slots)
        self.sink = self.num_pages          # physical sink page index
        self._ref = onp.zeros(self.num_pages, onp.int32)
        # free stack: low indices leased first (stable tests/debug dumps)
        self._free: List[int] = list(range(self.num_pages - 1, -1, -1))
        self._tables = onp.full((self.slots, self.max_pages), self.sink,
                                onp.int32)
        self._leased = onp.zeros(self.slots, onp.int32)   # entries per slot
        # a windowed kind: entries before this one were given back (slide)
        self._front = onp.zeros(self.slots, onp.int32)
        self.prefix_cache_enabled = bool(prefix_cache)
        # ledger mutations happen on the engine thread, but stats() (the
        # /healthz load signal) is read from HTTP handler threads — the
        # lock keeps the prefix-dict iteration safe against concurrent
        # insert/evict/LRU-refresh
        self._lock = _guards.make_lock("serve.PagePool._lock")
        # LRU: key -> list of entries (collision bucket)
        self._prefix: "OrderedDict[int, List[_PrefixEntry]]" = OrderedDict()
        # counters surfaced via stats() and the mxnet_serve_page_* family
        self.leases = 0
        self.frees = 0
        self.cow_forks = 0
        self.windows_folded = 0
        self.pages_folded = 0
        self.pages_recycled = 0
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_saved = 0
        self.prefix_collisions = 0
        self.prefix_evictions = 0
        _metrics.SERVE_PAGE_POOL.set(self.num_pages)

    # ------------------------------------------------------------ accounting
    def free_pages(self) -> int:
        return len(self._free)

    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    def cached_pages(self) -> int:
        """Pages held ONLY by the prefix cache (reclaimable)."""
        with self._lock:
            return self._cache_only_pages()

    def _cache_only_pages(self) -> int:
        pages = {e.page for bucket in self._prefix.values() for e in bucket}
        return sum(1 for p in pages if self._ref[p] == 1)

    def table(self, slot: int) -> onp.ndarray:
        """The slot's block-table row (a live view — snapshot before
        handing it to a dispatch)."""
        return self._tables[slot]

    def check_consistent(self):
        """Test hook: refcounts must equal table references + cache
        references, and the free list must hold exactly the zero-ref
        pages."""
        with self._lock:
            self._check_consistent_locked()

    def _check_consistent_locked(self):
        ref = onp.zeros(self.num_pages, onp.int64)
        for s in range(self.slots):
            for p in self._tables[s]:
                if p != self.sink:
                    ref[p] += 1
        seen = set()
        for bucket in self._prefix.values():
            for e in bucket:
                # one cache ref per entry (chained entries each pin their
                # own page exactly once)
                assert e.page not in seen, "duplicate cache entry page"
                seen.add(e.page)
                ref[e.page] += 1
        assert (ref == self._ref).all(), \
            f"refcount drift: {ref.tolist()} vs {self._ref.tolist()}"
        free = {p for p in range(self.num_pages) if self._ref[p] == 0}
        assert free == set(self._free), "free list drift"

    # ------------------------------------------------------------ allocation
    def _alloc(self, n: int) -> List[int]:
        """Pop ``n`` free pages, evicting LRU prefix entries as needed."""
        while len(self._free) < n and self._evict_one():
            pass
        if len(self._free) < n:
            raise OutOfPages(
                f"page pool exhausted: need {n}, "
                f"{len(self._free)} free of {self.num_pages} "
                f"({self.pages_in_use()} leased)")
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._ref[p] = 1
        self.leases += n
        _metrics.SERVE_PAGE_LEASES.inc(n)
        self._observe()
        return out

    def _decref(self, page: int):
        if page == self.sink:
            return
        self._ref[page] -= 1
        assert self._ref[page] >= 0, f"page {page} over-freed"
        if self._ref[page] == 0:
            self._free.append(int(page))
            self.frees += 1
        self._observe()

    def _observe(self):
        _metrics.SERVE_PAGE_IN_USE.set(self.pages_in_use())

    # ------------------------------------------------------------ leasing
    def lease(self, slot: int, tokens: int) -> int:
        """Grow ``slot``'s table for the dispatch that brings it to
        ``tokens`` positions (``layout.entries``: a page for every
        ``page_size`` of them or, folded, what the model's arithmetic says,
        the page a window's summaries go to included). Returns the
        number of pages newly leased; raises :class:`OutOfPages` (after
        evicting reclaimable prefix entries) when the pool is exhausted —
        the table is left unchanged in that case (all-or-nothing)."""
        need = self.layout.entries(tokens)
        if need > self.max_pages:
            raise MXNetError(
                f"request needs {need} pages but max_len allows only "
                f"{self.max_pages}")
        with self._lock:
            have = int(self._leased[slot])
            if need <= have:
                return 0
            fresh = self._alloc(need - have)
            self._tables[slot, have:need] = fresh
            self._leased[slot] = need
            return len(fresh)

    def fold(self, slot: int) -> int:
        """Shrink a leased table whose last window is finished: the window's
        ``layout.window_pages`` entries are replaced by the one entry behind
        them (the page the dispatch wrote their summaries to, which
        ``lease`` brought), the replaced pages are dereferenced and the
        table ends there. The pool allocates nothing here, so it cannot run
        out: the call either finds a window's pages and one more leased or
        raises and leaves the table as it was. Returns the pages that went
        back to the free list."""
        count = self.layout.window_pages
        with self._lock:
            start = int(self._leased[slot]) - count - 1
            if not count or start < 0:
                raise MXNetError(
                    f"fold needs a window's {count} entries and the page "
                    f"for their summaries; slot {slot} has "
                    f"{int(self._leased[slot])} leased")
            row = self._tables[slot]
            old = [int(p) for p in row[start:start + count]]
            row[start] = row[start + count]
            row[start + 1:] = self.sink
            self._leased[slot] = start + 1
            free0 = len(self._free)
            for p in old:
                self._decref(p)
            self.windows_folded += 1
            self.pages_folded += count
            _metrics.SERVE_PAGE_FOLDS.inc(count)
            return len(self._free) - free0

    def slide(self, slot: int, pos: int) -> int:
        """A windowed kind, before the dispatch whose first new position is
        ``pos``: dereference every entry of the slot's table that lies
        wholly behind that position's window (``layout.first_live``) and
        point it at the sink. The pool allocates nothing here. Returns the
        pages that went back to the free list."""
        with self._lock:
            lo = int(self._front[slot])
            hi = min(self.layout.first_live(pos), int(self._leased[slot]))
            if hi <= lo:
                return 0
            row = self._tables[slot]
            free0 = len(self._free)
            for i in range(lo, hi):
                self._decref(int(row[i]))
            row[lo:hi] = self.sink
            self._front[slot] = hi
            n = len(self._free) - free0
            self.pages_recycled += n
            _metrics.SERVE_WINDOW_PAGES_RECYCLED.inc(n)
            return n

    def held(self, slot: int) -> int:
        """Table entries the slot holds now (those slid past are not)."""
        return int(self._leased[slot]) - int(self._front[slot])

    def release(self, slot: int):
        """Return every page the slot references (shared pages survive
        under their remaining refs)."""
        with self._lock:
            self._release_locked(slot)

    def _release_locked(self, slot: int):
        for i in range(int(self._leased[slot])):
            self._decref(int(self._tables[slot, i]))
        self._tables[slot, :] = self.sink
        self._leased[slot] = 0
        self._front[slot] = 0

    def release_all(self):
        with self._lock:
            for s in range(self.slots):
                self._release_locked(s)

    # ------------------------------------------------------------ copy-on-write
    def writable(self, slot: int, start: int, end: int
                 ) -> List[Tuple[int, int]]:
        """Pages the slot must fork before writing token positions
        ``[start, end)``: every mapped page in that range with refs > 1.
        Returns [(table_index, physical_page)]."""
        out = []
        lo = start // self.page_size
        hi = pages_for(end, self.page_size)
        with self._lock:
            for i in range(lo, min(hi, int(self._leased[slot]))):
                p = int(self._tables[slot, i])
                if p != self.sink and self._ref[p] > 1:
                    out.append((i, p))
        return out

    def fork(self, slot: int, table_index: int) -> Tuple[int, int]:
        """Copy-on-write bookkeeping for one shared page: lease a fresh
        page, point the slot's table at it, drop the shared ref. Returns
        (src_page, dst_page) — the engine performs the device copy."""
        with self._lock:
            src = int(self._tables[slot, table_index])
            dst = self._alloc(1)[0]
            self._tables[slot, table_index] = dst
            self._decref(src)
            self.cow_forks += 1
        _metrics.SERVE_PAGE_COW.inc()
        return src, dst

    # ------------------------------------------------------------ prefix cache
    @staticmethod
    def _hash(tokens: Tuple[int, ...]) -> int:
        """Chain key for a token prefix. sha1 over the raw int32 bytes —
        stable across processes (replica routers may compare hit rates)
        and cheap at prompt scale. Tests monkeypatch this to force
        collisions."""
        data = onp.asarray(tokens, onp.int32).tobytes()
        return int.from_bytes(hashlib.sha1(data).digest()[:8], "little")

    def match_prefix(self, tokens: Sequence[int], count: bool = True
                     ) -> Tuple[List[int], int]:
        """Longest cached prefix of ``tokens``: ([physical pages],
        matched_len). The match is capped at ``len(tokens) - 1`` so at
        least one token always goes through prefill (token0's logits must
        be computed). Collisions (key match, token mismatch) stop the
        walk. Does NOT take refs — ``map_prefix`` does.
        ``count=False`` (migration-export probes) leaves the hit/miss
        accounting untouched — those counters mean ADMISSIONS."""
        if not self.prefix_cache_enabled:
            return [], 0
        toks = tuple(int(t) for t in tokens)
        cap = len(toks) - 1
        pages: List[int] = []
        matched = 0
        i = 0
        with self._lock:
            while i * self.page_size < cap:
                best: Optional[_PrefixEntry] = None
                # longest extension first (the full page, then shorter
                # partial tails), capped so at least one token stays
                # unprefilled
                for ln in range(min(cap - i * self.page_size,
                                    self.page_size), 0, -1):
                    ent = self._lookup(toks, i * self.page_size + ln)
                    if ent is not None:
                        best = ent
                        break
                if best is None:
                    break
                pages.append(best.page)
                matched = best.prefix_len
                if len(best.chunk) < self.page_size:
                    break                  # partial tail page ends the walk
                i += 1
        if not count:
            return pages, matched
        if matched:
            self.prefix_hits += 1
            self.prefix_tokens_saved += matched
            _metrics.SERVE_PREFIX_HITS.inc()
            _metrics.SERVE_PREFIX_TOKENS_SAVED.inc(matched)
        else:
            self.prefix_misses += 1
            _metrics.SERVE_PREFIX_MISSES.inc()
        return pages, matched

    def _lookup(self, toks: Tuple[int, ...], length: int
                ) -> Optional[_PrefixEntry]:
        if length > len(toks):
            return None
        key = self._hash(toks[:length])
        bucket = self._prefix.get(key)
        if bucket is None:
            return None
        page_index = (length - 1) // self.page_size
        lo = page_index * self.page_size
        for ent in bucket:
            if ent.prefix_len == length and ent.chunk == toks[lo:length]:
                self._prefix.move_to_end(key)          # LRU refresh
                return ent
        # key present but tokens differ: a genuine hash collision — fall
        # back to prefilling this span rather than serving someone else's
        # KV rows
        self.prefix_collisions += 1
        _metrics.SERVE_PREFIX_COLLISIONS.inc()
        return None

    def map_prefix(self, slot: int, pages: Sequence[int], matched: int):
        """Point the slot's table at the matched pages (taking one ref
        each). The caller prefills from ``matched`` onward; a partial
        tail page will fork on its first write (``writable``)."""
        with self._lock:
            for i, p in enumerate(pages):
                self._tables[slot, i] = p
                self._ref[p] += 1
            self._leased[slot] = len(pages)
            self._observe()

    def insert_prefix(self, slot: int, tokens: Sequence[int]):
        """Publish the slot's prompt pages into the prefix cache: one
        chained entry per page (full pages plus the partial tail).
        Entries already present (same chain key + tokens) are skipped —
        republishing a popular prefix must not duplicate pages."""
        if not self.prefix_cache_enabled:
            return
        toks = tuple(int(t) for t in tokens)
        npages = pages_for(len(toks), self.page_size)
        with self._lock:
            for i in range(npages):
                length = min((i + 1) * self.page_size, len(toks))
                if self._lookup(toks, length) is not None:
                    continue
                page = int(self._tables[slot, i])
                if page == self.sink:
                    break
                chunk = toks[i * self.page_size:length]
                ent = _PrefixEntry(page=page, page_index=i, chunk=chunk,
                                   prefix_len=length)
                self._prefix.setdefault(self._hash(toks[:length]), []) \
                    .append(ent)
                self._ref[page] += 1
            self._observe()

    def prefix_summary(self, top_n: int) -> List[List[int]]:
        """Bounded advert of the cache's hottest roots for the router's
        prefix-affinity scoring: ``[[chain_key, prefix_len, refs], ...]``,
        the top ``top_n`` entries ranked by (page refcount, prefix
        length). A router holding a prompt checks ``prefix_key(
        prompt[:prefix_len]) == chain_key`` — a match implies (up to the
        hash) this replica maps those ``prefix_len`` tokens without
        re-prefilling them. ``top_n <= 0`` disables the advert (an empty
        list); the payload stays O(top_n) regardless of pool size."""
        if top_n <= 0 or not self.prefix_cache_enabled:
            return []
        with self._lock:
            roots = [[int(key), int(ent.prefix_len),
                      int(self._ref[ent.page])]
                     for key, bucket in self._prefix.items()
                     for ent in bucket]
        roots.sort(key=lambda r: (-r[2], -r[1], r[0]))
        return roots[:int(top_n)]

    def adopt_prefix(self, tokens: Sequence[int],
                     lengths: Sequence[int]) -> List[Tuple[int, int]]:
        """Migration import: allocate and publish prefix-cache entries
        for the chain positions ``lengths`` of ``tokens`` (each a prefix
        length ending a page chunk, ascending). Already-cached positions
        are skipped — the dup contract of :meth:`insert_prefix`. Returns
        ``[(prefix_len, page)]`` for the freshly adopted entries; the
        engine writes the shipped KV payload into each page. Allocation
        is all-or-nothing (:class:`OutOfPages` leaves the cache
        unchanged). The LRU may, in principle, evict earlier links of
        the same chain to make room — the match walk then stops at the
        hole and the tail re-prefills, which is safe, just slower."""
        toks = tuple(int(t) for t in tokens)
        out: List[Tuple[int, int]] = []
        if not self.prefix_cache_enabled:
            return out
        with self._lock:
            fresh = [int(ln) for ln in lengths
                     if 0 < int(ln) <= len(toks)
                     and self._lookup(toks, int(ln)) is None]
            pages = self._alloc(len(fresh))
            for ln, page in zip(fresh, pages):
                lo = ((ln - 1) // self.page_size) * self.page_size
                ent = _PrefixEntry(page=page,
                                   page_index=(ln - 1) // self.page_size,
                                   chunk=toks[lo:ln], prefix_len=ln)
                self._prefix.setdefault(self._hash(toks[:ln]), []) \
                    .append(ent)
                out.append((ln, page))
            self._observe()
        return out

    def _evict_one(self) -> bool:
        """Drop the least-recently-used prefix entry; True if anything was
        evicted. Freeing is a side effect of the decref (cache-only pages
        return to the free list; pages still mapped by slots just lose
        their cache pin)."""
        if not self._prefix:
            return False
        key, bucket = next(iter(self._prefix.items()))
        ent = bucket.pop(0)
        if not bucket:
            del self._prefix[key]
        self._decref(ent.page)
        self.prefix_evictions += 1
        return True

    def clear_prefix_cache(self):
        with self._lock:
            while self._evict_one():
                pass

    # ------------------------------------------------------------ stats
    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "page_size": self.page_size,
                "pages": self.num_pages,
                "pages_in_use": self.pages_in_use(),
                "pages_free": self.free_pages(),
                "pages_cached_only": self._cache_only_pages(),
                "leases": self.leases,
                "cow_forks": self.cow_forks,
                "windows_folded": self.windows_folded,
                "pages_folded": self.pages_folded,
                "pages_recycled": self.pages_recycled,
                "prefix_entries": sum(len(b)
                                      for b in self._prefix.values()),
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses,
                "prefix_tokens_saved": self.prefix_tokens_saved,
                "prefix_collisions": self.prefix_collisions,
                "prefix_evictions": self.prefix_evictions,
            }
