"""Paged KV-cache page pool: the host-side allocator behind the slot
serving engine (``serving/engine.py:SlotGenerativeModel``, ISSUE 17).

A cache that reserved one worst-case ``[n_slots, S, H, D]`` region per
layer would waste, for every short request, the rows it never reaches.
The pool holds ``n_pages`` fixed-size pages instead (``[n_pages,
page_size, H*D]`` per layer on device) and admits by FREE-PAGE count:
a request whose prompt pads to bucket ``P`` with token budget ``B``
holds ``span = ceil((P + B) / page_size)`` pages, not ``S`` rows — so
the same HBM budget carries more concurrent decode slots
(docs/serving.md "Paged KV cache").

This module is pure host bookkeeping — device K/V bytes never move
through it. Three cooperating structures:

- **Free list** — page ids available for immediate allocation.
  :meth:`PagePool.acquire` takes ``span - shared`` of them (raising
  :class:`PagesExhaustedError` when reclaim cannot cover the request);
  :meth:`PagePool.release` returns a slot's non-shared tail pages.

- **Radix tree over prompt pages** — nodes keyed by the tuple of
  ``page_size`` token ids a FULL prompt page holds (partial trailing
  pages are never shared: the page boundary is the sharing grain).
  Admission walks the tree along the prompt: every node found is a
  physically shared page (refcount++, no allocation, no prefill write —
  the K/V rows for position ``j`` depend only on token ``j``, so the
  resident rows are bit-identical to what this prompt's prefill would
  write). The first divergent page is where copy-on-write happens: the
  request gets a PRIVATE page from the free list and the prefill's
  recompute-write populates it — divergence never touches the shared
  page, so no device copy exists anywhere in the protocol.

- **Evictable prefix cache** — releasing a slot decrements its chain's
  refcounts but keeps refcount-0 nodes RESIDENT (their pages stay out
  of the free list): the next request with the same system prompt
  re-shares them without a prefill write. Under allocation pressure
  refcount-0 leaves are reclaimed LRU-first
  (``paddle_kv_page_evictions_total{cause="capacity"}``);
  :meth:`PagePool.reset` drops the whole cache (``cause="reset"``).

- **Window group** — a model whose layers are of two kinds, some
  attending the whole context and some only the last ``window``
  positions, keeps the two kinds in pools of their own (another device
  array a layer, another page-id space) and leases them apart: the FULL
  group as above (span, radix sharing), the WINDOW group a ring of at
  most ``ceil(window / page_size) + 1`` pages a slot, addressed by TRUE
  position (``ring[(position // page_size) % len(ring)]``). Admission
  takes the pages of the prompt's last ``window`` positions and of what
  the budget will write, up to the ring's length, and is refused when
  EITHER group lacks pages; a long prompt's pages behind its window are
  never taken. When a decoding slot enters a page its ring has no room
  for, :meth:`PagePool.window_advance` first returns the pages that lie
  behind every future query's window
  (``paddle_kv_window_pages_released_total``) and then takes one: a
  take after admission always follows a release, so it cannot fail. The
  window group takes no part in prefix sharing (its rows are written by
  every admission: a prefix-shared admission recomputes the prompt
  anyway).

Thread discipline matches the engine: one dispatcher at a time — no
internal locking.
"""

from __future__ import annotations

import heapq

from typing import Dict, List, Optional, Sequence, Tuple

from paddle_tpu.ops.kv_attention import window_ring
from paddle_tpu.serving import metrics as smetrics


class PagesExhaustedError(RuntimeError):
    """Admission cannot be satisfied: free pages + evictable cached
    pages < the private pages the request needs. The engine translates
    this into a :class:`~paddle_tpu.serving.engine.SlotExhaustedError`
    carrying the occupancy counts (kind='exhausted' over the wire)."""


class _Node:
    """One full prompt page in the radix tree: ``key`` is the tuple of
    page_size token ids it stores, ``page`` the pool page holding their
    K/V rows, ``refs`` how many in-flight slots reference it."""

    __slots__ = ("key", "page", "refs", "children", "parent", "last_use")

    def __init__(self, key, page, parent):
        self.key = key
        self.page = page
        self.refs = 0
        self.children: Dict[tuple, "_Node"] = {}
        self.parent = parent
        self.last_use = 0


class _SlotLease:
    __slots__ = ("pages", "nodes", "tail", "n_shared")

    def __init__(self, pages, nodes, tail, n_shared):
        self.pages = pages        # full span, logical-page order
        self.nodes = nodes        # tree nodes referenced (chain order)
        self.tail = tail          # private non-tree pages
        self.n_shared = n_shared  # leading pages found in the tree


class _WindowLease:
    """A slot's share of the window group: ``ring[e]`` is the page that
    holds logical page ``lp`` (``lp % len(ring) == e``) for ``lo <= lp <
    hi``, -1 elsewhere."""

    __slots__ = ("ring", "lo", "hi")

    def __init__(self, ring, lo, hi):
        self.ring = ring
        self.lo = lo              # lowest logical page still held
        self.hi = hi              # one past the highest page held

    def held(self) -> int:
        return self.hi - self.lo


class PagePool:
    """Free-list page allocator + prompt-prefix radix tree for one
    serving model's paged KV pool. Page ids index the device pools'
    leading axis; the engine turns a lease into the slot's page-table
    row and the prefill's write-row vector. ``window_pages`` pages of a
    second group, for layers that attend the last ``window`` positions
    alone (module docstring, "Window group")."""

    def __init__(self, n_pages: int, page_size: int, model: str = "",
                 window_pages: int = 0, window: int = 0):
        if n_pages < 1 or page_size < 1:
            raise ValueError(f"bad pool geometry: n_pages={n_pages}, "
                             f"page_size={page_size}")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.model = model
        self.window = int(window)
        self.window_pages = int(window_pages)
        self.window_ring = window_ring(window, page_size) \
            if window_pages else 0
        if 0 < self.window_pages < self.window_ring:
            raise ValueError(
                f"bad pool geometry: {window_pages} window pages cannot "
                f"hold one slot's ring of {self.window_ring}")
        self._wfree: List[int] = list(range(self.window_pages))[::-1]
        self._wslots: Dict[int, _WindowLease] = {}
        self._free: List[int] = list(range(self.n_pages))[::-1]
        self._root = _Node(None, -1, None)
        self._slots: Dict[int, _SlotLease] = {}
        self._clock = 0
        self._cached = 0          # refcount-0 nodes resident in the tree
        self._publish()

    # -- accounting -------------------------------------------------------
    def free_count(self) -> int:
        """Pages on the free list (excludes evictable cached pages)."""
        return len(self._free)

    def available_count(self) -> int:
        """Pages an admission could obtain: free + evictable cached."""
        return len(self._free) + self._cached

    def shared_count(self) -> int:
        """Pages referenced by >= 2 in-flight slots (each once) — the
        prefix-sharing witness gauge."""
        return sum(1 for nd in self._iter_nodes() if nd.refs >= 2)

    def cached_count(self) -> int:
        return self._cached

    def page_refs(self, page: int) -> int:
        """Refcount of the tree node holding ``page`` (0 if cached,
        absent if the page is free or privately held) — the witness the
        prefix-sharing tests assert against."""
        for nd in self._iter_nodes():
            if nd.page == page:
                return nd.refs
        raise KeyError(f"page {page} is not in the prefix tree")

    def span_for(self, total_len: int, draft_window: int = 0) -> int:
        """Pages needed to hold ``total_len`` cache positions.

        ``draft_window`` reserves headroom for speculative decoding: a
        draft–verify engine may write up to ``draft_window`` rows past
        the committed frontier inside one dispatch, so an engine that
        drafts a full window right up to its ``max_new`` budget needs
        ``ceil((total_len + draft_window) / page_size)`` pages to avoid
        an off-by-K overflow on the last step. (The in-tree engine caps
        each window at ``remaining - 1`` drafts, which keeps writes
        within ``total_len`` — the headroom is defensive for drafters
        that do not.)"""
        return -(-(int(total_len) + int(draft_window)) // self.page_size)

    def stats(self) -> dict:
        out = {"pages_total": self.n_pages,
               "pages_free": self.free_count(),
               "pages_cached": self._cached,
               "pages_shared": self.shared_count(),
               "slots": len(self._slots)}
        if self.window_pages:
            out.update(window_pages_total=self.window_pages,
                       window_pages_free=len(self._wfree))
        return out

    def window_free_count(self) -> int:
        return len(self._wfree)

    def _iter_nodes(self):
        stack = list(self._root.children.values())
        while stack:
            nd = stack.pop()
            yield nd
            stack.extend(nd.children.values())

    def _publish(self):
        if not self.model:
            return
        # the unlabelled pair counts every group's pages; a pool with a
        # window group publishes each group beside it
        smetrics.KV_PAGES_TOTAL.labels(model=self.model).set(
            self.n_pages + self.window_pages)
        self._publish_free()
        if self.window_pages:
            for group, total in (("full", self.n_pages),
                                 ("window", self.window_pages)):
                smetrics.KV_GROUP_PAGES_TOTAL.labels(
                    model=self.model, group=group).set(total)
        smetrics.KV_PREFIX_SHARED_PAGES.labels(model=self.model).set(
            self.shared_count())

    def _publish_free(self):
        """The free gauges alone (no walk of the tree: a decoding slot
        that recycles a window page calls this between admissions)."""
        smetrics.KV_PAGES_FREE.labels(model=self.model).set(
            self.free_count() + len(self._wfree))
        if self.window_pages:
            for group, free in (("full", self.free_count()),
                                ("window", len(self._wfree))):
                smetrics.KV_GROUP_PAGES_FREE.labels(
                    model=self.model, group=group).set(free)

    # -- eviction ---------------------------------------------------------
    def _evict(self, count: int, cause: str) -> int:
        """Reclaim up to ``count`` pages, the LRU refcount-0 LEAF first
        (a refcount-0 node's whole subtree is refcount-0 — any slot
        holding a child holds the parent — so leaf-first reclaim reaches
        every cached page). ONE walk of the tree whatever ``count``: the
        leaves go into a heap by last use, and a parent joins it when
        its last child goes. (A walk per page made an admission into a
        pool full of cached prompts a 150 ms stall on the chip's host:
        PERF.md, PR 31.)"""
        heap = [(nd.last_use, i, nd)
                for i, nd in enumerate(self._iter_nodes())
                if nd.refs == 0 and not nd.children]
        heapq.heapify(heap)
        tie, done = len(heap), 0
        evictions = smetrics.KV_PAGE_EVICTIONS.labels(
            model=self.model, cause=cause) if self.model else None
        while done < count and heap:
            _use, _i, victim = heapq.heappop(heap)
            parent = victim.parent
            del parent.children[victim.key]
            self._free.append(victim.page)
            self._cached -= 1
            done += 1
            if evictions is not None:
                evictions.inc()
            if parent is not self._root and parent.refs == 0 \
                    and not parent.children:
                tie += 1
                heapq.heappush(heap, (parent.last_use, tie, parent))
        return done

    def _take_pages(self, need: int) -> List[int]:
        short = need - len(self._free)
        if short > 0 and self._evict(short, "capacity") < short:
            raise PagesExhaustedError(
                f"model {self.model!r}: need {need} pages, "
                f"{len(self._free)} free and nothing evictable "
                f"({self.n_pages} total)")
        return [self._free.pop() for _ in range(need)]

    # -- lease lifecycle --------------------------------------------------
    def acquire(self, slot: int, tokens: Sequence[int], span: int,
                total_len: Optional[int] = None
                ) -> Tuple[List[int], int]:
        """Lease ``span`` pages to ``slot`` for a prompt of ``tokens``
        (and, in a pool with a window group, the ring that ``total_len``
        = prompt + budget positions need: refused, and nothing taken,
        when either group lacks pages):
        walk the radix tree along the FULL prompt pages, share every
        node found (refcount++), allocate private pages for the rest,
        and insert the new full prompt pages so later requests share
        them. Returns ``(pages, n_shared)`` — ``pages[p]`` backs
        logical positions ``[p*page_size, (p+1)*page_size)`` of the
        slot; the first ``n_shared * page_size`` positions are already
        resident (the prefill skips their writes)."""
        if slot in self._slots:
            raise ValueError(f"slot {slot} already holds a page lease")
        tokens = [int(t) for t in tokens]
        full = min(len(tokens) // self.page_size, int(span))
        if span < 1:
            raise ValueError(f"span {span} < 1")
        wlo, whi = self._window_span(len(tokens), total_len)
        if whi - wlo > len(self._wfree):
            raise PagesExhaustedError(
                f"model {self.model!r}: admission needs {whi - wlo} "
                f"window pages, {len(self._wfree)} free of "
                f"{self.window_pages}")
        # 1) longest shared prefix of full prompt pages
        chain: List[_Node] = []
        cur = self._root
        for p in range(full):
            key = tuple(tokens[p * self.page_size:
                               (p + 1) * self.page_size])
            child = cur.children.get(key)
            if child is None:
                break
            chain.append(child)
            cur = child
        n_shared = len(chain)
        # 2) PIN the chain, THEN allocate private pages: a refcount-0
        # chain node is an LRU eviction candidate, and _take_pages must
        # never reclaim a page this very admission is about to share —
        # the reclaimed page would come back as a private page of the
        # same lease and the prefill write would clobber the shared
        # prefix K/V. Pinning first also makes available_count() exact
        # (chain pages are no longer evictable), so the pre-check below
        # guarantees _take_pages succeeds without partial evictions.
        need = span - n_shared
        self._clock += 1
        for nd in chain:
            if nd.refs == 0:
                self._cached -= 1     # cache hit: resident page re-shared
            nd.refs += 1
            nd.last_use = self._clock
        try:
            if need > self.available_count():
                raise PagesExhaustedError(
                    f"model {self.model!r}: admission needs {need} "
                    f"private pages ({span}-page span, {n_shared} "
                    f"shared), only {self.free_count()} free + "
                    f"{self._cached} evictable of {self.n_pages}")
            private = self._take_pages(need)
        except PagesExhaustedError:
            for nd in chain:          # unpin: failed admission is a no-op
                nd.refs -= 1
                if nd.refs == 0:
                    self._cached += 1
            raise
        # 3) insert the remaining FULL prompt pages (they hold exactly
        # page_size token-addressed rows once this admission's prefill
        # writes them) — the tail (partial prompt page + generation
        # pages) is private forever
        nodes = list(chain)
        k = 0
        for p in range(n_shared, full):
            key = tuple(tokens[p * self.page_size:
                               (p + 1) * self.page_size])
            nd = _Node(key, private[k], cur)
            nd.refs = 1
            nd.last_use = self._clock
            cur.children[key] = nd
            cur = nd
            nodes.append(nd)
            k += 1
        tail = private[k:]
        pages = [nd.page for nd in nodes] + tail
        self._slots[slot] = _SlotLease(pages, nodes, tail, n_shared)
        if self.window_pages:
            ring = [-1] * self.window_ring
            for lp in range(wlo, whi):
                ring[lp % self.window_ring] = self._wfree.pop()
            self._wslots[slot] = _WindowLease(ring, wlo, whi)
        self._publish()
        return pages, n_shared

    # -- the window group -------------------------------------------------
    def _window_span(self, prompt_len: int, total_len: Optional[int]
                     ) -> Tuple[int, int]:
        """The logical pages [lo, hi) of the window group an admission
        takes: from the page of the first position the FIRST decode step
        still sees (``prompt_len - window + 1``) to the page of the last
        position ever written, at most a ring's length."""
        if not self.window_pages:
            return 0, 0
        if total_len is None:
            raise ValueError("a pool with a window group leases by "
                             "total_len (prompt + budget positions)")
        lo = max(0, int(prompt_len) - self.window + 1) // self.page_size
        hi = (max(int(total_len), int(prompt_len)) - 1) \
            // self.page_size + 1
        return lo, min(hi, lo + self.window_ring)

    def window_lease(self, slot: int) -> Optional[_WindowLease]:
        return self._wslots.get(slot)

    def window_advance(self, slot: int, position: int) -> bool:
        """``slot`` is about to write ``position`` (a TRUE position) and
        attend the ``window`` positions up to it: return to the group
        the pages whose rows all lie behind that window (and so behind
        every later one), then make sure the page of ``position`` is
        held. True when the slot's ring changed. Idempotent: a second
        call for the same position finds nothing to return and the page
        held. A page is only taken where the ring was full, right after
        one was returned, so this cannot run out of pages."""
        lease = self._wslots.get(slot)
        if lease is None:
            return False
        ring, n = lease.ring, self.window_ring
        lp = int(position) // self.page_size
        if lp < lease.hi:
            return False
        dead = max(0, int(position) - self.window + 1) // self.page_size
        released = 0
        while lease.lo < min(dead, lease.hi):
            e = lease.lo % n
            self._wfree.append(ring[e])
            ring[e] = -1
            lease.lo += 1
            released += 1
        lease.lo = max(lease.lo, dead)
        lease.hi = max(lease.hi, lease.lo)
        while lease.hi <= lp:
            if not self._wfree:
                raise PagesExhaustedError(
                    f"model {self.model!r}: no window page for slot "
                    f"{slot} at position {position}")
            ring[lease.hi % n] = self._wfree.pop()
            lease.hi += 1
        if self.model:
            if released:
                smetrics.KV_WINDOW_PAGES_RELEASED.labels(
                    model=self.model).inc(released)
            self._publish_free()
        return True

    def _window_release(self, slot: int):
        lease = self._wslots.pop(slot, None)
        if lease is not None:
            self._wfree.extend(p for p in lease.ring if p >= 0)

    def release(self, slot: int):
        """Return ``slot``'s lease: tail pages go straight to the free
        list; tree pages drop a refcount and STAY RESIDENT at zero (the
        evictable prefix cache — releasing one sharer never frees pages
        another still references, and never frees the cached copy
        either until capacity demands it)."""
        lease = self._slots.pop(slot, None)
        if lease is None:
            return
        self._window_release(slot)
        self._clock += 1
        for nd in reversed(lease.nodes):
            nd.refs -= 1
            if nd.refs == 0:
                nd.last_use = self._clock
                self._cached += 1
        self._free.extend(lease.tail)
        self._publish()

    def abort(self, slot: int):
        """Failed-admission release: the nodes THIS lease inserted hold
        pages its prefill never wrote, so unlike :meth:`release` they
        must not stay resident as prefix cache (a later request with
        the same prompt would share garbage K/V) — they leave the tree
        and their pages go straight back to the free list. Pre-existing
        shared nodes just drop a refcount as usual."""
        lease = self._slots.pop(slot, None)
        if lease is None:
            return
        self._window_release(slot)
        inserted = set(lease.nodes[lease.n_shared:])
        self._clock += 1
        for nd in reversed(lease.nodes):      # deepest-first: children
            nd.refs -= 1                      # drop before parents
            if nd.refs > 0:
                continue
            if nd in inserted and not nd.children:
                del nd.parent.children[nd.key]
                self._free.append(nd.page)
            else:
                nd.last_use = self._clock
                self._cached += 1
        self._free.extend(lease.tail)
        self._publish()

    def lease(self, slot: int) -> Optional[_SlotLease]:
        return self._slots.get(slot)

    def reset(self):
        """Drop every lease AND the prefix cache (engine reset/warmup:
        the device pools are about to be scrubbed or reused, so cached
        pages would alias stale K/V)."""
        self._slots.clear()
        self._wslots.clear()
        self._wfree = list(range(self.window_pages))[::-1]
        n = sum(1 for _ in self._iter_nodes())
        if n and self.model:
            smetrics.KV_PAGE_EVICTIONS.labels(
                model=self.model, cause="reset").inc(n)
        self._root.children.clear()
        self._cached = 0
        self._free = list(range(self.n_pages))[::-1]
        self._publish()
