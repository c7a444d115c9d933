"""Host-side page allocator for the paged KV cache (infer/continuous.py
``cache_mode="paged"``; device op: ops/paged_attention.py).

The device holds one pool of KV pages per layer — ``(L, n_pages, K,
page_size, D)``, kv-heads before page slots (ops/paged_attention.py's
Mosaic trailing-dim requirement) — and per-slot page tables map logical
block index -> physical page.
This module is the host bookkeeping around that pool:

- **Free-list allocation** with refcounts: a page may back several slots'
  tables at once (shared prefix blocks).
- **Content-addressed dedup**: every FULL page of a prompt is published
  under the key ``(parent_physical_page_id, exact_tokens_in_page)``; a
  later prompt whose leading blocks walk to published pages reuses them
  (refcount bump, no prefill) — vLLM-style automatic prefix caching, no
  ``register_prefix`` call required. The key chains through the *physical*
  parent page id and compares the block's actual tokens, so equal keys
  mean equal full prefixes by construction — no reliance on hash
  collision resistance (a colliding ``hash()`` key would silently serve
  another prompt's KV). Only full, immutable pages are ever shared: a
  slot's partial tail page and its decode pages are private, so there is
  no copy-on-write fault path — sharing is read-only by construction.
- **LRU eviction**: published pages whose only reference is the hash cache
  are reclaimable; allocation pressure evicts them oldest-first.

Page 0 is a reserved sentinel: dead slots' table tails point at it, the
kernel's out-of-range page fetches clamp to it, and the per-tick tail
flush aims its invalid rows at it — so live data can never collide with a
stale table entry.

The allocator is plain Python on the host — admission policy is not a TPU
problem (same stance as the continuous engine's scheduler).
"""

from __future__ import annotations

from collections import OrderedDict, deque

import numpy as np

__all__ = ["EvictedPage", "PageAllocator", "WindowedAllocator", "block_keys"]

PageKey = tuple[int, tuple[int, ...]]

# One page leaving the content cache, as ``on_evict`` reports it: the
# physical id being reclaimed, the chain ROOT (<= 0 adapter namespace),
# and the exact token blocks from the root up to and including this page.
# The blocks — not the physical key — are what survive the tier boundary:
# host_tier.py re-interns them under never-recycled node ids, so a spilled
# entry can never verify against a recycled physical id's new content
# (ISSUE 13).
EvictedPage = tuple[int, int, tuple[tuple[int, ...], ...]]


def block_keys(tokens: list[int], page_size: int, parents: list[int]) -> list[PageKey]:
    """Content keys for the FULL pages of ``tokens``: page i's key is
    ``(physical id of page i-1, page i's exact tokens)`` (parent 0 = the
    sentinel for the first page). Equal keys mean equal full prefixes by
    induction over verified parents — no hash-collision exposure."""
    out: list[PageKey] = []
    for i, start in enumerate(range(0, len(tokens) - page_size + 1, page_size)):
        parent = parents[i - 1] if i > 0 else 0
        out.append((parent, tuple(tokens[start:start + page_size])))
    return out


class PageAllocator:
    """Refcounted page pool bookkeeping with content-hash reuse."""

    def __init__(self, n_pages: int, on_evict=None, group_payload=None):
        if n_pages < 2:
            raise ValueError(f"need >= 2 pages (page 0 is reserved), got {n_pages}")
        self.n_pages = n_pages
        # LRU reclaims of published (cache-only) pages. ``on_evict`` is an
        # optional callback fired once per reclaim with the full evicted
        # GROUP — the claimed page plus every cascaded descendant, parent
        # first, each as an :data:`EvictedPage` — BEFORE the pages are
        # handed back, so the engine can both count the eviction
        # (``prefix_cache_evictions``, ISSUE 8) and capture the KV for the
        # host-RAM tier spill (ISSUE 13) while the content is still
        # addressable. ``group_payload`` (zero-arg predicate, default
        # always-True) gates that collection: a tier-less, handoff-less
        # engine consumes only the eviction COUNT, and walking chains /
        # materializing block tuples inside ``alloc`` on the admission
        # path would be pure waste there — the callback then receives an
        # empty tuple.
        self.evictions = 0
        self._on_evict = on_evict
        self._group_payload = group_payload
        self._free: deque[int] = deque(range(1, n_pages))
        self._ref = [0] * n_pages
        self._key_to_page: dict[PageKey, int] = {}
        self._page_key: dict[int, PageKey] = {}
        # parent physical page -> keys of published children chained to it.
        # Needed so evicting a parent CASCADES: a child key (parent_pid,
        # tokens) left behind after parent_pid is recycled and republished
        # with different content would match a later prompt and serve KV
        # computed under the OLD prefix — silent cross-request corruption.
        self._children: dict[int, set[PageKey]] = {}
        # Insertion-ordered: oldest published key evicts first.
        self._lru: OrderedDict[PageKey, None] = OrderedDict()
        # Incrementally-maintained count of published pages whose only
        # reference is the content cache (ref == 1). The gateway's
        # freshness window polls every replica's /stats AND /health each
        # interval, and the old O(published-pages) scan ran on every poll —
        # at fleet scale that is a per-second full-cache walk (ISSUE 13
        # satellite). Updated at every ref/publish transition; pinned
        # equal to the scan by test_kvtier's equivalence drill.
        self._evictable = 0

    # -- capacity ------------------------------------------------------------

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_evictable(self) -> int:
        """Published pages reclaimable right now (cache-only reference).
        O(1): an incrementally-updated counter, not a scan — /stats and
        /health poll this from HTTP threads every gateway interval."""
        return self._evictable

    def scan_evictable(self) -> int:
        """The O(published-pages) ground truth ``n_evictable`` used to
        recompute per call — kept as the equivalence-test oracle."""
        # list() snapshots atomically under the GIL: callers may read this
        # from HTTP threads while the driver thread publishes/evicts.
        return sum(
            1 for k, p in list(self._key_to_page.items()) if self._ref[p] == 1
        )

    # -- alloc / free --------------------------------------------------------

    def alloc(self, n: int) -> list[int]:
        """Allocate ``n`` private pages (ref 1 each), evicting LRU published
        pages if the free list runs short. Raises when truly out."""
        out: list[int] = []
        while len(out) < n:
            if self._free:
                pid = self._free.popleft()
            else:
                pid = self._evict_one()
                if pid is None:
                    # Roll back so a failed multi-page request leaks nothing.
                    for p in out:
                        self.release(p)
                    raise MemoryError(
                        f"page pool exhausted ({self.n_pages} pages, 0 evictable)"
                    )
            self._ref[pid] = 1
            out.append(pid)
        return out

    def _evict_one(self) -> int | None:
        for key in self._lru:
            pid = self._key_to_page[key]
            if self._ref[pid] == 1:  # only the content cache holds it
                # Collect the whole group (claimed page + cascaded
                # descendants, parent first) BEFORE unpublishing: the
                # chain walk needs the maps intact, and the host-tier
                # spill needs every page the reclaim is about to make
                # unmatchable, not just the one the allocator claims.
                group = ()
                if self._on_evict is not None and (
                    self._group_payload is None or self._group_payload()
                ):
                    group = self._collect_group(key, pid)
                self._unpublish(key, pid, claimed=True)
                self.evictions += 1
                if self._on_evict is not None:
                    self._on_evict(group)
                return pid
        return None

    def _chain_blocks(self, pid: int) -> tuple[int, tuple[tuple[int, ...], ...]]:
        """``(root, token blocks root..pid)`` for a PUBLISHED page — walks
        parent keys up. Every published page's ancestors are published (the
        unpublish cascade guarantees it), so the walk always reaches a
        non-positive root."""
        blocks: list[tuple[int, ...]] = []
        cur = pid
        while cur > 0:
            key = self._page_key[cur]
            blocks.append(key[1])
            cur = key[0]
        return cur, tuple(reversed(blocks))

    def _collect_group(
        self, key: PageKey, pid: int,
        root: int | None = None,
        blocks: tuple[tuple[int, ...], ...] | None = None,
    ) -> list[EvictedPage]:
        """Claimed page + cascaded descendants, parent first. The chain
        walk runs ONCE for the head; descendants extend the parent's
        blocks incrementally (token tuples shared by reference) — a
        per-member walk would make a deep cascade O(depth^2) of tuple
        materialization inside alloc() on the admission path."""
        if blocks is None:
            root, blocks = self._chain_blocks(pid)
        out: list[EvictedPage] = [(pid, root, blocks)]
        for child_key in list(self._children.get(pid, ())):
            child_pid = self._key_to_page.get(child_key)
            if child_pid is not None:
                out.extend(self._collect_group(
                    child_key, child_pid, root, blocks + (child_key[1],)
                ))
        return out

    def _unpublish(self, key: PageKey, pid: int, *, claimed: bool) -> None:
        """Remove a published key (and cascade through descendants).

        ``claimed=True`` means the caller (eviction inside ``alloc``) takes
        ownership of ``pid`` directly — it must NOT also land on the free
        list. Cascaded descendants are never claimed: dropping the cache's
        reference frees them when nothing else holds them (in-flight users
        keep their refcounts; only matchability and the cache ref go)."""
        if self._ref[pid] == 1:
            # Leaving the published set while cache-only: no longer counted
            # evictable (release() below won't see it published anymore).
            self._evictable -= 1
        del self._key_to_page[key]
        del self._page_key[pid]
        self._lru.pop(key, None)
        parent_kids = self._children.get(key[0])
        if parent_kids is not None:
            parent_kids.discard(key)
            if not parent_kids:
                del self._children[key[0]]
        # Cascade: children's keys chain through THIS physical id; once it
        # can be recycled, those keys would verify against the wrong
        # content.
        for child_key in list(self._children.pop(pid, ())):
            child_pid = self._key_to_page.get(child_key)
            if child_pid is not None:
                self._unpublish(child_key, child_pid, claimed=False)
        if claimed:
            self._ref[pid] -= 1  # the cache's reference passes to the caller
        else:
            self.release(pid)  # the cache's own reference

    def purge_root(self, root: int) -> int:
        """Unpublish every chain published under content root ``root``
        (non-positive adapter namespace, see ``publish_chain``) — the
        adapter-evict seam (ISSUE 16): a freed pool row's published pages
        would otherwise prefix-match a future adapter installed into the
        same row and serve KV computed under the OLD weights. In-flight
        users keep their refcounts (only matchability and the cache ref
        go — the registry drains the row before calling this anyway).
        Returns the number of first-level chains purged."""
        purged = 0
        for key in list(self._children.get(root, ())):
            pid = self._key_to_page.get(key)
            if pid is not None:
                self._unpublish(key, pid, claimed=False)
                purged += 1
        return purged

    def retain(self, pid: int) -> None:
        if self._ref[pid] == 1 and pid in self._page_key:
            self._evictable -= 1  # published cache-only page gains a user
        self._ref[pid] += 1

    def release(self, pid: int) -> None:
        if pid == 0:
            return
        self._ref[pid] -= 1
        if self._ref[pid] < 0:
            raise AssertionError(f"double release of page {pid}")
        if self._ref[pid] == 1 and pid in self._page_key:
            self._evictable += 1  # published page dropped to cache-only
        if self._ref[pid] == 0:
            self._free.append(pid)

    # whether ``hold`` does anything (the engine skips its walk over the
    # slots, every tick, where it does not)
    holds_spans = False

    def hold(self, pages: list[int], span: tuple[int, int],
             start: int | None, end: int = 0) -> tuple[int, int]:
        """A row whose pages are ``pages`` (by logical index) is about to run
        a program over positions ``[start, end)`` (``None``: it ends). One pool
        keeps every page of a row until the row ends, so there is nothing to
        do; ``WindowedAllocator`` moves the row's hold on its second pool."""
        return span

    def room(self, tokens: int) -> bool:
        """Whether ``hold`` can follow a row through prefill chunks of
        ``tokens`` tokens, whatever it holds now (one pool: always)."""
        return True

    # -- content cache -------------------------------------------------------

    def lookup(self, key: PageKey) -> int | None:
        """Published page for content key ``key`` (bumps LRU recency)."""
        pid = self._key_to_page.get(key)
        if pid is not None:
            self._lru.move_to_end(key)
        return pid

    def publish(self, key: PageKey, pid: int) -> None:
        """Register ``pid`` as the page for content key ``key``. The cache
        takes its own reference, keeping the page reclaimable-but-resident
        after the owning request finishes."""
        if key in self._key_to_page:
            return  # first publisher wins; the duplicate stays private
        self._key_to_page[key] = pid
        self._page_key[pid] = key
        self._children.setdefault(key[0], set()).add(key)
        self._lru[key] = None
        self._ref[pid] += 1
        if self._ref[pid] == 1:
            # Publishers normally hold their own reference (so ref lands at
            # >= 2 here); a publish from a bare cache insert — the host-tier
            # swap-in path releases its alloc ref after publishing — makes
            # the page immediately evictable.
            self._evictable += 1

    def publish_chain(
        self, tokens: list[int], page_size: int, own_pages: list[int],
        root: int = 0,
    ) -> None:
        """Publish the full pages of ``tokens`` backed by ``own_pages``
        (the owner's physical page per block, shared or private). Walks the
        CANONICAL chain: when a key is already published, the cached page —
        not the owner's private duplicate — becomes the parent for the next
        key, so all equal prefixes share one chain. ``root`` namespaces the
        chain's first parent (multi-LoRA: identical tokens under different
        adapters produce different KV, so each adapter id gets its own
        non-positive root, disjoint from physical page ids)."""
        parent = root
        for i, pid in enumerate(own_pages):
            block = tuple(tokens[i * page_size:(i + 1) * page_size])
            key = (parent, block)
            existing = self._key_to_page.get(key)
            if existing is None:
                self.publish(key, pid)
                parent = pid
            else:
                self._lru.move_to_end(key)
                parent = existing

    def match_prefix(self, tokens: list[int], page_size: int,
                     root: int = 0) -> list[int]:
        """Longest run of published pages covering ``tokens``' leading FULL
        pages — each returned page is retained for the caller. At least one
        token is always left unmatched so the caller's prefill produces the
        next-token logits. ``root``: see ``publish_chain``."""
        usable = len(tokens) - 1
        if usable < page_size:
            return []
        pages: list[int] = []
        parent = root
        for i in range(usable // page_size):
            block = tuple(tokens[i * page_size:(i + 1) * page_size])
            pid = self.lookup((parent, block))
            if pid is None:
                break
            pages.append(pid)
            parent = pid
        for pid in pages:
            self.retain(pid)
        return pages


class WindowedAllocator(PageAllocator):
    """Two pools, page ids of their own, for a stack with window attention
    layers (models/swa.py; infer/page_format.py ``WindowKVPages``).

    The pool this class inherits is the FULL layers': a row keeps every page
    of it, the content cache publishes and evicts them, all as before. The
    WINDOW layers' pool hangs off it: a full page ``f`` may have a COMPANION
    window page ``companion[f]`` that holds the same tokens' keys and values
    in the window layers. A companion is held by the rows that still attend
    to it and, if ``f`` is published and had one at that moment, by the
    content cache; held by nobody it is free.

    - A row holds the companions of a SPAN of its logical pages (``hold``):
      from the page of the first position its next program can attend to,
      ``start - (window - 1)``, to the last page that program writes. As the
      row's position passes a page, ``hold`` gives that companion up
      (``released``; ``freed`` where that emptied it). So a live row holds at
      most ``ceil(window / page_size) + 1`` window pages of context plus
      those of the chunk or tick in flight.
    - Publishing a full page makes the cache a holder of the companion it has
      then: at the end of a prefill or of an answer those are the pages
      inside the last window, which is what a later hit can need.
    - A hit of ``n`` pages is usable at length ``m <= n`` only where the
      pages ``[m - reach, m)`` all have companions (``reach = ceil(window /
      page_size)``): ``match_prefix`` grants the longest such ``m`` and gives
      back the rest (``hits_whole`` / ``hits_short`` / ``hits_refused``).
    - Under pressure ``hold`` evicts the least recently used companion that
      only the cache holds (``window_evictions``); the full page stays
      published, and a later hit over it is shortened or refused.
    """

    holds_spans = True

    def __init__(self, n_pages: int, window_pages: int, *, window: int, page_size: int,
                 **kw):
        super().__init__(n_pages, **kw)
        if window_pages < 2:
            raise ValueError(f"need >= 2 window pages (page 0 is reserved), got {window_pages}")
        self.window_pages, self.window, self.page_size = window_pages, window, page_size
        self.reach = -(-window // page_size)
        self.companion = np.zeros((n_pages,), np.int32)
        self._wfree: deque[int] = deque(range(1, window_pages))
        self._wref = [0] * window_pages
        # full pages whose companion the content cache holds, least recent first
        self._wcached: OrderedDict[int, None] = OrderedDict()
        self.released = self.freed = self.window_evictions = 0
        self.hits_whole = self.hits_short = self.hits_refused = 0

    @property
    def n_window_free(self) -> int:
        return len(self._wfree)

    @property
    def n_window_cached(self) -> int:
        """Companions only the content cache holds (reclaimable)."""
        return sum(1 for f in list(self._wcached) if self._wref[self.companion[f]] == 1)

    def _drop(self, f: int, *, by_row: bool) -> None:
        w = int(self.companion[f])
        self._wref[w] -= 1
        if self._wref[w] < 0:
            raise AssertionError(f"double release of window page {w}")
        self.released += by_row
        if self._wref[w] == 0:
            self.companion[f] = 0
            self._wfree.append(w)
            self.freed += by_row

    def _acquire(self, f: int) -> None:
        w = int(self.companion[f])
        if w:
            self._wref[w] += 1
            return
        if self._wfree:
            w = self._wfree.popleft()
        else:
            victim = next((g for g in self._wcached
                           if self._wref[self.companion[g]] == 1), None)
            if victim is None:
                raise MemoryError(
                    f"window page pool exhausted ({self.window_pages} pages, 0 evictable)")
            del self._wcached[victim]
            w = int(self.companion[victim])
            self.companion[victim] = 0
            self.window_evictions += 1
        self.companion[f] = w
        self._wref[w] = 1

    def pages_of(self, start: int | None, end: int) -> tuple[int, int]:
        """The logical pages a program over positions ``[start, end)`` reads or
        writes in the window layers."""
        if start is None:
            return 0, 0
        lo = max(start - (self.window - 1), 0) // self.page_size
        return lo, max(lo, -(-end // self.page_size))

    def room(self, tokens: int) -> bool:
        need = self.reach + 2 + -(-tokens // self.page_size)
        return len(self._wfree) + self.n_window_cached >= need

    def hold(self, pages, span, start, end=0):
        a, b = span
        lo, hi = self.pages_of(start, end)
        hi = min(hi, len(pages))
        lo = min(lo, hi)
        got: list[int] = []
        try:
            for i in range(lo, hi):
                if not a <= i < b:
                    self._acquire(pages[i])
                    got.append(i)
        except MemoryError:
            for i in got:  # a failed move leaves the row's hold as it was
                self._drop(pages[i], by_row=False)
            raise
        for i in range(a, b):
            if not lo <= i < hi:
                self._drop(pages[i], by_row=True)
        return lo, hi

    def alloc(self, n: int) -> list[int]:
        out = super().alloc(n)
        for pid in out:
            if self.companion[pid]:
                raise AssertionError(f"page {pid} recycled with a companion")
        return out

    def publish(self, key: PageKey, pid: int) -> None:
        fresh = key not in self._key_to_page
        super().publish(key, pid)
        if fresh and self.companion[pid] and pid not in self._wcached:
            self._wref[self.companion[pid]] += 1
            self._wcached[pid] = None

    def _unpublish(self, key: PageKey, pid: int, *, claimed: bool) -> None:
        if pid in self._wcached:
            del self._wcached[pid]
            self._drop(pid, by_row=False)
        super()._unpublish(key, pid, claimed=claimed)

    def match_prefix(self, tokens, page_size, root=0):
        pages = super().match_prefix(tokens, page_size, root)
        n = len(pages)
        m = n
        while m > 0 and not all(self.companion[p] for p in pages[max(0, m - self.reach):m]):
            m -= 1
        for pid in pages[m:]:
            self.release(pid)
        if n:
            if m == n:
                self.hits_whole += 1
            elif m:
                self.hits_short += 1
            else:
                self.hits_refused += 1
        for pid in pages[max(0, m - self.reach):m]:
            if pid in self._wcached:
                self._wcached.move_to_end(pid)
        return pages[:m]

    def window_table(self, table: np.ndarray, spans: list[tuple[int, int]]) -> np.ndarray:
        """The window layers' page table: the companions of each row's held
        span, the sentinel everywhere else."""
        out = np.zeros_like(table)
        for slot, (lo, hi) in enumerate(spans):
            out[slot, lo:hi] = self.companion[table[slot, lo:hi]]
        return out
