"""Paged KV cache: block-pool allocator + prefix trie (vLLM-style).

The slot-arena continuous batcher reserved ``max_slots * max_seq_len``
K/V rows up front — every admitted sequence paid for its worst case, and
two requests sharing a 500-token system prompt each re-prefilled and
re-stored it. This module replaces that arena with a **block pool**:

- one ``[num_pages, page_len, heads, dim]`` arena per layer
  (``PagedKVPool``) — the only device memory the KV cache ever holds;
- a free-list **allocator** (``PageAllocator``) hands fixed-size pages to
  requests; a request's KV is a *page table* (list of page ids), so its
  footprint is ``ceil(len/page_len)`` pages, not ``max_seq_len`` rows;
- pages are **ref-counted**: a page shared by N readers frees only when
  the last one releases it, and ``cow()`` gives a writer its own copy
  (copy-on-write) when the page is shared;
- a **prefix cache** (``PrefixCache``) — a hash-trie keyed by
  ``(parent, token-block)`` chains — maps full prompt blocks to the pages
  already holding their K/V, so a request sharing a system prompt reuses
  those pages instead of re-prefilling them. Eviction is LRU over
  *leaf* nodes whose page nobody else holds (trie-only refs), so a chain
  never dangles.

The control plane (allocator + trie) is pure Python — unit-testable
without a device. ``PagedKVPool`` adds the per-layer jax arenas and the
page-copy executable the engine uses for COW.

Page 0 is reserved as the **scratch page**: page-table rows of inactive
slots (and positions beyond a request's allocation) point at it, so the
fixed-shape decode executable always has somewhere harmless to write.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = ["PoolExhausted", "PageAllocator", "PrefixCache", "PagedKVPool",
           "LAYER_KEEPS",
           "HostPagePool", "token_blocks", "window_page_bound"]


class PoolExhausted(RuntimeError):
    """The page pool cannot serve the allocation (even after eviction)."""


def token_blocks(tokens, page_len: int, limit: Optional[int] = None
                 ) -> List[Tuple[int, ...]]:
    """The FULL ``page_len``-sized token blocks of a prompt — the trie's
    key units. A trailing partial block is never a key (it would receive
    decode writes)."""
    n = len(tokens) // page_len
    if limit is not None:
        n = min(n, limit)
    return [tuple(int(t) for t in tokens[i * page_len:(i + 1) * page_len])
            for i in range(n)]


class PageAllocator:
    """Free-list page allocator with ref counts (pure control plane).

    Invariants (asserted by ``check()``):
    - page 0 is reserved (never allocated, refcount pinned);
    - every page is either on the free list (ref 0) or live (ref >= 1);
    - ``free_pages + live_pages == num_pages - 1``.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(f"need >= 2 pages (1 scratch + 1 usable), "
                             f"got {num_pages}")
        self.num_pages = int(num_pages)
        self._free: List[int] = list(range(1, num_pages))
        self._ref = [0] * num_pages
        self._ref[0] = 1  # scratch page: pinned forever
        self.alloc_total = 0
        self.free_total = 0
        self.cow_total = 0
        self.peak_live = 0    # the most pages ever live at once

    # -- queries --------------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    @property
    def usable_pages(self) -> int:
        """Pages a single request could ever hold (pool minus scratch)."""
        return self.num_pages - 1

    def ref(self, page: int) -> int:
        return self._ref[page]

    # -- alloc / retain / release ---------------------------------------------
    def alloc(self, n: int) -> List[int]:
        """n fresh pages at refcount 1, or ``PoolExhausted`` (all-or-
        nothing: a partial grab is never held across the raise)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            raise PoolExhausted(
                f"need {n} pages, {len(self._free)} free "
                f"({self.live_pages} live) of {self.usable_pages} usable "
                f"[pool={self.num_pages} incl. scratch, "
                f"alloc_total={self.alloc_total}, "
                f"free_total={self.free_total}]")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        self.alloc_total += n
        self.peak_live = max(self.peak_live, self.live_pages)
        return pages

    def retain(self, page: int) -> None:
        if page == 0:
            return  # scratch is pinned; sharing it is a no-op
        if self._ref[page] <= 0:
            raise RuntimeError(f"retain of free page {page}")
        self._ref[page] += 1

    def release(self, page: int) -> None:
        if page == 0:
            return
        r = self._ref[page]
        if r <= 0:
            raise RuntimeError(f"double free of page {page}")
        self._ref[page] = r - 1
        if r == 1:
            self._free.append(page)
            self.free_total += 1

    def cow(self, page: int) -> Tuple[int, bool]:
        """Copy-on-write: the caller wants to WRITE ``page``. Exclusive
        pages (ref 1) are returned as-is; shared pages cost one fresh page
        (caller must copy the contents device-side) and drop the shared
        ref. Returns ``(writable_page, copied)``."""
        if page != 0 and self._ref[page] == 1:
            return page, False
        new = self.alloc(1)[0]
        self.release(page)
        self.cow_total += 1
        return new, True

    def check(self) -> None:
        """Assert the allocator invariants (test hook)."""
        assert self._ref[0] >= 1, "scratch page unpinned"
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate page on free list"
        assert 0 not in free, "scratch page on free list"
        for p in range(1, self.num_pages):
            if p in free:
                assert self._ref[p] == 0, (p, self._ref[p])
            else:
                assert self._ref[p] >= 1, (p, self._ref[p])
        assert self.free_pages + self.live_pages == self.num_pages - 1


class _TrieNode:
    __slots__ = ("key", "parent", "page", "children", "last_used")

    def __init__(self, key, parent, page, last_used):
        self.key = key
        self.parent = parent      # parent key (None for depth-0 blocks)
        self.page = page
        self.children = 0         # live child count (eviction is leaf-only)
        self.last_used = last_used


class PrefixCache:
    """Hash-trie over token-block chains -> KV pages.

    A node's key is ``(parent_key, block_tokens)`` — the full token
    context is encoded in the chain, so equal blocks under different
    prefixes never collide. The trie holds ONE allocator ref per adopted
    page; ``evict()`` walks least-recently-used *leaves* whose page has no
    other holder, so eviction can never free a page out from under a
    reader or orphan a reachable child.
    """

    def __init__(self):
        from ..analysis.lockdep import lock as _named_lock  # lazy: no cycle

        self._lock = _named_lock("serving.PrefixCache._lock")
        self._nodes: Dict[Any, _TrieNode] = {}
        self._tick = 0
        self.lookups = 0
        self.hits = 0
        self.hit_tokens = 0
        self.lookup_tokens = 0
        self.inserts = 0
        self.evictions = 0

    @staticmethod
    def _key(parent, block) -> Tuple:
        return (parent, block)

    @staticmethod
    def chain_key(blocks: Sequence[Tuple[int, ...]]):
        """The trie key of chain ``blocks`` (deterministic — computable
        without trie state, so warm-tier keys survive eviction)."""
        parent = None
        for block in blocks:
            parent = (parent, block)
        return parent

    # -- reads ----------------------------------------------------------------
    def match(self, blocks: Sequence[Tuple[int, ...]], page_len: int,
              allocator: Optional[PageAllocator] = None) -> List[int]:
        """Longest cached chain for ``blocks``; returns its pages. When an
        allocator is given each returned page is retained FOR THE CALLER
        (released by the caller when its request finishes)."""
        with self._lock:
            self._tick += 1
            self.lookups += 1
            self.lookup_tokens += len(blocks) * page_len
            pages: List[int] = []
            parent = None
            for block in blocks:
                node = self._nodes.get(self._key(parent, block))
                if node is None:
                    break
                node.last_used = self._tick
                pages.append(node.page)
                parent = node.key
            if pages:
                self.hits += 1
                self.hit_tokens += len(pages) * page_len
            if allocator is not None:
                for p in pages:
                    allocator.retain(p)
            return pages

    def match_len(self, blocks: Sequence[Tuple[int, ...]]) -> int:
        """Depth of the longest cached chain (no refs taken, no LRU bump)
        — the router's prefix-affinity probe."""
        with self._lock:
            depth, parent = 0, None
            for block in blocks:
                node = self._nodes.get(self._key(parent, block))
                if node is None:
                    break
                depth += 1
                parent = node.key
            return depth

    # -- writes ---------------------------------------------------------------
    def insert(self, blocks: Sequence[Tuple[int, ...]], pages: Sequence[int],
               allocator: PageAllocator) -> int:
        """Adopt ``pages[i]`` as the cached KV of chain ``blocks[:i+1]``.
        Existing nodes keep their page (first writer wins — both copies
        hold identical K/V); new nodes retain theirs. Returns the number
        of newly adopted pages."""
        assert len(blocks) == len(pages)
        adopted = 0
        with self._lock:
            self._tick += 1
            parent = None
            for block, page in zip(blocks, pages):
                key = self._key(parent, block)
                node = self._nodes.get(key)
                if node is None:
                    node = _TrieNode(key, parent, page, self._tick)
                    self._nodes[key] = node
                    allocator.retain(page)
                    if parent is not None:
                        self._nodes[parent].children += 1
                    self.inserts += 1
                    adopted += 1
                else:
                    node.last_used = self._tick
                parent = key
        return adopted

    def evict(self, n_pages: int, allocator: PageAllocator,
              on_evict=None) -> int:
        """Free up to ``n_pages`` pages by dropping LRU leaves whose page
        has no holder besides the trie (ref == 1). Returns pages freed.

        ``on_evict(key, page)`` — if given — is called for each victim
        BEFORE its page is released, while the page contents are still
        valid: the warm-tier spill hook."""
        freed = 0
        with self._lock:
            while freed < n_pages:
                victim = None
                for node in self._nodes.values():
                    if node.children:
                        continue
                    if allocator.ref(node.page) != 1:
                        continue  # someone is reading it right now
                    if victim is None or node.last_used < victim.last_used:
                        victim = node
                if victim is None:
                    break
                if on_evict is not None:
                    on_evict(victim.key, victim.page)
                del self._nodes[victim.key]
                if victim.parent is not None:
                    self._nodes[victim.parent].children -= 1
                allocator.release(victim.page)
                self.evictions += 1
                freed += 1
        return freed

    def release_all(self, allocator: PageAllocator) -> None:
        """Drop every node (engine close): release the trie's refs."""
        with self._lock:
            for node in self._nodes.values():
                allocator.release(node.page)
            self._nodes.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._nodes)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"nodes": len(self._nodes), "lookups": self.lookups,
                    "hits": self.hits, "hit_tokens": self.hit_tokens,
                    "lookup_tokens": self.lookup_tokens,
                    "inserts": self.inserts, "evictions": self.evictions,
                    "hit_rate": round(self.hit_tokens /
                                      max(self.lookup_tokens, 1), 4)}


class HostPagePool:
    """Replica-local warm tier: evicted prefix-cache pages spill here.

    Page contents live in host RAM, int8-quantized with per-page scales
    (~4x cheaper than device-resident fp32).  Admission is frequency
    gated — a chain key must be *seen* ``admit_threshold`` times before
    its bytes are kept (the PR-14 ``HotRowCache`` ghost-counter pattern)
    — and residency is LRU under a byte budget.  Keys are deterministic
    trie chain keys (``PrefixCache.chain_key``) so a warm page can be
    restored into a fresh trie after eviction.
    """

    def __init__(self, capacity_bytes: int = 64 << 20,
                 admit_threshold: int = 2, ghost_cap: int = 2048):
        from collections import OrderedDict

        from ..analysis.lockdep import lock as _named_lock  # lazy: no cycle

        self.capacity_bytes = int(capacity_bytes)
        self.admit_threshold = int(admit_threshold)
        self.ghost_cap = int(ghost_cap)
        self._entries = OrderedDict()   # key -> (k_q, k_s, v_q, v_s, nbytes)
        self._bytes = 0
        self._ghost: Dict[Any, int] = {}
        self.hits = 0
        self.misses = 0
        self.admits = 0
        self.rejects = 0
        self.evictions = 0
        self.restores = 0
        self._lock = _named_lock("serving.HostPagePool._lock")

    def note_access(self, key) -> None:
        with self._lock:
            self._ghost[key] = self._ghost.get(key, 0) + 1
            if len(self._ghost) > self.ghost_cap:
                self._ghost = {k: v // 2 for k, v in self._ghost.items()
                               if v // 2 > 0}

    def put(self, key, k_layers, v_layers) -> bool:
        """Spill one page (per-layer ``[page_len, heads, dim]`` arrays)."""
        import numpy as np

        from .kv_transfer import quantize_page

        with self._lock:
            seen = self._ghost.get(key, 0)
        if key is None or seen < self.admit_threshold:
            with self._lock:
                self.rejects += 1
            return False
        k_q, k_s, v_q, v_s = [], [], [], []
        nbytes = 0
        for arr in k_layers:
            q, s = quantize_page(np.asarray(arr))
            k_q.append(q); k_s.append(s); nbytes += q.nbytes
        for arr in v_layers:
            q, s = quantize_page(np.asarray(arr))
            v_q.append(q); v_s.append(s); nbytes += q.nbytes
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return True
            if nbytes > self.capacity_bytes:
                self.rejects += 1
                return False
            while self._bytes + nbytes > self.capacity_bytes and self._entries:
                _, old = self._entries.popitem(last=False)
                self._bytes -= old[4]
                self.evictions += 1
            self._entries[key] = (k_q, k_s, v_q, v_s, nbytes)
            self._bytes += nbytes
            self.admits += 1
            return True

    def get(self, key, dtype=None):
        """Dequantized ``(k_layers, v_layers)`` for ``key``, or None."""
        from .kv_transfer import dequantize_page

        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            k_q, k_s, v_q, v_s, _ = ent
        import numpy as np

        dt = dtype or np.float32
        return ([dequantize_page(q, s, dt) for q, s in zip(k_q, k_s)],
                [dequantize_page(q, s, dt) for q, s in zip(v_q, v_s)])

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            total = self.hits + self.misses
            return {"entries": len(self._entries), "bytes": self._bytes,
                    "capacity_bytes": self.capacity_bytes,
                    "hits": self.hits, "misses": self.misses,
                    "hit_rate": round(self.hits / total, 4) if total else 0.0,
                    "admits": self.admits, "rejects": self.rejects,
                    "evictions": self.evictions, "restores": self.restores}


def window_page_bound(window: int, tokens: int, page_len: int) -> int:
    """The most pages a slot's window layers hold while a program of
    ``tokens`` window tokens runs (1: a decode round; a chunk's width: a
    prefill call): the ``window - 1`` keys behind its first query and its own
    ``tokens`` keys span at most ``ceil((window + tokens) / page_len) + 1``
    pages — 6 for a slot that decodes at window 512 and pages of 128, 21
    while its 2048-token chunk runs."""
    return -(-(int(window) + int(tokens)) // int(page_len)) + 1


# What a layer of each declared kind (``cache_spec["layers"]``) keeps: the
# paging kind whose pool and table hold its pages (``None``: it pages nothing)
# and whether it keeps a row of the ``state_spec`` arenas. ``"full+state"`` is
# a layer of BOTH memories: a page for every ``page_len`` tokens AND a row by
# slot (an attention whose keys are mixed over the sequence before they are
# cached: the conv's tail is the row). ``layers_by_kind`` counts from this
# table alone: a kind's name is only a name.
LAYER_KEEPS = {"full": ("full", False), "window": ("window", False),
               "state": (None, True), "none": (None, False),
               "full+state": ("full", True)}


def _paging(kinds) -> List[str]:
    """The paging kind of each layer of ``kinds`` that pages, in order: one
    K/V (or latent) arena each."""
    return [pages for pages, _row in map(LAYER_KEEPS.get, kinds) if pages]


def latent_width(dim: int) -> int:
    """Columns of a latent arena: ``dim`` rounded up to whole 128-lane
    tiles (the TPU lays a row out so anyway; a page is DMA'd whole)."""
    return -(-int(dim) // 128) * 128


class PagedKVPool:
    """The device half: per-layer K/V page arenas + the control plane.

    ``allocate(n)`` serves from the free list, evicting LRU prefix-cache
    entries when short — so a hot serving process naturally trades cold
    cached prefixes for live requests. With a ``warm_pool``, evicted
    prefix pages spill (int8) to host RAM and can be restored by
    ``warm_restore`` instead of re-prefilling.
    """

    def __init__(self, num_layers: int, num_pages: int, page_len: int,
                 num_heads: int, head_dim: int, dtype,
                 prefix_cache: bool = True,
                 warm_pool: Optional[HostPagePool] = None,
                 state_spec=None, max_slots: int = 0, cache_spec=None,
                 window_pages: int = 0):
        import jax.numpy as jnp

        self.page_len = int(page_len)
        self.num_pages = int(num_pages)
        self.allocator = PageAllocator(num_pages)
        self.trie: Optional[PrefixCache] = PrefixCache() if prefix_cache \
            else None
        self.warm = warm_pool
        # what a token leaves in a layer (the served model's ``cache_spec``):
        # K and V of [heads, head_dim] — or ONE latent row, kept in ``k``
        # alone at a whole number of 128-lane tiles (a page is DMA'd whole)
        # — or K and V by the layer's KIND: a "full" layer keeps a page for
        # every ``page_len`` tokens cached, from ``allocator``; a "window"
        # layer only the pages that still hold a key some later query can
        # see, from ``window_allocator`` (``window_pages`` of them, scratch
        # included). Both kinds lay a page out [heads, page_len, head_dim]:
        # one K/V head's tokens contiguous (``ranged_paged_attention``) — or
        # ONE latent row by the layer's kind (a latent spec with ``"layers"``:
        # the same two allocators, a window layer's rows of their own width)
        self.cache_spec = cache_spec
        self.window_allocator: Optional[PageAllocator] = None
        self.layer_kinds: Optional[List[str]] = None
        # a cache that declares its layers' KINDS, K/V or latent
        # (``cache_spec["layers"]``): a "full" layer's arenas hold the pool's
        # pages, a "window" layer's ``window_pages`` pages of an allocator of
        # their own; a "state" layer keeps no page but a row of the state
        # arenas below, a "none" layer nothing at all, a "full+state" layer a
        # full layer's pages AND a row (``LAYER_KEEPS``)
        kinds = self._kinds(cache_spec, num_layers, window_pages,
                            prefix_cache or warm_pool is not None, state_spec)
        kinds = [None] * num_layers if kinds is None else _paging(kinds)
        pages_of = [window_pages if kind == "window" else num_pages
                    for kind in kinds]
        if cache_spec is None:
            shapes = [(num_pages, page_len, num_heads, head_dim)] * num_layers
        elif cache_spec["kind"] == "latent":
            # a window layer's row may have a width of its own
            # (``cache_spec["window_row"]``)
            wide = {"window": cache_spec.get("window_row", cache_spec)["dim"]}
            shapes = [(n, page_len, latent_width(
                wide.get(kind, cache_spec["dim"])))
                for n, kind in zip(pages_of, kinds)]
        elif cache_spec["kind"] == "kv_by_layer":
            # (an arena for each layer that pages, in the layers' order)
            shapes = [(n, num_heads, page_len, head_dim) for n in pages_of]
        elif cache_spec["kind"] == "none":
            # NOTHING paged: every layer's memory is its recurrent state
            # (``state_spec``), whatever the context. No arena, no page a
            # request could hold; the allocator keeps the scratch page alone
            if state_spec is None:
                raise ValueError(
                    "cache_spec of kind 'none' with no state_spec: the "
                    "model would remember nothing")
            if prefix_cache or warm_pool is not None:
                raise ValueError(
                    "a model with nothing paged has no prefix cache and no "
                    "warm tier: there is no page to share or spill")
            shapes = []
        else:
            raise ValueError(
                f"unknown cache kind {cache_spec['kind']!r}: a served "
                "model's cache_spec is None (K and V), 'latent', "
                "'kv_by_layer' or 'none'")
        self.k = [jnp.zeros(shape, dtype) for shape in shapes]
        if cache_spec is not None and cache_spec["kind"] == "latent":
            # a latent cache keeps no V; with an index row (a learned sparse
            # attention: ``cache_spec["index"]``) ``v`` holds the index keys
            # instead, one arena a layer that owns an indexer ("full"), on
            # the same pages: page p of a sequence holds its tokens' latent
            # rows in ``k`` and their index keys in ``v``
            index = cache_spec.get("index")
            self.v = [] if not index else [
                jnp.zeros((num_pages, page_len, int(index["dim"])), dtype)
                for kind in index["layers"] if kind == "full"]
        else:
            self.v = [jnp.zeros(shape, dtype) for shape in shapes]
        # the second kind of cache: per layer, one slot-indexed arena per
        # entry of a recurrent model's ``state_spec`` ({name: (per-slot
        # shape, dtype)}) — e.g. the SSM state [slots, heads, P, N] and the
        # conv tail [slots, d_conv - 1, channels]. Donated into every
        # program like the K/V arenas and updated in place; a row is
        # overwritten whole when its slot is admitted. None: K/V only. Where
        # the cache declares its layers' kinds, the layers that keep a row
        # alone have one (in their order), else every layer.
        self.state = None if state_spec is None else [
            {name: jnp.zeros((int(max_slots),) + tuple(shape), dt)
             for name, (shape, dt) in state_spec.items()}
            for _ in range(num_layers if self.layer_kinds is None
                           else self.layers_by_kind().get("state", 0))]

    def _kinds(self, cache_spec, num_layers: int, window_pages: int,
               shares_pages: bool, state_spec) -> Optional[List[str]]:
        """The layers' kinds where the cache declares them (``"layers"``: a
        K/V cache ``kv_by_layer``, or a latent one), and the window layers'
        allocator with them; ``None`` for a cache of one kind."""
        kinds = (cache_spec or {}).get("layers")
        if kinds is None:
            return None
        kinds = list(kinds)
        if len(kinds) != num_layers or set(kinds) - set(LAYER_KEEPS):
            raise ValueError(
                f"cache_spec['layers'] must name {num_layers} layers "
                "'full' or 'window' (pages), 'state' or 'none', or "
                f"'full+state' (pages and a state row), got {kinds}")
        keeps = [LAYER_KEEPS[kind] for kind in kinds]
        if any(row for _pages, row in keeps) != (state_spec is not None):
            raise ValueError(
                "cache_spec['layers'] names a layer that keeps state "
                "('state', 'full+state') exactly where the model declares a "
                f"state_spec: got {kinds} and state_spec {state_spec}")
        if not any(pages for pages, _row in keeps):
            raise ValueError(
                f"cache_spec['layers'] {kinds} pages nothing: a model with "
                "nothing paged declares a cache_spec of kind 'none'")
        if shares_pages:
            raise ValueError(
                "a cache of two layer kinds has no prefix cache and no "
                "warm tier: a shared page behind a window has been "
                "given back, and a layer's recurrent state is in no page")
        self.layer_kinds = kinds
        if "window" in kinds:
            self.window = int(cache_spec["window"])
            self.window_allocator = PageAllocator(window_pages)
        return kinds

    # -- control plane --------------------------------------------------------
    def allocate(self, n: int) -> List[int]:
        """n pages, evicting cached prefixes if the free list is short."""
        short = n - self.allocator.free_pages
        if short > 0 and self.trie is not None:
            self.trie.evict(short, self.allocator,
                            on_evict=self._spill if self.warm is not None
                            else None)
        return self.allocator.alloc(n)

    def _spill(self, key, page: int) -> None:
        """Warm-tier spill hook: page contents -> host RAM (int8)."""
        import numpy as np

        self.warm.note_access(key)
        k_layers = [np.asarray(a[page]) for a in self.k]
        v_layers = [np.asarray(a[page]) for a in self.v]
        self.warm.put(key, k_layers, v_layers)

    def warm_restore(self, blocks: Sequence[Tuple[int, ...]]) -> int:
        """Extend the trie's cached chain for ``blocks`` from the warm
        tier: for each block past the device-resident match depth with a
        warm hit, allocate a page, dequantize-write its contents, and
        adopt it into the trie. Returns pages restored."""
        if self.trie is None or self.warm is None or not blocks:
            return 0
        import numpy as np

        depth = self.trie.match_len(blocks)
        # note accesses for the whole tail so repeat traffic becomes
        # admittable even before anything is ever spilled
        for j in range(depth, len(blocks)):
            self.warm.note_access(PrefixCache.chain_key(blocks[:j + 1]))
        if depth >= len(blocks):
            return 0
        chain_pages = self.trie.match(blocks[:depth], self.page_len)
        restored = 0
        for j in range(depth, len(blocks)):
            key = PrefixCache.chain_key(blocks[:j + 1])
            ent = self.warm.get(key, dtype=self.k[0].dtype)
            if ent is None:
                break
            try:
                page = self.allocate(1)[0]
            except PoolExhausted:
                break
            k_layers, v_layers = ent
            self.write_pages([page],
                             [kl[np.newaxis] for kl in k_layers],
                             [vl[np.newaxis] for vl in v_layers])
            chain_pages.append(page)
            adopted = self.trie.insert(blocks[:j + 1], chain_pages,
                                       self.allocator)
            self.allocator.release(page)  # trie owns it now
            if not adopted:
                break  # raced: an identical chain landed first
            self.warm.restores += 1
            restored += 1
        return restored

    def can_allocate(self, n: int, n_window: int = 0,
                     window_reserved: int = 0) -> bool:
        """Can a request join that needs ``n`` pages of the full layers
        and, in a cache of two layer kinds, ``n_window`` pages of the window
        layers while ``window_reserved`` more stay promised to the slots
        that are decoding (each may yet grow to its bound)?"""
        if n_window and n_window > \
                self.window_allocator.free_pages - window_reserved:
            return False
        free = self.allocator.free_pages
        if n <= free:
            return True
        if self.trie is None:
            return False
        # leaf-only eviction frees parents as it goes, so every trie-only
        # page is ultimately reachable: count all of them
        evictable = sum(1 for node in self.trie._nodes.values()
                        if self.allocator.ref(node.page) == 1)
        return n <= free + evictable

    def ensure_writable(self, page: int) -> Tuple[int, bool]:
        """COW hook: give the caller a page it may write. When the page is
        shared, a fresh page is allocated and the K/V CONTENT IS COPIED
        device-side before returning."""
        new, copied = self.allocator.cow(page)
        if copied:
            self._copy_page(page, new)
        return new, copied

    def _copy_page(self, src: int, dst: int) -> None:
        import jax

        fn = getattr(self, "_copy_fn", None)
        if fn is None:
            def copy(arena, s, d):
                return arena.at[d].set(arena[s])

            fn = self._copy_fn = jax.jit(copy)
        import numpy as np

        s, d = np.int32(src), np.int32(dst)
        self.k = [fn(a, s, d) for a in self.k]
        self.v = [fn(a, s, d) for a in self.v]

    # -- page transfer (export / install) -------------------------------------
    def read_pages(self, pages: Sequence[int]):
        """Page CONTENTS as per-layer host arrays ``[n, page_len, h, d]``
        (the export path). Caller must hold refs on ``pages``."""
        import jax.numpy as jnp
        import numpy as np

        idx = jnp.asarray(list(pages), dtype=jnp.int32)
        return ([np.asarray(a[idx]) for a in self.k],
                [np.asarray(a[idx]) for a in self.v])

    def write_pages(self, pages: Sequence[int], k_stacks, v_stacks) -> None:
        """Scatter-write page CONTENTS into the arenas (the install
        path). ``k_stacks[li]``/``v_stacks[li]`` are ``[n, page_len, h,
        d]`` arrays; data is cast to the arena dtype."""
        import jax
        import jax.numpy as jnp

        fn = getattr(self, "_install_fn", None)
        if fn is None:
            def put(arena, idx, data):
                return arena.at[idx].set(data)

            fn = self._install_fn = jax.jit(put)
        idx = jnp.asarray(list(pages), dtype=jnp.int32)
        self.k = [fn(a, idx, jnp.asarray(d, dtype=a.dtype))
                  for a, d in zip(self.k, k_stacks)]
        self.v = [fn(a, idx, jnp.asarray(d, dtype=a.dtype))
                  for a, d in zip(self.v, v_stacks)]

    # -- observability --------------------------------------------------------
    def bytes(self) -> int:
        return sum(int(a.nbytes) for a in self.k) + \
            sum(int(a.nbytes) for a in self.v)

    def bytes_by_kind(self) -> Dict[str, int]:
        """The arenas' bytes by layer kind (one kind, "full", for a cache
        that declares none); the latent rows and the index keys apart for a
        latent cache with an index row, and the latent rows by layer kind
        where it declares two (``latent_full`` / ``latent_window``); the
        state arenas as ``"state"`` where some layers are declared to keep
        one."""
        kinds = _paging(self.layer_kinds or ["full"] * len(self.k))
        if self.cache_spec is not None and self.cache_spec.get("index"):
            names = [f"latent_{kind}" for kind in kinds] \
                if self.layer_kinds else ["latent"] * len(self.k)
            out = {name: 0 for name in names}
            for name, a in zip(names, self.k):
                out[name] += int(a.nbytes)
            return {**out, "index": sum(int(a.nbytes) for a in self.v)}
        out = {kind: 0 for kind in kinds}
        for arenas in (self.k, self.v):
            for kind, a in zip(kinds, arenas):
                out[kind] += int(a.nbytes)
        if self.layer_kinds and self.state:
            out["state"] = self.state_bytes()
        return out

    def layers_by_kind(self) -> Dict[str, int]:
        """How many layers keep what: ``{"full": 1, "state": 4, "none": 4}``
        for a cache that declares its layers' kinds (a ``"full+state"`` layer
        under both its memories: ``{"full": 20, "state": 20}``), else every
        layer under the one kind the cache has."""
        if self.layer_kinds is None:
            one = "kv" if self.cache_spec is None else self.cache_spec["kind"]
            return {one: len(self.k) or len(self.state or ())}
        out: Dict[str, int] = {}
        for pages, row in map(LAYER_KEEPS.get, self.layer_kinds):
            names = [pages] * bool(pages) + ["state"] * row
            for name in names or ["none"]:
                out[name] = out.get(name, 0) + 1
        return out

    def live_pages_by_kind(self) -> Dict[str, int]:
        out = {"full": self.allocator.live_pages}
        if self.window_allocator is not None:
            out["window"] = self.window_allocator.live_pages
        return out

    def state_bytes(self) -> int:
        return sum(int(a.nbytes) for layer in self.state or ()
                   for a in layer.values())

    def stats(self) -> Dict[str, Any]:
        a = self.allocator
        out = {"pages_total": a.num_pages, "page_len": self.page_len,
               "cache": "kv" if self.cache_spec is None
               else self.cache_spec["kind"],
               "pages_free": a.free_pages, "pages_live": a.live_pages,
               "pages_peak": a.peak_live, "pool_bytes": self.bytes(),
               "state_bytes": self.state_bytes(),
               "layers_by_kind": self.layers_by_kind(),
               "arenas": {"kv": len(self.k),
                          "state": len(self.state or ())},
               "alloc_total": a.alloc_total, "cow_total": a.cow_total,
               "headroom": round(a.free_pages / max(a.usable_pages, 1), 4)}
        if self.window_allocator is not None:
            w, by_kind = self.window_allocator, self.bytes_by_kind()
            out["window"] = {
                "window": self.window, "pages_total": w.num_pages,
                "pages_free": w.free_pages, "pages_live": w.live_pages,
                "pages_peak": w.peak_live, "alloc_total": w.alloc_total,
                "free_total": w.free_total,
                "pool_bytes": by_kind.get("window",
                                          by_kind.get("latent_window")),
                "headroom": round(w.free_pages / max(w.usable_pages, 1), 4)}
        if self.trie is not None:
            out["prefix"] = self.trie.stats()
        if self.warm is not None:
            out["warm"] = self.warm.stats()
        return out
