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

import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

__all__ = ["PoolExhausted", "PageAllocator", "PrefixCache", "PagedKVPool",
           "LAYER_KEEPS", "CacheLayout", "SlotPages", "PageDemand",
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
# and whether it keeps a row of the ``state_spec`` arenas. A kind's name is
# only a name: whoever asks what a layer keeps reads this pair
# (``CacheLayout.keeps``).
LAYER_KEEPS = {"full": ("full", False), "window": ("window", False),
               "state": (None, True), "none": (None, False),
               "full+state": ("full", True)}


def latent_width(dim: int) -> int:
    """Columns of a latent arena: ``dim`` rounded up to whole 128-lane
    tiles (the TPU lays a row out so anyway; a page is DMA'd whole)."""
    return -(-int(dim) // 128) * 128


class LatentRow(NamedTuple):
    """The one row a token leaves in a latent layer of one paging kind."""
    dim: int                  # values a token leaves
    width: int                # the arena's columns (``latent_width(dim)``)
    value_dim: int            # leading columns that are the value
    scale: Optional[float]    # softmax scale (None: the model's attn_scale)
    heads: Optional[int]      # query heads (None: the model's num_heads)


class CacheIndex(NamedTuple):
    """A learned sparse attention's index row (``cache_spec["index"]``)."""
    dim: int
    heads: int
    topk: int
    layers: Tuple[Optional[str], ...]  # "full": owns an indexer; "shared":
    # attends the last selection; None: selects nothing (a window layer)

    @property
    def indexers(self) -> int:
        return self.layers.count("full")


# what a cache kind cannot use, in the words the engine refuses it with: by
# feature, the first kind that applies (``CacheLayout.refuses``)
_STATE = "{model} carries recurrent state per slot: "
_WINDOW = ("{model} keeps a sliding window of {window} keys in some of its "
           "layers, whose pages go back to the pool as the window passes "
           "them: ")
_REFUSALS = {
    "prefix_cache": (
        ("stateful", _STATE + "a cached K/V prefix has no state to resume "
         "from, so the prefix cache cannot serve it — pass "
         "GenerationConfig(prefix_cache=False)"),
        ("window", _WINDOW + "a cached prefix's pages behind the window are "
         "gone, so the prefix cache cannot serve it — pass "
         "GenerationConfig(prefix_cache=False)")),
    "draft_model": (
        ("stateful", _STATE + "a rejected draft token would have advanced it "
         "and it cannot be rolled back, so speculative decoding is refused — "
         "pass draft_model=None"),
        ("window", _WINDOW + "a verify round that rejects draft tokens would "
         "have to take back pages already given away, so speculative decoding "
         "is refused — pass draft_model=None"),
        ("index", "{model} attends the keys an indexer selects: a verify "
         "window of draft tokens would select with them in the cache and no "
         "test holds that path yet, so speculative decoding is refused — pass "
         "draft_model=None")),
    "warm_pool": (
        ("unpaged", "{model} keeps no K/V pages at all: the warm tier has "
         "nothing to spill or restore — pass "
         "GenerationConfig(warm_pool_bytes=0)"),
        ("mixed", "{model} keeps pages in some of its layers and a recurrent "
         "state in others (or both in one): the warm tier spills and restores "
         "prefixes of pages, and a prefix's state is in none — pass "
         "GenerationConfig(warm_pool_bytes=0)"),
        ("window", _WINDOW + "the warm tier spills and restores whole "
         "prefixes — pass GenerationConfig(warm_pool_bytes=0)"),
        ("latent", "{model} caches one latent row a token: the warm tier "
         "spills and restores K/V pages — pass "
         "GenerationConfig(warm_pool_bytes=0)")),
    "kv_transfer": (
        ("unpaged", "{model} keeps no K/V pages at all — a sequence is its "
         "recurrent state, and no state snapshot is shipped"),
        ("window", "{model} keeps a sliding window in some of its layers — "
         "their pages behind the window have gone back to the pool, so a "
         "prompt's cache cannot be read out or installed page by page"),
        ("latent", "{model} caches one latent row a token — the page "
         "shipper's wire format is K and V stacks of [pages, page_len, heads, "
         "dim] and cannot carry it yet"),
        ("stateful", "{model} carries recurrent state per slot — its K/V "
         "pages alone do not resume a sequence, and no state snapshot is "
         "shipped with them")),
}


@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """What a served model's ``cache_spec`` (and ``state_spec``) come to: the
    ONE reader of that format, for the pool's arenas, the window programs'
    ``attend`` by layer and the engine's refusals and counters alike. Never
    changed once parsed.

    ``cache_spec`` says what ONE token leaves in a layer's paged cache:

    - ``None`` (kind ``"kv"``: GPT-2, Falcon-H1): a key and a value of
      ``[kv_heads, head_dim]``; arenas K and V ``[pages, page_len, kv_heads,
      head_dim]`` a layer (``kernels.pallas.paged_attention``, which walks
      every page of every row);
    - ``{"kind": "latent", "dim": d, "value_dim": dv}``: ONE row of ``d``
      values, the first ``dv`` the value; one arena a layer, ``[pages,
      page_len, d rounded up to 128 lanes]`` (``k``; no V), and
      ``mla_paged_attention`` walks the pages each row's length covers. With
      an ``"index"`` group ``{"dim": di, "heads": hi, "topk": k, "layers":
      [...]}`` (a learned sparse attention) a token leaves a SECOND row of
      ``di`` values, its index key, in every layer the group's ``layers`` calls
      ``"full"`` (it owns an indexer): arenas of their own (``v``: ``[pages,
      page_len, di]``) on the SAME page table, so the prefix trie shares both
      rows of a page; a ``"shared"`` layer attends what the last ``"full"``
      one selected and ``None`` (a window layer) selects nothing;
    - ``{"kind": "kv_by_layer", "layers": [...]}``: a key and a value again,
      laid out ``[pages, kv_heads, page_len, head_dim]`` — one K/V head's
      tokens contiguous — for ``ranged_paged_attention``, which takes each
      row's own range of pages;
    - ``{"kind": "none"}``: NOTHING paged — no arena, no table, admission by
      slots alone; every layer's memory is its recurrent state, so it needs a
      ``state_spec`` (Brumby).

    ``"layers"`` (either ranged kind: ``"kv_by_layer"``, or ``"latent"``)
    names what EACH layer keeps (``LAYER_KEEPS``): ``"full"`` a page for every
    ``page_len`` tokens cached, from the pool's allocator; ``"window"`` only
    the pages that still hold a key a later query can see (``"window": n``:
    query ``i`` sees keys ``i - n < j <= i``), from an allocator and through a
    table of their own — the tables of a cache that declares kinds are stacked
    ``[kinds, rows, B]``, both by absolute block; ``"state"`` no page but a row
    of the ``state_spec`` arenas; ``"none"`` nothing (a position-wise layer);
    ``"full+state"`` both a full layer's pages and a row (an attention whose
    keys are mixed over the sequence before they are cached: the conv's tail).
    Arenas exist for the layers that page alone (in their order) and state
    arenas (``state_spec``: ``{name: (per-slot shape, dtype)}``) for those
    that keep a row alone; at least one layer pages, and a ``state_spec`` is
    declared exactly where some layer keeps a row. With no ``"layers"`` every
    layer is ``"full"`` (kind ``"none"``: nothing) and keeps a row iff the
    model has a ``state_spec``. A latent cache's window layers may have a row
    of their own, ``"window_row": {"dim", "value_dim", "scale", "heads"}``."""

    kind: str                       # "kv", "latent", "kv_by_layer", "none"
    paged: bool                     # some layer keeps pages
    latent: bool                    # a page holds rows, not keys and values
    ranged: bool                    # the kernel takes each row's own range
    stateful: bool                  # some layer keeps a row (``state_spec``)
    by_layer: bool                  # kinds are declared: tables are stacked
    kinds: Optional[List[str]]      # the declared kinds, a layer each
    keeps: List[Tuple[Optional[str], bool]]  # (paging kind, row) a layer
    table_kinds: Tuple[str, ...]    # ("full",) or ("full", "window")
    layers_of: Dict[str, int]       # table kind -> layers that page so
    state_layers: int               # layers that keep a row
    window: int                     # the window layers' n (0: none)
    index: Optional[CacheIndex]
    rows: Dict[str, LatentRow]      # a latent cache's row, by paging kind
    page: Tuple[int, int, int]      # (kv heads, page_len, head_dim)
    state_spec: Optional[Dict[str, Any]]
    # why this kind cannot use "prefix_cache", "draft_model", "warm_pool",
    # "kv_transfer" (a feature it can use has no entry); why no page of it
    # can be shared or spilled (None: one can)
    refuses: Dict[str, str]
    private: Optional[str]

    @staticmethod
    def kind_of(cache_spec) -> str:
        if cache_spec is None:
            return "kv"
        kind = cache_spec["kind"]
        if kind not in ("latent", "kv_by_layer", "none"):
            raise ValueError(
                f"unknown cache kind {kind!r}: a served "
                "model's cache_spec is None (K and V), 'latent', "
                "'kv_by_layer' or 'none'")
        return kind

    @classmethod
    def ranges(cls, cache_spec) -> bool:
        """Whether the cache's kernel takes each row's own range of pages."""
        return cls.kind_of(cache_spec) in ("latent", "kv_by_layer")

    @classmethod
    def parse(cls, cache_spec, state_spec, num_layers: int, page_len: int,
              num_kv_heads: int, head_dim: int) -> "CacheLayout":
        spec = cache_spec or {}
        stateful = state_spec is not None
        kinds = spec.get("layers")
        if kinds is not None:
            kinds = list(kinds)
            if len(kinds) != num_layers or set(kinds) - set(LAYER_KEEPS):
                raise ValueError(
                    f"cache_spec['layers'] must name {num_layers} layers "
                    "'full' or 'window' (pages), 'state' or 'none', or "
                    f"'full+state' (pages and a state row), got {kinds}")
            keeps = [LAYER_KEEPS[kind] for kind in kinds]
            if any(row for _pages, row in keeps) != stateful:
                raise ValueError(
                    "cache_spec['layers'] names a layer that keeps state "
                    "('state', 'full+state') exactly where the model "
                    "declares a "
                    f"state_spec: got {kinds} and state_spec {state_spec}")
            if not any(pages for pages, _row in keeps):
                raise ValueError(
                    f"cache_spec['layers'] {kinds} pages nothing: a model "
                    "with nothing paged declares a cache_spec of kind 'none'")
        kind = cls.kind_of(cache_spec)
        if kind == "none" and not stateful:
            raise ValueError(
                "cache_spec of kind 'none' with no state_spec: the "
                "model would remember nothing")
        if kinds is None:
            keeps = [("full" if kind != "none" else None, stateful)] \
                * num_layers
        paging = [pages for pages, _row in keeps]
        window = int(spec["window"]) if "window" in paging else 0
        table_kinds = ("full", "window")[:1 + bool(window)]
        latent = kind == "latent"
        rows, index = {}, None
        if latent:
            for name in table_kinds:
                own = spec.get("window_row", spec) if name == "window" \
                    else spec
                rows[name] = LatentRow(
                    int(own["dim"]), latent_width(own["dim"]),
                    int(own["value_dim"]), own.get("scale"),
                    own.get("heads"))
            if spec.get("index"):
                group = spec["index"]
                index = CacheIndex(int(group["dim"]), int(group["heads"]),
                                   int(group["topk"]), tuple(group["layers"]))
        private = None
        if kinds is not None:
            private = ("a cache of two layer kinds has no prefix cache and "
                       "no warm tier: a shared page behind a window has been "
                       "given back, and a layer's recurrent state is in no "
                       "page")
        elif kind == "none":
            private = ("a model with nothing paged has no prefix cache and "
                       "no warm tier: there is no page to share or spill")
        applies = {"stateful": stateful, "window": bool(window),
                   "index": index is not None, "unpaged": kind == "none",
                   "mixed": kinds is not None and not window,
                   "latent": latent}
        refuses = {}
        for feature, reasons in _REFUSALS.items():
            why = next((why for what, why in reasons if applies[what]), None)
            if why is not None:
                refuses[feature] = why.replace("{window}", str(window))
        return cls(
            kind=kind, paged=kind != "none", latent=latent,
            ranged=kind in ("latent", "kv_by_layer"), stateful=stateful,
            by_layer=kinds is not None, kinds=kinds, keeps=keeps,
            table_kinds=table_kinds,
            layers_of={name: paging.count(name) for name in table_kinds},
            state_layers=sum(row for _pages, row in keeps), window=window,
            index=index, rows=rows,
            page=(int(num_kv_heads), int(page_len), int(head_dim)),
            state_spec=state_spec, refuses=refuses, private=private)

    def arenas(self, num_pages: int, window_pages: int):
        """The shapes of ``(k, v)``: an arena for each layer that pages, in
        the layers' order, a window layer's of ``window_pages`` pages; ``v``
        of a latent cache holds its index keys, an arena an indexer."""
        G, PL, d = self.page
        paging = [kind for kind, _row in self.keeps if kind]
        pages = [window_pages if kind == "window" else num_pages
                 for kind in paging]
        if self.latent:
            index = self.index
            return ([(p, PL, self.rows[kind].width)
                     for p, kind in zip(pages, paging)],
                    [(num_pages, PL, index.dim)] * index.indexers
                    if index else [])
        shapes = [(p, G, PL, d) if self.ranged else (p, PL, G, d)
                  for p in pages]
        return shapes, shapes


class SlotPages:
    """The pages ONE slot holds, handed out by ``PagedKVPool.slot_pages`` and
    filled, slid and emptied by the pool alone (``join``, ``slide``,
    ``release``). ``tables``: a row for each paging kind; ``table`` (row 0):
    page ids by absolute block (0: the scratch page), ``blocks`` of them
    allocated (0: the slot is free), the leading ``shared`` borrowed from the
    prefix cache. With window layers ``wtable`` (row 1) is theirs, by ABSOLUTE
    block too: blocks ``[wlo, whi)`` hold a page, the blocks behind the
    window have given theirs back."""

    __slots__ = ("tables", "table", "blocks", "shared", "wtable", "wlo",
                 "whi")

    def __init__(self, n_blocks: int, kinds: int):
        self.tables = np.zeros((kinds, n_blocks), dtype=np.int32)
        self.table = self.tables[0]
        self.wtable = self.tables[1] if kinds > 1 else None
        self.blocks = self.shared = self.wlo = self.whi = 0


class PageDemand(NamedTuple):
    """What a request asks of the pool, worked out ONCE at submit
    (``PagedKVPool.demand``): the admission scan runs under the engine lock."""
    blocks: List[Tuple[int, ...]]   # the prompt's full token-blocks
    total: int                      # worst-case pages of a full layer
    window: int                     # window pages its widest call holds
    prompt_len: int


class PagedKVPool:
    """The device half: per-layer page arenas + the control plane of the
    cache ``layout`` describes — and every slot's pages (``SlotPages``): the
    scheduler asks whether a request can join (``can_allocate``), joins it
    (``join``), moves a slot to its next program's positions (``slide``) and
    gives its pages back (``release``); how many allocators answer is the
    pool's business.

    ``allocate`` evicts LRU prefix-cache entries when the free list is short
    (with a ``warm_pool`` they spill, int8, to host RAM: ``warm_restore``).
    ``n_blocks``: the blocks of a sequence's table (``max_seq_len`` in
    pages). ``chunk``: the most tokens one program writes (the engine's
    largest prefill bucket) — it sets how many pages a slot's window layers
    hold while its chunk runs (``window_page_bound``), and ``window_pages``
    (None: every slot's decode bound + three chunks' worth + scratch) must
    cover every slot decoding plus one such chunk.
    """

    def __init__(self, layout: CacheLayout, num_pages: int, dtype,
                 prefix_cache: bool = True,
                 warm_pool: Optional[HostPagePool] = None,
                 max_slots: int = 0, n_blocks: int = 0,
                 window_pages: Optional[int] = None, chunk: int = 1):
        import jax.numpy as jnp

        if layout.private and (prefix_cache or warm_pool is not None):
            raise ValueError(layout.private)
        self.layout = layout
        self.layer_kinds = layout.kinds
        self.page_len = pl = layout.page[1]
        self.num_pages = int(num_pages)
        self.n_blocks, self.chunk = int(n_blocks), int(chunk)
        self.allocator = PageAllocator(num_pages)
        self.trie: Optional[PrefixCache] = PrefixCache() if prefix_cache \
            else None
        self.warm = warm_pool
        # the window layers' pages: an allocator of their own, and the most
        # a slot holds of them while it decodes (``window_bound``)
        self.window = layout.window
        self.window_allocator: Optional[PageAllocator] = None
        self.window_bound = 0
        if self.window:
            S = int(max_slots)
            self.window_bound = bound = window_page_bound(self.window, 1, pl)
            widest = window_page_bound(self.window, chunk, pl)
            if window_pages is None:
                window_pages = S * bound + 3 * widest + 1
            if window_pages < S * bound + widest + 1:
                raise ValueError(
                    f"window_pages {window_pages}: the window layers need "
                    f"{bound} pages for each of {S} slots that decode, "
                    f"{widest} for the one whose {chunk}-token chunk is "
                    f"running, and the scratch page: {S * bound + widest + 1}")
            self.window_allocator = PageAllocator(window_pages)
        # tables stacked by kind, [kinds, rows, B], where kinds are declared
        self._stacked = len(layout.table_kinds) * layout.by_layer
        self._slots: List[SlotPages] = []
        self._reserved: Optional[int] = None   # ``_window_reserved``, cached
        k_shapes, v_shapes = layout.arenas(num_pages, window_pages)
        self.k = [jnp.zeros(shape, dtype) for shape in k_shapes]
        self.v = [jnp.zeros(shape, dtype) for shape in v_shapes]
        # the second kind of cache: one slot-indexed arena per entry of the
        # ``state_spec`` in every layer that keeps a row (the SSM state
        # [slots, heads, P, N], the conv tail). Donated into every program
        # like the K/V arenas and updated in place; a row is overwritten
        # whole when its slot is admitted. None: pages only
        self.state = None if not layout.stateful else [
            {name: jnp.zeros((int(max_slots),) + tuple(shape), dt)
             for name, (shape, dt) in layout.state_spec.items()}
            for _ in range(layout.state_layers)]

    # -- a slot's pages -------------------------------------------------------
    def slot_pages(self) -> SlotPages:
        """The (empty) pages of one more slot; the pool keeps it in view."""
        pages = SlotPages(self.n_blocks, len(self.layout.table_kinds))
        self._slots.append(pages)
        return pages

    def tables_shape(self, rows: int) -> Tuple[int, ...]:
        """A window program's page tables for ``rows`` rows: ``[rows, B]``,
        or a table for each paging kind stacked, ``[kinds, rows, B]``."""
        return ((self._stacked,) if self._stacked else ()) + \
            (rows, self.n_blocks)

    def put_tables(self, out: np.ndarray, row: int, pages: SlotPages) -> None:
        """A slot's tables into row ``row`` of ``out`` (``tables_shape``)."""
        out[..., row, :] = pages.tables if self._stacked else pages.table

    def row_tables(self, pages: SlotPages) -> np.ndarray:
        """A slot's tables as a one-row program takes them — a COPY: the
        programs that read it may still be in flight when the slot's own
        tables are written again."""
        own = pages.tables if self._stacked else pages.table
        return own[..., None, :].copy()

    def demand(self, prompt, max_new_tokens: int) -> PageDemand:
        """What a request must find free at its join: its worst case in full
        pages and, of the window pages, what its widest prefill call holds —
        never less than a decoding slot's."""
        p, pl = len(prompt), self.page_len
        if not self.layout.paged:
            return PageDemand([], 0, 0, p)
        window = max(window_page_bound(self.window, min(p, self.chunk), pl),
                     self.window_bound) if self.window else 0
        return PageDemand(token_blocks(prompt, pl),
                          -(-(p + max_new_tokens) // pl), window, p)

    def _window_reserved(self) -> int:
        """Window pages promised to the running slots beyond what they hold
        (each may grow to its decode bound); once an admission scan."""
        if self._reserved is None:
            bound = self.window_bound
            self._reserved = sum(max(bound - (s.whi - s.wlo), 0)
                                 for s in self._slots if s.blocks)
        return self._reserved

    def can_allocate(self, demand: PageDemand) -> bool:
        """Can this request join now? Its worst case less what the prefix
        cache holds of it must be free (or evictable) and, with window layers,
        its widest call's pages beside what the decoding slots are promised."""
        wa = self.window_allocator
        if wa is not None and \
                demand.window > wa.free_pages - self._window_reserved():
            return False
        n = demand.total
        if self.trie is not None:
            n -= self.trie.match_len(
                demand.blocks[: (demand.prompt_len - 1) // self.page_len])
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

    def join(self, pages: SlotPages, demand: PageDemand) -> Tuple[int, int]:
        """Give a joining prompt ALL its pages, or leave the pool as it was
        and raise ``PoolExhausted``: borrow its cached prefix's pages,
        allocate private ones for the rest, and take the window pages of its
        first prefill call (the later calls': ``slide``). Returns ``(m,
        taken)``: the leading blocks borrowed — the prefill starts at ``m *
        page_len`` — and the window pages taken."""
        p, pl, total = demand.prompt_len, self.page_len, demand.total
        table = pages.table
        table[:] = 0
        # prefix reuse: longest cached chain of full prompt blocks, capped
        # so at least one suffix token remains to produce the first logits
        shared: List[int] = []
        if self.trie is not None:
            head = demand.blocks[: (p - 1) // pl]
            if self.warm is not None:
                # a previously-evicted prefix then costs a host dequantize
                # instead of a re-prefill
                self.warm_restore(head)
            shared = self.trie.match(head, pl, self.allocator)
        m = len(shared)
        try:
            private = self.allocate(total - m)
        except PoolExhausted:
            for pg in shared:
                self.allocator.release(pg)
            raise
        table[:m] = shared
        table[m:total] = private
        pages.blocks, pages.shared = total, m
        # COW hook: every block the decode path will write must be
        # exclusively ours. By construction they already are (the trie
        # shares FULL prompt blocks only), so this is a no-op guard — but a
        # future partial-block sharing scheme lands here.
        for bi in range(p // pl, total):
            pg, copied = self.ensure_writable(int(table[bi]))
            if copied:
                table[bi] = pg
        try:
            _released, taken = self.slide(
                pages, m * pl, min(p, m * pl + self.chunk) - 1)
        except PoolExhausted:
            self.release(pages)
            raise
        self._reserved = None
        return m, taken

    def slide(self, pages: SlotPages, lo: int, hi: int) -> Tuple[int, int]:
        """The next program's queries of this slot sit at positions ``[lo,
        hi]``: its window layers give back every page whose keys all lie
        behind ``lo - (window - 1)``, the first key ``lo`` can see, and take
        pages for the blocks up to ``hi``'s; ``(released, taken)``. Nothing
        to do, ``(0, 0)``, with no window layer. Pages change hands in
        dispatch order, which is the device's order: a program still in
        flight reads its own copy of the table and runs before whatever
        writes the page next."""
        wa = self.window_allocator
        if wa is None:
            return 0, 0
        pl, wtable = self.page_len, pages.wtable
        first = min(max(lo - (self.window - 1), 0) // pl, pages.whi)
        released = max(first - pages.wlo, 0)
        if released:
            for b in range(pages.wlo, first):
                wa.release(int(wtable[b]))
                wtable[b] = 0
            pages.wlo = first
            self._reserved = None
        need = hi // pl + 1
        taken = max(need - pages.whi, 0)
        if taken:   # positions are contiguous: wlo <= first <= whi
            wtable[pages.whi:need] = wa.alloc(taken)
            pages.whi = need
            self._reserved = None
        return released, taken

    def release(self, pages: SlotPages) -> None:
        """Drop a slot's page refs (shared AND private; pages the trie
        adopted survive on its ref and stay reusable)."""
        table = pages.table
        for bi in range(pages.blocks):
            self.allocator.release(int(table[bi]))
        table[:] = 0
        pages.blocks = pages.shared = 0
        if self.window_allocator is not None:
            for bi in range(pages.wlo, pages.whi):
                self.window_allocator.release(int(pages.wtable[bi]))
            pages.wtable[:] = 0
            pages.wlo = pages.whi = 0
        self._reserved = None

    def adopt(self, pages: SlotPages, demand: PageDemand) -> None:
        """A finished prefill's full prompt blocks into the prefix cache."""
        fp = demand.prompt_len // self.page_len
        self.trie.insert(demand.blocks[:fp],
                         [int(x) for x in pages.table[:fp]], self.allocator)

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
        s, d = np.int32(src), np.int32(dst)
        self.k = [fn(a, s, d) for a in self.k]
        self.v = [fn(a, s, d) for a in self.v]

    # -- page transfer (export / install) -------------------------------------
    def read_pages(self, pages: Sequence[int]):
        """Page CONTENTS as per-layer host arrays ``[n, page_len, h, d]``
        (the export path). Caller must hold refs on ``pages``."""
        import jax.numpy as jnp
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
        kinds = [kind for kind, _row in self.layout.keeps if kind]
        if self.layout.index:
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
            return {self.layout.kind: len(self.k) or len(self.state or ())}
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
               "cache": self.layout.kind,
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
