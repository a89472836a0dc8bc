"""Host-side paged-KV management: refcounted page allocator, the prefix
index (vLLM-style prefix caching), and the device-pool wrapper.

Device-side layout and the attention ops live in ``repro.nn.paged`` /
``repro.models.init_paged_cache``; this module owns the mutable host
state the scheduler works against:

  * ``PageAllocator`` — refcounted allocation over pool page ids with an
    LRU *cached* tier: a page whose refcount drops to zero but whose
    contents are registered in the prefix index becomes reusable-but-
    evictable instead of free.  ``alloc`` consumes free pages first and
    only then evicts cached pages (dropping their index entries via the
    ``on_evict`` callback), so unreferenced cached pages are always
    reclaimed before any running request is preempted.  Page 0 is the
    reserved *scratch* page (padded/idle writes land there), so ids
    handed out are in ``[1, n_pages)``.
  * ``PrefixIndex`` — maps hash-chained full pages of prompt tokens to
    the pool page holding their K/V, so admission can map already-cached
    prefix pages into a new request's page table and skip prefilling
    those tokens (DESIGN.md §7).
  * ``PagedKVCache`` — the device pools plus per-slot page tables and
    lengths (numpy, mirrored to device each engine step).
"""
from __future__ import annotations

import functools
import hashlib
import math
from collections import OrderedDict, deque
from typing import Callable, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.models import init_paged_cache, supports_paged_cache
from repro.quant.kvcache import heads_layout, stored_layout


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page_jit(pools, src, dst):
    """In-place page duplication: the pool buffers are donated so XLA
    updates one page per pool instead of materializing a full copy of
    every pool (the eager ``a.at[...].set`` a COW event used to run
    reallocated the ENTIRE pool per layer leaf).  ``src``/``dst`` are
    traced scalars — one compile covers every page pair."""
    return jax.tree_util.tree_map(
        lambda a: a.at[:, dst].set(a[:, src]), pools)


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold n_tokens (at least one)."""
    return max(1, math.ceil(n_tokens / page_size))


class PageAllocator:
    """Refcounted LIFO allocator over pool pages [1, n_pages).

    Lifecycle of a page::

        free ──alloc──▶ held (ref 1) ──retain──▶ shared (ref k)
          ▲                  │ free (ref→0)
          │     unregistered │          registered in the prefix index
          └──────────────────┴──▶ cached (LRU) ──alloc evicts──▶ held

    ``alloc`` is all-or-nothing (returns None when the request can't be
    covered) so admission control never partially commits a sequence.
    ``mark_cached``/``on_evict`` are the prefix index's hooks: marked
    pages park in the cached LRU at ref 0 instead of the free list, and
    eviction (oldest first) notifies the index to drop its entry."""

    def __init__(self, n_pages: int,
                 on_evict: Optional[Callable[[int], None]] = None):
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is scratch)")
        self.n_pages = n_pages
        self.on_evict = on_evict
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._ref: Dict[int, int] = {}
        self._cached: "OrderedDict[int, None]" = OrderedDict()
        self._cacheable = set()

    @property
    def n_free(self) -> int:
        """Allocatable pages: truly free + evictable cached."""
        return len(self._free) + len(self._cached)

    @property
    def n_free_strict(self) -> int:
        """Truly free pages only (no cached-tier eviction needed) — the
        ``pages_free`` gauge (DESIGN.md §9)."""
        return len(self._free)

    @property
    def n_held(self) -> int:
        """Pages currently referenced by at least one sequence — the
        ``pages_held`` gauge."""
        return len(self._ref)

    @property
    def n_cached(self) -> int:
        return len(self._cached)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def can_alloc(self, n: int) -> bool:
        return n <= self.n_free

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > self.n_free:
            return None
        out = []
        for _ in range(n):
            if self._free:
                p = self._free.pop()
            else:                           # evict LRU cached page
                p, _ = self._cached.popitem(last=False)
                self._cacheable.discard(p)
                if self.on_evict is not None:
                    self.on_evict(p)
            self._ref[p] = 1
            out.append(p)
        return out

    def retain(self, page: int) -> None:
        """Add a reference: share a held page, or revive a cached one."""
        if page in self._cached:
            del self._cached[page]
            self._ref[page] = 1
            return
        if self._ref.get(page, 0) < 1:
            raise ValueError(f"retain of unheld page {page}")
        self._ref[page] += 1

    def free(self, pages: List[int]) -> None:
        """Drop one reference per page; a page reaching refcount 0 parks
        in the cached LRU if the prefix index registered it, else returns
        to the free list.  Pages are processed in REVERSE argument order:
        a sequence frees its pages in chain order, so reversing parks the
        chain tail first → LRU eviction reclaims tails before heads, and
        a surviving head keeps its (still-matchable) chain prefix alive
        instead of orphaning unmatchable tail entries."""
        for p in reversed(pages):
            if self._ref.get(p, 0) < 1:
                raise ValueError(f"double/foreign free of page {p}")
            self._ref[p] -= 1
            if self._ref[p] == 0:
                del self._ref[p]
                if p in self._cacheable:
                    self._cached[p] = None
                else:
                    self._free.append(p)

    def mark_cached(self, page: int) -> None:
        """Flag a page's contents as index-registered (prefix-reusable)."""
        self._cacheable.add(page)

    def unmark_cached(self, page: int) -> None:
        self._cacheable.discard(page)
        if page in self._cached:            # no index entry left → free
            del self._cached[page]
            self._free.append(page)


class PrefixIndex:
    """Host-side prefix cache: full immutable pages of prompt tokens,
    keyed by a hash chain, mapped to the pool page holding their K/V.

    The chain key of page ``i`` is a SHA-256 digest chained over the
    parent digest and the page's raw token bytes, so it commits to *all*
    tokens in pages ``0..i`` — a page can only be reused when the entire
    prefix up to and including it matches (collision-proof in practice,
    and deterministic across processes, unlike builtin ``hash``).
    ``match`` retains every returned page (caller must ``free`` them
    through the allocator, like any other held page); at least one token
    is always left unmatched so the last-token logits that seed decoding
    are recomputed.
    """

    WINDOW = 32                                 # admissions per hit window

    def __init__(self, alloc: PageAllocator, page_size: int):
        self.alloc = alloc
        self.page_size = page_size
        self._pages: Dict[bytes, int] = {}      # chain digest → page id
        self._keys: Dict[int, bytes] = {}       # page id → chain digest
        alloc.on_evict = self.drop_page
        self.hit_tokens = 0
        self.lookup_tokens = 0
        # (hit, lookup) token pairs of the most recent admissions — the
        # windowed hit-rate gauge, so a long-lived engine's hit rate
        # tracks the CURRENT traffic mix, not its lifetime average
        self._recent: "deque[tuple]" = deque(maxlen=self.WINDOW)

    def __len__(self) -> int:
        return len(self._pages)

    def chain_keys(self, tokens: np.ndarray) -> List[bytes]:
        """Chain digest for each full page of ``tokens``.  A pure function
        of the (immutable) prompt — callers memoize it per request so a
        head-of-line request blocked on pages doesn't re-hash its whole
        prompt every scheduler tick."""
        ps = self.page_size
        keys: List[bytes] = []
        h = b""
        for i in range(len(tokens) // ps):
            blk = np.asarray(tokens[i * ps:(i + 1) * ps], np.int32).tobytes()
            h = hashlib.sha256(h + blk).digest()
            keys.append(h)
        return keys

    def match(self, tokens: np.ndarray, n_target: Optional[int] = None,
              keys: Optional[List[bytes]] = None) -> List[int]:
        """Longest cached page-chain prefix of ``tokens``, capped so at
        least one of the first ``n_target`` (default ``len(tokens)``)
        tokens remains to prefill.  Every returned page is retained.

        Does NOT touch the hit/lookup counters — the caller commits them
        with ``record`` only when the admission actually goes through, so
        a head-of-line request re-matched every step while blocked on
        pages doesn't inflate the reported hit rate."""
        n_target = len(tokens) if n_target is None else n_target
        cap = max(0, (n_target - 1) // self.page_size)
        if keys is None:
            keys = self.chain_keys(tokens)
        out: List[int] = []
        for i, key in enumerate(keys):
            if i >= cap:
                break
            page = self._pages.get(key)
            if page is None:
                break
            self.alloc.retain(page)
            out.append(page)
        return out

    def record(self, n_hit_pages: int, n_target: int) -> None:
        """Commit one admission's hit/lookup token counts to the stats."""
        self.lookup_tokens += n_target
        self.hit_tokens += n_hit_pages * self.page_size
        self._recent.append((n_hit_pages * self.page_size, n_target))

    def insert(self, tokens: np.ndarray, pages: List[int],
               keys: Optional[List[bytes]] = None) -> int:
        """Register the full-page prefix of ``tokens`` living in
        ``pages`` (a prefilled request's page list).  Pages already
        registered under the same key are skipped (first writer wins).
        Returns the number of newly indexed pages."""
        added = 0
        if keys is None:
            keys = self.chain_keys(tokens)
        for i, key in enumerate(keys):
            if i >= len(pages):
                break
            if key in self._pages:
                continue                    # another request got there first
            page = pages[i]
            if page in self._keys:          # page already backs another key
                continue
            self._pages[key] = page
            self._keys[page] = key
            self.alloc.mark_cached(page)
            added += 1
        return added

    def drop_page(self, page: int) -> None:
        key = self._keys.pop(page, None)
        if key is not None:
            self._pages.pop(key, None)
        self.alloc.unmark_cached(page)

    @property
    def hit_rate(self) -> float:
        return self.hit_tokens / self.lookup_tokens if self.lookup_tokens \
            else 0.0

    @property
    def windowed_hit_rate(self) -> float:
        """Hit rate over the last ``WINDOW`` admissions only."""
        lookup = sum(n for _, n in self._recent)
        return sum(h for h, _ in self._recent) / lookup if lookup else 0.0


def _convert_stage(st: dict, layout) -> dict:
    """Apply a ``quant.kvcache`` layout conversion (``stored_layout`` or
    ``heads_layout``) to a stage's k and v pools with their scale rows."""
    out = {}
    for s in ("k", "v"):
        if f"scale_{s}" in st:
            out[f"pool_{s}"], out[f"scale_{s}"] = layout(
                st[f"pool_{s}"], st[f"scale_{s}"])
        else:
            out[f"pool_{s}"] = layout(st[f"pool_{s}"])
    return out


class PagedKVCache:
    """Device page pools + host page tables for a fixed slot count.

    ``pools`` is the jit-carried pytree (donated through every step): per
    stage the lane-dense ``pool_k/pool_v (L, n_pages, ps, n_kv·Dp)`` and,
    for quantized modes, the ``scale_k/scale_v`` side pools.  ``layers``
    is the same pools per head, for readers outside the steps.  The cache
    holds one of the two forms at a time: reading ``layers`` turns the
    pools into it, and the next read of ``pools`` turns them back (both
    at once do not fit a chip's memory beside a deployment-size pool).
    ``ptab``/``lens`` are numpy, written by the scheduler and uploaded as
    small int arrays each step.  Unassigned table entries stay 0 →
    scratch page.

    ``sanitize=True`` attaches the shadow page ledger (DESIGN.md §12):
    every allocator transition and every ``set_pages``/``set_len``/
    ``copy_page`` call is validated against the page state machine and
    conservation is asserted after each operation.  Host-only overhead;
    the ``Engine`` enables it from ``REPRO_SANITIZE=1`` / ``--sanitize``.
    """

    def __init__(self, cfg, n_slots: int, n_pages: int, page_size: int,
                 max_seq_pages: int, sanitize: bool = False):
        if not supports_paged_cache(cfg):
            raise ValueError(f"arch {cfg.arch!r} has no paged-cache support")
        self.cfg = cfg
        self.n_slots = n_slots
        self.n_pages = n_pages
        self.page_size = page_size
        self.max_seq_pages = min(max_seq_pages, n_pages - 1)
        self._heads = None
        self.pools = init_paged_cache(cfg, n_pages, page_size)["layers"]
        self.alloc = PageAllocator(n_pages)
        self.ptab = np.zeros((n_slots, self.max_seq_pages), np.int32)
        self.lens = np.zeros((n_slots,), np.int32)
        self.ledger = None
        if sanitize:
            from repro.analysis.ledger import attach_ledger
            attach_ledger(self)          # sets self.ledger

    @property
    def pools(self) -> dict:
        if self._pools is None:          # held per head since ``layers``
            self._pools = {name: _convert_stage(st, stored_layout)
                           for name, st in self._heads.items()}
            self._heads = None
        return self._pools

    @pools.setter
    def pools(self, pools: dict) -> None:
        self._pools, self._heads = pools, None

    @property
    def layers(self) -> dict:
        """The pools per head: ``pool_k/pool_v (L, n_pages, ps, n_kv, Dp)``
        and, for quantized modes, ``scale_k/scale_v (L, n_pages, ps,
        n_kv)``, for inspection outside the jitted steps, which carry the
        lane-dense ``pools``.  Built on the first read after a step (a
        copy of the pools, which the cache then drops), so a read between
        steps costs two whole-pool relayouts: only the benchmark's check
        after its window reads it.  It goes, with ``_heads``, once that
        check reads ``pools`` (ROADMAP.md A13)."""
        if self._heads is None:
            to_heads = functools.partial(heads_layout, n_kv=self.cfg.n_kv_p)
            self._heads = {name: _convert_stage(st, to_heads)
                           for name, st in self._pools.items()}
            self._pools = None
        return self._heads

    @property
    def max_seq_tokens(self) -> int:
        return self.max_seq_pages * self.page_size

    def set_pages(self, slot: int, pages: List[int]) -> None:
        row = np.zeros((self.max_seq_pages,), np.int32)
        row[:len(pages)] = pages
        self.ptab[slot] = row

    def set_len(self, slot: int, n: int) -> None:
        self.lens[slot] = n

    def reset_slot(self, slot: int) -> None:
        self.ptab[slot] = 0
        self.lens[slot] = 0

    def copy_page(self, src: int, dst: int) -> None:
        """Copy-on-write support: duplicate one pool page on device (every
        layer stage, k and v — and, for quantized pools, the per-page
        scale rows, which sit in the same layers tree with the page axis
        at position 1 so the tree_map covers them).  Rare — only taken
        when a write would land in a page shared with another sequence.
        Runs jitted with the pool buffers donated, so the copy is
        in-place (no full-pool reallocation; the COW test asserts
        pointer stability and scale carry)."""
        self.pools = _copy_page_jit(self.pools, jnp.int32(src),
                                    jnp.int32(dst))

    def pages_dev(self) -> jnp.ndarray:
        return jnp.asarray(self.ptab)

    def lens_dev(self) -> jnp.ndarray:
        return jnp.asarray(self.lens)

    def mem_bytes(self) -> int:
        """Total cache bytes: every pool leaf across stages (k+v value
        pools AND the quantized modes' scale side pools) plus the host
        page-table/lens buffers mirrored to device each step."""
        return self.pool_bytes() + self.ptab.nbytes + self.lens.nbytes

    def pool_bytes(self) -> int:
        """Device pool bytes only (value + scale pools) — the HBM the
        page budget actually occupies."""
        held = self._heads if self._pools is None else self._pools
        return sum(a.size * a.dtype.itemsize
                   for st in held.values() for a in st.values())

    def kv_bytes_per_token(self) -> float:
        """Pool bytes a single cached token costs across all layer
        stages — value bytes plus (for int8/int4) its f32 scale rows.
        Every pool leaf is (L, n_pages, page_size, ...), so this is just
        the pool total over the token capacity.  The 4x/~7x drop under
        int8/int4 is the ``kv_bytes_per_token`` gauge (DESIGN.md §11)."""
        return self.pool_bytes() / (self.n_pages * self.page_size)
