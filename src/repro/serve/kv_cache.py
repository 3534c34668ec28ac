"""Block/paged KV cache for the continuous-batching engine.

The serving memory system is split into three layers (see docs/serving.md):

  * :class:`PageAllocator` — a host-side free-list over physical page ids.
    Pure Python, no device state; raises :class:`PagePoolExhausted` when a
    request cannot be satisfied.
  * :class:`PageTable`   — host-side slot→page bookkeeping: one row of
    logical-page → physical-page ids per slot (``-1`` = unallocated), grown
    lazily as a slot's sequence crosses page boundaries.
  * :class:`PagedKVCache` — the device arrays (built by
    ``Model.init_paged_cache``) plus a :class:`PageTable`. KV for the
    attention families lives in a shared physical pool of fixed-size pages,
    so HBM scales with *live tokens* across all slots instead of
    ``num_slots × max_seq``. Mamba2 states are O(1) per slot and are
    stored slot-indexed (no paging); they are recycled when a slot is
    evicted (the first prefill chunk of the next occupant resets them).

One extra physical page (the last one, never handed out by the allocator)
serves as a *trash page*: scatter targets for padded prefill positions and
for inactive decode slots are redirected there, so no masking is needed on
the write path.

Automatic prefix caching (docs/serving.md §Prefix caching) rides on top:
pages carry reference counts, every *fully written* prompt page is
indexed by a chained content hash of the token ids it covers (a hash trie
at page granularity), and a newly admitted request whose prompt shares a
page-aligned prefix maps the matching pages into its page table
read-shared instead of recomputing their KV. Unreferenced-but-indexed
pages are parked in an LRU (:class:`PrefixCache`) and reclaimed lazily —
eviction decrefs, it no longer frees.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kv_codebook import CODEBOOK_KEY


class PagePoolExhausted(RuntimeError):
    """Raised when a page allocation cannot be satisfied.

    Carries a human-readable account of the pool state so serving errors
    surface as capacity problems, not shape errors deep inside jit.
    """


class PageAllocator:
    """Ref-counted free-list allocator over ``num_pages`` physical page ids.

    Pages are plain ints in ``[0, num_pages)``. ``alloc`` is all-or-nothing:
    it either returns exactly ``n`` page ids (each with refcount 1) or
    raises :class:`PagePoolExhausted` without allocating anything.

    Reference counting supports shared-prefix page reuse: a page mapped
    into several page-table rows holds one reference per row.  ``free``
    is a decref — the page returns to the free list only when the last
    reference drops, and dropping a reference a page does not hold is a
    hard error (double-free), never a silent corruption.

    A page can also be *checked out* with refcount 0: the prefix cache
    parks unreferenced-but-still-indexed pages outside the free list
    (their KV content stays valid for future prefix hits) and hands them
    back via :meth:`restore` when reclaimed, or re-activates them via
    :meth:`revive` on a prefix hit.
    """

    def __init__(self, num_pages: int):
        if num_pages <= 0:
            raise ValueError(f"num_pages must be positive, got {num_pages}")
        self.num_pages = num_pages
        # pop() from the tail → pages are handed out in ascending id order.
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self._ref: List[int] = [0] * num_pages

    @property
    def available(self) -> int:
        """Number of pages currently free."""
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.num_pages - len(self._free)

    def _check(self, p: int) -> None:
        if not (0 <= p < self.num_pages):
            raise ValueError(f"invalid page id {p}")

    def refcount(self, p: int) -> int:
        self._check(p)
        return self._ref[p]

    def alloc(self, n: int) -> List[int]:
        """Allocate ``n`` pages (refcount 1 each); raises if short."""
        if n > len(self._free):
            raise PagePoolExhausted(
                f"requested {n} page(s) but only {self.available} of "
                f"{self.num_pages} are free")
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._ref[p] = 1
        return out

    def incref(self, p: int) -> int:
        """Add a reference to a live page (refcount must be >= 1)."""
        self._check(p)
        if self._ref[p] <= 0:
            raise ValueError(
                f"incref on page {p} with refcount {self._ref[p]} "
                f"(revive() is the path for parked cached pages)")
        self._ref[p] += 1
        return self._ref[p]

    def decref(self, p: int) -> int:
        """Drop one reference; returns the new count. Does NOT free —
        the caller decides between the free list and the prefix-cache LRU
        when the count reaches zero. Refcount 0 pages raise (double-free).
        """
        self._check(p)
        if self._ref[p] <= 0:
            raise ValueError(
                f"double-free: page {p} has refcount {self._ref[p]}")
        self._ref[p] -= 1
        return self._ref[p]

    def free(self, pages: List[int]) -> None:
        """Decref each page; a page whose last reference drops returns to
        the free list (exactly once — a second free raises)."""
        for p in pages:
            if self.decref(p) == 0:
                self._free.append(p)

    def revive(self, p: int) -> None:
        """Re-activate a parked refcount-0 page (prefix-cache hit): the
        page is NOT on the free list; it simply gains its first
        reference again."""
        self._check(p)
        if self._ref[p] != 0:
            raise ValueError(
                f"revive on page {p} with refcount {self._ref[p]}")
        self._ref[p] = 1

    def restore(self, p: int) -> None:
        """Return a parked refcount-0 page to the free list (the prefix
        cache reclaimed it — its cached content is dropped)."""
        self._check(p)
        if self._ref[p] != 0:
            raise ValueError(
                f"restore on page {p} with refcount {self._ref[p]}")
        self._free.append(p)


def _chunk_keys(tokens, page_size: int,
                salt: int = 0) -> List[Tuple[int, tuple]]:
    """Chained content keys of ``tokens`` at page granularity.

    Key ``i`` is ``(hash(key_{i-1}), chunk_i_token_tuple)`` and covers
    tokens ``[0, (i+1)*page_size)`` — the chain makes a page identify its
    *entire prefix* (KV at position p depends on every token <= p). The
    current chunk's actual token ids sit in the key, so a lookup compares
    the page's own tokens exactly; ancestry, however, is carried by the
    chained 64-bit parent hash, so a cross-prefix false match still needs
    a ``hash()`` collision between two *parent* chains (~2^-64 per pair —
    negligible by accident, though not cryptographically hard). Only full
    pages are keyed; the tail remainder is ignored.

    ``salt`` seeds the chain. A vector-quantized pool stores codebook
    INDICES, which are only comparable under the codebook that produced
    them — seeding with the codebook fingerprint makes pages written
    under different codebooks (or a quantized vs an fp pool) live in
    disjoint key spaces, so they can never alias.
    """
    out: List[Tuple[int, tuple]] = []
    h = salt
    for i in range(len(tokens) // page_size):
        key = (h, tuple(tokens[i * page_size:(i + 1) * page_size]))
        out.append(key)
        h = hash(key)
    return out


@dataclasses.dataclass
class PrefixMatch:
    """Reuse plan for one prompt against the prefix index.

    ``pages`` are mapped read-shared into the new slot's table; ``tokens``
    prompt tokens skip prefill. ``cow_page`` is set when the whole prompt
    is covered by indexed pages: the final prompt token must still run
    prefill (its logits seed decode) and its KV write would land inside
    the last shared page — that page is copy-on-write forked instead.
    """
    tokens: int = 0
    pages: List[int] = dataclasses.field(default_factory=list)
    cow_page: Optional[int] = None

    @property
    def reused_pages(self) -> int:
        return len(self.pages)


class PrefixCache:
    """Content-keyed index over full KV pages + LRU of unreferenced pages.

    ``(parent_hash, chunk_tokens) -> page`` lookups drive prefix
    matching (exact tuple comparison — see :func:`_chunk_keys`); the LRU
    keeps pages whose refcount dropped to zero ("recently freed") out of
    the free list so their content can still be shared, and surrenders
    the oldest ones when the allocator runs dry.
    """

    def __init__(self, page_size: int):
        self.page_size = page_size
        self._page_of: Dict[Tuple[int, tuple], int] = {}   # key -> page id
        self._key_of: Dict[int, Tuple[int, tuple]] = {}    # page id -> key
        self._lru: "OrderedDict[int, None]" = OrderedDict()

    def lookup(self, key: Tuple[int, tuple]) -> Optional[int]:
        return self._page_of.get(key)

    def is_registered(self, page: int) -> bool:
        return page in self._key_of

    def register(self, key: Tuple[int, tuple], page: int) -> None:
        if key in self._page_of or page in self._key_of:
            raise ValueError(f"page {page} / key already registered")
        self._page_of[key] = page
        self._key_of[page] = key

    def unregister(self, page: int) -> None:
        """Drop a page's index entry (and LRU membership, if parked)."""
        key = self._key_of.pop(page, None)
        if key is not None:
            self._page_of.pop(key, None)
        self._lru.pop(page, None)

    def park(self, page: int) -> None:
        """An indexed page lost its last reference: keep it (LRU)."""
        self._lru[page] = None
        self._lru.move_to_end(page)

    def unpark(self, page: int) -> None:
        """An indexed parked page regained a reference."""
        self._lru.pop(page, None)

    def pop_lru(self) -> int:
        """Reclaim the least-recently-parked page (drops its index entry)."""
        page = next(iter(self._lru))
        self.unregister(page)
        return page

    @property
    def reclaimable(self) -> int:
        """Parked pages the allocator may reclaim under pressure."""
        return len(self._lru)


class PageTable:
    """Host-side slot → physical-page mapping.

    Row ``s`` maps slot ``s``'s logical pages (token positions
    ``[i*page_size, (i+1)*page_size)``) to physical page ids; ``-1`` marks
    an unallocated logical page. The device copy is cached and invalidated
    on every mutation (allocation happens a few times per request, not per
    token, so the host→device transfers are rare and tiny).
    """

    def __init__(self, num_slots: int, max_seq: int, page_size: int,
                 num_pages: Optional[int] = None,
                 prefix_cache: bool = True, content_salt: int = 0):
        if max_seq % page_size:
            raise ValueError(
                f"max_seq ({max_seq}) must be a multiple of page_size "
                f"({page_size})")
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.page_size = page_size
        self.pages_per_slot = max_seq // page_size
        if num_pages is None:
            num_pages = num_slots * self.pages_per_slot
        self.allocator = PageAllocator(num_pages)
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(page_size) if prefix_cache else None)
        self.table = np.full((num_slots, self.pages_per_slot), -1, np.int32)
        self._slot_pages: List[List[int]] = [[] for _ in range(num_slots)]
        # seed of the content-hash chain (see _chunk_keys): pages written
        # under different pool encodings must never alias
        self.content_salt = content_salt
        # bytes per physical page across k+v and all layers — set by
        # PagedKVCache from the actual device arrays; 0 = unknown (bare
        # PageTable use in tests)
        self.page_bytes = 0
        # per-slot registration cursor: (full pages hashed, chain hash) —
        # lets register_prefix resume mid-prompt instead of rehashing the
        # whole prefix on every prefill chunk
        self._reg_state: List[Tuple[int, int]] = [
            (0, content_salt)] * num_slots
        self._dev: Optional[jnp.ndarray] = None

    # -- capacity queries ---------------------------------------------------
    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` tokens."""
        return max(1, math.ceil(n_tokens / self.page_size))

    @property
    def available_pages(self) -> int:
        """Free pages plus parked cached pages (reclaimable on demand)."""
        extra = self.prefix.reclaimable if self.prefix is not None else 0
        return self.allocator.available + extra

    @property
    def pressure(self) -> float:
        """Pool pressure in ``[0, 1]``: the fraction of physical pages
        that could NOT be handed to a new allocation right now (live
        slot-referenced pages; parked prefix pages are reclaimable on
        demand and count as capacity). 1.0 = the pool cannot grow any
        sequence without preempting. The engine's degradation ladder
        (docs/robustness.md) steps on this signal."""
        return 1.0 - self.available_pages / self.allocator.num_pages

    def occupancy(self) -> str:
        """One-line pool accounting for capacity-error messages and
        preemption logs: live (slot-referenced), cached-parked (prefix
        LRU, reclaimable), and free pages — with the byte sizes behind
        them when :attr:`page_bytes` is known (pages of a quantized pool
        are 4-16x smaller than fp pages; page counts alone no longer
        describe HBM use)."""
        pages = (f"pool: {self.live_pages} live, "
                 f"{self.prefix.reclaimable if self.prefix else 0} "
                 f"cached-parked, {self.allocator.available} free of "
                 f"{self.allocator.num_pages} pages "
                 f"({self.page_size} tokens each)")
        if not self.page_bytes:
            return pages
        mib = self.page_bytes / (1 << 20)
        return (f"{pages}; {self.live_pages * mib:.2f} MiB live of "
                f"{self.allocator.num_pages * mib:.2f} MiB "
                f"({self.page_bytes} B/page)")

    def can_fit(self, n_tokens: int,
                match: Optional[PrefixMatch] = None) -> bool:
        """Whether ``n_tokens`` tokens' pages could be allocated now.

        With a ``match``, only the UNSHARED pages count against capacity
        — matched pages are mapped by reference — but matched pages that
        are currently parked stop being reclaimable once adopted, so they
        are deducted from the available side."""
        need = self.pages_for(n_tokens)
        avail = self.available_pages
        if match is not None and self.prefix is not None:
            need -= match.reused_pages
            parked = self.prefix._lru
            cand = match.pages + (
                [match.cow_page] if match.cow_page is not None else [])
            avail -= sum(1 for p in cand if p in parked)
        return need <= avail

    def check_admissible(self, n_tokens: int) -> None:
        """Raise if a request of ``n_tokens`` could NEVER be served.

        Catches both per-slot overflow (prompt longer than ``max_seq``) and
        pool overflow (prompt needs more pages than exist), so impossible
        requests fail loudly instead of deadlocking the admission queue.
        """
        if n_tokens > self.max_seq:
            raise PagePoolExhausted(
                f"request of {n_tokens} tokens exceeds max_seq="
                f"{self.max_seq} (pages_per_slot={self.pages_per_slot})")
        if self.pages_for(n_tokens) > self.allocator.num_pages:
            raise PagePoolExhausted(
                f"request of {n_tokens} tokens needs "
                f"{self.pages_for(n_tokens)} pages but the pool only has "
                f"{self.allocator.num_pages} ({self.occupancy()})")

    # -- mutation -----------------------------------------------------------
    def _alloc(self, n: int) -> List[int]:
        """Allocate ``n`` fresh pages, reclaiming parked cached pages
        (oldest first) when the free list runs short."""
        if self.prefix is not None:
            while (self.allocator.available < n
                   and self.prefix.reclaimable):
                self.allocator.restore(self.prefix.pop_lru())
        try:
            return self.allocator.alloc(n)
        except PagePoolExhausted as e:
            # re-raise with the pool accounting attached so capacity
            # failures are debuggable from the message alone
            raise PagePoolExhausted(f"{e} ({self.occupancy()})") from None

    def _retain(self, page: int) -> None:
        """Take a reference on a cached page: parked pages are revived
        out of the LRU, live pages are increfed."""
        if self.allocator.refcount(page) == 0:
            self.prefix.unpark(page)
            self.allocator.revive(page)
        else:
            self.allocator.incref(page)

    def _release_page(self, page: int) -> None:
        """Drop one reference; an unreferenced page is parked in the
        prefix LRU when indexed (content stays shareable) and returned
        to the free list otherwise."""
        if self.allocator.decref(page) == 0:
            if self.prefix is not None and self.prefix.is_registered(page):
                self.prefix.park(page)
            else:
                self.allocator.restore(page)

    def ensure(self, slot: int, n_tokens: int) -> None:
        """Grow slot ``slot`` to cover token positions ``[0, n_tokens)``.

        Allocates the missing logical pages (all-or-nothing); raises
        :class:`PagePoolExhausted` when the pool cannot supply them — the
        caller decides whether to wait, or preempt a slot.
        """
        need = self.pages_for(n_tokens)
        if need > self.pages_per_slot:
            raise PagePoolExhausted(
                f"slot {slot}: {n_tokens} tokens exceed max_seq="
                f"{self.max_seq} ({self.occupancy()})")
        have = len(self._slot_pages[slot])
        if need <= have:
            return
        new = self._alloc(need - have)
        for i, p in enumerate(new):
            self.table[slot, have + i] = p
        self._slot_pages[slot].extend(new)
        self._dev = None

    def release(self, slot: int) -> None:
        """Evict a slot: decref its pages, clear its row. Pages still
        referenced by another slot stay live; unreferenced indexed pages
        are parked for future prefix hits; the rest return to the pool."""
        if self._slot_pages[slot]:
            for p in self._slot_pages[slot]:
                self._release_page(p)
            self._slot_pages[slot] = []
            self.table[slot, :] = -1
            self._dev = None
        self._reg_state[slot] = (0, self.content_salt)

    def trim(self, slot: int, n_tokens: int) -> int:
        """Shrink a slot to the pages covering ``n_tokens`` tokens
        (speculative-decoding rollback: rejected draft rows beyond the
        accepted position may leave whole tail pages unused).

        Only pages wholly ABOVE the keep mark are dropped, each via
        :meth:`_release_page` — a page another slot still references
        merely loses this slot's reference, and an indexed page parks in
        the prefix LRU. In practice a draft tail page is always a fresh
        refcount-1 allocation: shared prefix pages sit below ``slot.pos``
        (prefill never rolls back), which the rollback property tests
        assert. Returns the number of pages dropped from the row."""
        keep = 0 if n_tokens <= 0 else self.pages_for(n_tokens)
        row = self._slot_pages[slot]
        if len(row) <= keep:
            return 0
        dropped = row[keep:]
        del row[keep:]
        for p in dropped:
            self._release_page(p)
        self.table[slot, keep:] = -1
        self._dev = None
        return len(dropped)

    # -- prefix caching -----------------------------------------------------
    def match_prefix(self, tokens) -> PrefixMatch:
        """Plan (read-only) the longest page-aligned prefix reuse for a
        prompt: consecutive indexed pages from position 0. The final
        prompt token always runs prefill — a full-prompt match converts
        its last page into a copy-on-write fork (see :class:`PrefixMatch`).
        """
        m = PrefixMatch()
        if self.prefix is None or len(tokens) <= 1:
            return m
        for key in _chunk_keys(tokens, self.page_size, self.content_salt):
            page = self.prefix.lookup(key)
            if page is None:
                break
            m.pages.append(page)
        m.tokens = len(m.pages) * self.page_size
        if m.pages and m.tokens >= len(tokens):
            m.cow_page = m.pages.pop()
            m.tokens = len(tokens) - 1
        return m

    def adopt_prefix(self, slot: int,
                     match: PrefixMatch) -> Optional[Tuple[int, int]]:
        """Map a :class:`PrefixMatch` into an empty slot row.

        Matched pages are increfed (revived out of the LRU when parked)
        and written into the row read-shared. A ``cow_page`` is forked:
        a fresh page is allocated in its place and ``(src, dst)`` is
        returned so the caller can copy the donor page's KV device-side;
        the donor keeps its index entry and loses only the transient
        reference. Raises :class:`PagePoolExhausted` (after rolling the
        row back) if the fork cannot be allocated."""
        if not match.pages and match.cow_page is None:
            return None
        assert not self._slot_pages[slot], \
            f"adopt_prefix on non-empty slot {slot}"
        row = self._slot_pages[slot]
        for p in match.pages:
            self._retain(p)
            self.table[slot, len(row)] = p
            row.append(p)
        pair = None
        if match.cow_page is not None:
            src = match.cow_page
            self._retain(src)        # pin: _alloc's reclaim must not take it
            try:
                dst = self._alloc(1)[0]
            except PagePoolExhausted:
                self._release_page(src)
                self.release(slot)   # roll back the shared mappings
                raise
            self.table[slot, len(row)] = dst
            row.append(dst)
            self._release_page(src)  # unpin (back to the LRU if unshared)
            pair = (src, dst)
        self._dev = None
        return pair

    def register_prefix(self, slot: int, tokens, n_covered: int) -> None:
        """Index the slot's fully written prompt pages by content key.

        ``n_covered`` tokens of ``tokens`` have complete KV (prefill
        progress); every full page below that mark becomes shareable.
        First writer wins: keys already indexed (including by this very
        slot's shared pages) are skipped. Incremental: the per-slot
        cursor resumes the hash chain where the previous chunk left it,
        so a whole prompt is hashed exactly once."""
        if self.prefix is None:
            return
        ps = self.page_size
        n_full = min(n_covered, len(tokens)) // ps
        done, h = self._reg_state[slot]
        if n_full <= done:
            return
        row = self._slot_pages[slot]
        for i in range(done, n_full):
            key = (h, tuple(tokens[i * ps:(i + 1) * ps]))
            h = hash(key)
            if self.prefix.lookup(key) is None \
                    and not self.prefix.is_registered(row[i]):
                self.prefix.register(key, row[i])
        self._reg_state[slot] = (n_full, h)

    # -- device view --------------------------------------------------------
    def device(self, sharding=None) -> jnp.ndarray:
        """(num_slots, pages_per_slot) int32 device copy (cached).

        ``sharding``: optional placement for the copy — the sharded serving
        engine passes a replicated ``NamedSharding`` so the table lands on
        every mesh device without a resharding step inside jit."""
        if self._dev is None:
            self._dev = (jnp.asarray(self.table) if sharding is None
                         else jax.device_put(self.table, sharding))
        return self._dev

    @property
    def live_pages(self) -> int:
        """Pages referenced by at least one slot. Parked cached pages
        (refcount 0, held only by the prefix LRU) are logically free
        capacity and are not counted."""
        parked = self.prefix.reclaimable if self.prefix is not None else 0
        return self.allocator.in_use - parked

    @property
    def cached_pages(self) -> int:
        """Pages currently indexed by the prefix cache (live + parked)."""
        return len(self.prefix._key_of) if self.prefix is not None else 0


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page(data: Dict[str, jax.Array], src, dst) -> Dict[str, jax.Array]:
    """Copy one physical page's K/V rows (CoW fork). ``src``/``dst`` are
    traced scalars, so every fork reuses one compiled executable.

    Callers must pass ONLY the page-pool leaves (``{"k", "v"}`` or
    ``{"latent"}``) — the
    axis-1 copy is meaningless for anything else (a quantized cache's
    codebook tables, say), and would silently corrupt it."""
    return jax.tree_util.tree_map(
        lambda t: t.at[:, dst].set(t[:, src]), data)


class PagedKVCache:
    """Device cache arrays + page table for one serving engine instance.

    ``data`` is the pytree returned by ``Model.init_paged_cache``:

      * attention families: ``{"k": (L, P+1, KVH, page, HD), "v": ...}``
        where ``P`` is the physical pool size and the final page is the
        trash page (see module docstring); latent attention keeps one
        ``{"latent": (L, P+1, page, W)}`` pool under the same table.
      * ssm: ``{"conv": (L, slots, K-1, C), "h": (L, slots, H, HP, N)}`` —
        slot-indexed recurrent state, recycled on eviction.
      * hybrid: ``{"mamba": {...}, "attn": {"k": (n_inv, slots, T, KVH,
        HD), ...}}`` — the handful of shared-attention invocations keep a
        slot-dense cache (documented trade-off in docs/serving.md).

    The engine passes ``data`` and ``table.device()`` into jitted
    prefill/decode functions and stores the updated ``data`` back.
    """

    def __init__(self, model, num_slots: int, max_seq: int,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 dtype=None, prefix_cache: bool = True, codebook=None):
        from repro.models.model import ATTN_FAMILIES
        self.cfg = model.cfg
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.page_size = page_size
        self.paged = model.cfg.family in ATTN_FAMILIES
        self.codebook = codebook
        # Prefix reuse needs *paged* state: Mamba2 / hybrid recurrent
        # state is a single evolving tensor per slot — there is no
        # page-granular unit of it to share, so those families always
        # report a zero reusable prefix (match_prefix below).
        # A quantized pool salts the content-hash chain with the codebook
        # fingerprint: its pages hold codes, not rows, and codes from
        # different codebooks must never satisfy each other's lookups.
        salt = codebook.fingerprint() if codebook is not None else 0
        self.table = PageTable(num_slots, max_seq, page_size, num_pages,
                               prefix_cache=prefix_cache and self.paged,
                               content_salt=salt)
        self.data: Dict[str, Any] = model.init_paged_cache(
            num_slots, max_seq, page_size,
            num_pages=self.table.allocator.num_pages, dtype=dtype,
            codebook=codebook)
        self.cow_forks = 0
        # set by Engine: CoW forks annotate the owning replica's trace
        # track (docs/observability.md); None outside an engine
        self.obs = None
        if self.paged:
            self.table.page_bytes = self.page_bytes

    # -- byte accounting ----------------------------------------------------
    @property
    def bytes_per_token(self) -> int:
        """HBM bytes ONE cached token occupies across k+v and all layers
        — computed from the actual pool arrays, so it reflects the pool
        encoding (fp rows vs uint8 codes) and dtype automatically."""
        if not self.paged:
            return 0
        total = 0
        for key in self._page_keys():
            t = self.data[key]          # (L, P+1, [KVH,] page, W)
            total += (t.size // (t.shape[1] * t.shape[-2])
                      * t.dtype.itemsize)
        return total

    def _page_keys(self):
        """The pool leaves with a page axis (a quantized pool also holds
        its codebook, which has none)."""
        return [key for key in self.data if key != CODEBOOK_KEY]

    @property
    def page_bytes(self) -> int:
        """Bytes one physical page pins across k+v and all layers."""
        return self.bytes_per_token * self.page_size

    @property
    def pool_bytes(self) -> int:
        """Total allocatable pool capacity in bytes (trash page
        excluded — it is never handed out)."""
        return self.page_bytes * self.table.allocator.num_pages \
            if self.paged else 0

    @property
    def live_bytes(self) -> int:
        """Bytes pinned by slot-referenced pages right now."""
        return self.page_bytes * self.table.live_pages \
            if self.paged else 0

    # Paging only applies to the attention families; ssm/hybrid slots hold
    # constant-size state, so capacity checks are trivially true there.
    def pages_for(self, n: int) -> int:
        return self.table.pages_for(n) if self.paged else 0

    def can_fit(self, n_tokens: int,
                match: Optional[PrefixMatch] = None) -> bool:
        return self.table.can_fit(n_tokens, match) if self.paged else True

    def check_admissible(self, n_tokens: int) -> None:
        if n_tokens > self.max_seq:
            raise PagePoolExhausted(
                f"request of {n_tokens} tokens exceeds max_seq="
                f"{self.max_seq}")
        if self.paged:
            self.table.check_admissible(n_tokens)

    def ensure(self, slot: int, n_tokens: int) -> None:
        if self.paged:
            self.table.ensure(slot, n_tokens)

    def release(self, slot: int) -> None:
        if self.paged:
            self.table.release(slot)

    def trim(self, slot: int, n_tokens: int) -> int:
        """Speculative rollback: drop tail pages past ``n_tokens``."""
        return self.table.trim(slot, n_tokens) if self.paged else 0

    def occupancy(self) -> str:
        return self.table.occupancy() if self.paged else \
            f"slot-dense cache ({self.num_slots} slots)"

    @property
    def pressure(self) -> float:
        """Pool pressure in ``[0, 1]`` (see :meth:`PageTable.pressure`).
        Non-paged families (ssm / hybrid recurrent state) hold O(1)
        state per slot — capacity pressure is a slot-count question the
        scheduler already answers, so they report 0.0 here."""
        return self.table.pressure if self.paged else 0.0

    # -- prefix caching -----------------------------------------------------
    def match_prefix(self, tokens) -> PrefixMatch:
        """Longest reusable page-aligned prefix for ``tokens`` (read-only
        probe; also the router's affinity metric). Non-paged families
        (ssm/hybrid recurrent state) always report zero reuse."""
        if not self.paged:
            return PrefixMatch()
        return self.table.match_prefix(tokens)

    def adopt_prefix(self, slot: int, match: PrefixMatch) -> int:
        """Map a match into ``slot`` and perform the device-side CoW copy
        when the plan forked a page. Returns the tokens covered."""
        if not self.paged or (not match.pages and match.cow_page is None):
            return 0
        pair = self.table.adopt_prefix(slot, match)
        if pair is not None:
            src, dst = pair
            # page leaves only: a quantized cache also carries the
            # codebook pytree, which has no page axis to copy along
            pages = {key: self.data[key] for key in self._page_keys()}
            copied = _copy_page(pages, jnp.int32(src), jnp.int32(dst))
            self.data = {**self.data, **copied}
            self.cow_forks += 1
            if self.obs is not None:
                self.obs.annotate("cow_fork", slot=slot, src=int(src),
                                  dst=int(dst))
        return match.tokens

    def register_prefix(self, slot: int, tokens, n_covered: int) -> None:
        if self.paged:
            self.table.register_prefix(slot, tokens, n_covered)

    def table_device(self, sharding=None) -> jnp.ndarray:
        return self.table.device(sharding)

    @property
    def live_pages(self) -> int:
        return self.table.live_pages if self.paged else 0

    @property
    def cached_pages(self) -> int:
        return self.table.cached_pages if self.paged else 0
