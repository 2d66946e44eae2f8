"""Paged KV cache manager: a refcounted page pool with prefix sharing and
copy-on-write.

Device state (``Model.init_paged_cache``): head-major k/v page pools
(L, num_pages, KV, page, hd), a block table (B, max_blocks) int32 and
per-slot lengths (B,) int32.  The manager owns the host mirrors, the page
FREE LIST and the per-page REFCOUNTS; page 0 is the reserved NULL page —
never allocated, the landing zone for inactive slots' appends and
unallocated table entries (so the Pallas kernel's scalar-prefetched DMA
address is always valid).

Prefix sharing maps ONE physical page into SEVERAL block tables
(``share()``): a request admitted with a prompt prefix already resident in
a live slot's pages references those pages instead of recomputing them —
rope positions are request-relative in the paged decode path, so the K/V
rows for an identical token prefix are bit-identical across slots and the
reference is exact, not approximate.  Pages referenced more than once are
IMMUTABLE: before any slot may append into a page with refcount > 1 the
scheduler reserves a copy-on-write (``cow_reserve``: fresh page allocated,
table rewired, copy queued) and the tick's reservations are flushed in ONE
batched donated device dispatch (``cow_flush`` — a tick privatizing N pages
issues one copy call whose census bytes are exactly N x page_bytes,
standalone and in-fusion), rewiring only the writing slot's table entries.
Eviction decrements refcounts; a page returns to the free list only when
its refcount reaches zero, so evicting a sharer never frees a page another
slot still references.

CROSS-LIFETIME RETENTION (``retain=True``): when a slot is freed its
page-aligned token-prefix pages are not returned to the free list but
moved to a RETAINED pool — refcount 0 (no block table references them),
content frozen, keyed by the same rolling-hash prefix digests the
engine's live-donor index uses (``prefix_digests``).  A later admission
whose prompt starts with the same tokens adopts those pages by reference
(``match_retained`` / ``adopt_retained``) even though the donor is long
gone — request-relative rope makes the frozen K/V rows exact for any
adopter.  Retained pages are reclaimable BY DEFINITION: the allocation
choke point (``_alloc_page``, used by ``ensure``/``cow_reserve``/
``seize_pages``) lazily reclaims them under pool pressure (LRU or
digest-popularity order, ``retain_policy``), so the scheduler's reserve
path drains the retained pool before it ever stalls or preempts, and a
fault-plan squeeze can seize straight through it.

Invariants (``check()``, fuzz-asserted by the property harness): every
page's refcount equals the number of block-table references to it; the
null page plus every referenced page plus the free list plus the seized
set plus the retained-only pages cover [0, num_pages) exactly — no page
is ever double-allocated, leaked, or freed while referenced or retained.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Dict, Iterable, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model

# rolling-hash prefix digests — shared with the engine's live-donor index
# (serve/engine.py) so a retained entry and a live slot hash identically
_HASH_MUL = 1_000_003
_HASH_MOD = (1 << 61) - 1


def digest_step(h: int, tok: int) -> int:
    """One token step of the rolling prefix digest (+1 biases token 0)."""
    return (h * _HASH_MUL + int(tok) + 1) % _HASH_MOD


def prefix_digests(tokens, page: int) -> List[int]:
    """Digest of every PAGE-ALIGNED prefix of ``tokens``: out[j] covers
    tokens[:(j+1)*page].  Only full pages are digested — retention is
    page-granular (a partial trailing page is recomputed by the adopter)."""
    out: List[int] = []
    h = 0
    for idx, t in enumerate(tokens):
        h = digest_step(h, t)
        if (idx + 1) % page == 0:
            out.append(h)
    return out


@dataclasses.dataclass
class RetainedPrefix:
    """A dead slot's page-aligned prompt prefix, held for re-sharing.

    ``tokens`` is the exact token prefix (len == page * len(pages)),
    ``pages`` the physical pages that hold its K/V rows, ``keys`` the
    (n_pages, digest) lookup keys this entry is registered under (one per
    page boundary, so a shorter prompt can still hit a longer entry).
    ``stamp`` is the retention clock at last touch (LRU), ``hits`` the
    number of adoptions (digest popularity)."""
    tokens: List[int]
    pages: List[int]
    keys: List[Tuple[int, int]]
    stamp: int
    hits: int = 0


def _copy_pages(pool, dst, src):
    """Device page copy on a stacked (L, num_pages, ...) pool: pages
    ``src`` are written into pages ``dst`` (both (n,) int32).  Jitted with
    a donated pool so the copy is in place — the HLO is a gather + scatter
    of (L x n) page tiles whose census bytes scale with the pages copied,
    never with the pool.  Every (layer, page) pair is indexed explicitly
    so both indexed dims are the major ones: a plain ``pool[:, src]``
    scatter on the page axis is lowered with whole-pool transposes
    around it, which moves the whole pool per copy."""
    layers = jnp.arange(pool.shape[0], dtype=jnp.int32)[:, None]
    return pool.at[layers, dst[None, :]].set(pool[layers, src[None, :]])


def _copy_pages_both(k, v, dst, src):
    """COW copies k AND v in one dispatch (both pools donated)."""
    return _copy_pages(k, dst, src), _copy_pages(v, dst, src)


def _copy_pages_quant(k, v, ks, vs, dst, src):
    """Quantized COW: k, v AND their per-row scale pools move in the same
    single dispatch — int8 rows + f32 scales are copied verbatim, so the
    privatized page is bit-exact (no requantization on the copy path)."""
    return (_copy_pages(k, dst, src), _copy_pages(v, dst, src),
            _copy_pages(ks, dst, src), _copy_pages(vs, dst, src))


class PagedKVCache:
    """Host-side manager for the paged decode cache (see module docstring)."""

    RETAIN_POLICIES = ("lru", "popularity")

    def __init__(self, model: Model, max_batch: int, max_seq: int, *,
                 page_size: int = 16, max_blocks: int = 0,
                 num_pages: int = 0, retain: bool = False,
                 retain_cap: int = 0, retain_policy: str = "lru"):
        if retain_policy not in self.RETAIN_POLICIES:
            raise ValueError(f"unknown retain_policy {retain_policy!r}; "
                             f"expected one of {self.RETAIN_POLICIES}")
        self.page = page_size
        self.max_blocks = max_blocks or -(-max_seq // page_size)
        # default pool: every slot can hold its full table + the null page
        self.num_pages = num_pages or (max_batch * self.max_blocks + 1)
        self.B = max_batch
        arrays = model.init_paged_cache(max_batch, self.max_blocks,
                                        self.page, self.num_pages)
        self.k = arrays["k"]
        self.v = arrays["v"]
        # quantized pools (cfg.kv_dtype == "int8") carry per-row-per-head
        # f32 scale pools that travel WITH their pages through every copy
        self.k_scale = arrays.get("k_scale")
        self.v_scale = arrays.get("v_scale")
        self.quantized = self.k_scale is not None
        self.table = np.zeros((max_batch, self.max_blocks), np.int32)
        self.length = np.zeros((max_batch,), np.int32)
        self.owned: List[List[int]] = [[] for _ in range(max_batch)]
        self.refcount = np.zeros((self.num_pages,), np.int32)
        self.free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._gather = jax.jit(lambda pool, perm: pool[:, perm],
                               donate_argnums=(0,))
        self._copy = jax.jit(_copy_pages_both, donate_argnums=(0, 1))
        self._copy_quant = jax.jit(_copy_pages_quant,
                                   donate_argnums=(0, 1, 2, 3))
        # per-page bytes across BOTH pools (+ scale pools when quantized) —
        # derived from the ACTUAL pool itemsize so census byte gates stay
        # exact for any kv_dtype
        self.page_bytes = 2 * self.k.nbytes // self.num_pages
        if self.quantized:
            self.page_bytes += 2 * self.k_scale.nbytes // self.num_pages
        self.cow_copies = 0
        self.cow_bytes = 0
        self.cow_dispatches = 0          # device copy calls (1 per flush)
        self.shared_pages = 0            # share() page references handed out
        # (dst, src, slot, blk) page pairs reserved by cow_reserve()
        # awaiting the one batched device copy of the tick (cow_flush);
        # slot/blk tag each pair so a reservation can be rolled back
        # (cow_rollback) or cancelled when its slot is freed mid-tick
        # (free_slot) without orphaning a pending copy into a free page
        self._pending_cow: List[Tuple[int, int, int, int]] = []
        # pages temporarily seized from the free list by the fault-
        # injection harness (serve/faults.py pool-pressure events): not
        # allocatable, not referenced — check() accounts for them so the
        # pool partition invariant survives injected pressure
        self.seized: Set[int] = set()
        # slot rows whose table/length changed since the engine last synced
        # its device mirrors (admission, COW, eviction, defrag mark these;
        # the engine uploads ONLY these rows, then clears the set)
        self.dirty: Set[int] = set(range(max_batch))
        # -- cross-lifetime retention (see module docstring) ---------------
        self.retain = retain
        self.retain_cap = retain_cap          # max retained-ONLY pages; 0 = pool-bounded
        self.retain_policy = retain_policy
        self.retained: List[RetainedPrefix] = []
        # (n_pages, digest) -> entries registered under that page boundary;
        # several entries can share a key (same prefix, different lengths)
        self._retained_keys: Dict[Tuple[int, int], List[RetainedPrefix]] = {}
        # per-page count of RetainedPrefix entries holding the page — a
        # page leaves the free-list candidate set while this OR refcount
        # is nonzero (overlapping entries may retain the same page)
        self.retained_refs = np.zeros((self.num_pages,), np.int32)
        self._retain_clock = 0
        self.retained_hits = 0               # adopt_retained() calls
        self.retained_hit_tokens = 0         # tokens re-shared from retained
        self.retained_reclaimed_pages = 0    # pages freed under pressure
        self.retained_dropped = 0            # entries reclaimed/flushed

    # -- allocation ----------------------------------------------------------

    def _alloc_page(self) -> Optional[int]:
        """THE allocation choke point: pop a free page, lazily reclaiming
        retained entries (policy order) when the free list is dry.  Every
        reserve-path primitive (``ensure``, ``cow_reserve``,
        ``seize_pages``) allocates through here, so retained pages are
        exactly as allocatable as free pages — the scheduler never stalls
        or preempts while the retained pool could still be drained."""
        if not self.free and self.retained:
            self.reclaim_retained(1)
        return self.free.pop() if self.free else None

    def ensure(self, i: int, n_tokens: int) -> bool:
        """Allocate pages so slot ``i`` can hold ``n_tokens`` tokens.
        Returns False (allocating nothing further) if the free list runs
        dry — the scheduler then grants the slot fewer steps (or stalls it)
        until eviction frees pages."""
        need = -(-n_tokens // self.page)
        if need > self.max_blocks:
            raise RuntimeError(
                f"slot {i} needs {need} blocks > max_blocks="
                f"{self.max_blocks} (request exceeds max_seq)")
        while len(self.owned[i]) < need:
            pg = self._alloc_page()
            if pg is None:
                return False
            self.refcount[pg] = 1
            self.table[i, len(self.owned[i])] = pg
            self.owned[i].append(pg)
            self.dirty.add(i)
        return True

    def share(self, dst: int, donor: int, n_tokens: int) -> None:
        """Map the donor's pages covering token positions [0, n_tokens)
        into slot ``dst``'s block table (refcount bump, no allocation, no
        copy) and set its length.  ``dst`` must be empty.  The trailing
        page may be partially filled — ``dst`` reads only rows below its
        own length there, and its first append into it triggers ``cow()``.
        """
        assert not self.owned[dst], "share() target slot must be empty"
        need = -(-n_tokens // self.page)
        pages = self.owned[donor][:need]
        assert len(pages) == need, "donor does not cover the shared prefix"
        for j, pg in enumerate(pages):
            self.table[dst, j] = pg
            self.refcount[pg] += 1
        self.owned[dst] = list(pages)
        self.length[dst] = n_tokens
        self.shared_pages += need
        self.dirty.add(dst)

    def cow_reserve(self, i: int, blk: int) -> bool:
        """Reserve a copy-on-write of block ``blk`` of slot ``i``: if the
        page is shared (refcount > 1), allocate a fresh destination page,
        rewire only this slot's table entry, and QUEUE the (dst, src) page
        copy for the tick's single batched device dispatch (``cow_flush``).
        All host bookkeeping (refcounts, tables, counters) happens here;
        only the device copy is deferred — nothing reads or writes the
        reserved pages until the flush lands, because the scheduler flushes
        before the engine issues the tick's decode dispatch.  Returns False
        if the free list is dry (the scheduler stalls the slot until
        eviction frees a page).  No-op on exclusively-owned pages."""
        pg = self.owned[i][blk]
        if self.refcount[pg] <= 1:
            return True
        q = self._alloc_page()
        if q is None:
            return False
        self._pending_cow.append((q, pg, i, blk))
        self.refcount[pg] -= 1
        self.refcount[q] = 1
        self.owned[i][blk] = q
        self.table[i, blk] = q
        self.cow_copies += 1
        self.cow_bytes += self.page_bytes
        self.dirty.add(i)
        return True

    def cow_flush(self) -> int:
        """Privatize every page queued by ``cow_reserve`` in ONE donated
        gather/scatter dispatch over both pools (the batched COW: a tick
        that privatizes N pages costs one device call, not N).  The batch
        is NOT padded — the device moves exactly pages_copied x page_bytes
        (the census-pinned claim); the copy program compiles once per
        distinct batch size, bounded by the pages a single tick can touch
        (B x (ceil(chunk/page) + 1)); ``warm_copy`` pre-compiles the
        common small sizes so typical flushes never compile mid-tick.
        Returns the pages copied."""
        if not self._pending_cow:
            return 0
        dst = jnp.asarray([p[0] for p in self._pending_cow], jnp.int32)
        src = jnp.asarray([p[1] for p in self._pending_cow], jnp.int32)
        if self.quantized:               # scales move with their pages
            (self.k, self.v, self.k_scale, self.v_scale) = self._copy_quant(
                self.k, self.v, self.k_scale, self.v_scale, dst, src)
        else:
            self.k, self.v = self._copy(self.k, self.v, dst, src)
        n = len(self._pending_cow)
        self._pending_cow.clear()
        self.cow_dispatches += 1
        return n

    def cow_rollback(self, i: int, from_blk: int = 0) -> int:
        """Undo slot ``i``'s PENDING copy-on-write reservations at block
        indices >= ``from_blk``: the shared mapping is restored (source
        refcount bumped back, table/owned rewired to the original page)
        and the reserved destination page returns to the free list before
        any device copy was issued.  The scheduler calls this when a grant
        shrinks below a block it already reserved — under pool pressure
        the reserved page must go to a slot that can actually advance, not
        sit privatized ahead of an append that will never reach it.
        Returns the number of reservations undone."""
        kept, undone = [], 0
        for (q, pg, s, b) in self._pending_cow:
            if s == i and b >= from_blk:
                self.refcount[pg] += 1
                self.refcount[q] = 0
                self.free.append(q)
                self.owned[i][b] = pg
                self.table[i, b] = pg
                self.cow_copies -= 1
                self.cow_bytes -= self.page_bytes
                undone += 1
            else:
                kept.append((q, pg, s, b))
        self._pending_cow = kept
        if undone:
            self.dirty.add(i)
        return undone

    def cow_many(self, items: Iterable[Tuple[int, int]]) -> int:
        """Batched copy-on-write: privatize ALL shared (slot, blk) pairs in
        one device dispatch.  Pairs whose page is already exclusive are
        skipped; a dry free list stops the batch at the first unservable
        pair (pairs after it are NOT privatized).  Returns the number of
        pages copied.  Convenience wrapper over the reserve/flush pair — a
        caller that must react per pair (e.g. the tick scheduler clipping
        a slot's grant when its COW cannot be served) calls
        ``cow_reserve`` itself and flushes once at the end of the plan."""
        for i, blk in items:
            if not self.cow_reserve(i, blk):
                break
        return self.cow_flush()

    def warm_copy(self, sizes: Tuple[int, ...] = (1, 2)) -> None:
        """Pre-compile the batched page copy for the given batch sizes
        (null-page self-copies: page 0 onto page 0) so the common COW
        flush sizes never pay an XLA compile inside a serving tick —
        compiled against the ACTUAL pool dtype (quantized pools warm the
        four-pool copy cell).  Counters are untouched — this is not a COW.
        """
        for n in sizes:
            idx = jnp.zeros((n,), jnp.int32)
            if self.quantized:
                (self.k, self.v, self.k_scale,
                 self.v_scale) = self._copy_quant(
                    self.k, self.v, self.k_scale, self.v_scale, idx, idx)
            else:
                self.k, self.v = self._copy(self.k, self.v, idx, idx)

    def cow(self, i: int, blk: int) -> bool:
        """Single-page copy-on-write (reserve + immediate flush) — kept for
        callers outside the tick scheduler's batched path."""
        ok = self.cow_reserve(i, blk)
        self.cow_flush()
        return ok

    def shared_blocks(self, i: int, lo_tok: int, hi_tok: int) -> List[int]:
        """Block indices of slot ``i`` whose pages are shared (refcount > 1)
        among the blocks that appends to token positions [lo_tok, hi_tok)
        would touch — the set ``cow()`` must privatize before the tick."""
        b0 = lo_tok // self.page
        b1 = (hi_tok - 1) // self.page
        return [b for b in range(b0, min(b1, len(self.owned[i]) - 1) + 1)
                if self.refcount[self.owned[i][b]] > 1]

    def seize_pages(self, n: int) -> List[int]:
        """Fault injection (pool pressure): remove up to ``n`` pages from
        the free list into the SEIZED set — temporarily unallocatable, as
        if another tenant grabbed them.  Retained pages are reclaimable by
        definition, so a squeeze deeper than the free list drains the
        retained pool too (entries dropped through ``reclaim_retained`` —
        the digest map forgets them cleanly before their pages move).
        ``check()`` accounts for seized pages, so every invariant keeps
        holding under injected pressure.  Returns the seized page ids
        (pass them back to ``release_pages``)."""
        took = []
        while len(took) < n:
            pg = self._alloc_page()
            if pg is None:
                break
            took.append(pg)
        self.seized.update(took)
        return took

    def release_pages(self, pages: Iterable[int]) -> None:
        """Return previously seized pages to the free list."""
        for pg in pages:
            assert pg in self.seized, f"page {pg} was not seized"
            self.seized.discard(pg)
            self.free.append(pg)

    def free_slot(self, i: int, retain_tokens=None) -> None:
        """Eviction: drop slot ``i``'s references; pages whose refcount
        reaches zero go back to the free list (a page another slot still
        references — or a retained entry still holds — stays live).  Any
        PENDING copy-on-write reservation the slot holds is cancelled
        first (rolled back, not flushed): preemption/cancellation can free
        a slot mid-tick, and a pending copy into a page that just returned
        to the free list would corrupt whoever allocates it next
        (regression + fuzz pinned).

        ``retain_tokens`` (the slot's exact token history, prompt +
        emitted) opts the slot's page-aligned prefix into the retained
        pool BEFORE the references drop — cross-lifetime sharing: a later
        admission with the same prompt prefix adopts those pages even
        though this slot is gone."""
        if self._pending_cow:
            self.cow_rollback(i)
        if self.retain and retain_tokens is not None:
            self._retain_prefix(self.owned[i], retain_tokens)
        for pg in reversed(self.owned[i]):
            self.refcount[pg] -= 1
            if self.refcount[pg] == 0 and self.retained_refs[pg] == 0:
                self.free.append(pg)
        self.owned[i] = []
        self.table[i, :] = 0
        self.length[i] = 0
        self.dirty.add(i)
        if self.retain and self.retain_cap > 0:
            # cap counts retained-ONLY pages, so it must run after the
            # donor's references dropped (its pages just became refcount 0)
            self._enforce_retain_cap()

    # -- cross-lifetime retention --------------------------------------------

    def _retain_prefix(self, pages: List[int], tokens) -> None:
        """Register ``tokens``' page-aligned prefix (held in ``pages``) as
        a RetainedPrefix.  Exact duplicates (same length, same tokens) are
        touched, not re-inserted; a shorter/longer prefix of an existing
        entry coexists with it (shared physical pages carry a
        ``retained_refs`` count per entry)."""
        n_full = min(len(tokens) // self.page, len(pages))
        if n_full == 0:
            return
        toks = [int(t) for t in tokens[:n_full * self.page]]
        digests = prefix_digests(toks, self.page)
        self._retain_clock += 1
        for cand in self._retained_keys.get((n_full, digests[-1]), []):
            if cand.tokens == toks:          # exact duplicate: refresh LRU
                cand.stamp = self._retain_clock
                return
        entry = RetainedPrefix(
            tokens=toks, pages=list(pages[:n_full]),
            keys=[(j + 1, d) for j, d in enumerate(digests)],
            stamp=self._retain_clock)
        self.retained.append(entry)
        for pg in entry.pages:
            self.retained_refs[pg] += 1
        for key in entry.keys:
            self._retained_keys.setdefault(key, []).append(entry)

    def _retained_only(self) -> Set[int]:
        """Pages held ONLY by retained entries (refcount 0) — the pages a
        reclamation can actually return to the free list."""
        return {int(p) for p in np.flatnonzero(
            (self.retained_refs > 0) & (self.refcount == 0))}

    def _enforce_retain_cap(self) -> None:
        while len(self._retained_only()) > self.retain_cap:
            victims = [e for e in self._reclaim_order()
                       if self._entry_freeable(e)]
            if not victims:
                break
            self._drop_entry(victims[0])

    def _entry_freeable(self, e: RetainedPrefix) -> int:
        """Pages dropping ``e`` would return to the free list."""
        return sum(1 for pg in e.pages
                   if self.refcount[pg] == 0 and self.retained_refs[pg] == 1)

    def _reclaim_order(self) -> List[RetainedPrefix]:
        if self.retain_policy == "popularity":
            # least-adopted first; LRU breaks ties
            return sorted(self.retained, key=lambda e: (e.hits, e.stamp))
        return sorted(self.retained, key=lambda e: e.stamp)

    def _drop_entry(self, e: RetainedPrefix) -> int:
        """Forget a retained entry: unregister its digest keys, drop its
        page holds, free pages nobody else holds.  Returns pages freed."""
        self.retained.remove(e)
        for key in e.keys:
            owners = self._retained_keys[key]
            owners.remove(e)
            if not owners:
                del self._retained_keys[key]
        freed = 0
        for pg in e.pages:
            self.retained_refs[pg] -= 1
            if self.retained_refs[pg] == 0 and self.refcount[pg] == 0:
                self.free.append(pg)
                freed += 1
        self.retained_dropped += 1
        self.retained_reclaimed_pages += freed
        return freed

    def reclaim_retained(self, need: int) -> int:
        """Drop retained entries in policy order until >= ``need`` pages
        returned to the free list (or the pool is dry).  Entries whose
        pages are ALL still live (adopted by running slots) are skipped —
        dropping them frees nothing and would only forget a popular
        digest.  Adoption bumps refcount, so reclamation can never touch a
        page a live slot just re-shared."""
        freed = 0
        for e in self._reclaim_order():
            if freed >= need:
                break
            if self._entry_freeable(e) == 0:
                continue
            freed += self._drop_entry(e)
        return freed

    def flush_retained(self) -> int:
        """Drop EVERY retained entry (tests / shutdown).  Returns pages
        returned to the free list."""
        freed = 0
        for e in list(self.retained):
            freed += self._drop_entry(e)
        return freed

    def match_retained(self, prompt, cap: int):
        """Longest page-aligned retained prefix of ``prompt[:cap]``.
        Walks the rolling digest outward page by page, stopping at the
        first boundary with no registered entry (an entry registers every
        boundary it covers, so a miss at n pages rules out all longer
        matches).  The winning candidate is verified token-exact; on a
        digest collision falls back to a linear scan over all entries.
        Returns (entry, n_tokens) or (None, 0)."""
        if not self.retained:
            return None, 0
        limit = min(cap, len(prompt))
        h = 0
        best: Optional[RetainedPrefix] = None
        best_n = 0
        for idx in range(limit):
            h = digest_step(h, prompt[idx])
            if (idx + 1) % self.page:
                continue
            owners = self._retained_keys.get(((idx + 1) // self.page, h))
            if not owners:
                break
            best, best_n = owners[0], idx + 1
        if best is not None \
                and best.tokens[:best_n] != [int(t) for t in
                                             prompt[:best_n]]:
            best, best_n = None, 0           # collision: exact fallback
            for e in self.retained:
                n = 0
                for a, b in zip(e.tokens, prompt[:limit]):
                    if a != int(b):
                        break
                    n += 1
                n -= n % self.page
                if n > best_n:
                    best, best_n = e, n
        if best is None or best_n == 0:
            return None, 0
        return best, best_n

    def adopt_retained(self, dst: int, entry: RetainedPrefix,
                       n_tokens: int) -> None:
        """Cross-lifetime ``share()``: map the retained entry's pages
        covering [0, n_tokens) into empty slot ``dst``'s block table
        (refcount bump — the pages become live again) and set its length.
        Adopted pages are FULL, so the adopter never writes into them
        (appends land past the prefix); refcount > 0 also shields them
        from ``reclaim_retained`` for as long as the adopter runs."""
        assert not self.owned[dst], "adopt_retained() target must be empty"
        assert n_tokens % self.page == 0, "retained adoption is page-aligned"
        need = n_tokens // self.page
        assert need <= len(entry.pages), "entry does not cover the prefix"
        for j, pg in enumerate(entry.pages[:need]):
            self.table[dst, j] = pg
            self.refcount[pg] += 1
        self.owned[dst] = list(entry.pages[:need])
        self.length[dst] = n_tokens
        self.shared_pages += need
        self._retain_clock += 1
        entry.stamp = self._retain_clock
        entry.hits += 1
        self.retained_hits += 1
        self.retained_hit_tokens += n_tokens
        self.dirty.add(dst)

    # -- bookkeeping ----------------------------------------------------------

    @property
    def live_pages(self) -> int:
        """Distinct physical pages referenced by at least one slot."""
        return len({p for o in self.owned for p in o})

    @property
    def retained_pages(self) -> int:
        """Distinct pages held by retained entries (live or not)."""
        return len({p for e in self.retained for p in e.pages})

    @property
    def allocatable(self) -> int:
        """Pages an allocation could obtain RIGHT NOW: the free list plus
        retained-only pages (reclaimable by definition)."""
        return len(self.free) + len(self._retained_only())

    @property
    def logical_pages(self) -> int:
        """Block-table references summed over slots (>= live_pages when
        prefix sharing maps one page into several tables)."""
        return sum(len(o) for o in self.owned)

    def utilization(self) -> float:
        """Fraction of allocatable pages currently referenced by live
        slots (physical: shared pages count once)."""
        return self.live_pages / max(1, self.num_pages - 1)

    def occupancy(self) -> float:
        """Fraction of rows in live pages holding real tokens — intra-page
        fragmentation, invariant under defrag (which only renumbers)."""
        rows = self.live_pages * self.page
        # shared rows are stored once but the physical rows written are
        # exactly the DISTINCT tokens: count each live page's filled rows
        # under its furthest-advanced referent
        fill = {}
        for i in range(self.B):
            n = int(self.length[i])
            for j, pg in enumerate(self.owned[i]):
                f = min(self.page, max(0, n - j * self.page))
                fill[pg] = max(fill.get(pg, 0), f)
        return sum(fill.values()) / rows if rows else 0.0

    def check(self, allow_pending: bool = False) -> None:
        """Refcount/free-list/table invariants (cheap; the property harness
        calls this every fuzz step).  ``allow_pending=True`` checks the
        MID-PLAN state (reservations made, flush not yet issued): pending
        pairs must reference live pages only — a pending copy into or out
        of a free page is exactly the corruption ``free_slot``'s
        cancellation and ``cow_rollback`` exist to prevent."""
        if allow_pending:
            free = set(self.free)
            for (q, pg, s, b) in self._pending_cow:
                assert q not in free and pg not in free, \
                    f"pending COW ({q} <- {pg}) references a free page"
                assert self.refcount[q] == 1, \
                    f"pending COW destination {q} has refcount " \
                    f"{self.refcount[q]}"
                assert 0 <= b < len(self.owned[s]) \
                    and self.owned[s][b] == q, \
                    f"pending COW for slot {s} block {b} lost its rewire"
        else:
            assert not self._pending_cow, "unflushed COW reservations"
        refs = Counter(p for o in self.owned for p in o)
        assert 0 not in refs, "null page referenced"
        for i, o in enumerate(self.owned):
            assert len(set(o)) == len(o), f"slot {i} references a page twice"
            assert list(self.table[i, :len(o)]) == o, "table/owned drift"
            assert not self.table[i, len(o):].any(), "stale table entry"
        for p in range(1, self.num_pages):
            assert self.refcount[p] == refs.get(p, 0), \
                f"page {p}: refcount {self.refcount[p]} != " \
                f"{refs.get(p, 0)} table references"
        assert len(set(self.free)) == len(self.free), "free-list duplicate"
        assert not set(refs) & set(self.free), "page both referenced and free"
        assert not self.seized & set(refs), "seized page still referenced"
        assert not self.seized & set(self.free), "seized page still free"
        # -- retained-pool invariants (three-way partition) -----------------
        rr = Counter(p for e in self.retained for p in e.pages)
        assert 0 not in rr, "null page retained"
        for p in range(1, self.num_pages):
            assert self.retained_refs[p] == rr.get(p, 0), \
                f"page {p}: retained_refs {self.retained_refs[p]} != " \
                f"{rr.get(p, 0)} retaining entries"
        rset = set(rr)
        assert not rset & set(self.free), "retained page in the free list"
        assert not rset & self.seized, "retained page seized"
        for e in self.retained:
            assert e.pages and len(e.tokens) == self.page * len(e.pages), \
                "retained entry is not page-aligned"
            assert len(set(e.pages)) == len(e.pages), \
                "retained entry holds a page twice"
            digs = prefix_digests(e.tokens, self.page)
            assert e.keys == [(j + 1, d) for j, d in enumerate(digs)], \
                "retained entry digests drifted from its tokens"
            for key in e.keys:
                assert e in self._retained_keys.get(key, []), \
                    f"retained entry unregistered under key {key}"
        n_keys = sum(len(v) for v in self._retained_keys.values())
        assert n_keys == sum(len(e.keys) for e in self.retained), \
            "digest map holds keys for a dropped entry"
        retained_only = {p for p in rset if self.refcount[p] == 0}
        assert set(refs) | set(self.free) | self.seized | retained_only \
            == set(range(1, self.num_pages)), "page leaked"

    # -- defrag ----------------------------------------------------------------

    def defrag(self) -> None:
        """Compact OCCUPIED pages to the contiguous pool prefix (one
        donated device gather per pool) and rewrite the block tables.
        A page shared by several tables moves ONCE and every table entry is
        renumbered to the same new id.  The layout after compaction is
        [null | live | retained-only | seized | free]: retained entries'
        pages and the seized set are renumbered through the same
        permutation and kept OUT of the rebuilt free list (seized pages
        re-entering free after a defrag was a live fuzz-found bug).
        Purely physical: logical contents are untouched, so engine output
        is bit-identical across defrags (property-tested)."""
        self.cow_flush()                 # pending copies address OLD page ids
        mapping = {0: 0}
        perm = [0]                                    # new -> old; null stays
        for i in range(self.B):
            for j, pg in enumerate(self.owned[i]):
                if pg not in mapping:
                    mapping[pg] = len(perm)
                    perm.append(pg)
                self.table[i, j] = mapping[pg]
            self.owned[i] = [mapping[pg] for pg in self.owned[i]]
        for e in self.retained:          # retained-only pages pack next
            for pg in e.pages:
                if pg not in mapping:
                    mapping[pg] = len(perm)
                    perm.append(pg)
            e.pages = [mapping[pg] for pg in e.pages]
        for pg in sorted(self.seized):   # seized keep their rows, renumbered
            if pg not in mapping:
                mapping[pg] = len(perm)
                perm.append(pg)
        self.seized = {mapping[pg] for pg in self.seized}
        kept = len(perm) - 1             # live + retained-only + seized
        perm.extend(p for p in range(1, self.num_pages) if p not in mapping)
        new_rc = np.zeros_like(self.refcount)
        new_rr = np.zeros_like(self.retained_refs)
        for old, new in mapping.items():
            new_rc[new] = self.refcount[old]
            new_rr[new] = self.retained_refs[old]
        self.refcount = new_rc
        self.retained_refs = new_rr
        self.free = list(range(self.num_pages - 1, kept, -1))
        perm_dev = jnp.asarray(np.asarray(perm, np.int32))
        self.k = self._gather(self.k, perm_dev)
        self.v = self._gather(self.v, perm_dev)
        if self.quantized:               # scales renumber with their pages
            self.k_scale = self._gather(self.k_scale, perm_dev)
            self.v_scale = self._gather(self.v_scale, perm_dev)
        self.dirty.update(range(self.B))     # every table renumbered
