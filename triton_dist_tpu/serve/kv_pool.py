"""KVPool — shared paged KV storage for the serving plane.

The pool generalizes `mega.qwen3.PagedMegaKVCache` from a per-model
snapshot into a SERVING resource (ref: mega_triton_kernel/models/
paged_kv_cache.py): k/v are shared page pools and the page table maps
SLOTS (bounded concurrency lanes of the fixed-geometry serve step) onto
pool pages. Where the megakernel cache bump-allocates and never frees,
the pool runs a full allocator lifecycle: allocate-on-admit,
grow-per-chunk, free-on-finish, and eviction (reclaim a victim's pages
so a higher-priority request can run; the victim requeues and
re-prefills bit-identically — engine.make_serve_step).

Layout: TOKEN-MAJOR pages, k/v (L, P, page, Hkv, D). A page is `page`
whole token rows of the step's dense (L, B, T, Hkv, D) view, so the
step's round trip moves each byte once: the read is `pool[:, table]`
with no transposition (KVCache.dense_view), and the write puts a
step's rows back as page slabs in place (KVCache.scatter_step). The kv
head axis (3) is the tensor-parallel one. This module and
models/kv_cache.py are the only places that know the order; the two
boundaries that speak another convert there: the migration image
(`export_pages` / `install`, whose wire order (L, Hkv, n_pages, page,
D) is xslice/migrate.py's) and the megakernel bridge (`as_mega_cache`,
the megakernel's own (L, Hkv, P, page, D)).

Page 0 is RESERVED (the null page): unallocated table entries point at
it, and the serve step routes padding-column KV writes to it, so a
garbage write can never land on another sequence's live page. The
allocator therefore hands out pages [1, P) and `capacity` excludes the
reserved page.

Sharing (ISSUE 14, the prefix plane): pages are REFCOUNTED. A freshly
allocated page has refcount 1 (its slot); `ref_pages` lets an external
holder — the radix prefix cache, serve/prefix.py — retain pages past
their slot's lifetime, and `share` admits a slot whose leading pages
ARE another holder's pages (copy-on-write discipline: a shared page is
only ever READ — the serve step writes at positions >= lengths, and a
shared prefix always ends on a page boundary at/below lengths — and
`cow` gives a slot a private copy the moment it would need to write
one). `release`/`unref_pages` decrement; a page returns to the free
list only at refcount 0, so eviction can never reclaim a page another
slot or the cache still reads. `check()` generalizes the page-0
null-page / leak / alias assertions: every page's refcount must equal
its holder count (slot table occurrences + external holds), and the
free list is exactly the refcount-0 pages.

What a page keeps of a token is the family's
(`ModelConfig.page_arrays`): keys and values a kv head in two pools,
or, for a family with latent attention, ONE row a token in one pool
`k` (Lf, P, page, 1, W) from which keys and values both come; `v` is
then None and nothing is allocated in its place.

A second kind of state (the hybrid family, models/hybrid.py): pages
hold what the attention blocks keep only, and every delta-net block
keeps PER-SLOT state beside them — `rec`
(Ll, slots, Hv, dk, dv) float32 and `conv` (Ll, slots, K-1, channels),
their shapes the family's (`models.hybrid.state_shapes`); a
state-space (Mamba-2) block keeps the same two, `rec` (Ls, slots,
heads, P, N) float32 and `conv` over its x | B | C channels, under
the same names and counters.
It needs no allocator: a slot's life covers it, because the serve step
starts a slot whose length is 0 from zero state and leaves the state of
a slot with no valid column as it was; admit, eviction with re-prefill
and release therefore touch `lengths` alone. `state` is everything the
step carries, as one pytree. What would have to copy or ship that
state (prefix sharing, copy-on-write, page export / install, the
megakernel bridge) refuses such a pool.

A third kind, of the same standing: a WINDOW block (attention over the
last `sliding_window` positions) keeps no pages at all but a fixed
per-slot TAIL, `win` = (k, v) each (Lw, slots, window, Hkv, D)
(`models.hybrid.window_shapes`): allocated once, here; shifted by the
step itself (a slot's valid rows in, as many out); never grown and
never read through the table, so its bytes a slot are the same at
every context. A slot of length 0 reads none of it. The pages, the
table and every counter of them are then the GLOBAL blocks' alone.
The same refusals hold, for the same reason.

Host/device split: page bookkeeping (free list, per-slot page lists,
lengths, refcounts) is host-side numpy — the scheduler reads it every
step — while k/v live on device and are donated through the step
function (`cow` is the one bookkeeping op that also touches device
state: it copies the page's k/v rows).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.models.kv_cache import KVCache


def pages_for(n_tokens: int, page: int) -> int:
    """ceil(n_tokens / page) — the page demand of a sequence."""
    return -(-n_tokens // page)


class PoolExhausted(RuntimeError):
    """No free pages (and the caller chose not to evict)."""


class KVPool:
    """Shared paged KV pool over `slots` concurrency lanes.

    total_pages counts ALLOCATABLE pages (the reserved null page is
    added on top); it defaults to full provisioning
    (slots * max_pages), and smaller pools oversubscribe — the point of
    paging — with eviction as the pressure valve.
    """

    def __init__(self, engine, slots: int, page: int,
                 max_pages: Optional[int] = None,
                 total_pages: Optional[int] = None):
        cfg = engine.cfg
        assert engine.max_len % page == 0, (
            f"page {page} must divide the engine horizon "
            f"{engine.max_len}"
        )
        self.engine = engine
        self.slots = slots
        self.page = page
        self.max_pages = max_pages or engine.max_len // page
        self.t_max = self.max_pages * page
        self.capacity = (total_pages if total_pages is not None
                         else slots * self.max_pages)
        assert self.capacity >= 1, "pool needs at least one page"

        n = int(engine.mesh.shape[engine.axis])
        dt = jnp.dtype(cfg.dtype)
        sharding = NamedSharding(engine.mesh,
                                 P(None, None, None, engine.axis, None))
        # one pool an array of the family's page (module doc); zeros
        # created IN the sharding: never whole on one device
        pools = [jnp.zeros((cfg.num_kv_layers, 1 + self.capacity, page,
                            heads // n * n, width), dt, device=sharding)
                 for heads, width in cfg.page_arrays]
        self.k, self.v = pools if len(pools) == 2 else (pools[0], None)
        # pool bytes of one position, all page layers together
        self.kv_bytes_per_token = cfg.num_kv_layers * cfg.kv_bytes_per_token
        # the delta-net blocks' per-slot state and the window blocks'
        # tails (module doc): nothing where the pattern has no such block
        self.rec = self.conv = None
        self.win = ()
        self.state_bytes_per_slot = self.window_bytes_per_slot = 0
        self._slot_state = ""
        if cfg.is_hybrid:
            from triton_dist_tpu.models import hybrid

            here = NamedSharding(engine.mesh, P())
            self._slot_state = hybrid.slot_state(cfg)
            shapes = hybrid.state_shapes(cfg, slots)
            if shapes:
                self.rec = jnp.zeros(shapes[0], jnp.float32, device=here)
                self.conv = jnp.zeros(shapes[1], dt, device=here)
                self.state_bytes_per_slot = (
                    self.rec.nbytes + self.conv.nbytes) // slots
            self.win = tuple(jnp.zeros(shape, dt, device=here)
                             for shape in hybrid.window_shapes(cfg, slots))
            self.window_bytes_per_slot = sum(
                w.nbytes for w in self.win) // slots

        self.table = np.zeros((slots, self.max_pages), np.int32)
        self.lengths = np.zeros((slots,), np.int32)
        self._free: List[int] = list(range(self.capacity, 0, -1))  # pop=1 first
        self._pages: List[Optional[List[int]]] = [None] * slots  # None=free
        # refcount per page id (index 0 = the null page, always 0).
        # refcount == number of holders: slot-table occurrences plus
        # external holds (the prefix cache); 0 <=> on the free list.
        self._refs = np.zeros((1 + self.capacity,), np.int32)
        self._ext: Dict[int, int] = {}  # page -> external hold count

    @property
    def state(self):
        """Everything the serve step carries, as ONE pytree (the step's
        `cache` argument and third result)."""
        pages = KVCache(self.k, self.v, None).pools
        if self.rec is not None:
            pages += (self.rec, self.conv)
        return pages + self.win

    @state.setter
    def state(self, new) -> None:
        if self.win:
            new, self.win = new[:-len(self.win)], tuple(new[-len(self.win):])
        if self.rec is not None:
            *new, self.rec, self.conv = new
        self.k, self.v = new if len(new) == 2 else (new[0], None)

    def _pages_only(self, what: str) -> None:
        if self._slot_state:
            raise NotImplementedError(
                f"{what} moves pages, and this pool's slots also carry "
                f"{self._slot_state} that it has no way to copy, share or "
                "ship")

    # -- queries --------------------------------------------------------

    def free_pages(self) -> int:
        return len(self._free)

    def used_pages(self, slot: Optional[int] = None) -> int:
        """Pages held by a slot (or all slots). A page shared across
        slots counts once per holder — this is table occupancy, not
        distinct-page pressure (free_pages reads the latter)."""
        if slot is not None:
            ps = self._pages[slot]
            return 0 if ps is None else len(ps)
        return sum(len(p) for p in self._pages if p is not None)

    def live_tokens(self, slots) -> int:
        """Valid token positions held by `slots`, together."""
        return sum(int(self.lengths[s]) for s in slots)

    def dense_view_tokens(self) -> int:
        """Token positions a serve step's `KVCache.dense_view` of this
        pool's table gathers (a layer and kv head)."""
        return KVCache.dense_view_tokens(self.table.shape, self.page)

    def refcount(self, page: int) -> int:
        return int(self._refs[page])

    def shared_pages(self) -> int:
        """Distinct pages with refcount > 1 (the sharing win)."""
        return int(np.sum(self._refs > 1))

    def free_slot(self) -> Optional[int]:
        for s, p in enumerate(self._pages):
            if p is None:
                return s
        return None

    def check(self) -> None:
        """Allocator invariants (leak/alias/refcount guard): every
        page's refcount equals its holder count (slot-table occurrences
        + external holds), the free list is exactly the refcount-0
        pages (each once), a page appears at most once per slot, and
        the null page is held nowhere."""
        held = [pg for ps in self._pages if ps is not None for pg in ps]
        assert 0 not in held and 0 not in self._free, (
            "null page leaked into the allocator"
        )
        assert 0 not in self._ext and all(
            v > 0 for v in self._ext.values()), (
            f"malformed external holds {self._ext}"
        )
        for s, ps in enumerate(self._pages):
            if ps is not None:
                assert len(ps) == len(set(ps)), (
                    f"page aliased within slot {s}: {ps}"
                )
                assert list(self.table[s, :len(ps)]) == ps, (
                    f"slot {s} table drifted from its page list"
                )
        assert len(self._free) == len(set(self._free)), (
            "page aliased within the free list"
        )
        holders = np.zeros_like(self._refs)
        for pg in held:
            holders[pg] += 1
        for pg, n in self._ext.items():
            holders[pg] += n
        assert np.array_equal(holders, self._refs), (
            f"refcount drift: holders {np.flatnonzero(holders != self._refs)}"
        )
        free_set = set(self._free)
        for pg in range(1, self.capacity + 1):
            if self._refs[pg] == 0:
                assert pg in free_set, f"page {pg} leaked (ref 0, not free)"
            else:
                assert pg not in free_set, (
                    f"page {pg} aliased: refcount {self._refs[pg]} but "
                    "on the free list"
                )

    # -- lifecycle ------------------------------------------------------

    def _alloc(self, need: int) -> List[int]:
        assert need <= len(self._free)
        new = [self._free.pop() for _ in range(need)]
        self._refs[new] = 1
        return new

    def admit(self, slot: int, n_tokens: int) -> None:
        """Claim `slot` and allocate pages for an n_tokens history
        (allocate-on-admit). Raises PoolExhausted/AssertionError rather
        than partially allocating."""
        assert self._pages[slot] is None, f"slot {slot} already in use"
        need = max(pages_for(n_tokens, self.page), 1)
        assert need <= self.max_pages, (
            f"{n_tokens} tokens need {need} pages > table width "
            f"{self.max_pages}"
        )
        if need > len(self._free):
            raise PoolExhausted(
                f"need {need} pages, {len(self._free)} free"
            )
        self._pages[slot] = self._alloc(need)
        self.table[slot, :need] = self._pages[slot]
        self.lengths[slot] = 0

    def share(self, slot: int, shared: Sequence[int],
              n_tokens: int) -> None:
        """Claim `slot` with its LEADING pages shared from another
        holder (the prefix-cache hit path): each page of `shared` is
        increfed into the slot's table, fresh pages are allocated for
        the rest of an n_tokens history, and the slot length starts at
        the shared coverage (len(shared) * page tokens of KV are
        already live in those pages). All-or-nothing like admit.

        COW discipline: the serve step writes at positions >= lengths,
        and the shared pages cover exactly [0, lengths) — a shared page
        is never written through this slot (cow() exists for callers
        that break that alignment)."""
        self._pages_only("KVPool.share (the prefix cache)")
        assert self._pages[slot] is None, f"slot {slot} already in use"
        shared = [int(p) for p in shared]
        assert all(self._refs[p] >= 1 for p in shared), (
            f"sharing unheld page(s) {shared}"
        )
        assert len(shared) == len(set(shared)), f"aliased share {shared}"
        need_total = max(pages_for(n_tokens, self.page), 1,
                         len(shared))
        assert need_total <= self.max_pages, (
            f"{n_tokens} tokens need {need_total} pages > table width "
            f"{self.max_pages}"
        )
        fresh = need_total - len(shared)
        if fresh > len(self._free):
            raise PoolExhausted(
                f"need {fresh} fresh pages, {len(self._free)} free"
            )
        self._refs[shared] += 1
        ps = shared + self._alloc(fresh)
        self._pages[slot] = ps
        self.table[slot, :len(ps)] = ps
        self.lengths[slot] = len(shared) * self.page

    def ref_pages(self, pages: Sequence[int]) -> None:
        """External hold (the prefix cache retaining pages): increfs
        each page so release()/eviction can never reclaim it."""
        for p in pages:
            p = int(p)
            assert 1 <= p <= self.capacity and self._refs[p] >= 1, (
                f"external ref of unheld page {p}"
            )
            self._refs[p] += 1
            self._ext[p] = self._ext.get(p, 0) + 1

    def unref_pages(self, pages: Sequence[int]) -> int:
        """Drop an external hold; pages reaching refcount 0 return to
        the free list. Returns the number of pages actually freed."""
        freed = 0
        for p in pages:
            p = int(p)
            assert self._ext.get(p, 0) >= 1, (
                f"external unref of page {p} without a hold"
            )
            self._ext[p] -= 1
            if self._ext[p] == 0:
                del self._ext[p]
            self._refs[p] -= 1
            assert self._refs[p] >= 0
            if self._refs[p] == 0:
                self._free.append(p)
                freed += 1
        return freed

    def cow(self, slot: int, page_idx: int) -> int:
        """Copy-on-write: give `slot` a PRIVATE copy of its
        `page_idx`-th page. A no-op (returns the page) when the slot is
        already the only holder; otherwise allocates a fresh page,
        copies the k/v rows on device, swaps it into the slot's table,
        and drops this slot's hold on the shared original. Returns the
        (possibly new) page id; raises PoolExhausted when no page is
        free for the copy."""
        self._pages_only("KVPool.cow")
        ps = self._pages[slot]
        assert ps is not None, f"slot {slot} is not admitted"
        assert 0 <= page_idx < len(ps)
        old = ps[page_idx]
        if self._refs[old] == 1:
            return old
        if not self._free:
            raise PoolExhausted("no free page for the COW copy")
        (new,) = self._alloc(1)
        self.k = self.k.at[:, new].set(self.k[:, old])
        self.v = self.v.at[:, new].set(self.v[:, old])
        ps[page_idx] = new
        self.table[slot, page_idx] = new
        self._refs[old] -= 1
        return new

    def ensure(self, slot: int, upto_tokens: int) -> bool:
        """Grow `slot`'s allocation to cover `upto_tokens` (all-or-
        nothing). False = exhausted; the scheduler then evicts or
        stalls the slot."""
        ps = self._pages[slot]
        assert ps is not None, f"slot {slot} is not admitted"
        need = pages_for(upto_tokens, self.page) - len(ps)
        if need <= 0:
            return True
        assert len(ps) + need <= self.max_pages, (
            f"slot {slot}: {upto_tokens} tokens exceed the "
            f"{self.max_pages}-page table"
        )
        if need > len(self._free):
            return False
        new = self._alloc(need)
        self.table[slot, len(ps):len(ps) + need] = new
        ps.extend(new)
        return True

    def release(self, slot: int) -> None:
        """Free `slot`: drop its hold on every page (free-on-finish /
        eviction). Pages still held elsewhere — shared with another
        slot or retained by the prefix cache — survive; only
        refcount-0 pages return to the free list. Double-free is an
        assertion, not a silent no-op."""
        ps = self._pages[slot]
        assert ps is not None, f"double free of slot {slot}"
        for p in reversed(ps):
            self._refs[p] -= 1
            assert self._refs[p] >= 0, f"over-release of page {p}"
            if self._refs[p] == 0:
                self._free.append(p)
        self._pages[slot] = None
        self.table[slot] = 0
        self.lengths[slot] = 0

    # -- export ---------------------------------------------------------

    def export_pages(self, slot: int, n_tokens: Optional[int] = None):
        """Snapshot `slot`'s live KV pages as host numpy in the
        migration image's order (L, Hkv, n_pages, page, D) — a wire
        format (xslice/migrate.py), so the pool's own order is turned
        into it here, on the host. `n_tokens` trims to the pages
        covering the first n_tokens positions (default: all of the
        slot's pages). Pure gather; bitwise."""
        self._pages_only("KVPool.export_pages (xslice migration)")
        ps = self._pages[slot]
        assert ps is not None, f"slot {slot} is not admitted"
        if n_tokens is not None:
            ps = ps[:max(pages_for(n_tokens, self.page), 1)]
        idx = jnp.asarray(ps, jnp.int32)
        return tuple(
            np.ascontiguousarray(np.asarray(pool[:, idx]).transpose(
                0, 3, 1, 2, 4)) for pool in (self.k, self.v))

    def install(self, slot: int, k_pages, v_pages,
                n_tokens: int) -> None:
        """Admit `slot` and install migrated KV pages
        ((L, Hkv, n_pages, page, D), the image's order) covering
        an n_tokens prefix — the destination half of the KV migration
        handoff. Page COUNT must match the admit demand; lengths starts
        at n_tokens (the migrated history is live). All-or-nothing:
        raises PoolExhausted before touching device state."""
        self._pages_only("KVPool.install (xslice migration)")
        need = max(pages_for(n_tokens, self.page), 1)
        assert k_pages.shape[2] == need and v_pages.shape[2] == need, (
            f"{n_tokens} tokens need {need} pages, image has "
            f"{k_pages.shape[2]}/{v_pages.shape[2]}"
        )
        self.admit(slot, n_tokens)
        idx = jnp.asarray(self._pages[slot], jnp.int32)

        def put(pool, image):  # the image's order into the pool's
            return pool.at[:, idx].set(jnp.asarray(
                np.asarray(image).transpose(0, 2, 3, 1, 4), pool.dtype))

        self.k, self.v = put(self.k, k_pages), put(self.v, v_pages)
        self.lengths[slot] = n_tokens

    def to_dense(self):
        """Host-side dense (L, B, T, Hkv, D) models.KVCache snapshot
        (pure gather; bitwise — tests and the mega bridge use it)."""
        return KVCache.dense_view(self.k, self.v,
                                  jnp.asarray(self.table),
                                  jnp.asarray(self.lengths))

    def as_mega_cache(self):
        """Snapshot the pool as a mega.qwen3.PagedMegaKVCache, page ids
        and table as they are, so the megakernel's paged decode path
        runs over serve-plane state. The megakernel keeps its own page
        order (L, Hkv, P, page, D): the bridge hands it a transposed
        COPY of the pool (a decode handoff, not shared ownership). Its
        bump allocator resumes at the pool high-water mark; note it
        will NOT see pages freed back to this pool's free list."""
        self._pages_only("KVPool.as_mega_cache (the megakernel bridge)")
        from triton_dist_tpu.mega.qwen3 import PagedMegaKVCache

        high = max((max(ps) for ps in self._pages if ps), default=0)
        return PagedMegaKVCache(
            k=jnp.moveaxis(self.k, 3, 1), v=jnp.moveaxis(self.v, 3, 1),
            table=jnp.asarray(self.table),
            length=jnp.asarray(self.lengths),
            next_free=jnp.asarray(high + 1, jnp.int32),
        )
