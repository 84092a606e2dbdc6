"""KV-cache pools: whole-row slots and fixed-size token pages.

Two bookkeeping planes share one admission interface (``can_admit`` /
``admit`` / ``release`` / ``prepare_decode`` / ``seal_prefilled``) so the
scheduler and engine are pool-agnostic:

``SlotCachePool`` — the original plane: the cache is one pytree with
``n_slots`` batch rows; a request owns one whole row from prefill to
retirement.  Admission is gated on free *slots*.

``PagedCachePool`` — the paged plane: the device cache is a pool of
``n_pages`` fixed-size token pages (``page_size`` rows each, one physical
page axis per cache leaf) plus one reserved *trash* page, and each live
request holds a page *table* mapping its logical pages to physical ones.
A request's cache can therefore span non-contiguous fragments, and
admission is gated on free **pages**, not free slots:

  * admission reserves the request's worst-case page count
    (``ceil((prompt_len + max_new - 1) / page_size)``) so decode growth
    can never be starved mid-flight (preemption-free reservation);
  * prefill claims only the pages the prompt needs; decode claims more
    lazily (*grow-on-decode*), structurally bounded by the reservation;
  * unclaimed logical pages point at the trash page, so every write is
    well-defined (the trash page absorbs writes from idle rows as zeros,
    and reads of it are masked: decode attention masks positions beyond
    each request's depth).

**Prefix sharing (``share_prefixes=True``)** adds the production
capacity lever: prompts that agree on their leading FULL pages map those
logical pages onto the SAME physical pages, tracked by per-page
refcounts and a ``prefix.PrefixIndex``.  The cost model changes from
worst-case private reservation to ``shared + private``: a follower
reserves (and can ever claim) only the pages the index did NOT already
hold, so a template-heavy workload admits far more concurrency out of
the same pool.  Copy-on-write happens at page granularity inside the
dispatches that already exist:

  * only pages the prompt fills completely are shareable; a partial
    last prompt page (prompt tokens + upcoming decode writes) is
    *copied* — claimed privately and written by the request's own
    prefill scatter — which is the only place a request's token stream
    diverges from the shared region;
  * every pool keeps TWO host page maps: ``table`` (the read map decode
    attention uses) and ``write_table`` (the write map decode's row
    writes and prefill's scatter use), and a shared page's write entries
    are the trash
    page for every holder — once a page is sealed, no dispatch can
    write it, so "no request ever writes a page with refcount > 1"
    holds structurally (property-tested) and no write sees duplicate
    non-trash indices;
  * growth pages (decode writes) are always private, so grow-on-decode
    and the reservation argument are unchanged.

Both pools are pure id bookkeeping with conservation counters
(``n_allocated == n_freed`` once drained, property-tested; a shared
page is allocated once and freed once — when its refcount hits zero —
no matter how many requests attached to it).  The tensor side lives in
the helper functions: ``write_slot`` splices a prefilled row into the
slot pool; ``scatter_page_view`` writes prefilled rows into their pages
and ``gather_page_view`` is its inverse, the per-slot contiguous *view*
(decode reads and writes the pool in place, see
``models.transformer.paged_decode_step``; the view is the oracle its
tests compare against).
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from .prefix import PrefixIndex, page_key

BATCH_AXIS = 1  # cache-leaf batch axis for the supported families


class SlotCachePool:
    def __init__(self, n_slots: int):
        assert n_slots >= 1
        self.n_slots = int(n_slots)
        # min-heap: allocate() hands out the LOWEST free slot (test-pinned)
        # in O(log n) — the old sorted list paid an O(n) shift per pop(0)
        # and an O(n log n) re-sort per free
        self._free: List[int] = list(range(n_slots))
        self._used: set = set()
        self.n_allocated = 0
        self.n_freed = 0

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return len(self._used)

    @property
    def drained(self) -> bool:
        return not self._used

    @property
    def occupancy(self) -> float:
        """Instantaneous used fraction (the metrics-plane gauge)."""
        return len(self._used) / self.n_slots

    def active_slots(self) -> Tuple[int, ...]:
        return tuple(sorted(self._used))

    def allocate(self) -> int:
        if not self._free:
            raise RuntimeError("cache pool exhausted: no free slots")
        slot = heapq.heappop(self._free)
        self._used.add(slot)
        self.n_allocated += 1
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._used:
            raise RuntimeError(f"slot {slot} is not allocated")
        self._used.remove(slot)
        heapq.heappush(self._free, slot)
        self.n_freed += 1

    # ---- pool-agnostic admission interface (scheduler/engine) ----------
    def can_admit(self, request) -> bool:
        """True iff ``admit`` would succeed right now.

        Callers may rely on: (a) no side effects — safe to probe
        speculatively; (b) consistency — ``can_admit`` followed by
        ``admit`` in the same scheduler step cannot fail, because only
        ``admit``/``release`` mutate capacity and the engine loop is
        single-threaded.  The slot plane's only resource is a free row.
        """
        return self.free_count > 0

    def admit(self, request) -> int:
        """Take a whole cache row for ``request`` and return the slot id.

        Callers may rely on: the row is exclusively owned until
        ``release``; ``write_slot`` overwrites it whole at prefill so no
        previous occupant's bytes are ever visible.  Raises if no row is
        free (callers must gate on ``can_admit``)."""
        return self.allocate()

    def release(self, request) -> None:
        """Return ``request``'s row to the free list.

        Callers may rely on: capacity freed here is admissible in the
        SAME scheduler step (retire-before-admit), and conservation —
        every ``admit`` is matched by exactly one ``release`` before
        ``drained`` can be True."""
        self.free(request.slot)

    def prepare_decode(self, requests, k: int) -> None:
        """Claim whatever the next ``k`` fused decode steps will write.

        Slot rows are whole — nothing to claim — so this is a no-op;
        the paged plane overrides it with page growth.  Callers may rely
        on it being infallible for admitted requests on BOTH planes."""

    def seal_prefilled(self, requests) -> None:
        """Hook the engine calls right after the prefill dispatch that
        wrote ``requests``'s cache state.  Slot rows need no sealing;
        the paged plane uses it to publish shareable prefix pages (and
        write-protect them).  Callers may rely on: after this returns,
        every page/row the prefill wrote is safe to share per the pool's
        sharing policy, and no writable alias of a shared page remains.
        """


@dataclasses.dataclass
class _PagedLive:
    """Host bookkeeping for one in-flight request on the paged plane."""

    slot: int
    private_reserved: int        # pages this request may claim itself
    pages: List[int]             # logical order; head may be shared
    n_shared: int                # attached (refcount > 1 capable) head pages
    pending_keys: List[Tuple[int, bytes]]   # pages to index at seal time


class PagedCachePool:
    """Page allocator + per-request page tables for the paged KV plane.

    ``table`` is the host-side (numpy) READ page map, shape
    ``(n_slots, pages_per_slot)`` int32: row = decode-batch slot, column =
    logical page index, value = physical page id (``trash_page`` when
    unclaimed).  ``write_table`` is the WRITE map the scatters use: it
    equals ``table`` except that shared (sealed) pages are replaced by
    the trash page, so no dispatch can ever write a page two requests
    read.  The engine pushes both to device as arguments of every jitted
    dispatch — values change per step, shapes never do.

    With ``share_prefixes=False`` (the default) the two tables are
    always equal and every page has refcount 1: behaviour is exactly
    the PR 5 private-reservation plane.
    """

    def __init__(self, n_pages: int, page_size: int, n_slots: int,
                 pages_per_slot: int, share_prefixes: bool = False):
        assert n_pages >= 1 and page_size >= 1
        assert n_slots >= 1 and pages_per_slot >= 1
        # a pool smaller than one slot's view could never admit a
        # worst-case request: the engine would spin forever un-admitting
        assert n_pages >= pages_per_slot, (
            f"n_pages={n_pages} < pages_per_slot={pages_per_slot}")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.n_slots = int(n_slots)
        self.pages_per_slot = int(pages_per_slot)
        self.share_prefixes = bool(share_prefixes)
        self.prefix_index: Optional[PrefixIndex] = (
            PrefixIndex(page_size) if share_prefixes else None)
        # free-page STACK (LIFO), not a heap: page identity is
        # interchangeable (the table indirection absorbs any order), so
        # claims are O(1) pops off the end instead of O(log n) sifts —
        # the allocator sits on the per-decode-step path via
        # prepare_decode.  Seeded descending so the first claims still
        # hand out low page ids.  Rows stay a min-heap: slot order is
        # test-pinned.
        self._free_pages: List[int] = list(range(n_pages - 1, -1, -1))
        self._free_rows: List[int] = list(range(n_slots))
        self._live: Dict[int, _PagedLive] = {}
        # refcount per CLAIMED physical page (1 for private pages, +1 per
        # attached sharer); a page leaves the dict when it is freed
        self._rc: Dict[int, int] = {}
        # page-budget accounting: claimed pages (counted ONCE each, no
        # matter how many requests share them) + every live request's
        # not-yet-claimed private reservation.  Admission gates new
        # private needs against this, which is what makes grow-on-decode
        # infallible even under sharing.
        self._reserved_total = 0
        self.table = np.full((n_slots, pages_per_slot), self.trash_page,
                             np.int32)
        self.write_table = np.full((n_slots, pages_per_slot),
                                   self.trash_page, np.int32)
        # rid -> final claimed page tuple, recorded at release (tests and
        # benchmarks assert fragmentation: requests span non-contiguous
        # physical pages)
        self.page_history: Dict[int, Tuple[int, ...]] = {}
        self.n_allocated = 0   # pages claimed (conservation counters)
        self.n_freed = 0       # pages returned (refcount hit zero)
        # sharing evidence (benchmark / regression-gate counters)
        self.n_shared_attached = 0   # page attachments through the index
        self.max_refcount = 0        # high-water refcount ever observed
        self.peak_used_pages = 0     # high-water used_pages

    @property
    def trash_page(self) -> int:
        """Reserved physical page absorbing writes from inactive slots and
        unclaimed logical pages (index ``n_pages``: one past the real
        pool, so leaves carry ``n_pages + 1`` physical pages)."""
        return self.n_pages

    @property
    def view_len(self) -> int:
        return self.pages_per_slot * self.page_size

    @property
    def free_page_count(self) -> int:
        return len(self._free_pages)

    @property
    def used_pages(self) -> int:
        return self.n_pages - len(self._free_pages)

    @property
    def reserved_pages(self) -> int:
        return self._reserved_total

    @property
    def free_count(self) -> int:
        """Admittable-request lower bound (kept for engine fast-paths):
        0 when no row or no unreserved page remains."""
        if not self._free_rows:
            return 0
        return max(0, self.n_pages - self._reserved_total)

    @property
    def used_count(self) -> int:
        return len(self._live)

    @property
    def drained(self) -> bool:
        return not self._live

    @property
    def occupancy(self) -> float:
        """Instantaneous used-page fraction (the metrics-plane gauge)."""
        return self.used_pages / self.n_pages

    def pages_needed(self, prompt_len: int, max_new: int) -> int:
        """Worst-case pages a request can ever hold: prompt positions plus
        the ``max_new - 1`` decode writes (the final token is returned but
        never written back)."""
        tokens = prompt_len + max(max_new - 1, 0)
        return -(-tokens // self.page_size)

    def prefill_pages(self, prompt_len: int) -> int:
        return -(-prompt_len // self.page_size)

    def live_pages(self, rid: int) -> Tuple[int, ...]:
        return tuple(self._live[rid].pages)

    def shared_pages(self, rid: int) -> Tuple[int, ...]:
        """The attached (index-matched) head of ``rid``'s page chain."""
        e = self._live[rid]
        return tuple(e.pages[:e.n_shared])

    def refcount(self, page: int) -> int:
        return self._rc.get(page, 0)

    def _match(self, request) -> List[int]:
        """Physical pages ``request`` can attach to (empty when sharing
        is off).  Pure read — can_admit probes it speculatively."""
        if self.prefix_index is None:
            return []
        return self.prefix_index.match(request.prompt)

    def _claim_one(self, rid: int) -> int:
        e = self._live[rid]
        if len(e.pages) - e.n_shared >= e.private_reserved:
            raise RuntimeError(
                f"request {rid} grew past its reservation of "
                f"{e.private_reserved} private pages — admission must "
                f"reserve the worst-case decode length")
        if not self._free_pages:
            raise RuntimeError(
                "page pool exhausted despite reservations — allocator "
                "invariant broken (claimed pages must never exceed the "
                "reserved total)")
        page = self._free_pages.pop()
        e.pages.append(page)
        self._rc[page] = 1
        self.max_refcount = max(self.max_refcount, 1)
        col = len(e.pages) - 1
        self.table[e.slot, col] = page
        self.write_table[e.slot, col] = page   # private: writable
        self.n_allocated += 1
        self.peak_used_pages = max(self.peak_used_pages, self.used_pages)
        return page

    # ---- pool-agnostic admission interface -----------------------------
    def can_admit(self, request) -> bool:
        """True iff ``admit`` would succeed right now: a free decode row
        AND enough unreserved pages for the request's worst case *after*
        subtracting the prefix pages the index can already supply.

        Callers may rely on: (a) no side effects — the prefix match is a
        pure dict walk; (b) can_admit-then-admit consistency within one
        scheduler step (nothing mutates capacity or the index between
        them); (c) reserving the PRIVATE worst case up front is what
        keeps the plane preemption-free — grow-on-decode can never fail
        mid-flight, shared or not, because growth pages are always part
        of the private reservation."""
        if not self._free_rows:
            return False
        need = self.pages_needed(request.prompt_len, request.max_new)
        if need > self.pages_per_slot:
            raise RuntimeError(
                f"request needs {need} pages but a slot's view holds only "
                f"{self.pages_per_slot} — admission control must bound "
                f"prompt_len + max_new to the configured cache length")
        private_need = need - len(self._match(request))
        return self._reserved_total + private_need <= self.n_pages

    def admit(self, request) -> int:
        """Admit ``request``: attach the longest materialized shared
        prefix (refcount + 1 per page, zero new pages), claim the rest
        of its prompt pages privately, and reserve its remaining private
        worst case.  Returns the decode-row slot.

        Callers may rely on: (a) the returned slot's ``table`` row maps
        every already-claimed logical page, shared head first;
        (b) ``write_table`` masks attached pages to the trash page from
        the very first dispatch, so the request can never write what it
        shares; (c) full prompt pages this request claims privately are
        *registered* for future sharing but attachable only after
        ``seal_prefilled`` — nobody can share an unwritten page;
        (d) raises instead of over-committing (gate on ``can_admit``)."""
        if not self.can_admit(request):
            raise RuntimeError("page pool cannot admit this request")
        slot = heapq.heappop(self._free_rows)
        need = self.pages_needed(request.prompt_len, request.max_new)
        shared = self._match(request)
        e = _PagedLive(slot=slot, private_reserved=need - len(shared),
                       pages=[], n_shared=len(shared), pending_keys=[])
        self._live[request.rid] = e
        self._reserved_total += e.private_reserved
        for col, page in enumerate(shared):       # attach, never write
            e.pages.append(page)
            self._rc[page] += 1
            self.max_refcount = max(self.max_refcount, self._rc[page])
            self.table[slot, col] = page
            self.write_table[slot, col] = self.trash_page
            self.n_shared_attached += 1
        for _ in range(self.prefill_pages(request.prompt_len)
                       - len(shared)):
            page = self._claim_one(request.rid)
            col = len(e.pages) - 1
            # a full prompt page this request creates becomes shareable
            # once its prefill lands (partial pages stay private: decode
            # writes continue into them — the page-granular CoW copy)
            if (self.prefix_index is not None
                    and (col + 1) * self.page_size <= request.prompt_len):
                key = page_key(request.prompt, col, self.page_size)
                if self.prefix_index.register(key, page):
                    e.pending_keys.append((page, key))
        return slot

    def seal_prefilled(self, requests) -> None:
        """Publish the shareable pages the prefill dispatch just wrote:
        materialize their index entries (followers may attach from the
        NEXT scheduler step on) and write-protect them in
        ``write_table`` — from here on no dispatch carries a writable
        alias of a shareable page.

        Callers may rely on: ordering — the engine calls this after the
        prefill call and before the step's decode dispatch, so a sealed
        page is never gathered before it holds real KV bytes."""
        if self.prefix_index is None:
            return
        for r in requests:
            e = self._live.get(r.rid)
            if e is None:
                continue
            for page, _key in e.pending_keys:
                self.prefix_index.materialize(page)
                col = e.pages.index(page)
                self.write_table[e.slot, col] = self.trash_page
            e.pending_keys = []

    def grow_to(self, rid: int, n_tokens: int) -> None:
        """Claim pages until the request's claimed region covers
        ``n_tokens`` cache positions (grow-on-decode).  Growth pages are
        always private — the shared head never grows."""
        e = self._live[rid]
        while len(e.pages) * self.page_size < n_tokens:
            self._claim_one(rid)

    def prepare_decode(self, requests, k: int) -> None:
        """Claim every page the next ``k`` fused decode steps will write:
        step i writes position ``prompt_len + (n_generated - 1) + i``, so
        the claimed region must cover ``prompt_len + n_generated - 1 + k``
        tokens.

        Callers may rely on: infallibility for admitted requests — the
        admission-time private reservation covers every growth page, so
        this can never raise mid-flight (no preemption, no OOM), with or
        without sharing."""
        for r in requests:
            self.grow_to(r.rid, r.prompt_len + r.n_generated - 1 + k)

    def release(self, request) -> None:
        """Return ``request``'s capacity: decrement every held page's
        refcount, free the pages that hit zero (evicting their index
        entries), give back the unclaimed private reservation, reset the
        slot's table rows, and free the decode row.

        Callers may rely on: (a) retire-before-admit — capacity released
        here is admissible in the same scheduler step; (b) conservation —
        a shared page is freed exactly once, by its LAST holder, so
        ``n_allocated == n_freed`` at drain and every refcount is zero;
        (c) an index entry never names a freed page; (d) safe for
        requests killed mid-flight (the fleet requeue path) — partially
        grown requests release cleanly."""
        rid = request.rid
        if rid not in self._live:
            raise RuntimeError(f"request {rid} holds no pages")
        e = self._live.pop(rid)
        self.page_history[rid] = tuple(e.pages)
        # unclaimed private reservation comes back whole...
        self._reserved_total -= (e.private_reserved
                                 - (len(e.pages) - e.n_shared))
        # ...claimed pages come back one refcount at a time.  Push in
        # reverse so the request's FIRST freed page is on top of the
        # stack — the next claim reuses the hottest line first.
        for page in reversed(e.pages):
            self._rc[page] -= 1
            if self._rc[page] == 0:
                del self._rc[page]
                if self.prefix_index is not None:
                    self.prefix_index.evict(page)
                self._free_pages.append(page)
                self._reserved_total -= 1
                self.n_freed += 1
        self.table[e.slot, :] = self.trash_page
        self.write_table[e.slot, :] = self.trash_page
        heapq.heappush(self._free_rows, e.slot)


# ===========================================================================
# tensor helpers
# ===========================================================================

def write_slot(pool_tree, request_tree, slot: int):
    """Splice a single-request cache (batch dim 1) into pool row ``slot``.

    Every leaf is written whole, including its zero tail beyond the
    prompt, so the slot carries no state from a previous occupant.
    """
    return jax.tree_util.tree_map(
        lambda pool, one: jax.lax.dynamic_update_slice_in_dim(
            pool, one.astype(pool.dtype), slot, axis=BATCH_AXIS),
        pool_tree, request_tree)


def _trash_mask(table, n_phys: int, rank: int):
    """(1, S, npp, 1, ...) bool: True where a table entry is the trash
    page (id ``n_phys - 1``), broadcastable against gathered pages."""
    mask = table == (n_phys - 1)
    return mask.reshape((1,) + mask.shape + (1,) * (rank - 3))


def gather_page_view(pool_tree, table):
    """Physical page pool -> per-slot contiguous view.

    Leaves are ``(L, n_pages + 1, page_size, ...)``; ``table`` is the
    (n_slots, pages_per_slot) int32 READ map — shared physical pages may
    appear in several rows, which is exactly how prefix sharing reuses
    one prompt's KV across requests.  Returns leaves of shape
    ``(L, n_slots, pages_per_slot * page_size, ...)`` — exactly the slot
    plane's layout, so the slot plane's decode math runs on the view and
    positions beyond a request's depth (stale bytes in freshly claimed
    pages) are masked by decode attention.  Serving decode does not
    build it (it reads pages in place); it is the oracle the in-place
    path is tested against.

    Trash-backed logical pages are forced to exact ZEROS rather than the
    trash page's bytes: the trash page absorbs racing duplicate scatter
    writes, and a torn write could leave inf/NaN bit patterns there —
    attention masking zeroes the *probability* of those positions, but
    ``0 * inf`` in the value contraction would still be NaN.  Zeros are
    inert under masking exactly.
    """
    def gather(leaf):
        g = leaf[:, table]                     # (L, S, npp, ps, ...)
        g = jax.numpy.where(_trash_mask(table, leaf.shape[1], g.ndim),
                            jax.numpy.zeros((), g.dtype), g)
        L, S, npp, ps = g.shape[:4]
        return g.reshape(L, S, npp * ps, *g.shape[4:])
    with jax.named_scope("page_gather"):
        return jax.tree_util.tree_map(gather, pool_tree)


def scatter_page_view(pool_tree, view_tree, table):
    """Per-slot contiguous view -> physical page pool (inverse gather).

    ``table`` here is the WRITE map: page ownership of its non-trash
    entries is exclusive among live requests (shared pages are masked to
    the trash page for every holder — the copy-on-write discipline), so
    slot views write disjoint physical pages.  Every DUPLICATE index in
    the map is therefore the trash page; its updates are forced to zero
    so all racing writers carry identical bytes — the scatter's
    nondeterministic duplicate ordering then cannot produce torn values
    (and the trash page stays all-zero for the pool's lifetime).
    """
    def scatter(leaf, view):
        L, S, Tv = view.shape[:3]
        npp = table.shape[1]
        pages = view.reshape(L, S, npp, Tv // npp, *view.shape[3:])
        pages = jax.numpy.where(_trash_mask(table, leaf.shape[1],
                                            pages.ndim),
                                jax.numpy.zeros((), pages.dtype), pages)
        return leaf.at[:, table].set(pages)
    with jax.named_scope("page_scatter"):
        return jax.tree_util.tree_map(scatter, pool_tree, view_tree)
