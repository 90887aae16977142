"""Standing batched KV-cache manager for the serve engine.

Owns one decode cache of ``n_slots`` batch slots allocated at the decode
budget (``model.cache_init(cfg, n_slots, budget)``) and keeps it resident
across the engine's whole lifetime — requests come and go, the cache
arrays never reallocate.  Admission packs a new request's prefilled
(batch=1, budget-aligned) cache into its slot with one jitted
``dynamic_update_slice`` per leaf (``serve.step.cache_slot_insert``);
because the slot index is a traced scalar, inserting into slot 0 and slot
7 share a single compiled program.

Invariant: every slot independently satisfies the ring invariant — slot
``j`` of sequence ``b``'s ring of width ``W`` holds absolute position
``p ≡ j (mod W)`` — because ``align_prefill_cache`` establishes it at the
standing budget and per-sequence decode writes (``widx[b] = pos[b] mod
W``) maintain it per batch row.  Retirement needs no cache work at all:
a stale slot is garbage-masked (its next admission overwrites every slot
of the ring and the pos plane wholesale).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels.decode_attention.decode_attention import last_live_page
from ...models import model as M
from .. import paging as P
from ..step import cache_slot_extract, cache_slot_insert

# one compiled insert/extract shared by every manager instance (jit
# caches on pytree structure + slot is traced, so all slots, all
# managers of the same config reuse a single program)
insert_jit = jax.jit(cache_slot_insert)
extract_jit = jax.jit(cache_slot_extract)

# paged-pool device ops, shared the same way (cfg is the static arg;
# page ids and the slot index are traced, so every admission/retirement
# of a given config reuses one compiled scatter/gather/scrub/copy)
paged_insert_jit = jax.jit(P.insert_pages, static_argnums=0)
paged_extract_jit = jax.jit(P.extract_pages, static_argnums=0)
paged_scrub_jit = jax.jit(P.scrub_pages, static_argnums=0)
paged_gather_jit = jax.jit(P.gather_prefix, static_argnums=0)
paged_copy_jit = jax.jit(P.copy_pages, static_argnums=0)


class CowBatch:
    """Per-tick accumulator for copy-on-write page copies.

    :meth:`PagedCacheManager.prepare_write` plans copies slot by slot
    (``{kind: ([srcs], [dsts])}``); paying one device dispatch per slot
    would serialize the Decode lane behind a chain of tiny copies.  The
    engine folds every slot's plan in here and drains the tick's union
    as **one** ``paged_copy_jit`` argument pair: per-kind copy lists
    padded to a shared power-of-two width with null→null identity
    copies (the null page is garbage by contract, so copying it onto
    itself is a no-op), which keeps the copy program compiling once per
    width bucket instead of once per exact list-length combination."""

    def __init__(self, kinds):
        self._pending: Dict[str, Tuple[List[int], List[int]]] = \
            {kind: ([], []) for kind in kinds}

    def add(self, plan: Dict[str, Tuple[List[int], List[int]]]) -> int:
        """Fold one slot's copy plan in; returns the number of real
        (non-padding) copies it contributed, for the engine's
        ``cow_copies`` accounting."""
        for kind, (s, d) in plan.items():
            self._pending[kind][0].extend(s)
            self._pending[kind][1].extend(d)
        return sum(len(s) for s, _ in plan.values())

    def drain(self) -> Optional[Tuple[Dict[str, jnp.ndarray],
                                      Dict[str, jnp.ndarray]]]:
        """The padded device ``(src, dst)`` dicts for ``paged_copy_jit``
        — or ``None`` when nothing is pending — and reset.  Every kind
        is padded to the same power-of-two width so the uniform pytree
        structure hits one compiled copy program per bucket."""
        n = max(len(s) for s, _ in self._pending.values())
        if n == 0:
            return None
        nb = 1
        while nb < n:
            nb *= 2
        src, dst = {}, {}
        for kind, (s, d) in self._pending.items():
            a = np.full(nb, P.PAGE_NULL, np.int32)
            a[:len(s)] = s
            b = np.full(nb, P.PAGE_NULL, np.int32)
            b[:len(d)] = d
            src[kind] = jnp.asarray(a)
            dst[kind] = jnp.asarray(b)
            self._pending[kind] = ([], [])
        return src, dst


class BatchedCacheManager:
    def __init__(self, cfg: M.ModelConfig, n_slots: int, budget: int):
        self.cfg = cfg
        self.n_slots = n_slots
        self.budget = budget
        self.cache: Dict[str, Any] = M.cache_init(cfg, n_slots, budget)

    def insert(self, one_cache: Dict[str, Any], slot: int) -> None:
        """Pack a batch=1 budget-aligned cache into ``slot`` in place."""
        self.cache = insert_jit(self.cache, one_cache, jnp.int32(slot))

    def extract(self, slot: int) -> Dict[str, Any]:
        """Batch=1 view of ``slot`` (debugging / migration)."""
        return extract_jit(self.cache, jnp.int32(slot))

    def update(self, cache: Dict[str, Any]) -> None:
        """Adopt the cache pytree returned by a batched decode step."""
        self.cache = cache


class PagedCacheManager:
    """Block-granular cache manager over the paged KV pool.

    Owns the per-kind arenas (``paging.paged_cache_init``), the host-side
    page tables, a refcounted :class:`~repro.serve.paging.PageAllocator`
    per cache kind, and (with ``prefix_sharing``) a
    :class:`~repro.serve.paging.PrefixIndex` per kind.  Slots cost
    nothing until pages are bound to them: admission allocates exactly
    the pages the prompt fills — mapping any already-resident shared
    prefix by reference instead (``match_prefix``/``admit_pages``) —
    decode grows a sequence one page at a time and copies-on-write off
    shared pages (``prepare_write``), and retirement drops references,
    returning a page to the free list only at refcount 0.

    ``pool_pages`` caps the allocatable pages of every kind (clamped to
    the dense-equivalent full provision ``n_slots · W/page_size``; at
    least one budget-length sequence must always fit).  The default
    (None) is full provision — paged layout with dense capacity.

    Prefix sharing is disabled automatically for configs with state
    caches (ssm / rec): a mid-prompt prefill restart would need the
    prefix-final recurrent state, which pages do not carry.
    """

    def __init__(self, cfg: M.ModelConfig, n_slots: int, budget: int,
                 page_size: int = 4, pool_pages: Optional[int] = None,
                 prefix_sharing: bool = True):
        self.cfg = cfg
        self.n_slots = n_slots
        self.budget = budget
        self.page_size = page_size
        self.widths = P.kv_widths(cfg, budget)
        assert self.widths, \
            "paged serving needs at least one attention cache kind"
        self.n_ptes: Dict[str, int] = {}
        arena: Dict[str, int] = {}
        for kind, W in self.widths.items():
            assert W % page_size == 0, \
                f"page_size {page_size} must divide the {kind!r} ring " \
                f"width {W}"
            n_ptes = W // page_size
            full = n_slots * n_ptes
            cap = full if pool_pages is None else min(pool_pages, full)
            assert cap >= n_ptes, \
                f"pool of {cap} {kind!r} pages cannot hold one " \
                f"budget-length sequence ({n_ptes} pages)"
            self.n_ptes[kind] = n_ptes
            arena[kind] = cap
        self.alloc = {kind: P.PageAllocator(cap + 1)
                      for kind, cap in arena.items()}
        self.tables = {kind: np.full((n_slots, n), P.PAGE_NULL, np.int32)
                       for kind, n in self.n_ptes.items()}
        has_state = any(
            kind in ("ssm", "rec")
            for kinds, _ in M.cache_layout(cfg) for kind in kinds)
        self.sharing = bool(prefix_sharing) and not has_state
        self.prefix: Dict[str, P.PrefixIndex] = \
            {kind: P.PrefixIndex(page_size) for kind in self.widths} \
            if self.sharing else {}
        self.cache: Dict[str, Any] = P.paged_cache_init(
            cfg, n_slots, budget, page_size, arena)
        self._dirty = True
        # table rows mutated since the last sync — the only rows the
        # stale-entry validation needs to rescan (everything else was
        # proven clean by an earlier sync)
        self._touched: Dict[str, set] = \
            {kind: set(range(n_slots)) for kind in self.widths}

    # -- page accounting -------------------------------------------------
    def used_ptes(self, kind: str, n_positions: int) -> int:
        """Pages of ``kind`` a sequence with ``n_positions`` written
        positions occupies: the ring wraps in place once full."""
        W = self.widths[kind]
        if n_positions >= W:
            return self.n_ptes[kind]
        return math.ceil(max(n_positions, 0) / self.page_size)

    def decode_walk(self, pos: np.ndarray) -> Tuple[int, int]:
        """Pages the paged decode kernel walks for one step at the
        positions ``pos`` (one entry per row of the step, ``-1`` for a
        padded or idle row), summed over the cache kinds, and the pages
        its grid spans (rows × ``n_ptes``)."""
        live = budget = 0
        for kind, n_ptes in self.n_ptes.items():
            live += int((last_live_page(pos, self.widths[kind],
                                        self.page_size) + 1).sum())
            budget += len(pos) * n_ptes
        return live, budget

    def match_prefix(self, prompt, chain=None
                     ) -> Tuple[int, Dict[str, List[int]]]:
        """Longest resident shared prefix of ``prompt`` (full pages
        only, uniform across kinds).  Returns
        ``(shared_tokens, {kind: page-id run})`` — ``(0, {})`` when
        sharing is off, when the prompt would wrap any kind's ring
        (``L > W``: that ring cannot retain the prefix at its logical
        front), or when nothing matches.  Capped at ``prompt_len - 1``
        so admission always prefills at least the final token (the
        first output token falls out of the prefill logits).  Pure —
        admission re-matches per candidate, so pages registered by an
        earlier admission in the same tick are already visible.
        ``chain``: the sequence's :class:`paging.PrefixChain` — carries
        the running hash across ticks so re-matching a queued prompt
        costs zero hashes instead of re-walking the chain."""
        L = len(prompt)
        if not self.sharing or any(L > W for W in self.widths.values()):
            return 0, {}
        cap = (L - 1) // self.page_size
        if cap <= 0:
            return 0, {}
        # the chain keys depend only on tokens and page size (uniform
        # across kinds): hash once, bounded by cap, probe every index
        if chain is not None:
            keys = chain.keys(prompt, cap)
        else:
            keys = list(next(iter(self.prefix.values())).keys(prompt, cap))
        runs = {kind: idx.match_keys(keys)
                for kind, idx in self.prefix.items()}
        m = min(len(r) for r in runs.values())
        if m <= 0:
            return 0, {}
        return m * self.page_size, {kind: r[:m] for kind, r in runs.items()}

    def exclusive_pages(self, slot: int) -> int:
        """Pages (all kinds) only ``slot``'s table references — the
        pages a preemption of this slot would actually return to the
        free list.  Evicting a sequence whose pages are mostly shared
        (refcount > 1) relieves almost no pool pressure, so the engine's
        victim score is dominated by this count (DESIGN.md
        "Sharing-aware scheduling")."""
        n = 0
        for kind in self.widths:
            alloc = self.alloc[kind]
            for p in self.tables[kind][slot]:
                if p != P.PAGE_NULL and alloc.refcount(p) == 1:
                    n += 1
        return n

    def pin_shared_prefix(self, slot: int, tokens, chain=None
                          ) -> Tuple[int, Dict[str, List[int]]]:
        """Pin (refcount++) the leading run of ``slot``'s *genuinely
        shared* prefix pages across a preemption: pages that are (a)
        still registered in the prefix index under the slot's own chain
        keys and (b) referenced by another holder too (refcount > 1).
        Returns ``(pinned_tokens, {kind: page run})`` — the pin keeps
        those pages resident and registered until the sequence resumes
        (``match_resume`` finds them again and maps them by reference)
        or dies (``release_pinned``), even if every co-sharer retires in
        between.  Restricting pins to refcount > 1 pages means the
        preemption frees exactly the pages it would have freed anyway —
        pinning never blunts pool relief.  ``tokens`` is the sequence's
        *written* token run (prompt + decode-written outputs)."""
        if not self.sharing:
            return 0, {}
        L = len(tokens)
        if any(L > W for W in self.widths.values()):
            return 0, {}        # a wrapped ring holds no logical prefix
        cap = L // self.page_size
        if cap <= 0:
            return 0, {}
        if chain is not None:
            keys = chain.keys(tokens, cap)
        else:
            keys = list(next(iter(self.prefix.values())).keys(tokens, cap))
        m = cap
        for kind, idx in self.prefix.items():
            row = self.tables[kind][slot]
            k = 0
            while k < m:
                page = int(row[k])
                if (page == P.PAGE_NULL or
                        self.alloc[kind].refcount(page) <= 1 or
                        idx.page_for(keys[k]) != page):
                    break
                k += 1
            m = k
            if m == 0:
                return 0, {}
        kept: Dict[str, List[int]] = {}
        for kind in self.widths:
            run = [int(p) for p in self.tables[kind][slot][:m]]
            for p in run:
                self.alloc[kind].share(p)
            kept[kind] = run
        return m * self.page_size, kept

    def release_pinned(self, kept: Dict[str, List[int]]
                       ) -> Dict[str, np.ndarray]:
        """Drop the pin references of a :meth:`pin_shared_prefix` run
        (resume re-shared the pages through ``admit_pages``, or the
        sequence died, or the engine spilled the pins to un-wedge
        admission).  Returns the per-kind freed-page report in
        :meth:`release_slot`'s padded layout — non-null entries are
        pages that reached refcount 0 and must be scrubbed before
        reuse."""
        out: Dict[str, np.ndarray] = {}
        for kind in self.widths:
            freed = self.alloc[kind].free(kept.get(kind, ()))
            if self.sharing:
                for p in freed:
                    self.prefix[kind].forget(p)
            padded = np.full(self.n_ptes[kind], P.PAGE_NULL, np.int32)
            padded[:len(freed)] = freed
            out[kind] = padded
        return out

    def match_resume(self, tokens, chain=None
                     ) -> Tuple[int, Dict[str, List[int]]]:
        """Longest registered full-page prefix of a *resuming*
        sequence's written tokens — the swap-in analogue of
        :meth:`match_prefix`.  Differences: the cap is ``len(tokens) //
        page_size`` (nothing needs to be prefilled — the swap blob
        restores the remainder — so the final token need not be held
        back), and the wrap gate is on the written length itself (a
        sequence that wrapped some ring restores everything from the
        blob).  Matched pages are mapped by reference by
        ``admit_pages``; the pages the preemption pinned are a prefix of
        this match by construction (pins keep their registrations
        alive), so a preempt → resume cycle re-attaches to at least
        everything it was sharing before."""
        L = len(tokens)
        if not self.sharing or any(L > W for W in self.widths.values()):
            return 0, {}
        cap = L // self.page_size
        if cap <= 0:
            return 0, {}
        if chain is not None:
            keys = chain.keys(tokens, cap)
        else:
            keys = list(next(iter(self.prefix.values())).keys(tokens, cap))
        runs = {kind: idx.match_keys(keys)
                for kind, idx in self.prefix.items()}
        m = min(len(r) for r in runs.values())
        if m <= 0:
            return 0, {}
        return m * self.page_size, {kind: r[:m] for kind, r in runs.items()}

    def register_decode_page(self, slot: int, tokens, chain=None) -> None:
        """Publish the decode-produced page that just closed — the page
        holding positions ``[L - page_size, L)`` of ``tokens`` (the
        sequence's written prompt + output run, ``L`` a page multiple) —
        so later prompts that extend this sequence's prompt *and output*
        share past the prompt (agentic fan-out).  Only the single
        just-closed page is registered: earlier pages may have been
        CoW'd or wrapped since their close, so a whole-row registration
        would publish stale keys.  The closing write itself guarantees
        the page is exclusively held (a shared page is never written —
        CoW redirects first), and content equality with a prefill of the
        same tokens is the conformance suite's decode≡prefill bit-
        exactness invariant."""
        if not self.sharing:
            return
        L = len(tokens)
        t = L // self.page_size - 1
        if t < 0:
            return
        for kind, idx in self.prefix.items():
            if L > self.widths[kind]:
                continue            # this ring wrapped: page t is stale
            page = int(self.tables[kind][slot, t])
            if page == P.PAGE_NULL:
                continue
            if chain is not None:
                key = chain.keys(tokens, t + 1)[t]
            else:
                key = list(idx.keys(tokens, t + 1))[t]
            idx.register(tokens, [page], keys=[key])

    def can_ever_admit(self, n_positions: int,
                       shared_pages: int = 0) -> bool:
        """False iff a sequence with ``n_positions`` written positions
        needs more *fresh* pages of some kind than the arena could ever
        grant — no amount of retiring or preempting other sequences can
        make the admission succeed.  The engine fails such a request
        with ``OUT_OF_RESOURCES`` instead of blocking the queue on it
        forever (``can_admit`` gates the *transient* case)."""
        return all(
            self.used_ptes(kind, n_positions) - shared_pages <=
            self.alloc[kind].capacity
            for kind in self.widths)

    def can_admit(self, n_positions: int, shared_pages: int = 0) -> bool:
        """True iff every kind has the *fresh* pages a sequence with
        ``n_positions`` already-written positions needs right now, the
        first ``shared_pages`` of which are mapped by reference and cost
        nothing (optimistic: later growth is served lazily, preempting
        if the pool runs dry)."""
        return all(
            self.alloc[kind].n_free >=
            self.used_ptes(kind, n_positions) - shared_pages
            for kind in self.widths)

    def admit_pages(self, slot: int, n_positions: int,
                    shared: Optional[Dict[str, List[int]]] = None) -> bool:
        """Bind the pages for ``n_positions`` written positions to
        ``slot`` (all kinds, all-or-nothing with rollback).  With
        ``shared`` (a ``match_prefix`` run), the run is mapped by
        reference — refcount++ on already-resident pages — and only the
        remainder is freshly allocated."""
        shared = shared or {}
        granted: List = []
        for kind in self.widths:
            m = len(shared.get(kind, ()))
            ids = self.alloc[kind].alloc(
                self.used_ptes(kind, n_positions) - m)
            if ids is None:
                for k, i in granted:
                    self.alloc[k].free(i)
                return False
            granted.append((kind, ids))
        for kind, ids in granted:
            pre = [int(p) for p in shared.get(kind, ())]
            for p in pre:
                self.alloc[kind].share(p)
            row = self.tables[kind][slot]
            row[:] = P.PAGE_NULL
            row[:len(pre)] = pre
            row[len(pre):len(pre) + len(ids)] = ids
            self._touched[kind].add(slot)
        self._dirty = True
        return True

    def register_prefix(self, slot: int, prompt, chain=None) -> None:
        """Publish the slot's full-page prompt blocks in the prefix
        index so later admissions with the same prefix map them by
        reference.  Skips kinds whose ring wrapped during prefill
        (``L > W``: the logical front no longer holds the prefix);
        idempotent for pages that were themselves mapped from the
        index.  ``chain``: precomputed :class:`paging.PrefixChain` —
        registration reuses the admission-time keys (O(new pages))."""
        if not self.sharing:
            return
        L = len(prompt)
        for kind, idx in self.prefix.items():
            if L > self.widths[kind]:
                continue
            n_full = L // self.page_size
            keys = chain.keys(prompt, n_full) if chain is not None else None
            idx.register(prompt, self.tables[kind][slot][:n_full],
                         keys=keys)

    def prepare_write(self, slot: int, pos: int
                      ) -> Optional[Dict[str, Tuple[List[int], List[int]]]]:
        """Make the ring slot position ``pos`` writes to writable in
        every kind: lazily allocate the backing page (growth), and when
        the page is shared (refcount > 1) allocate a copy-on-write
        target and swap the table entry.  Returns ``{kind: ([src],
        [dst])}`` — the page copies the caller must run
        (``paging.copy_pages``) *before* the decode step so the write
        lands in a private copy (``{}`` when none are needed) — or None
        on pool exhaustion with every partial grant rolled back (the
        engine preempts and retries; preemption may itself drop a
        refcount to 1 and obviate the copy).  An exclusive in-place
        write (refcount == 1) deregisters the page from the prefix
        index: its content is about to stop being the registered
        prefix."""
        grow: List[Tuple[str, int, int]] = []
        cow: List[Tuple[str, int, int, int]] = []
        inplace: List[Tuple[str, int]] = []
        for kind, W in self.widths.items():
            pte = (pos % W) // self.page_size
            page = int(self.tables[kind][slot, pte])
            if page == P.PAGE_NULL:
                ids = self.alloc[kind].alloc(1)
                if ids is None:
                    self._rollback(grow, cow)
                    return None
                grow.append((kind, pte, ids[0]))
            elif self.alloc[kind].refcount(page) > 1:
                ids = self.alloc[kind].alloc(1)
                if ids is None:
                    self._rollback(grow, cow)
                    return None
                cow.append((kind, pte, page, ids[0]))
            else:
                inplace.append((kind, page))
        for kind, pte, page in grow:
            self.tables[kind][slot, pte] = page
            self._touched[kind].add(slot)
            self._dirty = True
        out: Dict[str, Tuple[List[int], List[int]]] = {}
        for kind, pte, src, dst in cow:
            self.tables[kind][slot, pte] = dst
            freed = self.alloc[kind].free([src])
            assert not freed, "CoW source was exclusively held"
            out.setdefault(kind, ([], []))
            out[kind][0].append(src)
            out[kind][1].append(dst)
            self._touched[kind].add(slot)
            self._dirty = True
        if self.sharing:
            for kind, page in inplace:
                self.prefix[kind].forget(page)
        return out

    def _rollback(self, grow, cow) -> None:
        for kind, _, page in grow:
            self.alloc[kind].free([page])
        for kind, _, _, dst in cow:
            self.alloc[kind].free([dst])

    def release_slot(self, slot: int) -> Dict[str, np.ndarray]:
        """Drop the slot's page references and null its table rows.
        Returns, per kind, the page ids that actually reached refcount
        0 — padded to the row width with :data:`~repro.serve.paging.
        PAGE_NULL` so the scrub program never retraces — the **only**
        pages whose validity planes the caller may scrub
        (``paging.scrub_pages``).  Pages another sequence still
        references stay resident, registered, and untouched: a scrub of
        a freed-but-shared page is impossible because release never
        reports one."""
        out: Dict[str, np.ndarray] = {}
        for kind in self.widths:
            row = self.tables[kind][slot]
            freed = self.alloc[kind].free(
                int(p) for p in row if p != P.PAGE_NULL)
            if self.sharing:
                for p in freed:
                    self.prefix[kind].forget(p)
            padded = np.full(self.n_ptes[kind], P.PAGE_NULL, np.int32)
            padded[:len(freed)] = freed
            out[kind] = padded
            self.tables[kind][slot] = P.PAGE_NULL
            self._touched[kind].add(slot)
        self._dirty = True
        return out

    def table_ids(self, slot: int) -> Dict[str, np.ndarray]:
        """Copy of the slot's current page-table rows (per kind)."""
        return {kind: self.tables[kind][slot].copy()
                for kind in self.widths}

    # -- device side -----------------------------------------------------
    def sync(self) -> None:
        """Push the host tables into the cache pytree's ``page_table``
        leaves (no-op when nothing changed since the last sync).  A
        stale entry — a non-null table slot naming a page the allocator
        no longer holds — raises before anything reaches the device:
        decoding through it would read (or scrub-race) a freed page.
        Only rows touched since the last sync are rescanned (earlier
        syncs proved the rest clean), so the check stays O(mutations),
        not O(table), on the per-tick path."""
        if not self._dirty:
            return
        for kind, slots in self._touched.items():
            table = self.tables[kind]
            for s in slots:
                for p in table[s]:
                    if p != P.PAGE_NULL and \
                            self.alloc[kind].refcount(p) == 0:
                        raise AssertionError(
                            f"stale page-table entry: {kind!r} page "
                            f"{int(p)} (slot {s}) is not held by the "
                            "allocator")
            slots.clear()
        self.cache = P.with_page_tables(self.cfg, self.cache, self.tables)
        self._dirty = False

    def update(self, cache: Dict[str, Any]) -> None:
        """Adopt the cache pytree returned by a decode / insert / scrub
        step."""
        self.cache = cache

    # -- stats -----------------------------------------------------------
    def pages_held(self) -> Dict[str, int]:
        return {kind: a.n_held for kind, a in self.alloc.items()}

    def occupancy(self) -> Dict[str, float]:
        """Per-kind held fraction of the arena (0.0–1.0) — the pool
        occupancy the serve metrics gauge reports."""
        return {kind: a.n_held / a.capacity
                for kind, a in self.alloc.items()}

    def resident_bytes(self) -> int:
        """K/V bytes of the standing arenas (the pool's real footprint)."""
        return P.kv_resident_bytes(self.cache)


__all__ = ["BatchedCacheManager", "PagedCacheManager", "paged_insert_jit",
           "paged_extract_jit", "paged_scrub_jit", "paged_gather_jit",
           "paged_copy_jit"]
