"""Paged KV-cache pool: block-table paging over one shared device arena.

The QMC deployment splits the memory system so LPDDR5 carries *only* the
dynamic KV stream (weights live in eMEMs). This module is the serving-side
half of that bargain: instead of one contiguous ``[1, max_len, kv_dim]``
slab per decode slot, every sequence draws fixed-size pages from a single
``[n_pages, page, kv_dim]`` arena (per layer group), addressed through a
per-sequence block table. That gives

  * O(page) internal fragmentation instead of O(max_len) over-allocation,
  * free-list recycling the moment a sequence finishes, and
  * a single batched decode step over all slots (the gather path in
    ``models/attention.py``) rather than N sequential batch-1 calls.

Page-size choice is a memory-system knob, not just a software one: a page
is the granule the paged gather streams from DRAM, so it should be a
multiple of the LPDDR5 burst (64 B bus transactions in
``memsys/devices.py``). The default ``page=16`` tokens keeps every
per-head page a whole number of bursts for both the fp and int8 cache
layouts; ``memsys.workload.kv_traffic_paged`` charges this page-rounded
traffic — the live pages the block-table-aware Pallas kernel
(``kernels/paged_attention.py``, engine ``paged_attention=True``) really
streams. (The XLA reference gather in ``models/attention.py``
materializes the full table width instead — ``live_only=False`` in the
traffic model.)

Host-side metadata (free list, block tables, per-slot lengths) lives here;
the device arena itself is an ordinary cache pytree built by
``models.kvcache.paged_init_cache`` and threaded through ``jax.jit`` by the
engine. Page 0 is reserved as the null page for inactive decode lanes.

Pages are **reference counted** so the prefix cache
(``serve/prefix_cache.py``) can alias one physical page into many block
tables: a page's refcount is the number of slot mappings plus one if the
prefix-cache index holds it. A shared page (refcount > 1) is never
scattered into — the first divergent write goes through :meth:`cow`,
which hands the slot a private copy and decrements the shared count.
The free list only ever holds refcount-0 pages; double-frees and frees of
still-referenced pages raise instead of silently corrupting the arena.
"""
from __future__ import annotations

from collections import deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.memsys.workload import pages_for  # noqa: F401  (canonical rule)
from repro.models import kvcache as KV
from repro.models.config import ModelConfig


class PoolExhausted(Exception):
    """Raised when an allocation cannot be satisfied even after preemption."""


class PageAccountingError(AssertionError):
    """Refcount / free-list invariant violation (a COW or lifetime bug)."""


class PagedKVPool:
    """Free-list page allocator + per-slot block tables + page refcounts.

    Pure host-side bookkeeping: device state is the arena pytree the engine
    owns. ``n_pages`` counts usable pages; one extra null page (id 0) is
    always added to the arena.
    """

    def __init__(self, cfg: ModelConfig, *, n_pages: int, page: int,
                 max_slots: int, max_pages_per_seq: int,
                 cache_dtype=jnp.float32):
        if page & (page - 1):
            raise ValueError(f"page size must be a power of 2, got {page}")
        self.cfg = cfg
        self.page = page
        self.n_pages = n_pages
        self.max_slots = max_slots
        self.max_pages_per_seq = max_pages_per_seq
        self.cache_dtype = cache_dtype
        # page 0 = null page -> usable ids are 1..n_pages
        self.free: deque = deque(range(1, n_pages + 1))
        self._free_set = set(self.free)      # O(1) double-free detection
        self.ref = np.zeros(n_pages + 1, np.int32)   # refcount per page id
        self.slot_pages: List[List[int]] = [[] for _ in range(max_slots)]
        self.block_tables = np.zeros((max_slots, max_pages_per_seq),
                                     np.int32)
        self.pages_peak = 0
        self.cow_copies = 0
        # host↔device page-op round-trip counters (cumulative over the
        # pool's life; the engine diffs them per run into EngineStats —
        # these quantify the prefix-cache adopt/COW host overhead):
        # adopt_calls counts page-adoption events (block-table rewrites
        # for cached/dedup'd pages), tables_rebuilds counts device_tables
        # host→device uploads
        self.adopt_calls = 0
        self.tables_rebuilds = 0
        # set on every block-table mutation, cleared by the engine after
        # it pushes the tables to the device (the fused apply_page_ops
        # flush) — pure decode rounds skip the rebuild entirely
        self.tables_dirty = True

    # ---- allocation ----------------------------------------------------
    @property
    def free_count(self) -> int:
        return len(self.free)

    @property
    def used_count(self) -> int:
        return self.n_pages - len(self.free)

    @property
    def pinned_count(self) -> int:
        """Pages mapped by at least one live slot (never evictable)."""
        return len({p for pages in self.slot_pages for p in pages})

    @property
    def cached_only_count(self) -> int:
        """Pages held only by the prefix-cache index (evictable)."""
        return self.used_count - self.pinned_count

    def can_fit(self, n_tokens: int) -> bool:
        return pages_for(n_tokens, self.page) <= len(self.free)

    def _pop_free(self) -> int:
        pid = self.free.popleft()
        self._free_set.discard(pid)
        if self.ref[pid] != 0:
            raise PageAccountingError(
                f"page {pid} on the free list with refcount "
                f"{self.ref[pid]}")
        self.ref[pid] = 1
        return pid

    def release(self, pid: int) -> bool:
        """Drop one reference to pid; recycle it when the count hits 0.

        Returns True when the page actually went back to the free list.
        Raises :class:`PageAccountingError` on double-free (page already
        free) or on a refcount underflow."""
        if pid in self._free_set:
            raise PageAccountingError(f"double free of page {pid}")
        if self.ref[pid] <= 0:
            raise PageAccountingError(
                f"release of page {pid} with refcount {self.ref[pid]}")
        self.ref[pid] -= 1
        if self.ref[pid] == 0:
            self.free.append(pid)
            self._free_set.add(pid)
            return True
        return False

    def retain(self, pid: int) -> None:
        """Add a reference (the prefix cache publishing a page)."""
        if pid in self._free_set or self.ref[pid] <= 0:
            raise PageAccountingError(
                f"retain of unallocated page {pid}")
        self.ref[pid] += 1

    def ensure(self, slot: int, n_tokens: int) -> Optional[List[int]]:
        """Grow slot's allocation to cover n_tokens positions.

        Returns the list of newly allocated page ids, or None if the free
        list cannot satisfy the request (caller decides whom to preempt or
        which cached pages to evict)."""
        have = len(self.slot_pages[slot])
        need = pages_for(n_tokens, self.page)
        if need > self.max_pages_per_seq:
            raise PoolExhausted(
                f"sequence needs {need} pages > max_pages_per_seq="
                f"{self.max_pages_per_seq}")
        if need <= have:
            return []
        if need - have > len(self.free):
            return None
        fresh = [self._pop_free() for _ in range(need - have)]
        self.tables_dirty = True
        for j, pid in enumerate(fresh, start=have):
            self.slot_pages[slot].append(pid)
            self.block_tables[slot, j] = pid
        self.pages_peak = max(self.pages_peak, self.used_count)
        return fresh

    def adopt(self, slot: int, page_ids: List[int]) -> None:
        """Map already-live (cache-held) pages into an empty slot's table.

        The slot shares the pages read-only: each gains a reference, and a
        later write into one must go through :meth:`cow` first."""
        if self.slot_pages[slot]:
            raise PageAccountingError(
                f"adopt into non-empty slot {slot}")
        self.adopt_calls += 1
        self.tables_dirty = True
        for j, pid in enumerate(page_ids):
            if pid == 0:
                raise PageAccountingError(
                    "adopt of the reserved null page 0 (would alias every "
                    "inactive lane's scratch page into a live table)")
            self.retain(pid)
            self.slot_pages[slot].append(pid)
            self.block_tables[slot, j] = pid
        self.pages_peak = max(self.pages_peak, self.used_count)

    def cow(self, slot: int, token_pos: int):
        """Make the page holding ``token_pos`` private to ``slot``.

        Returns None when the page is already private (refcount 1),
        ``(src, dst)`` when a fresh page ``dst`` was mapped in place of the
        shared ``src`` — the caller must copy the device page contents —
        or False when the free list is empty (caller evicts/preempts and
        retries). The shared page's refcount is decremented; it is never
        written."""
        j = token_pos // self.page
        pid = self.slot_pages[slot][j]
        if self.ref[pid] == 1:
            return None
        if not self.free:
            return False
        dst = self._pop_free()
        self.tables_dirty = True
        self.slot_pages[slot][j] = dst
        self.block_tables[slot, j] = dst
        self.ref[pid] -= 1          # shared copy stays live elsewhere
        self.cow_copies += 1
        self.pages_peak = max(self.pages_peak, self.used_count)
        return pid, dst

    def trim(self, slot: int, n_tokens: int) -> int:
        """Shrink the slot's allocation back to what ``n_tokens``
        positions need; returns how many pages were recycled.

        The speculative-decode rollback — and the pipelined engine's
        EOS-lag rollback, which is the same move: a verify step
        allocates pages out to the full draft length (a pipelined round
        allocates for the one token dispatched past an EOS that landed
        during the readback lag), and when the model rejects a suffix
        (or the EOS retires) the tail pages hold only garbage K/V
        (already masked by ``valid_len`` until real tokens overwrite
        those positions). Tail pages were freshly allocated for
        positions past the live prefix, so they are never
        prefix-cache-shared; release still goes through the refcount
        for safety."""
        keep = pages_for(n_tokens, self.page)
        n = 0
        while len(self.slot_pages[slot]) > keep:
            pid = self.slot_pages[slot].pop()
            self.block_tables[slot, len(self.slot_pages[slot])] = 0
            self.tables_dirty = True
            n += bool(self.release(pid))
        return n

    def free_slot(self, slot: int) -> int:
        """Drop the slot's references; returns how many pages were recycled
        (pages still held by the prefix cache stay allocated)."""
        n = 0
        for pid in self.slot_pages[slot]:
            n += bool(self.release(pid))
        self.slot_pages[slot] = []
        self.block_tables[slot, :] = 0
        self.tables_dirty = True
        return n

    def device_tables(self, n_groups: int) -> jax.Array:
        """Block tables as a device array broadcast over layer groups.

        Built afresh per call (a few hundred int32s — negligible next to
        the step): the serving steps donate the arena the tables ride
        in, so a cached device buffer would be dead after its first
        use. The tables go up as a copy (``jnp.array``): the pool
        rewrites them in place, and on the CPU ``jnp.asarray`` maps an
        aligned host buffer without copying."""
        self.tables_rebuilds += 1
        tbl = jnp.array(self.block_tables)
        return jnp.broadcast_to(tbl[None], (n_groups,) + tbl.shape)

    # ---- device arena --------------------------------------------------
    def init_arena(self):
        """Fresh zeroed arena pytree (leading n_groups dim, +1 null page)."""
        return KV.paged_init_cache(self.cfg, self.n_pages + 1, self.page,
                                   self.max_slots, self.max_pages_per_seq,
                                   self.cache_dtype)

    def check_tables(self) -> None:
        """Null-page aliasing guard: page 0 must never appear in a live
        region of a block table, and every live region must mirror
        ``slot_pages``. Until now only convention protected this — a
        corrupted table would silently attend over null-page garbage (or
        another sequence's KV). Raises :class:`PageAccountingError`
        instead. O(max_slots * max_pages_per_seq) host ints per step."""
        for s, pages in enumerate(self.slot_pages):
            n = len(pages)
            live = self.block_tables[s, :n]
            if (live == 0).any() or live.tolist() != pages:
                raise PageAccountingError(
                    f"slot {s} block table {self.block_tables[s].tolist()} "
                    f"diverged from its page map {pages} (null page in a "
                    f"live region, or a stale/corrupted table)")
            if self.block_tables[s, n:].any():
                raise PageAccountingError(
                    f"slot {s} maps pages beyond its {n} live entries: "
                    f"{self.block_tables[s].tolist()}")

    def install_tables(self, arena):
        """Return arena with current block tables written into every group.

        Tables are validated by :meth:`check_tables` on every install, so
        a corrupted mapping raises before any step can attend over
        garbage."""
        self.check_tables()
        tbl = self.device_tables(self.cfg.n_groups)
        out = {}
        for key, grp in arena.items():
            grp = dict(grp)
            if "attn" in grp:
                attn = dict(grp["attn"])
                attn["block_tbl"] = tbl
                grp["attn"] = attn
            out[key] = grp
        return out


