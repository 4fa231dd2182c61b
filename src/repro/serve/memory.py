"""Memory layer of the serving core: a host-side facade over the page
machinery.

:class:`MemoryManager` owns everything the scheduler used to reach into
directly — the :class:`~repro.serve.pages.PageLayout`, the refcounted
:class:`~repro.serve.pages.PagePool` (one per ``data`` shard), the host
page-table mirror, and the per-slot prefix-index bookkeeping — behind a
narrow interface split in two:

  * **capacity queries** (``can_reserve_for`` / ``available_for`` /
    ``pages_for_len`` / ``held``): what the pure planner
    (serve/plan.py) consults; read-only, no device work, no JAX.
  * **mutations** (``reserve`` / ``grow`` / ``extend_to`` / ``adopt`` /
    ``prepare_write`` / ``truncate`` / ``release``): what the executor
    applies when a plan runs. Each mutation keeps the page-table mirror
    in sync, so callers never touch page ids directly.

Everything here is numpy + stdlib — property tests drive the planner
against a real ``MemoryManager`` without compiling anything.

**Data-axis pool partitioning.** With ``data_shards = D > 1`` the
allocatable pages split into ``D`` equal sub-pools, each with its own
trash row, laid out so physical page ids align with the GSPMD blocks of
a page-axis-sharded pool leaf: shard ``d`` owns rows
``[d * (P/D + 1), (d + 1) * (P/D + 1))`` with the block's last row as
its trash page. Slot ``s`` maps to shard ``s * D // n_slots`` — the same
contiguous ranges the batch axis shards into — and allocates pages only
from its shard's sub-pool, so steady-state decode reads and writes stay
on the device that owns both the slot row and the page slice. Prefix
indexing and preemption victims are shard-local. ``D = 1`` (the default
and every unmeshed configuration) is bit-for-bit the single-pool
behavior.
"""
from __future__ import annotations

import numpy as np

from repro.serve.pages import PageLayout, PagePool, prefix_page_keys


class MemoryManager:
    """Facade over layout + pools + page-table mirror + prefix bookkeeping.

    ``layout`` is the *global* page geometry (``total_pages`` rows
    including one trash row per data shard); ``pt`` is the host mirror of
    the device page table, ``(n_slots, max_pages)`` int32 global ids.
    A ``layout`` of None builds a no-op manager for unpaged models.
    """

    def __init__(self, layout: PageLayout | None, n_slots: int):
        self.layout = layout
        self.n_slots = n_slots
        self.paged = layout is not None
        if not self.paged:
            self.pools: list[PagePool] = []
            self.pt = None
            self.slot_keys: dict[int, list[bytes]] = {}
            self.slot_reg: dict[int, int] = {}
            return
        D = layout.data_shards
        if layout.n_pages % D:
            raise ValueError(
                f"n_pages {layout.n_pages} not divisible by data_shards {D}"
            )
        per = layout.n_pages // D
        local = PageLayout(
            page_size=layout.page_size, n_pages=per, span=layout.span
        )
        self.pools = [PagePool(local) for _ in range(D)]
        self._per = per  # allocatable pages per shard
        self._stride = per + 1  # rows per shard block (incl. its trash row)
        self.pt = np.empty((n_slots, layout.max_pages), np.int32)
        for s in range(n_slots):
            self.pt[s, :] = self.trash_of(s)
        self.slot_keys = {}  # slot -> prompt page keys (prefix sharing)
        self.slot_reg = {}  # slot -> leading pages registered in the index

    # -- shard geometry ------------------------------------------------------
    @property
    def data_shards(self) -> int:
        return self.layout.data_shards if self.paged else 1

    def shard_of(self, slot: int) -> int:
        """Data shard owning ``slot`` (same ranges the batch axis splits)."""
        return slot * self.data_shards // self.n_slots

    def trash_of(self, slot: int) -> int:
        """Global id of ``slot``'s shard-local trash row."""
        return self.shard_of(slot) * self._stride + self._per

    def _pool(self, slot: int) -> PagePool:
        return self.pools[self.shard_of(slot)]

    def _to_global(self, slot: int, pids: list[int]) -> list[int]:
        off = self.shard_of(slot) * self._stride
        return [off + p for p in pids]

    # -- compatibility: the single-pool view (tests, stats) ------------------
    @property
    def pool(self) -> PagePool | None:
        """The sole pool when unsharded (every pre-existing test and the
        unmeshed serving path); sharded callers go through the facade."""
        if not self.paged:
            return None
        if len(self.pools) != 1:
            raise AttributeError(
                "MemoryManager.pool is single-shard only; use the facade "
                "methods (the pool is partitioned across data shards)"
            )
        return self.pools[0]

    # -- capacity queries (planner-facing, read-only) ------------------------
    @property
    def max_pages(self) -> int:
        return self.layout.max_pages if self.paged else 0

    @property
    def page_size(self) -> int:
        return self.layout.page_size if self.paged else 0

    @property
    def n_pages(self) -> int:
        return self.layout.n_pages if self.paged else 0

    def pages_for_len(self, length: int) -> int:
        return self.layout.pages_for_len(length) if self.paged else 0

    def held(self, slot: int) -> int:
        """Pages currently allocated to ``slot``."""
        return len(self._pool(slot).allocated(slot)) if self.paged else 0

    def available_for(self, slot: int) -> int:
        """Pages admissible to a new reservation in ``slot``'s shard."""
        return self._pool(slot).available()

    def can_reserve_for(self, slot: int, n: int) -> bool:
        return self._pool(slot).can_reserve(n)

    def lookup_prefix_len(self, slot: int, prompt: np.ndarray) -> int:
        """Indexed-prefix pages a prompt would adopt in ``slot``'s shard."""
        keys = prefix_page_keys(prompt, self.layout.page_size)
        return self._pool(slot).lookup_prefix(keys)

    @property
    def in_use(self) -> int:
        return sum(p.in_use for p in self.pools)

    @property
    def peak_in_use(self) -> int:
        return sum(p.peak_in_use for p in self.pools)

    def available_total(self) -> int:
        return sum(p.available() for p in self.pools)

    def reset_peaks(self) -> None:
        """Reset every shard pool's peak-usage watermark (benchmarks scope
        peak bytes past warmup/primer phases). No-op when unpaged."""
        for p in self.pools:
            p.reset_peaks()

    # -- mutations (executor-facing) -----------------------------------------
    def reserve(self, slot: int, n: int) -> None:
        """Open ``slot``'s reservation and point its table row at trash."""
        self._pool(slot).reserve(slot, n)
        self.pt[slot, :] = self.trash_of(slot)

    def extend_to(self, slot: int, n_total: int) -> bool:
        return self._pool(slot).extend_to(slot, n_total)

    def grow(self, slot: int, n_total: int) -> None:
        """Allocate up to ``n_total`` pages and map them in the mirror."""
        pool = self._pool(slot)
        held = len(pool.allocated(slot))
        if n_total > held:
            new = self._to_global(slot, pool.grow_to(slot, n_total))
            self.pt[slot, held:n_total] = new

    def adopt(self, slot: int, prompt: np.ndarray, src_len: int) -> int:
        """Adopt the longest indexed prefix of ``prompt`` (capped below
        ``src_len`` so at least one token still streams); returns adopted
        *tokens*. Must run right after ``reserve``."""
        P = self.layout.page_size
        keys = prefix_page_keys(prompt, P)
        pool = self._pool(slot)
        adopted = pool.adopt_prefix(slot, keys[: (src_len - 1) // P])
        if adopted:
            self.pt[slot, :adopted] = self._to_global(
                slot, pool.allocated(slot)
            )
        self.slot_keys[slot] = keys
        self.slot_reg[slot] = adopted
        return adopted * P

    def register_progress(self, slot: int, tokens_done: int) -> None:
        """Index ``slot``'s newly-completed full prompt pages."""
        keys = self.slot_keys.get(slot)
        if keys is None:
            return
        pool = self._pool(slot)
        done = min(tokens_done // self.layout.page_size, len(keys))
        for j in range(self.slot_reg.get(slot, 0), done):
            pool.register_page(slot, j, keys[j])
        self.slot_reg[slot] = max(self.slot_reg.get(slot, 0), done)

    def prepare_write(
        self, slot: int, start: int, stop: int
    ) -> list[tuple[int, int, int]]:
        """CoW-fork shared pages in the write range; re-points the mirror
        and returns global ``(logical, old, new)`` triples for the device
        copy (empty in the steady state)."""
        forks = self._pool(slot).prepare_write(slot, start, stop)
        if not forks:
            return []
        off = self.shard_of(slot) * self._stride
        out = [(j, off + old, off + new) for j, old, new in forks]
        for j, _, new in out:
            self.pt[slot, j] = new
        return out

    def truncate(
        self, slot: int, n_total: int, keep_reservation: bool
    ) -> int:
        """Drop trailing pages to ``n_total`` (spec rollback); trash-points
        the vacated mirror entries. Returns the number removed."""
        removed = self._pool(slot).truncate_to(
            slot, n_total, keep_reservation=keep_reservation
        )
        if removed:
            self.pt[slot, n_total : n_total + len(removed)] = self.trash_of(
                slot
            )
        return len(removed)

    def unmap(self, slot: int) -> None:
        """Point ``slot``'s table row at trash but keep its pages: the slot
        decodes no more, and releases them once its last token is read."""
        self.pt[slot, :] = self.trash_of(slot)

    def release(self, slot: int) -> None:
        """Free the slot's pages, reservation, mirror row, and prefix
        bookkeeping (indexed pages park in the shard's cached list)."""
        self._pool(slot).release(slot)
        self.pt[slot, :] = self.trash_of(slot)
        self.slot_keys.pop(slot, None)
        self.slot_reg.pop(slot, None)

    def drop_slot_keys(self, slot: int) -> None:
        self.slot_keys.pop(slot, None)
        self.slot_reg.pop(slot, None)

    # -- reporting -----------------------------------------------------------
    def stats(self) -> dict[str, int]:
        if not self.paged:
            return {}
        agg = dict(self.pools[0].stats())
        for p in self.pools[1:]:
            for k, v in p.stats().items():
                if k == "page_size":
                    continue
                agg[k] += v
        agg["page_size"] = self.layout.page_size
        agg["data_shards"] = self.data_shards
        return agg
