"""Paged KV cache: block tables + free-list page allocation (host side).

Device side, each attention layer's KV lives in a PAGE POOL
``(num_pages, Hkv_loc, rows, width)`` instead of a dense per-slot
``(B, Hkv_loc, S_max, hd)`` buffer: each head's page holds its
``page_size`` tokens in order, ``width / hd`` tokens to a lane row
(``kernels.flash_decode.page_rows``). A request's tokens map onto pool
pages through its BLOCK-TABLE row (``pages_per_slot`` page ids), so
requests of wildly different lengths pack densely and a freed slot's
pages simply return to the free list — the successor request gets a
fresh table row and the stale KV is unreachable by construction (no
slot-reuse leak).

Host side, :class:`PagedKVCache` is the allocator:

* **per-DP-shard free lists** — each data rank holds its own pool
  replica and serves its own batch slots, so page ids are local to the
  shard that owns the slot;
* **whole-request allocation at admission** (prompt + max_new tokens),
  so an admitted request can never stall mid-decode for pages;
* **scratch page 0** — reserved on every shard. Masked writes (idle
  batch lanes, prompt padding) are steered there and unallocated table
  entries point at it, so the device programs need no bounds branches;
  attention masks it out by length, and masked logits underflow to
  exact zeros, which is what makes slot isolation bit-exact.

This module is pure host Python/NumPy (no jax import) so the allocator
unit tests stay sub-millisecond.
"""
from __future__ import annotations

from typing import List

import numpy as np


class PagedKVCache:
    """Free-list page allocator + per-slot block tables.

    ``table`` is the (batch, pages_per_slot) int32 array handed to the
    device programs; ``lens`` tracks tokens currently cached per slot
    (the next write position).
    """

    def __init__(self, *, batch: int, max_len: int, page_size: int = 16,
                 num_pages: int = 0, dp_shards: int = 1):
        assert batch % dp_shards == 0, (batch, dp_shards)
        assert page_size > 0 and max_len > 0
        self.batch = batch
        self.max_len = max_len
        self.page_size = page_size
        self.dp_shards = dp_shards
        self.slots_per_shard = batch // dp_shards
        self.pages_per_slot = -(-max_len // page_size)  # ceil
        if num_pages <= 0:
            # dense-equivalent residency: every local slot can hold max_len
            num_pages = 1 + self.slots_per_shard * self.pages_per_slot
        if num_pages < self.pages_per_slot + 1:
            raise ValueError(
                f"num_pages={num_pages} cannot hold one max_len={max_len} "
                f"request (+scratch); need >= {self.pages_per_slot + 1}")
        self.num_pages = num_pages
        # LIFO free stacks per shard; page 0 reserved as scratch
        self._free: List[List[int]] = [
            list(range(num_pages - 1, 0, -1)) for _ in range(dp_shards)
        ]
        self._slot_pages: List[List[int]] = [[] for _ in range(batch)]
        self.table = np.zeros((batch, self.pages_per_slot), np.int32)
        self.lens = np.zeros((batch,), np.int32)

    # ------------------------------------------------------------------
    def shard(self, slot: int) -> int:
        return slot // self.slots_per_shard

    def pages_needed(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 1) // self.page_size)

    def free_pages(self, shard: int) -> int:
        return len(self._free[shard])

    def can_alloc(self, slot: int, n_tokens: int) -> bool:
        return (not self._slot_pages[slot]
                and self.pages_needed(n_tokens) <= self.free_pages(self.shard(slot)))

    def alloc(self, slot: int, n_tokens: int) -> bool:
        """Reserve pages for a request of ``n_tokens`` total (prompt +
        generation) in ``slot``. All-or-nothing; False if short on pages."""
        if not self.can_alloc(slot, n_tokens):
            return False
        need = self.pages_needed(n_tokens)
        free = self._free[self.shard(slot)]
        pages = [free.pop() for _ in range(need)]
        self._slot_pages[slot] = pages
        row = np.zeros((self.pages_per_slot,), np.int32)
        row[:need] = pages
        self.table[slot] = row
        self.lens[slot] = 0
        return True

    def free(self, slot: int) -> None:
        """Return the slot's pages to its shard's free list and zero the
        table row (successor requests can never reach the old KV)."""
        pages = self._slot_pages[slot]
        self._free[self.shard(slot)].extend(reversed(pages))
        self._slot_pages[slot] = []
        self.table[slot] = 0
        self.lens[slot] = 0

    def pages_held(self) -> int:
        """Non-scratch pages currently allocated, over all shards."""
        total = self.dp_shards * (self.num_pages - 1)
        return total - sum(len(f) for f in self._free)

    def occupancy(self) -> float:
        """Fraction of non-scratch pages currently allocated."""
        return self.pages_held() / max(1, self.dp_shards * (self.num_pages - 1))
