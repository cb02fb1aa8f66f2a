"""Paged KV cache: fixed-size blocks + free-list allocator (DESIGN.md §18).

The contiguous serving cache is (B, max_len, kvh, hd) per layer — memory
scales with worst-case length whether or not a lane is live. The paged pool
is one flat slot array per layer, (n_slots = n_blocks·page, kvh, hd), carved
into fixed ``page``-token blocks handed out by a host-side free list. Each
request owns a *block table* — the ordered block ids covering its positions
— and the decode step indexes the pool by a gather through the table
(``models.layers.paged_gather``), so cache memory scales with **live
tokens**, not ``B × max_len``.

Block 0 is the reserved **null block**: unallocated block-table entries and
inactive decode lanes point at it, so in-graph writes always have a legal
(garbage) destination and no lane ever needs a branch. Nothing live is ever
read from it — the decode mask hides every position past a request's
``pos``.

Bit-identity (pinned by tests/test_serve_continuous.py): when a request's
blocks happen to be allocated in ascending contiguous order, the gathered
view *is* the contiguous cache, row for row; the allocator hands out lowest
ids first so a fresh pool reproduces the contiguous layout exactly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

NULL_BLOCK = 0


def n_pages(n_tokens: int, page: int) -> int:
    return -(-n_tokens // page)


class BlockAllocator:
    """Host-side free list over the pool's block ids.

    Ids ``[reserved, n_blocks)`` are allocatable; ``[0, reserved)`` (the
    null block) never leave the allocator. Lowest ids are handed out first
    so fresh allocations are contiguous-ascending (the bit-identity
    layout); freed blocks are recycled LIFO.
    """

    def __init__(self, n_blocks: int, reserved: int = 1):
        if n_blocks <= reserved:
            raise ValueError(f"need n_blocks > {reserved} (the null "
                             f"block), got {n_blocks}")
        self.n_blocks = int(n_blocks)
        self.reserved = int(reserved)
        # stack: pop() takes from the end, so store descending
        self._free: List[int] = list(range(n_blocks - 1, reserved - 1, -1))

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def capacity(self) -> int:
        return self.n_blocks - self.reserved

    def alloc(self, k: int) -> Optional[List[int]]:
        """k blocks, or None when the pool cannot cover them (all-or-
        nothing: a partial grab would deadlock two growing requests)."""
        if k < 0:
            raise ValueError(f"alloc({k})")
        if k > len(self._free):
            return None
        return [self._free.pop() for _ in range(k)]

    def free(self, ids: List[int]) -> None:
        for b in ids:
            if not self.reserved <= b < self.n_blocks:
                raise ValueError(f"freeing foreign block {b}")
            if b in self._free:
                raise ValueError(f"double free of block {b}")
        self._free.extend(reversed(ids))


#: XLA module name of the prefill scatter, as a profile names its runs
WRITE_PROGRAM = "jit_write"


@dataclasses.dataclass
class PagedCache:
    """The device pool + its host-side accounting for one serving session.

    ``pool`` is the model's stacked per-kind slot arrays
    (``Model.init_paged``); jitted writers are built per (length) shape and
    donate the pool, so there is never more than one live copy.
    """
    model: Any
    page: int
    n_blocks: int
    pool: Any = None
    writers: Optional[dict] = None      # share across sessions to keep the
                                        # per-length writer jits warm

    def __post_init__(self):
        self.n_slots = self.n_blocks * self.page
        self.alloc = BlockAllocator(self.n_blocks)
        if self.pool is None:
            self.pool = self.model.init_paged(self.n_slots)
        self._writers = {} if self.writers is None else self.writers

    # -- prefill scatter ---------------------------------------------------

    def _writer(self, length: int):
        """Jitted pool-donating scatter of a (L, 1, S, kvh, hd) prefill
        cache into slot rows; compiled once per prompt length."""
        fn = self._writers.get(length)
        if fn is None:
            def write(pool, cache, slots):
                def one(kname):
                    dst, src = pool[kname], cache[kname]
                    out = dict(dst)
                    for leaf in ("k", "v"):
                        out[leaf] = dst[leaf].at[:, slots].set(
                            src[leaf][:, 0].astype(dst[leaf].dtype))
                    return out
                return {kn: one(kn) for kn in pool}
            fn = jax.jit(write, donate_argnums=(0,))     # WRITE_PROGRAM
            self._writers[length] = fn
        return fn

    def write_prefill(self, cache, blocks: List[int], length: int) -> None:
        """Scatter prefill K/V rows [0, length) into the request's blocks.

        The prefill cache may be longer than ``length`` (padded prompts);
        extra rows are routed to the null block.
        """
        L = jax.tree_util.tree_leaves(cache)[0].shape[2]
        slots = np.zeros(L, np.int32)            # overflow -> null block
        flat = self.slot_ids(blocks)
        slots[:length] = flat[:length]
        self.pool = self._writer(L)(self.pool, cache,
                                    jnp.asarray(slots))

    # -- layout helpers ----------------------------------------------------

    def slot_ids(self, blocks: List[int]) -> np.ndarray:
        """Flat slot ids covered by a block list, in position order."""
        b = np.asarray(blocks, np.int64)
        return (b[:, None] * self.page
                + np.arange(self.page)[None, :]).reshape(-1)

    def block_row(self, blocks: List[int], max_pages: int) -> np.ndarray:
        """One block-table row, null-padded to the static table width."""
        if len(blocks) > max_pages:
            raise ValueError(f"{len(blocks)} blocks > table width "
                             f"{max_pages}")
        row = np.full(max_pages, NULL_BLOCK, np.int32)
        row[:len(blocks)] = blocks
        return row

    def gather_contiguous(self, blocks: List[int], length: int):
        """Reconstruct the contiguous (L, 1, length, kvh, hd) cache view of
        one request from the pool — the bit-identity probe the tests pin
        against the legacy contiguous cache."""
        slots = jnp.asarray(self.slot_ids(blocks)[:length])
        return {kn: {leaf: self.pool[kn][leaf][:, slots][:, None]
                     for leaf in ("k", "v")}
                for kn in self.pool}
