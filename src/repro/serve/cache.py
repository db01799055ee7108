"""KV-cache memory management for continuous batching: the dense slot
slab and the paged block pool.

**Slot slab** (the PR 3 layout, still used by recurrent/hybrid/VLM
stacks): the model's ordinary decode cache (``ModelAPI.init_cache``)
with batch = ``max_batch`` — every leaf is ``(n_blocks, max_batch, ...)``
with the batch dimension at axis 1. A *slot* is one index of that batch
dimension; admission writes a freshly prefilled single-request cache
into the slot, retirement simply abandons it.

**Paged pool** (attention-only stacks): KV memory is ``n_pages`` fixed-
size pages shared by every slot. :class:`PagePool` is the host-side
block allocator — per-slot page tables, all-or-nothing alloc, free-page
budget for admission, compaction (``defrag``) — and, since PR 6,
**refcounted**: one physical page may appear in many slots' page tables
(cross-request prefix sharing, ``serve.prefix.PrefixIndex``), may be
pinned by the prefix index with no slot referencing it (``cache``/
``uncache``), and is copy-on-written (``cow``) before a slot writes
into a page another holder can still see. The device side, and the one
home of the pool's layout, is ``repro.kernels.paged_pool``: it builds
the pool (``ModelAPI.init_paged_cache`` calls it), writes it (the chunk
program's ``insert``), hands the kernel one layer's view (read, with
``table_row`` feeding the page table), and copies and permutes pages
(``copy_pages`` and ``apply_defrag`` below are thin entry points over
it). Invalidation is ``free_slot``: dropping the mapping *is* the
invalidation; no mask pass needed, which is the point of paging. Memory
no longer scales as ``max_batch x max_len`` but as actual tokens held,
the serving analogue of the paper's partition-what-no-longer-fits story
(§3).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import paged_pool


def init_slab(api, max_batch: int, max_len: int, window=None):
    """Batched decode cache with one slot per concurrent request."""
    return api.init_cache(max_batch, max_len, window)


def write_slot(slab, cache, slot):
    """Write a prefilled single-request cache (batch dim 1) into ``slot``.

    slot: traced int32 — one compiled program serves every slot index.
    """
    return jax.tree_util.tree_map(
        lambda s, n: jax.lax.dynamic_update_slice_in_dim(
            s, n.astype(s.dtype), slot, axis=1
        ),
        slab, cache,
    )


def read_slot(slab, slot: int):
    """Single-request view of ``slot`` (batch dim kept, size 1)."""
    return jax.tree_util.tree_map(
        lambda s: jax.lax.dynamic_slice_in_dim(s, slot, 1, axis=1), slab
    )


# --------------------------------------------------------------------------- #
# Paged block pool (host-side allocator).
# --------------------------------------------------------------------------- #
class PagePool:
    """Refcounted fixed-size-page allocator over ``n_pages`` physical
    pages.

    Pure Python, no jax: the pool decides *which* physical pages a slot's
    logical positions map to; the device side consumes the mapping as an
    ``(max_batch, max_pages)`` int32 page table (``table_row``). A
    physical page is in exactly one of three states:

      * **free** — on the free list, content meaningless;
      * **referenced** — mapped by ``refcount(p) >= 1`` slots (prefix
        sharing maps one physical page into many tables);
      * **cached** — refcount 0 but pinned by the prefix index
        (``cache``), holding reusable KV until ``uncache`` (LRU
        eviction under pool pressure) releases it.

    Invariants (property-tested in tests/test_serve.py and
    tests/test_prefix.py):

      * ``alloc`` is all-or-nothing — a partial grant never leaks pages;
      * ``free_slot`` decrements every mapped page; only pages reaching
        refcount 0 *and* not cached return to the free list — no page is
        freed while any slot or the index can still read it;
      * ``cow`` never hands a slot a page another holder can see: a
        shared mapping (refcount > 1, or cached) is swapped for a fresh
        page, the original keeps its other holders;
      * ``defrag`` preserves every slot's logical->token mapping *and*
        all sharing structure (a page mapped by k slots is moved once
        and all k tables point at its new index); cached pages keep
        their content too.
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 1 or page_size < 1:
            raise ValueError("n_pages and page_size must be >= 1")
        self.n_pages = n_pages
        self.page_size = page_size
        self._free: List[int] = list(range(n_pages - 1, -1, -1))
        self._slots: Dict[int, List[int]] = {}
        self._ref: List[int] = [0] * n_pages
        self._cached: set = set()

    # ------------------------------------------------------------------ #
    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` logical positions."""
        return max(0, -(-n_tokens // self.page_size))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.n_pages - len(self._free)

    def utilization(self) -> float:
        return self.used_pages / self.n_pages

    def slot_pages(self, slot: int) -> List[int]:
        return list(self._slots.get(slot, ()))

    def refcount(self, page: int) -> int:
        """Slot references on ``page`` (index pins are separate)."""
        return self._ref[page]

    def is_cached(self, page: int) -> bool:
        return page in self._cached

    def is_shared(self, page: int) -> bool:
        """True when a write to ``page`` would be visible to another
        holder — a second slot, or the prefix index."""
        return self._ref[page] > 1 or page in self._cached

    # ------------------------------------------------------------------ #
    def alloc(self, slot: int, n: int) -> bool:
        """Append ``n`` fresh pages to ``slot``; all-or-nothing."""
        if n > len(self._free):
            return False
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        self._slots.setdefault(slot, []).extend(pages)
        return True

    def share(self, slot: int, pages: List[int]) -> None:
        """Append already-live pages to ``slot``'s table (prefix hit).

        Each page must be referenced or cached — sharing a free page
        would map memory the allocator can hand to someone else.
        """
        for p in pages:
            if self._ref[p] == 0 and p not in self._cached:
                raise ValueError(f"page {p} is free; cannot share it")
        for p in pages:
            self._ref[p] += 1
        self._slots.setdefault(slot, []).extend(pages)

    def ensure(self, slot: int, n_tokens: int) -> bool:
        """Grow ``slot`` so positions [0, n_tokens) are mapped."""
        have = len(self._slots.get(slot, ()))
        return self.alloc(slot, max(0, self.pages_for(n_tokens) - have))

    def free_slot(self, slot: int) -> int:
        """Drop every mapping of ``slot``; a page returns to the free
        list only once nothing else (slot or index pin) holds it."""
        pages = self._slots.pop(slot, [])
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0 and p not in self._cached:
                self._free.append(p)
        return len(pages)

    # ------------------------------------------------------------------ #
    def cache(self, pages: List[int]) -> None:
        """Pin ``pages`` for the prefix index: refcount-0 pins survive
        ``free_slot`` and leave the pool only via ``uncache``."""
        for p in pages:
            if self._ref[p] == 0 and p not in self._cached:
                raise ValueError(f"page {p} is free; cannot cache it")
        self._cached.update(pages)

    def uncache(self, pages: List[int]) -> int:
        """Drop index pins; returns how many pages became free."""
        freed = 0
        for p in pages:
            if p in self._cached:
                self._cached.discard(p)
                if self._ref[p] == 0:
                    self._free.append(p)
                    freed += 1
        return freed

    def cow(self, slot: int, logical: int):
        """Copy-on-write: give ``slot`` a private page at table index
        ``logical`` before it writes there.

        Returns ``(src, dst)`` physical ids for the device-side content
        copy, or ``None`` when the mapping is already private (no copy
        needed). Raises if a copy is needed but the free list is empty —
        callers evict/preempt first.
        """
        pages = self._slots[slot]
        src = pages[logical]
        if not self.is_shared(src):
            return None
        if not self._free:
            raise RuntimeError(
                f"cow needs a free page (slot {slot}, logical {logical}) "
                f"but the pool is exhausted")
        dst = self._free.pop()
        self._ref[dst] = 1
        self._ref[src] -= 1  # shared -> still held by someone else
        pages[logical] = dst
        return (src, dst)

    def table_row(self, slot: int, max_pages: int) -> np.ndarray:
        """(max_pages,) int32 page-table row for ``slot`` (-1 unmapped)."""
        row = np.full((max_pages,), -1, np.int32)
        pages = self._slots.get(slot, ())
        row[: len(pages)] = pages
        return row

    # ------------------------------------------------------------------ #
    def defrag(self) -> np.ndarray:
        """Compact occupied pages to the lowest physical indices.

        Returns ``perm`` of shape (n_pages + 1,): ``new_pool[i] =
        old_pool[perm[i]]`` — apply to the device pools with
        :func:`apply_defrag` *before* the next step consumes the updated
        page tables. The trailing trash page stays put. After
        compaction the free list is the contiguous tail, so long-lived
        mixed workloads keep allocation O(1) and (on real hardware)
        DMA-friendly.
        """
        order: List[int] = []
        remap: Dict[int, int] = {}
        for slot in sorted(self._slots):
            new_pages = []
            for old in self._slots[slot]:
                if old not in remap:  # shared pages move exactly once
                    remap[old] = len(order)
                    order.append(old)
                new_pages.append(remap[old])
            self._slots[slot] = new_pages
        # refcount-0 cached pages hold reusable KV: compact them right
        # after the referenced pages so the free tail stays truly free
        for old in sorted(self._cached):
            if old not in remap:
                remap[old] = len(order)
                order.append(old)
        free_old = [i for i in range(self.n_pages) if i not in remap]
        self._free = list(range(self.n_pages - 1, len(order) - 1, -1))
        new_ref = [0] * self.n_pages
        for old, new in remap.items():
            new_ref[new] = self._ref[old]
        self._ref = new_ref
        self._cached = {remap[p] for p in self._cached}
        perm = np.empty((self.n_pages + 1,), np.int32)
        perm[: len(order)] = order
        perm[len(order): self.n_pages] = free_old
        perm[self.n_pages] = self.n_pages  # trash page fixed
        return perm

    @staticmethod
    def remap_from_perm(perm) -> Dict[int, int]:
        """old physical id -> new physical id for a ``defrag`` perm
        (``new_pool[i] = old_pool[perm[i]]``); consumed by
        ``serve.prefix.PrefixIndex.remap``."""
        return {int(old): new for new, old in enumerate(perm[:-1])}


def apply_defrag(cache, perm):
    """Every paged pool of ``cache`` in the post-``defrag`` page order
    (``perm`` from :meth:`PagePool.defrag`); other entries are kept."""
    perm = jnp.asarray(perm, jnp.int32)  # one transfer for every pool
    return paged_pool.map_pools(cache, lambda p: paged_pool.permute(p, perm))


def copy_pages(cache, src: List[int], dst: List[int]):
    """Duplicate physical pages ``src[i] -> dst[i]`` in every paged pool
    of ``cache`` (the device half of :meth:`PagePool.cow`)."""
    if not src:
        return cache
    return paged_pool.map_pools(
        cache, lambda p: paged_pool.copy_pages(p, src, dst))


def invalidate_beyond(cache, true_len):
    """Mark ring slots at index >= per-example ``true_len`` as empty.

    Serving right-pads prompts to one compile shape before prefill; the
    padded positions' K/V land in ring slots ``true_len..pad_len-1`` with
    valid ``slot_pos`` entries and would be attended to. Resetting their
    ``slot_pos`` to -1 makes ``decode_attention`` mask them, which (with
    causal prefill) makes the padded prefill exactly equivalent to an
    unpadded one. Recurses over any cache structure, rewriting only
    attention entries (dicts carrying k/v/slot_pos); enc-dec ``cross``
    caches hold full encoder K/V and are left untouched.

    true_len: (B,) int32 per-example true lengths (media included).
    """
    tl = jnp.asarray(true_len, jnp.int32).reshape(-1)

    def fix(slot_pos):  # (n_blocks, B, L)
        idx = jnp.arange(slot_pos.shape[-1], dtype=jnp.int32)
        keep = idx[None, None, :] < tl[None, :, None]
        return jnp.where(keep, slot_pos, jnp.int32(-1))

    def rec(node):
        if isinstance(node, dict):
            if "slot_pos" in node and "k" in node:
                out = dict(node)
                out["slot_pos"] = fix(node["slot_pos"])
                return out
            return {
                k: (v if k == "cross" else rec(v)) for k, v in node.items()
            }
        if isinstance(node, (tuple, list)):
            return tuple(rec(x) for x in node)
        return node

    return rec(cache)
