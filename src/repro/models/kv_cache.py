"""Paged KV cache: fixed-size blocks, a free-list allocator, block tables.

The serving problem this solves: the pre-paging server gave every slot one
fixed-length ring of ``prompt_len + max_new`` K/V rows, so heterogeneous
traffic paid worst-case memory per slot and a single shared ``prompt_len``.
Paging decouples *logical* sequence length from *physical* cache geometry —
the same move the reconfigurable IMC macros make for array geometry: a pool
of ``num_blocks`` fixed-size blocks per attention layer is shared by all
slots, and a per-slot **block table** maps logical block ``j`` (positions
``[j*block_size, (j+1)*block_size)``) to a physical block id.

Three pieces live here:

  * :class:`BlockAllocator` — host-side free-list bookkeeping with
    ``alloc`` / ``append`` / ``release`` per slot, worst-case *reservations*
    so admission can guarantee a request will never run dry mid-decode, and
    :meth:`check` invariants (every block owned by at most one slot; tables
    are dense prefixes).
  * :class:`PagedAttnCache` — the device-side pool for one attention layer:
    ``k``/``v`` of shape ``(num_blocks, block_size, KV, hd)`` (plus int8
    scale pools), indexed by the block table at decode time.
  * pure pytree surgery — :func:`init_paged_cache` builds an empty paged
    :class:`~repro.models.transformer.StackCache` from one request's ring
    cache, and :func:`merge_prefill_cache` scatters a freshly prefilled
    (B=1, possibly padded) ring cache into the pools at the positions its
    ``key_pos`` names.  Both are jit-friendly (the slot index and block
    table ride as traced arguments, so steady-state admission never
    retraces).

The ring path in :mod:`repro.models.attention` remains the oracle: paged
decode is asserted bit-identical to it in ``tests/test_paged_kv.py``.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import AttnCache, PagedAttnCache

__all__ = [
    "BlockAllocator", "OutOfBlocks", "PagedAttnCache",
    "init_paged_cache", "merge_prefill_cache", "set_slot", "broadcast_slots",
]


class OutOfBlocks(RuntimeError):
    """The free list (minus outstanding reservations) cannot cover a request."""


class BlockAllocator:
    """Free-list allocator over ``num_blocks`` KV blocks with per-slot tables.

    ``alloc(slot, n, reserve=m)`` hands ``n`` physical blocks to ``slot`` now
    and *reserves* ``m`` more from the shared budget (admission control: a
    request that may grow to ``n+m`` blocks is admitted only if all of them
    are guaranteed).  ``append(slot)`` materializes one block — drawing from
    the slot's reservation first — when decode crosses a block boundary.
    ``release(slot)`` returns everything to the free list (early, when a
    request finishes before its ``max_new_tokens`` budget).
    """

    def __init__(self, num_blocks: int, block_size: int, slots: int,
                 max_blocks_per_slot: Optional[int] = None):
        if num_blocks < 1 or block_size < 1 or slots < 1:
            raise ValueError(
                f"invalid paged geometry: {num_blocks} blocks x "
                f"{block_size} tokens, {slots} slots")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.slots = slots
        self.max_blocks_per_slot = max_blocks_per_slot or num_blocks
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._tables: List[List[int]] = [[] for _ in range(slots)]
        self._reserved: List[int] = [0] * slots

    # ------------------------------------------------------------- queries
    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` KV rows."""
        return -(-n_tokens // self.block_size)

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def available(self) -> int:
        """Free blocks not promised to anyone (the admission budget)."""
        return len(self._free) - sum(self._reserved)

    def can_admit(self, n_blocks: int) -> bool:
        return n_blocks <= self.available

    def slot_blocks(self, slot: int) -> List[int]:
        return list(self._tables[slot])

    # ------------------------------------------------------------ mutation
    def alloc(self, slot: int, n: int, reserve: int = 0) -> List[int]:
        """Assign ``n`` blocks to ``slot`` and reserve ``reserve`` more."""
        if len(self._tables[slot]) + self._reserved[slot] + n + reserve \
                > self.max_blocks_per_slot:
            raise OutOfBlocks(
                f"slot {slot}: {n}+{reserve} blocks exceed the per-slot "
                f"table width {self.max_blocks_per_slot}")
        if n + reserve > self.available:
            raise OutOfBlocks(
                f"need {n}+{reserve} blocks, only {self.available} of "
                f"{self.num_blocks} available (free={self.num_free}, "
                f"reserved={sum(self._reserved)})")
        got = [self._free.pop() for _ in range(n)]
        self._tables[slot].extend(got)
        self._reserved[slot] += reserve
        return got

    def append(self, slot: int) -> int:
        """One more block for ``slot`` (reservation-first, else free budget)."""
        if len(self._tables[slot]) >= self.max_blocks_per_slot:
            raise OutOfBlocks(f"slot {slot}: block table full "
                              f"({self.max_blocks_per_slot})")
        if self._reserved[slot] > 0:
            self._reserved[slot] -= 1
        elif self.available < 1:
            raise OutOfBlocks(f"slot {slot}: free list dry on append")
        blk = self._free.pop()
        self._tables[slot].append(blk)
        return blk

    def release(self, slot: int) -> List[int]:
        """Return all of ``slot``'s blocks (and reservation) to the pool."""
        blks = self._tables[slot]
        self._free.extend(blks)
        self._tables[slot] = []
        self._reserved[slot] = 0
        return blks

    # ----------------------------------------------------------- the table
    def table(self) -> np.ndarray:
        """(slots, max_blocks_per_slot) int32 block table; -1 = empty."""
        t = np.full((self.slots, self.max_blocks_per_slot), -1, np.int32)
        for s, blks in enumerate(self._tables):
            t[s, :len(blks)] = blks
        return t

    def table_row(self, slot: int) -> np.ndarray:
        return self.table()[slot]

    def check(self) -> None:
        """Assert the allocator invariants (tests and chaos drills call this).

        * partition: free list + all slot tables = exactly ``num_blocks``
          distinct ids — no block is double-assigned or leaked;
        * tables are dense prefixes (block ``j`` of a slot covers logical
          positions ``[j*bs, (j+1)*bs)`` — compaction is never needed);
        * reservations are non-negative and covered by the free list.
        """
        owned = [b for t in self._tables for b in t]
        allb = self._free + owned
        assert len(set(owned)) == len(owned), "block double-assigned"
        assert sorted(allb) == list(range(self.num_blocks)), \
            "free+assigned is not a partition of the pool"
        for s, t in enumerate(self._tables):
            assert len(t) <= self.max_blocks_per_slot, f"slot {s} overfull"
        assert all(r >= 0 for r in self._reserved), "negative reservation"
        assert sum(self._reserved) <= len(self._free), \
            "reservations exceed the free list"


# ------------------------------------------------------------ device caches
def _cache_entry_leaf(x) -> bool:
    return isinstance(x, (AttnCache, PagedAttnCache))


def _batch_axis(one) -> int:
    """Batch axis of a B=1 cache leaf: grouped leaves are (G, 1, ...) ->
    axis 1; tail leaves are (1, ...) -> axis 0 (pos scalars handled upstream).
    """
    return 1 if one.ndim >= 2 and one.shape[1] == 1 else 0


def broadcast_slots(one, slots: int):
    """Zero-filled batch leaf with ``slots`` rows, shaped after a B=1 leaf."""
    if one.ndim == 0:  # scalar pos -> per-slot position vector
        return jnp.zeros((slots,), one.dtype)
    axis = _batch_axis(one)
    reps = [1] * one.ndim
    reps[axis] = slots
    return jnp.tile(jnp.zeros_like(one), reps)


def set_slot(b, o, slot):
    """Write one request's B=1 cache leaf into the batch cache at ``slot``.

    ``slot`` may be a traced scalar: scalars route through ``.at[slot]`` and
    arrays through ``dynamic_update_slice``, so admission jit-compiles once.
    """
    if b.ndim == 0:
        return b
    if o.ndim == 0:
        return b.at[slot].set(o.astype(b.dtype))
    return jax.lax.dynamic_update_slice_in_dim(
        b, o.astype(b.dtype), slot, axis=_batch_axis(o))


def _empty_pool_like(one: AttnCache, num_blocks: int,
                     block_size: int) -> PagedAttnCache:
    """Zeroed paged pools shaped after one ring cache leaf (keeps the group
    axis and KV/hd geometry; drops the per-slot time axis)."""

    def pool(ring, tail_dims):
        # ring k/v: (..., 1, T, KV, hd) tail_dims=2; scales: (..., 1, T, KV)
        # tail_dims=1.  Drop the (1, T) per-slot window, keep any group axis.
        lead = ring.shape[:-(2 + tail_dims)]
        shape = lead + (num_blocks, block_size) + ring.shape[-tail_dims:]
        return jnp.zeros(shape, ring.dtype)

    return PagedAttnCache(
        k=pool(one.k, 2), v=pool(one.v, 2),
        k_scale=None if one.k_scale is None else pool(one.k_scale, 1),
        v_scale=None if one.v_scale is None else pool(one.v_scale, 1))


def init_paged_cache(one, slots: int, num_blocks: int, block_size: int):
    """Empty batched paged cache shaped after one request's ring StackCache.

    Attention leaves become shared :class:`PagedAttnCache` pools; recurrent
    and conv states stay dense per-slot tensors (they are O(1) in sequence
    length, so paging buys nothing there); ``pos`` becomes a per-slot vector.
    """
    from repro.models.transformer import StackCache

    def build(entry):
        if isinstance(entry, AttnCache):
            return _empty_pool_like(entry, num_blocks, block_size)
        return jax.tree.map(lambda o: broadcast_slots(o, slots), entry)

    groups = jax.tree.map(build, one.groups, is_leaf=_cache_entry_leaf)
    tail = jax.tree.map(build, one.tail, is_leaf=_cache_entry_leaf)
    return StackCache(groups, tail, jnp.zeros((slots,), jnp.int32))


def _scatter_ring(pool: PagedAttnCache, ring: AttnCache,
                  table_row) -> PagedAttnCache:
    """Scatter a (B=1) ring cache's valid rows into the paged pools.

    Destination of ring row ``j`` is named by its own ``key_pos[j]`` (the
    ring's source of truth): position ``p`` lands at flat pool row
    ``table_row[p // bs] * bs + p % bs``.  Invalid rows (``key_pos == -1``,
    e.g. the padded tail of a bucketed ragged prefill) and rows whose logical
    block is unallocated map out of bounds and are dropped.
    """
    nb, bs = pool.k.shape[-4], pool.k.shape[-3]
    kp = ring.key_pos  # (..., 1, T)
    tbl = jnp.where(table_row < 0, nb, table_row)  # OOB sentinel
    blk = tbl[jnp.clip(kp, 0, None) // bs]  # (..., 1, T)
    dest = jnp.where(kp >= 0, blk * bs + kp % bs, nb * bs)  # (..., 1, T)
    idx = jnp.squeeze(dest, axis=-2)  # drop the B=1 axis -> (..., T)

    def scat(pool_arr, ring_arr, tail_dims):
        # pool (..., NB, bs, *tail); ring (..., 1, T, *tail); idx (..., T)
        flat = pool_arr.reshape(pool_arr.shape[:-(2 + tail_dims)] + (nb * bs,)
                                + pool_arr.shape[-tail_dims:])
        src = jnp.squeeze(ring_arr, axis=-(2 + tail_dims))  # (..., T, *tail)
        if flat.ndim == 1 + tail_dims:  # tail leaf: (NB*bs, *tail)
            out = flat.at[idx].set(src.astype(flat.dtype), mode="drop")
        else:  # grouped leaf: (G, NB*bs, *tail) with idx (G, T)
            out = jax.vmap(
                lambda f, i, s: f.at[i].set(s.astype(f.dtype), mode="drop")
            )(flat, idx, src)
        return out.reshape(pool_arr.shape)

    return PagedAttnCache(
        k=scat(pool.k, ring.k, 2), v=scat(pool.v, ring.v, 2),
        k_scale=(None if pool.k_scale is None
                 else scat(pool.k_scale, ring.k_scale, 1)),
        v_scale=(None if pool.v_scale is None
                 else scat(pool.v_scale, ring.v_scale, 1)))


def merge_prefill_cache(batch, one, table_row, slot):
    """Merge one request's freshly prefilled (B=1) ring cache into the batch.

    Pure function of (batch paged cache, ring cache, (max_blocks,) block
    table row, slot index) — jit it once and admission is data-only:
    attention leaves scatter into the shared pools via the table row,
    recurrent/conv states and the per-slot ``pos`` write at ``slot``.
    """
    from repro.models.transformer import StackCache

    def merge(b, o):
        if isinstance(b, PagedAttnCache):
            return _scatter_ring(b, o, table_row)
        return jax.tree.map(lambda bb, oo: set_slot(bb, oo, slot), b, o)

    groups = jax.tree.map(merge, batch.groups, one.groups,
                          is_leaf=_cache_entry_leaf)
    tail = jax.tree.map(merge, batch.tail, one.tail,
                        is_leaf=_cache_entry_leaf)
    pos = batch.pos.at[slot].set(one.pos.astype(batch.pos.dtype))
    return StackCache(groups, tail, pos)
