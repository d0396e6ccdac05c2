"""Per-sequence block tables over per-dp-row physical block pools.

``PagedKVCache`` is the control plane of the paged cache: for each engine
slot it keeps the logical→physical block mapping and the number of mapped
blocks.  The data plane — the head-major
``[dp*num_blocks, kv_slots, block_size, Dh]`` pools inside the jitted step
functions — is owned by the model/engine; the manager only decides *which*
physical block backs each logical block.

Data parallelism pages per row: each dp row owns an independent
``BlockAllocator`` over its own ``num_blocks`` physical blocks (block 0 of
every row is that row's null block), and the engine slots are statically
partitioned into ``dp`` contiguous ranges of ``slots_per_row`` — slot ``s``
belongs to row ``s // slots_per_row``.  Block-table entries are *row-local*
ids: inside ``shard_map`` each dp shard indexes its local pool slice
directly, so the indirection needs no cross-row arithmetic on device.  The
data plane concatenates the row pools on the leading block axis (sharded
over the dp mesh axes), so host-side *global* physical ids — what
``copy_on_write`` returns for the COW data plane and what the shared-block
invariance check consumes — are ``row * num_blocks + local``.

Why the block layout is shard-invariant (the paper's §3.3.1 condition,
extended to paging): a block's ``kv_slots`` axis is sharded over the
tp-major model group exactly like the contiguous cache's head axis, and the
``[block_size, Dh]`` axes are unsharded; the leading block axis is
sharded over the *dp* axes only, which both configs share untouched.  Base
(SP,TP) and shift (TP) configs therefore assign identical byte ranges of
every physical block to identical devices, and the block table itself is
replicated across the model group (sharded only over dp, aligned with the
pool rows) — so an SP↔TP switch on a paged cache still moves zero bytes,
per row and globally.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from .block_allocator import BlockAllocator, BlockOOM
from .prefix_index import PrefixIndex


def blocks_for_tokens(n_tokens: int, block_size: int) -> int:
    """Physical blocks needed to hold ``n_tokens`` cache entries (ceil —
    the last block's tail slots are the paging fragmentation)."""
    return -(-max(n_tokens, 0) // block_size)


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n — the engine's shape-bucketing rule
    (compiled-program reuse). The roofline CostModel imports it so gather
    pricing buckets exactly like the engine's sliced launches; keep ONE
    definition or the model silently drifts from the behavior it prices."""
    p = 1
    while p < n:
        p <<= 1
    return p


class PagedKVCache:
    def __init__(self, num_blocks: int, block_size: int, max_seqs: int,
                 max_blocks_per_seq: int, dp: int = 1):
        assert dp >= 1 and max_seqs % dp == 0, \
            f"max_seqs={max_seqs} must be divisible by dp={dp}"
        self.block_size = block_size
        self.max_seqs = max_seqs
        self.max_blocks_per_seq = max_blocks_per_seq
        self.dp = dp
        self.slots_per_row = max_seqs // dp
        # per-row physical blocks INCLUDING each row's own null block
        self.num_blocks_per_row = num_blocks
        self.allocators: List[BlockAllocator] = [
            BlockAllocator(num_blocks) for _ in range(dp)]
        # logical block i of slot s lives in physical block table[s, i] of
        # row s // slots_per_row's pool (row-LOCAL id); unmapped entries
        # point at the row's null block (0)
        self.table = np.zeros((max_seqs, max_blocks_per_seq), np.int32)
        self.n_mapped = np.zeros((max_seqs,), np.int32)
        # slots whose table rows changed since the last take_dirty() — lets
        # the engine keep a persistent host mirror and re-copy only changed
        # rows instead of rebuilding the full [max_seqs, nmax] array each step
        self._dirty: set = set()
        # optional per-row prefix caches: when set, allocation pressure in a
        # row first evicts that row's unpinned cached-prefix blocks
        # (leaf-first LRU) before reporting OOM. Rows never evict each other.
        self.prefix_indices: List[Optional[PrefixIndex]] = [None] * dp

    # ------------------------------------------------------------ dp helpers
    def row_of(self, seq: int) -> int:
        """dp row that owns engine slot ``seq``."""
        return seq // self.slots_per_row

    def global_block(self, row: int, local_block: int) -> int:
        """Data-plane (pool-global) id of ``local_block`` in ``row``."""
        return row * self.num_blocks_per_row + local_block

    @property
    def table3(self) -> np.ndarray:
        """``[dp, slots_per_row, nmax]`` view of the block tables (shares
        memory with the flat ``[max_seqs, nmax]`` table)."""
        return self.table.reshape(self.dp, self.slots_per_row,
                                  self.max_blocks_per_seq)

    # ----------------------------------------------------- dp=1 conveniences
    @property
    def allocator(self) -> BlockAllocator:
        """The single allocator of a dp=1 cache (most tests / the serial
        engine path). Row-ambiguous under dp>1 — use ``allocators[row]``."""
        assert self.dp == 1, "kv.allocator is ambiguous under dp>1"
        return self.allocators[0]

    @property
    def prefix_index(self) -> Optional[PrefixIndex]:
        assert self.dp == 1, "kv.prefix_index is ambiguous under dp>1"
        return self.prefix_indices[0]

    @prefix_index.setter
    def prefix_index(self, idx: Optional[PrefixIndex]):
        assert self.dp == 1, "kv.prefix_index is ambiguous under dp>1"
        self.prefix_indices[0] = idx

    def take_dirty(self) -> set:
        """Slots whose tables changed since the last call (and clear)."""
        d, self._dirty = self._dirty, set()
        return d

    # ------------------------------------------------------------- queries
    @property
    def num_free_blocks(self) -> int:
        return sum(a.num_free for a in self.allocators)

    @property
    def num_used_blocks(self) -> int:
        return sum(a.num_used for a in self.allocators)

    def row_free_blocks(self, row: int) -> int:
        return self.allocators[row].num_free

    def capacity_tokens(self, seq: int) -> int:
        """Tokens the currently mapped blocks of ``seq`` can hold."""
        return int(self.n_mapped[seq]) * self.block_size

    def can_allocate(self, n_tokens: int, cached_blocks=(),
                     row: int = 0) -> bool:
        """True when ``n_tokens`` worth of NEW blocks (minus the
        ``cached_blocks`` a prefix match already covers) fits in ``row``'s
        free list plus what that row's prefix-cache eviction could reclaim
        right now.

        The matched blocks must not be double-counted: an index-only
        (refcount 1) matched block appears in ``reclaimable()`` too, but
        mapping it pins it — it both satisfies one needed block AND stops
        being evictable, so it is subtracted from the eviction credit."""
        need = blocks_for_tokens(n_tokens, self.block_size) \
            - len(cached_blocks)
        alloc = self.allocators[row]
        avail = alloc.num_free
        idx = self.prefix_indices[row]
        if idx is not None:
            matched_evictable = sum(
                1 for b in cached_blocks if alloc.ref_count(b) == 1)
            avail += max(idx.reclaimable() - matched_evictable, 0)
        return need <= avail

    def seq_blocks(self, seq: int):
        """Row-local physical block ids mapped by ``seq``, logical order."""
        return [int(b) for b in self.table[seq, :self.n_mapped[seq]]]

    # ------------------------------------------------------------ alloc/free
    def _alloc(self, n: int, row: int) -> List[int]:
        """Allocate ``n`` blocks from ``row``'s pool, evicting that row's
        unpinned cached-prefix blocks (leaf-first LRU) under pressure.
        Raises BlockOOM like the allocator. Eviction only runs when it can
        fully cover the shortfall — a doomed allocation must leave the
        index untouched so a failed ensure/COW is genuinely state-unchanged
        (failed admission probes must not drain the prefix cache)."""
        alloc = self.allocators[row]
        idx = self.prefix_indices[row]
        short = n - alloc.num_free
        if short > 0 and idx is not None and idx.reclaimable() >= short:
            idx.evict(short)
        return alloc.alloc(n)

    def ensure(self, seq: int, n_tokens: int) -> bool:
        """Grow ``seq``'s table to cover ``n_tokens`` positions. Returns
        False (state unchanged) when its row's free list cannot satisfy
        it."""
        need = blocks_for_tokens(n_tokens, self.block_size)
        if need > self.max_blocks_per_seq:
            raise ValueError(
                f"sequence needs {need} blocks > max_blocks_per_seq="
                f"{self.max_blocks_per_seq}")
        grow = need - int(self.n_mapped[seq])
        if grow <= 0:
            return True
        try:
            new = self._alloc(grow, self.row_of(seq))
        except BlockOOM:
            return False
        self.table[seq, self.n_mapped[seq]:need] = new
        self.n_mapped[seq] = need
        self._dirty.add(seq)
        return True

    def assign_prefix(self, seq: int, blocks: Sequence[int]):
        """Map already-cached prefix blocks (row-local ids from the row's
        ``PrefixIndex.match``) into an empty slot's table, taking one
        reference per block. The sequence then prefills starting at
        ``len(blocks) * block_size``."""
        assert self.n_mapped[seq] == 0, "prefix assignment into a mapped slot"
        assert BlockAllocator.NULL_BLOCK not in blocks
        alloc = self.allocators[self.row_of(seq)]
        for b in blocks:
            alloc.incref(b)
        n = len(blocks)
        self.table[seq, :n] = np.asarray(blocks, np.int32)
        self.n_mapped[seq] = n
        if n:
            self._dirty.add(seq)

    def copy_on_write(self, seq: int, start_tok: int,
                      end_tok: int) -> Tuple[bool, List[Tuple[int, int]]]:
        """Make the mapped blocks covering positions ``[start_tok, end_tok)``
        exclusively owned before a write: every block with refcount > 1 in
        that range is remapped to a fresh block from the sequence's row.
        Returns ``(ok, copies)`` where ``copies`` is the [(src, dst), ...]
        list of physical block copies — in pool-GLOBAL ids (row offset
        applied), ready for the data plane — the caller must apply to the
        device pool BEFORE the write lands (the manager is control-plane
        only). On OOM returns ``(False, [])`` with the table unchanged."""
        if end_tok <= start_tok:
            return True, []
        row = self.row_of(seq)
        alloc = self.allocators[row]
        first = start_tok // self.block_size
        last = min((end_tok - 1) // self.block_size, int(self.n_mapped[seq]) - 1)
        shared = [i for i in range(first, last + 1)
                  if alloc.ref_count(int(self.table[seq, i])) > 1]
        if not shared:
            return True, []
        try:
            fresh = self._alloc(len(shared), row)
        except BlockOOM:
            return False, []
        off = row * self.num_blocks_per_row
        copies = []
        for i, dst in zip(shared, fresh):
            src = int(self.table[seq, i])
            alloc.decref(src)               # shared: decrements, never frees
            self.table[seq, i] = dst
            copies.append((src + off, dst + off))
        self._dirty.add(seq)
        return True, copies

    def free_seq(self, seq: int):
        blocks = self.seq_blocks(seq)
        # Refcount invariants (the COW path relies on these to keep the free
        # list sound): a mapped entry is never the null block — freeing a
        # slot can therefore never decref block 0, whose refcount the
        # allocator does not track — and shared blocks (prefix-cache pins,
        # forked tables) are DECREMENTED here, not freed; the last holder
        # (or an index eviction) returns them to the free list.
        assert BlockAllocator.NULL_BLOCK not in blocks, \
            f"slot {seq} maps the null block — table corrupt"
        self.allocators[self.row_of(seq)].free(blocks)
        self.table[seq, :] = BlockAllocator.NULL_BLOCK
        self.n_mapped[seq] = 0
        self._dirty.add(seq)

    def truncate(self, seq: int, n_tokens: int) -> int:
        """Unmap ``seq``'s tail blocks beyond ``n_tokens`` coverage —
        the speculative-decode rollback primitive. Rejected draft KV
        needs no data-plane work: positions inside kept blocks are
        masked by the context length and overwritten position-
        idempotently by later steps; only blocks wholly past the
        accepted length are returned here (decrement-not-free, same
        invariants as ``free_seq``). Returns the number of table
        entries unmapped."""
        keep = blocks_for_tokens(n_tokens, self.block_size)
        cur = int(self.n_mapped[seq])
        if keep >= cur:
            return 0
        tail = [int(b) for b in self.table[seq, keep:cur]]
        assert BlockAllocator.NULL_BLOCK not in tail, \
            f"slot {seq} maps the null block — table corrupt"
        self.allocators[self.row_of(seq)].free(tail)
        self.table[seq, keep:cur] = BlockAllocator.NULL_BLOCK
        self.n_mapped[seq] = keep
        self._dirty.add(seq)
        return cur - keep

    def fork(self, src: int, dst: int):
        """Share src's blocks into dst (ref-counted) — prefix-sharing hook.
        Writes into dst must go through ``copy_on_write`` first. Both slots
        must live in the same dp row: physical blocks never cross rows."""
        assert src != dst, "fork onto itself"
        assert self.row_of(src) == self.row_of(dst), \
            "fork across dp rows — blocks are row-physical"
        assert self.n_mapped[dst] == 0, "fork into a mapped slot"
        # dst's table must be fully cleared (all-null), not just n_mapped=0:
        # stale physical ids past n_mapped would alias freed blocks if a
        # later ensure() grew the row without rewriting every entry.
        assert (self.table[dst] == BlockAllocator.NULL_BLOCK).all(), \
            f"slot {dst} table not cleared before fork"
        alloc = self.allocators[self.row_of(src)]
        for b in self.seq_blocks(src):
            alloc.incref(b)
        n = int(self.n_mapped[src])
        self.table[dst, :n] = self.table[src, :n]
        self.n_mapped[dst] = n
        self._dirty.add(dst)

    # ----------------------------------------------------------- snapshot
    def state_dict(self) -> dict:
        return {"block_size": self.block_size,
                "max_blocks_per_seq": self.max_blocks_per_seq,
                "dp": self.dp,
                "table": self.table.copy(),
                "n_mapped": self.n_mapped.copy(),
                "allocators": [a.state_dict() for a in self.allocators]}

    @classmethod
    def from_state(cls, state: dict) -> "PagedKVCache":
        # pre-dp snapshots carried a single "allocator" and no "dp" key —
        # load them as dp=1 so a PR-3-era checkpoint still restores
        alloc_states = state.get("allocators") or [state["allocator"]]
        kv = cls(alloc_states[0]["num_blocks"], state["block_size"],
                 state["table"].shape[0], state["max_blocks_per_seq"],
                 dp=state.get("dp", 1))
        kv.table = state["table"].copy()
        kv.n_mapped = state["n_mapped"].copy()
        kv.allocators = [BlockAllocator.from_state(s) for s in alloc_states]
        kv._dirty = set(range(kv.table.shape[0]))   # force mirror refresh
        return kv
