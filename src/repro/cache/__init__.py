"""Paged KV-cache management (vLLM-style, shard-invariant, per-dp-row).

The physical KV pool is a pool of fixed-size head-major blocks
``[dp * num_blocks, kv_head_slots, block_size, head_dim]`` — ``num_blocks``
blocks per data-parallel row, leading axis sharded over the dp mesh axes —
whose *head* dimension carries the only model-parallel sharding:
``P(dp_axes, model_axes, None, None)``.  Because the base (SP,TP) and shift
(TP) configurations share the same tp-major model group (paper §3.3.1) and
identical dp axes, the byte-range→device map of every block is identical
under both configs: switching parallelism moves zero bytes even though
sequences now live in scattered blocks.  Block tables are int32 indices
replicated across the model group (sharded only over dp, aligned with the
pool rows), so the indirection itself is also rank-invariant.

``BlockAllocator`` hands out ref-counted physical blocks from a free list —
one allocator per dp row, with row-local ids in the tables so each dp shard
indexes its local pool slice directly; ``PagedKVCache`` maps each engine
slot to a logical→physical block table (slots partition statically into dp
rows).  Both are host-side (numpy) control-plane objects — the data plane
stays in jitted model step functions that consume the block table as a
device array.

``PrefixIndex`` adds automatic prefix caching on top: full blocks of token
ids are indexed by chained hash and pinned with their own reference, so a
later request with the same prompt prefix maps the cached blocks instead of
recomputing them.  Writes into shared blocks go through
``PagedKVCache.copy_on_write``.
"""
from .block_allocator import BlockAllocator, BlockOOM
from .paged import PagedKVCache, blocks_for_tokens, pow2_bucket
from .prefix_index import PrefixIndex

__all__ = ["BlockAllocator", "BlockOOM", "PagedKVCache", "PrefixIndex",
           "blocks_for_tokens", "pow2_bucket"]
