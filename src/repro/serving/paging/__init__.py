"""Paged KV-cache subsystem: fixed-size block arena + radix prefix cache
+ block-table-aware attention (paged decode, chunked-prefill extend)."""
from repro.serving.paging.allocator import (BlockPool, PagedKVCache,
                                            SlotFork, SwappedChain,
                                            arena_shape)
from repro.serving.paging.attention import (decode_step_paged,
                                            extend_step_paged, gather_blocks,
                                            verify_step_paged, write_kv)
from repro.serving.paging.radix import RadixPrefixCache

__all__ = [
    "BlockPool", "PagedKVCache", "RadixPrefixCache", "SlotFork",
    "SwappedChain", "arena_shape",
    "decode_step_paged", "extend_step_paged", "gather_blocks",
    "verify_step_paged", "write_kv",
]
