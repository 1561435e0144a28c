"""Host-side KV block allocator.

The bookkeeping half of the paged cache (device half:
``dlti_tpu.ops.kv_cache``) — the role vLLM's C++/Python BlockManager plays in
the stack the reference claims but doesn't ship (``README.md:10``). One
manager a pool: a model whose layers differ in their attention window has
two groups of layers, a pool size and a manager each
(``serving.engine``: the group that sees every key, and the window group,
whose blocks are released behind the window while their sequence lives).

Allocation is delegated to the C++ core via ctypes (``native/``, built from
source on first use — ``dlti_tpu.utils.native``); where that cannot be
built a pure-Python free-list serves. Both implement the same contract and
are covered by the same tests.

Physical block 0 is reserved as a trash block: inactive decode slots write
their (ignored) K/V there, so the compiled decode step never needs a branch
on slot liveness.
"""

from __future__ import annotations

from typing import List, Optional

from dlti_tpu.utils.native import load_native_runtime


class BlockManager:
    """Free-list allocator over ``num_blocks`` physical KV blocks."""

    TRASH_BLOCK = 0

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is reserved)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._native = load_native_runtime()
        if self._native is not None:
            self._handle = self._native.dlti_allocator_create(num_blocks)
        else:
            self._handle = None
            # Block 0 reserved; LIFO free list for cache locality.
            self._free: List[int] = list(range(num_blocks - 1, 0, -1))
            # O(1) double-free guard: the set of live (handed-out) blocks.
            # A double free would silently put one block on the free list
            # twice — two sequences then share a "private" block and decode
            # state corrupts with no error anywhere near the cause.
            self._allocated: set = set()

    def __del__(self):
        if getattr(self, "_native", None) is not None and self._handle:
            self._native.dlti_allocator_destroy(self._handle)
            self._handle = None

    # ------------------------------------------------------------------
    @property
    def num_free(self) -> int:
        if self._native is not None:
            return self._native.dlti_allocator_num_free(self._handle)
        return len(self._free)

    def blocks_needed(self, num_tokens: int) -> int:
        return -(-num_tokens // self.block_size)

    def can_allocate(self, n: int) -> bool:
        return self.num_free >= n

    def allocate(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` blocks; returns None (allocating nothing) if they
        don't all fit — admission is all-or-nothing."""
        if n == 0:
            return []
        if self._native is not None:
            import ctypes

            out = (ctypes.c_int32 * n)()
            ok = self._native.dlti_allocator_allocate(self._handle, n, out)
            return list(out) if ok else None
        if len(self._free) < n:
            return None
        blocks = [self._free.pop() for _ in range(n)]
        self._allocated.update(blocks)
        return blocks

    def free(self, blocks: List[int]) -> None:
        """Return ``blocks`` to the pool. Raises on an invalid id or a
        double free (all-or-nothing: a rejected call frees none), instead
        of silently corrupting the pool into handing one block to two
        sequences."""
        if not blocks:
            return
        if self._native is not None:
            import ctypes

            arr = (ctypes.c_int32 * len(blocks))(*blocks)
            ok = self._native.dlti_allocator_free_checked(
                self._handle, len(blocks), arr)
            if not ok:
                raise ValueError(
                    f"invalid or double free in {blocks} (native "
                    "allocator rejected the batch; no block was freed)")
            return
        # Validate the whole batch first (including intra-batch
        # duplicates) so a raise frees nothing.
        seen: set = set()
        for b in blocks:
            if b == self.TRASH_BLOCK or b <= 0 or b >= self.num_blocks:
                raise ValueError(f"freeing invalid block {b}")
            if b not in self._allocated or b in seen:
                raise ValueError(
                    f"double free of block {b} (not currently allocated); "
                    "freeing it again would hand the same block to two "
                    "sequences and silently corrupt their KV")
            seen.add(b)
        for b in blocks:
            self._allocated.discard(b)
            self._free.append(b)
