"""Block-pool KV allocation for the continuous engine (paged KV cache).

The dense slot layout allocates one ``(L, B, K, T, hd)`` cache with
``T = max_seq_len`` for EVERY slot, so a 64-slot batch pays full-window HBM
and decode bandwidth for rows holding a 300-token prompt — the round-5
capture (before PR 1, in git history) had device decode steps/s collapsing
by almost two thirds from B=8 to B=64 on exactly that waste. PagedAttention (vLLM; Kwon et al. 2023) and JetStream's TPU serving
design both make the same move: carve the KV arena into fixed-size physical
**blocks**, give every row an int32 *block table* mapping its logical token
positions onto pool blocks, and allocate blocks only as a row's frontier
actually reaches them.

This module is the HOST-side allocator — pure bookkeeping, no jax imports:

- **free list**: physical block ids are handed out O(1) from a deque and
  returned on release; no compaction is ever needed (any block serves any
  logical position — the table provides the indirection);
- **ref counts**: a block mapped into several rows' tables (prefix-cache
  hits sharing a prompt head) is freed only when its LAST reader releases
  it, which is what makes shared prefix blocks copy-free;
- **the null block**: physical block 0 is RESERVED and never allocated.
  Table entries for logical blocks a row has not reached (or fully-padded
  regions) point at it; device code may harmlessly write junk there and the
  attention kernels never read it (out-of-window blocks are skipped), so
  executables can keep static loop shapes without per-block conditionals;
- **exhaustion is an exception, not a crash**: ``alloc`` is all-or-nothing
  and raises :class:`PoolExhausted`; the engine turns that into admission
  backpressure (requests wait in the queue → the PR-4 admission gate sheds
  429s) or mid-decode preemption, never an OOM abort.

The device arena itself — ``(L, num_blocks, K, block_size, hd)`` plus scale
planes under int8-KV — is engine state (it is donated through the step
executables); the pool only tracks which physical ids are live.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Dict, Iterable, List

from rag_llm_k8s_tpu.obs import flight

__all__ = ["KVBlockPool", "PoolExhausted", "NULL_BLOCK"]

# physical block 0: reserved write-sink / never-read placeholder (see module
# docstring). Every block table starts life filled with it.
NULL_BLOCK = 0


class PoolExhausted(RuntimeError):
    """The pool cannot serve an allocation right now.

    Deliberately NOT an OOM: every block is accounted for, the device arena
    is intact, and freeing any row (retire / eviction / preemption) makes
    the allocation servable again. Callers translate this into
    backpressure, not a reset.
    """

    def __init__(self, requested: int, available: int):
        super().__init__(
            f"kv pool exhausted: requested {requested} block(s), "
            f"{available} free"
        )
        self.requested = requested
        self.available = available


class KVBlockPool:
    """Free-list + ref-count allocator over ``num_blocks`` physical blocks
    of ``block_size`` tokens each (block 0 reserved as the null block).

    Thread-safe: the scheduler thread owns the hot path, but prefix-block
    pinning and metric scrapes arrive from other threads; every method
    takes the one small lock.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError(
                f"kv pool needs >= 2 blocks (1 reserved null + 1 usable), "
                f"got {num_blocks}"
            )
        if block_size < 1:
            raise ValueError(f"block_size={block_size}: expected >= 1")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._lock = threading.Lock()
        # LIFO reuse: a just-freed block's arena region is the likeliest to
        # still be resident in any cache hierarchy, and tests get
        # deterministic id sequences either way
        self._free: deque = deque(range(1, self.num_blocks))
        self._refs: Dict[int, int] = {}
        # cumulative counters (engine stats)
        self.total_allocs = 0
        self.total_exhaustions = 0
        # per-tier occupancy of REGISTERED prefix blocks (hotness tiering,
        # engine/tiering.py): the engine accounts each registration's
        # blocks under its chunk's tier at register/drop/retier time, so
        # admission can tell "the pool is full of hot rows" (true pressure)
        # from "the pool is full of demotable cache warmth" (reclaimable).
        # Pure bookkeeping — the allocator itself is tier-oblivious.
        self._tier_blocks: Dict[str, int] = {"hot": 0, "warm": 0, "cold": 0}

    # -- capacity -------------------------------------------------------
    def blocks_for(self, tokens: int) -> int:
        """Blocks needed to cover ``tokens`` logical positions. Also the
        incremental-admission arithmetic: a chunked prefill (ISSUE 16)
        grows a row per scheduled chunk by
        ``blocks_for(progress + chunk) - blocks_for(progress)`` instead of
        paying the whole prompt's allocation up front."""
        return max(0, -(-int(tokens) // self.block_size))

    def available(self) -> int:
        with self._lock:
            return len(self._free)

    def blocks_in_use(self) -> int:
        with self._lock:
            return (self.num_blocks - 1) - len(self._free)

    def usable_blocks(self) -> int:
        """Allocatable capacity (total minus the reserved null block)."""
        return self.num_blocks - 1

    def can_alloc(self, n: int) -> bool:
        with self._lock:
            return n <= len(self._free)

    def fragmentation(self, used_tokens: int) -> float:
        """INTERNAL fragmentation: the fraction of allocated token slots not
        holding live KV (``1 - used / (in_use * block_size)``). External
        fragmentation cannot exist here — any free block satisfies any
        request — so this is the number worth a gauge: it is the pad/waste
        the paged layout still pays (bounded by one block per row plus
        ref-shared prefix tails) vs the dense layout's full-window waste."""
        in_use = self.blocks_in_use()
        if in_use <= 0:
            return 0.0
        cap = in_use * self.block_size
        return max(0.0, min(1.0, 1.0 - float(used_tokens) / cap))

    # -- alloc / ref / free --------------------------------------------
    def alloc(self, n: int) -> List[int]:
        """Take ``n`` blocks (refcount 1 each) — ALL-OR-NOTHING. Raises
        :class:`PoolExhausted` without side effects when short."""
        if n <= 0:
            return []
        with self._lock:
            free = len(self._free)
            if n > free:
                self.total_exhaustions += 1
                ids = None
            else:
                ids = [self._free.pop() for _ in range(n)]
                for b in ids:
                    self._refs[b] = 1
                self.total_allocs += n
                free -= n
        # journal outside the lock: the flight recorder is lock-cheap but
        # the allocator's lock is on the admission hot path
        if ids is None:
            flight.emit("pool_exhausted", requested=n, free=free)
            raise PoolExhausted(n, free)
        flight.emit("pool_alloc", blocks=n, free=free)
        return ids

    def ref(self, ids: Iterable[int]) -> None:
        """Add one reference to each block (prefix sharing: a row mapping a
        cached block into its table pins it for the row's lifetime)."""
        with self._lock:
            for b in ids:
                if b == NULL_BLOCK:
                    continue
                if b not in self._refs:
                    raise ValueError(f"ref() of unallocated block {b}")
                self._refs[b] += 1

    def free(self, ids: Iterable[int]) -> int:
        """Drop one reference per block; blocks reaching zero return to the
        free list. Null blocks and duplicates-after-zero are rejected loudly
        (a double free is a table-bookkeeping bug, not a runtime condition).
        Returns how many blocks actually became free."""
        reclaimed = 0
        with self._lock:
            for b in ids:
                if b == NULL_BLOCK:
                    continue
                refs = self._refs.get(b)
                if refs is None:
                    raise ValueError(f"free() of unallocated block {b}")
                if refs <= 1:
                    del self._refs[b]
                    self._free.append(b)
                    reclaimed += 1
                else:
                    self._refs[b] = refs - 1
            free = len(self._free)
        if reclaimed:
            flight.emit("pool_free", blocks=reclaimed, free=free)
        return reclaimed

    def refcount(self, block: int) -> int:
        with self._lock:
            return self._refs.get(block, 0)

    # -- tier accounting (hotness-aware KV tiering) ---------------------
    def account_tier(self, tier: str, delta: int) -> None:
        """Move ``delta`` registered-prefix blocks into ``tier``'s ledger
        (negative = out). The engine calls this at registration, drop, and
        retier sites; clamped at zero so a double-drop can't go negative."""
        if tier not in self._tier_blocks:
            raise ValueError(
                f"unknown kv tier {tier!r}; tiers: {tuple(self._tier_blocks)}"
            )
        with self._lock:
            self._tier_blocks[tier] = max(0, self._tier_blocks[tier] + delta)

    def tier_occupancy(self) -> Dict[str, int]:
        """Registered-prefix blocks per tier + the non-registration rest
        (``rows`` — blocks owned by live decode rows, derived)."""
        with self._lock:
            out = dict(self._tier_blocks)
            registered = sum(out.values())
            in_use = (self.num_blocks - 1) - len(self._free)
            out["rows"] = max(0, in_use - registered)
            return out

    def reset(self) -> None:
        """Return EVERY block to the free list (engine reset: the arena is
        rebuilt and every table with it — holding stale refs would leak the
        pool a reset at a time; tests assert zero leaked blocks after the
        chaos lane's EngineStateLost)."""
        with self._lock:
            self._refs.clear()
            self._free = deque(range(1, self.num_blocks))
            # registrations died with the arena: their tier ledgers must
            # read zero or admission would see phantom reclaimable warmth
            for t in self._tier_blocks:
                self._tier_blocks[t] = 0

    def stats(self) -> Dict[str, int]:
        with self._lock:
            in_use = (self.num_blocks - 1) - len(self._free)
            return {
                "kv_pool_blocks_total": self.num_blocks - 1,
                "kv_pool_blocks_in_use": in_use,
                "kv_pool_blocks_free": len(self._free),
                "kv_pool_allocs_total": self.total_allocs,
                "kv_pool_exhaustions_total": self.total_exhaustions,
                "kv_pool_tier_hot_blocks": self._tier_blocks["hot"],
                "kv_pool_tier_warm_blocks": self._tier_blocks["warm"],
            }

    def __repr__(self) -> str:  # debugging / log lines
        s = self.stats()
        return (
            f"KVBlockPool(bs={self.block_size}, "
            f"in_use={s['kv_pool_blocks_in_use']}/{s['kv_pool_blocks_total']})"
        )
