"""HBM KV block-pool allocator (ISSUE 12 tentpole, host side).

The dense serving cache makes concurrency a function of ``batch ×
max_len``: every admitted row owns a full ``max_len`` run of KV slots
whether it uses them or not, and PR 9's capacity model shows that dead
padding IS the measured batch ceiling (14.78 GiB static at batch 40 →
runtime OOM). The paged layout (PagedAttention, vLLM SOSP '23) splits
the resident cache into one static arena of ``n_blocks`` fixed-size
blocks (``block_size == SEQ_BUCKET`` — the serving grain, so prompt
buckets and prefix-entry buckets are always whole-block runs) plus a
per-row int32 block table. Every jit-visible shape stays static; what
becomes dynamic is purely HOST bookkeeping — which pool block backs
which logical row position — and that bookkeeping lives here.

This class is the ONE allocator the refactor unifies row allocation,
prefix-entry pinning and copy-on-write around:

  * ``alloc(n)`` hands out ``n`` blocks at refcount 1 (or None — the
    admission gate: a request only admits when its whole reservation
    fits, so decode can never OOM mid-flight);
  * ``incref``/``decref`` implement prefix sharing: a prefix-cache hit
    aliases the entry's full blocks into the new row's table instead of
    copying them, and the block returns to the free list only when its
    LAST owner (rows + the cache entry itself) drops it;
  * ``cow`` is the copy-on-write primitive: a writer that holds a
    shared block trades it for a private copy target (the device copy
    is the caller's admission scatter — see ``serve.py``), bumping
    ``cow_copies`` so sharing efficiency is observable;
  * block 0 is the permanently-reserved SCRATCH block: free rows' and
    finished rows' tables point at it, so the segment kernels'
    unconditional frozen-row writes (the donated-aliasing rule) land in
    storage nothing ever reads — never in a recycled block another
    request now owns.

Thread contract: the owning ``ContinuousBatcher`` is externally
serialized, but HTTP handler threads read ``stats()`` — so every
mutation and compound read runs under ``_lock`` (the ``_GUARDED_BY``
annotations below are enforced by egpt-check rule ``lock``, and the
spy-lock test in ``tests/test_paged_blocks.py`` holds alloc/free inside
the critical section). Lock order: ``PrefixCache._lock ->
BlockPool._lock`` (entry eviction releases blocks while holding the
trie lock); this lock is a leaf above only the metric locks.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence

from eventgpt_tpu.obs import memory as obs_memory
from eventgpt_tpu.obs import metrics as obs_metrics

# Reserved scratch block: free/finished rows' block tables point here so
# frozen-row garbage writes can never land in a recycled block.
SCRATCH_BLOCK = 0


class BlockPoolError(RuntimeError):
    """Allocator invariant violated (double free, unknown block, refcount
    underflow) — a bug, never an overload signal (overload is ``alloc``
    returning None)."""


class BlockPool:
    """Refcounted free-list allocator over ``n_blocks`` pool blocks of
    ``block_size`` KV positions each.

    ``n_blocks`` counts the whole arena INCLUDING the scratch block, so
    ``usable`` (= n_blocks - 1) is the real capacity the admission gate
    sees. ``block_bytes`` is carried for observability only (the gauges
    and ``stats()`` report bytes alongside block counts).
    """

    # Lock-discipline contract (egpt-check rule ``lock``): the free
    # list, refcounts, spill registry and counters only move under the
    # pool lock.
    _GUARDED_BY = {
        "_free": "_lock",
        "_refs": "_lock",
        "_spilled": "_lock",
        "_next_spill_id": "_lock",
        "allocs": "_lock",
        "frees": "_lock",
        "cow_copies": "_lock",
        "alloc_failures": "_lock",
        "spills": "_lock",
        "restores": "_lock",
    }

    def __init__(self, n_blocks: int, block_size: int,
                 block_bytes: int = 0):
        if n_blocks < 2:
            raise ValueError(
                f"block pool needs >= 2 blocks (1 scratch + 1 usable), "
                f"got {n_blocks}")
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        self.block_bytes = int(block_bytes)
        self._lock = threading.Lock()
        # Refcount per block; scratch is permanently pinned at 1 so it
        # can never be handed out or freed.
        self._refs: List[int] = [0] * self.n_blocks
        self._refs[SCRATCH_BLOCK] = 1
        # LIFO free list: recently-freed blocks are re-used first, which
        # keeps the touched working set small.
        self._free: List[int] = list(range(self.n_blocks - 1, 0, -1))
        self.allocs = 0
        self.frees = 0
        self.cow_copies = 0
        self.alloc_failures = 0
        # Spill registry (ISSUE 16): run_id -> block count of a row's
        # KV run whose BYTES left the arena for the host-RAM SpillStore.
        # The device blocks themselves return to the free list at spill
        # time; the registry only remembers how many blocks the run
        # needs back so ``restore`` stays a plain allocation with a
        # loud-failure identity check.
        self._spilled: Dict[int, int] = {}
        self._next_spill_id = 0
        self.spills = 0
        self.restores = 0
        self._export_gauges_locked()

    # -- capacity ---------------------------------------------------------

    @property
    def usable(self) -> int:
        """Blocks the allocator can ever hand out (excludes scratch)."""
        return self.n_blocks - 1

    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    def used_blocks(self) -> int:
        with self._lock:
            return self.usable - len(self._free)

    def free_bytes(self) -> int:
        """Free capacity in bytes (``free_blocks * block_bytes``; 0 when
        the pool was built without a byte size) — the decode-placement
        headroom signal the disaggregated router balances on (ISSUE 17):
        block counts only compare within one worker's geometry, bytes
        compare across a fleet."""
        with self._lock:
            return len(self._free) * self.block_bytes

    def blocks_for(self, positions: int) -> int:
        """Blocks covering ``positions`` KV slots (ceil at the block
        grain) — the reservation arithmetic shared by admission gating,
        the mem-guard repricing and the ledger's closed form."""
        return (max(int(positions), 0) + self.block_size - 1) \
            // self.block_size

    # -- alloc / free -----------------------------------------------------

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` fresh blocks at refcount 1, or None when the pool cannot
        cover them (the caller defers admission — never a partial
        grant, so a failed admission holds nothing to unwind)."""
        if n <= 0:
            return []
        with self._lock:
            if n > len(self._free):
                self.alloc_failures += 1
                obs_metrics.SERVE_KV_ALLOC_FAILURES.inc()
                return None
            out = [self._free.pop() for _ in range(n)]
            for b in out:
                self._refs[b] = 1
            self.allocs += n
            self._export_gauges_locked()
        return out

    def incref(self, blocks: Sequence[int]) -> None:
        """Add one owner to each block (prefix-entry aliasing)."""
        with self._lock:
            for b in blocks:
                self._check_live_locked(b)
                self._refs[b] += 1

    def decref(self, blocks: Sequence[int]) -> int:
        """Drop one owner from each block; blocks reaching refcount 0
        return to the free list. Returns how many were actually freed."""
        freed = 0
        with self._lock:
            for b in blocks:
                self._check_live_locked(b)
                self._refs[b] -= 1
                if self._refs[b] == 0:
                    self._free.append(b)
                    freed += 1
            self.frees += freed
            self._export_gauges_locked()
        return freed

    def cow(self, block: int) -> Optional[int]:
        """Copy-on-write: trade one reference on a SHARED ``block`` for a
        private block. Returns the private target (the caller performs
        the device copy / re-scatter), or ``block`` itself when it is
        already exclusively owned (no copy needed), or None when the
        pool has no room for the copy. Counts a copy only when one
        actually happens — ``egpt_serve_kv_cow_copies_total``."""
        with self._lock:
            self._check_live_locked(block)
            if self._refs[block] == 1:
                return block
            if not self._free:
                self.alloc_failures += 1
                obs_metrics.SERVE_KV_ALLOC_FAILURES.inc()
                return None
            new = self._free.pop()
            self._refs[new] = 1
            self._refs[block] -= 1
            self.allocs += 1
            self.cow_copies += 1
            obs_metrics.SERVE_KV_COW_COPIES.inc()
            self._export_gauges_locked()
            return new

    def note_cow(self) -> None:
        """Count a copy-on-write copy performed OUTSIDE ``cow`` — the
        serving admission path re-creates a divergent boundary block via
        its scatter (the copy and the write are one dispatch) rather
        than calling ``cow`` per block."""
        with self._lock:
            self.cow_copies += 1
        obs_metrics.SERVE_KV_COW_COPIES.inc()

    # -- spill / restore (ISSUE 16) ---------------------------------------

    def spill_out(self, blocks: Sequence[int]) -> int:
        """Evict an EXCLUSIVELY-OWNED block run from the arena: every
        block must be live at refcount exactly 1 (a pinned / aliased
        block has another owner whose table would dangle — refused with
        ``BlockPoolError``, and the caller falls back to
        drop-and-re-prefill). The blocks return to the free list — the
        caller has already gathered their bytes to the host — and the
        returned ``run_id`` names the registry entry ``restore`` checks
        against. Spilling a block twice fails naturally: the first
        spill freed it, so ``_check_live_locked`` raises."""
        blocks = list(blocks)
        with self._lock:
            for b in blocks:
                self._check_live_locked(b)
                if self._refs[b] != 1:
                    raise BlockPoolError(
                        f"block {b} has refcount {self._refs[b]}: "
                        f"spill-while-pinned refused (an aliased owner "
                        f"would dangle)")
            for b in blocks:
                self._refs[b] = 0
                self._free.append(b)
            run_id = self._next_spill_id
            self._next_spill_id += 1
            self._spilled[run_id] = len(blocks)
            self.frees += len(blocks)
            self.spills += 1
            self._export_gauges_locked()
        return run_id

    def restore(self, run_id: int, n: int) -> Optional[List[int]]:
        """Re-admit a spilled run: ``n`` fresh blocks (the caller
        scatters the host bytes back through the paged admission seam),
        or None when the pool cannot cover them yet — the run stays
        registered and restorable. An unknown / already-restored /
        dropped ``run_id`` is a lifecycle bug and raises loudly."""
        with self._lock:
            if run_id not in self._spilled:
                raise BlockPoolError(
                    f"spill run {run_id} is not registered "
                    f"(already restored, dropped, or never spilled)")
            n = max(int(n), 0)
            if n > len(self._free):
                self.alloc_failures += 1
                obs_metrics.SERVE_KV_ALLOC_FAILURES.inc()
                return None
            out = [self._free.pop() for _ in range(n)]
            for b in out:
                self._refs[b] = 1
            del self._spilled[run_id]
            self.allocs += n
            self.restores += 1
            self._export_gauges_locked()
        return out

    def drop_spilled(self, run_id: int) -> None:
        """Forget a spilled run without restoring it (the victim chose
        / fell back to re-prefill, or expired). Dropping an unknown run
        raises — a double drop means two owners thought they held it."""
        with self._lock:
            if run_id not in self._spilled:
                raise BlockPoolError(
                    f"spill run {run_id} is not registered "
                    f"(double drop, or already restored)")
            del self._spilled[run_id]

    def spilled_runs(self) -> int:
        with self._lock:
            return len(self._spilled)

    def ref(self, block: int) -> int:
        with self._lock:
            return self._refs[block]

    def _check_live_locked(self, b: int) -> None:
        if b == SCRATCH_BLOCK:
            raise BlockPoolError("scratch block is not refcounted")
        if not (0 < b < self.n_blocks):
            raise BlockPoolError(f"block {b} out of range")
        if self._refs[b] <= 0:
            raise BlockPoolError(f"block {b} is free (double free?)")

    # -- observability ----------------------------------------------------

    def _export_gauges_locked(self) -> None:
        obs_metrics.SERVE_KV_BLOCKS_FREE.set(len(self._free))
        obs_metrics.SERVE_KV_BLOCKS_USED.set(self.usable - len(self._free))

    def stats(self) -> Dict[str, Any]:
        """Snapshot for ``GET /memory`` (lock-held)."""
        with self._lock:
            free = len(self._free)
            n_spilled = len(self._spilled)
            return {
                "n_blocks": self.n_blocks,
                "block_size": self.block_size,
                "block_bytes": self.block_bytes,
                "usable_blocks": self.usable,
                "free_blocks": free,
                "used_blocks": self.usable - free,
                "allocs": self.allocs,
                "frees": self.frees,
                "cow_copies": self.cow_copies,
                "alloc_failures": self.alloc_failures,
                "spills": self.spills,
                "restores": self.restores,
                "spilled_runs": n_spilled,
            }


class SpillStore:
    """Pinned host-RAM store for spilled KV runs (ISSUE 16).

    One record per preempted request: the gathered dense KV bytes plus
    whatever host state re-activation needs (length, logits row, spec
    ids). A byte BUDGET (``--spill_capacity_mb``) bounds resident host
    bytes — ``put`` refuses over-budget records (the caller falls back
    to drop-and-re-prefill) and the refusal count is the exhaustion
    signal the 503 admission path keys on. Resident bytes are priced
    into the memory ledger under the ``spill`` component so
    ``GET /memory`` shows the host tier next to the device tiers.

    Thread contract: the owning batcher is externally serialized but
    HTTP handler threads read ``stats()`` — mutations run under
    ``_lock``. Lock order: SpillStore._lock -> MemoryLedger lock ->
    metric locks (the ledger resize happens inside the critical
    section, matching the prefix cache's discipline).
    """

    _GUARDED_BY = {
        "_recs": "_lock",
        "used_bytes": "_lock",
        "puts": "_lock",
        "takes": "_lock",
        "drops": "_lock",
        "rejects": "_lock",
    }

    def __init__(self, capacity_bytes: int, owner: str = "spill"):
        self.capacity_bytes = max(int(capacity_bytes), 0)
        self._mem_key = f"{owner}/spill"
        self._lock = threading.Lock()
        self._recs: Dict[int, Dict[str, Any]] = {}
        self.used_bytes = 0
        self.puts = 0
        self.takes = 0
        self.drops = 0
        self.rejects = 0

    @property
    def enabled(self) -> bool:
        return self.capacity_bytes > 0

    def would_fit(self, nbytes: int) -> bool:
        with self._lock:
            return self.capacity_bytes - self.used_bytes >= int(nbytes)

    def put(self, rid: int, record: Dict[str, Any], nbytes: int) -> bool:
        """Admit one spilled run, or refuse (False) when the budget
        cannot cover it — never evicts: a spilled run is live request
        state, not a cache entry."""
        nbytes = int(nbytes)
        with self._lock:
            if rid in self._recs:
                raise BlockPoolError(
                    f"request {rid} already holds a spill record "
                    f"(double spill?)")
            if nbytes > self.capacity_bytes - self.used_bytes:
                self.rejects += 1
                return False
            record = dict(record)
            record["nbytes"] = nbytes
            self._recs[rid] = record
            self.used_bytes += nbytes
            self.puts += 1
            obs_memory.LEDGER.resize("spill", self._mem_key,
                                     self.used_bytes)
            obs_metrics.SERVE_SPILL_STORE_BYTES.set(self.used_bytes)
            obs_metrics.SERVE_SPILL_BYTES.inc(nbytes)
        return True

    def peek(self, rid: int) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._recs.get(rid)

    def take(self, rid: int) -> Dict[str, Any]:
        """Remove and return a record (restore succeeded / the caller
        owns the bytes now). Unknown rids raise — a restore of a run
        that was never spilled (or already taken) is a lifecycle bug."""
        with self._lock:
            rec = self._recs.pop(rid, None)
            if rec is None:
                raise BlockPoolError(
                    f"request {rid} holds no spill record "
                    f"(double restore, or never spilled)")
            self.used_bytes -= int(rec["nbytes"])
            self.takes += 1
            obs_memory.LEDGER.resize("spill", self._mem_key,
                                     self.used_bytes)
            obs_metrics.SERVE_SPILL_STORE_BYTES.set(self.used_bytes)
        return rec

    def drop(self, rid: int) -> None:
        """Discard a record without restoring (the victim expired or
        fell back to re-prefill). Unknown rids are a no-op — drop runs
        in terminal sweeps that may repeat."""
        with self._lock:
            rec = self._recs.pop(rid, None)
            if rec is None:
                return
            self.used_bytes -= int(rec["nbytes"])
            self.drops += 1
            obs_memory.LEDGER.resize("spill", self._mem_key,
                                     self.used_bytes)
            obs_metrics.SERVE_SPILL_STORE_BYTES.set(self.used_bytes)

    def clear(self) -> None:
        with self._lock:
            self._recs.clear()
            self.used_bytes = 0
            obs_memory.LEDGER.release("spill", self._mem_key)
            obs_metrics.SERVE_SPILL_STORE_BYTES.set(0)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "capacity_bytes": self.capacity_bytes,
                "used_bytes": self.used_bytes,
                "records": len(self._recs),
                "puts": self.puts,
                "takes": self.takes,
                "drops": self.drops,
                "rejects": self.rejects,
            }
