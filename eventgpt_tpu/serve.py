"""Continuous-batching serving loop (iteration-level request scheduling).

The reference serves one request per process (``inference.py`` — load,
generate, print; its ``dataset/constants.py:1-4`` controller/worker
heartbeat constants are vestiges of a LLaVA serving stack that never
shipped). This module is the serving runtime the reference implies but
lacks: a fixed-shape decode batch whose ROWS are a resource — requests
join a running batch as rows free up, instead of waiting for the whole
batch to drain.

TPU-shaped design (everything jit-visible is static-shape):

  * One KV cache of (max_batch, max_len) rows lives in HBM for the life of
    the server; rows are FREE or ACTIVE.
  * Admission: a batch-1 prefill at the prompt's bucketed length, then the
    row's prompt KV/logits are written into the shared cache at the free
    row index (``_admit_row_jit`` — a per-buffer dynamic-update on the
    batch axis). One prefill executable per prompt bucket, reused forever.
  * Decode runs in fixed ``chunk``-token segments (``_decode_segment_jit``:
    the whole-budget ``lax.while_loop`` of ``_decode_loop_jit`` with
    per-row budgets and a frozen mask). Between segments the host harvests
    finished rows and admits queued requests — the segment size is the
    scheduling latency, and at 32 tokens the extra dispatch overhead is
    ~2-3% of decode (r05 chip run: whole-budget vs 64-token budgets).
  * Frozen/free rows keep flowing through the fused step (a ``lax.cond``
    skip would break the donated cache aliasing — same reasoning as
    ``_decode_loop_jit``); their writes land above their frozen lengths —
    kept in bounds by ``submit()``'s slack reservation (prompt + budget +
    slack <= max_len, so a finished row's write slot never reaches the
    buffer edge; XLA *drops*, not clamps, out-of-bounds scatter updates,
    so the slack is the invariant that matters) — are masked out of every
    attention read, and are overwritten when the row is re-admitted.
  * PREFIX-KV CACHE (ISSUE 4): a token-id trie of prompt-head KV blocks
    (``PrefixCache``) replaces the old single ``set_prefix`` slot —
    populated by the operator AND automatically on admission prefill
    (system-prompt / event-block heads), matched longest-prefix at
    admission, refcount-pinned while rows decode from an entry, LRU-
    evicted under an HBM byte budget (``prefix_cache_bytes``). Repeated
    heads across many concurrent sessions admit by a KV copy + suffix
    prefill instead of recompute; an event entry never serves a request
    whose pixels are a different stream.
  * BATCHED ADMISSION PREFILL: all full-prefill admissions ready at one
    dispatch boundary run as ONE padded batched prefill (``_prefill_wave``
    — N dispatches become one per wave), scattered into the shared cache
    in one more dispatch (``_scatter_wave``).
  * STALL-FREE ADMISSION (ISSUE 5): when ``prefill_budget > 0`` and rows
    are actively decoding, admissions no longer pause the batch for an
    exclusive prefill/suffix wave. Each admitting request becomes a
    piggyback LANE: its prompt embeddings (for a prefix-cache hit, the
    entry's KV copy is the lane's starting offset and only the suffix
    embeds load) sit in a resident (K, S_lane, D) buffer, and every
    decode dispatch becomes a MIXED segment — the existing decode/spec
    body plus a batched ``decode_kstep`` advancing each live lane by
    ``chunk_p`` prompt positions against its own lane-cache row, all in
    ONE executable (compiled per (batch, chunk, K, S_lane, chunk_p)
    bucket). In-flight rows therefore commit tokens at every admission
    boundary; the per-boundary prompt-token budget is
    ``K_cap * chunk_p <= prefill_budget``. A finished lane joins the
    shared cache through the same scatter/activation path as every other
    admission (NaN quarantine, insert-on-prefill, Medusa seeding, TTFT
    ramp), so chains stay byte-identical to the exclusive paths. With no
    active decode rows (nothing to stall) the scheduler still picks the
    wave/exclusive prefill — fastest to completion; the policy chooses
    per boundary.
  * PIPELINED scheduling (default): the between-segment control state
    (frozen mask, per-row budgets, gather base) is ALSO device-resident,
    updated in-graph by the segment kernels, so segment N+1 dispatches
    from device state while the host is still harvesting segment N —
    detokenization, history/draft bookkeeping and admission prep overlap
    device compute instead of serializing between dispatches. At most
    one segment is in flight; row mutations (cancel, deadline, the
    suffix, chunked, lane-finish and paged admissions) drain the
    pipeline at the dispatch boundary first. A FULL-PREFILL admission
    drains only to land: its row-independent half (pop and reserve the
    rows, upload, tower, splice, pad, the wave's prefill into a cache of
    its own: ``_stage``) is dispatched while the segment is still in
    flight and stands in the device's queue behind it, and only the
    logits' readback, the scatter into the shared cache and the rows'
    activation (``_land``) wait for the drain. With nothing in flight
    (an idle server, ``pipeline=False``) the same two halves run back to
    back. Chains are byte-identical to the synchronous path
    (``pipeline=False``).

Mesh-sharded serving (``mesh=``): the resident cache / logits / ids_buf
are placed by ``parallel/serving.py``'s layout (batch over ``(data,
fsdp)``, KV heads and vocab over ``model``) and every scheduler jit gets
pinned out-shardings so the donated cache keeps aliasing in place —
the composition of this module with ``parallel/serving.py`` that the
BASELINE north star (13B continuous batching over a pod) requires.

Greedy equivalence: rows are independent in attention (per-row lengths,
positions, masks), so a request decoded in a shared batch commits the same
greedy chain as ``eventchat.generate`` run alone — tested exactly on the
CPU f32 suite (``tests/test_serve.py``); on TPU bf16 the usual
batch-tiling numerics apply.
"""

from __future__ import annotations

import functools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from eventgpt_tpu import faults
from eventgpt_tpu import serve_blocks
from eventgpt_tpu import serve_spec
from eventgpt_tpu.config import EventChatConfig
from eventgpt_tpu.obs import journey as obs_journey
from eventgpt_tpu.obs import memory as obs_memory
from eventgpt_tpu.obs import metrics as obs_metrics
from eventgpt_tpu.obs import series as obs_series
from eventgpt_tpu.obs import trace as obs_trace
from eventgpt_tpu.constants import SEQ_BUCKET
from eventgpt_tpu.models import eventchat, llama as llama_mod
from eventgpt_tpu.ops.sampling import sample
from eventgpt_tpu.workload import SLO, SLO_CLASSES


class QueueFullError(RuntimeError):
    """submit() refused: the admission queue is at ``max_queue``. The HTTP
    layer maps this to 429 + Retry-After (backpressure, not failure)."""


# Terminal request statuses (``ContinuousBatcher.finish_status``). "ok"
# covers both EOS and budget exhaustion; everything else is a forced
# finish whose row was freed without burning the remaining budget.
STATUS_OK = "ok"
STATUS_DEADLINE = "deadline_exceeded"
STATUS_CANCELLED = "cancelled"
STATUS_NAN = "nan_quarantined"
# Both memory tiers exhausted (ISSUE 16): the block pool cannot cover
# the admission even after the preemption scan AND the host spill store
# has no budget left — the request is refused NOW (the HTTP layer maps
# it to 503 + Retry-After) instead of hanging deferred past its
# deadline. Only raised with preemption armed; defer-only servers keep
# the pre-16 behavior.
STATUS_RESOURCE = "resource_exhausted"

# Forced-finish statuses -> the flight-recorder event kind that marks
# them in the request's timeline (obs/journey.py EVENT_KINDS).
_JOURNEY_FORCED_KIND = {
    STATUS_DEADLINE: "deadline",
    STATUS_CANCELLED: "cancel",
    STATUS_NAN: "nan_quarantine",
}


def _pixels_key(pixel_values) -> bytes:
    """Content key of an event-pixel tensor (shape + sha1 of the f32
    bytes) — the event-block prefix guard's identity check (ADVICE r5
    medium: token ids alone cannot distinguish two streams)."""
    import hashlib

    # egpt-check: ignore[hot-sync] -- request pixels are host numpy by the submit() contract; this hashes host bytes, no device value exists here
    arr = np.ascontiguousarray(np.asarray(pixel_values, np.float32))
    return str(arr.shape).encode() + hashlib.sha1(arr.tobytes()).digest()


@dataclass
class _PrefixEntry:
    """One cached prompt-head KV block (ISSUE 4 tentpole). ``ids`` is the
    token path (includes the event sentinel for through-event entries);
    ``pixels_key`` pins an event entry to ITS stream — the wrong-stream
    guard lives in the lookup, not at the call site. ``kv`` holds the
    bucket-length (L, 1, bucket, KV, hd) K/V blocks (quant-aware), never
    donated to any jit, so eviction/replacement can only ever drop the
    last Python reference AFTER every in-flight copy completed."""
    ids: tuple
    pixels_key: Optional[bytes]
    has_event: bool
    kv: Optional[Dict[str, Any]]
    length: int          # real cache positions the entry covers
    bucket: int          # stored block length (serving bucket grain)
    nbytes: int
    pins: int = 0        # rows currently decoding that admitted from this
    tick: int = 0        # LRU clock at last insert/hit
    hits: int = 0
    # Paged layout (ISSUE 12): the entry IS a pinned run of pool blocks
    # (``kv`` is None) — "copy" on a hit is block-table aliasing with a
    # refcount, eviction is a ``BlockPool.decref``, and the dense
    # (L, 1, bucket) view the exclusive suffix/lane paths read is
    # gathered on demand (``ContinuousBatcher._entry_kv``).
    blocks: Optional[List[int]] = None
    # Detached (evicted/replaced) while pinned: a DENSE entry's arrays
    # stay alive through plain object references, but a paged entry's
    # storage is pool blocks — releasing them under a pinned entry
    # would hand a still-needed prefix to the next admission. The
    # release defers to the LAST pin drain (``_drain_entry_pin``).
    detached: bool = False


class PrefixCache:
    """Token-id trie of prompt-head KV blocks with LRU eviction — the
    multi-entry replacement for the single ``set_prefix`` slot (the
    RadixAttention idea at this server's SEQ_BUCKET granularity: entries
    are stored at the prompt bucket grain and keyed on ``(ids,
    pixels_key)``). Populated by ``set_prefix`` (operator insert, the old
    API) AND automatically on admission prefill (the system-prompt and
    event-block heads of every fully-prefilled prompt), so repeated heads
    across many concurrent sessions become cache hits without operator
    action.

    Rules:
      * longest-prefix match wins (``lookup``); an event entry never
        serves a request whose own pixels are a different stream;
      * ``budget`` bytes of HBM (0 = unbounded): inserts evict the
        least-recently-used UNPINNED entries until the new total fits;
      * a pinned entry (``pins`` > 0: some row admitted from it is still
        decoding) is never evicted — the refcount drains at row finish,
        so replacement under pressure cannot yank a hot session's head
        (and the detached-object rule in ``insert`` makes replacing a
        pinned key safe: pins drain on the detached entry, whose KV the
        in-flight rows' own references keep alive).

    Mutations are host-side dict ops under ``_lock`` (the scheduler
    thread inserts/looks up; HTTP handler threads read ``stats()``).
    Device arrays are only ever referenced, never mutated in place.
    ``budget`` is immutable after construction (undeclared below on
    purpose); ``_PrefixEntry.pins`` mutates under the OWNING engine's
    lock (every pin/drain site is scheduler-thread code), which the
    eviction sweep also runs under — the entry objects ride the
    batcher's external serialization, not this lock.
    """

    # Lock-discipline contract (egpt_check rule ``lock``): every
    # read/write of these goes through ``with self._lock`` or a
    # ``*_locked`` helper.
    _GUARDED_BY = {
        "_root": "_lock",
        "bytes": "_lock",
        "n_entries": "_lock",
        "hits": "_lock",
        "misses": "_lock",
        "evictions": "_lock",
        "insertions": "_lock",
        "_tick": "_lock",
    }

    def __init__(self, budget_bytes: int = 0):
        import threading

        self.budget = int(budget_bytes)
        # Paged servers attach their BlockPool here (immutable after
        # construction, like ``budget``): dropping an entry then also
        # decrefs its pinned block run. Lock order: PrefixCache._lock ->
        # BlockPool._lock (leafward, like the ledger/metric locks).
        self.pool = None
        self._root: Dict[str, Any] = {"c": {}, "e": {}}
        self._lock = threading.Lock()
        self.bytes = 0
        self.n_entries = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.insertions = 0
        self._tick = 0
        # Memory-ledger identity (ISSUE 9): this cache's entry bytes are
        # one "prefix_cache" component entry, resized on insert/evict
        # (lock order: PrefixCache._lock -> MemoryLedger._lock, leafward
        # like the metric locks).
        self._mem_key = f"pc{id(self):x}/entries"

    def _iter_nodes_locked(self):
        stack = [self._root]
        while stack:
            node = stack.pop()
            yield node
            stack.extend(node["c"].values())

    def entries(self) -> List[_PrefixEntry]:
        with self._lock:
            return [e for node in self._iter_nodes_locked()
                    for e in node["e"].values()]

    def get(self, ids, pixels_key) -> Optional[_PrefixEntry]:
        """Exact-key entry, or None (the insert-on-prefill dedupe)."""
        with self._lock:
            node = self._root
            for tok in ids:
                node = node["c"].get(tok)
                if node is None:
                    return None
            return node["e"].get(pixels_key)

    def lookup(self, ids, pixels_key) -> Optional[_PrefixEntry]:
        """Longest-prefix match: the deepest entry whose token path is a
        PROPER prefix of ``ids`` and whose stream identity is compatible
        with the request — a text entry needs the event sentinel in the
        remaining suffix, an event entry needs it consumed AND the
        request's own pixels to BE its stream (``pixels_key`` None =
        suffix-only session traffic, which inherits the entry's stream by
        construction). Among entries at one node the most recently used
        matching one wins. Hit/miss counting is the caller's (the
        admission path counts after its fit check)."""
        try:
            from eventgpt_tpu.constants import EVENT_TOKEN_INDEX
            sent = list(ids).index(EVENT_TOKEN_INDEX)
        except ValueError:
            sent = -1
        best = None
        with self._lock:
            node = self._root
            for d, tok in enumerate(ids):
                node = node["c"].get(tok)
                if node is None:
                    break
                if d + 1 >= len(ids):
                    break  # entry must be a PROPER prefix
                cand = None
                for e in node["e"].values():
                    if e.has_event:
                        if sent < 0 or sent > d:
                            continue  # sentinel must be inside the entry
                        if (pixels_key is not None
                                and e.pixels_key != pixels_key):
                            continue  # wrong stream: never serve this KV
                    elif sent <= d:
                        continue  # text entry: sentinel must be in suffix
                    if cand is None or e.tick > cand.tick:
                        cand = e
                if cand is not None:
                    best = cand  # deeper nodes visited later: longest wins
        return best

    def count_hit(self, entry: _PrefixEntry) -> None:
        with self._lock:
            self._tick += 1
            entry.tick = self._tick
            entry.hits += 1
            self.hits += 1
        obs_metrics.SERVE_PREFIX_HITS.inc()

    def count_miss(self) -> None:
        with self._lock:
            self.misses += 1
        obs_metrics.SERVE_PREFIX_MISSES.inc()

    def insert(self, entry: _PrefixEntry) -> bool:
        """Insert (or replace) the entry at its ``(ids, pixels_key)`` key,
        then evict LRU unpinned entries until the budget holds. False =
        refused (the entry alone exceeds the budget)."""
        if self.budget and entry.nbytes > self.budget:
            return False
        with self._lock:
            node = self._root
            for tok in entry.ids:
                node = node["c"].setdefault(tok, {"c": {}, "e": {}})
            old = node["e"].pop(entry.pixels_key, None)
            if old is not None:
                # Replacement detaches the old entry object; any pins on
                # it drain harmlessly there, and its KV stays alive via
                # the in-flight rows' references until they finish. A
                # paged entry's block run drops ITS refcount only — rows
                # aliasing those blocks keep their own refs.
                self.bytes -= old.nbytes
                self.n_entries -= 1
                self._release_blocks_locked(old)
            self._tick += 1
            entry.tick = self._tick
            node["e"][entry.pixels_key] = entry
            self.bytes += entry.nbytes
            self.n_entries += 1
            self.insertions += 1
            self._evict_locked()
            # Gauge export reads bytes/n_entries: stay under the lock
            # (metric locks are leaf locks — the order here is always
            # PrefixCache._lock -> _Metric._lock, never reversed).
            self._export_gauges_locked()
            # Ledger resize rides the same critical section so the
            # component bytes can never disagree with self.bytes
            # (the spy-lock test in tests/test_memory_ledger.py holds
            # the mutation inside it).
            obs_memory.LEDGER.resize("prefix_cache", self._mem_key,
                                     self.bytes)
        obs_metrics.SERVE_PREFIX_INSERTIONS.inc()
        return True

    def _evict_locked(self) -> None:
        if not self.budget:
            return
        while self.bytes > self.budget:
            victim_node, victim_key, victim = None, None, None
            for node in self._iter_nodes_locked():
                for key, e in node["e"].items():
                    if e.pins > 0:
                        continue  # refcount pin: in-flight rows admit from it
                    if victim is None or e.tick < victim.tick:
                        victim_node, victim_key, victim = node, key, e
            if victim is None:
                # Everything left is pinned: stay over budget until the
                # pins drain (the next insert retries the sweep).
                return
            del victim_node["e"][victim_key]
            self.bytes -= victim.nbytes
            self.n_entries -= 1
            self.evictions += 1
            self._release_blocks_locked(victim)
            obs_metrics.SERVE_PREFIX_EVICTIONS.inc()

    def _release_blocks_locked(self, entry: _PrefixEntry) -> None:
        """Drop a detached paged entry's block refs (its share only —
        aliasing rows hold their own). A PINNED entry (selected for an
        in-flight admission, seeding a pending lane, or backing active
        rows) defers the release to its last pin drain — the paged twin
        of the dense detached-object rule."""
        if entry.blocks and self.pool is not None:
            if entry.pins > 0:
                entry.detached = True
                return
            self.pool.decref(entry.blocks)
            entry.blocks = None

    def reclaim_blocks(self, pool, need: int) -> int:
        """Block-pressure eviction (ISSUE 12): evict LRU UNPINNED entries
        until ``pool`` has ``need`` free blocks or nothing evictable is
        left — the paged admission gate's reclaim path, which unifies
        prefix-entry eviction with row allocation (an idle entry's
        pinned run is the only reclaimable pool capacity). Returns the
        number of entries evicted."""
        evicted = 0
        with self._lock:
            while pool.free_blocks() < need:
                victim_node, victim_key, victim = None, None, None
                for node in self._iter_nodes_locked():
                    for key, e in node["e"].items():
                        if e.pins > 0 or not e.blocks:
                            continue
                        if victim is None or e.tick < victim.tick:
                            victim_node, victim_key, victim = node, key, e
                if victim is None:
                    break
                del victim_node["e"][victim_key]
                self.bytes -= victim.nbytes
                self.n_entries -= 1
                self.evictions += 1
                evicted += 1
                self._release_blocks_locked(victim)
                obs_metrics.SERVE_PREFIX_EVICTIONS.inc()
            if evicted:
                self._export_gauges_locked()
                obs_memory.LEDGER.resize("prefix_cache", self._mem_key,
                                         self.bytes)
        return evicted

    def evict_covering(self, blocks) -> int:
        """Evict every UNPINNED entry whose block run intersects
        ``blocks`` — the spill path's targeted sweep (ISSUE 16): an
        insert-on-prefill entry aliases its creator row's run at ref 2,
        and the pool refuses to spill a block another owner could still
        read, so preempting that row first evicts the idle entries
        riding its blocks (dropping them to ref 1). Pinned entries stay
        — a pending lane or selected admission is still reading them,
        and the caller degrades to drop-and-re-prefill. Returns the
        number of entries evicted."""
        want = set(blocks)
        if not want:
            return 0
        evicted = 0
        with self._lock:
            for node in self._iter_nodes_locked():
                for key in [k for k, e in node["e"].items()
                            if e.pins <= 0 and e.blocks
                            and not want.isdisjoint(e.blocks)]:
                    victim = node["e"].pop(key)
                    self.bytes -= victim.nbytes
                    self.n_entries -= 1
                    self.evictions += 1
                    evicted += 1
                    self._release_blocks_locked(victim)
                    obs_metrics.SERVE_PREFIX_EVICTIONS.inc()
            if evicted:
                self._export_gauges_locked()
                obs_memory.LEDGER.resize("prefix_cache", self._mem_key,
                                         self.bytes)
        return evicted

    def _export_gauges_locked(self) -> None:
        obs_metrics.SERVE_PREFIX_BYTES.set(self.bytes)
        obs_metrics.SERVE_PREFIX_ENTRIES.set(self.n_entries)

    def __del__(self):
        # A replaced/dropped cache must not leave stale bytes in the
        # memory ledger (``reset_prefix_cache`` swaps in a fresh one).
        # Best-effort: interpreter teardown may have torn the
        # ledger down first.
        try:
            obs_memory.LEDGER.release("prefix_cache", self._mem_key)
        except Exception:
            pass

    def clear(self) -> None:
        """Drop every entry: paged entries
        release their block runs through the same deferred-on-pins rule
        as eviction, the trie/bytes reset, counters KEEP counting (a
        fresh-counter reset is ``ContinuousBatcher.reset_prefix_cache``,
        which swaps in a new cache)."""
        with self._lock:
            for node in self._iter_nodes_locked():
                for e in node["e"].values():
                    self._release_blocks_locked(e)
            self._root = {"c": {}, "e": {}}
            self.bytes = 0
            self.n_entries = 0
            self._export_gauges_locked()
            obs_memory.LEDGER.resize("prefix_cache", self._mem_key, 0)

    def stats(self) -> Dict[str, Any]:
        """Snapshot for ``GET /prefix_cache`` (lock-held, host-only)."""
        with self._lock:
            entries = [
                {"ids_len": len(e.ids), "has_event": e.has_event,
                 "length": e.length, "bucket": e.bucket,
                 "nbytes": e.nbytes, "pins": e.pins, "hits": e.hits}
                for node in self._iter_nodes_locked() for e in node["e"].values()
            ]
            return {
                "entries": sorted(entries, key=lambda d: -d["hits"]),
                "n_entries": self.n_entries,
                "bytes": self.bytes,
                "budget_bytes": self.budget,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "insertions": self.insertions,
                "hit_ratio": (self.hits / (self.hits + self.misses)
                              if (self.hits + self.misses) else 0.0),
            }


def _decode_segment(
    params,
    cfg: EventChatConfig,
    logits,          # (B, V) per-row next-token logits
    cache,
    key,
    frozen,          # (B,) bool — FREE rows or rows already finished
    n_rem,           # (B,) int32 remaining token budget per row
    chunk: int,
    eos_token_id: int,
    temperature: float = 0.0,
    top_p: float = 1.0,
    nan_gate: bool = True,
):
    """Up to ``chunk`` decode steps over the shared batch.

    Returns (tokens (B, chunk), n_new (B,), done (B,), finite, logits,
    cache, key, frozen_out, n_rem_out): ``tokens[r, :n_new[r]]`` are row
    r's newly committed tokens; ``done[r]`` marks rows that hit EOS inside
    this segment (budget exhaustion is the host's bookkeeping via
    n_rem - n_new == 0). A decoder with sparse experts returns a tenth,
    ``counted``: what its layers counted in each step
    (``cache["moe_stats"]`` stacked over the ``chunk`` steps), leaving the
    device with the other outputs. ``frozen_out``/``n_rem_out`` are the NEXT
    segment's control state computed in-graph — the exact bookkeeping the
    host harvest applies (freeze on EOS / budget exhaustion / non-finite
    logits when ``nan_gate``), kept device-resident so the pipelined
    scheduler can dispatch segment N+1 from them before segment N's
    outputs are ever fetched to the host.
    """
    b = logits.shape[0]
    dec = eventchat.decoder_of(cfg)
    tokens0 = jnp.full((b, chunk), eos_token_id, jnp.int32)
    n_new0 = jnp.zeros((b,), jnp.int32)
    done0 = jnp.zeros((b,), bool)
    counted0 = (jnp.zeros((chunk,) + cache["moe_stats"].shape, jnp.int32)
                if "moe_stats" in cache else None)

    def cond(state):
        t, _, n_new, done = state[:4]
        live = ~(frozen | done) & (n_new < n_rem)
        return (t < chunk) & live.any()

    def body(state):
        t, tokens, n_new, done, logits, cache, key, counted = state
        key, sub = jax.random.split(key)
        nxt = sample(logits, sub, temperature, top_p)
        commit = ~(frozen | done) & (n_new < n_rem)
        nxt = jnp.where(commit, nxt, eos_token_id)
        tokens = tokens.at[:, t].set(jnp.where(commit, nxt, tokens[:, t]))
        n_new = n_new + commit.astype(jnp.int32)
        done = done | (commit & (nxt == eos_token_id))

        # Unconditional advance preserves donated-cache aliasing through the
        # while_loop (see _decode_loop_jit). Frozen rows' slot writes stay
        # in bounds via submit()'s slack reservation and are masked out of
        # every attention read.
        emb = dec.embed_tokens(params["llama"], nxt[:, None])
        new_logits, cache = dec.decode_step(
            params["llama"], cfg.llama, emb, cache, live=commit
        )
        # Frozen rows keep their pre-segment logits AND their length: the
        # row must resume exactly where it stopped when the next segment
        # runs (length would otherwise creep by one per segment step).
        # (State that is not addressed by length stays put inside the
        # step: ``live``.)
        logits = jnp.where(commit[:, None], new_logits, logits)
        cache = {**cache, "length": jnp.where(
            commit, cache["length"], cache["length"] - 1
        )}
        if counted is not None:
            counted = counted.at[t].set(cache["moe_stats"])
        return t + 1, tokens, n_new, done, logits, cache, key, counted

    t, tokens, n_new, done, logits, cache, key, counted = lax.while_loop(
        cond, body, (jnp.int32(0), tokens0, n_new0, done0, logits, cache,
                     key, counted0)
    )
    # Per-row non-finite-logit flag, computed IN-GRAPH (one fused reduce
    # per segment, no extra host dispatch): the scheduler quarantines a
    # non-finite row instead of letting NaN logits poison the engine.
    finite = jnp.isfinite(logits).all(axis=-1)
    # Device-resident scheduler carry: mirror the host harvest's row
    # bookkeeping (budget decrement, freeze on EOS / exhaustion / NaN
    # quarantine) so the next segment can dispatch without a host sync.
    n_rem_out = n_rem - n_new
    frozen_out = frozen | done | (n_rem_out <= 0)
    if nan_gate:
        frozen_out = frozen_out | ~finite
    n_rem_out = jnp.where(frozen_out, 0, n_rem_out)
    out = (tokens, n_new, done, finite, logits, cache, key,
           frozen_out, n_rem_out)
    return out if counted is None else out + (counted,)


_decode_segment_jit = functools.partial(
    jax.jit,
    static_argnames=("cfg", "chunk", "eos_token_id", "temperature", "top_p",
                     "nan_gate"),
    donate_argnames=("cache",),
)(_decode_segment)


def _spec_segment(
    params,
    cfg: EventChatConfig,
    cache,
    key,
    ids_buf,          # (B, S) committed ids; -1 at event/pad positions
    base_pos,         # (B,) next unwritten ids_buf slot at segment start
    frozen,           # (B,) bool
    n_rem,            # (B,) int32 remaining budget per row
    n_iters: int,
    window: int,
    eos_token_id: int,
    temperature: float = 0.0,
    top_p: float = 1.0,
    history=None,     # (H,) server-wide served-text lookup buffer
    medusa=None,      # trained draft heads (models/medusa.py)
    drafts=None,      # (B, >=W-1) per-row carried drafts (Medusa mode);
                      # may be WIDER than this window (the adaptive
                      # server keeps one (B, max_window-1) resident
                      # buffer across buckets — only the first W-1
                      # columns are consumed/updated, the rest pass
                      # through untouched)
    depth=None,       # (B,) int32 per-row draft-depth cap (ISSUE 13);
                      # None = full depth (the fixed-K server)
):
    """``n_iters`` speculative verify iterations over the shared batch —
    the serving form of ``models/eventchat._spec_loop_jit`` (same
    suffix-vote or trained-head drafting, same greedy/rejection-sampled
    verification) with per-row budgets and a frozen mask, stopping for
    admission every segment. In Medusa mode the drafts ride the loop
    carry (each verify emits the next window's drafts from the correction
    position's hidden); a row whose commit was budget-capped drops out of
    ``live`` the same iteration, so stale drafts are never consumed —
    admission reseeds them from the prefill hidden.

    Invariant per active row: ``cache["length"] == base_pos + n_new - 1``
    (every committed token except the newest has its KV cached; the
    admission path seeds it by committing the prefill argmax/sample as the
    first token). Commits are CAPPED at the remaining budget (no
    overshoot — the row may be harvested right after this segment), and a
    row is ``done`` only when its EOS lands within that cap.

    Returns (ids_buf, n_new (B,), done (B,), cache, key, drafts,
    n_iters_run, frozen_out, n_rem_out, base_pos_out, row_acc (B,),
    row_off (B,), pos_acc (W-1,), pos_off (W-1,)) — ``n_iters_run``
    is the executed iteration count, so the server can report REALIZED
    acceptance (committed tokens per verify iteration) on live traffic
    instead of inferring it; ``frozen_out``/``n_rem_out``/``base_pos_out``
    are the next segment's device-resident control state (the same
    bookkeeping the host harvest applies), so the pipelined scheduler can
    dispatch segment N+1 before fetching segment N. The trailing four are
    the adaptive controller's food (ISSUE 13), all UNCAPPED acceptance
    (budget caps are scheduling, not draft quality): per-row accepted /
    offered draft counts over the segment, and the same split per draft
    POSITION — realized per-head yield for Medusa pruning, per-level
    yield for the lookup chain.
    """
    from eventgpt_tpu.models.eventchat import _spec_draft_verify

    b, s_ids = ids_buf.shape
    bidx = jnp.arange(b)
    iarr = jnp.arange(window)[None, :]
    d_w = max(window - 1, 0)
    iarr1 = jnp.arange(d_w)[None, :]
    eos = eos_token_id
    if drafts is None:
        drafts = jnp.zeros((b, d_w), jnp.int32)

    def cond(state):
        it, _, n_new, done = state[:4]
        live = ~(frozen | done) & (n_new < n_rem)
        return (it < n_iters) & live.any()

    def body(state):
        (it, ids_buf, n_new, done, cache, key, drafts,
         row_acc, row_off, pos_acc, pos_off) = state
        active = ~(frozen | done) & (n_new < n_rem)
        pos = base_pos + n_new
        # The adaptive server's resident draft buffer is max_window
        # wide; this bucket consumes/updates only its first W-1 columns
        # (static slice — identity when the widths match).
        drafts_w = drafts[:, :d_w]
        commit, m_count, first_eos, hit, cache, key, drafts_w = (
            _spec_draft_verify(
                params, cfg, ids_buf, pos, cache, key, window,
                temperature, top_p, eos, history=history,
                medusa=medusa, drafts_in=drafts_w, depth=depth,
            )
        )
        drafts = drafts.at[:, :d_w].set(drafts_w)
        # Acceptance accounting (ISSUE 13): accepted = m_count - 1
        # (the correction token is not a draft), offered = the row's
        # effective depth this verify — both UNCAPPED by budget.
        offered = (jnp.minimum(depth, d_w) if depth is not None
                   else jnp.full((b,), d_w, jnp.int32))
        offered = jnp.where(active, offered, 0)
        acc_i = jnp.where(active, m_count - 1, 0)
        row_acc = row_acc + acc_i
        row_off = row_off + offered
        if d_w:
            pos_acc = pos_acc + (
                (iarr1 < acc_i[:, None]) & active[:, None]
            ).astype(jnp.int32).sum(axis=0)
            pos_off = pos_off + (
                (iarr1 < offered[:, None]) & active[:, None]
            ).astype(jnp.int32).sum(axis=0)
        # Unlike the one-shot loop, commits are CAPPED at the remaining
        # budget (the row may be harvested right after this segment) and a
        # row is done only when its EOS lands within the cap.
        cap = jnp.where(active, n_rem - n_new, 0)
        m_eff = jnp.minimum(jnp.where(hit, first_eos + 1, m_count), cap)

        wpos = jnp.clip(pos[:, None] + iarr, 0, s_ids - 1)
        cur = ids_buf[bidx[:, None], wpos]
        ids_buf = ids_buf.at[bidx[:, None], wpos].set(
            jnp.where(iarr < m_eff[:, None], commit, cur)
        )
        n_new = n_new + m_eff
        done = done | (active & hit & (first_eos + 1 <= cap))
        cache = {**cache, "length": cache["length"] + m_eff}
        return (it + 1, ids_buf, n_new, done, cache, key, drafts,
                row_acc, row_off, pos_acc, pos_off)

    (it, ids_buf, n_new, done, cache, key, drafts,
     row_acc, row_off, pos_acc, pos_off) = lax.while_loop(
        cond, body,
        (jnp.int32(0), ids_buf, jnp.zeros((b,), jnp.int32),
         jnp.zeros((b,), bool), cache, key, drafts,
         jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
         jnp.zeros((d_w,), jnp.int32), jnp.zeros((d_w,), jnp.int32)),
    )
    # Device-resident scheduler carry (see _decode_segment): the
    # speculative path's NaN gate is the admission check, so the carry is
    # just EOS/budget bookkeeping plus the advanced gather base.
    n_rem_out = n_rem - n_new
    frozen_out = frozen | done | (n_rem_out <= 0)
    n_rem_out = jnp.where(frozen_out, 0, n_rem_out)
    base_pos_out = base_pos + n_new
    return (ids_buf, n_new, done, cache, key, drafts, it,
            frozen_out, n_rem_out, base_pos_out,
            row_acc, row_off, pos_acc, pos_off)


_spec_segment_jit = functools.partial(
    jax.jit,
    static_argnames=("cfg", "n_iters", "window", "eos_token_id",
                     "temperature", "top_p"),
    donate_argnames=("cache",),
)(_spec_segment)


# The planes of a cache that hold a row's state, rows on axis 1: keys and
# values by position, and what the decoder's module names as a row's fixed
# state (``fixed_state``: a recurrent layer's, a window layer's ring). Any
# other entry of a cache is not a row's.
_BY_POSITION = ("k", "v")


def _expert_counts(counted) -> Dict[str, list]:
    """Span args from what expert layers counted: ``counted`` (steps,
    layers, 6) int32 of ``models/experts.STATS``. A step of a segment
    that did not run, or ran for no live row, counted no token and is left
    out. By step: held experts that received a token, a layer; the tokens
    of the fullest held expert, a layer; assignments that fell on held
    experts, a layer; tokens routed; whether the held assignments passed
    the grouped product's capacity, a layer; the (row tile, group) pairs
    the grouped product visited, a layer."""
    counted = np.asarray(counted)
    ran = counted[counted[:, 0, 3] > 0]
    return {"experts_touched": ran[:, :, 0].tolist(),
            "expert_fullest": ran[:, :, 1].tolist(),
            "held_assignments": ran[:, :, 2].tolist(),
            "routed_tokens": ran[:, 0, 3].tolist(),
            "experts_over_capacity": ran[:, :, 4].tolist(),
            "expert_tiles": ran[:, :, 5].tolist()}


def _admission_readback(logits, prefilled_cache, prefill_span):
    """The NaN quarantine's readback of an admission's logits; what the
    prefill's expert layers counted comes in the same fetch and is set on
    ``prefill_span``. Returns the logits on the host."""
    host_logits, counted = jax.device_get(
        (logits, prefilled_cache.get("moe_stats")))
    if counted is not None and prefill_span is not None:
        prefill_span.set(**_expert_counts(counted[None]))
    return np.asarray(host_logits)


def _admit_row(cache, logits_buf, row, row_cache, row_logits, fixed=()):
    """Insert a batch-1 prefill result at batch row ``row`` of the shared
    cache (dynamic-update on the batch axis; the prompt bucket length of
    ``row_cache`` is a static shape — one compile per bucket). Every plane
    of the row's state goes in (``fixed``: the decoder's ``fixed_state``): a
    slot handed to a new request starts from the new request's state."""

    def ins(buf, rbuf):
        if isinstance(buf, dict):
            return {"q": ins(buf["q"], rbuf["q"]), "s": ins(buf["s"], rbuf["s"])}
        return lax.dynamic_update_slice(
            buf, rbuf.astype(buf.dtype),
            (0, row, 0) + (0,) * (buf.ndim - 3),
        )

    new_cache = {
        **cache,
        **{name: ins(cache[name], row_cache[name])
           for name in _BY_POSITION + tuple(fixed)},
        "length": cache["length"].at[row].set(row_cache["length"][0]),
    }
    return new_cache, logits_buf.at[row].set(row_logits[0])


_admit_row_jit = functools.partial(
    jax.jit, donate_argnames=("cache", "logits_buf"),
    static_argnames=("fixed",)
)(_admit_row)


def _admit_wave(cache, logits_buf, rows, wave_k, wave_v, wave_len,
                wave_logits, wave_fixed=None):
    """Scatter one BATCHED admission prefill into the shared cache: every
    wave member's row lands in ONE dispatch instead of N ``_admit_row``
    calls. ``rows`` (Nb,) carries the destination row per wave slot;
    slots padded to the power-of-two wave size (and NaN-quarantined
    members) carry ``row == max_batch``, which is out of bounds — XLA
    DROPS out-of-bounds scatter updates (the same rule the frozen-row
    slack reservation relies on), so pad slots write nothing.
    ``wave_fixed``: the wave's planes of state that does not grow with the
    position (the decoder's ``fixed_state``), scattered whole; None where the decoder
    has none."""
    s1 = (wave_k["q"] if isinstance(wave_k, dict) else wave_k).shape[2]

    def ins(buf, wbuf):
        if isinstance(buf, dict):
            return {"q": ins(buf["q"], wbuf["q"]),
                    "s": ins(buf["s"], wbuf["s"])}
        return buf.at[:, rows, :s1].set(wbuf.astype(buf.dtype))

    new_cache = {
        **cache,
        "k": ins(cache["k"], wave_k),
        "v": ins(cache["v"], wave_v),
        **{name: cache[name].at[:, rows].set(plane.astype(cache[name].dtype))
           for name, plane in (wave_fixed or {}).items()},
        "length": cache["length"].at[rows].set(
            wave_len.astype(cache["length"].dtype)),
    }
    return new_cache, logits_buf.at[rows].set(wave_logits)


_admit_wave_jit = functools.partial(
    jax.jit, donate_argnames=("cache", "logits_buf")
)(_admit_wave)


def _pool_scatter(buf, dst_blocks, src):
    """Scatter a dense (L, N, S, ...) cache buffer into pool blocks: the
    source's position axis splits into S/block_size whole blocks (S is
    bucket-grained, block_size == SEQ_BUCKET, so it always divides) and
    each lands at ``dst_blocks[i]`` of the (L, n_blocks, block_size, ...)
    arena. Destinations >= n_blocks (the OOB sentinel) are DROPPED by
    XLA's out-of-bounds scatter rule — prefix-ALIASED source blocks
    (their pool content is shared, never rewritten), pad blocks beyond a
    row's reservation, and warmup's dead dispatch all ride it."""
    if isinstance(buf, dict):
        return {"q": _pool_scatter(buf["q"], dst_blocks, src["q"]),
                "s": _pool_scatter(buf["s"], dst_blocks, src["s"])}
    l, bs = buf.shape[0], buf.shape[2]
    n_src = (src.shape[1] * src.shape[2]) // bs
    r = src.reshape((l, n_src, bs) + buf.shape[3:])
    return buf.at[:, dst_blocks.reshape(-1)].set(r.astype(buf.dtype))


def _admit_row_paged(cache, logits_buf, row, dst_blocks, bt_row, row_cache,
                     row_logits):
    """Paged form of ``_admit_row``: scatter the batch-1 prefilled row
    cache into the row's allocated pool blocks and install its block
    table. ``dst_blocks`` (s1/bs,) carries the pool destination per
    source block (OOB = dropped: aliased prefix blocks and beyond-
    reservation pad); ``bt_row`` (nbpr,) is the row's new table (scratch
    0 above the reservation). ``row == max_batch`` drops the bt/length/
    logits update — warmup's dead dispatch."""
    new_cache = {
        "k": _pool_scatter(cache["k"], dst_blocks, row_cache["k"]),
        "v": _pool_scatter(cache["v"], dst_blocks, row_cache["v"]),
        "bt": cache["bt"].at[row].set(bt_row),
        "length": cache["length"].at[row].set(row_cache["length"][0]),
    }
    return new_cache, logits_buf.at[row].set(row_logits[0])


_admit_row_paged_jit = functools.partial(
    jax.jit, donate_argnames=("cache", "logits_buf")
)(_admit_row_paged)


def _admit_wave_paged(cache, logits_buf, rows, dst_blocks, bt_rows, wave_k,
                      wave_v, wave_len, wave_logits):
    """Paged form of ``_admit_wave``: every member's row cache scatters
    into ITS block run in one dispatch. ``dst_blocks`` (Nb, s1/bs) maps
    (member, source block) -> pool block (OOB = dropped: pad members,
    NaN-quarantined members, aliased prefix blocks, beyond-reservation
    pad); ``rows``/``bt_rows`` install tables and lengths with the same
    OOB-drop rule as the dense wave scatter."""
    new_cache = {
        "k": _pool_scatter(cache["k"], dst_blocks, wave_k),
        "v": _pool_scatter(cache["v"], dst_blocks, wave_v),
        "bt": cache["bt"].at[rows].set(bt_rows),
        "length": cache["length"].at[rows].set(
            wave_len.astype(cache["length"].dtype)),
    }
    return new_cache, logits_buf.at[rows].set(wave_logits)


_admit_wave_paged_jit = functools.partial(
    jax.jit, donate_argnames=("cache", "logits_buf")
)(_admit_wave_paged)


def _gather_blocks(k, v, blocks):
    """Dense (L, 1, m*bs, KV, hd) view of ``m`` pool blocks — a paged
    prefix entry's KV for the exclusive suffix / lane-seed paths (the
    same values ``_slice_prefix_block`` would have copied out of a dense
    row; a gather is a copy, so chains stay byte-identical). Inputs are
    never donated: the pool is the resident cache."""

    def g(buf):
        if isinstance(buf, dict):
            return {"q": g(buf["q"]), "s": g(buf["s"])}
        x = buf[:, blocks]  # (L, m, bs, KV, hd)
        return x.reshape((x.shape[0], 1, x.shape[1] * x.shape[2])
                         + x.shape[3:])

    return g(k), g(v)


_gather_blocks_jit = functools.partial(
    jax.jit, donate_argnames=()
)(_gather_blocks)


def _pool_write(cache, dst_blocks, src_k, src_v):
    """Write dense (L, 1, S) K/V buffers into entry-owned pool blocks —
    the operator ``set_prefix`` insert (admissions ride the richer
    ``_admit_row_paged``)."""
    return {**cache,
            "k": _pool_scatter(cache["k"], dst_blocks, src_k),
            "v": _pool_scatter(cache["v"], dst_blocks, src_v)}


_pool_write_jit = functools.partial(
    jax.jit, donate_argnames=("cache",)
)(_pool_write)


def _slice_prefix_block(k, v, row, bucket: int):
    """Copy cache positions [0, bucket) of batch row ``row`` out of a
    prefilled row/wave cache — the insert-on-prefill entry copy (one
    small device-to-device slice per NEW head; repeat heads dedupe before
    ever reaching here). The inputs are not donated: the source cache is
    still owed to the row admission scatter."""

    def sl(buf):
        if isinstance(buf, dict):
            return {"q": sl(buf["q"]), "s": sl(buf["s"])}
        sizes = (buf.shape[0], 1, bucket) + buf.shape[3:]
        start = (jnp.int32(0), row, jnp.int32(0)) + (jnp.int32(0),) * (buf.ndim - 3)
        return lax.dynamic_slice(buf, start, sizes)

    return sl(k), sl(v)


_slice_prefix_jit = functools.partial(
    jax.jit, static_argnames=("bucket",)
)(_slice_prefix_block)


def _chunk_prefill(params, cfg: EventChatConfig, embeds, cache,
                   start, new_len, last_idx, chunk: int):
    """One chunked-admission advance: feed prompt positions
    [start, start+chunk) of ``embeds`` (1, S1, D) through the speculative
    verification kernel (``decode_kstep`` — identical attention semantics
    to one-shot prefill: query i at cache position length+i attends to
    slots [0, length+i]), then pin the cache length to ``new_len`` (the
    real prompt prefix filled so far — trailing chunk positions past the
    prompt are pad, masked from every future read).

    ``start`` must satisfy start+chunk <= S1 (the batcher validates that
    ``chunk`` divides the bucket grain, so dynamic_slice never clamps —
    a clamped slice would desynchronize embed positions from the cache
    write slots). Returns (last_logits (1, V) f32 and last_hidden (1, D)
    at window index ``last_idx`` — the prompt's final real token on the
    finishing chunk, unused otherwise — and the advanced cache).
    """
    emb = lax.dynamic_slice(
        embeds, (0, start, 0), (1, chunk, embeds.shape[-1])
    )
    logits, hidden, cache = llama_mod.decode_kstep(
        params["llama"], cfg.llama, emb, cache, return_hidden=True
    )
    last = jnp.take_along_axis(
        logits, jnp.reshape(last_idx, (1, 1, 1)), axis=1
    )[:, 0]
    # Final-norm hidden at the same position: seeds the Medusa drafts at
    # admission (XLA DCEs it when the caller drops it).
    last_hidden = jnp.take_along_axis(
        hidden, jnp.reshape(last_idx, (1, 1, 1)), axis=1
    )[:, 0]
    return last, last_hidden, {**cache, "length": new_len}


_chunk_prefill_jit = functools.partial(
    jax.jit, static_argnames=("cfg", "chunk"), donate_argnames=("cache",)
)(_chunk_prefill)


def _lane_advance(params, cfg: EventChatConfig, lane_embeds, lane_cache,
                  start, new_len, last_idx, chunk_p: int):
    """One piggybacked chunked-prefill advance over the K resident lanes
    (ISSUE 5): each lane row gathers its own ``chunk_p``-wide window of
    prompt embeddings at ``start`` and runs it through ``decode_kstep``
    against its own lane-cache row — the batched form of
    ``_chunk_prefill``, with the same pad rule (trailing positions past
    the prompt write garbage above ``new_len``, masked from every future
    read). ``start`` is authoritative for the write base (the carried
    lane-cache length is overwritten), so idle/ready lane slots passed
    with ``start == new_len`` advance nothing real — their garbage writes
    land above their pinned length. Gather indices clip at the buffer
    edge, which only ever touches pad positions (the batcher sizes the
    lane bucket to hold every member's prompt).

    Returns (last_logits (K, V), last_hidden (K, D), lane_cache) — the
    last-real-token row of each lane's window, meaningful only on a
    lane's finishing chunk (the batcher slices it there).
    """
    k, s, _ = lane_embeds.shape
    idx = jnp.clip(
        start[:, None] + jnp.arange(chunk_p)[None, :], 0, s - 1
    )
    emb = jnp.take_along_axis(lane_embeds, idx[:, :, None], axis=1)
    lane_cache = {**lane_cache, "length": start}
    logits, hidden, lane_cache = llama_mod.decode_kstep(
        params["llama"], cfg.llama, emb, lane_cache, return_hidden=True
    )
    last = jnp.take_along_axis(
        logits, jnp.reshape(last_idx, (-1, 1, 1)), axis=1
    )[:, 0]
    last_hidden = jnp.take_along_axis(
        hidden, jnp.reshape(last_idx, (-1, 1, 1)), axis=1
    )[:, 0]
    return last, last_hidden, {**lane_cache, "length": new_len}


def _mixed_decode_segment(
    params, cfg: EventChatConfig, logits, cache, key, frozen, n_rem,
    lane_embeds, lane_cache, lane_start, lane_new_len, lane_last_idx,
    chunk: int, chunk_p: int, eos_token_id: int,
    temperature: float = 0.0, top_p: float = 1.0, nan_gate: bool = True,
):
    """The mixed-segment executable (ISSUE 5 tentpole, plain-decode
    form): the unchanged ``_decode_segment`` body PLUS the piggybacked
    prefill lanes, in one dispatch. The two halves touch disjoint state
    (shared cache rows vs lane-cache rows; rows are independent in
    attention), so XLA is free to interleave them and the decode rows'
    tokens commit in the same dispatch that advances the admissions —
    the stall class the exclusive prefill wave had is gone by
    construction. Returns the decode outputs followed by the lane
    outputs of ``_lane_advance``."""
    dec = _decode_segment(
        params, cfg, logits, cache, key, frozen, n_rem, chunk,
        eos_token_id, temperature, top_p, nan_gate,
    )
    lane = _lane_advance(
        params, cfg, lane_embeds, lane_cache, lane_start, lane_new_len,
        lane_last_idx, chunk_p,
    )
    return dec + lane


_mixed_decode_segment_jit = functools.partial(
    jax.jit,
    static_argnames=("cfg", "chunk", "chunk_p", "eos_token_id",
                     "temperature", "top_p", "nan_gate"),
    donate_argnames=("cache", "lane_cache"),
)(_mixed_decode_segment)


def _mixed_spec_segment(
    params, cfg: EventChatConfig, cache, key, ids_buf, base_pos, frozen,
    n_rem, lane_embeds, lane_cache, lane_start, lane_new_len,
    lane_last_idx, n_iters: int, window: int, chunk_p: int,
    eos_token_id: int, temperature: float = 0.0, top_p: float = 1.0,
    history=None, medusa=None, drafts=None, depth=None,
):
    """Mixed segment, speculative form: ``_spec_segment`` + the
    piggybacked prefill lanes in one dispatch (see
    ``_mixed_decode_segment``)."""
    spec = _spec_segment(
        params, cfg, cache, key, ids_buf, base_pos, frozen, n_rem,
        n_iters, window, eos_token_id, temperature, top_p,
        history=history, medusa=medusa, drafts=drafts, depth=depth,
    )
    lane = _lane_advance(
        params, cfg, lane_embeds, lane_cache, lane_start, lane_new_len,
        lane_last_idx, chunk_p,
    )
    return spec + lane


_mixed_spec_segment_jit = functools.partial(
    jax.jit,
    static_argnames=("cfg", "n_iters", "window", "chunk_p",
                     "eos_token_id", "temperature", "top_p"),
    donate_argnames=("cache", "lane_cache"),
)(_mixed_spec_segment)


def _lane_seed(lane_cache, slot, pk, pv):
    """Copy a prefix-cache entry's KV block into lane row ``slot`` at
    position 0 — the 'suffix copies become the piggybacked lane's
    starting offset' rule (ISSUE 5): the lane then advances only the
    suffix, reading the seeded prefix through ``decode_kstep``'s
    attention window exactly as ``_prefix_prefill`` would. The lane
    cache is ALWAYS unquantized (see ``_lane_extract``), so an int8
    entry block dequantizes here — the same values the exclusive suffix
    path's attention reads."""

    def ins(buf, src):
        if isinstance(src, dict):  # int8 entry into the unquant lane
            src = llama_mod._kv_dequant(src, buf.dtype)
        return lax.dynamic_update_slice(
            buf, src.astype(buf.dtype),
            (0, slot, 0) + (0,) * (buf.ndim - 3),
        )

    return {"k": ins(lane_cache["k"], pk), "v": ins(lane_cache["v"], pv),
            "length": lane_cache["length"]}


_lane_seed_jit = functools.partial(
    jax.jit, donate_argnames=("lane_cache",)
)(_lane_seed)


def _lane_extract(lane_k, lane_v, slot, pk, pv, bucket: int, quant: bool,
                  plen: int = 0):
    """Slice lane row ``slot`` into a (1, bucket) admission row cache.

    The lane prefills UNQUANTIZED even on an int8-KV server: one-shot
    ``prefill`` attends over full-precision K/V and quantizes only at
    the cache write, so a lane that quantized per chunk (as
    ``decode_kstep`` does on a quant cache) would read back dequantized
    values mid-prompt and drift off the one-shot chain. Instead the
    quantization happens ONCE, here, from the same full-precision values
    prefill's write sees — byte-identical resident rows. A seeded prefix
    entry's ORIGINAL (q, s) block overlays its region afterwards, so the
    prefix lands exactly as the exclusive suffix path copies it (a
    requantize of the dequantized seed could wobble the scales). Only
    the entry's REAL region [0, plen) overlays — its stored block is
    bucket-length with pad above ``plen``, which must not clobber the
    lane's freshly-prefilled suffix positions."""
    k, v = _slice_prefix_block(lane_k, lane_v, slot, bucket)
    if quant:
        k, v = llama_mod._kv_quantize(k), llama_mod._kv_quantize(v)

        def overlay(buf, src):
            if isinstance(buf, dict):
                return {"q": overlay(buf["q"], src["q"]),
                        "s": overlay(buf["s"], src["s"])}
            src = src[:, :, :plen]
            return lax.dynamic_update_slice(
                buf, src.astype(buf.dtype), (0,) * buf.ndim
            )

        if pk is not None:
            k, v = overlay(k, pk), overlay(v, pv)
    return k, v


_lane_extract_jit = functools.partial(
    jax.jit, static_argnames=("bucket", "quant", "plen")
)(_lane_extract)


def _prefix_prefill(params, cfg: EventChatConfig, pk, pv, plen,
                    cache, suffix_embeds, new_len, last_idx):
    """Admission with a shared-prefix KV seed (VERDICT r4 #7): copy the
    prefix's cached K/V block into the fresh row cache, pin the length to
    the prefix length, and run ONLY the suffix through ``decode_kstep`` —
    identical attention semantics to prefilling the whole prompt (suffix
    query i at position plen+i attends to [0, plen+i], reading the shared
    prefix K/V), at the cost of the suffix instead of the prompt. The
    reference recomputes the full prompt per request
    (``/root/reference/inference.py:52-63``); this is the beyond-parity
    axis for shared-prompt-head traffic.

    BATCHED since ISSUE 4: the same body serves the suffix-admission
    WAVE — ``pk``/``pv`` carry N stacked entry blocks (mixed entries are
    fine: each row copies ITS block; rows are independent in attention),
    ``plen``/``new_len``/``last_idx`` are per-row. The batch-1 call sites
    pass N = 1 and a scalar ``last_idx`` unchanged.

    Trailing suffix-pad positions write garbage K/V above ``new_len`` —
    masked from every future read, same as ``_chunk_prefill``'s pad rule.
    Returns (last_logits (N, V), last_hidden (N, D), advanced cache).
    """

    def copy(buf, src):
        if isinstance(buf, dict):  # quantized plane: payload + scales
            return {"q": copy(buf["q"], src["q"]),
                    "s": copy(buf["s"], src["s"])}
        return lax.dynamic_update_slice(
            buf, src.astype(buf.dtype), (0,) * buf.ndim
        )

    cache = {
        "k": copy(cache["k"], pk),
        "v": copy(cache["v"], pv),
        "length": plen,
    }
    logits, hidden, cache = llama_mod.decode_kstep(
        params["llama"], cfg.llama, suffix_embeds, cache, return_hidden=True
    )
    last = jnp.take_along_axis(
        logits, jnp.reshape(last_idx, (-1, 1, 1)), axis=1
    )[:, 0]
    last_hidden = jnp.take_along_axis(
        hidden, jnp.reshape(last_idx, (-1, 1, 1)), axis=1
    )[:, 0]
    return last, last_hidden, {**cache, "length": new_len}


_prefix_prefill_jit = functools.partial(
    jax.jit, static_argnames=("cfg",), donate_argnames=("cache",)
)(_prefix_prefill)


@functools.partial(jax.jit, static_argnames=("width",))
def _gather_new_jit(ids_buf, base_pos, width: int):
    """Per-row window ``ids_buf[r, base_pos[r] : base_pos[r] + width]`` —
    the speculative harvest reads back only the slots a segment could have
    written (width >= n_iters * window) instead of the whole (B, max_len)
    buffer, so host-transfer cost scales with tokens produced, not cache
    size."""
    b, s = ids_buf.shape
    idx = jnp.clip(
        base_pos[:, None] + jnp.arange(width)[None, :], 0, s - 1
    )
    return ids_buf[jnp.arange(b)[:, None], idx]


# -- mesh-sharded scheduler jits ------------------------------------------
#
# Same bodies as the single-chip jits above, with OUTPUT SHARDINGS PINNED
# to the resident buffers' placement. Without the pin, GSPMD may lay the
# returned cache out differently from the donated input cache, silently
# breaking buffer aliasing — a second full-size cache allocation per
# segment (the _get_sharded_prefill reasoning, models/eventchat.py).
# Keyed per (config, statics, shardings): one compile per serving setup.


@functools.lru_cache(maxsize=16)
def _get_sharded_decode_segment(
    cfg, chunk, eos_token_id, temperature, top_p, nan_gate,
    flat_cache_sh, cache_treedef, logits_sh, toks_sh, b_sh, key_sh,
):
    cache_sh = jax.tree_util.tree_unflatten(cache_treedef, list(flat_cache_sh))
    return jax.jit(
        lambda params, logits, cache, key, frozen, n_rem: _decode_segment(
            params, cfg, logits, cache, key, frozen, n_rem,
            chunk, eos_token_id, temperature, top_p, nan_gate,
        ),
        donate_argnums=(2,),
        # The trailing (b_sh, b_sh) pins the device-resident carry
        # (frozen_out, n_rem_out) to the batch placement so the pipelined
        # re-dispatch feeds it straight back without a reshard.
        out_shardings=(toks_sh, b_sh, b_sh, b_sh, logits_sh, cache_sh,
                       key_sh, b_sh, b_sh),
    )


@functools.lru_cache(maxsize=16)
def _get_sharded_spec_segment(
    cfg, n_iters, window, eos_token_id, temperature, top_p,
    flat_cache_sh, cache_treedef, ids_sh, b_sh, key_sh, drafts_sh,
):
    cache_sh = jax.tree_util.tree_unflatten(cache_treedef, list(flat_cache_sh))
    scalar_sh = jax.sharding.NamedSharding(
        key_sh.mesh, jax.sharding.PartitionSpec()
    )
    return jax.jit(
        lambda params, cache, key, ids_buf, base_pos, frozen, n_rem, history,
        medusa, drafts, depth=None:
        _spec_segment(
            params, cfg, cache, key, ids_buf, base_pos, frozen, n_rem,
            n_iters, window, eos_token_id, temperature, top_p,
            history=history, medusa=medusa, drafts=drafts, depth=depth,
        ),
        donate_argnums=(1,),
        # (b_sh, b_sh, b_sh) after it: the pipelined carry pins
        # (frozen_out, n_rem_out, base_pos_out) — see the decode
        # variant. Trailing: acceptance accounting (row_* batch-placed,
        # pos_* replicated — ISSUE 13).
        out_shardings=(ids_sh, b_sh, b_sh, cache_sh, key_sh, drafts_sh,
                       scalar_sh, b_sh, b_sh, b_sh,
                       b_sh, b_sh, scalar_sh, scalar_sh),
    )


@functools.lru_cache(maxsize=16)
def _get_sharded_admit(flat_cache_sh, cache_treedef, logits_sh):
    cache_sh = jax.tree_util.tree_unflatten(cache_treedef, list(flat_cache_sh))
    return jax.jit(
        _admit_row,
        donate_argnums=(0, 1),
        out_shardings=(cache_sh, logits_sh),
    )


@functools.lru_cache(maxsize=16)
def _get_sharded_chunk_prefill(cfg, chunk, flat_row_sh, row_treedef, last_sh,
                               hidden_sh):
    row_sh = jax.tree_util.tree_unflatten(row_treedef, list(flat_row_sh))
    return jax.jit(
        lambda params, embeds, cache, start, new_len, last_idx:
        _chunk_prefill(
            params, cfg, embeds, cache, start, new_len, last_idx, chunk
        ),
        donate_argnums=(2,),
        out_shardings=(last_sh, hidden_sh, row_sh),
    )


@functools.lru_cache(maxsize=16)
def _get_sharded_prefix_prefill(cfg, flat_row_sh, row_treedef, last_sh,
                                hidden_sh):
    row_sh = jax.tree_util.tree_unflatten(row_treedef, list(flat_row_sh))
    return jax.jit(
        lambda params, pk, pv, plen, cache, suffix_embeds, new_len, last_idx:
        _prefix_prefill(
            params, cfg, pk, pv, plen, cache, suffix_embeds, new_len,
            last_idx,
        ),
        donate_argnums=(4,),
        out_shardings=(last_sh, hidden_sh, row_sh),
    )


@functools.lru_cache(maxsize=16)
def _get_sharded_admit_wave(flat_cache_sh, cache_treedef, logits_sh):
    """Batched-admission scatter with the shared cache/logits placement
    pinned (same aliasing reasoning as ``_get_sharded_admit``: an
    unpinned output would silently break the donated-cache aliasing)."""
    cache_sh = jax.tree_util.tree_unflatten(cache_treedef, list(flat_cache_sh))
    return jax.jit(
        _admit_wave,
        donate_argnums=(0, 1),
        out_shardings=(cache_sh, logits_sh),
    )


@functools.lru_cache(maxsize=16)
def _get_sharded_admit_paged(flat_cache_sh, cache_treedef, logits_sh):
    """Paged row admission under a mesh, with the pool/table placement
    pinned (the donated-cache aliasing rule, same as the dense admit)."""
    cache_sh = jax.tree_util.tree_unflatten(cache_treedef, list(flat_cache_sh))
    return jax.jit(
        _admit_row_paged,
        donate_argnums=(0, 1),
        out_shardings=(cache_sh, logits_sh),
    )


@functools.lru_cache(maxsize=16)
def _get_sharded_admit_wave_paged(flat_cache_sh, cache_treedef, logits_sh):
    cache_sh = jax.tree_util.tree_unflatten(cache_treedef, list(flat_cache_sh))
    return jax.jit(
        _admit_wave_paged,
        donate_argnums=(0, 1),
        out_shardings=(cache_sh, logits_sh),
    )


@functools.lru_cache(maxsize=16)
def _get_sharded_pool_write(flat_cache_sh, cache_treedef):
    cache_sh = jax.tree_util.tree_unflatten(cache_treedef, list(flat_cache_sh))
    return jax.jit(
        _pool_write, donate_argnums=(0,), out_shardings=cache_sh,
    )


@functools.lru_cache(maxsize=32)
def _get_sharded_gather_blocks(block_sh, quant):
    """Paged entry-KV gather under a mesh: output block pinned to the
    prefix-entry placement (``parallel/serving.prefix_block_sharding``),
    same as the dense ``_get_sharded_slice_prefix``."""
    out_sh = ({"q": block_sh, "s": block_sh} if quant else block_sh)
    return jax.jit(_gather_blocks, out_shardings=(out_sh, out_sh))


@functools.lru_cache(maxsize=32)
def _get_sharded_slice_prefix(bucket, block_sh, quant):
    """Entry copy (insert-on-prefill) under a mesh, with the output block
    pinned to the prefix-entry placement (``parallel/serving.
    prefix_block_sharding``: KV heads over ``model``, everything else
    replicated — batch is 1, so the batch axes drop out)."""
    out_sh = ({"q": block_sh, "s": block_sh} if quant else block_sh)
    return jax.jit(
        lambda k, v, row: _slice_prefix_block(k, v, row, bucket),
        out_shardings=(out_sh, out_sh),
    )


@functools.lru_cache(maxsize=16)
def _get_sharded_mixed_decode_segment(
    cfg, chunk, chunk_p, eos_token_id, temperature, top_p, nan_gate,
    flat_cache_sh, cache_treedef, logits_sh, toks_sh, b_sh, key_sh,
    flat_lane_sh, lane_treedef, lane_emb_sh, lane_last_sh, lane_hidden_sh,
):
    """Mixed decode segment under the serving mesh: the decode half pins
    the same carry/cache shardings as ``_get_sharded_decode_segment``;
    the lane half pins the lane cache to its resident placement
    (``parallel/serving.shard_kv_cache`` at batch K) so the donated lane
    buffers keep aliasing across boundaries."""
    cache_sh = jax.tree_util.tree_unflatten(cache_treedef, list(flat_cache_sh))
    lane_sh = jax.tree_util.tree_unflatten(lane_treedef, list(flat_lane_sh))
    return jax.jit(
        lambda params, logits, cache, key, frozen, n_rem, lane_embeds,
        lane_cache, lane_start, lane_new_len, lane_last_idx:
        _mixed_decode_segment(
            params, cfg, logits, cache, key, frozen, n_rem, lane_embeds,
            lane_cache, lane_start, lane_new_len, lane_last_idx,
            chunk, chunk_p, eos_token_id, temperature, top_p, nan_gate,
        ),
        donate_argnums=(2, 7),
        out_shardings=(toks_sh, b_sh, b_sh, b_sh, logits_sh, cache_sh,
                       key_sh, b_sh, b_sh,
                       lane_last_sh, lane_hidden_sh, lane_sh),
    )


@functools.lru_cache(maxsize=16)
def _get_sharded_mixed_spec_segment(
    cfg, n_iters, window, chunk_p, eos_token_id, temperature, top_p,
    flat_cache_sh, cache_treedef, ids_sh, b_sh, key_sh, drafts_sh,
    flat_lane_sh, lane_treedef, lane_emb_sh, lane_last_sh, lane_hidden_sh,
):
    cache_sh = jax.tree_util.tree_unflatten(cache_treedef, list(flat_cache_sh))
    lane_sh = jax.tree_util.tree_unflatten(lane_treedef, list(flat_lane_sh))
    scalar_sh = jax.sharding.NamedSharding(
        key_sh.mesh, jax.sharding.PartitionSpec()
    )
    return jax.jit(
        lambda params, cache, key, ids_buf, base_pos, frozen, n_rem,
        history, medusa, drafts, lane_embeds, lane_cache, lane_start,
        lane_new_len, lane_last_idx, depth=None:
        _mixed_spec_segment(
            params, cfg, cache, key, ids_buf, base_pos, frozen, n_rem,
            lane_embeds, lane_cache, lane_start, lane_new_len,
            lane_last_idx, n_iters, window, chunk_p, eos_token_id,
            temperature, top_p, history=history, medusa=medusa,
            drafts=drafts, depth=depth,
        ),
        donate_argnums=(1, 11),
        out_shardings=(ids_sh, b_sh, b_sh, cache_sh, key_sh, drafts_sh,
                       scalar_sh, b_sh, b_sh, b_sh,
                       b_sh, b_sh, scalar_sh, scalar_sh,
                       lane_last_sh, lane_hidden_sh, lane_sh),
    )


@functools.lru_cache(maxsize=32)
def _get_sharded_lane_extract(bucket, quant, block_sh, plen):
    """Lane-row extraction under a mesh, with the admission row-cache
    block pinned to the prefix-entry placement (same reasoning as
    ``_get_sharded_slice_prefix``)."""
    out_sh = ({"q": block_sh, "s": block_sh} if quant else block_sh)
    return jax.jit(
        lambda k, v, slot, pk, pv: _lane_extract(
            k, v, slot, pk, pv, bucket, quant, plen),
        out_shardings=(out_sh, out_sh),
    )


@functools.lru_cache(maxsize=16)
def _get_sharded_lane_seed(flat_lane_sh, lane_treedef):
    """Entry-KV seed of one lane row with the lane cache's placement
    pinned (the donated-buffer aliasing rule, same as every other
    resident-state jit here)."""
    lane_sh = jax.tree_util.tree_unflatten(lane_treedef, list(flat_lane_sh))
    return jax.jit(
        _lane_seed, donate_argnums=(0,), out_shardings=lane_sh,
    )


@dataclass
class _PendingLane:
    """One piggybacked admission (ISSUE 5): the row is reserved (frozen),
    the prompt embeddings sit in lane-embeds slot ``slot``, and every
    mixed segment advances the lane ``chunk_p`` prompt positions against
    its lane-cache row until ``filled >= prompt_len`` — then the lane's
    row cache is sliced out and joins the shared cache through the
    normal admission tail (``_finish_admission``). For a prefix-cache
    hit, the entry's KV was seeded at [0, filled0) and only the suffix
    embeds were loaded."""
    req: "_Request"
    row: int
    slot: int
    prompt_len: int
    filled: int = 0
    entry: Optional["_PrefixEntry"] = None
    last_logits: Any = None   # (1, V) future, valid after the final chunk
    last_hidden: Any = None   # (1, D) future, Medusa seeding


@dataclass
class _PendingAdmission:
    """A chunked admission in flight: the row is reserved (frozen), the
    prompt prefix [0, filled) is prefilled into ``row_cache``, and one
    chunk advances per scheduler step so active rows keep decoding."""
    req: "_Request"
    row: int
    embeds: Any          # (1, S1, D) padded prompt embeddings
    prompt_len: int
    row_cache: Any
    filled: int = 0
    last_logits: Any = None


@dataclass
class _Prefilled:
    """A full-prefill admission between its two halves. The members' rows
    are reserved (frozen) and their tower, splice and prefill are dispatched
    into a fresh cache of the wave's own; nothing of the shared cache, the
    carry or the host mirror has been touched. ``_land`` is the other half:
    readback, scatter, activation, against settled state."""
    members: List[tuple]  # (req, row), in the order of the wave's slots
    cache: Any            # the wave's (or the row's) own prefilled cache
    logits: Any           # (nb, V) future
    hidden: Any           # (nb, D) future for Medusa seeding, else None
    prompt_lens: List[int]
    span: Any             # admit.prefill: gets the expert counters at readback


@dataclass
class _Request:
    rid: int
    input_ids: Sequence[int]
    pixel_values: Any
    max_new_tokens: int
    tokens: List[int] = field(default_factory=list)
    row: int = -1
    # Cache positions the prompt will occupy (text + event tokens) —
    # computed once at submit; the memory headroom guard predicts the
    # next admission wave's bytes from it without re-walking input_ids.
    prompt_len: int = 0
    # Service timestamps (time.perf_counter at submit / first committed
    # token / completion) — the continuous-batching latency story: TTFT
    # and completion latency per request (``request_stats``, SLO scoring).
    t_submit: float = 0.0
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    # Imported handoffs rebase t_submit into the past by the prefill
    # leg's shipped duration so stats/SLO score the whole life; the
    # journey leg must stay LOCAL (the coordinator stitches legs from
    # durations) — this holds the local begin stamp for it.
    t_journey: Optional[float] = None
    # Last harvest that committed tokens for this row (inter-token-latency
    # telemetry: gaps between consecutive harvests, weighted by tokens).
    t_last: Optional[float] = None
    # Telemetry phase of the request's async trace span: "queued" until it
    # leaves the admission queue, then "active"; _record_finish closes
    # whichever is open (obs/trace.py request-lifecycle events).
    phase: str = "queued"
    # Absolute perf_counter deadline (None = no deadline). Enforced both
    # while queued and between decode segments: an expired row is frozen
    # and finished with STATUS_DEADLINE instead of burning its budget.
    deadline: Optional[float] = None
    # Prefix-cache entry this row admitted from (refcount pin: the entry
    # cannot be LRU-evicted until the row finishes; _record_finish drains
    # it). None for full-prefill admissions.
    prefix_entry: Optional["_PrefixEntry"] = None
    # Service-level objective (ISSUE 6): the class + targets this
    # request is scored against at finish (workload.SLO; None = unscored
    # — the pre-SLO behavior). Scoring reads clocks and host state only,
    # so chains are byte-identical with or without an SLO attached.
    slo: Optional[SLO] = None
    # Paged KV reservation (ISSUE 12): pool blocks this request holds —
    # ``owned`` at refcount 1 (its private writable run), ``aliased``
    # shared with a prefix entry (incref'd full blocks below the
    # divergence point). Both decref on EVERY terminal/export path
    # (``_paged_release``); ``kv_bt_written`` marks that the row's
    # device block table points at them and must be reset to scratch.
    kv_blocks_owned: List[int] = field(default_factory=list)
    kv_blocks_aliased: List[int] = field(default_factory=list)
    kv_bt_written: bool = False
    # Block-tier preemption (ISSUE 16): while a preempted request waits
    # re-queued, ``spill_run`` names its BlockPool spill registry entry
    # (None = the drop-and-re-prefill path, or never preempted) and the
    # SpillStore holds its gathered KV under ``rid``. ``preempts``
    # counts evictions (observability: ``request_stats``).
    spill_run: Optional[int] = None
    preempts: int = 0
    # Prefill/decode disaggregation (ISSUE 17): on a decode-role worker,
    # the gathered block-run record this request arrived with (the
    # spill-record shape, shipped over RPC). ``_admit`` splices it into
    # the local arena instead of re-prefilling; cleared once spliced.
    handoff_rec: Optional[Dict[str, Any]] = None


class ContinuousBatcher:
    """Row-level continuous batching over one resident KV cache.

    >>> srv = ContinuousBatcher(params, cfg, max_batch=4, max_len=1024)
    >>> rid = srv.submit(input_ids, pixel_values, max_new_tokens=64)
    >>> answers = srv.run_until_drained()   # {rid: [token ids]}

    Greedy by default (temperature 0); sampling configs apply serverwide.

    ``mesh``: a serving ``Mesh`` (data/fsdp/model, context=1). ``params``
    must already be placed by ``parallel.serving.shard_params_for_serving``;
    the batcher places its resident cache / logits / ids_buf to match and
    pins every scheduler jit's out-shardings (BASELINE config 5: 13B
    continuous batching needs the serving mesh AND row-level admission at
    once — vs the reference's single-GPU one-shot ``inference.py:52-63``).

    Threading contract (egpt_check rule ``lock``): this class is
    single-threaded BY DESIGN — every method touches resident device
    buffers, and the owning ``ServingEngine`` serializes all access
    behind its ``_lock`` (``_EXTERNAL_LOCK`` below). It must never
    spawn a thread or grow a lock of its own; state shared lock-free
    with handler threads (``request_stats``, ``finished`` snapshots)
    is read-only on their side and bounded here.

    Dispatch-path contract (rule ``hot-sync``): the hot set rooted at
    ``step``/``_dispatch_segment`` (``_HOT_ROOTS``) contains no host
    sync — ``.item()``, ``jax.device_get``, ``np.asarray`` of device
    values, ``block_until_ready`` — except at the three annotated
    harvest points (``_harvest_segment``; the admission NaN-quarantine
    readbacks in ``_scatter_wave``/``_finish_admission``). That is the
    static guarantee behind the pipelined scheduler's overlap ratio.
    """

    _EXTERNAL_LOCK = "ServingEngine._lock"
    _HOT_ROOTS = ("step", "_dispatch_segment")

    def __init__(
        self,
        params,
        cfg: EventChatConfig,
        max_batch: int = 4,
        max_len: int = 1024,
        chunk: int = 32,
        temperature: float = 0.0,
        top_p: float = 1.0,
        eos_token_id: Optional[int] = 2,
        seed: int = 0,
        kv_quant: bool = False,
        speculative: int = 0,
        mesh=None,
        prefill_chunk: int = 0,
        history_len: int = 2048,
        draft_head=None,
        first_chunk: int = 0,
        max_queue: int = 0,
        nan_check: bool = True,
        pipeline: bool = True,
        prefix_cache: bool = True,
        prefix_cache_bytes: int = 0,
        prefix_insert: bool = True,
        prefill_budget: int = 0,
        prefill_lane_chunk: int = 0,
        slo_window: int = 256,
        mem_headroom_bytes: int = 0,
        mem_capacity_bytes: int = 0,
        kv_layout: str = "dense",
        kv_pool_blocks: int = 0,
        preempt: bool = False,
        spill_capacity_mb: int = 0,
        spec_buckets=None,
        spec_ema_alpha: float = 0.3,
        spec_draft_cost: float = 0.05,
        spec_hysteresis: float = 0.05,
        spec_row_window: int = 4,
        spec_head_min_yield: float = 0.05,
        role: str = "colocated",
    ):
        if prefill_chunk and (2 * SEQ_BUCKET) % prefill_chunk:
            # A chunk that does not divide the bucket grain would force
            # dynamic_slice to clamp the final chunk's start, desyncing
            # embed positions from cache write slots (_chunk_prefill).
            raise ValueError(
                f"prefill_chunk must divide the prompt bucket grain "
                f"{2 * SEQ_BUCKET}, got {prefill_chunk}"
            )
        # What the decoder carries, from its module: the kinds of state a
        # row keeps, the cap on an admission wave, its own span counts and
        # the flags it cannot serve yet (models/eventchat.decoder_of).
        self._dec = eventchat.decoder_of(cfg)
        eventchat.refuse_unserved(cfg, **{
            "--kv_cache int8": kv_quant,
            "--kv_layout paged": kv_layout == "paged",
            "--speculative": speculative, "--spec_buckets": spec_buckets,
            "--draft_head": draft_head is not None,
            "--prefill_chunk": prefill_chunk,
            "--prefill_budget": prefill_budget > 0,
            "--prefix_cache_mb": prefix_cache, "--preempt": preempt,
            "--role": role != "colocated",
            "--mesh_model": mesh is not None})
        if mesh is not None:
            from eventgpt_tpu.parallel import serving as serving_mod

            serving_mod._require_serving_mesh(mesh)
            serving_mod.require_flash_heads_divide(cfg.llama, mesh)
        self.mesh = mesh
        self.params, self.cfg = params, cfg
        # The most positions (rows x bucket) one admission wave may prefill;
        # 0: as many as there are free rows.
        self._wave_tokens = int(self._dec.wave_tokens(cfg.llama))
        self._fixed_state = tuple(self._dec.fixed_state(cfg.llama))
        # Admission pads prompts to the serving bucket grain; a max_len off
        # the grain would let a bucketed row_cache outgrow the shared cache
        # (a trace-time shape crash). Round up once here.
        grain = 2 * SEQ_BUCKET
        max_len = ((max_len + grain - 1) // grain) * grain
        self.max_batch, self.max_len, self.chunk = max_batch, max_len, chunk
        # TTFT ramp: while any active row still owes its FIRST token, run
        # segments of this length instead of the full chunk, so fresh
        # admissions surface a token after ~first_chunk iterations rather
        # than a whole segment (VERDICT r4 #4 — the 0.2 s prefill /
        # multi-second TTFT gap is segment granularity, not prefill).
        # 0 disables; costs one extra cached executable per segment kind.
        # Speculative rows commit their first token AT admission
        # (_admit_speculative), so the ramp predicate (an active row with
        # t_first unset) is unsatisfiable there — drop the flag rather
        # than compile a ramp executable no segment can ever select.
        self.first_chunk = (
            min(int(first_chunk), chunk)
            if first_chunk and not speculative and not spec_buckets else 0
        )
        self.temperature, self.top_p = float(temperature), float(top_p)
        self.eos = eos_token_id if eos_token_id is not None else -1
        self.eos_token_id = eos_token_id
        self._dtype = jax.tree_util.tree_leaves(params["llama"])[0].dtype
        if self._dtype not in (jnp.bfloat16, jnp.float32):
            self._dtype = jnp.bfloat16  # quantized tree: compute in bf16
        self.kv_quant = kv_quant
        # KV layout (ISSUE 12 tentpole): "dense" keeps one (B, max_len)
        # row per batch slot; "paged" replaces it with ONE block-pool
        # arena (n_blocks × SEQ_BUCKET positions per layer/plane) plus
        # per-row int32 block tables — allocation becomes block-granular
        # (admission gated by FREE BLOCKS, not batch × max_len), prefix
        # "copies" become table aliasing with copy-on-write, and every
        # jit-visible shape stays static. Chains are byte-identical
        # across layouts (the gather/scatter translation is pure
        # indexing — tests/test_paged_blocks.py holds the full matrix).
        if kv_layout not in ("dense", "paged"):
            raise ValueError(
                f"kv_layout must be 'dense' or 'paged', got {kv_layout!r}")
        # Disaggregated serving role (ISSUE 17): "colocated" (default)
        # admits AND decodes — the single-engine behavior, unchanged.
        # "prefill" runs chunked/batched admission only: each activated
        # row's block run is gathered and parked in ``handoff_ready``
        # for the fleet coordinator to ship (``_handoff_sweep``).
        # "decode" additionally accepts gathered records through
        # ``import_handoff`` and splices them into its own arena. The
        # handoff record is block-shaped (the PR 16 spill record), so
        # split roles require the paged layout.
        if role not in ("colocated", "prefill", "decode"):
            raise ValueError(
                f"role must be 'colocated', 'prefill' or 'decode', "
                f"got {role!r}")
        if role != "colocated" and kv_layout != "paged":
            raise ValueError(
                f"role={role!r} requires kv_layout='paged' (the handoff "
                f"moves block runs)")
        self.role = role
        if role == "prefill":
            # Piggyback lanes advance inside the decode dispatch, which
            # a prefill-role scheduler never runs — lanes would starve.
            # Chunked/wave admission covers the prefill worker's job.
            prefill_budget = 0
        self.kv_layout = kv_layout
        self._paged = kv_layout == "paged"
        self._pool: Optional[serve_blocks.BlockPool] = None
        if self._paged:
            self._kv_block_size = SEQ_BUCKET
            self._nbpr = max_len // SEQ_BUCKET  # table width (blocks/row)
            # Default pool = dense-equivalent capacity (+1 scratch): the
            # layout change alone never shrinks what fits. Operators cap
            # it lower (--kv_pool_blocks) to trade peak concurrency for
            # HBM.
            n_blocks = int(kv_pool_blocks) or (max_batch * self._nbpr + 1)
            min_blocks = (2 * SEQ_BUCKET) // SEQ_BUCKET + 1
            if n_blocks < min_blocks:
                # One prompt-grain bucket + scratch is the floor; a
                # request needing more than the pool holds is rejected
                # loudly at submit() (the per-request fit rule).
                raise ValueError(
                    f"kv_pool_blocks={n_blocks} cannot hold one prompt "
                    f"bucket ({min_blocks - 1} blocks + 1 scratch)")
            self.cache = llama_mod.init_paged_kv_cache(
                cfg.llama, max_batch, max_len, n_blocks, SEQ_BUCKET,
                dtype=self._dtype, quant=kv_quant,
            )
        else:
            self.cache = self._dec.init_cache(
                cfg.llama, max_batch, max_len, dtype=self._dtype,
                quant=kv_quant
            )
        # Vocab from the actual lm_head leaf, not cfg: special-token
        # registration can grow the embeddings past cfg.llama.vocab_size
        # (prepare_model's resize).
        vocab = eventchat._vocab_size(params)
        self.logits = jnp.zeros((max_batch, vocab), jnp.float32)
        # Speculative serving (window > 0): rows draft from their own
        # committed-token buffer; the prefill argmax/sample is committed at
        # admission (the _spec_segment_jit invariant) so no logits state
        # carries between segments.
        self.speculative = int(speculative)
        # Adaptive speculation (ISSUE 13 tentpole): ``spec_buckets``
        # (e.g. "0,2,4,8") makes the verification window a PER-DISPATCH-
        # BOUNDARY decision — the jax-free ``serve_spec.SpecController``
        # tracks the realized acceptance EMA + per-row windows the
        # harvest feeds it, and each boundary selects one precompiled
        # bucket executable (K=0 -> the draft-free window-1 segment, so
        # pathological traffic degrades to baseline cost) plus a per-row
        # draft-depth mask. ``speculative`` becomes the DEFAULT window
        # (the fault-degradation bucket; max bucket when 0). Chains are
        # byte-identical to any fixed K — verification makes every
        # draft exact, depth only moves latency.
        _buckets = serve_spec.parse_spec_buckets(spec_buckets) \
            if isinstance(spec_buckets, (str, type(None))) \
            else tuple(sorted({max(int(k), 1) for k in spec_buckets}))
        self._spec_ctl: Optional[serve_spec.SpecController] = None
        self.spec_windows: Optional[tuple] = None
        if _buckets:
            if not self.speculative:
                self.speculative = max(_buckets)
            self._spec_ctl = serve_spec.SpecController(
                _buckets, default_window=self.speculative,
                ema_alpha=spec_ema_alpha, draft_cost=spec_draft_cost,
                hysteresis=spec_hysteresis, row_window=spec_row_window,
                head_min_yield=spec_head_min_yield,
                # The mixed-boundary draft budget is the SAME token
                # budget lane admission enforces (ISSUE 5): drafts and
                # piggybacked prefill compete for boundary latency.
                draft_budget=max(int(prefill_budget), 0),
            )
            self.spec_windows = self._spec_ctl.windows
        # Buffer/slack sizing bound: the largest window any boundary can
        # select (== speculative for the fixed-K server).
        self.spec_max = (self._spec_ctl.max_window if self._spec_ctl
                         else self.speculative)
        self.draft_head = draft_head
        if draft_head is not None:
            if not self.speculative:
                raise ValueError(
                    "draft_head requires speculative=K > 0 (the heads "
                    "draft into the K-token verification window)"
                )
            from eventgpt_tpu.models.medusa import num_draft_heads

            n_heads = num_draft_heads(draft_head)
            if n_heads < self.spec_max - 1:
                # Validate at construction: the first medusa_drafts call
                # otherwise raises at ADMISSION time, tearing down the
                # serving loop mid-drain (the submit()-validation rule).
                # Adaptive serving seeds/carries max_window-1 drafts.
                raise ValueError(
                    f"draft_head has {n_heads} heads but the largest "
                    f"speculation window {self.spec_max} needs "
                    f"{self.spec_max - 1}"
                )
        if self.speculative:
            self.ids_buf = jnp.full((max_batch, self.max_len), -1, jnp.int32)
            self.base_pos = np.zeros((max_batch,), np.int64)
            # Per-row carried drafts (consumed only in Medusa mode; a
            # zeros dummy otherwise keeps the segment signature uniform).
            # Sized to the LARGEST bucket — every bucket's executable
            # consumes/updates its first W-1 columns of the same
            # resident buffer (no per-switch reshape, no extra dispatch).
            self.spec_drafts = jnp.zeros(
                (max_batch, max(self.spec_max - 1, 0)), jnp.int32
            )
        # Server-wide served-text history: a chronological buffer of prompt
        # text + committed answers across ALL requests, used as extra
        # lookup context by the speculative draft (_suffix_vote_drafts) —
        # cross-request echo ("The scene depicts...") is draftable even on
        # a request's first turn. 0 disables.
        self._history = (
            np.full((int(history_len),), -1, np.int64)
            if self.speculative and history_len else None
        )
        self.key = jax.random.PRNGKey(seed)
        if mesh is not None:
            self._init_mesh_placement(vocab)
        self.frozen = np.ones((max_batch,), bool)   # all rows FREE
        self.n_rem = np.zeros((max_batch,), np.int64)
        self.rows: List[Optional[_Request]] = [None] * max_batch
        self.queue: deque[_Request] = deque()
        # Prefill->decode handoff outbox (ISSUE 17): records the
        # prefill role's sweep gathered, awaiting coordinator
        # collection (``pop_handoffs``); the counters feed the /fleet
        # role block and the /stats fleet-wide aggregation.
        self.handoff_ready: List[Dict[str, Any]] = []
        self.handoffs_gathered = 0
        self.handoffs_gathered_bytes = 0
        self.handoffs_spliced = 0
        self.handoffs_spliced_bytes = 0
        self.finished: Dict[int, List[int]] = {}
        # Terminal status per finished rid (STATUS_*): drained by the
        # serving engine at harvest; bounded for direct batcher users the
        # same way request_stats is.
        self.finish_status: Dict[int, str] = {}
        # 0 = unbounded (library default; the HTTP front end passes its
        # --max_queue). A bounded queue turns overload into an explicit
        # QueueFullError at submit instead of unbounded host growth.
        self.max_queue = int(max_queue)
        self.nan_check = bool(nan_check)
        # Live requests carrying a deadline (maintained by submit /
        # _record_finish): the per-step expiry scan is skipped outright
        # when zero, so deadline-less traffic pays nothing.
        self._n_deadlines = 0
        self._next_rid = 0
        self.prefill_chunk = int(prefill_chunk)
        self._pending: Optional[_PendingAdmission] = None
        # A full-prefill admission whose first half was dispatched behind
        # the segment in flight (``_stage``); ``_admit_queue`` lands it.
        self._staged: Optional[_Prefilled] = None
        # Prefix-KV cache (ISSUE 4 tentpole): the multi-entry trie that
        # replaced the single set_prefix slot. ``prefix_cache=False`` is
        # the A/B escape hatch (every admission full-prefills);
        # ``prefix_insert=False`` keeps lookups but disables the
        # automatic insert-on-prefill population (operator-set entries
        # only — the r5 single-slot behavior, for benchmarking).
        self._prefix_cache = (
            PrefixCache(int(prefix_cache_bytes)) if prefix_cache else None
        )
        self.prefix_insert = bool(prefix_insert)
        # Per-position K+V bytes of one resident cache row — the prefix
        # cache's accounting unit (entry nbytes = bucket * this; derived
        # from the live buffers so int8-KV halves it automatically).
        _kv_leaves = jax.tree_util.tree_leaves(
            {"k": self.cache["k"], "v": self.cache["v"]})
        _kv_positions = (
            self._pool_n_blocks() * self._kv_block_size if self._paged
            else max_batch * self.max_len)
        self._kv_pos_bytes = max(
            1, sum(x.nbytes for x in _kv_leaves) // _kv_positions)
        if self._paged:
            # The ONE allocator rows, prefix entries and COW share
            # (serve_blocks.BlockPool): refcounted free list over the
            # arena, scratch block 0 reserved for dead-row writes.
            self._pool = serve_blocks.BlockPool(
                self._pool_n_blocks(), SEQ_BUCKET,
                block_bytes=SEQ_BUCKET * self._kv_pos_bytes)
            self.block_deferrals = 0
            if self._prefix_cache is not None:
                # Paged entries pin pool blocks; eviction decrefs them.
                self._prefix_cache.pool = self._pool
        # Block-tier preemption + host-RAM KV spill (ISSUE 16): with
        # ``preempt`` armed (paged layout only), an interactive
        # admission the free list cannot cover EVICTS the lowest-value
        # active rows instead of deferring — each victim's KV either
        # spills to the pinned host store (byte-exact restore through
        # the paged admission seam) or drops for re-prefill, chosen per
        # request by measured spill bytes/bandwidth vs recompute FLOPs.
        # Off by default: the defer-only baseline is unchanged.
        self.preempt = bool(preempt) and self._paged
        self._spill_store: Optional[serve_blocks.SpillStore] = None
        if self._paged:
            self._spill_store = serve_blocks.SpillStore(
                max(int(spill_capacity_mb), 0) * (1 << 20),
                owner=f"b{id(self):x}")
        self.preemptions = 0
        # Spill-vs-recompute policy state: device->host bandwidth EWMA
        # (re-measured at every gather) and the recompute rate seed.
        # Recompute is priced estimate()-consistently: ~2 * params *
        # positions FLOPs re-prefilled at the assumed sustained rate.
        self._spill_bw_Bps = 5e9
        self._spill_param_count = max(
            obs_memory.params_bytes(params) // 2, 1)
        self._recompute_flops_per_s = 5e12
        # Pipelined scheduling (the default): between-segment control state
        # (frozen / n_rem / base_pos) ALSO lives on device, updated
        # in-graph by the segment kernels, so segment N+1 is dispatched
        # from device state before segment N's outputs are fetched and the
        # host harvest runs concurrently with device compute. Double-
        # buffered: at most ONE segment in flight; admissions, cancels and
        # deadline expiries drain the pipeline first (they mutate rows).
        # ``pipeline=False`` is the synchronous escape hatch — byte-
        # identical chains either way (rows are independent in attention
        # and greedy decode is deterministic per row).
        self.pipeline = bool(pipeline)
        # Stall-free admission (ISSUE 5): a per-boundary prompt-token
        # budget folded into the decode dispatch itself. 0 = off (every
        # admission runs the exclusive wave/suffix/chunked paths — the
        # A/B escape hatch and the library default). When on, up to
        # ``_lane_cap`` admissions ride as piggyback lanes, each advanced
        # ``_lane_chunk`` prompt positions per mixed segment, so
        # lanes * chunk_p <= prefill_budget tokens of prefill land per
        # boundary while every in-flight row keeps committing tokens.
        self.prefill_budget = max(int(prefill_budget), 0)
        lane_chunk = int(prefill_lane_chunk) or min(
            self.prefill_budget, SEQ_BUCKET)
        self._lane_chunk = (
            max(1, min(lane_chunk, self.prefill_budget))
            if self.prefill_budget else 0)
        self._lane_cap = (
            max(1, min(self.prefill_budget // self._lane_chunk, max_batch))
            if self.prefill_budget else 0)
        self._lanes: List[_PendingLane] = []
        self._lane_free: List[int] = list(range(self._lane_cap))
        self._lane_cache = None       # resident (K_cap, S_lane) KV rows
        self._lane_embeds = None      # resident (K_cap, S_lane, D) embeds
        self._lane_bucket = 0         # S_lane: grown to the largest member
        self._inflight: Optional[dict] = None  # dispatched, unharvested
        # (frozen, n_rem, base_pos) device arrays as of the LAST dispatch;
        # None = stale (host mutated rows) -> rebuilt from the host mirror
        # at the next dispatch. Host mutations only happen drained, so the
        # mirror is authoritative whenever this is None.
        self._dev_carry = None
        # Service metrics: per-request TTFT / completion latency keyed by
        # rid, plus the phase-scoped counters reset_serving_stats() owns
        # (admission stall totals/max — the bound chunked prefill exists
        # to cut — and realized speculative acceptance: committed tokens
        # per verify iteration, AGGREGATE across batch rows = tokens per
        # weight-streaming pass, so it exceeds the per-chain window bound
        # when several rows are active).
        self.request_stats: Dict[int, Dict[str, float]] = {}
        # Windowed goodput (ISSUE 6): the last ``slo_window`` SLO-classed
        # finishes, True per request that met every armed target — the
        # egpt_serve_slo_goodput_ratio gauge is their mean.
        self._slo_window_len = max(int(slo_window), 1)
        # HBM memory ledger (ISSUE 9): attribute every resident buffer
        # this server holds to a named component. Keys are namespaced by
        # owner so fleet replicas report their own share; the weight
        # tree is keyed by the TREE's identity — N replicas built off
        # one tree register the same entry once (a resize to the same
        # size is a no-op).
        self._mem_owner = f"b{id(self):x}"
        # Flight recorder (ISSUE 10): request ids are per-batcher, so
        # each batcher records its timelines under a process-unique
        # owner id (a fleet runs N batchers in one process). Owner
        # registration works disarmed too — arming later just starts
        # recording.
        self._journey_owner = obs_journey.register_owner(self._mem_owner)
        if self._prefix_cache is not None:
            # Re-key the cache's ledger entry under this server's owner
            # namespace so the per-replica view (GET /fleet) includes
            # its prefix bytes (safe pre-insert: no entry exists yet).
            self._prefix_cache._mem_key = \
                f"{self._mem_owner}/prefix_cache"
        obs_memory.LEDGER.register(
            "weights", f"shared/params-{id(params):x}",
            obs_memory.params_bytes(params))
        if self._paged:
            # Ledger split (ISSUE 12 satellite): the arena and the table
            # are separate components, so /memory shows where paged
            # bytes live (the table is the only term that scales with
            # max_batch; the pool scales with blocks).
            obs_memory.LEDGER.register(
                "kv_pool", f"{self._mem_owner}/kv_pool",
                obs_memory.params_bytes(
                    {"k": self.cache["k"], "v": self.cache["v"]}))
            obs_memory.LEDGER.register(
                "kv_block_table", f"{self._mem_owner}/kv_block_table",
                self.cache["bt"].nbytes + self.cache["length"].nbytes)
        else:
            obs_memory.LEDGER.register(
                "kv_cache", f"{self._mem_owner}/kv_cache",
                obs_memory.params_bytes(self.cache))
        obs_memory.LEDGER.register(
            "logits", f"{self._mem_owner}/logits", self.logits.nbytes)
        if self.speculative:
            obs_memory.LEDGER.register(
                "ids_buf", f"{self._mem_owner}/ids_buf",
                self.ids_buf.nbytes)
            obs_memory.LEDGER.register(
                "draft", f"{self._mem_owner}/spec_drafts",
                self.spec_drafts.nbytes)
        if draft_head is not None:
            obs_memory.LEDGER.register(
                "draft", f"shared/medusa-{id(draft_head):x}",
                obs_memory.params_bytes(draft_head))
        if self.pipeline:
            # Device-resident scheduler carry (frozen bool + n_rem i32
            # + base_pos i32): small, but it IS a named resident
            # allocation — the component list stays exhaustive.
            self._mem_carry_bytes = max_batch * (
                1 + 4 + (4 if self.speculative else 0))
            obs_memory.LEDGER.register(
                "carry", f"{self._mem_owner}/carry", self._mem_carry_bytes)
        # Admission headroom guard (ISSUE 9): defer admission waves when
        # the ledger predicts the next wave would push the accounted
        # total past capacity - headroom. 0 = off (the A/B escape
        # hatch and the library default). Capacity: explicit override,
        # else the device's reported limit (0 on CPU -> guard inert).
        self.mem_headroom_bytes = max(int(mem_headroom_bytes), 0)
        self._mem_capacity = int(mem_capacity_bytes) or (
            obs_memory.device_capacity_bytes()
            if self.mem_headroom_bytes else 0)
        self.mem_deferrals = 0
        # Compiled-footprint probe result (warmup() fills it; lazily
        # probed on first memory_stats() otherwise).
        self._compiled_footprint: Optional[Dict[str, Any]] = None
        # Last chosen speculation window (journey spec_depth events fire
        # on CHANGE only; persists across reset_serving_stats).
        self._spec_last_window = self.speculative
        self.reset_serving_stats()

    def __del__(self):
        # A dropped batcher must not leave stale owner-keyed bytes in
        # the memory ledger (multi-server processes: fleet rebuilds,
        # tests). The shared weight-tree entry stays — the
        # tree may outlive this server. Best-effort: interpreter
        # teardown may have torn the ledger down first.
        owner = getattr(self, "_mem_owner", None)
        if owner is None:
            return  # __init__ raised before registration
        try:
            for comp, key in (("kv_cache", "kv_cache"),
                              ("kv_pool", "kv_pool"),
                              ("kv_block_table", "kv_block_table"),
                              ("logits", "logits"),
                              ("ids_buf", "ids_buf"),
                              ("draft", "spec_drafts"),
                              ("carry", "carry"),
                              ("lanes", "lanes"),
                              ("spill", "spill")):
                obs_memory.LEDGER.release(comp, f"{owner}/{key}")
        except Exception:
            pass

    def _init_mesh_placement(self, vocab: int) -> None:
        """Place the resident buffers on the serving mesh and record their
        shardings (the out-sharding pins for every scheduler jit)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from eventgpt_tpu.parallel import serving as serving_mod

        mesh = self.mesh
        self._serving = serving_mod
        self.cache = serving_mod.shard_kv_cache(self.cache, self.cfg.llama, mesh)
        baxes = serving_mod.serving_batch_axes(mesh, self.max_batch)
        bspec = baxes if baxes else None
        model_n = mesh.shape.get("model", 1)
        vocab_ax = "model" if (model_n > 1 and vocab % model_n == 0) else None
        self._logits_sh = NamedSharding(mesh, P(bspec, vocab_ax))
        # Batch-1 admission logits (chunked prefill's last-token output).
        self._row_logits_sh = NamedSharding(mesh, P(None, vocab_ax))
        self.logits = jax.device_put(self.logits, self._logits_sh)
        self._b_sh = NamedSharding(mesh, P(bspec))
        self._toks_sh = NamedSharding(mesh, P(bspec, None))
        self._key_sh = NamedSharding(mesh, P())
        self.key = jax.device_put(self.key, self._key_sh)
        if self.speculative:
            self._ids_sh = NamedSharding(mesh, P(bspec, None))
            self.ids_buf = jax.device_put(self.ids_buf, self._ids_sh)
            self._drafts_sh = NamedSharding(mesh, P(bspec, None))
            self.spec_drafts = jax.device_put(self.spec_drafts,
                                              self._drafts_sh)
        cache_sh = jax.tree_util.tree_map(lambda x: x.sharding, self.cache)
        flat, treedef = jax.tree_util.tree_flatten(cache_sh)
        self._cache_flat_sh, self._cache_treedef = tuple(flat), treedef

    # -- client surface ---------------------------------------------------

    def warmup(self, prompt_lens: Optional[Sequence[int]] = None) -> int:
        """Precompile every executable a request could hit — the vision
        encoder, one prefill per prompt bucket (+ the chunked-prefill
        kernel when enabled), row admission, and the decode/spec segment —
        so no request pays XLA compile (or persistent-cache executable
        load) mid-service. ``prompt_lens``: expected prompt lengths (text +
        event tokens); default warms every bucket up to max_len/context.

        Runs the REAL jit callables against the live resident state: a
        zeros batch-1 prefill admitted into row 0 is dead storage (the row
        stays FREE/frozen; its cache slots and logits are overwritten at
        the next real admission), and a segment with every row frozen
        exits its while_loop at entry — a no-op dispatch that still
        compiles and caches the executable. That reasoning only holds on
        an idle server — warming into a live row 0 (or zeroing active
        cache lengths) would corrupt in-flight requests, so admission
        must not have started yet.  Returns the number of warmed
        callables.
        """
        from eventgpt_tpu.models.eventchat import _prefill_jit, _prefill_sharded

        if (self.queue or self._pending is not None
                or any(r is not None for r in self.rows)):
            raise RuntimeError(
                "warmup() must run before any request is admitted: it "
                "writes dummy state into row 0 and resets cache lengths, "
                "which would corrupt in-flight rows"
            )

        grain = 2 * SEQ_BUCKET
        if prompt_lens is None:
            limit = min(
                self.max_len,
                ((self.cfg.llama.max_seq_len + grain - 1) // grain) * grain,
            )
            buckets = list(range(grain, limit + 1, grain))
        else:
            buckets = sorted({
                min(((max(int(p), 1) + grain - 1) // grain) * grain,
                    self.max_len)
                for p in prompt_lens
            })
        n = 0
        pv = jnp.zeros(
            (1, self.cfg.num_event_frames, 3, self.cfg.vision.image_size,
             self.cfg.vision.image_size), self._dtype,
        )
        if self.mesh is not None:
            pv = self._serving.shard_batch_array(pv, self.mesh)
        jax.block_until_ready(
            eventchat.encode_events_batch(self.params, self.cfg, pv,
                                          sharded=self.mesh is not None)
        )
        n += 1
        want_hidden = self.draft_head is not None
        for s1 in buckets:
            padded, mask, row_cache = self._dummy_prefill_args(s1)
            if self.mesh is not None:
                pre = _prefill_sharded(
                    self.params, self.cfg, padded, mask, row_cache,
                    self.mesh, return_hidden=want_hidden,
                )
            else:
                pre = _prefill_jit(
                    self.params, self.cfg, padded, mask, row_cache, True,
                    return_hidden=want_hidden,
                )
            row_logits, row_cache = pre[0], pre[-1]
            n += 1
            if self.prefill_chunk:
                # One chunk at this bucket's embed shape compiles the
                # chunked-admission executable (its dummy cache is dropped).
                chunk_cache = self._new_row_cache(s1)
                start_arr = jnp.asarray(0, jnp.int32)
                new_len = jnp.asarray([1], jnp.int32)
                last_idx = jnp.asarray(0, jnp.int32)
                if self.mesh is not None:
                    from jax.sharding import PartitionSpec as P

                    row_sh = jax.tree_util.tree_map(
                        lambda x: x.sharding, chunk_cache
                    )
                    flat, treedef = jax.tree_util.tree_flatten(row_sh)
                    fn = _get_sharded_chunk_prefill(
                        self.cfg, self.prefill_chunk, tuple(flat),
                        treedef, self._row_logits_sh,
                        jax.sharding.NamedSharding(self.mesh, P(None, None)),
                    )
                    fn(self.params, padded, chunk_cache, start_arr,
                       new_len, last_idx)
                else:
                    _chunk_prefill_jit(
                        self.params, self.cfg, padded, chunk_cache,
                        start_arr, new_len, last_idx, self.prefill_chunk,
                    )
                n += 1
            # Admission executable (keyed per bucket): write into row 0 —
            # dead storage for a FREE row, overwritten at real admission.
            # Paged: every destination is the OOB sentinel (all writes
            # dropped) and the row index is out of bounds too — the
            # executable compiles, the pool stays untouched.
            if self._paged:
                oob_dst = jnp.full((s1 // self._kv_block_size,),
                                   self._pool.n_blocks, jnp.int32)
                btr = jnp.zeros((self._nbpr,), jnp.int32)
                if self.mesh is not None:
                    oob_dst = self._serving.replicate(oob_dst, self.mesh)
                    btr = self._serving.replicate(btr, self.mesh)
                    admit = _get_sharded_admit_paged(
                        self._cache_flat_sh, self._cache_treedef,
                        self._logits_sh
                    )
                else:
                    admit = _admit_row_paged_jit
                self.cache, self.logits = admit(
                    self.cache, self.logits, self.max_batch, oob_dst, btr,
                    row_cache, row_logits
                )
            else:
                if self.mesh is not None:
                    admit = _get_sharded_admit(
                        self._cache_flat_sh, self._cache_treedef,
                        self._logits_sh
                    )
                else:
                    admit = functools.partial(
                        _admit_row_jit, fixed=self._fixed_state)
                self.cache, self.logits = admit(
                    self.cache, self.logits, 0, row_cache, row_logits
                )
            n += 1
        # Zero the dummy row length so its pre-admission frozen-row write
        # slot stays far from the buffer edge (hygiene; writes above the
        # length are masked/dropped either way).
        self.cache = {**self.cache, "length": self.cache["length"] * 0}
        # Segment executable(s): all rows frozen -> no-op dispatch that
        # still compiles and caches. Dispatched with an explicit carry and
        # record_carry=False so the resident pipeline carry (and the armed
        # fault plan's serve.dispatch counters) stay untouched.
        warm_carry = [
            jnp.asarray(np.ones((self.max_batch,), bool)),
            jnp.zeros((self.max_batch,), jnp.int32),
            (jnp.zeros((self.max_batch,), jnp.int32)
             if self.speculative else None),
        ]
        if self.mesh is not None:
            warm_carry = list(self._serving.place_carry(
                self.mesh, self.max_batch, *warm_carry
            ))
        chunks = [None] + ([self.first_chunk] if self.first_chunk else [])
        # Adaptive speculation (ISSUE 13): every bucket in the window
        # set is its own (n_iters, window)-keyed executable — prime
        # them ALL here, so a mid-serve depth switch NEVER compiles
        # (the no-new-compilation contract tests/test_spec_adaptive
        # pins via the jit cache size).
        windows = (list(self.spec_windows) if self.spec_windows
                   else [None])
        for ck in chunks:
            for w in windows:
                # The TTFT-ramp segment is its own executable (chunk is
                # a static arg) — warm it too or the first admission
                # pays it.
                rec = self._dispatch_segment(
                    chunk=ck, carry=tuple(warm_carry), record_carry=False,
                    probe_faults=False, window=w,
                )
                jax.block_until_ready(rec["n_new"])
                n += 1
        if self.prefill_budget:
            # Mixed-segment executables (ISSUE 5): idle lanes against the
            # largest requested prompt bucket — the decode half exits at
            # entry, the lane half runs a garbage chunk above length 0
            # (masked); nothing touches resident rows.
            self._ensure_lane_buffers(buckets[-1])
            for ck in chunks:
                for w in windows:
                    rec = self._dispatch_segment(
                        chunk=ck, carry=tuple(warm_carry),
                        record_carry=False, probe_faults=False,
                        warm_mixed=True, window=w,
                    )
                    jax.block_until_ready(rec["n_new"])
                    n += 1
        self._dev_carry = None
        if self._prefix_cache is not None and self._prefix_cache.n_entries:
            # Prefix-admission (suffix) executables, one per distinct
            # entry shape (_prefix_prefill at the smallest suffix bucket
            # — query tails; a longer real suffix compiles its own). The
            # dummy row caches are discarded, nothing touches the
            # resident state, and record=False keeps the warmup
            # dispatches out of the hit/dispatch telemetry and the armed
            # fault plans (the serve.prefix_copy site counts only real
            # admissions).
            from eventgpt_tpu.constants import EVENT_TOKEN_INDEX

            dummy_pv = np.zeros(
                (self.cfg.num_event_frames, 3, self.cfg.vision.image_size,
                 self.cfg.vision.image_size), np.float32,
            )
            warmed_shapes = set()
            for entry in self._prefix_cache.entries():
                shape_key = (entry.bucket, entry.has_event, entry.length)
                if shape_key in warmed_shapes:
                    continue
                dummy = [0] if entry.has_event else [EVENT_TOKEN_INDEX]
                if self._prefix_admit(entry, dummy_pv, dummy,
                                      record=False) is not None:
                    warmed_shapes.add(shape_key)
                    n += 1
        # Compiled-footprint probe (ISSUE 9): the segment executable was
        # compiled moments ago, so the AOT re-lower here is a compile-
        # cache load — record its temp/argument/output sizes while the
        # server is still idle (compiled_stats never raises).
        self._compiled_footprint = self._probe_compiled_footprint()
        return n

    def _dummy_prefill_args(self, bucket: int):
        """(embeds, mask, row cache) of a zeros batch-1 prompt filling
        ``bucket`` — what warmup() prefills, placed as admission would."""
        padded = jnp.zeros((1, bucket, self.cfg.llama.hidden_size),
                           self._dtype)
        mask = jnp.ones((1, bucket), bool)
        if self.mesh is not None:
            padded = self._serving.shard_batch_array(padded, self.mesh)
            mask = self._serving.shard_batch_array(mask, self.mesh)
        return padded, mask, self._new_row_cache(bucket)

    def prefill_hlo(self, bucket: int) -> str:
        """Optimized HLO text of the batch-1 prefill executable ``warmup``
        builds for ``bucket`` (same callable, same argument shapes, so a
        warmed server loads it from the compile cache). ``chip_smoke.py``
        reads it to see that flash went through Mosaic — a
        ``tpu_custom_call`` in the text — neither interpreted nor
        replaced."""
        from eventgpt_tpu.models.eventchat import _prefill_jit, \
            _sharded_prefill_fn

        padded, mask, row_cache = self._dummy_prefill_args(bucket)
        want_hidden = self.draft_head is not None
        if self.mesh is None:
            lowered = _prefill_jit.lower(
                self.params, self.cfg, padded, mask, row_cache, True,
                return_hidden=want_hidden)
        else:
            lowered = _sharded_prefill_fn(
                self.cfg, padded, row_cache, self.mesh, want_hidden,
            ).lower(self.params, padded, mask, row_cache)
        return lowered.compile().as_text()

    def set_prefix(self, input_ids: Sequence[int],
                   pixel_values=None) -> int:
        """Prefill a shared prompt prefix ONCE and INSERT it into the
        prefix-KV cache (since ISSUE 4 this is one entry among many — the
        cache also populates itself on admission prefill; POST /prefix is
        an insert, not a replacement). Admissions whose prompts start
        with these exact ids skip its encode + prefill and run only their
        suffix (``_prefix_prefill``). Two regimes:

          * text-only prefix (the system-prompt head): suffixes carry the
            event sentinel and still pay CLIP encode;
          * prefix THROUGH the event block (``pixel_values`` given):
            multi-turn-session traffic over one stream — suffixes are
            plain text, so admission skips the CLIP encode too.

        Non-matching prompts fall back to the full prefill path
        untouched. Returns the prefix length in cache positions."""
        from eventgpt_tpu.constants import EVENT_TOKEN_INDEX
        from eventgpt_tpu.data.tokenizer import split_at_event
        from eventgpt_tpu.models.eventchat import _pad_batch, _prefill_jit, \
            _prefill_sharded, splice_embeddings

        if self._prefix_cache is None:
            raise RuntimeError(
                "prefix cache is disabled (prefix_cache=False); set_prefix "
                "has nowhere to insert"
            )
        ids = list(input_ids)
        n_ev = sum(1 for t in ids if t == EVENT_TOKEN_INDEX)
        if n_ev > 1:
            raise ValueError(f"prefix may contain at most one event "
                             f"sentinel, got {n_ev}")
        if n_ev == 1 and pixel_values is None:
            raise ValueError("prefix contains the event sentinel; "
                             "pixel_values is required")
        if n_ev == 1:
            pv = jnp.asarray(pixel_values, self._dtype)[None]
            if self.mesh is not None:
                pv = self._serving.shard_batch_array(pv, self.mesh)
            ev = eventchat.encode_events_batch(self.params, self.cfg, pv,
                                               sharded=self.mesh is not None)
            embeds = [splice_embeddings(
                self.params, self.cfg, split_at_event(ids), ev[0]
            )]
        else:
            embeds = [llama_mod.embed_tokens(
                self.params["llama"], jnp.asarray([ids], jnp.int32)
            )[0]]
        padded, mask, lens = _pad_batch(embeds)
        p_len = int(lens[0])
        grain = 2 * SEQ_BUCKET
        s1p = min(((p_len + grain - 1) // grain) * grain, self.max_len)
        if p_len + SEQ_BUCKET > self.max_len:
            # Loud fit check (submit()'s rule): the prefix plus at least
            # one suffix bucket must fit the server, or every admission
            # would fall back to full prefill (and the pad below would
            # crash on a negative width for a prefix past max_len).
            raise ValueError(
                f"prefix ({p_len} positions) does not fit server "
                f"max_len {self.max_len} with room for a suffix"
            )
        padded = jnp.pad(padded, ((0, 0), (0, s1p - p_len), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, s1p - p_len)))
        row_cache = self._new_row_cache(s1p)
        if self.mesh is not None:
            padded = self._serving.shard_batch_array(padded, self.mesh)
            mask = self._serving.shard_batch_array(mask, self.mesh)
            _, row_cache = _prefill_sharded(
                self.params, self.cfg, padded, mask, row_cache, self.mesh
            )
        else:
            _, row_cache = _prefill_jit(
                self.params, self.cfg, padded, mask, row_cache, True
            )
        blocks = None
        kv = {"k": row_cache["k"], "v": row_cache["v"]}
        if self._paged:
            # The operator entry owns its own block run (refcount 1 from
            # the cache): scatter the prefilled row into fresh pool
            # blocks; admissions then alias them like any other entry.
            nblk = s1p // self._kv_block_size
            blocks = self._pool.alloc(nblk)
            if blocks is None:
                self._prefix_cache.reclaim_blocks(self._pool, nblk)
                blocks = self._pool.alloc(nblk)
            if blocks is None:
                raise ValueError(
                    f"prefix entry needs {nblk} pool blocks; only "
                    f"{self._pool.free_blocks()} free (raise "
                    f"--kv_pool_blocks)")
            dst = jnp.asarray(blocks, jnp.int32)
            if self.mesh is not None:
                dst = self._serving.replicate(dst, self.mesh)
                fn = _get_sharded_pool_write(
                    self._cache_flat_sh, self._cache_treedef)
                self.cache = fn(self.cache, dst, row_cache["k"],
                                row_cache["v"])
            else:
                self.cache = _pool_write_jit(
                    self.cache, dst, row_cache["k"], row_cache["v"])
            kv = None
        entry = _PrefixEntry(
            ids=tuple(ids),
            # Identity of the prefix's event stream: admissions whose
            # pixels differ must NOT reuse this KV.
            pixels_key=(_pixels_key(pixel_values) if n_ev == 1 else None),
            has_event=n_ev == 1,
            kv=kv, blocks=blocks,
            length=p_len, bucket=s1p,
            nbytes=s1p * self._kv_pos_bytes,
        )
        if not self._prefix_cache.insert(entry):
            if blocks:
                self._pool.decref(blocks)
            raise ValueError(
                f"prefix entry ({entry.nbytes} bytes at bucket {s1p}) "
                f"exceeds the prefix-cache budget "
                f"{self._prefix_cache.budget} (raise --prefix_cache_mb)"
            )
        return p_len

    def _prefix_lookup(self, req) -> Optional[tuple]:
        """Longest-prefix match of ``req``'s prompt against the cache:
        (entry, suffix_ids) of the deepest compatible entry, or None
        (full-prefill fallback). The wrong-stream guard (ADVICE r5
        medium) lives in ``PrefixCache.lookup``: an event entry whose
        pixels differ from the request's own stream is never returned —
        though the request may still hit a shallower TEXT entry, whose
        KV carries no event content."""
        pc = self._prefix_cache
        if pc is None or pc.n_entries == 0:
            return None
        pk = (None if req.pixel_values is None
              else _pixels_key(req.pixel_values))
        ids = list(req.input_ids)
        entry = pc.lookup(ids, pk)
        if entry is None:
            return None
        return entry, ids[len(entry.ids):]

    def _prefix_suffix_ids(self, req) -> Optional[List[int]]:
        """Suffix of ``req``'s prompt after the longest matching cached
        prefix, or None when nothing matches (full-prefill fallback)."""
        hit = self._prefix_lookup(req)
        return None if hit is None else hit[1]

    def _prefix_fit(self, entry: _PrefixEntry,
                    suffix_ids) -> Optional[tuple]:
        """Bucket arithmetic of a suffix admission against ``entry``:
        (suf_len, prompt_len, chunk, s1), or None when the row bucket
        can't host entry block + padded suffix (full-prefill fallback).
        Runs BEFORE any encode, so a falling-back request pays its CLIP
        once, on the full path — and before wave grouping, which keys on
        (chunk, s1)."""
        from eventgpt_tpu.constants import EVENT_TOKEN_INDEX

        p_len = entry.length
        if entry.has_event:
            suf_len = len(suffix_ids)
        else:
            suf_len = (
                sum(1 for t in suffix_ids if t != EVENT_TOKEN_INDEX)
                + self.cfg.num_event_tokens
            )
        prompt_len = p_len + suf_len
        chunk = ((suf_len + SEQ_BUCKET - 1) // SEQ_BUCKET) * SEQ_BUCKET
        grain = 2 * SEQ_BUCKET
        s1 = min(
            ((max(prompt_len, p_len + chunk) + grain - 1) // grain) * grain,
            self.max_len,
        )
        if p_len + chunk > s1 or s1 < entry.bucket:
            # Prompt too close to max_len for the padded suffix, or the
            # row bucket can't host the entry's stored block.
            return None
        return suf_len, prompt_len, chunk, s1

    def _suffix_embed(self, entry: _PrefixEntry, pixel_values, suffix_ids,
                      chunk: int, suf_len: int, rid: Optional[int] = None):
        """(1, chunk, D) padded suffix embeddings for one admission: a
        through-event entry's suffix is plain text (no CLIP); a text
        entry's suffix carries the sentinel and pays its own encode."""
        from eventgpt_tpu.data.tokenizer import split_at_event
        from eventgpt_tpu.models.eventchat import splice_embeddings

        if entry.has_event:
            emb = llama_mod.embed_tokens(
                self.params["llama"], jnp.asarray([suffix_ids], jnp.int32)
            )
        else:
            pv = self._upload_pixels([pixel_values], [rid])
            with obs_trace.span("encode", "admit", n=1, rid=rid):
                ev = eventchat.encode_events_batch(
                    self.params, self.cfg, pv, sharded=self.mesh is not None)
                emb = splice_embeddings(
                    self.params, self.cfg, split_at_event(suffix_ids), ev[0]
                )[None]
        assert emb.shape[1] == suf_len, (emb.shape, suf_len)
        return jnp.pad(emb, ((0, 0), (0, chunk - suf_len), (0, 0)))

    def _suffix_wave_sh(self, nb: int):
        """(last_sh, hidden_sh) pins for a batch-``nb`` suffix prefill
        under the serving mesh (batch over the serving batch axes, vocab
        axis reused from the resident logits placement)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        baxes = self._serving.serving_batch_axes(self.mesh, nb)
        bspec = baxes if baxes else None
        vocab_ax = self._logits_sh.spec[1]
        return (NamedSharding(self.mesh, P(bspec, vocab_ax)),
                NamedSharding(self.mesh, P(bspec, None)))

    def _prefix_admit(self, entry: _PrefixEntry, pixel_values, suffix_ids,
                      record: bool = True, rid: Optional[int] = None):
        """Suffix-only admission against one cached prefix-KV entry.
        Returns (row_cache, row_logits, row_hidden, prompt_len), or None
        when ``_prefix_fit`` rejects (fall back to full prefill).
        ``record=False`` (warmup) skips the ``serve.prefix_copy`` fault
        probe and the dispatch counter."""
        fit = self._prefix_fit(entry, suffix_ids)
        if fit is None:
            return None
        suf_len, prompt_len, chunk, s1 = fit
        emb = self._suffix_embed(entry, pixel_values, suffix_ids, chunk,
                                 suf_len, rid)
        if record:
            # The copy boundary is its own fault site (ISSUE 4 satellite):
            # a fault HERE lands with a row reserved and an entry about to
            # be read — exactly the window the engine's sweep and the
            # entry's never-donated KV must survive.
            faults.maybe_fail("serve.prefix_copy")
            faults.maybe_delay("serve.prefix_copy")
        with obs_trace.span("prefix_copy", "sched", plen=entry.length,
                            suffix=suf_len, rid=rid):
            row_cache = self._new_row_cache(s1)
            new_len = jnp.asarray([prompt_len], jnp.int32)
            last_idx = jnp.asarray(suf_len - 1, jnp.int32)
            plen_arr = jnp.asarray([entry.length], jnp.int32)
            ekv = self._entry_kv(entry)
            with obs_trace.span("prefill", "admit", n=1, positions=chunk,
                                rid=rid):
                if self.mesh is not None:
                    emb = self._serving.shard_batch_array(emb, self.mesh)
                    row_sh = jax.tree_util.tree_map(
                        lambda x: x.sharding, row_cache)
                    flat, treedef = jax.tree_util.tree_flatten(row_sh)
                    from jax.sharding import PartitionSpec as P

                    hidden_sh = jax.sharding.NamedSharding(
                        self.mesh, P(None, None))
                    fn = _get_sharded_prefix_prefill(
                        self.cfg, tuple(flat), treedef, self._row_logits_sh,
                        hidden_sh,
                    )
                    last, hidden, row_cache = fn(
                        self.params, ekv["k"], ekv["v"], plen_arr,
                        row_cache, emb, new_len, last_idx,
                    )
                else:
                    last, hidden, row_cache = _prefix_prefill_jit(
                        self.params, self.cfg, ekv["k"], ekv["v"],
                        plen_arr, row_cache, emb, new_len, last_idx,
                    )
        if record:
            obs_metrics.SERVE_PREFILL_DISPATCHES.inc(kind="suffix")
        return row_cache, last, hidden, prompt_len

    def _admit_suffix_wave(self, members: List[tuple]) -> None:
        """BATCHED suffix admission: N prefix-cache hits sharing the
        padded (chunk, s1) shape run ONE stacked entry-copy +
        ``decode_kstep`` dispatch, scattered into the shared cache with
        the same one-dispatch wave insert as ``_admit_wave``. Entries may
        DIFFER per member (each row copies its own stacked block) — this
        is what makes round-robin session traffic, which hits S distinct
        heads at every boundary, N→1 instead of N sequential suffix
        dispatches. Members: (req, row, entry, suffix_ids, fit) tuples."""
        n = len(members)
        nb = 1 << (n - 1).bit_length()
        _, _, chunk, s1 = members[0][4]
        for req, row, entry, suffix_ids, fit in members:
            self._prefix_cache.count_hit(entry)
        faults.maybe_fail("serve.prefix_copy")
        faults.maybe_delay("serve.prefix_copy")
        rids = [m[0].rid for m in members]
        with obs_trace.span("prefix_copy", "sched", wave=n, rids=rids):
            s_pre = max(m[2].bucket for m in members)

            def pad_block(buf, width):
                if isinstance(buf, dict):
                    return {"q": pad_block(buf["q"], width),
                            "s": pad_block(buf["s"], width)}
                return jnp.pad(buf, ((0, 0), (0, 0), (0, width - buf.shape[2]))
                               + ((0, 0),) * (buf.ndim - 3))

            def cat_blocks(blocks):
                if isinstance(blocks[0], dict):
                    return {"q": jnp.concatenate([b["q"] for b in blocks], 1),
                            "s": jnp.concatenate([b["s"] for b in blocks], 1)}
                return jnp.concatenate(blocks, axis=1)

            ekvs = [self._entry_kv(m[2]) for m in members]
            pks = [pad_block(kv["k"], s_pre) for kv in ekvs]
            pvs = [pad_block(kv["v"], s_pre) for kv in ekvs]
            if nb > n:
                # Pad slots reuse the first member's block (their rows scatter
                # out of bounds and their length is pinned to 1 below).
                pks += [pks[0]] * (nb - n)
                pvs += [pvs[0]] * (nb - n)
            wave_pk, wave_pv = cat_blocks(pks), cat_blocks(pvs)
            embs = [self._suffix_embed(m[2], m[0].pixel_values, m[3], chunk,
                                       m[4][0], m[0].rid)
                    for m in members]
            emb = jnp.concatenate(
                embs + [jnp.zeros_like(embs[0])] * (nb - n), axis=0)
            plen_arr = jnp.asarray(
                [m[2].length for m in members] + [1] * (nb - n), jnp.int32)
            new_len = jnp.asarray(
                [m[4][1] for m in members] + [1] * (nb - n), jnp.int32)
            last_idx = jnp.asarray(
                [m[4][0] - 1 for m in members] + [0] * (nb - n), jnp.int32)
            prompt_lens = [m[4][1] for m in members]
            row_cache = llama_mod.init_kv_cache(
                self.cfg.llama, nb, s1, dtype=self._dtype, quant=self.kv_quant)
            with obs_trace.span("prefill", "admit", n=n, positions=chunk,
                                rids=rids):
                if self.mesh is not None:
                    emb = self._serving.shard_batch_array(emb, self.mesh)
                    row_cache = self._serving.shard_kv_cache(
                        row_cache, self.cfg.llama, self.mesh)
                    row_sh = jax.tree_util.tree_map(lambda x: x.sharding, row_cache)
                    flat, treedef = jax.tree_util.tree_flatten(row_sh)
                    last_sh, hidden_sh = self._suffix_wave_sh(nb)
                    fn = _get_sharded_prefix_prefill(
                        self.cfg, tuple(flat), treedef, last_sh, hidden_sh,
                    )
                    last, hidden, row_cache = fn(
                        self.params, wave_pk, wave_pv, plen_arr, row_cache, emb,
                        new_len, last_idx,
                    )
                else:
                    last, hidden, row_cache = _prefix_prefill_jit(
                        self.params, self.cfg, wave_pk, wave_pv, plen_arr,
                        row_cache, emb, new_len, last_idx,
                    )
        obs_metrics.SERVE_PREFILL_DISPATCHES.inc(kind="suffix_wave")
        self._scatter_wave(
            [(m[0], m[1]) for m in members], row_cache, last,
            hidden if self.draft_head is not None else None, prompt_lens,
            entries=[m[2] for m in members], path="suffix_wave",
        )
        for m in members:
            # Selection pins drain after the wave read every entry
            # (surviving rows hold their own activation pins).
            self._drain_entry_pin(m[2])

    def submit(self, input_ids: Sequence[int], pixel_values,
               max_new_tokens: int = 64,
               deadline_s: Optional[float] = None,
               slo: Optional[SLO] = None) -> int:
        """Enqueue one request; raises immediately if it cannot fit, so one
        oversized request never tears down the serving loop mid-drain.

        ``deadline_s``: seconds from now after which the request is
        finished with ``STATUS_DEADLINE`` (whatever tokens it committed so
        far are returned) instead of holding a batch row for its full
        budget. Raises ``QueueFullError`` when the admission queue is at
        ``max_queue`` (backpressure — the caller should retry later).

        ``slo``: the request's service-level objective (``workload.SLO``
        — class name + TTFT/ITL/latency targets). Scored at finish
        (``_record_finish``) into the ``egpt_serve_slo_*`` metrics and
        ``slo_stats()``; purely observational — scheduling is unchanged
        and chains stay byte-identical with or without it. The class
        name must be one of ``SLO_CLASSES`` (it becomes a metric label;
        bounded cardinality, lint rule 5)."""
        from eventgpt_tpu.constants import EVENT_TOKEN_INDEX

        if slo is not None and slo.name not in SLO_CLASSES:
            raise ValueError(
                f"unknown SLO class {slo.name!r}: one of {SLO_CLASSES} "
                f"(class names are metric labels and must stay a closed "
                f"set)"
            )
        if self.max_queue and len(self.queue) >= self.max_queue:
            raise QueueFullError(
                f"admission queue is full ({len(self.queue)}/"
                f"{self.max_queue} requests queued); retry later"
            )

        ids = list(input_ids)
        n_text = sum(1 for t in ids if t != EVENT_TOKEN_INDEX)
        n_ev = sum(1 for t in ids if t == EVENT_TOKEN_INDEX)
        if n_ev != 1:
            # splice_embeddings would reject this during _admit, AFTER the
            # request left the queue — validate here so the loop never
            # tears down mid-drain.
            raise ValueError(
                f"prompt must contain exactly one {EVENT_TOKEN_INDEX} event "
                f"sentinel, got {n_ev}"
            )
        prompt_len = min(
            n_text + self.cfg.num_event_tokens, self.cfg.llama.max_seq_len
        )
        # Speculative rows write one verify window past their last commit.
        slack = 1 + self.spec_max
        if prompt_len + max_new_tokens + slack > self.max_len:
            raise ValueError(
                f"request does not fit: prompt {prompt_len} + budget "
                f"{max_new_tokens} exceeds server max_len {self.max_len}"
            )
        if self._paged:
            need = self._blocks_needed(prompt_len, max_new_tokens)
            if need > self._pool.usable:
                # Same loud-at-submit rule as the max_len check: a
                # request no pool state could ever cover must not sit in
                # the queue deferring forever.
                raise ValueError(
                    f"request does not fit: needs {need} KV blocks, the "
                    f"pool holds {self._pool.usable} (raise "
                    f"--kv_pool_blocks)"
                )
        rid = self._next_rid
        self._next_rid += 1
        req = _Request(rid, ids, pixel_values, max_new_tokens)
        req.prompt_len = prompt_len
        req.slo = slo
        req.t_submit = time.perf_counter()
        if deadline_s is not None:
            req.deadline = req.t_submit + float(deadline_s)
            self._n_deadlines += 1
        self.queue.append(req)
        obs_metrics.SERVE_QUEUE_DEPTH.set(len(self.queue))
        obs_trace.async_begin(
            "queued", rid, prompt_len=prompt_len, budget=max_new_tokens,
            **({"slo_class": slo.name} if slo is not None else {}))
        obs_journey.begin(
            self._journey_owner, rid, t=req.t_submit,
            prompt_len=prompt_len, budget=max_new_tokens,
            **({"slo_class": slo.name} if slo is not None else {}))
        obs_series.note_submit()
        return rid

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or in-flight request: its row is freed (or it
        leaves the queue / pending admission), whatever tokens it already
        committed are finished under ``STATUS_CANCELLED``. Returns False
        when the rid is unknown or already finished."""
        for req in self.queue:
            if req.rid == rid:
                self.queue.remove(req)
                self._finish_forced(req, STATUS_CANCELLED)
                return True
        if self._pending is not None and self._pending.req.rid == rid:
            p, self._pending = self._pending, None
            self.rows[p.row] = None  # row stays frozen; cache untouched
            self._finish_forced(p.req, STATUS_CANCELLED)
            return True
        for req, row in (self._staged.members if self._staged else ()):
            if req.rid == rid and self.rows[row] is req:
                # Staged, not landed: the row was only reserved, and the
                # member's slot of the wave scatters out of bounds.
                self.rows[row] = None
                self._finish_forced(req, STATUS_CANCELLED)
                return True
        for l in self._lanes:
            if l.req.rid == rid:
                # A piggybacked admission mid-prefill: drop the lane and
                # free the reserved row (same contract as a cancelled
                # pending chunked admission — no tokens were committed).
                self._lanes.remove(l)
                self._lane_free.append(l.slot)
                self.rows[l.row] = None
                if l.entry is not None:
                    self._drain_entry_pin(l.entry)
                self._finish_forced(l.req, STATUS_CANCELLED)
                return True
        for r, req in enumerate(self.rows):
            if req is not None and req.rid == rid:
                # Cancelling an ACTIVE row mutates frozen/n_rem: settle
                # the in-flight segment first so the forced finish applies
                # at the dispatch boundary (the tokens it committed in
                # that segment are kept — same contract as the
                # synchronous path).
                self._drain()
                if self.rows[r] is not req:
                    # The drained segment finished the row itself.
                    return False
                self._finish_row(r, status=STATUS_CANCELLED)
                return True
        return False

    def export_requests(self) -> List[Dict[str, Any]]:
        """Drain hook (ISSUE 7): settle the pipeline, then strip EVERY
        unfinished request — active rows, piggyback lanes, the pending
        chunked admission, the queue — out of the scheduler and return
        their re-admission records, in submission order. The fleet
        supervisor re-routes these to surviving replicas when a replica
        dies (``ServingEngine.kill``).

        Committed tokens are DISCARDED on purpose: failover re-decodes
        from the prompt, and greedy chains are deterministic per request
        (rows are independent in attention), so the survivor's chain is
        byte-identical to an uninterrupted run. Deadlines export as the
        REMAINING headroom (absolute perf_counter deadlines do not
        transfer between submit calls). Nothing reaches ``finished`` /
        ``finish_status`` — the request is not over, it is moving."""
        self._drain()
        by_rid: Dict[int, _Request] = {}
        for req in self.queue:
            by_rid[req.rid] = req
        self.queue.clear()
        if self._pending is not None:
            p, self._pending = self._pending, None
            self.rows[p.row] = None  # row stays frozen; cache untouched
            by_rid[p.req.rid] = p.req
        for l in self._lanes:
            self.rows[l.row] = None  # lane KV is dead storage
            if l.entry is not None:
                self._drain_entry_pin(l.entry)
            by_rid[l.req.rid] = l.req
        self._lanes = []
        self._lane_free = list(range(self._lane_cap))
        self._staged = None  # its members leave with the rows below
        for r, req in enumerate(self.rows):
            if req is None:
                continue
            self.rows[r] = None
            self.frozen[r] = True
            self.n_rem[r] = 0
            by_rid[req.rid] = req
        # The host mirror changed under the device carry: rebuild at the
        # next dispatch (same rule as every external forced finish).
        self._dev_carry = None
        now = time.perf_counter()
        out: List[Dict[str, Any]] = []
        for rid in sorted(by_rid):
            req = by_rid[rid]
            if self._paged:
                # A drained request's blocks free EXACTLY (the fleet
                # handoff seam): owned + aliased refs drop here; the
                # device tables reset wholesale below.
                req.kv_bt_written = False
                self._paged_release(req)
                if req.spill_run is not None:
                    # A spilled request exports like any other: its host
                    # record drops (the survivor re-decodes from the
                    # prompt — same byte-identical argument as rows).
                    self._pool.drop_spilled(req.spill_run)
                    req.spill_run = None
                    self._spill_store.drop(req.rid)
            if req.prefix_entry is not None:
                # Same pin-drain rule as _record_finish: the entry must
                # not stay unevictable behind a request that left.
                self._drain_entry_pin(req.prefix_entry)
                req.prefix_entry = None
            if req.deadline is not None:
                self._n_deadlines -= 1
            if self._spec_ctl is not None:
                self._spec_ctl.forget(req.rid)
            obs_trace.async_end(req.phase, req.rid, status="exported")
            # The request is not over, it is MOVING: close this
            # replica's timeline as "exported" (a journey-only
            # terminal — finish_status is never written here) so the
            # fleet's stitched view can attribute the abandoned
            # assignment's wall time to failover_redo_s.
            obs_journey.event(self._journey_owner, req.rid, "exported",
                              t=now)
            obs_journey.finish(
                self._journey_owner, req.rid, "exported",
                t_submit=req.t_submit, t_done=now,
                slo_class=(req.slo.name if req.slo is not None
                           else None))
            out.append({
                "rid": req.rid,
                "input_ids": list(req.input_ids),
                "pixel_values": req.pixel_values,
                "max_new_tokens": req.max_new_tokens,
                "deadline_s": (req.deadline - now
                               if req.deadline is not None else None),
                "slo": req.slo,
            })
        if self._paged:
            # Every row left the scheduler: all tables back to scratch,
            # so no dead row's frozen writes can reach a block the next
            # admissions re-allocate.
            self.cache = {**self.cache,
                          "bt": jnp.zeros_like(self.cache["bt"])}
        obs_metrics.SERVE_QUEUE_DEPTH.set(0)
        obs_metrics.SERVE_ACTIVE_ROWS.set(0)
        return out

    def run_until_drained(self) -> Dict[int, List[int]]:
        while self.queue or any(r is not None for r in self.rows):
            self.step()
        # A trailing all-frozen segment can still be in flight after the
        # final harvest freed every row; collect it before returning.
        self._drain()
        out, self.finished = self.finished, {}
        return out

    def reset_prefix_cache(self) -> None:
        """Swap in a fresh (same-budget) prefix cache with fresh
        counters. This is THE supported reset: replacing
        ``_prefix_cache`` by hand would orphan a paged cache's pinned
        block runs (their refs would never decref — the pool drains
        monotonically until admission livelocks on the block gate).
        ``clear()`` releases the old entries' blocks under the
        deferred-on-pins rule first; the old object's ledger key is
        detached so its GC cannot release the successor's bytes."""
        if self._prefix_cache is None:
            return
        old = self._prefix_cache
        old.clear()
        # Tombstone the old key: __del__ would otherwise release the
        # NEW cache's ledger entry (same owner-derived key).
        old._mem_key = f"{self._mem_owner}/prefix_cache_dropped{id(old):x}"
        self._prefix_cache = PrefixCache(old.budget)
        self._prefix_cache._mem_key = f"{self._mem_owner}/prefix_cache"
        if self._paged:
            self._prefix_cache.pool = self._pool

    def prefix_cache_stats(self) -> Dict[str, Any]:
        """Prefix-KV cache snapshot (``GET /prefix_cache``): entry list,
        byte budget/usage, hit/miss/eviction counters."""
        if self._prefix_cache is None:
            return {"enabled": False}
        return {"enabled": True, "insert_on_prefill": self.prefix_insert,
                **self._prefix_cache.stats()}

    def journey(self, rid: int) -> Optional[Dict[str, Any]]:
        """One request's flight-recorder timeline (ISSUE 10): the full
        event list plus, once finished, the phase decomposition and
        dominant cause (``GET /request?rid=N``). None when the recorder
        is disarmed or the rid has left the retention ring."""
        return obs_journey.get(self._journey_owner, rid)

    def journey_index(self, n: int = 64) -> List[Dict[str, Any]]:
        """Recent finished request timelines, newest first — the
        ``GET /requests`` index (rid / status / slo / cause / e2e)."""
        return obs_journey.index(self._journey_owner, n)

    def memory_summary(self) -> Dict[str, Any]:
        """Cheap ledger view (host ints only — safe once per scheduler
        step): process totals + this server's own component share + the
        headroom-guard state. ``/stats`` merges it under ``"memory"``
        the way ``"slo"`` rides the snapshot."""
        s = obs_memory.LEDGER.summary()
        s["owner"] = obs_memory.LEDGER.snapshot(self._mem_owner)
        s["guard"] = {
            "headroom_bytes": self.mem_headroom_bytes,
            "capacity_bytes": self._mem_capacity,
            "deferrals": self.mem_deferrals,
        }
        if self._paged:
            s["kv_blocks"] = self._pool.stats()
            s["kv_blocks"]["deferrals"] = self.block_deferrals
            s["kv_blocks"]["preemptions"] = self.preemptions
            s["spill"] = self._spill_store.stats()
            s["spill"]["preempt"] = self.preempt
        return s

    def memory_estimate(self) -> Dict[str, Any]:
        """The static capacity model at THIS server's exact config
        (``obs.memory.estimate``): what the resident components should
        cost, from closed-form arithmetic — the number the ledger is
        reconciled against and the planning tool for configs that do
        not exist yet."""
        return obs_memory.estimate(
            self.cfg, max_batch=self.max_batch, max_len=self.max_len,
            kv_quant=self.kv_quant,
            dtype_bytes=jnp.dtype(self._dtype).itemsize,
            speculative=self.speculative,
            prefill_budget=self.prefill_budget,
            prefill_lane_chunk=self._lane_chunk,
            lane_bucket=self._lane_bucket or None,
            prefix_cache_bytes=(self._prefix_cache.budget
                                if self._prefix_cache is not None else 0),
            weights_bytes=obs_memory.params_bytes(self.params),
            vocab=int(self.logits.shape[1]),
            mesh_shape=(dict(self.mesh.shape)
                        if self.mesh is not None else None),
            kv_layout=self.kv_layout,
            kv_pool_blocks=(self._pool_n_blocks() if self._paged else 0),
            kv_block_size=(self._kv_block_size if self._paged else 0),
        )

    def memory_stats(self, reconcile: bool = True) -> Dict[str, Any]:
        """The ``GET /memory`` payload: ledger summary + a FRESH
        ``jax.live_arrays()`` reconciliation + the static estimate + the
        compiled-footprint probe. Walks every live buffer — poll-route
        cost, never per-step (``memory_summary`` is the cheap form)."""
        out = self.memory_summary()
        if reconcile:
            out["reconcile"] = obs_memory.LEDGER.reconcile()
        out["estimate"] = self.memory_estimate()
        out["compiled"] = self.compiled_footprint()
        return out

    def compiled_footprint(self, probe: bool = True) -> Dict[str, Any]:
        """XLA-side bytes of the segment executable this server
        dispatches (temp/argument/output sizes via
        ``memory_analysis()``) — the allocations the ledger cannot see.
        ``warmup()`` fills it right after compiling the executables (the
        AOT re-lower is a compile-cache load there); otherwise probed
        lazily on first call. ``probe=False`` only reports what exists."""
        if self._compiled_footprint is None and probe:
            self._compiled_footprint = self._probe_compiled_footprint()
        return self._compiled_footprint or {"probed": False}

    def _probe_compiled_footprint(self) -> Dict[str, Any]:
        """Lower + compile the resident decode/spec segment at the live
        shapes and pull ``memory_analysis()`` (``obs.memory.
        compiled_stats``). AOT lowering never executes, so the donated
        resident buffers are safe to pass."""
        frozen = jnp.asarray(np.ones((self.max_batch,), bool))
        n_rem = jnp.zeros((self.max_batch,), jnp.int32)
        base_pos = (jnp.zeros((self.max_batch,), jnp.int32)
                    if self.speculative else None)
        if self.mesh is not None:
            frozen, n_rem, base_pos = self._serving.place_carry(
                self.mesh, self.max_batch, frozen, n_rem, base_pos)
        if self.speculative:
            n_iters = max(1, self.chunk // self.speculative)
            history = (jnp.asarray(self._history.astype(np.int32))
                       if self._history is not None else None)
            # Adaptive servers probe the executable the live traffic
            # actually runs — depth array included (fixed-K probes the
            # depth-less trace, same as before ISSUE 13).
            probe_depth = (jnp.zeros((self.max_batch,), jnp.int32)
                           if self._spec_ctl is not None else None)
            if self.mesh is not None:
                if history is not None:
                    history = self._serving.replicate(history, self.mesh)
                if probe_depth is not None:
                    probe_depth = jax.device_put(probe_depth, self._b_sh)
                fn = _get_sharded_spec_segment(
                    self.cfg, n_iters, self.speculative, int(self.eos),
                    self.temperature, self.top_p, self._cache_flat_sh,
                    self._cache_treedef, self._ids_sh, self._b_sh,
                    self._key_sh, self._drafts_sh,
                )
                stats = obs_memory.compiled_stats(
                    fn, self.params, self.cache, self.key, self.ids_buf,
                    base_pos, frozen, n_rem, history, self.draft_head,
                    self.spec_drafts, probe_depth,
                )
            else:
                stats = obs_memory.compiled_stats(
                    _spec_segment_jit, self.params, self.cfg, self.cache,
                    self.key, self.ids_buf, base_pos, frozen, n_rem,
                    n_iters, self.speculative, int(self.eos),
                    self.temperature, self.top_p, history=history,
                    medusa=self.draft_head, drafts=self.spec_drafts,
                    depth=probe_depth,
                )
        elif self.mesh is not None:
            fn = _get_sharded_decode_segment(
                self.cfg, self.chunk, int(self.eos), self.temperature,
                self.top_p, self.nan_check, self._cache_flat_sh,
                self._cache_treedef, self._logits_sh, self._toks_sh,
                self._b_sh, self._key_sh,
            )
            stats = obs_memory.compiled_stats(
                fn, self.params, self.logits, self.cache, self.key,
                frozen, n_rem,
            )
        else:
            stats = obs_memory.compiled_stats(
                _decode_segment_jit, self.params, self.cfg, self.logits,
                self.cache, self.key, frozen, n_rem, self.chunk,
                int(self.eos), self.temperature, self.top_p,
                self.nan_check,
            )
        return {"segment": "spec" if self.speculative else "decode",
                "chunk": self.chunk, **stats}

    def slo_stats(self) -> Dict[str, Any]:
        """SLO-attainment snapshot (ISSUE 6): per-class finished/met
        counts + attainment ratio, and the windowed goodput ratio —
        host-side counters, so the numbers exist with telemetry disarmed
        (the `/stats` merge reads them here; /metrics
        exposes the same story as ``egpt_serve_slo_*``)."""
        classes: Dict[str, Dict[str, Any]] = {}
        for (name, met), n in sorted(self.slo_counts.items()):
            c = classes.setdefault(name, {"finished": 0, "met": 0})
            c["finished"] += n
            if met:
                c["met"] += n
        for c in classes.values():
            c["attainment"] = (c["met"] / c["finished"]
                               if c["finished"] else 0.0)
        w = len(self._slo_window)
        return {
            "classes": classes,
            "window_n": w,
            "window_size": self._slo_window_len,
            "goodput_ratio": (sum(self._slo_window) / w) if w else 0.0,
        }

    def spec_tokens_per_iteration(self) -> float:
        """Realized aggregate acceptance: committed tokens per verify
        iteration (= per weight-streaming pass, summed across batch rows
        — exceeds the per-chain window bound when several rows are
        active). THE definition; /stats and
        ``scripts/medusa_acceptance.py`` both read it here."""
        return self.spec_tokens / max(self.spec_iterations, 1)

    def spec_stats(self) -> Dict[str, Any]:
        """Adaptive-speculation snapshot (ISSUE 13): the ``spec`` block
        of ``GET /stats`` (accepted tokens per dispatch, mean chosen
        window, masked rows)
        plus the controller's own state. Host-side counters — available
        with telemetry disarmed, the prefix-cache counter convention."""
        out: Dict[str, Any] = {
            "speculative": self.speculative,
            "accepted_per_dispatch": round(
                self.spec_tokens / max(self.spec_dispatches, 1), 3),
            "spec_depth_mean": round(
                self.spec_depth_sum / max(self.spec_dispatches, 1), 3),
            "masked_rows": self.spec_masked_rows,
            "dispatches": self.spec_dispatches,
            "tokens_per_iteration": round(
                self.spec_tokens_per_iteration(), 3),
        }
        if self._spec_ctl is not None:
            out["adaptive"] = self._spec_ctl.stats()
        return out

    def reset_serving_stats(self) -> None:
        """Zero the phase-scoped counters (admission stalls, speculative
        acceptance, pipeline overlap) — e.g. after warmup or an unmeasured
        first request, so a measured window reports only its own traffic."""
        self.admission_s = 0.0
        self.admission_max_s = 0.0
        self.spec_iterations = 0
        self.spec_tokens = 0
        # Adaptive speculation (ISSUE 13), phase-scoped like the
        # acceptance counters above: dispatches + chosen-window sum
        # (their ratio is ``spec_stats``' depth_mean), rows masked
        # below full depth, and the bounded chosen-window trace the
        # replay-determinism test compares run-to-run. Controller EMA
        # state is NOT reset — it is live policy, not a statistic.
        self.spec_dispatches = 0
        self.spec_depth_sum = 0
        self.spec_masked_rows = 0
        self.spec_depth_trace: deque = deque(maxlen=4096)
        # Pipeline overlap accounting (all host-observable):
        #   device_segment_s  — host time BLOCKED waiting on the device
        #                       (the visible, un-hidden device time);
        #   host_gap_s        — host scheduler time between a fetch
        #                       returning and the next fetch blocking
        #                       (harvest bookkeeping, admission prep,
        #                       dispatch calls);
        #   overlap_hidden_s  — the part of host_gap_s spent while a
        #                       dispatched segment was verifiably still
        #                       running on the device (counted only when
        #                       the following fetch actually blocked).
        self.seg_count = 0
        self.device_segment_s = 0.0
        self.host_gap_s = 0.0
        self.overlap_hidden_s = 0.0
        self._t_prev_fetch_end: Optional[float] = None
        # Stall-free admission evidence (ISSUE 5): mixed_boundaries counts
        # harvested segments that carried live piggyback lanes alongside
        # live decode rows; mixed_zero_harvests counts those where the
        # decode rows committed ZERO tokens — by construction this stays
        # 0 (a live row commits at least one token per segment), and
        # tests/test_mixed_segments.py asserts it: in-flight rows receive
        # tokens during every admission boundary. mixed_prefill_tokens
        # totals the prompt positions advanced inside mixed segments.
        self.mixed_boundaries = 0
        self.mixed_zero_harvests = 0
        self.mixed_prefill_tokens = 0
        # SLO attainment (ISSUE 6), phase-scoped like everything above:
        # (class, met) -> finished-request counts (host-side, so goodput
        # is reportable with telemetry disarmed too, the prefix-cache
        # counter convention), plus the windowed-goodput ring.
        self.slo_counts: Dict[tuple, int] = {}
        self._slo_window: deque = deque(maxlen=self._slo_window_len)

    def overlap_ratio(self) -> float:
        """Fraction of host scheduler work hidden behind device compute
        (0 on the synchronous path: the fetch starts right after its own
        dispatch, so nothing is ever in flight during host work)."""
        return (self.overlap_hidden_s / self.host_gap_s
                if self.host_gap_s > 0 else 0.0)

    # -- scheduler core ---------------------------------------------------

    def step(self) -> None:
        """One scheduling iteration: expire deadlines, admit into free
        rows (one prefill chunk when a chunked admission is in flight),
        dispatch one decode segment, harvest finished rows.

        Pipelined (the default): the segment is dispatched from the
        device-resident carry FIRST, then the PREVIOUS segment's outputs
        are fetched — so detokenization, history/draft bookkeeping and
        admission prep run while the chip is already computing the next
        segment. Anything that must mutate rows (an expired deadline, a
        pending chunked prefill, the landing of an admission) drains the
        pipeline at the dispatch boundary before it is applied; the half
        of a full-prefill admission that mutates none (``_stage``) runs
        at the end of the step, when the harvest has freed rows and the
        segment just dispatched has its whole length ahead of it (and at
        the top of a step too while at least as many rows are free as
        decode: ``_fill_first``). With
        ``pipeline=False`` (or while the TTFT ramp owes a first token)
        every step harvests its own segment — the synchronous schedule.
        """
        faults.maybe_fail("serve.step")
        faults.maybe_delay("serve.step")
        piggy = (self.prefill_budget > 0
                 and (bool(self._lanes) or not bool(self.frozen.all())))
        if self._staged is None and self._fill_first():
            # What arrived since the last step goes behind what is left
            # of the segment in flight, and lands in this step.
            self._stage()
        landing = self._staged is not None
        if self._inflight is not None and (
                self._deadline_expired()
                or self._pending is not None
                or landing
                or any(l.filled >= l.prompt_len for l in self._lanes)
                or (self.queue and not piggy
                    and any(r is None for r in self.rows)
                    and not self._stages_head())):
            # A forced finish or admission is about to mutate rows: apply
            # it against settled state, at the dispatch boundary. A
            # piggyback JOIN is exempt (ISSUE 5): it only reserves a row
            # (host-side) and touches the lane buffers, never the decode
            # carry — so lane boundaries keep the pipeline full; only a
            # lane FINISH (activation) drains. So is a full-prefill
            # admission that ``_stage`` will take at the end of this step:
            # the pipeline stays full, and only its landing drains.
            self._drain()
        self._expire_deadlines()
        t0 = time.perf_counter()
        admitted = self._admit()
        dt_admit = time.perf_counter() - t0
        self.admission_s += dt_admit
        self.admission_max_s = max(self.admission_max_s, dt_admit)
        if admitted:
            # Only steps that did admission work (popped the queue or
            # advanced a pending chunked prefill) are observed — no-op
            # probes would drown the stall distribution in microseconds.
            obs_metrics.SERVE_ADMISSION.observe(dt_admit)
        if self.role == "prefill":
            # Prefill role: admission IS the job. Activated rows never
            # decode here — the sweep gathers each one's block run into
            # the handoff outbox for the coordinator to ship to a decode
            # worker; chunked admissions keep advancing through _admit
            # above. Nothing dispatches, so there is never an in-flight
            # segment to drain.
            self._handoff_sweep()
            return
        if all(r is None for r in self.rows):
            self._drain()  # trailing all-frozen segment, if any
            return
        if bool(self.frozen.all()) and not self._lanes:
            # Only reserved (pending-admission) rows exist — nothing to
            # decode yet; the pending prefill advanced above. (The mirror
            # only lags toward MORE-frozen, so mirror-all-frozen implies
            # the device carry is all-frozen too.) With live piggyback
            # lanes we fall through instead: the mixed dispatch advances
            # them even though the decode half no-ops — the starvation
            # guard that keeps lanes draining when nothing is decoding.
            self._drain()
            return
        chunk = self.chunk
        ramp = bool(self.first_chunk) and any(
            req is not None and not self.frozen[r] and req.t_first is None
            for r, req in enumerate(self.rows)
        )
        if ramp:
            # A fresh admission owes its first token: run the short ramp
            # segment so TTFT is ~first_chunk iterations, not a full chunk
            # — and harvest it synchronously, which is exactly what a
            # TTFT-sensitive phase wants.
            chunk = self.first_chunk
        prev, self._inflight = self._inflight, None
        rec = self._dispatch_segment(chunk=chunk)
        if prev is not None:
            # Harvest segment N while N+1 runs: THE overlap — this fetch
            # returns as soon as N's outputs exist, not when N+1 ends.
            self._harvest_segment(prev)
        if self.pipeline and not ramp:
            self._inflight = rec
            if not landing or self._fill_first():
                # The harvest above freed rows and ``rec`` has its whole
                # length ahead of it: the next admission's tower and
                # prefill queue up behind it now. (Not in the step that
                # landed the last one, while most rows decode: the rows
                # that two segments free make one wave, as they did when
                # admission drained, and a wave's fixed costs are shared.)
                self._stage()
        else:
            self._harvest_segment(rec)

    def _drain(self) -> None:
        """Harvest the in-flight segment (if any): after this the host
        mirror of frozen/n_rem/base_pos is settled and rows may be
        mutated."""
        if self._inflight is not None:
            rec, self._inflight = self._inflight, None
            self._harvest_segment(rec)

    def abort_pipeline(self) -> None:
        """Discard the in-flight segment record and the device carry (the
        engine's fault path): the dangling dispatch's outputs are ignored
        — its rows are being failed anyway — and the next dispatch
        re-uploads the repaired host view."""
        self._inflight = None
        self._dev_carry = None
        self._staged = None  # its members hold rows: the sweep fails them

    def _deadline_expired(self) -> bool:
        """Cheap host predicate: does any live deadline need a forced
        finish this step? (Gates the pipeline drain — deadline-less
        traffic, and traffic whose deadlines have headroom, never
        serializes on it.)"""
        if self._n_deadlines <= 0:
            return False
        now = time.perf_counter()

        def expired(req):
            return req.deadline is not None and now > req.deadline

        return (any(expired(q) for q in self.queue)
                or (self._pending is not None
                    and expired(self._pending.req))
                or any(req is not None and expired(req)
                       for req in self.rows))

    def _expire_deadlines(self) -> None:
        """Forced finish for every request past its deadline: queued ones
        leave the queue, a pending admission is dropped (its row stays
        frozen), and active rows are frozen mid-decode — each finished
        with ``STATUS_DEADLINE`` and its committed-so-far tokens."""
        if self._n_deadlines <= 0:
            return  # deadline-less traffic: zero per-step scan cost
        now = time.perf_counter()

        def expired(req):
            return req.deadline is not None and now > req.deadline

        if self.queue and any(expired(q) for q in self.queue):
            keep = deque()
            for req in self.queue:
                if expired(req):
                    self._finish_forced(req, STATUS_DEADLINE)
                else:
                    keep.append(req)
            self.queue = keep
        if self._pending is not None and expired(self._pending.req):
            p, self._pending = self._pending, None
            self.rows[p.row] = None
            self._finish_forced(p.req, STATUS_DEADLINE)
        for req, row in (self._staged.members if self._staged else ()):
            if self.rows[row] is req and expired(req):
                # Staged, not landed: as a cancelled member, its slot of
                # the wave scatters out of bounds.
                self.rows[row] = None
                self._finish_forced(req, STATUS_DEADLINE)
        for l in [x for x in self._lanes if expired(x.req)]:
            # A piggybacked admission expired mid-prefill: drop the lane
            # (its slot's KV is dead storage) and free the reserved row.
            # No drain needed — the lane never touched the decode carry.
            self._lanes.remove(l)
            self._lane_free.append(l.slot)
            self.rows[l.row] = None
            if l.entry is not None:
                self._drain_entry_pin(l.entry)
            self._finish_forced(l.req, STATUS_DEADLINE)
        for r, req in enumerate(self.rows):
            if req is not None and not self.frozen[r] and expired(req):
                # A deadline can cross between step()'s drain check and
                # this scan: settle any in-flight segment before mutating
                # the row (idempotent when already drained), and re-check
                # — the harvest may have finished the row itself.
                self._drain()
                if self.rows[r] is req and not self.frozen[r]:
                    self._finish_row(r, status=STATUS_DEADLINE)

    def _spec_boundary(self, forced: Optional[int] = None,
                       mixed: bool = False, record: bool = True):
        """Resolve this dispatch boundary's speculation window and
        per-row draft-depth mask (ISSUE 13). Fixed-K servers (no
        ``spec_buckets``) return (K, None) — the pre-adaptive
        executables, unchanged. Adaptive servers consult the
        ``SpecController`` (or honor ``forced`` — warmup priming a
        specific bucket) and ALWAYS return a depth array, so every
        boundary runs the same executable signature the warmup
        compiled. The ``serve.spec_adapt`` fault site fires here: a
        trip degrades THIS boundary to the fixed default window at
        full depth — adaptive policy off for one boundary, service
        untouched (chaos-tested)."""
        if self._spec_ctl is None:
            w = forced if forced is not None else self.speculative
            if record:
                # Fixed-K boundaries count too: accepted-per-dispatch /
                # depth-mean columns must be comparable across the
                # adaptive-vs-fixed A/B.
                self.spec_dispatches += 1
                self.spec_depth_sum += w
                self.spec_depth_trace.append(w)
            return w, None
        ctl = self._spec_ctl
        w = forced
        depths = None
        masked = 0
        if w is None:
            try:
                faults.maybe_fail("serve.spec_adapt")
                faults.maybe_delay("serve.spec_adapt")
                live = sum(1 for r, req in enumerate(self.rows)
                           if req is not None and not self.frozen[r])
                w = ctl.select_window(live_rows=live, mixed=mixed)
                depths, masked = ctl.depths(
                    [req.rid if req is not None else None
                     for req in self.rows], w)
            except faults.InjectedFault:
                w = ctl.default_window
                depths = None
                masked = 0
        if depths is None:
            depths = [w - 1] * self.max_batch
        # depths is a host-built policy list — the comprehension keeps
        # that visible to the hot-sync lint (no device value in sight).
        depth = jnp.asarray(np.asarray([int(d) for d in depths], np.int32))
        if self.mesh is not None:
            depth = jax.device_put(depth, self._b_sh)
        if record:
            self.spec_dispatches += 1
            self.spec_depth_sum += w
            self.spec_masked_rows += masked
            self.spec_depth_trace.append(w)
            obs_metrics.SERVE_SPEC_DEPTH.observe(w)
            if masked:
                obs_metrics.SERVE_SPEC_MASKED.inc(masked)
            if w != self._spec_last_window:
                # Depth SWITCH: stamp every live row's timeline (the
                # requests whose latency the new bucket shapes);
                # same-kind merge keeps the journey bounded.
                self._spec_last_window = w
                for r, req in enumerate(self.rows):
                    if req is not None and not self.frozen[r]:
                        obs_journey.event(
                            self._journey_owner, req.rid, "spec_depth",
                            window=w)
        return w, depth

    def _dispatch_segment(self, chunk: Optional[int] = None, carry=None,
                          record_carry: bool = True,
                          probe_faults: bool = True,
                          warm_mixed: bool = False,
                          window: Optional[int] = None) -> dict:
        """Dispatch one decode/spec segment on the resident state WITHOUT
        waiting for it, and advance the device-resident carry. Returns the
        in-flight record ``_harvest_segment`` consumes — every entry a
        device array future, so the call returns as soon as XLA enqueues
        the work.

        ``chunk`` defaults to the full segment length; the TTFT ramp
        passes ``first_chunk`` (each distinct value is its own cached
        executable). ``carry`` overrides the (frozen, n_rem, base_pos)
        inputs and ``record_carry=False`` leaves the resident carry
        untouched — the warmup path, which dispatches an all-frozen
        segment purely to compile/cache the executable (the while_loop
        exits at entry). ``probe_faults=False`` also skips the
        ``serve.dispatch`` fault site there, so armed chaos plans count
        only scheduler dispatches. ``warm_mixed`` forces the MIXED
        executable with idle lanes (warmup's compile of the piggyback
        path). ``window`` forces a specific speculation bucket (warmup
        priming every bucket's executable); None lets the adaptive
        controller choose (ISSUE 13) — or uses the fixed K.

        With live piggyback lanes (ISSUE 5) the dispatch is a MIXED
        segment: the same decode/spec body plus every lane advancing
        ``chunk_p`` prompt positions, one executable, one dispatch — the
        in-flight rows commit tokens at every admission boundary. The
        ``serve.mixed_dispatch`` fault site fires at the lane-advance
        boundary; a fault there degrades THIS boundary to a plain decode
        dispatch with every lane re-queued (``_requeue_lanes``): the
        admitting requests re-admit later, the decode rows never notice."""
        if chunk is None:
            chunk = self.chunk
        if probe_faults:
            # The dispatch boundary is its own fault site: a fault HERE
            # lands with a segment possibly in flight, which is exactly
            # the window the engine's abort/restart path must survive.
            faults.maybe_fail("serve.dispatch")
            faults.maybe_delay("serve.dispatch")
        if carry is not None:
            frozen, n_rem, base_pos = carry
        elif self._dev_carry is not None:
            frozen, n_rem, base_pos = self._dev_carry
        else:
            # Host mutated rows (admission / forced finish / init) — all
            # of which happen drained, so the mirror is authoritative.
            frozen = jnp.asarray(self.frozen)
            n_rem = jnp.asarray(self.n_rem.astype(np.int32))
            base_pos = (jnp.asarray(self.base_pos.astype(np.int32))
                        if self.speculative else None)
            if self.mesh is not None:
                frozen, n_rem, base_pos = self._serving.place_carry(
                    self.mesh, self.max_batch, frozen, n_rem, base_pos
                )
        mixed = (warm_mixed or bool(self._lanes)) \
            and self._lane_cache is not None
        if mixed and self._lanes:
            try:
                # The lane-advance boundary is its own fault site: a
                # fault HERE lands with admissions mid-prefill riding
                # the decode dispatch — the lane-degradation handler
                # must re-queue them without touching decode rows.
                faults.maybe_fail("serve.mixed_dispatch")
                faults.maybe_delay("serve.mixed_dispatch")
            except Exception:
                self._requeue_lanes()
                mixed = False
        if mixed:
            (lane_start, lane_new_len, lane_last_idx, lane_adv,
             lane_tok) = self._lane_args()
        # Per-boundary speculation decision (ISSUE 13): window bucket +
        # per-row depth mask, BEFORE the dispatch so the executable is
        # picked host-side with zero device sync.
        spec_w = spec_depth = None
        if self.speculative:
            spec_w, spec_depth = self._spec_boundary(
                window, mixed=mixed and bool(self._lanes),
                record=record_carry)
        rec = {"chunk": chunk, "frozen_in": frozen,
               "wait_at_dispatch": self.device_segment_s}
        if record_carry:
            # Warmup's all-frozen compile dispatches pass record_carry=False
            # and stay out of the telemetry the same way they stay out of
            # the overlap counters.
            obs_metrics.SERVE_SEGMENTS.inc()
            obs_metrics.SERVE_OCCUPANCY.observe(
                int(self.max_batch - int(self.frozen.sum())))
        # live: the rows this segment decodes for, by the host's mirror
        # (which a pipelined carry may be one segment ahead of); rows:
        # those it pays for.
        # own: what the decoder's module counts of them, from their lengths
        # as the host mirrors them (no fetch).
        live, own = (), {}
        if obs_trace.enabled():
            live_reqs = [req for r, req in enumerate(self.rows)
                         if req is not None and not self.frozen[r]]
            live = [req.rid for req in live_reqs]
            own = self._dec.span_counts(self.cfg.llama, [
                req.prompt_len + len(req.tokens) for req in live_reqs])
        with obs_trace.span("dispatch", "sched", chunk=chunk, live=len(live),
                            rows=self.max_batch,
                            lanes=len(self._lanes) if mixed else 0,
                            rids=live, **own) as rec["span"]:
            lane_out = None
            if self.speculative:
                n_iters = max(1, chunk // spec_w)
                history = (jnp.asarray(self._history.astype(np.int32))
                           if self._history is not None else None)
                if self.mesh is not None:
                    if history is not None:
                        history = self._serving.replicate(history, self.mesh)
                    if mixed:
                        last_sh, hidden_sh = self._suffix_wave_sh(self._lane_cap)
                        fn = _get_sharded_mixed_spec_segment(
                            self.cfg, n_iters, spec_w,
                            self._lane_chunk, int(self.eos),
                            self.temperature, self.top_p,
                            self._cache_flat_sh, self._cache_treedef,
                            self._ids_sh, self._b_sh, self._key_sh,
                            self._drafts_sh, self._lane_flat_sh,
                            self._lane_treedef, self._lane_emb_sh,
                            last_sh, hidden_sh,
                        )
                        (self.ids_buf, n_new, done, self.cache, self.key,
                         self.spec_drafts, it, frozen_out, n_rem_out,
                         base_pos_out, row_acc, row_off, pos_acc, pos_off,
                         *lane_out) = fn(
                            self.params, self.cache, self.key, self.ids_buf,
                            base_pos, frozen, n_rem, history, self.draft_head,
                            self.spec_drafts, self._lane_embeds,
                            self._lane_cache, lane_start, lane_new_len,
                            lane_last_idx, spec_depth,
                        )
                    else:
                        fn = _get_sharded_spec_segment(
                            self.cfg, n_iters, spec_w, int(self.eos),
                            self.temperature, self.top_p,
                            self._cache_flat_sh, self._cache_treedef,
                            self._ids_sh, self._b_sh, self._key_sh,
                            self._drafts_sh,
                        )
                        (self.ids_buf, n_new, done, self.cache, self.key,
                         self.spec_drafts, it, frozen_out, n_rem_out,
                         base_pos_out, row_acc, row_off, pos_acc,
                         pos_off) = fn(
                            self.params, self.cache, self.key, self.ids_buf,
                            base_pos, frozen, n_rem, history, self.draft_head,
                            self.spec_drafts, spec_depth,
                        )
                elif mixed:
                    (self.ids_buf, n_new, done, self.cache, self.key,
                     self.spec_drafts, it, frozen_out, n_rem_out,
                     base_pos_out, row_acc, row_off, pos_acc, pos_off,
                     *lane_out) = (
                        _mixed_spec_segment_jit(
                            self.params, self.cfg, self.cache, self.key,
                            self.ids_buf, base_pos, frozen, n_rem,
                            self._lane_embeds, self._lane_cache, lane_start,
                            lane_new_len, lane_last_idx, n_iters,
                            spec_w, self._lane_chunk,
                            int(self.eos), self.temperature, self.top_p,
                            history=history, medusa=self.draft_head,
                            drafts=self.spec_drafts, depth=spec_depth,
                        )
                    )
                else:
                    (self.ids_buf, n_new, done, self.cache, self.key,
                     self.spec_drafts, it, frozen_out, n_rem_out,
                     base_pos_out, row_acc, row_off, pos_acc, pos_off) = (
                        _spec_segment_jit(
                            self.params, self.cfg, self.cache, self.key,
                            self.ids_buf, base_pos,
                            frozen, n_rem, n_iters, spec_w,
                            int(self.eos), self.temperature, self.top_p,
                            history=history, medusa=self.draft_head,
                            drafts=self.spec_drafts, depth=spec_depth,
                        )
                    )
                # Read back only the window a segment could have written
                # (n_iters * window <= max(chunk, window) slots per row), not
                # the whole (B, max_len) buffer. The gather runs on the
                # OUTPUT ids_buf at the PRE-segment base — enqueued now, so
                # the harvest is one device_get with no extra dispatch.
                width = max(chunk, spec_w)
                rec.update(
                    gather=_gather_new_jit(self.ids_buf, base_pos, width),
                    it=it, n_new=n_new, done=done, window=spec_w,
                    row_acc=row_acc, row_off=row_off,
                    pos_acc=pos_acc, pos_off=pos_off,
                )
            else:
                if self.mesh is not None:
                    if mixed:
                        last_sh, hidden_sh = self._suffix_wave_sh(self._lane_cap)
                        fn = _get_sharded_mixed_decode_segment(
                            self.cfg, chunk, self._lane_chunk, int(self.eos),
                            self.temperature, self.top_p, self.nan_check,
                            self._cache_flat_sh, self._cache_treedef,
                            self._logits_sh, self._toks_sh, self._b_sh,
                            self._key_sh, self._lane_flat_sh,
                            self._lane_treedef, self._lane_emb_sh,
                            last_sh, hidden_sh,
                        )
                        (tokens, n_new, done, fin, self.logits, self.cache,
                         self.key, frozen_out, n_rem_out, *lane_out) = fn(
                            self.params, self.logits, self.cache, self.key,
                            frozen, n_rem, self._lane_embeds,
                            self._lane_cache, lane_start, lane_new_len,
                            lane_last_idx,
                        )
                    else:
                        fn = _get_sharded_decode_segment(
                            self.cfg, chunk, int(self.eos),
                            self.temperature, self.top_p, self.nan_check,
                            self._cache_flat_sh, self._cache_treedef,
                            self._logits_sh, self._toks_sh, self._b_sh,
                            self._key_sh,
                        )
                        (tokens, n_new, done, fin, self.logits, self.cache,
                         self.key, frozen_out, n_rem_out) = fn(
                            self.params, self.logits, self.cache, self.key,
                            frozen, n_rem,
                        )
                elif mixed:
                    (tokens, n_new, done, fin, self.logits, self.cache,
                     self.key, frozen_out, n_rem_out, *lane_out) = (
                        _mixed_decode_segment_jit(
                            self.params, self.cfg, self.logits, self.cache,
                            self.key, frozen, n_rem, self._lane_embeds,
                            self._lane_cache, lane_start, lane_new_len,
                            lane_last_idx, chunk, self._lane_chunk,
                            int(self.eos), self.temperature, self.top_p,
                            self.nan_check,
                        )
                    )
                else:
                    (tokens, n_new, done, fin, self.logits, self.cache,
                     self.key, frozen_out, n_rem_out, *counted) = (
                        _decode_segment_jit(
                            self.params, self.cfg, self.logits, self.cache,
                            self.key, frozen, n_rem, chunk, int(self.eos),
                            self.temperature, self.top_p, self.nan_check,
                        )
                    )
                    rec["counted"] = counted[0] if counted else None
                base_pos_out = None
                rec.update(tokens=tokens, n_new=n_new, done=done, fin=fin)
            if lane_out is not None:
                # Lane bookkeeping happens at DISPATCH (not harvest): the
                # advance is deterministic, so the pipelined scheduler can
                # build the NEXT boundary's lane args before this segment's
                # outputs are fetched. A lane that just covered its prompt
                # keeps its final-chunk logits/hidden as futures — sliced and
                # fetched only when the (drained) finish path runs.
                lane_last, lane_hidden, self._lane_cache = lane_out
                for l, end in lane_adv:
                    l.filled = end
                    if l.filled >= l.prompt_len:
                        l.last_logits = lane_last[l.slot: l.slot + 1]
                        l.last_hidden = lane_hidden[l.slot: l.slot + 1]
                if record_carry and lane_adv:
                    self.mixed_prefill_tokens += lane_tok
                    obs_metrics.SERVE_MIXED_SEGMENTS.inc()
                    obs_metrics.SERVE_MIXED_LANES.observe(len(lane_adv))
                    obs_metrics.SERVE_MIXED_PREFILL_TOKENS.inc(lane_tok)
                    obs_metrics.SERVE_PREFILL_DISPATCHES.inc(kind="piggyback")
                    rec["n_lanes"] = len(lane_adv)
            if record_carry:
                self._dev_carry = (frozen_out, n_rem_out, base_pos_out)
                self.seg_count += 1
        rec["t_dispatch"] = time.perf_counter()
        return rec

    # egpt-check: harvest -- THE designed blocking point: fetches a settled segment; downstream runs on harvested host state
    def _harvest_segment(self, rec: dict) -> None:
        """Fetch one dispatched segment's outputs (the host blocks HERE,
        and only here) and apply the row bookkeeping: commit tokens,
        stamp TTFT, decrement budgets, finish EOS/exhausted/NaN rows —
        the same transitions the segment already applied to the device
        carry, so no re-upload is needed on this path."""
        t_fetch = time.perf_counter()
        if self._t_prev_fetch_end is not None:
            gap = t_fetch - self._t_prev_fetch_end
            self.host_gap_s += gap
            obs_metrics.SERVE_HOST_GAP.inc(gap)
        # The fetch block IS the visible device time: one span per
        # segment, so Perfetto shows the un-hidden device share against
        # the dispatch/harvest host spans.
        with obs_trace.span("segment_fetch", "sched") as fetch:
            if self.speculative:
                (new_np, it_v, n_new, done, frozen_in, row_acc, row_off,
                 pos_acc, pos_off) = jax.device_get(
                    (rec["gather"], rec["it"], rec["n_new"], rec["done"],
                     rec["frozen_in"], rec["row_acc"], rec["row_off"],
                     rec["pos_acc"], rec["pos_off"])
                )
                new_np = np.asarray(new_np)
                tokens = None
                finite = counted = None
            else:
                # The quarantine mask is computed in-graph and rides the
                # same device_get as the segment outputs — no extra
                # dispatch or round trip on the hot path.
                (tokens, n_new, done, finite, frozen_in,
                 counted) = jax.device_get(
                    (rec["tokens"], rec["n_new"], rec["done"], rec["fin"],
                     rec["frozen_in"], rec.get("counted"))
                )
                finite = np.asarray(finite) if self.nan_check else None
                tokens = np.asarray(tokens)
                new_np = None
            t_end = time.perf_counter()
            wait = t_end - t_fetch
            # The rows this segment decoded for (its input freeze mask).
            rids = ([req.rid for r, req in enumerate(self.rows)
                     if req is not None and not frozen_in[r]]
                    if obs_trace.enabled() else ())
            fetch.set(wait_s=round(wait, 6), rids=rids)
        if wait > 1e-4:
            # The device was still busy when the host arrived: everything
            # the host did since this segment's dispatch — minus any time
            # it spent blocked fetching the previous segment — ran hidden
            # behind device compute.
            blocked_since = self.device_segment_s - rec["wait_at_dispatch"]
            hidden = max(0.0, t_fetch - rec["t_dispatch"] - blocked_since)
            self.overlap_hidden_s += hidden
            obs_metrics.SERVE_OVERLAP_HIDDEN.inc(hidden)
        self.device_segment_s += wait
        obs_metrics.SERVE_SEGMENT.observe(wait)
        self._t_prev_fetch_end = t_end
        with obs_trace.span("harvest", "sched", rids=rids) as sp:
            if counted is not None and obs_trace.enabled():
                # What the segment's expert layers counted, on the span
                # that enqueued it and on this one.
                counts = _expert_counts(counted)
                if counts["routed_tokens"]:  # a segment that ran a step
                    rec["span"].set(**counts)
                    sp.set(**counts)
            if self.speculative:
                self.spec_iterations += int(it_v)
                self.spec_tokens += int(n_new.sum())
                if self._spec_ctl is not None:
                    # Feed the controller the segment's UNCAPPED acceptance
                    # (per-row and per-position) — the depth policy for the
                    # NEXT boundary; in pipelined mode one boundary of lag,
                    # deterministically (the choice for N+1 was already made
                    # at its dispatch).
                    r_acc = np.asarray(row_acc)
                    r_off = np.asarray(row_off)
                    f_in = np.asarray(frozen_in)
                    self._spec_ctl.observe(
                        [(req.rid, int(r_acc[r]), int(r_off[r]))
                         for r, req in enumerate(self.rows)
                         if req is not None and not f_in[r]],
                        [int(x) for x in np.asarray(pos_acc)],
                        [int(x) for x in np.asarray(pos_off)],
                    )
                    obs_metrics.SERVE_SPEC_ACCEPT.set(
                        self._spec_ctl.accept_ema or 0.0)
            n_new = np.asarray(n_new)
            done = np.asarray(done)
            frozen_in = np.asarray(frozen_in)
            if rec.get("n_lanes"):
                # Stall-free evidence (ISSUE 5): this segment carried live
                # piggyback lanes. If decode rows were live too, they must
                # have committed tokens in the SAME dispatch — a zero-token
                # harvest here would be exactly the stall class the mixed
                # segment exists to remove.
                live = ~frozen_in
                if live.any():
                    self.mixed_boundaries += 1
                    if int(n_new[live].sum()) == 0:
                        self.mixed_zero_harvests += 1
            now = time.perf_counter()
            committed = 0
            for r, req in enumerate(self.rows):
                # frozen_in is the segment's INPUT freeze mask (the host
                # mirror may already be one segment ahead of this harvest):
                # rows frozen at dispatch produced nothing here.
                if req is None or frozen_in[r]:
                    continue
                if finite is not None and not finite[r]:
                    # Non-finite logits poison only this ROW: its segment
                    # tokens (sampled from NaN/inf logits) are discarded, the
                    # row is frozen and the request fails with a structured
                    # status — the batch and the engine keep running. (The
                    # in-graph carry froze it the same way: nan_gate mirrors
                    # nan_check.)
                    self._finish_row(r, status=STATUS_NAN, stale_carry=False)
                    continue
                if self.speculative:
                    new = new_np[r, : n_new[r]]
                    self.base_pos[r] += int(n_new[r])
                else:
                    new = tokens[r, : n_new[r]]
                if len(new):
                    committed += len(new)
                    obs_journey.event(self._journey_owner, req.rid,
                                      "segment", t=now, tokens=len(new))
                    if req.t_first is None:
                        req.t_first = now
                    elif req.t_last is not None:
                        # Inter-token latency: tokens land in harvest-sized
                        # groups, so the observable per-token gap is the mean
                        # over this harvest interval, weighted by its token
                        # count. A row's FIRST harvest is excluded — those
                        # gaps live inside TTFT.
                        obs_metrics.SERVE_ITL.observe(
                            (now - req.t_last) / len(new), n=len(new))
                    req.t_last = now
                    obs_metrics.SERVE_TOKENS.inc(len(new))
                req.tokens.extend(int(t) for t in new)
                self.n_rem[r] -= int(n_new[r])
                if done[r] or self.n_rem[r] <= 0:
                    # The device carry already froze this row in-graph — the
                    # harvest only mirrors it, so the carry stays valid.
                    self._finish_row(r, stale_carry=False)
            sp.set(tokens=committed)

    def _finish_row(self, r: int, status: str = STATUS_OK,
                    stale_carry: bool = True) -> None:
        req = self.rows[r]
        self.rows[r] = None
        self.frozen[r] = True
        self.n_rem[r] = 0
        if stale_carry:
            # External forced finish (deadline / cancel): the device carry
            # no longer matches the host view — rebuild it from the
            # mirror at the next dispatch. Callers guarantee the pipeline
            # is drained first, so the mirror is settled. Harvest-driven
            # finishes pass False: the segment froze the row in-graph
            # already, and invalidating here would roll the carry back
            # behind a segment that is already in flight.
            self._dev_carry = None
        self._record_finish(req, status)

    def _finish_forced(self, req: _Request, status: str) -> None:
        """Terminal bookkeeping for a request that never held (or no
        longer holds) a batch row — expired in the queue, cancelled, or
        quarantined at admission."""
        self._record_finish(req, status)

    def _record_finish(self, req: _Request, status: str) -> None:
        if self._paged:
            # Block reservation drains on EVERY terminal path (EOS,
            # budget, deadline, cancel, quarantine) — the paged twin of
            # the prefix-pin drain below; freed blocks are what the
            # admission gate hands the next deferred request.
            self._paged_release(req)
            if req.spill_run is not None:
                # Died while spilled (deadline in the re-queue, cancel):
                # the registry entry and the host record drain here —
                # the one non-restore exit of the spill lifecycle.
                self._pool.drop_spilled(req.spill_run)
                req.spill_run = None
                self._spill_store.drop(req.rid)
        if req.prefix_entry is not None:
            # Drain the refcount pin on EVERY terminal path (EOS, budget,
            # deadline, cancel, quarantine): the entry becomes evictable
            # once its last in-flight row is gone (and a detached paged
            # entry's deferred block run frees on the last drain).
            self._drain_entry_pin(req.prefix_entry)
            req.prefix_entry = None
        if req.deadline is not None:
            self._n_deadlines -= 1
        if self._spec_ctl is not None:
            # Drop the per-row acceptance window on every terminal path
            # (the controller's host state must not grow per request).
            self._spec_ctl.forget(req.rid)
        ids = req.tokens
        if (self.eos_token_id is not None and ids
                and ids[-1] == self.eos_token_id):
            ids = ids[:-1]
        req.t_done = time.perf_counter()
        # Bounded: a long-lived server must not grow host state per
        # request forever (oldest-first eviction; dicts are
        # insertion-ordered). finish_status is drained at harvest by the
        # engine; the same bound protects direct batcher users.
        while len(self.request_stats) >= 8192:
            self.request_stats.pop(next(iter(self.request_stats)))
        while len(self.finish_status) >= 8192:
            self.finish_status.pop(next(iter(self.finish_status)))
        ttft = (req.t_first if req.t_first is not None
                else req.t_done) - req.t_submit
        latency = req.t_done - req.t_submit
        # Realized mean inter-token gap over the request (first token
        # excluded — that interval is TTFT). Tokens land in harvest-sized
        # groups, so this is the request-level mean of the same quantity
        # the egpt_serve_itl_seconds histogram samples per harvest.
        n_commit = len(req.tokens)
        itl = ((req.t_last - req.t_first) / (n_commit - 1)
               if (req.t_first is not None and req.t_last is not None
                   and n_commit > 1) else 0.0)
        self.request_stats[req.rid] = {
            "ttft_s": ttft,
            "latency_s": latency,
            "itl_s": itl,
        }
        if req.t_first is not None:
            # Forced finishes that never committed a token (expired in the
            # queue, cancelled pre-admission) have no first token; their
            # t_done stand-in would pollute the TTFT distribution.
            obs_metrics.SERVE_TTFT.observe(ttft)
        obs_metrics.SERVE_LATENCY.observe(latency)
        obs_metrics.SERVE_REQUESTS.inc(status=status)
        slo_met: Optional[bool] = None
        if req.slo is not None:
            # SLO attainment (ISSUE 6): score the request against its
            # class targets on EVERY terminal path — a deadline-expired
            # interactive request that never committed scores on its
            # t_done stand-in TTFT, which is a miss whenever the target
            # is tighter than the time already burned (Sarathi-style
            # goodput counts completions within SLO, so forced finishes
            # must not vanish from the denominator).
            slo_met = req.slo.met(ttft, itl, latency)
            key = (req.slo.name, slo_met)
            self.slo_counts[key] = self.slo_counts.get(key, 0) + 1
            self._slo_window.append(slo_met)
            self.request_stats[req.rid]["slo_met"] = float(slo_met)
            obs_metrics.SERVE_SLO_REQUESTS.inc(
                slo_class=req.slo.name,
                met="true" if slo_met else "false")
            if req.t_first is not None:
                obs_metrics.SERVE_SLO_TTFT.observe(
                    ttft, slo_class=req.slo.name)
            if n_commit > 1:
                obs_metrics.SERVE_SLO_ITL.observe(
                    itl, slo_class=req.slo.name)
            obs_metrics.SERVE_SLO_LATENCY.observe(
                latency, slo_class=req.slo.name)
            obs_metrics.SERVE_SLO_GOODPUT.set(
                sum(self._slo_window) / len(self._slo_window))
        obs_metrics.SERVE_ACTIVE_ROWS.set(
            sum(r is not None for r in self.rows))
        obs_metrics.SERVE_QUEUE_DEPTH.set(len(self.queue))
        obs_trace.async_end(
            req.phase, req.rid, status=status, tokens=len(ids),
            **({"slo_class": req.slo.name, "slo_met": slo_met}
               if req.slo is not None else {}))
        # Flight recorder (ISSUE 10): mark forced finishes, close the
        # timeline (computes the phase decomposition + dominant cause)
        # and export the miss cause for SLO-missed finishes. Host
        # clocks/ints only — chains are byte-identical armed or not.
        forced_kind = _JOURNEY_FORCED_KIND.get(status)
        if forced_kind is not None:
            obs_journey.event(self._journey_owner, req.rid, forced_kind,
                              t=req.t_done)
        jrec = obs_journey.finish(
            self._journey_owner, req.rid, status,
            t_submit=(req.t_journey if req.t_journey is not None
                      else req.t_submit), t_done=req.t_done,
            slo_class=(req.slo.name if req.slo is not None else None),
            slo_met=slo_met)
        if jrec is not None and req.slo is not None and not slo_met:
            obs_metrics.SERVE_SLO_MISS_CAUSE.inc(
                slo_class=req.slo.name, cause=jrec["cause"])
        if status == STATUS_OK:
            self._history_append(ids)
        self.finished[req.rid] = ids
        self.finish_status[req.rid] = status

    def _history_append(self, toks) -> None:
        """Append committed/prompt text to the chronological history ring
        (oldest tokens shift out; -1 fillers are dropped at the source so
        they never waste lookup slots)."""
        if self._history is None:
            return
        arr = np.asarray([t for t in toks if t >= 0], np.int64)
        if not len(arr):
            return
        h = len(self._history)
        if len(arr) >= h:
            self._history[:] = arr[-h:]
        else:
            self._history[:-len(arr)] = self._history[len(arr):]
            self._history[-len(arr):] = arr

    # -- stall-free admission lanes (ISSUE 5) -----------------------------

    def _ensure_lane_buffers(self, s1: int) -> None:
        """Allocate (or grow to bucket ``s1``) the resident lane buffers:
        a (K_cap, S_lane) KV cache and a (K_cap, S_lane, D) prompt-embed
        buffer. Growth pads the position axis, preserving live lanes'
        state; each distinct S_lane compiles its own mixed executable, so
        buckets stay at the prompt grain (rare growth, bounded
        executables). Safe with a segment in flight: the pads enqueue on
        the donated buffers' output futures."""
        grain = 2 * SEQ_BUCKET
        s1 = min(((s1 + grain - 1) // grain) * grain, self.max_len)
        if self._lane_cache is not None and s1 <= self._lane_bucket:
            return
        d = self.cfg.llama.hidden_size
        if self._lane_cache is None:
            # ALWAYS unquantized, even on an int8-KV server: the lane's
            # attention must read the same full-precision K/V one-shot
            # prefill reads; quantization happens once, at finish
            # (_lane_extract) — exactly where prefill's write does.
            self._lane_cache = llama_mod.init_kv_cache(
                self.cfg.llama, self._lane_cap, s1, dtype=self._dtype,
                quant=False)
            self._lane_embeds = jnp.zeros(
                (self._lane_cap, s1, d), self._dtype)
        else:
            pad = s1 - self._lane_bucket

            def grow(buf):
                if isinstance(buf, dict):
                    return {"q": grow(buf["q"]), "s": grow(buf["s"])}
                return jnp.pad(buf, ((0, 0), (0, 0), (0, pad))
                               + ((0, 0),) * (buf.ndim - 3))

            self._lane_cache = {
                "k": grow(self._lane_cache["k"]),
                "v": grow(self._lane_cache["v"]),
                "length": self._lane_cache["length"],
            }
            self._lane_embeds = jnp.pad(
                self._lane_embeds, ((0, 0), (0, pad), (0, 0)))
        self._lane_bucket = s1
        if self.mesh is not None:
            self._lane_cache = self._serving.shard_kv_cache(
                self._lane_cache, self.cfg.llama, self.mesh)
            self._lane_embeds = self._serving.shard_batch_array(
                self._lane_embeds, self.mesh)
            lane_sh = jax.tree_util.tree_map(
                lambda x: x.sharding, self._lane_cache)
            flat, treedef = jax.tree_util.tree_flatten(lane_sh)
            self._lane_flat_sh, self._lane_treedef = tuple(flat), treedef
            self._lane_emb_sh = self._lane_embeds.sharding
        # Ledger resize (ISSUE 9): lane growth is the one resident
        # allocation that moves mid-service — account it where it
        # happens (metadata reads only; no host sync on this path).
        obs_memory.LEDGER.resize(
            "lanes", f"{self._mem_owner}/lanes",
            obs_memory.params_bytes(self._lane_cache)
            + self._lane_embeds.nbytes)

    def _start_full_lane(self, req: "_Request", row: int) -> None:
        """Open a piggyback lane for a full-prefill admission: the whole
        prompt's embeddings load into the lane slot; the mixed segments
        advance it ``chunk_p`` positions per boundary from position 0."""
        padded, _, prompt_len = self._prep_request(req)
        self._ensure_lane_buffers(padded.shape[1])
        slot = self._lane_free.pop()
        emb = padded[0]
        self._lane_embeds = self._lane_embeds.at[
            slot, : emb.shape[0]].set(emb)
        if self.mesh is not None:
            self._lane_embeds = jax.device_put(
                self._lane_embeds, self._lane_emb_sh)
        self._lanes.append(_PendingLane(req, row, slot, prompt_len))
        obs_journey.event(self._journey_owner, req.rid, "lane_join",
                          slot=slot, filled=0, prompt_len=prompt_len)

    def _start_suffix_lane(self, req: "_Request", row: int,
                           entry: _PrefixEntry, suffix_ids,
                           fit: tuple) -> None:
        """Open a piggyback lane for a prefix-cache hit: the entry's KV
        block seeds the lane row at [0, entry.length) (the copy is the
        lane's starting offset) and only the SUFFIX embeds load — the
        lane advances from ``filled = entry.length``."""
        suf_len, prompt_len, _, s1 = fit
        self._prefix_cache.count_hit(entry)
        # Same fault site as the exclusive suffix paths: the copy
        # boundary, with a row reserved and an entry about to be read.
        faults.maybe_fail("serve.prefix_copy")
        faults.maybe_delay("serve.prefix_copy")
        # LANE pin (past the fault probes, so a tripped admission never
        # leaks it): the lane re-reads the entry at finish (the int8
        # overlay) and its seed blocks must stay un-recycled for the
        # lane's whole pendency; every lane-termination path drains it.
        entry.pins += 1
        with obs_trace.span("prefix_copy", "sched", plen=entry.length,
                            suffix=suf_len, rid=req.rid) as copy:
            self._ensure_lane_buffers(max(s1, entry.bucket))
            slot = self._lane_free.pop()
            slot_arr = jnp.asarray(slot, jnp.int32)
            if self.mesh is not None:
                seed = _get_sharded_lane_seed(
                    self._lane_flat_sh, self._lane_treedef)
            else:
                seed = _lane_seed_jit
            ekv = self._entry_kv(entry)
            self._lane_cache = seed(
                self._lane_cache, slot_arr, ekv["k"], ekv["v"])
            emb = self._suffix_embed(entry, req.pixel_values, suffix_ids,
                                     suf_len, suf_len, req.rid)
            plen = entry.length
            self._lane_embeds = self._lane_embeds.at[
                slot, plen: plen + suf_len].set(emb[0])
            if self.mesh is not None:
                self._lane_embeds = jax.device_put(
                    self._lane_embeds, self._lane_emb_sh)
            copy.set(lane=slot)
        self._lanes.append(_PendingLane(
            req, row, slot, prompt_len, filled=plen, entry=entry))
        obs_journey.event(self._journey_owner, req.rid, "lane_join",
                          slot=slot, filled=plen, prompt_len=prompt_len)

    def _lane_args(self) -> tuple:
        """Per-boundary lane inputs for the mixed dispatch: (start,
        new_len, last_idx) over all K_cap slots plus the list of
        (lane, end) pairs this boundary actually advances and their
        total real prompt tokens. Idle and already-finished slots run a
        no-op chunk (start == new_len; garbage above the pinned length,
        masked)."""
        k = self._lane_cap
        start = np.zeros((k,), np.int32)
        new_len = np.zeros((k,), np.int32)
        last_idx = np.zeros((k,), np.int32)
        advancing: List[tuple] = []
        n_tok = 0
        for l in self._lanes:
            start[l.slot] = l.filled
            if l.filled >= l.prompt_len:
                new_len[l.slot] = l.filled  # ready: pinned, no advance
                continue
            end = min(l.filled + self._lane_chunk, l.prompt_len)
            new_len[l.slot] = end
            last_idx[l.slot] = max(0, min(l.prompt_len - 1 - l.filled,
                                          self._lane_chunk - 1))
            advancing.append((l, end))
            n_tok += end - l.filled
        return (jnp.asarray(start), jnp.asarray(new_len),
                jnp.asarray(last_idx), advancing, n_tok)

    def _requeue_lanes(self) -> None:
        """Lane-degradation handler (the ``serve.mixed_dispatch`` fault
        path): every piggybacked admission goes back to the FRONT of the
        queue (original order), its reserved row is released, and the
        boundary degrades to a plain decode dispatch — decode rows are
        untouched. Re-admission re-prefills from scratch through
        whichever path the next boundary picks."""
        for l in reversed(self._lanes):
            self.rows[l.row] = None  # row stays frozen; lane KV is dead
            if l.entry is not None:
                self._drain_entry_pin(l.entry)
            self.queue.appendleft(l.req)
        self._lanes = []
        self._lane_free = list(range(self._lane_cap))
        obs_metrics.SERVE_QUEUE_DEPTH.set(len(self.queue))

    def _finish_ready_lanes(self) -> bool:
        """Complete every lane whose prompt is fully prefilled: slice its
        lane-cache row out and run the NORMAL admission tail
        (``_finish_admission`` — NaN quarantine, insert-on-prefill,
        shared-cache scatter, activation incl. Medusa seeding), so a
        piggybacked admission is indistinguishable from an exclusive one
        from the row's first decoded token onward. Callers guarantee the
        pipeline is drained (activation rewrites the carry)."""
        done = False
        for l in [x for x in self._lanes if x.filled >= x.prompt_len]:
            self._lanes.remove(l)
            self._lane_free.append(l.slot)
            done = True
            pk = pv = None
            plen = 0
            if self.kv_quant and l.entry is not None:
                ekv = self._entry_kv(l.entry)
                pk, pv = ekv["k"], ekv["v"]
                plen = l.entry.length
            slot_arr = jnp.asarray(l.slot, jnp.int32)
            if self.mesh is not None:
                fn = _get_sharded_lane_extract(
                    self._lane_bucket, self.kv_quant,
                    self._serving.prefix_block_sharding(
                        self.mesh, self.cfg.llama),
                    plen,
                )
                k, v = fn(self._lane_cache["k"], self._lane_cache["v"],
                          slot_arr, pk, pv)
            else:
                k, v = _lane_extract_jit(
                    self._lane_cache["k"], self._lane_cache["v"],
                    slot_arr, pk, pv, self._lane_bucket, self.kv_quant,
                    plen,
                )
            row_cache = {"k": k, "v": v,
                         "length": jnp.asarray([l.prompt_len], jnp.int32)}
            obs_journey.event(self._journey_owner, l.req.rid,
                              "lane_finish", slot=l.slot,
                              prompt_len=l.prompt_len)
            self._finish_admission(
                l.req, l.row, l.prompt_len, row_cache, l.last_logits,
                l.last_hidden if self.draft_head is not None else None,
                prefix_entry=l.entry, path="lane",
            )
            if l.entry is not None:
                # Lane pin drains once the activation holds its own.
                self._drain_entry_pin(l.entry)
        return done

    # -- paged KV block pool (ISSUE 12) -----------------------------------

    def _pool_n_blocks(self) -> int:
        buf = (self.cache["k"]["q"] if isinstance(self.cache["k"], dict)
               else self.cache["k"])
        return buf.shape[1]

    def _blocks_needed(self, prompt_len: int, max_new: int) -> int:
        """Blocks one request reserves at admission: cover its prompt
        BUCKET (the admission scatter writes whole bucket-grain blocks)
        and its decode horizon ``prompt + budget + slack`` (the same
        slack submit() validates — speculative rows write one verify
        window past their last commit). Reserving the full horizon up
        front is what makes block admission deadlock-free: a row that
        admitted can always finish, no mid-decode allocation, no
        preemption machinery."""
        grain = 2 * SEQ_BUCKET
        bucket = min(((prompt_len + grain - 1) // grain) * grain,
                     self.max_len)
        slack = 1 + self.spec_max
        cover = min(max(bucket, prompt_len + max_new + slack), self.max_len)
        return self._pool.blocks_for(cover)

    def _paged_admit_gate(self) -> bool:
        """Used-token admission (the tentpole's scheduling half): the
        queue head admits only when its whole block reservation fits the
        pool's FREE list — not when a dense row would have fit. Under
        pressure the gate first reclaims LRU unpinned prefix entries
        (their pinned runs are the only idle pool capacity — eviction
        and row allocation share the one allocator); still short, the
        head stays queued and finishing rows free the blocks it needs.
        Deferral is pure timing: whatever chain a request decodes is
        unchanged, same as the byte-headroom guard."""
        req = self.queue[0]
        need = self._blocks_needed(req.prompt_len, req.max_new_tokens)
        if self._pool.free_blocks() >= need:
            return True
        if self._prefix_cache is not None:
            self._prefix_cache.reclaim_blocks(self._pool, need)
            if self._pool.free_blocks() >= need:
                return True
        if self.preempt and self._preempt_for(req, need):
            return True
        if (self.preempt and req.slo is not None
                and req.slo.name == "interactive"
                and self._spill_store.enabled
                and not self._spill_store.would_fit(
                    self._pool.block_bytes or 1)):
            # Both tiers exhausted (ISSUE 16 satellite): the scan found
            # no victims to cover the head and the host store cannot
            # take even one more block — refuse NOW with
            # ``resource_exhausted`` (HTTP 503 + Retry-After) instead
            # of letting the request hang deferred past its deadline.
            self.queue.popleft()
            obs_metrics.SERVE_QUEUE_DEPTH.set(len(self.queue))
            self._finish_forced(req, STATUS_RESOURCE)
            return False
        self._paged_defer(req, need)
        return False

    def _paged_defer(self, req, need: int) -> None:
        self.block_deferrals += 1
        obs_metrics.SERVE_KV_BLOCK_DEFERRALS.inc()
        obs_trace.instant("kv_block_defer", cat="mem", need_blocks=need,
                          free_blocks=self._pool.free_blocks())
        if obs_journey.enabled():
            obs_journey.event(self._journey_owner, req.rid,
                              "kv_block_defer", need_blocks=need,
                              free_blocks=self._pool.free_blocks())

    def _paged_requeue(self, req, row: int) -> None:
        """Undo a pop whose reservation failed: release the row, put the
        request back at the queue FRONT (original order), count the
        deferral. Nothing was allocated (alloc never partially grants)
        and nothing touched device state."""
        self.rows[row] = None
        req.row = -1
        self.queue.appendleft(req)
        obs_metrics.SERVE_QUEUE_DEPTH.set(len(self.queue))
        self._paged_defer(
            req, self._blocks_needed(req.prompt_len, req.max_new_tokens))

    def _paged_reserve(self, req, s1: int,
                       entry: Optional[_PrefixEntry] = None) -> bool:
        """Allocate the request's block reservation (aliasing the entry's
        full blocks below the divergence point on a prefix hit). False =
        pool cannot cover it right now — the caller re-queues the
        request (never a partial grant)."""
        slack = 1 + self.spec_max
        cover = min(max(s1, req.prompt_len + req.max_new_tokens + slack),
                    self.max_len)
        total = self._pool.blocks_for(cover)
        aliased: List[int] = []
        if entry is not None and entry.blocks:
            n_shared = min(entry.length // self._kv_block_size, total,
                           len(entry.blocks))
            aliased = list(entry.blocks[:n_shared])
        owned = self._pool.alloc(total - len(aliased))
        if owned is None:
            return False
        if aliased:
            self._pool.incref(aliased)
            if entry.length % self._kv_block_size:
                # The entry diverges mid-block: the admission scatter
                # re-creates that block's shared head in the row's first
                # OWNED block — THE copy-on-write copy, counted here.
                self._pool.note_cow()
        req.kv_blocks_aliased = aliased
        req.kv_blocks_owned = owned
        return True

    def _paged_bt_row(self, req) -> np.ndarray:
        """The row's block table: reservation first (aliased run, then
        owned), scratch block 0 above it (frozen writes land there)."""
        bt = np.full((self._nbpr,), serve_blocks.SCRATCH_BLOCK, np.int32)
        run = req.kv_blocks_aliased + req.kv_blocks_owned
        bt[: len(run)] = run
        return bt

    def _paged_dst_blocks(self, req, s1: int) -> np.ndarray:
        """Scatter destinations for the row's (s1-bucket) prefilled
        cache: aliased source blocks and blocks beyond the reservation
        (pure pad — a wave/lane bucket can exceed a short member's own)
        go to the OOB sentinel, which XLA drops."""
        n_src = s1 // self._kv_block_size
        oob = self._pool.n_blocks
        dst = np.full((n_src,), oob, np.int32)
        na = len(req.kv_blocks_aliased)
        own = req.kv_blocks_owned
        for j in range(na, n_src):
            if j - na < len(own):
                dst[j] = own[j - na]
        return dst

    def _paged_release(self, req) -> None:
        """Return the request's reservation on EVERY terminal/export
        path, and point its dead row's table back at scratch so the
        segment kernels' unconditional frozen writes can never land in
        a recycled block."""
        if req.kv_blocks_owned:
            self._pool.decref(req.kv_blocks_owned)
            req.kv_blocks_owned = []
        if req.kv_blocks_aliased:
            self._pool.decref(req.kv_blocks_aliased)
            req.kv_blocks_aliased = []
        if req.kv_bt_written and req.row >= 0:
            self.cache = {
                **self.cache,
                "bt": self.cache["bt"].at[req.row].set(
                    serve_blocks.SCRATCH_BLOCK),
            }
            req.kv_bt_written = False

    # -- block-tier preemption + host-RAM KV spill (ISSUE 16) -------------

    def _preempt_for(self, req, need: int) -> bool:
        """Preemption scan (the tentpole): evict the lowest-value active
        rows — batch class only, worst deadline headroom first (a
        no-deadline row has nothing to miss and goes first) — until the
        interactive head's ``need`` blocks fit the free list. Never
        preempts interactive for interactive (thrash), never for batch
        heads (they defer like today). The ``serve.preempt`` fault site
        degrades the whole scan back to the plain used-token deferral —
        no victim is touched on a trip."""
        if req.slo is None or req.slo.name != "interactive":
            return False
        try:
            faults.maybe_fail("serve.preempt")
            faults.maybe_delay("serve.preempt")
        except faults.InjectedFault:
            return False
        # Settle any in-flight segment first (the export_requests rule:
        # rows may only be mutated drained) — the harvest itself can
        # finish rows and free enough blocks to cover the head.
        self._drain()
        if self._pool.free_blocks() >= need:
            return True
        now = time.perf_counter()
        victims = []
        for r, vic in enumerate(self.rows):
            if vic is None or self.frozen[r]:
                continue  # free, lane-reserved or pending rows
            if vic.slo is not None and vic.slo.name == "interactive":
                continue
            headroom = (vic.deadline - now
                        if vic.deadline is not None else float("-inf"))
            victims.append((headroom, r, vic))
        if not victims:
            return False
        victims.sort(key=lambda x: (x[0], x[1]))
        for _, r, vic in victims:
            if self._pool.free_blocks() >= need:
                break
            if self.rows[r] is not vic or self.frozen[r]:
                continue  # the drain's harvest finished it meanwhile
            self._preempt_row(vic)
        return self._pool.free_blocks() >= need

    def _preempt_row(self, vic) -> None:
        """Evict ONE active row: spill its KV run to the host store when
        the policy prefers it (falling back to drop on any spill-path
        failure — fault trip, budget refusal, pinned run), else release
        the blocks for re-prefill; either way the victim re-queues at
        the BACK with its committed chain obligation intact (restored
        byte-exact, or re-decoded from the prompt — greedy chains are
        deterministic per row, the export_requests argument)."""
        row = vic.row
        mode = "spill" if (self._spill_choose(vic)
                           and self._spill_victim(vic)) else "drop"
        if mode == "drop":
            # Re-prefill re-decodes the whole chain from the prompt:
            # committed tokens are DISCARDED so the re-admission path
            # (prefill sample + segments) rebuilds them byte-identical.
            self._paged_release(vic)
            vic.tokens = []
        if vic.prefix_entry is not None:
            self._drain_entry_pin(vic.prefix_entry)
            vic.prefix_entry = None
        self.rows[row] = None
        vic.row = -1
        self.frozen[row] = True
        self.n_rem[row] = 0
        if self.speculative:
            self.base_pos[row] = 0
        if self._spec_ctl is not None:
            self._spec_ctl.forget(vic.rid)
        # Host row state changed under the device carry: rebuild at the
        # next dispatch (we are drained — _preempt_for settled it).
        self._dev_carry = None
        obs_trace.async_end("active", vic.rid, status="preempted")
        obs_trace.async_begin("queued", vic.rid)
        vic.phase = "queued"
        vic.preempts += 1
        self.preemptions += 1
        self.queue.append(vic)
        obs_metrics.SERVE_QUEUE_DEPTH.set(len(self.queue))
        obs_metrics.SERVE_ACTIVE_ROWS.set(
            sum(r is not None for r in self.rows))
        obs_metrics.SERVE_PREEMPTIONS.inc(mode=mode)
        obs_journey.event(self._journey_owner, vic.rid, "preempt",
                          mode=mode, row=row)

    def _spill_choose(self, vic) -> bool:
        """The spill-vs-recompute policy: spill only an exclusively
        owned run (aliased/pinned blocks have other owners — the pool
        would refuse) that fits the host budget, and only when the
        measured round-trip (bytes out + back at the gather-bandwidth
        EWMA) undercuts re-prefilling the positions decoded so far
        (~2 * params * positions FLOPs at the assumed sustained rate —
        the same closed-form pricing estimate() uses for bytes)."""
        store = self._spill_store
        if (store is None or not store.enabled
                or vic.kv_blocks_aliased or not vic.kv_blocks_owned):
            return False
        if any(self._pool.ref(b) != 1 for b in vic.kv_blocks_owned):
            # Insert-on-prefill aliased part of the run to idle cache
            # entries (ref 2). Those entries are about to outlive their
            # creator anyway — evict the unpinned ones covering this run
            # and re-check; a surviving pin means a live reader, so drop.
            if self._prefix_cache is not None:
                self._prefix_cache.evict_covering(vic.kv_blocks_owned)
            if any(self._pool.ref(b) != 1 for b in vic.kv_blocks_owned):
                return False
        nbytes = len(vic.kv_blocks_owned) * (
            self._pool.block_bytes or self._kv_block_size)
        if not store.would_fit(nbytes):
            return False
        positions = vic.prompt_len + len(vic.tokens)
        spill_s = 2.0 * nbytes / max(self._spill_bw_Bps, 1.0)
        recompute_s = (2.0 * self._spill_param_count * positions
                       / max(self._recompute_flops_per_s, 1.0))
        return spill_s <= recompute_s

    def _spill_victim(self, vic) -> bool:
        """Execute one spill, fault-safely ordered: the ``serve.spill``
        site + the gather + the store admission all happen BEFORE any
        pool mutation, so a trip or refusal anywhere leaves the pool
        (and the victim's reservation) exactly as it was and the caller
        degrades to drop-and-re-prefill."""
        try:
            faults.maybe_fail("serve.spill")
            faults.maybe_delay("serve.spill")
            rec = self._gather_spill_record(vic)
        except faults.InjectedFault:
            return False
        if not self._spill_store.put(vic.rid, rec, rec["nbytes_kv"]):
            return False
        try:
            run_id = self._pool.spill_out(vic.kv_blocks_owned)
        except serve_blocks.BlockPoolError:
            # A pin raced the eligibility check: undo the store record
            # and drop instead — the pool is untouched (spill_out
            # validates before mutating).
            self._spill_store.drop(vic.rid)
            return False
        vic.spill_run = run_id
        vic.kv_blocks_owned = []
        if vic.kv_bt_written and vic.row >= 0:
            # Same dead-row rule as _paged_release: the row's table must
            # point at scratch before its blocks are re-allocated.
            self.cache = {
                **self.cache,
                "bt": self.cache["bt"].at[vic.row].set(
                    serve_blocks.SCRATCH_BLOCK),
            }
            vic.kv_bt_written = False
        obs_journey.event(self._journey_owner, vic.rid, "spill",
                          bytes=rec["nbytes_kv"], blocks=rec["n_blocks"])
        return True

    # egpt-check: harvest -- spill gathers the victim's KV run + row state to host RAM; the preemption boundary is a drained admission decision, outside the pipelined dispatch overlap
    def _gather_spill_record(self, vic,
                             blocks: Optional[List[int]] = None
                             ) -> Dict[str, Any]:
        """The victim's complete re-activation state, gathered dense to
        host RAM: its block run's KV (the same ``_gather_blocks`` copy
        ``export_requests``' drain seam and the prefix entries use),
        cache length, logits row, and the speculative row state
        (ids_buf / base_pos / medusa drafts). Whole-block copies are
        byte-exact — attention masks positions past ``length``, so the
        restore scatter reproduces the row bit-for-bit.

        ``blocks`` overrides the gathered run (the prefill->decode
        handoff gathers the aliased+owned table run, trimmed to the
        blocks covering ``length``); default is the spill path's
        exclusively-owned run."""
        row = vic.row
        block_ids = vic.kv_blocks_owned if blocks is None else blocks
        blocks = jnp.asarray(block_ids, jnp.int32)
        if self.mesh is not None:
            blocks = self._serving.replicate(blocks, self.mesh)
            fn = _get_sharded_gather_blocks(
                self._serving.prefix_block_sharding(self.mesh,
                                                    self.cfg.llama),
                self.kv_quant,
            )
            k, v = fn(self.cache["k"], self.cache["v"], blocks)
        else:
            k, v = _gather_blocks_jit(self.cache["k"], self.cache["v"],
                                      blocks)
        dev = {"k": k, "v": v, "length": self.cache["length"][row],
               "logits": self.logits[row]}
        if self.speculative:
            dev["ids"] = self.ids_buf[row]
        if self.draft_head is not None and self.spec_max > 1:
            dev["drafts"] = self.spec_drafts[row]
        t0 = time.perf_counter()
        host = jax.device_get(dev)
        elapsed = time.perf_counter() - t0
        nbytes = int(sum(np.asarray(x).nbytes
                         for x in jax.tree_util.tree_leaves(host)))
        # Bandwidth EWMA feeding _spill_choose (measured, not assumed).
        self._spill_bw_Bps = (0.7 * self._spill_bw_Bps
                              + 0.3 * nbytes / max(elapsed, 1e-6))
        host["n_blocks"] = len(block_ids)
        host["nbytes_kv"] = nbytes
        host["base_pos"] = (int(self.base_pos[row])
                            if self.speculative else 0)
        return host

    def _paged_restore(self, req, row: int) -> bool:
        """Re-admit a spilled request (the RESTORE half of the seam):
        fresh blocks from the pool's spill registry, then the SAME
        ``_admit_row_paged`` scatter a prefill admission rides — host KV
        in, block table + length + logits row installed in one donated
        dispatch. False = the pool cannot cover the run right now (the
        caller re-queues; the run and the store record stay put)."""
        rec = self._spill_store.peek(req.rid)
        if rec is None:  # lifecycle bug — fail loudly, not silently
            raise serve_blocks.BlockPoolError(
                f"request {req.rid} has spill_run={req.spill_run} but "
                f"no spill record")
        blocks = self._pool.restore(req.spill_run, rec["n_blocks"])
        if blocks is None:
            return False
        self._spill_store.take(req.rid)
        req.spill_run = None
        req.kv_blocks_owned = blocks
        req.kv_blocks_aliased = []
        dst = jnp.asarray(blocks, jnp.int32)
        btr = jnp.asarray(self._paged_bt_row(req))
        row_cache = {"k": rec["k"], "v": rec["v"],
                     "length": np.asarray([rec["length"]], np.int32)}
        row_logits = rec["logits"][None]
        if self.mesh is not None:
            dst = self._serving.replicate(dst, self.mesh)
            btr = self._serving.replicate(btr, self.mesh)
            admit = _get_sharded_admit_paged(
                self._cache_flat_sh, self._cache_treedef,
                self._logits_sh)
        else:
            admit = _admit_row_paged_jit
        self.cache, self.logits = admit(
            self.cache, self.logits, row, dst, btr, row_cache, row_logits
        )
        req.kv_bt_written = True
        self.rows[row] = req
        req.row = row
        self.frozen[row] = False
        self.n_rem[row] = req.max_new_tokens - len(req.tokens)
        if self.speculative:
            self.ids_buf = self.ids_buf.at[row].set(
                jnp.asarray(rec["ids"]))
            if self.mesh is not None:
                self.ids_buf = jax.device_put(self.ids_buf, self._ids_sh)
            self.base_pos[row] = rec["base_pos"]
        if "drafts" in rec:
            self.spec_drafts = self.spec_drafts.at[row].set(
                jnp.asarray(rec["drafts"]))
            if self.mesh is not None:
                self.spec_drafts = jax.device_put(
                    self.spec_drafts, self._drafts_sh)
        self._dev_carry = None
        obs_trace.async_end("queued", req.rid)
        obs_trace.async_begin("active", req.rid)
        req.phase = "active"
        obs_metrics.SERVE_RESTORES.inc()
        obs_metrics.SERVE_ACTIVE_ROWS.set(
            sum(r is not None for r in self.rows))
        obs_journey.event(self._journey_owner, req.rid, "restore",
                          row=row, blocks=rec["n_blocks"])
        return True

    # -- prefill/decode disaggregation: paged-KV handoff (ISSUE 17) --------

    def _handoff_sweep(self) -> None:
        """Prefill role only (``step`` calls this instead of
        dispatching): every ACTIVATED row leaves the scheduler through
        the handoff outbox — its block run gathered to host RAM, its
        reservation released — so the next admission wave always finds
        free rows and free blocks. Reserved rows (a pending chunked
        admission, a piggyback lane) stay: they are mid-admission and
        sweep on a later step, once activated."""
        for row, req in enumerate(self.rows):
            if req is None or self.frozen[row]:
                continue
            if self.n_rem[row] <= 0:
                # The budget was met inside the admission dispatch (a
                # 1-token speculative budget commits t0 at activation):
                # nothing is left to decode, so nothing moves — finish
                # here like a colocated harvest would.
                self._finish_row(row)
                continue
            self._handoff_gather(req)

    def _handoff_gather(self, req) -> None:
        """Gather one activated row into a handoff record and tear the
        row down (the per-request half of ``export_requests``' drain
        seam). The record is the spill record plus routing state: the
        shipped KV covers only the blocks up to ``length`` (attention
        masks everything past it and decode overwrites positions before
        reading them — the spill byte-identity argument), while
        ``n_total`` names the full reservation the decode worker must
        re-allocate. Prefix-aliased blocks ship as part of the run —
        sharing does not cross the wire; the decode side owns a private
        copy."""
        row = req.row
        length = req.prompt_len + len(req.tokens)
        run = req.kv_blocks_aliased + req.kv_blocks_owned
        n_ship = min(max(self._pool.blocks_for(length), 1), len(run))
        rec = self._gather_spill_record(req, blocks=run[:n_ship])
        rec["n_total"] = len(run)
        self._paged_release(req)
        if req.prefix_entry is not None:
            self._drain_entry_pin(req.prefix_entry)
            req.prefix_entry = None
        self.rows[row] = None
        req.row = -1
        self.frozen[row] = True
        self.n_rem[row] = 0
        if self.speculative:
            self.base_pos[row] = 0
        if self._spec_ctl is not None:
            self._spec_ctl.forget(req.rid)
        if req.deadline is not None:
            self._n_deadlines -= 1
        self._dev_carry = None
        now = time.perf_counter()
        obs_trace.async_end(req.phase, req.rid, status="handoff")
        self.handoffs_gathered += 1
        self.handoffs_gathered_bytes += rec["nbytes_kv"]
        obs_metrics.PROCFLEET_HANDOFFS.inc(stage="gathered")
        obs_metrics.SERVE_ACTIVE_ROWS.set(
            sum(r is not None for r in self.rows))
        obs_journey.event(self._journey_owner, req.rid, "kv_handoff",
                          stage="gathered", bytes=rec["nbytes_kv"],
                          blocks=rec["n_blocks"])
        # The request is not over, it is MOVING (the export_requests
        # rule): "handoff" is a journey-only terminal — finish_status is
        # never written — and the closed prefill-leg journey rides the
        # outbox record so the coordinator can stitch both legs plus
        # the wire time into one exact-sum timeline.
        obs_journey.finish(
            self._journey_owner, req.rid, "handoff",
            t_submit=req.t_submit, t_done=now,
            slo_class=(req.slo.name if req.slo is not None else None))
        self.handoff_ready.append({
            "rid": req.rid,
            "input_ids": list(req.input_ids),
            "tokens": list(req.tokens),
            "max_new_tokens": req.max_new_tokens,
            "prompt_len": req.prompt_len,
            # Durations, not timestamps (clocks don't cross processes):
            # the decode worker rebases its local clock by elapsed_s so
            # TTFT / latency / SLO attainment score the request's WHOLE
            # life, not just the decode leg. t_gather stays worker-local
            # (the handler refreshes elapsed_s with the outbox wait at
            # each collect and strips it from the wire record).
            "t_gather": now,
            "elapsed_s": now - req.t_submit,
            "ttft_s": (req.t_first - req.t_submit
                       if req.t_first is not None else None),
            "deadline_s": (req.deadline - now
                           if req.deadline is not None else None),
            "slo": req.slo,
            "preempts": req.preempts,
            "journey": obs_journey.get(self._journey_owner, req.rid),
            "rec": rec,
        })

    def pop_handoffs(self) -> List[Dict[str, Any]]:
        """Drain the handoff outbox (the coordinator's collection hook).
        Delivery past this point is the caller's problem — the worker
        handler keeps popped records replayable until the coordinator
        acks them, so a collect lost to a transport fault re-serves."""
        out, self.handoff_ready = self.handoff_ready, []
        return out

    def import_handoff(self, input_ids: Sequence[int],
                       max_new_tokens: int, rec: Dict[str, Any],
                       tokens: Sequence[int] = (), prompt_len: int = 0,
                       deadline_s: Optional[float] = None,
                       slo: Optional[SLO] = None,
                       elapsed_s: float = 0.0,
                       ttft_s: Optional[float] = None) -> int:
        """Decode role: accept a prefill worker's gathered block-run
        record. The request enqueues like a submit but SPLICES at
        admission (``_handoff_splice``) instead of prefilling, and it
        bypasses ``max_queue`` — it was already admitted into the system
        at the prefill worker's queue, and bouncing it here would strand
        KV that no longer exists anywhere else. ``pixel_values`` are
        deliberately absent: the splice never re-prefills, and the REDO
        path re-routes from the coordinator's own submission record."""
        if self.role == "prefill":
            raise ValueError(
                "a prefill-role scheduler cannot import handoffs")
        if not self._paged:
            raise ValueError("import_handoff requires kv_layout='paged'")
        if slo is not None and slo.name not in SLO_CLASSES:
            raise ValueError(
                f"unknown SLO class {slo.name!r}: one of {SLO_CLASSES}")
        need = self._blocks_needed(int(prompt_len), max_new_tokens)
        if need > self._pool.usable:
            raise ValueError(
                f"handoff does not fit: needs {need} KV blocks, the "
                f"pool holds {self._pool.usable} (raise "
                f"--kv_pool_blocks)")
        rid = self._next_rid
        self._next_rid += 1
        req = _Request(rid, list(input_ids), None, max_new_tokens)
        req.tokens = list(tokens)
        req.prompt_len = int(prompt_len)
        req.slo = slo
        now = time.perf_counter()
        # Rebase the request's clock by the prefill leg + wire time
        # (shipped as a DURATION — absolute stamps never cross
        # processes): t_submit lands in the past and t_first at the
        # prefill worker's commit offset, so every downstream stat —
        # ttft_s, itl_s (the handoff gap is one inter-token interval),
        # latency_s, slo.met — scores the request's whole life exactly
        # like a colocated run, with no special-casing in _finish_row.
        # The deadline anchors at NOW: deadline_s is the REMAINING
        # headroom, already net of the elapsed time.
        req.t_submit = now - max(float(elapsed_s or 0.0), 0.0)
        req.t_journey = now
        if ttft_s is not None:
            req.t_first = req.t_submit + float(ttft_s)
        if deadline_s is not None:
            req.deadline = now + float(deadline_s)
            self._n_deadlines += 1
        req.handoff_rec = rec
        self.queue.append(req)
        obs_metrics.SERVE_QUEUE_DEPTH.set(len(self.queue))
        obs_trace.async_begin("queued", rid, prompt_len=req.prompt_len,
                              budget=max_new_tokens)
        # No obs_series.note_submit(): the arrival was already counted
        # at the prefill worker — an import is a continuation, and
        # double-counting would skew the fleet-wide arrival series.
        # The journey leg stays LOCAL (t=now, not the rebased stamp):
        # the coordinator stitches prefill phases + handoff_s + this
        # leg from durations, so a rebased begin would double-count.
        obs_journey.begin(
            self._journey_owner, rid, t=now,
            prompt_len=req.prompt_len, budget=max_new_tokens,
            **({"slo_class": slo.name} if slo is not None else {}))
        return rid

    def _handoff_splice(self, req, row: int) -> bool:
        """Splice an imported handoff record into the local arena: a
        fresh fully-owned allocation for the FULL reservation
        (``n_total`` — the same blocks-for-cover arithmetic both roles
        compute from identical flags), then the SAME ``_admit_row_paged``
        scatter every paged admission rides, over the shipped prefix of
        the run. False = the pool cannot cover the reservation right
        now (only an allocation race against the gate's pre-check — the
        caller re-queues, the record stays put)."""
        rec = req.handoff_rec
        total = int(rec.get("n_total", rec["n_blocks"]))
        blocks = self._pool.alloc(total)
        if blocks is None:
            return False
        req.handoff_rec = None
        req.kv_blocks_owned = blocks
        req.kv_blocks_aliased = []
        n_ship = int(rec["n_blocks"])
        dst = jnp.asarray(blocks[:n_ship], jnp.int32)
        btr = jnp.asarray(self._paged_bt_row(req))
        row_cache = {"k": rec["k"], "v": rec["v"],
                     "length": np.asarray([rec["length"]], np.int32)}
        row_logits = np.asarray(rec["logits"])[None]  # egpt-check: ignore[hot-sync] -- rec came off the RPC wire: every plane is already host-resident numpy (the raw-frame decoder builds them), so this asarray is a view, never a device fetch
        if self.mesh is not None:
            dst = self._serving.replicate(dst, self.mesh)
            btr = self._serving.replicate(btr, self.mesh)
            admit = _get_sharded_admit_paged(
                self._cache_flat_sh, self._cache_treedef,
                self._logits_sh)
        else:
            admit = _admit_row_paged_jit
        self.cache, self.logits = admit(
            self.cache, self.logits, row, dst, btr, row_cache, row_logits
        )
        req.kv_bt_written = True
        self.rows[row] = req
        req.row = row
        self.frozen[row] = False
        self.n_rem[row] = req.max_new_tokens - len(req.tokens)
        if self.speculative:
            self.ids_buf = self.ids_buf.at[row].set(
                jnp.asarray(rec["ids"]))
            if self.mesh is not None:
                self.ids_buf = jax.device_put(self.ids_buf, self._ids_sh)
            self.base_pos[row] = rec["base_pos"]
        if "drafts" in rec:
            self.spec_drafts = self.spec_drafts.at[row].set(
                jnp.asarray(rec["drafts"]))
            if self.mesh is not None:
                self.spec_drafts = jax.device_put(
                    self.spec_drafts, self._drafts_sh)
        self._dev_carry = None
        obs_trace.async_end("queued", req.rid)
        obs_trace.async_begin("active", req.rid)
        req.phase = "active"
        nbytes = int(rec.get("nbytes_kv", 0))
        self.handoffs_spliced += 1
        self.handoffs_spliced_bytes += nbytes
        obs_metrics.PROCFLEET_HANDOFFS.inc(stage="spliced")
        obs_metrics.SERVE_ACTIVE_ROWS.set(
            sum(r is not None for r in self.rows))
        obs_journey.event(self._journey_owner, req.rid, "kv_handoff",
                          stage="spliced", row=row, blocks=n_ship,
                          bytes=nbytes)
        return True

    def _drain_entry_pin(self, entry: _PrefixEntry) -> None:
        """Drop one refcount pin; on the LAST drain of a DETACHED paged
        entry, release its deferred block run (see
        ``PrefixCache._release_blocks_locked``). Every pin site —
        selection (hit chosen for this boundary's admission), pending
        lane, active row — drains through here, so a replaced/evicted
        entry's blocks can never free while something still reads
        them."""
        entry.pins -= 1
        if (entry.pins <= 0 and entry.detached and entry.blocks
                and self._pool is not None):
            self._pool.decref(entry.blocks)
            entry.blocks = None
            entry.detached = False

    def _entry_kv(self, entry: _PrefixEntry) -> Dict[str, Any]:
        """The entry's dense (L, 1, bucket) KV view: stored buffers for
        dense-layout entries; a pool gather for paged ones (same values
        the dense copy would carry — the exclusive suffix / lane paths
        stay layout-agnostic)."""
        if entry.kv is not None:
            return entry.kv
        blocks = jnp.asarray(entry.blocks, jnp.int32)
        if self.mesh is not None:
            blocks = self._serving.replicate(blocks, self.mesh)
            fn = _get_sharded_gather_blocks(
                self._serving.prefix_block_sharding(self.mesh,
                                                    self.cfg.llama),
                self.kv_quant,
            )
            k, v = fn(self.cache["k"], self.cache["v"], blocks)
        else:
            k, v = _gather_blocks_jit(self.cache["k"], self.cache["v"],
                                      blocks)
        return {"k": k, "v": v}

    def _admit(self) -> bool:
        """``_admit_queue`` under the ``sched.admit`` span, which is
        recorded when the step did admission work: with the requests it
        worked for and the paths they took."""
        took: List[tuple] = []  # (rid, path)
        staged = len(self._staged.members) if self._staged else 0
        with obs_trace.span("admit", "sched") as sp:
            did_work = self._admit_queue(took)
            if did_work:
                sp.set(rids=[rid for rid, _ in took], n=len(took),
                       path="+".join(sorted({p for _, p in took})),
                       staged=staged)
            else:
                sp.drop()
        return did_work

    def _admit_queue(self, took: List[tuple]) -> bool:
        """Returns True when this step did admission work (advanced a
        pending chunked prefill or popped the queue) — the telemetry
        gate for the admission-stall histogram. ``took`` receives one
        (rid, path) per request this call worked for.

        Admission policy per popped request (ISSUE 5): with a
        ``prefill_budget`` armed AND rows actively decoding (or lanes
        already live), the request becomes a PIGGYBACK LANE — prefix-KV
        hits seed the lane with the entry's block, misses load the whole
        prompt — advanced inside the decode dispatch itself, up to
        ``K_cap`` lanes at a time (excess requests stay queued; decode
        keeps flowing either way). Otherwise (nothing to stall, or
        budget off): longest-prefix match against the prefix-KV cache
        (suffix-only admission), else the chunked path (when actives are
        decoding), else collected into this step's FULL-PREFILL WAVE —
        every wave member runs in ONE batched prefill dispatch
        (``_prefill_wave``) instead of N sequential batch-1 prefills.

        A wave that ``_stage`` prefilled behind the last segment lands
        here, and nothing else is popped in the same step, nor in a step
        that left a segment in flight: a row that a segment freed is
        staged behind a later one, so that the device does not wait for
        this path's host work while it has rows to decode."""
        faults.maybe_fail("serve.admit")
        faults.maybe_delay("serve.admit")
        did_work = False
        landed, self._staged = self._staged, None
        if landed is not None:
            did_work = True
            took += [(req.rid, "wave" if len(landed.members) > 1 else "row")
                     for req, _ in landed.members]
            self._land(landed)
        if self._lanes:
            # step() drained the pipeline when any lane was ready, so
            # the activations below apply against settled state.
            took += [(l.req.rid, "lane") for l in self._lanes
                     if l.filled >= l.prompt_len]
            did_work |= self._finish_ready_lanes()
        if self._pending is not None:
            did_work = True
            took.append((self._pending.req.rid, "chunk"))
            self._advance_pending()
        # Piggyback is the per-boundary choice only while something is
        # decoding (or lanes are mid-flight — join them); with every row
        # frozen there is nothing to stall and the exclusive wave is the
        # fastest path to completion.
        piggy = (self.prefill_budget > 0
                 and (bool(self._lanes) or not bool(self.frozen.all())))
        # Memory headroom guard (ISSUE 9): when the ledger predicts the
        # next admission wave would exceed capacity - headroom, the
        # queue stays queued this boundary — decode keeps flowing, and
        # finishing rows free the bytes the deferred wave needs.
        # A step that landed a staged wave pops nothing more either, nor
        # does one that left a segment in flight for ``_stage`` to hide
        # the admission behind (a lane's join never needed it drained).
        hold = (landed is not None
                or (self._inflight is not None and not piggy)
                or self._mem_guard_defers())
        wave: List[tuple] = []  # (req, row) full-prefill admissions
        hits: List[tuple] = []  # (req, row, entry, suffix_ids, fit)
        while (self._pending is None and self.queue and not hold
               and any(self.rows[r] is None
                       for r in range(self.max_batch))):
            if piggy and not self._lane_free:
                break  # lanes at the token budget: the rest stay queued
            if wave and not self._wave_takes(wave, self.queue[0]):
                break  # the wave is at its positions: the rest stay queued
            if self._paged and not self._paged_admit_gate():
                break  # pool can't cover the head's block reservation
            req, row = self._take_head()
            did_work = True
            if self._paged and req.spill_run is not None:
                # A preempted-and-spilled head restores through the
                # paged admission seam instead of re-prefilling: fresh
                # blocks + the byte-exact scatter of its gathered KV
                # (ISSUE 16). The gate pre-checked the same reservation
                # arithmetic, so failure here is only an eviction race.
                if self._paged_restore(req, row):
                    took.append((req.rid, "restore"))
                    continue
                self._paged_requeue(req, row)
                break
            if self._paged and req.handoff_rec is not None:
                # A prefill worker's handoff splices through the same
                # paged admission seam (ISSUE 17): fresh blocks for the
                # full reservation, the shipped run scattered byte-exact
                # — never a re-prefill. The gate pre-checked the same
                # reservation arithmetic, so failure is only an
                # allocation race.
                if self._handoff_splice(req, row):
                    took.append((req.rid, "handoff"))
                    continue
                self._paged_requeue(req, row)
                break
            hit = None
            if self._prefix_cache is not None:
                with obs_trace.span("prefix_lookup", "sched",
                                    rid=req.rid) as sp:
                    hit = self._prefix_lookup(req)
                    sp.set(hit=hit is not None)
            if hit is not None:
                entry, suffix_ids = hit
                fit = self._prefix_fit(entry, suffix_ids)
                if fit is not None and self._paged and not \
                        self._paged_reserve(req, fit[3], entry):
                    # The gate pre-checked the FULL (no-aliasing) need,
                    # but a racing entry eviction or a one-grain suffix
                    # overshoot can still lose the allocation: requeue
                    # at the front, never a partial grant.
                    self._paged_requeue(req, row)
                    break
                if fit is not None:
                    obs_journey.event(
                        self._journey_owner, req.rid, "prefix", hit=True,
                        matched=entry.length, entry_tokens=len(entry.ids))
                    if piggy:
                        self._start_suffix_lane(req, row, entry,
                                                suffix_ids, fit)
                        took.append((req.rid, "lane"))
                        continue
                    # SELECTION pin: the entry must survive (and a paged
                    # entry's blocks must stay un-recycled) until this
                    # boundary's suffix admission has read it — the
                    # block-gate's entry reclaim skips pinned entries.
                    entry.pins += 1
                    hits.append((req, row, entry, suffix_ids, fit))
                    continue
            if self._prefix_cache is not None:
                self._prefix_cache.count_miss()
                obs_journey.event(self._journey_owner, req.rid, "prefix",
                                  hit=False)
            if self._paged:
                grain = 2 * SEQ_BUCKET
                s1 = min(((req.prompt_len + grain - 1) // grain) * grain,
                         self.max_len)
                if not self._paged_reserve(req, s1):
                    self._paged_requeue(req, row)
                    break
            if piggy:
                self._start_full_lane(req, row)
                took.append((req.rid, "lane"))
                continue
            if self.prefill_chunk and not bool(self.frozen.all()):
                # Active rows are decoding: chunked admission. The row is
                # reserved (kept frozen) and ONE prefill chunk advances
                # per scheduler step, so a long prompt stalls each decode
                # segment by at most one chunk instead of its full prefill.
                padded, mask, prompt_len = self._prep_request(req)
                row_cache = self._new_row_cache(padded.shape[1])
                self._pending = _PendingAdmission(
                    req, row, padded, prompt_len, row_cache
                )
                took.append((req.rid, "chunk"))
                self._advance_pending()
                break
            wave.append((req, row))
        # Suffix admissions first, grouped into waves by padded shape:
        # round-robin session traffic hits S DIFFERENT heads at one
        # boundary, so the wave stacks per-member entry blocks — batching
        # by entry alone would leave S sequential dispatches.
        groups: Dict[tuple, List[tuple]] = {}
        for h in hits:
            groups.setdefault((h[4][2], h[4][3]), []).append(h)
        for (_, _), members in sorted(groups.items()):
            obs_metrics.SERVE_ADMISSION_WAVE.observe(len(members))
            took += [(m[0].rid, "suffix" if len(members) == 1
                      else "suffix_wave") for m in members]
            if len(members) == 1:
                req, row, entry, suffix_ids, fit = members[0]
                try:
                    pre_admit = self._prefix_admit(entry,
                                                   req.pixel_values,
                                                   suffix_ids, rid=req.rid)
                    if pre_admit is None:  # unreachable: fit pre-checked
                        wave.append((req, row))
                        continue
                    self._prefix_cache.count_hit(entry)
                    (row_cache, row_logits, row_hidden,
                     prompt_len) = pre_admit
                    self._finish_admission(
                        req, row, prompt_len, row_cache, row_logits,
                        row_hidden if self.draft_head is not None
                        else None,
                        prefix_entry=entry, path="suffix",
                    )
                finally:
                    # Selection pin drains once the admission read the
                    # entry — or on the fault path (serve.prefix_copy),
                    # where the engine sweep fails the request.
                    self._drain_entry_pin(entry)
            else:
                try:
                    self._admit_suffix_wave(members)
                except BaseException:
                    for m in members:
                        self._drain_entry_pin(m[2])
                    raise
        if wave:
            took += [(req.rid, "wave" if len(wave) > 1 else "row")
                     for req, _ in wave]
            self._land(self._prefill_members(wave))
        return did_work

    def _take_head(self) -> tuple:
        """Pop the queue's head into the first free row: (req, row). The
        row is reserved NOW (it stays frozen until activation): a fault
        mid-admission (serve.prefix_copy, a prefill error) must leave the
        request somewhere the engine's sweep can fail cleanly instead of
        stranding its waiter."""
        req = self.queue.popleft()
        t_deq = time.perf_counter()
        obs_metrics.SERVE_QUEUE_DEPTH.set(len(self.queue))
        obs_metrics.SERVE_QUEUE_WAIT.observe(t_deq - req.t_submit)
        obs_journey.event(self._journey_owner, req.rid, "queue",
                          t=t_deq, depth=len(self.queue))
        if req.phase == "queued":
            obs_trace.async_end("queued", req.rid)
            obs_trace.async_begin("active", req.rid)
            req.phase = "active"
        row = next(r for r in range(self.max_batch) if self.rows[r] is None)
        self.rows[row] = req
        req.row = row
        return req, row

    def _fill_first(self) -> bool:
        """At least as many rows are free as decode (on the host mirror):
        filling them comes before keeping the few that decode flowing. An
        admission is then staged as soon as it is seen, at the top of a
        step behind whatever is left of the segment in flight, and landed
        in that step, as early as the drained path would have admitted it.
        While most rows decode, staging waits for the end of a step (a
        whole segment to hide behind, one wave for the rows of two
        segments) and a free row for its turn."""
        free = sum(r is None for r in self.rows)
        return free >= self.max_batch - int(self.frozen.sum())

    def _stages_head(self) -> bool:
        """Whether the queue's head is an admission that ``_stage`` hides
        behind a segment in flight: the drained path would run the
        exclusive wave (or its batch-1 form) for it. Not while a chunked
        admission or a lane is open, nor with a budget that makes the
        request a lane or a chunked admission while rows decode; not a
        head that the prefix cache can serve; no head of a paged server
        (its gate reclaims, preempts and reserves blocks of the shared
        pool; spilled and handed-off heads are paged too); not a wave over
        the memory guard's budget. Those keep the drained path."""
        return bool(
            self.pipeline and self.queue and self._pending is None
            and not self._lanes and not self._paged
            and (not (self.prefill_budget > 0 or self.prefill_chunk)
                 or self.frozen.all())
            and not self._mem_over_budget()
            and (self._prefix_cache is None
                 or self._prefix_lookup(self.queue[0]) is None))

    def _stage(self) -> None:
        """The row-independent half of a full-prefill admission, behind
        the segment in flight: pop the queue's heads into free rows as
        ``_admit_queue`` does, then upload, encode, splice, pad and
        dispatch their prefill into a fresh cache (``_prefill_members``),
        and keep the result for ``_admit_queue`` to land once the segment
        is drained. Nothing here waits for the device or touches the
        shared cache, the carry, ``frozen`` or ``n_rem``: a reserved row
        was frozen when the segment in flight was dispatched, so its
        harvest passes over it.

        It takes a power of two of members, the most that free rows, the
        queue and ``_wave_takes`` allow: a wave pads to one, a padded slot
        costs the tower and the prefill of a request, and a row left free
        until the next wave costs its share of a segment or two."""
        free = sum(r is None for r in self.rows)
        if (self._inflight is None or self._staged is not None or not free
                or not self._stages_head()):
            return
        heads: List[tuple] = []  # as ``_wave_takes`` reads a wave: (req, row)
        for req in self.queue:
            if (len(heads) >= free or not self._wave_takes(heads, req)
                    or (heads and self._prefix_cache is not None
                        and self._prefix_lookup(req) is not None)):
                break
            heads.append((req, None))
        if not heads:
            return
        n = 1 << (len(heads).bit_length() - 1)
        with obs_trace.span("admit", "sched", n=n, staged=n) as sp:
            wave = []
            for _ in range(n):
                if self._prefix_cache is not None:
                    self._prefix_cache.count_miss()
                    obs_journey.event(self._journey_owner,
                                      self.queue[0].rid, "prefix", hit=False)
                wave.append(self._take_head())
            sp.set(rids=[req.rid for req, _ in wave],
                   path="wave" if n > 1 else "row")
            self._staged = self._prefill_members(wave)

    def _wave_takes(self, wave: List[tuple], req: _Request) -> bool:
        """Whether one more member keeps the wave's prefill (members padded
        to a power of two, prompts to the widest member's bucket) within
        the decoder's ``wave_tokens``."""
        if not self._wave_tokens:
            return True
        grain = 2 * SEQ_BUCKET
        widest = max([r.prompt_len for r, _ in wave] + [req.prompt_len])
        s1 = min(((widest + grain - 1) // grain) * grain, self.max_len)
        return (1 << len(wave).bit_length()) * s1 <= self._wave_tokens

    def _mem_next_wave_bytes(self) -> int:
        """Predicted device bytes of admitting the queue head(s) that
        COULD land this boundary (one per free row): the grain-rounded
        row-cache block per member, doubled when insert-on-prefill will
        also copy a prefix entry — conservative on purpose (a guard
        that under-predicts is a guard that OOMs)."""
        grain = 2 * SEQ_BUCKET
        free = sum(1 for r in self.rows if r is None)
        if self._paged:
            # Paged repricing (ISSUE 12 satellite): the wave is priced
            # at the BLOCK grain — each head's actual reservation — not
            # as dense rows, and without the insert-on-prefill doubling
            # (paged insert aliases the row's blocks; it copies
            # nothing). The transient admission row-cache is bucket-
            # sized, which the reservation already covers, so the old
            # dense pricing would double-count headroom the pool no
            # longer needs.
            total = 0
            for i, req in enumerate(self.queue):
                if i >= free:
                    break
                total += (self._blocks_needed(req.prompt_len,
                                              req.max_new_tokens)
                          * self._pool.block_bytes)
            return total
        factor = 2 if (self._prefix_cache is not None
                       and self.prefix_insert) else 1
        total = 0
        for i, req in enumerate(self.queue):
            if i >= free:
                break
            bucket = min(((req.prompt_len + grain - 1) // grain) * grain,
                         self.max_len)
            total += factor * bucket * self._kv_pos_bytes
        return total

    def _mem_over_budget(self) -> bool:
        """The guard's arithmetic alone: armed, and the ledger plus the
        next wave's predicted bytes pass capacity - headroom. ``_stage``
        asks it and leaves the deferral, its count and its fault site to
        the drained path's one decision a boundary."""
        return bool(
            self.mem_headroom_bytes and self._mem_capacity and self.queue
            and obs_memory.LEDGER.total() + self._mem_next_wave_bytes()
            > self._mem_capacity - self.mem_headroom_bytes)

    def _mem_guard_defers(self) -> bool:
        """One headroom-guard decision per admission boundary. Deferral
        is pure TIMING — whatever chain a request decodes is unchanged
        (rows are independent in attention), so armed-vs-disarmed runs
        stay byte-identical; ``mem_headroom_bytes == 0`` (the default)
        or an unknown capacity disarms it outright. The guard never
        starves an idle server: with nothing in flight to free bytes,
        deferring would deadlock, so admission proceeds regardless."""
        if not (self.mem_headroom_bytes and self._mem_capacity
                and self.queue):
            return False
        if (self._pending is None and not self._lanes
                and all(r is None for r in self.rows)):
            return False  # nothing in flight will ever free bytes
        try:
            # The guard decision is its own fault site: a trip degrades
            # THIS boundary to guard-off (availability over protection)
            # — admission proceeds, the trip is counted.
            faults.maybe_fail("serve.mem_guard")
            faults.maybe_delay("serve.mem_guard")
        except faults.InjectedFault:
            return False
        if not self._mem_over_budget():
            return False
        predicted = self._mem_next_wave_bytes()
        self.mem_deferrals += 1
        obs_metrics.MEM_GUARD_DEFERRALS.inc()
        obs_trace.instant("mem_guard_defer", cat="mem",
                          predicted_bytes=predicted)
        if obs_journey.enabled():
            # Flight recorder (ISSUE 10): the deferral lands in the
            # timeline of every queue head that COULD have admitted
            # this boundary (the same heads _mem_next_wave_bytes
            # predicted) — their decomposition's defer_s starts here.
            free = sum(1 for r in self.rows if r is None)
            for i, q in enumerate(self.queue):
                if i >= free:
                    break
                obs_journey.event(self._journey_owner, q.rid,
                                  "mem_guard_defer",
                                  predicted_bytes=predicted)
        return True

    def _prep_request(self, req: _Request):
        """Host + encode prep for one admission: CLIP encode, splice, pad
        to the prompt bucket. Returns (padded (1, S1, D), mask, prompt_len).
        submit() validated the fit and max_len is grain-aligned, so the
        bucketed prompt can never outgrow the shared cache."""
        from eventgpt_tpu.data.tokenizer import split_at_event
        from eventgpt_tpu.models.eventchat import _pad_batch, splice_embeddings

        pv = self._upload_pixels([req.pixel_values], [req.rid])
        with obs_trace.span("encode", "admit", n=1, rid=req.rid):
            ev = eventchat.encode_events_batch(self.params, self.cfg, pv,
                                               sharded=self.mesh is not None)
            embeds = [splice_embeddings(
                self.params, self.cfg, split_at_event(req.input_ids), ev[0]
            )]
            padded, mask, lens = _pad_batch(embeds)
            prompt_len = int(lens[0])
            bucket = 2 * SEQ_BUCKET
            s1 = min(((prompt_len + bucket - 1) // bucket) * bucket,
                     self.max_len)
            padded = jnp.pad(padded, ((0, 0), (0, s1 - prompt_len), (0, 0)))
            mask = jnp.pad(mask, ((0, 0), (0, s1 - prompt_len)))
            if self.mesh is not None:
                padded = self._serving.shard_batch_array(padded, self.mesh)
                mask = self._serving.shard_batch_array(mask, self.mesh)
        return padded, mask, prompt_len

    def _upload_pixels(self, pixels: list, rids: list, pad_to: int = 0):
        """The requests' pixel frames, host to device, as one
        (max(len(pixels), pad_to), frames, 3, H, W) batch in the compute
        dtype (zero rows pad), batch-sharded under a mesh."""
        n = len(pixels)
        with obs_trace.span(
                "upload", "admit", n=n,
                bytes=sum(int(getattr(px, "nbytes", 0)) for px in pixels),
                **({"rid": rids[0]} if n == 1 else {"rids": rids})):
            if n == 1:
                pv = jnp.asarray(pixels[0], self._dtype)[None]
            else:
                pv = jnp.stack([jnp.asarray(px, self._dtype)
                                for px in pixels])
            if pad_to > n:
                pv = jnp.concatenate(
                    [pv, jnp.zeros((pad_to - n,) + pv.shape[1:],
                                   self._dtype)])
            if self.mesh is not None:
                pv = self._serving.shard_batch_array(pv, self.mesh)
        return pv

    def _new_row_cache(self, s1: int):
        row_cache = self._dec.init_cache(
            self.cfg.llama, 1, s1, dtype=self._dtype, quant=self.kv_quant
        )
        if self.mesh is not None:
            row_cache = self._serving.shard_kv_cache(
                row_cache, self.cfg.llama, self.mesh
            )
        return row_cache

    def _advance_pending(self) -> None:
        """Run one prefill chunk of the in-flight admission; on the final
        chunk, insert the row into the shared cache and activate it.
        Starvation guard: when no row is actively decoding (nothing to
        stall — chunk-per-step would just serialize the admission against
        no-op segments), drain ALL remaining chunks at once."""
        while self._pending is not None:
            self._advance_pending_one()
            if self._pending is None or not bool(self.frozen.all()):
                return

    def _advance_pending_one(self) -> None:
        p = self._pending
        c = self.prefill_chunk
        start = p.filled
        end = min(start + c, p.prompt_len)
        start_arr = jnp.asarray(start, jnp.int32)
        new_len = jnp.asarray([end], jnp.int32)
        last_idx = jnp.asarray(
            max(0, min(p.prompt_len - 1 - start, c - 1)), jnp.int32
        )
        with obs_trace.span("prefill", "admit", n=1, positions=end - start,
                            rid=p.req.rid):
            if self.mesh is not None:
                from jax.sharding import PartitionSpec as P

                row_sh = jax.tree_util.tree_map(
                    lambda x: x.sharding, p.row_cache
                )
                flat, treedef = jax.tree_util.tree_flatten(row_sh)
                hidden_sh = jax.sharding.NamedSharding(
                    self.mesh, P(None, None))
                fn = _get_sharded_chunk_prefill(
                    self.cfg, c, tuple(flat), treedef, self._row_logits_sh,
                    hidden_sh,
                )
                last, last_hidden, p.row_cache = fn(
                    self.params, p.embeds, p.row_cache, start_arr, new_len,
                    last_idx,
                )
            else:
                last, last_hidden, p.row_cache = _chunk_prefill_jit(
                    self.params, self.cfg, p.embeds, p.row_cache,
                    start_arr, new_len, last_idx, c,
                )
        obs_metrics.SERVE_PREFILL_DISPATCHES.inc(kind="chunk")
        p.filled = end
        p.last_logits = last
        if p.filled >= p.prompt_len:
            self._finish_admission(
                p.req, p.row, p.prompt_len, p.row_cache, last,
                last_hidden if self.draft_head is not None else None,
                path="chunk",
            )
            self._pending = None

    def _prefill_members(self, wave: List[tuple]) -> _Prefilled:
        """First half of a full-prefill admission, of one member or of a
        wave: everything up to the prefill's dispatch, into a cache of the
        wave's own. Waits for nothing on the device."""
        from eventgpt_tpu.models.eventchat import _prefill_jit, _prefill_sharded

        obs_metrics.SERVE_ADMISSION_WAVE.observe(len(wave))
        if len(wave) > 1:
            return self._prefill_wave(wave)
        # Single admission: the batch-1 path (its executables are the
        # ones warmup precompiles). Medusa mode also needs the prompt's
        # last hidden to seed the row's first draft window.
        (req, row), = wave
        padded, mask, prompt_len = self._prep_request(req)
        want_hidden = self.draft_head is not None
        with obs_trace.span("prefill", "admit", n=1,
                            positions=int(padded.shape[1]), rid=req.rid,
                            **self._prefill_counts(int(padded.shape[1]),
                                                   [prompt_len])
                            ) as prefill_span:
            row_cache = self._new_row_cache(padded.shape[1])
            if self.mesh is not None:
                pre = _prefill_sharded(
                    self.params, self.cfg, padded, mask, row_cache,
                    self.mesh, return_hidden=want_hidden,
                )
            else:
                pre = _prefill_jit(
                    self.params, self.cfg, padded, mask, row_cache, True,
                    return_hidden=want_hidden,
                )
        obs_metrics.SERVE_PREFILL_DISPATCHES.inc(kind="full")
        if want_hidden:
            row_logits, row_hidden, row_cache = pre
        else:
            (row_logits, row_cache), row_hidden = pre, None
        return _Prefilled(wave, row_cache, row_logits, row_hidden,
                          [prompt_len], prefill_span)

    def _prefill_counts(self, positions: int, lengths: List[int]) -> dict:
        """What the decoder's module counts of a full prefill of
        ``positions`` over rows of ``lengths``, for its span; nothing while
        tracing is off."""
        if not obs_trace.enabled():
            return {}
        return self._dec.prefill_counts(self.cfg.llama, positions, lengths)

    def _land(self, p: _Prefilled) -> None:
        """Second half: readback, scatter and activation, against settled
        state. A member that left its row since the first half (cancelled
        or past its deadline while staged) is passed over."""
        if len(p.members) > 1:
            self._scatter_wave(p.members, p.cache, p.logits, p.hidden,
                               p.prompt_lens, prefill_span=p.span)
            return
        (req, row), = p.members
        if self.rows[row] is req:
            self._finish_admission(req, row, p.prompt_lens[0], p.cache,
                                   p.logits, p.hidden, prefill_span=p.span)

    def _prefill_wave(self, wave: List[tuple]) -> _Prefilled:
        """BATCHED admission prefill (the tentpole's second half): N
        admissions ready at one dispatch boundary run ONE prefill at a
        common bucket instead of N sequential batch-1 dispatches (the
        r4 batch-16 leg was "bounded by the 16 per-request prefills").
        The ~100 ms per dispatch that made a wave cost ~1/N of the
        sequential path was measured on the r05-era set-up; it predates
        this round and is to be re-measured (ROADMAP S1), as is every
        chunk / ramp / budget default tuned against it. The CLIP encode is
        batched the same way. Members pad to the widest member's prompt
        bucket and to the next power-of-two wave size (log-bounded
        executable count); pad slots scatter to row index ``max_batch``,
        which XLA drops as out of bounds. Chains are unchanged: rows are
        independent in attention, and the per-row kernel is the same one
        ``generate`` already runs batched (bit-exact on the CPU f32
        suite, tests/test_prefix_cache.py)."""
        from eventgpt_tpu.data.tokenizer import split_at_event
        from eventgpt_tpu.models.eventchat import (
            _pad_batch, _prefill_jit, _prefill_sharded, splice_embeddings,
        )

        n = len(wave)
        nb = 1 << (n - 1).bit_length()
        rids = [req.rid for req, _ in wave]
        pv = self._upload_pixels([req.pixel_values for req, _ in wave], rids,
                                 pad_to=nb)
        with obs_trace.span("encode", "admit", n=n, rids=rids):
            ev = eventchat.encode_events_batch(self.params, self.cfg, pv,
                                               sharded=self.mesh is not None)
            embeds = [splice_embeddings(self.params, self.cfg,
                                        split_at_event(req.input_ids), ev[i])
                      for i, (req, _) in enumerate(wave)]
            padded, mask, lens = _pad_batch(embeds)
            prompt_lens = [int(x) for x in lens]
            grain = 2 * SEQ_BUCKET
            s1 = min(((max(prompt_lens) + grain - 1) // grain) * grain,
                     self.max_len)
            padded = jnp.pad(
                padded, ((0, nb - n), (0, s1 - padded.shape[1]), (0, 0)))
            mask = jnp.pad(mask, ((0, nb - n), (0, s1 - mask.shape[1])))
            if nb > n:
                # Pad rows keep ONE real position: their (dropped) garbage
                # KV stays finite instead of feeding an all-masked softmax.
                mask = mask.at[n:, 0].set(True)
            if self.mesh is not None:
                padded = self._serving.shard_batch_array(padded, self.mesh)
                mask = self._serving.shard_batch_array(mask, self.mesh)
        want_hidden = self.draft_head is not None
        # pad slots keep one position (above)
        with obs_trace.span("prefill", "admit", n=n, positions=s1, rids=rids,
                            **self._prefill_counts(
                                s1, prompt_lens + [1] * (nb - n))
                            ) as prefill_span:
            wave_cache = self._dec.init_cache(
                self.cfg.llama, nb, s1, dtype=self._dtype,
                quant=self.kv_quant)
            if self.mesh is not None:
                wave_cache = self._serving.shard_kv_cache(
                    wave_cache, self.cfg.llama, self.mesh)
                pre = _prefill_sharded(
                    self.params, self.cfg, padded, mask, wave_cache,
                    self.mesh, return_hidden=want_hidden,
                )
            else:
                pre = _prefill_jit(
                    self.params, self.cfg, padded, mask, wave_cache, True,
                    return_hidden=want_hidden,
                )
        obs_metrics.SERVE_PREFILL_DISPATCHES.inc(kind="wave")
        if want_hidden:
            wave_logits, wave_hidden, wave_cache = pre
        else:
            (wave_logits, wave_cache), wave_hidden = pre, None
        return _Prefilled(wave, wave_cache, wave_logits, wave_hidden,
                          prompt_lens, prefill_span)

    # egpt-check: harvest -- admission NaN quarantine is a mandated readback of the wave logits before they touch the shared cache
    def _scatter_wave(self, members: List[tuple], wave_cache, wave_logits,
                      wave_hidden, prompt_lens: List[int],
                      entries: Optional[List[_PrefixEntry]] = None,
                      path: str = "wave", prefill_span=None) -> None:
        """Common tail of both admission waves: per-member NaN
        quarantine, insert-on-prefill of new heads, the one-dispatch
        scatter of every surviving row into the shared cache, then row
        activation. ``members`` are (req, row) pairs; quarantined and
        pow2-pad slots keep row index ``max_batch`` (dropped by the
        scatter's out-of-bounds rule). What the prefill's expert layers
        counted comes with the logits' readback and is set on
        ``prefill_span``."""
        with obs_trace.span("scatter", "admit", n=len(members),
                            rids=[req.rid for req, _ in members]):
            n = len(members)
            nb = (wave_cache["k"]["q"] if isinstance(wave_cache["k"], dict)
                  else wave_cache["k"]).shape[1]
            rows = np.full((nb,), self.max_batch, np.int32)  # OOB = dropped
            good = []
            finite = None
            if self.nan_check:
                finite = np.isfinite(_admission_readback(
                    wave_logits, wave_cache, prefill_span)[:n]).all(axis=-1)
            for i, (req, row) in enumerate(members):
                if self.rows[row] is not req:
                    # Left its reserved row while the wave was staged
                    # (cancelled, past its deadline): finished already.
                    continue
                if finite is not None and not finite[i]:
                    # Same per-request quarantine as the batch-1 path: the
                    # poisoned member never touches the shared cache (its
                    # wave slot scatters out of bounds); siblings admit.
                    self.rows[row] = None
                    self.frozen[row] = True
                    self._finish_forced(req, STATUS_NAN)
                    continue
                self._insert_prefix_on_prefill(req, wave_cache, src_row=i)
                rows[i] = row
                good.append((i, req, row))
            rows_arr = jnp.asarray(rows)
            if self._paged:
                wk = wave_cache["k"]
                s1 = (wk["q"] if isinstance(wk, dict) else wk).shape[2]
                oob = self._pool.n_blocks
                n_src = s1 // self._kv_block_size
                dst = np.full((nb, n_src), oob, np.int32)
                bt_rows = np.full((nb, self._nbpr),
                                  serve_blocks.SCRATCH_BLOCK, np.int32)
                for i, req, row in good:
                    # Quarantined/pad slots keep all-OOB rows: their wave KV
                    # never touches the pool (their reservations were freed
                    # by _record_finish before this scatter was built).
                    dst[i] = self._paged_dst_blocks(req, s1)
                    bt_rows[i] = self._paged_bt_row(req)
                    req.kv_bt_written = True
                dst_arr, bt_arr = jnp.asarray(dst), jnp.asarray(bt_rows)
                if self.mesh is not None:
                    rows_arr = self._serving.replicate(rows_arr, self.mesh)
                    dst_arr = self._serving.replicate(dst_arr, self.mesh)
                    bt_arr = self._serving.replicate(bt_arr, self.mesh)
                    admit = _get_sharded_admit_wave_paged(
                        self._cache_flat_sh, self._cache_treedef,
                        self._logits_sh
                    )
                else:
                    admit = _admit_wave_paged_jit
                self.cache, self.logits = admit(
                    self.cache, self.logits, rows_arr, dst_arr, bt_arr,
                    wave_cache["k"], wave_cache["v"], wave_cache["length"],
                    wave_logits,
                )
            else:
                if self.mesh is not None:
                    rows_arr = self._serving.replicate(rows_arr, self.mesh)
                    admit = _get_sharded_admit_wave(
                        self._cache_flat_sh, self._cache_treedef,
                        self._logits_sh
                    )
                else:
                    admit = _admit_wave_jit
                fixed = {name: wave_cache[name]
                         for name in self._fixed_state}
                self.cache, self.logits = admit(
                    self.cache, self.logits, rows_arr, wave_cache["k"],
                    wave_cache["v"], wave_cache["length"], wave_logits,
                    *([fixed] if fixed else []),
                )
            for i, req, row in good:
                row_hidden = (wave_hidden[i:i + 1]
                              if wave_hidden is not None else None)
                obs_journey.event(self._journey_owner, req.rid, "admit",
                                  path=path, row=row)
                self._activate_row(req, row, prompt_lens[i],
                                   wave_logits[i:i + 1], row_hidden,
                                   entries[i] if entries is not None else None)

    def _insert_prefix_on_prefill(self, req, row_cache,
                                  src_row: int = 0) -> None:
        """Insert-on-prefill (the tentpole's population rule): after any
        admission that filled a row cache through the request's whole
        prompt, slice its reusable heads into the prefix cache — the
        TEXT head before the event sentinel (shared across ALL streams)
        and the head THROUGH the event block (keyed to this request's
        stream). The next request repeating a head admits by copy. Repeat
        heads dedupe on the exact ``(ids, pixels_key)`` key, so steady
        traffic pays one trie probe here, not a device copy."""
        pc = self._prefix_cache
        if pc is None or not self.prefix_insert:
            return
        from eventgpt_tpu.constants import EVENT_TOKEN_INDEX

        ids = list(req.input_ids)
        try:
            sent = ids.index(EVENT_TOKEN_INDEX)
        except ValueError:
            return
        heads = []
        if sent >= 1:
            heads.append((tuple(ids[:sent]), None, False, sent))
        if req.pixel_values is not None:
            heads.append((tuple(ids[:sent + 1]),
                          _pixels_key(req.pixel_values), True,
                          sent + self.cfg.num_event_tokens))
        grain = 2 * SEQ_BUCKET
        for hid, pk, has_ev, hlen in heads:
            if hlen + SEQ_BUCKET > self.max_len:
                continue  # no room for any suffix: a match could never admit
            if pc.get(hid, pk) is not None:
                continue  # already cached (the hit path touches its LRU)
            bucket = min(((hlen + grain - 1) // grain) * grain, self.max_len)
            nbytes = bucket * self._kv_pos_bytes
            if pc.budget and nbytes > pc.budget:
                continue  # would be refused: skip the device copy outright
            if self._paged:
                # Paged insert-on-prefill is ZERO-COPY: the entry ALIASES
                # the admitting row's block run over [0, bucket) — one
                # incref, no device slice. Positions < hlen are append-
                # only (never rewritten); the creator's own writes above
                # hlen in the tail block are masked from every consumer
                # (entry readers pin length = hlen), the same pad rule
                # the dense entry snapshot carries.
                nblk = bucket // self._kv_block_size
                run = (req.kv_blocks_aliased + req.kv_blocks_owned)[:nblk]
                if len(run) < nblk:
                    continue  # reservation shorter than the head bucket
                self._pool.incref(run)
                if not pc.insert(_PrefixEntry(
                        ids=hid, pixels_key=pk, has_event=has_ev,
                        kv=None, blocks=run, length=hlen, bucket=bucket,
                        nbytes=nbytes)):
                    self._pool.decref(run)
                continue
            k, v = self._slice_prefix(row_cache, bucket, src_row)
            pc.insert(_PrefixEntry(
                ids=hid, pixels_key=pk, has_event=has_ev,
                kv={"k": k, "v": v}, length=hlen, bucket=bucket,
                nbytes=nbytes,
            ))

    def _slice_prefix(self, cache, bucket: int, src_row: int = 0):
        """(k, v) blocks of cache positions [0, bucket) at batch row
        ``src_row`` — the entry-copy primitive (sharded variant pins the
        block placement, ``parallel/serving.prefix_block_sharding``)."""
        row_arr = jnp.asarray(src_row, jnp.int32)
        if self.mesh is not None:
            quant = isinstance(cache["k"], dict)
            block_sh = self._serving.prefix_block_sharding(
                self.mesh, self.cfg.llama)
            fn = _get_sharded_slice_prefix(bucket, block_sh, quant)
            return fn(cache["k"], cache["v"], row_arr)
        return _slice_prefix_jit(cache["k"], cache["v"], row_arr, bucket)

    # egpt-check: harvest -- admission NaN quarantine reads back the row logits before the row joins the shared cache
    def _finish_admission(self, req, row, prompt_len, row_cache,
                          row_logits, row_hidden=None,
                          prefix_entry=None, path: str = "full",
                          prefill_span=None) -> None:
        """Insert the prefilled row into the shared cache + activate it."""
        with obs_trace.span("scatter", "admit", n=1, rid=req.rid):
            finite = True
            if self.nan_check:
                finite = bool(np.isfinite(_admission_readback(
                    row_logits, row_cache, prefill_span)).all())
            if not finite:
                # Prefill produced non-finite logits: quarantine the REQUEST
                # before it touches the shared cache (the speculative path's
                # only NaN gate — it commits the prefill sample at admission
                # and carries no per-segment logits to check).
                self.rows[row] = None
                self.frozen[row] = True
                self._finish_forced(req, STATUS_NAN)
                return
            self._insert_prefix_on_prefill(req, row_cache)
            if self._paged:
                rk = row_cache["k"]
                s1 = (rk["q"] if isinstance(rk, dict) else rk).shape[2]
                dst = jnp.asarray(self._paged_dst_blocks(req, s1))
                btr = jnp.asarray(self._paged_bt_row(req))
                if self.mesh is not None:
                    dst = self._serving.replicate(dst, self.mesh)
                    btr = self._serving.replicate(btr, self.mesh)
                    admit = _get_sharded_admit_paged(
                        self._cache_flat_sh, self._cache_treedef,
                        self._logits_sh)
                else:
                    admit = _admit_row_paged_jit
                self.cache, self.logits = admit(
                    self.cache, self.logits, row, dst, btr, row_cache,
                    row_logits
                )
                req.kv_bt_written = True
            else:
                if self.mesh is not None:
                    admit = _get_sharded_admit(
                        self._cache_flat_sh, self._cache_treedef,
                        self._logits_sh
                    )
                else:
                    admit = functools.partial(
                        _admit_row_jit, fixed=self._fixed_state)
                self.cache, self.logits = admit(
                    self.cache, self.logits, row, row_cache, row_logits
                )
            obs_journey.event(self._journey_owner, req.rid, "admit",
                              path=path, row=row)
            self._activate_row(req, row, prompt_len, row_logits, row_hidden,
                               prefix_entry)

    def _activate_row(self, req, row, prompt_len, row_logits,
                      row_hidden=None, prefix_entry=None) -> None:
        """Post-insert activation bookkeeping, shared by the batch-1 and
        wave admission paths."""
        self.rows[row] = req
        req.row = row
        if prefix_entry is not None:
            # Refcount pin (ISSUE 4 satellite): the entry must survive
            # LRU pressure while this row decodes from its KV — a hot
            # session's head is the worst possible victim. Drained by
            # _record_finish on ANY terminal path.
            prefix_entry.pins += 1
            req.prefix_entry = prefix_entry
        obs_metrics.SERVE_ACTIVE_ROWS.set(
            sum(r is not None for r in self.rows))
        # Row activation below rewrites frozen/n_rem (and base_pos for
        # speculative rows): the next dispatch re-uploads the host mirror.
        # _admit only runs drained, so the mirror is settled here.
        self._dev_carry = None
        if self.draft_head is not None and self.spec_max > 1:
            from eventgpt_tpu.models import medusa as medusa_mod

            # Seed the row's first draft window from the prompt's last
            # hidden (the heads at that position predict the tokens after
            # the prefill-argmax commit — the _spec_segment carry rule).
            # The FULL max-window buffer is seeded: any bucket a later
            # boundary selects finds its first W-1 columns fresh.
            row_drafts = medusa_mod.medusa_drafts(
                self.params["llama"], self.draft_head, row_hidden,
                self.spec_max - 1,
            )
            self.spec_drafts = self.spec_drafts.at[row].set(row_drafts[0])
            if self.mesh is not None:
                self.spec_drafts = jax.device_put(
                    self.spec_drafts, self._drafts_sh
                )
        if self.speculative:
            self._admit_speculative(req, row, prompt_len, row_logits)
            return
        self.frozen[row] = False
        self.n_rem[row] = req.max_new_tokens

    def _admit_speculative(self, req, row: int, prompt_len: int,
                           row_logits) -> None:
        """Speculative-row bookkeeping: reset + write the row's token-id
        view of the spliced prompt (the bigram-lookup context) and commit
        the prefill token as the first generated token (the
        ``_spec_segment_jit`` invariant: cache length == committed - 1)."""
        from eventgpt_tpu.data.tokenizer import split_at_event
        from eventgpt_tpu.models.eventchat import _spliced_text_ids

        if req.max_new_tokens == 0:
            # Parity with one-shot generate (and the plain server): a zero
            # budget returns zero tokens — skip the prefill-token commit
            # that seeds the speculative invariant.
            req.tokens = []
            self._finish_row(row)
            return
        row_ids = _spliced_text_ids(
            split_at_event(req.input_ids), self.cfg.num_event_tokens,
            self.cfg.llama.max_seq_len,
        )[: self.max_len]
        self._history_append(row_ids)  # prompt text joins the lookup pool
        # Canonical sampler (argmax at T=0) — the same first-token commit
        # rule as _spec_loop_jit.
        import time

        self.key, sub = jax.random.split(self.key)
        t0 = int(sample(row_logits, sub, self.temperature, self.top_p)[0])
        req.t_first = time.perf_counter()
        req.t_last = req.t_first
        self.ids_buf = (
            self.ids_buf.at[row].set(-1)
            .at[row, : len(row_ids)].set(jnp.asarray(row_ids))
            .at[row, prompt_len].set(t0)
        )
        if self.mesh is not None:
            # Scatter chains can drop the batch sharding; re-pin so the next
            # spec segment's pinned input/output shardings stay aliasing.
            self.ids_buf = jax.device_put(self.ids_buf, self._ids_sh)
        self.base_pos[row] = prompt_len + 1
        req.tokens = [t0]
        self.n_rem[row] = req.max_new_tokens - 1
        hit_eos = self.eos_token_id is not None and t0 == self.eos_token_id
        if hit_eos or self.n_rem[row] <= 0:
            self.frozen[row] = True
            self._finish_row(row)
        else:
            self.frozen[row] = False
