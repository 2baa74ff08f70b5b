"""Paged KV block pool (ISSUE 12): allocator properties, lock
discipline, the paged==dense exactness matrix (greedy, int8-KV,
speculative, mixed piggyback segments, chunked admission, pipelined vs
synchronous), prefix-hit block-table aliasing with copy-on-write,
used-token admission under pool pressure, export-drain block accounting,
and the capacity model held byte-exact against the live arena.

The whole point of the layout change is that it is INVISIBLE to chains:
the block-table translation is pure indexing (a gather is a copy), so a
request decoded against the pool commits the same greedy chain as
against the dense cache — exact on the CPU f32 suite, same bar as every
scheduler change before it."""

import threading

import jax
import numpy as np
import pytest

from eventgpt_tpu.config import EventChatConfig
from eventgpt_tpu.models import eventchat
from eventgpt_tpu.obs import memory as obs_memory
from eventgpt_tpu.serve import ContinuousBatcher
from eventgpt_tpu.serve_blocks import (
    SCRATCH_BLOCK, BlockPool, BlockPoolError,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = EventChatConfig.tiny()
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(5))
    return cfg, params


def _pv(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(cfg.num_event_frames, 3, cfg.vision.image_size,
                            cfg.vision.image_size)).astype(np.float32)


def _reqs(cfg):
    return [
        ([1, 5, -200, 9, 9], _pv(cfg, 0), 8),
        ([1, -200, 7, 7, 8, 14], _pv(cfg, 1), 7),
        ([3, -200, 11], _pv(cfg, 2), 9),
    ]


def _run(params, cfg, reqs, **kw):
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None, **kw)
    rids = [srv.submit(ids, pv, b) for ids, pv, b in reqs]
    out = srv.run_until_drained()
    return [out[r] for r in rids], srv


# -- allocator properties ---------------------------------------------------


def test_block_pool_randomized_invariants():
    """Random alloc/incref/decref/cow traffic against a model: refcounts
    never underflow, free + used == usable at every step, a block is
    never simultaneously free and referenced, COW only copies shared
    blocks. The property harness for 'alloc/free/refcount/COW never
    double-free'."""
    rng = np.random.default_rng(7)
    pool = BlockPool(33, 64)
    held = []  # (block, times-referenced-by-us)
    for _ in range(2000):
        op = rng.integers(0, 4)
        if op == 0:
            got = pool.alloc(int(rng.integers(1, 5)))
            if got is not None:
                for b in got:
                    assert b != SCRATCH_BLOCK
                    assert pool.ref(b) == 1
                    held.append(b)
        elif op == 1 and held:
            b = held[rng.integers(len(held))]
            pool.incref([b])
            held.append(b)
        elif op == 2 and held:
            i = int(rng.integers(len(held)))
            b = held.pop(i)
            pool.decref([b])
        elif op == 3 and held:
            b = held[int(rng.integers(len(held)))]
            shared = pool.ref(b) > 1
            nb = pool.cow(b)
            if nb is None:
                continue
            if shared:
                assert nb != b and pool.ref(nb) == 1
                held[held.index(b)] = nb
            else:
                assert nb == b  # exclusive: no copy
        # Global invariants after every operation.
        st = pool.stats()
        assert st["free_blocks"] + st["used_blocks"] == st["usable_blocks"]
        assert st["used_blocks"] == len(set(held))
        for b in set(held):
            assert pool.ref(b) == held.count(b)
    # Full teardown: every reference drains, the pool refills exactly.
    for b in list(held):
        pool.decref([b])
    assert pool.free_blocks() == pool.usable


def test_block_pool_misuse_raises():
    pool = BlockPool(5, 64)
    blocks = pool.alloc(2)
    pool.decref([blocks[0]])
    with pytest.raises(BlockPoolError):  # double free
        pool.decref([blocks[0]])
    with pytest.raises(BlockPoolError):  # scratch is not refcounted
        pool.incref([SCRATCH_BLOCK])
    with pytest.raises(BlockPoolError):  # out of range
        pool.decref([99])
    assert pool.alloc(100) is None  # over-ask: refusal, not partial grant
    assert pool.stats()["alloc_failures"] == 1


def test_block_pool_cow_shares_until_divergence():
    pool = BlockPool(6, 64)
    run = pool.alloc(2)
    pool.incref(run)  # second owner (the aliasing row)
    assert [pool.ref(b) for b in run] == [2, 2]
    private = pool.cow(run[1])  # writer diverges at block 1
    assert private != run[1] and pool.ref(private) == 1
    assert pool.ref(run[1]) == 1  # one ref traded away
    assert pool.stats()["cow_copies"] == 1
    # Exclusive block: cow is the identity, no copy counted.
    assert pool.cow(private) == private
    assert pool.stats()["cow_copies"] == 1


class _SpyLock:
    """Records free-list length at every acquire/release — proves
    alloc/free mutate INSIDE the pool's critical section (the
    ``_GUARDED_BY`` contract egpt-check asserts statically)."""

    def __init__(self, pool):
        self._pool = pool
        self._real = threading.Lock()
        self.events = []

    def __enter__(self):
        self._real.acquire()
        self.events.append(("enter", len(self._pool._free)))
        return self

    def __exit__(self, *exc):
        self.events.append(("exit", len(self._pool._free)))
        self._real.release()
        return False


def test_block_pool_alloc_free_mutate_under_the_lock():
    pool = BlockPool(9, 64)
    spy = _SpyLock(pool)
    pool._lock = spy
    try:
        got = pool.alloc(3)
        pool.decref(got)
    finally:
        pool._lock = threading.Lock()
    # First acquire saw the untouched free list; the alloc's release saw
    # exactly 3 fewer; the decref round-trips back — every mutation
    # landed between an enter and its exit.
    assert spy.events[0] == ("enter", 8)
    assert ("exit", 5) in spy.events
    assert spy.events[-1] == ("exit", 8)


# -- paged == dense exactness matrix ----------------------------------------


@pytest.mark.parametrize("kw", [
    dict(kv_quant=True),
    dict(speculative=4),
    dict(prefill_budget=8),          # mixed piggyback segments
    dict(prefill_chunk=64),          # chunked admission
    dict(pipeline=False),            # synchronous escape hatch
], ids=["int8_kv", "speculative", "mixed_lanes", "chunked_prefill",
        "no_pipeline"])
def test_paged_equals_dense_matrix(tiny, kw):
    cfg, params = tiny
    reqs = _reqs(cfg)
    dense, _ = _run(params, cfg, reqs, **kw)
    paged, _ = _run(params, cfg, reqs, kv_layout="paged", **kw)
    assert dense == paged


# -- prefix sharing: aliasing + copy-on-write -------------------------------


def _head_reqs(cfg, n_head=60):
    """Two sessions over ONE event stream whose shared head spans a full
    block (head length n_head + num_event_tokens > SEQ_BUCKET), so the
    second admission aliases at least one whole pool block and COW-copies
    the divergent boundary block."""
    pv = _pv(cfg, 3)
    head = [1] + [7] * (n_head - 1) + [-200]
    return [(head + [9, 9], pv, 8), (head + [11, 4, 5], pv, 8)], pv


def test_paged_prefix_hit_aliases_then_diverges(tiny):
    """The COW exactness test: session 1 populates the entry
    (insert-on-prefill aliases its blocks zero-copy), session 2 admits
    through the hit path — full blocks below the divergence point are
    SHARED (refcount > 1, no new allocation for them), the divergent
    boundary block is re-created privately (a counted COW copy) — and
    both chains equal the cold dense run."""
    cfg, params = tiny
    reqs, pv = _head_reqs(cfg)

    def seq(**kw):
        srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256,
                                chunk=4, eos_token_id=None, **kw)
        outs = []
        for ids, p, b in reqs:  # sequential: entry exists for request 2
            rid = srv.submit(ids, p, b)
            outs.append(srv.run_until_drained()[rid])
        return outs, srv

    dense, _ = seq()
    paged, srv = seq(kv_layout="paged")
    assert dense == paged
    pool = srv._pool
    st = pool.stats()
    hlen = reqs[0][0].index(-200) + 1 + cfg.num_event_tokens - 1
    assert hlen > pool.block_size  # the head really spans a block
    # The hit admission aliased the entry's full block(s) and COW-copied
    # the mid-block divergence.
    assert st["cow_copies"] >= 1
    entries = srv._prefix_cache.entries()
    assert entries and all(e.blocks for e in entries)
    # Shared full blocks carry the entry's ref after both rows finished.
    ev_entry = max(entries, key=lambda e: e.length)
    assert all(pool.ref(b) >= 1 for b in ev_entry.blocks)


def test_paged_suffix_lane_over_entry_matches_dense(tiny):
    """Prefix hit under piggyback admission (the lane seed reads the
    entry through the pool gather) — both layouts, int8-KV, same
    chains."""
    cfg, params = tiny
    reqs, _ = _head_reqs(cfg)

    def seq(**kw):
        srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256,
                                chunk=4, eos_token_id=None, kv_quant=True,
                                prefill_budget=8, **kw)
        outs = []
        for ids, p, b in reqs:
            rid = srv.submit(ids, p, b)
            outs.append(srv.run_until_drained()[rid])
        return outs

    assert seq() == seq(kv_layout="paged")


# -- used-token admission ---------------------------------------------------


def test_paged_pool_pressure_defers_then_completes(tiny):
    """A pool too small for two concurrent reservations serializes
    admission through the block gate (deferrals counted, decode keeps
    flowing) — and every chain still matches the unconstrained dense
    run. This is the used-token admission the dense layout cannot
    express: the gate reads FREE BLOCKS, not free rows."""
    cfg, params = tiny
    reqs = _reqs(cfg)
    dense, _ = _run(params, cfg, reqs)
    paged, srv = _run(params, cfg, reqs, kv_layout="paged",
                      kv_pool_blocks=4, prefix_cache=False)
    assert dense == paged
    assert srv.block_deferrals > 0
    assert srv._pool.free_blocks() == srv._pool.usable  # all drained


def test_paged_submit_rejects_never_fitting_request(tiny):
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            kv_layout="paged", kv_pool_blocks=4)
    # Fits max_len (111 + 100 + 1 <= 256) but needs 4 blocks against a
    # 3-usable pool: refused loudly at submit, never queued to defer
    # forever.
    with pytest.raises(ValueError, match="KV blocks"):
        srv.submit([1, -200] + [7] * 100, _pv(cfg), 100)


def test_reset_prefix_cache_releases_paged_blocks(tiny):
    """A cache reset goes through ``reset_prefix_cache()``: it releases
    every entry's block run back to the pool (swapping the cache by hand
    orphaned them — the pool drained monotonically from reset to reset
    until the block gate livelocked, caught live by a workload replay)."""
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None, kv_layout="paged")
    for seed in range(3):
        rid = srv.submit([1, 5, -200, 9, 9], _pv(cfg, seed), 6)
        srv.run_until_drained()
        assert srv._pool.used_blocks() > 0  # entries hold blocks
        srv.reset_prefix_cache()
        assert srv._pool.used_blocks() == 0, f"leg {seed} leaked blocks"
        assert srv._prefix_cache.n_entries == 0


def test_paged_gate_reclaims_unpinned_prefix_entries(tiny):
    """Entry eviction unifies with row allocation: when the free list
    cannot cover the queue head, the gate evicts LRU unpinned entries
    (their pinned runs are the only idle pool capacity) instead of
    deadlocking an idle server."""
    cfg, params = tiny
    reqs, _ = _head_reqs(cfg)
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=256, chunk=4,
                            eos_token_id=None, kv_layout="paged",
                            kv_pool_blocks=4)
    ids, pv, b = reqs[0]
    rid = srv.submit(ids, pv, b)
    srv.run_until_drained()
    assert srv._prefix_cache.n_entries > 0  # entries hold pool blocks
    held = srv._pool.used_blocks()
    assert held > 0
    # A fresh unrelated request needs more than free_blocks: the gate
    # must reclaim entries and admit rather than defer forever.
    rid2 = srv.submit([3, -200, 11], _pv(cfg, 9), 9)
    out = srv.run_until_drained()
    assert len(out[rid2]) == 9
    assert srv._prefix_cache.evictions >= 1


# -- export / drain ---------------------------------------------------------


def test_export_requests_frees_blocks_exactly(tiny):
    """The fleet-drain seam: exporting mid-flight returns every
    unfinished request's reservation to the pool exactly (used-block
    delta == the blocks those requests held) and resets their tables to
    scratch; re-submission elsewhere reproduces the dense chains."""
    cfg, params = tiny
    reqs = _reqs(cfg)
    dense, _ = _run(params, cfg, reqs)
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None, kv_layout="paged",
                            prefix_cache=False)
    rids = [srv.submit(ids, pv, b) for ids, pv, b in reqs]
    srv.step()  # two admissions + one segment in flight
    held = sum(len(r.kv_blocks_owned) + len(r.kv_blocks_aliased)
               for r in srv.rows if r is not None)
    assert held > 0
    before = srv._pool.used_blocks()
    recs = srv.export_requests()
    freed = before - srv._pool.used_blocks()
    # Everything unfinished freed its exact reservation (finished rows —
    # if the drain completed one — freed theirs at finish already).
    assert srv._pool.used_blocks() == 0
    assert freed <= held and freed >= 0
    assert bool(np.all(np.asarray(srv.cache["bt"]) == 0))
    # The moved requests re-decode byte-identically on a second server.
    srv2 = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                             eos_token_id=None, kv_layout="paged")
    rid_map = {}
    for rec in recs:
        rid_map[rec["rid"]] = srv2.submit(
            rec["input_ids"], rec["pixel_values"], rec["max_new_tokens"])
    out2 = srv2.run_until_drained()
    partial = {r: srv.finished.get(r) for r in rids}
    for old_rid, new_rid in rid_map.items():
        want = dense[rids.index(old_rid)]
        assert out2[new_rid] == want
    # Requests the drain finished on srv match too.
    for i, rid in enumerate(rids):
        if partial[rid] is not None:
            assert partial[rid] == dense[i]


# -- capacity model / ledger ------------------------------------------------


def test_paged_estimate_byte_exact_against_live_pool(tiny):
    """``MemoryLedger.estimate()`` in block-pool terms: the kv_pool and
    kv_block_table components equal the live arena's real nbytes, and
    the ledger registered exactly those numbers under the new component
    split — the refactor's acceptance harness."""
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=8,
                            kv_layout="paged")
    est = srv.memory_estimate()["components"]
    assert "kv_cache" not in est
    assert est["kv_pool"] == obs_memory.params_bytes(
        {"k": srv.cache["k"], "v": srv.cache["v"]})
    assert est["kv_block_table"] == (srv.cache["bt"].nbytes
                                     + srv.cache["length"].nbytes)
    own = obs_memory.LEDGER.snapshot(srv._mem_owner)
    assert own["kv_pool"] == est["kv_pool"]
    assert own["kv_block_table"] == est["kv_block_table"]
    # int8 arena: payload halves + scale planes, still byte-exact.
    srv8 = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=8,
                             kv_layout="paged", kv_quant=True)
    est8 = srv8.memory_estimate()["components"]
    assert est8["kv_pool"] == obs_memory.params_bytes(
        {"k": srv8.cache["k"], "v": srv8.cache["v"]})
    assert est8["kv_pool"] < est["kv_pool"]
    # A capped pool prices below the dense-equivalent default: the
    # memory the paged layout exists to recover.
    capped = obs_memory.estimate(
        cfg, max_batch=2, max_len=256, kv_layout="paged",
        kv_pool_blocks=5)
    assert capped["components"]["kv_pool"] < est["kv_pool"]


@pytest.mark.slow  # heavyweight mesh tier, like tests/test_sharded_serve.py
def test_paged_sharded_matches_dense_single_chip(tiny):
    """Sharded leg of the exactness matrix: a paged batcher whose arena
    lives on the serving mesh (blocks replicated over the batch axes,
    KV heads over ``model``) commits the same chains as the single-chip
    dense server."""
    from eventgpt_tpu.config import MeshConfig
    from eventgpt_tpu.parallel import make_mesh
    from eventgpt_tpu.parallel.serving import shard_params_for_serving

    cfg, params = tiny
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, context=1, model=2))
    sharded = shard_params_for_serving(params, cfg, mesh)
    reqs = _reqs(cfg)
    dense, _ = _run(params, cfg, reqs)
    srv = ContinuousBatcher(sharded, cfg, max_batch=2, max_len=256,
                            chunk=4, eos_token_id=None, mesh=mesh,
                            kv_layout="paged")
    rids = [srv.submit(ids, pv, b) for ids, pv, b in reqs]
    out = srv.run_until_drained()
    assert [out[r] for r in rids] == dense


def test_paged_warmup_leaves_pool_untouched(tiny):
    """Warmup's dead admission dispatches ride the OOB sentinel: the
    executables compile, the pool allocates nothing, and the first real
    request decodes the dense chain."""
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None, kv_layout="paged")
    srv.warmup(prompt_lens=[16])
    assert srv._pool.used_blocks() == 0
    reqs = _reqs(cfg)
    dense, _ = _run(params, cfg, reqs)
    rid = srv.submit(*reqs[0])
    assert srv.run_until_drained()[rid] == dense[0]
