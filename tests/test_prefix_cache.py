"""Prefix-KV cache (ISSUE 4 tentpole): the token-id trie that replaced
the single ``set_prefix`` slot — auto-populated on admission prefill,
longest-prefix matched at admission, refcount-pinned while rows decode
from an entry, LRU-evicted under an HBM byte budget — plus the batched
admission prefill (one dispatch per wave of full-prefill admissions).

Fast tier on purpose: the exactness contract (cache-on == cache-off ==
one-shot ``generate``, byte-identical) and the eviction/pinning/
wrong-stream safety rules must run on every iteration, not only in slow
e2e sweeps. The heavier config matrix (speculative / Medusa / int8-KV /
pipelined × cache-on/off) lives in ``tests/test_serve.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventgpt_tpu.config import EventChatConfig
from eventgpt_tpu.models import eventchat
from eventgpt_tpu.obs import metrics as obs_metrics
from eventgpt_tpu.serve import ContinuousBatcher, PrefixCache, _pixels_key


@pytest.fixture(scope="module")
def tiny():
    cfg = EventChatConfig.tiny()
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(5))
    return cfg, params


def _pv(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(cfg.num_event_frames, 3, cfg.vision.image_size,
                            cfg.vision.image_size)).astype(np.float32)


def _oneshot(params, cfg, ids, pv, budget):
    return eventchat.generate(
        params, cfg, [ids], jnp.asarray(pv)[None], max_new_tokens=budget,
        temperature=0.0, eos_token_id=None,
    )[0]


def _srv(params, cfg, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 256)
    kw.setdefault("chunk", 4)
    kw.setdefault("eos_token_id", None)
    return ContinuousBatcher(params, cfg, **kw)


def test_insert_on_prefill_populates_and_hits(tiny):
    """A full admission prefill inserts the prompt's text head AND its
    event-block head; a later same-session request admits from the event
    entry (suffix-only prefill) with a byte-identical chain."""
    cfg, params = tiny
    srv = _srv(params, cfg)
    ids, pv = [1, 5, -200, 9, 9], _pv(cfg, 0)
    a = srv.submit(ids, pv, 6)
    out_a = srv.run_until_drained()
    st = srv.prefix_cache_stats()
    assert st["enabled"] and st["n_entries"] == 2  # text head + event head
    kinds = {(e["has_event"], e["ids_len"]) for e in st["entries"]}
    assert kinds == {(False, 2), (True, 3)}
    b = srv.submit(ids, pv, 6)
    out_b = srv.run_until_drained()
    assert srv._prefix_cache.hits == 1
    want = _oneshot(params, cfg, ids, pv, 6)
    assert out_a[a] == want and out_b[b] == want


def test_wrong_stream_never_hits_event_entry(tiny):
    """ISSUE 4 non-negotiable: same prompt text, different pixels must
    never read an event-block entry's KV. It MAY hit the (stream-free)
    text head; the lookup result proves which entry served it."""
    cfg, params = tiny
    srv = _srv(params, cfg)
    pv_a, pv_b = _pv(cfg, 4), _pv(cfg, 7)
    head = [1, 5, -200, 7]
    srv.set_prefix(head, pixel_values=pv_a)  # event entry only (no split)
    ids = head + [9, 9]

    class Req:
        input_ids = ids
        pixel_values = pv_b

    assert srv._prefix_lookup(Req) is None  # wrong stream, no text entry
    Req.pixel_values = pv_a
    entry, suffix = srv._prefix_lookup(Req)
    assert entry.has_event and suffix == [9, 9]
    Req.pixel_values = None                  # session traffic: inherits
    entry, _ = srv._prefix_lookup(Req)
    assert entry.has_event
    # Served end to end: both streams get their own exact chains.
    same = srv.submit(ids, pv_a, 6)
    other = srv.submit(ids, pv_b, 6)
    out = srv.run_until_drained()
    assert out[same] == _oneshot(params, cfg, ids, pv_a, 6)
    assert out[other] == _oneshot(params, cfg, ids, pv_b, 6)
    assert out[same] != out[other]
    # After the full prefill, the wrong stream has its OWN event entry —
    # and the next lookup for pv_b picks it, never pv_a's.
    Req.pixel_values = pv_b
    hit = srv._prefix_lookup(Req)
    assert hit is not None and hit[0].pixels_key == _pixels_key(pv_b)


def test_longest_prefix_match_prefers_deeper_entry(tiny):
    """With both the text head and the through-event head cached, a
    matching session request takes the DEEPEST entry (the event head —
    it also skips the CLIP encode)."""
    cfg, params = tiny
    srv = _srv(params, cfg)
    ids, pv = [1, 5, -200, 9, 9], _pv(cfg, 0)
    rid = srv.submit(ids, pv, 5)
    srv.run_until_drained()

    class Req:
        input_ids = ids
        pixel_values = pv

    entry, suffix = srv._prefix_lookup(Req)
    assert entry.has_event and len(entry.ids) == 3 and suffix == [9, 9]


def test_lru_eviction_under_byte_budget(tiny):
    """Inserts beyond the budget evict the least-recently-used unpinned
    entry; the byte accounting tracks; an entry larger than the whole
    budget is refused loudly at set_prefix."""
    cfg, params = tiny
    probe = _srv(params, cfg)
    entry_bytes = 128 * probe._kv_pos_bytes  # one bucket-128 text entry
    srv = _srv(params, cfg, prefix_cache_bytes=2 * entry_bytes)
    srv.set_prefix([1, 5, 7])
    srv.set_prefix([2, 6, 8])
    pc = srv._prefix_cache
    assert pc.n_entries == 2 and pc.bytes == 2 * entry_bytes
    srv.set_prefix([3, 9, 4])  # evicts the oldest ([1, 5, 7])
    assert pc.n_entries == 2 and pc.bytes <= pc.budget
    assert pc.evictions == 1
    assert pc.get((1, 5, 7), None) is None
    assert pc.get((2, 6, 8), None) is not None
    assert pc.get((3, 9, 4), None) is not None
    # A single entry above the whole budget is refused, not silently kept.
    tight = _srv(params, cfg, prefix_cache_bytes=entry_bytes // 2)
    with pytest.raises(ValueError, match="budget"):
        tight.set_prefix([1, 5, 7])


def test_pin_blocks_eviction_while_row_decodes(tiny):
    """ISSUE 4 satellite (the replacement hazard): evicting under
    pressure while a row decodes from an entry must not yank that entry
    — the refcount pin keeps it resident until its last row finishes,
    and the decoded chain stays byte-identical."""
    cfg, params = tiny
    probe = _srv(params, cfg)
    entry_bytes = 128 * probe._kv_pos_bytes
    srv = _srv(params, cfg, max_batch=1, chunk=2,
               prefix_cache_bytes=entry_bytes, prefix_insert=False)
    head, pv = [1, 5, -200, 7], _pv(cfg, 1)
    srv.set_prefix(head, pixel_values=pv)
    pc = srv._prefix_cache
    ids = head + [9, 9]
    rid = srv.submit(ids, pv, 10)
    srv.step()  # admit from the entry (pin), decode one 2-token segment
    entry = pc.get(tuple(head), _pixels_key(pv))
    assert entry is not None and entry.pins == 1
    # Pressure: a second insert overflows the 1-entry budget. The pinned
    # entry must survive; the eviction sweep takes the only unpinned
    # candidate (the newcomer itself).
    srv.set_prefix([2, 6, 8])
    assert pc.get(tuple(head), _pixels_key(pv)) is entry
    assert pc.evictions == 1 and pc.n_entries == 1
    out = srv.run_until_drained()
    assert entry.pins == 0  # drained at row finish
    assert out[rid] == _oneshot(params, cfg, ids, pv, 10)
    # Unpinned now: the next insert under pressure evicts it.
    srv.set_prefix([3, 9, 4])
    assert pc.get(tuple(head), _pixels_key(pv)) is None


def test_wave_batched_admission_exact_and_counted(tiny):
    """N admissions ready at one dispatch boundary run as ONE batched
    prefill (N -> 1 dispatches, the admission-wave histogram observes
    N), and every member's chain equals one-shot generate."""
    cfg, params = tiny
    reqs = [
        ([1, 5, -200, 9, 9], _pv(cfg, 0), 6),
        ([1, -200, 7, 7, 8, 14], _pv(cfg, 1), 5),
        ([3, -200, 11], _pv(cfg, 2), 7),
    ]
    wave0 = obs_metrics.SERVE_PREFILL_DISPATCHES.value(kind="wave")
    full0 = obs_metrics.SERVE_PREFILL_DISPATCHES.value(kind="full")
    obs_on = obs_metrics.enabled()
    srv = _srv(params, cfg, max_batch=4)
    rids = [srv.submit(i, p, b) for i, p, b in reqs]  # all queued pre-step
    out = srv.run_until_drained()
    for rid, (i, p, b) in zip(rids, reqs):
        assert out[rid] == _oneshot(params, cfg, i, p, b), rid
    if obs_on:
        assert obs_metrics.SERVE_PREFILL_DISPATCHES.value(kind="wave") \
            == wave0 + 1
        assert obs_metrics.SERVE_PREFILL_DISPATCHES.value(kind="full") \
            == full0  # zero sequential batch-1 prefills


def test_wave_quarantines_nan_member_and_admits_siblings(tiny):
    """A poisoned member of a batched wave is quarantined per-request
    (its slot scatters out of bounds, never touching the shared cache);
    its siblings admit from the same dispatch and decode exactly."""
    cfg, params = tiny
    bad = _pv(cfg, 0).copy()
    bad[:] = np.nan
    reqs = [
        ([1, 5, -200, 9, 9], _pv(cfg, 1), 6),
        ([1, -200, 7, 7], bad, 6),
        ([3, -200, 11], _pv(cfg, 2), 5),
    ]
    srv = _srv(params, cfg, max_batch=4)
    rids = [srv.submit(i, p, b) for i, p, b in reqs]
    out = srv.run_until_drained()
    assert out[rids[1]] == [] \
        and srv.finish_status[rids[1]] == "nan_quarantined"
    assert out[rids[0]] == _oneshot(params, cfg, reqs[0][0], reqs[0][1], 6)
    assert out[rids[2]] == _oneshot(params, cfg, reqs[2][0], reqs[2][1], 5)


def test_wave_mixed_prompt_buckets(tiny):
    """Members whose own prompt buckets differ pad to the widest bucket;
    chains stay byte-identical to one-shot (the cross-bucket masked
    prefill is bit-stable on the CPU f32 suite)."""
    cfg, params = tiny
    long_text = [1] + [7] * 130  # prompt_len > 128 -> bucket 256
    reqs = [
        (long_text + [-200, 9], _pv(cfg, 0), 5),
        ([3, -200, 11], _pv(cfg, 1), 5),     # bucket 128 member
    ]
    srv = _srv(params, cfg, max_batch=4, max_len=512)
    rids = [srv.submit(i, p, b) for i, p, b in reqs]
    out = srv.run_until_drained()
    for rid, (i, p, b) in zip(rids, reqs):
        assert out[rid] == _oneshot(params, cfg, i, p, b), rid


def test_disabled_cache_and_insert_off_modes(tiny):
    """prefix_cache=False: set_prefix has nowhere to insert (loud), and
    serving full-prefills every request. prefix_insert=False keeps the
    operator-entry path but never auto-populates (the r5 single-slot
    behavior)."""
    cfg, params = tiny
    off = _srv(params, cfg, prefix_cache=False)
    with pytest.raises(RuntimeError, match="disabled"):
        off.set_prefix([1, 5, 7])
    ids, pv = [1, 5, -200, 9], _pv(cfg, 0)
    rid = off.submit(ids, pv, 5)
    assert off.run_until_drained()[rid] == _oneshot(params, cfg, ids, pv, 5)
    noins = _srv(params, cfg, prefix_insert=False)
    rid = noins.submit(ids, pv, 5)
    noins.run_until_drained()
    assert noins.prefix_cache_stats()["n_entries"] == 0


def test_trie_lookup_rules_standalone():
    """PrefixCache unit rules, no model: proper-prefix only, sentinel on
    the correct side, wrong-stream exclusion, longest match, LRU tick."""
    from eventgpt_tpu.serve import _PrefixEntry

    pc = PrefixCache()
    text = _PrefixEntry(ids=(1, 5), pixels_key=None, has_event=False,
                        kv={}, length=2, bucket=128, nbytes=10)
    ev_a = _PrefixEntry(ids=(1, 5, -200), pixels_key=b"A", has_event=True,
                        kv={}, length=12, bucket=128, nbytes=10)
    ev_b = _PrefixEntry(ids=(1, 5, -200), pixels_key=b"B", has_event=True,
                        kv={}, length=12, bucket=128, nbytes=10)
    for e in (text, ev_a, ev_b):
        assert pc.insert(e)
    ids = [1, 5, -200, 9]
    assert pc.lookup(ids, b"A") is ev_a          # deepest, right stream
    assert pc.lookup(ids, b"B") is ev_b
    assert pc.lookup(ids, b"C") is text          # wrong stream -> text head
    assert pc.lookup(ids, None) in (ev_a, ev_b)  # session traffic
    assert pc.lookup([1, 5, -200], b"A") is text  # event entry not proper
    assert pc.lookup([1, 5], None) is None       # text entry not proper
    assert pc.lookup([2, 5, -200, 9], b"A") is None
    # Text entry invalid when the sentinel is NOT in the suffix.
    assert pc.lookup([1, 5, 9, 9], None) is None
    # Replacement at the same key detaches the old entry.
    ev_a2 = _PrefixEntry(ids=(1, 5, -200), pixels_key=b"A", has_event=True,
                         kv={}, length=12, bucket=128, nbytes=10)
    assert pc.insert(ev_a2)
    assert pc.n_entries == 3 and pc.lookup(ids, b"A") is ev_a2
