"""Continuous-batching server: equivalence with one-shot generate.

Rows are independent in attention (per-row lengths/positions/masks), so a
request decoded inside the shared batch must commit the same greedy chain
as ``eventchat.generate`` run alone — exact on the CPU f32 suite.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventgpt_tpu.config import EventChatConfig
from eventgpt_tpu.models import eventchat
from eventgpt_tpu.serve import ContinuousBatcher

pytestmark = pytest.mark.slow  # heavyweight e2e tier (-m 'not slow' to skip)

EOS = 2


@pytest.fixture(scope="module")
def tiny():
    cfg = EventChatConfig.tiny()
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(5))
    return cfg, params


def _pv(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(cfg.num_event_frames, 3, cfg.vision.image_size,
                            cfg.vision.image_size)).astype(np.float32)


def _oneshot(params, cfg, ids, pv, budget, eos=None):
    return eventchat.generate(
        params, cfg, [ids], jnp.asarray(pv)[None], max_new_tokens=budget,
        temperature=0.0, eos_token_id=eos,
    )[0]


def test_batched_equals_sequential_generate(tiny):
    cfg, params = tiny
    reqs = [
        ([1, 5, -200, 9, 9], _pv(cfg, 0), 10),
        ([1, -200, 7, 7, 8, 14], _pv(cfg, 1), 7),
        ([3, -200, 11], _pv(cfg, 2), 12),
    ]
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None)
    rids = [srv.submit(ids, pv, budget) for ids, pv, budget in reqs]
    out = srv.run_until_drained()
    assert sorted(out) == sorted(rids)
    for rid, (ids, pv, budget) in zip(rids, reqs):
        want = _oneshot(params, cfg, ids, pv, budget)
        assert out[rid] == want, f"request {rid}"
        assert len(out[rid]) == budget


def test_midflight_admission_and_row_reuse(tiny):
    """Second wave of requests joins while the first is mid-decode; rows
    recycle; per-request chains still match one-shot generate."""
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=3,
                            eos_token_id=None)
    first = [srv.submit([1, 5, -200, 9], _pv(cfg, 0), 9),
             srv.submit([1, -200, 7, 7], _pv(cfg, 1), 9)]
    srv.step()  # both admitted, one 3-token segment decoded
    late = srv.submit([3, -200, 11, 4], _pv(cfg, 2), 6)
    out = srv.run_until_drained()
    assert sorted(out) == sorted(first + [late])
    for rid, (ids, pv, budget) in zip(
        first + [late],
        [([1, 5, -200, 9], _pv(cfg, 0), 9),
         ([1, -200, 7, 7], _pv(cfg, 1), 9),
         ([3, -200, 11, 4], _pv(cfg, 2), 6)],
    ):
        assert out[rid] == _oneshot(params, cfg, ids, pv, budget)


def test_eos_stops_row_early(tiny):
    cfg, params = tiny
    ids, pv = [1, 5, -200, 9, 9], _pv(cfg, 0)
    full = _oneshot(params, cfg, ids, pv, 12)
    eos = full[4]
    want = _oneshot(params, cfg, ids, pv, 12, eos=eos)
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=5,
                            eos_token_id=eos)
    rid = srv.submit(ids, pv, 12)
    out = srv.run_until_drained()
    assert out[rid] == want
    assert len(out[rid]) < 12


def test_oversized_request_rejected_at_submit(tiny):
    """Rejection happens at submit() so one bad request cannot tear down a
    draining loop or strand queued/in-flight requests."""
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=128, chunk=4)
    good = srv.submit([1, -200, 5], _pv(cfg), 4)
    with pytest.raises(ValueError, match="exceeds server max_len"):
        srv.submit([1, -200, 5], _pv(cfg), 4096)
    out = srv.run_until_drained()  # the good request still completes
    assert list(out) == [good] and len(out[good]) == 4


def test_off_grain_max_len_rounds_up(tiny):
    """max_len off the 128-token bucket grain is rounded up, so a bucketed
    prompt row can never outgrow the shared cache (trace-time crash)."""
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=200, chunk=4,
                            eos_token_id=None)
    assert srv.max_len == 256
    ids, pv = [1, 5, -200, 9], _pv(cfg, 3)
    rid = srv.submit(ids, pv, 5)
    out = srv.run_until_drained()
    assert out[rid] == _oneshot(params, cfg, ids, pv, 5)


def test_missing_sentinel_rejected_at_submit(tiny):
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=128)
    with pytest.raises(ValueError, match="exactly one"):
        srv.submit([1, 5, 9], _pv(cfg), 4)
    with pytest.raises(ValueError, match="exactly one"):
        srv.submit([1, -200, 5, -200], _pv(cfg), 4)


def test_kv_quant_server_equals_kv_quant_generate(tiny):
    cfg, params = tiny
    ids, pv = [1, 5, -200, 9], _pv(cfg, 4)
    want = eventchat.generate(
        params, cfg, [ids], jnp.asarray(pv)[None], max_new_tokens=6,
        temperature=0.0, eos_token_id=None, kv_quant=True,
    )[0]
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=3,
                            eos_token_id=None, kv_quant=True)
    rid = srv.submit(ids, pv, 6)
    out = srv.run_until_drained()
    assert out[rid] == want


@pytest.mark.parametrize("window", [2, 4])
def test_speculative_server_equals_generate(tiny, window):
    """Speculative continuous batching commits the exact greedy chains."""
    cfg, params = tiny
    reqs = [
        ([1, 5, -200, 9, 9], _pv(cfg, 0), 10),
        ([1, -200, 7, 7, 8, 14], _pv(cfg, 1), 7),
        ([3, -200, 11], _pv(cfg, 2), 12),
    ]
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None, speculative=window)
    rids = [srv.submit(ids, pv, budget) for ids, pv, budget in reqs]
    out = srv.run_until_drained()
    for rid, (ids, pv, budget) in zip(rids, reqs):
        assert out[rid] == _oneshot(params, cfg, ids, pv, budget), f"req {rid}"


def test_speculative_server_eos_and_reuse(tiny):
    cfg, params = tiny
    ids, pv = [1, 5, -200, 9, 9], _pv(cfg, 0)
    full = _oneshot(params, cfg, ids, pv, 12)
    eos = full[4]
    want = _oneshot(params, cfg, ids, pv, 12, eos=eos)
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=256, chunk=4,
                            eos_token_id=eos, speculative=4)
    a = srv.submit(ids, pv, 12)
    b = srv.submit(ids, pv, 12)  # queued; reuses the row after a finishes
    out = srv.run_until_drained()
    assert out[a] == want and out[b] == want
    assert len(want) < 12


def test_spec_server_zero_budget_returns_zero_tokens(tiny):
    """ADVICE r3: max_new_tokens=0 must return [] on the speculative
    server, matching one-shot generate and the plain server (the prefill
    token used to be committed unconditionally)."""
    cfg, params = tiny
    ids, pv = [1, 5, -200, 9], _pv(cfg, 0)
    for spec in (0, 4):
        srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=256,
                                chunk=4, eos_token_id=None, speculative=spec)
        rid = srv.submit(ids, pv, 0)
        follow = srv.submit(ids, pv, 3)  # row must recycle cleanly after
        out = srv.run_until_drained()
        assert out[rid] == [], f"speculative={spec}"
        assert out[follow] == _oneshot(params, cfg, ids, pv, 3)


def test_chunked_prefill_equals_oneshot(tiny):
    """prefill_chunk splits admission prefill into decode-interleaved
    chunks (VERDICT r3 weak #3); committed chains must stay exact."""
    cfg, params = tiny
    reqs = [
        ([1, 5, -200, 9, 9], _pv(cfg, 0), 10),
        ([1, -200, 7, 7, 8, 14], _pv(cfg, 1), 7),
        ([3, -200, 11], _pv(cfg, 2), 12),
    ]
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None, prefill_chunk=8)
    rids = [srv.submit(ids, pv, budget) for ids, pv, budget in reqs]
    out = srv.run_until_drained()
    for rid, (ids, pv, budget) in zip(rids, reqs):
        assert out[rid] == _oneshot(params, cfg, ids, pv, budget), f"req {rid}"


def test_chunked_prefill_decode_progresses_across_admission(tiny):
    """While a multi-chunk admission is in flight, active rows keep
    committing tokens every scheduler step (the whole point of chunking:
    a long prompt cannot stall the batch for its full prefill)."""
    cfg, params = tiny
    # prefix_cache off: with insert-on-prefill, B's shared text head
    # ([1, 5]) would hit the cache and admit via the (cheap, one-shot)
    # suffix path instead of exercising the chunked machinery under test.
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=2,
                            eos_token_id=None, prefill_chunk=8,
                            prefix_cache=False)
    a = srv.submit([1, 5, -200, 9], _pv(cfg, 0), 12)
    srv.step()  # admit A (no actives yet -> one-shot prefill), decode 2
    req_a = next(r for r in srv.rows if r is not None and r.rid == a)
    # Long prompt: 10 event tokens + text -> prompt_len 14 -> 2 chunks of 8.
    b = srv.submit([1, 5, 6, 7, -200, 9], _pv(cfg, 1), 4)
    before = len(req_a.tokens)
    srv.step()  # chunk 1 of B's prefill + A's decode segment
    assert srv._pending is not None and srv._pending.req.rid == b
    assert len(req_a.tokens) == before + 2, (
        "active row must keep decoding while the admission is mid-prefill"
    )
    out = srv.run_until_drained()
    assert out[a] == _oneshot(params, cfg, [1, 5, -200, 9], _pv(cfg, 0), 12)
    assert out[b] == _oneshot(params, cfg, [1, 5, 6, 7, -200, 9],
                              _pv(cfg, 1), 4)


def test_chunked_prefill_speculative(tiny):
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None, prefill_chunk=8,
                            speculative=4)
    reqs = [
        ([1, 5, -200, 9, 9], _pv(cfg, 0), 10),
        ([1, -200, 7, 7, 8, 14], _pv(cfg, 1), 7),
        ([3, -200, 11], _pv(cfg, 2), 6),
    ]
    rids = [srv.submit(ids, pv, budget) for ids, pv, budget in reqs]
    out = srv.run_until_drained()
    for rid, (ids, pv, budget) in zip(rids, reqs):
        assert out[rid] == _oneshot(params, cfg, ids, pv, budget), f"req {rid}"


def test_chunked_prefill_rejects_off_grain_chunk(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="divide the prompt bucket grain"):
        ContinuousBatcher(params, cfg, max_batch=1, prefill_chunk=48)


def test_warmup_precompiles_and_serves_exactly(tiny):
    """warmup() compiles encode/prefill/admit/segment against the live
    state without corrupting it; a subsequent real request decodes the
    exact one-shot chain. (The latency effect — first request ~= steady
    state — is the benchmark's ``compiles_in_window``, on the chip.)"""
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None)
    n = srv.warmup(prompt_lens=[14])
    assert n >= 3  # encode + >=1 bucket prefill + admit + segment
    ids, pv = [1, 5, -200, 9, 9], _pv(cfg, 0)
    rid = srv.submit(ids, pv, 8)
    out = srv.run_until_drained()
    assert out[rid] == _oneshot(params, cfg, ids, pv, 8)


def test_warmup_speculative_and_request_stats(tiny):
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None, speculative=4,
                            prefill_chunk=8)
    srv.warmup(prompt_lens=[14])
    ids, pv = [1, 5, -200, 9], _pv(cfg, 1)
    rid = srv.submit(ids, pv, 6)
    out = srv.run_until_drained()
    assert out[rid] == _oneshot(params, cfg, ids, pv, 6)
    stats = srv.request_stats[rid]
    assert 0 <= stats["ttft_s"] <= stats["latency_s"]
    assert srv.admission_s > 0


def test_speculative_server_acceptance_on_repetitive_chain(tiny):
    """Zeros model -> constant chain: the server's drafting collapses
    iterations just like the one-shot spec loop."""
    cfg, _ = tiny
    params = jax.tree_util.tree_map(
        jnp.zeros_like, eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(0))
    )
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=256, chunk=16,
                            eos_token_id=None, speculative=4)
    rid = srv.submit([1, 5, -200, 9], _pv(cfg, 0), 16)
    out = srv.run_until_drained()
    assert out[rid] == [0] * 16


def test_first_chunk_ramp_equals_oneshot(tiny):
    """The TTFT ramp (short segments while a fresh admission owes its
    first token) is a pure scheduling change: greedy chains must equal
    one-shot generate, including mid-flight admissions that re-trigger
    the ramp, and warmup must precompile the ramp executable."""
    cfg, params = tiny
    reqs = [
        ([1, 5, -200, 9, 9], _pv(cfg, 0), 12),
        ([1, -200, 7, 7, 8, 14], _pv(cfg, 1), 9),
        ([3, -200, 11], _pv(cfg, 2), 11),
    ]
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=8,
                            eos_token_id=None, first_chunk=2)
    assert srv.first_chunk == 2
    srv.warmup(prompt_lens=[16])
    rids = [srv.submit(ids, pv, budget) for ids, pv, budget in reqs]
    out = srv.run_until_drained()
    for rid, (ids, pv, budget) in zip(rids, reqs):
        assert out[rid] == _oneshot(params, cfg, ids, pv, budget)


def test_first_chunk_ramp_speculative_is_dropped(tiny):
    """Speculative rows commit their first token at admission, so the
    ramp predicate can never fire — the batcher drops the flag (no dead
    executable compiled at warmup) and chains stay exact."""
    cfg, params = tiny
    ids, pv, budget = [1, 5, -200, 9, 9], _pv(cfg, 3), 10
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=8,
                            eos_token_id=None, speculative=4, first_chunk=4)
    assert srv.first_chunk == 0
    rid = srv.submit(ids, pv, budget)
    out = srv.run_until_drained()
    assert out[rid] == _oneshot(params, cfg, ids, pv, budget)


def test_prefix_reuse_text_prefix_equals_oneshot(tiny):
    """Shared text prefix (system-prompt head): admissions run only their
    suffix against the cached prefix KV; chains must equal one-shot
    generate, and non-matching prompts fall back to the full prefill."""
    cfg, params = tiny
    system = [1, 5, 7, 7, 8]
    reqs = [
        (system + [-200, 9, 9], _pv(cfg, 0), 10),
        (system + [-200, 11, 3, 4], _pv(cfg, 1), 8),
        ([2, 6] + [-200, 11], _pv(cfg, 2), 9),  # does NOT match the prefix
    ]
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None)
    assert srv.set_prefix(system) == len(system)
    rids = [srv.submit(ids, pv, budget) for ids, pv, budget in reqs]
    out = srv.run_until_drained()
    for rid, (ids, pv, budget) in zip(rids, reqs):
        assert out[rid] == _oneshot(params, cfg, ids, pv, budget), rid


def test_prefix_reuse_event_prefix_equals_oneshot(tiny):
    """Prefix THROUGH the event block (multi-turn session): suffixes are
    plain text and skip CLIP encode entirely; exactness must hold."""
    cfg, params = tiny
    pv = _pv(cfg, 4)
    head = [1, 5, -200, 7]
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None)
    srv.set_prefix(head, pixel_values=pv)
    reqs = [(head + [9, 9, 12], 10), (head + [3], 8)]
    rids = [srv.submit(ids, pv, budget) for ids, budget in reqs]
    out = srv.run_until_drained()
    for rid, (ids, budget) in zip(rids, reqs):
        assert out[rid] == _oneshot(params, cfg, ids, pv, budget), rid


def test_prefix_reuse_speculative_and_kv_quant(tiny):
    """Prefix admission composes with the speculative server (prefill
    argmax commit + Medusa hidden seeding) and the int8 KV cache."""
    cfg, params = tiny
    system = [1, 5, 7, 7, 8]
    ids, pv, budget = system + [-200, 9, 9], _pv(cfg, 5), 10
    heads = {"w": jax.random.normal(jax.random.PRNGKey(3),
                                    (3, cfg.llama.hidden_size,
                                     cfg.llama.hidden_size)) * 0.5}
    for kw in (dict(speculative=4), dict(speculative=4, draft_head=heads),
               dict(kv_quant=True)):
        srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256,
                                chunk=4, eos_token_id=None, **kw)
        srv.set_prefix(system)
        rid = srv.submit(ids, pv, budget)
        out = srv.run_until_drained()
        want = _oneshot(params, cfg, ids, pv, budget)
        assert out[rid] == want, kw


def test_prefix_validation(tiny):
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=256, chunk=4,
                            eos_token_id=None)
    with pytest.raises(ValueError, match="pixel_values"):
        srv.set_prefix([1, -200, 5])
    with pytest.raises(ValueError, match="at most one"):
        srv.set_prefix([1, -200, -200], _pv(cfg, 0))


def test_prefix_warmup_and_fit_check(tiny):
    """warmup() precompiles the prefix-admission executable (its contract:
    no request pays a compile mid-service), and an oversized prefix fails
    loudly at set_prefix, not as a pad crash."""
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=256, chunk=4,
                            eos_token_id=None)
    base = ContinuousBatcher(params, cfg, max_batch=1, max_len=256, chunk=4,
                             eos_token_id=None)
    n_base = base.warmup(prompt_lens=[16])
    srv.set_prefix([1, 5, 7])
    assert srv.warmup(prompt_lens=[16]) == n_base + 1  # + prefix executable
    ids, pv = [1, 5, 7, -200, 9], _pv(cfg, 6)
    rid = srv.submit(ids, pv, 6)
    out = srv.run_until_drained()
    assert out[rid] == _oneshot(params, cfg, ids, pv, 6)

    tight = ContinuousBatcher(params, cfg, max_batch=1, max_len=128, chunk=4,
                              eos_token_id=None)
    with pytest.raises(ValueError, match="does not fit"):
        tight.set_prefix(list(range(1, 120)))


def test_prefix_takes_precedence_over_chunked_prefill(tiny):
    """With both prefill_chunk and a prefix set, matching requests use the
    (cheap, one-shot) suffix prefill; non-matching ones still go through
    the chunked-admission machinery. Chains stay exact either way."""
    cfg, params = tiny
    system = [1, 5, 7, 7, 8]
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None, prefill_chunk=8)
    srv.set_prefix(system)
    reqs = [
        (system + [-200, 9, 9], 0, 8),   # prefix path
        ([2, 6, -200, 11], 1, 8),        # fallback; chunked once decoding
        (system + [-200, 3], 2, 6),      # prefix path again
    ]
    rids = [srv.submit(ids, _pv(cfg, s), b) for ids, s, b in reqs]
    out = srv.run_until_drained()
    for rid, (ids, s, b) in zip(rids, reqs):
        assert out[rid] == _oneshot(params, cfg, ids, _pv(cfg, s), b), rid


def test_event_prefix_wrong_stream_falls_back_to_full_prefill(tiny):
    """ADVICE r5 medium: with a prefix THROUGH the event block, a request
    whose prompt ids match but whose pixels are a DIFFERENT stream must
    get answers computed against its own stream (full prefill fallback),
    not the prefix's cached KV; matching pixels still take the cheap
    prefix path. Both must equal one-shot generate exactly."""
    cfg, params = tiny
    pv_a, pv_b = _pv(cfg, 4), _pv(cfg, 7)
    head = [1, 5, -200, 7]
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None)
    srv.set_prefix(head, pixel_values=pv_a)
    ids = head + [9, 9, 12]
    same = srv.submit(ids, pv_a, 8)
    other = srv.submit(ids, pv_b, 8)
    out = srv.run_until_drained()
    assert out[same] == _oneshot(params, cfg, ids, pv_a, 8)
    assert out[other] == _oneshot(params, cfg, ids, pv_b, 8)
    # The guard is observable: different streams, different answers
    # (pv_b used to silently inherit pv_a's KV and match `same`).
    assert out[other] != out[same]


def test_deadline_and_cancel_preserve_batch_exactness(tiny):
    """Forced finishes (deadline expiry, cancel) free rows mid-flight;
    the surviving and subsequent requests must still commit their exact
    one-shot greedy chains — scheduling-only intervention, no numeric
    contamination from the freed rows."""
    import time as _time

    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=3,
                            eos_token_id=None)
    doomed = srv.submit([1, 5, -200, 9], _pv(cfg, 0), 12, deadline_s=60.0)
    keeper = srv.submit([1, -200, 7, 7], _pv(cfg, 1), 9)
    srv.step()
    req = next(r for r in srv.rows if r is not None and r.rid == doomed)
    req.deadline = _time.perf_counter() - 1.0
    late = srv.submit([3, -200, 11, 4], _pv(cfg, 2), 6)
    cancel_me = srv.submit([3, -200, 11], _pv(cfg, 3), 6)
    assert srv.cancel(cancel_me)  # still queued: cancelled before a row
    out = srv.run_until_drained()
    assert srv.finish_status[doomed] == "deadline_exceeded"
    assert srv.finish_status[cancel_me] == "cancelled"
    assert out[cancel_me] == []
    want_doomed = _oneshot(params, cfg, [1, 5, -200, 9], _pv(cfg, 0), 12)
    assert out[doomed] == want_doomed[: len(out[doomed])]  # exact prefix
    assert len(out[doomed]) < 12
    assert out[keeper] == _oneshot(params, cfg, [1, -200, 7, 7], _pv(cfg, 1), 9)
    assert out[late] == _oneshot(params, cfg, [3, -200, 11, 4], _pv(cfg, 2), 6)


def test_first_chunk_ramp_with_eos_in_ramp_segment(tiny):
    """A row whose EOS lands inside the short ramp segment freezes there
    and matches the eos-stopped one-shot chain."""
    cfg, params = tiny
    ids, pv = [1, 5, -200, 9, 9], _pv(cfg, 0)
    full = _oneshot(params, cfg, ids, pv, 12)
    eos = full[1]  # stop within the 3-token ramp
    want = _oneshot(params, cfg, ids, pv, 12, eos=eos)
    assert len(want) < 4
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=256, chunk=8,
                            eos_token_id=eos, first_chunk=3)
    rid = srv.submit(ids, pv, 12)
    follow = srv.submit(ids, pv, 12)  # row recycles after the ramp freeze
    out = srv.run_until_drained()
    assert out[rid] == want and out[follow] == want


# -- pipelined scheduler (ISSUE 2) ----------------------------------------


def _chains(params, cfg, reqs, pipeline, prefix=None, **kw):
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None, pipeline=pipeline, **kw)
    if prefix is not None:
        srv.set_prefix(prefix)
    rids = [srv.submit(ids, pv, budget) for ids, pv, budget in reqs]
    out = srv.run_until_drained()
    return [out[r] for r in rids], srv


_PIPE_CONFIGS = {
    "greedy": dict(),
    "int8_kv": dict(kv_quant=True),
    "speculative": dict(speculative=4),
    "spec_int8_kv": dict(speculative=4, kv_quant=True),
    "ttft_ramp": dict(first_chunk=2),
    "chunked_prefill": dict(prefill_chunk=8),
}


@pytest.mark.parametrize("name", sorted(_PIPE_CONFIGS))
def test_pipelined_equals_synchronous_and_oneshot(tiny, name):
    """The exactness contract that makes the pipelined scheduler shippable
    as the DEFAULT: with segment N+1 dispatched from device-resident
    state while the host harvests N, every configuration must commit
    chains byte-identical to the synchronous scheduler AND to one-shot
    generate. Scheduling is the only thing pipelining may change."""
    cfg, params = tiny
    kw = _PIPE_CONFIGS[name]
    reqs = [
        ([1, 5, -200, 9, 9], _pv(cfg, 0), 10),
        ([1, -200, 7, 7, 8, 14], _pv(cfg, 1), 7),
        ([3, -200, 11], _pv(cfg, 2), 12),
    ]
    piped, srv = _chains(params, cfg, reqs, True, **kw)
    synced, _ = _chains(params, cfg, reqs, False, **kw)
    assert piped == synced, name
    for got, (ids, pv, budget) in zip(piped, reqs):
        assert got == _oneshot(params, cfg, ids, pv, budget), name
    assert srv.pipeline and srv.seg_count > 0


def test_pipelined_prefix_and_medusa_equal_synchronous(tiny):
    """Prefix-KV reuse and trained-head drafting ride the same pipelined
    dispatch path; chains must match the synchronous scheduler and
    one-shot generate."""
    cfg, params = tiny
    system = [1, 5, 7, 7, 8]
    reqs = [
        (system + [-200, 9, 9], _pv(cfg, 0), 10),
        ([2, 6, -200, 11], _pv(cfg, 1), 8),   # prefix fallback path
    ]
    heads = {"w": jax.random.normal(jax.random.PRNGKey(3),
                                    (3, cfg.llama.hidden_size,
                                     cfg.llama.hidden_size)) * 0.5}
    for kw in (dict(prefix=system),
               dict(speculative=4, draft_head=heads)):
        piped, _ = _chains(params, cfg, reqs, True, **kw)
        synced, _ = _chains(params, cfg, reqs, False, **kw)
        assert piped == synced, kw
        for got, (ids, pv, budget) in zip(piped, reqs):
            assert got == _oneshot(params, cfg, ids, pv, budget), kw


def test_pipelined_eos_and_row_recycling(tiny):
    """EOS inside an in-flight segment: the device carry freezes the row
    in-graph, the harvest mirrors it, and the freed row re-admits the
    queued request with a fresh carry upload — chains stay exact."""
    cfg, params = tiny
    ids, pv = [1, 5, -200, 9, 9], _pv(cfg, 0)
    full = _oneshot(params, cfg, ids, pv, 12)
    eos = full[4]
    want = _oneshot(params, cfg, ids, pv, 12, eos=eos)
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=256, chunk=5,
                            eos_token_id=eos, pipeline=True)
    a = srv.submit(ids, pv, 12)
    b = srv.submit(ids, pv, 12)  # queued: admitted at a drain boundary
    out = srv.run_until_drained()
    assert out[a] == want and out[b] == want and len(want) < 12
    assert srv._inflight is None  # run_until_drained settles the pipeline


def test_pipelined_deadline_and_cancel_at_dispatch_boundary(tiny):
    """Forced finishes drain the pipeline before mutating rows: the
    doomed row keeps an exact one-shot PREFIX, survivors and late
    admissions keep exact full chains."""
    import time as _time

    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=3,
                            eos_token_id=None, pipeline=True)
    doomed = srv.submit([1, 5, -200, 9], _pv(cfg, 0), 12, deadline_s=60.0)
    keeper = srv.submit([1, -200, 7, 7], _pv(cfg, 1), 9)
    srv.step()
    req = next(r for r in srv.rows if r is not None and r.rid == doomed)
    req.deadline = _time.perf_counter() - 1.0
    late = srv.submit([3, -200, 11, 4], _pv(cfg, 2), 6)
    cancel_me = srv.submit([3, -200, 11], _pv(cfg, 3), 6)
    assert srv.cancel(cancel_me)
    out = srv.run_until_drained()
    assert srv.finish_status[doomed] == "deadline_exceeded"
    want_doomed = _oneshot(params, cfg, [1, 5, -200, 9], _pv(cfg, 0), 12)
    assert out[doomed] == want_doomed[: len(out[doomed])]
    assert len(out[doomed]) < 12
    assert out[keeper] == _oneshot(params, cfg, [1, -200, 7, 7],
                                   _pv(cfg, 1), 9)
    assert out[late] == _oneshot(params, cfg, [3, -200, 11, 4],
                                 _pv(cfg, 2), 6)
    assert out[cancel_me] == []


# -- prefix-KV cache (ISSUE 4) --------------------------------------------


_CACHE_CONFIGS = {
    "greedy": dict(),
    "int8_kv": dict(kv_quant=True),
    "speculative": dict(speculative=4),
    "ttft_ramp": dict(first_chunk=2),
    "chunked_prefill": dict(prefill_chunk=8),
    "sync": dict(pipeline=False),
}


@pytest.mark.parametrize("name", sorted(_CACHE_CONFIGS))
def test_prefix_cache_on_off_matrix(tiny, name):
    """ISSUE 4 exactness contract: with the radix prefix cache auto-
    populating on admission prefill (multi-session traffic: two streams,
    repeat requests, a wrong-stream request and a non-matching prompt),
    every configuration commits chains byte-identical to the cache-off
    server AND to one-shot generate. Caching may only change WHERE a
    prompt's KV comes from, never its values."""
    cfg, params = tiny
    kw = _CACHE_CONFIGS[name]
    reqs = [
        ([1, 5, -200, 9, 9], _pv(cfg, 0), 8),
        ([1, 5, -200, 9, 9], _pv(cfg, 1), 8),   # same text, OTHER stream
        ([1, 5, -200, 3], _pv(cfg, 0), 7),      # session-0 repeat: hit
        ([2, 6, -200, 11], _pv(cfg, 2), 6),     # non-matching head
        ([1, 5, -200, 9, 9], _pv(cfg, 1), 8),   # session-1 repeat: hit
    ]
    outs = {}
    for cache in (True, False):
        srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256,
                                chunk=4, eos_token_id=None,
                                prefix_cache=cache, **kw)
        rids = [srv.submit(i, p, b) for i, p, b in reqs]
        out = srv.run_until_drained()
        outs[cache] = [out[r] for r in rids]
        if cache:
            assert srv._prefix_cache.hits >= 2, name
    assert outs[True] == outs[False], name
    for got, (i, p, b) in zip(outs[True], reqs):
        assert got == _oneshot(params, cfg, i, p, b), name


def test_prefix_cache_medusa_draft_head(tiny):
    """Trained-head drafting rides the suffix-admission path (the hit's
    last hidden seeds the draft window) — exactness must hold with the
    cache populating itself across sessions."""
    cfg, params = tiny
    heads = {"w": jax.random.normal(jax.random.PRNGKey(3),
                                    (3, cfg.llama.hidden_size,
                                     cfg.llama.hidden_size)) * 0.5}
    reqs = [
        ([1, 5, -200, 9, 9], _pv(cfg, 0), 8),
        ([1, 5, -200, 3], _pv(cfg, 0), 7),
        ([1, 5, -200, 9, 9], _pv(cfg, 1), 8),
    ]
    outs = {}
    for cache in (True, False):
        srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256,
                                chunk=4, eos_token_id=None, speculative=4,
                                draft_head=heads, prefix_cache=cache)
        rids = [srv.submit(i, p, b) for i, p, b in reqs]
        out = srv.run_until_drained()
        outs[cache] = [out[r] for r in rids]
    assert outs[True] == outs[False]
    for got, (i, p, b) in zip(outs[True], reqs):
        assert got == _oneshot(params, cfg, i, p, b)


def test_set_prefix_coexists_with_auto_entries_and_warmup(tiny):
    """Operator-set entries (set_prefix / POST /prefix) and auto-inserted
    heads share the trie; warmup precompiles one suffix executable per
    distinct entry shape; chains stay exact through both."""
    cfg, params = tiny
    system = [1, 5, 7, 7, 8]
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None)
    srv.set_prefix(system)
    srv.set_prefix(system + [4])  # a second, deeper operator entry
    n = srv.warmup(prompt_lens=[16])
    assert n >= 2
    reqs = [
        (system + [4, -200, 9, 9], _pv(cfg, 0), 8),   # deeper entry wins
        (system + [-200, 11, 3], _pv(cfg, 1), 7),
        (system + [4, -200, 9, 9], _pv(cfg, 0), 8),   # event-head hit now
    ]
    rids = [srv.submit(i, p, b) for i, p, b in reqs]
    out = srv.run_until_drained()
    for rid, (i, p, b) in zip(rids, reqs):
        assert out[rid] == _oneshot(params, cfg, i, p, b)
    assert srv._prefix_cache.hits == len(reqs)


def test_pipelined_overlap_counters(tiny):
    """The overlap instrumentation ``GET /stats`` reports: pipelined
    runs hide host work behind in-flight segments (overlap_ratio > 0);
    the synchronous path measures ~0 by construction; warmup and
    reset_serving_stats leave a clean measurement window."""
    cfg, params = tiny
    # Long segments (chunk 32) keep the device busy past the host's
    # bookkeeping on any machine, so the in-flight window is reliably
    # observed; tiny segments can finish before the host arrives, which
    # (correctly, conservatively) counts as no overlap.
    reqs = [([1, 5, -200, 9], _pv(cfg, 0), 96),
            ([1, -200, 7, 7], _pv(cfg, 1), 96)]
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=32,
                            eos_token_id=None, pipeline=True)
    srv.warmup(prompt_lens=[14])
    srv.reset_serving_stats()
    for ids, pv, budget in reqs:
        srv.submit(ids, pv, budget)
    srv.run_until_drained()
    assert srv.seg_count >= 2
    assert srv.host_gap_s > 0 and srv.device_segment_s >= 0
    assert srv.overlap_ratio() > 0, (
        srv.host_gap_s, srv.device_segment_s, srv.overlap_hidden_s)
    sync = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=32,
                             eos_token_id=None, pipeline=False)
    for ids, pv, budget in reqs:
        sync.submit(ids, pv, budget)
    sync.run_until_drained()
    # Synchronous: only the dispatch-call overhead itself ever overlaps
    # (the fetch starts right after its own dispatch) — near-zero, and
    # far below the pipelined ratio on identical traffic.
    assert sync.overlap_ratio() < 0.1
    assert srv.overlap_ratio() > 2 * sync.overlap_ratio()
