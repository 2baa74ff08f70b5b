"""Adaptive speculation (ISSUE 13): acceptance-driven draft depth.

The contract that makes a live depth knob shippable, pinned fast-tier:

  * EXACTNESS — adaptive-K chains are byte-identical to fixed-K and to
    one-shot ``generate`` across the matrix (greedy / int8-KV / paged /
    mixed-lanes / pipeline-off / Medusa heads): verification commits
    the target chain at ANY draft depth, so the controller can only
    move latency, never bytes.
  * DETERMINISM — same trace + same seed => the same depth-choice
    sequence (the controller is a pure function of harvested
    acceptance).
  * NO RECOMPILES — every bucket's executable is primed by
    ``warmup()``; a depth-switching replay leaves the segment jit
    caches untouched (the acceptance criterion's cache-size test).
  * CHAOS — the ``serve.spec_adapt`` fault site degrades one boundary
    to the fixed default window, chains untouched (lint rule 4 arms
    the site here).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventgpt_tpu import faults
from eventgpt_tpu import serve as serve_mod
from eventgpt_tpu import serve_spec
from eventgpt_tpu.config import EventChatConfig
from eventgpt_tpu.models import eventchat
from eventgpt_tpu.serve import ContinuousBatcher


@pytest.fixture(autouse=True)
def _disarm():
    faults.disable()
    yield
    faults.disable()


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


REQS = [([1, 5, -200, 9, 9], 0, 14), ([1, -200, 7, 7], 1, 5)]
LATE = [([1, 5, -200, 3], 0, 8), ([2, 6, -200, 11], 3, 7)]


def _run(params, cfg, **kw):
    """Staged traffic: two rows decode, one finishes fast (row recycles),
    two late arrivals join mid-flight — the shape that exercises depth
    switches across admissions."""
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None, **kw)
    rids = [srv.submit(i, _pv(cfg, s), b) for i, s, b in REQS]
    srv.step()
    srv.step()
    rids += [srv.submit(i, _pv(cfg, s), b) for i, s, b in LATE]
    out = srv.run_until_drained()
    return [out[r] for r in rids], srv


MATRIX = {
    "plain": {},
    "int8_kv": dict(kv_quant=True),
    "paged": dict(kv_layout="paged"),
    "mixed_lanes": dict(prefill_budget=8, prefill_lane_chunk=4),
    "pipeline_off": dict(pipeline=False),
}


@pytest.mark.parametrize("name", sorted(MATRIX))
def test_adaptive_equals_fixed_and_oneshot(tiny, name):
    cfg, params = tiny
    kw = MATRIX[name]
    want = [_oneshot(params, cfg, i, _pv(cfg, s), b)
            for i, s, b in REQS + LATE]
    fixed, _ = _run(params, cfg, speculative=4, **kw)
    adaptive, srv = _run(params, cfg, spec_buckets="0,2,4", **kw)
    assert fixed == want, name
    assert adaptive == want, name
    # Every boundary chose a primed bucket. Which one is the policy's
    # choice: test_controller_* hold the switching rule on a scripted
    # acceptance series.
    trace = list(srv.spec_depth_trace)
    assert trace and set(trace) <= set(srv.spec_windows), trace


def test_adaptive_medusa_draft_head(tiny):
    cfg, params = tiny
    from eventgpt_tpu.models import medusa as medusa_mod

    heads = medusa_mod.init_medusa_params(cfg.llama, 3)
    heads = {"w": jax.random.normal(jax.random.PRNGKey(7),
                                    heads["w"].shape) * 0.01}
    want = [_oneshot(params, cfg, i, _pv(cfg, s), b)
            for i, s, b in REQS + LATE]
    got, srv = _run(params, cfg, spec_buckets="0,2,4", draft_head=heads)
    assert got == want
    assert srv.spec_max == 4


def test_adaptive_high_acceptance_holds_top_bucket(tiny):
    """Zeros weights -> constant chains -> ~full acceptance: the
    controller must ramp to (and hold) the LARGEST bucket, and commits
    per dispatch must beat the draft-free floor."""
    cfg, _ = tiny
    zeros = jax.tree_util.tree_map(
        jnp.zeros_like, eventchat.init_eventchat_params(
            cfg, jax.random.PRNGKey(0)))
    srv = ContinuousBatcher(zeros, cfg, max_batch=1, max_len=256, chunk=16,
                            eos_token_id=None, spec_buckets="0,2,4")
    rid = srv.submit([1, 5, -200, 9], _pv(cfg, 0), 40)
    out = srv.run_until_drained()
    assert out[rid] == [0] * 40
    trace = list(srv.spec_depth_trace)
    # Optimistic start at 4, and once acceptance lands it stays there.
    assert trace[-1] == 4, trace
    assert srv._spec_ctl.accept_ema > 0.9
    st = srv.spec_stats()
    assert st["accepted_per_dispatch"] > 2.0, st


def test_depth_choice_sequence_deterministic(tiny):
    """Same trace + same seed => same depth-choice sequence, run to run
    (fresh servers, fresh controllers)."""
    cfg, params = tiny

    def trace_once():
        _, srv = _run(params, cfg, spec_buckets="0,2,4")
        return list(srv.spec_depth_trace), srv.spec_stats()

    t1, s1 = trace_once()
    t2, s2 = trace_once()
    assert t1 == t2
    assert s1["accepted_per_dispatch"] == s2["accepted_per_dispatch"]
    assert s1["spec_depth_mean"] == s2["spec_depth_mean"]


def test_warmup_primes_all_buckets_no_recompile(tiny):
    """The acceptance criterion: an adaptive replay compiles NOTHING
    after warmup — every bucket executable (plain + mixed) was primed,
    so the jit cache sizes are stable whatever depths it chose."""
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None, spec_buckets="0,2,4",
                            prefill_budget=8, prefill_lane_chunk=4)
    srv.warmup(prompt_lens=[8])
    spec_cache = serve_mod._spec_segment_jit._cache_size()
    mixed_cache = serve_mod._mixed_spec_segment_jit._cache_size()
    rids = [srv.submit(i, _pv(cfg, s), b) for i, s, b in REQS]
    srv.step()
    srv.step()
    rids += [srv.submit(i, _pv(cfg, s), b) for i, s, b in LATE]
    out = srv.run_until_drained()
    assert sorted(out) == sorted(rids)
    assert set(srv.spec_depth_trace) <= set(srv.spec_windows)
    assert serve_mod._spec_segment_jit._cache_size() == spec_cache
    assert serve_mod._mixed_spec_segment_jit._cache_size() == mixed_cache


def test_spec_adapt_fault_degrades_boundary(tiny):
    """Chaos (lint rule 4): a ``serve.spec_adapt`` trip degrades that
    boundary to the fixed default window at full depth — chains stay
    byte-identical, the trip is visible in faults.stats(), and service
    continues on the adaptive policy afterwards."""
    cfg, params = tiny
    want = [_oneshot(params, cfg, i, _pv(cfg, s), b)
            for i, s, b in REQS + LATE]
    faults.configure("serve.spec_adapt:n=2")
    got, srv = _run(params, cfg, spec_buckets="0,2,4")
    st = faults.stats()["serve.spec_adapt"]
    assert st["fires"] == 1, st
    assert got == want
    # The degraded boundary ran the DEFAULT window (max bucket = 4):
    # boundary #2 in the trace must be 4 even though the controller
    # would have started backing off.
    assert list(srv.spec_depth_trace)[1] == srv.speculative


def test_per_row_masking_counts_and_stays_exact(tiny):
    """Force the bucket to stay wide (huge hysteresis pins the
    optimistic max window) while per-row acceptance is ~0: rows get
    masked below full depth, the masked-rows counter moves, chains
    stay byte-identical."""
    cfg, params = tiny
    want = [_oneshot(params, cfg, i, _pv(cfg, s), b)
            for i, s, b in REQS + LATE]
    got, srv = _run(params, cfg, spec_buckets="2,4",
                    spec_hysteresis=1e9)
    assert got == want
    assert set(srv.spec_depth_trace) == {4}  # hysteresis pinned it
    assert srv.spec_masked_rows > 0
    assert srv.spec_stats()["masked_rows"] == srv.spec_masked_rows


def test_export_and_finish_drop_controller_rows(tiny):
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None, spec_buckets="0,2,4")
    srv.submit([1, 5, -200, 9], _pv(cfg, 0), 20)
    srv.submit([1, -200, 7, 7], _pv(cfg, 1), 20)
    for _ in range(3):
        srv.step()
    assert srv._spec_ctl.stats()["tracked_rows"] > 0
    recs = srv.export_requests()
    assert len(recs) == 2
    assert srv._spec_ctl.stats()["tracked_rows"] == 0
    out = srv.run_until_drained()
    assert out == {}


# -- controller policy units (jax-free) -----------------------------------


def test_expected_commits_formula():
    assert serve_spec.expected_commits(0.0, 7) == 1.0
    assert serve_spec.expected_commits(1.0, 7) == 8.0
    np.testing.assert_allclose(serve_spec.expected_commits(0.5, 2), 1.75)


def test_controller_backs_off_and_ramps():
    ctl = serve_spec.SpecController((1, 2, 4, 8), default_window=8,
                                    hysteresis=0.0, draft_cost=0.1)
    # Optimistic before data:
    assert ctl.select_window() == 8
    # Zero acceptance -> the draft-free bucket wins.
    ctl.observe([(0, 0, 7), (1, 0, 7)], [0] * 7, [2] * 7)
    assert ctl.select_window() == 1
    # Near-perfect acceptance -> back to the top bucket.
    for _ in range(20):
        ctl.observe([(0, 7, 7)], [1] * 7, [1] * 7)
    assert ctl.select_window() == 8
    assert ctl.switches >= 2


def test_controller_hysteresis_prevents_thrash():
    ctl = serve_spec.SpecController((1, 8), default_window=8,
                                    hysteresis=10.0)
    ctl.observe([(0, 0, 7)], [0] * 7, [1] * 7)
    # The winner (1) cannot clear the huge hysteresis margin.
    assert ctl.select_window() == 8


def test_controller_head_pruning_caps_depth():
    ctl = serve_spec.SpecController((1, 2, 4, 8), default_window=8,
                                    head_min_yield=0.3)
    # Positions 0-1 yield well, position 2 dies -> cap = 2.
    for _ in range(5):
        ctl.observe([(0, 3, 7)], [9, 7, 0, 0, 0, 0, 0],
                    [10, 10, 10, 10, 10, 10, 10])
    assert ctl.head_cap(8) == 2
    depths, masked = ctl.depths([0], 8)
    assert depths[0] <= 2
    assert masked == 1


def test_controller_mixed_budget_caps_window():
    ctl = serve_spec.SpecController((1, 2, 4, 8), default_window=8,
                                    draft_budget=8)
    for _ in range(10):
        ctl.observe([(0, 7, 7)], [1] * 7, [1] * 7)
    # 4 live rows * (8-1) drafts = 28 > budget 8; 2 fits (4*1=4 <= 8).
    assert ctl.select_window(live_rows=4, mixed=True) == 2
    # Off-mixed boundaries are uncapped.
    assert ctl.select_window(live_rows=4, mixed=False) == 8


def test_parse_spec_buckets():
    assert serve_spec.parse_spec_buckets("0,2,4,8") == (1, 2, 4, 8)
    assert serve_spec.parse_spec_buckets("") is None
    assert serve_spec.parse_spec_buckets(None) is None
    assert serve_spec.parse_spec_buckets("4, 2, 4") == (2, 4)
    with pytest.raises(ValueError):
        serve_spec.parse_spec_buckets("-1")
