"""One seeded trace, one plain arm, and every other way of serving it:
each request's greedy chain is the plain arm's, token for token.

Greedy decoding commits the same chain however a request is scheduled,
cached, drafted, evicted or routed: rows do not see each other in
attention, a prefix hit copies what a prefill would have written, a
rejected draft is rolled back, a preempted row resumes from its own keys
and values. That is a property of the program, so it is asserted on a run
of the program: a trace from ``workload.generate_trace`` (gamma(0.5)
arrivals, lognormal-capped prompt and output lengths, one-shot, multi-turn
and re-submit sessions) replayed unpaced through a ``ContinuousBatcher``
with the benchmark cells' flags (the plain arm, itself held to
``eventchat.generate``), then through each arm below.

Not here, because they have their own identity tests over worker
processes and handoff records: process fleets (``tests/test_fleet_proc.py``)
and prefill / decode disaggregation (``tests/test_handoff.py``).
"""

import jax
import numpy as np
import pytest

from eventgpt_tpu import workload as wl
from eventgpt_tpu.config import EventChatConfig
from eventgpt_tpu.models import eventchat
from eventgpt_tpu.obs import journey as obs_journey
from eventgpt_tpu.obs import series as obs_series
from eventgpt_tpu.obs import trace as obs_trace
from eventgpt_tpu.serve import ContinuousBatcher

SPEC = wl.WorkloadSpec(
    seed=31, n_requests=28, rate_rps=50.0, arrival="gamma", gamma_shape=0.5,
    sessions=3, p_oneshot=0.3, p_chat=0.4, p_stream=0.3,
    prompt_max=40, output_min=3, output_max=12)
MAX_LEN = 256
# The benchmark cells' flags (benchmark/configs/*.json): segments of four
# tokens, exclusive admission waves, no prefix cache, dense, pipelined.
PLAIN = dict(max_batch=4, max_len=MAX_LEN, chunk=4, prefill_budget=0,
             prefix_cache=False, eos_token_id=None)


@pytest.fixture(scope="module")
def tiny():
    cfg = EventChatConfig.tiny()
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(5))
    return cfg, params


@pytest.fixture(scope="module")
def trace(tiny):
    cfg, _ = tiny
    trace = wl.generate_trace(SPEC)
    assert {r.kind for r in trace} == set(wl.KINDS)
    assert {r.slo_class for r in trace} == set(wl.SLO_CLASSES)
    # Every arm's row holds the longest request and the widest draft window.
    need = max(len(r.input_ids) - 1 + cfg.num_event_tokens
               + r.max_new_tokens for r in trace)
    assert need + 1 + 4 <= MAX_LEN
    return trace


def _pixels_for(cfg):
    shape = (cfg.num_event_frames, 3, cfg.vision.image_size,
             cfg.vision.image_size)
    streams = {}

    def pixels_for(r):
        if r.pixels_seed not in streams:
            streams[r.pixels_seed] = wl.stream_pixels(shape, r.pixels_seed)
        return streams[r.pixels_seed]

    return pixels_for


def _server(tiny, **kw):
    cfg, params = tiny
    return ContinuousBatcher(params, cfg, **{**PLAIN, **kw})


def _replay(tiny, trace, slo=False, paced=False, srv=None, **kw):
    """Chains by trace index, and the server that made them."""
    srv = srv or _server(tiny, **kw)
    done = wl.replay(
        srv, trace, pixels_for=_pixels_for(tiny[0]), paced=paced,
        slo_for=(lambda r: SPEC.slo_for(r.slo_class)) if slo else None)
    return done["finished"], srv


@pytest.fixture(scope="module")
def plain(tiny, trace):
    chains, _ = _replay(tiny, trace)
    assert sorted(chains) == [r.idx for r in trace]
    assert all(len(chains[r.idx]) == r.max_new_tokens for r in trace)
    return chains


def test_plain_arm_is_the_one_shot_answer(tiny, trace, plain):
    """The reference arm is itself held to a path that shares no
    scheduler with it: one of each kind of session through
    ``eventchat.generate``."""
    cfg, params = tiny
    pixels_for = _pixels_for(cfg)
    for kind in wl.KINDS:
        r = next(r for r in trace if r.kind == kind)
        (once,) = eventchat.generate(
            params, cfg, [r.input_ids], np.asarray(pixels_for(r))[None],
            max_new_tokens=r.max_new_tokens, temperature=0.0,
            eos_token_id=None)
        assert plain[r.idx] == once, kind


def _telemetry_armed(tiny, trace):
    """The trace ring, the flight recorder and the time-series store all
    recording, every request scored against its class's targets."""
    obs_trace.configure()
    obs_journey.configure(4 * len(trace))
    obs_series.configure(interval_s=0.05, keep=1024, autostart=True)
    try:
        chains, srv = _replay(tiny, trace, slo=True)
        assert len(obs_trace.active().events()) > len(trace)
        assert len(obs_journey.index(n=4 * len(trace))) == len(trace)
        scored = srv.slo_stats()["classes"]
        assert sum(c["finished"] for c in scored.values()) == len(trace)
    finally:
        obs_series.disable()
        obs_journey.disable()
        obs_trace.disable()
    return chains


def _telemetry_off(tiny, trace):
    """``--no_telemetry``: the metrics registry disarmed."""
    from eventgpt_tpu.obs import metrics as obs_metrics

    obs_metrics.configure(False)
    try:
        return _replay(tiny, trace)[0]
    finally:
        obs_metrics.configure(True)


def _paced(tiny, trace):
    """Open loop on the wall clock: each request submitted at its
    arrival time, whatever the server is doing then."""
    return _replay(tiny, trace, paced=True)[0]


def _cli_defaults(tiny, trace):
    """What ``python -m eventgpt_tpu.cli.serve`` ships with no flag given:
    its segment length, lanes at a segment's budget, the prefix cache at
    its byte budget."""
    from eventgpt_tpu.cli.serve import build_parser

    args = build_parser().parse_args([])
    assert args.prefill_budget < 0 and not args.no_prefix_cache
    chains, srv = _replay(
        tiny, trace, max_batch=args.max_batch, chunk=args.chunk,
        prefill_budget=args.chunk, prefix_cache=True,
        prefix_cache_bytes=int(args.prefix_cache_mb * 1024 * 1024),
        pipeline=not args.no_pipeline, kv_layout=args.kv_layout)
    assert srv.prefix_cache_stats()["hits"] > 0
    return chains


def _lanes(tiny, trace):
    chains, srv = _replay(tiny, trace, prefill_budget=PLAIN["chunk"])
    assert srv.mixed_boundaries > 0 and srv.mixed_zero_harvests == 0
    return chains


def _prefix_cache(tiny, trace):
    chains, srv = _replay(tiny, trace, prefix_cache=True)
    assert srv.prefix_cache_stats()["hits"] > 0
    return chains


def _kv_paged(tiny, trace):
    chains, srv = _replay(tiny, trace, kv_layout="paged")
    pool = srv.memory_summary()["kv_blocks"]
    assert pool["free_blocks"] + pool["used_blocks"] == pool["usable_blocks"]
    return chains


def _no_pipeline(tiny, trace):
    chains, srv = _replay(tiny, trace, pipeline=False)
    assert srv.overlap_ratio() < 0.1
    return chains


def _first_chunk(tiny, trace):
    return _replay(tiny, trace, first_chunk=1)[0]


def _chunked_prefill(tiny, trace):
    return _replay(tiny, trace, prefill_chunk=64)[0]


def _spec_fixed(tiny, trace):
    chains, srv = _replay(tiny, trace, speculative=4)
    assert srv.spec_iterations > 0
    return chains


def _spec_buckets(tiny, trace):
    chains, srv = _replay(tiny, trace, spec_buckets="0,2,4")
    depths = set(srv.spec_depth_trace)
    assert depths and depths <= set(srv.spec_windows)
    return chains


def _preempt_spill(tiny, trace):
    """A pool that holds one row's blocks and a spare: interactive
    arrivals evict batch rows, whose keys and values go to host memory
    and come back."""
    srv = _server(tiny, kv_layout="paged", kv_pool_blocks=4, preempt=True,
                  spill_capacity_mb=64)
    # The price of a recompute on a toy model is nothing: make the policy
    # choose the spill so that the arm covers it.
    srv._recompute_flops_per_s = 1.0
    # Batch-class work first, so that it holds the pool when the
    # interactive requests arrive.
    order = sorted(trace, key=lambda r: (r.slo_class != "batch", r.idx))
    chains, _ = _replay(tiny, order, slo=True, srv=srv)
    pool = srv._pool.stats()
    assert srv.preemptions > 0 and pool["spills"] > 0
    assert pool["restores"] == pool["spills"] and pool["spilled_runs"] == 0
    return chains


def _fleet_2(tiny, trace):
    """Two replicas behind the router's own client surface, the trace
    submitted in arrival order: routing is placement only."""
    from eventgpt_tpu.cli.serve import ServingEngine
    from eventgpt_tpu.data.tokenizer import load_tokenizer
    from eventgpt_tpu.fleet import Fleet

    tok = load_tokenizer("byte")
    pixels_for = _pixels_for(tiny[0])
    fleet = Fleet([ServingEngine(_server(tiny), tok) for _ in range(2)],
                  tok, probe_interval_s=0.01)
    try:
        frids = {r.idx: fleet.submit_ids(r.input_ids, pixels_for(r),
                                         r.max_new_tokens) for r in trace}
        chains = {idx: fleet.result(f, timeout=120)
                  for idx, f in frids.items()}
        assert {fleet.replica_of(f) for f in frids.values()} == {0, 1}
    finally:
        fleet.shutdown()
    return chains


ARMS = {
    "telemetry_armed": _telemetry_armed,
    "telemetry_off": _telemetry_off,
    "paced": _paced,
    "cli_defaults": _cli_defaults,
    "lanes": _lanes,
    "prefix_cache": _prefix_cache,
    "kv_paged": _kv_paged,
    "no_pipeline": _no_pipeline,
    "first_chunk": _first_chunk,
    "chunked_prefill": _chunked_prefill,
    "spec_fixed": _spec_fixed,
    "spec_buckets": _spec_buckets,
    "preempt_spill": _preempt_spill,
    "fleet_2": _fleet_2,
}


@pytest.mark.parametrize("arm", list(ARMS))
def test_chains_identical_to_the_plain_arm(tiny, trace, plain, arm):
    chains = ARMS[arm](tiny, trace)
    assert sorted(chains) == sorted(plain)
    for r in trace:
        assert chains[r.idx] == plain[r.idx], (arm, r.idx, r.kind)
