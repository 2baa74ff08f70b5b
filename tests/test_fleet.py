"""Fleet serving tests (ISSUE 7): prefix-affinity routing, SLO-aware
shedding, drain/re-admission hooks, and the class-aware Retry-After
derivation. Chaos-side coverage (fault sites, kill -> drain -> re-route
-> recovery) lives in tests/test_fleet_chaos.py. Fast tier: tiny config,
CPU, the same (max_batch=1, chunk=2) shapes the serve chaos suite
compiles, so the jit cache is shared across files."""

import time

import jax
import numpy as np
import pytest

from eventgpt_tpu import faults
from eventgpt_tpu.config import EventChatConfig
from eventgpt_tpu.constants import EVENT_TOKEN_INDEX
from eventgpt_tpu.fleet import (Fleet, FleetShedError, affinity_key,
                                retry_after_s)
from eventgpt_tpu.models import eventchat
from eventgpt_tpu.serve import ContinuousBatcher
from eventgpt_tpu.workload import SLO


@pytest.fixture(autouse=True)
def _disarm():
    faults.disable()
    yield
    faults.disable()


@pytest.fixture(scope="module")
def tiny():
    cfg = EventChatConfig.tiny()
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _pv(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(cfg.num_event_frames, 3, cfg.vision.image_size,
                            cfg.vision.image_size)).astype(np.float32)


def _ids(suffix=()):
    return [1, 7, 7, EVENT_TOKEN_INDEX, 9, 10, 11] + list(suffix)


def _batcher(tiny, **kw):
    cfg, params = tiny
    kw.setdefault("max_batch", 1)
    kw.setdefault("chunk", 2)
    kw.setdefault("max_len", 256)
    kw.setdefault("eos_token_id", None)
    return ContinuousBatcher(params, cfg, **kw)


def _fleet(tiny, n=2, probe_interval_s=0.01, **kw):
    from eventgpt_tpu.cli.serve import ServingEngine
    from eventgpt_tpu.data.tokenizer import load_tokenizer

    tok = load_tokenizer("byte")
    bkw = kw.pop("batcher_kw", {})
    engines = [ServingEngine(_batcher(tiny, **bkw), tok) for _ in range(n)]
    return Fleet(engines, tok, probe_interval_s=probe_interval_s, **kw)


def test_retry_after_is_class_aware_and_goodput_derived():
    """The 429 hint: batch backs off harder than interactive at EVERY
    load level, sinking goodput lengthens both, and the hint is capped."""
    assert retry_after_s("interactive", 1.0) < retry_after_s("batch", 1.0)
    assert retry_after_s("interactive", 0.3) > retry_after_s(
        "interactive", 1.0)
    assert retry_after_s("batch", 0.0) == pytest.approx(16.0)
    assert retry_after_s("batch", 0.3, queue_depth=100, max_queue=10) \
        <= 60.0
    # Unknown class names take the conservative (batch) base.
    assert retry_after_s("???", 1.0) == retry_after_s("batch", 1.0)


def test_affinity_key_matches_prefix_identity(tiny):
    cfg, _ = tiny
    a = affinity_key(_ids(), _pv(cfg, 1))
    b = affinity_key(_ids((55, 56)), _pv(cfg, 1))   # same head, new turn
    c = affinity_key(_ids(), _pv(cfg, 2))           # different stream
    assert a == b
    assert a != c


def test_export_requests_drains_and_readmission_is_exact(tiny):
    """The serve.py drain hook: export strips queued AND in-flight
    requests (tokens discarded), the batcher is left empty, and
    re-admitting the records elsewhere reproduces the uninterrupted
    greedy chains byte-for-byte."""
    cfg, _ = tiny
    src = _batcher(tiny)
    reqs = [(_ids((20 + i,)), _pv(cfg, i), 8) for i in range(3)]
    rids = [src.submit(ids, pv, n) for ids, pv, n in reqs]
    for _ in range(2):  # rid 0 decodes mid-chain; the rest sit queued
        src.step()
    recs = src.export_requests()
    assert [r["rid"] for r in recs] == rids
    assert not src.queue and all(r is None for r in src.rows)
    assert src.finished == {}  # exported, not finished
    # Any prior partial progress is discarded: re-admission re-decodes.
    dst = _batcher(tiny)
    moved = {r["rid"]: dst.submit(r["input_ids"], r["pixel_values"],
                                  r["max_new_tokens"],
                                  deadline_s=r["deadline_s"], slo=r["slo"])
             for r in recs}
    out = dst.run_until_drained()
    ref_b = _batcher(tiny)
    ref_rids = [ref_b.submit(ids, pv, n) for ids, pv, n in reqs]
    ref = ref_b.run_until_drained()
    for old, new in zip(rids, ref_rids):
        assert out[moved[old]] == ref[new]


def test_router_affinity_same_session_lands_same_replica(tiny):
    """Same-session (same head + stream) requests pin to one replica —
    and that replica's prefix cache is the one collecting the hits
    (egpt_serve_prefix_cache_* feed from these per-replica counters)."""
    cfg, _ = tiny
    fleet = _fleet(tiny)
    try:
        frids = []
        for turn in range(3):
            f = fleet.submit_ids(_ids(tuple(range(30, 30 + turn))),
                                 _pv(cfg, 7), 4)
            fleet.result(f, timeout=120)
            frids.append(f)
        homes = {fleet.replica_of(f) for f in frids}
        assert len(homes) == 1, f"session bounced across replicas: {homes}"
        home = homes.pop()
        other = 1 - home
        pinned = fleet.replicas[home].engine.batcher.prefix_cache_stats()
        idle = fleet.replicas[other].engine.batcher.prefix_cache_stats()
        assert pinned["hits"] >= 1          # turns 2/3 reuse the head
        assert idle["hits"] == 0 and idle["misses"] == 0
        # A different stream has no pin: least-queue may pick either
        # replica, but the router must still serve it.
        f = fleet.submit_ids(_ids(), _pv(cfg, 8), 4)
        assert len(fleet.result(f, timeout=120)) == 4
    finally:
        fleet.shutdown()


def test_shedding_batch_only_and_interactive_protected(tiny):
    """The acceptance bar: under the same overload, shedding armed keeps
    the interactive SLO-met ratio >= the unarmed ratio, and ONLY
    batch-class requests are shed (the egpt_fleet_shed_total label
    story, asserted on its host-side mirror + the registry counter)."""
    from eventgpt_tpu.obs import metrics as obs_metrics

    cfg, _ = tiny
    inter = SLO("interactive", ttft_s=0.25)
    batch = SLO("batch", latency_s=60.0)

    def overload(fleet):
        """12 batch requests swamp both replicas, then 4 interactive
        arrive behind them."""
        frids, shed = [], 0
        for i in range(12):
            try:
                frids.append((batch, fleet.submit_ids(
                    _ids((60,)), _pv(cfg, 200 + i), 12, slo=batch)))
            except FleetShedError:
                shed += 1
        for i in range(4):
            frids.append((inter, fleet.submit_ids(
                _ids((61,)), _pv(cfg, 300 + i), 4, slo=inter)))
        for _, f in frids:
            fleet.result(f, timeout=120)
        st = fleet.slo_stats()["classes"]
        return st.get("interactive", {"attainment": 1.0})["attainment"], shed

    shed_before = obs_metrics.FLEET_SHED.value(slo_class="batch")
    unarmed = _fleet(tiny, shed_queue_depth=0, shed_goodput_ratio=0.0)
    try:
        unarmed_ratio, unarmed_shed = overload(unarmed)
        assert unarmed_shed == 0 and unarmed.n_shed == {}
    finally:
        unarmed.shutdown()
    armed = _fleet(tiny, shed_queue_depth=2, shed_goodput_ratio=0.0)
    try:
        armed_ratio, armed_shed = overload(armed)
        assert armed_shed > 0
        assert armed.n_shed.get("batch", 0) == armed_shed
        assert "interactive" not in armed.n_shed  # never policy-shed
        assert obs_metrics.FLEET_SHED.value(slo_class="batch") \
            == shed_before + armed_shed
        assert armed_ratio >= unarmed_ratio
    finally:
        armed.shutdown()


def test_shed_error_carries_goodput_derived_hint(tiny):
    cfg, _ = tiny
    fleet = _fleet(tiny, shed_queue_depth=1)
    try:
        # Saturate with UNCLASSED fillers (not shed-eligible) so only
        # the batch-class probe below can shed: 2 active rows + 2
        # queued. The queued pair cannot leave the queue before their
        # replicas' 64-token decodes finish, so the probe submitted
        # right behind them deterministically sees queue depth >= 1.
        fillers = [fleet.submit_ids(_ids(), _pv(cfg, i), 64)
                   for i in range(1, 5)]
        with pytest.raises(FleetShedError) as e:
            fleet.submit_ids(_ids(), _pv(cfg, 9), 4,
                             slo=SLO("batch", latency_s=60.0))
        assert e.value.slo_class == "batch"
        assert e.value.retry_after_s >= retry_after_s("batch", 1.0) * 0.99
        for f in fillers:
            fleet.result(f, timeout=120)
    finally:
        fleet.shutdown()


def test_failover_repins_session_to_survivor(tiny):
    """After a kill, the failed-over session's pin MOVES: later turns of
    the same session route to the survivor (no bouncing back to the
    dead replica), and the revived replica rejoins the pool."""
    cfg, _ = tiny
    fleet = _fleet(tiny)
    try:
        f0 = fleet.submit_ids(_ids(), _pv(cfg, 9), 4)
        fleet.result(f0, timeout=120)
        home = fleet.replica_of(f0)
        f1 = fleet.submit_ids(_ids((70,)), _pv(cfg, 9), 16)
        deadline = time.time() + 30
        while time.time() < deadline and not any(
                r is not None
                for r in fleet.replicas[home].engine.batcher.rows):
            time.sleep(0.002)
        fleet.kill_replica(home)
        assert len(fleet.result(f1, timeout=120)) == 16
        survivor = fleet.replica_of(f1)
        assert survivor != home
        # Next turn of the same session follows the failover pin.
        f2 = fleet.submit_ids(_ids((70, 71)), _pv(cfg, 9), 4)
        fleet.result(f2, timeout=120)
        assert fleet.replica_of(f2) == survivor
        # Recovery: the revived replica is routable again.
        fleet.restart_replica(home)
        assert fleet.replicas[home].routable
        assert not fleet.breaker_open()
    finally:
        fleet.shutdown()
