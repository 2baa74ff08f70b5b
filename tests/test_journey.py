"""Flight recorder + tail-latency attribution (ISSUE 10): the phase
decomposition's sum invariant (property-tested over adversarial
checkpoint subsets), the batcher/engine wiring across every terminal
path (the terminal-status audit: one ``finish_status`` per request,
journey finish byte-identical to it), the mem-guard defer phase, chain
neutrality armed vs disarmed, the miss-cause metric, the HTTP surface
(/request, /requests, /trace?rid, the per-response debug block) and the
fleet-level shed/route journeys."""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from eventgpt_tpu import faults
from eventgpt_tpu.config import EventChatConfig
from eventgpt_tpu.constants import EVENT_TOKEN_INDEX
from eventgpt_tpu.models import eventchat
from eventgpt_tpu.obs import journey as obs_journey
from eventgpt_tpu.obs import metrics as obs_metrics
from eventgpt_tpu.serve import ContinuousBatcher
from eventgpt_tpu.workload import SLO


@pytest.fixture(autouse=True)
def _armed():
    faults.disable()
    obs_journey.configure(512)
    yield
    faults.disable()
    obs_journey.disable()


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
    kw.setdefault("max_batch", 2)
    kw.setdefault("chunk", 4)
    kw.setdefault("max_len", 256)
    kw.setdefault("eos_token_id", None)
    return ContinuousBatcher(params, cfg, **kw)


# -- decomposition property -------------------------------------------------

def test_phase_decomposition_sums_exactly_property():
    """THE invariant: whatever subset / ordering of checkpoints a
    timeline saw, the six phases partition [t_submit, t_done] exactly
    and every phase is non-negative; the dominant cause always lands
    inside the closed enum. 300 randomized timelines, including
    adversarial ones (events out of checkpoint order, missing
    checkpoints, zero-length requests)."""
    rng = np.random.default_rng(7)
    rec = obs_journey.JourneyRecorder(keep=1000)
    for trial in range(300):
        t0 = float(rng.uniform(0.0, 100.0))
        e2e = float(rng.uniform(0.0, 20.0))
        # Event offsets drawn UNSORTED on purpose: the clamp must
        # repair any ordering into a monotone chain.
        offs = rng.uniform(0.0, e2e if e2e > 0 else 1.0, size=4)
        present = rng.integers(0, 2, size=4).astype(bool)
        rec.begin(0, trial, t=t0)
        if present[0]:
            rec.event(0, trial, "mem_guard_defer", t=t0 + offs[0])
        if present[1]:
            rec.event(0, trial, "queue", t=t0 + offs[1])
        if present[2]:
            rec.event(0, trial, "admit", t=t0 + offs[2])
        if present[3]:
            rec.event(0, trial, "segment", t=t0 + offs[3], tokens=3)
        out = rec.finish(0, trial, "ok", t_submit=t0, t_done=t0 + e2e)
        phases = out["phases"]
        assert sum(phases.values()) == pytest.approx(out["e2e_s"],
                                                     abs=1e-9), \
            (trial, phases, out["e2e_s"])
        assert all(v >= -1e-12 for v in phases.values()), (trial, phases)
        assert set(phases) == set(obs_journey.PHASE_KEYS)
        assert out["cause"] in obs_journey.MISS_CAUSES
    assert rec.stats()["duplicate_finishes"] == 0


def test_recorder_bounds_and_enum_are_closed():
    rec = obs_journey.JourneyRecorder(keep=4, max_events=8, live_cap=8)
    with pytest.raises(ValueError):
        rec.event(0, 0, "not_a_kind")
    # The finished ring holds exactly ``keep`` newest records.
    for rid in range(10):
        rec.begin(0, rid, t=float(rid))
        rec.finish(0, rid, "ok", t_done=float(rid) + 1.0)
    idx = rec.index(0, n=100)
    assert [r["rid"] for r in idx] == [9, 8, 7, 6]
    # Per-timeline cap: a long defer streak merges into the trailing
    # same-kind event instead of growing without bound.
    rec.begin(0, 99, t=0.0)
    for i in range(50):
        rec.event(0, 99, "mem_guard_defer", t=0.1 + 0.01 * i)
    out = rec.finish(0, 99, "ok", t_done=2.0)
    assert len(out["events"]) <= 8 + 1  # cap + the finish event
    # Checkpoint bookkeeping survived the merge: defer started at the
    # FIRST deferral.
    assert out["phases"]["queue_s"] == pytest.approx(0.1, abs=1e-9)


def test_dominant_cause_rules():
    assert obs_journey.dominant_cause("nan_quarantined", {
        "queue_s": 100.0}) == "nan_quarantine"
    assert obs_journey.dominant_cause("shed", None) == "shed"
    assert obs_journey.dominant_cause("ok", {
        "queue_s": 1.0, "defer_s": 3.0, "admission_s": 0.5,
        "decode_s": 2.0, "host_gap_s": 0.0,
        "failover_redo_s": 0.0}) == "defer"
    assert obs_journey.dominant_cause("ok", {k: 0.0 for k in
                                             obs_journey.PHASE_KEYS}) \
        == "other"


# -- batcher wiring ---------------------------------------------------------

def test_batcher_journey_full_lifecycle(tiny):
    cfg, params = tiny
    srv = _batcher(tiny)
    pv = _pv(cfg)
    r0 = srv.submit(_ids(), pv, 8, slo=SLO("batch", latency_s=30.0))
    out = srv.run_until_drained()
    j = srv.journey(r0)
    assert j is not None and j["finished"]
    kinds = [e["kind"] for e in j["events"]]
    assert kinds[0] == "submit" and kinds[-1] == "finish"
    assert "queue" in kinds and "admit" in kinds and "segment" in kinds
    assert j["status"] == "ok" and j["slo_met"] is True
    assert j["tokens"] == len(out[r0]) == 8
    # The decomposition sums to the SAME latency request_stats reports
    # (identical submit/done floats by construction).
    assert sum(j["phases"].values()) == pytest.approx(j["e2e_s"], abs=1e-9)
    assert j["e2e_s"] == pytest.approx(
        srv.request_stats[r0]["latency_s"], abs=1e-9)
    # The index surfaces it newest-first with the compact fields.
    idx = srv.journey_index()
    assert idx[0]["rid"] == r0 and idx[0]["status"] == "ok"


def test_terminal_status_audit_matches_finish_status(tiny):
    """Terminal-status audit (ISSUE 10 satellite): every terminal path
    writes exactly one ``finish_status`` and the journey's finish
    carries the byte-identical status string — ok, deadline (queued
    AND active), cancel (queued AND active), NaN quarantine."""
    cfg, params = tiny
    pv = _pv(cfg)
    nan_pv = pv.copy()
    nan_pv[:] = np.nan
    srv = _batcher(tiny, max_batch=1)
    statuses = {}

    # ok
    r_ok = srv.submit(_ids(), pv, 4)
    srv.run_until_drained()
    statuses[r_ok] = "ok"
    # cancelled while queued (row busy with an active request)
    r_long = srv.submit(_ids((21,)), pv, 16)
    srv.step()  # r_long admits and decodes
    r_cq = srv.submit(_ids((22,)), pv, 4)
    assert srv.cancel(r_cq)
    statuses[r_cq] = "cancelled"
    # deadline expired while queued
    r_dq = srv.submit(_ids((23,)), pv, 4, deadline_s=0.0)
    time.sleep(0.002)
    srv.step()
    statuses[r_dq] = "deadline_exceeded"
    # cancelled while actively decoding
    assert srv.cancel(r_long)
    statuses[r_long] = "cancelled"
    srv.run_until_drained()
    # NaN quarantine at admission
    r_nan = srv.submit(_ids((24,)), nan_pv, 4)
    srv.run_until_drained()
    statuses[r_nan] = "nan_quarantined"

    forced_kind = {"deadline_exceeded": "deadline", "cancelled": "cancel",
                   "nan_quarantined": "nan_quarantine"}
    for rid, want in statuses.items():
        assert srv.finish_status[rid] == want, rid
        j = srv.journey(rid)
        assert j is not None and j["finished"], rid
        # Byte-identical status, exactly one finish event.
        assert j["status"] == srv.finish_status[rid], rid
        fins = [e for e in j["events"] if e["kind"] == "finish"]
        assert len(fins) == 1 and fins[0]["status"] == want, rid
        if want in forced_kind:
            assert any(e["kind"] == forced_kind[want]
                       for e in j["events"]), (rid, j["events"])
        assert sum(j["phases"].values()) == pytest.approx(j["e2e_s"],
                                                          abs=1e-9)
    # No terminal path finished a journey twice.
    assert obs_journey.active().stats()["duplicate_finishes"] == 0


def test_engine_fault_sweep_finishes_journeys_as_engine_fault(tiny):
    """Forced finishes from the ENGINE fault sweep bypass
    _record_finish — the sweep must close the journals itself, with the
    same terminal status the engine reports (the audit's engine leg)."""
    from eventgpt_tpu.cli.serve import ServingEngine
    from eventgpt_tpu.data.tokenizer import load_tokenizer

    cfg, _ = tiny
    eng = ServingEngine(_batcher(tiny), load_tokenizer("byte"),
                        breaker_threshold=1)
    try:
        # Park the loop so the fault lands deterministically.
        eng._stop = True
        eng._wake.set()
        eng._thread.join(timeout=10)
        rid_q = eng.submit_ids(_ids(), _pv(cfg), 4)       # stays queued
        eng.batcher.step()                                # admits + decodes
        rid_row = rid_q
        rid_q2 = eng.submit_ids(_ids((31,)), _pv(cfg), 4)
        eng._on_fault(RuntimeError("boom"))  # threshold 1: trips, sweeps all
        for rid in (rid_row, rid_q2):
            assert eng._status[rid] == "engine_fault", rid
            j = eng.journey(rid)
            assert j is not None and j["status"] == "engine_fault", rid
            fins = [e for e in j["events"] if e["kind"] == "finish"]
            assert len(fins) == 1, rid
        assert obs_journey.active().stats()["duplicate_finishes"] == 0
    finally:
        eng.shutdown()


def test_export_closes_journey_as_exported_without_finish_status(tiny):
    cfg, params = tiny
    srv = _batcher(tiny, max_batch=1)
    r0 = srv.submit(_ids(), _pv(cfg), 8)
    srv.step()
    recs = srv.export_requests()
    assert [r["rid"] for r in recs] == [r0]
    j = srv.journey(r0)
    assert j is not None and j["status"] == "exported"
    assert r0 not in srv.finish_status  # journey-only terminal


def test_mem_guard_defer_lands_in_the_timeline(tiny):
    """A deferred admission's timeline shows the mem_guard_defer event
    and its decomposition charges the deferred wait to defer_s, not
    queue_s — the 'why was this request late' answer ISSUE 9's
    aggregate counter could not give."""
    from eventgpt_tpu.obs import memory as obs_memory

    cfg, params = tiny
    srv = _batcher(tiny, prefix_cache=False, mem_headroom_bytes=1,
                   mem_capacity_bytes=obs_memory.LEDGER.total() + 2)
    pv = _pv(cfg)
    r1 = srv.submit(_ids(), pv, 8)
    srv.step()  # idle bypass: r1 admits
    r2 = srv.submit(_ids((3,)), pv, 4)
    srv.step()
    assert srv.mem_deferrals >= 1
    srv.run_until_drained()
    j = srv.journey(r2)
    assert any(e["kind"] == "mem_guard_defer" for e in j["events"])
    assert j["phases"]["defer_s"] > 0.0
    assert sum(j["phases"].values()) == pytest.approx(j["e2e_s"], abs=1e-9)


def test_chains_byte_identical_armed_vs_disarmed(tiny):
    cfg, params = tiny
    pv = _pv(cfg)
    reqs = [(_ids((40 + i,)), 4 + i) for i in range(3)]
    chains = []
    for armed in (True, False):
        obs_journey.configure(256) if armed else obs_journey.disable()
        srv = _batcher(tiny)
        rids = [srv.submit(ids, pv, n) for ids, n in reqs]
        out = srv.run_until_drained()
        chains.append([out[r] for r in rids])
    assert chains[0] == chains[1]


def test_miss_cause_metric_counts_every_missed_finish(tiny):
    cfg, params = tiny
    srv = _batcher(tiny)
    causes = obs_metrics.METRIC_LABELS[
        "egpt_serve_slo_miss_cause_total"]["cause"]
    assert causes == obs_journey.MISS_CAUSES  # the two literals agree

    def total():
        return sum(obs_metrics.SERVE_SLO_MISS_CAUSE.value(
            slo_class="interactive", cause=c) for c in causes)

    before = total()
    # An unmeetable TTFT target: every request misses.
    slo = SLO("interactive", ttft_s=1e-9)
    rids = [srv.submit(_ids((50 + i,)), _pv(cfg), 4, slo=slo)
            for i in range(3)]
    srv.run_until_drained()
    assert total() - before == 3
    for rid in rids:
        assert srv.journey(rid)["cause"] in causes


# -- HTTP surface -----------------------------------------------------------

def _serve_http(engine, cfg):
    from http.server import ThreadingHTTPServer

    from eventgpt_tpu.cli.serve import make_handler

    httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                make_handler(engine, cfg))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


def _event_npy_b64(tmp_path, n=4000):
    import base64
    import os

    from eventgpt_tpu.ops.raster import STREAM_DTYPE

    rng = np.random.default_rng(0)
    arr = np.zeros(n, dtype=STREAM_DTYPE)
    arr["x"] = rng.integers(0, 64, n)
    arr["y"] = rng.integers(0, 48, n)
    arr["t"] = np.sort(rng.integers(0, 50_000, n)).astype(np.uint64)
    arr["p"] = rng.integers(0, 2, n)
    path = os.path.join(str(tmp_path), "events.npy")
    np.save(path, arr)
    with open(path, "rb") as f:
        return base64.b64encode(f.read()).decode()


def _get(url, timeout=60):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def test_http_request_requests_trace_and_debug_block(tiny, tmp_path):
    """The slow-request runbook surface (OBSERVABILITY.md): /requests
    -> /request?rid=N -> /trace?rid=N, plus the {"debug": true}
    response block — one request explained end to end over HTTP."""
    from eventgpt_tpu.cli.serve import ServingEngine
    from eventgpt_tpu.data.tokenizer import load_tokenizer
    from eventgpt_tpu.obs import trace as obs_trace

    cfg, _ = tiny
    obs_trace.configure(4096)
    eng = ServingEngine(_batcher(tiny), load_tokenizer("byte"))
    httpd, url = _serve_http(eng, cfg)
    try:
        b64 = _event_npy_b64(tmp_path)
        req = urllib.request.Request(
            url + "/v1/generate",
            json.dumps({"query": "slow?", "event_b64": b64,
                        "max_new_tokens": 4, "slo_class": "interactive",
                        "debug": True}).encode(),
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            out = json.loads(r.read())
        rid = out["rid"]
        # Debug block rode the response: timeline + decomposition.
        dbg = out["debug"]
        assert dbg["rid"] == rid and dbg["finished"]
        assert sum(dbg["phases"].values()) == pytest.approx(
            dbg["e2e_s"], abs=1e-9)
        # ... and the raw ids (the byte tokenizer drops what it cannot
        # render from "answer").
        assert len(out["token_ids"]) == out["tokens"]
        # /requests index lists it with its cause.
        idx = _get(url + "/requests")
        assert idx["enabled"] is True
        assert any(r["rid"] == rid for r in idx["requests"])
        # /request?rid=N returns the full timeline.
        j = _get(url + f"/request?rid={rid}")
        assert [e["kind"] for e in j["events"]][0] == "submit"
        assert j["status"] == "ok"
        # /trace?rid=N filters the span ring to this request's events.
        tr = _get(url + f"/trace?rid={rid}")
        assert tr["traceEvents"], "rid filter dropped everything"
        assert all(e.get("id") == rid
                   or (e.get("args") or {}).get("rid") == rid
                   or rid in (e.get("args") or {}).get("rids", ())
                   for e in tr["traceEvents"])
        full = _get(url + "/trace")
        assert len(full["traceEvents"]) > len(tr["traceEvents"])
        # Bad/unknown queries fail structurally.
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(url + "/request")
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(url + "/request?rid=999999")
        assert e.value.code == 404
    finally:
        httpd.shutdown()
        httpd.server_close()
        eng.shutdown()
        obs_trace.disable()


# -- fleet wiring -----------------------------------------------------------

def test_fleet_journey_routes_and_sheds(tiny):
    from eventgpt_tpu.cli.serve import ServingEngine
    from eventgpt_tpu.data.tokenizer import load_tokenizer
    from eventgpt_tpu.fleet import Fleet, FleetShedError

    cfg, _ = tiny
    tok = load_tokenizer("byte")
    engines = [ServingEngine(_batcher(tiny, max_batch=1), tok)
               for _ in range(2)]
    fleet = Fleet(engines, tok, probe_interval_s=0.01)
    try:
        f0 = fleet.submit_ids(_ids(), _pv(cfg, 5), 4,
                              slo=SLO("batch", latency_s=30.0))
        assert len(fleet.result(f0, timeout=120)) == 4
        # Collection is asynchronous (the supervisor tick finishes the
        # fleet journey): wait for it.
        deadline = time.time() + 30
        j = None
        while time.time() < deadline:
            j = fleet.journey(f0)
            if j is not None and j.get("finished"):
                break
            time.sleep(0.01)
        assert j is not None and j["finished"] and j["status"] == "ok"
        kinds = [e["kind"] for e in j["events"]]
        assert "route" in kinds
        # The stitched view attaches the replica-level timeline.
        legs = j["assignments"]
        assert len(legs) == 1 and legs[0]["journey"]["status"] == "ok"
        assert j["phases"]["failover_redo_s"] == 0.0
        assert sum(j["phases"].values()) == pytest.approx(j["e2e_s"],
                                                          abs=1e-9)
        # A policy shed records its own terminal journey.
        fleet._overloaded = lambda: (True, "forced by test")
        with pytest.raises(FleetShedError):
            fleet.submit_ids(_ids((60,)), _pv(cfg, 6), 4,
                             slo=SLO("batch", latency_s=30.0))
        shed = [r for r in fleet.journeys() if r["status"] == "shed"]
        assert shed and shed[0]["cause"] == "shed"
    finally:
        fleet.shutdown()
