"""Time-series store + burn-rate alerting (ISSUE 15,
eventgpt_tpu/obs/series.py): sampler determinism on a synthetic clock,
ring retention, windowed rate/quantile derivation units, hysteresis
no-flap, the EWMA arrival estimator, armed-vs-disarmed chain identity
across engine variants, coordinator aggregation over stub workers, and
the load story — a tight-SLO saturation replay fires slo_burn +
queue_trend while the same trace at x1 fires nothing. All fast tier
except the variant chain matrix (each variant is one tiny jax build)."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from eventgpt_tpu.obs import metrics as obs_metrics
from eventgpt_tpu.obs import series as obs_series
from eventgpt_tpu.obs import trace as obs_trace
from eventgpt_tpu.obs.series import ALERT_RULES, SeriesStore


@pytest.fixture(autouse=True)
def _fresh_registry_and_store():
    """Every test gets an armed registry with zeroed counters and a
    disarmed module store; restore the disarmed default after."""
    obs_metrics.configure(True)
    obs_metrics.REGISTRY.reset()
    obs_series.disable()
    yield
    obs_series.disable()
    obs_metrics.configure(True)


def _store(**kw):
    """A store on a synthetic clock: tests pass ``now=`` explicitly, so
    the wall clock never participates."""
    kw.setdefault("interval_s", 1.0)
    kw.setdefault("keep", 64)
    return SeriesStore(clock=lambda: pytest.fail(
        "store read the real clock — tests must pass now="), **kw)


# -- sampling + retention --------------------------------------------------


def test_sample_determinism_on_synthetic_clock():
    s = _store()
    obs_metrics.SERVE_QUEUE_DEPTH.set(3.0)
    obs_metrics.SERVE_TOKENS.inc(7)
    p = s.sample_once(now=10.0)
    assert p["t"] == 10.0
    assert p["queue_depth"] == 3.0
    assert p["tokens_total"] == 7.0
    # Same registry state, later tick: only the time axis moves.
    q = s.sample_once(now=11.0)
    assert q["queue_depth"] == 3.0
    assert q["t"] == 11.0


def test_ring_retention_is_bounded():
    s = _store(keep=8)
    for i in range(20):
        s.sample_once(now=float(i))
    snap = s.snapshot(now=20.0, n=100)
    assert snap["samples"] == 20
    assert snap["dropped"] == 12
    assert len(snap["points"]) == 8
    # Oldest survivor is sample 12 (ages are duration-aligned).
    assert snap["points"][0]["age_s"] == pytest.approx(8.0)


def test_snapshot_points_are_duration_aligned():
    """No absolute perf_counter value crosses the export boundary —
    a coordinator merges worker series across process clocks."""
    s = _store()
    s.sample_once(now=1000.0)
    s.sample_once(now=1001.0)
    snap = s.snapshot(now=1001.5)
    assert [p["age_s"] for p in snap["points"]] == [1.5, 0.5]
    flat = json.dumps(snap)
    assert "1000.0" not in flat and "1001.0" not in flat


# -- derivation units ------------------------------------------------------


def test_windowed_rates_have_per_second_units():
    s = _store()
    s.sample_once(now=0.0)
    obs_metrics.SERVE_REQUESTS.inc(12, status="ok")
    obs_metrics.SERVE_TOKENS.inc(48)
    s.note_submit(6)
    s.sample_once(now=4.0)
    d = s.snapshot(now=4.0, window_s=10.0)["derived"]
    assert d["request_rate_per_s"] == pytest.approx(3.0)
    assert d["token_rate_per_s"] == pytest.approx(12.0)
    assert d["submit_rate_per_s"] == pytest.approx(1.5)


def test_windowed_quantiles_from_bucket_deltas():
    # Pre-window traffic must NOT leak into the windowed quantile: park
    # 100 fast observations, sample, then observe slow ones.
    s = _store()
    for _ in range(100):
        obs_metrics.SERVE_TTFT.observe(0.001)
    s.sample_once(now=0.0)
    for _ in range(10):
        obs_metrics.SERVE_TTFT.observe(0.9)
    s.sample_once(now=1.0)
    d = s.snapshot(now=1.0, window_s=1.0)["derived"]
    # All 10 in-window observations land in one bucket: p50 == p99 ==
    # that bucket's upper bound, and it must cover 0.9.
    assert d["ttft_p50_s"] == d["ttft_p99_s"]
    assert d["ttft_p50_s"] >= 0.9
    # The 0.001s pre-window mass would have dragged p50 to the floor.
    assert d["ttft_p50_s"] > 0.01


def test_gauge_last_min_max_over_window():
    s = _store()
    for t, v in ((0.0, 5.0), (1.0, 9.0), (2.0, 2.0)):
        obs_metrics.SERVE_QUEUE_DEPTH.set(v)
        s.sample_once(now=t)
    d = s.snapshot(now=2.0, window_s=10.0)["derived"]
    assert (d["queue_depth_last"], d["queue_depth_min"],
            d["queue_depth_max"]) == (2.0, 2.0, 9.0)


def test_ewma_arrival_estimator():
    s = _store(ewma_tau_s=2.0)
    s.sample_once(now=0.0)
    s.note_submit(10)            # 10 arrivals over the next 1s tick
    p = s.sample_once(now=1.0)
    import math
    alpha = 1.0 - math.exp(-1.0 / 2.0)
    assert p["arrival_rate_ewma"] == pytest.approx(alpha * 10.0)
    # No arrivals: the estimate decays, never jumps negative.
    q = s.sample_once(now=2.0)
    assert 0.0 < q["arrival_rate_ewma"] < p["arrival_rate_ewma"]


# -- alert rules + hysteresis ----------------------------------------------


def _slo_finish(met: int, missed: int):
    if met:
        obs_metrics.SERVE_SLO_REQUESTS.inc(met, slo_class="interactive",
                                           met="true")
    if missed:
        obs_metrics.SERVE_SLO_REQUESTS.inc(missed, slo_class="interactive",
                                           met="false")


def test_slo_burn_fires_after_arm_samples_and_clears_with_hysteresis():
    s = _store(slo_target=0.9, fast_window_s=2.0, slow_window_s=6.0,
               arm_samples=2, clear_samples=3, slo_min_finished=1)
    t = 0.0
    s.sample_once(now=t)
    # Burn both windows: 50% attainment, well under the 0.9 target.
    for _ in range(4):
        t += 1.0
        _slo_finish(met=5, missed=5)
        s.sample_once(now=t)
    al = s.alerts_snapshot(now=t)
    assert al["rules"]["slo_burn"]["active"]
    assert al["rules"]["slo_burn"]["fired"] == 1
    assert al["active"] == ["slo_burn"]
    # Recovery must hold clear_samples CLEAN ticks before it stands
    # down (the first recovery tick's fast window still straddles burn
    # samples, so it does not count).
    for i in range(4):
        t += 1.0
        _slo_finish(met=20, missed=0)
        s.sample_once(now=t)
    al = s.alerts_snapshot(now=t)
    assert not al["rules"]["slo_burn"]["active"]
    assert al["rules"]["slo_burn"]["transitions"] == 2
    states = [ev["state"] for ev in al["log"]]
    assert states == ["firing", "cleared"]


def test_slo_burn_single_miss_under_traffic_floor_stays_quiet():
    """One missed request among a handful of finishes is a 50% 'burn'
    in a short window — the volume floor keeps it from paging (the x1
    artifact leg carries exactly this shape)."""
    s = _store(slo_target=0.9, fast_window_s=2.0, slow_window_s=6.0,
               arm_samples=1, slo_min_finished=8)
    t = 0.0
    s.sample_once(now=t)
    for _ in range(6):
        t += 1.0
        _slo_finish(met=1, missed=1)   # 2 finishes/tick < floor of 8
        s.sample_once(now=t)
    assert s.alerts_snapshot(now=t)["active"] == []


def test_hysteresis_does_not_flap_on_boundary_noise():
    """Queue oscillating across the fire threshold: one firing, zero
    flapping — the clear condition (half the floor) is strictly looser
    than the fire condition."""
    s = _store(queue_min=8.0, fast_window_s=1.0, slow_window_s=20.0,
               arm_samples=2, clear_samples=3)
    t = 0.0
    # Establish a low-queue baseline so the trend test can confirm.
    for _ in range(5):
        obs_metrics.SERVE_QUEUE_DEPTH.set(0.0)
        s.sample_once(now=t)
        t += 1.0
    for depth in (9.0, 7.5, 9.0, 7.5, 9.0, 7.5, 9.0, 7.5):
        obs_metrics.SERVE_QUEUE_DEPTH.set(depth)
        s.sample_once(now=t)
        t += 1.0
    al = s.alerts_snapshot(now=t)
    assert al["rules"]["queue_trend"]["fired"] == 1
    assert al["rules"]["queue_trend"]["transitions"] == 1  # never cleared
    assert al["rules"]["queue_trend"]["active"]


def test_queue_trend_arrival_gate_orders_burst_vs_saturation():
    """With the arrival gate armed, a lone deep burst at low offered
    load does NOT fire (it drains itself), while a shallower backlog
    under sustained arrival pressure DOES — the x1-vs-x16 artifact
    separation, unit-sized."""
    def run(queue, submits_per_tick):
        obs_metrics.REGISTRY.reset()
        s = _store(queue_min=2.0, queue_arrival_min=60.0,
                   fast_window_s=2.0, slow_window_s=6.0,
                   ewma_tau_s=1.0, arm_samples=2)
        t = 0.0
        s.sample_once(now=t)
        for depth in queue:
            t += 1.0
            s.note_submit(submits_per_tick)
            obs_metrics.SERVE_QUEUE_DEPTH.set(depth)
            s.sample_once(now=t)
        return s.alerts_snapshot(now=t)["rules"]["queue_trend"]["fired"]

    assert run(queue=(14.0, 14.0, 14.0, 0.0), submits_per_tick=7) == 0
    assert run(queue=(5.0, 5.0, 5.0, 5.0), submits_per_tick=100) == 1


def test_cause_shift_fires_on_dominant_cause_divergence():
    s = _store(fast_window_s=2.0, slow_window_s=8.0, cause_min_misses=4,
               arm_samples=1)
    t = 0.0
    s.sample_once(now=t)
    for _ in range(6):   # slow window dominated by admission misses
        t += 1.0
        obs_metrics.SERVE_SLO_MISS_CAUSE.inc(2, slo_class="interactive",
                                             cause="admission")
        s.sample_once(now=t)
    assert s.alerts_snapshot(now=t)["active"] == []
    for _ in range(2):   # fast window flips to queue misses
        t += 1.0
        obs_metrics.SERVE_SLO_MISS_CAUSE.inc(4, slo_class="interactive",
                                             cause="queue")
        s.sample_once(now=t)
    al = s.alerts_snapshot(now=t)
    assert al["rules"]["cause_shift"]["active"]
    assert any(ev.get("detail") == "admission->queue" for ev in al["log"])


def test_breaker_flap_counts_state_changes():
    s = _store(slow_window_s=10.0, flap_min=3, arm_samples=1)
    t = 0.0
    for state in (0.0, 1.0, 0.0, 1.0):
        obs_metrics.SERVE_BREAKER_OPEN.set(state)
        s.sample_once(now=t)
        t += 1.0
    al = s.alerts_snapshot(now=t)
    assert al["rules"]["breaker_flap"]["active"]
    assert al["rules"]["breaker_flap"]["value"] == 3.0


def test_mem_shrink_needs_capacity_and_fires_on_low_headroom():
    s = _store(arm_samples=1)                     # no capacity: inert
    obs_metrics.MEM_TOTAL.set(1e9)
    s.sample_once(now=0.0)
    assert s.alerts_snapshot(now=0.0)["active"] == []
    s = _store(mem_capacity_bytes=1000, mem_headroom_frac=0.1,
               arm_samples=2)
    t = 0.0
    for total in (800.0, 920.0, 960.0):
        obs_metrics.MEM_TOTAL.set(total)
        s.sample_once(now=t)
        t += 1.0
    al = s.alerts_snapshot(now=t)
    assert al["rules"]["mem_shrink"]["active"]
    assert al["rules"]["mem_shrink"]["value"] == pytest.approx(0.04)


def test_transitions_export_gauge_and_counter():
    obs_series.configure(interval_s=1.0, keep=16, autostart=False,
                         queue_min=2.0, fast_window_s=2.0,
                         slow_window_s=6.0, arm_samples=1)
    store = obs_series.active()
    for t in range(5):            # low-queue baseline for the trend test
        store.sample_once(now=float(t))
    obs_metrics.SERVE_QUEUE_DEPTH.set(50.0)
    store.sample_once(now=5.0)
    text = obs_metrics.REGISTRY.render_prometheus()
    assert 'egpt_alert_active{rule="queue_trend"} 1' in text
    assert 'egpt_alert_transitions_total{rule="queue_trend"} 1' in text
    # Every rule renders 0/1 from configure-time pre-set, never absent.
    for rule in ALERT_RULES:
        assert f'egpt_alert_active{{rule="{rule}"}}' in text


def test_alert_rules_literal_matches_metric_label_enum():
    assert obs_metrics.METRIC_LABELS["egpt_alert_active"]["rule"] == \
        ALERT_RULES
    assert obs_metrics.METRIC_LABELS[
        "egpt_alert_transitions_total"]["rule"] == ALERT_RULES


# -- module arming + probes ------------------------------------------------


def test_disarmed_probes_are_noops():
    obs_series.disable()
    assert not obs_series.enabled()
    obs_series.note_submit()          # must not raise, must not arm
    assert obs_series.sample_now() is None
    assert obs_series.snapshot() == {"enabled": False}
    assert obs_series.alerts() == {"enabled": False}
    st = obs_series.alert_stats()
    assert st["enabled"] is False


def test_configure_arms_and_interval_zero_disarms():
    obs_series.configure(interval_s=0.5, keep=32, autostart=False)
    assert obs_series.enabled()
    obs_series.note_submit(3)
    obs_series.sample_now()
    snap = obs_series.snapshot()
    assert snap["enabled"] and snap["samples"] == 1
    obs_series.configure(interval_s=0.0)
    assert not obs_series.enabled()


def test_sampler_thread_runs_on_cadence():
    obs_series.configure(interval_s=0.02, keep=64, autostart=True)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if obs_series.snapshot()["samples"] >= 3:
            break
        time.sleep(0.01)
    assert obs_series.snapshot()["samples"] >= 3
    obs_series.disable()


# -- chain identity across engine variants ---------------------------------


VARIANTS = {
    "plain": {},
    "int8_kv": {"kv_quant": True},
    "paged": {"kv_layout": "paged"},
    "spec": {"speculative": 2},
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_chains_identical_armed_vs_disarmed(variant):
    """The acceptance invariant per engine variant: the sampler reads
    host clocks and registry floats only, so arming it (tight cadence,
    sampling DURING decode) must not move a single token."""
    import jax

    from eventgpt_tpu.config import EventChatConfig
    from eventgpt_tpu.models import eventchat
    from eventgpt_tpu.serve import ContinuousBatcher

    cfg = EventChatConfig.tiny()
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(5))
    rng = np.random.default_rng(0)
    pv = rng.normal(size=(cfg.num_event_frames, 3, cfg.vision.image_size,
                          cfg.vision.image_size)).astype(np.float32)

    def run(armed):
        if armed:
            obs_series.configure(interval_s=0.005, keep=512,
                                 autostart=True, queue_min=1.0,
                                 arm_samples=1)
        else:
            obs_series.disable()
        srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256,
                                chunk=8, eos_token_id=None,
                                **VARIANTS[variant])
        rids = [srv.submit([1, 5, -200, 9, 9], pv, 8) for _ in range(3)]
        out = srv.run_until_drained()
        return [out[r] for r in rids]

    armed = run(True)
    assert obs_series.snapshot()["samples"] >= 1
    disarmed = run(False)
    assert armed == disarmed


# -- saturation replay: alerts fire at x16, stay quiet at x1 ---------------


class _Throttled:
    """Step-rate governor around a ContinuousBatcher: pins service
    capacity BETWEEN the x1 and x16 offered loads so the saturation
    contrast is a property of the test, not of how fast this CPU runs
    the (very fast when warm) tiny model."""

    def __init__(self, inner, delay_s):
        self._inner, self._delay = inner, delay_s

    def step(self):
        time.sleep(self._delay)
        return self._inner.step()

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_saturation_replay_fires_alerts_x16_but_not_x1():
    """The closed-loop acceptance property on the REAL serving path:
    one trace, one alerting config, two offered loads. At x1 (healthy:
    arrivals slower than service, generous targets) NO rule fires —
    the arrival gate keeps a gamma clump from reading as saturation
    and the traffic floor keeps a stray miss from reading as burn. At
    x16 (saturated: the whole trace lands in a burst, targets tight)
    queue_trend fires on sustained depth + arrival pressure and
    slo_burn fires on windowed attainment collapse."""
    import jax

    from eventgpt_tpu import workload as wl
    from eventgpt_tpu.config import EventChatConfig
    from eventgpt_tpu.models import eventchat
    from eventgpt_tpu.serve import ContinuousBatcher

    cfg = EventChatConfig.tiny()
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(5))
    spec = wl.WorkloadSpec(seed=11, n_requests=28, rate_rps=6.0,
                           arrival="gamma", sessions=2, prompt_max=16,
                           output_min=6, output_max=10)
    trace = wl.generate_trace(spec)

    def pixels_for(r):
        rng = np.random.default_rng(r.pixels_seed)
        return rng.normal(
            size=(cfg.num_event_frames, 3, cfg.vision.image_size,
                  cfg.vision.image_size)).astype(np.float32)

    def leg(rate_mult, slo):
        srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256,
                                chunk=4, eos_token_id=None)
        # Warm EVERY shape the measured replay will hit (full trace,
        # unpaced, store disarmed) so compile stalls never masquerade
        # as saturation.
        wl.replay(srv, trace, pixels_for=pixels_for, paced=False)
        obs_metrics.REGISTRY.reset()
        obs_series.configure(
            interval_s=0.02, keep=4096, autostart=True,
            fast_window_s=0.4, slow_window_s=1.5, slo_min_finished=3,
            queue_min=3.0, queue_arrival_min=24.0, ewma_tau_s=0.5,
            arm_samples=2, clear_samples=3)
        try:
            wl.replay(_Throttled(srv, 0.008), trace,
                      pixels_for=pixels_for, rate_mult=rate_mult,
                      paced=True, slo_for=lambda r: slo)
            return obs_series.alerts()["rules"]
        finally:
            obs_series.disable()

    generous = wl.SLO("interactive", ttft_s=30.0, itl_s=10.0,
                      latency_s=120.0)
    tight = wl.SLO("interactive", ttft_s=0.005, itl_s=0.002,
                   latency_s=0.01)

    quiet = leg(1.0, generous)
    assert sum(r["fired"] for r in quiet.values()) == 0, quiet

    hot = leg(16.0, tight)
    assert hot["queue_trend"]["fired"] >= 1, hot
    assert hot["slo_burn"]["fired"] >= 1, hot
