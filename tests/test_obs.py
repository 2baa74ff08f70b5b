"""Telemetry subsystem (``eventgpt_tpu/obs/``): histogram bucketing edge
cases, Prometheus exposition golden text, trace ring round-trip, the
``POST /profile`` / ``GET /metrics`` / ``GET /trace`` HTTP surface, and
the load-bearing invariant — greedy chains are BYTE-IDENTICAL with
telemetry armed vs disarmed (instrumentation reads clocks, never jax
values). All fast tier: the new subsystem must be cheap enough to test
on every iteration."""

import json
import math
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from eventgpt_tpu.obs import metrics as obs_metrics
from eventgpt_tpu.obs import profiling as obs_profiling
from eventgpt_tpu.obs import series as obs_series
from eventgpt_tpu.obs import trace as obs_trace


@pytest.fixture(autouse=True)
def _restore_global_telemetry():
    """Tests flip the process-global switches; restore what was armed
    before (the module-scoped HTTP server keeps its tracer across its
    tests)."""
    prev_enabled = obs_metrics.REGISTRY.enabled
    prev_tracer = obs_trace.active()
    yield
    obs_metrics.configure(prev_enabled)
    obs_trace._tracer = prev_tracer


# -- histograms ------------------------------------------------------------


def test_log2_buckets_cover_and_double():
    b = obs_metrics.log2_buckets(0.001, 1.0)
    assert b[0] <= 0.001 and b[-1] >= 1.0
    for lo, hi in zip(b, b[1:]):
        assert hi == 2 * lo
    with pytest.raises(ValueError):
        obs_metrics.log2_buckets(0.0, 1.0)
    with pytest.raises(ValueError):
        obs_metrics.log2_buckets(2.0, 1.0)


def test_histogram_bucket_edges():
    r = obs_metrics.Registry()
    h = r.histogram("egpt_t_seconds", "t", (0.25, 0.5, 1.0))
    h.observe(0.25)       # exactly on a bound -> that bucket (le semantics)
    h.observe(0.2500001)  # just past -> next bucket
    h.observe(-1.0)       # below range -> first bucket
    h.observe(1.0)        # top bound -> last finite bucket
    h.observe(7.0, n=2)   # above range -> +Inf overflow, weighted
    text = r.render_prometheus()
    assert 'egpt_t_seconds_bucket{le="0.25"} 2' in text      # 0.25 and -1
    assert 'egpt_t_seconds_bucket{le="0.5"} 3' in text
    assert 'egpt_t_seconds_bucket{le="1"} 4' in text
    assert 'egpt_t_seconds_bucket{le="+Inf"} 6' in text
    assert "egpt_t_seconds_count 6" in text
    assert math.isclose(h.count(), 6)
    # Quantiles are bucket upper bounds; overflow reports the last bound.
    assert h.quantile(0.5) == 0.5
    assert h.quantile(0.99) == 1.0


def test_histogram_weighted_observe_and_sum():
    r = obs_metrics.Registry()
    h = r.histogram("egpt_t_seconds", "t", (1.0, 2.0))
    h.observe(0.5, n=4)
    assert h.count() == 4
    assert h._summary()["sum"] == pytest.approx(2.0)
    assert h._summary()["mean"] == pytest.approx(0.5)


def test_registration_rules():
    r = obs_metrics.Registry()
    r.counter("egpt_a_total", "a")
    with pytest.raises(ValueError, match="already registered"):
        r.counter("egpt_a_total", "again")
    with pytest.raises(ValueError, match="must match"):
        r.gauge("Bad-Name", "b")
    with pytest.raises(ValueError, match="strictly increasing"):
        r.histogram("egpt_b_seconds", "b", (2.0, 1.0))


def test_disabled_registry_is_noop():
    r = obs_metrics.Registry()
    c = r.counter("egpt_a_total", "a")
    h = r.histogram("egpt_b_seconds", "b", (1.0,))
    r.configure(False)
    c.inc(5)
    h.observe(0.5)
    assert c.total() == 0 and h.count() == 0
    r.configure(True)
    c.inc(5)
    assert c.total() == 5


# -- Prometheus exposition golden ------------------------------------------


def test_prometheus_exposition_golden():
    r = obs_metrics.Registry()
    c = r.counter("egpt_g_requests_total", "Finished requests")
    g = r.gauge("egpt_g_depth", "Queue depth")
    h = r.histogram("egpt_g_wait_seconds", "Wait", (0.5, 1.0))
    c.inc()
    c.inc(2, status="ok")
    g.set(3)
    h.observe(0.25)
    h.observe(0.75, n=2)
    h.observe(9.0)
    r.set_common_labels(process="0")
    expected = (
        "# HELP egpt_g_requests_total Finished requests\n"
        "# TYPE egpt_g_requests_total counter\n"
        'egpt_g_requests_total{process="0"} 1\n'
        'egpt_g_requests_total{process="0",status="ok"} 2\n'
        "# HELP egpt_g_depth Queue depth\n"
        "# TYPE egpt_g_depth gauge\n"
        'egpt_g_depth{process="0"} 3\n'
        "# HELP egpt_g_wait_seconds Wait\n"
        "# TYPE egpt_g_wait_seconds histogram\n"
        'egpt_g_wait_seconds_bucket{process="0",le="0.5"} 1\n'
        'egpt_g_wait_seconds_bucket{process="0",le="1"} 3\n'
        'egpt_g_wait_seconds_bucket{process="0",le="+Inf"} 4\n'
        'egpt_g_wait_seconds_sum{process="0"} 10.75\n'
        'egpt_g_wait_seconds_count{process="0"} 4\n'
    )
    assert r.render_prometheus() == expected


def test_label_escaping():
    r = obs_metrics.Registry()
    c = r.counter("egpt_e_total", "e")
    c.inc(site='a"b\\c\nd')
    text = r.render_prometheus()
    assert 'site="a\\"b\\\\c\\nd"' in text


# -- trace ring round-trip -------------------------------------------------


def test_trace_roundtrip_nesting_and_durations(tmp_path):
    tracer = obs_trace.configure(64)
    with obs_trace.span("outer", cat="test", k=1):
        time.sleep(0.002)
        with obs_trace.span("inner", cat="test"):
            time.sleep(0.001)
    obs_trace.async_begin("queued", 7, budget=8)
    obs_trace.async_end("queued", 7, status="ok")
    path = str(tmp_path / "t.trace")
    n = tracer.write(path)
    evs = obs_trace.load_trace(path)
    assert len(evs) == n == 4
    by_name = {e["name"]: e for e in evs}
    outer, inner = by_name["outer"], by_name["inner"]
    assert all(e["dur"] >= 0 for e in evs if e["ph"] == "X")
    # Spans nest: inner's interval sits inside outer's.
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    b = next(e for e in evs if e["ph"] == "b")
    e = next(e for e in evs if e["ph"] == "e")
    assert b["id"] == e["id"] == 7 and e["ts"] >= b["ts"]
    assert b["args"]["budget"] == 8 and e["args"]["status"] == "ok"


def test_trace_ring_is_bounded():
    tracer = obs_trace.configure(4)
    for i in range(10):
        obs_trace.instant(f"e{i}")
    evs = tracer.events()
    assert [e["name"] for e in evs] == ["e6", "e7", "e8", "e9"]
    assert tracer.dropped() == 6


def test_disarmed_probes_are_noops():
    obs_trace.disable()
    with obs_trace.span("x"):
        pass
    obs_trace.instant("y")
    obs_trace.async_begin("z", 1)
    obs_trace.async_end("z", 1)  # nothing to assert beyond "did not raise"
    assert obs_trace.active() is None


# -- profiling -------------------------------------------------------------


def test_profile_capture_smoke(tmp_path):
    import jax.numpy as jnp

    d = str(tmp_path / "prof")
    out = obs_profiling.capture(0.0, d)
    _ = jnp.zeros((2, 2)) + 1  # some device work inside/around the window
    assert out == d and os.path.isdir(d)
    files = [f for _, _, fs in os.walk(d) for f in fs]
    assert files, "profiler capture produced no files"
    # Annotations are armed only during a window / with a profile_dir.
    assert not obs_profiling.armed()
    obs_profiling.configure(d)
    assert obs_profiling.armed()
    obs_trace.configure(16)
    with obs_profiling.step_annotation(3):
        with obs_trace.span("unit", "test"):  # holds its TraceAnnotation
            pass
    obs_profiling.configure(None)
    assert not obs_profiling.armed()


# -- chain neutrality (the acceptance-criteria invariant) ------------------


def _tiny_serve_chains(armed: bool):
    import jax
    import numpy as np

    from eventgpt_tpu.config import EventChatConfig
    from eventgpt_tpu.models import eventchat
    from eventgpt_tpu.serve import ContinuousBatcher

    cfg = EventChatConfig.tiny()
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(5))
    rng = np.random.default_rng(0)
    pv = rng.normal(size=(cfg.num_event_frames, 3, cfg.vision.image_size,
                          cfg.vision.image_size)).astype(np.float32)
    obs_metrics.configure(armed)
    if armed:
        obs_trace.configure(4096)
    else:
        obs_trace.disable()
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=8,
                            eos_token_id=None)
    rids = [srv.submit([1, 5, -200, 9, 9], pv, 8) for _ in range(3)]
    out = srv.run_until_drained()
    return [out[r] for r in rids]


def test_chain_neutrality():
    armed = _tiny_serve_chains(True)
    # While armed: the registry saw the traffic and the ring has spans.
    assert obs_metrics.SERVE_TTFT.count() >= 3
    assert obs_metrics.SERVE_TOKENS.total() >= 24
    names = {e["name"] for e in obs_trace.active().events()}
    assert {"dispatch", "segment_fetch", "queued", "active"} <= names
    disarmed = _tiny_serve_chains(False)
    assert armed == disarmed  # byte-identical greedy chains


# -- HTTP surface: /metrics, /trace, POST /profile, /stats merge -----------


@pytest.fixture(scope="module")
def obs_server():
    from eventgpt_tpu.cli import serve as serve_cli

    ns = type("A", (), {})()
    ns.model_path = "tiny-random"
    ns.tokenizer_path = None
    ns.host, ns.port = "127.0.0.1", 0
    ns.event_root = None
    ns.conv_mode = "eventgpt_v1"
    ns.max_batch, ns.max_len, ns.chunk = 2, 256, 8
    ns.temperature = 0.0
    ns.dtype, ns.quant, ns.kv_cache = "float32", "none", "bf16"
    ns.speculative, ns.prefill_chunk, ns.warmup = 0, 0, False
    ns.mesh_data = ns.mesh_fsdp = ns.mesh_model = 1
    ns.use_event_qformer = False
    ns.pretrain_query_embedder = ns.pretrain_attention_layers = None
    httpd, engine = serve_cli.build_server(ns)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    port = httpd.server_address[1]
    yield f"http://127.0.0.1:{port}", engine
    httpd.shutdown()
    engine.shutdown()
    httpd.server_close()
    obs_trace.disable()
    obs_series.disable()


def _get(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


def test_metrics_route_is_prometheus_text(obs_server):
    url, _ = obs_server
    status, ctype, body = _get(url + "/metrics")
    assert status == 200
    assert ctype.startswith("text/plain")
    text = body.decode()
    assert text.startswith("# HELP ")
    assert "# TYPE egpt_serve_ttft_seconds histogram" in text
    assert "egpt_serve_ttft_seconds_bucket" in text
    assert "# TYPE egpt_serve_requests_total counter" in text
    # Every exposed family is a registered egpt_ name (format sanity).
    for line in text.splitlines():
        if not line.startswith("#"):
            assert line.startswith("egpt_"), line


def test_trace_route_returns_chrome_trace(obs_server):
    url, _ = obs_server
    status, _, body = _get(url + "/trace")
    assert status == 200
    obj = json.loads(body)
    assert isinstance(obj["traceEvents"], list)
    assert obj["droppedEvents"] == 0


def test_stats_merges_registry_summary(obs_server):
    url, _ = obs_server
    status, _, body = _get(url + "/stats")
    assert status == 200
    s = json.loads(body)
    assert "egpt_serve_ttft_seconds" in s["metrics"]
    assert "count" in s["metrics"]["egpt_serve_ttft_seconds"]


def test_series_and_alerts_routes(obs_server):
    """ISSUE 15: GET /series is the sampled ring (duration-aligned
    points + windowed derivations), GET /alerts the per-rule hysteresis
    state, and /stats carries the cheap "alerts" block (the "slo" /
    "memory" merge pattern) — all armed by the default
    --series_interval_s on a plain single-engine server."""
    from eventgpt_tpu.obs.series import ALERT_RULES

    url, _ = obs_server
    status, _, body = _get(url + "/series?window_s=30&n=16")
    assert status == 200
    obj = json.loads(body)
    assert obj["enabled"] is True
    assert "derived" in obj and isinstance(obj["points"], list)
    for p in obj["points"]:
        assert "age_s" in p and "t" not in p

    status, _, body = _get(url + "/alerts")
    assert status == 200
    al = json.loads(body)
    assert al["enabled"] is True
    assert set(al["rules"]) == set(ALERT_RULES)
    assert isinstance(al["active"], list) and isinstance(al["log"], list)

    status, _, body = _get(url + "/stats")
    assert status == 200
    st = json.loads(body)
    assert st["alerts"]["enabled"] is True
    assert isinstance(st["alerts"]["active"], list)

    with pytest.raises(urllib.error.HTTPError) as e:
        _get(url + "/series?window_s=bogus")
    assert e.value.code == 400


def test_post_profile_smoke(obs_server):
    url, _ = obs_server
    req = urllib.request.Request(
        url + "/profile", json.dumps({"seconds": 0.05}).encode(),
        {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        assert r.status == 200
        out = json.loads(r.read())
    assert out["seconds"] == 0.05
    d = out["profile_dir"]
    assert os.path.isdir(d)
    files = [f for _, _, fs in os.walk(d) for f in fs]
    assert files, f"no profiler output under {d}"


def test_post_profile_rejects_bad_seconds(obs_server):
    url, _ = obs_server
    req = urllib.request.Request(
        url + "/profile", json.dumps({"seconds": 1e9}).encode(),
        {"Content-Type": "application/json"})
    try:
        urllib.request.urlopen(req, timeout=30)
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400
