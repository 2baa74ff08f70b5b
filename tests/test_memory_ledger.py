"""HBM memory ledger (ISSUE 9): component accounting, the static
capacity model held byte-exact against live buffers, live-array
reconciliation (the ≥90% acceptance bar, in a clean subprocess),
headroom-guard semantics (defer-then-drain, idle bypass, chain
neutrality, the ``serve.mem_guard`` chaos site), the compiled-footprint
probe, and the ledger's lock discipline (spy-lock: byte counters mutate
inside the critical section)."""

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventgpt_tpu import faults
from eventgpt_tpu.config import EventChatConfig
from eventgpt_tpu.models import eventchat
from eventgpt_tpu.obs import memory as obs_memory
from eventgpt_tpu.obs.memory import COMPONENTS, MemoryLedger
from eventgpt_tpu.serve import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny():
    cfg = EventChatConfig.tiny()
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(5))
    return cfg, params


def _pv(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(cfg.num_event_frames, 3, cfg.vision.image_size,
                            cfg.vision.image_size)).astype(np.float32)


def _ids(n_tail=2):
    return [1] + [7] * 3 + [-200] + [9] * n_tail


def _oneshot(params, cfg, ids, pv, budget):
    return eventchat.generate(
        params, cfg, [ids], jnp.asarray(pv)[None], max_new_tokens=budget,
        temperature=0.0, eos_token_id=None,
    )[0]


# -- ledger arithmetic ------------------------------------------------------


def test_ledger_register_resize_release_and_peak():
    led = MemoryLedger()
    led.register("kv_cache", "a/kv", 100)
    led.register("weights", "shared/w", 50)
    assert led.total() == 150 and led.peak_bytes == 150
    led.resize("kv_cache", "a/kv", 40)  # shrink moves the delta
    assert led.total() == 90
    assert led.peak_bytes == 150  # peak is a high-water mark
    led.reset_peak()
    assert led.peak_bytes == 90
    led.release("kv_cache", "a/kv")
    led.release("kv_cache", "a/kv")  # repeat release is a no-op
    assert led.total() == 50
    assert led.snapshot() == {"weights": 50}
    # Owner filter sees only that namespace's keys.
    led.register("kv_cache", "b1/kv_cache", 7)
    assert led.snapshot(owner="b1") == {"kv_cache": 7}
    assert led.snapshot(owner="nope") == {}
    s = led.summary()
    assert s["total_bytes"] == 57 and s["entries"] == 2


def test_ledger_rejects_unknown_component():
    led = MemoryLedger()
    with pytest.raises(ValueError, match="unknown memory component"):
        led.register("hbm_misc", "x", 1)


def test_components_match_metric_label_enum():
    """The ledger validates at register time, the metric class at
    observe time — the two literals must stay identical or a legal
    component would raise at gauge export."""
    from eventgpt_tpu.obs.metrics import METRIC_LABELS

    assert tuple(METRIC_LABELS["egpt_mem_component_bytes"]["component"]) \
        == tuple(COMPONENTS)


# -- static capacity model vs live buffers ----------------------------------


def test_estimate_matches_live_buffers_byte_exact(tiny):
    """The capacity model's kv/logits/weights terms equal the resident
    buffers' real nbytes — the closed form IS the constructor's
    arithmetic, not an approximation."""
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=8)
    est = srv.memory_estimate()["components"]
    assert est["kv_cache"] == obs_memory.params_bytes(srv.cache)
    assert est["logits"] == srv.logits.nbytes
    assert est["weights"] == obs_memory.params_bytes(params)
    # And the ledger registered exactly those numbers.
    own = obs_memory.LEDGER.snapshot(srv._mem_owner)
    assert own["kv_cache"] == est["kv_cache"]
    assert own["logits"] == est["logits"]


def test_estimate_matches_lane_buffers_and_int8_kv(tiny):
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=8,
                            kv_quant=True, prefill_budget=8)
    est = srv.memory_estimate()["components"]
    assert est["kv_cache"] == obs_memory.params_bytes(srv.cache)
    # int8 KV: payload halves, scale plane rides along — strictly below
    # the bf16 form of the same shape.
    bf16 = obs_memory.estimate(cfg, max_batch=2, max_len=256)
    assert est["kv_cache"] < bf16["components"]["kv_cache"]
    # Lane buffers: allocate at the default bucket and compare exactly
    # (the lane cache is ALWAYS unquantized — the exactness rule).
    srv._ensure_lane_buffers(64)
    live_lanes = (obs_memory.params_bytes(srv._lane_cache)
                  + srv._lane_embeds.nbytes)
    est2 = srv.memory_estimate()["components"]
    assert est2["lanes"] == live_lanes
    assert obs_memory.LEDGER.snapshot(srv._mem_owner)["lanes"] == live_lanes


def test_estimate_sharding_divisors_compose_with_parallel_serving(tiny):
    """The mesh arithmetic in estimate() is the SAME rule set
    parallel/serving.py applies: batch over the largest dividing prefix
    of (data, fsdp), KV heads over model when divisible."""
    from eventgpt_tpu.config import MeshConfig
    from eventgpt_tpu.parallel import make_mesh
    from eventgpt_tpu.parallel.serving import serving_batch_axes

    cfg, _ = tiny
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, context=1, model=2))
    batch = 4
    est = obs_memory.estimate(cfg, max_batch=batch, max_len=256,
                              mesh_shape=dict(mesh.shape))
    prod = 1
    for ax in serving_batch_axes(mesh, batch):
        prod *= mesh.shape[ax]
    assert est["divisors"]["batch"] == prod == 4
    model_n = mesh.shape["model"]
    want_heads = model_n if cfg.llama.num_kv_heads % model_n == 0 else 1
    assert est["divisors"]["kv_heads"] == want_heads == 2
    full = obs_memory.estimate(cfg, max_batch=batch, max_len=256)
    assert est["per_device"]["kv_cache"] == \
        full["components"]["kv_cache"] // (4 * 2)


# -- prefix cache + spy lock ------------------------------------------------


def test_prefix_cache_bytes_tracked_through_insert_and_evict(tiny):
    cfg, params = tiny
    probe = ContinuousBatcher(params, cfg, max_batch=1, max_len=256)
    probe.set_prefix(_ids()[:5], pixel_values=_pv(cfg))
    entry_bytes = probe._prefix_cache.entries()[0].nbytes
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=256,
                            prefix_cache_bytes=2 * entry_bytes)
    own = lambda: obs_memory.LEDGER.snapshot(srv._mem_owner).get(
        "prefix_cache", 0)
    assert own() == 0
    srv.set_prefix(_ids()[:5], pixel_values=_pv(cfg, 1))
    assert own() == srv._prefix_cache.bytes == entry_bytes
    srv.set_prefix(_ids()[:5], pixel_values=_pv(cfg, 2))
    srv.set_prefix(_ids()[:5], pixel_values=_pv(cfg, 3))  # evicts LRU
    assert srv._prefix_cache.evictions >= 1
    assert own() == srv._prefix_cache.bytes <= 2 * entry_bytes


class _SpyLock:
    """Records the ledger's total at every acquire/release — proves the
    byte-counter mutation lands INSIDE the critical section (the
    lock-discipline contract the egpt-check ``lock`` rule asserts
    statically; this is the runtime spy for the evict/admit paths)."""

    def __init__(self, ledger):
        self._ledger = ledger
        self._real = threading.Lock()
        self.events = []

    def __enter__(self):
        self._real.acquire()
        self.events.append(("enter", self._ledger.total_bytes))
        return self

    def __exit__(self, *exc):
        self.events.append(("exit", self._ledger.total_bytes))
        self._real.release()
        return False


def test_prefix_admit_and_evict_mutate_ledger_bytes_under_the_lock(
        tiny, monkeypatch):
    cfg, params = tiny
    led = MemoryLedger()
    monkeypatch.setattr(obs_memory, "LEDGER", led)
    probe = ContinuousBatcher(params, cfg, max_batch=1, max_len=256)
    probe.set_prefix(_ids()[:5], pixel_values=_pv(cfg))
    entry_bytes = probe._prefix_cache.entries()[0].nbytes
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=256,
                            prefix_cache_bytes=entry_bytes)
    before = led.total()
    spy = _SpyLock(led)
    led._lock = spy
    try:
        srv.set_prefix(_ids()[:5], pixel_values=_pv(cfg, 1))  # insert
        srv.set_prefix(_ids()[:5], pixel_values=_pv(cfg, 2))  # + evict
    finally:
        led._lock = threading.Lock()
    assert srv._prefix_cache.evictions >= 1
    # First acquire saw the PRE-insert total (nothing mutated outside
    # the lock) and some release saw the insert land.
    assert spy.events[0] == ("enter", before)
    assert ("exit", before + entry_bytes) in spy.events
    # The evict+insert round-trip settles back at one entry's bytes,
    # and every mutation happened between an enter and its exit.
    assert led.total() == before + entry_bytes


# -- headroom guard ---------------------------------------------------------


def test_mem_guard_defers_then_drains_and_chains_hold(tiny):
    """Over-budget admission waves stay queued while rows decode (the
    ledger predicts the wave), drain once the batch frees, and the
    served chains match one-shot generate exactly."""
    cfg, params = tiny
    total_now = obs_memory.LEDGER.total()
    srv = ContinuousBatcher(
        params, cfg, max_batch=2, max_len=256, chunk=4,
        eos_token_id=None, prefix_cache=False, mem_headroom_bytes=1,
        # Capacity leaves NO room for any admission wave: every guarded
        # boundary defers.
        mem_capacity_bytes=total_now + 2,
    )
    pv = _pv(cfg)
    r1 = srv.submit(_ids(), pv, 8)
    srv.step()  # idle server: guard bypassed, r1 admits
    assert srv.rows.count(None) == srv.max_batch - 1
    r2 = srv.submit(_ids(3), pv, 4)
    srv.step()
    # r1 is decoding -> the wave for r2 is deferred, not dropped; once
    # r1 finishes (freeing its bytes) the idle bypass admits r2.
    assert srv.mem_deferrals >= 1
    assert any(req.rid == r2 for req in srv.queue)
    out = srv.run_until_drained()
    assert out[r1] == _oneshot(params, cfg, _ids(), pv, 8)
    assert out[r2] == _oneshot(params, cfg, _ids(3), pv, 4)


@pytest.mark.parametrize("kv_quant,speculative", [(False, 0), (True, 0),
                                                  (False, 3)])
def test_mem_guard_armed_vs_disarmed_chains_byte_identical(
        tiny, kv_quant, speculative):
    """The ISSUE 9 acceptance bar: guard + ledger armed (with real
    headroom) vs disarmed — greedy chains byte-identical across the
    serve matrix axes (plain / int8-KV / speculative)."""
    cfg, params = tiny
    pv = _pv(cfg)
    reqs = [(_ids(i + 1), 4 + i) for i in range(3)]
    chains = []
    for armed in (True, False):
        srv = ContinuousBatcher(
            params, cfg, max_batch=2, max_len=256, chunk=4,
            eos_token_id=None, kv_quant=kv_quant, speculative=speculative,
            mem_headroom_bytes=1024 if armed else 0,
            mem_capacity_bytes=(obs_memory.LEDGER.total()
                                + (64 << 20)) if armed else 0,
        )
        rids = [srv.submit(i, pv, b) for i, b in reqs]
        out = srv.run_until_drained()
        chains.append([out[r] for r in rids])
    assert chains[0] == chains[1]


def test_mem_guard_fault_site_degrades_to_admission(tiny):
    """Chaos: a ``serve.mem_guard`` trip degrades THAT boundary to
    guard-off — the admission proceeds (availability over protection),
    the trip is counted, and the engine never sees the fault."""
    cfg, params = tiny
    faults.configure("serve.mem_guard:n=1")
    try:
        srv = ContinuousBatcher(
            params, cfg, max_batch=2, max_len=256, chunk=4,
            eos_token_id=None, prefix_cache=False, mem_headroom_bytes=1,
            mem_capacity_bytes=obs_memory.LEDGER.total() + 2,
        )
        pv = _pv(cfg)
        r1 = srv.submit(_ids(), pv, 8)
        srv.step()  # idle bypass: no guard probe consumed
        r2 = srv.submit(_ids(3), pv, 4)
        srv.step()  # first guarded boundary: the trip fires HERE
        st = faults.stats()["serve.mem_guard"]
        assert st["fires"] == 1
        # The degraded boundary admitted r2 instead of deferring it.
        assert srv.mem_deferrals == 0
        assert not any(req.rid == r2 for req in srv.queue)
        out = srv.run_until_drained()
        assert out[r2] == _oneshot(params, cfg, _ids(3), pv, 4)
        assert out[r1] == _oneshot(params, cfg, _ids(), pv, 8)
    finally:
        faults.configure(None)


# -- reconciliation + probe + surfaces --------------------------------------


def test_reconciliation_accounts_90pct_in_clean_process():
    """THE acceptance criterion: on the CPU tiny model, registered
    component bytes cover ≥ 90% of jax.live_arrays() after warmup.
    Runs in a fresh subprocess — the test suite's own session fixtures
    hold live arrays this process's ledger never registered."""
    script = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import jax
import numpy as np
from eventgpt_tpu.config import EventChatConfig
from eventgpt_tpu.models import eventchat
from eventgpt_tpu.obs import memory as obs_memory
from eventgpt_tpu.serve import ContinuousBatcher

cfg = EventChatConfig.tiny()
params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(5))
srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=8,
                        prefill_budget=8)
srv.warmup(prompt_lens=[40])
pv = np.random.default_rng(0).normal(
    size=(cfg.num_event_frames, 3, cfg.vision.image_size,
          cfg.vision.image_size)).astype(np.float32)
rid = srv.submit([1] + [7] * 3 + [-200] + [9] * 2, pv, 6)
srv.run_until_drained()
print(json.dumps(obs_memory.LEDGER.reconcile()))
"""
    proc = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["live_bytes"] > 0
    assert rec["accounted_ratio"] >= 0.90, rec


def test_compiled_footprint_probe_reports_xla_sizes(tiny):
    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=8)
    fp = srv.compiled_footprint()
    assert fp["segment"] == "decode" and fp["chunk"] == 8
    if "unavailable" not in fp:  # backend-dependent; CPU supports it
        for k in ("temp_bytes", "argument_bytes", "output_bytes"):
            assert isinstance(fp[k], int) and fp[k] >= 0
        # The donated resident cache must alias, not double-allocate.
        assert fp["alias_bytes"] >= obs_memory.params_bytes(srv.cache)
    # warmup() stores the probe so GET /memory never compiles cold.
    srv2 = ContinuousBatcher(params, cfg, max_batch=1, max_len=256, chunk=8)
    srv2.warmup(prompt_lens=[40])
    assert srv2._compiled_footprint is not None


def test_engine_stats_merge_and_memory_route_payload(tiny):
    from eventgpt_tpu.cli.serve import ServingEngine
    from eventgpt_tpu.data.tokenizer import load_tokenizer

    cfg, params = tiny
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=256, chunk=8)
    eng = ServingEngine(srv, load_tokenizer("byte"))
    try:
        st = eng.stats()
        # One /stats poll shows latency, goodput AND bytes (ISSUE 9).
        assert st["memory"]["total_bytes"] > 0
        assert st["memory"]["components"]["kv_cache"] > 0
        assert st["memory"]["guard"]["headroom_bytes"] == 0
        ms = eng.memory_stats()
        assert ms["reconcile"]["live_bytes"] > 0
        assert ms["estimate"]["components"]["kv_cache"] == \
            obs_memory.params_bytes(srv.cache)
        assert "compiled" in ms and "owner" in ms
    finally:
        eng.shutdown()


def test_fleet_memory_stats_report_per_replica_share(tiny):
    from eventgpt_tpu.cli.serve import ServingEngine
    from eventgpt_tpu.data.tokenizer import load_tokenizer
    from eventgpt_tpu.fleet import Fleet

    cfg, params = tiny
    batchers = [ContinuousBatcher(params, cfg, max_batch=1, max_len=256,
                                  chunk=8) for _ in range(2)]
    engines = [ServingEngine(b, load_tokenizer("byte")) for b in batchers]
    fleet = Fleet(engines, probe_interval_s=0.02)
    try:
        ms = fleet.memory_stats()
        assert len(ms["replicas"]) == 2
        for rep in ms["replicas"]:
            assert rep["components"]["kv_cache"] == \
                obs_memory.params_bytes(batchers[rep["replica"]].cache)
        # /fleet per-replica summary carries the byte share too.
        per = fleet.stats()["fleet"]["per_replica"]
        for r in per:
            assert r["memory_bytes"] > 0
        # One shared weight tree: the process total counts it ONCE —
        # strictly less than weights-per-replica double counting.
        w = obs_memory.params_bytes(params)
        owned = sum(sum(r["components"].values()) for r in ms["replicas"])
        assert ms["total_bytes"] >= owned + w
    finally:
        fleet.shutdown()
