"""A full-prefill admission in two halves (``ContinuousBatcher._stage`` /
``_land``): the row-independent half (pop, reserve, upload, tower, splice,
wave prefill into a cache of its own) is dispatched while a decode segment is
in flight, and only readback, scatter and activation wait for the drain. On
the CPU at toy widths, float32, greedy: every request's tokens are those of
the synchronous schedule and of the order of events the scheduler had before
(``_stage`` switched off), for the tiny dense preset and for the hybrid's toy
configuration."""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import loader
from eventgpt_tpu import faults
from eventgpt_tpu import serve as serve_mod
from eventgpt_tpu.config import EventChatConfig, from_hf_config
from eventgpt_tpu.constants import EVENT_TOKEN_INDEX
from eventgpt_tpu.models import eventchat, nemotron_h as nh
from eventgpt_tpu.obs import trace as obs_trace
from eventgpt_tpu.serve import ContinuousBatcher

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu", reason="a CPU test")

KINDS = ("dense", "hybrid")
_MODELS = {}


@pytest.fixture(autouse=True)
def _disarm():
    faults.disable()
    yield
    faults.disable()
    obs_trace.disable()


def model(kind):
    if kind not in _MODELS:
        if kind == "dense":
            cfg = EventChatConfig.tiny()
            params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(0))
        else:
            toy = loader.read_json(os.path.join(
                loader.HERE, "configs", "nemotron3-super-tiny.json"))
            cfg = from_hf_config(toy, attn_impl="dense")
            params = eventchat.init_eventchat_params(
                cfg, jax.random.PRNGKey(0), jnp.float32)
            params["llama"] = nh.init_params(
                cfg.llama, jax.random.PRNGKey(1), jnp.float32)
        _MODELS[kind] = cfg, params
    return _MODELS[kind]


def request(cfg, rng, n_text):
    ids = ([1] + [int(i) for i in rng.integers(3, 200, n_text)]
           + [EVENT_TOKEN_INDEX] + [int(i) for i in rng.integers(3, 200, 5)])
    side = cfg.vision.image_size
    return ids, rng.standard_normal(
        (cfg.num_event_frames, 3, side, side)).astype(np.float32)


def server(kind, **kw):
    cfg, params = model(kind)
    kw = {"max_batch": 2, "max_len": 256, "chunk": 2, "eos_token_id": None,
          "prefix_cache": False, **kw}
    return ContinuousBatcher(params, cfg, **kw)


def admits(ring):
    """(n, staged, children) of every ``sched.admit`` span of the ring."""
    spans = [e for e in ring if e.get("ph") == "X"]
    out = []
    for e in spans:
        if e["name"] == "admit" and e.get("cat") == "sched":
            kids = sorted(
                k["name"] for k in spans if k.get("cat") == "admit"
                and e["ts"] <= k["ts"] and k["ts"] + k["dur"] <= e["ts"] + e["dur"]
                and k["args"].get("parent") == "admit")
            out.append((e["args"]["n"], e["args"]["staged"], kids))
    return out


def serve_staggered(srv, groups, steps_between):
    """Submit group after group, ``steps_between`` scheduler steps apart."""
    rids = []
    groups = list(groups)
    while groups or srv.queue or any(r is not None for r in srv.rows):
        if groups:
            rids += [srv.submit(*req) for req in groups.pop(0)]
            for _ in range(steps_between):
                srv.step()
        else:
            srv.step()
    srv._drain()
    return rids, dict(srv.finished)


def decoding(srv, budget=40, n=1):
    """``srv`` with ``n`` requests decoding, a segment in flight, a row free
    and nothing queued: where ``_stage`` has something to hide behind."""
    cfg = srv.cfg
    rng = np.random.default_rng(11)
    rids = [srv.submit(*request(cfg, rng, 6 + i), budget) for i in range(n)]
    srv.step()
    assert srv._inflight is not None and srv._staged is None
    assert any(r is None for r in srv.rows)
    return rids


def alone(kind, req, budget):
    one = server(kind, max_batch=1, pipeline=False)
    rid = one.submit(*req, budget)
    return one.run_until_drained()[rid]


# -- the same tokens under every order of events ---------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_tokens_are_those_of_the_synchronous_and_of_the_old_order(kind):
    """Two rows, seven requests of unequal prompts and budgets arriving in
    groups: finished rows are handed on. Staged, the wave's prefill was
    dispatched with a segment in flight for most of them; the tokens are
    those of ``pipeline=False`` and of the drained order the scheduler had."""
    cfg, _ = model(kind)
    rng = np.random.default_rng(0)
    reqs = [request(cfg, rng, n) + (b,) for n, b in
            ((7, 3), (12, 9), (9, 6), (15, 7), (11, 5), (8, 4), (13, 8))]
    groups = [reqs[:3], reqs[3:5], reqs[5:]]
    seen = {}
    for order in ("staged", "synchronous", "old"):
        srv = server(kind, pipeline=order != "synchronous")
        if order == "old":
            srv._stage = lambda: None
        obs_trace.configure(8192)
        try:
            rids, out = serve_staggered(srv, groups, steps_between=2)
            ring = obs_trace.active().events()
        finally:
            obs_trace.disable()
        assert srv._staged is None and all(r is None for r in srv.rows)
        seen[order] = [out[r] for r in rids], admits(ring)
    want = [alone(kind, req[:2], req[2]) for req in reqs[:2]]
    assert seen["staged"][0][:2] == want
    assert seen["staged"][0] == seen["synchronous"][0] == seen["old"][0]
    assert [len(t) for t in seen["staged"][0]] == [r[2] for r in reqs]
    # the other two never stage; here every admission but the first does
    for order in ("synchronous", "old"):
        assert all(staged == 0 for _, staged, _ in seen[order][1])
    staged_members = sum(s for n, s, kids in seen["staged"][1] if "prefill" in kids)
    assert staged_members >= len(reqs) - 3


@pytest.mark.parametrize("kind", KINDS)
def test_the_admit_spans_keep_their_children_on_both_paths(kind):
    cfg, _ = model(kind)
    rng = np.random.default_rng(1)
    srv = server(kind, max_batch=4)
    obs_trace.configure(8192)
    try:
        for n in (6, 9):                      # an idle server: the drained path
            srv.submit(*request(cfg, rng, n), 8)
        srv.step()
        for n in (7, 8):                      # a segment in flight: staged
            srv.submit(*request(cfg, rng, n), 3)
        srv.run_until_drained()
        ring = obs_trace.active().events()
    finally:
        obs_trace.disable()
    whole = ["encode", "prefill", "scatter", "upload"]
    assert admits(ring) == [
        (2, 0, whole),                               # drained: both halves
        (2, 2, ["encode", "prefill", "upload"]),     # staged: the first half
        (2, 2, ["scatter"]),                         # ... and its landing
    ]


def test_with_nothing_in_flight_the_drained_path_runs():
    cfg, _ = model("dense")
    rng = np.random.default_rng(2)
    for kw in ({"pipeline": False}, {}):
        srv = server("dense", **kw)
        staged = []
        stage = srv._stage
        srv._stage = lambda: (stage(), staged.append(srv._staged))[0]
        obs_trace.configure(4096)
        try:
            # one at a time into an idle server: no segment to hide behind
            for n in (5, 8, 6):
                srv.submit(*request(cfg, rng, n), 4)
                srv.run_until_drained()
            ring = obs_trace.active().events()
        finally:
            obs_trace.disable()
        assert staged and all(s is None for s in staged)  # asked, and no
        assert [(n, s) for n, s, _ in admits(ring)] == [(1, 0)] * 3


# -- a reserved row under the segment it was reserved under -----------------------

@pytest.mark.parametrize("kind", KINDS)
def test_a_reserved_row_passes_through_the_harvest_untouched(kind):
    cfg, _ = model(kind)
    srv = server(kind)
    (first,) = decoding(srv)
    req = request(cfg, np.random.default_rng(3), 9)
    rid = srv.submit(*req, 5)
    shared = (srv.cache, srv.logits, srv._dev_carry, srv._inflight)
    mirror = (srv.frozen.copy(), srv.n_rem.copy())
    srv._stage()
    # staging read and wrote nothing shared: the same arrays, the same mirror
    assert srv._staged is not None and not srv.queue
    assert all(a is b for a, b in zip(
        shared, (srv.cache, srv.logits, srv._dev_carry, srv._inflight)))
    assert (srv.frozen == mirror[0]).all() and (srv.n_rem == mirror[1]).all()
    ((staged_req, row),) = srv._staged.members
    assert srv.rows[row] is staged_req and srv.frozen[row]
    planes = {name: np.asarray(buf)[:, row].copy()
              for name, buf in srv.cache.items() if name in ("conv", "h")}
    assert (kind == "hybrid") == bool(planes)
    srv._drain()            # the harvest of the segment it was reserved under
    assert srv.rows[row] is staged_req and srv.frozen[row]
    assert staged_req.tokens == [] and staged_req.t_first is None
    assert srv.n_rem[row] == 0 and rid not in srv.finished
    for name, before in planes.items():
        assert (np.asarray(srv.cache[name])[:, row] == before).all()
    out = srv.run_until_drained()
    assert out[rid] == alone(kind, req, 5) and len(out[first]) == 40


# -- a member that leaves between the two halves -----------------------------------

def _row_of(srv, row):
    """What the shared state holds for one row."""
    cache = jax.tree_util.tree_map(np.asarray, srv.cache)
    out = {"logits": np.asarray(srv.logits)[row].copy(),
           "length": cache["length"][row].copy()}
    for name in ("conv", "h"):
        if name in cache:
            out[name] = cache[name][:, row].copy()
    return out


@pytest.mark.parametrize("how, members", [
    ("cancel", 1), ("cancel", 2), ("deadline", 2), ("nan", 1), ("nan", 3)])
@pytest.mark.parametrize("kind", KINDS)
def test_a_member_that_leaves_while_staged_never_reaches_the_shared_cache(
        kind, how, members):
    cfg, _ = model(kind)
    srv = server(kind, max_batch=4)
    decoding(srv)
    rng = np.random.default_rng(4)
    reqs = [request(cfg, rng, 6 + 2 * i) for i in range(members)]
    rids = [srv.submit(*req, 4, deadline_s=3600.0) for req in reqs]
    srv._stage()
    # a wave takes a power of two of members: the third of three stays queued
    assert [req.rid for req, _ in srv._staged.members] == rids[:2]
    assert [q.rid for q in srv.queue] == rids[2:]
    gone, row = srv._staged.members[0]
    if how == "cancel":
        assert srv.cancel(gone.rid) and srv.rows[row] is None
    elif how == "deadline":
        gone.deadline = time.perf_counter() - 1.0
    else:
        srv._staged.logits = srv._staged.logits.at[0].set(jnp.nan)
    # the top of a step, up to the landing
    assert srv._deadline_expired() == (how == "deadline")
    srv._drain()
    srv._expire_deadlines()
    before = _row_of(srv, row)
    assert srv._admit() and srv._staged is None
    after = _row_of(srv, row)
    assert all((before[k] == after[k]).all() for k in before)
    assert srv.rows[row] is None and srv.frozen[row]
    assert srv.finish_status[gone.rid] == {
        "cancel": serve_mod.STATUS_CANCELLED,
        "deadline": serve_mod.STATUS_DEADLINE,
        "nan": serve_mod.STATUS_NAN}[how]
    out = srv.run_until_drained()
    assert out[gone.rid] == []
    for rid, req in list(zip(rids, reqs))[1:]:       # the siblings admit
        assert srv.finish_status[rid] == serve_mod.STATUS_OK
        assert out[rid] == alone(kind, req, 4)


@pytest.mark.parametrize("queued, free, want", [
    (1, 3, 1), (2, 3, 2), (3, 3, 2), (5, 7, 4), (7, 5, 4), (9, 8, 8)])
def test_a_staged_wave_holds_a_power_of_two_of_members(queued, free, want):
    """A wave pads to a power of two and a padded slot costs what a request
    costs: staging takes the largest power of two that rows and queue give,
    and what is left goes behind a later segment."""
    cfg, _ = model("dense")
    srv = server("dense", max_batch=free + 1)
    decoding(srv, budget=60)
    rng = np.random.default_rng(7)
    rids = [srv.submit(*request(cfg, rng, 5 + i % 3), 2) for i in range(queued)]
    srv._stage()
    assert [req.rid for req, _ in srv._staged.members] == rids[:want]
    out = srv.run_until_drained()
    assert all(len(out[r]) == 2 for r in rids)


def test_the_step_that_lands_a_wave_stages_none():
    """The rows that two segments free make one wave, as they did when
    admission drained: a wave's fixed costs are shared."""
    cfg, _ = model("dense")
    srv = server("dense", max_batch=4)
    decoding(srv)
    rng = np.random.default_rng(8)
    a, b = (srv.submit(*request(cfg, rng, 7), 30) for _ in range(2))
    srv._stage()
    assert len(srv._staged.members) == 2
    c = srv.submit(*request(cfg, rng, 6), 3)
    srv.step()                                   # lands a and b
    assert srv._staged is None and srv._inflight is not None
    assert [q.rid for q in srv.queue] == [c] and srv.rows.count(None) == 1
    segments = srv.seg_count
    srv.step()                                   # pipelined: no drain for c
    assert srv.seg_count == segments + 1 and srv._inflight is not None
    assert [req.rid for req, _ in srv._staged.members] == [c]
    out = srv.run_until_drained()
    assert [len(out[r]) for r in (a, b, c)] == [30, 30, 3]


def test_a_mostly_empty_batch_is_filled_at_the_top_of_the_step():
    """As many rows free as decoding: the arrival is staged as soon as the
    scheduler sees it and lands in that step, as early as the drained path
    admitted it. While most rows decode it waits for the step's end."""
    cfg, _ = model("dense")
    rng = np.random.default_rng(9)
    for live, lands_at_once in ((2, True), (3, False)):
        srv = server("dense", max_batch=4)
        decoding(srv, n=live)
        rid = srv.submit(*request(cfg, rng, 7), 3)
        segments = srv.seg_count
        srv.step()
        assert srv.seg_count == segments + 1
        if lands_at_once:
            assert srv._staged is None and not srv.frozen[srv.rows.index(
                next(r for r in srv.rows if r is not None and r.rid == rid))]
        else:
            assert [req.rid for req, _ in srv._staged.members] == [rid]
        assert len(srv.run_until_drained()[rid]) == 3


def test_an_export_takes_the_staged_members_with_the_rows():
    cfg, _ = model("dense")
    srv = server("dense", max_batch=4)
    (first,) = decoding(srv)
    rng = np.random.default_rng(5)
    rids = [srv.submit(*request(cfg, rng, 7 + i), 4) for i in range(2)]
    srv._stage()
    assert srv._staged is not None
    moved = srv.export_requests()
    assert [m["rid"] for m in moved] == [first] + rids
    assert srv._staged is None and all(r is None for r in srv.rows)
    assert not srv.finished


# -- a fault between staging and landing -------------------------------------------

@pytest.mark.parametrize("site", ["serve.admit", "serve.step"])
def test_a_fault_between_the_halves_fails_the_staged_cleanly(site):
    """The engine's sweep finds a staged member in the row it reserved: its
    waiter gets the fault, nothing of the wave is landed later, and the
    restarted scheduler serves the next request."""
    from eventgpt_tpu.cli.serve import ServingEngine
    from eventgpt_tpu.data.tokenizer import load_tokenizer

    cfg, _ = model("dense")
    srv = server("dense", max_batch=4)
    eng = ServingEngine(srv, load_tokenizer("byte"), breaker_threshold=5)
    rng = np.random.default_rng(6)
    try:
        first = eng.submit_ids(*request(cfg, rng, 4), 60)
        staged = []
        t_end = time.time() + 120
        while not staged and time.time() < t_end:
            with eng._lock:             # the scheduler is between two steps
                if srv._inflight is None or srv._staged is not None:
                    continue
                for _ in range(2):
                    rid = srv.submit(*request(cfg, rng, 7), 4)
                    eng._done[rid] = threading.Event()
                    eng.n_requests += 1
                    staged.append(rid)
                srv._stage()
                assert srv._staged is not None and not srv.queue
                faults.configure(f"{site}:n=1")
            eng._wake.set()
        assert staged
        for rid in [first] + staged:
            with pytest.raises(RuntimeError, match="InjectedFault"):
                eng.result(rid, timeout=120)
        assert eng.n_faults == 1
        with eng._lock:
            assert srv._staged is None and srv._inflight is None
            assert all(r is None for r in srv.rows) and not srv.queue
        again = eng.submit_ids(*request(cfg, rng, 4), 5)
        assert len(eng.result(again, timeout=120)) == 5
    finally:
        eng.shutdown()
