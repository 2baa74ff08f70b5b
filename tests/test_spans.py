"""One probe, one clock (``obs/trace.span``): parents, request ids and args
completed late; the same interval as a ``TraceAnnotation`` on the profiler's
clock; nothing at all when disarmed; the span table against the call sites
and the documents; and, against a tiny server over HTTP, a span at every
layer boundary of a served request, with chains byte-identical whether the
probes are armed or not. All on the CPU."""

import ast
import base64
import glob
import json
import os
import re
import threading
import time
import tracemalloc
import urllib.request

import numpy as np
import pytest

from eventgpt_tpu.obs import metrics as obs_metrics
from eventgpt_tpu.obs import profiling as obs_profiling
from eventgpt_tpu.obs import series as obs_series
from eventgpt_tpu.obs import trace as obs_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN_NAMES = {(d.cat, d.name) for d in obs_trace.SPANS}


@pytest.fixture(autouse=True)
def _restore_global_telemetry():
    prev_tracer, prev_dir = obs_trace.active(), obs_profiling._profile_dir
    prev_enabled = obs_metrics.REGISTRY.enabled
    yield
    obs_trace._tracer = prev_tracer
    obs_profiling.configure(prev_dir)
    obs_metrics.configure(prev_enabled)


def _x(events, name):
    return [e for e in events if e["ph"] == "X" and e["name"] == name]


# -- the probe ------------------------------------------------------------------


def test_nested_span_records_parent_rid_and_args_set_late():
    tracer = obs_trace.configure(64)
    with obs_trace.span("outer", "test", rid=7) as outer:
        with obs_trace.span("inner", "test") as inner:
            inner.set(rid=7, n=2)          # before it closes
        with obs_trace.span("dropped", "test") as gone:
            gone.drop()
        outer.set(path="wave")
    inner.set(late=True)                   # after it closed: same event
    evs = tracer.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]
    inner_ev, outer_ev = evs
    assert inner_ev["args"] == {"parent": "outer", "rid": 7, "n": 2,
                                "late": True}
    assert outer_ev["args"] == {"rid": 7, "path": "wave"}   # no parent: top
    assert outer_ev["ts"] <= inner_ev["ts"]
    assert inner_ev["ts"] + inner_ev["dur"] <= outer_ev["ts"] + outer_ev["dur"]
    # a snapshot is a copy: a later set() does not reach into it
    outer.set(more=1)
    assert "more" not in outer_ev["args"]
    assert tracer.events()[1]["args"]["more"] == 1


def test_close_ends_the_interval_before_the_block_does():
    tracer = obs_trace.configure(8)
    lock = threading.Lock()
    with obs_trace.span("lock_wait", "engine") as wait, lock:
        wait.close()
        time.sleep(0.02)                   # the hold is not the wait
        with obs_trace.span("held", "test"):
            pass
    wait.set(rid=3)
    by = {e["name"]: e for e in tracer.events()}
    assert by["lock_wait"]["dur"] < 10_000 and by["lock_wait"]["args"] == {"rid": 3}
    assert "parent" not in by["held"]["args"]    # the wait was over


def test_each_thread_has_its_own_parents():
    tracer = obs_trace.configure(64)
    ready, go = threading.Event(), threading.Event()

    def other():
        with obs_trace.span("theirs", "test"):
            ready.set()
            go.wait(5)

    t = threading.Thread(target=other)
    t.start()
    assert ready.wait(5)
    with obs_trace.span("mine", "test"):
        pass
    go.set()
    t.join(5)
    assert not t.is_alive()
    assert all("parent" not in e.get("args", {}) for e in tracer.events())


def test_disarmed_probes_allocate_nothing():
    obs_trace.disable()
    first = obs_trace.span("x", "y", a=1)
    assert first is obs_trace.span("z") is obs_trace._NULL
    with first as sp:
        assert sp.set(rid=1) is None and sp.drop() is None
        assert sp.close() is None

    def probe():
        with obs_trace.span("step", "engine") as sp:
            sp.set()
        obs_trace.instant("i")

    for _ in range(64):
        probe()                            # warm every cache first
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(2000):
        probe()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(s.size_diff for s in after.compare_to(before, "filename")
                if s.traceback[0].filename.endswith(
                    os.path.join("obs", "trace.py")))
    assert grown <= 0, f"{grown} bytes kept by 2000 disarmed probes"
    assert getattr(obs_trace._open, "stack", None) in (None, [])


def test_no_annotation_is_made_unless_the_profiler_is_armed(monkeypatch):
    import jax

    made = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        made.append((name, kw))
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    obs_trace.configure(16)
    obs_profiling.configure(None)
    with obs_trace.span("step", "engine"):
        pass
    assert made == []
    obs_profiling.configure("/nonexistent-but-armed")
    with obs_trace.span("step", "engine", live=2):
        with obs_trace.span("admit", "sched"):
            pass
    assert made == [("engine.step", {"live": 2}),
                    ("sched.admit", {"parent": "step"})]


def test_a_capture_holds_the_rings_spans_on_one_clock(tmp_path):
    """With the profiler armed a jax.profiler capture's host plane holds
    ``<cat>.<name>`` for every span of the ring, and the offset between the
    two clocks over matched spans spreads by under 1 ms."""
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    tracer = obs_trace.configure(256)
    d = str(tmp_path / "prof")
    obs_profiling.configure(d)
    obs_profiling.start_trace(d)
    try:
        for i in range(12):
            with obs_trace.span("step", "engine", live=i):
                with obs_trace.span("dispatch", "sched", chunk=4, rids=[i]):
                    (jnp.ones((32, 32)) @ jnp.ones((32, 32))).block_until_ready()
                time.sleep(0.002)
    finally:
        obs_profiling.stop_trace()
    path = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))[0]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("engine.step", "sched.dispatch"):
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns, dict(ev.stats)))
    ring = [e for e in tracer.events() if e["ph"] == "X"]
    assert len(ring) == 24
    offsets = []
    for name in ("step", "dispatch"):
        cat = {"step": "engine", "dispatch": "sched"}[name]
        mine = sorted(_x(ring, name), key=lambda e: e["ts"])
        theirs = sorted(found[f"{cat}.{name}"])
        assert len(mine) == len(theirs) == 12
        for e, (start_ns, dur_ns, stats) in zip(mine, theirs):
            offsets.append(start_ns - e["ts"] * 1e3)
            # the ring's interval lies inside the annotation's
            assert e["dur"] * 1e3 <= dur_ns + 1e6
    assert dict(theirs[0][2])["parent"] == "step"
    assert max(offsets) - min(offsets) < 1e6, "the two clocks drift apart"


# -- the table, the call sites, the documents -------------------------------------


def _span_calls():
    """(file, cat, name) of every ``obs_trace.span(...)`` call of the
    program."""
    out = []
    for path in glob.glob(os.path.join(ROOT, "eventgpt_tpu", "**", "*.py"),
                          recursive=True):
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "span"
                    and getattr(node.func.value, "id", "") == "obs_trace"):
                name, cat = node.args[0].value, node.args[1].value
                out.append((os.path.relpath(path, ROOT), cat, name))
    return out


def test_every_call_site_is_a_row_of_the_table_and_every_row_has_one():
    calls = _span_calls()
    assert {(c, n) for _, c, n in calls} == SPAN_NAMES
    assert len({d.name for d in obs_trace.SPANS}) == len(obs_trace.SPANS), \
        "names are read without their category (GET /trace, the benchmark)"


def test_no_second_probe():
    """Every span is made by ``obs_trace.span``: no after-the-fact
    ``complete``, no hand-made annotation outside ``obs/``."""
    for path in glob.glob(os.path.join(ROOT, "eventgpt_tpu", "**", "*.py"),
                          recursive=True):
        rel = os.path.relpath(path, ROOT)
        src = open(path).read()
        if not rel.startswith(os.path.join("eventgpt_tpu", "obs")):
            assert not re.search(r"(?<!Step)TraceAnnotation", src), rel
        if rel != os.path.join("eventgpt_tpu", "obs", "trace.py"):
            assert not re.search(r"\btr(acer)?\.complete\(", src), rel
        assert "obs_profiling.annotation" not in src, rel


@pytest.mark.parametrize("doc", ["OBSERVABILITY.md", "PERF.md"])
def test_the_documents_list_the_tables_spans(doc):
    text = open(os.path.join(ROOT, doc)).read()
    missing = [f"{d.cat}.{d.name}" for d in obs_trace.SPANS
               if f"`{d.cat}.{d.name}`" not in text]
    assert not missing, f"{doc} does not name {missing}"
    if doc == "OBSERVABILITY.md":       # generated from the table
        assert obs_trace.span_table_markdown() in text
    # and names no span of these categories that the table lacks
    named = set(re.findall(r"`((?:http|engine|sched|admit)\.[a-z_]+)`", text))
    assert named <= {f"{c}.{n}" for c, n in SPAN_NAMES}, named


# -- a tiny server over HTTP ------------------------------------------------------


def _event_b64(tmp, seed):
    from eventgpt_tpu.ops.raster import STREAM_DTYPE

    rng = np.random.default_rng(seed)
    n = 3000
    arr = np.zeros(n, dtype=STREAM_DTYPE)
    arr["x"] = rng.integers(0, 64, n)
    arr["y"] = rng.integers(0, 48, n)
    arr["t"] = np.sort(rng.integers(0, 50_000, n)).astype(np.uint64)
    arr["p"] = rng.integers(0, 2, n)
    path = os.path.join(str(tmp), f"events{seed}.npy")
    np.save(path, arr)
    with open(path, "rb") as f:
        return base64.b64encode(f.read()).decode()


def _post(url, payload, timeout=300):
    req = urllib.request.Request(
        url + "/v1/generate", json.dumps(payload).encode(),
        {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(url, timeout=60):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _ask_all(url, streams, budget=6):
    """Five requests at once (waves and lone rows both happen), then one
    alone; (answers in a fixed order, rids)."""
    out = {}

    def go(i):
        out[i] = _post(url, {"query": f"What moves in window {i}?",
                             "event_b64": streams[i % len(streams)],
                             "max_new_tokens": budget, "debug": True})

    threads = [threading.Thread(target=go, args=(i,)) for i in range(5)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    assert not any(t.is_alive() for t in threads)
    go(5)
    return [out[i]["token_ids"] for i in range(6)], [out[i]["rid"]
                                                     for i in range(6)]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A tiny server that answered the same six requests twice: once with
    the ring and the profiler armed, once with every probe disarmed."""
    from eventgpt_tpu.cli import serve as serve_cli

    tmp = tmp_path_factory.mktemp("spans")
    ns = type("A", (), {})()
    ns.model_path = "tiny-random"
    ns.tokenizer_path = None
    ns.host, ns.port = "127.0.0.1", 0
    ns.event_root = None
    ns.conv_mode = "eventgpt_v1"
    ns.max_batch, ns.max_len, ns.chunk = 2, 256, 4
    ns.temperature = 0.0
    ns.dtype, ns.quant, ns.kv_cache = "float32", "none", "bf16"
    ns.speculative, ns.prefill_chunk, ns.warmup = 0, 0, False
    ns.mesh_data = ns.mesh_fsdp = ns.mesh_model = 1
    ns.use_event_qformer = False
    ns.pretrain_query_embedder = ns.pretrain_attention_layers = None
    ns.profile_dir = str(tmp / "profile")        # arms the annotations
    # every admission a full prefill of its own, alone or in a wave (the
    # paths the benchmark's cells take; hits and lanes: the test below)
    ns.no_prefix_cache, ns.prefill_budget = True, 0
    prev = obs_trace.active(), obs_profiling._profile_dir
    httpd, engine = serve_cli.build_server(ns)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    streams = [_event_b64(tmp, s) for s in (1, 2, 3)]
    try:
        assert obs_trace.enabled() and obs_profiling.armed()
        armed, rids = _ask_all(url, streams)
        ring = obs_trace.active().events()
        by_rid = {rid: _get(url + f"/trace?rid={rid}")["traceEvents"]
                  for rid in rids}
        tracer = obs_trace.active()
        obs_trace.disable()
        obs_profiling.configure(None)
        t_disarmed = time.perf_counter()
        disarmed, _ = _ask_all(url, streams)
        assert obs_trace.active() is None
        yield {"armed": armed, "disarmed": disarmed, "rids": rids,
               "ring": ring, "by_rid": by_rid, "t_disarmed": t_disarmed,
               "after": tracer.events()}
    finally:
        httpd.shutdown()
        engine.shutdown()
        httpd.server_close()
        obs_trace._tracer = prev[0]
        obs_profiling.configure(prev[1])
        obs_series.disable()


def test_chains_are_identical_with_every_span_armed(served):
    assert all(len(t) == 6 for t in served["armed"])
    assert served["armed"] == served["disarmed"]
    # a span open at the disarming still closes into its ring; none begins
    late = [e for e in served["after"]
            if e["ts"] > served["t_disarmed"] * 1e6]
    assert not late, "a disarmed probe wrote to the old ring"


def test_every_table_row_of_the_serving_path_was_recorded(served):
    seen = {(e["cat"], e["name"]) for e in served["ring"] if e["ph"] == "X"}
    want = {(d.cat, d.name) for d in obs_trace.SPANS
            if d.cat != "train" and not d.name.startswith("prefix_")}
    assert want <= seen, want - seen


def test_each_request_has_its_front_end_spans_and_trace_rid_returns_them(served):
    for rid in served["rids"]:
        evs = served["by_rid"][rid]
        for name in ("http_read", "host_prep", "lock_wait"):
            mine = [e for e in _x(evs, name) if e["args"].get("rid") == rid]
            assert len(mine) == 1, (rid, name)
        read, prep, wait = (_x(evs, n)[0] for n in
                            ("http_read", "host_prep", "lock_wait"))
        assert read["args"]["bytes"] > 1000
        assert read["ts"] <= prep["ts"] <= wait["ts"]
        assert read["tid"] == prep["tid"] == wait["tid"]
        names = {e["name"] for e in evs}
        # the engine thread's work for it, and its async lifecycle
        assert {"step", "admit", "upload", "encode", "prefill", "scatter",
                "dispatch", "segment_fetch", "harvest", "stream_push",
                "queued", "active"} <= names, (rid, names)
        for e in evs:
            args = e.get("args") or {}
            assert (e.get("id") == rid or args.get("rid") == rid
                    or rid in args.get("rids", ())), e


def test_every_span_has_a_request_and_every_inner_one_a_parent(served):
    spans = [e for e in served["ring"] if e["ph"] == "X"]
    outermost = {"http_read", "host_prep", "lock_wait", "step", "idle_wait"}
    for e in spans:
        args = e.get("args") or {}
        if e["name"] != "idle_wait":
            assert "rid" in args or "rids" in args, e
        assert ("parent" in args) == (e["name"] not in outermost), e
    parents = {(e["name"], e["args"]["parent"]) for e in spans
               if "parent" in (e.get("args") or {})}
    assert {("admit", "step"), ("dispatch", "step"), ("harvest", "step"),
            ("segment_fetch", "step"), ("stream_push", "step"),
            ("upload", "admit"), ("encode", "admit"), ("prefill", "admit"),
            ("scatter", "admit")} <= parents, parents


def test_the_children_of_a_step_lie_inside_it(served):
    spans = [e for e in served["ring"] if e["ph"] == "X"]
    steps = sorted(_x(spans, "step"), key=lambda e: e["ts"])
    assert len(steps) >= 4
    tid = steps[0]["tid"]
    starts = np.asarray([s["ts"] for s in steps])
    covered = np.zeros(len(steps))
    for e in spans:
        if e["tid"] != tid or (e.get("args") or {}).get("parent") != "step":
            continue
        i = int(np.searchsorted(starts, e["ts"], side="right")) - 1
        assert i >= 0, e
        s = steps[i]
        assert s["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= s["ts"] + s["dur"] + 1.0, (e, s)
        covered[i] += e["dur"]
    assert (covered <= np.asarray([s["dur"] for s in steps]) + 1.0).all()
    # nothing of the engine thread's spans lies outside a step or a wait
    for s, nxt in zip(steps, steps[1:]):
        assert s["ts"] + s["dur"] <= nxt["ts"]


def test_dispatch_counts_live_rows_of_the_rows_it_pays_for(served):
    disp = _x(served["ring"], "dispatch")
    assert disp
    for e in disp:
        a = e["args"]
        assert 0 <= a["live"] <= a["rows"] == 2
        assert len(a["rids"]) == a["live"] and a["chunk"] == 4
    assert any(e["args"]["live"] == 2 for e in disp)
    admits = _x(served["ring"], "admit")
    assert {p for e in admits for p in e["args"]["path"].split("+")} <= {
        "row", "wave", "suffix", "suffix_wave", "lane", "chunk"}
    assert all(e["args"]["n"] == len(e["args"]["rids"]) for e in admits)
    # a staged wave has two spans, its staging and its landing
    assert sorted({r for e in admits for r in e["args"]["rids"]}) == sorted(
        served["rids"])
    ups = _x(served["ring"], "upload")
    assert all(e["args"]["bytes"] > 0 and e["args"]["n"] >= 1 for e in ups)


def test_an_admission_says_whether_it_was_staged_and_keeps_its_children(served):
    """``sched.admit`` carries ``staged``: 0 where both halves of the
    admission ran drained, the members where the first half (upload, encode,
    prefill) was dispatched under a segment in flight; then the landing is a
    ``sched.admit`` of its own around the scatter. Either way the four
    ``admit.*`` spans lie inside a ``sched.admit``."""
    spans = [e for e in served["ring"] if e["ph"] == "X"]
    admits = sorted(_x(spans, "admit"), key=lambda e: e["ts"])
    assert all(e["args"]["staged"] in (0, e["args"]["n"]) for e in admits)
    assert admits[0]["args"]["staged"] == 0          # an idle server
    assert any(e["args"]["staged"] for e in admits)  # five at once, two rows

    def inside(e):
        return sorted(k["name"] for k in spans if k["cat"] == "admit"
                      and e["ts"] <= k["ts"]
                      and k["ts"] + k["dur"] <= e["ts"] + e["dur"] + 1.0)

    whole = ["encode", "prefill", "scatter", "upload"]
    halves = {0: [], 1: []}
    for e in admits:
        if not e["args"]["staged"]:
            assert inside(e) == whole, e
        else:
            halves[inside(e) == ["scatter"]].append(e)
    assert all(inside(e) == whole[:2] + whole[3:] for e in halves[0])
    assert [e["args"]["rids"] for e in halves[0]] == [
        e["args"]["rids"] for e in halves[1]]        # each staging landed
    kids = [k for k in spans if k["cat"] == "admit"]
    assert all(k["args"]["parent"] == "admit" for k in kids)
    assert len(kids) == sum(len(inside(e)) for e in admits)


def test_a_prefix_hit_records_its_lookup_and_copy_with_the_request():
    import jax

    from eventgpt_tpu.config import EventChatConfig
    from eventgpt_tpu.models import eventchat
    from eventgpt_tpu.serve import ContinuousBatcher

    cfg = EventChatConfig.tiny()
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(5))
    pv = np.random.default_rng(0).normal(
        size=(cfg.num_event_frames, 3, cfg.vision.image_size,
              cfg.vision.image_size)).astype(np.float32)
    tracer = obs_trace.configure(4096)
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None)
    first = srv.submit([1, 5, -200, 9, 9], pv, 4)
    out = srv.run_until_drained()
    again = srv.submit([1, 5, -200, 9, 7], pv, 4)      # the cached head
    out.update(srv.run_until_drained())
    assert len(out[first]) == len(out[again]) == 4
    spans = [e for e in tracer.events() if e["ph"] == "X"]
    looks = _x(spans, "prefix_lookup")
    assert [(e["args"]["rid"], e["args"]["hit"]) for e in looks] == [
        (first, False), (again, True)]
    copy, = _x(spans, "prefix_copy")
    assert copy["args"]["rid"] == again and copy["args"]["parent"] == "admit"
    fills = [e for e in _x(spans, "prefill") if e["args"]["rid"] == again]
    assert [e["args"]["parent"] for e in fills] == ["prefix_copy"]
    admits = _x(spans, "admit")
    assert [e["args"]["path"] for e in admits] == ["row", "suffix"]
    # without an engine thread the batcher's own spans are the outermost
    assert all("parent" not in e["args"] for e in admits)


# -- names on the device ----------------------------------------------------------------


def _trace_scopes_on_recorded_trace():
    """``scripts/trace_scopes.py`` as a module, and the small trace recorded
    on a TPU v5 lite for the benchmark's tests."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_scopes", os.path.join(ROOT, "scripts", "trace_scopes.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, os.path.join(ROOT, "tests", "benchmark_suite", "data",
                             "small_spans_tpu.xplane.pb")


def test_scopes_are_read_off_a_recorded_tpu_trace():
    """``scripts/trace_scopes.py`` on the small trace recorded on a TPU v5
    lite for the benchmark's tests: the scope path is the metadata's
    ``tf_op``, a fusion carries its root's, and self times by scope sum to
    the programs' device time."""
    mod, path = _trace_scopes_on_recorded_trace()
    with open(path, "rb") as f:
        dev = mod.device_plane(f.read())
    paths = {r["stats"].get("tf_op") for r in dev["meta"].values()}
    assert "jit(_decode_segment)/decode_attn/bd,kd->bk/dot_general:" in paths
    table = mod.seconds_by_scope(path)
    decode = {sc: s for (prog, sc), s in table.items()
              if prog == "jit__decode_segment"}
    assert set(decode) == {"decode_attn", "mlp", "-"}
    assert 0.2 < decode["decode_attn"] / sum(decode.values()) < 0.8
    assert decode["-"] < 0.01 * sum(decode.values())
    assert table[("jit__prefill_jit", "prefill_attn")] > 0
    # every operation of the line is counted once: the rows' union
    rows = dev[mod.OPS_LINE]
    merged = rows[np.argsort(rows[:, 1])]
    assert sum(table.values()) * 1e9 == pytest.approx(
        float(mod.self_ns(rows[:, 1:3]).sum()))
    assert len(merged) == len(rows) > 20


def test_scopes_list_a_programs_operations(capsys):
    """``--ops``: the same self times, by operation of the programs a regex
    matches; they sum to the programs' time by scope."""
    mod, path = _trace_scopes_on_recorded_trace()
    by_scope = mod.seconds_by_scope(path)
    by_op = mod.seconds_by_scope(path, by_op=True)
    assert len(by_op) > len(by_scope)
    for key, secs in by_scope.items():
        assert secs == pytest.approx(
            sum(s for k, s in by_op.items() if k[:2] == key))
    assert mod.main([path, "--ops", "decode_segment", "--top", "5"]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert "decode_segment" in lines[0] and len(lines) == 6
    assert all(" ms " in ln for ln in lines[1:])
    assert any("decode_attn" in ln for ln in lines[1:])
