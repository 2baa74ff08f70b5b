"""The per-layer readers of the program's spans: the ring's (hand-made run
data) and the device trace's, on a small trace recorded on a TPU v5 lite
under the program's own probe (``data/small_spans_tpu.xplane.pb``: three
engine-like steps, each an admission with an upload, a prefill and a blocked
scatter, a decode dispatch and its fetch, then an idle wait; sleeps inside leaf spans, inside ``sched.admit``
and ``engine.step`` themselves, and between steps)."""

import os

import numpy as np
import pytest

from benchmark import host_spans, loader, measure, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = os.path.join(HERE, "data", "small_spans_tpu.xplane.pb")
PLAIN = os.path.join(HERE, "data", "small_tpu.xplane.pb")
PEAKS = loader.read_json(os.path.join(loader.HERE, "peaks.json"))["TPU v5 lite"]


def run_with(ring=(), trace=None):
    return measure.RunData(
        cell={"name": "c"}, params={}, hf={}, t0=10.0, t1=20.0, rows=[],
        ring=list(ring), compiles_in_window=0, device_kind="TPU v5 lite",
        n_chips=1, peaks=PEAKS, trace=trace)


def traced(path, window_s=None):
    trace = trace_reduce.reduce_file(path, window_s=window_s)
    trace["xplane_path"] = path
    return run_with(trace=trace)


def x(name, at_s, dur_ms, cat="sched", **args):
    return {"name": name, "ph": "X", "cat": cat, "ts": at_s * 1e6,
            "dur": dur_ms * 1e3, "args": args}


# -- the ring's readers -------------------------------------------------------------

RING = (
    [x("host_prep", 9.0, 500, "http", rid=0)]                # before the window
    + [x("host_prep", 11 + i, 10 * (i + 1), "http", rid=i) for i in range(5)]
    + [x("lock_wait", 11 + i, 100 * (i + 1), "engine", rid=i) for i in range(4)]
    + [x("step", 10.5 + i / 2, 20 + i, "engine") for i in range(11)]
    + [x("step", 12.0, 999)]                                  # not the engine's
    + [x("dispatch", 12, 1, chunk=4, live=8, rows=8),
       x("dispatch", 13, 1, chunk=4, live=3, rows=8),
       x("dispatch", 14, 1, chunk=4),                         # a parent's span
       x("dispatch", 25, 1, chunk=4, live=8, rows=8)]         # after the window
    + [{"name": "queued", "ph": "b", "id": 1, "ts": 12e6}])


@pytest.mark.parametrize("name, want", [
    ("host_prep_ms.p50", 30.0),            # 10, 20, 30, 40, 50
    ("lock_wait_ms.p50", 250.0),           # 100, 200, 300, 400
    ("step_hold_ms.p90", 29.0),            # 20 .. 30
    ("live_rows_pct", 100.0 * 11 / 16),
])
def test_a_ring_reader_takes_the_windows_spans(name, want):
    reader = measure.load_reader("layer_metrics", name)
    assert reader.read(run_with(RING)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["host_prep_ms.p50", "lock_wait_ms.p50",
                                  "step_hold_ms.p90", "live_rows_pct"])
def test_a_ring_reader_finds_nothing_in_a_parents_ring(name):
    """The program before these spans: ``dispatch`` without counters,
    nothing else."""
    ring = [x("dispatch", 12, 1, chunk=4), x("admit", 12, 3)]
    assert measure.load_reader("layer_metrics", name).read(run_with(ring)) is None


# -- interval arithmetic ---------------------------------------------------------------


def test_idle_between_is_length_less_busy():
    busy = np.array([[10, 20], [30, 50], [80, 100]], np.int64)
    a = np.array([0, 10, 15, 20, 25, 45, 0, 95], np.int64)
    b = np.array([10, 20, 35, 30, 26, 85, 200, 300], np.int64)
    #            before, busy, 5+10 busy of 20, a gap, in a gap, 5 busy+30+5 busy,
    #            clipped to [10, 100], clipped to [95, 100]
    assert host_spans.idle_between(busy, a, b).tolist() == [
        0, 0, 10, 10, 1, 30, 40, 0]
    assert host_spans.idle_between(np.zeros((0, 2), np.int64), a, b).sum() == 0


def test_innermost_names_each_instant_after_the_deepest_open_span():
    spans = [("engine.step", 0, 100), ("sched.admit", 10, 60),
             ("admit.upload", 20, 30), ("admit.scatter", 40, 60),
             ("sched.dispatch", 70, 80), ("engine.idle_wait", 120, 150)]
    assert host_spans.innermost(spans) == [
        ("engine.step", 0, 10), ("sched.admit", 10, 20),
        ("admit.upload", 20, 30), ("sched.admit", 30, 40),
        ("admit.scatter", 40, 60), ("engine.step", 60, 70),
        ("sched.dispatch", 70, 80), ("engine.step", 80, 100),
        ("engine.idle_wait", 120, 150)]
    pieces = host_spans.innermost(spans[::-1])        # any order in
    assert sum(b - a for _, a, b in pieces) == 100 + 30


# -- the recorded trace ---------------------------------------------------------------


def test_the_recorded_trace_holds_the_annotations():
    x_ = host_spans.read_file(SPANS)
    names = {n for n, _, _ in x_["engine"]}
    assert names == {"engine.step", "sched.admit", "admit.upload",
                     "admit.prefill", "admit.scatter", "sched.dispatch",
                     "sched.segment_fetch", "engine.idle_wait"}
    assert len(x_["busy"]) >= 6
    # the busy intervals are device_idle_pct's own
    assert (x_["busy"][:, 1] - x_["busy"][:, 0]).sum() / 1e9 == pytest.approx(
        trace_reduce.reduce_file(SPANS)["busy_s"], rel=1e-9)


def test_idle_by_span_sums_to_the_devices_idle_time():
    run = traced(SPANS)
    table = host_spans.idle_by_span(run)
    by = table["by_innermost_span_s"]
    idle = run.trace["window_s"] - run.trace["busy_s"]
    assert table["idle_s"] == pytest.approx(idle)
    assert sum(by.values()) == pytest.approx(idle, rel=1e-6)
    leaves = sum(v for k, v in by.items() if k not in
                 host_spans.CONTAINERS + (host_spans.NO_SPAN, host_spans.EDGES))
    assert leaves + table["unspanned_s"] == pytest.approx(idle, rel=1e-6)
    # what the recorder slept through, three times each: 4 ms in the upload
    # (beside the upload itself), 2 ms in admit itself, 3 ms in the scatter,
    # 2 ms in the step itself, 10 ms in the idle wait (the last one ends
    # after the device's last operation, and is cut there)
    assert 0.012 <= by["admit.upload"] <= 0.02
    assert by["sched.admit"] == pytest.approx(0.006, abs=0.003)
    assert by["engine.step"] == pytest.approx(0.006, abs=0.003)
    assert by["engine.idle_wait"] >= 0.02
    assert by["admit.scatter"] >= 0.009
    assert table["upload_s"] == pytest.approx(by["admit.upload"])
    assert table["admit_s"] == pytest.approx(
        by["sched.admit"] + by["admit.upload"] + by["admit.prefill"]
        + by["admit.scatter"])
    assert table["unspanned_s"] == pytest.approx(
        by["sched.admit"] + by["engine.step"] + by[host_spans.NO_SPAN]
        + by[host_spans.EDGES])


def test_a_longer_stated_window_adds_its_idle_time_to_the_edges():
    run = traced(SPANS, window_s=1.0)
    table = host_spans.idle_by_span(run)
    assert table["by_innermost_span_s"][host_spans.EDGES] > 0.8
    assert sum(table["by_innermost_span_s"].values()) == pytest.approx(
        1.0 - run.trace["busy_s"], rel=1e-6)


@pytest.mark.parametrize("name", ["idle_upload_pct", "idle_admit_pct",
                                  "idle_unspanned_pct"])
def test_an_idle_reader_on_the_recorded_trace(name):
    run = traced(SPANS)
    value = measure.load_reader("layer_metrics", name).read(run)
    idle_pct = measure.load_reader("layer_metrics", "device_idle_pct").read(run)
    assert 0 < value < idle_pct
    key = name[len("idle_"):-len("_pct")] + "_s"
    assert value == pytest.approx(
        100 * run.trace["idle_by_span"][key] / run.trace["window_s"])


@pytest.mark.parametrize("name", ["idle_upload_pct", "idle_admit_pct",
                                  "idle_unspanned_pct"])
def test_a_trace_of_a_program_without_the_spans_gives_nothing(name):
    """The parent's traces: device planes, no annotation of the program."""
    run = traced(PLAIN)
    assert host_spans.read_file(PLAIN)["engine"] is None
    assert measure.load_reader("layer_metrics", name).read(run) is None


def test_without_the_file_the_readers_give_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(host_spans, "TRACE_DIR", str(tmp_path))
    run = run_with(trace={"window_s": 1.0, "busy_s": 0.5, "modules": {}})
    for name in ("idle_upload_pct", "idle_admit_pct", "idle_unspanned_pct"):
        assert measure.load_reader("layer_metrics", name).read(run) is None


def test_idle_outside_the_hosts_recording_is_the_windows_edge(monkeypatch):
    """The device plane starts before the host tracer is up: idle time before
    the engine thread's first annotation is no fault of the program's spans."""
    ms = 1_000_000
    busy = np.array([[0, 10 * ms], [40 * ms, 50 * ms], [90 * ms, 100 * ms]],
                    np.int64)
    engine = [("engine.step", 45 * ms, 95 * ms),
              ("sched.dispatch", 60 * ms, 70 * ms)]
    monkeypatch.setattr(host_spans, "of_run",
                        lambda run: {"busy": busy, "engine": engine})
    monkeypatch.setattr(host_spans, "_publish", lambda table: None)
    run = run_with(trace={"window_s": 0.120, "busy_s": 0.030})
    by = host_spans.idle_by_span(run)["by_innermost_span_s"]
    assert by["sched.dispatch"] == pytest.approx(0.010)
    assert by["engine.step"] == pytest.approx(0.030)     # 50-60, 70-90
    assert by[host_spans.NO_SPAN] == pytest.approx(0.0)
    # 10-40 before the first annotation, and the 20 ms the window states
    # beyond the operations
    assert by[host_spans.EDGES] == pytest.approx(0.050)
    assert sum(by.values()) == pytest.approx(0.120 - 0.030)
