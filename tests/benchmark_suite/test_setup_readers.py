"""The set-up readers (``setup_compile_s``, ``setup_programs``,
``setup_cache_hit_pct``) on hand-made rings: only what began before the
window counts, nested compile intervals count once, a ring without
``ring_start`` or without a ``compile.xla`` span gives nothing, and the table
behind them is written with its parts."""

import json

import pytest

from benchmark import loader, measure, setup_spans

PEAKS = loader.read_json(loader.HERE + "/peaks.json")["TPU v5 lite"]
READERS = ("setup_compile_s", "setup_programs", "setup_cache_hit_pct")


@pytest.fixture(autouse=True)
def _table_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setattr(setup_spans, "TABLE_PATH",
                        str(tmp_path / "setup_by_program.json"))


def run_with(ring):
    return measure.RunData(
        cell={"name": "c"}, params={}, hf={}, t0=100.0, t1=110.0, rows=[],
        ring=list(ring), compiles_in_window=0, device_kind="TPU v5 lite",
        n_chips=1, peaks=PEAKS)


def x(name, cat, at_s, dur_s, tid=1, **args):
    return {"name": name, "ph": "X", "cat": cat, "ts": at_s * 1e6,
            "dur": dur_s * 1e6, "tid": tid, "args": args}


START = {"name": "ring_start", "ph": "i", "s": "t", "cat": "trace",
         "ts": 1e6, "tid": 1}
RING = [
    START,
    x("build", "setup", 2.0, 60.0),
    x("load", "setup", 2.0, 10.0, parent="build"),
    x("warmup", "setup", 20.0, 40.0, parent="build", programs=3),
    # a trace nested in a trace, then its lowering and its compile: 1 + 1 + 4 s
    x("jaxpr", "compile", 21.0, 1.0, fun="outer", parent="warmup"),
    x("jaxpr", "compile", 21.5, 0.25, fun="inner", parent="warmup"),
    x("lower", "compile", 22.0, 1.0, fun="jit(outer)", parent="warmup"),
    x("xla", "compile", 23.0, 4.0, fun="jit(outer)", parent="warmup",
      cache="miss"),
    x("xla", "compile", 30.0, 1.0, fun="jit(small)", parent="warmup",
      cache="hit"),
    # the engine thread's first admission, after build_engine: 2 s
    x("prefill", "admit", 70.0, 3.0, tid=2, parent="admit"),
    x("xla", "compile", 70.5, 2.0, tid=2, fun="jit(_prefill_jit)",
      parent="prefill", cache="off"),
    # inside the window: none of it counts
    x("jaxpr", "compile", 101.0, 1.0, tid=2, fun="late", parent="prefill"),
    x("xla", "compile", 102.0, 5.0, tid=2, fun="jit(late)",
      parent="prefill", cache="hit"),
]


def read(name, ring):
    return measure.load_reader("layer_metrics", name).read(run_with(ring))


@pytest.mark.parametrize("name, want", [
    ("setup_compile_s", 1.0 + 1.0 + 4.0 + 1.0 + 2.0),
    ("setup_programs", 3.0),
    ("setup_cache_hit_pct", 100.0 / 3),
])
def test_a_reader_counts_only_what_began_before_the_window(name, want):
    assert read(name, RING) == pytest.approx(want)


def test_a_nested_interval_counts_once():
    assert setup_spans.union_s([(0.0, 4.0), (1.0, 2.0), (3.0, 5.0),
                                (7.0, 8.0)]) == 6.0
    # the same intervals on two threads count twice: both threads were busy
    twice = [START] + [x("xla", "compile", 2.0, 1.0, tid=t, fun="f",
                         cache="off") for t in (1, 2)]
    assert read("setup_compile_s", twice) == pytest.approx(2.0)


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("ring", [
    RING[1:],                                        # ring_start overwritten
    [e for e in RING if e["name"] != "xla"],         # no compile.xla span
    [START] + RING[-2:],                             # all in the window
    [],                                              # --trace_buffer 0
], ids=["no_ring_start", "no_xla", "none_before_t0", "empty"])
def test_a_reader_gives_nothing_on_a_parents_ring(name, ring):
    assert read(name, ring) is None


def test_the_table_is_written_with_its_parts():
    run = run_with(RING)
    assert measure.load_reader("layer_metrics", "setup_programs").read(
        run) == 3.0
    with open(setup_spans.TABLE_PATH) as f:
        table = json.load(f)
    assert table["phases_s"] == {"setup.build": 60.0, "setup.warmup": 40.0,
                                 "setup.load": 10.0}
    assert table["compile_s_by_parent"] == pytest.approx(
        {"setup.warmup": 7.0, "admit.prefill": 2.0})
    assert table["compile_s_by_phase"] == pytest.approx(
        {"setup.warmup": 7.0, setup_spans.AFTER_BUILD: 2.0})
    assert list(table["by_program"]) == ["jit(outer)", "jit(_prefill_jit)",
                                         "jit(small)"]       # longest first
    assert table["by_program"]["jit(small)"] == {"n": 1, "s": 1.0, "hits": 1}
    # the readers of one run share one table
    assert setup_spans.of_run(run) is setup_spans.of_run(run)
