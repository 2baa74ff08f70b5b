"""BENCHMARK.json against the contract's static rules, and every name in it
against a file."""

import json
import os
import re

import pytest

from benchmark import loader, measure

ROOT = loader.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return loader.read_benchmark()


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["source"].startswith("https://") and len(c["source"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        hf = loader.read_json(os.path.join(ROOT, c["file"]))
        assert hf["source"] == c["source"] and hf["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16 and 1 <= len(c["why"]) <= 200
        for key in c["reduced"]:  # never a width
            assert not re.search(r"(_dim|_rank|hidden|intermediate|head)", key)


def test_workloads(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(names) // 4)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        path = os.path.join(ROOT, "benchmark", "workloads", w["name"] + ".json")
        params = loader.read_json(path)  # the cell's traffic is a data file
        assert params["arrivals"]["process"] in ("gamma", "onoff")
        assert {"sample", "limit_gap", "limit_mean_gap"} <= set(params["check"])


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200
        # each listed cell reports the end-to-end metric this one moves
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in moved.get("workloads", cells), (m["name"], w)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for c in cells:  # setup_s, one more end-to-end metric, one per-layer
        assert len(measure.metrics_for(bench, c, "end_to_end")) >= 2
        assert len(measure.metrics_for(bench, c, "per_layer")) >= 1


def test_every_metric_has_its_reader(bench):
    for m in bench["end_to_end"]:
        if m["name"] != "setup_s":
            assert callable(measure.load_reader("end_to_end", m["name"]).read)
    for m in bench["per_layer"]:
        assert callable(measure.load_reader("layer_metrics", m["name"]).read)
    with pytest.raises(FileNotFoundError, match="no_such_metric"):
        measure.load_reader("layer_metrics", "no_such_metric")


def test_step_mfu_stands_beside_each_kernel_roofline(bench):
    """A kernel's roofline that moves an end-to-end metric has a whole-step
    share with ``mfu`` in its name moving the same metric."""
    by_moves = {}
    for m in bench["per_layer"]:
        by_moves.setdefault(m["moves"], []).append(m["name"])
    for m in bench["per_layer"]:
        if m["layer"] == "kernels":
            assert any("mfu" in n.split("_") for n in by_moves[m["moves"]]), m


def test_files_under_paths_have_plain_names(bench):
    for p in bench["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert PATH.match(rel), rel


def test_peaks_table_names_its_source():
    peaks = loader.read_json(os.path.join(ROOT, "benchmark", "peaks.json"))
    assert "Google Cloud" in peaks["_source"]
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
