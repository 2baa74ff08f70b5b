"""A later PR adds a configuration, a traffic mix, a per-layer metric and a
cell as new files plus new BENCHMARK.json entries, and edits no file that is
there: shown on a temporary copy of the benchmark, whose ``run.py`` then runs
the new cell (rehearsed on the CPU) and reports the new metric."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import loader

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu", reason="a CPU test")


def test_add_one_of_each_in_a_copy(tmp_path):
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(loader.ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = loader.read_benchmark()
    before = {p: (root / "benchmark" / p).read_bytes()
              for p in ("run.py", "measure.py", "traffic/__init__.py",
                        "workloads/mistral7b.camera_qa.json")}

    # 1. a configuration: a file of sizes
    cfg = loader.read_json(os.path.join(loader.HERE, "configs",
                                        "mistral-7b-event.json"))
    cfg.update(name="mistral-7b-event-fused",
               flags=cfg["flags"] + ["--fuse_params"])
    (root / "benchmark/configs/mistral-7b-event-fused.json").write_text(
        json.dumps(cfg))
    bench["configs"].append({
        "name": "mistral-7b-event-fused", "source": cfg["source"],
        "file": "benchmark/configs/mistral-7b-event-fused.json",
        "reduced": [], "why": "the same widths, q|k|v and gate|up fused"})
    # 2. a traffic mix: a data file the general generator reads
    mix = loader.read_json(os.path.join(loader.HERE, "workloads",
                                        "mistral7b.camera_qa.json"))
    mix["arrivals"] = {"process": "gamma", "shape": 1.0, "rate_per_s": 1.0}
    (root / "benchmark/workloads/mistral7b-fused.camera_steady.json"
     ).write_text(json.dumps(mix))
    bench["workloads"].append({
        "name": "mistral7b-fused.camera_steady",
        "config": "mistral-7b-event-fused", "traffic": "camera_steady",
        "chips": 1, "why": "Poisson arrivals on the fused tree"})
    # 3. a per-layer metric: a small reader of its own
    (root / "benchmark/layer_metrics/plain_share_pct.py").write_text(
        '"""Share of the window\'s requests that finished ok."""\n\n\n'
        "def read(run):\n"
        "    rows = run.window_rows()\n"
        "    return 100.0 * sum(r.ok for r in rows) / len(rows) if rows "
        "else None\n")
    bench["per_layer"].append({
        "name": "plain_share_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "out_tok_per_s",
        "workloads": ["mistral7b-fused.camera_steady"]})
    for m in bench["per_layer"]:
        if m["name"] == "gen_late_ms.p90":
            m["workloads"].append("mistral7b-fused.camera_steady")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": loader.ROOT + os.pathsep + os.environ.get(
               "PYTHONPATH", "")}
    cmd = [sys.executable, "benchmark/run.py", "--workload",
           "mistral7b-fused.camera_steady", "--seed", "3", "--seconds", "3",
           "--trace", "1", "--rehearsal"]
    done = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["rehearsal_saw"]["plain_share_pct"] == 100.0
    assert "gen_late_ms.p90" in last["rehearsal_saw"]
    assert last["rehearsal_checks_passed"] is True
    # nothing that was there was edited
    for p, raw in before.items():
        assert (root / "benchmark" / p).read_bytes() == raw

    # a cell whose file is missing fails with its name
    bench["workloads"].append({
        "name": "mistral7b.nowhere", "config": "mistral-7b-event",
        "traffic": "nowhere", "chips": 1, "why": "no file"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cmd[3] = "mistral7b.nowhere"
    done = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=120)
    assert done.returncode != 0 and done.stdout == ""
    assert "mistral7b.nowhere" in done.stderr


def test_alone_in_a_directory_it_prints_no_result(tmp_path):
    """A directory with BENCHMARK.json and the files under ``paths`` only: the
    program is missing, so the run fails before a word goes to stdout."""
    root = tmp_path / "bare"
    shutil.copytree(os.path.join(loader.ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(loader.ROOT, "BENCHMARK.json"), root)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mistral7b.camera_qa", "--seed", "1", "--seconds", "1", "--trace", "0",
         "--rehearsal"], cwd=root, env=env, capture_output=True, text=True,
        timeout=120)
    assert done.returncode != 0 and done.stdout == ""
    assert "eventgpt_tpu" in done.stderr
