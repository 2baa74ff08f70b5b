"""The hybrid configuration's files: its counts by hand at the toy's sizes
and at the published ones, the bytes of a step following the program's
counter, the three new readers on a hand-made run, the cell's rehearsal, the
experts' flips behind the widest gap, and the server's refusals through
``build_server``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import flops, loader, measure

CELL = "nemotron3-super.camera_describe"
TOY = loader.read_json(os.path.join(loader.HERE, "configs",
                                    "nemotron3-super-tiny.json"))
FULL = loader.read_json(os.path.join(loader.HERE, "configs",
                                     "nemotron3-super-120b-event.json"))
COUNTS = loader.counts_of(TOY)


def dispatch(ts_s: float, touched, fullest, held, tokens) -> dict:
    return {"name": "dispatch", "ph": "X", "ts": ts_s * 1e6, "dur": 10.0,
            "args": {"chunk": 4, "live": 3, "rows": 4,
                     "experts_touched": touched, "expert_fullest": fullest,
                     "held_assignments": held, "routed_tokens": tokens}}


def run_of(ring, hf=TOY, trace=None) -> measure.RunData:
    return measure.RunData(
        cell={}, params={}, hf=hf, t0=10.0, t1=20.0, rows=[], ring=ring,
        compiles_in_window=0, device_kind="TPU v5 lite", n_chips=1,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace=trace)


def test_the_counts_by_hand_at_the_toys_sizes():
    # d 64; M: 8 heads x 16, 2 groups x 16 states, 4 taps: inner 128, conv
    # channels 128 + 2 x 2 x 16 = 192; *: 4 / 2 heads of 16; E: 32 experts,
    # 8 held, top 6, latent 32, expert 48, shared 96; MEMEMEM*EME; vocab 512
    assert COUNTS.mamba_params(TOY) == 64 * (128 + 192 + 8) + 192 * 4 + 128 * 64
    assert COUNTS.attention_params(TOY) == 2 * 64 * 64 + 2 * 64 * 32
    assert COUNTS.expert_params(TOY) == 2 * 32 * 48
    assert COUNTS.moe_dense_params(TOY) == 64 * 32 + 2 * 64 * 32 + 2 * 64 * 96
    dense = 5 * 29952 + 12288 + 5 * 18432
    assert COUNTS.dense_params(TOY) == dense == 254208
    assert COUNTS.recurrence_flops(TOY) == 5 * 8 * 16 * 16
    token = 2 * dense + 5 * 10240
    head = 2 * 64 * 512
    assert flops.attention_flops(TOY, 10, 5) == 1 * 4 * 4 * 16 * (50 + 55)
    # a prompt's routed experts: 6 x 8 / 32 = 1.5 held assignments a token
    # a layer, 2 FLOP a multiply-add of an expert's 3072 parameters
    routed = 5 * 1.5 * 2 * 3072
    assert flops.prefill_flops(TOY, 10, 5) == ((token + routed) * 10
                                               + 256 * 105 + head)
    assert flops.decode_flops(TOY, 100) == token + head + 256 * 101
    assert flops.state_bytes_per_position(TOY) == 2 * 2 * 16 * 1 * 2 == 128
    assert flops.state_bytes_per_row(TOY) == 2 * 5 * (8 * 16 * 16 * 4
                                                      + 3 * 192 * 2)
    assert flops.weight_bytes_per_step(TOY) == (dense + 64 * 512) * 2


def test_the_counts_at_the_published_sizes_are_the_issues():
    c = loader.counts_of(FULL)
    assert c.mamba_params(FULL) == 4096 * 18560 + 10240 * 4 + 8192 * 4096
    assert round(c.mamba_params(FULL) / 1e6, 1) == 109.6
    assert round(c.attention_params(FULL) / 1e6, 1) == 35.7
    assert c.expert_params(FULL) * 2 == 11010048  # 11.0 MB an expert
    assert round((c.moe_dense_params(FULL) + 128 * c.expert_params(FULL))
                 / 1e6, 0) == 759
    assert flops.state_bytes_per_position(FULL) == 1024
    assert flops.state_bytes_per_row(FULL) == 2 * 5 * (128 * 64 * 128 * 4
                                                       + 3 * 10240 * 2)
    # a step that reads every held expert of the five layers: the decoder
    # and the head, 9.04 GB
    every = [dispatch(11.0, [[128] * 5], [[3] * 5], [[352] * 5], [64])]
    assert round(flops.weight_bytes_per_step(FULL, run_of(every, FULL)) / 1e9,
                 2) == 9.03
    assert round(flops.weight_bytes_per_step(FULL) / 1e9, 2) == 1.98


def test_the_bytes_of_a_step_follow_the_counter():
    least = flops.weight_bytes_per_step(TOY)
    assert flops.weight_bytes_per_step(TOY, run_of([])) == least
    ring = [dispatch(11.0, [[2, 3, 0, 1, 2], [4, 4, 4, 4, 4]],
                     [[1] * 5, [2] * 5], [[2, 3, 0, 1, 2], [6] * 5], [1, 2]),
            dispatch(12.0, [[8] * 5], [[3] * 5], [[12] * 5], [3]),
            dispatch(99.0, [[1] * 5], [[1] * 5], [[1] * 5], [1])]  # outside
    run = run_of(ring)
    steps = list(COUNTS.decode_steps(run))
    assert [s["routed_tokens"] for s in steps] == [1, 2, 3]
    read = (8 + 20 + 40) / 3  # distinct held experts a step, all layers
    assert COUNTS.experts_read_per_step(run) == pytest.approx(read)
    assert flops.weight_bytes_per_step(TOY, run) == pytest.approx(
        least + read * 3072 * 2)
    # a token met (8 + 30 + 60) held assignments over 6 tokens x 5 layers
    per = 98 / 30
    assert COUNTS.held_assignments_per_token(run) == pytest.approx(per)
    assert flops.decode_flops(TOY, 7, run) == pytest.approx(
        flops.decode_flops(TOY, 7) + 5 * per * 2 * 3072)
    # with a traced window, the steps of that window alone
    traced = run_of(ring, trace={"t0": 11.5, "t1": 12.5})
    assert COUNTS.experts_read_per_step(traced) == 40
    # decode_hbm_pct through the counter: more experts read, more bytes
    assert flops.decode_step_bytes(TOY, [10, 20], run) == pytest.approx(
        flops.weight_bytes_per_step(TOY, run) + 128 * 30 + 2 * 93440)


def test_the_new_readers_on_a_hand_made_run():
    read = lambda name, run: measure.load_reader("layer_metrics", name).read(run)
    ring = [dispatch(11.0, [[2, 4, 0, 2, 2], [4, 4, 4, 4, 4]],
                     [[1, 2, 0, 1, 1], [2] * 5],
                     [[2, 6, 0, 2, 2], [8] * 5], [1, 2]),
            dispatch(12.0, [[8] * 5], [[3] * 5], [[12] * 5], [3]),
            dispatch(13.0, [], [], [], []),            # a segment that ran no step
            {"name": "dispatch", "ph": "X", "ts": 14e6, "args": {"rows": 4}},
            dispatch(99.0, [[1] * 5], [[1] * 5], [[1] * 5], [1])]
    run = run_of(ring)
    # 8 held: the first segment's ten cells average 3.0 of 8, the second 8 of 8
    assert read("experts_touched_pct", run) == pytest.approx(
        (100 * 30 / 80 + 100.0) / 2)
    # fullest x held / assignments, cells with assignments only
    first = [1 * 8 / 2, 2 * 8 / 6, 1 * 8 / 2, 1 * 8 / 2] + [2 * 8 / 8] * 5
    assert read("expert_load_max_over_mean", run) == pytest.approx(
        (sum(first) / 9 + 3 * 8 / 12) / 2)
    held = 12 + 40 + 60
    assert read("held_assignments_pct", run) == pytest.approx(
        100 * held / (6 * 5 * (1 + 2 + 3)))
    for name in ("experts_touched_pct", "expert_load_max_over_mean",
                 "held_assignments_pct"):
        assert read(name, run_of([])) is None
        # a dense configuration's run has no such counter and no such key
        dense = loader.read_json(os.path.join(loader.HERE, "configs",
                                              "rehearsal-tiny.json"))
        assert read(name, run_of(ring[3:4], dense)) is None


def test_benchmark_json_has_the_cell_and_its_metrics():
    bench = loader.read_benchmark()
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": "nemotron3-super-120b-event",
                    "traffic": "camera_describe", "chips": 1}
    reported = {m["name"] for m in measure.metrics_for(bench, CELL, "per_layer")}
    assert {"decode_hbm_pct", "prefill_mfu_pct", "serve_mfu_pct",
            "experts_touched_pct", "expert_load_max_over_mean",
            "held_assignments_pct"} <= reported and len(reported) == 23
    assert FULL["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert (FULL["num_hidden_layers"], FULL["n_routed_experts"],
            FULL["vocab_size"]) == (11, 128, 32768)
    assert "--quant" not in FULL["flags"]
    assert TOY["hybrid_override_pattern"] == \
        FULL["hybrid_override_pattern"][:11]


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS") != "cpu",
                    reason="a CPU test")
def test_the_cell_rehearses_with_its_control(tmp_path):
    """From a copy of the benchmark's files, as ``test_data_driven.py``
    rehearses: a traced run empties ``.bench_out/trace`` under its own root,
    and another file's traced rehearsal may run beside this one."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(loader.ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(loader.ROOT, "BENCHMARK.json"), root)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": loader.ROOT + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 28), "--seconds", "3", "--trace", "1", "--rehearsal",
         "--control"], cwd=root, env=env, capture_output=True,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["configuration"] == {
        "name": "nemotron3-super-tiny", "reference": "references/nemotron_h.py",
        "control": "int8", "counts": "counts/nemotron_h.py",
        "rehearsal": "nemotron3-super-tiny"}
    assert last["control"] == "int8"
    # float32 on the CPU: the served tokens are the reference's own
    assert last["served_reading"]["served_gap"] <= 1e-3
    assert last["check"]["tokens_compared"]["value"] >= 3
    assert last["check"]["stream_tokens_lost"]["value"] == 0
    saw = last["rehearsal_saw"]
    assert 0 < saw["experts_touched_pct"] <= 100
    assert saw["expert_load_max_over_mean"] >= 1
    assert 0 < saw["held_assignments_pct"] <= 100
    assert saw["compiles_in_window"] == 0 and "live_rows_pct" in saw


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS") != "cpu",
                    reason="a CPU test")
def test_the_widest_gap_sits_where_an_experts_choice_differs(tmp_path):
    """``scripts/expert_flips.py`` at the toy's widths, one seed: the served
    model (bfloat16) and the int8 control each choose other experts than the
    float32 reference at some positions, the widest gap lies at one of
    them, and where every choice is the reference's the gap stays small
    (PERF.md section 6, PR 28: why ``served_gap`` is not held for the cell)."""
    out = tmp_path / "flips.json"
    done = subprocess.run(
        [sys.executable, os.path.join(loader.ROOT, "scripts", "expert_flips.py"),
         "nemotron3-super-tiny", "2147500999", "96", "dense", str(out)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    got = json.loads(out.read_text())
    for side in ("served", "control_int8"):
        r = got[side]
        assert r["positions"] == 96
        assert 0 < r["positions_with_every_choice_the_same"] < 96
        assert r["widest_gap"] == r["widest_gap_where_a_choice_differs"]
        assert r["widest_gap_there"] < 0.1 * r["widest_gap"]
        assert any(layer["only_here"] for layer in r["layers_at_the_widest"])


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS") != "cpu",
                    reason="a CPU test")
@pytest.mark.parametrize("flags, named", [
    (["--kv_layout", "paged"], "--kv_layout paged"),
    (["--kv_cache", "int8"], "--kv_cache int8"),
    (["--speculative", "4"], "--speculative"),
    (["--spec_buckets", "0,2,4"], "--spec_buckets"),
    (["--prefill_chunk", "64"], "--prefill_chunk"),
    (["--prefill_budget", "8"], "--prefill_budget"),
    (["--prefill_budget", "-1"], "--prefill_budget"),
    (["--quant", "int8"], "--quant"),
    (["--fuse_params"], "--fuse_params"),
    (["--prefix_cache_mb", "64"], "--prefix_cache_mb"),
])
def test_build_server_refuses_by_the_flags_name(flags, named):
    """The CLI's own ``build_server`` on the toy configuration: each option
    whose mechanism carries no recurrent state refuses with its name; none
    serves."""
    from eventgpt_tpu.cli import serve as serve_cli

    argv = ["--model_path", loader.PREFIX + "nemotron3-super-tiny",
            "--dtype", "float32", "--max_batch", "2", "--max_len", "256",
            "--host", "127.0.0.1", "--port", "0"]
    if named != "--prefix_cache_mb":
        argv.append("--no_prefix_cache")
    if "--prefill_budget" not in flags:
        argv += ["--prefill_budget", "0"]
    seam = loader.Seam(seed=3, rehearsal=True)
    seam.install()
    try:
        args = serve_cli.build_parser().parse_args(argv + flags)
        with pytest.raises(ValueError) as e:
            serve_cli.build_server(args)
    finally:
        seam.uninstall()
    assert named in str(e.value) and "recurrent state" in str(e.value)
