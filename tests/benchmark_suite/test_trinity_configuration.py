"""The Trinity configuration's files: the file's ``bytes`` against the shapes
the program serves, its counts by hand at the toy's sizes (the band's pairs,
the ring's bytes) and at the published ones, the two new readers on a
hand-made ring and trace, the cell's rehearsal with its control, and the
server's refusals through ``build_server``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import flops, loader, measure

CELL = "trinity-large.camera_log_describe"
TOY = loader.read_json(os.path.join(loader.HERE, "configs",
                                    "trinity-large-tiny.json"))
FULL = loader.read_json(os.path.join(loader.HERE, "configs",
                                     "trinity-large-event.json"))
COUNTS = loader.counts_of(TOY)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def dispatch(ts_s: float, live: int, past=None, **counters) -> dict:
    args = {"chunk": 4, "live": live, "rows": 4, **counters}
    if past is not None:
        args["past_window"] = past
    return {"name": "dispatch", "ph": "X", "ts": ts_s * 1e6, "dur": 10.0,
            "args": args}


def run_of(ring, hf=TOY, trace=None) -> measure.RunData:
    return measure.RunData(
        cell={}, params={}, hf=hf, t0=10.0, t1=20.0, rows=[], ring=ring,
        compiles_in_window=0, device_kind="TPU v5 lite", n_chips=1,
        peaks=PEAKS, trace=trace)


def test_the_files_bytes_against_the_served_shapes():
    """The table in the file (ISSUE 33's), leaf by leaf of what
    ``served_shapes`` says the program holds, and the cache as
    ``afmoe.init_cache`` builds it."""
    import jax
    import jax.numpy as jnp

    from eventgpt_tpu.config import from_hf_config
    from eventgpt_tpu.models import afmoe
    from eventgpt_tpu.models.synthetic import served_shapes

    cfg = from_hf_config(FULL, attn_impl="dense")
    shapes = served_shapes(cfg, jnp.bfloat16, "none", False)
    size = lambda tree: sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(tree))
    layers = shapes["llama"]["layers"]
    assert len(layers) == 5 and "mlp" in layers[0] and "experts" in layers[1]
    attn = sum(size(layers[1][n]) for n in
               ("q_proj", "k_proj", "v_proj", "gate_proj", "o_proj"))
    assert round(attn / 1e9, 3) == 0.126
    assert round(size(layers[1]["experts"]) / 1e9, 3) == 1.812
    assert layers[1]["experts"]["gate"].shape == (32, 3072, 3072)
    assert layers[1]["router"].shape == (3072, 256)
    assert round(size(layers[1]) / 1e9, 3) == 1.996
    assert round(size(layers[0]) / 1e9, 3) == 0.352
    assert round((size(shapes["llama"]["embed_tokens"])
                  + size(shapes["llama"]["lm_head"])) / 1e9, 3) == 0.307
    assert round(size(shapes) / 1e9, 2) == 9.29
    for text in ("0.126", "1.812", "1.996", "0.352", "0.307", "9.29"):
        assert any(text in v for v in FULL["bytes"].values()), text
    cache = jax.eval_shape(
        lambda: afmoe.init_cache(cfg.llama, 32, 12800, jnp.bfloat16))
    assert cache["k"].shape == (1, 32, 12800, 8, 128)
    rings = afmoe.fixed_state(cfg.llama)
    assert len(rings) == 8 and all(
        cache[name].shape == (1, 32, 4096, 8, 128) for name in rings)
    plane = size(cache["k"]) + size(cache["v"])
    ring = sum(size(cache[name]) for name in rings)
    assert (round(plane / 1e9, 2), round(ring / 1e9, 2)) == (1.68, 2.15)
    assert round((plane + ring) / 1e9, 2) == 3.83
    # without the ring: 4 more planes of 12,800 positions
    assert round(5 * plane / 1e9, 2) == 8.39


def test_the_counts_by_hand_at_the_toys_sizes():
    # d 64; 4 / 2 heads of 16; window 16; dense MLP 128; 32 experts, 8 held,
    # top 4, expert 32; layers: window(dense), window, window, window,
    # global; vocab 512
    attn = 3 * 64 * 64 + 2 * 64 * 32
    assert COUNTS.attention_params(TOY) == attn
    assert COUNTS.expert_params(TOY) == 3 * 64 * 32
    dense = 5 * attn + 3 * 64 * 128 + 4 * (64 * 32 + 3 * 64 * 32)
    assert COUNTS.dense_params(TOY) == dense
    # the band: a query at position p sees min(p + 1, 16) keys
    assert COUNTS.band_pairs(10, 0, 16) == 55
    assert COUNTS.band_pairs(20, 0, 16) == 136 + 4 * 16
    assert COUNTS.band_pairs(10, 5, 16) == sum(min(p + 1, 16)
                                               for p in range(5, 15))
    assert COUNTS.band_pairs(7, 30, 16) == 7 * 16
    assert COUNTS.band_pairs(40, 0, 1 << 20) == COUNTS.causal_pairs(40, 0)
    pairs = 1 * (10 * 30 + 55) + 4 * 10 * 16
    assert flops.attention_flops(TOY, 10, 30) == 4 * 4 * 16 * pairs
    head = 2 * 64 * 512
    # a prompt's routed experts: 4 x 8 / 32 = 1 held assignment a token a layer
    routed = 4 * 1.0 * 2 * 6144
    assert flops.prefill_flops(TOY, 10, 30) == (
        (2 * dense + routed) * 10 + 4 * 4 * 16 * pairs + head)
    assert flops.decode_flops(TOY, 100) == (
        2 * dense + head + 4 * 4 * 16 * (101 + 4 * 16))
    # the plane: one global layer's k and v of a position; the rings: four
    # window layers x 16 slots
    assert flops.state_bytes_per_position(TOY) == 2 * 2 * 16 * 1 * 2 == 128
    assert flops.state_bytes_per_row(TOY) == 2 * 2 * 16 * 16 * 4 * 2 == 8192
    assert flops.weight_bytes_per_step(TOY) == (dense + 64 * 512) * 2


def test_the_counts_at_the_published_sizes_are_the_issues():
    c = loader.counts_of(FULL)
    assert round(c.attention_params(FULL) / 1e6, 1) == 62.9
    assert c.expert_params(FULL) * 2 == 56623104  # 56.6 MB an expert
    assert flops.state_bytes_per_position(FULL) == 4096
    assert flops.state_bytes_per_row(FULL) == 4 * 4096 * 4096
    # a prompt's prefill: 14.8 TFLOP linear (ISSUE 33 reckoned 14.6), 1.9
    # global, 4.1 banded against 7.4 unbanded
    n = 12288
    assert round(4 * 48 * 128 * c.causal_pairs(n, 0) / 1e12, 1) == 1.9
    assert round(4 * 4 * 48 * 128 * c.band_pairs(n, 0, 4096) / 1e12, 1) == 4.1
    assert round(4 * 4 * 48 * 128 * c.causal_pairs(n, 0) / 1e12, 1) == 7.4
    linear = flops.prefill_flops(FULL, n) - flops.attention_flops(FULL, n)
    assert round(linear / 1e12, 1) == 14.8
    # a step of 32 rows that touches 13 held experts a layer: dense weights
    # and head 1.24 GB, experts 2.9 GB, keys and values 3.8 GB
    step = [dispatch(11.0, 32, 32, experts_touched=[[13] * 4],
                     expert_fullest=[[2] * 4], held_assignments=[[16] * 4],
                     routed_tokens=[32])]
    run = run_of(step, FULL)
    assert round(flops.weight_bytes_per_step(FULL) / 1e9, 2) == 1.24
    assert round((flops.weight_bytes_per_step(FULL, run)
                  - flops.weight_bytes_per_step(FULL)) / 1e9, 1) == 2.9
    state = flops.decode_step_bytes(FULL, [12400] * 32, run) \
        - flops.weight_bytes_per_step(FULL, run)
    assert round(state / 1e9, 1) == 3.8
    assert flops.decode_flops(FULL, 12400, run) == pytest.approx(
        flops.decode_flops(FULL, 12400) + 4 * 0.5 * 2 * c.expert_params(FULL))


def test_the_two_new_readers_on_a_hand_made_ring_and_trace():
    read = lambda name, run: measure.load_reader("layer_metrics", name).read(run)
    ring = [dispatch(11.0, 3, 3), dispatch(12.0, 4, 2),
            dispatch(13.0, 0, 0),                     # no live row
            dispatch(14.0, 2),                        # a program without it
            dispatch(99.0, 4, 0)]                     # outside the window
    assert read("rows_past_window_pct", run_of(ring)) == pytest.approx(
        100 * 5 / 7)
    assert read("rows_past_window_pct", run_of([])) is None
    assert read("rows_past_window_pct", run_of(ring[3:4])) is None
    # the banded kernel: two calls of one prompt at the toy's 4 heads, 2 kv
    # heads, 16 of head size, window 16, 256 positions
    name = ("%flash_window_forward.1 = bf16[4,256,16]{2,1,0:T(8,128)(2,1)} "
            "custom-call(...)")
    other = "%flash_forward.6 = bf16[4,256,16]{2,1,0} custom-call(...)"
    trace = {"t0": 11.0, "t1": 16.0, "ops": {
        name: {"runs": 2, "total_s": 4e-6},
        other: {"runs": 1, "total_s": 1.0}}}
    pairs = 136 + 240 * 16
    flop = 4 * 4 * 16 * pairs
    byts = 2 * 16 * 256 * (2 * 4 + 2 * 2)
    assert COUNTS.band_flash_call(4, 256, 16, TOY) == {
        "flop": float(flop), "bytes": float(byts)}
    least = max(flop / 197e12, byts / 819e9)
    assert read("flash_window_roofline", run_of([], trace=trace)) == \
        pytest.approx(100 * 2 * least / 4e-6)
    assert read("flash_window_roofline", run_of([])) is None
    assert read("flash_window_roofline", run_of(
        [], trace={**trace, "ops": {other: trace["ops"][other]}})) is None
    # a configuration whose counts know no band: nothing, and no error
    dense = loader.read_json(os.path.join(loader.HERE, "configs",
                                          "rehearsal-tiny.json"))
    assert read("flash_window_roofline", run_of([], dense, trace)) is None


def test_benchmark_json_has_the_cell_and_its_metrics():
    bench = loader.read_benchmark()
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": "trinity-large-event",
                    "traffic": "camera_log_describe", "chips": 1}
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == FULL["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    reported = {m["name"] for m in measure.metrics_for(bench, CELL, "per_layer")}
    assert {"flash_window_roofline", "rows_past_window_pct", "flash_roofline",
            "decode_hbm_pct", "prefill_mfu_pct", "serve_mfu_pct",
            "experts_touched_pct", "expert_load_max_over_mean",
            "held_assignments_pct"} <= reported
    assert "staged_admit_pct" not in reported and len(reported) == 25
    for name in ("flash_window_roofline", "rows_past_window_pct"):
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL]
    # every published number is in the file under its own key
    row = {"hidden_size": 3072, "num_attention_heads": 48,
           "num_key_value_heads": 8, "head_dim": 128,
           "intermediate_size": 12288, "moe_intermediate_size": 3072,
           "num_experts_per_tok": 4, "sliding_window": 4096,
           "route_scale": 2.448, "rope_theta": 10000,
           "max_position_embeddings": 262144, "rms_norm_eps": 1e-05,
           "global_attn_every_n_layers": 4, "num_shared_experts": 1}
    assert {k: FULL[k] for k in row} == row
    assert FULL["published"]["num_experts"] == 256
    assert (FULL["num_hidden_layers"], FULL["num_dense_layers"],
            FULL["num_experts"], FULL["vocab_size"]) == (5, 1, 32, 25024)
    assert FULL["chips_sharing_a_layer"] == 8
    assert FULL["n_routed_experts"] == FULL["num_experts"]
    assert "--quant" not in FULL["flags"]
    kept = [FULL["layer_types"][i] for i in FULL["layers_kept"]]
    assert kept == TOY["layer_types"] and len(FULL["layer_types"]) == 60
    # every prompt of the mix is three windows long, on the grain
    from benchmark import traffic

    params = loader.read_json(os.path.join(loader.HERE, "workloads",
                                           CELL + ".json"))
    qs = traffic.questions(params)
    from benchmark.reference import prompt_ids

    pre, post = prompt_ids(qs[0])
    prompt = len(pre) + flops.event_tokens(FULL) + len(post)
    assert 3 * 4096 - 128 < prompt <= 3 * 4096
    assert prompt + params["budget"]["max"] + 1 <= 12800


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS") != "cpu",
                    reason="a CPU test")
def test_the_cell_rehearses_with_its_control(tmp_path):
    """From a copy of the benchmark's files, as ``test_data_driven.py``
    rehearses. The toy's prompts (246 positions) wrap the ring of 16 fifteen
    times; float32 on the CPU, so the served tokens are the reference's own,
    and the int8 control's are not."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(loader.ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(loader.ROOT, "BENCHMARK.json"), root)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": loader.ROOT + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 33), "--seconds", "3", "--trace", "1", "--rehearsal",
         "--control"], cwd=root, env=env, capture_output=True,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["configuration"] == {
        "name": "trinity-large-tiny", "reference": "references/afmoe.py",
        "control": "int8", "counts": "counts/afmoe.py",
        "rehearsal": "trinity-large-tiny"}
    assert last["control"] == "int8"
    assert last["served_reading"]["served_gap"] <= 1e-3
    # the control in the program's place is not correct, by the cell's limits
    assert last["rehearsal_checks_passed"] is False
    assert (last["check"]["mean_gap"]["value"]
            > last["check"]["mean_gap"]["limit"])
    assert last["check"]["tokens_compared"]["value"] >= 3
    assert last["check"]["stream_tokens_lost"]["value"] == 0
    saw = last["rehearsal_saw"]
    assert saw["rows_past_window_pct"] == 100.0
    assert 0 < saw["experts_touched_pct"] <= 100
    assert 0 < saw["held_assignments_pct"] <= 100
    assert saw["compiles_in_window"] == 0 and "live_rows_pct" in saw
    assert "staged_admit_pct" not in saw


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS") != "cpu",
                    reason="a CPU test")
@pytest.mark.parametrize("flags, named", [
    (["--kv_layout", "paged"], "--kv_layout paged"),
    (["--kv_cache", "int8"], "--kv_cache int8"),
    (["--speculative", "4"], "--speculative"),
    (["--prefill_chunk", "64"], "--prefill_chunk"),
    (["--prefill_budget", "8"], "--prefill_budget"),
    (["--quant", "int8"], "--quant"),
    (["--fuse_params"], "--fuse_params"),
    (["--prefix_cache_mb", "64"], "--prefix_cache_mb"),
])
def test_build_server_refuses_by_the_flags_name(flags, named):
    """The CLI's own ``build_server`` on the toy configuration: each option
    that cannot serve a ring refuses with its name and the ring's reason."""
    from eventgpt_tpu.cli import serve as serve_cli

    argv = ["--model_path", loader.PREFIX + "trinity-large-tiny",
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
    assert named in str(e.value) and "window layers" in str(e.value)
