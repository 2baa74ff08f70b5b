"""The Granite configuration's files: the file's ``bytes`` against the shapes
the program serves, its counts by hand at the toy's sizes and at the
published ones, the new reader (``ssm_step_roofline``) on a hand-made trace,
the cell's rehearsal with its control, the server's refusals through
``build_server``, and the cell's entries in ``BENCHMARK.json``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import flops, loader, measure

CELL = "granite-4.0-h-small.camera_describe"
TOY = loader.read_json(os.path.join(loader.HERE, "configs",
                                    "granite-4.0-h-small-tiny.json"))
FULL = loader.read_json(os.path.join(loader.HERE, "configs",
                                     "granite-4.0-h-small-event.json"))
NEMOTRON = loader.read_json(os.path.join(loader.HERE, "configs",
                                         "nemotron3-super-120b-event.json"))
COUNTS = loader.counts_of(TOY)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def dispatch(ts_s: float, touched, fullest, held, tokens) -> dict:
    return {"name": "dispatch", "ph": "X", "ts": ts_s * 1e6, "dur": 10.0,
            "args": {"chunk": 4, "live": 3, "rows": 4,
                     "experts_touched": touched, "expert_fullest": fullest,
                     "held_assignments": held, "routed_tokens": tokens}}


def run_of(ring, hf=TOY, trace=None) -> measure.RunData:
    return measure.RunData(
        cell={}, params={}, hf=hf, t0=10.0, t1=20.0, rows=[], ring=ring,
        compiles_in_window=0, device_kind="TPU v5 lite", n_chips=1,
        peaks=PEAKS, trace=trace)


def test_the_files_bytes_against_the_served_shapes():
    """The table in the file, block by block of what ``served_shapes`` says
    the program holds, and the cache as ``nemotron_h.init_cache`` builds it
    at the cell's 64 rows x 2,048 positions."""
    import jax
    import jax.numpy as jnp

    from eventgpt_tpu.config import from_hf_config
    from eventgpt_tpu.models import nemotron_h
    from eventgpt_tpu.models.synthetic import served_shapes

    cfg = from_hf_config(FULL, attn_impl="dense")
    shapes = served_shapes(cfg, jnp.bfloat16, "none", False)
    count = lambda tree: sum(leaf.size for leaf in
                             jax.tree_util.tree_leaves(tree))
    size = lambda tree: sum(leaf.size * leaf.dtype.itemsize
                            for leaf in jax.tree_util.tree_leaves(tree))
    dec = shapes["llama"]
    layers = dec["layers"]
    assert len(layers) == 20 and "embed_tokens" not in dec
    assert round(count(layers[0]) / 1e6, 1) == 102.3       # a mamba mixer
    assert round(count(layers[10]) / 1e6, 1) == 41.9       # the attention
    assert round(count(layers[1]) / 1e6, 1) == 358.9       # an expert sublayer
    assert layers[1]["experts_gate"].shape == (36, 4096, 768)
    assert layers[1]["experts_down"].shape == (36, 768, 4096)
    assert layers[1]["shared_gate"].shape == (4096, 1536)
    assert layers[1]["router"].shape == (4096, 72)
    assert round(count(layers) / 1e9, 2) == 4.55
    assert round(size(layers) / 1e9, 2) == 9.10
    assert dec["lm_head"].shape == (4096, 50176)  # the tied table
    assert round(size(dec["lm_head"]) / 1e9, 2) == 0.41
    assert round(size({k: v for k, v in shapes.items() if k != "llama"})
                 / 1e9, 2) == 0.68
    assert round(size(shapes) / 1e9, 3) == 10.197
    for text in ("102.3 M", "41.9 M", "358.9 M", "4.55 G", "9.10 GB",
                 "0.41 GB", "0.68 GB", "10.19 GB", "10.197 GB", "2.98 GB"):
        assert any(text in v for v in FULL["bytes"].values()), text
    cache = jax.eval_shape(
        lambda: nemotron_h.init_cache(cfg.llama, 64, 2048, jnp.bfloat16))
    assert cache["h"].shape == (9, 64, 128, 64, 128)
    assert cache["k"].shape == (1, 64, 2048, 8, 128)
    assert round(size(cache["h"]) / 1e9, 2) == 2.42
    assert round(size(cache["conv"]) / 1e9, 2) == 0.03
    assert round((size(cache["k"]) + size(cache["v"])) / 1e9, 2) == 0.54
    assert round(size(cache) / 1e9, 2) == 2.98


def test_no_answer_can_end_at_the_tied_heads_end_of_sequence():
    """The tied table is the head's leaf, so the end-of-sequence column that
    ``weights.make_tree`` zeroes in a head is that id's logit at every
    position (0) and its embedding: no answer ends before its budget."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import weights
    from eventgpt_tpu.config import from_hf_config
    from eventgpt_tpu.models.synthetic import served_shapes

    cfg = from_hf_config(TOY, attn_impl="dense")
    tree = weights.make_tree(served_shapes(cfg, jnp.float32, "none", False),
                             2147483690, never=(2,), rules=TOY["weights"])
    head = np.asarray(tree["llama"]["lm_head"])
    assert head.shape == (64, 512) and not head[:, 2].any()
    assert np.count_nonzero(head) == 64 * 511
    assert abs(float(head.std()) * 12 * 8 - 1) < 0.1  # 1 / (12 sqrt(64))


def test_the_counts_by_hand_at_the_toys_sizes():
    # d 64; mamba: 8 heads x 16, 1 group x 16 states, 4 taps: inner 128,
    # conv channels 128 + 2 x 16 = 160; attention: 4 / 2 heads of 16;
    # experts: 16 in the router, 8 held, top 5, width 48, shared 96;
    # layers mamba x 5, attention, mamba x 4; vocab 512, tied
    assert COUNTS.mamba_params(TOY) == 64 * (128 + 160 + 8) + 160 * 4 + 128 * 64
    assert COUNTS.attention_params(TOY) == 2 * 64 * 64 + 2 * 64 * 32
    assert COUNTS.expert_params(TOY) == 3 * 64 * 48
    assert COUNTS.moe_dense_params(TOY) == 64 * 16 + 3 * 64 * 96
    dense = 9 * 27776 + 12288 + 10 * 19456
    assert COUNTS.dense_params(TOY) == dense == 456832
    assert COUNTS.recurrence_flops(TOY) == 5 * 8 * 16 * 16
    head = 2 * 64 * 512
    assert flops.attention_flops(TOY, 10, 5) == 1 * 4 * 4 * 16 * (50 + 55)
    # a prompt's routed experts: 5 x 8 / 16 = 2.5 held assignments a token
    # a layer, 2 FLOP a multiply-add of an expert's 9216 parameters
    token = 2 * dense + 9 * 10240
    routed = 10 * 2.5 * 2 * 9216
    assert flops.prefill_flops(TOY, 10, 5) == ((token + routed) * 10
                                               + 256 * 105 + head)
    assert flops.decode_flops(TOY, 100) == token + head + 256 * 101
    assert flops.state_bytes_per_position(TOY) == 2 * 2 * 16 * 1 * 2 == 128
    assert flops.state_bytes_per_row(TOY) == 2 * 9 * (8 * 16 * 16 * 4
                                                      + 3 * 160 * 2)
    # the tied head counted once, as the head
    assert flops.weight_bytes_per_step(TOY) == (dense + 64 * 512) * 2
    # a step whose tokens touched 3 held experts a layer, 2 tokens
    ring = [dispatch(11.0, [[3] * 10], [[1] * 10], [[4] * 10], [2])]
    run = run_of(ring)
    assert flops.weight_bytes_per_step(TOY, run) == (
        dense + 64 * 512 + 30 * 9216) * 2
    assert flops.decode_flops(TOY, 100, run) == (
        flops.decode_flops(TOY, 100) + 10 * 2.0 * 2 * 9216)


def test_the_counts_at_the_published_sizes_are_the_issues():
    c = loader.counts_of(FULL)
    assert c.mamba_params(FULL) == 4096 * 16768 + 8448 * 4 + 8192 * 4096
    assert round(c.mamba_params(FULL) / 1e6, 1) == 102.3
    assert round(c.attention_params(FULL) / 1e6, 1) == 41.9
    assert c.expert_params(FULL) == 3 * 4096 * 768 == 9437184
    assert round((c.moe_dense_params(FULL) + 36 * c.expert_params(FULL))
                 / 1e6, 1) == 358.9
    assert (c.layers_of(FULL, "mamba"), c.layers_of(FULL, "attention"),
            c.layers_of(FULL, "moe")) == (9, 1, 10)
    # a step of 64 rows that touches all 36 held experts of every layer:
    # experts 6.8 GB, dense weights and the head 2.7 GB, h read and written
    # 4.8 GB (4.9 with the conv tails)
    every = [dispatch(11.0, [[36] * 10], [[18] * 10], [[320] * 10], [64])]
    run = run_of(every, FULL)
    assert round(flops.weight_bytes_per_step(FULL) / 1e9, 2) == 2.72
    assert round((flops.weight_bytes_per_step(FULL, run)
                  - flops.weight_bytes_per_step(FULL)) / 1e9, 2) == 6.79
    assert flops.state_bytes_per_row(FULL) == 2 * 9 * (
        128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert round(64 * 2 * 9 * 128 * 64 * 128 * 4 / 1e9, 2) == 4.83
    assert flops.state_bytes_per_position(FULL) == 2 * 8 * 128 * 2
    # a prompt's decoder: 818 positions at ~2.7 TFLOP
    assert round(flops.prefill_flops(FULL, 818) / 1e12, 1) == 2.7


def test_the_new_reader_on_a_hand_made_trace():
    read = measure.load_reader("layer_metrics", "ssm_step_roofline").read
    from benchmark.layer_metrics import ssm_step_roofline as mod

    # Granite: nine planes, 64 rows, one group; Nemotron: five, eight groups
    granite = ("%ssm_step.1 = (f32[9,64,128,64,128]{4,3,2,1,0:T(8,128)}, "
               "f32[64,128,64]{2,1,0:T(8,128)S(1)}) custom-call(%h.1, ...), "
               'custom_call_target="tpu_custom_call"')
    other = ("%custom-call = f32[64,128,64]{1,2,0:T(8,128)S(1)} "
             "custom-call(%slice-done), custom_call_target=\"ConcatBitcast\"")
    fusion = "%fusion.12 = f32[9,64,128,64,128]{4,3,2,1,0} fusion(%ssm_step)"
    trace = {"t0": 11.0, "t1": 16.0, "ops": {
        granite: {"runs": 40, "total_s": 40 * 1.0e-3},
        other: {"runs": 40, "total_s": 1.0}, fusion: {"runs": 1, "total_s": 1.0}}}
    plane = 64 * 128 * 64 * 128
    byts = 4.0 * (2 * plane + 3 * 64 * 128 * 64 + 2 * 64 * 1 * 128)
    assert mod.price(9, 64, 128, 64, 128, 1) == {"flop": 5.0 * plane,
                                                  "bytes": byts}
    least = max(5.0 * plane / 197e12, byts / 819e9)
    assert byts / 819e9 > 5.0 * plane / 197e12  # memory sets the bound
    assert read(run_of([], FULL, trace)) == pytest.approx(
        100 * least / 1.0e-3)
    assert round(100 * least / 1.0e-3, 1) == 66.3  # 543 MB in 1 ms
    nemotron = granite.replace("f32[9,", "f32[5,")
    got = read(run_of([], NEMOTRON, {**trace, "ops": {
        nemotron: {"runs": 20, "total_s": 20 * 0.83e-3}}}))
    byts8 = 4.0 * (2 * plane + 3 * 64 * 128 * 64 + 2 * 64 * 8 * 128)
    assert got == pytest.approx(100 * byts8 / 819e9 / 0.83e-3)
    # nothing without a trace, without the kernel, or without recurrent state
    assert read(run_of([], FULL)) is None
    assert read(run_of([], FULL, {**trace, "ops": {other: trace["ops"][other]}})) is None
    dense = loader.read_json(os.path.join(loader.HERE, "configs",
                                          "rehearsal-tiny.json"))
    assert read(run_of([], dense, trace)) is None


def test_benchmark_json_has_the_cell_and_its_metrics():
    bench = loader.read_benchmark()
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": "granite-4.0-h-small-event",
                    "traffic": "camera_describe", "chips": 1}
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert entry["file"] == "benchmark/configs/granite-4.0-h-small-event.json"
    assert entry["reduced"] == FULL["reduced"] == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]
    reported = {m["name"] for m in measure.metrics_for(bench, CELL, "per_layer")}
    hybrid = {m["name"] for m in measure.metrics_for(
        bench, "nemotron3-super.camera_describe", "per_layer")}
    assert hybrid - {"staged_admit_pct"} <= reported
    assert {"ssm_step_roofline", "decode_hbm_pct", "prefill_mfu_pct",
            "experts_touched_pct", "expert_load_max_over_mean",
            "held_assignments_pct", "setup_compile_s", "setup_programs",
            "setup_cache_hit_pct"} <= reported
    assert not {"staged_admit_pct", "flash_window_roofline",
                "rows_past_window_pct"} & reported
    (m,) = [m for m in bench["per_layer"] if m["name"] == "ssm_step_roofline"]
    assert m == {"name": "ssm_step_roofline", "unit": "%", "better": "higher",
                 "source": "device_trace", "layer": "kernels",
                 "moves": "out_tok_per_s", "workloads": [
                     CELL, "nemotron3-super.camera_describe",
                     "nemotron3-super.camera_describe_peak"]}
    # every number of the catalog's row under its own key, but the three cut
    catalog = {"attention_multiplier": 0.0078125, "embedding_multiplier": 12,
               "hidden_size": 4096, "intermediate_size": 768,
               "logits_scaling": 16, "mamba_chunk_size": 256,
               "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
               "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
               "max_position_embeddings": 131072, "num_attention_heads": 32,
               "num_experts_per_tok": 10, "num_key_value_heads": 8,
               "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
               "rope_theta": 10000, "shared_intermediate_size": 1536}
    assert {k: FULL[k] for k in catalog} == catalog
    assert len(FULL["layer_types"]) == 40 and FULL["tie_word_embeddings"]
    assert FULL["published"] == {"num_hidden_layers": 40,
                                 "num_local_experts": 72, "vocab_size": 100352}
    assert (FULL["num_hidden_layers"], FULL["num_local_experts"],
            FULL["vocab_size"]) == (10, 36, 50176)
    assert FULL["n_routed_experts"] == FULL["num_local_experts"]
    assert FULL["chips_sharing_a_layer"] == 2 and "--quant" not in FULL["flags"]
    assert TOY["layer_types"] == FULL["layer_types"][:10]


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS") != "cpu",
                    reason="a CPU test")
def test_the_cell_rehearses_with_its_control(tmp_path):
    """From a copy of the benchmark's files, as ``test_data_driven.py``
    rehearses. float32 on the CPU, so the served tokens are the reference's
    own; the int8 control's are not."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(loader.ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(loader.ROOT, "BENCHMARK.json"), root)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": loader.ROOT + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 42), "--seconds", "3", "--trace", "1", "--rehearsal",
         "--control"], cwd=root, env=env, capture_output=True,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["configuration"] == {
        "name": "granite-4.0-h-small-tiny",
        "reference": "references/granitemoehybrid.py", "control": "int8",
        "counts": "counts/granitemoehybrid.py",
        "rehearsal": "granite-4.0-h-small-tiny"}
    assert last["control"] == "int8"
    assert last["served_reading"]["served_gap"] <= 1e-5
    assert last["check"]["tokens_compared"]["value"] >= 3
    assert last["check"]["stream_tokens_lost"]["value"] == 0
    saw = last["rehearsal_saw"]
    assert 0 < saw["experts_touched_pct"] <= 100
    assert saw["expert_load_max_over_mean"] >= 1
    assert 0 < saw["held_assignments_pct"] <= 100
    assert saw["compiles_in_window"] == 0 and "live_rows_pct" in saw
    assert saw["setup_programs"] > 0
    assert "staged_admit_pct" not in saw and "ssm_step_roofline" not in saw


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
    """The CLI's own ``build_server`` on the toy configuration: the hybrid's
    refusals hold for a ``granitemoehybrid`` file unchanged."""
    from eventgpt_tpu.cli import serve as serve_cli

    argv = ["--model_path", loader.PREFIX + "granite-4.0-h-small-tiny",
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
