"""benchmark/flops.py against hand counts for both configurations."""

import os

import pytest

from benchmark import flops, loader


def hf_of(name):
    return loader.read_json(os.path.join(loader.HERE, "configs", name + ".json"))


def test_mistral_7b_hand_counts():
    hf = hf_of("mistral-7b-event")
    # a layer: q 4096x4096, k and v 4096x1024 each, o 4096x4096, 3 x 4096x14336
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert flops.decoder_layer_params(hf) == layer == 218103808
    assert flops.decoder_params(hf) == 32 * layer
    assert flops.lm_head_params(hf) == 4096 * 32000
    assert flops.kv_bytes_per_position(hf) == 8 * 128 * 2 * 32 * 2 == 131072
    assert flops.event_tokens(hf) == 5 + 577
    # one token at context 900: 2 x (decoder + head) + 4 x 32 layers x 4096 x 901
    want = 2 * (32 * layer + 4096 * 32000) + 32 * 4 * 32 * 128 * 901
    assert flops.decode_flops(hf, 900) == pytest.approx(want)
    # a step of 16 rows at 900 positions: int8 weights + scales + live KV
    w = 32 * layer + 4096 * 32000
    cols = 32 * (4096 + 2 * 1024 + 4096 + 2 * 14336 + 4096) + 32000
    assert flops.weight_bytes_per_step(hf) == w + 4 * cols
    assert flops.decode_step_bytes(hf, [900] * 16) == (
        w + 4 * cols + 16 * 900 * 131072)


def test_internlm2_hand_counts():
    hf = hf_of("internlm2-1.8b-event")
    layer = 2048 * 2048 * 2 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert flops.decoder_layer_params(hf) == layer
    assert flops.decoder_params(hf) == 24 * layer == 1509949440
    assert flops.lm_head_params(hf) == 2048 * 92544
    assert flops.kv_bytes_per_position(hf) == 8 * 128 * 2 * 24 * 2 == 98304


@pytest.mark.parametrize("name", ["mistral-7b-event", "internlm2-1.8b-event"])
def test_tower_and_prefill(name):
    hf = hf_of(name)
    d = hf["hidden_size"]
    # CLIP ViT-L/14-336: 24 x (4 x 1024^2 + 2 x 1024 x 4096) + patch embedding
    assert flops.tower_params(hf) == 24 * (4 * 1024**2 + 2 * 1024 * 4096) \
        + 3 * 14 * 14 * 1024
    assert flops.projector_params(hf) == 1024 * d + 2 * d * d
    enc = flops.encode_flops(hf)
    matmuls = 5 * 577 * 2 * (24 * (4 * 1024**2 + 2 * 1024 * 4096)
                             + flops.projector_params(hf))
    assert matmuls < enc < 1.15 * matmuls  # attention and patches on top
    # prefill of 800 positions: 2 x params x positions dominates
    pre = flops.prefill_flops(hf, 800)
    assert pre == pytest.approx(
        2 * flops.decoder_params(hf) * 800
        + hf["num_hidden_layers"] * 4 * d * (800 * 801 / 2)
        + 2 * flops.lm_head_params(hf))
    # a prefix hit computes the suffix only, attending to the cached part
    hit = flops.prefill_flops(hf, 64, before=736)
    assert hit < pre / 8
    assert flops.attention_flops(hf, 64, 736) == pytest.approx(
        hf["num_hidden_layers"] * 4 * d * (64 * 736 + 64 * 65 / 2))


def test_flash_call_and_roofline():
    peaks = loader.read_json(os.path.join(loader.HERE, "peaks.json"))["TPU v5 lite"]
    call = flops.flash_call(896, 896, 32, 128, batch=2)
    assert call["flop"] == 4 * 2 * 32 * 128 * 896 * 896 / 2
    assert call["bytes"] == 2 * 2 * 32 * 128 * 4 * 896
    # at one prompt bucket the two bounds all but meet; the larger one holds
    t, bound = flops.roofline_s(call["flop"], call["bytes"], peaks)
    assert t == pytest.approx(max(call["flop"] / 197e12, call["bytes"] / 819e9))
    t, bound = flops.roofline_s(1e12, 1e6, peaks)
    assert bound == "compute" and t == pytest.approx(1e12 / 197e12)
    t, bound = flops.roofline_s(1e6, 1e9, peaks)
    assert bound == "memory" and t == pytest.approx(1e9 / 819e9)
