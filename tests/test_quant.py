"""Weight-only int8 quantization: numerics + end-to-end decode parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventgpt_tpu.config import LlamaConfig
from eventgpt_tpu.models import llama as llama_mod
from eventgpt_tpu.ops import quant


def test_quantize_roundtrip_error_bounded():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 48), jnp.float32)
    q = quant.quantize_tensor(w)
    assert q["q"].dtype == jnp.int8
    assert q["s"].shape == (1, 48)
    deq = quant.dequantize_tensor(q)
    # Max error per element is half a quantization step (scale/2).
    step = np.asarray(q["s"])[0]
    err = np.abs(np.asarray(deq) - np.asarray(w))
    assert (err <= step / 2 + 1e-6).all()


def test_quantized_matmul_close():
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(k1, (4, 64), jnp.float32)
    w = jax.random.normal(k2, (64, 32), jnp.float32)
    y_ref = x @ w
    y_q = quant.matmul(x, quant.quantize_tensor(w))
    # int8 per-channel weight quantization over K=64 contractions: ~1%
    # mean relative error (per-element quant noise max|w|/127/sqrt(12),
    # accumulated over sqrt(K)).
    rel = np.abs(np.asarray(y_q - y_ref)) / (np.abs(np.asarray(y_ref)) + 1.0)
    assert rel.mean() < 2e-2


def test_stacked_layer_quantization_shapes():
    w = jax.random.normal(jax.random.PRNGKey(2), (3, 16, 8), jnp.float32)
    q = quant.quantize_tensor(w)
    assert q["q"].shape == (3, 16, 8)
    assert q["s"].shape == (3, 1, 8)
    # Per-layer slices must equal quantizing each layer independently.
    q0 = quant.quantize_tensor(w[0])
    np.testing.assert_array_equal(np.asarray(q["q"][0]), np.asarray(q0["q"]))


def test_quantized_llama_forward_close():
    cfg = LlamaConfig.tiny()
    params = llama_mod.init_llama_params(cfg, jax.random.PRNGKey(0))
    qparams = quant.quantize_llama_params(params)
    assert qparams["layers"]["attn"]["q"]["q"].dtype == jnp.int8
    # Embeddings/norms stay dense.
    assert not quant.is_quantized(qparams["embed_tokens"])
    assert not quant.is_quantized(qparams["layers"]["input_norm"])

    embeds = llama_mod.embed_tokens(params, jnp.arange(24).reshape(2, 12))
    logits_ref = llama_mod.forward(params, cfg, embeds)
    logits_q = llama_mod.forward(qparams, cfg, embeds)
    # Same argmax on nearly every position; logits close.
    agree = (np.asarray(logits_ref.argmax(-1)) == np.asarray(logits_q.argmax(-1))).mean()
    assert agree > 0.9
    assert np.abs(np.asarray(logits_q - logits_ref)).mean() < 0.05 * np.abs(
        np.asarray(logits_ref)
    ).mean() + 0.05


def test_quantized_decode_matches_quantized_prefill():
    """Prefill-then-decode under int8 agrees with one-shot prefill (the same
    invariant the bf16 path tests), proving the cache path handles the
    quantized tree."""
    cfg = LlamaConfig.tiny()
    params = quant.quantize_llama_params(
        llama_mod.init_llama_params(cfg, jax.random.PRNGKey(3))
    )
    ids = jnp.arange(10)[None]
    embeds = llama_mod.embed_tokens(params, ids)
    mask = jnp.ones((1, 10), bool)

    cache = llama_mod.init_kv_cache(cfg, 1, 16, jnp.float32)
    logits_all, cache = llama_mod.prefill(params, cfg, embeds[:, :9], mask[:, :9], cache)
    step_logits, _ = llama_mod.decode_step(
        params, cfg, embeds[:, 9:10], cache
    )
    full = llama_mod.forward(params, cfg, embeds, mask)
    np.testing.assert_allclose(
        np.asarray(step_logits[0]), np.asarray(full[0, -1]), rtol=2e-4, atol=2e-4
    )


def test_int8_kv_cache_decode_close_to_bf16():
    """Prefill + decode with an int8 KV cache tracks the f32-cache results
    (per-vector symmetric scales keep the error at the int8 noise floor),
    and greedy generate picks the same tokens."""
    from eventgpt_tpu.config import EventChatConfig
    from eventgpt_tpu.models import eventchat

    cfg = EventChatConfig.tiny()
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(4))
    pv = jnp.zeros((1, cfg.num_event_frames, 3, cfg.vision.image_size,
                    cfg.vision.image_size), jnp.float32)
    ids = [1, 5, -200, 9, 9, 12]
    out_ref = eventchat.generate(params, cfg, [ids], pv, max_new_tokens=8,
                                 temperature=0.0, eos_token_id=2)[0]
    out_q = eventchat.generate(params, cfg, [ids], pv, max_new_tokens=8,
                               temperature=0.0, eos_token_id=2, kv_quant=True)[0]
    assert out_q == out_ref


def test_int8_kv_cache_logit_error_bounded():
    cfg = LlamaConfig.tiny()
    params = llama_mod.init_llama_params(cfg, jax.random.PRNGKey(5))
    ids = jnp.arange(12)[None]
    embeds = llama_mod.embed_tokens(params, ids)
    mask = jnp.ones((1, 12), bool)

    def run(quant_cache):
        cache = llama_mod.init_kv_cache(cfg, 1, 16, jnp.float32, quant=quant_cache)
        logits, cache = llama_mod.prefill(params, cfg, embeds[:, :11],
                                          mask[:, :11], cache)
        step_logits, _ = llama_mod.decode_step(params, cfg, embeds[:, 11:12], cache)
        return np.asarray(step_logits)

    ref = run(False)
    got = run(True)
    assert np.abs(got - ref).max() < 0.1 * (np.abs(ref).max() + 1)


def test_fused_params_forward_matches_unfused():
    """fuse_llama_params (qkv / gate-up concat) is numerically a no-op."""
    cfg = LlamaConfig.tiny()
    params = llama_mod.init_llama_params(cfg, jax.random.PRNGKey(12))
    fused = llama_mod.fuse_llama_params(params)
    assert "qkv" in fused["layers"]["attn"] and "q" not in fused["layers"]["attn"]
    embeds = llama_mod.embed_tokens(params, jnp.arange(24).reshape(2, 12))
    a = np.asarray(llama_mod.forward(params, cfg, embeds))
    b = np.asarray(llama_mod.forward(fused, cfg, embeds))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_fused_quantized_decode_matches_prefill():
    """Fusion + int8 quantization composed, through prefill/decode."""
    cfg = LlamaConfig.tiny()
    params = quant.quantize_llama_params(
        llama_mod.fuse_llama_params(
            llama_mod.init_llama_params(cfg, jax.random.PRNGKey(13))
        )
    )
    ids = jnp.arange(10)[None]
    embeds = llama_mod.embed_tokens(params, ids)
    mask = jnp.ones((1, 10), bool)
    cache = llama_mod.init_kv_cache(cfg, 1, 16, jnp.float32)
    _, cache = llama_mod.prefill(params, cfg, embeds[:, :9], mask[:, :9], cache)
    step_logits, _ = llama_mod.decode_step(params, cfg, embeds[:, 9:10], cache)
    full = llama_mod.forward(params, cfg, embeds, mask)
    np.testing.assert_allclose(
        np.asarray(step_logits[0]), np.asarray(full[0, -1]), rtol=2e-4, atol=2e-4
    )
