"""Weight-only int8 quantization for the decode path.

Batch-1 decode is HBM-bandwidth-bound: every generated token streams the
full weight set out of HBM (~13.5 GB bf16 for 7B), so tokens/sec is capped
at bandwidth / weight-bytes. Storing matmul weights as int8 with per-output-
channel f32 scales halves the bytes read per token; the dequantize
(int8 -> bf16 multiply-by-scale) fuses into the matmul operands on TPU, so
the MXU still sees bf16 inputs while HBM only ever sees int8.

The reference reaches the same class of optimization through bitsandbytes
(``requirements.txt:11``; ``TrainingArguments.bits/quant_type`` in the
training pyc, SURVEY.md §2.2). Here it is a pure-functional tree transform:
``quantize_llama_params`` maps selected weight leaves to
``{"q": int8, "s": f32 scale}`` dicts, and the matmul helper in
``models/llama.py`` dispatches on leaf type — the same jitted decode code
serves both precisions.

Symmetric per-channel scheme: ``s = max|w| / 127`` over the contraction
axis, ``q = round(w / s)``. Activations, norms, embeddings, and the KV cache
stay in the compute dtype.
"""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

QuantizedLeaf = Dict[str, jnp.ndarray]  # {"q": int8 [..., K, N], "s": f32 [..., 1, N]}


def is_quantized(leaf: Any) -> bool:
    return isinstance(leaf, dict) and "q" in leaf and "s" in leaf


def is_lora(leaf: Any) -> bool:
    """Apply-form LoRA composite leaf: {"w": base, "a": A*scale, "b": B}.

    ``x @ W_eff`` evaluates as ``x@w + (x@a)@b`` — the rank-r update is two
    skinny matmuls instead of a materialized (K, N) delta, so stage-2 never
    holds a second copy of the 7B weight set (``train/lora.py:apply_lora``).
    """
    return isinstance(leaf, dict) and "w" in leaf and "a" in leaf and "b" in leaf


def _quantize8_impl(w, xp) -> QuantizedLeaf:
    """Shared int8 math, parameterized on the array namespace (jnp on
    device, numpy on host) so the two paths cannot drift."""
    w32 = xp.asarray(w).astype(xp.float32)
    amax = xp.max(xp.abs(w32), axis=-2, keepdims=True)  # (..., 1, N)
    scale = xp.maximum(amax, 1e-8) / 127.0
    q = xp.clip(xp.round(w32 / scale), -127, 127).astype(xp.int8)
    return {"q": q, "s": scale.astype(xp.float32)}


def quantize_tensor(w: jnp.ndarray) -> QuantizedLeaf:
    """Quantize a (..., K, N) matmul weight per output channel (axis -1)."""
    return _quantize8_impl(w, jnp)


def dequantize_tensor(leaf: QuantizedLeaf, dtype=jnp.float32) -> jnp.ndarray:
    return (leaf["q"].astype(jnp.float32) * leaf["s"]).astype(dtype)


def _lora_branch_input(x: jnp.ndarray, w: Any) -> jnp.ndarray:
    """Adapter-branch input, with inverted dropout when the composite leaf
    carries per-layer mask state (``train/lora.py:apply_lora`` with a step
    key). peft semantics: only the A@B branch sees the dropped input."""
    if "k" not in w:
        return x
    import jax

    keep = 1.0 - w["dr"]
    mask = jax.random.bernoulli(w["k"], keep, x.shape)
    return jnp.where(mask, x / keep.astype(x.dtype), jnp.zeros((), x.dtype))


def matmul(x: jnp.ndarray, w: Any) -> jnp.ndarray:
    """x @ w for a plain or quantized weight leaf.

    For quantized leaves the int8->compute-dtype convert fuses into the dot
    (HBM reads int8); the per-channel scale applies to the f32 accumulator
    output, preserving the dense path's f32 accumulation.
    """
    if is_lora(w):
        xl = _lora_branch_input(x, w)
        delta = jnp.matmul(xl, w["a"].astype(x.dtype)) @ w["b"].astype(x.dtype)
        return matmul(x, w["w"]) + delta
    if is_quantized(w):
        y = jnp.matmul(
            x, w["q"].astype(x.dtype), preferred_element_type=jnp.float32
        )
        return (y * w["s"]).astype(x.dtype)
    return x @ w


def matmul_f32_out(x: jnp.ndarray, w: Any) -> jnp.ndarray:
    """Like ``matmul`` but returns the f32 accumulator (lm_head logits)."""
    if is_lora(w):
        xl = _lora_branch_input(x, w)
        delta = jnp.matmul(xl, w["a"].astype(x.dtype)) @ w["b"].astype(x.dtype)
        return matmul_f32_out(x, w["w"]) + delta.astype(jnp.float32)
    if is_quantized(w):
        y = jnp.matmul(
            x, w["q"].astype(x.dtype), preferred_element_type=jnp.float32
        )
        return y * w["s"]
    return jnp.matmul(x, w, preferred_element_type=jnp.float32)


def quantize_tensor_host(w) -> QuantizedLeaf:
    """Numpy-side ``quantize_tensor`` for host-resident checkpoints.

    Quantizing a 7B tree on-device would hold the bf16 tree, the growing
    int8 tree, and f32 upcast temps in HBM at once (> 20 GB on a 16 GB
    chip); on host it is just RAM. Use before device placement
    (``cli/infer.py``).
    """
    import numpy as np

    return _quantize8_impl(w, np)


def quantize_llama_params(params: Dict[str, Any],
                          host: bool = False) -> Dict[str, Any]:
    """Quantize every matmul weight of a llama param tree (embeddings and
    norms untouched). Stacked-layer leaves (L, K, N) quantize per layer and
    channel; the scan over layers slices ``q``/``s`` together.

    ``host=True`` runs the numpy path (see ``quantize_tensor_host``)."""
    qt = quantize_tensor_host if host else quantize_tensor
    out = {k: v for k, v in params.items()}
    out["lm_head"] = qt(params["lm_head"])
    layers = dict(params["layers"])
    layers["attn"] = {k: qt(v) for k, v in params["layers"]["attn"].items()}
    layers["mlp"] = {k: qt(v) for k, v in params["layers"]["mlp"].items()}
    out["layers"] = layers
    return out
