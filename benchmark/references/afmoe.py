"""The plain reference of the ``afmoe`` decoder (Arcee Trinity): window and
global attention layers over sparse experts, written out from the
configuration's keys. float32, ``jax.default_matmul_precision("highest")``,
no cache, no ring, no kernel, one request at a time. The contract is
``benchmark/reference.py``'s.

* Input: ``h = inputs_embeds * sqrt(hidden_size)`` where ``mup_enabled`` (the
  published model scales ``inputs_embeds``, so the spliced event positions
  are scaled with the text's: listed under the file's ``assumed``).
* A layer (``eps`` = ``rms_norm_eps``): ``h = h + norm_post_attn(attn(
  norm_in(h)))``, then ``h = h + norm_post_mlp(mlp(norm_pre_mlp(h)))``: four
  RMS norms with weights.
* ``attn(x)``: ``q = x Wq`` (heads x head size), ``k = x Wk``, ``v = x Wv``
  (kv heads; query head ``h`` reads kv head ``h // (heads / kv heads)``),
  ``g = x Wg``; ``q`` and ``k`` RMS-normed over the head's channels with a
  weight each. A layer whose entry of ``layer_types`` is
  ``sliding_attention`` rotates ``q`` and ``k`` (``rope_theta``, the
  rotate-half convention) and query ``i`` sees keys ``j`` with ``0 <= i - j
  < sliding_window``; a ``full_attention`` layer applies no positional
  embedding and sees every ``j <= i``. Output ``(softmax(q k^T / sqrt(head
  size)) v * sigmoid(g)) Wo``. **Attention is computed in blocks of
  ``Q_BLOCK`` queries against every key under the mask**, so that a prompt
  of three windows fits beside the served tree.
* ``mlp``, layers before ``num_dense_layers``: ``Wd(silu(Wg x) * Wu x)``.
* ``mlp``, the others: ``s = sigmoid(x Wr)`` over every expert of the
  deployment (the router's own width); the ``num_experts_per_tok`` largest
  of ``s + expert_bias`` (the bias chooses only); weights ``s`` at the
  chosen, divided by their sum (``route_norm``), times ``route_scale``; the
  shared expert's SwiGLU plus the weighted sum of the chosen experts'
  SwiGLUs, **a loop over the experts held here** (``num_experts`` of
  ``published.num_experts``, from ``experts_offset``): what the absent
  experts would add is left out, as in the program.
* Output: final RMS norm, the untied head over the held slice of the
  vocabulary.

The layers are the published ones the file keeps (``layers_kept``: indices
into ``layer_types``; without it the first ``num_hidden_layers``), the
first ``num_dense_layers`` of them dense. One layer's leaves are upcast at a
time, one expert's at a time inside the loop.

``lower="int8"`` rounds every decoder matrix (the head among them, not the
embedding table) to int8 with one scale an output channel: the nearest
precision below bfloat16 weights that this server has.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from benchmark.reference import _f32, _rms_norm

LOWER = ("int8",)
Q_BLOCK = 512


def decoder_of(tree):
    return tree["llama"]


def embedding_of(decoder):
    return decoder["embed_tokens"]


def _int8(w):
    """Symmetric int8 with one scale an output channel (the last axis; the
    scale is taken over the axis that is summed, the one before it)."""
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-12) / 127.0
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _weights(lower: Optional[str]):
    if lower not in (None,) + LOWER:
        raise ValueError(f"lower {lower!r}: not one of {LOWER}")
    return (lambda leaf: _int8(_f32(leaf))) if lower == "int8" else _f32


def layer_types_of(hf: dict) -> list:
    depth = int(hf["num_hidden_layers"])
    kept = hf.get("layers_kept", range(depth))
    types = [hf["layer_types"][int(i)] for i in kept]
    if len(types) != depth:
        raise ValueError(f"{len(types)} layers kept, num_hidden_layers "
                         f"{depth}")
    return types


def _sizes(hf: dict) -> dict:
    return dict(
        heads=int(hf["num_attention_heads"]),
        kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf["head_dim"]), window=int(hf["sliding_window"]),
        theta=float(hf.get("rope_theta", 10000.0)),
        top_k=int(hf["num_experts_per_tok"]), held=int(hf["num_experts"]),
        offset=int(hf.get("experts_offset", 0)),
        scale=float(hf.get("route_scale", 1.0)),
        route_norm=bool(hf.get("route_norm", True)),
        eps=float(hf.get("rms_norm_eps", 1e-5)))


def _rotate(x, theta: float):
    """x (T, heads, hd), position = index: the rotate-half convention."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    half = hd // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "window", "theta", "eps", "lower"))
def _attention(layer, x, *, heads, kv_heads, head_dim, window, theta, eps,
               lower):
    """``window``: the positions a query sees, itself included; 0 for a global
    layer (every earlier position, and no rotation)."""
    w_of = _weights(lower)
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        y = _rms_norm(x, layer["input_norm"], eps)
        q = (y @ w_of(layer["q_proj"])).reshape(t, heads, head_dim)
        k = (y @ w_of(layer["k_proj"])).reshape(t, kv_heads, head_dim)
        v = (y @ w_of(layer["v_proj"])).reshape(t, kv_heads, head_dim)
        gate = jax.nn.sigmoid(y @ w_of(layer["gate_proj"]))
        q = _rms_norm(q, layer["q_norm"], eps)
        k = _rms_norm(k, layer["k_norm"], eps)
        if window:
            q, k = _rotate(q, theta), _rotate(k, theta)
        rep = heads // kv_heads
        k = jnp.repeat(k, rep, axis=1)  # query head h reads kv head h // rep
        v = jnp.repeat(v, rep, axis=1)
        block = min(Q_BLOCK, t)
        pad = (-t) % block
        qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, block, heads, head_dim)
        j = jnp.arange(t)[None, :]

        def rows(args):
            q_blk, start = args
            i = start + jnp.arange(block)[:, None]
            see = j <= i
            if window:
                see = see & (i - j < window)
            sc = jnp.einsum("qhd,khd->hqk", q_blk, k) / math.sqrt(head_dim)
            pr = jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", pr, v)

        ctx = jax.lax.map(rows, (qs, jnp.arange(qs.shape[0]) * block))
        ctx = ctx.reshape(-1, heads * head_dim)[:t] * gate
        return x + _rms_norm(ctx @ w_of(layer["o_proj"]),
                             layer["post_attn_norm"], eps)


def _swiglu(y, w, w_of):
    return (jax.nn.silu(y @ w_of(w["gate"])) * (y @ w_of(w["up"]))) \
        @ w_of(w["down"])


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _dense_mlp(layer, x, *, eps, lower):
    w_of = _weights(lower)
    with jax.default_matmul_precision("highest"):
        y = _rms_norm(x, layer["pre_mlp_norm"], eps)
        return x + _rms_norm(_swiglu(y, layer["mlp"], w_of),
                             layer["post_mlp_norm"], eps)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "held", "offset", "scale", "route_norm", "eps", "lower"))
def _experts(layer, x, *, top_k, held, offset, scale, route_norm, eps, lower):
    w_of = _weights(lower)
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        y = _rms_norm(x, layer["pre_mlp_norm"], eps)
        s = jax.nn.sigmoid(y @ w_of(layer["router"]))       # (T, every expert)
        _, chosen = jax.lax.top_k(s + _f32(layer["expert_bias"]), top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        if route_norm:
            w = w / w.sum(-1, keepdims=True)
        w = w * scale
        # (T, every expert): a token's weight for each expert, 0 if not chosen
        weight = jnp.zeros_like(s).at[jnp.arange(t)[:, None], chosen].set(w)

        def one(e, acc):  # expert ``offset + e`` of the deployment, held here
            mine = {name: jax.lax.dynamic_index_in_dim(leaf, e, keepdims=False)
                    for name, leaf in layer["experts"].items()}
            we = jax.lax.dynamic_index_in_dim(weight, offset + e, axis=1,
                                              keepdims=False)
            return acc + we[:, None] * _swiglu(y, mine, w_of)

        routed = jax.lax.fori_loop(0, held, one, jnp.zeros_like(y))
        out = routed + _swiglu(y, layer["shared"], w_of)
        return x + _rms_norm(out, layer["post_mlp_norm"], eps)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(decoder, x, rows, *, eps, lower):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x[rows], decoder["final_norm"], eps)
        return x @ _weights(lower)(decoder["lm_head"])


def decoder_logits(decoder, embeds, rows, hf: dict, lower: Optional[str] = None):
    """(T, D) input embeddings -> float32 logits at positions ``rows``.
    Causal throughout, so padding after the last real position changes
    nothing before it."""
    _weights(lower)  # refuses a precision that is not in LOWER, unrun
    z = _sizes(hf)
    types = layer_types_of(hf)
    if len(decoder["layers"]) != len(types):
        raise ValueError(f"{len(decoder['layers'])} layers in the tree, "
                         f"{len(types)} in the file")
    x = embeds.astype(jnp.float32)
    if hf.get("mup_enabled", False):
        x = x * math.sqrt(int(hf["hidden_size"]))
    for i, (kind, layer) in enumerate(zip(types, decoder["layers"])):
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"layer_types: no layer {kind!r}")
        x = _attention(
            layer, x, heads=z["heads"], kv_heads=z["kv_heads"],
            head_dim=z["head_dim"], theta=z["theta"], eps=z["eps"],
            window=z["window"] if kind == "sliding_attention" else 0,
            lower=lower)
        if i < int(hf["num_dense_layers"]):
            x = _dense_mlp(layer, x, eps=z["eps"], lower=lower)
        else:
            x = _experts(layer, x, top_k=z["top_k"], held=z["held"],
                         offset=z["offset"], scale=z["scale"],
                         route_norm=z["route_norm"], eps=z["eps"],
                         lower=lower)
    return _head({"final_norm": decoder["final_norm"],
                  "lm_head": decoder["lm_head"]}, x, rows,
                 eps=z["eps"], lower=lower)
