"""The Granite-4.0-H decoder's plain reference: ``granitemoehybrid`` layers as
IBM publishes them, written out from the configuration's keys. float32,
``jax.default_matmul_precision("highest")``, no cache, no kernel, no
chunking, one request at a time. The contract is ``benchmark/reference.py``'s.

The input embeddings (the spliced sequence: token embeddings and projected
event features) are multiplied by ``embedding_multiplier``. Each of the first
``num_hidden_layers`` entries of ``layer_types`` is one layer of two
residual steps, each scaled by ``residual_multiplier``:

    x <- h + residual_multiplier * mixer(rmsnorm(h))
    h <- x + residual_multiplier * (moe(rmsnorm(x)) + shared(rmsnorm(x)))

then a final RMS norm (``rms_norm_eps`` everywhere) and the head.

* ``mamba``, Mamba-2: ``[z | xBC | dt] = x W_in`` (no bias); ``xBC <-
  silu(causal depthwise conv(xBC) + b)``; ``x`` (``mamba_n_heads`` x
  ``mamba_d_head``), ``B``, ``C`` (``mamba_n_groups`` x ``mamba_d_state``;
  head ``h`` reads group ``h // (heads / groups)``); ``dt <- softplus(dt +
  dt_bias)``; ``A = -exp(A_log)``; **the recurrence as the plain sequential
  scan over positions**, ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = h_t C_t + D x_t``; ``y <- rmsnorm over each group's channels (y *
  silu(z)) * w``; out ``y W_out``.
* ``attention``: GQA, causal, no bias, no positional embedding
  (``position_embedding_type`` ``nope``); scores ``q . k`` times
  ``attention_multiplier`` (not ``1 / sqrt(head_dim)``).
* ``moe``: router logits ``x W_r`` over every expert of the deployment
  (``published.num_local_experts``); the ``num_experts_per_tok`` largest, and
  a softmax over those; expert ``k`` is ``W_out,k (silu(a) * b)`` with ``[a |
  b] = x W_in,k`` (``intermediate_size`` each; stored as the leaves ``gate``
  and ``up``), **a loop over the experts held here** (``num_local_experts``
  from ``experts_offset``): what the absent experts would add is left out,
  as in the program.
* ``shared``: the same gated form at ``shared_intermediate_size``.
* Head: ``rmsnorm(h) E^T / logits_scaling``, ``E`` the embedding table
  (``tie_word_embeddings``). The served tree holds the table once, as the
  head's leaf ``lm_head`` (hidden x vocab): a token's embedding is its
  column.

The served tree's ``layers`` is a list of blocks, a mixer block and then an
expert block for each layer; one block's leaves are upcast at a time, one
expert's at a time inside the loop, so the reference fits beside the served
tree. It imports nothing of the program and no other reference module.

``lower="int8"`` rounds every decoder matrix to int8 with one scale an output
channel (the head's product over the embedding table among them, the depthwise
kernel too; the embedding lookup is not rounded): the nearest precision below
bfloat16 weights that this server has.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from benchmark.reference import _f32, _rms_norm

LOWER = ("int8",)


def decoder_of(tree):
    return tree["llama"]


def embedding_of(decoder):
    return decoder["lm_head"].T


def _int8(w):
    """Symmetric int8 with one scale an output channel (the last axis; the
    scale is taken over the axis that is summed, the one before it)."""
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-12) / 127.0
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _weights(lower: Optional[str]):
    if lower not in (None,) + LOWER:
        raise ValueError(f"lower {lower!r}: not one of {LOWER}")
    return (lambda leaf: _int8(_f32(leaf))) if lower == "int8" else _f32


def _sizes(hf: dict) -> dict:
    heads = int(hf["num_attention_heads"])
    return dict(
        heads=heads, kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf.get("head_dim") or hf["hidden_size"] // heads),
        m_heads=int(hf["mamba_n_heads"]), m_head_dim=int(hf["mamba_d_head"]),
        groups=int(hf["mamba_n_groups"]), state=int(hf["mamba_d_state"]),
        taps=int(hf["mamba_d_conv"]), top_k=int(hf["num_experts_per_tok"]),
        held=int(hf["num_local_experts"]),
        offset=int(hf.get("experts_offset", 0)),
        residual=float(hf["residual_multiplier"]),
        scores=float(hf["attention_multiplier"]),
        eps=float(hf["rms_norm_eps"]))


@functools.partial(jax.jit, static_argnames=(
    "m_heads", "m_head_dim", "groups", "state", "taps", "residual", "eps",
    "lower"))
def _mamba(layer, x, *, m_heads, m_head_dim, groups, state, taps, residual,
           eps, lower):
    w_of = _weights(lower)
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        inner, gn = m_heads * m_head_dim, groups * state
        y = _rms_norm(x, layer["norm"], eps)
        zxbcdt = y @ w_of(layer["in_proj"])
        z = zxbcdt[:, :inner]
        xbc = zxbcdt[:, inner:2 * inner + 2 * gn]
        dt = zxbcdt[:, 2 * inner + 2 * gn:]
        # causal depthwise convolution: the last tap multiplies the position
        # itself; the taps are a matrix (channels, taps), scaled a channel
        conv_w = w_of(layer["conv_w"].T).T
        xp = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
        conv = sum(xp[j:j + t] * conv_w[:, j] for j in range(taps))
        xbc = jax.nn.silu(conv + _f32(layer["conv_b"]))
        xs = xbc[:, :inner].reshape(t, m_heads, m_head_dim)
        per = m_heads // groups
        b = jnp.repeat(xbc[:, inner:inner + gn].reshape(t, groups, state),
                       per, axis=1)                       # (T, heads, state)
        c = jnp.repeat(xbc[:, inner + gn:].reshape(t, groups, state),
                       per, axis=1)
        dt = jax.nn.softplus(dt + _f32(layer["dt_bias"]))  # (T, heads)
        a = -jnp.exp(_f32(layer["A_log"]))                  # (heads,)

        def step(h, at):
            x_t, b_t, c_t, dt_t = at
            h = h * jnp.exp(dt_t * a)[:, None, None] \
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            return h, jnp.einsum("hpn,hn->hp", h, c_t)

        h0 = jnp.zeros((m_heads, m_head_dim, state), jnp.float32)
        _, ys = jax.lax.scan(step, h0, (xs, b, c, dt))
        ys = ys + xs * _f32(layer["D"])[:, None]
        g = (ys.reshape(t, inner) * jax.nn.silu(z)).reshape(t, groups, -1)
        g = g / jnp.sqrt((g * g).mean(-1, keepdims=True) + eps)
        out = (g.reshape(t, inner) * _f32(layer["gate_norm"])) \
            @ w_of(layer["out_proj"])
        return x + residual * out


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "scores", "residual", "eps", "lower"))
def _attention(layer, x, *, heads, kv_heads, head_dim, scores, residual, eps,
               lower):
    w_of = _weights(lower)
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        y = _rms_norm(x, layer["norm"], eps)
        q = (y @ w_of(layer["q_proj"])).reshape(t, heads, head_dim)
        k = (y @ w_of(layer["k_proj"])).reshape(t, kv_heads, head_dim)
        v = (y @ w_of(layer["v_proj"])).reshape(t, kv_heads, head_dim)
        rep = heads // kv_heads
        k = jnp.repeat(k, rep, axis=1)  # query head h reads kv head h // rep
        v = jnp.repeat(v, rep, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) * scores
        causal = jnp.tril(jnp.ones((t, t), bool))
        pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
        ctx = jnp.einsum("hqk,khd->qhd", pr, v).reshape(t, heads * head_dim)
        return x + residual * (ctx @ w_of(layer["o_proj"]))


@functools.partial(jax.jit, static_argnames=(
    "top_k", "held", "offset", "residual", "eps", "lower"))
def _experts(layer, x, *, top_k, held, offset, residual, eps, lower):
    w_of = _weights(lower)
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        y = _rms_norm(x, layer["norm"], eps)
        logits = y @ w_of(layer["router"])                   # (T, every expert)
        top, chosen = jax.lax.top_k(logits, top_k)
        w = jax.nn.softmax(top, -1)
        # (T, every expert): a token's weight for each expert, 0 if not chosen
        weight = jnp.zeros_like(logits).at[
            jnp.arange(t)[:, None], chosen].set(w)

        def expert(e, name):
            return w_of(jax.lax.dynamic_index_in_dim(
                layer[f"experts_{name}"], e, keepdims=False))

        def one(e, acc):  # expert ``offset + e`` of the deployment, held here
            we = jax.lax.dynamic_index_in_dim(weight, offset + e, axis=1,
                                              keepdims=False)
            a = jax.nn.silu(y @ expert(e, "gate")) * (y @ expert(e, "up"))
            return acc + we[:, None] * (a @ expert(e, "down"))

        routed = jax.lax.fori_loop(0, held, one, jnp.zeros_like(y))
        shared = (jax.nn.silu(y @ w_of(layer["shared_gate"]))
                  * (y @ w_of(layer["shared_up"]))) @ w_of(layer["shared_down"])
        return x + residual * (routed + shared)


@functools.partial(jax.jit, static_argnames=("scaling", "eps", "lower"))
def _head(decoder, x, rows, *, scaling, eps, lower):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x[rows], decoder["final_norm"], eps)
        return (x @ _weights(lower)(decoder["lm_head"])) / scaling


def decoder_logits(decoder, embeds, rows, hf: dict, lower: Optional[str] = None):
    """(T, D) input embeddings -> float32 logits at positions ``rows``.
    Causal throughout (the scan, the convolution, the attention), so padding
    after the last real position changes nothing before it."""
    _weights(lower)  # refuses a precision that is not in LOWER, unrun
    z = _sizes(hf)
    types = list(hf["layer_types"])[:int(hf["num_hidden_layers"])]
    if len(decoder["layers"]) != 2 * len(types):
        raise ValueError(f"{len(decoder['layers'])} blocks in the tree, "
                         f"{2 * len(types)} for {len(types)} layers")
    x = embeds.astype(jnp.float32) * float(hf["embedding_multiplier"])
    for i, kind in enumerate(types):
        mixer, moe = decoder["layers"][2 * i], decoder["layers"][2 * i + 1]
        if kind == "mamba":
            x = _mamba(mixer, x,
                       m_heads=z["m_heads"], m_head_dim=z["m_head_dim"],
                       groups=z["groups"], state=z["state"], taps=z["taps"],
                       residual=z["residual"], eps=z["eps"], lower=lower)
        elif kind == "attention":
            x = _attention(mixer, x,
                           heads=z["heads"], kv_heads=z["kv_heads"],
                           head_dim=z["head_dim"], scores=z["scores"],
                           residual=z["residual"], eps=z["eps"], lower=lower)
        else:
            raise ValueError(f"layer_types: no layer {kind!r}")
        x = _experts(moe, x, top_k=z["top_k"], held=z["held"],
                     offset=z["offset"], residual=z["residual"],
                     eps=z["eps"], lower=lower)
    if not hf.get("tie_word_embeddings"):
        raise ValueError("granitemoehybrid reference: the head is tied to "
                         "the embedding table (tie_word_embeddings)")
    return _head({"final_norm": decoder["final_norm"],
                  "lm_head": decoder["lm_head"]}, x, rows,
                 scaling=float(hf["logits_scaling"]), eps=z["eps"],
                 lower=lower)
