"""The hybrid decoder's plain reference: ``nemotron_h`` blocks as NVIDIA
publishes them for Nemotron-3-Super, written out from the configuration's
keys. float32, ``jax.default_matmul_precision("highest")``, no cache, no
kernel, no chunking, one request at a time. The contract is
``benchmark/reference.py``'s.

Each of the ``num_hidden_layers`` blocks is ``x <- x + mixer(rmsnorm(x))``
with the one mixer its character of ``hybrid_override_pattern`` names; a
final RMS norm (``layer_norm_epsilon``) and the head follow.

* ``M``, Mamba-2: ``[z | xBC | dt] = x W_in``; ``xBC <- silu(causal
  depthwise conv(xBC) + b)``; ``x`` (heads x head size), ``B``, ``C`` (groups
  x state; head ``h`` reads group ``h // (heads / groups)``); ``dt <-
  softplus(dt + dt_bias)``; ``A = -exp(A_log)``; **the recurrence as the
  plain sequential scan over positions**, ``h_t = exp(dt_t A) h_{t-1} + dt_t
  x_t (x) B_t``, ``y_t = h_t C_t + D x_t``; ``y <- rmsnorm over each group's
  channels (y * silu(z)) * w``; out ``y W_out``. ``time_step_min/max/floor``
  are the initialiser's and clamp nothing here.
* ``*``, attention: GQA, causal, no bias, no positional embedding (the
  published ``nemotron_h`` modelling code applies none; ``rope_theta`` is
  carried by the file and unused), no MLP in the block.
* ``E``, latent sparse experts: ``s = sigmoid(x W_r)`` over every expert of
  the deployment (the router's own width); the ``num_experts_per_tok``
  largest of ``s + e_score_correction_bias``; weights ``s_k / sum``
  (``norm_topk_prob``) times ``routed_scaling_factor``; ``u = x W_down``;
  ``E_k(u) = W2_k relu(W1_k u)^2``, **a loop over the experts held here**
  (``n_routed_experts`` of ``published.n_routed_experts``, from
  ``experts_offset``): what the absent experts would add is left out, as in
  the program; ``y = (sum_k w_k E_k(u)) W_up + shared(x)``.

The multi-token-prediction head (``num_nextn_predict_layers``) drafts and is
not on the next-token path: it is not here. The served tree's ``layers`` is
a list of blocks in the pattern's order; one block's leaves are upcast at a
time, one expert's at a time inside the loop, so the reference fits beside
the served tree.

``lower="int8"`` rounds every decoder matrix (the head and the depthwise
kernel among them, not the embedding table) to int8 with one scale an output
channel: the nearest precision below bfloat16 weights that this server has.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from benchmark.reference import _f32, _rms_norm

LOWER = ("int8",)


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


def _sizes(hf: dict) -> dict:
    held = int(hf["n_routed_experts"])
    return dict(
        heads=int(hf["num_attention_heads"]),
        kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf["head_dim"]),
        m_heads=int(hf["mamba_num_heads"]), m_head_dim=int(hf["mamba_head_dim"]),
        groups=int(hf["n_groups"]), state=int(hf["ssm_state_size"]),
        taps=int(hf["conv_kernel"]), top_k=int(hf["num_experts_per_tok"]),
        held=held, offset=int(hf.get("experts_offset", 0)),
        scaling=float(hf.get("routed_scaling_factor", 1.0)),
        norm_topk=bool(hf.get("norm_topk_prob", True)),
        eps=float(hf.get("layer_norm_epsilon", 1e-5)))


@functools.partial(jax.jit, static_argnames=(
    "m_heads", "m_head_dim", "groups", "state", "taps", "eps", "lower"))
def _mamba(layer, x, *, m_heads, m_head_dim, groups, state, taps, eps, lower):
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
        return x + (g.reshape(t, inner) * _f32(layer["gate_norm"])) \
            @ w_of(layer["out_proj"])


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "eps", "lower"))
def _attention(layer, x, *, heads, kv_heads, head_dim, eps, lower):
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
        sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(head_dim)
        causal = jnp.tril(jnp.ones((t, t), bool))
        pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
        ctx = jnp.einsum("hqk,khd->qhd", pr, v).reshape(t, heads * head_dim)
        return x + ctx @ w_of(layer["o_proj"])


@functools.partial(jax.jit, static_argnames=(
    "top_k", "held", "offset", "scaling", "norm_topk", "eps", "lower"))
def _experts(layer, x, *, top_k, held, offset, scaling, norm_topk, eps, lower):
    w_of = _weights(lower)
    relu2 = lambda v: jnp.square(jax.nn.relu(v))
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        y = _rms_norm(x, layer["norm"], eps)
        s = jax.nn.sigmoid(y @ w_of(layer["router"]))       # (T, every expert)
        _, chosen = jax.lax.top_k(
            s + _f32(layer["e_score_correction_bias"]), top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        if norm_topk:
            w = w / w.sum(-1, keepdims=True)
        w = w * scaling
        # (T, every expert): a token's weight for each expert, 0 if not chosen
        weight = jnp.zeros_like(s).at[jnp.arange(t)[:, None], chosen].set(w)
        u = y @ w_of(layer["latent_down"])

        def one(e, acc):  # expert ``offset + e`` of the deployment, held here
            up = w_of(jax.lax.dynamic_index_in_dim(
                layer["experts_up"], e, keepdims=False))
            down = w_of(jax.lax.dynamic_index_in_dim(
                layer["experts_down"], e, keepdims=False))
            we = jax.lax.dynamic_index_in_dim(weight, offset + e, axis=1,
                                              keepdims=False)
            return acc + we[:, None] * (relu2(u @ up) @ down)

        routed = jax.lax.fori_loop(0, held, one, jnp.zeros_like(u))
        shared = relu2(y @ w_of(layer["shared_up"])) @ w_of(layer["shared_down"])
        return x + routed @ w_of(layer["latent_up"]) + shared


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(decoder, x, rows, *, eps, lower):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x[rows], decoder["final_norm"], eps)
        return x @ _weights(lower)(decoder["lm_head"])


def decoder_logits(decoder, embeds, rows, hf: dict, lower: Optional[str] = None):
    """(T, D) input embeddings -> float32 logits at positions ``rows``.
    Causal throughout (the scan, the convolution, the attention), so padding
    after the last real position changes nothing before it."""
    _weights(lower)  # refuses a precision that is not in LOWER, unrun
    z = _sizes(hf)
    pattern = str(hf["hybrid_override_pattern"])[:int(hf["num_hidden_layers"])]
    if len(decoder["layers"]) != len(pattern):
        raise ValueError(f"{len(decoder['layers'])} blocks in the tree, "
                         f"{len(pattern)} in the pattern")
    x = embeds.astype(jnp.float32)
    for kind, layer in zip(pattern, decoder["layers"]):
        if kind == "M":
            x = _mamba(layer, x,
                       m_heads=z["m_heads"], m_head_dim=z["m_head_dim"],
                       groups=z["groups"], state=z["state"], taps=z["taps"],
                       eps=z["eps"], lower=lower)
        elif kind == "E":
            x = _experts(layer, x,
                         top_k=z["top_k"], held=z["held"], offset=z["offset"],
                         scaling=z["scaling"], norm_topk=z["norm_topk"],
                         eps=z["eps"], lower=lower)
        elif kind == "*":
            x = _attention(layer, x,
                           heads=z["heads"], kv_heads=z["kv_heads"],
                           head_dim=z["head_dim"], eps=z["eps"], lower=lower)
        else:
            raise ValueError(f"hybrid_override_pattern: no block {kind!r}")
    return _head({"final_norm": decoder["final_norm"],
                  "lm_head": decoder["lm_head"]}, x, rows,
                 eps=z["eps"], lower=lower)
