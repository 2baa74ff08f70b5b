"""Hybrid decoder: Mamba-2 mixers, latent sparse experts and GQA layers.

The ``nemotron_h`` block is ``x <- x + mixer(rmsnorm(x))`` with exactly one
mixer, chosen by the block's character of ``HybridConfig.pattern``:

* ``M``, Mamba-2: ``[z | xBC | dt] = x W_in``; ``xBC <- silu(causal
  depthwise conv(xBC) + b)``, split into ``x`` (heads x head_dim) and ``B``,
  ``C`` (groups x state, one group for heads / groups heads); ``dt <-
  softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; ``h_t = exp(dt_t A)
  h_{t-1} + dt_t x_t (x) B_t``; ``y_t = h_t C_t + D x_t``; ``y <- rmsnorm
  over each group's channels (y * silu(z)) * w``; out ``y W_out``.
* ``E``, sparse experts in a latent: ``s = sigmoid(x W_r)`` in float32 over
  every expert of the deployment; the ``num_experts_per_tok`` largest of ``s
  + e_score_correction_bias``; weights ``s_k / sum`` times
  ``routed_scaling_factor``; ``u = x W_down`` into the latent; expert ``E_k(u)
  = W2_k relu(W1_k u)^2``; ``y = (sum_k w_k E_k(u)) W_up + shared(x)``,
  ``shared`` one relu^2 MLP on the full width.
* ``*``, attention: GQA, causal, no positional embedding, no MLP.

The parameters are a list of blocks in the pattern's order, each a dict of
its kind's leaves, walked by a Python loop (three bodies, eleven blocks as
served). They are **not** stacked on a leading axis as ``models/llama.py``
stacks its alike layers: a static slice of a stacked expert tensor is
copied before the grouped product reads it (0.7 GB a tensor at the served
sizes: 6.5 GB of temporaries in one prefill, by the TPU compiler's own
account). The state is stacked by kind, each block reading and writing its
kind's plane at a static index, in place. The same four entry points as
the dense decoder (``init_params``, ``init_cache``, ``prefill``,
``decode_step``; ``forward`` for tests), chosen by
``models/eventchat.decoder_of``.

**State.** One cache object holds both kinds: ``k`` / ``v`` by position for
the ``*`` layers, and for the ``M`` layers a row's fixed state: ``conv`` (the
last ``conv_kernel - 1`` inputs of the convolution) and ``h`` (float32).
Every plane has the rows on axis 1, so admission scatters them alike.
Prefill is the chunked scan at ``chunk_size`` (matrix products within a
chunk, a recurrence across chunks); a right-padded wave leaves each row's
state as it was at its own last real position (``dt = 0`` at pads, the conv
tail gathered at each row's length). Decode is one recurrence step, which
reads and writes each plane of ``h`` once, in place (``ops/ssm_step.py``:
update and readout of a block while it is in VMEM);
``decode_step(live=...)`` leaves the state of rows that are not live
untouched, since a recurrent state cannot be rolled back by ``length`` as
keys and values can.

**Precision.** The residual stream, the norms, the router, the recurrence
and every elementwise step are float32; a matrix product takes its input in
the compute type (bfloat16 as served) and accumulates in float32, so each
product rounds its input once and nothing else is rounded.

**A share of the experts.** The layer routes over all
``n_routed_experts``, computes the ``experts_held`` it holds (a prompt's
tokens sorted by expert, one grouped product: ``lax.ragged_dot``; a decode
step's few tokens against every held expert, ``DENSE_EXPERTS_UP_TO``) and
drops what the absent ones would add. ``moe_stats`` in the cache is what the last call's
expert layers counted (``STATS``), so that it leaves the device with the
caller's other outputs.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from eventgpt_tpu.config import HybridConfig
from eventgpt_tpu.models import experts as experts_mod
from eventgpt_tpu.models.llama import (
    _attn_block, _cache_write, _lm_head, embed_tokens, rms_norm,
)
from eventgpt_tpu.ops.quant import matmul as _mm, matmul_f32_out as _mm_f32
from eventgpt_tpu.ops.ssm_step import ssm_step

Params = Dict[str, Any]
Cache = Dict[str, jnp.ndarray]

# What an expert layer counts in one call (``models/experts.STATS``), stacked
# over the ``E`` layers: ``cache["moe_stats"]``, (E layers, 5) int32.
STATS = experts_mod.STATS

# Up to this many tokens (a decode step's rows) the held experts are computed
# as one batched product over every held expert, each token weighted 0 where
# it did not choose the expert; above it, as a grouped product over the
# tokens sorted by expert (``lax.ragged_dot``). Either way a step reads each
# held expert's weights once. The batched form's operations grow with the
# tokens and pass the time of that read at peak FLOP/s over bytes/s tokens
# (240 on a TPU v5e), so the threshold stays well under it; the only size
# measured is 64 rows x 22, where the grouped product (groups of 2-3 rows)
# takes 2.1 ms a product and the weights' stream 0.86 ms (PERF.md).
DENSE_EXPERTS_UP_TO = 128

# -- what ``ContinuousBatcher`` asks a decoder module ------------------------
def fixed_state(cfg: HybridConfig) -> Tuple[str, ...]:
    """The planes of a row's state that do not grow with its position (rows
    on axis 1; scattered whole at admission), beside ``k`` / ``v`` by
    position."""
    return ("conv", "h")


# The most positions one admission wave may prefill at once (rows x bucket):
# the expert layer sorts ``num_experts_per_tok`` assignments a position, and
# its buffers grow with them. ``ContinuousBatcher`` cuts a wave here.
WAVE_TOKENS = 8192
# A decoder with recurrent layers keeps, beside keys and values by position,
# a state a row that cannot be sliced at a position, rolled back by
# ``length`` or shared between rows. The mechanisms below move, share or
# roll back keys and values only, so each refuses such a decoder rather
# than serve a stale state (ROADMAP.md, Queue 2).
REFUSED_AS = "a decoder with recurrent state"
REFUSES = {
    "--kv_cache int8": "the int8 cache holds keys and values only",
    "--kv_layout paged": "a block holds keys and values by position only",
    "--speculative": "a rejected draft cannot be rolled back out of a "
                     "recurrent state",
    "--spec_buckets": "a rejected draft cannot be rolled back out of a "
                      "recurrent state",
    "--draft_head": "speculation is refused",
    "--prefill_chunk": "chunked admission prefills through decode_kstep, "
                       "which carries no recurrent state",
    "--prefill_budget": "piggyback lanes prefill through decode_kstep, "
                        "which carries no recurrent state (pass "
                        "--prefill_budget 0)",
    "--prefix_cache_mb": "a prefix entry holds keys and values and no "
                         "snapshot of the recurrent state at its end "
                         "(pass --no_prefix_cache)",
    "--preempt": "a spill record holds block runs only",
    "--role": "a handoff record holds block runs only",
    "--mesh_model": "the decoder runs on one device (no expert axis in "
                    "parallel/mesh.py; --mesh_data and --mesh_fsdp "
                    "likewise)",
    "--quant": "ops/quant is two-dimensional and does not take stacked "
               "experts",
    "--fuse_params": "there is no q|k|v or gate|up to fuse",
}


def span_counts(cfg: HybridConfig, lengths) -> Dict[str, int]:
    """Nothing of this decoder's own on a dispatch span (the expert layers'
    counts leave the device with the segment)."""
    return {}


# -- parameters ---------------------------------------------------------------

def init_params(cfg: HybridConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    d = cfg.hidden_size
    inner, ch = cfg.mamba_inner, cfg.conv_channels
    heads, hd = cfg.mamba_num_heads, cfg.resolved_head_dim()
    qd, kvd = cfg.num_heads * hd, cfg.num_kv_heads * hd
    lat, f, fs = (cfg.moe_latent_size, cfg.moe_intermediate_size,
                  cfg.moe_shared_expert_intermediate_size)
    keys = iter(jax.random.split(key, 16 * cfg.num_layers + 4))
    residual = 1.0 / math.sqrt(2 * cfg.num_layers)

    def dense(fan_in, shape, gain=1.0):
        return (jax.random.normal(next(keys), shape, dtype)
                * (gain / math.sqrt(fan_in)))

    def uniform(shape, low, high):
        return jax.random.uniform(next(keys), shape, jnp.float32, low, high)

    def block(kind: str) -> Params:
        if kind == "M":
            # dt_bias: the inverse softplus of a step drawn log-uniformly
            # from time_step_min .. time_step_max (0.001 .. 0.1), the
            # published initialiser; A_log: the logarithm of A in 1 .. 16.
            step = jnp.exp(uniform((heads,), math.log(1e-3), math.log(1e-1)))
            return {
                "norm": jnp.ones((d,), dtype),
                "in_proj": dense(d, (d, inner + ch + heads)),
                "conv_w": dense(cfg.conv_kernel, (ch, cfg.conv_kernel)),
                "conv_b": jnp.zeros((ch,), dtype),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(uniform((heads,), 1.0, 16.0)),
                "D": jnp.ones((heads,), jnp.float32),
                "gate_norm": jnp.ones((inner,), dtype),
                "out_proj": dense(inner, (inner, d), residual),
            }
        if kind == "E":
            return {
                "norm": jnp.ones((d,), dtype),
                "router": dense(d, (d, cfg.n_routed_experts)),
                "e_score_correction_bias": jnp.zeros(
                    (cfg.n_routed_experts,), jnp.float32),
                "latent_down": dense(d, (d, lat)),
                "experts_up": dense(lat, (cfg.experts_held, lat, f)),
                "experts_down": dense(f, (cfg.experts_held, f, lat)),
                "latent_up": dense(lat, (lat, d), residual),
                "shared_up": dense(d, (d, fs)),
                "shared_down": dense(fs, (fs, d), residual),
            }
        return {
            "norm": jnp.ones((d,), dtype),
            "q_proj": dense(d, (d, qd)),
            "k_proj": dense(d, (d, kvd)),
            "v_proj": dense(d, (d, kvd)),
            "o_proj": dense(qd, (qd, d), residual),
        }

    return {
        "embed_tokens": jax.random.normal(next(keys), (cfg.vocab_size, d),
                                          dtype) * 0.02,
        "layers": [block(kind) for kind in cfg.pattern],
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense(d, (d, cfg.vocab_size)),
    }


def init_cache(cfg: HybridConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, quant: bool = False) -> Cache:
    """Keys and values by position for the ``*`` layers; ``conv`` and ``h``
    (float32) a row for the ``M`` layers; what the expert layers last
    counted. Rows are axis 1 of every plane."""
    if quant:
        raise ValueError("the int8 cache holds keys and values only; the "
                         "hybrid decoder's recurrent state has no int8 form")
    n_m, n_e, n_a = cfg.count("M"), cfg.count("E"), cfg.count("*")
    kv = (n_a, batch, max_len, cfg.num_kv_heads, cfg.resolved_head_dim())
    return {
        "k": jnp.zeros(kv, dtype),
        "v": jnp.zeros(kv, dtype),
        "conv": jnp.zeros((n_m, batch, cfg.conv_kernel - 1,
                           cfg.conv_channels), dtype),
        "h": jnp.zeros((n_m, batch, cfg.mamba_num_heads, cfg.mamba_head_dim,
                        cfg.ssm_state_size), jnp.float32),
        "moe_stats": jnp.zeros((n_e, len(STATS)), jnp.int32),
        "length": jnp.zeros((batch,), jnp.int32),
    }


# -- M: the Mamba-2 mixer -------------------------------------------------------

def _split_in_proj(cfg: HybridConfig, zxbcdt):
    inner, ch = cfg.mamba_inner, cfg.conv_channels
    return (zxbcdt[..., :inner], zxbcdt[..., inner:inner + ch],
            zxbcdt[..., inner + ch:])


def _split_xbc(cfg: HybridConfig, xbc):
    """(..., channels) -> x (..., heads, head_dim), B, C (..., groups, state)."""
    inner, gn = cfg.mamba_inner, cfg.n_groups * cfg.ssm_state_size
    lead = xbc.shape[:-1]
    x = xbc[..., :inner].reshape(lead + (cfg.mamba_num_heads,
                                         cfg.mamba_head_dim))
    b = xbc[..., inner:inner + gn].reshape(lead + (cfg.n_groups,
                                                   cfg.ssm_state_size))
    c = xbc[..., inner + gn:].reshape(lead + (cfg.n_groups,
                                              cfg.ssm_state_size))
    return x, b, c


def _gated_norm(cfg: HybridConfig, y, z, weight):
    """rmsnorm over each group's channels of ``y * silu(z)``, times ``w``;
    float32."""
    lead = y.shape[:-1]
    g = (y * jax.nn.silu(z)).reshape(lead + (cfg.n_groups, -1))
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                      + cfg.rms_norm_eps)
    return g.reshape(lead + (-1,)) * weight.astype(jnp.float32)


def _ssm_scan(cfg: HybridConfig, x, dt, a_head, b, c, h0):
    """The chunked scan, float32. x (B, T, H, P); dt (B, T, H), 0 at pads;
    a_head (H,) = -exp(A_log); b, c (B, T, G, N); h0 (B, H, P, N). Returns y
    (B, T, H, P) without the ``D x`` skip, and the state after position T.
    Within a chunk of Q positions the outputs are matrix products (the
    decays ``exp(cs_i - cs_j)`` of the chunk's cumulated ``dt A`` mask a
    Q x Q product of C and B); across chunks the state is carried by a
    ``lax.scan``. The products are small beside the projections (a few
    per cent of a layer's operations), so they run at full float32
    precision."""
    bsz, t, heads, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = heads // g
    q = cfg.chunk_size
    pad = (-t) % q
    if pad:  # dt = 0: the state passes through, the outputs are dropped
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // q

    def chunks(v):  # (B, nc * Q, ...) -> (nc, B, Q, ...)
        return jnp.moveaxis(v.reshape((bsz, nc, q) + v.shape[2:]), 1, 0)

    lower = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]

    def chunk(h, xs):
        x_c, dt_c, b_c, c_c = xs
        cs = jnp.cumsum(dt_c * a_head, axis=1)               # (B, Q, H) <= 0
        xdt = (x_c * dt_c[..., None]).reshape(bsz, q, g, r, p)
        # Within the chunk: y_i = sum_{j <= i} (C_i . B_j) exp(cs_i - cs_j) dt_j x_j
        cb = jnp.einsum("bign,bjgn->bgij", c_c, b_c)
        seg = cs[:, :, None, :] - cs[:, None, :, :]           # (B, i, j, H)
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
        m = cb[:, :, None] * jnp.moveaxis(decay, 3, 1).reshape(
            bsz, g, r, q, q)                                  # (B, G, R, i, j)
        y = jnp.einsum("bgrij,bjgrp->bigrp", m, xdt)
        # The state carried in, decayed to position i.
        hg = h.reshape(bsz, g, r, p, n)
        y = y + jnp.einsum("bign,bgrpn->bigrp", c_c, hg) \
            * jnp.exp(cs).reshape(bsz, q, g, r)[..., None]
        # The state carried out: decayed over the chunk, plus each position's
        # input decayed to the chunk's end.
        to_end = jnp.exp(cs[:, -1:, :] - cs).reshape(bsz, q, g, r)
        h_new = hg * jnp.exp(cs[:, -1]).reshape(bsz, g, r)[..., None, None] \
            + jnp.einsum("bjgrp,bjgn->bgrpn", xdt * to_end[..., None], b_c)
        return h_new.reshape(bsz, heads, p, n), y.reshape(bsz, q, heads, p)

    with jax.default_matmul_precision("highest"):
        h, y = lax.scan(chunk, h0,
                        (chunks(x), chunks(dt), chunks(b), chunks(c)))
    return jnp.moveaxis(y, 0, 1).reshape(bsz, nc * q, heads, p)[:, :t], h


def _mamba_prefill(cfg: HybridConfig, layer: Params, x_in, mask, lengths):
    """x_in (B, T, D), right-padded by ``mask`` -> (out (B, T, D) float32,
    conv tail (B, K-1, C) and h (B, H, P, N) as they are after each row's
    last real position)."""
    bsz, t, _ = x_in.shape
    k = cfg.conv_kernel
    z, xbc, dt = _split_in_proj(cfg, _mm_f32(x_in, layer["in_proj"]))
    with jax.named_scope("ssm_scan"):
        # Causal depthwise convolution: tap j multiplies the input K-1-j back.
        xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        taps = layer["conv_w"].astype(jnp.float32)
        conv = sum(xp[:, j:j + t] * taps[:, j] for j in range(k))
        # The tail a decode step continues from: the K-1 inputs that end at
        # each row's own length (zeros before the first position).
        tail = jax.vmap(lambda row, n: lax.dynamic_slice_in_dim(
            row, n, k - 1, axis=0))(xp, lengths)
        xbc = jax.nn.silu(conv + layer["conv_b"].astype(jnp.float32))
        x, b, c = _split_xbc(cfg, xbc)
        dt = jax.nn.softplus(dt + layer["dt_bias"])
        dt = jnp.where(mask[..., None], dt, 0.0)
        h0 = jnp.zeros((bsz, cfg.mamba_num_heads, cfg.mamba_head_dim,
                        cfg.ssm_state_size), jnp.float32)
        y, h = _ssm_scan(cfg, x, dt, -jnp.exp(layer["A_log"]), b, c, h0)
        y = y + x * layer["D"][:, None]
        y = _gated_norm(cfg, y.reshape(bsz, t, -1), z, layer["gate_norm"])
    return _mm_f32(y.astype(x_in.dtype), layer["out_proj"]), tail, h


def _mamba_step(cfg: HybridConfig, layer: Params, x_in, tail, h_buf, i: int,
                live):
    """One position a row. x_in (B, D); tail (B, K-1, C); h_buf (planes, B,
    H, P, N) float32, of which this layer steps plane ``i`` in place;
    ``live`` (B,) bool or None. Rows that are not live keep tail and h."""
    bsz = x_in.shape[0]
    z, xbc, dt = _split_in_proj(cfg, _mm_f32(x_in, layer["in_proj"]))
    with jax.named_scope("ssm_step"):
        window = jnp.concatenate([tail.astype(jnp.float32), xbc[:, None]],
                                 axis=1)                          # (B, K, C)
        conv = jnp.sum(window * layer["conv_w"].astype(jnp.float32).T, axis=1)
        new_tail = window[:, 1:].astype(tail.dtype)
        x, b, c = _split_xbc(cfg, jax.nn.silu(
            conv + layer["conv_b"].astype(jnp.float32)))
        dt = jax.nn.softplus(dt + layer["dt_bias"])
        if live is not None:
            # dt = 0: decay = 1 and xdt = 0, so the row's h stays as it is
            dt = jnp.where(live[:, None], dt, 0.0)
            new_tail = jnp.where(live[:, None, None], new_tail, tail)
        decay = jnp.exp(dt * -jnp.exp(layer["A_log"]))          # (B, H)
        h_buf, y = ssm_step(h_buf, i, decay, x * dt[..., None], b, c)
        y = y + x * layer["D"][:, None]
        y = _gated_norm(cfg, y.reshape(bsz, -1), z, layer["gate_norm"])
    return (_mm_f32(y.astype(x_in.dtype), layer["out_proj"]), new_tail,
            h_buf)


# -- E: sparse experts in a latent, and the shared expert ----------------------

def _routing(cfg: HybridConfig) -> experts_mod.Routing:
    return experts_mod.Routing(
        top_k=cfg.num_experts_per_tok, held=cfg.experts_held,
        offset=cfg.experts_offset, normalise=cfg.norm_topk_prob,
        scale=cfg.routed_scaling_factor)


def _moe_block(cfg: HybridConfig, layer: Params, y, counted, dtype):
    """y (T, D) float32, normed; ``counted`` (T,) bool: the tokens that are
    real or live; ``dtype``: the compute type of the products. Returns (the
    layer's output (T, D) float32, its ``STATS`` (5,) int32):
    ``models/experts.sparse_experts`` with relu^2 experts inside the latent
    projections."""
    return experts_mod.sparse_experts(
        _routing(cfg), y, counted, dtype,
        router=layer["router"], bias=layer["e_score_correction_bias"],
        experts={"up": layer["experts_up"], "down": layer["experts_down"]},
        shared={"up": layer["shared_up"], "down": layer["shared_down"]},
        latent=(layer["latent_down"], layer["latent_up"]),
        dense_up_to=DENSE_EXPERTS_UP_TO)


# -- the four entry points -----------------------------------------------------

def _no_rotation(b: int, t: int, hd: int):
    """cos = 1, sin = 0: ``_attn_block`` rotates by nothing (the published
    ``nemotron_h`` attention has no positional embedding)."""
    return jnp.ones((b, t, hd), jnp.float32), jnp.zeros((b, t, hd), jnp.float32)


def _project_qkv(cfg: HybridConfig, y, layer: Params):
    b, t, _ = y.shape
    hd = cfg.resolved_head_dim()
    return (_mm(y, layer["q_proj"]),
            _mm(y, layer["k_proj"]).reshape(b, t, cfg.num_kv_heads, hd),
            _mm(y, layer["v_proj"]).reshape(b, t, cfg.num_kv_heads, hd))


def prefill(
    params: Params,
    cfg: HybridConfig,
    inputs_embeds: jnp.ndarray,
    attention_mask: jnp.ndarray,
    cache: Cache,
    last_only: bool = False,
    mesh=None,
    return_hidden: bool = False,
) -> Tuple[jnp.ndarray, Cache]:
    """The whole prompt, as ``llama.prefill``: (logits, filled cache);
    ``attention_mask`` True at real positions, right-padded. The prompt's
    keys and values occupy slots [0, T); each row's ``conv`` and ``h`` are
    its state after its own last real position."""
    if mesh is not None:
        raise ValueError("the hybrid decoder runs on one device: no mesh")
    b, t, _ = inputs_embeds.shape
    hd = cfg.resolved_head_dim()
    lengths = attention_mask.astype(jnp.int32).sum(axis=1)
    cos, sin = _no_rotation(b, t, hd)
    use_flash = cfg.attn_impl == "flash"
    mask = None
    if not use_flash:
        causal = jnp.tril(jnp.ones((t, t), bool))
        visible = causal[None, None] & attention_mask[:, None, None, :]
        mask = jnp.where(visible, 0.0, jnp.finfo(jnp.float32).min)

    dtype = inputs_embeds.dtype
    x = inputs_embeds.astype(jnp.float32)
    k_buf, v_buf, conv_buf, h_buf = (cache["k"], cache["v"], cache["conv"],
                                     cache["h"])
    stats = []
    seen = {"M": 0, "E": 0, "*": 0}
    for kind, layer in zip(cfg.pattern, params["layers"]):
        i = seen[kind]  # the block's plane of its kind's state
        seen[kind] += 1
        if kind == "M":
            y = rms_norm(x, layer["norm"], cfg.rms_norm_eps).astype(dtype)
            out, tail, h = _mamba_prefill(cfg, layer, y, attention_mask,
                                          lengths)
            conv_buf = conv_buf.at[i].set(tail.astype(conv_buf.dtype))
            h_buf = h_buf.at[i].set(h)
        elif kind == "E":
            y = rms_norm(x, layer["norm"], cfg.rms_norm_eps)
            out, st = _moe_block(cfg, layer, y.reshape(b * t, -1),
                                 attention_mask.reshape(b * t), dtype)
            out = out.reshape(b, t, -1)
            stats.append(st)
        else:
            y = rms_norm(x, layer["norm"], cfg.rms_norm_eps).astype(dtype)
            q_proj, k, v = _project_qkv(cfg, y, layer)
            out = _attn_block(cfg, q_proj, {"attn": {"o": layer["o_proj"]}}, cos, sin, k, v,
                              mask=mask, valid=attention_mask,
                              use_flash=use_flash, scope="prefill_attn")
            k_buf = k_buf.at[i, :, :t].set(k.astype(k_buf.dtype))
            v_buf = v_buf.at[i, :, :t].set(v.astype(v_buf.dtype))
        x = x + out

    new_cache = {"k": k_buf, "v": v_buf, "conv": conv_buf, "h": h_buf,
                 "moe_stats": (jnp.stack(stats) if stats
                               else cache["moe_stats"]),
                 "length": lengths}
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps).astype(dtype)
    if last_only:
        last = jnp.take_along_axis(
            x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
        if return_hidden:
            return _lm_head(params, last), last, new_cache
        return _lm_head(params, last), new_cache
    logits = _lm_head(params, x)
    if return_hidden:
        return logits, x, new_cache
    return logits, new_cache


def decode_step(
    params: Params,
    cfg: HybridConfig,
    token_embeds: jnp.ndarray,
    cache: Cache,
    live: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Cache]:
    """One token a row, as ``llama.decode_step``: token_embeds (B, 1, D) ->
    (logits (B, V), cache with ``length + 1``). ``live`` (B,) bool: rows
    that are not live keep their recurrent state (the caller rolls their
    ``length`` back, which is all that keys and values need) and are left
    out of the expert layers' counts."""
    b = token_embeds.shape[0]
    hd = cfg.resolved_head_dim()
    max_len = cache["k"].shape[2]
    pos = cache["length"]
    cos, sin = _no_rotation(b, 1, hd)
    valid = jnp.arange(max_len)[None, :] <= pos[:, None]
    mask = jnp.where(valid[:, None, None, :], 0.0, jnp.finfo(jnp.float32).min)
    batch_idx = jnp.arange(b)
    counted = live if live is not None else jnp.ones((b,), bool)

    dtype = token_embeds.dtype
    x = token_embeds.astype(jnp.float32)
    k_buf, v_buf, conv_buf, h_buf = (cache["k"], cache["v"], cache["conv"],
                                     cache["h"])
    stats = []
    seen = {"M": 0, "E": 0, "*": 0}
    for kind, layer in zip(cfg.pattern, params["layers"]):
        i = seen[kind]  # the block's plane of its kind's state
        seen[kind] += 1
        if kind == "M":
            y = rms_norm(x, layer["norm"], cfg.rms_norm_eps).astype(dtype)
            out, tail, h_buf = _mamba_step(cfg, layer, y[:, 0], conv_buf[i],
                                           h_buf, i, live)
            conv_buf = conv_buf.at[i].set(tail)
            out = out[:, None]
        elif kind == "E":
            y = rms_norm(x, layer["norm"], cfg.rms_norm_eps)
            out, st = _moe_block(cfg, layer, y[:, 0], counted, dtype)
            out = out[:, None]
            stats.append(st)
        else:
            y = rms_norm(x, layer["norm"], cfg.rms_norm_eps).astype(dtype)
            q_proj, k_new, v_new = _project_qkv(cfg, y, layer)
            k_buf = _cache_write(k_buf, i, batch_idx, pos, k_new[:, 0], False)
            v_buf = _cache_write(v_buf, i, batch_idx, pos, v_new[:, 0], False)
            out = _attn_block(cfg, q_proj, {"attn": {"o": layer["o_proj"]}}, cos, sin,
                              k_buf[i].astype(dtype), v_buf[i].astype(dtype),
                              mask, scope="decode_attn")
        x = x + out

    new_cache = {"k": k_buf, "v": v_buf, "conv": conv_buf, "h": h_buf,
                 "moe_stats": (jnp.stack(stats) if stats
                               else cache["moe_stats"]),
                 "length": cache["length"] + 1}
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps).astype(dtype)
    return _lm_head(params, x[:, 0]), new_cache


def forward(
    params: Params,
    cfg: HybridConfig,
    inputs_embeds: jnp.ndarray,
    attention_mask: Optional[jnp.ndarray] = None,
    mesh=None,
) -> jnp.ndarray:
    """Cache-free full forward -> logits (B, T, V), for tests."""
    b, t, _ = inputs_embeds.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, t), bool)
    cache = init_cache(cfg, b, t, dtype=inputs_embeds.dtype)
    logits, _ = prefill(params, cfg, inputs_embeds, attention_mask, cache,
                        mesh=mesh)
    return logits
