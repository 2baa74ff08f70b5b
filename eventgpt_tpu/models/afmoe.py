"""Window and global attention layers over sparse experts (``afmoe``).

The published Arcee Trinity block, from its ``config.json``:

* input: ``h = inputs_embeds * sqrt(hidden_size)`` (``mup_enabled``; the
  spliced event positions are scaled with the text's);
* a layer: ``h = h + norm_post_attn(attn(norm_in(h)))``, then ``h = h +
  norm_post_mlp(mlp(norm_pre_mlp(h)))``: four RMS norms with weights;
* ``attn(x)``: ``q = x Wq``, ``k = x Wk``, ``v = x Wv``, ``g = x Wg``; ``q``
  and ``k`` RMS-normed over the head's channels with a weight each; a
  **window layer** (``layer_types[i] == "sliding_attention"``) rotates ``q``
  and ``k`` (``rope_theta``) and query ``i`` sees keys ``j`` with ``0 <= i -
  j < sliding_window``; a **global layer** applies no positional embedding
  and sees every ``j <= i``; output ``(softmax(q k^T / sqrt(hd)) v *
  sigmoid(g)) Wo``;
* ``mlp``, the first ``num_dense_layers`` layers: ``Wd(silu(Wg x) * Wu x)``;
  the others: sigmoid-routed SwiGLU experts over the experts held here,
  beside one shared expert (``models/experts.py``, the layer the hybrid
  decoder calls too);
* output: final RMS norm, untied head.

The parameters are a list of layers, each a dict of its leaves, walked by a
Python loop as ``models/nemotron_h.py`` walks its blocks (a static slice of
a stacked expert tensor is copied before the grouped product reads it). The
same four entry points as the other decoders (``init_params``,
``init_cache``, ``prefill``, ``decode_step``; ``forward`` for tests), chosen
by ``models/eventchat.decoder_of``.

**State: two kinds of keys and values in one cache.** A global layer keeps a
plane of ``max_len`` positions (``k`` / ``v``: (global layers, B, max_len,
KV, hd)), addressed by position and rolled back by ``length`` as the dense
decoder's. A window layer keeps a **ring** of ``sliding_window`` slots
(``k_ring<i>`` / ``v_ring<i>`` for the ``i``-th window layer: (1, B,
sliding_window, KV, hd)), position ``p`` at slot ``p % sliding_window``: a
row's fixed state, whatever its length. Each ring is an array of its own:
stacked on a leading axis, a layer's ring was copied whole before every
decode step's attention read it (a static slice of a stacked buffer is a
copy: 8 copies of 268 MB a step as served, 41 % of the decode program's
device time; my chip run, PR 33). Keys are rotated before they are written, so the order of the
slots does not matter to the scores. Prefill of a prompt longer than the
window leaves each row's last ``sliding_window`` real positions in its ring
(a right-padded wave: gathered at each row's own length); a decode step
writes one slot and reads the ring whole: once a row is past the window
every slot is live, before that the slots above ``length`` are masked. The
slot a step overwrites held position ``length - sliding_window``, which no
later query sees, so a row that is not live (``decode_step(live=...)``; the
caller rolls its ``length`` back) loses nothing a later step reads, exactly
as with the plane's slot above ``length``. An admission scatters a row's
ring whole, so a recycled slot never shows a stale key.

**Precision** as the hybrid's: the residual stream, the norms, the router
and the softmax are float32; a matrix product takes its input in the compute
type (bfloat16 as served) and accumulates in float32.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from eventgpt_tpu.config import AfmoeConfig
from eventgpt_tpu.models import experts as experts_mod
from eventgpt_tpu.models.llama import (
    _cache_read_layer, _cache_write, _lm_head, _mlp_block, apply_rope,
    embed_tokens, grouped_attention, rms_norm, rope_tables,
)
from eventgpt_tpu.ops.quant import matmul as _mm, matmul_f32_out as _mm_f32

Params = Dict[str, Any]
Cache = Dict[str, jnp.ndarray]

WINDOW, GLOBAL = "sliding_attention", "full_attention"
STATS = experts_mod.STATS

# -- what ``ContinuousBatcher`` asks a decoder module ------------------------
def fixed_state(cfg: AfmoeConfig) -> Tuple[str, ...]:
    """The planes of a row's state that do not grow with its position (rows
    on axis 1; scattered whole at admission), beside ``k`` / ``v`` by
    position: each window layer's ring of keys and of values."""
    n = cfg.count(WINDOW)
    return (tuple(f"k_ring{i}" for i in range(n))
            + tuple(f"v_ring{i}" for i in range(n)))


# The most positions one admission wave may prefill at once (rows x bucket):
# the expert layer sorts ``num_experts_per_tok`` assignments a position and
# the prompts this decoder is served for are three windows long, so a wave
# is one such prompt (12,288 positions), or up to 16 short ones.
WAVE_TOKENS = 16384
# Up to this many tokens the held experts are computed as one batched product
# over every held expert, above it as a grouped product over the assignments
# sorted by expert (``models/experts.py``). At the served load (32 rows x 4
# of 256 experts, 32 held) a step's tokens choose 5-7 of the 32 held experts
# a layer, the grouped product does not read the experts no token chose, and
# the batched one reads all 32: 0.80 ms against 2.66 ms a layer at the
# served shapes (my chip run, PR 33; PERF.md section 5). So: the grouped
# form at every size.
DENSE_EXPERTS_UP_TO = 0
# What cannot serve a ring yet, by the flag's name: each mechanism below
# moves, shares, slices or rolls back keys and values by position only.
REFUSED_AS = "a decoder with window layers (a ring of keys and values)"
REFUSES = {
    "--kv_cache int8": "the int8 cache has no ring",
    "--kv_layout paged": "a block holds a plane's positions; a ring's slots "
                         "are not positions",
    "--speculative": "a rejected draft's keys have already overwritten the "
                     "ring's oldest slots and cannot be rolled back",
    "--spec_buckets": "a rejected draft's keys have already overwritten the "
                      "ring's oldest slots and cannot be rolled back",
    "--draft_head": "speculation is refused",
    "--prefill_chunk": "chunked admission prefills through decode_kstep, "
                       "which writes no ring",
    "--prefill_budget": "piggyback lanes prefill through decode_kstep, which "
                        "writes no ring (pass --prefill_budget 0)",
    "--prefix_cache_mb": "a prefix entry holds a plane's positions and no "
                         "ring as it stood at the prefix's end (pass "
                         "--no_prefix_cache)",
    "--preempt": "a spill record holds block runs only",
    "--role": "a handoff record holds block runs only",
    "--mesh_model": "the decoder runs on one device (no expert axis in "
                    "parallel/mesh.py; --mesh_data and --mesh_fsdp likewise)",
    "--quant": "ops/quant is two-dimensional and does not take stacked "
               "experts",
    "--fuse_params": "the gate projection and the q / k norms sit between "
                     "q|k|v; there is no fused form",
}


# -- parameters ---------------------------------------------------------------

def init_params(cfg: AfmoeConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    d, hd = cfg.hidden_size, cfg.resolved_head_dim()
    qd, kvd = cfg.num_heads * hd, cfg.num_kv_heads * hd
    f = cfg.moe_intermediate_size
    keys = iter(jax.random.split(key, 20 * cfg.num_layers + 4))
    residual = 1.0 / math.sqrt(2 * cfg.num_layers)

    def dense(fan_in, shape, gain=1.0):
        return (jax.random.normal(next(keys), shape, dtype)
                * (gain / math.sqrt(fan_in)))

    def swiglu(lead, width, out_gain):
        return {"gate": dense(d, lead + (d, width)),
                "up": dense(d, lead + (d, width)),
                "down": dense(width, lead + (width, d), out_gain)}

    def layer(i: int) -> Params:
        out = {
            "input_norm": jnp.ones((d,), dtype),
            "q_proj": dense(d, (d, qd)),
            "k_proj": dense(d, (d, kvd)),
            "v_proj": dense(d, (d, kvd)),
            "gate_proj": dense(d, (d, qd)),
            "q_norm": jnp.ones((hd,), dtype),
            "k_norm": jnp.ones((hd,), dtype),
            "o_proj": dense(qd, (qd, d), residual),
            "post_attn_norm": jnp.ones((d,), dtype),
            "pre_mlp_norm": jnp.ones((d,), dtype),
            "post_mlp_norm": jnp.ones((d,), dtype),
        }
        if i < cfg.num_dense_layers:
            out["mlp"] = swiglu((), cfg.intermediate_size, residual)
        else:
            out["router"] = dense(d, (d, cfg.num_experts))
            out["expert_bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
            out["experts"] = swiglu((cfg.experts_held,), f, residual)
            out["shared"] = swiglu((), f, residual)
        return out

    return {
        "embed_tokens": jax.random.normal(next(keys), (cfg.vocab_size, d),
                                          dtype) * 0.02,
        "layers": [layer(i) for i in range(cfg.num_layers)],
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense(d, (d, cfg.vocab_size)),
    }


def init_cache(cfg: AfmoeConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, quant: bool = False) -> Cache:
    """A plane of ``max_len`` positions for each global layer (stacked), a
    ring of ``sliding_window`` slots for each window layer (an array each),
    and what the expert layers last counted. Rows are axis 1 of every plane
    and ring."""
    if quant:
        raise ValueError("the int8 cache has no ring: a decoder with window "
                         "layers keeps its keys and values as served")
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim()
    plane = (cfg.count(GLOBAL), batch, max_len, kv, hd)
    ring = (1, batch, cfg.sliding_window, kv, hd)
    return {
        "k": jnp.zeros(plane, dtype),
        "v": jnp.zeros(plane, dtype),
        **{name: jnp.zeros(ring, dtype) for name in fixed_state(cfg)},
        "moe_stats": jnp.zeros((cfg.num_layers - cfg.num_dense_layers,
                                len(STATS)), jnp.int32),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def span_counts(cfg: AfmoeConfig, lengths) -> Dict[str, int]:
    """What a ``sched.dispatch`` span carries beside the scheduler's own
    counts, from the host's mirror of the live rows' lengths (no fetch):
    ``past_window``, the rows at or past the window, every slot of whose
    rings is live."""
    return {"past_window": sum(int(n) >= cfg.sliding_window
                               for n in lengths)}


# -- a layer's two halves --------------------------------------------------------

def _routing(cfg: AfmoeConfig) -> experts_mod.Routing:
    return experts_mod.Routing(
        top_k=cfg.num_experts_per_tok, held=cfg.experts_held,
        offset=cfg.experts_offset, normalise=cfg.route_norm,
        scale=cfg.route_scale)


def _project(cfg: AfmoeConfig, layer: Params, y, cos, sin, rotate: bool):
    """y (B, T, D) in the compute type -> q (B, T, H, hd), k, v (B, T, KV,
    hd) and the gate (B, T, H * hd): q and k normed over the head's channels,
    rotated where the layer is a window layer."""
    b, t, _ = y.shape
    hd = cfg.resolved_head_dim()
    q = _mm(y, layer["q_proj"]).reshape(b, t, cfg.num_heads, hd)
    k = _mm(y, layer["k_proj"]).reshape(b, t, cfg.num_kv_heads, hd)
    v = _mm(y, layer["v_proj"]).reshape(b, t, cfg.num_kv_heads, hd)
    gate = _mm(y, layer["gate_proj"])
    q = rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
    k = rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
    if rotate:
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    return q, k, v, gate


def _attn_out(cfg: AfmoeConfig, layer: Params, ctx, gate):
    """ctx (B, T, ...) -> the attention half's output (B, T, D) float32: the
    gate, the output projection, the norm after it."""
    b, t = gate.shape[:2]
    with jax.named_scope("attn_gate"):
        ctx = ctx.reshape(b, t, -1) * jax.nn.sigmoid(
            gate.astype(jnp.float32)).astype(ctx.dtype)
    return rms_norm(_mm_f32(ctx, layer["o_proj"]), layer["post_attn_norm"],
                    cfg.rms_norm_eps)


def _mlp_half(cfg: AfmoeConfig, layer: Params, x, counted, dtype):
    """x (B, T, D) float32 -> (the MLP half's output (B, T, D) float32, the
    expert layer's ``STATS`` or None for a dense layer). ``counted`` (B, T)
    bool: the tokens that are real or live."""
    b, t, d = x.shape
    y = rms_norm(x, layer["pre_mlp_norm"], cfg.rms_norm_eps)
    if "mlp" in layer:
        out, stats = _mlp_block(y.astype(dtype), layer).astype(jnp.float32), None
    else:
        out, stats = experts_mod.sparse_experts(
            _routing(cfg), y.reshape(b * t, d), counted.reshape(b * t), dtype,
            router=layer["router"], bias=layer["expert_bias"],
            experts=layer["experts"], shared=layer["shared"],
            dense_up_to=DENSE_EXPERTS_UP_TO)
        out = out.reshape(b, t, d)
    return rms_norm(out, layer["post_mlp_norm"], cfg.rms_norm_eps), stats


def _scale_in(cfg: AfmoeConfig, embeds):
    x = embeds.astype(jnp.float32)
    return x * math.sqrt(cfg.hidden_size) if cfg.mup_enabled else x


def _to_ring(cfg: AfmoeConfig, kv, lengths):
    """kv (B, T, KV, hd) by position -> (B, sliding_window, KV, hd) by slot:
    slot ``s`` holds the last real position ``p < length`` with ``p %
    sliding_window == s`` (a slot no position has reached holds position 0,
    and is masked until a step writes it)."""
    t, w = kv.shape[1], cfg.sliding_window
    if t <= w:
        return jnp.pad(kv, ((0, 0), (0, w - t), (0, 0), (0, 0)))
    slots = jnp.arange(w)[None, :]
    last = lengths[:, None] - 1
    pos = jnp.maximum(slots + w * jnp.floor_divide(last - slots, w), 0)
    return jnp.take_along_axis(kv, pos[:, :, None, None], axis=1)


# -- the four entry points -----------------------------------------------------

def prefill(
    params: Params,
    cfg: AfmoeConfig,
    inputs_embeds: jnp.ndarray,
    attention_mask: jnp.ndarray,
    cache: Cache,
    last_only: bool = False,
    mesh=None,
    return_hidden: bool = False,
) -> Tuple[jnp.ndarray, Cache]:
    """The whole prompt, as ``llama.prefill``: (logits, filled cache);
    ``attention_mask`` True at real positions, right-padded. A global
    layer's keys and values occupy slots [0, T) of its plane; a window
    layer's ring holds each row's last ``sliding_window`` real positions."""
    if mesh is not None:
        raise ValueError("the afmoe decoder runs on one device: no mesh")
    b, t, _ = inputs_embeds.shape
    w = cfg.sliding_window
    lengths = attention_mask.astype(jnp.int32).sum(axis=1)
    cos, sin = rope_tables(cfg, jnp.broadcast_to(jnp.arange(t)[None], (b, t)))
    use_flash = cfg.attn_impl == "flash"
    masks = {}
    if not use_flash:
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        causal = (j <= i)[None, None] & attention_mask[:, None, None, :]
        neg = jnp.finfo(jnp.float32).min
        masks = {GLOBAL: jnp.where(causal, 0.0, neg),
                 WINDOW: jnp.where(causal & (i - j < w)[None, None], 0.0, neg)}

    dtype = inputs_embeds.dtype
    x = _scale_in(cfg, inputs_embeds)
    bufs = {name: cache[name] for name in ("k", "v") + fixed_state(cfg)}
    stats = []
    seen = {WINDOW: 0, GLOBAL: 0}
    for kind, layer in zip(cfg.layer_types, params["layers"]):
        li = seen[kind]  # the layer's plane or ring of its kind
        seen[kind] += 1
        window = kind == WINDOW
        y = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps).astype(dtype)
        q, k, v, gate = _project(cfg, layer, y, cos, sin, rotate=window)
        with jax.named_scope("prefill_attn_window" if window
                             else "prefill_attn"):
            if use_flash:
                from eventgpt_tpu.ops.flash_attention import (
                    flash_attention_blocked,
                )

                ctx = flash_attention_blocked(
                    q, k, v, valid=attention_mask,
                    window=w if window else None)
            else:
                ctx = grouped_attention(q, k, v, masks[kind])
        x = x + _attn_out(cfg, layer, ctx, gate)
        if window:
            for name, new in ((f"k_ring{li}", k), (f"v_ring{li}", v)):
                bufs[name] = _to_ring(cfg, new, lengths).astype(
                    bufs[name].dtype)[None]
        else:
            bufs["k"] = bufs["k"].at[li, :, :t].set(k.astype(bufs["k"].dtype))
            bufs["v"] = bufs["v"].at[li, :, :t].set(v.astype(bufs["v"].dtype))
        out, st = _mlp_half(cfg, layer, x, attention_mask, dtype)
        if st is not None:
            stats.append(st)
        x = x + out

    new_cache = {**bufs,
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
    cfg: AfmoeConfig,
    token_embeds: jnp.ndarray,
    cache: Cache,
    live: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Cache]:
    """One token a row, as ``llama.decode_step``: token_embeds (B, 1, D) ->
    (logits (B, V), cache with ``length + 1``). ``live`` (B,) bool: rows
    that are not live are left out of the expert layers' counts; their
    writes (the plane's slot above ``length``, the ring's slot of the
    position a window behind) touch nothing a later step reads, and the
    caller rolls their ``length`` back."""
    b = token_embeds.shape[0]
    w = cfg.sliding_window
    max_len = cache["k"].shape[2]
    pos = cache["length"]
    cos, sin = rope_tables(cfg, pos[:, None])
    neg = jnp.finfo(jnp.float32).min

    def visible(slots: int):  # (B, 1, 1, slots): slot s holds a key <= pos
        ok = jnp.arange(slots)[None, :] <= pos[:, None]
        return jnp.where(ok[:, None, None, :], 0.0, neg)

    masks = {GLOBAL: visible(max_len), WINDOW: visible(w)}
    batch_idx = jnp.arange(b)
    counted = (live if live is not None else jnp.ones((b,), bool))[:, None]

    dtype = token_embeds.dtype
    x = _scale_in(cfg, token_embeds)
    bufs = {name: cache[name] for name in ("k", "v") + fixed_state(cfg)}
    stats = []
    seen = {WINDOW: 0, GLOBAL: 0}
    for kind, layer in zip(cfg.layer_types, params["layers"]):
        n = seen[kind]
        seen[kind] += 1
        window = kind == WINDOW
        # a ring is its own array (index 0 of a unit axis), a plane is its
        # kind's n-th
        kn, vn, li = ((f"k_ring{n}", f"v_ring{n}", 0) if window
                      else ("k", "v", n))
        y = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps).astype(dtype)
        q, k, v, gate = _project(cfg, layer, y, cos, sin, rotate=window)
        slot = pos % w if window else pos
        bufs[kn] = _cache_write(bufs[kn], li, batch_idx, slot, k[:, 0], False)
        bufs[vn] = _cache_write(bufs[vn], li, batch_idx, slot, v[:, 0], False)
        with jax.named_scope("decode_attn_window" if window
                             else "decode_attn"):
            ctx = grouped_attention(
                q, _cache_read_layer(bufs[kn], li, dtype, False),
                _cache_read_layer(bufs[vn], li, dtype, False), masks[kind])
        x = x + _attn_out(cfg, layer, ctx, gate)
        out, st = _mlp_half(cfg, layer, x, counted, dtype)
        if st is not None:
            stats.append(st)
        x = x + out

    new_cache = {**bufs,
                 "moe_stats": (jnp.stack(stats) if stats
                               else cache["moe_stats"]),
                 "length": cache["length"] + 1}
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps).astype(dtype)
    return _lm_head(params, x[:, 0]), new_cache


def forward(
    params: Params,
    cfg: AfmoeConfig,
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
