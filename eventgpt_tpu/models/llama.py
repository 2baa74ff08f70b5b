"""LLaMA/Vicuna decoder-only LM, TPU-first.

Functional JAX reimplementation of the reference's HF ``LlamaForCausalLM``
backbone (``model/EventChatModel.py:166-176``): RMSNorm, RoPE, GQA-capable
attention, SwiGLU MLP. Numerics match HF LLaMA.

TPU-first design (SURVEY.md §7):
  * layers stacked on a leading axis, driven by ``lax.scan`` — O(1) compile
    time in depth; the stacked axis shards cleanly under fsdp;
  * the decode path is split into three jit units — ``prefill`` (batched
    matmuls over the whole prompt, writes the KV cache) and ``decode_step``
    (one token, reads the HBM-resident cache) — mirroring the reference's
    one-shot multimodal embed + HF generate loop seam
    (``model/EventChatModel.py:296-297``, SURVEY.md §3.3);
  * f32 softmax/logit accumulation under bf16 params;
  * accepts ``inputs_embeds`` directly, because the multimodal path splices
    event features into the embedding sequence before the LM ever runs.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from eventgpt_tpu.config import LlamaConfig
from eventgpt_tpu.ops.quant import matmul as _mm, matmul_f32_out as _mm_f32

Params = Dict[str, Any]
KVCache = Dict[str, jnp.ndarray]  # {"k": [L,B,S,KV,hd], "v": [L,B,S,KV,hd], "length": [B]}


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    norm = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (norm * weight.astype(jnp.float32)).astype(x.dtype)


def rope_tables(cfg: LlamaConfig, positions: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for given positions: (..., head_dim) each, f32.

    HF convention: inv_freq over even indices, table is concat(freqs, freqs),
    rotation by rotate_half (split at head_dim/2).
    """
    hd = cfg.resolved_head_dim()
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., hd/2)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, hd); cos/sin: (B, S, hd) -> rotated x (HF rotate_half)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    return x * cos + rotated * sin


def init_llama_params(cfg: LlamaConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    d, i, l = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    hd = cfg.resolved_head_dim()
    qd, kvd = cfg.num_heads * hd, cfg.num_kv_heads * hd
    keys = jax.random.split(key, 8)

    def dense(k, fan_in, shape):
        return jax.random.normal(k, shape, dtype) * (1.0 / math.sqrt(fan_in))

    return {
        "embed_tokens": jax.random.normal(keys[0], (cfg.vocab_size, d), dtype) * 0.02,
        "layers": {
            "input_norm": jnp.ones((l, d), dtype),
            "attn": {
                "q": dense(keys[1], d, (l, d, qd)),
                "k": dense(keys[2], d, (l, d, kvd)),
                "v": dense(keys[3], d, (l, d, kvd)),
                "o": dense(keys[4], qd, (l, qd, d)),
            },
            "post_norm": jnp.ones((l, d), dtype),
            "mlp": {
                "gate": dense(keys[5], d, (l, d, i)),
                "up": dense(keys[6], d, (l, d, i)),
                "down": dense(keys[7], i, (l, i, d)),
            },
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense(keys[0], d, (d, cfg.vocab_size)),
    }


# The names a decoder module is reached by (models/eventchat.decoder_of),
# and what ``ContinuousBatcher`` asks one: the dense decoder's whole state is
# keys and values by position, no wave is capped, and no flag is refused.
init_params = init_llama_params
WAVE_TOKENS = 0
REFUSES: Dict[str, str] = {}


def fixed_state(cfg: LlamaConfig) -> Tuple[str, ...]:
    """No plane of a row's state but keys and values by position."""
    return ()


def span_counts(cfg: LlamaConfig, lengths) -> Dict[str, int]:
    """Nothing of this decoder's own on a dispatch span."""
    return {}


def embed_tokens(params: Params, input_ids: jnp.ndarray) -> jnp.ndarray:
    return params["embed_tokens"][input_ids]


def _remat_policy(cfg: LlamaConfig):
    """Map ``cfg.remat_policy`` onto a ``jax.checkpoint`` policy (ISSUE
    13 satellite — the stage-2 remat sweep). "full" is jax's default
    (save nothing, recompute every layer activation — the pre-sweep
    behavior, byte-identical HLO to passing no policy at all);
    "nothing_saveable" is the same semantics via the explicit policy
    object; "dots_saveable" (and the no-batch-dims variant) save matmul
    outputs, trading HBM for the ~19 TFLOP/step of stage-2 recompute
    full remat pays at 7B. Forward-only callers (serving) never hit the
    policy: it only shapes the backward pass."""
    name = getattr(cfg, "remat_policy", "full")
    if name == "full":
        return None
    return getattr(jax.checkpoint_policies, name)


def resize_token_embeddings(params: Params, new_vocab_size: int) -> Params:
    """Grow embed/lm_head rows, initializing new rows to the mean of old ones.

    Mirrors ``resize_token_embeddings`` + the mean-init of
    ``initialize_vision_tokenizer`` (``model/EventChatModel.py:202-212``,
    ``inference.py:39``). Shrinking truncates.
    """
    embed = params["embed_tokens"]
    head = params["lm_head"]
    old = embed.shape[0]
    if new_vocab_size <= old:
        return {**params, "embed_tokens": embed[:new_vocab_size],
                "lm_head": head[:, :new_vocab_size]}
    n_new = new_vocab_size - old
    embed_new = jnp.concatenate(
        [embed, jnp.broadcast_to(embed.mean(axis=0, keepdims=True), (n_new, embed.shape[1]))]
    )
    head_new = jnp.concatenate(
        [head, jnp.broadcast_to(head.mean(axis=1, keepdims=True), (head.shape[0], n_new))],
        axis=1,
    )
    return {**params, "embed_tokens": embed_new, "lm_head": head_new}


def fuse_llama_params(params: Params) -> Params:
    """Inference-time transform: concat q|k|v and gate|up along the output
    axis so each decode layer runs 5 weight matmuls instead of 7.

    The standard serving-stack transform (vLLM/TensorRT fuse qkv the same
    way). Measured on v5e batch-1 int8 decode it is perf-neutral (83.6 vs
    84.1 tok/s — XLA already pipelines the split dots at bandwidth), so it
    stays opt-in; it mainly helps wider batches and shorter layers. Fuse
    AFTER loading (and BEFORE quantization, so scales are computed on the
    fused tensor and stream with it). Not for training: LoRA targets
    address the unfused names.
    """
    import numpy as np

    layers = params["layers"]
    attn, mlp = layers["attn"], layers["mlp"]
    # Host (numpy) trees fuse on host — a jnp.concatenate here would pull
    # the whole 7B tree onto the device before quantization/sharding.
    xp = jnp if isinstance(attn["q"], jax.Array) else np
    fused = {
        **params,
        "layers": {
            **layers,
            "attn": {
                "qkv": xp.concatenate(
                    [attn["q"], attn["k"], attn["v"]], axis=-1
                ),
                "o": attn["o"],
            },
            "mlp": {
                "gate_up": xp.concatenate(
                    [mlp["gate"], mlp["up"]], axis=-1
                ),
                "down": mlp["down"],
            },
        },
    }
    return fused


def _project_qkv(cfg: LlamaConfig, y: jnp.ndarray, layer: Params):
    """y (B, T, D) -> (q, k, v) pre-RoPE, honoring fused or split leaves.
    q: (B, T, H*hd); k/v: (B, T, KV, hd)."""
    b, t, _ = y.shape
    hd = cfg.resolved_head_dim()
    qd, kvd = cfg.num_heads * hd, cfg.num_kv_heads * hd
    attn = layer["attn"]
    if "qkv" in attn:
        qkv = _mm(y, attn["qkv"])
        q, k, v = qkv[..., :qd], qkv[..., qd:qd + kvd], qkv[..., qd + kvd:]
    else:
        q = _mm(y, attn["q"])
        k = _mm(y, attn["k"])
        v = _mm(y, attn["v"])
    return q, k.reshape(b, t, cfg.num_kv_heads, hd), v.reshape(b, t, cfg.num_kv_heads, hd)


def _repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, KV, hd) -> (B, S, KV*n_rep, hd), GQA head replication (head
    ``g * n_rep + r`` reads KV head ``g``). Only for callees that take
    repeated heads: the flash kernel, the serving-mesh flash shard_map and
    the ring in ``_attn_block``, and Ulysses after its all-to-all
    (``parallel/ulysses.py``). Dense attention never repeats."""
    if n_rep == 1:
        return x
    b, s, kv, hd = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, hd)).reshape(b, s, kv * n_rep, hd)


def _attn_block(cfg: LlamaConfig, q_proj: jnp.ndarray, layer: Params,
                cos: jnp.ndarray, sin: jnp.ndarray,
                k_full: jnp.ndarray, v_full: jnp.ndarray,
                mask: Optional[jnp.ndarray] = None,
                valid: Optional[jnp.ndarray] = None,
                use_flash: bool = False,
                ring_fn=None,
                flash_fn=None,
                scope: str = "attn") -> jnp.ndarray:
    """Shared attention plumbing (RoPE on the precomputed q projection + o
    proj) with a score-computation switch: dense additive ``mask``
    (B,1,Q,S), the Pallas flash kernel with a (B,S) ``valid`` padding mask
    (causal implied), a ring-attention shard_map ``ring_fn`` for sequence
    parallelism over the ``context`` mesh axis, or a serving-mesh flash
    shard_map ``flash_fn`` (``parallel/serving.py:serving_flash_shard_map``).
    q_proj: (B,Q,H*hd) from ``_project_qkv`` (possibly a fused-qkv slice);
    k/v_full: (B,S,KV,hd). The dense branch (every decode step, and prefill
    under ``attn_impl="dense"``) groups the query's heads per KV head and
    reads K / V as they are; the kernels and the ring take K / V repeated to
    H heads (``_repeat_kv``). ``scope`` names scores, softmax and value
    product (and a kernel's GQA repeat) on a device trace (``prefill_attn``
    / ``decode_attn``; metadata only)."""
    b, q_len, _ = q_proj.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim()

    q = q_proj.reshape(b, q_len, h, hd)
    q = apply_rope(q, cos, sin)
    if ring_fn is not None and getattr(ring_fn, "accepts_unrepeated_kv", False):
        # Ulysses repeats GQA heads AFTER its all-to-all — the exchange
        # moves KV-count bytes, not H-count (ADVICE r2).
        ctx = ring_fn(q, k_full, v_full, valid, valid).reshape(b, q_len, h * hd)
        return _mm(ctx, layer["attn"]["o"])
    rep = h // kvh
    with jax.named_scope(scope):
        if ring_fn is not None or flash_fn is not None or use_flash:
            k, v = _repeat_kv(k_full, rep), _repeat_kv(v_full, rep)

        if ring_fn is not None:
            ctx = ring_fn(q, k, v, valid, valid)
        elif flash_fn is not None:
            ctx = flash_fn(q, k, v, valid)
        elif use_flash:
            from eventgpt_tpu.ops.flash_attention import flash_attention

            ctx = flash_attention(q, k, v, valid=valid, causal=True)
        else:
            ctx = grouped_attention(q, k_full, v_full, mask)
    return _mm(ctx.reshape(b, q_len, h * hd), layer["attn"]["o"])


def grouped_attention(q: jnp.ndarray, k_full: jnp.ndarray,
                      v_full: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Dense attention of q (B, Q, H, hd) over K / V as stored (B, S, KV,
    hd) under an additive ``mask`` (B, 1, Q, S): the read every decode step
    takes. Queries regrouped per KV head (h = g * rep + r, _repeat_kv's
    order) and contracted against K / V as they are: M = rep * Q rows per
    (b, g) product, no repeated and no f32 copy of K / V. Returns (B, Q,
    KV, rep, hd) in q's type."""
    b, q_len, h, hd = q.shape
    kvh = k_full.shape[2]
    qg = q.reshape(b, q_len, kvh, h // kvh, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_full,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / math.sqrt(hd)) + mask[:, :, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v_full)


def _mlp_block(x: jnp.ndarray, layer: Params) -> jnp.ndarray:
    mlp = layer["mlp"]
    with jax.named_scope("mlp"):
        if "gate_up" in mlp:
            gu = _mm(x, mlp["gate_up"])
            i = gu.shape[-1] // 2
            gate, up = gu[..., :i], gu[..., i:]
        else:
            gate, up = _mm(x, mlp["gate"]), _mm(x, mlp["up"])
        return _mm(jax.nn.silu(gate) * up, mlp["down"])


def _lm_head(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("lm_head"):
        return _mm_f32(x, params["lm_head"])


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
                  quant: bool = False) -> KVCache:
    """KV cache buffers. ``quant=True`` stores int8 payloads with one f32
    scale per (layer, row, position, head) — half the HBM footprint and
    stream bandwidth of bf16 (the cache is the dominant batched-decode
    allocation: 369 MB/row at 7B)."""
    hd = cfg.resolved_head_dim()
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, hd)
    if quant:
        def qbuf():
            return {"q": jnp.zeros(shape, jnp.int8),
                    "s": jnp.zeros(shape[:-1] + (1,), jnp.float32)}

        return {"k": qbuf(), "v": qbuf(),
                "length": jnp.zeros((batch,), jnp.int32)}
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def init_paged_kv_cache(cfg: LlamaConfig, batch: int, max_len: int,
                        n_blocks: int, block_size: int,
                        dtype=jnp.bfloat16, quant: bool = False) -> KVCache:
    """Paged KV cache (ISSUE 12): ONE static block-pool arena per plane —
    (L, n_blocks, block_size, KV, hd) — plus a per-row int32 block table
    ``bt`` (batch, max_len // block_size). Rows no longer own dense
    ``max_len`` runs: logical position ``p`` of row ``r`` lives at pool
    slot ``(bt[r, p // bs], p % bs)``, so resident bytes scale with the
    blocks actually reserved, not ``batch × max_len``. Every shape stays
    static for XLA; the dynamic part (which block backs which row) is
    host bookkeeping (``serve_blocks.BlockPool``). Tables start at block
    0 — the pool's reserved scratch block — so an unadmitted row's
    unconditional frozen writes land in storage nothing reads."""
    if max_len % block_size:
        raise ValueError(
            f"max_len {max_len} must be a block_size {block_size} multiple")
    hd = cfg.resolved_head_dim()
    shape = (cfg.num_layers, n_blocks, block_size, cfg.num_kv_heads, hd)
    if quant:
        def qbuf():
            return {"q": jnp.zeros(shape, jnp.int8),
                    "s": jnp.zeros(shape[:-1] + (1,), jnp.float32)}

        k, v = qbuf(), qbuf()
    else:
        k, v = jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
    return {
        "k": k,
        "v": v,
        "bt": jnp.zeros((batch, max_len // block_size), jnp.int32),
        "length": jnp.zeros((batch,), jnp.int32),
    }


init_cache = init_kv_cache


def _kv_is_quant(cache: KVCache) -> bool:
    return isinstance(cache["k"], dict)


def _kv_is_paged(cache: KVCache) -> bool:
    return "bt" in cache


def _kv_max_len(cache: KVCache) -> int:
    """Logical per-row KV capacity: dense reads it off the buffer's slot
    axis; paged, off the block table (rows × blocks-per-row view)."""
    buf = cache["k"]["q"] if _kv_is_quant(cache) else cache["k"]
    if _kv_is_paged(cache):
        return cache["bt"].shape[1] * buf.shape[2]
    return buf.shape[2]


def _cache_write(buf, li, batch_idx, slots, vals, quant: bool, bt=None):
    """Write new K/V rows into layer ``li`` of a cache buffer — THE cache
    write for both decode paths, so the bf16-vs-int8 handling cannot drift
    between them. ``slots`` (B,) writes one slot per row (decode_step's hot
    loop — lowers to an in-place dynamic-update-slice); (B, K) writes a
    verification window per row (decode_kstep — a scatter). ``vals`` has a
    matching leading shape + (KV, hd).

    ``bt`` (paged cache): logical slots translate through the row's block
    table to (pool block, offset) pairs. Values written are identical to
    the dense path's — the translation is pure indexing — which is what
    keeps paged chains byte-identical to dense ones. Writable blocks are
    exclusively owned by construction (copy-on-write in the serving
    allocator), so the scatter indices of live rows never collide; frozen
    rows' garbage writes all land in the shared scratch block, whose
    content no attention read ever sees (masked above ``length``)."""
    if bt is not None:
        bs = (buf["q"] if quant else buf).shape[2]
        blk = slots // bs
        off = slots % bs
        blocks = (bt[batch_idx, blk] if slots.ndim == 1
                  else bt[batch_idx[:, None], blk])
        if quant:
            qs = _kv_quantize(vals)
            return {"q": buf["q"].at[li, blocks, off].set(qs["q"]),
                    "s": buf["s"].at[li, blocks, off].set(qs["s"])}
        return buf.at[li, blocks, off].set(vals.astype(buf.dtype))
    idx = batch_idx if slots.ndim == 1 else batch_idx[:, None]
    if quant:
        qs = _kv_quantize(vals)
        return {"q": buf["q"].at[li, idx, slots].set(qs["q"]),
                "s": buf["s"].at[li, idx, slots].set(qs["s"])}
    return buf.at[li, idx, slots].set(vals.astype(buf.dtype))


def _cache_read_layer(buf, li, dtype, quant: bool, bt=None):
    """Layer ``li`` of a cache buffer as (B, S, KV, hd) in ``dtype``. For the
    int8 cache the dequant fuses into the attention einsum's operand reads:
    HBM streams int8 payloads + 1/hd scales instead of bf16.

    ``bt`` (paged cache): the pure-jnp gather fallback — pool blocks
    gather through the block table into the same (B, S, KV, hd) view the
    dense path reads (S = blocks_per_row × block_size), so the attention
    math downstream is untouched and bitwise identical (a gather is a
    copy). The view is a per-layer TEMPORARY — 1/L of the dense cache's
    residency — not a resident buffer. This gather is the only paged read
    the program has: the Pallas kernels of ``ops/decode_attention.py``
    (int8 caches only) would compute attention block by block without the
    view, but nothing calls them (ROADMAP D6)."""
    if bt is not None:
        b, nbpr = bt.shape
        if quant:
            lq = lax.dynamic_index_in_dim(buf["q"], li, keepdims=False)[bt]
            ls = lax.dynamic_index_in_dim(buf["s"], li, keepdims=False)[bt]
            x = _kv_dequant({"q": lq, "s": ls}, dtype)
        else:
            x = lax.dynamic_index_in_dim(buf, li, keepdims=False)[bt]
            x = x.astype(dtype)
        # (B, nbpr, bs, KV, hd) -> (B, nbpr * bs, KV, hd)
        return x.reshape(b, nbpr * x.shape[2], x.shape[3], x.shape[4])
    if quant:
        leaf = {"q": lax.dynamic_index_in_dim(buf["q"], li, keepdims=False),
                "s": lax.dynamic_index_in_dim(buf["s"], li, keepdims=False)}
        return _kv_dequant(leaf, dtype)
    return lax.dynamic_index_in_dim(buf, li, keepdims=False).astype(dtype)


def _kv_quantize(x: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """(..., hd) -> {"q": int8, "s": f32 (..., 1)}; symmetric per-vector."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x32 / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s}


def _kv_dequant(leaf: Dict[str, jnp.ndarray], dtype) -> jnp.ndarray:
    return (leaf["q"].astype(jnp.float32) * leaf["s"]).astype(dtype)


def prefill(
    params: Params,
    cfg: LlamaConfig,
    inputs_embeds: jnp.ndarray,
    attention_mask: jnp.ndarray,
    cache: KVCache,
    last_only: bool = False,
    mesh=None,
    return_hidden: bool = False,
) -> Tuple[jnp.ndarray, KVCache]:
    """Run the full prompt; returns (logits, filled cache).

    ``attention_mask`` is bool (B, T): True = real token, False = right pad.
    The prompt occupies cache slots [0, T); cache["length"] records the true
    per-row prompt length for the decode phase.

    ``last_only=False`` -> logits (B, T, V) (training/eval). ``last_only=True``
    -> logits (B, V) at each row's final real token — the only position
    ``generate`` consumes; skipping the other T-1 lm_head columns saves
    T x vocab f32 per row (0.66 GB at B=8, S=640).

    ``attn_impl == "ring"`` (or ``"ulysses"``) with a ``mesh`` whose
    ``context`` axis is > 1 runs sequence-parallel attention: ring rotates
    KV blocks via ppermute (``parallel/ring.py``); ulysses re-shards
    sequence<->heads with two all-to-alls and runs full-sequence local
    attention (``parallel/ulysses.py``; local heads must divide by the
    context size). T must divide the context axis size. Both fall back to
    dense on a context-1 mesh.
    """
    if _kv_is_paged(cache):
        # Serving never prefills into the pool directly: admission
        # prefills a dense per-request row cache and SCATTERS it into
        # allocated blocks (serve._admit_row_paged) — the seam that
        # keeps one prefill executable per bucket, pool-size-agnostic.
        raise ValueError(
            "prefill writes dense caches; scatter into a paged pool via "
            "the serving admission path")
    b, t, d = inputs_embeds.shape
    positions = jnp.cumsum(attention_mask.astype(jnp.int32), axis=1) - 1
    positions = jnp.maximum(positions, 0)
    cos, sin = rope_tables(cfg, positions)

    ring_fn = None
    flash_fn = None
    if mesh is not None and mesh.shape.get("context", 1) > 1:
        if cfg.attn_impl == "ring":
            from eventgpt_tpu.parallel.ring import ring_attention_shard_map

            ring_fn = ring_attention_shard_map(mesh, causal=True)
        elif cfg.attn_impl == "ulysses":
            from eventgpt_tpu.parallel.ulysses import ulysses_attention_shard_map

            ring_fn = ulysses_attention_shard_map(mesh, causal=True)
    elif mesh is not None and cfg.attn_impl == "flash":
        # Serving mesh (context=1): flash runs per-shard under shard_map —
        # batch over (data, fsdp), heads over model (the bare Pallas call is
        # opaque to GSPMD and would all-gather every operand).
        from eventgpt_tpu.parallel.serving import (
            require_flash_heads_divide, serving_flash_shard_map,
        )

        require_flash_heads_divide(cfg, mesh)
        flash_fn = serving_flash_shard_map(mesh, b)
    use_flash = cfg.attn_impl == "flash" and flash_fn is None
    if use_flash or ring_fn is not None or flash_fn is not None:
        mask = None  # causal + padding masks applied inline
    else:
        causal = jnp.tril(jnp.ones((t, t), bool))
        visible = causal[None, None] & attention_mask[:, None, None, :]
        mask = jnp.where(visible, 0.0, jnp.finfo(jnp.float32).min)

    x = inputs_embeds

    def block(carry, xs):
        layer, = xs
        h_in = carry
        y = rms_norm(h_in, layer["input_norm"], cfg.rms_norm_eps)
        q_proj, k, v = _project_qkv(cfg, y, layer)
        k = apply_rope(k, cos, sin)
        h_mid = h_in + _attn_block(cfg, q_proj, layer, cos, sin, k, v,
                                   mask=mask, valid=attention_mask,
                                   use_flash=use_flash, ring_fn=ring_fn,
                                   flash_fn=flash_fn, scope="prefill_attn")
        y2 = rms_norm(h_mid, layer["post_norm"], cfg.rms_norm_eps)
        h_out = h_mid + _mlp_block(y2, layer)
        return h_out, (k, v)

    block_fn = (jax.checkpoint(block, prevent_cse=False,
                               policy=_remat_policy(cfg))
                if cfg.remat else block)
    x, (k_all, v_all) = lax.scan(block_fn, x, (params["layers"],))

    # In-place slot write (aliases the donated cache buffers; jnp.pad here
    # would materialize a second full-size cache copy).
    lengths = attention_mask.astype(jnp.int32).sum(axis=1)

    def write(buf, vals):
        if isinstance(buf, dict):  # int8 cache: quantize the new slots
            qs = _kv_quantize(vals)
            return {"q": buf["q"].at[:, :, :t].set(qs["q"]),
                    "s": buf["s"].at[:, :, :t].set(qs["s"])}
        return buf.at[:, :, :t].set(vals.astype(buf.dtype))

    new_cache = {
        "k": write(cache["k"], k_all),
        "v": write(cache["v"], v_all),
        "length": lengths,
    }
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    # return_hidden uniformly appends the final-norm hidden as a THIRD
    # element — (B, D) at the last real token with last_only, (B, T, D)
    # otherwise (Medusa head seeding / training, models/medusa.py). A
    # caller that ignores unused outputs pays nothing: XLA dead-code
    # eliminates the lm_head matmul when only the hidden is consumed.
    if last_only:
        last = jnp.take_along_axis(
            x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1
        )[:, 0]  # (B, D)
        if return_hidden:
            return _lm_head(params, last), last, new_cache
        return _lm_head(params, last), new_cache
    logits = _lm_head(params, x)
    if return_hidden:
        return logits, x, new_cache
    return logits, new_cache


def decode_step(
    params: Params,
    cfg: LlamaConfig,
    token_embeds: jnp.ndarray,
    cache: KVCache,
    live: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, KVCache]:
    """One decode step. token_embeds: (B, 1, D). Returns (logits [B, V], cache).

    The new token lands at slot ``cache["length"]`` with position id equal to
    the number of real tokens so far (right-pad-free positions).

    ``live`` (B,) bool is part of the decoders' common signature and unused
    here: a row that is not live is rolled back by its ``length`` alone,
    which the caller does, since every slot above it is masked and
    overwritten; only a recurrent state needs the mask inside the step.
    """
    b = token_embeds.shape[0]
    max_len = _kv_max_len(cache)
    pos = cache["length"]  # (B,)
    cos, sin = rope_tables(cfg, pos[:, None])

    slot = pos  # write index per batch row
    valid = jnp.arange(max_len)[None, :] <= slot[:, None]  # (B, S) incl. new slot
    mask = jnp.where(valid[:, None, None, :], 0.0, jnp.finfo(jnp.float32).min)

    batch_idx = jnp.arange(b)
    quant = _kv_is_quant(cache)
    bt = cache.get("bt")  # paged: logical->pool block translation

    # The cache rides the scan as CARRY (not xs/ys): XLA aliases carry
    # buffers across iterations, so the (B,)-slot _cache_write lowers to an
    # in-place one-slot dynamic-update-slice. The previous xs/ys form
    # restacked the full (L, B, S, KV, hd) k and v buffers every decode
    # step — ~800 MB of pure copy traffic per token at 7B/S=768, measured
    # ~2 ms/token.
    def block(carry, xs):
        h_in, k_buf, v_buf = carry
        layer, li = xs
        y = rms_norm(h_in, layer["input_norm"], cfg.rms_norm_eps)
        q_proj, k_new, v_new = _project_qkv(cfg, y, layer)
        k_new = apply_rope(k_new, cos, sin)
        k_buf = _cache_write(k_buf, li, batch_idx, slot, k_new[:, 0], quant,
                             bt=bt)
        v_buf = _cache_write(v_buf, li, batch_idx, slot, v_new[:, 0], quant,
                             bt=bt)
        h_mid = h_in + _attn_block(cfg, q_proj, layer, cos, sin,
                                   _cache_read_layer(k_buf, li, h_in.dtype,
                                                     quant, bt=bt),
                                   _cache_read_layer(v_buf, li, h_in.dtype,
                                                     quant, bt=bt),
                                   mask, scope="decode_attn")
        y2 = rms_norm(h_mid, layer["post_norm"], cfg.rms_norm_eps)
        h_out = h_mid + _mlp_block(y2, layer)
        return (h_out, k_buf, v_buf), None

    (x, k_all, v_all), _ = lax.scan(
        block, (token_embeds, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.num_layers)),
    )
    new_cache = {"k": k_all, "v": v_all, "length": cache["length"] + 1}
    if bt is not None:
        new_cache["bt"] = bt
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = _lm_head(params, x[:, 0])
    return logits, new_cache


def decode_kstep(
    params: Params,
    cfg: LlamaConfig,
    token_embeds: jnp.ndarray,
    cache: KVCache,
    return_hidden: bool = False,
) -> Tuple[jnp.ndarray, KVCache]:
    """K-token verification step for speculative decoding.

    token_embeds: (B, K, D) — a window of candidate tokens appended after the
    cache contents. Returns (logits (B, K, V) f32, cache with K slots written
    and length advanced by K). The caller commits a prefix of the window by
    rolling ``length`` back to ``old_length + accepted`` — slots above
    ``length`` are masked out of every future attention read and are
    overwritten by the next window, so partial acceptance needs no undo.

    Query i sits at global position length+i and sees cache slots
    [0, length+i] — exactly what ``decode_step`` would have seen feeding the
    window one token at a time, so greedy argmax over these logits equals the
    sequential greedy chain (the speculative path's correctness contract).
    Weight streaming is the decode bottleneck (PERF.md section 5): the K-row
    GEMMs read the same bytes as one decode_step, which is why verifying K
    tokens costs ~one token's wall time at batch 1.
    """
    b, kq, _ = token_embeds.shape
    max_len = _kv_max_len(cache)
    base = cache["length"]  # (B,) tokens already cached
    offs = jnp.arange(kq)
    pos = base[:, None] + offs[None, :]  # (B, K) global positions
    cos, sin = rope_tables(cfg, pos)

    # Query i attends to slots [0, base+i] (its own slot included).
    valid = jnp.arange(max_len)[None, None, :] <= pos[:, :, None]  # (B, K, S)
    mask = jnp.where(valid[:, None], 0.0, jnp.finfo(jnp.float32).min)  # (B,1,K,S)

    batch_idx = jnp.arange(b)
    quant = _kv_is_quant(cache)
    bt = cache.get("bt")  # paged: logical->pool block translation

    def block(carry, xs):
        h_in, k_buf, v_buf = carry
        layer, li = xs
        y = rms_norm(h_in, layer["input_norm"], cfg.rms_norm_eps)
        q_proj, k_new, v_new = _project_qkv(cfg, y, layer)
        k_new = apply_rope(k_new, cos, sin)
        k_buf = _cache_write(k_buf, li, batch_idx, pos, k_new, quant, bt=bt)
        v_buf = _cache_write(v_buf, li, batch_idx, pos, v_new, quant, bt=bt)
        h_mid = h_in + _attn_block(cfg, q_proj, layer, cos, sin,
                                   _cache_read_layer(k_buf, li, h_in.dtype,
                                                     quant, bt=bt),
                                   _cache_read_layer(v_buf, li, h_in.dtype,
                                                     quant, bt=bt),
                                   mask, scope="decode_attn")
        y2 = rms_norm(h_mid, layer["post_norm"], cfg.rms_norm_eps)
        h_out = h_mid + _mlp_block(y2, layer)
        return (h_out, k_buf, v_buf), None

    (x, k_all, v_all), _ = lax.scan(
        block, (token_embeds, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.num_layers)),
    )
    new_cache = {"k": k_all, "v": v_all, "length": cache["length"] + kq}
    if bt is not None:
        new_cache["bt"] = bt
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = _lm_head(params, x)  # (B, K, V)
    if return_hidden:
        # Per-window-position final-norm hidden: the Medusa draft path
        # selects the correction position's hidden to seed the next
        # window's drafts (models/eventchat._spec_draft_verify).
        return logits, x, new_cache
    return logits, new_cache


def forward(
    params: Params,
    cfg: LlamaConfig,
    inputs_embeds: jnp.ndarray,
    attention_mask: Optional[jnp.ndarray] = None,
    mesh=None,
) -> jnp.ndarray:
    """Cache-free full forward -> logits (B, T, V). Training / eval path.
    The cache written by prefill is unused here and DCE'd by XLA."""
    b, t, _ = inputs_embeds.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, t), bool)
    cache = init_kv_cache(cfg, b, t, dtype=inputs_embeds.dtype)
    logits, _ = prefill(params, cfg, inputs_embeds, attention_mask, cache,
                        mesh=mesh)
    return logits
