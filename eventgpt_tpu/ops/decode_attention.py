"""Pallas fused int8-KV decode attention (single query over the HBM cache).

Why this kernel exists: batch-1 decode at 7B streams the whole weight set
per token, and the KV cache is the next-largest stream —
~0.5-0.7 GB/token bf16 at the reference's 512-token budget. The int8 cache
halves those bytes, but through plain XLA the dequantize (int8 * f32 scale
-> bf16) costs more VPU time than the bandwidth it saves: measured a WASH
at batch 1 (12.3 vs 11.9 ms/token on the r05 chip run, 2026-07-31). This
kernel performs the dequant in VMEM fused into the attention dots, so HBM
traffic actually drops to the int8 payload + per-vector scales and the
wash becomes a win.

Shape/layout contract (matches ``models/llama.py`` cache layout):
  * cache buffers: (L, B, S, KV, hd) int8 payload, (L, B, S, KV, 1) f32
    scales — the kernel receives the FULL stacked-layer buffer and selects
    the layer with a scalar-prefetched index (``PrefetchScalarGridSpec``),
    so the surrounding ``lax.scan`` over layers never materializes a
    per-layer slice copy.
  * q: (B, KV, G, hd) — post-RoPE query heads regrouped per KV head
    (G = H // KV, GQA-aware without repeating K/V).
  * n_valid: (B,) int32 — slots [0, n_valid) are attendable (the caller has
    already written the current token's K/V at slot n_valid-1).

Grid: (B, KV); each cell computes (G, hd) of output from one row's one KV
head: dequantized (S, hd) K/V tiles live only in VMEM. S is padded to a
lane multiple by the caller (cache lengths are bucket-aligned already).

Where the CPU was asked for the kernel runs in interpreter mode (CPU-mesh
tests), like ``ops/flash_attention.py`` (``utils/platform.pallas_interpret``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from eventgpt_tpu.utils.platform import pallas_interpret

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _decode_attn_kernel(li_ref, nv_ref, q_ref, kq_ref, ks_ref, vq_ref,
                        vs_ref, o_ref, *, scale: float, block_kv: int):
    """One (batch row, KV-head group) cell: dequant + masked attention.

    Block refs (layer axis dropped by its None block dim): q
    (1, block_kv, G, hd); payloads (1, S, block_kv, hd); scales
    (1, S, block_kv, 1). TPU tiling wants the last two block dims
    (divisible-by-8, 128-multiple-or-full), which is why KV rides in
    groups of ``block_kv`` and the head loop is unrolled here instead of
    gridded.
    """
    b = pl.program_id(0)
    nv = nv_ref[b]

    for h in range(block_kv):
        # Scales are per cache ROW (one f32 per (slot, head)), so they
        # commute past the hd-contraction: score[g,j] = (q . k8[j]) * ks[j],
        # and p @ (v8 * vs) = (p * vs^T) @ v8. Applying them post-dot means
        # the only VMEM temps are bf16 casts of the int8 payloads (int8
        # values are exactly representable in bf16) instead of f32
        # dequantized planes — that difference is what fits the kernel in
        # scoped VMEM at S ~ 1200.
        q = q_ref[0, h].astype(jnp.bfloat16)                     # (G, hd)
        k8 = kq_ref[0, :, h, :].astype(jnp.bfloat16)             # (S, hd)
        s = jax.lax.dot_general(
            q, k8, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (ks_ref[0, :, h].reshape(1, -1) * scale)             # (G, S)

        g, s_len = s.shape
        j = jax.lax.broadcasted_iota(jnp.int32, (g, s_len), 1)
        s = jnp.where(j < nv, s, NEG_INF)

        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(axis=-1, keepdims=True)
        pv = (p * vs_ref[0, :, h].reshape(1, -1)).astype(jnp.bfloat16)
        v8 = vq_ref[0, :, h, :].astype(jnp.bfloat16)             # (S, hd)
        o = jax.lax.dot_general(
            pv, v8, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) / jnp.maximum(l, 1e-30)
        o_ref[0, h] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_int8(
    q: jnp.ndarray,       # (B, KV, G, hd) post-RoPE queries
    k_q: jnp.ndarray,     # (L, B, S, KV, hd) int8
    k_s: jnp.ndarray,     # (L, B, S, KV, 1) f32
    v_q: jnp.ndarray,     # (L, B, S, KV, hd) int8
    v_s: jnp.ndarray,     # (L, B, S, KV, 1) f32
    li: jnp.ndarray,      # scalar int32 layer index
    n_valid: jnp.ndarray,  # (B,) int32 attendable slot count
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Returns (B, KV, G, hd) attention context in q.dtype."""
    b, kv, g, hd = q.shape
    _, _, s, _, _ = k_q.shape
    if interpret is None:
        interpret = pallas_interpret()
    scale = 1.0 / math.sqrt(hd)
    # KV-head group per grid cell: last-two block-dim tiling wants the KV
    # block divisible by 8 (or the full axis); 8 keeps VMEM per cell at
    # ~2.4 MB of int8 payload for S~1152.
    block_kv = 8 if kv % 8 == 0 else kv

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # (li, n_valid)
        grid=(b, kv // block_kv),
        in_specs=[
            pl.BlockSpec((1, block_kv, g, hd),
                         lambda bi, hi, li_r, nv_r: (bi, hi, 0, 0)),
            pl.BlockSpec((None, 1, s, block_kv, hd),
                         lambda bi, hi, li_r, nv_r: (li_r[0], bi, 0, hi, 0)),
            pl.BlockSpec((None, 1, s, block_kv, 1),
                         lambda bi, hi, li_r, nv_r: (li_r[0], bi, 0, hi, 0)),
            pl.BlockSpec((None, 1, s, block_kv, hd),
                         lambda bi, hi, li_r, nv_r: (li_r[0], bi, 0, hi, 0)),
            pl.BlockSpec((None, 1, s, block_kv, 1),
                         lambda bi, hi, li_r, nv_r: (li_r[0], bi, 0, hi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_kv, g, hd),
                               lambda bi, hi, li_r, nv_r: (bi, hi, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_decode_attn_kernel, scale=scale, block_kv=block_kv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
        # Double-buffered int8 blocks + per-head cast temps exceed the 16 MB
        # default scoped-VMEM budget at S ~ 1200; v5e has 128 MB VMEM.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024
        ),
    )(jnp.asarray(li, jnp.int32).reshape(1), jnp.asarray(n_valid, jnp.int32),
      q, k_q, k_s, v_q, v_s)


def decode_attention_int8_reference(q, k_q, k_s, v_q, v_s, li, n_valid):
    """Plain-XLA semantics twin (dequant-then-attend) for tests."""
    b, kv, g, hd = q.shape
    k = (k_q[li].astype(jnp.float32) * k_s[li])  # (B, S, KV, hd)
    v = (v_q[li].astype(jnp.float32) * v_s[li])
    s = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32), k) / math.sqrt(hd)
    mask = jnp.arange(k.shape[1])[None, None, None, :] < n_valid[:, None, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v)
    return o.astype(q.dtype)


# -- paged decode attention (ISSUE 12) ------------------------------------
#
# The serving cache becomes ONE block-pool arena (L, N, bs, KV, hd) plus
# per-row int32 block tables (serve.py kv_layout="paged"). The scheduler's
# CPU-tier fallback gathers the table into the dense (B, S, KV, hd) view
# inside the layer scan (models/llama._cache_read_layer — a per-layer
# TEMPORARY, 1/L of the dense cache's residency). This kernel is the TPU
# form of that read: attention runs block-by-block with a scalar-
# prefetched block table steering the BlockSpec index_map, an online-
# softmax accumulator carrying (m, l, acc) across the block axis — the
# dense view is never materialized at all, and HBM streams only the int8
# payload + scales of the blocks the row actually owns a table entry for.


def _paged_attn_kernel(li_ref, bt_ref, nv_ref, q_ref, kq_ref, ks_ref,
                       vq_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                       scale: float, block_kv: int, n_bpr: int):
    """One (row, head group, table entry) cell: dequant + one block's
    masked partial attention, folded into the running online-softmax
    state. Grid order is (b, hi, ni) with ni FASTEST, so the scratch
    (m, l, acc) carries exactly one (b, hi) cell's accumulation: ni == 0
    initializes it, ni == n_bpr - 1 normalizes into the output block
    (revisited across ni — it stays resident in VMEM)."""
    b = pl.program_id(0)
    ni = pl.program_id(2)
    nv = nv_ref[b]
    bs = kq_ref.shape[1]

    @pl.when(ni == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for h in range(block_kv):
        # Same post-dot scale placement as the dense kernel: bf16 casts
        # of int8 payloads are the only VMEM temps.
        q = q_ref[0, h].astype(jnp.bfloat16)                     # (G, hd)
        k8 = kq_ref[0, :, h, :].astype(jnp.bfloat16)             # (bs, hd)
        s = jax.lax.dot_general(
            q, k8, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (ks_ref[0, :, h].reshape(1, -1) * scale)             # (G, bs)

        g, _ = s.shape
        j = jax.lax.broadcasted_iota(jnp.int32, (g, bs), 1) + ni * bs
        s = jnp.where(j < nv, s, NEG_INF)

        m_prev = m_ref[h]                                        # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                                   # (G, bs)
        l_new = l_ref[h] * alpha + p.sum(axis=-1, keepdims=True)
        pv = (p * vs_ref[0, :, h].reshape(1, -1)).astype(jnp.bfloat16)
        v8 = vq_ref[0, :, h, :].astype(jnp.bfloat16)             # (bs, hd)
        acc = acc_ref[h] * alpha + jax.lax.dot_general(
            pv, v8, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[h] = m_new
        l_ref[h] = l_new
        acc_ref[h] = acc

    @pl.when(ni == n_bpr - 1)
    def _finalize():
        for h in range(block_kv):
            o_ref[0, h] = (acc_ref[h]
                           / jnp.maximum(l_ref[h], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_int8_paged(
    q: jnp.ndarray,        # (B, KV, G, hd) post-RoPE queries
    k_q: jnp.ndarray,      # (L, N, bs, KV, hd) int8 pool arena
    k_s: jnp.ndarray,      # (L, N, bs, KV, 1) f32 scales
    v_q: jnp.ndarray,      # (L, N, bs, KV, hd) int8
    v_s: jnp.ndarray,      # (L, N, bs, KV, 1) f32
    li: jnp.ndarray,       # scalar int32 layer index
    block_tables: jnp.ndarray,  # (B, n_bpr) int32 pool block per row slot
    n_valid: jnp.ndarray,  # (B,) int32 attendable LOGICAL slot count
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Returns (B, KV, G, hd) attention context in q.dtype — the paged
    twin of ``decode_attention_int8``: identical math over the blocks
    ``block_tables`` names, streaming only those blocks from HBM."""
    b, kv, g, hd = q.shape
    _, _, bs, _, _ = k_q.shape
    n_bpr = block_tables.shape[1]
    if interpret is None:
        interpret = pallas_interpret()
    scale = 1.0 / math.sqrt(hd)
    block_kv = 8 if kv % 8 == 0 else kv

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # (li, block_tables, n_valid)
        grid=(b, kv // block_kv, n_bpr),
        in_specs=[
            pl.BlockSpec((1, block_kv, g, hd),
                         lambda bi, hi, ni, li_r, bt_r, nv_r: (bi, hi, 0, 0)),
            pl.BlockSpec((None, 1, bs, block_kv, hd),
                         lambda bi, hi, ni, li_r, bt_r, nv_r:
                         (li_r[0], bt_r[bi, ni], 0, hi, 0)),
            pl.BlockSpec((None, 1, bs, block_kv, 1),
                         lambda bi, hi, ni, li_r, bt_r, nv_r:
                         (li_r[0], bt_r[bi, ni], 0, hi, 0)),
            pl.BlockSpec((None, 1, bs, block_kv, hd),
                         lambda bi, hi, ni, li_r, bt_r, nv_r:
                         (li_r[0], bt_r[bi, ni], 0, hi, 0)),
            pl.BlockSpec((None, 1, bs, block_kv, 1),
                         lambda bi, hi, ni, li_r, bt_r, nv_r:
                         (li_r[0], bt_r[bi, ni], 0, hi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_kv, g, hd),
                               lambda bi, hi, ni, li_r, bt_r, nv_r:
                               (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_kv, g, 1), jnp.float32),   # running max
            pltpu.VMEM((block_kv, g, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_kv, g, hd), jnp.float32),  # running context
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, scale=scale,
                          block_kv=block_kv, n_bpr=n_bpr),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024
        ),
    )(jnp.asarray(li, jnp.int32).reshape(1),
      jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(n_valid, jnp.int32),
      q, k_q, k_s, v_q, v_s)


def decode_attention_int8_paged_reference(q, k_q, k_s, v_q, v_s, li,
                                          block_tables, n_valid):
    """Plain-XLA twin: gather the table into the dense view (exactly the
    CPU-tier fallback ``models/llama._cache_read_layer`` runs), then the
    dense reference math."""
    kq = k_q[li][block_tables]  # (B, n_bpr, bs, KV, hd)
    ks = k_s[li][block_tables]
    vq = v_q[li][block_tables]
    vs = v_s[li][block_tables]

    def flat(x):
        return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])

    k = flat(kq).astype(jnp.float32) * flat(ks)
    v = flat(vq).astype(jnp.float32) * flat(vs)
    b, kv, g, hd = q.shape
    s = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32), k) / math.sqrt(hd)
    mask = jnp.arange(k.shape[1])[None, None, None, :] < \
        n_valid[:, None, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v)
    return o.astype(q.dtype)
