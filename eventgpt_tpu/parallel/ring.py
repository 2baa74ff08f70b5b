"""Ring attention: sequence/context parallelism over the ``context`` mesh axis.

The reference has no long-context story at all — context is hard-capped at
2048 (``model/EventChatModel.py:378``) and no sequence parallelism exists
anywhere in its stack (SURVEY.md §2.4). This module is the designed-in
escape hatch: Q/K/V are sharded along the sequence axis over the ``context``
mesh axis; each device computes blockwise attention against its local KV
chunk while KV blocks rotate around the ring via ``lax.ppermute`` (one ICI
hop per step), with flash-style online-softmax accumulation so the full
score matrix never materializes. Compute on step i overlaps the transfer
for step i+1 (XLA schedules the ppermute DMA concurrently with the matmuls).

Causality is enforced with *global* positions, so results are bit-compatible
with dense causal attention up to f32 summation order.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh


def _ring_attention_local(
    q: jnp.ndarray,       # (B, Sq, H, hd)  local query chunk
    k: jnp.ndarray,       # (B, Sk, H, hd)  local key chunk (start of ring)
    v: jnp.ndarray,       # (B, Sk, H, hd)
    q_valid: jnp.ndarray,  # (B, Sq) bool — padding mask for local queries
    kv_valid: jnp.ndarray,  # (B, Sk) bool
    axis_name: str,
    causal: bool = True,
) -> jnp.ndarray:
    """Per-shard body (inside shard_map): online-softmax over ring steps."""
    axis_size = lax.psum(1, axis_name)
    axis_idx = lax.axis_index(axis_name)

    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    q_pos = axis_idx * sq + jnp.arange(sq)  # global query positions

    neg = jnp.finfo(jnp.float32).min
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def step(i, carry):
        o, m, l, k_cur, v_cur, kvv_cur = carry
        # Chunk currently held arrived from device (axis_idx - i) mod n.
        src = (axis_idx - i) % axis_size
        k_pos = src * sk + jnp.arange(sk)

        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_cur,
                       preferred_element_type=jnp.float32) * scale
        valid = kvv_cur[:, None, None, :]
        if causal:
            valid = valid & (k_pos[None, None, None, :] <= q_pos[None, None, :, None])
        s = jnp.where(valid, s, neg)

        m_new = jnp.maximum(m, s.max(axis=-1))
        # exp(neg - m_new) underflows to 0 for fully-masked rows.
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_cur.dtype), v_cur,
                        preferred_element_type=jnp.float32)
        o_new = o * corr.transpose(0, 2, 1)[..., None] + pv

        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        kvv_nxt = lax.ppermute(kvv_cur, axis_name, perm)
        return o_new, m_new, l_new, k_nxt, v_nxt, kvv_nxt

    # Fresh zeros are "unvarying" under shard_map's manual-axes typing while
    # the loop outputs vary per device; pcast marks them explicitly.
    from eventgpt_tpu.parallel.mesh import AXES

    def _vary(x):
        return lax.pcast(x, AXES, to="varying")

    o0 = _vary(jnp.zeros((b, sq, h, hd), jnp.float32))
    m0 = _vary(jnp.full((b, h, sq), neg, jnp.float32))
    l0 = _vary(jnp.zeros((b, h, sq), jnp.float32))
    o, m, l, _, _, _ = lax.fori_loop(
        0, axis_size, step, (o0, m0, l0, k, v, kv_valid)
    )
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 2, 1)[..., None]
    out = jnp.where(q_valid[:, :, None, None], out, 0.0)
    return out.astype(q.dtype)


def ring_self_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    valid: Optional[jnp.ndarray] = None,
    causal: bool = True,
    axis_name: str = "context",
) -> jnp.ndarray:
    """Sequence-parallel causal attention over ``mesh``'s ``context`` axis.

    Shapes (global): q/k/v (B, S, H, hd); S must divide by the context axis
    size. ``valid`` (B, S) marks real tokens (None -> all real). Batch
    shards over (data, fsdp), heads over model, sequence over context.
    """
    b, s, h, hd = q.shape
    if valid is None:
        valid = jnp.ones((b, s), bool)
    return _ring_jitted(mesh, causal, axis_name)(q, k, v, valid, valid)


def ring_attention_shard_map(mesh: Mesh, causal: bool = True,
                             axis_name: str = "context"):
    """Un-jitted shard_map over the ring body: ``f(q, k, v, q_valid,
    kv_valid) -> out``. This is the form model code calls *inside* its own
    jit (``models/llama.py`` when ``attn_impl == 'ring'``); shard_map
    composes with the surrounding GSPMD partitioning."""
    from eventgpt_tpu.parallel.sp_common import SP_QKV_SPEC, SP_VALID_SPEC

    return jax.shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(SP_QKV_SPEC, SP_QKV_SPEC, SP_QKV_SPEC,
                  SP_VALID_SPEC, SP_VALID_SPEC),
        out_specs=SP_QKV_SPEC,
    )


@functools.lru_cache(maxsize=32)
def _ring_jitted(mesh: Mesh, causal: bool, axis_name: str):
    """One jitted shard_map per (mesh, causal, axis) — rebuilding it per call
    would retrace and recompile on every invocation."""
    return jax.jit(ring_attention_shard_map(mesh, causal, axis_name))


def dense_reference_attention(q, k, v, valid=None, causal=True):
    """Unsharded reference implementation (tests / single chip)."""
    b, s, h, hd = q.shape
    if valid is None:
        valid = jnp.ones((b, s), bool)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    mask = valid[:, None, None, :]
    if causal:
        pos = jnp.arange(s)
        mask = mask & (pos[None, None, None, :] <= pos[None, None, :, None])
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = jnp.where(valid[:, :, None, None], out, 0.0)
    return out.astype(q.dtype)
