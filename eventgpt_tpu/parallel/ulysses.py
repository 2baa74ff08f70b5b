"""Ulysses-style all-to-all sequence parallelism over the ``context`` axis.

The second context-parallel mode next to ring attention
(``parallel/ring.py``): instead of rotating KV blocks around a ring (one
ppermute per step, compute overlapping transfer), Ulysses re-shards with two
collectives — an all-to-all that trades the sequence shard for a HEAD shard
(each device ends up with the FULL sequence for H/C of the heads), a plain
local attention over the complete sequence, and an inverse all-to-all back
to sequence sharding. (DeepSpeed-Ulysses; the reference stack has neither
mode — SURVEY.md §2.4.)

Trade-off vs ring: Ulysses moves O(S·H·hd / C) twice per layer regardless of
the context size and runs attention as one dense local call (simple, fast
when heads are plentiful and ICI all-to-all is cheap — the v5e torus);
ring's traffic is comparable but pipelined across C steps, and it keeps
full-head locality (no H % C divisibility requirement). Both enforce
causality with global positions and are dense-equivalent up to f32
summation order; `LlamaConfig.attn_impl` picks "ring" or "ulysses".
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh


def _repeat_heads(x: jnp.ndarray, rep: int) -> jnp.ndarray:
    """(B, S, KV, hd) -> (B, S, KV*rep, hd) GQA head replication — the one
    canonical implementation lives in models/llama.py."""
    from eventgpt_tpu.models.llama import _repeat_kv

    return _repeat_kv(x, rep)


def _ulysses_attention_local(
    q: jnp.ndarray,        # (B, S/C, H, hd) local sequence chunk
    k: jnp.ndarray,        # (B, S/C, KV, hd) — UN-repeated GQA heads
    v: jnp.ndarray,
    q_valid: jnp.ndarray,   # (B, S/C) bool
    kv_valid: jnp.ndarray,  # (B, S/C) bool
    axis_name: str,
    causal: bool = True,
    inner: str = "flash",
) -> jnp.ndarray:
    """Per-shard body (inside shard_map): all-to-all -> full-sequence local
    attention on a head shard -> inverse all-to-all.

    GQA traffic (ADVICE r2): K/V cross the ICI with their NATIVE head count
    and are repeated to the query heads only AFTER the exchange — a
    pre-repeat would multiply all-to-all bytes by H/KV. The post-exchange
    repeat is exact when contiguous query-head blocks map to contiguous KV
    blocks (KV % C == 0 and (H/C) % rep == 0); otherwise the pre-repeat
    fallback keeps correctness on odd head splits.

    ``inner="flash"`` runs the blockwise Pallas kernel over the gathered
    sequence — O(S·block) forward memory instead of the dense (B,H,S,S)
    f32 score matrix (the long-context regime is this mode's whole
    purpose). ``inner="dense"`` keeps the materialized form.
    """
    ctx = lax.axis_size(axis_name)
    rep = q.shape[2] // k.shape[2]
    post_repeat = (
        rep > 1 and k.shape[2] % ctx == 0 and (q.shape[2] // ctx) % rep == 0
    )
    if rep > 1 and not post_repeat:
        k = _repeat_heads(k, rep)
        v = _repeat_heads(v, rep)

    # seq-shard -> head-shard: device j receives head block j over the FULL
    # sequence (chunks concatenate in axis order = global token order).
    qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kvv = lax.all_gather(kv_valid, axis_name, axis=1, tiled=True)  # (B, S)
    if post_repeat:
        # Query block [j*H/C, (j+1)*H/C) consumes exactly KV block
        # [j*KV/C, (j+1)*KV/C) under contiguous GQA mapping (head i -> kv
        # i // rep), so the local repeat reproduces the pre-repeat layout.
        kh = _repeat_heads(kh, rep)
        vh = _repeat_heads(vh, rep)

    b, s, hc, hd = qh.shape
    if inner == "flash":
        from eventgpt_tpu.ops.flash_attention import flash_attention

        out = flash_attention(qh, kh, vh, valid=kvv, causal=causal)
    else:
        scale = 1.0 / math.sqrt(hd)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh,
                            preferred_element_type=jnp.float32) * scale
        mask = kvv[:, None, None, :]
        if causal:
            pos = jnp.arange(s)
            mask = mask & (pos[None, None, None, :] <= pos[None, None, :, None])
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vh.dtype), vh,
                         preferred_element_type=jnp.float32).astype(q.dtype)

    # head-shard -> seq-shard (exact inverse exchange).
    out = lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)
    return jnp.where(q_valid[:, :, None, None], out, 0.0)


def ulysses_attention_shard_map(mesh: Mesh, causal: bool = True,
                                axis_name: str = "context",
                                inner: str = "flash"):
    """Un-jitted shard_map: ``f(q, k, v, q_valid, kv_valid) -> out`` with the
    same calling convention as ``ring_attention_shard_map`` — the form
    ``models/llama.py`` calls inside its own jit when
    ``attn_impl == "ulysses"``. LOCAL heads (H / model) must divide by the
    context size (heads re-shard across the axis); validated here at trace
    time so every caller gets the friendly error, not a shard_map failure.

    K/V may be passed with their native (un-repeated) GQA head count —
    ``accepts_unrepeated_kv`` advertises this to the caller; the repeat
    happens after the all-to-all (ICI bytes scale with KV, not H)."""
    from eventgpt_tpu.parallel.sp_common import SP_QKV_SPEC, SP_VALID_SPEC

    fn = jax.shard_map(
        functools.partial(_ulysses_attention_local, axis_name=axis_name,
                          causal=causal, inner=inner),
        mesh=mesh,
        in_specs=(SP_QKV_SPEC, SP_QKV_SPEC, SP_QKV_SPEC,
                  SP_VALID_SPEC, SP_VALID_SPEC),
        out_specs=SP_QKV_SPEC,
        # The Pallas flash kernel's out_shape carries no varying-mesh-axes
        # annotation; skip the vma check (the specs above pin the layout).
        check_vma=False,
    )

    def checked(q, k, v, q_valid, kv_valid):
        local_heads = q.shape[2] // mesh.shape["model"]
        ctx = mesh.shape[axis_name]
        if local_heads % max(ctx, 1):
            raise ValueError(
                f"ulysses re-shards heads over the context axis: "
                f"H/model = {local_heads} must divide by context={ctx} "
                f"(use ring attention otherwise)"
            )
        return fn(q, k, v, q_valid, kv_valid)

    checked.accepts_unrepeated_kv = True
    return checked


def ulysses_self_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    valid: Optional[jnp.ndarray] = None,
    causal: bool = True,
    axis_name: str = "context",
) -> jnp.ndarray:
    """Jitted convenience entry: global-shape q/k/v (B, S, H, hd); S must
    divide by the context axis and H by (context x model)."""
    b, s, h, hd = q.shape
    if valid is None:
        valid = jnp.ones((b, s), bool)
    return _ulysses_jitted(mesh, causal, axis_name)(q, k, v, valid, valid)


@functools.lru_cache(maxsize=32)
def _ulysses_jitted(mesh: Mesh, causal: bool, axis_name: str):
    return jax.jit(ulysses_attention_shard_map(mesh, causal, axis_name))
