"""Pallas TPU flash attention for the prefill path.

The reference relies on flash-attn via pip on GPU (``requirements.txt:31``);
this is the TPU-native equivalent: a fused attention kernel that never
materializes the (S, S) score matrix in HBM. Per (batch*head, q-block) grid
cell, the kernel streams KV blocks through VMEM with online-softmax
accumulation in f32 (the flash recurrence), applying causal + padding masks
inline. Softmax statistics live in registers; the MXU sees one
(BLOCK_Q, hd) x (hd, BLOCK_K) and one (BLOCK_Q, BLOCK_K) x (BLOCK_K, hd)
matmul per step.

Two kernels. ``_flash_kernel`` keeps a head's whole K and V in fast memory and
loops over their blocks inside one grid cell: right for prompts of a few
thousand positions (every dense decoder's prefill). ``_flash_blocked_kernel``
takes K and V **in blocks through the grid** (a third, sequential grid axis
over key blocks, the online-softmax state in scratch between its steps), so
a head of any length fits, and takes a ``window``: query ``i`` sees keys
``j`` with ``0 <= i - j < window``, and the key axis of the grid covers only
the band (its blocks are bounded below by ``(q_start - window + 1) //
block_k`` as they are bounded above by the diagonal; a step outside the band
maps to a block that is already resident, moves nothing and computes
nothing). It reads K and V at their own head count (GQA: query head ``h``
reads head ``h // (H / KV)``), so nothing is repeated in HBM. Its
``pallas_call`` is named ``flash_window_forward`` where a window is given and
``flash_forward`` where the call is a full causal square, which is what
``benchmark/layer_metrics/flash_roofline.py`` prices under that name.

Where the CPU was asked for the kernel runs in interpreter mode (tests on
the CPU mesh; ``utils/platform.pallas_interpret`` decides, for every
kernel in ``ops/``); the dense path in ``models/llama.py`` remains the default until the
config opts in (``LlamaConfig.attn_impl = "flash"``).
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
# The blocked kernel's block of queries and of keys (positions): 14.0 / 20.0
# ms a banded / full call of 12,288 positions x 48 heads at 1024, against
# 17.0 / 27.7 at 512 and 33.6 / 59.8 at 256 (my chip run, PR 33).
BLOCK = 1024


def _flash_kernel(q_ref, k_ref, v_ref, valid_ref, out_ref, *,
                  block_k: int, causal: bool, scale: float):
    """One (batch*head, q-block) cell: stream KV blocks, online softmax.

    Shapes: q_ref (BQ, hd); k_ref/v_ref (S, hd); valid_ref (1, S) int32;
    out_ref (BQ, hd).
    """
    bq, hd = q_ref.shape
    s = k_ref.shape[0]
    q_start = pl.program_id(1) * bq

    q = q_ref[:].astype(jnp.float32) * scale
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    num_kv = s // block_k

    def body(kb, carry):
        acc, m, l = carry
        k_off = kb * block_k
        k_blk = k_ref[pl.ds(k_off, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(k_off, block_k), :].astype(jnp.float32)

        scores = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)

        k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        mask = valid_ref[0, pl.ds(k_off, block_k)][None, :] > 0
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        scores = jnp.where(mask, scores, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(scores, axis=1))
        p = jnp.exp(scores - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * corr[:, None] + pv
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, hd), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)

    if causal:
        # KV blocks strictly above the diagonal contribute nothing; bound the
        # loop at the last block this q-block can see.
        num_kv_eff = jax.lax.div(q_start + bq - 1, block_k) + 1
        num_kv_eff = jnp.minimum(num_kv_eff, num_kv)
    else:
        num_kv_eff = num_kv
    acc, m, l = jax.lax.fori_loop(0, num_kv_eff, body, (acc0, m0, l0))

    # Fully-masked rows (padding queries) have l == 0; emit zeros.
    l_safe = jnp.maximum(l, 1e-30)
    out_ref[:] = (acc / l_safe[:, None]).astype(out_ref.dtype)


def _band(q_start, block_q: int, block_k: int, window):
    """The first and the last block of keys a block of queries can see: the
    diagonal above, and below it the block of key ``q_start - window + 1``."""
    last = jax.lax.div(q_start + block_q - 1, block_k)
    if window is None:
        return 0, last
    return jax.lax.div(jnp.maximum(q_start - window + 1, 0), block_k), last


def _flash_blocked_kernel(q_ref, k_ref, v_ref, valid_ref, out_ref,
                          acc_ref, m_ref, l_ref, *, block_q: int,
                          block_k: int, window, scale: float):
    """One (batch*head, q-block, step) cell: step ``j`` of the key axis is
    key block ``first + j`` of the q-block's band. q_ref (BQ, hd); k_ref /
    v_ref (BK, hd), one block; valid_ref (1, BK) int32; out_ref (BQ, hd);
    scratch acc (BQ, hd), m and l (BQ, 1) float32, carried over the steps."""
    j = pl.program_id(2)
    q_start = pl.program_id(1) * block_q
    first, last = _band(q_start, block_q, block_k, window)
    kb = first + j

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(kb <= last)
    def _():
        scores = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (BQ, BK)
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = (valid_ref[...] > 0) & (k_pos <= q_pos)
        if window is not None:
            mask = mask & (q_pos - k_pos < window)
        scores = jnp.where(mask, scores, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        # A row with no visible key yet keeps m = NEG_INF: exp(0) = 1 a
        # masked score, which the mask takes out again.
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        # Fully-masked rows (padding queries) have l == 0; emit zeros.
        out_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block", "interpret"))
def _flash_blocked_forward(q, k, v, valid, window=None, block: int = BLOCK,
                           interpret: bool | None = None):
    """Causal attention with K and V blocked through the grid. q (B, S, H,
    hd); k, v (B, S, KV, hd), KV dividing H; ``window``: None, or the
    positions a query sees (itself included)."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    if interpret is None:
        interpret = pallas_interpret()
    block = min(block, ((s + 127) // 128) * 128)
    s_pad = ((s + block - 1) // block) * block
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
        valid = jnp.pad(valid, ((0, 0), (0, s_pad - s)))
    if window is not None and window >= s_pad:
        window = None  # the band is the whole causal square

    def to_bh(x):  # (B, S, N, hd) -> (B*N, S, hd)
        return x.transpose(0, 2, 1, 3).reshape(-1, s_pad, hd)

    n_blocks = s_pad // block
    steps = n_blocks if window is None else min(
        n_blocks, (window - 1 + block + block - 1) // block + 1)

    def kv_block(bh, qb, j):
        first, last = _band(qb * block, block, block, window)
        return jnp.minimum(first + j, last)

    def kv_map(bh, qb, j):
        return (bh // h) * kvh + (bh % h) // rep, kv_block(bh, qb, j), 0

    kernel = functools.partial(
        _flash_blocked_kernel, block_q=block, block_k=block, window=window,
        scale=1.0 / math.sqrt(hd))
    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_blocks, steps),
        in_specs=[
            pl.BlockSpec((None, block, hd), lambda bh, qb, j: (bh, qb, 0)),
            pl.BlockSpec((None, block, hd), kv_map),
            pl.BlockSpec((None, block, hd), kv_map),
            pl.BlockSpec((None, 1, block),
                         lambda bh, qb, j: (bh // h, 0, kv_block(bh, qb, j))),
        ],
        out_specs=pl.BlockSpec((None, block, hd),
                               lambda bh, qb, j: (bh, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block, hd), jnp.float32),
                        pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        # The kernel's name on a device trace: the banded call has its own
        # (benchmark/layer_metrics/flash_window_roofline.py); a full causal
        # square is what flash_roofline.py prices as ``flash_forward``.
        name="flash_forward" if window is None else "flash_window_forward",
    )(to_bh(q), to_bh(k), to_bh(v), valid.astype(jnp.int32)[:, None, :])
    out = out.reshape(b, h, s_pad, hd).transpose(0, 2, 1, 3)[:, :s]
    return jnp.where(valid[:, :s, None, None], out, 0)


def flash_attention_blocked(q, k, v, valid=None, window: int | None = None,
                            interpret: bool | None = None) -> jnp.ndarray:
    """Causal fused attention for long or windowed prompts (forward only).
    q (B, S, H, hd); k, v (B, S, KV, hd) at their own head count; ``valid``
    (B, S) bool padding mask; ``window``: query ``i`` sees keys ``j`` with
    ``0 <= i - j < window`` (None: every ``j <= i``). Returns (B, S, H, hd)
    in q.dtype; padded queries' rows are zero."""
    if valid is None:
        valid = jnp.ones(q.shape[:2], bool)
    return _flash_blocked_forward(q, k, v, valid, window=window,
                                  interpret=interpret)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    valid: jnp.ndarray | None = None,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused attention. q/k/v: (B, S, H, hd) with KV already head-repeated;
    ``valid``: (B, S) bool padding mask. Returns (B, S, H, hd) in q.dtype.

    Differentiable: the forward pass is the Pallas kernel; the backward pass
    recomputes attention densely (standard softmax-attention VJP) — at the
    2048-token parity envelope the (S, S) backward materialization matches
    what the reference's training path did anyway.

    S is padded to a block multiple internally; hd should be a multiple of
    128 for peak MXU utilization (LLaMA-7B: hd=128).
    """
    b, s, h, hd = q.shape
    if valid is None:
        valid = jnp.ones((b, s), bool)
    return _flash_vjp(q, k, v, valid, causal, block_q, block_k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_vjp(q, k, v, valid, causal, block_q, block_k, interpret):
    return _flash_forward(q, k, v, valid, causal, block_q, block_k, interpret)


def _flash_vjp_fwd(q, k, v, valid, causal, block_q, block_k, interpret):
    out = _flash_forward(q, k, v, valid, causal, block_q, block_k, interpret)
    return out, (q, k, v, valid)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, valid = res
    scale = 1.0 / math.sqrt(q.shape[-1])
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)

    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf,
                        preferred_element_type=jnp.float32) * scale
    mask = valid[:, None, None, :]
    if causal:
        pos = jnp.arange(s)
        mask = mask & (pos[None, None, None, :] <= pos[None, None, :, None])
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    # Zero fully-masked (padded-query) rows, matching the forward's zeroing.
    p = p * valid[:, None, :, None]

    dv = jnp.einsum("bhqk,bqhd->bkhd", p, gf, preferred_element_type=jnp.float32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", gf, vf, preferred_element_type=jnp.float32)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, kf,
                    preferred_element_type=jnp.float32) * scale
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf,
                    preferred_element_type=jnp.float32) * scale
    import numpy as _np

    dvalid = _np.zeros(valid.shape, dtype=jax.dtypes.float0)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dvalid


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def _flash_forward(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    valid: jnp.ndarray,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    b, s, h, hd = q.shape
    if interpret is None:
        interpret = pallas_interpret()

    # Pad to a common multiple so both the q-grid and the kv loop tile S
    # exactly (max() alone under-covers when neither block divides the other).
    unit = _lcm(block_q, block_k)
    s_pad = ((s + unit - 1) // unit) * unit
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s), (0, 0), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        valid = jnp.pad(valid, ((0, 0), (0, s_pad - s)))

    # (B, S, H, hd) -> (B*H, S, hd)
    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s_pad, hd)

    qh, kh, vh = to_bh(q), to_bh(k), to_bh(v)
    valid_i = jnp.repeat(valid.astype(jnp.int32), h, axis=0)[:, None, :]  # (B*H,1,S)

    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, causal=causal, scale=scale
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, s_pad // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, hd), lambda bh, qb: (bh, qb, 0)),
            pl.BlockSpec((None, s_pad, hd), lambda bh, qb: (bh, 0, 0)),
            pl.BlockSpec((None, s_pad, hd), lambda bh, qb: (bh, 0, 0)),
            pl.BlockSpec((None, 1, s_pad), lambda bh, qb: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, hd), lambda bh, qb: (bh, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, hd), q.dtype),
        interpret=interpret,
        # The kernel's name on a device trace
        # (benchmark/layer_metrics/flash_roofline.py finds it by this name).
        name="flash_forward",
    )(qh, kh, vh, valid_i)

    out = out.reshape(b, h, s_pad, hd).transpose(0, 2, 1, 3)[:, :s]
    # Zero padded-query rows (kv masking alone leaves them attending).
    return jnp.where(valid[:, :s, None, None], out, 0)
