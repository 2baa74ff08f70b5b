"""Pallas TPU kernel for packed-int4 weight-only matmul (decode path).

Why a kernel at all: XLA will not fuse the nibble unpack of a packed int4
weight into the dot — it materializes the dequantized bf16 planes through
HBM, which makes plain-XLA int4 *slower* than int8 (measured 16.5 vs
70.3 tok/s at 7B batch-1 decode on v5e). Here the packed bytes stream
HBM -> VMEM once and the shift/mask/scale dequant happens in VMEM
feeding the MXU directly, so HBM traffic is 0.5 bytes/weight — half of
int8's, on the path where tokens/sec is weight-bytes/bandwidth.

Layout contract matches ``ops/quant.quantize_tensor4``: byte ``[r, n]``
holds logical contraction rows ``2r`` (high nibble) and ``2r+1`` (low
nibble), offset-binary (value + 8); group scales ``s[g, n]`` cover
``group`` logical rows. The even/odd split means the kernel never
interleaves: ``x @ W = x_even @ hi + x_odd @ lo`` with both planes plain
shift/masks of the block bytes.

Grid: ``(N / BLOCK_N, HK / BLOCK_KP)`` with the packed-row dimension
innermost; the f32 output block is revisited across the K steps and
accumulates in VMEM (init at the first step).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from eventgpt_tpu.utils.platform import pallas_interpret

BLOCK_N = 256
BLOCK_KP = 128  # packed rows per step = 256 logical contraction rows


def _int4_kernel(xe_ref, xo_ref, w_ref, s_ref, out_ref, *, half_group: int,
                 groups_per_step: int):
    """One (n-block, k-step) cell.

    xe/xo_ref: (B, BKP) bf16 — even/odd logical rows of x for this k step.
    w_ref: (BKP, BN) uint8 packed. s_ref: (GB, BN) f32 — this step's group
    scales (the host reshapes scales to (k_steps, GB, N) so the block's
    trailing dims equal full array dims, satisfying the sublane tiling rule
    that a raw (GB, BN) block of a (Gc, N) array would break).
    out_ref: (B, BN) f32 accumulator.
    """
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    # Offset-binary nibbles -> centered int -> bf16 (exact: int4 values are
    # integers <= 8, representable in bf16 losslessly). Bit ops run at i32
    # (Mosaic cannot legalize sub-word shifts: 'arith.shrui' on vector<i8>).
    w = w_ref[:].astype(jnp.int32)
    bkp, bn = w.shape
    gb = groups_per_step
    hi = ((w >> 4) - 8).astype(jnp.bfloat16).reshape(gb, half_group, bn)
    lo = ((w & 0xF) - 8).astype(jnp.bfloat16).reshape(gb, half_group, bn)

    # f32 group scales applied to f32 per-group dot partials — numerically
    # IDENTICAL to the XLA fallback (ops/quant.py:_matmul4). The previous
    # form pre-scaled bf16 nibbles by bf16-cast scales: two roundings whose
    # error depended on shape alignment (kernel vs fallback divergence,
    # ADVICE r2). The MXU still sees pure-integer bf16 operands.
    b = xe_ref.shape[0]
    xe = jnp.swapaxes(xe_ref[:].reshape(b, gb, half_group), 0, 1)  # (gb,B,hg)
    xo = jnp.swapaxes(xo_ref[:].reshape(b, gb, half_group), 0, 1)
    dims = (((2,), (1,)), ((0,), (0,)))
    part = jax.lax.dot_general(xe, hi, dims,
                               preferred_element_type=jnp.float32)
    part += jax.lax.dot_general(xo, lo, dims,
                                preferred_element_type=jnp.float32)
    out_ref[:] += jnp.sum(part * s_ref[:][:, None, :], axis=0)


def supported(k: int, n: int, group: int) -> bool:
    """Shape-alignment gate for the kernel; callers fall back to the XLA
    path otherwise (small/tiny-model dims)."""
    hk = k // 2
    return (
        k % 2 == 0
        and n % BLOCK_N == 0
        and hk % BLOCK_KP == 0
        and group % 2 == 0
        and (group // 2) <= BLOCK_KP
        and BLOCK_KP % (group // 2) == 0
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def int4_matmul(x: jnp.ndarray, q4: jnp.ndarray, s: jnp.ndarray,
                interpret: bool | None = None) -> jnp.ndarray:
    """x (B, K) @ packed-int4 weight -> (B, N) f32.

    q4: (K/2, N) uint8, s: (Gc, N) f32 — the ``quantize_tensor4`` layout.
    """
    if interpret is None:
        interpret = pallas_interpret()
    b, k = x.shape
    hk, n = q4.shape
    gc = s.shape[0]
    group = k // gc
    half_group = group // 2

    xb = x.astype(jnp.bfloat16).reshape(b, hk, 2)
    xe, xo = xb[..., 0], xb[..., 1]

    grid = (n // BLOCK_N, hk // BLOCK_KP)
    gb = BLOCK_KP // half_group  # groups per k step
    s_steps = s.reshape(grid[1], gb, n)

    out = pl.pallas_call(
        functools.partial(_int4_kernel, half_group=half_group,
                          groups_per_step=gb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, BLOCK_KP), lambda j, ki: (0, ki),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((b, BLOCK_KP), lambda j, ki: (0, ki),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((BLOCK_KP, BLOCK_N), lambda j, ki: (ki, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, gb, BLOCK_N), lambda j, ki: (ki, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((b, BLOCK_N), lambda j, ki: (0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=interpret,
    )(xe, xo, q4, s_steps)
    return out
