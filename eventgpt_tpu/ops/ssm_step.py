"""Pallas TPU kernel for one decode step of the Mamba-2 recurrent state.

A decode step of an ``M`` layer (``models/nemotron_h._mamba_step``) turns a
plane of the stacked state ``h_buf`` (planes, B, H, P, N) float32 into

    h' = h * decay[..., None, None] + xdt[..., None] * B[:, group, None, None, :]
    y  = sum_n h' * C[:, group, None, None, :]

(a head belongs to group ``head // (H / G)``). As plain XLA that is three
passes over the plane, the largest array a step touches (268 MB a plane as
served): the update reads it and writes it back into the stacked buffer,
and the sum over ``n`` reads what was just written. Here a block of one
row's heads is loaded once, updated and summed while it is in VMEM, and
stored once: the kernel receives the FULL stacked buffer, the block's
``index_map`` picks the plane at a static index (as
``ops/decode_attention.py`` picks a layer of the stacked cache) and
``input_output_aliases`` hands the buffer back, so no plane is sliced,
copied or re-stacked. Every value is float32 and the arithmetic is the
expression above, term for term; a row with ``decay = 1`` and ``xdt = 0``
(a row that is not live) keeps its state bit for bit.

Both relayouts (``decay`` and ``xdt`` spread over the lanes, ``y`` summed
over them) are left to Mosaic: on a TPU v5e a call takes what a kernel
that only copies the plane takes (0.85 against 0.84 ms as served; PERF.md
section 6, PR 32), so the DMA sets the pace and not they.

A plane whose trailing dimensions cannot be tiled (``N`` not a multiple of
128 lanes, ``P`` not of 8 sublanes: the rehearsal's toy widths) takes the
plain expression, ``ssm_step_reference``, which is also the twin the tests
hold the kernel to. Where the CPU was asked for the kernel runs in
interpreter mode, like ``ops/flash_attention.py``
(``utils/platform.pallas_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from eventgpt_tpu.utils.platform import pallas_interpret

# The most bytes of ``h`` one grid cell holds: in and out, each double
# buffered, four such blocks lie in VMEM. On a v5e 0.5, 1, 2 and 4 MB read
# 0.973, 0.869, 0.850 and 0.853 ms a call as served (PERF.md section 6, PR 32).
BLOCK_BYTES = 2 * 1024 * 1024


def ssm_step_reference(h_buf, i: int, decay, xdt, b, c):
    """The plain expression: (``h_buf`` with plane ``i`` stepped, y (B, H, P))."""
    _, bsz, heads, p, n = h_buf.shape
    g = b.shape[1]
    r = heads // g
    hg = h_buf[i].reshape(bsz, g, r, p, n)
    hg = hg * decay.reshape(bsz, g, r)[..., None, None] \
        + xdt.reshape(bsz, g, r, p)[..., None] * b[:, :, None, None, :]
    y = jnp.sum(hg * c[:, :, None, None, :], axis=-1)
    return h_buf.at[i].set(hg.reshape(bsz, heads, p, n)), y.reshape(xdt.shape)


def tileable(p: int, n: int) -> bool:
    """Whether a head's (P, N) lies on whole (8, 128) float32 tiles."""
    return p % 8 == 0 and n % 128 == 0


def _groups_per_block(g: int, r: int, p: int, n: int) -> int:
    """Groups a grid cell holds: the most that fit ``BLOCK_BYTES`` among the
    divisors of ``g`` whose heads fill whole sublane tiles of the (heads, P)
    blocks; a row's every group where none does."""
    fits = [gb for gb in range(1, g + 1)
            if g % gb == 0 and (gb * r) % 8 == 0
            and gb * r * p * n * 4 <= BLOCK_BYTES]
    return max(fits) if fits else g


def _ssm_step_kernel(h_ref, decay_ref, xdt_ref, b_ref, c_ref, h_out_ref,
                     y_ref, *, gb: int, r: int):
    """One (row, block of ``gb`` groups) cell. h_ref / h_out_ref
    (gb * r, P, N), plane and row dropped by their None block dims;
    decay_ref (decay spread over P), xdt_ref, y_ref (gb * r, P); b_ref, c_ref
    (G, N), the row's every group."""
    first = pl.program_id(1) * gb
    for j in range(gb):
        heads = slice(j * r, (j + 1) * r)
        b = b_ref[pl.ds(first + j, 1), :][None]                  # (1, 1, N)
        c = c_ref[pl.ds(first + j, 1), :][None]
        h = h_ref[heads] * decay_ref[heads][:, :, None] \
            + xdt_ref[heads][:, :, None] * b
        h_out_ref[heads] = h
        y_ref[heads] = jnp.sum(h * c, axis=-1)


@functools.partial(jax.jit, static_argnames=("i", "interpret"))
def ssm_step(
    h_buf: jnp.ndarray,   # (planes, B, H, P, N) float32, the stacked state
    i: int,               # the plane this layer steps
    decay: jnp.ndarray,   # (B, H)
    xdt: jnp.ndarray,     # (B, H, P)
    b: jnp.ndarray,       # (B, G, N)
    c: jnp.ndarray,       # (B, G, N)
    interpret: bool | None = None,
):
    """Returns (``h_buf`` with plane ``i`` stepped in place, y (B, H, P))."""
    _, bsz, heads, p, n = h_buf.shape
    g = b.shape[1]
    r = heads // g
    if not tileable(p, n):
        return ssm_step_reference(h_buf, i, decay, xdt, b, c)
    if interpret is None:
        interpret = pallas_interpret()
    gb = _groups_per_block(g, r, p, n)
    hb = gb * r
    plane = pl.BlockSpec((None, None, hb, p, n),
                         lambda bi, gi: (i, bi, gi, 0, 0))
    heads_of_row = pl.BlockSpec((None, hb, p), lambda bi, gi: (bi, gi, 0))
    groups_of_row = pl.BlockSpec((None, g, n), lambda bi, gi: (bi, 0, 0))
    return pl.pallas_call(
        functools.partial(_ssm_step_kernel, gb=gb, r=r),
        grid=(bsz, g // gb),
        in_specs=[plane, heads_of_row, heads_of_row, groups_of_row,
                  groups_of_row],
        out_specs=[plane, heads_of_row],
        out_shape=[jax.ShapeDtypeStruct(h_buf.shape, h_buf.dtype),
                   jax.ShapeDtypeStruct(xdt.shape, jnp.float32)],
        input_output_aliases={0: 0},
        interpret=interpret,
        # The kernel's name on a device trace.
        name="ssm_step",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # Four blocks of ``h`` and the cell's temporaries pass the 16 MB
            # default of scoped VMEM; a v5e has 128 MB.
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
    )(h_buf, jnp.broadcast_to(decay[:, :, None], xdt.shape), xdt, b, c)
