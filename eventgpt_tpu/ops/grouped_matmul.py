"""Pallas TPU grouped matrix product for the sparse decoders' experts.

``gmm(x, w, sizes)``: the rows of ``x`` (A, in) are sorted by group, group
``e`` holding ``sizes[e]`` consecutive rows, and each group's rows are
multiplied by its own matrix ``w[e]`` (in, out). The result (A, out) is in
the operands' type, each product accumulated in float32: what
``lax.ragged_dot(x, w, sizes)`` computes, for the rows the groups cover.

``lax.ragged_dot`` on a TPU works in row tiles of 512 and pays a whole tile
for every (tile, group) pair it visits, so at Trinity's prefill (~6,100
held rows in 22-28 touched groups of 32) the MXU does about 3.3 times the
held rows' work (PERF.md section 7, fault 10). Here a row tile is
``ROW_TILE`` = 128 rows, the width of a v5e's MXU, and the grid runs over
(out tile, visit): a visit is one (row tile, group) pair that holds a row of
the group, listed in row order, so a tile that two groups share is visited
twice in a row and its output block stays in VMEM between the two (each
visit stores its own group's rows only). The whole ``in`` width is one
block, so the weight block's index stays the same across a group's
consecutive row tiles and Pallas skips its copy: the weights are read once
per (group, out tile), as ``ragged_dot`` reads them, and the rows once per
out tile. Rows past ``sum(sizes)`` are never visited, and what the output
holds there is undefined; so is a row of a tile that no group holds.

The out tile is the widest whole number of 128 lanes that divides ``out``
and keeps a weight block within a budget, a smaller one where the call is a
single row tile (``out_tile``; all of ``out`` where it is not a multiple of
128), and the call asks for the VMEM of its blocks alone (``vmem_bytes``).
``tiles(sizes)`` counts the visits from the sizes alone (``models/experts.py``
reports it, whichever product runs). Where the CPU was asked for the kernel
runs in interpreter mode, like ``ops/flash_attention.py``
(``utils/platform.pallas_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from eventgpt_tpu.utils.platform import pallas_interpret

ROW_TILE = 128
# The most bytes of one weight block (in x out tile), double buffered with
# the row and output blocks in the scoped VMEM the call asks for
# (``vmem_bytes``). Alone, a wider block reads the rows fewer times: at
# Trinity's prefill slab (12,288 rows, 3,072 -> 3,072, ~6,100 held rows in 25
# groups) an out tile of 3,072 / 1,536 / 1,024 / 512 took 1.260 / 1.276 /
# 1.297 / 1.347 ms a product against ``ragged_dot``'s 2.322 on a v5e (PERF.md
# section 6). In the program, the VMEM a call asks for XLA cannot keep its
# own arrays in: asking 64 MB sent every product's output of Trinity's
# prefill to HBM, and gathering the down products' back to the tokens took
# 2.5 ms a layer where it takes a fraction of one from VMEM (compiled for a
# v5e: 0 of 24 outputs in VMEM at 64 MB, 16 at 56 or less). So a call asks
# for its blocks alone, and 6 MB a weight block keeps that to 19 MB (1,024
# at Trinity, the whole 2,688 / 1,024 at the hybrid's 1,024 -> 2,688 ->
# 1,024).
# A call of one row tile reads no row twice, and there the last visit's
# product, which no copy hides, grows with the block: Trinity's decode step
# (128 rows) took 0.159 / 0.161 / 0.169 ms at 512 / 1,024 / 3,072,
# ``ragged_dot`` 0.210; 512 asks 11 MB of the decode program's VMEM.
WEIGHT_BLOCK_BYTES = 6 * 1024 * 1024
ONE_TILE_WEIGHT_BYTES = 3 * 1024 * 1024
LANES = 128


def _bounds(sizes, tm: int):
    """Each group's first row tile and the row tile past its last (0 and 0
    for an empty group)."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    held = sizes > 0
    return (jnp.where(held, starts // tm, 0),
            jnp.where(held, (ends + tm - 1) // tm, 0))


def tiles(sizes, tm: int = ROW_TILE):
    """The (row tile, group) pairs that hold a row of the group: ``sizes``
    (E,) int32 -> () int32."""
    first, past = _bounds(sizes, tm)
    return jnp.sum(past - first).astype(jnp.int32)


def _visits(sizes, rows: int, tm: int):
    """The grid's visits in row order: (group, row tile) of each, (V,)
    int32 each where ``V = rows / tm + E - 1`` bounds them, and how many of
    them are real. A place past the real ones repeats a valid index."""
    e = sizes.shape[0]
    first, past = _bounds(sizes, tm)
    per_group = past - first
    ends = jnp.cumsum(per_group)
    v = jnp.arange(rows // tm + e - 1, dtype=jnp.int32)
    # A visit's group: how many groups' visits end at or before it. A
    # compare and a sum over (V, E) ints, no search.
    group = jnp.minimum(jnp.sum(ends[None, :] <= v[:, None], axis=1,
                                dtype=jnp.int32), e - 1)
    tile = first[group] + v - (ends[group] - per_group[group])
    return (group, jnp.clip(tile, 0, rows // tm - 1),
            ends[-1].astype(jnp.int32))


def out_tile(rows: int, d_in: int, d_out: int, itemsize: int) -> int:
    """The out columns of a block: the widest multiple of 128 that divides
    ``d_out`` with a weight block of at most ``WEIGHT_BLOCK_BYTES``, or
    ``ONE_TILE_WEIGHT_BYTES`` where the call's rows are one row tile."""
    if d_out % LANES:
        return d_out
    budget = (ONE_TILE_WEIGHT_BYTES if rows <= ROW_TILE
              else WEIGHT_BLOCK_BYTES)
    fits = [n for n in range(LANES, d_out + 1, LANES)
            if d_out % n == 0 and d_in * n * itemsize <= budget]
    return max(fits) if fits else LANES


def vmem_bytes(d_in: int, tn: int, itemsize: int, tm: int = ROW_TILE) -> int:
    """The scoped VMEM a call asks for: its row, weight and output blocks,
    each double buffered, and the float32 product, with a quarter over, in
    whole MiB. No more: what the call leaves, XLA may keep other arrays in
    (the down product's output, which the layer gathers back to the tokens,
    among them)."""
    need = (2 * (tm * d_in + d_in * tn + tm * tn) * itemsize
            + tm * tn * (4 + itemsize))
    return -(-need * 5 // 4 // 2 ** 20) * 2 ** 20


def _gmm_kernel(offsets_ref, groups_ref, tiles_ref, x_ref, w_ref, out_ref, *,
                tm: int):
    """One (out tile, visit) cell: x_ref (tm, in), w_ref (in, tn) of the
    visit's group, out_ref (tm, tn); the tile's rows of that group are
    stored, every other row of the block is left as it is."""
    v = pl.program_id(1)
    g = groups_ref[v]
    row = tiles_ref[v] * tm + lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    # A bfloat16 product is exact in one pass, whatever precision the
    # caller's context asks for (Mosaic refuses more passes of bfloat16).
    precision = (lax.Precision.DEFAULT if x_ref.dtype == jnp.bfloat16
                 else None)
    product = jnp.dot(x_ref[...], w_ref[...], precision=precision,
                      preferred_element_type=jnp.float32)
    out_ref[...] = jnp.where(mine, product.astype(out_ref.dtype), out_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def gmm(
    x: jnp.ndarray,       # (A, in), rows sorted by group
    w: jnp.ndarray,       # (E, in, out)
    sizes: jnp.ndarray,   # (E,) int32, summing to at most A
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(A, out) in the operands' type: row ``r`` of group ``e`` is ``x[r] @
    w[e]``; rows past ``sum(sizes)`` hold nothing that may be read."""
    if interpret is None:
        interpret = pallas_interpret()
    dtype = jnp.result_type(x.dtype, w.dtype)
    x, w = x.astype(dtype), w.astype(dtype)
    a, d_in = x.shape
    e, _, d_out = w.shape
    tm = ROW_TILE
    pad = -a % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    rows = a + pad
    tn = out_tile(rows, d_in, d_out, jnp.dtype(dtype).itemsize)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(sizes).astype(jnp.int32)])
    groups, row_tiles, n = _visits(sizes.astype(jnp.int32), rows, tm)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((rows, d_out), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # A call whose groups are all empty visits one tile and stores
            # nothing: the grid is never empty.
            grid=(d_out // tn, jnp.maximum(n, 1)),
            in_specs=[
                pl.BlockSpec((tm, d_in), lambda j, v, o, g, t: (t[v], 0)),
                pl.BlockSpec((None, d_in, tn),
                             lambda j, v, o, g, t: (g[v], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, o, g, t: (t[v], j)),
        ),
        interpret=interpret,
        # The kernel's name on a device trace.
        name="expert_gmm",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(d_in, tn, jnp.dtype(dtype).itemsize),
        ),
    )(offsets, groups, row_tiles, x, w)
    return out[:a] if pad else out
