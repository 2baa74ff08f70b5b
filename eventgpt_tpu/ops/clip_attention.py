"""Pallas TPU attention for the event tower (CLIP ViT-L/14-336).

``clip_attention(q, k, v, heads)``: non-causal softmax attention over whole
frames, ``softmax(q k^T) v`` for every head, in the layout the tower's
projections produce: q, k and v (N, S, H * hd), q already scaled, the
result (N, S, H * hd) in the operands' type. At the tower's shapes a frame
is 577 tokens of 16 heads x 64.

``xla_attention`` is the same arithmetic through XLA: the scores of
every head as one float32 tensor (N, H, S, S) in HBM (16 x 577 x 577 x 4 B
= 21.3 MB a frame a layer), written, read back by the softmax, written
again in the operands' type and read by the second product. The kernel
keeps a head's scores in VMEM: a grid cell is one frame and one block of
``BLOCK_LANES`` lanes (eight heads of 64), and reads that block of q, k and
v whole, the 577 rows of each (a block's row axis may be the whole axis
whatever its length), so no key is padded and no softmax runs online: one
plain softmax a head over all of its keys. The cell takes its lanes 128 at
a time, a pair of heads, and a head's 64 lanes are picked from them by a
select and not by a slice: its product with K then contracts 128 lanes, of
which the other head's are zero, and its product with V computes 128
lanes, of which it keeps its own 64. The MXU is 128 wide, so either costs
what 64 lanes would. Nothing is transposed in HBM.

The arithmetic is the XLA form's: operands in their type, the scores and
the softmax in float32, the probabilities cast to the operands' type before
the product with V, which accumulates in float32. Only the order of the
sums may differ. The call is named ``clip_attention`` on a device trace
(its time lies under the scope ``tower``); it is neither of the causal
kernels of ``ops/flash_attention.py``, which ``flash_roofline`` and
``flash_window_roofline`` price by their names. Differentiable: the
backward pass is the XLA form's (the tower is frozen: a train step's
gradient stops at its output). Where the CPU was asked for the kernel runs
in interpreter mode (``utils/platform.pallas_interpret``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from eventgpt_tpu.utils.platform import pallas_interpret

LANES = 128
# A grid cell's lanes at most, where whole heads fill 128: a cell takes the
# largest of 128, 256 and 512 that divides the width (512 of 1,024, 256 of
# 768, 128 of 640), 128 lanes at a time. A layer's
# attention at 20 frames (a wave of 4 requests) took 0.555 / 0.512 / 0.491 /
# 0.483 ms at 128 / 256 / 512 / 1,024 lanes on a v5e, the XLA form 1.540;
# 1,024 lanes (16 heads unrolled) took twice as long to compile the tower,
# 12.0 s against 6.2 at 512 (compiled for a described v5e).
BLOCK_LANES = 512


def xla_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  heads: int) -> jnp.ndarray:
    """The XLA form: q, k, v (N, S, H * hd), q scaled -> (N, S, H * hd)."""
    b, s, d = q.shape
    q, k, v = (x.reshape(b, s, heads, d // heads) for x in (q, k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, d)


def block_lanes(heads: int, head_dim: int) -> tuple:
    """A grid cell's lanes and the chunk of them whose heads are computed
    together. Where whole heads fill 128 lanes and the width is a multiple
    of 128: the width's greatest common divisor with ``BLOCK_LANES`` (a
    power of two times 128), in chunks of 128, so the cells tile the width;
    else the whole width as one chunk (toy widths)."""
    width = heads * head_dim
    if width % LANES or LANES % head_dim:
        return width, width
    return math.gcd(width, BLOCK_LANES), LANES


def _heads(q, k, v, head_dim: int):
    """q, k, v (S, C), C / head_dim heads side by side -> (S, C) float32."""
    # A bfloat16 product is exact in one pass, whatever precision the
    # caller's context asks for (Mosaic refuses more passes of bfloat16).
    precision = lax.Precision.DEFAULT if q.dtype == jnp.bfloat16 else None
    lane = lax.broadcasted_iota(jnp.int32, (1, q.shape[1]), 1) // head_dim
    out = None
    for h in range(q.shape[1] // head_dim):
        mine = lane == h
        scores = lax.dot_general(
            jnp.where(mine, q, 0), k, (((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        p = jnp.exp(scores - jnp.max(scores, axis=1, keepdims=True))
        probs = (p / jnp.sum(p, axis=1, keepdims=True)).astype(v.dtype)
        ctx = jnp.dot(probs, v, precision=precision,
                      preferred_element_type=jnp.float32)
        out = ctx if out is None else jnp.where(mine, ctx, out)
    return out


def _kernel(q_ref, k_ref, v_ref, out_ref, *, head_dim: int, chunk: int):
    """One (frame, lane block) cell: q_ref, k_ref, v_ref, out_ref (S, W),
    a chunk of lanes (whole heads) at a time."""
    for c in range(0, q_ref.shape[1], chunk):
        lanes = slice(c, c + chunk)
        out_ref[:, lanes] = _heads(q_ref[:, lanes], k_ref[:, lanes],
                                   v_ref[:, lanes], head_dim
                                   ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _forward(q, k, v, heads: int, interpret: bool | None = None):
    if interpret is None:
        interpret = pallas_interpret()
    n, s, d = q.shape
    lanes, chunk = block_lanes(heads, d // heads)
    spec = pl.BlockSpec((None, s, lanes), lambda f, c: (f, 0, c))
    return pl.pallas_call(
        functools.partial(_kernel, head_dim=d // heads, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((n, s, d), q.dtype),
        grid=(n, d // lanes),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        # The kernel's name on a device trace.
        name="clip_attention",
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def clip_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   heads: int) -> jnp.ndarray:
    """q, k, v (N, S, H * hd), q scaled -> (N, S, H * hd) in q's type."""
    return _forward(q, k, v, heads)


def _fwd(q, k, v, heads):
    return _forward(q, k, v, heads), (q, k, v)


def _bwd(heads, res, g):
    _, pullback = jax.vjp(
        lambda q, k, v: xla_attention(q, k, v, heads), *res)
    return pullback(g)


clip_attention.defvjp(_fwd, _bwd)
