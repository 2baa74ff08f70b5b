"""One sparse expert layer for every decoder that has one.

``s = sigmoid(y W_r)`` in float32 over every expert of the deployment; the
``top_k`` largest of ``s + bias`` (the bias chooses only); weights ``s`` at
the chosen, divided by their sum where ``normalise``, times ``scale``. Or,
where the router's form is ``softmax_topk``: the ``top_k`` largest logits
``y W_r`` and a softmax over those, times ``scale``. The result is the
weighted sum of the chosen experts **over the experts held here**
(``offset .. offset + held``: expert parallelism without its exchange, what
the absent experts would add is left out) plus one shared expert on the
full width.

What differs between the decoders is told by what a caller hands over, not
by a flag: an expert whose weights have a ``gate`` is ``down(silu(gate x) *
up x)``, one without is ``down(relu(up x)^2)``; a ``latent`` pair of
projections wraps the routed experts (``models/nemotron_h.py``) or does not
(``models/afmoe.py``). The two forms of the held experts' product, and the
token count ``dense_up_to`` that parts them, are the caller's to choose at
its own load (PERF.md): up to it, one batched product over every held
expert, each token weighted 0 where it did not choose the expert; above it,
a grouped product over the assignments sorted by expert: on the chip the
Pallas kernel ``ops/grouped_matmul.gmm`` (row tiles of 128), on the CPU
``lax.ragged_dot`` (``_product``).

**The grouped product's capacity.** The sort puts the assignments that fall
on held experts first, and of a prompt's they are few (``held`` of the
router's width, were the router even). So the grouped branch gathers,
multiplies and returns ``capacity`` sorted rows at a time and not all ``T *
top_k``: ``CAPACITY_FACTOR`` times that even share, in whole row tiles
(``ROW_TILE``), read from the shapes of the call. The result is exact for
every routing: a loop runs as many such slabs as the held assignments fill
(one, wherever they number at most the capacity; none is dropped where they
number more), each slab's groups being the held experts' counts clipped to
it. A call whose capacity is half its assignments or more (a decode step, a
decoder that holds every expert or a quarter of them) has nothing to cut and
compiles to the one grouped product over all of them.

``STATS`` is what a layer counts in one call over the tokens that are real
(prefill) or live (decode); a decoder stacks them over its expert layers as
``cache["moe_stats"]`` so that they leave the device with the caller's other
outputs.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from eventgpt_tpu.ops.grouped_matmul import ROW_TILE, gmm, tiles
from eventgpt_tpu.ops.quant import matmul as _mm, matmul_f32_out as _mm_f32
from eventgpt_tpu.utils.platform import backend_platform

# Held experts that received a token, the tokens of the fullest held expert,
# the assignments that fell on held experts, the tokens routed, and whether
# the held assignments of every token computed passed the grouped product's
# capacity, so that a second slab ran (0 where the call has no capacity),
# and the (row tile, group) pairs the grouped product visits over every
# assignment computed (``ops/grouped_matmul.tiles``; 0 in the batched form).
STATS = ("touched", "fullest", "held_assignments", "tokens", "over_capacity",
         "tiles")

# A slab of the grouped product holds this many times the assignments that
# would fall on the held experts were the router even (Trinity's prompts
# read 0.52-1.62 times that share a layer: PERF.md), in whole row tiles.
CAPACITY_FACTOR = 2


class Routing(NamedTuple):
    """The router's constants: experts a token, experts held here and where
    the share starts among the deployment's, whether the chosen weights are
    divided by their sum, what they are multiplied by, and the router's
    form (``sigmoid`` or ``softmax_topk``)."""
    top_k: int
    held: int
    offset: int
    normalise: bool
    scale: float
    form: str = "sigmoid"


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def route(routing: Routing, router, bias, y):
    """y (T, D) float32 -> (experts (T, K) int32 over the whole deployment,
    weights (T, K) float32). Scores, choice and weights in float32; ``bias``
    is None for the ``softmax_topk`` form, which has none."""
    with jax.named_scope("moe_route"):
        if routing.form == "softmax_topk":
            with jax.default_matmul_precision("highest"):
                logits = y @ router.astype(jnp.float32)
            top, experts = lax.top_k(logits, routing.top_k)
            return experts, jax.nn.softmax(top, axis=-1) * routing.scale
        with jax.default_matmul_precision("highest"):
            s = jax.nn.sigmoid(y @ router.astype(jnp.float32))
        _, experts = lax.top_k(s + bias, routing.top_k)
        w = jnp.take_along_axis(s, experts, axis=-1)
        if routing.normalise:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return experts, w * routing.scale


def _per_expert(key, held: int):
    """Assignments a held expert: ``key`` (A,) int32 in 0 .. held, ``held``
    standing for an absent expert. A compare and a sum (no scatter)."""
    return jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                   dtype=jnp.int32)


def _batched(u, w: Dict[str, Any]):
    """Every held expert over every token: u (T, in) -> (E, T, out)."""
    if "gate" in w:
        a = (jax.nn.silu(jnp.einsum("tl,elf->etf", u, w["gate"]))
             * jnp.einsum("tl,elf->etf", u, w["up"]))
    else:
        a = relu2(jnp.einsum("tl,elf->etf", u, w["up"]))
    return jnp.einsum("etf,efl->etl", a, w["down"])


def _product():
    """The grouped product: the Pallas kernel on the chip, at every shape a
    served cell calls it with (faster than ``lax.ragged_dot`` at Trinity's
    prefill slab and decode step and at the hybrid's prefill wave: PERF.md
    section 6); ``lax.ragged_dot`` on the CPU, where the kernel would
    run in the interpreter (as dense attention stands in for flash)."""
    return gmm if backend_platform() == "tpu" else lax.ragged_dot


def _grouped(x, w: Dict[str, Any], sizes):
    """Assignments sorted by held expert: x (A, in) -> (A, out); a row past
    ``sum(sizes)`` holds nothing that may be read."""
    product = _product()
    if "gate" in w:
        a = (jax.nn.silu(product(x, w["gate"], sizes))
             * product(x, w["up"], sizes))
    else:
        a = relu2(product(x, w["up"], sizes))
    return product(a, w["down"], sizes)


def capacity(assignments: int, held: int, width: int) -> int:
    """The sorted rows one slab of the grouped product holds, for a call of
    ``assignments`` over ``held`` experts of a router ``width`` wide."""
    n = -(-CAPACITY_FACTOR * assignments * held // (width * ROW_TILE))
    return max(1, n) * ROW_TILE


def _in_slabs(u, w: Dict[str, Any], weight, mine, order, sizes, c: int):
    """The weighted sum of ``_grouped`` over the held assignments alone, ``c``
    sorted rows at a time: u (T, in), ``weight`` and ``mine`` (T, K),
    ``order`` (T * K,) the assignments sorted by held expert, ``sizes`` (E,)
    -> ((T, out) float32, whether they passed ``c``). As many slabs run as
    the held assignments fill, and at least one."""
    t, k = weight.shape
    ends = jnp.cumsum(sizes)
    # A last slab may end past the assignments; what it holds there no
    # group computes.
    padded = jnp.pad(order, (0, -order.shape[0] % c))
    # An assignment's place among the sorted rows, one plane a choice: the
    # rows come back as (K, T, out), which is the product's own layout.
    rank = jnp.argsort(order).reshape(t, k).T

    def slab(s):
        lo = s * c
        rows = lax.dynamic_slice(padded, (lo,), (c,))
        here = jnp.diff(jnp.clip(ends, lo, lo + c), prepend=lo)
        o = _grouped(u[rows // k], w, here)
        at = rank - lo
        mask = mine.T & (at >= 0) & (at < c)
        o = jnp.where(mask[..., None], o[jnp.clip(at, 0, c - 1)], 0)
        return jnp.einsum("ktl,tk->tl", o.astype(jnp.float32), weight)

    # The first slab stands before the loop: every call runs it, and its sum
    # then adds to nothing (0.6 ms of a 15 ms layer on the chip: PERF.md).
    routed = lax.fori_loop(1, (ends[-1] + c - 1) // c,
                           lambda s, routed: routed + slab(s), slab(0))
    return routed, ends[-1] > c


def _one(y, w: Dict[str, Any]):
    """The shared expert: y (T, D) in the compute type -> (T, D) float32."""
    if "gate" in w:
        return _mm_f32(jax.nn.silu(_mm(y, w["gate"])) * _mm(y, w["up"]),
                       w["down"])
    return _mm_f32(relu2(_mm(y, w["up"])), w["down"])


def sparse_experts(routing: Routing, y, counted, dtype, *, router, bias,
                   experts: Dict[str, Any], shared: Dict[str, Any],
                   latent: Optional[Tuple[Any, Any]] = None,
                   dense_up_to: int = 0):
    """y (T, D) float32, normed; ``counted`` (T,) bool: the tokens that are
    real or live; ``dtype``: the compute type of the products. ``experts``:
    the held experts' stacked weights ``{"up", "down"}`` or ``{"gate", "up",
    "down"}`` (E, in, out); ``shared``: one expert's, unstacked; ``latent``:
    the ``(down, up)`` projections around the routed experts, or None.
    Returns (the layer's output (T, D) float32, its ``STATS`` (6,) int32).
    Every token is computed; only the counted ones are counted."""
    t = y.shape[0]
    k, held = routing.top_k, routing.held
    chosen, w = route(routing, router, bias, y)
    y = y.astype(dtype)
    over, visits = False, 0
    with jax.named_scope("moe_experts"):
        u = _mm(y, latent[0]) if latent is not None else y
        local = chosen - routing.offset
        mine = (local >= 0) & (local < held)
        if t <= dense_up_to:
            # A decode step: every held expert computes every token, and a
            # token's weight for an expert it did not choose is 0.
            weight = jnp.zeros((t, held + 1), jnp.float32).at[
                jnp.arange(t)[:, None], jnp.where(mine, local, held)
            ].set(jnp.where(mine, w, 0.0))[:, :held]
            o = _batched(u, experts)
            routed = jnp.einsum("etl,te->tl", o.astype(jnp.float32), weight)
        else:
            # Assignments sorted by held expert; those of absent experts
            # sort behind every group and are computed by none.
            key = jnp.where(mine, local, held).reshape(t * k)
            order = jnp.argsort(key)
            sizes = _per_expert(key, held)
            c = capacity(t * k, held, router.shape[-1])
            # A slab starts on a row tile (``c`` is whole tiles), so the
            # slabs visit the pairs the one product over them all would.
            visits = tiles(sizes)
            if 2 * c < t * k:
                routed, over = _in_slabs(u, experts, w, mine, order, sizes,
                                         c)
            else:
                o = _grouped(u[order // k], experts, sizes)
                # Back in the tokens' order; a row no group computed holds
                # nothing that may be read.
                o = jnp.where(mine[..., None],
                              o[jnp.argsort(order)].reshape(t, k, -1), 0)
                routed = jnp.einsum("tkl,tk->tl", o.astype(jnp.float32), w)
        if latent is not None:
            routed = _mm_f32(routed.astype(dtype), latent[1])
    with jax.named_scope("moe_shared"):
        shared_out = _one(y, shared)
    load = _per_expert(jnp.where(mine & counted[:, None], local, held)
                       .reshape(t * k), held)
    stats = jnp.stack([jnp.sum(load > 0), jnp.max(load), jnp.sum(load),
                       jnp.sum(counted), over, visits]).astype(jnp.int32)
    return routed + shared_out, stats
