"""The benchmark's weights: one seeded tree, made on the device in one call.

The program gives the *shapes* it serves (``models/synthetic.served_shapes``:
the real init's structure with the decoder fused / quantized as the flags
ask); every *value* is made here from ``--seed``, so the program and the
plain reference (``benchmark/reference.py``) read the same numbers and
neither makes the other's.

The rules are the ones ``models/synthetic.py`` documents (copied, see
PERF.md Open questions): a matmul weight is a uniform int8 grid times a
per-output-channel scale that keeps ``x @ W`` at the variance of the real
init (1 / sqrt(fan_in), and a further 1 / sqrt(2 * layers) on a projection
into the residual stream); lookup tables are unit normal, norm scales one,
biases zero. int8 leaves keep ``{"q", "s"}`` as served; everything else is
multiplied out in the served float type.

One rule is the benchmark's own: the head's column of every id in ``never``
(the tokenizer's end-of-sequence id) is zero, so that id's logit is 0 at
every position and greedy decoding never picks it. Under a random head a
few seeds (2 of the 18 read at 7B, PERF.md section 6) reach the
end-of-sequence id in every tenth answer, and an answer that ends there is
shorter than its budget: the seed would change the work. With the column
zero every answer runs to its budget on every seed.
"""

from __future__ import annotations

import math

_INT8_STD = math.sqrt((256 ** 2 - 1) / 12.0)
_LOOKUP_TABLES = ("embed_tokens", "position_embedding", "class_embedding")
_BRANCH_OUT = ("o", "down", "fc2")
_LEAF_PARTS = ("q", "s", "kernel", "bias")


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _int8(key, shape):
    import jax
    import jax.numpy as jnp

    bits = jax.random.bits(key, shape, dtype=jnp.uint8)
    return jax.lax.bitcast_convert_type(bits, jnp.int8)


def _weight_std(weight: str, fan_in: int, n_stacked: int) -> float:
    gain = (1.0 / math.sqrt(2 * n_stacked)
            if weight in _BRANCH_OUT and n_stacked else 1.0)
    return gain / math.sqrt(fan_in)


def _fill(key, keys, leaf, siblings, never=()):
    """One leaf's values; in the head, the columns of ``never`` zeroed."""
    out = _draw(key, keys, leaf, siblings)
    name = str(keys[-1])
    if never and "lm_head" in keys and name in ("s", "kernel", "lm_head"):
        out = out.at[..., list(never)].set(0)
    return out


def _draw(key, keys, leaf, siblings):
    import jax
    import jax.numpy as jnp

    name, shape, dtype = str(keys[-1]), leaf.shape, leaf.dtype
    composite = set(siblings) <= set(_LEAF_PARTS)
    weight = str(keys[-2]) if composite and len(keys) > 1 else name
    n_stacked = shape[0] if "layers" in keys and len(shape) == 3 else 0
    if dtype == jnp.int8:
        return _int8(key, shape)
    if name == "s" and "q" in siblings:
        fan_in = siblings["q"].shape[-2]
        std = _weight_std(weight, fan_in, n_stacked)
        return (jax.random.uniform(key, shape, jnp.float32, 0.9, 1.1)
                * (std / _INT8_STD))
    if name == "scale" or name.endswith("norm"):
        return jnp.ones(shape, dtype)
    if name == "bias":
        return jnp.zeros(shape, dtype)
    if name in _LOOKUP_TABLES:
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)
    if dtype not in (jnp.bfloat16, jnp.float32):
        raise ValueError(f"no rule for leaf {keys} of type {dtype}")
    std = _weight_std(weight, shape[-2], n_stacked)
    return (_int8(key, shape).astype(jnp.float32)
            * (std / _INT8_STD)).astype(dtype)


def make_tree(shapes, seed: int, never=()):
    """ShapeDtypeStruct tree -> device tree of the same structure, seeded.
    One jitted call; nothing is made on the host. ``never``: token ids
    whose head column is zero (see the module's text)."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    plan = []
    for path, leaf in flat:
        keys = [p.key if hasattr(p, "key") else p.idx for p in path]
        siblings = shapes
        for k in keys[:-1]:
            siblings = siblings[k]
        plan.append((keys, leaf, siblings if isinstance(siblings, dict) else {}))

    def build(key):
        leaves = []
        for i, (keys, leaf, siblings) in enumerate(plan):
            leaves.append(_fill(jax.random.fold_in(key, i), keys, leaf,
                                siblings, tuple(never)))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))
