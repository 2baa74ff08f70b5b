"""Seeded synthetic weights at a preset's full width, with no checkpoint.

A 7B checkpoint is ~13.5 GB on disk, and initialising one on device in the
compute dtype needs as much HBM before quantization even starts. So a
full-width start without a checkpoint builds the tree the way a quantized
load ends up: on the host, leaf by leaf, directly at the final (fused /
quantized) shapes. ``load_model`` serves it under ``--model_path
eventgpt-7b-random``.

The values are random, not zeros: zero weights multiply a wrong kernel's
output away. Matmul weights are uniform int8 values times a
per-output-channel scale chosen so that ``x @ W`` keeps the variance the
real init (``init_*_params``: normal / sqrt(fan_in)) gives it — stored as
``{"q", "s"}`` under int8, multiplied out in the
compute dtype otherwise. Norm scales are ones and biases zeros, as in the
real init; ``tests/test_synthetic.py`` holds the two trees to one
structure.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Sequence

import jax
import numpy as np

from eventgpt_tpu.config import EventChatConfig

# The ``--model_path`` spelling of the full-width checkpoint-free model
# (``tiny-random`` keeps its on-device init, ``cli/infer.load_model``).
SYNTHETIC_7B = "eventgpt-7b-random"

# Std of an integer uniform on [-128, 127].
_INT8_STD = math.sqrt((256 ** 2 - 1) / 12.0)
# Lookup tables start the residual stream at unit RMS, and the projections
# that write into it are scaled down by sqrt(2 * layers): what a token is
# then survives to the head. With the real init's 0.02 tables the stream is
# all layer output, every hidden state looks alike and greedy decoding
# repeats one id whatever the prompt — which hides a wrong answer as well
# as zero weights do.
_LOOKUP_TABLES = ("embed_tokens", "position_embedding", "class_embedding")
_BRANCH_OUT = ("o", "down", "fc2")
_LEAF_PARTS = ("q", "s", "kernel", "bias")


def served_shapes(cfg: EventChatConfig, dtype, quant: str, fuse: bool):
    """ShapeDtypeStruct tree of the model as it is served: the real init's
    structure, with the llama tree fused and quantized as asked."""
    from eventgpt_tpu.models import eventchat, llama as llama_mod
    from eventgpt_tpu.ops import quant as quant_mod

    shapes = jax.eval_shape(
        lambda k: eventchat.init_eventchat_params(cfg, k, dtype),
        jax.random.PRNGKey(0),
    )

    def transform(p):
        if fuse:
            p = llama_mod.fuse_llama_params(p)
        if quant == "int8":
            p = quant_mod.quantize_llama_params(p)
        return p

    eventchat.refuse_unserved(cfg, **{
        "--quant": quant != "none", "--fuse_params": fuse})
    if eventchat.decoder_of(cfg) is not llama_mod:
        return shapes  # fusing and quantization are the dense decoder's
    shapes["llama"] = jax.eval_shape(transform, shapes["llama"])
    return shapes


def _int8(rng: np.random.Generator, shape) -> np.ndarray:
    # The full int8 range is numpy's fast path (~6x a bounded draw): the
    # 6.5e9 weights of a 7B tree take seconds, not minutes.
    return rng.integers(-128, 128, shape, np.int8)


def _weight_std(weight: str, fan_in: int, n_stacked: int) -> float:
    """Std of a matmul weight's entries: 1/sqrt(fan_in), and a further
    1/sqrt(2 * layers) on a projection into the residual stream."""
    gain = (1.0 / math.sqrt(2 * n_stacked)
            if weight in _BRANCH_OUT and n_stacked else 1.0)
    return gain / math.sqrt(fan_in)


def _fill(keys: Sequence[Any], leaf, siblings: Dict[str, Any],
          rng: np.random.Generator) -> np.ndarray:
    name, shape, dtype = str(keys[-1]), leaf.shape, leaf.dtype
    # The weight a leaf belongs to (``o`` for ``attn.o.s`` and for
    # ``attn.o.kernel``), and how many layers its leading axis stacks
    # (0 = not a per-layer stack).
    composite = set(siblings) <= set(_LEAF_PARTS)
    weight = str(keys[-2]) if composite and len(keys) > 1 else name
    n_stacked = shape[0] if "layers" in keys and len(shape) == 3 else 0
    if dtype == np.int8:  # an int8 leaf's payload
        return _int8(rng, shape)
    if name == "s" and "q" in siblings:
        # Per-channel scales, a little uneven so that a kernel which
        # mislays them changes the answer.
        std = _weight_std(weight, siblings["q"].shape[-2], n_stacked)
        return (rng.uniform(0.9, 1.1, shape) * std / _INT8_STD
                ).astype(np.float32)
    if name == "scale" or name.endswith("norm"):
        return np.ones(shape, dtype)
    if name == "bias":
        return np.zeros(shape, dtype)
    if name in _LOOKUP_TABLES:
        return rng.standard_normal(shape, np.float32).astype(dtype)
    # An unquantized matmul weight: the same integer grid, multiplied out.
    std = _weight_std(weight, shape[-2], n_stacked)
    return (_int8(rng, shape).astype(np.float32) * (std / _INT8_STD)
            ).astype(dtype)


def random_eventchat_params(cfg: EventChatConfig, dtype, quant: str = "none",
                            fuse: bool = False, seed: int = 0
                            ) -> Dict[str, Any]:
    """Host (numpy) EventChat param tree for ``cfg``, seeded, at the shapes
    ``prepare_model`` hands to the device after ``--fuse_params`` /
    ``--quant`` — neither transform runs again on it."""
    shapes = served_shapes(cfg, dtype, quant, fuse)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rng = np.random.default_rng(seed)
    leaves = []
    for path, leaf in flat:
        # Dict keys, or positions in a list (the projector's layer stack).
        keys = [p.key if hasattr(p, "key") else p.idx for p in path]
        siblings = shapes
        for k in keys[:-1]:
            siblings = siblings[k]
        leaves.append(_fill(keys, leaf, siblings, rng))
    return jax.tree_util.tree_unflatten(treedef, leaves)
