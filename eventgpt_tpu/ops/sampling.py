"""Token sampling: greedy, temperature, top-p — jit-friendly.

Replaces the HF GenerationMixin sampling configuration the reference relies
on (``inference.py:52-63``: do_sample iff temperature > 0, top_p, greedy
otherwise). All paths are shape-static and run on device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def greedy(logits: jnp.ndarray) -> jnp.ndarray:
    """(B, V) -> (B,) argmax token ids."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def top_p_filter(logits: jnp.ndarray, top_p: float) -> jnp.ndarray:
    """Mask logits outside the smallest nucleus with cumulative prob >= top_p.

    Keeps every token whose inclusion is needed to reach top_p (the standard
    "shift right" nucleus rule: the first token crossing the threshold stays).
    """
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    # Position i is cut iff the cumulative mass *before* it already >= top_p.
    cut = (cum - sorted_probs) >= top_p
    # Translate the sorted-space cut into a per-token logit threshold.
    threshold = jnp.min(jnp.where(cut, jnp.inf, sorted_logits), axis=-1, keepdims=True)
    return jnp.where(logits < threshold, -jnp.inf, logits)


def sample(
    logits: jnp.ndarray,
    key: jax.Array,
    temperature: float = 1.0,
    top_p: float = 1.0,
) -> jnp.ndarray:
    """(B, V) logits -> (B,) sampled ids. temperature <= 0 means greedy."""
    with jax.named_scope("sample"):
        if temperature <= 0.0:
            return greedy(logits)
        scaled = logits.astype(jnp.float32) / temperature
        if top_p < 1.0:
            scaled = top_p_filter(scaled, top_p)
        return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
