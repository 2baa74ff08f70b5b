"""Medusa-style trained draft heads — the learned alternative to
prompt-lookup speculative drafting.

The reference serves with plain HF generate (``inference.py:52-63``) and
has no speculative path at all; this module is the second half of the
framework's drafting story (VERDICT r3 #3): where the lookup rule
(``models/eventchat._suffix_vote_drafts``) can only echo text it has seen,
K trained heads predict tokens t+2..t+K+1 from the final-norm hidden state
at t (Cai et al., "Medusa: Simple LLM inference acceleration framework
with multiple decoding heads", arXiv:2401.10774 — architecture only; all
code here is original). The verification forward makes ANY draft exact
(greedy chain identity / rejection-sampling distribution), so head quality
affects only speed, never correctness — tested with random heads in
``tests/test_medusa.py``.

TPU shape: one residual SiLU block per head, stacked as a single
(K, D, D) einsum so all heads run in one MXU matmul; logits reuse the
frozen (possibly int8-quantized) lm_head. Heads initialize to ZERO,
making each head's logits exactly the base model's next-token logits (the
paper's identity start) — training only has to learn the *offset* from
that baseline.

Training (``train/medusa.py``) freezes the whole model and fits only the
(K, D, D) stack with the existing optimizer/trainer machinery — the same
"frozen base + small trainable set" recipe as stage-2 LoRA.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from eventgpt_tpu.config import LlamaConfig
from eventgpt_tpu.ops.quant import matmul_f32_out as _mm_f32

MedusaParams = Dict[str, Any]


def init_medusa_params(
    cfg: LlamaConfig, num_heads: int, dtype=jnp.float32
) -> MedusaParams:
    """K draft heads: ``w`` (K, D, D). Zeros => silu(x @ 0) = 0 => each
    head's hidden equals x, so its logits equal the base model's own
    next-token logits (identity start; no RNG needed)."""
    d = cfg.hidden_size
    return {"w": jnp.zeros((num_heads, d, d), dtype)}


def num_draft_heads(medusa: MedusaParams) -> int:
    return int(medusa["w"].shape[0])


def medusa_hidden(medusa: MedusaParams, x: jnp.ndarray,
                  k: Optional[int] = None) -> jnp.ndarray:
    """(..., D) -> (..., K, D): h_k = x + silu(x @ w_k) — all heads in one
    stacked einsum (a single (K*D, D)-shaped MXU contraction). ``k``
    statically prunes the head stack to the first k heads BEFORE the
    einsum (ISSUE 13 head pruning: a smaller speculation bucket's
    executable must not pay the pruned heads' matmul + lm_head at every
    verify; None = all heads, the training/eval form)."""
    w = medusa["w"] if k is None else medusa["w"][:k]
    proj = jnp.einsum("...d,kde->...ke", x, w.astype(x.dtype))
    return x[..., None, :] + jax.nn.silu(proj)


def medusa_logits(
    llama_params: Any, medusa: MedusaParams, x: jnp.ndarray,
    k: Optional[int] = None,
) -> jnp.ndarray:
    """(..., D) -> (..., K, V) f32 through the frozen (possibly quantized)
    lm_head. Head k's logits score the token at stream offset k+2 from
    the position whose hidden is ``x`` (offset +1 is the base lm_head's
    own prediction). ``k`` prunes the stack (see ``medusa_hidden``)."""
    return _mm_f32(medusa_hidden(medusa, x, k), llama_params["lm_head"])


def medusa_drafts(
    llama_params: Any, medusa: MedusaParams, x: jnp.ndarray, k: int
) -> jnp.ndarray:
    """Greedy drafts for the next verification window: (B, D) -> (B, k)
    int32 (argmax per head, truncated/validated to k heads). The
    truncation happens in the HEAD STACK (``medusa_hidden``), so a
    window-W speculation bucket only computes its W-1 heads."""
    n = num_draft_heads(medusa)
    if k > n:
        raise ValueError(
            f"window needs {k} drafts but the Medusa stack has {n} heads "
            f"(train with num_heads >= window - 1)"
        )
    logits = medusa_logits(llama_params, medusa, x, k)  # (B, k, V)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def medusa_loss(
    llama_params: Any,
    medusa: MedusaParams,
    hidden: jnp.ndarray,     # (B, T, D) final-norm hidden (llama.prefill
                             # with return_hidden=True / forward path)
    labels: jnp.ndarray,     # (B, T) token ids; IGNORE_INDEX masked out
    ignore_index: int = -100,
):
    """Sum over heads of next-(k+2)-token cross-entropy.

    Head k at position t predicts ``labels[t + k + 2]`` (offset +1 is the
    base model's own next token — not a draft). Positions whose target is
    out of range or IGNORE_INDEX contribute nothing. Returns
    (scalar loss, per-head mean CE (K,)) — the per-head curve is the
    diagnostic: later heads are strictly harder.
    """
    b, t, _ = hidden.shape
    k = num_draft_heads(medusa)
    logits = medusa_logits(llama_params, medusa, hidden)  # (B, T, K, V)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    losses = []
    for ki in range(k):
        off = ki + 2
        val = t - off
        if val <= 0:
            losses.append(jnp.float32(0.0))
            continue
        tgt = labels[:, off:]                      # (B, T-off)
        lp = logp[:, :val, ki]                     # (B, T-off, V)
        valid = tgt != ignore_index
        safe = jnp.where(valid, tgt, 0)
        ce = -jnp.take_along_axis(lp, safe[:, :, None], axis=2)[:, :, 0]
        n = jnp.maximum(valid.sum(), 1)
        losses.append(jnp.where(valid, ce, 0.0).sum() / n)
    per_head = jnp.stack(losses)
    return per_head.sum(), per_head


def save_medusa(path: str, medusa: MedusaParams) -> None:
    """Head-stack npz IO lives HERE (not train/medusa.py) so inference
    entry points can load heads without importing the optax/training
    stack."""
    import numpy as np

    np.savez(path, w=np.asarray(medusa["w"]))


def load_medusa(path: str, dtype=None) -> MedusaParams:
    import numpy as np

    with np.load(path) as z:
        w = z["w"]
    arr = jnp.asarray(w) if dtype is None else jnp.asarray(w, dtype)
    return {"w": arr}
