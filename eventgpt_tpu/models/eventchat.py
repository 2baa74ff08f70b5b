"""EventChat: the multimodal composition (vision tower + projector + LLM).

TPU-first redesign of ``model/EventChatModel.py``. The reference interleaves
ragged Python list surgery with HF generate (``prepare_inputs_labels_for_
multimodal``, ``:292-428``); here the same semantics factor into three clean
jit units (the seam identified in SURVEY.md §3.3):

  1. ``encode_events``  — CLIP -> projector -> adaptor -> spatio-temporal pool
  2. ``prefill``        — spliced prompt embeddings through the LM, KV cache fill
  3. ``decode_step``    — single-token autoregressive step on the HBM cache

The embedding splice itself (``splice_embeddings``) is static-shape: the
host splits ids at the -200 sentinel once, and the device concatenates
[text embeds | event tokens | text embeds]. Batching right-pads to a shared
length exactly like the reference (``model/EventChatModel.py:383-413``,
padding_side='right'), and the spliced sequence is truncated to the model
context (``:378-381``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from eventgpt_tpu.config import EventChatConfig
from eventgpt_tpu.constants import SEQ_BUCKET
from eventgpt_tpu.models import clip as clip_mod
from eventgpt_tpu.models import llama as llama_mod
from eventgpt_tpu.models import projector as proj_mod
from eventgpt_tpu.ops.pooling import spatio_temporal_pool
from eventgpt_tpu.ops.sampling import sample

Params = Dict[str, Any]


def decoder_of(cfg: EventChatConfig):
    """The decoder's module, by the kind of ``cfg.llama``: the one place that
    picks it. Each defines ``init_params``, ``init_cache``, ``embed_tokens``,
    ``prefill``, ``decode_step`` and ``forward`` under the same signatures;
    the decoder's subtree of the parameters is ``params["llama"]`` whichever
    it is. Each also says what ``ContinuousBatcher`` has to know of it:
    ``fixed_state`` (the planes of a row's state that do not grow with its
    position), ``WAVE_TOKENS`` (the most positions one admission wave may
    prefill; 0: no cap), ``span_counts`` (what a dispatch span carries of
    the decoder's own) and ``REFUSES`` / ``REFUSED_AS`` (the flags it cannot
    serve yet, each with its reason: ``refuse_unserved``). What only the
    dense decoder has (``decode_kstep``, the paged and int8 caches, fusing,
    quantization) is reached through ``llama_mod`` by name."""
    from eventgpt_tpu.config import AfmoeConfig, HybridConfig

    if isinstance(cfg.llama, HybridConfig):
        from eventgpt_tpu.models import nemotron_h

        return nemotron_h
    if isinstance(cfg.llama, AfmoeConfig):
        from eventgpt_tpu.models import afmoe

        return afmoe
    return llama_mod


def refuse_unserved(cfg: EventChatConfig, **asked) -> None:
    """Raise for the first option that is on and that the configuration's
    decoder cannot serve, by its flag's name and with the decoder's own
    reason (its module's ``REFUSES``). ``ContinuousBatcher``,
    ``cli/infer.prepare_model`` and ``synthetic.served_shapes`` ask."""
    dec = decoder_of(cfg)
    for flag, on in asked.items():
        if on and flag in dec.REFUSES:
            raise ValueError(f"{flag} is refused for {dec.REFUSED_AS}: "
                             f"{dec.REFUSES[flag]}")


def init_eventchat_params(cfg: EventChatConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params = {
        "clip": clip_mod.init_clip_params(cfg.vision, k1, dtype),
        "projector": proj_mod.init_projector_params(cfg.projector, k2, dtype),
        "llama": decoder_of(cfg).init_params(cfg.llama, k3, dtype),
    }
    if cfg.use_event_qformer:
        from eventgpt_tpu.models import qformer as qformer_mod

        params["qformer"] = qformer_mod.init_qformer_params(cfg.qformer, k4, dtype)
    return params


def _encode_feats(params: Params, cfg: EventChatConfig, frames: jnp.ndarray,
                  pin=None) -> jnp.ndarray:
    """(N, C, H, W) frames -> (N, num_tokens, D_lm) projected features:
    CLIP -> stop_gradient -> MLP projector -> feature adaptor. The
    stop_gradient is the exact JAX statement of the reference's
    detach-then-requires_grad trick that confines gradients to the
    projector stack (``model/EventChatModel.py:185-191``). ``pin``:
    optional batch-sharding constraint threaded through the CLIP layer
    scan and applied after each projector stage (see ``clip_encode``)."""
    # named_scope: metadata only (the scope path an operation carries on a
    # device trace); no program's arithmetic or name changes with it.
    with jax.named_scope("tower"):
        feats = clip_mod.clip_encode(params["clip"], cfg.vision, frames,
                                     pin=pin)
        feats = jax.lax.stop_gradient(feats)
    with jax.named_scope("projector"):
        feats = proj_mod.apply_projector(params["projector"], feats)
        if pin is not None:
            feats = pin(feats)
        feats = proj_mod.apply_adaptor(params["projector"], feats)
        if pin is not None:
            feats = pin(feats)
    return feats


def _encode_tail(params: Params, cfg: EventChatConfig, feats: jnp.ndarray) -> jnp.ndarray:
    """Per-sample (T, num_tokens, D) projected features -> (num_event_tokens,
    D) event tokens: Q-Former aggregation, raw patch concatenation, or the
    spatio-temporal pool (``model/EventChatModel.py:304-312``)."""
    if cfg.use_event_qformer:
        # Config-gated Q-Former path (use_event_qformer, model/
        # EventChatModel.py:78-81): learned queries aggregate the projected
        # frames into cfg.qformer.num_queries LM tokens.
        from eventgpt_tpu.models import qformer as qformer_mod

        return qformer_mod.qformer_encode(params["qformer"], cfg.qformer, feats)
    if not cfg.use_spatio_temporal_pool:
        # spatial_temporal_encoder=False path: raw per-frame patch tokens,
        # frames concatenated along the token axis.
        return feats.reshape(-1, feats.shape[-1])
    return spatio_temporal_pool(feats, cfg.num_temporal_tokens)


@functools.partial(jax.jit, static_argnames=("cfg",))
def encode_events(params: Params, cfg: EventChatConfig, pixel_values: jnp.ndarray) -> jnp.ndarray:
    """(T, C, H, W) frames -> (num_event_tokens, D_lm) pooled event tokens."""
    return _encode_tail(params, cfg, _encode_feats(params, cfg, pixel_values))


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def encode_events_batch(params: Params, cfg: EventChatConfig,
                        pixel_values: jnp.ndarray, mesh=None) -> jnp.ndarray:
    """(B, T, C, H, W) -> (B, num_event_tokens, D_lm).

    The CLIP tower and projector run batched over the flattened B*T frame
    axis instead of ``vmap``-per-sample: the former nested ``jit`` under
    ``vmap`` was an opaque call boundary to the SPMD partitioner, which
    forced per-layer "involuntary full rematerialization" resharding of
    the CLIP activations on every sharded train step (VERDICT r5 weak
    #1). ``mesh`` (static) additionally pins the tower's scan carry to
    the batch sharding so the sharded step's dryrun artifact is
    warning-free; None (the single-chip default) changes nothing.
    """
    b, t = pixel_values.shape[:2]
    pin = None
    if mesh is not None:
        from jax.sharding import NamedSharding

        from eventgpt_tpu.parallel.sharding import batch_spec

        pin = lambda x: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, batch_spec(x.ndim))
        )
    flat = pixel_values.reshape((b * t,) + pixel_values.shape[2:])
    feats = _encode_feats(params, cfg, flat, pin=pin)
    feats = feats.reshape((b, t) + feats.shape[1:])
    return jax.vmap(lambda f: _encode_tail(params, cfg, f))(feats)


def splice_embeddings(
    params: Params,
    cfg: EventChatConfig,
    segments: Sequence[np.ndarray],
    event_tokens: jnp.ndarray,
    max_context: Optional[int] = None,
) -> jnp.ndarray:
    """Interleave text-segment embeddings with event-token blocks.

    ``segments`` are the host-side id chunks around each -200 sentinel
    (``split_at_event``); ``event_tokens`` is (num_events, n_tok, D) or
    (n_tok, D) for a single clip. Returns (T, D), truncated to the smaller
    of the model context and ``max_context`` (the reference's 2048 cap,
    ``model/EventChatModel.py:378-381``).
    """
    if event_tokens.ndim == 2:
        event_tokens = event_tokens[None]
    num_events = len(segments) - 1
    if event_tokens.shape[0] != num_events:
        raise ValueError(
            f"{num_events} event sentinel(s) in prompt but "
            f"{event_tokens.shape[0]} event clip(s) provided"
        )
    embed_dtype = params["llama"]["embed_tokens"].dtype
    parts: List[jnp.ndarray] = []
    with jax.named_scope("splice"):
        for kind, val in _interleave_segments(segments):
            if kind == "text":
                ids = jnp.asarray(np.asarray(val, dtype=np.int32))
                parts.append(decoder_of(cfg).embed_tokens(params["llama"], ids))
            else:
                parts.append(event_tokens[val].astype(embed_dtype))
        out = jnp.concatenate(parts, axis=0)
    limit = cfg.llama.max_seq_len if max_context is None else min(cfg.llama.max_seq_len, max_context)
    if out.shape[0] > limit:
        # Text overflow truncates silently (reference parity, model/
        # EventChatModel.py:378-381) — but cutting into an event block would
        # silently destroy the visual input, so that fails loudly instead
        # (e.g. non-pool mode: 5*577 event tokens vs a 2048 context).
        n_text = sum(len(s) for s in segments)
        last_event_end = out.shape[0] - len(segments[-1])
        if num_events and last_event_end > limit:
            raise ValueError(
                f"spliced sequence ({out.shape[0]} tokens: {n_text} text + "
                f"{num_events}x{event_tokens.shape[1]} event) exceeds the "
                f"context cap {limit} inside an event block; raise "
                f"max_seq_len/--context_len or enable spatio-temporal pooling"
            )
    return out[:limit]


def _interleave_segments(segments: Sequence[np.ndarray]):
    """THE spliced-sequence layout: yields ("text", seg) / ("event", i) parts
    in order, skipping empty text segments. ``splice_embeddings`` (embedding
    stream) and ``_spliced_text_ids`` (token-id stream for the speculative
    n-gram lookup) both iterate this, so the two views of the sequence cannot
    drift apart."""
    num_events = len(segments) - 1
    for i, seg in enumerate(segments):
        if len(seg):
            yield ("text", seg)
        if i < num_events:
            yield ("event", i)


def _spliced_text_ids(
    segments: Sequence[np.ndarray], n_event_tok: int, limit: int
) -> np.ndarray:
    """Token-id layout of the spliced sequence: text ids in place, event-block
    positions filled with -1 (present in the embedding stream but not
    matchable / draftable by the speculative n-gram lookup)."""
    parts: List[np.ndarray] = []
    for kind, val in _interleave_segments(segments):
        if kind == "text":
            parts.append(np.asarray(val, dtype=np.int32))
        else:
            parts.append(np.full((n_event_tok,), -1, np.int32))
    out = np.concatenate(parts) if parts else np.zeros((0,), np.int32)
    return out[:limit]


def _pad_batch(embeds: List[jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray, np.ndarray]:
    """Right-pad per-sample (T_i, D) embeds to (B, T_max, D) + bool mask."""
    lens = np.array([int(e.shape[0]) for e in embeds])
    t_max = int(lens.max())
    padded = jnp.stack([
        jnp.pad(e, ((0, t_max - e.shape[0]), (0, 0))) for e in embeds
    ])
    mask = jnp.asarray(np.arange(t_max)[None, :] < lens[:, None])
    return padded, mask, lens


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "last_only", "return_hidden"),
    donate_argnames=("cache",),
)
def _prefill_jit(params, cfg: EventChatConfig, embeds, mask, cache,
                 last_only=False, return_hidden=False):
    return decoder_of(cfg).prefill(
        params["llama"], cfg.llama, embeds, mask, cache, last_only=last_only,
        return_hidden=return_hidden,
    )


@functools.lru_cache(maxsize=32)
def _get_sharded_prefill(cfg: EventChatConfig, flat_sh, treedef, logits_sh,
                         mesh, hidden_sh=None):
    """Serving-mesh prefill with pinned output shardings.

    Without the pin, GSPMD is free to lay the written cache out differently
    from the donated input cache, which silently breaks buffer aliasing —
    a second full-size cache allocation per prefill (the donation warnings
    the CPU-mesh tests would otherwise print). Keyed per (cfg, cache
    shardings): one compile per serving configuration. ``mesh`` reaches
    ``llama_mod.prefill`` so a flash config runs the kernel per-shard
    (``serving_flash_shard_map``) instead of downgrading to dense scores.
    ``hidden_sh`` (set by the Medusa draft path) additionally returns the
    last real token's final-norm hidden state.
    """
    cache_sh = jax.tree_util.tree_unflatten(treedef, list(flat_sh))
    if hidden_sh is not None:
        return jax.jit(
            lambda params, embeds, mask, cache: llama_mod.prefill(
                params["llama"], cfg.llama, embeds, mask, cache,
                last_only=True, mesh=mesh, return_hidden=True,
            ),
            donate_argnums=(3,),
            out_shardings=(logits_sh, hidden_sh, cache_sh),
        )
    return jax.jit(
        lambda params, embeds, mask, cache: llama_mod.prefill(
            params["llama"], cfg.llama, embeds, mask, cache, last_only=True,
            mesh=mesh,
        ),
        donate_argnums=(3,),
        out_shardings=(logits_sh, cache_sh),
    )


def _sharded_prefill_fn(cfg: EventChatConfig, embeds, cache, mesh,
                        return_hidden=False):
    """The pinned serving-mesh prefill jit for these argument layouts:
    ``fn(params, embeds, mask, cache)``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from eventgpt_tpu.parallel.serving import serving_batch_axes

    cache_sh = jax.tree_util.tree_map(lambda x: x.sharding, cache)
    flat, treedef = jax.tree_util.tree_flatten(cache_sh)
    baxes = serving_batch_axes(mesh, embeds.shape[0])
    bspec = baxes if baxes else None
    model_n = mesh.shape.get("model", 1)
    vocab_ax = (
        "model"
        if model_n > 1 and cfg.llama.vocab_size % model_n == 0
        else None
    )
    logits_sh = NamedSharding(mesh, P(bspec, vocab_ax))
    hidden_sh = NamedSharding(mesh, P(bspec, None)) if return_hidden else None
    return _get_sharded_prefill(cfg, tuple(flat), treedef, logits_sh, mesh,
                                hidden_sh)


def _prefill_sharded(params, cfg: EventChatConfig, embeds, mask, cache, mesh,
                     return_hidden=False):
    fn = _sharded_prefill_fn(cfg, embeds, cache, mesh, return_hidden)
    return fn(params, embeds, mask, cache)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def _decode_jit(params, cfg: EventChatConfig, tokens, cache):
    dec = decoder_of(cfg)
    token_embeds = dec.embed_tokens(params["llama"], tokens[:, None])
    return dec.decode_step(params["llama"], cfg.llama, token_embeds, cache)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "temperature", "top_p", "eos_token_id"),
    donate_argnames=("cache",),
)
def _decode_loop_jit(
    params,
    cfg: EventChatConfig,
    first_logits,
    cache,
    key,
    max_new_tokens: int,
    temperature: float,
    top_p: float,
    eos_token_id: int,
):
    """Whole autoregressive loop on device (lax.while_loop): no per-token
    host sync — the HF generate loop re-entered Python every step
    (SURVEY.md §3.1 hot loop); here the host reads back once at the end.

    Returns (tokens [B, max_new_tokens] int32, n_generated [B], cache).
    Rows that hit EOS are frozen to EOS thereafter. The final cache is
    returned ONLY so XLA can alias the donated input cache into an output
    buffer — without a matching output the donation is unusable ("donated
    buffers were not usable") and the while_loop carry double-buffers the
    cache, which at 7B batch 8 is the difference between fitting HBM and
    OOM. Callers drop it immediately.
    """
    b = first_logits.shape[0]
    tokens0 = jnp.zeros((b, max(max_new_tokens, 1)), jnp.int32)
    done0 = jnp.zeros((b,), bool)

    def cond(state):
        step, _, done, _, _, _ = state
        return (step < max_new_tokens) & ~done.all()

    def body(state):
        step, tokens, done, logits, cache, key = state
        key, sub = jax.random.split(key)
        next_tok = sample(logits, sub, temperature, top_p)
        next_tok = jnp.where(done, eos_token_id, next_tok)
        tokens = tokens.at[:, step].set(next_tok)
        done = done | (next_tok == eos_token_id)

        # Unconditional advance: a lax.cond pass-through branch here would
        # break XLA's aliasing of the donated KV cache through the
        # while_loop (a second full cache copy stays live — 3 GB at B=8).
        # The cost is one trailing decode_step past the stop condition.
        dec = decoder_of(cfg)
        token_embeds = dec.embed_tokens(params["llama"], next_tok[:, None])
        logits, cache = dec.decode_step(
            params["llama"], cfg.llama, token_embeds, cache
        )
        return step + 1, tokens, done, logits, cache, key

    step, tokens, done, _, cache, _ = lax.while_loop(
        cond, body, (jnp.int32(0), tokens0, done0, first_logits, cache, key)
    )
    return tokens[:, :max_new_tokens], step, cache


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "num_beams", "max_new_tokens", "eos_token_id",
                     "gather_start"),
    # No cache donation: the first op repeats the cache to num_beams x its
    # size, so the donated buffers could never be reused anyway (XLA would
    # just warn on every call).
)
def _beam_loop_jit(
    params,
    cfg: EventChatConfig,
    first_logits,
    cache,
    num_beams: int,
    max_new_tokens: int,
    eos_token_id: int,
    gather_start: int = 0,
):
    """On-device deterministic beam search (length-normalized, HF
    ``length_penalty=1.0`` semantics): cumulative log-prob divided by the
    generated length at selection time.

    The reference exposes ``num_beams`` through HF generate
    (``inference.py:22``, default 1). Beams live as an expanded batch
    (B*num_beams rows) over the same decode_step; each iteration re-gathers
    the KV cache rows by parent-beam index.

    ``gather_start`` bounds that regather (VERDICT r2 weak #4): slots below
    the shortest prompt length are byte-identical across beams (repeated
    from one prefill row, decode writes only at slot >= prompt length), so
    each step permutes just the tail ``[gather_start, S)`` — copy traffic
    O(L*B*k*(S - gather_start)) per token instead of O(L*B*k*S).

    Returns (tokens [B, max_new_tokens] of the best beam, lengths [B]).
    """
    b, v = first_logits.shape
    k = num_beams
    neg = jnp.float32(-1e30)

    logp0 = jax.nn.log_softmax(first_logits.astype(jnp.float32), axis=-1)
    scores, tok0 = lax.top_k(logp0, k)                       # (B, k)
    # tree_map keeps this agnostic to the cache payload (bf16 arrays or
    # int8 {"q","s"} dicts).
    rep = lambda t, ax: jax.tree_util.tree_map(lambda x: jnp.repeat(x, k, axis=ax), t)
    cache = {
        "k": rep(cache["k"], 1),
        "v": rep(cache["v"], 1),
        "length": jnp.repeat(cache["length"], k, axis=0),
    }
    tokens0 = jnp.zeros((b, k, max_new_tokens), jnp.int32).at[:, :, 0].set(tok0)
    done0 = tok0 == eos_token_id
    lengths0 = jnp.ones((b, k), jnp.int32)
    rows = jnp.arange(b)[:, None]

    # Done beams may only extend with EOS at zero extra log-prob, freezing
    # their score while open beams keep accumulating.
    eos_only = jnp.full((v,), neg).at[eos_token_id].set(0.0)

    def cond(state):
        step, _, _, done, _, _ = state
        return (step < max_new_tokens) & ~done.all()

    def body(state):
        step, tokens, scores, done, lengths, cache = state
        last = jnp.take_along_axis(
            tokens, jnp.full((b, k, 1), step - 1, jnp.int32), axis=2
        )[:, :, 0]
        emb = llama_mod.embed_tokens(params["llama"], last.reshape(b * k)[:, None])
        logits, cache = llama_mod.decode_step(params["llama"], cfg.llama, emb, cache)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1).reshape(b, k, v)
        logp = jnp.where(done[:, :, None], eos_only[None, None, :], logp)

        cand = (scores[:, :, None] + logp).reshape(b, k * v)
        new_scores, idx = lax.top_k(cand, k)                  # (B, k)
        parent = idx // v
        tok = idx % v

        tokens = tokens[rows, parent].at[:, :, step].set(tok)
        par_done = done[rows, parent]
        lengths = jnp.where(par_done, lengths[rows, parent],
                            lengths[rows, parent] + 1)
        done = par_done | (tok == eos_token_id)

        flat_parent = (rows * k + parent).reshape(-1)
        sel = lambda t: jax.tree_util.tree_map(
            lambda x: x.at[:, :, gather_start:].set(
                x[:, flat_parent, gather_start:]
            ),
            t,
        )
        cache = {
            "k": sel(cache["k"]),
            "v": sel(cache["v"]),
            "length": cache["length"][flat_parent],
        }
        return step + 1, tokens, new_scores, done, lengths, cache

    _, tokens, scores, done, lengths, _ = lax.while_loop(
        cond, body,
        (jnp.int32(1), tokens0, scores, done0, lengths0, cache),
    )
    norm = scores / jnp.maximum(lengths, 1).astype(jnp.float32)
    best = jnp.argmax(norm, axis=1)                           # (B,)
    row = jnp.arange(b)
    return tokens[row, best], lengths[row, best]


def _spec_probs(logits, temperature: float, top_p: float):
    """Sampling distribution at each verify position: temperature scaling +
    nucleus filter, matching the plain path (``ops/sampling.sample``)."""
    from eventgpt_tpu.ops.sampling import top_p_filter

    scaled = logits.astype(jnp.float32) / temperature
    if top_p < 1.0:
        scaled = top_p_filter(scaled, top_p)  # rank-agnostic (axis=-1 ops)
    return jax.nn.softmax(scaled, axis=-1)


def _spec_commit_sampled(p, drafts, u, key):
    """Rejection-sampling acceptance for point-mass (n-gram) drafts.

    ``p``: (B, W, V) target distributions — ``p[:, i]`` is P(next token |
    window prefix through position i). ``drafts``: (B, W-1) proposed tokens
    for window positions 1..W-1 (-1 = unmatchable filler, never accepted).
    ``u``: (B, W-1) uniforms. The draft "distribution" q is a point mass, so
    draft i+1 is accepted with probability p_i(d) (Leviathan/Chen speculative
    sampling with degenerate q), and the first rejection resamples from
    norm(max(p - q, 0)) = p with the rejected token zeroed — the committed
    chain is exactly distributed as sequential sampling from p.

    Returns (a, corrected): a (B,) accepted-draft count; corrected (B,) the
    token sampled at the first rejection (or from the final position's p on
    full acceptance).
    """
    b, w, v = p.shape
    bidx = jnp.arange(b)
    if w == 1:  # degenerate window: no drafts, sample the one token
        corrected = jax.random.categorical(
            key, jnp.log(jnp.maximum(p[:, 0], 1e-38)), axis=-1
        ).astype(jnp.int32)
        return jnp.zeros((b,), jnp.int32), corrected
    d_valid = drafts >= 0
    d_safe = jnp.clip(drafts, 0, v - 1)
    p_draft = jnp.where(
        d_valid,
        jnp.take_along_axis(p[:, :-1], d_safe[:, :, None], axis=2)[:, :, 0],
        0.0,
    )  # (B, W-1): acceptance probability of each draft
    acc = jnp.cumprod((u < p_draft).astype(jnp.int32), axis=1)
    a = acc.sum(axis=1)  # (B,) accepted prefix length

    p_a = p[bidx, a]  # (B, V) distribution at the first rejection point
    # Zero the rejected token's mass (only when a < W-1: full acceptance
    # samples the bonus token from the untouched final distribution).
    rej = jnp.where(a < w - 1, d_safe[bidx, jnp.minimum(a, w - 2)], -1)
    rej_valid = (a < w - 1) & d_valid[bidx, jnp.minimum(a, w - 2)]
    onehot = jax.nn.one_hot(jnp.maximum(rej, 0), v, dtype=p_a.dtype)
    p_adj = jnp.where(rej_valid[:, None], p_a * (1.0 - onehot), p_a)
    corrected = jax.random.categorical(
        key, jnp.log(jnp.maximum(p_adj, 1e-38)), axis=-1
    ).astype(jnp.int32)
    return a, corrected


# Longest-suffix lookup depth for speculative drafting: matches of up to
# this many trailing tokens are scored; the deepest match level wins.
# 8 covers the clause-length echoes in the reference's published answers
# (scripts/spec_acceptance_sim.py sweeps 4/8/16: flat beyond 8).
SPEC_LOOKUP_MAX = 8


def _vocab_size(params: Params) -> int:
    """Actual vocab from the lm_head leaf (special-token registration can
    grow it past cfg.llama.vocab_size)."""
    head = params["llama"]["lm_head"]
    leaf = head["q"] if isinstance(head, dict) else head
    return int(leaf.shape[-1])


def _suffix_match_levels(tokens, suffix):
    """Per-position RAW suffix-match depth. ``tokens`` (..., P) is a
    lookup buffer (-1 = unmatchable filler), ``suffix`` (B, LMAX) the
    current tail newest-first. Returns (levels (B, P) int32, cont
    (B or 1, P) continuation tokens). A match of depth l ends at position
    j iff tokens[j-k] == suffix[:, k] for all k < l (fillers never match:
    suffix entries < 0 are skipped). Callers gate the returned depth by
    their committed/continuation mask; keeping the raw depth separate is
    what lets ``_advance_match_levels`` extend it in O(P) per drafted
    token instead of re-running this LMAX-deep scan.
    """
    lmax = suffix.shape[1]
    p = tokens.shape[-1]
    idx = jnp.arange(p)
    toks2d = tokens if tokens.ndim == 2 else tokens[None, :]
    shifted = jnp.stack(
        [jnp.roll(toks2d, k, axis=-1) for k in range(lmax)]
    )  # (LMAX, rows, P): shifted[k, :, j] = tokens[:, j-k] (wrapped)
    run = jnp.ones(toks2d.shape, bool)
    levels = jnp.zeros(toks2d.shape, jnp.int32)
    for k in range(lmax):
        tok_k = suffix[:, k][:, None]  # (B, 1)
        eq = (shifted[k] == tok_k) & (tok_k >= 0) & (idx >= k)[None, :]
        run = run & eq
        levels = levels + run.astype(jnp.int32)
    cont = jnp.roll(toks2d, -1, axis=-1)  # cont[:, j] = tokens[:, j+1]
    return levels, cont


def _advance_match_levels(tokens, levels, d):
    """Advance raw match depths when the suffix gains ``d`` (B,) on its
    newest side: depth(j | [d]+suffix) = tokens[j]==d ? 1 +
    min(depth(j-1 | suffix), LMAX-1) : 0 — every old match must continue
    through the new newest token, one position later, and the suffix
    window still holds only SPEC_LOOKUP_MAX entries (the min). Exactly
    the depth the full rescan would compute, at O(P) instead of
    O(LMAX * P) per draft position — the vectorization that keeps the
    speculative draft's traced graph (and the serving segment built on
    it) at LMAX + window ops instead of LMAX * window.
    """
    toks2d = tokens if tokens.ndim == 2 else tokens[None, :]
    prev = jnp.concatenate(
        [jnp.zeros_like(levels[:, :1]), levels[:, :-1]], axis=1
    )  # depth at j-1 under the old suffix; position 0 has no predecessor
    hit = (toks2d == d[:, None]) & (d[:, None] >= 0)
    return jnp.where(hit, 1 + jnp.minimum(prev, SPEC_LOOKUP_MAX - 1), 0)


def _suffix_vote_drafts(
    params, ids_buf, pos, window: int, history=None,
):
    """Draft ``window - 1`` tokens by longest-suffix majority vote
    (replaces round 3's latest-bigram rule; ``scripts/
    spec_acceptance_sim.py`` measures 1.26 vs 1.19 tokens/iteration on the
    reference's published multi-turn answers, 1.34 with a server history).

    Per draft position (re-queried as drafts extend the suffix — a drafted
    token can seed the next lookup): score every committed position of
    ``ids_buf[:, :pos-1]`` (and the optional server-wide ``history``
    buffer) by how many trailing tokens match the current suffix
    (up to ``SPEC_LOOKUP_MAX``); among positions at the deepest match
    level, majority-vote their continuation tokens (ties -> smallest id,
    argmax order); no match at all falls back to repeating the newest
    token (the r3 filler rule). Fillers (-1) never match or vote.

    The LMAX-deep scan (``_suffix_match_levels``) runs ONCE per verify;
    each further draft position extends the depths incrementally
    (``_advance_match_levels``) — identical drafts, at a fraction of the
    traced ops per window.
    """
    b, s_ids = ids_buf.shape
    if window <= 1:
        return jnp.zeros((b, 0), jnp.int32)
    bidx = jnp.arange(b)
    v = _vocab_size(params)
    idx = jnp.arange(s_ids)

    sidx = pos[:, None] - 1 - jnp.arange(SPEC_LOOKUP_MAX)[None, :]
    suffix = jnp.where(
        sidx >= 0,
        ids_buf[bidx[:, None], jnp.clip(sidx, 0, s_ids - 1)],
        -1,
    )  # (B, LMAX) newest-first
    committed = idx[None, :] <= (pos - 2)[:, None]  # ends with committed cont
    raw, cont = _suffix_match_levels(ids_buf, suffix)
    gate = committed & (cont >= 0)
    if history is not None:
        h = history.shape[-1]
        hcommitted = (jnp.arange(h) <= h - 2)[None, :]
        hraw, hcont = _suffix_match_levels(history, suffix)
        hgate = hcommitted & (hcont >= 0)

    newest = suffix[:, 0]  # fallback source: the tail's newest token
    drafts = []
    for i in range(window - 1):
        if i:
            raw = _advance_match_levels(ids_buf, raw, newest)
            if history is not None:
                hraw = _advance_match_levels(history, hraw, newest)
        levels = jnp.where(gate, raw, 0)
        lstar = levels.max(axis=1)  # (B,)
        if history is not None:
            hlevels = jnp.where(hgate, hraw, 0)
            lstar = jnp.maximum(lstar, hlevels.max(axis=1))
        at_max = (levels == lstar[:, None]) & (lstar[:, None] > 0)
        votes = jnp.zeros((b, v), jnp.int32).at[
            bidx[:, None], jnp.clip(cont, 0, v - 1)
        ].add(at_max.astype(jnp.int32))
        if history is not None:
            h_at_max = (hlevels == lstar[:, None]) & (lstar[:, None] > 0)
            votes = votes.at[
                bidx[:, None],
                jnp.clip(jnp.broadcast_to(hcont, (b, h)), 0, v - 1),
            ].add(h_at_max.astype(jnp.int32))
        d = jnp.argmax(votes, axis=1).astype(jnp.int32)
        d = jnp.where(lstar > 0, d, newest)  # fallback: repeat newest
        drafts.append(d)
        newest = d
    return jnp.stack(drafts, axis=1)  # (B, W-1)


def _spec_draft_verify(
    params,
    cfg: EventChatConfig,
    ids_buf,
    pos,             # (B,) next unwritten ids_buf slot per row
    cache,
    key,
    window: int,
    temperature: float,
    top_p: float,
    eos: int,
    history=None,    # optional (H,) server-wide served-text lookup buffer
    medusa=None,     # optional trained draft heads (models/medusa.py)
    drafts_in=None,  # (B, W-1) drafts carried from the previous window
                     # (Medusa mode: heads ran at the last correction's
                     # hidden state, one iteration ago)
    depth=None,      # optional (B,) int32 per-row draft-depth cap
                     # (ISSUE 13): draft positions >= depth[r] are masked
                     # to the -1 unmatchable filler, capping row r's
                     # effective window at depth[r]+1 committed tokens
                     # per verify WITHOUT a new executable. Exact by the
                     # same rule that makes drafts exact: a masked draft
                     # is simply never accepted (greedy: -1 != argmax;
                     # sampled: d_valid gates acceptance), so the chain
                     # is byte-identical at any mask. None = full depth.
):
    """THE speculative draft-and-verify step, shared by the one-shot loop
    (``_spec_loop_jit``) and the serving segment
    (``serve._spec_segment_jit``) so the exact-chain contract cannot drift
    between them.

    Drafts window-1 tokens by longest-suffix majority-vote lookup over
    ``ids_buf[:, :pos]`` (+ the optional server ``history`` buffer —
    ``_suffix_vote_drafts``) — or, when ``medusa`` is given, consumes the
    trained-head drafts carried in ``drafts_in`` and emits the NEXT
    window's drafts from the correction position's hidden state. Either
    way the window is verified in one ``decode_kstep`` (greedy argmax at
    temperature 0, rejection sampling otherwise) and the commit window
    built identically — draft quality affects speed, never the chain.
    The cache is returned with ``length`` RESTORED to its entry value —
    the caller advances it by however many tokens it actually commits
    (budget caps differ between callers).

    Returns (commit (B, W), m_count (B,), first_eos (B,), hit (B,),
    cache, key, next_drafts): ``commit[:, :m]`` are committable tokens,
    ``m_count`` the un-capped commit count (accepted + correction),
    ``first_eos``/``hit`` locate an EOS inside the commit prefix;
    ``next_drafts`` echoes ``drafts_in`` in lookup mode.
    """
    b, s_ids = ids_buf.shape
    bidx = jnp.arange(b)
    iarr = jnp.arange(window)[None, :]
    sampled = temperature > 0.0

    c0 = ids_buf[bidx, jnp.maximum(pos - 1, 0)]  # newest committed token
    if medusa is not None:
        drafts = drafts_in
    else:
        drafts = _suffix_vote_drafts(params, ids_buf, pos, window, history)
    if depth is not None and window > 1:
        # Per-row depth mask (ISSUE 13): positions past the row's cap
        # become the unmatchable filler — acceptance stops there, the
        # correction token still comes from logits that only attended
        # to accepted (target-equal) positions, so the commit is exact.
        drafts = jnp.where(
            jnp.arange(window - 1)[None, :] < depth[:, None], drafts, -1)

    wtoks = jnp.concatenate([c0[:, None], drafts], axis=1)  # (B, W)
    prev_len = cache["length"]
    embeds = llama_mod.embed_tokens(params["llama"], wtoks)
    if medusa is not None:
        logits, hidden, cache = llama_mod.decode_kstep(
            params["llama"], cfg.llama, embeds, cache, return_hidden=True
        )
    else:
        logits, cache = llama_mod.decode_kstep(
            params["llama"], cfg.llama, embeds, cache
        )
    if sampled:
        key, ku, kc = jax.random.split(key, 3)
        p = _spec_probs(logits, temperature, top_p)
        u = jax.random.uniform(ku, (b, window - 1))
        a, corrected = _spec_commit_sampled(p, drafts, u, kc)
    else:
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, W)
        # Accepted prefix: drafts[:, :a] all equal their greedy target.
        acc = jnp.cumprod((drafts == g[:, :-1]).astype(jnp.int32), axis=1)
        a = acc.sum(axis=1)                       # (B,) in [0, W-1]
        corrected = g[bidx, a]
    drafts_p = jnp.concatenate([drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)
    commit = jnp.where(iarr < a[:, None], drafts_p, corrected[:, None])
    m_count = a + 1

    is_eos = (commit == eos) & (iarr < m_count[:, None])
    first_eos = jnp.min(jnp.where(is_eos, iarr, window), axis=1)
    hit = first_eos < window
    cache = {**cache, "length": prev_len}
    if medusa is not None:
        from eventgpt_tpu.models import medusa as medusa_mod

        # The correction token was sampled from position ``a``'s logits;
        # the heads at that SAME position's hidden predict the tokens
        # after it — the next window's drafts, with no extra forward.
        x_sel = hidden[bidx, a]  # (B, D)
        next_drafts = medusa_mod.medusa_drafts(
            params["llama"], medusa, x_sel, window - 1
        )
    else:
        next_drafts = drafts_in
    return commit, m_count, first_eos, hit, cache, key, next_drafts


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "window", "eos_token_id",
                     "temperature", "top_p"),
    donate_argnames=("cache",),
)
def _spec_loop_jit(
    params,
    cfg: EventChatConfig,
    first_logits,
    cache,
    ids_buf,
    prompt_lens,
    max_new_tokens: int,
    window: int,
    eos_token_id: int,
    temperature: float = 0.0,
    top_p: float = 1.0,
    key=None,
    medusa=None,
    first_drafts=None,
):
    """Speculative decoding: lookup (or trained-head) drafting + one
    K-token verification forward per iteration. Greedy (temperature 0) or
    sampled (temperature > 0, nucleus top_p — the reference's default run
    shape, ``inference.py:19-22``). With ``medusa`` (models/medusa.py),
    drafts come from the trained heads instead of the suffix lookup:
    ``first_drafts`` seeds the first window (heads applied to the prefill
    hidden), and each verify step emits the next window's drafts from the
    correction position's hidden — same exactness contracts either way.

    Decode at batch 1 is weight-bandwidth-bound (PERF.md section 5): one
    ``decode_step`` streams ~3.4 GB of int8 weights to emit ONE token. A
    ``decode_kstep`` window streams the same bytes to score ``window``
    candidate positions, so every accepted draft token is a whole
    weight-streaming pass saved. Drafts come from a bigram match against the
    prompt + generated text (`prompt lookup decoding`: the most recent
    earlier occurrence of the current bigram predicts its continuation) —
    no draft model, no extra weights.

    Correctness contracts: at temperature 0, a draft is committed only when
    it equals the verifier's argmax at its position and the first mismatch
    is replaced by that argmax — EXACTLY the plain greedy chain. At
    temperature > 0, drafts go through rejection sampling against the
    verifier's distribution (``_spec_commit_sampled``) — the committed chain
    is EXACTLY DISTRIBUTED as sequential sampling, token for token (not the
    same stream as the plain loop, which burns its PRNG differently).
    Worst case (no draft ever accepted) each iteration still commits one
    token — the plain chain at ~decode cost plus the small window overhead.

    ``ids_buf`` is the committed-token buffer: spliced-prompt text ids with
    event-block positions holding -1 (never matchable), generated ids
    appended at ``prompt_lens + n_gen``. Invariant at each iteration head:
    ``cache["length"] == prompt_lens + n_gen - 1`` — every committed token
    except the newest has its KV cached; the verification window feeds that
    newest token plus ``window - 1`` drafts.

    Returns (ids_buf, n_gen [B], n_iters, cache) — outputs are read back
    from ``ids_buf`` at [prompt_lens, prompt_lens + n_gen). The cache is
    returned only to keep the donated input buffers aliasable (see
    ``_decode_loop_jit``); callers drop it.
    """
    b = first_logits.shape[0]
    s_ids = ids_buf.shape[1]
    bidx = jnp.arange(b)
    iarr = jnp.arange(window)[None, :]
    eos = eos_token_id
    if key is None:
        key = jax.random.PRNGKey(0)

    key, k0 = jax.random.split(key)
    t0 = sample(first_logits, k0, temperature, top_p)  # argmax at T=0
    ids_buf0 = ids_buf.at[bidx, prompt_lens].set(t0)
    n_gen0 = jnp.ones((b,), jnp.int32)
    done0 = t0 == eos
    drafts0 = (first_drafts if medusa is not None
               else jnp.zeros((b, max(window - 1, 0)), jnp.int32))

    def cond(state):
        _, n_gen, done, _, _, _, _ = state
        return (~done & (n_gen < max_new_tokens)).any()

    def body(state):
        ids_buf, n_gen, done, cache, n_iters, key, drafts = state
        active = ~done & (n_gen < max_new_tokens)
        pos = prompt_lens + n_gen          # next ids_buf write slot
        commit, m_count, first_eos, hit, cache, key, drafts = (
            _spec_draft_verify(
                params, cfg, ids_buf, pos, cache, key, window,
                temperature, top_p, eos, medusa=medusa, drafts_in=drafts,
            )
        )
        # EOS stops the commit window at (and including) the EOS token;
        # this loop allows budget overshoot (clipped at readback).
        m_eff = jnp.where(active, jnp.where(hit, first_eos + 1, m_count), 0)

        wpos = jnp.clip(pos[:, None] + iarr, 0, s_ids - 1)
        cur = ids_buf[bidx[:, None], wpos]
        ids_buf = ids_buf.at[bidx[:, None], wpos].set(
            jnp.where(iarr < m_eff[:, None], commit, cur)
        )
        n_gen = n_gen + m_eff
        done = done | (active & hit)
        # Keep KV only for committed tokens minus the newest (stale slots
        # above length are masked everywhere and overwritten by the next
        # window).
        cache = {**cache, "length": cache["length"] + m_eff}
        return ids_buf, n_gen, done, cache, n_iters + 1, key, drafts

    ids_buf, n_gen, done, cache, n_iters, _, _ = lax.while_loop(
        cond, body,
        (ids_buf0, n_gen0, done0, cache, jnp.int32(0), key, drafts0),
    )
    return ids_buf, n_gen, n_iters, cache


def generate(
    params: Params,
    cfg: EventChatConfig,
    input_ids_batch: Sequence[Sequence[int]],
    pixel_values_batch: jnp.ndarray,
    max_new_tokens: int = 512,
    temperature: float = 0.0,
    top_p: float = 1.0,
    eos_token_id: Optional[int] = 2,
    seed: int = 0,
    # Serving cache grain: 2x the training SEQ_BUCKET — a multiple keeps the
    # train/serve shape interactions aligned (the reason the constant is
    # shared) while preserving the coarser serving granularity: halving it
    # to 64 would double the set of compiled prefill/decode shapes a server
    # cycles through across prompt lengths (a full XLA compile each).
    bucket: int = 2 * SEQ_BUCKET,
    max_context: Optional[int] = None,
    num_beams: int = 1,
    kv_quant: bool = False,
    mesh=None,
    speculative: int = 0,
    spec_stats: Optional[Dict[str, int]] = None,
    draft_head=None,
) -> List[List[int]]:
    """Autoregressive generation over a batch of event-QA prompts.

    Flag parity with the reference run (``inference.py:52-63``): sampling is
    enabled iff temperature > 0, nucleus top_p, greedy otherwise; decode
    stops per-row at EOS or after ``max_new_tokens``. ``num_beams > 1``
    switches to deterministic length-normalized beam search (temperature /
    top_p are ignored, as with HF ``do_sample=False`` beam decoding).

    ``mesh``: a serving ``Mesh`` (data/fsdp/model axes, context=1). Params
    must already be placed by ``parallel.serving.shard_params_for_serving``;
    this function shards the activations and KV cache to match, and the
    existing jit units compile to one SPMD program (the BASELINE north-star
    layout: pjit-sharded FSDP/TP weights, HBM-resident sharded cache —
    vs the reference's single-GPU ``inference.py:52-63``).

    ``speculative``: verify-window size K > 0 enables speculative decoding
    (suffix-lookup draft + K-token verify, ``_spec_loop_jit``) — at
    temperature 0 exactly the plain greedy chain; at temperature > 0
    rejection-sampled to the exact sampling distribution. Usually far
    fewer weight-streaming passes. Composes with ``kv_quant`` and
    ``mesh``; requires num_beams 1. ``draft_head``: a trained Medusa stack
    (``models/medusa.py``) switches drafting from lookup to the learned
    heads (needs >= speculative-1 heads); same exactness contracts.

    ``input_ids_batch``: token ids containing -200 sentinels.
    ``pixel_values_batch``: (B, T_frames, C, H, W).
    """
    from eventgpt_tpu.data.tokenizer import split_at_event

    compute_dtype = jax.tree_util.tree_leaves(params["llama"])[0].dtype

    if decoder_of(cfg).REFUSES and (
            speculative or num_beams > 1 or kv_quant or mesh is not None):
        raise ValueError(
            f"{decoder_of(cfg).REFUSED_AS} generates greedy or sampled, on "
            "one device, with the plain cache: speculative, num_beams, "
            "kv_quant and mesh are refused (beams regather keys and values "
            "only; a rejected draft cannot be rolled back)")
    if speculative and num_beams > 1:
        raise ValueError(
            "speculative decoding composes with greedy/sampled decode, "
            "not beam search: num_beams must be 1"
        )

    serving = None
    if mesh is not None:
        from eventgpt_tpu.parallel import serving as serving_mod

        serving = serving_mod
        serving._require_serving_mesh(mesh)
        pixel_values_batch = serving.shard_batch_array(
            pixel_values_batch, mesh, compute_dtype
        )

    event_tokens = encode_events_batch(
        params, cfg, jnp.asarray(pixel_values_batch, dtype=compute_dtype)
    )
    embeds = [
        splice_embeddings(params, cfg, split_at_event(ids), event_tokens[i], max_context)
        for i, ids in enumerate(input_ids_batch)
    ]
    padded, mask, lens = _pad_batch(embeds)
    b, t = padded.shape[:2]

    # Bucket the cache length to stabilize compiled shapes across prompts.
    # Speculative windows overshoot by up to `speculative` committed tokens
    # and write one full window past the last commit — reserve 2 windows.
    max_len = t + max_new_tokens + (2 * speculative if speculative else 0)
    max_len = ((max_len + bucket - 1) // bucket) * bucket
    cache = decoder_of(cfg).init_cache(
        cfg.llama, b, max_len, dtype=compute_dtype, quant=kv_quant
    )
    if serving is not None:
        padded = serving.shard_batch_array(padded, mesh)
        mask = serving.shard_batch_array(mask, mesh)
        cache = serving.shard_kv_cache(cache, cfg.llama, mesh)

    want_hidden = bool(speculative) and draft_head is not None
    last_hidden = None
    if serving is not None:
        pre = _prefill_sharded(params, cfg, padded, mask, cache, mesh,
                               return_hidden=want_hidden)
    else:
        pre = _prefill_jit(params, cfg, padded, mask, cache, True,
                           return_hidden=want_hidden)
    if want_hidden:
        last_logits, last_hidden, cache = pre
    else:
        last_logits, cache = pre

    key = jax.random.PRNGKey(seed)
    if serving is not None:
        key = serving.replicate(key, mesh)
    if max_new_tokens == 0:
        return [[] for _ in range(b)]
    # EOS sentinel: a real id stops rows early; None decodes the full budget
    # (an out-of-vocab sentinel that never matches a sampled token).
    eos = eos_token_id if eos_token_id is not None else -1
    if num_beams > 1:
        # Bucketed down to the SEQ_BUCKET grain (a lower bound on lens.min()
        # is all correctness needs): gather_start is a STATIC jit arg, and
        # an exact lens.min() would recompile the whole beam loop per
        # distinct prompt length.
        tokens, lengths = _beam_loop_jit(
            params, cfg, last_logits, cache, int(num_beams),
            max_new_tokens, int(eos),
            gather_start=(int(lens.min()) // SEQ_BUCKET) * SEQ_BUCKET,
        )
        out_tokens = np.asarray(jax.device_get(tokens))
        out_lengths = np.asarray(jax.device_get(lengths))
        results = []
        for i in range(b):
            ids = [int(t) for t in out_tokens[i, : out_lengths[i]]]
            if ids and eos_token_id is not None and ids[-1] == eos_token_id:
                ids = ids[:-1]
            results.append(ids)
        return results
    if speculative:
        window = int(speculative)
        limit = (
            cfg.llama.max_seq_len
            if max_context is None
            else min(cfg.llama.max_seq_len, max_context)
        )
        n_ev = int(event_tokens.shape[1])
        ids_host = np.full((b, max_len), -1, np.int32)
        for i, ids in enumerate(input_ids_batch):
            row = _spliced_text_ids(split_at_event(ids), n_ev, limit)
            ids_host[i, : len(row)] = row
        ids_buf = jnp.asarray(ids_host)
        plens = jnp.asarray(lens.astype(np.int32))
        if serving is not None:
            # Everything in the loop is batch-parallel (per-row scatter
            # writes, bigram scan, argmax over the model-sharded vocab) —
            # GSPMD partitions it like the plain decode loop.
            ids_buf = serving.shard_batch_array(ids_buf, mesh)
            plens = serving.shard_batch_array(plens, mesh)
        first_drafts = None
        if draft_head is not None:
            from eventgpt_tpu.models import medusa as medusa_mod

            first_drafts = medusa_mod.medusa_drafts(
                params["llama"], draft_head, last_hidden, window - 1
            )
        out_buf, n_gen, n_iters, cache = _spec_loop_jit(
            params, cfg, last_logits, cache, ids_buf, plens,
            max_new_tokens, window, int(eos),
            temperature=float(temperature), top_p=float(top_p), key=key,
            medusa=draft_head, first_drafts=first_drafts,
        )
        del cache  # returned only for donation aliasing
        out_np = np.asarray(jax.device_get(out_buf))
        gen_np = np.asarray(jax.device_get(n_gen))
        if spec_stats is not None:
            spec_stats["iterations"] = int(jax.device_get(n_iters))
            spec_stats["tokens"] = int(np.minimum(gen_np, max_new_tokens).sum())
        results = []
        for i in range(b):
            row = out_np[i, lens[i] : lens[i] + min(int(gen_np[i]), max_new_tokens)]
            ids_out: List[int] = []
            for tid in row:
                if eos_token_id is not None and tid == eos_token_id:
                    break
                ids_out.append(int(tid))
            results.append(ids_out)
        return results
    tokens, num_steps, cache = _decode_loop_jit(
        params, cfg, last_logits, cache, key,
        max_new_tokens, float(temperature), float(top_p), int(eos),
    )
    del cache  # returned only for donation aliasing
    out_tokens = np.asarray(jax.device_get(tokens))  # single host readback
    num_steps = int(num_steps)

    results: List[List[int]] = []
    for i in range(b):
        ids: List[int] = []
        for tid in out_tokens[i, :num_steps]:
            if eos_token_id is not None and tid == eos_token_id:
                break
            ids.append(int(tid))
        results.append(ids)
    return results


def forward_train(
    params: Params,
    cfg: EventChatConfig,
    inputs_embeds: jnp.ndarray,
    attention_mask: jnp.ndarray,
) -> jnp.ndarray:
    """Training forward: spliced embeds -> logits (B, T, V)."""
    return decoder_of(cfg).forward(params["llama"], cfg.llama, inputs_embeds,
                                   attention_mask)
