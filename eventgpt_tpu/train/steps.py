"""Jitted training steps for the two-stage recipe.

Stage 1 (projector warm-up): CLIP and the LM are frozen; only the projector
MLP (+ feature adaptor) trains — the reference implements this by detaching
the CLIP output and re-enabling grad (``model/EventChatModel.py:185-191``);
here the boundary is simply which pytree is differentiated.

Stage 2 (LoRA finetune): the LM is adapted through an apply-form LoRA tree
(``x@W + (x@A)@B`` composite leaves, ``train/lora.py:apply_lora``) so the
frozen base weights are never copied; the projector keeps training with its
own LR group (``mm_projector_lr``).

Both steps consume the fixed-layout batches of ``train/data.py``: the
embedding splice is a static-shape ``take_along_axis`` + ``where`` — the
XLA-compilable redesign of ``prepare_inputs_labels_for_multimodal``
(``model/EventChatModel.py:292-428``).

Sharding: the step functions are plain ``jax.jit``; placement follows the
input shardings (params via ``parallel.shard_params``, batches via
``batch_spec``), and XLA inserts the psums over ``data``/``fsdp`` — no
hand-written collectives (SURVEY.md §2.4).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from eventgpt_tpu.config import EventChatConfig
from eventgpt_tpu.constants import IGNORE_INDEX
from eventgpt_tpu.models import eventchat, llama as llama_mod
from eventgpt_tpu.train.lora import LoraConfig, apply_lora

Params = Dict[str, Any]
Batch = Dict[str, jnp.ndarray]


def multimodal_embeds(params: Params, cfg: EventChatConfig, batch: Batch,
                      mesh=None) -> jnp.ndarray:
    """Fixed-layout splice: text embeddings with event tokens gathered in.

    ``event_index[b, t]`` maps each event slot to its row in the pooled
    event-token block; non-event positions read the text embedding table.

    ``mesh`` pins the CLIP/event activations and text embeddings to the
    batch sharding (VERDICT r5 weak #1): without the pin, GSPMD resolves
    the conflict between the batch-sharded pixels and the fsdp/model-
    sharded CLIP+projector weights by rematerializing the activations
    per layer ("involuntary full rematerialization" on every sharded
    train step).
    """
    if mesh is not None:
        from jax.sharding import NamedSharding

        from eventgpt_tpu.parallel.sharding import batch_spec

        pin = lambda x: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, batch_spec(x.ndim))
        )
    else:
        pin = lambda x: x
    ev = eventchat.encode_events_batch(
        params, cfg, pin(batch["pixel_values"]), mesh=mesh
    )  # (B,E,D)
    ev = pin(ev)
    llama_params = params["llama"]
    if mesh is not None and not isinstance(llama_params["embed_tokens"], dict):
        # Pin the table's feature dim replicated for THIS gather: the
        # partitioner already all-gathers the (model, fsdp)-sharded table
        # to serve batch-sharded indices, but without the pin it lays the
        # gather output out D-sharded and then force-remats it to the
        # batch sharding the splice needs.
        from jax.sharding import NamedSharding, PartitionSpec as P

        llama_params = {**llama_params, "embed_tokens":
                        jax.lax.with_sharding_constraint(
                            llama_params["embed_tokens"],
                            NamedSharding(mesh, P("model", None)))}
    txt = pin(llama_mod.embed_tokens(llama_params, batch["token_ids"]))  # (B,T,D)
    ev = ev.astype(txt.dtype)
    gathered = jnp.take_along_axis(
        ev, batch["event_index"][:, :, None].astype(jnp.int32), axis=1
    )  # (B,T,D)
    return pin(jnp.where(batch["event_pos"][:, :, None], gathered, txt))


def lm_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Next-token CE over non-IGNORE positions. Returns (loss, n_valid)."""
    shift_logits = logits[:, :-1].astype(jnp.float32)
    shift_labels = labels[:, 1:]
    valid = shift_labels != IGNORE_INDEX
    safe_labels = jnp.where(valid, shift_labels, 0)
    ll = jax.nn.log_softmax(shift_logits, axis=-1)
    nll = -jnp.take_along_axis(ll, safe_labels[..., None], axis=-1)[..., 0]
    n_valid = valid.sum()
    loss = jnp.where(valid, nll, 0.0).sum() / jnp.maximum(n_valid, 1)
    return loss, n_valid


def _forward_loss(params: Params, cfg: EventChatConfig, batch: Batch,
                  mesh=None) -> jnp.ndarray:
    embeds = multimodal_embeds(params, cfg, batch, mesh=mesh)
    logits = llama_mod.forward(params["llama"], cfg.llama, embeds,
                               batch["attn_mask"], mesh=mesh)
    loss, _ = lm_loss(logits, batch["labels"])
    return loss


class TrainState(NamedTuple):
    trainable: Params     # differentiated pytree (stage-dependent structure)
    frozen: Params        # non-differentiated base params
    opt_state: Any
    step: jnp.ndarray


def stage1_combine(trainable: Params, frozen: Params, step=None) -> Params:
    """Trainable = {"projector" [, "qformer"] [, "embed_new"]}; CLIP + LM
    frozen.

    ``embed_new`` (present when ``mm_use_im_start_end`` added special
    tokens) shadows the LAST rows of the frozen embedding table — the
    masked-update form of the reference's ``initialize_vision_tokenizer``
    (``model/EventChatModel.py:198-217``: new rows mean-init +
    input-embeddings trainable; originals receive no gradient, and the
    output head rows stay frozen as the reference sets
    ``output_embeddings.requires_grad = False``).
    """
    llama = frozen["llama"]
    if "embed_new" in trainable:
        emb = llama["embed_tokens"]
        n_new = trainable["embed_new"].shape[0]
        llama = {**llama, "embed_tokens": jnp.concatenate(
            [emb[:-n_new], trainable["embed_new"].astype(emb.dtype)]
        )}
    out = {"clip": frozen["clip"], "llama": llama,
           "projector": trainable["projector"]}
    if "qformer" in trainable:
        out["qformer"] = trainable["qformer"]
    return out


def make_stage2_combine(lora_cfg: LoraConfig,
                        dropout_seed: int = 0,
                        projector_source: str = "trainable") -> Callable[..., Params]:
    """Trainable = {"projector", "lora"}; base LM enters as constants.

    With ``lora_cfg.dropout > 0`` the returned combine takes a third
    ``step`` argument: the train step passes its step counter, from which a
    per-step dropout key derives (``fold_in`` — deterministic, resume-safe);
    eval/serving pass ``None`` and get the deterministic adapted model.

    ``projector_source="frozen"`` serves the ``freeze_mm_mlp_adapter``
    recipe (projector moved to the frozen tree, SURVEY §2.2) — same combine
    otherwise, so the dropout-key logic exists exactly once.
    """

    def combine(trainable: Params, frozen: Params, step=None) -> Params:
        key = None
        if lora_cfg.dropout > 0.0 and step is not None:
            key = jax.random.fold_in(jax.random.PRNGKey(dropout_seed), step)
        source = frozen if projector_source == "frozen" else trainable
        out = {
            "clip": frozen["clip"],
            "projector": source["projector"],
            "llama": apply_lora(frozen["llama"], trainable["lora"], lora_cfg,
                                dropout_key=key),
        }
        if "qformer" in trainable:
            out["qformer"] = trainable["qformer"]
        return out

    return combine


def make_train_step(
    cfg: EventChatConfig,
    optimizer: optax.GradientTransformation,
    combine: Callable[[Params, Params], Params] = stage1_combine,
    donate: bool = True,
    mesh=None,
):
    """Build the jitted step: (state, batch) -> (state, metrics).

    Gradients flow only into ``state.trainable`` — the frozen tree is a
    closure-free constant argument, which is the whole freeze mechanism
    (no requires_grad bookkeeping as in the reference).

    ``mesh`` enables sequence-parallel attention when its ``context`` axis
    is > 1 and ``cfg.llama.attn_impl`` is ``"ring"`` or ``"ulysses"``.
    """
    @functools.partial(
        jax.jit,
        static_argnames=(),
        donate_argnums=(0,) if donate else (),
    )
    def step(state: TrainState, batch: Batch):
        def loss_fn(trainable):
            # All combines share the (trainable, frozen, step) signature;
            # the step counter drives per-step LoRA dropout keys. Eval
            # paths call without it and stay deterministic.
            params = combine(trainable, state.frozen, state.step)
            return _forward_loss(params, cfg, batch, mesh)

        loss, grads = jax.value_and_grad(loss_fn)(state.trainable)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.trainable)
        trainable = optax.apply_updates(state.trainable, updates)
        gnorm = optax.global_norm(grads)
        new_state = TrainState(trainable, state.frozen, opt_state, state.step + 1)
        return new_state, {"loss": loss, "grad_norm": gnorm}

    return step


def make_eval_step(cfg: EventChatConfig,
                   combine: Callable[[Params, Params], Params] = stage1_combine,
                   mesh=None):
    # Explicit empty pins: eval reuses ``state`` across batches, so
    # nothing may be donated, and there are no static args (jit-hygiene
    # convention — pins are declared, never implied).
    @functools.partial(jax.jit, static_argnames=(), donate_argnums=())
    def step(state: TrainState, batch: Batch):
        params = combine(state.trainable, state.frozen)
        embeds = multimodal_embeds(params, cfg, batch, mesh=mesh)
        logits = llama_mod.forward(params["llama"], cfg.llama, embeds,
                                   batch["attn_mask"], mesh=mesh)
        loss, n = lm_loss(logits, batch["labels"])
        return {"loss": loss, "n_tokens": n}

    return step


def init_train_state(
    trainable: Params,
    frozen: Params,
    optimizer: optax.GradientTransformation,
) -> TrainState:
    return TrainState(
        trainable=trainable,
        frozen=frozen,
        opt_state=optimizer.init(trainable),
        step=jnp.zeros((), jnp.int32),
    )


def split_stage1(params: Params,
                 trainable_embed_rows: int = 0) -> Tuple[Params, Params]:
    """Full param tree -> (trainable, frozen) for stage 1.

    The Q-Former (when the config gates it in) trains alongside the
    projector — it sits on the same gradient path between the frozen CLIP
    tower and the frozen LM.

    ``trainable_embed_rows`` > 0 makes the LAST n embedding rows (the
    special tokens ``mm_use_im_start_end`` just appended) a trainable leaf
    — ``initialize_vision_tokenizer`` parity, see ``stage1_combine``."""
    trainable = {"projector": params["projector"]}
    if trainable_embed_rows > 0:
        trainable["embed_new"] = (
            params["llama"]["embed_tokens"][-trainable_embed_rows:]
        )
    if "qformer" in params:
        trainable["qformer"] = params["qformer"]
    return trainable, {"clip": params["clip"], "llama": params["llama"]}


def split_stage2(
    params: Params, cfg: EventChatConfig, lora_cfg: LoraConfig, key: jax.Array,
    dtype=jnp.float32,
) -> Tuple[Params, Params]:
    """Full param tree -> (trainable incl. fresh LoRA, frozen base)."""
    from eventgpt_tpu.train.lora import init_lora_params

    trainable = {
        "projector": params["projector"],
        "lora": init_lora_params(cfg.llama, lora_cfg, key, dtype),
    }
    if "qformer" in params:
        trainable["qformer"] = params["qformer"]
    frozen = {"clip": params["clip"], "llama": params["llama"]}
    return trainable, frozen


def batch_to_device(batch: Dict[str, Any], mesh=None) -> Batch:
    """Host batch -> device, sharded over (data, fsdp) when a mesh is given.
    The host-to-device transfer is the second half of the trainer's
    data-wait split."""
    if mesh is None:
        return {k: jnp.asarray(v) for k, v in batch.items()}
    from jax.sharding import NamedSharding

    from eventgpt_tpu.parallel.sharding import batch_spec

    dp = mesh.shape["data"] * mesh.shape["fsdp"]
    b = next(iter(batch.values())).shape[0]
    if b % dp:
        # Silently replicating here would quietly lose all data parallelism
        # on a misconfigured pod run — fail loudly instead (VERDICT r1 #6).
        raise ValueError(
            f"batch size {b} does not divide the data-parallel extent "
            f"dp={dp} (mesh data={mesh.shape['data']} x "
            f"fsdp={mesh.shape['fsdp']}); pick a batch that is a multiple "
            f"of dp or shrink the mesh"
        )
    else:
        # 2D (B, T) arrays additionally shard the sequence axis over the
        # context axis (ring-attention sequence parallelism); a context-1
        # axis (or a non-dividing T) makes that a no-op.
        ctx = mesh.shape["context"]
        spec_fn = lambda v: batch_spec(
            np_ndim(v),
            seq_axis=1 if np_ndim(v) == 2 and v.shape[1] % ctx == 0 else None,
        )
    return {
        k: jax.device_put(jnp.asarray(v), NamedSharding(mesh, spec_fn(v)))
        for k, v in batch.items()
    }


def np_ndim(x) -> int:
    return getattr(x, "ndim", 0)
