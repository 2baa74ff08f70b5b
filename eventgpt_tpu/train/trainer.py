"""Training driver: the in-tree replacement for the external LLaVA/HF Trainer.

Wires dataset -> collator -> sharded jit step -> metrics -> checkpoints
(SURVEY.md §3.2 reconstructs this loop from the pyc + requirements). All
distributed behavior comes from shardings; the loop body is identical on one
chip and on a pod.
"""

from __future__ import annotations

import json
import logging
import math
import os
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from eventgpt_tpu import checkpoint as ckpt
from eventgpt_tpu import constants
from eventgpt_tpu import faults
from eventgpt_tpu.config import EventChatConfig, MeshConfig
from eventgpt_tpu.obs import metrics as obs_metrics
from eventgpt_tpu.obs import profiling as obs_profiling
from eventgpt_tpu.parallel import best_mesh_config, make_mesh, shard_params
from eventgpt_tpu.parallel.dist import is_primary
from eventgpt_tpu.parallel.sharding import (
    clip_param_specs,
    llama_param_specs,
    projector_param_specs,
    tree_shardings,
)
from eventgpt_tpu.train import steps as steps_mod
from eventgpt_tpu.train.args import DataArguments, ModelArguments, TrainingArguments
from eventgpt_tpu.train.data import EventChatDataset, batch_iterator
from eventgpt_tpu.train.lora import LoraConfig, lora_param_specs
from eventgpt_tpu.train.optim import linear_warmup_cosine, make_optimizer
from eventgpt_tpu.train.prefetch import PrefetchIterator
from eventgpt_tpu.train.resilience import GracefulShutdown, Heartbeat

log = logging.getLogger("eventgpt_tpu.train")


class TrainingDivergedError(RuntimeError):
    """Loss went non-finite; training state before the divergence is on disk."""


class Trainer:
    """Two-stage EventChat trainer.

    ``stage=1`` trains the projector only; ``stage=2`` trains LoRA +
    projector. Parameters are sharded over ``Mesh(data, fsdp, context,
    model)``; batches shard over (data, fsdp).
    """

    def __init__(
        self,
        cfg: EventChatConfig,
        params: Dict[str, Any],
        tokenizer: Any,
        model_args: ModelArguments,
        data_args: DataArguments,
        train_args: TrainingArguments,
        mesh=None,
    ):
        self.cfg = cfg
        self.margs, self.dargs, self.targs = model_args, data_args, train_args

        if mesh is None:
            if train_args.mesh_data > 0 and train_args.mesh_fsdp > 0:
                mcfg = MeshConfig(
                    data=train_args.mesh_data, fsdp=train_args.mesh_fsdp,
                    model=train_args.mesh_model, context=train_args.mesh_context,
                )
            else:
                mcfg = best_mesh_config(
                    jax.device_count(),
                    model=train_args.mesh_model, context=train_args.mesh_context,
                )
            # An explicit mesh smaller than the host's device count is valid
            # (smoke runs on a virtual mesh); take the first N devices.
            mesh = make_mesh(mcfg, devices=jax.devices()[:mcfg.num_devices])
        self.mesh = mesh

        if train_args.attn_impl:
            import dataclasses

            cfg = dataclasses.replace(
                cfg, llama=dataclasses.replace(cfg.llama, attn_impl=train_args.attn_impl)
            )
        if getattr(train_args, "remat_policy", "full") != \
                cfg.llama.remat_policy:
            # Stage-2 remat-policy sweep (ISSUE 13 satellite): thread the
            # CLI choice into the config the train step closes over —
            # LlamaConfig.__post_init__ validates the name.
            import dataclasses

            cfg = dataclasses.replace(
                cfg, llama=dataclasses.replace(
                    cfg.llama, remat_policy=train_args.remat_policy)
            )
        ctx = mesh.shape["context"]
        if ctx > 1 and cfg.llama.attn_impl not in ("ring", "ulysses"):
            raise ValueError(
                "mesh_context > 1 requires attn_impl='ring' or 'ulysses' "
                "(sequence parallelism); dense/flash attention cannot "
                "consume a context-sharded sequence"
            )
        if ctx > 1 and cfg.llama.attn_impl == "ulysses":
            local_heads = cfg.llama.num_heads // mesh.shape["model"]
            if local_heads % ctx:
                raise ValueError(
                    f"attn_impl='ulysses' re-shards heads over context: "
                    f"num_heads/model = {local_heads} must divide by "
                    f"mesh_context={ctx} (use attn_impl='ring' otherwise)"
                )
        if ctx > 1 and constants.SEQ_BUCKET % ctx:
            # Collated batches pad T to a multiple of the SEQ_BUCKET grain
            # (train/data.py:collate_fixed_layout), so a context size that
            # divides it always divides T; anything else would die with an
            # opaque shard_map divisibility error on the first step.
            raise ValueError(
                f"mesh_context={ctx} must divide the {constants.SEQ_BUCKET}-token "
                f"sequence bucket (use 2, 4, 8, ...)"
            )

        # --- special-token registration (initialize_vision_tokenizer,
        # model/EventChatModel.py:193-217): patch/start/end tokens grow the
        # tokenizer, embeddings resize with mean-init of the new rows; when
        # mm_use_im_start_end, the NEW rows additionally become a trainable
        # stage-1 leaf (the reference unfreezes input embeddings and keeps
        # the output head frozen).
        self.num_new_im_tokens = 0
        if model_args.mm_use_im_patch_token:
            tokenizer.add_tokens([constants.DEFAULT_EVENT_PATCH_TOKEN],
                                 special_tokens=True)
        if model_args.mm_use_im_start_end:
            self.num_new_im_tokens = tokenizer.add_tokens(
                [constants.DEFAULT_EV_START_TOKEN, constants.DEFAULT_EV_END_TOKEN],
                special_tokens=True,
            )
        if len(tokenizer) > cfg.llama.vocab_size:
            from eventgpt_tpu.models.llama import resize_token_embeddings

            import dataclasses as _dc

            params = {**params,
                      "llama": resize_token_embeddings(params["llama"],
                                                       len(tokenizer))}
            cfg = _dc.replace(
                cfg, llama=_dc.replace(cfg.llama, vocab_size=len(tokenizer))
            )
        self.cfg = cfg

        self.dataset = EventChatDataset(
            data_args.data_path, tokenizer, cfg,
            event_folder=data_args.event_folder,
            conv_version=data_args.conv_version,
            image_aspect_ratio=data_args.image_aspect_ratio,
        )
        # Held-out evaluation set (HF Trainer's eval_dataset seat in
        # make_supervised_data_module, SURVEY.md §2.2 — the reference always
        # passes None; here it is a real option).
        self.eval_dataset = None
        if data_args.eval_data_path:
            self.eval_dataset = EventChatDataset(
                data_args.eval_data_path, tokenizer, cfg,
                event_folder=data_args.event_folder,
                conv_version=data_args.conv_version,
                image_aspect_ratio=data_args.image_aspect_ratio,
            )

        # --- stage split + shardings -----------------------------------
        # bf16 applies to the FROZEN tree and the forward compute only;
        # trainable master weights and AdamW moments stay f32 (ADVICE r1:
        # bf16 Adam moments degrade stage-1 projector training), with a
        # cast to the compute dtype inside the combine.
        dtype = jnp.bfloat16 if train_args.bf16 else jnp.float32
        self.compute_dtype = dtype
        proj_specs = projector_param_specs(
            cfg.projector.use_feature_adaptor, cfg.projector.mlp_depth
        )
        from eventgpt_tpu.parallel.sharding import vocab_safe_llama_specs

        frozen_specs = {
            "clip": clip_param_specs(),
            "llama": vocab_safe_llama_specs(
                llama_param_specs(), cfg.llama.vocab_size, mesh
            ),
        }

        self.lora_cfg: Optional[LoraConfig] = None
        if train_args.stage == 2 or train_args.lora_enable:
            self.lora_cfg = LoraConfig(
                r=train_args.lora_r, alpha=train_args.lora_alpha,
                dropout=train_args.lora_dropout,
            )
            trainable, frozen = steps_mod.split_stage2(
                params, cfg, self.lora_cfg, jax.random.PRNGKey(train_args.seed),
                dtype=jnp.float32,  # LoRA factors stay f32 for optimizer stability
            )
            if train_args.lora_weight_path:
                from eventgpt_tpu import checkpoint as ckpt_mod

                trainable["lora"] = jax.tree_util.tree_map(
                    lambda x: jnp.asarray(x, jnp.float32),
                    ckpt_mod.load_component(train_args.lora_weight_path,
                                            strip_prefix="lora."),
                )
            trainable_specs = {"projector": proj_specs,
                               "lora": lora_param_specs(self.lora_cfg.targets)}
            if "qformer" in trainable:
                from eventgpt_tpu.parallel.sharding import qformer_param_specs

                trainable_specs["qformer"] = qformer_param_specs()
            if train_args.freeze_mm_mlp_adapter:
                # Projector stays frozen during stage 2 (freeze_mm_mlp_adapter,
                # SURVEY.md §2.2): move it to the frozen tree.
                frozen = {**frozen, "projector": trainable.pop("projector")}
                frozen_specs = {**frozen_specs, "projector": proj_specs}
                trainable_specs = {
                    k: v for k, v in trainable_specs.items() if k != "projector"
                }
                self.combine = steps_mod.make_stage2_combine(
                    self.lora_cfg, dropout_seed=train_args.seed,
                    projector_source="frozen",
                )
            else:
                self.combine = steps_mod.make_stage2_combine(
                    self.lora_cfg, dropout_seed=train_args.seed
                )
        else:
            if train_args.freeze_mm_mlp_adapter:
                raise ValueError(
                    "freeze_mm_mlp_adapter with stage 1 would leave nothing "
                    "trainable (stage 1 trains only the projector)"
                )
            trainable, frozen = steps_mod.split_stage1(
                params, trainable_embed_rows=self.num_new_im_tokens
            )
            trainable_specs = {"projector": proj_specs}
            if "embed_new" in trainable:
                from jax.sharding import PartitionSpec as P

                # 2 rows cannot shard over the vocab ("model") axis the way
                # the full table does; features follow the table's fsdp dim.
                trainable_specs["embed_new"] = P(None, "fsdp")
            if "qformer" in trainable:
                from eventgpt_tpu.parallel.sharding import qformer_param_specs

                trainable_specs["qformer"] = qformer_param_specs()
            self.combine = steps_mod.stage1_combine

        # Master trainables f32; frozen tree in the compute dtype; the
        # forward sees everything in compute dtype via the combine wrapper.
        trainable = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, jnp.float32), trainable
        )
        # A host (numpy) leaf is cast on the host, so that shard_params
        # below places each shard straight from it: jnp.asarray would first
        # land the whole unsharded frozen tree — 14 GB at 7B — on device 0.
        frozen = jax.tree_util.tree_map(
            lambda x: (x.astype(dtype) if isinstance(x, np.ndarray)
                       else jnp.asarray(x, dtype)), frozen)
        base_combine = self.combine

        def cast_combine(tr, fz, step=None, _base=base_combine, _dt=dtype):
            tr = jax.tree_util.tree_map(lambda x: x.astype(_dt), tr)
            return _base(tr, fz, step)

        self.combine = cast_combine

        trainable = shard_params(trainable, trainable_specs, mesh)
        frozen = shard_params(frozen, frozen_specs, mesh)

        # --- optimizer ---------------------------------------------------
        # HF semantics throughout: per_device_train_batch_size is per chip
        # (global batch = per_device x dp), and max_steps / warmup /
        # save_steps / the schedule all count OPTIMIZER updates — one per
        # gradient_accumulation_steps micro-batches (optax.MultiSteps ticks
        # the inner schedule at that same rate).
        dp = self.mesh.shape["data"] * self.mesh.shape["fsdp"]
        self.global_batch_size = train_args.per_device_train_batch_size * dp
        accum = max(train_args.gradient_accumulation_steps, 1)
        micro_per_epoch = len(self.dataset) // self.global_batch_size
        steps_per_epoch = max(micro_per_epoch // accum, 1)
        total = (train_args.max_steps if train_args.max_steps > 0
                 else steps_per_epoch * train_args.num_train_epochs)
        warmup = (train_args.warmup_steps if train_args.warmup_steps > 0
                  else int(total * train_args.warmup_ratio))
        schedule = linear_warmup_cosine(
            train_args.learning_rate, total, warmup,
            min_lr=train_args.min_lr, warmup_start_lr=0.0 if warmup else -1.0,
        )
        self.optimizer = make_optimizer(
            schedule,
            weight_decay=train_args.weight_decay,
            grad_clip=train_args.max_grad_norm,
            projector_lr=train_args.mm_projector_lr,
            accum_steps=train_args.gradient_accumulation_steps,
        )
        self.total_steps = total

        self.state = steps_mod.init_train_state(trainable, frozen, self.optimizer)
        self.train_step = steps_mod.make_train_step(
            cfg, self.optimizer, self.combine, mesh=mesh
        )
        self.eval_step = steps_mod.make_eval_step(cfg, self.combine, mesh=mesh)
        self.metrics_path = os.path.join(train_args.output_dir, "metrics.jsonl")
        # Telemetry (ISSUE 3): per-OPTIMIZER-step JSONL — wall time split
        # into data-wait vs compute plus the egpt_train_* registry summary;
        # metrics.jsonl stays the sparse human log it always was.
        self.telemetry = (
            obs_metrics.JsonlSink(
                os.path.join(train_args.output_dir, "telemetry.jsonl"))
            if train_args.telemetry else None
        )
        self._profiling = False
        if train_args.profile_dir:
            # Arms StepTraceAnnotation around every micro-step; the actual
            # capture window opens at profile_start_step (_maybe_profile).
            obs_profiling.configure(train_args.profile_dir)
        self.heartbeat = Heartbeat(train_args.output_dir)
        self._last_ckpt: Optional[str] = None
        if train_args.on_divergence not in ("raise", "rewind"):
            raise ValueError(
                f"on_divergence must be 'raise' or 'rewind', "
                f"got {train_args.on_divergence!r}"
            )

    # ------------------------------------------------------------------
    def _log(self, record: Dict[str, Any]) -> None:
        if not is_primary():
            return
        os.makedirs(self.targs.output_dir, exist_ok=True)
        with open(self.metrics_path, "a") as f:
            f.write(json.dumps(record) + "\n")
        log.info("step %s: %s", record.get("step"), record)

    def evaluate(self, step: Optional[int] = None) -> Dict[str, float]:
        """Mean next-token loss over the held-out set (token-weighted);
        logs an ``eval_loss`` record and returns it."""
        if self.eval_dataset is None:
            raise ValueError("no eval dataset (set --eval_data_path)")
        from eventgpt_tpu.constants import IGNORE_INDEX

        dp = self.mesh.shape["data"] * self.mesh.shape["fsdp"]
        total_loss, total_tokens = 0.0, 0
        for host_batch in batch_iterator(
            self.eval_dataset, self.global_batch_size, self.cfg,
            shuffle=False, drop_last=False,
            max_len=self.targs.model_max_length,
        ):
            b = next(iter(host_batch.values())).shape[0]
            if b % dp:
                # Pad the trailing partial batch to the data-parallel extent
                # with IGNORE-labeled copies: they shard cleanly and
                # contribute zero tokens to the token-weighted mean.
                pad = dp - b % dp
                host_batch = {
                    k: np.concatenate([v] + [v[:1]] * pad) for k, v in host_batch.items()
                }
                host_batch["labels"][b:] = IGNORE_INDEX
            batch = steps_mod.batch_to_device(host_batch, self.mesh)
            metrics = self.eval_step(self.state, batch)
            n = float(jax.device_get(metrics["n_tokens"]))
            total_loss += float(jax.device_get(metrics["loss"])) * n
            total_tokens += n
        if total_tokens == 0:
            raise ValueError(
                f"eval dataset {self.dargs.eval_data_path!r} produced zero "
                f"supervised tokens — empty or fully filtered eval set"
            )
        record = {
            "eval_loss": total_loss / total_tokens,
            "eval_tokens": int(total_tokens),
            **({"step": step} if step is not None else {}),
        }
        self._log(record)
        return record

    def save(self, tag: str = "last") -> str:
        """Full state checkpoint + the stage-1 style component artifact."""
        out = os.path.join(self.targs.output_dir, f"ckpt_{tag}")
        if is_primary():
            os.makedirs(self.targs.output_dir, exist_ok=True)
        ckpt.save_checkpoint(out, {
            "trainable": self.state.trainable,
            "opt_state": self.state.opt_state,
            "step": self.state.step,
        })
        if is_primary():
            # Durable step record: --resume_from auto orders checkpoints by
            # this, never by mtime (which rsync/gcsfuse fabricate) — see
            # checkpoint.find_latest_checkpoint.
            with open(os.path.join(out, "STEP"), "w") as f:
                f.write(str(int(jax.device_get(self.state.step))))
        self._last_ckpt = out
        if is_primary():
            if "projector" in self.state.trainable:
                ckpt.save_component(
                    os.path.join(self.targs.output_dir, f"projector_{tag}.npz"),
                    jax.device_get(self.state.trainable["projector"]),
                    prefix="model.visual_projector.",
                )
            if "embed_new" in self.state.trainable:
                # Reference artifact shape: the trained special-token rows
                # under 'model.embed_tokens.weight' — the
                # initialize_vision_tokenizer load path accepts exactly the
                # num_new_tokens rows (model/EventChatModel.py:225-227).
                ckpt.save_component(
                    os.path.join(self.targs.output_dir,
                                 f"embed_tokens_{tag}.npz"),
                    {"embed_tokens": {
                        "weight": jax.device_get(
                            self.state.trainable["embed_new"]
                        )}},
                    prefix="model.",
                )
            if "lora" in self.state.trainable:
                ckpt.save_component(
                    os.path.join(self.targs.output_dir, f"lora_{tag}.npz"),
                    jax.device_get(self.state.trainable["lora"]),
                    prefix="lora.",
                )
            if "qformer" in self.state.trainable:
                from eventgpt_tpu.models.qformer import save_qformer_components

                save_qformer_components(
                    jax.device_get(self.state.trainable["qformer"]),
                    os.path.join(self.targs.output_dir, f"query_embedder_{tag}.npz"),
                    os.path.join(self.targs.output_dir, f"attention_layers_{tag}.npz"),
                    num_heads=self.cfg.qformer.num_heads,
                )
        return out

    def resume(self, path: str) -> None:
        target = {
            "trainable": self.state.trainable,
            "opt_state": self.state.opt_state,
            "step": self.state.step,
        }
        restored = ckpt.load_checkpoint(path, target)
        # Orbax restores every leaf COMMITTED to its target sharding. Leaves
        # that were never mesh-sharded (optimizer counts/scalars, created
        # eagerly by optax.init) restore committed to a single device, which
        # a later train_step on the multi-device mesh rejects as a device
        # mismatch — re-place those as mesh-replicated.
        from jax.sharding import NamedSharding, PartitionSpec

        def replicate_unsharded(leaf):
            if not hasattr(leaf, "sharding") or isinstance(
                leaf.sharding, NamedSharding
            ):
                return leaf
            return jax.device_put(
                leaf, NamedSharding(self.mesh, PartitionSpec())
            )

        restored = jax.tree_util.tree_map(replicate_unsharded, restored)
        self.state = steps_mod.TrainState(
            restored["trainable"], self.state.frozen,
            restored["opt_state"], restored["step"],
        )
        self._last_ckpt = path

    # ------------------------------------------------------------------
    def train(self, shutdown: Optional[GracefulShutdown] = None) -> Dict[str, float]:
        """Run the training loop.

        ``shutdown`` (a pre-armed ``GracefulShutdown``) is injectable for
        fault-injection tests; by default one is installed here so SIGTERM/
        SIGINT preemption checkpoints ``ckpt_preempt`` and returns cleanly
        (``{"preempted": True}`` in the result; relaunch with
        ``--resume_from auto``). Non-finite loss follows
        ``TrainingArguments.on_divergence``: ``"raise"`` (default) or
        ``"rewind"`` — reload the latest checkpoint and continue with a
        reshuffled batch order, at most ``max_divergence_rewinds`` times.
        """
        own_shutdown = shutdown is None
        if own_shutdown:
            shutdown = GracefulShutdown().install()
        try:
            return self._train_loop(shutdown)
        finally:
            if own_shutdown:
                shutdown.uninstall()
            if self._profiling:
                # Training ended (or died) inside the capture window:
                # close the profiler trace so the dump is loadable.
                obs_profiling.stop_trace()
                self._profiling = False

    def _maybe_profile(self, step: int) -> None:
        """Open/close the --profile_dir capture window at optimizer-step
        boundaries: steps [profile_start_step, +profile_num_steps) run
        inside one jax.profiler trace (start > 1 keeps compile out)."""
        targs = self.targs
        if not targs.profile_dir:
            return
        start = max(int(targs.profile_start_step), 1)
        stop = start + max(int(targs.profile_num_steps), 1)
        if not self._profiling and step + 1 == start:
            obs_profiling.start_trace(targs.profile_dir)
            self._profiling = True
            self._log({"event": "profile_start", "step": step + 1,
                       "dir": targs.profile_dir})
        elif self._profiling and step + 1 >= stop:
            obs_profiling.stop_trace()
            self._profiling = False
            self._log({"event": "profile_stop", "step": step})

    def _train_loop(self, shutdown: GracefulShutdown) -> Dict[str, float]:
        targs = self.targs
        accum = max(targs.gradient_accumulation_steps, 1)
        # state.step counts micro-batches (it ticks inside the jitted step);
        # user-facing step counts optimizer updates (HF semantics).
        micro = int(jax.device_get(self.state.step))
        step = micro // accum
        done = False
        last_metrics: Dict[str, float] = {}
        t_start = time.perf_counter()
        tokens_seen = 0
        rewinds = 0
        ckpt_tokens: Dict[str, int] = {}  # tokens_seen at each save point
        last_beat = 0.0
        last_eval_step = -1

        if len(self.dataset) < self.global_batch_size:
            raise ValueError(
                f"dataset has {len(self.dataset)} entries but the global "
                f"batch is {self.global_batch_size} "
                f"({targs.per_device_train_batch_size}/device x dp="
                f"{self.global_batch_size // targs.per_device_train_batch_size}); "
                f"every epoch would yield zero batches (drop_last)"
            )
        # With max_steps > 0, cycle epochs until the step budget is spent
        # (HF Trainer semantics); otherwise run num_train_epochs exactly.
        epochs = targs.num_train_epochs if targs.max_steps <= 0 else 10**9
        epoch = -1
        while epoch + 1 < epochs:
            epoch += 1
            if done:
                break
            it = batch_iterator(
                self.dataset, self.global_batch_size, self.cfg,
                # + rewinds: a divergence rewind replays from the checkpoint
                # with a DIFFERENT shuffle, so a poisonous batch order is not
                # deterministically re-entered.
                shuffle=True, seed=targs.seed + epoch + 1000 * rewinds,
                group_by_modality_length=targs.group_by_modality_length,
                max_len=targs.model_max_length,
            )
            if targs.prefetch_depth > 0:
                # Overlap host preprocessing (np.load + rasterize + CLIP
                # resize) with the device step; the finally closes the
                # producer on every exit path (preempt, divergence, done).
                it = PrefetchIterator(it, depth=targs.prefetch_depth)
            window: list = []  # (loss, grad_norm) device scalars, one per micro
            win_data_wait = 0.0  # host-blocked-on-data share of the window
            t_window = time.perf_counter()
            diverged = False
            self._maybe_profile(step)

            def timed_iter(src):
                # Iterator wait measured per micro-batch without touching
                # the loop's continue-paths: (seconds_waiting, batch).
                src = iter(src)
                while True:
                    t0 = time.perf_counter()
                    try:
                        x = next(src)
                    except StopIteration:
                        return
                    yield time.perf_counter() - t0, x

            try:
                for dt_iter, host_batch in timed_iter(it):
                    # Micro-batch-boundary fault site: a chaos test can
                    # kill or slow any step deterministically and assert
                    # the preemption/divergence/heartbeat story holds.
                    faults.maybe_fail("train.step")
                    faults.maybe_delay("train.step")
                    # Local flag check is free; the cross-host AGREEMENT collective
                    # (globally_requested) only runs every preempt_poll_micros so
                    # multi-host runs don't fence async dispatch per micro-batch.
                    # All hosts share the micro counter, so they poll (and thus
                    # act) at the same boundary.
                    poll = (jax.process_count() == 1
                            or micro % max(targs.preempt_poll_micros, 1) == 0)
                    if poll and shutdown.globally_requested():
                        # Step-numbered name so auto-resume can order it without
                        # trusting filesystem mtimes (checkpoint.py ordering).
                        self.save(f"preempt_step{step}")
                        last_metrics = {**last_metrics, "preempted": True,
                                        "reason": shutdown.reason, "step": step}
                        self._log({"event": "preempt", "reason": shutdown.reason,
                                   "step": step})
                        return last_metrics
                    t0 = time.perf_counter()
                    batch = steps_mod.batch_to_device(host_batch, self.mesh)
                    dt_data = dt_iter + (time.perf_counter() - t0)
                    win_data_wait += dt_data
                    obs_metrics.TRAIN_DATA_WAIT.observe(dt_data)
                    with obs_profiling.step_annotation(micro):
                        self.state, metrics = self.train_step(self.state, batch)
                    micro += 1
                    tok_n = int(host_batch["attn_mask"].sum())
                    tokens_seen += tok_n
                    obs_metrics.TRAIN_TOKENS.inc(tok_n)
                    window.append((metrics["loss"], metrics["grad_norm"]))
                    if micro % accum:
                        continue  # gradients still accumulating
                    step += 1

                    need_log = step % targs.logging_steps == 0 or step == 1
                    need_save = targs.save_steps > 0 and step % targs.save_steps == 0
                    if need_log or need_save:
                        # Mean over the accumulation window (HF reports per
                        # optimizer step, not last-micro-batch noise). Host
                        # readback only on logging/save steps — an unconditional
                        # device_get would fence async dispatch every step. Save
                        # steps read the loss too, so a checkpoint is never
                        # written from a window that already went non-finite
                        # (rewind would otherwise reload poisoned state).
                        loss = float(jax.device_get(sum(w[0] for w in window))) / len(window)
                        gnorm = float(jax.device_get(sum(w[1] for w in window))) / len(window)
                        if not math.isfinite(loss):
                            if (targs.on_divergence == "rewind"
                                    and rewinds < targs.max_divergence_rewinds
                                    and self._last_ckpt):
                                rewinds += 1
                                self._log({"event": "divergence_rewind",
                                           "step": step, "loss": loss,
                                           "rewind": rewinds,
                                           "checkpoint": self._last_ckpt})
                                self.resume(self._last_ckpt)
                                micro = int(jax.device_get(self.state.step))
                                step = micro // accum
                                # Discarded steps' tokens don't count twice in
                                # tokens_per_s (replay re-counts them).
                                tokens_seen = ckpt_tokens.get(self._last_ckpt,
                                                              tokens_seen)
                                diverged = True
                                break  # new epoch iterator, reshuffled
                            raise TrainingDivergedError(
                                f"non-finite loss {loss} at optimizer step {step}; "
                                f"restart with --resume_from auto to continue from "
                                f"the last checkpoint in {targs.output_dir}"
                            )
                        if need_log:
                            dt = time.perf_counter() - t_window
                            last_metrics = {
                                "step": step, "epoch": epoch, "loss": loss,
                                "grad_norm": gnorm,
                                "step_time_s": round(dt, 4),
                                "tokens_per_s": round(tokens_seen / (time.perf_counter() - t_start), 1),
                            }
                            self._log(last_metrics)
                    # -- telemetry: per-optimizer-step JSONL + registry --
                    # step_wall splits into data-wait (host blocked on the
                    # iterator / host-to-device) and compute (everything
                    # else: step dispatch, device wait at readbacks).
                    step_wall = time.perf_counter() - t_window
                    compute_s = max(step_wall - win_data_wait, 0.0)
                    obs_metrics.TRAIN_STEP_SECONDS.observe(step_wall)
                    obs_metrics.TRAIN_COMPUTE.observe(compute_s)
                    obs_metrics.TRAIN_STEPS.inc()
                    if need_log:
                        obs_metrics.TRAIN_LOSS.set(loss)
                        obs_metrics.TRAIN_GRAD_NORM.set(gnorm)
                    if self.telemetry is not None and is_primary():
                        rec = {"step": step, "micro": micro,
                               "step_wall_s": round(step_wall, 6),
                               "data_wait_s": round(win_data_wait, 6),
                               "compute_s": round(compute_s, 6),
                               "tokens_seen": tokens_seen}
                        if need_log:
                            rec["loss"] = loss
                            rec["grad_norm"] = gnorm
                        # The registry view rides along so the JSONL is
                        # self-contained (same numbers /metrics would
                        # expose on a server).
                        rec["registry"] = obs_metrics.REGISTRY.summary(
                            ("egpt_train_",))
                        self.telemetry.write(rec)
                    self._maybe_profile(step)
                    win_data_wait = 0.0
                    window.clear()
                    t_window = time.perf_counter()
                    # Liveness beat on its own time cadence (not logging_steps):
                    # watchdogs need a staleness bound independent of logging
                    # config. Loss rides along only when this step logged one.
                    now = time.perf_counter()
                    if is_primary() and (
                        need_log or now - last_beat > targs.heartbeat_interval_s
                    ):
                        self.heartbeat.beat(step, **({"loss": loss} if need_log else {}))
                        last_beat = now
                    if need_save:
                        self.save(f"step{step}")
                        ckpt_tokens[self._last_ckpt] = tokens_seen
                    if (self.eval_dataset is not None and targs.eval_steps > 0
                            and step % targs.eval_steps == 0):
                        last_metrics = {**last_metrics, **self.evaluate(step)}
                        last_eval_step = step
                    if 0 < targs.max_steps <= step:
                        done = True
                        break
            finally:
                # Stop the producer thread on every exit path (normal
                # exhaustion, preempt return, divergence/done break,
                # exception) — a blocked put() must not leak per epoch.
                if isinstance(it, PrefetchIterator):
                    it.close()
            if diverged:
                # Replay the epoch range from the restored step; the epoch
                # counter stays (rewinds bump the shuffle seed instead).
                epoch -= 1
        if (self.eval_dataset is not None and targs.eval_steps >= 0
                and last_eval_step != step):
            # Skip when the in-loop eval already ran at this exact step —
            # the state is unchanged and a second full pass is pure waste.
            last_metrics = {**last_metrics, **self.evaluate(step)}
        self.save("last")
        return last_metrics
