"""Training CLI: the in-tree replacement for the external LLaVA launch.

Flags mirror the recovered ModelArguments / DataArguments / TrainingArguments
(SURVEY.md §2.2) via dataclass reflection — every field is a ``--flag``.

Usage (projector warm-up on a toy dataset):
  python -m eventgpt_tpu.cli.train --model_name_or_path tiny-random \\
      --data_path data.json --event_folder samples/ --stage 1 --max_steps 20

Stage 2 (LoRA):  add ``--stage 2 --lora_r 64 --lora_alpha 16``.
Multi-host:      run one process per host with EGPT_COORDINATOR /
                 EGPT_NUM_PROCESSES / EGPT_PROCESS_ID set (parallel/dist.py).
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
from typing import Optional, get_args, get_origin

import jax

from eventgpt_tpu.parallel.dist import initialize_distributed
from eventgpt_tpu.train.args import DataArguments, ModelArguments, TrainingArguments
from eventgpt_tpu.train.trainer import Trainer


def _add_dataclass_args(parser: argparse.ArgumentParser, cls) -> None:
    for f in dataclasses.fields(cls):
        tp = f.type if not isinstance(f.type, str) else eval(f.type)  # noqa: S307
        if get_origin(tp) is not None:  # Optional[X] -> X
            inner = [a for a in get_args(tp) if a is not type(None)]
            tp = inner[0] if inner else str
        if tp is bool:
            parser.add_argument(
                f"--{f.name}", type=lambda v: v.lower() in ("true", "1", "yes"),
                default=f.default,
            )
        else:
            parser.add_argument(f"--{f.name}", type=tp, default=f.default)


def _extract(args: argparse.Namespace, cls):
    return cls(**{f.name: getattr(args, f.name) for f in dataclasses.fields(cls)})


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description="EventGPT-TPU trainer")
    for cls in (ModelArguments, DataArguments, TrainingArguments):
        _add_dataclass_args(parser, cls)
    parser.add_argument(
        "--resume_from", type=str, default=None,
        help="checkpoint dir, or 'auto' to continue from the most recent "
             "ckpt_step*/ckpt_last under --output_dir (crash/preemption "
             "recovery: relaunch the same command with this flag)",
    )
    parser.add_argument(
        "--trace_out", type=str, default=None,
        help="arm the obs span tracer and write the run's Chrome trace "
             "events here at exit (Perfetto / chrome://tracing; "
             "OBSERVABILITY.md)",
    )
    return parser


def main(argv=None):
    from eventgpt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    logging.basicConfig(level=logging.INFO)
    args = build_parser().parse_args(argv)

    initialize_distributed()

    margs = _extract(args, ModelArguments)
    dargs = _extract(args, DataArguments)
    targs = _extract(args, TrainingArguments)

    from eventgpt_tpu.cli.infer import load_model

    cfg, params, tokenizer = load_model(
        margs.model_name_or_path, "bfloat16" if targs.bf16 else "float32"
    )

    if margs.use_event_qformer and not cfg.use_event_qformer:
        # CLI gate-in (initialize_vision_modules sets use_event_qformer on
        # the config the same way, model/EventChatModel.py:117-121).
        from eventgpt_tpu.config import QFormerConfig

        cfg = dataclasses.replace(
            cfg, use_event_qformer=True,
            qformer=QFormerConfig(hidden_size=cfg.llama.hidden_size),
        )
    if cfg.use_event_qformer and "qformer" not in params:
        # Covers both the CLI gate-in and checkpoints whose config.json
        # already sets use_event_qformer (their state dicts never carry the
        # weights — component files or fresh init fill them).
        from eventgpt_tpu.models.qformer import init_qformer_params

        params["qformer"] = init_qformer_params(
            cfg.qformer, jax.random.PRNGKey(targs.seed + 1)
        )

    if margs.pretrain_mm_mlp_adapter:
        from eventgpt_tpu import checkpoint as ckpt

        params["projector"] = ckpt.load_component(
            margs.pretrain_mm_mlp_adapter, strip_prefix="model.visual_projector."
        )
    if margs.pretrain_feature_adaptor:
        from eventgpt_tpu import checkpoint as ckpt

        params["projector"]["adaptor"] = ckpt.load_component(
            margs.pretrain_feature_adaptor, strip_prefix="model.feature_adaptor."
        )
        if not cfg.projector.use_feature_adaptor:
            # Keep the config in sync or the sharding-spec tree and the
            # param tree disagree at Trainer construction.
            cfg = dataclasses.replace(
                cfg, projector=dataclasses.replace(
                    cfg.projector, use_feature_adaptor=True
                )
            )
    if margs.pretrain_query_embedder or margs.pretrain_attention_layers:
        from eventgpt_tpu.models.qformer import load_qformer_components

        if "qformer" not in params:
            raise ValueError(
                "pretrain_query_embedder/pretrain_attention_layers require "
                "--use_event_qformer true (or a use_event_qformer checkpoint)"
            )
        params["qformer"] = load_qformer_components(
            params["qformer"],
            query_embedder_path=margs.pretrain_query_embedder,
            attention_layers_path=margs.pretrain_attention_layers,
        )

    trainer = Trainer(cfg, params, tokenizer, margs, dargs, targs)
    if args.resume_from == "auto":
        from eventgpt_tpu.checkpoint import find_latest_checkpoint

        latest = find_latest_checkpoint(targs.output_dir)
        if latest:
            logging.getLogger(__name__).info("auto-resuming from %s", latest)
            trainer.resume(latest)
    elif args.resume_from:
        trainer.resume(args.resume_from)
    tracer = None
    if args.trace_out:
        from eventgpt_tpu.obs import trace as obs_trace

        tracer = obs_trace.configure(65536)
    try:
        metrics = trainer.train()
    finally:
        if tracer is not None:
            n = tracer.write(args.trace_out)
            logging.getLogger(__name__).info(
                "wrote %d trace events to %s", n, args.trace_out)
    print(metrics)
    return metrics


if __name__ == "__main__":
    main()
