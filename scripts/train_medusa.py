"""Train a Medusa draft-head stack on a finetune dataset.

The product loop for the trained-draft serving story: take a (finetuned)
EventChat checkpoint + the same dataset JSON the stage-2 trainer eats,
freeze the whole model, fit only the (K, D, D) head stack
(``train/medusa.py``), and save an ``.npz`` that ``--draft_head`` on the
infer CLI / the batcher / the HTTP server loads. Heads learn
P(token_{t+k+2} | hidden_t) over the model's own supervision targets —
a few hundred steps at 7B is the Medusa paper's regime.

Smoke (tiny random weights, toy data):
  python scripts/train_medusa.py --model_path tiny-random \
      --data_path qa.json --event_folder data/ --num_heads 3 \
      --max_steps 20 --out medusa.npz
"""

from __future__ import annotations

import argparse
import json
import time


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model_path", default="tiny-random")
    p.add_argument("--tokenizer_path", default=None)
    p.add_argument("--data_path", required=True)
    p.add_argument("--event_folder", default="")
    p.add_argument("--conv_version", default="v1")
    p.add_argument("--num_heads", type=int, default=3,
                   help="draft heads K (serve with speculative <= K+1)")
    p.add_argument("--max_steps", type=int, default=200)
    p.add_argument("--batch_size", type=int, default=2)
    p.add_argument("--learning_rate", type=float, default=1e-3)
    p.add_argument("--max_len", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--logging_steps", type=int, default=10)
    p.add_argument("--dtype", default="float32",
                   choices=["bfloat16", "float32"])
    p.add_argument("--out", default="medusa.npz")
    # prepare_model (shared with the infer/eval CLIs) reads these:
    p.add_argument("--use_event_qformer", action="store_true")
    p.add_argument("--pretrain_query_embedder", default=None)
    p.add_argument("--pretrain_attention_layers", default=None)
    p.add_argument("--spatial_temporal_encoder", default=True,
                   type=lambda s: s.lower() not in ("false", "0"))
    p.add_argument("--quant", default="none",
                   choices=["none", "int8"],
                   help="frozen-base storage during head training")
    p.add_argument("--fuse_params", action="store_true")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from eventgpt_tpu.cli.infer import load_model, prepare_model
    from eventgpt_tpu.train.data import EventChatDataset, batch_iterator
    from eventgpt_tpu.train.medusa import (
        init_medusa_state, make_medusa_train_step, save_medusa,
    )
    from eventgpt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg, params, tokenizer = load_model(
        args.model_path, args.dtype, None, args.tokenizer_path
    )
    cfg, params = prepare_model(cfg, params, tokenizer, args)

    dataset = EventChatDataset(
        args.data_path, tokenizer, cfg, event_folder=args.event_folder,
        conv_version=args.conv_version,
    )
    opt = optax.adamw(args.learning_rate)
    state = init_medusa_state(cfg, params, args.num_heads, opt)
    step_fn = make_medusa_train_step(cfg, opt)

    step = 0
    t0 = time.perf_counter()
    last = {"loss": float("nan")}
    while step < args.max_steps:
        for host in batch_iterator(
            dataset, args.batch_size, cfg, shuffle=True,
            seed=args.seed + step, max_len=args.max_len,
        ):
            batch = {k: jnp.asarray(v) for k, v in host.items()}
            state, metrics = step_fn(state, batch)
            step += 1
            if step % args.logging_steps == 0 or step == args.max_steps:
                last = {
                    "step": step,
                    "loss": float(metrics["loss"]),
                    "per_head": [round(float(x), 4)
                                 for x in metrics["per_head_loss"]],
                    "grad_norm": float(metrics["grad_norm"]),
                    "s_per_step": round(
                        (time.perf_counter() - t0) / step, 3),
                }
                print(json.dumps(last))
            if step >= args.max_steps:
                break
    if not np.isfinite(last["loss"]):
        raise RuntimeError(f"medusa training diverged: loss={last['loss']}")
    save_medusa(args.out, jax.device_get(state.trainable))
    print(f"saved {args.num_heads}-head stack to {args.out}")
    return last


if __name__ == "__main__":
    main()
