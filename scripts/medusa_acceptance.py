"""Trained-Medusa vs lookup drafting on identical live serving traffic.

The round-4 verdict's standing gap: the Medusa machinery existed but no
number showed trained heads accepting more than the suffix-vote lookup
draft. This script is that experiment, fully reproducible in-tree
(VERDICT r4 #2):

  1. Build the deterministic motion-QA corpus
     (``data/motion_corpus.py``): pixels -> direction/speed is learnable,
     per-sample track counts are not echoable.
  2. Finetune the tiny model (full LM + projector — the study needs a
     model that actually *generates* the distribution; LoRA parity is
     stage-2's job, not this experiment's) until its greedy captions
     track the corpus.
  3. Train a Medusa head stack (``train/medusa.py``) on the same data.
  4. Serve the held-out split through three fresh ``ContinuousBatcher``
     instances — lookup draft, trained heads, random heads — with
     identical traffic, budgets and windows, and compare realized
     acceptance (``spec_tokens_per_iteration``: committed tokens per
     model weight pass, the number that buys wall-clock).

Greedy chains must be IDENTICAL across all three (speculation is exact);
only the accept rate may differ. Prints one JSON line.

The reference has no speculation at all (one forward per token,
``/root/reference/model/EventChatModel.py:237-276``) — both columns here
are beyond-parity; the study ranks the framework's own two drafters.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time


def _finetune(cfg, params, tokenizer, dataset, steps, batch_size, lr, log_every):
    """Full-model finetune (LM + projector; CLIP frozen)."""
    import jax
    import jax.numpy as jnp

    from eventgpt_tpu.train import steps as steps_mod
    from eventgpt_tpu.train.data import batch_iterator
    from eventgpt_tpu.train.optim import linear_warmup_cosine, make_optimizer

    trainable = {"llama": params["llama"], "projector": params["projector"]}
    frozen = {"clip": params["clip"]}

    def combine(trainable, frozen, step=None):
        return {"clip": frozen["clip"], "llama": trainable["llama"],
                "projector": trainable["projector"]}

    opt = make_optimizer(linear_warmup_cosine(lr, steps, max(steps // 20, 1)))
    state = steps_mod.init_train_state(trainable, frozen, opt)
    step_fn = steps_mod.make_train_step(cfg, opt, combine, donate=False)

    step, loss = 0, float("nan")
    epoch = 0
    while step < steps:
        for host in batch_iterator(dataset, batch_size, cfg, shuffle=True,
                                   seed=epoch):
            batch = {k: jnp.asarray(v) for k, v in host.items()}
            state, metrics = step_fn(state, batch)
            step += 1
            if step % log_every == 0 or step == steps:
                loss = float(jax.device_get(metrics["loss"]))
                print(f"[finetune] step {step}/{steps} loss {loss:.4f}",
                      file=sys.stderr, flush=True)
            if step >= steps:
                break
        epoch += 1
    if not loss == loss:
        raise RuntimeError("finetune diverged (NaN)")
    return {"clip": frozen["clip"], "llama": state.trainable["llama"],
            "projector": state.trainable["projector"]}, loss


def _train_heads(cfg, params, dataset, num_heads, steps, batch_size, lr,
                 log_every):
    import jax
    import jax.numpy as jnp
    import optax

    from eventgpt_tpu.train.data import batch_iterator
    from eventgpt_tpu.train.medusa import init_medusa_state, make_medusa_train_step

    opt = optax.adamw(lr)
    state = init_medusa_state(cfg, params, num_heads, opt)
    step_fn = make_medusa_train_step(cfg, opt, donate=False)
    step, loss = 0, float("nan")
    epoch = 0
    while step < steps:
        for host in batch_iterator(dataset, batch_size, cfg, shuffle=True,
                                   seed=1000 + epoch):
            batch = {k: jnp.asarray(v) for k, v in host.items()}
            state, metrics = step_fn(state, batch)
            step += 1
            if step % log_every == 0 or step == steps:
                loss = float(jax.device_get(metrics["loss"]))
                print(f"[medusa] step {step}/{steps} loss {loss:.4f} "
                      f"per_head {[round(float(x), 3) for x in metrics['per_head_loss']]}",
                      file=sys.stderr, flush=True)
            if step >= steps:
                break
        epoch += 1
    if not loss == loss:
        raise RuntimeError("medusa training diverged (NaN)")
    return jax.device_get(state.trainable), loss


def _serve_traffic(params, cfg, traffic, draft_head, speculative, budget,
                   max_batch, eos):
    """One fresh batcher (cold history — the honest serving start), all
    eval requests, -> (answers by submit order, tok/iter, wall_s)."""
    from eventgpt_tpu.serve import ContinuousBatcher

    srv = ContinuousBatcher(
        params, cfg, max_batch=max_batch, max_len=256, chunk=16,
        eos_token_id=eos, speculative=speculative, draft_head=draft_head,
    )
    # Warm every executable, then zero the counters: the first draft
    # config must not pay everyone's compiles, and acceptance counters
    # must reflect only measured traffic.
    srv.warmup(prompt_lens=[len(traffic[0][0]) + 16])
    srv.reset_serving_stats()
    t0 = time.perf_counter()
    rids = [srv.submit(ids, px, budget) for ids, px in traffic]
    outs = srv.run_until_drained()
    wall = time.perf_counter() - t0
    return [outs[r] for r in rids], srv.spec_tokens_per_iteration(), wall


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--out_dir", default=None,
                   help="corpus/workspace dir (default: fresh tempdir)")
    p.add_argument("--n_train", type=int, default=96)
    p.add_argument("--n_eval", type=int, default=16)
    p.add_argument("--finetune_steps", type=int, default=600)
    p.add_argument("--medusa_steps", type=int, default=400)
    p.add_argument("--batch_size", type=int, default=8)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--medusa_lr", type=float, default=2e-3)
    p.add_argument("--num_heads", type=int, default=3)
    p.add_argument("--speculative", type=int, default=4)
    p.add_argument("--budget", type=int, default=56)
    p.add_argument("--max_batch", type=int, default=1,
                   help="1 = sequential serving, so tokens_per_iteration "
                        "is PER-CHAIN acceptance (comparable to the "
                        "lookup draft's, which this script also runs); "
                        ">1 reports "
                        "aggregate per weight pass")
    p.add_argument("--log_every", type=int, default=50)
    p.add_argument("--save_heads", default=None,
                   help="optionally save the trained stack (.npz)")
    args = p.parse_args(argv)

    import jax
    import numpy as np

    from eventgpt_tpu.cli.infer import load_model
    from eventgpt_tpu.constants import EVENT_TOKEN_INDEX
    from eventgpt_tpu.data.motion_corpus import build_motion_corpus

    args_dir = args.out_dir or tempfile.mkdtemp(prefix="medusa_acc_")
    paths = build_motion_corpus(args_dir, args.n_train, args.n_eval)

    cfg, params, tokenizer = load_model("tiny-random", "float32", None, None)

    from eventgpt_tpu.train.data import EventChatDataset

    dataset = EventChatDataset(paths["train"], tokenizer, cfg,
                               event_folder=paths["events"],
                               conv_version="plain")

    t0 = time.perf_counter()
    model, ft_loss = _finetune(cfg, params, tokenizer, dataset,
                               args.finetune_steps, args.batch_size,
                               args.lr, args.log_every)
    ft_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    heads, md_loss = _train_heads(cfg, model, dataset, args.num_heads,
                                  args.medusa_steps, args.batch_size,
                                  args.medusa_lr, args.log_every)
    md_s = time.perf_counter() - t0
    if args.save_heads:
        from eventgpt_tpu.models.medusa import save_medusa

        save_medusa(args.save_heads, heads)

    # Held-out traffic: the serving-side twin of preprocess_plain's layout
    # (bos, event block, newline) — the distribution the model was tuned on.
    with open(paths["eval"]) as f:
        eval_entries = json.load(f)
    from eventgpt_tpu.ops.image import process_event_file

    nl = tokenizer("\n", add_special_tokens=False)["input_ids"]
    bos = getattr(tokenizer, "bos_token_id", None)
    prompt = ([bos] if bos is not None else []) + [EVENT_TOKEN_INDEX] + list(nl)
    traffic = []
    for e in eval_entries:
        _, px = process_event_file(
            os.path.join(paths["events"], e["event"]),
            cfg.num_event_frames, cfg.vision.image_size)
        traffic.append((list(prompt), px))
    eos = getattr(tokenizer, "eos_token_id", None)

    rng = np.random.default_rng(7)
    random_heads = {"w": jax.numpy.asarray(
        rng.normal(size=np.shape(heads["w"])).astype(np.float32) * 0.5)}

    results = {}
    answers = {}
    for name, draft in (("lookup", None), ("medusa_trained", heads),
                        ("medusa_random", random_heads)):
        outs, tpi, wall = _serve_traffic(
            model, cfg, traffic, draft, args.speculative, args.budget,
            args.max_batch, eos)
        results[name] = {"tokens_per_iteration": round(tpi, 3),
                         "wall_s": round(wall, 2)}
        answers[name] = outs

    # Exactness: speculation must never change the greedy chain.
    if not (answers["lookup"] == answers["medusa_trained"]
            == answers["medusa_random"]):
        raise RuntimeError("greedy chains diverged across draft types — "
                           "speculation exactness violated")

    # How well did the model actually learn the distribution? (context for
    # the acceptance numbers; NOT a correctness gate)
    decoded = tokenizer.batch_decode(answers["lookup"],
                                     skip_special_tokens=True)
    exact = sum(
        d.strip() == e["conversations"][1]["value"].strip()
        for d, e in zip(decoded, eval_entries))

    record = {
        "metric": "medusa_vs_lookup_tokens_per_iteration",
        "value": results["medusa_trained"]["tokens_per_iteration"],
        "unit": "tok/weight-pass",
        "lookup": results["lookup"],
        "medusa_trained": results["medusa_trained"],
        "medusa_random": results["medusa_random"],
        "speculative_window": args.speculative,
        "num_heads": args.num_heads,
        "traffic_requests": len(traffic),
        "budget": args.budget,
        "finetune": {"steps": args.finetune_steps, "loss": round(ft_loss, 4),
                     "wall_s": round(ft_s, 1)},
        "medusa_train": {"steps": args.medusa_steps,
                         "loss": round(md_loss, 4),
                         "wall_s": round(md_s, 1)},
        "eval_caption_exact": f"{exact}/{len(decoded)}",
        "workspace": args_dir,
    }
    print(json.dumps(record))
    return record


if __name__ == "__main__":
    main()
