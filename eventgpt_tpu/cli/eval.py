"""Batched inference across event samples (BASELINE.json config 2).

The reference publishes Q/A transcripts for samples 1-4
(``/root/reference/README.md:92-160``) as its only correctness artifact; the
north-star check is greedy answers matching those transcripts. This CLI runs
N event files through ONE batched generate call — the spatio-temporal event
encoder, projector, and 7B decode all batched — and optionally diffs each
answer against an expectations file.

Usage:
  python -m eventgpt_tpu.cli.eval --model_path <ckpt> \\
      --event_frames s1.npy,s2.npy,s3.npy,s4.npy \\
      --query "What is happening in this scene?" \\
      [--queries_json per_sample.json] [--expected expected.json]

``--queries_json``: JSON list of per-sample query strings (overrides
--query). ``--expected``: JSON list of expected answer strings; prints
PASS/FAIL per sample and exits nonzero on any mismatch (the transcript-parity
gate, greedy/temperature-0 recommended for it to be meaningful).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from eventgpt_tpu.data.conversation import prepare_event_prompt
from eventgpt_tpu.data.tokenizer import tokenize_with_event
from eventgpt_tpu.models import eventchat
from eventgpt_tpu.ops.image import process_event_file


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="Batched EventGPT evaluation")
    p.add_argument("--model_path", type=str, required=True)
    p.add_argument("--tokenizer_path", type=str, default=None)
    p.add_argument("--event_frames", type=str, required=True,
                   help="comma-separated .npy event files")
    p.add_argument("--query", type=str, default="What is happening in this scene?")
    p.add_argument("--queries_json", type=str, default=None)
    p.add_argument("--expected", type=str, default=None)
    p.add_argument("--conv_mode", type=str, default="eventgpt_v1")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top_p", type=float, default=1.0)
    p.add_argument("--max_new_tokens", type=int, default=512)
    p.add_argument("--num_beams", type=int, default=1)
    p.add_argument("--context_len", type=int, default=2048)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--dtype", type=str, default="bfloat16",
                   choices=["bfloat16", "float32"])
    p.add_argument("--quant", type=str, default="none",
                   choices=["none", "int8"])
    p.add_argument("--kv_cache", type=str, default="bf16", choices=["bf16", "int8"],
                   help="KV cache storage; int8 halves cache memory/bandwidth "
                        "— the wide-batch (BASELINE config 2) serving knob")
    p.add_argument("--fuse_params", action="store_true",
                   help="fuse q|k|v and gate|up weights (5 matmuls/layer)")
    # Serving mesh, same surface as cli/infer.py.
    p.add_argument("--mesh_data", type=int, default=1)
    p.add_argument("--mesh_fsdp", type=int, default=1)
    p.add_argument("--mesh_model", type=int, default=1)
    # Q-Former serving, same surface as cli/infer.py.
    p.add_argument("--use_event_qformer", action="store_true")
    p.add_argument("--pretrain_query_embedder", type=str, default=None)
    p.add_argument("--pretrain_attention_layers", type=str, default=None)
    p.add_argument("--speculative", type=int, default=0,
                   help="speculative decode window (exact greedy chain at "
                        "temperature 0, exact sampling distribution above; "
                        "num_beams must be 1)")
    p.add_argument("--draft_head", default=None,
                   help="trained Medusa head stack (.npz) for speculative "
                        "drafting (requires --speculative > 0)")
    p.add_argument("--timing", action="store_true")
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    from eventgpt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import numpy as np

    from eventgpt_tpu.cli.infer import (
        load_model, prepare_model, serving_mesh_from_args,
    )

    if args.draft_head is not None and not args.speculative:
        raise ValueError(
            "--draft_head requires --speculative K > 0 (the heads draft "
            "into the K-token verification window)"
        )
    from eventgpt_tpu.models.medusa import load_medusa

    files = [f for f in args.event_frames.split(",") if f]
    if args.queries_json:
        with open(args.queries_json) as f:
            queries = json.load(f)
        if len(queries) != len(files):
            raise ValueError(
                f"{len(queries)} queries for {len(files)} event files"
            )
    else:
        queries = [args.query] * len(files)

    t0 = time.perf_counter()
    cfg, params, tokenizer = load_model(
        args.model_path, args.dtype, None, args.tokenizer_path
    )
    # Shared post-load prep (token registration, resize, quant, Q-Former
    # gate-in, placement) — one implementation for both CLIs. One mesh per
    # run: params, activations, and the KV cache share the same Mesh object.
    mesh = serving_mesh_from_args(args)
    cfg, params = prepare_model(cfg, params, tokenizer, args, mesh=mesh)
    t_load = time.perf_counter() - t0

    # One batched preprocessing + generate pass over all samples.
    t0 = time.perf_counter()
    pixels, ids = [], []
    for path, query in zip(files, queries):
        _, pv = process_event_file(path, cfg.num_event_frames,
                                   cfg.vision.image_size)
        pixels.append(pv)
        ids.append(tokenize_with_event(
            prepare_event_prompt(query, args.conv_mode), tokenizer
        ))
    pixels = np.stack(pixels)
    t_prep = time.perf_counter() - t0

    t0 = time.perf_counter()
    out_ids = eventchat.generate(
        params, cfg, ids, pixels,
        max_new_tokens=args.max_new_tokens,
        temperature=args.temperature,
        top_p=args.top_p,
        eos_token_id=getattr(tokenizer, "eos_token_id", None),
        seed=args.seed,
        max_context=args.context_len,
        num_beams=args.num_beams,
        kv_quant=args.kv_cache == "int8",
        mesh=mesh,
        speculative=args.speculative,
        draft_head=(None if args.draft_head is None else
                    load_medusa(args.draft_head)),
    )
    t_gen = time.perf_counter() - t0

    answers = [a.strip() for a in
               tokenizer.batch_decode(out_ids, skip_special_tokens=True)]
    for path, answer in zip(files, answers):
        print(f"=== {path}\n{answer}")
    if args.timing:
        n = sum(len(o) for o in out_ids)
        print(f"[timing] load={t_load:.2f}s prep={t_prep:.2f}s "
              f"generate={t_gen:.2f}s ({n} tokens batch={len(files)}, "
              f"{n / t_gen:.2f} tok/s)", file=sys.stderr)

    if args.expected:
        with open(args.expected) as f:
            expected = json.load(f)
        if len(expected) != len(answers):
            raise ValueError(
                f"{len(expected)} expected answers for {len(answers)} samples"
            )
        failures = 0
        for path, got, want in zip(files, answers, expected):
            ok = got == want.strip()
            failures += not ok
            print(f"[{'PASS' if ok else 'FAIL'}] {path}", file=sys.stderr)
        if failures:
            print(f"{failures}/{len(answers)} transcript mismatches",
                  file=sys.stderr)
            sys.exit(1)
    return answers


if __name__ == "__main__":
    main()
