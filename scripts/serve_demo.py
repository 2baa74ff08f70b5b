"""Continuous-batching server demo: N event-QA requests through one
resident decode batch (``eventgpt_tpu/serve.py``).

The reference answers one request per process (``inference.py``); here
requests join a running batch as rows free up — submit more queries than
``--max_batch`` and watch them stream through without a batch drain.

Threading note (audited by ``scripts/egpt_check.py``, ISSUE 8): this
demo drives the ``ContinuousBatcher`` from the main thread only —
consistent with the batcher's ``_EXTERNAL_LOCK`` single-owner contract
(here the owner is simply this script; no engine, no lock needed).
``scripts/`` is inside the suite's scan set, so a future edit that
spawns a thread around the batcher or mints an untracked jit gets
flagged, not merged.

Usage (offline smoke, tiny random weights):
  python scripts/serve_demo.py --event_frame /root/reference/samples/sample1.npy \
      --queries "What is happening?;Describe the scene.;What moves fastest?" \
      --max_batch 2 --max_new_tokens 24
Real checkpoints: --model_path <hf dir> (same loader as cli/infer.py).
"""

from __future__ import annotations

import argparse
import time


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model_path", default="tiny-random")
    p.add_argument("--tokenizer_path", default=None)
    p.add_argument("--event_frame", required=True,
                   help="event .npy to answer about; with --event_root, a "
                        "path relative to (and confined under) that root")
    p.add_argument("--event_root", default=None,
                   help="optional allowlist root: --event_frame must "
                        "resolve inside it (same confinement as "
                        "cli/serve.py — set this when the frame name "
                        "comes from anything other than your own shell)")
    p.add_argument("--queries", required=True,
                   help="';'-separated natural-language questions")
    p.add_argument("--conv_mode", default="eventgpt_v1")
    p.add_argument("--max_batch", type=int, default=2)
    p.add_argument("--max_len", type=int, default=1024)
    p.add_argument("--chunk", type=int, default=32)
    p.add_argument("--max_new_tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--dtype", default="bfloat16",
                   choices=["bfloat16", "float32"])
    p.add_argument("--quant", default="none", choices=["none", "int8"])
    p.add_argument("--fuse_params", action="store_true",
                   help="fuse qkv / gate-up before quantization (+4%% at "
                        "batch 8 on the r05 chip run)")
    p.add_argument("--kv_cache", default="bf16", choices=["bf16", "int8"])
    p.add_argument("--speculative", type=int, default=0,
                   help="verify-window size K (0 = plain decode)")
    p.add_argument("--draft_head", default=None,
                   help="trained Medusa head stack (.npz) for speculative "
                        "drafting (requires --speculative > 0)")
    p.add_argument("--warmup", action="store_true",
                   help="precompile every (bucket, segment) executable "
                        "before serving (ContinuousBatcher.warmup)")
    p.add_argument("--prefill_chunk", type=int, default=0,
                   help="decode-interleaved admission prefill chunk "
                        "(0 = one-shot admission prefill)")
    p.add_argument("--no_pipeline", action="store_true",
                   help="disable the pipelined scheduler (synchronous "
                        "segment dispatch; chains are identical either "
                        "way)")
    p.add_argument("--first_chunk", type=int, default=0,
                   help="TTFT ramp: short segment while a fresh admission "
                        "owes its first token (0 = off)")
    p.add_argument("--mesh_data", type=int, default=1)
    p.add_argument("--mesh_fsdp", type=int, default=1)
    p.add_argument("--mesh_model", type=int, default=1)
    p.add_argument("--seed", type=int, default=0)
    # prepare_model (shared with the infer/eval CLIs) reads these:
    p.add_argument("--use_event_qformer", action="store_true")
    p.add_argument("--pretrain_query_embedder", default=None)
    p.add_argument("--pretrain_attention_layers", default=None)
    args = p.parse_args(argv)

    frame = args.event_frame
    if args.event_root is not None:
        # Fail before touching the model: same confinement as cli/serve.py.
        from eventgpt_tpu.utils.paths import resolve_event_path

        frame = resolve_event_path(args.event_root, frame)

    from eventgpt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from eventgpt_tpu.cli.infer import load_model, prepare_model
    from eventgpt_tpu.data.conversation import prepare_event_prompt
    from eventgpt_tpu.data.tokenizer import tokenize_with_event
    from eventgpt_tpu.ops.image import process_event_file
    from eventgpt_tpu.serve import ContinuousBatcher

    from eventgpt_tpu.parallel.serving import build_serving_mesh

    cfg, params, tokenizer = load_model(
        args.model_path, args.dtype, None, args.tokenizer_path
    )
    # Mesh goes through prepare_model so the host tree lands sharded —
    # never a full unsharded copy on one chip first (cli/serve.py has the
    # same rule).
    mesh = build_serving_mesh(args.mesh_data, args.mesh_fsdp, args.mesh_model)
    cfg, params = prepare_model(cfg, params, tokenizer, args, mesh=mesh)
    _, pixels = process_event_file(
        frame, cfg.num_event_frames, cfg.vision.image_size
    )

    draft_head = None
    if args.draft_head:
        from eventgpt_tpu.models.medusa import load_medusa

        draft_head = load_medusa(args.draft_head)
    srv = ContinuousBatcher(
        params, cfg, max_batch=args.max_batch, max_len=args.max_len,
        chunk=args.chunk, temperature=args.temperature,
        eos_token_id=getattr(tokenizer, "eos_token_id", None),
        kv_quant=args.kv_cache == "int8", speculative=args.speculative,
        mesh=mesh, prefill_chunk=args.prefill_chunk,
        draft_head=draft_head, first_chunk=args.first_chunk,
        pipeline=not args.no_pipeline,
    )
    if args.warmup:
        t0 = time.perf_counter()
        n = srv.warmup()
        print(f"[warmup: {n} executables in {time.perf_counter() - t0:.2f}s]")
    queries = [q for q in args.queries.split(";") if q.strip()]
    t0 = time.perf_counter()
    rids = {}
    for q in queries:
        ids = tokenize_with_event(
            prepare_event_prompt(q.strip(), args.conv_mode), tokenizer
        )
        rids[srv.submit(ids, pixels, args.max_new_tokens)] = q.strip()
    out = srv.run_until_drained()
    dt = time.perf_counter() - t0
    tot = 0
    for rid, q in rids.items():
        answer = tokenizer.batch_decode([out[rid]],
                                        skip_special_tokens=True)[0].strip()
        tot += len(out[rid])
        print(f"Q: {q}\nA: {answer}\n")
    print(f"[{len(queries)} requests, {tot} tokens, {dt:.2f}s, "
          f"{tot / dt:.1f} tok/s aggregate]")
    return out


if __name__ == "__main__":
    main()
