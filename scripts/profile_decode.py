#!/usr/bin/env python3
"""Capture a jax.profiler trace of the decode loop and print an op-time
breakdown — the tool behind PERFORMANCE.md's decomposition.

Runs the product decode path (flash prefill + whole-budget while_loop) at a
chosen preset/quantization, traces one timed loop invocation, then parses the
chrome-trace export to attribute device time to fusions. On a v5e this is
how the KV-cache-restacking copies (~2 ms/token) and the per-dispatch
overhead of the r05-era set-up were isolated.

Usage:
  python scripts/profile_decode.py [--preset 7b|13b|tiny] [--quant int8|int4|bf16]
      [--decode_tokens 64] [--trace_dir /tmp/egpt-trace] [--top 20]
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def capture(args) -> str:
    """Run + trace one decode-loop invocation; stamps a meta.json next to
    the trace so later --summarize_only runs divide by the right budget."""
    import jax
    import jax.numpy as jnp

    from bench import _build_params, _event_pixels, _sync
    from eventgpt_tpu.config import EventChatConfig
    from eventgpt_tpu.data.tokenizer import split_at_event
    from eventgpt_tpu.models import eventchat, llama as llama_mod
    from eventgpt_tpu.models.eventchat import (
        _decode_loop_jit, _pad_batch, _prefill_jit, splice_embeddings,
    )

    cfg = {"7b": EventChatConfig.eventgpt_7b,
           "13b": EventChatConfig.eventgpt_13b,
           "tiny": EventChatConfig.tiny}[args.preset]()
    dtype = jnp.bfloat16
    quant = args.quant if args.preset in ("7b", "13b") else "bf16"
    if quant != args.quant:
        print(f"[profile] preset {args.preset} forces quant={quant} "
              f"(requested {args.quant})", file=sys.stderr)
    print(f"[profile] preset={args.preset} quant={quant} "
          f"decode_tokens={args.decode_tokens}", file=sys.stderr)
    params = _build_params(cfg, dtype, quant)
    pixels = jnp.asarray(_event_pixels(cfg, 1), dtype)
    ev = eventchat.encode_events_batch(params, cfg, pixels)
    _sync(ev)

    ids = [1] + [7] * 34 + [-200] + [9] * 16
    embeds = [splice_embeddings(params, cfg, split_at_event(ids), ev[0])]
    padded, mask, _ = _pad_batch(embeds)
    prompt_len = 35 + cfg.num_event_tokens + 16
    cache_len = ((prompt_len + args.decode_tokens + 64) // 64) * 64

    def prefill_once():
        cache = llama_mod.init_kv_cache(cfg.llama, 1, cache_len, dtype)
        return _prefill_jit(params, cfg, padded, mask, cache, True)

    key = jax.random.PRNGKey(0)

    def loop(lg, cch):
        toks, n, cch = _decode_loop_jit(
            params, cfg, lg, cch, key, args.decode_tokens, 0.0, 1.0, -1
        )
        del cch  # returned only for donation aliasing
        return toks, n

    last, cache = prefill_once()
    _sync(last)
    toks, _ = loop(last, cache)  # compile
    _sync(toks)
    last, cache = prefill_once()
    _sync(last)
    with jax.profiler.trace(args.trace_dir):
        toks, _ = loop(last, cache)
        _sync(toks)
    with open(os.path.join(args.trace_dir, "meta.json"), "w") as f:
        json.dump({"decode_tokens": args.decode_tokens,
                   "preset": args.preset, "quant": quant}, f)
    return args.trace_dir


def summarize(trace_dir: str, decode_tokens: int, top: int) -> None:
    meta_path = os.path.join(trace_dir, "meta.json")
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
        if meta.get("decode_tokens") != decode_tokens:
            print(f"[profile] trace was captured with decode_tokens="
                  f"{meta.get('decode_tokens')}; using that for the "
                  f"per-token math", file=sys.stderr)
            decode_tokens = int(meta["decode_tokens"])
    paths = glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.trace.json.gz"))
    if not paths:
        sys.exit(f"no chrome trace found under {trace_dir}")
    with gzip.open(sorted(paths)[-1], "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    pids = {e["pid"]: e["args"].get("name", "")
            for e in events if e.get("ph") == "M" and e.get("name") == "process_name"}
    dev_pids = {p for p, n in pids.items() if "TPU" in n or "/device" in n.lower()}
    tot, cnt = collections.Counter(), collections.Counter()
    for e in events:
        if e.get("ph") == "X" and e.get("pid") in dev_pids:
            tot[e.get("name", "?")] += e.get("dur", 0)
            cnt[e.get("name", "?")] += 1
    # The whole-loop spans double-count their children; report them first,
    # then per-op rows.
    loops = [(n, d) for n, d in tot.items() if n.startswith(("jit_", "while"))]
    for name, dur in sorted(loops, key=lambda x: -x[1]):
        print(f"{dur / 1e3:9.2f} ms  total   {name[:80]}")
    if loops:
        per_tok = max(d for _, d in loops) / 1e3 / decode_tokens
        print(f"-> device-side {per_tok:.2f} ms/token "
              f"({1e3 / per_tok:.1f} tok/s before dispatch overhead)")
    print(f"{'ms':>9}  {'count':>6}  op")
    shown = 0
    for name, dur in tot.most_common():
        if name.startswith(("jit_", "while")):
            continue
        print(f"{dur / 1e3:9.2f}  {cnt[name]:6d}  {name[:80]}")
        shown += 1
        if shown >= top:
            break


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="7b", choices=["7b", "13b", "tiny"])
    p.add_argument("--quant", default="int8", choices=["int8", "int4", "bf16"])
    p.add_argument("--decode_tokens", type=int, default=64)
    p.add_argument("--trace_dir", default="/tmp/egpt-trace")
    p.add_argument("--top", type=int, default=20)
    p.add_argument("--summarize_only", action="store_true",
                   help="skip capture; parse an existing --trace_dir")
    args = p.parse_args()
    if not args.summarize_only:
        capture(args)
    summarize(args.trace_dir, args.decode_tokens, args.top)


if __name__ == "__main__":
    main()
