#!/usr/bin/env python3
"""Streaming event-QA demo: native threaded IO -> windowed model answers.

Connects the two halves the reference ships separately and never joins: the
C++ threaded event-stream consumer (EventsDataIO's PushData/PopDataUntil
seam, via the ctypes bridge) feeds 50 ms windows into the rasterize ->
CLIP -> projector -> LLM pipeline, answering the query once per window —
the "understanding of high-speed scenes within 50 ms" scenario the
reference README describes (README.md:119) as an actual running loop.

Usage:
  python scripts/stream_demo.py [--events stream.txt|structured.npy]
      [--model_path tiny-random] [--query "..."] [--window_ms 50]
      [--max_windows 3] [--paced] [--pace_factor 10]

Without --events, a structured npy is synthesized from the reference's
sample1.npy (whose on-disk form is a pickled dict the native reader
deliberately does not parse).

Threading note (audited by ``scripts/egpt_check.py``, ISSUE 8): the
only concurrency here lives INSIDE the native reader (its own C++
consumer thread behind the ctypes seam); the Python side runs the
rasterize -> CLIP -> LLM pipeline on the main thread with no shared
mutable Python state — nothing for the lock-discipline rule to guard,
and the scan keeps it that way.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

SAMPLE = "/root/reference/samples/sample1.npy"


def synthesize_stream(tmp_dir: str) -> str:
    """Reference sample (pickled dict) -> structured npy the native
    streaming reader consumes (shared layout helper in ops/raster)."""
    from eventgpt_tpu.ops.raster import events_to_structured_stream, load_event_npy

    path = os.path.join(tmp_dir, "stream_demo_events.npy")
    np.save(path, events_to_structured_stream(load_event_npy(SAMPLE)))
    return path


def main(argv=None):
    from eventgpt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser(description="Streaming event-QA demo")
    p.add_argument("--events", type=str, default=None,
                   help="txt ('t x y p') or structured npy stream")
    p.add_argument("--model_path", type=str, default="tiny-random")
    p.add_argument("--tokenizer_path", type=str, default=None)
    p.add_argument("--query", type=str, default="What is happening?")
    p.add_argument("--conv_mode", type=str, default="eventgpt_v1")
    p.add_argument("--window_ms", type=float, default=50.0)
    p.add_argument("--max_windows", type=int, default=3)
    p.add_argument("--max_new_tokens", type=int, default=32)
    p.add_argument("--paced", action="store_true",
                   help="replay at wall-clock rate")
    p.add_argument("--pace_factor", type=float, default=1.0)
    # prepare_model surface (parity with cli/infer.py).
    p.add_argument("--dtype", default="bfloat16", choices=["bfloat16", "float32"])
    p.add_argument("--quant", default="none", choices=["none", "int8"])
    p.add_argument("--speculative", type=int, default=0,
                   help="speculative greedy decode window (exact-equivalent; "
                        "cuts per-answer decode latency when text repeats)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--use_event_qformer", action="store_true")
    p.add_argument("--pretrain_query_embedder", type=str, default=None)
    p.add_argument("--pretrain_attention_layers", type=str, default=None)
    args = p.parse_args(argv)

    from eventgpt_tpu.cli.infer import load_model, prepare_model
    from eventgpt_tpu.data.conversation import prepare_event_prompt
    from eventgpt_tpu.data.tokenizer import tokenize_with_event
    from eventgpt_tpu.models import eventchat
    from eventgpt_tpu.native import EventStream, available
    from eventgpt_tpu.ops.image import clip_preprocess_batch
    from eventgpt_tpu.ops.raster import events_to_frames, events_window_us

    if not available():
        sys.exit("libegpt_native.so not built; run scripts/build_native.sh")

    events_path = args.events
    if events_path is None:
        if not os.path.exists(SAMPLE):
            sys.exit("no --events given and the reference sample is absent")
        import tempfile

        events_path = synthesize_stream(tempfile.gettempdir())
        print(f"[stream] synthesized {events_path} from sample1.npy",
              file=sys.stderr)

    cfg, params, tokenizer = load_model(
        args.model_path, args.dtype, None, args.tokenizer_path
    )
    cfg, params = prepare_model(cfg, params, tokenizer, args)
    input_ids = tokenize_with_event(
        prepare_event_prompt(args.query, args.conv_mode), tokenizer
    )

    window_s = args.window_ms / 1e3
    answered = 0
    # One consolidated array per field; events behind the cursor are dropped
    # after each emission round so memory and per-window work stay bounded
    # by the window population, not the whole recording.
    buf = {k: np.empty(0, d) for k, d in
           (("x", np.uint16), ("y", np.uint16), ("t", np.float64), ("p", np.uint8))}
    cursor = None

    with EventStream(events_path, paced=args.paced,
                     pace_factor=args.pace_factor) as stream:
        while answered < args.max_windows:
            out = stream.pop_until(1e18)  # drain whatever the producer has
            if out["t"].size:
                buf = {k: np.concatenate([buf[k], out[k]]) for k in buf}
            t_all = buf["t"]
            if cursor is None and t_all.size:
                cursor = float(t_all.min())
            # Emit every complete window currently in the buffer.
            while (cursor is not None and t_all.size
                   and (t_all.max() >= cursor + window_s
                        or not stream.running())
                   and answered < args.max_windows):
                sel = (t_all >= cursor) & (t_all < cursor + window_s)
                if sel.sum() >= cfg.num_event_frames:
                    ev = events_window_us(buf, sel)
                    t0 = time.perf_counter()
                    frames = events_to_frames(ev, cfg.num_event_frames)
                    pixels = clip_preprocess_batch(frames, cfg.vision.image_size)
                    out_ids = eventchat.generate(
                        params, cfg, [input_ids], pixels[None],
                        max_new_tokens=args.max_new_tokens, temperature=0.0,
                        eos_token_id=getattr(tokenizer, "eos_token_id", None),
                        speculative=args.speculative,
                    )[0]
                    answer = tokenizer.batch_decode(
                        [out_ids], skip_special_tokens=True
                    )[0].strip()
                    dt = time.perf_counter() - t0
                    print(f"[{cursor * 1e3:8.1f}ms +{args.window_ms:.0f}ms | "
                          f"{int(sel.sum())} events | {dt * 1e3:.0f} ms] "
                          f"{answer}")
                    answered += 1
                cursor += window_s
                if not stream.running() and t_all.max() < cursor:
                    break
            if cursor is not None and t_all.size:
                keep = t_all >= cursor  # windows only advance
                if not keep.all():
                    buf = {k: buf[k][keep] for k in buf}
                    t_all = buf["t"]
            if not stream.running() and (t_all.size == 0
                                         or (cursor is not None
                                             and t_all.max() < cursor)):
                break
            time.sleep(0.005)
    print(f"[stream] answered {answered} window(s)", file=sys.stderr)
    return answered


if __name__ == "__main__":
    main()
