#!/usr/bin/env python3
"""Device time of the event tower with its attention in the Pallas kernel
(``ops/clip_attention.py``) and in the XLA form, on the chip.

For waves of 1, 2, 4 and 8 requests (5 frames of 577 tokens each):

* ``encode_events_batch`` (CLIP ViT-L/14-336 in bfloat16, projector,
  pool), once with each form of the attention (``sharded=True`` keeps the
  XLA form, as under a mesh), and the largest absolute difference between
  the two outputs;
* the attention alone, one layer (N frames x 577 x 16 heads x 64), in the
  XLA form and in the kernel as served, with the largest absolute
  difference between them: the form's 24 layers against the whole tower
  say what share of it the attention is.

Each program runs ``--calls`` times under the profiler; its time is the
device self time of its operations over the calls
(``scripts/trace_scopes.py``), and the tower's programs' ten largest
operations are listed. Weights are random at the initialiser's deviations
and pixels normal, from ``--seed``. A chip run only: the CPU gives no time
(``--rehearsal`` under ``JAX_PLATFORMS=cpu`` runs a toy tower through the
same code, both towers in the XLA form and the kernel in interpreter
mode, and prints no time). It is not run by any cell.

Usage: python3 scripts/tower_timing.py [--out DIR] [--calls 20] [--seed 0]
       JAX_PLATFORMS=cpu python3 scripts/tower_timing.py --rehearsal
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import json
import os
import shutil
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from eventgpt_tpu.config import EventChatConfig, VisionConfig  # noqa: E402
from eventgpt_tpu.models import clip, eventchat  # noqa: E402
from eventgpt_tpu.models import projector as proj_mod  # noqa: E402
from eventgpt_tpu.ops import clip_attention as ca  # noqa: E402
from eventgpt_tpu.utils.platform import backend_platform  # noqa: E402
from scripts.trace_scopes import seconds_by_scope  # noqa: E402

WAVES = (1, 2, 4, 8)
FRAMES = 5


def model(toy: bool, seed: int):
    cfg = EventChatConfig()
    if toy:
        cfg = dataclasses.replace(cfg, vision=VisionConfig(
            hidden_size=1024, intermediate_size=256, num_layers=1,
            num_heads=16, image_size=28, patch_size=14))
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    params = {"clip": clip.init_clip_params(cfg.vision, k1, jnp.bfloat16),
              "projector": proj_mod.init_projector_params(
                  cfg.projector, k2, jnp.bfloat16)}
    return cfg, params


def named(fn, name: str):
    fn.__name__ = name
    return jax.jit(fn)


def towers(cfg, params, seed: int):
    """(program name -> (jitted fn, args), pairs: (kernel, xla) names)."""
    progs, pairs = {}, []
    v = cfg.vision
    for wave in WAVES:
        pixels = jax.random.normal(
            jax.random.PRNGKey(seed + wave),
            (wave, FRAMES, v.num_channels, v.image_size, v.image_size),
            jnp.float32).astype(jnp.bfloat16)
        for form, sharded in (("kernel", False), ("xla", True)):
            def fn(params, pixels, sharded=sharded):
                return eventchat.encode_events_batch(params, cfg, pixels,
                                                     sharded=sharded)
            name = f"tower_{form}_wave{wave}"
            progs[name] = (named(fn, name), (params, pixels))
        pairs.append((f"tower_kernel_wave{wave}", f"tower_xla_wave{wave}"))
        n = wave * FRAMES
        keys = jax.random.split(jax.random.PRNGKey(seed + 100 + wave), 3)
        q, k, vv = (jax.random.normal(key, (n, v.num_tokens, v.hidden_size),
                                      jnp.float32).astype(jnp.bfloat16)
                    for key in keys)
        q = (q / np.sqrt(v.head_dim)).astype(jnp.bfloat16)
        for form, attend in (("kernel", ca.clip_attention),
                             ("xla", ca.xla_attention)):
            def fn(q, k, vv, attend=attend):
                return attend(q, k, vv, v.num_heads)
            name = f"attn_{form}_n{n}"
            progs[name] = (named(fn, name), (q, k, vv))
        pairs.append((f"attn_kernel_n{n}", f"attn_xla_n{n}"))
    return progs, pairs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=".bench_out/tower_timing")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    chip = backend_platform() == "tpu"
    if not chip and not args.rehearsal:
        sys.stderr.write("a chip run only (--rehearsal on the CPU)\n")
        return 2
    cfg, params = model(args.rehearsal, args.seed)
    progs, pairs = towers(cfg, params, args.seed)
    outputs = {name: np.asarray(fn(*a), np.float32)
               for name, (fn, a) in progs.items()}
    gaps = {}
    for got, want in pairs:
        gaps[got] = float(np.max(np.abs(outputs[got] - outputs[want])))
        print(f"[tower] {got}: largest |difference| to {want} "
              f"{gaps[got]:.4g} (largest |value| "
              f"{float(np.max(np.abs(outputs[want]))):.4g})", flush=True)
    if not chip:
        print("[tower] rehearsal: no time on the CPU")
        return 0
    os.makedirs(args.out, exist_ok=True)
    # One capture a program: two programs of one shape share an executable's
    # fingerprint, and a capture names both after one of them.
    table = {}
    for name, (fn, a) in progs.items():
        where = os.path.join(args.out, name)
        jax.profiler.start_trace(where)
        for _ in range(args.calls):
            r = fn(*a)
        jax.block_until_ready(r)
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(where, "**", "*.xplane.pb"),
                                recursive=True))[-1]
        ops = collections.defaultdict(float)
        for (_, _, op), sec in seconds_by_scope(path, by_op=True).items():
            ops[op] += 1e3 * sec / args.calls
        table[name] = {"ms": sum(ops.values()), "gap": gaps.get(name),
                       "top_ops_ms": dict(sorted(
                           ops.items(), key=lambda kv: -kv[1])[:10])}
        print(f"[tower] {name:32s} {table[name]['ms']:9.4f} ms a call",
              flush=True)
        if name.startswith("tower_"):
            for op, ms in table[name]["top_ops_ms"].items():
                print(f"[tower]     {ms:9.4f} ms  {op}", flush=True)
        shutil.rmtree(where)
    layers = cfg.vision.num_layers
    for wave in WAVES:
        n = wave * FRAMES
        kernel, xla = (table[f"tower_{f}_wave{wave}"]["ms"]
                       for f in ("kernel", "xla"))
        attn, attn_kernel = (table[f"attn_{f}_n{n}"]["ms"] * layers
                             for f in ("xla", "kernel"))
        print(f"[tower] wave {wave}: tower {xla:.3f} -> {kernel:.3f} ms "
              f"({xla / wave:.3f} -> {kernel / wave:.3f} a request); the "
              f"attention x {layers} layers {attn:.3f} -> {attn_kernel:.3f} "
              f"ms, the XLA form's {100 * attn / xla:.1f} % of its tower",
              flush=True)
    with open(os.path.join(args.out, "times.json"), "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
