"""Headline benchmarks on the real chip.

Prints exactly one JSON line per run:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

The default ``--mode all`` records the full north-star picture in ONE
record (VERDICT r2 weak #1: the driver artifact must carry the strongest
truthful numbers, not the 64-token smoke config):

  * headline: 7B batch-1 decode tok/s at the REFERENCE run shape —
    512 new tokens (``/root/reference/inference.py:19``), int8 weights,
    flash prefill, whole-budget ``lax.while_loop`` decode (one dispatch).
  * batch sweep at the same budget (bf16 KV, int8-KV fallback where bf16
    OOMs — the 16 GB chip limit is recorded, not hidden).
  * 13B single-chip decode (int8 — the only way 13B fits one v5e).
  * stage-2 QLoRA train-step time (second north-star metric).
  * warm-start: encode/prefill first-call latency in a FRESH process with
    the persistent compilation cache populated (cold-start story,
    ``eventgpt_tpu/utils/compile_cache.py``).
  * continuous-batching serving (batch-4 bf16-KV and batch-8 int8-KV):
    aggregate tok/s plus the latency story — TTFT / completion
    percentiles, admission stall, first-request latency on a warmed
    server (VERDICT r3: the serving story must reach the artifact).

Each leg runs in its own subprocess: HBM is returned between legs (7B
int8 + 13B int8 cannot coexist on a 16 GB chip) and the warm-start
numbers are honest second-process measurements by construction.

Modes for manual use: --mode decode|train|warm_probe|spec|serve with
--preset {auto,7b,13b,tiny} --decode_tokens N --batch N
--quant {int8,int4,bf16} --kv {bf16,int8} --sweep --seq N --steps N.

Measurement rules: every timing ends in ``block_until_ready`` (dispatch is
asynchronous), and whole-model loops are what the records compare. The
per-dispatch cost the older records were tuned around predates this round
of work and is to be re-measured (ROADMAP S1).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SAMPLE = "/root/reference/samples/sample1.npy"


def _sync(x) -> None:
    """The timing fence: dispatch is asynchronous, so a clock is read only
    after the work it brackets has finished on the device."""
    import jax

    jax.block_until_ready(x)


def _build_params(cfg, dtype, quant: str, fuse: bool = False,
                  zeros: bool = False):
    """Device param tree from the package's checkpoint-free builder
    (``eventgpt_tpu/models/synthetic.py``, the one ``--model_path
    eventgpt-7b-random`` loads): seeded random values at the served (fused /
    quantized) shapes, built on host so HBM never holds bf16 + int8 copies
    at once. ``zeros`` gives the all-zero tree of the same shapes, whose
    greedy chain is constant — the speculation legs' acceptance ceiling."""
    import jax
    import jax.numpy as jnp

    from eventgpt_tpu.cli.infer import place_params
    from eventgpt_tpu.models import synthetic

    quant = quant if quant in ("int8", "int4") else "none"
    if zeros:
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            synthetic.served_shapes(cfg, dtype, quant, fuse))
    return place_params(
        synthetic.random_eventchat_params(cfg, dtype, quant, fuse), dtype)


def _event_pixels(cfg, batch):
    import numpy as np

    if os.path.exists(SAMPLE):
        from eventgpt_tpu.ops.image import process_event_file

        _, pixels = process_event_file(SAMPLE, cfg.num_event_frames, cfg.vision.image_size)
    else:
        pixels = np.zeros(
            (cfg.num_event_frames, 3, cfg.vision.image_size, cfg.vision.image_size),
            np.float32,
        )
    return np.stack([pixels] * batch)


def _emit(record, mode: str, value: float):
    """Attach vs_baseline from (or create) the committed per-mode baseline."""
    path = os.path.join(HERE, "bench_baseline.json" if mode == "decode"
                        else f"bench_{mode}_baseline.json")
    vs = 1.0
    if os.path.exists(path):
        with open(path) as f:
            base = json.load(f)
        if base.get("metric") == record["metric"] and base.get("value"):
            ratio = value / base["value"]
            # Lower is better for time metrics ("s/step", "s", "ms").
            is_time = record["unit"].startswith("s") or record["unit"] == "ms"
            vs = round(1.0 / ratio if is_time else ratio, 3)
    else:
        with open(path, "w") as f:
            json.dump(record, f)
    record["vs_baseline"] = vs
    print(json.dumps(record))
    return record


def _resolve_preset(args):
    import jax

    platform = jax.devices()[0].platform
    preset = args.preset
    if preset == "auto":
        preset = "7b" if platform == "tpu" else "tiny"
    from eventgpt_tpu.config import EventChatConfig

    cfg = {"7b": EventChatConfig.eventgpt_7b,
           "13b": EventChatConfig.eventgpt_13b,
           "tiny": EventChatConfig.tiny}[preset]()
    return preset, cfg, platform


def _procfleet_preset(args):
    """(preset, cfg, platform) for a parent of process-fleet workers,
    without touching a device: a parent that initialised a backend would
    hold the chip its workers need. The workers load ``tiny-random``
    themselves, so that is the one preset; the platform is the one the
    workers were started for — they refuse any other
    (``utils/platform.backend_platform``)."""
    from eventgpt_tpu.config import EventChatConfig
    from eventgpt_tpu.utils.platform import requested_platform

    if args.preset not in ("auto", "tiny"):
        raise SystemExit(
            "process-fleet workload legs support the tiny preset only "
            "(workers load --model_path tiny-random themselves)")
    return "tiny", EventChatConfig.tiny(), requested_platform()


def _journey_attribution(journeys, class_of, n_exemplars=3):
    """Tail-latency attribution from flight-recorder timelines
    (ISSUE 10): per SLO class, the p99 of every decomposition phase
    plus the share of TAIL latency each phase owns (the slowest ~10%
    of the class's requests, by phase-sum over e2e-sum) — so a p99
    story reads "61% queue + 24% defer", not a bare number. Returns
    (per_class_extras, leg_extras): leg extras carry a zero-filled
    miss-cause breakdown (every cause key always present, so
    compare_bench --require stays satisfiable) and the slowest-K
    exemplar timelines.

    ``journeys``: {trace idx: journey record or None} — records need
    ``phases``/``e2e_s`` (finished + recorder armed)."""
    import numpy as np

    from eventgpt_tpu.obs.journey import MISS_CAUSES, PHASE_KEYS

    by_class = {}
    for idx, j in journeys.items():
        if j and j.get("phases") and j.get("e2e_s") is not None:
            by_class.setdefault(class_of[idx], []).append(j)
    per_class = {}
    for cname in sorted(set(class_of.values())):
        items = by_class.get(cname, [])
        if not items:
            per_class[cname] = {
                **{f"{k[:-2]}_p99_s": 0.0 for k in PHASE_KEYS},
                "attribution": {k: 0.0 for k in PHASE_KEYS},
            }
            continue
        e2e = np.asarray([j["e2e_s"] for j in items], float)
        cols = {k: np.asarray([j["phases"].get(k, 0.0) for j in items],
                              float) for k in PHASE_KEYS}
        out = {f"{k[:-2]}_p99_s": round(float(np.percentile(v, 99)), 4)
               for k, v in cols.items()}
        k_tail = max(1, len(items) // 10)
        order = np.argsort(e2e)[::-1][:k_tail]
        tail_e2e = float(e2e[order].sum()) or 1.0
        out["attribution"] = {
            k: round(float(cols[k][order].sum()) / tail_e2e, 4)
            for k in PHASE_KEYS}
        per_class[cname] = out
    miss = {c: 0 for c in MISS_CAUSES}
    for j in journeys.values():
        if j and j.get("slo_met") is False:
            miss[j.get("cause") or "other"] = \
                miss.get(j.get("cause") or "other", 0) + 1
    slow = sorted((j for j in journeys.values()
                   if j and j.get("phases")),
                  key=lambda j: -j["e2e_s"])[:n_exemplars]
    leg = {
        "miss_causes": miss,
        "slowest": [{
            "rid": j["rid"],
            "slo_class": j.get("slo_class"),
            "status": j.get("status"),
            "slo_met": j.get("slo_met"),
            "cause": j.get("cause"),
            "e2e_s": round(j["e2e_s"], 4),
            "phases": {k: round(float(v), 4)
                       for k, v in j["phases"].items()},
            "events": j["events"],
        } for j in slow],
    }
    return per_class, leg


def run_decode(args):
    import jax
    import jax.numpy as jnp

    from eventgpt_tpu.data.tokenizer import split_at_event
    from eventgpt_tpu.models import eventchat, llama as llama_mod
    from eventgpt_tpu.models.eventchat import (
        _decode_loop_jit, _pad_batch, _prefill_jit, splice_embeddings,
    )

    preset, cfg, platform = _resolve_preset(args)
    dtype = jnp.bfloat16
    params = _build_params(cfg, dtype,
                           args.quant if preset in ("7b", "13b") else "bf16",
                           fuse=args.fuse)

    pixels = jnp.asarray(_event_pixels(cfg, 1), dtype)
    ids = [1] + [7] * 34 + [-200] + [9] * 16
    prompt_len = 35 + cfg.num_event_tokens + 16

    t0 = time.perf_counter()
    ev = eventchat.encode_events_batch(params, cfg, pixels)
    _sync(ev)
    t_encode_compile = time.perf_counter() - t0

    def _to_paged_cache(cache, bs=64):
        """Re-shape a prefilled dense cache into the paged block-pool
        pytree (ISSUE 12): dense (L, B, S, ...) rows become B*S/bs pool
        blocks behind row-major block tables (+ the reserved scratch
        block 0). Pure reshape/concat — the VALUES are identical, so the
        decode loop's paged chain is the dense chain and the measured
        delta is exactly the block-table gather cost."""
        def pool(buf):
            if isinstance(buf, dict):
                return {"q": pool(buf["q"]), "s": pool(buf["s"])}
            l, b, s = buf.shape[:3]
            blocks = buf.reshape((l, b * (s // bs), bs) + buf.shape[3:])
            return jnp.concatenate(
                [jnp.zeros_like(blocks[:, :1]), blocks], axis=1)

        k_buf = cache["k"]["q"] if isinstance(cache["k"], dict) \
            else cache["k"]
        _, b, s = k_buf.shape[:3]
        nbpr = s // bs
        bt = 1 + jnp.arange(b * nbpr, dtype=jnp.int32).reshape(b, nbpr)
        return {"k": pool(cache["k"]), "v": pool(cache["v"]), "bt": bt,
                "length": cache["length"]}

    def measure(batch: int, kv: str, phase_box: dict = None,
                layout: str = "dense"):
        # ``phase_box`` (ISSUE 9): records which PHASE an OOM escapes
        # from — "compile" until the decode loop's first call (XLA
        # compile + first dispatch at the new shapes) has synced,
        # "runtime" for the measured steady-state run — so the batch
        # sweep can capture OOM as data instead of a dead leg.
        if phase_box is not None:
            phase_box["phase"] = "compile"
        embeds = [
            splice_embeddings(params, cfg, split_at_event(ids), ev[0])
            for _ in range(batch)
        ]
        padded, mask, lens = _pad_batch(embeds)
        # +1: the fused loop's unconditional advance writes one slot past the
        # budget; 64-step rounding keeps cache slack small (the cache is the
        # dominant batched-decode allocation at 7B).
        cache_len = ((prompt_len + args.decode_tokens + 64) // 64) * 64

        def prefill_once():
            cache = llama_mod.init_kv_cache(
                cfg.llama, batch, cache_len, dtype, quant=kv == "int8"
            )
            last, cache = _prefill_jit(params, cfg, padded, mask, cache, True)
            if layout == "paged":
                cache = _to_paged_cache(cache)
            return last, cache

        t0 = time.perf_counter()
        last, cache = prefill_once()
        _sync(last)
        t_prefill_first = time.perf_counter() - t0

        key = jax.random.PRNGKey(0)
        # eos=-1 never matches -> the loop always runs the full budget.
        # The trailing cache return exists only for donation aliasing; drop
        # it right away so it never holds a second copy live.
        def loop(lg, cch):
            toks, n, cch = _decode_loop_jit(
                params, cfg, lg, cch, key, args.decode_tokens, 0.0, 1.0, -1
            )
            del cch
            return toks, n

        toks, _ = loop(last, cache)  # compile
        _sync(toks)
        if phase_box is not None:
            phase_box["phase"] = "runtime"

        t0 = time.perf_counter()
        last2, cache2 = prefill_once()
        _sync(last2)
        t_prefill = time.perf_counter() - t0
        # Free before the measured run: a second live cache would shift the
        # sweep's bf16-vs-int8 OOM boundary (the thing being recorded).
        del last2, cache2

        last, cache = prefill_once()
        _sync(last)
        t0 = time.perf_counter()
        toks, _ = loop(last, cache)
        _sync(toks)
        dt = time.perf_counter() - t0
        return args.decode_tokens * batch / dt, t_prefill, t_prefill_first

    tok_s, t_prefill, t_prefill_first = measure(args.batch, args.kv)

    extras = {
        "quant": args.quant if preset in ("7b", "13b") else "bf16",
        "kv_cache": args.kv,
        "batch": args.batch,
        "decode_tokens": args.decode_tokens,
        "prefill_s": round(t_prefill, 3),
        "prefill_first_s": round(t_prefill_first, 3),
        "encode_first_s": round(t_encode_compile, 3),
        "attn_impl": cfg.llama.attn_impl,
        "platform": platform,
    }
    if args.sweep:
        def is_oom(e):
            return any(s in str(e) for s in
                       ("RESOURCE_EXHAUSTED", "ResourceExhausted",
                        "Ran out of memory"))

        sweep, sweep_kv, sweep_retries = {}, {}, {}
        sweep_oom, sweep_est = {}, {}
        sweep_paged, sweep_est_paged = {}, {}
        # Closed-form resident-bytes estimate per point (ISSUE 9): the
        # bytes-vs-batch curve PERFORMANCE.md "Batch scaling" needed —
        # weights + B dense rows at the leg's cache length, per KV
        # storage. The measured ceilings (b40 runtime / b48 compile on
        # 16 GB) are what the capacity model must predict.
        from eventgpt_tpu.obs import memory as obs_memory

        w_bytes = obs_memory.params_bytes(params)
        est_cache_len = ((prompt_len + args.decode_tokens + 64) // 64) * 64

        def point_est_bytes(b, kv, layout="dense"):
            pos = obs_memory.kv_pos_bytes(cfg, kv_quant=kv == "int8")
            if layout == "paged":
                # Block-pool closed form (ISSUE 12; mirrors
                # obs_memory.estimate's kv_pool + kv_block_table terms):
                # arena at this leg's USED tokens + scratch + tables.
                nbpr = est_cache_len // 64
                return (w_bytes + (b * nbpr + 1) * 64 * pos
                        + b * nbpr * 4 + b * 4)
            return w_bytes + b * (est_cache_len * pos + 4)

        # Monotonicity only holds among the sweep's own bf16 points; the
        # headline tok_s is a valid predecessor only for batch-1 bf16.
        prev = tok_s if (args.batch == 1 and args.kv == "bf16") else 0.0
        for b in (2, 4, 8):
            # bf16 KV first; where the cache no longer fits the 16 GB chip,
            # int8 KV (half the footprint) is the product answer
            # (cli/eval.py --kv_cache int8) — record which one ran.
            phase = {}
            try:
                r, _, _ = measure(b, "bf16", phase)
                if r < prev * 0.8:
                    # Aggregate decode throughput is monotone in batch on
                    # this chip; a point far below its predecessor is
                    # suspect (observed once on the r05-era set-up: 56
                    # tok/s at batch 8 vs 475 on the immediate re-run; not
                    # yet re-observed on this round's). One retry —
                    # BOTH measurements recorded (ADVICE r5: a silent
                    # max() can mask a real batch-scaling regression as a
                    # glitch; batch_sweep_retries keeps the evidence).
                    sys.stderr.write(
                        f"sweep batch {b}: {r:.1f} tok/s < 0.8x previous "
                        f"({prev:.1f}) — transient glitch, re-measuring\n")
                    r2, _, _ = measure(b, "bf16")
                    sweep_retries[str(b)] = {
                        "first": round(r, 2), "retry": round(r2, 2)}
                    r = max(r, r2)
                prev = max(prev, r)
                sweep[str(b)], sweep_kv[str(b)] = round(r, 2), "bf16"
                sweep_est[str(b)] = point_est_bytes(b, "bf16")
            except Exception as e:
                if not is_oom(e):
                    raise
                # OOM is DATA, not a dead leg (ISSUE 9): record which
                # phase each storage's attempt died in. "compile"
                # covers XLA compile + the first dispatch at the new
                # shapes (donated-buffer allocation happens there);
                # "runtime" means the compiled executable OOMed on the
                # measured steady-state run.
                sweep_oom[str(b)] = {"bf16": phase.get("phase", "compile")}
                try:
                    phase = {}
                    r, _, _ = measure(b, "int8", phase)
                    sweep[str(b)], sweep_kv[str(b)] = round(r, 2), "int8"
                    sweep_est[str(b)] = point_est_bytes(b, "int8")
                except Exception as e2:
                    if not is_oom(e2):
                        raise
                    sweep[str(b)], sweep_kv[str(b)] = "oom", "int8"
                    sweep_oom[str(b)]["int8"] = phase.get("phase",
                                                          "compile")
                    sweep_est[str(b)] = point_est_bytes(b, "int8")
            # Paged twin (ISSUE 12): the same point through the block
            # pool (dense prefill -> reshape into the arena -> block-
            # table decode; values identical, so the tok/s delta IS the
            # gather cost) with the block-pool closed form alongside —
            # OOM recorded as data like every other leg. Where the
            # dense attempt fell back to int8 KV, the paged twin pairs
            # at that same storage.
            kv_for = sweep_kv.get(str(b), "bf16")
            phase = {}
            try:
                r, _, _ = measure(b, kv_for, phase, layout="paged")
                sweep_paged[str(b)] = round(r, 2)
            except Exception as e:
                if not is_oom(e):
                    raise
                sweep_paged[str(b)] = "oom"
                sweep_oom.setdefault(str(b), {})["paged"] = \
                    phase.get("phase", "compile")
            sweep_est_paged[str(b)] = point_est_bytes(b, kv_for, "paged")
        extras["batch_sweep_tok_s"] = sweep
        extras["batch_sweep_kv"] = sweep_kv
        extras["batch_sweep_est_bytes"] = sweep_est
        extras["batch_sweep_tok_s_paged"] = sweep_paged
        extras["batch_sweep_est_bytes_paged"] = sweep_est_paged
        if sweep_oom:
            extras["batch_sweep_oom"] = sweep_oom
        if sweep_retries:
            extras["batch_sweep_retries"] = sweep_retries

    record = {
        "metric": f"tokens_per_sec_per_chip_{preset}_decode",
        "value": round(tok_s, 2),
        "unit": "tok/s",
        **extras,
    }
    return _emit(record, "decode", tok_s)


def run_spec(args):
    """Speculative-decoding leg: greedy decode through the n-gram-draft +
    K-token-verify loop (``models/eventchat.py:_spec_loop_jit``).

    An all-zero tree (``_build_params(zeros=True)``) produces a constant
    greedy chain, which the bigram lookup drafts perfectly — so the measured tok/s is the acceptance
    CEILING (every iteration commits the full window). The zero-acceptance
    FLOOR needs no separate run: every loop iteration costs the same wall
    time regardless of how many drafts verify (all shapes are static), so
    floor = iterations / dt — one committed token per iteration. Real
    checkpoints land between the two according to how repetitive the
    generated text is; tokens-per-iteration is recorded so the acceptance is
    read, never inferred. (A "random weights" floor was tried and rejected:
    random logits still collapse to a repetitive argmax chain — the dominant
    lm_head column wins for most hidden states — and the lookup drafts it.)
    """
    import jax.numpy as jnp
    import numpy as np

    from eventgpt_tpu.data.tokenizer import split_at_event
    from eventgpt_tpu.models import eventchat, llama as llama_mod
    from eventgpt_tpu.models.eventchat import (
        _pad_batch, _prefill_jit, _spec_loop_jit, _spliced_text_ids,
        splice_embeddings,
    )

    preset, cfg, platform = _resolve_preset(args)
    dtype = jnp.bfloat16
    quant = args.quant if preset in ("7b", "13b") else "bf16"
    params = _build_params(cfg, dtype, quant, zeros=True)

    pixels = jnp.asarray(_event_pixels(cfg, 1), dtype)
    ids = [1] + [7] * 34 + [-200] + [9] * 16
    window = args.spec_window
    ev = eventchat.encode_events_batch(params, cfg, pixels)
    embeds = [splice_embeddings(params, cfg, split_at_event(ids), ev[0])]
    padded, mask, lens = _pad_batch(embeds)
    prompt_len = int(lens[0])
    cache_len = ((prompt_len + args.decode_tokens + 2 * window + 64) // 64) * 64

    ids_host = np.full((1, cache_len), -1, np.int32)
    row = _spliced_text_ids(split_at_event(ids), cfg.num_event_tokens,
                            cfg.llama.max_seq_len)
    ids_host[0, : len(row)] = row
    plens = jnp.asarray(lens.astype(np.int32))

    def prefill_once():
        cache = llama_mod.init_kv_cache(cfg.llama, 1, cache_len, dtype,
                                        quant=args.kv == "int8")
        return _prefill_jit(params, cfg, padded, mask, cache, True)

    def loop(lg, cch):
        out, n_gen, n_iters, cch = _spec_loop_jit(
            params, cfg, lg, cch, jnp.asarray(ids_host), plens,
            args.decode_tokens, window, -1,
        )
        del cch  # returned only for donation aliasing
        return out, n_gen, n_iters

    last, cache = prefill_once()
    out, n_gen, n_iters = loop(last, cache)  # compile
    _sync(out)
    del out, n_gen, n_iters, last, cache  # 13B int8 + two caches is >16 GB
    last, cache = prefill_once()
    _sync(last)
    t0 = time.perf_counter()
    out, n_gen, n_iters = loop(last, cache)
    _sync(out)
    dt = time.perf_counter() - t0
    committed = min(int(n_gen[0]), args.decode_tokens)
    iters = int(n_iters)

    record = {
        "metric": f"spec_decode_{preset}",
        "value": round(committed / dt, 2),  # ceiling: zeros weights draft fully
        "unit": "tok/s",
        "window": window,
        "decode_tokens": committed,
        "iterations": iters,
        "tokens_per_iteration": round(committed / max(iters, 1), 2),
        # Zero-acceptance bound from the SAME run: one committed token per
        # iteration at the measured (shape-static) iteration cost.
        "floor_tok_s": round(iters / dt, 2),
        "kv_cache": args.kv,
        "quant": quant,
        "platform": platform,
    }
    print(json.dumps(record))
    return record


def run_serve(args):
    """Continuous-batching leg: N requests through the resident decode
    batch (``eventgpt_tpu/serve.py``). Part of ``--mode all`` since r4
    (VERDICT r3 weak #1/#2): emits the aggregate rate AND the latency
    story — per-request TTFT and completion percentiles, admission stall,
    and the first-request latency on a fresh (warmed) server."""
    import jax.numpy as jnp
    import numpy as np

    from eventgpt_tpu.obs import metrics as obs_metrics
    from eventgpt_tpu.serve import ContinuousBatcher

    # Telemetry A/B (--serve_telemetry 0 disarms the registry): the armed
    # run records the TTFT / inter-token-latency DISTRIBUTIONS into the
    # BENCH json, and the pair measures the instrumentation overhead
    # (<2% contract, PERFORMANCE.md "Telemetry overhead").
    telemetry = bool(args.serve_telemetry)
    obs_metrics.configure(telemetry)
    preset, cfg, platform = _resolve_preset(args)
    dtype = jnp.bfloat16
    quant = args.quant if preset in ("7b", "13b") else "bf16"
    params = _build_params(cfg, dtype, quant)
    pixels = _event_pixels(cfg, 1)[0]
    ids = [1] + [7] * 34 + [-200] + [9] * 16
    prompt_len = 35 + cfg.num_event_tokens + 16

    n_req = args.serve_requests
    srv = ContinuousBatcher(
        params, cfg, max_batch=args.serve_batch,
        max_len=((prompt_len + args.decode_tokens
                  + _spec_slack(args) + 128) // 128) * 128,
        chunk=args.serve_chunk, eos_token_id=None,
        kv_quant=args.kv == "int8",
        speculative=args.serve_spec,
        spec_buckets=args.serve_spec_buckets or None,
        prefill_chunk=args.serve_prefill_chunk,
        first_chunk=args.serve_first_chunk or 0,
        pipeline=bool(args.serve_pipeline),
        prefix_cache=bool(args.serve_prefix_cache),
        prefix_insert=bool(args.serve_cache_insert),
        prefill_budget=int(args.serve_prefill_budget),
        kv_layout=args.serve_kv_layout,
        kv_pool_blocks=int(args.serve_kv_pool_blocks),
    )
    # Multi-session traffic (ISSUE 4): --serve_sessions S > 0 serves S
    # distinct event streams round-robin — the prefix cache's target
    # shape (repeated system-prompt + per-session event-block heads).
    # S == 0 keeps the single-stream legacy traffic.
    sessions = max(int(args.serve_sessions), 0)
    if sessions:
        rngs = [np.random.default_rng(1000 + s) for s in range(sessions)]
        shape = (cfg.num_event_frames, 3, cfg.vision.image_size,
                 cfg.vision.image_size)
        session_pixels = [r.normal(size=shape).astype(np.float32)
                          for r in rngs]
    else:
        session_pixels = [pixels]
    if args.serve_prefix or (
            sessions and bool(args.serve_prefix_cache)
            and bool(args.serve_cache_insert)):
        # Session-style shared prefix: system text + the event block
        # (every request in this leg shares the stream); admissions
        # prefill only the 16-token query tail and skip CLIP encode.
        # The multi-session auto-cache legs install it too, BEFORE
        # warmup: the measured traffic recreates the same entry shapes,
        # and warmup() can only precompile suffix executables for
        # entries that exist — without this the cold window pays the
        # _prefix_prefill XLA compile on its first hit.
        srv.set_prefix(ids[: 1 + 34 + 1], pixel_values=session_pixels[0])
    t0 = time.perf_counter()
    warmed = srv.warmup(prompt_lens=[prompt_len]) if args.warmup else 0
    t_warm = time.perf_counter() - t0

    # First request on the fresh server: with --warmup this must cost
    # steady-state latency (nothing left to compile or load mid-service).
    t0 = time.perf_counter()
    r0 = srv.submit(ids, session_pixels[0], args.decode_tokens)
    first = srv.run_until_drained()
    t_first_req = time.perf_counter() - t0
    assert len(first[r0]) == args.decode_tokens

    def _fresh_cache():
        if (srv._prefix_cache is not None and sessions
                and bool(args.serve_cache_insert)):
            # Auto-populated cache: drop the warmup/priming entries so
            # the window that follows counts its cold misses honestly.
            # (Skipped when insert-on-prefill is off — there the
            # operator-set entry IS the leg being measured.) Through
            # the batcher's API: a hand-swapped cache would orphan a
            # paged server's pinned block runs (ISSUE 12).
            srv.reset_prefix_cache()

    if sessions and args.warmup:
        # Wave-executable priming (unmeasured): batcher.warmup() cannot
        # know the wave shapes traffic will produce, so replay the
        # measured window's cold trajectory once against a fresh cache —
        # burst 1 of S requests MISSES together (compiles the batched
        # encode + miss-wave prefill + scatter), burst 2 HITS together
        # (compiles the batched suffix wave). The measured window below
        # then pays zero XLA compile, like every other warmed leg.
        _fresh_cache()
        for burst in range(2):
            for i in range(min(sessions, srv.max_batch)):
                srv.submit(ids, session_pixels[i % len(session_pixels)], 4)
            srv.run_until_drained()
        if args.serve_prefill_budget:
            # Piggyback-lane executables (ISSUE 5): the synchronized
            # bursts above never open lanes (admissions land with no
            # actives), so replay one STAGGERED shape — a long-lived row
            # plus late joins — compiling the lane seed/extract jits at
            # the real lane bucket (warmup() already compiled the mixed
            # segments themselves).
            r = srv.submit(ids, session_pixels[0], 16)
            srv.step()
            srv.step()
            for i in (1, 2):
                srv.submit(ids, session_pixels[i % len(session_pixels)], 4)
            srv.run_until_drained()

    srv.reset_serving_stats()  # exclude the warmup/first-request phase
    _fresh_cache()
    obs_metrics.REGISTRY.reset()  # same phase scoping for the registry
    from eventgpt_tpu.obs import memory as obs_memory

    obs_memory.LEDGER.reset_peak()  # peak scoped to the measured window
    # --serve_stagger varies per-request budgets so rows finish (and
    # admission boundaries land) at DIFFERENT segments — the traffic
    # shape where stall-free admission matters; synchronized budgets
    # admit in whole waves with no one decoding, which never stalls
    # anyone. Deterministic, identical across A/B arms.
    budgets = [args.decode_tokens] * n_req
    if args.serve_stagger:
        # Stagger in SEGMENT-CHUNK units: co-admitted rows then finish
        # at different boundaries, so later admissions land while the
        # rest decode (budgets below the chunk spread would still finish
        # inside one segment and admit onto an idle batch).
        budgets = [max(args.serve_chunk // 2,
                       args.decode_tokens - (i % 4) * args.serve_chunk)
                   for i in range(n_req)]
    t0 = time.perf_counter()
    rids = [srv.submit(ids, session_pixels[i % len(session_pixels)],
                       budgets[i])
            for i in range(n_req)]
    out = srv.run_until_drained()
    dt = time.perf_counter() - t0
    tot = sum(len(out[r]) for r in rids)
    ttfts = np.array([srv.request_stats[r]["ttft_s"] for r in rids])
    lats = np.array([srv.request_stats[r]["latency_s"] for r in rids])
    # Memory ledger (ISSUE 9): every serve point records where the
    # bytes live — peak + component breakdown + the live-array
    # reconcile + the compiled executable footprint warmup probed.
    mem = obs_memory.LEDGER.summary()
    mem["reconcile"] = obs_memory.LEDGER.reconcile()
    mem["compiled"] = srv.compiled_footprint(probe=False)
    if args.serve_kv_layout == "paged":
        # Block-pool pressure over the measured window (ISSUE 12):
        # used/free blocks, COW copies, gate deferrals.
        mem["kv_blocks"] = srv.memory_summary().get("kv_blocks")
    record = {
        "metric": f"serve_aggregate_{preset}",
        "value": round(tot / dt, 2),
        "unit": "tok/s",
        "requests": n_req,
        "tokens": tot,
        "max_batch": srv.max_batch,
        "chunk": args.serve_chunk,
        "kv_layout": args.serve_kv_layout,
        "decode_tokens": args.decode_tokens,
        "ttft_p50_s": round(float(np.percentile(ttfts, 50)), 3),
        "ttft_p99_s": round(float(np.percentile(ttfts, 99)), 3),
        "latency_p50_s": round(float(np.percentile(lats, 50)), 3),
        "latency_p99_s": round(float(np.percentile(lats, 99)), 3),
        "first_chunk": args.serve_first_chunk or 0,
        "prefix_reuse": bool(args.serve_prefix),
        # Prefix-KV cache story (ISSUE 4): hit ratio over the measured
        # window (batcher-level counters — they count with telemetry
        # disarmed too), plus the admission-dispatch shape below when
        # the registry is armed.
        "sessions": sessions,
        "prefix_cache": bool(args.serve_prefix_cache),
        "prefix_cache_insert": bool(args.serve_cache_insert),
        **({k: v for k, v in [
            ("prefix_cache_hit_ratio",
             round(srv.prefix_cache_stats().get("hit_ratio", 0.0), 3)),
            ("prefix_cache_entries",
             srv.prefix_cache_stats().get("n_entries", 0)),
            ("prefix_cache_evictions",
             srv.prefix_cache_stats().get("evictions", 0)),
        ]} if args.serve_prefix_cache else {}),
        # Pipelined-scheduler overlap story (host-observable; definitions
        # in PERFORMANCE.md "Pipelined scheduling"): host_gap_s is the
        # host scheduler time between segments, device_segment_s the time
        # the host actually BLOCKED on the device, overlap_ratio the
        # fraction of host work hidden behind in-flight segments. The
        # synchronous path (--serve_pipeline 0) measures ~0 overlap by
        # construction — that difference IS the win being recorded.
        "pipeline": bool(args.serve_pipeline),
        "segments": srv.seg_count,
        "host_gap_s": round(srv.host_gap_s, 3),
        "device_segment_s": round(srv.device_segment_s, 3),
        "overlap_ratio": round(srv.overlap_ratio(), 3),
        "admission_stall_s": round(srv.admission_s, 3),
        "admission_max_stall_s": round(srv.admission_max_s, 3),
        # Stall-free admission (ISSUE 5): the per-boundary prompt-token
        # budget, the mixed-segment counters, and the acceptance
        # property — zero-token harvests while a lane was advancing must
        # be 0 (in-flight rows receive tokens during every admission
        # boundary).
        "prefill_budget": int(args.serve_prefill_budget),
        "serve_stagger": int(args.serve_stagger),
        "mixed_boundaries": srv.mixed_boundaries,
        "mixed_zero_token_boundaries": srv.mixed_zero_harvests,
        "mixed_prefill_tokens": srv.mixed_prefill_tokens,
        "first_request_s": round(t_first_req, 3),
        "mem_peak_bytes": mem["peak_bytes"],
        "memory": mem,
        "warmup": bool(args.warmup),
        "warmup_s": round(t_warm, 3),
        "warmed_executables": warmed,
        "prefill_chunk": args.serve_prefill_chunk,
        "kv_cache": args.kv,
        "speculative": args.serve_spec,
        "spec_buckets": args.serve_spec_buckets or "",
        **({"spec_tokens_per_iteration":
            round(srv.spec_tokens_per_iteration(), 2),
            **_spec_leg_columns(srv)}
           if srv.speculative else {}),
        "quant": quant,
        "platform": platform,
        "telemetry": telemetry,
    }
    if telemetry:
        # Registry snapshot: the latency DISTRIBUTIONS (log2-bucket
        # summaries), not just the means/percentiles numpy computed above
        # — so the perf trajectory carries shape, and the numbers are the
        # exact ones a live server would expose on /metrics.
        record["metrics"] = obs_metrics.REGISTRY.summary((
            "egpt_serve_ttft_seconds", "egpt_serve_itl_seconds",
            "egpt_serve_queue_wait_seconds", "egpt_serve_segment_seconds",
            "egpt_serve_batch_occupancy_rows",
            "egpt_serve_prefix_cache_", "egpt_serve_admission_wave_rows",
        ))
        # Admission-dispatch shape (ISSUE 4): counter-verified from the
        # same egpt_* registry a live server scrapes — N queued
        # admissions should cost ~1 "wave" dispatch, not N "full" ones,
        # and cache hits should move dispatches into the cheap "suffix"
        # bucket.
        disp = obs_metrics.SERVE_PREFILL_DISPATCHES
        record["prefill_dispatches"] = {
            k: int(disp.value(kind=k))
            for k in ("full", "wave", "chunk", "suffix", "suffix_wave",
                      "piggyback")
            if disp.value(kind=k)
        }
        record["prefill_dispatches_total"] = int(disp.total())
        wave_summary = obs_metrics.SERVE_ADMISSION_WAVE._summary()
        record["admission_wave_size_mean"] = round(
            float(wave_summary.get("mean", 0.0)), 2)
        record["admission_waves"] = int(wave_summary.get("count", 0))
        # Per-boundary admission-stall distribution (the A/B acceptance
        # number for ISSUE 5: budget-on p50 must undercut wave-only by
        # >= 50% on staggered multi-session traffic).
        adm = obs_metrics.SERVE_ADMISSION._summary()
        record["admission_p50_s"] = adm.get("p50", 0.0)
        record["admission_mean_s"] = adm.get("mean", 0.0)
        record["admission_observations"] = adm.get("count", 0)
    print(json.dumps(record))
    return record


def _spec_slack(args):
    """max_len slack for the largest speculation window a boundary can
    select (submit() reserves 1 + spec_max slots past the budget)."""
    buckets = [int(x) for x in
               str(getattr(args, "serve_spec_buckets", "") or "").split(",")
               if x.strip()]
    return max([int(args.serve_spec)] + buckets + [0])


def _spec_leg_columns(srv):
    """Adaptive-speculation sweep-leg columns (ISSUE 13): shared by the
    workload legs and the spec A/B record."""
    st = srv.spec_stats()
    out = {
        "accepted_per_dispatch": st["accepted_per_dispatch"],
        "spec_depth_mean": st["spec_depth_mean"],
        "spec_masked_rows": st["masked_rows"],
    }
    ad = st.get("adaptive")
    if ad is not None:
        out["spec_accept_ema"] = ad.get("accept_ema") or 0.0
        out["spec_switches"] = ad.get("switches", 0)
    return out


def _series_arm_leg(telemetry: bool):
    """Arm the time-series store for one workload leg (ISSUE 15):
    sub-second cadence sized to CPU-backend leg durations (a x16 leg
    lasts ~1 s), second-denominated fast/slow burn windows, and a
    fresh ring + alert state per leg so the fired counts are
    per-point. Returns the store (None disarmed)."""
    from eventgpt_tpu.obs import series as obs_series

    if not telemetry:
        obs_series.disable()
        return None
    # Tight cadence + short windows (CPU legs last seconds, not
    # minutes); the arrival gate swaps queue_trend's confirmation to
    # offered-load pressure — on this trace a lone ~14-deep burst at
    # x1 drains itself (EWMA ~27/s), while x16's recurring backlog
    # rides ~100/s arrivals.
    return obs_series.configure(
        interval_s=0.05, keep=4096, autostart=True,
        fast_window_s=0.25, slow_window_s=1.0,
        slo_min_finished=8, queue_min=2.0, queue_arrival_min=60.0,
        arm_samples=2, clear_samples=3)


def _series_leg_columns(store, duration_s: float) -> dict:
    """``leg["series"]`` (sampled timeline + whole-leg derivations) and
    ``leg["alerts"]`` (per-rule fired counts + the per-point firing
    log). Key names are deliberately outside compare_bench's direction
    patterns except goodput_ratio_min, which gates higher-is-better on
    purpose: a lower windowed-goodput floor under the same trace IS a
    regression."""
    from eventgpt_tpu.obs.series import ALERT_RULES

    if store is None:
        return {}
    store.stop()  # freeze the ring before reading it
    snap = store.snapshot(window_s=duration_s + 1.0, n=4096)
    al = store.alerts_snapshot()
    d = snap["derived"]
    series = {
        "interval_s": snap["interval_s"],
        "samples": snap["samples"],
        **{k: d[k] for k in ("request_rate_per_s", "token_rate_per_s",
                             "submit_rate_per_s", "arrival_rate_ewma",
                             "queue_depth_last", "queue_depth_max",
                             "goodput_ratio_min") if k in d},
        # The raw timeline (bounded): lists of dicts are flatten-inert
        # in compare_bench — audit data, not a gated metric.
        "points": snap["points"][-512:],
    }
    alerts = {
        "fired": {r: al["rules"][r]["fired"] for r in ALERT_RULES},
        "fired_total": sum(al["rules"][r]["fired"] for r in ALERT_RULES),
        "active_end": al["active"],
        "log": al["log"],
    }
    return {"series": series, "alerts": alerts}


def run_workload(args):
    """Trace-driven workload replay (ISSUE 6): open-loop replay of a
    seeded traffic trace (``eventgpt_tpu/workload.py`` — bursty
    arrivals, heavy-tailed lengths, session mixes) against the
    continuous batcher across an offered-load sweep, reporting
    **SLO-attainment goodput** (the Orca/Sarathi metric) alongside
    tok/s. Per sweep point: goodput (SLO-met requests/s), per-class
    TTFT/ITL/latency percentiles, prefix-cache hit ratio, admission
    stall and batch occupancy. ``--workload_ab_reps`` appends an
    INTERLEAVED A/B — telemetry+SLO scoring armed vs disarmed+plain
    submit — asserting chains stay byte-identical and measuring the
    instrumentation overhead against the <2% contract."""
    import numpy as np

    import jax.numpy as jnp

    from eventgpt_tpu import workload as wl
    from eventgpt_tpu.obs import metrics as obs_metrics
    from eventgpt_tpu.serve import ContinuousBatcher

    telemetry = bool(args.serve_telemetry)
    obs_metrics.configure(telemetry)
    procfleet = int(getattr(args, "proc_fleet", 0) or 0) > 1
    if procfleet:
        preset, cfg, platform = _procfleet_preset(args)
    else:
        preset, cfg, platform = _resolve_preset(args)
        quant = args.quant if preset in ("7b", "13b") else "bf16"
        params = _build_params(cfg, jnp.bfloat16, quant)

    if args.workload_trace:
        # Replaying a saved trace reproduces a prior run's traffic
        # byte-for-byte (the JSONL is a pure function of its spec).
        spec, trace = wl.load_trace(args.workload_trace)
    else:
        spec = wl.WorkloadSpec(
            seed=args.workload_seed,
            n_requests=args.workload_requests,
            rate_rps=args.workload_rate,
            arrival=args.workload_arrival,
            sessions=args.workload_sessions,
            output_min=args.workload_output_min,
            output_max=args.workload_output_max,
            interactive_ttft_s=args.slo_ttft_s,
            interactive_itl_s=args.slo_itl_s,
            batch_latency_s=args.slo_latency_s,
        )
        trace = wl.generate_trace(spec)
    if args.workload_save:
        wl.save_trace(args.workload_save, spec, trace)

    # Flight recorder (ISSUE 10): keep every request of a measured
    # point so the per-class attribution tables and slowest-K exemplar
    # timelines come from complete data; rides the telemetry A/B
    # switch (disarmed = one global check, chains byte-identical).
    from eventgpt_tpu.obs import journey as obs_journey

    if telemetry:
        obs_journey.configure(max(1024, 2 * len(trace)))
    else:
        obs_journey.disable()

    if procfleet:
        # Process-fleet leg (ISSUE 11): the same trace through worker
        # PROCESSES behind the RPC coordinator. Each worker loads its own
        # tree and owns its device; this parent built no params and
        # initialised no backend (_procfleet_preset).
        return _run_workload_procfleet(args, preset, cfg, platform,
                                       spec, trace)
    if int(getattr(args, "fleet", 0) or 0) > 1:
        # Fleet leg (ISSUE 7): the same trace through the router tier.
        return _run_workload_fleet(args, preset, cfg, platform, params,
                                   spec, trace)

    # Size the server to the trace (speculative slack included — the
    # LARGEST adaptive bucket when --serve_spec_buckets is armed), like
    # submit() will re-validate per request.
    need = max(wl.cache_positions(r, cfg.num_event_tokens)
               + r.max_new_tokens for r in trace)
    max_len = ((need + 1 + _spec_slack(args) + 127) // 128) * 128
    srv = ContinuousBatcher(
        params, cfg, max_batch=args.serve_batch, max_len=max_len,
        chunk=args.serve_chunk, eos_token_id=None,
        kv_quant=args.kv == "int8", speculative=args.serve_spec,
        spec_buckets=args.serve_spec_buckets or None,
        first_chunk=args.serve_first_chunk or 0,
        pipeline=bool(args.serve_pipeline),
        prefix_cache=bool(args.serve_prefix_cache),
        prefix_insert=bool(args.serve_cache_insert),
        prefill_budget=int(args.serve_prefill_budget),
        kv_layout=args.serve_kv_layout,
        kv_pool_blocks=int(args.serve_kv_pool_blocks),
    )
    shape = (cfg.num_event_frames, 3, cfg.vision.image_size,
             cfg.vision.image_size)
    pix_cache = {}

    def pixels_for(r):
        if r.pixels_seed not in pix_cache:
            pix_cache[r.pixels_seed] = wl.stream_pixels(shape, r.pixels_seed)
        return pix_cache[r.pixels_seed]

    def slo_for(r):
        return spec.slo_for(r.slo_class)

    def fresh_cache():
        if (srv._prefix_cache is not None
                and bool(args.serve_cache_insert)):
            # Batcher API, not a hand swap: paged entries pin pool
            # blocks that must release with the entries (ISSUE 12).
            srv.reset_prefix_cache()

    plens = sorted({wl.cache_positions(r, cfg.num_event_tokens)
                    for r in trace})
    t0 = time.perf_counter()
    warmed = srv.warmup(prompt_lens=plens) if args.warmup else 0
    t_warm = time.perf_counter() - t0
    if args.warmup:
        # Cold-trajectory priming (the multi-session bench convention):
        # batcher.warmup() cannot know which wave/suffix/lane shapes the
        # trace produces, so one unmeasured unpaced replay compiles
        # them; the measured legs then pay zero XLA compile.
        wl.replay(srv, trace, pixels_for=pixels_for, paced=False)

    from eventgpt_tpu.obs import memory as obs_memory

    class_of = {r.idx: r.slo_class for r in trace}
    span = max(r.t_arrival for r in trace) or 1e-9
    mults = [float(x) for x in args.workload_mults.split(",") if x]
    sweep = []
    for mult in mults:
        fresh_cache()
        srv.reset_serving_stats()
        obs_metrics.REGISTRY.reset()
        obs_memory.LEDGER.reset_peak()  # per-point peak (ISSUE 9)
        # Fresh series ring + alert state per point (ISSUE 15): the
        # sampler thread runs through the replay, the alert evaluator
        # fires on the transient saturation the end-state numbers
        # cannot show (x16's queue build-up clears before the leg ends).
        series_store = _series_arm_leg(telemetry)
        res = wl.replay(srv, trace, pixels_for=pixels_for,
                        rate_mult=mult, paced=True, slo_for=slo_for)
        st = srv.slo_stats()
        met_total = sum(c["met"] for c in st["classes"].values())
        fin_total = sum(c["finished"] for c in st["classes"].values())
        toks = sum(len(v) for v in res["finished"].values())
        per_class = {}
        for cname, cagg in sorted(st["classes"].items()):
            stats = [srv.request_stats[res["rids"][idx]]
                     for idx in res["rids"] if class_of[idx] == cname
                     and res["rids"][idx] in srv.request_stats]

            def pct(key, q):
                vals = [s[key] for s in stats]
                return round(float(np.percentile(vals, q)), 4) if vals \
                    else 0.0

            per_class[cname] = {
                "requests": cagg["finished"],
                "met": cagg["met"],
                "attainment": round(cagg["attainment"], 4),
                "ttft_p50_s": pct("ttft_s", 50),
                "ttft_p99_s": pct("ttft_s", 99),
                "itl_p50_s": pct("itl_s", 50),
                "itl_p99_s": pct("itl_s", 99),
                "latency_p50_s": pct("latency_s", 50),
                "latency_p99_s": pct("latency_s", 99),
            }
        # Tail-latency attribution (ISSUE 10): per-class phase p99s +
        # the share of tail latency each phase owns, a zero-filled
        # miss-cause breakdown and the slowest-K exemplar timelines.
        jmap = {idx: srv.journey(rid)
                for idx, rid in res["rids"].items()}
        pc_extra, leg_extra = _journey_attribution(jmap, class_of)
        for cname, extra in pc_extra.items():
            per_class.setdefault(cname, {}).update(extra)
        leg = {
            "rate_mult": mult,
            "offered_rps": round(len(trace) / (span / mult), 3),
            "duration_s": round(res["duration_s"], 3),
            # THE metric: requests that finished within their SLO per
            # wall second — tok/s rides along for the ceiling story.
            "goodput_rps": round(met_total / res["duration_s"], 3),
            "slo_met_ratio": round(met_total / max(fin_total, 1), 4),
            "goodput_ratio_windowed": round(st["goodput_ratio"], 4),
            "tok_s": round(toks / res["duration_s"], 2),
            "classes": per_class,
            "admission_stall_s": round(srv.admission_s, 3),
            "mixed_boundaries": srv.mixed_boundaries,
            "mixed_zero_token_boundaries": srv.mixed_zero_harvests,
            # Adaptive speculation (ISSUE 13): accepted tokens per
            # segment DISPATCH is the first-class column — the number
            # the 8x spec spread is decided by — plus the mean chosen
            # window and the per-row mask count (informational).
            **(_spec_leg_columns(srv) if srv.speculative else {}),
            # Memory ledger (ISSUE 9): per-point peak + component
            # breakdown + the accounted/unaccounted reconcile — the
            # bytes column of the goodput story.
            "mem_peak_bytes": obs_memory.LEDGER.summary()["peak_bytes"],
            "memory": {
                **{k: v for k, v in obs_memory.LEDGER.summary().items()
                   if k in ("total_bytes", "peak_bytes", "components")},
                "reconcile": obs_memory.LEDGER.reconcile(),
            },
        }
        if args.serve_kv_layout == "paged":
            # Block-pool pressure per sweep point (ISSUE 12).
            leg["kv_blocks"] = srv.memory_summary().get("kv_blocks")
        leg.update(leg_extra)
        if args.serve_prefix_cache:
            leg["prefix_cache_hit_ratio"] = round(
                srv.prefix_cache_stats().get("hit_ratio", 0.0), 3)
        if telemetry:
            occ = obs_metrics.SERVE_OCCUPANCY._summary()
            leg["occupancy_mean"] = round(float(occ.get("mean", 0.0)), 2)
            adm = obs_metrics.SERVE_ADMISSION._summary()
            leg["admission_p50_s"] = adm.get("p50", 0.0)
        leg.update(_series_leg_columns(series_store, res["duration_s"]))
        sweep.append(leg)

    ab = None
    if args.workload_ab_reps:
        # Interleaved A/B (machine-phase drift is the noise floor —
        # PERFORMANCE.md): armed arm = telemetry registry on + SLO
        # classes submitted; disarmed arm = registry off + plain
        # submit. Chains must match byte-for-byte (scoring reads
        # clocks, never jax values) and the armed arm must hold the
        # <2% serve-throughput overhead contract.
        on_tok, off_tok = [], []
        on_cpu, off_cpu = [], []
        chains_identical = True
        ref = None
        # One unmeasured unpaced replay first: the sweep ran PACED, so
        # the A/B's unpaced admission shapes (bigger waves) may hit
        # cold executables — the warmup-discipline rule every leg obeys.
        fresh_cache()
        srv.reset_serving_stats()
        wl.replay(srv, trace, pixels_for=pixels_for, paced=False)
        for _rep in range(args.workload_ab_reps):
            # Alternate the within-pair order: a slow monotone machine
            # drift across one pair would otherwise read as a uniform
            # armed-arm bias (the ±10% per-rep straggler envelope makes
            # a 5-pair median land past 2% more often than it should).
            order = (True, False) if _rep % 2 == 0 else (False, True)
            for armed in order:
                obs_metrics.configure(armed)
                # The flight recorder rides the armed arm (ISSUE 10):
                # the A/B's chain-identity + <2% overhead contract now
                # covers journey recording too.
                if armed:
                    obs_journey.configure(max(1024, 2 * len(trace)))
                else:
                    obs_journey.disable()
                # The series sampler rides the armed arm too (ISSUE 15):
                # the A/B's chain-identity + <2% overhead contract now
                # covers background sampling + alert evaluation.
                _series_arm_leg(armed)
                fresh_cache()
                srv.reset_serving_stats()
                t_cpu0 = time.process_time()
                res = wl.replay(srv, trace, pixels_for=pixels_for,
                                paced=False,
                                slo_for=slo_for if armed else None)
                cpu = time.process_time() - t_cpu0
                toks = sum(len(v) for v in res["finished"].values())
                (on_tok if armed else off_tok).append(
                    round(toks / res["duration_s"], 2))
                (on_cpu if armed else off_cpu).append(round(cpu, 4))
                if ref is None:
                    ref = res["finished"]
                elif res["finished"] != ref:
                    chains_identical = False
        obs_metrics.configure(telemetry)
        if telemetry:
            obs_journey.configure(max(1024, 2 * len(trace)))
        _series_arm_leg(telemetry)
        # PAIRED estimate on PROCESS CPU TIME: instrumentation cost is
        # host CPU work by construction (clock reads, lock'd dict
        # writes, journey appends), and on the CPU backend the model
        # compute is in-process too — so the cpu_off/cpu_on ratio
        # captures the whole added cost while excluding hypervisor
        # scheduling wander, which wall-clock pairing cannot cancel at
        # 2% resolution on sub-second legs (measured: the SAME binary
        # with identical arms reads ±5% on wall pairs but <1% on CPU
        # pairs — PERFORMANCE.md "Workload replay"). The wall tok/s
        # arrays stay in the record for continuity/audit, with the
        # wall-based median kept as overhead_frac_wall.
        pair_ratios = [off / on for on, off in zip(on_cpu, off_cpu)]
        wall_ratios = [on / off for on, off in zip(on_tok, off_tok)]
        ab = {
            "reps": args.workload_ab_reps,
            "slo_on_tok_s": on_tok,
            "slo_off_tok_s": off_tok,
            "slo_on_cpu_s": on_cpu,
            "slo_off_cpu_s": off_cpu,
            "overhead_frac": round(
                1.0 - float(np.median(pair_ratios)), 4),
            "overhead_frac_wall": round(
                1.0 - float(np.median(wall_ratios)), 4),
            "overhead_frac_mean": round(
                1.0 - (sum(on_tok) / len(on_tok))
                / (sum(off_tok) / len(off_tok)), 4),
            "chains_identical": chains_identical,
        }

    base_leg = next((l for l in sweep if l["rate_mult"] == 1.0),
                    sweep[0] if sweep else None)
    record = {
        "metric": f"workload_goodput_{preset}",
        "value": base_leg["goodput_rps"] if base_leg else 0.0,
        "unit": "req/s",
        "requests": len(trace),
        "arrival": spec.arrival,
        "rate_rps": spec.rate_rps,
        "sessions": spec.sessions,
        "seed": spec.seed,
        # Output-cap flags (ISSUE 8 satellite): tok_s is only pairable
        # across records generated from the SAME trace shape — r01 shipped
        # without these, so compare_bench had to skip tok_s across
        # topologies. trace_output_tokens is the audit number (the sum of
        # budgets an eos-free replay serves exactly).
        "output_min": spec.output_min,
        "output_max": spec.output_max,
        "trace_output_tokens": sum(r.max_new_tokens for r in trace),
        "slo": {
            "interactive": {"ttft_s": spec.interactive_ttft_s,
                            "itl_s": spec.interactive_itl_s},
            "batch": {"latency_s": spec.batch_latency_s},
        },
        "max_batch": srv.max_batch,
        "chunk": args.serve_chunk,
        "kv_layout": args.serve_kv_layout,
        "prefill_budget": int(args.serve_prefill_budget),
        "pipeline": bool(args.serve_pipeline),
        "prefix_cache": bool(args.serve_prefix_cache),
        "warmup": bool(args.warmup),
        "warmup_s": round(t_warm, 3),
        "warmed_executables": warmed,
        "sweep": sweep,
        **({"ab": ab} if ab is not None else {}),
        "kv_cache": args.kv,
        "speculative": args.serve_spec,
        "spec_buckets": args.serve_spec_buckets or "",
        "quant": quant,
        "platform": platform,
        "telemetry": telemetry,
    }
    print(json.dumps(record))
    if args.workload_out:
        # The WORKLOAD_r0N.json artifact form (pretty-printed; the fast
        # tier schema-validates the checked-in copies).
        with open(args.workload_out, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
    return record


def run_workload_spec(args):
    """Adaptive-vs-fixed speculation A/B under workload replay (ISSUE 13
    — THE judgment the tentpole is shipped on). Two model regimes over
    the SAME seeded trace, each replayed at every load mult by a fixed-K
    arm (``--spec_ab_fixed_k``) and an adaptive arm
    (``--serve_spec_buckets``):

      * **easy** — a zeroed weight tree decodes a constant chain, so
        suffix-vote acceptance is ~1: the controller must HOLD the top
        bucket and tie fixed-K (the honest negative if it only ties);
      * **adversarial** — the random tiny tree's chains have ~zero
        draft acceptance: fixed-K burns a K-wide verify per ~1 token
        while the controller must back off toward the K=0 bucket and
        STRICTLY beat fixed K (the acceptance criterion).

    Chains must be byte-identical between the arms at every point —
    verification makes any draft depth exact; depth is latency only.
    Writes the WORKLOAD_SPEC_r0N.json artifact via --workload_out."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from eventgpt_tpu import workload as wl
    from eventgpt_tpu.obs import metrics as obs_metrics
    from eventgpt_tpu.serve import ContinuousBatcher

    obs_metrics.configure(bool(args.serve_telemetry))
    preset, cfg, platform = _resolve_preset(args)
    dtype = jnp.bfloat16
    quant = args.quant if preset in ("7b", "13b") else "bf16"
    # The easy regime is the all-zero tree: it decodes a constant chain,
    # so suffix-vote acceptance is ~1 — the easiest possible draft
    # traffic.
    params_easy = _build_params(cfg, dtype, quant, zeros=True)
    if isinstance(params_easy["llama"]["lm_head"], dict):
        raise SystemExit("workload_spec needs an unquantized tree "
                         "(run --preset tiny / --quant bf16)")
    # The adversarial regime: a COUNTER model. Zeroed blocks pass the
    # input embedding straight to the final norm, and lm_head is the
    # (unit-normalized) embedding table rolled by one row — greedy
    # argmax maps each token to its ring neighbor, so the chain walks
    # the vocab monotonically and its continuation NEVER appears in the
    # lookup context (no self-repetition, no cross-request echo):
    # suffix-vote acceptance is exactly zero, the worst case for a
    # fixed wide window and precisely the traffic adaptive depth must
    # survive by backing off.
    emb = jax.random.normal(
        jax.random.PRNGKey(13),
        params_easy["llama"]["embed_tokens"].shape, jnp.float32)
    emb = emb / jnp.linalg.norm(emb, axis=-1, keepdims=True)
    params = jax.tree_util.tree_map(jnp.zeros_like, params_easy)
    params["llama"] = {
        **params["llama"],
        "embed_tokens": emb.astype(dtype),
        "final_norm": jnp.ones_like(params_easy["llama"]["final_norm"]),
        "lm_head": jnp.roll(emb, -1, axis=0).T.astype(
            params_easy["llama"]["lm_head"].dtype),
    }

    spec = wl.WorkloadSpec(
        seed=args.workload_seed, n_requests=args.workload_requests,
        rate_rps=args.workload_rate, arrival=args.workload_arrival,
        sessions=args.workload_sessions,
        output_min=args.workload_output_min,
        output_max=args.workload_output_max,
        interactive_ttft_s=args.slo_ttft_s,
        interactive_itl_s=args.slo_itl_s,
        batch_latency_s=args.slo_latency_s,
    )
    trace = wl.generate_trace(spec)
    buckets = args.serve_spec_buckets or "0,2,4,8"
    fixed_k = int(args.spec_ab_fixed_k)
    mults = [float(x) for x in args.workload_mults.split(",") if x]
    spec_max = max([fixed_k] + [int(x) for x in buckets.split(",") if x])

    shape = (cfg.num_event_frames, 3, cfg.vision.image_size,
             cfg.vision.image_size)
    pix_cache = {}

    def pixels_for(r):
        if r.pixels_seed not in pix_cache:
            pix_cache[r.pixels_seed] = wl.stream_pixels(shape, r.pixels_seed)
        return pix_cache[r.pixels_seed]

    def slo_for(r):
        return spec.slo_for(r.slo_class)

    need = max(wl.cache_positions(r, cfg.num_event_tokens)
               + r.max_new_tokens for r in trace)
    max_len = ((need + 1 + spec_max + 127) // 128) * 128
    plens = sorted({wl.cache_positions(r, cfg.num_event_tokens)
                    for r in trace})

    def run_arm(model_params, adaptive, mult):
        """One replay leg. ``mult > 0`` is the open-loop paced form
        (goodput under offered load); ``mult == 0`` is the UNPACED
        throughput point — every request submitted at once, so tok_s
        measures the server, not the arrival process (the paced points
        on a tiny trace are arrival-bound and tie by construction)."""
        srv = ContinuousBatcher(
            model_params, cfg, max_batch=args.serve_batch,
            max_len=max_len, chunk=args.serve_chunk, eos_token_id=None,
            kv_quant=args.kv == "int8", speculative=fixed_k,
            spec_buckets=(buckets if adaptive else None),
            pipeline=bool(args.serve_pipeline),
            prefix_cache=bool(args.serve_prefix_cache),
            prefix_insert=bool(args.serve_cache_insert),
            prefill_budget=int(args.serve_prefill_budget),
        )
        if args.warmup:
            srv.warmup(prompt_lens=plens)
            wl.replay(srv, trace, pixels_for=pixels_for, paced=False)
        srv.reset_serving_stats()
        res = wl.replay(srv, trace, pixels_for=pixels_for,
                        rate_mult=mult or 1.0, paced=mult > 0,
                        slo_for=slo_for)
        st = srv.slo_stats()
        met = sum(c["met"] for c in st["classes"].values())
        fin = sum(c["finished"] for c in st["classes"].values())
        toks = sum(len(v) for v in res["finished"].values())
        leg = {
            "rate_mult": mult,
            "goodput_rps": round(met / res["duration_s"], 3),
            "slo_met_ratio": round(met / max(fin, 1), 4),
            "tok_s": round(toks / res["duration_s"], 2),
            "duration_s": round(res["duration_s"], 3),
            **_spec_leg_columns(srv),
        }
        # Chains keyed by trace index (fresh servers hand out the same
        # rids in submission order; the map makes that explicit).
        chains = {int(i): res["finished"][rid]
                  for i, rid in res["rids"].items()
                  if rid in res["finished"]}
        return leg, chains

    legs = {}
    chains_identical = True
    # The paced mults judge goodput under offered load; the trailing
    # rate_mult-0 point is the UNPACED throughput leg where the verify
    # width's compute cost is actually visible (the strict
    # adaptive-beats-fixed gate lives there).
    mults = mults + [0.0]
    for regime, model_params in (("easy", params_easy),
                                 ("adversarial", params)):
        fixed_sweep, adaptive_sweep = [], []
        for mult in mults:
            f_leg, f_chains = run_arm(model_params, False, mult)
            a_leg, a_chains = run_arm(model_params, True, mult)
            same = f_chains == a_chains
            chains_identical &= same
            f_leg["chains_identical"] = a_leg["chains_identical"] = same
            fixed_sweep.append(f_leg)
            adaptive_sweep.append(a_leg)
            sys.stderr.write(
                f"workload_spec {regime} x{mult}: fixed tok_s "
                f"{f_leg['tok_s']} vs adaptive {a_leg['tok_s']} "
                f"(depth_mean {a_leg['spec_depth_mean']}, chains "
                f"{'==' if same else '!='})\n")
        legs[regime] = {"fixed": {"sweep": fixed_sweep},
                        "adaptive": {"sweep": adaptive_sweep}}

    # Headline: adaptive-over-fixed tok/s ratio on the adversarial
    # trace at the highest load point (the 8x-spread recovery).
    adv_f = legs["adversarial"]["fixed"]["sweep"][-1]["tok_s"]
    adv_a = legs["adversarial"]["adaptive"]["sweep"][-1]["tok_s"]
    record = {
        "metric": f"workload_spec_ab_{preset}",
        "value": round(adv_a / max(adv_f, 1e-9), 3),
        "unit": "x (adaptive/fixed tok_s, adversarial leg)",
        "requests": len(trace),
        "seed": spec.seed,
        "arrival": spec.arrival,
        "sessions": spec.sessions,
        "output_min": spec.output_min,
        "output_max": spec.output_max,
        "trace_output_tokens": sum(r.max_new_tokens for r in trace),
        "rate_rps": spec.rate_rps,
        "max_batch": args.serve_batch,
        "chunk": args.serve_chunk,
        "fixed_k": fixed_k,
        "spec_buckets": buckets,
        "chains_identical": chains_identical,
        "legs": legs,
        "warmup": bool(args.warmup),
        "quant": quant,
        "platform": platform,
    }
    print(json.dumps(record))
    if args.workload_out:
        with open(args.workload_out, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
    return record


def run_workload_oom(args):
    """Pool-oversubscription preemption A/B (ISSUE 16 — THE judgment
    the tentpole is shipped on). One seeded trace replayed at every
    ``--oom_oversub`` undersizing point — the paged block pool shrunk
    to 1/x of the trace's dense-equivalent capacity — by two arms:

      * **defer** — the pre-16 policy: an interactive admission that
        free blocks cannot cover waits behind the batch rows holding
        them (the OOM cliff, paid in interactive TTFT);
      * **preempt** — block-tier preemption armed: the head evicts the
        lowest-value batch row, which spills its KV run to host RAM or
        drops and re-prefills (whichever the measured bytes-vs-FLOPs
        price says), and re-enters at the back of the queue.

    Both arms must finish every request with its chain byte-identical
    to an UNPREEMPTED ample-pool reference replay (``chains_identical``
    — preemption is a scheduling decision, never a numerics one), with
    zero ``BlockPoolError``s; the preempt arm's interactive attainment
    and goodput are the graceful-degradation curve PERFORMANCE.md
    plots. Writes the WORKLOAD_OOM_r0N.json artifact via
    --workload_out."""
    import numpy as np

    import jax.numpy as jnp

    from eventgpt_tpu import workload as wl
    from eventgpt_tpu.constants import SEQ_BUCKET
    from eventgpt_tpu.obs import metrics as obs_metrics
    from eventgpt_tpu.serve import ContinuousBatcher
    from eventgpt_tpu.serve_blocks import BlockPoolError

    obs_metrics.configure(bool(args.serve_telemetry))
    preset, cfg, platform = _resolve_preset(args)
    dtype = jnp.bfloat16
    quant = args.quant if preset in ("7b", "13b") else "bf16"
    params = _build_params(cfg, dtype, quant)

    spec = wl.WorkloadSpec(
        seed=args.workload_seed, n_requests=args.workload_requests,
        rate_rps=args.workload_rate, arrival=args.workload_arrival,
        sessions=args.workload_sessions,
        output_min=args.workload_output_min,
        output_max=args.workload_output_max,
        interactive_ttft_s=args.slo_ttft_s,
        interactive_itl_s=args.slo_itl_s,
        batch_latency_s=args.slo_latency_s,
    )
    trace = wl.generate_trace(spec)
    class_of = {r.idx: r.slo_class for r in trace}

    shape = (cfg.num_event_frames, 3, cfg.vision.image_size,
             cfg.vision.image_size)
    pix_cache = {}

    def pixels_for(r):
        if r.pixels_seed not in pix_cache:
            pix_cache[r.pixels_seed] = wl.stream_pixels(shape, r.pixels_seed)
        return pix_cache[r.pixels_seed]

    def slo_for(r):
        return spec.slo_for(r.slo_class)

    need = max(wl.cache_positions(r, cfg.num_event_tokens)
               + r.max_new_tokens for r in trace)
    max_len = ((need + 1 + 127) // 128) * 128
    plens = sorted({wl.cache_positions(r, cfg.num_event_tokens)
                    for r in trace})
    # The dense-equivalent pool (what kv_pool_blocks=0 sizes) and the
    # floor below which submit() itself refuses the largest request —
    # undersizing clamps there, so every point is oversubscribed but
    # admissible.
    full_blocks = args.serve_batch * (max_len // SEQ_BUCKET) + 1
    biggest = max(
        (min(max(((wl.cache_positions(r, cfg.num_event_tokens)
                   + 2 * SEQ_BUCKET - 1) // (2 * SEQ_BUCKET))
                 * (2 * SEQ_BUCKET),
                 wl.cache_positions(r, cfg.num_event_tokens)
                 + r.max_new_tokens + 1), max_len)
         + SEQ_BUCKET - 1) // SEQ_BUCKET
        for r in trace)

    def make_srv(pool_blocks, preempt):
        return ContinuousBatcher(
            params, cfg, max_batch=args.serve_batch, max_len=max_len,
            chunk=args.serve_chunk, eos_token_id=None,
            kv_quant=args.kv == "int8",
            pipeline=bool(args.serve_pipeline),
            prefix_cache=bool(args.serve_prefix_cache),
            prefix_insert=bool(args.serve_cache_insert),
            prefill_budget=int(args.serve_prefill_budget),
            kv_layout="paged", kv_pool_blocks=pool_blocks,
            preempt=preempt,
            spill_capacity_mb=int(args.oom_spill_mb) if preempt else 0,
        )

    def run_leg(pool_blocks, preempt, oversub, paced=True, warm=False):
        srv = make_srv(pool_blocks, preempt)
        if preempt and platform == "cpu":
            # The 5e12 FLOP/s recompute price assumes an accelerator;
            # a CPU prefill sustains orders of magnitude less, so spill
            # would never win on the smoke preset. Price it at a
            # CPU-scale sustained rate instead — the policy then splits
            # honestly between spill and drop per victim size.
            srv._recompute_flops_per_s = 1e9
        if warm and args.warmup:
            srv.warmup(prompt_lens=plens)
            wl.replay(srv, trace, pixels_for=pixels_for, paced=False)
            srv.reset_serving_stats()
            obs_metrics.REGISTRY.reset()
        res = wl.replay(srv, trace, pixels_for=pixels_for,
                        rate_mult=args.oom_rate_mult if paced else 1.0,
                        paced=paced, slo_for=slo_for)
        st = srv.slo_stats()
        met = sum(c["met"] for c in st["classes"].values())
        fin = sum(c["finished"] for c in st["classes"].values())
        toks = sum(len(v) for v in res["finished"].values())
        # replay()'s finished map is keyed by TRACE idx already (NOT
        # rid — a warmed server's measured replay hands out rids past
        # the warm leg's, so indexing by rid silently drops chains).
        chains = {int(i): v for i, v in res["finished"].items()}
        pool = srv._pool.stats()
        leg = {
            # compare_bench pairs sweep points by rate_mult; the swept
            # axis HERE is pool undersizing, so the factor takes that
            # slot (the offered mult is constant — echoed below).
            "rate_mult": oversub,
            "pool_blocks": pool_blocks,
            "offered_mult": args.oom_rate_mult,
            "duration_s": round(res["duration_s"], 3),
            "goodput_rps": round(met / res["duration_s"], 3),
            "slo_met_ratio": round(met / max(fin, 1), 4),
            "tok_s": round(toks / res["duration_s"], 2),
            "classes": {
                cname: {"requests": cagg["finished"], "met": cagg["met"],
                        "attainment": round(cagg["attainment"], 4)}
                for cname, cagg in sorted(st["classes"].items())
            },
            "preemptions_total": srv.preemptions,
            "kv_block_deferrals": srv.block_deferrals,
            "spills": pool["spills"],
            "restores": pool["restores"],
            "spilled_runs_leaked": pool["spilled_runs"],
            **({"spill_store": {
                k: srv._spill_store.stats()[k]
                for k in ("used_bytes", "puts", "takes", "drops",
                          "rejects")}}
               if srv._spill_store is not None else {}),
        }
        return leg, chains

    oversubs = [float(x) for x in args.oom_oversub.split(",") if x]
    # Unpreempted ample-pool reference: THE chains every arm must
    # reproduce (and the warm leg that pays the XLA compiles once).
    _, ref_chains = run_leg(full_blocks, False, 1.0, paced=False,
                            warm=True)

    legs = {"defer": {"sweep": []}, "preempt": {"sweep": []}}
    chains_identical = True
    pool_errors = 0
    for x in oversubs:
        pool_blocks = max(int(full_blocks / x), biggest + 1, 3)
        for arm, preempt in (("defer", False), ("preempt", True)):
            try:
                leg, chains = run_leg(pool_blocks, preempt, x)
            except BlockPoolError as e:  # acceptance: NEVER fires
                pool_errors += 1
                sys.stderr.write(f"workload_oom {arm} x{x}: "
                                 f"BlockPoolError {e}\n")
                continue
            same = chains == ref_chains
            chains_identical &= same
            leg["chains_identical"] = int(same)
            legs[arm]["sweep"].append(leg)
            sys.stderr.write(
                f"workload_oom {arm} x{x} ({pool_blocks} blocks): "
                f"goodput {leg['goodput_rps']} met "
                f"{leg['slo_met_ratio']} preempts "
                f"{leg['preemptions_total']} spills {leg['spills']} "
                f"(chains {'==' if same else '!='})\n")

    # Headline: worst-point preempt-over-defer goodput ratio — > 1.0
    # means preemption beat deferral at EVERY oversubscription point.
    ratios = [p["goodput_rps"] / max(d["goodput_rps"], 1e-9)
              for d, p in zip(legs["defer"]["sweep"],
                              legs["preempt"]["sweep"])]
    record = {
        "metric": f"workload_oom_ab_{preset}",
        "value": round(min(ratios), 3) if ratios else 0.0,
        "unit": "x (preempt/defer goodput, worst oversubscription "
                "point)",
        "requests": len(trace),
        "seed": spec.seed,
        "arrival": spec.arrival,
        "sessions": spec.sessions,
        "output_min": spec.output_min,
        "output_max": spec.output_max,
        "rate_rps": spec.rate_rps,
        "offered_mult": args.oom_rate_mult,
        "max_batch": args.serve_batch,
        "chunk": args.serve_chunk,
        "kv_layout": "paged",
        "full_pool_blocks": full_blocks,
        "oversub": oversubs,
        "spill_capacity_mb": int(args.oom_spill_mb),
        "block_pool_errors": pool_errors,
        "chains_identical": int(chains_identical),
        "legs": legs,
        "warmup": bool(args.warmup),
        "quant": quant,
        "platform": platform,
    }
    print(json.dumps(record))
    if args.workload_out:
        with open(args.workload_out, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
    return record


def _run_workload_fleet(args, preset, cfg, platform, params, spec, trace):
    """``--mode workload --fleet N`` (ISSUE 7): replay the same seeded
    trace through the replica supervisor + prefix-affinity router
    instead of one batcher. Per sweep point the record carries the
    single-engine keys (goodput, SLO-met ratio, per-class percentiles,
    tok/s) PLUS the fleet-only keys: per-replica goodput / hit ratio /
    served counts, shed and rejected totals, and failover counts —
    the router's observability story under load. Engines self-drive
    (each replica runs its own scheduler thread), so the replay here
    only paces submissions and collects results."""
    import numpy as np

    from eventgpt_tpu import workload as wl
    from eventgpt_tpu.cli.serve import ServingEngine
    from eventgpt_tpu.data.tokenizer import load_tokenizer
    from eventgpt_tpu.fleet import Fleet, FleetShedError
    from eventgpt_tpu.obs import memory as obs_memory
    from eventgpt_tpu.obs import metrics as obs_metrics
    from eventgpt_tpu.serve import ContinuousBatcher, QueueFullError

    n_fleet = int(args.fleet)
    telemetry = bool(args.serve_telemetry)
    obs_metrics.configure(telemetry)
    need = max(wl.cache_positions(r, cfg.num_event_tokens)
               + r.max_new_tokens for r in trace)
    max_len = ((need + 1 + args.serve_spec + 127) // 128) * 128
    batchers = [
        ContinuousBatcher(
            params, cfg, max_batch=args.serve_batch, max_len=max_len,
            chunk=args.serve_chunk, eos_token_id=None,
            kv_quant=args.kv == "int8", speculative=args.serve_spec,
            first_chunk=args.serve_first_chunk or 0,
            pipeline=bool(args.serve_pipeline),
            prefix_cache=bool(args.serve_prefix_cache),
            prefix_insert=bool(args.serve_cache_insert),
            prefill_budget=int(args.serve_prefill_budget),
        )
        for _ in range(n_fleet)
    ]
    shape = (cfg.num_event_frames, 3, cfg.vision.image_size,
             cfg.vision.image_size)
    pix_cache = {}

    def pixels_for(r):
        if r.pixels_seed not in pix_cache:
            pix_cache[r.pixels_seed] = wl.stream_pixels(shape, r.pixels_seed)
        return pix_cache[r.pixels_seed]

    plens = sorted({wl.cache_positions(r, cfg.num_event_tokens)
                    for r in trace})
    t0 = time.perf_counter()
    # The replicas share the jit executable cache (identical shapes), so
    # warming each is one compile pass + (N-1) cache hits.
    warmed = (sum(b.warmup(prompt_lens=plens) for b in batchers)
              if args.warmup else 0)
    t_warm = time.perf_counter() - t0

    engines = [ServingEngine(b, load_tokenizer("byte")) for b in batchers]
    fleet = Fleet(
        engines, probe_interval_s=0.02,
        shed_goodput_ratio=float(getattr(args, "fleet_shed_goodput", 0.5)),
        shed_queue_depth=int(getattr(args, "fleet_shed_queue", 0)),
    )

    def slo_for(r):
        return spec.slo_for(r.slo_class)

    def replay(rate_mult, paced=True, with_slo=True):
        tr0 = time.perf_counter()
        frids = {}
        shed = rejected = 0
        for r in trace:
            if paced:
                while True:
                    dt = r.t_arrival / rate_mult - (time.perf_counter()
                                                    - tr0)
                    if dt <= 0:
                        break
                    time.sleep(min(dt, 0.005))
            try:
                frids[r.idx] = fleet.submit_ids(
                    r.input_ids, pixels_for(r), r.max_new_tokens,
                    slo=slo_for(r) if with_slo else None)
            except FleetShedError:
                shed += 1
            except QueueFullError:
                rejected += 1
        finished = {idx: fleet.result(f, timeout=600)
                    for idx, f in frids.items()}
        return {"frids": frids, "finished": finished,
                "duration_s": time.perf_counter() - tr0,
                "shed": shed, "rejected": rejected}

    def reset_point():
        fleet.reset_stats()
        for b in batchers:
            b.reset_serving_stats()
            if b._prefix_cache is not None and bool(args.serve_cache_insert):
                b.reset_prefix_cache()
        obs_metrics.REGISTRY.reset()
        obs_memory.LEDGER.reset_peak()  # per-point peak (ISSUE 9)

    if args.warmup:
        # Cold-trajectory priming, fleet form: one unmeasured unpaced
        # replay compiles the trace's wave/suffix/lane shapes on every
        # replica the router touches.
        replay(1.0, paced=False, with_slo=False)

    class_of = {r.idx: r.slo_class for r in trace}
    span = max(r.t_arrival for r in trace) or 1e-9
    mults = [float(x) for x in args.workload_mults.split(",") if x]
    sweep = []
    for mult in mults:
        reset_point()
        # One process-global series store senses the whole thread fleet
        # (FLEET_QUEUE_DEPTH feeds queue_trend) — ISSUE 15.
        series_store = _series_arm_leg(telemetry)
        res = replay(mult, paced=True)
        st = fleet.slo_stats()
        met_total = sum(c["met"] for c in st["classes"].values())
        fin_total = sum(c["finished"] for c in st["classes"].values())
        toks = sum(len(v) for v in res["finished"].values())
        stats_of = fleet.batcher.request_stats
        per_class = {}
        for cname, cagg in sorted(st["classes"].items()):
            stats = [stats_of.get(res["frids"][idx])
                     for idx in res["frids"] if class_of[idx] == cname]
            stats = [s for s in stats if s]

            def pct(key, q):
                vals = [s[key] for s in stats if key in s]
                return round(float(np.percentile(vals, q)), 4) if vals \
                    else 0.0

            per_class[cname] = {
                "requests": cagg["finished"],
                "met": cagg["met"],
                "attainment": round(cagg["attainment"], 4),
                "ttft_p50_s": pct("ttft_s", 50),
                "ttft_p99_s": pct("ttft_s", 99),
                "itl_p50_s": pct("itl_s", 50),
                "itl_p99_s": pct("itl_s", 99),
                "latency_p50_s": pct("latency_s", 50),
                "latency_p99_s": pct("latency_s", 99),
            }
        # Tail-latency attribution, fleet form (ISSUE 10): stitched
        # fleet journeys — failover_redo_s is a real phase here.
        jmap = {idx: fleet.journey(frid)
                for idx, frid in res["frids"].items()}
        pc_extra, leg_extra = _journey_attribution(jmap, class_of)
        for cname, extra in pc_extra.items():
            per_class.setdefault(cname, {}).update(extra)
        served_by = {}
        for idx, frid in res["frids"].items():
            rep = fleet.replica_of(frid)
            served_by.setdefault(rep, []).append(idx)
        replicas = []
        for rep in fleet.replicas:
            rst = rep.engine.batcher.slo_stats()
            rmet = sum(c["met"] for c in rst["classes"].values())
            rfin = sum(c["finished"] for c in rst["classes"].values())
            replicas.append({
                "replica": rep.idx,
                "requests": rfin,
                "goodput_rps": round(rmet / res["duration_s"], 3),
                "slo_met_ratio": round(rmet / max(rfin, 1), 4),
                "tokens": sum(len(res["finished"][i])
                              for i in served_by.get(rep.idx, [])),
                "prefix_cache_hit_ratio": round(
                    rep.engine.batcher.prefix_cache_stats().get(
                        "hit_ratio", 0.0), 3),
                # Per-replica resident share (ISSUE 9): this replica's
                # OWN ledger components — weights are shared, counted
                # once in the point-level memory summary.
                "memory_bytes": sum(obs_memory.LEDGER.snapshot(
                    rep.engine.batcher._mem_owner).values()),
            })
        hits = sum(r.engine.batcher.prefix_cache_stats().get("hits", 0)
                   for r in fleet.replicas)
        misses = sum(r.engine.batcher.prefix_cache_stats().get("misses", 0)
                     for r in fleet.replicas)
        sweep.append({
            "rate_mult": mult,
            "offered_rps": round(len(trace) / (span / mult), 3),
            "duration_s": round(res["duration_s"], 3),
            "goodput_rps": round(met_total / res["duration_s"], 3),
            "slo_met_ratio": round(met_total / max(fin_total, 1), 4),
            "tok_s": round(toks / res["duration_s"], 2),
            **leg_extra,
            "prefix_cache_hit_ratio": round(
                hits / (hits + misses), 3) if (hits + misses) else 0.0,
            "classes": per_class,
            # fleet-only keys from here down (OBSERVABILITY.md "Fleet
            # workload record" documents them; compare_bench gates only
            # the direction-aware shared keys above):
            "shed_total": res["shed"],
            "rejected_total": res["rejected"],
            "failovers": fleet.n_failovers,
            "replicas": replicas,
            # Process-wide ledger peak (N replicas + one shared weight
            # tree — NOT comparable to a single-engine point's peak;
            # OBSERVABILITY.md "Fleet workload record").
            "mem_peak_bytes": obs_memory.LEDGER.summary()["peak_bytes"],
            "memory": {
                **{k: v for k, v in obs_memory.LEDGER.summary().items()
                   if k in ("total_bytes", "peak_bytes", "components")},
                "reconcile": obs_memory.LEDGER.reconcile(),
            },
            **_series_leg_columns(series_store, res["duration_s"]),
        })

    record = {
        "metric": f"workload_fleet_goodput_{preset}",
        "value": (next((l for l in sweep if l["rate_mult"] == 1.0),
                       sweep[0])["goodput_rps"] if sweep else 0.0),
        "unit": "req/s",
        "fleet": n_fleet,
        "requests": len(trace),
        "arrival": spec.arrival,
        "rate_rps": spec.rate_rps,
        "sessions": spec.sessions,
        "seed": spec.seed,
        # Same output-cap identity keys as the single-engine record, so
        # compare_bench can pair tok_s across topologies (ISSUE 8).
        "output_min": spec.output_min,
        "output_max": spec.output_max,
        "trace_output_tokens": sum(r.max_new_tokens for r in trace),
        "slo": {
            "interactive": {"ttft_s": spec.interactive_ttft_s,
                            "itl_s": spec.interactive_itl_s},
            "batch": {"latency_s": spec.batch_latency_s},
        },
        "shed_goodput_ratio": float(getattr(args, "fleet_shed_goodput", 0.5)),
        "shed_queue_depth": int(getattr(args, "fleet_shed_queue", 0)),
        "max_batch": args.serve_batch,
        "chunk": args.serve_chunk,
        "prefill_budget": int(args.serve_prefill_budget),
        "pipeline": bool(args.serve_pipeline),
        "prefix_cache": bool(args.serve_prefix_cache),
        "warmup": bool(args.warmup),
        "warmup_s": round(t_warm, 3),
        "warmed_executables": warmed,
        "sweep": sweep,
        "kv_cache": args.kv,
        "speculative": args.serve_spec,
        "quant": quant_name(args, preset),
        "platform": platform,
        "telemetry": telemetry,
    }
    fleet.shutdown()
    print(json.dumps(record))
    if args.workload_out:
        with open(args.workload_out, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
    return record


def _run_workload_procfleet(args, preset, cfg, platform, spec, trace):
    """``--mode workload --proc_fleet N`` (ISSUE 11): replay the same
    seeded trace through N worker PROCESSES behind the RPC
    coordinator. The record carries the shared SLO-goodput keys
    (goodput_rps / slo_met_ratio / per-class attainment +
    percentiles + attribution), so compare_bench gates it against the
    thread-fleet artifact on service quality; tok_s and memory keys
    are per-topology by construction — N separate jax processes
    contend for the same CPUs and keep N separate ledgers — so the
    record sets ``proc_fleet`` and compare_bench drops those keys
    cross-topology with an ``unpaired`` note (the PR 8/9 convention).
    Per-worker numbers (goodput, hit ratio, OWN-process ledger bytes)
    ride each sweep leg."""
    import sys

    import numpy as np

    from eventgpt_tpu import workload as wl
    from eventgpt_tpu.fleet_proc import ProcFleet
    from eventgpt_tpu.serve import QueueFullError

    n_proc = int(args.proc_fleet)
    need = max(wl.cache_positions(r, cfg.num_event_tokens)
               + r.max_new_tokens for r in trace)
    max_len = ((need + 1 + args.serve_spec + 127) // 128) * 128
    worker_cmd = [
        sys.executable, "-m", "eventgpt_tpu.cli.serve", "--worker",
        "--model_path", "tiny-random",
        "--max_batch", str(args.serve_batch),
        "--max_len", str(max_len),
        "--chunk", str(args.serve_chunk),
        "--kv_cache", args.kv,
        "--speculative", str(args.serve_spec),
        "--first_chunk", str(args.serve_first_chunk or 0),
        "--prefill_budget", str(int(args.serve_prefill_budget)),
        "--max_queue", "0",
    ]
    if not args.serve_pipeline:
        worker_cmd.append("--no_pipeline")
    if not args.serve_prefix_cache:
        worker_cmd.append("--no_prefix_cache")
    if not args.serve_telemetry:
        worker_cmd.append("--no_telemetry")
    t0 = time.perf_counter()
    fleet = ProcFleet(worker_cmd, n_proc, spawn_timeout_s=600,
                      probe_interval_s=0.03, rpc_deadline_s=60.0,
                      shutdown_drain_s=60.0)
    t_boot = time.perf_counter() - t0

    shape = (cfg.num_event_frames, 3, cfg.vision.image_size,
             cfg.vision.image_size)
    pix_cache = {}

    def pixels_for(r):
        if r.pixels_seed not in pix_cache:
            pix_cache[r.pixels_seed] = wl.stream_pixels(shape, r.pixels_seed)
        return pix_cache[r.pixels_seed]

    def slo_for(r):
        return spec.slo_for(r.slo_class)

    def replay(rate_mult, paced=True, with_slo=True):
        tr0 = time.perf_counter()
        frids = {}
        rejected = 0
        for r in trace:
            if paced:
                while True:
                    dt = r.t_arrival / rate_mult - (time.perf_counter()
                                                    - tr0)
                    if dt <= 0:
                        break
                    time.sleep(min(dt, 0.005))
            try:
                frids[r.idx] = fleet.submit_ids(
                    r.input_ids, pixels_for(r), r.max_new_tokens,
                    slo=slo_for(r) if with_slo else None)
            except QueueFullError:
                rejected += 1
        finished = {idx: fleet.result(f, timeout=600)
                    for idx, f in frids.items()}
        return {"frids": frids, "finished": finished,
                "duration_s": time.perf_counter() - tr0,
                "rejected": rejected}

    def refresh_snapshots():
        # The supervisor refreshes snapshots once per probe tick; a
        # point's accounting reads them RIGHT after the last finish,
        # so fetch fresh ones explicitly.
        for slot in fleet.slots:
            if slot.addr is not None:
                try:
                    slot.snapshot = fleet._rpc(slot, "snapshot",
                                               deadline_s=30.0)
                except Exception:
                    pass

    if args.warmup:
        # Cold-trajectory priming, process form: one unmeasured unpaced
        # replay compiles the trace's wave/suffix/lane shapes inside
        # every worker the router touches (each process has its own
        # XLA cache).
        replay(1.0, paced=False, with_slo=False)

    class_of = {r.idx: r.slo_class for r in trace}
    span = max(r.t_arrival for r in trace) or 1e-9
    mults = [float(x) for x in args.workload_mults.split(",") if x]
    sweep = []
    for mult in mults:
        fleet.reset_stats(
            clear_prefix_cache=bool(args.serve_cache_insert))
        # Coordinator-side series store (ISSUE 15): senses arrivals at
        # the router; workers carry their own stores behind the RPC
        # seam (GET /series aggregates both).
        series_store = _series_arm_leg(bool(args.serve_telemetry))
        res = replay(mult, paced=True)
        refresh_snapshots()
        st = fleet.slo_stats()
        met_total = sum(c["met"] for c in st["classes"].values())
        fin_total = sum(c["finished"] for c in st["classes"].values())
        toks = sum(len(v) for v in res["finished"].values())
        stats_of = fleet.batcher.request_stats
        per_class = {}
        for cname, cagg in sorted(st["classes"].items()):
            stats = [stats_of.get(res["frids"][idx])
                     for idx in res["frids"] if class_of[idx] == cname]
            stats = [s for s in stats if s]

            def pct(key, q):
                vals = [s[key] for s in stats if key in s]
                return round(float(np.percentile(vals, q)), 4) if vals \
                    else 0.0

            per_class[cname] = {
                "requests": cagg["finished"],
                "met": cagg["met"],
                "attainment": round(cagg["attainment"], 4),
                "ttft_p50_s": pct("ttft_s", 50),
                "ttft_p99_s": pct("ttft_s", 99),
                "itl_p50_s": pct("itl_s", 50),
                "itl_p99_s": pct("itl_s", 99),
                "latency_p50_s": pct("latency_s", 50),
                "latency_p99_s": pct("latency_s", 99),
            }
        # Tail attribution from the coordinator-stitched journeys
        # (worker-measured phases + failover_redo_s, ISSUE 10/11).
        jmap = {idx: fleet.journey(frid)
                for idx, frid in res["frids"].items()}
        pc_extra, leg_extra = _journey_attribution(jmap, class_of)
        for cname, extra in pc_extra.items():
            per_class.setdefault(cname, {}).update(extra)
        served_by = {}
        for idx, frid in res["frids"].items():
            served_by.setdefault(fleet.worker_of(frid), []).append(idx)
        workers = []
        for slot in fleet.slots:
            wst = slot.snapshot.get("slo", {})
            wmet = sum(c["met"] for c in wst.get("classes", {}).values())
            wfin = sum(c["finished"]
                       for c in wst.get("classes", {}).values())
            workers.append({
                "worker": slot.idx,
                "state": slot.state,
                "requests": wfin,
                "goodput_rps": round(wmet / res["duration_s"], 3),
                "slo_met_ratio": round(wmet / max(wfin, 1), 4),
                "tokens": sum(len(res["finished"][i])
                              for i in served_by.get(slot.idx, [])),
                "prefix_cache_hit_ratio": round(
                    slot.snapshot.get("prefix_cache", {}).get(
                        "hit_ratio", 0.0), 3),
                # This worker's OWN process-ledger share (its weights
                # live in its own process — nothing is shared).
                "memory_bytes": sum(
                    slot.snapshot.get("memory", {}).get(
                        "owner", {}).values()),
            })
        hits = sum(s.snapshot.get("prefix_cache", {}).get("hits", 0)
                   for s in fleet.slots)
        misses = sum(s.snapshot.get("prefix_cache", {}).get("misses", 0)
                     for s in fleet.slots)
        sweep.append({
            "rate_mult": mult,
            "offered_rps": round(len(trace) / (span / mult), 3),
            "duration_s": round(res["duration_s"], 3),
            "goodput_rps": round(met_total / res["duration_s"], 3),
            "slo_met_ratio": round(met_total / max(fin_total, 1), 4),
            "tok_s": round(toks / res["duration_s"], 2),
            **leg_extra,
            "prefix_cache_hit_ratio": round(
                hits / (hits + misses), 3) if (hits + misses) else 0.0,
            "classes": per_class,
            # process-fleet-only keys (OBSERVABILITY.md "Process-fleet
            # workload record"):
            "rejected_total": res["rejected"],
            "failovers": fleet.n_failovers,
            "worker_deaths": fleet.n_deaths,
            "respawns": fleet.n_respawns,
            "workers": workers,
            "memory": {"per_worker": [
                {"worker": w["worker"],
                 "memory_bytes": w["memory_bytes"]} for w in workers]},
            **_series_leg_columns(series_store, res["duration_s"]),
        })

    record = {
        "metric": f"workload_procfleet_goodput_{preset}",
        "value": (next((l for l in sweep if l["rate_mult"] == 1.0),
                       sweep[0])["goodput_rps"] if sweep else 0.0),
        "unit": "req/s",
        # Topology key: compare_bench pairs tok_s/memory only within
        # one process topology (N jax processes contend for the same
        # CPUs — cross-topology throughput is architecture, not drift).
        "proc_fleet": n_proc,
        "requests": len(trace),
        "arrival": spec.arrival,
        "rate_rps": spec.rate_rps,
        "sessions": spec.sessions,
        "seed": spec.seed,
        "output_min": spec.output_min,
        "output_max": spec.output_max,
        "trace_output_tokens": sum(r.max_new_tokens for r in trace),
        "slo": {
            "interactive": {"ttft_s": spec.interactive_ttft_s,
                            "itl_s": spec.interactive_itl_s},
            "batch": {"latency_s": spec.batch_latency_s},
        },
        "max_batch": args.serve_batch,
        "chunk": args.serve_chunk,
        "prefill_budget": int(args.serve_prefill_budget),
        "pipeline": bool(args.serve_pipeline),
        "prefix_cache": bool(args.serve_prefix_cache),
        "warmup": bool(args.warmup),
        "boot_s": round(t_boot, 3),
        "sweep": sweep,
        "kv_cache": args.kv,
        "speculative": args.serve_spec,
        "quant": quant_name(args, preset),
        "platform": platform,
        "telemetry": bool(args.serve_telemetry),
    }
    fleet.shutdown()
    print(json.dumps(record))
    if args.workload_out:
        with open(args.workload_out, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
    return record


def run_workload_disagg(args):
    """``--mode workload_disagg`` (ISSUE 17): the disaggregation
    tentpole's judge. Replays ONE seeded trace against four process
    topologies on the paged KV layout — colocated 2- and 4-worker
    fleets, 1 prefill + 1 decode (resource-matched: same two
    processes, split by role), and 1P:3D (the 4-process ratio sized
    to the decode-heavy trace) — at the same offered-load
    multipliers. Per
    arm the record carries the shared SLO keys (goodput, per-class
    TTFT/ITL percentiles, journey attribution with the ``handoff_s``
    phase) plus the handoff counters; TTFT/latency for handed-off
    requests score the request's WHOLE life (the import rebases the
    decode worker's clock by the shipped prefill-leg duration), so the
    tails are honestly comparable across arms. Every arm must serve
    byte-identical chains (``chains_identical`` — disaggregation is a
    placement decision, never a numerics one), and the ``comparison``
    block states the claim the artifact is checked in for: at the
    saturation point, disagg TTFT p99 (admission never waits behind
    decode-occupied rows) AND ITL p99 (decode never stalls behind a
    neighbour's chunked prefill) both at-or-under the colocated
    fleet's. Cross-arm tok_s is architecture, not drift —
    ``proc_fleet_roles`` joins compare_bench's trace identity so those
    keys drop with an ``unpaired`` note."""
    import sys

    import numpy as np

    from eventgpt_tpu import workload as wl
    from eventgpt_tpu.fleet_proc import ProcFleet
    from eventgpt_tpu.obs import journey as obs_journey
    from eventgpt_tpu.obs import metrics as obs_metrics
    from eventgpt_tpu.serve import QueueFullError

    preset, cfg, platform = _procfleet_preset(args)
    telemetry = bool(args.serve_telemetry)
    obs_metrics.configure(telemetry)
    if args.workload_trace:
        spec, trace = wl.load_trace(args.workload_trace)
    else:
        spec = wl.WorkloadSpec(
            seed=args.workload_seed,
            n_requests=args.workload_requests,
            rate_rps=args.workload_rate,
            arrival=args.workload_arrival,
            sessions=args.workload_sessions,
            output_min=args.workload_output_min,
            output_max=args.workload_output_max,
            interactive_ttft_s=args.slo_ttft_s,
            interactive_itl_s=args.slo_itl_s,
            batch_latency_s=args.slo_latency_s,
        )
        trace = wl.generate_trace(spec)
    if args.workload_save:
        wl.save_trace(args.workload_save, spec, trace)
    obs_journey.configure(max(1024, 2 * len(trace)))

    need = max(wl.cache_positions(r, cfg.num_event_tokens)
               + r.max_new_tokens for r in trace)
    max_len = ((need + 1 + args.serve_spec + 127) // 128) * 128
    worker_cmd = [
        sys.executable, "-m", "eventgpt_tpu.cli.serve", "--worker",
        "--model_path", "tiny-random",
        "--max_batch", str(args.serve_batch),
        "--max_len", str(max_len),
        "--chunk", str(args.serve_chunk),
        "--kv_cache", args.kv,
        "--kv_layout", "paged",
        "--speculative", str(args.serve_spec),
        "--first_chunk", str(args.serve_first_chunk or 0),
        "--prefill_budget", str(int(args.serve_prefill_budget)),
        "--max_queue", "0",
    ]
    if not args.serve_pipeline:
        worker_cmd.append("--no_pipeline")
    if not args.serve_prefix_cache:
        worker_cmd.append("--no_prefix_cache")
    if not telemetry:
        worker_cmd.append("--no_telemetry")

    shape = (cfg.num_event_frames, 3, cfg.vision.image_size,
             cfg.vision.image_size)
    pix_cache = {}

    def pixels_for(r):
        if r.pixels_seed not in pix_cache:
            pix_cache[r.pixels_seed] = wl.stream_pixels(shape, r.pixels_seed)
        return pix_cache[r.pixels_seed]

    def slo_for(r):
        return spec.slo_for(r.slo_class)

    class_of = {r.idx: r.slo_class for r in trace}
    span = max(r.t_arrival for r in trace) or 1e-9
    mults = [float(x) for x in args.workload_mults.split(",") if x]

    def run_arm(n_proc, roles):
        """One topology: boot, warm, sweep, shut down. Returns a full
        workload-shaped record (individually compare_bench-gateable)
        plus the per-point chains for the cross-arm identity check."""
        t0 = time.perf_counter()
        fleet = ProcFleet(worker_cmd, n_proc, roles=roles,
                          spawn_timeout_s=600, probe_interval_s=0.03,
                          rpc_deadline_s=60.0, shutdown_drain_s=60.0)
        t_boot = time.perf_counter() - t0

        def replay(rate_mult, paced=True, with_slo=True):
            tr0 = time.perf_counter()
            frids = {}
            rejected = 0
            for r in trace:
                if paced:
                    while True:
                        dt = (r.t_arrival / rate_mult
                              - (time.perf_counter() - tr0))
                        if dt <= 0:
                            break
                        time.sleep(min(dt, 0.005))
                try:
                    frids[r.idx] = fleet.submit_ids(
                        r.input_ids, pixels_for(r), r.max_new_tokens,
                        slo=slo_for(r) if with_slo else None)
                except QueueFullError:
                    rejected += 1
            finished = {idx: fleet.result(f, timeout=600)
                        for idx, f in frids.items()}
            return {"frids": frids, "finished": finished,
                    "duration_s": time.perf_counter() - tr0,
                    "rejected": rejected}

        def refresh_snapshots():
            # SLO class counts live in worker snapshots the supervisor
            # refreshes once per probe tick; each point's accounting
            # reads them right after the last finish, so fetch fresh.
            for slot in fleet.slots:
                if slot.addr is not None:
                    try:
                        slot.snapshot = fleet._rpc(slot, "snapshot",
                                                   deadline_s=30.0)
                    except Exception:
                        pass

        if args.warmup:
            # Cold-trajectory priming: compiles the trace's shapes —
            # including the handoff splice executable on the decode
            # side — inside every worker the router touches.
            replay(1.0, paced=False, with_slo=False)

        sweep = []
        chains_by_mult = {}
        for mult in mults:
            fleet.reset_stats(
                clear_prefix_cache=bool(args.serve_cache_insert))
            res = replay(mult, paced=True)
            refresh_snapshots()
            st = fleet.slo_stats()
            met_total = sum(c["met"] for c in st["classes"].values())
            fin_total = sum(c["finished"] for c in st["classes"].values())
            toks = sum(len(v) for v in res["finished"].values())
            stats_of = fleet.batcher.request_stats
            per_class = {}
            for cname, cagg in sorted(st["classes"].items()):
                stats = [stats_of.get(res["frids"][idx])
                         for idx in res["frids"]
                         if class_of[idx] == cname]
                stats = [s for s in stats if s]

                def pct(key, q):
                    vals = [s[key] for s in stats if key in s]
                    return (round(float(np.percentile(vals, q)), 4)
                            if vals else 0.0)

                per_class[cname] = {
                    "requests": cagg["finished"],
                    "met": cagg["met"],
                    "attainment": round(cagg["attainment"], 4),
                    "ttft_p50_s": pct("ttft_s", 50),
                    "ttft_p99_s": pct("ttft_s", 99),
                    "itl_p50_s": pct("itl_s", 50),
                    "itl_p99_s": pct("itl_s", 99),
                    "latency_p50_s": pct("latency_s", 50),
                    "latency_p99_s": pct("latency_s", 99),
                }
            jmap = {idx: fleet.journey(frid)
                    for idx, frid in res["frids"].items()}
            pc_extra, leg_extra = _journey_attribution(jmap, class_of)
            for cname, extra in pc_extra.items():
                per_class.setdefault(cname, {}).update(extra)
            with fleet._lock:
                handoffs = {
                    "shipped": fleet.n_handoffs,
                    "bytes": fleet.n_handoff_bytes,
                    "retries": fleet.n_handoff_retries,
                    "redos": fleet.n_handoff_redos,
                }
            chains_by_mult[mult] = dict(res["finished"])
            sweep.append({
                "rate_mult": mult,
                "offered_rps": round(len(trace) / (span / mult), 3),
                "duration_s": round(res["duration_s"], 3),
                "goodput_rps": round(met_total / res["duration_s"], 3),
                "slo_met_ratio": round(met_total / max(fin_total, 1), 4),
                "tok_s": round(toks / res["duration_s"], 2),
                **leg_extra,
                "classes": per_class,
                "rejected_total": res["rejected"],
                "failovers": fleet.n_failovers,
                "handoffs": handoffs,
            })
        record = {
            "metric": f"workload_disagg_goodput_{preset}",
            "value": (next((x for x in sweep if x["rate_mult"] == 1.0),
                           sweep[0])["goodput_rps"] if sweep else 0.0),
            "unit": "req/s",
            "proc_fleet": n_proc,
            "proc_fleet_roles": roles or "colocated",
            "kv_layout": "paged",
            "requests": len(trace),
            "arrival": spec.arrival,
            "rate_rps": spec.rate_rps,
            "sessions": spec.sessions,
            "seed": spec.seed,
            "output_min": spec.output_min,
            "output_max": spec.output_max,
            "trace_output_tokens": sum(r.max_new_tokens for r in trace),
            "slo": {
                "interactive": {"ttft_s": spec.interactive_ttft_s,
                                "itl_s": spec.interactive_itl_s},
                "batch": {"latency_s": spec.batch_latency_s},
            },
            "max_batch": args.serve_batch,
            "chunk": args.serve_chunk,
            "prefill_budget": int(args.serve_prefill_budget),
            "warmup": bool(args.warmup),
            "boot_s": round(t_boot, 3),
            "sweep": sweep,
            "kv_cache": args.kv,
            "speculative": args.serve_spec,
            "quant": quant_name(args, preset),
            "platform": platform,
            "telemetry": telemetry,
        }
        fleet.shutdown()
        return record, chains_by_mult

    # Each disagg arm judges against the colocated fleet with the SAME
    # process count: on a shared-CPU host, N jax processes timesharing
    # the cores IS part of the topology (the WORKLOAD_PROCFLEET
    # pairing lesson), so a 4-process disagg arm vs a 2-process fleet
    # would measure the oversubscription, not the role split. 1P:1D vs
    # colocated-2 is the resource-matched headline pair; the 4-process
    # arm uses a 1:3 ratio because the replayed trace is decode-heavy
    # (short chat prompts, long generations) — pool ratios are sized to
    # the workload's prefill:decode compute split, not fixed at 1:1.
    arms = [("colocated2", 2, None), ("colocated4", 4, None),
            ("disagg_1p1d", 2, "1:1"), ("disagg_1p3d", 4, "1:3")]
    baseline_of = {"disagg_1p1d": "colocated2",
                   "disagg_1p3d": "colocated4"}
    records = {}
    chains = {}
    for name, n_proc, roles in arms:
        sys.stderr.write(f"workload_disagg arm {name} "
                         f"({n_proc} workers, roles={roles})\n")
        records[name], chains[name] = run_arm(n_proc, roles)

    # Chain identity across every arm and every sweep point: the same
    # trace request must decode to the same bytes whether its KV
    # crossed a process boundary or not.
    ref = chains["colocated2"][mults[0]]
    chains_identical = all(
        chains[name][mult] == ref
        for name, _, _ in arms for mult in mults)

    sat = mults[-1]

    def tails(name, mult):
        legs = records[name]["sweep"]
        leg = next(x for x in legs if x["rate_mult"] == mult)
        cl = leg["classes"].get("interactive", {})
        return {"ttft_p99_s": cl.get("ttft_p99_s", 0.0),
                "itl_p99_s": cl.get("itl_p99_s", 0.0),
                "goodput_rps": leg["goodput_rps"]}

    comparison = {"saturation_rate_mult": sat,
                  "colocated2": tails("colocated2", sat),
                  "colocated4": tails("colocated4", sat)}
    for name, base_name in baseline_of.items():
        t = tails(name, sat)
        base = comparison[base_name]
        comparison[name] = {
            **t,
            "baseline": base_name,
            "ttft_p99_beats_colocated":
                t["ttft_p99_s"] <= base["ttft_p99_s"],
            "itl_p99_beats_colocated":
                t["itl_p99_s"] <= base["itl_p99_s"],
        }

    record = {
        "metric": f"workload_disagg_{preset}",
        "value": records["disagg_1p1d"]["value"],
        "unit": "req/s",
        "chains_identical": bool(chains_identical),
        "comparison": comparison,
        "arms": records,
    }
    print(json.dumps(record))
    if args.workload_out:
        with open(args.workload_out, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
    return record


def quant_name(args, preset):
    return args.quant if preset in ("7b", "13b") else "bf16"


def run_stream(args):
    """Streaming-QA latency envelope (VERDICT r4 #6): the reference claims
    "understanding of high-speed scenes within 50 ms"
    (``/root/reference/README.md:119``) but ships no running loop; this leg
    measures ours. The native threaded reader (``native.EventStream``)
    feeds 50 ms windows of the reference sample; per window we record
    window-available -> FIRST TOKEN (raster + CLIP preprocess + encode +
    prefill + 1-token commit) and -> ANSWER COMPLETE (32 tokens), both
    warmed, medians over the windows."""
    import tempfile

    import jax.numpy as jnp
    import numpy as np

    from eventgpt_tpu.models import eventchat
    from eventgpt_tpu.native import EventStream, available
    from eventgpt_tpu.ops.image import clip_preprocess_batch
    from eventgpt_tpu.ops.raster import (
        events_to_frames, events_to_structured_stream, events_window_us,
        load_event_npy,
    )

    preset0, _, _ = _resolve_preset(args)
    if not available():
        # Skip-record, not a crash (ISSUE 5 satellite): hosts without the
        # native build still complete run_all with an honest JSON marker
        # instead of a stderr traceback and a missing leg.
        record = {"metric": f"stream_first_token_{preset0}",
                  "skipped": "libegpt_native missing"}
        print(json.dumps(record))
        return record
    if not os.path.exists(SAMPLE):
        record = {"metric": f"stream_first_token_{preset0}",
                  "skipped": f"reference sample missing: {SAMPLE}"}
        print(json.dumps(record))
        return record

    preset, cfg, platform = _resolve_preset(args)
    dtype = jnp.bfloat16
    quant = args.quant if preset in ("7b", "13b") else "bf16"
    params = _build_params(cfg, dtype, quant)
    # Prompt shape of the inference CLI run (system + query + event block).
    ids = [1] + [7] * 34 + [-200] + [9] * 16

    window_s = args.stream_window_ms / 1e3
    answer_budget = 32
    firsts, completes, counts = [], [], []
    # Reference sample -> structured stream the native reader consumes.
    # Private per-run directory, not a fixed name in the shared tmp dir
    # (ADVICE r5: concurrent runs clobbered each other, and a pre-placed
    # symlink at the world-writable path could redirect the np.save).
    with tempfile.TemporaryDirectory(prefix="egpt_bench_") as stream_dir:
        stream_path = os.path.join(stream_dir, "bench_stream.npy")
        np.save(stream_path,
                events_to_structured_stream(load_event_npy(SAMPLE)))
        with EventStream(stream_path) as stream:
            # Unpaced replay: drain everything, then window on event time —
            # the measured quantity is processing latency per available
            # window, which paced replay would only pad with idle waiting.
            buf = {k: np.empty(0, d) for k, d in
                   (("x", np.uint16), ("y", np.uint16),
                    ("t", np.float64), ("p", np.uint8))}
            while True:
                out = stream.pop_until(1e18)
                if out["t"].size:
                    buf = {k: np.concatenate([buf[k], out[k]]) for k in buf}
                if not stream.running():
                    break
                time.sleep(0.002)
    t_all = buf["t"]
    cursor = float(t_all.min())

    def answer(ev, budget):
        frames = events_to_frames(ev, cfg.num_event_frames)
        pixels = clip_preprocess_batch(frames, cfg.vision.image_size)
        # eos_token_id=None: the metric is a fixed-length decode (an EOS
        # from real weights must not shrink the measured budget).
        out = eventchat.generate(
            params, cfg, [ids], pixels[None], max_new_tokens=budget,
            temperature=0.0, eos_token_id=None,
        )[0]
        return out

    windows = []
    while cursor < t_all.max():
        sel = (t_all >= cursor) & (t_all < cursor + window_s)
        cursor += window_s
        if sel.sum() < cfg.num_event_frames:
            continue
        windows.append(events_window_us(buf, sel))
    if not windows:
        raise RuntimeError("stream produced no measurable 50 ms windows")
    # Compile/load both executables outside the measured loop —
    # steady-state streaming is the claim under test. Short recordings
    # (sample1 is one window) are re-measured round-robin so the medians
    # rest on stream_windows samples either way.
    answer(windows[0], 1)
    answer(windows[0], answer_budget)
    for i in range(args.stream_windows):
        ev = windows[i % len(windows)]
        t0 = time.perf_counter()
        first = answer(ev, 1)
        firsts.append(time.perf_counter() - t0)
        assert len(first) == 1
        t0 = time.perf_counter()
        full = answer(ev, answer_budget)
        completes.append(time.perf_counter() - t0)
        assert len(full) == answer_budget
        counts.append(int(len(ev["t"])))
    record = {
        "metric": f"stream_first_token_{preset}",
        "value": round(float(np.median(firsts)) * 1e3, 1),
        "unit": "ms",
        "stream_window_ms": args.stream_window_ms,
        "windows_measured": len(completes),
        "distinct_windows": len(windows),
        "events_per_window_median": int(np.median(counts)),
        "stream_first_token_ms": round(float(np.median(firsts)) * 1e3, 1),
        "stream_answer_complete_ms": round(
            float(np.median(completes)) * 1e3, 1),
        "answer_tokens": answer_budget,
        "quant": quant,
        "platform": platform,
    }
    return _emit(record, "stream", record["value"])


def run_warm_probe(args):
    """Cold-start probe: encode + prefill first-call latency in THIS process.

    Run after a decode leg has populated the persistent compilation cache
    and the measured times are warm starts (executable deserialization
    instead of XLA compilation) — the VERDICT r2 #2 'second-process < 1 s'
    contract."""
    import jax
    import jax.numpy as jnp

    from eventgpt_tpu.data.tokenizer import split_at_event
    from eventgpt_tpu.models import eventchat, llama as llama_mod
    from eventgpt_tpu.models.eventchat import (
        _decode_loop_jit, _pad_batch, _prefill_jit, splice_embeddings,
    )

    preset, cfg, platform = _resolve_preset(args)
    dtype = jnp.bfloat16
    params = _build_params(cfg, dtype,
                           args.quant if preset in ("7b", "13b") else "bf16")
    pixels = jnp.asarray(_event_pixels(cfg, 1), dtype)
    ids = [1] + [7] * 34 + [-200] + [9] * 16
    prompt_len = 35 + cfg.num_event_tokens + 16

    t0 = time.perf_counter()
    ev = eventchat.encode_events_batch(params, cfg, pixels)
    _sync(ev)
    t_encode = time.perf_counter() - t0

    embeds = [splice_embeddings(params, cfg, split_at_event(ids), ev[0])
              for _ in range(args.batch)]
    padded, mask, _ = _pad_batch(embeds)
    cache_len = ((prompt_len + args.decode_tokens + 64) // 64) * 64
    cache = llama_mod.init_kv_cache(
        cfg.llama, args.batch, cache_len, dtype, quant=args.kv == "int8"
    )
    t0 = time.perf_counter()
    last, cache = _prefill_jit(params, cfg, padded, mask, cache, True)
    _sync(last)
    t_prefill = time.perf_counter() - t0

    # The decode loop is the third (and largest) compile on the cold path
    # to a first answer; include its first call so the warm number covers
    # the whole serve pipeline. Timing includes the actual decode run —
    # subtract budget/tok_s for the pure compile share.
    t0 = time.perf_counter()
    toks, _, cache = _decode_loop_jit(
        params, cfg, last, cache, jax.random.PRNGKey(0),
        args.decode_tokens, 0.0, 1.0, -1,
    )
    del cache
    _sync(toks)
    t_decode_first = time.perf_counter() - t0

    record = {
        "metric": f"warm_start_{preset}",
        "value": round(t_encode + t_prefill + t_decode_first, 3),
        "unit": "s",
        "encode_first_s": round(t_encode, 3),
        "prefill_first_s": round(t_prefill, 3),
        "decode_loop_first_s": round(t_decode_first, 3),
        "platform": platform,
    }
    print(json.dumps(record))
    return record


# TPU v5e bf16 matmul peak (the chip PERFORMANCE.md's rooflines use);
# int8-weight training still runs its MXU passes in bf16 after dequant.
_V5E_PEAK_BF16_FLOPS = 197e12


def _train_flops_per_step(cfg, batch: int, seq: int) -> dict:
    """Analytic model FLOPs for one stage-2 step (multiply-add = 2).

    Decomposition (what actually runs, not 6ND folklore):
      * LLaMA matmuls fwd: 2 * n_mm * tokens.
      * LLaMA attention fwd: scores + AV, causal-halved:
        2 * L * seq^2 * q_dim per sample.
      * backward: dgrad through every frozen LLaMA matmul is required for
        LoRA (chain rule through the base), and dgrad is exactly ONE
        matmul of equal cost (dX = dY @ W^T) — wgrad exists only for the
        LoRA/projector leaves (negligible). So matmul bwd ~ 1x fwd, NOT
        the full-training 2x. Attention bwd needs dV, dA, dQ, dK — four
        matmuls vs the forward's two -> attention bwd = 2x attention fwd.
      * CLIP tower: forward only — stage 2 takes no gradient through it
        (the projector is the first trainable node on that path) —
        matmuls PLUS the attention score/AV term (ADVICE r5: 2 matmuls
        * 2 FLOP/MAC * L * T^2 * h over T = 577 tokens per frame,
        bidirectional so no causal halving; ~0.3 TFLOP/step at the 7B
        best point — omitting it understated CLIP by ~9%).
      * remat recompute is NOT counted (standard MFU counts model FLOPs;
        the recompute shows up as lower MFU, which is the point).
    """
    lc = cfg.llama
    hd = lc.resolved_head_dim()
    q_dim = lc.num_heads * hd
    kv_dim = lc.num_kv_heads * hd
    n_mm = lc.num_layers * (
        lc.hidden_size * q_dim + 2 * lc.hidden_size * kv_dim
        + q_dim * lc.hidden_size + 3 * lc.hidden_size * lc.intermediate_size
    ) + lc.hidden_size * lc.vocab_size  # lm_head; embed is a gather
    tokens = batch * seq
    llama_mm_fwd = 2.0 * n_mm * tokens
    llama_attn_fwd = 2.0 * lc.num_layers * seq * seq * q_dim * batch / 2.0 * 2.0
    # (scores + AV = 2 matmuls) * causal 1/2 — written out so the factors
    # are auditable: 2 FLOP/MAC * 2 matmuls * 1/2 causal = 2.
    vc = cfg.vision
    clip_seq = (vc.image_size // vc.patch_size) ** 2 + 1  # 577 at ViT-L/336
    n_frames = batch * cfg.num_event_frames
    clip_tokens = n_frames * clip_seq
    n_clip = vc.num_layers * (4 * vc.hidden_size ** 2
                              + 2 * vc.hidden_size * vc.intermediate_size)
    # Attention score/AV term: 2 FLOP/MAC * 2 matmuls * L * T^2 * h per
    # frame, no causal halving (the vision tower is bidirectional).
    clip_attn_fwd = 2.0 * 2.0 * vc.num_layers * clip_seq * clip_seq \
        * vc.hidden_size * n_frames
    clip_fwd = 2.0 * n_clip * clip_tokens + clip_attn_fwd
    llama_fwd = llama_mm_fwd + llama_attn_fwd
    # fwd + dgrad-only matmul bwd (1x) + attention bwd (2x attn fwd):
    total = 2.0 * llama_mm_fwd + 3.0 * llama_attn_fwd + clip_fwd
    return {"total": total, "llama_fwd": llama_fwd, "clip_fwd": clip_fwd,
            "clip_attn_fwd": clip_attn_fwd, "n_llama_mm_params": n_mm}


def run_train(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from eventgpt_tpu.train import steps as steps_mod
    from eventgpt_tpu.train.lora import LoraConfig
    from eventgpt_tpu.train.optim import linear_warmup_cosine, make_optimizer

    preset, cfg, platform = _resolve_preset(args)
    if args.remat != "default":
        import dataclasses

        cfg = dataclasses.replace(
            cfg, llama=dataclasses.replace(cfg.llama,
                                           remat=args.remat == "on"))
    if args.remat_policy != cfg.llama.remat_policy:
        # Remat-policy sweep plumbing (ISSUE 13 satellite): the stage-2
        # step's jax.checkpoint policy as a bench axis, so the
        # full / dots_saveable / nothing_saveable sweep can run on
        # hardware with one flag flip per leg.
        import dataclasses

        cfg = dataclasses.replace(
            cfg, llama=dataclasses.replace(cfg.llama,
                                           remat_policy=args.remat_policy))
    dtype = jnp.bfloat16

    # QLoRA-style stage 2 by default at 7B: int8 frozen base + apply-form
    # LoRA keeps the whole train step inside one v5e chip's HBM (bf16 base
    # measures 18.6G > 15.75G); mirrors the reference's bits/nf4 quantized
    # finetune options (TrainingArguments, SURVEY.md §2.2).
    quant = args.quant if preset in ("7b", "13b") else "bf16"
    params = _build_params(cfg, dtype, quant)
    lcfg = LoraConfig(r=args.lora_r)
    trainable, frozen = steps_mod.split_stage2(
        params, cfg, lcfg, jax.random.PRNGKey(1), dtype=jnp.float32
    )
    opt = make_optimizer(linear_warmup_cosine(1e-4, 1000, 10))
    state = steps_mod.init_train_state(trainable, frozen, opt)
    step_fn = steps_mod.make_train_step(
        cfg, opt, steps_mod.make_stage2_combine(lcfg), donate=True
    )

    # Stage-2 shaped batch: one event block + text at --seq tokens.
    from eventgpt_tpu.train.data import synthetic_multimodal_batch

    b, seq = args.batch, args.seq
    host = synthetic_multimodal_batch(
        cfg, b, seq, pixel_values=_event_pixels(cfg, b),
        mask_event_labels=True,
    )
    batch = {
        k: jnp.asarray(v, dtype) if k == "pixel_values" else jnp.asarray(v)
        for k, v in host.items()
    }

    state, metrics = step_fn(state, batch)  # compile
    _sync(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, metrics = step_fn(state, batch)
    _sync(metrics["loss"])
    dt = (time.perf_counter() - t0) / args.steps

    tokens_per_step = int(host["attn_mask"].sum())
    flops = _train_flops_per_step(cfg, b, seq)
    record = {
        "metric": f"stage2_step_time_{preset}",
        "value": round(dt, 4),
        "unit": "s/step",
        "batch": b,
        "seq": seq,
        "lora_r": args.lora_r,
        "quant": quant,
        "remat": cfg.llama.remat,
        "remat_policy": cfg.llama.remat_policy,
        "tokens_per_s": round(tokens_per_step / dt, 1),
        "model_tflops_per_step": round(flops["total"] / 1e12, 2),
        "loss_finite": bool(np.isfinite(float(metrics["loss"]))),
        "platform": platform,
    }
    if platform == "tpu":
        record["mfu"] = round(flops["total"] / dt / _V5E_PEAK_BF16_FLOPS, 4)
    return _emit(record, "train", dt)


def run_train_sweep(args):
    """Stage-2 step time over batch x seq x remat (VERDICT r4 #3): each
    point is a fresh subprocess (clean HBM; OOM at one point must not
    poison the next), recorded honestly including OOM entries. Emits ONE
    JSON line with the grid and the best throughput config."""
    points = []
    best = None
    # Remat axes (ISSUE 13 satellite): remat-on runs once per requested
    # checkpoint POLICY (--remat_policy picks one; full remat is the
    # r4-era behavior), remat-off stays the OOM-probing endpoint. The
    # hardware sweep flips --remat_policy per leg to fill the
    # full / dots_saveable middle ground VERDICT r5 flagged.
    remat_axes = [("on", args.remat_policy), ("off", None)]
    for remat, policy in remat_axes:
        for seq in (704, 1408):
            for batch in (1, 2, 4, 8):
                leg_args = ["--mode", "train", "--preset", args.preset,
                            "--quant", args.quant, "--steps", str(args.steps),
                            "--seq", str(seq), "--batch", str(batch),
                            "--lora_r", str(args.lora_r), "--remat", remat]
                if policy is not None:
                    leg_args += ["--remat_policy", policy]
                try:
                    r = _leg(leg_args, timeout=2400)
                    pt = {"batch": batch, "seq": seq, "remat": remat == "on",
                          "remat_policy": policy,
                          "step_s": r["value"],
                          "tokens_per_s": r["tokens_per_s"],
                          "mfu": r.get("mfu")}
                    if best is None or pt["tokens_per_s"] > best["tokens_per_s"]:
                        best = pt
                except Exception as e:
                    msg = str(e)[-200:]
                    pt = {"batch": batch, "seq": seq, "remat": remat == "on",
                          "remat_policy": policy,
                          "oom_or_error": msg}
                points.append(pt)
                sys.stderr.write(f"train_sweep point {pt}\n")
    record = {
        "metric": f"stage2_train_sweep_{args.preset}",
        "value": best["tokens_per_s"] if best else 0.0,
        "unit": "tok/s",
        "vs_baseline": 1.0,
        "best": best,
        "grid": points,
    }
    print(json.dumps(record))
    return record


def _leg(extra_args, timeout=3600):
    """Run one bench leg in a fresh subprocess; return its last-line JSON.
    Subprocess stdout is NOT echoed (the all-mode contract is one JSON
    line); stderr passes through for debugging."""
    cmd = [sys.executable, os.path.abspath(__file__)] + extra_args
    proc = subprocess.run(cmd, cwd=HERE, timeout=timeout,
                          capture_output=True, text=True)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
        raise RuntimeError(f"bench leg {extra_args} failed rc={proc.returncode}")
    lines = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    if not lines:
        sys.stderr.write(proc.stdout[-2000:] + proc.stderr[-2000:])
        raise RuntimeError(f"bench leg {extra_args} produced no JSON")
    return json.loads(lines[-1])


def run_all(args):
    """One merged record: headline decode @ the reference run shape, batch
    sweep, 13B, train step, warm start, serving (aggregate + latency).
    Each leg is a subprocess (clean HBM between legs; warm numbers are
    second-process by construction)."""
    base = ["--preset", args.preset, "--decode_tokens", str(args.decode_tokens),
            "--quant", args.quant, "--batch", str(args.batch),
            "--kv", args.kv] + (["--fuse"] if args.fuse else [])
    headline = _leg(["--mode", "decode", "--sweep"] + base)

    record = dict(headline)
    try:
        warm = _leg(["--mode", "warm_probe"] + base)
        record["encode_first_warm_s"] = warm["encode_first_s"]
        record["prefill_first_warm_s"] = warm["prefill_first_s"]
        record["decode_loop_first_warm_s"] = warm["decode_loop_first_s"]
    except Exception as e:
        sys.stderr.write(f"warm probe failed: {e}\n")

    # Streaming-QA latency envelope (r5): first-token / answer-complete
    # per 50 ms native-stream window.
    try:
        st = _leg(["--mode", "stream", "--preset", args.preset,
                   "--quant", args.quant])
        if "skipped" in st:
            record["stream_skipped"] = st["skipped"]
        else:
            record["stream_first_token_ms"] = st["stream_first_token_ms"]
            record["stream_answer_complete_ms"] = \
                st["stream_answer_complete_ms"]
            record["stream_window_ms"] = st["stream_window_ms"]
    except Exception as e:
        sys.stderr.write(f"stream leg failed: {e}\n")

    # 13B fits one chip only via int8; off-TPU (tiny CPU runs) skip it.
    if headline.get("platform") == "tpu" and args.preset in ("auto", "7b"):
        try:
            r13 = _leg(["--mode", "decode", "--preset", "13b",
                        "--decode_tokens", str(args.decode_tokens),
                        "--quant", "int8"])
            record["decode_13b_tok_s"] = r13["value"]
        except Exception as e:
            sys.stderr.write(f"13b leg failed: {e}\n")

    # Speculative decode bracket from ONE leg: ceiling (zeros weights give a
    # fully-draftable chain) and the zero-acceptance floor (iterations/dt —
    # exact, since iteration cost is shape-static). Real-checkpoint
    # throughput lands between them by text repetitiveness.
    try:
        sc = _leg(["--mode", "spec", "--preset", args.preset,
                   "--decode_tokens", str(args.decode_tokens),
                   "--quant", args.quant,
                   "--spec_window", str(args.spec_window)])
        record["spec_ceiling_tok_s"] = sc["value"]
        record["spec_floor_tok_s"] = sc["floor_tok_s"]
        record["spec_tokens_per_iteration"] = sc["tokens_per_iteration"]
    except Exception as e:
        sys.stderr.write(f"spec leg failed: {e}\n")

    try:
        tr = _leg(["--mode", "train", "--preset", args.preset,
                   "--quant", args.quant, "--steps", str(args.steps),
                   "--seq", str(args.seq), "--lora_r", str(args.lora_r)])
        record["train_step_s"] = tr["value"]
        record["train_tokens_per_s"] = tr.get("tokens_per_s")
        record["train_mfu"] = tr.get("mfu")
    except Exception as e:
        sys.stderr.write(f"train leg failed: {e}\n")
    # Best-throughput config from the r5 sweep (PERFORMANCE.md "Stage-2
    # finetune": batch 2 x 704 edges out batch 1 by ~7%; remat-off OOMs).
    if args.batch == 1:
        try:
            tb = _leg(["--mode", "train", "--preset", args.preset,
                       "--quant", args.quant, "--steps", str(args.steps),
                       "--seq", str(args.seq), "--lora_r", str(args.lora_r),
                       "--batch", "2"])
            record["train_best_tokens_per_s"] = tb.get("tokens_per_s")
            record["train_best_mfu"] = tb.get("mfu")
            record["train_best_config"] = {"batch": 2, "seq": args.seq,
                                           "remat": True}
        except Exception as e:
            sys.stderr.write(f"train best-config leg failed: {e}\n")

    # Serving legs (VERDICT r3 weak #1/#2: the serving story must reach
    # the driver artifact, with latency): batch 4 and batch 8, both
    # warmed, at the reference's 512 budget.
    serve_base = ["--mode", "serve", "--preset", args.preset,
                  "--quant", args.quant,
                  "--decode_tokens", str(args.decode_tokens),
                  "--serve_requests", str(args.serve_requests),
                  "--serve_chunk", str(args.serve_chunk),
                  # r5 segment sweep: the 16-token TTFT ramp is free at
                  # batch 4 (+0.5% aggregate, -26% TTFT p50) and trades
                  # 9% for -29% TTFT at batch 8 — PERFORMANCE.md table.
                  # None = unset: ramp 16 on the batch-4 leg; an explicit
                  # --serve_first_chunk (incl. 0) passes through.
                  "--serve_first_chunk",
                  str(16 if args.serve_first_chunk is None
                      else args.serve_first_chunk),
                  "--warmup", "1"]
    try:
        sv = _leg(serve_base + ["--serve_batch", "4"])
        record["serve_aggregate_tok_s"] = sv["value"]
        for k in ("ttft_p50_s", "ttft_p99_s", "latency_p50_s",
                  "latency_p99_s", "admission_stall_s", "first_request_s",
                  "warmup_s", "host_gap_s", "device_segment_s",
                  "overlap_ratio"):
            record[f"serve_{k}"] = sv[k]
    except Exception as e:
        sys.stderr.write(f"serve leg failed: {e}\n")
    # Batch 8 runs plain bf16 KV since the r4 donation fix (int8 KV is
    # kept as the fallback for configs where bf16 no longer fits). The
    # TTFT ramp is off here: at one admission wave it trades 9% aggregate
    # for TTFT the b4 leg already covers, and this leg's job is the
    # max-aggregate record.
    try:
        sv8 = _leg(serve_base + ["--serve_batch", "8",
                                 "--serve_first_chunk", "0"])
        record["serve_b8_tok_s"] = sv8["value"]
        record["serve_b8_kv"] = sv8["kv_cache"]
        record["serve_b8_latency_p99_s"] = sv8["latency_p99_s"]
    except Exception as e:
        sys.stderr.write(f"serve b8 bf16 leg failed: {e}\n")
        try:
            sv8 = _leg(serve_base + ["--serve_batch", "8", "--kv", "int8",
                                     "--serve_first_chunk", "0"])
            record["serve_b8_tok_s"] = sv8["value"]
            record["serve_b8_kv"] = "int8"
            record["serve_b8_latency_p99_s"] = sv8["latency_p99_s"]
        except Exception as e2:
            sys.stderr.write(f"serve b8 int8 leg failed: {e2}\n")

    # Shared-prefix serving legs (r5): session prefix (system + event)
    # cached once, admissions prefill only the query tail, plus the TTFT
    # ramp (with suffix prefills this cheap the short first segment is
    # ~free). Batch 16 answers r4's "bounded by the 16 per-request
    # prefills" (+36%); batch 32 is the single-chip ceiling (b40 OOMs at
    # runtime, b48 at compile).
    for width in (16, 32):
        try:
            sv = _leg(["--mode", "serve", "--preset", args.preset,
                       "--quant", args.quant, "--decode_tokens", "128",
                       "--serve_requests", str(width),
                       "--serve_batch", str(width),
                       "--kv", "int8", "--warmup", "1",
                       "--serve_prefix", "1", "--serve_first_chunk", "16"])
            record[f"serve_b{width}_prefix_tok_s"] = sv["value"]
            record[f"serve_b{width}_prefix_ttft_p50_s"] = sv["ttft_p50_s"]
        except Exception as e:
            sys.stderr.write(f"serve b{width} prefix leg failed: {e}\n")

    # Multi-session prefix-cache legs (ISSUE 4): S distinct event streams
    # round-robin — the radix cache's target traffic. Three-way A/B on
    # IDENTICAL traffic: cache on (auto insert-on-prefill), the r5
    # single-slot emulation (one operator entry, no auto-insert), and
    # cache off (full prefill per request). The BENCH json carries the
    # hit ratio, the dispatch-count shape (wave vs full vs suffix) and
    # the wave-size histogram for each.
    ms_base = ["--mode", "serve", "--preset", args.preset,
               "--quant", args.quant, "--decode_tokens", "128",
               "--serve_requests", "16", "--serve_batch", "4",
               "--kv", "int8", "--warmup", "1", "--serve_sessions", "4"]
    for tag, extra in (
        ("", ["--serve_prefix_cache", "1"]),
        ("_slot", ["--serve_prefix_cache", "1", "--serve_cache_insert", "0",
                   "--serve_prefix", "1"]),
        ("_nocache", ["--serve_prefix_cache", "0"]),
    ):
        try:
            sv = _leg(ms_base + extra)
            record[f"serve_ms4{tag}_tok_s"] = sv["value"]
            record[f"serve_ms4{tag}_ttft_p50_s"] = sv["ttft_p50_s"]
            if "prefix_cache_hit_ratio" in sv:
                record[f"serve_ms4{tag}_hit_ratio"] = \
                    sv["prefix_cache_hit_ratio"]
            if "prefill_dispatches" in sv:
                record[f"serve_ms4{tag}_prefill_dispatches"] = \
                    sv["prefill_dispatches"]
        except Exception as e:
            sys.stderr.write(f"serve ms4{tag} leg failed: {e}\n")

    # Stall-free admission A/B (ISSUE 5): identical STAGGERED
    # multi-session traffic (rows finish at different boundaries, so
    # admissions land while others decode), budget on vs wave-only. The
    # acceptance numbers: admission-stall p50 drops >= 50% at
    # equal-or-better aggregate tok/s, and zero zero-token boundaries
    # while lanes were in flight.
    for tag, extra in (
        ("_budget", ["--serve_prefill_budget", "128"]),
        ("_waveonly", ["--serve_prefill_budget", "0"]),
    ):
        try:
            sv = _leg(ms_base + ["--serve_chunk", "32",
                                 "--serve_stagger", "1"] + extra)
            record[f"serve_ms4{tag}_tok_s"] = sv["value"]
            record[f"serve_ms4{tag}_ttft_p50_s"] = sv["ttft_p50_s"]
            record[f"serve_ms4{tag}_admission_stall_s"] = \
                sv["admission_stall_s"]
            if "admission_p50_s" in sv:
                record[f"serve_ms4{tag}_admission_p50_s"] = \
                    sv["admission_p50_s"]
            record[f"serve_ms4{tag}_mixed_boundaries"] = \
                sv["mixed_boundaries"]
            record[f"serve_ms4{tag}_zero_token_boundaries"] = \
                sv["mixed_zero_token_boundaries"]
        except Exception as e:
            sys.stderr.write(f"serve ms4{tag} leg failed: {e}\n")

    # Trace-driven workload replay (ISSUE 6): SLO-attainment goodput
    # under bursty arrivals — and the PR 5 stall-free-admission win
    # re-confirmed under that traffic: budget-on vs wave-only on the
    # IDENTICAL seeded trace (scripts/compare_bench.py is the gate that
    # diffs these records across rounds instead of eyeballing).
    wl_base = ["--mode", "workload", "--preset", args.preset,
               "--quant", args.quant, "--serve_batch", "4",
               "--serve_chunk", "32", "--warmup", "1",
               "--workload_requests", "32",
               "--workload_arrival", "gamma",
               "--workload_mults", "1.0,2.0"]
    for tag, extra in (
        ("_budget", ["--serve_prefill_budget", "128"]),
        ("_waveonly", ["--serve_prefill_budget", "0"]),
    ):
        try:
            sv = _leg(wl_base + extra)
            record[f"workload{tag}_goodput_rps"] = sv["value"]
            legs = sv.get("sweep") or [{}]
            record[f"workload{tag}_slo_met_ratio"] = \
                legs[0].get("slo_met_ratio")
            record[f"workload{tag}_tok_s"] = legs[0].get("tok_s")
            inter = legs[0].get("classes", {}).get("interactive", {})
            record[f"workload{tag}_ttft_p99_s"] = inter.get("ttft_p99_s")
            if sv.get("ab"):
                record[f"workload{tag}_slo_overhead_frac"] = \
                    sv["ab"]["overhead_frac"]
                record[f"workload{tag}_chains_identical"] = \
                    sv["ab"]["chains_identical"]
        except Exception as e:
            sys.stderr.write(f"workload{tag} leg failed: {e}\n")

    # Fleet serving (ISSUE 7): the same bursty trace through 2 replicas
    # behind the prefix-affinity router — aggregate goodput plus the
    # router-tier counters (shed/failovers) land in the round record.
    try:
        sv = _leg(wl_base + ["--fleet", "2",
                             "--serve_prefill_budget", "128"])
        record["workload_fleet2_goodput_rps"] = sv["value"]
        legs = sv.get("sweep") or [{}]
        record["workload_fleet2_slo_met_ratio"] = \
            legs[0].get("slo_met_ratio")
        record["workload_fleet2_tok_s"] = legs[0].get("tok_s")
        record["workload_fleet2_shed_total"] = legs[0].get("shed_total")
        record["workload_fleet2_failovers"] = legs[0].get("failovers")
    except Exception as e:
        sys.stderr.write(f"workload fleet leg failed: {e}\n")

    print(json.dumps(record))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--mode", default="all",
                   choices=["all", "decode", "train", "train_sweep",
                            "warm_probe", "spec", "serve", "stream",
                            "workload", "workload_spec", "workload_oom",
                            "workload_disagg"])
    # -- pool-oversubscription preemption A/B (ISSUE 16) --
    p.add_argument("--oom_oversub", default="2,3,4",
                   help="mode=workload_oom: pool-undersizing factors — "
                        "each point shrinks the paged block pool to "
                        "1/x of the trace's dense-equivalent capacity "
                        "and replays defer-only vs preempt+spill arms")
    p.add_argument("--oom_spill_mb", type=int, default=256,
                   help="mode=workload_oom: host-RAM spill budget for "
                        "the preemption arm")
    p.add_argument("--oom_rate_mult", type=float, default=4.0,
                   help="mode=workload_oom: offered-load multiplier for "
                        "every oversubscription point (the pool, not "
                        "the arrival rate, is the swept axis)")
    # -- trace-driven workload replay (ISSUE 6) --
    p.add_argument("--workload_requests", type=int, default=32,
                   help="mode=workload: requests in the generated trace")
    p.add_argument("--workload_rate", type=float, default=4.0,
                   help="mode=workload: mean offered arrival rate (req/s) "
                        "at rate_mult 1.0")
    p.add_argument("--workload_arrival", default="gamma",
                   choices=["poisson", "gamma", "onoff"],
                   help="mode=workload: arrival process (gamma shape<1 "
                        "and onoff are the bursty shapes)")
    p.add_argument("--workload_seed", type=int, default=0,
                   help="mode=workload: trace seed (same seed = "
                        "byte-identical JSONL trace)")
    p.add_argument("--workload_sessions", type=int, default=4,
                   help="mode=workload: persistent chat/stream sessions")
    p.add_argument("--workload_mults", default="1.0,2.0,4.0",
                   help="mode=workload: offered-load multipliers for the "
                        "goodput-vs-load sweep (comma-separated)")
    p.add_argument("--workload_output_min", type=int, default=4,
                   help="mode=workload: output-length cap floor "
                        "(lognormal tail is clipped to [min, max])")
    p.add_argument("--workload_output_max", type=int, default=32,
                   help="mode=workload: output-length cap ceiling")
    p.add_argument("--workload_trace", default=None,
                   help="mode=workload: replay this saved JSONL trace "
                        "instead of generating one")
    p.add_argument("--workload_save", default=None,
                   help="mode=workload: save the generated trace as JSONL "
                        "(byte-for-byte replayable)")
    p.add_argument("--workload_ab_reps", type=int, default=2,
                   help="mode=workload: interleaved telemetry+SLO armed "
                        "vs disarmed A/B repetitions (0 = skip)")
    p.add_argument("--workload_out", default=None,
                   help="mode=workload: also write the record as a "
                        "pretty-printed WORKLOAD_r0N.json artifact")
    p.add_argument("--proc_fleet", type=int, default=0,
                   help="workload mode: replay through N worker "
                        "PROCESSES behind the RPC coordinator "
                        "(ISSUE 11; tiny preset only — workers load "
                        "tiny-random themselves). Produces the "
                        "workload_procfleet_* record")
    p.add_argument("--fleet", type=int, default=0,
                   help="mode=workload: replay through N ServingEngine "
                        "replicas behind the prefix-affinity router "
                        "(ISSUE 7); 0/1 = the single-batcher replay")
    p.add_argument("--fleet_shed_goodput", type=float, default=0.5,
                   help="fleet leg: shed batch-class requests while the "
                        "aggregate windowed goodput ratio is below this "
                        "(0 disarms)")
    p.add_argument("--fleet_shed_queue", type=int, default=0,
                   help="fleet leg: shed batch-class requests while the "
                        "aggregate queue depth is at/above this "
                        "(0 disarms)")
    p.add_argument("--slo_ttft_s", type=float, default=1.0,
                   help="interactive-class TTFT target (0 disarms)")
    p.add_argument("--slo_itl_s", type=float, default=0.25,
                   help="interactive-class mean inter-token-gap target "
                        "(0 disarms)")
    p.add_argument("--slo_latency_s", type=float, default=30.0,
                   help="batch-class end-to-end latency target "
                        "(0 disarms)")
    p.add_argument("--stream_window_ms", type=float, default=50.0,
                   help="mode=stream: event window length")
    p.add_argument("--stream_windows", type=int, default=5,
                   help="mode=stream: windows to measure (medians)")
    p.add_argument("--spec_window", type=int, default=8,
                   help="speculative verify window (mode=spec)")
    p.add_argument("--serve_requests", type=int, default=8,
                   help="requests for mode=serve")
    p.add_argument("--serve_batch", type=int, default=4,
                   help="max_batch (resident decode rows) for mode=serve; "
                        "1 measures the sequential-serving baseline")
    p.add_argument("--serve_chunk", type=int, default=128,
                   help="decode segment length for mode=serve")
    p.add_argument("--serve_spec", type=int, default=0,
                   help="speculative window for mode=serve (0 = plain)")
    p.add_argument("--serve_spec_buckets", default="",
                   help="adaptive speculation buckets for mode=serve/"
                        "workload/workload_spec (ISSUE 13), e.g. "
                        "'0,2,4,8'; empty = fixed --serve_spec")
    p.add_argument("--spec_ab_fixed_k", type=int, default=8,
                   help="mode=workload_spec: the fixed window the "
                        "adaptive arm is judged against (the adversarial "
                        "leg must strictly beat it)")
    p.add_argument("--serve_prefill_chunk", type=int, default=0,
                   help="decode-interleaved admission prefill chunk for "
                        "mode=serve (0 = one-shot prefill)")
    p.add_argument("--serve_prefill_budget", type=int, default=0,
                   help="mode=serve: stall-free admission (ISSUE 5) — "
                        "prompt tokens folded into each decode dispatch "
                        "as piggyback lanes (0 = off: exclusive "
                        "wave/suffix admission, the A/B baseline)")
    p.add_argument("--serve_stagger", type=int, default=0,
                   help="mode=serve: 1 = vary per-request budgets so "
                        "rows finish at different boundaries (admissions "
                        "then land while others decode — the stall-free "
                        "admission A/B traffic shape)")
    p.add_argument("--serve_first_chunk", type=int, default=None,
                   help="TTFT-ramp segment length while a fresh admission "
                        "owes its first token (0 = off; unset = off for "
                        "mode=serve, 16 for the batch-4 leg of mode=all)")
    p.add_argument("--serve_prefix", type=int, default=0,
                   help="mode=serve: 1 = set a shared system+event prefix "
                        "(set_prefix) so admissions prefill only the query "
                        "tail")
    p.add_argument("--serve_sessions", type=int, default=0,
                   help="mode=serve: number of DISTINCT event streams the "
                        "requests round-robin over (0 = single stream); "
                        "the prefix-KV cache's multi-session traffic shape")
    p.add_argument("--serve_prefix_cache", type=int, default=1,
                   help="mode=serve: 1 (default) = prefix-KV cache armed "
                        "(auto insert-on-prefill + longest-prefix match); "
                        "0 = disabled, for cache A/B runs")
    p.add_argument("--serve_cache_insert", type=int, default=1,
                   help="mode=serve: 0 disables insert-on-prefill (cache "
                        "holds only operator-set entries — the r5 single-"
                        "slot behavior, for regression comparison)")
    p.add_argument("--serve_telemetry", type=int, default=1,
                   help="mode=serve: 1 (default) = metrics registry armed "
                        "(TTFT/ITL distributions recorded in the BENCH "
                        "json); 0 = disarmed, for overhead A/B runs")
    p.add_argument("--serve_pipeline", type=int, default=1,
                   help="mode=serve: 1 (default) = pipelined scheduler "
                        "(segment N+1 dispatched from device-resident "
                        "state while the host harvests N); 0 = the "
                        "synchronous escape hatch, for A/B runs")
    p.add_argument("--serve_kv_layout", default="dense",
                   choices=["dense", "paged"],
                   help="mode=serve/workload: resident KV layout "
                        "(ISSUE 12). 'paged' = SEQ_BUCKET block pool + "
                        "per-row block tables, admission gated by free "
                        "blocks; records carry kv_layout so "
                        "compare_bench pairs layouts honestly")
    p.add_argument("--serve_kv_pool_blocks", type=int, default=0,
                   help="paged pool size in blocks incl. scratch "
                        "(0 = dense-equivalent capacity)")
    p.add_argument("--preset", default="auto", choices=["auto", "7b", "13b", "tiny"])
    # Reference run shape: inference.py:19 max_new_tokens=512.
    p.add_argument("--decode_tokens", type=int, default=512)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--quant", default="int8", choices=["int8", "int4", "bf16"])
    p.add_argument("--fuse", action=argparse.BooleanOptionalAction, default=False,
                   help="fuse qkv / gate-up projections before quantization")
    p.add_argument("--kv", default="bf16", choices=["bf16", "int8"],
                   help="decode KV cache storage")
    p.add_argument("--sweep", action="store_true")
    p.add_argument("--seq", type=int, default=704)
    p.add_argument("--steps", type=int, default=4)
    p.add_argument("--lora_r", type=int, default=16)
    p.add_argument("--remat_policy", default="full",
                   choices=["full", "nothing_saveable", "dots_saveable",
                            "dots_with_no_batch_dims_saveable"],
                   help="jax.checkpoint policy for mode=train (ISSUE 13 "
                        "satellite): what the backward pass may SAVE "
                        "instead of recomputing (full = save nothing)")
    p.add_argument("--remat", default="default", choices=["default", "on", "off"],
                   help="override cfg.llama.remat for mode=train (default: "
                        "the config's value, True at 7B)")
    p.add_argument("--warmup", type=int, default=0,
                   help="mode=serve: precompile every executable via "
                        "ContinuousBatcher.warmup() before serving")
    args = p.parse_args()

    if args.mode == "all":
        # No cache/backend init here: the orchestrator does no compute, and
        # holding a live TPU client would undercut the per-leg HBM isolation
        # (each leg enables the cache itself).
        run_all(args)
        return
    if args.mode == "train_sweep":
        run_train_sweep(args)  # subprocess orchestrator, like run_all
        return

    from eventgpt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.mode == "decode":
        run_decode(args)
    elif args.mode == "warm_probe":
        run_warm_probe(args)
    elif args.mode == "spec":
        run_spec(args)
    elif args.mode == "serve":
        run_serve(args)
    elif args.mode == "workload":
        run_workload(args)
    elif args.mode == "workload_spec":
        run_workload_spec(args)
    elif args.mode == "workload_oom":
        run_workload_oom(args)
    elif args.mode == "workload_disagg":
        run_workload_disagg(args)
    elif args.mode == "stream":
        run_stream(args)
    else:
        run_train(args)


if __name__ == "__main__":
    main()
