#!/usr/bin/env python3
"""Does the serving path still start on the chip?

    python3 chip_smoke.py            # on a machine with a TPU; exit 0 = yes

One process, which owns the chip: it builds the HTTP server the way
``python -m eventgpt_tpu.cli.serve`` does (``build_parser`` /
``build_server``) at EventGPT-7B widths — CLIP ViT-L/14-336 tower,
projector, 32-layer / 4096-hidden decoder, int8 weights, flash prefill —
over seeded random weights (``--model_path eventgpt-7b-random``), warms it
up, and answers real ``POST /v1/generate`` requests over the loopback on
an ephemeral port. It passes only if

  * JAX reports a TPU (it refuses to run anywhere else);
  * the Pallas flash kernel agrees with a dense float32 reference on the
    chip, within ``FLASH_TOL``;
  * every request is answered 200 / ``ok`` with the number of tokens it
    asked for, with at least ``MIN_IN_FLIGHT`` in flight at once, one of
    them streamed and one repeated so that the prefix cache is hit;
  * the scheduler never restarted, no row was NaN-quarantined, and the
    answers are not one repeated id;
  * the warmed prefill executable contains a Mosaic custom call: flash
    was compiled for the chip, not interpreted and not replaced.

Every phase runs under a watchdog; a failed check raises, and the exit
code is the result. The last line of standard output is the verdict, one
JSON object with exactly two keys: ``{"ok": true, "device": {"platform",
"kind", "count"}}``, the device as JAX reports it. The line before it
(``[smoke] report {...}``) carries what the run saw: widths, requests,
tokens, the flash error, cache and memory. The wall times in it are smoke
timings of one cold run, not metrics.

``--rehearsal`` is the same script at ``tiny-random`` width on a CPU that
was asked for (``JAX_PLATFORMS=cpu``): it checks this file's own control
flow before chip time is spent, never stands in for the chip, and says
``rehearsal`` in everything it prints. It is never chosen for you.
"""

from __future__ import annotations

import argparse
import base64
import contextlib
import http.client
import importlib.metadata
import io
import json
import os
import re
import sys
import threading
import time
import urllib.request

SEED = 20260926
NEW_TOKENS = 48          # per request (the contract's floor is 32)
MIN_IN_FLIGHT = 4
# Flash vs dense reference, q/k/v ~ N(0, 1) in bf16 at the r05 prompt shape.
# The kernel keeps f32 accumulators but the MXU multiplies in bf16 passes
# and the output is rounded to bf16 (8 mantissa bits, |out| up to ~4 =>
# half an ulp is 8e-3); the rest is the bf16 rounding of the probabilities
# entering the second matmul (2^-9 relative on a sum of |v| ~ 1 terms).
# Measured on the v5e for this PR: see CHANGES.md. A wrong mask, a dropped
# block or a stale scale moves the output by O(1), two orders above this.
FLASH_SHAPE = (1, 640, 32, 128)
FLASH_TOL = {"max_abs": 3e-2, "mean_abs": 3e-3}

# Watchdog limits (seconds): together under the 1200 s the whole run has,
# each several times what a cold run took on the v5e (9 / 53 / 26 / 0.1 s).
PHASE_LIMIT_S = {"flash_check": 120, "build_and_warmup": 600,
                 "serving": 300, "inspect": 120}


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@contextlib.contextmanager
def phase(name: str, timings: dict):
    """Time one phase into ``timings`` under a watchdog. A phase that
    outlives its limit cannot be unwound (a hung compile never returns to
    Python), so the watchdog ends the process; daemon threads die with it."""
    limit_s = PHASE_LIMIT_S[name]

    def _expired():
        sys.stderr.write(f"chip_smoke: phase {name!r} exceeded {limit_s}s\n")
        sys.stderr.flush()
        os._exit(3)

    dog = threading.Timer(limit_s, _expired)
    dog.daemon = True
    dog.start()
    t0 = time.perf_counter()
    print(f"[smoke] {name} ...", flush=True)
    try:
        yield
    finally:
        dog.cancel()
        timings[name] = round(time.perf_counter() - t0, 2)
        print(f"[smoke] {name}: {timings[name]}s", flush=True)


def version(pkg: str) -> str:
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "absent"


# -- requests ----------------------------------------------------------------

def event_stream_b64(seed: int) -> str:
    """A seeded 50 ms event stream, as the base64 ``.npy`` a client uploads
    (``ops/raster.STREAM_DTYPE``: x, y, t in microseconds, polarity)."""
    import numpy as np

    from eventgpt_tpu.ops.raster import events_to_structured_stream

    rng = np.random.default_rng(seed)
    n = 40_000
    ev = events_to_structured_stream({
        "x": rng.integers(0, 640, n), "y": rng.integers(0, 480, n),
        "t": np.sort(rng.integers(0, 50_000, n)),
        "p": rng.integers(0, 2, n)})
    buf = io.BytesIO()
    np.save(buf, ev)
    return base64.b64encode(buf.getvalue()).decode()


def get_json(url: str, timeout: float = 30.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def post_generate(host: str, port: int, payload: dict, timeout: float) -> dict:
    """One ``POST /v1/generate``. Returns {"code", "body"} — for a streamed
    request ``body`` is the terminal event plus the list of all events."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", "/v1/generate", json.dumps(payload).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()  # http.client undoes the chunked framing
    finally:
        conn.close()
    if not payload.get("stream"):
        return {"code": resp.status, "body": json.loads(raw)}
    events = [json.loads(line) for line in raw.splitlines() if line.strip()]
    check(bool(events) and events[-1].get("done") is True,
          f"stream did not end in a done event: {events[-1:]}")
    return {"code": resp.status, "body": {**events[-1], "events": events}}


def committed_tokens(journey: dict) -> int:
    """Tokens the scheduler committed for a request, from its flight-
    recorder timeline (segment events carry their harvest's count)."""
    return sum(int(e.get("tokens", 0)) for e in journey["events"]
               if e["kind"] == "segment")


def check_answer(tag: str, res: dict, journey: dict, asked: int) -> int:
    """One answer against what was asked; returns the tokens committed."""
    body = res["body"]
    check(res["code"] == 200, f"{tag}: HTTP {res['code']}: {body}")
    check(body.get("status") == "ok",
          f"{tag}: terminal status {body.get('status')!r} (a "
          f"nan_quarantined row or a forced finish is a failure)")
    check(journey is not None and journey.get("status") == "ok",
          f"{tag}: flight recorder disagrees: {journey}")
    done = committed_tokens(journey)
    if "tokens" in body:  # not streamed: the response counts its tokens
        n = body["tokens"]
        check(len(body["token_ids"]) == n, f"{tag}: token_ids != tokens")
        # The scheduler strips a trailing EOS from the answer; nothing else
        # may end a request that finished ``ok`` below its budget.
        check(n == asked or (n < asked and done == n + 1),
              f"{tag}: {n} tokens for a budget of {asked} (committed "
              f"{done}): short, and not an EOS stop")
    else:
        # A stream carries no count; ``ok`` below the budget is an EOS stop
        # (every forced finish has another status).
        check(0 < done <= asked,
              f"{tag}: streamed request committed {done} of {asked}")
    return done


def serve_requests(host: str, port: int, rehearsal: bool) -> dict:
    """The traffic: eight requests sent at once over two event streams (one
    of them streamed) into four batch rows, then the first one again for
    the prefix cache."""
    url = f"http://{host}:{port}"
    streams = [event_stream_b64(SEED + 1), event_stream_b64(SEED + 2)]
    questions = ["What is happening in this scene?",
                 "Describe the motion of the objects.",
                 "Is anything moving towards the camera?",
                 "How many objects are visible?",
                 "What changed in the last few milliseconds?",
                 "Is the camera itself moving?",
                 "Which direction is the fastest object heading?",
                 "Is the scene indoors or outdoors?"]
    asked = 8 if rehearsal else NEW_TOKENS
    payloads = [{"query": q, "event_b64": streams[i % 2],
                 "max_new_tokens": asked, "debug": True}
                for i, q in enumerate(questions)]
    payloads[2] = {**payloads[2], "stream": True}
    payloads[2].pop("debug")
    results = [None] * len(payloads)
    errors = []

    def go(i):
        try:
            results[i] = post_generate(host, port, payloads[i], 240.0)
        except BaseException as e:  # re-raised below, on the main thread
            errors.append((i, e))

    threads = [threading.Thread(target=go, args=(i,), daemon=True)
               for i in range(len(payloads))]
    for t in threads:
        t.start()
    # In-flight high-water mark, read the way a load balancer would.
    peak = 0
    while any(t.is_alive() for t in threads):
        h = get_json(url + "/health")
        check(h["status"] == "ok", f"/health degraded mid-run: {h}")
        peak = max(peak, h["active"] + h["queued"])
        time.sleep(0.02)
    for t in threads:
        t.join(timeout=1.0)
    if errors:
        raise errors[0][1]
    check(all(r is not None for r in results), "a request thread died")
    check(peak >= MIN_IN_FLIGHT,
          f"at most {peak} requests were in flight at once; the smoke needs "
          f">= {MIN_IN_FLIGHT} so that admission meets decoding rows")

    # The first request again, alone: its prompt head (system prompt + the
    # event block) is in the prefix cache now.
    hits_before = get_json(url + "/prefix_cache").get("hits", 0)
    repeat = post_generate(host, port, payloads[0], 240.0)
    results.append(repeat)
    pc = get_json(url + "/prefix_cache")
    check(pc.get("hits", 0) > hits_before,
          f"the repeated request did not hit the prefix cache: {pc}")

    all_ids = []
    paths = []
    tokens = 0
    for i, res in enumerate(results):
        rid = res["body"]["rid"]
        journey = get_json(url + f"/request?rid={rid}")
        tokens += check_answer(f"request {i} (rid {rid})", res, journey,
                               asked)
        all_ids += res["body"].get("token_ids", [])
        paths += [e.get("path") for e in journey["events"]
                  if e["kind"] in ("admit", "lane_join")]
    check(len(set(all_ids)) > 1,
          f"every answer token is the id {set(all_ids)}: the model's "
          f"output does not depend on its input")

    health = get_json(url + "/health")
    stats = get_json(url + "/stats")
    check(health["status"] == "ok" and health["restarts"] == 0,
          f"scheduler restarted (a crashed segment looks like a slow one "
          f"otherwise): {health}")
    check(stats["faults"] == 0, f"scheduler faults: {stats['faults']}")
    same = repeat["body"]["token_ids"] == results[0]["body"]["token_ids"]
    # Printed, not asserted: with random weights the arg-max flips on
    # rounding between the full-prefill and the prefix-hit executables.
    print(f"[smoke] repeated request token-identical to its first run: "
          f"{same}", flush=True)
    return {
        "requests_sent": len(results),
        "requests_ok": len(results),
        "tokens": tokens,
        "peak_in_flight": peak,
        "admission_paths": sorted({p for p in paths if p}),
        "prefix_cache_hits": pc.get("hits", 0),
        "repeat_token_identical": same,
        "distinct_token_ids": len(set(all_ids)),
    }


# -- the flash kernel against a reference ------------------------------------

def flash_check(rehearsal: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from eventgpt_tpu.ops.flash_attention import flash_attention

    b, s, h, hd = (1, 256, 4, 64) if rehearsal else FLASH_SHAPE
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q = jax.random.normal(kq, (b, s, h, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, h, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, h, hd), jnp.bfloat16)
    valid = jnp.arange(s)[None, :] < s - 37  # right padding, as in serving

    out = flash_attention(q, k, v, valid=valid, causal=True)

    @jax.jit
    def dense(q, k, v, valid):
        qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
        with jax.default_matmul_precision("highest"):
            sc = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / (hd ** 0.5)
            pos = jnp.arange(s)
            mask = valid[:, None, None, :] & (pos[None, None, None, :]
                                              <= pos[None, None, :, None])
            p = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
        return jnp.where(valid[:, :, None, None], o, 0.0)

    ref = dense(q, k, v, valid)
    err = jnp.abs(out.astype(jnp.float32) - ref)
    got = {"shape": [b, s, h, hd], "max_abs": float(err.max()),
           "mean_abs": float(err.mean()),
           "finite": bool(jnp.isfinite(out.astype(jnp.float32)).all())}
    check(got["finite"], f"flash output is not finite: {got}")
    check(out.shape == q.shape and out.dtype == q.dtype,
          f"flash output {out.shape} {out.dtype}")
    check(got["max_abs"] <= FLASH_TOL["max_abs"]
          and got["mean_abs"] <= FLASH_TOL["mean_abs"],
          f"flash disagrees with the dense reference: {got} vs {FLASH_TOL}")
    return got


# -- main ---------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny width on a CPU that was asked for "
                         "(JAX_PLATFORMS=cpu): checks this script's control "
                         "flow, never stands in for the chip")
    opts = ap.parse_args()
    say = "REHEARSAL (cpu, tiny width) — not a chip result: " \
        if opts.rehearsal else ""

    import jax

    dev = jax.devices()[0]
    want = "cpu" if opts.rehearsal else "tpu"
    if dev.platform != want:
        # Nothing on standard output: no accelerator, no result.
        sys.stderr.write(
            f"chip_smoke: needs a {want} device, JAX found "
            f"{dev.platform!r} ({dev.device_kind}). The smoke never falls "
            f"back; --rehearsal runs on JAX_PLATFORMS=cpu only.\n")
        return 2
    # The program, before a word goes to standard output: a directory that
    # holds this script and nothing else of the repo fails here, silently.
    from eventgpt_tpu import native
    from eventgpt_tpu.cli import serve as serve_cli
    from eventgpt_tpu.models.synthetic import SYNTHETIC_7B

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[smoke] {say}device {device}; jax {jax.__version__}, jaxlib "
          f"{version('jaxlib')}, libtpu {version('libtpu')}, python "
          f"{sys.version.split()[0]}", flush=True)

    timings = {}
    with phase("flash_check", timings):
        flash = flash_check(opts.rehearsal)

    # The server, as main() builds it: the CLI's own parser and defaults
    # (--max_batch 4 --max_len 1024), its own construction.
    model = "tiny-random" if opts.rehearsal else SYNTHETIC_7B
    argv = ["--model_path", model, "--quant", "int8", "--warmup",
            "--host", "127.0.0.1", "--port", "0"]
    args = serve_cli.build_parser().parse_args(argv)
    with phase("build_and_warmup", timings):
        log = io.StringIO()
        with contextlib.redirect_stdout(log):
            httpd, engine = serve_cli.build_server(args)
        sys.stdout.write(log.getvalue())
        warm = re.search(r"warmup: (\d+) executables in ([\d.]+)s",
                         log.getvalue())
        check(warm is not None, "the server did not report its warm-up")
    timings["warmup"] = float(warm.group(2))
    timings["setup"] = round(timings["build_and_warmup"] - timings["warmup"], 2)

    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    host, port = httpd.server_address[:2]
    try:
        with phase("serving", timings):
            served = serve_requests(host, port, opts.rehearsal)
        with phase("inspect", timings):
            batcher = engine.batcher
            cfg = batcher.cfg
            mosaic = None
            if not opts.rehearsal:
                hlo = batcher.prefill_hlo(batcher.max_len)
                mosaic = hlo.count("tpu_custom_call")
                check(cfg.llama.attn_impl == "flash" and mosaic > 0,
                      f"no Mosaic custom call in the prefill executable "
                      f"(attn_impl={cfg.llama.attn_impl!r}): flash was "
                      f"interpreted or replaced")
            cache_dir = jax.config.jax_compilation_cache_dir
            entries = (len(os.listdir(cache_dir))
                       if cache_dir and os.path.isdir(cache_dir) else 0)
            mem = dev.memory_stats() or {}
    finally:
        httpd.shutdown()
        engine.shutdown()
        httpd.server_close()

    report = {
        **({"rehearsal": "passed on the cpu at tiny width; says nothing "
                         "about the chip"} if opts.rehearsal else {}),
        "versions": {"jax": jax.__version__, "jaxlib": version("jaxlib"),
                     "libtpu": version("libtpu")},
        "model": {"path": model, "quant": args.quant,
                  "attn_impl": cfg.llama.attn_impl,
                  "llama": {"layers": cfg.llama.num_layers,
                            "hidden": cfg.llama.hidden_size,
                            "heads": cfg.llama.num_heads,
                            "vocab": cfg.llama.vocab_size},
                  "vision": {"layers": cfg.vision.num_layers,
                             "hidden": cfg.vision.hidden_size,
                             "image": cfg.vision.image_size},
                  "max_batch": args.max_batch, "max_len": args.max_len},
        **served,
        "warmed_executables": int(warm.group(1)),
        "flash_vs_dense": flash,
        "mosaic_custom_calls_in_prefill": mosaic,
        "smoke_timings_s": timings,
        "compile_cache": {"dir": cache_dir, "entries": entries},
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "raster_native": native.available(),
    }
    # Two lines, both JSON: what the run saw, then the verdict. The verdict
    # is the last line and holds exactly ``ok`` and the device as JAX reports
    # it — the shape the driver reads; a rehearsal's verdict is never true.
    print(f"[smoke] {say}report {json.dumps(report)}", flush=True)
    print(json.dumps({"ok": not opts.rehearsal, "device": device}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
