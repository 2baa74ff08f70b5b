#!/usr/bin/env python3
"""One run of one cell, in one process that owns the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the HTTP server the way ``python -m eventgpt_tpu.cli.serve`` does
(``build_parser`` / ``build_server``; ``cli.infer.load_model`` is the one
name replaced, ``benchmark/loader.py``), warms it, makes the scheduler meet
every admission shape once (``benchmark/prime.py``), replays a short prelude
of the cell's own traffic over HTTP until a pass compiles nothing, drives
the seeded traffic (from ``lead_s`` before the window, so that the window
opens on the state of a long run) for ``--seconds``, waits for what is in
flight, reads the flight recorder, frees the server, checks a sample of the
served answers against the plain reference, and prints one JSON object as
its last line of standard output.

It never falls back: without a TPU (or with fewer chips than the cell asks)
it exits 2 with nothing on standard output. ``--rehearsal`` runs the same
control flow at toy widths on ``JAX_PLATFORMS=cpu``, says ``rehearsal`` in
every line, reports no metric and ``"correct": false`` whatever it saw: it
checks this file, never the chip.
"""

from __future__ import annotations

import os
import sys
import time

_T_IMPORT = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# This checkout's own files first, whatever PYTHONPATH holds.
sys.path[:] = [ROOT] + [p for p in sys.path if p != ROOT]

import argparse  # noqa: E402
import gc  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import contextlib  # noqa: E402
import shutil  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")


def process_start() -> float:
    """perf_counter at which this process started (interpreter start-up
    included), from /proc; the import of this file where /proc has none."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        age = up - ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= age < 3600.0:
            return time.perf_counter() - age
    except (OSError, ValueError, IndexError):
        pass
    return _T_IMPORT


def log(say: str, msg: str) -> None:
    print(f"[bench] {say}{msg}", flush=True)


class CompileCounter:
    """XLA compilations and cache loads, counted as JAX reports them."""

    def __init__(self):
        import jax

        self.n = 0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def since(self, n0: int) -> str:
        """The programs compiled or loaded after the first ``n0``, counted
        by name."""
        seen = {}
        for name in self.names[n0:]:
            seen[name] = seen.get(name, 0) + 1
        return ", ".join(f"{k} x{v}" for k, v in sorted(seen.items()))


def merged(params: dict, rehearsal: bool) -> dict:
    """A mix's parameters; the rehearsal's overrides laid over them."""
    out = {k: v for k, v in params.items() if k != "rehearsal"}
    if rehearsal:
        for k, v in params.get("rehearsal", {}).items():
            out[k] = {**out[k], **v} if isinstance(v, dict) and isinstance(
                out.get(k), dict) else v
    return out


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} {name!r} in BENCHMARK.json "
                     f"(have: {', '.join(e['name'] for e in entries)})")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy widths on JAX_PLATFORMS=cpu: control flow only")
    ap.add_argument("--control", default="", choices=("", "int4"),
                    help="put the reference in this lower precision in the "
                         "program's place: its tokens, at the served "
                         "positions, go through the checks, and the run has "
                         "to come out as not correct")
    return ap


def set_up(opts, t_start: float):
    """Everything before the traffic: the cell's files, the chip, the server
    built and warmed, the admission shapes primed, the prelude passed.
    Returns what the window needs, or the exit code where there is no
    chip. ``benchmark/sweep.py`` starts from here too."""
    from benchmark import loader

    bench = loader.read_benchmark()
    cell = find(bench["workloads"], opts.workload, "workload")
    cfg_entry = find(bench["configs"], cell["config"], "configuration")
    params_path = os.path.join(HERE, "workloads", cell["name"] + ".json")
    if not os.path.exists(params_path):
        raise SystemExit(f"benchmark: cell {cell['name']!r} has no {params_path}")
    params = merged(loader.read_json(params_path), opts.rehearsal)
    say = ("REHEARSAL (cpu, toy widths), not a chip result: "
           if opts.rehearsal else "")

    if opts.rehearsal:
        if os.environ.get("JAX_PLATFORMS", "") != "cpu":
            sys.stderr.write("benchmark: --rehearsal runs on JAX_PLATFORMS=cpu "
                             "only, and is never chosen for you\n")
            return 2
    elif not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # One fixed directory inside the checkout, for the harness's own
        # programs and (JAX's variable being set) the server's alike.
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".xla_cache")

    import jax

    devices = jax.devices()
    want = "cpu" if opts.rehearsal else "tpu"
    chips = int(cell["chips"])
    if devices[0].platform != want or (len(devices) < chips
                                       and not opts.rehearsal):
        sys.stderr.write(
            f"benchmark: cell {cell['name']} needs {chips} {want} device(s); "
            f"JAX found {len(devices)} x {devices[0].platform!r} "
            f"({devices[0].device_kind}). No fallback, no result.\n")
        return 2
    # The program, before a word goes to standard output: alone in a
    # directory this import fails and nothing is printed.
    from eventgpt_tpu.cli import serve as serve_cli

    from benchmark import client, traffic

    if not opts.rehearsal:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    peaks_all = loader.read_json(os.path.join(HERE, "peaks.json"))
    if not opts.rehearsal and device["kind"] not in peaks_all:
        sys.stderr.write(f"benchmark: device kind {device['kind']!r} is not "
                         f"in benchmark/peaks.json\n")
        return 2
    log(say, f"cell {cell['name']} seed {opts.seed} seconds {opts.seconds} "
             f"trace {opts.trace} device {device} jax {jax.__version__}")

    compiles = CompileCounter()
    config_name = "rehearsal-tiny" if opts.rehearsal else cfg_entry["name"]
    hf = loader.read_json(loader.config_file(config_name, bench))
    seam = loader.Seam(opts.seed, opts.rehearsal)
    seam.install()
    profile_dir = None
    if opts.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR, exist_ok=True)
        profile_dir = TRACE_DIR
    argv = loader.server_argv(config_name, hf, params, opts.rehearsal,
                              profile_dir)
    args = serve_cli.build_parser().parse_args(argv)
    captured = io.StringIO()
    t_build = time.perf_counter()
    with contextlib.redirect_stdout(captured):
        httpd, engine = serve_cli.build_server(args)
    seam.uninstall()
    for line in captured.getvalue().splitlines():
        log(say, f"server: {line.strip()}")
    log(say, f"server built and warmed in {time.perf_counter() - t_build:.1f}s; "
             f"{compiles.n} programs compiled or loaded so far, "
             f"{time.perf_counter() - t_start:.1f}s since start")
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    host, port = httpd.server_address[:2]
    pool = traffic.stream_pool(params, opts.seed)
    driver = client.Driver(host, port, pool)
    b = types.SimpleNamespace(
        bench=bench, cell=cell, params=params, say=say, devices=devices,
        device=device, chips=chips, peaks=peaks_all.get(device["kind"], {}),
        compiles=compiles, hf=hf, seam=seam, args=args, httpd=httpd,
        engine=engine, server=server, pool=pool, driver=driver)
    try:
        _prelude(b, opts, t_start)
    except BaseException:
        shut_down(b)
        raise
    return b


def _prelude(b, opts, t_start: float) -> None:
    """Admission waves of every size, then passes of the mix's own requests
    over HTTP until one compiles nothing."""
    import base64

    from benchmark import client, prime, reference, traffic

    pre = b.params["prelude"]
    bursts = pre["bursts"]
    if pre.get("waves"):
        pixels = reference.pixels_from_npy(
            base64.b64decode(b.pool[0]),
            reference.widths_of(b.hf)["image_size"])
        if not prime.waves(b.engine, pixels, traffic.questions(b.params)[0],
                           pre["waves"], lambda: b.compiles.n,
                           lambda msg: log(b.say, msg)):
            bursts = pre["waves"]["sizes"]
    for i in range(int(pre.get("passes", 2))):
        before = b.compiles.n
        sched = traffic.burst_schedule({**b.params, "prelude": {
            **pre, "bursts": bursts}}, opts.seed + 7919 * (i + 1))
        span = sched.requests[-1].due_s + 1.0
        left = client.join_all(b.driver.run_open(
            sched, time.perf_counter() + 0.05, span), 90.0)
        log(b.say, f"prelude pass {i + 1}: {len(sched.requests)} requests in "
                   f"bursts, {b.compiles.n - before} programs compiled or "
                   f"loaded ({b.compiles.since(before)[:300]}), {left} "
                   f"unanswered, {time.perf_counter() - t_start:.1f}s "
                   f"since start")
        if b.compiles.n == before:
            break


def shut_down(b) -> None:
    b.httpd.shutdown()
    b.engine.shutdown()
    b.httpd.server_close()


def run(opts) -> int:
    t_start = process_start()
    b = set_up(opts, t_start)
    if isinstance(b, int):
        return b
    from eventgpt_tpu.obs import trace as obs_trace

    from benchmark import client, correct, measure, traffic

    cell, params, say, engine, driver = b.cell, b.params, b.say, b.engine, b.driver
    compiles = b.compiles
    # The mix starts ``lead_s`` (whole blocks of the schedule) before the
    # window, so that the window sees the rows and the queue it would see in
    # a long run; the lead is set-up.
    lead_s = float(params.get("lead_s", 0.0))
    sched = traffic.build_schedule(params, opts.seed, lead_s + opts.seconds)
    trace_s = float(params.get("trace_s", 5.0)) if opts.trace else 0.0
    try:
        t_sched = time.perf_counter() + 0.05
        t0 = t_sched + lead_s
        t1 = t0 + opts.seconds
        setup_s = t0 - t_start
        marks = {}
        opener = threading.Timer(max(0.0, t0 - time.perf_counter()),
                                 lambda: marks.update(compiles=compiles.n))
        opener.start()
        tracer = None
        if opts.trace:
            tracer = threading.Thread(
                target=_trace_window, daemon=True,
                args=(t0 + max(0.0, (opts.seconds - trace_s) / 2), trace_s))
            tracer.start()
        threads = driver.run_open(sched, t_sched, lead_s + opts.seconds)
        delay = t1 - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        opener.join()
        compiles_in_window = compiles.n - marks["compiles"]
        if compiles_in_window:
            log(say, f"compiled or loaded inside the window: "
                     f"{compiles.since(marks['compiles'])}")
        left = client.join_all(threads, 60.0)
        if tracer is not None:
            tracer.join(120.0)
        log(say, f"window closed; {left} requests never answered within a "
                 f"minute of the close")
        # -- what the program recorded ----------------------------------------
        journeys = {}
        for rec in driver.records:
            if rec.rid is not None:
                j = engine.journey(rec.rid)
                if j is not None:
                    journeys[rec.rid] = j
        ring = obs_trace.active().events() if obs_trace.active() else []
        health = engine.stats()
        restarts, faults = engine.n_restarts, engine.n_faults
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in b.devices[:max(b.chips, 1)])
    finally:
        shut_down(b)

    submit_ts = {rid: j["t_submit"] for rid, j in journeys.items()
                 if "t_submit" in j}
    rows = measure.rows_from(driver.records, journeys, submit_ts)
    data = measure.RunData(
        cell=cell, params=params, hf=b.hf, t0=t0, t1=t1, rows=rows, ring=ring,
        compiles_in_window=compiles_in_window, device_kind=b.device["kind"],
        n_chips=b.chips, peaks=b.peaks)
    win = data.window_rows()
    attempted = len(win)
    failed = sum(not r.ok for r in win)
    log(say, f"window: {attempted} requests due, {failed} failed, "
             f"{sum(r.tokens for r in win)} tokens; scheduler restarts "
             f"{restarts}, faults {faults}; active {health.get('active_rows')} "
             f"queued {health.get('queued')}")
    for r in win:
        if not r.ok:
            log(say, f"failed request: status {r.status} rid {r.rid}")
            break
    log(say, "phases ms p50/p90/mean: " + "; ".join(
        f"{name} {measure.percentile(v, 50):.1f}/{measure.percentile(v, 90):.1f}"
        f"/{sum(v) / len(v):.1f}"
        for name, v in measure.phases(win).items() if v))
    # Every token the program says it committed reached its client.
    lost = sum(abs(int(journeys[rec.rid].get("tokens", 0)) - len(rec.text))
               for rec in driver.records
               if rec.status == "ok" and rec.rid in journeys)
    short = sum(rec.status == "ok" and len(rec.text) < rec.req.budget
                for rec in driver.records)
    log(say, f"{short} answers ended before their budget")
    if lost:
        odd =[rec for rec in driver.records
               if rec.status == "ok" and rec.rid in journeys
               and int(journeys[rec.rid].get("tokens", 0)) != len(rec.text)]
        log(say, f"{len(odd)} answers differ from the recorder's count; rid: "
                 f"budget / recorded / streamed / last id streamed: " + "; ".join(
                     f"{rec.rid}: {rec.req.budget} / "
                     f"{journeys[rec.rid].get('tokens')} / {len(rec.text)} / "
                     f"{rec.token_ids[-1] if rec.text else None}"
                     for rec in odd[:24]))

    # -- free the program's state, then the reference -------------------------
    tree, hf_cfg, args = b.seam.tree, b.seam.hf, b.args
    pool, bench, device, chips = b.pool, b.bench, b.device, b.chips
    del b, engine
    gc.collect()
    finished = [{"rid": rec.rid, "tokens": rec.token_ids,
                 "stream": rec.req.stream, "question": rec.req.question}
                for rec in driver.records
                if t0 <= rec.t_due < t1 and rec.status == "ok" and rec.text]
    chk = params["check"]
    sample = correct.choose_sample(finished, int(chk["sample"]), opts.seed)
    import base64

    t_ref = time.perf_counter()
    result = correct.compare(
        tree, hf_cfg, sample, lambda i: base64.b64decode(pool[i]),
        t_pad=int(args.max_len), a_pad=int(params["budget"]["max"]),
        control=opts.control or None)
    ref_s = time.perf_counter() - t_ref
    for who, res in result.items():
        log(say, f"reference over {res['requests']} requests, "
                 f"{res['tokens']} tokens ({who}; the reference took "
                 f"{ref_s:.1f}s in all): "
                 f"widest gap {res['served_gap']:.4f} at {res['worst']}, "
                 f"{res['mismatches']} tokens not the reference's first, "
                 f"mean gap {res['mean_gap']:.5f}")
    # With --control the lower precision stands in the program's place.
    judged = result["control" if opts.control else "served"]
    limit = float(chk["limit_gap"])
    limit_mean = float(chk["limit_mean_gap"])
    min_tokens = int(chk.get("min_tokens", 1))
    checks = {
        "served_gap": {"value": judged["served_gap"], "limit": limit,
                       "ok": judged["served_gap"] <= limit},
        "mean_gap": {"value": judged["mean_gap"], "limit": limit_mean,
                     "ok": judged["mean_gap"] <= limit_mean},
        "tokens_compared": {"value": judged["tokens"], "limit": min_tokens,
                            "ok": judged["tokens"] >= min_tokens},
        "stream_tokens_lost": {"value": lost, "limit": 0, "ok": lost == 0},
        "scheduler_restarts": {"value": restarts, "limit": 0,
                               "ok": restarts == 0},
    }
    checks_passed = all(c["ok"] for c in checks.values())
    is_correct = checks_passed and not opts.rehearsal

    # -- metrics ---------------------------------------------------------------
    if opts.trace:
        from benchmark import trace_reduce

        span = _TRACED.get("stop", 0.0) - _TRACED.get("start", 0.0)
        try:
            data.trace = trace_reduce.reduce_dir(TRACE_DIR, data, window_s=span)
        except ValueError as e:
            if not opts.rehearsal:  # a cpu trace has no device plane
                raise
            log(say, f"trace not reduced: {e}")
        if data.trace is not None:
            t = data.trace
            t["t0"], t["t1"] = _TRACED["start"], _TRACED["stop"]
            log(say, f"trace: window {t['window_s']:.3f}s busy {t['busy_s']:.3f}s "
                     f"({100 * t['busy_s'] / t['window_s']:.1f} %), "
                     f"{len(t['modules'])} programs, {len(t['ops'])} op names")
            for name, m in sorted(t["modules"].items(),
                                  key=lambda kv: -kv[1]["total_s"])[:12]:
                log(say, f"trace program {name}: {m['runs']} runs, "
                         f"{m['total_s']:.3f}s, median {m['median_s'] * 1e3:.3f} ms")
    section = "per_layer" if opts.trace else "end_to_end"
    kind = "layer_metrics" if opts.trace else "end_to_end"
    metrics = {}
    for m in measure.metrics_for(bench, cell["name"], section):
        if m["name"] == "setup_s":
            value = setup_s
        else:
            value = measure.load_reader(kind, m["name"]).read(data)
        if value is None:
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if opts.trace:
        # Every run reports its set-up; the traced run's is not judged.
        log(say, f"setup_s {setup_s:.3f}")
    dev = {**device, "count": chips if not opts.rehearsal else device["count"],
           "memory_peak_bytes": int(peak)}
    out = {"correct": bool(is_correct), "attempted": attempted,
           "failed": failed}
    if opts.rehearsal:
        out["rehearsal"] = ("toy widths on the cpu; says nothing about the "
                            "chip, reports no metric and is never correct")
        out["metrics"] = {}
        out["rehearsal_saw"] = {k: v["value"] for k, v in metrics.items()}
        out["rehearsal_checks_passed"] = checks_passed
    else:
        out["metrics"] = metrics
    if opts.trace and data.trace is not None:
        dev["busy_s"] = data.trace["busy_s"]
        dev["window_s"] = data.trace["window_s"]
        out["breakdown"] = data.trace["breakdown"]
    out["device"] = dev
    if opts.control:
        out["control"] = opts.control
        out["served_reading"] = {k: result["served"][k]
                                 for k in ("served_gap", "mean_gap", "tokens")}
    out["check"] = {k: {"value": v["value"], "limit": v["limit"]}
                    for k, v in checks.items()}
    for name, c in checks.items():
        sys.stderr.write(f"check {name}: {c['value']} limit {c['limit']} "
                         f"{'ok' if c['ok'] else 'NOT OK'}\n")
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


_TRACED = {}


def _trace_window(t_begin: float, seconds: float) -> None:
    from eventgpt_tpu.obs import profiling as obs_profiling

    delay = t_begin - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    obs_profiling.start_trace(TRACE_DIR)
    _TRACED["start"] = time.perf_counter()
    time.sleep(seconds)
    _TRACED["stop"] = time.perf_counter()
    obs_profiling.stop_trace()


def main(argv=None) -> int:
    opts = build_parser().parse_args(argv)
    return run(opts)


if __name__ == "__main__":
    sys.exit(main())
