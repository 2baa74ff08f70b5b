"""Ring-buffered request/step tracing in Chrome trace-event format.

``span(name, cat, **args)`` is the one probe the serving and training
paths mark an interval with. It records one host-observed interval
(``perf_counter`` at enter/exit) in a ring that keeps the newest
``capacity`` events, so a long-lived server holds a bounded,
always-current window that ``GET /trace`` snapshots on demand and
``--trace_out`` dumps at shutdown. While the profiler is armed
(``obs.profiling.armed()``: ``--profile_dir`` or a ``POST /profile``
window) the same span also holds a ``jax.profiler.TraceAnnotation``
named ``<cat>.<name>`` over the same interval, so every program span of
a captured window is a host event of the ``.xplane.pb``, on the clock of
the device's operations. Events follow the Chrome trace-event format, so
a capture loads directly in Perfetto (ui.perfetto.dev) or
chrome://tracing:

  * ``X`` complete events: the spans of ``SPANS`` below (one row a span:
    name, category, layer, thread, what it brackets). ``args.parent`` is
    the name of the span open on the same thread when this one began
    (absent at top level); ``args.rid`` / ``args.rids`` name the request
    or requests the interval worked for. ``Span.set(**args)`` completes
    a span's args at any time (a handler's spans open before the request
    has an id);
  * ``b``/``e`` async events keyed by request id: each request's
    lifecycle (``queued`` -> ``active`` -> end with a ``status`` arg),
    which is how a single request's timeline reads across overlapping
    scheduler spans;
  * ``i`` instants: point happenings (faults, breaker trips).
    ``configure()`` writes ``ring_start`` as a fresh ring's first event: a
    reader that finds it knows that nothing since the arming (set-up
    included) was overwritten.

Compile spans. ``configure()`` also registers, once a process, a
``jax.monitoring`` time-span listener and event listener. JAX reports each
trace, lowering and XLA compile (or persistent-cache load) after the fact,
with wall-clock ends, so these three rows of ``SPANS`` are not ``with``
blocks: the listener writes each interval as an ``X`` event, its ends put
on ``perf_counter`` by an offset taken at ``configure()`` (the clock of
every other span). ``args.fun`` is JAX's ``fun_name``, ``args.parent`` the
span open on the compiling thread when the interval ends (the set-up phase
or admission stage that asked for the program) and ``compile.xla``'s
``args.cache`` is ``hit`` or ``miss`` from the persistent cache's events on
the same thread inside the interval, ``off`` where neither fired. Each
``compile.xla`` also counts into ``egpt_compiles_total{cache}`` and
``egpt_compile_seconds_total``. Disarmed, both callbacks are the one
``_tracer is None`` check.

Disarmed (the default) every probe is one module-global ``is None``
check, the ``faults.py`` discipline; no timestamps are read and no
objects allocated, so the hot path pays nothing. Armed, a span is two
``perf_counter`` calls plus one dict append under a lock (and one
``TraceAnnotation`` while the profiler is armed; none is made
otherwise). Tracing reads clocks only, never jax values, so chains are
byte-identical armed or disarmed
(tests/test_obs.py::test_chain_neutrality).

File format (``write()``): the Chrome JSON Array Format, one event per
line: a ``[`` line, then ``{event},`` lines. The spec makes the
closing ``]`` optional precisely so producers can append and crash
safely; Perfetto and chrome://tracing both load it. ``load_trace()``
reads it back (round-trip tested).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

from eventgpt_tpu.obs import metrics as obs_metrics
from eventgpt_tpu.obs import profiling as obs_profiling

_US = 1e6


class SpanDef(NamedTuple):
    name: str
    cat: str
    layer: str     # PERF.md's list of layers
    thread: str
    brackets: str
    listener: bool = False   # written by the compile listener, not a probe


_FRONT = "HTTP front end and engine thread"
_SCHED = "scheduler"
_STEPS = "model steps"
_SETUP = "set-up (cli/serve.py build_engine; the scheduler's first admissions)"
_COMPILING = "the compiling thread"

# Every interval the program marks, by the one probe below. The documents
# (OBSERVABILITY.md "Tracing", PERF.md section 3) list these rows and
# tests/test_spans.py holds both and every call site to them.
SPANS = (
    SpanDef("http_read", "http", _FRONT, "handler",
            "do_POST /v1/generate: rfile.read + json.loads (bytes, rid)"),
    SpanDef("host_prep", "http", _FRONT, "handler",
            "_decode_pixels (base64, raster, CLIP preprocess) and "
            "tokenization (rid)"),
    SpanDef("lock_wait", "engine", _FRONT, "handler",
            "submit_ids: from asking for ServingEngine._lock to holding "
            "it (rid)"),
    SpanDef("step", "engine", _FRONT, "engine",
            "one hold of ServingEngine._lock by _loop: batcher.step(), "
            "stream push, harvest, snapshot (queued, live at entry)"),
    SpanDef("idle_wait", "engine", _FRONT, "engine",
            "_wake.wait when there is nothing to do"),
    SpanDef("stream_push", "engine", _FRONT, "engine",
            "_push_stream_deltas_locked + _harvest_locked (finished, rids)"),
    SpanDef("admit", "sched", _SCHED, "engine",
            "ContinuousBatcher._admit and _stage, recorded when they did "
            "admission work (rids, n, path; staged: the members whose "
            "upload, tower and prefill were dispatched while a segment was "
            "in flight, 0 on the drained path; a staged wave has one span "
            "for its staging and one for its landing)"),
    SpanDef("dispatch", "sched", _SCHED, "engine",
            "_dispatch_segment: enqueue one decode / speculation segment "
            "(chunk, live, rows, lanes, rids; of a decoder with window "
            "layers past_window, the live rows at or past the window by the "
            "host's mirror of their lengths; set at its harvest, of a "
            "decoder with sparse experts, by step: experts_touched, "
            "expert_fullest, held_assignments, experts_over_capacity a "
            "layer, routed_tokens)"),
    SpanDef("segment_fetch", "sched", _SCHED, "engine",
            "_harvest_segment: the blocked fetch of a segment's outputs "
            "(wait_s, rids)"),
    SpanDef("harvest", "sched", _SCHED, "engine",
            "_harvest_segment: the host bookkeeping after the fetch "
            "(tokens, rids; the segment's expert counters as on "
            "sched.dispatch)"),
    SpanDef("prefix_lookup", "sched", _SCHED, "engine",
            "_admit: the prefix-KV trie probe (hit, rid)"),
    SpanDef("prefix_copy", "sched", _SCHED, "engine",
            "suffix admission: entry copy + suffix prefill dispatch "
            "(plen, suffix or wave; rid or rids)"),
    SpanDef("upload", "admit", _STEPS, "engine",
            "pixels host to device: jnp.asarray / jnp.stack / "
            "shard_batch_array (bytes, n, rid or rids)"),
    SpanDef("encode", "admit", _STEPS, "engine",
            "encode_events_batch + splice + pad (n, rid or rids)"),
    SpanDef("prefill", "admit", _STEPS, "engine",
            "the _prefill_jit / _prefill_sharded / chunk / suffix call "
            "(n, positions, rid or rids; of a decoder with the blocked flash "
            "kernel, flash_blocks and flash_blocks_interior: the key blocks "
            "it computes a head and those it computes without a mask, over "
            "the wave's layers and rows; set at the logits' readback, the "
            "prefill's expert counters as on sched.dispatch, one step)"),
    SpanDef("scatter", "admit", _STEPS, "engine",
            "_scatter_wave / _finish_admission: the logits readback (NaN "
            "quarantine), prefix insertion, scatter, activation (n, rid or "
            "rids)"),
    SpanDef("build", "setup", _SETUP, "main",
            "cli/serve.py build_engine after the ring is armed: load, "
            "place, batchers, warmup, engines"),
    SpanDef("load", "setup", _SETUP, "main",
            "cli.infer.load_model: the checkpoint (or seeded tree) to host"),
    SpanDef("place", "setup", _SETUP, "main",
            "cli.infer.prepare_model: quantize, fuse, place on the mesh"),
    SpanDef("batcher", "setup", _SETUP, "main",
            "ContinuousBatcher construction: resident caches and buffers"),
    SpanDef("warmup", "setup", _SETUP, "main",
            "the batcher.warmup() calls (programs: the count they return)"),
    SpanDef("jaxpr", "compile", _SETUP, _COMPILING,
            "JAX's jaxpr_trace_duration: tracing a function to a jaxpr "
            "(fun, parent; nested for nested functions)", True),
    SpanDef("lower", "compile", _SETUP, _COMPILING,
            "JAX's jaxpr_to_mlir_module_duration: lowering to MLIR "
            "(fun, parent)", True),
    SpanDef("xla", "compile", _SETUP, _COMPILING,
            "JAX's backend_compile_duration: one XLA compile or persistent-"
            "cache load (fun, parent, cache: hit, miss or off)", True),
)


def span_table_markdown() -> str:
    """``SPANS`` as the table OBSERVABILITY.md "Tracing" holds
    (tests/test_spans.py keeps the two equal)."""
    rows = ["| Span (`cat.name`) | Thread | Layer | Brackets (args) |",
            "| --- | --- | --- | --- |"]
    rows += [f"| `{d.cat}.{d.name}` | {d.thread} | {d.layer} | {d.brackets} |"
             for d in SPANS]
    return "\n".join(rows)


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass

    def drop(self) -> None:
        pass

    def close(self) -> None:
        pass


_NULL = _NullSpan()
_open = threading.local()   # .stack: the spans open on this thread


class Span:
    """One armed interval: an ``X`` event in the ring when it closes and,
    while the profiler is armed, a ``TraceAnnotation`` over the same
    interval."""

    __slots__ = ("_tr", "name", "cat", "args", "_t0", "_ann", "_keep",
                 "_closed")

    def __init__(self, tr: "Tracer", name: str, cat: str, args: dict):
        self._tr = tr
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = 0.0
        self._ann = None
        self._keep = True
        self._closed = False

    def set(self, **args) -> None:
        """Complete the span's args: before it closes, or after (the
        ring's event shares them)."""
        with self._tr._lock:
            self.args.update(args)

    def drop(self) -> None:
        """Leave this interval out of the ring (a probe that found
        nothing to do)."""
        self._keep = False

    def __enter__(self):
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        if stack:
            self.args["parent"] = stack[-1].name
        stack.append(self)
        if obs_profiling.armed():
            import jax

            self._ann = jax.profiler.TraceAnnotation(
                f"{self.cat}.{self.name}", **self.args)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def close(self) -> None:
        """End the interval now; leaving the ``with`` block later adds
        nothing (``with span(...) as wait, lock: wait.close()`` times the
        wait for the lock and not its hold)."""
        if self._closed:
            return
        self._closed = True
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        _open.stack.remove(self)
        if self._keep:
            self._tr.complete(self.name, self._t0, t1, cat=self.cat,
                              args=self.args)

    def __exit__(self, *exc):
        self.close()
        return False


class Tracer:
    """Bounded ring of Chrome trace events. All mutation under one lock
    (scheduler + handler + trainer threads)."""

    def __init__(self, capacity: int = 65536):
        self.capacity = max(int(capacity), 1)
        # time.time() + wall_offset = perf_counter: JAX's compile spans
        # come with wall-clock ends.
        # egpt-check: ignore[tele-clock] -- JAX reports compile intervals on the wall clock; this one read, at arming, is the offset that puts them on perf_counter
        self.wall_offset = time.perf_counter() - time.time()
        self._buf: List[Optional[dict]] = [None] * self.capacity
        self._head = 0   # next write slot
        self._n = 0      # events ever added
        self._lock = threading.Lock()
        self._pid = os.getpid()

    # -- recording --------------------------------------------------------

    def _add(self, ev: dict) -> None:
        with self._lock:
            self._buf[self._head] = ev
            self._head = (self._head + 1) % self.capacity
            self._n += 1

    def complete(self, name: str, t0: float, t1: float, cat: str = "serve",
                 args: Optional[Dict[str, Any]] = None) -> None:
        ev = {"name": name, "ph": "X", "cat": cat,
              "ts": t0 * _US, "dur": max(t1 - t0, 0.0) * _US,
              "pid": self._pid, "tid": threading.get_ident()}
        if args is not None:
            ev["args"] = args  # a Span's own dict: Span.set reaches it
        self._add(ev)

    def instant(self, name: str, cat: str = "serve",
                args: Optional[Dict[str, Any]] = None) -> None:
        ev = {"name": name, "ph": "i", "s": "t", "cat": cat,
              "ts": time.perf_counter() * _US,
              "pid": self._pid, "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._add(ev)

    def async_begin(self, name: str, id: int, cat: str = "request",
                    args: Optional[Dict[str, Any]] = None) -> None:
        ev = {"name": name, "ph": "b", "cat": cat, "id": int(id),
              "ts": time.perf_counter() * _US,
              "pid": self._pid, "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._add(ev)

    def async_end(self, name: str, id: int, cat: str = "request",
                  args: Optional[Dict[str, Any]] = None) -> None:
        ev = {"name": name, "ph": "e", "cat": cat, "id": int(id),
              "ts": time.perf_counter() * _US,
              "pid": self._pid, "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._add(ev)

    # -- export -----------------------------------------------------------

    def events(self) -> List[dict]:
        """Snapshot of the ring, oldest first. Chrome trace viewers sort
        by ts anyway; the order here just keeps dumps readable."""
        with self._lock:
            if self._n < self.capacity:
                out = [e for e in self._buf[: self._head]]
            else:
                out = self._buf[self._head:] + self._buf[: self._head]
            return [{**e, "args": dict(e["args"])} if "args" in e
                    else dict(e) for e in out if e is not None]

    def dropped(self) -> int:
        """Events the ring has overwritten (0 until it wraps)."""
        with self._lock:
            return max(self._n - self.capacity, 0)

    def write(self, path: str) -> int:
        """Dump the ring as a Chrome JSON Array Format file, one event
        per line (the trailing ``]`` is optional per the spec, so the
        file is valid even if a later append crashes). Returns the
        number of events written."""
        evs = self.events()
        with open(path, "w") as f:
            f.write("[\n")
            for ev in evs:
                f.write(json.dumps(ev) + ",\n")
        return len(evs)


def load_trace(path: str) -> List[dict]:
    """Read a ``write()``/Chrome-array trace back into a list of events
    (tolerates the optional trailing ``]`` and per-line commas)."""
    with open(path) as f:
        text = f.read().strip()
    if text.startswith("["):
        text = text[1:]
    if text.endswith("]"):
        text = text[:-1]
    out = []
    for line in text.splitlines():
        line = line.strip().rstrip(",")
        if line:
            out.append(json.loads(line))
    return out


_tracer: Optional[Tracer] = None


def configure(capacity: int = 65536) -> Tracer:
    """Arm tracing with a ring of ``capacity`` events, ``ring_start`` its
    first; returns the tracer. ``capacity <= 0`` disarms."""
    global _tracer
    if capacity <= 0:
        _tracer = None
        return None  # type: ignore[return-value]
    _listen()
    tracer = Tracer(capacity)
    tracer.instant("ring_start", cat="trace")
    _tracer = tracer
    return tracer


def disable() -> None:
    global _tracer
    _tracer = None


def active() -> Optional[Tracer]:
    return _tracer


def enabled() -> bool:
    return _tracer is not None


# -- armed-checked probe helpers (the call-site surface) -------------------
# Each is a single module-global load + None check when disarmed.

def span(name: str, cat: str = "serve", **args):
    t = _tracer
    if t is None:
        return _NULL
    return Span(t, name, cat, args)


def instant(name: str, cat: str = "serve", **args) -> None:
    t = _tracer
    if t is not None:
        t.instant(name, cat=cat, args=args or None)


def async_begin(name: str, id: int, cat: str = "request", **args) -> None:
    t = _tracer
    if t is not None:
        t.async_begin(name, id, cat=cat, args=args or None)


def async_end(name: str, id: int, cat: str = "request", **args) -> None:
    t = _tracer
    if t is not None:
        t.async_end(name, id, cat=cat, args=args or None)


# -- the compile listener (JAX reports the interval after the fact) --------

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "xla",
}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_listening = False


def _listen() -> None:
    """Register the two callbacks with ``jax.monitoring``, once a process
    (JAX keeps a listener for good)."""
    global _listening
    if _listening:
        return
    from jax import monitoring

    monitoring.register_event_time_span_listener(_on_time_span)
    monitoring.register_event_listener(_on_event)
    _listening = True


def _on_event(event: str, **kw) -> None:
    """A persistent-cache verdict, kept on the compiling thread for the
    ``compile.xla`` interval that is still open around it."""
    if _tracer is None:
        return
    verdict = _CACHE_EVENTS.get(event)
    if verdict is not None:
        _open.cache = verdict


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    t = _tracer
    if t is None:
        return
    name = _COMPILE_EVENTS.get(event)
    if name is None:
        return
    args = {"fun": str(kw.get("fun_name", "?"))}
    stack = getattr(_open, "stack", None)
    if stack:
        args["parent"] = stack[-1].name
    if name == "xla":
        # JAX fires the verdict inside compile_or_get_cached, which only
        # runs inside this interval: the one kept is this compile's.
        args["cache"] = getattr(_open, "cache", None) or "off"
        _open.cache = None
        obs_metrics.COMPILES.inc(cache=args["cache"])
        obs_metrics.COMPILE_SECONDS.inc(max(end - start, 0.0))
    t.complete(name, start + t.wall_offset, end + t.wall_offset,
               cat="compile", args=args)
