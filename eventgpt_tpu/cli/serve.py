"""Network-facing serving front-end over the continuous batcher.

The reference's LLaVA lineage implies a controller/worker serving stack it
never shipped (the heartbeat vestiges at
``/root/reference/dataset/constants.py:1-4`` — CONTROLLER_HEART_BEAT_
EXPIRATION etc. with no server behind them). This module is that surface,
TPU-first: ONE process owns the chip and the resident decode batch
(``eventgpt_tpu/serve.py``); a stdlib ThreadingHTTPServer front end feeds
it through a thread-safe engine, so concurrency lives in the scheduler's
row-level admission — not in process fan-out. A controller tier is not
re-created: on TPU the accelerator is single-owner, and multi-host
serving scales by sharding the batcher over the mesh
(``--mesh_data/fsdp/model``), not by LLaVA's worker pools.

Endpoints:
  POST /v1/generate  {"query": str,
                      "event_path": .npy path under --event_root |
                      "event_b64": base64 .npy bytes,
                      "max_new_tokens": int = 64,
                      "stream": bool = false}
      -> {"answer": str, "tokens": N, "ttft_s": x, "latency_s": y}
      or (stream) chunked text deltas as they commit, newline-framed JSON.
  GET  /health       -> {"status": "ok", "active": N, "queued": N}
      (lock-free snapshot: answers inside a probe timeout even mid-segment)
  GET  /stats        -> serverwide counters + recent request stats +
      a summary of the telemetry registry (obs/metrics.py).
  GET  /prefix_cache -> prefix-KV cache snapshot (entries, bytes,
      hit/miss/eviction counters); POST /prefix inserts an entry.
  GET  /metrics      -> Prometheus text exposition (scrape target:
      TTFT / inter-token-latency / queue-wait histograms, counters,
      breaker state — the catalogue is in OBSERVABILITY.md).
  GET  /trace        -> Chrome trace JSON of the live span ring
      (request lifecycles + scheduler dispatch/harvest; load in
      Perfetto or chrome://tracing).
  POST /profile      {"seconds": N} -> capture a jax.profiler window of
      live traffic into --profile_dir; returns the trace directory.

``event_path`` is directory-allowlisted: without ``--event_root`` it is
disabled entirely (clients upload streams inline via ``event_b64``), and
with it the resolved path must stay inside the root.

Smoke (tiny random weights):
  python -m eventgpt_tpu.cli.serve --model_path tiny-random --port 8600 \
      --event_root /root/reference/samples &
  curl -s localhost:8600/v1/generate -d '{"query": "What is happening?",
      "event_path": "sample1.npy"}'
"""

from __future__ import annotations

import argparse
import base64
import contextlib
import itertools
import json
import math
import queue
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional

from eventgpt_tpu import faults  # stdlib-only; safe before jax loads
from eventgpt_tpu.obs import journey as obs_journey  # stdlib-only too
from eventgpt_tpu.obs import metrics as obs_metrics
from eventgpt_tpu.obs import series as obs_series  # stdlib-only too
from eventgpt_tpu.obs import trace as obs_trace


class ServingEngine:
    """Thread-safe wrapper around one ``ContinuousBatcher``.

    The batcher itself is single-threaded by design (every method touches
    resident device buffers); the engine serializes access behind one
    lock and runs the scheduler loop on a dedicated thread, parking it
    when no work exists. HTTP handler threads only do host-side prep
    (event file -> pixels, tokenize) and block on per-request events.

    Request-lifecycle hardening: a scheduler-thread exception no longer
    kills the engine for good. The dying thread fails the in-flight rows
    cleanly (their waiters/streams get the fault), keeps queued requests
    for re-admission, and RESTARTS the scheduler thread. A circuit
    breaker counts consecutive faults: at ``breaker_threshold`` it trips
    — queued requests are failed too, ``/health`` flips to ``degraded``
    and submits are refused (503) until ``breaker_cooldown_s`` elapses
    (half-open: traffic is admitted again; the first clean step closes
    the breaker, the next fault re-trips it instantly). ``heartbeat_dir``
    arms the same atomic liveness file the trainer writes
    (``train/resilience.Heartbeat``) so one external watchdog convention
    covers both.

    Lock discipline (egpt_check rule ``lock``): ``_GUARDED_BY`` below is
    the checkable contract. Full-guard attributes are only touched under
    ``_lock`` (or in ``*_locked`` helpers); ``/w`` attributes take the
    lock to WRITE but are read lock-free by design — the snapshot/flag
    pattern that lets ``/health``, ``/stats`` and ``breaker_open()``
    answer inside a probe timeout while the scheduler thread holds the
    lock through a multi-second decode segment (reads of a
    GIL-atomically swapped dict/bool/int are safe; readers tolerate
    one-step staleness). ``_wake``/``_stop``/``_thread`` and the
    scheduler-thread-private fields (``_n_steps``, ``_last_beat``) are
    deliberately undeclared: Event is self-synchronizing and the rest
    are single-thread state.
    """

    _GUARDED_BY = {
        # full guard: multi-step mutations that must be atomic
        "batcher": "_lock",
        "_answers": "_lock",
        "_sent": "_lock",
        "_abandoned": "_lock",
        # writes locked, lock-free reads by design (see docstring)
        "_done": "_lock/w",
        "_status": "_lock/w",
        "_streams": "_lock/w",
        "_dead": "_lock/w",
        "_snapshot": "_lock/w",
        "_consec_faults": "_lock/w",
        "_t_fault": "_lock/w",
        "fault": "_lock/w",
        "n_faults": "_lock/w",
        "n_restarts": "_lock/w",
        "n_requests": "_lock/w",
        # threads asking for _lock in turn (a Condition the scheduler waits on)
        "_asking": "_turn",
    }

    def __init__(self, batcher, tokenizer, conv_mode: str = "eventgpt_v1",
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 5.0,
                 heartbeat_dir: Optional[str] = None,
                 heartbeat_interval_s: float = 1.0,
                 trace_out: Optional[str] = None):
        self.batcher = batcher
        # Chrome-trace dump destination written at shutdown (--trace_out);
        # GET /trace snapshots the live ring any time before that.
        self.trace_out = trace_out
        self.tokenizer = tokenizer
        self.conv_mode = conv_mode
        self._lock = threading.Lock()
        # ``threading.Lock`` is not fair: the scheduler thread, which asks
        # for ``_lock`` again right after releasing it, would get it back
        # before a woken submitter runs, and submitters would get in only
        # when the engine idles. They (and the fleet supervisor's calls:
        # ``_in_turn``) count themselves here, the last one to leave
        # notifies, and the scheduler thread waits for that between two
        # steps (``_let_submitters_in``).
        self._asking = 0
        self._turn = threading.Condition()
        self._wake = threading.Event()
        self._stop = False
        self._done: Dict[int, threading.Event] = {}
        self._answers: Dict[int, list] = {}
        self._status: Dict[int, str] = {}  # terminal status per rid
        self._streams: Dict[int, queue.Queue] = {}
        self._sent: Dict[int, int] = {}
        self._abandoned: set = set()  # timed-out rids: drop at harvest
        self.n_requests = 0
        self.t_start = time.time()
        self.fault: Any = None  # repr of the LAST scheduler fault
        self.n_faults = 0          # total scheduler faults survived
        self.n_restarts = 0        # scheduler-thread restarts
        self._consec_faults = 0    # consecutive (no clean step between)
        self._t_fault = 0.0        # monotonic time of the last fault
        self.breaker_threshold = max(int(breaker_threshold), 1)
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        # Fleet kill state (ISSUE 7): a killed replica parks its
        # scheduler loop and refuses submits until revive() — the
        # supervisor drained its requests for re-admission elsewhere.
        self._dead = False
        self._n_steps = 0
        self._heartbeat = None
        self._hb_interval = float(heartbeat_interval_s)
        self._last_beat = 0.0
        if heartbeat_dir:
            from eventgpt_tpu.train.resilience import Heartbeat

            self._heartbeat = Heartbeat(heartbeat_dir)
        # Lock-free stats snapshot: /health and /stats must answer inside
        # a load balancer's probe timeout even while the scheduler thread
        # holds the lock through a multi-second decode segment. Rebuilt
        # after every step; staleness is bounded by one segment.
        self._snapshot: Dict[str, Any] = self._build_snapshot_locked()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    # -- client side ------------------------------------------------------

    def breaker_open(self) -> bool:
        """True while the circuit breaker refuses new work: the fault
        count hit the threshold and the cooldown has not elapsed. After
        the cooldown the breaker is HALF-OPEN — submits flow again, one
        clean step resets the count, one more fault re-trips."""
        return (self._consec_faults >= self.breaker_threshold
                and time.monotonic() - self._t_fault < self.breaker_cooldown_s)

    def breaker_retry_after_s(self) -> Optional[float]:
        """Derived Retry-After for breaker-open 503s (ISSUE 11
        satellite, the 429 paths' discipline): the REMAINING cooldown
        before the half-open probe admits traffic — the one number the
        engine actually knows about when it will take work again.
        None while the breaker is closed (the caller falls back to the
        goodput-derived hint)."""
        if not self.breaker_open():
            return None
        remaining = (self.breaker_cooldown_s
                     - (time.monotonic() - self._t_fault))
        return max(remaining, 1.0)

    def submit(self, query: str, pixels, max_new_tokens: int,
               stream: bool = False,
               deadline_s: Optional[float] = None,
               slo=None) -> int:
        from eventgpt_tpu.data.conversation import prepare_event_prompt
        from eventgpt_tpu.data.tokenizer import tokenize_with_event

        ids = tokenize_with_event(
            prepare_event_prompt(query, self.conv_mode), self.tokenizer
        )
        return self.submit_ids(ids, pixels, max_new_tokens, stream=stream,
                               deadline_s=deadline_s, slo=slo)

    def submit_ids(self, ids, pixels, max_new_tokens: int,
                   stream: bool = False,
                   deadline_s: Optional[float] = None,
                   slo=None) -> int:
        """``submit`` for a pre-tokenized prompt — the fleet router's
        entry point (it tokenized once already, to compute the request's
        prefix-affinity key)."""
        if self.breaker_open() or self._dead:
            raise RuntimeError(f"serving engine is down: {self.fault}")
        with self._in_turn(), \
                obs_trace.span("lock_wait", "engine") as wait, self._lock:
            wait.close()  # the lock is held: what follows is its hold
            # Re-check under the lock: a breaker trip (or kill) while
            # the caller prepared the request has already swept _done —
            # an event registered after the sweep would burn its
            # caller's full timeout.
            if self.breaker_open() or self._dead:
                raise RuntimeError(f"serving engine is down: {self.fault}")
            rid = self.batcher.submit(ids, pixels, max_new_tokens,
                                      deadline_s=deadline_s, slo=slo)
            self._done[rid] = threading.Event()
            if stream:
                self._streams[rid] = queue.Queue()
                self._sent[rid] = 0
            self.n_requests += 1
        wait.set(rid=rid)
        self._wake.set()
        return rid

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or in-flight request; its waiter is released
        with whatever tokens were committed, under status ``cancelled``.
        False when the rid is unknown or already finished."""
        with self._lock:
            ok = self.batcher.cancel(rid)
            if ok:
                self._harvest_locked()
                self._snapshot = self._build_snapshot_locked()
        if ok:
            self._wake.set()
        return ok

    def set_prefix(self, prefix_prompt: str, pixels=None) -> int:
        """Install a shared-prefix KV seed (``ContinuousBatcher.set_prefix``)
        from raw prompt text (may contain the ``<event>`` placeholder, in
        which case ``pixels`` carries its stream). Matching admissions skip
        the prefix's encode + prefill from then on; non-matching prompts
        fall back to the full path untouched. Returns the prefix length in
        cache positions. Safe on a live engine: the prefix prefill builds
        its own row cache and never touches resident rows."""
        from eventgpt_tpu.data.tokenizer import tokenize_with_event

        ids = tokenize_with_event(prefix_prompt, self.tokenizer)
        with self._lock:
            return self.batcher.set_prefix(ids, pixel_values=pixels)

    def status(self, rid: int) -> str:
        """Terminal status of a finished request ('ok' when it finished
        normally or is unknown/still running)."""
        return self._status.get(rid, "ok")

    def result(self, rid: int, timeout: float = 600.0):
        """Block until the request finishes; returns its token ids."""
        ev = self._done[rid]
        if not ev.wait(timeout):
            with self._lock:
                # The batcher will still finish this request; with its
                # waiter gone the answer would sit in _answers forever
                # (unbounded host growth on a long-lived server). Either
                # take the answer that landed in the race window, or mark
                # the rid for drop-at-harvest like an orphaned stream.
                self._done.pop(rid, None)
                if rid in self._answers:
                    return self._answers.pop(rid)
                self._abandoned.add(rid)
            raise TimeoutError(f"request {rid} did not finish in {timeout}s")
        with self._lock:
            self._done.pop(rid, None)
            if rid not in self._answers:
                raise RuntimeError(
                    f"serving engine is down: "
                    f"{self.fault or self._status.get(rid, 'unknown fault')}")
            return self._answers.pop(rid)

    def try_result(self, rid: int):
        """Non-blocking collection for the fleet supervisor: ``(tokens,
        status)`` once the request is terminal — ``(None,
        "engine_fault")`` when a scheduler fault failed it (the
        supervisor's cue to fail it over) — else ``None`` (still
        running). Consuming: a delivered answer is popped, like
        ``result``."""
        with self._in_turn(), self._lock:
            if rid in self._answers:
                self._done.pop(rid, None)
                return self._answers.pop(rid), self._status.get(rid, "ok")
            if self._status.get(rid) == "engine_fault":
                self._done.pop(rid, None)
                return None, "engine_fault"
        return None

    def try_status(self, rid: int):
        """Terminal status of a STREAMED request once its harvest
        delivered through the stream queue (answers never reach
        ``_answers`` there), else None — the supervisor's stream-side
        counterpart of ``try_result``."""
        with self._in_turn(), self._lock:
            st = self._status.get(rid)
            if st is not None and rid not in self._streams:
                return st
        return None

    def kill(self) -> list:
        """Simulated replica death (the fleet chaos contract): deliver
        anything already finished, then strip EVERY unfinished request
        out of the batcher (``ContinuousBatcher.export_requests``) and
        return the re-admission records — the supervisor re-routes them
        to survivors. The scheduler loop parks and submits are refused
        until ``revive()``. Engine-side waiter state for the exported
        rids is dropped: the fleet owns those clients now."""
        with self._in_turn(), self._lock:
            self._dead = True
            # Finished-but-uncollected answers are real results — hand
            # them to try_result instead of re-running them elsewhere.
            self._push_stream_deltas_locked()
            self._harvest_locked()
            recs = self.batcher.export_requests()
            # export_requests settles the in-flight pipelined segment
            # first (_drain), which can FINISH a request right here —
            # after the harvest above, and out of rows so never
            # exported. Harvest again or the answer strands in
            # batcher.finished (the parked loop will not run again) and
            # the fleet supervisor polls try_result forever.
            self._harvest_locked()
            for rec in recs:
                rid = rec["rid"]
                self._done.pop(rid, None)
                self._streams.pop(rid, None)
                self._sent.pop(rid, None)
                self._abandoned.discard(rid)
            self._snapshot = self._build_snapshot_locked()
        self._wake.set()
        return recs

    def collect_handoffs(self) -> List[Dict[str, Any]]:
        """Drain the prefill-role batcher's handoff outbox (ISSUE 17) —
        the coordinator pulls these on its probe cadence and ships each
        to a decode worker. Empty on colocated/decode engines."""
        with self._lock:
            b = self.batcher
            if not hasattr(b, "pop_handoffs"):
                return []
            return b.pop_handoffs()

    def import_handoff(self, ids, max_new_tokens: int, rec,
                       tokens=(), prompt_len: int = 0,
                       deadline_s=None, slo=None,
                       elapsed_s: float = 0.0, ttft_s=None) -> int:
        """Accept a prefill worker's gathered block-run record into the
        decode-role batcher (ISSUE 17). Same breaker/kill gate as
        ``submit_ids`` — a degraded decode worker must refuse the ship
        so the coordinator retries elsewhere instead of stranding KV."""
        if self.breaker_open() or self._dead:
            raise RuntimeError(f"serving engine is down: {self.fault}")
        with self._lock:
            if self.breaker_open() or self._dead:
                raise RuntimeError(
                    f"serving engine is down: {self.fault}")
            rid = self.batcher.import_handoff(
                ids, max_new_tokens, rec, tokens=tokens,
                prompt_len=prompt_len, deadline_s=deadline_s, slo=slo,
                elapsed_s=elapsed_s, ttft_s=ttft_s)
            self._done[rid] = threading.Event()
            self.n_requests += 1
        self._wake.set()
        return rid

    def revive(self) -> None:
        """Recovery half of ``kill``: the replica re-enters service with
        a clean slate (the kill already swept the batcher) and a closed
        breaker."""
        with self._lock:
            self._dead = False
            self._consec_faults = 0
            self.fault = None
            self._snapshot = self._build_snapshot_locked()
        self._wake.set()

    @property
    def alive(self) -> bool:
        return not self._dead

    def snapshot(self) -> Dict[str, Any]:
        """The lock-free stats snapshot (staleness bounded by one
        scheduler step) — the fleet supervisor's cheap health/load
        read."""
        return self._snapshot

    def goodput_ratio(self) -> float:
        """Windowed SLO-attainment of this engine, 1.0 until the window
        holds anything (an empty window is no evidence of overload) —
        the 429 Retry-After derivation reads this."""
        slo = self._snapshot.get("slo", {})
        if not slo.get("window_n"):
            return 1.0
        return float(slo.get("goodput_ratio", 1.0))

    def stream_queue(self, rid: int) -> queue.Queue:
        """Per-request queue of cumulative token-id lists. Two sentinels:
        ``None`` = request finished normally; a ``dict`` = engine fault
        (``{"fault": repr}``) — consumers must surface it, not decode it."""
        return self._streams[rid]

    def _build_snapshot_locked(self) -> Dict[str, Any]:
        """Caller holds the lock (or the batcher is idle at init)."""
        b = self.batcher
        return {
            "active_rows": sum(r is not None for r in b.rows),
            "queued": len(b.queue),
            "max_batch": b.max_batch,
            "max_len": b.max_len,
            "max_queue": b.max_queue,
            "speculative": b.speculative,
            "faults": self.n_faults,
            "restarts": self.n_restarts,
            "admission_s": round(b.admission_s, 3),
            # Pipelined scheduler: how much host scheduling the in-flight
            # segment is hiding (``overlap_ratio`` below).
            "pipeline": bool(getattr(b, "pipeline", False)),
            # Stall-free admission (ISSUE 5): live piggyback lanes and
            # the per-boundary prompt-token budget driving them.
            "prefill_budget": getattr(b, "prefill_budget", 0),
            "lanes": len(getattr(b, "_lanes", ()) or ()),
            "overlap_ratio": round(b.overlap_ratio(), 3)
            if hasattr(b, "overlap_ratio") else 0.0,
            # SLO classes + windowed goodput (ISSUE 6): per-class
            # attainment so /stats carries the class alongside /metrics.
            "slo": b.slo_stats() if hasattr(b, "slo_stats") else {},
            # Memory ledger (ISSUE 9): totals + per-component bytes +
            # headroom-guard state, merged the way "slo" was — one
            # /stats poll shows latency, goodput AND bytes. Host ints
            # only (the jax.live_arrays reconcile lives on /memory).
            "memory": (b.memory_summary()
                       if hasattr(b, "memory_summary") else {}),
            **({"spec_tokens_per_iteration":
                round(b.spec_tokens_per_iteration(), 2),
                # Adaptive speculation (ISSUE 13): accepted tokens per
                # dispatch, mean chosen window, controller EMA + masked
                # rows — the /stats face of egpt_serve_spec_*.
                "spec": b.spec_stats() if hasattr(b, "spec_stats")
                else {}}
               if b.speculative else {}),
            # Disaggregated serving (ISSUE 17): the worker's role, its
            # block-pool headroom (the decode-placement signal — bytes
            # compare across a fleet, block counts only within one
            # geometry) and the staged handoff counters.
            "role": getattr(b, "role", "colocated"),
            **({"kv_free_blocks": b._pool.free_blocks(),
                "kv_free_bytes": b._pool.free_bytes()}
               if getattr(b, "_pool", None) is not None else {}),
            **({"handoff": {
                "pending": len(b.handoff_ready),
                "gathered": b.handoffs_gathered,
                "gathered_bytes": b.handoffs_gathered_bytes,
                "spliced": b.handoffs_spliced,
                "spliced_bytes": b.handoffs_spliced_bytes}}
               if hasattr(b, "handoff_ready") else {}),
            # reversed() on a dict view walks newest-first without
            # materializing the (bounded-at-8192) stats map each step.
            "recent": {
                str(k): {kk: round(vv, 3)
                         for kk, vv in b.request_stats[k].items()}
                for k in itertools.islice(reversed(b.request_stats), 8)
            },
        }

    def journey(self, rid: int) -> Optional[Dict[str, Any]]:
        """One request's flight-recorder timeline + decomposition
        (ISSUE 10, ``GET /request?rid=N``). Lock-free: the recorder
        guards its own host-side state, like the metrics registry."""
        # egpt-check: ignore[lock] -- the batcher binding is set once in __init__ and never rebound; the journey surface reads the recorder's own lock-guarded host state only (the /memory rule)
        return self.batcher.journey(rid)

    def journeys(self, n: int = 64) -> List[Dict[str, Any]]:
        """Recent finished request timelines (``GET /requests``)."""
        # egpt-check: ignore[lock] -- same read-only recorder surface as journey()
        return self.batcher.journey_index(n)

    def series(self, window_s: Optional[float] = None,
               n: Optional[int] = None) -> Dict[str, Any]:
        """The ``GET /series`` payload (ISSUE 15): the sampled
        time-series ring + windowed derivations. Lock-free here — the
        store guards its own host-side state, like the recorder."""
        return obs_series.snapshot(window_s=window_s, n=n)

    def alerts(self) -> Dict[str, Any]:
        """The ``GET /alerts`` payload (ISSUE 15): per-rule hysteresis
        state + the bounded transition log."""
        return obs_series.alerts()

    def memory_stats(self) -> Dict[str, Any]:
        """The ``GET /memory`` payload (ISSUE 9): ledger + fresh
        live-array reconciliation + static estimate + compiled
        footprint. Deliberately OUTSIDE the engine lock — the reconcile
        walks every live buffer and a cold-probe compile can take
        seconds; both read metadata/host state only, and the batcher's
        memory surface takes no scheduler-owned mutable state."""
        # egpt-check: ignore[lock] -- the batcher binding is set once in __init__ and never rebound; memory_stats reads its ledger/metadata surface only, and holding the engine lock across a live-array walk or an AOT compile would block the scheduler for seconds (the render-outside-the-lock rule /metrics follows)
        return self.batcher.memory_stats()

    def stats(self) -> Dict[str, Any]:
        # Lock-free by design (see _snapshot); counters are GIL-atomic.
        return {
            "uptime_s": round(time.time() - self.t_start, 1),
            "requests": self.n_requests,
            "status": "degraded" if self.breaker_open() else "ok",
            **self._snapshot,
            # Registry merge (ISSUE 3): the same numbers /metrics exposes
            # in Prometheus text, summarized — histogram p50/p99 are log2-
            # bucket upper bounds, see obs/metrics.py.
            "metrics": obs_metrics.serve_summary(),
            # Health state next to latency and bytes (ISSUE 15): active
            # alert rules + the last few transitions; the full log and
            # the series behind it ride GET /alerts and GET /series.
            "alerts": obs_series.alert_stats(),
        }

    def shutdown(self) -> None:
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=10)
        if self.trace_out:
            tracer = obs_trace.active()
            if tracer is not None:
                n = tracer.write(self.trace_out)
                print(f"[serve] wrote {n} trace events to {self.trace_out}")

    # -- scheduler thread -------------------------------------------------

    def _loop(self) -> None:
        while not self._stop:
            try:
                faults.maybe_fail("serve.loop")
                with self._lock:
                    # A killed replica parks: the fleet drained its work
                    # and will revive() it (or not) — stepping a swept
                    # batcher would be harmless but dishonest health.
                    busy = (not self._dead
                            and (self.batcher.queue
                                 or any(r is not None
                                        for r in self.batcher.rows)))
                    if busy:
                        self._step_locked()
            except Exception as e:  # scheduler death must be LOUD
                self._on_fault(e)
                if not self._stop:
                    # Restart the scheduler on a FRESH thread (the fault
                    # may have left this one's stack in a weird spot);
                    # brief backoff so a hard fault loop cannot spin.
                    time.sleep(min(0.05 * self._consec_faults, 0.5))
                    with self._lock:
                        self.n_restarts += 1
                    obs_metrics.SERVE_SCHED_RESTARTS.inc()
                    self._thread = threading.Thread(
                        target=self._loop, daemon=True)
                    self._thread.start()
                return
            self._let_submitters_in()
            self._maybe_beat()
            if not busy:
                with obs_trace.span("idle_wait", "engine"):
                    self._wake.wait(timeout=0.1)
                self._wake.clear()

    @contextlib.contextmanager
    def _in_turn(self):
        """Around ``with self._lock:`` on a thread that is not the
        scheduler's: ``submit_ids``, and the fleet supervisor's
        ``try_result``, ``try_status`` and ``kill`` (a supervisor that gets
        in only when the engine idles kills no replica mid-decode). Counted
        as asking from before it asks until it has let the lock go, so that
        the scheduler thread lets it in between two steps; the last to
        leave notifies."""
        with self._turn:
            self._asking += 1
        try:
            yield
        finally:
            with self._turn:
                self._asking -= 1
                if not self._asking:
                    self._turn.notify_all()

    def _let_submitters_in(self, at_most_s: float = 0.05) -> None:
        """Between two holds of ``_lock`` by the scheduler thread: sleep,
        off the lock, until every thread that is asking ``_in_turn`` has had
        it (a ``submit_ids`` holds it for one ``batcher.submit``), so that
        the next step admits what arrived during the last one. Returns at
        once when none is asking. Bounded: arrivals that keep coming do not
        hold the scheduler for more than ``at_most_s``."""
        with self._turn:
            if self._asking:  # notified only when the count reaches 0
                self._turn.wait(timeout=at_most_s)

    def _step_locked(self) -> None:
        """One hold of the lock by the scheduler thread: the batcher's
        step, the streams' new tokens, the finished answers, the
        lock-free snapshot."""
        b = self.batcher
        with obs_trace.span(
                "step", "engine", queued=len(b.queue),
                live=sum(r is not None for r in b.rows)) as step:
            b.step()
            with obs_trace.span("stream_push", "engine") as push:
                rids = self._push_stream_deltas_locked()
                finished = self._harvest_locked()
                push.set(finished=len(finished), rids=rids + finished)
            self._n_steps += 1
            if self._consec_faults:
                # A clean step closes the breaker: the fault
                # streak is over and /health returns to ok.
                self._consec_faults = 0
                self.fault = None
                obs_metrics.SERVE_BREAKER_OPEN.set(0)
            # Snapshot only when state moved (idle polls would
            # rebuild 10x/s for nothing); submits wake the
            # loop, so queue growth shows within one pass.
            self._snapshot = self._build_snapshot_locked()
            if obs_trace.enabled():
                step.set(rids=[r.rid for r in b.rows if r is not None]
                         + finished)

    def _maybe_beat(self) -> None:
        """Serving liveness beat (same file format + staleness predicate
        as the trainer's): step count, queue depth, breaker state."""
        if self._heartbeat is None:
            return
        now = time.monotonic()
        if now - self._last_beat < self._hb_interval:
            return
        self._last_beat = now
        try:
            s = self._snapshot
            self._heartbeat.beat(
                self._n_steps,
                status="degraded" if self.breaker_open() else "ok",
                active=s.get("active_rows", 0), queued=s.get("queued", 0),
                faults=self.n_faults, restarts=self.n_restarts,
            )
        except OSError:
            pass  # liveness reporting must never kill the scheduler

    def _on_fault(self, e: Exception) -> None:
        """One scheduler fault: fail the IN-FLIGHT rows cleanly (their
        waiters get the fault instead of burning timeouts), keep queued
        requests for the restarted scheduler to re-admit, and trip the
        circuit breaker when the streak reaches the threshold (then
        queued requests are failed too and submits are refused until the
        cooldown's half-open probe)."""
        with self._lock:
            # Fault bookkeeping mutates under the lock (the race detector
            # caught the old lock-free increments): revive() zeroes
            # _consec_faults under the lock from another thread, so an
            # unlocked += here could lose the trip that opens the
            # breaker.
            self.fault = repr(e)
            self.n_faults += 1
            self._consec_faults += 1
            self._t_fault = time.monotonic()
            tripped = self._consec_faults >= self.breaker_threshold
        obs_metrics.SERVE_SCHED_FAULTS.inc()
        obs_trace.instant("scheduler_fault", cat="engine", error=repr(e))
        if tripped:
            obs_metrics.SERVE_BREAKER_OPEN.set(1)
            obs_trace.instant("breaker_trip", cat="engine")
        with self._lock:
            b = self.batcher
            # A fault can land mid-pipeline (e.g. at the serve.dispatch
            # boundary) with a segment still in flight: drop the in-flight
            # record and the device carry so the restarted scheduler's
            # first dispatch re-uploads the repaired host view instead of
            # resuming from stale device state.
            if hasattr(b, "abort_pipeline"):
                b.abort_pipeline()
            if getattr(b, "_lanes", None):
                # Piggybacked admissions mid-prefill: their requests are
                # failed by the rows sweep below (the row is reserved);
                # drop the lane records so the restarted scheduler never
                # tries to finish a dead lane.
                b._lanes.clear()
                b._lane_free = list(range(b._lane_cap))
            failed = []
            j_owner = getattr(b, "_journey_owner", None)
            t_sweep = time.perf_counter()

            def _fail_journey(req):
                # The sweep bypasses _record_finish, so it closes the
                # flight-recorder timeline itself: the journey's finish
                # must match the engine-side terminal status
                # byte-for-byte (the ISSUE 10 terminal-status audit).
                if j_owner is not None:
                    slo = getattr(req, "slo", None)
                    obs_journey.finish(
                        j_owner, req.rid, "engine_fault",
                        t_submit=req.t_submit, t_done=t_sweep,
                        slo_class=(slo.name if slo is not None else None))

            for r, req in enumerate(b.rows):
                if req is None:
                    continue
                b.rows[r] = None
                b.frozen[r] = True
                b.n_rem[r] = 0
                ent = getattr(req, "prefix_entry", None)
                if ent is not None:
                    # The sweep bypasses _record_finish: drain the
                    # prefix-cache refcount pin here or the entry would
                    # stay unevictable forever.
                    ent.pins -= 1
                    req.prefix_entry = None
                failed.append(req.rid)
                _fail_journey(req)
            b._pending = None
            if tripped:
                for req in b.queue:
                    failed.append(req.rid)
                    _fail_journey(req)
                b.queue.clear()
            for rid in failed:
                self._status[rid] = "engine_fault"
                if rid in self._streams:
                    # A dict sentinel, not None: the stream handler must
                    # surface the fault, not end the body as a normal done.
                    self._streams.pop(rid).put({"fault": self.fault})
                    self._sent.pop(rid, None)
                    self._done.pop(rid, None)
                elif rid in self._done:
                    # result() sees no answer -> raises the fault (the
                    # entry stays for a waiter that arrives post-sweep).
                    self._done[rid].set()
                self._abandoned.discard(rid)
            self._snapshot = self._build_snapshot_locked()

    def _push_stream_deltas_locked(self) -> List[int]:
        """Returns the rids whose streams got new tokens."""
        pushed = []
        for req in self.batcher.rows:
            if req is None or req.rid not in self._streams:
                continue
            n = len(req.tokens)
            if n > self._sent[req.rid]:
                self._streams[req.rid].put(list(req.tokens[:n]))
                self._sent[req.rid] = n
                pushed.append(req.rid)
        return pushed

    def _harvest_locked(self) -> List[int]:
        """Returns the rids that finished."""
        if not self.batcher.finished:
            return []
        done, self.batcher.finished = self.batcher.finished, {}
        for rid, toks in done.items():
            status = self.batcher.finish_status.pop(rid, "ok")
            if rid in self._abandoned:
                # Its waiter timed out and went away; keeping the answer
                # would leak it (result() registered the drop).
                self._abandoned.discard(rid)
                continue
            # Bounded terminal-status map (same oldest-first rule as the
            # batcher's request_stats): the handler reads it right after
            # result(), eviction only matters for abandoned waiters.
            while len(self._status) >= 8192:
                self._status.pop(next(iter(self._status)))
            self._status[rid] = status
            if rid in self._streams:
                # Stream consumers hold their own queue reference; drop
                # ALL engine-side state here — a streamed request never
                # calls result(), so nothing else would (unbounded growth
                # on a long-lived server otherwise; the batcher bounds
                # request_stats for the same reason).
                q = self._streams.pop(rid)
                q.put(list(toks))
                # None = finished normally; a status dict = forced finish
                # (deadline/cancel/quarantine) the handler must surface.
                q.put(None if status == "ok" else {"status": status})
                self._sent.pop(rid, None)
                self._done.pop(rid, None)
                continue
            self._answers[rid] = toks
            if rid in self._done:
                self._done[rid].set()
        return list(done)


def _decode_pixels(payload: Dict[str, Any], cfg, event_root=None):
    """event_path (confined under --event_root) or event_b64 (inline npy)
    -> pixel frames."""
    from eventgpt_tpu.ops.image import process_event_file
    from eventgpt_tpu.utils.paths import resolve_event_path

    if "event_path" in payload:
        # Network-facing file access is allowlisted by directory: without
        # --event_root, server-local paths are disabled outright (clients
        # upload via event_b64); with it, the resolved path must stay
        # inside the root — no probing the server's filesystem. The
        # confinement logic is shared with scripts/serve_demo.py.
        path = resolve_event_path(event_root, payload["event_path"])
        try:
            _, pixels = process_event_file(
                path, cfg.num_event_frames, cfg.vision.image_size
            )
        except FileNotFoundError:
            raise ValueError(
                f"no such event file under --event_root: "
                f"{payload['event_path']}"
            )
        return pixels
    if "event_b64" in payload:
        import tempfile

        raw = base64.b64decode(payload["event_b64"])
        # Round-trip through a real file so one loader (load_event_npy's
        # restricted unpickler included) serves both entry points.
        with tempfile.NamedTemporaryFile(suffix=".npy") as f:
            f.write(raw)
            f.flush()
            _, pixels = process_event_file(
                f.name, cfg.num_event_frames, cfg.vision.image_size
            )
        return pixels
    raise ValueError("request needs event_path or event_b64")


def make_handler(engine: ServingEngine, cfg, event_root=None,
                 default_budget: int = 64,
                 max_body_bytes: int = 32 * 1024 * 1024,
                 default_deadline_s: Optional[float] = None,
                 slo_classes: Optional[Dict[str, Any]] = None):
    if slo_classes is None:
        # Server-default SLO targets per class (ISSUE 6); build_server
        # overrides from --slo_* flags. A payload "slo_class" picks one;
        # optional payload slo_ttft_s / slo_itl_s / slo_latency_s
        # override the targets for that request only.
        from eventgpt_tpu.workload import SLO

        slo_classes = {
            "interactive": SLO("interactive", ttft_s=1.0, itl_s=0.25),
            "batch": SLO("batch", latency_s=30.0),
        }

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):  # quiet by default
            pass

        def _json(self, code: int, obj, headers=None) -> None:
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            from urllib.parse import parse_qs, urlsplit

            # Routes take query strings since ISSUE 10 (/request?rid=N,
            # /trace?rid=N); bare paths behave exactly as before.
            split = urlsplit(self.path)
            route, query = split.path, parse_qs(split.query)
            if route == "/request":
                # Flight recorder (ISSUE 10): one request's full event
                # timeline + phase decomposition + dominant miss cause.
                try:
                    rid = int(query["rid"][0])
                except (KeyError, ValueError, IndexError):
                    self._json(400, {"error": "need ?rid=N"})
                    return
                rec = engine.journey(rid)
                if rec is None:
                    self._json(404, {
                        "error": f"no journey for rid {rid} (unknown, "
                                 f"evicted from the retention ring, or "
                                 f"the recorder is disarmed — "
                                 f"--journey_keep)"})
                    return
                self._json(200, rec)
                return
            if route == "/requests":
                # Recent finished index: rid / status / slo / cause —
                # the "which request should I look at" entry point of
                # the slow-request runbook (OBSERVABILITY.md).
                try:
                    n = int(query.get("n", ["64"])[0])
                except ValueError:
                    self._json(400, {"error": "bad ?n="})
                    return
                self._json(200, {"requests": engine.journeys(n),
                                 "enabled": obs_journey.enabled()})
                return
            if route == "/series":
                # Time-series store (ISSUE 15): the sampled ring +
                # windowed derivations (?window_s=S bounds the
                # derivation window, ?n=N the returned points). Fleet
                # engines aggregate per-replica/per-worker stores.
                try:
                    window_s = (float(query["window_s"][0])
                                if "window_s" in query else None)
                    n = int(query["n"][0]) if "n" in query else None
                except (ValueError, IndexError):
                    self._json(400, {"error": "bad ?window_s= or ?n="})
                    return
                self._json(200, engine.series(window_s=window_s, n=n))
                return
            if route == "/alerts":
                # Burn-rate alert state (ISSUE 15): per-rule hysteresis
                # state + the bounded firing/clearing log — the runbook
                # entry point (/alerts -> /series -> /requests ->
                # /request?rid=N, OBSERVABILITY.md).
                self._json(200, engine.alerts())
                return
            if route == "/trace":
                tracer = obs_trace.active()
                if tracer is None:
                    self._json(404, {"error": "tracing disarmed "
                                              "(--trace_buffer 0)"})
                    return
                evs = tracer.events()
                if "rid" in query:
                    # ?rid=N filters the ring to one request's spans
                    # (ISSUE 10 satellite): the async lifecycle events
                    # carry the rid as their Chrome-trace id, a span that
                    # worked for one request carries args.rid, one that
                    # worked for several (a wave, a dispatch, a step)
                    # args.rids — the device-level half of a
                    # flight-recorder timeline.
                    try:
                        rid = int(query["rid"][0])
                    except (ValueError, IndexError):
                        self._json(400, {"error": "bad ?rid="})
                        return
                    evs = [e for e in evs
                           if e.get("id") == rid
                           or (e.get("args") or {}).get("rid") == rid
                           or rid in (e.get("args") or {}).get("rids", ())]
                self._json(200, {"traceEvents": evs,
                                 "droppedEvents": tracer.dropped()})
                return
            if self.path == "/health":
                if engine.breaker_open():
                    # Breaker open: the load balancer should drain this
                    # replica until the cooldown's half-open probe. The
                    # derived Retry-After (remaining cooldown, else the
                    # goodput-derived hint) rides here too, so probes
                    # and clients share one backoff story (ISSUE 11).
                    from eventgpt_tpu.fleet import retry_after_s

                    ra = getattr(engine, "breaker_retry_after_s",
                                 lambda: None)()
                    if ra is None:
                        ra = retry_after_s("batch",
                                           engine.goodput_ratio())
                    self._json(503, {"status": "degraded",
                                     "error": engine.fault,
                                     "faults": engine.n_faults,
                                     "restarts": engine.n_restarts,
                                     "retry_after_s": round(ra, 3)},
                               headers={"Retry-After":
                                        str(max(1, math.ceil(ra)))})
                    return
                s = engine.stats()
                self._json(200, {"status": "ok",
                                 "active": s["active_rows"],
                                 "queued": s["queued"],
                                 "restarts": engine.n_restarts})
            elif self.path == "/stats":
                self._json(200, engine.stats())
            elif self.path == "/fleet" and hasattr(engine, "fleet_stats"):
                # Fleet topology + routing/shedding policy + per-replica
                # health (ISSUE 7) — only mounted when the engine IS a
                # fleet router (cli fleet mode).
                self._json(200, engine.fleet_stats())
            elif self.path == "/memory":
                # HBM memory ledger (ISSUE 9): per-component bytes,
                # jax.live_arrays reconciliation (accounted/unaccounted
                # split), the static capacity estimate and the compiled
                # executable footprint. Runs outside the engine lock
                # like /metrics — pollable mid-segment.
                self._json(200, engine.memory_stats())
            elif self.path == "/prefix_cache":
                # Prefix-KV cache snapshot (ISSUE 4): entry list, byte
                # budget/usage, hit/miss/eviction counters. Lock-free
                # like /stats — the cache guards its own host-side state.
                self._json(200, engine.batcher.prefix_cache_stats())
            elif self.path == "/metrics":
                # Prometheus text exposition (scrape target). Rendering
                # walks the registry outside the engine lock — safe inside
                # a probe timeout even mid-segment, like /health.
                body = obs_metrics.REGISTRY.render_prometheus().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self._json(404, {"error": f"no route {self.path}"})

        def do_POST(self):
            if self.path not in ("/v1/generate", "/cancel", "/prefix",
                                 "/profile"):
                self._json(404, {"error": f"no route {self.path}"})
                return
            try:
                cl = self.headers.get("Content-Length")
                if cl is None:
                    # Missing Content-Length (ISSUE 11 hardening): every
                    # POST here carries a JSON body, so "no length" is
                    # either a broken client or a smuggling probe —
                    # reject instead of treating it as an empty body.
                    raise ValueError
                n = int(cl)
                if n < 0:
                    # read(-1) would block until client EOF, pinning this
                    # handler thread forever.
                    raise ValueError
            except ValueError:
                # Rejecting without reading the body desynchronizes
                # HTTP/1.1 keep-alive framing (unread body bytes would be
                # parsed as the next request line) — close the connection.
                self.close_connection = True
                self._json(400, {"error": "bad Content-Length"})
                return
            if n > max_body_bytes:
                # Reject BEFORE reading: Content-Length is attacker-
                # controlled, and decoding an arbitrarily large event_b64
                # would let any client that reaches the port allocate
                # unbounded host memory per request.
                self.close_connection = True  # unread body: see above
                self._json(413, {"error":
                                 f"body {n} bytes exceeds the "
                                 f"{max_body_bytes}-byte limit "
                                 f"(--max_body_mb)"})
                return
            if self.path == "/profile":
                # On-demand jax.profiler window on the RUNNING server:
                # {"seconds": N} captures N seconds of live traffic into
                # --profile_dir (or a fresh temp dir) and returns the
                # trace directory for TensorBoard/XProf. Blocks this
                # handler thread for the window; the scheduler keeps
                # serving — that is the traffic being profiled.
                from eventgpt_tpu.obs import profiling as obs_profiling

                try:
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    seconds = float(payload.get("seconds", 2.0))
                    if not (0.0 <= seconds <= 120.0):
                        raise ValueError(
                            f"seconds must be in [0, 120], got {seconds}")
                except Exception as e:  # bad request
                    self._json(400, {"error": str(e)})
                    return
                try:
                    d = obs_profiling.capture(seconds)
                except obs_profiling.CaptureBusyError as e:
                    self._json(409, {"error": str(e)})
                    return
                except Exception as e:
                    self._json(500, {"error": str(e)})
                    return
                self._json(200, {"profile_dir": d, "seconds": seconds})
                return
            if self.path == "/cancel":
                try:
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    rid = int(payload["rid"])
                except Exception as e:  # bad request
                    self._json(400, {"error": str(e)})
                    return
                self._json(200, {"rid": rid,
                                 "cancelled": engine.cancel(rid)})
                return
            if self.path == "/prefix":
                # Admin route: INSERT a prefix-KV cache entry on a
                # RUNNING server — {"prefix_prompt": str, optional
                # "event_path"/"event_b64" when the prefix runs through
                # the event block}. Since ISSUE 4 the cache is a
                # multi-entry trie, so repeated POSTs accumulate entries
                # (same key = replace) next to the ones admission prefill
                # inserts automatically; GET /prefix_cache lists them.
                try:
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    prompt = payload["prefix_prompt"]
                    pixels = None
                    if "event_path" in payload or "event_b64" in payload:
                        pixels = _decode_pixels(payload, cfg, event_root)
                    plen = engine.set_prefix(prompt, pixels)
                except (KeyError, ValueError) as e:  # bad request
                    self._json(400, {"error": str(e)})
                    return
                except Exception as e:
                    self._json(500, {"error": str(e)})
                    return
                st = engine.batcher.prefix_cache_stats()
                self._json(200, {"prefix_len": plen,
                                 "entries": st.get("n_entries", 0),
                                 "bytes": st.get("bytes", 0)})
                return
            from eventgpt_tpu.data.conversation import prepare_event_prompt
            from eventgpt_tpu.data.tokenizer import tokenize_with_event
            from eventgpt_tpu.fleet import FleetShedError, retry_after_s
            from eventgpt_tpu.serve import QueueFullError

            try:
                with obs_trace.span("http_read", "http", bytes=n) as read:
                    payload = json.loads(self.rfile.read(n) or b"{}")
                query = payload["query"]
                budget = int(payload.get("max_new_tokens", default_budget))
                deadline = payload.get("deadline_s", default_deadline_s)
                deadline = float(deadline) if deadline else None
                slo = None
                if "slo_class" in payload:
                    # Per-request SLO class (ISSUE 6): unknown names are
                    # the client's fault — the class set is closed
                    # (bounded metric-label cardinality).
                    name = str(payload["slo_class"])
                    if name not in slo_classes:
                        raise ValueError(
                            f"unknown slo_class {name!r}: one of "
                            f"{sorted(slo_classes)}")
                    slo = slo_classes[name]
                    overrides = {
                        k[4:]: float(payload[k])
                        for k in ("slo_ttft_s", "slo_itl_s",
                                  "slo_latency_s") if k in payload
                    }
                    if overrides:
                        import dataclasses

                        slo = dataclasses.replace(slo, **overrides)
                with obs_trace.span("host_prep", "http") as prep:
                    pixels = _decode_pixels(payload, cfg, event_root)
                    ids = tokenize_with_event(
                        prepare_event_prompt(query, engine.conv_mode),
                        engine.tokenizer)
            except Exception as e:  # bad request, not a server fault
                self._json(400, {"error": str(e)})
                return
            stream = bool(payload.get("stream", False))
            t0 = time.perf_counter()
            try:
                rid = engine.submit_ids(ids, pixels, budget, stream=stream,
                                        deadline_s=deadline, slo=slo)
                read.set(rid=rid)
                prep.set(rid=rid)
            except (QueueFullError, FleetShedError) as e:
                # Backpressure, not failure: tell the client to come
                # back (bounded admission queue — ISSUE 1; fleet shed —
                # ISSUE 7). Retry-After is CLASS-AWARE and derived from
                # the current goodput window (fleet.retry_after_s), not
                # a fixed constant: batch traffic backs off harder, and
                # both classes back off longer the further attainment
                # has sunk. A shed carries its hint on the exception;
                # queue-full derives it here from the engine's window.
                cls_name = slo.name if slo is not None else "batch"
                ra = getattr(e, "retry_after_s", None)
                if ra is None:
                    ra = retry_after_s(cls_name, engine.goodput_ratio())
                body = json.dumps({
                    "error": str(e),
                    "slo_class": cls_name,
                    "retry_after_s": round(ra, 3),
                }).encode()
                self.send_response(429)
                self.send_header("Content-Type", "application/json")
                self.send_header("Retry-After", str(max(1, math.ceil(ra))))
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            except ValueError as e:
                # submit()'s own validation (budget does not fit max_len,
                # malformed sentinel count) is still the client's fault.
                self._json(400, {"error": str(e)})
                return
            except RuntimeError as e:
                # Engine degraded (circuit breaker open): surface the loud
                # 503 /health already advertises instead of letting this
                # handler thread throw and drop the connection. Like the
                # 429 paths, the 503 carries a DERIVED Retry-After
                # (ISSUE 11 satellite): the breaker's remaining cooldown
                # when the engine knows it, else the class-aware
                # goodput-derived hint.
                cls_name = slo.name if slo is not None else "batch"
                ra = getattr(engine, "breaker_retry_after_s",
                             lambda: None)()
                if ra is None:
                    ra = retry_after_s(cls_name, engine.goodput_ratio())
                body = json.dumps({
                    "error": str(e),
                    "retry_after_s": round(ra, 3),
                }).encode()
                self.send_response(503)
                self.send_header("Content-Type", "application/json")
                self.send_header("Retry-After", str(max(1, math.ceil(ra))))
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if stream:
                try:
                    self._stream_response(rid)
                except (BrokenPipeError, ConnectionResetError, OSError):
                    # Client went away mid-stream. Never write a second
                    # status line into a started chunked body; the engine
                    # drains and drops the orphaned queue at harvest.
                    pass
                return
            try:
                toks = engine.result(rid)
            except RuntimeError as e:
                # Scheduler fault failed this request (engine restarted
                # behind it) — same 503 contract as a refused submit.
                self._json(503, {"error": str(e)})
                return
            except Exception as e:
                self._json(500, {"error": str(e)})
                return
            try:
                text = engine.tokenizer.batch_decode(
                    [toks], skip_special_tokens=True
                )[0].strip()
                status = engine.status(rid)
                stats = engine.batcher.request_stats.get(rid, {})
                obj = {
                    "answer": text, "tokens": len(toks), "rid": rid,
                    "status": status,
                    "ttft_s": round(stats.get("ttft_s", 0.0), 3),
                    "latency_s": round(
                        stats.get("latency_s",
                                  time.perf_counter() - t0), 3),
                }
                if slo is not None:
                    obj["slo_class"] = slo.name
                    if "slo_met" in stats:
                        obj["slo_met"] = bool(stats["slo_met"])
                if payload.get("debug"):
                    # Flight recorder (ISSUE 10): {"debug": true} rides
                    # the request's own response with its full timeline
                    # + phase decomposition — no second round trip to
                    # /request?rid=N needed while debugging a client. The
                    # raw ids ride along: a tokenizer that cannot render
                    # an id (the byte tokenizer over a 32000-row head)
                    # drops it from "answer".
                    obj["debug"] = engine.journey(rid)
                    obj["token_ids"] = [int(t) for t in toks]
                # Forced finishes map to structured HTTP errors (the
                # partial answer rides along): deadline -> 504,
                # cancel -> 499 (client asked), NaN quarantine -> 500,
                # resource exhaustion (block pool AND spill budget both
                # spent — ISSUE 16) -> 503 with the same derived
                # Retry-After the breaker/shed paths carry.
                code = {"ok": 200, "deadline_exceeded": 504,
                        "cancelled": 499,
                        "resource_exhausted": 503,
                        "nan_quarantined": 500}.get(status, 500)
                if code != 200:
                    obj["error"] = status
                if code == 503:
                    cls_name = slo.name if slo is not None else "batch"
                    ra = getattr(engine, "breaker_retry_after_s",
                                 lambda: None)()
                    if ra is None:
                        ra = retry_after_s(cls_name,
                                           engine.goodput_ratio())
                    obj["retry_after_s"] = round(ra, 3)
                    self._json(code, obj, headers={
                        "Retry-After": str(max(1, math.ceil(ra)))})
                else:
                    self._json(code, obj)
            except Exception as e:
                self._json(500, {"error": str(e)})

        def _stream_response(self, rid: int) -> None:
            """Chunked transfer: one JSON line per delta. Deltas re-decode
            the cumulative prefix each time, and hold back any trailing
            U+FFFD replacement chars: a multibyte char split across decode
            segments first decodes as \\ufffd and is REPLACED in the next
            cumulative decode — emitted eagerly it would corrupt the
            stream (a chunked body cannot retract bytes). Stripped tails
            that never resolve (genuinely invalid bytes) flush in the
            terminal delta. When a longer decode REWRITES earlier text
            (sentencepiece whitespace/detokenization effects make the
            cumulative decode non-prefix-stable), a corrective
            ``{"restart": full_text}`` event replaces the client's buffer
            — so apply(deltas ∘ restarts) == the final answer always."""
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()

            def chunk(obj) -> None:
                line = (json.dumps(obj) + "\n").encode()
                self.wfile.write(f"{len(line):x}\r\n".encode())
                self.wfile.write(line + b"\r\n")

            sent = ""

            def emit(new_text: str) -> None:
                nonlocal sent
                if new_text == sent:
                    return
                if new_text.startswith(sent):
                    chunk({"delta": new_text[len(sent):], "rid": rid})
                else:
                    chunk({"restart": new_text, "rid": rid})
                sent = new_text

            q = engine.stream_queue(rid)
            text = ""
            while True:
                toks = q.get()
                if toks is None:
                    break
                if isinstance(toks, dict):
                    if "status" in toks:  # forced finish (deadline/
                        break             # cancel/quarantine): terminal
                    chunk({"done": True, "rid": rid,  # engine fault
                           "error": toks["fault"],
                           "answer": sent.strip()})
                    self.wfile.write(b"0\r\n\r\n")
                    return
                text = engine.tokenizer.batch_decode(
                    [toks], skip_special_tokens=True
                )[0]
                emit(text.rstrip("�"))
            emit(text)  # flush any held-back tail, rewritten or not
            status = engine.status(rid)
            final = {"done": True, "rid": rid, "answer": sent.strip(),
                     "status": status}
            if status != "ok":
                final["error"] = status
            chunk(final)
            self.wfile.write(b"0\r\n\r\n")

    return Handler


# Every flag that shapes a worker's batcher/engine MUST cross the
# process boundary to --worker processes (workers load their own model
# and build their own engine — separate processes share no state). The
# forwarding is DECLARED here, not buried in an argv builder, so the
# regression guard (tests/test_fleet_proc.py::test_worker_argv_*) can
# assert two things mechanically: (1) every entry round-trips through a
# fully-populated args namespace, and (2) every parser flag is
# classified — forwarded, coordinator-only, or per-slot — so a new
# serving flag cannot silently stay coordinator-side (the bug class
# that once ran paged-pool workers dense).
#
# Kinds: "value"  — always forwarded as --dest str(value);
#        "opt"    — forwarded only when set (None/empty skipped);
#        "flag"   — store_true, forwarded only when truthy.
WORKER_FORWARDED_FLAGS = (
    ("model_path", "value", "tiny-random"),
    ("conv_mode", "value", "eventgpt_v1"),
    ("dtype", "value", "bfloat16"),
    ("quant", "value", "none"),
    ("kv_cache", "value", "bf16"),
    ("kv_layout", "value", "dense"),
    ("kv_pool_blocks", "value", 0),
    ("spill_capacity_mb", "value", 0),
    ("max_batch", "value", 4),
    ("max_len", "value", 1024),
    ("chunk", "value", 128),
    ("temperature", "value", 0.0),
    ("speculative", "value", 0),
    ("prefill_chunk", "value", 0),
    ("prefill_budget", "value", -1),
    ("first_chunk", "value", 0),
    ("max_queue", "value", 256),
    ("prefix_cache_mb", "value", 512.0),
    ("mem_headroom_mb", "value", 0.0),
    ("mem_capacity_mb", "value", 0.0),
    ("breaker_threshold", "value", 3),
    ("breaker_cooldown_s", "value", 5.0),
    ("slo_window", "value", 256),
    ("journey_keep", "value", 512),
    ("series_interval_s", "value", 1.0),
    ("series_keep", "value", 512),
    ("spec_ema_alpha", "value", 0.3),
    ("spec_draft_cost", "value", 0.05),
    ("spec_row_window", "value", 4),
    ("spec_head_min_yield", "value", 0.05),
    ("spec_buckets", "opt", ""),
    ("tokenizer_path", "opt", None),
    ("draft_head", "opt", None),
    ("preempt", "flag", False),
    ("fuse_params", "flag", False),
    ("no_pipeline", "flag", False),
    ("no_prefix_cache", "flag", False),
    ("no_telemetry", "flag", False),
    ("warmup", "flag", False),
)

# Parser flags that deliberately do NOT cross to workers: the HTTP
# front-end, fleet topology/policy (the coordinator owns routing), the
# coordinator-side telemetry sinks, and knobs whose payloads ride the
# RPC ops instead of argv (SLO targets travel inside each submit's SLO
# object; --faults crosses via the inherited EGPT_FAULTS env var;
# --prefix_prompt installs through the set_prefix op). Mesh flags stay
# here too: a proc-fleet worker owns a single-chip mesh — the
# multi-host sharded-generate leg is the ROADMAP's open half.
WORKER_COORDINATOR_ONLY = frozenset({
    "host", "port", "event_root", "max_body_mb", "max_new_tokens",
    "default_deadline_s", "prefix_prompt", "prefix_event",
    "heartbeat_dir",  # per-slot: _spawn appends the slot's own dir
    "fleet", "proc_fleet", "proc_fleet_roles", "drain_timeout_s",
    "fleet_shed_goodput", "fleet_shed_queue", "fleet_probe_interval_s",
    "fleet_heartbeat_stale_s", "fleet_restart_s",
    "procfleet_rpc_deadline_s", "procfleet_rpc_retries",
    "procfleet_spawn_timeout_s", "procfleet_respawn_backoff_s",
    "procfleet_crash_window_s", "procfleet_crash_limit",
    "procfleet_handoff_retries",
    "slo_interactive_ttft_s", "slo_interactive_itl_s",
    "slo_batch_latency_s",
    "trace_buffer", "trace_out", "profile_dir", "faults",
    "mesh_data", "mesh_fsdp", "mesh_model",
    "use_event_qformer", "pretrain_query_embedder",
    "pretrain_attention_layers",
})

# Flags the coordinator appends PER SLOT in fleet_proc._spawn (never
# taken from the coordinator's own namespace): the worker marker, the
# readiness handshake, the slot index, and the slot's serving role.
WORKER_PER_SLOT = frozenset({
    "worker", "worker_ready_file", "worker_slot", "role",
})


def _worker_argv(args) -> list:
    """The worker process's command line: the coordinator's own model +
    engine flags, re-serialized behind ``--worker`` from the
    ``WORKER_FORWARDED_FLAGS`` declaration above."""
    import sys

    argv = [sys.executable, "-m", "eventgpt_tpu.cli.serve", "--worker"]
    for dest, kind, default in WORKER_FORWARDED_FLAGS:
        val = getattr(args, dest, default)
        if kind == "flag":
            if val:
                argv.append(f"--{dest}")
        elif kind == "opt":
            if val:
                argv += [f"--{dest}", str(val)]
        else:
            argv += [f"--{dest}", str(val)]
    return argv


def build_engine(args, force_single: bool = False):
    """(cfg, engine) — everything below the HTTP layer: telemetry
    arming, model load, batcher/engine construction, and the fleet
    tiers (``--fleet N`` threads, ``--proc_fleet N`` worker processes).
    Shared by ``build_server`` and the process-fleet ``--worker``
    entrypoint (``force_single`` makes a worker build exactly one
    engine whatever the fleet flags say — a worker must never recurse
    into spawning its own fleet)."""
    from eventgpt_tpu.utils.compile_cache import enable_compile_cache

    # Initialises no backend: the --proc_fleet coordinator passes through
    # here and must leave the chip to its workers.
    enable_compile_cache()
    # Telemetry arming (ISSUE 3): metrics are on unless --no_telemetry;
    # the span tracer keeps a bounded ring (0 disarms); --profile_dir
    # arms the jax.profiler annotations and sets the POST /profile
    # destination. All three are chain-neutral — they read clocks, never
    # jax values (tests/test_obs.py::test_chain_neutrality).
    if getattr(args, "no_telemetry", False):
        obs_metrics.configure(False)
        obs_trace.disable()
        obs_journey.disable()
        obs_series.disable()
    else:
        buf = int(getattr(args, "trace_buffer", 65536) or 0)
        if buf > 0:
            obs_trace.configure(buf)
        # Flight recorder (ISSUE 10): last N finished request
        # timelines, armed like the span tracer (0 disarms; disarmed =
        # one global check per probe, chains byte-identical either way).
        keep = int(getattr(args, "journey_keep", 512) or 0)
        if keep > 0:
            obs_journey.configure(keep)
        # Time-series store + burn-rate alerts (ISSUE 15): samples the
        # registry on a fixed cadence into a bounded ring and evaluates
        # ALERT_RULES each tick (0 disarms either flag; armed cost is
        # one registry read per interval, chain-neutral like the rest).
        interval = float(getattr(args, "series_interval_s", 1.0) or 0.0)
        skeep = int(getattr(args, "series_keep", 512) or 0)
        if interval > 0 and skeep > 0:
            cap_mb = float(getattr(args, "mem_capacity_mb", 0.0) or 0.0)
            obs_series.configure(
                interval_s=interval, keep=skeep,
                mem_capacity_bytes=(int(cap_mb * 2 ** 20)
                                    if cap_mb > 0 else None))
    if getattr(args, "profile_dir", None):
        from eventgpt_tpu.obs import profiling as obs_profiling

        obs_profiling.configure(args.profile_dir)
    if getattr(args, "faults", None):
        # Arm fault injection from the CLI (EGPT_FAULTS works too): chaos
        # drills against a live server use the same spec grammar as tests.
        faults.configure(getattr(args, "faults"))
    n_proc = int(getattr(args, "proc_fleet", 0) or 0)
    if n_proc > 1 and not force_single:
        # Process-fleet mode (ISSUE 11): the coordinator loads NO model
        # — workers own their engines in their own processes (separate
        # failure domains, the whole point). It only needs the config
        # (pixel preprocessing in the handler) and a tokenizer (submit
        # + routing key).
        from eventgpt_tpu.cli.infer import model_config_and_tokenizer
        from eventgpt_tpu.fleet_proc import ProcFleet

        if (getattr(args, "proc_fleet_roles", None)
                and getattr(args, "kv_layout", "dense") != "paged"):
            # Fail HERE, not as a worker crash loop: the handoff moves
            # block runs, so split roles without the paged layout can
            # never boot.
            raise ValueError(
                "--proc_fleet_roles requires --kv_layout paged (the "
                "prefill->decode handoff ships paged-KV block runs)")
        # An explicit attn_impl: the coordinator reads the event-pipeline
        # envelope only, and resolving the platform default would
        # initialise a backend — the chip belongs to the workers.
        cfg, tokenizer = model_config_and_tokenizer(
            args.model_path, "dense", getattr(args, "tokenizer_path", None))
        engine = ProcFleet(
            _worker_argv(args), n_proc,
            tokenizer=tokenizer, conv_mode=args.conv_mode,
            # Prefill/decode disaggregation (ISSUE 17): "P:D" splits the
            # worker pool into roles; unset = every worker colocated.
            roles=getattr(args, "proc_fleet_roles", None) or None,
            handoff_retries=int(getattr(args, "procfleet_handoff_retries",
                                        3)),
            heartbeat_dir=getattr(args, "heartbeat_dir", None),
            probe_interval_s=getattr(args, "fleet_probe_interval_s",
                                     0.05),
            heartbeat_stale_s=getattr(args, "fleet_heartbeat_stale_s",
                                      5.0),
            rpc_deadline_s=getattr(args, "procfleet_rpc_deadline_s",
                                   15.0),
            rpc_retries=int(getattr(args, "procfleet_rpc_retries", 3)),
            spawn_timeout_s=getattr(args, "procfleet_spawn_timeout_s",
                                    180.0),
            respawn_backoff_s=getattr(args,
                                      "procfleet_respawn_backoff_s",
                                      0.25),
            crash_window_s=getattr(args, "procfleet_crash_window_s",
                                   60.0),
            crash_limit=int(getattr(args, "procfleet_crash_limit", 3)),
            shutdown_drain_s=getattr(args, "drain_timeout_s", 30.0),
        )
        return cfg, engine
    from eventgpt_tpu.cli.infer import load_model, prepare_model
    from eventgpt_tpu.parallel.serving import build_serving_mesh
    from eventgpt_tpu.serve import ContinuousBatcher
    from eventgpt_tpu.utils.platform import backend_platform

    # This process serves (a single engine, or one --worker): it takes
    # the device here, and refuses a silent fall back to the CPU.
    backend_platform()
    cfg, params, tokenizer = load_model(
        args.model_path, args.dtype, None, args.tokenizer_path,
        quant=args.quant, fuse=getattr(args, "fuse_params", False),
    )
    # prepare_model places the host tree straight onto the mesh — a
    # post-hoc reshard would first materialize the full unsharded tree in
    # one chip's HBM (exactly what the mesh path exists to avoid at 7B+).
    mesh = build_serving_mesh(args.mesh_data, args.mesh_fsdp, args.mesh_model)
    cfg, params = prepare_model(cfg, params, tokenizer, args, mesh=mesh)
    draft_head = None
    if getattr(args, "draft_head", None):
        from eventgpt_tpu.models.medusa import load_medusa

        draft_head = load_medusa(args.draft_head)

    def _make_batcher():
        return ContinuousBatcher(
            params, cfg, max_batch=args.max_batch, max_len=args.max_len,
            chunk=args.chunk, temperature=args.temperature,
            eos_token_id=getattr(tokenizer, "eos_token_id", None),
            kv_quant=args.kv_cache == "int8", speculative=args.speculative,
            mesh=mesh, prefill_chunk=args.prefill_chunk,
            draft_head=draft_head,
            first_chunk=getattr(args, "first_chunk", 0),
            max_queue=getattr(args, "max_queue", 0),
            pipeline=not getattr(args, "no_pipeline", False),
            prefix_cache=not getattr(args, "no_prefix_cache", False),
            prefix_cache_bytes=int(
                getattr(args, "prefix_cache_mb", 512.0) * 1024 * 1024),
            # Stall-free admission (ISSUE 5): -1 = auto (one segment's
            # worth of prompt tokens per boundary), 0 = off (waves).
            prefill_budget=(args.chunk
                            if getattr(args, "prefill_budget", -1) < 0
                            else int(args.prefill_budget)),
            slo_window=int(getattr(args, "slo_window", 256)),
            # Memory headroom guard (ISSUE 9): 0 disarms (the default);
            # capacity 0 = the device's own reported limit.
            mem_headroom_bytes=int(
                getattr(args, "mem_headroom_mb", 0.0) * 1024 * 1024),
            mem_capacity_bytes=int(
                getattr(args, "mem_capacity_mb", 0.0) * 1024 * 1024),
            # Paged KV block pool (ISSUE 12): block-granular allocation
            # + used-token admission; "dense" is the A/B escape hatch.
            kv_layout=getattr(args, "kv_layout", "dense"),
            kv_pool_blocks=int(getattr(args, "kv_pool_blocks", 0)),
            # Block-tier preemption + host-RAM KV spill (ISSUE 16):
            # under block exhaustion an interactive admission preempts
            # the lowest-value batch row (spill-or-recompute priced per
            # victim) instead of deferring behind it.
            preempt=bool(getattr(args, "preempt", False)),
            spill_capacity_mb=int(getattr(args, "spill_capacity_mb", 0)),
            # Adaptive speculation (ISSUE 13): empty = fixed-K serving.
            spec_buckets=getattr(args, "spec_buckets", None) or None,
            spec_ema_alpha=float(getattr(args, "spec_ema_alpha", 0.3)),
            spec_draft_cost=float(getattr(args, "spec_draft_cost", 0.05)),
            spec_row_window=int(getattr(args, "spec_row_window", 4)),
            spec_head_min_yield=float(
                getattr(args, "spec_head_min_yield", 0.05)),
            # Disaggregated serving role (ISSUE 17): per-worker, set by
            # the coordinator's _spawn; colocated everywhere else.
            role=getattr(args, "role", "colocated"),
        )

    def _make_engine(batcher, hb_dir):
        return ServingEngine(
            batcher, tokenizer, args.conv_mode,
            breaker_threshold=getattr(args, "breaker_threshold", 3),
            breaker_cooldown_s=getattr(args, "breaker_cooldown_s", 5.0),
            heartbeat_dir=hb_dir,
            trace_out=getattr(args, "trace_out", None),
        )

    n_fleet = 0 if force_single else int(getattr(args, "fleet", 0) or 0)
    hb_root = getattr(args, "heartbeat_dir", None)
    if n_fleet > 1:
        # Fleet mode (ISSUE 7): N in-process replicas (one weight tree,
        # N resident caches/schedulers — the jit cache shares their
        # executables) behind the prefix-affinity router. The handler
        # serves the router through the same engine surface.
        import os as _os

        from eventgpt_tpu.fleet import Fleet

        batchers = [_make_batcher() for _ in range(n_fleet)]
        if args.warmup:
            t0 = time.perf_counter()
            n = sum(b.warmup() for b in batchers)
            print(f"[serve] warmup: {n} executables in "
                  f"{time.perf_counter() - t0:.1f}s")
        engines = [
            _make_engine(b, _os.path.join(hb_root, f"replica{i}")
                         if hb_root else None)
            for i, b in enumerate(batchers)
        ]
        engine = Fleet(
            engines, tokenizer, args.conv_mode,
            probe_interval_s=getattr(args, "fleet_probe_interval_s", 0.05),
            heartbeat_stale_s=getattr(args, "fleet_heartbeat_stale_s", 5.0),
            shed_goodput_ratio=getattr(args, "fleet_shed_goodput", 0.5),
            shed_queue_depth=getattr(args, "fleet_shed_queue", 0),
            replica_restart_s=getattr(args, "fleet_restart_s", 0) or None,
        )
    else:
        batcher = _make_batcher()
        if args.warmup:
            t0 = time.perf_counter()
            n = batcher.warmup()
            print(f"[serve] warmup: {n} executables in "
                  f"{time.perf_counter() - t0:.1f}s")
        engine = _make_engine(batcher, hb_root)
    if getattr(args, "prefix_prompt", None):
        # Startup form of POST /prefix: cache the shared prompt head's KV
        # once, before traffic. --prefix_event supplies the stream when
        # the prefix text carries the <event> placeholder.
        pixels = None
        if getattr(args, "prefix_event", None):
            from eventgpt_tpu.ops.image import process_event_file

            _, pixels = process_event_file(
                args.prefix_event, cfg.num_event_frames,
                cfg.vision.image_size,
            )
        plen = engine.set_prefix(args.prefix_prompt, pixels)
        print(f"[serve] shared prefix cached: {plen} positions")
    return cfg, engine


def build_server(args) -> tuple:
    """(ThreadingHTTPServer, engine) — separated from main() so tests
    can run the real stack in-process on an ephemeral port. The engine
    may be a single ``ServingEngine``, a thread ``Fleet`` or a
    ``ProcFleet`` coordinator; the handler serves all three through
    the same surface."""
    cfg, engine = build_engine(args)
    default_deadline = getattr(args, "default_deadline_s", 0) or None
    # Per-class SLO targets (ISSUE 6): a payload {"slo_class": ...}
    # scores the request against these at finish (0 disarms a target).
    from eventgpt_tpu.workload import SLO

    slo_classes = {
        "interactive": SLO(
            "interactive",
            ttft_s=getattr(args, "slo_interactive_ttft_s", 1.0) or None,
            itl_s=getattr(args, "slo_interactive_itl_s", 0.25) or None),
        "batch": SLO(
            "batch",
            latency_s=getattr(args, "slo_batch_latency_s", 30.0) or None),
    }
    httpd = ThreadingHTTPServer(
        (args.host, args.port),
        make_handler(engine, cfg, getattr(args, "event_root", None),
                     default_budget=getattr(args, "max_new_tokens", 64),
                     max_body_bytes=int(
                         getattr(args, "max_body_mb", 32) * 1024 * 1024),
                     default_deadline_s=default_deadline,
                     slo_classes=slo_classes),
    )
    return httpd, engine


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI's full argparse surface, separated from main()
    so the worker-argv regression guard can enumerate every flag and
    assert it is classified (WORKER_FORWARDED_FLAGS /
    WORKER_COORDINATOR_ONLY / WORKER_PER_SLOT)."""
    p = argparse.ArgumentParser()
    p.add_argument("--model_path", default="tiny-random")
    p.add_argument("--tokenizer_path", default=None)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8600)
    p.add_argument("--event_root", default=None,
                   help="directory event_path requests resolve under; "
                        "unset = server-local paths disabled (event_b64 "
                        "only)")
    p.add_argument("--conv_mode", default="eventgpt_v1")
    p.add_argument("--max_body_mb", type=float, default=32.0,
                   help="largest accepted POST body (413 above this); size "
                        "for the biggest event_b64 upload you expect")
    p.add_argument("--max_batch", type=int, default=4)
    p.add_argument("--max_len", type=int, default=1024)
    p.add_argument("--chunk", type=int, default=128)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--max_new_tokens", type=int, default=64)
    p.add_argument("--dtype", default="bfloat16",
                   choices=["bfloat16", "float32"])
    p.add_argument("--quant", default="none", choices=["none", "int8"])
    p.add_argument("--fuse_params", action="store_true",
                   help="fuse qkv / gate-up before quantization (+4%% at "
                        "batch 8, neutral at batch 1 on the r05 chip run)")
    p.add_argument("--kv_cache", default="bf16", choices=["bf16", "int8"])
    p.add_argument("--kv_layout", default="dense",
                   choices=["dense", "paged"],
                   help="resident KV layout (ISSUE 12): 'paged' replaces "
                        "the dense (batch, max_len) cache with one "
                        "SEQ_BUCKET-block pool + per-row block tables — "
                        "admission gated by free blocks (used tokens), "
                        "prefix hits alias block runs with copy-on-"
                        "write. Chains are byte-identical to 'dense' "
                        "(the A/B escape hatch)")
    p.add_argument("--kv_pool_blocks", type=int, default=0,
                   help="paged pool size in blocks incl. the scratch "
                        "block (0 = dense-equivalent capacity: "
                        "max_batch * max_len/SEQ_BUCKET + 1). Size it by "
                        "expected USED tokens, not worst case — "
                        "GET /memory's kv_blocks shows live pressure")
    p.add_argument("--preempt", action="store_true",
                   help="block-tier preemption (ISSUE 16, paged layout "
                        "only): when free blocks cannot cover an "
                        "interactive admission, preempt the lowest-value "
                        "batch row (worst deadline headroom first) "
                        "instead of deferring the head behind it. Each "
                        "victim either spills its KV run to host RAM "
                        "(--spill_capacity_mb) or drops and re-prefills "
                        "— whichever the measured bytes-vs-FLOPs price "
                        "says is cheaper. Chains stay byte-identical on "
                        "both paths")
    p.add_argument("--spill_capacity_mb", type=int, default=0,
                   help="host-RAM budget for preempted KV runs (0 = no "
                        "spill store: every preemption drops and "
                        "re-prefills). Spilled bytes show on GET /memory "
                        "under the 'spill' component and "
                        "egpt_serve_spill_store_bytes")
    p.add_argument("--speculative", type=int, default=0)
    p.add_argument("--spec_buckets", default="",
                   help="adaptive speculation (ISSUE 13): comma-separated "
                        "draft-window buckets, e.g. '0,2,4,8' (0 = the "
                        "draft-free fallback segment). Each dispatch "
                        "boundary selects one precompiled bucket from the "
                        "measured acceptance EMA and masks low-acceptance "
                        "rows' drafts; --speculative becomes the default/"
                        "fault-degradation window (max bucket when 0). "
                        "Empty = fixed-K serving")
    p.add_argument("--spec_ema_alpha", type=float, default=0.3,
                   help="acceptance-EMA step per harvested segment")
    p.add_argument("--spec_draft_cost", type=float, default=0.05,
                   help="relative marginal verify cost per draft position "
                        "(the controller's cost model: ~0 when decode is "
                        "weight-streaming bound, higher on small models)")
    p.add_argument("--spec_row_window", type=int, default=4,
                   help="per-row acceptance window (segments) behind the "
                        "per-row draft-depth mask")
    p.add_argument("--spec_head_min_yield", type=float, default=0.05,
                   help="prune draft heads/lookup levels whose realized "
                        "yield EMA falls below this")
    p.add_argument("--draft_head", default=None,
                   help="trained Medusa head stack (.npz) for speculative "
                        "drafting (requires --speculative > 0 or "
                        "--spec_buckets)")
    p.add_argument("--prefill_chunk", type=int, default=0)
    p.add_argument("--prefill_budget", type=int, default=-1,
                   help="stall-free admission (ISSUE 5): prompt tokens "
                        "folded into each decode dispatch as piggyback "
                        "prefill lanes while rows are decoding (mixed "
                        "segments). -1 = auto (--chunk tokens per "
                        "boundary, the default); 0 = off — every "
                        "admission runs the exclusive wave/suffix path "
                        "(the A/B escape hatch)")
    p.add_argument("--first_chunk", type=int, default=0,
                   help="TTFT ramp: short segment length while a fresh "
                        "admission owes its first token (0 = off): "
                        "earlier first tokens for more dispatches")
    p.add_argument("--warmup", action="store_true")
    p.add_argument("--no_pipeline", action="store_true",
                   help="disable pipelined scheduling (dispatch segment "
                        "N+1 from device-resident state while the host "
                        "harvests segment N); the synchronous escape "
                        "hatch — chains are byte-identical either way")
    p.add_argument("--prefix_prompt", default=None,
                   help="shared prompt-prefix text cached once at startup "
                        "(ContinuousBatcher.set_prefix); may contain the "
                        "<event> placeholder if --prefix_event supplies "
                        "its stream. Also settable at runtime via "
                        "POST /prefix")
    p.add_argument("--prefix_event", default=None,
                   help="event .npy backing the <event> block inside "
                        "--prefix_prompt (prefix-through-event-block "
                        "sessions; suffixes then skip CLIP encode)")
    p.add_argument("--prefix_cache_mb", type=float, default=512.0,
                   help="HBM byte budget for the prefix-KV cache (LRU "
                        "eviction above it; 0 = unbounded). The cache "
                        "populates itself on admission prefill and via "
                        "POST /prefix; GET /prefix_cache shows it")
    p.add_argument("--no_prefix_cache", action="store_true",
                   help="disable the prefix-KV cache entirely (every "
                        "admission full-prefills; the A/B escape hatch — "
                        "chains are byte-identical either way)")
    # -- HBM memory ledger + admission headroom (ISSUE 9) --
    p.add_argument("--mem_headroom_mb", type=float, default=0.0,
                   help="admission headroom guard: defer admission "
                        "waves while the memory ledger predicts the "
                        "next wave would leave less than this many MB "
                        "of device capacity free (0 = off, the A/B "
                        "escape hatch; GET /memory shows the ledger)")
    p.add_argument("--mem_capacity_mb", type=float, default=0.0,
                   help="device capacity the headroom guard budgets "
                        "against (0 = the device's own reported "
                        "bytes_limit; CPU reports none, so set this "
                        "explicitly there)")
    # -- request-lifecycle hardening (ISSUE 1) --
    p.add_argument("--max_queue", type=int, default=256,
                   help="admission-queue bound: submits beyond this get "
                        "429 + Retry-After (0 = unbounded)")
    p.add_argument("--default_deadline_s", type=float, default=0.0,
                   help="per-request deadline applied when the payload "
                        "has no deadline_s (0 = none); expiry returns 504 "
                        "with the tokens committed so far")
    p.add_argument("--breaker_threshold", type=int, default=3,
                   help="consecutive scheduler faults that trip the "
                        "circuit breaker (health -> degraded, POSTs 503)")
    p.add_argument("--breaker_cooldown_s", type=float, default=5.0,
                   help="seconds the tripped breaker refuses work before "
                        "the half-open probe admits traffic again")
    p.add_argument("--heartbeat_dir", default=None,
                   help="directory for the serving heartbeat.json "
                        "(train/resilience.py format; unset = disabled)")
    # -- fleet serving (ISSUE 7; DISTRIBUTED.md "Fleet serving") --
    p.add_argument("--fleet", type=int, default=0,
                   help="run N ServingEngine replicas behind the "
                        "prefix-affinity router (0/1 = single engine). "
                        "Replicas share the weight tree; each owns its "
                        "resident KV cache and scheduler thread")
    # -- process fleet (ISSUE 11; DISTRIBUTED.md "Process fleet") --
    p.add_argument("--proc_fleet", type=int, default=0,
                   help="run N worker PROCESSES (each a full "
                        "ServingEngine + model + jax runtime) behind "
                        "the RPC coordinator (0/1 = single engine). "
                        "Separate failure domains: a worker death is "
                        "drained/redone onto survivors and the slot "
                        "respawns with backoff")
    p.add_argument("--proc_fleet_roles", default=None,
                   help="prefill/decode disaggregation (ISSUE 17): "
                        "'P:D' splits the --proc_fleet workers into P "
                        "prefill-role workers (admission only; each "
                        "activated row's paged-KV block run is gathered "
                        "and shipped) and D decode-role workers (splice "
                        "the shipped run into their own arena and "
                        "decode). P+D must equal --proc_fleet; requires "
                        "--kv_layout paged. Unset = every worker "
                        "colocated (the default, unchanged). Greedy "
                        "chains are byte-identical either way")
    p.add_argument("--procfleet_handoff_retries", type=int, default=3,
                   help="decode workers a shipped handoff is tried "
                        "against before the coordinator falls back to "
                        "REDO (re-submit from its own record)")
    p.add_argument("--role", default="colocated",
                   choices=["colocated", "prefill", "decode"],
                   help="this worker's serving role (set per slot by "
                        "the --proc_fleet_roles coordinator; not a "
                        "user-facing flag)")
    p.add_argument("--worker", action="store_true",
                   help="run as one process-fleet worker: build a "
                        "single engine and serve the length-prefixed "
                        "JSON-over-TCP RPC ops instead of HTTP "
                        "(spawned by the --proc_fleet coordinator; "
                        "needs --worker_ready_file)")
    p.add_argument("--worker_ready_file", default=None,
                   help="path the worker writes its "
                        "{port, pid} readiness handshake to")
    p.add_argument("--worker_slot", type=int, default=0,
                   help="the coordinator slot index this worker fills "
                        "(informational: logs/heartbeat labelling)")
    p.add_argument("--drain_timeout_s", type=float, default=30.0,
                   help="graceful-shutdown bound: seconds SIGTERM/"
                        "SIGINT (and proc-fleet coordinator shutdown) "
                        "waits for in-flight requests before exiting")
    p.add_argument("--procfleet_rpc_deadline_s", type=float, default=15.0,
                   help="per-op RPC deadline the coordinator gives a "
                        "worker call (connect + send + response)")
    p.add_argument("--procfleet_rpc_retries", type=int, default=3,
                   help="transport-failure retries per RPC call "
                        "(exponential backoff + jitter under the "
                        "deadline; mutating ops never retry after "
                        "their bytes were sent)")
    p.add_argument("--procfleet_spawn_timeout_s", type=float,
                   default=180.0,
                   help="seconds a spawned worker may take to become "
                        "ready before the slot books a crash")
    p.add_argument("--procfleet_respawn_backoff_s", type=float,
                   default=0.25,
                   help="initial per-slot respawn backoff after a "
                        "worker death (doubles per consecutive crash)")
    p.add_argument("--procfleet_crash_window_s", type=float, default=60.0,
                   help="crash-loop window: crashes older than this "
                        "stop counting toward the breaker")
    p.add_argument("--procfleet_crash_limit", type=int, default=3,
                   help="crashes inside the window that trip the "
                        "slot's crash-loop breaker (the fleet gives "
                        "the slot up and degrades capacity)")
    p.add_argument("--fleet_shed_goodput", type=float, default=0.5,
                   help="shed batch-class requests while the aggregate "
                        "windowed goodput ratio is below this "
                        "(0 disarms the goodput signal)")
    p.add_argument("--fleet_shed_queue", type=int, default=0,
                   help="shed batch-class requests while the aggregate "
                        "queued-request count is at/above this "
                        "(0 disarms the queue-depth signal)")
    p.add_argument("--fleet_probe_interval_s", type=float, default=0.05,
                   help="supervisor health-probe / collection period")
    p.add_argument("--fleet_heartbeat_stale_s", type=float, default=5.0,
                   help="replica heartbeat age that marks it unroutable "
                        "(fleet mode writes per-replica heartbeats under "
                        "--heartbeat_dir/replicaN)")
    p.add_argument("--fleet_restart_s", type=float, default=0.0,
                   help="auto-revive a killed replica after this many "
                        "seconds (0 = operator restart only)")
    # -- SLO classes + goodput (ISSUE 6; OBSERVABILITY.md) --
    p.add_argument("--slo_interactive_ttft_s", type=float, default=1.0,
                   help="interactive-class TTFT target scored at finish "
                        "(payload slo_class=interactive; 0 disarms)")
    p.add_argument("--slo_interactive_itl_s", type=float, default=0.25,
                   help="interactive-class mean inter-token-gap target "
                        "(0 disarms)")
    p.add_argument("--slo_batch_latency_s", type=float, default=30.0,
                   help="batch-class end-to-end latency target "
                        "(payload slo_class=batch; 0 disarms)")
    p.add_argument("--slo_window", type=int, default=256,
                   help="finished SLO-classed requests in the windowed "
                        "goodput gauge egpt_serve_slo_goodput_ratio")
    # -- telemetry (ISSUE 3; OBSERVABILITY.md) --
    p.add_argument("--journey_keep", type=int, default=512,
                   help="flight recorder: retain the last N finished "
                        "request timelines (GET /requests, "
                        "GET /request?rid=N, per-request debug blocks "
                        "and the egpt_serve_slo_miss_cause_total "
                        "attribution ride it; 0 disarms)")
    p.add_argument("--series_interval_s", type=float, default=1.0,
                   help="time-series store sampling cadence: one "
                        "registry sample + alert-rule evaluation per "
                        "interval (GET /series, GET /alerts; 0 disarms "
                        "the store and the burn-rate alerts)")
    p.add_argument("--series_keep", type=int, default=512,
                   help="time-series ring length in samples (bounded "
                        "retention: keep x interval seconds of history; "
                        "0 disarms)")
    p.add_argument("--trace_buffer", type=int, default=65536,
                   help="request/step trace ring capacity in events "
                        "(GET /trace snapshots it; 0 disarms tracing)")
    p.add_argument("--trace_out", default=None,
                   help="write the trace ring as Chrome trace events "
                        "(Perfetto / chrome://tracing) at shutdown")
    p.add_argument("--profile_dir", default=None,
                   help="destination for POST /profile jax.profiler "
                        "captures; setting it also arms the per-segment "
                        "profiler annotations (unset: captures go to a "
                        "temp dir)")
    p.add_argument("--no_telemetry", action="store_true",
                   help="disarm the metrics registry and the span tracer "
                        "(A/B switch; chains are byte-identical either "
                        "way — the registry just stops counting)")
    p.add_argument("--faults", default=None,
                   help="arm deterministic fault injection, e.g. "
                        "'serve.step:n=5' (see eventgpt_tpu/faults.py; "
                        "EGPT_FAULTS env var equivalent)")
    p.add_argument("--mesh_data", type=int, default=1)
    p.add_argument("--mesh_fsdp", type=int, default=1)
    p.add_argument("--mesh_model", type=int, default=1)
    # prepare_model (shared with infer/eval CLIs) reads these:
    p.add_argument("--use_event_qformer", action="store_true")
    p.add_argument("--pretrain_query_embedder", default=None)
    p.add_argument("--pretrain_attention_layers", default=None)
    return p


def main(argv=None):
    p = build_parser()
    args = p.parse_args(argv)

    if args.worker:
        # Process-fleet worker (ISSUE 11): one engine, RPC instead of
        # HTTP. serve_worker installs its own SIGTERM/SIGINT handlers
        # (stop -> engine.shutdown -> exit 0).
        if not args.worker_ready_file:
            p.error("--worker requires --worker_ready_file")
        from eventgpt_tpu.fleet_proc import serve_worker

        _, engine = build_engine(args, force_single=True)
        return serve_worker(engine, args.worker_ready_file)

    httpd, engine = build_server(args)
    host, port = httpd.server_address[:2]
    print(f"[serve] listening on http://{host}:{port} "
          f"(max_batch={args.max_batch}, chunk={args.chunk})")

    # Graceful drain (ISSUE 11 satellite): SIGTERM/SIGINT stop
    # ADMISSION (the accept loop), let in-flight requests finish
    # (bounded by --drain_timeout_s) so their handler threads write
    # complete responses, then exit 0 — a signal mid-decode no longer
    # kills committed work. httpd.shutdown() must run off the signal
    # handler's thread (it joins the serve_forever loop).
    import signal as _signal

    got_signal = threading.Event()

    def _on_signal(signum, frame):
        got_signal.set()
        threading.Thread(target=httpd.shutdown, daemon=True).start()

    _signal.signal(_signal.SIGTERM, _on_signal)
    _signal.signal(_signal.SIGINT, _on_signal)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if got_signal.is_set():
            deadline = time.monotonic() + args.drain_timeout_s
            print("[serve] draining in-flight requests "
                  f"(<= {args.drain_timeout_s:.0f}s)")
            while time.monotonic() < deadline:
                s = engine.stats()
                if not (s.get("active_rows", 0) or s.get("queued", 0)):
                    break
                time.sleep(0.05)
            # One breath for handler threads to finish writing the
            # responses of requests that just left the engine.
            time.sleep(0.25)
        engine.shutdown()
        httpd.server_close()
        if got_signal.is_set():
            print("[serve] drained, exiting")


if __name__ == "__main__":
    main()
