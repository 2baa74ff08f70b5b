"""Process-fleet serving: worker processes behind an RPC coordinator
(ISSUE 11).

PR 7's ``Fleet`` runs N replicas as THREADS in one process — one
weight tree, one jax runtime, one failure domain: a process death (the
exact event SIGKILL chaos injects here) kills every replica at once.
This module crosses the process boundary, step 1 of ROADMAP item 1: a
``ProcFleet`` coordinator with the same client surface as ``Fleet``
(so ``cli.serve.make_handler`` serves it unchanged) that spawns N
worker PROCESSES, each owning a full ``ServingEngine`` + model + jax
runtime, and talks to them over the minimal length-prefixed
JSON-over-TCP RPC in ``rpc.py``. No jax collectives cross the
boundary — each worker has its own device state — so the whole tier
runs in tier-1 on CPU, and ``export_requests``-over-RPC is the exact
seam the later prefill/decode KV handoff (DistServe / Splitwise) will
reuse: today the drain moves a request's RECORD, the disaggregated
tier will move its record plus KV.

Robustness is the headline, in four layers:

1. **Every RPC edge is bounded.** Per-op deadlines, bounded
   exponential backoff + jitter, mutating ops never blind-retried
   (``rpc.call``). Fault sites ``procfleet.rpc`` (a trip is a
   transport failure the retry loop must absorb), ``procfleet.spawn``
   (a trip fails that spawn attempt — the backoff/respawn path
   handles it) and ``procfleet.worker_kill`` (the trip IS the scripted
   SIGKILL of the busiest worker) make every layer chaos-testable.
2. **Liveness is observed three ways**: heartbeat files (each worker
   writes the trainer-format beat under ``--heartbeat_dir/replicaN``,
   the PR 7 convention), RPC probe timeouts (lock-free ops only — a
   worker busy compiling is SLOW, not DEAD), and ``Popen.poll()`` exit
   codes. A stale/unreachable worker is DRAINED while it still
   answers: ``export_requests`` over RPC strips its queued + in-flight
   requests and re-routes them mid-decode (committed tokens discarded;
   greedy chains are deterministic per request, so the survivor's
   chain is byte-identical to an uninterrupted run — the PR 7 bar). A
   hard-dead worker (SIGKILL) gets the REDO path: the coordinator
   re-submits from its own records, and the journey recorder charges
   the abandoned assignment's wall time to ``failover_redo_s``
   (``worker_lost`` / ``respawn`` joined ``EVENT_KINDS`` for this).
3. **Respawn with a crash-loop breaker.** A dead slot respawns after a
   per-slot exponential backoff; K crashes inside ``crash_window_s``
   trip the slot's breaker — the fleet gives the slot up and degrades
   capacity instead of burning CPU on a doomed spawn loop. ``/health``
   stays green while ≥ 1 worker is routable.
4. **Shutdown drains.** The coordinator waits (bounded) for in-flight
   requests, then asks every worker to shut down over RPC before
   escalating to terminate/kill.

Prefix-affinity routing reuses ``fleet.affinity_key`` verbatim (the
``PrefixCache``'s own identity), so a session keeps hitting the worker
whose radix cache holds its head. Per-worker component bytes surface
through ``/fleet`` and ``GET /memory`` — each worker reports its OWN
process ledger (unlike the thread fleet there is no shared tree: N
processes = N weight copies, the honest cost of the failure-domain
boundary).

Cross-process clocks: ``perf_counter`` is per-process, so the
coordinator stitches journeys from DURATIONS, not absolute stamps —
the final assignment's worker-measured phase decomposition plus
``failover_redo_s`` = (coordinator time of the final assignment −
coordinator submit time). The phase-sum invariant (phases sum to the
reported e2e exactly) holds by construction; RPC transport time on the
final assignment lands in the small gap between the journey's e2e and
the client-observed wall time (documented, not hidden).

Streaming: the coordinator's streams are DELIVER-AT-FINISH (one
cumulative delta + the terminal sentinel). Nothing leaves the process
before the request is terminal, which is exactly why — unlike the
in-process fleet — streamed requests CAN fail over here.

Prefill/decode disaggregation (ISSUE 17): ``--proc_fleet_roles P:D``
splits the fleet into PREFILL workers (chunked/batched admission only
— their scheduler never dispatches a decode segment) and DECODE
workers. New requests route to the prefill pool (prefix affinity
unchanged — the radix caches live where the prompts land); when a
prefill worker finishes admission it gathers the request's paged block
run (the PR 16 spill record: block-table-named KV at SEQ_BUCKET grain
+ int8 scale planes + sampling state + the closed prefill-leg journey)
into a handoff outbox. The coordinator's supervisor pumps that outbox:
``collect_handoffs`` pulls records over the raw-binary RPC frame (KV
bytes ride verbatim, no b64 inflation), ``import_handoff`` ships each
to the decode worker with the most free block-pool bytes, and
``ack_handoffs`` releases the prefill side's replay copy only after
the ship lands. Every ship attempt probes the ``procfleet.handoff``
fault site; a failed attempt retries against other decode workers
(bounded by ``handoff_retries``) and then falls back to the REDO path
— never a double splice: the decode handler dedups imports on the
coordinator-assigned ``hid`` token, so a retried ship whose first ack
was lost re-serves the same worker rid. Greedy chains are
byte-identical to a colocated run (the splice rides the same paged
admission executable). Journeys stitch THREE legs from durations:
prefill phases + ``handoff_s`` (coordinator collect->import wall time)
+ decode phases + ``failover_redo_s``, exact-sum as ever.

A jax-free STUB worker (``python -m eventgpt_tpu.fleet_proc
--stub_worker``) serves the same RPC surface over a deterministic fake
engine, so the coordinator's spawn/retry/respawn/crash-loop logic is
testable in milliseconds; the chain-identity and SIGKILL chaos tests
run real ``cli.serve --worker`` processes.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from eventgpt_tpu import faults, rpc
from eventgpt_tpu.fleet import affinity_key
from eventgpt_tpu.obs import journey as obs_journey
from eventgpt_tpu.obs import metrics as obs_metrics
from eventgpt_tpu.obs import series as obs_series
from eventgpt_tpu.obs import trace as obs_trace

def _map_remote(e: rpc.RpcRemoteError) -> Exception:
    """Remote exception type name -> the local exception the serving
    stack's callers already handle."""
    if e.type_name == "QueueFullError":
        # Re-raise as the REAL engine exception so make_handler's
        # except clause catches it (lazy import: jax-heavy module).
        from eventgpt_tpu.serve import QueueFullError

        return QueueFullError(e.remote_msg)
    if e.type_name == "ValueError":
        return ValueError(e.remote_msg)
    return RuntimeError(f"worker error: {e}")


# -- worker side -----------------------------------------------------------

class WorkerHandler:
    """The RPC op table over one ``ServingEngine`` (or the test stub).

    Ops: submit_ids / try_result / try_results / try_status / cancel /
    export_requests / snapshot / stats / memory / journey / set_prefix /
    ping / shutdown / collect_handoffs / ack_handoffs / import_handoff.

    ``try_result`` is made IDEMPOTENT here: the engine pops a delivered
    answer, so a retried poll whose first response was lost would find
    nothing and the request would hang forever. Delivered results are
    kept in a bounded replay cache so the retry re-serves the same
    record (the coordinator-side dedup key is the rid).

    The handoff ops get the same treatment from both sides (ISSUE 17):
    ``collect_handoffs`` parks popped records in ``_handoff_unacked``
    and re-serves them until ``ack_handoffs`` — a collect response lost
    to a transport fault replays instead of stranding KV; and
    ``import_handoff`` dedups on the coordinator-assigned ``hid`` in a
    bounded ``_imported`` cache, so a retried ship whose first response
    was lost returns the original rid instead of splicing twice.
    """

    # Lock discipline (egpt-check rule ``lock``): the replay caches are
    # written from concurrent RPC connection threads.
    _GUARDED_BY = {"_delivered": "_lock", "_handoff_unacked": "_lock",
                   "_imported": "_lock"}

    REPLAY_CAP = 4096

    def __init__(self, engine):
        self.engine = engine
        self.stop_event = threading.Event()
        self._lock = threading.Lock()
        self._delivered: Dict[int, dict] = {}
        self._handoff_unacked: Dict[int, dict] = {}
        self._imported: Dict[str, int] = {}

    def _result_record(self, rid: int) -> Optional[dict]:
        with self._lock:
            if rid in self._delivered:
                return self._delivered[rid]
        got = self.engine.try_result(rid)
        if got is None:
            return None
        tokens, status = got
        rec = {
            "tokens": tokens, "status": status,
            "stats": dict(self.engine.batcher.request_stats.get(rid, {})),
            # The worker-side flight-recorder timeline (phases included
            # once finished): the coordinator stitches failover_redo_s
            # on top of these worker-measured durations.
            "journey": self.engine.journey(rid),
        }
        with self._lock:
            self._delivered[rid] = rec
            while len(self._delivered) > self.REPLAY_CAP:
                self._delivered.pop(next(iter(self._delivered)))
        return rec

    def __call__(self, op: str, p: dict) -> Any:
        eng = self.engine
        if op == "ping":
            return {"pid": os.getpid(), "alive": eng.alive}
        if op == "submit_ids":
            return eng.submit_ids(
                list(p["input_ids"]), p["pixel_values"],
                int(p["max_new_tokens"]),
                deadline_s=p.get("deadline_s"), slo=p.get("slo"))
        if op == "try_result":
            return self._result_record(int(p["rid"]))
        if op == "try_results":
            return {str(rid): self._result_record(int(rid))
                    for rid in p["rids"]}
        if op == "try_status":
            return eng.try_status(int(p["rid"]))
        if op == "cancel":
            return eng.cancel(int(p["rid"]))
        if op == "export_requests":
            # kill(): deliver finished work to the replay path, park the
            # scheduler, strip + return every unfinished request — the
            # graceful-drain half of the failover story. The process
            # stays up so the coordinator can still collect
            # finished-but-uncollected answers before shutdown.
            return eng.kill()
        if op == "snapshot":
            s = dict(eng.snapshot())
            s["breaker_open"] = eng.breaker_open()
            s["alive"] = eng.alive
            s["goodput_ratio"] = eng.goodput_ratio()
            s["n_faults"] = eng.n_faults
            s["n_restarts"] = eng.n_restarts
            pc = dict(eng.batcher.prefix_cache_stats())
            pc.pop("entries", None)  # per-entry dumps don't aggregate
            s["prefix_cache"] = pc
            # Active alert rules ride the probe snapshot (ISSUE 15), so
            # the coordinator's /stats can show fleet-wide health state
            # without an extra RPC fan-out per poll.
            s["alerts_active"] = eng.alerts().get("active", [])
            return s
        if op == "stats":
            return eng.stats()
        if op == "memory":
            return eng.memory_stats()
        if op == "series":
            # Time-series pull (ISSUE 15): the worker's own store, ages
            # already duration-aligned to the worker's clock — absolute
            # perf_counter values never cross the process boundary.
            return eng.series(window_s=p.get("window_s"), n=p.get("n"))
        if op == "alerts":
            return eng.alerts()
        if op == "journey":
            return eng.journey(int(p["rid"]))
        if op == "set_prefix":
            return eng.set_prefix(p["prefix_prompt"],
                                  p.get("pixel_values"))
        if op == "collect_handoffs":
            # Prefill role: drain the engine's outbox into the replay
            # dict, then serve EVERYTHING unacked — a coordinator whose
            # previous collect response was lost sees the same records
            # again (delivery is at-least-once; the decode side's hid
            # dedup makes the re-ship idempotent).
            fresh = (eng.collect_handoffs()
                     if hasattr(eng, "collect_handoffs") else [])
            now = time.perf_counter()
            with self._lock:
                for rec in fresh:
                    self._handoff_unacked[int(rec["rid"])] = rec
                out = []
                for rec in self._handoff_unacked.values():
                    # Refresh elapsed_s with the outbox wait at every
                    # serve (stored record untouched — replays refresh
                    # again), and keep the worker-local stamp off the
                    # wire: only durations cross processes.
                    wire = {k: v for k, v in rec.items()
                            if k != "t_gather"}
                    if rec.get("t_gather") is not None:
                        wire["elapsed_s"] = (
                            (rec.get("elapsed_s") or 0.0)
                            + (now - rec["t_gather"]))
                    out.append(wire)
                return out
        if op == "ack_handoffs":
            with self._lock:
                for rid in p["rids"]:
                    self._handoff_unacked.pop(int(rid), None)
            return True
        if op == "import_handoff":
            hid = str(p["hid"])
            with self._lock:
                if hid in self._imported:
                    return self._imported[hid]
            rid = eng.import_handoff(
                list(p["input_ids"]), int(p["max_new_tokens"]), p["rec"],
                tokens=list(p.get("tokens") or ()),
                prompt_len=int(p.get("prompt_len", 0)),
                deadline_s=p.get("deadline_s"), slo=p.get("slo"),
                elapsed_s=float(p.get("elapsed_s") or 0.0),
                ttft_s=p.get("ttft_s"))
            with self._lock:
                self._imported[hid] = rid
                while len(self._imported) > self.REPLAY_CAP:
                    self._imported.pop(next(iter(self._imported)))
            return rid
        if op == "shutdown":
            self.stop_event.set()
            return True
        raise ValueError(f"unknown rpc op {op!r}")


def _write_ready_file(path: str, port: int) -> None:
    """Atomic readiness handshake: the coordinator polls for this file
    and reads the worker's ephemeral port from it (tmp + rename, like
    the heartbeat — a half-written file is never observed)."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"port": port, "pid": os.getpid()}, f)
    os.replace(tmp, path)


def serve_worker(engine, ready_file: str) -> int:
    """Run one worker's RPC server until a ``shutdown`` op (or
    SIGTERM/SIGINT) arrives; returns the process exit code. The
    engine's own heartbeat thread (``--heartbeat_dir``) keeps beating
    the whole time — that file is the coordinator's liveness signal."""
    handler = WorkerHandler(engine)
    server = rpc.RpcServer(handler)

    def _on_signal(signum, frame):
        handler.stop_event.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    _write_ready_file(ready_file, server.port)
    handler.stop_event.wait()
    # Graceful exit: settle the engine (drains the in-flight segment)
    # before the RPC server goes away, so a drain-then-shutdown
    # coordinator never races the parked scheduler.
    try:
        engine.shutdown()
    finally:
        server.stop()
    return 0


# -- the test stub (jax-free worker) ---------------------------------------

class _StubBatcher:
    """The minimal ``engine.batcher`` surface ``WorkerHandler`` reads."""

    def __init__(self):
        self.request_stats: Dict[int, dict] = {}

    def prefix_cache_stats(self) -> dict:
        return {"enabled": False}


class _StubEngine:
    """Deterministic jax-free fake of the ``ServingEngine`` surface the
    RPC worker exposes: request ``(ids, budget)`` "decodes" to
    ``[(sum(ids) + k) % 251 for k in range(budget)]`` after
    ``token_delay_s`` per token — the same function in every process,
    so coordinator failover tests can assert chain identity without
    paying a jax import. Used by ``--stub_worker`` mode only.

    Role support (ISSUE 17): a ``prefill`` stub "admits" a request in
    one ``token_delay_s`` and moves it to the handoff outbox with a
    deterministic ndarray "KV" payload (the input ids verbatim — it
    crosses the raw-binary RPC frame, and the decode stub REJECTS a
    corrupted array, so stub fleet tests assert bit-exact transport);
    a ``decode`` stub's ``import_handoff`` enqueues the request like a
    submit, finishing with the SAME chain function — byte-identical to
    a colocated stub run."""

    _GUARDED_BY = {"_reqs": "_lock", "_done": "_lock",
                   "_handoffs": "_lock"}

    def __init__(self, token_delay_s: float = 0.005,
                 role: str = "colocated"):
        self.token_delay_s = float(token_delay_s)
        self.role = role
        self.batcher = _StubBatcher()
        self.alive = True
        self.n_faults = 0
        self.n_restarts = 0
        self._lock = threading.Lock()
        self._next_rid = 0
        self._reqs: Dict[int, dict] = {}   # live: rid -> record
        self._done: Dict[int, tuple] = {}  # finished: rid -> (toks, st)
        self._handoffs: List[dict] = []    # prefill role: the outbox
        self.handoffs_gathered = 0
        self.handoffs_spliced = 0
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def submit_ids(self, ids, pixels, max_new_tokens, stream=False,
                   deadline_s=None, slo=None) -> int:
        if not self.alive:
            raise RuntimeError("stub engine is down (killed)")
        obs_series.note_submit()
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            self._reqs[rid] = {
                "rid": rid, "ids": list(ids), "pixels": pixels,
                "budget": int(max_new_tokens), "t0": time.perf_counter(),
                "deadline_s": deadline_s, "slo": slo,
            }
        return rid

    def _chain(self, ids, budget) -> List[int]:
        s = sum(int(t) for t in ids)
        return [(s + k) % 251 for k in range(budget)]

    def _loop(self) -> None:
        while True:
            time.sleep(self.token_delay_s)
            now = time.perf_counter()
            with self._lock:
                if not self.alive:
                    continue
                for rid, r in list(self._reqs.items()):
                    if self.role == "prefill":
                        # Admission-only: one token_delay_s of "prefill"
                        # moves the request to the outbox — never a
                        # decode. The fake KV plane is the ids verbatim
                        # (int32), so the raw-frame transport is
                        # asserted bit-exact at the decode stub.
                        if now - r["t0"] < self.token_delay_s:
                            continue
                        self._reqs.pop(rid)
                        self.handoffs_gathered += 1
                        self._handoffs.append({
                            "rid": rid,
                            "input_ids": list(r["ids"]),
                            "tokens": [],
                            "max_new_tokens": r["budget"],
                            "prompt_len": len(r["ids"]),
                            "deadline_s": r["deadline_s"],
                            "slo": r["slo"],
                            "preempts": 0,
                            "journey": None,
                            "rec": {
                                "n_blocks": 1, "n_total": 1,
                                "length": len(r["ids"]),
                                "nbytes_kv": 4 * len(r["ids"]),
                                "kv": np.asarray(r["ids"], np.int32),
                            },
                        })
                        continue
                    if now - r["t0"] >= self.token_delay_s * r["budget"]:
                        self._reqs.pop(rid)
                        self._done[rid] = (
                            self._chain(r["ids"], r["budget"]), "ok")
                        self.batcher.request_stats[rid] = {
                            "latency_s": now - r["t0"], "slo_met": True}

    def collect_handoffs(self) -> List[dict]:
        with self._lock:
            out, self._handoffs = self._handoffs, []
            return out

    def import_handoff(self, input_ids, max_new_tokens, rec,
                       tokens=(), prompt_len=0, deadline_s=None,
                       slo=None, elapsed_s=0.0, ttft_s=None) -> int:
        if not self.alive:
            raise RuntimeError("stub engine is down (killed)")
        kv = rec.get("kv")
        if kv is not None and np.asarray(kv).tolist() != \
                [int(t) for t in input_ids]:
            # The transport contract IS the test: a handoff whose KV
            # plane didn't survive the raw frame bit-exact must refuse
            # the splice, not decode garbage.
            raise ValueError("stub handoff KV plane corrupted in transit")
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            self.handoffs_spliced += 1
            self._reqs[rid] = {
                "rid": rid, "ids": list(input_ids), "pixels": None,
                "budget": int(max_new_tokens), "t0": time.perf_counter(),
                "deadline_s": deadline_s, "slo": slo,
            }
        return rid

    def try_result(self, rid):
        with self._lock:
            return self._done.pop(rid, None)

    def try_status(self, rid):
        return None

    def cancel(self, rid) -> bool:
        with self._lock:
            return self._reqs.pop(rid, None) is not None

    def kill(self) -> list:
        with self._lock:
            self.alive = False
            recs = [{"rid": r["rid"], "input_ids": r["ids"],
                     "pixel_values": r["pixels"],
                     "max_new_tokens": r["budget"],
                     "deadline_s": r["deadline_s"], "slo": r["slo"]}
                    for r in self._reqs.values()]
            self._reqs.clear()
            return recs

    def breaker_open(self) -> bool:
        return not self.alive

    def goodput_ratio(self) -> float:
        return 1.0

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "active_rows": len(self._reqs), "queued": 0,
                "slo": {}, "memory": {}, "role": self.role,
                # Fake pool headroom that shrinks with load, so the
                # decode-placement policy is exercised at stub speed.
                "kv_free_bytes": (1 << 20) - 4096 * len(self._reqs),
                "kv_free_blocks": 256 - len(self._reqs),
                "handoff": {
                    "pending": len(self._handoffs),
                    "gathered": self.handoffs_gathered,
                    "gathered_bytes": 0,
                    "spliced": self.handoffs_spliced,
                    "spliced_bytes": 0,
                },
            }

    def stats(self) -> dict:
        return {"stub": True, **self.snapshot()}

    def memory_stats(self) -> dict:
        return {"stub": True}

    def series(self, window_s=None, n=None) -> dict:
        # The stub worker arms a REAL store (series.py is jax-free), so
        # the procfleet aggregation tests exercise the genuine RPC +
        # merge path at stub speed.
        return obs_series.snapshot(window_s=window_s, n=n)

    def alerts(self) -> dict:
        return obs_series.alerts()

    def journey(self, rid):
        return None

    def set_prefix(self, prompt, pixels=None) -> int:
        return 0

    def shutdown(self) -> None:
        self.alive = False


def _stub_main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--stub_worker", action="store_true")
    p.add_argument("--worker_ready_file", required=True)
    p.add_argument("--worker_slot", type=int, default=0)
    p.add_argument("--heartbeat_dir", default=None)
    p.add_argument("--token_delay_s", type=float, default=0.005)
    p.add_argument("--role", default="colocated",
                   choices=("colocated", "prefill", "decode"))
    args = p.parse_args(argv)
    # A real (tiny) time-series store per stub worker: the aggregation
    # tests assert over genuine sampled rings, not canned dicts.
    obs_series.configure(interval_s=0.02, keep=256)
    engine = _StubEngine(token_delay_s=args.token_delay_s,
                         role=args.role)
    if args.heartbeat_dir:
        from eventgpt_tpu.train.resilience import Heartbeat

        hb = Heartbeat(args.heartbeat_dir)

        def _beat():
            n = 0
            while True:
                try:
                    hb.beat(n, status="ok")
                except OSError:
                    pass
                n += 1
                time.sleep(0.2)

        threading.Thread(target=_beat, daemon=True).start()
    return serve_worker(engine, args.worker_ready_file)


# -- coordinator -----------------------------------------------------------

@dataclass
class _ProcRequest:
    """One request the coordinator owns end to end (the process-fleet
    twin of ``fleet._FleetRequest``). ``worker``/``rid`` are the
    CURRENT assignment; ``t_assign`` is the coordinator-clock stamp of
    that assignment (the redo-cost anchor — worker clocks are not
    comparable across processes)."""
    frid: int
    input_ids: List[int]
    pixel_values: Any
    max_new_tokens: int
    deadline: Optional[float]          # absolute coordinator perf_counter
    slo: Any
    key: tuple
    stream: bool
    worker: int
    rid: int
    t_submit: float
    t_assign: float
    failovers: int = 0
    done: threading.Event = field(default_factory=threading.Event)
    tokens: Optional[List[int]] = None
    status: str = "ok"
    stats: Dict[str, float] = field(default_factory=dict)
    stream_q: Any = None
    # Disaggregation (ISSUE 17): the closed prefill-leg phase
    # decomposition (rides the handoff record) and the coordinator-
    # measured collect->import wall time — both stitched into the final
    # journey. Reset on failover: a REDO restarts the whole chain, and
    # only the FINAL chain's legs may sum into the timeline.
    prefill_phases: Optional[Dict[str, float]] = None
    handoff_s: float = 0.0


@dataclass
class WorkerSlot:
    """One supervised worker-process slot. ``state`` drives
    routability: only ``ok`` slots receive work. Single-writer from
    the supervisor thread in steady state (the documented Replica
    exception from PR 7/8 — operator kill/drain transitions are
    idempotent); cross-object fields are outside the lock detector's
    static scope either way."""
    idx: int
    proc: Optional[subprocess.Popen] = None
    addr: Optional[Tuple[str, int]] = None
    # colocated | prefill | decode (fixed at fleet construction: a
    # slot's role survives respawn — the topology is static)
    role: str = "colocated"
    # starting | ok | suspect | draining | dead | failed
    state: str = "starting"
    generation: int = 0                # spawn attempts (ready-file key)
    t_spawn: float = 0.0               # monotonic spawn start
    t_dead: float = 0.0
    t_respawn: float = 0.0             # monotonic: respawn allowed after
    crashes: List[float] = field(default_factory=list)  # monotonic stamps
    consec_crashes: int = 0
    kills: int = 0                     # operator/chaos kills + drains
    inflight: int = 0                  # coordinator-side assigned count
    snapshot: Dict[str, Any] = field(default_factory=dict)
    ready_file: str = ""
    hb_dir: Optional[str] = None
    log_path: str = ""
    respawn_frids: List[int] = field(default_factory=list)

    @property
    def routable(self) -> bool:
        return self.state == "ok"


class _ProcRequestStats:
    """``.get(frid)`` view over finished requests — the shape
    ``make_handler`` expects of ``engine.batcher.request_stats``."""

    def __init__(self, fleet: "ProcFleet"):
        self._fleet = fleet

    def get(self, frid: int, default=None):
        freq = self._fleet._requests.get(frid)
        if freq is None or not freq.done.is_set():
            return default if default is not None else {}
        return freq.stats


class _ProcBatcherView:
    """The minimal ``engine.batcher`` surface the HTTP handler reads,
    aggregated across worker snapshots (one RPC-free read: the
    supervisor refreshes snapshots every probe tick)."""

    def __init__(self, fleet: "ProcFleet"):
        self._fleet = fleet
        self.request_stats = _ProcRequestStats(fleet)

    def prefix_cache_stats(self) -> Dict[str, Any]:
        per = []
        hits = misses = 0
        for slot in self._fleet.slots:
            st = dict(slot.snapshot.get("prefix_cache", {}))
            per.append({"worker": slot.idx, **st})
            hits += st.get("hits", 0)
            misses += st.get("misses", 0)
        return {
            "enabled": any(p.get("enabled") for p in per),
            "hits": hits,
            "misses": misses,
            "hit_ratio": hits / (hits + misses) if (hits + misses) else 0.0,
            "workers": per,
        }

    def slo_stats(self) -> Dict[str, Any]:
        return self._fleet.slo_stats()


class ProcFleet:
    """Coordinator over N worker processes with the client surface of
    a ``ServingEngine`` (submit / result / status / cancel /
    stream_queue / stats / breaker_open / set_prefix), so
    ``cli.serve.make_handler`` serves a process fleet unchanged. See
    the module docstring for the robustness layers.

    Lock discipline (egpt-check rule ``lock``): same contract as
    ``Fleet`` — the routing table and request-map WRITES mutate under
    ``_lock``; ``/w`` attributes are read lock-free by design
    (``result`` must not hold the lock while waiting). RPC submits
    happen under the lock (the fleet -> worker "lock order": workers
    never call back into the coordinator, so it cannot invert);
    collection/probe RPCs run outside it. ``WorkerSlot`` fields are
    the documented single-writer exception (supervisor thread), like
    ``fleet.Replica.state``."""

    _GUARDED_BY = {
        # full guard: routing/bookkeeping state with compound updates
        "_pins": "_lock",
        "_next_frid": "_lock",
        # writes locked; lock-free reads are the snapshot/flag pattern
        "_requests": "_lock/w",
        "n_requests": "_lock/w",
        "n_failovers": "_lock/w",
        "n_deaths": "_lock/w",
        "n_respawns": "_lock/w",
        "n_kills": "_lock/w",
        "n_crash_looped": "_lock/w",
        "n_handoffs": "_lock/w",
        "n_handoff_bytes": "_lock/w",
        "n_handoff_retries": "_lock/w",
        "n_handoff_redos": "_lock/w",
        "fault": "_lock/w",
    }

    def __init__(self, worker_cmd: Sequence[str], n_workers: int,
                 tokenizer=None, conv_mode: str = "eventgpt_v1",
                 workdir: Optional[str] = None,
                 heartbeat_dir: Optional[str] = None,
                 probe_interval_s: float = 0.05,
                 heartbeat_stale_s: float = 5.0,
                 rpc_deadline_s: float = 15.0,
                 rpc_retries: int = 3,
                 drain_deadline_s: float = 30.0,
                 spawn_timeout_s: float = 120.0,
                 respawn_backoff_s: float = 0.25,
                 respawn_backoff_max_s: float = 10.0,
                 crash_window_s: float = 60.0,
                 crash_limit: int = 3,
                 max_failovers: int = 3,
                 shutdown_drain_s: float = 30.0,
                 roles: Optional[str] = None,
                 handoff_retries: int = 3):
        if n_workers < 1:
            raise ValueError("a process fleet needs at least one worker")
        # Disaggregated topology (ISSUE 17): "P:D" fixes the first P
        # slots as prefill workers, the rest as decode. None keeps
        # every slot colocated — the default topology, byte-for-byte
        # the pre-disaggregation fleet.
        self.roles: Optional[Tuple[int, int]] = None
        if roles:
            p_str, sep, d_str = str(roles).partition(":")
            try:
                if not sep:
                    raise ValueError(roles)
                n_p, n_d = int(p_str), int(d_str)
            except ValueError:
                raise ValueError(
                    f"bad proc_fleet_roles {roles!r} (want P:D, e.g. 1:1)")
            if n_p < 1 or n_d < 1:
                raise ValueError(
                    f"proc_fleet_roles {roles!r}: a disaggregated fleet "
                    f"needs at least one prefill AND one decode worker")
            if n_p + n_d != n_workers:
                raise ValueError(
                    f"proc_fleet_roles {roles!r}: {n_p}+{n_d} workers "
                    f"!= fleet size {n_workers}")
            self.roles = (n_p, n_d)
        self.handoff_retries = int(handoff_retries)
        self.worker_cmd = list(worker_cmd)
        self.tokenizer = tokenizer
        self.conv_mode = conv_mode
        self.probe_interval_s = float(probe_interval_s)
        self.heartbeat_stale_s = float(heartbeat_stale_s)
        self.rpc_deadline_s = float(rpc_deadline_s)
        self.rpc_retries = int(rpc_retries)
        self.drain_deadline_s = float(drain_deadline_s)
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.respawn_backoff_s = float(respawn_backoff_s)
        self.respawn_backoff_max_s = float(respawn_backoff_max_s)
        self.crash_window_s = float(crash_window_s)
        self.crash_limit = int(crash_limit)
        self.max_failovers = int(max_failovers)
        self.shutdown_drain_s = float(shutdown_drain_s)
        if workdir is None:
            import tempfile

            self._tmpdir = tempfile.TemporaryDirectory(
                prefix="egpt_procfleet_")
            workdir = self._tmpdir.name
        else:
            self._tmpdir = None
            os.makedirs(workdir, exist_ok=True)
        self.workdir = workdir
        self.heartbeat_root = heartbeat_dir
        self._lock = threading.Lock()
        self._requests: Dict[int, _ProcRequest] = {}
        self._pins: Dict[tuple, int] = {}
        self._next_frid = 0
        self._stop = False
        self.t_start = time.time()
        self.n_requests = 0
        self.n_failovers = 0
        self.n_deaths = 0
        self.n_respawns = 0
        self.n_kills = 0
        self.n_crash_looped = 0
        self.n_handoffs = 0
        self.n_handoff_bytes = 0
        self.n_handoff_retries = 0
        self.n_handoff_redos = 0
        # Serializes collect->ship->ack per pump pass: the supervisor's
        # periodic pump and a drain's flush pump must not ship the same
        # replayed record concurrently (the hid dedup would still
        # prevent a double splice, but the bookkeeping would race).
        self._pump_lock = threading.Lock()
        self.fault: Any = None
        self._journey_owner = obs_journey.register_owner("procfleet")
        self.slots = [self._make_slot(i) for i in range(n_workers)]
        obs_metrics.PROCFLEET_WORKERS.set(n_workers)
        for slot in self.slots:
            self._spawn(slot)
        self._wait_boot()
        self._thread = threading.Thread(target=self._supervise, daemon=True)
        self._thread.start()

    # -- spawning ----------------------------------------------------------

    def _make_slot(self, idx: int) -> WorkerSlot:
        hb = (os.path.join(self.heartbeat_root, f"replica{idx}")
              if self.heartbeat_root else None)
        role = "colocated"
        if self.roles is not None:
            role = "prefill" if idx < self.roles[0] else "decode"
        return WorkerSlot(idx=idx, hb_dir=hb, role=role,
                          log_path=os.path.join(self.workdir,
                                                f"worker{idx}.log"))

    def _spawn(self, slot: WorkerSlot) -> bool:
        """Launch one worker process into ``slot`` (state ->
        ``starting``; readiness is polled by the supervisor). A
        ``procfleet.spawn`` trip fails THIS attempt — it is booked as a
        crash so the backoff/breaker policy governs retries, exactly
        like a real exec failure."""
        slot.generation += 1
        slot.ready_file = os.path.join(
            self.workdir, f"worker{slot.idx}.g{slot.generation}.ready")
        cmd = self.worker_cmd + [
            "--worker_ready_file", slot.ready_file,
            "--worker_slot", str(slot.idx),
        ]
        if slot.role != "colocated":
            cmd += ["--role", slot.role]
        if slot.hb_dir:
            cmd += ["--heartbeat_dir", slot.hb_dir]
        try:
            faults.maybe_fail("procfleet.spawn")
            faults.maybe_delay("procfleet.spawn")
            log = open(slot.log_path, "ab")
            try:
                slot.proc = subprocess.Popen(
                    cmd, stdout=log, stderr=subprocess.STDOUT,
                    cwd=os.getcwd())
            finally:
                log.close()
        except (faults.InjectedFault, OSError) as e:
            slot.proc = None
            self._book_crash(slot, f"spawn failed: {e!r}")
            return False
        slot.state = "starting"
        slot.t_spawn = time.monotonic()
        slot.addr = None
        obs_trace.instant("worker_spawn", cat="procfleet")
        return True

    def _wait_boot(self) -> None:
        """Block until every slot left ``starting`` (ready, crashed, or
        spawn-timeout) — at least one must be routable or the fleet
        cannot exist."""
        deadline = time.monotonic() + self.spawn_timeout_s
        while time.monotonic() < deadline:
            for slot in self.slots:
                if slot.state == "starting":
                    self._check_ready(slot)
                elif slot.state == "dead" \
                        and time.monotonic() >= slot.t_respawn:
                    self._maybe_respawn(slot)
            if all(s.state in ("ok", "failed") for s in self.slots):
                break
            time.sleep(0.02)
        self._export_routable_gauge()
        if not any(s.routable for s in self.slots):
            states = {s.idx: s.state for s in self.slots}
            self.shutdown()
            raise RuntimeError(
                f"no worker became routable within {self.spawn_timeout_s}s "
                f"(states: {states}; logs under {self.workdir})")

    def _check_ready(self, slot: WorkerSlot) -> None:
        """Advance a ``starting`` slot: ready file -> addr -> ok; a
        dead process or an expired spawn deadline books a crash."""
        if slot.proc is not None and slot.proc.poll() is not None:
            self._book_crash(
                slot, f"worker {slot.idx} exited rc={slot.proc.returncode} "
                      f"during startup (log: {slot.log_path})")
            return
        if os.path.exists(slot.ready_file):
            try:
                with open(slot.ready_file) as f:
                    info = json.load(f)
                slot.addr = ("127.0.0.1", int(info["port"]))
                self._rpc(slot, "ping", deadline_s=5.0)
            except (OSError, ValueError, KeyError, rpc.RpcError):
                return  # not answering yet: keep polling
            slot.state = "ok"
            slot.consec_crashes = 0
            self._export_routable_gauge()
            return
        if time.monotonic() - slot.t_spawn > self.spawn_timeout_s:
            self._kill_proc(slot)
            self._book_crash(
                slot, f"worker {slot.idx} never became ready within "
                      f"{self.spawn_timeout_s}s")

    def _book_crash(self, slot: WorkerSlot, why: str) -> None:
        """Crash bookkeeping + the crash-loop breaker (robustness layer
        3): K crashes inside the window -> give the slot up for good —
        capacity degrades, the fleet stays up on the others."""
        now = time.monotonic()
        slot.proc = None
        slot.addr = None
        slot.t_dead = now
        slot.crashes.append(now)
        slot.crashes = [t for t in slot.crashes
                        if now - t <= self.crash_window_s]
        slot.consec_crashes += 1
        with self._lock:
            self.fault = why
        if len(slot.crashes) >= self.crash_limit:
            slot.state = "failed"
            with self._lock:
                self.n_crash_looped += 1
            obs_metrics.PROCFLEET_CRASH_LOOPS.inc()
            obs_trace.instant("worker_crash_loop", cat="procfleet")
        else:
            slot.state = "dead"
            backoff = min(
                self.respawn_backoff_s
                * (2.0 ** max(slot.consec_crashes - 1, 0)),
                self.respawn_backoff_max_s)
            slot.t_respawn = now + backoff
        self._export_routable_gauge()

    def _maybe_respawn(self, slot: WorkerSlot) -> None:
        if slot.state != "dead" or time.monotonic() < slot.t_respawn:
            return
        if self._spawn(slot):
            with self._lock:
                self.n_respawns += 1
            obs_metrics.PROCFLEET_RESPAWNS.inc()
            # The respawn is part of the affected requests' story: any
            # request this slot's death re-routed that is STILL live
            # gets the respawn event (the chaos test asserts the
            # worker_lost -> failover -> respawn sequence).
            frids, slot.respawn_frids = slot.respawn_frids, []
            for frid in frids:
                freq = self._requests.get(frid)
                if freq is not None and not freq.done.is_set():
                    obs_journey.event(self._journey_owner, frid,
                                      "respawn", worker=slot.idx)

    def _kill_proc(self, slot: WorkerSlot) -> None:
        if slot.proc is None:
            return
        try:
            slot.proc.kill()
            slot.proc.wait(timeout=5)
        except (OSError, subprocess.TimeoutExpired):
            pass

    # -- rpc helper --------------------------------------------------------

    def _rpc(self, slot: WorkerSlot, op: str,
             payload: Optional[dict] = None, *,
             deadline_s: Optional[float] = None,
             retry_sent: bool = True) -> Any:
        if slot.addr is None:
            raise rpc.RpcError(f"worker {slot.idx} has no address "
                               f"(state {slot.state})")
        return rpc.call(slot.addr, op, payload,
                        deadline_s=(self.rpc_deadline_s
                                    if deadline_s is None else deadline_s),
                        retries=self.rpc_retries, retry_sent=retry_sent)

    # -- client surface ----------------------------------------------------

    @property
    def batcher(self) -> _ProcBatcherView:
        return _ProcBatcherView(self)

    @property
    def n_faults(self) -> int:
        return sum(s.snapshot.get("n_faults", 0) for s in self.slots)

    @property
    def n_restarts(self) -> int:
        return sum(s.snapshot.get("n_restarts", 0) for s in self.slots)

    def breaker_open(self) -> bool:
        """The fleet refuses work only when NO worker is routable —
        one healthy worker keeps /health green (lost capacity shows in
        egpt_procfleet_workers_routable instead). A disaggregated
        fleet needs one routable worker of EACH role: a prefill-only
        fleet can admit but never decode, a decode-only fleet can
        never admit."""
        if self.roles is not None:
            return not (
                any(s.routable and s.role == "prefill"
                    for s in self.slots)
                and any(s.routable and s.role == "decode"
                        for s in self.slots))
        return not any(s.routable for s in self.slots)

    def goodput_ratio(self) -> float:
        met = 0.0
        n = 0
        for slot in self.slots:
            st = slot.snapshot.get("slo", {})
            w = st.get("window_n", 0)
            met += st.get("goodput_ratio", 0.0) * w
            n += w
        return met / n if n else 1.0

    def queue_depth(self) -> int:
        return sum(s.snapshot.get("queued", 0) for s in self.slots)

    def submit(self, query: str, pixels, max_new_tokens: int,
               stream: bool = False, deadline_s: Optional[float] = None,
               slo=None) -> int:
        from eventgpt_tpu.data.conversation import prepare_event_prompt
        from eventgpt_tpu.data.tokenizer import tokenize_with_event

        ids = tokenize_with_event(
            prepare_event_prompt(query, self.conv_mode), self.tokenizer)
        return self.submit_ids(ids, pixels, max_new_tokens, stream=stream,
                               deadline_s=deadline_s, slo=slo)

    def submit_ids(self, input_ids: Sequence[int], pixels,
                   max_new_tokens: int, stream: bool = False,
                   deadline_s: Optional[float] = None, slo=None) -> int:
        """Route one request: affinity -> least-inflight, submit over
        RPC (non-idempotent: never retried after the bytes left — an
        unreachable worker is marked suspect and the NEXT candidate is
        tried instead, so transport trouble costs locality, not
        availability), track for supervision."""
        # Coordinator-side arrival sensing (ISSUE 15): workers only see
        # their routed share, so the fleet-wide EWMA lives here.
        obs_series.note_submit()
        key = affinity_key(input_ids, pixels)
        with self._lock:
            last_err: Optional[Exception] = None
            tried: set = set()
            while True:
                slot, reason = self._route_locked(key, exclude=tried)
                try:
                    rid = self._rpc(
                        slot, "submit_ids",
                        {"input_ids": list(input_ids),
                         "pixel_values": pixels,
                         "max_new_tokens": int(max_new_tokens),
                         "deadline_s": deadline_s, "slo": slo},
                        retry_sent=False)
                    break
                except rpc.RpcRemoteError as e:
                    raise _map_remote(e) from e
                except rpc.RpcError as e:
                    # Transport failure: this worker is suspect (the
                    # supervisor's probe will drain or declare it) —
                    # try the next candidate rather than failing the
                    # client while capacity remains.
                    last_err = e
                    tried.add(slot.idx)
                    slot.state = "suspect"
                    self._export_routable_gauge()
                    if not any(s.routable for s in self.slots):
                        raise RuntimeError(
                            f"no routable worker accepted the submit: "
                            f"{last_err!r}") from e
            t = time.perf_counter()
            frid = self._next_frid
            self._next_frid += 1
            freq = _ProcRequest(
                frid=frid, input_ids=list(input_ids), pixel_values=pixels,
                max_new_tokens=int(max_new_tokens),
                deadline=(t + deadline_s if deadline_s is not None
                          else None),
                slo=slo, key=key, stream=stream, worker=slot.idx, rid=rid,
                t_submit=t, t_assign=t)
            if stream:
                import queue as _queue

                freq.stream_q = _queue.Queue()
            self._requests[frid] = freq
            self._pins[key] = slot.idx
            self.n_requests += 1
            slot.inflight += 1
            obs_metrics.FLEET_ROUTED.inc(reason=reason)
            obs_journey.begin(
                self._journey_owner, frid, t=t, budget=max_new_tokens,
                **({"slo_class": slo.name} if slo is not None else {}))
            obs_journey.event(self._journey_owner, frid, "route", t=t,
                              worker=slot.idx, worker_rid=rid,
                              reason=reason)
        return frid

    def result(self, frid: int, timeout: float = 600.0) -> List[int]:
        freq = self._requests[frid]
        if not freq.done.wait(timeout):
            raise TimeoutError(
                f"procfleet request {frid} did not finish in {timeout}s")
        if freq.tokens is None:
            raise RuntimeError(
                f"procfleet request {frid} failed after {freq.failovers} "
                f"failover(s): {freq.status} ({self.fault})")
        return freq.tokens

    def status(self, frid: int) -> str:
        freq = self._requests.get(frid)
        return freq.status if freq is not None else "ok"

    def worker_of(self, frid: int) -> int:
        return self._requests[frid].worker

    # The thread fleet's name for it: tests drive both through one.
    replica_of = worker_of

    def cancel(self, frid: int) -> bool:
        with self._lock:
            freq = self._requests.get(frid)
            if freq is None or freq.done.is_set():
                return False
            slot = self.slots[freq.worker]
        try:
            return bool(self._rpc(slot, "cancel", {"rid": freq.rid},
                                  deadline_s=5.0))
        except rpc.RpcError:
            return False

    def stream_queue(self, frid: int):
        return self._requests[frid].stream_q

    def set_prefix(self, prefix_prompt: str, pixels=None) -> int:
        """Broadcast the operator prefix insert to every routable
        worker (the fleet-wide POST /prefix contract)."""
        plen = 0
        for slot in self.slots:
            if not slot.routable:
                continue
            try:
                plen = int(self._rpc(slot, "set_prefix",
                                     {"prefix_prompt": prefix_prompt,
                                      "pixel_values": pixels}))
            except rpc.RpcError:
                continue
        return plen

    def slo_stats(self) -> Dict[str, Any]:
        classes: Dict[str, Dict[str, int]] = {}
        for slot in self.slots:
            st = slot.snapshot.get("slo", {})
            for name, c in st.get("classes", {}).items():
                agg = classes.setdefault(name, {"finished": 0, "met": 0})
                agg["finished"] += c["finished"]
                agg["met"] += c["met"]
        for c in classes.values():
            c["attainment"] = (c["met"] / c["finished"]
                               if c["finished"] else 0.0)
        return {"classes": classes, "goodput_ratio": self.goodput_ratio()}

    def stats(self) -> Dict[str, Any]:
        per = []
        for slot in self.slots:
            s = slot.snapshot
            per.append({
                "worker": slot.idx,
                "state": slot.state,
                "role": slot.role,
                "pid": slot.proc.pid if slot.proc else None,
                "active_rows": s.get("active_rows", 0),
                "queued": s.get("queued", 0),
                "inflight": slot.inflight,
                # Disaggregation surface (ISSUE 17): block-pool
                # headroom (the decode-placement signal) and the
                # worker-side handoff counters from the last probe.
                "kv_free_blocks": s.get("kv_free_blocks"),
                "kv_free_bytes": s.get("kv_free_bytes"),
                "handoff": s.get("handoff") or {},
                "faults": s.get("n_faults", 0),
                "restarts": s.get("n_restarts", 0),
                "crashes": len(slot.crashes),
                "kills": slot.kills,
                "goodput_ratio": s.get("slo", {}).get(
                    "goodput_ratio", 0.0),
                "prefix_cache_hit_ratio": s.get("prefix_cache", {}).get(
                    "hit_ratio", 0.0),
                # Per-worker component bytes (each worker is its OWN
                # process: its ledger covers its weights + caches —
                # nothing is shared across the boundary).
                "memory_bytes": sum(
                    s.get("memory", {}).get("owner", {}).values()),
            })
        with self._lock:
            n_pins = len(self._pins)
        return {
            "uptime_s": round(time.time() - self.t_start, 1),
            "requests": self.n_requests,
            "status": "degraded" if self.breaker_open() else "ok",
            "active_rows": sum(p["active_rows"] for p in per),
            "queued": sum(p["queued"] for p in per),
            "fleet": {
                "proc_fleet": True,
                "workers": len(self.slots),
                "routable": sum(s.routable for s in self.slots),
                "pins": n_pins,
                "goodput_ratio": round(self.goodput_ratio(), 4),
                "failovers": self.n_failovers,
                "deaths": self.n_deaths,
                "respawns": self.n_respawns,
                "kills": self.n_kills,
                "crash_looped": self.n_crash_looped,
                # Role topology + handoff totals (ISSUE 17): None/0s
                # on a colocated fleet — the shape is stable so /fleet
                # consumers need no feature detection.
                "roles": (f"{self.roles[0]}:{self.roles[1]}"
                          if self.roles is not None else None),
                "handoffs": {
                    "shipped": self.n_handoffs,
                    "bytes": self.n_handoff_bytes,
                    "retries": self.n_handoff_retries,
                    "redos": self.n_handoff_redos,
                    "gathered": sum(
                        (p["handoff"] or {}).get("gathered", 0)
                        for p in per),
                    "spliced": sum(
                        (p["handoff"] or {}).get("spliced", 0)
                        for p in per),
                    "pending": sum(
                        (p["handoff"] or {}).get("pending", 0)
                        for p in per),
                },
                "per_worker": per,
            },
            "metrics": obs_metrics.REGISTRY.summary(
                ("egpt_serve_", "egpt_procfleet_")),
            # Unlike the thread fleet there is no process-global ledger
            # to report: each worker accounts its own bytes, summarized
            # per worker above (GET /memory fetches the full ledgers).
            "memory": {"per_worker": [
                {"worker": p["worker"], "memory_bytes": p["memory_bytes"]}
                for p in per]},
            # Coordinator store state + each worker's active rules from
            # the cached probe snapshots (ISSUE 15) — no RPC fan-out on
            # the stats poll; GET /alerts pulls the full worker logs.
            "alerts": {
                **obs_series.alert_stats(),
                "workers_active": sorted({
                    r for slot in self.slots
                    for r in slot.snapshot.get("alerts_active", [])}),
            },
        }

    def fleet_stats(self) -> Dict[str, Any]:
        """The /fleet route body (topology + policy + live state)."""
        return {
            **self.stats()["fleet"],
            "policy": {
                "probe_interval_s": self.probe_interval_s,
                "heartbeat_stale_s": self.heartbeat_stale_s,
                "rpc_deadline_s": self.rpc_deadline_s,
                "rpc_retries": self.rpc_retries,
                "respawn_backoff_s": self.respawn_backoff_s,
                "respawn_backoff_max_s": self.respawn_backoff_max_s,
                "crash_window_s": self.crash_window_s,
                "crash_limit": self.crash_limit,
                "max_failovers": self.max_failovers,
                "handoff_retries": self.handoff_retries,
            },
        }

    def memory_stats(self) -> Dict[str, Any]:
        """``GET /memory``, process-fleet form: each worker's OWN
        ledger + reconciliation, fetched over RPC (per-worker component
        bytes — the ISSUE 11 memory-plumbing satellite). Workers that
        do not answer inside the probe deadline report an error entry
        instead of stalling the route."""
        out = []
        for slot in self.slots:
            if slot.addr is None:
                out.append({"worker": slot.idx, "state": slot.state})
                continue
            try:
                out.append({"worker": slot.idx, "state": slot.state,
                            **self._rpc(slot, "memory",
                                        deadline_s=10.0)})
            except rpc.RpcError as e:
                out.append({"worker": slot.idx, "state": slot.state,
                            "error": repr(e)})
        return {"proc_fleet": True, "workers": out}

    def series(self, window_s: Optional[float] = None,
               n: Optional[int] = None) -> Dict[str, Any]:
        """``GET /series``, process-fleet form (ISSUE 15): each
        worker's OWN sampled ring + derivations, fetched over RPC, plus
        the coordinator's store. Every export is duration-aligned
        (ages relative to each store's own now) — worker perf_counter
        clocks are not comparable across processes, ages are. A worker
        that does not answer inside the deadline reports an error entry
        instead of stalling the route (the /memory contract)."""
        workers = []
        for slot in self.slots:
            if slot.addr is None:
                workers.append({"worker": slot.idx, "state": slot.state})
                continue
            try:
                workers.append({"worker": slot.idx, "state": slot.state,
                                **self._rpc(slot, "series",
                                            {"window_s": window_s, "n": n},
                                            deadline_s=10.0)})
            except rpc.RpcError as e:
                workers.append({"worker": slot.idx, "state": slot.state,
                                "error": repr(e)})
        # Fleet-wide aggregate over the answering workers: rates sum,
        # depths sum, attainment floors take the worst replica.
        agg: Dict[str, float] = {}
        for w in workers:
            d = w.get("derived") or {}
            for key in ("request_rate_per_s", "token_rate_per_s",
                        "submit_rate_per_s", "queue_depth_last"):
                if key in d:
                    agg[key] = round(agg.get(key, 0.0) + d[key], 6)
            for key in ("goodput_ratio_min", "attainment_windowed"):
                if key in d:
                    agg[key] = min(agg.get(key, 1.0), d[key])
        return {
            "proc_fleet": True,
            "coordinator": obs_series.snapshot(window_s=window_s, n=n),
            "workers": workers,
            "aggregate": agg,
        }

    def alerts(self) -> Dict[str, Any]:
        """``GET /alerts``, process-fleet form: the coordinator's rule
        state + each worker's, pulled over RPC (error entries for
        non-answering workers, like /series)."""
        workers = []
        for slot in self.slots:
            if slot.addr is None:
                workers.append({"worker": slot.idx, "state": slot.state})
                continue
            try:
                workers.append({"worker": slot.idx, "state": slot.state,
                                **self._rpc(slot, "alerts",
                                            deadline_s=10.0)})
            except rpc.RpcError as e:
                workers.append({"worker": slot.idx, "state": slot.state,
                                "error": repr(e)})
        return {
            "proc_fleet": True,
            "coordinator": obs_series.alerts(),
            "workers": workers,
            "active": sorted({r for w in workers
                              for r in w.get("active", [])}),
        }

    def journey(self, frid: int) -> Optional[Dict[str, Any]]:
        """Coordinator timeline (route / worker_lost / failover / repin
        / respawn) with each assignment's worker timeline attached over
        RPC, plus the stitched decomposition stored at finish."""
        rec = obs_journey.get(self._journey_owner, frid)
        if rec is None:
            return None
        legs = []
        for w_idx, rid in self._assignments_of(rec["events"]):
            jr = None
            if w_idx is not None and rid is not None \
                    and 0 <= w_idx < len(self.slots) \
                    and self.slots[w_idx].addr is not None:
                try:
                    jr = self._rpc(self.slots[w_idx], "journey",
                                   {"rid": rid}, deadline_s=5.0)
                except rpc.RpcError:
                    jr = None
            legs.append({"worker": w_idx, "rid": rid, "journey": jr})
        rec["assignments"] = legs
        return rec

    def journeys(self, n: int = 64) -> List[Dict[str, Any]]:
        return obs_journey.index(self._journey_owner, n)

    @staticmethod
    def _assignments_of(events) -> List[tuple]:
        out = []
        for ev in events:
            if ev.get("kind") == "route":
                out.append((ev.get("worker"), ev.get("worker_rid")))
            elif ev.get("kind") == "failover":
                out.append((ev.get("to_worker"), ev.get("worker_rid")))
            elif (ev.get("kind") == "kv_handoff"
                    and ev.get("stage") == "shipped"):
                # The decode leg of a disaggregated request is a real
                # assignment: its worker holds the continued timeline.
                out.append((ev.get("to_worker"), ev.get("worker_rid")))
        return out

    # -- routing -----------------------------------------------------------

    def _route_locked(self, key: tuple, exclude=()) -> tuple:
        """(slot, reason): the key's pinned worker while routable, else
        least coordinator-tracked inflight (snapshot queue depths lag a
        probe tick; the coordinator's own assignment count does not).
        Disaggregated fleets route new submissions to the PREFILL pool
        only — prefix affinity keys prefill placement, where the radix
        caches actually serve prompt heads."""
        pool = [s for s in self.slots
                if s.routable and s.idx not in exclude
                and (self.roles is None or s.role == "prefill")]
        if not pool:
            raise RuntimeError(
                f"no routable{' prefill' if self.roles else ''} worker "
                f"({len(self.slots)} slot(s)): {self.fault}")
        pinned = self._pins.get(key)
        if pinned is not None and pinned not in exclude \
                and self.slots[pinned].routable \
                and (self.roles is None
                     or self.slots[pinned].role == "prefill"):
            return self.slots[pinned], "affinity"
        return (min(pool, key=lambda s: (s.inflight, s.idx)),
                "least_queue")

    def _route_decode_locked(self, exclude=()) -> Optional[WorkerSlot]:
        """Decode placement balances BLOCK-POOL HEADROOM, not queue
        depth: the splice must re-allocate the request's full paged
        reservation, so the worker with the most free KV bytes (from
        its last probe snapshot; coordinator-tracked inflight breaks
        ties) takes the next handoff. None when no decode worker is
        currently routable — the caller keeps the record replayable."""
        pool = [s for s in self.slots
                if s.routable and s.role == "decode"
                and s.idx not in exclude]
        if not pool:
            return None

        def headroom(s: WorkerSlot):
            snap = s.snapshot or {}
            return (snap.get("kv_free_bytes")
                    or snap.get("kv_free_blocks") or 0)

        return min(pool, key=lambda s: (-headroom(s), s.inflight, s.idx))

    # -- supervision -------------------------------------------------------

    def kill_worker(self, idx: int) -> None:
        """Operator/chaos hard kill: SIGKILL the worker process NOW.
        The supervisor's next pass observes the exit and runs the REDO
        failover (no drain possible — the process is gone)."""
        slot = self.slots[idx]
        if slot.proc is None:
            return
        slot.kills += 1
        with self._lock:
            self.n_kills += 1
        try:
            slot.proc.kill()
        except OSError:
            pass
        obs_trace.instant("worker_kill", cat="procfleet")

    def drain_worker(self, idx: int) -> int:
        """Operator graceful drain: export the worker's unfinished
        requests over RPC and re-route them (committed tokens
        discarded — chains stay byte-identical), collect anything it
        already finished, then shut the process down. Returns the
        number of re-routed requests. The slot respawns per the normal
        backoff policy (a drain is a kill, not a crash)."""
        slot = self.slots[idx]
        if slot.state in ("dead", "failed") or slot.addr is None:
            return 0
        slot.state = "draining"
        slot.kills += 1
        with self._lock:
            self.n_kills += 1
        self._export_routable_gauge()
        if slot.role == "prefill":
            # Flush the handoff outbox BEFORE the export: gathered
            # records are neither queued nor in-flight on this worker
            # any more (the gather tore the row down), so the export
            # would miss them and their KV would die with the process.
            self._pump_slot_handoffs(slot)
        try:
            exported = self._rpc(slot, "export_requests",
                                 deadline_s=self.drain_deadline_s)
        except rpc.RpcError:
            # It stopped answering mid-drain: hard loss, redo path.
            self._kill_proc(slot)
            self._on_worker_lost(slot, f"worker {idx} unreachable "
                                       f"during drain", graceful=False)
            return 0
        if slot.role == "prefill":
            # Once more after the export parked the scheduler: a row
            # gathered between the first flush and the park would
            # otherwise strand. Nothing can gather after this (the
            # engine is parked), so the outbox is now final.
            self._pump_slot_handoffs(slot)
            # Anything STILL unacked could not ship (e.g. no decode
            # worker routable right now). Its KV dies with this
            # process — REDO each owner from the coordinator record
            # rather than stranding it behind the graceful-drain
            # "finished but uncollected" skip below.
            try:
                left = self._rpc(slot, "collect_handoffs",
                                 deadline_s=10.0)
            except rpc.RpcError:
                left = []
            with self._lock:
                for out in left or []:
                    freq = next(
                        (f for f in self._requests.values()
                         if f.worker == slot.idx
                         and f.rid == int(out["rid"])
                         and not f.done.is_set()), None)
                    if freq is None:
                        continue
                    remaining = (freq.deadline - time.perf_counter()
                                 if freq.deadline is not None else None)
                    self._failover_locked(freq, remaining, "redo")
        moved = self._on_worker_lost(
            slot, f"worker {idx} drained", graceful=True,
            exported=exported or [])
        # Collect finished-but-uncollected answers while the parked
        # worker still answers, then take the process down cleanly.
        self._collect()
        try:
            self._rpc(slot, "shutdown", deadline_s=5.0)
        except rpc.RpcError:
            pass
        if slot.proc is not None:
            try:
                slot.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self._kill_proc(slot)
        now = time.monotonic()
        slot.proc = None
        slot.addr = None
        slot.state = "dead"
        slot.t_dead = now
        slot.t_respawn = now + self.respawn_backoff_s
        self._export_routable_gauge()
        return moved

    def _on_worker_lost(self, slot: WorkerSlot, why: str,
                        graceful: bool, exported=None) -> int:
        """Fail over a lost worker's live requests. Graceful: exported
        records re-submit with their remaining deadline headroom
        (path=drain). Hard: the coordinator re-submits from its OWN
        records (path=redo) and stamps ``worker_lost`` on each victim's
        timeline. Returns the number of moved requests."""
        path = "drain" if graceful else "redo"
        with self._lock:
            self.n_deaths += 1
            self.fault = why
        obs_metrics.PROCFLEET_WORKER_DEATHS.inc()
        obs_trace.instant("worker_lost", cat="procfleet", why=why)
        by_rid = {rec["rid"]: rec for rec in (exported or [])}
        moved = 0
        with self._lock:
            victims = [f for f in self._requests.values()
                       if f.worker == slot.idx and not f.done.is_set()]
            for freq in victims:
                if graceful and freq.rid not in by_rid:
                    # Finished at the worker but uncollected: the drain
                    # sequence's collect pass (worker still answering)
                    # delivers it — leave it tracked.
                    continue
                if not graceful:
                    obs_journey.event(self._journey_owner, freq.frid,
                                      "worker_lost", worker=slot.idx)
                rec = by_rid.get(freq.rid)
                deadline_s = (rec.get("deadline_s") if rec is not None
                              else (freq.deadline - time.perf_counter()
                                    if freq.deadline is not None
                                    else None))
                if self._failover_locked(freq, deadline_s, path):
                    moved += 1
                    slot.respawn_frids.append(freq.frid)
        return moved

    def _failover_locked(self, freq: _ProcRequest,
                         deadline_s: Optional[float],
                         path: str,
                         avoid_current: bool = True) -> bool:
        """Re-route one request to a surviving worker (caller holds the
        lock). The session's pin MOVES with it. Returns True when the
        request found a new home. In a disaggregated fleet the REDO
        pool is the PREFILL side regardless of where the request died:
        a lost decode worker took the spliced KV with it, so the only
        way forward is a fresh prefill -> handoff chain (greedy chains
        are deterministic per request — the re-run is byte-identical).

        ``avoid_current=False`` keeps the request's CURRENT worker in
        the candidate pool: a handoff-ship failure redoes from a
        healthy prefill worker — excluding it (the rule for a dying
        worker) would dead-end a 1-prefill fleet for no reason."""
        freq.failovers += 1
        if freq.failovers > self.max_failovers:
            self._finish_locked(freq, None, "engine_fault")
            return False
        tried = {freq.worker} if avoid_current else set()
        while True:
            pool = [s for s in self.slots
                    if s.routable and s.idx not in tried
                    and (self.roles is None or s.role == "prefill")]
            if not pool:
                self._finish_locked(freq, None, "engine_fault")
                return False
            slot = min(pool, key=lambda s: (s.inflight, s.idx))
            try:
                rid = self._rpc(
                    slot, "submit_ids",
                    {"input_ids": freq.input_ids,
                     "pixel_values": freq.pixel_values,
                     "max_new_tokens": freq.max_new_tokens,
                     "deadline_s": deadline_s, "slo": freq.slo},
                    retry_sent=False)
                break
            except (rpc.RpcError, rpc.RpcRemoteError) as e:
                with_fault = repr(e)
                tried.add(slot.idx)
                if isinstance(e, rpc.RpcError):
                    slot.state = "suspect"
                    self._export_routable_gauge()
                self.fault = with_fault
        old = freq.worker
        self.slots[old].inflight = max(self.slots[old].inflight - 1, 0)
        freq.worker = slot.idx
        freq.rid = rid
        freq.t_assign = time.perf_counter()
        # The abandoned attempt's prefill/handoff legs must not sum
        # into the final timeline — their wall time is exactly what
        # failover_redo_s charges (t_submit -> this assignment).
        freq.prefill_phases = None
        freq.handoff_s = 0.0
        slot.inflight += 1
        self._pins[freq.key] = slot.idx
        self.n_failovers += 1
        obs_metrics.PROCFLEET_FAILOVERS.inc(
            path=("drain" if path == "drain" else "redo"))
        obs_metrics.FLEET_ROUTED.inc(reason="repin")
        obs_journey.event(self._journey_owner, freq.frid, "failover",
                          from_worker=old, to_worker=slot.idx,
                          worker_rid=rid, path=path)
        obs_journey.event(self._journey_owner, freq.frid, "repin",
                          worker=slot.idx)
        return True

    def _stitch_locked(self, freq: _ProcRequest,
                       worker_journey: Optional[dict]):
        """(t_submit, t_done, phases) stitched across processes from
        DURATIONS (worker clocks are not comparable): the final
        assignment's worker-measured phases + ``failover_redo_s`` =
        coordinator wall time from first submit to the final
        assignment. A disaggregated request stitches THREE legs: the
        prefill worker's closed phase decomposition (rides the handoff
        record) sums keywise into the decode leg's, ``handoff_s`` is
        the coordinator-measured collect->import wall time, and
        ``failover_redo_s`` covers any abandoned chains before the
        final one. The phase-sum invariant holds by construction.
        When the worker timeline is unavailable (its recorder
        disarmed, or the worker is gone) a failed-over request still
        charges redo honestly — the final leg's unattributed time
        lands in decode_s, the phase it overwhelmingly is."""
        redo = (max(freq.t_assign - freq.t_submit, 0.0)
                if freq.failovers else 0.0)
        if worker_journey is None or not worker_journey.get("phases"):
            if not freq.failovers and not freq.handoff_s:
                return None
            t_done = time.perf_counter()
            phases = {k: 0.0 for k in obs_journey.PHASE_KEYS}
            phases["handoff_s"] = freq.handoff_s
            phases["decode_s"] = max(
                t_done - freq.t_submit - redo - freq.handoff_s, 0.0)
            phases["failover_redo_s"] = redo
            return freq.t_submit, t_done, phases
        phases = dict(worker_journey["phases"])
        leg_e2e = sum(v for k, v in worker_journey["phases"].items()
                      if k not in ("failover_redo_s", "handoff_s"))
        prefill_e2e = 0.0
        if freq.prefill_phases:
            for k, v in freq.prefill_phases.items():
                if k in ("failover_redo_s", "handoff_s"):
                    continue
                phases[k] = phases.get(k, 0.0) + v
                prefill_e2e += v
        phases["handoff_s"] = freq.handoff_s
        phases["failover_redo_s"] = redo
        t_done = (freq.t_submit + redo + prefill_e2e
                  + freq.handoff_s + leg_e2e)
        return freq.t_submit, t_done, phases

    def _finish_locked(self, freq: _ProcRequest, tokens, status: str,
                       worker_journey: Optional[dict] = None) -> None:
        freq.tokens = tokens
        freq.status = status
        if obs_journey.enabled():
            stitched = self._stitch_locked(freq, worker_journey)
            slo_met = freq.stats.get("slo_met")
            obs_journey.finish(
                self._journey_owner, freq.frid, status,
                t_submit=(stitched[0] if stitched else freq.t_submit),
                t_done=(stitched[1] if stitched else None),
                slo_class=getattr(freq.slo, "name", None),
                slo_met=(bool(slo_met) if slo_met is not None else None),
                phases=(stitched[2] if stitched else None),
                failovers=freq.failovers)
        if freq.stream and freq.stream_q is not None:
            # Deliver-at-finish streaming (see the module docstring):
            # one cumulative delta, then the engine stream protocol's
            # terminal sentinel.
            if tokens is not None:
                freq.stream_q.put(list(tokens))
                freq.stream_q.put(None if status == "ok"
                                  else {"status": status})
            else:
                freq.stream_q.put({"fault": str(self.fault)})
        if 0 <= freq.worker < len(self.slots):
            s = self.slots[freq.worker]
            s.inflight = max(s.inflight - 1, 0)
        freq.done.set()
        while len(self._requests) >= 8192:
            oldest = next(iter(self._requests))
            if not self._requests[oldest].done.is_set():
                break  # never evict a live request
            self._requests.pop(oldest)

    def _supervise(self) -> None:
        """The supervisor loop (never dies): readiness, liveness (poll
        + heartbeat + RPC probe), scripted chaos kills, respawn with
        backoff, and result collection."""
        while not self._stop:
            try:
                for slot in self.slots:
                    self._probe(slot)
                try:
                    faults.maybe_fail("procfleet.worker_kill")
                except faults.InjectedFault:
                    # The chaos trip IS the SIGKILL: take down the
                    # busiest routable worker — the worst case, it
                    # holds in-flight decodes that must be redone.
                    pool = [s for s in self.slots if s.routable]
                    if pool:
                        victim = max(pool,
                                     key=lambda s: (s.inflight, -s.idx))
                        self.kill_worker(victim.idx)
                self._pump_handoffs()
                self._collect()
                self._export_routable_gauge()
            except Exception as e:  # defensive: supervision must survive
                with self._lock:
                    self.fault = repr(e)
            time.sleep(self.probe_interval_s)

    def _probe(self, slot: WorkerSlot) -> None:
        if slot.state == "failed":
            return
        if slot.state == "starting":
            self._check_ready(slot)
            return
        if slot.state == "dead":
            self._maybe_respawn(slot)
            return
        # ok / suspect / draining: the process must still exist.
        if slot.proc is not None and slot.proc.poll() is not None:
            rc = slot.proc.returncode
            slot.proc = None
            slot.addr = None
            prev = slot.state
            self._book_crash(
                slot, f"worker {slot.idx} exited rc={rc} "
                      f"(state was {prev})")
            self._on_worker_lost(
                slot, f"worker {slot.idx} died (rc={rc})",
                graceful=False)
            return
        if slot.state == "draining":
            return  # drain_worker owns this slot's transitions
        # Heartbeat staleness: a wedged worker (process alive, loop
        # stuck) is drained while its RPC server still answers.
        if slot.hb_dir is not None:
            from eventgpt_tpu.train.resilience import Heartbeat

            hb_path = os.path.join(slot.hb_dir, Heartbeat.FILENAME)
            if os.path.exists(hb_path) and Heartbeat.is_stale(
                    hb_path, self.heartbeat_stale_s):
                self.drain_worker(slot.idx)
                return
        # RPC probe: lock-free ops only (snapshot) — a worker busy
        # compiling holds the engine lock, and probing through it
        # would misread SLOW as DEAD.
        try:
            snap = self._rpc(slot, "snapshot", deadline_s=5.0)
            slot.snapshot = snap or {}
            if slot.state == "suspect":
                slot.state = "ok"
                self._export_routable_gauge()
        except rpc.RpcError:
            if slot.state == "suspect":
                # Second strike: it answered neither the submit nor
                # the probe — drain it (the drain's own RPC failure
                # escalates to the hard-loss redo path).
                self.drain_worker(slot.idx)
            else:
                slot.state = "suspect"
                self._export_routable_gauge()

    def _collect(self) -> None:
        """Harvest finished requests: one batched ``try_results`` RPC
        per worker holding live assignments; engine-faulted requests
        fail over (redo)."""
        with self._lock:
            live = [f for f in self._requests.values()
                    if not f.done.is_set()]
        by_slot: Dict[int, List[_ProcRequest]] = {}
        for freq in live:
            by_slot.setdefault(freq.worker, []).append(freq)
        for idx, freqs in by_slot.items():
            slot = self.slots[idx]
            if slot.addr is None:
                continue
            try:
                got = self._rpc(slot, "try_results",
                                {"rids": [f.rid for f in freqs]},
                                deadline_s=self.rpc_deadline_s)
            except rpc.RpcError:
                continue  # probe handles slot health
            for freq in freqs:
                rec = (got or {}).get(str(freq.rid))
                if rec is None:
                    continue
                with self._lock:
                    if freq.done.is_set() or freq.worker != idx:
                        continue  # failed over meanwhile
                    if rec["status"] == "engine_fault":
                        remaining = (
                            freq.deadline - time.perf_counter()
                            if freq.deadline is not None else None)
                        self._failover_locked(freq, remaining, "redo")
                        continue
                    freq.stats = dict(rec.get("stats") or {})
                    self._finish_locked(freq, rec["tokens"],
                                        rec["status"],
                                        worker_journey=rec.get("journey"))

    # -- prefill/decode handoff pump (ISSUE 17) ----------------------------

    def _pump_handoffs(self) -> None:
        """Move gathered block runs from prefill outboxes to decode
        arenas (supervisor tick). Delivery is at-least-once end to end:
        unacked records replay from the prefill worker, the decode
        worker's hid dedup absorbs the duplicates."""
        if self.roles is None:
            return
        for slot in self.slots:
            if slot.role != "prefill" or slot.addr is None:
                continue
            if slot.state not in ("ok", "draining"):
                continue
            self._pump_slot_handoffs(slot)

    def _pump_slot_handoffs(self, slot: WorkerSlot) -> None:
        """One collect -> ship* -> ack pass over ``slot``'s outbox
        (serialized by ``_pump_lock``: the supervisor's periodic pump
        and a drain's flush must not ship the same replayed record
        concurrently)."""
        with self._pump_lock:
            try:
                recs = self._rpc(slot, "collect_handoffs",
                                 deadline_s=self.rpc_deadline_s)
            except rpc.RpcError:
                return  # probe handles slot health; records replay
            acked: List[int] = []
            for out in recs or []:
                try:
                    if self._ship_handoff(slot, out):
                        acked.append(int(out["rid"]))
                except Exception as e:  # defensive: one bad record
                    acked.append(int(out["rid"]))  # must not wedge
                    with self._lock:              # the whole outbox
                        self.fault = f"handoff ship failed: {e!r}"
            if acked:
                try:
                    self._rpc(slot, "ack_handoffs", {"rids": acked},
                              deadline_s=10.0)
                except rpc.RpcError:
                    pass  # re-served next collect; hid dedup absorbs

    def _ship_handoff(self, src: WorkerSlot, out: dict) -> bool:
        """Ship one gathered record to a decode worker. True = the
        record is settled at the source (shipped, stale, or fallen
        back to REDO) and can be acked; False keeps it replayable
        (transient: no decode worker reachable right now). Each
        attempt probes the ``procfleet.handoff`` fault site — a trip
        is a transport failure mid-ship that the bounded retry loop
        must absorb without ever double-splicing."""
        src_rid = int(out["rid"])
        with self._lock:
            freq = next(
                (f for f in self._requests.values()
                 if f.worker == src.idx and f.rid == src_rid
                 and not f.done.is_set()), None)
        if freq is None:
            return True  # stale replay: the request moved on already
        # The spawn generation is part of the identity: a respawned
        # prefill worker's engine rid counter restarts at 0, so a bare
        # slot:rid pair would collide with a pre-respawn record still
        # sitting in a decode worker's dedup cache — the import would
        # "dedup" onto a long-finished stranger's rid.
        hid = f"{src.idx}.{src.generation}:{src_rid}"
        rec = out.get("rec") or {}
        nbytes = int(rec.get("nbytes_kv", 0))
        n_blocks = int(rec.get("n_blocks", 0))
        t0 = time.perf_counter()
        tried: set = set()
        attempts = 0
        rid2 = None
        dslot = None
        while attempts < max(self.handoff_retries, 1):
            with self._lock:
                dslot = self._route_decode_locked(exclude=tried)
            if dslot is None:
                break
            attempts += 1
            try:
                faults.maybe_fail("procfleet.handoff")
                faults.maybe_delay("procfleet.handoff")
                rid2 = self._rpc(
                    dslot, "import_handoff",
                    {"hid": hid,
                     "input_ids": out["input_ids"],
                     "tokens": out.get("tokens") or [],
                     "max_new_tokens": out["max_new_tokens"],
                     "prompt_len": out.get("prompt_len", 0),
                     "deadline_s": out.get("deadline_s"),
                     "slo": out.get("slo"),
                     "elapsed_s": out.get("elapsed_s"),
                     "ttft_s": out.get("ttft_s"),
                     "rec": rec},
                    retry_sent=False)
                break
            except (faults.InjectedFault, rpc.RpcError,
                    rpc.RpcRemoteError) as e:
                rid2 = None
                tried.add(dslot.idx)
                with self._lock:
                    self.n_handoff_retries += 1
                    self.fault = (f"handoff {hid} -> worker "
                                  f"{dslot.idx}: {e!r}")
                if isinstance(e, rpc.RpcError):
                    dslot.state = "suspect"
                    self._export_routable_gauge()
        if rid2 is None:
            if attempts == 0:
                return False  # no decode worker up: keep it replayable
            # Retries exhausted: the REDO fallback — re-prefill from
            # the coordinator's own record. Never a double splice: no
            # import succeeded, so the shipped KV reached no arena.
            with self._lock:
                if freq.done.is_set() or freq.worker != src.idx:
                    return True
                self.n_handoff_redos += 1
                deadline_s = (freq.deadline - time.perf_counter()
                              if freq.deadline is not None else None)
                # The source prefill worker is HEALTHY (the failure was
                # on the decode side): keep it in the redo pool.
                self._failover_locked(freq, deadline_s, "redo",
                                      avoid_current=False)
            return True
        dt = time.perf_counter() - t0
        with self._lock:
            already = (freq.worker == dslot.idx and freq.rid == rid2)
            moved = freq.done.is_set() or freq.worker != src.idx
            if not moved:
                src.inflight = max(src.inflight - 1, 0)
                freq.worker = dslot.idx
                freq.rid = int(rid2)
                freq.prefill_phases = ((out.get("journey") or {})
                                       .get("phases") or None)
                freq.handoff_s += dt
                dslot.inflight += 1
                self.n_handoffs += 1
                self.n_handoff_bytes += nbytes
        if moved:
            if not already:
                # The request finished/failed over while we shipped:
                # the import is an orphan — cancel it best-effort (a
                # missed cancel decodes into the replay cache and ages
                # out; it can never double-deliver).
                try:
                    self._rpc(dslot, "cancel", {"rid": int(rid2)},
                              deadline_s=5.0)
                except rpc.RpcError:
                    pass
            return True
        obs_metrics.PROCFLEET_HANDOFFS.inc(stage="shipped")
        obs_metrics.PROCFLEET_HANDOFF_BYTES.inc(nbytes)
        obs_metrics.PROCFLEET_HANDOFF_SECONDS.observe(dt)
        obs_journey.event(self._journey_owner, freq.frid, "kv_handoff",
                          stage="shipped", from_worker=src.idx,
                          to_worker=dslot.idx, worker_rid=int(rid2),
                          bytes=nbytes, blocks=n_blocks)
        return True

    def _export_routable_gauge(self) -> None:
        obs_metrics.PROCFLEET_ROUTABLE.set(
            sum(s.routable for s in self.slots))

    # -- shutdown ----------------------------------------------------------

    def shutdown(self) -> None:
        """Coordinator shutdown drains before it exits (robustness
        layer 4): wait (bounded) for in-flight requests, ask every
        worker to stop over RPC, then escalate terminate -> kill."""
        if self._stop:
            return
        deadline = time.monotonic() + self.shutdown_drain_s
        while time.monotonic() < deadline:
            with self._lock:
                live = any(not f.done.is_set()
                           for f in self._requests.values())
            if not live:
                break
            time.sleep(0.05)
        self._stop = True
        if getattr(self, "_thread", None) is not None:
            self._thread.join(timeout=10)
        for slot in self.slots:
            if slot.addr is not None:
                try:
                    self._rpc(slot, "shutdown", deadline_s=5.0)
                except rpc.RpcError:
                    pass
        for slot in self.slots:
            if slot.proc is None:
                continue
            try:
                slot.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                try:
                    slot.proc.terminate()
                    slot.proc.wait(timeout=5)
                except (OSError, subprocess.TimeoutExpired):
                    self._kill_proc(slot)
            slot.proc = None
        if self._tmpdir is not None:
            try:
                self._tmpdir.cleanup()
            except OSError:
                pass


def stub_worker_cmd(token_delay_s: float = 0.005) -> List[str]:
    """The jax-free stub worker command (coordinator-logic tests)."""
    return [sys.executable, "-m", "eventgpt_tpu.fleet_proc",
            "--stub_worker", "--token_delay_s", str(token_delay_s)]


if __name__ == "__main__":
    sys.exit(_stub_main())
