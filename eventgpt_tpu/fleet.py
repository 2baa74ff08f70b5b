"""Fleet serving: replica supervisor + prefix-affinity router (ISSUE 7).

Everything before this module hardens and accelerates ONE engine;
ROADMAP item 3 is the tier that turns "a server" into "a service": a
router that owns N ``ServingEngine`` replicas (threads in one process —
the same engine code path the single-engine CLI runs) and decides, per
request, WHERE it runs and WHETHER it runs at all:

  * **Prefix-affinity routing.** A session goes where its radix prefix
    is hot: the router keys each request by the same ``(ids-head,
    pixels_key)`` identity the ``PrefixCache`` trie uses (the prompt
    head through the event sentinel + the stream's content hash), and
    pins that key to the replica that served it first. Repeat turns of
    a chat session and stream re-submits therefore land on the replica
    whose prefix-KV cache already holds their head — the DistServe /
    Splitwise-style KV-affinity placement, with PR 4's hit ratio as the
    per-replica evidence. Unpinned keys (and pins whose replica left
    the pool) fall back to least queue depth.
  * **SLO-aware shedding.** When the fleet is overloaded — the windowed
    goodput ratio (PR 6's ``egpt_serve_slo_goodput_ratio`` signal,
    aggregated across replicas) drops below ``shed_goodput_ratio``, or
    the aggregate queue depth crosses ``shed_queue_depth`` — the router
    sheds ``batch``-class requests at submit with a class-aware
    Retry-After hint (``retry_after_s``). ``interactive`` requests are
    never policy-shed; they only see natural ``QueueFullError``
    backpressure when every replica's bounded queue is full.
  * **Supervision + failover.** A supervisor thread probes each
    replica's health (circuit-breaker state, liveness heartbeat
    staleness, kill state) and marks unhealthy replicas unroutable.
    When a replica dies (``kill_replica`` / the ``fleet.replica_kill``
    chaos site), its unfinished requests — queued AND in-flight — are
    drained via ``ContinuousBatcher.export_requests`` and re-routed to
    survivors, re-pinning their sessions; requests an engine fault
    already failed (status ``engine_fault``) fail over the same way.
    Failover re-decodes from the prompt: greedy chains are
    deterministic per request, so the failed-over chain is
    byte-identical to an uninterrupted single-engine run (the chaos
    test's acceptance bar). A revived replica (``restart_replica`` or
    ``replica_restart_s`` auto-restart) re-enters the routing pool.

Deliberately jax-free (stdlib + numpy), like ``workload.py``: the
router tier holds no device state — it moves host-side request records
between engines that do. Chaos sites: ``fleet.route`` (a route fault
degrades that submit to least-queue), ``fleet.probe`` (a probe fault
marks the probed replica unroutable until a clean probe),
``fleet.replica_kill`` (the trip IS the scripted kill).
"""

from __future__ import annotations

import hashlib
import math
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from eventgpt_tpu import faults
from eventgpt_tpu.constants import EVENT_TOKEN_INDEX
from eventgpt_tpu.obs import journey as obs_journey
from eventgpt_tpu.obs import metrics as obs_metrics
from eventgpt_tpu.obs import series as obs_series
from eventgpt_tpu.obs import trace as obs_trace

# Per-class base backoff for 429 hints: batch traffic has latency
# headroom by definition, so it is told to stay away longer.
_RETRY_BASE_S = {"interactive": 1.0, "batch": 4.0}
_RETRY_MAX_S = 60.0


class FleetShedError(RuntimeError):
    """The router refused a request under its SLO-aware overload policy
    (batch-class shed — backpressure, not failure). Carries the
    class-aware backoff hint the HTTP layer turns into Retry-After."""

    def __init__(self, msg: str, slo_class: str, retry_after_s: float):
        super().__init__(msg)
        self.slo_class = slo_class
        self.retry_after_s = retry_after_s


def _ledger_summary() -> Dict[str, Any]:
    """Process memory-ledger totals for the fleet /stats poll (host
    ints only; the import stays lazy like ``memory_stats`` so this
    module keeps its jax-free import surface)."""
    from eventgpt_tpu.obs import memory as obs_memory

    return obs_memory.LEDGER.summary()


def retry_after_s(slo_class: str, goodput_ratio: float = 1.0,
                  queue_depth: int = 0, max_queue: int = 0) -> float:
    """Class-aware 429 backoff derived from the CURRENT goodput window
    (ISSUE 7 satellite — replaces the fixed ``Retry-After: 1``): the
    further the windowed SLO-attainment ratio is below 1.0, the longer
    clients are told to stay away (linear, up to 4x the class base),
    scaled up again by relative queue pressure when known. ``batch``
    starts at a higher base than ``interactive`` — shed batch traffic
    must not come back first and re-trigger the shed."""
    base = _RETRY_BASE_S.get(slo_class, _RETRY_BASE_S["batch"])
    g = min(max(float(goodput_ratio), 0.0), 1.0)
    scale = 1.0 + 3.0 * (1.0 - g)
    if max_queue > 0 and queue_depth > 0:
        scale *= 1.0 + min(queue_depth / float(max_queue), 1.0)
    return min(base * scale, _RETRY_MAX_S)


def affinity_key(input_ids: Sequence[int], pixel_values: Any) -> tuple:
    """The routing identity of a request: its prompt head THROUGH the
    event sentinel plus the stream's content hash — the same identity
    the ``PrefixCache`` keys its through-event entries on, so
    same-key => the pinned replica's radix cache holds this head. The
    pixel hash matches ``serve._pixels_key``'s semantics (shape + f32
    content) without importing the jax-heavy module."""
    ids = list(input_ids)
    try:
        head = tuple(ids[: ids.index(EVENT_TOKEN_INDEX) + 1])
    except ValueError:
        head = tuple(ids)
    arr = np.ascontiguousarray(np.asarray(pixel_values, np.float32))
    digest = str(arr.shape).encode() + hashlib.sha1(arr.tobytes()).digest()
    return (head, digest)


@dataclass
class _FleetRequest:
    """One request the router owns end to end. ``replica``/``rid`` are
    the CURRENT assignment (failover re-points them); the client waits
    on ``done``, which only the supervisor (or submit-time shed) sets."""
    frid: int
    input_ids: List[int]
    pixel_values: Any
    max_new_tokens: int
    deadline: Optional[float]          # absolute perf_counter, or None
    slo: Any
    key: tuple
    stream: bool
    replica: int
    rid: int
    t_submit: float
    failovers: int = 0
    done: threading.Event = field(default_factory=threading.Event)
    tokens: Optional[List[int]] = None
    status: str = "ok"
    stats: Dict[str, float] = field(default_factory=dict)
    stream_q: Any = None               # the engine queue object (held so
    #                                    a dead replica's fault can still
    #                                    reach the streaming client)


@dataclass
class Replica:
    """One supervised engine. ``state`` drives routability: only ``ok``
    replicas receive new work; ``degraded`` (breaker open / stale
    heartbeat / probe fault) and ``dead`` (killed) are skipped until a
    clean probe or a restart re-admits them."""
    idx: int
    engine: Any
    state: str = "ok"                  # ok | degraded | dead
    t_dead: float = 0.0
    kills: int = 0
    probe_faults: int = 0

    @property
    def routable(self) -> bool:
        return self.state == "ok"

    def depth(self) -> int:
        """Routing load signal: queued + active rows (host-side reads,
        GIL-atomic enough for a heuristic)."""
        b = self.engine.batcher
        return len(b.queue) + sum(r is not None for r in b.rows)


class _FleetRequestStats:
    """``.get(frid)`` view over finished fleet requests — the shape
    ``make_handler`` expects of ``engine.batcher.request_stats``."""

    def __init__(self, fleet: "Fleet"):
        self._fleet = fleet

    def get(self, frid: int, default=None):
        freq = self._fleet._requests.get(frid)
        if freq is None or not freq.done.is_set():
            return default if default is not None else {}
        return freq.stats


class _FleetBatcherView:
    """The minimal ``engine.batcher`` surface the HTTP handler reads
    (request stats + prefix-cache snapshot), aggregated fleet-wide."""

    def __init__(self, fleet: "Fleet"):
        self._fleet = fleet
        self.request_stats = _FleetRequestStats(fleet)

    def prefix_cache_stats(self) -> Dict[str, Any]:
        per = []
        hits = misses = 0
        for rep in self._fleet.replicas:
            st = rep.engine.batcher.prefix_cache_stats()
            st.pop("entries", None)  # per-entry dumps don't aggregate
            per.append({"replica": rep.idx, **st})
            hits += st.get("hits", 0)
            misses += st.get("misses", 0)
        return {
            "enabled": any(p.get("enabled") for p in per),
            "hits": hits,
            "misses": misses,
            "hit_ratio": hits / (hits + misses) if (hits + misses) else 0.0,
            "replicas": per,
        }

    def slo_stats(self) -> Dict[str, Any]:
        return self._fleet.slo_stats()


class Fleet:
    """Replica supervisor + router with the client surface of a
    ``ServingEngine`` (submit / result / status / cancel / stream_queue
    / stats / breaker_open / set_prefix), so ``cli.serve.make_handler``
    serves a fleet unchanged. See the module docstring for policy.

    Lock discipline (egpt_check rule ``lock``): ``_GUARDED_BY`` is the
    checkable contract. The routing table (``_pins``), the request map's
    WRITES, and every host counter mutate under ``_lock``; ``/w``
    attributes are read lock-free by design (``result`` must not hold
    the lock while waiting; ``status``/``stream_queue`` tolerate
    one-tick staleness on a GIL-atomic dict read). Lock ORDER is fleet
    -> engine: ``submit_ids`` holds ``_lock`` across
    ``engine.submit_ids`` (which takes the engine lock); engine code
    never takes the fleet lock, so the order cannot invert. Replica
    ``state`` strings are a documented exception: single-writer from
    the supervisor thread in steady state, with the rare operator
    ``kill_replica``/``restart_replica`` transitions idempotent —
    cross-object fields are outside the detector's static scope either
    way (see analysis/lock_discipline.py "Known static limits")."""

    _GUARDED_BY = {
        # full guard: routing/bookkeeping state with compound updates
        "_pins": "_lock",
        "_next_frid": "_lock",
        "n_shed": "_lock",
        # writes locked; lock-free reads are the snapshot/flag pattern
        "_requests": "_lock/w",
        "n_requests": "_lock/w",
        "n_failovers": "_lock/w",
        "n_kills": "_lock/w",
        "n_route_faults": "_lock/w",
        "fault": "_lock/w",
    }

    def __init__(self, engines: Sequence[Any], tokenizer=None,
                 conv_mode: str = "eventgpt_v1",
                 probe_interval_s: float = 0.05,
                 heartbeat_stale_s: float = 5.0,
                 shed_goodput_ratio: float = 0.5,
                 shed_min_window: int = 8,
                 shed_queue_depth: int = 0,
                 max_failovers: int = 3,
                 replica_restart_s: Optional[float] = None):
        if not engines:
            raise ValueError("a fleet needs at least one replica engine")
        self.replicas = [Replica(i, e) for i, e in enumerate(engines)]
        self.tokenizer = tokenizer
        self.conv_mode = conv_mode
        self.probe_interval_s = float(probe_interval_s)
        self.heartbeat_stale_s = float(heartbeat_stale_s)
        # Shedding thresholds: 0 disarms that signal. Goodput shedding
        # only engages once the aggregate window holds shed_min_window
        # finishes — an empty window reads 0.0 and would shed a cold
        # fleet forever.
        self.shed_goodput_ratio = float(shed_goodput_ratio)
        self.shed_min_window = int(shed_min_window)
        self.shed_queue_depth = int(shed_queue_depth)
        self.max_failovers = int(max_failovers)
        self.replica_restart_s = replica_restart_s
        self._lock = threading.Lock()
        self._requests: Dict[int, _FleetRequest] = {}
        self._pins: Dict[tuple, int] = {}      # affinity key -> replica idx
        self._next_frid = 0
        self._stop = False
        self.t_start = time.time()
        self.n_requests = 0
        # Host-side counters (/stats and tests read these; the egpt_fleet_*
        # registry mirrors them for /metrics):
        self.n_shed: Dict[str, int] = {}
        self.n_failovers = 0
        self.n_kills = 0
        self.n_route_faults = 0
        self.fault: Any = None                 # repr of the last replica loss
        # Flight recorder (ISSUE 10): the router records its own
        # request-level timeline (route / shed / failover / repin) under
        # a fleet owner id; per-replica decode timelines live under each
        # batcher's owner, stitched together by ``journey(frid)``.
        self._journey_owner = obs_journey.register_owner("fleet")
        obs_metrics.FLEET_REPLICAS.set(len(self.replicas))
        obs_metrics.FLEET_ROUTABLE.set(len(self.replicas))
        self._thread = threading.Thread(target=self._supervise, daemon=True)
        self._thread.start()

    # -- client surface ---------------------------------------------------

    @property
    def batcher(self) -> _FleetBatcherView:
        return _FleetBatcherView(self)

    @property
    def n_faults(self) -> int:
        return sum(r.engine.n_faults for r in self.replicas)

    @property
    def n_restarts(self) -> int:
        return sum(r.engine.n_restarts for r in self.replicas)

    def breaker_open(self) -> bool:
        """The fleet refuses work only when NO replica is routable —
        one healthy replica keeps /health green (degraded capacity shows
        in the egpt_fleet_replicas_routable gauge instead)."""
        return not any(r.routable for r in self.replicas)

    def goodput_ratio(self) -> float:
        """Aggregate windowed SLO-attainment across replicas, weighted
        by window occupancy; 1.0 until the window holds anything (an
        empty window must not read as total SLO collapse)."""
        met = 0.0
        n = 0
        for rep in self.replicas:
            st = rep.engine.batcher.slo_stats()
            w = st.get("window_n", 0)
            met += st.get("goodput_ratio", 0.0) * w
            n += w
        return met / n if n else 1.0

    def queue_depth(self) -> int:
        return sum(len(r.engine.batcher.queue) for r in self.replicas)

    def submit(self, query: str, pixels, max_new_tokens: int,
               stream: bool = False, deadline_s: Optional[float] = None,
               slo=None) -> int:
        from eventgpt_tpu.data.conversation import prepare_event_prompt
        from eventgpt_tpu.data.tokenizer import tokenize_with_event

        ids = tokenize_with_event(
            prepare_event_prompt(query, self.conv_mode), self.tokenizer
        )
        return self.submit_ids(ids, pixels, max_new_tokens, stream=stream,
                               deadline_s=deadline_s, slo=slo)

    def submit_ids(self, input_ids: Sequence[int], pixels,
                   max_new_tokens: int, stream: bool = False,
                   deadline_s: Optional[float] = None, slo=None) -> int:
        """Route one request: shed-check, pick a replica (affinity ->
        least-queue), submit there, track for supervision. Raises
        ``FleetShedError`` (policy shed), the replica's
        ``QueueFullError`` (every routable replica full), or
        ``RuntimeError`` when no replica is routable at all."""
        try:
            self._maybe_shed(slo)
        except FleetShedError:
            # A shed is a terminal outcome the flight recorder must
            # still explain: it gets an frid-keyed timeline of its own
            # (submit -> shed -> finish{status: shed}), so /requests
            # shows refusals next to served traffic.
            if obs_journey.enabled():
                with self._lock:
                    frid = self._next_frid
                    self._next_frid += 1
                t = time.perf_counter()
                cls = getattr(slo, "name", None)
                obs_journey.begin(self._journey_owner, frid, t=t,
                                  slo_class=cls)
                obs_journey.event(self._journey_owner, frid, "shed", t=t)
                obs_journey.finish(self._journey_owner, frid, "shed",
                                   t_submit=t, t_done=t, slo_class=cls)
            raise
        key = affinity_key(input_ids, pixels)
        with self._lock:
            rep, reason = self._route_locked(key)
            rid = rep.engine.submit_ids(
                list(input_ids), pixels, max_new_tokens, stream=stream,
                deadline_s=deadline_s, slo=slo)
            obs_metrics.FLEET_ROUTED.inc(reason=reason)
            frid = self._next_frid
            self._next_frid += 1
            freq = _FleetRequest(
                frid=frid, input_ids=list(input_ids), pixel_values=pixels,
                max_new_tokens=max_new_tokens,
                deadline=(time.perf_counter() + deadline_s
                          if deadline_s is not None else None),
                slo=slo, key=key, stream=stream, replica=rep.idx, rid=rid,
                t_submit=time.perf_counter())
            if stream:
                freq.stream_q = rep.engine.stream_queue(rid)
            self._requests[frid] = freq
            self._pins[key] = rep.idx
            self.n_requests += 1
            obs_journey.begin(
                self._journey_owner, frid, t=freq.t_submit,
                budget=max_new_tokens,
                **({"slo_class": slo.name} if slo is not None else {}))
            obs_journey.event(self._journey_owner, frid, "route",
                              t=freq.t_submit, replica=rep.idx,
                              replica_rid=rid, reason=reason)
        obs_metrics.FLEET_QUEUE_DEPTH.set(self.queue_depth())
        return frid

    def result(self, frid: int, timeout: float = 600.0) -> List[int]:
        freq = self._requests[frid]
        if not freq.done.wait(timeout):
            raise TimeoutError(
                f"fleet request {frid} did not finish in {timeout}s")
        if freq.tokens is None:
            raise RuntimeError(
                f"fleet request {frid} failed after {freq.failovers} "
                f"failover(s): {freq.status} ({self.fault})")
        return freq.tokens

    def status(self, frid: int) -> str:
        freq = self._requests.get(frid)
        return freq.status if freq is not None else "ok"

    def replica_of(self, frid: int) -> int:
        """The replica that served (or is serving) the request: what
        the tests' affinity and failover assertions read."""
        return self._requests[frid].replica

    def cancel(self, frid: int) -> bool:
        with self._lock:
            freq = self._requests.get(frid)
            if freq is None or freq.done.is_set():
                return False
            rep = self.replicas[freq.replica]
        return rep.engine.cancel(freq.rid)

    def stream_queue(self, frid: int):
        return self._requests[frid].stream_q

    def set_prefix(self, prefix_prompt: str, pixels=None) -> int:
        """Broadcast an operator prefix insert to EVERY replica (the
        single-engine POST /prefix contract, fleet-wide: a session may
        land anywhere before it has a pin)."""
        plen = 0
        for rep in self.replicas:
            if rep.routable:
                plen = rep.engine.set_prefix(prefix_prompt, pixels)
        return plen

    def stats(self) -> Dict[str, Any]:
        reps = []
        for rep in self.replicas:
            s = rep.engine.snapshot()
            reps.append({
                "replica": rep.idx,
                "state": rep.state,
                "active_rows": s.get("active_rows", 0),
                "queued": s.get("queued", 0),
                "faults": rep.engine.n_faults,
                "restarts": rep.engine.n_restarts,
                "kills": rep.kills,
                "goodput_ratio": s.get("slo", {}).get("goodput_ratio", 0.0),
                "prefix_cache_hit_ratio":
                    rep.engine.batcher.prefix_cache_stats().get(
                        "hit_ratio", 0.0),
                # Per-replica memory share (ISSUE 9): this replica's
                # OWN ledger components (resident cache, lanes, ...) —
                # the shared weight tree lives in the process totals,
                # not here (it is one allocation, not N).
                "memory_bytes": sum(
                    s.get("memory", {}).get("owner", {}).values()),
            })
        with self._lock:
            # _pins/n_shed are compound-mutated (full guard): snapshot
            # under the lock — dict(d) can raise if d resizes mid-copy.
            n_pins = len(self._pins)
            shed = dict(self.n_shed)
        return {
            "uptime_s": round(time.time() - self.t_start, 1),
            "requests": self.n_requests,
            "status": "degraded" if self.breaker_open() else "ok",
            "active_rows": sum(r["active_rows"] for r in reps),
            "queued": sum(r["queued"] for r in reps),
            "fleet": {
                "replicas": len(self.replicas),
                "routable": sum(r.routable for r in self.replicas),
                "pins": n_pins,
                "goodput_ratio": round(self.goodput_ratio(), 4),
                "shed": shed,
                "failovers": self.n_failovers,
                "kills": self.n_kills,
                "route_faults": self.n_route_faults,
                "per_replica": reps,
            },
            "metrics": obs_metrics.REGISTRY.summary(
                ("egpt_serve_", "egpt_fleet_")),
            # Ledger totals ride the fleet poll too (ISSUE 9): one
            # process, one jax runtime — the process ledger IS the
            # fleet's memory story (per-replica shares are in
            # per_replica[].memory_bytes above).
            "memory": _ledger_summary(),
            # Active alert rules + last transitions (ISSUE 15): the
            # store samples the process registry, which already carries
            # the fleet aggregates (egpt_fleet_queue_depth feeds the
            # queue_trend rule), so one store senses the whole fleet.
            "alerts": obs_series.alert_stats(),
        }

    def fleet_stats(self) -> Dict[str, Any]:
        """The /fleet route body (topology + policy + live state)."""
        return {
            **self.stats()["fleet"],
            "policy": {
                "shed_goodput_ratio": self.shed_goodput_ratio,
                "shed_min_window": self.shed_min_window,
                "shed_queue_depth": self.shed_queue_depth,
                "max_failovers": self.max_failovers,
                "probe_interval_s": self.probe_interval_s,
                "heartbeat_stale_s": self.heartbeat_stale_s,
                "replica_restart_s": self.replica_restart_s,
            },
        }

    def memory_stats(self) -> Dict[str, Any]:
        """The fleet ``GET /memory`` payload (ISSUE 9): process ledger
        totals + reconciliation (one process, one jax runtime — the
        ledger IS fleet-wide) plus each replica's own component share.
        The weight tree appears once in the totals: replicas share it
        by construction (one tree, N schedulers)."""
        from eventgpt_tpu.obs import memory as obs_memory

        out = obs_memory.LEDGER.summary()
        out["reconcile"] = obs_memory.LEDGER.reconcile()
        out["replicas"] = [
            {"replica": rep.idx,
             "components": obs_memory.LEDGER.snapshot(
                 rep.engine.batcher._mem_owner)}
            for rep in self.replicas
        ]
        return out

    def series(self, window_s: Optional[float] = None,
               n: Optional[int] = None) -> Dict[str, Any]:
        """The fleet ``GET /series`` payload (ISSUE 15). One process,
        one registry, one store: replicas are threads, the sampler
        already sees the fleet-wide gauges (the router overwrites
        egpt_fleet_queue_depth each route, each replica's scheduler the
        serve gauges — the store samples max of the two). Per-replica
        instantaneous context rides alongside the shared ring."""
        out = obs_series.snapshot(window_s=window_s, n=n)
        out["per_replica"] = [
            {"replica": rep.idx, "state": rep.state,
             "queued": rep.engine.snapshot().get("queued", 0)}
            for rep in self.replicas
        ]
        return out

    def alerts(self) -> Dict[str, Any]:
        """The fleet ``GET /alerts`` payload (ISSUE 15): the shared
        process store's rule state — fleet-wide by construction."""
        return obs_series.alerts()

    def slo_stats(self) -> Dict[str, Any]:
        """Aggregate per-class attainment across replicas (the fleet's
        ``slo`` block of ``GET /stats``)."""
        classes: Dict[str, Dict[str, int]] = {}
        for rep in self.replicas:
            st = rep.engine.batcher.slo_stats()
            for name, c in st.get("classes", {}).items():
                agg = classes.setdefault(name, {"finished": 0, "met": 0})
                agg["finished"] += c["finished"]
                agg["met"] += c["met"]
        for c in classes.values():
            c["attainment"] = (c["met"] / c["finished"]
                               if c["finished"] else 0.0)
        return {"classes": classes, "goodput_ratio": self.goodput_ratio()}

    def shutdown(self) -> None:
        self._stop = True
        self._thread.join(timeout=10)
        for rep in self.replicas:
            rep.engine.shutdown()

    # -- routing ----------------------------------------------------------

    def _route_locked(self, key: tuple):
        """(replica, reason) for one submit. Affinity first: the key's
        pinned replica, while routable. A ``fleet.route`` chaos trip
        degrades THIS decision to least-queue (the handling contract:
        a broken affinity table must cost locality, not availability)."""
        pool = [r for r in self.replicas if r.routable]
        if not pool:
            raise RuntimeError(
                f"no routable replica ({len(self.replicas)} configured): "
                f"{self.fault}")
        try:
            faults.maybe_fail("fleet.route")
            faults.maybe_delay("fleet.route")
            pinned = self._pins.get(key)
            if pinned is not None and self.replicas[pinned].routable:
                return self.replicas[pinned], "affinity"
        except faults.InjectedFault:
            self.n_route_faults += 1
        return min(pool, key=lambda r: (r.depth(), r.idx)), "least_queue"

    def _maybe_shed(self, slo) -> None:
        """Batch-first admission control at the router edge. Only
        ``batch``-class requests are ever policy-shed; everything else
        rides the replicas' own queue bounds."""
        if slo is None or getattr(slo, "name", None) != "batch":
            return
        overloaded, why = self._overloaded()
        if not overloaded:
            return
        ra = retry_after_s("batch", self.goodput_ratio(),
                           queue_depth=self.queue_depth(),
                           max_queue=max(self.shed_queue_depth, 1))
        with self._lock:
            self.n_shed["batch"] = self.n_shed.get("batch", 0) + 1
        obs_metrics.FLEET_SHED.inc(slo_class="batch")
        obs_trace.instant("fleet_shed", cat="fleet", why=why)
        raise FleetShedError(
            f"fleet shed batch-class request ({why}); retry in ~{ra:.0f}s",
            "batch", ra)

    def _overloaded(self):
        if self.shed_queue_depth > 0:
            q = self.queue_depth()
            if q >= self.shed_queue_depth:
                return True, f"queue depth {q} >= {self.shed_queue_depth}"
        if self.shed_goodput_ratio > 0.0:
            n = sum(r.engine.batcher.slo_stats().get("window_n", 0)
                    for r in self.replicas)
            g = self.goodput_ratio()
            if n >= self.shed_min_window and g < self.shed_goodput_ratio:
                return True, (f"windowed goodput {g:.2f} < "
                              f"{self.shed_goodput_ratio}")
        return False, ""

    # -- supervision ------------------------------------------------------

    def kill_replica(self, idx: int) -> int:
        """Kill one replica NOW (operator API and the chaos handler):
        mark it dead, drain its unfinished requests and re-route them to
        survivors. Returns the number of failed-over requests. Streamed
        requests cannot fail over (bytes already left through their
        chunked body) — their clients get the fault sentinel instead."""
        rep = self.replicas[idx]
        if rep.state == "dead":
            return 0
        rep.state = "dead"
        rep.t_dead = time.monotonic()
        rep.kills += 1
        with self._lock:
            # Counter/fault writes go under the lock (the lock contract;
            # rep.state above is the documented Replica exception).
            # engine.kill() below stays OUTSIDE it: fleet -> engine is
            # the lock order, and kill holds the engine lock for a full
            # drain.
            self.n_kills += 1
            self.fault = f"replica {idx} killed"
        obs_metrics.FLEET_REPLICA_DEATHS.inc()
        obs_trace.instant("replica_kill", cat="fleet")
        self._export_routable_gauge()
        exported = rep.engine.kill()
        by_rid = {rec["rid"]: rec for rec in exported}
        moved = 0
        with self._lock:
            victims = [f for f in self._requests.values()
                       if f.replica == idx and not f.done.is_set()]
            for freq in victims:
                rec = by_rid.get(freq.rid)
                if freq.stream:
                    # Mid-stream failover would replay already-sent
                    # bytes; surface the fault like an engine death.
                    self._finish_locked(freq, None, "engine_fault")
                    if freq.stream_q is not None:
                        freq.stream_q.put({"fault": self.fault})
                    continue
                if rec is None:
                    # Finished at the engine but uncollected: kill()
                    # harvested first, so try_result still serves it on
                    # the next supervisor tick. Leave it tracked.
                    continue
                self._failover_locked(freq, rec.get("deadline_s"))
                moved += 1
        obs_metrics.FLEET_QUEUE_DEPTH.set(self.queue_depth())
        return moved

    def restart_replica(self, idx: int) -> None:
        """Recovery: revive a killed replica and re-admit it to the
        routing pool (the kill -> drain -> re-route -> RECOVERY tail)."""
        rep = self.replicas[idx]
        rep.engine.revive()
        rep.state = "ok"
        obs_trace.instant("replica_restart", cat="fleet")
        self._export_routable_gauge()

    def _failover_locked(self, freq: _FleetRequest,
                         deadline_s: Optional[float]) -> None:
        """Re-route one request to a survivor (caller holds the lock).
        The session's pin MOVES with it — subsequent turns follow the
        failed-over request to its new replica (re-pin), rebuilding
        prefix locality there instead of bouncing per turn."""
        freq.failovers += 1
        if freq.failovers > self.max_failovers:
            self._finish_locked(freq, None, "engine_fault")
            return
        pool = [r for r in self.replicas
                if r.routable and r.idx != freq.replica]
        if not pool:
            pool = [r for r in self.replicas if r.routable]
        if not pool:
            self._finish_locked(freq, None, "engine_fault")
            return
        rep = min(pool, key=lambda r: (r.depth(), r.idx))
        try:
            freq.rid = rep.engine.submit_ids(
                freq.input_ids, freq.pixel_values, freq.max_new_tokens,
                deadline_s=deadline_s, slo=freq.slo)
        except Exception as e:  # survivor refused (full/degraded): give up
            self.fault = repr(e)
            self._finish_locked(freq, None, "engine_fault")
            return
        old_replica = freq.replica
        freq.replica = rep.idx
        self._pins[freq.key] = rep.idx
        self.n_failovers += 1
        obs_metrics.FLEET_FAILOVERS.inc()
        obs_metrics.FLEET_ROUTED.inc(reason="repin")
        obs_journey.event(self._journey_owner, freq.frid, "failover",
                          from_replica=old_replica, to_replica=rep.idx,
                          replica_rid=freq.rid)
        obs_journey.event(self._journey_owner, freq.frid, "repin",
                          replica=rep.idx)

    @staticmethod
    def _assignments_of(events) -> List[tuple]:
        """(replica, rid) per assignment, from a fleet journey's route/
        failover events (works on both the raw and export shapes)."""
        out = []
        for ev in events:
            if ev.get("kind") == "route":
                out.append((ev.get("replica"), ev.get("replica_rid")))
            elif ev.get("kind") == "failover":
                out.append((ev.get("to_replica"), ev.get("replica_rid")))
        return out

    def _stitch_locked(self, freq: _FleetRequest):
        """(t_submit, t_done, phases) of the whole fleet request,
        stitched across its assignments: the FINAL assignment's phase
        decomposition plus ``failover_redo_s`` = the wall time the
        abandoned assignments burned (first replica submit -> final
        replica submit — queued, decoded-and-discarded, and re-routed
        time all land there, which is exactly what a failover costs).
        The sum invariant holds by construction: phases partition
        [first.t_submit, final.t_done]. None when the recorder is
        disarmed or the replica timelines are gone."""
        raw = obs_journey.raw(self._journey_owner, freq.frid)
        if raw is None:
            return None
        raws = []
        for rep_idx, rid in self._assignments_of(raw["events"]):
            if rep_idx is None or rid is None \
                    or not (0 <= rep_idx < len(self.replicas)):
                continue
            b = self.replicas[rep_idx].engine.batcher
            r = obs_journey.raw(getattr(b, "_journey_owner", -1), rid)
            if r is not None:
                raws.append(r)
        final = next((r for r in reversed(raws)
                      if r.get("finished") and r.get("phases")), None)
        if final is None:
            return None
        first = raws[0]
        redo = max(final["t_submit"] - first["t_submit"], 0.0)
        phases = dict(final["phases"])
        phases["failover_redo_s"] = redo
        return first["t_submit"], final["t_done"], phases

    def journey(self, frid: int) -> Optional[Dict[str, Any]]:
        """Fleet passthrough of ``GET /request?rid=N`` (ISSUE 10): the
        router-level timeline (route / shed / failover / repin) with
        each assignment's replica timeline attached, plus the stitched
        decomposition stored at finish."""
        rec = obs_journey.get(self._journey_owner, frid)
        if rec is None:
            return None
        legs = []
        for rep_idx, rid in self._assignments_of(rec["events"]):
            jr = None
            if rep_idx is not None and rid is not None \
                    and 0 <= rep_idx < len(self.replicas):
                jr = self.replicas[rep_idx].engine.batcher.journey(rid)
            legs.append({"replica": rep_idx, "rid": rid, "journey": jr})
        rec["assignments"] = legs
        return rec

    def journeys(self, n: int = 64) -> List[Dict[str, Any]]:
        """Recent finished fleet requests (``GET /requests``)."""
        return obs_journey.index(self._journey_owner, n)

    def _finish_locked(self, freq: _FleetRequest, tokens,
                       status: str) -> None:
        freq.tokens = tokens
        freq.status = status
        if obs_journey.enabled():
            # Close the fleet journey BEFORE releasing the waiter: a
            # client that polls journey(frid) right after result()
            # must see the finished, stitched record.
            stitched = self._stitch_locked(freq)
            slo_met = freq.stats.get("slo_met")
            obs_journey.finish(
                self._journey_owner, freq.frid, status,
                t_submit=(stitched[0] if stitched else freq.t_submit),
                t_done=(stitched[1] if stitched else None),
                slo_class=getattr(freq.slo, "name", None),
                slo_met=(bool(slo_met) if slo_met is not None else None),
                phases=(stitched[2] if stitched else None),
                failovers=freq.failovers)
        freq.done.set()
        # Bounded finished map (the engine's request_stats rule): a
        # long-lived router must not grow per-request state forever.
        while len(self._requests) >= 8192:
            oldest = next(iter(self._requests))
            if not self._requests[oldest].done.is_set():
                break  # never evict a live request
            self._requests.pop(oldest)

    def _supervise(self) -> None:
        """The supervisor loop: probe health, run scripted chaos kills,
        collect finished/faulted requests, auto-restart dead replicas.
        Must never die — every probe failure is a health SIGNAL here."""
        while not self._stop:
            try:
                faults.maybe_delay("fleet.probe")
                for rep in self.replicas:
                    self._probe(rep)
                try:
                    faults.maybe_fail("fleet.replica_kill")
                except faults.InjectedFault:
                    # The chaos trip IS the kill: take down the busiest
                    # routable replica (the worst case — it holds
                    # in-flight decodes that must fail over).
                    pool = [r for r in self.replicas if r.routable]
                    if pool:
                        victim = max(pool, key=lambda r: (r.depth(), -r.idx))
                        self.kill_replica(victim.idx)
                self._collect()
                self._export_routable_gauge()
                obs_metrics.FLEET_QUEUE_DEPTH.set(self.queue_depth())
            except Exception as e:  # defensive: supervision must survive
                with self._lock:
                    self.fault = repr(e)
            time.sleep(self.probe_interval_s)

    def _probe(self, rep: Replica) -> None:
        if rep.state == "dead":
            if (self.replica_restart_s is not None
                    and time.monotonic() - rep.t_dead
                    >= self.replica_restart_s):
                self.restart_replica(rep.idx)
            return
        try:
            faults.maybe_fail("fleet.probe")
        except faults.InjectedFault:
            # A failed probe means health is UNKNOWN: pull the replica
            # from the pool until a clean probe says otherwise — the
            # same action a real probe timeout would take.
            rep.probe_faults += 1
            rep.state = "degraded"
            return
        eng = rep.engine
        healthy = not eng.breaker_open()
        hb = getattr(eng, "_heartbeat", None)
        if healthy and hb is not None:
            from eventgpt_tpu.train.resilience import Heartbeat

            healthy = not Heartbeat.is_stale(hb.path, self.heartbeat_stale_s)
        rep.state = "ok" if healthy else "degraded"

    def _collect(self) -> None:
        """Harvest finished requests and fail over engine-faulted ones
        (an engine fault fails in-flight rows with status engine_fault;
        queued requests a NON-tripped fault kept are simply re-served
        by the restarted scheduler — no failover needed)."""
        with self._lock:
            live = [f for f in self._requests.values()
                    if not f.done.is_set()]
        for freq in live:
            rep = self.replicas[freq.replica]
            if freq.stream:
                st = rep.engine.try_status(freq.rid)
                if st is not None:
                    with self._lock:
                        self._finish_locked(freq, [], st)
                continue
            got = rep.engine.try_result(freq.rid)
            if got is None:
                continue
            tokens, status = got
            if status == "engine_fault":
                with self._lock:
                    remaining = (freq.deadline - time.perf_counter()
                                 if freq.deadline is not None else None)
                    self._failover_locked(freq, remaining)
                continue
            with self._lock:
                freq.stats = dict(
                    rep.engine.batcher.request_stats.get(freq.rid, {}))
                self._finish_locked(freq, tokens, status)

    def _export_routable_gauge(self) -> None:
        obs_metrics.FLEET_ROUTABLE.set(
            sum(r.routable for r in self.replicas))
