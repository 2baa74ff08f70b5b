"""Set-up as the program's own ring recorded it.

``obs/trace.configure()`` writes ``ring_start`` as a fresh ring's first event
and, from then on, every trace, lowering and XLA compile (or persistent-cache
load) JAX reports, as ``compile.jaxpr``, ``compile.lower`` and ``compile.xla``
spans (``args.fun``, ``args.parent``; ``compile.xla`` also ``args.cache``:
``hit``, ``miss`` or ``off``), beside the ``setup.*`` phases of
``cli/serve.py`` ``build_engine``. ``of_run`` reads the events that began
before the window (``run.t0``) once a run:

* ``compile_s``: the union of the ``compile.*`` intervals on each thread,
  summed over threads (a nested trace counts once);
* ``programs``: the ``compile.xla`` spans, compiled or loaded
  (``compiles_in_window``'s counting rule, before the window);
* ``cache_hits``: those that read ``cache`` ``hit``;

and writes the table behind them to standard error and to
``.bench_out/setup_by_program.json``: the phases' seconds, compile seconds by
``parent`` and by phase, and each program by ``fun``, longest first.

A ring without ``ring_start`` (overwritten, or a program from before the PR
that brought it) or without a ``compile.xla`` span gives ``None``; nothing
raises.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TABLE_PATH = os.path.join(ROOT, ".bench_out", "setup_by_program.json")
RING_START = "ring_start"
AFTER_BUILD = "(after setup.build)"


def union_s(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            total += b - a
            reach = b
        elif b > reach:
            total += b - reach
            reach = b
    return total


def _by_thread(spans: List[dict]) -> Dict[int, List[Tuple[float, float]]]:
    out: Dict[int, List[Tuple[float, float]]] = {}
    for e in spans:
        out.setdefault(e.get("tid", 0), []).append(
            (e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6))
    return out


def _union_over_threads(spans: List[dict]) -> float:
    return sum(union_s(iv) for iv in _by_thread(spans).values())


def _phase_of(e: dict, phases: List[dict]) -> str:
    """The innermost ``setup.*`` span of the same thread that holds ``e``'s
    start."""
    best = None
    for p in phases:
        if (p.get("tid") == e.get("tid") and p["ts"] <= e["ts"] < p["ts"] + p["dur"]
                and (best is None or p["dur"] < best["dur"])):
            best = p
    return f"setup.{best['name']}" if best else AFTER_BUILD


def table(ring: List[dict], t0: float) -> Optional[dict]:
    """The set-up table of one ring, from the events that began before
    ``t0`` (seconds, ``perf_counter``); ``None`` as the module says."""
    lo = t0 * 1e6
    before = [e for e in ring if e.get("ts", lo) < lo]
    if not any(e.get("name") == RING_START and e.get("ph") == "i"
               for e in before):
        return None
    spans = [e for e in before if e.get("ph") == "X"]
    compiles = [e for e in spans if e.get("cat") == "compile"]
    xla = [e for e in compiles if e["name"] == "xla"]
    if not xla:
        return None
    phases = [e for e in spans if e.get("cat") == "setup"]
    cats = {e["name"]: e.get("cat", "?") for e in spans}

    phase_s: Dict[str, float] = {}
    for p in phases:
        key = f"setup.{p['name']}"
        phase_s[key] = phase_s.get(key, 0.0) + p["dur"] / 1e6
    groups: Dict[str, Dict[str, List[dict]]] = {"parent": {}, "phase": {}}
    for e in compiles:
        parent = (e.get("args") or {}).get("parent")
        key = f"{cats.get(parent, '?')}.{parent}" if parent else "(none)"
        groups["parent"].setdefault(key, []).append(e)
        groups["phase"].setdefault(_phase_of(e, phases), []).append(e)

    programs: Dict[str, dict] = {}
    for e in xla:
        args = e.get("args") or {}
        p = programs.setdefault(args.get("fun", "?"),
                                {"n": 0, "s": 0.0, "hits": 0})
        p["n"] += 1
        p["s"] += e["dur"] / 1e6
        p["hits"] += args.get("cache") == "hit"

    def ordered(d):
        return dict(sorted(d.items(), key=lambda kv: -kv[1]))

    return {
        "compile_s": _union_over_threads(compiles),
        "programs": len(xla),
        "cache_hits": sum((e.get("args") or {}).get("cache") == "hit"
                          for e in xla),
        "xla_s": sum(e["dur"] for e in xla) / 1e6,
        "phases_s": ordered(phase_s),
        "compile_s_by_parent": ordered({k: _union_over_threads(v)
                                        for k, v in groups["parent"].items()}),
        "compile_s_by_phase": ordered({k: _union_over_threads(v)
                                       for k, v in groups["phase"].items()}),
        "by_program": dict(sorted(programs.items(),
                                  key=lambda kv: -kv[1]["s"])),
        "ring_events": len(ring),
    }


def of_run(run) -> Optional[dict]:
    """``table`` of the run's ring, computed and published once a run."""
    cached = getattr(run, "_setup_table", False)
    if cached is not False:
        return cached
    t = table(run.ring, run.t0)
    run._setup_table = t
    if t is not None:
        _publish(t)
    return t


def _publish(t: dict) -> None:
    head = {k: v for k, v in t.items() if k != "by_program"}
    sys.stderr.write("setup_by_program " + json.dumps(head) + "\n")
    try:
        os.makedirs(os.path.dirname(TABLE_PATH), exist_ok=True)
        with open(TABLE_PATH, "w") as f:
            json.dump(t, f, indent=1)
    except OSError:
        pass
