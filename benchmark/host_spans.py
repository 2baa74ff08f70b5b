"""The program's spans on the device trace's clock.

While the profiler is armed, every ``obs.trace.span`` of the program also
holds a ``jax.profiler.TraceAnnotation`` named ``<cat>.<name>``, so a traced
run's ``.xplane.pb`` holds the program's intervals as host events beside the
device's operations, on one clock. ``of_run`` reads that file once a run:

* the busy intervals of the first device plane, exactly as
  ``device_idle_pct`` takes them (``trace_reduce.union_ns`` over the
  ``XLA Ops`` line), and with them the idle time inside any interval;
* the annotations of the engine thread (the host line that holds
  ``engine.step``), flattened to the innermost span open at each instant:
  idle seconds by span. ``engine.step`` and ``sched.admit`` hold other spans
  and do not count as cover by themselves: idle time whose innermost span is
  one of them, or none, is what the measurement cannot see.

A program without these spans (the parent of the PR that brought them) gives
``None`` from every reader here; nothing raises.
"""

from __future__ import annotations

import functools
import json
import os
import sys
from typing import Dict, List, Optional

import numpy as np

from benchmark import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")   # run.py's
TABLE_PATH = os.path.join(ROOT, ".bench_out", "idle_by_span.json")
CATS = ("http.", "engine.", "sched.", "admit.")
ENGINE_MARK = "engine.step"
CONTAINERS = ("engine.step", "sched.admit")
NO_SPAN = "(no span)"
EDGES = "(window edges)"


# -- the file, once a run -----------------------------------------------------

@functools.lru_cache(maxsize=4)
def read_file(path: str) -> dict:
    """Everything the readers below need of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, threads = [], []
    for plane in pd.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            devices.append((int(m.group(2)), plane))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans = [(ev.name, int(ev.start_ns),
                          int(ev.start_ns) + int(ev.duration_ns))
                         for ev in ln.events if ev.name.startswith(CATS)]
                if spans:
                    threads.append(spans)
    busy = np.zeros((0, 2), np.int64)
    if devices:
        first = min(devices, key=lambda d: d[0])[1]
        lines = {ln.name: ln for ln in first.lines}
        src = lines.get(trace_reduce.OPS_LINE) or lines.get(
            trace_reduce.MODULES_LINE)
        if src is not None:
            _, busy = trace_reduce.union_ns(trace_reduce._events(src)[1])
    engine = next((t for t in threads
                   if any(n == ENGINE_MARK for n, _, _ in t)), None)
    return {"busy": busy, "engine": engine}


def of_run(run) -> Optional[dict]:
    """The traced run's file, read once; ``None`` without a trace."""
    if run.trace is None:
        return None
    path = run.trace.get("xplane_path") or trace_reduce.find_xplane(TRACE_DIR)
    if not path or not os.path.exists(path):
        return None
    return read_file(path)


# -- idle time by span ---------------------------------------------------------

def idle_between(busy: np.ndarray, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Idle ns inside each [a, b), clipped to the span of the device's
    operations: the interval's length less the busy time inside it."""
    if not len(busy):
        return np.zeros(len(a), np.int64)
    lo, hi = busy[0, 0], busy[-1, 1]
    a, b = np.clip(a, lo, hi), np.clip(b, lo, hi)
    done = np.concatenate([[0], np.cumsum(busy[:, 1] - busy[:, 0])])

    def busy_before(t):
        i = np.searchsorted(busy[:, 0], t, side="right")   # intervals begun
        last = np.maximum(i - 1, 0)
        inside = np.minimum(t, busy[last, 1]) - busy[last, 0]
        return np.where(i > 0, done[last] + inside, 0)

    return np.maximum((b - a) - (busy_before(b) - busy_before(a)), 0)


def innermost(spans: List[tuple]) -> List[tuple]:
    """One thread's nested (name, start, end) spans as disjoint
    (name, start, end) pieces, each named after the innermost span open in
    it."""
    out, stack = [], []          # stack: [name, end]; cursor: time reached
    cursor = None
    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= a:
            top = stack.pop()
            if top[1] > cursor:
                out.append((top[0], cursor, top[1]))
                cursor = top[1]
        if stack and a > cursor:
            out.append((stack[-1][0], cursor, a))
        cursor = a if cursor is None else max(cursor, a)
        stack.append([name, max(b, a)])
    while stack:
        top = stack.pop()
        if top[1] > cursor:
            out.append((top[0], cursor, top[1]))
            cursor = top[1]
    return out


def idle_by_span(run) -> Optional[dict]:
    """Where the device's idle time of the traced window lies, by the
    innermost span of the engine thread. ``None`` where the file holds no
    such spans. Seconds; ``idle_s`` is ``device_idle_pct``'s own
    (window - busy), and the rows sum to it: what lies before the engine
    thread's first annotation or after its last one (or outside the device's
    own first and last operation) is ``(window edges)``."""
    x = of_run(run)
    if x is None or x["engine"] is None or not len(x["busy"]):
        return None
    if "idle_by_span" in run.trace:      # the three idle readers share it
        return run.trace["idle_by_span"]
    busy, spans = x["busy"], x["engine"]
    pieces = innermost(spans)
    a = np.asarray([p[1] for p in pieces], np.int64)
    b = np.asarray([p[2] for p in pieces], np.int64)
    by: Dict[str, float] = {}
    for (name, _, _), ns in zip(pieces, idle_between(busy, a, b)):
        by[name] = by.get(name, 0.0) + float(ns) / 1e9
    # Between the engine thread's first and last annotation the host was
    # being recorded: idle time there under no span is the program's. The
    # device plane starts before the host tracer is up on every thread and
    # may end after it; idle time out there is the window's edge.
    recorded = float(idle_between(busy, a[:1], b[-1:])[0]) / 1e9
    idle_s = max(run.trace["window_s"] - run.trace["busy_s"], 0.0)
    by[NO_SPAN] = max(recorded - sum(by.values()), 0.0)
    by[EDGES] = max(idle_s - recorded, 0.0)

    def within(name):
        iv = np.asarray([(s, e) for n, s, e in spans if n == name],
                        np.int64).reshape(-1, 2)
        return float(idle_between(busy, iv[:, 0], iv[:, 1]).sum()) / 1e9

    seen = sum(v for k, v in by.items()
               if k not in CONTAINERS + (NO_SPAN, EDGES))
    table = {
        "window_s": run.trace["window_s"], "idle_s": idle_s,
        "by_innermost_span_s": dict(sorted(by.items(), key=lambda kv: -kv[1])),
        "upload_s": within("admit.upload"),
        "admit_s": within("sched.admit"),
        "unspanned_s": max(idle_s - seen, 0.0),
    }
    run.trace["idle_by_span"] = table
    _publish(table)
    return table


def _publish(table: dict) -> None:
    """The whole table to standard error and to
    ``.bench_out/idle_by_span.json`` (``breakdown`` is trace_reduce's)."""
    sys.stderr.write("idle_by_span " + json.dumps(table) + "\n")
    try:
        os.makedirs(os.path.dirname(TABLE_PATH), exist_ok=True)
        with open(TABLE_PATH, "w") as f:
            json.dump(table, f, indent=1)
    except OSError:
        pass


def idle_pct(run, key: str) -> Optional[float]:
    """``upload_s`` / ``admit_s`` / ``unspanned_s`` of the table over the
    traced window, in percent."""
    table = idle_by_span(run)
    if table is None or not table["window_s"]:
        return None
    return 100.0 * table[key] / table["window_s"]
