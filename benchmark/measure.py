"""From what a run recorded to the table every metric reader works on.

``RunData`` is all a reader sees: the window's bounds, one row a request
(when it was due and sent and when each streamed delta reached the client,
by the harness's clock; beside them its flight-recorder timeline put on the
same ``perf_counter``), the ``obs/trace`` ring and, in a traced run, the
reduced device trace. Readers live in
``benchmark/end_to_end/<name>.py`` and ``benchmark/layer_metrics/<name>.py``
and are found by the metric's name in ``BENCHMARK.json``.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import os
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Row:
    """One request, with times on the process's perf_counter (seconds)."""
    t_due: float
    t_sent: float
    t_done: Optional[float]
    status: str
    rid: Optional[int]
    budget: int
    deltas: List[tuple] = dataclasses.field(default_factory=list)  # client: (t, n)
    t_submit: Optional[float] = None      # the engine took it (after host prep)
    t_admit: Optional[float] = None       # it left the queue
    t_active: Optional[float] = None      # its row was activated
    admit_path: str = ""
    prefix_hit: Optional[bool] = None
    matched: int = 0                      # positions a prefix hit supplied
    prompt_len: int = 0

    @property
    def ok(self) -> bool:
        return self.status == "ok" and bool(self.deltas)

    @property
    def tokens(self) -> int:
        return sum(n for _, n in self.deltas)

    @property
    def t_first(self) -> Optional[float]:
        return self.deltas[0][0] if self.deltas else None

    @property
    def t_last(self) -> Optional[float]:
        return self.deltas[-1][0] if self.deltas else None


@dataclasses.dataclass
class RunData:
    cell: dict
    params: dict
    hf: dict
    t0: float
    t1: float
    rows: List[Row]                       # every request sent, prelude too
    ring: List[dict]                      # the obs/trace ring's events
    compiles_in_window: int
    device_kind: str
    n_chips: int
    peaks: dict
    trace: Optional[dict] = None          # trace_reduce.reduce(...) or None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def window_rows(self) -> List[Row]:
        """Requests that were due inside the window."""
        return [r for r in self.rows if self.t0 <= r.t_due < self.t1]

    def tokens_in(self, lo: float, hi: float) -> int:
        """Answer tokens that reached their clients in [lo, hi), whatever
        request they belong to."""
        return sum(n for r in self.rows for t, n in r.deltas if lo <= t < hi)


@functools.lru_cache(maxsize=None)
def program_classes() -> Dict[str, list]:
    import json
    import re

    with open(os.path.join(HERE, "programs.json")) as f:
        raw = json.load(f)
    return {k: [re.compile(p) for p in v] for k, v in raw.items()
            if not k.startswith("_")}


def class_seconds(run: "RunData", cls: str) -> float:
    """Device seconds, in the traced window, of one class of programs."""
    pats = program_classes()[cls]
    return sum(m["total_s"] for n, m in run.trace["modules"].items()
               if any(p.search(n) for p in pats))


def class_modules(run: "RunData", cls: str) -> Dict[str, dict]:
    pats = program_classes()[cls]
    return {n: m for n, m in run.trace["modules"].items()
            if any(p.search(n) for p in pats)}


def traced_tokens(run: "RunData") -> int:
    return run.tokens_in(run.trace["t0"], run.trace["t1"])


def traced_admissions(run: "RunData", lanes: bool) -> List["Row"]:
    """Requests whose whole admission (left the queue ... row activated) lies
    inside the traced window; ``lanes`` keeps or drops those that prefilled
    as a lane inside decode dispatches."""
    lo, hi = run.trace["t0"], run.trace["t1"]
    out = []
    for r in run.rows:
        if r.t_admit is None or r.t_active is None:
            continue
        if lo <= r.t_admit and r.t_active <= hi:
            if lanes or not r.admit_path.startswith("lane"):
                out.append(r)
    return out


def admission_flops(run: "RunData", rows: List["Row"]) -> float:
    """Tower + projector + decoder prefill of these admissions, by what the
    flight recorder says each had to compute."""
    from benchmark import flops

    total = 0.0
    for r in rows:
        new = max(r.prompt_len - r.matched, 1)
        total += flops.prefill_flops(run.hf, new, r.matched)
        if not r.prefix_hit:
            total += flops.encode_flops(run.hf)
    return total


def phases(rows: List["Row"]) -> Dict[str, List[float]]:
    """Where each answered request's time to its first tokens went, in ms:
    due to sent (the generator), sent to the engine's submit (HTTP, raster,
    preprocess), submit to leaving the queue, the admission (tower, prefill,
    insertion), the row's activation to the first delta at the client; then
    due to first delta, due to the end of the answer, and the answer's
    tokens."""
    out = {"late": [], "http+prep": [], "queued": [], "admission": [],
           "first_segment": [], "ttft": [], "tpot": [], "answer": [],
           "tokens": []}
    for r in rows:
        if not r.ok or None in (r.t_submit, r.t_admit, r.t_active):
            continue
        out["late"].append((r.t_sent - r.t_due) * 1e3)
        out["http+prep"].append((r.t_submit - r.t_sent) * 1e3)
        out["queued"].append((r.t_admit - r.t_submit) * 1e3)
        out["admission"].append((r.t_active - r.t_admit) * 1e3)
        out["first_segment"].append((r.t_first - r.t_active) * 1e3)
        out["ttft"].append((r.t_first - r.t_due) * 1e3)
        if r.tokens >= 2:
            out["tpot"].append((r.t_last - r.t_first) / (r.tokens - 1) * 1e3)
        if r.t_done is not None:
            out["answer"].append((r.t_done - r.t_due) * 1e3)
        out["tokens"].append(float(r.tokens))
    return out


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def rows_from(records, journeys: Dict[int, dict], submit_ts: Dict[int, float]):
    """``records``: the driver's ``Sent`` list. ``journeys``: rid -> the
    flight recorder's exported timeline (times relative to submit).
    ``submit_ts``: rid -> perf_counter of the ``queued`` span's begin."""
    rows = []
    for rec in records:
        row = Row(rec.t_due, rec.t_sent, rec.t_done, rec.status, rec.rid,
                  rec.req.budget, list(rec.deltas))
        j = journeys.get(rec.rid) if rec.rid is not None else None
        base = submit_ts.get(rec.rid) if rec.rid is not None else None
        if j is not None and base is not None:
            row.t_submit = base
            for ev in j.get("events", []):
                t = base + float(ev.get("t_s", 0.0))
                kind = ev.get("kind")
                if kind == "queue" and row.t_admit is None:
                    row.t_admit = t
                elif kind == "lane_join" and not row.admit_path:
                    row.admit_path = "lane:" + str(ev.get("path", ""))
                elif kind == "admit":
                    row.t_active = t
                    if not row.admit_path:
                        row.admit_path = str(ev.get("path", kind))
                elif kind == "prefix":
                    row.prefix_hit = bool(ev.get("hit", False))
                    row.matched = int(ev.get("matched", 0) or 0)
            row.prompt_len = int(j.get("prompt_len", 0) or 0)
            if j.get("status") not in (None, "ok") and row.status == "ok":
                row.status = str(j["status"])
        rows.append(row)
    return rows


def load_reader(kind: str, name: str):
    """The reader of one metric: ``benchmark/<kind>/<name>.py`` with a
    ``read(run) -> float | None``. A metric without its file is an error
    that names it."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"metric {name!r} is in BENCHMARK.json but {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise AttributeError(f"{path}: no read(run)")
    return mod


def metrics_for(bench: dict, cell_name: str, section: str) -> List[dict]:
    """The metrics of one section that this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell_name in m["workloads"]]
