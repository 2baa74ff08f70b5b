"""The one general traffic generator. A traffic mix is a data file of
parameters (``benchmark/workloads/<cell>.json``); this module turns it and
``--seed`` into a schedule. The program never sees any of this: it receives
HTTP requests only.

The arrival processes are copied from the program's
``eventgpt_tpu/workload.py`` ``generate_trace`` (PERF.md Open questions
lists the original for deletion), resized to event-camera traffic: 50 ms
windows of 40,000 events at 640 x 480 (DSEC's sensor), one question about
each window, answer budgets with a heavy tail.

Every seed gets the same work in another order, block by block: a mix is a
train of blocks (``arrivals.block`` requests, 16 unless stated; one on + off
period of an on-off mix), and every block carries the same inter-arrival
gaps (drawn once from the mix's own ``base_seed``, scaled to fill the block)
and the same answer budgets (the lognormal's quantiles), each permuted by
``--seed``, which also chooses streams and questions. Any stretch of whole
blocks is then the same work whatever the seed.

Requests are due on a schedule whether or not earlier ones have finished
(independent cameras: an open loop). ``arrivals.process`` is ``gamma``
(``shape`` < 1 is burstier than Poisson, 1 is Poisson) or ``onoff``
(Poisson inside ``on_s`` bursts, silent for ``off_s``, same mean rate).
"""

from __future__ import annotations

import base64
import dataclasses
import io
import os
from typing import List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Request:
    due_s: float          # offset from the schedule's start
    stream: int           # index into the pool of event streams
    question: str
    budget: int           # max_new_tokens


@dataclasses.dataclass
class Schedule:
    requests: List[Request]            # sorted by due_s
    n_streams: int


def questions(params: dict) -> List[str]:
    path = os.path.join(HERE, params.get("questions", "questions.txt"))
    with open(path) as f:
        out = [line.rstrip("\n") for line in f if line.strip()]
    if not out:
        raise ValueError(f"{path}: no questions")
    if len({len(q.encode()) for q in out}) != 1:
        # The program runs eager array code whose shapes follow the prompt's
        # exact length, so every new length compiles programs mid-request
        # (PERF.md Findings). One length keeps the window free of compiles.
        raise ValueError(f"{path}: questions must be of one byte length")
    return out


def _budget_block(params: dict, size: int) -> np.ndarray:
    """``size`` answer budgets that stand for the mix's lognormal: its
    quantiles at (i + 0.5) / size, clipped. The same for every seed."""
    from statistics import NormalDist

    b = params["budget"]
    if b.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"budget.dist {b['dist']!r}: only lognormal")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / size) for i in range(size)])
    draws = np.exp(np.log(b["median"]) + b.get("sigma", 0.6) * z)
    return np.clip(np.rint(draws), b["min"], b["max"]).astype(int)


def _gap_block(arr: dict, size: int, span_s: float, rng_base) -> np.ndarray:
    """``size`` inter-arrival gaps that fill ``span_s`` exactly and stand for
    the arrival process: the quantiles at (i + 0.5) / size of a large sample
    drawn from the mix's base seed. The same for every run seed."""
    proc = arr["process"]
    if proc == "onoff":
        sample = rng_base.exponential(1.0, 65536)
    elif proc == "gamma":
        sample = rng_base.gamma(arr["shape"], 1.0, 65536)
    else:
        raise ValueError(f"arrivals.process {proc!r}")
    g = np.quantile(sample, (np.arange(size) + 0.5) / size)
    return g * (span_s / g.sum())


def block_of(params: dict) -> tuple:
    """(requests a block, seconds a block, seconds of it in which requests
    arrive). Every block of a mix carries the same gaps and the same
    answer budgets, in an order drawn from the run's seed, so any stretch of
    whole blocks is the same work whatever the seed. An on-off mix's block
    is one on + off period."""
    arr = params["arrivals"]
    rate = float(arr["rate_per_s"])
    if arr.get("process") == "onoff":
        period = float(arr["on_s"]) + float(arr["off_s"])
        return max(1, int(round(rate * period))), period, float(arr["on_s"])
    size = int(arr.get("block", 16))
    return size, size / rate, size / rate


def burst_schedule(params: dict, seed: int) -> Schedule:
    """The prelude of a mix: bursts of ``prelude.bursts`` requests due
    at one instant each, ``prelude.gap_s`` apart, so that set-up meets the
    admission-wave sizes the window can meet. Same pool, questions and
    budgets as the mix itself; other draws."""
    pre = params["prelude"]
    rng = np.random.default_rng([int(seed), 0xB0057])
    qs = questions(params)
    pool = int(params["streams"]["pool"])
    sizes = [int(k) for k in pre["bursts"]]
    budgets = rng.permutation(np.resize(_budget_block(params, 16), sum(sizes)))
    reqs, i = [], 0
    for b, k in enumerate(sizes):
        for _ in range(k):
            reqs.append(Request(b * float(pre["gap_s"]), i % pool,
                                qs[int(rng.integers(0, len(qs)))],
                                int(min(budgets[i], pre.get("max_budget", 1 << 30)))))
            i += 1
    return Schedule(reqs, pool)


def build_schedule(params: dict, seed: int, seconds: float) -> Schedule:
    base = np.random.default_rng(int(params.get("base_seed", 0)))
    rng = np.random.default_rng(int(seed))
    qs = questions(params)
    pool = int(params["streams"]["pool"])
    size, period, on_s = block_of(params)
    gaps = _gap_block(params["arrivals"], size, on_s, base)
    budgets = _budget_block(params, size)
    first = int(rng.integers(0, pool))
    reqs = []
    for blk in range(int(np.ceil(seconds / period))):
        g = rng.permutation(gaps)
        # the first arrival not always a whole gap into the block
        due = blk * period + np.cumsum(g) - g[0] * rng.random()
        for t, bud in zip(due, rng.permutation(budgets)):
            if t < seconds:
                reqs.append(Request(float(t), (first + len(reqs)) % pool,
                                    qs[int(rng.integers(0, len(qs)))],
                                    int(bud)))
    return Schedule(reqs, pool)


def event_stream_npy(rng: np.random.Generator, spec: dict) -> bytes:
    """One seeded event stream as the ``.npy`` a client uploads: the
    program's structured layout (x, y: u2; t: u8 microseconds; p: u1)."""
    n = int(spec["events"])
    dt = np.dtype([("x", "<u2"), ("y", "<u2"), ("t", "<u8"), ("p", "u1")])
    ev = np.zeros(n, dt)
    ev["x"] = rng.integers(0, int(spec["width"]), n)
    ev["y"] = rng.integers(0, int(spec["height"]), n)
    ev["t"] = np.sort(rng.integers(0, int(spec["window_us"]), n))
    ev["p"] = rng.integers(0, 2, n)
    buf = io.BytesIO()
    np.save(buf, ev)
    return buf.getvalue()


def stream_pool(params: dict, seed: int) -> List[bytes]:
    """The pool of distinct streams, each base64-encoded once, in set-up."""
    spec = params["streams"]
    rng = np.random.default_rng([int(seed), 0x5EED])
    return [base64.b64encode(event_stream_npy(rng, spec))
            for _ in range(int(spec["pool"]))]
