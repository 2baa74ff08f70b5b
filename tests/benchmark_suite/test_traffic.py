"""The general generator: seeded, the same work for every seed, open-loop due
times inside the window."""

import base64
import io
import os

import numpy as np
import pytest

from benchmark import loader, traffic
from benchmark.run import merged

CELLS = ["mistral7b.camera_qa", "internlm2-1.8b.camera_burst"]


def params_of(cell, rehearsal=False):
    path = os.path.join(loader.HERE, "workloads", cell + ".json")
    return merged(loader.read_json(path), rehearsal)


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_schedule(cell):
    p = params_of(cell)
    a = traffic.build_schedule(p, 2**31 + 11, 51.0)
    b = traffic.build_schedule(p, 2**31 + 11, 51.0)
    assert a == b
    c = traffic.build_schedule(p, 12, 51.0)
    assert a != c


@pytest.mark.parametrize("cell", CELLS)
def test_open_loop_same_work_in_another_order(cell):
    p = params_of(cell)
    a = traffic.build_schedule(p, 1, 51.0)
    b = traffic.build_schedule(p, 2, 51.0)
    n = round(p["arrivals"]["rate_per_s"] * 51.0)
    assert len(a.requests) == len(b.requests) == n
    assert sorted(r.budget for r in a.requests) == sorted(
        r.budget for r in b.requests)
    for s in (a, b):
        due = [r.due_s for r in s.requests]
        assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 51.0
        lo, hi = p["budget"]["min"], p["budget"]["max"]
        assert all(lo <= r.budget <= hi for r in s.requests)
        # whole blocks are alike: the same budgets and the same time taken
        size, period, _ = traffic.block_of(p)
        for k in range(int(51.0 / period)):
            blk = [r for r in s.requests if k * period <= r.due_s < (k + 1) * period]
            assert len(blk) == size
            assert sorted(r.budget for r in blk) == sorted(
                r.budget for r in s.requests[:size])
        # a fresh window each: consecutive requests never share a stream
        assert all(x.stream != y.stream
                   for x, y in zip(s.requests, s.requests[1:]))


def test_onoff_arrivals_fall_in_the_on_periods():
    p = params_of("internlm2-1.8b.camera_burst")
    on, off = p["arrivals"]["on_s"], p["arrivals"]["off_s"]
    s = traffic.build_schedule(p, 7, 51.0)
    assert all((r.due_s % (on + off)) < on + 1e-9 for r in s.requests)


def test_gamma_is_burstier_than_poisson():
    p = params_of("mistral7b.camera_qa")
    q = {**p, "arrivals": {**p["arrivals"], "shape": 1.0}}  # Poisson
    cv = lambda s: np.std(np.diff([r.due_s for r in s.requests])) / np.mean(
        np.diff([r.due_s for r in s.requests]))
    assert cv(traffic.build_schedule(p, 3, 400.0)) > 1.2 > 0.8 < cv(
        traffic.build_schedule(q, 3, 400.0))


def test_an_unknown_arrival_process_is_refused():
    p = params_of("mistral7b.camera_qa")
    with pytest.raises(ValueError, match="arrivals.process"):
        traffic.build_schedule(
            {**p, "arrivals": {**p["arrivals"], "process": "uniform"}}, 1, 5.0)


def test_both_cells_offer_more_than_their_knee():
    """PERF.md's sweeps: knee 3 requests/s at 7B, 6-8 at 1.8B. Below the knee
    no latency held a bound (PERF.md section 2), so both are throughput cells."""
    assert params_of("mistral7b.camera_qa")["arrivals"]["rate_per_s"] == 4.0
    assert params_of("internlm2-1.8b.camera_burst")["arrivals"][
        "rate_per_s"] == 10.0


def test_burst_prelude_covers_the_wave_sizes():
    p = params_of("mistral7b.camera_qa")
    s = traffic.burst_schedule(p, 9)
    sizes = {}
    for r in s.requests:
        sizes[r.due_s] = sizes.get(r.due_s, 0) + 1
    assert sorted(sizes.values()) == sorted(p["prelude"]["bursts"])
    assert all(r.budget <= p["prelude"]["max_budget"] for r in s.requests)


def test_stream_pool_is_seeded_and_in_the_sensor():
    p = params_of("mistral7b.camera_qa", rehearsal=True)
    a, b = traffic.stream_pool(p, 2**31 + 5), traffic.stream_pool(p, 2**31 + 5)
    assert a == b and a != traffic.stream_pool(p, 6)
    assert len(a) == p["streams"]["pool"] and len(set(a)) == len(a)
    ev = np.load(io.BytesIO(base64.b64decode(a[0])))
    assert len(ev) == p["streams"]["events"]
    assert ev["x"].max() < p["streams"]["width"]
    assert ev["y"].max() < p["streams"]["height"]
    assert (np.diff(ev["t"].astype(np.int64)) >= 0).all()
    assert ev["t"].max() < p["streams"]["window_us"]


def test_questions_are_of_one_length():
    qs = traffic.questions({})
    assert len(qs) >= 8 and len({len(q.encode()) for q in qs}) == 1
