"""``staged_admit_pct`` on hand-made rings: the share of the window's admitted
requests whose first half was dispatched behind a segment in flight."""

import pytest

from benchmark import loader, measure

CELLS = ["mistral7b.camera_qa", "internlm2-1.8b.camera_burst",
         "nemotron3-super.camera_describe"]


def run_with(ring):
    return measure.RunData(
        cell={"name": "c"}, params={}, hf={}, t0=10.0, t1=20.0, rows=[],
        ring=list(ring), compiles_in_window=0, device_kind="TPU v5 lite",
        n_chips=1, peaks={}, trace=None)


def admit(at_s, rids, staged=None, path="wave"):
    args = {"rids": list(rids), "n": len(rids), "path": path}
    if staged is not None:
        args["staged"] = staged
    return {"name": "admit", "ph": "X", "cat": "sched", "ts": at_s * 1e6,
            "dur": 2e3, "args": args}


def staged_wave(at_s, rids):
    """A staged wave's two spans: its staging, then its landing."""
    return [admit(at_s, rids, len(rids)), admit(at_s + 0.08, rids, len(rids))]


def read(ring):
    return measure.load_reader("layer_metrics", "staged_admit_pct").read(
        run_with(ring))


@pytest.mark.parametrize("ring, want", [
    # every member staged: counted once, though each has two spans
    (staged_wave(11, [1, 2, 3]) + staged_wave(12, [4]), 100.0),
    # none: the drained path writes staged = 0
    ([admit(11, [1, 2], 0), admit(12, [3], 0, "row")], 0.0),
    # three of five; spans outside the window and other names do not count
    (staged_wave(11, [1, 2]) + [admit(12, [3, 4], 0)] + staged_wave(13, [5])
     + staged_wave(9, [8, 9]) + [admit(25, [7], 0)]
     + [{**admit(12, [6], 0), "name": "dispatch"}], 60.0),
    # a landing whose staging began before the window still names its members
    ([admit(10.01, [1, 2], 2), admit(11, [3, 4], 0)], 50.0),
])
def test_the_share_of_members_staged(ring, want):
    assert read(ring) == pytest.approx(want)


@pytest.mark.parametrize("ring", [
    [],
    [admit(11, [1, 2]), admit(12, [3])],        # a parent's ring: no such arg
    [admit(25, [1], 1)],                        # nothing in the window
])
def test_nothing_to_read_gives_nothing(ring):
    assert read(ring) is None


def test_benchmark_json_lists_it_for_every_cell():
    bench = loader.read_benchmark()
    (m,) = [m for m in bench["per_layer"] if m["name"] == "staged_admit_pct"]
    assert m == {"name": "staged_admit_pct", "unit": "%", "better": "higher",
                 "source": "program_span", "layer": "scheduler",
                 "moves": "out_tok_per_s", "workloads": CELLS}
    assert bench["per_layer"][-1] is m          # appended, nothing moved
