"""The reduction from a profiler trace to numbers: interval arithmetic by
hand, and a small trace recorded on a TPU v5 lite, checked in beside this
file (``data/small_tpu.xplane.pb``: three runs of one jitted program with
idle gaps between them)."""

import os

import numpy as np
import pytest

from benchmark import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_tpu.xplane.pb")


def test_union_of_intervals():
    iv = np.array([[0, 10], [5, 12], [20, 30], [30, 31], [100, 101]], np.int64)
    total, merged = trace_reduce.union_ns(iv)
    assert total == 12 + 11 + 1
    assert merged.tolist() == [[0, 12], [20, 31], [100, 101]]
    assert trace_reduce.union_ns(np.zeros((0, 2), np.int64))[0] == 0
    # nested and unsorted
    total, merged = trace_reduce.union_ns(np.array([[50, 60], [0, 100], [10, 20]]))
    assert total == 100 and merged.tolist() == [[0, 100]]


def test_self_time_takes_nested_rows_out():
    # a while of 100 with two bodies of 30 and 40 inside, the second holding
    # a fusion of 10; then an operation on its own
    iv = np.array([[0, 100], [10, 40], [50, 90], [60, 70], [200, 250]], np.int64)
    assert trace_reduce.self_ns(iv).tolist() == [30, 30, 30, 10, 50]
    assert trace_reduce.self_ns(iv).sum() == trace_reduce.union_ns(iv)[0]


def test_module_name_drops_the_fingerprint():
    assert trace_reduce.module_name("jit__decode_segment(1094003569)") == \
        "jit__decode_segment"
    assert trace_reduce.module_name("jit_step") == "jit_step"


def test_no_trace_is_an_error(tmp_path):
    assert trace_reduce.find_xplane(str(tmp_path)) is None
    with pytest.raises(FileNotFoundError):
        trace_reduce.reduce_dir(str(tmp_path))


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_tpu_trace():
    r = trace_reduce.reduce_file(DATA, window_s=None)
    step = r["modules"]["jit_small_step"]
    assert step["runs"] == 3
    assert 0 < step["median_s"] < 0.01
    # busy is the union of the operations, inside the span they cover
    assert 0 < r["busy_s"] <= r["span_s"] == r["window_s"]
    assert r["busy_s"] == pytest.approx(step["total_s"], rel=0.2)
    # 20 ms of sleep between runs: the device idles most of the span
    assert r["busy_s"] / r["span_s"] < 0.2
    ops = r["breakdown"]["device_ops"]
    assert 1 <= len(ops) <= 10 and ops == sorted(ops, key=lambda o: -o[1])
    gaps = r["breakdown"]["idle_gaps"]
    assert 2 <= len(gaps) <= 10 and gaps[0][1] > 0.015
    assert sum(o["total_s"] for o in r["ops"].values()) == pytest.approx(
        r["busy_s"], rel=0.05)
    # a longer stated window only adds idle time
    wide = trace_reduce.reduce_file(DATA, window_s=1.0)
    assert wide["window_s"] == 1.0 and wide["busy_s"] == r["busy_s"]


def test_program_classes_are_families_of_names():
    """A program renamed within its family, or a new one of it, is counted."""
    from benchmark import measure

    cls = measure.program_classes()
    hit = lambda c, n: any(p.search(n) for p in cls[c])
    for name, want in [("jit__decode_segment", "decode"),
                       ("jit__mixed_spec_segment", "decode"),
                       ("jit__decode_segment_v2", "decode"),
                       ("jit__prefill_jit", "prefill"),
                       ("jit__prefix_prefill", "prefill"),
                       ("jit_encode_events_batch", "encode"),
                       ("jit__admit_wave_paged", "admit")]:
        assert [c for c in ("encode", "prefill", "decode", "admit")
                if hit(c, name)] == [want]
    assert not any(hit(c, "jit__pad") for c in ("encode", "prefill", "decode",
                                                "admit"))
