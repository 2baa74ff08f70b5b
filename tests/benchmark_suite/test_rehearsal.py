"""``run.py --rehearsal`` end to end on the CPU at toy widths: the harness's
control flow, the shape of its last line, the plain reference against the
served path (GQA, event splice, prefill waves, decode through the cache), the
control put in the program's place, and a timed path broken underneath."""

import json
import os

import pytest

from benchmark import run

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu",
    reason="the rehearsal runs where the CPU was asked for")


def rehearse(capfd, *argv):
    rc = run.main(list(argv) + ["--rehearsal"])
    out, err = capfd.readouterr()
    lines = [ln for ln in out.splitlines() if ln.strip()]
    return rc, lines, err


@pytest.fixture(scope="module")
def qa_run(request):
    """One cell, rehearsed once for the tests that read its lines."""
    capfd = request.getfixturevalue("capfd_module")
    return rehearse(capfd, "--workload", "mistral7b.camera_qa", "--seed",
                    str(2**31 + 17), "--seconds", "3", "--trace", "0")


@pytest.fixture(scope="module")
def capfd_module(request):
    """A module-scoped stand-in for capfd: capture by file descriptor."""
    from _pytest.capture import FDCapture, MultiCapture

    class Cap:
        def __init__(self):
            self.cap = MultiCapture(in_=None, out=FDCapture(1), err=FDCapture(2))
            self.cap.start_capturing()

        def readouterr(self):
            return self.cap.readouterr()

    capman = request.config.pluginmanager.getplugin("capturemanager")
    capman.suspend_global_capture(in_=False)
    c = Cap()
    yield c
    c.cap.stop_capturing()
    capman.resume_global_capture()


def test_last_line_shape_and_never_correct(qa_run):
    rc, lines, err = qa_run
    assert rc == 0
    last = json.loads(lines[-1])
    assert list(last)[-1] == "check"  # the compared numbers come last
    assert {"correct", "attempted", "failed", "metrics", "device",
            "check"} <= set(last)
    assert last["correct"] is False and last["metrics"] == {}
    assert "rehearsal" in last and last["device"]["platform"] == "cpu"
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["attempted"] > 0 and last["failed"] == 0
    assert all("REHEARSAL" in ln for ln in lines[:-1] if ln.startswith("[bench]"))
    # each number compared is printed beside its limit, last on stderr
    tail = [ln for ln in err.splitlines() if ln.startswith("check ")]
    assert len(tail) == len(last["check"])
    for name, c in last["check"].items():
        assert set(c) == {"value", "limit"}
        assert any(ln.startswith(f"check {name}:") for ln in tail)


def test_reference_agrees_with_the_served_path(qa_run):
    """float32 at toy widths: every served token, read off the stream by the
    client, is the reference's first, so both gaps are nought."""
    last = json.loads(qa_run[1][-1])
    assert last["rehearsal_checks_passed"] is True
    assert last["check"]["served_gap"]["value"] == 0.0
    assert last["check"]["mean_gap"]["value"] == 0.0
    assert last["check"]["tokens_compared"]["value"] >= 3
    assert last["check"]["stream_tokens_lost"]["value"] == 0
    assert last["rehearsal_saw"]["out_tok_per_s"] > 0


def test_the_control_goes_through_the_checks(capfd, monkeypatch):
    """``--control int4``: the lower precision's tokens stand where the served
    ones stood and the same checks decide. With a limit the cell's size would
    set (toy widths flip few tokens by little), the run is not correct, and
    the served reading rides beside it."""
    from benchmark import loader

    real = loader.read_json

    def tight(path):
        d = real(path)
        if path.endswith("mistral7b.camera_qa.json"):
            d["check"]["limit_gap"] = d["check"]["limit_mean_gap"] = 1e-7
            d["rehearsal"]["streams"]["pool"] = 8
            d["rehearsal"]["check"]["sample"] = 8
        return d

    monkeypatch.setattr(loader, "read_json", tight)
    rc, lines, err = rehearse(capfd, "--workload", "mistral7b.camera_qa",
                              "--seed", "5", "--seconds", "4", "--trace", "0",
                              "--control", "int4")
    last = json.loads(lines[-1])
    assert rc == 0 and last["control"] == "int4"
    assert last["served_reading"]["served_gap"] == 0.0
    assert last["check"]["served_gap"]["value"] > 0.0
    assert last["rehearsal_checks_passed"] is False and "NOT OK" in err


def test_a_token_altered_where_it_is_produced_is_not_correct(capfd, monkeypatch):
    from eventgpt_tpu import serve

    real = serve.ContinuousBatcher._finish_row

    def altered(self, r, *a, **kw):
        req = self.rows[r]
        if req is not None and len(req.tokens) >= 2:
            req.tokens[1] = (int(req.tokens[1]) + 101) % 256 + 3
        return real(self, r, *a, **kw)

    monkeypatch.setattr(serve.ContinuousBatcher, "_finish_row", altered)
    rc, lines, err = rehearse(capfd, "--workload", "mistral7b.camera_qa",
                              "--seed", "31", "--seconds", "3", "--trace", "0")
    last = json.loads(lines[-1])
    assert rc == 0 and last["correct"] is False
    assert last["rehearsal_checks_passed"] is False
    assert last["check"]["served_gap"]["value"] > 0.0
    assert "NOT OK" in err


def test_traced_rehearsal_reports_per_layer_names(capfd):
    rc, lines, err = rehearse(capfd, "--workload", "internlm2-1.8b.camera_burst",
                              "--seed", "8", "--seconds", "3", "--trace", "1")
    last = json.loads(lines[-1])
    assert rc == 0 and last["metrics"] == {}
    assert {"gen_late_ms.p90", "queue_wait_ms.p90", "tokens_per_dispatch",
            "compiles_in_window"} <= set(last["rehearsal_saw"])
    # set-up met every admission shape (benchmark/prime.py): none in the window
    assert any("primed admission waves of [1, 2, 3, 4]" in ln for ln in lines)
    assert last["rehearsal_saw"]["compiles_in_window"] == 0
    # a cpu trace has no device plane: no device number under any name
    assert "tpot_ms.p90" in last["rehearsal_saw"]
    assert not any(k.startswith(("device_idle", "decode_", "prefill_", "serve_",
                                 "flash_"))
                   for k in last["rehearsal_saw"])
    assert "busy_s" not in last["device"]


def test_no_tpu_no_result(capfd, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    rc = run.main(["--workload", "mistral7b.camera_qa", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capfd.readouterr()
    assert rc == 2 and out == "" and "No fallback" in err


def test_rehearsal_is_never_chosen_for_you(capfd, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "")
    rc = run.main(["--workload", "mistral7b.camera_qa", "--seed", "1",
                   "--seconds", "1", "--trace", "0", "--rehearsal"])
    out, err = capfd.readouterr()
    assert rc == 2 and out == ""
