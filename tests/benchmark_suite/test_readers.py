"""Per-layer readers on hand-made run data: what each takes from the reduced
trace, and that a reader with nothing to read returns nothing."""

import os

import pytest

from benchmark import flops, loader, measure

PEAKS = loader.read_json(os.path.join(loader.HERE, "peaks.json"))["TPU v5 lite"]
HF = loader.read_json(os.path.join(loader.HERE, "configs",
                                   "mistral-7b-event.json"))
# The flash kernel's event as the chip's trace names it (my chip run, PR 24).
FLASH = ("%_flash_forward.6 = bf16[512,896,128]{2,1,0:T(8,128)(2,1)} "
         "custom-call(bf16[512,896,128]{2,1,0:T(8,128)(2,1)} %bitcast.1, ...)")


def run_with(trace):
    return measure.RunData(
        cell={"name": "c"}, params={}, hf=HF, t0=0.0, t1=10.0, rows=[],
        ring=[], compiles_in_window=0, device_kind="TPU v5 lite",
        n_chips=1, peaks=PEAKS, trace=trace)


def test_flash_roofline_reads_the_custom_call_by_its_shapes():
    reader = measure.load_reader("layer_metrics", "flash_roofline")
    secs = 0.192
    run = run_with({"ops": {
        FLASH: {"runs": 32, "total_s": secs},
        "%fusion.1 = bf16[16,896,4096] fusion(...)": {"runs": 32, "total_s": 1.0},
        "%other = bf16[4,4,4] custom-call(...), custom_call_target=\"Sharding\"":
            {"runs": 1, "total_s": 1.0}}})
    call = flops.flash_call(896, 896, 512, 128)
    assert call["flop"] == 4 * 512 * 128 * 896 * 896 / 2
    least, bound = flops.roofline_s(call["flop"], call["bytes"], PEAKS)
    assert bound == "memory"  # at one prompt bucket the bounds all but meet
    assert reader.read(run) == pytest.approx(100.0 * 32 * least / secs)
    assert 0 < reader.read(run) < 100


@pytest.mark.parametrize("name", [m["name"] for m in
                                  loader.read_benchmark()["per_layer"]
                                  if m["source"] == "device_trace"])
def test_a_device_reader_with_no_trace_returns_nothing(name):
    assert measure.load_reader("layer_metrics", name).read(run_with(None)) is None


def test_flash_roofline_finds_nothing_without_the_kernel():
    reader = measure.load_reader("layer_metrics", "flash_roofline")
    assert reader.read(run_with({"ops": {
        "%fusion.1 = bf16[16,896,4096] fusion(...)": {"runs": 3,
                                                       "total_s": 1.0}}})) is None
