"""Admission against the chip's bf16 peak: FLOP of tower + projector +
decoder prefill for the admissions that lie wholly inside the traced window
and ran as programs of their own (not as lanes inside decode dispatches),
over the device time of the encode and prefill programs in that window times
the peak. Admissions cut by the window's edges are left out of the FLOP and
not of the time, so the share errs low."""

from benchmark.measure import (admission_flops, class_seconds,
                               traced_admissions)


def read(run):
    if run.trace is None:
        return None
    rows = traced_admissions(run, lanes=False)
    secs = class_seconds(run, "prefill") + class_seconds(run, "encode")
    if not rows or not secs:
        return None
    return 100.0 * admission_flops(run, rows) / (
        secs * run.peaks["bf16_flops_per_s"])
