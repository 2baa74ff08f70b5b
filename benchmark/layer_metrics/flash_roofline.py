"""The Pallas flash-attention kernel against its roofline: the least time the
chip could take for the calls of the traced window (FLOP and bytes from each
call's shapes, benchmark/flops.py: a causal prompt-bucket square at the
decoder's head count) over the device time of the kernel's events. The
kernel's events are told from others by the op names in
benchmark/programs.json; where none is found the reader returns nothing."""

import re

from benchmark import flops
from benchmark.measure import program_classes


def read(run):
    if run.trace is None:
        return None
    pats = program_classes()["flash_kernel_op"]
    calls = secs = 0
    least = 0.0
    for name, op in run.trace["ops"].items():
        low = name.lower()
        if "custom-call" not in low or not any(p.search(low) for p in pats):
            continue
        # The kernel's output, bf16[rows x heads, positions, head size]
        # (seen on the chip: ``%_flash_forward.6 = bf16[512,896,128]
        # custom-call(...)`` for a wave of 16 at 32 heads), gives the call's
        # shapes.
        m = re.search(r"=\s*bf16\[(\d+),(\d+),(\d+)\]", name)
        if not m:
            continue
        bh, s, hd = (int(x) for x in m.groups())
        call = flops.flash_call(s, s, bh, hd)
        t, _ = flops.roofline_s(call["flop"], call["bytes"], run.peaks)
        least += t * op["runs"]
        secs += op["total_s"]
        calls += op["runs"]
    if not calls or not secs:
        return None
    return 100.0 * least / secs
