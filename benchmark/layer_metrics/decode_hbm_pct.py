"""Decode against the memory roofline: the bytes the decode steps of the
traced window had to read (int8 weights once a step, keys and values of the
live positions only) over the chip's memory bandwidth, as a share of the
device time the decode programs took. Steps are counted from the trace: runs
of the decode-class programs times the configuration's ``--chunk`` (steps a
segment). Contexts are the rows' own: each token of the window read its
row's prompt and the answer before it."""

from benchmark import flops
from benchmark.measure import class_modules, class_seconds


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace["t0"], run.trace["t1"]
    flags = list(run.hf.get("flags", []))
    if "--chunk" not in flags:
        return None
    chunk = int(flags[flags.index("--chunk") + 1])
    steps = chunk * sum(m["runs"] for m in class_modules(run, "decode").values())
    secs = class_seconds(run, "decode")
    kv_pos = 0.0
    for r in run.rows:
        seen = 0
        for t, k in r.deltas:
            if lo <= t < hi:
                kv_pos += k * (r.prompt_len + seen + k / 2.0)
            seen += k
    if not steps or not secs or not kv_pos:
        return None
    need = (steps * flops.weight_bytes_per_step(run.hf)
            + kv_pos * flops.kv_bytes_per_position(run.hf))
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / secs
