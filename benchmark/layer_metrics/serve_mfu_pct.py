"""The whole serving step against the chip's bf16 peak: every model FLOP of
the traced window (admissions wholly inside it, lanes too, and each token
that reached its client in it at its row's context) over window x chips x peak."""

from benchmark import flops
from benchmark.measure import admission_flops, traced_admissions


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace["t0"], run.trace["t1"]
    total = admission_flops(run, traced_admissions(run, lanes=True))
    for r in run.rows:
        n = sum(k for t, k in r.deltas if lo <= t < hi)
        if n:
            total += n * flops.decode_flops(run.hf, r.prompt_len + r.tokens // 2)
    if not total:
        return None
    return 100.0 * total / ((hi - lo) * run.n_chips
                            * run.peaks["bf16_flops_per_s"])
