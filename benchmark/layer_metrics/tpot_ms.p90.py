"""90th percentile over requests of the time per output token of a whole
answer: (last delta - first delta) / (tokens - 1) at the client, over every
request due in the window that finished ``ok`` with at least two tokens.
Tokens arrive a segment at a time, so single gaps are bursts by
construction; this is the pace the reader of an answer feels, and admissions
of other requests stretch it."""

from benchmark.measure import percentile


def read(run):
    v = [(r.t_last - r.t_first) / (r.tokens - 1) * 1e3
         for r in run.window_rows() if r.ok and r.tokens >= 2]
    return percentile(v, 90) if v else None
