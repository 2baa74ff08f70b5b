"""Answer tokens that reached their clients in the window over the scheduler's ``dispatch``
spans (the obs/trace ring) that began in it."""


def read(run):
    lo, hi = run.t0 * 1e6, run.t1 * 1e6
    n = sum(1 for e in run.ring if e.get("name") == "dispatch"
            and e.get("ph") == "X" and lo <= e.get("ts", 0) < hi)
    tokens = run.tokens_in(run.t0, run.t1)
    return tokens / n if n and tokens else None
