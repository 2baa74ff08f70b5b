"""Median duration of the ``engine.lock_wait`` spans (the ``obs/trace``
ring) that began in the window: a handler thread, in ``submit_ids``, from
asking for ``ServingEngine._lock`` to holding it. Nothing where the program
records no such span."""

from benchmark.measure import percentile


def read(run):
    lo, hi = run.t0 * 1e6, run.t1 * 1e6
    v = [e["dur"] / 1e3 for e in run.ring
         if e.get("name") == "lock_wait" and e.get("ph") == "X"
         and lo <= e.get("ts", 0) < hi]
    return percentile(v, 50) if v else None
