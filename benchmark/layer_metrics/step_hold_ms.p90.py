"""90th percentile of the duration of the ``engine.step`` spans (the
``obs/trace`` ring) that began in the window: one hold of
``ServingEngine._lock`` by the scheduler thread, through ``batcher.step()``,
the stream push and the harvest. A submit waits at most one of these if the
lock is fair. Nothing where the program records no such span."""

from benchmark.measure import percentile


def read(run):
    lo, hi = run.t0 * 1e6, run.t1 * 1e6
    v = [e["dur"] / 1e3 for e in run.ring
         if e.get("name") == "step" and e.get("cat") == "engine"
         and e.get("ph") == "X" and lo <= e.get("ts", 0) < hi]
    return percentile(v, 90) if v else None
