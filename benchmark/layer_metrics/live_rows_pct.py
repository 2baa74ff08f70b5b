"""Rows that decoded over rows paid for: sum of ``live`` over sum of
``rows`` on the ``dispatch`` spans (the ``obs/trace`` ring) that began in the
window, in percent. ``live`` is the scheduler's own count at the dispatch (its
host mirror, which a pipelined carry may be one segment ahead of). Nothing
where the spans carry no such counters."""


def read(run):
    lo, hi = run.t0 * 1e6, run.t1 * 1e6
    live = rows = 0
    for e in run.ring:
        args = e.get("args") or {}
        if (e.get("name") == "dispatch" and e.get("ph") == "X"
                and lo <= e.get("ts", 0) < hi and "rows" in args):
            live += int(args.get("live", 0))
            rows += int(args["rows"])
    return 100.0 * live / rows if rows else None
