"""Requests whose admission's first half (upload, tower, splice, the wave's
prefill) was dispatched while a decode segment was in flight, over all the
requests admitted: the members (``rids``) of the ``sched.admit`` spans (the
``obs/trace`` ring) that began in the window and carry ``staged`` > 0, over
the members of all such spans, in percent. A staged wave has two spans, its
staging and its landing, so members are counted once, by request id. Nothing
where no span carries the arg (a program that admits drained only, as the
parent of the PR that brought it) or none admitted."""


def read(run):
    lo, hi = run.t0 * 1e6, run.t1 * 1e6
    staged, seen = set(), set()
    for e in run.ring:
        args = e.get("args") or {}
        if (e.get("name") == "admit" and e.get("ph") == "X"
                and e.get("cat", "sched") == "sched"
                and lo <= e.get("ts", 0) < hi and "staged" in args):
            rids = args.get("rids") or ()
            seen.update(rids)
            if args["staged"]:
                staged.update(rids)
    return 100.0 * len(staged) / len(seen) if seen else None
