"""Live rows whose length is at or past the window over live rows: sum of
``past_window`` over sum of ``live`` on the ``dispatch`` spans (the
``obs/trace`` ring) that began in the window, in percent. ``past_window`` is
the decoder module's own count at the dispatch, from the scheduler's host
mirror of its rows' lengths (``models/afmoe.span_counts``): at 100 every
step reads every slot of every ring, which is what ``counts/afmoe.py``'s
``state_bytes_per_row`` counts. Nothing where the spans carry no such
counter."""


def read(run):
    lo, hi = run.t0 * 1e6, run.t1 * 1e6
    past = live = 0
    for e in run.ring:
        args = e.get("args") or {}
        if (e.get("name") == "dispatch" and e.get("ph") == "X"
                and lo <= e.get("ts", 0) < hi and "past_window" in args):
            past += int(args["past_window"])
            live += int(args.get("live", 0))
    return 100.0 * past / live if live else None
