"""Device idle time of the traced window that lies inside a ``sched.admit``
annotation of the engine thread (the whole admission: upload, tower, prefill,
scatter), over the window, in percent (benchmark/host_spans.py)."""

from benchmark import host_spans


def read(run):
    if run.trace is None:
        return None
    return host_spans.idle_pct(run, "admit_s")
