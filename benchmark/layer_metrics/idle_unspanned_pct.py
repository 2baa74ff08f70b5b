"""Device idle time of the traced window that no leaf span of the engine
thread covers, over the window, in percent: idle time whose innermost
annotation is ``engine.step`` or ``sched.admit`` themselves, or none, or that
lies at the window's edges. ``engine.idle_wait`` counts as cover. What the
measurement still cannot see (benchmark/host_spans.py)."""

from benchmark import host_spans


def read(run):
    if run.trace is None:
        return None
    return host_spans.idle_pct(run, "unspanned_s")
