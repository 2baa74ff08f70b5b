"""Device time of one run of an admission-prefill program (the prefill class
of benchmark/programs.json), median over its runs in the traced window; of
the program of that class that took most time."""

from benchmark.measure import class_modules


def read(run):
    if run.trace is None:
        return None
    mods = class_modules(run, "prefill")
    if not mods:
        return None
    top = max(mods.values(), key=lambda m: m["total_s"])
    return top["median_s"] * 1e3
