"""Of the set-up's ``compile.xla`` spans (benchmark/setup_spans.py), those
that the persistent compile cache answered (``cache`` ``hit``), in percent.
Nothing from a ring without ``ring_start`` or without a ``compile.xla``
span."""

from benchmark import setup_spans


def read(run):
    t = setup_spans.of_run(run)
    return None if t is None else 100.0 * t["cache_hits"] / t["programs"]
