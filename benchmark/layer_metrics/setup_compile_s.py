"""Seconds of set-up that JAX spent tracing, lowering and compiling (or
loading from the persistent cache): the union of the program's
``compile.*`` spans that began before the window, on each thread, summed
over threads (benchmark/setup_spans.py). Nothing from a ring without
``ring_start`` or without a ``compile.xla`` span (the parent of the PR that
brought them)."""

from benchmark import setup_spans


def read(run):
    t = setup_spans.of_run(run)
    return None if t is None else t["compile_s"]
