"""XLA programs compiled or loaded from the persistent cache during set-up:
the program's ``compile.xla`` spans that began before the window
(``compiles_in_window``'s counting rule, before the window;
benchmark/setup_spans.py). Nothing from a ring without ``ring_start`` or
without a ``compile.xla`` span."""

from benchmark import setup_spans


def read(run):
    t = setup_spans.of_run(run)
    return None if t is None else float(t["programs"])
