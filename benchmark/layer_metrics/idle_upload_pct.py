"""Device idle time of the traced window that lies inside an
``admit.upload`` annotation of the engine thread (pixels host to device), over
the window, in percent. Busy intervals as ``device_idle_pct`` takes them; the
annotations are in the same file, on the same clock
(benchmark/host_spans.py)."""

from benchmark import host_spans


def read(run):
    if run.trace is None:
        return None
    return host_spans.idle_pct(run, "upload_s")
