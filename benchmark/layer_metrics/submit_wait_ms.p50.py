"""Median, over the window's answered requests, of the time from the client's
send to the engine's submit: the HTTP read, base64, raster and CLIP
preprocess in the handler thread, then the wait for ``ServingEngine._lock``,
which the scheduler thread holds through every step."""

from benchmark.measure import percentile


def read(run):
    v = [(r.t_submit - r.t_sent) * 1e3 for r in run.window_rows()
         if r.ok and r.t_submit is not None]
    return percentile(v, 50) if v else None
