"""Time a request waited in the admission queue: from the engine's submit to
the flight recorder's ``queue`` event (it left the queue), 90th percentile
over the window's requests that got that far."""

from benchmark.measure import percentile


def read(run):
    v = [(r.t_admit - r.t_submit) * 1e3 for r in run.window_rows()
         if r.t_admit is not None and r.t_submit is not None]
    return percentile(v, 90) if v else None
