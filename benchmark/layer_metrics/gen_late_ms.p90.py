"""How late the load generator ran: 90th percentile of sent - due over the
window's requests. A starved generator must not read as a fast server."""

from benchmark.measure import percentile


def read(run):
    v = [(r.t_sent - r.t_due) * 1e3 for r in run.window_rows()]
    return percentile(v, 90) if v else None
