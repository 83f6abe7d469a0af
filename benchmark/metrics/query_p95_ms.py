"""``query_p95_ms``: the 95th percentile of the client-side latency (send
to decoded rows) of every query completed in the window."""

import statistics


def read(run):
    lat = [(r["t_done"] - r["t_send"]) * 1e3 for r in run.records if r["ok"]]
    if len(lat) < 20:  # fewer than one sample beyond the 95th percentile
        return None
    return statistics.quantiles(lat, n=100, method="inclusive")[94]
