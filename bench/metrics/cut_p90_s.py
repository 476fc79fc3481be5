"""90th percentile of request latency over every request of the window
(Python's ``statistics.quantiles``, exclusive method)."""

import statistics


def read(run):
    lat = [r.latency for r in run.requests]
    if len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=10)[8]
