"""Device busy microseconds inside the ``solve`` spans of the traced slice
per engine iteration in them.  An upper bound on one iteration's cost: the
spans also hold fusion, the gap heuristic, cut extraction and the
certificate."""


def read(run):
    if run.trace is None:
        return None
    iters = sum(x for r in run.traced for x in r.engine_iters)
    busy = run.trace["span_busy_s"].get("solve", 0.0)
    return 1e6 * busy / iters if iters and busy else None
