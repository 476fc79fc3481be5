"""Device busy microseconds inside the program's ``maxflow.sweeps`` spans
of the traced slice per engine iteration of its requests: the sweep loop's
device cost without entry labels, extraction and certificate."""


def read(run):
    if run.trace is None:
        return None
    busy = run.trace["span_busy_s"].get("maxflow.sweeps", 0.0)
    iters = sum(x for r in run.traced for x in r.engine_iters)
    return 1e6 * busy / iters if busy and iters else None
