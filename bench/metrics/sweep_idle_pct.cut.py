"""Share of the program's ``maxflow.sweeps`` spans of the traced slice in
which no operation ran on the device: the host's time inside the sweep
loop (dispatch, the per-sweep fetch of its counters)."""


def read(run):
    if run.trace is None:
        return None
    span = run.trace["span_s"].get("maxflow.sweeps", 0.0)
    busy = run.trace["span_busy_s"].get("maxflow.sweeps", 0.0)
    return 100.0 * (1.0 - busy / span) if span and busy else None
