"""Mean milliseconds of ``Solver.prepare`` (host build and partition, and
the state's transfer to the device) per cut of the window."""


def read(run):
    t = [r.spans["prepare"] for r in run.requests if "prepare" in r.spans]
    return 1e3 * sum(t) / len(t) if t else None
