"""Mean ``SweepStats.sweeps`` per cut of the window."""


def read(run):
    s = [x for r in run.requests for x in r.sweeps]
    return sum(s) / len(s) if s else None
