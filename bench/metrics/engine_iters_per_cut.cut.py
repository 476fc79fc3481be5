"""Mean ``SweepStats.engine_iters`` (push/relabel iterations summed over
regions) per cut of the window."""


def read(run):
    s = [x for r in run.requests for x in r.engine_iters]
    return sum(s) / len(s) if s else None
