"""Mean milliseconds of ``ProblemHandle.update`` (the warm-start
reparameterisation on the device, to ``block_until_ready``) per re-cut of
the window."""


def read(run):
    t = [r.spans["update"] for r in run.requests if "update" in r.spans]
    return 1e3 * sum(t) / len(t) if t else None
