"""Window seconds per instance cut in the window."""


def read(run):
    return run.window_s / sum(r.cuts for r in run.requests)
