"""Instances cut in the window per window second."""


def read(run):
    return sum(r.cuts for r in run.requests) / run.window_s
