"""Solver compile-cache misses in the window (``Solver.cache_info``): calls
into the front end that traced a new device program."""


def read(run):
    return run.cache_misses
