"""Plain reference for the benchmark's correctness check.

It imports nothing of the program under test and takes nothing the
program made: it reads the instance dicts of ``bench.families``.
"""

from .maxflow import min_cut, min_cut_quantized  # noqa: F401
