"""The chip benchmark of the region-discharge mincut/maxflow solver; see
``bench/run.py``."""
