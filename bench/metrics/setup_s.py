"""Set-up seconds: process start to the first timed request (imports, JAX
start, instance generation, compiles or cache loads, warm-up)."""


def read(run):
    return run.setup_s
