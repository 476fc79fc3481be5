"""Milliseconds of backend compiles charged to the program's spans in the
window, per cut: what the front end compiles on the request path (0 when
set-up built every program).  The program's spans are recorded in traced
runs only."""


def read(run):
    cuts = sum(r.cuts for r in run.requests)
    if not run.program_spans or not cuts:
        return None
    return 1e3 * sum(s.compile_s for s in run.program_spans) / cuts
