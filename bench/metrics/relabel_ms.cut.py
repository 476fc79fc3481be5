"""Milliseconds of the program's ``maxflow.global_relabel`` spans (the
warm start's relabel, run on entry to a solve) per re-cut of the window; a
re-cut that skips the relabel adds 0.  The program's spans are recorded in
traced runs only."""


def read(run):
    cuts = sum(r.cuts for r in run.requests)
    if not run.program_spans or not cuts:
        return None
    t = [s.seconds for s in run.program_spans
         if s.name == "maxflow.global_relabel"]
    return 1e3 * sum(t) / cuts
