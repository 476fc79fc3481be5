"""Milliseconds of the program's ``maxflow.certificate`` spans (the cut ==
flow check of every converged solve) per cut of the window.  The program's
spans are recorded in traced runs only."""


def read(run):
    t = [s.seconds for s in run.program_spans
         if s.name == "maxflow.certificate"]
    cuts = sum(r.cuts for r in run.requests)
    return 1e3 * sum(t) / cuts if t and cuts else None
