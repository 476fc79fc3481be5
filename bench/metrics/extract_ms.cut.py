"""Milliseconds of the program's ``maxflow.extract_cut`` spans (cut
extraction, closed on the cut's device array) per cut of the window.  The
program's spans are recorded in traced runs only."""


def read(run):
    t = [s.seconds for s in run.program_spans
         if s.name == "maxflow.extract_cut"]
    cuts = sum(r.cuts for r in run.requests)
    return 1e3 * sum(t) / cuts if t and cuts else None
