"""Milliseconds of the program's ``maxflow.finish`` spans (cut extraction
and certificate, one per instance) per instance cut in the window.  The
program's spans are recorded in traced runs only."""


def read(run):
    t = [s.seconds for s in run.program_spans if s.name == "maxflow.finish"]
    cuts = sum(r.cuts for r in run.requests)
    return 1e3 * sum(t) / cuts if t and cuts else None
