"""Mean ARD stages per cut of the window: the ``ard_stages`` attribute of
the program's ``maxflow.solve`` spans (``SweepStats.ard_stages``, the
stages each region's discharge ran, summed over sweeps and regions).  With
``engine_iters_per_cut.cut`` it splits the engine's iterations into stages
and iterations per stage.  The program's spans are recorded in traced runs
only; a program whose spans lack the attribute reads nothing."""


def read(run):
    s = [sp.attrs["ard_stages"] for sp in run.program_spans
         if sp.name == "maxflow.solve"
         and sp.attrs.get("ard_stages") is not None]
    return sum(s) / len(s) if s else None
