"""The control of the benchmark's correctness check, and its readings.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 [--seconds 5]

Runs the cell as ``bench/run.py`` does, with the program's answers replaced
by the plain reference computed on capacities held in 8 bits
(``reference.min_cut_quantized``): the step from the configuration's exact
integers to a narrower storage type that a later change could be tempted
to take.  The update path of a warm re-cut still runs in the program, so
the replaced answer is the one for the instance the program holds.  For
each seed it prints the numbers the run compares, with their limits; the
control has to fail at least one of them.  The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)


def _as_instance(problem) -> dict:
    return dict(n=problem.num_vertices, edges=problem.edges,
                cap_fwd=problem.cap_fwd, cap_bwd=problem.cap_bwd,
                excess=problem.excess, sink_cap=problem.sink_cap)


def _answer(problem, solve):
    flow, source = solve(_as_instance(problem))
    stats = types.SimpleNamespace(sweeps=1, engine_iters=1)
    return types.SimpleNamespace(flow_value=flow, source_side=source,
                                 stats=stats, converged=True)


@contextlib.contextmanager
def replaced_answers(solve):
    """Within the block, every ``ProblemHandle.solve`` and
    ``Solver.solve_many`` of the program answers with ``solve(instance)``
    (a ``(flow, source_side)`` function of the instance dict)."""
    from repro.core import solver as _solver

    handle_solve = _solver.ProblemHandle.solve
    solve_many = _solver.Solver.solve_many

    def one(self, **kw):
        return _answer(self.problem, solve)

    def many(self, items, parts=None, **kw):
        self.last_batch_stats = [None]
        return [_answer(getattr(it, "problem", it), solve) for it in items]

    _solver.ProblemHandle.solve = one
    _solver.Solver.solve_many = many
    try:
        yield
    finally:
        _solver.ProblemHandle.solve = handle_solve
        _solver.Solver.solve_many = solve_many


def readings(cell: str, seed: int, seconds: float, solve, **run_kw) -> dict:
    """The compared numbers of one run of ``cell`` under ``solve``."""
    from bench import run

    out = io.StringIO()
    with replaced_answers(solve):
        rc = run.run(["--workload", cell, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", "0"], out=out,
                     err=io.StringIO(), **run_kw)
    if rc:
        raise RuntimeError(f"{cell} seed {seed}: run exited {rc}")
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    return dict(correct=line["correct"], attempted=line["attempted"],
                **{k: v["value"] for k, v in line["checks"].items()})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from bench.reference import min_cut_quantized

    for seed in args.seeds:
        r = readings(args.workload, seed, args.seconds, min_cut_quantized)
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              control="8-bit capacities", **r)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
