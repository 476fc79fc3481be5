"""Legacy one-shot entry points — thin shims over the ``Solver`` session.

The public front-end is ``core.solver``: ``Solver(options)`` →
``prepare(problem)`` → ``handle.solve()`` / ``handle.update(...)`` /
``Solver.solve_many([...])``, one unified ``MincutResult``/``SweepStats``
shape across the host-loop, device-resident, sharded and batched routes,
plus warm-start incremental re-solves.

This module keeps the pre-session surface alive, bit-identically:

* ``solve_mincut`` — one problem, one cold solve
  (``Solver.prepare().solve()``);
* ``solve_mincut_batch`` / ``BatchedSolver`` — a fleet of problems through
  the shape-bucketed batched driver (``Solver.solve_many``).

Downstream callers and all pre-session tests run unmodified; new code
should talk to ``Solver`` directly.
"""

from __future__ import annotations

import numpy as np

from repro.core import executor as _executor
from repro.core import sweep as _sweep
from repro.core.graph import Problem
from repro.core.solver import (MincutResult, ProblemHandle, Solver,
                               SolverCacheInfo, SolverOptions)

# legacy name for the cache-accounting record returned by
# ``BatchedSolver.cache_info`` (now the session-wide ``SolverCacheInfo``)
BatchCacheInfo = SolverCacheInfo

__all__ = [
    "BatchCacheInfo", "BatchedSolver", "MincutResult", "ProblemHandle",
    "Solver", "SolverCacheInfo", "SolverOptions", "solve_mincut",
    "solve_mincut_batch",
]


def solve_mincut(
    problem: Problem,
    part: np.ndarray | None = None,
    num_regions: int = 4,
    config: _sweep.SweepConfig | None = None,
    check: bool = True,
) -> MincutResult:
    """Solve MINCUT/MAXFLOW with region discharge sweeps (one-shot).

    ``part`` — region id per vertex; defaults to node-number slicing into
    ``num_regions`` regions (the paper's fallback partitioner).
    ``check=False`` skips the host-side cut-cost == flow assertion (one
    device fetch + an O(n*E) host reduction per solve) on serving paths.

    Equivalent to ``Solver(...).prepare(problem, part).solve()`` — for
    sequences of related problems, keep the ``Solver`` session instead:
    it amortizes build/compile across calls and re-solves warm after
    ``handle.update``.
    """
    solver = Solver(SolverOptions.from_sweep_config(
        config, num_regions=num_regions, check=check))
    return solver.prepare(problem, part).solve()


class BatchedSolver:
    """Shape-bucketed, compile-cached multi-instance solver front-end.

    Legacy wrapper over ``Solver.solve_many``: packs problems into
    power-of-two shape buckets, one batched device program per bucket,
    compiled solves cached per ``(bucket_shape, SweepConfig)`` —
    ``cache_info()`` reports hits/misses.  Per-instance results are
    bit-identical to ``solve_mincut`` on the same problem.
    """

    def __init__(self, config: _sweep.SweepConfig | None = None, *,
                 num_regions: int = 4, check: bool = True):
        self.config = config or _sweep.SweepConfig()
        self._solver = Solver(SolverOptions.from_sweep_config(
            self.config, num_regions=num_regions, check=check))
        # fail fast on configurations the batched executor does not take
        # (UnsupportedFeatureError is a ValueError, as this raise always was)
        _executor.BatchedExecutor.validate(self.config)
        self.num_regions = num_regions
        self.check = check

    def solve(self, problems, parts=None) -> list[MincutResult]:
        return self._solver.solve_many(problems, parts)

    @property
    def last_batch_stats(self):
        return self._solver.last_batch_stats

    def cache_info(self) -> BatchCacheInfo:
        return self._solver.cache_info()


def solve_mincut_batch(
    problems,
    parts=None,
    num_regions: int = 4,
    config: _sweep.SweepConfig | None = None,
    check: bool = True,
) -> list[MincutResult]:
    """Solve a fleet of independent problems through the batched driver.

    One-shot convenience over ``Solver.solve_many`` (a kept ``Solver``
    session amortizes the compile cache across calls): problems are packed
    into shape buckets and each bucket is solved by one batched device
    program.  Results are returned in input order and are bit-identical per instance to ``solve_mincut``.
    """
    solver = BatchedSolver(config, num_regions=num_regions, check=check)
    return solver.solve(problems, parts)
