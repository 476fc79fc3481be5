"""The seed presents a window's problems under symmetries of the grid: the
inputs change, the problem and the solver's work do not."""

from __future__ import annotations

import json

import numpy as np
import pytest

from bench import families
from bench.reference import min_cut
from bench.tests.helpers import ROOT


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("shape, want", [
    ((32, 32), list(range(8))),
    ((24, 24), list(range(8))),
    ((14, 16), [0, 1, 5, 6]),
    ((15, 14), [0, 1]),
])
def test_symmetries_keep_the_partition(shape, want):
    assert families.symmetries(shape, (4, 4)) == want


@pytest.mark.parametrize("name", ["synth2d-8c", "seg2d-seeds"])
def test_presented_problem_has_the_same_answer_moved(name):
    inst = families.make(_config(name), 12, 12, families.rng_for(5, 1))
    flow, source = min_cut(inst)
    for k in families.symmetries((12, 12), (4, 4)):
        moved = families.transform(inst, k)
        assert not k or not np.array_equal(moved["edges"], inst["edges"])
        m_flow, m_source = min_cut(moved)
        assert m_flow == flow
        for v in range(inst["n"]):
            assert m_source[families.moved_vertex((12, 12), k, v)] == source[v]


@pytest.mark.parametrize("name", ["synth2d-8c", "seg2d-seeds"])
def test_presented_problem_costs_the_solver_the_same(name):
    from bench.loops import to_problem
    from repro.core import Solver, SolverOptions

    config = _config(name)
    solver = Solver(SolverOptions(**config["solver"]))
    inst = families.make(config, 12, 16, families.rng_for(9, 1))
    work = set()
    for k in families.symmetries((12, 16), (4, 4)):
        moved = families.transform(inst, k)
        res = solver.prepare(to_problem(moved), families.grid_partition(
            moved["shape"], (4, 4))).solve()
        work.add((res.flow_value, res.stats.sweeps, res.stats.engine_iters))
    assert len(work) == 1
