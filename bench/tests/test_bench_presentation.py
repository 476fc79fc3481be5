"""The seed presents a window's problems under symmetries of the grid: the
inputs change, the problem and the solver's work do not.  In 2-D and in
3-D, on 4-, 8-, 6- and 26-connected grids."""

from __future__ import annotations

import json

import numpy as np
import pytest

from bench import families
from bench.reference import min_cut
from bench.tests.generators.volume_seeds import offsets
from bench.tests.helpers import ROOT


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("shape, splits, want", [
    ((32, 32), (4, 4), list(range(8))),
    ((24, 24), (4, 4), list(range(8))),
    ((14, 16), (4, 4), [0, 1, 5, 6]),
    ((15, 14), (4, 4), [0, 1]),
    ((8, 8, 8), (2, 2, 2), list(range(48))),
    ((16, 8, 8), (4, 2, 2), list(range(16))),
    ((8, 8, 8), (4, 2, 2), list(range(16))),
    ((9, 8, 8), (4, 2, 2), [0, 2, 4, 6, 8, 10, 12, 14]),
])
def test_symmetries_keep_the_partition(shape, splits, want):
    assert families.symmetries(shape, splits) == want


# The square's eight, in the order every 2-D seed has drawn them.
_SQUARE = (
    lambda v: v, lambda v: v.T, lambda v: v[::-1, ::-1],
    lambda v: v[::-1, ::-1].T, lambda v: v[::-1, :], lambda v: v[:, ::-1],
    lambda v: v.T[::-1, :], lambda v: v.T[:, ::-1])


@pytest.mark.parametrize("shape", [(3, 3), (2, 5)])
def test_square_symmetries_keep_their_order(shape):
    v = np.arange(shape[0] * shape[1]).reshape(shape)
    for k, sym in enumerate(_SQUARE):
        assert np.array_equal(families._symmetry_map(shape, k), sym(v))


def test_volume_symmetries_keep_their_order():
    """d >= 3: k = 2^d * p + mask, p the p-th of itertools.permutations,
    bit a of mask a flip of axis a of the permuted grid."""
    syms = families.signed_permutations(3)
    assert len(syms) == 48 == len(set(syms))
    assert syms[:3] == (((0, 1, 2), ()), ((0, 1, 2), (0,)),
                        ((0, 1, 2), (1,)))
    assert syms[7] == ((0, 1, 2), (0, 1, 2))
    assert syms[8] == ((0, 2, 1), ())
    assert syms[13] == ((0, 2, 1), (0, 2))
    assert syms[47] == ((2, 1, 0), (0, 1, 2))
    v = np.arange(2 * 3 * 4).reshape(2, 3, 4)
    assert np.array_equal(families._symmetry_map((2, 3, 4), 13),
                          v.transpose(0, 2, 1)[::-1, :, ::-1])


def _volume(shape, neighbours, seed):
    """Random capacities and dense terminals on a 3-D grid of the test
    family's neighbourhood."""
    rng = families.rng_for(seed, 1)
    edges = families.grid_edges(shape, offsets(len(shape), neighbours))
    term = rng.randint(-60, 61, size=int(np.prod(shape)))
    return dict(n=int(np.prod(shape)), edges=edges,
                cap_fwd=rng.randint(1, 21, size=len(edges)).astype(np.int32),
                cap_bwd=rng.randint(1, 21, size=len(edges)).astype(np.int32),
                excess=np.where(term > 0, term, 0).astype(np.int32),
                sink_cap=np.where(term < 0, -term, 0).astype(np.int32),
                shape=shape)


@pytest.mark.parametrize("family, shape, splits, seed", [
    ("synth2d-8c", (12, 12), (4, 4), 5),
    ("seg2d-seeds", (12, 12), (4, 4), 5),
    ("synth2d-8c", (12, 16), (4, 4), 9),
    ("seg2d-seeds", (12, 16), (4, 4), 9),
    ("faces", (6, 6, 6), (2, 2, 2), 3),
    ("all", (6, 6, 6), (2, 2, 2), 3),
    ("faces", (8, 4, 4), (4, 2, 2), 3),
    ("all", (8, 4, 4), (4, 2, 2), 3),
])
def test_presented_problem_is_the_same_problem(family, shape, splits, seed):
    """Under every allowed symmetry: the same flow, the cut moved with the
    vertices, and the same sweeps and engine iterations in the solver."""
    from bench.loops import to_problem
    from repro.core import Solver, SolverOptions

    if len(shape) == 2:
        inst = families.make(_config(family), shape,
                             families.rng_for(seed, 1))
    else:
        inst = _volume(shape, family, seed)
    flow, source = min_cut(inst)
    solver = Solver(SolverOptions(num_regions=int(np.prod(splits))))
    work = set()
    for k in families.symmetries(shape, splits):
        moved = families.transform(inst, k)
        assert not k or not np.array_equal(moved["edges"], inst["edges"])
        m_flow, m_source = min_cut(moved)
        assert m_flow == flow
        where = [families.moved_vertex(shape, k, v) for v in range(inst["n"])]
        assert np.array_equal(m_source[where], source)
        res = solver.prepare(to_problem(moved), families.grid_partition(
            moved["shape"], splits)).solve()
        assert res.flow_value == flow
        work.add((res.stats.sweeps, res.stats.engine_iters))
    assert len(work) == 1
