"""Cut extraction (``sweep.extract_cut``): one compiled program per
(K, V, E), dtypes and shardings, counted by the session's trace counter,
and the canonical sink side of a NumPy residual-reachability reference."""

from __future__ import annotations

from collections import defaultdict, deque

import jax
import numpy as np
import pytest

from repro.core import Solver, SolverOptions, build, extract_cut, grid_partition
from repro.core import sweep as sweep_mod
from repro.data.grids import synthetic_grid

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def extraction_compiles():
    """Backend compiles of ``extract_cut_fixpoint`` while the test runs."""
    names: list[str] = []

    def listener(event, duration, fun_name="?", **kw):
        if event == COMPILE_EVENT and "extract_cut_fixpoint" in fun_name:
            names.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    yield names
    jax.monitoring.unregister_event_duration_listener(listener)


def _grid(side, seed=0, blocks=(4, 4)):
    p = synthetic_grid(side, side, connectivity=8, strength=150, seed=seed)
    return p, grid_partition((side, side), blocks)


def _sink_side_ref(state) -> np.ndarray:
    """T = {v : v reaches t in the residual graph}, by breadth-first search
    from the vertices with residual sink capacity over reversed arcs."""
    cf, emask, sink_cf, vmask, nbr_region, nbr_local = (
        np.asarray(a) for a in (state.cf, state.emask, state.sink_cf,
                                state.vmask, state.nbr_region,
                                state.nbr_local))
    vmask = vmask.astype(bool)
    arcs = (cf > 0) & emask.astype(bool) & vmask[:, :, None]
    preds = defaultdict(list)
    for k, v, e in zip(*np.nonzero(arcs)):
        preds[(int(nbr_region[k, v, e]), int(nbr_local[k, v, e]))].append(
            (int(k), int(v)))
    reach = (sink_cf > 0) & vmask
    queue = deque((int(k), int(v)) for k, v in zip(*np.nonzero(reach)))
    while queue:
        for u in preds[queue.popleft()]:
            if not reach[u]:
                reach[u] = True
                queue.append(u)
    return reach


def test_same_shape_solves_compile_extraction_once(fresh_compile_cache,
                                                   extraction_compiles):
    s = Solver(SolverOptions())
    for seed in (0, 1):
        p, part = _grid(10, seed, blocks=(2, 2))
        s.prepare(p, part).solve()
    assert len(extraction_compiles) == 1, extraction_compiles


def test_cross_arc_count_does_not_key_the_compile(fresh_compile_cache,
                                                  extraction_compiles):
    """14² and 16² under a 4×4 partition share (K, V, E) = (16, 16, 8) but
    not their cross-arc count: one extraction program serves both."""
    s = Solver(SolverOptions())
    handles = [s.prepare(*_grid(side, seed))
               for seed, side in enumerate((14, 16))]
    assert handles[0].state.cf.shape == handles[1].state.cf.shape
    assert (handles[0].state.cross_src.shape
            != handles[1].state.cross_src.shape)
    results = s.solve_many(handles)
    assert [r.converged for r in results] == [True, True]
    for h in handles:       # and again outside the batch: still cached
        extract_cut(h.meta, h.state)
    assert len(extraction_compiles) == 1, extraction_compiles


def test_cache_info_counts_an_extraction_trace_once(fresh_compile_cache):
    s = Solver(SolverOptions())
    p, part = _grid(8, blocks=(2, 2))
    meta, state, _ = build(p, part)
    before = s.cache_info().traces
    first = extract_cut(meta, state)
    assert s.cache_info().traces == before + 1
    second = extract_cut(meta, state)
    assert s.cache_info().traces == before + 1
    np.testing.assert_array_equal(np.asarray(first), np.asarray(second))


@pytest.mark.parametrize("route", ["host", "batched"])
def test_solve_whose_extraction_traces_is_a_miss(fresh_compile_cache, route):
    """A solve that traces nothing but the extraction program is a
    compile-cache miss: the session notes it after the cut is extracted."""
    s = Solver(SolverOptions())

    def solve(seed):
        p, part = _grid(10, seed, blocks=(2, 2))
        h = s.prepare(p, part)
        return h.solve() if route == "host" else s.solve_many([h])[0]

    solve(0)
    solve(1)
    info = s.cache_info()
    assert info.misses == 1 and info.hits == 1
    sweep_mod.extract_cut_fixpoint.clear_cache()
    solve(2)
    after = s.cache_info()
    assert after.traces == info.traces + 1
    assert after.misses == 2 and after.hits == 1


@pytest.mark.parametrize("route", ["host", "batched", "sharded"])
def test_extract_cut_matches_numpy_reference(route):
    s = Solver(SolverOptions())
    problems = [_grid(12, seed, blocks=(2, 2)) for seed in (3, 4)]
    handles = [s.prepare(p, part) for p, part in problems]
    if route == "host":
        results = [h.solve() for h in handles]
    elif route == "batched":
        results = s.solve_many(handles)
    else:
        mesh = jax.make_mesh((1,), ("regions",))
        results = [h.solve(mesh=mesh) for h in handles]
    for res in results:
        assert res.converged
        got = np.asarray(extract_cut(res.meta, res.state))
        want = _sink_side_ref(res.state)
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(~res.source_side,
                                      res.layout.to_flat(want))
