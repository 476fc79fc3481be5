"""The ``Solver`` session front-end: prepared handles, unified routes,
compile-cache accounting.

Everything here is about the session plumbing — warm-start semantics have
their own suite (tests/test_warmstart.py), legacy-shim equivalence its own
(tests/test_api_compat.py).
"""

import dataclasses
import sys

import numpy as np
import pytest

import jax

from repro.core import (Solver, SolverCacheInfo, SolverOptions, SweepConfig,
                        grid_partition, solve_mincut)
from repro.data.grids import random_sparse, synthetic_grid
from repro.kernels.ref import maxflow_oracle


def _instance(g=10, seed=0):
    p = synthetic_grid(g, g, connectivity=8, strength=150, seed=seed)
    return p, grid_partition((g, g), (2, 2))


def test_options_absorb_sweep_config():
    cfg = SweepConfig(method="prd", engine_backend="pallas",
                      engine_max_iters=4, device_resident=True,
                      host_sync_every=3)
    opts = SolverOptions.from_sweep_config(cfg, num_regions=9, check=False)
    assert opts.sweep_config() == cfg
    assert opts.num_regions == 9 and opts.check is False
    # every SweepConfig field exists on SolverOptions (nothing silently
    # dropped when new sweep knobs appear)
    sw = {f.name for f in dataclasses.fields(SweepConfig)}
    so = {f.name for f in dataclasses.fields(SolverOptions)}
    assert sw <= so


def test_options_validation():
    with pytest.raises(AssertionError):
        SolverOptions(warm_labels="sometimes")
    with pytest.raises(AssertionError):
        SolverOptions(exchange="psum")


@pytest.mark.parametrize("front_end",
                         ["SweepConfig", "SolverOptions", "maxflow_solve"])
def test_pallas_with_chunk_iters_refused(front_end, monkeypatch, capsys):
    """The fused chunked engine is XLA only: every front end refuses the
    blocked Pallas kernel with engine_chunk_iters, naming both knobs."""
    if front_end == "maxflow_solve":
        from repro.launch import maxflow_solve

        monkeypatch.setattr(sys, "argv", [
            "maxflow_solve", "--engine-backend", "pallas",
            "--engine-chunk-iters", "8"])
        with pytest.raises(SystemExit) as ei:
            maxflow_solve.main()
        assert ei.value.code == 2               # argparse's usage error
        said = capsys.readouterr().err
    else:
        options = {"SweepConfig": SweepConfig,
                   "SolverOptions": SolverOptions}[front_end]
        with pytest.raises(ValueError) as ei:
            options(engine_backend="pallas", engine_chunk_iters=8)
        said = str(ei.value)
    assert "engine_backend='pallas'" in said
    assert "engine_chunk_iters" in said
    with pytest.raises(AssertionError):
        SolverOptions(method="bfs")


def test_prepare_solve_matches_one_shot():
    p, part = _instance()
    want, _ = maxflow_oracle(p)
    for opts in [SolverOptions(), SolverOptions(method="prd"),
                 SolverOptions(device_resident=True)]:
        legacy = solve_mincut(p, part=part, config=opts.sweep_config())
        res = Solver(opts).prepare(p, part).solve()
        assert res.flow_value == legacy.flow_value == want
        np.testing.assert_array_equal(res.source_side, legacy.source_side)
        np.testing.assert_array_equal(np.asarray(res.state.d),
                                      np.asarray(legacy.state.d))
        assert res.stats.sweeps == legacy.stats.sweeps
        assert res.stats.engine_iters == legacy.stats.engine_iters
        assert res.stats.engine_launches == legacy.stats.engine_launches
        assert res.stats.scope == "instance"


def test_solver_solve_is_prepare_solve():
    p, part = _instance(seed=3)
    s = Solver(SolverOptions())
    assert s.solve(p, part).flow_value == \
        s.prepare(p, part).solve().flow_value


def test_second_solved_handle_is_warm_noop():
    """Re-solving an untouched warm handle costs zero sweeps and returns
    the same flow."""
    p, part = _instance(seed=1)
    h = Solver(SolverOptions()).prepare(p, part)
    r1 = h.solve()
    r2 = h.solve()
    assert r2.flow_value == r1.flow_value
    assert r2.stats.sweeps == 0


def test_cache_info_zero_retrace_same_shape(fresh_compile_cache):
    """A second same-shape problem through the session reuses every
    compiled program.  (fresh_compile_cache clears the process-global jit
    caches, so the first solve is deterministically a miss under any test
    ordering.)"""
    s = Solver(SolverOptions())
    p1, part = _instance(seed=4)
    s.prepare(p1, part).solve()
    info1 = s.cache_info()
    assert info1.misses == 1 and info1.hits == 0
    p2, _ = _instance(seed=5)
    s.prepare(p2, part).solve()
    info2 = s.cache_info()
    assert info2.traces == info1.traces
    assert info2.hits == info1.hits + 1
    assert isinstance(info2, SolverCacheInfo)


def test_solve_many_handles_problems_and_scope():
    s = Solver(SolverOptions())
    probs = [synthetic_grid(8, 8, seed=i) for i in range(2)] \
        + [random_sparse(14, 28, seed=7)]
    handles = [s.prepare(probs[0]), probs[1], probs[2]]   # mixed input kinds
    res = s.solve_many(handles)
    for p, r in zip(probs, res):
        assert r.flow_value == maxflow_oracle(p)[0]
        assert r.stats.scope == "batch"
    # the prepared handle came back warm
    assert handles[0].warm
    # per-instance launch/sync fields carry the globals of their batch
    batch_launches = {bs.engine_launches for bs in s.last_batch_stats}
    assert all(r.stats.engine_launches in batch_launches for r in res)


def test_solve_many_keeps_handles_warm():
    s = Solver(SolverOptions())
    probs = [synthetic_grid(8, 8, seed=i) for i in (11, 12)]
    hs = [s.prepare(p) for p in probs]
    res1 = s.solve_many(hs)
    # untouched warm handles re-enter the batched driver converged
    res2 = s.solve_many(hs)
    for r1, r2 in zip(res1, res2):
        assert r2.flow_value == r1.flow_value
        assert r2.stats.sweeps == 0
    for h in hs:
        assert h.warm


def test_solve_many_warm_after_update_matches_cold():
    s = Solver(SolverOptions())
    probs = [synthetic_grid(8, 8, seed=i) for i in (21, 22, 23)]
    hs = [s.prepare(p) for p in probs]
    s.solve_many(hs)
    rng = np.random.RandomState(2)
    m = len(hs[1].problem.edges)
    idx = rng.choice(m, size=4, replace=False)
    hs[1].update(arcs=idx,
                 cap_fwd=rng.randint(0, 301, size=4).astype(np.int32))
    res = s.solve_many(hs)
    for h, r in zip(hs, res):
        cold = solve_mincut(h.problem, part=h.part)
        assert r.flow_value == cold.flow_value


def test_solve_many_rejections():
    s = Solver(SolverOptions(parallel=False))
    with pytest.raises(ValueError):
        s.solve_many([_instance()[0]])
    s2 = Solver(SolverOptions(use_boundary_relabel=True))
    with pytest.raises(ValueError):
        s2.solve_many([_instance()[0]])
    # a handle from another session is refused
    a, b = Solver(SolverOptions()), Solver(SolverOptions())
    h = a.prepare(_instance()[0])
    with pytest.raises(ValueError):
        b.solve_many([h])


def test_reset_returns_to_cold():
    p, part = _instance(seed=6)
    s = Solver(SolverOptions())
    h = s.prepare(p, part)
    h.solve()
    rng = np.random.RandomState(8)
    idx = rng.choice(len(p.edges), size=5, replace=False)
    h.update(arcs=idx, cap_fwd=rng.randint(0, 301, size=5).astype(np.int32))
    h.reset()
    assert not h.warm and int(h._flow_offset) == 0
    res = h.solve()
    cold = solve_mincut(h.problem, part=part)
    assert res.flow_value == cold.flow_value
    assert res.stats.sweeps == cold.stats.sweeps


def test_sharded_route_unified_result():
    """handle.solve(mesh=...) returns the same MincutResult shape with the
    sharded driver underneath (1-device mesh: plumbing, not scaling)."""
    p, part = _instance(seed=9)
    mesh = jax.make_mesh((1,), ("regions",))
    s = Solver(SolverOptions())
    h = s.prepare(p, part)
    res = h.solve(mesh=mesh)
    ref = solve_mincut(p, part=part)
    assert res.flow_value == ref.flow_value
    assert res.stats.scope == "instance"
    assert res.stats.sweeps >= 1 and res.stats.host_syncs >= 1
    # fields the sharded driver cannot observe are None, not fake zeros
    assert res.stats.engine_iters is None
    assert res.stats.engine_launches is None
    # second sharded solve through the session: memoized program, no trace
    traces = s.cache_info().traces
    h2 = s.prepare(_instance(seed=10)[0], part)
    h2.solve(mesh=mesh)
    assert s.cache_info().traces == traces


def test_sharded_route_warm_recut():
    """A capacity update after a sharded solve (the explicit-axis mesh
    ``jax.make_mesh`` builds) re-solves warm on the mesh and matches a
    cold solve of the updated problem."""
    p, part = _instance(seed=11)
    mesh = jax.make_mesh((1,), ("regions",))
    h = Solver(SolverOptions()).prepare(p, part)
    h.solve(mesh=mesh)
    rng = np.random.RandomState(3)
    idx = rng.choice(len(p.edges), size=6, replace=False)
    h.update(arcs=idx, cap_fwd=rng.randint(0, 301, size=6).astype(np.int32))
    res = h.solve(mesh=mesh)
    assert res.flow_value == solve_mincut(h.problem, part=part).flow_value


def test_sharded_route_honours_max_sweeps():
    """``SolverOptions.max_sweeps`` caps the sharded route as it caps the
    host loop: the same unconverged state, bit for bit."""
    p, part = _instance(seed=12)
    opts = SolverOptions(max_sweeps=1, engine_max_iters=4)
    mesh = jax.make_mesh((1,), ("regions",))
    got = Solver(opts).prepare(p, part).solve(mesh=mesh)
    want = Solver(opts).prepare(p, part).solve()
    assert not want.converged and not got.converged
    assert got.stats.sweeps == want.stats.sweeps == 1
    for k in ("flow_to_t", "d", "excess", "cf"):
        np.testing.assert_array_equal(np.asarray(getattr(got.state, k)),
                                      np.asarray(getattr(want.state, k)))
