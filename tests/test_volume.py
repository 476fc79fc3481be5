"""Seeded 3-D segmentation volumes (``repro.data.grids.
segmentation_seeds_volume``) on the ``Solver`` path, and the
``SweepStats.ard_stages`` counter on every route that observes it.

Small volumes, held to the Edmonds-Karp oracle: the exact flow and its
canonical (minimal source side) cut, bit for bit.
"""

import io
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

from invariants import assert_sweep_bound
from repro.core import Solver, SolverOptions, grid_partition, spans
from repro.data.grids import (grid_edges, segmentation_seeds_volume,
                              volume_offsets)
from repro.kernels.ref import maxflow_oracle

# (shape, neighbours, splits): 26-connected in a 2x2x2 region grid, and a
# 6-connected box whose region grid is flat along one axis
VOLUMES = {
    "26c-6x6x6": ((6, 6, 6), "all", (2, 2, 2)),
    "6c-9x10x11": ((9, 10, 11), "faces", (2, 1, 2)),
}
ROUTES = {
    "host": dict(),
    "device": dict(device_resident=True),
}


def _volume(name, seed=3):
    shape, neighbours, splits = VOLUMES[name]
    p = segmentation_seeds_volume(shape, neighbours=neighbours, seed=seed)
    return p, grid_partition(shape, splits), int(np.prod(splits))


def _solve(name, method, route, seed=3):
    """(result, problem) of one volume solved on ``route``."""
    p, part, k = _volume(name, seed)
    solver = Solver(SolverOptions(num_regions=k, method=method,
                                  **ROUTES.get(route, {})))
    if route == "batched":
        return solver.solve_many([p], [part])[0], p
    return solver.prepare(p, part).solve(), p


# --------------------------------------------------------------------------
# the family
# --------------------------------------------------------------------------

@pytest.mark.parametrize("shape, neighbours", [
    ((6, 6, 6), "all"), ((7, 5, 4), "faces"), ((9, 11), "all"),
    ((4, 5, 3, 3), "faces")], ids=["3d-26", "3d-6", "2d-8", "4d-8"])
def test_generator_is_valid_and_deterministic(shape, neighbours):
    p = segmentation_seeds_volume(shape, neighbours=neighbours,
                                  smoothness=9, seed_strength=50, seed=5)
    q = segmentation_seeds_volume(shape, neighbours=neighbours,
                                  smoothness=9, seed_strength=50, seed=5)
    for f in ("edges", "cap_fwd", "cap_bwd", "excess", "sink_cap"):
        np.testing.assert_array_equal(getattr(p, f), getattr(q, f))
    n = int(np.prod(shape))
    assert p.num_vertices == n
    e = p.edges
    assert e.dtype == np.int64 and (e >= 0).all() and (e < n).all()
    assert (e[:, 0] != e[:, 1]).all()
    assert len({tuple(sorted(x)) for x in e.tolist()}) == len(e)
    # neighbours are one step apart along every axis
    step = np.abs(np.stack(np.unravel_index(e[:, 0], shape))
                  - np.stack(np.unravel_index(e[:, 1], shape)))
    assert step.max() == 1
    if neighbours == "faces":
        assert (step.sum(0) == 1).all()
    np.testing.assert_array_equal(p.cap_fwd, p.cap_bwd)
    assert p.cap_fwd.min() >= 1 and p.cap_fwd.max() <= 9
    # source links in the central ball only, sink links on the border only
    idx = np.indices(shape).reshape(len(shape), -1)
    border = ((idx < 2) | (idx >= np.array(shape)[:, None] - 2)).any(0)
    assert ((p.sink_cap >= 50) & (p.sink_cap < 65) == border).all()
    assert (p.excess[border] == 0).all()
    assert ((p.excess == 0) | ((p.excess >= 50) & (p.excess < 65))).all()
    r = segmentation_seeds_volume(shape, neighbours=neighbours,
                                  smoothness=9, seed_strength=50, seed=6)
    assert not np.array_equal(p.cap_fwd, r.cap_fwd)


@pytest.mark.parametrize("n", [2, 3, 5, 8])
@pytest.mark.parametrize("neighbours", ["all", "faces"])
def test_edge_count_matches_closed_form(n, neighbours):
    m = len(segmentation_seeds_volume((n,) * 3, neighbours=neighbours).edges)
    if neighbours == "all":
        assert m == 3 * n * n * (n - 1) + 6 * n * (n - 1) ** 2 \
            + 4 * (n - 1) ** 3
    else:
        assert m == 3 * n * n * (n - 1)


def test_offsets_and_edges():
    assert volume_offsets(3, "faces") == [(1, 0, 0), (0, 1, 0), (0, 0, 1)]
    assert len(volume_offsets(3, "all")) == 13
    assert len(volume_offsets(2, "all")) == 4
    np.testing.assert_array_equal(grid_edges((2, 3), [(0, 1)]),
                                  [[0, 1], [1, 2], [3, 4], [4, 5]])
    with pytest.raises(ValueError, match="neighbours"):
        volume_offsets(3, "edges")


# --------------------------------------------------------------------------
# the Solver path, held to the oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["ard", "prd"])
@pytest.mark.parametrize("route", ["host", "device", "batched"])
@pytest.mark.parametrize("volume", list(VOLUMES))
def test_solver_cuts_volume_to_oracle(volume, route, method):
    res, p = _solve(volume, method, route)
    want, source = maxflow_oracle(p)
    assert res.flow_value == want
    np.testing.assert_array_equal(res.source_side, source)
    assert res.stats.converged
    p_, part, k = _volume(volume)
    meta = Solver(SolverOptions(num_regions=k)).prepare(p_, part).meta
    assert_sweep_bound(meta, res.stats, ard=method == "ard", where=volume)


@pytest.mark.parametrize("volume", list(VOLUMES))
def test_ball_update_equals_cold_solve(volume):
    """A brush stroke (a ball of voxels relabelled as object) on a warm 3-D
    handle cuts to what a cold solve of the edited volume gives."""
    p, part, k = _volume(volume)
    shape = VOLUMES[volume][0]
    solver = Solver(SolverOptions(num_regions=k))
    h = solver.prepare(p, part)
    h.solve()
    idx = np.indices(shape).reshape(len(shape), -1)
    centre = np.array([2, 1, 1])[:, None]
    ball = ((idx - centre) ** 2).sum(0) <= 2
    exc, snk = p.excess.copy(), p.sink_cap.copy()
    exc[ball], snk[ball] = 200, 0
    h.update(excess=exc, sink_cap=snk)
    warm = h.solve()
    edited = type(p)(num_vertices=p.num_vertices, edges=p.edges,
                     cap_fwd=p.cap_fwd, cap_bwd=p.cap_bwd, excess=exc,
                     sink_cap=snk)
    cold = Solver(SolverOptions(num_regions=k)).prepare(edited, part).solve()
    want, source = maxflow_oracle(edited)
    assert warm.flow_value == cold.flow_value == want
    np.testing.assert_array_equal(warm.source_side, cold.source_side)
    np.testing.assert_array_equal(warm.source_side, source)


# --------------------------------------------------------------------------
# SweepStats.ard_stages
# --------------------------------------------------------------------------

@pytest.mark.parametrize("volume", list(VOLUMES))
def test_ard_stages_equal_on_every_route(volume):
    """The host loop, the device-resident loop and the batched driver count
    the same stages, and the sequential sweep counts them on the resident
    and the streaming route alike."""
    got = {r: _solve(volume, "ard", r)[0].stats for r in
           ("host", "device", "batched")}
    stages = {r: s.ard_stages for r, s in got.items()}
    assert len(set(stages.values())) == 1, stages
    p, part, k = _volume(volume)
    # at least the sink stage of every region, every sweep
    assert stages["host"] >= k * got["host"].sweeps > 0
    seq = dict(num_regions=k, parallel=False, use_global_gap=False)
    resident = Solver(SolverOptions(**seq)).prepare(p, part).solve()
    streamed = Solver(SolverOptions(streaming=True, **seq)).prepare(
        p, part).solve()
    assert resident.stats.ard_stages == streamed.stats.ard_stages > 0
    assert resident.stats.engine_iters == streamed.stats.engine_iters


def test_batched_ard_stages_are_per_instance():
    p, part, k = _volume("26c-6x6x6")
    q, _, _ = _volume("26c-6x6x6", seed=4)
    solver = Solver(SolverOptions(num_regions=k))
    alone = [solver.prepare(x, part).solve().stats.ard_stages
             for x in (p, q)]
    together = [r.stats.ard_stages
                for r in solver.solve_many([p, q], [part, part])]
    assert together == alone
    np.testing.assert_array_equal(solver.last_batch_stats[0].ard_stages,
                                  alone)


@pytest.mark.parametrize("route", ["host", "device", "batched"])
def test_prd_has_no_stages(route):
    assert _solve("6c-9x10x11", "prd", route)[0].stats.ard_stages is None


@pytest.mark.parametrize("method", ["ard", "prd"])
@pytest.mark.parametrize("route", ["host", "device"])
def test_ard_stages_reach_the_solve_span(route, method):
    with spans.recording() as recs:
        res, _ = _solve("6c-9x10x11", method, route)
    solve = [r for r in recs if r.name == "maxflow.solve"]
    assert len(solve) == 1
    assert solve[0].attrs["ard_stages"] == res.stats.ard_stages
    assert solve[0].attrs["engine_iters"] == res.stats.engine_iters
    assert (res.stats.ard_stages is None) == (method == "prd")


@pytest.mark.parametrize("route", ["host", "device", "batched"])
def test_host_syncs_per_solve_unchanged(route):
    """The counter rides the transfers that carry ``engine_iters``: one
    entry check plus one per sweep on the host loop, one per solve on the
    device-resident and batched drivers."""
    stats = _solve("26c-6x6x6", "ard", route)[0].stats
    want = stats.sweeps + 1 if route == "host" else 1
    assert stats.host_syncs == want


# --------------------------------------------------------------------------
# the CLI
# --------------------------------------------------------------------------

def test_cli_cuts_a_volume(monkeypatch):
    from repro.launch import maxflow_solve

    monkeypatch.setattr(sys, "argv", [
        "maxflow_solve", "--volume", "6x6x6", "--neighbours", "26",
        "--regions", "2x2x2", "--seed", "3"])
    out = io.StringIO()
    with redirect_stdout(out):
        maxflow_solve.main()
    p, _, _ = _volume("26c-6x6x6")
    want, _ = maxflow_oracle(p)
    line = out.getvalue()
    assert f"flow={want} " in line
    assert "ard_stages=" in line and "engine_iters=" in line
    assert "ard_stages=None" not in line
