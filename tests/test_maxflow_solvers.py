"""Solver correctness: every variant must reach the oracle maxflow value and
produce a consistent minimum cut, within the paper's sweep bounds."""

import numpy as np
import pytest

from repro.core import (SweepConfig, build, cut_value, extract_cut,
                        grid_partition, solve_mincut)
from repro.core.sweep import sweep_bound
from repro.data.grids import random_sparse, segmentation_grid, synthetic_grid
from repro.kernels.ref import maxflow_oracle

VARIANTS = [
    SweepConfig(method="ard", parallel=True),
    SweepConfig(method="ard", parallel=False),
    SweepConfig(method="prd", parallel=True),
    SweepConfig(method="prd", parallel=False),
]


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("cfg", VARIANTS,
                         ids=["ard-par", "ard-seq", "prd-par", "prd-seq"])
def test_random_sparse_matches_oracle(seed, cfg):
    p = random_sparse(14, 28, seed=seed)
    want, _ = maxflow_oracle(p)
    res = solve_mincut(p, num_regions=3, config=cfg)
    assert res.flow_value == want
    # cut consistency is asserted inside solve_mincut (cost == flow)
    assert res.stats.sweeps <= sweep_bound(res.meta, cfg)


@pytest.mark.parametrize("cfg", VARIANTS[:2], ids=["ard-par", "ard-seq"])
def test_grid_instance(cfg):
    p = synthetic_grid(16, 16, connectivity=8, strength=120, seed=1)
    want, _ = maxflow_oracle(p)
    part = grid_partition((16, 16), (2, 2))
    res = solve_mincut(p, part=part, config=cfg)
    assert res.flow_value == want


def test_heuristics_preserve_correctness():
    p = synthetic_grid(16, 16, connectivity=8, strength=150, seed=2)
    want, _ = maxflow_oracle(p)
    part = grid_partition((16, 16), (2, 2))
    for cfg in [
        SweepConfig(method="ard", use_boundary_relabel=True),
        SweepConfig(method="ard", partial_discharge=True),
        SweepConfig(method="ard", partial_discharge=True,
                    use_boundary_relabel=True),
        SweepConfig(method="ard", use_global_gap=False),
    ]:
        res = solve_mincut(p, part=part, config=cfg)
        assert res.flow_value == want, cfg


def test_ard_fewer_sweeps_than_prd():
    """The paper's headline experimental claim (Fig. 8, Table 1)."""
    p = synthetic_grid(20, 20, connectivity=8, strength=150, seed=3)
    part = grid_partition((20, 20), (2, 2))
    ard = solve_mincut(p, part=part, config=SweepConfig(method="ard"))
    prd = solve_mincut(p, part=part, config=SweepConfig(method="prd"))
    assert ard.flow_value == prd.flow_value
    assert ard.stats.sweeps <= prd.stats.sweeps


@pytest.mark.parametrize("method", ["ard", "prd"])
def test_bfs_partition_irregular_end_to_end(method):
    """An irregular (non-grid) instance solved through a BFS-grown
    partition — exercises partition.bfs_partition in a full solve, which
    the grid/block partition tests never reach."""
    from repro.core import bfs_partition

    p = random_sparse(24, 60, seed=7)
    want, _ = maxflow_oracle(p)
    part = bfs_partition(p.num_vertices, p.edges, 3, seed=1)
    assert part.min() >= 0 and part.max() <= 2 and len(part) == 24
    res = solve_mincut(p, part=part, config=SweepConfig(method=method))
    assert res.flow_value == want
    assert res.stats.sweeps <= sweep_bound(res.meta, SweepConfig(method=method))


def test_segmentation_instance():
    p = segmentation_grid(20, 20, seed=0)
    want, _ = maxflow_oracle(p)
    res = solve_mincut(p, num_regions=4,
                       config=SweepConfig(method="ard"))
    assert res.flow_value == want


def test_source_side_is_minimal_cut():
    p = random_sparse(12, 24, seed=9)
    want, oracle_side = maxflow_oracle(p)
    res = solve_mincut(p, num_regions=2)
    # the extracted sink side T = {v -> t} is the canonical minimal sink
    # side; the oracle computes the minimal *source* side {s -> v}; both
    # cuts must have the same (optimal) cost.
    meta, state0, layout = build(p, np.zeros(p.num_vertices, np.int64))
    assert res.flow_value == want


@pytest.mark.parametrize("method", ["ard", "prd"])
def test_engine_backend_full_solve_parity(method):
    """The Pallas engine backend (interpret mode on CPU) must be a drop-in
    replacement: identical flow value, labels, and sweep count vs XLA."""
    instances = [
        (synthetic_grid(12, 12, connectivity=8, strength=120, seed=1),
         grid_partition((12, 12), (2, 2))),
        (random_sparse(14, 28, seed=2), None),
    ]
    for p, part in instances:
        want, _ = maxflow_oracle(p)
        res = {}
        for be in ("xla", "pallas"):
            cfg = SweepConfig(method=method, engine_backend=be)
            res[be] = solve_mincut(p, part=part, num_regions=3, config=cfg)
            assert res[be].flow_value == want
        assert res["xla"].flow_value == res["pallas"].flow_value
        np.testing.assert_array_equal(np.asarray(res["xla"].state.d),
                                      np.asarray(res["pallas"].state.d))
        assert res["xla"].stats.sweeps == res["pallas"].stats.sweeps


@pytest.mark.parametrize("method", ["ard", "prd"])
def test_fused_engine_full_solve_parity(method):
    """The fused XLA engine (chunk_iters=k) must be a drop-in replacement
    on full solves: oracle flow value, identical labels and sweep counts
    vs the unfused path, with the expected launch reduction."""
    p = random_sparse(16, 32, seed=5)
    want, _ = maxflow_oracle(p)
    base = solve_mincut(p, num_regions=3,
                        config=SweepConfig(method=method))
    assert base.flow_value == want
    for chunk in (1, 8):
        cfg = SweepConfig(method=method, engine_chunk_iters=chunk)
        res = solve_mincut(p, num_regions=3, config=cfg)
        assert res.flow_value == want, chunk
        np.testing.assert_array_equal(np.asarray(res.state.d),
                                      np.asarray(base.state.d),
                                      err_msg=f"chunk={chunk}")
        assert res.stats.sweeps == base.stats.sweeps
        assert res.stats.engine_iters == base.stats.engine_iters
        # one traced body per iteration vs 2 phase programs: exactly 2x
        # fewer dispatches
        assert res.stats.engine_launches * 2 == base.stats.engine_launches


def test_trivial_cases():
    # no edges: flow = sum(min(excess, sink_cap)) per vertex
    p = random_sparse(5, 0, seed=0)
    want, _ = maxflow_oracle(p)
    res = solve_mincut(p, num_regions=2)
    assert res.flow_value == want
    # single region (degenerate partition)
    p = random_sparse(10, 20, seed=3)
    want, _ = maxflow_oracle(p)
    res = solve_mincut(p, num_regions=1)
    assert res.flow_value == want
