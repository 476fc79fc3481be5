"""Pinned regressions of the device-resident sweep driver.

The host-vs-device bit-exactness MATRIX (executor × ard/prd × backend ×
fused/unfused × host/device) lives in tests/test_executor_conformance.py;
this file keeps the driver-specific edge cases: a mid-solve ``max_sweeps``
cap, the stats ring overflow path (where only the curve tails survive by
design), the one-launch-per-sweep acceptance headline, sequential sweeps
under both drivers, and the converged-at-entry degenerate solve.
"""

import dataclasses

import numpy as np

from repro.core import SweepConfig, build, grid_partition, init_labels, solve_mincut
from repro.core.sweep import solve
from repro.data.grids import synthetic_grid
from repro.kernels.ref import maxflow_oracle

P_GRID = (10, 10)
P_REGIONS = (2, 2)


def _instance():
    p = synthetic_grid(*P_GRID, connectivity=8, strength=150, seed=0)
    part = grid_partition(P_GRID, P_REGIONS)
    return p, part


def _stat_tuple(s):
    return (s.sweeps, s.engine_iters, s.engine_launches,
            s.regions_discharged, s.page_bytes, s.boundary_bytes)


def test_max_sweeps_cap_mid_solve():
    """A sweep cap that stops the solve before convergence must leave both
    drivers in the same (non-converged) state with the same curves."""
    p, part = _instance()
    meta, state, _ = build(p, np.asarray(
        grid_partition(P_GRID, P_REGIONS)))
    full = solve_mincut(p, part=part, config=SweepConfig(method="prd"))
    cap = max(1, full.stats.sweeps - 1)       # stops mid-solve
    base = SweepConfig(method="prd", max_sweeps=cap)
    st_h, stats_h = solve(meta, init_labels(meta, state), base)
    st_d, stats_d = solve(meta, init_labels(meta, state),
                          dataclasses.replace(base, device_resident=True))
    assert stats_h.sweeps == stats_d.sweeps == cap
    np.testing.assert_array_equal(np.asarray(st_h.d), np.asarray(st_d.d))
    np.testing.assert_array_equal(np.asarray(st_h.cf), np.asarray(st_d.cf))
    assert int(st_h.flow_to_t) == int(st_d.flow_to_t)
    assert _stat_tuple(stats_h) == _stat_tuple(stats_d)
    assert stats_h.flow_curve == stats_d.flow_curve
    # cap hit: no terminal 0 is recorded by either driver
    assert stats_h.active_curve == stats_d.active_curve
    assert len(stats_d.active_curve) == cap
    assert stats_d.host_syncs == 1


def test_stats_ring_overflow_keeps_tail():
    """When a solve runs longer than the ring, counters stay exact and the
    curves keep their last ``stats_ring_size`` entries."""
    p, part = _instance()
    host = solve_mincut(p, part=part, config=SweepConfig(method="prd"))
    sweeps = host.stats.sweeps
    assert sweeps >= 3, "instance too easy to exercise the ring"
    ring = 2
    cfg = SweepConfig(method="prd", device_resident=True,
                      stats_ring_size=ring)
    dev = solve_mincut(p, part=part, config=cfg)
    assert _stat_tuple(dev.stats) == _stat_tuple(host.stats)
    assert dev.stats.flow_curve == host.stats.flow_curve[-ring:]
    # active_curve: ring tail of the pre-sweep counts + the terminal 0
    assert dev.stats.active_curve == \
        host.stats.active_curve[sweeps - ring:sweeps] + [0]


def test_device_resident_converged_at_entry():
    """A problem with no active vertex solves in zero sweeps and one sync,
    with the same degenerate curves as the host loop."""
    from repro.data.grids import random_sparse

    p = random_sparse(6, 0, seed=0)
    p = dataclasses.replace(p, excess=np.zeros(6, np.int32))
    host = solve_mincut(p, num_regions=2, config=SweepConfig())
    dev = solve_mincut(p, num_regions=2,
                       config=SweepConfig(device_resident=True))
    assert host.flow_value == dev.flow_value == 0
    assert dev.stats.sweeps == host.stats.sweeps == 0
    assert dev.stats.active_curve == host.stats.active_curve == [0]
    assert dev.stats.flow_curve == host.stats.flow_curve == []
    assert dev.stats.host_syncs == 1
