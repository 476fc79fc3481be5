"""Cross-executor conformance: every route is THE SAME algorithm.

One parameterized matrix — executor/driver × ard/prd × engine backend
(xla-unfused / xla-fused / pallas-blocked) — asserting bit-exact flow,
labels, residuals and statistics against the scalar host-loop oracle
(``sweep.solve``, the paper's Alg. 1/2 reference driver), which is itself
checked against the Edmonds–Karp oracle.  This replaces the per-driver
bit-exactness matrices that used to live in test_sweep_driver.py /
test_batch.py (their pinned driver regressions remain there).

Also here, because they are the executor interface's contract:

* the capability matrix — every (feature, executor) pair either validates
  or fails fast with one consistent ``UnsupportedFeatureError`` (a
  ``ValueError`` and a ``NotImplementedError``) at every front end;
* the mid-solve invariant check — the preflow/labeling/conservation
  invariants of ``tests/invariants.py`` hold at every sweep boundary,
  attached through ``sweep.solve``'s ``on_sweep`` stats hook.
"""

import dataclasses
from functools import lru_cache

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import invariants
from repro.core import (SweepConfig, Solver, SolverOptions, build,
                        grid_partition, solve_mincut, solve_mincut_batch)
from repro.core.executor import (BatchedExecutor, Capabilities,
                                 LocalExecutor, ShardedExecutor,
                                 StreamingExecutor,
                                 UnsupportedFeatureError, required_features)
from repro.core import sweep as sweep_mod
from repro.core.graph import init_labels
from repro.data.grids import synthetic_grid
from repro.kernels.ref import maxflow_oracle

P_GRID = (10, 10)
P_REGIONS = (2, 2)

# engine configurations every executor must agree under: the unfused
# two-phase engine, the fused chunked XLA engine, the blocked pallas kernel
BACKENDS = [("xla", None), ("xla", 8), ("pallas", None)]
BACKEND_IDS = ["xla-unfused", "xla-fused", "pallas-blocked"]


@lru_cache(maxsize=None)
def _instance(seed=0):
    p = synthetic_grid(*P_GRID, connectivity=8, strength=150, seed=seed)
    part = grid_partition(P_GRID, P_REGIONS)
    return p, part


@lru_cache(maxsize=None)
def _cfg(method, backend, chunk, **kw) -> SweepConfig:
    return SweepConfig(method=method, engine_backend=backend,
                       engine_chunk_iters=chunk)


@lru_cache(maxsize=None)
def _oracle(method, backend, chunk, seed=0):
    """The scalar host-loop solve — the conformance reference — plus the
    Edmonds–Karp flow value it must (and does) reproduce."""
    p, part = _instance(seed)
    want, _ = maxflow_oracle(p)
    res = solve_mincut(p, part=part, config=_cfg(method, backend, chunk))
    assert res.flow_value == want, "host-loop oracle off the true maxflow"
    assert res.stats.host_syncs == res.stats.sweeps + 1
    return res, want


def _assert_state_bitexact(ref, got, msg=""):
    assert got.flow_value == ref.flow_value, msg
    np.testing.assert_array_equal(np.asarray(ref.state.d),
                                  np.asarray(got.state.d), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(ref.state.cf),
                                  np.asarray(got.state.cf), err_msg=msg)


@pytest.mark.parametrize("backend,chunk", BACKENDS, ids=BACKEND_IDS)
@pytest.mark.parametrize("method", ["ard", "prd"])
def test_local_device_resident_conformance(method, backend, chunk):
    """LocalExecutor, device-resident driver: everything observable equals
    the host loop — state, counters, curves — with one host sync."""
    p, part = _instance()
    ref, _ = _oracle(method, backend, chunk)
    cfg = dataclasses.replace(_cfg(method, backend, chunk),
                              device_resident=True)
    got = solve_mincut(p, part=part, config=cfg)
    _assert_state_bitexact(ref, got, f"{method}/{backend}/{chunk}")
    s_ref, s_got = ref.stats, got.stats
    assert (s_got.sweeps, s_got.engine_iters, s_got.engine_launches,
            s_got.regions_discharged, s_got.page_bytes,
            s_got.boundary_bytes) == \
           (s_ref.sweeps, s_ref.engine_iters, s_ref.engine_launches,
            s_ref.regions_discharged, s_ref.page_bytes,
            s_ref.boundary_bytes)
    assert s_got.flow_curve == s_ref.flow_curve
    assert s_got.active_curve == s_ref.active_curve
    assert s_got.host_syncs == 1


@pytest.mark.parametrize("backend,chunk", BACKENDS, ids=BACKEND_IDS)
@pytest.mark.parametrize("method", ["ard", "prd"])
def test_batched_executor_conformance(method, backend, chunk):
    """BatchedExecutor: every instance of a 2-instance batch is bit-equal
    to its standalone solve; launch/sync counters are global (scope
    "batch"): the batch's launches are the sum of its instances'."""
    p, part = _instance(0)
    p2, _ = _instance(1)
    ref, _ = _oracle(method, backend, chunk, seed=0)
    ref2, _ = _oracle(method, backend, chunk, seed=1)
    cfg = _cfg(method, backend, chunk)
    got = solve_mincut_batch([p, p2], parts=[part, part], config=cfg)
    for single, batched in ((ref, got[0]), (ref2, got[1])):
        _assert_state_bitexact(single, batched,
                               f"{method}/{backend}/{chunk}")
        bs, ss = batched.stats, single.stats
        assert bs.scope == "batch"
        assert bs.sweeps == ss.sweeps
        assert bs.engine_iters == ss.engine_iters
        assert bs.regions_discharged == ss.regions_discharged
        assert bs.page_bytes == ss.page_bytes
        assert bs.boundary_bytes == ss.boundary_bytes
        assert bs.host_syncs == 1
    assert got[0].stats.engine_launches == \
        ref.stats.engine_launches + ref2.stats.engine_launches


@pytest.mark.parametrize("device_resident", [False, True],
                         ids=["host", "device"])
@pytest.mark.parametrize("backend,chunk", BACKENDS, ids=BACKEND_IDS)
@pytest.mark.parametrize("method", ["ard", "prd"])
def test_sharded_executor_conformance(method, backend, chunk,
                                      device_resident):
    """ShardedExecutor (1-device mesh: conformance, not scaling): flow,
    labels, residuals and sweep count equal the host-loop oracle; the
    multi-device regressions live in test_multidevice.py."""
    p, part = _instance()
    ref, _ = _oracle(method, backend, chunk)
    mesh = jax.make_mesh((1,), ("regions",))
    cfg = dataclasses.replace(_cfg(method, backend, chunk),
                              device_resident=device_resident)
    opts = SolverOptions.from_sweep_config(cfg)
    got = Solver(opts).prepare(p, part).solve(mesh=mesh)
    _assert_state_bitexact(ref, got,
                           f"{method}/{backend}/{chunk}/{device_resident}")
    assert got.stats.sweeps == ref.stats.sweeps
    # the sharded route does not observe engine dispatches
    assert got.stats.engine_iters is None
    assert got.stats.engine_launches is None
    assert got.stats.host_syncs == \
        (1 if device_resident else ref.stats.sweeps)


@pytest.mark.parametrize("backend,chunk", BACKENDS, ids=BACKEND_IDS)
@pytest.mark.parametrize("method", ["ard", "prd"])
def test_streaming_executor_conformance(method, backend, chunk):
    """StreamingExecutor: staging regions through the disk spill pool one
    at a time is bit-exact with the all-resident sequential host loop —
    flow, labels, residuals, sweep count and engine counters — while the
    stats additionally account the staged traffic and |B|."""
    p, part = _instance()
    want, _ = maxflow_oracle(p)
    cfg = dataclasses.replace(_cfg(method, backend, chunk),
                              parallel=False, use_global_gap=False)
    ref = solve_mincut(p, part=part, config=cfg)
    assert ref.flow_value == want
    got = Solver(SolverOptions.from_sweep_config(
        cfg, streaming=True)).prepare(p, part).solve()
    _assert_state_bitexact(ref, got, f"streaming/{method}/{backend}/{chunk}")
    s_ref, s_got = ref.stats, got.stats
    assert s_got.sweeps == s_ref.sweeps
    assert s_got.engine_iters == s_ref.engine_iters
    assert s_got.regions_discharged == s_ref.regions_discharged
    assert s_got.flow_curve == s_ref.flow_curve
    assert s_got.active_curve == s_ref.active_curve
    # comms accounting: |B| is on every route; the staged-bytes ledgers
    # are the streaming route's own contribution
    assert s_got.num_boundary == s_ref.num_boundary \
        == ref.meta.num_boundary
    assert s_got.staged_in_bytes > 0 and s_got.staged_out_bytes > 0
    assert s_ref.staged_in_bytes == 0 and s_ref.staged_out_bytes == 0
    invariants.assert_sweep_bound(ref.meta, s_got, ard=method == "ard")


# --------------------------------------------------------------------------
# capability matrix: one consistent fail-fast surface
# --------------------------------------------------------------------------

FEATURE_CFG = {
    "parallel": dict(parallel=True),
    "sequential": dict(parallel=False),
    "boundary_relabel": dict(use_boundary_relabel=True),
    "partial_discharge": dict(partial_discharge=True),
    "global_gap": dict(use_global_gap=True),
}
ALL_EXECUTORS = [LocalExecutor, BatchedExecutor, ShardedExecutor,
                 StreamingExecutor]


def test_required_features_maps_every_validated_flag():
    seq_all = SweepConfig(parallel=False, use_boundary_relabel=True,
                          partial_discharge=True, use_global_gap=True)
    assert set(required_features(seq_all)) == set(FEATURE_CFG) - {"parallel"}
    assert required_features(
        SweepConfig(use_global_gap=False)) == ("parallel",)
    assert required_features(
        SweepConfig(parallel=False, use_global_gap=False)) == ("sequential",)


@pytest.mark.parametrize("executor", ALL_EXECUTORS,
                         ids=lambda e: e.name)
@pytest.mark.parametrize("feature", sorted(FEATURE_CFG))
def test_capability_matrix(executor, feature):
    """Every (feature, executor) pair: supported configs validate,
    unsupported ones raise the one consistent error.

    A feature's probe config can require more than the probed feature
    (e.g. boundary_relabel rides the default parallel sweep), so the
    expected rejection is the FIRST flag of ``required_features`` the
    executor lacks — validate's documented fail-fast order."""
    cfg = SweepConfig(**{"use_global_gap": False, **FEATURE_CFG[feature]})
    req = required_features(cfg)
    assert feature in req
    unsupported = [f for f in req
                   if not getattr(executor.capabilities, f)]
    if not unsupported:
        executor.validate(cfg)          # must not raise
    else:
        with pytest.raises(UnsupportedFeatureError) as ei:
            executor.validate(cfg)
        err = ei.value
        # one consistent surface: executor name + feature in the message,
        # catchable as the historical ValueError AND as the precise
        # NotImplementedError
        assert isinstance(err, ValueError)
        assert isinstance(err, NotImplementedError)
        assert err.executor == executor.name
        assert err.feature == unsupported[0]
        assert executor.name in str(err) and err.feature in str(err)


def test_capability_declarations_pin_the_support_matrix():
    """The support matrix is part of the public contract — changing it is
    a deliberate act, not a refactor side effect."""
    assert LocalExecutor.capabilities == Capabilities(batched=False)
    assert BatchedExecutor.capabilities == Capabilities(
        sequential=False, boundary_relabel=False, batched=True,
        host_loop=False)
    assert ShardedExecutor.capabilities == Capabilities(
        sequential=False, boundary_relabel=False)
    assert StreamingExecutor.capabilities == Capabilities(
        parallel=False, boundary_relabel=False, global_gap=False,
        batched=False, device_resident=False)


def test_unsupported_combos_fail_fast_at_every_front_end():
    """The same config is rejected with the same error type no matter
    which entry point routes it to an incapable executor."""
    p, part = _instance()
    mesh = jax.make_mesh((1,), ("regions",))
    for bad in (SweepConfig(parallel=False),
                SweepConfig(use_boundary_relabel=True)):
        with pytest.raises(UnsupportedFeatureError):
            solve_mincut_batch([p], parts=[part], config=bad)
        with pytest.raises(UnsupportedFeatureError):
            Solver(SolverOptions.from_sweep_config(bad)).solve_many(
                [p], parts=[part])
        # the sharded route used to silently ignore these flags; now it
        # refuses them at the interface
        with pytest.raises(UnsupportedFeatureError):
            Solver(SolverOptions.from_sweep_config(bad)).prepare(
                p, part).solve(mesh=mesh)


# --------------------------------------------------------------------------
# mid-solve invariants at every sweep boundary (the stats hook)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["ard", "prd"])
def test_invariants_hold_at_every_sweep_boundary(method):
    """Preflow validity, labeling validity and flow conservation hold at
    every sweep boundary of the host-loop driver, via the on_sweep hook."""
    p, part = _instance()
    meta, state, _ = build(p, np.asarray(part))
    state = init_labels(meta, state)
    total0 = invariants.preflow_total(state)
    seen = []

    def on_sweep(st, sweeps_done):
        where = f"after sweep {sweeps_done} ({method})"
        invariants.assert_valid_preflow(meta, st, where)
        invariants.assert_valid_labeling(meta, st, ard=method == "ard",
                                         where=where)
        invariants.assert_flow_conservation(meta, st, total0, where)
        seen.append(sweeps_done)

    cfg = SweepConfig(method=method)
    _st, stats = sweep_mod.solve(meta, state, cfg, on_sweep=on_sweep)
    assert seen == list(range(1, stats.sweeps + 1))
    assert stats.sweeps >= 1


def test_on_sweep_needs_the_host_loop():
    p, part = _instance()
    meta, state, _ = build(p, np.asarray(part))
    with pytest.raises(ValueError):
        sweep_mod.solve(meta, init_labels(meta, state),
                        SweepConfig(device_resident=True),
                        on_sweep=lambda st, i: None)
