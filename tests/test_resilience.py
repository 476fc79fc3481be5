"""Resilience layer: checkpoint/resume bit-exactness, supervised retry
under deterministic fault injection, the degradation ladder, input
validation, and structured non-convergence.

The headline matrix: an interrupted-then-resumed solve must match the
uninterrupted one BIT-EXACTLY — flow, labels, residuals, sweep count,
engine iterations and the per-sweep curves — at EVERY sweep boundary, on
every route (host loop, device-resident, batched, sharded), cold and
warm.  The routes are bit-identical to each other by the repo's executor
conformance suite, so cross-route resume (a device checkpoint continued
on the host loop) must be exact too.
"""

import dataclasses

import numpy as np
import pytest

import jax

from repro.core import (CertificateError, CheckpointMismatchError,
                        CheckpointPolicy, FaultPlan, ProblemValidationError,
                        Solver, SolverOptions, SweepConfig, build,
                        fault_injection, grid_partition, init_labels)
from repro.core import resilience as res
from repro.core.sweep import solve
from repro.data.dimacs import read_dimacs
from repro.data.grids import synthetic_grid
from repro.kernels.ref import maxflow_oracle

P_GRID = (10, 10)
P_REGIONS = (2, 2)


def _instance():
    p = synthetic_grid(*P_GRID, connectivity=8, strength=150, seed=0)
    part = np.asarray(grid_partition(P_GRID, P_REGIONS))
    return p, part


def _built():
    p, part = _instance()
    meta, state, _ = build(p, part)
    return p, part, meta, state


def _steps(directory):
    return sorted(int(d.name[5:]) for d in directory.iterdir()
                  if d.is_dir() and d.name.startswith("step_")
                  and not d.name.endswith(".tmp"))


def _assert_same_solve(st_a, stats_a, st_b, stats_b):
    """Bit-exactness on everything the ISSUE pins (host_syncs excepted:
    a resumed solve legitimately pays extra host re-entries)."""
    np.testing.assert_array_equal(np.asarray(st_a.d), np.asarray(st_b.d))
    np.testing.assert_array_equal(np.asarray(st_a.cf), np.asarray(st_b.cf))
    np.testing.assert_array_equal(np.asarray(st_a.excess),
                                  np.asarray(st_b.excess))
    assert int(st_a.flow_to_t) == int(st_b.flow_to_t)
    for k in ("sweeps", "engine_iters", "engine_launches",
              "regions_discharged", "flow_curve", "active_curve",
              "converged"):
        assert getattr(stats_a, k) == getattr(stats_b, k), k


# --------------------------------------------------------------------------
# checkpoint/resume bit-exactness
# --------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["prd", "ard"])
def test_host_resume_every_boundary_bit_exact(tmp_path, method):
    """Host route: resuming from EVERY sweep boundary reproduces the
    uninterrupted solve bit-exactly (state, counters and curves)."""
    _p, _part, meta, state = _built()
    cfg = SweepConfig(method=method)
    base_st, base_stats = solve(meta, init_labels(meta, state), cfg)
    assert base_stats.sweeps >= 3, "instance too easy for a boundary matrix"

    ckdir = tmp_path / method
    solve(meta, init_labels(meta, state), cfg,
          checkpoint=CheckpointPolicy(directory=ckdir, every=1))
    steps = _steps(ckdir)
    assert steps == list(range(1, base_stats.sweeps + 1))

    for step in steps:
        ck = res.load_checkpoint(ckdir, step)
        assert ck.sweeps == step and ck.route == "host"
        st_r, stats_r = solve(meta, init_labels(meta, state), cfg,
                              resume_from=ck)
        _assert_same_solve(st_r, stats_r, base_st, base_stats)


def test_device_resume_every_boundary_and_cross_route(tmp_path):
    """Device-resident route (host_sync_every=1: a checkpointable boundary
    per sweep): every boundary resumes bit-exactly — on the device route
    AND on the host loop (checkpoints are route-portable by design)."""
    _p, _part, meta, state = _built()
    cfg_d = SweepConfig(method="prd", device_resident=True,
                        host_sync_every=1)
    cfg_h = SweepConfig(method="prd")
    base_st, base_stats = solve(meta, init_labels(meta, state), cfg_d)

    solve(meta, init_labels(meta, state), cfg_d,
          checkpoint=CheckpointPolicy(directory=tmp_path, every=1))
    steps = _steps(tmp_path)
    assert steps and steps[-1] == base_stats.sweeps

    for step in steps:
        ck = res.load_checkpoint(tmp_path, step)
        assert ck.route == "device"
        st_r, stats_r = solve(meta, init_labels(meta, state), cfg_d,
                              resume_from=ck)
        _assert_same_solve(st_r, stats_r, base_st, base_stats)
        # cross-route: the same checkpoint continued on the host loop
        st_x, stats_x = solve(meta, init_labels(meta, state), cfg_h,
                              resume_from=ck)
        _assert_same_solve(st_x, stats_x, base_st, base_stats)


def test_preempted_solve_resumes_bit_exact(tmp_path):
    """The deployment story end to end: a checkpointed solve is preempted
    mid-solve, then resumed from the latest on-disk checkpoint."""
    _p, _part, meta, state = _built()
    cfg = SweepConfig(method="ard")
    base_st, base_stats = solve(meta, init_labels(meta, state), cfg)
    assert base_stats.sweeps >= 4

    with fault_injection(FaultPlan("preempt", at_sweep=3)):
        with pytest.raises(res.PreemptionError):
            solve(meta, init_labels(meta, state), cfg,
                  checkpoint=CheckpointPolicy(directory=tmp_path, every=2))
    latest = res.latest_checkpoint(tmp_path)
    assert latest is not None and 2 <= latest.sweeps <= 3

    st_r, stats_r = solve(meta, init_labels(meta, state), cfg,
                          resume_from=tmp_path)     # directory form
    _assert_same_solve(st_r, stats_r, base_st, base_stats)


def test_batched_route_resume_matches(tmp_path):
    """Batched route: one checkpoint stream for the whole shape bucket;
    preempt at a sync boundary, re-pack the same fleet, resume."""
    probs = [synthetic_grid(8, 8, seed=s) for s in range(3)]
    want = [maxflow_oracle(p)[0] for p in probs]
    opts = SolverOptions(method="ard", num_regions=4, host_sync_every=2)
    base = Solver(opts).solve_many(list(probs))

    with fault_injection(FaultPlan("preempt", at_sweep=2)):
        with pytest.raises(res.PreemptionError):
            Solver(opts).solve_many(
                list(probs),
                checkpoint=CheckpointPolicy(directory=tmp_path, every=1))
    assert _steps(tmp_path), "no checkpoint published before the preempt"
    assert res.latest_checkpoint(tmp_path).route == "batch"

    got = Solver(opts).solve_many(list(probs), resume_from=tmp_path)
    for r, b, w in zip(got, base, want):
        assert r.flow_value == b.flow_value == w
        assert r.converged and b.converged
        assert r.stats.sweeps == b.stats.sweeps
        assert r.stats.engine_iters == b.stats.engine_iters
        np.testing.assert_array_equal(r.source_side, b.source_side)
        np.testing.assert_array_equal(np.asarray(r.state.d),
                                      np.asarray(b.state.d))


def test_sharded_route_resume_matches(tmp_path):
    """Sharded route (1-device mesh: plumbing, not scaling): preempt at a
    mid-solve boundary, resume from disk through a fresh handle."""
    p, part = _instance()
    mesh = jax.make_mesh((1,), ("regions",))
    opts = SolverOptions(method="prd")
    base = Solver(opts).prepare(p, part).solve(mesh=mesh)
    assert base.stats.sweeps >= 3

    h = Solver(opts).prepare(p, part)
    with fault_injection(FaultPlan("preempt", at_sweep=2)):
        with pytest.raises(res.PreemptionError):
            h.solve(mesh=mesh,
                    checkpoint=CheckpointPolicy(directory=tmp_path, every=1))
    latest = res.latest_checkpoint(tmp_path)
    assert latest is not None and latest.route == "sharded"
    assert latest.sweeps < base.stats.sweeps

    got = Solver(opts).prepare(p, part).solve(mesh=mesh,
                                              resume_from=tmp_path)
    assert got.flow_value == base.flow_value
    assert got.converged and got.stats.sweeps == base.stats.sweeps
    np.testing.assert_array_equal(got.source_side, base.source_side)
    np.testing.assert_array_equal(np.asarray(got.state.d),
                                  np.asarray(base.state.d))
    np.testing.assert_array_equal(np.asarray(got.state.cf),
                                  np.asarray(base.state.cf))


def test_warm_handle_resume_matches(tmp_path):
    """Warm leg of the matrix: a warm re-solve after an update checkpoints,
    preempts and resumes to the same result as its uninterrupted twin (the
    handle's flow-offset bookkeeping riding in the checkpoint)."""
    p, part = _instance()
    n = p.num_vertices

    def warm_handle():
        h = Solver(SolverOptions(method="ard")).prepare(p, part)
        h.solve()
        # zero half the t-links, widen the rest, double the source mass:
        # the warm re-solve has multi-sweep work to do
        sink = np.where(np.arange(n) % 2 == 0, 0,
                        2 * p.sink_cap).astype(np.int32)
        return h.update(excess=2 * p.excess, sink_cap=sink)

    a = warm_handle()
    base = a.solve()
    assert base.stats.sweeps >= 2

    b = warm_handle()
    assert int(b._flow_offset) == int(a._flow_offset)
    with fault_injection(FaultPlan("preempt", at_sweep=1)):
        with pytest.raises(res.PreemptionError):
            b.solve(checkpoint=CheckpointPolicy(directory=tmp_path, every=1))
    assert res.latest_checkpoint(tmp_path).flow_offset == int(a._flow_offset)
    got = b.solve(resume_from=tmp_path)
    assert got.flow_value == base.flow_value
    assert got.stats.sweeps == base.stats.sweeps
    np.testing.assert_array_equal(np.asarray(got.state.d),
                                  np.asarray(base.state.d))
    np.testing.assert_array_equal(np.asarray(got.state.cf),
                                  np.asarray(base.state.cf))


def test_checkpoint_fingerprint_guards_resume(tmp_path):
    """A checkpoint from different math (prd vs ard) must refuse to
    resume; so must a snapshot that is not a solve checkpoint at all."""
    _p, _part, meta, state = _built()
    solve(meta, init_labels(meta, state), SweepConfig(method="prd"),
          checkpoint=CheckpointPolicy(directory=tmp_path, every=1))
    with pytest.raises(CheckpointMismatchError):
        solve(meta, init_labels(meta, state), SweepConfig(method="ard"),
              resume_from=tmp_path)
    # a plain (training-style) snapshot is not a solve checkpoint
    other = tmp_path / "train"
    res.snapshot_save(other, 7, {"w": np.zeros(3)})
    with pytest.raises(CheckpointMismatchError):
        res.load_checkpoint(other)


def test_snapshot_atomicity_and_latest(tmp_path):
    """Crashed-writer debris (.tmp dirs) is invisible; restore is a
    bit-exact inverse of save; empty dirs answer None/FileNotFoundError."""
    tree = {"a": np.arange(12, dtype=np.int32).reshape(3, 4),
            "b": {"c": np.ones(5, np.int64)}}
    res.snapshot_save(tmp_path, 1, tree)
    res.snapshot_save(tmp_path, 3, tree)
    (tmp_path / "step_00000002.tmp").mkdir()       # a crashed writer
    assert res.snapshot_latest(tmp_path) == 3
    back = res.snapshot_restore(tmp_path, 3, tree)
    np.testing.assert_array_equal(np.asarray(back["a"]), tree["a"])
    np.testing.assert_array_equal(np.asarray(back["b"]["c"]), tree["b"]["c"])

    empty = tmp_path / "none"
    assert res.latest_checkpoint(empty) is None
    with pytest.raises(FileNotFoundError):
        res.load_checkpoint(empty)


# --------------------------------------------------------------------------
# the solve supervisor under the fault matrix
# --------------------------------------------------------------------------

def test_supervisor_retries_resumes_and_backs_off(tmp_path):
    p, part = _instance()
    base = Solver(SolverOptions(method="prd")).prepare(p, part).solve()

    delays: list[float] = []
    h = Solver(SolverOptions(method="prd")).prepare(p, part)
    sup = res.SolveSupervisor.for_handle(
        h, checkpoint_dir=tmp_path, checkpoint_every=1,
        retry=res.RetryPolicy(max_retries=3, sleep=delays.append))
    with fault_injection(FaultPlan("raise", at_sweep=2, times=2)):
        got = sup.solve(resume=False)
    assert got.flow_value == base.flow_value and got.converged
    assert sup.report.attempts == 3
    assert sup.report.resumes == 2
    assert len(sup.report.failures) == 2
    assert delays == [0.05, 0.1]                  # base * factor**(i-1)
    np.testing.assert_array_equal(got.source_side, base.source_side)


def test_supervisor_exhausts_retries(tmp_path):
    p, part = _instance()
    h = Solver(SolverOptions(method="prd")).prepare(p, part)
    sup = res.SolveSupervisor.for_handle(
        h, checkpoint_dir=tmp_path, checkpoint_every=1,
        retry=res.RetryPolicy(max_retries=2, sleep=lambda s: None))
    with fault_injection(FaultPlan("raise", at_sweep=1, times=-1)):
        with pytest.raises(res.InjectedFault):
            sup.solve(resume=False)
    assert sup.report.attempts == 3               # 1 + max_retries
    assert len(sup.report.failures) == 3


def test_supervisor_batch_route(tmp_path):
    probs = [synthetic_grid(8, 8, seed=s) for s in (0, 1)]
    want = [maxflow_oracle(p)[0] for p in probs]
    solver = Solver(SolverOptions(method="ard", num_regions=4,
                                  host_sync_every=1))
    sup = res.SolveSupervisor.for_batch(
        solver, probs, checkpoint_dir=tmp_path, checkpoint_every=1,
        retry=res.RetryPolicy(sleep=lambda s: None))
    with fault_injection(FaultPlan("preempt", at_sweep=1)):
        got = sup.solve(resume=False)
    assert [r.flow_value for r in got] == want
    assert all(r.converged for r in got)
    assert sup.report.attempts == 2 and sup.report.resumes == 1


def test_corrupt_labels_caught_by_certificate():
    """Boundary-exchange corruption makes the solve 'converge' to a wrong
    answer; check=True must refuse to certify it, with a diagnosis."""
    p, part = _instance()
    want = maxflow_oracle(p)[0]
    h = Solver(SolverOptions(method="prd")).prepare(p, part)
    with fault_injection(FaultPlan("corrupt_labels", at_sweep=1, times=-1)):
        with pytest.raises(CertificateError) as ei:
            h.solve()
    diag = ei.value.diagnosis
    assert diag.reason == "certificate"
    assert diag.cut_cost is not None and diag.flow_value != diag.cut_cost
    assert diag.flow_value < want                 # the corruption lost flow
    assert "cut cost" in str(ei.value)
    # CertificateError still IS the historical AssertionError
    assert isinstance(ei.value, AssertionError)


# --------------------------------------------------------------------------
# degradation ladder
# --------------------------------------------------------------------------

def test_degrade_config_walks_the_ladder():
    # two chains, one rung each: pallas-unfused -> xla-unfused and
    # xla-fused -> xla-unfused
    for top, rung in ((SweepConfig(engine_backend="pallas"),
                       "pallas-unfused"),
                      (SweepConfig(engine_chunk_iters=64), "xla-fused")):
        assert res.config_rung(top) == rung
        bot = res.degrade_config(top)
        assert res.config_rung(bot) == "xla-unfused"
        assert res.degrade_config(bot) is None
    assert set(res.KERNEL_LADDER) == {"pallas-unfused", "xla-fused",
                                      "xla-unfused"}
    assert res.is_kernel_failure(res.VmemOverflowError("x"))
    assert res.is_kernel_failure(ValueError("RESOURCE_EXHAUSTED: vmem"))
    assert not res.is_kernel_failure(res.InjectedFault("x"))
    assert not res.is_kernel_failure(KeyError("unrelated"))


@pytest.mark.parametrize("exc", [
    NotImplementedError("Unimplemented primitive in Pallas TPU lowering "
                        "for KernelType.TC: cumsum"),
    NotImplementedError("Only 2D gather is supported"),
    RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: Invalid "
                 "relayout"),
    AttributeError("module 'jax.experimental.pallas.tpu' has no attribute "
                   "'TPUMemorySpace'"),
], ids=["unimplemented-primitive", "gather", "mosaic", "missing-api"])
def test_lowering_refusal_is_not_ladder_eligible(exc):
    """A kernel the compiler refuses, or an API that is gone, must raise:
    stepping down would hide that the requested route never ran."""
    assert not res.is_kernel_failure(exc)
    cfg = SweepConfig(engine_backend="pallas")

    def run(c):
        raise exc

    notes = []
    with pytest.raises(type(exc)):
        res.run_with_degradation(run, cfg, notes)
    assert notes == []


def test_fused_pallas_on_tpu_raises_up_front(monkeypatch):
    """On a TPU (steered here through ``jax.default_backend``) as anywhere
    else, pallas with engine_chunk_iters is refused when the options are
    built — before a handle, a trace or a ladder exists — by an error
    naming both knobs."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="engine_backend='pallas'.*"
                                         "engine_chunk_iters"):
        SolverOptions(method="prd", engine_backend="pallas",
                      engine_chunk_iters=8)


@pytest.mark.parametrize("engine,rung", [
    (dict(engine_chunk_iters=64), "xla-fused"),
    (dict(engine_backend="pallas"), "pallas-unfused"),
], ids=["xla-fused", "pallas-unfused"])
def test_vmem_overflow_degrades_one_rung(engine, rung):
    """A kernel-class failure mid-solve re-runs one rung down; the result
    is bit-correct and the degradation is recorded, never silent."""
    p, part = _instance()
    want = maxflow_oracle(p)[0]
    h = Solver(SolverOptions(method="prd", **engine)).prepare(p, part)
    with fault_injection(FaultPlan("vmem_overflow", at_sweep=1)):
        got = h.solve()
    assert got.flow_value == want and got.converged
    assert len(got.stats.degraded) == 1
    assert got.stats.degraded[0].startswith(f"{rung} -> xla-unfused")


def test_ladder_bottoms_out():
    p, part = _instance()
    h = Solver(SolverOptions(method="prd")).prepare(p, part)   # xla-unfused
    with fault_injection(FaultPlan("vmem_overflow", at_sweep=1)):
        with pytest.raises(res.VmemOverflowError):
            h.solve()


# --------------------------------------------------------------------------
# input validation + structured non-convergence
# --------------------------------------------------------------------------

def test_validate_problem_rejects_bad_inputs():
    p, _part = _instance()
    neg = dataclasses.replace(
        p, cap_fwd=np.where(np.arange(len(p.cap_fwd)) == 0, -1,
                            p.cap_fwd).astype(np.int32))
    with pytest.raises(ProblemValidationError, match="negative cap_fwd"):
        Solver().prepare(neg)

    pair = dataclasses.replace(
        p,
        cap_fwd=np.where(np.arange(len(p.cap_fwd)) == 0, 1 << 29,
                         p.cap_fwd).astype(np.int32),
        cap_bwd=np.where(np.arange(len(p.cap_bwd)) == 0, 1 << 29,
                         p.cap_bwd).astype(np.int32))
    with pytest.raises(ProblemValidationError, match="INF_CAP"):
        Solver().prepare(pair)

    term = dataclasses.replace(
        p, excess=np.where(np.arange(p.num_vertices) == 0, 1 << 30,
                           p.excess).astype(np.int64))
    with pytest.raises(ProblemValidationError):
        Solver().prepare(term)


def test_update_guard_and_opt_out():
    p, part = _instance()
    h = Solver(SolverOptions()).prepare(p, part)
    h.solve()
    with pytest.raises(ProblemValidationError, match="update"):
        h.update(cap_fwd=np.full(len(p.cap_fwd), -3, np.int32))
    # the rejected update must not have touched the handle's problem
    np.testing.assert_array_equal(h.problem.cap_fwd, p.cap_fwd)
    # opt-out: check=False skips the overflow screens (serving path)
    risky = dataclasses.replace(
        p,
        cap_fwd=np.where(np.arange(len(p.cap_fwd)) == 0, 1 << 29,
                         p.cap_fwd).astype(np.int32),
        cap_bwd=np.where(np.arange(len(p.cap_bwd)) == 0, 1 << 29,
                         p.cap_bwd).astype(np.int32))
    Solver(SolverOptions(check=False)).prepare(risky)   # does not raise


def test_dimacs_rejects_overflow_risk():
    text = ("p max 4 3\n" "n 1 s\n" "n 4 t\n"
            f"a 1 2 {1 << 30}\n" "a 2 3 5\n" "a 3 4 5\n")
    with pytest.raises(ProblemValidationError, match="DIMACS input"):
        read_dimacs(text)


def test_max_sweeps_yields_structured_nonconvergence():
    p, part = _instance()
    full = Solver(SolverOptions(method="prd")).prepare(p, part).solve()
    assert full.converged and full.diagnosis is None
    assert full.stats.sweeps >= 2

    capped = Solver(SolverOptions(method="prd", max_sweeps=1)).prepare(
        p, part).solve()                          # check=True must NOT raise
    assert capped.converged is False
    assert capped.stats.converged is False
    d = capped.diagnosis
    assert d is not None and d.reason == "max_sweeps"
    assert d.sweeps == 1 and d.max_sweeps == 1
    assert d.active_vertices > 0
    assert d.violations == []                     # intact, just unfinished
    assert "max_sweeps" in d.summary()
    assert capped.flow_value <= full.flow_value


# --------------------------------------------------------------------------
# converged-checkpoint short-circuit (no extra no-op sweep on resume)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("device_resident", [False, True])
def test_sharded_converged_checkpoint_resumes_without_extra_sweep(
        tmp_path, device_resident):
    """Sharded resume from the CONVERGED final-boundary checkpoint must
    return the finished result without re-entering the sweep loop — the
    legacy converged-entry semantics (ShardedExecutor.keep_running's
    ``idx == start`` term) would otherwise run one extra no-op sweep."""
    p, part = _instance()
    mesh = jax.make_mesh((1,), ("regions",))
    opts = SolverOptions(method="prd", device_resident=device_resident,
                         host_sync_every=1 if device_resident else None)
    base = Solver(opts).prepare(p, part).solve(
        mesh=mesh, checkpoint=CheckpointPolicy(directory=tmp_path, every=1))
    assert base.converged and base.stats.sweeps >= 2

    latest = res.latest_checkpoint(tmp_path)
    assert latest.sweeps == base.stats.sweeps
    assert res.checkpoint_converged(latest)

    got = Solver(opts).prepare(p, part).solve(mesh=mesh,
                                              resume_from=tmp_path)
    assert got.converged
    assert got.flow_value == base.flow_value
    assert got.stats.sweeps == base.stats.sweeps, \
        "converged-checkpoint resume ran extra sweeps"
    np.testing.assert_array_equal(got.source_side, base.source_side)
    np.testing.assert_array_equal(np.asarray(got.state.d),
                                  np.asarray(base.state.d))

    # a NON-converged mid-solve checkpoint must not short-circuit
    mid = res.load_checkpoint(tmp_path, step=1)
    assert not res.checkpoint_converged(mid)
