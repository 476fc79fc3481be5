"""Serving-tier robustness matrix (deterministic: fake clock, injected
faults, no wall time).

Every scenario asserts BOTH the typed error a client sees and the
``ServiceStats`` counter it increments: deadline expiry mid-solve and in
the queue, queue-full shedding (per tenant), LRU handle eviction + warm
resume, circuit-breaker trip/cooldown/recovery over the kernel ladder,
supervised retry of injected batch faults — plus the overload acceptance
scenario: bursty load over capacity with tight deadlines and a kernel
fault mid-stream keeps the service up and bounded (every request resolves
to a result or a typed error, queue depth never exceeds its bound,
in-flight requests survive the fault via degradation).
"""

import numpy as np
import pytest

from repro.core import FaultPlan, Solver, SolverOptions, fault_injection
from repro.data.grids import synthetic_grid
from repro.kernels.ref import maxflow_oracle
from repro.serve import (DeadlineExceeded, ERROR_TAXONOMY, MaxflowService,
                         RequestFailed, ServiceClosed, ServiceConfig,
                         ServiceError, ServiceOverloaded, SolveRequest,
                         solve_with_deadline)


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _grid(seed=0, n=6):
    return synthetic_grid(n, n, seed=seed)


OPTS = SolverOptions(num_regions=4)


# --------------------------------------------------------------------------
# baseline: continuous batching matches the oracle
# --------------------------------------------------------------------------

def test_service_mixed_stream_matches_oracle():
    """Heterogeneous shapes through the continuous-batching loop give
    per-instance oracle flows; the liveness invariant holds throughout."""
    svc = MaxflowService(OPTS, ServiceConfig(max_batch=2, sync_every=2),
                         clock=FakeClock())
    probs = [_grid(seed=s) for s in range(3)] + [_grid(seed=7, n=8)]
    tickets = [svc.submit(SolveRequest(problem=p)) for p in probs]
    svc.run_until_idle()
    for p, t in zip(probs, tickets):
        assert t.outcome().flow_value == maxflow_oracle(p)[0]
    assert svc.stats.completed == len(probs)
    assert svc.stats.swaps == len(probs)
    assert svc.healthy() and svc.ready()
    rep = svc.report()
    assert rep["completed"] == len(probs) and rep["healthy"]
    assert set(rep["breaker"]) == {"pallas-unfused", "xla-fused",
                                   "xla-unfused"}


def test_service_slot_swap_admits_into_live_batch():
    """With one slot per bucket, a second same-shape request must wait
    for the slot and then swap into the LIVE batch (no new bucket)."""
    svc = MaxflowService(OPTS, ServiceConfig(max_batch=1, sync_every=1),
                         clock=FakeClock())
    p1, p2 = _grid(seed=0), _grid(seed=1)
    t1 = svc.submit(SolveRequest(problem=p1))
    t2 = svc.submit(SolveRequest(problem=p2))
    svc.step()
    assert svc.stats.in_flight == 1 and svc.stats.queue_depth == 1
    svc.run_until_idle()
    assert t1.outcome().flow_value == maxflow_oracle(p1)[0]
    assert t2.outcome().flow_value == maxflow_oracle(p2)[0]
    assert len(svc._buckets) == 1
    assert svc.stats.swaps == 2


def test_warm_session_recut_through_service():
    """A session request re-cuts warm: the prepared handle is reused and
    the updated problem's flow matches a cold oracle solve."""
    svc = MaxflowService(OPTS, ServiceConfig(max_batch=2),
                         clock=FakeClock())
    p = _grid(seed=3)
    t1 = svc.submit(SolveRequest(problem=p, session="cam"))
    svc.run_until_idle()
    assert t1.outcome().flow_value == maxflow_oracle(p)[0]
    arcs = np.arange(4)
    t2 = svc.submit(SolveRequest(
        session="cam",
        update={"arcs": arcs, "cap_fwd": p.cap_fwd[arcs] + 70}))
    svc.run_until_idle()
    updated = svc._sessions["cam"].problem
    assert t2.outcome().flow_value == maxflow_oracle(updated)[0]
    assert svc.stats.completed == 2


# --------------------------------------------------------------------------
# deadlines
# --------------------------------------------------------------------------

def test_deadline_expiry_mid_solve():
    """A deadline crossing mid-solve kills the request at the next sweep
    boundary with sweeps-completed + partial-flow diagnostics."""
    p = _grid(seed=0, n=10)
    base = Solver(OPTS).solve(p)
    assert base.stats.sweeps >= 3, "instance too easy to expire mid-solve"
    clk = FakeClock()
    svc = MaxflowService(OPTS, ServiceConfig(max_batch=1, sync_every=1),
                         clock=clk)
    t = svc.submit(SolveRequest(problem=p, timeout=5.0))
    svc.step()                       # admitted; one sweep run
    assert svc.stats.in_flight == 1
    clk.advance(10.0)                # deadline passes mid-solve
    svc.step()
    assert t.done
    with pytest.raises(DeadlineExceeded) as ei:
        t.outcome()
    err = ei.value
    assert err.stage == "running"
    assert err.sweeps_completed >= 1
    assert isinstance(err.partial_flow, int)
    assert 0 <= err.partial_flow <= base.flow_value  # a valid preflow's
    assert err.code == "deadline_exceeded" and not err.retriable
    assert svc.stats.deadline_misses == 1
    assert svc.healthy()
    # the freed slot serves the next request normally
    t2 = svc.submit(SolveRequest(problem=_grid(seed=2)))
    svc.run_until_idle()
    assert t2.outcome().flow_value == maxflow_oracle(_grid(seed=2))[0]


def test_deadline_expiry_in_queue():
    """A request whose deadline passes before admission dies in the queue
    (stage="queued", zero sweeps)."""
    clk = FakeClock()
    svc = MaxflowService(OPTS, ServiceConfig(max_batch=1, sync_every=1),
                         clock=clk)
    # same shape: t2 must wait for t1's (only) slot in the shared bucket
    t1 = svc.submit(SolveRequest(problem=_grid(seed=0, n=10)))
    t2 = svc.submit(SolveRequest(problem=_grid(seed=1, n=10), timeout=2.0))
    svc.step()                       # t1 takes the only slot; t2 queued
    clk.advance(5.0)
    svc.step()
    assert t2.done
    with pytest.raises(DeadlineExceeded) as ei:
        t2.outcome()
    assert ei.value.stage == "queued"
    assert ei.value.sweeps_completed == 0
    assert svc.stats.deadline_misses == 1
    svc.run_until_idle()
    assert t1.outcome().converged


def test_solve_with_deadline_single_handle_routes():
    """The single-handle deadline route: aborts at a sweep boundary with
    diagnostics; the handle survives and re-solves cleanly after."""
    p = _grid(seed=0, n=10)
    for opts in (OPTS,
                 SolverOptions(num_regions=4, device_resident=True,
                               host_sync_every=1)):
        base = Solver(opts).solve(p)
        assert base.stats.sweeps >= 3
        clk = FakeClock()

        def ticking():
            clk.advance(1.0)
            return clk.t

        h = Solver(opts).prepare(p)
        with pytest.raises(DeadlineExceeded) as ei:
            solve_with_deadline(h, timeout=2.5, clock=ticking)
        err = ei.value
        assert err.stage == "running" and err.sweeps_completed >= 1
        assert err.sweeps_completed < base.stats.sweeps
        assert 0 <= err.partial_flow <= base.flow_value
        assert h.solve().flow_value == base.flow_value  # handle intact


# --------------------------------------------------------------------------
# admission control
# --------------------------------------------------------------------------

def test_queue_full_sheds_per_tenant():
    svc = MaxflowService(OPTS, ServiceConfig(max_queue=2, retry_after=1.5),
                         clock=FakeClock())
    probs = [_grid(seed=s) for s in range(5)]
    tenants = ["a", "a", "b", "a", "b"]
    tickets = [svc.submit(SolveRequest(problem=p, tenant=tn))
               for p, tn in zip(probs, tenants)]
    shed = [t for t in tickets if t.done]
    assert len(shed) == 3            # queue bound 2: requests 3-5 shed
    for t in shed:
        with pytest.raises(ServiceOverloaded) as ei:
            t.outcome()
        err = ei.value
        assert err.retriable and err.retry_after == 1.5
        assert err.queue_depth == 2 and err.bound == 2
    assert svc.stats.sheds == 3
    assert svc.stats.sheds_by_tenant == {"a": 1, "b": 2}
    assert not svc.ready()           # full queue: not ready
    svc.run_until_idle()
    assert svc.ready() and svc.healthy()
    assert svc.stats.completed == 2  # the admitted two completed
    # shedding is immediate and typed, never an unbounded queue
    assert svc.stats.max_queue_depth <= 2


def test_closed_service_rejects_typed():
    svc = MaxflowService(OPTS, clock=FakeClock())
    svc.close()
    t = svc.submit(SolveRequest(problem=_grid()))
    with pytest.raises(ServiceClosed):
        t.outcome()
    assert svc.stats.submitted == 0  # never entered


def test_malformed_request_fails_typed_and_service_survives():
    """A re-cut against a session the service never saw (e.g. its create
    request was shed) must fail THAT request typed, not crash the loop."""
    svc = MaxflowService(OPTS, clock=FakeClock())
    bad = svc.submit(SolveRequest(session="never-created",
                                  update=dict(arcs=np.array([0]))))
    good = svc.submit(SolveRequest(problem=_grid()))
    svc.run_until_idle()
    with pytest.raises(RequestFailed) as ei:
        bad.outcome()
    assert "never-created" in str(ei.value) and ei.value.attempts == 0
    assert svc.stats.failed == 1
    assert good.outcome().flow_value == maxflow_oracle(_grid())[0]
    assert svc.healthy()


# --------------------------------------------------------------------------
# handle LRU + eviction-to-checkpoint + warm resume
# --------------------------------------------------------------------------

def test_lru_eviction_and_warm_resume(tmp_path):
    p = _grid(seed=0)
    probe = Solver(OPTS).prepare(p)
    one = MaxflowService._handle_bytes(probe)
    svc = MaxflowService(
        OPTS,
        ServiceConfig(max_batch=1, handle_budget_bytes=int(1.5 * one),
                      eviction_dir=str(tmp_path)),
        clock=FakeClock())
    ta = svc.submit(SolveRequest(problem=p, session="a"))
    svc.run_until_idle()
    tb = svc.submit(SolveRequest(problem=_grid(seed=1), session="b"))
    svc.run_until_idle()
    # budget fits ~1.5 handles: LRU session "a" was checkpointed off
    assert svc.stats.evictions == 1
    assert "a" in svc._evicted and "a" not in svc._sessions
    assert "b" in svc._sessions
    assert any(tmp_path.glob("a/step_*")), "no eviction snapshot on disk"
    assert svc.stats.resident_bytes <= int(1.5 * one)

    # next request for "a" resumes it warm: zero sweeps, same flow
    ta2 = svc.submit(SolveRequest(session="a"))
    svc.run_until_idle()
    assert svc.stats.warm_resumes == 1
    assert ta2.outcome().flow_value == ta.outcome().flow_value
    assert ta2.outcome().stats.sweeps == 0, "resumed session was not warm"
    assert "a" in svc._sessions and "a" not in svc._evicted
    assert svc.healthy()


# --------------------------------------------------------------------------
# circuit breaker over the degradation ladder
# --------------------------------------------------------------------------

PALLAS_OPTS = SolverOptions(num_regions=4, engine_backend="pallas")


def test_breaker_trip_cooldown_recovery():
    """A kernel fault degrades the chunk down the ladder WITHOUT failing
    the in-flight request, trips the rung's breaker (threshold 1), which
    is then skipped at entry until the cooldown's half-open probe closes
    it again."""
    clk = FakeClock()
    svc = MaxflowService(
        PALLAS_OPTS,
        ServiceConfig(max_batch=1, sync_every=4, breaker_threshold=1,
                      breaker_cooldown=30.0),
        clock=clk)
    p1 = _grid(seed=0)
    with fault_injection(FaultPlan("vmem_overflow", at_sweep=1, times=1,
                                   route="device")):
        t1 = svc.submit(SolveRequest(problem=p1))
        svc.run_until_idle()
    # the in-flight request survived the fault via the ladder
    assert t1.outcome().flow_value == maxflow_oracle(p1)[0]
    assert svc.stats.faults == 1
    assert svc.stats.degradations == 1
    assert svc.stats.breaker_trips == 1
    assert svc.board["pallas-unfused"].state == "open"

    # while open: chunks enter one rung down, skipping the broken rung
    p2 = _grid(seed=1)
    t2 = svc.submit(SolveRequest(problem=p2))
    svc.run_until_idle()
    assert t2.outcome().flow_value == maxflow_oracle(p2)[0]
    assert svc.stats.breaker_skips >= 1
    assert svc.stats.faults == 1     # no new fault: the rung was skipped

    # cooldown elapses: half-open lets one probe through; success closes
    clk.advance(31.0)
    assert svc.board["pallas-unfused"].state == "half-open"
    p3 = _grid(seed=2)
    t3 = svc.submit(SolveRequest(problem=p3))
    svc.run_until_idle()
    assert t3.outcome().flow_value == maxflow_oracle(p3)[0]
    assert svc.board["pallas-unfused"].state == "closed"
    assert svc.report()["breaker"]["pallas-unfused"] == "closed"


# --------------------------------------------------------------------------
# supervised retries of faulted batches
# --------------------------------------------------------------------------

def test_supervisor_retries_injected_fault():
    """A non-kernel injected fault re-runs the chunk from the intact
    boundary; the request completes with the oracle flow."""
    svc = MaxflowService(OPTS, ServiceConfig(max_batch=1, max_retries=2),
                         clock=FakeClock())
    p = _grid(seed=0)
    with fault_injection(FaultPlan("raise", at_sweep=1, times=1,
                                   route="device")):
        t = svc.submit(SolveRequest(problem=p))
        svc.run_until_idle()
    assert t.outcome().flow_value == maxflow_oracle(p)[0]
    assert svc.stats.faults == 1 and svc.stats.retries == 1
    assert svc.stats.failed == 0


def test_supervisor_exhaustion_fails_typed():
    """Retries exhausted: the batch's requests resolve to RequestFailed;
    the service stays up and serves the next request."""
    svc = MaxflowService(OPTS, ServiceConfig(max_batch=1, max_retries=1),
                         clock=FakeClock())
    p = _grid(seed=0)
    with fault_injection(FaultPlan("raise", at_sweep=1, times=-1,
                                   route="device")):
        t = svc.submit(SolveRequest(problem=p))
        svc.run_until_idle()
    with pytest.raises(RequestFailed) as ei:
        t.outcome()
    assert ei.value.attempts == 2    # first run + 1 retry
    assert "InjectedFault" in ei.value.cause
    assert svc.stats.failed == 1 and svc.stats.retries == 1
    assert svc.healthy()
    t2 = svc.submit(SolveRequest(problem=p))
    svc.run_until_idle()
    assert t2.outcome().flow_value == maxflow_oracle(p)[0]


# --------------------------------------------------------------------------
# the acceptance scenario: overload + tight deadlines + mid-stream fault
# --------------------------------------------------------------------------

def test_overload_with_tight_deadlines_and_fault_stays_bounded():
    """Offered load beyond capacity with 25% tight deadlines and a kernel
    fault mid-stream: the service stays up, every request resolves to a
    result or a typed error, and the queue never exceeds its bound."""
    clk = FakeClock()
    svc = MaxflowService(
        PALLAS_OPTS,
        ServiceConfig(max_queue=4, max_batch=2, sync_every=1,
                      breaker_threshold=1),
        clock=clk)
    probs = [_grid(seed=s) for s in range(12)]
    tickets = []
    with fault_injection(FaultPlan("vmem_overflow", at_sweep=2, times=1,
                                   route="device")):
        for i, p in enumerate(probs):
            timeout = 0.5 if i % 4 == 0 else None     # 25% tight
            tickets.append(svc.submit(SolveRequest(
                problem=p, timeout=timeout, tenant=f"t{i % 2}")))
            if i % 3 == 2:           # bursty: 3 submits per service step
                svc.step()
                clk.advance(0.4)
            assert svc.stats.queue_depth <= 4, "queue bound violated"
        svc.run_until_idle()

    for t in tickets:               # every request reached a terminal,
        assert t.done               # typed outcome — none vanished
        if t.error is not None:
            assert isinstance(t.error, ServiceError)
            assert t.error.code in ERROR_TAXONOMY
        else:
            assert t.result.flow_value >= 0
    s = svc.stats
    assert s.completed + s.deadline_misses + s.sheds + s.failed \
        == s.submitted == len(probs)
    assert s.max_queue_depth <= 4
    assert s.failed == 0            # the kernel fault degraded, not failed
    assert s.faults >= 1 and s.degradations >= 1
    assert s.completed >= 1
    assert svc.healthy()
    # completed requests are CORRECT under overload, not just resolved
    for p, t in zip(probs, tickets):
        if t.error is None:
            assert t.result.flow_value == maxflow_oracle(p)[0]
