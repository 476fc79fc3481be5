"""Warm-start incremental re-solves (``handle.update`` + ``handle.solve``).

The acceptance bar: after ANY capacity perturbation, a warm re-solve must
reach exactly the flow value (and a valid mincut) of a cold solve on the
perturbed problem — the Kohli-Torr reparameterization of
``graph.apply_update`` plus the ``warm_labels`` policy are pure
performance devices.  Checked across perturbation classes
(increase-only / decrease-only / mixed; p in {1%, 10%}) on 16^2/24^2
grids, across ard/prd x xla/xla-fused/pallas x host-loop/device-resident
drivers,
and on the 64^2 interactive-segmentation instance where the warm solve
must also use strictly fewer sweeps than the cold one.  The preflow and
label invariants of ``test_discharge_invariants.py`` are asserted
directly on the reparameterized state.
"""

import numpy as np
import pytest

from repro.core import Solver, SolverOptions, solve_mincut, grid_partition
from repro.core.graph import intra_mask
from repro.core.labels import gather_ghost_labels
from repro.data.grids import segmentation_seeds_grid, synthetic_grid

# every solve in this module runs check=True: the cut-cost == flow
# assertion inside the solver prices the extracted cut in the CURRENT
# (perturbed, un-reparameterized) initial network, so each warm solve
# already proves its cut is a mincut of the perturbed problem.


def _perturb_kwargs(problem, rng, kind, p):
    m = len(problem.edges)
    k = max(1, int(round(p * m)))
    idx = rng.choice(m, size=k, replace=False)
    if kind == "increase":
        new_f = problem.cap_fwd[idx] + rng.randint(1, 151, size=k)
        new_b = problem.cap_bwd[idx] + rng.randint(1, 151, size=k)
    elif kind == "decrease":
        new_f = problem.cap_fwd[idx] // rng.randint(2, 5, size=k)
        new_b = problem.cap_bwd[idx] // rng.randint(2, 5, size=k)
    else:                                   # mixed: re-randomize
        new_f = rng.randint(0, 301, size=k)
        new_b = rng.randint(0, 301, size=k)
    return dict(arcs=idx, cap_fwd=new_f.astype(np.int32),
                cap_bwd=new_b.astype(np.int32))


def _assert_warm_matches_cold(handle, solver, part, opts):
    """Warm re-solve == cold solve of the (updated) problem, exactly."""
    warm = handle.solve()
    cold = solve_mincut(handle.problem, part=part,
                        config=opts.sweep_config())
    assert warm.flow_value == cold.flow_value
    # both cuts already passed the cut-cost == flow check; they need not be
    # the identical partition (mincuts are not unique), so compare values
    return warm, cold


@pytest.mark.parametrize("g", [16, 24])
@pytest.mark.parametrize("kind", ["increase", "decrease", "mixed"])
@pytest.mark.parametrize("p", [0.01, 0.1], ids=["p1", "p10"])
def test_warm_resolve_matches_cold(g, kind, p):
    prob = synthetic_grid(g, g, connectivity=8, strength=150, seed=g)
    part = grid_partition((g, g), (2, 2))
    opts = SolverOptions()
    solver = Solver(opts)
    handle = solver.prepare(prob, part)
    handle.solve()
    rng = np.random.RandomState(hash((g, kind, p)) % (2**31))
    handle.update(**_perturb_kwargs(handle.problem, rng, kind, p))
    _assert_warm_matches_cold(handle, solver, part, opts)


DRIVER_MATRIX = [
    ("ard", "xla", None, False),
    ("ard", "xla", None, True),
    ("ard", "xla", 8, False),
    ("ard", "xla", 8, True),
    ("ard", "pallas", None, False),
    ("ard", "pallas", None, True),
    ("prd", "xla", None, False),
    ("prd", "xla", None, True),
    ("prd", "xla", 8, False),
    ("prd", "xla", 8, True),
    ("prd", "pallas", None, False),
    ("prd", "pallas", None, True),
]
DRIVER_IDS = [f"{m}-{b}{'-fused' if c else ''}-{'dr' if d else 'host'}"
              for m, b, c, d in DRIVER_MATRIX]


@pytest.mark.parametrize("method,backend,chunk,dr", DRIVER_MATRIX,
                         ids=DRIVER_IDS)
def test_warm_resolve_across_drivers(method, backend, chunk, dr):
    prob = synthetic_grid(16, 16, connectivity=8, strength=150, seed=1)
    part = grid_partition((16, 16), (2, 2))
    opts = SolverOptions(method=method, engine_backend=backend,
                         engine_chunk_iters=chunk, device_resident=dr)
    solver = Solver(opts)
    handle = solver.prepare(prob, part)
    handle.solve()
    rng = np.random.RandomState(3)
    handle.update(**_perturb_kwargs(handle.problem, rng, "mixed", 0.1))
    _assert_warm_matches_cold(handle, solver, part, opts)


def test_warm_host_loop_and_device_resident_bitexact():
    """The two single-instance drivers must agree bit-exactly on the SAME
    warm entry state (labels, flow, counters) — warmth is driver-
    independent."""
    import dataclasses

    from repro.core import build, solve

    prob = synthetic_grid(16, 16, connectivity=8, strength=150, seed=2)
    part = grid_partition((16, 16), (2, 2))
    opts = SolverOptions()
    solver = Solver(opts)
    handle = solver.prepare(prob, part)
    handle.solve()
    rng = np.random.RandomState(5)
    handle.update(**_perturb_kwargs(handle.problem, rng, "mixed", 0.05))
    entry = handle._entry_state()
    cfg = opts.sweep_config()
    st_h, stats_h = solve(handle.meta, entry, cfg, warm=True)
    st_d, stats_d = solve(handle.meta, entry,
                          dataclasses.replace(cfg, device_resident=True),
                          warm=True)
    assert int(st_h.flow_to_t) == int(st_d.flow_to_t)
    np.testing.assert_array_equal(np.asarray(st_h.d), np.asarray(st_d.d))
    np.testing.assert_array_equal(np.asarray(st_h.cf), np.asarray(st_d.cf))
    assert stats_h.sweeps == stats_d.sweeps
    assert stats_h.engine_iters == stats_d.engine_iters
    assert stats_h.engine_launches == stats_d.engine_launches


def test_terminal_updates_match_cold():
    """excess / sink_cap deltas (incl. decreases below drained flow) warm-
    resolve to the cold flow."""
    prob = synthetic_grid(16, 16, connectivity=8, strength=150, seed=7)
    part = grid_partition((16, 16), (2, 2))
    opts = SolverOptions()
    solver = Solver(opts)
    handle = solver.prepare(prob, part)
    handle.solve()
    rng = np.random.RandomState(9)
    snk = handle.problem.sink_cap.copy()
    exc = handle.problem.excess.copy()
    nz = np.nonzero(snk)[0]
    snk[nz[: len(nz) // 2]] = 0             # drop t-links below their flow
    ez = np.nonzero(exc)[0]
    exc[ez[: len(ez) // 3]] //= 4           # retract source mass
    exc[ez[len(ez) // 3:]] += rng.randint(0, 100, size=len(ez)
                                          - len(ez) // 3)
    handle.update(excess=exc, sink_cap=snk)
    _assert_warm_matches_cold(handle, solver, part, opts)


def test_stacked_updates_before_one_solve():
    """Several updates may accumulate before the next solve; offsets and
    deltas compose."""
    prob = synthetic_grid(16, 16, connectivity=8, strength=150, seed=11)
    part = grid_partition((16, 16), (2, 2))
    opts = SolverOptions()
    solver = Solver(opts)
    handle = solver.prepare(prob, part)
    handle.solve()
    rng = np.random.RandomState(13)
    for kind in ("decrease", "increase", "mixed"):
        handle.update(**_perturb_kwargs(handle.problem, rng, kind, 0.03))
    _assert_warm_matches_cold(handle, solver, part, opts)


def test_update_before_first_solve_is_plain_edit():
    """Updating a cold handle is just a capacity edit — the first solve
    equals a cold solve of the edited problem."""
    prob = synthetic_grid(16, 16, connectivity=8, strength=150, seed=17)
    part = grid_partition((16, 16), (2, 2))
    opts = SolverOptions()
    solver = Solver(opts)
    handle = solver.prepare(prob, part)
    rng = np.random.RandomState(19)
    handle.update(**_perturb_kwargs(handle.problem, rng, "mixed", 0.1))
    res = handle.solve()
    cold = solve_mincut(handle.problem, part=part)
    assert res.flow_value == cold.flow_value
    assert int(handle._flow_offset) == 0    # zero flow: nothing to clamp


# --------------------------------------------------------------------------
# Invariants of the reparameterized state (test_discharge_invariants.py's
# properties, checked right after ``update`` + the label policy).
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["increase", "decrease", "mixed"])
def test_reparameterized_state_invariants(kind):
    prob = synthetic_grid(16, 16, connectivity=8, strength=150, seed=23)
    part = grid_partition((16, 16), (2, 2))
    opts = SolverOptions()
    solver = Solver(opts)
    handle = solver.prepare(prob, part)
    handle.solve()
    rng = np.random.RandomState(29)
    handle.update(**_perturb_kwargs(handle.problem, rng, kind, 0.1))
    meta = handle.meta
    st = handle._entry_state()              # warm_labels policy applied
    lay = handle.layout
    p = handle.problem

    cf = np.asarray(st.cf)
    sink_cf = np.asarray(st.sink_cf)
    excess = np.asarray(st.excess)
    d = np.asarray(st.d)
    vmask = np.asarray(st.vmask)

    # preflow validity: nonnegative residuals and excess everywhere
    assert (cf >= 0).all() and (sink_cf >= 0).all()
    assert (excess[vmask] >= 0).all()

    # residual pair invariant: cf(u,v) + cf(v,u) == c'(u,v) + c'(v,u)
    flat = cf.reshape(-1)
    pair = flat[lay.edge_arc_u] + flat[lay.edge_arc_v]
    np.testing.assert_array_equal(
        pair, p.cap_fwd.astype(np.int64) + p.cap_bwd)

    # t-links cover the reparameterization: sink_cf >= sink_cap - drained,
    # and padding slots stay untouched
    assert not sink_cf[~vmask].any() and not excess[~vmask].any()

    # label validity (ARD, cf. test_discharge_invariants): for d(u) < d_inf
    # a residual intra arc needs d(u) <= d(v), a residual cross arc
    # d(u) <= d(ghost) + 1, and an open t-link d(u) == 0
    intra = np.asarray(intra_mask(st))
    emask = np.asarray(st.emask)
    nbr = np.asarray(st.nbr_local)
    ghost = np.asarray(gather_ghost_labels(st))
    K, V, E = cf.shape
    for r in range(K):
        for u in range(V):
            if not vmask[r, u] or d[r, u] >= meta.d_inf_ard:
                continue
            if sink_cf[r, u] > 0:
                assert d[r, u] == 0, (r, u)
            for e in range(E):
                if not emask[r, u, e] or cf[r, u, e] <= 0:
                    continue
                if intra[r, u, e]:
                    assert d[r, u] <= d[r, nbr[r, u, e]], (r, u, e)
                elif ghost[r, u, e] < meta.d_inf_ard:
                    assert d[r, u] <= ghost[r, u, e] + 1, (r, u, e)


# --------------------------------------------------------------------------
# The 64^2 acceptance instance: bit-exact flow, strictly fewer sweeps.
# --------------------------------------------------------------------------

def test_warm_start_64x64_acceptance():
    """On the 64^2 interactive-segmentation instance, a warm re-solve after
    a 1% capacity perturbation reaches the cold flow value bit-exactly in
    strictly fewer sweeps, and the same-shape re-solve cycle retraces
    nothing."""
    prob = segmentation_seeds_grid(64, 64, seed=0)
    part = grid_partition((64, 64), (4, 4))
    opts = SolverOptions(num_regions=16)
    solver = Solver(opts)
    handle = solver.prepare(prob, part)
    handle.solve()

    rng = np.random.RandomState(0)
    handle.update(**_perturb_kwargs(handle.problem, rng, "mixed", 0.01))
    warm, cold = _assert_warm_matches_cold(handle, solver, part, opts)
    assert warm.stats.sweeps < cold.stats.sweeps
    assert warm.stats.engine_launches < cold.stats.engine_launches

    # second same-shape cycle: the session retraces nothing.  (warm2's
    # optimality is certified by the in-solve cut-cost == flow check: a cut
    # whose cost in the perturbed initial network equals the flow value
    # proves both are optimal, no cold reference needed.)
    traces = solver.cache_info().traces
    handle.update(**_perturb_kwargs(handle.problem, rng, "mixed", 0.01))
    handle.solve()
    assert solver.cache_info().traces == traces
