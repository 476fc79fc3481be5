"""Property-based tests (hypothesis): solver == oracle on arbitrary sparse
networks — through EVERY region executor — and the paper's structural
invariants hold after every sweep (via tests/invariants.py)."""

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -r "
           "requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax
import jax.numpy as jnp

import invariants
from repro.core import (Solver, SolverOptions, SweepConfig, build,
                        init_labels, solve_mincut, solve_mincut_batch)
from repro.core.graph import Problem
from repro.core.partition import block_partition
from repro.core.sweep import num_active, parallel_sweep
from repro.kernels.ref import maxflow_oracle


@st.composite
def problems(draw, max_n=12, max_m=20, max_cap=60):
    n = draw(st.integers(3, max_n))
    m = draw(st.integers(0, min(max_m, n * (n - 1) // 2)))
    rng = np.random.RandomState(draw(st.integers(0, 2**31 - 1)))
    pairs = set()
    while len(pairs) < m:
        u, v = rng.randint(0, n, 2)
        if u != v and (u, v) not in pairs and (v, u) not in pairs:
            pairs.add((u, v))
    edges = np.asarray(sorted(pairs), np.int64).reshape(-1, 2)
    return Problem(
        num_vertices=n, edges=edges,
        cap_fwd=rng.randint(0, max_cap, size=len(edges)).astype(np.int32),
        cap_bwd=rng.randint(0, max_cap, size=len(edges)).astype(np.int32),
        excess=rng.randint(0, 40, size=n).astype(np.int32),
        sink_cap=rng.randint(0, 40, size=n).astype(np.int32))


@settings(max_examples=25, deadline=None)
@given(problems(), st.integers(1, 4), st.booleans())
def test_flow_matches_oracle(p, k, use_ard):
    want, _ = maxflow_oracle(p)
    cfg = SweepConfig(method="ard" if use_ard else "prd")
    res = solve_mincut(p, num_regions=min(k, p.num_vertices), config=cfg)
    assert res.flow_value == want


# every route through the one generic executor loop: local host loop,
# local device-resident, batched (1-instance bucket), sharded (1-device
# mesh).  Shrinking-friendly small bounds: shapes stay tiny so a failing
# example minimizes fast.
EXECUTOR_ROUTES = ("host", "device", "batched", "sharded")


def _solve_via(route, p, part, cfg):
    if route == "batched":
        return solve_mincut_batch([p], parts=[part], config=cfg)[0]
    if route == "sharded":
        mesh = jax.make_mesh((1,), ("regions",))
        s = Solver(SolverOptions.from_sweep_config(cfg))
        return s.prepare(p, part).solve(mesh=mesh)
    if route == "device":
        cfg = SweepConfig(**{**cfg.__dict__, "device_resident": True})
    return solve_mincut(p, part=part, config=cfg)


@settings(max_examples=12, deadline=None)
@given(problems(max_n=9, max_m=14), st.sampled_from(EXECUTOR_ROUTES),
       st.booleans())
def test_flow_matches_oracle_every_executor(p, route, use_ard):
    want, _ = maxflow_oracle(p)
    cfg = SweepConfig(method="ard" if use_ard else "prd")
    part = block_partition(p.num_vertices, min(2, p.num_vertices))
    res = _solve_via(route, p, part, cfg)
    assert res.flow_value == want, route


@settings(max_examples=10, deadline=None)
@given(problems(max_n=8, max_m=12, max_cap=30), st.data())
def test_warm_resolve_after_delta_matches_oracle(p, data):
    """Warm-start re-solve after a random capacity delta: the session
    continues from the solved preflow and must land on the updated
    problem's true maxflow."""
    s = Solver(SolverOptions(num_regions=2))
    h = s.prepare(p)
    assert h.solve().flow_value == maxflow_oracle(p)[0]
    m, n = len(p.edges), p.num_vertices
    if m:
        h.update(cap_fwd=np.asarray(
            data.draw(st.lists(st.integers(0, 30), min_size=m, max_size=m)),
            np.int32))
    h.update(sink_cap=np.asarray(
        data.draw(st.lists(st.integers(0, 30), min_size=n, max_size=n)),
        np.int32))
    want, _ = maxflow_oracle(h.problem)
    assert h.solve().flow_value == want


@settings(max_examples=8, deadline=None)
@given(problems(max_n=8, max_m=12, max_cap=30),
       problems(max_n=8, max_m=12, max_cap=30), st.data())
def test_batched_warm_resolve_matches_oracle(p1, p2, data):
    """A 2-instance batch through the batched executor, then a random
    capacity delta on one instance and a warm batched re-solve: both
    instances must track their own oracle throughout."""
    s = Solver(SolverOptions(num_regions=2))
    h1, h2 = s.prepare(p1), s.prepare(p2)
    r = s.solve_many([h1, h2])
    assert r[0].flow_value == maxflow_oracle(p1)[0]
    assert r[1].flow_value == maxflow_oracle(p2)[0]
    m = len(p1.edges)
    if m:
        h1.update(cap_fwd=np.asarray(
            data.draw(st.lists(st.integers(0, 30), min_size=m, max_size=m)),
            np.int32))
    r2 = s.solve_many([h1, h2])       # h1 warm after the delta, h2 warm
    assert r2[0].flow_value == maxflow_oracle(h1.problem)[0]
    assert r2[1].flow_value == maxflow_oracle(p2)[0]


@settings(max_examples=10, deadline=None)
@given(problems(), st.integers(2, 3))
def test_sweep_invariants(p, k):
    """After every parallel ARD sweep: labels valid, monotone; flow sane
    (the checkers live in tests/invariants.py, shared with the
    conformance suite's sweep-boundary hook)."""
    part = block_partition(p.num_vertices, k)
    meta, state, _ = build(p, part)
    state = init_labels(meta, state)
    cfg = SweepConfig(method="ard", use_global_gap=False)
    prev_d = np.asarray(state.d)
    total0 = invariants.preflow_total(state)
    for sweep in range(12):
        if int(num_active(meta, state, cfg)) == 0:
            break
        state, *_ = parallel_sweep(meta, state, cfg,
                                  jnp.asarray(sweep, jnp.int32))
        d = np.asarray(state.d)
        assert (d >= prev_d).all(), "labels must be monotone"
        prev_d = d
        invariants.assert_valid_preflow(meta, state)
        invariants.assert_valid_labeling(meta, state, ard=True)
        invariants.assert_flow_conservation(meta, state, total0)


@settings(max_examples=10, deadline=None)
@given(problems())
def test_reduction_sound(p):
    from repro.core import region_reduction

    part = block_partition(p.num_vertices, 2)
    meta, state, layout = build(p, part)
    red = region_reduction(meta, state)
    want, _ = maxflow_oracle(p)
    res = solve_mincut(p, part=part)
    src = res.source_side
    sk = layout.to_flat(np.asarray(red.strong_sink))
    ss = layout.to_flat(np.asarray(red.strong_source))
    assert not (src & sk).any(), "strong sink on source side"
    assert (src[ss]).all() or not ss.any(), "strong source on sink side"
