"""Dtype-narrowing correctness: policy selection, validation guards,
byte accounting and bit-exactness vs the int32 oracle on every route."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (Solver, SolverOptions, grid_partition, solve_mincut)
from repro.core import dtypes as _dt
from repro.core.graph import (ProblemValidationError, build,
                              validate_problem, validate_update_dtypes)
from repro.core.sweep import SweepConfig, _page_and_msg_bytes
from repro.data.grids import synthetic_grid


def _small_problem(seed=1):
    """A 10x10 grid whose capacity mass fits the int16 flow bound."""
    p = synthetic_grid(10, 10, connectivity=4, strength=3, seed=seed)
    assert _dt.flows_fit_narrow(_dt.flow_mass(p))
    return p, grid_partition((10, 10), (2, 2))


def _big_problem():
    """A 16x16 grid whose capacity mass exceeds the int16 flow bound."""
    p = synthetic_grid(16, 16, connectivity=8, strength=150, seed=0)
    assert not _dt.flows_fit_narrow(_dt.flow_mass(p))
    return p, grid_partition((16, 16), (2, 2))


def _map_narrow_labels(d16):
    """Narrow labels -> the wide value space (sentinel classes map by a
    monotone offset), for exact comparison against an int32 solve."""
    d = np.asarray(d16).astype(np.int64)
    return np.where(d >= _dt.NARROW_INF_LABEL,
                    d - _dt.NARROW_INF_LABEL + _dt.INF_LABEL_WIDE, d)


# ---------------------------------------------------------------- policy

class TestPolicySelection:
    def test_int32_default_everywhere(self):
        p, part = _small_problem()
        meta, state, _ = build(p, part)
        assert meta.kernel_dtypes == _dt.WIDE
        assert state.cf.dtype == jnp.int32 and state.d.dtype == jnp.int32

    def test_auto_narrows_when_bounds_fit(self):
        p, part = _small_problem()
        meta, state, _ = build(p, part, dtype_policy="auto")
        assert meta.kernel_dtypes == _dt.NARROW
        assert state.cf.dtype == jnp.int16 and state.d.dtype == jnp.int16
        assert state.excess.dtype == jnp.int16

    def test_auto_falls_back_per_family(self):
        p, part = _big_problem()
        meta, _, _ = build(p, part, dtype_policy="auto")
        kd = meta.kernel_dtypes
        assert kd.flow == "int32"          # mass over the int16 bound
        assert kd.label == "int16"         # labels still fit
        assert kd.mask == "int8"           # any narrow family -> int8 masks

    def test_narrow_policy_raises_typed_error_naming_bound(self):
        p, part = _big_problem()
        with pytest.raises(ProblemValidationError) as e:
            validate_problem(p, context="problem", dtype_policy="narrow")
        msg = str(e.value)
        assert "int16" in msg and str(_dt.NARROW_FLOW_LIMIT) in msg

    def test_unknown_policy_rejected(self):
        with pytest.raises(ValueError):
            SolverOptions(dtype_policy="float16")
        p, part = _small_problem()
        with pytest.raises(ValueError):
            build(p, part, dtype_policy="int8")

    def test_sentinels_order_like_wide(self):
        assert _dt.inf_label_for("int16") == _dt.NARROW_INF_LABEL
        assert _dt.inf_label_for("int32") == _dt.INF_LABEL_WIDE
        # every representable narrow label sits strictly below the sentinel
        assert _dt.NARROW_LABEL_LIMIT + 1 < _dt.NARROW_INF_LABEL + 1 \
            < np.iinfo(np.int16).max


# ------------------------------------------------------------ validation

class TestUpdateGuard:
    def test_update_rejects_mass_growth_past_bound(self):
        p, part = _small_problem()
        s = Solver(SolverOptions(dtype_policy="narrow"))
        h = s.prepare(p, part)
        h.solve()
        m = len(p.edges)
        with pytest.raises(ProblemValidationError) as e:
            h.update(arcs=np.arange(m),
                     cap_fwd=np.full(m, 2000, np.int32))
        assert "int16" in str(e.value) and "re-prepare" in str(e.value)

    def test_update_within_bound_stays_narrow_and_exact(self):
        p, part = _small_problem()
        s16 = Solver(SolverOptions(dtype_policy="narrow"))
        s32 = Solver(SolverOptions(dtype_policy="int32"))
        h16, h32 = s16.prepare(p, part), s32.prepare(p, part)
        h16.solve(), h32.solve()
        idx = np.arange(6)
        caps = np.full(6, 5, np.int32)
        r16 = h16.update(arcs=idx, cap_fwd=caps).solve()
        r32 = h32.update(arcs=idx, cap_fwd=caps).solve()
        assert r16.flow_value == r32.flow_value
        assert h16.state.cf.dtype == jnp.int16

    def test_validate_update_dtypes_noop_for_wide(self):
        p, part = _small_problem()
        meta, _, _ = build(p, part)                  # wide build
        big, _ = _big_problem()
        validate_update_dtypes(meta, big)            # must not raise


# ------------------------------------------------------- byte accounting

class TestByteAccounting:
    def test_page_and_msg_bytes_wide_matches_historical(self):
        p, part = _small_problem()
        meta, state, _ = build(p, part)
        V, E = meta.region_size, meta.max_degree
        page, msg = _page_and_msg_bytes(meta)
        assert page == 16 * V * E + 16 * V
        assert msg == 8 * meta.num_cross_arcs

    def test_page_bytes_shrink_under_narrowing(self):
        p, part = _small_problem()
        meta_w, _, _ = build(p, part)
        meta_n, _, _ = build(p, part, dtype_policy="narrow")
        page_w, msg_w = _page_and_msg_bytes(meta_w)
        page_n, msg_n = _page_and_msg_bytes(meta_n)
        # the int32 topology slabs (nbr/rev) never narrow, so the page
        # shrinks less than the value-only fused VMEM does (~36% here)
        assert page_n < 0.70 * page_w
        assert msg_n == msg_w // 2        # (4+4) -> (2+2) bytes per arc


# ---------------------------------------------------------- bit-exactness

class TestBitExactMatrix:
    @pytest.mark.parametrize("method", ["ard", "prd"])
    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    @pytest.mark.parametrize("device_resident", [False, True])
    def test_narrow_matches_int32_oracle(self, method, backend,
                                         device_resident):
        p, part = _small_problem()
        out = {}
        for policy in ("int32", "narrow"):
            s = Solver(SolverOptions(
                method=method, engine_backend=backend,
                device_resident=device_resident, dtype_policy=policy))
            h = s.prepare(p, part)
            r = h.solve()
            out[policy] = r
        r32, r16 = out["int32"], out["narrow"]
        assert r16.flow_value == r32.flow_value
        assert r16.stats.sweeps == r32.stats.sweeps
        assert r16.stats.engine_iters == r32.stats.engine_iters
        assert (r16.source_side == r32.source_side).all()
        assert (np.asarray(r16.state.cf)
                == np.asarray(r32.state.cf)).all()
        assert (_map_narrow_labels(r16.state.d)
                == np.asarray(r32.state.d)).all()

    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_narrow_matches_int32_batched(self, backend):
        probs = [synthetic_grid(10, 10, connectivity=4, strength=3, seed=s)
                 for s in range(3)]
        part = grid_partition((10, 10), (2, 2))
        out = {}
        for policy in ("int32", "narrow"):
            s = Solver(SolverOptions(engine_backend=backend,
                                     dtype_policy=policy))
            rs = s.solve_many(probs, [part] * 3)
            out[policy] = [(r.flow_value, r.stats.sweeps,
                            r.stats.engine_iters) for r in rs]
        assert out["int32"] == out["narrow"]

    def test_narrow_matches_int32_sharded_one_device(self):
        p, part = _small_problem()
        mesh = jax.make_mesh((1,), ("regions",))
        out = {}
        for policy in ("int32", "narrow"):
            s = Solver(SolverOptions(dtype_policy=policy))
            h = s.prepare(p, part)
            r = h.solve(mesh=mesh)
            out[policy] = r
        r32, r16 = out["int32"], out["narrow"]
        assert r16.flow_value == r32.flow_value
        assert r16.stats.sweeps == r32.stats.sweeps
        assert (r16.source_side == r32.source_side).all()
        assert r16.state.cf.dtype == jnp.int16      # narrowed back at exit
        assert (_map_narrow_labels(r16.state.d)
                == np.asarray(r32.state.d)).all()

    def test_oracle_flow_on_narrow(self):
        from repro.kernels.ref import maxflow_oracle

        p, part = _small_problem()
        want, _ = maxflow_oracle(p)
        r = Solver(SolverOptions(dtype_policy="narrow")) \
            .prepare(p, part).solve()
        assert r.flow_value == want


# --------------------------------------------------------- compile cache

class TestCompileCacheKeys:
    def test_dtypes_in_meta_split_jit_keys(self):
        p, part = _small_problem()
        meta_w, _, _ = build(p, part)
        meta_n, _, _ = build(p, part, dtype_policy="narrow")
        assert meta_w != meta_n           # frozen metadata IS the jit key
        assert meta_w.kernel_dtypes != meta_n.kernel_dtypes

    def test_pack_built_separates_dtype_buckets(self):
        from repro.core.graph import pack_built

        p, part = _small_problem()
        builds = []
        for i, policy in enumerate(("int32", "narrow")):
            meta, state, layout = build(p, part, dtype_policy=policy)
            builds.append((i, meta, state, layout, state))
        packs = pack_built(builds)
        assert len(packs) == 2            # same dims, different dtypes


# --------------------------------------------------------------- CLI/API

class TestFrontEnds:
    def test_solve_mincut_unchanged_default(self):
        p, part = _small_problem()
        res = solve_mincut(p, part=part, config=SweepConfig())
        assert res.meta.kernel_dtypes == _dt.WIDE

    def test_options_roundtrip(self):
        o = SolverOptions(dtype_policy="auto", check=False)
        assert o.sweep_config() == SweepConfig()     # session knobs only
        o2 = dataclasses.replace(o, dtype_policy="int32")
        assert o2.check is False
