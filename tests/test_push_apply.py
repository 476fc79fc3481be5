"""The engine's push application pulls each intra-region push from the
reverse arc (``kernels.push_relabel.make_push_apply``).  That holds only on
layouts whose ``rev_slot`` pairs intra arcs: these tests check the pairing
on every layout the program builds, and the pull against the scatter-form
oracle (``kernels.ref.fused_iteration_ref``) iteration by iteration."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import SweepConfig, build, grid_partition
from repro.core.engine import push_relabel_batched
from repro.core.graph import intra_mask, pack_built
from repro.core.partition import bfs_partition
from repro.data.dimacs import read_dimacs_sharded, write_dimacs
from repro.data.grids import (random_sparse, segmentation_seeds_volume,
                              synthetic_grid)
from repro.kernels.ref import fused_iteration_ref
from repro.stream import build_stream


def _assert_pairs_intra_arcs(nbr_region, nbr_local, rev_slot, emask,
                             region_ids=None):
    """On intra arcs ``rev_slot`` is an involution and ``intra`` is
    symmetric under it: the reverse of ``(k, v, e)`` is ``(k, nbr_local,
    rev_slot)``, itself intra, whose reverse is ``(k, v, e)``."""
    nbr_region, nbr_local, rev_slot, emask = map(
        np.asarray, (nbr_region, nbr_local, rev_slot, emask))
    K, V, E = nbr_local.shape
    own = np.arange(K) if region_ids is None else np.asarray(region_ids)
    intra = (nbr_region == own[:, None, None]) & emask
    k, v, e = np.nonzero(intra)
    assert len(k), "layout has no intra arc"
    n, r = nbr_local[k, v, e], rev_slot[k, v, e]
    assert ((n >= 0) & (n < V) & (r >= 0) & (r < E)).all()
    assert intra[k, n, r].all()
    np.testing.assert_array_equal(nbr_local[k, n, r], v)
    np.testing.assert_array_equal(rev_slot[k, n, r], e)


def _built(problem, part):
    _, state, _ = build(problem, part)
    return [(state.nbr_region, state.nbr_local, state.rev_slot,
             state.emask)]


def _grid(connectivity):
    p = synthetic_grid(12, 12, connectivity=connectivity, seed=3)
    return _built(p, grid_partition((12, 12), (2, 2)))


def _volume(neighbours):
    p = segmentation_seeds_volume((6, 6, 6), neighbours=neighbours, seed=3)
    return _built(p, grid_partition((6, 6, 6), (2, 2, 2)))


def _random_sparse():
    p = random_sparse(60, 180, seed=3)
    return _built(p, bfs_partition(60, p.edges, 4))


def _fleet_bucket():
    builds = []
    for i, side in enumerate((14, 15, 16)):
        p = synthetic_grid(side, side, connectivity=8, seed=i)
        meta, state, layout = build(p, grid_partition((side, side), (4, 4)))
        builds.append((i, meta, state, layout, state))
    out = []
    for batch in pack_built(builds):
        st = batch.state
        out += [(st.nbr_region[b], st.nbr_local[b], st.rev_slot[b],
                 st.emask[b]) for b in range(len(batch.indices))]
    return out


def _pool_rows(ss):
    """Each region's rows of a streaming pool alone, as the streaming
    executor discharges them."""
    try:
        rows = [ss.store.load(r)[0] for r in range(ss.meta.num_regions)]
    finally:
        ss.store.close()
    return [tuple(row[f][None] for f in ("nbr_region", "nbr_local",
                                         "rev_slot", "emask")) + (r,)
            for r, row in enumerate(rows)]


def _stream_rows():
    p = synthetic_grid(10, 12, connectivity=8, seed=3)
    return _pool_rows(build_stream(
        p, grid_partition((10, 12), (2, 2)),
        SweepConfig(method="ard", parallel=False), prefetch=False))


def _dimacs_shard_rows():
    sd = read_dimacs_sharded(write_dimacs(random_sparse(40, 120, seed=3)), 4)
    try:
        return _pool_rows(sd.to_stream(
            SweepConfig(method="ard", parallel=False), prefetch=False))
    finally:
        sd.close()


LAYOUTS = {
    "grid-4c": lambda: _grid(4),
    "grid-8c": lambda: _grid(8),
    "volume-6n": lambda: _volume("faces"),
    "volume-26n": lambda: _volume("all"),
    "random-sparse": _random_sparse,
    "pack-built-fleet": _fleet_bucket,
    "stream-rows": _stream_rows,
    "dimacs-shard-rows": _dimacs_shard_rows,
}


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_layout_pairs_intra_arcs(layout):
    """Every layout the program builds emits the pairing the pull reads."""
    views = LAYOUTS[layout]()
    assert views
    for view in views:
        nbr_region, nbr_local, rev_slot, emask = view[:4]
        region_ids = [view[4]] if len(view) > 4 else None
        _assert_pairs_intra_arcs(nbr_region, nbr_local, rev_slot, emask,
                                 region_ids)


def _apply_case(family, dtype_policy):
    if family == "grid-8c":
        p = synthetic_grid(10, 10, connectivity=8, strength=20,
                           excess_mag=50, seed=1)
        part = grid_partition((10, 10), (2, 2))
    else:
        p = segmentation_seeds_volume((6, 6, 6), neighbours="all",
                                      smoothness=3, seed_strength=30, seed=1)
        part = grid_partition((6, 6, 6), (2, 2, 2))
    meta, state, _ = build(p, part, dtype_policy=dtype_policy)
    return meta, state


@pytest.mark.parametrize("route", ["unfused", "fused-xla"])
@pytest.mark.parametrize("dtype_policy", ["int32", "narrow"])
@pytest.mark.parametrize("family", ["grid-8c", "volume-26n"])
def test_push_apply_matches_scatter_ref(family, dtype_policy, route):
    """Iteration by iteration on built regions, the engine (pull apply)
    equals the scatter-form oracle on every state field, at int32 and
    int16 storage.  The oracle runs on the state widened to int32."""
    meta, state = _apply_case(family, dtype_policy)
    want_dt = jnp.int16 if dtype_policy == "narrow" else jnp.int32
    assert state.cf.dtype == want_dt
    intra = intra_mask(state)
    topo = dict(nbr_local=state.nbr_local, rev_slot=state.rev_slot,
                intra=intra, emask=state.emask, vmask=state.vmask,
                cross_pushable=state.emask & ~intra,
                cross_lab=jnp.zeros(state.cf.shape, state.d.dtype))
    d_inf = meta.d_inf_prd
    chunk = 1 if route == "fused-xla" else None

    step = jax.jit(lambda cf, s, e, lab: push_relabel_batched(
        cf, s, e, lab, d_inf=d_inf, max_iters=1, backend="xla",
        chunk_iters=chunk, **topo))
    w = lambda a: jnp.asarray(a, jnp.int32)
    ref = jax.jit(jax.vmap(lambda cf, s, e, lab, nbr, rev, it, em, vm, cl,
                           cp: fused_iteration_ref(
        w(cf), w(s), w(e), w(lab), nbr, rev, it, em, vm, w(cl), cp, d_inf)))

    cf, sink_cf, excess = state.cf, state.sink_cf, state.excess
    lab = jnp.zeros_like(state.d)
    moved = 0
    for it in range(30):
        es = step(cf, sink_cf, excess, lab)
        want = ref(cf, sink_cf, excess, lab, topo["nbr_local"],
                   topo["rev_slot"], intra, topo["emask"], topo["vmask"],
                   topo["cross_lab"], topo["cross_pushable"])
        got = (es.cf, es.sink_cf, es.excess, es.lab, es.out_push,
               es.sink_pushed, es.relabel_sum)
        names = ("cf", "sink_cf", "excess", "lab", "out_push",
                 "sink_pushed", "relabel_sum")
        for name, x, y in zip(names, got, want):
            np.testing.assert_array_equal(
                np.asarray(x).astype(np.int64), np.asarray(y),
                err_msg=f"iteration {it} field {name}")
        assert es.cf.dtype == want_dt and es.excess.dtype == want_dt
        moved += int(np.abs(np.asarray(es.cf, np.int64)
                            - np.asarray(cf, np.int64))[np.asarray(intra)]
                     .sum())
        cf, sink_cf, excess, lab = es.cf, es.sink_cf, es.excess, es.lab
    assert moved > 0, "no intra-region push in the run"
