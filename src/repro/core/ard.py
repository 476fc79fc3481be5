"""Augmented-path Region Discharge (ARD) — the paper's contribution (Sec. 4).

Discharge of a region R:

  stage 0   — augment excess to the sink t inside the region network G^R;
  stage k>0 — augment excess to T_k = {t} ∪ {w in B^R : d(w) < k}, i.e. to
              boundary (ghost) vertices in order of increasing label;
  finally   — region-relabel (Alg. 3, ARD variant) recomputes the region's
              labels w.r.t. the *region distance* d^B from the frozen
              boundary labels.

Each stage is a maxflow from the excess vertices to the stage target set; we
compute it with the vectorized push-relabel engine seeded by exact BFS
distances to the targets (engine.py) — the TPU-native analogue of the BK
search trees used by the paper's implementation.  Stages iterate over the
*distinct* ghost labels actually present (the efficient implementation of
Sec. 6), and the partial-discharge heuristic (Sec. 6.2) caps the admissible
stage by the sweep number.

The returned pair (f', d') satisfies Statement 9 — optimality (no active
vertex left in R), label monotony, validity, and flow direction — which is
what the 2|B|^2 + 1 sweep bound needs; tests/test_invariants.py checks these
properties directly.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.engine import (bfs_to_targets, push_relabel,
                               push_relabel_batched)
from repro.core.graph import INF_LABEL
from repro.core.labels import _region_relabel_one

_I32 = jnp.int32


class DischargeResult(NamedTuple):
    cf: jax.Array          # i32[V,E]
    sink_cf: jax.Array     # i32[V]
    excess: jax.Array      # i32[V]
    d: jax.Array           # i32[V]   new labels d' of the region's vertices
    out_push: jax.Array    # i32[V,E] flow pushed over cross arcs
    sink_pushed: jax.Array  # i32[]
    engine_iters: jax.Array  # i32[]
    stages: jax.Array      # i32[]
    engine_launches: jax.Array  # i32[] compute-program dispatches (see engine)


def _distinct_sorted_ghost_labels(ghost_d, cross, emask, d_inf):
    """Leading distinct ghost labels (< d_inf) in ascending order, then INF.

    Prepends -1 so that index 0 is always the sink-only stage (T_0 = {t}).

    Stage scheduling is int32 regardless of the label storage dtype — the
    schedule is tiny and only compared against, never stored back."""
    flat = jnp.where(cross & emask & (ghost_d < d_inf),
                     ghost_d.astype(_I32), INF_LABEL).reshape(-1)
    s = jnp.sort(flat)
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    distinct = jnp.sort(jnp.where(first, s, INF_LABEL))
    return jnp.concatenate([jnp.full((1,), -1, _I32), distinct])


def ard_discharge_one(cf, sink_cf, excess, ghost_d, *, nbr_local, rev_slot,
                      intra, emask, vmask, d_inf: int, stage_cap,
                      max_iters: int | None = None,
                      backend: str = "xla",
                      chunk_iters: int | None = None) -> DischargeResult:
    """ARD on a single region network (vmapped over regions by sweep.py).

    ``ghost_d``  — frozen labels of cross-arc destinations (paper: d|B^R).
    ``stage_cap`` — largest ghost label admissible as an augmentation target
                    this sweep (partial discharges, Sec. 6.2); pass d_inf for
                    a full discharge.
    ``backend``  — engine compute-phase backend ("xla" or "pallas").
    ``chunk_iters`` — fused chunked engine (k iterations per launch); None
                    keeps the unfused two-phase engine.
    """
    V, E = cf.shape
    cross = emask & ~intra
    linf_local = V + 2
    stage_vals = _distinct_sorted_ghost_labels(ghost_d, cross, emask, d_inf)
    n_vals = stage_vals.shape[0]
    stage_cap = jnp.asarray(stage_cap, _I32)

    def stage_body(carry):
        i, cf, sink_cf, excess, out_push, sink_pushed, iters, launches = carry
        lvl = stage_vals[i]
        target_cross = cross & (ghost_d <= lvl) & (ghost_d < d_inf)
        lab0 = bfs_to_targets(
            cf, sink_cf, nbr_local=nbr_local, intra=intra, emask=emask,
            vmask=vmask, target_cross=target_cross, linf=linf_local,
            label_dtype=ghost_d.dtype)
        es = push_relabel(
            cf, sink_cf, excess, lab0,
            nbr_local=nbr_local, rev_slot=rev_slot, intra=intra, emask=emask,
            vmask=vmask, cross_pushable=target_cross,
            cross_lab=jnp.zeros_like(ghost_d), d_inf=linf_local,
            sink_open=True, max_iters=max_iters, backend=backend,
            chunk_iters=chunk_iters)
        return (i + 1, es.cf, es.sink_cf, es.excess,
                out_push + es.out_push, sink_pushed + es.sink_pushed,
                iters + es.iters, launches + es.launches)

    def stage_cond(carry):
        i = carry[0]
        more = i < n_vals
        lvl = stage_vals[jnp.minimum(i, n_vals - 1)]
        return more & (lvl < INF_LABEL) & (lvl <= stage_cap)

    init = (jnp.zeros((), _I32), cf, sink_cf, excess,
            jnp.zeros((V, E), cf.dtype), jnp.zeros((), _I32),
            jnp.zeros((), _I32), jnp.zeros((), _I32))
    (i, cf, sink_cf, excess, out_push, sink_pushed, iters,
     launches) = jax.lax.while_loop(stage_cond, stage_body, init)

    # final region-relabel (Alg. 3, ARD variant) on the post-discharge network
    d_new = _region_relabel_one(
        cf, sink_cf, ghost_d, nbr_local=nbr_local, intra=intra, emask=emask,
        vmask=vmask, d_inf=d_inf, hop_cost=0)
    return DischargeResult(cf, sink_cf, excess, d_new, out_push,
                           sink_pushed, iters, i, launches)


def ard_discharge_batched(cf, sink_cf, excess, ghost_d, *, nbr_local,
                          rev_slot, intra, emask, vmask, d_inf,
                          stage_cap, max_iters: int | None = None,
                          backend: str = "xla",
                          chunk_iters: int | None = None,
                          linf=None) -> DischargeResult:
    """ARD on all K regions of a parallel sweep, collectively.

    The batched counterpart of ``jax.vmap(ard_discharge_one)``: the stage
    loop advances every region in lockstep (a region whose stage schedule
    is exhausted is frozen by a per-region select, exactly like vmapped
    while_loop batching), and each stage's engine run goes through
    ``engine.push_relabel_batched``, one engine run for every region.
    Per-region results (state, labels, out_push, engine iterations, stage
    counts) are bit-identical to the vmapped scalar path;
    ``engine_launches`` becomes the global dispatch count of the sweep.

    ``d_inf``/``stage_cap`` may be scalars or per-region i32[K] vectors and
    ``linf`` overrides the per-region engine/BFS ceiling (default: the
    padded row count ``V + 2``) — a solve batch's regions carry their own
    instance's ceilings, which keeps every region's iteration sequence
    identical to the instance's standalone solve regardless of bucket
    padding.
    """
    K, V, E = cf.shape
    cross = emask & ~intra
    d_inf = jnp.broadcast_to(jnp.asarray(d_inf, _I32), (K,))
    linf = jnp.broadcast_to(
        jnp.asarray(V + 2 if linf is None else linf, _I32), (K,))
    stage_cap = jnp.broadcast_to(jnp.asarray(stage_cap, _I32), (K,))
    stage_vals = jax.vmap(_distinct_sorted_ghost_labels)(
        ghost_d, cross, emask, d_inf)                        # [K, n_vals]
    n_vals = stage_vals.shape[1]

    bfs_batched = jax.vmap(
        lambda cf, s, nl, it, em, vm, tc, li: bfs_to_targets(
            cf, s, nbr_local=nl, intra=it, emask=em, vmask=vm,
            target_cross=tc, linf=li))

    def stage_more(i):
        lvl = jnp.take_along_axis(
            stage_vals, jnp.minimum(i, n_vals - 1)[:, None], axis=1)[:, 0]
        more = (i < n_vals) & (lvl < INF_LABEL) & (lvl <= stage_cap)
        return lvl, more

    def stage_body(carry):
        i, cf, sink_cf, excess, out_push, sink_pushed, iters, launches = carry
        lvl, more = stage_more(i)                            # [K], [K]
        target_cross = cross & (ghost_d <= lvl[:, None, None]) \
            & (ghost_d < d_inf[:, None, None])
        lab0 = bfs_batched(cf, sink_cf, nbr_local, intra, emask, vmask,
                           target_cross, linf)
        lab0 = lab0.astype(ghost_d.dtype)
        es = push_relabel_batched(
            cf, sink_cf, excess, lab0,
            nbr_local=nbr_local, rev_slot=rev_slot, intra=intra, emask=emask,
            vmask=vmask, cross_pushable=target_cross,
            cross_lab=jnp.zeros_like(ghost_d), d_inf=linf,
            sink_open=True, max_iters=max_iters, backend=backend,
            chunk_iters=chunk_iters)
        w3, w2 = more[:, None, None], more[:, None]
        return (i + more.astype(_I32),
                jnp.where(w3, es.cf, cf),
                jnp.where(w2, es.sink_cf, sink_cf),
                jnp.where(w2, es.excess, excess),
                out_push + jnp.where(w3, es.out_push, 0),
                sink_pushed + jnp.where(more, es.sink_pushed, 0),
                iters + jnp.where(more, es.iters, 0),
                launches + es.launches)

    def stage_cond(carry):
        _, more = stage_more(carry[0])
        return more.any()

    zk = jnp.zeros((K,), _I32)
    init = (zk, cf, sink_cf, excess, jnp.zeros((K, V, E), cf.dtype), zk, zk,
            jnp.zeros((), _I32))
    (i, cf, sink_cf, excess, out_push, sink_pushed, iters,
     launches) = jax.lax.while_loop(stage_cond, stage_body, init)

    d_new = jax.vmap(
        lambda cf, s, g, nl, it, em, vm, di: _region_relabel_one(
            cf, s, g, nbr_local=nl, intra=it, emask=em, vmask=vm,
            d_inf=di, hop_cost=0))(
        cf, sink_cf, ghost_d, nbr_local, intra, emask, vmask, d_inf)
    return DischargeResult(cf, sink_cf, excess, d_new, out_push,
                           sink_pushed, iters, i, launches)
