"""Distance labelings: region-relabel (Alg. 3) and gap heuristics (Alg. 4).

Two label semantics coexist in the paper and here:

* PRD labels lower-bound the *hop* distance ``d*`` to the sink
  (ceiling ``d_inf_prd = n``);
* ARD labels lower-bound the *region* distance ``d*B`` — the number of
  inter-region boundary crossings on a residual path to the sink
  (ceiling ``d_inf_ard = |B|``, paper Sec. 4.1).

Both region-relabel variants are one vectorized Bellman-Ford fixpoint over
the region's residual arcs: ARD propagates labels at zero cost through
intra-region arcs (Alg. 3 without the `d_current += 1` line), PRD at unit
cost.  Gap heuristics operate on label histograms — boundary-only bins for
ARD (sufficient per Sec. 5.3), all-vertex bins for PRD.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import dtypes as _dt
from repro.core.graph import (FlowState, GraphMeta, INF_LABEL, gather_at_nbr,
                              intra_mask)

_I32 = jnp.int32

# traces of the jitted global-relabel program (the warm-start label
# refresh) — part of the session front-end's combined cache observable
_TRACE_COUNT = 0


def trace_count() -> int:
    return _TRACE_COUNT

# static histogram cap for gap heuristics (labels above the cap are simply
# not gap-checked; the heuristic stays sound)
GAP_HIST_CAP = 4096


def gather_ghost_labels(state: FlowState) -> jax.Array:
    """i32[K,V,E] — label of every arc's destination vertex (global gather).

    In the distributed runtime this is the per-sweep boundary label exchange;
    under pjit it lowers to an all-gather of the (small) label array.
    """
    return gather_at_nbr(state.d, state.nbr_region, state.nbr_local)


def _region_relabel_one(cf, sink_cf, ghost_d, *, nbr_local, intra, emask,
                        vmask, d_inf, hop_cost: int):
    """Alg. 3 on one region network (vmapped over regions by the caller)."""
    V, E = cf.shape
    ldt = ghost_d.dtype
    inf = jnp.asarray(_dt.inf_label_for(ldt.name), ldt)
    d_inf = jnp.asarray(d_inf).astype(ldt)
    cross = emask & ~intra
    seed_ok = cross & (cf > 0) & (ghost_d < d_inf)
    base = jnp.where(seed_ok, ghost_d + 1, inf).min(axis=1)
    sink_lab = ldt.type(0) if hop_cost == 0 else ldt.type(1)
    base = jnp.where(sink_cf > 0, jnp.minimum(base, sink_lab), base)
    base = jnp.where(vmask, base, inf)

    def body(carry):
        lab, _ = carry
        nlab = jnp.where(intra & emask & (cf > 0), lab[nbr_local], inf)
        relaxed = jnp.minimum(base, nlab.min(axis=1) + hop_cost)
        relaxed = jnp.minimum(lab, jnp.where(vmask, relaxed, inf))
        return relaxed, (relaxed != lab).any()

    lab, _ = jax.lax.while_loop(lambda c: c[1], body, (base, jnp.asarray(True)))
    return jnp.minimum(lab, d_inf)


def region_relabel(meta: GraphMeta, state: FlowState, *, ard: bool) -> FlowState:
    """Recompute labels of every region from the boundary labels (Alg. 3).

    Returns labels ``max(d, relabel(d))`` — the max of two valid labelings is
    valid (paper Sec. 6.1), and monotony (d' >= d) is required by the sweep
    complexity proofs.
    """
    ghost_d = gather_ghost_labels(state)
    intra = intra_mask(state)
    d_inf = meta.d_inf_ard if ard else meta.d_inf_prd
    fn = jax.vmap(
        lambda cf, s, g, nl, it, em, vm: _region_relabel_one(
            cf, s, g, nbr_local=nl, intra=it, emask=em, vmask=vm,
            d_inf=d_inf, hop_cost=0 if ard else 1))
    new_d = fn(state.cf, state.sink_cf, ghost_d, state.nbr_local, intra,
               state.emask, state.vmask)
    return state.replace(d=jnp.maximum(state.d, new_d))


@partial(jax.jit, static_argnums=(0, 2))
def global_relabel(meta: GraphMeta, state: FlowState, ard: bool) -> FlowState:
    """Exact distance labeling of the whole residual network, from scratch.

    Iterates the region-relabel operator from the all-zero labeling to its
    least fixpoint — the exact region distance d*B (ARD) / hop distance d*
    (PRD) of every vertex in the *current* residual network, with
    unreachable vertices at ``d_inf``.  One outer iteration propagates
    labels one region hop, so the trip count is the region-graph diameter
    (devices: a handful of cheap relabel programs, no discharge engine
    runs).

    This is the warm-start label refresh: after ``graph.apply_update``
    adds residual capacity, previously-kept labels can overestimate true
    distances arbitrarily far upstream (unsound — trapped excess would
    never re-activate); exact recomputation is sound *unconditionally*
    (exact distances are valid labels by definition) and tight, so a warm
    re-solve starts from the best labeling the network admits.
    """
    global _TRACE_COUNT
    _TRACE_COUNT += 1

    def body(carry):
        st, _ = carry
        new = region_relabel(meta, st, ard=ard)
        return new, (new.d != st.d).any()

    st = state.replace(d=jnp.zeros_like(state.d))
    st, _ = jax.lax.while_loop(lambda c: c[1], body,
                               (st, jnp.asarray(True)))
    return st


def gap_new_labels(d, vmask, is_boundary, d_inf, *, cap: int, ard: bool):
    """Shared body of the global gap heuristic (Sec. 5.1).

    ``d_inf`` may be a python int (single-instance path) or a traced
    scalar (the batched driver's per-instance ceiling); ``cap`` is the
    static histogram capacity.  Any cap >= min(d_inf + 1, GAP_HIST_CAP)
    yields the same gap label: member labels are < d_inf so larger
    histograms only add empty bins beyond the scan range — which is what
    lets ``core.batch`` pin cap at ``GAP_HIST_CAP`` under vmap while
    staying bit-equal to this heuristic.
    """
    member = vmask & (d < d_inf)
    if ard:
        member = member & is_boundary
    vals = jnp.where(member, d, 0).reshape(-1)
    w = member.reshape(-1).astype(_I32)
    hist = jnp.zeros((cap,), _I32).at[jnp.clip(vals, 0, cap - 1)].add(w)
    idx = jnp.arange(cap)
    max_lab = jnp.max(jnp.where(member, d, 0))
    is_gap = (hist == 0) & (idx >= 1) & (idx <= jnp.minimum(max_lab, cap - 1))
    g = jnp.min(jnp.where(is_gap, idx, INF_LABEL))
    return jnp.where(vmask & (d > g) & (d < d_inf), d_inf, d).astype(d.dtype)


def global_gap(meta: GraphMeta, state: FlowState, *, ard: bool) -> FlowState:
    """Global gap heuristic (Sec. 5.1).

    If no vertex carries label g (0 < g < d_inf) then every vertex with a
    label above g cannot reach the sink and is raised to d_inf.  For ARD the
    histogram over *boundary* labels suffices (Sec. 5.3); PRD uses all
    vertices.
    """
    d_inf = meta.d_inf_ard if ard else meta.d_inf_prd
    cap = min(d_inf + 1, GAP_HIST_CAP)
    new_d = gap_new_labels(state.d, state.vmask, state.is_boundary, d_inf,
                           cap=cap, ard=ard)
    return state.replace(d=new_d)


def region_gap_prd(meta: GraphMeta, state: FlowState, region: jax.Array) -> FlowState:
    """Region gap heuristic for PRD (Alg. 4), applied to one region.

    If no vertex of R has label g, vertices of R with g < d(v) < d_next are
    raised to d_next + 1 where d_next is the smallest boundary label > g.
    """
    d_inf = meta.d_inf_prd
    cap = min(d_inf + 1, GAP_HIST_CAP)
    K, V = state.d.shape
    in_r = (jnp.arange(K)[:, None] == region) & state.vmask
    member = in_r & (state.d < d_inf)
    vals = jnp.where(member, state.d, 0).reshape(-1)
    w = member.reshape(-1).astype(_I32)
    hist = jnp.zeros((cap,), _I32).at[jnp.clip(vals, 0, cap - 1)].add(w)
    idx = jnp.arange(cap)
    max_lab = jnp.max(jnp.where(member, state.d, 0))
    is_gap = (hist == 0) & (idx >= 1) & (idx <= jnp.minimum(max_lab, cap - 1))
    g = jnp.min(jnp.where(is_gap, idx, INF_LABEL))
    # smallest boundary label above the gap (paper: d_next; d_inf if none)
    ghost_d = gather_ghost_labels(state)
    cross = state.emask & ~intra_mask(state)
    r_cross = cross & in_r[:, :, None]
    # heuristic bookkeeping runs int32 (outside the kernels); the result is
    # cast back to the state's label dtype, which d_inf fits by the range
    # check whenever labels are stored narrow
    bnd = jnp.where(r_cross & (ghost_d > g), ghost_d.astype(_I32), INF_LABEL)
    d_next = jnp.minimum(jnp.min(bnd), d_inf)
    raise_mask = member & (state.d > g) & (state.d < d_next)
    new_d = jnp.where(raise_mask,
                      jnp.minimum(d_next + 1, d_inf), state.d)
    return state.replace(d=new_d.astype(state.d.dtype))
