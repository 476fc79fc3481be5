"""Region reduction (Alg. 5, Sec. 8) — single-flow improvement of Kovtun's
auxiliary problems.

Kovtun's construction solves two auxiliary problems on the region network:
aux1 adds infinite links boundary -> sink (strong *source* detection), aux2
adds infinite links source -> boundary (strong *sink* detection).  Alg. 5
computes both with a single flow, exploiting that after Augment(s, t) the
s-reachable and t-reaching parts of the region are disjoint (Statement 11).

Key equivalence used here: because the added links are infinite, every aux
min cut places all boundary vertices on the auxiliary-terminal side, so each
aux network is *exactly* equivalent to the subnetwork induced by R alone
with cross-arc capacities folded into terminal capacities:

    aux1:  extra sink capacity  at u:  sum_w  c_f(u, w)   (residual out-arcs)
    aux2:  extra source mass    at u:  sum_w  c_f(w, u)   (residual in-arcs)

(Transit paths u -> w -> u' through a boundary vertex never help: flow
arriving at w can always exit into w's infinite terminal link instead.)
This removes any need to model ghost-hop paths on device; all reachability
and augmentation is strictly intra-region and therefore runs for every
region simultaneously on the [K, V, E] arrays.

The steps, matching Alg. 5 with the folding above:

  1. Augment(s, t)        — excess -> t-links inside the region;
  2. Augment(s, B^S)      — remaining excess -> residual out-arc exits
                            (maxflow only uses s-reachable exits = B^S);
  3. Augment(B^T, t)      — virtual excess = residual in-arc capacity,
                            pushed to t (only the t-reaching part moves
                            = B^T); leftover virtual excess is discarded;
  4. classify:  s -> v           => strong source  (v in C for every opt cut)
                v -> t           => strong sink    (v in C̄ for every opt cut)
                else v -/-> B^R  => weak source
                else B^R -/-> v  => weak sink

"Decided" = strong sink | weak source (paper Table 3).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.engine import bfs_to_targets, push_relabel
from repro.core.graph import FlowState, GraphMeta, gather_at_nbr, intra_mask

_I32 = jnp.int32


class ReductionResult(NamedTuple):
    strong_source: jax.Array   # bool[K,V]
    strong_sink: jax.Array     # bool[K,V]
    weak_source: jax.Array     # bool[K,V]
    weak_sink: jax.Array       # bool[K,V]
    decided: jax.Array         # bool[K,V]  strong sink | weak source


def _reach_forward(state: FlowState, seed: jax.Array, intra) -> jax.Array:
    """Vertices reachable from ``seed`` through intra residual arcs."""
    K, V, E = state.cf.shape

    def body(carry):
        reach, _ = carry
        hop = (state.cf > 0) & state.emask & intra & reach[:, :, None]
        rf = reach.reshape(-1).at[
            (state.nbr_region * V + state.nbr_local).reshape(-1)].max(
            hop.reshape(-1))
        new = (rf.reshape(K, V) | reach) & state.vmask
        return new, (new != reach).any()

    reach, _ = jax.lax.while_loop(lambda c: c[1], body,
                                  (seed & state.vmask, jnp.asarray(True)))
    return reach


def _reach_backward(state: FlowState, target: jax.Array, intra) -> jax.Array:
    """Vertices from which ``target`` is reachable through intra residuals."""
    def body(carry):
        reach, _ = carry
        nbr_reach = gather_at_nbr(reach, state.nbr_region, state.nbr_local)
        ok = (state.cf > 0) & state.emask & intra & nbr_reach
        new = (reach | ok.any(axis=2)) & state.vmask
        return new, (new != reach).any()

    reach, _ = jax.lax.while_loop(lambda c: c[1], body,
                                  (target & state.vmask, jnp.asarray(True)))
    return reach


def _augment_all(meta: GraphMeta, state: FlowState, *, target_cross,
                 sink_open: bool, excess=None,
                 backend: str = "xla") -> FlowState:
    """Maxflow from excess to {sink?} ∪ cross-arc exits, in every region."""
    intra = intra_mask(state)
    V = meta.region_size
    exc = state.excess if excess is None else excess
    linf = V + 2

    def one(cf, sink_cf, e, tc, nl, rs, it, em, vm):
        lab0 = bfs_to_targets(cf, sink_cf, nbr_local=nl, intra=it, emask=em,
                              vmask=vm, target_cross=tc, linf=linf,
                              sink_open=sink_open)
        es = push_relabel(cf, sink_cf, e, lab0, nbr_local=nl, rev_slot=rs,
                          intra=it, emask=em, vmask=vm, cross_pushable=tc,
                          cross_lab=jnp.zeros_like(cf), d_inf=linf,
                          sink_open=sink_open, backend=backend)
        return es.cf, es.sink_cf, es.excess, es.sink_pushed

    cf, sink_cf, exc, sink_pushed = jax.vmap(one)(
        state.cf, state.sink_cf, exc, target_cross, state.nbr_local,
        state.rev_slot, intra, state.emask, state.vmask)
    return state.replace(cf=cf, sink_cf=sink_cf, excess=exc,
                         flow_to_t=state.flow_to_t + sink_pushed.sum())


def region_reduction(meta: GraphMeta, state: FlowState, *,
                     backend: str = "xla") -> ReductionResult:
    """Kovtun's two auxiliary maxflows (folded form) for all regions.

    ``backend`` selects the discharge engine's compute-phase implementation
    ("xla" or "pallas"), like ``SweepConfig.engine_backend`` for the sweeps.

    Faithfulness note (DESIGN.md): Alg. 5 computes both aux problems with a
    *single* flow per region by exploiting the disjointness of the
    s-reachable and t-reaching parts (Statement 11).  That sharing requires
    per-region reverse-arc bookkeeping on the cross arcs; in this
    all-regions-simultaneously layout neighbouring regions would corrupt
    each other's in-arc capacities (found by hypothesis testing), so the
    sound formulation here runs the two phases on separate scratch copies —
    Kovtun's original two flows, each still a single vectorized pass over
    every region at once.
    """
    K, V, E = state.cf.shape
    intra = intra_mask(state)
    cross = state.emask & ~intra
    src, dst = state.cross_src, state.cross_dst
    no_targets = jnp.zeros((K, V, E), bool)

    # ---- phase A (aux1: boundary -> sink flooded out) ----
    # step 1: Augment(s, t); step 2: Augment(s, B^S) — every residual
    # out-arc is an exit of capacity c_f(u, w); maxflow reaches exactly the
    # s-reachable exits = B^S.
    stA = _augment_all(meta, state, target_cross=no_targets, sink_open=True,
                       backend=backend)
    stA = _augment_all(meta, stA, target_cross=cross, sink_open=False,
                       backend=backend)

    # ---- phase B (aux2: source -> boundary flooded in) ----
    # fresh copy; sources = original excess + original in-arc capacities
    # injected as virtual excess at the entry vertices.
    arc_cf0 = state.cf[src[:, 0], src[:, 1], src[:, 2]]
    virt = jnp.zeros((K * V,), _I32).at[dst[:, 0] * V + dst[:, 1]].add(
        jnp.where(state.cross_valid, jnp.maximum(arc_cf0, 0), 0)
    ).reshape(K, V)
    stB = _augment_all(meta, state, target_cross=no_targets, sink_open=True,
                       excess=state.excess + virt, backend=backend)

    # ---- classification ----
    strong_source = _reach_forward(stA, stA.excess > 0, intra)
    strong_sink = _reach_backward(stB, stB.sink_cf > 0, intra)
    out_any = ((stA.cf > 0) & cross).any(axis=2)
    to_boundary = _reach_backward(stA, out_any, intra)
    in_any = jnp.zeros((K * V,), bool).at[dst[:, 0] * V + dst[:, 1]].max(
        (arc_cf0 > 0) & state.cross_valid).reshape(K, V)
    from_boundary = _reach_forward(stB, in_any, intra)
    rest = state.vmask & ~strong_source & ~strong_sink
    weak_source = rest & ~to_boundary
    weak_sink = rest & ~from_boundary
    decided = (strong_sink | weak_source) & state.vmask
    return ReductionResult(strong_source & state.vmask,
                           strong_sink & state.vmask,
                           weak_source, weak_sink, decided)
