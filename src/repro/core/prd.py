"""Push-Relabel Region Discharge (PRD) — the Delong-Boykov baseline (Sec. 3).

Discharge of a region R applies Push and Relabel to vertices of R until no
active vertex remains, with the labels of the boundary B^R frozen.  Labels
live in the *hop-distance* space (ceiling d_inf = n), unlike ARD's region
distance.  The paper proves a tight O(n^2) sweep bound for this operator
(Theorems 1-2, Appendix A) — the experiments reproduce the asymptotic gap
versus ARD's 2|B|^2 + 1.

The region-internal solver is the same synchronous vectorized push-relabel
engine; for PRD it simply runs *directly on the global labels* (which is the
definition of PRD), pushing to lower-labelled intra vertices, to the sink,
and across boundary arcs to frozen-labelled ghosts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.ard import DischargeResult
from repro.core.engine import push_relabel, push_relabel_batched

_I32 = jnp.int32


def prd_discharge_one(cf, sink_cf, excess, d, ghost_d, *, nbr_local, rev_slot,
                      intra, emask, vmask, d_inf: int,
                      max_iters: int | None = None,
                      backend: str = "xla",
                      chunk_iters: int | None = None) -> DischargeResult:
    """PRD on a single region network (vmapped over regions by sweep.py)."""
    V, E = cf.shape
    cross = emask & ~intra
    es = push_relabel(
        cf, sink_cf, excess, d,
        nbr_local=nbr_local, rev_slot=rev_slot, intra=intra, emask=emask,
        vmask=vmask, cross_pushable=cross, cross_lab=ghost_d, d_inf=d_inf,
        sink_open=True, max_iters=max_iters, backend=backend,
        chunk_iters=chunk_iters)
    return DischargeResult(es.cf, es.sink_cf, es.excess, es.lab, es.out_push,
                           es.sink_pushed, es.iters,
                           jnp.ones((), _I32), es.launches)


def prd_discharge_batched(cf, sink_cf, excess, d, ghost_d, *, nbr_local,
                          rev_slot, intra, emask, vmask, d_inf,
                          max_iters: int | None = None,
                          backend: str = "xla",
                          chunk_iters: int | None = None
                          ) -> DischargeResult:
    """PRD on all K regions of a parallel sweep, collectively.

    Batched counterpart of ``jax.vmap(prd_discharge_one)``: PRD is a single
    engine run per region, so this is one ``engine.push_relabel_batched``
    call for the whole sweep.  Per-region results are bit-identical to
    the vmapped scalar path; ``engine_launches`` is the global dispatch
    count.  ``d_inf`` may be a scalar or per-region i32[K] (a solve batch's
    regions keep their own instance's label ceiling).
    """
    K, V, E = cf.shape
    cross = emask & ~intra
    d_inf = jnp.broadcast_to(jnp.asarray(d_inf, _I32), (K,))
    es = push_relabel_batched(
        cf, sink_cf, excess, d,
        nbr_local=nbr_local, rev_slot=rev_slot, intra=intra, emask=emask,
        vmask=vmask, cross_pushable=cross, cross_lab=ghost_d, d_inf=d_inf,
        sink_open=True, max_iters=max_iters, backend=backend,
        chunk_iters=chunk_iters)
    return DischargeResult(es.cf, es.sink_cf, es.excess, es.lab, es.out_push,
                           es.sink_pushed, es.iters,
                           jnp.ones((K,), _I32), es.launches)
