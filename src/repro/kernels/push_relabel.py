"""Pallas TPU kernels: the push/relabel compute phase on an ELL block.

The hot spot of every region discharge is the per-vertex row scan over the
padded adjacency: test admissibility against the neighbour labels, split
the vertex's excess over admissible arcs (exclusive prefix sum), and
compute the relabel minimum.

Blocked two-phase kernel (``push_relabel_phase``)
-------------------------------------------------
The grid tiles the vertex dimension: each program instance loads a
``(BV, E)`` tile of residuals and of neighbour labels plus ``(BV, 1)``
columns of the per-vertex values (BV = 256 rows by default).  The
neighbour-label gather ``lab[nbr]`` and the application of the deltas
(``make_push_apply``: reverse arcs and receiver excess, pulled through
``rev_slot``) stay in XLA: Mosaic lowers no 1-D gather.  The excess split
is an exclusive prefix unrolled over the E static lanes (Mosaic has no
cumsum), and narrow int16
storage computes in int32 inside the kernel (Mosaic reduces no int16
vectors).  This is the kernel of the ``engine_backend="pallas"`` route; it
compiles for TPU v5e (tests/test_tpu_compile.py) and is bit-exact against
``kernels/ref.py`` and the XLA phase (tests/test_kernels.py).

``engine_phase`` is the engine-facing adapter that accepts core/engine.py's
mask semantics (``cross_pushable``/``emask``/``vmask``/``sink_open``); the
``backend="pallas"`` path of ``repro.core.engine.push_relabel`` calls it
twice per iteration (pre-push for the deltas, post-push for the relabels).

Shared engine bodies
--------------------
``make_push_apply`` is the one formulation of the push application (every
engine route applies through it), and ``make_fused_iteration`` is the whole
iteration — push split, apply, post-push relabel — as one traced function:
the body of the fused XLA engine (``engine_chunk_iters`` on
``backend="xla"``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import dtypes as _dt

INF_LABEL = 2**30
DEFAULT_BLOCK_V = 256


def _inf_for(dtype) -> int:
    """Label-infinity sentinel for the label dtype in play: 2**30 for
    int32, 2**14 for int16 (``repro.core.dtypes``).  Every real label is
    strictly below either sentinel, so comparisons/min/max order
    identically — the narrow path stays bit-exact."""
    return _dt.inf_label_for(dtype)


def _pr_kernel(lab_ref, cf_ref, sink_cf_ref, excess_ref, nlab_ref, d_inf_ref,
               *out_refs, mode: str):
    """One vertex-block: push deltas and/or relabel candidates.

    Per-vertex values arrive as ``[BV, 1]`` columns beside the ``[BV, E]``
    arc tiles, and ``nlab`` holds every arc's destination label, already
    gathered and gated by the caller (Mosaic lowers no 1-D gather).
    ``mode`` ("both" | "push" | "relabel") statically selects the outputs:
    ``(d_sink [BV, 1], d_arc [BV, E])`` for a push, ``new_lab [BV, 1]``
    for a relabel — pallas_call is opaque to XLA DCE, so the kernel
    computes and writes only what the engine consumes.
    """
    # narrow (int16) storage computes in int32: Mosaic reduces no int16
    # vectors, and the build-time range check keeps every value and
    # partial sum inside the narrow range, so the widened math is exact
    wide = lambda ref: ref[...].astype(jnp.int32)
    lab = wide(lab_ref)                          # [BV, 1]
    cf = wide(cf_ref)                            # [BV, E]
    sink_cf = wide(sink_cf_ref)                  # [BV, 1]
    excess = wide(excess_ref)                    # [BV, 1]
    nlab = wide(nlab_ref)                        # [BV, E]
    d_inf = d_inf_ref[...]                       # [1, 1]
    inf = _inf_for(lab_ref.dtype)

    act = (excess > 0) & (lab < d_inf)
    adm = (cf > 0) & (lab == nlab + 1) & act
    sink_adm = (sink_cf > 0) & (lab == 1) & act

    if mode in ("both", "push"):
        d_sink_ref, d_arc_ref = out_refs[:2]
        sink_cap = jnp.where(sink_adm, sink_cf, 0)
        arc_cap = jnp.where(adm, cf, 0)
        avail = jnp.where(act, excess, 0)
        # exclusive prefix over (sink, arc 0 .. arc E-1), unrolled over the
        # E static lanes: Mosaic has no cumsum.  Integer sums wrap the same
        # in any order, so this equals the XLA phase's cumsum bit for bit.
        col = jax.lax.broadcasted_iota(jnp.int32, cf.shape, 1)
        cum_excl = jnp.broadcast_to(sink_cap, cf.shape)
        for j in range(cf.shape[1] - 1):
            cum_excl = cum_excl + jnp.where(col > j, arc_cap[:, j:j + 1], 0)
        d_sink_ref[...] = jnp.clip(avail, 0, sink_cap).astype(
            d_sink_ref.dtype)
        d_arc_ref[...] = jnp.clip(avail - cum_excl, 0, arc_cap).astype(
            d_arc_ref.dtype)

    if mode in ("both", "relabel"):
        new_lab_ref = out_refs[-1]
        any_adm = jnp.max(adm.astype(jnp.int32), axis=1, keepdims=True) > 0
        no_adm = act & ~any_adm & ~sink_adm
        cand = jnp.min(jnp.where(cf > 0, nlab + 1, inf), axis=1,
                       keepdims=True)
        cand = jnp.where(sink_cf > 0, jnp.minimum(cand, 1), cand)
        new_lab_ref[...] = jnp.where(
            no_adm, jnp.maximum(jnp.minimum(cand, d_inf), lab),
            lab).astype(new_lab_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_v", "interpret", "mode"))
def push_relabel_phase(lab, cf, sink_cf, excess, nbr, intra, pushable,
                       cross_lab, d_inf, *, block_v: int = DEFAULT_BLOCK_V,
                       interpret: bool = True, mode: str = "both"):
    """Pallas-tiled push/relabel compute phase.

    Returns (delta [V, 1+E] with the sink in column 0, new_lab [V]).
    Masks are 0/1 integers or bools; value dtypes follow the inputs.
    ``mode`` statically prunes the unused output's compute ("push": zero
    new_lab changes, "relabel": zero deltas); "both" computes everything.
    The neighbour-label gather runs here in XLA; the kernel sees the
    gathered ``[V, E]`` tile.
    """
    assert mode in ("both", "push", "relabel"), mode
    V, E = cf.shape
    inf = _inf_for(lab.dtype)
    nlab = jnp.where(intra != 0, lab[nbr], cross_lab)
    nlab = jnp.where(pushable != 0, nlab, inf)
    bv = min(block_v, V)
    pad = -V % bv                    # pad rows to a whole number of tiles
    rows = lambda a, fill=0: jnp.pad(
        a, ((0, pad),) + ((0, 0),) * (a.ndim - 1), constant_values=fill)
    column = lambda a, fill=0: rows(a, fill).reshape(V + pad, 1)
    args = (column(lab, inf), rows(cf), column(sink_cf), column(excess),
            rows(nlab, inf), jnp.reshape(jnp.asarray(d_inf, jnp.int32),
                                          (1, 1)))
    tile = lambda w: pl.BlockSpec((bv, w), lambda i: (i, 0))
    out_specs, out_shape = [], []
    if mode in ("both", "push"):
        out_specs += [tile(1), tile(E)]
        out_shape += [jax.ShapeDtypeStruct((V + pad, 1), cf.dtype),
                      jax.ShapeDtypeStruct((V + pad, E), cf.dtype)]
    if mode in ("both", "relabel"):
        out_specs.append(tile(1))
        out_shape.append(jax.ShapeDtypeStruct((V + pad, 1), lab.dtype))
    outs = pl.pallas_call(
        functools.partial(_pr_kernel, mode=mode),
        grid=((V + pad) // bv,),
        in_specs=[tile(1), tile(E), tile(1), tile(1), tile(E),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    if mode == "relabel":
        delta = jnp.zeros((V, 1 + E), cf.dtype)
    else:
        delta = jnp.concatenate([outs[0], outs[1]], axis=1)[:V]
    new_lab = lab if mode == "push" else outs[-1][:V, 0]
    return delta, new_lab


def make_push_apply(*, nbr, rev_slot, intra):
    """Build the apply step of one engine iteration (everything but the sink).

    ``apply(cf, excess, d_arc) -> (cf, excess, d_cross)`` takes every arc's
    push ``d_arc [V, E]`` off its own residual and its vertex's excess, and
    credits each intra-region push to the reverse arc and the receiving
    vertex.  The credit is a *pull*: arc ``(v, e)`` gathers what its reverse
    ``(nbr[v, e], rev_slot[v, e])`` pushed, and a row sum gives the vertex's
    receipts.  That is one gather where a push-side apply takes two
    scatter-adds (reverse arcs, receiver excess), and the same integers in
    another order, so every sum is exact.  Cross-region pushes come back
    as ``d_cross`` for the sweep driver.

    Contract (every layout ``graph.build``, ``graph.pack_built``,
    ``stream.build_stream`` and ``data.dimacs.read_dimacs_sharded``
    emit): on intra arcs ``rev_slot`` is an involution — the reverse of
    ``(v, e)`` is ``(nbr[v, e], rev_slot[v, e])``, whose reverse is
    ``(v, e)`` — and ``intra`` is symmetric under it.  A padding slot
    (``intra`` false) credits 0.  The unfused engine body and
    ``make_fused_iteration`` both apply through this, so the repo has one
    formulation of the push application; ``kernels.ref.fused_iteration_ref``
    keeps the scatter form as the oracle.
    """
    V, E = nbr.shape
    src = nbr * E + rev_slot          # flat slot of each arc's reverse

    def apply(cf, excess, d_arc):
        back = jnp.where(intra, d_arc.reshape(V * E)[src], 0)
        cf = cf - d_arc + back
        # row sums stay in the storage dtype: a vertex's pushes are bounded
        # by its excess and its receipts by the capacity mass, which the
        # narrow range check covers
        excess = (excess - jnp.sum(d_arc, axis=1, dtype=d_arc.dtype)
                  + jnp.sum(back, axis=1, dtype=d_arc.dtype))
        return cf, excess, jnp.where(intra, 0, d_arc)

    return apply


def make_fused_iteration(*, nbr, rev_slot, intra, pushable, cross_lab, vmask,
                         d_inf, sink_open: bool):
    """Build the pure fused-iteration function of the fused XLA engine.

    ``iteration(cf, sink_cf, excess, lab) -> (cf, sink_cf, excess, new_lab,
    d_cross, d_sink_total, relabel_inc)`` performs push compute (labels
    frozen), the push application (``make_push_apply``: reverse arcs and
    receiver excess pulled through ``rev_slot``) and the post-push relabel
    in one function — one compute program per iteration where the unfused
    engine dispatches two phase calls.  ``kernels.ref.fused_iteration_ref``
    stays the independent oracle.  ``intra``/``pushable``/``vmask`` are
    bool, ``d_inf`` an i32 scalar.
    """
    apply = make_push_apply(nbr=nbr, rev_slot=rev_slot, intra=intra)
    inf = _inf_for(cross_lab.dtype)

    def iteration(cf, sink_cf, excess, lab):
        # label ceiling arrives int32 (scalar plumbing); every real label
        # fits the narrow dtype by the build-time range check
        dinf = jnp.asarray(d_inf).astype(lab.dtype)
        # ---- push compute (labels frozen) ----
        act = (excess > 0) & (lab < dinf) & vmask
        nlab = jnp.where(intra, lab[nbr], cross_lab)
        nlab = jnp.where(pushable, nlab, inf)
        adm = (cf > 0) & (lab[:, None] == nlab + 1) & act[:, None]
        sink = sink_cf if sink_open else jnp.zeros_like(sink_cf)
        sink_adm = (sink > 0) & (lab == 1) & act
        sink_cap = jnp.where(sink_adm, sink, 0)
        arc_cap = jnp.where(adm, cf, 0)
        caps = jnp.concatenate([sink_cap[:, None], arc_cap], axis=1)
        avail = jnp.where(act, excess, 0)
        cum_excl = jnp.cumsum(caps, axis=1, dtype=caps.dtype) - caps
        delta = jnp.clip(avail[:, None] - cum_excl, 0, caps)
        d_sink = delta[:, 0]
        d_arc = delta[:, 1:]
        # ---- push application (intra credits pulled from reverse arcs) ----
        cf, excess, d_cross = apply(cf, excess - d_sink, d_arc)
        sink_cf = sink_cf - d_sink
        # ---- relabel (on the post-push residual graph) ----
        act2 = (excess > 0) & (lab < dinf) & vmask
        adm2 = (cf > 0) & (lab[:, None] == nlab + 1) & act2[:, None]
        sink2 = sink_cf if sink_open else jnp.zeros_like(sink_cf)
        sink_adm2 = (sink2 > 0) & (lab == 1) & act2
        no_adm = act2 & ~adm2.any(axis=1) & ~sink_adm2
        cand = jnp.where(cf > 0, nlab + 1, inf).min(axis=1)
        cand = jnp.where(sink2 > 0, jnp.minimum(cand, 1), cand)
        new_lab = jnp.where(
            no_adm, jnp.maximum(jnp.minimum(cand, dinf), lab), lab)
        # accumulators cross iterations and regions: always int32
        relabel_inc = jnp.sum(jnp.where(vmask, new_lab - lab, 0),
                              dtype=jnp.int32)
        return (cf, sink_cf, excess, new_lab, d_cross,
                jnp.sum(d_sink, dtype=jnp.int32), relabel_inc)

    return iteration


def engine_phase(lab, cf, sink_cf, excess, *, nbr_local, intra, emask, vmask,
                 cross_pushable, cross_lab, d_inf, sink_open: bool = True,
                 block_v: int = DEFAULT_BLOCK_V, interpret: bool = True,
                 mode: str = "both"):
    """Engine-semantics adapter over ``push_relabel_phase``.

    Folds the engine's masks into the kernel's inputs: arcs are pushable iff
    intra or cross-enabled (and real, per ``emask``); vertices outside
    ``vmask`` are made inactive by zeroing their excess; a closed sink is a
    zero sink capacity.  Returns (delta [V, 1+E] with sink column 0, new_lab
    [V]) — exactly what one compute phase of ``core.engine.push_relabel``
    consumes.  ``mode`` prunes the output the caller discards ("push" /
    "relabel" / "both").
    """
    pushable = (cross_pushable | intra) & emask
    excess = jnp.where(vmask, excess, 0)
    sink = sink_cf if sink_open else jnp.zeros_like(sink_cf)
    return push_relabel_phase(lab, cf, sink, excess, nbr_local,
                              intra, pushable, cross_lab,
                              d_inf, block_v=block_v, interpret=interpret,
                              mode=mode)
