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

Region-resident fused kernel (interpret mode only)
--------------------------------------------------
``fused_engine_run_batched`` is one ``grid=(K,)`` (or ``grid=(B, K)`` for a
solve batch) ``pallas_call`` whose block is a *whole region*: each program
instance advances its region up to ``iter_limit`` complete engine
iterations — push split, intra-region apply, post-push relabel — with
the state resident and a per-region early exit.  ``fused_engine_run`` is
its single-region form.  Its body gathers ``lab[nbr]`` and the reverse
arcs' pushes and takes a cumsum inside the kernel, which Mosaic refuses
("Only 2D gather is supported" is the first refusal;
tests/test_tpu_compile.py lowers it).  It
therefore runs only under the Pallas interpreter, where it is bit-exact
against the other engine routes; on a TPU ``core.engine`` refuses the route
up front.  ``core.engine`` also falls back to the blocked path when a
region exceeds the VMEM budget (``fused_region_fits_vmem``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
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


# --------------------------------------------------------------------------
# Region-resident fused discharge: k full iterations per kernel launch.
# --------------------------------------------------------------------------

# VMEM working set of one fused iteration, per value family.  [V, E]
# arrays: cf, out_push, d_arc, pulled credits carry flow; nbr, rev_slot
# are int32 indices; intra, pushable are masks; cross_lab carries labels.
# caps/delta are flow-valued [V, 1+E].  [V] vectors: sink_cf, excess,
# avail carry flow; lab, new_lab carry labels; vmask is a mask; plus two
# int32 scalar/misc words per row.  The budget leaves headroom under the
# ~16 MiB/core of TPU VMEM for double buffering and the scalar plumbing.
FUSED_VMEM_BUDGET_BYTES = 12 * 2**20


def fused_region_vmem_bytes(V: int, E: int,
                            dtypes: _dt.KernelDtypes | None = None) -> int:
    """Estimated VMEM bytes of the region-resident fused kernel's state.

    Dtype-aware: each value family is costed at its own itemsize (the old
    formula hard-coded 4-byte words for everything, so it over-estimated
    the narrow configurations and would have kept them on the blocked
    path).  With all-int32 dtypes this is exactly the historical
    ``4 * (9*V*E + 2*V*(E+1) + 8*V)``.
    """
    kd = _dt.WIDE if dtypes is None else dtypes
    fb = np.dtype(kd.flow).itemsize
    lb = np.dtype(kd.label).itemsize
    mb = np.dtype(kd.mask).itemsize
    return (fb * (4 * V * E + 2 * V * (E + 1) + 3 * V)   # flow values
            + 4 * (2 * V * E + 2 * V)                    # int32 indices/misc
            + mb * (2 * V * E + V)                       # masks
            + lb * (V * E + 2 * V))                      # labels


def fused_region_fits_vmem(V: int, E: int,
                           budget_bytes: int | None = None,
                           dtypes: _dt.KernelDtypes | None = None) -> bool:
    budget = FUSED_VMEM_BUDGET_BYTES if budget_bytes is None else budget_bytes
    return fused_region_vmem_bytes(V, E, dtypes) <= budget


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
    """Build the pure fused-iteration function shared by both backends.

    ``iteration(cf, sink_cf, excess, lab) -> (cf, sink_cf, excess, new_lab,
    d_cross, d_sink_total, relabel_inc)`` performs push compute (labels
    frozen), the push application (``make_push_apply``: reverse arcs and
    receiver excess pulled through ``rev_slot``) and the post-push relabel
    in one function — the per-step unit of the region-resident kernel and of
    the fused XLA engine body.  Defining it once is what makes the two
    fused backends bit-exact by construction; ``kernels.ref.
    fused_iteration_ref`` stays the independent oracle.  ``intra``/
    ``pushable``/``vmask`` are bool, ``d_inf`` an i32 scalar.
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


def _fused_region_loop(lab, cf, sink_cf, excess, nbr, rev_slot, intra,
                       pushable, cross_lab, vmask, d_inf, limit, *,
                       sink_open: bool):
    """Up to ``limit`` fused engine iterations on one region's arrays.

    One iteration is bit-identical to one trip of the unfused engine loop
    (push compute -> push apply -> post-push relabel); the while_loop
    exits early once no vertex is active, so idle regions cost O(1).  This
    is the shared in-kernel body of the single-region (``grid=()``) and the
    grid-over-regions (``grid=(K,)``) fused kernels.
    """
    V, E = cf.shape
    vmask = vmask != 0
    d_inf = jnp.asarray(d_inf).astype(lab.dtype)
    iteration = make_fused_iteration(
        nbr=nbr, rev_slot=rev_slot, intra=intra != 0,
        pushable=pushable != 0, cross_lab=cross_lab,
        vmask=vmask, d_inf=d_inf, sink_open=sink_open)

    def body(carry):
        cf, sink_cf, excess, lab, out_push, sinkp, rls, it = carry
        cf, sink_cf, excess, lab, d_cross, d_sink, rinc = iteration(
            cf, sink_cf, excess, lab)
        return (cf, sink_cf, excess, lab, out_push + d_cross,
                sinkp + d_sink, rls + rinc, it + 1)

    def cond(carry):
        cf, sink_cf, excess, lab, out_push, sinkp, rls, it = carry
        return (it < limit) & ((excess > 0) & (lab < d_inf) & vmask).any()

    z = jnp.zeros((), jnp.int32)
    init = (cf, sink_cf, excess, lab, jnp.zeros((V, E), cf.dtype), z, z, z)
    return jax.lax.while_loop(cond, body, init)


def _fused_kernel_grid(lab_ref, cf_ref, sink_cf_ref, excess_ref, nbr_ref,
                       rev_ref, intra_ref, pushable_ref, cross_lab_ref,
                       vmask_ref, scal_ref, cf_out, sink_out, exc_out,
                       lab_out, push_out, sinkp_out, rls_out, it_out, *,
                       sink_open: bool, nlead: int):
    """Grid program instance: region ``pl.program_id(0)`` (``grid=(K,)``)
    or region (``pl.program_id(0)``, ``pl.program_id(1)``) of a solve batch
    (``grid=(B, K)``).

    Every ref carries ``nlead`` leading block dimensions of 1 (one region's
    tile); per-vertex ``[V]`` values ride as ``[1, V]`` rows and scalars as
    ``[1, 1]`` so each block's last two dimensions are whole array
    dimensions, as the TPU tiling requires.  ``scal_ref`` is this region's
    (d_inf, iter_limit) row.  The in-kernel early exit makes an idle or
    already-converged region cost O(1), so one launch can mix hot and idle
    regions — and converged and running instances — freely.
    """
    z = (0,) * nlead
    row = z + (0,)
    scal = scal_ref[row]
    cf, sink_cf, excess, lab, out_push, sinkp, rls, it = _fused_region_loop(
        lab_ref[row], cf_ref[z], sink_cf_ref[row], excess_ref[row],
        nbr_ref[z], rev_ref[z], intra_ref[z], pushable_ref[z],
        cross_lab_ref[z], vmask_ref[row], scal[0], scal[1],
        sink_open=sink_open)
    cf_out[z] = cf
    sink_out[row] = sink_cf
    exc_out[row] = excess
    lab_out[row] = lab
    push_out[z] = out_push
    for ref, val in ((sinkp_out, sinkp), (rls_out, rls), (it_out, it)):
        ref[...] = jnp.full(ref.shape, val, ref.dtype)


@functools.partial(jax.jit, static_argnames=("sink_open", "interpret"))
def fused_engine_run(lab, cf, sink_cf, excess, nbr, rev_slot, intra, pushable,
                     cross_lab, vmask, d_inf, iter_limit, *,
                     sink_open: bool = True, interpret: bool = True):
    """Run up to ``iter_limit`` fused engine iterations in one kernel launch.

    Region-resident mode: ``block_v = V`` (the caller guarantees
    ``fused_region_fits_vmem``).  Masks are int32 (0/1) for portable Pallas
    lowering; ``iter_limit`` is dynamic so the driver can clamp the last
    chunk to a ``max_iters`` cap.  The single-region convenience form of
    ``fused_engine_run_batched`` (K = 1 grid, same kernel body).  Returns
    the post-chunk region state plus this launch's accumulators:
    ``(cf, sink_cf, excess, lab, out_push, sink_pushed, relabel_sum, iters)``.
    """
    one = lambda a: a[None]
    outs = fused_engine_run_batched(
        one(lab), one(cf), one(sink_cf), one(excess), one(nbr),
        one(rev_slot), one(intra), one(pushable), one(cross_lab), one(vmask),
        d_inf, jnp.reshape(jnp.asarray(iter_limit, jnp.int32), (1,)),
        sink_open=sink_open, interpret=interpret)
    return tuple(o[0] for o in outs)


@functools.partial(jax.jit, static_argnames=("sink_open", "interpret"))
def fused_engine_run_batched(lab, cf, sink_cf, excess, nbr, rev_slot, intra,
                             pushable, cross_lab, vmask, d_inf, iter_limit, *,
                             sink_open: bool = True, interpret: bool = True):
    """All regions of a sweep — or of a solve batch — in ONE kernel launch.

    The grid-over-regions variant of ``fused_engine_run``: with
    ``[K, V, E]`` inputs the program is ``grid=(K,)`` and instance k owns
    region k's ``[V, E]`` tile; with ``[B, K, V, E]`` inputs it is
    ``grid=(B, K)`` and instance (b, k) owns region k of solve-batch
    instance b.  Each advances its tile up to ``iter_limit[...]`` complete
    fused engine iterations with per-region in-kernel early exit — an idle
    region (or every region of a converged instance) costs O(1).
    ``d_inf`` and ``iter_limit`` broadcast against the lead shape, so each
    batch instance keeps its own label ceiling and iteration budget (the
    driver's per-instance convergence flag is a zeroed budget).
    Per-region results are bit-identical to separate ``fused_engine_run``
    calls; what changes is the dispatch count: one launch instead of K
    (resp. B*K).

    Per-vertex values ride as ``[*lead, 1, V]`` rows and scalars as
    ``[*lead, 1, 1]`` so each block's last two dimensions are whole array
    dimensions, as the TPU tiling requires; Mosaic then reaches the body
    and refuses it (see the module docstring).

    Returns ``(cf, sink_cf, excess, lab, out_push, sink_pushed [lead],
    relabel_sum [lead], iters [lead])`` where ``lead`` = ``(K,)`` or
    ``(B, K)``.
    """
    lead = cf.shape[:-2]
    V, E = cf.shape[-2:]
    nlead = len(lead)
    assert nlead in (1, 2), cf.shape
    scal = jnp.stack(
        [jnp.broadcast_to(jnp.asarray(d_inf, jnp.int32), lead),
         jnp.broadcast_to(jnp.asarray(iter_limit, jnp.int32), lead)],
        axis=-1)                                           # [*lead, 2]
    blk = lambda *tail: pl.BlockSpec(
        (1,) * nlead + tail, lambda *ids: ids + (0,) * len(tail))
    vec, scl = blk(1, V), blk(1, 1)
    mat = blk(V, E)
    row = lambda a: a.reshape(lead + (1, V))
    lab, sink_cf, excess, vmask = map(row, (lab, sink_cf, excess, vmask))
    outs = pl.pallas_call(
        functools.partial(_fused_kernel_grid, sink_open=sink_open,
                          nlead=nlead),
        grid=lead,
        in_specs=[vec, mat, vec, vec, mat, mat, mat, mat, mat, vec,
                  blk(1, 2)],
        out_specs=[mat, vec, vec, vec, mat, scl, scl, scl],
        out_shape=[
            jax.ShapeDtypeStruct(lead + (V, E), cf.dtype),       # cf
            jax.ShapeDtypeStruct(lead + (1, V), sink_cf.dtype),  # sink_cf
            jax.ShapeDtypeStruct(lead + (1, V), excess.dtype),   # excess
            jax.ShapeDtypeStruct(lead + (1, V), lab.dtype),      # lab
            jax.ShapeDtypeStruct(lead + (V, E), cf.dtype),       # out_push
            jax.ShapeDtypeStruct(lead + (1, 1), jnp.int32),   # sink_pushed
            jax.ShapeDtypeStruct(lead + (1, 1), jnp.int32),   # relabel_sum
            jax.ShapeDtypeStruct(lead + (1, 1), jnp.int32),   # iters
        ],
        interpret=interpret,
    )(lab, cf, sink_cf, excess, nbr, rev_slot, intra, pushable, cross_lab,
      vmask, scal.reshape(lead + (1, 2)))
    cf, sink_cf, excess, lab, out_push, sinkp, rls, it = outs
    vecs = (a.reshape(lead + (V,)) for a in (sink_cf, excess, lab))
    return (cf, *vecs, out_push, *(a.reshape(lead) for a in (sinkp, rls, it)))


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
