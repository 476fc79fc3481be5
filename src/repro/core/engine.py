"""Synchronous vectorized push-relabel engine (region-local).

This is the TPU-native replacement for the paper's region-internal solvers
(BK search trees for ARD, HPR buckets for PRD).  All per-vertex work is a
dense row operation over the padded ELL adjacency, so one engine iteration is
a handful of vector ops — the shape the VPU/MXU wants.  The scheme alternates
two *pure* phases, which keeps the labeling valid under full synchrony:

  push phase    — every active vertex pushes through its admissible arcs
                  (labels frozen); pairwise push conflicts are impossible
                  because d(u) = d(v)+1 and d(v) = d(u)+1 cannot both hold;
  relabel phase — every vertex that is still active *and* has no admissible
                  arc on the post-push residual graph relabels to
                  1 + min(neighbour labels).  Relabels see the arcs created
                  by this iteration's pushes, so validity is preserved.

The per-row multi-arc push uses an exclusive-cumsum split of the vertex's
excess over its admissible arcs (sink column first), i.e. a vertex performs
*all* its saturating pushes plus at most one non-saturating push per
iteration, like a whole Discharge step of [Goldberg-Tarjan 88] at once.

Used by prd.py (global labels, paper Sec. 3) and by each ARD stage
(BFS-initialised local labels toward the stage target set, Sec. 4.2).

Backends
--------
The per-iteration *compute phase* (admissibility, excess split, relabel
minimum — everything except the scatter application of the deltas) is a pure
function from the current state to ``(delta [V, 1+E], new_lab [V])``, and is
selectable:

  "xla"    — dense-row jnp ops (``_phase_xla``), the original engine code;
  "pallas" — the blocked VMEM-tiled kernel ``repro.kernels.push_relabel``
             (compiled by Mosaic on a TPU, interpreted elsewhere), sharing
             the exact integer math of the XLA phase, so the two backends
             are bit-identical.

Each iteration calls the phase twice: once on the pre-push state (the delta
output drives the push) and once on the post-push state (the new_lab output
is the relabel — relabels must see the arcs created by this iteration's
pushes).  Scatter application of the deltas (reverse arcs, receiver excess)
stays in XLA in both backends, as the kernel docstring prescribes.

Fused chunked mode (``chunk_iters``)
------------------------------------
With ``chunk_iters=k`` the engine switches to the *region-resident fused*
driver: the outer ``lax.while_loop`` body advances up to ``k`` complete
iterations per trip instead of one.  For ``backend="pallas"`` one trip is a
single ``fused_engine_run`` kernel launch with the whole region state in
VMEM (push split, intra-region scatter and post-push relabel all in-kernel,
early exit when no vertex is active); when the region exceeds the VMEM
budget (``kernels.push_relabel.fused_region_fits_vmem``) the engine falls
back to the blocked two-phase path.  Mosaic refuses that kernel's body, so
it runs only interpreted: on a TPU the fused pallas route raises
``UnsupportedFeatureError`` when it is traced.  For ``backend="xla"`` one
trip is the symmetric single traced body (the shared
``kernels.push_relabel.make_fused_iteration`` inside an inner bounded
loop) — one compute+apply+relabel program per iteration instead of two
phase calls.  All four paths (fused/unfused × xla/pallas) are bit-exact;
``EngineState.launches`` counts compute-program dispatches per engine run
(2 per iteration unfused; fused: 1 per chunk on pallas — a real kernel
launch — and 1 per iteration on xla, which fuses the two phase calls but
keeps per-iteration program structure) for the benchmark's
launch-reduction accounting.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import dtypes as _dt
from repro.core.executor import UnsupportedFeatureError
from repro.kernels import push_relabel as _pr_kernel

_I32 = jnp.int32


def _mask_dtype(cf, lab):
    """Kernel mask staging dtype: int8 whenever either value family is
    stored narrow (the KernelDtypes policy), int32 otherwise."""
    return jnp.int8 if (cf.dtype.itemsize < 4 or lab.dtype.itemsize < 4) \
        else jnp.int32


def _kernel_dtypes(cf, lab) -> _dt.KernelDtypes:
    """Reconstruct the KernelDtypes policy in force from live arrays (for
    the dtype-aware VMEM budget check)."""
    mask = "int8" if (cf.dtype.itemsize < 4 or lab.dtype.itemsize < 4) \
        else "int32"
    return _dt.KernelDtypes(label=lab.dtype.name, flow=cf.dtype.name,
                            mask=mask)

ENGINE_BACKENDS = ("xla", "pallas")


class EngineState(NamedTuple):
    cf: jax.Array          # i32[V,E]
    sink_cf: jax.Array     # i32[V]
    excess: jax.Array      # i32[V]
    lab: jax.Array         # i32[V]
    out_push: jax.Array    # i32[V,E]  flow pushed over cross arcs (not yet applied remotely)
    sink_pushed: jax.Array  # i32[]    flow absorbed by the sink this run
    iters: jax.Array       # i32[]
    relabel_sum: jax.Array  # i32[]    total label increase (for complexity accounting)
    launches: jax.Array    # i32[]    compute-program dispatches: 2/iter unfused,
    #                                 1/chunk fused-pallas, 1/iter fused-xla


def _phase_xla(lab, cf, sink_cf, excess, *, nbr_local, intra, pushable,
               cross_lab, d_inf):
    """One push/relabel compute phase in dense XLA row ops.

    Same contract as the Pallas kernel (``kernels.push_relabel``): inputs are
    pre-gated (``pushable`` already folds cross/emask; inactive vertices have
    zero excess; a closed sink is zero ``sink_cf``), output is the push delta
    split (sink in column 0) plus the relabel target of every active vertex
    with no admissible arc.  Mirrors ``kernels.ref.push_relabel_iteration_ref``.
    """
    inf = jnp.asarray(_dt.inf_label_for(lab.dtype.name), lab.dtype)
    d_inf = jnp.asarray(d_inf).astype(lab.dtype)
    act = (excess > 0) & (lab < d_inf)
    nlab = jnp.where(intra, lab[nbr_local], cross_lab)
    nlab = jnp.where(pushable, nlab, inf)
    adm = (cf > 0) & (lab[:, None] == nlab + 1) & act[:, None]
    sink_adm = (sink_cf > 0) & (lab == 1) & act
    sink_cap = jnp.where(sink_adm, sink_cf, 0)
    arc_cap = jnp.where(adm, cf, 0)
    caps = jnp.concatenate([sink_cap[:, None], arc_cap], axis=1)   # [V,1+E]
    avail = jnp.where(act, excess, 0)
    cum_excl = jnp.cumsum(caps, axis=1, dtype=caps.dtype) - caps
    delta = jnp.clip(avail[:, None] - cum_excl, 0, caps)           # [V,1+E]
    no_adm = act & ~adm.any(axis=1) & ~sink_adm
    cand = jnp.where(cf > 0, nlab + 1, inf).min(axis=1)
    cand = jnp.where(sink_cf > 0, jnp.minimum(cand, 1), cand)
    new_lab = jnp.where(no_adm,
                        jnp.maximum(jnp.minimum(cand, d_inf), lab), lab)
    return delta, new_lab


def make_phase(backend: str, *, nbr_local, intra, emask, vmask,
               cross_pushable, cross_lab, d_inf, sink_open: bool = True,
               block_v: int | None = None, interpret: bool | None = None):
    """Build the compute-phase closure for ``backend``.

    The returned ``phase(lab, cf, sink_cf, excess, mode="both") -> (delta,
    new_lab)`` applies the engine's gating (cross/emask arc gate, vmask
    excess gate, sink_open) and dispatches to the XLA rows or the Pallas
    kernel.  Both backends receive identical gated inputs and implement
    identical int32 math, so their outputs are bit-equal.  ``mode`` ("push" /
    "relabel") statically prunes the output the caller discards — XLA DCEs
    that itself, but a pallas_call is opaque to DCE, so the kernel takes the
    hint explicitly.
    """
    if backend not in ENGINE_BACKENDS:
        raise ValueError(f"unknown engine backend {backend!r}; "
                         f"expected one of {ENGINE_BACKENDS}")
    d_inf = jnp.asarray(d_inf, _I32)

    if backend == "pallas":
        # interpret mode everywhere but real TPUs (CPU containers, tests)
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        if block_v is None:
            block_v = _pr_kernel.DEFAULT_BLOCK_V

        def phase(lab, cf, sink_cf, excess, mode="both"):
            return _pr_kernel.engine_phase(
                lab, cf, sink_cf, excess, nbr_local=nbr_local, intra=intra,
                emask=emask, vmask=vmask, cross_pushable=cross_pushable,
                cross_lab=cross_lab, d_inf=d_inf, sink_open=sink_open,
                block_v=block_v, interpret=interpret, mode=mode)
        return phase

    pushable = (cross_pushable | intra) & emask

    def phase(lab, cf, sink_cf, excess, mode="both"):
        excess = jnp.where(vmask, excess, 0)
        sink = sink_cf if sink_open else jnp.zeros_like(sink_cf)
        return _phase_xla(lab, cf, sink, excess, nbr_local=nbr_local,
                          intra=intra, pushable=pushable,
                          cross_lab=cross_lab, d_inf=d_inf)
    return phase


def _push_relabel_fused(cf, sink_cf, excess, lab, *, nbr_local, rev_slot,
                        intra, emask, vmask, cross_pushable, cross_lab, d_inf,
                        sink_open, max_iters, backend, chunk_iters,
                        interpret) -> EngineState:
    """Chunked fused driver on a single region: one launch advances up to
    ``chunk_iters`` complete iterations, early-exiting as soon as no vertex
    is active.  Thin K = 1 wrapper over ``_push_relabel_fused_batched`` so
    the chunk-clamping / early-exit / launch-accounting logic exists once;
    the accounting is identical at K = 1 (pallas: 1 per trip; xla: 1 per
    advanced iteration).
    """
    one = lambda a: a[None]
    es = _push_relabel_fused_batched(
        one(cf), one(sink_cf), one(excess), one(lab),
        nbr_local=one(nbr_local), rev_slot=one(rev_slot), intra=one(intra),
        emask=one(emask), vmask=one(vmask),
        cross_pushable=one(cross_pushable), cross_lab=one(cross_lab),
        d_inf=d_inf, sink_open=sink_open, max_iters=max_iters,
        backend=backend, chunk_iters=chunk_iters, interpret=interpret)
    return EngineState(es.cf[0], es.sink_cf[0], es.excess[0], es.lab[0],
                       es.out_push[0], es.sink_pushed[0], es.iters[0],
                       es.relabel_sum[0], es.launches)


def push_relabel(
    cf: jax.Array,
    sink_cf: jax.Array,
    excess: jax.Array,
    lab: jax.Array,
    *,
    nbr_local: jax.Array,
    rev_slot: jax.Array,
    intra: jax.Array,
    emask: jax.Array,
    vmask: jax.Array,
    cross_pushable: jax.Array,   # bool[V,E] cross arcs usable in this run
    cross_lab: jax.Array,        # i32[V,E]  frozen label of cross destinations
    d_inf,                       # label ceiling (python int or i32 scalar)
    sink_open: bool = True,
    max_iters: int | None = None,
    backend: str = "xla",
    block_v: int | None = None,
    interpret: bool | None = None,
    chunk_iters: int | None = None,
    vmem_budget_bytes: int | None = None,
) -> EngineState:
    """Run push/relabel until no active vertex remains.

    Returns the final engine state; ``out_push`` holds the flow sent over
    cross-region arcs, to be fused/applied by the sweep driver.  ``backend``
    selects the compute-phase implementation ("xla" dense rows or the fused
    "pallas" kernel); ``chunk_iters=k`` selects the fused chunked driver
    (one launch per k iterations, region state resident); all combinations
    produce bit-identical states.  A Pallas region that exceeds the VMEM
    budget falls back to the blocked two-phase path.
    """
    V, E = cf.shape
    d_inf = jnp.asarray(d_inf, _I32)
    if chunk_iters is not None and backend == "pallas" \
            and not _pr_kernel.fused_region_fits_vmem(
                V, E, vmem_budget_bytes, dtypes=_kernel_dtypes(cf, lab)):
        chunk_iters = None       # region too big to sit in VMEM: blocked path
    if chunk_iters is not None:
        return _push_relabel_fused(
            cf, sink_cf, excess, lab, nbr_local=nbr_local, rev_slot=rev_slot,
            intra=intra, emask=emask, vmask=vmask,
            cross_pushable=cross_pushable, cross_lab=cross_lab, d_inf=d_inf,
            sink_open=sink_open, max_iters=max_iters, backend=backend,
            chunk_iters=chunk_iters, interpret=interpret)
    flat_n = V * E
    zero_e = jnp.zeros((V, E), cf.dtype)
    phase = make_phase(backend, nbr_local=nbr_local, intra=intra, emask=emask,
                       vmask=vmask, cross_pushable=cross_pushable,
                       cross_lab=cross_lab, d_inf=d_inf, sink_open=sink_open,
                       block_v=block_v, interpret=interpret)

    def active_mask(s: EngineState):
        return (s.excess > 0) & (s.lab < d_inf) & vmask

    def body(s: EngineState) -> EngineState:
        # ---- push phase (compute on the pre-push state) ----
        delta, _ = phase(s.lab, s.cf, s.sink_cf, s.excess, mode="push")
        d_sink = delta[:, 0]
        d_arc = delta[:, 1:]
        # row sums stay in the storage dtype (bounded by the vertex's
        # excess, which the narrow range check already covers); an implicit
        # int32 promotion here would silently widen the while-loop carry
        pushed = d_sink + jnp.sum(d_arc, axis=1, dtype=d_arc.dtype)

        # ---- scatter application (always XLA: global, cross-tile) ----
        excess = s.excess - pushed
        sink_cf = s.sink_cf - d_sink
        cf = s.cf - d_arc
        # intra reverse arcs + receiver excess
        d_intra = jnp.where(intra, d_arc, 0)
        flat_idx = (nbr_local * E + rev_slot).reshape(flat_n)
        cf = (cf.reshape(flat_n).at[flat_idx]
              .add(d_intra.reshape(flat_n), mode="drop").reshape(V, E))
        recv = jnp.zeros((V,), cf.dtype).at[nbr_local.reshape(flat_n)].add(
            d_intra.reshape(flat_n), mode="drop")
        excess = excess + recv
        # cross arcs: flow leaves the region (applied later by the driver)
        d_cross = d_arc - d_intra
        out_push = s.out_push + d_cross

        s2 = EngineState(cf, sink_cf, excess, s.lab, out_push,
                         s.sink_pushed + jnp.sum(d_sink, dtype=_I32),
                         s.iters + 1, s.relabel_sum, s.launches + 2)
        # ---- relabel phase (on the post-push residual graph) ----
        _, new_lab = phase(s2.lab, s2.cf, s2.sink_cf, s2.excess,
                           mode="relabel")
        relabel_sum = s2.relabel_sum + jnp.sum(
            jnp.where(vmask, new_lab - s2.lab, 0), dtype=_I32)
        return s2._replace(lab=new_lab, relabel_sum=relabel_sum)

    def cond(s: EngineState):
        ok = active_mask(s).any()
        if max_iters is not None:
            ok = ok & (s.iters < max_iters)
        return ok

    init = EngineState(cf, sink_cf, excess, lab, zero_e,
                       jnp.zeros((), _I32), jnp.zeros((), _I32),
                       jnp.zeros((), _I32), jnp.zeros((), _I32))
    return jax.lax.while_loop(cond, body, init)


def _push_relabel_fused_batched(cf, sink_cf, excess, lab, *, nbr_local,
                                rev_slot, intra, emask, vmask, cross_pushable,
                                cross_lab, d_inf, sink_open, max_iters,
                                backend, chunk_iters, interpret,
                                grid2d: tuple[int, int] | None = None
                                ) -> EngineState:
    """Fused chunked driver over ALL regions at once (grid-over-regions).

    One outer trip advances every still-running region by up to
    ``chunk_iters`` iterations: on ``backend="pallas"`` the trip is a single
    ``fused_engine_run_batched`` launch (``grid=(K,)``, per-region in-kernel
    early exit); on ``backend="xla"`` it is one traced batched body with
    per-region run masking.  Each region's iteration sequence is exactly the
    scalar driver's (a region advances iff it has an active vertex and
    budget left), so per-region states and iteration counts are
    bit-identical to ``jax.vmap`` of the scalar path.  ``launches`` is the
    *global* dispatch count: 1 per trip on pallas (the kernel covers every
    region), one traced body per advanced region-iteration on xla —
    mirroring the scalar fused accounting summed over regions.

    ``d_inf`` may be a scalar or a per-region i32[K] vector (a solve
    batch's regions carry their instance's ceiling).  ``grid2d=(B, Kr)``
    with ``K == B*Kr`` reshapes the pallas launch to the ``grid=(B, Kr)``
    kernel form — same launch count, but the grid names the instance axis.
    """
    K, V, E = cf.shape
    chunk = int(chunk_iters)
    assert chunk >= 1
    d_inf = jnp.broadcast_to(jnp.asarray(d_inf, _I32), (K,))
    pushable = (cross_pushable | intra) & emask
    zero_e = jnp.zeros((K, V, E), cf.dtype)
    zero_k = jnp.zeros((K,), _I32)

    def region_active(excess, lab):
        return ((excess > 0) & (lab < d_inf[:, None]) & vmask).any(axis=1)

    if backend == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        if not interpret:
            # Mosaic refuses the fused body (in-kernel gather, cumsum and
            # scatter-add; tests/test_tpu_compile.py): refuse the route
            # here, typed, rather than let the kernel's lowering error
            # reach the degradation ladder
            raise UnsupportedFeatureError(
                "TPU", "fused_pallas",
                "Mosaic cannot lower its in-kernel gather, cumsum and "
                "scatter-add; use engine_backend='xla' with "
                "engine_chunk_iters, or engine_backend='pallas' without it")
        md = _mask_dtype(cf, lab)
        intra_i = intra.astype(md)
        pushable_i = pushable.astype(md)
        vmask_i = vmask.astype(md)
        lead = (K,) if grid2d is None else tuple(grid2d)
        assert math.prod(lead) == K, (lead, K)
        rs = lambda a: a.reshape(lead + a.shape[1:])

        def launch(lab, cf, sink_cf, excess, limit):
            out = _pr_kernel.fused_engine_run_batched(
                rs(lab), rs(cf), rs(sink_cf), rs(excess), rs(nbr_local),
                rs(rev_slot), rs(intra_i), rs(pushable_i), rs(cross_lab),
                rs(vmask_i), rs(d_inf), rs(limit),
                sink_open=sink_open, interpret=interpret)
            return tuple(o.reshape((K,) + o.shape[len(lead):]) for o in out)
    else:
        # the same pure fused iteration, vmapped over the region axis; a
        # per-region run mask freezes regions that are idle or out of
        # budget, exactly like vmap-of-while_loop batching does
        def one_region(cf, sink_cf, excess, lab, nbr, rev, it_m, pu_m, cl,
                       vm, di):
            step = _pr_kernel.make_fused_iteration(
                nbr=nbr, rev_slot=rev, intra=it_m, pushable=pu_m,
                cross_lab=cl, vmask=vm, d_inf=di, sink_open=sink_open)
            return step(cf, sink_cf, excess, lab)

        batched_iteration = jax.vmap(one_region)

        def launch(lab, cf, sink_cf, excess, limit):
            def icond(c):
                cf, sink_cf, excess, lab, op, sp, rs, it = c
                return ((it < limit) & region_active(excess, lab)).any()

            def ibody(c):
                cf, sink_cf, excess, lab, op, sp, rs, it = c
                run = (it < limit) & region_active(excess, lab)      # [K]
                ncf, nsink, nexc, nlab, d_cross, d_sink, rinc = \
                    batched_iteration(cf, sink_cf, excess, lab, nbr_local,
                                      rev_slot, intra, pushable, cross_lab,
                                      vmask, d_inf)
                w3, w2 = run[:, None, None], run[:, None]
                cf = jnp.where(w3, ncf, cf)
                sink_cf = jnp.where(w2, nsink, sink_cf)
                excess = jnp.where(w2, nexc, excess)
                lab = jnp.where(w2, nlab, lab)
                op = op + jnp.where(w3, d_cross, 0)
                sp = sp + jnp.where(run, d_sink, 0)
                rs = rs + jnp.where(run, rinc, 0)
                return (cf, sink_cf, excess, lab, op, sp, rs,
                        it + run.astype(_I32))

            init = (cf, sink_cf, excess, lab, zero_e, zero_k, zero_k, zero_k)
            return jax.lax.while_loop(icond, ibody, init)

    def cond(s: EngineState):
        run = region_active(s.excess, s.lab)
        if max_iters is not None:
            run = run & (s.iters < max_iters)
        return run.any()

    def body(s: EngineState) -> EngineState:
        limit = jnp.full((K,), chunk, _I32)
        if max_iters is not None:
            limit = jnp.minimum(limit, jnp.asarray(max_iters, _I32) - s.iters)
        cf, sink_cf, excess, lab, dpush, dsink, drls, dit = launch(
            s.lab, s.cf, s.sink_cf, s.excess, limit)
        # one real kernel launch covers every region on pallas; the fused
        # XLA body is one compute program per advanced region-iteration
        # (the scalar fused-xla accounting, summed over regions)
        dln = jnp.ones((), _I32) if backend == "pallas" else dit.sum()
        return EngineState(cf, sink_cf, excess, lab, s.out_push + dpush,
                           s.sink_pushed + dsink, s.iters + dit,
                           s.relabel_sum + drls, s.launches + dln)

    init = EngineState(cf, sink_cf, excess, lab, zero_e, zero_k, zero_k,
                       zero_k, jnp.zeros((), _I32))
    return jax.lax.while_loop(cond, body, init)


def push_relabel_batched(
    cf: jax.Array,               # i32[K,V,E]
    sink_cf: jax.Array,          # i32[K,V]
    excess: jax.Array,           # i32[K,V]
    lab: jax.Array,              # i32[K,V]
    *,
    nbr_local: jax.Array,
    rev_slot: jax.Array,
    intra: jax.Array,
    emask: jax.Array,
    vmask: jax.Array,
    cross_pushable: jax.Array,
    cross_lab: jax.Array,
    d_inf,
    sink_open: bool = True,
    max_iters: int | None = None,
    backend: str = "xla",
    block_v: int | None = None,
    interpret: bool | None = None,
    chunk_iters: int | None = None,
    vmem_budget_bytes: int | None = None,
    grid2d: tuple[int, int] | None = None,
) -> EngineState:
    """Run push/relabel on all K regions of a sweep through one entry point.

    The batched counterpart of ``push_relabel``: per-region results (state,
    ``out_push``, iteration counts) are bit-identical to vmapping the
    scalar engine, but the fused paths dispatch over regions collectively —
    one ``grid=(K,)`` kernel launch per chunk on ``backend="pallas"``
    instead of K independent launch sequences.  ``EngineState`` fields are
    the [K]-batched forms except ``launches``, which is the global dispatch
    count of this engine run.  Unfused configurations (``chunk_iters=None``)
    and Pallas regions over the VMEM budget fall back to ``jax.vmap`` of
    the scalar engine (per-region launch counts summed).

    ``d_inf`` may be a scalar or per-region i32[K] (each region of a solve
    batch keeps its own instance's ceiling).  ``grid2d=(B, Kr)`` renders
    the fused pallas launch as a ``grid=(B, Kr)`` program over the flat
    region axis ``K == B*Kr`` (the solve-batch form); results and launch
    counts are unchanged.
    """
    K, V, E = cf.shape
    d_inf = jnp.asarray(d_inf, _I32)
    if chunk_iters is not None and backend == "pallas" \
            and not _pr_kernel.fused_region_fits_vmem(
                V, E, vmem_budget_bytes, dtypes=_kernel_dtypes(cf, lab)):
        chunk_iters = None
    if chunk_iters is None:
        d_inf_k = jnp.broadcast_to(d_inf, (K,))
        fn = lambda cf, s, e, l, nl, rs, it, em, vm, cp, cl, di: push_relabel(
            cf, s, e, l, nbr_local=nl, rev_slot=rs, intra=it, emask=em,
            vmask=vm, cross_pushable=cp, cross_lab=cl, d_inf=di,
            sink_open=sink_open, max_iters=max_iters, backend=backend,
            block_v=block_v, interpret=interpret)
        es = jax.vmap(fn)(cf, sink_cf, excess, lab, nbr_local, rev_slot,
                          intra, emask, vmask, cross_pushable, cross_lab,
                          d_inf_k)
        return es._replace(launches=es.launches.sum())
    return _push_relabel_fused_batched(
        cf, sink_cf, excess, lab, nbr_local=nbr_local, rev_slot=rev_slot,
        intra=intra, emask=emask, vmask=vmask, cross_pushable=cross_pushable,
        cross_lab=cross_lab, d_inf=d_inf, sink_open=sink_open,
        max_iters=max_iters, backend=backend, chunk_iters=chunk_iters,
        interpret=interpret, grid2d=grid2d)


def bfs_to_targets(
    cf: jax.Array,
    sink_cf: jax.Array,
    *,
    nbr_local: jax.Array,
    intra: jax.Array,
    emask: jax.Array,
    vmask: jax.Array,
    target_cross: jax.Array,   # bool[V,E] cross arcs that enter the target set
    linf,
    sink_open: bool = True,
    label_dtype=None,
) -> jax.Array:
    """Exact hop distance to the target set through residual arcs.

    Vectorized Bellman-Ford (unit weights); converges in <= diameter rounds.
    Used to initialise each ARD stage's local labels — the engine then starts
    from the true distance, which is what makes the staged discharge behave
    like the paper's shortest-path-first augmentation.
    """
    V, E = cf.shape
    ldt = _I32 if label_dtype is None else jnp.dtype(label_dtype)
    linf = jnp.asarray(linf).astype(ldt)
    base = jnp.where(
        (target_cross & emask & (cf > 0)).any(axis=1), linf.dtype.type(1),
        linf)
    if sink_open:
        base = jnp.where(sink_cf > 0, jnp.minimum(base, 1), base)
    base = jnp.where(vmask, base, linf)

    def body(carry):
        lab, _ = carry
        nlab = jnp.where(intra & emask & (cf > 0), lab[nbr_local], linf)
        relaxed = jnp.minimum(lab, jnp.minimum(base, nlab.min(axis=1) + 1))
        relaxed = jnp.where(vmask, relaxed, linf)
        return relaxed, (relaxed != lab).any()

    def cond(carry):
        return carry[1]

    lab0 = base
    lab, _ = jax.lax.while_loop(cond, body, (lab0, jnp.asarray(True)))
    return jnp.minimum(lab, linf)
