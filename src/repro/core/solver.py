"""Solver sessions: prepared problem handles, warm-start incremental
re-solves, and one unified front-end over every solve route.

The paper's target workloads are *sequences* of closely related maxflow
problems — vision instances whose capacities change a little between
frames while the region structure stays fixed (Sec. 7; the dynamic-cuts
line of work in PAPERS.md).  A serving system therefore wants three things
the one-shot entry points cannot give it:

* **prepared handles** — ``Solver.prepare(problem)`` runs the host-side
  ``build``/``Layout`` blocking ONCE and keeps the ``GraphMeta`` plus the
  device-resident ``FlowState``; every subsequent solve and update reuses
  them;
* **warm-start re-solves** — ``handle.update(...)`` applies a capacity
  delta directly on device by reparameterizing the residual network in the
  Kohli-Torr dynamic-cuts style (``graph.apply_update``): residuals are
  clamped into the new capacities, clamped overflow returns to vertex
  excess, uncoverable deficits are cancelled against the t-link with the
  flow-value offset tracked per handle.  ``handle.solve()`` then continues
  from the warm preflow through the *same* sweep drivers instead of
  re-solving from zero;
* **one front-end** — ``handle.solve()`` dispatches to the host-loop or
  device-resident driver (``SolverOptions.device_resident``), to the
  sharded SPMD driver (``mesh=``), and ``Solver.solve_many([...])`` to the
  shape-bucketed batched driver — all returning the same
  ``MincutResult``/``SweepStats`` shape, all sharing one compile cache
  (``Solver.cache_info``).

Label semantics across an update (``SolverOptions.warm_labels``): labels
must stay valid *lower bounds* on residual distance-to-sink.  Capacity
*decreases* only remove residual arcs, so kept labels stay valid; any
residual-capacity *increase* (including the deficit-cancellation t-links)
can create new residual arcs that invalidate labels arbitrarily far
upstream — trapped excess parked at ``d_inf`` would never re-activate.
The default ``"auto"`` policy therefore refreshes labels with
``labels.global_relabel`` — the exact distance labeling of the updated
residual network, sound unconditionally and *tight*, computed by a
handful of cheap relabel programs (no discharge engine runs) — but only
when an update actually added residual capacity (``apply_update``'s
``grew`` flag); pure decreases keep their still-valid labels for free.
``"keep"`` always skips the refresh (caller asserts decrease-only
updates), ``"reset"`` starts from the cold ``Init`` labels.
"""

from __future__ import annotations

import dataclasses
import hashlib
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune as _autotune
from repro.core import batch as _batch
from repro.core import distributed as _distributed
from repro.core import dtypes as _dt
from repro.core import executor as _executor
from repro.core import graph as _graph
from repro.core import invariants as _inv
from repro.core import labels as _labels
from repro.core import partition as _partition
from repro.core import resilience as _res
from repro.core import spans as _spans
from repro.core import sweep as _sweep
from repro.core.graph import (FlowState, GraphMeta, GraphUpdate, Layout,
                              Problem, _round_pow2)


@dataclass
class MincutResult:
    flow_value: int                 # maximum preflow value == mincut cost
    source_side: np.ndarray         # bool[n] vertex in the source set C
    stats: _sweep.SweepStats
    meta: GraphMeta
    state: FlowState
    layout: Layout
    converged: bool = True          # False: max_sweeps ran out with active
    #                                 vertices left — flow_value is a valid
    #                                 preflow's, possibly below the maximum,
    #                                 and the cut certificate was NOT checked
    diagnosis: _inv.NonConvergence | None = None
    #                                 structured report when not converged
    #                                 (which invariants hold, what stopped)


@dataclass(frozen=True)
class SolverOptions:
    """One place for every solver knob (a frozen, hashable dataclass).

    Absorbs the previously scattered configuration surface: the
    ``SweepConfig`` fields (see ``sweep.SweepConfig`` for their meaning),
    the front-end kwargs ``num_regions``/``check``, and the sharded-route
    ``exchange`` mode.  Session-only knobs:

    warm_labels — label policy of a warm re-solve after ``update``:
        ``"auto"`` (default) refresh labels with the exact global relabel
        (``labels.global_relabel`` — sound for any update, tight, a few
        cheap device programs) iff the update added residual capacity
        anywhere, else keep them (capacity removal only raises true
        distances, so kept labels stay valid); ``"keep"``/``True`` always
        keep (caller asserts decrease-only updates); ``"reset"``/
        ``False`` re-initialize to the cold ``Init`` labels.
    dtype_policy — kernel storage-dtype policy (``dtypes.DTYPE_POLICIES``):
        ``"int32"`` (default) keeps the wide baseline; ``"auto"`` narrows
        labels/residuals to int16 (masks to int8) whenever this problem's
        range bounds allow, falling back to int32 per family; ``"narrow"``
        forces narrowing and makes a failed bound a typed
        ``ProblemValidationError`` at ``prepare`` time.  Narrowed handles
        re-check the flow bound on every ``update`` (capacity growth can
        outgrow int16; topology — hence the label bound — cannot change).
    autotune — resolve ``engine_chunk_iters`` (and fused-vs-blocked
        dispatch) per ``(bucket dims, backend, dtypes)`` key through the
        VMEM-budget autotuner (``core.autotune``) instead of the static
        default.  An explicitly pinned ``engine_chunk_iters`` wins over
        the tuner; tuned decisions persist in a JSON cache so repeat keys
        cost zero search and zero retrace.
    streaming — route solves through the out-of-core streaming executor
        (``repro.stream``): regions are staged one at a time from a disk
        spill pool, at most ``max_resident_regions`` region states are in
        memory at once, and only the |B|-sized boundary layer persists
        between visits.  Requires the sequential sweep without the global
        gap heuristic (``parallel=False``, ``use_global_gap=False``) —
        anything else raises ``UnsupportedFeatureError`` naming the flag.
        ``spill_dir`` pins the pool to a durable directory (kill-resume
        needs the pool to outlive the process); ``None`` uses a temp dir
        deleted when the solve finishes.  ``prefetch`` overlaps the next
        region's disk read with the current region's discharge.
    """

    # --- sweep/engine knobs (mirror sweep.SweepConfig) ---
    method: str = "ard"
    parallel: bool = True
    partial_discharge: bool = False
    use_global_gap: bool = True
    use_boundary_relabel: bool = False
    max_sweeps: int | None = None
    engine_max_iters: int | None = None
    engine_backend: str = "xla"
    engine_chunk_iters: int | None = None
    device_resident: bool = False
    host_sync_every: int | None = None
    stats_ring_size: int = 1024
    # --- session knobs ---
    num_regions: int = 4
    check: bool = True
    warm_labels: bool | str = "auto"
    dtype_policy: str = "int32"
    autotune: bool = False
    # --- sharded-route knobs ---
    exchange: str = "full"
    # --- streaming-route knobs ---
    streaming: bool = False
    max_resident_regions: int = 2
    spill_dir: str | None = None
    prefetch: bool = True

    def __post_init__(self):
        assert self.warm_labels in (True, False, "auto", "keep", "reset")
        assert self.exchange in ("full", "boundary")
        assert self.max_resident_regions >= 1
        if self.dtype_policy not in _dt.DTYPE_POLICIES:
            raise ValueError(
                f"unknown dtype_policy {self.dtype_policy!r}; expected one "
                f"of {_dt.DTYPE_POLICIES}")
        self.sweep_config()     # delegate knob validation to SweepConfig

    def sweep_config(self) -> _sweep.SweepConfig:
        """The ``SweepConfig`` view consumed by the sweep drivers."""
        fields = {f.name for f in dataclasses.fields(_sweep.SweepConfig)}
        return _sweep.SweepConfig(**{
            f.name: getattr(self, f.name)
            for f in dataclasses.fields(self) if f.name in fields})

    @classmethod
    def from_sweep_config(cls, cfg: _sweep.SweepConfig | None = None,
                          **session_kw) -> "SolverOptions":
        """Lift a legacy ``SweepConfig`` (plus front-end kwargs) into
        session options — the bridge the backward-compat shims use."""
        kw = dataclasses.asdict(cfg) if cfg is not None else {}
        kw.update(session_kw)
        return cls(**kw)

    def _labels_mode(self) -> str:
        return {True: "keep", False: "reset"}.get(
            self.warm_labels, self.warm_labels)


@dataclass
class SolverCacheInfo:
    """Compile-cache accounting of one ``Solver`` session.

    ``hits``/``misses`` count solve/update program invocations served by an
    already-compiled executable vs ones that traced something new;
    ``traces`` is the raw trace counter those are derived from (a
    same-shape re-solve must leave it unchanged).
    """

    hits: int = 0
    misses: int = 0
    traces: int = 0


def _finish(meta: GraphMeta, state0: FlowState, state: FlowState,
            layout: Layout, stats: _sweep.SweepStats, check: bool,
            offset: int = 0, *, converged: bool = True, ard: bool = True,
            max_sweeps: int | None = None) -> MincutResult:
    """Extract the cut and package a result (shared by every route).

    ``offset`` — accumulated flow-value offset of the handle's
    deficit-cancelling reparameterizations: the solved ``flow_to_t`` of
    the reparameterized network exceeds the true maxflow by exactly this
    constant (see ``graph.apply_update``), and the cut partition is
    unchanged, so subtracting it here restores the true value.
    ``check`` verifies that the cut cost in the (current, un-reparameter-
    ized) initial network equals that value — an extra device fetch plus
    an O(n*E) host reduction, so serving paths may disable it.

    A solve that stopped at ``max_sweeps`` (``converged=False``) returns a
    structured result — ``MincutResult.converged=False`` plus a
    ``NonConvergence`` diagnosis naming the active-vertex count and any
    broken invariants — and SKIPS the certificate (a non-maximum preflow's
    cut cost legitimately differs from its flow).  A converged solve whose
    certificate fails raises the typed ``CertificateError`` (an
    ``AssertionError``, as the historical bare assert was) carrying the
    same diagnosis on ``.diagnosis``.
    """
    with _spans.span("maxflow.finish"):
        with _spans.span("maxflow.extract_cut") as sp:
            sink_side = _sweep.extract_cut(meta, state)
            sp.wait(sink_side)
        flow = int(state.flow_to_t) - offset
        diagnosis = None
        if not converged:
            diagnosis = _inv.diagnose(
                meta, state, ard=ard, reason="max_sweeps",
                sweeps=stats.sweeps, max_sweeps=max_sweeps, flow_value=flow)
        elif check:
            with _spans.span("maxflow.certificate"):
                cost = int(_sweep.cut_value(meta, state0, sink_side))
            if cost != flow:
                raise _inv.CertificateError(
                    f"internal error: cut cost {cost} != max preflow {flow}",
                    _inv.diagnose(meta, state, ard=ard, reason="certificate",
                                  sweeps=stats.sweeps, max_sweeps=max_sweeps,
                                  flow_value=flow, cut_cost=cost))
        source_flat = ~layout.to_flat(np.asarray(sink_side))
    return MincutResult(flow_value=flow, source_side=source_flat,
                        stats=stats, meta=meta, state=state, layout=layout,
                        converged=converged, diagnosis=diagnosis)


def _pad_i32(a: np.ndarray, size: int) -> jnp.ndarray:
    out = np.zeros(size, np.int32)
    out[: len(a)] = a
    return jnp.asarray(out)


def _widen_state(st: FlowState) -> FlowState:
    """Cast a (possibly narrowed) state up to the sharded driver's int32.

    Label sentinels translate by a monotone offset — the narrow infinity
    class ``[2**14, ...)`` maps onto the wide class ``[2**30, ...)``
    preserving relative order — so the widened state is exactly what a
    wide build of the same problem would hold, and the sharded solve is
    bit-identical to the wide route.
    """
    if st.cf.dtype == jnp.int32 and st.d.dtype == jnp.int32:
        return st
    d = st.d.astype(jnp.int32)
    if st.d.dtype != jnp.int32:
        d = jnp.where(d >= _dt.NARROW_INF_LABEL,
                      d - _dt.NARROW_INF_LABEL + _dt.INF_LABEL_WIDE, d)
    return st.replace(cf=st.cf.astype(jnp.int32),
                      sink_cf=st.sink_cf.astype(jnp.int32),
                      excess=st.excess.astype(jnp.int32), d=d)


def _narrow_state(st: FlowState, meta: GraphMeta) -> FlowState:
    """Cast a sharded-route int32 result back to the handle's storage
    dtypes (inverse of ``_widen_state``; no-op for wide handles).

    Finite labels are all below the narrow limit by the prepare-time
    bound; anything in the wide infinity class maps back by the same
    offset, and any other over-limit value (all ``>= d_inf``, hence
    semantically infinite) clamps to the narrow sentinel.
    """
    kd = meta.kernel_dtypes
    if kd.flow == "int32" and kd.label == "int32":
        return st
    fdt = jnp.dtype(kd.flow_np)
    d = st.d
    if kd.label != "int32":
        d = jnp.where(
            d >= _dt.INF_LABEL_WIDE,
            d - _dt.INF_LABEL_WIDE + _dt.NARROW_INF_LABEL,
            jnp.minimum(d, _dt.NARROW_INF_LABEL)).astype(
                jnp.dtype(kd.label_np))
    return st.replace(cf=st.cf.astype(fdt), sink_cf=st.sink_cf.astype(fdt),
                      excess=st.excess.astype(fdt), d=d)


class ProblemHandle:
    """A prepared problem inside a ``Solver`` session.

    Holds the one-time ``build`` artifacts (``meta``/``layout``), the
    device-resident current state, and the initial network of the
    *current* problem (``state0``, maintained incrementally across
    updates) used by the cut-cost check.  After a solve the handle is
    *warm*: ``update`` reparameterizes the solved preflow in place and the
    next ``solve`` continues from it.
    """

    def __init__(self, solver: "Solver", problem: Problem,
                 part: np.ndarray, meta: GraphMeta, state: FlowState,
                 layout: Layout):
        self.solver = solver
        self.problem = problem
        self.part = part
        self.meta = meta
        self.layout = layout
        self.state = state            # current device state (residuals, d)
        self.state0 = state           # initial network of current problem
        self.warm = False             # a solved preflow is resident
        self._dirty = False           # updates applied since the last solve
        self._grew = jnp.zeros((), bool)   # any residual capacity increase
        #                                    since the last solve (device)
        self._flow_offset = jnp.zeros((), jnp.int32)

    # -- update ------------------------------------------------------------

    def update(self, *, cap_fwd=None, cap_bwd=None, excess=None,
               sink_cap=None, arcs=None) -> "ProblemHandle":
        """Apply a capacity/terminal delta to the prepared problem.

        ``cap_fwd``/``cap_bwd`` — new ABSOLUTE edge capacities: full
        ``[m]`` arrays, or, with ``arcs`` (edge indices into
        ``problem.edges``), values for just those edges.  ``excess``/
        ``sink_cap`` — new absolute terminal arrays ``[n]``.  Topology is
        fixed per handle (that is the point of preparing); new edges need
        a fresh ``prepare``.

        The delta lands on device through one jitted scatter program
        (``graph.apply_update``) with the changed-entry count padded to a
        power of two, so steady-state perturbations of similar size reuse
        one compiled update.  Statistics semantics: ``SweepStats`` always
        describes one solve call, so counters "reset" naturally on the
        next ``solve``; ``flow_to_t`` (and the flow-offset bookkeeping)
        carry across updates.  Returns ``self`` for chaining.
        """
        with _spans.span("maxflow.update"):
            return self._update(cap_fwd, cap_bwd, excess, sink_cap, arcs)

    def _update(self, cap_fwd, cap_bwd, excess, sink_cap, arcs):
        p = self.problem
        m, n = len(p.edges), p.num_vertices
        if arcs is not None:
            idx = np.atleast_1d(np.asarray(arcs, np.int64))
            assert idx.ndim == 1
            if len(idx):
                assert idx.min() >= 0 and idx.max() < m, "arc index range"
            new_fwd, new_bwd = p.cap_fwd.copy(), p.cap_bwd.copy()
            if cap_fwd is not None:
                new_fwd[idx] = np.asarray(cap_fwd, np.int32)
            if cap_bwd is not None:
                new_bwd[idx] = np.asarray(cap_bwd, np.int32)
        else:
            # np.array (not asarray): the arrays become the handle's new
            # baseline, so aliasing the caller's buffer would make a later
            # mutate-and-update diff against itself and drop the edit
            new_fwd = p.cap_fwd if cap_fwd is None \
                else np.array(cap_fwd, np.int32)
            new_bwd = p.cap_bwd if cap_bwd is None \
                else np.array(cap_bwd, np.int32)
        new_exc = p.excess if excess is None else np.array(excess, np.int32)
        new_snk = p.sink_cap if sink_cap is None \
            else np.array(sink_cap, np.int32)
        assert new_fwd.shape == (m,) and new_bwd.shape == (m,)
        assert new_exc.shape == (n,) and new_snk.shape == (n,)
        newp = dataclasses.replace(p, cap_fwd=new_fwd, cap_bwd=new_bwd,
                                   excess=new_exc, sink_cap=new_snk)
        with _spans.span("maxflow.validate"):
            if self.solver.options.check:
                # reject negative / overflow-risk capacities before they
                # land on device (opt-out: SolverOptions.check=False)
                _graph.validate_problem(newp, context="update")
            else:
                assert (new_fwd >= 0).all() and (new_bwd >= 0).all()
                assert (new_exc >= 0).all() and (new_snk >= 0).all()
            # narrowed storage is sized by the flow-mass bound at prepare
            # time; an update that grows total capacity past it would wrap
            # int16 residuals silently — always rejected, even unchecked
            _graph.validate_update_dtypes(self.meta, newp)

        d_fwd = new_fwd.astype(np.int64) - p.cap_fwd
        d_bwd = new_bwd.astype(np.int64) - p.cap_bwd
        changed = np.nonzero((d_fwd != 0) | (d_bwd != 0))[0]
        d_snk = new_snk.astype(np.int64) - p.sink_cap
        d_exc = new_exc.astype(np.int64) - p.excess
        tchanged = np.nonzero((d_snk != 0) | (d_exc != 0))[0]
        lay = self.layout
        V = self.meta.region_size
        tflat = lay.part[tchanged] * V + lay.local_id[tchanged]

        j = _round_pow2(max(1, len(changed)))
        tp = _round_pow2(max(1, len(tchanged)))
        upd = GraphUpdate(
            arc_u=_pad_i32(lay.edge_arc_u[changed], j),
            arc_v=_pad_i32(lay.edge_arc_v[changed], j),
            vtx_u=_pad_i32(lay.edge_vtx_u[changed], j),
            vtx_v=_pad_i32(lay.edge_vtx_v[changed], j),
            d_cap_fwd=_pad_i32(d_fwd[changed], j),
            d_cap_bwd=_pad_i32(d_bwd[changed], j),
            t_vtx=_pad_i32(tflat, tp),
            d_sink=_pad_i32(d_snk[tchanged], tp),
            d_excess=_pad_i32(d_exc[tchanged], tp))

        before = self.solver._trace_total()
        with _spans.span("maxflow.apply_update", arcs=len(changed),
                         terminals=len(tchanged), bucket=max(j, tp)) as sp:
            self.state, self.state0, grew, doff = _graph.apply_update(
                self.state, self.state0, upd)
            sp.wait(self.state, self.state0)
        self.solver._note(before)
        self._dirty = True
        self._grew = self._grew | grew
        self._flow_offset = self._flow_offset + doff
        self.problem = newp
        return self

    def reset(self) -> "ProblemHandle":
        """Forget the solved preflow: the next solve runs cold (from the
        current problem's initial network)."""
        self.state = self.state0
        self.warm = False
        self._dirty = False
        self._grew = jnp.zeros((), bool)
        self._flow_offset = jnp.zeros((), jnp.int32)
        return self

    # -- solve -------------------------------------------------------------

    def _entry_state(self) -> FlowState:
        """The state a solve starts from, with the label policy applied.

        ``"auto"`` refreshes labels (exact global relabel) only when an
        update actually ADDED residual capacity somewhere
        (``apply_update``'s ``grew`` flag, one scalar fetch): pure
        decreases only remove residual arcs, so the kept labels remain
        valid lower bounds and the relabel fixpoint would be wasted work.
        """
        with _spans.span("maxflow.entry_state") as sp:
            if not self.warm:
                sp.set(labels="cold")
                return _graph.init_labels(self.meta, self.state)
            mode = self.solver.options._labels_mode()
            st = self.state
            if mode == "reset":
                sp.set(labels="reset")
                return st.replace(d=jnp.zeros_like(st.d))
            if mode == "auto" and self._dirty and bool(self._grew):
                sp.set(labels="relabel")
                with _spans.span("maxflow.global_relabel") as rl:
                    st = _labels.global_relabel(
                        self.meta, st, self.solver.options.method == "ard")
                    rl.wait(st.d)
                return st
            sp.set(labels="kept")
            return st                 # "keep", or labels provably valid

    def _layout_salt(self) -> str:
        """Fingerprint salt binding checkpoints to THIS partition — two
        same-shaped problems with different region assignments must not
        cross-resume."""
        return hashlib.sha256(
            np.ascontiguousarray(self.part).tobytes()).hexdigest()[:16]

    def solve(self, *, mesh=None, axes=("regions",), checkpoint=None,
              resume_from=None, on_sweep=None) -> MincutResult:
        """Solve (or warm re-solve) the prepared problem.

        Routes on the session options: host-loop or device-resident sweep
        driver by default, the sharded SPMD driver when a ``mesh`` is
        given.  Cold solves start from the paper's ``Init``; warm solves
        continue from the resident preflow with labels per
        ``SolverOptions.warm_labels``.

        ``checkpoint`` — a ``resilience.CheckpointPolicy`` or a directory
        path: capture resumable sweep-boundary checkpoints (the handle
        stamps its layout digest and warm-start flow offset into them).
        ``resume_from`` — a ``SolveCheckpoint`` or checkpoint directory:
        continue an interrupted solve bit-exactly; the checkpoint's flow
        offset is adopted (authoritative for a cross-process resume).

        Accelerator resource exhaustion (``resilience.is_kernel_failure``)
        degrades the engine configuration one ladder rung at a time
        (pallas-fused -> xla-fused -> xla-unfused,
        ``resilience.degrade_config``) and re-runs — every rung is
        bit-exact, and each degradation is recorded in ``stats.degraded``,
        never silent.  Any other failure, a kernel the compiler refuses
        among them, raises.

        ``on_sweep(state, sweeps_done)`` — optional sweep-boundary hook
        (fires at every boundary on the host loop, at the
        ``host_sync_every`` boundaries on the device-resident and sharded
        drivers) — the serving tier's deadline-enforcement point.
        """
        opts = self.solver.options
        route = ("streaming" if opts.streaming else
                 "sharded" if mesh is not None else
                 "device" if opts.device_resident else "host")
        with _spans.span("maxflow.solve", route=route) as sp:
            res = self._solve(mesh, axes, checkpoint, resume_from, on_sweep)
            sp.set(sweeps=res.stats.sweeps,
                   engine_iters=res.stats.engine_iters,
                   ard_stages=res.stats.ard_stages)
            return res

    def _solve(self, mesh, axes, checkpoint, resume_from, on_sweep):
        opts = self.solver.options
        cfg = opts.sweep_config()
        if opts.autotune:
            cfg = _autotune.tuned_sweep_config(cfg, self.meta)
        salt = self._layout_salt()
        if isinstance(checkpoint, (str, Path)):
            checkpoint = _res.CheckpointPolicy(directory=checkpoint)
        ckpt_obj = resume_from
        if isinstance(ckpt_obj, (str, Path)):
            ckpt_obj = _res.load_checkpoint(ckpt_obj)
        if ckpt_obj is not None:
            # the checkpoint's bookkeeping is authoritative across processes
            self._flow_offset = jnp.asarray(ckpt_obj.flow_offset, jnp.int32)
        if checkpoint is not None:
            checkpoint = dataclasses.replace(
                checkpoint, salt=salt, flow_offset=int(self._flow_offset))
        before = self.solver._trace_total()  # before _entry_state: the
        #                 warm-labels relabel program's trace must count
        st_in = self._entry_state()
        d_inf = (self.meta.d_inf_ard if opts.method == "ard"
                 else self.meta.d_inf_prd)

        def run(c):
            if opts.streaming:
                if mesh is not None:
                    raise ValueError(
                        "streaming and mesh are mutually exclusive routes: "
                        "the streaming executor stages regions through host "
                        "memory one at a time, the sharded driver keeps all "
                        "of them device-resident")
                from repro import stream as _stream
                ss = _stream.open_stream(
                    self.meta, st_in, c, spill_dir=opts.spill_dir,
                    max_resident_regions=opts.max_resident_regions,
                    prefetch=opts.prefetch, cold_labels=False)
                try:
                    ss, stats = _stream.solve_stream(
                        ss, on_sweep=on_sweep, checkpoint=checkpoint,
                        resume_from=ckpt_obj, salt=salt)
                    st = _stream.assemble_state(ss, st_in)
                finally:
                    ss.store.close()
                return st, stats
            if mesh is not None:
                # the sharded driver's state specs are pinned to int32
                # (distributed.py builds abstract int32 avals for the SPMD
                # programs), so a narrowed handle widens at entry and
                # narrows back at exit.  The sentinel classes map 1:1
                # (monotone offset), so results are bit-exact either way.
                st_sh = _widen_state(st_in)
                st, sweeps, syncs = _distributed.solve_sharded(
                    self.meta, st_sh, mesh, c, axes=tuple(axes),
                    exchange=opts.exchange, return_stats=True,
                    checkpoint=checkpoint, resume_from=ckpt_obj, salt=salt,
                    on_sweep=on_sweep)
                # back beside the handle's other arrays (state0, updates,
                # cut extraction); the next sharded solve re-shards it
                st = jax.device_put(
                    st, jax.tree.map(lambda a: a.sharding, st_sh))
                st = _narrow_state(st, self.meta)
                _pb, msg_bytes = _sweep._page_and_msg_bytes(self.meta)
                stats = _sweep.SweepStats(
                    sweeps=sweeps, engine_iters=None, ard_stages=None,
                    engine_launches=None,
                    host_syncs=syncs, boundary_bytes=sweeps * msg_bytes,
                    page_bytes=None, num_boundary=self.meta.num_boundary,
                    regions_discharged=None,
                    converged=int(st.active(d_inf).sum()) == 0)
                return st, stats
            return _sweep.solve(self.meta, st_in, c, warm=True,
                                checkpoint=checkpoint, resume_from=ckpt_obj,
                                salt=salt, on_sweep=on_sweep)

        notes: list[str] = []
        st, stats = _res.run_with_degradation(run, cfg, notes)
        stats.degraded = notes + stats.degraded
        self.state = st
        self.warm = True
        self._dirty = False
        self._grew = jnp.zeros((), bool)
        res = _finish(self.meta, self.state0, st, self.layout, stats,
                      opts.check, offset=int(self._flow_offset),
                      converged=stats.converged, ard=opts.method == "ard",
                      max_sweeps=cfg.max_sweeps)
        # noted after _finish, so that a cut-extraction trace makes a miss
        self.solver._note(before)
        return res


class Solver:
    """A solver session: one ``SolverOptions``, one compile cache, every
    route.

    ``prepare`` a problem once, then ``solve``/``update``/``solve`` its
    handle as capacities evolve; hand a fleet of handles (or raw problems)
    to ``solve_many`` for the shape-bucketed batched driver; pass
    ``mesh=`` to a handle's solve for the sharded SPMD driver.  All routes
    return the same ``MincutResult`` shape and share the session's
    compiled programs — ``cache_info()`` reports hits/misses, where a miss
    is an invocation that actually traced a device program (sweep, batch,
    sharded-sweep, update or cut-extraction tracers combined).
    """

    def __init__(self, options: SolverOptions | None = None, **overrides):
        if options is None:
            options = SolverOptions(**overrides)
        elif overrides:
            options = dataclasses.replace(options, **overrides)
        self.options = options
        self.cache = SolverCacheInfo()
        self.last_batch_stats: list[_batch.BatchStats] = []

    # -- compile-cache accounting -----------------------------------------

    @staticmethod
    def _trace_total() -> int:
        import sys
        sm = sys.modules.get("repro.stream.executor")
        return (_sweep.trace_count() + _batch.trace_count()
                + _graph.update_trace_count() + _labels.trace_count()
                + _distributed.trace_count()
                + (sm.trace_count() if sm is not None else 0))

    def _note(self, before: int) -> None:
        now = self._trace_total()
        if now > before:
            self.cache.misses += 1
        else:
            self.cache.hits += 1
        self.cache.traces = now

    def cache_info(self) -> SolverCacheInfo:
        self.cache.traces = self._trace_total()
        return dataclasses.replace(self.cache)   # a snapshot, not an alias

    # -- the front-end -----------------------------------------------------

    def prepare(self, problem: Problem,
                part: np.ndarray | None = None) -> ProblemHandle:
        """Region-block a problem once; returns its session handle.

        ``part`` — region id per vertex; defaults to node-number slicing
        into ``options.num_regions`` regions (the paper's fallback
        partitioner, as before).
        """
        with _spans.span("maxflow.prepare", n=problem.num_vertices,
                         m=len(problem.edges)):
            if self.options.check or self.options.dtype_policy == "narrow":
                # fail fast on malformed input (negative capacities, int32
                # overflow risk vs INF_CAP) before any device work; serving
                # paths opt out with SolverOptions.check=False — except the
                # forced-narrow bound check, which must never be silent
                with _spans.span("maxflow.validate"):
                    _graph.validate_problem(
                        problem, context="problem",
                        dtype_policy=self.options.dtype_policy)
            if part is None:
                part = _partition.block_partition(problem.num_vertices,
                                                  self.options.num_regions)
            part = np.asarray(part)
            with _spans.span("maxflow.build") as sp:
                meta, state, layout = _graph.build(
                    problem, part, dtype_policy=self.options.dtype_policy)
                sp.wait(state)
                sp.set(K=meta.num_regions, V=meta.region_size,
                       E=meta.max_degree)
            return ProblemHandle(self, problem, part, meta, state, layout)

    def solve(self, problem: Problem, part: np.ndarray | None = None, *,
              mesh=None) -> MincutResult:
        """One-shot convenience: ``prepare(problem, part).solve()``."""
        return self.prepare(problem, part).solve(mesh=mesh)

    def solve_many(self, items, parts=None, *, checkpoint=None,
                   resume_from=None) -> list[MincutResult]:
        """Solve a fleet through the shape-bucketed batched driver.

        ``items`` — ``ProblemHandle``s of this session and/or raw
        ``Problem``s (prepared on the fly, ``parts[i]`` honored).  Handles
        enter with their current state — so previously-solved, updated
        handles ride the batched driver *warm* — and leave warm, exactly
        as if solved individually.  Per-instance results are bit-identical
        to ``handle.solve()`` on the same state; ``engine_launches``/
        ``host_syncs`` in the returned stats are global to each batch
        (``SweepStats.scope == "batch"``).

        ``checkpoint``/``resume_from`` — sweep-boundary checkpointing as
        in ``handle.solve``, restricted to fleets that pack into ONE shape
        bucket (one checkpoint stream per solve; re-pack the same items in
        the same order to resume).
        """
        with _spans.span("maxflow.solve_many", B=len(items)) as sp:
            results = self._solve_many(items, parts, checkpoint, resume_from)
            sp.set(buckets=len(self.last_batch_stats),
                   engine_iters=sum(r.stats.engine_iters for r in results))
            return results

    def _solve_many(self, items, parts, checkpoint, resume_from):
        cfg = self.options.sweep_config()
        if self.options.streaming:
            raise ValueError(
                "solve_many and streaming are mutually exclusive: the "
                "batched driver packs every instance device-resident; "
                "solve streaming handles one at a time instead")
        _executor.BatchedExecutor.validate(cfg)
        if isinstance(checkpoint, (str, Path)):
            checkpoint = _res.CheckpointPolicy(directory=checkpoint)
        handles: list[ProblemHandle] = []
        for i, it in enumerate(items):
            if isinstance(it, ProblemHandle):
                if it.solver is not self:
                    raise ValueError("handle belongs to another Solver "
                                     "session")
                handles.append(it)
            else:
                part = parts[i] if parts is not None else None
                handles.append(self.prepare(it, part))

        # trace window opens before the entry states: a warm handle's
        # label-refresh program must be attributed to this invocation
        before = self._trace_total()
        builds = [(i, h.meta, h._entry_state(), h.layout, h.state0)
                  for i, h in enumerate(handles)]
        with _spans.span("maxflow.pack") as sp:
            packs = _graph.pack_built(builds)
            sp.wait([p.state for p in packs])
        if (checkpoint is not None or resume_from is not None) \
                and len(packs) != 1:
            raise ValueError(
                f"checkpointed solve_many needs a single shape bucket "
                f"(one checkpoint stream per solve); these items pack "
                f"into {len(packs)} buckets")
        salt = hashlib.sha256(b"".join(
            np.ascontiguousarray(h.part).tobytes()
            for h in handles)).hexdigest()[:16]
        results: list[MincutResult | None] = [None] * len(handles)
        self.last_batch_stats = []
        for packed in packs:
            cfg_b = cfg
            if self.options.autotune:
                cfg_b = _autotune.tuned_sweep_config(cfg, packed.meta)
            bstate, bstats = _batch.solve_batch(
                packed, cfg_b, checkpoint=checkpoint, resume_from=resume_from,
                salt=salt)
            self.last_batch_stats.append(bstats)
            for b, idx in enumerate(packed.indices):
                h = handles[idx]
                meta = h.meta
                K, V, E = (meta.num_regions, meta.region_size,
                           meta.max_degree)
                st = h.state0.replace(
                    cf=bstate.cf[b, :K, :V, :E],
                    sink_cf=bstate.sink_cf[b, :K, :V],
                    excess=bstate.excess[b, :K, :V],
                    d=bstate.d[b, :K, :V],
                    flow_to_t=bstate.flow_to_t[b])
                sweeps = int(bstats.sweeps[b])
                page_bytes, msg_bytes = _sweep._page_and_msg_bytes(meta)
                converged = bool(bstats.converged[b]) \
                    if bstats.converged is not None else True
                stats = _sweep.SweepStats(
                    sweeps=sweeps,
                    engine_iters=int(bstats.engine_iters[b]),
                    ard_stages=None if bstats.ard_stages is None
                    else int(bstats.ard_stages[b]),
                    engine_launches=bstats.engine_launches,
                    host_syncs=bstats.host_syncs,
                    boundary_bytes=sweeps * msg_bytes,
                    page_bytes=sweeps * meta.num_regions * page_bytes,
                    num_boundary=meta.num_boundary,
                    regions_discharged=sweeps * meta.num_regions,
                    scope="batch", converged=converged)
                h.state = st
                h.warm = True
                h._dirty = False
                h._grew = jnp.zeros((), bool)
                results[idx] = _finish(
                    meta, h.state0, st, h.layout, stats, self.options.check,
                    offset=int(h._flow_offset), converged=converged,
                    ard=self.options.method == "ard",
                    max_sweeps=cfg.max_sweeps)
            # noted after the bucket's _finish calls, as in a single solve
            self._note(before)
            before = self._trace_total()
        return results
