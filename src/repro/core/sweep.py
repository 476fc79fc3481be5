"""Generic sequential (Alg. 1) and parallel (Alg. 2) region-discharge sweeps.

A *sweep* is one pass in which every region is discharged once — the paper's
complexity currency (≈ disk I/O in streaming mode, ≈ network messages in
parallel mode, ≈ ICI collective traffic here).

Parallel sweeps discharge all regions concurrently on frozen boundary labels
and then *fuse* boundary flow with the conflict rule of Alg. 2:

    alpha(u, v) = [ d'(u) <= d'(v) + 1 ]
    flow u->v is accepted iff alpha(v, u)   (the reverse arc stays valid)

Rejected flow is refunded to the sender's excess and residual.  Sequential
sweeps discharge regions one at a time, applying boundary flow immediately
(no conflicts by construction).

The driver also hosts the optional heuristics of Secs. 5-6 (global gap,
boundary-relabel, partial discharges) and the per-sweep accounting used by
the paper's tables (sweeps, boundary bytes, engine iterations, page I/O).

Two solve drivers share the same sweep programs and are bit-identical:
the host loop runs one jitted program + one host sync per sweep, while the
device-resident driver (``SweepConfig.device_resident``) runs the whole
loop — discharge, fusion, heuristics, convergence check and statistics —
inside one ``lax.while_loop``, syncing to the host once per
``host_sync_every`` sweeps (default: once per solve).  Parallel sweeps
discharge all K regions through the *batched* operators.

Both drivers are thin composition over the generic region-executor loop
(``core.executor``): ``solve`` instantiates ``executor.LocalExecutor``
over this module's sweep bodies and hands it to ``executor.run_host`` /
``executor.run_device`` — the same loop that runs the batched
(``core.batch``) and sharded (``core.distributed``) executors, so the
convergence/statistics logic exists exactly once.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import executor as _executor
from repro.core import heuristics
from repro.core import resilience as _res
from repro.core.ard import ard_discharge_batched, ard_discharge_one
from repro.core.engine import ENGINE_BACKENDS
from repro.core.graph import FlowState, GraphMeta, gather_at_nbr, intra_mask
from repro.core.labels import (gather_ghost_labels, global_gap,
                               region_relabel)
from repro.core.prd import prd_discharge_batched, prd_discharge_one

_I32 = jnp.int32

# bumped once per trace of a jitted program of this module (one-sweep
# bodies, the device-resident multi-sweep driver, cut extraction) — the
# observable behind the session front-end's ``Solver.cache_info``: a
# re-solve on a known shape must not bump it.
_TRACE_COUNT = 0


def trace_count() -> int:
    return _TRACE_COUNT


def _bump_trace() -> None:
    """Called from inside traced code (the generic executor device chunk,
    cut extraction): runs once per trace, never on cached invocations."""
    global _TRACE_COUNT
    _TRACE_COUNT += 1


@dataclass(frozen=True)
class SweepConfig:
    """Solver configuration.

    method              — "ard" (paper's contribution) or "prd" (baseline).
    parallel            — Alg. 2 (all regions concurrently + fusion) vs Alg. 1.
    partial_discharge   — Sec. 6.2: sweep s only augments to labels < s.
    use_global_gap      — Sec. 5.1 global gap heuristic each sweep.
    use_boundary_relabel— Sec. 6.1 boundary-relabel heuristic each sweep.
    max_sweeps          — hard cap (defaults to the theoretical bound).
    engine_max_iters    — safety cap for the inner engine (None = unbounded).
    engine_backend      — compute-phase backend of the discharge engine:
                          "xla" (dense rows) or "pallas" (the blocked
                          kernel, interpret mode off-TPU); bit-identical
                          results.
    engine_chunk_iters  — fused XLA engine: up to k complete iterations per
                          outer engine trip, one traced body per iteration;
                          None keeps the unfused two-phase engine.  XLA
                          only: with engine_backend="pallas" it raises
                          ``ValueError``.  Bit-identical to unfused.
    device_resident     — run the whole solve loop (discharge, fusion, gap
                          heuristic, convergence check, statistics) inside
                          one ``lax.while_loop`` on device instead of one
                          jitted program + one host sync per sweep;
                          bit-identical results, per-sweep curves kept in
                          fixed ``stats_ring_size`` device rings.
    host_sync_every     — device-resident escape hatch: return to the host
                          (one ``device_get``) every m sweeps; None (the
                          default) syncs only at convergence / the sweep
                          cap, i.e. a single sync per solve.
    stats_ring_size     — capacity of the device-resident flow/active curve
                          rings; only the last ``stats_ring_size`` sweeps
                          of the curves survive when a solve runs longer
                          (counters stay exact).
    """

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

    def __post_init__(self):
        assert self.method in ("ard", "prd")
        assert self.engine_backend in ENGINE_BACKENDS
        assert self.engine_chunk_iters is None or self.engine_chunk_iters >= 1
        if self.engine_backend == "pallas" \
                and self.engine_chunk_iters is not None:
            raise ValueError(
                "engine_backend='pallas' takes no engine_chunk_iters: the "
                "fused chunked engine is XLA only (use engine_backend='xla' "
                "with engine_chunk_iters, or engine_backend='pallas' with "
                "engine_chunk_iters=None)")
        assert self.host_sync_every is None or self.host_sync_every >= 1
        assert self.stats_ring_size >= 1


@dataclass
class SweepStats:
    """Per-solve accounting in the paper's I/O currency.

    ``scope`` says what the launch/sync counters cover: ``"instance"`` —
    every field is about this one solve; ``"batch"`` — the result came out
    of a batched multi-instance solve, so ``engine_launches``/``host_syncs``
    are GLOBAL to the whole batch that shared the launch/sync stream (the
    per-instance split would be fiction), while ``sweeps``/``engine_iters``/
    ``ard_stages`` and the byte counters remain exact per-instance values.
    Fields typed ``int | None`` are ``None`` on routes that cannot observe
    them (the sharded driver counts neither engine dispatches nor stages;
    ``ard_stages`` is ``None`` for ``method="prd"``, which has no stages).
    """

    sweeps: int = 0
    engine_iters: int | None = 0
    ard_stages: int | None = 0   # ARD stages run (one engine run per
    #                              distinct ghost label, ``ard.py``), summed
    #                              over sweeps and regions
    engine_launches: int | None = 0   # compute-program dispatches (2/iter
    #                              unfused, 1/iter fused)
    host_syncs: int = 0          # device->host transfers of the solve loop
    #                              (host loop: 1 + 1/sweep; device-resident:
    #                              1 per host_sync_every sweeps, 1 total by
    #                              default)
    boundary_bytes: int = 0      # flow+label messages over the cut (paper: I/O)
    page_bytes: int | None = 0   # streaming-mode region load/store bytes
    #                              (in-memory routes: the MODEL cost — what
    #                              the sweep WOULD stage; the streaming
    #                              executor reports measured staged bytes in
    #                              staged_in/out_bytes alongside it)
    num_boundary: int | None = None   # |B|: boundary vertices (cross-table
    #                              endpoints at build time) — the paper's
    #                              sweep-bound parameter (2|B|^2 + 1)
    staged_in_bytes: int = 0     # streaming executor: bytes actually read
    #                              from the spill pool (cache hits are free)
    staged_out_bytes: int = 0    # streaming executor: bytes written back
    regions_discharged: int | None = 0
    flow_curve: list = dataclasses.field(default_factory=list)
    active_curve: list = dataclasses.field(default_factory=list)
    scope: str = "instance"      # "instance" | "batch" (see class docstring)
    converged: bool = True       # False: stopped at max_sweeps with active
    #                              vertices left (see MincutResult.diagnosis)
    degraded: list = dataclasses.field(default_factory=list)
    #                              engine degradations taken mid-solve
    #                              (resilience ladder rungs) — never silent


_STAT_KEYS = ("sweeps", "engine_iters", "ard_stages", "engine_launches",
              "host_syncs", "boundary_bytes", "page_bytes", "num_boundary",
              "staged_in_bytes", "staged_out_bytes", "regions_discharged",
              "flow_curve", "active_curve", "converged", "degraded")


def stats_to_dict(stats: SweepStats) -> dict:
    """JSON-serializable accounting snapshot (checkpoint manifests)."""
    return {k: getattr(stats, k) for k in _STAT_KEYS}


def stats_from_dict(d: dict) -> SweepStats:
    """Inverse of :func:`stats_to_dict` (tolerates missing keys)."""
    return SweepStats(**{k: d[k] for k in _STAT_KEYS if k in d})


def _d_inf(meta: GraphMeta, cfg: SweepConfig) -> int:
    return meta.d_inf_ard if cfg.method == "ard" else meta.d_inf_prd


def _discharge_all(meta: GraphMeta, state: FlowState, cfg: SweepConfig,
                   ghost_d: jax.Array, stage_cap):
    """Discharge all regions of a parallel sweep through the batched entry
    points (``ard_discharge_batched``/``prd_discharge_batched``).
    Per-region results are bit-identical to the vmapped scalar path;
    ``DischargeResult.engine_launches`` is the sweep's global dispatch
    count.
    """
    intra = intra_mask(state)
    kw = dict(nbr_local=state.nbr_local, rev_slot=state.rev_slot,
              intra=intra, emask=state.emask, vmask=state.vmask,
              max_iters=cfg.engine_max_iters, backend=cfg.engine_backend,
              chunk_iters=cfg.engine_chunk_iters)
    if cfg.method == "ard":
        return ard_discharge_batched(
            state.cf, state.sink_cf, state.excess, ghost_d,
            d_inf=meta.d_inf_ard, stage_cap=stage_cap, **kw)
    return prd_discharge_batched(
        state.cf, state.sink_cf, state.excess, state.d, ghost_d,
        d_inf=meta.d_inf_prd, **kw)


def _apply_cross_flow(state: FlowState, out_push: jax.Array,
                      accept: jax.Array) -> FlowState:
    """Apply fused boundary flow through the flat cross-arc table.

    ``accept[x]`` — Alg. 2 line 5 decision for cross arc x.  Accepted flow
    raises the receiver's reverse residual + excess; rejected flow is
    refunded to the sender (residual and excess), matching the paper's
    "do not allow the flow to cross the boundary in one of the directions".
    The flat scatter indices are the build-time precomputed
    ``cross_*_arc``/``cross_*_vtx`` fields of ``FlowState`` — static
    topology, so no jitted sweep rebuilds them from ``cross_src``/
    ``cross_dst``.
    """
    K, V, E = state.cf.shape
    delta = out_push.reshape(-1)[state.cross_src_arc]
    acc = jnp.where(accept, delta, 0)
    rej = delta - acc
    flat = state.cf.reshape(-1)
    flat = flat.at[state.cross_dst_arc].add(acc, mode="drop")
    flat = flat.at[state.cross_src_arc].add(rej, mode="drop")
    cf = flat.reshape(K, V, E)
    eflat = state.excess.reshape(-1)
    eflat = eflat.at[state.cross_dst_vtx].add(acc, mode="drop")
    eflat = eflat.at[state.cross_src_vtx].add(rej, mode="drop")
    excess = eflat.reshape(K, V)
    return state.replace(cf=cf, excess=excess)


@partial(jax.jit, static_argnums=(0, 2))
def parallel_sweep(meta: GraphMeta, state: FlowState, cfg: SweepConfig,
                   sweep_idx: jax.Array):
    """One sweep of Alg. 2: concurrent discharges + label/flow fusion."""
    global _TRACE_COUNT
    _TRACE_COUNT += 1
    ghost_d = gather_ghost_labels(state)
    stage_cap = jnp.where(
        jnp.asarray(cfg.partial_discharge),
        jnp.maximum(sweep_idx - 1, -1).astype(_I32),
        _I32(meta.d_inf_ard))
    res = _discharge_all(meta, state, cfg, ghost_d, stage_cap)
    new = state.replace(cf=res.cf, sink_cf=res.sink_cf, excess=res.excess,
                        d=jnp.maximum(state.d, res.d),
                        flow_to_t=state.flow_to_t + res.sink_pushed.sum())
    # ---- fusion (Alg. 2 lines 4-6) ----
    src, dst = new.cross_src, new.cross_dst
    du = new.d[src[:, 0], src[:, 1]]
    dv = new.d[dst[:, 0], dst[:, 1]]
    accept = dv <= du + 1          # alpha(v, u): reverse arc stays valid
    new = _apply_cross_flow(new, res.out_push, accept)
    if cfg.use_boundary_relabel and cfg.method == "ard":
        new = heuristics.boundary_relabel(meta, new)
    if cfg.use_global_gap:
        new = global_gap(meta, new, ard=cfg.method == "ard")
    return (new, res.engine_iters.sum(), res.stages.sum(),
            res.engine_launches.sum())


@partial(jax.jit, static_argnums=(0, 2))
def sequential_sweep(meta: GraphMeta, state: FlowState, cfg: SweepConfig,
                     sweep_idx: jax.Array):
    """One sweep of Alg. 1: discharge regions one by one, apply immediately.

    Regions with no active vertex are skipped (paper Sec. 5.3) — the
    discharge engine exits in O(1) for them and the page-I/O accounting in
    ``solve`` only counts discharged regions.
    """
    global _TRACE_COUNT
    _TRACE_COUNT += 1
    K, V, E = state.cf.shape
    d_inf = _d_inf(meta, cfg)
    stage_cap_all = jnp.where(
        jnp.asarray(cfg.partial_discharge),
        jnp.maximum(sweep_idx - 1, -1).astype(_I32),
        _I32(meta.d_inf_ard))
    # sweep-invariant: depends only on static topology, so hoist it out of
    # the per-region loop (ghost labels change per discharge and stay inside)
    intra = intra_mask(state)

    def body(k, carry):
        state, iters, stages, launches, discharged = carry
        sl = lambda a: jax.lax.dynamic_index_in_dim(a, k, 0, keepdims=False)
        # ghost labels only for the arcs of region k (a [V,E] gather) — the
        # other K-1 regions' ghosts are never read by this discharge, so
        # gathering the full [K,V,E] table per region iteration is K x
        # wasted label traffic
        ghost_k = state.d[sl(state.nbr_region), sl(state.nbr_local)]
        active = ((sl(state.excess) > 0) & (sl(state.d) < d_inf)
                  & sl(state.vmask)).any()

        def run(state):
            if cfg.method == "ard":
                res = ard_discharge_one(
                    sl(state.cf), sl(state.sink_cf), sl(state.excess),
                    ghost_k, nbr_local=sl(state.nbr_local),
                    rev_slot=sl(state.rev_slot), intra=sl(intra),
                    emask=sl(state.emask), vmask=sl(state.vmask),
                    d_inf=meta.d_inf_ard, stage_cap=stage_cap_all,
                    max_iters=cfg.engine_max_iters,
                    backend=cfg.engine_backend,
                    chunk_iters=cfg.engine_chunk_iters)
            else:
                res = prd_discharge_one(
                    sl(state.cf), sl(state.sink_cf), sl(state.excess),
                    sl(state.d), ghost_k, nbr_local=sl(state.nbr_local),
                    rev_slot=sl(state.rev_slot), intra=sl(intra),
                    emask=sl(state.emask), vmask=sl(state.vmask),
                    d_inf=meta.d_inf_prd, max_iters=cfg.engine_max_iters,
                    backend=cfg.engine_backend,
                    chunk_iters=cfg.engine_chunk_iters)
            upd = lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v, k, 0)
            st = state.replace(
                cf=upd(state.cf, res.cf),
                sink_cf=upd(state.sink_cf, res.sink_cf),
                excess=upd(state.excess, res.excess),
                d=upd(state.d, jnp.maximum(sl(state.d), res.d)),
                flow_to_t=state.flow_to_t + res.sink_pushed)
            # apply this region's boundary pushes immediately (no conflicts)
            out_push = jnp.zeros_like(state.cf).at[k].set(res.out_push)
            src = st.cross_src
            mine = src[:, 0] == k
            st = _apply_cross_flow(st, out_push, accept=mine)
            if cfg.use_global_gap:
                st = global_gap(meta, st, ard=cfg.method == "ard")
            return st, res.engine_iters, res.stages, res.engine_launches

        def skip(state):
            z = jnp.zeros((), _I32)
            return state, z, z, z

        state, it, sg, ln = jax.lax.cond(active, run, skip, state)
        return (state, iters + it, stages + sg, launches + ln,
                discharged + active.astype(_I32))

    z = jnp.zeros((), _I32)
    state, iters, stages, launches, discharged = jax.lax.fori_loop(
        0, K, body, (state, z, z, z, z))
    if cfg.use_boundary_relabel and cfg.method == "ard":
        state = heuristics.boundary_relabel(meta, state)
    return state, iters, stages, launches, discharged


def num_active(meta: GraphMeta, state: FlowState, cfg: SweepConfig) -> jax.Array:
    return state.active(_d_inf(meta, cfg)).sum()


def sweep_bound(meta: GraphMeta, cfg: SweepConfig) -> int:
    """Theoretical sweep bound: 2|B|^2 + 1 for ARD, 2 n^2 for PRD."""
    if cfg.method == "ard":
        return 2 * meta.num_boundary * meta.num_boundary + 1
    return 2 * meta.num_vertices * meta.num_vertices


def _page_and_msg_bytes(meta):
    # bytes of one region page (cf + labels + excess + topology) — paper's
    # streaming unit; boundary message = flow + label per cross arc.  Costed
    # per value family at the build-selected storage dtypes: the [V,E] page
    # is one flow array (cf), two int32 topology arrays (nbr/rev) and one
    # mask (emask); the [V] vectors are two flow (sink_cf/excess), one label
    # (d) and one mask (vmask).  All-int32 this is the historical
    # ``16*V*E + 16*V`` and 8 bytes/cross-arc exactly.  Computable from the
    # meta alone so the streaming executor can account pages without ever
    # materializing a FlowState.
    fb = np.dtype(meta.flow_dtype).itemsize
    lb = np.dtype(meta.label_dtype).itemsize
    mb = 1 if (fb < 4 or lb < 4) else 4
    V, E = meta.region_size, meta.max_degree
    page_bytes = (fb + 2 * 4 + mb) * V * E + (2 * fb + lb + mb) * V
    return page_bytes, (fb + lb) * meta.num_cross_arcs


def _device_stats(host, syncs, max_sweeps, R, page_bytes, msg_bytes,
                  seed_syncs=0):
    """SweepStats from a fetched device-resident carry.

    The carry holds ABSOLUTE counters (a checkpoint-resumed ``carry0``
    seeds them with the interrupted solve's values), so the reconstruction
    is complete without seed accumulation; only ``host_syncs`` counts per
    incarnation and needs the checkpoint's total added.
    """
    idx, it, sg, ln, dc, fr, ar, n_act = host
    stats = SweepStats()
    done = int(idx)
    stats.host_syncs = seed_syncs + syncs
    stats.sweeps = done
    stats.engine_iters = int(it)
    stats.ard_stages = int(sg)
    stats.engine_launches = int(ln)
    stats.regions_discharged = int(dc)
    stats.page_bytes = int(dc) * page_bytes
    stats.boundary_bytes = done * msg_bytes
    first = max(0, done - R)
    stats.flow_curve = [int(fr[j % R]) for j in range(first, done)]
    stats.active_curve = [int(ar[j % R]) for j in range(first, done)]
    stats.converged = int(n_act) == 0
    if int(n_act) == 0 and done < max_sweeps:
        stats.active_curve.append(int(n_act))   # the terminal 0 the host
        #                                         loop records on its exit
    return stats


def _solve_device_resident(meta: GraphMeta, state: FlowState,
                           cfg: SweepConfig, ex, *, fp: str = "",
                           checkpoint=None, ckpt=None, on_sweep=None):
    """Device-resident solve: one kernel-program chain per host sync.

    The whole sweep loop — discharge, fusion, gap heuristic, convergence
    check and statistics accumulation — runs inside the generic
    ``executor.run_device`` loop; the host is re-entered once per
    ``cfg.host_sync_every`` sweeps (default: only at convergence or the
    sweep cap, i.e. exactly one ``device_get`` per solve).  Bit-exact with
    the host loop on state and counters; the flow/active curves live in
    fixed-size device rings, so only the last ``stats_ring_size`` sweeps
    of the curves survive very long solves.

    Checkpoints (``checkpoint``: a ``resilience.CheckpointPolicy``) are
    captured at the host-sync boundaries — the only host re-entry this
    driver has, so ``cfg.host_sync_every`` bounds the checkpoint cadence
    from below.  ``ckpt`` (a verified ``resilience.SolveCheckpoint``)
    resumes: counters and curve rings are rebuilt into the loop carry, so
    the continued solve is bit-exact with an uninterrupted one.
    """
    bound = sweep_bound(meta, cfg)
    max_sweeps = cfg.max_sweeps if cfg.max_sweeps is not None else bound
    R = cfg.stats_ring_size
    page_bytes, msg_bytes = _page_and_msg_bytes(meta)

    carry0 = None
    seed_syncs = 0
    degraded: list = []
    if ckpt is not None:
        state = _res.restore_state(state, ckpt.payload)
        seed = stats_from_dict(ckpt.stats)
        seed_syncs = seed.host_syncs
        degraded = list(seed.degraded)
        done0 = seed.sweeps
        # rebuild the curve rings: ring slot j % R holds sweep j's value
        # for the last min(done0, R) sweeps (older slots are never read);
        # the active curve is trimmed to the flow curve's length to drop
        # the terminal 0 a converged checkpoint may carry
        flow_curve = seed.flow_curve
        active_curve = seed.active_curve[:len(flow_curve)]
        first = max(0, done0 - R)
        fr = np.zeros((R,), np.int32)
        ar = np.zeros((R,), np.int32)
        for j in range(first, done0):
            fr[j % R] = flow_curve[j - first]
            ar[j % R] = active_curve[j - first]
        carry0 = (jnp.asarray(done0, _I32),
                  jnp.asarray(seed.engine_iters, _I32),
                  jnp.asarray(seed.ard_stages, _I32),
                  jnp.asarray(seed.engine_launches, _I32),
                  jnp.asarray(seed.regions_discharged, _I32),
                  jnp.asarray(fr), jnp.asarray(ar),
                  jnp.asarray(int(ckpt.payload["n_act"]), _I32))

    ckpt_sync = None
    if checkpoint is not None:
        last_saved = [ckpt.sweeps if ckpt is not None else 0]

        def ckpt_sync(st, host, syncs):
            done, running = ex.progress(host, max_sweeps)
            if running and done - last_saved[0] < checkpoint.every:
                return
            stats = _device_stats(host, syncs, max_sweeps, R, page_bytes,
                                  msg_bytes, seed_syncs=seed_syncs)
            stats.degraded = list(degraded)
            payload = _res.state_payload(st)
            payload["n_act"] = np.asarray(host[-1], np.int32)
            _res.save_checkpoint(checkpoint.directory, _res.SolveCheckpoint(
                fingerprint=fp, route="device", sweeps=done,
                payload=payload, stats=stats_to_dict(stats),
                flow_offset=checkpoint.flow_offset))
            last_saved[0] = done

    on_sync = ckpt_sync
    if on_sweep is not None:
        # the device route's sweep-boundary hook fires at the
        # host_sync_every boundaries — the only host re-entries it has;
        # the checkpoint capture runs FIRST so a hook that aborts the
        # solve (the serving tier's deadline enforcement) leaves the
        # boundary durably checkpointed
        def on_sync(st, host, syncs):
            if ckpt_sync is not None:
                ckpt_sync(st, host, syncs)
            on_sweep(st, int(host[0]))

    state, host, syncs = _executor.run_device(
        ex, state, max_sweeps, cfg.host_sync_every, carry0=carry0,
        on_sync=on_sync)
    stats = _device_stats(host, syncs, max_sweeps, R, page_bytes, msg_bytes,
                          seed_syncs=seed_syncs)
    stats.degraded = list(degraded)
    return state, stats


def solve(meta: GraphMeta, state: FlowState, cfg: SweepConfig | None = None,
          *, warm: bool = False, on_sweep=None, checkpoint=None,
          resume_from=None, salt: str = ""):
    """Run sweeps until no active vertex remains (maximum preflow reached).

    ``warm`` — continue from the given state *as is*: its preflow (``cf``/
    ``excess``/``sink_cf``/``flow_to_t``) and labels are taken as the
    starting point, so a re-solve after a warm-start update
    (``graph.apply_update``) picks up from the previous optimum instead of
    from zero.  The caller owns label validity (the session front-end's
    ``warm_labels`` policy).  With ``warm=False`` (the cold entry) labels
    are (re-)initialized to the paper's ``Init`` — idempotent with
    ``graph.init_labels``, so pre-initialized callers are unaffected.

    ``on_sweep(state, sweeps_done)`` — optional sweep-boundary hook (tests
    use it to check the preflow/labeling invariants mid-solve; the serving
    tier enforces request deadlines with it).  On the host loop it fires
    at every sweep boundary; on the device-resident driver at the
    ``host_sync_every`` boundaries (the only host re-entries it has —
    requesting it with ``host_sync_every=None`` is an error, since the
    hook could never fire before the solve completes).

    ``checkpoint`` — a ``resilience.CheckpointPolicy``: capture a
    resumable ``SolveCheckpoint`` atomically on disk at sweep boundaries
    (host loop: every ``checkpoint.every`` sweeps + the final boundary;
    device-resident: at the ``host_sync_every`` boundaries under the same
    cadence).  ``resume_from`` — a ``SolveCheckpoint`` or a checkpoint
    directory (latest wins): continue the interrupted solve BIT-EXACTLY —
    flow, labels, sweeps and engine counters match the uninterrupted run
    (``host_syncs`` honestly counts both incarnations' syncs).  A
    checkpoint from different math (method/heuristics/layout) is rejected
    with ``CheckpointMismatchError``; engine-backend and driver knobs are
    deliberately NOT part of the identity (every route/rung is
    bit-identical), so cross-driver resume is allowed.  ``salt`` — extra
    fingerprint input (the session front-end's layout digest); a given
    ``checkpoint.salt`` wins.

    Returns (state, SweepStats).  Two drivers, bit-identical results, both
    thin composition over the generic executor loop (``core.executor``):

    * host loop (default) — ``executor.run_host``: each sweep is one
      jitted device program with one host sync after it; the paper's
      statistics (sweeps, I/O bytes) are accumulated between programs,
      exactly like the streaming solver accounts disk I/O between region
      loads;
    * ``cfg.device_resident`` — ``executor.run_device``: the loop itself
      moves into a ``lax.while_loop``; the host is re-entered once per
      ``cfg.host_sync_every`` sweeps (default: once per solve).
    """
    cfg = cfg or SweepConfig()
    _executor.LocalExecutor.validate(cfg)
    ex = _executor.LocalExecutor(meta, cfg)
    if checkpoint is not None:
        salt = checkpoint.salt
    fp = _res.solve_fingerprint(meta, cfg, salt)
    ckpt = _res.resolve_resume(resume_from, fp)
    if ckpt is None and not warm:
        state = state.replace(d=jnp.zeros_like(state.d))
    if cfg.device_resident:
        if on_sweep is not None and cfg.host_sync_every is None:
            raise ValueError(
                "on_sweep needs a host boundary to fire from; the "
                "device-resident driver only has them at host_sync_every "
                "boundaries (set cfg.host_sync_every), not inside the "
                "lax.while_loop")
        state, stats = _solve_device_resident(
            meta, state, cfg, ex, fp=fp, checkpoint=checkpoint, ckpt=ckpt,
            on_sweep=on_sweep)
    else:
        state, stats = _solve_host(
            meta, state, cfg, ex, on_sweep=on_sweep, fp=fp,
            checkpoint=checkpoint, ckpt=ckpt)
    stats.num_boundary = meta.num_boundary
    if cfg.method != "ard":
        stats.ard_stages = None
    return state, stats


def _solve_host(meta: GraphMeta, state: FlowState, cfg: SweepConfig, ex, *,
                on_sweep=None, fp: str = "", checkpoint=None, ckpt=None):
    """Host-loop solve with checkpoint capture at every sweep boundary."""
    bound = sweep_bound(meta, cfg)
    max_sweeps = cfg.max_sweeps if cfg.max_sweeps is not None else bound
    page_bytes, msg_bytes = _page_and_msg_bytes(meta)

    seed = None
    start = 0
    if ckpt is not None:
        state = _res.restore_state(state, ckpt.payload)
        seed = stats_from_dict(ckpt.stats)
        # drop the terminal 0 a converged checkpoint may carry in its
        # active curve — the resumed loop's entry check re-records it
        seed.active_curve = seed.active_curve[:len(seed.flow_curve)]
        start = ckpt.sweeps

    def build(trace, active_pre, syncs, sweeps):
        """Accumulated stats = checkpoint seed + this incarnation's trace."""
        stats = SweepStats() if seed is None else stats_from_dict(
            stats_to_dict(seed))
        stats.host_syncs += syncs
        stats.sweeps = sweeps
        stats.active_curve = stats.active_curve + active_pre
        stats.flow_curve = list(stats.flow_curve)
        stats.degraded = list(stats.degraded)
        for n_act, flow, it, sg, ln, dc in trace:
            stats.engine_iters += it
            stats.ard_stages += sg
            stats.engine_launches += ln
            stats.regions_discharged += dc
            stats.page_bytes += dc * page_bytes
            stats.boundary_bytes += msg_bytes
            stats.flow_curve.append(flow)
        return stats

    on_obs = None
    last_saved = [start]
    if checkpoint is not None:
        def on_obs(st, idx, trace, active_pre):
            if idx - last_saved[0] < checkpoint.every:
                return
            _save_host_ckpt(st, idx, trace, active_pre)

        def _save_host_ckpt(st, idx, trace, active_pre):
            # syncs so far this incarnation: 1 entry check + 1 per sweep
            stats = build(trace, active_pre, 1 + len(trace), idx)
            stats.converged = bool(trace and trace[-1][0] == 0)
            payload = _res.state_payload(st)
            payload["n_act"] = np.asarray(
                trace[-1][0] if trace else 0, np.int32)
            _res.save_checkpoint(checkpoint.directory, _res.SolveCheckpoint(
                fingerprint=fp, route="host", sweeps=idx, payload=payload,
                stats=stats_to_dict(stats),
                flow_offset=checkpoint.flow_offset))
            last_saved[0] = idx

    state, trace, active_pre, syncs, sweeps = _executor.run_host(
        ex, state, max_sweeps, on_sweep=on_sweep, start=start, on_obs=on_obs)
    stats = build(trace, active_pre, syncs, sweeps)
    if trace:
        stats.converged = trace[-1][0] == 0
    elif active_pre:
        stats.converged = active_pre[-1] == 0
    elif seed is not None:
        stats.converged = bool(seed.converged)
    if checkpoint is not None and sweeps > last_saved[0]:
        _save_host_ckpt(state, sweeps, trace, active_pre)
    return state, stats


@jax.jit
def extract_cut_fixpoint(cf: jax.Array, emask: jax.Array, sink_cf: jax.Array,
                         vmask: jax.Array, nbr_region: jax.Array,
                         nbr_local: jax.Array) -> jax.Array:
    """The residual-reachability fixpoint behind :func:`extract_cut`.

    Takes only the six arrays it reads, so its compile cache is keyed on
    (K, V, E), dtypes and shardings: instances that differ only in their
    cross-arc count share one executable.
    """
    _bump_trace()

    def body(carry):
        reach, _ = carry
        nbr_reach = gather_at_nbr(reach, nbr_region, nbr_local)
        ok = (cf > 0) & emask & nbr_reach
        new = (sink_cf > 0) | ok.any(axis=2)
        new = (new | reach) & vmask
        return new, (new != reach).any()

    init = (sink_cf > 0) & vmask
    reach, _ = jax.lax.while_loop(lambda c: c[1], body,
                                  (init, jnp.asarray(True)))
    return reach


def extract_cut(meta: GraphMeta, state: FlowState) -> jax.Array:
    """Minimum cut (bool[K,V]: True = sink side T = {v : v -> t in G_f}).

    Global residual-reachability fixpoint — the paper's final labeling
    sweeps, collapsed into one exact computation.
    """
    return extract_cut_fixpoint(state.cf, state.emask, state.sink_cf,
                                state.vmask, state.nbr_region,
                                state.nbr_local)


def cut_value(meta: GraphMeta, state0: FlowState, sink_side: jax.Array) -> jax.Array:
    """Cost of the cut (C, C̄) with C̄ = sink_side, in the *initial* network.

    cost = sum_{v in C̄} e(v) + sum_{v in C} sink_cap(v)
         + sum of cap(u,v) over arcs u in C, v in C̄.

    ``sink_side`` moves to ``state0``'s devices first: a cut extracted
    from region-sharded state is priced where the initial network lives.
    """
    sink_side = jax.device_put(sink_side, state0.vmask.sharding)
    src_side = ~sink_side & state0.vmask
    e_term = jnp.sum(jnp.where(sink_side & state0.vmask, state0.excess, 0),
                     dtype=_I32)
    t_term = jnp.sum(jnp.where(src_side, state0.sink_cf, 0), dtype=_I32)
    nbr_sink = gather_at_nbr(sink_side, state0.nbr_region, state0.nbr_local)
    arc_cut = (src_side[:, :, None] & nbr_sink & state0.emask)
    c_term = jnp.sum(jnp.where(arc_cut, state0.cf, 0), dtype=_I32)
    return e_term + t_term + c_term
