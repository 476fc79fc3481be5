"""The streaming sweep: load -> discharge -> write back -> exchange.

One sweep visits regions 0..K-1 in order, exactly Alg. 1, except that a
region's [V,E] slabs live on disk between visits and the inter-visit
state is the |B|-sized boundary layer:

    for k in 0..K-1:
        if region k has no active vertex: continue        # zero I/O
        topo, flow = store.load(k)                        # staged in
        store.prefetch(next active region)                # overlaps ...
        apply pend (incoming cross flow) + e_B            #  ... compute
        ghost   = labels of k's neighbours (own d + d_B)
        result  = fused per-region discharge (device)     # same engine,
        flow_to_t += sink_pushed                          #  same dtypes,
        pend/e_B += out_push over k's out arcs            #  same chunking
        d_B/e_B[k's boundary] = new labels/excess
        store.writeback(k, new flow family)               # staged out

Bit-exactness vs the resident ``sequential_sweep`` holds because (a) the
per-region discharge is the SAME jitted operator on bit-identical
inputs — the ghost gather differs only at emask-invalid slots, which the
engine never reads; (b) boundary pushes apply to the receiver before its
visit, matching the immediate ``_apply_cross_flow``; (c) the skip test
``region_active`` equals the resident ``any(active)`` per region (see
``boundary.py``).  The conformance suite asserts this per state field
across ard/prd x engine backends.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import numpy as np

from repro.core import executor as _executor
from repro.core import resilience as _res
from repro.core.ard import ard_discharge_one
from repro.core.prd import prd_discharge_one
from repro.core.sweep import (SweepStats, _page_and_msg_bytes, stats_from_dict,
                              stats_to_dict, sweep_bound)
from repro.stream.boundary import BoundaryPlan, BoundaryState
from repro.stream.store import FLOW_FIELDS, StreamStore

# traces of the jitted per-region discharge — one per (shape, dtypes,
# config); every staged region of every sweep reuses it.  Counted into
# ``Solver.cache_info`` with the other routes' programs.
_TRACE_COUNT = 0


def trace_count() -> int:
    return _TRACE_COUNT


def _bump_trace() -> None:
    global _TRACE_COUNT
    _TRACE_COUNT += 1


def _make_discharge(meta, cfg):
    """One jitted [V,E] discharge shared by every region of the solve."""
    import jax
    import jax.numpy as jnp

    d_inf = meta.d_inf_ard if cfg.method == "ard" else meta.d_inf_prd

    def fn(cf, sink_cf, excess, d, ghost, stage_cap,
           nbr_local, rev_slot, intra, emask, vmask):
        _bump_trace()
        kw = dict(nbr_local=nbr_local, rev_slot=rev_slot, intra=intra,
                  emask=emask, vmask=vmask, d_inf=d_inf,
                  max_iters=cfg.engine_max_iters,
                  backend=cfg.engine_backend,
                  chunk_iters=cfg.engine_chunk_iters)
        if cfg.method == "ard":
            res = ard_discharge_one(cf, sink_cf, excess, ghost,
                                    stage_cap=stage_cap, **kw)
        else:
            res = prd_discharge_one(cf, sink_cf, excess, d, ghost, **kw)
        return (res.cf, res.sink_cf, res.excess, jnp.maximum(d, res.d),
                res.out_push, res.sink_pushed, res.engine_iters, res.stages,
                res.engine_launches)

    return jax.jit(fn)


@dataclass
class StreamState:
    """Everything the host loop threads through a streaming solve.

    NOT a ``FlowState``: the resident footprint is the boundary layer
    plus the store's ``max_resident`` region slabs.  Duck-types the two
    surfaces the generic drivers touch (``num_active``; the state slot
    of ``executor.run_host``/the fault hook).
    """

    meta: Any
    cfg: Any
    store: StreamStore
    plan: BoundaryPlan
    bnd: BoundaryState
    _discharge: Any = None
    _sweep_stats: dict = field(default_factory=dict)

    def __post_init__(self):
        if self._discharge is None:
            self._discharge = _make_discharge(self.meta, self.cfg)

    @property
    def d_inf(self) -> int:
        return self.meta.d_inf_ard if self.cfg.method == "ard" \
            else self.meta.d_inf_prd

    def num_active(self) -> int:
        return self.bnd.num_active(self.d_inf)

    def payload(self) -> dict:
        """Checkpoint payload: boundary layer + the pool version vector.
        The region slabs themselves are already durable in the pool."""
        p = self.bnd.payload()
        p["versions"] = self.store.versions.copy()
        return p

    def restore(self, payload: dict) -> None:
        self.bnd.restore(payload)
        self.store.attach(payload["versions"])


def _materialize_region(ss: StreamState, k: int) -> tuple[dict, dict]:
    """Stage region k in with its pending cross flow applied.

    Returns ``(topo, flow)`` where ``flow`` is a fresh copy (the resident
    cache is never mutated in place): residuals get the parked ``pend``
    increments, boundary excess syncs from the authoritative ``e_B``.
    """
    topo, flow0 = ss.store.load(k)
    flow = {f: flow0[f].copy() for f in FLOW_FIELDS}
    plan = ss.plan
    ix = plan.in_x[k]
    if len(ix):
        np.add.at(flow["cf"], (plan.in_l[k], plan.in_s[k]), ss.bnd.pend[ix])
        ss.bnd.pend[ix] = 0
    bl = plan.bnd_local[k]
    if len(bl):
        flow["excess"][bl] = ss.bnd.e_B[plan.bnd_bid[k]]
    return topo, flow


def _next_active(ss: StreamState, k: int) -> int | None:
    """First region after k the sweep will visit (active regions only
    gain activity until discharged, so this prediction cannot go stale —
    at worst an intermediate region turns active first and the prefetch
    is consumed one visit later than planned)."""
    for j in range(k + 1, ss.meta.num_regions):
        if ss.bnd.region_active(j, ss.plan, ss.d_inf):
            return j
    return None


def stream_sweep(ss: StreamState, idx) -> tuple[StreamState, tuple]:
    """One full sweep over staged regions; the ``sweep_host`` body of
    ``StreamingExecutor``.  Returns ``(ss, obs)`` with obs =
    ``(n_active, flow_to_t, engine_iters, ard_stages, engine_launches,
    regions_discharged, staged_in_delta, staged_out_delta)`` — the first
    six exactly the resident host loop's observation tuple.
    """
    import jax

    meta, cfg, plan, bnd = ss.meta, ss.cfg, ss.plan, ss.bnd
    d_inf = ss.d_inf
    in0 = ss.store.staged_in_bytes
    out0 = ss.store.staged_out_bytes
    iters = stages = launches = discharged = 0
    sweep_idx = int(idx)
    stage_cap = np.int32(max(sweep_idx - 1, -1)) if cfg.partial_discharge \
        else np.int32(meta.d_inf_ard)

    for k in range(meta.num_regions):
        if not bnd.region_active(k, plan, d_inf):
            continue
        topo, flow = _materialize_region(ss, k)
        ss.store.prefetch(_next_active(ss, k))
        own = topo["nbr_region"] == k
        intra = own & topo["emask"]
        # ghost labels: own region's labels through nbr_local (intra
        # slots), the boundary layer's labels on cross slots; invalid
        # slots are never read by the engine (emask-masked)
        ghost = flow["d"][topo["nbr_local"]]
        ol, os_, ox = plan.out_l[k], plan.out_s[k], plan.out_x[k]
        if len(ox):
            ghost[ol, os_] = bnd.d_B[plan.out_dst_bid[k]]
        out = ss._discharge(flow["cf"], flow["sink_cf"], flow["excess"],
                            flow["d"], ghost, stage_cap,
                            topo["nbr_local"], topo["rev_slot"], intra,
                            topo["emask"], topo["vmask"])
        (cf, sink_cf, excess, d, out_push, sink_pushed, it, sg, ln) = (
            np.asarray(a) for a in jax.device_get(out))
        bnd.flow_to_t += int(sink_pushed)
        iters += int(it)
        stages += int(sg)
        launches += int(ln)
        discharged += 1
        if len(ox):
            deltas = out_push[ol, os_]
            np.add.at(bnd.pend, ox, deltas)
            np.add.at(bnd.e_B, plan.out_dst_bid[k], deltas)
        new_flow = {"cf": cf, "sink_cf": sink_cf, "excess": excess, "d": d}
        bnd.absorb_region(plan, k, new_flow, topo["is_boundary"],
                          topo["vmask"], d_inf)
        ss.store.writeback(k, new_flow)

    obs = (bnd.num_active(d_inf), bnd.flow_to_t, iters, stages, launches,
           discharged, ss.store.staged_in_bytes - in0,
           ss.store.staged_out_bytes - out0)
    return ss, obs


# --------------------------------------------------------------------------
# opening a stream (spill) and closing one (assemble)
# --------------------------------------------------------------------------

def open_stream(meta, state, cfg, *, spill_dir=None, max_resident_regions=2,
                prefetch=True, cold_labels=True) -> StreamState:
    """Spill a built ``FlowState`` into a fresh pool, one region at a time.

    The session front-end's entry: the state is already resident there,
    so this is a staging pass, not a memory win — the win is every sweep
    after it.  For instances that never fit, build shard-wise instead
    (``repro.stream.build.build_stream``).  ``cold_labels`` zeroes ``d``
    during the spill (the cold-start ``Init``), saving the separate
    device-side zeroing pass.
    """
    from repro.core import graph as _graph
    from repro.stream.boundary import make_plan

    store = StreamStore(meta.num_regions, spill_dir,
                        max_resident=max_resident_regions, prefetch=prefetch)
    plan = make_plan(np.asarray(state.cross_src), np.asarray(state.cross_dst),
                     np.asarray(state.cross_valid), meta.num_regions)
    assert plan.num_boundary == meta.num_boundary, \
        (plan.num_boundary, meta.num_boundary)
    kd = meta.kernel_dtypes
    bnd = BoundaryState.zeros(plan, kd.label_np, kd.flow_np)
    ss = StreamState(meta=meta, cfg=cfg, store=store, plan=plan, bnd=bnd)
    flow_to_t = int(np.asarray(state.flow_to_t))
    d_inf = ss.d_inf
    for r in range(meta.num_regions):
        topo = _graph.extract_region(state, r, _graph.REGION_TOPO_FIELDS)
        flow = _graph.extract_region(state, r, _graph.REGION_FLOW_FIELDS)
        if cold_labels:
            flow["d"] = np.zeros_like(flow["d"])
        store.put_region(r, topo, flow)
        bnd.absorb_region(plan, r, flow, topo["is_boundary"], topo["vmask"],
                          d_inf)
    bnd.flow_to_t = flow_to_t
    return ss


def assemble_state(ss: StreamState, state):
    """Reassemble a resident ``FlowState`` from the streamed shards (cut
    extraction / certificate checks).  Pending cross flow is flushed into
    each region as it is staged, so the result is exact even when the
    solve stopped at the sweep cap."""
    import jax.numpy as jnp

    from repro.core import graph as _graph

    for r in range(ss.meta.num_regions):
        _, flow = _materialize_region(ss, r)
        state = _graph.insert_region(state, r, flow)
    return state.replace(flow_to_t=jnp.asarray(ss.bnd.flow_to_t,
                                               state.flow_to_t.dtype))


# --------------------------------------------------------------------------
# the solve driver (mirrors sweep._solve_host, 8-tuple observations)
# --------------------------------------------------------------------------

def solve_stream(ss: StreamState, *, on_sweep=None, checkpoint=None,
                 resume_from=None, salt: str = ""):
    """Run streamed sweeps to convergence; returns ``(ss, SweepStats)``.

    Checkpoints ride the existing ``CheckpointPolicy`` at sweep
    boundaries with route ``"stream"``: the payload is the |B|-sized
    boundary layer + the pool's per-region version vector — the region
    slabs are already durable in the pool (a streaming solve IS a
    sequence of region checkpoints), so capture cost is O(|B|), not
    O(n).  Resume re-attaches the pool at the checkpointed versions and
    is bit-exact, including across a SIGKILL mid-sweep (newer orphan
    versions the dead process published are pruned on the next
    writeback).
    """
    meta, cfg = ss.meta, ss.cfg
    _executor.StreamingExecutor.validate(cfg)
    ex = _executor.StreamingExecutor(meta, cfg)
    if checkpoint is not None:
        salt = checkpoint.salt
    fp = _res.solve_fingerprint(meta, cfg, salt)
    ckpt = _res.resolve_resume(resume_from, fp)
    bound = sweep_bound(meta, cfg)
    max_sweeps = cfg.max_sweeps if cfg.max_sweeps is not None else bound
    page_bytes, msg_bytes = _page_and_msg_bytes(meta)

    seed = None
    start = 0
    if ckpt is not None:
        ss.restore(ckpt.payload)
        seed = stats_from_dict(ckpt.stats)
        seed.active_curve = seed.active_curve[:len(seed.flow_curve)]
        start = ckpt.sweeps

    def build(trace, active_pre, syncs, sweeps):
        stats = SweepStats() if seed is None else stats_from_dict(
            stats_to_dict(seed))
        stats.host_syncs += syncs
        stats.sweeps = sweeps
        stats.active_curve = stats.active_curve + active_pre
        stats.flow_curve = list(stats.flow_curve)
        stats.degraded = list(stats.degraded)
        for n_act, flow, it, sg, ln, dc, sin, sout in trace:
            stats.engine_iters += it
            stats.ard_stages += sg
            stats.engine_launches += ln
            stats.regions_discharged += dc
            stats.page_bytes += dc * page_bytes
            stats.boundary_bytes += msg_bytes
            stats.staged_in_bytes += sin
            stats.staged_out_bytes += sout
            stats.flow_curve.append(flow)
        stats.num_boundary = meta.num_boundary
        return stats

    on_obs = None
    last_saved = [start]
    if checkpoint is not None:
        def on_obs(st, idx, trace, active_pre):
            if idx - last_saved[0] < checkpoint.every:
                return
            _save_ckpt(st, idx, trace, active_pre)

        def _save_ckpt(st, idx, trace, active_pre):
            stats = build(trace, active_pre, 1 + len(trace), idx)
            stats.converged = bool(trace and trace[-1][0] == 0)
            payload = st.payload()
            payload["n_act"] = np.asarray(
                trace[-1][0] if trace else 0, np.int32)
            _res.save_checkpoint(checkpoint.directory, _res.SolveCheckpoint(
                fingerprint=fp, route="stream", sweeps=idx, payload=payload,
                stats=stats_to_dict(stats),
                flow_offset=checkpoint.flow_offset))
            st.store.protect(payload["versions"])
            last_saved[0] = idx

    ss, trace, active_pre, syncs, sweeps = _executor.run_host(
        ex, ss, max_sweeps, on_sweep=on_sweep, start=start, on_obs=on_obs)
    stats = build(trace, active_pre, syncs, sweeps)
    if trace:
        stats.converged = trace[-1][0] == 0
    elif active_pre:
        stats.converged = active_pre[-1] == 0
    elif seed is not None:
        stats.converged = bool(seed.converged)
    if checkpoint is not None and sweeps > last_saved[0]:
        _save_ckpt(ss, sweeps, trace, active_pre)
    if cfg.method != "ard":
        stats.ard_stages = None
    return ss, stats
