"""Distributed P-ARD/P-PRD under shard_map — regions sharded over devices.

This is the paper's parallel mode mapped onto a TPU mesh: each device owns a
contiguous block of regions (rows of every [K, V, E] array); one sweep is a
single SPMD program whose only cross-device traffic is

  * an all-gather of the distance labels d[K, V] (the paper's boundary-label
    messages), and
  * a psum of the flat cross-arc flow deltas [X] plus the acceptance fusion
    (the paper's boundary-flow messages),

i.e. exactly the paper's "communication ∝ boundary" property — the roofline
collective term of the maxflow workload is the boundary exchange and nothing
else.  Region discharges themselves contain no collectives (they are the
paper's independent region computations), so compute/communication overlap
is naturally available to the scheduler.

This module provides the sharded one-sweep program plus spec builders for
the multi-pod dry-run; the solve loop itself is the generic region-executor
loop of ``core.executor`` (``ShardedExecutor`` + ``run_host``/
``run_device``), shared with the local and batched drivers.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import executor as _executor
from repro.core import heuristics
from repro.core import resilience as _res
from repro.core.ard import ard_discharge_batched
from repro.core.graph import FlowState, GraphMeta, INF_LABEL
from repro.core.labels import GAP_HIST_CAP
from repro.core.prd import prd_discharge_batched
from repro.core.sweep import SweepConfig

_I32 = jnp.int32


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication checker off: the sweep body
    mixes replicated (cross-arc tables) and sharded (region) operands."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# bumped once per trace of the sharded one-sweep body — part of the session
# front-end's combined compile-cache observable (Solver.cache_info)
_TRACE_COUNT = 0


def trace_count() -> int:
    return _TRACE_COUNT


def _bump_trace() -> None:
    global _TRACE_COUNT
    _TRACE_COUNT += 1


def region_axis_sharding(mesh: Mesh, axes) -> dict:
    """PartitionSpecs for a FlowState sharded over its region axis."""
    kv = P(axes)                     # [K, V]   arrays
    kve = P(axes)                    # [K, V, E] arrays
    rep = P()
    return dict(
        nbr_region=kve, nbr_local=kve, rev_slot=kve, emask=kve, vmask=kv,
        is_boundary=kv, cross_src=rep, cross_dst=rep, cross_group=rep,
        cross_valid=rep, cross_src_arc=rep, cross_dst_arc=rep,
        cross_src_vtx=rep, cross_dst_vtx=rep,
        cf=kve, sink_cf=kv, excess=kv, d=kv, flow_to_t=rep,
    )


def flowstate_shardings(mesh: Mesh, axes) -> FlowState:
    spec = region_axis_sharding(mesh, axes)
    return FlowState(**{k: NamedSharding(mesh, v) for k, v in spec.items()})


def _one_sweep_local(meta: GraphMeta, cfg: SweepConfig, axes,
                     state: FlowState, sweep_idx,
                     exchange: str = "full"):
    """Per-shard body of one parallel sweep (runs under shard_map).

    ``exchange`` — "full": all-gather the whole label array (baseline);
    "boundary": exchange only the labels the remote side actually needs
    (one psum over the flat cross-arc table) — the beyond-paper optimized
    schedule; see EXPERIMENTS.md §Perf for the measured exchange-mode and
    engine-backend numbers.
    """
    global _TRACE_COUNT
    _TRACE_COUNT += 1
    Kl, V, E = state.cf.shape                     # local regions
    # region offset of this shard (flat index over possibly-multiple axes)
    idx = jnp.zeros((), _I32)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    offset = idx * Kl

    src, dst = state.cross_src, state.cross_dst
    dst_local_r0 = dst[:, 0] - offset
    dst_mine0 = (dst_local_r0 >= 0) & (dst_local_r0 < Kl)
    dl0 = jnp.clip(dst_local_r0, 0, Kl - 1)
    src_local_r0 = src[:, 0] - offset
    src_mine0 = (src_local_r0 >= 0) & (src_local_r0 < Kl)
    sl0 = jnp.clip(src_local_r0, 0, Kl - 1)

    # ---- boundary label exchange ----
    if exchange == "full":
        d_full = jax.lax.all_gather(state.d, axes, axis=0, tiled=True)
        ghost_d = d_full[state.nbr_region, state.nbr_local]
    else:
        # labels of cross-arc destinations only: one [X] psum
        contrib = jnp.where(dst_mine0, state.d[dl0, dst[:, 1]], 0)
        dst_label = jax.lax.psum(contrib, axes)                    # [X]
        ghost_flat = jnp.zeros((Kl * V * E,), _I32).at[
            (sl0 * V + src[:, 1]) * E + src[:, 2]].max(
            jnp.where(src_mine0, dst_label, 0), mode="drop")
        ghost_d = ghost_flat.reshape(Kl, V, E)

    own = offset + jnp.arange(Kl, dtype=_I32)
    intra = (state.nbr_region == own[:, None, None]) & state.emask

    stage_cap = jnp.where(
        jnp.asarray(cfg.partial_discharge),
        jnp.maximum(sweep_idx - 1, -1).astype(_I32),
        _I32(meta.d_inf_ard))

    # batched discharge over this shard's local regions: same per-region
    # results as vmapping the scalar operators, but the fused pallas path
    # is one grid-over-regions kernel launch per chunk per shard
    disc_kw = dict(nbr_local=state.nbr_local, rev_slot=state.rev_slot,
                   intra=intra, emask=state.emask, vmask=state.vmask,
                   max_iters=cfg.engine_max_iters,
                   backend=cfg.engine_backend,
                   chunk_iters=cfg.engine_chunk_iters)
    if cfg.method == "ard":
        res = ard_discharge_batched(
            state.cf, state.sink_cf, state.excess, ghost_d,
            d_inf=meta.d_inf_ard, stage_cap=stage_cap, **disc_kw)
    else:
        res = prd_discharge_batched(
            state.cf, state.sink_cf, state.excess, state.d, ghost_d,
            d_inf=meta.d_inf_prd, **disc_kw)

    new_d_local = jnp.maximum(state.d, res.d)
    cf, sink_cf, excess = res.cf, res.sink_cf, res.excess

    # ---- boundary flow exchange + fusion (Alg. 2 lines 4-6) ----
    src_mine, sl = src_mine0, sl0
    dst_mine, dl = dst_mine0, dl0
    delta_local = jnp.where(src_mine,
                            res.out_push[sl, src[:, 1], src[:, 2]], 0)
    if exchange == "full":
        delta = jax.lax.psum(delta_local, axes)                  # [X]
        d_full2 = jax.lax.all_gather(new_d_local, axes, axis=0, tiled=True)
        du = d_full2[src[:, 0], src[:, 1]]
        dv = d_full2[dst[:, 0], dst[:, 1]]
    else:
        # fuse the three [X] exchanges into one stacked psum
        du_c = jnp.where(src_mine, new_d_local[sl, src[:, 1]], 0)
        dv_c = jnp.where(dst_mine, new_d_local[dl, dst[:, 1]], 0)
        packed = jax.lax.psum(
            jnp.stack([delta_local, du_c, dv_c]), axes)          # [3, X]
        delta, du, dv = packed[0], packed[1], packed[2]
    accept = dv <= du + 1
    acc = jnp.where(accept, delta, 0)
    rej = delta - acc
    flat = cf.reshape(-1)
    flat = flat.at[(dl * V + dst[:, 1]) * E + dst[:, 2]].add(
        jnp.where(dst_mine, acc, 0), mode="drop")
    flat = flat.at[(sl * V + src[:, 1]) * E + src[:, 2]].add(
        jnp.where(src_mine, rej, 0), mode="drop")
    cf = flat.reshape(Kl, V, E)
    ef = excess.reshape(-1)
    ef = ef.at[dl * V + dst[:, 1]].add(jnp.where(dst_mine, acc, 0),
                                       mode="drop")
    ef = ef.at[sl * V + src[:, 1]].add(jnp.where(src_mine, rej, 0),
                                       mode="drop")
    excess = ef.reshape(Kl, V)

    flow_to_t = state.flow_to_t + jax.lax.psum(res.sink_pushed.sum(), axes)

    # ---- global gap heuristic (psum histogram) ----
    # the sharded mirror of labels.gap_new_labels: ARD histograms boundary
    # labels only (Sec. 5.3), PRD all vertices — identical member sets and
    # scan range to the local driver's heuristic, so labels stay bit-equal
    d_local = new_d_local
    if cfg.use_global_gap:
        ard = cfg.method == "ard"
        d_inf = meta.d_inf_ard if ard else meta.d_inf_prd
        cap = min(d_inf + 1, GAP_HIST_CAP)
        member = state.vmask & (d_local < d_inf)
        if ard:
            member = member & state.is_boundary
        vals = jnp.where(member, d_local, 0).reshape(-1)
        hist = jnp.zeros((cap,), _I32).at[jnp.clip(vals, 0, cap - 1)].add(
            member.reshape(-1).astype(_I32))
        hist = jax.lax.psum(hist, axes)
        idxs = jnp.arange(cap)
        max_lab = jax.lax.pmax(jnp.max(jnp.where(member, d_local, 0)), axes)
        is_gap = (hist == 0) & (idxs >= 1) & \
            (idxs <= jnp.minimum(max_lab, cap - 1))
        g = jnp.min(jnp.where(is_gap, idxs, INF_LABEL))
        d_local = jnp.where(state.vmask & (d_local > g) & (d_local < d_inf),
                            d_inf, d_local).astype(_I32)

    n_active = jax.lax.psum(
        ((excess > 0) & (d_local < (meta.d_inf_ard if cfg.method == "ard"
                                    else meta.d_inf_prd))
         & state.vmask).sum(), axes)

    out = state.replace(cf=cf, sink_cf=sink_cf, excess=excess, d=d_local,
                        flow_to_t=flow_to_t)
    return out, n_active


def _memoized(fn):
    """Memoize a sharded-program builder on its (hashable) arguments.

    ``jax.jit`` caches per function object, so rebuilding the shard_map
    body on every ``solve_sharded`` call used to retrace/recompile each
    time; a session issuing warm re-solves through the sharded route must
    reuse the program.  Keyed on (meta, mesh, cfg, axes, exchange) — all
    hashable.
    """
    import functools

    return functools.lru_cache(maxsize=64)(fn)


@_memoized
def make_sharded_sweep(meta: GraphMeta, mesh: Mesh, cfg: SweepConfig,
                       axes=("regions",), exchange: str = "full"):
    """Build the jitted one-sweep SPMD program for a region-sharded mesh.

    ``axes`` — mesh axis name(s) the region dimension is sharded over; for
    the production pod mesh the regions axis spans ("pod", "data", "model")
    flattened, i.e. K = 512 regions on 512 chips.
    """
    spec = region_axis_sharding(mesh, axes)
    in_specs = (FlowState(**spec), P())
    out_specs = (FlowState(**spec), P())
    body = partial(_one_sweep_local, meta, cfg, axes, exchange=exchange)
    fn = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    return jax.jit(fn)


@_memoized
def make_sharded_solve(meta: GraphMeta, mesh: Mesh, cfg: SweepConfig,
                       axes=("regions",), exchange: str = "full"):
    """Build the jitted device-resident multi-sweep SPMD program.

    ``run(state, start_idx, limit) -> (state, sweep_idx, n_active)``
    advances the solve from sweep ``start_idx`` until convergence or
    ``limit`` total sweeps inside one ``lax.while_loop`` under shard_map —
    no host round trip between sweeps.  The loop predicate consumes the
    psum'd global active count, which is replicated across shards, so
    control flow stays uniform.
    """
    spec = region_axis_sharding(mesh, axes)
    in_specs = (FlowState(**spec), P(), P())
    out_specs = (FlowState(**spec), P(), P())
    ex = _executor.ShardedExecutor(meta, cfg, tuple(axes), exchange)

    def chunk(state: FlowState, start_idx, limit):
        # the generic executor loop, per shard: the executor's psum'd
        # active count keeps the predicate uniform across shards
        state, carry = _executor.while_sweeps(
            ex, state, ex.loop_carry(state, start_idx), limit)
        idx, _start, n_act = carry
        return state, idx, n_act

    fn = shard_map(chunk, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    return jax.jit(fn)


def grid_like_meta(num_regions: int, region_size: int,
                   degree: int) -> GraphMeta:
    """A ``GraphMeta`` shaped like a square grid cut into square regions:
    about ``4 * sqrt(V)`` cross arcs per region.  For AOT lowering (the
    dry run, the TPU compile tests), where no instance is built."""
    K, V = num_regions, region_size
    X = int(4 * (V ** 0.5)) * K
    return GraphMeta(num_regions=K, region_size=V, max_degree=degree,
                     num_vertices=K * V, num_boundary=X // 2,
                     num_cross_arcs=X, num_ghost_groups=X,
                     d_inf_ard=X // 2, d_inf_prd=K * V)


def maxflow_input_specs(meta: GraphMeta) -> FlowState:
    """ShapeDtypeStructs of a FlowState for AOT lowering (dry-run)."""
    K, V, E = meta.num_regions, meta.region_size, meta.max_degree
    X = meta.num_cross_arcs
    f = jax.ShapeDtypeStruct
    return FlowState(
        nbr_region=f((K, V, E), jnp.int32), nbr_local=f((K, V, E), jnp.int32),
        rev_slot=f((K, V, E), jnp.int32), emask=f((K, V, E), jnp.bool_),
        vmask=f((K, V), jnp.bool_), is_boundary=f((K, V), jnp.bool_),
        cross_src=f((X, 3), jnp.int32), cross_dst=f((X, 3), jnp.int32),
        cross_group=f((X,), jnp.int32), cross_valid=f((X,), jnp.bool_),
        cross_src_arc=f((X,), jnp.int32), cross_dst_arc=f((X,), jnp.int32),
        cross_src_vtx=f((X,), jnp.int32), cross_dst_vtx=f((X,), jnp.int32),
        cf=f((K, V, E), jnp.int32), sink_cf=f((K, V), jnp.int32),
        excess=f((K, V), jnp.int32), d=f((K, V), jnp.int32),
        flow_to_t=f((), jnp.int32))


def solve_sharded(meta: GraphMeta, state: FlowState, mesh: Mesh,
                  cfg: SweepConfig | None = None, axes=("regions",),
                  max_sweeps: int | None = None, exchange: str = "full",
                  device_resident: bool | None = None,
                  host_sync_every: int | None = None,
                  return_stats: bool = False,
                  checkpoint=None, resume_from=None, salt: str = "",
                  on_sweep=None):
    """Sharded sweep loop (device-resident state; regions over the mesh).

    Default driver: one jitted SPMD sweep program + one host sync per
    sweep.  With ``device_resident`` (also picked up from
    ``cfg.device_resident``) the whole loop runs in a ``lax.while_loop``
    under shard_map and the host is re-entered once per
    ``host_sync_every`` sweeps (default: once per solve) — the same
    treatment as ``core.sweep.solve``.  Returns (state, sweeps), or
    (state, sweeps, host_syncs) with ``return_stats`` (the session
    front-end's route).  The compiled SPMD programs are memoized on
    (meta, mesh, cfg, axes, exchange), so repeated solves — a session's
    warm re-solves in particular — reuse them.

    ``checkpoint``/``resume_from``/``salt`` — sweep-boundary
    checkpointing exactly as in ``sweep.solve``: the host driver captures
    at every sweep boundary under the ``checkpoint.every`` cadence, the
    device-resident driver at its ``host_sync_every`` boundaries; the
    payload is the fully-gathered flow state (one ``device_get``), so a
    resume may re-land on a different mesh (elastic) — the re-entry
    ``device_put`` re-shards it.  A checkpoint taken at a CONVERGED final
    boundary short-circuits: the finished result returns without
    re-entering the sweep loop (the sharded loop's converged-entry
    semantics would otherwise burn one no-op sweep).

    ``on_sweep(state, sweeps_done)`` — optional sweep-boundary hook, as in
    ``sweep.solve``: every sweep boundary on the host driver, the
    ``host_sync_every`` boundaries on the device-resident driver.
    """
    cfg = cfg or SweepConfig()
    _executor.ShardedExecutor.validate(cfg)
    axes = tuple(axes) if not isinstance(axes, str) else (axes,)
    if device_resident is None:
        device_resident = cfg.device_resident
    if host_sync_every is None:
        host_sync_every = cfg.host_sync_every
    if max_sweeps is None:
        max_sweeps = cfg.max_sweeps
    shardings = flowstate_shardings(mesh, axes)
    if checkpoint is not None:
        salt = checkpoint.salt
    fp = _res.solve_fingerprint(meta, cfg, salt)
    ckpt = _res.resolve_resume(resume_from, fp)
    start = 0
    seed_syncs = 0
    if ckpt is not None:
        state = _res.restore_state(state, ckpt.payload)
        start = ckpt.sweeps
        seed_syncs = int(ckpt.stats.get("host_syncs", 0))
    state = jax.device_put(state, shardings)
    if ckpt is not None and _res.checkpoint_converged(ckpt):
        # a converged final-boundary checkpoint: the solve is already
        # finished — re-entering the loop would run one no-op sweep, since
        # the sharded loop keeps the legacy converged-entry semantics
        # (ShardedExecutor.keep_running's ``idx == start`` term)
        return (state, start, seed_syncs) if return_stats \
            else (state, start)
    bound = (2 * meta.num_boundary ** 2 + 1 if cfg.method == "ard"
             else 2 * meta.num_vertices ** 2)
    limit = max_sweeps if max_sweeps is not None else bound
    ex = _executor.ShardedExecutor(meta, cfg, axes, exchange)

    def save(st, sweeps_done, n_act, syncs):
        payload = _res.state_payload(st)
        payload["n_act"] = np.asarray(n_act, np.int32)
        _res.save_checkpoint(checkpoint.directory, _res.SolveCheckpoint(
            fingerprint=fp, route="sharded", sweeps=sweeps_done,
            payload=payload,
            stats={"sweeps": sweeps_done, "host_syncs": seed_syncs + syncs},
            flow_offset=checkpoint.flow_offset))

    if device_resident:
        run = make_sharded_solve(meta, mesh, cfg, axes, exchange=exchange)

        def chunk(state, carry, cap):
            state, idx, n_act = run(state, jnp.asarray(carry[0], _I32), cap)
            return state, (idx, n_act)

        carry0 = None
        if ckpt is not None:
            carry0 = (jnp.asarray(start, _I32),
                      jnp.asarray(int(ckpt.payload["n_act"]), _I32))

        ckpt_sync = None
        if checkpoint is not None:
            last_saved = [start]

            def ckpt_sync(st, host, syncs):
                done, running = ex.progress(host, limit)
                if running and done - last_saved[0] < checkpoint.every:
                    return
                save(st, done, host[-1], syncs)
                last_saved[0] = done

        on_sync = ckpt_sync
        if on_sweep is not None:
            # checkpoint first: a hook that aborts the solve (deadline
            # enforcement) leaves the boundary durably checkpointed
            def on_sync(st, host, syncs):
                if ckpt_sync is not None:
                    ckpt_sync(st, host, syncs)
                on_sweep(st, int(host[0]))

        state, host, host_syncs = _executor.run_device(
            ex, state, limit, host_sync_every, chunk=chunk, carry0=carry0,
            on_sync=on_sync)
        return (state, int(host[0]), seed_syncs + host_syncs) \
            if return_stats else (state, int(host[0]))

    sweep_fn = make_sharded_sweep(meta, mesh, cfg, axes, exchange=exchange)

    def one(state, idx):
        state, n_active = sweep_fn(state, jnp.asarray(idx, _I32))
        return state, (n_active,)

    on_obs = None
    last_saved = [start]
    if checkpoint is not None:
        def on_obs(st, idx, trace, active_pre):
            if idx - last_saved[0] < checkpoint.every:
                return
            save(st, idx, trace[-1][0], len(trace))
            last_saved[0] = idx

    state, trace, _pre, host_syncs, sweeps = _executor.run_host(
        ex, state, limit, sweep=one, start=start, on_obs=on_obs,
        on_sweep=on_sweep)
    if checkpoint is not None and sweeps > last_saved[0] and trace:
        save(state, sweeps, trace[-1][0], len(trace))
    return (state, sweeps, seed_syncs + host_syncs) if return_stats \
        else (state, sweeps)
