"""Batched multi-instance solving: the instance axis as a first dimension.

The paper solves one network at a time, but its target workloads (vision
maxflow fleets, serving traffic) arrive as many similar-shaped problems.
This module lifts the device-resident sweep driver of ``sweep.py`` over a
leading instance axis B:

* one batched parallel sweep discharges **every region of every instance**
  through the batched discharge operators, over the flat ``B*K`` region
  axis;
* the whole multi-sweep loop runs in one ``lax.while_loop`` with
  **per-instance convergence flags**: an instance that has converged (or
  exhausted its sweep budget) is frozen by per-instance selects and its
  excess is zeroed on the way into the discharge, so its regions take the
  engine's O(1) early exit — a converged instance costs what an idle
  region costs today;
* per-instance label ceilings (``BatchState.d_inf_*``, ``linf``) are
  device arrays, so every instance runs exactly the iteration sequence of
  its standalone solve regardless of bucket padding: flow, labels, sweep
  counts and engine iteration counts are **bit-identical per instance** to
  ``sweep.solve`` on the unpacked problem (asserted in
  tests/test_batch.py).

Compilation is keyed by ``(BatchMeta, SweepConfig)`` — the hashable
fields of the frozen ``executor.BatchedExecutor`` that is the jit static
of the generic device chunk — so any batch landing in a previously seen
shape bucket reuses the executable with zero retracing (``trace_count()``
exposes the retrace counter to ``Solver.cache_info`` and the tests; the
time a batch spends in each layer is on the ``maxflow.*`` spans of
``core/spans.py``).

Batched solving is intentionally scoped to the serving configuration:
parallel sweeps (Alg. 2) with the optional global-gap / partial-discharge
heuristics; sequential sweeps and the boundary-relabel heuristic keep the
single-instance driver.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import executor as _executor
from repro.core import resilience as _res
from repro.core.ard import ard_discharge_batched
from repro.core.graph import BatchMeta, BatchState, PackedBatch
from repro.core.labels import GAP_HIST_CAP, gap_new_labels
from repro.core.prd import prd_discharge_batched
from repro.core.sweep import SweepConfig, sweep_bound

_I32 = jnp.int32

# bumped once per trace of the batched device program — the observable the
# compile-cache accounting (BatchedSolver.cache_info, bench_batch --smoke)
# asserts against: a second batch in a known bucket must not bump it.
_TRACE_COUNT = 0


def trace_count() -> int:
    return _TRACE_COUNT


def _bump_trace() -> None:
    """Called from inside traced code (the generic executor device chunk):
    runs once per trace, never on cached invocations."""
    global _TRACE_COUNT
    _TRACE_COUNT += 1


@dataclass
class BatchStats:
    """Per-batch solve accounting (host side, after the final sync).

    ``sweeps``/``engine_iters``/``ard_stages`` are per-instance i32[B]
    (bit-equal to the standalone drivers; ``ard_stages`` is ``None`` for
    ``method="prd"``); ``engine_launches`` and ``host_syncs`` are global
    to the batch — the whole point of batching is that the batch shares
    one launch/sync stream, so a per-instance split would be fiction.
    Per-instance ``SweepStats`` derived from this record are marked
    ``scope="batch"`` so the global counters cannot be misread as
    per-instance (see ``sweep.SweepStats``).
    """

    sweeps: np.ndarray
    engine_iters: np.ndarray
    ard_stages: np.ndarray | None = None
    engine_launches: int = 0
    host_syncs: int = 0
    converged: np.ndarray | None = None   # bool[B]: instance reached zero
    #                                       active vertices within budget


def _ghost_labels(state: BatchState) -> jax.Array:
    """i32[B,K,V,E] — per-instance gather of every arc destination's label."""
    return jax.vmap(lambda d, r, l: d[r, l])(
        state.d, state.nbr_region, state.nbr_local)


def _intra(state: BatchState) -> jax.Array:
    K = state.nbr_region.shape[1]
    own = jnp.arange(K, dtype=state.nbr_region.dtype)[None, :, None, None]
    return (state.nbr_region == own) & state.emask


def num_active_batch(state: BatchState, d_inf: jax.Array) -> jax.Array:
    """i32[B] — active-vertex count of every instance."""
    act = (state.excess > 0) & (state.d < d_inf[:, None, None]) & state.vmask
    return act.sum(axis=(1, 2)).astype(_I32)


def _global_gap_batch(state: BatchState, d_inf: jax.Array,
                      ard: bool) -> BatchState:
    """Per-instance ``labels.global_gap`` with dynamic ceilings.

    The histogram capacity must be static under vmap, so it is pinned at
    ``GAP_HIST_CAP``; ``labels.gap_new_labels`` documents why that is
    bit-equal to the single-instance heuristic's ``min(d_inf + 1, cap)``.
    """
    fn = partial(gap_new_labels, cap=GAP_HIST_CAP, ard=ard)
    new_d = jax.vmap(fn)(state.d, state.vmask, state.is_boundary, d_inf)
    return state.replace(d=new_d)


def _apply_cross_flow_batch(state: BatchState, out_push: jax.Array,
                            accept: jax.Array) -> BatchState:
    """Per-instance form of ``sweep._apply_cross_flow``.

    Gathers each cross arc's pushed flow through the bucket-dim flat
    indices, zeroing padded table entries (their index-0 slots alias real
    arcs), and scatters accepted/refunded flow instance-locally.
    """
    B = state.cf.shape[0]
    delta = jnp.take_along_axis(out_push.reshape(B, -1),
                                state.cross_src_arc, axis=1)
    delta = jnp.where(state.cross_valid, delta, 0)
    acc = jnp.where(accept, delta, 0)
    rej = delta - acc

    def one(flat, dst, src, acc, rej):
        flat = flat.at[dst].add(acc, mode="drop")
        return flat.at[src].add(rej, mode="drop")

    cf = jax.vmap(one)(state.cf.reshape(B, -1), state.cross_dst_arc,
                       state.cross_src_arc, acc, rej).reshape(state.cf.shape)
    excess = jax.vmap(one)(
        state.excess.reshape(B, -1), state.cross_dst_vtx,
        state.cross_src_vtx, acc, rej).reshape(state.excess.shape)
    return state.replace(cf=cf, excess=excess)


def _parallel_sweep_batch(bmeta: BatchMeta, cfg: SweepConfig,
                          state: BatchState, sweep_idx: jax.Array,
                          run: jax.Array | None = None):
    """One parallel sweep (Alg. 2) over every instance of the batch.

    Identical math to ``sweep.parallel_sweep`` applied per instance: the
    discharge goes through the flat [B*K] batched operators with
    per-region ceilings, fusion uses the bucket-dim cross tables, and the
    gap heuristic runs per instance.  ``run`` (bool[B]) marks the
    instances whose result the driver will keep — frozen instances get
    their ARD stage schedule emptied (cap -2 admits not even the sink
    stage) so they never add stage-loop trips to the shared launch stream.
    Returns ``(state, engine_iters [B], ard_stages [B], engine_launches
    scalar)`` — launches are global to the batch.
    """
    B, K = bmeta.num_instances, bmeta.num_regions
    V, E = bmeta.region_size, bmeta.max_degree
    ard = cfg.method == "ard"
    d_inf = state.d_inf_ard if ard else state.d_inf_prd       # [B]
    ghost = _ghost_labels(state)
    intra = _intra(state)
    f3 = lambda a: a.reshape(B * K, V, E)
    f2 = lambda a: a.reshape(B * K, V)
    rep = lambda a: jnp.repeat(a, K)                          # [B] -> [B*K]
    kw = dict(nbr_local=f3(state.nbr_local), rev_slot=f3(state.rev_slot),
              intra=f3(intra), emask=f3(state.emask), vmask=f2(state.vmask),
              max_iters=cfg.engine_max_iters, backend=cfg.engine_backend,
              chunk_iters=cfg.engine_chunk_iters)
    if ard:
        if cfg.partial_discharge:
            stage_cap = jnp.broadcast_to(
                jnp.maximum(sweep_idx - 1, -1).astype(_I32), (B,))
        else:
            stage_cap = d_inf
        if run is not None:
            stage_cap = jnp.where(run, stage_cap, -2)
        res = ard_discharge_batched(
            f3(state.cf), f2(state.sink_cf), f2(state.excess), f3(ghost),
            d_inf=rep(d_inf), stage_cap=rep(stage_cap), linf=rep(state.linf),
            **kw)
    else:
        res = prd_discharge_batched(
            f3(state.cf), f2(state.sink_cf), f2(state.excess), f2(state.d),
            f3(ghost), d_inf=rep(d_inf), **kw)
    u3 = lambda a: a.reshape(B, K, V, E)
    u2 = lambda a: a.reshape(B, K, V)
    new = state.replace(
        cf=u3(res.cf), sink_cf=u2(res.sink_cf), excess=u2(res.excess),
        d=jnp.maximum(state.d, u2(res.d)),
        flow_to_t=state.flow_to_t + res.sink_pushed.reshape(B, K).sum(1))
    # ---- fusion (Alg. 2 lines 4-6), per instance ----
    dflat = new.d.reshape(B, K * V)
    du = jnp.take_along_axis(dflat, new.cross_src_vtx, axis=1)
    dv = jnp.take_along_axis(dflat, new.cross_dst_vtx, axis=1)
    accept = (dv <= du + 1) & new.cross_valid
    new = _apply_cross_flow_batch(new, u3(res.out_push), accept)
    if cfg.use_global_gap:
        new = _global_gap_batch(new, d_inf, ard)
    iters = res.engine_iters.reshape(B, K).sum(1)
    stages = res.stages.reshape(B, K).sum(1)
    return new, iters, stages, res.engine_launches


def solve_batch(packed: PackedBatch, cfg: SweepConfig | None = None, *,
                checkpoint=None, resume_from=None, salt: str = ""):
    """Solve every instance of a packed bucket; returns (BatchState, stats).

    The batched mirror of ``sweep.solve`` in its device-resident form —
    ``executor.BatchedExecutor`` through the same generic
    ``executor.run_device`` loop as the local driver, with per-instance
    sweep budgets and convergence flags in the carry: one
    ``lax.while_loop`` trip is one complete parallel sweep of every
    still-running instance; frozen instances (converged or out of budget)
    are excluded by per-instance selects, with excess zeroed on the way
    into the discharge so their regions cost the engine's O(1) early exit
    inside the shared launch.  The host is re-entered once per
    ``cfg.host_sync_every`` sweeps (default: once per solve).
    Per-instance flow, labels, sweep counts and engine iteration counts
    are bit-identical to solving each instance alone.

    ``checkpoint``/``resume_from`` — sweep-boundary checkpointing exactly
    as in ``sweep.solve``, captured at the ``host_sync_every`` boundaries;
    the whole bucket is one checkpoint (per-instance sweeps/iters arrays
    ride in the payload), fingerprinted over the bucket shape AND every
    member instance's ``GraphMeta``, so a resume must re-pack the same
    instances in the same order.
    """
    cfg = cfg or SweepConfig()
    _executor.BatchedExecutor.validate(cfg)
    bmeta, state = packed.meta, packed.state
    B = bmeta.num_instances

    limit = np.zeros(B, np.int64)
    for b, meta in enumerate(packed.metas):
        bound = sweep_bound(meta, cfg)
        limit[b] = bound if cfg.max_sweeps is None \
            else min(cfg.max_sweeps, bound)
    limit = np.minimum(limit, np.iinfo(np.int32).max).astype(np.int32)

    ex = _executor.BatchedExecutor(bmeta, cfg)

    fp = _res.solve_fingerprint(
        bmeta, cfg, salt + "|" + ";".join(repr(m) for m in packed.metas))
    ckpt = _res.resolve_resume(resume_from, fp)
    carry0 = None
    seed_syncs = 0
    if ckpt is not None:
        state = _res.restore_state(state, ckpt.payload)
        seed_syncs = int(ckpt.stats.get("host_syncs", 0))
        carry0 = (jnp.asarray(ckpt.payload["sweeps"], _I32),
                  jnp.asarray(ckpt.payload["engine_iters"], _I32),
                  jnp.asarray(ckpt.payload["ard_stages"], _I32),
                  jnp.asarray(int(ckpt.stats["engine_launches"]), _I32),
                  jnp.asarray(ckpt.payload["n_act"], _I32))

    on_sync = None
    if checkpoint is not None:
        last_saved = [ckpt.sweeps if ckpt is not None else 0]

        def on_sync(st, host, syncs):
            done, running = ex.progress(host, limit)
            if running and done - last_saved[0] < checkpoint.every:
                return
            sweeps, iters, stages, launches, n_act = host
            payload = _res.state_payload(st)
            payload["sweeps"] = np.asarray(sweeps, np.int32)
            payload["engine_iters"] = np.asarray(iters, np.int32)
            payload["ard_stages"] = np.asarray(stages, np.int32)
            payload["n_act"] = np.asarray(n_act, np.int32)
            _res.save_checkpoint(checkpoint.directory, _res.SolveCheckpoint(
                fingerprint=fp, route="batch", sweeps=done, payload=payload,
                stats={"engine_launches": int(launches),
                       "host_syncs": seed_syncs + syncs},
                flow_offset=checkpoint.flow_offset))
            last_saved[0] = done

    state, host, syncs = _executor.run_device(
        ex, state, limit, cfg.host_sync_every, carry0=carry0,
        on_sync=on_sync)
    sweeps, iters, stages, launches, n_act = host
    return state, BatchStats(
        sweeps=np.asarray(sweeps, np.int64),
        engine_iters=np.asarray(iters, np.int64),
        ard_stages=np.asarray(stages, np.int64)
        if cfg.method == "ard" else None,
        engine_launches=int(launches), host_syncs=seed_syncs + syncs,
        converged=np.asarray(n_act) == 0)
