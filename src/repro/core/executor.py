"""Region executors: ONE generic sweep loop over every solve route.

The paper's algorithm is a single loop — "discharge all regions, exchange
boundary flow/labels, apply heuristics, repeat until no vertex is active"
(Alg. 1/2) — but the repo grew three hand-kept copies of it: the
host-loop/device-resident driver (``core.sweep``), the batched
multi-instance driver (``core.batch``) and the sharded SPMD driver
(``core.distributed``).  This module factors the loop out.

A :class:`RegionExecutor` is one *strategy* for advancing a solve by one
sweep (conceptually: ``discharge_all`` -> ``exchange_boundary`` ->
relabel/gap hooks -> ``converged`` -> ``stats``; the concrete drivers fuse
those stages into one traced program per sweep, so the executor interface
exposes them at sweep granularity):

``init_carry(state)``
    The statistics/convergence carry threaded through the loop.
``one_sweep(state, carry, limit)``
    Discharge every region once, fuse boundary flow, run the heuristic
    hooks, refresh the carry (traceable: runs under ``lax.while_loop``).
``keep_running(state, carry, limit)``
    The loop predicate (traceable).
``progress(host_carry, limit)``
    Host-side view of a fetched carry -> ``(sweeps_done, still_running)``.
``sweep_host(state, idx)``
    One sweep for the host-loop driver, returning ``(state, obs)`` with
    ``obs[0]`` the post-sweep active count (the convergence observable).

Two generic drivers run any executor to completion:

* :func:`run_host` — one traced program + one host sync per sweep (the
  paper's streaming accounting point), with an optional ``on_sweep`` hook
  called at every sweep boundary (the conformance suite's mid-solve
  invariant checker);
* :func:`run_device` — the whole loop inside ``lax.while_loop`` on device
  (:func:`while_sweeps`), one host sync per ``host_sync_every`` sweeps.

Executors are frozen dataclasses, hashable on ``(meta, cfg)`` — they ARE
the jit static argument of the generic device chunk, so the compile-cache
semantics (``trace_count``-based ``Solver.cache_info``) are unchanged: a
re-solve on a known shape reuses the executable without retracing.

Feature support is declared, not buried: every executor carries a
:class:`Capabilities` record, and :meth:`RegionExecutor.validate` turns an
unsupported ``SweepConfig`` into one consistent
:class:`UnsupportedFeatureError` at the interface (a ``ValueError`` and a
``NotImplementedError``) instead of a silent fallback or a deep-driver
raise.
"""

from __future__ import annotations

import abc
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans as _spans

_I32 = jnp.int32


# --------------------------------------------------------------------------
# test-only fault hook (core.resilience.FaultPlan)
# --------------------------------------------------------------------------
# Every generic driver fires the installed hook at its host boundaries —
# after each sweep in run_host, after each device_get in run_device — so
# the SAME deterministic fault matrix (raise at sweep k, corrupt labels,
# preemption, VMEM overflow) exercises every executor route.  The hook may
# raise (the injected failure) or return a replacement state (corruption).
# Production solves never install one; install via
# ``resilience.fault_injection`` (a context manager that restores it).

_FAULT_HOOK: Callable | None = None


def set_fault_hook(hook: Callable | None) -> Callable | None:
    """Install ``hook(route, state, sweeps_done)``; returns the previous
    hook so callers (the ``fault_injection`` context manager) can restore
    it.  ``route`` is ``"host"`` or ``"device"``."""
    global _FAULT_HOOK
    prev, _FAULT_HOOK = _FAULT_HOOK, hook
    return prev


def _fire_fault_hook(route: str, state, sweeps_done: int):
    if _FAULT_HOOK is None:
        return state
    out = _FAULT_HOOK(route, state, sweeps_done)
    return state if out is None else out


# --------------------------------------------------------------------------
# capability flags + the one consistent error surface
# --------------------------------------------------------------------------

class UnsupportedFeatureError(ValueError, NotImplementedError):
    """A ``SweepConfig`` requests a feature its executor does not implement.

    Subclasses ``ValueError`` (the historical raise of the batched front
    ends, kept for callers that catch it) and ``NotImplementedError`` (what
    the capability actually is: one code path away, not a user error).
    """

    def __init__(self, executor: str, feature: str, hint: str):
        self.executor = executor
        self.feature = feature
        super().__init__(
            f"the {executor} executor does not support {FEATURE_DOC[feature]}"
            f" ({feature}); {hint}")


@dataclass(frozen=True)
class Capabilities:
    """What a :class:`RegionExecutor` can run (True = supported).

    ``parallel``/``sequential``/``boundary_relabel``/``partial_discharge``/
    ``global_gap`` map 1:1 onto ``SweepConfig`` knobs and are validated
    against it; ``batched``/``warm_start``/``device_resident``/``host_loop``
    document the driver surface (see the capability table in
    ARCHITECTURE.md).
    """

    parallel: bool = True            # Alg. 2 sweeps (cfg.parallel=True)
    sequential: bool = True          # Alg. 1 sweeps (cfg.parallel=False)
    boundary_relabel: bool = True    # Sec. 6.1 heuristic
    partial_discharge: bool = True   # Sec. 6.2 staged augmentation
    global_gap: bool = True          # Sec. 5.1 heuristic
    batched: bool = False            # leading instance axis
    warm_start: bool = True          # resume from a resident preflow
    device_resident: bool = True     # lax.while_loop multi-sweep driver
    host_loop: bool = True           # one program + one sync per sweep


FEATURE_DOC = {
    "parallel": "parallel sweeps (Alg. 2)",
    "sequential": "sequential sweeps (Alg. 1)",
    "boundary_relabel": "the boundary-relabel heuristic (Sec. 6.1)",
    "partial_discharge": "partial discharges (Sec. 6.2)",
    "global_gap": "the global gap heuristic (Sec. 5.1)",
    "batched": "a leading instance axis",
    "warm_start": "warm-started solves",
    "device_resident": "the device-resident multi-sweep driver",
    "host_loop": "the host-loop driver",
    "fused_pallas": "the region-resident fused Pallas kernel "
                    "(engine_backend='pallas' with engine_chunk_iters)",
}

_HINTS = {
    "parallel": "set parallel=False: the streaming executor visits staged "
                "regions one at a time (Alg. 1 order) by construction",
    "sequential": "use the local executor (sweep.solve) for Alg. 1 sweeps",
    "boundary_relabel": "use the local executor (sweep.solve) for the "
                        "boundary-relabel heuristic",
}


def required_features(cfg) -> tuple[str, ...]:
    """The :class:`Capabilities` flags a ``SweepConfig`` actually exercises."""
    out = []
    if cfg.parallel:
        out.append("parallel")
    if not cfg.parallel:
        out.append("sequential")
    if cfg.use_boundary_relabel:
        out.append("boundary_relabel")
    if cfg.partial_discharge:
        out.append("partial_discharge")
    if cfg.use_global_gap:
        out.append("global_gap")
    return tuple(out)


# --------------------------------------------------------------------------
# the executor interface
# --------------------------------------------------------------------------

class RegionExecutor(abc.ABC):
    """One strategy for advancing a region-discharge solve by one sweep."""

    name: str = "abstract"
    capabilities: Capabilities = Capabilities()

    # True: the generic host loop checks convergence BEFORE each sweep (and
    # a converged entry state runs zero sweeps); False: the check happens
    # after the sweep (a converged entry still runs one no-op sweep) —
    # the two historical driver semantics, preserved bit-exactly.
    entry_check: bool = True

    @classmethod
    def validate(cls, cfg) -> None:
        """Fail fast (one consistent message) on unsupported features."""
        for feat in required_features(cfg):
            if not getattr(cls.capabilities, feat):
                raise UnsupportedFeatureError(
                    cls.name, feat,
                    _HINTS.get(feat, "see Capabilities in core/executor.py"))

    # -- traceable pieces (run under jit / lax.while_loop) -----------------

    @abc.abstractmethod
    def init_carry(self, state) -> tuple:
        """Statistics/convergence carry at sweep 0 (eager, pre-loop)."""

    @abc.abstractmethod
    def one_sweep(self, state, carry, limit):
        """Advance one sweep: discharge all regions, exchange boundary
        flow/labels, run relabel/gap hooks, update the carry."""

    @abc.abstractmethod
    def keep_running(self, state, carry, limit):
        """Loop predicate: not converged and the sweep budget remains."""

    # -- host-side pieces ---------------------------------------------------

    @abc.abstractmethod
    def num_active(self, state):
        """Convergence observable (scalar active-vertex count)."""

    @abc.abstractmethod
    def sweep_host(self, state, idx):
        """One sweep for the host-loop driver -> ``(state, obs)``;
        ``obs[0]`` must be the post-sweep active count."""

    @abc.abstractmethod
    def progress(self, host_carry, limit):
        """Fetched carry -> ``(sweeps_done: int, still_running: bool)``."""

    def note_trace(self) -> None:
        """Bump the owning module's trace counter (compile-cache stats)."""


# --------------------------------------------------------------------------
# the ONE generic sweep loop (device + host drivers)
# --------------------------------------------------------------------------

def while_sweeps(ex: RegionExecutor, state, carry, limit):
    """The generic loop itself: run sweeps until ``keep_running`` fails.

    Pure traced code — usable directly under ``jax.jit`` (the local and
    batched device chunks) and under ``shard_map`` (the sharded SPMD
    program), which is how all three drivers share it.
    """

    def cond(c):
        st, cr = c
        return ex.keep_running(st, cr, limit)

    def body(c):
        st, cr = c
        return ex.one_sweep(st, cr, limit)

    return jax.lax.while_loop(cond, body, (state, carry))


@partial(jax.jit, static_argnums=(0,))
def _device_chunk(ex: RegionExecutor, state, carry, limit):
    """One host-sync chunk of the device-resident driver.

    Jitted with the executor as the (hashable) static argument — the
    compile cache is keyed on ``(type(ex), meta, cfg)``, exactly the keying
    of the pre-unification per-driver programs.
    """
    ex.note_trace()
    return while_sweeps(ex, state, carry, limit)


@partial(jax.jit, static_argnums=(0,))
def _slot_swap(ex: "BatchedExecutor", state, carry, slot, inst):
    """Swap one instance into slot ``slot`` of a live batch (see
    ``BatchedExecutor.swap_slot``).  One compiled program per bucket shape,
    reused for every admission into that bucket."""
    ex.note_trace()
    state = jax.tree_util.tree_map(
        lambda dst, src: dst.at[slot].set(src[0]), state, inst)
    sweeps, iters, stages, launches, _ = carry
    zero = jnp.zeros((), _I32)
    sweeps = sweeps.at[slot].set(zero)
    iters = iters.at[slot].set(zero)
    stages = stages.at[slot].set(zero)
    return state, (sweeps, iters, stages, launches, ex.num_active(state))


def run_device(ex: RegionExecutor, state, limit, host_sync_every,
               chunk: Callable | None = None, carry0=None,
               on_sync: Callable | None = None):
    """Device-resident driver: the loop lives in ``lax.while_loop``; the
    host is re-entered once per ``host_sync_every`` sweeps (None: once per
    solve).  Returns ``(state, final_host_carry, host_syncs)``.

    ``limit`` — total sweep budget: a python int, or a per-instance
    ``np.int32[B]`` for the batched executor.  ``chunk`` overrides the
    generic jitted chunk (the sharded route passes its memoized
    mesh-bound SPMD program).  ``carry0`` overrides ``ex.init_carry`` —
    the checkpoint-resume entry: a carry restored from a snapshot
    continues counters/rings (and the sweep index the executors thread
    through ``carry[0]``) exactly where the interrupted solve stopped.
    ``on_sync(state, host_carry, host_syncs)`` — optional hook fired at
    every host-sync boundary (after the ``device_get``), the
    checkpoint-capture point of the device-resident routes.
    """
    if chunk is None:
        chunk = partial(_device_chunk, ex)
    with _spans.span("maxflow.sweeps", route="device") as sp:
        carry = ex.init_carry(state) if carry0 is None else carry0
        syncs = 0
        done = 0 if carry0 is None \
            else ex.progress(jax.device_get(carry), limit)[0]
        while True:
            cap = limit if host_sync_every is None \
                else np.minimum(limit, done + host_sync_every)
            with _spans.span("maxflow.sync"):
                state, carry = chunk(state, carry, jnp.asarray(cap, _I32))
                host = jax.device_get(carry)
            syncs += 1
            done, running = ex.progress(host, limit)
            if on_sync is not None:
                on_sync(state, host, syncs)
            state = _fire_fault_hook("device", state, done)
            if not running:
                break
        sp.set(sweeps=done, host_syncs=syncs)
    return state, host, syncs


def run_host(ex: RegionExecutor, state, limit,
             sweep: Callable | None = None,
             on_sweep: Callable | None = None,
             start: int = 0,
             on_obs: Callable | None = None):
    """Host-loop driver: one traced program + one host sync per sweep.

    ``on_sweep(state, sweeps_done)`` — optional hook called at every sweep
    boundary (after the sweep's device program, before the next), the
    attachment point of the conformance suite's mid-solve invariant
    checker.  ``sweep`` overrides ``ex.sweep_host`` (the sharded route
    passes its memoized mesh-bound program).  ``start`` — first sweep
    index (checkpoint resume: the loop continues at the interrupted
    solve's absolute sweep count).  ``on_obs(state, sweeps_done, trace,
    active_pre)`` — optional hook fired after every sweep's fetch with the
    LIVE observation lists, the checkpoint-capture point of the host
    route (it sees this incarnation's full accounting so far).

    Returns ``(state, trace, active_pre, host_syncs, sweeps)`` where
    ``trace`` is the list of fetched per-sweep observations,
    ``active_pre`` the pre-sweep active counts (the host-loop
    ``active_curve``, only populated for ``entry_check`` executors) and
    ``sweeps`` the absolute sweep index reached (counts from ``start``).
    """
    if sweep is None:
        sweep = ex.sweep_host
    with _spans.span("maxflow.sweeps", route="host") as sp:
        state, trace, active_pre, syncs, idx = _host_loop(
            ex, state, limit, sweep, on_sweep, start, on_obs)
        sp.set(sweeps=idx - start, host_syncs=syncs)
    return state, trace, active_pre, syncs, idx


def _host_loop(ex, state, limit, sweep, on_sweep, start, on_obs):
    trace: list[tuple] = []
    active_pre: list[int] = []
    syncs = 0
    n_act = None
    if ex.entry_check:
        n_act = int(jax.device_get(ex.num_active(state)))
        syncs += 1
    idx = start
    while idx < limit:
        if ex.entry_check:
            active_pre.append(n_act)
            if n_act == 0:
                break
        with _spans.span("maxflow.sweep", index=idx):
            state, obs = sweep(state, idx)
            host_obs = tuple(int(x) for x in jax.device_get(obs))
        syncs += 1
        idx += 1
        trace.append(host_obs)
        n_act = host_obs[0]
        # on_obs (the checkpoint capture) before on_sweep: a hook that
        # aborts the solve (deadline enforcement) leaves the boundary
        # durably checkpointed
        if on_obs is not None:
            on_obs(state, idx, trace, active_pre)
        if on_sweep is not None:
            on_sweep(state, idx)
        state = _fire_fault_hook("host", state, idx)
        if not ex.entry_check and n_act == 0:
            break
    return state, trace, active_pre, syncs, idx


# --------------------------------------------------------------------------
# the three executors
# --------------------------------------------------------------------------
# The sweep bodies stay in their home modules (they ARE those modules'
# subject matter); the executors import them lazily to break the
# module-level cycle (sweep/batch/distributed import this module for the
# generic loop and the validation surface).

@dataclass(frozen=True)
class LocalExecutor(RegionExecutor):
    """Single-instance solve on the local device (``core.sweep``).

    Carry layout (the device-resident statistics mirror): ``(sweep_idx,
    engine_iters, ard_stages, engine_launches, regions_discharged,
    flow_ring [R], active_ring [R], n_active)``.
    """

    meta: Any
    cfg: Any

    name = "local"
    capabilities = Capabilities(batched=False)
    entry_check = True

    def _sweep_mod(self):
        from repro.core import sweep
        return sweep

    def note_trace(self) -> None:
        self._sweep_mod()._bump_trace()

    def num_active(self, state):
        sw = self._sweep_mod()
        return sw.num_active(self.meta, state, self.cfg)

    def init_carry(self, state) -> tuple:
        z = jnp.zeros((), _I32)
        ring = jnp.zeros((self.cfg.stats_ring_size,), _I32)
        return (z, z, z, z, z, ring, ring,
                self.num_active(state).astype(_I32))

    def one_sweep(self, state, carry, limit):
        sw = self._sweep_mod()
        meta, cfg = self.meta, self.cfg
        idx, it, sg, ln, dc, fr, ar, n_act = carry
        R = cfg.stats_ring_size
        ar = ar.at[idx % R].set(n_act)
        if cfg.parallel:
            state, dit, dsg, dln = sw.parallel_sweep(meta, state, cfg, idx)
            ddc = _I32(meta.num_regions)
        else:
            state, dit, dsg, dln, ddc = sw.sequential_sweep(
                meta, state, cfg, idx)
        n_act = self.num_active(state).astype(_I32)
        fr = fr.at[idx % R].set(state.flow_to_t)
        return state, (idx + 1, it + dit, sg + dsg, ln + dln, dc + ddc, fr,
                       ar, n_act)

    def keep_running(self, state, carry, limit):
        idx, n_act = carry[0], carry[-1]
        return (idx < limit) & (n_act > 0)

    def progress(self, host_carry, limit):
        idx, n_act = host_carry[0], host_carry[-1]
        return int(idx), int(n_act) != 0 and int(idx) < int(limit)

    def sweep_host(self, state, idx):
        sw = self._sweep_mod()
        meta, cfg = self.meta, self.cfg
        sweep_idx = jnp.asarray(idx, _I32)
        if cfg.parallel:
            state, iters, stages, launches = sw.parallel_sweep(
                meta, state, cfg, sweep_idx)
            disc = _I32(meta.num_regions)
        else:
            state, iters, stages, launches, disc = sw.sequential_sweep(
                meta, state, cfg, sweep_idx)
        obs = (self.num_active(state), state.flow_to_t, iters, stages,
               launches, disc)
        return state, obs


@dataclass(frozen=True)
class BatchedExecutor(RegionExecutor):
    """Multi-instance solve over a leading instance axis (``core.batch``).

    Carry layout: ``(sweeps [B], engine_iters [B], ard_stages [B],
    engine_launches, n_active [B])`` — per-instance convergence flags live
    in the loop (``run = (sweeps < limit) & (n_act > 0)``), so a converged
    instance is frozen by selects and costs the engine's O(1) early exit
    inside the shared launch.  Device-resident only: the whole point of the batch is
    sharing one launch/sync stream, which a per-sweep host loop would
    forfeit.
    """

    bmeta: Any
    cfg: Any

    name = "batched"
    capabilities = Capabilities(
        sequential=False, boundary_relabel=False, batched=True,
        host_loop=False)
    entry_check = True

    def _batch_mod(self):
        from repro.core import batch
        return batch

    def note_trace(self) -> None:
        self._batch_mod()._bump_trace()

    def _d_inf(self, state):
        return state.d_inf_ard if self.cfg.method == "ard" \
            else state.d_inf_prd

    def num_active(self, state):
        return self._batch_mod().num_active_batch(state, self._d_inf(state))

    def init_carry(self, state) -> tuple:
        zb = jnp.zeros((self.bmeta.num_instances,), _I32)
        return (zb, zb, zb, jnp.zeros((), _I32), self.num_active(state))

    def one_sweep(self, state, carry, limit):
        bt = self._batch_mod()
        sweeps, it, sg, ln, n_act = carry
        run = (sweeps < limit) & (n_act > 0)                    # [B]
        st_in = state.replace(
            excess=jnp.where(run[:, None, None], state.excess, 0))
        new, dit, dsg, dln = bt._parallel_sweep_batch(
            self.bmeta, self.cfg, st_in, sweeps, run)
        w3 = run[:, None, None, None]
        w2 = run[:, None, None]
        state = state.replace(
            cf=jnp.where(w3, new.cf, state.cf),
            sink_cf=jnp.where(w2, new.sink_cf, state.sink_cf),
            excess=jnp.where(w2, new.excess, state.excess),
            d=jnp.where(w2, new.d, state.d),
            flow_to_t=jnp.where(run, new.flow_to_t, state.flow_to_t))
        n_act = self.num_active(state)
        return state, (sweeps + run.astype(_I32),
                       it + jnp.where(run, dit, 0),
                       sg + jnp.where(run, dsg, 0), ln + dln, n_act)

    def keep_running(self, state, carry, limit):
        sweeps, n_act = carry[0], carry[-1]
        return ((sweeps < limit) & (n_act > 0)).any()

    def progress(self, host_carry, limit):
        sweeps, n_act = host_carry[0], host_carry[-1]
        done = int(sweeps.max(initial=0))
        running = bool(((n_act > 0) & (sweeps < limit)).any())
        return done, running

    def sweep_host(self, state, idx):
        raise UnsupportedFeatureError(
            self.name, "host_loop",
            "the batched driver is device-resident by construction")

    # -- continuous batching -------------------------------------------------

    def swap_slot(self, state, carry, slot, inst_state):
        """Admit one instance into bucket slot ``slot`` of a live batch.

        ``inst_state`` — a ``BatchState`` with instance axis B == 1 and the
        same (K, V, E, X) bucket dims (``graph.pack_built`` on one build):
        every field (topology, cross tables, per-instance ceilings, flow
        state) is written into slot ``slot``, and the carry's per-instance
        counters for that slot reset to zero, with ``n_active`` recomputed
        so the slot's run flag (``sweeps < limit & n_act > 0``) turns live
        on the next chunk.  The previous occupant is overwritten — the
        caller (the serving tier's continuous-batching loop) only swaps
        into slots whose instance has been harvested or cancelled.  Returns
        ``(state, carry)``; one compiled swap program per bucket shape.
        """
        return _slot_swap(self, state, carry, jnp.asarray(slot, _I32),
                          inst_state)


@dataclass(frozen=True)
class ShardedExecutor(RegionExecutor):
    """SPMD solve with regions sharded over a mesh (``core.distributed``).

    The traceable pieces run *per shard under shard_map*: ``one_sweep``
    wraps the collective sweep body (all-gather/psum boundary exchange),
    and the psum'd global active count keeps the loop predicate uniform
    across shards.  Loop carry: ``(sweep_idx, start_idx, n_active)`` —
    ``start_idx`` pins the legacy semantics that a converged entry state
    still runs one (no-op) sweep, which is also why ``entry_check`` is
    False for the host loop.  The host-visible chunk carry is
    ``(sweep_idx, n_active)``.
    """

    meta: Any
    cfg: Any
    axes: tuple
    exchange: str = "full"

    name = "sharded"
    capabilities = Capabilities(sequential=False, boundary_relabel=False)
    entry_check = False

    def _dist_mod(self):
        from repro.core import distributed
        return distributed

    def note_trace(self) -> None:
        self._dist_mod()._bump_trace()

    def _d_inf(self):
        return self.meta.d_inf_ard if self.cfg.method == "ard" \
            else self.meta.d_inf_prd

    def num_active(self, state):
        # per-shard body: psum'd global count, replicated across shards
        act = ((state.excess > 0) & (state.d < self._d_inf())
               & state.vmask).sum()
        return jax.lax.psum(act, self.axes).astype(_I32)

    def init_carry(self, state) -> tuple:
        # host-visible chunk carry; run_device feeds carry[0] back as the
        # next chunk's start index through the mesh-bound program
        return (jnp.zeros((), _I32), jnp.ones((), _I32))

    def loop_carry(self, state, start_idx) -> tuple:
        return (start_idx, start_idx, self.num_active(state))

    def one_sweep(self, state, carry, limit):
        idx, start, _ = carry
        state, n_act = self._dist_mod()._one_sweep_local(
            self.meta, self.cfg, self.axes, state, idx, self.exchange)
        return state, (idx + 1, start, n_act)

    def keep_running(self, state, carry, limit):
        idx, start, n_act = carry
        # (idx == start) keeps the legacy host-loop semantics on an
        # already-converged input: one (no-op) sweep still runs, so every
        # driver reports identical sweep counts in every case
        return (idx < limit) & ((n_act > 0) | (idx == start))

    def progress(self, host_carry, limit):
        idx, n_act = host_carry[0], host_carry[-1]
        return int(idx), int(n_act) != 0 and int(idx) < int(limit)

    def sweep_host(self, state, idx):
        raise RuntimeError("the sharded host loop runs through the memoized "
                           "mesh-bound sweep program (distributed."
                           "make_sharded_sweep), passed to run_host")


@dataclass(frozen=True)
class StreamingExecutor(RegionExecutor):
    """Out-of-core single-instance solve: regions staged one at a time
    from a disk-backed spill pool (``repro.stream``).

    The state threaded through the generic host loop is a
    ``stream.StreamState`` (spill-pool handle + resident-set manager +
    the |B|-sized boundary arrays), NOT a ``FlowState`` — at any moment
    only ``max_resident_regions`` [V, E] slabs are in memory.  Host-loop
    only: the premise is that the instance does not fit resident, so
    there is nothing for a device-side ``while_loop`` to hold.
    Sequential sweeps only: the paper's streaming mode IS Alg. 1 —
    regions are visited in order and boundary flow/labels apply
    immediately, which is what makes one-region residency sufficient.
    Global gap needs every label in memory at once, so it is declared
    unsupported rather than approximated.
    """

    meta: Any
    cfg: Any

    name = "streaming"
    capabilities = Capabilities(
        parallel=False, boundary_relabel=False, global_gap=False,
        device_resident=False)
    entry_check = True

    def _stream_mod(self):
        from repro.stream import executor as stream_executor
        return stream_executor

    def note_trace(self) -> None:
        self._stream_mod()._bump_trace()

    def num_active(self, state):
        return state.num_active()

    def init_carry(self, state) -> tuple:
        raise UnsupportedFeatureError(
            self.name, "device_resident",
            "the streaming executor runs through the host loop (run_host)")

    def one_sweep(self, state, carry, limit):
        raise UnsupportedFeatureError(
            self.name, "device_resident",
            "the streaming executor runs through the host loop (run_host)")

    def keep_running(self, state, carry, limit):
        raise UnsupportedFeatureError(
            self.name, "device_resident",
            "the streaming executor runs through the host loop (run_host)")

    def progress(self, host_carry, limit):
        raise UnsupportedFeatureError(
            self.name, "device_resident",
            "the streaming executor runs through the host loop (run_host)")

    def sweep_host(self, state, idx):
        return self._stream_mod().stream_sweep(state, idx)


EXECUTORS = (LocalExecutor, BatchedExecutor, ShardedExecutor,
             StreamingExecutor)
