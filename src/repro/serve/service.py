"""Continuous-batching maxflow service with a robustness layer.

``MaxflowService`` turns the solver session layer into a *service*: an
admission-controlled request queue feeding shape-bucketed, continuously
batched solve loops.  Each power-of-two bucket shape owns ONE live batch
of ``max_batch`` slots driven chunk-by-chunk through the generic
``executor._device_chunk`` program; a slot whose instance converged (or
died) is freed and the next queued request of that shape is swapped in
via ``BatchedExecutor.swap_slot`` — admission into a *running* batch,
no repack, no retrace (one compiled swap program per bucket).

The service is deliberately **step-driven and single-threaded**: every
externally observable action happens inside ``submit`` or ``step``, the
clock is injected, and device work happens in bounded chunks
(``sync_every`` sweeps per bucket per step).  That makes the whole
robustness matrix deterministic under a fake clock — which is how the
test suite drives deadline expiry mid-solve, breaker cooldowns and
eviction without wall time — while a real deployment just calls
``step()`` in a loop (``run_until_idle``, ``replay_stream``, or the
``launch/maxflow_serve.py`` CLI).

The robustness layer, each with its typed outcome and counter:

* **deadlines** — enforced at sweep boundaries only (the chunk
  boundaries of the bucket loop; ``solve_with_deadline`` does the same
  through the ``on_sweep`` hook of the single-handle routes), so an
  expired request dies at a consistent preflow and its
  ``DeadlineExceeded`` carries sweeps-completed and partial-flow
  diagnostics;
* **admission control** — a bounded queue; overflow is shed immediately
  with ``ServiceOverloaded`` (retry-after, per-tenant shed accounting)
  instead of queueing unboundedly;
* **handle eviction** — named sessions keep prepared handles warm on
  device under an LRU with a byte budget; evicted handles are
  checkpointed (``resilience.snapshot_save``) and transparently resumed
  warm on their next request;
* **circuit breaker** — kernel-class chunk failures walk the
  pallas -> xla / xla-fused -> xla-unfused ladder as usual, but a rung that
  keeps failing is *opened* and skipped at chunk entry for a cooldown
  (``serve.breaker``), so a wedged backend stops costing a failed launch
  per chunk;
* **supervised retries** — non-kernel chunk faults re-run the chunk from
  the intact pre-chunk state up to ``max_retries`` times before the
  batch's in-flight requests resolve to ``RequestFailed``.

Everything lands in ``ServiceStats`` (``service.report()``), including
the liveness invariant the acceptance test asserts: every submitted
request is exactly one of resolved / queued / in-flight.
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..core import executor as _executor
from ..core import graph as _graph
from ..core import resilience as _res
from ..core import sweep as _sweep
from ..core.solver import (MincutResult, ProblemHandle, Solver,
                           SolverOptions, _finish)
from .breaker import BreakerBoard
from .errors import (DeadlineExceeded, RequestFailed, ServiceClosed,
                     ServiceOverloaded)
from .stats import ServiceStats

_I32 = jnp.int32


# --------------------------------------------------------------------------
# requests and tickets
# --------------------------------------------------------------------------

@dataclass
class SolveRequest:
    """One unit of service work.

    ``problem`` — the network to cut (required unless ``session`` names a
    live prepared session and ``update`` re-cuts it).  ``session`` — a
    client-chosen key: the prepared handle is cached under it, so later
    requests with the same key warm-start (and may carry ``update``, a
    dict of ``ProblemHandle.update`` kwargs applied before the re-solve).
    ``timeout`` — seconds from submission to the deadline (None: the
    service default).  ``tenant`` — shed-accounting bucket.
    """

    problem: object | None = None
    part: np.ndarray | None = None
    session: str | None = None
    update: dict | None = None
    timeout: float | None = None
    tenant: str = "default"
    request_id: str = ""


@dataclass
class Ticket:
    """The service's promise for one submitted request.

    Exactly one of ``result``/``error`` is set once ``done``; ``error``
    is always a typed ``serve.errors.ServiceError``.
    """

    request: SolveRequest
    submitted_at: float
    deadline_at: float | None
    done: bool = False
    result: MincutResult | None = None
    error: Exception | None = None
    _handle: ProblemHandle | None = field(default=None, repr=False)
    _inst: object | None = field(default=None, repr=False)

    def outcome(self):
        """The result, or raises the typed error (once resolved)."""
        assert self.done, "request not resolved yet — step the service"
        if self.error is not None:
            raise self.error
        return self.result


@dataclass(frozen=True)
class ServiceConfig:
    """Service-level knobs (solver knobs stay in ``SolverOptions``)."""

    max_queue: int = 64            # admission bound; beyond: shed
    max_batch: int = 4             # slots per shape bucket
    sync_every: int = 1            # sweeps per bucket per step (the
    #                                deadline-enforcement granularity)
    default_timeout: float | None = None
    retry_after: float = 0.5       # hint stamped on sheds
    max_retries: int = 2           # chunk re-runs before RequestFailed
    handle_budget_bytes: int | None = None   # session LRU byte budget
    eviction_dir: str | None = None          # where evicted handles go
    breaker_threshold: int = 3
    breaker_window: float = 60.0
    breaker_cooldown: float = 30.0

    def __post_init__(self):
        assert self.max_queue >= 1 and self.max_batch >= 1
        assert self.sync_every >= 1 and self.max_retries >= 0


@dataclass
class _Slot:
    ticket: Ticket
    handle: ProblemHandle
    session: str | None


class _Bucket:
    """One live batch: ``max_batch`` slots of one power-of-two shape."""

    def __init__(self, bmeta, state, carry, ex):
        self.bmeta = bmeta
        self.state = state
        self.carry = carry
        self.ex = ex                      # base-config executor (swaps)
        B = bmeta.num_instances
        self.slots: list[_Slot | None] = [None] * B
        self.limits = np.zeros(B, np.int32)
        self.sweeps_host = np.zeros(B, np.int32)
        self.syncs = 0

    @property
    def occupied(self) -> bool:
        return any(s is not None for s in self.slots)

    def free_slot(self) -> int | None:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None


# --------------------------------------------------------------------------
# the service
# --------------------------------------------------------------------------

class MaxflowService:
    """See the module docstring.  ``clock`` is injectable (tests pass a
    fake); the default is ``time.monotonic``."""

    def __init__(self, options: SolverOptions | None = None,
                 config: ServiceConfig | None = None, clock=None):
        self.options = options if options is not None else SolverOptions()
        self.config = config if config is not None else ServiceConfig()
        self._clock = clock if clock is not None else time.monotonic
        self._cfg = self.options.sweep_config()
        _executor.BatchedExecutor.validate(self._cfg)
        self.solver = Solver(self.options)
        self.stats = ServiceStats()
        self.board = BreakerBoard(
            threshold=self.config.breaker_threshold,
            window=self.config.breaker_window,
            cooldown=self.config.breaker_cooldown, clock=self._clock)
        self._queue: deque[Ticket] = deque()
        self._buckets: dict[tuple, _Bucket] = {}
        self._sessions: "OrderedDict[str, ProblemHandle]" = OrderedDict()
        self._evicted: dict[str, dict] = {}
        self._seq = 0
        self._evict_seq = 0
        self._closed = False
        self._started_at = self._clock()

    # -- submission ---------------------------------------------------------

    def submit(self, request: SolveRequest | None = None, **kw) -> Ticket:
        """Admit (or shed) one request; returns its ``Ticket``.

        Never blocks and never raises for per-request conditions: a full
        queue resolves the ticket immediately with ``ServiceOverloaded``,
        a closed service with ``ServiceClosed`` (closed rejections are
        not counted as submissions — the request never entered).
        """
        if request is None:
            request = SolveRequest(**kw)
        if not request.request_id:
            request.request_id = f"r{self._seq:06d}"
        self._seq += 1
        now = self._clock()
        timeout = request.timeout if request.timeout is not None \
            else self.config.default_timeout
        ticket = Ticket(request, submitted_at=now,
                        deadline_at=None if timeout is None
                        else now + timeout)
        if self._closed:
            ticket.done = True
            ticket.error = ServiceClosed(request.request_id)
            return ticket
        self.stats.submitted += 1
        if len(self._queue) >= self.config.max_queue:
            self.stats.record_shed(request.tenant)
            ticket.done = True
            ticket.error = ServiceOverloaded(
                request.request_id, retry_after=self.config.retry_after,
                queue_depth=len(self._queue), bound=self.config.max_queue,
                tenant=request.tenant)
            return ticket
        self._queue.append(ticket)
        self.stats.observe_queue(len(self._queue))
        return ticket

    # -- the service loop ---------------------------------------------------

    def step(self) -> int:
        """One service round: expire queued deadlines, admit into free
        slots, advance every occupied bucket by ``sync_every`` sweeps,
        harvest/expire slots, enforce the session byte budget.  Returns
        the number of requests resolved this round."""
        before = self.stats.resolved
        self._expire_queued()
        self._admit_from_queue()
        for bucket in list(self._buckets.values()):
            if bucket.occupied:
                self._pump_bucket(bucket)
        self._enforce_budget()
        self._refresh_gauges()
        return self.stats.resolved - before

    @property
    def pending(self) -> int:
        return len(self._queue) + sum(
            1 for b in self._buckets.values()
            for s in b.slots if s is not None)

    def run_until_idle(self, max_steps: int = 100_000) -> None:
        steps = 0
        while self.pending:
            self.step()
            steps += 1
            assert steps < max_steps, "service failed to drain"

    def close(self, drain: bool = True) -> None:
        """Stop accepting work; by default drain what is already in."""
        if drain:
            self.run_until_idle()
        else:
            self._expire_queued()
        self._closed = True

    # -- probes + reporting -------------------------------------------------

    def healthy(self) -> bool:
        self._refresh_gauges()
        return self.stats.healthy()

    def ready(self) -> bool:
        return not self._closed \
            and self.stats.ready(self.config.max_queue)

    def report(self) -> dict:
        self._refresh_gauges()
        self.stats.note_elapsed(self._clock() - self._started_at)
        out = self.stats.report(breaker_state=self.board.snapshot())
        out["ready"] = self.ready()
        return out

    # -- queue-side deadline + admission ------------------------------------

    def _resolve_error(self, ticket: Ticket, err: Exception) -> None:
        ticket.done = True
        ticket.error = err

    def _resolve_result(self, ticket: Ticket, res: MincutResult) -> None:
        ticket.done = True
        ticket.result = res
        self.stats.completed += 1
        self.stats.record_latency(self._clock() - ticket.submitted_at)

    def _expire_queued(self) -> None:
        now = self._clock()
        keep: deque[Ticket] = deque()
        for t in self._queue:
            if t.deadline_at is not None and now >= t.deadline_at:
                self.stats.deadline_misses += 1
                self._resolve_error(t, DeadlineExceeded(
                    t.request.request_id,
                    deadline=t.deadline_at - t.submitted_at,
                    elapsed=now - t.submitted_at, sweeps_completed=0,
                    stage="queued"))
            else:
                keep.append(t)
        self._queue = keep
        self.stats.observe_queue(len(self._queue))

    def _inflight_sessions(self) -> set:
        return {s.session for b in self._buckets.values()
                for s in b.slots if s is not None and s.session}

    def _resolve_handle(self, req: SolveRequest) -> ProblemHandle:
        """The prepared handle of a request: session cache hit, warm
        resume of an evicted session, or a fresh ``prepare`` — then any
        ``update`` delta applied (exactly once per request)."""
        if req.session is not None:
            h = self._sessions.get(req.session)
            if h is None and req.session in self._evicted:
                h = self._restore_session(req.session)
            if h is not None:
                self._sessions.move_to_end(req.session)
                if req.update:
                    h.update(**req.update)
                return h
            if req.problem is None:
                raise KeyError(
                    f"session {req.session!r} unknown and the request "
                    f"carries no problem to prepare it from")
        h = self.solver.prepare(req.problem, req.part)
        if req.session is not None:
            self._sessions[req.session] = h
        if req.update:
            h.update(**req.update)
        return h

    def _admit_from_queue(self) -> None:
        """Scan the queue in order, swapping each request into a free
        slot of its shape bucket (FIFO per bucket; a request whose bucket
        is full — or whose session is already in flight — waits without
        blocking other shapes)."""
        inflight = self._inflight_sessions()
        keep: deque[Ticket] = deque()
        for t in self._queue:
            if t.request.session is not None \
                    and t.request.session in inflight:
                keep.append(t)
                continue
            if self._admit_one(t):
                if t.request.session is not None:
                    inflight.add(t.request.session)
            else:
                keep.append(t)
        self._queue = keep
        self.stats.observe_queue(len(self._queue))

    def _admit_one(self, ticket: Ticket) -> bool:
        req = ticket.request
        if ticket._handle is None:
            try:
                ticket._handle = self._resolve_handle(req)
            except Exception as exc:
                # malformed request (unknown session, bad update delta,
                # unbuildable problem): fail THIS request typed — the
                # loop must survive any single request
                self.stats.failed += 1
                self._resolve_error(ticket, RequestFailed(
                    req.request_id,
                    cause=f"{type(exc).__name__}: {exc}", attempts=0))
                return True               # resolved: drop from the queue
        h = ticket._handle
        # dtype strings join the shape key: a narrowed handle must never
        # share a batched executable with a wide one of the same dims
        key = _graph.bucket_shape_for(h.meta) + (
            h.meta.label_dtype, h.meta.flow_dtype, h.meta.mask_dtype)
        bucket = self._buckets.get(key)
        if bucket is not None and bucket.free_slot() is None:
            return False
        if ticket._inst is None:
            # B == 1 pack of the entry state: the swap-in payload
            ticket._inst = _graph.pack_built(
                [(0, h.meta, h._entry_state(), h.layout, h.state0)],
                pad_batch=False)[0]
        pack1 = ticket._inst
        if bucket is None:
            bucket = self._new_bucket(pack1)
            self._buckets[key] = bucket
        slot = bucket.free_slot()
        bucket.state, bucket.carry = bucket.ex.swap_slot(
            bucket.state, bucket.carry, slot, pack1.state)
        bound = _sweep.sweep_bound(h.meta, self._cfg)
        if self._cfg.max_sweeps is not None:
            bound = min(bound, self._cfg.max_sweeps)
        bucket.limits[slot] = min(bound, np.iinfo(np.int32).max)
        bucket.sweeps_host[slot] = 0
        bucket.slots[slot] = _Slot(ticket, h, req.session)
        self.stats.admitted += 1
        self.stats.swaps += 1
        ticket._inst = None               # the batch owns the state now
        return True

    def _new_bucket(self, pack1) -> _Bucket:
        """An empty ``max_batch``-slot batch of ``pack1``'s bucket shape
        (all-zero slots are inert: masked off, zero excess, converged at
        entry — exactly ``pack_built``'s batch padding)."""
        B = self.config.max_batch
        bmeta = dataclasses.replace(pack1.meta, num_instances=B)
        state = jax.tree_util.tree_map(
            lambda x: jnp.zeros((B,) + x.shape[1:], x.dtype), pack1.state)
        ex = _executor.BatchedExecutor(bmeta, self._cfg)
        return _Bucket(bmeta, state, ex.init_carry(state), ex)

    # -- chunk execution: breaker + ladder + retries -------------------------

    def _run_chunk(self, bucket: _Bucket):
        """Advance one bucket by up to ``sync_every`` sweeps per slot.

        Returns ``(host_carry, None)`` on success or ``(None, (exc,
        attempts))`` once retries are exhausted.  Kernel-class failures
        are recorded on the rung's breaker and degraded down the ladder
        (the pre-chunk state is intact, so the re-run is bit-exact);
        everything else is retried up to ``max_retries`` times.
        """
        cap = np.minimum(bucket.limits,
                         bucket.sweeps_host + self.config.sync_every)
        cfg, skips = self.board.entry_config(self._cfg)
        self.stats.breaker_skips += skips
        attempts = 0
        while True:
            rung = _res.config_rung(cfg)
            ex = _executor.BatchedExecutor(bucket.bmeta, cfg)
            try:
                state, carry = _executor._device_chunk(
                    ex, bucket.state, bucket.carry, jnp.asarray(cap, _I32))
                host = jax.device_get(carry)
                done = int(np.asarray(host[0]).max(initial=0))
                state = _executor._fire_fault_hook("device", state, done)
            except Exception as exc:       # noqa: BLE001 — every chunk
                #   fault maps to a typed outcome; nothing leaks upward
                self.stats.faults += 1
                attempts += 1
                if _res.is_kernel_failure(exc):
                    self.board.record(rung, ok=False)
                    self.stats.breaker_trips = self.board.trips
                    down = _res.degrade_config(cfg)
                    if down is not None:
                        self.stats.degradations += 1
                        cfg = down
                        continue
                if attempts <= self.config.max_retries:
                    self.stats.retries += 1
                    continue
                return None, (exc, attempts)
            self.board.record(rung, ok=True)
            bucket.state, bucket.carry = state, carry
            return host, None

    def _pump_bucket(self, bucket: _Bucket) -> None:
        host, failure = self._run_chunk(bucket)
        if host is None:
            exc, attempts = failure
            for b, slot in enumerate(bucket.slots):
                if slot is not None:
                    self._fail_slot(bucket, b, exc, attempts)
            return
        bucket.syncs += 1
        # np.array (not asarray): device_get buffers are read-only and
        # sweeps_host is written on swap-in
        sweeps, iters, stages, launches, n_act = (np.array(x) for x in host)
        now = self._clock()
        for b, slot in enumerate(bucket.slots):
            if slot is None:
                continue
            if n_act[b] == 0 or sweeps[b] >= bucket.limits[b]:
                self._harvest(bucket, b, sweeps, iters, stages,
                              int(launches), n_act)
            elif slot.ticket.deadline_at is not None \
                    and now >= slot.ticket.deadline_at:
                self._expire_slot(bucket, b, sweeps, now)
        bucket.sweeps_host = sweeps

    # -- slot resolution -----------------------------------------------------

    def _release(self, bucket: _Bucket, b: int) -> None:
        bucket.slots[b] = None
        bucket.limits[b] = 0   # run flag off until the next swap-in

    def _harvest(self, bucket: _Bucket, b: int, sweeps, iters, stages,
                 launches: int, n_act) -> None:
        """Unpack slot ``b`` into a ``MincutResult`` (the ``solve_many``
        unpacking, per slot) and leave the session handle warm."""
        slot = bucket.slots[b]
        h = slot.handle
        meta = h.meta
        K, V, E = meta.num_regions, meta.region_size, meta.max_degree
        bstate = bucket.state
        st = h.state0.replace(
            cf=bstate.cf[b, :K, :V, :E], sink_cf=bstate.sink_cf[b, :K, :V],
            excess=bstate.excess[b, :K, :V], d=bstate.d[b, :K, :V],
            flow_to_t=bstate.flow_to_t[b])
        sw = int(sweeps[b])
        converged = bool(n_act[b] == 0)
        page_bytes, msg_bytes = _sweep._page_and_msg_bytes(meta)
        stats = _sweep.SweepStats(
            sweeps=sw, engine_iters=int(iters[b]),
            ard_stages=int(stages[b]) if self._cfg.method == "ard" else None,
            engine_launches=launches, host_syncs=bucket.syncs,
            boundary_bytes=sw * msg_bytes,
            page_bytes=sw * meta.num_regions * page_bytes,
            regions_discharged=sw * meta.num_regions,
            scope="batch", converged=converged)
        h.state = st
        h.warm = True
        h._dirty = False
        h._grew = jnp.zeros((), bool)
        try:
            res = _finish(meta, h.state0, st, h.layout, stats,
                          self.options.check, offset=int(h._flow_offset),
                          converged=converged,
                          ard=self.options.method == "ard",
                          max_sweeps=self._cfg.max_sweeps)
        except AssertionError as exc:   # CertificateError: a wrong answer
            #   must not crash the loop; it fails THIS request, typed
            self.stats.failed += 1
            self._resolve_error(slot.ticket, RequestFailed(
                slot.ticket.request.request_id,
                cause=f"{type(exc).__name__}: {exc}", attempts=1))
            self._release(bucket, b)
            return
        self._resolve_result(slot.ticket, res)
        self._release(bucket, b)

    def _expire_slot(self, bucket: _Bucket, b: int, sweeps,
                     now: float) -> None:
        slot = bucket.slots[b]
        t = slot.ticket
        partial = int(jax.device_get(bucket.state.flow_to_t[b])) \
            - int(slot.handle._flow_offset)
        self.stats.deadline_misses += 1
        self._resolve_error(t, DeadlineExceeded(
            t.request.request_id, deadline=t.deadline_at - t.submitted_at,
            elapsed=now - t.submitted_at, sweeps_completed=int(sweeps[b]),
            partial_flow=partial, stage="running"))
        self._release(bucket, b)

    def _fail_slot(self, bucket: _Bucket, b: int, exc: Exception,
                   attempts: int) -> None:
        slot = bucket.slots[b]
        self.stats.failed += 1
        self._resolve_error(slot.ticket, RequestFailed(
            slot.ticket.request.request_id,
            cause=f"{type(exc).__name__}: {exc}", attempts=attempts))
        self._release(bucket, b)

    # -- session LRU + eviction ----------------------------------------------

    @staticmethod
    def _handle_bytes(h: ProblemHandle) -> int:
        seen: set[int] = set()
        total = 0
        for leaf in jax.tree_util.tree_leaves((h.state, h.state0)):
            if id(leaf) in seen:
                continue   # state/state0 share topology buffers
            seen.add(id(leaf))
            total += getattr(leaf, "nbytes", 0)
        return total

    def _resident_bytes(self) -> int:
        return sum(self._handle_bytes(h) for h in self._sessions.values())

    def _enforce_budget(self) -> None:
        budget = self.config.handle_budget_bytes
        if budget is None or self.config.eviction_dir is None:
            return
        inflight = self._inflight_sessions()
        queued = {t.request.session for t in self._queue
                  if t.request.session}
        while self._resident_bytes() > budget:
            victim = next((k for k in self._sessions
                           if k not in inflight and k not in queued), None)
            if victim is None:
                break   # everything resident is busy; over budget for now
            self._evict_session(victim)

    def _evict_session(self, key: str) -> None:
        h = self._sessions.pop(key)
        d = Path(self.config.eviction_dir) / key
        step = self._evict_seq
        self._evict_seq += 1
        _res.snapshot_save(
            d, step,
            {"state": _res.state_payload(h.state),
             "state0": _res.state_payload(h.state0)},
            extra={"kind": "evicted_session", "session": key,
                   "flow_offset": int(h._flow_offset),
                   "warm": bool(h.warm), "dirty": bool(h._dirty),
                   "grew": bool(h._grew)})
        self._evicted[key] = {"problem": h.problem, "part": h.part,
                              "dir": str(d), "step": step}
        self.stats.evictions += 1

    def _restore_session(self, key: str) -> ProblemHandle:
        """Re-prepare an evicted session and pour its checkpointed state
        back in — the next solve runs warm, as if never evicted."""
        info = self._evicted.pop(key)
        h = self.solver.prepare(info["problem"], info["part"])
        like = {"state": _res.state_payload(h.state),
                "state0": _res.state_payload(h.state0)}
        payload = _res.snapshot_restore(info["dir"], info["step"], like)
        h.state = _res.restore_state(h.state, payload["state"])
        h.state0 = _res.restore_state(h.state0, payload["state0"])
        extra = _res.snapshot_manifest(info["dir"], info["step"])["extra"]
        h.warm = bool(extra["warm"])
        h._dirty = bool(extra["dirty"])
        h._grew = jnp.asarray(bool(extra["grew"]))
        h._flow_offset = jnp.asarray(int(extra["flow_offset"]), _I32)
        self._sessions[key] = h
        self.stats.warm_resumes += 1
        return h

    def _refresh_gauges(self) -> None:
        self.stats.observe_queue(len(self._queue))
        self.stats.in_flight = sum(
            1 for b in self._buckets.values()
            for s in b.slots if s is not None)
        self.stats.resident_bytes = self._resident_bytes()


# --------------------------------------------------------------------------
# single-handle deadline route + stream replay
# --------------------------------------------------------------------------

class _DeadlineAbort(Exception):
    """Internal control-flow signal of ``solve_with_deadline``."""


def solve_with_deadline(handle: ProblemHandle, *, timeout: float,
                        clock=None, mesh=None,
                        axes=("regions",)) -> MincutResult:
    """``handle.solve()`` with a deadline enforced at sweep boundaries.

    The same enforcement points as the service's bucket loop, through the
    single-handle routes' ``on_sweep`` hook: every boundary on the host
    loop, the ``host_sync_every`` boundaries on the device-resident and
    sharded drivers (which therefore need ``host_sync_every`` set).
    Raises :class:`~repro.serve.errors.DeadlineExceeded` with
    sweeps-completed and partial-flow diagnostics; the handle's resident
    state is left untouched by an aborted solve.
    """
    clock = clock if clock is not None else time.monotonic
    t0 = clock()
    deadline = t0 + timeout
    seen: dict = {"sweeps": 0, "flow": None}

    def on_sweep(state, sweeps_done):
        seen["sweeps"] = sweeps_done
        seen["flow"] = state.flow_to_t
        if clock() >= deadline:
            raise _DeadlineAbort()

    try:
        return handle.solve(mesh=mesh, axes=axes, on_sweep=on_sweep)
    except _DeadlineAbort:
        partial = None
        if seen["flow"] is not None:
            partial = int(jax.device_get(seen["flow"])) \
                - int(handle._flow_offset)
        raise DeadlineExceeded(
            "solve", deadline=timeout, elapsed=clock() - t0,
            sweeps_completed=seen["sweeps"], partial_flow=partial,
            stage="running") from None


def replay_stream(service: MaxflowService, requests, *,
                  rate: float | None = None) -> list[Ticket]:
    """Feed ``requests`` into ``service`` at ``rate`` req/s (None: one
    burst), stepping the service while pacing, then drain.  Returns the
    tickets in submission order — the bench/CLI driver.

    Pacing honors the offered rate even when a single ``step()`` takes
    several intervals: every request whose scheduled time has already
    passed is submitted before the next step, so a slow service sees the
    backlog (and sheds) instead of silently throttling the stream.  Rate
    pacing needs a real (advancing) clock; with ``rate=None`` the whole
    stream is one burst and any clock works."""
    tickets = []
    reqs = list(requests)
    interval = 0.0 if not rate else 1.0 / rate
    start = service._clock()
    i = 0
    while i < len(reqs):
        if not rate or service._clock() >= start + i * interval:
            tickets.append(service.submit(reqs[i]))
            i += 1
            continue
        service.step()
        if not service.pending:
            # idle and ahead of schedule: wait out the gap (stepping an
            # idle service burns CPU without advancing the stream)
            gap = (start + i * interval) - service._clock()
            if gap > 0:
                time.sleep(min(gap, 0.01))
    service.run_until_idle()
    return tickets


__all__ = ["MaxflowService", "ServiceConfig", "SolveRequest", "Ticket",
           "replay_stream", "solve_with_deadline"]
