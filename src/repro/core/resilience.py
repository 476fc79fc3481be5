"""Fault-tolerant solves: sweep-boundary checkpoints, a solve supervisor
with deterministic fault injection, and a graceful-degradation ladder.

The paper's deployment model is failure-prone by construction — regions
"loaded into the memory one-by-one or located on separate machines in a
network" — so a solve must survive preemption, device loss and kernel
resource-exhaustion failures instead of losing every sweep.  Three layers:

**Sweep-boundary checkpoints.**  A :class:`SolveCheckpoint` captures the
mutable flow state (``cf``/``sink_cf``/``excess``/``d``/``flow_to_t``),
the accumulated :class:`~repro.core.sweep.SweepStats` accounting
(counters + curve tails), the warm-start flow offset of the owning
session handle, and a config/layout fingerprint.  Every route exposes a
capture point at its natural host boundary — the ``on_obs`` hook of the
host loop, the ``on_sync`` hook of the device-resident/batched/sharded
loops — and writes snapshots atomically (write-to-temp, fsync-free
``os.rename`` publish: a crashed writer never corrupts the latest
checkpoint).  ``sweep.solve(resume_from=)`` / ``handle.solve(
resume_from=)`` / ``Solver.solve_many(resume_from=)`` /
``distributed.solve_sharded(resume_from=)`` continue BIT-EXACTLY: an
interrupted-then-resumed solve matches the uninterrupted one on flow,
labels, sweeps and engine iterations (asserted per boundary in
tests/test_resilience.py).

**Solve supervisor + fault injection.**  :class:`SolveSupervisor` wraps
any route with checkpoint-every-N-sweeps, retry with exponential backoff
and resume-from-latest.  The deterministic :class:`FaultPlan` (raise at
sweep k, corrupt boundary-exchange labels, simulate preemption, force a
VMEM overflow) installs into the test-only hook of ``core.executor`` via
:func:`fault_injection`, so every executor is exercised under the same
fault matrix.

**Degradation ladder.**  Accelerator resource exhaustion (VMEM or device
memory) degrades the engine configuration one rung at a time —
pallas-fused -> xla-fused -> xla-unfused (:func:`degrade_config`) —
re-running the route on the next rung; every rung is bit-exact by the
repo's engine-equivalence invariant, and every degradation is recorded
in ``SweepStats.degraded`` (never silent).  The engine's build-time
static VMEM fallback is surfaced the same way (:func:`vmem_fallback_note`).

This module also owns the ONE atomic-snapshot implementation
(:func:`snapshot_save`/:func:`snapshot_restore`/:func:`snapshot_latest`),
adopted from the orphan ``train/checkpoint.py`` scaffolding — which now
delegates here.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable

import jax
import numpy as np

from repro.core import executor as _executor

# --------------------------------------------------------------------------
# error surface
# --------------------------------------------------------------------------


class InjectedFault(RuntimeError):
    """A deterministic test fault raised by a :class:`FaultPlan`."""


class PreemptionError(InjectedFault):
    """Simulated preemption: the solve process is torn down mid-solve."""


class VmemOverflowError(RuntimeError):
    """Kernel region state exceeds the VMEM budget (real or injected) —
    a kernel-class failure the degradation ladder handles."""


class CheckpointMismatchError(ValueError):
    """A checkpoint's fingerprint does not match the solve it would resume
    (different method/heuristics, different problem layout)."""


# --------------------------------------------------------------------------
# degradation ladder: pallas-fused -> xla-fused -> xla-unfused
# --------------------------------------------------------------------------

KERNEL_LADDER = ("pallas-fused", "xla-fused", "xla-unfused")


def config_rung(cfg) -> str:
    """The ladder rung a ``SweepConfig``'s engine knobs sit on."""
    fused = "fused" if cfg.engine_chunk_iters is not None else "unfused"
    return f"{cfg.engine_backend}-{fused}"


def degrade_config(cfg):
    """One rung down — or ``None`` at the bottom (nothing left to shed).

    pallas anything -> same shape on xla (sheds the Pallas kernel);
    xla-fused -> xla-unfused (sheds the chunked resident engine).  Every
    rung computes bit-identical results (the repo's engine-equivalence
    invariant), so degradation changes performance, never answers.
    """
    if cfg.engine_backend == "pallas":
        return dataclasses.replace(cfg, engine_backend="xla")
    if cfg.engine_chunk_iters is not None:
        return dataclasses.replace(cfg, engine_chunk_iters=None)
    return None


def is_kernel_failure(exc: BaseException) -> bool:
    """Is this exception a genuine accelerator resource exhaustion (VMEM or
    device memory) that a cheaper rung may avoid?

    Only :class:`VmemOverflowError` and ``RESOURCE_EXHAUSTED`` qualify.  A
    lowering refusal, a missing API or a logic error raises: stepping down
    on those would hide that the requested route never ran on the device.
    """
    if isinstance(exc, VmemOverflowError):
        return True
    if isinstance(exc, InjectedFault):
        return False
    return "RESOURCE_EXHAUSTED" in f"{exc}"


def run_with_degradation(run: Callable, cfg, notes: list[str]):
    """Run ``run(cfg)``, stepping down the ladder on kernel failures.

    Appends one note per degradation to ``notes`` (the caller surfaces
    them in ``SweepStats.degraded``).  Non-kernel failures and a ladder
    that bottoms out re-raise.  Returns ``run``'s result.
    """
    while True:
        try:
            return run(cfg)
        except Exception as exc:          # noqa: BLE001 — classified below
            nxt = degrade_config(cfg)
            if nxt is None or not is_kernel_failure(exc):
                raise
            notes.append(
                f"{config_rung(cfg)} -> {config_rung(nxt)}: "
                f"{type(exc).__name__}: {exc}")
            cfg = nxt


def vmem_fallback_note(cfg, region_size: int, max_degree: int,
                       dtypes=None) -> str | None:
    """Surface the engine's build-time static VMEM fallback.

    The fused pallas engine silently falls back to the blocked two-phase
    path when a region's resident state exceeds the VMEM budget
    (``kernels.push_relabel.fused_region_fits_vmem``); this returns the
    degradation note the drivers record in ``SweepStats.degraded`` so the
    fallback is visible (results are bit-exact either way).
    """
    if cfg.engine_backend != "pallas" or cfg.engine_chunk_iters is None:
        return None
    from repro.kernels import push_relabel as _pr
    if _pr.fused_region_fits_vmem(region_size, max_degree, dtypes=dtypes):
        return None
    return (f"pallas-fused: region state (V={region_size}, E={max_degree}) "
            f"exceeds the VMEM budget; engine uses the blocked two-phase "
            f"path (bit-exact)")


# --------------------------------------------------------------------------
# atomic pytree snapshots (the ONE implementation; train/checkpoint.py
# delegates here)
# --------------------------------------------------------------------------

MANIFEST = "manifest.json"


def _flatten_with_paths(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for kp, leaf in flat:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        out.append((path, leaf))
    return out


def snapshot_save(directory: str | Path, step: int, state: Any,
                  extra: dict | None = None) -> Path:
    """Atomically snapshot a pytree of arrays under ``<dir>/step_NNNNNNNN``.

    Every leaf is saved into one .npz together with a manifest recording
    tree structure, dtypes and shapes (bf16 stored as a raw uint16 view).
    The publish step is an atomic ``os.rename`` of the fully-written temp
    directory — a crashed writer never corrupts the latest snapshot,
    which is the property every resume path here relies on.
    """
    directory = Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    final = directory / f"step_{step:08d}"
    tmp = directory / f"step_{step:08d}.tmp"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)

    leaves = _flatten_with_paths(state)
    manifest = {"step": step, "leaves": [], "extra": extra or {}}
    arrays = {}
    for i, (path, leaf) in enumerate(leaves):
        arr = np.asarray(jax.device_get(leaf))
        key = f"a{i:05d}"
        # bf16 has no numpy dtype: store raw uint16 view + dtype tag
        dtype = str(arr.dtype) if not hasattr(leaf, "dtype") \
            else str(leaf.dtype)
        if dtype == "bfloat16":
            arr = arr.view(np.uint16)
        arrays[key] = arr
        manifest["leaves"].append(
            {"path": path, "key": key, "dtype": dtype,
             "shape": list(arr.shape)})
    np.savez(tmp / "arrays.npz", **arrays)
    (tmp / MANIFEST).write_text(json.dumps(manifest, indent=1))
    if final.exists():
        shutil.rmtree(final)
    os.rename(tmp, final)                      # atomic publish
    return final


def snapshot_latest(directory: str | Path) -> int | None:
    """Highest fully-published snapshot step in ``directory`` (or None)."""
    directory = Path(directory)
    if not directory.exists():
        return None
    steps = []
    for p in directory.iterdir():
        if p.is_dir() and p.name.startswith("step_") \
                and not p.name.endswith(".tmp") \
                and (p / MANIFEST).exists():
            steps.append(int(p.name[5:]))
    return max(steps) if steps else None


def snapshot_manifest(directory: str | Path, step: int) -> dict:
    return json.loads(
        (Path(directory) / f"step_{step:08d}" / MANIFEST).read_text())


def _snapshot_arrays(directory: str | Path, step: int) -> tuple[dict, dict]:
    """(path -> numpy array, manifest) of one snapshot, dtype-restored."""
    path = Path(directory) / f"step_{step:08d}"
    manifest = json.loads((path / MANIFEST).read_text())
    data = np.load(path / "arrays.npz")
    by_path = {}
    for leaf in manifest["leaves"]:
        arr = data[leaf["key"]]
        if leaf["dtype"] == "bfloat16":
            import ml_dtypes
            arr = arr.view(ml_dtypes.bfloat16)
        by_path[leaf["path"]] = arr
    return by_path, manifest


def snapshot_restore(directory: str | Path, step: int, like: Any,
                     shardings: Any | None = None) -> Any:
    """Restore into the structure of ``like`` (a pytree of arrays or
    ShapeDtypeStructs).  ``shardings`` (same structure) re-lays the arrays
    onto the *current* mesh — the elastic path.
    """
    by_path, _manifest = _snapshot_arrays(directory, step)
    like_leaves = _flatten_with_paths(like)
    treedef = jax.tree_util.tree_structure(like)
    shard_leaves = (jax.tree_util.tree_leaves(shardings)
                    if shardings is not None else [None] * len(like_leaves))
    out = []
    for (lpath, lleaf), sh in zip(like_leaves, shard_leaves):
        if lpath not in by_path:
            raise KeyError(f"checkpoint missing leaf {lpath!r}")
        arr = by_path[lpath]
        if tuple(arr.shape) != tuple(lleaf.shape):
            raise ValueError(
                f"shape mismatch for {lpath}: ckpt {arr.shape} "
                f"vs state {lleaf.shape}")
        if sh is not None:
            out.append(jax.device_put(arr, sh))
        else:
            out.append(jax.numpy.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------
# solve checkpoints
# --------------------------------------------------------------------------

def solve_fingerprint(meta, cfg, salt: str = "") -> str:
    """Identity of the math a checkpoint belongs to.

    Hashes the problem layout (``GraphMeta``/``BatchMeta`` — all padded
    shapes and label ceilings), the *math-affecting* ``SweepConfig``
    fields (method, Alg. 1/2, heuristics) and an optional caller salt
    (the session front-end hashes ``Layout.part`` so two same-shaped
    problems do not cross-resume).  Engine-backend knobs, sweep budgets
    and accounting knobs are deliberately EXCLUDED: every backend rung and
    every route computes bit-identical states, so resuming a pallas-fused
    device-resident solve on the xla host loop — or after a degradation —
    is exact and allowed.
    """
    math_fields = ("method", "parallel", "partial_discharge",
                   "use_global_gap", "use_boundary_relabel")
    key = "|".join([repr(meta)]
                   + [f"{f}={getattr(cfg, f)!r}" for f in math_fields]
                   + [salt])
    return hashlib.sha256(key.encode()).hexdigest()[:16]


@dataclass(frozen=True)
class CheckpointPolicy:
    """Where and how often a route captures :class:`SolveCheckpoint`\\ s.

    ``every`` — sweep cadence: the host loop saves at each sweep boundary
    whose absolute index advanced >= ``every`` past the last save; the
    device-resident routes save at their ``host_sync_every`` boundaries
    under the same rule (a sync boundary is the only host re-entry they
    have).  ``flow_offset`` — the owning session handle's warm-start
    flow-value offset, recorded so a cross-process resume restores the
    handle bookkeeping.  ``salt`` — extra fingerprint input (the session
    front-end's layout digest).
    """

    directory: str | Path
    every: int = 5
    flow_offset: int = 0
    salt: str = ""

    def __post_init__(self):
        assert self.every >= 1


@dataclass
class SolveCheckpoint:
    """One resumable sweep-boundary snapshot of a solve.

    ``payload`` — the mutable device state (``cf``/``sink_cf``/``excess``/
    ``d``/``flow_to_t`` as host numpy arrays) plus the route's loop-carry
    scalars/arrays (``n_act``; per-instance ``sweeps``/``iters`` arrays on
    the batched route; on the streaming route the payload is the O(|B|)
    boundary layer plus the spill pool's per-region version vector — the
    region interiors themselves stay in the pool, already durable).
    ``stats`` — the accumulated ``SweepStats``
    accounting at the boundary (counters, curve tails, syncs, degradation
    notes).  ``sweeps`` — absolute sweep index of the boundary (max over
    instances on the batched route); doubles as the snapshot step, so
    ``snapshot_latest`` finds the furthest boundary.
    """

    fingerprint: str
    route: str               # "host" | "device" | "sharded" | "batch"
    #                          | "stream"
    sweeps: int
    payload: dict
    stats: dict
    flow_offset: int = 0


def state_payload(state) -> dict:
    """Host copies of the mutable flow-state fields (one device fetch)."""
    cf, sink_cf, excess, d, flow = jax.device_get(
        (state.cf, state.sink_cf, state.excess, state.d, state.flow_to_t))
    return {"cf": np.asarray(cf), "sink_cf": np.asarray(sink_cf),
            "excess": np.asarray(excess), "d": np.asarray(d),
            "flow_to_t": np.asarray(flow)}


def restore_state(state, payload: dict):
    """The inverse of :func:`state_payload` on a live state pytree."""
    import jax.numpy as jnp
    return state.replace(
        cf=jnp.asarray(payload["cf"]),
        sink_cf=jnp.asarray(payload["sink_cf"]),
        excess=jnp.asarray(payload["excess"]),
        d=jnp.asarray(payload["d"]),
        flow_to_t=jnp.asarray(payload["flow_to_t"]))


def save_checkpoint(directory: str | Path, ckpt: SolveCheckpoint) -> Path:
    """Atomically publish a checkpoint at step ``ckpt.sweeps``."""
    return snapshot_save(
        directory, ckpt.sweeps, ckpt.payload,
        extra={"kind": "solve_checkpoint", "fingerprint": ckpt.fingerprint,
               "route": ckpt.route, "sweeps": ckpt.sweeps,
               "stats": ckpt.stats, "flow_offset": ckpt.flow_offset})


def load_checkpoint(directory: str | Path,
                    step: int | None = None) -> SolveCheckpoint:
    """Load a checkpoint (the latest when ``step`` is None)."""
    if step is None:
        step = snapshot_latest(directory)
        if step is None:
            raise FileNotFoundError(
                f"no checkpoint under {directory!r}")
    payload, manifest = _snapshot_arrays(directory, step)
    extra = manifest["extra"]
    if extra.get("kind") != "solve_checkpoint":
        raise CheckpointMismatchError(
            f"snapshot {directory}/step_{step:08d} is not a solve "
            f"checkpoint")
    return SolveCheckpoint(
        fingerprint=extra["fingerprint"], route=extra["route"],
        sweeps=int(extra["sweeps"]), payload=payload,
        stats=extra["stats"], flow_offset=int(extra.get("flow_offset", 0)))


def latest_checkpoint(directory: str | Path) -> SolveCheckpoint | None:
    """The furthest published checkpoint, or None when none exist."""
    step = snapshot_latest(directory)
    return None if step is None else load_checkpoint(directory, step)


def checkpoint_converged(ckpt: SolveCheckpoint) -> bool:
    """True when the checkpoint was captured at a CONVERGED final boundary.

    The payload's ``n_act`` loop-carry records the active-vertex count at
    the boundary (per instance on the batched route): all-zero means the
    maximum preflow was already reached and there is nothing left to
    sweep, so a resume can return the restored state directly instead of
    re-entering the sweep loop (the sharded loop's converged-entry
    semantics would otherwise burn one no-op sweep).  A checkpoint without
    the carry (foreign/legacy payloads) conservatively counts as not
    converged.
    """
    n_act = ckpt.payload.get("n_act")
    if n_act is None:
        return False
    return bool((np.asarray(n_act) == 0).all())


def resolve_resume(resume_from, fingerprint: str) -> SolveCheckpoint | None:
    """Normalize a route's ``resume_from`` argument and verify identity.

    Accepts a :class:`SolveCheckpoint`, a checkpoint directory (loads the
    latest), or None.  Raises :class:`CheckpointMismatchError` when the
    checkpoint belongs to different math/layout than the solve it would
    resume.
    """
    if resume_from is None:
        return None
    if isinstance(resume_from, (str, Path)):
        resume_from = load_checkpoint(resume_from)
    if resume_from.fingerprint != fingerprint:
        raise CheckpointMismatchError(
            f"checkpoint fingerprint {resume_from.fingerprint} != solve "
            f"fingerprint {fingerprint}: the checkpoint was taken under "
            f"a different method/heuristic configuration or problem "
            f"layout and cannot resume this solve")
    return resume_from


# --------------------------------------------------------------------------
# deterministic fault injection
# --------------------------------------------------------------------------

@dataclass
class FaultPlan:
    """A deterministic fault fired at a sweep boundary.

    ``kind`` — ``"raise"`` (a generic mid-solve failure), ``"preempt"``
    (simulated preemption: :class:`PreemptionError`), ``"vmem_overflow"``
    (a kernel-class :class:`VmemOverflowError` the degradation ladder
    handles), or ``"corrupt_labels"`` (silently pins every boundary
    vertex's label at the ceiling — the boundary-exchange corruption that
    makes a solve "converge" to a WRONG answer, which the cut==flow
    certificate must catch).  Fires at the first boundary whose absolute
    sweep count reaches ``at_sweep``, at most ``times`` times (-1: every
    boundary from there on).  ``route`` optionally restricts firing to
    ``"host"`` or ``"device"`` boundaries.
    """

    kind: str
    at_sweep: int
    times: int = 1
    route: str | None = None
    fired: int = field(default=0, compare=False)

    def __post_init__(self):
        assert self.kind in ("raise", "preempt", "vmem_overflow",
                             "corrupt_labels"), self.kind

    def __call__(self, route: str, state, sweeps_done: int):
        if self.route is not None and route != self.route:
            return None
        if sweeps_done < self.at_sweep:
            return None
        if self.times >= 0 and self.fired >= self.times:
            return None
        self.fired += 1
        where = f"at sweep {sweeps_done} ({route} boundary)"
        if self.kind == "raise":
            raise InjectedFault(f"injected fault {where}")
        if self.kind == "preempt":
            raise PreemptionError(f"injected preemption {where}")
        if self.kind == "vmem_overflow":
            raise VmemOverflowError(
                f"injected VMEM overflow {where}: fused region state "
                f"exceeds the VMEM budget")
        # corrupt_labels: pin boundary labels at the ceiling — excess
        # trapped there goes inactive, the solve stops early with a
        # too-small flow, and check=True must refuse to certify it
        import jax.numpy as jnp

        from repro.core import dtypes as _dt
        inf = state.d.dtype.type(_dt.inf_label_for(state.d.dtype.name))
        d = jnp.where(state.is_boundary & state.vmask, inf, state.d)
        return state.replace(d=d)


@contextmanager
def fault_injection(plan: FaultPlan | Callable | None):
    """Install a fault plan into the executor hook for the ``with`` body.

    The previous hook is restored on exit, including on the injected
    exception itself — the hook never leaks across tests.
    """
    prev = _executor.set_fault_hook(plan)
    try:
        yield plan
    finally:
        _executor.set_fault_hook(prev)


# --------------------------------------------------------------------------
# the solve supervisor
# --------------------------------------------------------------------------

@dataclass
class RetryPolicy:
    """Exponential backoff schedule of the supervisor's retries.

    ``sleep`` is injectable so tests run the full schedule without wall
    time.  Delay of retry i (1-based): ``min(base * factor**(i-1), max)``.
    """

    max_retries: int = 3
    backoff_base: float = 0.05
    backoff_factor: float = 2.0
    backoff_max: float = 2.0
    sleep: Callable = time.sleep


@dataclass
class SupervisorReport:
    """What one supervised solve went through."""

    attempts: int = 0
    resumes: int = 0
    backoffs: list = field(default_factory=list)
    failures: list = field(default_factory=list)


class SolveSupervisor:
    """Run any solve route to completion across failures.

    Wraps a ``runner(policy, resume_from) -> result`` closure (build one
    with :meth:`for_handle` or :meth:`for_batch`) with checkpoint-every-N
    sweeps, retry-with-exponential-backoff and resume-from-latest: each
    failed attempt sleeps the backoff, reloads the newest checkpoint the
    failed attempt published, and re-enters the route, which continues
    bit-exactly from that boundary.  Kernel-class failures are already
    absorbed one level down by the degradation ladder inside the routes
    (recorded in ``SweepStats.degraded``); what reaches the supervisor is
    the process-level failure matrix — preemptions, device loss, injected
    faults — plus anything the ladder could not shed.
    """

    def __init__(self, runner: Callable, *, checkpoint_dir: str | Path,
                 checkpoint_every: int = 5,
                 retry: RetryPolicy | None = None,
                 policy: CheckpointPolicy | None = None):
        self.runner = runner
        self.policy = policy if policy is not None else CheckpointPolicy(
            directory=checkpoint_dir, every=checkpoint_every)
        self.retry = retry or RetryPolicy()
        self.report = SupervisorReport()

    @classmethod
    def for_handle(cls, handle, *, mesh=None, axes=("regions",), **kw):
        """Supervise ``handle.solve()`` (host/device-resident/sharded)."""
        def runner(policy, resume_from):
            return handle.solve(mesh=mesh, axes=axes, checkpoint=policy,
                                resume_from=resume_from)
        return cls(runner, **kw)

    @classmethod
    def for_batch(cls, solver, items, parts=None, **kw):
        """Supervise ``solver.solve_many(items)`` (the batched route)."""
        def runner(policy, resume_from):
            return solver.solve_many(items, parts, checkpoint=policy,
                                     resume_from=resume_from)
        return cls(runner, **kw)

    def _latest(self) -> SolveCheckpoint | None:
        return latest_checkpoint(self.policy.directory)

    def solve(self, *, resume: bool | str = "auto"):
        """Drive the route to a result; raises only when retries exhaust.

        ``resume`` — ``"auto"``/True: start from the latest checkpoint in
        the policy directory when one exists (the restart-after-kill
        path); False: first attempt starts fresh (later retries still
        resume from what this run checkpointed).
        """
        resume_from = self._latest() if resume in ("auto", True) else None
        attempt = 0
        while True:
            self.report.attempts += 1
            try:
                return self.runner(self.policy, resume_from)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as exc:      # noqa: BLE001 — retried/re-raised
                attempt += 1
                self.report.failures.append(
                    f"{type(exc).__name__}: {exc}")
                if attempt > self.retry.max_retries:
                    raise
                delay = min(
                    self.retry.backoff_base
                    * self.retry.backoff_factor ** (attempt - 1),
                    self.retry.backoff_max)
                self.report.backoffs.append(delay)
                self.retry.sleep(delay)
                resume_from = self._latest()
                if resume_from is not None:
                    self.report.resumes += 1


__all__ = [
    "CheckpointMismatchError", "CheckpointPolicy", "FaultPlan",
    "InjectedFault", "KERNEL_LADDER", "PreemptionError", "RetryPolicy",
    "SolveCheckpoint", "SolveSupervisor", "SupervisorReport",
    "VmemOverflowError", "checkpoint_converged", "config_rung",
    "degrade_config",
    "fault_injection", "is_kernel_failure", "latest_checkpoint",
    "load_checkpoint", "resolve_resume", "restore_state",
    "run_with_degradation", "save_checkpoint", "snapshot_latest",
    "snapshot_manifest", "snapshot_restore", "snapshot_save",
    "solve_fingerprint", "state_payload", "vmem_fallback_note",
]
