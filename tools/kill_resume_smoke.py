"""Kill-and-resume smoke: a REAL process death, not a simulated one.

The in-process fault matrix (tests/test_resilience.py) injects exceptions;
this script closes the remaining gap in the deployment story by SIGKILLing
a checkpointing solve mid-sweep — no cleanup handlers, no atexit, exactly
what a preempted worker looks like — and then resuming from whatever the
dead process managed to publish:

1. a child process runs the solve with sweep-boundary checkpoints and
   ``os.kill(getpid(), SIGKILL)`` at sweep K (installed through the
   executor fault hook, which fires AFTER the boundary's checkpoint);
   the parent touches no device until the child is dead, so the child
   can hold the accelerator;
2. the parent solves the instance uninterrupted (the baseline);
3. the parent asserts the child died on SIGKILL, that the latest published
   checkpoint is a mid-solve boundary, resumes from it, and asserts the
   result is BIT-EXACT against the baseline (flow, labels, residuals,
   sweep count, engine iterations, curves).

The atomic write-to-temp-then-rename snapshot protocol is what makes step
3 safe: a snapshot the child was writing when it died is a ``.tmp`` dir
the resume never sees.

``--streaming`` runs the same protocol through the out-of-core route:
the child builds the instance straight into a DURABLE spill pool
(``<ckdir>_pool``), checkpoints the |B|-sized boundary layer + pool
version vector at every sweep boundary, and dies mid-solve; the resume
re-attaches the surviving pool at the checkpointed versions — including
any orphan newer versions the dead process published after its last
checkpoint — and must match the uninterrupted streamed solve bit-exactly.

Usage (CI: the ``resilience`` and ``streaming`` jobs):

    PYTHONPATH=src python tools/kill_resume_smoke.py
    PYTHONPATH=src python tools/kill_resume_smoke.py --streaming
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

KILL_AT = 3


def _built():
    import numpy as np

    from repro.core import build, grid_partition
    from repro.data.grids import synthetic_grid

    p = synthetic_grid(10, 10, connectivity=8, strength=150, seed=0)
    part = np.asarray(grid_partition((10, 10), (2, 2)))
    meta, state, _ = build(p, part)
    return meta, state


def child(ckdir: str) -> None:
    """Checkpoint every boundary; die hard at sweep KILL_AT."""
    from repro.core import executor, init_labels, resilience
    from repro.core.sweep import SweepConfig, solve

    def die(route, state, sweeps_done):
        if sweeps_done >= KILL_AT:
            os.kill(os.getpid(), signal.SIGKILL)   # no goodbye

    executor.set_fault_hook(die)
    meta, state = _built()
    solve(meta, init_labels(meta, state), SweepConfig(method="ard"),
          checkpoint=resilience.CheckpointPolicy(directory=ckdir, every=1))
    raise SystemExit("unreachable: the solve outlived its kill sweep")


def _stream_cfg():
    from repro.core.sweep import SweepConfig

    return SweepConfig(method="ard", parallel=False, use_global_gap=False)


def _stream_problem():
    import numpy as np

    from repro.core import grid_partition
    from repro.data.grids import synthetic_grid

    p = synthetic_grid(10, 10, connectivity=8, strength=150, seed=0)
    return p, np.asarray(grid_partition((10, 10), (2, 2)))


def child_streaming(ckdir: str) -> None:
    """Streamed solve into a durable pool; die hard at sweep KILL_AT."""
    from repro.core import executor, resilience
    from repro.stream import build_stream, solve_stream

    def die(route, state, sweeps_done):
        if sweeps_done >= KILL_AT:
            os.kill(os.getpid(), signal.SIGKILL)   # no goodbye

    executor.set_fault_hook(die)
    p, part = _stream_problem()
    ss = build_stream(p, part, _stream_cfg(), spill_dir=ckdir + "_pool",
                      prefetch=False)
    solve_stream(ss, checkpoint=resilience.CheckpointPolicy(
        directory=ckdir, every=1))
    raise SystemExit("unreachable: the solve outlived its kill sweep")


def _run_child(ckdir: str, *flags: str) -> None:
    """Run the doomed child and check that it died on SIGKILL.  The parent
    touches no device before this returns: a chip serves one process."""
    proc = subprocess.run(
        [sys.executable, __file__, *flags, "--child", ckdir],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == -signal.SIGKILL, (
        f"child exited {proc.returncode}, wanted SIGKILL "
        f"({-signal.SIGKILL})\n--- child stderr ---\n{proc.stderr}")


def parent_streaming(ckdir: str) -> None:
    import numpy as np

    from repro.core import resilience
    from repro.stream import build_stream, solve_stream

    _run_child(ckdir, "--streaming")
    p, part = _stream_problem()
    ss = build_stream(p, part, _stream_cfg(), prefetch=False)
    ss, base_stats = solve_stream(ss)
    base_bnd = (ss.bnd.d_B.copy(), ss.bnd.e_B.copy(), ss.bnd.flow_to_t)
    ss.store.close()
    assert base_stats.sweeps > KILL_AT, \
        f"instance converges in {base_stats.sweeps} sweeps; nothing to kill"

    latest = resilience.latest_checkpoint(ckdir)
    assert latest is not None, "the killed child published no checkpoint"
    assert latest.route == "stream", latest.route
    assert latest.sweeps == KILL_AT, \
        f"latest checkpoint at sweep {latest.sweeps}, wanted {KILL_AT}"
    print(f"[kill-resume --streaming] child SIGKILLed; latest checkpoint "
          f"at sweep {latest.sweeps}/{base_stats.sweeps}")

    # resume against the pool the dead process left behind
    ss2 = build_stream(p, part, _stream_cfg(), spill_dir=ckdir + "_pool",
                       prefetch=False)
    ss2, stats = solve_stream(ss2, resume_from=ckdir)
    np.testing.assert_array_equal(ss2.bnd.d_B, base_bnd[0])
    np.testing.assert_array_equal(ss2.bnd.e_B, base_bnd[1])
    assert ss2.bnd.flow_to_t == base_bnd[2]
    for k in ("sweeps", "engine_iters", "flow_curve", "converged"):
        assert getattr(stats, k) == getattr(base_stats, k), k
    assert stats.staged_in_bytes > 0
    ss2.store.close()
    print(f"[kill-resume --streaming] resumed {latest.sweeps} -> "
          f"{stats.sweeps} sweeps: flow={base_bnd[2]} — bit-exact vs "
          f"uninterrupted. OK")


def parent(ckdir: str) -> None:
    import numpy as np

    from repro.core import init_labels, resilience
    from repro.core.sweep import SweepConfig, solve

    _run_child(ckdir)
    meta, state = _built()
    cfg = SweepConfig(method="ard")
    base_st, base_stats = solve(meta, init_labels(meta, state), cfg)
    assert base_stats.sweeps > KILL_AT, \
        f"instance converges in {base_stats.sweeps} sweeps; nothing to kill"

    latest = resilience.latest_checkpoint(ckdir)
    assert latest is not None, "the killed child published no checkpoint"
    assert latest.sweeps == KILL_AT, \
        f"latest checkpoint at sweep {latest.sweeps}, wanted {KILL_AT}"
    print(f"[kill-resume] child SIGKILLed; latest checkpoint at sweep "
          f"{latest.sweeps}/{base_stats.sweeps}")

    st, stats = solve(meta, init_labels(meta, state), cfg,
                      resume_from=ckdir)
    np.testing.assert_array_equal(np.asarray(st.d), np.asarray(base_st.d))
    np.testing.assert_array_equal(np.asarray(st.cf), np.asarray(base_st.cf))
    assert int(st.flow_to_t) == int(base_st.flow_to_t)
    for k in ("sweeps", "engine_iters", "engine_launches", "flow_curve",
              "active_curve", "converged"):
        assert getattr(stats, k) == getattr(base_stats, k), k
    print(f"[kill-resume] resumed {latest.sweeps} -> {stats.sweeps} "
          f"sweeps: flow={int(st.flow_to_t)} — bit-exact vs uninterrupted. "
          f"OK")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", default=None, metavar="CKDIR",
                    help=argparse.SUPPRESS)
    ap.add_argument("--streaming", action="store_true",
                    help="run the protocol through the out-of-core "
                         "streaming route (durable spill pool + O(|B|) "
                         "checkpoints)")
    args = ap.parse_args()
    if args.child:
        (child_streaming if args.streaming else child)(args.child)
    else:
        with tempfile.TemporaryDirectory(prefix="kill_resume_") as d:
            (parent_streaming if args.streaming else parent)(
                str(Path(d) / "ck"))


if __name__ == "__main__":
    main()
