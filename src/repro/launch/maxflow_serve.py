"""Maxflow serving launcher: replay a mixed request stream through the
continuous-batching service (repro.serve).

    PYTHONPATH=src python -m repro.launch.maxflow_serve \
        --stream 6x6,8x8,10x10 --requests 24 --rate 8 \
        --tight-frac 0.25 --tight-timeout 0.05

Each spec is an HxW synthetic grid or a DIMACS ``.max`` path; requests
cycle through the specs and are paced at ``--rate`` req/s (omit for one
burst).  A ``--tight-frac`` fraction carries a ``--tight-timeout``
deadline, enforced at sweep boundaries (misses come back as typed
``DeadlineExceeded`` partial results, not hangs).  The bounded queue
sheds overflow with ``ServiceOverloaded`` + retry-after.

Large warm re-cut sessions ride along with ``--sessions``:

    PYTHONPATH=src python -m repro.launch.maxflow_serve \
        --requests 16 --rate 4 --sessions 2 --recuts 3 \
        --session-grid 24x24 --handle-budget-mb 8 --eviction-dir /tmp/ev

Each session first solves a ``--session-grid`` instance, then submits
``--recuts`` incremental capacity-perturbation re-cuts against the warm
handle (evicted-to-checkpoint handles resume warm when the
``--handle-budget-mb`` LRU budget forces them out).

Prints one line per resolved request and the final ``service.report()``
(p50/p99, throughput, sheds, evictions, deadline misses, breaker state);
``--report PATH`` also writes it as JSON.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _build_requests(ap, args):
    import re
    from pathlib import Path

    from repro.core import grid_partition
    from repro.data.grids import synthetic_grid
    from repro.serve import SolveRequest

    ry, rx = (int(v) for v in args.regions.split("x"))

    def spec_problem(spec, seed):
        grid = re.fullmatch(r"(\d+)x(\d+)", spec)
        if grid and not Path(spec).exists():    # a file named HxW wins
            h, w = int(grid[1]), int(grid[2])
            return (synthetic_grid(h, w, connectivity=args.connectivity,
                                   strength=args.strength, seed=seed),
                    grid_partition((h, w), (ry, rx)))
        if Path(spec).is_file():
            from repro.data.dimacs import read_dimacs
            return read_dimacs(spec), None
        ap.error(f"stream spec {spec!r} is neither HxW nor an existing "
                 "DIMACS file")

    specs = args.stream.split(",")
    tight_every = (0 if args.tight_frac <= 0
                   else max(1, round(1 / args.tight_frac)))
    reqs = []
    for i in range(args.requests):
        prob, part = spec_problem(specs[i % len(specs)], args.seed + i)
        timeout = (args.tight_timeout
                   if tight_every and i % tight_every == 0
                   else args.timeout)
        reqs.append(SolveRequest(problem=prob, part=part, timeout=timeout,
                                 tenant=f"t{i % 2}"))

    # warm re-cut sessions: one create + --recuts updates each, spread
    # evenly through the stream so re-cuts land on warm (possibly
    # evicted-and-restored) handles
    rng = np.random.RandomState(args.seed)
    sh, sw = (int(v) for v in args.session_grid.split("x"))
    spart = grid_partition((sh, sw), (ry, rx))
    session_reqs = []
    for s in range(args.sessions):
        prob = synthetic_grid(sh, sw, connectivity=args.connectivity,
                              strength=args.strength, seed=args.seed + 97 + s)
        m = len(prob.edges)
        session_reqs.append(SolveRequest(problem=prob, part=spart,
                                         session=f"s{s}",
                                         timeout=args.timeout))
        k = max(1, int(round(args.perturb * m)))
        hi = 2 * args.strength + 1
        for _ in range(args.recuts):
            session_reqs.append(SolveRequest(
                session=f"s{s}", timeout=args.timeout,
                update=dict(arcs=rng.choice(m, size=k, replace=False),
                            cap_fwd=rng.randint(0, hi, size=k)
                            .astype(np.int32))))
    if session_reqs:
        stride = max(1, len(reqs) // len(session_reqs) or 1)
        for j, r in enumerate(session_reqs):    # order preserves
            reqs.insert(min(len(reqs), (j + 1) * stride + j), r)  # create
        #                                         before that session's
        #                                         re-cuts (FIFO per session)
    return reqs


def main():
    from repro.core.engine import ENGINE_BACKENDS

    ap = argparse.ArgumentParser()
    ap.add_argument("--stream", default="6x6,8x8,10x10",
                    metavar="SPEC[,SPEC...]",
                    help="request mix: HxW synthetic grids and/or DIMACS "
                         ".max paths, cycled --requests times")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=None, metavar="R",
                    help="offered load in req/s (default: one burst)")
    ap.add_argument("--timeout", type=float, default=None,
                    help="default per-request deadline in seconds")
    ap.add_argument("--tight-frac", type=float, default=0.0, metavar="F",
                    help="fraction of stream requests given the tight "
                         "deadline (deadline-miss pressure)")
    ap.add_argument("--tight-timeout", type=float, default=0.05)
    ap.add_argument("--sessions", type=int, default=0, metavar="S",
                    help="warm re-cut sessions interleaved into the stream")
    ap.add_argument("--recuts", type=int, default=2, metavar="M",
                    help="incremental re-cuts per session")
    ap.add_argument("--session-grid", default="16x16")
    ap.add_argument("--perturb", type=float, default=0.02,
                    help="fraction of session edges re-randomized per re-cut")
    ap.add_argument("--regions", default="2x2")
    ap.add_argument("--method", choices=["ard", "prd"], default="ard")
    ap.add_argument("--engine-backend", choices=list(ENGINE_BACKENDS),
                    default="xla")
    ap.add_argument("--engine-chunk-iters", type=int, default=None)
    ap.add_argument("--connectivity", type=int, default=8)
    ap.add_argument("--strength", type=int, default=150)
    ap.add_argument("--max-queue", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--sync-every", type=int, default=2,
                    help="sweeps between deadline/harvest checks")
    ap.add_argument("--max-retries", type=int, default=2)
    ap.add_argument("--handle-budget-mb", type=float, default=None,
                    help="device-memory budget for resident prepared "
                         "handles; LRU overflow is evicted to checkpoint")
    ap.add_argument("--eviction-dir", default=None,
                    help="snapshot directory for evicted sessions "
                         "(required with --handle-budget-mb)")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="also write the final service report as JSON")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if (args.handle_budget_mb is None) != (args.eviction_dir is None):
        ap.error("--handle-budget-mb and --eviction-dir go together")

    from repro.core import SolverOptions
    from repro.launch.cache import enable_compile_cache
    from repro.serve import (MaxflowService, ServiceConfig, replay_stream)

    enable_compile_cache()
    ry, rx = (int(v) for v in args.regions.split("x"))
    opts = SolverOptions(method=args.method, num_regions=ry * rx,
                         engine_backend=args.engine_backend,
                         engine_chunk_iters=args.engine_chunk_iters)
    cfg = ServiceConfig(
        max_queue=args.max_queue, max_batch=args.max_batch,
        sync_every=args.sync_every, max_retries=args.max_retries,
        default_timeout=args.timeout,
        handle_budget_bytes=None if args.handle_budget_mb is None
        else int(args.handle_budget_mb * 2**20),
        eviction_dir=args.eviction_dir)
    service = MaxflowService(opts, cfg)
    reqs = _build_requests(ap, args)

    t0 = time.time()
    tickets = replay_stream(service, reqs, rate=args.rate)
    dt = time.time() - t0
    for t in tickets:
        req = t.request
        what = (f"session={req.session}" if req.session
                else f"problem<{len(req.problem.edges)} edges>")
        if t.error is None:
            print(f"[serve] {req.request_id} {what}: "
                  f"flow={t.result.flow_value} "
                  f"sweeps={t.result.stats.sweeps}")
        else:
            print(f"[serve] {req.request_id} {what}: "
                  f"{t.error.code}: {t.error}")
    service.close()
    report = service.report()
    print(f"[serve] {len(tickets)} requests in {dt:.2f}s "
          f"({len(tickets) / max(dt, 1e-9):.1f} offered/s): "
          f"{json.dumps(report, indent=2, default=str)}")
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2, default=str)
        print(f"[serve] report written to {args.report}")


if __name__ == "__main__":
    main()
