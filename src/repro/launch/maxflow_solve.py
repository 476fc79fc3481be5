"""Distributed mincut launcher.

    PYTHONPATH=src python -m repro.launch.maxflow_solve \
        --height 64 --width 64 --regions 2x2 --method ard [--sharded]

Solves a synthetic instance (paper Sec. 7.1) with the region-discharge
solver and verifies flow value == independently-computed cut cost.  With
--sharded the parallel sweep runs under shard_map across however many
devices are available (regions per device = K / n_devices).

Batched throughput mode solves a fleet of instances through the
shape-bucketed batched driver (one grid=(B,K) device program per bucket):

    PYTHONPATH=src python -m repro.launch.maxflow_solve \
        --batch 64x64,64x64,48x48 --regions 2x2 --engine-chunk-iters 8

Each HxW entry becomes one synthetic instance (seeds --seed, --seed+1,
...); per-instance results are bit-identical to single solves.  DIMACS
``.max`` files (see repro.data.dimacs) can be mixed in by path:
``--batch instance.max,64x64``.

Volume mode cuts a seeded 3-D segmentation volume (Boykov & Funka-Lea;
``repro.data.grids.segmentation_seeds_volume``) under an AxBxC grid of
regions, 6- or 26-connected; a DxHxW entry of ``--batch`` is one such
volume:

    PYTHONPATH=src python -m repro.launch.maxflow_solve \
        --volume 16x16x16 --neighbours 26 --regions 4x2x2

Warm-start serving mode re-solves the prepared instance N times through
ONE ``Solver`` session, perturbing a P-fraction of the edge capacities
before each re-solve (``handle.update`` reparameterizes the residual
network on device; the solve continues from the warm preflow):

    PYTHONPATH=src python -m repro.launch.maxflow_solve \
        --height 64 --width 64 --regions 4x4 --resolve 5 --perturb 0.01

Prints per-re-solve sweeps/launches and the session's compile-cache
hits/misses (steady state: zero retraces per cycle).

Out-of-core streaming mode stages regions one at a time from a disk
spill pool, so instances bigger than device memory solve with at most
``--max-resident-regions`` region states in memory (bit-identical to the
sequential in-memory sweep):

    PYTHONPATH=src python -m repro.launch.maxflow_solve \
        --height 1024 --width 1024 --regions 4x4 --streaming \
        --max-resident-regions 2 [--spill-dir /scratch/pool]

Fault tolerance: ``--checkpoint-dir DIR [--checkpoint-every N]`` captures
resumable sweep-boundary checkpoints during the solve; ``--resume``
continues bit-exactly from the latest one after a kill/preemption
(``repro.core.resilience``; exercised end-to-end by
tools/kill_resume_smoke.py).
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def main():
    from repro.core.engine import ENGINE_BACKENDS

    ap = argparse.ArgumentParser()
    ap.add_argument("--height", type=int, default=64)
    ap.add_argument("--width", type=int, default=64)
    ap.add_argument("--connectivity", type=int, default=8)
    ap.add_argument("--strength", type=int, default=150)
    ap.add_argument("--regions", default=None,
                    help="grid of regions, one extent per axis: AxB for a "
                         "2-D grid, AxBxC for a volume (default 2x2, or "
                         "2x2x2 with --volume)")
    ap.add_argument("--volume", default=None, metavar="DxHxW",
                    help="volume mode: cut a seeded segmentation volume "
                         "(segmentation_seeds_volume) of this extent in "
                         "place of the 2-D synthetic grid")
    ap.add_argument("--neighbours", type=int, choices=[6, 26], default=26,
                    help="volume mode: 6 (faces) or 26 (all) neighbours")
    ap.add_argument("--method", choices=["ard", "prd"], default="ard")
    ap.add_argument("--sequential", action="store_true")
    ap.add_argument("--sharded", action="store_true")
    ap.add_argument("--streaming", action="store_true",
                    help="out-of-core route (repro.stream): stage regions "
                         "one at a time from a disk spill pool, keeping at "
                         "most --max-resident-regions region states in "
                         "memory and only the |B|-sized boundary layer "
                         "between visits; implies the sequential sweep "
                         "without the global gap heuristic")
    ap.add_argument("--max-resident-regions", type=int, default=2,
                    metavar="R",
                    help="streaming route: LRU resident-set size in regions "
                         "(default 2: the discharging region + the "
                         "prefetched next)")
    ap.add_argument("--spill-dir", default=None, metavar="DIR",
                    help="streaming route: durable spill-pool directory "
                         "(kill-resume needs the pool to outlive the "
                         "process); default: a temp dir deleted after the "
                         "solve")
    ap.add_argument("--engine-backend", choices=list(ENGINE_BACKENDS),
                    default="xla",
                    help="discharge-engine compute phase: dense XLA rows or "
                         "the blocked Pallas kernel (interpret mode "
                         "off-TPU)")
    ap.add_argument("--engine-chunk-iters", type=int, default=None,
                    metavar="K",
                    help="fused XLA engine: up to K complete iterations per "
                         "engine trip, one traced body each (xla backend "
                         "only); default: unfused two-phase engine")
    ap.add_argument("--device-resident", action="store_true",
                    help="run the whole sweep loop in one lax.while_loop "
                         "on device: one host sync per solve instead of "
                         "one per sweep (bit-identical results)")
    ap.add_argument("--host-sync-every", type=int, default=None, metavar="M",
                    help="device-resident escape hatch: return to the host "
                         "every M sweeps (default: only at convergence)")
    ap.add_argument("--batch", default=None, metavar="SPEC[,SPEC...]",
                    help="batched throughput mode: comma-separated instance "
                         "specs (HxW synthetic grid, DxHxW segmentation "
                         "volume or a DIMACS .max path) "
                         "solved together through solve_mincut_batch — one "
                         "shape-bucketed grid=(B,K) device program per "
                         "bucket, compiled solve cached per bucket shape")
    ap.add_argument("--dtype-policy", choices=["int32", "auto", "narrow"],
                    default="int32",
                    help="kernel storage dtypes: int32 baseline (default), "
                         "auto (narrow labels/residuals to int16 and masks "
                         "to int8 when this instance's range bounds allow, "
                         "per-family int32 fallback), or narrow (forced; a "
                         "failed bound is a ProblemValidationError)")
    ap.add_argument("--no-check", action="store_true",
                    help="skip the host-side cut-cost == flow assertion "
                         "(an extra device fetch + O(n*E) host reduction "
                         "per solve) — the serving-path setting")
    ap.add_argument("--resolve", type=int, default=0, metavar="N",
                    help="warm-start serving mode: N incremental re-solves "
                         "through one Solver session, perturbing a "
                         "--perturb fraction of edge capacities before "
                         "each (handle.update + warm handle.solve)")
    ap.add_argument("--perturb", type=float, default=0.01, metavar="P",
                    help="fraction of edges re-randomized per re-solve "
                         "(default 0.01)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="capture resumable sweep-boundary checkpoints "
                         "under DIR (atomic write-then-rename snapshots; "
                         "see repro.core.resilience)")
    ap.add_argument("--checkpoint-every", type=int, default=5, metavar="N",
                    help="checkpoint cadence in sweeps (default 5; the "
                         "device-resident routes capture at their "
                         "--host-sync-every boundaries)")
    ap.add_argument("--resume", action="store_true",
                    help="continue bit-exactly from the latest checkpoint "
                         "in --checkpoint-dir when one exists (the "
                         "restart-after-preemption path)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.core import SweepConfig, grid_partition
    from repro.data.grids import segmentation_seeds_volume, synthetic_grid
    from repro.launch.cache import enable_compile_cache

    try:
        cfg = SweepConfig(method=args.method,
                          parallel=not (args.sequential or args.streaming),
                          use_global_gap=not args.streaming,
                          engine_backend=args.engine_backend,
                          engine_chunk_iters=args.engine_chunk_iters,
                          device_resident=args.device_resident,
                          host_sync_every=args.host_sync_every)
    except ValueError as exc:
        ap.error(str(exc))
    enable_compile_cache()
    neighbours = {6: "faces", 26: "all"}[args.neighbours]
    regions = tuple(int(v) for v in (
        args.regions or ("2x2x2" if args.volume else "2x2")).split("x"))
    num_regions = int(np.prod(regions))

    def volume(spec, seed):
        shape = tuple(int(v) for v in spec.split("x"))
        if len(shape) != len(regions):
            ap.error(f"volume {spec} and --regions "
                     f"{'x'.join(map(str, regions))} differ in dimension")
        return (segmentation_seeds_volume(shape, neighbours=neighbours,
                                          seed=seed),
                grid_partition(shape, regions))
    if args.streaming:
        if args.sharded:
            ap.error("--streaming and --sharded are mutually exclusive "
                     "routes")
        if not args.sequential:
            print("[maxflow] --streaming implies the sequential sweep "
                  "without the global gap heuristic (Alg. 1 staged order)")

    checkpoint = resume_from = None
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume needs --checkpoint-dir")
    if args.checkpoint_dir:
        from repro.core import resilience as _res

        checkpoint = _res.CheckpointPolicy(directory=args.checkpoint_dir,
                                           every=args.checkpoint_every)
        if args.resume and _res.snapshot_latest(args.checkpoint_dir) \
                is not None:
            resume_from = args.checkpoint_dir
            print(f"[maxflow] resuming from checkpoint sweep "
                  f"{_res.snapshot_latest(args.checkpoint_dir)} "
                  f"under {args.checkpoint_dir}")

    if args.batch:
        if args.resolve:
            ap.error("--resolve works on a single prepared instance; "
                     "it cannot be combined with --batch")
        if args.checkpoint_dir:
            ap.error("--checkpoint-dir on the batch route goes through "
                     "Solver.solve_many(checkpoint=...); the CLI wires "
                     "the single-instance routes only")
        import re
        from pathlib import Path

        from repro.data.dimacs import read_dimacs

        probs, parts = [], []
        for i, spec in enumerate(args.batch.split(",")):
            grid = re.fullmatch(r"(\d+)x(\d+)", spec)
            vol = re.fullmatch(r"\d+x\d+x\d+", spec)
            if grid and not Path(spec).exists():   # a file named HxW wins
                h, w = int(grid[1]), int(grid[2])
                probs.append(synthetic_grid(
                    h, w, connectivity=args.connectivity,
                    strength=args.strength, seed=args.seed + i))
                parts.append(grid_partition((h, w), regions))
            elif vol and not Path(spec).exists():
                prob, part = volume(spec, args.seed + i)
                probs.append(prob)
                parts.append(part)
            elif Path(spec).is_file():
                probs.append(read_dimacs(spec))
                parts.append(None)     # node-number fallback partitioner
            else:
                ap.error(f"--batch spec {spec!r} is neither HxW, DxHxW nor "
                         "an existing DIMACS file")
        from repro.core import Solver, SolverOptions

        solver = Solver(SolverOptions.from_sweep_config(
            cfg, num_regions=num_regions, check=not args.no_check,
            dtype_policy=args.dtype_policy))
        t0 = time.time()
        results = solver.solve_many(probs, parts)
        dt = time.time() - t0
        for i, res in enumerate(results):
            print(f"[maxflow]   instance {i}: flow={res.flow_value} "
                  f"sweeps={res.stats.sweeps} "
                  f"engine_iters={res.stats.engine_iters} "
                  f"ard_stages={res.stats.ard_stages}")
        launches = sum(bs.engine_launches for bs in solver.last_batch_stats)
        syncs = sum(bs.host_syncs for bs in solver.last_batch_stats)
        print(f"[maxflow] batch of {len(results)} ({args.method}, "
              f"{args.engine_backend}, "
              f"{len(solver.last_batch_stats)} bucket(s)): "
              f"launches={launches} host_syncs={syncs} t={dt:.2f}s "
              f"({len(results) / max(dt, 1e-9):.1f} instances/s)")
        return

    if args.volume:
        prob, part = volume(args.volume, args.seed)
    else:
        prob = synthetic_grid(args.height, args.width,
                              connectivity=args.connectivity,
                              strength=args.strength, seed=args.seed)
        part = grid_partition((args.height, args.width), regions)

    # one Solver session for the cold solve and every warm re-solve: the
    # build/Layout and every compiled program are reused across the loop
    from repro.core import Solver, SolverOptions

    solver = Solver(SolverOptions.from_sweep_config(
        cfg, num_regions=num_regions, check=not args.no_check,
        dtype_policy=args.dtype_policy, streaming=args.streaming,
        max_resident_regions=args.max_resident_regions,
        spill_dir=args.spill_dir))
    handle = solver.prepare(prob, part)

    mesh = None
    if args.sharded:
        n_dev = len(jax.devices())
        assert handle.meta.num_regions % n_dev == 0, \
            f"K={handle.meta.num_regions} must divide over {n_dev} devices"
        mesh = jax.make_mesh((n_dev,), ("regions",))

    t0 = time.time()
    res = handle.solve(mesh=mesh, checkpoint=checkpoint,
                       resume_from=resume_from)
    route = (f"sharded x{len(jax.devices())}" if args.sharded
             else f"streaming(resident={args.max_resident_regions})"
             if args.streaming
             else f"device_resident={cfg.device_resident}")
    kd = handle.meta.kernel_dtypes
    print(f"[maxflow] {args.method} parallel={cfg.parallel} {route} "
          f"dtypes={kd.label}/{kd.flow}/{kd.mask}: "
          f"flow={res.flow_value} sweeps={res.stats.sweeps} "
          f"engine_iters={res.stats.engine_iters} "
          f"ard_stages={res.stats.ard_stages} "
          f"launches={res.stats.engine_launches} "
          f"host_syncs={res.stats.host_syncs} "
          f"boundary_bytes={res.stats.boundary_bytes} "
          f"page_bytes={res.stats.page_bytes} "
          f"t={time.time()-t0:.2f}s")
    if args.streaming:
        print(f"[maxflow]   staged_in={res.stats.staged_in_bytes} "
              f"staged_out={res.stats.staged_out_bytes} "
              f"|B|={res.stats.num_boundary}")

    rng = np.random.RandomState(args.seed + 1)
    m = len(handle.problem.edges)
    for i in range(args.resolve):
        k = max(1, int(round(args.perturb * m)))
        idx = rng.choice(m, size=k, replace=False)
        hi = 2 * args.strength + 1
        handle.update(
            arcs=idx,
            cap_fwd=rng.randint(0, hi, size=k).astype(np.int32),
            cap_bwd=rng.randint(0, hi, size=k).astype(np.int32))
        t0 = time.time()
        res = handle.solve(mesh=mesh)
        info = solver.cache_info()
        print(f"[maxflow] re-solve {i + 1}/{args.resolve} "
              f"(perturbed {k}/{m} edges): flow={res.flow_value} "
              f"sweeps={res.stats.sweeps} "
              f"launches={res.stats.engine_launches} "
              f"host_syncs={res.stats.host_syncs} t={time.time()-t0:.2f}s "
              f"cache_hits={info.hits} cache_misses={info.misses}")


if __name__ == "__main__":
    main()
