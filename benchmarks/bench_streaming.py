"""Out-of-core streaming vs all-resident: peak host RSS + bit-exactness.

The claim under test is the subsystem's reason to exist: an instance
that arrives as a DIMACS file can be solved while holding only
``max_resident_regions`` region slabs (plus the |B|-sized boundary
layer) in memory, producing the bit-identical flow of the all-resident
pipeline.  Three subprocesses:

  setup     — ``data.generators.pipeline_levels`` -> ``write_dimacs``.
              Unmeasured: the file on disk is the instance.
  resident  — ``read_dimacs`` (the whole edge list in memory) ->
              ``build`` (the full ``[K, V, E]`` state) -> solve.
  streaming — ``read_dimacs_sharded`` (single pass, O(n) vectors,
              per-region shards spilled to disk) -> ``to_stream`` ->
              ``solve_stream`` with ``max_resident_regions=2``.

Each measured arm runs in its OWN subprocess because ``ru_maxrss`` is a
process-lifetime high-water mark (see ``common.peak_rss_bytes``) — two
arms in one process would attribute the first arm's peak to the second.
The pipeline instance emits its edges in sorted order, so the file-order
sharded ingest and the sort-order resident build assign identical arc
slots: the two arms agree sweep for sweep, not just on the flow value.

Usage:

    PYTHONPATH=src python benchmarks/bench_streaming.py --smoke
    PYTHONPATH=src python benchmarks/bench_streaming.py \
        --out BENCH_streaming.json          # n = 1,048,576 evidence run

``--smoke`` (CI) runs a small instance and asserts the same contract:
bit-exact flow/sweeps and streaming peak RSS < ``--ratio`` (default
0.5) of the resident peak.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))


def _part(rows, levels, regions):
    import numpy as np

    assert levels % regions == 0
    return np.arange(rows * levels) // (rows * (levels // regions))


def _cfg():
    from repro.core.sweep import SweepConfig

    return SweepConfig(method="ard", parallel=False, use_global_gap=False)


def run_arm(arm, path, args) -> None:
    """Child entry: one arm, one JSON result line on stdout."""
    from common import peak_rss_bytes

    t0 = time.perf_counter()
    if arm == "setup":
        from repro.data.dimacs import write_dimacs
        from repro.data.generators import pipeline_levels

        p = pipeline_levels(rows=args.rows, levels=args.levels)
        write_dimacs(p, path)
        out = {"num_vertices": p.num_vertices, "num_arcs": len(p.edges),
               "file_mb": round(os.path.getsize(path) / 2**20, 1)}
    elif arm == "resident":
        from repro.core import solve_mincut
        from repro.data.dimacs import read_dimacs

        p = read_dimacs(path)
        res = solve_mincut(p, _part(args.rows, args.levels, args.regions),
                           config=_cfg(), check=False)
        assert res.stats.converged
        out = {"flow": int(res.flow_value), "sweeps": int(res.stats.sweeps),
               "engine_iters": int(res.stats.engine_iters),
               "num_boundary": int(res.stats.num_boundary or 0),
               "staged_in_bytes": 0}
    else:
        from repro.stream.executor import solve_stream
        from repro.data.dimacs import read_dimacs_sharded

        sd = read_dimacs_sharded(path,
                                 _part(args.rows, args.levels, args.regions))
        ss = sd.to_stream(_cfg(),
                          max_resident_regions=args.max_resident_regions)
        ss, stats = solve_stream(ss)
        assert stats.converged
        out = {"flow": int(ss.bnd.flow_to_t), "sweeps": int(stats.sweeps),
               "engine_iters": int(stats.engine_iters),
               "num_boundary": int(stats.num_boundary or 0),
               "staged_in_bytes": int(stats.staged_in_bytes)}
        ss.store.close()
        sd.close()
    out.update(arm=arm, wall_s=round(time.perf_counter() - t0, 2),
               peak_rss_bytes=peak_rss_bytes())
    print(json.dumps(out), flush=True)


def _spawn(arm, path, args):
    cmd = [sys.executable, __file__, "--arm", arm, "--instance", str(path),
           "--rows", str(args.rows), "--levels", str(args.levels),
           "--regions", str(args.regions),
           "--max-resident-regions", str(args.max_resident_regions)]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    assert proc.returncode == 0, \
        f"{arm} arm failed:\n{proc.stdout}\n{proc.stderr}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main() -> None:
    import tempfile

    from common import emit_csv

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small instance, assert the contract, no JSON")
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--levels", type=int, default=128)
    ap.add_argument("--regions", type=int, default=16,
                    help="level-major blocks (levels %% regions == 0)")
    ap.add_argument("--max-resident-regions", type=int, default=2)
    ap.add_argument("--ratio", type=float, default=0.5,
                    help="required streaming/resident peak-RSS ceiling")
    ap.add_argument("--out", default=None, metavar="JSON")
    ap.add_argument("--arm", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--instance", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.smoke:
        # big enough that the edge list / region slabs dominate the
        # interpreter's ~200 MB baseline RSS, or the ratio says nothing
        args.rows, args.levels, args.regions = 2048, 128, 16

    if args.arm:
        run_arm(args.arm, args.instance, args)
        return

    n = args.rows * args.levels
    with tempfile.TemporaryDirectory(prefix="bench_streaming_") as d:
        path = Path(d) / "instance.max"
        print(f"[bench_streaming] pipeline_levels rows={args.rows} "
              f"levels={args.levels} (n={n}), {args.regions} regions, "
              f"max_resident_regions={args.max_resident_regions}",
              flush=True)
        setup = _spawn("setup", path, args)
        print(f"[bench_streaming] instance: {setup['num_arcs']} arcs, "
              f"{setup['file_mb']} MB DIMACS", flush=True)
        res = _spawn("resident", path, args)
        stm = _spawn("streaming", path, args)

    assert stm["flow"] == res["flow"], \
        f"streaming flow {stm['flow']} != resident {res['flow']}"
    assert stm["sweeps"] == res["sweeps"], (stm["sweeps"], res["sweeps"])
    assert stm["engine_iters"] == res["engine_iters"]
    ratio = stm["peak_rss_bytes"] / res["peak_rss_bytes"]
    for r in (res, stm):
        emit_csv(f"streaming/n{n}/{r['arm']}", r["wall_s"] * 1e6,
                 f"rss_mb={r['peak_rss_bytes'] / 2**20:.0f} "
                 f"sweeps={r['sweeps']} flow={r['flow']}")
    print(f"[bench_streaming] peak RSS streaming/resident = {ratio:.3f} "
          f"(required < {args.ratio}); flow bit-exact ({res['flow']})",
          flush=True)
    assert ratio < args.ratio, \
        f"streaming peak RSS ratio {ratio:.3f} >= {args.ratio}"

    if args.out:
        doc = {"instance": {"kind": "pipeline_levels", "rows": args.rows,
                            "levels": args.levels, "num_vertices": n,
                            "num_arcs": setup["num_arcs"],
                            "dimacs_mb": setup["file_mb"],
                            "regions": args.regions},
               "config": {"method": "ard", "parallel": False,
                          "use_global_gap": False,
                          "max_resident_regions": args.max_resident_regions},
               "resident": res, "streaming": stm,
               "rss_ratio": round(ratio, 4)}
        Path(args.out).write_text(json.dumps(doc, indent=1) + "\n")
        print(f"[bench_streaming] wrote {args.out}", flush=True)


if __name__ == "__main__":
    main()
