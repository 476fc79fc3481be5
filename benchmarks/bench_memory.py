"""Memory benchmark: dtype-narrowed storage, VMEM headroom, launch counts.

Measures what the memory-lean kernel work actually buys, per dtype policy
(``int32`` baseline vs ``auto``/forced-``narrow``):

  * **bytes/vertex** of the region page (the sweep drivers' per-region
    HBM round trip, ``sweep._page_and_msg_bytes``) and bytes per boundary
    message arc;
  * **fused-kernel VMEM** for reference region shapes
    (``kernels.push_relabel.fused_region_vmem_bytes``) and the largest
    region that stays VMEM-resident under the budget, before/after
    narrowing;
  * **launch accounting**: engine launches per solve for unfused /
    fused-xla / fused-pallas, with the engine invariants asserted (2 per
    iteration unfused, 1 per iteration fused-xla, 1 per chunk trip
    fused-pallas; the fused Pallas kernel runs interpreted only);
  * **cost terms** (FLOPs, bytes accessed, peak bytes) of the
    AOT-compiled parallel-sweep program for at least two kernel configs,
    on the backend the benchmark runs on.

Writes ``BENCH_memory.json``.

    PYTHONPATH=src python benchmarks/bench_memory.py [--quick]
        [--smoke] [--out BENCH_memory.json]

``--smoke`` (the CI guard) asserts on a tiny instance that: narrowed
solves match the wide flow bit-exactly; the autotuner's decision for the
instance's key fits the VMEM budget; the launch/sync counters obey the
engine invariants; and one AOT-compiled 16x16 sweep reports nonzero
cost terms.

Also exposes the ``run(emit, quick)`` contract of benchmarks/run.py.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.common import emit_csv  # noqa: E402

POLICIES = ("int32", "auto")
FUSED_CHUNK_ITERS = 8


def _page_rows(size, regions):
    """bytes/vertex + msg bytes/arc per dtype policy for one instance."""
    from repro.core import grid_partition
    from repro.core.graph import build
    from repro.core.sweep import _page_and_msg_bytes
    from repro.data.grids import synthetic_grid

    p = synthetic_grid(size, size, connectivity=4, strength=3, seed=0)
    part = grid_partition((size, size), regions)
    rows = []
    for policy in POLICIES:
        meta, state, _ = build(p, part, dtype_policy=policy)
        page, msg = _page_and_msg_bytes(meta)
        kd = meta.kernel_dtypes
        rows.append(dict(
            instance=f"grid{size}x{size}",
            policy=policy,
            dtypes=f"{kd.label}/{kd.flow}/{kd.mask}",
            page_bytes=page,
            page_bytes_per_vertex=round(page / meta.region_size, 2),
            msg_bytes_per_arc=round(msg / max(1, meta.num_cross_arcs), 2),
        ))
    wide = rows[0]["page_bytes"]
    for r in rows[1:]:
        r["page_reduction"] = round(1 - r["page_bytes"] / wide, 3)
    return rows


def _vmem_rows():
    """Fused-kernel VMEM for reference shapes + max resident region."""
    from repro.core import dtypes as _dt
    from repro.kernels.push_relabel import (FUSED_VMEM_BUDGET_BYTES,
                                            fused_region_vmem_bytes)

    shapes = [(256, 8), (1024, 8), (4096, 8)]   # 16^2 / 32^2 / 64^2 regions
    rows = []
    for V, E in shapes:
        wide = fused_region_vmem_bytes(V, E, _dt.WIDE)
        narrow = fused_region_vmem_bytes(V, E, _dt.NARROW)
        rows.append(dict(
            region=f"V={V},E={E}",
            vmem_bytes_int32=wide,
            vmem_bytes_narrow=narrow,
            vmem_reduction=round(1 - narrow / wide, 3),
        ))

    def max_resident(kd, E=8):
        v = 1
        while fused_region_vmem_bytes(2 * v, E, kd) \
                <= FUSED_VMEM_BUDGET_BYTES:
            v *= 2
        return v

    return rows, dict(
        budget_bytes=FUSED_VMEM_BUDGET_BYTES,
        max_resident_vertices_int32=max_resident(_dt.WIDE),
        max_resident_vertices_narrow=max_resident(_dt.NARROW),
    )


def _launch_rows(size, regions):
    """Engine-launch accounting per mode, invariants asserted."""
    from repro.core import SweepConfig, grid_partition, solve_mincut
    from repro.data.grids import synthetic_grid

    p = synthetic_grid(size, size, connectivity=4, strength=3, seed=0)
    part = grid_partition((size, size), regions)
    rows = []
    for backend, chunk, mode in (("xla", None, "unfused"),
                                 ("xla", FUSED_CHUNK_ITERS, "fused-xla"),
                                 ("pallas", FUSED_CHUNK_ITERS,
                                  "fused-pallas")):
        cfg = SweepConfig(method="ard", engine_backend=backend,
                          engine_chunk_iters=chunk)
        res = solve_mincut(p, part=part, config=cfg)
        iters, launches = res.stats.engine_iters, res.stats.engine_launches
        if mode == "unfused":
            assert launches == 2 * iters, (launches, iters)
        elif mode == "fused-xla":
            assert launches == iters, (launches, iters)
        else:                         # fused-pallas: one launch per trip
            assert launches <= iters, (launches, iters)
        rows.append(dict(mode=mode, engine_iters=iters,
                         engine_launches=launches, flow=res.flow_value))
    flows = {r["flow"] for r in rows}
    assert len(flows) == 1, "mode parity violated in bench"
    return rows


def _roofline_rows(size, regions):
    """Cost terms (FLOPs, bytes, peak bytes) of the AOT-compiled parallel
    sweep per config, on the backend this runs on.  Times against chip
    peaks are left out: a program compiled for the CPU has no v5e
    roofline."""
    import jax.numpy as jnp

    from repro.core import SweepConfig, grid_partition
    from repro.core.graph import build, init_labels
    from repro.core.sweep import parallel_sweep
    from repro.data.grids import synthetic_grid
    from repro.roofline import analysis as _ra

    p = synthetic_grid(size, size, connectivity=4, strength=3, seed=0)
    part = grid_partition((size, size), regions)
    rows = []
    for policy in POLICIES:
        meta, state, _ = build(p, part, dtype_policy=policy)
        state = init_labels(meta, state)
        for backend, chunk in (("xla", None),
                               ("pallas", FUSED_CHUNK_ITERS)):
            cfg = SweepConfig(method="ard", engine_backend=backend,
                              engine_chunk_iters=chunk)
            compiled = parallel_sweep.lower(
                meta, state, cfg, jnp.asarray(0, jnp.int32)).compile()
            cost = compiled.cost_analysis()
            mem = _ra.memory_summary(compiled)
            rows.append(dict(
                config=f"{backend}/"
                       f"{'fused' if chunk else 'unfused'}/{policy}",
                flops=float(cost.get("flops", 0.0)),
                bytes_accessed=float(cost.get("bytes accessed", 0.0)),
                peak_bytes_per_device=mem.get(
                    "approx_peak_bytes_per_device"),
            ))
    return rows


def collect(quick: bool = False) -> dict:
    import jax

    size, regions = (8, (2, 2)) if quick else (16, (2, 2))
    vmem_rows, resident = _vmem_rows()
    launch_rows = _launch_rows(size, regions)
    return dict(
        bench="memory",
        platform=jax.default_backend(),
        jax_version=jax.__version__,
        pallas_interpret=jax.default_backend() != "tpu",
        page_bytes=_page_rows(size, regions),
        fused_vmem=vmem_rows,
        vmem_resident=resident,
        launch_accounting=launch_rows,
        roofline=_roofline_rows(size, regions),
    )


def smoke() -> None:
    """CI guard: narrowing is bit-exact, the autotuner stays in budget,
    launch/sync counters obey the engine invariants, and one AOT-compiled
    sweep reports its cost terms."""
    import tempfile

    from repro.core import Solver, SolverOptions, grid_partition
    from repro.core.autotune import tune
    from repro.data.grids import synthetic_grid
    from repro.kernels.push_relabel import FUSED_VMEM_BUDGET_BYTES
    from repro.kernels.ref import maxflow_oracle

    p = synthetic_grid(8, 8, connectivity=4, strength=3, seed=0)
    part = grid_partition((8, 8), (2, 2))
    want, _ = maxflow_oracle(p)
    flows = {}
    for policy in ("int32", "narrow"):
        s = Solver(SolverOptions(dtype_policy=policy))
        h = s.prepare(p, part)
        res = h.solve()
        flows[policy] = (res.flow_value, res.stats.sweeps,
                         res.stats.engine_iters)
        assert res.flow_value == want, (policy, res.flow_value, want)
        if policy == "narrow":
            assert h.meta.kernel_dtypes.flow == "int16", h.meta.kernel_dtypes
    assert flows["int32"] == flows["narrow"], flows
    print(f"smoke ok: narrow == int32 == oracle "
          f"(flow={want}, sweeps={flows['int32'][1]}, "
          f"iters={flows['int32'][2]})")

    with tempfile.TemporaryDirectory() as d:
        meta = Solver(SolverOptions(dtype_policy="auto")) \
            .prepare(p, part).meta
        tc = tune(meta.region_size, meta.max_degree, backend="pallas",
                  dtypes=meta.kernel_dtypes, cache=Path(d) / "at.json")
        assert (not tc.fused) or tc.vmem_bytes <= FUSED_VMEM_BUDGET_BYTES, \
            tc
        tc2 = tune(meta.region_size, meta.max_degree, backend="pallas",
                   dtypes=meta.kernel_dtypes, cache=Path(d) / "at.json")
        assert tc == tc2, "autotune cache not deterministic"
    print(f"smoke ok: autotuned config in budget "
          f"(fused={tc.fused}, vmem={tc.vmem_bytes}B, "
          f"chunk_iters={tc.engine_chunk_iters})")

    rows = _launch_rows(8, (2, 2))
    counts = ", ".join("{}={}".format(r["mode"], r["engine_launches"])
                       for r in rows)
    print(f"smoke ok: launch invariants hold ({counts})")

    rl = _roofline_rows(16, (2, 2))
    assert len(rl) >= 2
    for r in rl:
        assert r["bytes_accessed"] > 0, r
    print(f"smoke ok: cost terms on {len(rl)} AOT-compiled configs")
    print("smoke passed: memory/dtype plumbing verified")


def run(emit=emit_csv, quick: bool = False) -> None:
    data = collect(quick=quick)
    for row in data["page_bytes"]:
        emit(f"memory/page/{row['instance']}/{row['policy']}",
             row["page_bytes_per_vertex"],
             f"dtypes={row['dtypes']};msg_per_arc={row['msg_bytes_per_arc']}")
    for row in data["fused_vmem"]:
        emit(f"memory/vmem/{row['region']}", row["vmem_bytes_narrow"],
             f"int32={row['vmem_bytes_int32']};"
             f"reduction={row['vmem_reduction']}")
    for row in data["roofline"]:
        emit(f"memory/roofline/{row['config']}", row["bytes_accessed"],
             f"flops={row['flops']}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-instance invariants check (CI), no JSON")
    ap.add_argument("--out", default=str(Path(__file__).resolve().parents[1]
                                         / "BENCH_memory.json"))
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    data = collect(quick=args.quick)
    Path(args.out).write_text(json.dumps(data, indent=2) + "\n")
    print(f"wrote {args.out}")
    print(json.dumps(data["vmem_resident"], indent=2))
    for row in data["fused_vmem"] + data["roofline"]:
        print(row)


if __name__ == "__main__":
    main()
