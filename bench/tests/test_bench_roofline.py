"""Byte counts of the roofline and the fleet's shape bucket."""

from __future__ import annotations

import json

import numpy as np

from bench import families, roofline
from bench.loops import to_problem
from bench.tests.helpers import ROOT


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_bytes_independent_of_the_handle_dtype():
    """The count is the same whatever storage the program picks: an
    int32 and an ``auto`` handle of one instance differ in dtype, not in
    the bytes one iteration must move."""
    from repro.core import Solver, SolverOptions

    cfg = _config("seg2d-seeds")
    inst = families.make(cfg, (8, 8), families.rng_for(3, 1))
    part = families.grid_partition((8, 8), (2, 2))
    metas = [Solver(SolverOptions(num_regions=4, dtype_policy=p)).prepare(
        to_problem(inst), part).meta for p in ("int32", "auto")]
    assert metas[0].flow_dtype != metas[1].flow_dtype
    counts = {roofline.iteration_bytes(_instance_of(m, inst), part)
              for m in metas}
    assert len(counts) == 1


def _instance_of(meta, inst):
    # what the benchmark knows of an instance: its logical sizes only
    assert meta.num_vertices == inst["n"]
    return inst


def test_bytes_by_hand():
    inst = dict(n=4, edges=np.array([[0, 1], [2, 3]]),
                cap_fwd=np.array([1, 1]), cap_bwd=np.array([1, 1]),
                excess=np.array([1, 0, 1, 0]), sink_cap=np.array([0, 1, 0, 1]))
    part = np.array([0, 0, 1, 1])
    # int16 flows and labels; per region 2 arcs and 2 vertices:
    # 2 * (2 + 2) + 2 * 2 * (2 * 2 + 2)
    assert roofline.iteration_bytes(inst, part) == 2 * 4 + 2 * 2 * 6
    big = dict(inst, excess=np.array([40000, 0, 0, 0]))
    assert roofline.value_bytes(big, part) == (4, 2)


def test_fleet_sides_pack_into_one_bucket():
    from repro.core import Solver, SolverOptions
    from repro.core.graph import bucket_shape_for

    traffic = json.loads((ROOT / "bench/traffic/fleet.json").read_text())
    cfg = _config("synth2d-8c")
    lo, hi = traffic["sides"]
    solver = Solver(SolverOptions(**cfg["solver"]))
    shapes = set()
    for h in range(lo, hi + 1):
        for w in range(lo, hi + 1):
            inst = families.make(cfg, (h, w), families.rng_for(h * 100 + w))
            part = families.partition(cfg, inst)
            shapes.add(bucket_shape_for(solver.prepare(
                to_problem(inst), part).meta))
    assert len(shapes) == 1, shapes
