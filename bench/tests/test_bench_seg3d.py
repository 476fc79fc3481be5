"""The ``seg3d-26c.cold`` cell: it runs correct on the CPU at a tiny side,
its generator is the program's, its inputs are pinned, and its new reader
reads the program's stage counter."""

from __future__ import annotations

import hashlib
import json
import types

import numpy as np
import pytest

from bench import families
from bench.run import load_metric
from bench.tests.helpers import ROOT, run_cell, tiny_checkout
from bench.tests.test_bench_inputs import _hash_instance, _loop

CELL = "seg3d-26c.cold"
CONFIG = json.loads((ROOT / "bench/configs/seg3d-26c.json").read_text())


def _tiny(tmp_path):
    """A checkout whose ``cold-16`` traffic is cut to side 6."""
    root = tiny_checkout(tmp_path)
    path = root / "bench/traffic/cold-16.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), side=6,
                                    pool=2)))
    return root


def test_cell_runs_correct_on_cpu(tmp_path):
    """Untraced and traced, with the traffic cut to side 6; the traced run
    reads a positive stage count per cut."""
    root = _tiny(tmp_path)
    rc, line, err = run_cell(root, CELL)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert set(line["metrics"]) == {"setup_s", "cut_s"}
    rc, line, err = run_cell(root, CELL, trace=1)
    assert rc == 0, err
    assert line["correct"] is True, err
    m = line["metrics"]
    assert m["ard_stages_per_cut.cut"]["value"] > 0
    # every region runs at least its sink stage in every sweep
    assert m["ard_stages_per_cut.cut"]["value"] >= \
        CONFIG["solver"]["num_regions"] * m["sweeps_per_cut.cut"]["value"]


def test_control_fails(tmp_path):
    """The reference on 8-bit capacities in the program's place reads
    flow mismatches, so the check that decides ``correct`` tells the cell's
    exact integers from the next narrower storage type."""
    from bench import control
    from bench.reference import min_cut_quantized

    r = control.readings(CELL, 2**31 + 11, 1.0, min_cut_quantized,
                         accept_devices=lambda devices: True,
                         root=_tiny(tmp_path))
    assert r["correct"] is False
    assert r["flow_mismatches"] > 0


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_generator_is_the_programs(seed):
    """Draw for draw the program's ``segmentation_seeds_volume``, and the
    harness test family that the 3-D sizing ran on."""
    from repro.data.grids import segmentation_seeds_volume

    shape = (7, 9, 8)
    mine = families.make(CONFIG, shape, np.random.RandomState(seed % 2**32))
    prog = segmentation_seeds_volume(shape, **CONFIG["params"],
                                     seed=seed % 2**32)
    sizing = families.make(dict(CONFIG, family="volume_seeds"), shape,
                           np.random.RandomState(seed % 2**32),
                           ROOT / "bench" / "tests")
    assert mine["n"] == prog.num_vertices
    for k, f in (("edges", "edges"), ("cap_fwd", "cap_fwd"),
                 ("cap_bwd", "cap_bwd"), ("excess", "excess"),
                 ("sink_cap", "sink_cap")):
        np.testing.assert_array_equal(mine[k], getattr(prog, f))
        np.testing.assert_array_equal(mine[k], sizing[k])
        assert mine[k].dtype == getattr(prog, f).dtype


def test_inputs_are_pinned():
    """The window's 16 volumes and their presentations to seeds 0..15, in
    the manner of ``test_bench_inputs.py``: 16 of the cube's 48 signed axis
    permutations map the 4x2x2 partition onto itself."""
    lp = _loop("seg3d-26c", "cold-16", 0)
    assert len(lp.pool) == 16
    assert [inst["shape"] for inst in lp.pool] == [(16, 16, 16)] * 16
    assert len(lp.pool[0]["edges"]) == 46620
    h = hashlib.sha256()
    for inst in lp.pool:
        _hash_instance(h, inst)
    presented, syms = hashlib.sha256(), []
    for seed in range(16):
        lp.ctx.seed = seed
        ks = []
        for i in range(8):
            key, inst = lp._present(("pool", i), lp.pool[i], i)
            ks.append(str(key[-1]))
            _hash_instance(presented, inst)
        syms.append(",".join(ks))
    assert families.symmetries((16, 16, 16), (4, 2, 2)) == PINNED_SYMMETRIES
    assert dict(pool=h.hexdigest(), presented=presented.hexdigest(),
                syms=" ".join(syms)) == PINNED


# the identity and the swap of the two 2-split axes, each with any flips
PINNED_SYMMETRIES = list(range(16))
PINNED = dict(
    pool="137665957669dbb552099299c9d98cddb70570efbfbcb73cfa41ed0b157447cd",
    presented=(
        "092569f27ae76e9ac462cfb6f9fa4b2bd3a77f488a8efdadcc79ef9f34747071"),
    syms=("15,11,12,13,4,7,1,8 4,6,7,15,12,5,8,13 13,7,6,1,5,9,11,8 "
          "1,5,6,10,0,8,9,13 7,9,13,6,6,9,7,13 9,5,14,11,10,8,6,1 "
          "9,7,5,5,3,0,14,14 1,13,10,6,9,2,4,14 11,3,6,11,14,2,10,9 "
          "13,2,4,3,9,5,2,15 8,14,2,5,13,15,10,12 6,4,12,4,9,14,0,14 "
          "6,4,6,10,11,1,14,15 11,10,14,11,10,13,7,9 4,4,0,4,15,3,0,3 "
          "13,6,8,10,3,6,3,13"))


def _spans(*attrs):
    return types.SimpleNamespace(program_spans=[
        types.SimpleNamespace(name=name, attrs=a) for name, a in attrs])


def test_reader_reads_the_solve_spans():
    read = load_metric("ard_stages_per_cut.cut")
    run = _spans(("maxflow.solve", {"ard_stages": 10, "sweeps": 2}),
                 ("maxflow.sweeps", {"sweeps": 2}),
                 ("maxflow.solve", {"ard_stages": 30}))
    assert read(run) == 20
    # a program whose spans lack the attribute, or carry None (prd)
    assert read(_spans(("maxflow.solve", {"sweeps": 2}))) is None
    assert read(_spans(("maxflow.solve", {"ard_stages": None}))) is None
    assert read(_spans()) is None
