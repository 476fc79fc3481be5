"""The check that decides ``correct`` comes out false for the control and
for each fault the cells can have, with the harness's look for a chip
skipped and the timed path broken underneath."""

from __future__ import annotations

import contextlib
import dataclasses

import numpy as np
import pytest

from bench import control
from bench.reference import min_cut, min_cut_quantized
from bench.tests.helpers import tiny_checkout


def _readings(tmp_path, cell, solve=None, seed=2**31 + 3):
    root = tiny_checkout(tmp_path)
    kw = dict(accept_devices=lambda devices: True, root=root)
    return control.readings(cell, seed, 1.0, solve or min_cut, **kw)


@pytest.mark.parametrize("cell", ["synth2d-8c.cold", "seg2d-seeds.recut",
                                  "synth2d-8c.fleet", "seg2d-seeds.cold"])
def test_control_fails(tmp_path, cell):
    r = _readings(tmp_path, cell, min_cut_quantized)
    assert r["correct"] is False
    assert r["flow_mismatches"] > 0


def test_reference_in_place_of_the_program_passes(tmp_path):
    """The harness itself is sound: the exact reference in the program's
    place is correct, so the control fails for its precision alone."""
    r = _readings(tmp_path, "seg2d-seeds.recut", min_cut)
    assert r["correct"] is True


@contextlib.contextmanager
def _patched(obj, name, wrap):
    orig = getattr(obj, name)
    setattr(obj, name, wrap(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


def _run(tmp_path, cell):
    from bench.tests.helpers import run_cell

    rc, line, err = run_cell(tiny_checkout(tmp_path), cell)
    assert rc == 0, err
    return line


@pytest.mark.parametrize("cell", ["synth2d-8c.cold", "seg2d-seeds.recut"])
def test_fault_answer_altered(tmp_path, cell):
    """An answer altered where it is produced: one vertex moves side."""
    from repro.core import solver

    def wrap(orig):
        def solve(self, **kw):
            res = orig(self, **kw)
            side = res.source_side.copy()
            side[len(side) // 2] ^= True
            return dataclasses.replace(res, source_side=side)
        return solve

    with _patched(solver.ProblemHandle, "solve", wrap):
        line = _run(tmp_path, cell)
    assert line["correct"] is False
    assert line["checks"]["cut_mismatches"]["value"] > 0


def test_fault_update_leaves_state_unchanged(tmp_path):
    """A re-cut whose update step returns the session unchanged."""
    from repro.core import solver

    with _patched(solver.ProblemHandle, "update",
                  lambda orig: lambda self, **kw: self):
        line = _run(tmp_path, "seg2d-seeds.recut")
    assert line["correct"] is False


def test_fault_half_the_batch_left_out(tmp_path):
    """A fleet call that solves half its instances and hands the first
    half's answers to the rest."""
    from repro.core import solver

    def wrap(orig):
        def solve_many(self, items, parts=None, **kw):
            half = len(items) // 2
            res = orig(self, items[:half], parts[:half], **kw)
            return res + res[:len(items) - half]
        return solve_many

    with _patched(solver.Solver, "solve_many", wrap):
        line = _run(tmp_path, "synth2d-8c.fleet")
    assert line["correct"] is False


def test_fault_flow_off_by_one(tmp_path):
    from repro.core import solver

    def wrap(orig):
        def solve_many(self, items, parts=None, **kw):
            res = orig(self, items, parts, **kw)
            res[-1] = dataclasses.replace(res[-1],
                                          flow_value=res[-1].flow_value + 1)
            return res
        return solve_many

    with _patched(solver.Solver, "solve_many", wrap):
        line = _run(tmp_path, "synth2d-8c.fleet")
    assert line["correct"] is False
    assert line["checks"]["flow_mismatches"]["value"] > 0
    assert np.isfinite(line["metrics"]["cuts_per_s"]["value"])
