"""The trace reducer, on interval arithmetic and on a small trace recorded
on a TPU v5e chip: two cold cuts of an 8x8 synthetic grid in 2x2 regions,
each wrapped in the ``request``, ``prepare`` and ``solve`` spans the
harness writes."""

from __future__ import annotations

from pathlib import Path

import numpy as np

from bench import trace

DATA = Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"


def test_union_and_intersection():
    u = trace.union([(5, 7), (0, 2), (1, 3), (6, 9), (4, 4)])
    assert u.tolist() == [[0, 3], [5, 9]]
    assert trace.total(u) == 7
    w = trace.intersect(u, np.array([[2.0, 6.0]]))
    assert w.tolist() == [[2, 3], [5, 6]]
    assert trace.total(trace.union([])) == 0


def test_reduces_a_chip_trace():
    red = trace.reduce(str(DATA), ("prepare", "solve"))
    assert red["planes"] and all(p.startswith("/device:TPU")
                                 for p in red["planes"])
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 < red["span_busy_s"]["solve"] <= red["span_s"]["solve"]
    assert red["span_busy_s"]["solve"] <= red["busy_s"]
    assert red["device_ops"] and all(t > 0 for _, t in red["device_ops"])
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    gaps = sum(t for _, t in red["idle_gaps"])
    assert gaps <= red["window_s"] - red["busy_s"] + 1e-9
