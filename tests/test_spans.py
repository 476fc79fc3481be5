"""Spans at the solver's layer boundaries (``repro.core.spans``): the
records they keep, what they cost with recording off, where compiles are
charged, and the span tree of each route."""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Solver, SolverOptions, grid_partition, spans
from repro.data.grids import synthetic_grid


@pytest.fixture(autouse=True)
def _recording_off():
    spans.stop()
    yield
    spans.stop()


class _Counting:
    """Stands in for ``TraceAnnotation`` and ``block_until_ready``."""

    def __init__(self, monkeypatch):
        self.annotations, self.waits = [], 0
        real_wait = jax.block_until_ready
        counter = self

        class Annotation:
            def __init__(self, name, **kw):
                counter.annotations.append((name, kw))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        def wait(x):
            counter.waits += 1
            return real_wait(x)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        monkeypatch.setattr(jax, "block_until_ready", wait)


def test_nesting_parent_and_root_ids():
    with spans.recording() as recs:
        with spans.span("a", k=1):
            with spans.span("b"):
                with spans.span("c") as c:
                    c.set(late=2)
            with spans.span("d"):
                pass
        with spans.span("e"):
            pass
    by = {r.name: r for r in recs}
    assert [r.name for r in recs] == ["c", "b", "d", "a", "e"]  # by closing
    a = by["a"]
    assert a.parent is None and a.root == a.id and a.attrs == {"k": 1}
    assert by["b"].parent == a.id and by["d"].parent == a.id
    assert by["c"].parent == by["b"].id
    assert {by[n].root for n in "abcd"} == {a.id}
    assert by["e"].parent is None and by["e"].root == by["e"].id
    assert by["c"].attrs == {"late": 2}
    for r in recs:
        assert 0 < r.start_ns <= r.end_ns
    assert a.start_ns <= by["b"].start_ns and by["d"].end_ns <= a.end_ns
    assert spans.drain() == []


def test_span_closes_when_its_body_raises():
    with spans.recording() as recs:
        with pytest.raises(ValueError):
            with spans.span("outer"):
                with spans.span("inner"):
                    raise ValueError
        with spans.span("after"):
            pass
    by = {r.name: r for r in recs}
    assert set(by) == {"inner", "outer", "after"}
    assert by["after"].parent is None


def test_off_records_nothing_creates_no_annotation_and_waits_on_nothing(
        monkeypatch):
    seen = _Counting(monkeypatch)
    x = jnp.arange(4)
    with spans.span("a", k=1) as sp:
        sp.wait(x)
        sp.set(k=2)
        with spans.span("b"):
            pass
    assert spans.drain() == []
    assert seen.annotations == [] and seen.waits == 0


def test_recording_annotates_each_span(monkeypatch):
    seen = _Counting(monkeypatch)
    with spans.recording():
        with spans.span("a", k=1):
            with spans.span("b"):
                pass
    assert seen.annotations == [("a", {"k": 1}), ("b", {})]


def test_outputs_are_awaited_only_while_recording(monkeypatch):
    seen = _Counting(monkeypatch)
    x = jnp.arange(8) * 2
    with spans.span("off") as sp:
        sp.wait(x)
    assert seen.waits == 0
    with spans.recording():
        with spans.span("on") as sp:
            sp.wait(x)
        with spans.span("nothing named"):
            pass
    assert seen.waits == 1


def test_compile_charged_to_innermost_span():
    def fresh(x):                   # a new function: jit compiles it anew
        return jnp.sin(x) * 3 + 1

    with spans.recording() as recs:
        with spans.span("parent"):
            with spans.span("child"):
                jax.jit(fresh)(jnp.arange(5.0)).block_until_ready()
    by = {r.name: r for r in recs}
    assert by["child"].compiles >= 1 and by["child"].compile_s > 0
    assert by["parent"].compiles == 0 and by["parent"].compile_s == 0


def test_compiles_outside_recording_are_not_charged():
    with spans.recording() as recs:
        with spans.span("quiet"):
            pass
    jax.jit(lambda x: jnp.cos(x) - 2)(jnp.arange(3.0)).block_until_ready()
    assert recs[0].compiles == 0


def test_summary_totals_per_name():
    recs = [spans.Span("a", 1, None, 1, {}, 0, 2_000_000, 0.001, 1),
            spans.Span("a", 2, None, 2, {}, 5, 1_000_005, 0.0, 0),
            spans.Span("b", 3, None, 3, {}, 0, 500_000)]
    out = spans.summary(recs)
    assert list(out) == ["a", "b"]
    assert out["a"]["count"] == 2 and out["a"]["compiles"] == 1
    assert out["a"]["total_ms"] == pytest.approx(3.0)
    assert out["a"]["compile_ms"] == pytest.approx(1.0)
    assert out["b"]["total_ms"] == pytest.approx(0.5)


# ---------------------------------------------------------------- solver

G = 8


def _problem(seed=0, g=G):
    return (synthetic_grid(g, g, connectivity=8, strength=60, excess_mag=90,
                           seed=seed), grid_partition((g, g), (2, 2)))


def _tree(recs, root):
    """(name, [children...]) of ``root``, children in start order, runs of
    one name folded into one entry."""
    kids = {}
    for r in sorted(recs, key=lambda r: r.start_ns):
        kids.setdefault(r.parent, []).append(r)

    def walk(r):
        out = []
        for k in kids.get(r.id, []):
            sub = walk(k)
            if out and out[-1] == (k.name, sub):
                continue
            out.append((k.name, sub))
        return out

    return root.name, walk(root)


def _roots(recs):
    return [r for r in sorted(recs, key=lambda r: r.start_ns)
            if r.parent is None]


PREPARE = ("maxflow.prepare", [("maxflow.validate", []),
                               ("maxflow.build", [])])
FINISH = ("maxflow.finish", [("maxflow.extract_cut", []),
                             ("maxflow.certificate", [])])


def test_cold_solve_span_tree():
    p, part = _problem()
    with spans.recording() as recs:
        Solver(SolverOptions(num_regions=4)).prepare(p, part).solve()
    roots = _roots(recs)
    assert [_tree(recs, r) for r in roots] == [PREPARE, (
        "maxflow.solve", [
            ("maxflow.entry_state", []),
            ("maxflow.sweeps", [("maxflow.sweep", [])]),
            FINISH])]
    solve = roots[1]
    assert solve.attrs["route"] == "host" and solve.attrs["sweeps"] >= 1
    build = next(r for r in recs if r.name == "maxflow.build")
    assert (build.attrs["K"], build.attrs["V"]) == (4, (G // 2) ** 2)
    entry = next(r for r in recs if r.name == "maxflow.entry_state")
    assert entry.attrs["labels"] == "cold"
    sweeps = next(r for r in recs if r.name == "maxflow.sweeps")
    assert sweeps.attrs["sweeps"] == solve.attrs["sweeps"]
    assert sweeps.attrs["host_syncs"] == sweeps.attrs["sweeps"] + 1
    assert sum(r.name == "maxflow.sweep" for r in recs) == \
        solve.attrs["sweeps"]


def test_device_resident_solve_syncs_once():
    p, part = _problem()
    with spans.recording() as recs:
        Solver(SolverOptions(num_regions=4, device_resident=True)).prepare(
            p, part).solve()
    solve = _roots(recs)[1]
    assert solve.attrs["route"] == "device"
    assert _tree(recs, solve)[1][1] == (
        "maxflow.sweeps", [("maxflow.sync", [])])
    assert sum(r.name == "maxflow.sync" for r in recs) == 1


def test_warm_update_and_solve_span_tree():
    p, part = _problem()
    handle = Solver(SolverOptions(num_regions=4)).prepare(p, part)
    handle.solve()
    cap = p.cap_fwd.copy()
    cap[::3] += 40                      # residual capacity grows: relabel
    with spans.recording() as recs:
        handle.update(cap_fwd=cap)
        handle.solve()
    update, solve = _roots(recs)
    assert _tree(recs, update) == ("maxflow.update", [
        ("maxflow.validate", []), ("maxflow.apply_update", [])])
    changed = int((cap != p.cap_fwd).sum())
    apply = next(r for r in recs if r.name == "maxflow.apply_update")
    assert apply.attrs["arcs"] == changed and apply.attrs["terminals"] == 0
    assert apply.attrs["bucket"] == 1 << (changed - 1).bit_length()
    tree = _tree(recs, solve)
    assert tree[1][0] == ("maxflow.entry_state",
                          [("maxflow.global_relabel", [])])
    assert tree[1][-1] == FINISH
    entry = next(r for r in recs if r.name == "maxflow.entry_state")
    assert entry.attrs["labels"] == "relabel"


def test_solve_many_span_tree():
    items = [_problem(seed) for seed in (1, 2, 3)]
    with spans.recording() as recs:
        Solver(SolverOptions(num_regions=4)).solve_many(
            [p for p, _ in items], [part for _, part in items])
    (root,) = _roots(recs)
    assert root.attrs["B"] == 3 and root.attrs["buckets"] == 1
    name, kids = _tree(recs, root)
    assert name == "maxflow.solve_many"
    assert kids == [PREPARE, ("maxflow.entry_state", []),
                    ("maxflow.pack", []),
                    ("maxflow.sweeps", [("maxflow.sync", [])]), FINISH]
    for n, count in [("maxflow.prepare", 3), ("maxflow.entry_state", 3),
                     ("maxflow.finish", 3), ("maxflow.sweeps", 1)]:
        assert sum(r.name == n and r.parent == root.id
                   for r in recs) == count, n


def _answers(results):
    return [(r.flow_value, r.source_side, dataclasses.asdict(r.stats))
            for r in results]


def _session(record):
    """Cold solve, warm update + solve, and a fleet call: their answers."""
    p, part = _problem(seed=5)
    solver = Solver(SolverOptions(num_regions=4))
    with spans.recording() if record else contextlib.nullcontext():
        handle = solver.prepare(p, part)
        out = [handle.solve()]
        exc = p.excess.copy()
        exc[: G] += 25
        out.append(handle.update(excess=exc).solve())
        out += solver.solve_many([_problem(s)[0] for s in (6, 7)],
                                 [part, part])
    return _answers(out)


def test_answers_identical_with_recording_on_and_off():
    off, on = _session(False), _session(True)
    assert len(off) == len(on) == 4
    for (f0, s0, st0), (f1, s1, st1) in zip(off, on):
        assert f0 == f1
        np.testing.assert_array_equal(s0, s1)
        assert st0 == st1


def test_solve_with_recording_off_adds_no_annotation_or_sync(monkeypatch):
    p, part = _problem()
    solver = Solver(SolverOptions(num_regions=4))
    solver.prepare(p, part).solve()             # compile outside the count
    seen = _Counting(monkeypatch)
    handle = solver.prepare(p, part)
    handle.solve()
    handle.update(excess=p.excess + 1).solve()
    solver.solve_many([p, p], [part, part])
    assert seen.annotations == [] and seen.waits == 0
    assert spans.drain() == []
