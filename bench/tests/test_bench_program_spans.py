"""The trace reducer with the program's own span names (``maxflow.*``,
``repro.core.spans``) beside the harness's: the harness's numbers do not
move, and an idle gap is named by the innermost span around it."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"
BENCH_NAMES = ("prepare", "update", "solve", "solve_many")
PROGRAM_NAMES = ("maxflow.prepare", "maxflow.build", "maxflow.solve",
                 "maxflow.sweeps", "maxflow.sweep", "maxflow.finish",
                 "maxflow.extract_cut", "maxflow.certificate")


@pytest.mark.parametrize("key", ["busy_s", "window_s", "span_busy_s",
                                 "span_s", "device_ops", "idle_gaps"])
def test_program_names_leave_the_harness_numbers_as_they_are(key):
    plain = trace.reduce(str(DATA), BENCH_NAMES)
    both = trace.reduce(str(DATA), BENCH_NAMES + PROGRAM_NAMES)
    if isinstance(plain[key], dict):
        assert {k: both[key][k] for k in plain[key]} == plain[key]
        # a name the trace does not hold reads as no time at all
        assert all(both[key][k] == 0 for k in PROGRAM_NAMES)
    else:
        assert both[key] == plain[key]


def _iv(*pairs):
    return trace.union(pairs)


def test_gap_named_by_the_innermost_span():
    spans = {"solve": _iv((0, 100)),
             "maxflow.solve": _iv((1, 99)),
             "maxflow.sweeps": _iv((2, 60)),
             "maxflow.sweep": _iv((2, 10), (12, 20)),
             "maxflow.finish": _iv((61, 98)),
             "maxflow.extract_cut": _iv((61, 90))}
    assert trace._host_activity(spans, 5) == "maxflow.sweep"
    assert trace._host_activity(spans, 11) == "maxflow.sweeps"
    assert trace._host_activity(spans, 70) == "maxflow.extract_cut"
    assert trace._host_activity(spans, 95) == "maxflow.finish"
    assert trace._host_activity(spans, 99.5) == "solve"
    assert trace._host_activity(spans, 150) == "between"



def _span(name, ms, compile_ms=0.0):
    from repro.core.spans import Span

    return Span(name=name, id=0, parent=None, root=0, attrs={}, start_ns=0,
                end_ns=int(ms * 1e6), compile_s=compile_ms / 1e3)


def _fake_run(spans=(), trace=None):
    from types import SimpleNamespace

    reqs = [SimpleNamespace(cuts=2, engine_iters=[100, 300]),
            SimpleNamespace(cuts=2, engine_iters=[200, 400])]
    return SimpleNamespace(requests=reqs, traced=reqs[1:],
                           program_spans=list(spans), trace=trace)


_SPANS = [_span("maxflow.solve", 50.0, compile_ms=3.0),
          _span("maxflow.extract_cut", 2.0), _span("maxflow.extract_cut", 6.0),
          _span("maxflow.certificate", 4.0),
          _span("maxflow.global_relabel", 10.0, compile_ms=1.0),
          _span("maxflow.finish", 12.0)]
_TRACE = dict(span_busy_s={"maxflow.sweeps": 0.03},
              span_s={"maxflow.sweeps": 0.04})


@pytest.mark.parametrize("metric, want", [
    ("extract_ms.cut", 8.0 / 4),
    ("certificate_ms.cut", 4.0 / 4),
    ("compile_ms.cut", 4.0 / 4),
    ("relabel_ms.cut", 10.0 / 4),
    ("finish_ms.fleet", 12.0 / 4),
    ("sweeps_us_per_iter.cut", 0.03e6 / 600),
    ("sweep_idle_pct.cut", 25.0),
])
def test_span_reader_arithmetic(metric, want):
    """Each reader of the program's spans, on a run of 4 cuts whose traced
    requests hold 600 engine iterations; a run that recorded nothing
    reads nothing."""
    from bench.run import load_metric

    read = load_metric(metric)
    assert read(_fake_run(_SPANS, _TRACE)) == pytest.approx(want)
    assert read(_fake_run()) is None


def _program_span_metrics(cell):
    import json

    from bench.tests.helpers import ROOT

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {m["name"] for m in spec["per_layer"]
            if m["source"] == "program_span" and cell in m["workloads"]}


@pytest.mark.parametrize("cell", ["seg2d-seeds.recut", "synth2d-8c.fleet",
                                  "seg2d-seeds.cold"])
def test_traced_run_hands_the_program_spans_to_readers(tmp_path, cell):
    """Traced, the window's spans reach every reader of them and the stderr
    summary; untraced, nothing is recorded and recording is left off."""
    import json

    from repro.core import spans

    from bench.tests.helpers import run_cell, tiny_checkout

    root = tiny_checkout(tmp_path)
    rc, line, err = run_cell(root, cell, trace=1)
    assert rc == 0 and line["correct"] is True, err
    want = _program_span_metrics(cell)
    assert want and want <= set(line["metrics"])
    info = json.loads(next(ln for ln in err.splitlines()
                           if ln.startswith('{"cuts_checked"')))
    root_span = "maxflow.solve_many" if "fleet" in cell else "maxflow.solve"
    assert info["program_spans"][root_span]["count"] >= 1
    rc, line, err = run_cell(root, cell, trace=0)
    assert rc == 0 and line["correct"] is True, err
    info = json.loads(next(ln for ln in err.splitlines()
                           if ln.startswith('{"cuts_checked"')))
    assert info["program_spans"] is None
    assert not spans._recording
