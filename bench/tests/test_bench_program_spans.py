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

