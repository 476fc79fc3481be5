"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

The traced slice is the span from the first to the last host event named
``request`` (the benchmark wraps each traced request in one).  Device
activity is the events of the line ``XLA Ops`` of every device plane
(``/device:TPU:<i>``); where a plane has no such line, every line but the
module and step summaries.  Busy time is the union of those intervals,
clipped to the slice, averaged over the planes.  Layer spans are host
events with the benchmark's span names; the busy time inside a layer is
the busy union intersected with the union of that layer's spans.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
SUMMARY_LINES = ("XLA Modules", "Steps", "Source code", "XLA TraceMe",
                 "Framework Ops", "Framework Name Scope")
SLICE_SPAN = "request"


def union(intervals) -> np.ndarray:
    """Sorted, disjoint [start, end) intervals covering ``intervals``."""
    iv = np.asarray(sorted(intervals), dtype=np.float64).reshape(-1, 2)
    out = []
    for s, e in iv:
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64).reshape(-1, 2)


def total(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two disjoint sorted interval sets."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if s < e:
            out.append((s, e))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.asarray(out, dtype=np.float64).reshape(-1, 2)


def _device_lines(plane):
    lines = list(plane.lines)
    ops = [ln for ln in lines if ln.name == OP_LINE]
    return ops or [ln for ln in lines if ln.name not in SUMMARY_LINES]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def reduce(path: str, span_names=()) -> dict:
    """Device numbers of one trace, in seconds.

    ``busy_s``/``window_s``: device busy time (mean over device planes) and
    the traced slice; ``span_busy_s[name]``: busy time inside the host
    spans called ``name``; ``span_s[name]``: their union's length;
    ``device_ops``: the 10 ops that took most device time;
    ``idle_gaps``: the 10 longest idle gaps, each named by the innermost
    benchmark span the host was in.  ``planes``/``lines`` name what was
    read.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = {name: [] for name in (SLICE_SPAN, *span_names)}
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in spans:
                    spans[ev.name].append((ev.start_ns, ev.end_ns))
    if not spans[SLICE_SPAN]:
        raise ValueError(f"trace {path} has no '{SLICE_SPAN}' span")
    lo = min(s for s, _ in spans[SLICE_SPAN])
    hi = max(e for _, e in spans[SLICE_SPAN])
    window = np.asarray([[lo, hi]], dtype=np.float64)
    span_iv = {k: union(v) for k, v in spans.items() if k != SLICE_SPAN}

    busy, span_busy, op_time, per_plane = 0.0, {}, {}, []
    line_names = set()
    for plane in devices:
        evs = []
        for line in _device_lines(plane):
            line_names.add(line.name)
            for ev in line.events:
                evs.append((ev.start_ns, ev.end_ns))
                name = ev.name.split(" = ", 1)[0]     # "%fusion.3 = s32[..."
                op_time[name] = op_time.get(name, 0.0) + ev.duration_ns
        iv = intersect(union(evs), window)
        per_plane.append(iv)
        busy += total(iv)
        for k, siv in span_iv.items():
            span_busy[k] = span_busy.get(k, 0.0) + total(intersect(iv, siv))
    n = max(1, len(devices))
    gaps = []
    if per_plane:
        iv = per_plane[0]
        edges = np.concatenate([[lo], iv.reshape(-1), [hi]]).reshape(-1, 2)
        for s, e in edges:
            if e > s:
                gaps.append((_host_activity(span_iv, (s + e) / 2), e - s))
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return dict(
        busy_s=busy / n / 1e9, window_s=(hi - lo) / 1e9,
        span_busy_s={k: v / n / 1e9 for k, v in span_busy.items()},
        span_s={k: total(v) / 1e9 for k, v in span_iv.items()},
        device_ops=[[name, t / n / 1e9] for name, t in ops],
        idle_gaps=[[name, float(t) / 1e9] for name, t in gaps[:10]],
        planes=[p.name for p in devices], lines=sorted(line_names))


def _host_activity(span_iv: dict, t: float) -> str:
    """The shortest benchmark span around time ``t``, or ``between``."""
    best, best_len = "between", float("inf")
    for name, iv in span_iv.items():
        if not len(iv):
            continue
        k = np.searchsorted(iv[:, 0], t, side="right") - 1
        if k >= 0 and iv[k, 1] > t and iv[k, 1] - iv[k, 0] < best_len:
            best, best_len = name, iv[k, 1] - iv[k, 0]
    return best
