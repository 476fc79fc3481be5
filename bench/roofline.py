"""Bytes one engine iteration must move, from an instance's logical sizes.

One engine iteration of the region-discharge solver is one synchronous
push/relabel step of one region (``SweepStats.engine_iters`` sums them over
regions).  Whatever layout, route or dtype runs it, the step has to read
every residual arc of the region and the label at its head, and read and
write every vertex's excess, sink residual and label.  The count below
uses the mean region (n / K vertices, 2m / K directed arcs), the real and
unpadded sizes, and each value at the narrowest type that the program's
``auto`` dtype policy could pick for the instance (16-bit flows when the
total capacity is under 2**15, 16-bit labels when the label bound is under
2**14 - 2), so no implementation can move less.  Topology (neighbour ids,
masks) is left out: a grid can compute it.  The work is integer
compare/add/min with no matrix-unit work, so the roofline is HBM bandwidth.
"""

from __future__ import annotations

import numpy as np

NARROW_FLOW_LIMIT = 2 ** 15          # total capacity under this: int16
NARROW_LABEL_LIMIT = 2 ** 14 - 2     # label bound at most this: int16


def value_bytes(inst: dict, part: np.ndarray) -> tuple[int, int]:
    """(flow bytes, label bytes) of the narrowest type ``auto`` allows."""
    mass = sum(int(np.asarray(inst[k], np.int64).sum())
               for k in ("cap_fwd", "cap_bwd", "excess", "sink_cap"))
    region_size = int(np.bincount(part).max())
    label_bound = max(inst["n"], region_size + 2)
    flow = 2 if mass < NARROW_FLOW_LIMIT else 4
    label = 2 if label_bound <= NARROW_LABEL_LIMIT else 4
    return flow, label


def iteration_bytes(inst: dict, part: np.ndarray) -> float:
    """Bytes one engine iteration of the mean region must move."""
    k = int(part.max()) + 1
    flow, label = value_bytes(inst, part)
    arcs = 2 * len(inst["edges"]) / k
    verts = inst["n"] / k
    return arcs * (flow + label) + verts * 2 * (2 * flow + label)
