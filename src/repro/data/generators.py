"""Hard DIMACS-family instance generators: GENRMF and Washington RLG.

The synthetic grids of ``data.grids`` converge in a handful of sweeps —
fine for conformance, useless for exercising the sweep loop, the
partial-discharge ladder, or the streaming executor's staged passes.
The two classic maxflow generator families below produce the opposite
regime: long augmenting paths and flow that must percolate through many
regions, so sweep counts grow with instance depth (the inputs the
paper's sweep-bound analysis is about).

Both express the classic source/sink construction in this repo's
terminal form: the designated source vertex carries ``excess`` equal to
the total capacity of its incident arcs (an inexhaustible supply for the
rest of the graph), the sink vertex a ``sink_cap`` equal to its incident
capacity — exactly the reduction DIMACS ``n s``/``n t`` lines get in
``data.dimacs.read_dimacs``, so maxflow values match the classical
statement of each family.
"""

from __future__ import annotations

import numpy as np

from repro.core.graph import Problem


def _dedup_directed(u: np.ndarray, w: np.ndarray,
                    cap: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Accumulate parallel directed arcs (u, w) into one edge row each."""
    key = u.astype(np.int64) * (w.max() + 1 if len(w) else 1) + w
    uniq, inv = np.unique(key, return_inverse=True)
    cap_sum = np.zeros(len(uniq), dtype=np.int64)
    np.add.at(cap_sum, inv, cap)
    first = np.zeros(len(uniq), dtype=np.int64)
    first[inv[::-1]] = np.arange(len(u) - 1, -1, -1)
    edges = np.stack([u[first], w[first]], axis=1).astype(np.int64)
    return edges, cap_sum.astype(np.int32)


def _terminal_caps(n: int, edges: np.ndarray, cap_fwd: np.ndarray,
                   cap_bwd: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-vertex total outgoing / incoming arc capacity."""
    out_cap = np.zeros(n, dtype=np.int64)
    in_cap = np.zeros(n, dtype=np.int64)
    np.add.at(out_cap, edges[:, 0], cap_fwd)
    np.add.at(out_cap, edges[:, 1], cap_bwd)
    np.add.at(in_cap, edges[:, 1], cap_fwd)
    np.add.at(in_cap, edges[:, 0], cap_bwd)
    return out_cap, in_cap


def genrmf(a: int = 6, b: int = 6, *, c1: int = 1, c2: int = 100,
           seed: int = 0) -> Problem:
    """GENRMF (Goldfarb & Grigoriadis): b frames of an a x a grid.

    In-frame 4-neighbor edges carry the saturating capacity ``c2 * a^2``
    in both directions; each vertex of frame z sends one arc of random
    capacity in ``[c1, c2]`` to a uniformly random vertex of frame z+1.
    Source: corner of the first frame; sink: opposite corner of the last.
    All flow must thread the b-1 narrow random inter-frame cuts, so
    augmenting paths are long and sweep counts grow with ``b`` — the
    standard hard case for push-relabel orderings.
    """
    assert a >= 2 and b >= 2 and 0 <= c1 <= c2
    rng = np.random.RandomState(seed)
    n = a * a * b
    vid = np.arange(n).reshape(b, a, a)
    big = np.int32(c2 * a * a)

    e_u, e_w, e_fwd, e_bwd = [], [], [], []
    for dy, dx in ((0, 1), (1, 0)):
        u = vid[:, : a - dy, : a - dx].reshape(-1)
        w = vid[:, dy:, dx:].reshape(-1)
        e_u.append(u)
        e_w.append(w)
        e_fwd.append(np.full(len(u), big, dtype=np.int32))
        e_bwd.append(np.full(len(u), big, dtype=np.int32))
    for z in range(b - 1):
        u = vid[z].reshape(-1)
        w = vid[z + 1].reshape(-1)[rng.randint(0, a * a, size=a * a)]
        e_u.append(u)
        e_w.append(w)
        e_fwd.append(rng.randint(c1, c2 + 1, size=a * a).astype(np.int32))
        e_bwd.append(np.zeros(a * a, dtype=np.int32))

    edges = np.stack([np.concatenate(e_u), np.concatenate(e_w)],
                     axis=1).astype(np.int64)
    cap_fwd = np.concatenate(e_fwd)
    cap_bwd = np.concatenate(e_bwd)

    src = int(vid[0, 0, 0])
    snk = int(vid[b - 1, a - 1, a - 1])
    out_cap, in_cap = _terminal_caps(n, edges, cap_fwd, cap_bwd)
    excess = np.zeros(n, dtype=np.int32)
    sink_cap = np.zeros(n, dtype=np.int32)
    excess[src] = out_cap[src]
    sink_cap[snk] = in_cap[snk]
    return Problem(num_vertices=n, edges=edges, cap_fwd=cap_fwd,
                   cap_bwd=cap_bwd, excess=excess, sink_cap=sink_cap)


def pipeline_levels(rows: int = 64, levels: int = 16, *, pipe_cap: int = 114,
                    mix_cap: int = 2, supply: int = 100) -> Problem:
    """Absorbing level pipeline: big, deterministic, fast-converging.

    ``levels`` columns of ``rows`` vertices; every vertex of level l
    sends a ``pipe_cap`` arc straight ahead to (l+1, same row) and seven
    ``mix_cap`` arcs to the next level's rows shifted by 1..7 (cyclic) —
    eight distinct targets, in-degree exactly eight.  Every vertex's
    out-capacity (``pipe_cap + 7*mix_cap``) covers its worst-case inflow
    (``pipe_cap`` from the pipe + ``7*mix_cap`` mixed), and the last
    level's ``sink_cap`` covers everything, so NO excess is ever stuck:
    labels stay near zero, the sequential sweep drains the instance in a
    handful of passes, and the maxflow equals the injected supply
    (``supply * rows``) exactly.

    This is the scaling instance of the out-of-core route (resident
    against streamed peak RSS, EXPERIMENTS.md): solve cost grows linearly
    with ``rows`` while sweep and engine-iteration counts stay flat — the
    GENRMF/RLG families above stress the algorithm, this one stresses
    the memory system.  Edges are emitted in sorted ``(u, v)`` order, so
    a DIMACS round trip through ``read_dimacs`` (which sorts) and the
    file-order ``read_dimacs_sharded`` ingest reproduce the exact same
    arc slots — the resident and streamed solves are bit-identical
    sweep for sweep.
    """
    assert rows >= 8 and levels >= 2
    assert supply <= pipe_cap and pipe_cap <= pipe_cap + 7 * mix_cap
    n = rows * levels
    vid = np.arange(n).reshape(levels, rows)

    r = np.arange(rows)
    # eight next-level targets per vertex: shift 0 (the pipe) carries
    # pipe_cap, shifts 1..7 carry mix_cap; sorted per source vertex so
    # the global edge list is lexicographically ordered
    shifts = np.arange(8)
    tgt_row = (r[:, None] + shifts[None, :]) % rows          # [rows, 8]
    cap_row = np.where(shifts == 0, pipe_cap,
                       mix_cap)[None, :].repeat(rows, 0)     # [rows, 8]
    order = np.argsort(tgt_row, axis=1, kind="stable")
    tgt_row = np.take_along_axis(tgt_row, order, axis=1)
    cap_row = np.take_along_axis(cap_row, order, axis=1)

    us, ws, caps = [], [], []
    for l in range(levels - 1):
        us.append(np.repeat(vid[l], 8))
        ws.append((vid[l + 1][0] + tgt_row).reshape(-1))
        caps.append(cap_row.reshape(-1))
    edges = np.stack([np.concatenate(us), np.concatenate(ws)],
                     axis=1).astype(np.int64)
    cap_fwd = np.concatenate(caps).astype(np.int32)
    cap_bwd = np.zeros(len(edges), dtype=np.int32)

    excess = np.zeros(n, dtype=np.int32)
    sink_cap = np.zeros(n, dtype=np.int32)
    excess[vid[0]] = supply
    sink_cap[vid[-1]] = pipe_cap + 7 * mix_cap
    return Problem(num_vertices=n, edges=edges, cap_fwd=cap_fwd,
                   cap_bwd=cap_bwd, excess=excess, sink_cap=sink_cap)


def washington_rlg(rows: int = 8, levels: int = 12, *, degree: int = 3,
                   max_cap: int = 100, seed: int = 0) -> Problem:
    """Washington random level graph (RLG).

    ``levels`` columns of ``rows`` vertices; every vertex sends ``degree``
    arcs of random capacity in ``[1, max_cap]`` to random vertices of the
    next column (parallel draws accumulate).  The source feeds the whole
    first column, the last column drains to the sink.  Flow has to cross
    every level, so the solve needs at least ~``levels`` region visits
    when columns are partitioned across regions.
    """
    assert rows >= 1 and levels >= 2 and degree >= 1 and max_cap >= 1
    rng = np.random.RandomState(seed)
    n = rows * levels
    vid = np.arange(n).reshape(levels, rows)

    us, ws, caps = [], [], []
    for j in range(levels - 1):
        us.append(np.repeat(vid[j], degree))
        ws.append(vid[j + 1][rng.randint(0, rows, size=rows * degree)])
        caps.append(rng.randint(1, max_cap + 1, size=rows * degree))
    edges, cap_fwd = _dedup_directed(
        np.concatenate(us), np.concatenate(ws), np.concatenate(caps))
    cap_bwd = np.zeros(len(edges), dtype=np.int32)

    out_cap, in_cap = _terminal_caps(n, edges, cap_fwd, cap_bwd)
    excess = np.zeros(n, dtype=np.int32)
    sink_cap = np.zeros(n, dtype=np.int32)
    excess[vid[0]] = out_cap[vid[0]]
    sink_cap[vid[-1]] = in_cap[vid[-1]]
    return Problem(num_vertices=n, edges=edges, cap_fwd=cap_fwd,
                   cap_bwd=cap_bwd, excess=excess, sink_cap=sink_cap)
