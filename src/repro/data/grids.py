"""Synthetic maxflow instance generators (paper Sec. 7.1).

The paper's synthetic family: an N-D grid with a regular connectivity
structure, integer excess/deficit per node uniform in [-mag, mag] (positive
=> source link, negative => sink link), and constant edge capacity
("strength").  ``connectivity_offsets`` reproduces the displacement list of
Sec. 7.1: (0,1),(1,0) -> 4-connected, first 8 -> 8-connected, etc.
"""

from __future__ import annotations

import itertools

import numpy as np

from repro.core.graph import Problem

# paper Sec. 7.1 displacement list (pairs added symmetrically)
_DISPLACEMENTS = [
    (0, 1), (1, 0), (1, 2), (2, 1), (1, 3), (3, 1), (2, 3), (3, 2),
    (0, 2), (2, 0), (2, 2), (3, 3), (3, 4), (4, 2),
]


def connectivity_offsets(connectivity: int) -> list[tuple[int, int]]:
    assert connectivity % 2 == 0 and connectivity <= 2 * len(_DISPLACEMENTS)
    return _DISPLACEMENTS[: connectivity // 2]


def synthetic_grid(height: int, width: int, *, connectivity: int = 8,
                   strength: int = 150, excess_mag: int = 500,
                   seed: int = 0) -> Problem:
    """Paper Sec. 7.1 synthetic 2D problem."""
    rng = np.random.RandomState(seed)
    n = height * width
    vid = np.arange(n).reshape(height, width)
    edges = []
    for dy, dx in connectivity_offsets(connectivity):
        dst = vid[dy:, dx:]
        edges.append(np.stack(
            [vid[: height - dy, : width - dx].reshape(-1),
             dst.reshape(-1)], axis=1))
    edges = np.concatenate(edges, axis=0).astype(np.int64)
    m = len(edges)
    cap = np.full(m, strength, dtype=np.int32)
    term = rng.randint(-excess_mag, excess_mag + 1, size=n)
    excess = np.where(term > 0, term, 0).astype(np.int32)
    sink_cap = np.where(term < 0, -term, 0).astype(np.int32)
    return Problem(num_vertices=n, edges=edges, cap_fwd=cap.copy(),
                   cap_bwd=cap.copy(), excess=excess, sink_cap=sink_cap)


def segmentation_grid(height: int, width: int, *, seed: int = 0,
                      smoothness: int = 20, depth: int = 1) -> Problem:
    """Vision-style segmentation instance: noisy foreground disk unaries +
    contrast-modulated pairwise terms (stands in for the BJ01/BF06 family of
    Table 1)."""
    rng = np.random.RandomState(seed)
    n = height * width * depth
    yy, xx = np.mgrid[:height, :width]
    cy, cx, r = height / 2, width / 2, min(height, width) / 3
    fg = ((yy - cy) ** 2 + (xx - cx) ** 2 < r * r)
    noise = rng.randint(0, 15, size=(height, width))
    exc2d = np.where(fg, 30 + noise, 0)
    snk2d = np.where(~fg, 30 + noise, 0)
    vid = np.arange(n).reshape(depth, height, width)
    edges = []
    for dz, dy, dx in [(0, 0, 1), (0, 1, 0), (1, 0, 0)][: (3 if depth > 1 else 2)]:
        a = vid[: depth - dz or None, : height - dy or None, : width - dx or None]
        b = vid[dz:, dy:, dx:]
        edges.append(np.stack([a.reshape(-1), b.reshape(-1)], axis=1))
    edges = np.concatenate(edges, axis=0).astype(np.int64)
    cap = rng.randint(1, smoothness + 1, size=len(edges)).astype(np.int32)
    excess = np.tile(exc2d.reshape(-1), depth).astype(np.int32)
    sink_cap = np.tile(snk2d.reshape(-1), depth).astype(np.int32)
    return Problem(num_vertices=n, edges=edges, cap_fwd=cap.copy(),
                   cap_bwd=cap.copy(), excess=excess, sink_cap=sink_cap)


def segmentation_seeds_grid(height: int, width: int, *, seed: int = 0,
                            smoothness: int = 20,
                            seed_strength: int = 200) -> Problem:
    """Interactive-segmentation instance: SPARSE scribble terminals.

    Unlike ``segmentation_grid`` (dense unaries — every pixel has a
    terminal link, so every region touches the sink and solves are very
    local), this is the paper's interactive BJ01 shape: a foreground
    scribble (small disk at the center) carries source mass, a background
    scribble (the image border frame) carries sink capacity, and ALL flow
    must travel across the 4-connected grid between them — crossing many
    region boundaries, which is what makes sweep counts (and warm-start
    re-solves) interesting.
    """
    rng = np.random.RandomState(seed)
    n = height * width
    yy, xx = np.mgrid[:height, :width]
    cy, cx, r = height / 2, width / 2, min(height, width) / 3
    fg_seed = ((yy - cy) ** 2 + (xx - cx) ** 2 < (r / 3) ** 2)
    bg_seed = (yy < 2) | (yy >= height - 2) | (xx < 2) | (xx >= width - 2)
    exc2d = np.where(fg_seed & ~bg_seed,
                     seed_strength + rng.randint(0, 15, size=(height, width)),
                     0)
    snk2d = np.where(bg_seed,
                     seed_strength + rng.randint(0, 15, size=(height, width)),
                     0)
    vid = np.arange(n).reshape(height, width)
    edges = []
    for dy, dx in [(0, 1), (1, 0)]:
        a = vid[: height - dy or None, : width - dx or None]
        b = vid[dy:, dx:]
        edges.append(np.stack([a.reshape(-1), b.reshape(-1)], axis=1))
    edges = np.concatenate(edges, axis=0).astype(np.int64)
    cap = rng.randint(1, smoothness + 1, size=len(edges)).astype(np.int32)
    return Problem(num_vertices=n, edges=edges, cap_fwd=cap.copy(),
                   cap_bwd=cap.copy(),
                   excess=exc2d.reshape(-1).astype(np.int32),
                   sink_cap=snk2d.reshape(-1).astype(np.int32))


def volume_offsets(d: int, neighbours: str) -> list[tuple[int, ...]]:
    """One offset of each +-pair of a d-dimensional grid neighbourhood: the
    unit axes for ``"faces"`` (6-connected in 3-D), every nonzero step in
    {-1, 0, 1}^d whose first nonzero entry is 1 for ``"all"`` (26-connected
    in 3-D)."""
    if neighbours == "faces":
        return [tuple(int(a == b) for b in range(d)) for a in range(d)]
    if neighbours == "all":
        return [o for o in itertools.product((-1, 0, 1), repeat=d)
                if any(o) and o[next(i for i, x in enumerate(o) if x)] == 1]
    raise ValueError(f"unknown neighbours {neighbours!r}: 'faces' or 'all'")


def grid_edges(shape: tuple[int, ...], offsets) -> np.ndarray:
    """The undirected pairs (v, v + offset) of a row-major ``shape`` grid
    that lie inside it, offset by offset in the order given, each block
    row-major in v."""
    vid = np.arange(int(np.prod(shape))).reshape(shape)
    out = []
    for off in offsets:
        src = tuple(slice(max(0, -o), n - max(0, o))
                    for o, n in zip(off, shape))
        dst = tuple(slice(max(0, o), n - max(0, -o))
                    for o, n in zip(off, shape))
        out.append(np.stack([vid[src].reshape(-1), vid[dst].reshape(-1)],
                            axis=1))
    return np.concatenate(out, axis=0).astype(np.int64)


def segmentation_seeds_volume(shape: tuple[int, ...], *,
                              neighbours: str = "all", smoothness: int = 20,
                              seed_strength: int = 200,
                              seed: int = 0) -> Problem:
    """Seeded segmentation of a volume of any dimension (Boykov &
    Funka-Lea, Graph Cuts and Efficient N-D Image Segmentation, IJCV 2006;
    the ``*.n6c``/``*.n26c`` volumes of the UWO vision max-flow benchmark).

    The N-D form of ``segmentation_seeds_grid``: neighbours are the
    ``"faces"`` (6-connected in 3-D) or ``"all"`` (26-connected) grid
    points one step away, each pair with one contrast weight uniform in
    [1, smoothness] on both arcs; a ball of radius min(shape)/9 at the
    centre holds source links, the 2-voxel border sink links, each of
    ``seed_strength`` plus noise in [0, 15).  Vertices are numbered
    row-major.
    """
    shape = tuple(int(s) for s in shape)
    rng = np.random.RandomState(seed)
    idx = np.indices(shape)
    r = min(shape) / 9
    fg = sum((idx[d] - n / 2) ** 2 for d, n in enumerate(shape)) < r * r
    bg = np.zeros(shape, dtype=bool)
    for d, n in enumerate(shape):
        bg |= (idx[d] < 2) | (idx[d] >= n - 2)
    exc = np.where(fg & ~bg, seed_strength + rng.randint(0, 15, size=shape), 0)
    snk = np.where(bg, seed_strength + rng.randint(0, 15, size=shape), 0)
    edges = grid_edges(shape, volume_offsets(len(shape), neighbours))
    cap = rng.randint(1, smoothness + 1, size=len(edges)).astype(np.int32)
    return Problem(num_vertices=int(np.prod(shape)), edges=edges,
                   cap_fwd=cap, cap_bwd=cap.copy(),
                   excess=exc.reshape(-1).astype(np.int32),
                   sink_cap=snk.reshape(-1).astype(np.int32))


def random_sparse(n: int, m: int, *, cap_mag: int = 100, term_mag: int = 50,
                  seed: int = 0) -> Problem:
    """Random sparse instance (property-test fodder)."""
    rng = np.random.RandomState(seed)
    if n < 2:
        raise ValueError("need n >= 2")
    pairs = set()
    edges = []
    while len(edges) < m:
        u, v = rng.randint(0, n, size=2)
        if u == v or (u, v) in pairs or (v, u) in pairs:
            continue
        pairs.add((u, v))
        edges.append((u, v))
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    cap_f = rng.randint(0, cap_mag + 1, size=len(edges)).astype(np.int32)
    cap_b = rng.randint(0, cap_mag + 1, size=len(edges)).astype(np.int32)
    excess = rng.randint(0, term_mag + 1, size=n).astype(np.int32)
    sink_cap = rng.randint(0, term_mag + 1, size=n).astype(np.int32)
    return Problem(num_vertices=n, edges=edges, cap_fwd=cap_f, cap_bwd=cap_b,
                   excess=excess, sink_cap=sink_cap)
