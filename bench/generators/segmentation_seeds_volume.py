"""Seeded segmentation of a volume of any dimension (Boykov & Funka-Lea,
Graph Cuts and Efficient N-D Image Segmentation, IJCV 2006; a copy of the
program's ``repro.data.grids`` generator): neighbours are the ``faces``
(6-connected in 3-D) or ``all`` (26-connected) grid points one step away,
each pair with one contrast weight uniform in [1, smoothness]; a ball of
radius min(shape)/9 at the centre holds source links and the 2-voxel
border sink links, each of ``seed_strength`` plus noise in [0, 15)."""

from __future__ import annotations

import itertools

import numpy as np

from bench.families import grid_edges


def offsets(d: int, neighbours: str) -> list[tuple[int, ...]]:
    """One offset of each +-pair: the unit axes for ``faces``, every
    nonzero step in {-1, 0, 1}^d whose first nonzero entry is 1 for
    ``all``."""
    if neighbours == "faces":
        return [tuple(int(a == b) for b in range(d)) for a in range(d)]
    if neighbours == "all":
        return [o for o in itertools.product((-1, 0, 1), repeat=d)
                if any(o) and o[next(i for i, x in enumerate(o) if x)] == 1]
    raise ValueError(f"unknown neighbours {neighbours!r}")


def make(shape: tuple[int, ...], rng: np.random.RandomState, *,
         neighbours: str, smoothness: int, seed_strength: int) -> dict:
    idx = np.indices(shape)
    r = min(shape) / 9
    fg = sum((idx[d] - n / 2) ** 2 for d, n in enumerate(shape)) < r * r
    bg = np.zeros(shape, dtype=bool)
    for d, n in enumerate(shape):
        bg |= (idx[d] < 2) | (idx[d] >= n - 2)
    exc = np.where(fg & ~bg, seed_strength + rng.randint(0, 15, size=shape), 0)
    snk = np.where(bg, seed_strength + rng.randint(0, 15, size=shape), 0)
    edges = grid_edges(shape, offsets(len(shape), neighbours))
    cap = rng.randint(1, smoothness + 1, size=len(edges)).astype(np.int32)
    return dict(n=int(np.prod(shape)), edges=edges, cap_fwd=cap,
                cap_bwd=cap.copy(), excess=exc.reshape(-1).astype(np.int32),
                sink_cap=snk.reshape(-1).astype(np.int32), shape=tuple(shape))
