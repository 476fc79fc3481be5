"""Paper Sec. 7.1 synthetic 2-D problem (a copy of the program's
``repro.data.grids`` generator): constant edge capacity ``strength``; each
vertex draws an integer in [-mag, mag], positive as a source link, negative
as a sink link."""

from __future__ import annotations

import numpy as np

from bench.families import grid_edges

# Paper Sec. 7.1 displacement list; the first k/2 pairs give k-connectivity.
_DISPLACEMENTS = [(0, 1), (1, 0), (1, 2), (2, 1), (1, 3), (3, 1), (2, 3),
                  (3, 2), (0, 2), (2, 0), (2, 2), (3, 3), (3, 4), (4, 2)]


def make(shape: tuple[int, ...], rng: np.random.RandomState, *,
         connectivity: int, strength: int, excess_mag: int) -> dict:
    if len(shape) != 2:
        raise ValueError(f"synthetic_grid is 2-D, not {shape}")
    height, width = shape
    if connectivity % 2 or connectivity > 2 * len(_DISPLACEMENTS):
        raise ValueError(f"unsupported connectivity {connectivity}")
    n = height * width
    edges = grid_edges((height, width), _DISPLACEMENTS[:connectivity // 2])
    cap = np.full(len(edges), strength, dtype=np.int32)
    term = rng.randint(-excess_mag, excess_mag + 1, size=n)
    return dict(n=n, edges=edges, cap_fwd=cap, cap_bwd=cap.copy(),
                excess=np.where(term > 0, term, 0).astype(np.int32),
                sink_cap=np.where(term < 0, -term, 0).astype(np.int32),
                shape=(height, width))
