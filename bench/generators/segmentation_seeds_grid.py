"""Interactive segmentation (Boykov-Jolly scribbles; a copy of the
program's ``repro.data.grids`` generator): a 4-connected grid with random
contrast weights in [1, smoothness], a foreground scribble (a disk of a
ninth of the side at the centre) holding source links and a background
scribble (the 2-pixel border) holding sink links, each of
``seed_strength`` plus noise in [0, 15)."""

from __future__ import annotations

import numpy as np

from bench.families import grid_edges


def make(shape: tuple[int, ...], rng: np.random.RandomState, *,
         smoothness: int, seed_strength: int) -> dict:
    if len(shape) != 2:
        raise ValueError(f"segmentation_seeds_grid is 2-D, not {shape}")
    height, width = shape
    n = height * width
    yy, xx = np.mgrid[:height, :width]
    cy, cx, r = height / 2, width / 2, min(height, width) / 3
    fg = (yy - cy) ** 2 + (xx - cx) ** 2 < (r / 3) ** 2
    bg = (yy < 2) | (yy >= height - 2) | (xx < 2) | (xx >= width - 2)
    exc = np.where(fg & ~bg, seed_strength + rng.randint(0, 15, size=(
        height, width)), 0)
    snk = np.where(bg, seed_strength + rng.randint(0, 15, size=(
        height, width)), 0)
    edges = grid_edges((height, width), [(0, 1), (1, 0)])
    cap = rng.randint(1, smoothness + 1, size=len(edges)).astype(np.int32)
    return dict(n=n, edges=edges, cap_fwd=cap, cap_bwd=cap.copy(),
                excess=exc.reshape(-1).astype(np.int32),
                sink_cap=snk.reshape(-1).astype(np.int32),
                shape=(height, width))
