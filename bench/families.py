"""Instance families of the benchmark, made from a seed.

The generators are copies of the program's own (``repro.data.grids``), kept
here so that a later change to the program cannot change what the benchmark
feeds it.  An instance is a plain dict of numpy arrays:

    n         number of vertices
    edges     int64[m, 2]  undirected pairs (u, v)
    cap_fwd   int32[m]     capacity u -> v
    cap_bwd   int32[m]     capacity v -> u
    excess    int32[n]     source t-link capacity
    sink_cap  int32[n]     sink t-link capacity
    shape     (height, width) of the pixel grid

``FAMILIES`` maps the ``family`` of a configuration file to its generator.
"""

from __future__ import annotations

import numpy as np

# Paper Sec. 7.1 displacement list; the first k/2 pairs give k-connectivity.
_DISPLACEMENTS = [(0, 1), (1, 0), (1, 2), (2, 1), (1, 3), (3, 1), (2, 3),
                  (3, 2), (0, 2), (2, 0), (2, 2), (3, 3), (3, 4), (4, 2)]


def _grid_edges(height: int, width: int, offsets) -> np.ndarray:
    vid = np.arange(height * width).reshape(height, width)
    out = [np.stack([vid[:height - dy, :width - dx].reshape(-1),
                     vid[dy:, dx:].reshape(-1)], axis=1)
           for dy, dx in offsets]
    return np.concatenate(out, axis=0).astype(np.int64)


def synthetic_grid(height: int, width: int, rng: np.random.RandomState, *,
                   connectivity: int, strength: int, excess_mag: int) -> dict:
    """Paper Sec. 7.1 synthetic 2-D problem: constant edge capacity
    ``strength``; each vertex draws an integer in [-mag, mag], positive as
    a source link, negative as a sink link."""
    if connectivity % 2 or connectivity > 2 * len(_DISPLACEMENTS):
        raise ValueError(f"unsupported connectivity {connectivity}")
    n = height * width
    edges = _grid_edges(height, width, _DISPLACEMENTS[:connectivity // 2])
    cap = np.full(len(edges), strength, dtype=np.int32)
    term = rng.randint(-excess_mag, excess_mag + 1, size=n)
    return dict(n=n, edges=edges, cap_fwd=cap, cap_bwd=cap.copy(),
                excess=np.where(term > 0, term, 0).astype(np.int32),
                sink_cap=np.where(term < 0, -term, 0).astype(np.int32),
                shape=(height, width))


def segmentation_seeds_grid(height: int, width: int,
                            rng: np.random.RandomState, *, smoothness: int,
                            seed_strength: int) -> dict:
    """Interactive segmentation (Boykov-Jolly scribbles): a 4-connected
    grid with random contrast weights in [1, smoothness], a foreground
    scribble (a disk of a ninth of the side at the centre) holding source
    links and a background scribble (the 2-pixel border) holding sink
    links, each of ``seed_strength`` plus noise in [0, 15)."""
    n = height * width
    yy, xx = np.mgrid[:height, :width]
    cy, cx, r = height / 2, width / 2, min(height, width) / 3
    fg = (yy - cy) ** 2 + (xx - cx) ** 2 < (r / 3) ** 2
    bg = (yy < 2) | (yy >= height - 2) | (xx < 2) | (xx >= width - 2)
    exc = np.where(fg & ~bg, seed_strength + rng.randint(0, 15, size=(
        height, width)), 0)
    snk = np.where(bg, seed_strength + rng.randint(0, 15, size=(
        height, width)), 0)
    edges = _grid_edges(height, width, [(0, 1), (1, 0)])
    cap = rng.randint(1, smoothness + 1, size=len(edges)).astype(np.int32)
    return dict(n=n, edges=edges, cap_fwd=cap, cap_bwd=cap.copy(),
                excess=exc.reshape(-1).astype(np.int32),
                sink_cap=snk.reshape(-1).astype(np.int32),
                shape=(height, width))


FAMILIES = {
    "synthetic_grid": synthetic_grid,
    "segmentation_seeds_grid": segmentation_seeds_grid,
}


def make(config: dict, height: int, width: int,
         rng: np.random.RandomState) -> dict:
    """One instance of ``config``'s family at ``height`` x ``width``."""
    return FAMILIES[config["family"]](height, width, rng, **config["params"])


def grid_partition(shape: tuple[int, int], splits: tuple[int, int]
                   ) -> np.ndarray:
    """Region id per vertex: the grid cut into splits[0] x splits[1]
    blocks of (nearly) equal extent, row-major (paper Sec. 5.3)."""
    idx = np.indices(shape)
    region = np.zeros(shape, dtype=np.int64)
    for d, (extent, s) in enumerate(zip(shape, splits)):
        region = region * s + (idx[d] * s) // extent
    return region.reshape(-1)


def disk(shape: tuple[int, int], cy: int, cx: int, radius: int
         ) -> np.ndarray:
    """Vertex ids of the pixels within ``radius`` of (cy, cx)."""
    yy, xx = np.mgrid[:shape[0], :shape[1]]
    inside = (yy - cy) ** 2 + (xx - cx) ** 2 <= radius * radius
    return np.flatnonzero(inside.reshape(-1))


# The eight symmetries of the square, as maps from the old grid of vertex
# ids to the new one: new[y, x] = old vertex id at that position.
_SYMMETRIES = (
    lambda v: v, lambda v: v.T, lambda v: v[::-1, ::-1],
    lambda v: v[::-1, ::-1].T, lambda v: v[::-1, :], lambda v: v[:, ::-1],
    lambda v: v.T[::-1, :], lambda v: v.T[:, ::-1])


def _symmetry_map(shape: tuple[int, int], k: int) -> np.ndarray:
    return _SYMMETRIES[k](np.arange(shape[0] * shape[1]).reshape(shape))


def symmetries(shape: tuple[int, int], splits: tuple[int, int]
               ) -> list[int]:
    """The symmetries of the square that map the grid partition of
    ``shape`` onto the grid partition of the image's shape, region for
    region: under those an instance is the same problem, relabelled."""
    old = grid_partition(shape, splits)
    out = []
    for k in range(len(_SYMMETRIES)):
        m = _symmetry_map(shape, k)
        new = grid_partition(m.shape, splits)
        pairs = set(zip(new.tolist(), old[m.reshape(-1)].tolist()))
        if len(pairs) == len(set(new.tolist())) == len(set(old.tolist())):
            out.append(k)
    return out


def transform(inst: dict, k: int) -> dict:
    """``inst`` under symmetry ``k``: vertices renumbered by their new grid
    position, each edge and terminal carried with its vertices."""
    m = _symmetry_map(inst["shape"], k)
    new_id = np.empty(inst["n"], dtype=np.int64)
    new_id[m.reshape(-1)] = np.arange(inst["n"])
    excess = np.empty_like(inst["excess"])
    sink_cap = np.empty_like(inst["sink_cap"])
    excess[new_id], sink_cap[new_id] = inst["excess"], inst["sink_cap"]
    return dict(inst, edges=new_id[inst["edges"]], excess=excess,
                sink_cap=sink_cap, shape=m.shape)


def moved_vertex(shape: tuple[int, int], k: int, v: int) -> int:
    """The id that vertex ``v`` of a ``shape`` grid takes under ``k``."""
    return int(np.flatnonzero(_symmetry_map(shape, k).reshape(-1) == v)[0])


def rng_for(seed: int, *stream: int) -> np.random.RandomState:
    """A generator for one stream of a run, from a seed of any size."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), *stream])
    return np.random.RandomState(ss.generate_state(1)[0])
