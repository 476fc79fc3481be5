"""Instance families of the benchmark, made from a seed, and the grid
geometry they share.

A family is one file, ``bench/generators/<family>.py``, found by the
``family`` of a configuration file.  Its ``make(shape, rng, **params)``
makes one instance on a grid of any number of dimensions; an instance is a
plain dict of numpy arrays:

    n         number of vertices
    edges     int64[m, 2]  undirected pairs (u, v)
    cap_fwd   int32[m]     capacity u -> v
    cap_bwd   int32[m]     capacity v -> u
    excess    int32[n]     source t-link capacity
    sink_cap  int32[n]     sink t-link capacity
    shape     the grid's extent per axis, vertices numbered row-major

The generators are copies of the program's own (``repro.data.grids``), kept
here so that a later change to the program cannot change what the benchmark
feeds it.  The grid's dimension is the configuration's: one extent per entry
of ``partition.splits``.
"""

from __future__ import annotations

import functools
import importlib.util
import itertools
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


@functools.lru_cache(maxsize=None)
def load_generator(family: str, bench: Path = BENCH):
    """``bench/generators/<family>.py``'s ``make``."""
    path = bench / "generators" / f"{family}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_generator_" + family.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.make


def make(config: dict, shape: tuple[int, ...], rng: np.random.RandomState,
         bench: Path = BENCH) -> dict:
    """One instance of ``config``'s family on a grid of ``shape``."""
    return load_generator(config["family"], bench)(
        tuple(int(s) for s in shape), rng, **config["params"])


def grid_edges(shape: tuple[int, ...], offsets) -> np.ndarray:
    """The undirected pairs (v, v + offset) of a ``shape`` grid that lie
    inside it, offset by offset in the order given, each block row-major
    in v."""
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


def grid_partition(shape: tuple[int, ...], splits: tuple[int, ...]
                   ) -> np.ndarray:
    """Region id per vertex: the grid cut into splits[0] x splits[1] x ...
    blocks of (nearly) equal extent, row-major (paper Sec. 5.3)."""
    if len(shape) != len(splits):
        raise ValueError(f"grid {shape} and splits {splits} differ in "
                         f"dimension")
    idx = np.indices(shape)
    region = np.zeros(shape, dtype=np.int64)
    for d, (extent, s) in enumerate(zip(shape, splits)):
        region = region * s + (idx[d] * s) // extent
    return region.reshape(-1)


def partition(config: dict, inst: dict) -> np.ndarray:
    """Region id per vertex of ``inst``, as ``config["partition"]`` says."""
    part = config["partition"]
    if part["kind"] != "grid":
        raise SystemExit(f"partition kind {part['kind']!r} is not served: "
                         f"the benchmark partitions grids only ('grid')")
    splits = tuple(part["splits"])
    if len(splits) != len(inst["shape"]):
        raise SystemExit(f"partition splits {list(splits)} do not fit the "
                         f"{len(inst['shape'])}-D grid {inst['shape']}")
    return grid_partition(inst["shape"], splits)


def ball(shape: tuple[int, ...], centre: tuple[int, ...], radius: int
         ) -> np.ndarray:
    """Vertex ids of the grid points within ``radius`` of ``centre``."""
    idx = np.indices(shape)
    dist2 = sum((idx[d] - c) ** 2 for d, c in enumerate(centre))
    return np.flatnonzero((dist2 <= radius * radius).reshape(-1))


# A symmetry of the grid is a signed axis permutation (perm, flips): the
# new grid of vertex ids is np.flip(np.transpose(old, perm), flips), so
# new[position] = the old vertex id at that position.  The eight of the
# square keep the order every 2-D presentation has been drawn in (as
# lambdas: v, v.T, v[::-1, ::-1], v[::-1, ::-1].T, v[::-1, :], v[:, ::-1],
# v.T[::-1, :], v.T[:, ::-1]); changing it changes what each seed presents.
_SQUARE = (((0, 1), ()), ((1, 0), ()), ((0, 1), (0, 1)), ((1, 0), (0, 1)),
           ((0, 1), (0,)), ((0, 1), (1,)), ((1, 0), (0,)), ((1, 0), (1,)))


@functools.lru_cache(maxsize=None)
def signed_permutations(d: int) -> tuple:
    """The 2^d * d! symmetries of a d-dimensional grid, in a fixed order.
    For d = 2, ``_SQUARE``.  Otherwise symmetry k = 2^d * p + mask: the
    p-th permutation of ``itertools.permutations(range(d))``, then a flip
    of axis a of the permuted grid for each bit a set in ``mask``."""
    if d == 2:
        return _SQUARE
    return tuple((perm, tuple(a for a in range(d) if mask >> a & 1))
                 for perm in itertools.permutations(range(d))
                 for mask in range(2 ** d))


def _symmetry_map(shape: tuple[int, ...], k: int) -> np.ndarray:
    perm, flips = signed_permutations(len(shape))[k]
    old = np.arange(int(np.prod(shape))).reshape(shape)
    return np.flip(np.transpose(old, perm), flips)


def symmetries(shape: tuple[int, ...], splits: tuple[int, ...]
               ) -> list[int]:
    """The symmetries of the grid that map the grid partition of ``shape``
    onto the grid partition of the image's shape, region for region: under
    those an instance is the same problem, relabelled."""
    old = grid_partition(shape, splits)
    out = []
    for k in range(len(signed_permutations(len(shape)))):
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


def moved_vertex(shape: tuple[int, ...], k: int, v: int) -> int:
    """The id that vertex ``v`` of a ``shape`` grid takes under ``k``."""
    return int(np.flatnonzero(_symmetry_map(shape, k).reshape(-1) == v)[0])


def rng_for(seed: int, *stream: int) -> np.random.RandomState:
    """A generator for one stream of a run, from a seed of any size."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), *stream])
    return np.random.RandomState(ss.generate_state(1)[0])
