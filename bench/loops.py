"""The load loops a traffic file can name, and what each checks.

A traffic file (``bench/traffic/<mix>.json``) holds ``{"loop": <kind>,
...parameters}``; ``LOOPS[kind]`` is built once per run with the cell's
configuration and the run's seed, then driven by ``bench/run.py``:

    loop = LOOPS[kind](ctx)   # set-up: instances, warm-up of every shape
    loop.request(i)           # one timed request -> Request
    loop.expected()           # (answer, reference instance) pairs to check

Every loop serves a grid of the configuration's dimension, one axis per
entry of ``partition.splits``: a ``side`` of the traffic is the extent of
every axis.  Every window cycles the same problems in the same order, made
from ``_BASE_SEED``; the run's seed presents each of them under a symmetry
of the grid that maps the partition onto itself (``families.symmetries``): the
vertices are renumbered, edges and terminals move with them, and the
problem, its maximum flow and the solver's count of sweeps and engine
iterations stay the same.  So every seed does the same work on inputs of its
own.  The instances of the warm-up are fresh draws from the run's seed, and
are checked too.
"""

from __future__ import annotations

import dataclasses
import itertools
import time

import numpy as np

from bench import families

_BASE_SEED = 0                                   # the window's problems
_POOL, _WARM, _SYM, _STROKES = 1, 2, 3, 4        # random streams


@dataclasses.dataclass(eq=False)
class Request:
    """One timed request: its host-clock span and what it produced."""

    t0: float
    t1: float
    cuts: int                           # instances cut by this request
    sweeps: list                        # SweepStats.sweeps per instance
    engine_iters: list                  # SweepStats.engine_iters per instance
    spans: dict                         # layer span name -> seconds
    answers: list                       # (flow_value, source_side, key)
    failed: int = 0

    @property
    def latency(self) -> float:
        return self.t1 - self.t0


def to_problem(inst: dict):
    from repro.core.graph import Problem

    return Problem(num_vertices=inst["n"], edges=inst["edges"],
                   cap_fwd=inst["cap_fwd"], cap_bwd=inst["cap_bwd"],
                   excess=inst["excess"], sink_cap=inst["sink_cap"])


class _Loop:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.config
        self.traffic = ctx.traffic
        self.splits = tuple(self.config["partition"]["splits"])
        self.dims = len(self.splits)
        self.instances: dict = {}       # key -> instance dict, for checks
        self.warm_answers: list = []    # answers produced during set-up
        self._symmetries: dict = {}     # shape -> symmetries it allows

    def _make(self, shape, rng):
        return families.make(self.config, shape, rng, self.ctx.bench)

    def _instance(self, key, shape, rng):
        inst = self._make(shape, rng)
        self.instances[key] = inst
        return inst

    def _part(self, inst):
        return families.partition(self.config, inst)

    def _present(self, key, inst, i):
        """Problem ``key`` as the run presents it at draw ``i``: under a
        symmetry that the run's seed draws; (key, instance)."""
        shape = inst["shape"]
        if shape not in self._symmetries:
            self._symmetries[shape] = families.symmetries(shape, self.splits)
        allowed = self._symmetries[shape]
        k = allowed[families.rng_for(self.ctx.seed, _SYM, i).randint(
            len(allowed))]
        key = key + (k,)
        if key not in self.instances:
            self.instances[key] = families.transform(inst, k)
        return key, self.instances[key]

    size = 1                            # instances a request cuts

    def expected(self):
        return self.warm_answers, self.instances

    def failed(self, t0: float, t1: float) -> Request:
        """The record of a request that raised."""
        return Request(t0, t1, self.size, [], [], {}, [], failed=self.size)


def _sync(jax, handle):
    jax.block_until_ready(handle.state.cf)


class Cold(_Loop):
    """Closed loop, one caller: each request cuts one fresh instance of
    side ``side`` through ``Solver.prepare(p, part).solve()``."""

    def __init__(self, ctx):
        super().__init__(ctx)
        shape = (self.traffic["side"],) * self.dims
        self.pool = [self._make(shape, families.rng_for(_BASE_SEED, _POOL, i))
                     for i in range(self.traffic["pool"])]
        for j in range(self.traffic.get("warmup", 1)):
            key = ("warm", j)
            inst = self._instance(key, shape, families.rng_for(
                ctx.seed, _WARM, j))
            self.warm_answers.append(self._cut(inst, key).answers[0])

    def _cut(self, inst, key):
        jax, spans = self.ctx.jax, self.ctx.spans
        problem, part = to_problem(inst), self._part(inst)
        t0 = time.perf_counter()
        with spans("prepare") as sp_prep:
            handle = self.ctx.solver.prepare(problem, part)
            _sync(jax, handle)
        with spans("solve") as sp_solve:
            res = handle.solve()
        t1 = time.perf_counter()
        return Request(t0, t1, 1, [res.stats.sweeps],
                       [res.stats.engine_iters],
                       {"prepare": sp_prep.seconds, "solve": sp_solve.seconds},
                       [(res.flow_value, res.source_side, key)])

    def request(self, i):
        k = i % len(self.pool)
        key, inst = self._present(("pool", k), self.pool[k], i)
        return self._cut(inst, key)


class Recut(_Loop):
    """One interactive session: an image of side ``side`` prepared and
    cut in set-up, then each request is one brush stroke (a ball of
    ``brush_radius`` pixels, labelled opposite to the session's cut at its
    centre) through ``handle.update(excess=, sink_cap=)`` and
    ``handle.solve()``.  A stroke first restores the previous stroke's
    pixels, so the session stays the same over any window.  The seed
    presents the image, and the strokes with it, under one symmetry."""

    def __init__(self, ctx):
        super().__init__(ctx)
        image = self._make((self.traffic["side"],) * self.dims,
                           families.rng_for(_BASE_SEED, _POOL, 0))
        self.image_shape = image["shape"]
        (*_, sym), base = self._present(("base",), image, 0)
        self.base = base
        self.handle = ctx.solver.prepare(to_problem(base), self._part(base))
        res = self.handle.solve()
        self.base_source = res.source_side
        self.warm_answers.append((res.flow_value, res.source_side,
                                  ("base", sym)))
        self.strokes = self._strokes(families.rng_for(
            _BASE_SEED, _STROKES), self.traffic["strokes"], sym)
        self._warm_buckets()
        warm = self._strokes(families.rng_for(ctx.seed, _WARM),
                             self.traffic.get("warmup", 4))
        for j, st in enumerate(warm):
            self.warm_answers.append(self._stroke(st, ("warm", j)).answers[0])

    def _strokes(self, rng, count, sym=0):
        """(centre, painted excess, painted sink_cap, edited instance); the
        centres are drawn on the image as made and moved by ``sym``."""
        shape = self.image_shape
        r = self.traffic["brush_radius"]
        strength = self.config["params"]["seed_strength"]
        out = []
        for _ in range(count):
            centre = tuple(int(rng.randint(n)) for n in shape)
            v = families.moved_vertex(shape, sym, int(np.ravel_multi_index(
                centre, shape)))
            centre = tuple(int(c) for c in np.unravel_index(
                v, self.base["shape"]))
            pix = families.ball(self.base["shape"], centre, r)
            exc, snk = self.base["excess"].copy(), self.base["sink_cap"].copy()
            if self.base_source[v]:     # on the object: paint background
                exc[pix], snk[pix] = 0, strength
            else:                       # on the background: object
                exc[pix], snk[pix] = strength, 0
            out.append((centre, exc, snk, dict(self.base, excess=exc,
                                               sink_cap=snk)))
        return out

    def _warm_buckets(self):
        """Compile the update program for every size bucket a stroke can
        land in, on a second handle of the same image.  Each update raises
        sink capacities, so each solve also runs the global relabel that a
        stroke's solve runs: set-up builds it whatever the warm-up strokes
        draw."""
        jax = self.ctx.jax
        scratch = self.ctx.solver.prepare(to_problem(self.base),
                                          self._part(self.base))
        scratch.solve()
        r = self.traffic["brush_radius"]
        most = 2 * len(families.ball((4 * r + 2,) * self.dims,
                                     (2 * r,) * self.dims, r))
        b = 1
        while b < 2 * most:
            # exactly b terminal entries differ from the handle's problem
            snk = self.base["sink_cap"].copy()
            snk[:b] = scratch.problem.sink_cap[:b] + 1
            scratch.update(sink_cap=snk)
            scratch.solve()
            b *= 2
        _sync(jax, scratch)
        del scratch

    def _stroke(self, stroke, key):
        jax, spans = self.ctx.jax, self.ctx.spans
        _, exc, snk, edited = stroke
        self.instances[key] = edited
        t0 = time.perf_counter()
        with spans("update") as sp_upd:
            self.handle.update(excess=exc, sink_cap=snk)
            _sync(jax, self.handle)
        with spans("solve") as sp_solve:
            res = self.handle.solve()
        t1 = time.perf_counter()
        return Request(t0, t1, 1, [res.stats.sweeps],
                       [res.stats.engine_iters],
                       {"update": sp_upd.seconds, "solve": sp_solve.seconds},
                       [(res.flow_value, res.source_side, key)])

    def request(self, i):
        k = i % len(self.strokes)
        return self._stroke(self.strokes[k], ("stroke", k))


class Fleet(_Loop):
    """Closed loop of ``Solver.solve_many`` calls, each on ``batch``
    instances whose extent along each axis is drawn from ``sides``
    (inclusive), a range that packs into one shape bucket (checked
    here)."""

    def __init__(self, ctx):
        super().__init__(ctx)
        t = self.traffic
        self.size = t["batch"]
        self.calls = [self._batch(("pool", c), families.rng_for(
            _BASE_SEED, _POOL, c)) for c in range(t["calls"])]
        self._check_one_bucket()
        for j in range(t.get("warmup", 1)):
            items = self._batch(("warm", j), families.rng_for(
                ctx.seed, _WARM, j))
            self.instances.update(items)
            self.warm_answers.extend(self._solve(items).answers)

    def _batch(self, key, rng):
        lo, hi = self.traffic["sides"]
        out = []
        for b in range(self.traffic["batch"]):
            shape = tuple(int(x) for x in rng.randint(lo, hi + 1,
                                                      size=self.dims))
            out.append((key + (b,), self._make(shape, rng)))
        return out

    def _check_one_bucket(self):
        from repro.core.graph import bucket_shape_for

        lo, hi = self.traffic["sides"]
        shapes = {}
        for shape in itertools.product(range(lo, hi + 1), repeat=self.dims):
            inst = self._make(shape, np.random.RandomState(0))
            handle = self.ctx.solver.prepare(to_problem(inst),
                                             self._part(inst))
            shapes[shape] = bucket_shape_for(handle.meta)
        if len(set(shapes.values())) != 1:
            raise SystemExit(f"fleet sides {lo}..{hi} pack into more than "
                             f"one shape bucket: {shapes}")

    def _solve(self, items):
        spans = self.ctx.spans
        problems = [to_problem(inst) for _, inst in items]
        parts = [self._part(inst) for _, inst in items]
        t0 = time.perf_counter()
        with spans("solve_many") as sp:
            results = self.ctx.solver.solve_many(problems, parts)
        t1 = time.perf_counter()
        if len(self.ctx.solver.last_batch_stats) != 1:
            raise RuntimeError("a fleet call packed into more than one "
                               "shape bucket")
        return Request(t0, t1, len(items), [r.stats.sweeps for r in results],
                       [r.stats.engine_iters for r in results],
                       {"solve_many": sp.seconds},
                       [(r.flow_value, r.source_side, key)
                        for r, (key, _) in zip(results, items)])

    def request(self, i):
        call = self.calls[i % len(self.calls)]
        return self._solve([self._present(key, inst, i * self.size + b)
                            for b, (key, inst) in enumerate(call)])


LOOPS = {"cold": Cold, "recut": Recut, "fleet": Fleet}
