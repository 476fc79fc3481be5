"""The window's inputs are pinned: the problem pools of the four cells, the
recut session's image and strokes and the fleet's calls, made by the
harness, hash to the digests below, and seeds 0-15 draw the presentations
listed.  A change to the harness that alters any of them changes what every
accepted number of these cells was measured on."""

from __future__ import annotations

import hashlib
import json

import jax
import numpy as np
import pytest

from bench import control, loops, run
from bench.reference import min_cut
from bench.tests.helpers import ROOT


def _hash_instance(h, inst):
    h.update(repr((int(inst["n"]), tuple(inst["shape"]))).encode())
    for k in ("edges", "cap_fwd", "cap_bwd", "excess", "sink_cap"):
        a = np.ascontiguousarray(inst[k])
        h.update(a.dtype.str.encode())
        h.update(a.tobytes())


def _loop(config, traffic, seed):
    """The cell's load loop as set-up builds it, with the reference in the
    program's place for the cuts set-up takes."""
    from repro.core import Solver, SolverOptions

    cfg = json.loads((ROOT / "bench/configs" / f"{config}.json").read_text())
    tr = json.loads((ROOT / "bench/traffic" / f"{traffic}.json").read_text())
    solver = Solver(SolverOptions(**cfg["solver"]))
    ctx = run.Context(jax, cfg, tr, seed, solver, run.Spans())
    with control.replaced_answers(min_cut):
        return loops.LOOPS[tr["loop"]](ctx)


def _cold(config):
    lp = _loop(config, "cold", 0)
    h = hashlib.sha256()
    for inst in lp.pool:
        _hash_instance(h, inst)
    presented, syms = hashlib.sha256(), []
    for seed in range(16):
        lp.ctx.seed = seed
        ks = ""
        for i in range(8):
            k = i % len(lp.pool)
            key, inst = lp._present(("pool", k), lp.pool[k], i)
            ks += str(key[-1])
            _hash_instance(presented, inst)
        syms.append(ks)
    return dict(pool=h.hexdigest(), presented=presented.hexdigest(),
                syms=" ".join(syms))


def _recut():
    image, strokes, syms = hashlib.sha256(), hashlib.sha256(), []
    for seed in range(16):
        lp = _loop("seg2d-seeds", "recut", seed)
        syms.append(str(lp.warm_answers[0][2][-1]))
        _hash_instance(image, lp.base)
        for centre, exc, snk, _ in lp.strokes:
            strokes.update(repr(tuple(int(c) for c in centre)).encode())
            strokes.update(exc.tobytes())
            strokes.update(snk.tobytes())
    return dict(image=image.hexdigest(), strokes=strokes.hexdigest(),
                syms=" ".join(syms))


def _fleet():
    lp = _loop("synth2d-8c", "fleet", 0)
    h = hashlib.sha256()
    for call in lp.calls:
        for key, inst in call:
            h.update(repr(key).encode())
            _hash_instance(h, inst)
    presented, syms = hashlib.sha256(), []
    for seed in range(16):
        lp.ctx.seed = seed
        ks = ""
        for b, (key, inst) in enumerate(lp.calls[0]):
            key, inst = lp._present(key, inst, b)
            ks += str(key[-1])
            _hash_instance(presented, inst)
        syms.append(ks)
    return dict(calls=h.hexdigest(), presented=presented.hexdigest(),
                syms=" ".join(syms))


# Per cell: the digests of its inputs as made and as presented to seeds
# 0..15, and per seed the symmetry (families.signed_permutations) of each of
# the first draws: 8 requests of a cold cell, the recut session, the 16
# instances of the fleet's first call.
PINNED = {
    "synth2d-8c.cold": dict(
        pool="c1bdb5e5d5bca5202d8a4263379743e802c2b988c920f1be5549505e3a10f45e",
        presented=(
            "f833dd943298a4bc135251c3cda9d3e806db4edb73e4b18e72cfb2c0b88c21b9"),
        syms=("73454710 46774505 57615130 15620015 71566175 15632061 "
              "17553066 15261246 33636221 52431527 06255724 64441606 "
              "64623167 32632571 44047303 56023635")),
    "seg2d-seeds.cold": dict(
        pool="59b0f5bb49d7fe8bfe62f3ad5bda5ea4c0c75f7a54a58a7dd0de1de7268ed0c7",
        presented=(
            "a65466824587859217f8080a87c7380dbb3a9f0e54f6a929dc2e88587ce0042e"),
        syms=("73454710 46774505 57615130 15620015 71566175 15632061 "
              "17553066 15261246 33636221 52431527 06255724 64441606 "
              "64623167 32632571 44047303 56023635")),
    "seg2d-seeds.recut": dict(
        image="4ca15a03a87bff7518ffc3773b1a41470965872c21c1041460c86ddc414893cf",
        strokes="502d513a034ba823dcda89bb7326c644bfba3e92966ba8762f08a0a8d4f7232e",
        syms=("7 4 5 1 7 1 1 1 3 5 0 6 6 3 4 5")),
    "synth2d-8c.fleet": dict(
        calls="963190a33743275560762eb7bd6d177c6a97339e9ef602db64a65caf6e43e564",
        presented=(
            "e7e1e5f97297c0783256c919f8c24bd4de4772c7936ac41ada844f2daf7969a7"),
        syms=("6301471017067151 0671450114540650 1741511017630101 "
              "1540001110511161 6110611101630100 1541200111060550 "
              "1711300017070150 1540120014171001 6341620101504660 "
              "1201150111071111 0641570010071511 5400160017637661 "
              "5440310100627510 6241251117054110 0400730104650160 "
              "1600361110127500")),
}

MAKE = {"synth2d-8c.cold": lambda: _cold("synth2d-8c"),
        "seg2d-seeds.cold": lambda: _cold("seg2d-seeds"),
        "seg2d-seeds.recut": _recut,
        "synth2d-8c.fleet": _fleet}


@pytest.mark.parametrize("cell", list(PINNED))
def test_inputs_are_pinned(cell):
    assert MAKE[cell]() == PINNED[cell]
