"""Smoke test of the maxflow solver on a TPU chip, through the public API.

    python chip_smoke.py              # one chip: every single-chip phase
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

One chip.  An 8-connected ``synthetic_grid`` (paper Sec. 7.1) is cut into
4x4 regions and solved cold through ``Solver.prepare(...).solve()`` on
three engine routes: the default (XLA engine, one host sync per sweep),
the device-resident fused XLA engine, and the blocked Pallas kernel.
Every solve checks its cut == flow certificate; the routes are bit-exact
by design, so flow and sweep counts must agree, and none may step down the
degradation ladder.  Then two warm re-cuts (a 1% capacity perturbation
through ``handle.update``), a short ``MaxflowService`` pass of small mixed
grids plus a warm session re-cut, checked against the Edmonds-Karp oracle,
and last the megapixel (1024x1024) instance for one sweep with capped
engine runs on every route, whose states must be bit-identical.

Four chips.  The sharded SPMD route over a four-chip mesh (16 regions, 4
per chip) against the single-chip device-resident route it must equal:
solved to convergence at the small size, then the capped megapixel sweep.
Per-device peak memory shows the regions spread over the chips.

Each phase prints one line.  The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU the script exits non-zero before solving anything.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Vertices per side of the instance solved to convergence.  A converged
# megapixel solve is out of reach of the current engine on a v5e: engine
# iterations grow steeply with region size, so a converged solve took
# 27 s at 64x64 and 416 s at 96x96, and the first sweep alone 700 s at
# 256x256 (one v5e chip, jax 0.9).
GRID = 64
# The megapixel instance, run for a fixed amount of work on every route:
# the real-size programs, device memory and multi-tile Pallas grid.
BIG = 1024
BOUNDED = dict(max_sweeps=1, engine_max_iters=16)
REGIONS = (4, 4)
ROUTES = {                  # SolverOptions overrides per route
    "xla": {},
    "xla-fused-device": dict(engine_chunk_iters=8, device_resident=True),
    "pallas": dict(engine_backend="pallas"),
}
SERVICE_GRIDS = (16, 20, 24, 32, 16, 20, 24, 32)
PERTURB = 0.01


class Phases:
    """Per-phase seconds, and the one line each phase prints.

    ``compile_s`` sums XLA backend compiles (JAX's monitoring events; they
    do not nest); ``solve_s`` is the rest of the phase's wall time,
    tracing included, closed by ``block_until_ready``.
    """

    def __init__(self, jax):
        self.jax = jax
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, *args, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def run(self, name, fn):
        c0, t0 = self.compile_s, time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        compile_s = self.compile_s - c0
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.jax.devices()]
        dev = self.jax.devices()[0]
        line = dict(phase=name, device=dev.device_kind, **out,
                    compile_s=round(compile_s, 3),
                    solve_s=round(wall - compile_s, 3),
                    peak_bytes_in_use=peaks if len(peaks) > 1 else peaks[0])
        print(json.dumps(line), flush=True)
        return out


def _instance(n):
    from repro.core import grid_partition
    from repro.data.grids import synthetic_grid

    p = synthetic_grid(n, n, connectivity=8, strength=150, seed=0)
    return p, grid_partition((n, n), REGIONS)


def _solve(jax, handle, **kw):
    res = handle.solve(**kw)
    jax.block_until_ready(res.state.cf)
    assert res.converged, res.diagnosis
    assert res.stats.degraded == [], res.stats.degraded
    return res


def _summary(res):
    return dict(flow=res.flow_value, sweeps=res.stats.sweeps,
                engine_iters=res.stats.engine_iters)


def _options(num_regions=REGIONS[0] * REGIONS[1], **kw):
    from repro.core import SolverOptions

    return SolverOptions(num_regions=num_regions, check=True, **kw)


def one_chip(jax, phases):
    import numpy as np

    from repro.core import Solver

    p, part = _instance(GRID)
    results = {}
    handles = {}
    for route, kw in ROUTES.items():
        handle = Solver(_options(**kw)).prepare(p, part)
        res = phases.run(f"solve/{route}/{GRID}x{GRID}", lambda: _summary(
            _solve(jax, handle)))
        results[route] = res
        handles[route] = handle
    first = results["xla"]
    for route, res in results.items():
        assert (res["flow"], res["sweeps"]) == (first["flow"],
                                                first["sweeps"]), results

    # warm re-cuts on the default route (the CLI's --resolve path)
    handle = handles["xla"]
    rng = np.random.RandomState(1)
    m = len(p.edges)
    k = max(1, int(round(PERTURB * m)))
    for i in range(2):
        idx = rng.choice(m, size=k, replace=False)
        handle.update(arcs=idx,
                      cap_fwd=rng.randint(0, 301, size=k).astype(np.int32),
                      cap_bwd=rng.randint(0, 301, size=k).astype(np.int32))
        phases.run(f"recut/{i + 1}/{k}-edges", lambda: _summary(
            _solve(jax, handle)))

    phases.run("service", lambda: _service())
    bounded(jax, phases, [(route, kw, {}) for route, kw in ROUTES.items()])


def bounded(jax, phases, runs):
    """The megapixel instance for one capped sweep on each of ``runs``
    (name, SolverOptions overrides, ``solve`` arguments).  They must leave
    bit-identical states, and nothing may degrade.  Peak device memory is
    process-wide, so cumulative over the phases; the host-loop routes also
    print what XLA reports for their sweep program.
    """
    import numpy as np

    from repro.core import Solver

    p, part = _instance(BIG)
    states = {}
    for name, kw, solve_kw in runs:
        t0 = time.perf_counter()
        handle = Solver(_options(**kw, **BOUNDED)).prepare(p, part)
        jax.block_until_ready(handle.state.cf)
        prepare_s = time.perf_counter() - t0

        def solve():
            out, states[name] = _bounded_solve(jax, handle, **solve_kw)
            return dict(prepare_s=round(prepare_s, 3), **out)

        phases.run(f"bounded/{name}/{BIG}x{BIG}", solve)
        del handle                    # free its device arrays for the next
    first = states[runs[0][0]]
    for name, state in states.items():
        for k in first:
            assert np.array_equal(state[k], first[k]), (name, k)


def _bounded_solve(jax, handle, mesh=None):
    import jax.numpy as jnp
    import numpy as np

    from repro.core.sweep import parallel_sweep

    out = {}
    opts = handle.solver.options
    if not opts.device_resident:      # the host loop runs this program
        mem = parallel_sweep.lower(handle.meta, handle.state,
                                   opts.sweep_config(),
                                   jnp.int32(0)).compile().memory_analysis()
        out.update(sweep_argument_bytes=mem.argument_size_in_bytes,
                   sweep_temp_bytes=mem.temp_size_in_bytes)
    res = handle.solve(mesh=mesh)
    jax.block_until_ready(res.state.cf)
    assert res.stats.degraded == [], res.stats.degraded
    out.update(_summary(res), converged=res.converged)
    state = {k: np.asarray(getattr(res.state, k))
             for k in ("flow_to_t", "d", "excess", "sink_cf", "cf")}
    return out, state


def _service():
    import numpy as np

    from repro.data.grids import synthetic_grid
    from repro.kernels.ref import maxflow_oracle
    from repro.serve import MaxflowService, ServiceConfig, SolveRequest

    svc = MaxflowService(_options(num_regions=4), ServiceConfig(max_batch=4))
    problems = [synthetic_grid(n, n, connectivity=8 if i % 2 else 4,
                               strength=150, seed=i)
                for i, n in enumerate(SERVICE_GRIDS)]
    tickets = [svc.submit(SolveRequest(problem=q)) for q in problems]
    session = problems[0]
    tickets.append(svc.submit(SolveRequest(problem=session, session="s")))
    svc.run_until_idle()
    arcs = np.arange(8)
    recut = svc.submit(SolveRequest(
        session="s", update={"arcs": arcs,
                             "cap_fwd": session.cap_fwd[arcs] + 70}))
    svc.run_until_idle()
    svc.close()
    want = [maxflow_oracle(q)[0] for q in problems + [session]]
    want.append(maxflow_oracle(svc._sessions["s"].problem)[0])
    got = [t.outcome().flow_value for t in tickets + [recut]]
    assert got == want, (got, want)
    return dict(requests=len(got), flows_match_oracle=True,
                completed=svc.stats.completed)


def four_chips(jax, phases):
    from repro.core import Solver

    assert len(jax.devices()) == 4, jax.devices()
    p, part = _instance(GRID)
    kw = ROUTES["xla-fused-device"]
    mesh = jax.make_mesh((4,), ("regions",))
    sharded = phases.run(f"solve/sharded-x4/{GRID}x{GRID}", lambda: _summary(
        _solve(jax, Solver(_options(**kw)).prepare(p, part), mesh=mesh)))
    single = phases.run(f"solve/xla-fused-device/{GRID}x{GRID}",
                        lambda: _summary(_solve(
                            jax, Solver(_options(**kw)).prepare(p, part))))
    assert (sharded["flow"], sharded["sweeps"]) == (single["flow"],
                                                    single["sweeps"]), \
        (sharded, single)
    bounded(jax, phases, [("sharded-x4", kw, dict(mesh=mesh)),
                          ("xla-fused-device", kw, {})])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    from repro.launch.cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"no TPU: JAX found {dev.platform} devices")
    enable_compile_cache()
    phases = Phases(jax)
    (four_chips if args.chips == 4 else one_chip)(jax, phases)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
