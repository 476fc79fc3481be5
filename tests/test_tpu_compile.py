"""AOT compiles for a described TPU v5e topology: what the chip's compiler
accepts, checked without a chip.

The topology is described inside a module fixture (only the worker that
runs this file loads the TPU compiler), and every test compiles in this
process.  Code that asks ``jax.default_backend()`` still sees the CPU
here, so the kernels are called with ``interpret=False`` and sweep-level
programs are steered from the test (``tpu_backend``).
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core import SweepConfig, build
from repro.core import grid_partition
from repro.core.distributed import (flowstate_shardings, grid_like_meta,
                                    make_sharded_sweep, maxflow_input_specs)
from repro.core.sweep import parallel_sweep
from repro.data.grids import synthetic_grid
from repro.kernels import push_relabel as pr

V_REGION, E = 65536, 8            # one region of a 1024^2 grid cut 4x4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described device is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:              # noqa: BLE001 — any refusal skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_backend(monkeypatch):
    """Make the engine resolve the TPU backend while a program is traced
    (it picks compiled kernels when ``jax.default_backend()`` is "tpu")."""
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    monkeypatch.undo()
    jax.clear_caches()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("value_dtype,mask_dtype",
                         [(jnp.int32, jnp.bool_), (jnp.int16, jnp.int8)],
                         ids=["int32", "int16"])
def test_blocked_kernel_compiles(one_chip, value_dtype, mask_dtype):
    """The blocked two-phase kernel (push and relabel outputs), one real
    region per call."""
    s = lambda shape, dt: _sds(one_chip, shape, dt)
    V = V_REGION
    args = (s((V,), value_dtype), s((V, E), value_dtype),
            s((V,), value_dtype), s((V,), value_dtype),
            s((V, E), jnp.int32), s((V, E), mask_dtype),
            s((V, E), mask_dtype), s((V, E), value_dtype),
            s((), jnp.int32))
    fn = lambda *a: pr.push_relabel_phase(*a, interpret=False, mode="both")
    compiled = jax.jit(fn).lower(*args).compile()
    assert _has_kernel(compiled)


def test_blocked_kernel_compiles_padded_rows(one_chip):
    """A region whose row count is not a whole number of tiles (a 100^2
    grid cut 3x3: 1,156 vertices) takes the kernel's row padding."""
    s = lambda shape, dt=jnp.int32: _sds(one_chip, shape, dt)
    V = 34 * 34
    assert V % pr.DEFAULT_BLOCK_V
    args = (s((V,)), s((V, E)), s((V,)), s((V,)), s((V, E)),
            s((V, E), jnp.bool_), s((V, E), jnp.bool_), s((V, E)), s(()))
    fn = lambda *a: pr.push_relabel_phase(*a, interpret=False, mode="both")
    assert _has_kernel(jax.jit(fn).lower(*args).compile())


def _grid_state(n, sharding):
    p = synthetic_grid(n, n, connectivity=8, strength=150, seed=0)
    meta, state, _ = build(p, grid_partition((n, n), (4, 4)))
    return meta, jax.tree.map(
        lambda a: _sds(sharding, a.shape, a.dtype), state)


def _while_loops(hlo: str) -> dict:
    """``{while name: opcodes reachable from its body}`` of an HLO module's
    text, following ``body=``, ``calls=``, ``to_apply=`` and the like into
    nested and fused computations."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    callee = re.compile(r"(?:body|condition|calls|to_apply|branch_computations"
                        r"|called_computations)=\{?(%[^}\s]+(?:, %[^},\s]+)*)")
    opcode = re.compile(r"= .*? ([a-z][a-z0-9\-]*)\(")

    def reach(name):
        seen, todo = set(), [name]
        while todo:
            c = todo.pop()
            if c in seen or c not in comps:
                continue
            seen.add(c)
            for line in comps[c]:
                for m in callee.finditer(line):
                    todo += [x.strip().lstrip("%").rstrip(",")
                             for x in m.group(1).split(",")]
        return [m.group(1) for c in seen for line in comps[c]
                if (m := opcode.search(line))]

    loops = {}
    for lines in comps.values():
        for line in lines:
            m = re.search(r"%(while\.\d+) = .* while\(.*body=%([^,\s]+)",
                          line)
            if m:
                loops[m.group(1)] = reach(m.group(2))
    return loops


def test_engine_loop_applies_pushes_without_scatter(one_chip, tpu_backend):
    """The engine's while body (the innermost loop that holds the excess
    split's cumsum) applies intra-region pushes by gathering from the
    reverse arc: two gathers (neighbour labels, reverse pushes) and no
    scatter, on a 32^2 8-connected grid in 4x4 regions (xla backend)."""
    meta, state = _grid_state(32, one_chip)
    compiled = parallel_sweep.lower(
        meta, state, SweepConfig(method="ard", engine_backend="xla"),
        _sds(one_chip, (), jnp.int32)).compile()
    loops = _while_loops(compiled.as_text())
    engine = [ops for ops in loops.values()
              if "reduce-window" in ops and "while" not in ops]
    assert len(engine) == 1, sorted(loops)
    ops = engine[0]
    assert ops.count("scatter") == 0, ops
    assert ops.count("gather") == 2, ops


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_parallel_sweep_compiles(one_chip, tpu_backend, backend):
    """One ARD parallel sweep of a 256^2 grid in 4x4 regions; the pallas
    route carries the Mosaic kernel."""
    meta, state = _grid_state(256, one_chip)
    cfg = SweepConfig(method="ard", engine_backend=backend)
    compiled = parallel_sweep.lower(
        meta, state, cfg, _sds(one_chip, (), jnp.int32)).compile()
    assert _has_kernel(compiled) == (backend == "pallas")
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_sharded_sweep_compiles_on_four_chips(topo):
    """The sharded one-sweep SPMD program over a 4-chip mesh: 16 regions,
    4 per chip, with the boundary exchange as collectives."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("regions",))
    meta = grid_like_meta(16, 4096, E)
    fn = make_sharded_sweep(meta, mesh, SweepConfig(method="ard"))
    specs = jax.tree.map(
        lambda a, sh: _sds(sh, a.shape, a.dtype),
        maxflow_input_specs(meta), flowstate_shardings(mesh, ("regions",)))
    idx = _sds(NamedSharding(mesh, jax.sharding.PartitionSpec()), (),
               jnp.int32)
    compiled = fn.lower(specs, idx).compile()
    text = compiled.as_text()
    assert "all-gather" in text and "all-reduce" in text
