import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT-lower + compile every (arch x shape x mesh) cell.

For each cell:

1. the FULL-DEPTH step program (train_step / prefill / decode serve_step) is
   lowered with ShapeDtypeStruct inputs and compiled for the production mesh
   with scan-over-layers (compact HLO) — this proves the sharding config is
   coherent and yields the realistic memory_analysis();
2. two PROBE programs at depth = 1 and 2 block-pattern periods, with every
   scan fully unrolled, give exact per-period FLOPs / bytes / collective
   bytes (XLA cost analysis counts while bodies once, so the full program
   undercounts by the trip count).  Totals are the affine extrapolation
       total = probe1 + (num_layers/period - 1) * (probe2 - probe1),
   exact for homogeneous stacks and accurate to the partial final period
   otherwise.

Results are written as JSON per cell for EXPERIMENTS.md §Dry-run/§Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-mini-3.8b \
      --shape train_4k --mesh single --out experiments/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

The maxflow solver itself is dry-run with --arch maxflow (region = chip).
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, shape_skip_reason
from repro.configs.registry import ARCHS, get_arch
from repro.launch.mesh import make_production_mesh
from repro.roofline import analysis as roof

# the chip the production mesh models (launch/mesh.py: v5e-256 pods);
# its published peaks price the compiled cost terms
TARGET_DEVICE_KIND = "TPU v5 lite"
Q_CHUNK_THRESHOLD = 2048      # chunk whenever S exceeds this
Q_CHUNK = 1024
MICROBATCHES = 1              # grad-accumulation factor (hillclimb knob)


def _mesh_tag(multi_pod: bool) -> str:
    return "multi" if multi_pod else "single"


def _probe_depth(cfg) -> int:
    if cfg.block_kind == "xlstm":
        return 2
    if cfg.block_kind == "rglru":
        return 3
    if cfg.pattern_local:
        return cfg.pattern_local + cfg.pattern_global
    return 1


def _lower_cell(cfg, shape, mesh, *, unroll):
    """Build + lower the step program for one cell; returns lowered."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch import shardings as shd
    from repro.models import model as model_lib
    from repro.train import optimizer as opt_lib
    from repro.train import serve as serve_lib
    from repro.train import train_loop as tl

    q_chunk = Q_CHUNK if shape.seq_len > Q_CHUNK_THRESHOLD else None
    params_shape = jax.eval_shape(
        lambda k: model_lib.init_params(cfg, k, jnp.bfloat16),
        jax.random.PRNGKey(0))

    if shape.kind == "train":
        step, state_sh, bspec = tl.make_sharded_train_step(
            cfg, mesh, opt_lib.AdamWConfig(), donate=False,
            seq_len=shape.seq_len, unroll=unroll, q_chunk=q_chunk,
            global_batch=shape.global_batch, microbatches=MICROBATCHES)
        opt_shape = jax.eval_shape(
            __import__("repro.train.optimizer", fromlist=["x"])
            .init_opt_state, params_shape)
        state = tl.TrainState(params=params_shape, opt=opt_shape)
        batch = tl.train_batch_specs(cfg, shape.seq_len, shape.global_batch)
        return step.lower(state, batch)

    if shape.kind == "prefill":
        p_sh = shd.param_shardings(cfg, mesh, params_shape)
        cache_shape = serve_lib.cache_specs_struct(
            cfg, shape.global_batch, shape.seq_len)
        c_sh = shd.cache_specs(cfg, mesh, cache_shape)
        dp = 1
        for a in mesh.axis_names:
            if a in ("pod", "data"):
                dp *= mesh.shape[a]
        bspec = NamedSharding(mesh, shd.batch_pspec(mesh)
                              if shape.global_batch % dp == 0 else P())
        act_sh = None
        if shape.seq_len % mesh.shape["model"] == 0:
            dpa = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
            act_sh = NamedSharding(mesh, P(
                dpa if shape.global_batch % dp == 0 else None,
                "model", None))
        batch = _prefill_batch_specs(cfg, shape)

        def bsh(x):
            if x.ndim >= 1 and x.shape[0] == shape.global_batch \
                    and shape.global_batch % dp == 0:
                return bspec
            return NamedSharding(mesh, P())

        batch_sh = jax.tree.map(bsh, batch)
        fn = serve_lib.make_prefill_step(cfg, unroll=unroll, q_chunk=q_chunk,
                                         act_sharding=act_sh)
        step = jax.jit(fn, in_shardings=(p_sh, batch_sh, c_sh),
                       out_shardings=(None, c_sh))
        cache_struct = cache_shape
        return step.lower(params_shape, batch, cache_struct)

    # decode
    step, p_sh, c_sh, t_sh = serve_lib.make_sharded_decode_step(
        cfg, mesh, shape.global_batch, shape.seq_len, unroll=unroll)
    cache_shape = serve_lib.cache_specs_struct(
        cfg, shape.global_batch, shape.seq_len)
    toks = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    return step.lower(params_shape, toks, cache_shape)


def _cost_triple(compiled, hlo=None):
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    text = hlo if hlo is not None else compiled.as_text()
    coll = roof.collective_bytes(text)
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            float(coll["total"]), coll)


def dryrun_lm_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
                   probes: bool = True, cfg_override=None) -> dict:
    from repro.models import model as model_lib

    cfg = cfg_override if cfg_override is not None else get_arch(arch_name)
    shape = SHAPES[shape_name]
    skip = shape_skip_reason(cfg, shape)
    if skip:
        return {"arch": arch_name, "shape": shape_name,
                "mesh": _mesh_tag(multi_pod), "status": "skip",
                "reason": skip}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size

    t0 = time.time()
    with mesh:
        lowered = _lower_cell(cfg, shape, mesh, unroll=1)
        t_lower = round(time.time() - t0, 1)
        compiled = lowered.compile()
        t_compile = round(time.time() - t0 - t_lower, 1)
    mem = roof.memory_summary(compiled)
    raw_flops, raw_bytes, raw_coll, _ = _cost_triple(compiled)

    # ---- probes: exact per-period cost ----
    flops = nbytes = coll = None
    coll_detail = {}
    if probes:
        base = _probe_depth(cfg)
        vals = []
        for depth in (base, 2 * base):
            pcfg = dataclasses.replace(cfg, num_layers=depth)
            with mesh:
                pl = _lower_cell(pcfg, shape, mesh, unroll=True)
                pc = pl.compile()
            vals.append(_cost_triple(pc))
        n = cfg.num_layers / base
        f1, b1, c1, d1 = vals[0]
        f2, b2, c2, d2 = vals[1]
        # per-period slopes; clamped at 0 — XLA occasionally optimises the
        # 2-period probe below the 1-period one (fusion differences), and a
        # negative per-layer cost is non-physical.
        flops = f1 + (n - 1) * max(f2 - f1, 0.0)
        nbytes = b1 + (n - 1) * max(b2 - b1, 0.0)
        coll = c1 + (n - 1) * max(c2 - c1, 0.0)
        coll_detail = {
            "probe1": d1["per_kind"], "probe2": d2["per_kind"],
            "counts_probe2": d2["counts"],
        }

    n_params = model_lib.param_count(cfg, active_only=True)
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        model_flops = 6.0 * n_params * tokens
    elif shape.kind == "prefill":
        model_flops = 2.0 * n_params * shape.seq_len * shape.global_batch
    else:
        model_flops = 2.0 * n_params * shape.global_batch

    use_f = flops if flops is not None else raw_flops
    use_b = nbytes if nbytes is not None else raw_bytes
    use_c = coll if coll is not None else raw_coll
    peaks = roof.peaks_for(TARGET_DEVICE_KIND)
    compute_s = use_f / peaks.flops
    memory_s = use_b / peaks.hbm_bw
    collective_s = use_c / peaks.link_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    rec = {
        "arch": arch_name, "shape": shape_name,
        "mesh": _mesh_tag(multi_pod), "status": "ok", "n_chips": n_chips,
        "lower_s": t_lower, "compile_s": t_compile,
        "memory": mem,
        "raw_cost": {"flops": raw_flops, "bytes": raw_bytes,
                     "coll_bytes": raw_coll,
                     "note": "scan bodies counted once (see probes)"},
        "roofline": {
            "flops": use_f, "bytes_accessed": use_b, "coll_bytes": use_c,
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s,
            "bottleneck": max(terms, key=terms.get),
            "model_flops": model_flops,
            "useful_ratio": (model_flops / (use_f * n_chips)
                             if use_f else 0.0),
            "coll_detail": coll_detail,
        },
        "n_params": n_params,
    }
    return rec


def _prefill_batch_specs(cfg, shape):
    f = jax.ShapeDtypeStruct
    B, S = shape.global_batch, shape.seq_len
    if cfg.frontend == "audio_frames":
        return {"frames": f((B, S, cfg.frontend_dim), jnp.bfloat16)}
    if cfg.frontend == "vision_patches":
        return {"tokens": f((B, S - cfg.num_patches), jnp.int32),
                "patches": f((B, cfg.num_patches, cfg.frontend_dim),
                             jnp.bfloat16)}
    return {"tokens": f((B, S), jnp.int32)}


def dryrun_maxflow(*, multi_pod: bool, region_size: int = 4096,
                   degree: int = 8, exchange: str = "full") -> dict:
    """Dry-run the distributed P-ARD sweep: one region per chip."""
    from repro.core.distributed import (grid_like_meta, make_sharded_sweep,
                                        maxflow_input_specs)
    from repro.core.sweep import SweepConfig

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    V, E = region_size, degree
    meta = grid_like_meta(n_chips, V, E)
    axes = tuple(mesh.axis_names)
    t0 = time.time()
    with mesh:
        fn = make_sharded_sweep(meta, mesh, SweepConfig(method="ard"),
                                axes=axes, exchange=exchange)
        specs = maxflow_input_specs(meta)
        lowered = fn.lower(specs, jax.ShapeDtypeStruct((), jnp.int32))
        t_lower = round(time.time() - t0, 1)
        compiled = lowered.compile()
        t_compile = round(time.time() - t0 - t_lower, 1)
    flops, nbytes, coll, coll_d = _cost_triple(compiled)
    peaks = roof.peaks_for(TARGET_DEVICE_KIND)
    terms = {"compute": flops / peaks.flops,
             "memory": nbytes / peaks.hbm_bw,
             "collective": coll / peaks.link_bw}
    return {
        "arch": f"maxflow-pard-{exchange}", "shape": f"V{V}xE{E}",
        "mesh": _mesh_tag(multi_pod), "status": "ok", "n_chips": n_chips,
        "lower_s": t_lower, "compile_s": t_compile,
        "memory": roof.memory_summary(compiled),
        "roofline": {
            "flops": flops, "bytes_accessed": nbytes, "coll_bytes": coll,
            "compute_s": terms["compute"], "memory_s": terms["memory"],
            "collective_s": terms["collective"],
            "bottleneck": max(terms, key=terms.get),
            "note": ("per-sweep cost; engine while-loops counted once per "
                     "discharge iteration — see benchmarks for measured "
                     "iteration counts"),
            "coll_detail": coll_d["per_kind"],
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
        cells.append(("maxflow", None))
    else:
        assert args.arch
        if args.arch == "maxflow":
            cells = [("maxflow", None)]
        else:
            shapes = [args.shape] if args.shape else list(SHAPES)
            cells = [(args.arch, s) for s in shapes]

    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape or 'sweep'}__{_mesh_tag(mp)}"
            path = out / f"{tag}.json"
            if path.exists():
                print(f"[dryrun] {tag}: cached", flush=True)
                continue
            print(f"[dryrun] {tag}: running...", flush=True)
            t0 = time.time()
            try:
                if arch == "maxflow":
                    rec = dryrun_maxflow(multi_pod=mp)
                else:
                    rec = dryrun_lm_cell(arch, shape, multi_pod=mp,
                                         probes=not args.no_probes)
            except Exception as e:
                rec = {"arch": arch, "shape": shape,
                       "mesh": _mesh_tag(mp), "status": "error",
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
            rec["wall_s"] = round(time.time() - t0, 1)
            path.write_text(json.dumps(rec, indent=2))
            print(f"[dryrun] {tag}: {rec['status']} ({rec['wall_s']}s)",
                  flush=True)


if __name__ == "__main__":
    main()
