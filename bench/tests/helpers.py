"""A small copy of the benchmark's data files for CPU runs of the harness."""

from __future__ import annotations

import io
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# traffic at a size a CPU test can run in seconds
TINY = {"cold": dict(side=12, pool=2),
        "recut": dict(side=12, strokes=4, warmup=2),
        "fleet": dict(batch=4, calls=2)}


def tiny_checkout(tmp: Path) -> Path:
    """BENCHMARK.json and bench/'s data files under ``tmp``, with the
    traffic cut to CPU size."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp)
    shutil.copytree(ROOT / "bench", tmp / "bench", ignore=shutil.ignore_patterns(
        ".jax_cache", "__pycache__", "tests"))
    for name, upd in TINY.items():
        path = tmp / "bench" / "traffic" / f"{name}.json"
        data = json.loads(path.read_text())
        data.update(upd)
        path.write_text(json.dumps(data))
    return tmp


def run_cell(root: Path, cell: str, seed: int = 2**31 + 11,
             seconds: float = 1.0, trace: int = 0):
    """One CPU run of ``cell``: (exit code, result line or None, stderr)."""
    from bench import run

    out, err = io.StringIO(), io.StringIO()
    rc = run.run(["--workload", cell, "--seed", str(seed), "--seconds",
                  str(seconds), "--trace", str(trace)],
                 accept_devices=lambda devices: True, root=root, out=out,
                 err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
