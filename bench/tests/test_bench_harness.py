"""The harness finds its parts by name, refuses to run without a TPU, and
reports what the contract asks for."""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import pytest

from bench.tests.helpers import ROOT, run_cell, tiny_checkout


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A configuration, a traffic mix and a per-layer metric are added as
    new files plus new BENCHMARK.json entries; no existing file changes."""
    root = tiny_checkout(tmp_path)
    before = _digests(root)
    bench = root / "bench"
    (bench / "configs" / "grid4-small.json").write_text(json.dumps(dict(
        name="grid4-small", family="synthetic_grid",
        params=dict(connectivity=4, strength=40, excess_mag=90),
        partition=dict(kind="grid", splits=[2, 2]),
        solver=dict(num_regions=4))))
    (bench / "traffic" / "tiny-cold.json").write_text(json.dumps(dict(
        loop="cold", side=8, pool=2, warmup=1)))
    (bench / "metrics" / "requests_seen.tiny.py").write_text(
        "def read(run):\n    return len(run.requests)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(name="grid4-small", source="test",
                                file="bench/configs/grid4-small.json",
                                reduced=[], why="test"))
    spec["workloads"].append(dict(name="grid4-small.tiny", config="grid4-small",
                                  traffic="tiny-cold", chips=1, why="test"))
    spec["per_layer"].append(dict(
        name="requests_seen.tiny", unit="count", better="higher",
        source="program_counter", layer="front end", moves="cut_s",
        workloads=["grid4-small.tiny"]))
    for m in spec["end_to_end"]:
        if m["name"] == "cut_s":
            m["workloads"].append("grid4-small.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digests(root)
    assert all(after[k] == v for k, v in before.items())
    rc, line, err = run_cell(root, "grid4-small.tiny")
    assert rc == 0, err
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "cut_s"}
    rc, line, err = run_cell(root, "grid4-small.tiny", trace=1)
    assert rc == 0, err
    assert line["metrics"]["requests_seen.tiny"]["value"] >= 1


def test_run_refuses_without_a_tpu(tmp_path):
    from bench import run
    import io

    out, err = io.StringIO(), io.StringIO()
    rc = run.run(["--workload", "synth2d-8c.cold", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], out=out, err=err)
    assert rc != 0
    assert out.getvalue() == ""
    assert "no accelerator" in err.getvalue()


def test_command_refuses_without_src_or_tpu(tmp_path):
    """Run as the driver does, from a directory that holds only
    BENCHMARK.json and bench/: it exits non-zero and prints no result."""
    tiny_checkout(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "synth2d-8c.cold",
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("cell", ["synth2d-8c.cold", "seg2d-seeds.recut",
                                  "synth2d-8c.fleet", "seg2d-seeds.cold"])
def test_cell_runs_correct_on_cpu(tmp_path, cell):
    root = tiny_checkout(tmp_path)
    rc, line, err = run_cell(root, cell)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert line["attempted"] >= 1 and line["failed"] == 0
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert line["checks"]["unwarmed_compiles"]["value"] == 0
    assert line["device"]["count"] == 1


def test_cache_serves_nothing_in_the_window(tmp_path):
    """Even where the persistent cache keeps every program (as JAX does for
    one whose compile once took a second), a later run's window loads
    nothing from it: the window runs on what set-up built."""
    import jax

    root = tiny_checkout(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in (5, 6):
        rc, line, err = run_cell(root, "seg2d-seeds.cold", seed=seed)
        assert rc == 0 and line["correct"] is True, err
        info = json.loads(next(ln for ln in err.splitlines()
                               if ln.startswith('{"cuts_checked"')))
        assert info["cache_loads_in_window"] == 0
        assert line["checks"]["unwarmed_compiles"]["value"] == 0
