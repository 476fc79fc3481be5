"""The harness finds its parts by name, refuses to run without a TPU, and
reports what the contract asks for."""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.tests.helpers import ROOT, run_cell, tiny_checkout


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


TEST_GENERATORS = Path(__file__).resolve().parent / "generators"
# a 26-connected volume family, found by name as a new generator file
VOLUME = dict(family="volume_seeds",
              params=dict(neighbours="all", smoothness=20, seed_strength=200),
              partition=dict(kind="grid", splits=[2, 2, 2]),
              solver=dict(num_regions=8))
READERS = {
    "requests_seen.tiny": "def read(run):\n    return len(run.requests)\n",
    "solves_recorded.tiny": (
        "def read(run):\n"
        "    n = sum(s.name == 'maxflow.solve' for s in run.program_spans)\n"
        "    return n or None\n"),
}


def _add_cell(root, cell, config, traffic, end_to_end):
    """A configuration, a traffic mix and the READERS added as new files
    plus new BENCHMARK.json entries; a family that the checkout lacks is
    added from TEST_GENERATORS."""
    bench = root / "bench"
    if not (bench / "generators" / f"{config['family']}.py").exists():
        shutil.copy(TEST_GENERATORS / f"{config['family']}.py",
                    bench / "generators")
    name = cell.split(".")[0]
    (bench / "configs" / f"{name}.json").write_text(json.dumps(
        dict(config, name=name)))
    (bench / "traffic" / f"{cell}.json").write_text(json.dumps(traffic))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(name=name, source="test",
                                file=f"bench/configs/{name}.json",
                                reduced=[], why="test"))
    spec["workloads"].append(dict(name=cell, config=name, traffic=cell,
                                  chips=1, why="test"))
    for metric, source in READERS.items():
        path = bench / "metrics" / f"{metric}.py"
        if not path.exists():
            path.write_text(source)
            spec["per_layer"].append(dict(
                name=metric, unit="count", better="higher",
                source="program_counter", layer="front end", moves="cut_s",
                workloads=[]))
        next(m for m in spec["per_layer"]
             if m["name"] == metric)["workloads"].append(cell)
    next(m for m in spec["end_to_end"]
         if m["name"] == end_to_end)["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.mark.parametrize("config", [
    dict(family="synthetic_grid",
         params=dict(connectivity=4, strength=40, excess_mag=90),
         partition=dict(kind="grid", splits=[2, 2]),
         solver=dict(num_regions=4)),
    VOLUME,
], ids=["2d-existing-family", "3d-new-family"])
def test_new_config_traffic_and_metric_are_found_by_name(tmp_path, config):
    """A configuration, a traffic mix and per-layer metrics (one reads the
    program's spans) are added as new files plus new BENCHMARK.json
    entries, a new family's generator too; no existing file changes."""
    root = tiny_checkout(tmp_path)
    before = _digests(root)
    _add_cell(root, "small.tiny", config,
              dict(loop="cold", side=8, pool=2, warmup=1), "cut_s")

    after = _digests(root)
    assert all(after[k] == v for k, v in before.items())
    rc, line, err = run_cell(root, "small.tiny")
    assert rc == 0, err
    assert line["correct"] is True, err
    assert set(line["metrics"]) == {"setup_s", "cut_s"}
    rc, line, err = run_cell(root, "small.tiny", trace=1)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert line["metrics"]["requests_seen.tiny"]["value"] >= 1
    assert line["metrics"]["solves_recorded.tiny"]["value"] >= 1


@pytest.mark.parametrize("config, traffic, end_to_end", [
    (VOLUME, dict(loop="recut", side=8, brush_radius=1, strokes=3, warmup=1),
     "cut_s"),
    # 6-connected: extents 7..8 pack into one shape bucket
    (dict(VOLUME, params=dict(VOLUME["params"], neighbours="faces")),
     dict(loop="fleet", batch=3, sides=[7, 8], calls=2, warmup=1),
     "cuts_per_s"),
], ids=["recut", "fleet"])
def test_volume_config_serves_every_loop(tmp_path, config, traffic,
                                         end_to_end):
    """A 3-D configuration under the warm-start and the batched loops: the
    brush is a ball, the fleet's extents are drawn per axis.  One warm-up
    stroke is enough: set-up builds every program a stroke runs."""
    root = tiny_checkout(tmp_path)
    _add_cell(root, "vol.tiny", config, traffic, end_to_end)
    rc, line, err = run_cell(root, "vol.tiny")
    assert rc == 0, err
    assert line["correct"] is True, err
    assert line["attempted"] >= 1 and line["failed"] == 0


@pytest.mark.parametrize("partition, message", [
    (dict(kind="bfs", regions=8), "partition kind 'bfs'"),
    (dict(kind="grid", splits=[2, 2]), "do not fit the 3-D grid"),
], ids=["kind", "dimension"])
def test_unserved_partition_is_refused_by_name(tmp_path, partition, message):
    from bench import families

    inst = families.make(VOLUME, (4, 4, 4), families.rng_for(1),
                         TEST_GENERATORS.parent)
    with pytest.raises(SystemExit, match=message):
        families.partition(dict(VOLUME, partition=partition), inst)


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
