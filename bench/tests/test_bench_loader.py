"""The benchmark's files: found by name, true to the paper, off-chip refusal.

Nothing here loads a TPU library: the harness is imported, never run on
a device, and the one run of ``bench/run.py`` is a child process held
to the CPU.
"""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, tracereduce

ROOT = Path(__file__).resolve().parents[2]
MAN = harness.manifest(ROOT)


@pytest.mark.parametrize("cell", [c["name"] for c in MAN["workloads"]])
def test_cell_files_found_by_name(cell):
    c = harness.find_cell(MAN, cell)
    config = harness.load_config(c["config"])
    traffic = harness.load_traffic(c["traffic"])
    ref = harness.load_module("references", config["reference"])
    points = harness.grid_points(config, traffic)
    assert points
    for p in points:
        ref.check_supported(p)
    assert set(traffic["limits"]) == {"z_max", "stats_z_max", "unfinished"}
    assert c["chips"] == max(1, points[0].get("engine_shards", 0))


@pytest.mark.parametrize("metric", [m["name"] for m in MAN["per_layer"]])
def test_metric_reader_found_by_name(metric):
    assert callable(harness.load_module("metrics", metric).read)


def test_config_entries_match_their_files():
    for c in MAN["configs"]:
        cfg = harness.load_json(ROOT / c["file"])
        assert cfg["name"] == c["name"]
        assert (ROOT / c["file"]).parent == harness.BENCH / "configs"
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}


def _throwaway(tmp_path):
    """A bench directory with one new cell, one new config and one new
    metric, added as files only."""
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "testdata",
                                                  "__pycache__"))
    cfg = harness.load_config("table1_exp")
    cfg["name"] = "small_cluster"
    cfg["params"].update(job_size=64, working_pool_size=72,
                         spare_pool_size=8, warm_standbys=2)
    (bench / "configs" / "small_cluster.json").write_text(json.dumps(cfg))
    traffic = {"entry": "run_replications_batch", "replicas": 32,
               "grid": {"warm_standbys": [0, 2], "recovery_time": [5.0]},
               "set": {"job_length": 1440.0}, "reference_replicas": 16,
               "limits": {"z_max": 6.0, "stats_z_max": 6.0,
                          "unfinished": 0}}
    (bench / "workloads" / "standby_grid.json").write_text(
        json.dumps(traffic))
    (bench / "metrics" / "span_s.py").write_text(
        "def read(view):\n    return view.span_s\n")
    man = json.loads(json.dumps(MAN))
    man["configs"].append({"name": "small_cluster", "source": "test",
                           "file": "bench/configs/small_cluster.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "standby_grid",
                             "config": "small_cluster",
                             "traffic": "standby_grid", "chips": 1,
                             "why": "test"})
    man["per_layer"].append({"name": "span_s", "unit": "s",
                             "better": "lower", "source": "device_trace",
                             "layer": "device", "moves":
                             "trajectories_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    return tmp_path, bench


def test_new_cell_config_and_metric_are_files_only(tmp_path):
    root, bench = _throwaway(tmp_path)
    man = harness.manifest(root)
    cell = harness.find_cell(man, "standby_grid")
    config = harness.load_config(cell["config"], bench)
    traffic = harness.load_traffic(cell["traffic"], bench)
    points = harness.grid_points(config, traffic)
    assert [(p["warm_standbys"], p["recovery_time"]) for p in points] == \
        [(0, 5.0), (2, 5.0)]
    assert all(p["job_length"] == 1440.0 and p["job_size"] == 64
               for p in points)
    study = harness.Study(config, traffic)
    assert study.trajectories == 64 and study.warm_steps() == 64
    names = [m["name"] for m in
             harness.metric_entries(man, "per_layer", "standby_grid")]
    assert names == ["span_s"]
    reader = harness.load_module("metrics", "span_s", bench)
    view = tracereduce.TraceView((0.0, 2e9), [tracereduce.Chip()], [])
    assert reader.read(view) == 2.0


def test_fig2a_grid_equals_paper_tables():
    from benchmarks.paper_tables import POOL_SIZES, paper_params
    from repro.core.params import PAPER_TABLE1_RANGES

    c = harness.find_cell(MAN, "fig2a_grid")
    study = harness.Study(harness.load_config(c["config"]),
                          harness.load_traffic(c["traffic"]))
    want = [paper_params(recovery_time=v, working_pool_size=w)
            for v in PAPER_TABLE1_RANGES["recovery_time"]
            for w in POOL_SIZES]
    assert study.params == want
    assert study.replicas == 16384


def test_table1_values_equal_params_defaults():
    from benchmarks.paper_tables import JOB_DAYS
    from repro.core import MINUTES_PER_DAY, Params

    defaults = dataclasses.asdict(Params())
    for name in ("table1_exp", "table1_lognormal_repair"):
        params = harness.load_config(name)["params"]
        for k, v in params.items():
            if k == "job_length":
                assert v == JOB_DAYS * MINUTES_PER_DAY
            elif k == "histogram":
                assert dict(v, channels=tuple(v["channels"])) == defaults[k]
            elif name == "table1_lognormal_repair" and k in (
                    "repair_distribution", "distribution_kwargs"):
                assert (params["repair_distribution"],
                        params["distribution_kwargs"]) == ("lognormal",
                                                           {"sigma": 1.2})
            else:
                assert v == defaults[k], k


def test_run_without_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "whatif_point", "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())
