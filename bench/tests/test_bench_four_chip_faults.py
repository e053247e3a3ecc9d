"""The comparison that decides ``correct`` on a four-chip cell fails what
it must fail.

A Fig. 2a-shaped study sharded over four devices (``engine_shards`` 4,
the ``fig2a_grid_4chip`` cell's traffic at 16 replicas a point) runs
through the harness on four forced host devices (the look for a chip
skipped), once sound and once with one shard's returned rows dropped
or altered after the sharded program returns.  The runs happen in one
child process, which alone sees four devices.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

CHILD = textwrap.dedent("""
    import json, shutil, sys
    from pathlib import Path
    import jax
    import numpy as np
    from bench import harness
    from repro.core import vectorized as vz

    assert jax.device_count() == 4
    tmp = Path(sys.argv[1])
    bench = tmp / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "testdata",
                                                  "__pycache__"))
    traffic = dict(harness.load_traffic("fig2a_grid_4chip"), replicas=16,
                   reference_replicas=16,
                   grid={"recovery_time": [10.0, 30.0],
                         "working_pool_size": [4112]})
    (bench / "workloads" / "small_4chip.json").write_text(
        json.dumps(traffic))
    man = harness.manifest()
    man["workloads"] = [{"name": "small_4chip", "config": "table1_exp",
                         "traffic": "small_4chip", "chips": 4,
                         "why": "test"}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(man))

    real_wait = vz._wait

    def one_shard(fault):
        # the rows shard 1 returned: replica columns R/4 .. R/2 - 1 of
        # every point, in the point-major batch
        def wait(run, args, kw):
            out, counters = real_wait(run, args, kw)
            P, R = args[2], args[3]
            rows = np.arange(P * R)
            hit = (rows % R) // (R // 4) == 1
            out = {k: fault(k, np.asarray(v), hit)
                   if np.ndim(v) and np.shape(v)[0] == P * R else v
                   for k, v in out.items()}
            return out, counters
        return wait

    def dropped(k, v, hit):
        v = v.copy()
        v[hit] = 0
        return v

    def altered(k, v, hit):
        if k != "total_time":
            return v
        v = v.copy()
        v[hit] *= 10.0
        return v

    results = {}
    for name, fault in (("sound", None), ("dropped", dropped),
                        ("altered", altered)):
        vz._wait = real_wait if fault is None else one_shard(fault)
        r = harness.run_cell("small_4chip", 2 ** 31 + 99, 0.2, False, 0.0,
                             root=tmp, bench=bench, require_chip=False,
                             log=lambda m: None)
        results[name] = {"correct": r["correct"], "checks": r["checks"],
                         "chips": r["device"]["count"],
                         "attempted": r["attempted"]}
    print(json.dumps(results))
""")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("four_chip")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jc"))
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT)]
        + [x for x in [env.get("PYTHONPATH")] if x])
    done = subprocess.run([sys.executable, "-c", CHILD, str(tmp)], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=ROOT)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_sound_sharded_run_is_correct(runs):
    assert runs["sound"]["chips"] == 4
    assert runs["sound"]["correct"], runs["sound"]["checks"]


def test_a_shards_rows_dropped_fails(runs):
    r = runs["dropped"]
    assert not r["correct"]
    # in every study of the window, a quarter of each point's replicas
    # came back unfinished
    assert r["checks"]["unfinished"]["value"] == r["attempted"] * 2 * 4


def test_a_shards_rows_altered_fails(runs):
    r = runs["altered"]
    assert not r["correct"]
    assert r["checks"]["unfinished"]["value"] == 0
    # the stats users read of the job's time are far off
    assert r["checks"]["stats_z_max"]["value"] > 6.0
