"""The comparison that decides ``correct`` fails what it must fail.

Each test drives a whole run of a small throwaway cell through the
harness on the CPU (the look for a chip skipped), once sound and once
with the timed path broken underneath, and once with the control — the
plain reference in bfloat16 — in the simulator's place.
"""

import json
import shutil

import jax
import numpy as np
import pytest

from bench import control, harness

SEED = 2 ** 31 + 12345


@pytest.fixture
def small_cell(tmp_path, monkeypatch):
    """Root and bench dirs holding one cell of the Table I cluster at
    16 replicas on two points."""
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "testdata",
                                                  "__pycache__"))
    traffic = {"entry": "run_replications_batch", "replicas": 16,
               "grid": {"working_pool_size": [4112, 4192]},
               "set": {}, "reference_replicas": 16,
               "limits": {"z_max": 6.0, "stats_z_max": 6.0,
                          "unfinished": 0}}
    (bench / "workloads" / "small_grid.json").write_text(
        json.dumps(traffic))
    man = harness.manifest()
    man["workloads"] = [{"name": "small_grid", "config": "table1_exp",
                         "traffic": "small_grid", "chips": 1,
                         "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    # keep the persistent cache of these runs out of the checkout, and
    # leave JAX's cache settings as they were
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.clear_caches()
    yield tmp_path, bench
    jax.clear_caches()
    for k, v in saved.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def _run(cell, **kw):
    root, bench = cell
    return harness.run_cell("small_grid", SEED, 0.2, False, 0.0, root=root,
                            bench=bench, require_chip=False,
                            log=lambda m: None, **kw)


def test_sound_run_is_correct(small_cell):
    r = _run(small_cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-2:] == ["checks", "_check_lines"]
    assert set(r["metrics"]) == {"setup_s", "trajectories_per_s"}


def _patch_extract(monkeypatch, fn):
    from repro.core import vectorized as vz

    real = vz._extract

    def broken(state, sl=slice(None), channels=()):
        return {k: (fn(v) if np.ndim(v) and k != "hist_edges" else v)
                for k, v in real(state, sl, channels).items()}

    monkeypatch.setattr(vz, "_extract", broken)


def test_state_left_unchanged_fails(small_cell, monkeypatch):
    from repro.core import vectorized as vz

    monkeypatch.setattr(vz, "_step_u", lambda s, *a, **k: s)
    r = _run(small_cell)
    assert not r["correct"]
    assert r["checks"]["unfinished"]["value"] > 0


def test_half_the_batch_left_out_fails(small_cell, monkeypatch):
    _patch_extract(monkeypatch, lambda v: v[: len(v) // 2])
    r = _run(small_cell)
    assert not r["correct"]
    assert r["checks"]["unfinished"]["value"] > 0


def test_answer_altered_where_produced_fails(small_cell, monkeypatch):
    from repro.kernels import ops

    real = ops.event_race

    def late(*a, **k):
        dt, ev = real(*a, **k)
        return dt * 1.25, ev

    monkeypatch.setattr(ops, "event_race", late)
    r = _run(small_cell)
    assert not r["correct"]
    assert r["checks"]["z_max"]["value"] > 6.0


def _scaled_stats(stats):
    from repro.core.metrics import Stat

    return {k: Stat(st.mean * 1.25, st.median * 1.25, st.std,
                    st.minimum, st.maximum,
                    {p: v * 1.25 for p, v in st.percentiles.items()})
            for k, st in stats.items()}


def _shifted_histograms(hists):
    from repro.core.histograms import Histogram

    return {ch: Histogram(h.edges, np.roll(h.counts, 1))
            for ch, h in hists.items()}


@pytest.mark.parametrize("where", ["aggregate_arrays",
                                   "histograms_from_arrays"])
def test_aggregation_altered_fails(small_cell, monkeypatch, where):
    # the host aggregation returns wrong numbers from sound arrays: every
    # stat a quarter high, or the pooled histograms one slot up
    from repro.core import backend

    real = getattr(backend, where)
    alter = (_scaled_stats if where == "aggregate_arrays"
             else _shifted_histograms)
    monkeypatch.setattr(backend, where, lambda *a, **k: alter(real(*a, **k)))
    r = _run(small_cell)
    assert not r["correct"]
    assert r["checks"]["z_max"]["value"] <= 6.0
    assert r["checks"]["stats_z_max"]["value"] > 6.0


def test_bfloat16_control_fails(small_cell):
    r = _run(small_cell, make_study=control.control_study(4))
    assert not r["correct"]
    assert r["checks"]["z_max"]["value"] > 6.0
