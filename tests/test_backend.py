"""Engine dispatch layer (core.backend) + batched sweep execution."""

import numpy as np
import pytest

from repro.core import MINUTES_PER_DAY as DAY
from repro.core import (OneWaySweep, Params, resolve_engine,
                        run_replications, run_replications_batch)
from repro.core.vectorized import simulate_ctmc, simulate_ctmc_sweep

BASE = Params(job_size=48, working_pool_size=56, spare_pool_size=8,
              warm_standbys=4, job_length=2 * DAY,
              random_failure_rate=1.0 / DAY, seed=3)


# ---------------------------------------------------------------------------
# engine resolution
# ---------------------------------------------------------------------------

def test_auto_resolves_ctmc_for_default_model():
    assert resolve_engine(BASE, "auto") == "ctmc"


@pytest.mark.parametrize("params", [
    BASE.replace(retirement_threshold=3),
    # weibull/bathtub/lognormal failures, weibull/lognormal/deterministic
    # repairs, and checkpoint rollback run on the CTMC fast path now
    # (tests/test_nonexp.py, tests/test_repair_dist.py,
    # tests/test_checkpoint_opt.py); deterministic failures and
    # user-registered families still fall back
    BASE.replace(failure_distribution="deterministic"),
    BASE.replace(bad_set_regeneration_period=1440.0),
    BASE.replace(standbys_can_fail=True),
])
def test_auto_falls_back_to_event(params):
    assert resolve_engine(params, "auto") == "event"
    rep = run_replications(params, 2, engine="auto")
    assert rep.engine == "event"
    assert len(rep.results) == 2
    assert rep.stats["total_time"].mean > 0


def test_explicit_ctmc_raises_outside_envelope():
    with pytest.raises(ValueError, match="outside the CTMC envelope"):
        run_replications(BASE.replace(retirement_threshold=3), 2,
                         engine="ctmc")


def test_unknown_engine_rejected():
    with pytest.raises(ValueError, match="unknown engine"):
        run_replications(BASE, 2, engine="warp")


def test_ctmc_replications_carry_arrays_not_results():
    rep = run_replications(BASE, 16, engine="ctmc")
    assert rep.engine == "ctmc"
    assert rep.results == []
    assert rep.arrays["total_time"].shape == (16,)
    assert rep.n == 16
    # exact per-run records ride along with the scalar metrics
    assert rep.arrays["run_durations"].shape == (16, BASE.max_run_records)
    assert rep.arrays["n_runs"].shape == (16,)
    # n_retired is exactly zero inside the CTMC envelope; modeled
    # metrics like silent repair failures must be real counts
    assert rep.stats["n_retired"].mean == 0.0
    assert rep.stats["n_failed_repairs"].mean > 0.0
    assert rep.stats["overhead_fraction"].mean > 0.0


def test_batch_routes_mixed_grids_in_order():
    grid = [BASE, BASE.replace(failure_distribution="deterministic"),
            BASE.replace(recovery_time=40.0)]
    reps = run_replications_batch(grid, 4, engine="auto")
    assert [r.engine for r in reps] == ["ctmc", "event", "ctmc"]
    assert all(r.n == 4 for r in reps)


# ---------------------------------------------------------------------------
# batched sweep vs event engine: statistical agreement
# ---------------------------------------------------------------------------

def test_sweep_ctmc_agrees_with_event_engine():
    values = [10.0, 20.0, 40.0]
    ct = OneWaySweep("b", "recovery_time", values, n_replications=512,
                     base_params=BASE, engine="ctmc").run()
    ev = OneWaySweep("b", "recovery_time", values, n_replications=32,
                     base_params=BASE, engine="event").run()
    for pc, pe in zip(ct.points, ev.points):
        assert pc.engine == "ctmc" and pe.engine == "event"
        sc, se_ = pc.stats["total_time"], pe.stats["total_time"]
        pooled = np.sqrt(sc.std ** 2 / pc.n_replications
                         + se_.std ** 2 / pe.n_replications)
        z = (sc.mean - se_.mean) / max(pooled, 1e-9)
        assert abs(z) < 3.5, (pc.values, sc.mean, se_.mean, z)


def test_sweep_points_match_single_point_runs():
    """The batched grid must equal per-point simulate_ctmc statistically
    (same model, independent draws)."""
    pts = [BASE.replace(recovery_time=v) for v in (10.0, 30.0)]
    batched = simulate_ctmc_sweep(pts, n_replicas=256, seed=0)
    for p, out in zip(pts, batched):
        single = simulate_ctmc(p, n_replicas=256, seed=1)
        for m in ("total_time", "n_failures"):
            a, b = out[m], single[m]
            se = np.sqrt(a.std() ** 2 / len(a) + b.std() ** 2 / len(b))
            assert abs(a.mean() - b.mean()) < 3.5 * max(se, 1e-9), m
        assert out["completed"].mean() > 0.99


def test_sweep_monotone_in_recovery_time():
    """Common random numbers across points -> monotone even at tiny n."""
    values = [5.0, 20.0, 40.0]
    res = OneWaySweep("m", "recovery_time", values, n_replications=8,
                      base_params=BASE, engine="ctmc").run()
    ts = res.column("total_time")
    assert ts[0] < ts[1] < ts[2], ts


# ---------------------------------------------------------------------------
# structure padding (deterministic pins; hypothesis sweeps the structure
# space in tests/test_property.py where available)
# ---------------------------------------------------------------------------

STRUCT_GRID = [BASE,
               BASE.replace(job_size=40),
               BASE.replace(spare_pool_size=16, warm_standbys=8),
               BASE.replace(job_length=1 * DAY)]


def test_padded_sweep_bit_identical_to_per_structure():
    pad = simulate_ctmc_sweep(STRUCT_GRID, n_replicas=32, seed=5,
                              max_steps=512, padded=True)
    ref = simulate_ctmc_sweep(STRUCT_GRID, n_replicas=32, seed=5,
                              max_steps=512, padded=False)
    for i, (a, b) in enumerate(zip(pad, ref)):
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k],
                                          err_msg=f"point {i} metric {k}")


def test_mixed_structure_grid_compiles_once():
    """The whole point of structure padding: a structural grid is one
    flat batch behind a single jit cache entry."""
    from repro.core import vectorized

    before = vectorized.compile_cache_size()
    reps = run_replications_batch(STRUCT_GRID, 8, engine="ctmc",
                                  max_steps=448)
    after = vectorized.compile_cache_size()
    # <= 1: another test may already have populated this exact signature
    assert after - before <= 1
    assert [r.engine for r in reps] == ["ctmc"] * len(STRUCT_GRID)
    assert all(r.n == 8 for r in reps)


def test_structural_sweep_agrees_with_event_engine():
    """job_size is a structural knob; the padded CTMC path must stay
    statistically indistinguishable from the event oracle."""
    values = [24, 48]
    ct = OneWaySweep("s", "job_size", values, n_replications=512,
                     base_params=BASE.replace(working_pool_size=64),
                     engine="ctmc").run()
    ev = OneWaySweep("s", "job_size", values, n_replications=32,
                     base_params=BASE.replace(working_pool_size=64),
                     engine="event").run()
    for pc, pe in zip(ct.points, ev.points):
        sc, se_ = pc.stats["total_time"], pe.stats["total_time"]
        pooled = np.sqrt(sc.std ** 2 / pc.n_replications
                         + se_.std ** 2 / pe.n_replications)
        z = (sc.mean - se_.mean) / max(pooled, 1e-9)
        assert abs(z) < 3.5, (pc.values, sc.mean, se_.mean, z)


# ---------------------------------------------------------------------------
# early exit
# ---------------------------------------------------------------------------

def test_early_exit_identical_to_full_scan():
    """Finished replicas are inert, so stopping at the first all-DONE
    chunk boundary must be bit-identical to burning the whole budget."""
    a = simulate_ctmc(BASE, n_replicas=64, seed=11, early_exit=True)
    b = simulate_ctmc(BASE, n_replicas=64, seed=11, early_exit=False)
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_early_exit_identical_for_sweep():
    pts = [BASE.replace(recovery_time=v) for v in (10.0, 30.0)]
    a = simulate_ctmc_sweep(pts, n_replicas=32, seed=7, early_exit=True)
    b = simulate_ctmc_sweep(pts, n_replicas=32, seed=7, early_exit=False)
    for oa, ob in zip(a, b):
        for k in oa:
            np.testing.assert_array_equal(oa[k], ob[k], err_msg=k)


# ---------------------------------------------------------------------------
# empty-sweep CSV (regression: rows[0] IndexError)
# ---------------------------------------------------------------------------

def test_write_csv_empty_sweep(tmp_path):
    res = OneWaySweep("empty", "recovery_time", [], n_replications=2,
                      base_params=BASE).run()
    assert res.points == []
    path = str(tmp_path / "empty.csv")
    res.write_csv(path)
    with open(path) as f:
        header = f.read().strip()
    assert header.startswith("recovery_time,")
    assert "total_time_ci95" in header
