"""Power-of-two shape bucketing of the padded CTMC sweep.

Two invariants: (1) sweeps whose (points, replicas, step-budget)
signatures fall in the same power-of-two bucket share exactly one
compiled XLA program (the compile-count regression guard); (2) the
inert phase-DONE padding rows never leak — real rows are bit-identical
to the unbucketed path.
"""

import numpy as np
import pytest

from repro.core import MINUTES_PER_DAY as DAY
from repro.core import OneWaySweep, Params, run_replications_batch
from repro.core import vectorized
from repro.core.vectorized import (_next_pow2, simulate_ctmc,
                                   simulate_ctmc_sweep)

BASE = Params(job_size=16, working_pool_size=32, spare_pool_size=4,
              warm_standbys=2, job_length=0.1 * DAY,
              random_failure_rate=2.0 / DAY, recovery_time=5.0,
              auto_repair_time=30.0, manual_repair_time=60.0, seed=0)


def test_next_pow2():
    assert [_next_pow2(n) for n in (0, 1, 2, 3, 4, 5, 33, 64)] \
        == [1, 1, 2, 4, 4, 8, 64, 64]


# ---------------------------------------------------------------------------
# compile-count regression (acceptance criterion)
# ---------------------------------------------------------------------------

#: a Params base no other test uses (distinct ring-buffer and histogram
#: shapes), so the compile-count assertions below measure cache entries
#: that only this module can create
def _unique_base():
    from repro.core import HistogramSpec
    return BASE.replace(max_run_records=7,
                        histogram=HistogramSpec(n_bins=40))


def test_same_bucket_sweeps_compile_exactly_one_program():
    """Different (P, R, step-budget), same power-of-two bucket -> the
    second sweep must not add a jit cache entry."""
    base = _unique_base()

    c0 = vectorized.compile_cache_size()
    grid_a = [base.replace(recovery_time=v) for v in (5.0, 10.0, 15.0)]
    run_replications_batch(grid_a, 12, engine="ctmc", max_steps=192)
    c1 = vectorized.compile_cache_size()

    # P: 3 -> bucket 4 vs 4 -> 4; R: 12 -> bucket 16 vs 9 -> 16; budget
    # 192 vs 256 (explicit budgets are honored exactly, so same-program
    # sharing needs whole chunks: both are multiples of 64, and the
    # chunk *count* is traced)
    grid_b = [base.replace(recovery_time=v) for v in (5.0, 10.0, 15.0, 20.0)]
    run_replications_batch(grid_b, 9, engine="ctmc", max_steps=256)
    c2 = vectorized.compile_cache_size()

    assert c1 - c0 == 1, "first sweep in a fresh bucket compiles once"
    assert c2 - c1 == 0, "same-bucket sweep must reuse the program"

    # and a sweep in a *different* R bucket compiles exactly one more
    run_replications_batch(grid_a, 20, engine="ctmc", max_steps=192)
    assert vectorized.compile_cache_size() - c2 == 1


def test_unbucketed_sweeps_recompile_per_shape():
    """The A/B control: bucketed=False keeps one program per exact
    (P, R) shape."""
    base = _unique_base()
    grid = [base.replace(recovery_time=v) for v in (5.0, 10.0, 15.0)]
    c0 = vectorized.compile_cache_size()
    simulate_ctmc_sweep(grid, n_replicas=11, seed=0, max_steps=192,
                        bucketed=False)
    simulate_ctmc_sweep(grid, n_replicas=13, seed=0, max_steps=192,
                        bucketed=False)
    assert vectorized.compile_cache_size() - c0 == 2


# ---------------------------------------------------------------------------
# padding rows are inert
# ---------------------------------------------------------------------------

def test_bucketed_bit_identical_to_unbucketed_on_real_rows():
    """Deterministic pin with non-power-of-two P and R: padding points,
    padding replicas, and the rounded budget must not change a single
    bit of any real row."""
    grid = [BASE.replace(recovery_time=v) for v in (5.0, 10.0, 15.0)]
    a = simulate_ctmc_sweep(grid, n_replicas=21, seed=4, max_steps=256,
                            bucketed=True)
    b = simulate_ctmc_sweep(grid, n_replicas=21, seed=4, max_steps=256,
                            bucketed=False)
    for i, (x, y) in enumerate(zip(a, b)):
        assert set(x) == set(y)
        for k in x:
            np.testing.assert_array_equal(x[k], y[k],
                                          err_msg=f"point {i} metric {k}")


@pytest.mark.parametrize("max_steps", [256, 100])
def test_bucketed_sweep_matches_single_point_run(max_steps):
    """A one-point bucketed sweep equals simulate_ctmc bit for bit: the
    pow2-width uniform draw guarantees the same stream for real
    replicas regardless of replica padding, and an explicit max_steps —
    chunk multiple or not (100 leaves a 36-step remainder) — is honored
    exactly rather than rounded up."""
    p = BASE.replace(recovery_time=7.0)
    sweep = simulate_ctmc_sweep([p], n_replicas=21, seed=9,
                                max_steps=max_steps, bucketed=True)[0]
    single = simulate_ctmc(p, n_replicas=21, seed=9, max_steps=max_steps)
    assert set(sweep) == set(single)
    for k in sweep:
        np.testing.assert_array_equal(sweep[k], single[k], err_msg=k)


def test_bucketed_early_exit_still_bit_identical():
    grid = [BASE.replace(recovery_time=v) for v in (5.0, 15.0)]
    a = simulate_ctmc_sweep(grid, n_replicas=12, seed=2, early_exit=True)
    b = simulate_ctmc_sweep(grid, n_replicas=12, seed=2, early_exit=False)
    for x, y in zip(a, b):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k], err_msg=k)


def test_bucketed_sweep_through_sweep_classes():
    """End to end through OneWaySweep: bucketing is on by default and
    changes no reported statistic vs bucketed=False."""
    kw = dict(n_replications=10, base_params=BASE, engine="ctmc")
    on = OneWaySweep("b", "recovery_time", [5.0, 10.0, 15.0], **kw).run()
    off = OneWaySweep("b", "recovery_time", [5.0, 10.0, 15.0],
                      bucketed=False, **kw).run()
    for po, pf in zip(on.points, off.points):
        assert po.stats["total_time"].mean == pf.stats["total_time"].mean
        assert po.stats["run_duration_pooled"].mean \
            == pf.stats["run_duration_pooled"].mean
