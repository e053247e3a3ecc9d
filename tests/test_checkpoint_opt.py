"""Checkpoint rollback + goodput optimization: cross-engine parity and
analytical cross-checks (PR 9 acceptance suite).

Covers the close-the-loop layer end to end:

  * event vs CTMC parity on a rollback-heavy config — mean total_time /
    lost_work / goodput agree within z < 3.5, checkpoint_overhead within
    rtol (its variance is near zero: the write count is deterministic),
    and the goodput histograms agree within one bin;
  * ``checkpoint_interval=0`` is pinned bit-identical — the rollback
    lanes must compile to dead code, so results cannot depend on the
    (traced) ``checkpoint_cost`` and lost_work/checkpoint_overhead are
    exactly zero;
  * a traced (checkpoint_interval x warm_standbys) grid compiles ONE
    XLA program;
  * :func:`repro.core.optimize.optimize_checkpoint_interval` lands
    within one grid notch of the Young/Daly interval in the
    low-overhead exponential regime, and its golden-section bracket
    history contracts geometrically;
  * hypothesis properties: goodput in [0, 1], monotone non-increasing
    in checkpoint_cost under common random numbers, lost_work == 0 at
    interval 0, and work conservation (sum of run records ~= useful +
    lost) on both engines.

The parity config uses interval=113.0 (non-commensurate with
job_length) deliberately: a job_length that is an exact multiple of the
interval makes the final write tie with completion, and fp drift breaks
the tie differently per engine.
"""

import math

import numpy as np
import pytest

from repro.core import (HistogramSpec, Params, run_replications,
                        run_replications_batch, simulate, young_daly_interval)
from repro.core.analytical import cluster_failure_rate
from repro.core.optimize import (default_interval_bounds,
                                 optimize_checkpoint_interval,
                                 optimize_knobs)
from repro.core.vectorized import simulate_ctmc, supports

DAY = 24 * 60.0

# Rollback-heavy but completing: fleet MTBF ~434 min >> interval, so
# jobs bank work steadily while still paying dozens of rollbacks.
BASE = Params(
    job_size=16,
    working_pool_size=20,
    spare_pool_size=4,
    warm_standbys=2,
    job_length=4 * DAY,
    random_failure_rate=0.2 / DAY,
    seed=3,
    checkpoint_interval=113.0,
    checkpoint_cost=5.0,
)


def _z(a_mean, a_std, a_n, b_mean, b_std, b_n):
    se = math.sqrt(a_std ** 2 / a_n + b_std ** 2 / b_n)
    return (a_mean - b_mean) / max(se, 1e-12)


# ---------------------------------------------------------------------------
# cross-engine parity (acceptance criterion)
# ---------------------------------------------------------------------------

def test_ctmc_accepts_checkpoint_rollback():
    assert supports(BASE)
    assert supports(Params(checkpoint_interval=60.0, checkpoint_cost=2.0))


def test_cross_engine_parity_rollback_heavy():
    """Mean total_time / lost_work / goodput: event vs CTMC within
    z < 3.5 on the rollback-heavy config; checkpoint_overhead within
    rtol (deterministic write count -> near-zero variance makes z
    meaningless)."""
    n_c, n_e = 512, 48
    rc = run_replications(BASE, n_c, engine="ctmc")
    re_ = run_replications(BASE, n_e, engine="event")
    for stat in ("total_time", "lost_work", "goodput"):
        sc, se = rc.stats[stat], re_.stats[stat]
        z = _z(sc.mean, sc.std, n_c, se.mean, se.std, n_e)
        assert abs(z) < 3.5, (stat, z, sc.mean, se.mean)
    oc = rc.stats["checkpoint_overhead"].mean
    oe = re_.stats["checkpoint_overhead"].mean
    assert oc == pytest.approx(oe, rel=0.02), (oc, oe)
    # both engines actually rolled back and wrote checkpoints
    assert rc.stats["lost_work"].mean > 0 and re_.stats["lost_work"].mean > 0
    assert oc > 0
    # goodput is a genuine fraction strictly inside (0, 1) here
    for rep in (rc, re_):
        assert 0.0 < rep.stats["goodput"].mean < 1.0


def test_goodput_histograms_agree_within_one_bin():
    """Pooled goodput histogram (one sample per completed job): p50 from
    the CTMC accumulator matches the event engine's empirical median
    within one bin width on the shared layout."""
    spec = HistogramSpec(low=0.01, high=1.0, n_bins=64,
                         channels=("run_duration", "recovery", "waiting",
                                   "goodput"))
    p = BASE.replace(histogram=spec)
    rc = run_replications(p, 256, engine="ctmc")
    h = rc.histograms["goodput"]
    assert h.total >= 250  # nearly every replica completes
    pool = np.array([r.goodput for r in simulate(p, 32) if not r.timed_out])
    assert len(pool) >= 30
    emp = float(np.percentile(pool, 50))
    assert abs(h.percentile(50) - emp) <= h.bin_width_at(emp)


# ---------------------------------------------------------------------------
# checkpoint_interval = 0: the rollback lanes must be dead code
# ---------------------------------------------------------------------------

def test_interval_zero_is_exactly_rollback_free():
    p = BASE.replace(checkpoint_interval=0.0)
    out = simulate_ctmc(p, n_replicas=32, seed=7)
    assert float(np.abs(out["lost_work"]).max()) == 0.0
    assert float(np.abs(out["checkpoint_overhead"]).max()) == 0.0
    # goodput still populated: useful == banked == all progressed work
    assert float(out["useful_work"].min()) > 0.0


def test_interval_zero_bit_identical_across_traced_cost():
    """With interval=0 the write cost is unreachable: trajectories must
    be bit-for-bit identical for any checkpoint_cost, proving the
    rollback machinery adds zero behavioural footprint when off."""
    p0 = BASE.replace(checkpoint_interval=0.0, checkpoint_cost=0.0)
    p1 = BASE.replace(checkpoint_interval=0.0, checkpoint_cost=50.0)
    o0 = simulate_ctmc(p0, n_replicas=16, seed=11)
    o1 = simulate_ctmc(p1, n_replicas=16, seed=11)
    for k in ("total_time", "useful_work", "n_failures", "completed",
              "lost_work", "checkpoint_overhead"):
        np.testing.assert_array_equal(np.asarray(o0[k]), np.asarray(o1[k]), k)


def test_interval_zero_identical_inside_mixed_grid():
    """An interval=0 row embedded in a grid next to rollback rows equals
    a standalone interval=0 run — the traced axis cannot leak across
    rows."""
    p0 = BASE.replace(checkpoint_interval=0.0, checkpoint_cost=0.0)
    grid = [p0, BASE, BASE.replace(checkpoint_interval=40.0)]
    reps = run_replications_batch(grid, 32, engine="ctmc")
    solo = run_replications(p0, 32, engine="ctmc")
    for stat in ("total_time", "overhead_fraction", "goodput", "lost_work"):
        assert reps[0].stats[stat].mean == solo.stats[stat].mean, stat
    assert reps[0].stats["lost_work"].mean == 0.0
    assert reps[1].stats["lost_work"].mean > 0.0


# ---------------------------------------------------------------------------
# one XLA program across the traced (interval x warm_standbys) grid
# ---------------------------------------------------------------------------

def test_checkpoint_grid_compiles_one_program():
    from repro.core import vectorized

    before = vectorized.compile_cache_size()
    grid = [BASE.replace(checkpoint_interval=iv, checkpoint_cost=c,
                         warm_standbys=w)
            for iv in (0.0, 60.0, 113.0, 240.0)
            for c, w in ((0.0, 0), (5.0, 2))]
    reps = run_replications_batch(grid, 16, engine="ctmc")
    assert len(reps) == 8
    after = vectorized.compile_cache_size()
    assert after - before <= 1, (before, after)


# ---------------------------------------------------------------------------
# analytical cross-check: Young/Daly pins the optimizer
# ---------------------------------------------------------------------------

def test_optimizer_lands_within_one_notch_of_young_daly():
    """Low-overhead exponential regime: the simulated goodput-optimal
    interval must fall inside the one-grid-notch bracket around the
    Young/Daly point (the acceptance criterion)."""
    lam = cluster_failure_rate(BASE)
    yd = young_daly_interval(BASE.checkpoint_cost, 1.0 / lam)
    res = optimize_checkpoint_interval(BASE, n_replicas=256, n_grid=12,
                                       refine_iters=8)
    assert res.young_daly == pytest.approx(yd)
    # locate yd's grid notch and assert the optimum is within one notch
    grid = np.array(res.grid)
    ratio = grid[1] / grid[0]
    notch = ratio ** 1.5  # one grid notch + golden-section slack
    assert yd / notch <= res.interval <= yd * notch, (res.interval, yd)
    # the coarse response is genuinely unimodal-ish: the argmax is
    # interior and beats both bracket endpoints
    best = int(np.argmax(res.grid_objective))
    assert 0 < best < len(grid) - 1
    assert res.objective >= max(res.grid_objective)


def test_golden_section_bracket_contracts():
    res = optimize_checkpoint_interval(BASE, n_replicas=64, n_grid=8,
                                       refine_iters=6)
    assert res.history, "refinement must record its bracket"
    widths = [b - a for a, b in res.history]
    for w0, w1 in zip(widths, widths[1:]):
        assert w1 < w0
        # golden-section contracts by exactly invphi per iteration
        assert w1 == pytest.approx(w0 * (math.sqrt(5) - 1) / 2, rel=1e-6)
    assert res.n_evals == 8 + 2 * len(res.history)
    # CRN makes the whole search deterministic in the seed
    res2 = optimize_checkpoint_interval(BASE, n_replicas=64, n_grid=8,
                                        refine_iters=6)
    assert res2.interval == res.interval
    assert res2.objective == res.objective


def test_optimizer_interval_stays_inside_its_search_bounds():
    """A micro-run of the golden-section search on a fast-failing fleet:
    the interval it returns lies inside its own grid, after one
    evaluation per grid point and two per refinement step."""
    fast = Params(job_size=16, working_pool_size=32, spare_pool_size=4,
                  warm_standbys=2, job_length=0.2 * DAY,
                  random_failure_rate=2.0 / DAY, recovery_time=5.0,
                  auto_repair_time=30.0, manual_repair_time=60.0, seed=0,
                  checkpoint_interval=20.0, checkpoint_cost=2.0)
    res = optimize_checkpoint_interval(fast, n_replicas=16, n_grid=4,
                                       refine_iters=2, engine="ctmc")
    assert min(res.grid) <= res.interval <= max(res.grid)
    assert len(res.grid) == 4 and res.history
    assert res.n_evals == 4 + 2 * len(res.history)


def test_default_interval_bounds_bracket_young_daly():
    lo, hi = default_interval_bounds(BASE)
    lam = cluster_failure_rate(BASE)
    yd = young_daly_interval(BASE.checkpoint_cost, 1.0 / lam)
    assert lo < yd < hi
    assert lo >= BASE.checkpoint_cost
    # failure-free fleet: no interior optimum, job-length-scaled fallback
    lo0, hi0 = default_interval_bounds(
        BASE.replace(random_failure_rate=0.0))
    assert 0 < lo0 < hi0 <= BASE.job_length


def test_optimize_knobs_coordinate_descent():
    axes = {"checkpoint_interval": (40.0, 80.0, 160.0),
            "warm_standbys": (0, 2)}
    res = optimize_knobs(BASE, axes, n_replicas=64, engine="ctmc",
                         max_sweeps=3)
    assert set(res.values) == set(axes)
    assert res.values["checkpoint_interval"] in (40.0, 80.0, 160.0, 113.0)
    assert res.n_evals >= sum(len(v) for v in axes.values())
    assert res.history and res.objective > 0
    # the reported optimum is axis-optimal in its final visit per knob
    last = {}
    for name, cand, vals in res.history:
        last[name] = (cand, vals)
    for name, (cand, vals) in last.items():
        assert res.values[name] == cand[int(np.argmax(vals))]
    with pytest.raises(ValueError):
        optimize_knobs(BASE, {})
    with pytest.raises(ValueError):
        optimize_knobs(BASE, {"not_a_field": (1, 2)})


# ---------------------------------------------------------------------------
# deterministic invariant pins (the hypothesis twins live in
# tests/test_checkpoint_property.py and skip when hypothesis is absent)
# ---------------------------------------------------------------------------

SHORT = BASE.replace(job_length=1 * DAY)


@pytest.mark.parametrize("seed", [0, 7, 101])
def test_goodput_is_a_fraction(seed):
    p = SHORT.replace(seed=seed)
    out = simulate_ctmc(p, n_replicas=8, seed=seed)
    g = np.asarray(out["useful_work"]) / np.maximum(
        np.asarray(out["total_time"]), 1e-9)
    assert (g >= 0.0).all() and (g <= 1.0 + 1e-9).all()
    rep = run_replications(p, 8, engine="ctmc")
    assert 0.0 <= rep.stats["goodput"].mean <= 1.0 + 1e-9


@pytest.mark.parametrize("seed", [0, 42])
def test_goodput_monotone_nonincreasing_in_cost(seed):
    """Under common random numbers a dearer write can only hurt: mean
    goodput is non-increasing in checkpoint_cost (same seed, same
    interval, CRN across the traced-cost grid)."""
    costs = (0.0, 2.0, 8.0, 20.0)
    grid = [SHORT.replace(checkpoint_cost=c, seed=seed) for c in costs]
    reps = run_replications_batch(grid, 32, engine="ctmc")
    g = [r.stats["goodput"].mean for r in reps]
    for a, b in zip(g, g[1:]):
        assert b <= a + 1e-9, g


@pytest.mark.parametrize("seed", [0, 5])
def test_work_conservation_both_engines(seed):
    """Every compute minute is either banked (useful) or rolled back
    (lost): the run records satisfy sum(records) = useful_work +
    lost_work - cur_run.  Run records exclude checkpoint-write wall
    time by construction, so the identity is exact up to ring-buffer
    truncation (avoided here: records fit)."""
    p = SHORT.replace(seed=seed, max_run_records=4096)
    for r in simulate(p, 2):
        if r.timed_out:
            continue
        assert sum(r.run_durations) == pytest.approx(
            r.useful_work + r.lost_work, rel=1e-6)
    out = simulate_ctmc(p, n_replicas=4, seed=seed)
    buf = np.asarray(out["run_durations"], np.float64)
    n_runs = np.asarray(out["n_runs"], np.int64)
    assert (n_runs <= buf.shape[1]).all(), "records must fit the buffer"
    valid = np.arange(buf.shape[1])[None, :] < n_runs[:, None]
    recorded = np.where(valid, buf, 0.0).sum(axis=1)
    expect = (np.asarray(out["useful_work"], np.float64)
              + np.asarray(out["lost_work"], np.float64)
              - np.asarray(out["cur_run"], np.float64))
    np.testing.assert_allclose(recorded, expect, rtol=1e-5, atol=1e-6)
