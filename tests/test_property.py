"""Property-based tests (hypothesis) for system invariants."""

import math

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep; skip module cleanly
from hypothesis import given, settings, strategies as st

from repro.core import (MINUTES_PER_DAY, ClusterSimulation, Params,
                        expected_failures, simulate_one)
from repro.core.histograms import Histogram, HistogramSpec
from repro.core.server import ServerState

DAY = MINUTES_PER_DAY

param_strategy = st.fixed_dictionaries({
    "job_size": st.integers(4, 48),
    "extra_working": st.integers(0, 16),
    "spare_pool_size": st.integers(0, 8),
    "warm_standbys": st.integers(0, 6),
    "random_failure_rate": st.floats(0.0, 4.0 / DAY),
    "systematic_mult": st.integers(0, 10),
    "systematic_failure_fraction": st.floats(0.0, 0.5),
    "recovery_time": st.floats(0.0, 60.0),
    "host_selection_time": st.floats(0.0, 15.0),
    "waiting_time": st.floats(0.0, 60.0),
    "diagnosis_probability": st.floats(0.0, 1.0),
    "diagnosis_uncertainty": st.floats(0.0, 1.0),
    "automated_repair_probability": st.floats(0.0, 1.0),
    "auto_repair_failure_probability": st.floats(0.0, 1.0),
    "manual_repair_failure_probability": st.floats(0.0, 1.0),
    "auto_repair_time": st.floats(1.0, 4 * 1440.0),
    "manual_repair_time": st.floats(1.0, 8 * 1440.0),
    "seed": st.integers(0, 2 ** 31 - 1),
})


def build(draw: dict) -> Params:
    d = dict(draw)
    job = d.pop("job_size")
    extra = d.pop("extra_working")
    mult = d.pop("systematic_mult")
    rate = d["random_failure_rate"]
    return Params(job_size=job,
                  working_pool_size=job + d["warm_standbys"] + extra,
                  job_length=1 * DAY,
                  systematic_failure_rate=mult * rate,
                  **d)


@settings(max_examples=25, deadline=None)
@given(param_strategy)
def test_invariants_hold_for_random_configs(draw):
    p = build(draw)
    sim = ClusterSimulation(p)
    r = sim.run()

    # total time covers the useful work plus accounted overheads
    assert r.total_time >= p.job_length - 1e-6
    assert r.useful_work == pytest.approx(p.job_length, rel=1e-9) or r.timed_out
    assert r.total_time + 1e-6 >= (p.host_selection_time + r.useful_work
                                   + r.recovery_overhead + r.stall_time
                                   + r.lost_work)

    # failure taxonomy adds up
    assert r.n_failures == r.n_random_failures + r.n_systematic_failures
    assert r.n_undiagnosed <= r.n_failures
    assert r.n_misdiagnosed <= r.n_failures - r.n_undiagnosed
    assert r.n_manual_repairs <= r.n_auto_repairs

    # replacement events can't exceed diagnosed failures
    diagnosed = r.n_failures - r.n_undiagnosed
    assert (r.n_standby_swaps + r.n_host_selections) <= diagnosed + 1

    # non-negativity
    for field in ("stall_time", "recovery_overhead", "lost_work"):
        assert getattr(r, field) >= -1e-9

    # server conservation across all states
    counts = sim.pools.conservation_counts()
    assert sum(counts.values()) == p.working_pool_size + p.spare_pool_size
    assert counts.get(ServerState.RUNNING.value, 0) == 0  # released at end


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000), st.floats(0.1 / DAY, 4.0 / DAY))
def test_paired_seeds_monotone_in_rate(seed, rate):
    """Same seed, higher failure rate => at least as many failures."""
    base = dict(job_size=16, working_pool_size=24, spare_pool_size=4,
                warm_standbys=2, job_length=0.5 * DAY, seed=seed)
    lo = simulate_one(Params(random_failure_rate=rate, **base))
    hi = simulate_one(Params(random_failure_rate=rate * 3, **base))
    # statistical monotonicity at matched seeds isn't guaranteed per-path
    # (different sample streams), so compare against analytic expectation
    assert hi.n_failures + 3 * math.sqrt(hi.n_failures + 1) >= lo.n_failures


@settings(max_examples=10, deadline=None)
@given(st.floats(0.0, 1.0), st.integers(0, 100))
def test_diagnosis_probability_bounds_repairs(dp, seed):
    p = Params(job_size=16, working_pool_size=22, spare_pool_size=4,
               warm_standbys=2, job_length=1 * DAY,
               random_failure_rate=2.0 / DAY, diagnosis_probability=dp,
               auto_repair_time=5.0, manual_repair_time=10.0, seed=seed)
    r = simulate_one(p)
    # every auto repair stems from a diagnosed failure
    assert r.n_auto_repairs <= r.n_failures - r.n_undiagnosed
    if dp == 0.0:
        assert r.n_undiagnosed == r.n_failures
        assert r.n_auto_repairs == 0


# ---------------------------------------------------------------------------
# structure-padded CTMC sweeps: padded == unpadded
# ---------------------------------------------------------------------------

def _ctmc_base(job: int, spare: int, warm: int) -> Params:
    return Params(job_size=job, working_pool_size=job + warm + 4,
                  spare_pool_size=spare, warm_standbys=warm,
                  job_length=0.2 * DAY, random_failure_rate=2.0 / DAY,
                  recovery_time=5.0, auto_repair_time=30.0,
                  manual_repair_time=60.0)


@settings(max_examples=4, deadline=None)
@given(st.integers(4, 8), st.integers(0, 2), st.integers(0, 2),
       st.integers(1, 4), st.integers(0, 1000))
def test_padded_sweep_matches_unpadded_mixed_grid(job, spare, warm, dsize,
                                                  seed):
    """A mixed-structure grid through the single-compilation padded path
    must reproduce each point run alone (same seed -> same
    per-replica-column uniforms on both paths)."""
    from repro.core.vectorized import simulate_ctmc_sweep

    base = _ctmc_base(job, spare, warm)
    grid = [base,
            base.replace(job_size=job + dsize,
                         working_pool_size=job + dsize + warm + 4),
            base.replace(spare_pool_size=spare + 2)]
    pad = simulate_ctmc_sweep(grid, n_replicas=16, seed=seed, max_steps=256)
    ref = [simulate_ctmc_sweep([p], n_replicas=16, seed=seed,
                               max_steps=256)[0] for p in grid]
    for i, (a, b) in enumerate(zip(pad, ref)):
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_allclose(
                a[k], b[k], rtol=1e-6, atol=1e-6,
                err_msg=f"point {i} metric {k}")


@settings(max_examples=4, deadline=None)
@given(st.integers(4, 8), st.integers(0, 2), st.integers(0, 1000))
def test_padded_sweep_bit_identical_same_structure(job, warm, seed):
    """Non-structural grids (rates/times only differ) must be bit-identical
    between the batched grid and each point run alone — same program
    semantics, same random stream."""
    from repro.core.vectorized import simulate_ctmc_sweep

    base = _ctmc_base(job, 2, warm)
    grid = [base.replace(recovery_time=v) for v in (5.0, 15.0)]
    pad = simulate_ctmc_sweep(grid, n_replicas=16, seed=seed, max_steps=256)
    ref = [simulate_ctmc_sweep([p], n_replicas=16, seed=seed,
                               max_steps=256)[0] for p in grid]
    for i, (a, b) in enumerate(zip(pad, ref)):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k],
                                          err_msg=f"point {i} metric {k}")


# ---------------------------------------------------------------------------
# streaming histogram accumulator (pure-numpy reference)
# ---------------------------------------------------------------------------

_spec = st.builds(
    lambda low, span, bins: HistogramSpec(low=low, high=low * span,
                                          n_bins=bins),
    st.floats(1e-3, 10.0), st.floats(10.0, 1e6), st.integers(1, 64))

_values = st.lists(st.floats(0.0, 1e8, allow_nan=False), max_size=200)


@settings(max_examples=50, deadline=None)
@given(_spec, _values, st.integers(1, 5))
def test_histogram_merge_associative_commutative(spec, values, n_chunks):
    """Accumulation order across replica chunks never matters: any
    chunking + merge order equals one-shot accumulation."""
    whole = Histogram.from_values(spec, values)
    chunks = [values[i::n_chunks] for i in range(n_chunks)]
    parts = [Histogram.from_values(spec, c) for c in chunks]
    fold_fwd = parts[0]
    for p in parts[1:]:
        fold_fwd = fold_fwd.merge(p)
    fold_rev = parts[-1]
    for p in reversed(parts[:-1]):
        fold_rev = p.merge(fold_rev)        # flipped operand order too
    np.testing.assert_array_equal(whole.counts, fold_fwd.counts)
    np.testing.assert_array_equal(whole.counts, fold_rev.counts)
    assert whole.total == len(values)


@settings(max_examples=50, deadline=None)
@given(_spec, _values)
def test_histogram_cdf_monotone_and_percentiles_ordered(spec, values):
    h = Histogram.from_values(spec, values)
    cdf = h.cdf()
    assert (np.diff(cdf) >= -1e-12).all()
    if values:
        assert cdf[-1] == pytest.approx(1.0)
        qs = [h.percentile(q) for q in (10, 50, 90, 99, 99.9)]
        assert all(a <= b + 1e-12 for a, b in zip(qs, qs[1:]))
        assert h.minimum() <= h.maximum()


@settings(max_examples=50, deadline=None)
@given(_spec, st.integers(0, 1000))
def test_histogram_bin_edges_left_closed_right_open(spec, i):
    """A value exactly on edge k lands deterministically in the bin that
    edge *opens* (counts slot k+1), never the one it closes."""
    edges = spec.edges()
    k = i % len(edges)
    h = Histogram.from_values(spec, [edges[k]])
    assert h.counts[k + 1] == 1.0
    assert h.counts.sum() == 1.0
    # and a value epsilon below stays in the closing bin
    below = np.nextafter(edges[k], 0.0)
    h2 = Histogram.from_values(spec, [below])
    assert h2.counts[k] == 1.0


# ---------------------------------------------------------------------------
# shape-bucketed CTMC sweeps: real rows identical to unbucketed
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(st.integers(1, 5), st.integers(3, 20), st.integers(0, 1000))
def test_bucketed_sweep_value_identical_on_real_rows(n_points, n_rep, seed):
    """Power-of-two padding points/replicas and the traced chunk count
    must leave every real row bit-identical for any (P, R, seed)."""
    from repro.core.vectorized import simulate_ctmc_sweep

    base = _ctmc_base(6, 2, 1)
    grid = [base.replace(recovery_time=4.0 + 2.0 * i)
            for i in range(n_points)]
    a = simulate_ctmc_sweep(grid, n_replicas=n_rep, seed=seed,
                            max_steps=256, bucketed=True)
    b = simulate_ctmc_sweep(grid, n_replicas=n_rep, seed=seed,
                            max_steps=256, bucketed=False)
    for i, (x, y) in enumerate(zip(a, b)):
        assert set(x) == set(y)
        for k in x:
            np.testing.assert_array_equal(x[k], y[k],
                                          err_msg=f"point {i} metric {k}")


# ---------------------------------------------------------------------------
# multi-job shared-pool properties (dispatcher fairness, determinism,
# job-permutation invariance) — both engines
# ---------------------------------------------------------------------------

def _mj_contended() -> Params:
    """Tight shared pool + slow finite shop: stalls are near-certain."""
    return Params(working_pool_size=26, spare_pool_size=2, job_size=8,
                  job_length=800.0, random_failure_rate=0.01,
                  systematic_failure_rate=0.02, auto_repair_time=120.0,
                  manual_repair_time=300.0, repair_servers=2,
                  histogram=None)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000))
def test_multijob_dispatcher_fifo_fairness(seed):
    """A repaired server handed to a stalled job always goes to the one
    stalled *earliest*: a job that stalled first is never passed over in
    favor of one that stalled later (FIFO starvation-freedom)."""
    from repro.core import JobSpec
    from repro.core.multijob import MultiJobSimulation

    jobs = [JobSpec(12, 800.0, warm_standbys=0),
            JobSpec(12, 1000.0, warm_standbys=0)]
    sim = MultiJobSimulation(_mj_contended(), jobs, seed=seed)
    disp = sim.dispatcher
    orig = disp.on_server_return
    handoffs = []

    def checked(server):
        stalled = [s for s in disp.schedulers
                   if s._stall_event is not None
                   and not s._stall_event.triggered]
        before = disp.stall_handoffs
        orig(server)
        if disp.stall_handoffs == before + 1:
            receiver = next(s for s in stalled
                            if s._stall_event.triggered)
            handoffs.append((receiver._stall_since,
                             min(s._stall_since for s in stalled)))

    # the shop captured the dispatcher's bound method at construction
    sim.repair_shop.on_return = checked
    sim.run()
    assert handoffs, "config failed to produce any stall hand-off"
    for got, earliest in handoffs:
        assert got == earliest


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 1000))
def test_multijob_seed_deterministic_both_engines(seed):
    """Same seed => identical multi-job results, on each engine."""
    from repro.core import (JobSpec, simulate_multijob,
                            simulate_multijob_ctmc_sweep)

    cluster = Params(working_pool_size=30, spare_pool_size=3, job_size=8,
                     job_length=300.0, random_failure_rate=0.004,
                     systematic_failure_rate=0.01, auto_repair_time=60.0,
                     manual_repair_time=150.0, repair_servers=2,
                     histogram=None)
    jobs = (JobSpec(12, 300.0, warm_standbys=1),
            JobSpec(8, 400.0, warm_standbys=1))

    a, b = (simulate_multijob_ctmc_sweep([(cluster, jobs)], n_replicas=8,
                                         seed=seed)[0] for _ in range(2))
    np.testing.assert_array_equal(a["makespan"], b["makespan"])
    for j in range(len(jobs)):
        for k in ("total_time", "n_failures", "stall_time"):
            np.testing.assert_array_equal(a["per_job"][j][k],
                                          b["per_job"][j][k])

    r0, r1 = (simulate_multijob(cluster, list(jobs), n_replications=2,
                                base_seed=seed) for _ in range(2))
    for x, y in zip(r0, r1):
        assert x.makespan == y.makespan
        assert x.stall_events == y.stall_events
        for px, py in zip(x.per_job, y.per_job):
            assert px.total_time == py.total_time
            assert px.n_failures == py.n_failures


@settings(max_examples=6, deadline=None)
@given(st.permutations([0, 1, 2]), st.integers(0, 500))
def test_multijob_fleet_metrics_permutation_invariant(perm, seed):
    """Relabeling jobs must not change fleet-pooled outcomes.  With no
    failures the trajectories are deterministic, so the invariance is
    exact on both engines (per-job marginals follow the permutation)."""
    from repro.core import (JobSpec, simulate_multijob,
                            simulate_multijob_ctmc_sweep)

    cluster = Params(working_pool_size=30, spare_pool_size=2, job_size=4,
                     job_length=50.0, random_failure_rate=0.0,
                     systematic_failure_rate=0.0, histogram=None)
    jobs = [JobSpec(8, 50.0, warm_standbys=1),
            JobSpec(6, 80.0, warm_standbys=1),
            JobSpec(4, 30.0, warm_standbys=0)]
    permuted = [jobs[i] for i in perm]

    r0 = simulate_multijob(cluster, jobs, base_seed=seed)[0]
    r1 = simulate_multijob(cluster, permuted, base_seed=seed)[0]
    assert r0.makespan == r1.makespan
    assert (sorted(r.total_time for r in r0.per_job)
            == sorted(r.total_time for r in r1.per_job))

    p0 = simulate_multijob_ctmc_sweep([(cluster, tuple(jobs))],
                                      n_replicas=4, seed=seed)[0]
    p1 = simulate_multijob_ctmc_sweep([(cluster, tuple(permuted))],
                                      n_replicas=4, seed=seed)[0]
    np.testing.assert_array_equal(p0["makespan"], p1["makespan"])
    for j, pj in enumerate(perm):
        np.testing.assert_array_equal(p0["per_job"][pj]["total_time"],
                                      p1["per_job"][j]["total_time"])


def test_multijob_permutation_invariant_in_law_with_failures():
    """With failures the fleet-pooled distribution is exchangeable in
    the job labels: permuting the job list moves the per-job marginals
    with it and leaves fleet metrics statistically unchanged."""
    from repro.core import JobSpec, simulate_multijob_ctmc_sweep

    cluster = Params(working_pool_size=40, spare_pool_size=4, job_size=8,
                     job_length=500.0, random_failure_rate=0.003,
                     systematic_failure_rate=0.008, auto_repair_time=90.0,
                     manual_repair_time=240.0, repair_servers=2,
                     histogram=None)
    jobs = (JobSpec(16, 500.0, warm_standbys=1),
            JobSpec(8, 700.0, warm_standbys=1))
    p0 = simulate_multijob_ctmc_sweep([(cluster, jobs)],
                                      n_replicas=512, seed=3)[0]
    p1 = simulate_multijob_ctmc_sweep([(cluster, jobs[::-1])],
                                      n_replicas=512, seed=4)[0]
    for metric in ("makespan", "stall_handoffs", "n_auto_repairs",
                   "n_shop_queued"):
        a = np.asarray(p0[metric], float)
        b = np.asarray(p1[metric], float)
        se = math.sqrt(a.var(ddof=1) / len(a) + b.var(ddof=1) / len(b))
        z = (a.mean() - b.mean()) / max(se, 1e-12)
        assert abs(z) < 4.0, f"{metric}: z={z:+.2f}"


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 60))
def test_expected_failures_scaling(seed):
    """Doubling job length ~doubles failures (renewal property)."""
    base = dict(job_size=64, working_pool_size=80, spare_pool_size=8,
                warm_standbys=8, random_failure_rate=1.0 / DAY, seed=seed)
    short = simulate_one(Params(job_length=1 * DAY, **base))
    long_ = simulate_one(Params(job_length=4 * DAY, **base))
    if short.n_failures >= 20:
        ratio = long_.n_failures / max(short.n_failures, 1)
        assert 2.0 < ratio < 8.0


# ---------------------------------------------------------------------------
# checkpoint rollback + goodput (deterministic twins live in
# tests/test_checkpoint_opt.py)
# ---------------------------------------------------------------------------

_CKPT = Params(job_size=16, working_pool_size=20, spare_pool_size=4,
               warm_standbys=2, job_length=1 * DAY,
               random_failure_rate=0.2 / DAY,
               checkpoint_interval=113.0, checkpoint_cost=5.0)


@settings(max_examples=15, deadline=None)
@given(iv=st.sampled_from([0.0, 30.0, 113.0, 300.0]),
       cost=st.sampled_from([0.0, 2.0, 10.0]),
       seed=st.integers(0, 2 ** 16))
def test_goodput_is_a_fraction(iv, cost, seed):
    """goodput = useful/wall in [0, 1] for any rollback configuration."""
    from repro.core import run_replications
    from repro.core.vectorized import simulate_ctmc

    p = _CKPT.replace(checkpoint_interval=iv, checkpoint_cost=cost,
                      seed=seed)
    out = simulate_ctmc(p, n_replicas=8, seed=seed)
    g = np.asarray(out["useful_work"]) / np.maximum(
        np.asarray(out["total_time"]), 1e-9)
    assert (g >= 0.0).all() and (g <= 1.0 + 1e-9).all()
    rep = run_replications(p, 8, engine="ctmc")
    assert 0.0 <= rep.stats["goodput"].mean <= 1.0 + 1e-9


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 16))
def test_goodput_monotone_nonincreasing_in_cost(seed):
    """Under common random numbers a dearer write can only hurt: mean
    goodput is non-increasing in checkpoint_cost (same seed, same
    interval, CRN across the traced-cost grid)."""
    from repro.core import run_replications_batch

    grid = [_CKPT.replace(checkpoint_cost=c, seed=seed)
            for c in (0.0, 2.0, 8.0, 20.0)]
    reps = run_replications_batch(grid, 32, engine="ctmc")
    g = [r.stats["goodput"].mean for r in reps]
    for a, b in zip(g, g[1:]):
        assert b <= a + 1e-9, g


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), cost=st.sampled_from([0.0, 5.0, 25.0]))
def test_lost_work_zero_at_interval_zero(seed, cost):
    """With the interval off the rollback lanes must be exactly dead."""
    from repro.core.vectorized import simulate_ctmc

    p = _CKPT.replace(checkpoint_interval=0.0, checkpoint_cost=cost,
                      seed=seed)
    out = simulate_ctmc(p, n_replicas=8, seed=seed)
    assert float(np.abs(out["lost_work"]).max()) == 0.0
    assert float(np.abs(out["checkpoint_overhead"]).max()) == 0.0


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 10))
def test_checkpoint_work_conservation_both_engines(seed):
    """Every compute minute is either banked (useful) or rolled back
    (lost): run records satisfy sum(records) = useful + lost - cur_run
    on the CTMC engine and sum(records) = useful + lost on completed
    event-engine runs."""
    from repro.core import simulate
    from repro.core.vectorized import simulate_ctmc

    p = _CKPT.replace(seed=seed, max_run_records=4096)
    for r in simulate(p, 2):
        if r.timed_out:
            continue
        assert sum(r.run_durations) == pytest.approx(
            r.useful_work + r.lost_work, rel=1e-6)
    out = simulate_ctmc(p, n_replicas=4, seed=seed)
    buf = np.asarray(out["run_durations"], np.float64)
    n_runs = np.asarray(out["n_runs"], np.int64)
    if (n_runs <= buf.shape[1]).all():
        valid = np.arange(buf.shape[1])[None, :] < n_runs[:, None]
        recorded = np.where(valid, buf, 0.0).sum(axis=1)
        expect = (np.asarray(out["useful_work"], np.float64)
                  + np.asarray(out["lost_work"], np.float64)
                  - np.asarray(out["cur_run"], np.float64))
        np.testing.assert_allclose(recorded, expect, rtol=1e-5, atol=1e-6)
