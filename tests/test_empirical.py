"""Trace-driven empirical hazards: fitting layer + CTMC fast path.

Pins the PR's acceptance criteria end to end:

  * the fitting layer turns event logs / MTTF tables into valid
    piecewise-constant segments (Nelson–Aalen and binned estimators);
  * ``hazard_kind``/``repair_kind`` dispatch the ``empirical`` family,
    collapse a single-segment builtin to the exponential program, and
    refuse degenerate segment sets (event-engine fallback);
  * cross-engine parity on pinned seeds: z<3.5 means, histogram
    percentiles within one bin, and a hazard *fitted from a timestamped
    failure log* runs on the CTMC engine in agreement with the oracle;
  * an N-point grid over different fitted edges/rates compiles as ONE
    XLA program (segment count is the only static key);
  * a single-segment empirical hazard is bit-identical to the
    exponential program, on both the failure and repair sides;
  * satellites: every degenerate hazard/repair parameterization falls
    back to the event engine and still completes; re-registered builtin
    names route off the fast path; scipy absence warns once; the
    ``engine="ctmc"`` refusals name the *actual* exclusion reasons.
"""

import json
import sys
import warnings

import numpy as np
import pytest

from repro.core import MINUTES_PER_DAY as DAY
from repro.core import (Params, register_distribution, resolve_engine,
                        resolve_engine_multijob, simulate)
from repro.core.distributions import _REGISTRY, Distribution, Weibull
from repro.core.empirical import (Empirical, PiecewiseFit,
                                  fit_piecewise_hazard, from_log,
                                  from_mttf_table, segments_mean,
                                  validate_segments)
from repro.core.hazards import (_scipy_available, hazard_kind,
                                hazard_segment_count, repair_kind,
                                repair_segment_count)
from repro.core.metrics import histograms_from_arrays
from repro.core.multijob import JobSpec
from repro.core.vectorized import (default_max_steps, simulate_ctmc,
                                   simulate_ctmc_sweep, supports,
                                   unsupported_reasons)

N_EVENT = 40
N_CTMC = 768

BASE = dict(job_size=24, working_pool_size=32, spare_pool_size=4,
            warm_standbys=2, job_length=2 * DAY,
            random_failure_rate=2.0 / DAY,
            systematic_failure_rate=4.0 / DAY, recovery_time=5.0,
            auto_repair_time=30.0, manual_repair_time=120.0, seed=5)

#: shape whose hazard genuinely varies over the ages the job visits:
#: edges land at ~0.4x and ~1.9x the configured mean after rescaling
EMP_SHAPE = {"edges": [0.4, 2.0], "rates": [0.3, 1.5, 0.7]}
EMPIRICAL = Params(failure_distribution="empirical",
                   distribution_kwargs=EMP_SHAPE, **BASE)
EMP_REPAIR = Params(repair_distribution="empirical",
                    distribution_kwargs={"edges": [0.5],
                                         "rates": [0.1, 2.0]}, **BASE)


def compare(p, metrics, n_event=N_EVENT, n_ctmc=N_CTMC, z_tol=3.5):
    out = simulate_ctmc(p, n_replicas=n_ctmc, seed=0)
    assert out["completed"].mean() > 0.99, "CTMC replicas did not finish"
    res = simulate(p, n_event)
    for m in metrics:
        ev = np.array([getattr(r, m) for r in res], float)
        ct = out[m]
        se = np.sqrt(ct.std() ** 2 / len(ct) + ev.std(ddof=1) ** 2 / len(ev))
        z = (ev.mean() - ct.mean()) / max(se, 1e-9)
        assert abs(z) < z_tol, (m, ev.mean(), ct.mean(), z)
    return out, res


# ---------------------------------------------------------------------------
# fitting / ingestion layer
# ---------------------------------------------------------------------------

def test_fit_flat_hazard_recovers_exponential_rate():
    rng = np.random.default_rng(0)
    d = rng.exponential(100.0, size=4000)
    for method in ("nelson-aalen", "binned"):
        fit = fit_piecewise_hazard(d, n_bins=5, method=method)
        assert validate_segments(fit.edges, fit.rates)
        assert fit.n_events == 4000 and fit.method == method
        # a flat hazard at ~1/100 in every segment, mean ~100
        assert np.allclose(fit.rates, 0.01, rtol=0.25), fit.rates
        assert 80.0 < fit.mean < 125.0, fit.mean


def test_fit_two_regime_hazard_sees_both_levels():
    rng = np.random.default_rng(1)
    # infant regime: rate 1/20 until ~40, then 1/400
    d = np.where(rng.random(6000) < 0.6, rng.exponential(20.0, 6000),
                 40.0 + rng.exponential(400.0, 6000))
    fit = fit_piecewise_hazard(d, n_bins=6)
    assert fit.rates[0] > 4 * fit.rates[-1], fit.rates


def test_fit_round_trips_through_json_and_params(tmp_path):
    fit = fit_piecewise_hazard(
        np.random.default_rng(2).exponential(50.0, 500), n_bins=4)
    blob = json.dumps(fit.to_json())
    rt = PiecewiseFit.from_json(json.loads(blob))
    assert rt.edges == fit.edges and rt.rates == fit.rates
    p = Params(**BASE, failure_distribution="empirical",
               distribution_kwargs=fit.distribution_kwargs)
    p.validate()
    assert hazard_kind(p) in ("empirical", "exponential")


def test_from_log_csv_and_jsonl(tmp_path):
    csvp = tmp_path / "events.csv"
    csvp.write_text("time,server,event\n10,a,failure\n30,a,failure\n"
                    "5,b,failure\n45,b,failure\n12,b,repair\n")
    d = from_log(csvp, event="failure")
    assert sorted(d) == [20.0, 40.0]          # per-entity interarrivals

    jp = tmp_path / "events.jsonl"
    jp.write_text('{"duration": 12.5}\n{"duration": 30.0}\n')
    assert sorted(from_log(jp)) == [12.5, 30.0]

    with pytest.raises(ValueError):
        empty = tmp_path / "empty.csv"
        empty.write_text("time,server\n")
        from_log(empty)


def test_from_mttf_table_and_empirical_distribution_sampling():
    edges, rates = from_mttf_table([0.0, 100.0, 500.0],
                                   [50.0, 200.0, 100.0])
    assert list(edges) == [100.0, 500.0]
    assert np.allclose(rates, [1 / 50, 1 / 200, 1 / 100])
    dist = Empirical(mean_value=300.0, edges=tuple(edges),
                     rates=tuple(rates))
    rng = np.random.default_rng(3)
    xs = np.array([dist.sample(rng) for _ in range(4000)])
    assert abs(xs.mean() - 300.0) < 4 * xs.std() / np.sqrt(len(xs))
    seg = dist.hazard_segments()
    assert seg is not None
    assert abs(segments_mean(*seg) - 300.0) / 300.0 < 1e-6


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def test_empirical_dispatch_and_segment_counts():
    assert hazard_kind(EMPIRICAL) == "empirical"
    assert hazard_segment_count(EMPIRICAL) == 3
    assert repair_kind(EMP_REPAIR) == "empirical"
    assert repair_segment_count(EMP_REPAIR) == 2
    assert supports(EMPIRICAL) and supports(EMP_REPAIR)
    assert resolve_engine(EMPIRICAL, "auto") == "ctmc"
    assert resolve_engine(EMP_REPAIR, "auto") == "ctmc"


def test_single_segment_collapses_to_exponential_kind():
    one = Params(**BASE, failure_distribution="empirical",
                 distribution_kwargs={"rates": [2.0]})
    assert hazard_kind(one) == "exponential"
    rone = Params(**BASE, repair_distribution="empirical",
                  distribution_kwargs={"rates": [0.7]})
    assert repair_kind(rone) == "exponential"


def test_degenerate_segments_fall_off_the_fast_path():
    dup = Params(**BASE, failure_distribution="empirical",
                 distribution_kwargs={"edges": [60.0, 60.0],
                                      "rates": [1.0, 2.0, 3.0]})
    assert hazard_kind(dup) is None and not supports(dup)
    neg = Params(**BASE, failure_distribution="empirical",
                 distribution_kwargs={"edges": [60.0],
                                      "rates": [1.0, -2.0]})
    assert hazard_kind(neg) is None
    # defective hazard (terminal rate 0): repair slots could wedge on an
    # infinite quantile — event engine only
    defective = Params(**BASE, repair_distribution="empirical",
                       distribution_kwargs={"edges": [60.0],
                                            "rates": [1.0, 0.0]})
    assert repair_kind(defective) is None


def test_hazard_segments_protocol_opts_registered_dist_onto_fast_path():
    class StepDist(Distribution):
        def __init__(self, mean_value):
            self.mean_value = mean_value

        def sample(self, rng):
            return float(rng.exponential(self.mean_value))

        def hazard_segments(self):
            r = 1.0 / self.mean_value
            return (np.array([self.mean_value]),
                    np.array([0.5 * r, 2.0 * r]))

        @property
        def mean(self):
            return self.mean_value

    register_distribution("stepdist", lambda mean, **_: StepDist(mean))
    try:
        p = Params(**BASE, failure_distribution="stepdist")
        # protocol families never collapse to exponential (their rates
        # have no guaranteed tie to the params rate) — always empirical
        assert hazard_kind(p) == "empirical"
        assert hazard_segment_count(p) == 2
        assert supports(p)
        out = simulate_ctmc(p, n_replicas=32, seed=0)
        assert out["completed"].mean() > 0.99
    finally:
        _REGISTRY.pop("stepdist", None)


# ---------------------------------------------------------------------------
# cross-engine parity (acceptance criteria)
# ---------------------------------------------------------------------------

def test_empirical_failures_match_event_oracle():
    compare(EMPIRICAL, ["total_time", "n_failures", "n_random_failures",
                        "n_systematic_failures", "n_auto_repairs",
                        "n_manual_repairs", "recovery_overhead",
                        "useful_work"])


def test_empirical_repairs_match_event_oracle():
    compare(EMP_REPAIR, ["total_time", "n_failures", "n_auto_repairs",
                         "n_manual_repairs", "stall_time",
                         "recovery_overhead"])


def test_empirical_histogram_percentiles_within_one_bin_of_oracle():
    out, res = compare(EMPIRICAL, ["total_time"], n_event=64, n_ctmc=512)
    hc = histograms_from_arrays(out)["run_duration"]
    pool = np.concatenate([r.run_durations for r in res])
    assert hc.total > 1000 and len(pool) > 1000
    for q in (50, 90, 99):
        emp = float(np.percentile(pool, q))
        est = hc.percentile(q)
        assert abs(est - emp) <= hc.bin_width_at(emp), (q, est, emp)


def test_hazard_fitted_from_timestamped_log_runs_on_ctmc(tmp_path):
    """The PR's headline path: timestamped CSV -> fit -> CTMC parity."""
    rng = np.random.default_rng(11)
    rows = []
    for server in range(60):
        t = 0.0
        for k in range(5):
            t += float(rng.exponential(200.0 if k < 1 else 900.0))
            rows.append((t, f"s{server}"))
    log = tmp_path / "failures.csv"
    with log.open("w") as fh:
        fh.write("time,server\n")
        for t, server in sorted(rows):
            fh.write(f"{t:.3f},{server}\n")
    fit = fit_piecewise_hazard(from_log(log), n_bins=4)
    p = Params(**dict(BASE, random_failure_rate=fit.rate,
                      systematic_failure_rate=2.0 * fit.rate),
               failure_distribution="empirical",
               distribution_kwargs=fit.distribution_kwargs)
    assert resolve_engine(p, "auto") == "ctmc"
    compare(p, ["total_time", "n_failures", "useful_work"], n_event=30)


# ---------------------------------------------------------------------------
# compile sharing + bit-identical reductions (acceptance criteria)
# ---------------------------------------------------------------------------

def test_edge_and_rate_grid_compiles_once():
    from repro.core import vectorized

    short = dict(BASE, job_length=0.25 * DAY)
    grid = [Params(failure_distribution="empirical",
                   distribution_kwargs={"edges": [0.3 + 0.1 * i, 2.0 + i],
                                        "rates": [0.4, 1.2 + 0.2 * i, 0.8]},
                   **short).replace(max_run_records=17)   # module-unique
            for i in range(4)]
    assert {hazard_segment_count(p) for p in grid} == {3}
    c0 = vectorized.compile_cache_size()
    res = simulate_ctmc_sweep(grid, n_replicas=12, seed=0, max_steps=2048)
    c1 = vectorized.compile_cache_size()
    assert c1 - c0 == 1, "an empirical edges/rates grid must share " \
        "one program (segment count is the only static key)"
    assert len(res) == 4


def test_single_segment_empirical_bit_identical_to_exponential():
    base = dict(BASE, max_run_records=17)
    p_exp = Params(**base)
    p_emp = Params(**base, failure_distribution="empirical",
                   distribution_kwargs={"rates": [3.0]})
    a = simulate_ctmc(p_exp, n_replicas=64, seed=3)
    b = simulate_ctmc(p_emp, n_replicas=64, seed=3)
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    # repair side: one segment == memoryless stage at rate 1/mean
    p_rem = Params(**base, repair_distribution="empirical",
                   distribution_kwargs={"rates": [1.0]})
    c = simulate_ctmc(p_rem, n_replicas=64, seed=3)
    for k in a:
        np.testing.assert_array_equal(a[k], c[k], err_msg=k)


def test_peak_segment_rate_budgets_more_steps():
    # a NARROW peak segment: mean rescaling normalizes the overall
    # level, so only a peak that is brief relative to the mean raises
    # the peak-to-average ratio the step budget keys on
    lo = Params(**BASE, failure_distribution="empirical",
                distribution_kwargs={"edges": [0.1], "rates": [1.0, 0.9]})
    hi = Params(**BASE, failure_distribution="empirical",
                distribution_kwargs={"edges": [0.1], "rates": [8.0, 0.9]})
    assert default_max_steps(hi) > default_max_steps(lo)


# ---------------------------------------------------------------------------
# satellite: degenerate parameterizations -> event engine, still complete
# ---------------------------------------------------------------------------

_TINY = dict(job_size=2, working_pool_size=3, spare_pool_size=1,
             warm_standbys=0, job_length=30.0, random_failure_rate=0.01,
             systematic_failure_rate=0.02, recovery_time=1.0,
             auto_repair_time=5.0, manual_repair_time=10.0, seed=1,
             histogram=None)

# (name, dist, kwargs, samplable): samplable=False marks kwargs the
# *distribution itself* cannot sample (k <= 0, sigma < 0, tau <= 0) —
# there the contract is routing off the compiled path plus a clear
# Python-level error from the generic sampler, never silent garbage
# out of an XLA program.  samplable=True cases are merely outside the
# fast-path envelope and must still complete on the event engine.
_DEGENERATE = [
    ("weibull-k0", "weibull", {"k": 0.0}, False),
    ("weibull-kneg", "weibull", {"k": -1.5}, False),
    ("lognormal-sigma0", "lognormal", {"sigma": 0.0}, True),
    ("lognormal-signeg", "lognormal", {"sigma": -2.0}, False),
    ("bathtub-infant-lt1", "bathtub", {"infant_factor": 0.5}, True),
    ("bathtub-tau0", "bathtub", {"infant_factor": 4.0, "infant_tau": 0.0},
     False),
    ("bathtub-weartau-neg", "bathtub", {"wear_start": 10.0,
                                        "wear_tau": -5.0}, True),
    ("empirical-empty", "empirical", {"edges": [], "rates": []}, True),
    ("empirical-dup-edges", "empirical", {"edges": [5.0, 5.0],
                                          "rates": [1.0, 2.0, 3.0]}, True),
]


@pytest.mark.parametrize("name,dist,kwargs,samplable", _DEGENERATE,
                         ids=[d[0] for d in _DEGENERATE])
def test_degenerate_failure_branch_falls_back_and_completes(name, dist,
                                                            kwargs,
                                                            samplable):
    p = Params(**_TINY, failure_distribution=dist,
               distribution_kwargs=kwargs)
    assert hazard_kind(p) is None
    assert resolve_engine(p, "auto") == "event"
    if samplable:
        res = simulate(p, 1)
        assert len(res) == 1 and res[0].total_time >= p.job_length
    else:
        with pytest.raises((ValueError, ZeroDivisionError, OverflowError)):
            simulate(p, 1)


@pytest.mark.parametrize("name,dist,kwargs,samplable", _DEGENERATE[:4]
                         + _DEGENERATE[-2:],
                         ids=[d[0] for d in _DEGENERATE[:4]
                              + _DEGENERATE[-2:]])
def test_degenerate_repair_branch_falls_back_and_completes(name, dist,
                                                           kwargs,
                                                           samplable):
    if dist == "bathtub":
        pytest.skip("bathtub is failure-only")
    p = Params(**_TINY, repair_distribution=dist,
               distribution_kwargs=kwargs)
    assert repair_kind(p) is None
    assert resolve_engine(p, "auto") == "event"
    if samplable:
        res = simulate(p, 1)
        assert len(res) == 1 and res[0].total_time >= p.job_length
    else:
        with pytest.raises((ValueError, ZeroDivisionError, OverflowError)):
            simulate(p, 1)


def test_reregistered_builtin_name_routes_off_the_fast_path():
    """A user redefinition of a builtin name must not silently run the
    builtin's CTMC program — the fast path verifies the *instance*."""
    saved = _REGISTRY["weibull"]

    class NotWeibull(Distribution):
        def __init__(self, mean_value):
            self.mean_value = mean_value

        def sample(self, rng):
            return float(rng.uniform(0, 2 * self.mean_value))

        @property
        def mean(self):
            return self.mean_value

    register_distribution("weibull", lambda mean, **_: NotWeibull(mean))
    try:
        pf = Params(**_TINY, failure_distribution="weibull",
                    distribution_kwargs={"k": 1.5})
        assert hazard_kind(pf) is None
        assert resolve_engine(pf, "auto") == "event"
        pr = Params(**_TINY, repair_distribution="weibull")
        assert repair_kind(pr) is None
        assert resolve_engine(pr, "auto") == "event"
    finally:
        _REGISTRY["weibull"] = saved
    assert isinstance(_REGISTRY["weibull"](100.0, k=1.5), Weibull)


# ---------------------------------------------------------------------------
# satellite: scipy-absence warning
# ---------------------------------------------------------------------------

def test_missing_scipy_warns_once_and_falls_back(monkeypatch):
    p = Params(**_TINY, failure_distribution="lognormal")
    assert hazard_kind(p) == "lognormal"       # scipy present: fast path
    _scipy_available.cache_clear()
    try:
        monkeypatch.setitem(sys.modules, "scipy", None)
        monkeypatch.setitem(sys.modules, "scipy.special", None)
        with pytest.warns(RuntimeWarning, match="scipy is unavailable"):
            assert hazard_kind(p) is None
        assert resolve_engine(p, "auto") == "event"
        # one-time: the lru_cache remembers the failed probe silently
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert hazard_kind(p) is None
    finally:
        monkeypatch.undo()          # restore sys.modules *now*, not at
        _scipy_available.cache_clear()   # teardown, so the probe re-runs
    assert hazard_kind(p) == "lognormal"


# ---------------------------------------------------------------------------
# satellite: refusal messages name the actual reasons
# ---------------------------------------------------------------------------

def test_scenario_plus_weibull_repair_refusal_names_real_cause():
    from repro.core.faultdomains import FaultTopology

    p = Params(**BASE, fault_domains=FaultTopology(n_racks=4,
                                                   rack_shock_rate=1e-4),
               repair_distribution="weibull")
    reasons = unsupported_reasons(p)
    assert len(reasons) == 1 and "exponential repairs" in reasons[0]
    with pytest.raises(ValueError, match="outside the CTMC envelope"):
        resolve_engine(p, "ctmc")
    with pytest.raises(ValueError, match="exponential repairs"):
        resolve_engine(p, "ctmc")
    # the stale pre-fix message named only distribution/extension causes
    # — assert the new one does NOT claim the distribution is at fault
    try:
        resolve_engine(p, "ctmc")
    except ValueError as e:
        assert "no fast-path" not in str(e)


def test_refusal_lists_every_applicable_reason():
    p = Params(**BASE, failure_distribution="deterministic",
               repair_servers=4, retirement_threshold=2)
    reasons = unsupported_reasons(p)
    assert len(reasons) == 3
    msg = "; ".join(reasons)
    assert "repair_servers" in msg and "retirement" in msg
    with pytest.raises(ValueError, match="repair_servers"):
        simulate_ctmc(p, n_replicas=2)
    assert unsupported_reasons(Params(**BASE)) == []


def test_multijob_refusal_names_real_cause():
    from repro.core.vectorized_multijob import unsupported_reasons_multijob

    cluster = Params(**BASE)
    jobs = [JobSpec(job_size=8, job_length=100.0, start_time=50.0)]
    reasons = unsupported_reasons_multijob(cluster, jobs)
    assert len(reasons) == 1 and "start" in reasons[0]
    with pytest.raises(ValueError, match="outside the CTMC envelope"):
        resolve_engine_multijob(cluster, jobs, "ctmc")
    with pytest.raises(ValueError, match="t=0"):
        resolve_engine_multijob(cluster, jobs, "ctmc")
