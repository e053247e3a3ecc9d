"""Non-exponential hazards on the vectorized fast path vs the event oracle.

The CTMC engine now runs Weibull and bathtub failure processes (see
docs/distributions.md and :mod:`repro.core.hazards`): Weibull via exact
closed-form conditional inversion, bathtub via piecewise-constant hazard
majorization + Ogata thinning.  These tests pin the acceptance criteria:

  * ``supports()`` says yes and ``engine=auto`` dispatches to ``ctmc``;
  * metric *means* match the event oracle within sampling error on
    pinned seeds (the same z-test discipline as tests/test_vectorized.py);
  * histogram percentiles match within one bin width and the CDFs agree
    at sampling-error scale;
  * degenerate parameterizations (Weibull k=1, flat bathtub) reproduce
    the exponential baseline — the two new sampling mechanisms are
    cross-checked against the already-validated exponential program.
"""

import numpy as np
import pytest

from repro.core import MINUTES_PER_DAY as DAY
from repro.core import OneWaySweep, Params, resolve_engine, simulate
from repro.core.hazards import hazard_kind
from repro.core.metrics import histograms_from_arrays, histograms_from_results
from repro.core.vectorized import (default_max_steps, simulate_ctmc,
                                   simulate_ctmc_sweep, supports)

N_EVENT = 40
N_CTMC = 768

#: small cluster with enough failures for tight statistics but cheap
#: event-oracle replications (the oracle's non-exponential sampler is
#: O(cluster size) per restart).  The systematic rate is cranked up so
#: systematic counts are O(several) per run — a near-zero-count metric
#: makes the z-test degenerate (the event side legitimately sees zero).
BASE = dict(job_size=24, working_pool_size=32, spare_pool_size=4,
            warm_standbys=2, job_length=2 * DAY,
            random_failure_rate=2.0 / DAY,
            systematic_failure_rate=4.0 / DAY, recovery_time=5.0,
            auto_repair_time=30.0, manual_repair_time=120.0, seed=5)

WEIBULL = Params(failure_distribution="weibull",
                 distribution_kwargs={"k": 1.5}, **BASE)
WEIBULL_INFANT = Params(failure_distribution="weibull",
                        distribution_kwargs={"k": 0.8}, **BASE)
BATHTUB = Params(failure_distribution="bathtub",
                 distribution_kwargs={"infant_factor": 8.0,
                                      "infant_tau": 0.25 * DAY},
                 **BASE)


def compare(p: Params, metrics, n_event=N_EVENT, n_ctmc=N_CTMC, z_tol=3.5):
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
# dispatch
# ---------------------------------------------------------------------------

def test_supported_families_and_dispatch():
    assert hazard_kind(WEIBULL) == "weibull"
    assert hazard_kind(BATHTUB) == "bathtub"
    assert supports(WEIBULL) and supports(BATHTUB)
    assert resolve_engine(WEIBULL, "auto") == "ctmc"
    assert resolve_engine(BATHTUB, "auto") == "ctmc"
    # lognormal failures and non-exponential repairs joined the fast
    # path (tests/test_repair_dist.py); user-registered families and
    # degenerate parameterizations are still outside the envelope
    assert supports(WEIBULL.replace(failure_distribution="lognormal"))
    assert supports(WEIBULL.replace(repair_distribution="weibull"))
    assert not supports(WEIBULL.replace(failure_distribution="deterministic"))
    assert hazard_kind(WEIBULL.replace(
        distribution_kwargs={"k": -1.0})) is None


def test_sweep_engine_auto_takes_fast_path():
    sweep = OneWaySweep("bt", "recovery_time", [5.0, 15.0],
                        n_replications=16, base_params=BATHTUB.replace(
                            job_length=0.25 * DAY), engine="auto")
    res = sweep.run()
    assert [pt.engine for pt in res.points] == ["ctmc", "ctmc"]
    assert res.points[0].stats["total_time"].mean \
        < res.points[1].stats["total_time"].mean


# ---------------------------------------------------------------------------
# cross-engine agreement (acceptance criteria)
# ---------------------------------------------------------------------------

def test_weibull_wearout_matches_event_oracle():
    compare(WEIBULL, ["total_time", "n_failures", "n_random_failures",
                      "n_systematic_failures", "n_auto_repairs",
                      "n_manual_repairs", "recovery_overhead",
                      "useful_work"])


def test_weibull_infant_mortality_matches_event_oracle():
    """k < 1: the hazard diverges at age zero — exactly the regime where
    thinning has no finite majorant and the closed-form conditional
    inversion must carry the load."""
    compare(WEIBULL_INFANT, ["total_time", "n_failures", "stall_time",
                             "n_standby_swaps"])


def test_bathtub_matches_event_oracle():
    compare(BATHTUB, ["total_time", "n_failures", "n_random_failures",
                      "n_systematic_failures", "n_auto_repairs",
                      "recovery_overhead"])


def test_weibull_histogram_percentiles_within_one_bin_of_oracle():
    out, res = compare(WEIBULL, ["total_time"], n_event=64, n_ctmc=512)
    hc = histograms_from_arrays(out)["run_duration"]
    pool = np.concatenate([r.run_durations for r in res])
    assert hc.total > 1000 and len(pool) > 1000
    for q in (50, 90, 99):
        emp = float(np.percentile(pool, q))
        est = hc.percentile(q)
        assert abs(est - emp) <= hc.bin_width_at(emp), (q, est, emp)


@pytest.mark.parametrize("params", [WEIBULL, BATHTUB],
                         ids=["weibull", "bathtub"])
def test_cross_engine_cdf_agreement(params):
    out = simulate_ctmc(params, n_replicas=512, seed=2)
    hc = histograms_from_arrays(out)
    he = histograms_from_results(simulate(params, 64), params.histogram)
    for ch in ("run_duration", "recovery"):
        sup = np.abs(hc[ch].cdf() - he[ch].cdf()).max()
        assert sup < 0.08, (ch, sup)


# ---------------------------------------------------------------------------
# degenerate parameterizations reduce to the exponential baseline
# ---------------------------------------------------------------------------

def _z(a: np.ndarray, b: np.ndarray) -> float:
    se = np.sqrt(a.std() ** 2 / len(a) + b.std() ** 2 / len(b))
    return float((a.mean() - b.mean()) / max(se, 1e-9))


def test_weibull_k1_reduces_to_exponential():
    """Weibull with k=1 *is* exponential; the inversion mechanism must
    reproduce the validated exponential program statistically."""
    pw = WEIBULL.replace(distribution_kwargs={"k": 1.0})
    exp_out = simulate_ctmc(Params(**BASE), n_replicas=768, seed=0)
    wb_out = simulate_ctmc(pw, n_replicas=768, seed=1)
    for m in ("total_time", "n_failures", "recovery_overhead"):
        assert abs(_z(exp_out[m], wb_out[m])) < 3.5, m


def test_flat_bathtub_reduces_to_exponential():
    """infant_factor=1 and wear beyond the horizon make g(t) == 1: every
    thinning candidate is accepted and the process is exponential."""
    pb = Params(failure_distribution="bathtub",
                distribution_kwargs={"infant_factor": 1.0,
                                     "wear_start": 1e9},
                **BASE)
    exp_out = simulate_ctmc(Params(**BASE), n_replicas=768, seed=0)
    bt_out = simulate_ctmc(pb, n_replicas=768, seed=1)
    for m in ("total_time", "n_failures", "recovery_overhead"):
        assert abs(_z(exp_out[m], bt_out[m])) < 3.5, m


# ---------------------------------------------------------------------------
# mechanics
# ---------------------------------------------------------------------------

def test_deterministic_given_seed_weibull():
    a = simulate_ctmc(WEIBULL, n_replicas=64, seed=11)
    b = simulate_ctmc(WEIBULL, n_replicas=64, seed=11)
    np.testing.assert_array_equal(a["total_time"], b["total_time"])


def test_single_point_sweep_bit_identical_weibull_and_bathtub():
    for p in (WEIBULL, BATHTUB):
        sweep = simulate_ctmc_sweep([p], n_replicas=21, seed=9,
                                    max_steps=4096)[0]
        single = simulate_ctmc(p, n_replicas=21, seed=9, max_steps=4096)
        assert set(sweep) == set(single)
        for k in sweep:
            np.testing.assert_array_equal(sweep[k], single[k], err_msg=k)


def test_mixed_family_grid_runs_in_input_order():
    short = dict(BASE, job_length=0.25 * DAY)
    grid = [Params(**short),
            Params(failure_distribution="weibull",
                   distribution_kwargs={"k": 1.5}, **short),
            Params(failure_distribution="bathtub", **short),
            Params(**short).replace(recovery_time=40.0)]
    res = simulate_ctmc_sweep(grid, n_replicas=32, seed=1)
    assert len(res) == len(grid)
    for r in res:
        assert r["completed"].mean() > 0.99
    # point 3 differs from point 0 only by a larger recovery time
    assert res[3]["total_time"].mean() > res[0]["total_time"].mean()


def test_weibull_k_is_traced_one_compile_per_bucket():
    from repro.core import vectorized

    short = dict(BASE, job_length=0.25 * DAY)
    base = Params(failure_distribution="weibull",
                  distribution_kwargs={"k": 1.5},
                  **short).replace(max_run_records=13)   # module-unique shape
    grid = [base.replace(distribution_kwargs={"k": kk})
            for kk in (0.9, 1.2, 1.5)]
    c0 = vectorized.compile_cache_size()
    simulate_ctmc_sweep(grid, n_replicas=12, seed=0, max_steps=1024)
    c1 = vectorized.compile_cache_size()
    assert c1 - c0 == 1, "a weibull-k grid must share one program"
    # infant mortality (smaller k) concentrates failures: monotone check
    out = simulate_ctmc_sweep(grid, n_replicas=128, seed=0)
    fails = [r["n_failures"].mean() for r in out]
    assert fails[0] > fails[1] > fails[2], fails


def test_budget_is_hazard_aware():
    """Infant-heavy hazards generate more events; the derived step
    budget must scale with the age-zero hazard, not the flat rate."""
    exp_steps = default_max_steps(Params(**BASE))
    assert default_max_steps(BATHTUB) > 2 * exp_steps
    assert default_max_steps(WEIBULL_INFANT) > exp_steps
