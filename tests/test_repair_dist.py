"""Non-exponential repairs + lognormal failures vs the event oracle.

The CTMC engine now runs Weibull / lognormal / deterministic *repair*
distributions through the repair-slot lane (durations sampled at shop
entry by exact inverse CDF — the same machinery the failure race uses,
:class:`repro.core.hazards.HazardSampler`), and lognormal *failures*
via Ogata thinning against the numerically-located hazard-mode bound.
These tests pin the acceptance criteria:

  * ``supports()`` says yes and ``engine=auto`` dispatches to ``ctmc``;
  * metric *means* match the event oracle within sampling error
    (z < 3.5 on pinned seeds, the test_vectorized.py discipline);
  * histogram percentiles match within one bin width in a stall-bound
    regime where the ETTR distribution IS the repair distribution;
  * weibull k=1 repairs statistically reduce to the validated
    exponential program, and exponential repairs keep the PR 4 program
    structure exactly (no slot state, original 8-wide uniform stream);
  * a repair-parameter grid compiles exactly one XLA program;
  * truncated horizons: a repair still in flight when the job completes
    is dropped by BOTH engines, and a repair completing *exactly* at
    ``total_time`` counts on both (repair-first tie resolution, matching
    the event heap's insertion order);
  * the float64 age carve-out (``Params.age_dtype``) closes the
    large-age cancellation of the weibull conditional inversion.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MINUTES_PER_DAY as DAY
from repro.core import (OneWaySweep, Params, resolve_engine,
                        run_replications, simulate)
from repro.core.hazards import (hazard_kind, repair_kind,
                                weibull_conditional_ttf)
from repro.core.metrics import histograms_from_arrays, histograms_from_results
from repro.core.vectorized import (_initial_state, _n_uniforms,
                                   _params_vector, _step_u, simulate_ctmc,
                                   simulate_ctmc_sweep, supports)

N_EVENT = 40
N_CTMC = 768

#: same small-but-busy cluster as tests/test_nonexp.py: cheap event
#: replications, O(100) failures per run for tight statistics, repair
#: times short enough that the shop stays busy without stalling.
BASE = dict(job_size=24, working_pool_size=32, spare_pool_size=4,
            warm_standbys=2, job_length=2 * DAY,
            random_failure_rate=2.0 / DAY,
            systematic_failure_rate=4.0 / DAY, recovery_time=5.0,
            auto_repair_time=30.0, manual_repair_time=120.0, seed=5)

WB_REPAIR = Params(repair_distribution="weibull",
                   distribution_kwargs={"k": 0.7}, **BASE)
LN_REPAIR = Params(repair_distribution="lognormal",
                   distribution_kwargs={"sigma": 1.2}, **BASE)
DET_REPAIR = Params(repair_distribution="deterministic", **BASE)
LN_FAIL = Params(failure_distribution="lognormal", **BASE)
COMBINED = Params(failure_distribution="lognormal",
                  repair_distribution="weibull",
                  distribution_kwargs={"k": 0.7, "sigma": 1.0}, **BASE)


def compare(p: Params, metrics, n_event=N_EVENT, n_ctmc=N_CTMC, z_tol=3.5):
    out = simulate_ctmc(p, n_replicas=n_ctmc, seed=0)
    assert out["completed"].mean() > 0.99, "CTMC replicas did not finish"
    assert out["n_repair_overflow"].sum() == 0, "repair-slot lane overflowed"
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
    assert repair_kind(WB_REPAIR) == "weibull"
    assert repair_kind(LN_REPAIR) == "lognormal"
    assert repair_kind(DET_REPAIR) == "deterministic"
    assert hazard_kind(LN_FAIL) == "lognormal"
    for p in (WB_REPAIR, LN_REPAIR, DET_REPAIR, LN_FAIL, COMBINED):
        assert supports(p)
        assert resolve_engine(p, "auto") == "ctmc"
    # degenerate parameterizations and user-registered families fall back
    assert repair_kind(WB_REPAIR.replace(
        distribution_kwargs={"k": -1.0})) is None
    assert hazard_kind(LN_FAIL.replace(
        distribution_kwargs={"sigma": 0.0})) is None
    assert not supports(WB_REPAIR.replace(repair_distribution="nonsense"))


def test_exponential_repairs_keep_pr4_program_structure():
    """The exponential reduction must be *structural*, not statistical:
    no slot lane in the scan state and the original 8-wide uniform
    stream, so the compiled program is the PR 4 one bit-for-bit."""
    state = _initial_state(Params(**BASE), 4)
    assert "repair_rem" not in state and "repair_stage" not in state
    assert _n_uniforms("exponential", "exponential") == 8
    # non-exponential repairs add exactly the slot lane + one uniform
    state = _initial_state(WB_REPAIR, 4)
    assert state["repair_rem"].shape[0] == 4
    assert bool(jnp.isinf(state["repair_rem"]).all())
    assert _n_uniforms("exponential", "weibull") == 9
    assert _n_uniforms("lognormal", "weibull") == 10


# ---------------------------------------------------------------------------
# cross-engine agreement (acceptance criteria)
# ---------------------------------------------------------------------------

def test_weibull_repairs_match_event_oracle():
    compare(WB_REPAIR, ["total_time", "n_failures", "n_auto_repairs",
                        "n_manual_repairs", "n_failed_repairs",
                        "recovery_overhead", "n_standby_swaps",
                        "useful_work"])


def test_lognormal_repairs_match_event_oracle():
    compare(LN_REPAIR, ["total_time", "n_failures", "n_auto_repairs",
                        "n_manual_repairs", "recovery_overhead"])


def test_deterministic_repairs_match_event_oracle():
    compare(DET_REPAIR, ["total_time", "n_failures", "n_auto_repairs",
                         "n_manual_repairs", "n_failed_repairs"])


def test_lognormal_failures_match_event_oracle():
    compare(LN_FAIL, ["total_time", "n_failures", "n_random_failures",
                      "n_systematic_failures", "n_auto_repairs",
                      "recovery_overhead", "useful_work"])


def test_combined_lognormal_failures_weibull_repairs():
    compare(COMBINED, ["total_time", "n_failures", "n_auto_repairs",
                       "n_manual_repairs", "recovery_overhead"])


def test_stall_bound_ettr_histogram_within_one_bin():
    """Starved pools: every failure stalls until its own repair returns,
    so the recovery (ETTR) histogram directly measures the sampled
    repair durations — percentile agreement here is the sharpest
    cross-engine check of the slot lane's inverse-CDF sampling."""
    p = Params(job_size=8, working_pool_size=9, spare_pool_size=0,
               warm_standbys=0, job_length=1 * DAY,
               random_failure_rate=4.0 / DAY,
               systematic_failure_rate=8.0 / DAY, recovery_time=5.0,
               auto_repair_time=45.0, manual_repair_time=180.0,
               diagnosis_probability=1.0,
               repair_distribution="weibull",
               distribution_kwargs={"k": 0.7}, seed=11)
    out = simulate_ctmc(p, n_replicas=512, seed=2)
    assert out["stall_time"].mean() > 0, "regime must actually stall"
    hc = histograms_from_arrays(out)
    he = histograms_from_results(simulate(p, 64), p.histogram)
    for ch in ("recovery", "run_duration"):
        sup = np.abs(hc[ch].cdf() - he[ch].cdf()).max()
        assert sup < 0.08, (ch, sup)
    hrec, erec = hc["recovery"], he["recovery"]
    assert hrec.total > 500 and erec.total > 500
    for q in (50, 90, 99):
        est, emp = hrec.percentile(q), erec.percentile(q)
        assert abs(est - emp) <= hrec.bin_width_at(emp), (q, est, emp)


def test_weibull_k1_repairs_reduce_to_exponential():
    """Weibull k=1 *is* exponential; the slot lane must reproduce the
    validated count-based exponential repair program statistically."""
    pw = WB_REPAIR.replace(distribution_kwargs={"k": 1.0})
    exp_out = simulate_ctmc(Params(**BASE), n_replicas=768, seed=0)
    wb_out = simulate_ctmc(pw, n_replicas=768, seed=1)
    for m in ("total_time", "n_failures", "n_auto_repairs",
              "n_manual_repairs", "recovery_overhead"):
        a, b = exp_out[m], wb_out[m]
        se = np.sqrt(a.std() ** 2 / len(a) + b.std() ** 2 / len(b))
        assert abs(a.mean() - b.mean()) / max(se, 1e-9) < 3.5, m


# ---------------------------------------------------------------------------
# batching mechanics
# ---------------------------------------------------------------------------

def test_repair_parameter_grid_compiles_once():
    from repro.core import vectorized

    short = dict(BASE, job_length=0.25 * DAY)
    base = Params(repair_distribution="weibull",
                  distribution_kwargs={"k": 0.7},
                  **short).replace(max_run_records=17)   # module-unique shape
    grid = [base.replace(auto_repair_time=v) for v in (20.0, 40.0, 60.0)]
    c0 = vectorized.compile_cache_size()
    simulate_ctmc_sweep(grid, n_replicas=12, seed=0, max_steps=1024)
    c1 = vectorized.compile_cache_size()
    assert c1 - c0 == 1, "a repair-parameter grid must share one program"


def test_single_point_sweep_bit_identical():
    for p in (WB_REPAIR, LN_FAIL, COMBINED):
        sweep = simulate_ctmc_sweep([p], n_replicas=21, seed=9,
                                    max_steps=4096)[0]
        single = simulate_ctmc(p, n_replicas=21, seed=9, max_steps=4096)
        assert set(sweep) == set(single)
        for k in sweep:
            np.testing.assert_array_equal(sweep[k], single[k], err_msg=k)


def test_mixed_repair_family_grid_runs_in_input_order():
    short = dict(BASE, job_length=0.25 * DAY)
    grid = [Params(**short),
            Params(repair_distribution="weibull",
                   distribution_kwargs={"k": 0.7}, **short),
            Params(failure_distribution="lognormal", **short),
            Params(**short).replace(recovery_time=40.0)]
    res = simulate_ctmc_sweep(grid, n_replicas=32, seed=1)
    assert len(res) == len(grid)
    for r in res:
        assert r["completed"].mean() > 0.99
    assert res[3]["total_time"].mean() > res[0]["total_time"].mean()


def test_sweep_engine_auto_takes_fast_path():
    sweep = OneWaySweep("rp", "auto_repair_time", [20.0, 60.0],
                        n_replications=16, base_params=WB_REPAIR.replace(
                            job_length=0.25 * DAY), engine="auto")
    res = sweep.run()
    assert [pt.engine for pt in res.points] == ["ctmc", "ctmc"]


def test_infinite_mean_repair_stage_sizes_to_physical_cap():
    """An infinite-mean repair stage (server never returns) must not
    crash the Little's-law slot sizing — the physical cap (every server
    in the shop) is the honest lane width there, including the NaN
    regime where the escalation term multiplies 0 * inf."""
    import math

    from repro.core.vectorized import _repair_slots_for

    p = WB_REPAIR.replace(manual_repair_time=math.inf)
    total = p.working_pool_size + p.spare_pool_size
    assert supports(p)
    assert 1 <= _repair_slots_for([p], "weibull") <= total
    nan_regime = p.replace(automated_repair_probability=1.0)
    assert 1 <= _repair_slots_for([nan_regime], "weibull") <= total


def test_repair_slot_overflow_is_surfaced():
    """A deliberately starved slot lane must count overflows and warn,
    never crash or silently drop the accounting."""
    p = Params(job_size=8, working_pool_size=16, spare_pool_size=0,
               warm_standbys=4, job_length=0.5 * DAY,
               random_failure_rate=8.0 / DAY, recovery_time=2.0,
               diagnosis_probability=1.0,
               repair_distribution="deterministic",
               auto_repair_time=5 * DAY, manual_repair_time=5 * DAY,
               repair_slots=1, seed=3)
    with pytest.warns(RuntimeWarning, match="repair-slot lane"):
        rep = run_replications(p, 64, engine="ctmc")
    assert rep.stats["n_repair_overflow"].mean > 0


# ---------------------------------------------------------------------------
# truncated horizons (engine parity at the job-completion boundary)
# ---------------------------------------------------------------------------

def test_repairs_in_flight_at_completion_dropped_on_both_engines():
    """A repair that has not finished when the job completes must not
    count on either engine (the event engine abandons pending repair
    processes; the CTMC scan freezes DONE replicas).  The pool is large
    enough that the job never stalls — a stalled job would legitimately
    wait out the 10-day repair and count it on both engines."""
    p = Params(job_size=4, working_pool_size=40, spare_pool_size=0,
               warm_standbys=8, job_length=0.5 * DAY,
               random_failure_rate=2.0 / DAY, systematic_failure_rate=0.0,
               recovery_time=2.0, diagnosis_probability=1.0,
               repair_distribution="deterministic",
               auto_repair_time=10 * DAY, manual_repair_time=10 * DAY,
               seed=7)
    out = simulate_ctmc(p, n_replicas=256, seed=0)
    res = simulate(p, 64)
    assert out["n_failures"].mean() > 0.3
    assert out["n_auto_repairs"].max() == 0
    assert max(r.n_auto_repairs for r in res) == 0
    assert any(r.n_failures > 0 for r in res)


def test_event_heap_runs_first_scheduled_at_equal_timestamps():
    """The event engine's convention the CTMC tie-break mirrors: at one
    timestamp, the earlier-scheduled timeout (the repair, submitted
    before the final phase started) runs first."""
    from repro.core.engine import Environment

    env = Environment()
    order = []

    def proc(name, delay):
        yield env.timeout(delay)
        order.append(name)

    env.process(proc("repair", 5.0), name="repair")       # scheduled first
    env.process(proc("complete", 5.0), name="complete")
    env.run()
    assert order == ["repair", "complete"]


def test_repair_completing_exactly_at_total_time_counts():
    """Exact tie between the repair residual and job completion: the
    repair resolves first (counted, histogram-binned) and the job
    completes at the same instant — identical to the event engine's
    heap order and total_time."""
    p = Params(job_size=4, working_pool_size=8, spare_pool_size=0,
               warm_standbys=0, job_length=100.0, host_selection_time=0.0,
               random_failure_rate=0.0, systematic_failure_rate=0.0,
               auto_repair_failure_probability=0.0,
               repair_distribution="deterministic", auto_repair_time=100.0,
               seed=0)
    state = _initial_state(p, 1)
    # one bad-class server mid-repair whose remaining time ties the
    # remaining work exactly
    state["repair_rem"] = state["repair_rem"].at[0, 0].set(100.0)
    state["repair_cls"] = state["repair_cls"].at[0, 0].set(1)
    pv = _params_vector(p)
    nu = _n_uniforms("exponential", "deterministic")
    u = jnp.full((1, nu), 0.5, jnp.float32)

    s1 = _step_u(state, u, pv, None, "exponential", "deterministic")
    assert float(s1["n_auto_repairs"][0]) == 1.0      # repair counted
    assert int(s1["phase"][0]) != 3                   # job not done yet
    assert float(s1["work_left"][0]) == 0.0
    assert bool(jnp.isinf(s1["repair_rem"]).all())    # slot freed
    t_tie = float(s1["t"][0])

    s2 = _step_u(s1, u, pv, None, "exponential", "deterministic")
    assert int(s2["phase"][0]) == 3                   # DONE at dt=0
    assert float(s2["total_time"][0]) == t_tie        # same instant
    assert float(s2["n_auto_repairs"][0]) == 1.0
    # the final run lands in the same histogram bin the event engine
    # would use for a 100-minute run duration
    edges = np.asarray(s2["hist_edges"])
    want_bin = int(np.searchsorted(edges, 100.0, side="right"))
    assert float(s2["hist"][0, 0, want_bin]) >= 1.0


# ---------------------------------------------------------------------------
# float64 age carve-out
# ---------------------------------------------------------------------------

def test_age_dtype_validation():
    with pytest.raises(ValueError, match="age_dtype"):
        Params(age_dtype="float16").validate()
    if not jax.config.jax_enable_x64:
        with pytest.raises(ValueError, match="x64"):
            simulate_ctmc(Params(age_dtype="float64", **BASE), n_replicas=4)


def test_float64_carve_out_closes_large_age_cancellation():
    """ROADMAP item: at age ~1e4 the float32 inversion
    ``(a^k + E/C)^(1/k) - a`` loses ~1e-3 min to cancellation; the
    float64 path must pin the error orders of magnitude lower."""
    age, k = 1.0e4, 1.5
    C, E = 1.0e-6, 0.1            # E/C << age^k: the cancellation regime
    ref = (age ** k + E / C) ** (1.0 / k) - age      # python float64

    f32 = float(weibull_conditional_ttf(
        jnp.float32(age), jnp.float32(C), k, jnp.float32(E)))
    err32 = abs(f32 - ref)

    jax.config.update("jax_enable_x64", True)
    try:
        f64 = float(weibull_conditional_ttf(
            jnp.float64(age), jnp.float64(C), k, jnp.float64(E)))
    finally:
        jax.config.update("jax_enable_x64", False)
    err64 = abs(f64 - ref)

    assert err32 > 1e-5, "test must sit in the cancellation regime"
    assert err64 < err32 / 10.0
    assert err64 < 1e-4 * max(ref, 1.0)


def test_age_dtype_float64_end_to_end():
    """The whole scan runs with the float64 age/repair lanes and stays
    statistically on top of the float32 program."""
    jax.config.update("jax_enable_x64", True)
    try:
        p64 = WB_REPAIR.replace(age_dtype="float64",
                                job_length=0.5 * DAY,
                                max_run_records=19)   # test-unique shapes
        p32 = p64.replace(age_dtype="float32")
        o64 = simulate_ctmc(p64, n_replicas=256, seed=0)
        o32 = simulate_ctmc(p32, n_replicas=256, seed=0)
    finally:
        jax.config.update("jax_enable_x64", False)
    assert o64["completed"].mean() > 0.99
    for m in ("total_time", "n_failures", "n_auto_repairs"):
        a, b = o64[m], o32[m]
        se = np.sqrt(a.std() ** 2 / len(a) + b.std() ** 2 / len(b))
        assert abs(a.mean() - b.mean()) / max(se, 1e-9) < 3.5, m


# ---------------------------------------------------------------------------
# budget sanity
# ---------------------------------------------------------------------------

def test_lognormal_budget_covers_thinning_candidates():
    """The derived step budget must absorb rejected thinning candidates
    (majorant-rate events), not just accepted failures — completion at
    the default budget is the observable contract."""
    out = simulate_ctmc(LN_FAIL, n_replicas=256, seed=4)
    assert out["completed"].mean() > 0.99
