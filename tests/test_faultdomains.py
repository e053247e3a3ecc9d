"""Correlated failure domains + scripted injection campaigns, both engines.

Acceptance criteria for :mod:`repro.core.faultdomains` (see
docs/scenarios.md):

  * topology / campaign validation happens in ``Params.validate``;
  * a zero-rate topology plus an empty campaign is *bit-identical* to a
    plain run on BOTH engines (the scenario machinery must draw nothing
    from the RNG and add no compartment noise);
  * cross-engine metric means agree within sampling error (z < 3.5) on a
    scenario combining stochastic rack/pod shocks, a scripted domain
    kill, and a maintenance window that pauses the repair shop — the
    kill lands mid-repair for some replicas;
  * campaigns are honored *exactly*: event counts, kill times, and
    members struck are deterministic;
  * per-domain shock telemetry is consistent (``domain_shocks`` sums to
    ``n_domain_shocks``);
  * a shock-rate sweep is traced — the whole grid compiles one program;
  * scenarios combined with non-exponential repairs stay on the event
    oracle (``supports()`` gating), where struck in-shop servers are
    re-broken by redrawing the stage.
"""

import numpy as np
import pytest

from repro.core import (Campaign, CampaignEvent, FaultTopology, OneWaySweep,
                        Params, Tracer, resolve_engine, run_replications,
                        simulate, simulate_one)
from repro.core.metrics import aggregate, histograms_from_arrays
from repro.core.simulation import ClusterSimulation
from repro.core.vectorized import simulate_ctmc, simulate_ctmc_sweep, supports

N_EVENT = 48
N_CTMC = 768

#: fleet of 40 divides evenly by 4 racks, so every pool holds exactly 25%
#: of each rack and the CTMC's fleet-fraction kill is the exact
#: expectation of the event engine's member count in every compartment
TOPO = FaultTopology(n_racks=4, racks_per_pod=2,
                     rack_shock_rate=1.2e-4, pod_shock_rate=3e-5)
CAMPAIGN = Campaign(events=(
    CampaignEvent(time=400.0, kind="kill", domain=2),
    CampaignEvent(time=900.0, kind="maintenance", duration=300.0),
))
BASE = Params(job_size=24, working_pool_size=32, spare_pool_size=8,
              warm_standbys=4, job_length=3000.0,
              random_failure_rate=2e-4, systematic_failure_rate=1e-3,
              recovery_time=10.0, seed=5)
SCENARIO = BASE.replace(fault_domains=TOPO, campaign=CAMPAIGN)


def _z(a: np.ndarray, b: np.ndarray) -> float:
    se = np.sqrt(a.std() ** 2 / len(a) + b.std(ddof=1) ** 2 / len(b))
    return float((b.mean() - a.mean()) / max(se, 1e-9))


# ---------------------------------------------------------------------------
# validation + dispatch
# ---------------------------------------------------------------------------

def test_topology_validation():
    with pytest.raises(ValueError, match="n_racks"):
        FaultTopology(n_racks=0).validate(8)
    with pytest.raises(ValueError, match="exceeds the fleet"):
        FaultTopology(n_racks=100).validate(8)
    with pytest.raises(ValueError, match="racks_per_pod"):
        FaultTopology(n_racks=4, pod_shock_rate=1e-4).validate(8)
    # validation is wired through Params.validate
    with pytest.raises(ValueError, match="exceeds the fleet"):
        BASE.replace(fault_domains=FaultTopology(n_racks=1000)).validate()


def test_campaign_validation_and_schedule():
    with pytest.raises(ValueError, match="require Params.fault_domains"):
        BASE.replace(campaign=Campaign(events=(
            CampaignEvent(time=1.0, kind="kill", domain=0),))).validate()
    with pytest.raises(ValueError, match="out of range"):
        SCENARIO.replace(campaign=Campaign(events=(
            CampaignEvent(time=1.0, kind="kill", domain=99),))).validate()
    with pytest.raises(ValueError, match="duration"):
        CampaignEvent(time=1.0, kind="maintenance").validate(None)
    # maintenance flattens to start/end; stable time sort
    assert CAMPAIGN.schedule() == [(400.0, 0, 2), (900.0, 1, 0),
                                   (1200.0, 2, 0)]


def test_domain_membership_stripes_fleet():
    total = BASE.working_pool_size + BASE.spare_pool_size
    racks = [TOPO.domain_members(d, total) for d in range(TOPO.n_racks)]
    assert sorted(s for r in racks for s in r) == list(range(total))
    assert all(len(r) == total // TOPO.n_racks for r in racks)
    # pod 1 = racks {2, 3}
    pod1 = TOPO.domain_members(TOPO.n_racks + 1, total)
    assert set(pod1) == set(racks[2]) | set(racks[3])


def test_supports_gates_scenario_with_nonexp_repairs_to_event():
    assert supports(SCENARIO)
    assert resolve_engine(SCENARIO, "auto") == "ctmc"
    nonexp = SCENARIO.replace(repair_distribution="weibull",
                              distribution_kwargs={"repair_k": 1.5})
    assert not supports(nonexp)
    assert resolve_engine(nonexp, "auto") == "event"


# ---------------------------------------------------------------------------
# bit-identity: inert scenario == plain run (both engines)
# ---------------------------------------------------------------------------

def test_inert_scenario_bit_identical_event():
    """Zero shock rates + empty campaign must not perturb the RNG or the
    event order: every metric of every replica is byte-identical."""
    inert = BASE.replace(
        fault_domains=FaultTopology(n_racks=4, racks_per_pod=2),
        campaign=Campaign())
    for seed in (5, 23, 77):
        a = simulate_one(BASE, seed=seed).to_dict()
        b = simulate_one(inert, seed=seed).to_dict()
        for k in ("n_domain_shocks", "n_shock_killed", "n_campaign_events"):
            assert b.pop(k) == 0
            a.pop(k)
        assert a == b, seed


def test_inert_scenario_reduces_exactly_ctmc():
    """The scenario program adds race lanes; with zero rates they never
    win, so every counter is bit-identical and the accumulated times
    agree to float32 reduction-order noise (one ulp)."""
    inert = BASE.replace(
        fault_domains=FaultTopology(n_racks=4, racks_per_pod=2),
        campaign=Campaign())
    plain = simulate_ctmc(BASE, n_replicas=64, seed=3, max_steps=4096)
    scen = simulate_ctmc(inert, n_replicas=64, seed=3, max_steps=4096)
    for k in plain:
        if k.startswith("n_") or k in ("completed", "domain_shocks"):
            np.testing.assert_array_equal(plain[k], scen[k], err_msg=k)
        else:
            np.testing.assert_allclose(plain[k], scen[k], rtol=1e-6,
                                       atol=1e-4, err_msg=k)
    assert scen["n_domain_shocks"].sum() == 0
    assert scen["domain_shocks"].sum() == 0


# ---------------------------------------------------------------------------
# cross-engine agreement (acceptance criteria)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def scenario_runs():
    out = simulate_ctmc(SCENARIO, n_replicas=N_CTMC, seed=6)
    assert out["completed"].mean() > 0.99, "CTMC replicas did not finish"
    res = simulate(SCENARIO, N_EVENT, base_seed=5)
    return out, res


def test_scenario_matches_event_oracle(scenario_runs):
    """Shocks + mid-run domain kill + maintenance window: metric means
    agree across engines within sampling error."""
    out, res = scenario_runs
    for m in ("total_time", "n_failures", "n_standby_swaps",
              "n_host_selections", "n_preemptions", "recovery_overhead",
              "n_domain_shocks", "n_shock_killed", "n_campaign_events"):
        ev = np.array([getattr(r, m) for r in res], float)
        z = _z(out[m], ev)
        assert abs(z) < 3.5, (m, ev.mean(), float(out[m].mean()), z)


def test_scenario_histogram_percentiles_one_bin(scenario_runs):
    out, res = scenario_runs
    hc = histograms_from_arrays(out)["run_duration"]
    pool = np.concatenate([r.run_durations for r in res])
    assert hc.total > 1000 and len(pool) > 500
    for q in (50, 90):
        emp = float(np.percentile(pool, q))
        est = hc.percentile(q)
        assert abs(est - emp) <= hc.bin_width_at(emp), (q, est, emp)


def test_per_domain_telemetry_consistent(scenario_runs):
    out, res = scenario_runs
    # CTMC: per-replica rows sum to the scalar counter
    assert out["domain_shocks"].shape == (N_CTMC, TOPO.n_domains)
    np.testing.assert_allclose(out["domain_shocks"].sum(axis=1),
                               out["n_domain_shocks"], rtol=1e-6)
    # event: same invariant, and the aggregate surfaces the scalar
    for r in res:
        assert len(r.domain_shocks) == TOPO.n_domains
        assert sum(r.domain_shocks) == r.n_domain_shocks
    stats = aggregate(res)
    assert stats["n_domain_shocks"].mean >= 0.0
    # rack shocks dominate: rack rate is 4x the pod rate
    ev_per_dom = np.sum([r.domain_shocks for r in res], axis=0)
    ct_per_dom = np.asarray(out["domain_shocks"]).sum(axis=0)
    assert ev_per_dom[:4].sum() > ev_per_dom[4:].sum()
    assert ct_per_dom[:4].sum() > ct_per_dom[4:].sum()


# ---------------------------------------------------------------------------
# campaigns are exact
# ---------------------------------------------------------------------------

def test_campaign_kill_is_exact_event():
    """No stochastic shocks: the kill fires at exactly t=400 and strikes
    exactly the 10 servers of rack 2, every replica, every seed."""
    p = SCENARIO.replace(fault_domains=FaultTopology(n_racks=4,
                                                     racks_per_pod=2))
    members = p.fault_domains.domain_members(
        2, p.working_pool_size + p.spare_pool_size)
    for seed in (1, 9):
        sim = ClusterSimulation(p, seed=seed)
        tracer = Tracer()
        tracer.attach(sim)
        r = sim.run()
        assert r.n_domain_shocks == 0
        assert r.n_campaign_events == 3
        assert r.n_shock_killed == len(members) == 10
        kills = [e for e in tracer.events if e.kind == "kill"]
        assert [e.time for e in kills] == [400.0]
        assert kills[0].detail == "domain=2 members=10"
        starts = [e for e in tracer.events if e.kind == "maint_start"]
        ends = [e for e in tracer.events if e.kind == "maint_end"]
        assert [e.time for e in starts] == [900.0]
        assert [e.time for e in ends] == [1200.0]


def test_campaign_kill_is_exact_ctmc():
    """Schedule counts are exact per replica; the *kill size* is exact
    only in expectation — the CTMC strikes ``fraction x count`` per
    compartment with systematic rounding, and per-replica occupancies
    need not divide evenly at t=400."""
    p = SCENARIO.replace(fault_domains=FaultTopology(n_racks=4,
                                                     racks_per_pod=2))
    out = simulate_ctmc(p, n_replicas=256, seed=2)
    np.testing.assert_array_equal(out["n_campaign_events"], 3.0)
    np.testing.assert_array_equal(out["n_domain_shocks"], 0.0)
    killed = np.asarray(out["n_shock_killed"], float)
    assert np.all((killed >= 7) & (killed <= 13))
    assert abs(killed.mean() - 10.0) < 0.3


def test_maintenance_pauses_repairs_resume_with_remaining():
    """Deterministic repairs: a repair in flight when the window opens
    finishes exactly ``window length`` later than it would have."""
    window = CampaignEvent(time=60.0, kind="maintenance", duration=500.0)
    p = BASE.replace(
        job_size=8, working_pool_size=12, spare_pool_size=4,
        warm_standbys=0, job_length=2000.0,
        random_failure_rate=2e-3, systematic_failure_rate=0.0,
        automated_repair_probability=1.0,
        auto_repair_failure_probability=0.0,
        manual_repair_failure_probability=0.0,
        repair_distribution="deterministic",
        auto_repair_time=100.0,
        campaign=Campaign(events=(window,)))
    sim = ClusterSimulation(p, seed=4)
    tracer = Tracer()
    tracer.attach(sim)
    sim.run()
    starts: dict = {}
    for e in tracer.events:
        if e.kind == "repair_start":
            starts.setdefault(e.server, []).append(e.time)
    dones = [(e.server, e.time) for e in tracer.events
             if e.kind == "repair_done"]
    assert dones, "need at least one completed repair"
    w0, w1 = window.time, window.time + window.duration
    for sid, t_done in dones:
        t0 = starts[sid].pop(0)  # visits per server pair up in order
        expect = t0 + p.auto_repair_time
        if t0 < w1 and expect > w0:        # overlaps the window: paused
            expect += w1 - max(t0, w0) if t0 >= w0 else window.duration
        # no repair may complete strictly inside the window
        assert not (w0 < t_done < w1), (sid, t_done)
        assert t_done == pytest.approx(expect, abs=1e-6), (sid, t0, t_done)


# ---------------------------------------------------------------------------
# traced shock rates: one compiled program per grid
# ---------------------------------------------------------------------------

def test_shock_rate_grid_compiles_once():
    from repro.core import vectorized

    base = SCENARIO.replace(job_length=500.0,
                            max_run_records=17)   # module-unique shape
    grid = [base.replace(fault_domains=FaultTopology(
                n_racks=4, racks_per_pod=2, rack_shock_rate=r,
                pod_shock_rate=3e-5))
            for r in (5e-5, 1.2e-4, 4e-4)]
    c0 = vectorized.compile_cache_size()
    out = simulate_ctmc_sweep(grid, n_replicas=96, seed=0, max_steps=2048)
    c1 = vectorized.compile_cache_size()
    assert c1 - c0 == 1, "a shock-rate grid must share one program"
    shocks = [r["n_domain_shocks"].mean() for r in out]
    assert shocks[0] < shocks[1] < shocks[2], shocks


def test_sweep_axis_and_csv_columns(tmp_path):
    """``rack_shock_rate`` is a first-class sweep axis and the scenario /
    truncation telemetry lands in the sweep table."""
    sweep = OneWaySweep("shock", "rack_shock_rate", [0.0, 4e-4],
                        n_replications=8,
                        base_params=SCENARIO.replace(job_length=500.0,
                                                     campaign=None),
                        engine="event")
    res = sweep.run()
    rows = res.to_rows()
    assert rows[0]["n_domain_shocks"] <= rows[1]["n_domain_shocks"]
    assert all("n_incomplete" in row for row in rows)
    path = tmp_path / "shock.csv"
    res.write_csv(str(path))
    header = path.read_text().splitlines()[0]
    assert "n_domain_shocks" in header and "n_incomplete" in header
    with pytest.raises(ValueError, match="requires Params.fault_domains"):
        OneWaySweep("bad", "rack_shock_rate", [1e-4], n_replications=1,
                    base_params=BASE).run()


# ---------------------------------------------------------------------------
# event-only: scenarios + non-exponential repairs (rebreak redraws)
# ---------------------------------------------------------------------------

def test_scenario_with_weibull_repairs_event_only():
    p = SCENARIO.replace(job_length=1500.0,
                         repair_distribution="weibull",
                         distribution_kwargs={"repair_k": 1.5})
    reps = run_replications(p, 6, engine="auto", base_seed=11)
    assert reps.engine == "event"
    assert reps.stats["n_campaign_events"].mean == 3.0
    assert reps.stats["n_shock_killed"].mean >= 10.0  # the scripted kill
    assert all(r.total_time < p.max_sim_time for r in reps.results)


# ---------------------------------------------------------------------------
# truncation telemetry (n_incomplete)
# ---------------------------------------------------------------------------

def test_n_incomplete_event_engine():
    p = BASE.replace(max_sim_time=100.0)  # job cannot finish in time
    r = simulate_one(p, seed=0)
    assert r.timed_out and r.n_incomplete == 1
    assert r.to_dict()["n_incomplete"] == 1
    stats = aggregate([r, simulate_one(BASE, seed=0)])
    assert stats["n_incomplete"].mean == pytest.approx(0.5)


def test_n_incomplete_ctmc_arrays():
    out = simulate_ctmc(BASE, n_replicas=16, seed=0, max_steps=8)
    from repro.core.metrics import aggregate_arrays
    stats = aggregate_arrays(out)
    assert stats["n_incomplete"].mean == pytest.approx(
        1.0 - float(out["completed"].mean()))
    assert stats["n_incomplete"].mean > 0.0
