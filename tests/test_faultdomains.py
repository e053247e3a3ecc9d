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
  * the race holds one lane per level of domains: the struck domain is
    uniform within its level, its membership and fleet fraction follow
    from its index in closed form, and the chunk loop's once-a-chunk
    add of the struck domains equals adding every step;
  * scenarios combined with non-exponential repairs stay on the event
    oracle (``supports()`` gating), where struck in-shop servers are
    re-broken by redrawing the stage.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Campaign, CampaignEvent, FaultTopology, OneWaySweep,
                        Params, Tracer, faultdomains,
                        resolve_engine, run_replications, simulate,
                        simulate_one)
from repro.core import vectorized as vz
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
#: many racks: 64 racks in 4 pods of 16, each rack 2 working-pool
#: servers and 1 spare (both pools stripe evenly, as in TOPO), and a
#: scripted kill of pod 2 (domain 66) mid-run
MANY_RACKS = Params(job_size=112, working_pool_size=128, spare_pool_size=64,
                    warm_standbys=4, job_length=3000.0,
                    random_failure_rate=1e-4, systematic_failure_rate=5e-4,
                    recovery_time=10.0, seed=5,
                    fault_domains=FaultTopology(
                        n_racks=64, racks_per_pod=16,
                        rack_shock_rate=1.5e-5, pod_shock_rate=4e-5),
                    campaign=Campaign(events=(
                        CampaignEvent(time=1200.0, kind="kill",
                                      domain=66),)))


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

def _both_engines(p):
    out = simulate_ctmc(p, n_replicas=N_CTMC, seed=6)
    assert out["completed"].mean() > 0.99, "CTMC replicas did not finish"
    res = simulate(p, N_EVENT, base_seed=5)
    return out, res


@pytest.fixture(scope="module")
def scenario_runs():
    return _both_engines(SCENARIO)


@pytest.fixture(scope="module")
def many_rack_runs():
    return _both_engines(MANY_RACKS)


@pytest.mark.parametrize("runs", ["scenario_runs", "many_rack_runs"],
                         ids=["rack_pod_campaign", "many_racks"])
def test_scenario_matches_event_oracle(runs, request):
    """Shocks + mid-run domain kill (+ a maintenance window on the small
    topology): metric means agree across engines within sampling error."""
    out, res = request.getfixturevalue(runs)
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


# ---------------------------------------------------------------------------
# one race lane per level: closed-form membership, uniform pick, flush
# ---------------------------------------------------------------------------

def _members_by_scan(topo, domain, total):
    """The definition by a scan of the whole fleet: server ``s`` is in
    rack ``s % n_racks`` and in that rack's pod."""
    if domain < topo.n_racks:
        return [s for s in range(total) if s % topo.n_racks == domain]
    pod = domain - topo.n_racks
    return [s for s in range(total)
            if (s % topo.n_racks) // topo.racks_per_pod == pod]


@pytest.mark.parametrize("n_racks,racks_per_pod,total", [
    (4, 2, 40),          # the test topology: 10 servers a rack
    (5, 2, 12),          # 5 racks do not divide 12; a last pod of 1 rack
    (7, 3, 50),
    (64, 16, 128),
    (10, 4, 10),         # one server a rack
    (3, 0, 9),           # no pod level
    (1536, 192, 2976),   # the Llama 3 cluster at its 2080-server pool
])
def test_closed_form_membership_equals_a_scan(n_racks, racks_per_pod,
                                              total):
    topo = FaultTopology(n_racks=n_racks, racks_per_pod=racks_per_pod,
                         rack_shock_rate=1e-5,
                         pod_shock_rate=2e-5 if racks_per_pod else 0.0)
    want = [_members_by_scan(topo, d, total)
            for d in range(topo.n_domains)]
    assert [topo.domain_members(d, total)
            for d in range(topo.n_domains)] == want
    sizes = [len(m) for m in want]
    assert [topo.domain_size(d, total)
            for d in range(topo.n_domains)] == sizes
    np.testing.assert_array_equal(
        topo.domain_fractions(total),
        np.asarray(sizes, np.float64) / total)
    # the compiled step's form of the same arithmetic
    got = faultdomains.domain_sizes(
        jnp.arange(topo.n_domains), jnp.int32(total), n_racks,
        jnp.int32(racks_per_pod), xp=jnp)
    np.testing.assert_array_equal(np.asarray(got), sizes)
    # the step budget's kill sizes: the rate-weighted mean of the scan
    p = Params(job_size=min(8, total), working_pool_size=total - 1,
               spare_pool_size=1, warm_standbys=0, fault_domains=topo,
               campaign=Campaign(events=(CampaignEvent(
                   time=5.0, kind="kill", domain=topo.n_domains - 1),)))
    rates = topo.domain_rates()
    lam = rates.sum()
    n_shocks = lam * 1000.0
    want_steps = (n_shocks * (2.0 + 4.0 * (rates * sizes).sum() / lam)
                  + 2.0 + 4.0 * sizes[-1])
    steps, _ = faultdomains.scenario_budget(p, 1000.0)
    assert steps == pytest.approx(want_steps, rel=1e-12)


def test_race_takes_one_lane_per_level():
    """The scenario's columns are a few per level, not two per domain,
    and the race sees K_EXP + 2 exponential lanes for racks and pods."""
    p = MANY_RACKS
    assert faultdomains.scenario_key(p) == (64, 4, (faultdomains.KILL,))
    n_base = len(vz._params_vector(MANY_RACKS.replace(
        fault_domains=None, campaign=None)))
    assert len(vz._params_vector(p)) == n_base + 4 + 3
    seen = []
    real = vz.ops.event_race

    def spy(rates, residuals, *a, **k):
        seen.append(rates.shape[1])
        return real(rates, residuals, *a, **k)

    import unittest.mock
    with unittest.mock.patch.object(vz.ops, "event_race", spy):
        jax.eval_shape(lambda s: vz._step(
            s, jax.random.PRNGKey(0), vz._params_vector(p), None,
            scen=faultdomains.scenario_key(p)),
            vz._initial_state(p, 8, None))
    assert seen == [vz.K_EXP + 2]


def test_struck_domain_is_uniform_within_its_level():
    """Hundreds of racks: the shock counts per rack, and per pod, over
    many replicas fit a uniform law (chi-square), and the levels split
    the shocks by their total rates."""
    from scipy import stats

    topo = FaultTopology(n_racks=300, racks_per_pod=10,
                         rack_shock_rate=1.5e-5, pod_shock_rate=5e-5)
    p = Params(job_size=16, working_pool_size=320, spare_pool_size=280,
               warm_standbys=2, job_length=2000.0, fault_domains=topo,
               histogram=None)
    out = simulate_ctmc(p, n_replicas=4096, seed=11)
    assert out["completed"].mean() == 1.0
    per = np.asarray(out["domain_shocks"]).sum(0)
    racks, pods = per[:300], per[300:]
    assert racks.sum() > 20000 and pods.sum() > 5000
    assert stats.chisquare(racks).pvalue > 1e-3
    assert stats.chisquare(pods).pvalue > 1e-3
    # rack level 300 x 1.5e-5 against pod level 30 x 5e-5: 3 to 1
    share = racks.sum() / per.sum()
    assert abs(share - 0.75) < 4 * np.sqrt(0.75 * 0.25 / per.sum())


def test_shock_flush_equals_adding_every_record():
    """Rows with none, a few, and more shocks than one pass places."""
    K, B, D = 64, 40, 37
    rng = np.random.default_rng(4)
    density = rng.choice([0.0, 0.05, 0.3, 0.9], size=B)
    recs = np.where(rng.random((K, B)) < density, rng.integers(0, D, (K, B)),
                    D).astype(np.int32)
    assert (recs < D).sum(0).max() > 2 * vz.SHOCK_SLOTS
    counts0 = rng.integers(0, 5, (B, D)).astype(np.float32)
    want = counts0.copy()
    k, b = np.nonzero(recs < D)
    np.add.at(want, (b, recs[k, b]), 1.0)
    got = vz._shock_flush(jnp.asarray(counts0), jnp.asarray(recs))
    np.testing.assert_array_equal(np.asarray(got), want)
    none = np.full((K, B), D, np.int32)
    np.testing.assert_array_equal(
        np.asarray(vz._shock_flush(jnp.asarray(counts0), jnp.asarray(none))),
        counts0)


def _adding_every_step(step):
    """``step`` made to add its struck domain at once: the counts ride in
    the scan carry as ``shocks_now``, and the chunk loop's own record is
    reset to the sentinel, so its flush adds nothing."""
    def immediate(s, u, *args, **kw):
        inner = {k: v for k, v in s.items() if k != "shocks_now"}
        ns = step(inner, u, *args, **kw)
        D = s["shocks_now"].shape[1]
        now = s["shocks_now"] + vz.row_hit(ns["shock_dom"], D)
        return dict(ns, shocks_now=now.astype(jnp.float32),
                    shock_dom=jnp.full_like(ns["shock_dom"], D))
    return immediate


@pytest.mark.parametrize("early_exit", [True, False],
                         ids=["early_exit", "full_budget"])
def test_deferred_shock_add_is_bit_identical_to_adding_every_step(
        early_exit, monkeypatch):
    p, R = MANY_RACKS.replace(
        fault_domains=FaultTopology(n_racks=64, racks_per_pod=16,
                                    rack_shock_rate=4e-4,
                                    pod_shock_rate=4e-4)), 12
    chunk = vz.DEFAULT_CHUNK_STEPS
    budget = 6 * chunk + 9                         # a remainder chunk
    init = vz._initial_state(p, R, None)
    args = (vz._params_vector(p), jax.random.PRNGKey(7), 1, R, chunk,
            np.int32(budget // chunk), budget % chunk, early_exit,
            vz._single_job_step(p))

    def run(state):
        # a fresh jit each time: the patched step is traced anew
        return jax.jit(lambda st: vz._chunk_loop(*args, st))(state)

    deferred = run(init)
    monkeypatch.setattr(vz, "_step_u", _adding_every_step(vz._step_u))
    at_once = run(dict(init, shocks_now=init["domain_shocks"]))
    np.testing.assert_array_equal(at_once.pop("domain_shocks"),
                                  init["domain_shocks"])
    at_once["domain_shocks"] = at_once.pop("shocks_now")
    assert set(deferred) == set(at_once)
    assert "shock_dom" not in deferred
    for k, v in at_once.items():
        got, want = np.asarray(deferred[k]), np.asarray(v)
        assert got.dtype == want.dtype and got.shape == want.shape, k
        assert got.tobytes() == want.tobytes(), k
    shocks = np.asarray(deferred["domain_shocks"])
    assert shocks.sum() > 0
    np.testing.assert_array_equal(shocks.sum(1), deferred["n_domain_shocks"])
