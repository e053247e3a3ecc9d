"""Cross-engine contention parity: multi-job CTMC vs the event oracle.

The multi-job compartment engine
(:mod:`repro.core.vectorized_multijob`) promotes the event-loop
``MultiJobSimulation`` semantics — J jobs sharing one spare pool and one
finite-server repair shop — onto the compiled fast path.  This suite
pins it against the event oracle:

  * 2-job and 4-job mixed-size clusters under spare-pool and
    repair-shop contention agree within |z| < 3.5 on per-job
    ETTF/recovery/waiting means and fleet/shop counters;
  * per-job distribution channels agree to one histogram bin;
  * conservation: servers across jobs + pools + shop sum to the fleet
    size at every recorded point on BOTH engines (the CTMC lane checks
    every scan step in-program; the event engine is stepped event by
    event and re-counted here);
  * reduction: a 1-job multi-job sweep is bit-identical to the
    single-job CTMC program and compiles nothing new; a J-job cluster
    with per-job standby headroom, a deep spare pool, and an unbounded
    shop factorizes into independent single-job runs;
  * regression (satellite): ``MultiJobResult`` surfaces the shared
    shop's counters and per-job recovery/waiting channels.

Documented approximations (see docs/multijob.md): ``n_host_selections``
and ``n_standby_swaps`` can drift beyond sampling error in *saturated*
regimes because the event engine's multi-set job membership has no
count-based twin; the metrics pinned here avoid relying on them.
"""

import math

import numpy as np
import pytest

from repro.core import (JobSpec, Params, aggregate_multijob_arrays,
                        pool_histograms, resolve_engine_multijob,
                        run_multijob_batch, run_replications_multijob,
                        simulate_multijob, simulate_multijob_ctmc_sweep,
                        supports_multijob)
from repro.core import vectorized as vz
from repro.core.multijob import MultiJobSimulation

Z_MAX = 3.5


def _z(a, b):
    a = np.asarray(a, float)
    b = np.asarray(b, float)
    se = math.sqrt(a.var(ddof=1) / len(a) + b.var(ddof=1) / len(b))
    return (a.mean() - b.mean()) / max(se, 1e-12)


def _z_hist(ha, hb):
    na, nb = ha.total, hb.total
    if na < 2 or nb < 2:
        return 0.0
    se = math.sqrt(ha.std() ** 2 / na + hb.std() ** 2 / nb)
    return (ha.mean() - hb.mean()) / max(se, 1e-12)


def _cdf_at(h, value):
    """Fraction of a histogram's mass in bins strictly below ``value``."""
    idx = int(np.searchsorted(np.asarray(h.edges, float), value,
                              side="right"))
    cum = np.cumsum(np.asarray(h.counts, float))
    below = cum[idx - 1] if idx > 0 else 0.0
    return below / max(h.total, 1)


def _assert_one_bin(ha, hb, what, qs=(50, 90)):
    """Percentiles agree to one bin of the shared log-binned layout.

    Bimodal channels (waiting: a zero-wait standby mode and a
    host-selection mode with empty bins between) can put a percentile on
    a knife edge where a <1% mass shift jumps many *empty* bins; there
    the criterion is the CDF form — the other engine assigns nearly the
    same cumulative mass at that percentile value.
    """
    edges = np.asarray(ha.edges, float)
    for q in qs:
        va, vb = ha.percentile(q), hb.percentile(q)
        ia = int(np.searchsorted(edges, va, side="right"))
        ib = int(np.searchsorted(edges, vb, side="right"))
        cdf_gap = abs(_cdf_at(hb, va) - q / 100.0)
        assert abs(ia - ib) <= 1 or cdf_gap <= 0.05, (
            f"{what} p{q}: bins {ia} vs {ib} ({va:.3f} vs {vb:.3f}), "
            f"cdf gap {cdf_gap:.3f}")


# Moderate contention: the shop queues and the spare pool runs dry
# sometimes, but the cluster is not saturated (where the event engine's
# multi-set membership approximation dominates host-selection counts).
TWO_JOB_CLUSTER = Params(
    working_pool_size=110, spare_pool_size=16, job_size=16,
    job_length=4000.0, random_failure_rate=0.001,
    systematic_failure_rate=0.005, auto_repair_time=180.0,
    manual_repair_time=480.0, repair_servers=6)
TWO_JOBS = (JobSpec(32, 4000.0, warm_standbys=2),
            JobSpec(16, 6000.0, warm_standbys=1))

FOUR_JOB_CLUSTER = Params(
    working_pool_size=110, spare_pool_size=12, job_size=16,
    job_length=3000.0, random_failure_rate=0.001,
    systematic_failure_rate=0.005, auto_repair_time=150.0,
    manual_repair_time=420.0, repair_servers=5)
FOUR_JOBS = (JobSpec(24, 3000.0, warm_standbys=2),
             JobSpec(16, 4000.0, warm_standbys=1),
             JobSpec(12, 3500.0, warm_standbys=1),
             JobSpec(8, 5000.0, warm_standbys=1))

_PINNED_JOB_METRICS = ("total_time", "n_failures", "stall_time",
                       "n_preemptions", "recovery_overhead")
_PINNED_FLEET_METRICS = ("makespan", "stall_handoffs", "n_auto_repairs",
                         "n_manual_repairs", "n_shop_queued")


def _parity_case(cluster, jobs, n_ctmc, n_event, seed):
    assert resolve_engine_multijob(cluster, jobs) == "ctmc"
    point = simulate_multijob_ctmc_sweep([(cluster, jobs)],
                                         n_replicas=n_ctmc, seed=seed)[0]
    agg = aggregate_multijob_arrays(point)
    results = simulate_multijob(cluster, list(jobs),
                                n_replications=n_event,
                                base_seed=seed + 1)

    # the contention machinery must actually be exercised on both sides
    assert float(np.mean(point["n_shop_queued"])) > 0
    assert np.mean([r.queue_events for r in results]) > 0
    assert float(np.max(point["conservation_err"])) == 0.0

    spec = cluster.histogram
    for j in range(len(jobs)):
        cj = point["per_job"][j]
        for metric in _PINNED_JOB_METRICS:
            ev = [float(getattr(r.per_job[j], metric)) for r in results]
            z = _z(cj[metric], ev)
            assert abs(z) < Z_MAX, f"job{j} {metric}: z={z:+.2f}"
        ct_hists = agg["per_job_histograms"][j]
        ev_hists = pool_histograms(
            [r.per_job_histograms(spec)[j] for r in results])
        for ch in ("run_duration", "recovery", "waiting"):
            z = _z_hist(ct_hists[ch], ev_hists[ch])
            assert abs(z) < Z_MAX, f"job{j} {ch} mean: z={z:+.2f}"
            _assert_one_bin(ct_hists[ch], ev_hists[ch], f"job{j} {ch}")

    fleet_event = {
        "makespan": [r.makespan for r in results],
        "stall_handoffs": [float(r.stall_events) for r in results],
        "n_auto_repairs": [float(r.cluster.n_auto_repairs)
                           for r in results],
        "n_manual_repairs": [float(r.cluster.n_manual_repairs)
                             for r in results],
        "n_shop_queued": [float(r.queue_events) for r in results],
    }
    for metric in _PINNED_FLEET_METRICS:
        z = _z(point[metric], fleet_event[metric])
        assert abs(z) < Z_MAX, f"fleet {metric}: z={z:+.2f}"


def test_two_job_contention_parity():
    _parity_case(TWO_JOB_CLUSTER, TWO_JOBS, n_ctmc=1024, n_event=96,
                 seed=17)


def test_four_job_contention_parity():
    _parity_case(FOUR_JOB_CLUSTER, FOUR_JOBS, n_ctmc=1024, n_event=80,
                 seed=29)


def test_backend_multijob_replications_structure():
    rep = run_replications_multijob(TWO_JOB_CLUSTER, TWO_JOBS, n=64,
                                    engine="auto", base_seed=11)
    assert rep.engine == "ctmc"
    assert len(rep.per_job) == len(TWO_JOBS)
    assert rep.fleet["makespan"].mean > 0
    assert rep.fleet["conservation_err"].maximum == 0.0
    assert set(rep.histograms) >= {"run_duration", "recovery", "waiting"}
    for jr in rep.per_job:
        assert jr.stats["total_time"].mean > 0


# ---------------------------------------------------------------------------
# conservation at every recorded point
# ---------------------------------------------------------------------------

def test_ctmc_conservation_every_step():
    """The in-scan invariant lane records the max per-step deviation of
    sum(job blocks) + pools + shop from the fleet size — exactly zero."""
    for cluster, jobs in ((TWO_JOB_CLUSTER, TWO_JOBS),
                          (FOUR_JOB_CLUSTER, FOUR_JOBS)):
        out = simulate_multijob_ctmc_sweep([(cluster, jobs)],
                                           n_replicas=256, seed=5)[0]
        assert float(np.max(out["conservation_err"])) == 0.0


def _accounted_sids(sim):
    """Every server, exactly once: pools, shop, job blocks, hand-offs."""
    sids = []
    pools, shop = sim.pools, sim.repair_shop
    sids += [s.sid for s in pools.working_free]
    sids += [s.sid for s in pools.spare_free]
    sids += [s.sid for s in pools.retired]
    sids += [s.sid for s in shop.in_repair]
    for coord in sim.coordinators:
        sids += [s.sid for s in coord.running_good + coord.running_bad]
        sched = coord.scheduler
        sids += [s.sid for s in sched.standbys]
        if sched._inflight is not None:
            sids.append(sched._inflight.sid)
        if (sched._stall_event is not None and sched._stall_event.triggered
                and sched._stall_server is not None):
            sids.append(sched._stall_server.sid)
    return sids


def test_event_conservation_every_step():
    """Step the event simulation one event at a time and re-count: job
    blocks, both pools, the shop (service + queue), and in-flight
    hand-offs partition the fleet at every event boundary."""
    sim = MultiJobSimulation(TWO_JOB_CLUSTER, list(TWO_JOBS), seed=23)
    total = (TWO_JOB_CLUSTER.working_pool_size
             + TWO_JOB_CLUSTER.spare_pool_size)
    procs = [sim.env.process(sim._run_job(i, spec), name=f"job{i}")
             for i, spec in enumerate(sim.jobs)]
    checked = 0
    while any(p.is_alive for p in procs):
        sim.env.step()
        sids = _accounted_sids(sim)
        assert sorted(sids) == list(range(total)), (
            f"conservation broke at t={sim.env.now:.2f}: "
            f"{len(sids)} accounted ({len(set(sids))} unique) of {total}")
        checked += 1
    assert checked > 500  # the walk actually covered a contended run
    assert sim.repair_shop.n_queued_events > 0


# ---------------------------------------------------------------------------
# reduction: 1 job == the single-job program; infinite pool factorizes
# ---------------------------------------------------------------------------

def test_one_job_reduction_bit_identical_and_no_new_compiles():
    single = Params(working_pool_size=40, spare_pool_size=6, job_size=24,
                    job_length=2000.0, random_failure_rate=0.002,
                    systematic_failure_rate=0.01,
                    auto_repair_time=120.0, manual_repair_time=300.0)
    spec = JobSpec(24, 2000.0, warm_standbys=2)
    sj = single.replace(warm_standbys=2)
    ref = vz.simulate_ctmc_sweep([sj], n_replicas=64, seed=13)[0]

    c_sj = vz.compile_cache_size()
    out = simulate_multijob_ctmc_sweep([(single, (spec,))],
                                       n_replicas=64, seed=13)[0]
    # one driver for both engines: the 1-job sweep reuses the warm
    # single-job program and never builds a multi-job one
    assert vz.compile_cache_size() == c_sj

    assert len(out["per_job"]) == 1
    arrays = out["per_job"][0]
    for k in ref:
        np.testing.assert_array_equal(
            np.asarray(arrays[k]), np.asarray(ref[k]),
            err_msg=f"1-job reduction differs on {k}")
    np.testing.assert_array_equal(np.asarray(out["makespan"]),
                                  np.asarray(ref["total_time"]))
    assert float(np.max(out["conservation_err"])) == 0.0
    assert float(np.max(out["n_shop_queued"])) == 0.0


def test_mixed_size_capacity_grid_compiles_once():
    """A mixed-size capacity grid — spare pool x repair servers, jobs of
    different sizes and lengths — is one compiled program (J is the only
    static key), and a second grid in the same bucket reuses it."""
    day = 1440.0
    cluster = Params(job_size=12, working_pool_size=40, spare_pool_size=4,
                     job_length=0.1 * day, random_failure_rate=2.0 / day,
                     recovery_time=5.0, auto_repair_time=30.0,
                     manual_repair_time=60.0, repair_servers=2, seed=0,
                     max_run_records=13)   # shapes no other test compiles
    jobs = (JobSpec(12, 0.1 * day, warm_standbys=1),
            JobSpec(8, 0.15 * day, warm_standbys=1))
    grid = [(cluster.replace(spare_pool_size=s, repair_servers=r), jobs)
            for s in (3, 4) for r in (2, 3)]
    c0 = vz.compile_cache_size()
    reps = run_multijob_batch(grid, 24, engine="ctmc", base_seed=0)
    assert vz.compile_cache_size() - c0 == 1
    assert [r.engine for r in reps] == ["ctmc"] * len(grid)
    assert all(r.fleet["completed"].mean == 1.0 for r in reps)
    run_multijob_batch(grid[:3], 20, engine="ctmc", base_seed=1)
    assert vz.compile_cache_size() - c0 == 1


def test_infinite_pool_and_shop_factorizes():
    """With per-job standby headroom, a deep spare pool, and an
    unbounded shop, jobs never contend: each job's marginals match an
    independent single-job run within |z| < 3.5."""
    cluster = Params(working_pool_size=220, spare_pool_size=150,
                     job_size=16, job_length=2000.0,
                     random_failure_rate=0.0015,
                     systematic_failure_rate=0.008,
                     recovery_time=10.0, auto_repair_time=120.0,
                     manual_repair_time=300.0, repair_servers=0)
    jobs = (JobSpec(24, 2000.0, warm_standbys=12),
            JobSpec(12, 3000.0, warm_standbys=12))
    out = simulate_multijob_ctmc_sweep([(cluster, jobs)],
                                       n_replicas=1024, seed=7)[0]
    assert float(np.max(out["conservation_err"])) == 0.0
    for j, spec in enumerate(jobs):
        solo = cluster.replace(job_size=spec.job_size,
                               job_length=spec.job_length,
                               warm_standbys=spec.warm_standbys)
        ref = vz.simulate_ctmc_sweep([solo], n_replicas=1024,
                                     seed=101 + j)[0]
        for metric in ("total_time", "n_failures", "stall_time"):
            z = _z(out["per_job"][j][metric], ref[metric])
            assert abs(z) < Z_MAX, f"job{j} {metric}: z={z:+.2f}"


# ---------------------------------------------------------------------------
# satellite regression: MultiJobResult surfaces shop + per-job channels
# ---------------------------------------------------------------------------

def test_multijob_result_surfaces_cluster_and_histograms():
    """The shared shop's repair counters historically vanished (written
    to a RunResult nobody kept) and per-job recovery/waiting channels
    had no accessor — the CTMC parity suite needs both as its oracle."""
    res = simulate_multijob(TWO_JOB_CLUSTER, list(TWO_JOBS),
                            n_replications=3, base_seed=41)
    spec = TWO_JOB_CLUSTER.histogram
    for r in res:
        assert r.cluster.n_auto_repairs > 0
        assert r.cluster.n_auto_repairs + r.cluster.n_manual_repairs > 0
        hists = r.per_job_histograms(spec)
        assert len(hists) == len(TWO_JOBS)
        for j, hd in enumerate(hists):
            rj = r.per_job[j]
            assert hd["recovery"].total == len(rj.recovery_durations)
            assert hd["waiting"].total == len(rj.waiting_durations)
            assert hd["run_duration"].total == len(rj.run_durations)
    assert any(r.queue_events > 0 for r in res)


def test_supports_multijob_gates():
    ok = TWO_JOB_CLUSTER
    assert supports_multijob(ok, TWO_JOBS)
    assert not supports_multijob(
        ok.replace(failure_distribution="weibull"), TWO_JOBS)
    assert not supports_multijob(
        ok.replace(checkpoint_interval=100.0), TWO_JOBS)
    assert not supports_multijob(
        ok, (JobSpec(8, 100.0, 0, start_time=5.0),))
    with pytest.raises(ValueError):
        resolve_engine_multijob(ok.replace(checkpoint_interval=100.0),
                                TWO_JOBS, engine="ctmc")
