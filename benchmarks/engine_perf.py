"""Engine performance benchmarks (beyond-paper §Perf support).

Measures:
  * event-driven engine throughput (events/s) — the paper's SimPy-class
    baseline, reimplemented;
  * vectorized CTMC engine throughput (replica-events/s) and its speedup —
    the TPU-shaped redesign (here timed on CPU; the same program
    compiles for TPU where the event_race Pallas kernel engages);
  * the event_race kernel microbenchmark (ref path on CPU).
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (MINUTES_PER_DAY, Campaign, CampaignEvent,
                        FaultTopology, OneWaySweep, Params, simulate)
from repro.core.vectorized import default_max_steps, simulate_ctmc
from repro.kernels import ops


def bench_params() -> Params:
    return Params(job_size=512, working_pool_size=560, spare_pool_size=64,
                  warm_standbys=16, job_length=8 * MINUTES_PER_DAY,
                  random_failure_rate=0.5 / MINUTES_PER_DAY, seed=0)


def event_engine_throughput(n_runs: int = 5) -> Dict[str, float]:
    p = bench_params()
    from repro.core.simulation import ClusterSimulation
    # warm-up one run (numpy rng setup etc.)
    ClusterSimulation(p, seed=99).run()
    t0 = time.perf_counter()
    events = 0
    for rep in range(n_runs):
        sim = ClusterSimulation(p, seed=rep)
        sim.run()
        events += sim.env.event_count
    dt = time.perf_counter() - t0
    return {"events_per_s": events / dt, "runs_per_s": n_runs / dt,
            "events_per_run": events / n_runs, "wall_s": dt}


def ctmc_engine_throughput(n_replicas: int = 2048) -> Dict[str, float]:
    p = bench_params()
    max_steps = default_max_steps(p)
    # compile
    simulate_ctmc(p, n_replicas=n_replicas, seed=0, max_steps=max_steps)
    t0 = time.perf_counter()
    simulate_ctmc(p, n_replicas=n_replicas, seed=1, max_steps=max_steps)
    dt = time.perf_counter() - t0
    return {"replicas_per_s": n_replicas / dt, "steps": max_steps,
            "wall_s": dt}


def event_race_kernel(R: int = 65536, iters: int = 20) -> Dict[str, float]:
    rng = np.random.default_rng(0)
    rates = jnp.asarray(rng.uniform(0, 1, (R, 16)).astype(np.float32))
    resid = jnp.asarray(rng.uniform(0.1, 5, (R, 2)).astype(np.float32))
    ut = jnp.asarray(rng.uniform(1e-6, 1, R).astype(np.float32))
    up = jnp.asarray(rng.uniform(0, 1, R).astype(np.float32))
    f = jax.jit(lambda *a: ops.event_race(*a))
    f(rates, resid, ut, up)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        dt_out, _ = f(rates, resid, ut, up)
    dt_out.block_until_ready()
    dt = time.perf_counter() - t0
    return {"races_per_s": R * iters / dt,
            "us_per_call": dt / iters * 1e6}


def sweep_bench_params() -> Params:
    """Mid-size cluster: large enough that the event engine does real
    per-event work, small enough that the full event-side grid finishes
    in tens of seconds."""
    return Params(job_size=512, working_pool_size=560, spare_pool_size=64,
                  warm_standbys=16, job_length=2 * MINUTES_PER_DAY,
                  random_failure_rate=0.25 / MINUTES_PER_DAY, seed=0)


def _agreement_points(ct_points, ev_points, key: str) -> list:
    """Per-point CTMC-vs-event agreement of total_time means, in
    pooled-standard-error units."""
    points = []
    for pc, pe in zip(ct_points, ev_points):
        sc, se_ = pc.stats["total_time"], pe.stats["total_time"]
        pooled_se = np.sqrt(sc.std ** 2 / pc.n_replications
                            + se_.std ** 2 / pe.n_replications)
        points.append({
            key: pc.values[key],
            "ctmc_total_time_mean": sc.mean,
            "event_total_time_mean": se_.mean,
            "pooled_se": float(pooled_se),
            "z": float((sc.mean - se_.mean) / max(pooled_se, 1e-9)),
        })
    return points


def _engine_ab_sweep(base: Params, n_points: int, n_replicas: int,
                     title: str, parameter: str = "recovery_time",
                     values=None) -> Dict[str, object]:
    """Shared A/B protocol: one parameter grid through both engines.

    CTMC runs twice (cold = compile-inclusive, then warm), the event
    engine once; reports wall clock, speedups, and per-point agreement
    of the ``total_time`` means in pooled-standard-error units.  Every
    engine-vs-engine sweep benchmark wraps this so the timing and
    agreement conventions cannot drift apart.  ``parameter`` defaults to
    the recovery-time grid; the repair benchmark sweeps a repair knob
    instead.
    """
    if values is None:
        values = [float(v) for v in np.linspace(5.0, 40.0, n_points)]
    kw = dict(n_replications=n_replicas, base_params=base, base_seed=0)

    ctmc_sweep = OneWaySweep(title, parameter, values, engine="ctmc", **kw)
    t0 = time.perf_counter()
    ct = ctmc_sweep.run()
    compile_s = time.perf_counter() - t0   # includes one-off XLA compile
    t0 = time.perf_counter()
    ct = ctmc_sweep.run()
    ctmc_s = time.perf_counter() - t0

    event_sweep = OneWaySweep(title, parameter, values, engine="event", **kw)
    t0 = time.perf_counter()
    ev = event_sweep.run()
    event_s = time.perf_counter() - t0

    points = _agreement_points(ct.points, ev.points, parameter)
    return {
        "n_points": n_points,
        "n_replicas": n_replicas,
        "event_wall_s": event_s,
        "ctmc_wall_s": ctmc_s,
        "ctmc_compile_wall_s": compile_s,
        "speedup_x": event_s / ctmc_s,
        "speedup_x_incl_compile": event_s / compile_s,
        "max_abs_z": max(abs(p["z"]) for p in points),
        "points": points,
    }


def sweep_throughput(n_points: int = 8, n_replicas: int = 256,
                     ) -> Dict[str, object]:
    """Grid-sweep wall clock: batched CTMC engine vs the event-driven
    loop, on the exponential baseline (see :func:`_engine_ab_sweep`)."""
    return _engine_ab_sweep(sweep_bench_params(), n_points, n_replicas,
                            "sweep-bench")


def structural_sweep_throughput(n_points: int = 8, n_replicas: int = 256,
                                ) -> Dict[str, object]:
    """Structural-grid wall clock: padded vs per-structure vs event engine.

    Sweeps ``job_size`` so every grid point is a *distinct pool
    structure*.  Before structure padding each point compiled its own XLA
    program; the padded path runs the whole grid as one flat batch with a
    single compilation.  Reports cold (compile-inclusive) and warm wall
    clock for both CTMC modes, the observed compile counts, the event
    engine baseline, and per-point padded-vs-event agreement in
    pooled-standard-error units.
    """
    from repro.core import vectorized
    from repro.core.vectorized import _struct_key

    # bench-unique ring-buffer size: gives this benchmark its own jit
    # cache entries, so the earlier sweep_throughput run (same padded
    # bucket otherwise) cannot turn the cold timing/compile count warm
    base = sweep_bench_params().replace(max_run_records=96)
    values = [384 + 16 * i for i in range(n_points)]
    kw = dict(n_replications=n_replicas, base_params=base, base_seed=0)
    grid = [base.replace(job_size=v) for v in values]
    assert len({_struct_key(p) for p in grid}) == n_points, \
        "benchmark grid must be fully structural"

    def timed_ctmc(padded):
        sw = OneWaySweep("structural-bench", "job_size", values,
                         engine="ctmc", padded=padded, **kw)
        c0 = vectorized.compile_cache_size()
        t0 = time.perf_counter()
        res = sw.run()
        cold = time.perf_counter() - t0
        c1 = vectorized.compile_cache_size()
        compiles = c1 - c0
        t0 = time.perf_counter()
        res = sw.run()
        warm = time.perf_counter() - t0
        return res, cold, warm, compiles

    ct, padded_cold_s, padded_warm_s, padded_compiles = timed_ctmc(True)
    _, per_struct_cold_s, per_struct_warm_s, per_struct_compiles = \
        timed_ctmc(False)

    t0 = time.perf_counter()
    ev = OneWaySweep("structural-bench", "job_size", values,
                     engine="event", **kw).run()
    event_s = time.perf_counter() - t0

    points = _agreement_points(ct.points, ev.points, "job_size")
    return {
        "n_points": n_points,
        "n_replicas": n_replicas,
        "event_wall_s": event_s,
        "padded_wall_s": padded_cold_s,
        "padded_warm_wall_s": padded_warm_s,
        "padded_compiles": padded_compiles,
        "per_structure_wall_s": per_struct_cold_s,
        "per_structure_warm_wall_s": per_struct_warm_s,
        "per_structure_compiles": per_struct_compiles,
        "padded_vs_per_structure_x": per_struct_cold_s / padded_cold_s,
        "padded_vs_per_structure_warm_x": per_struct_warm_s / padded_warm_s,
        "padded_vs_event_x": event_s / padded_cold_s,
        "max_abs_z": max(abs(p["z"]) for p in points),
        "points": points,
    }


def weibull_sweep_throughput(n_points: int = 8, n_replicas: int = 256,
                             ) -> Dict[str, object]:
    """Non-exponential fast path: a Weibull grid vs the event engine.

    Before this path existed, any non-exponential study fell back to the
    one-trajectory event engine, whose generic sampler draws one Python-
    level sample *per running server per restart* — the 10-15x sweep
    gap the hazard fast path closes.  Runs the same ``n_points x
    n_replicas`` recovery-time sweep under a Weibull wear-out hazard
    (k=1.5) through both engines and reports wall clock, speedup, and
    per-point agreement in pooled-standard-error units.  The cluster is
    kept smaller than ``sweep_bench_params`` because the event side is
    O(cluster size) per restart here, not O(1).
    """
    base = Params(job_size=64, working_pool_size=72, spare_pool_size=8,
                  warm_standbys=4, job_length=1 * MINUTES_PER_DAY,
                  random_failure_rate=0.5 / MINUTES_PER_DAY,
                  failure_distribution="weibull",
                  distribution_kwargs={"k": 1.5},
                  seed=0, max_run_records=88)   # bench-unique jit shapes
    return {
        "failure_distribution": base.failure_distribution,
        "distribution_kwargs": dict(base.distribution_kwargs),
        **_engine_ab_sweep(base, n_points, n_replicas, "nonexp-bench"),
    }


def repair_bench_params() -> Params:
    """The repair-policy benchmark scenario, shared with the CI quick
    gate (scripts/check_bench.py) so the gate always measures the same
    scenario it compares against: lognormal failures (sigma 1.0, where
    the event engine pays O(cluster) Python-level draws per restart) +
    Weibull k=0.7 repairs through the slot lane, on a 128-server job."""
    return Params(job_size=128, working_pool_size=144, spare_pool_size=16,
                  warm_standbys=8, job_length=1 * MINUTES_PER_DAY,
                  random_failure_rate=0.5 / MINUTES_PER_DAY,
                  failure_distribution="lognormal",
                  repair_distribution="weibull",
                  distribution_kwargs={"k": 0.7, "sigma": 1.0},
                  manual_repair_time=480.0, seed=0)


def repair_sweep_throughput(n_points: int = 8, n_replicas: int = 256,
                            ) -> Dict[str, object]:
    """Repair-policy grid on the fast path: the realistic repair study.

    Before the repair-slot lane (and the lognormal mode-bound majorant)
    existed, ANY non-exponential repair or lognormal failure pushed the
    whole study onto the one-trajectory event engine — making realistic
    repair-policy sweeps the slowest scenarios supported: fleet studies
    measure heavy-tailed failure AND repair times, and the event
    engine's generic failure sampler is O(cluster) Python-level draws
    per restart.  Sweeps ``auto_repair_time`` under lognormal failures
    (sigma 1.0) with Weibull k=0.7 repairs through both engines
    (8 x 256 by default; the CTMC side's cost is cluster-size
    *independent* — compartment counts plus an occupancy-sized slot
    lane — while the event side scales with the 128-server job).
    Reports wall clock, warm speedup, and per-point agreement.  The
    acceptance floor for this entry is a >= 5x warm speedup
    (scripts/check_bench.py gates it).
    """
    base = repair_bench_params().replace(
        max_run_records=72)   # bench-unique jit shapes
    values = [float(v) for v in np.linspace(30.0, 240.0, n_points)]
    return {
        "failure_distribution": base.failure_distribution,
        "repair_distribution": base.repair_distribution,
        "distribution_kwargs": dict(base.distribution_kwargs),
        **_engine_ab_sweep(base, n_points, n_replicas, "repair-bench",
                           parameter="auto_repair_time", values=values),
    }


def empirical_bench_params() -> Params:
    """The trace-driven benchmark scenario, shared with the CI quick
    gate (scripts/check_bench.py) so the gate always measures the same
    scenario it compares against: a 64-server job under a fitted-style
    3-segment piecewise-constant hazard (elevated wear-in, a settling
    middle segment, a long flat tail — the canonical shape
    ``fit_piecewise_hazard`` recovers from fleet failure logs).  The
    *shape* kwargs are mean-rescaled against ``random_failure_rate``;
    the edges are chosen so the scaled breakpoints (~40 and ~200 min)
    sit inside the ages a restart-reset phase actually visits.  The
    event engine's generic sampler pays O(cluster) Python-level draws
    per restart here; the CTMC samples by segment-wise conditional
    inversion with an exact per-segment majorant."""
    return Params(job_size=64, working_pool_size=72, spare_pool_size=8,
                  warm_standbys=4, job_length=1 * MINUTES_PER_DAY,
                  random_failure_rate=0.5 / MINUTES_PER_DAY,
                  failure_distribution="empirical",
                  distribution_kwargs={"edges": [0.02, 0.1],
                                       "rates": [2.5, 1.0, 0.7]},
                  seed=0)


def empirical_sweep_throughput(n_points: int = 8, n_replicas: int = 256,
                               ) -> Dict[str, object]:
    """Trace-driven grid on the fast path: empirical hazards vs the
    event engine.

    Before the piecewise-constant sampler existed, every log-fitted
    hazard fell back to the one-trajectory event engine — the exact
    studies the simulator exists for (replaying a fleet's measured
    failure curve) were the slowest ones it supported.  Sweeps the
    recovery-time grid under the shared 3-segment fitted-style hazard
    through both engines.  The segment *count* is the only static
    compile key — edges and rates are traced columns — so the whole
    grid must compile exactly one XLA program (``sweep_compiles``);
    the acceptance floor for this entry is a >= 5x warm speedup
    (scripts/check_bench.py gates both).
    """
    from repro.core import vectorized

    base = empirical_bench_params().replace(
        max_run_records=81)   # bench-unique jit shapes
    c0 = vectorized.compile_cache_size()
    out = _engine_ab_sweep(base, n_points, n_replicas, "empirical-bench")
    c1 = vectorized.compile_cache_size()
    return {
        "failure_distribution": base.failure_distribution,
        "distribution_kwargs": dict(base.distribution_kwargs),
        "n_segments": len(base.distribution_kwargs["rates"]),
        "sweep_compiles": c1 - c0,
        **out,
    }


def correlated_bench_params(job_length: float = None) -> Params:
    """The correlated-failure benchmark scenario, shared with the CI
    quick gate (scripts/check_bench.py): a 256-server job under
    *lognormal* failure times (the realistic heavy-tailed hazard, where
    the event engine pays O(cluster) Python-level draws per restart and
    the CTMC samples by compiled conditional inversion) with a 16-rack /
    4-racks-per-pod topology — the 320-server fleet stripes to exactly
    20 per rack, so the CTMC fleet-fraction kill is the exact
    expectation in every pool — stochastic rack+pod shocks, a scripted
    mid-run rack kill, and a maintenance window pausing the repair
    shop.  Campaign times scale with the job length so the quick gate
    can shrink the scenario without pushing the kill past the horizon."""
    base = Params(job_size=256, working_pool_size=288, spare_pool_size=32,
                  warm_standbys=8, job_length=2 * MINUTES_PER_DAY,
                  random_failure_rate=0.25 / MINUTES_PER_DAY,
                  failure_distribution="lognormal",
                  distribution_kwargs={"sigma": 1.0}, seed=0)
    if job_length is not None:
        base = base.replace(job_length=job_length)
    topo = FaultTopology(n_racks=16, racks_per_pod=4,
                         rack_shock_rate=1e-4, pod_shock_rate=2e-5)
    camp = Campaign(events=(
        CampaignEvent(time=0.25 * base.job_length, kind="kill", domain=3),
        CampaignEvent(time=0.5 * base.job_length, kind="maintenance",
                      duration=0.05 * base.job_length),
    ))
    return base.replace(fault_domains=topo, campaign=camp)


def correlated_sweep_throughput(n_points: int = 8, n_replicas: int = 256,
                                ) -> Dict[str, object]:
    """Shock-rate grid through both engines under the full scenario
    (stochastic domain shocks + scripted kill + maintenance window).

    The scenario's *structure* (domain count, campaign codes) is a
    static compile key while every rate, fraction, and time is traced,
    so the whole grid must compile exactly one XLA program
    (``sweep_compiles``) — and the event engine pays per-injection
    Python work per trajectory, so the batched scan's warm speedup
    floor for this entry is >= 5x (scripts/check_bench.py gates both).
    """
    from repro.core import vectorized

    base = correlated_bench_params().replace(
        max_run_records=73)   # bench-unique jit shapes
    values = [float(v) for v in np.linspace(2e-5, 2e-4, n_points)]
    c0 = vectorized.compile_cache_size()
    out = _engine_ab_sweep(base, n_points, n_replicas, "correlated-bench",
                           parameter="rack_shock_rate", values=values)
    c1 = vectorized.compile_cache_size()
    return {
        "failure_distribution": base.failure_distribution,
        "distribution_kwargs": dict(base.distribution_kwargs),
        "topology": {"n_racks": base.fault_domains.n_racks,
                     "racks_per_pod": base.fault_domains.racks_per_pod,
                     "pod_shock_rate": base.fault_domains.pod_shock_rate},
        "campaign_events": len(base.campaign.events),
        "sweep_compiles": c1 - c0,
        **out,
    }


def checkpoint_bench_params() -> Params:
    """The checkpoint-rollback benchmark scenario, shared with the CI
    quick gate (scripts/check_bench.py) so the gate always measures the
    same scenario it compares against: a 64-server job whose fleet MTBF
    (~90 min) sits inside the swept interval grid, so every point pays
    real rollbacks AND real writes — the regime the goodput knob
    actually trades in.  Exponential failures keep the event side on its
    O(1)-per-restart sampler; the gap measured here is the rollback
    bookkeeping itself."""
    return Params(job_size=64, working_pool_size=72, spare_pool_size=8,
                  warm_standbys=4, job_length=1 * MINUTES_PER_DAY,
                  random_failure_rate=0.25 / MINUTES_PER_DAY,
                  checkpoint_cost=5.0, seed=0)


def checkpoint_sweep_throughput(n_points: int = 8, n_replicas: int = 256,
                                ) -> Dict[str, object]:
    """Checkpoint-interval grid on the fast path: rollback vs the event
    engine.

    Before the rollback lanes landed, ``checkpoint_interval > 0`` was a
    hard CTMC refusal — every goodput study fell back to one event
    trajectory at a time, which is exactly the study the optimizer
    (:mod:`repro.core.optimize`) now runs hundreds of candidates for.
    Sweeps the interval grid (8 x 256 by default, fleet MTBF inside the
    grid) through both engines.  Both ``checkpoint_interval`` and
    ``checkpoint_cost`` are *traced* columns — zero new static compile
    keys — so the whole grid must compile exactly one XLA program
    (``sweep_compiles``); the acceptance floor for this entry is a
    >= 5x warm speedup (scripts/check_bench.py gates both).
    """
    from repro.core import vectorized

    base = checkpoint_bench_params().replace(
        max_run_records=97)   # bench-unique jit shapes
    values = [float(v) for v in np.linspace(15.0, 120.0, n_points)]
    c0 = vectorized.compile_cache_size()
    out = _engine_ab_sweep(base, n_points, n_replicas, "checkpoint-bench",
                           parameter="checkpoint_interval", values=values)
    c1 = vectorized.compile_cache_size()
    return {
        "checkpoint_cost": base.checkpoint_cost,
        "sweep_compiles": c1 - c0,
        **out,
    }


def checkpoint_smoke(n_replicas: int = 24) -> Dict[str, object]:
    """CI guard: a traced (checkpoint_interval x checkpoint_cost) grid
    must compile exactly one XLA program, and the golden-section
    optimizer must return an interval inside its own bounds with the
    advertised evaluation count; exits nonzero otherwise."""
    from repro.core import run_replications_batch, vectorized
    from repro.core.optimize import optimize_checkpoint_interval

    base = Params(job_size=16, working_pool_size=32, spare_pool_size=4,
                  warm_standbys=2, job_length=0.2 * MINUTES_PER_DAY,
                  random_failure_rate=2.0 / MINUTES_PER_DAY,
                  recovery_time=5.0, auto_repair_time=30.0,
                  manual_repair_time=60.0, seed=0, checkpoint_cost=2.0,
                  max_run_records=17)   # bench-unique jit shapes
    grid = [base.replace(checkpoint_interval=iv, checkpoint_cost=c)
            for iv in (0.0, 20.0, 45.0) for c in (0.0, 2.0)]
    c0 = vectorized.compile_cache_size()
    run_replications_batch(grid, n_replicas, engine="ctmc")
    c1 = vectorized.compile_cache_size()
    compiles = c1 - c0
    res = optimize_checkpoint_interval(
        base.replace(checkpoint_interval=20.0), n_replicas=16,
        n_grid=4, refine_iters=2, engine="ctmc")
    lo, hi = min(res.grid), max(res.grid)
    out = {"n_points": len(grid), "n_replicas": n_replicas,
           "compiles": compiles,
           "optimizer": {"interval": res.interval,
                         "objective": res.objective,
                         "young_daly": res.young_daly,
                         "n_evals": res.n_evals}}
    if compiles != 1:
        raise SystemExit(
            f"compile-count regression: traced checkpoint grid compiled "
            f"{compiles} XLA programs, expected exactly 1")
    if not (lo <= res.interval <= hi):
        raise SystemExit(
            f"optimizer regression: interval {res.interval} escaped its "
            f"search bounds ({lo}, {hi})")
    if res.n_evals != 4 + 2 * len(res.history):
        raise SystemExit(
            f"optimizer regression: {res.n_evals} evaluations for "
            f"4 grid + {len(res.history)} golden-section iterations")
    return out


def multijob_bench_params(job_length_scale: float = 1.0):
    """The multi-job benchmark scenario, shared with the CI quick gate
    (scripts/check_bench.py) so the gate measures the exact scenario it
    compares against: three mixed-size jobs (64/32/16 servers, different
    lengths) contending for one shared spare pool and one finite repair
    shop, hot enough (~400 failures per replication) that the engines
    spend their time on the contention machinery itself.  Distribution
    channels are off on both engines — the single-job sweep benchmarks
    already measure histogram cost; here the shared-lane dynamics are
    the subject.  ``job_length_scale`` shrinks every job proportionally
    for the quick gate without changing the contention structure."""
    from repro.core import JobSpec

    cluster = Params(job_size=16, working_pool_size=200,
                     spare_pool_size=12, job_length=0.5 * MINUTES_PER_DAY,
                     random_failure_rate=0.004,
                     systematic_failure_rate=0.01,
                     auto_repair_time=180.0, manual_repair_time=480.0,
                     repair_servers=4, histogram=None, seed=0)
    jobs = tuple(JobSpec(size, length * job_length_scale, warm_standbys=w)
                 for size, length, w in
                 ((64, 0.5 * MINUTES_PER_DAY, 2),
                  (32, 0.7 * MINUTES_PER_DAY, 1),
                  (16, 0.6 * MINUTES_PER_DAY, 1)))
    return cluster, jobs


def multijob_capacity_grid(cluster, jobs, spares, shops):
    """Mixed-size capacity grid: spare-pool depth x repair servers."""
    return [(cluster.replace(spare_pool_size=s, repair_servers=r), jobs)
            for s in spares for r in shops]


def multijob_sweep_throughput(n_points: int = 8, n_replicas: int = 256,
                              ) -> Dict[str, object]:
    """Multi-job capacity grid: compiled compartment engine vs the
    event-loop ``MultiJobSimulation`` oracle.

    Before the multi-job CTMC engine existed, every shared-pool study —
    the capacity-planning question the paper's assumption 6 carves out —
    ran one event trajectory at a time.  This sweeps the spare-pool
    depth x repair-server grid (8 points x 256 replicas by default) of
    the shared three-job scenario through both engines.  The job count J
    is the ONLY static compile key (sizes, lengths, rates, pool and shop
    capacities all stay traced), so the whole mixed-size grid must
    compile exactly one XLA program (``sweep_compiles``); the acceptance
    floor for this entry is a >= 4x warm speedup over the event oracle
    (scripts/check_bench.py gates both, plus fleet-makespan agreement).
    """
    from repro.core import run_multijob_batch, vectorized_multijob

    cluster, jobs = multijob_bench_params()
    cluster = cluster.replace(max_run_records=77)  # bench-unique shapes
    assert n_points % 2 == 0
    # a homogeneous high-contention grid: the batched scan runs every
    # replica until the slowest point finishes, so one hot point costs
    # the same as eight — measure the regime the engine is for
    spares = [7 + i for i in range(n_points // 2)]
    grid = multijob_capacity_grid(cluster, jobs, spares, (3, 4))

    c0 = vectorized_multijob.compile_cache_size()
    t0 = time.perf_counter()
    ct = run_multijob_batch(grid, n_replicas, engine="ctmc", base_seed=0)
    compile_s = time.perf_counter() - t0
    c1 = vectorized_multijob.compile_cache_size()
    t0 = time.perf_counter()
    ct = run_multijob_batch(grid, n_replicas, engine="ctmc", base_seed=0)
    ctmc_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    ev = run_multijob_batch(grid, n_replicas, engine="event", base_seed=0)
    event_s = time.perf_counter() - t0

    points = []
    for (params, _), pc, pe in zip(grid, ct, ev):
        sc, se_ = pc.fleet["makespan"], pe.fleet["makespan"]
        pooled_se = np.sqrt(sc.std ** 2 / pc.n + se_.std ** 2 / pe.n)
        points.append({
            "spare_pool_size": params.spare_pool_size,
            "repair_servers": params.repair_servers,
            "ctmc_makespan_mean": sc.mean,
            "event_makespan_mean": se_.mean,
            "pooled_se": float(pooled_se),
            "z": float((sc.mean - se_.mean) / max(pooled_se, 1e-9)),
        })
    return {
        "n_points": len(grid),
        "n_replicas": n_replicas,
        "n_jobs": len(jobs),
        "event_wall_s": event_s,
        "ctmc_wall_s": ctmc_s,
        "ctmc_compile_wall_s": compile_s,
        "speedup_x": event_s / ctmc_s,
        "speedup_x_incl_compile": event_s / compile_s,
        "sweep_compiles": c1 - c0,
        "max_abs_z": max(abs(p["z"]) for p in points),
        "points": points,
    }


def repair_smoke(n_replicas: int = 24) -> Dict[str, object]:
    """CI guard: a repair-parameter grid under non-exponential repairs
    must compile exactly one XLA program (repair scales/means stay
    traced); exits nonzero otherwise."""
    from repro.core import run_replications_batch, vectorized

    base = Params(job_size=16, working_pool_size=32, spare_pool_size=4,
                  warm_standbys=2, job_length=0.1 * MINUTES_PER_DAY,
                  random_failure_rate=2.0 / MINUTES_PER_DAY,
                  recovery_time=5.0, auto_repair_time=30.0,
                  manual_repair_time=60.0, seed=0,
                  repair_distribution="weibull",
                  distribution_kwargs={"k": 0.7},
                  max_run_records=9)   # bench-unique jit shapes
    grid = [base.replace(auto_repair_time=v) for v in (20.0, 30.0, 45.0)]
    c0 = vectorized.compile_cache_size()
    run_replications_batch(grid, n_replicas, engine="ctmc", max_steps=192)
    c1 = vectorized.compile_cache_size()
    compiles = c1 - c0
    out = {"n_points": len(grid), "n_replicas": n_replicas,
           "compiles": compiles}
    if compiles != 1:
        raise SystemExit(
            f"compile-count regression: repair-parameter grid compiled "
            f"{compiles} XLA programs, expected exactly 1")
    return out


def bucketed_sweep_throughput(n_replicas: int = 256) -> Dict[str, object]:
    """Shape bucketing: repeated sweeps of *different* sizes, one program.

    Runs three recovery-time sweeps whose (P, R, step-budget) signatures
    all fall in the same power-of-two bucket — (6, R), (8, R), (5, R)
    with different budgets — first bucketed (exactly one XLA compilation
    covering all three), then unbucketed (one per distinct shape).
    Reports compile counts and the wall-clock of the *second and third*
    sweeps, where bucketing pays off: they start warm instead of
    recompiling.
    """
    from repro.core import run_replications_batch, vectorized

    # bench-unique shape (see structural_sweep_throughput) so the
    # compile counts measure only this benchmark's sweeps
    base = sweep_bench_params().replace(max_run_records=80)

    def grids():
        return [[base.replace(recovery_time=5.0 + 5.0 * i)
                 for i in range(n)] for n in (6, 8, 5)]

    def timed(bucketed):
        c0 = vectorized.compile_cache_size()
        walls = []
        for grid in grids():
            t0 = time.perf_counter()
            run_replications_batch(grid, n_replicas, engine="ctmc",
                                   bucketed=bucketed)
            walls.append(time.perf_counter() - t0)
        c1 = vectorized.compile_cache_size()
        compiles = c1 - c0
        return walls, compiles

    b_walls, b_compiles = timed(True)
    u_walls, u_compiles = timed(False)
    return {
        "n_replicas": n_replicas,
        "sweep_points": [6, 8, 5],
        "bucketed_wall_s": b_walls,
        "bucketed_compiles": b_compiles,
        "unbucketed_wall_s": u_walls,
        "unbucketed_compiles": u_compiles,
        "resize_speedup_x": (sum(u_walls[1:]) / max(sum(b_walls[1:]), 1e-9)),
    }


def bucketing_smoke(n_replicas: int = 24) -> Dict[str, object]:
    """CI guard: same-bucket sweeps of different (P, R, step-budget)
    must share exactly one compiled program; exits nonzero otherwise."""
    from repro.core import run_replications_batch, vectorized

    base = Params(job_size=16, working_pool_size=32, spare_pool_size=4,
                  warm_standbys=2, job_length=0.1 * MINUTES_PER_DAY,
                  random_failure_rate=2.0 / MINUTES_PER_DAY,
                  recovery_time=5.0, auto_repair_time=30.0,
                  manual_repair_time=60.0, seed=0, max_run_records=11)
    grid_a = [base.replace(recovery_time=v) for v in (5.0, 10.0, 15.0)]
    grid_b = [base.replace(recovery_time=v)
              for v in (5.0, 10.0, 15.0, 20.0)]
    c0 = vectorized.compile_cache_size()
    run_replications_batch(grid_a, n_replicas, engine="ctmc", max_steps=192)
    run_replications_batch(grid_b, n_replicas - 7, engine="ctmc",
                           max_steps=256)
    c1 = vectorized.compile_cache_size()
    compiles = c1 - c0
    out = {"sweep_shapes": [[3, n_replicas], [4, n_replicas - 7]],
           "compiles": compiles}
    if compiles != 1:
        raise SystemExit(
            f"bucketing regression: two same-bucket sweeps compiled "
            f"{compiles} XLA programs, expected exactly 1")
    return out


def structural_smoke(n_points: int = 4, n_replicas: int = 32,
                     ) -> Dict[str, object]:
    """Tiny structural sweep guarding the compile-count invariant.

    Run by scripts/ci.sh on every tier-1 pass: a mixed-structure
    ``job_size`` grid must compile exactly one XLA program per padded
    group (= one for the whole grid).  Exits nonzero on regression.
    """
    from repro.core import vectorized

    base = Params(job_size=16, working_pool_size=32, spare_pool_size=4,
                  warm_standbys=2, job_length=0.1 * MINUTES_PER_DAY,
                  random_failure_rate=2.0 / MINUTES_PER_DAY,
                  recovery_time=5.0, auto_repair_time=30.0,
                  manual_repair_time=60.0, seed=0)
    values = [8 + 4 * i for i in range(n_points)]
    sweep = OneWaySweep("structural-smoke", "job_size", values,
                        n_replications=n_replicas, base_params=base,
                        engine="ctmc")
    c0 = vectorized.compile_cache_size()
    t0 = time.perf_counter()
    res = sweep.run()
    wall = time.perf_counter() - t0
    c1 = vectorized.compile_cache_size()
    compiles = c1 - c0
    out = {"n_points": n_points, "n_replicas": n_replicas,
           "wall_s": wall, "compiles": compiles,
           "total_time_means": [p.stats["total_time"].mean
                                for p in res.points]}
    if compiles != 1:
        raise SystemExit(
            f"compile-count regression: structural {n_points}-point sweep "
            f"compiled {compiles} XLA programs, expected exactly 1 per "
            "padded group")
    return out


def multijob_smoke(n_replicas: int = 24) -> Dict[str, object]:
    """CI guard: a mixed-size multi-job capacity grid (spare pool x
    repair servers, job sizes differing per spec) must compile exactly
    one XLA program — J is the only static key.  Exits nonzero
    otherwise."""
    from repro.core import JobSpec, run_multijob_batch, vectorized_multijob

    cluster = Params(job_size=12, working_pool_size=40, spare_pool_size=4,
                     job_length=0.1 * MINUTES_PER_DAY,
                     random_failure_rate=2.0 / MINUTES_PER_DAY,
                     recovery_time=5.0, auto_repair_time=30.0,
                     manual_repair_time=60.0, repair_servers=2, seed=0,
                     max_run_records=13)   # smoke-unique jit shapes
    jobs = (JobSpec(12, 0.1 * MINUTES_PER_DAY, warm_standbys=1),
            JobSpec(8, 0.15 * MINUTES_PER_DAY, warm_standbys=1))
    grid = multijob_capacity_grid(cluster, jobs, (3, 4), (2, 3))
    c0 = vectorized_multijob.compile_cache_size()
    reps = run_multijob_batch(grid, n_replicas, engine="ctmc", base_seed=0)
    c1 = vectorized_multijob.compile_cache_size()
    compiles = c1 - c0
    out = {"n_points": len(grid), "n_replicas": n_replicas,
           "n_jobs": len(jobs), "compiles": compiles,
           "makespan_means": [r.fleet["makespan"].mean for r in reps]}
    if compiles != 1:
        raise SystemExit(
            f"compile-count regression: mixed-size multi-job capacity "
            f"grid compiled {compiles} XLA programs, expected exactly 1")
    return out


def _sharded_child(n_dev: int, n_points: int = 4,
                   r_per_dev: int = 256) -> Dict[str, object]:
    """One weak-scaling measurement, run in a fresh process whose
    XLA_FLAGS already forced ``n_dev`` host devices (see
    :func:`sharded_weak_scaling` — device count is fixed at jax import,
    so each mesh size needs its own interpreter)."""
    import repro.core.vectorized as vz

    assert jax.device_count() >= n_dev, (jax.device_count(), n_dev)
    base = sweep_bench_params()
    values = [float(v) for v in np.linspace(5.0, 40.0, n_points)]
    pts = [base.replace(recovery_time=v) for v in values]
    R = r_per_dev * n_dev      # weak scaling: per-device work constant
    steps = max(default_max_steps(p) for p in pts)

    def run(shards):
        return vz.simulate_ctmc_sweep(pts, n_replicas=R, seed=0,
                                      max_steps=steps, shards=shards)

    run(n_dev)                                   # compile
    t0 = time.perf_counter()
    out = run(n_dev)                             # warm
    wall = time.perf_counter() - t0
    rec: Dict[str, object] = {
        "devices": n_dev,
        "n_points": n_points,
        "n_replicas": R,
        "wall_s": wall,
        "replicas_per_s": n_points * R / wall,
        # fresh process: the whole warm sweep must live in ONE compiled
        # sharded program
        "sweep_compiles": vz.shard_compile_cache_size(),
    }
    if n_dev == 1:
        def run_unsharded():
            return vz.simulate_ctmc_sweep(pts, n_replicas=R, seed=0,
                                          max_steps=steps, shards=0)

        base_out = run_unsharded()               # compile
        t0 = time.perf_counter()
        base_out = run_unsharded()               # warm
        rec["unsharded_wall_s"] = time.perf_counter() - t0
        rec["unsharded_replicas_per_s"] = (n_points * R
                                           / rec["unsharded_wall_s"])
        rec["mesh1_bitident"] = all(
            np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
            for a, b in zip(out, base_out) for k in a)
    return rec


def sharded_weak_scaling(device_counts=(1, 2, 4)) -> Dict[str, object]:
    """Weak-scaling curve of the replica-sharded CTMC sweep — a CPU-only
    correctness curve, not a speed figure.

    Spawns one child interpreter per mesh size with
    ``JAX_PLATFORMS=cpu`` and
    ``XLA_FLAGS=--xla_force_host_platform_device_count=D`` (the forced
    host-device recipe of docs/scaling.md): the children never ask for
    an accelerator, which the parent process may already hold.  It
    grows the replica count
    with the mesh so per-device work stays constant.  Reports per-point
    throughput, ``weak_scaling_efficiency`` (throughput at D devices
    over the 1-device mesh), the sharded-vs-unsharded retention at mesh
    size 1, the one-compile invariant, and the mesh-1 bit-identity
    check.  NOTE on CPU CI the forced devices share physical cores, so
    near-flat replica throughput (efficiency ~1) is the pass condition
    — real speedup needs real devices; scripts/check_bench.py floors
    efficiency, not speedup.
    """
    import json as _json
    import os
    import subprocess
    import sys as _sys

    points = []
    for d in device_counts:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
        out = subprocess.run(
            [_sys.executable, os.path.abspath(__file__),
             "--sharded-child", str(d)],
            env=env, capture_output=True, text=True, check=True)
        points.append(_json.loads(out.stdout.strip().splitlines()[-1]))
    base_tp = points[0]["replicas_per_s"]
    for p in points:
        p["weak_scaling_efficiency"] = p["replicas_per_s"] / base_tp
    un_tp = points[0]["unsharded_replicas_per_s"]
    return {
        "device_counts": list(device_counts),
        "max_devices": device_counts[-1],
        "points": points,
        "sharded_speedup_x": points[-1]["replicas_per_s"] / un_tp,
        "retention_1dev": base_tp / un_tp,
        "min_weak_scaling_efficiency": min(
            p["weak_scaling_efficiency"] for p in points),
        "mesh1_bitident": points[0]["mesh1_bitident"],
        "sweep_compiles": max(p["sweep_compiles"] for p in points),
    }


def speedup_summary() -> Dict[str, float]:
    ev = event_engine_throughput(n_runs=3)
    ct = ctmc_engine_throughput(n_replicas=2048)
    # normalize: wall time to simulate one full cluster-job trajectory
    ev_per_traj = 1.0 / ev["runs_per_s"]
    ct_per_traj = ct["wall_s"] / 2048
    return {"event_s_per_trajectory": ev_per_traj,
            "ctmc_s_per_trajectory": ct_per_traj,
            "speedup_x": ev_per_traj / ct_per_traj,
            **{f"event_{k}": v for k, v in ev.items()},
            **{f"ctmc_{k}": v for k, v in ct.items()}}


def write_sweep_artifact(sw: Dict[str, object],
                         path: str = "BENCH_sweep.json") -> str:
    """Persist the sweep benchmark as the machine-readable perf artifact.

    Lives at the repo root (not under results/) on purpose: it is the
    tracked perf trajectory, committed so regressions show up in review.
    """
    import json

    with open(path, "w") as f:
        json.dump(sw, f, indent=2)
    return path


if __name__ == "__main__":   # standalone: sweep benchmarks or CI smoke
    import json
    import sys

    from repro import compile_cache

    compile_cache.enable()

    if "--sharded-child" in sys.argv:
        d = int(sys.argv[sys.argv.index("--sharded-child") + 1])
        print(json.dumps(_sharded_child(d)))
        sys.exit(0)
    if "--smoke" in sys.argv:
        print(json.dumps({"structural": structural_smoke(),
                          "bucketing": bucketing_smoke(),
                          "repair": repair_smoke(),
                          "multijob": multijob_smoke(),
                          "checkpoint": checkpoint_smoke()}, indent=2))
        sys.exit(0)
    sw = sweep_throughput()
    sw["structural"] = structural_sweep_throughput()
    sw["bucketing"] = bucketed_sweep_throughput()
    sw["nonexp"] = weibull_sweep_throughput()
    sw["repair_dist"] = repair_sweep_throughput()
    sw["empirical"] = empirical_sweep_throughput()
    sw["correlated"] = correlated_sweep_throughput()
    sw["multijob"] = multijob_sweep_throughput()
    sw["checkpoint"] = checkpoint_sweep_throughput()
    sw["sharded"] = sharded_weak_scaling()
    sections = ("points", "structural", "bucketing", "nonexp", "repair_dist",
                "empirical", "correlated", "multijob", "checkpoint",
                "sharded")
    print(json.dumps({k: v for k, v in sw.items() if k not in sections},
                     indent=2))
    print(json.dumps({k: v for k, v in sw["structural"].items()
                      if k != "points"}, indent=2))
    print(json.dumps(sw["bucketing"], indent=2))
    for sec in ("nonexp", "repair_dist", "empirical", "correlated",
                "multijob", "checkpoint", "sharded"):
        print(json.dumps({k: v for k, v in sw[sec].items()
                          if k != "points"}, indent=2))
    print("wrote", write_sweep_artifact(sw))
