"""Engine dispatch: route replication studies to the right simulator.

AIReSim has two engines with one statistical contract:

  * ``event`` — the generator-coroutine DES (:mod:`repro.core.simulation`).
    Exact for every feature (retirement, bad-set regeneration, arbitrary
    distributions, checkpoint rollback), one trajectory at a time.
  * ``ctmc``  — the vectorized JAX engine (:mod:`repro.core.vectorized`).
    Covers the paper's exponential model, the age-dependent Weibull /
    bathtub / lognormal failure families, trace-driven ``empirical``
    piecewise-constant hazards (fitted from event logs via
    :mod:`repro.core.empirical`), *and* Weibull / lognormal /
    deterministic repair distributions (see ``vectorized.supports`` and
    docs/distributions.md), plus checkpoint rollback + write cost
    (``checkpoint_interval`` / ``checkpoint_cost``, both traced sweep
    axes), simulating thousands of replicas — and, via
    :func:`run_replications_batch`, whole sweep grids, including
    *structural* grids over job_size / pool sizes / warm_standbys — as a
    single compiled XLA program per hazard family (structure padding;
    see the vectorized module docstring).  Run-duration statistics are
    exact on both engines: the CTMC scan records per-run intervals in a
    ring buffer sized by ``Params.max_run_records``.

``engine="auto"`` (the default everywhere) picks ``ctmc`` whenever the
parameters are inside its supported envelope and silently falls back to
``event`` otherwise, so callers get the fast path for free without losing
feature coverage.  Passing ``engine="ctmc"`` explicitly raises if the
parameters are unsupported rather than silently degrading.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from . import tracing, vectorized, vectorized_multijob
from .histograms import Histogram
from .metrics import (RunResult, Stat, aggregate, aggregate_arrays,
                      aggregate_multijob_arrays, histograms_from_arrays,
                      histograms_from_results, pool_histograms)
from .multijob import JobSpec, MultiJobResult, simulate_multijob
from .params import Params
from .simulation import simulate

ENGINES = ("auto", "event", "ctmc")


def resolve_engine(params: Params, engine: str = "auto") -> str:
    """Map an engine request to the concrete engine that will run."""
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; expected one of "
                         f"{ENGINES}")
    if engine == "auto":
        return "ctmc" if vectorized.supports(params) else "event"
    if engine == "ctmc":
        # built from vectorized.unsupported_reasons — the single source
        # of truth shared with supports() — so the message names the
        # *actual* exclusion(s) instead of a hand-maintained stale list
        reasons = vectorized.unsupported_reasons(params)
        if reasons:
            raise ValueError(
                "engine='ctmc' requested but these Params are outside "
                "the CTMC envelope: " + "; ".join(reasons)
                + "; use engine='auto' to fall back to the event engine")
    return engine


@dataclass
class Replications:
    """Aggregated outcome of one replication study (one sweep point)."""

    engine: str                     # concrete engine that ran: event | ctmc
    n: int                          # number of replications
    stats: Dict[str, Stat]
    #: per-replication RunResults (event engine only; empty for ctmc —
    #: the whole point of the batched path is never materializing them)
    results: List[RunResult] = field(default_factory=list)
    #: raw {metric: (n,) ndarray} (ctmc engine only)
    arrays: Optional[Dict[str, np.ndarray]] = None
    #: pooled streaming histograms per channel (both engines, whenever
    #: ``Params.histogram`` is set) — unbounded-run-count ETTF/ETTR/
    #: waiting distributions, percentiles exact to one bin width
    histograms: Dict[str, Histogram] = field(default_factory=dict)


def _from_arrays(arrays: Dict[str, np.ndarray], n: int,
                 point: int = 0) -> Replications:
    """Host statistics of one point's per-replica arrays."""
    with tracing.span(tracing.AGGREGATE, point=point) as span:
        incomplete = int(n - arrays["completed"].sum())
        if incomplete:
            warnings.warn(
                f"{incomplete}/{n} CTMC replicas hit the step budget "
                "before finishing the job; means are biased low — raise "
                "max_steps (truncation is surfaced as the 'n_incomplete' "
                "metric and the 'completed' fraction in stats and sweep "
                "CSVs)",
                RuntimeWarning, stacklevel=3)
        overflows = int(arrays.get("n_repair_overflow",
                                   np.zeros(1)).sum())
        if overflows:
            warnings.warn(
                f"{overflows} diagnosed failure(s) found the repair-slot "
                "lane full (the server never leaves the shop; results are "
                "biased) — raise Params.repair_slots",
                RuntimeWarning, stacklevel=3)
        hists = histograms_from_arrays(arrays)
        stats = aggregate_arrays(arrays, histograms=hists)
        # the run records pooled into run_duration_pooled (none where
        # the ring was compiled out), the bulk of this span's work
        span.set_metadata(pooled=int(np.minimum(
            arrays["n_runs"], arrays["run_durations"].shape[1]).sum()))
        return Replications(engine="ctmc", n=n, stats=stats,
                            arrays=arrays, histograms=hists)


def _from_results(results: List[RunResult], n: int,
                  params: Params) -> Replications:
    hists = histograms_from_results(results, params.histogram)
    return Replications(engine="event", n=n,
                        stats=aggregate(results, histograms=hists),
                        results=results, histograms=hists)


def run_replications(params: Params, n: int, engine: str = "auto",
                     base_seed: Optional[int] = None,
                     impl: Optional[str] = None,
                     max_steps: Optional[int] = None) -> Replications:
    """Run ``n`` independent replications on the selected engine."""
    chosen = resolve_engine(params, engine)
    if chosen == "ctmc":
        seed = params.seed if base_seed is None else base_seed
        with tracing.study(points=1, replicas=n):
            arrays = vectorized.simulate_ctmc(params, n_replicas=n,
                                              seed=seed, impl=impl,
                                              max_steps=max_steps)
            return _from_arrays(arrays, n)
    results = simulate(params, n, base_seed=base_seed)
    return _from_results(results, n, params)


def run_replications_batch(params_list: Sequence[Params], n: int,
                           engine: str = "auto",
                           base_seed: Optional[int] = None,
                           impl: Optional[str] = None,
                           max_steps: Optional[int] = None,
                           progress: Optional[Callable[[int], None]] = None,
                           bucketed: bool = True,
                           ) -> List[Replications]:
    """Replication studies for a whole sweep grid, batched where possible.

    Every point that resolves to the CTMC engine is executed in a single
    ``vectorized.simulate_ctmc_sweep`` call — even a mixed-structure grid
    compiles exactly one XLA program.  ``bucketed=True`` (default)
    additionally rounds the (points, replicas, step-budget) shape
    signature up to its power-of-two bucket with inert padding rows, so
    repeated sweeps of different sizes reuse one compiled program.  The
    rest run through the event engine one by one.  Results come back in
    input order regardless of routing.

    ``progress(i)`` is invoked when work on grid point ``i`` starts:
    once per point as the sequential event engine reaches it, and for
    all batched CTMC points up front (they genuinely start together).

    A failure-free grid finishes in exactly host-selection + job time,
    which makes the routing observable:

    >>> from repro.core import Params, run_replications_batch
    >>> calm = Params(job_size=2, working_pool_size=3, spare_pool_size=1,
    ...               warm_standbys=0, job_length=10.0,
    ...               random_failure_rate=0.0, systematic_failure_rate=0.0,
    ...               histogram=None)
    >>> reps = run_replications_batch(
    ...     [calm, calm.replace(job_length=20.0)], n=2, engine="event")
    >>> [round(r.stats["total_time"].mean, 1) for r in reps]  # +3.0 select
    [13.0, 23.0]
    >>> [r.engine for r in reps]
    ['event', 'event']
    """
    params_list = list(params_list)
    chosen = [resolve_engine(p, engine) for p in params_list]
    out: List[Optional[Replications]] = [None] * len(params_list)

    ctmc_idx = [i for i, c in enumerate(chosen) if c == "ctmc"]
    if ctmc_idx:
        if progress:
            for i in ctmc_idx:
                progress(i)
        seed = (params_list[ctmc_idx[0]].seed if base_seed is None
                else base_seed)
        with tracing.study(points=len(ctmc_idx), replicas=n):
            arrays_list = vectorized.simulate_ctmc_sweep(
                [params_list[i] for i in ctmc_idx], n_replicas=n,
                seed=seed, impl=impl, max_steps=max_steps, bucketed=bucketed)
            for i, arrays in zip(ctmc_idx, arrays_list):
                out[i] = _from_arrays(arrays, n, point=i)

    for i, c in enumerate(chosen):
        if c == "event":
            if progress:
                progress(i)
            results = simulate(params_list[i], n, base_seed=base_seed)
            out[i] = _from_results(results, n, params_list[i])
    return out


# ---------------------------------------------------------------------------
# multi-job dispatch
# ---------------------------------------------------------------------------

def resolve_engine_multijob(cluster: Params, jobs: Sequence[JobSpec],
                            engine: str = "auto") -> str:
    """Multi-job twin of :func:`resolve_engine`.

    ``auto`` picks the compiled multi-job CTMC engine
    (:mod:`repro.core.vectorized_multijob`) whenever the cluster is
    inside its envelope — exponential failures and repairs, all jobs
    starting at t=0, none of the event-only extensions — and falls back
    to the event-loop :class:`~repro.core.multijob.MultiJobSimulation`
    otherwise.
    """
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; expected one of "
                         f"{ENGINES}")
    if engine == "auto":
        return ("ctmc"
                if vectorized_multijob.supports_multijob(cluster, jobs)
                else "event")
    if engine == "ctmc":
        reasons = vectorized_multijob.unsupported_reasons_multijob(
            cluster, jobs)
        if reasons:
            raise ValueError(
                "engine='ctmc' requested but this multi-job cluster is "
                "outside the CTMC envelope: " + "; ".join(reasons)
                + "; use engine='auto' to fall back")
    return engine


@dataclass
class MultiJobReplications:
    """Aggregated outcome of one multi-job replication study."""

    engine: str                     # concrete engine that ran
    n: int                          # number of replications
    #: one full Replications per job (same Stat keys as single-job runs)
    per_job: List[Replications]
    #: fleet-level Stats: makespan, shared-shop counters, stall_handoffs,
    #: n_shop_queued, conservation_err, completed, fleet_* sums, and
    #: fleet-pooled {channel}_dist
    fleet: Dict[str, Stat]
    #: fleet-pooled streaming histograms (all jobs' channels merged)
    histograms: Dict[str, Histogram] = field(default_factory=dict)


def _multijob_from_arrays(point: Dict[str, object], n: int,
                          index: int = 0) -> MultiJobReplications:
    """Host statistics of one multi-job point's arrays."""
    with tracing.span(tracing.AGGREGATE, point=index):
        agg = aggregate_multijob_arrays(point)
    per_job = []
    for arrays, stats, hists in zip(point["per_job"], agg["per_job"],
                                    agg["per_job_histograms"]):
        per_job.append(Replications(engine="ctmc", n=n, stats=stats,
                                    arrays=arrays, histograms=hists))
    incomplete = int(n - point["completed"].sum())
    if incomplete:
        warnings.warn(
            f"{incomplete}/{n} multi-job CTMC replicas hit the step budget "
            "before every job finished; means are biased low — raise "
            "max_steps", RuntimeWarning, stacklevel=3)
    return MultiJobReplications(engine="ctmc", n=n, per_job=per_job,
                                fleet=agg["fleet"],
                                histograms=agg["histograms"])


def _multijob_from_results(results: List[MultiJobResult], n: int,
                           cluster: Params) -> MultiJobReplications:
    n_jobs = len(results[0].per_job)
    per_job = [
        _from_results([r.per_job[j] for r in results], n, cluster)
        for j in range(n_jobs)]
    fleet: Dict[str, Stat] = {}
    lanes = {
        "makespan": [r.makespan for r in results],
        "stall_handoffs": [float(r.stall_events) for r in results],
        "n_auto_repairs": [float(r.cluster.n_auto_repairs)
                           for r in results],
        "n_manual_repairs": [float(r.cluster.n_manual_repairs)
                             for r in results],
        "n_failed_repairs": [float(r.cluster.n_failed_repairs)
                             for r in results],
        "n_shop_queued": [float(r.queue_events) for r in results],
        # the event loop conserves servers by construction (pinned by
        # test_multijob_conserves_servers); reported for key parity
        "conservation_err": [0.0] * n,
        "completed": [0.0 if any(p.timed_out for p in r.per_job) else 1.0
                      for r in results],
        "fleet_n_failures": [float(r.total_failures) for r in results],
        "fleet_stall_time": [sum(p.stall_time for p in r.per_job)
                             for r in results],
        "fleet_useful_work": [sum(p.useful_work for p in r.per_job)
                              for r in results],
    }
    for name, xs in lanes.items():
        fleet[name] = Stat.of(xs)
    pooled = pool_histograms([rep.histograms for rep in per_job])
    for ch, h in pooled.items():
        fleet[f"{ch}_dist"] = Stat.from_histogram(h)
    return MultiJobReplications(engine="event", n=n, per_job=per_job,
                                fleet=fleet, histograms=pooled)


def run_replications_multijob(cluster: Params, jobs: Sequence[JobSpec],
                              n: int, engine: str = "auto",
                              base_seed: Optional[int] = None,
                              impl: Optional[str] = None,
                              max_steps: Optional[int] = None,
                              ) -> MultiJobReplications:
    """``n`` independent multi-job replications on the selected engine."""
    return run_multijob_batch([(cluster, tuple(jobs))], n, engine=engine,
                              base_seed=base_seed, impl=impl,
                              max_steps=max_steps)[0]


def run_multijob_batch(points: Sequence, n: int, engine: str = "auto",
                       base_seed: Optional[int] = None,
                       impl: Optional[str] = None,
                       max_steps: Optional[int] = None,
                       ) -> List[MultiJobReplications]:
    """Multi-job replication studies for a whole capacity grid.

    ``points`` is a sequence of ``(cluster Params, [JobSpec, ...])``
    pairs.  Every point inside the multi-job CTMC envelope runs in a
    single :func:`~repro.core.vectorized_multijob.simulate_multijob_ctmc_sweep`
    call — points sharing a job count compile to ONE XLA program no
    matter how sizes, rates, or pool/shop capacities vary — and the rest
    fall back to the event-loop ``MultiJobSimulation`` one by one.
    """
    points = [(c, tuple(js)) for c, js in points]
    chosen = [resolve_engine_multijob(c, js, engine) for c, js in points]
    out: List[Optional[MultiJobReplications]] = [None] * len(points)

    ctmc_idx = [i for i, c in enumerate(chosen) if c == "ctmc"]
    if ctmc_idx:
        seed = (points[ctmc_idx[0]][0].seed if base_seed is None
                else base_seed)
        with tracing.study(points=len(ctmc_idx), replicas=n):
            point_outs = vectorized_multijob.simulate_multijob_ctmc_sweep(
                [points[i] for i in ctmc_idx], n_replicas=n, seed=seed,
                impl=impl, max_steps=max_steps)
            for i, po in zip(ctmc_idx, point_outs):
                out[i] = _multijob_from_arrays(po, n, i)

    for i, c in enumerate(chosen):
        if c == "event":
            cluster, js = points[i]
            results = simulate_multijob(
                cluster, list(js), n_replications=n,
                base_seed=cluster.seed if base_seed is None else base_seed)
            out[i] = _multijob_from_results(results, n, cluster)
    return out
