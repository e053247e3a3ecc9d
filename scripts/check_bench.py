#!/usr/bin/env python
"""Bench regression gate: keep BENCH_sweep.json an enforced contract.

Two modes:

* ``--quick`` (default; what plain ``scripts/ci.sh`` and hosted CI run):
  re-measures a scaled-down warm-speedup A/B for the exponential
  baseline sweep and the repair-distribution sweep, then checks them
  against the *committed* BENCH_sweep.json with a generous tolerance
  band (small grids amortize fixed overhead worse and CI runners are
  noisy, so the quick gate catches collapses — a fast path silently
  falling back to the event engine — not percent-level drift).

* ``--fresh PATH`` (what ``scripts/ci.sh --bench`` runs after
  regenerating the artifact): compares a freshly measured full artifact
  against a baseline copy saved before the run, enforcing relative
  bands, the absolute speedup floors (the repair_dist entry's >= 5x
  acceptance criterion among them), exact compile-count invariants, and
  cross-engine agreement sanity.  ``--append-history`` then appends a
  timestamped one-line JSON record to BENCH_history.jsonl so the perf
  trajectory is machine-readable across PRs.

Exit status is nonzero on any violated gate; every gate prints a
PASS/FAIL line so the CI log reads as a checklist.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from datetime import datetime, timezone

#: absolute warm-speedup floors for the full artifact — the claims the
#: README/BENCH entries make, enforced rather than aspirational
FULL_SPEEDUP_FLOORS = {
    "speedup_x": 3.0,            # exponential baseline sweep
    "nonexp.speedup_x": 5.0,     # weibull failure grid
    "repair_dist.speedup_x": 5.0,   # repair-policy grid (acceptance)
    "empirical.speedup_x": 5.0,     # trace-driven hazard grid (acceptance)
    "correlated.speedup_x": 5.0,    # fault-domain scenario grid (acceptance)
    "multijob.speedup_x": 4.0,      # shared-pool capacity grid (acceptance)
    "checkpoint.speedup_x": 3.0,    # rollback interval grid (acceptance)
}

#: non-speedup numeric floors — the replica-sharding section reports
#: throughput *retention* ratios (forced host devices share physical
#: cores on CI, so weak-scaling efficiency ~1 is ideal and real speedup
#: needs real devices; docs/scaling.md); floors catch the sharded path
#: collapsing, not parallel hardware appearing
FULL_VALUE_FLOORS = {
    # sharded throughput per replica at D devices vs the 1-device mesh
    "sharded.min_weak_scaling_efficiency": 0.3,
    # 1-device sharded dispatch vs the unsharded engine (shard_map tax)
    "sharded.retention_1dev": 0.6,
}

#: exact compile-count invariants of the full artifact
FULL_COMPILE_GATES = {
    "structural.padded_compiles": 1,
    "bucketing.bucketed_compiles": 1,
    # segment count is the only static key: one program per fitted grid
    "empirical.sweep_compiles": 1,
    # the scenario's rates/times are traced: one program per shock grid
    "correlated.sweep_compiles": 1,
    # J is the only static key: one program per mixed-size capacity grid
    "multijob.sweep_compiles": 1,
    # interval and cost are traced columns: one program per interval grid
    "checkpoint.sweep_compiles": 1,
    # mesh is a static key: one sharded program per weak-scaling child
    "sharded.sweep_compiles": 1,
}

_FAILURES = []


def _gate(name: str, ok: bool, detail: str) -> None:
    print(f"[{'PASS' if ok else 'FAIL'}] {name}: {detail}")
    if not ok:
        _FAILURES.append(name)


def _lookup(doc: dict, dotted: str):
    cur = doc
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def _git_sha() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            stderr=subprocess.DEVNULL).decode().strip()
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


# ---------------------------------------------------------------------------
# quick mode
# ---------------------------------------------------------------------------

def _quick_ab(base, parameter, values, n_replicas):
    """Warm CTMC wall vs event wall on a small grid (compile excluded)."""
    from repro.core import OneWaySweep

    kw = dict(n_replications=n_replicas, base_params=base, base_seed=0)
    ct = OneWaySweep("quick", parameter, values, engine="ctmc", **kw)
    ct.run()                                     # compile
    t0 = time.perf_counter()
    ct.run()
    ctmc_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    OneWaySweep("quick", parameter, values, engine="event", **kw).run()
    event_s = time.perf_counter() - t0
    return event_s / max(ctmc_s, 1e-9)


def run_quick(baseline: dict, tolerance: float) -> None:
    import os
    # `python scripts/check_bench.py` puts scripts/ (not the repo root)
    # first on sys.path; the benchmarks package lives at the root
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.engine_perf import repair_bench_params, sweep_bench_params
    from repro.core import MINUTES_PER_DAY

    # exponential baseline sweep, quick scale (distinct max_run_records
    # keeps the jit cache entries from colliding with test runs)
    base = sweep_bench_params().replace(job_length=0.5 * MINUTES_PER_DAY,
                                        max_run_records=61)
    q_exp = _quick_ab(base, "recovery_time", [5.0, 15.0, 25.0, 35.0], 64)
    b_exp = baseline.get("speedup_x")
    # a missing baseline key must fail loudly: `>= tolerance * 0` would
    # otherwise pass unconditionally — exactly the silent-collapse
    # regression this gate exists to catch
    _gate("quick.exponential_speedup",
          b_exp is not None and q_exp >= tolerance * b_exp,
          f"measured {q_exp:.2f}x warm (4x64 grid) vs committed "
          f"{'MISSING' if b_exp is None else f'{b_exp:.2f}x'} (8x256); "
          f"floor {tolerance:.2f}x of committed")

    # the exact scenario the committed repair_dist entry measures
    # (shared factory — the gate and the baseline cannot drift apart),
    # shrunk to quick scale
    rbase = repair_bench_params().replace(
        job_length=0.5 * MINUTES_PER_DAY, max_run_records=62)
    q_rep = _quick_ab(rbase, "auto_repair_time", [30.0, 90.0, 150.0, 210.0],
                      64)
    b_rep = _lookup(baseline, "repair_dist.speedup_x")
    _gate("quick.repair_dist_speedup",
          b_rep is not None and q_rep >= tolerance * b_rep,
          f"measured {q_rep:.2f}x warm (4x64 grid) vs committed "
          f"{'MISSING' if b_rep is None else f'{b_rep:.2f}x'} (8x256); "
          f"floor {tolerance:.2f}x of committed")

    # the trace-driven empirical scenario (shared factory): a fitted-
    # style 3-segment hazard through the piecewise-constant sampler —
    # the gate that catches a log-fitted study silently collapsing back
    # onto the O(cluster)-per-restart event engine
    from benchmarks.engine_perf import empirical_bench_params

    ebase = empirical_bench_params().replace(
        job_length=0.5 * MINUTES_PER_DAY, max_run_records=65)
    q_emp = _quick_ab(ebase, "recovery_time", [5.0, 15.0, 25.0, 35.0], 64)
    b_emp = _lookup(baseline, "empirical.speedup_x")
    _gate("quick.empirical_speedup",
          b_emp is not None and q_emp >= tolerance * b_emp,
          f"measured {q_emp:.2f}x warm (4x64 grid) vs committed "
          f"{'MISSING' if b_emp is None else f'{b_emp:.2f}x'} (8x256); "
          f"floor {tolerance:.2f}x of committed")

    # the correlated-failure scenario (shared factory again): domain
    # shocks + a scripted kill + a maintenance window, swept over the
    # rack shock rate — the gate that catches the scenario race lanes
    # silently knocking the grid off the single-program fast path
    from benchmarks.engine_perf import correlated_bench_params

    cbase = correlated_bench_params(
        job_length=0.5 * MINUTES_PER_DAY).replace(max_run_records=63)
    q_cor = _quick_ab(cbase, "rack_shock_rate",
                      [5e-5, 1e-4, 1.5e-4, 2e-4], 64)
    b_cor = _lookup(baseline, "correlated.speedup_x")
    _gate("quick.correlated_speedup",
          b_cor is not None and q_cor >= tolerance * b_cor,
          f"measured {q_cor:.2f}x warm (4x64 grid) vs committed "
          f"{'MISSING' if b_cor is None else f'{b_cor:.2f}x'} (8x256); "
          f"floor {tolerance:.2f}x of committed")

    # the multi-job shared-pool scenario (shared factory, half job
    # length): a capacity grid through the compartment engine vs the
    # event-loop MultiJobSimulation — catches the multi-job path
    # silently recompiling per point or collapsing to the event oracle
    from benchmarks.engine_perf import multijob_bench_params

    q_mj = _quick_multijob_ab(*multijob_bench_params(job_length_scale=0.5),
                              n_replicas=64)
    b_mj = _lookup(baseline, "multijob.speedup_x")
    _gate("quick.multijob_speedup",
          b_mj is not None and q_mj >= tolerance * b_mj,
          f"measured {q_mj:.2f}x warm (4x64 grid) vs committed "
          f"{'MISSING' if b_mj is None else f'{b_mj:.2f}x'} (8x256); "
          f"floor {tolerance:.2f}x of committed")

    # the checkpoint-rollback scenario (shared factory, half job
    # length): an interval grid through the rollback lanes vs the event
    # engine's segment loop — catches the traced interval/cost axes
    # silently knocking the grid back onto the event fallback
    from benchmarks.engine_perf import checkpoint_bench_params

    kbase = checkpoint_bench_params().replace(
        job_length=0.5 * MINUTES_PER_DAY, max_run_records=66)
    q_ck = _quick_ab(kbase, "checkpoint_interval",
                     [15.0, 45.0, 80.0, 120.0], 64)
    b_ck = _lookup(baseline, "checkpoint.speedup_x")
    _gate("quick.checkpoint_speedup",
          b_ck is not None and q_ck >= tolerance * b_ck,
          f"measured {q_ck:.2f}x warm (4x64 grid) vs committed "
          f"{'MISSING' if b_ck is None else f'{b_ck:.2f}x'} (8x256); "
          f"floor {tolerance:.2f}x of committed")

    # the replica-sharded dispatch at mesh size 1: bit-identity is exact
    # (the contract, not a tolerance) and the shard_map tax must not
    # collapse throughput
    _quick_sharded(baseline, tolerance)


def _quick_sharded(baseline: dict, tolerance: float) -> None:
    """1-device-mesh retention + bit-identity, in-process (quick CI has
    one visible device; the multi-device curve is full-mode only)."""
    import numpy as np

    import repro.core.vectorized as vz
    from benchmarks.engine_perf import sweep_bench_params
    from repro.core import MINUTES_PER_DAY
    from repro.core.vectorized import default_max_steps

    base = sweep_bench_params().replace(job_length=0.5 * MINUTES_PER_DAY,
                                        max_run_records=67)
    pts = [base.replace(recovery_time=v)
           for v in (5.0, 15.0, 25.0, 35.0)]
    steps = max(default_max_steps(p) for p in pts)

    def run(shards):
        return vz.simulate_ctmc_sweep(pts, n_replicas=64, seed=0,
                                      max_steps=steps, shards=shards)

    sh = run(1)                                   # compile
    t0 = time.perf_counter()
    sh = run(1)
    sharded_s = time.perf_counter() - t0
    un = run(0)                                   # compile
    t0 = time.perf_counter()
    un = run(0)
    unsharded_s = time.perf_counter() - t0

    ident = all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
                for a, b in zip(sh, un) for k in a)
    _gate("quick.sharded_mesh1_bitident", ident,
          "1-device mesh output identical to unsharded engine")
    q_ret = unsharded_s / max(sharded_s, 1e-9)
    b_ret = _lookup(baseline, "sharded.retention_1dev")
    _gate("quick.sharded_retention",
          b_ret is not None and q_ret >= tolerance * b_ret,
          f"measured {q_ret:.2f} retention (4x64 grid) vs committed "
          f"{'MISSING' if b_ret is None else f'{b_ret:.2f}'}; "
          f"floor {tolerance:.2f}x of committed")


def _quick_multijob_ab(cluster, jobs, n_replicas):
    """Warm multi-job CTMC wall vs the event oracle on a 4-point grid."""
    from benchmarks.engine_perf import multijob_capacity_grid
    from repro.core import run_multijob_batch

    grid = multijob_capacity_grid(
        cluster.replace(max_run_records=64),   # quick-unique jit shapes
        jobs, spares=(7, 9), shops=(3, 4))
    run_multijob_batch(grid, n_replicas, engine="ctmc", base_seed=0)
    t0 = time.perf_counter()
    run_multijob_batch(grid, n_replicas, engine="ctmc", base_seed=0)
    ctmc_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_multijob_batch(grid, n_replicas, engine="event", base_seed=0)
    event_s = time.perf_counter() - t0
    return event_s / max(ctmc_s, 1e-9)


# ---------------------------------------------------------------------------
# full mode
# ---------------------------------------------------------------------------

def run_full(fresh: dict, baseline: dict, rel_tolerance: float) -> None:
    for key, floor in FULL_SPEEDUP_FLOORS.items():
        val = _lookup(fresh, key)
        _gate(f"full.{key}.floor", val is not None and val >= floor,
              f"{val if val is None else round(val, 2)}x >= {floor}x")
        base = _lookup(baseline, key)
        if base:
            ok = val is not None and val >= (1.0 - rel_tolerance) * base
            _gate(f"full.{key}.band", ok,
                  f"{val if val is None else round(val, 2)}x within "
                  f"{rel_tolerance:.0%} of baseline {round(base, 2)}x")
    for key, floor in FULL_VALUE_FLOORS.items():
        val = _lookup(fresh, key)
        _gate(f"full.{key}.floor", val is not None and val >= floor,
              f"{val if val is None else round(val, 3)} >= {floor}")
    val = _lookup(fresh, "sharded.mesh1_bitident")
    _gate("full.sharded.mesh1_bitident", val is True,
          f"1-device mesh bit-identical to unsharded engine: {val}")
    val = _lookup(fresh, "sharded.max_devices")
    _gate("full.sharded.max_devices", val is not None and val >= 4,
          f"weak-scaling curve reaches {val} forced host devices (>= 4)")
    for key, want in FULL_COMPILE_GATES.items():
        val = _lookup(fresh, key)
        _gate(f"full.{key}", val == want, f"{val} == {want}")
    for sec in ("", "structural.", "nonexp.", "repair_dist.",
                "empirical.", "correlated.", "multijob.", "checkpoint."):
        key = f"{sec}max_abs_z"
        val = _lookup(fresh, key)
        _gate(f"full.{key}", val is not None and val < 4.0,
              f"cross-engine agreement |z| {val and round(val, 2)} < 4.0")


def append_history(fresh: dict, path: str) -> None:
    record = {
        "ts": datetime.now(timezone.utc).isoformat(timespec="seconds"),
        "git": _git_sha(),
        "speedup_x": fresh.get("speedup_x"),
        "structural_warm_x": _lookup(
            fresh, "structural.padded_vs_per_structure_warm_x"),
        "structural_padded_compiles": _lookup(
            fresh, "structural.padded_compiles"),
        "bucketing_resize_x": _lookup(fresh, "bucketing.resize_speedup_x"),
        "bucketing_compiles": _lookup(fresh, "bucketing.bucketed_compiles"),
        "nonexp_speedup_x": _lookup(fresh, "nonexp.speedup_x"),
        "repair_dist_speedup_x": _lookup(fresh, "repair_dist.speedup_x"),
        "empirical_speedup_x": _lookup(fresh, "empirical.speedup_x"),
        "empirical_compiles": _lookup(fresh, "empirical.sweep_compiles"),
        "correlated_speedup_x": _lookup(fresh, "correlated.speedup_x"),
        "correlated_compiles": _lookup(fresh, "correlated.sweep_compiles"),
        "multijob_speedup_x": _lookup(fresh, "multijob.speedup_x"),
        "multijob_compiles": _lookup(fresh, "multijob.sweep_compiles"),
        "checkpoint_speedup_x": _lookup(fresh, "checkpoint.speedup_x"),
        "checkpoint_compiles": _lookup(fresh, "checkpoint.sweep_compiles"),
        "sharded_speedup_x": _lookup(fresh, "sharded.sharded_speedup_x"),
        "sharded_devices": _lookup(fresh, "sharded.max_devices"),
        "sharded_efficiency": _lookup(
            fresh, "sharded.min_weak_scaling_efficiency"),
        "sharded_compiles": _lookup(fresh, "sharded.sweep_compiles"),
    }
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")
    print(f"appended perf record to {path}: {record}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default="BENCH_sweep.json",
                    help="committed/saved baseline artifact")
    ap.add_argument("--fresh", default=None,
                    help="freshly measured artifact to gate (full mode)")
    ap.add_argument("--quick", action="store_true",
                    help="scaled-down re-measurement vs the baseline "
                         "(default when --fresh is absent)")
    ap.add_argument("--quick-sharded", action="store_true",
                    help="only the replica-sharding quick gates "
                         "(mesh-1 bit-identity + retention) — what the "
                         "multi-device CI job runs")
    ap.add_argument("--tolerance", type=float, default=0.2,
                    help="quick mode: fraction of the committed speedup "
                         "the small-grid measurement must reach")
    ap.add_argument("--rel-tolerance", type=float, default=0.5,
                    help="full mode: allowed relative drop vs baseline")
    ap.add_argument("--append-history", nargs="?", const="BENCH_history.jsonl",
                    default=None, help="append a timestamped record "
                    "(full mode, after the gates pass)")
    args = ap.parse_args()

    with open(args.baseline) as f:
        baseline = json.load(f)

    if args.fresh:
        with open(args.fresh) as f:
            fresh = json.load(f)
        run_full(fresh, baseline, args.rel_tolerance)
        if not _FAILURES and args.append_history:
            append_history(fresh, args.append_history)
    elif args.quick_sharded:
        import os
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        _quick_sharded(baseline, args.tolerance)
    else:
        run_quick(baseline, args.tolerance)

    if _FAILURES:
        print(f"\nbench gate FAILED: {_FAILURES}", file=sys.stderr)
        return 1
    print("\nbench gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
