"""Benchmark driver: one function per paper table/figure + engine perf.

Prints ``name,us_per_call,derived`` CSV rows (one per benchmark) and
writes the full tables/plots under results/.

  * fig2a / fig2b        — paper Fig 2 reproductions (two-way sweeps)
  * table1_sensitivity   — the remaining Table-I knobs x pool size
  * engine_event / engine_ctmc / kernel_event_race — engine throughput
  * engine_sweep         — batched-CTMC vs event-driven grid sweep; also
    written as machine-readable BENCH_sweep.json (perf trajectory for CI)
  * roofline             — per (arch x shape) table from results/dryrun.json
    (run ``python -m repro.launch.dryrun`` first; skipped if absent)

Use REPRO_BENCH_FAST=1 for a quick pass (fewer replicas).
"""

from __future__ import annotations

import json
import os
import sys
import time

FAST = os.environ.get("REPRO_BENCH_FAST", "0") == "1"
RESULTS = os.environ.get("REPRO_RESULTS", "results")


def _row(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}")
    sys.stdout.flush()


def main() -> None:
    from benchmarks import engine_perf, paper_tables
    from repro import compile_cache

    compile_cache.enable()

    n_rep = 64 if FAST else 256

    t0 = time.perf_counter()
    rows = paper_tables.fig2a(n_replicas=n_rep)
    base = min(r["total_time_hours"] for r in rows)
    worst = max(r["total_time_hours"] for r in rows)
    _row("fig2a_recovery_time", (time.perf_counter() - t0) * 1e6,
         f"train_hours {base:.1f}..{worst:.1f} over recovery 10..30min")

    t0 = time.perf_counter()
    rows = paper_tables.fig2b(n_replicas=n_rep)
    base = min(r["total_time_hours"] for r in rows)
    worst = max(r["total_time_hours"] for r in rows)
    _row("fig2b_waiting_time", (time.perf_counter() - t0) * 1e6,
         f"train_hours {base:.1f}..{worst:.1f} over waiting 10..30min")

    t0 = time.perf_counter()
    rows = paper_tables.sensitivity(n_replicas=32 if FAST else 128)
    effects = paper_tables.effect_sizes(rows)
    flat = sum(1 for v in effects.values() if v < 0.05)
    _row("table1_sensitivity", (time.perf_counter() - t0) * 1e6,
         f"{flat}/{len(effects)} knobs flat (<5% effect); "
         f"max effect {max(effects.values()):.3f}")

    t0 = time.perf_counter()
    ev = engine_perf.event_engine_throughput(n_runs=2 if FAST else 5)
    _row("engine_event", (time.perf_counter() - t0) * 1e6,
         f"{ev['events_per_s']:.0f} events/s")

    t0 = time.perf_counter()
    ct = engine_perf.ctmc_engine_throughput(n_replicas=512 if FAST else 2048)
    _row("engine_ctmc", (time.perf_counter() - t0) * 1e6,
         f"{ct['replicas_per_s']:.1f} trajectories/s")

    t0 = time.perf_counter()
    k = engine_perf.event_race_kernel()
    _row("kernel_event_race", k["us_per_call"],
         f"{k['races_per_s'] / 1e6:.1f}M races/s")

    sp = engine_perf.speedup_summary()
    _row("engine_speedup", 0.0,
         f"ctmc {sp['speedup_x']:.1f}x faster per trajectory")

    t0 = time.perf_counter()
    sw = engine_perf.sweep_throughput(n_points=8,
                                      n_replicas=64 if FAST else 256)
    _row("engine_sweep", (time.perf_counter() - t0) * 1e6,
         f"batched ctmc {sw['speedup_x']:.1f}x faster than event loop "
         f"({sw['event_wall_s']:.1f}s -> {sw['ctmc_wall_s']:.2f}s, "
         f"max |z| {sw['max_abs_z']:.2f})")

    t0 = time.perf_counter()
    st = engine_perf.structural_sweep_throughput(
        n_points=8, n_replicas=64 if FAST else 256)
    _row("engine_structural_sweep", (time.perf_counter() - t0) * 1e6,
         f"padded {st['padded_compiles']} compile vs per-structure "
         f"{st['per_structure_compiles']}: "
         f"{st['padded_vs_per_structure_x']:.1f}x cold / "
         f"{st['padded_vs_per_structure_warm_x']:.1f}x warm, "
         f"max |z| {st['max_abs_z']:.2f}")
    sw["structural"] = st
    engine_perf.write_sweep_artifact(sw)

    # roofline table from the dry-run artifact
    dryrun_path = os.path.join(RESULTS, "dryrun.json")
    if os.path.exists(dryrun_path):
        with open(dryrun_path) as f:
            recs = json.load(f)
        ok = [r for r in recs if r.get("status") == "OK"]
        if ok:
            worst = min(ok, key=lambda r: r["roofline"]["roofline_fraction"])
            best = max(ok, key=lambda r: r["roofline"]["roofline_fraction"])
            _row("roofline", 0.0,
                 f"{len(ok)} cells; frac {worst['roofline']['roofline_fraction']:.3f}"
                 f" ({worst['arch']}/{worst['shape']}) .. "
                 f"{best['roofline']['roofline_fraction']:.3f}"
                 f" ({best['arch']}/{best['shape']})")
    else:
        _row("roofline", 0.0, "SKIPPED (run repro.launch.dryrun first)")


if __name__ == "__main__":
    main()
