"""Paper reproductions: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (one per benchmark) and
writes the full tables/plots under results/.

  * fig2a / fig2b        — paper Fig 2 reproductions (two-way sweeps)
  * table1_sensitivity   — the remaining Table-I knobs x pool size

The chip benchmark (``bench/``, ``BENCHMARK.json``) measures the
engine's speed.  Use REPRO_BENCH_FAST=1 for a quick pass (fewer
replicas).
"""

from __future__ import annotations

import os
import sys
import time

FAST = os.environ.get("REPRO_BENCH_FAST", "0") == "1"


def _row(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}")
    sys.stdout.flush()


def main() -> None:
    from benchmarks import paper_tables
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


if __name__ == "__main__":
    main()
