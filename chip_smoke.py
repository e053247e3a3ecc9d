"""On-chip smoke test: the compiled CTMC sweep and its Pallas race kernel.

    python chip_smoke.py               # one TPU chip
    python chip_smoke.py --four-chips  # the replica-sharded sweep on 4 chips

One chip: the paper's Table-I cluster (a 4096-server job, 4160-server
working pool, 200 spares, 16 warm standbys, 32-day job) on the Fig. 2a
grid — recovery_time {10, 20, 30} x working_pool_size {4112, 4128,
4160, 4192} — at 16384 replicas per point, through
``run_replications_batch(..., engine="ctmc")`` with the default race
kernel, plus a 3-job shared-pool capacity grid through
``run_multijob_batch(..., engine="ctmc")``.  It checks

  (a) that JAX runs on a TPU (there is no CPU path);
  (b) the Pallas race kernel against the pure-jnp reference at the
      engine's widths over 262144 replicas;
  (c) that every point ran on the ctmc engine to completion with finite
      stats, that the grid under ``impl="ref"`` agrees with it, and that
      every point's mean total time agrees with the committed CPU result
      ``results/fig2a_recovery_time.csv`` (|z| < 4);
  (d) that the timed warm run compiles nothing.

``--four-chips`` runs only the grid sharded over 4 chips and, for each
shard s, the unsharded one-chip run over that shard's replicas seeded
with ``fold_in(key, s)``; the two must be exactly equal.

Everything is generated from ``SEED``.  Timings name the device they
were measured on.  The last stdout line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed
check exits non-zero before printing it.
"""

from __future__ import annotations

import argparse
import csv
import json
import math
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
SEED = 11
REPLICAS = 16384
MJ_REPLICAS = 4096
#: the committed CPU run of the same grid (256 replicas, seed 0)
FIG2A_CSV = ROOT / "results" / "fig2a_recovery_time.csv"
Z_CSV = 4.0
Z_REF = 3.5


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
        sys.exit(1)


def require_tpu():
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"needs a TPU, but JAX found platform {dev.platform!r} "
          f"({dev.device_kind}); there is no CPU path")
    return dev


def fig2a_grid():
    from benchmarks.paper_tables import POOL_SIZES, paper_params
    from repro.core.params import PAPER_TABLE1_RANGES

    return [paper_params(recovery_time=v, working_pool_size=w)
            for v in PAPER_TABLE1_RANGES["recovery_time"]
            for w in POOL_SIZES]


def kernel_phase(kind: str) -> None:
    """(b) Pallas vs reference race at the engine's real widths."""
    from benchmarks.engine_perf import correlated_bench_params
    from repro.kernels import ops

    B = 16 * REPLICAS
    n_dom = correlated_bench_params().fault_domains.n_domains
    for k_exp, k_det, label in ((16, 3, "exponential"),
                                (16 + n_dom, 4, "correlated")):
        kr, ko, kd, kt, kp = jax.random.split(
            jax.random.PRNGKey(SEED + k_exp), 5)
        on = jax.random.uniform(ko, (B, k_exp)) > 0.2    # some lanes off
        rates = jax.random.uniform(kr, (B, k_exp), maxval=2.0) * on
        resid = jax.random.uniform(kd, (B, k_det), minval=0.01, maxval=5.0)
        resid = resid.at[: B // 4, 0].set(np.inf)       # some timers off
        ut = jax.random.uniform(kt, (B,), minval=1e-6, maxval=1.0)
        up = jax.random.uniform(kp, (B,))
        outs = {}
        for impl in ("pallas", "ref"):
            fn = jax.jit(lambda r, d, a, b, impl=impl: ops.event_race(
                r, d, a, b, impl=impl))
            if impl == "pallas":
                has_cc = "tpu_custom_call" in fn.lower(
                    rates, resid, ut, up).compile().as_text()
            outs[impl] = [np.asarray(x) for x in
                          jax.block_until_ready(fn(rates, resid, ut, up))]
        (dt_p, ev_p), (dt_r, ev_r) = outs["pallas"], outs["ref"]
        fin = np.isfinite(dt_r)
        check(np.array_equal(fin, np.isfinite(dt_p)), "dt finiteness differs")
        rel = float(np.max(np.abs(dt_p[fin] - dt_r[fin])
                           / np.maximum(np.abs(dt_r[fin]), 1e-30)))
        n_ev = int(np.sum(ev_p != ev_r))
        log(f"kernel[{label}] B={B} K_exp={k_exp} K_det={k_det} on {kind}: "
            f"tpu_custom_call={has_cc} max_rel_dt={rel:.3e} "
            f"event_mismatches={n_ev}")
        check(has_cc, "the compiled race holds no tpu_custom_call")
        check(rel <= 1e-6, f"kernel dt differs from ref by rel {rel:.3e}")
        check(n_ev == 0, f"kernel events differ from ref on {n_ev} rows")


def _finite_stats(rep) -> bool:
    return all(math.isfinite(s.mean) and math.isfinite(s.std)
               for s in rep.stats.values())


def _z(m1, se1, m2, se2) -> float:
    se = math.hypot(se1, se2)
    return 0.0 if se == 0.0 and m1 == m2 else (m1 - m2) / se


def grid_phase(kind: str) -> None:
    """(c)+(d) the Fig. 2a sweep on the default kernel."""
    import repro.core.vectorized as vz
    from repro.core import run_replications_batch
    from repro.kernels import ops

    impl = ops._default_impl()
    log(f"event_race impl resolved to {impl!r} (default_backend="
        f"{jax.default_backend()!r})")
    check(impl == "pallas", f"default race impl is {impl!r}, not 'pallas'")
    grid = fig2a_grid()
    [(_, R_run, run, args, kw)] = vz.sweep_programs(grid, REPLICAS, SEED)
    P_run = args[2]

    t0 = time.perf_counter()
    compiled = run.lower(*args, **kw).compile()
    compile_s = time.perf_counter() - t0
    has_cc = "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    log(f"grid program: {P_run}x{R_run} rows, tpu_custom_call={has_cc}, "
        f"argument_bytes={mem.argument_size_in_bytes} "
        f"temp_bytes={mem.temp_size_in_bytes}")
    log(f"[measured on {kind}] grid compile_s={compile_s:.3f}")
    check(has_cc, "the compiled sweep program holds no tpu_custom_call")

    def study(**kw_):
        return run_replications_batch(grid, REPLICAS, engine="ctmc",
                                      base_seed=SEED, **kw_)

    t0 = time.perf_counter()
    reps = study()
    first_s = time.perf_counter() - t0
    c0 = vz.compile_cache_size()
    t0 = time.perf_counter()
    reps = study()
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(run(*args, **kw))
    device_s = time.perf_counter() - t0
    warm_compiles = vz.compile_cache_size() - c0
    chunks, steps = int(out["chunks_run"]), int(out["steps_run"])
    del out
    log(f"[measured on {kind}] grid first_call_s={first_s:.3f} "
        f"warm_study_s={warm_s:.3f} warm_program_s={device_s:.3f} "
        f"(block_until_ready) chunks_run={chunks} steps_run={steps} "
        f"compiles_in_warm_window={warm_compiles}")
    check(warm_compiles == 0,
          f"the warm window compiled {warm_compiles} programs")

    reps_ref = study(impl="ref")
    same = all(np.array_equal(a.arrays[k], b.arrays[k], equal_nan=True)
               for a, b in zip(reps, reps_ref) for k in a.arrays)
    max_diff = 0.0 if same else max(
        float(np.max(np.abs(a.arrays[k].astype(np.float64)
                            - b.arrays[k].astype(np.float64))))
        for a, b in zip(reps, reps_ref) for k in a.arrays
        if a.arrays[k].size)
    log(f"pallas vs ref on {kind}: bit_identical={same} "
        f"max_abs_diff={max_diff!r}")

    with open(FIG2A_CSV) as f:
        cpu = {(float(r["recovery_time"]), int(r["working_pool_size"])): r
               for r in csv.DictReader(f)}
    n = REPLICAS
    for p, rep, rep_ref in zip(grid, reps, reps_ref):
        completed = float(rep.arrays["completed"].mean())
        tt = rep.stats["total_time"]
        mean_h, se_h = tt.mean / 60.0, tt.std / math.sqrt(n) / 60.0
        row = cpu[(p.recovery_time, p.working_pool_size)]
        z_csv = _z(mean_h, se_h, float(row["total_time_hours"]),
                   float(row["total_time_ci95_hours"]) / 1.96)
        z_ref = max(abs(_z(rep.stats[m].mean, rep.stats[m].std / math.sqrt(n),
                           rep_ref.stats[m].mean,
                           rep_ref.stats[m].std / math.sqrt(n)))
                    for m in ("total_time", "n_failures", "stall_time"))
        log(f"point recovery_time={p.recovery_time:g} "
            f"pool={p.working_pool_size}: engine={rep.engine} "
            f"replicas={rep.n} completed={completed} "
            f"total_time_h={mean_h:.4f}+-{se_h:.4f} "
            f"csv_h={float(row['total_time_hours']):.4f} z_csv={z_csv:+.3f} "
            f"max|z_ref|={z_ref:.3f}")
        check(rep.engine == "ctmc", f"point ran on {rep.engine}")
        check(completed == 1.0, f"completed share {completed} < 1")
        check(_finite_stats(rep), "a stat is not finite")
        check(abs(z_csv) < Z_CSV, f"|z| vs the CPU csv is {abs(z_csv):.2f}")
        check(same or z_ref < Z_REF, f"pallas vs ref |z| = {z_ref:.2f}")


def multijob_phase(kind: str) -> None:
    """The second compiled engine: 3 jobs sharing one pool and shop."""
    from benchmarks.engine_perf import (multijob_bench_params,
                                        multijob_capacity_grid)
    from repro.core import run_multijob_batch

    cluster, jobs = multijob_bench_params()
    grid = multijob_capacity_grid(cluster, jobs, (7, 8, 9, 10), (3, 4))
    t0 = time.perf_counter()
    reps = run_multijob_batch(grid, MJ_REPLICAS, engine="ctmc",
                              base_seed=SEED)
    wall = time.perf_counter() - t0
    log(f"[measured on {kind}] multijob {len(grid)} points x {MJ_REPLICAS} "
        f"replicas x {len(jobs)} jobs: first_call_s={wall:.3f}")
    for (c, _), rep in zip(grid, reps):
        done = rep.fleet["completed"].mean
        log(f"multijob spares={c.spare_pool_size} shop={c.repair_servers}: "
            f"engine={rep.engine} completed={done} "
            f"makespan_h={rep.fleet['makespan'].mean / 60:.4f}")
        check(rep.engine == "ctmc", f"multijob point ran on {rep.engine}")
        check(done == 1.0, f"multijob completed share {done} < 1")
        check(all(math.isfinite(s.mean) for s in rep.fleet.values())
              and all(_finite_stats(r) for r in rep.per_job),
              "a multijob stat is not finite")


def four_chip_phase(kind: str) -> None:
    """The sharded grid on 4 chips against its per-shard references."""
    from repro.core import run_replications_batch
    from repro.parallel import sharding as rsharding

    n = 4
    check(len(jax.devices()) >= n,
          f"--four-chips needs {n} devices, found {len(jax.devices())}")
    grid = fig2a_grid()
    mesh = rsharding.replica_mesh(n)
    ids = sorted(d.id for d in mesh.devices.flat)
    t0 = time.perf_counter()
    sharded = run_replications_batch(
        [p.replace(engine_shards=n) for p in grid], REPLICAS,
        engine="ctmc", base_seed=SEED)
    wall = time.perf_counter() - t0
    peaks = [d.memory_stats()["peak_bytes_in_use"]
             for d in mesh.devices.flat]
    log(f"[measured on {n} x {kind}] sharded grid first_call_s={wall:.3f} "
        f"mesh_device_ids={ids} peak_bytes_in_use={peaks}")
    check(len(set(ids)) == n, f"mesh devices are not distinct: {ids}")
    # each shard holds 16 x 4096 rows of ~2.3 KB of state
    check(min(peaks) > 16 * (REPLICAS // n) * 2048,
          f"a mesh device never held its shard's state: {peaks}")

    R_loc = REPLICAS // n
    keys = rsharding.shard_keys(jax.random.PRNGKey(SEED), n)
    for s in range(n):
        t0 = time.perf_counter()
        refs = run_replications_batch(grid, R_loc, engine="ctmc",
                                      base_seed=keys[s])
        wall = time.perf_counter() - t0
        rows = slice(s * R_loc, (s + 1) * R_loc)
        bad = [k for a, b in zip(sharded, refs) for k, v in a.arrays.items()
               if not np.array_equal(
                   v[rows] if v.ndim and v.shape[0] == REPLICAS else v,
                   b.arrays[k], equal_nan=True)]
        log(f"[measured on {kind}] shard {s}: reference {len(grid)}x{R_loc} "
            f"first_call_s={wall:.3f}; exactly_equal={not bad} "
            f"(differing lanes: {sorted(set(bad))})")
        check(not bad, f"shard {s} differs from its fold_in reference")


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run only the 4-chip sharded grid and its "
                             "per-shard one-chip references")
    args = parser.parse_args()

    dev = require_tpu()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro import compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x {len(jax.devices())}")
    log(f"compile cache: {compile_cache.enable()}")
    if args.four_chips:
        four_chip_phase(dev.device_kind)
    else:
        kernel_phase(dev.device_kind)
        grid_phase(dev.device_kind)
        multijob_phase(dev.device_kind)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
