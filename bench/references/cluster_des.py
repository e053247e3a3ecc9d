"""Plain reference of the single-job cluster model (AIReSim, arXiv:2603.07041).

One replica at a time, one event at a time, in plain Python: a job of
``job_size`` servers runs on a cluster with a working pool, a spare pool
and warm standbys; servers fail, are diagnosed, repaired and returned.
It is written from the paper's description of the model (§III, Table I)
and imports nothing of the simulator it checks.  Times are in minutes.

The model, as this file implements it:

* The fleet is ``working_pool_size + spare_pool_size`` servers, of which
  ``round(systematic_failure_fraction * fleet)`` are bad, drawn at
  random.  At ``host_selection_time`` the job takes ``job_size``
  servers and ``warm_standbys`` standbys from the working pool, chosen
  at random.
* While the job computes, every running server fails at
  ``random_failure_rate``; a bad one also at ``systematic_failure_rate``
  (exponential clocks, restarted at every restart of the job).
* A failure stops the job.  With ``diagnosis_probability`` the failed
  server is found and sent to repair; a replacement comes from the
  standbys (no wait), else the working pool (``host_selection_time``),
  else the spare pool (``waiting_time + host_selection_time``), else
  the job stalls until a repaired server returns.  Then the job pays
  ``recovery_time`` and computes again.  An undiagnosed failure keeps
  the failed server and pays ``recovery_time`` alone.
* Repair: an automated stage (mean ``auto_repair_time``); with
  ``automated_repair_probability`` it ends there and succeeds with
  ``1 - auto_repair_failure_probability``, otherwise a manual stage
  (mean ``manual_repair_time``) follows and succeeds with
  ``1 - manual_repair_failure_probability``.  Success makes a bad
  server good.  A repaired server refills the standbys up to
  ``warm_standbys``, else goes back to the pool it came from; a stalled
  job takes it at once.
* Every draw from a pool, the standbys or the running set picks a
  server uniformly at random.

Outputs follow the layout of the simulator's per-replica arrays, so one
comparison reads both: counters, ``total_time``, a ring of the last
``max_run_records`` compute intervals with ``n_runs``, and per-channel
histogram counts over log-spaced bins (``run_duration``: compute
interval per run; ``recovery``: failure to restart; ``waiting``:
failure to replacement in place).

``rnd`` rounds every stored real and every counter, so the same code
runs in a lower precision (the benchmark's control).  A replica that
has not finished after ``max_failures`` failures stops and reports
``completed`` 0.
"""

from __future__ import annotations

import bisect
import heapq
import math
import random
from typing import Callable, Dict, List, Optional

import numpy as np

#: Params keys this reference models; anything else set away from its
#: default makes :func:`check_supported` refuse the configuration.
MODELLED = {
    "random_failure_rate", "systematic_failure_rate",
    "systematic_failure_fraction", "recovery_time", "job_size",
    "job_length", "warm_standbys", "working_pool_size", "spare_pool_size",
    "host_selection_time", "waiting_time", "auto_repair_time",
    "manual_repair_time", "auto_repair_failure_probability",
    "manual_repair_failure_probability", "automated_repair_probability",
    "diagnosis_probability", "repair_distribution", "distribution_kwargs",
    "max_run_records", "histogram", "failure_distribution",
    "engine_shards", "event_race_impl", "seed",
}
#: keys that may be present but must keep the paper model's value
NEUTRAL = {"diagnosis_uncertainty": 0.0, "checkpoint_interval": 0.0,
           "checkpoint_cost": 0.0, "preemption_cost": 0.0,
           "bad_set_regeneration_period": 0.0, "retirement_threshold": 0,
           "standbys_can_fail": False, "repair_servers": 0,
           "fault_domains": None, "campaign": None}

CHANNELS = ("run_duration", "recovery", "waiting")


def check_supported(p: Dict) -> None:
    """Raise ValueError if ``p`` sets anything this reference does not model."""
    for k, v in p.items():
        if k in NEUTRAL:
            if v != NEUTRAL[k]:
                raise ValueError(f"reference does not model {k}={v!r}")
        elif k not in MODELLED:
            raise ValueError(f"reference does not know the key {k!r}")
    if p.get("failure_distribution", "exponential") != "exponential":
        raise ValueError("reference models exponential failures only")
    if p.get("repair_distribution", "exponential") not in ("exponential",
                                                           "lognormal"):
        raise ValueError("reference models exponential or lognormal "
                         "repairs only")


def edges(spec: Optional[Dict]) -> Optional[List[float]]:
    """Log-spaced histogram edges: ``n_bins + 1`` from ``low`` to
    ``high`` (None without a histogram)."""
    if not spec:
        return None
    lo, hi, n = math.log(spec["low"]), math.log(spec["high"]), spec["n_bins"]
    return [math.exp(lo + (hi - lo) * i / n) for i in range(n + 1)]


def _stage_sampler(p: Dict, mean: float, rng: random.Random):
    if p.get("repair_distribution", "exponential") == "exponential":
        return lambda: rng.expovariate(1.0 / mean)
    sigma = p["distribution_kwargs"]["sigma"]
    mu = math.log(mean) - 0.5 * sigma * sigma
    return lambda: rng.lognormvariate(mu, sigma)


def _pick(counts: List, rng: random.Random) -> int:
    """Class of a server drawn uniformly from per-class ``counts``."""
    u = rng.random() * sum(counts)
    acc = 0.0
    for c, n in enumerate(counts):
        acc += n
        if u < acc and n > 0:
            return c
    return max(c for c, n in enumerate(counts) if n > 0)


def replica(p: Dict, rng: random.Random, rnd: Callable[[float], float],
            max_failures: int) -> Dict:
    """Simulate one replica; returns its outputs (scalars and lists)."""
    # classes: 0 working-good, 1 working-bad, 2 spare-good, 3 spare-bad
    W, S = p["working_pool_size"], p["spare_pool_size"]
    J, K = p["job_size"], p["warm_standbys"]
    n_bad = int(round(p["systematic_failure_fraction"] * (W + S)))
    bad = rng.sample(range(W + S), n_bad)
    bad_w = sum(1 for i in bad if i < W)
    # place the working pool's bad servers at random positions: the
    # first J positions are the job, the next K the standbys
    pos = rng.sample(range(W), bad_w)
    run_b = sum(1 for i in pos if i < J)
    sb_b = sum(1 for i in pos if J <= i < J + min(K, W - J))
    n_sb = min(K, W - J)
    run = [J - run_b, run_b, 0, 0]
    sb = [n_sb - sb_b, sb_b, 0, 0]
    fw = [W - J - n_sb - (bad_w - run_b - sb_b), bad_w - run_b - sb_b, 0, 0]
    fs = [0, 0, S - (n_bad - bad_w), n_bad - bad_w]

    r, s = p["random_failure_rate"], p["systematic_failure_rate"]
    auto_stage = _stage_sampler(p, p["auto_repair_time"], rng)
    man_stage = _stage_sampler(p, p["manual_repair_time"], rng)
    shop: list = []          # (return time, seq, class, auto end, manual)
    seq = 0
    out = {"n_failures": 0.0, "n_host_selections": 0.0,
           "n_standby_swaps": 0.0, "n_preemptions": 0.0,
           "n_auto_repairs": 0.0, "n_manual_repairs": 0.0,
           "n_undiagnosed": 0.0, "stall_time": 0.0}
    runs: List[float] = []
    rec: Dict[str, List[float]] = {ch: [] for ch in CHANNELS}

    def bump(key, by=1.0):
        out[key] = rnd(out[key] + by)

    def give_back(c):
        """A repaired server of class ``c`` while the job does not stall."""
        if sum(sb) < K:
            sb[c] += 1
        elif c < 2:
            fw[c] += 1
        else:
            fs[c] += 1

    def repaired_before(t_end):
        """Return every server whose repair ends by ``t_end``."""
        while shop and shop[0][0] <= t_end:
            _, _, c, _, manual = heapq.heappop(shop)
            bump("n_auto_repairs")
            if manual:
                bump("n_manual_repairs")
            give_back(c)

    t = rnd(p["host_selection_time"])
    left = rnd(p["job_length"])
    completed = True
    failures = 0                    # exact count for the cap
    while True:
        rates = [run[0] * r, run[1] * (r + s), run[2] * r, run[3] * (r + s)]
        total = sum(rates)
        ttf = rng.expovariate(total) if total > 0 else math.inf
        if left <= ttf:
            repaired_before(t + left)
            runs.append(rnd(left))
            t = rnd(t + left)
            break
        if failures >= max_failures:
            completed = False
            break
        failures += 1
        repaired_before(t + ttf)
        t = rnd(t + ttf)
        left = rnd(left - ttf)
        runs.append(rnd(ttf))
        bump("n_failures")
        c = _pick(rates, rng)
        t_fail = t
        if rng.random() < p["diagnosis_probability"]:
            run[c] -= 1
            d_auto = auto_stage()
            manual = rng.random() >= p["automated_repair_probability"]
            d = d_auto + man_stage() if manual else d_auto
            fail_p = (p["manual_repair_failure_probability"] if manual
                      else p["auto_repair_failure_probability"])
            if rng.random() >= fail_p:
                c_back = c & 2          # success: the server is good now
            else:
                c_back = c
            seq += 1
            heapq.heappush(shop, (t + d, seq, c_back, t + d_auto, manual))
            if sum(sb):
                n = _pick(sb, rng)
                sb[n] -= 1
                bump("n_standby_swaps")
            elif sum(fw):
                n = _pick(fw, rng)
                fw[n] -= 1
                repaired_before(t + p["host_selection_time"])
                t = rnd(t + p["host_selection_time"])
                bump("n_host_selections")
            elif sum(fs):
                n = _pick(fs, rng)
                fs[n] -= 1
                wait = p["waiting_time"] + p["host_selection_time"]
                repaired_before(t + wait)
                t = rnd(t + wait)
                bump("n_preemptions")
                bump("n_host_selections")
            else:                       # stall: the next repaired server
                t_ret, _, n, _, man = heapq.heappop(shop)
                bump("n_auto_repairs")
                if man:
                    bump("n_manual_repairs")
                out["stall_time"] = rnd(out["stall_time"] + (t_ret - t))
                t = rnd(t_ret)
            run[n] += 1
        else:
            bump("n_undiagnosed")
        rec["waiting"].append(rnd(t - t_fail))
        repaired_before(t + p["recovery_time"])
        t = rnd(t + p["recovery_time"])
        rec["recovery"].append(rnd(t - t_fail))
    # repairs whose automated stage ended before the job did
    out["n_auto_repairs"] = rnd(out["n_auto_repairs"]
                                + sum(1 for e in shop if e[3] <= t))
    rec["run_duration"] = runs
    out["total_time"] = t
    out["completed"] = 1.0 if completed else 0.0
    out["runs"] = runs
    out["records"] = rec
    return out


def simulate_point(p: Dict, n: int, seed: int,
                   rnd: Optional[Callable[[float], float]] = None,
                   max_failures: Optional[int] = None) -> Dict[str, np.ndarray]:
    """``n`` replicas of point ``p`` as per-replica arrays.

    Replica ``i`` draws from ``random.Random(seed, i)``-style streams, so
    the same seed gives the same arrays.  ``max_failures`` defaults to
    ten times the failures the job would see with no repairs at all.
    """
    check_supported(p)
    rnd = rnd or (lambda x: x)
    if max_failures is None:
        fleet_rate = p["job_size"] * (p["random_failure_rate"]
                                      + p["systematic_failure_fraction"]
                                      * p["systematic_failure_rate"])
        max_failures = int(10 * fleet_rate * p["job_length"]) + 100
    spec = p["histogram"]
    edge = edges(spec) if spec else None
    ring = p["max_run_records"]
    scalars = ("total_time", "completed", "n_failures", "n_host_selections",
               "n_standby_swaps", "n_preemptions", "n_auto_repairs",
               "n_manual_repairs", "n_undiagnosed", "stall_time")
    arrays = {k: np.zeros(n) for k in scalars}
    arrays["n_runs"] = np.zeros(n, np.int64)
    arrays["run_durations"] = np.zeros((n, ring))
    channels = [ch for ch in spec["channels"] if ch in CHANNELS] if spec else []
    for ch in channels:
        arrays[f"hist_{ch}"] = np.zeros((n, spec["n_bins"] + 2))
    for i in range(n):
        rng = random.Random(f"{seed}:{i}")
        o = replica(p, rng, rnd, max_failures)
        for k in scalars:
            arrays[k][i] = o[k]
        runs = o["runs"]
        arrays["n_runs"][i] = len(runs)
        for j, v in enumerate(runs):          # slot = run index mod ring
            arrays["run_durations"][i, j % ring] = v
        for ch in channels:
            counts = arrays[f"hist_{ch}"][i]
            for v in o["records"][ch]:
                b = bisect.bisect_right(edge, v)
                counts[b] = rnd(counts[b] + 1.0)
    return arrays
