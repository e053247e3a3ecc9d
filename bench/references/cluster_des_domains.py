"""Plain reference of the single-job cluster model with rack and pod
fault domains (AIReSim, arXiv:2603.07041; docs/scenarios.md).

One replica at a time, one event at a time, in plain Python, with every
server tracked by its id.  It models what ``cluster_des`` models (read
that file's docstring for the base model: pools, standbys, failures,
diagnosis, the waterfall, the two-stage repair shop) and adds fault
domains.  It imports nothing of the simulator it checks.  Times are in
minutes.

The fault domains, as this file implements them:

* Servers ``0 .. working_pool_size - 1`` make the working pool and the
  rest the spare pool.  Server ``sid`` sits in rack ``sid % n_racks``;
  rack ``r`` in pod ``r // racks_per_pod`` (no pods when
  ``racks_per_pod`` is 0).
* Every rack shocks at ``rack_shock_rate`` and every pod at
  ``pod_shock_rate`` (exponential clocks, running whatever the job
  does).  The equal-rate clocks of a level are merged into one Poisson
  stream of the level's total rate, and the struck domain is drawn
  uniformly among the level's domains: the same process.
* A shock fails every member of the domain, wherever it is: running,
  standby, free in either pool, or in the repair shop, where its
  current stage starts over.  A struck server keeps its health class
  and goes through the normal repair chain.  Shock kills are not
  counted in ``n_failures``.
* Struck running servers end the job's compute interval (if it was
  computing) and are replaced at once, as one group, through the
  standby -> working pool -> spare pool waterfall, each draw uniform.
  The group pays one restart wait: ``recovery_time``, plus
  ``host_selection_time`` if any pool draw happened, plus
  ``waiting_time`` if any spare draw happened.  This replaces any
  restart the job was waiting for.  If the pools cannot cover the
  group, the job stalls until repairs return the whole shortfall, then
  pays ``recovery_time``.
* A repaired server goes to a stalled job first, then refills the
  standbys up to ``warm_standbys``, else returns to its own pool.
* A restart's waiting and recovery times are recorded when the restart
  is planned (a failure's replacement found, or a stall over), with the
  planned values.

Outputs have the layout of ``cluster_des`` (and of the simulator's
per-replica arrays).  ``rnd`` rounds every stored real and every
counter, so the same code runs in a lower precision (the benchmark's
control).  A replica that has not finished after ``max_failures``
failures stops and reports ``completed`` 0.
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
    "engine_shards", "event_race_impl", "seed", "fault_domains",
}
#: keys that may be present but must keep the paper model's value
NEUTRAL = {"diagnosis_uncertainty": 0.0, "checkpoint_interval": 0.0,
           "checkpoint_cost": 0.0, "preemption_cost": 0.0,
           "bad_set_regeneration_period": 0.0, "retirement_threshold": 0,
           "standbys_can_fail": False, "repair_servers": 0,
           "campaign": None}
#: the keys of ``fault_domains``
TOPOLOGY = {"n_racks", "racks_per_pod", "rack_shock_rate", "pod_shock_rate"}

CHANNELS = ("run_duration", "recovery", "waiting")
COMPUTE, OVERHEAD, STALL = 0, 1, 2
#: where a server is
RUN, SB, FW, FS, SHOP = range(5)


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
    topo = p.get("fault_domains")
    if topo is not None:
        if not isinstance(topo, dict) or set(topo) - TOPOLOGY \
                or "n_racks" not in topo:
            raise ValueError(f"reference does not model fault_domains="
                             f"{topo!r}")
        fleet = p["working_pool_size"] + p["spare_pool_size"]
        if not 1 <= topo["n_racks"] <= fleet:
            raise ValueError("every rack must hold a server")
        if topo.get("pod_shock_rate", 0.0) > 0 \
                and not topo.get("racks_per_pod", 0):
            raise ValueError("pod shocks need racks_per_pod >= 1")


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


class Pool:
    """A set of server ids with a uniform draw and O(1) removal."""

    def __init__(self, ids=()):
        self.ids: List[int] = []
        self.pos: Dict[int, int] = {}
        for s in ids:
            self.add(s)

    def __len__(self) -> int:
        return len(self.ids)

    def add(self, sid: int) -> None:
        self.pos[sid] = len(self.ids)
        self.ids.append(sid)

    def remove(self, sid: int) -> None:
        i = self.pos.pop(sid)
        last = self.ids.pop()
        if last != sid:
            self.ids[i] = last
            self.pos[last] = i

    def draw(self, rng: random.Random) -> int:
        sid = self.ids[int(rng.random() * len(self.ids))]
        self.remove(sid)
        return sid


def members(sid_count: int, n_racks: int, racks_per_pod: int,
            domain: int) -> List[int]:
    """Server ids of ``domain``: racks ``0 .. n_racks - 1``, then pods."""
    if domain < n_racks:
        return list(range(domain, sid_count, n_racks))
    lo = (domain - n_racks) * racks_per_pod
    racks = range(lo, min(lo + racks_per_pod, n_racks))
    return [s for r in racks for s in range(r, sid_count, n_racks)]


def replica(p: Dict, rng: random.Random, rnd: Callable[[float], float],
            max_failures: int) -> Dict:
    """Simulate one replica; returns its outputs (scalars and lists)."""
    W, S = p["working_pool_size"], p["spare_pool_size"]
    J, K = p["job_size"], p["warm_standbys"]
    N = W + S
    n_bad = int(round(p["systematic_failure_fraction"] * N))
    bad = [False] * N
    for s in rng.sample(range(N), n_bad):
        bad[s] = True
    order = rng.sample(range(W), W)       # the working pool, shuffled
    n_sb = min(K, W - J)
    where = [FW] * W + [FS] * S
    run = {False: Pool(), True: Pool()}   # running, by health class
    for s in order[:J]:
        run[bad[s]].add(s)
        where[s] = RUN
    sb = Pool(order[J:J + n_sb])
    for s in sb.ids:
        where[s] = SB
    fw = Pool(order[J + n_sb:])
    fs = Pool(range(W, N))

    r, sys_r = p["random_failure_rate"], p["systematic_failure_rate"]
    topo = p.get("fault_domains") or {}
    n_racks = topo.get("n_racks", 0)
    rpp = topo.get("racks_per_pod", 0) if n_racks else 0
    n_pods = -(-n_racks // rpp) if rpp else 0
    lam_rack = n_racks * topo.get("rack_shock_rate", 0.0)
    lam_pod = n_pods * topo.get("pod_shock_rate", 0.0)
    stages = (_stage_sampler(p, p["auto_repair_time"], rng),
              _stage_sampler(p, p["manual_repair_time"], rng))
    shop: list = []            # (end time, seq, sid, stage, token)
    token = [0] * N
    stage_of = [0] * N
    seq = 0
    out = {"n_failures": 0.0, "n_host_selections": 0.0,
           "n_standby_swaps": 0.0, "n_preemptions": 0.0,
           "n_auto_repairs": 0.0, "n_manual_repairs": 0.0,
           "n_undiagnosed": 0.0, "stall_time": 0.0,
           "n_domain_shocks": 0.0, "n_shock_killed": 0.0}
    runs: List[float] = []
    rec: Dict[str, List[float]] = {ch: [] for ch in CHANNELS}

    def bump(key, by=1.0):
        out[key] = rnd(out[key] + by)

    def start_stage(sid, stage, t):
        nonlocal seq
        seq += 1
        token[sid] += 1
        stage_of[sid] = stage
        heapq.heappush(shop, (t + stages[stage](), seq, sid, stage,
                              token[sid]))

    def to_shop(sid, t):
        where[sid] = SHOP
        start_stage(sid, 0, t)

    def join(sid):
        where[sid] = RUN
        run[bad[sid]].add(sid)

    def plan_restart(t, waited, extra=0.0):
        """The job restarts ``extra + recovery_time`` after ``t``, and
        has waited ``waited + extra`` for its servers by then."""
        rec["waiting"].append(rnd(waited + extra))
        rec["recovery"].append(rnd(waited + extra + p["recovery_time"]))
        return rnd(t + extra + p["recovery_time"])

    t = rnd(p["host_selection_time"])
    left = rnd(p["job_length"])
    phase, timer, deficit, stall_start = COMPUTE, math.inf, 0, 0.0
    cur = 0.0                 # compute since the job last (re)started
    completed = True
    failures = 0
    while True:
        n_good, n_badr = len(run[False]), len(run[True])
        lam_fail = (n_good * r + n_badr * (r + sys_r)) \
            if phase == COMPUTE else 0.0
        lam = lam_fail + lam_rack + lam_pod
        t_exp = t + rng.expovariate(lam) if lam > 0 else math.inf
        t_done = t + left if phase == COMPUTE else math.inf
        t_timer = timer if phase == OVERHEAD else math.inf
        while shop and shop[0][4] != token[shop[0][2]]:
            heapq.heappop(shop)           # a restarted stage's old end
        t_rep = shop[0][0] if shop else math.inf
        t_next = min(t_exp, t_done, t_timer, t_rep)
        if not math.isfinite(t_next):
            completed = False             # nothing can happen any more
            break
        if phase == COMPUTE:
            cur = rnd(cur + (t_next - t))
            left = rnd(left - (t_next - t))
        t = rnd(t_next)
        if t_next == t_done:
            runs.append(cur)
            break
        if t_next == t_timer:
            phase, timer, cur = COMPUTE, math.inf, 0.0
            continue
        if t_next == t_rep:
            _, _, sid, stage, _ = heapq.heappop(shop)
            fail_p = p["auto_repair_failure_probability"]
            if stage == 0:
                bump("n_auto_repairs")
                if rng.random() >= p["automated_repair_probability"]:
                    start_stage(sid, 1, t)
                    continue
            else:
                bump("n_manual_repairs")
                fail_p = p["manual_repair_failure_probability"]
            if rng.random() >= fail_p:
                bad[sid] = False          # success: the server is good now
            if phase == STALL:
                join(sid)
                deficit -= 1
                if deficit == 0:
                    out["stall_time"] = rnd(out["stall_time"]
                                            + (t - stall_start))
                    timer = plan_restart(t, t - stall_start)
                    phase = OVERHEAD
            elif len(sb) < K:
                where[sid] = SB
                sb.add(sid)
            elif sid < W:
                where[sid] = FW
                fw.add(sid)
            else:
                where[sid] = FS
                fs.add(sid)
            continue
        # an exponential clock: a failure, a rack shock or a pod shock
        u = rng.random() * lam
        if u < lam_fail:
            if failures >= max_failures:
                completed = False
                break
            failures += 1
            bump("n_failures")
            runs.append(cur)
            cur = 0.0
            pool = run[n_badr > 0 and u >= n_good * r]
            failed = pool.ids[int(rng.random() * len(pool))]
            if rng.random() >= p["diagnosis_probability"]:
                bump("n_undiagnosed")
                timer, phase = plan_restart(t, 0.0), OVERHEAD
                continue
            run[bad[failed]].remove(failed)
            to_shop(failed, t)
            if len(sb):
                join(sb.draw(rng))
                bump("n_standby_swaps")
                extra = 0.0
            elif len(fw):
                join(fw.draw(rng))
                bump("n_host_selections")
                extra = p["host_selection_time"]
            elif len(fs):
                join(fs.draw(rng))
                bump("n_host_selections")
                bump("n_preemptions")
                extra = p["waiting_time"] + p["host_selection_time"]
            else:
                phase, deficit, stall_start = STALL, 1, t
                continue
            timer, phase = plan_restart(t, 0.0, extra), OVERHEAD
            continue
        # a shock: the level by its share of the rate, the domain
        # uniform within the level
        bump("n_domain_shocks")
        if u < lam_fail + lam_rack:
            domain = int(rng.random() * n_racks)
        else:
            domain = n_racks + int(rng.random() * n_pods)
        hit_run = []
        for sid in members(N, n_racks, rpp, domain):
            w = where[sid]
            if w == RUN:
                hit_run.append(sid)
                continue
            if w == SHOP:
                start_stage(sid, stage_of[sid], t)   # the stage restarts
            else:
                (sb if w == SB else fw if w == FW else fs).remove(sid)
                to_shop(sid, t)
            bump("n_shock_killed")
        if not hit_run:
            continue
        if phase == COMPUTE:
            runs.append(cur)
            cur = 0.0
        for sid in hit_run:
            run[bad[sid]].remove(sid)
            to_shop(sid, t)
            bump("n_shock_killed")
        need = len(hit_run)
        took = {}
        for name, pool in (("sb", sb), ("fw", fw), ("fs", fs)):
            took[name] = min(need, len(pool))
            for _ in range(took[name]):
                join(pool.draw(rng))
            need -= took[name]
        bump("n_standby_swaps", took["sb"])
        bump("n_host_selections", took["fw"] + took["fs"])
        bump("n_preemptions", took["fs"])
        if need or phase == STALL:
            if phase != STALL:
                phase, stall_start = STALL, t
            deficit += need
            continue
        extra = 0.0
        if took["fw"] + took["fs"]:
            extra += p["host_selection_time"]
        if took["fs"]:
            extra += p["waiting_time"]
        timer, phase = plan_restart(t, 0.0, extra), OVERHEAD
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

    Replica ``i`` draws from ``random.Random(f"{seed}:{i}")``, so the
    same seed gives the same arrays.  ``max_failures`` defaults to ten
    times the failures the job would see with no repairs at all.
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
               "n_manual_repairs", "n_undiagnosed", "stall_time",
               "n_domain_shocks", "n_shock_killed")
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
