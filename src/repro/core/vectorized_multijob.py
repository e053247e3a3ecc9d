"""Vectorized multi-job CTMC engine: whole-cluster sweeps as one program.

The paper's headline case study is *capacity planning*: many concurrent
jobs of mixed sizes contending for one spare pool and one repair shop.
The single-job CTMC engine (:mod:`repro.core.vectorized`) models exactly
one job; this module promotes the event-loop multi-job semantics
(:mod:`repro.core.multijob` / ``scheduler`` / ``coordinator``) into the
compiled scan.

State layout (batch axis B = points x replicas, J jobs **static**):

  * per-job compartment blocks ``run`` / ``sb`` — each job carries its
    own running set and warm-standby complement over the 4 (origin x
    health) classes, its own phase/timer/work_left lanes, and its own
    run/recovery/waiting histogram channels;
  * shared pool lanes ``fw`` / ``fs`` — ONE working pool and ONE spare
    pool all jobs draw from (the contention the paper predicts at
    replacement acquisition);
  * a shared finite-server repair shop, partitioned **by owning job**:
    ``auto`` / ``man`` are the in-service stages (generalizing the PR 5
    repair-slot lane into a shop with ``Params.repair_servers`` service
    slots) and ``q`` is the waiting line behind them.  A departure
    admits one queued server proportionally over the queued (job,
    class) counts — exactly the uniform-random admission the event
    engine's :class:`~repro.core.repair.RepairShop` draws, so admission
    is exact in law.  ``repair_servers=0`` keeps the shop unbounded and
    the queue lane permanently empty.

Job count/structure is the only static compile key; job sizes, lengths,
rates, warm-standby targets, and pool/shop capacities are all traced —
so a mixed-size capacity grid (spare-pool size x repair servers) runs
as ONE compiled XLA program via :func:`simulate_multijob_ctmc_sweep`.

The driver is the single-job engine's: this module brings its step
(:class:`_MultiJobStep` around :func:`_mj_step_u`), parameter vector,
step budget, initial state and per-point extraction, and
:func:`repro.core.vectorized._build_programs` and ``_chunk_loop`` do
the rest — budget rounding, pow2 bucketing, sharding, the chunked scan
with early exit, the once-per-chunk histogram add, and the ``aires.*``
spans, scopes and counters of :mod:`repro.core.tracing`.

Dispatch semantics promoted from the event engine's ``Dispatcher``:

  * a repaired server goes to the **longest-stalled** job first (FIFO
    over stall-start times; ties resolve to the lowest job index, the
    stability of Python's ``min``), paying the host-selection surcharge
    iff the receiver is not the owner that submitted it;
  * otherwise the owning job refills its standby complement (if still
    active and below its warm target);
  * otherwise the server returns to its origin pool.

A completing job releases its running + standby servers to the pools;
stalled jobs grab one each (earliest stall first — the release-watcher
order of the event engine) with the host-selection surcharge always
charged (released servers are never members of the starved job).

Reduction: a 1-job cluster with an unbounded shop **routes to the
single-job engine** (``cluster.replace(job-spec overrides)`` through
:func:`repro.core.vectorized.simulate_ctmc_sweep`) — bit-identical
results from the same compiled program class.

Carve-outs (the event ``MultiJobSimulation`` remains the oracle):
exponential failures AND repairs only, no fault domains / campaigns /
checkpoint rollback / retirement / regeneration / failing standbys, and
all jobs start at t=0.  ``supports_multijob`` gates dispatch; see
docs/multijob.md for the exact-in-law guarantees and the documented
approximations (expectation initial bad-split, class-proportional
picks, batch-proportional release hand-offs).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from . import hazards, tracing
from . import vectorized as vz
from .multijob import JobSpec
from .params import Params
from .vectorized import (COMPUTE, DONE, OVERHEAD, STALL, _hist_bins,
                         _hist_flush, _selected_channels, default_max_steps,
                         row_hit)

#: per-job scalar metrics carried as (B, J) lanes — the per-job
#: RunResult fields the event oracle reports
_MJ_JOB_METRICS = (
    "total_time", "useful_work", "n_failures", "n_random_failures",
    "n_systematic_failures", "n_undiagnosed", "n_misdiagnosed",
    "n_preemptions", "n_host_selections", "n_standby_swaps",
    "stall_time", "recovery_overhead",
)

#: cluster-level (B,) metrics: the shared repair shop's counters (the
#: event engine's ``MultiJobResult.cluster``), the dispatcher's
#: stall hand-off count, shop-queue pressure, and the conservation check
_MJ_CLUSTER_METRICS = ("n_auto_repairs", "n_manual_repairs",
                       "n_failed_repairs", "stall_handoffs",
                       "n_shop_queued", "conservation_err")

#: uniform lanes per step: u_time, u_pick (event race), u_diag, u_wrong,
#: u_cls, u_esc, u_succ, u_pool (failure/repair path — same roles as the
#: single-job engine), u_adm (queue admission pick), u_rel
#: (completion-release class picks, golden-ratio shifted per hand-off)
_N_UNIFORMS = 10

_PHI = 0.6180339887498949


def unsupported_reasons_multijob(cluster: Params,
                                 jobs: Sequence[JobSpec]) -> list:
    """Why this cluster is outside the multi-job CTMC envelope.

    Empty list = inside.  The single source of truth for
    :func:`supports_multijob` and the ``engine="ctmc"`` refusal
    message built by :mod:`repro.core.backend` — mirroring
    ``vectorized.unsupported_reasons`` so the messages can never go
    stale against the actual dispatch conditions again.
    """
    reasons = []
    if len(jobs) < 1:
        reasons.append("no jobs given")
    if hazards.hazard_kind(cluster) != "exponential":
        reasons.append(
            "non-exponential failure distribution (the multi-job "
            "program has no per-job hazard lanes yet; the single-job "
            "CTMC engine covers weibull/bathtub/lognormal/empirical)")
    if hazards.repair_kind(cluster) != "exponential":
        reasons.append(
            "non-exponential repair distribution (the shared "
            "repair-shop lane is exponential-stage only)")
    if cluster.fault_domains is not None or cluster.campaign is not None:
        reasons.append(
            "fault domains / campaigns are single-job-fast-path or "
            "event-engine territory here")
    if cluster.retirement_threshold != 0:
        reasons.append("retirement policies are event-engine-only")
    if cluster.bad_set_regeneration_period != 0:
        reasons.append("bad-set regeneration is event-engine-only")
    if cluster.checkpoint_interval != 0:
        reasons.append("checkpoint rollback is event-engine-only")
    if cluster.standbys_can_fail:
        reasons.append("failing warm standbys are event-engine-only")
    if any(j.start_time != 0.0 for j in jobs):
        reasons.append(
            "staggered job start times (all jobs must start at t=0)")
    return reasons


def supports_multijob(cluster: Params, jobs: Sequence[JobSpec]) -> bool:
    """Can the multi-job CTMC engine run this cluster exactly-in-law?

    The multi-job compartment model covers the paper's exponential
    baseline — exponential failures and repairs — with any number of
    mixed-size jobs sharing one spare pool and one (optionally finite)
    repair shop.  Age-dependent hazards, per-server repair slots, fault
    domains/campaigns, and the event-engine-only extensions stay on the
    event-loop oracle, as do staggered job start times.
    """
    return not unsupported_reasons_multijob(cluster, jobs)


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------

def _mj_initial_counts(cluster: Params, jobs: Sequence[JobSpec]) -> dict:
    """Sequential expectation-split allocation, mirroring the event
    engine's job-order pops from one shared working pool at t=hs."""
    wp, sp = cluster.working_pool_size, cluster.spare_pool_size
    total = wp + sp
    n_bad = int(round(cluster.systematic_failure_fraction * total))
    bad_w = round(n_bad * wp / total)
    bad_s = n_bad - bad_w

    def split(n_take, pool_good, pool_bad):
        frac_bad = pool_bad / max(pool_good + pool_bad, 1)
        take_bad = int(round(n_take * frac_bad))
        return n_take - take_bad, take_bad

    w_good, w_bad = wp - bad_w, bad_w
    run, sb = [], []
    for spec in jobs:
        rg, rb = split(spec.job_size, w_good, w_bad)
        w_good -= rg
        w_bad -= rb
        n_sb = min(spec.warm_standbys, w_good + w_bad)
        sg, s_b = split(n_sb, w_good, w_bad)
        w_good -= sg
        w_bad -= s_b
        run.append([rg, rb, 0, 0])
        sb.append([sg, s_b, 0, 0])
    return {"run": run, "sb": sb,
            "fw": [w_good, w_bad, 0, 0],
            "fs": [0, 0, sp - bad_s, bad_s],
            "fleet_total": float(total)}


def _mj_initial_state_batch(points: Sequence[Tuple[Params, tuple]],
                            R: int, max_runs: int,
                            ) -> Dict[str, jnp.ndarray]:
    """Padded initial state for a structural grid, point-major (P*R, ...).

    As in the single-job engine, structure (job sizes, pool sizes, job
    lengths) enters purely as per-point initial *values*: every point of
    a group shares the (J-static) compartment layout, so a mixed-size
    capacity grid is one compiled program.
    """
    P = len(points)
    B = P * R
    J = len(points[0][1])
    counts = [_mj_initial_counts(c, js) for c, js in points]

    def rep(arr):
        return jnp.asarray(np.repeat(np.asarray(arr, np.float32), R,
                                     axis=0))

    state: Dict[str, jnp.ndarray] = {}
    state["run"] = rep([c["run"] for c in counts])          # (B, J, 4)
    state["sb"] = rep([c["sb"] for c in counts])
    state["fw"] = rep([c["fw"] for c in counts])            # (B, 4)
    state["fs"] = rep([c["fs"] for c in counts])
    state["auto"] = jnp.zeros((B, J, 4), jnp.float32)
    state["man"] = jnp.zeros((B, J, 4), jnp.float32)
    state["q"] = jnp.zeros((B, J, 4), jnp.float32)
    state["fleet_total"] = rep([c["fleet_total"] for c in counts])  # (B,)
    state["t"] = rep([c.host_selection_time for c, _ in points])
    state["work_left"] = rep([[j.job_length for j in js]
                              for _, js in points])         # (B, J)
    state["timer"] = jnp.full((B, J), jnp.inf, jnp.float32)
    state["stall_start"] = jnp.zeros((B, J), jnp.float32)
    state["phase"] = jnp.full((B, J), COMPUTE, jnp.int32)
    state["cur_run"] = jnp.zeros((B, J), jnp.float32)
    state["n_runs"] = jnp.zeros((B, J), jnp.int32)
    state["run_durations"] = jnp.zeros((B, J, max_runs), jnp.float32)
    spec = points[0][0].histogram
    sel = _selected_channels(spec)
    if sel:
        # job-major channels: row j * len(sel) + c is job j's channel c
        state["hist"] = jnp.zeros((B, J * len(sel), spec.n_counts),
                                  jnp.float32)
        state["hist_edges"] = jnp.asarray(spec.edges(), jnp.float32)
    for m in _MJ_JOB_METRICS:
        state.setdefault(m, jnp.zeros((B, J), jnp.float32))
    for m in _MJ_CLUSTER_METRICS:
        state[m] = jnp.zeros((B,), jnp.float32)
    return state


def _pick_cat(counts: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """Categorical draw proportional to counts: (B, K) x (B,) -> (B,)."""
    total = jnp.maximum(counts.sum(-1), 1e-30)
    cdf = jnp.cumsum(counts, axis=-1) / total[..., None]
    return jnp.minimum(
        jnp.sum((u[..., None] >= cdf).astype(jnp.int32), -1),
        counts.shape[-1] - 1)


def _onehot4(c: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.one_hot(c, 4, dtype=jnp.float32)


def _add_at_job(buf: jnp.ndarray, j1b: jnp.ndarray,
                upd: jnp.ndarray) -> jnp.ndarray:
    """``buf`` (B, J, 4) plus ``upd`` (B, 4) on the job that ``j1b``
    (B, J) selects: ``buf.at[rows, j].add(upd)`` as a dense select (see
    :func:`repro.core.vectorized.row_hit`)."""
    return buf + jnp.where(j1b[..., None], upd[:, None, :], 0.0)


# ---------------------------------------------------------------------------
# one transition
# ---------------------------------------------------------------------------

def _mj_step_u(s: Dict[str, jnp.ndarray], u: jnp.ndarray, pv: jnp.ndarray,
               J: int, impl: Optional[str],
               hist_channels: tuple) -> Dict[str, jnp.ndarray]:
    """One multi-job CTMC transition for a batch of replicas.

    ``pv`` columns: 14 shared model parameters [r_rand, r_sys, recovery,
    host_sel, waiting, auto_t, man_t, auto_fail, man_fail, p_auto, dp,
    du, preempt_cost, repair_servers] followed by J per-job warm-standby
    targets — a single vector or one row per replica (the batched sweep
    layout).  Race layout: 16J exponential lanes ([random-failure x4,
    systematic x4, auto-completion x4, manual x4] per job, job-major
    within each family block) + 2J deterministic residuals (per-job
    completion, then per-job overhead timer).
    """
    B = s["t"].shape[0]
    if pv.ndim == 1:
        col = [pv[i] for i in range(14)]
        warm = pv[14:14 + J]                                   # (J,)
        warm_of = lambda j: warm[j]                            # (B,)
    else:
        col = [pv[:, i] for i in range(14)]
        warm = pv[:, 14:14 + J]                                # (B, J)
        brows_w = jnp.arange(B)
        warm_of = lambda j: warm[brows_w, j]
    (r_rand, r_sys, recovery, host_sel, waiting, auto_t, man_t,
     auto_fail, man_fail, p_auto, dp, du, preempt_cost, cap) = col

    (u_time, u_pick, u_diag, u_wrong, u_cls, u_esc, u_succ, u_pool,
     u_adm, u_rel) = (u[:, i] for i in range(_N_UNIFORMS))

    rows = jnp.arange(B)
    jobs_ax = jnp.arange(J)
    computing = s["phase"] == COMPUTE                          # (B, J)
    in_overhead = s["phase"] == OVERHEAD
    stalled_pre = s["phase"] == STALL
    active_any = jnp.any(s["phase"] != DONE, axis=-1)          # (B,)

    def _e(x):      # scalar-or-(B,) param -> broadcast over (B, J, 4)
        return x if jnp.ndim(x) == 0 else x[:, None, None]

    def _j(x):      # scalar-or-(B,) param -> broadcast over (B, J)
        return x if jnp.ndim(x) == 0 else x[:, None]

    # ---- rates (B, 16J) -------------------------------------------------
    run = s["run"]
    bad_mask = jnp.asarray([0.0, 1.0, 0.0, 1.0], jnp.float32)
    comp3 = computing[..., None]
    fail_rand = run * _e(r_rand) * comp3
    fail_sys = run * bad_mask[None, None, :] * _e(r_sys) * comp3
    auto_rate = s["auto"] / jnp.maximum(_e(auto_t), 1e-9)
    man_rate = s["man"] / jnp.maximum(_e(man_t), 1e-9)
    rates = jnp.concatenate(
        [fail_rand.reshape(B, 4 * J), fail_sys.reshape(B, 4 * J),
         auto_rate.reshape(B, 4 * J), man_rate.reshape(B, 4 * J)],
        axis=-1) * active_any[:, None]

    residuals = jnp.concatenate(
        [jnp.where(computing, s["work_left"], jnp.inf),
         jnp.where(in_overhead, s["timer"], jnp.inf)], axis=-1)  # (B, 2J)

    with jax.named_scope(tracing.RACE):
        dt, ev = ops.event_race(rates, residuals, u_time, u_pick, impl=impl)
    dt = jnp.where(active_any & jnp.isfinite(dt), dt, 0.0)
    kx = 16 * J

    cls = (ev % 4).astype(jnp.int32)
    ej = ((ev % (4 * J)) // 4).astype(jnp.int32)       # owning/failing job
    ej1h = jax.nn.one_hot(ej, J, dtype=jnp.float32)    # (B, J)
    ej1b = ej1h > 0.5
    is_fail = active_any & (ev < 8 * J)
    is_sys = active_any & (ev >= 4 * J) & (ev < 8 * J)
    is_auto = active_any & (ev >= 8 * J) & (ev < 12 * J)
    is_man = active_any & (ev >= 12 * J) & (ev < 16 * J)
    is_complete = active_any[:, None] \
        & (ev[:, None] == kx + jobs_ax[None, :])               # (B, J)
    is_timer = active_any[:, None] \
        & (ev[:, None] == kx + J + jobs_ax[None, :])

    ns = dict(s)
    t_new = s["t"] + dt
    ns["t"] = t_new

    # ---- progress / completion / timers --------------------------------
    progress = jnp.where(computing, dt[:, None], 0.0)          # (B, J)
    ns["work_left"] = s["work_left"] - progress
    ns["useful_work"] = s["useful_work"] + progress
    timer_dec = jnp.where(in_overhead, s["timer"] - dt[:, None], s["timer"])
    ns["phase"] = jnp.where(is_complete, DONE, s["phase"])
    ns["phase"] = jnp.where(is_timer, COMPUTE, ns["phase"])
    ns["timer"] = jnp.where(is_timer, jnp.inf, timer_dec)
    ns["total_time"] = jnp.where(is_complete, t_new[:, None],
                                 s["total_time"])

    # ---- exact per-job run durations ------------------------------------
    fail_j = is_fail[:, None] & ej1b                           # (B, J)
    record = fail_j | is_complete
    run_val = s["cur_run"] + progress
    max_runs = s["run_durations"].shape[2]
    if max_runs:
        with jax.named_scope(tracing.RING):
            hit = row_hit(jnp.mod(s["n_runs"], max_runs), max_runs)
            ns["run_durations"] = jnp.where(hit & record[..., None],
                                            run_val[..., None],
                                            s["run_durations"])
    ns["n_runs"] = s["n_runs"] + record.astype(jnp.int32)
    ns["cur_run"] = jnp.where(record, 0.0, run_val)

    # ---- failure handling ----------------------------------------------
    f32 = lambda m: m.astype(jnp.float32)
    ns["n_failures"] = s["n_failures"] + f32(fail_j)
    ns["n_systematic_failures"] = s["n_systematic_failures"] \
        + f32(is_sys[:, None] & ej1b)
    ns["n_random_failures"] = s["n_random_failures"] \
        + f32((is_fail & ~is_sys)[:, None] & ej1b)

    diagnosed = is_fail & (u_diag < dp)
    wrong = diagnosed & (u_wrong < du)
    ns["n_undiagnosed"] = s["n_undiagnosed"] \
        + f32((is_fail & ~diagnosed)[:, None] & ej1b)
    ns["n_misdiagnosed"] = s["n_misdiagnosed"] + f32(wrong[:, None] & ej1b)

    run_f = run[rows, ej]                                      # (B, 4)
    sb_f = s["sb"][rows, ej]
    # stacked proportional picks: misdiagnosis target within the failing
    # job's own running set, standby take, working take, spare take
    stacked = jnp.stack([run_f, sb_f, s["fw"], s["fs"]], axis=1)
    uu = jnp.stack([u_cls, u_cls, u_pool, u_pool], axis=1)
    total_p = jnp.maximum(stacked.sum(-1), 1e-30)
    cdf_p = jnp.cumsum(stacked, axis=-1) / total_p[..., None]
    picks = jnp.minimum(
        jnp.sum((uu[..., None] >= cdf_p).astype(jnp.int32), -1), 3)
    pick1h = jax.nn.one_hot(picks, 4, dtype=jnp.float32)       # (B, 4, 4)

    rm1h = jnp.where(wrong[:, None], pick1h[:, 0], _onehot4(cls)) \
        * diagnosed[:, None]                                   # (B, 4)
    ns["run"] = _add_at_job(s["run"], ej1b, -rm1h)

    # shop entry: a free service slot starts the automated stage at
    # once; a full shop parks the server in the queue lane (by owner)
    cap_eff = jnp.where(cap > 0, cap, jnp.inf)
    shop_active = s["auto"].sum((-2, -1)) + s["man"].sum((-2, -1))  # (B,)
    has_slot = shop_active < cap_eff
    enters = diagnosed & has_slot
    queues = diagnosed & ~has_slot
    ns["auto"] = _add_at_job(s["auto"], ej1b, rm1h * enters[:, None])
    ns["q"] = _add_at_job(s["q"], ej1b, rm1h * queues[:, None])
    ns["n_shop_queued"] = s["n_shop_queued"] + f32(queues)

    # replacement waterfall: own standbys -> shared working -> shared
    # spare -> stall (the §II-B priority order, per job)
    sb_tot = sb_f.sum(-1)
    fw_tot = s["fw"].sum(-1)
    fs_tot = s["fs"].sum(-1)
    use_sb = diagnosed & (sb_tot > 0)
    use_fw = diagnosed & ~use_sb & (fw_tot > 0)
    use_fs = diagnosed & ~use_sb & ~use_fw & (fs_tot > 0)
    goes_stall = diagnosed & ~use_sb & ~use_fw & ~use_fs

    take = (pick1h[:, 1] * use_sb[:, None]
            + pick1h[:, 2] * use_fw[:, None]
            + pick1h[:, 3] * use_fs[:, None])
    ns["sb"] = _add_at_job(s["sb"], ej1b, -pick1h[:, 1] * use_sb[:, None])
    ns["fw"] = s["fw"] - pick1h[:, 2] * use_fw[:, None]
    ns["fs"] = s["fs"] - pick1h[:, 3] * use_fs[:, None]
    ns["run"] = _add_at_job(ns["run"], ej1b, take)
    ns["n_standby_swaps"] = s["n_standby_swaps"] + f32(use_sb[:, None] & ej1b)
    ns["n_host_selections"] = s["n_host_selections"] \
        + f32((use_fw | use_fs)[:, None] & ej1b)
    ns["n_preemptions"] = s["n_preemptions"] + f32(use_fs[:, None] & ej1b)

    fail_timer = (recovery
                  + jnp.where(use_fw | use_fs, host_sel, 0.0)
                  + jnp.where(use_fs, waiting + preempt_cost, 0.0))
    resolves = is_fail & ~goes_stall
    resolves_j = resolves[:, None] & ej1b
    stall_j = goes_stall[:, None] & ej1b
    ns["timer"] = jnp.where(resolves_j, fail_timer[:, None], ns["timer"])
    ns["phase"] = jnp.where(resolves_j, OVERHEAD, ns["phase"])
    ns["phase"] = jnp.where(stall_j, STALL, ns["phase"])
    ns["stall_start"] = jnp.where(stall_j, t_new[:, None], s["stall_start"])
    ns["recovery_overhead"] = s["recovery_overhead"] \
        + jnp.where(resolves_j, _j(recovery), 0.0)

    # ---- repair completions ---------------------------------------------
    rep1h = _onehot4(cls)
    ns["auto"] = _add_at_job(ns["auto"], ej1b, -rep1h * is_auto[:, None])
    ns["n_auto_repairs"] = s["n_auto_repairs"] + f32(is_auto)
    escalate = is_auto & (u_esc >= p_auto)
    ns["man"] = _add_at_job(
        s["man"], ej1b, rep1h * escalate[:, None] - rep1h * is_man[:, None])
    ns["n_manual_repairs"] = s["n_manual_repairs"] + f32(is_man)

    finishes = (is_auto & ~escalate) | is_man
    fail_prob = jnp.where(is_man, man_fail, auto_fail)
    healed = finishes & (u_succ >= fail_prob)
    ns["n_failed_repairs"] = s["n_failed_repairs"] + f32(finishes & ~healed)
    out_cls = jnp.where(healed, cls - (cls % 2), cls)          # bad -> good
    out1h = _onehot4(out_cls)
    spare_origin = out_cls >= 2

    # dispatcher: longest-stalled job anywhere > owner standby refill >
    # origin pool.  The host-selection surcharge applies iff the
    # receiver is NOT the owner that submitted the server (the event
    # engine's membership rule — only original members rejoin free).
    any_stalled = stalled_pre.any(-1)
    k_star = jnp.argmin(jnp.where(stalled_pre, s["stall_start"], jnp.inf),
                        axis=-1)                               # (B,)
    to_stalled = finishes & any_stalled
    k1b = jax.nn.one_hot(k_star, J, dtype=jnp.float32) > 0.5
    to_stalled_j = to_stalled[:, None] & k1b
    surcharge = to_stalled & (k_star != ej)
    ns["run"] = _add_at_job(ns["run"], k1b, out1h * to_stalled[:, None])
    unstall_timer = recovery + jnp.where(surcharge, host_sel, 0.0)
    ns["phase"] = jnp.where(to_stalled_j, OVERHEAD, ns["phase"])
    ns["timer"] = jnp.where(to_stalled_j, unstall_timer[:, None],
                            ns["timer"])
    stall_wait = t_new - s["stall_start"][rows, k_star]
    ns["stall_time"] = s["stall_time"] \
        + jnp.where(to_stalled_j, stall_wait[:, None], 0.0)
    ns["n_host_selections"] = ns["n_host_selections"] \
        + f32(surcharge[:, None] & k1b)
    ns["recovery_overhead"] = ns["recovery_overhead"] \
        + jnp.where(to_stalled_j, _j(recovery), 0.0)
    ns["stall_handoffs"] = s["stall_handoffs"] + f32(to_stalled)

    owner_active = s["phase"][rows, ej] != DONE
    sb_owner_tot = ns["sb"][rows, ej].sum(-1)
    to_sb = finishes & ~to_stalled & owner_active \
        & (sb_owner_tot < warm_of(ej))
    to_pool = finishes & ~to_stalled & ~to_sb
    ns["sb"] = _add_at_job(ns["sb"], ej1b, out1h * to_sb[:, None])
    ns["fw"] = ns["fw"] + out1h * (to_pool & ~spare_origin)[:, None]
    ns["fs"] = ns["fs"] + out1h * (to_pool & spare_origin)[:, None]

    # a departure frees a service slot: admit one queued server,
    # proportionally over the queued (job, class) counts — exact in law
    # vs the event shop's uniform-random admission
    q_flat = ns["q"].reshape(B, 4 * J)
    admit = finishes & (q_flat.sum(-1) > 0)
    pick_q = _pick_cat(q_flat, u_adm)
    qj = (pick_q // 4).astype(jnp.int32)
    qc1h = _onehot4(pick_q % 4) * admit[:, None]
    qj1b = row_hit(qj, J)
    ns["q"] = _add_at_job(ns["q"], qj1b, -qc1h)
    ns["auto"] = _add_at_job(ns["auto"], qj1b, qc1h)

    # ---- histogram bookkeeping for failure/unstall paths ---------------
    # per step each job records at most one recovery/waiting event:
    # a resolved failure (its own), a repair-return unstall, or (below)
    # a completion-release unstall
    ended = resolves_j | to_stalled_j                          # (B, J)
    rec_fail = fail_timer[:, None]
    rec_unst = (stall_wait + unstall_timer)[:, None]
    downtime = jnp.where(resolves_j, rec_fail,
                         jnp.where(to_stalled_j, rec_unst, 0.0))
    acq_fail = (fail_timer - recovery)[:, None]
    acq_unst = (stall_wait + unstall_timer - recovery)[:, None]
    acquire_wait = jnp.where(resolves_j, acq_fail,
                             jnp.where(to_stalled_j, acq_unst, 0.0))

    # ---- job completion: release running + standbys ---------------------
    any_complete = is_complete.any(-1)
    ci = jnp.argmax(is_complete, axis=-1)                      # (B,)
    rel = (ns["run"][rows, ci] + ns["sb"][rows, ci]) \
        * any_complete[:, None]                                # (B, 4)
    released = (row_hit(ci, J) & any_complete[:, None])[..., None]
    ns["run"] = jnp.where(released, 0.0, ns["run"])
    ns["sb"] = jnp.where(released, 0.0, ns["sb"])

    # released servers go to starving jobs first (earliest stall first,
    # one each — the release-watcher semantics), always paying the
    # host-selection surcharge; class picks are proportional over the
    # released batch (documented approximation: the event engine hands
    # the literal pushed server, an exchangeable draw from the same
    # batch).  The remainder lands in the origin pools.
    stalled_now = (ns["phase"] == STALL) & ~is_complete
    rel_rem = rel
    rel_timer = jnp.broadcast_to(
        jnp.asarray(recovery + host_sel, jnp.float32), (B,))
    for r in range(max(J - 1, 0)):
        can = any_complete & stalled_now.any(-1) & (rel_rem.sum(-1) > 0)
        k_r = jnp.argmin(jnp.where(stalled_now, ns["stall_start"],
                                   jnp.inf), axis=-1)
        kr1b = jax.nn.one_hot(k_r, J, dtype=jnp.float32) > 0.5
        can_j = can[:, None] & kr1b
        u_r = jnp.mod(u_rel + r * _PHI, 1.0)
        p1h = _onehot4(_pick_cat(rel_rem, u_r)) * can[:, None]
        rel_rem = rel_rem - p1h
        ns["run"] = _add_at_job(ns["run"], kr1b, p1h)
        rel_wait = t_new - ns["stall_start"][rows, k_r]
        ns["phase"] = jnp.where(can_j, OVERHEAD, ns["phase"])
        ns["timer"] = jnp.where(can_j, rel_timer[:, None], ns["timer"])
        ns["stall_time"] = ns["stall_time"] \
            + jnp.where(can_j, rel_wait[:, None], 0.0)
        ns["n_host_selections"] = ns["n_host_selections"] + f32(can_j)
        ns["recovery_overhead"] = ns["recovery_overhead"] \
            + jnp.where(can_j, _j(recovery), 0.0)
        ended = ended | can_j
        downtime = jnp.where(can_j, (rel_wait + rel_timer)[:, None],
                             downtime)
        acquire_wait = jnp.where(
            can_j, (rel_wait + rel_timer - recovery)[:, None],
            acquire_wait)
        stalled_now = stalled_now & ~can_j
    ns["fw"] = ns["fw"] + rel_rem * jnp.asarray([1, 1, 0, 0], jnp.float32)
    ns["fs"] = ns["fs"] + rel_rem * jnp.asarray([0, 0, 1, 1], jnp.float32)

    # ---- streaming per-job histograms -----------------------------------
    # as on the single-job step: a state with ``hist`` gets the step's
    # counts added, one with ``hist_bins`` (the chunk loop's) the step's
    # record, for the loop to add once per chunk
    with jax.named_scope(tracing.HIST):
        if "hist" in s or "hist_bins" in s:
            channel_vals = {"run_duration": (run_val, record),
                            "recovery": (downtime, ended),
                            "waiting": (acquire_wait, ended)}
            vals = jnp.stack([channel_vals[ch][0] for ch in hist_channels],
                             axis=2)                           # (B, J, S)
            masks = jnp.stack([channel_vals[ch][1] for ch in hist_channels],
                              axis=2)
            # job-major channels, rows on the last axis: (J * S, B)
            bins = _hist_bins(s["hist_edges"], vals.reshape(B, -1).T,
                              masks.reshape(B, -1).T)
            if "hist" in s:
                ns["hist"] = _hist_flush(s["hist"], bins[:, None])
            else:
                ns["hist_bins"] = bins

    # ---- conservation invariant ----------------------------------------
    tot = (ns["run"].sum((-2, -1)) + ns["sb"].sum((-2, -1))
           + ns["auto"].sum((-2, -1)) + ns["man"].sum((-2, -1))
           + ns["q"].sum((-2, -1)) + ns["fw"].sum(-1) + ns["fs"].sum(-1))
    ns["conservation_err"] = jnp.maximum(
        s["conservation_err"], jnp.abs(tot - s["fleet_total"]))
    return ns


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _mj_params_vector(cluster: Params, jobs: Sequence[JobSpec],
                      ) -> jnp.ndarray:
    base = np.asarray([
        cluster.random_failure_rate, cluster.systematic_failure_rate,
        cluster.recovery_time, cluster.host_selection_time,
        cluster.waiting_time, cluster.auto_repair_time,
        cluster.manual_repair_time, cluster.auto_repair_failure_probability,
        cluster.manual_repair_failure_probability,
        cluster.automated_repair_probability,
        cluster.diagnosis_probability, cluster.diagnosis_uncertainty,
        cluster.preemption_cost, float(cluster.repair_servers),
    ], np.float32)
    warm = np.asarray([float(j.warm_standbys) for j in jobs], np.float32)
    return jnp.asarray(np.concatenate([base, warm]))


def default_max_steps_multijob(cluster: Params,
                               jobs: Sequence[JobSpec],
                               safety: float = 2.0) -> int:
    """Per-job single-job budgets summed (each race event is one step),
    plus head-room for shop-queue churn under a tight capacity."""
    steps = 0
    for spec in jobs:
        p = cluster.replace(job_size=spec.job_size,
                            job_length=spec.job_length,
                            warm_standbys=spec.warm_standbys,
                            repair_servers=0)
        steps += default_max_steps(p, safety)
    return steps


@dataclasses.dataclass(frozen=True)
class _MultiJobStep:
    """The multi-job engine as the chunk loop's static ``step``
    (:func:`repro.core.vectorized._chunk_loop`): the static arguments of
    :func:`_mj_step_u`, hashed by value."""

    J: int
    impl: Optional[str]
    hist_channels: tuple

    n_uniforms = _N_UNIFORMS
    #: no fault domains or campaigns on this engine
    scen = None

    def __call__(self, s, u, pv):
        return _mj_step_u(s, u, pv, self.J, self.impl, self.hist_channels)

    def extract(self, state, rows) -> Dict[str, object]:
        return _extract_point(state, rows, self.J, self.hist_channels)


def _unsupported_error(cluster: Params, jobs) -> ValueError:
    reasons = unsupported_reasons_multijob(cluster, jobs) \
        or ["unknown reason — please report"]
    return ValueError(
        "this multi-job cluster is outside the CTMC envelope: "
        + "; ".join(reasons)
        + "; use core.multijob.simulate_multijob (or engine='auto') "
        "instead")


def _extract_point(state, rows, J: int, channels: tuple,
                   ) -> Dict[str, object]:
    """Per-point result: a list of single-job-compatible array dicts
    (one per job — ``metrics.aggregate_arrays`` consumes them directly)
    plus the cluster-level lanes."""
    per_job: List[Dict[str, np.ndarray]] = []
    edges = (np.asarray(state["hist_edges"], np.float64)
             if "hist" in state and channels else None)
    if edges is not None:
        hist = np.asarray(state["hist"][rows], np.float64)
        hist = hist.reshape(hist.shape[0], J, len(channels), -1)
    for j in range(J):
        d: Dict[str, np.ndarray] = {}
        for m in _MJ_JOB_METRICS:
            d[m] = np.asarray(state[m][rows, j])
        d["lost_work"] = np.zeros_like(d["useful_work"])
        d["completed"] = np.asarray(
            state["phase"][rows, j] == DONE, np.float32)
        d["run_durations"] = np.asarray(state["run_durations"][rows, j])
        d["n_runs"] = np.asarray(state["n_runs"][rows, j])
        d["cur_run"] = np.asarray(state["cur_run"][rows, j])
        if edges is not None:
            for ch_i, ch in enumerate(channels):
                d[f"hist_{ch}"] = hist[:, j, ch_i]
            d["hist_edges"] = edges
        per_job.append(d)
    out: Dict[str, object] = {"per_job": per_job}
    for m in _MJ_CLUSTER_METRICS:
        out[m] = np.asarray(state[m][rows])
    tt = np.stack([d["total_time"] for d in per_job], axis=-1)
    out["makespan"] = tt.max(-1)
    out["completed"] = np.asarray(
        np.prod([d["completed"] for d in per_job], axis=0), np.float32)
    return out


def _wrap_single_job(arrays: Dict[str, np.ndarray]) -> Dict[str, object]:
    """Adapt a single-job CTMC result dict to the multi-job shape (the
    J=1, unbounded-shop reduction path)."""
    R = len(arrays["total_time"])
    zeros = np.zeros(R, np.float32)
    out: Dict[str, object] = {"per_job": [arrays]}
    out["makespan"] = np.asarray(arrays["total_time"])
    out["completed"] = np.asarray(arrays.get("completed", zeros + 1.0))
    for m in ("n_auto_repairs", "n_manual_repairs", "n_failed_repairs"):
        out[m] = np.asarray(arrays.get(m, zeros))
    for m in ("stall_handoffs", "n_shop_queued", "conservation_err"):
        out[m] = zeros
    return out


def simulate_multijob_ctmc_sweep(
        points: Sequence[Tuple[Params, Sequence[JobSpec]]],
        n_replicas: int = 1024, seed: int = 0,
        max_steps: Optional[int] = None,
        impl: Optional[str] = None,
        chunk_steps: Optional[int] = None,
        early_exit: bool = True,
        bucketed: bool = True,
        max_runs: Optional[int] = None,
        shards: Optional[int] = None) -> List[Dict[str, object]]:
    """Batched multi-job sweep: one compiled program per job-count group.

    ``points`` is a sequence of ``(cluster Params, [JobSpec, ...])``
    pairs.  Points sharing a job count J (the only static structure key)
    — regardless of job sizes, lengths, rates, pool sizes, or shop
    capacity, all of which are traced — run as ONE flat (P*R,) batch
    through one XLA compilation, with pow2 shape bucketing and common
    random numbers exactly like the single-job sweep.

    Returns one dict per point: ``per_job`` is a list of
    single-job-compatible array dicts (feed each to
    ``metrics.aggregate_arrays``), plus cluster lanes ``makespan``,
    ``stall_handoffs``, the shared-shop counters, ``n_shop_queued``,
    ``conservation_err`` (max per-step deviation of the server-count
    invariant — exactly 0.0 in a correct run), and the all-jobs
    ``completed`` flag.

    Reduction: 1-job points with ``repair_servers == 0`` route through
    the single-job engine (bit-identical to a direct
    :func:`repro.core.vectorized.simulate_ctmc_sweep` call, same compile
    cache) — the multi-job program is only built when the multi-job
    machinery is actually needed.
    """
    points = [(c, tuple(js)) for c, js in points]
    with tracing.span(tracing.PREPARE) as prepare:
        for c, js in points:
            if not supports_multijob(c, js):
                raise _unsupported_error(c, js)
            # the cluster-level job fields are unused in multi-job mode;
            # validate through a per-job surrogate (the event engine's
            # Coordinator params are built the same way)
            c.replace(job_size=js[0].job_size, job_length=js[0].job_length,
                      warm_standbys=js[0].warm_standbys).validate()
            total_needed = sum(j.job_size + j.warm_standbys for j in js)
            if c.working_pool_size < total_needed:
                raise ValueError(
                    f"working pool {c.working_pool_size} cannot host "
                    f"{len(js)} jobs needing {total_needed}")
        if not points:
            return []
        if len({c.histogram for c, _ in points}) > 1:
            raise ValueError(
                "all points of a batched multi-job sweep must share the "
                "same Params.histogram spec (the in-scan accumulator "
                "layout is per-batch); split the grid or unify the spec")
        channels = _selected_channels(points[0][0].histogram)
        # replica sharding + kernel dispatch resolve exactly like the
        # single-job sweep: explicit args win, else the (single) Params
        # value — a mixed engine_shards grid raises, mixed kernel impls
        # split the compile groups
        shards = vz._resolve_shards(shards, [c for c, _ in points])

        # the single-job reduction, then one group per job count
        single_idx = [i for i, (c, js) in enumerate(points)
                      if len(js) == 1 and c.repair_servers == 0]
        programs = []
        if single_idx:
            sp = [points[i][0].replace(job_size=points[i][1][0].job_size,
                                       job_length=points[i][1][0].job_length,
                                       warm_standbys=points[i][1][0]
                                       .warm_standbys)
                  for i in single_idx]
            programs = [([single_idx[k] for k in idxs], *rest)
                        for idxs, *rest in vz.sweep_programs(
                            sp, n_replicas, seed, max_steps, impl,
                            chunk_steps, early_exit, bucketed, max_runs,
                            shards)]
        groups: Dict[_MultiJobStep, list] = {}
        for i, (c, js) in enumerate(points):
            if i not in single_idx:
                step = _MultiJobStep(
                    len(js), impl if impl is not None else c.event_race_impl,
                    channels)
                groups.setdefault(step, []).append(i)

        def initial_state(pts, R, step):
            mr = (max(c.max_run_records for c, _ in pts) if max_runs is None
                  else max_runs)
            return _mj_initial_state_batch(pts, R, mr)

        programs += vz._build_programs(
            [(idxs, step, [points[i] for i in idxs])
             for step, idxs in groups.items()],
            n_replicas, seed, max_steps, chunk_steps, early_exit, bucketed,
            shards, lambda pt: default_max_steps_multijob(*pt),
            lambda pt: _mj_params_vector(*pt), initial_state)
        prepare.set_metadata(
            rows=sum(args[0].shape[0] for _, _, _, args, _ in programs),
            real_rows=len(points) * n_replicas)
    results = vz._run_programs(programs, len(points), n_replicas)
    for i in single_idx:
        results[i] = _wrap_single_job(results[i])
    return results


def simulate_multijob_ctmc(cluster: Params, jobs: Sequence[JobSpec],
                           n_replicas: int = 1024, seed: int = 0,
                           **kw) -> Dict[str, object]:
    """Single-point convenience wrapper over the batched sweep."""
    return simulate_multijob_ctmc_sweep([(cluster, tuple(jobs))],
                                        n_replicas=n_replicas, seed=seed,
                                        **kw)[0]
