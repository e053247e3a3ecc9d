"""Vectorized JAX CTMC engine: thousands of AIReSim replicas per device.

TPU adaptation of the paper's DES (DESIGN.md §2.2): under the paper's
default exponential assumption the cluster is a continuous-time Markov
chain over server *compartments* — servers are exchangeable within
(origin x health) classes, so counts are sufficient state.  Each step
races the exponential clock families against the deterministic timers
(recovery / host-selection / completion) with the kernels.ops.event_race
Pallas kernel, then applies the winning transition with masked updates.
``lax.scan`` over events x vectorization over replicas turns a whole
replication study into a single XLA program; parameter sweeps stack one
level higher: :func:`simulate_ctmc_sweep` flattens a (points x replicas)
grid into one batch axis so an entire sweep — including *structural*
sweeps over job_size / pool sizes / warm_standbys — is a single compiled
program, and the scan runs in chunks inside a ``lax.while_loop`` that
stops as soon as every replica reaches DONE — the ``default_max_steps``
head-room is only paid when a trajectory actually needs it.

Structure padding: every point shares one compartment layout (4 classes
x 4 pools + the two repair shops), so differing pool structures differ
only in the *initial occupancy values*, which are traced inputs.  A
point with smaller pools leaves the surplus compartments at zero
occupancy; zero-count compartments contribute zero rates and are inert
in the event race.  :func:`simulate_ctmc_sweep` exploits this to run a
mixed-structure grid as one flat ``(P*R,)`` batch with exactly one XLA
compilation.

Exact run durations: the scan carries a per-replica ring buffer of the
last ``max_runs`` failure-to-failure useful-compute intervals (the event
engine's ``run_durations``), plus the total attempt count and the
in-flight interval, so ``metrics.aggregate_arrays`` reports
``run_duration_pooled`` / ``mean_run_duration`` exactly instead of the
former total_time/(n_failures+1) approximation.

Streaming histograms: alongside the ring buffer the scan accumulates
per-replica log-spaced histograms (``Params.histogram``, a
:class:`repro.core.histograms.HistogramSpec`) of run durations, recovery
downtime (ETTR), and replacement waiting — O(bins) memory with **no**
run-count bound, so distribution percentiles survive multi-year horizons
where the ring buffer truncates.  The bin layout matches the pure-numpy
reference accumulator in :mod:`repro.core.histograms` (left-closed /
right-open, under/overflow slots), so both engines emit comparable
distributions; ``histogram=None`` compiles the accumulator out.  Each
step only records its bins; the chunk loop adds a chunk's records to
the histogram once, after its scan (the counts are exact integers, so
this equals adding every step).

Checkpoint rollback + write cost: when ``Params.checkpoint_interval``
is positive, the scan tracks work-since-last-checkpoint in a dedicated
lane (no lossy ``mod`` arithmetic), charges it back on every failure
(``lost_work``), and races a deterministic checkpoint-write residual:
every ``checkpoint_interval`` minutes of phase work the replica enters
a ``checkpoint_cost``-minute OVERHEAD write (``checkpoint_overhead``),
during which the failure clock is frozen — the hazard age neither
advances nor resets, exactly the event engine's segment-loop timing.  A
checkpoint is durable from write start.  Both knobs are *traced*
columns: a (checkpoint_interval x checkpoint_cost x anything) grid is
one XLA program, and ``checkpoint_interval=0`` leaves the residual at
+inf — the same program, bit-identical trajectories and uniform stream.

Shape bucketing: on top of structure padding, ``simulate_ctmc_sweep``
(``bucketed=True``, the default) rounds the point
count P and replica count R up to powers of two with *inert* padding
rows (phase DONE from step 0, zero rates, masked out of extraction) and
rounds the step budget up to a whole number of chunks with the chunk
count passed as a traced scalar — so repeated sweeps of different
(P, R, step-budget) signatures inside one bucket share a single XLA
program.  Uniform draws are always generated at the power-of-two replica
width and sliced, which keeps bucketed results bit-identical to
unbucketed on the real rows.

Compartment classes: c = 2*origin + bad, i.e.
  0: working-origin good   1: working-origin bad
  2: spare-origin good     3: spare-origin bad

Event families (K_exp = 16): random failure x4 classes, systematic
failure x4, auto-repair completion x4, manual completion x4.
Deterministic (K_det = 2): job completion, recovery/host-selection timer.

Non-exponential hazards: Weibull, bathtub, and lognormal failure
processes run on this same fast path (``supports`` says yes;
``engine=auto`` dispatches here).  The scan carries a per-replica *phase
age* — failure clocks restart whenever the job (re)starts, so every
running server shares one age and the fleet's first failure is a single
age-indexed intensity per health class (see :mod:`repro.core.hazards`).
Weibull failures are sampled by exact closed-form conditional inversion
entering the event race as a deterministic residual; bathtub and
lognormal failures use hazard majorization with Ogata-style thinning
(accept/reject inside the compiled step, plus a window-expiry phantom
timer) — bathtub bounds its convex shape at the window endpoints,
lognormal bounds its unimodal hazard at the numerically-located mode
clipped into the window.  The hazard family is a static compile switch:
exponential grids keep the exact pre-existing program (same state, same
uniform stream), and each family compiles one program per shape bucket.

Non-exponential repairs: Weibull / lognormal / deterministic repair
distributions run here too, via a per-replica *repair-slot* lane.
Repair clocks differ from failure clocks in both ways that matter: they
do NOT reset when the job restarts, and servers enter the shop at
different times, so there is no shared age.  Each slot carries one
in-repair server's (class, stage, remaining duration); the duration is
sampled *at entry* by exact inverse CDF (the same family machinery the
failure race uses — :class:`repro.core.hazards.HazardSampler`), exactly
mirroring the event engine's ``RepairShop`` which draws the stage
duration when the stage begins.  The minimum remaining time enters the
event race as one more deterministic residual — placed FIRST so that an
exact tie with job completion resolves repair-first, matching the event
engine's heap order (the repair timeout was scheduled before the final
phase's completion timeout).  Escalation re-arms the winning slot with
a manual-stage draw.  The slot lane is auto-sized from the expected
shop occupancy (``Params.repair_slots`` overrides); a full lane
surfaces as the ``n_repair_overflow`` metric and a RuntimeWarning.
Exponential repairs keep the original count-based compartments
bit-for-bit (memoryless repairs need no per-server state).

Correlated failure domains + campaigns: when ``Params.fault_domains`` /
``Params.campaign`` are set (see :mod:`repro.core.faultdomains` and
docs/scenarios.md), the race grows one extra exponential lane per level
of fault domains (racks, pods) — a shared *shock* clock of the level's
domain count times its per-domain rate, live in every non-DONE phase;
the struck domain is uniform within its level, picked from where the
race's pick uniform fell inside the level's share of the total rate,
and its fleet fraction follows from its index in closed form — and the
flattened campaign schedule races as one more deterministic residual
(placed first, so a campaign entry beats a same-instant timer on both
engines).  Each step records its struck domain, and the chunk loop adds
a chunk's records to the per-domain ``domain_shocks`` counts at once,
as it adds the histogram.  A shock or scripted kill removes
``fraction x count`` servers from every pool at once (stochastically
rounded, class-proportional), sends them through the auto-repair
compartment, and
bulk-replaces the running block through the standby -> working ->
spare waterfall; the replacement shortfall accumulates in a ``deficit``
lane so the job only unstalls when the whole block is restored.
Maintenance windows gate the exponential repair rates to zero — exact
pause/resume by memorylessness.  The scenario *structure* (rack and pod
counts, schedule codes) is a static compile switch; every rate, the
fleet size, and each campaign time, fraction and target domain is
traced, so a shock-rate grid compiles once.  Scenarios require
exponential repairs on this path (``supports`` routes
non-exponential-repair scenarios to the event engine); in-shop servers
struck by a shock re-break, which is exact-in-law a no-op for
exponential stages and is therefore only counted.

Known approximations vs the event-driven oracle (validated statistically
in tests/test_vectorized.py, tests/test_nonexp.py, and
tests/test_repair_dist.py):
  * class-proportional sampling everywhere (exact under exchangeability);
  * misdiagnosis picks the wrong server proportionally over ALL running
    servers (the oracle excludes the failed one: O(1/4096) difference);
  * the initial bad-server split across pools uses its expectation;
  * a domain shock kills stochastically-rounded class-proportional
    counts per pool rather than a fixed member set (exact in
    expectation under round-robin striping), and a bulk replacement
    that partially stalls drops its host-selection surcharge (the
    stall interval dominates it on both engines).

Out of scope (routed to core.simulation): retirement, bad-set
regeneration, deterministic/user-registered failure distributions,
user-registered repair distributions, failing standbys, and fault
domains / campaigns combined with non-exponential repairs.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial, reduce
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.kernels import ops
from . import faultdomains, hazards, replicas, tracing
from .histograms import DEFAULT_CHANNELS, HIST_CHANNELS
from .params import Params

COMPUTE, OVERHEAD, STALL, DONE = 0, 1, 2, 3
K_EXP = 16

_METRICS = ("total_time", "n_failures", "n_random_failures",
            "n_systematic_failures", "n_preemptions", "n_auto_repairs",
            "n_manual_repairs", "n_failed_repairs", "n_host_selections",
            "n_standby_swaps", "n_undiagnosed", "n_misdiagnosed",
            "stall_time", "recovery_overhead", "lost_work", "useful_work",
            "checkpoint_overhead", "n_repair_overflow", "n_domain_shocks",
            "n_shock_killed", "n_campaign_events")


def unsupported_reasons(params: Params) -> list:
    """Why these params are outside the CTMC envelope (empty = inside).

    The single source of truth for :func:`supports` and for the
    ``engine="ctmc"`` refusal message in :mod:`repro.core.backend` —
    hand-maintained reason lists there went stale (PR 6 routed the
    fault-domain x non-exponential-repair combination to the event
    engine but the message never learned it), so the message is now
    *built* from this list.

    >>> from repro.core import Params
    >>> unsupported_reasons(Params())
    []
    >>> unsupported_reasons(Params(failure_distribution="deterministic"))
    ['failure distribution has no fast-path hazard family (closed-form \
exponential/weibull/bathtub/lognormal, an empirical fit, or a \
registered distribution with valid hazard_segments())']
    >>> from repro.core.faultdomains import FaultTopology
    >>> topo = FaultTopology(n_racks=8, rack_shock_rate=1e-5)
    >>> unsupported_reasons(Params(fault_domains=topo,
    ...                            repair_distribution="weibull"))
    ['fault domains / campaigns require exponential repairs on the \
fast path (a struck in-shop server would need a per-slot redraw)']
    """
    reasons = []
    if hazards.hazard_kind(params) is None:
        reasons.append(
            "failure distribution has no fast-path hazard family "
            "(closed-form exponential/weibull/bathtub/lognormal, an "
            "empirical fit, or a registered distribution with valid "
            "hazard_segments())")
    if hazards.repair_kind(params) is None:
        reasons.append(
            "repair distribution has no fast-path repair family "
            "(exponential/weibull/lognormal/deterministic, an empirical "
            "fit, or a registered distribution with valid "
            "hazard_segments())")
    if ((params.fault_domains is not None or params.campaign is not None)
            and hazards.repair_kind(params) != "exponential"):
        reasons.append(
            "fault domains / campaigns require exponential repairs on "
            "the fast path (a struck in-shop server would need a "
            "per-slot redraw)")
    if params.repair_servers != 0:
        reasons.append(
            "finite repair-shop capacity (repair_servers > 0) — the "
            "multi-job CTMC engine models it; the single-job program "
            "has no queue compartment")
    if params.retirement_threshold != 0:
        reasons.append("retirement policies are event-engine-only")
    if params.bad_set_regeneration_period != 0:
        reasons.append("bad-set regeneration is event-engine-only")
    if params.standbys_can_fail:
        reasons.append("failing warm standbys are event-engine-only")
    return reasons


def supports(params: Params) -> bool:
    """Can the CTMC engine simulate these params exactly?

    True for the paper's exponential baseline *and* the age-dependent
    Weibull / bathtub / lognormal failure families (sampled on the fast
    path via conditional inversion / hazard thinning) combined with
    exponential / Weibull / lognormal / deterministic repair
    distributions (sampled at shop entry via inverse CDF through the
    repair-slot lane), plus trace-driven ``empirical`` piecewise-
    constant hazards on both sides — see :mod:`repro.core.hazards`.
    Checkpoint rollback (``checkpoint_interval`` / ``checkpoint_cost``)
    runs on the fast path too, as traced knobs.  The event-engine-only
    extensions (retirement, bad-set regeneration, failing standbys)
    must be off.  ``engine="auto"`` falls back to the event engine
    whenever this returns False.

    >>> from repro.core import Params
    >>> supports(Params())                                    # Table-I default
    True
    >>> supports(Params(failure_distribution="weibull",
    ...                 distribution_kwargs={"k": 1.5}))      # wear-out
    True
    >>> supports(Params(failure_distribution="lognormal"))    # heavy tail
    True
    >>> supports(Params(repair_distribution="weibull",
    ...                 distribution_kwargs={"k": 0.7}))      # slow repairs
    True
    >>> supports(Params(failure_distribution="empirical",     # trace-driven
    ...                 distribution_kwargs={"edges": [24.0, 120.0],
    ...                                      "rates": [3.0, 1.0, 0.4]}))
    True
    >>> supports(Params(failure_distribution="deterministic"))  # event engine
    False
    >>> supports(Params(retirement_threshold=3))
    False

    Finite repair-shop capacity (``Params.repair_servers``) is modeled
    by the *multi-job* CTMC engine (:mod:`repro.core.vectorized_multijob`,
    which partitions the shop by owning job and carries a queued-server
    lane); the single-job program has no queue compartment, so such
    params route to the event engine here:

    >>> supports(Params(repair_servers=8))
    False

    Correlated fault domains and injection campaigns
    (:mod:`repro.core.faultdomains`) stay on the fast path under
    exponential repairs — a struck in-shop server's stage restart is
    exact-in-law a no-op there.  Non-exponential repairs would need
    per-slot redraws, so that combination routes to the event engine:

    >>> from repro.core.faultdomains import FaultTopology
    >>> topo = FaultTopology(n_racks=8, rack_shock_rate=1e-5)
    >>> supports(Params(fault_domains=topo))
    True
    >>> supports(Params(fault_domains=topo, repair_distribution="weibull"))
    False
    """
    return not unsupported_reasons(params)


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------

def _initial_counts(p: Params):
    total = p.working_pool_size + p.spare_pool_size
    n_bad = int(round(p.systematic_failure_fraction * total))
    bad_w = round(n_bad * p.working_pool_size / total)
    bad_s = n_bad - bad_w

    def split(n_take, pool_good, pool_bad):
        frac_bad = pool_bad / max(pool_good + pool_bad, 1)
        take_bad = int(round(n_take * frac_bad))
        return n_take - take_bad, take_bad

    w_good, w_bad = p.working_pool_size - bad_w, bad_w
    run_g, run_b = split(p.job_size, w_good, w_bad)
    w_good -= run_g
    w_bad -= run_b
    n_sb = min(p.warm_standbys, w_good + w_bad)
    sb_g, sb_b = split(n_sb, w_good, w_bad)
    w_good -= sb_g
    w_bad -= sb_b
    return {
        "run": [run_g, run_b, 0, 0],
        "sb": [sb_g, sb_b, 0, 0],
        "fw": [w_good, w_bad, 0, 0],
        "fs": [0, 0, p.spare_pool_size - bad_s, bad_s],
    }


def _age_dtype(p: Params):
    """Dtype of the hazard-age / repair-countdown lanes.

    The float64 carve-out (``Params.age_dtype``) needs the jax x64 flag;
    without it jnp would silently downcast to float32, so requesting it
    unenabled is a hard error rather than a quiet no-op.
    """
    if p.age_dtype == "float64":
        if not jax.config.jax_enable_x64:
            raise ValueError(
                "Params.age_dtype='float64' requires the jax x64 flag: "
                "set JAX_ENABLE_X64=1 or "
                'jax.config.update("jax_enable_x64", True) before '
                "simulating (float64 arrays silently degrade to float32 "
                "otherwise)")
        return jnp.float64
    return jnp.float32


def _initial_state_batch(pts, R: int, max_runs: int,
                         rkind: str = "exponential",
                         n_slots: int = 0,
                         scen=None) -> Dict[str, jnp.ndarray]:
    """Padded initial state for a structural grid, point-major (P*R, ...).

    All points share one compartment layout, so structural parameters
    (job_size, pool sizes, warm_standbys, systematic fraction, job_length,
    host-selection offset) enter purely as per-point initial *values*:
    compartments a small point does not populate sit at zero occupancy and
    therefore carry zero rates — inert in the event race.  That padding is
    what lets one compiled program cover every structure in the grid.

    ``rkind`` / ``n_slots`` size the repair-slot lane (non-exponential
    repairs only): ``repair_rem`` +inf marks a free slot.

    ``scen`` is the static scenario key ``(n_racks, n_pods, codes)``
    from :func:`repro.core.faultdomains.scenario_key` — it adds the
    replacement-deficit lane, the per-domain shock counters, and (when
    the flattened campaign schedule is non-empty) the schedule pointer
    and maintenance flag.
    """
    P = len(pts)
    B = P * R
    counts = [_initial_counts(p) for p in pts]
    adt = _age_dtype(pts[0])

    def tile(key):
        arr = np.asarray([c[key] for c in counts], np.float32)   # (P, 4)
        return jnp.asarray(np.repeat(arr, R, axis=0))            # (P*R, 4)

    def per_point(vals):
        return jnp.asarray(np.repeat(np.asarray(vals, np.float32), R))

    state = {k: tile(k) for k in ("run", "sb", "fw", "fs")}
    state["auto"] = jnp.zeros((B, 4), jnp.float32)
    state["man"] = jnp.zeros((B, 4), jnp.float32)
    state["t"] = per_point([p.host_selection_time for p in pts])
    state["work_left"] = per_point([p.job_length for p in pts])
    state["timer"] = jnp.full((B,), jnp.inf, jnp.float32)
    state["stall_start"] = jnp.zeros((B,), jnp.float32)
    state["phase"] = jnp.full((B,), COMPUTE, jnp.int32)
    #: phase age: compute minutes since the job last (re)started — the
    #: hazard clock of the non-exponential families (inert for
    #: exponential, where the process is memoryless)
    state["age"] = jnp.zeros((B,), adt)
    if rkind != "exponential":
        # repair-slot lane: one (class, stage, remaining) triple per
        # in-repair server; remaining counts down in wall-clock time and
        # never resets with the job (unlike the failure age above)
        state["repair_rem"] = jnp.full((B, n_slots), jnp.inf, adt)
        state["repair_cls"] = jnp.zeros((B, n_slots), jnp.int32)
        state["repair_stage"] = jnp.zeros((B, n_slots), jnp.int32)
    state["cur_run"] = jnp.zeros((B,), jnp.float32)
    #: compute minutes since the last durable checkpoint (resets at every
    #: write, restart, and completion); the failure's rollback charge and
    #: the write residual both read it — a dedicated lane instead of
    #: ``mod(phase_work, interval)``, which drifts under fp accumulation.
    #: Inert (stays 0-cost) when checkpoint_interval == 0.
    state["ckpt_work"] = jnp.zeros((B,), jnp.float32)
    #: 1.0 while the OVERHEAD phase is a checkpoint *write* (whose expiry
    #: resumes compute without resetting the hazard age), 0.0 otherwise
    state["in_ckpt"] = jnp.zeros((B,), jnp.float32)
    state["n_runs"] = jnp.zeros((B,), jnp.int32)
    state["run_durations"] = jnp.zeros((B, max_runs), jnp.float32)
    spec = pts[0].histogram
    sel = _selected_channels(spec)
    if sel:
        # only the channels the spec selects are carried through the
        # scan — unselected channels are compiled out of the state
        # entirely (a smaller carry, less to update each step).  The
        # grid shares the first point's bin layout.
        state["hist"] = jnp.zeros((B, len(sel), spec.n_counts),
                                  jnp.float32)
        state["hist_edges"] = jnp.asarray(spec.edges(), jnp.float32)
    if scen is not None:
        n_racks, n_pods, camp_codes = scen
        # outstanding replacements after bulk kills: the job unstalls
        # only when the whole struck block has been restored
        state["deficit"] = jnp.zeros((B,), jnp.float32)
        if n_racks:
            state["domain_shocks"] = jnp.zeros((B, n_racks + n_pods),
                                               jnp.float32)
        if len(camp_codes):
            state["camp_idx"] = jnp.zeros((B,), jnp.int32)
        if faultdomains.MAINT_START in camp_codes:
            state["maint"] = jnp.zeros((B,), jnp.float32)
    for m in _METRICS:
        state[m] = jnp.zeros((B,), jnp.float32)
    return state


#: state entries with no leading replica axis (scan-invariant constants)
_UNBATCHED_STATE = ("hist_edges",)


def _selected_channels(spec) -> tuple:
    """Channels carried through the scan, in fixed HIST_CHANNELS order.

    The tuple is part of the compiled program (it sizes the in-scan
    accumulator), so it must be derived deterministically from the spec,
    never from dict/set iteration order.
    """
    if spec is None:
        return ()
    return tuple(ch for ch in HIST_CHANNELS if ch in spec.channels)


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _bucket_pad_state(state: Dict[str, jnp.ndarray], P: int, R: int,
                      P_pad: int, R_pad: int) -> Dict[str, jnp.ndarray]:
    """Pad a (P*R, ...) point-major state to (P_pad*R_pad, ...).

    Padding rows start in phase DONE (every job of the row, where the
    phase has a job axis) with zero occupancies, so they carry zero
    rates and are inert for the entire scan — including the global
    early-exit check.  Extraction masks them out; only the shared shape
    signature (and therefore the compiled program) sees them.
    """
    out: Dict[str, jnp.ndarray] = {}
    for k, v in state.items():
        if k in _UNBATCHED_STATE:
            out[k] = v
            continue
        v = v.reshape((P, R) + v.shape[1:])
        pad = [(0, P_pad - P), (0, R_pad - R)] + [(0, 0)] * (v.ndim - 2)
        out[k] = jnp.pad(v, pad).reshape((P_pad * R_pad,) + v.shape[2:])
    real = ((jnp.arange(P_pad * R_pad) // R_pad < P)
            & (jnp.arange(P_pad * R_pad) % R_pad < R))
    phase = out["phase"]
    out["phase"] = jnp.where(real.reshape((-1,) + (1,) * (phase.ndim - 1)),
                             phase, DONE)
    return out


def _initial_state(p: Params, R: int,
                   max_runs: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    rkind = hazards.repair_kind(p) or "exponential"
    return _initial_state_batch(
        [p], R, _max_runs_for([p]) if max_runs is None else max_runs,
        rkind, _repair_slots_for([p], rkind), faultdomains.scenario_key(p))


def _max_runs_for(pts) -> int:
    return max(p.max_run_records for p in pts)


def _repair_slots_for(pts, rkind: str) -> int:
    """Repair-slot lane width for a batched group (host-side, static).

    Auto-sizing keeps the overflow probability astronomically small:
    twice the expected shop occupancy (Little's law via the hazard-aware
    event-rate estimate) plus eight standard deviations of the Poisson
    in-shop count.  Rounded up to a power of two so repair-parameter
    grids of similar scale share one compiled program — but never past
    the physical bound (every server in repair at once), where overflow
    is impossible and extra width is pure per-step cost: the slot
    min/argmin/masked-write ops are the lane's whole overhead.
    ``Params.repair_slots > 0`` overrides per point.
    """
    if rkind == "exponential":
        return 0
    n = 1
    for p in pts:
        total = p.working_pool_size + p.spare_pool_size
        if p.repair_slots > 0:
            want = min(p.repair_slots, total)
        else:
            occ = hazards.expected_repair_occupancy(p)
            # an infinite-mean repair stage (a disabled clock: the
            # server never returns) drives the Little's-law estimate to
            # inf/NaN; the physical cap is the honest answer there
            if not math.isfinite(occ):
                occ = float(total)
            want = min(int(2.0 * occ + 8.0 * math.sqrt(max(occ, 1.0)) + 8.0),
                       total)
        n = max(n, min(_next_pow2(want), total))
    return n


def _pick_classes(counts: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """Categorical draws proportional to counts: (R, G, 4) x (R, G) -> (R, G).

    One cumsum/reduction pass covers all G per-pool picks of a step —
    the scan body is op-dispatch-bound on CPU, so fusing the four pool
    draws keeps step latency down.
    """
    total = jnp.maximum(counts.sum(-1), 1e-30)
    cdf = jnp.cumsum(counts, axis=-1) / total[..., None]
    return jnp.minimum(
        jnp.sum((u[..., None] >= cdf).astype(jnp.int32), -1), 3)


def _onehot(c: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.one_hot(c, 4, dtype=jnp.float32)


def row_hit(col: jnp.ndarray, width: int) -> jnp.ndarray:
    """Boolean one-hot of per-replica column indices: ``(...,)`` ->
    ``(..., width)``.

    A step writes at most one column of each replica's buffers (ring
    slot, histogram bin, repair slot, job).  Written as
    ``buf.at[rows, col]`` XLA's TPU backend sorts every index and then
    scatters in a serial loop, which at 2^18 replica rows made one step
    take longer than the rest of the scan; a select against this mask
    is one dense vector pass, bit-identical to the scatter.
    """
    return jnp.arange(width) == col[..., None]


def _bin_bits(n_counts: int) -> int:
    """Bits of one channel's slot in a packed histogram record: the
    narrowest of 8 / 16 / 32 that holds the sentinel ``n_counts``."""
    return next(b for b in (8, 16, 32) if n_counts < 2 ** b)


def _hist_bins(edges: jnp.ndarray, vals: jnp.ndarray,
               masks: jnp.ndarray) -> jnp.ndarray:
    """One step's histogram record: ``(C, B)`` values and masks ->
    ``(n_words, B)`` uint32.

    Each channel's bin is ``searchsorted(edges, val, side="right")``
    (compared against every edge: the default binary search lowers to a
    gather loop on the TPU); a masked channel records the sentinel
    ``n_counts``, which matches no bin.  ``32 // _bin_bits(n_counts)``
    channels share a word, so the rows stay on the lane axis: a
    channel-minor record would pad the channels to a full lane tile.
    """
    n_counts = edges.shape[0] + 1
    bits = _bin_bits(n_counts)
    per = 32 // bits
    idx = jnp.searchsorted(edges, vals, side="right", method="compare_all")
    idx = jnp.where(masks, idx, n_counts).astype(jnp.uint32)
    return jnp.stack([
        reduce(jnp.bitwise_or, [
            idx[c] << (bits * (c - lo))
            for c in range(lo, min(lo + per, idx.shape[0]))])
        for lo in range(0, idx.shape[0], per)])


def _hist_flush(hist: jnp.ndarray, words) -> jnp.ndarray:
    """``hist`` (B, C, n_counts) plus the counts of K steps' records:
    ``words[w]`` is word ``w`` of :func:`_hist_bins` stacked over the
    steps, (K, B).

    Counts in int32 and converts once; the counts are integers below
    2**24, so the float32 sum equals adding the steps one at a time, in
    any order.
    """
    n_counts = hist.shape[-1]
    bits = _bin_bits(n_counts)
    per = 32 // bits
    idx = jnp.stack([(words[c // per] >> (bits * (c % per)))
                     & (2 ** bits - 1)
                     for c in range(hist.shape[1])],
                    axis=1).astype(jnp.int32)                  # (K, C, B)
    # (K, C, n_counts, B): the rows stay on the lane axis
    hit = jnp.arange(n_counts)[:, None] == idx[:, :, None, :]
    counts = jnp.sum(hit, axis=0, dtype=jnp.int32)
    return hist + jnp.moveaxis(counts, -1, 0).astype(jnp.float32)


#: a row's shock records placed per pass of :func:`_shock_flush`
SHOCK_SLOTS = 8


def _shock_flush(counts: jnp.ndarray, recs: jnp.ndarray) -> jnp.ndarray:
    """``counts`` (B, D) plus K steps' shock records: ``recs`` (K, B),
    each the struck domain or the sentinel D.

    A row takes few shocks in a chunk, so its records are ranked in step
    order and each pass adds the next :data:`SHOCK_SLOTS` of every row's
    records in one dense pass over ``counts``: a chunk in which no row
    took more costs one pass, a chunk without shocks none.  The counts
    are integers below 2**24, so this equals adding every step's record,
    in any order.
    """
    D = counts.shape[1]
    hit = recs < D
    rank = jnp.cumsum(hit, axis=0, dtype=jnp.int32) - 1
    n = rank[-1] + 1                                    # shocks per row

    def place(carry):
        j0, counts = carry
        add = 0
        for j in range(SHOCK_SLOTS):
            mine = hit & (rank == j0 + j)
            dom = jnp.where(n > j0 + j,
                            jnp.sum(jnp.where(mine, recs, 0), axis=0), D)
            add = add + row_hit(dom, D)
        return j0 + SHOCK_SLOTS, counts + add.astype(jnp.float32)

    return jax.lax.while_loop(lambda c: c[0] < n.max(), place,
                              (jnp.int32(0), counts))[1]


# ---------------------------------------------------------------------------
# one transition
# ---------------------------------------------------------------------------

def _struck_domain(rates: jnp.ndarray, u_pick: jnp.ndarray,
                   ev: jnp.ndarray, n_racks: int, n_pods: int):
    """The domain a shock strikes: uniform among the domains of the
    level whose lane won the race (racks ``0..n_racks-1``, then pods).

    The race picks lane ``k`` where ``u_pick`` falls in ``[C_{k-1},
    C_k)`` of the lanes' cumulative rates over their total.  Where it
    falls inside that interval is uniform, so its position picks one of
    the level's equal-rate domains by inverse CDF — what racing one
    lane per domain picks, up to float32 rounding.  Meaningless on a
    step no shock won; the caller masks it.
    """
    base = rates[:, :K_EXP].sum(-1)
    rack = rates[:, K_EXP]
    if n_pods:
        pod_won = ev == K_EXP + 1
        before = base + jnp.where(pod_won, rack, 0.0)
        width = jnp.where(pod_won, rates[:, K_EXP + 1], rack)
        n = jnp.where(pod_won, n_pods, n_racks)
        total = base + rack + rates[:, K_EXP + 1]
    else:
        before, width, n = base, rack, n_racks
        total = base + rack
    pos = (u_pick * total - before) / jnp.maximum(width, 1e-30)
    idx = jnp.clip(jnp.floor(pos * n), 0, n - 1).astype(jnp.int32)
    return jnp.where(pod_won, n_racks + idx, idx) if n_pods else idx


def _n_uniforms(kind: str, rkind: str = "exponential") -> int:
    """Uniform draws per step: the exponential program keeps its
    original 8-wide stream bit-for-bit; a non-exponential hazard family
    adds one lane (Exp(1) inversion draw for weibull, accept/reject for
    bathtub/lognormal) and a non-exponential repair family adds one
    more (the entry/escalation duration draw)."""
    return 8 + (kind != "exponential") + (rkind != "exponential")


def _step(s: Dict[str, jnp.ndarray], key_t: jax.Array, pv: jnp.ndarray,
          impl: Optional[str], kind: str = "exponential",
          rkind: str = "exponential",
          hist_channels: tuple = DEFAULT_CHANNELS,
          scen=None, n_seg: int = 0,
          n_rseg: int = 0) -> Dict[str, jnp.ndarray]:
    R = s["t"].shape[0]
    u = jax.random.uniform(key_t, (R, _n_uniforms(kind, rkind)),
                           dtype=jnp.float32, minval=1e-12, maxval=1.0)
    return _step_u(s, u, pv, impl, kind, rkind, hist_channels, scen,
                   n_seg, n_rseg)


def _step_u(s: Dict[str, jnp.ndarray], u: jnp.ndarray, pv: jnp.ndarray,
            impl: Optional[str], kind: str = "exponential",
            rkind: str = "exponential",
            hist_channels: tuple = DEFAULT_CHANNELS,
            scen=None, n_seg: int = 0,
            n_rseg: int = 0) -> Dict[str, jnp.ndarray]:
    """One CTMC transition for a batch of replicas.

    ``pv`` is either a single parameter vector shared by the whole batch
    or a (B, n_cols) matrix with one parameter row per replica — the
    layout the batched sweep uses after flattening the (points x
    replicas) grid.  Columns 0..15 are the base model parameters;
    the next ``hazards.hazard_col_count(kind, n_seg)`` columns are the
    failure-hazard block and the ``hazards.repair_col_count(rkind,
    n_rseg)`` after that the repair block, whose interpretations the
    *static* ``kind`` / ``rkind`` select (see :mod:`repro.core.hazards`).
    The closed-form families use the fixed 5 + 3 layout; the empirical
    family's blocks are ``[edges_a, rates_a, edges_b, rates_b]`` with
    the *static* segment counts ``n_seg`` / ``n_rseg`` sizing them —
    edge positions and rates stay traced, so a grid over fitted hazards
    from different log slices shares one compiled program.

    ``hist_channels`` is the static tuple of histogram channels the scan
    state carries (must match ``s["hist"].shape[1]``).  A state with
    ``hist`` gets the step's counts added; one with ``hist_bins`` instead
    (the chunk loop's) gets the step's record there, for the loop to add.

    ``scen`` is the static scenario key ``(n_racks, n_pods, codes)`` —
    when set, the scenario's trailing columns follow the repair columns
    (see :func:`repro.core.faultdomains.scenario_columns`) and the race
    gains one shock lane per level of fault domains (racks, pods) plus
    (for a non-empty schedule) a campaign residual.  A state with
    ``shock_dom`` (the chunk loop's) gets the step's struck domain
    there, for the loop to add to ``domain_shocks``.  Scenarios only
    reach this path with exponential repairs (``supports``), so
    ``scen`` and the repair-slot lane never co-exist.
    """
    n_hc = hazards.hazard_col_count(kind, n_seg)
    n_rc = hazards.repair_col_count(rkind, n_rseg)
    n_cols = 16 + n_hc + n_rc
    if pv.ndim == 1:
        cols = [pv[i] for i in range(16)]
        _c = lambda x: x            # param vs (B, 4) class arrays
    else:
        cols = [pv[:, i] for i in range(16)]
        _c = lambda x: x[:, None]
    (r_rand, r_sys, recovery, host_sel, waiting, auto_t, man_t,
     auto_fail, man_fail, p_auto, dp, du, ckpt, preempt_cost,
     warm_standbys, ckpt_cost) = cols

    def _vcol(lo, n):
        # contiguous column block (shared row or per-replica matrix);
        # the empirical segment arrays stay 1-/2-D instead of joining
        # the scalar unpack above
        return pv[lo:lo + n] if pv.ndim == 1 else pv[:, lo:lo + n]

    if kind == "empirical":
        # [rand edges (m-1), rand rates (m), sys edges (m-1), sys rates
        # (m)] — per-clock piecewise-constant hazards (hazard_columns)
        e_re = _vcol(16, n_seg - 1)
        e_rr = _vcol(16 + n_seg - 1, n_seg)
        e_se = _vcol(16 + 2 * n_seg - 1, n_seg - 1)
        e_sr = _vcol(16 + 3 * n_seg - 2, n_seg)
        hz = None
    else:
        hz = [pv[i] if pv.ndim == 1 else pv[:, i]
              for i in range(16, 16 + n_hc)]
    if rkind == "empirical":
        # [auto edges, auto rates, manual edges, manual rates] — stage
        # selection happens at slot entry below (repair_columns)
        r_ae = _vcol(16 + n_hc, n_rseg - 1)
        r_ar = _vcol(16 + n_hc + n_rseg - 1, n_rseg)
        r_me = _vcol(16 + n_hc + 2 * n_rseg - 1, n_rseg - 1)
        r_mr = _vcol(16 + n_hc + 3 * n_rseg - 2, n_rseg)
        rz = None
    else:
        rz = [pv[i] if pv.ndim == 1 else pv[:, i]
              for i in range(16 + n_hc, n_cols)]

    if scen is not None:
        # scenario columns: [fleet, racks_per_pod, rack rate, pod rate]
        # with a topology, then [times (L), kill fracs (L), target
        # domains (L)] — all traced; only the rack and pod counts, L and
        # the schedule codes are static
        n_racks, n_pods, camp_codes = scen
        D_dom = n_racks + n_pods
        Lc = len(camp_codes)
        has_maint = faultdomains.MAINT_START in camp_codes

        def _scol(lo, n):
            if not n:
                return None
            return pv[lo:lo + n] if pv.ndim == 1 else pv[:, lo:lo + n]

        n_tc = 4 if n_racks else 0
        if n_racks:
            fleet, rpp, rack_rate, pod_rate = (
                pv[n_cols + i] if pv.ndim == 1 else pv[:, n_cols + i]
                for i in range(n_tc))
        camp_t = _scol(n_cols + n_tc, Lc)
        camp_frac = _scol(n_cols + n_tc + Lc, Lc)
        camp_dom = _scol(n_cols + n_tc + 2 * Lc, Lc)

    u_time, u_pick, u_diag, u_wrong, u_cls, u_esc, u_succ, u_pool = (
        u[:, 0], u[:, 1], u[:, 2], u[:, 3], u[:, 4], u[:, 5], u[:, 6],
        u[:, 7])
    lane = 8
    u_haz = None
    if kind != "exponential":
        u_haz = u[:, lane]
        lane += 1
    u_dur = u[:, lane] if rkind != "exponential" else None

    computing = s["phase"] == COMPUTE
    in_overhead = s["phase"] == OVERHEAD
    stalled = s["phase"] == STALL
    active = s["phase"] != DONE
    # OVERHEAD flavor: a checkpoint *write* (timer expiry resumes compute
    # without resetting the hazard age) vs a recovery/restart (which does)
    in_ckpt_flag = s["in_ckpt"] > 0
    age = s["age"]
    # thinning families evaluate hazards on the float32 view: the
    # float64 age carve-out targets the weibull inversion / repair
    # countdown cancellations, not the (well-conditioned) hazard ratios
    age32 = age.astype(jnp.float32)

    # ---- rates (R, 16) ------------------------------------------------
    run = s["run"]
    # explicit f32: under the x64 flag (age_dtype carve-out) an
    # unannotated literal array would promote the whole rate matrix
    bad_mask = jnp.asarray([0.0, 1.0, 0.0, 1.0], jnp.float32)
    haz_weights = g_bar = hbar_r = hbar_s = None
    if kind == "weibull":
        # exact conditional inversion: the fleet's combined cumulative
        # hazard is C * age**k (all clocks share the shape k), so the
        # time-to-first-failure enters the race as a deterministic
        # residual and the failure channels carry no exponential rate.
        # haz_weights holds the per-channel hazard shares (age-invariant
        # because every clock shares the t**(k-1) profile) for the
        # failing-class pick below.
        c_rand, c_sys, w_k = hz[0], hz[1], hz[2]
        w_rand = run * _c(c_rand) * computing[:, None]
        w_sys = run * bad_mask[None, :] * _c(c_sys) * computing[:, None]
        haz_weights = jnp.concatenate([w_rand, w_sys], axis=-1)  # (B, 8)
        haz_resid = hazards.FAILURE_SAMPLERS["weibull"].conditional_residual(
            age, haz_weights.sum(-1), w_k, -jnp.log(u_haz))
        fail_rand = jnp.zeros_like(run)
        fail_sys = jnp.zeros_like(run)
    elif kind == "bathtub":
        # Ogata thinning: scale the exponential failure propensities by
        # the window majorant g_bar = max(g(age), g(age + W)) (valid by
        # convexity of g) and race a window-expiry phantom timer W; a
        # winning candidate is accepted below with prob g(age + dt)/g_bar.
        b_if, b_ti, b_ws, b_tw, b_win = hz[0], hz[1], hz[2], hz[3], hz[4]
        bt = hazards.FAILURE_SAMPLERS["bathtub"]
        g_bar = bt.majorant(age32, b_win, (b_if, b_ti, b_ws, b_tw))
        fail_rand = run * _c(r_rand) * g_bar[..., None] * computing[:, None]
        fail_sys = run * bad_mask[None, :] * _c(r_sys) * g_bar[..., None] \
            * computing[:, None]
        haz_resid = jnp.where(computing, b_win * jnp.ones_like(age32),
                              jnp.inf)
    elif kind == "lognormal":
        # Ogata thinning with the mode-located majorant: the lognormal
        # hazard is unimodal, so sup h over [age, age + W] is h at the
        # (numerically pre-located, traced) mode clipped into the
        # window.  Random and systematic clocks have different scales
        # and therefore different hazard *shapes* over age — each
        # family carries its own majorant and acceptance ratio
        # (thinning two independent NHPPs separately is exact).
        ln = hazards.FAILURE_SAMPLERS["lognormal"]
        l_sr, l_ss, l_sig, l_mode, l_win = hz[0], hz[1], hz[2], hz[3], hz[4]
        hbar_r = ln.majorant(age32, l_win, (l_sr, l_sig, l_mode))   # (B,)
        hbar_s = ln.majorant(age32, l_win, (l_ss, l_sig, l_mode))
        fail_rand = run * hbar_r[:, None] * computing[:, None]
        fail_sys = run * bad_mask[None, :] * hbar_s[:, None] \
            * computing[:, None]
        # both clocks disabled => zero window; disarm the expiry timer
        # instead of racing a zero residual forever
        win_eff = jnp.where(l_win > 0, l_win, jnp.inf)
        haz_resid = jnp.where(computing, win_eff * jnp.ones_like(age32),
                              jnp.inf)
    elif kind == "empirical":
        # Ogata thinning with the *exact* majorant: the window runs to
        # the nearest segment edge of either clock, over which both
        # hazards are constant — so the majorant is the current segment
        # rate and every in-window candidate is accepted (the accept
        # step below only guards fp edge crossings).  Phantom steps
        # occur only when a window-expiry timer re-anchors the race at
        # a segment boundary.  Random and systematic clocks carry their
        # own (edges, rates) columns and thin independently (exact for
        # two independent NHPPs).
        pe = hazards.FAILURE_SAMPLERS["empirical"]
        hbar_r = pe.hazard(age32, (e_re, e_rr))                     # (B,)
        hbar_s = pe.hazard(age32, (e_se, e_sr))
        fail_rand = run * hbar_r[:, None] * computing[:, None]
        fail_sys = run * bad_mask[None, :] * hbar_s[:, None] \
            * computing[:, None]
        win = jnp.minimum(hazards.piecewise_next_edge(age32, e_re),
                          hazards.piecewise_next_edge(age32, e_se))
        haz_resid = jnp.where(computing, win, jnp.inf)
    else:
        fail_rand = run * _c(r_rand) * computing[:, None]
        fail_sys = run * bad_mask[None, :] * _c(r_sys) * computing[:, None]
        haz_resid = None
    if rkind == "exponential":
        auto_rate = s["auto"] / jnp.maximum(_c(auto_t), 1e-9)
        man_rate = s["man"] / jnp.maximum(_c(man_t), 1e-9)
    else:
        # non-exponential repairs complete through the slot lane's
        # deterministic residual; the exponential repair channels carry
        # no rate (the auto/man compartment counts remain bookkeeping)
        auto_rate = jnp.zeros_like(run)
        man_rate = jnp.zeros_like(run)
    rate_parts = [fail_rand, fail_sys, auto_rate, man_rate]
    kx = K_EXP
    if scen is not None:
        if has_maint:
            # maintenance window: the repair shop is dark — gating the
            # exponential repair rates to zero is an exact pause/resume
            # (memorylessness); jnp.where keeps any inf in the rate
            # math from turning into 0*inf = NaN
            repair_on = (s["maint"] == 0.0)[:, None]
            auto_rate = jnp.where(repair_on, auto_rate, 0.0)
            man_rate = jnp.where(repair_on, man_rate, 0.0)
            rate_parts = [fail_rand, fail_sys, auto_rate, man_rate]
        if n_racks:
            # shared-shock lanes, one per level of fault domains (racks,
            # then pods): a level's n equal-rate domains race as one
            # exponential clock of n times the per-domain rate, live in
            # every non-DONE phase (a rack PDU does not care whether the
            # job is computing) — only the trailing * active masks them
            levels = [n_racks * rack_rate] + (
                [n_pods * pod_rate] if n_pods else [])
            rate_parts.append(jnp.stack(
                [jnp.broadcast_to(x, run.shape[:1]) for x in levels], -1))
            kx = K_EXP + len(levels)
    rates = jnp.concatenate(rate_parts, axis=-1) * active[:, None]

    # residual column order matters for exact ties (argmin takes the
    # first): the repair-slot residual comes FIRST so a repair completing
    # exactly at job completion resolves repair-first — the event
    # engine's heap semantics (the repair timeout was scheduled before
    # the final phase's completion timeout, so it pops first at equal
    # timestamps).  The job then completes in the next step at dt=0.
    resid_cols = []
    coff = 0
    if scen is not None and Lc:
        # campaign schedule residual: time to the next scheduled entry.
        # Placed before every other residual so a scripted kill at the
        # exact instant of a timer/completion resolves campaign-first —
        # the event engine's ShockInjector breaks the same tie the same
        # way.  Entries fire one per step (same-time entries burn
        # successive dt=0 steps in schedule order).
        brows0 = jnp.arange(run.shape[0])
        ci = jnp.clip(s["camp_idx"], 0, Lc - 1)
        ct = camp_t[ci] if camp_t.ndim == 1 else camp_t[brows0, ci]
        camp_pending = active & (s["camp_idx"] < Lc)
        resid_cols.append(jnp.where(
            camp_pending, jnp.maximum(ct - s["t"], 0.0), jnp.inf))
        coff = 1
    roff = 0
    if rkind != "exponential":
        # the repair-slot lane's work is scoped here, where its
        # completions are decoded, and in its own section below
        with jax.named_scope(tracing.REPAIR_LANE):
            rep_rem = s["repair_rem"]
            resid_cols.append(jnp.where(
                active, rep_rem.min(-1).astype(jnp.float32), jnp.inf))
        roff = 1
    resid_cols += [
        jnp.where(computing, s["work_left"], jnp.inf),
        jnp.where(in_overhead, s["timer"], jnp.inf),
    ]
    if haz_resid is not None:
        resid_cols.append(haz_resid)
    # checkpoint-write residual, appended LAST so no existing event index
    # shifts and an exact tie with completion resolves completion-first
    # (a finished job does not pay a final write on either engine).  At
    # checkpoint_interval == 0 the column is identically +inf — the race
    # never picks it and trajectories match the interval-free program
    # bit for bit.
    resid_cols.append(jnp.where(
        computing & (ckpt > 0),
        jnp.maximum(ckpt - s["ckpt_work"], 0.0), jnp.inf))
    residuals = jnp.stack(resid_cols, axis=-1)

    with jax.named_scope(tracing.RACE):
        dt, ev = ops.event_race(rates, residuals, u_time, u_pick,
                                impl=impl)
    dt = jnp.where(active & jnp.isfinite(dt), dt, 0.0)

    cls = (ev % 4).astype(jnp.int32)
    is_fail = active & (ev < 8)
    is_sys = active & (ev >= 4) & (ev < 8)
    if kind == "weibull":
        # the failure arrives on the hazard residual (kx + coff + roff
        # + 2); pick the failing channel from the hazard shares.  u_pick
        # is only consumed by the race when an *exponential* channel
        # wins, so it is fresh (and independent of dt) here.
        total_w = jnp.maximum(haz_weights.sum(-1), 1e-30)
        cdf8 = jnp.cumsum(haz_weights, axis=-1) / total_w[:, None]
        pick8 = jnp.minimum(
            jnp.sum((u_pick[:, None] >= cdf8).astype(jnp.int32), -1), 7)
        haz_fail = active & (ev == kx + coff + roff + 2)
        is_fail = haz_fail
        is_sys = haz_fail & (pick8 >= 4)
        cls = jnp.where(haz_fail, pick8 % 4, cls).astype(jnp.int32)
    elif kind == "bathtub":
        # accept/reject: a rejected candidate (and the window-expiry
        # event ev == kx + coff + roff + 2) is a phantom — time and work
        # advance, no state transition fires.
        g_at = hazards.FAILURE_SAMPLERS["bathtub"].hazard(
            age32 + dt, (hz[0], hz[1], hz[2], hz[3]))
        accept = u_haz * g_bar < g_at
        is_fail = is_fail & accept
        is_sys = is_sys & accept
    elif kind == "lognormal":
        # accept a candidate with prob h_family(age + dt) / h_bar_family
        ln = hazards.FAILURE_SAMPLERS["lognormal"]
        h_r = ln.hazard(age32 + dt, (hz[0], hz[2]))
        h_s = ln.hazard(age32 + dt, (hz[1], hz[2]))
        cand_sys = (ev >= 4) & (ev < 8)
        h_at = jnp.where(cand_sys, h_s, h_r)
        h_bar = jnp.where(cand_sys, hbar_s, hbar_r)
        accept = u_haz * h_bar < h_at
        is_fail = is_fail & accept
        is_sys = is_sys & accept
    elif kind == "empirical":
        # inside the window the hazard equals the majorant, so this
        # accepts (u < 1 always); it only bites when fp rounding lands
        # age + dt across a segment edge, where comparing against the
        # *new* segment's rate keeps the thinned process exact
        pe = hazards.FAILURE_SAMPLERS["empirical"]
        h_r = pe.hazard(age32 + dt, (e_re, e_rr))
        h_s = pe.hazard(age32 + dt, (e_se, e_sr))
        cand_sys = (ev >= 4) & (ev < 8)
        h_at = jnp.where(cand_sys, h_s, h_r)
        h_bar = jnp.where(cand_sys, hbar_s, hbar_r)
        accept = u_haz * h_bar <= h_at
        is_fail = is_fail & accept
        is_sys = is_sys & accept
    if rkind == "exponential":
        is_auto = active & (ev >= 8) & (ev < 12)
        is_man = active & (ev >= 12) & (ev < 16)
    else:
        # a slot repair completed: the winning slot's stage and class
        # drive the same downstream completion logic the exponential
        # channels feed (channels 8..16 are rateless here)
        with jax.named_scope(tracing.REPAIR_LANE):
            rows = jnp.arange(rep_rem.shape[0])
            won_slot = jnp.argmin(rep_rem, axis=-1)
            is_rep = active & (ev == kx + coff)
            done_stage = s["repair_stage"][rows, won_slot]
            cls = jnp.where(is_rep, s["repair_cls"][rows, won_slot],
                            cls).astype(jnp.int32)
            is_auto = is_rep & (done_stage == 0)
            is_man = is_rep & (done_stage == 1)
    is_complete = active & (ev == kx + coff + roff)
    is_timer = active & (ev == kx + coff + roff + 1)
    # checkpoint-write event: the last residual column (after the
    # hazard-window column when the family has one)
    ckpt_ev = kx + coff + roff + 2 + (1 if haz_resid is not None else 0)
    is_ckpt = active & (ev == ckpt_ev)

    if scen is not None:
        with jax.named_scope(tracing.SHOCK):
            # ---- correlated shock / campaign event sizing -------------------
            # Shock events arrive on the level lanes [K_EXP, kx); campaign
            # entries on the first residual (ev == kx).  A shock or scripted
            # kill is mutually exclusive with every other event this step,
            # so the idle failure-path uniforms (u_diag/u_wrong/u_cls/u_esc/
            # u_succ) are free to stochastically round the per-pool kill
            # counts without widening the per-step stream — which is what
            # keeps the rate->0 / empty-campaign programs bit-identical to
            # the scenario-free ones.
            brows = jnp.arange(run.shape[0])
            false_b = jnp.zeros_like(active)
            if n_racks:
                is_shock = active & (ev >= K_EXP) & (ev < kx)
                shock_dom = _struck_domain(rates, u_pick, ev, n_racks, n_pods)
            else:
                is_shock = false_b
                shock_dom = jnp.zeros_like(ev)
            if Lc:
                is_camp = camp_pending & (ev == kx)
                code_arr = jnp.asarray(camp_codes, jnp.int32)
                cur_code = code_arr[ci]
                is_kill = is_camp & (cur_code == faultdomains.KILL)
                is_m_on = is_camp & (cur_code == faultdomains.MAINT_START)
                is_m_off = is_camp & (cur_code == faultdomains.MAINT_END)
                kdom = (camp_dom[ci] if camp_dom.ndim == 1
                        else camp_dom[brows, ci]).astype(jnp.int32)
                kfrac = (camp_frac[ci] if camp_frac.ndim == 1
                         else camp_frac[brows, ci])
            else:
                is_camp = is_kill = is_m_on = is_m_off = false_b
                kdom = jnp.zeros_like(ev)
                kfrac = jnp.zeros_like(u_time)
            struck = is_shock | is_kill
            dom = jnp.where(is_shock, shock_dom, kdom)
            if n_racks:
                # the struck domain's share of the fleet, from its index
                dfrac = faultdomains.domain_sizes(
                    shock_dom, fleet.astype(jnp.int32), n_racks,
                    rpp.astype(jnp.int32), xp=jnp).astype(jnp.float32) / fleet
            else:
                dfrac = jnp.zeros_like(u_time)
            frac = jnp.where(is_kill, kfrac, dfrac)

            def _syscomp(cnt, tgt, uu):
                # systematic (stratified) rounding of a fractional per-class
                # target composition ``tgt`` (B, 4): returns integer
                # per-class counts n_c in {floor(tgt_c), ceil(tgt_c)} that
                # sum to the stochastic rounding of tgt.sum() — one uniform
                # drives both the total and its split.  With integer
                # occupancies and tgt_c <= cnt_c, n_c <= cnt_c always, so
                # compartments keep the whole-server invariant the repair
                # race's one-hot removals rely on.
                C = jnp.cumsum(tgt, axis=-1)
                Cm = jnp.concatenate([jnp.zeros_like(C[:, :1]), C[:, :-1]],
                                     axis=-1)
                up = jnp.maximum(jnp.ceil(C - uu[:, None]), 0.0)
                lo = jnp.maximum(jnp.ceil(Cm - uu[:, None]), 0.0)
                return up - lo

            def _sround(x, uu):
                fl = jnp.floor(x)
                return fl + (uu < x - fl).astype(jnp.float32)

            fr = frac[:, None]
            rm_run = _syscomp(run, run * fr, u_diag) * struck[:, None]
            rm_sb = _syscomp(s["sb"], s["sb"] * fr, u_wrong) * struck[:, None]
            rm_fw = _syscomp(s["fw"], s["fw"] * fr, u_cls) * struck[:, None]
            rm_fs = _syscomp(s["fs"], s["fs"] * fr, u_esc) * struck[:, None]
            k_run = rm_run.sum(-1)
            k_sb = rm_sb.sum(-1)
            k_fw = rm_fw.sum(-1)
            k_fs = rm_fs.sum(-1)
            # in-shop members re-break: exact-in-law a no-op under the
            # exponential stages this path guarantees — counted, not moved
            shop_tot0 = jnp.maximum(s["auto"].sum(-1) + s["man"].sum(-1), 0.0)
            k_shop = jnp.where(struck,
                               _sround(shop_tot0 * frac, u_succ), 0.0)
            # bulk replacement through the same standby -> working -> spare
            # waterfall a single failure uses, sized against the post-kill
            # pool occupancies (all integers, so the min-chain is exact)
            sb_rem = jnp.maximum(s["sb"].sum(-1) - k_sb, 0.0)
            fw_rem = jnp.maximum(s["fw"].sum(-1) - k_fw, 0.0)
            fs_rem = jnp.maximum(s["fs"].sum(-1) - k_fs, 0.0)
            t_sb = jnp.minimum(k_run, sb_rem)
            t_fw = jnp.minimum(k_run - t_sb, fw_rem)
            t_fs = jnp.minimum(k_run - t_sb - t_fw, fs_rem)
            shortfall = jnp.maximum(k_run - t_sb - t_fw - t_fs, 0.0)

            def _take(cnt, t, tot, uu):
                ratio = (t / jnp.maximum(tot, 1.0))[:, None]
                return _syscomp(cnt, cnt * ratio, uu)

            # the take compositions reuse u_pool (idle on shock steps) with
            # golden-ratio decorrelation shifts — correlated rounding across
            # pools is harmless (totals are exact; only the class split of a
            # single bulk event is approximated)
            PHI = 0.6180339887498949
            mv_sb = _take(s["sb"] - rm_sb, t_sb, sb_rem, u_pool)
            mv_fw = _take(s["fw"] - rm_fw, t_fw, fw_rem,
                          jnp.mod(u_pool + PHI, 1.0))
            mv_fs = _take(s["fs"] - rm_fs, t_fs, fs_rem,
                          jnp.mod(u_pool + 2.0 * PHI, 1.0))
            sh_affects = struck & (k_run > 0)
            # full replacements while already stalled must not clobber the
            # STALL — the original deficit is still outstanding
            sh_resolves = sh_affects & (shortfall <= 1e-6) & ~stalled
            sh_stalls = sh_affects & ~sh_resolves
            # one concurrent group restart: host selection / preemption
            # waits overlap across the block, so the overhead is charged
            # once per event, not per server
            shock_timer = (recovery
                           + jnp.where(t_fw + t_fs > 1e-6, host_sel, 0.0)
                           + jnp.where(t_fs > 1e-6, waiting + preempt_cost,
                                       0.0))

    ns = dict(s)
    ns["t"] = s["t"] + dt

    # ---- progress accounting -------------------------------------------
    # work accrues during every COMPUTE interval regardless of which event
    # ends it (failures, repair completions, job completion); failures —
    # and bulk shocks that gut the running block — roll back to the last
    # durable checkpoint.  The rollback charge is the dedicated
    # ``ckpt_work`` lane (work since the last write), so ``banked`` can go
    # negative on a failing step: it restores the already-banked portion
    # of the doomed interval, keeping the running sums algebraically
    # exact (sum(banked) = progress_total - lost_total) with no mod
    # arithmetic.  checkpoint_interval == 0 keeps the historical model:
    # nothing is ever lost.
    progress = jnp.where(computing, dt, 0.0)
    rollback = is_fail
    if scen is not None:
        rollback = rollback | (sh_affects & (computing | in_ckpt_flag))
    new_ckpt_work = s["ckpt_work"] + progress
    lost = jnp.where(rollback & (ckpt > 0), new_ckpt_work, 0.0)
    banked = progress - lost
    ns["work_left"] = s["work_left"] - banked
    ns["useful_work"] = s["useful_work"] + banked
    ns["lost_work"] = s["lost_work"] + lost
    # reset at every rollback, write start (durable from write start),
    # and completion; a paid write freezes the lane at 0 until compute
    # resumes (progress == 0 through OVERHEAD)
    ns["ckpt_work"] = jnp.where(rollback | is_ckpt | is_complete,
                                0.0, new_ckpt_work)

    # ---- completion / timer ----------------------------------------------
    # deterministic timers advance with the clock even when a concurrent
    # (repair) event ends the step first
    timer_dec = jnp.where(in_overhead, s["timer"] - dt, s["timer"])
    ns["phase"] = jnp.where(is_complete, DONE, s["phase"])
    ns["phase"] = jnp.where(is_timer, COMPUTE, ns["phase"])
    ns["timer"] = jnp.where(is_timer, jnp.inf, timer_dec)
    ns["total_time"] = jnp.where(is_complete, ns["t"], s["total_time"])

    # ---- checkpoint writes ----------------------------------------------
    # a paid write runs as an OVERHEAD interval flagged in_ckpt (its
    # expiry must NOT reset the hazard age: the failure clock is frozen
    # during the write, not restarted); a free write (checkpoint_cost ==
    # 0) banks the checkpoint without leaving COMPUTE.  Overhead wall
    # time accrues as it elapses, so a shock interrupting a write
    # charges only the partial write actually performed.
    paid_ckpt = is_ckpt & (ckpt_cost > 0)
    ns["phase"] = jnp.where(paid_ckpt, OVERHEAD, ns["phase"])
    ns["timer"] = jnp.where(paid_ckpt, ckpt_cost, ns["timer"])
    ns["in_ckpt"] = jnp.where(is_timer, 0.0,
                              jnp.where(paid_ckpt, 1.0, s["in_ckpt"]))
    ns["checkpoint_overhead"] = s["checkpoint_overhead"] \
        + jnp.where(in_ckpt_flag, dt, 0.0)

    # ---- exact run durations -------------------------------------------
    # a "run" is one useful-compute interval between restarts (start or
    # post-failure restart -> next failure or job completion), matching
    # the event engine's RunResult.run_durations (gross of checkpoint
    # rollback).  Repair completions during COMPUTE do not end a run.
    # Records land in a fixed ring buffer: slot n_runs % max_runs, so
    # overflow overwrites the oldest record; the overwrite count surfaces
    # downstream as the run_duration_truncated stat, and per-replica
    # means stay exact via sum(records) = useful + lost - cur_run.
    with jax.named_scope(tracing.RING):
        record = is_fail | is_complete
        if scen is not None:
            # a shock gutting the running set ends the in-flight compute
            # interval exactly like a failure would — including when it
            # lands mid-checkpoint-write (the compute interval is still the
            # one the interrupted write belongs to)
            record = record | (sh_affects & (computing | in_ckpt_flag))
        run_val = s["cur_run"] + progress
        max_runs = s["run_durations"].shape[1]
        if max_runs:    # static shape: max_runs=0 compiles the buffer out
            hit = row_hit(jnp.mod(s["n_runs"], max_runs), max_runs)
            ns["run_durations"] = jnp.where(hit & record[:, None],
                                            run_val[:, None],
                                            s["run_durations"])
        ns["n_runs"] = s["n_runs"] + record.astype(jnp.int32)
        ns["cur_run"] = jnp.where(record, 0.0, run_val)

    # ---- phase age (hazard clock) ---------------------------------------
    # advances only through COMPUTE time (phantoms included) and resets
    # when the recovery timer restarts the job — the event engine's
    # "failure clocks restart when the job restarts" semantics.  After a
    # failure the phase is OVERHEAD/STALL, so the frozen age is never
    # read before the reset.  A checkpoint-WRITE expiry resumes compute
    # with the age it froze at — the write suspends the failure clock,
    # it does not restart the fleet.
    ns["age"] = jnp.where(is_timer & ~in_ckpt_flag, 0.0, age + progress)

    # ---- failure handling ---------------------------------------------------
    f = is_fail.astype(jnp.float32)
    ns["n_failures"] = s["n_failures"] + f
    ns["n_systematic_failures"] = s["n_systematic_failures"] \
        + is_sys.astype(jnp.float32)
    ns["n_random_failures"] = s["n_random_failures"] \
        + (is_fail & ~is_sys).astype(jnp.float32)

    diagnosed = is_fail & (u_diag < dp)
    wrong = diagnosed & (u_wrong < du)
    ns["n_undiagnosed"] = s["n_undiagnosed"] \
        + (is_fail & ~diagnosed).astype(jnp.float32)
    ns["n_misdiagnosed"] = s["n_misdiagnosed"] + wrong.astype(jnp.float32)

    # one stacked categorical draw for all four pools; rep1h (the one-hot
    # of the raced class) doubles as the right-diagnosis removal mask
    picks = _pick_classes(
        jnp.stack([run, s["sb"], s["fw"], s["fs"]], axis=1),
        jnp.stack([u_cls, u_cls, u_pool, u_pool], axis=1))     # (R, 4)
    pick1h = jax.nn.one_hot(picks, 4, dtype=jnp.float32)       # (R, 4, 4)
    rep1h = _onehot(cls)
    rm1h = jnp.where(wrong[:, None], pick1h[:, 0], rep1h) \
        * diagnosed[:, None]
    ns["run"] = ns["run"] - rm1h
    ns["auto"] = ns["auto"] + rm1h

    # replacement waterfall (only when a server was removed)
    sb_tot = s["sb"].sum(-1)
    fw_tot = s["fw"].sum(-1)
    fs_tot = s["fs"].sum(-1)
    use_sb = diagnosed & (sb_tot > 0)
    use_fw = diagnosed & ~use_sb & (fw_tot > 0)
    use_fs = diagnosed & ~use_sb & ~use_fw & (fs_tot > 0)
    goes_stall = diagnosed & ~use_sb & ~use_fw & ~use_fs

    take = (pick1h[:, 1] * use_sb[:, None]
            + pick1h[:, 2] * use_fw[:, None]
            + pick1h[:, 3] * use_fs[:, None])
    ns["sb"] = ns["sb"] - pick1h[:, 1] * use_sb[:, None]
    ns["fw"] = ns["fw"] - pick1h[:, 2] * use_fw[:, None]
    ns["fs"] = ns["fs"] - pick1h[:, 3] * use_fs[:, None]
    ns["run"] = ns["run"] + take
    ns["n_standby_swaps"] = s["n_standby_swaps"] + use_sb.astype(jnp.float32)
    ns["n_host_selections"] = s["n_host_selections"] \
        + (use_fw | use_fs).astype(jnp.float32)
    ns["n_preemptions"] = s["n_preemptions"] + use_fs.astype(jnp.float32)

    fail_timer = (recovery
                  + jnp.where(use_fw | use_fs, host_sel, 0.0)
                  + jnp.where(use_fs, waiting + preempt_cost, 0.0))
    resolves = is_fail & ~goes_stall
    ns["timer"] = jnp.where(resolves, fail_timer, ns["timer"])
    ns["phase"] = jnp.where(resolves, OVERHEAD, ns["phase"])
    ns["phase"] = jnp.where(goes_stall, STALL, ns["phase"])
    ns["stall_start"] = jnp.where(goes_stall, ns["t"], s["stall_start"])
    ns["recovery_overhead"] = s["recovery_overhead"] \
        + jnp.where(resolves, recovery, 0.0)

    # ---- repair completions ----------------------------------------------
    ns["auto"] = ns["auto"] - rep1h * is_auto[:, None]
    ns["n_auto_repairs"] = s["n_auto_repairs"] + is_auto.astype(jnp.float32)
    escalate = is_auto & (u_esc >= p_auto)
    ns["man"] = ns["man"] + rep1h * escalate[:, None]
    ns["man"] = ns["man"] - rep1h * is_man[:, None]
    ns["n_manual_repairs"] = s["n_manual_repairs"] + is_man.astype(jnp.float32)

    finishes = (is_auto & ~escalate) | is_man
    fail_prob = jnp.where(is_man, man_fail, auto_fail)
    healed = finishes & (u_succ >= fail_prob)
    ns["n_failed_repairs"] = s["n_failed_repairs"] \
        + (finishes & ~healed).astype(jnp.float32)
    out_cls = jnp.where(healed, cls - (cls % 2), cls)  # bad -> good
    out1h = _onehot(out_cls)

    # returning server: stalled job > standby refill > origin pool
    to_stalled = finishes & stalled
    to_sb = finishes & ~to_stalled & (ns["sb"].sum(-1) < warm_standbys)
    to_pool = finishes & ~to_stalled & ~to_sb
    spare_origin = out_cls >= 2
    ns["run"] = ns["run"] + out1h * to_stalled[:, None]
    ns["sb"] = ns["sb"] + out1h * to_sb[:, None]
    ns["fw"] = ns["fw"] + out1h * (to_pool & ~spare_origin)[:, None]
    ns["fs"] = ns["fs"] + out1h * (to_pool & spare_origin)[:, None]
    if scen is None:
        unstall = to_stalled
    else:
        # outstanding-replacement deficit: a bulk kill can leave the
        # stalled job short several servers; each returning repair
        # retires one unit and the job only restarts once the whole
        # block is restored (struck / goes_stall / finishes are
        # mutually exclusive per step, so the chain is race-free)
        with jax.named_scope(tracing.SHOCK):
            deficit = (s["deficit"]
                       + jnp.where(goes_stall, 1.0, 0.0)
                       + jnp.where(struck, shortfall, 0.0))
            deficit = jnp.where(to_stalled,
                                jnp.maximum(deficit - 1.0, 0.0), deficit)
            unstall = to_stalled & (deficit <= 1e-6)
            ns["deficit"] = deficit
    ns["phase"] = jnp.where(unstall, OVERHEAD, ns["phase"])
    ns["timer"] = jnp.where(unstall, recovery, ns["timer"])
    ns["stall_time"] = s["stall_time"] \
        + jnp.where(unstall, ns["t"] - s["stall_start"], 0.0)
    ns["recovery_overhead"] = ns["recovery_overhead"] \
        + jnp.where(unstall, recovery, 0.0)

    if scen is not None:
        with jax.named_scope(tracing.SHOCK):
            # ---- correlated shock / campaign execution ----------------------
            # the struck block leaves every compartment at once and enters
            # the automated-repair stage; replacements drawn above through
            # the standard waterfall join the run set in the same step.
            # In-shop casualties (k_shop) re-break in place: under the
            # exponential stages this path guarantees, a restarted repair is
            # distributed exactly like the remaining one (memorylessness),
            # so they are counted but not moved.
            w = struck[:, None]
            ns["run"] = jnp.where(
                w, ns["run"] - rm_run + mv_sb + mv_fw + mv_fs, ns["run"])
            ns["sb"] = jnp.where(w, ns["sb"] - rm_sb - mv_sb, ns["sb"])
            ns["fw"] = jnp.where(w, ns["fw"] - rm_fw - mv_fw, ns["fw"])
            ns["fs"] = jnp.where(w, ns["fs"] - rm_fs - mv_fs, ns["fs"])
            ns["auto"] = jnp.where(
                w, ns["auto"] + rm_run + rm_sb + rm_fw + rm_fs, ns["auto"])
            ns["n_domain_shocks"] = s["n_domain_shocks"] \
                + is_shock.astype(jnp.float32)
            ns["n_campaign_events"] = s["n_campaign_events"] \
                + is_camp.astype(jnp.float32)
            ns["n_shock_killed"] = s["n_shock_killed"] \
                + jnp.where(struck, k_run + k_sb + k_fw + k_fs + k_shop, 0.0)
            ns["n_standby_swaps"] = ns["n_standby_swaps"] \
                + jnp.where(struck, t_sb, 0.0)
            ns["n_host_selections"] = ns["n_host_selections"] \
                + jnp.where(struck, t_fw + t_fs, 0.0)
            ns["n_preemptions"] = ns["n_preemptions"] \
                + jnp.where(struck, t_fs, 0.0)
            if "shock_dom" in s:
                # the step's struck domain, or the sentinel D; the chunk
                # loop adds a chunk's records at once
                ns["shock_dom"] = jnp.where(is_shock, dom, D_dom)
            if Lc:
                ns["camp_idx"] = s["camp_idx"] + is_camp.astype(jnp.int32)
            if has_maint:
                ns["maint"] = jnp.where(
                    is_m_on, 1.0, jnp.where(is_m_off, 0.0, s["maint"]))
            ns["timer"] = jnp.where(sh_resolves, shock_timer, ns["timer"])
            ns["phase"] = jnp.where(sh_resolves, OVERHEAD, ns["phase"])
            ns["phase"] = jnp.where(sh_stalls, STALL, ns["phase"])
            # a shock aborts any in-flight checkpoint write: the ensuing
            # OVERHEAD is a recovery (age resets when it expires)
            ns["in_ckpt"] = jnp.where(sh_affects, 0.0, ns["in_ckpt"])
            ns["stall_start"] = jnp.where(sh_stalls & ~stalled, ns["t"],
                                          ns["stall_start"])
            ns["recovery_overhead"] = ns["recovery_overhead"] \
                + jnp.where(sh_resolves, recovery, 0.0)

    # ---- repair-slot lane (non-exponential repairs) ----------------------
    # repairs run on wall-clock time: every occupied slot counts down by
    # dt through COMPUTE, OVERHEAD, and STALL alike, never resetting with
    # the job.  A completion frees the winning slot (escalation re-arms
    # it with a fresh manual-stage draw); a diagnosed failure claims the
    # first free slot with an auto-stage draw.  Durations are sampled at
    # entry by exact inverse CDF — precisely when the event engine's
    # RepairShop samples them — through the shared HazardSampler
    # machinery.  Entry and completion are mutually exclusive in one
    # step (single event), so one duration lane (u_dur) serves both.
    with jax.named_scope(tracing.REPAIR_LANE):
        if rkind != "exponential":
            rsampler = hazards.REPAIR_SAMPLERS[rkind]
            adt = rep_rem.dtype
            rem = jnp.where(active[:, None],
                            rep_rem - dt.astype(adt)[:, None], rep_rem)
            # completion (won_slot) and entry (first free slot) are mutually
            # exclusive per step — a single event ended it — so one masked
            # write per slot array covers both
            free = jnp.isinf(rem)
            any_free = free.any(-1)
            fslot = jnp.argmax(free, axis=-1)
            entered = diagnosed & any_free
            rm_cls = jnp.where(wrong, picks[:, 0], cls).astype(jnp.int32)
            # entry and escalation are mutually exclusive, so one quantile
            # evaluation with the stage-selected scale column serves both
            # (a second ndtri/pow per step is pure waste in the hot scan)
            if rkind == "empirical":
                # stage-select whole (edges, rates) blocks, then one
                # segment-inversion quantile; broadcast shared rows to the
                # batch so jnp.where can mix stages per replica
                B = run.shape[0]

                def _brow(x):
                    return x if x.ndim == 2 else jnp.broadcast_to(
                        x, (B,) + x.shape)

                esc2 = escalate[:, None]
                q_dur = rsampler.quantile(
                    u_dur, jnp.where(esc2, _brow(r_me), _brow(r_ae)),
                    jnp.where(esc2, _brow(r_mr), _brow(r_ar))).astype(adt)
            else:
                q_dur = rsampler.quantile(
                    u_dur, jnp.where(escalate, rz[1], rz[0]),
                    rz[2]).astype(adt)
            hit = row_hit(jnp.where(is_rep, won_slot, fslot), rem.shape[1])
            esc_h, ent_h = hit & escalate[:, None], hit & entered[:, None]
            ns["repair_rem"] = jnp.where(
                hit & finishes[:, None], jnp.inf,
                jnp.where(esc_h | ent_h, q_dur[:, None], rem))
            ns["repair_stage"] = jnp.where(
                esc_h, 1, jnp.where(ent_h, 0, s["repair_stage"]))
            ns["repair_cls"] = jnp.where(ent_h, rm_cls[:, None],
                                         s["repair_cls"])
            # a full lane: the incoming server stays in the shop forever
            # (bookkeeping-consistent but wrong); surfaced as a metric and a
            # RuntimeWarning downstream — raise Params.repair_slots
            ns["n_repair_overflow"] = s["n_repair_overflow"] \
                + (diagnosed & ~any_free).astype(jnp.float32)

    # ---- streaming histograms -------------------------------------------
    # O(bins) distribution accumulators with no run-count bound (the ring
    # buffer above truncates; these do not).  Bin layout mirrors
    # histograms.Histogram: searchsorted(side="right") over log-spaced
    # edges with under/overflow slots.  A failure resolved through the
    # waterfall records its downtime (ETTR) immediately; a stalled
    # failure records when the repaired server restarts the job, so the
    # stall interval is included — matching the event engine's
    # failure-to-restart timing.
    with jax.named_scope(tracing.HIST):
        if "hist" in s or "hist_bins" in s:
            stall_wait = ns["t"] - s["stall_start"]
            ended = resolves | unstall
            downtime = jnp.where(resolves, fail_timer, stall_wait + recovery)
            acquire_wait = jnp.where(resolves, fail_timer - recovery,
                                     stall_wait)
            if scen is not None:
                # a shock resolved through the waterfall records its planned
                # downtime at the resolve instant, like a plain failure
                ended = ended | sh_resolves
                downtime = jnp.where(sh_resolves, shock_timer, downtime)
                acquire_wait = jnp.where(sh_resolves, shock_timer - recovery,
                                         acquire_wait)
            # one fused bin search across the selected channels (static
            # ``hist_channels``, HIST_CHANNELS order); unselected channels
            # are compiled out entirely
            channel_vals = {"run_duration": (run_val, record),
                            "recovery": (downtime, ended),
                            "waiting": (acquire_wait, ended),
                            # one record per finished job: the realized
                            # useful-work fraction of its wall clock (pair
                            # with a (0.01, 1.0) bin range)
                            "goodput": (ns["useful_work"]
                                        / jnp.maximum(ns["t"], 1e-9),
                                        is_complete)}
            vals = jnp.stack([channel_vals[ch][0] for ch in hist_channels])
            masks = jnp.stack([channel_vals[ch][1] for ch in hist_channels])
            bins = _hist_bins(s["hist_edges"], vals, masks)
            if "hist" in s:
                ns["hist"] = _hist_flush(s["hist"], bins[:, None])
            else:
                ns["hist_bins"] = bins
    return ns


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _params_vector(p: Params) -> jnp.ndarray:
    base = np.asarray([
        p.random_failure_rate, p.systematic_failure_rate, p.recovery_time,
        p.host_selection_time, p.waiting_time, p.auto_repair_time,
        p.manual_repair_time, p.auto_repair_failure_probability,
        p.manual_repair_failure_probability, p.automated_repair_probability,
        p.diagnosis_probability, p.diagnosis_uncertainty,
        p.checkpoint_interval, p.preemption_cost, float(p.warm_standbys),
        p.checkpoint_cost,
    ], np.float32)
    parts = [base, hazards.hazard_columns(p), hazards.repair_columns(p)]
    if faultdomains.scenario_key(p) is not None:
        # trailing scenario columns (a few per level, 3 per campaign
        # entry) — traced, so a shock-rate or campaign-time grid shares
        # one compiled program
        with tracing.span(tracing.SCENARIO):
            parts.append(faultdomains.scenario_columns(p).astype(np.float32))
    return jnp.asarray(np.concatenate(parts))


def default_max_steps(p: Params, safety: float = 2.0) -> int:
    """Expected events (failures x ~3 repair/replace hops) + head-room.

    Hazard-aware: the event rate comes from
    :func:`repro.core.hazards.effective_event_rate` (the age-zero-ish
    hazard governs short restart-reset phases, so bathtub infant
    mortality or Weibull wear-in can multiply the exponential estimate),
    and bathtub thinning additionally budgets its window-expiry phantom
    steps.
    """
    lam = hazards.effective_event_rate(p)
    horizon = p.job_length * (1.0 + lam * (p.recovery_time + 2.0))
    extra = 0.0
    if p.fault_domains is not None or p.campaign is not None:
        # shocks + campaign entries + their bulk repair traffic, and the
        # horizon stretch of maintenance windows / shock recoveries
        with tracing.span(tracing.SCENARIO):
            extra, extra_h = faultdomains.scenario_budget(p, horizon)
        horizon += extra_h
    steps = max(128, int((lam * horizon + extra) * 3.2 * safety))
    if p.checkpoint_interval > 0:
        # every checkpoint_interval minutes of compute burns one
        # write-event step (plus its expiry step when the write is paid)
        writes = p.job_length / max(p.checkpoint_interval, 1e-9)
        steps += int(writes * (2.0 if p.checkpoint_cost > 0 else 1.0)
                     * safety)
    return steps + int(hazards.phantom_steps(p) * safety)


#: steps simulated per early-exit check (one compiled scan per chunk);
#: small chunks exit closer to the true max event count — the while-loop
#: bookkeeping per chunk is noise next to 64 scan steps
DEFAULT_CHUNK_STEPS = 64


@dataclasses.dataclass(frozen=True)
class _SingleJobStep:
    """The single-job engine as the chunk loop's static ``step``: the
    static arguments of :func:`_step_u`, hashed by value, so two studies
    of one signature share a compiled program."""

    impl: Optional[str]
    kind: str
    rkind: str
    hist_channels: tuple
    scen: object
    n_seg: int = 0
    n_rseg: int = 0

    @property
    def n_uniforms(self) -> int:
        return _n_uniforms(self.kind, self.rkind)

    def __call__(self, s, u, pv):
        return _step_u(s, u, pv, self.impl, self.kind, self.rkind,
                       self.hist_channels, self.scen, self.n_seg,
                       self.n_rseg)

    def extract(self, state, rows) -> Dict[str, np.ndarray]:
        return _extract(state, rows, self.hist_channels)


def _single_job_step(p: Params, impl: Optional[str] = None):
    """The step of ``p`` (``impl`` defaults to its
    ``Params.event_race_impl``)."""
    return _SingleJobStep(
        p.event_race_impl if impl is None else impl,
        hazards.hazard_kind(p), hazards.repair_kind(p), _hist_channels([p]),
        faultdomains.scenario_key(p), hazards.hazard_segment_count(p),
        hazards.repair_segment_count(p))


def _chunk_loop(pv: jnp.ndarray, key: jax.Array, P: int, R: int,
                chunk: int, n_chunks, rem: int, early_exit: bool, step,
                init_state: Dict[str, jnp.ndarray]):
    """Chunked scan with early exit; batch axis is B = P * R (point-major).

    The one chunk loop of both compiled engines: ``step`` is the engine
    (:class:`_SingleJobStep`, or the multi-job engine's step), called as
    ``step(state, u, pv)`` with ``step.n_uniforms`` uniforms a row.  The
    shared compute core of :func:`_run_chunked` (single-device jit) and
    :func:`_run_chunked_sharded` (per-shard body under shard_map, where R
    is the shard-local replica count and ``key`` the shard's folded
    key).  Runs exactly ``n_chunks * chunk + rem`` steps (minus chunks
    skipped by early exit).  ``n_chunks`` is a *traced* scalar — the
    while-loop trip count — so any two budgets with the same chunk size
    and remainder share one compiled program (the bucketed sweep path
    rounds the budget so ``rem == 0`` always).  Uniforms are drawn per
    *replica column* at the power-of-two width ``next_pow2(R)`` and
    sliced to R, then tiled across the P points: every sweep point sees
    common random numbers (the batched analogue of the event engine's
    same-seed-per-replication policy), and a bucket-padded run draws the
    identical stream for its real replica columns.  A ``phase`` with a
    job axis (the multi-job state) keeps a row running while any of its
    jobs is.
    """
    # every op of the program sits under the chunk scope (loop body,
    # exit check, remainder chunk and the outputs' final fix-up)
    with jax.named_scope(tracing.CHUNK):
        R_draw = _next_pow2(R)

        # the histogram leaves the scan: each step records its bins
        # (``hist_bins``, starting all sentinel), the scan stacks the
        # records, and one add per chunk folds them into ``hist``, which
        # rides in the chunk loop's carry; the per-domain shock counts
        # do the same with each step's struck domain (``shock_dom``)
        state = dict(init_state)
        hist = state.pop("hist", None)
        has_hist = hist is not None
        if has_hist:
            cb = (hist.shape[1], hist.shape[0])
            state["hist_bins"] = _hist_bins(
                state["hist_edges"], jnp.zeros(cb), jnp.zeros(cb, bool))
        shocks = state.pop("domain_shocks", None)
        if shocks is not None:
            state["shock_dom"] = jnp.full(shocks.shape[:1], shocks.shape[1],
                                          jnp.int32)

        def scan_body(state, u):
            if P > 1:
                with jax.named_scope(tracing.CRN_TILE):
                    u = jnp.tile(u, (P, 1))
            state = step(state, u, pv)
            return state, (tuple(state["hist_bins"]) if has_hist else None,
                           state.get("shock_dom"))

        def run_chunk(flushes, hist, shocks, state, i, n_steps):
            # one batched threefry call per chunk (a per-step split + draw is
            # the dominant scan cost on CPU); the non-exponential hazard /
            # repair families draw extra uniform lanes per step
            with jax.named_scope(tracing.DRAW):
                us = jax.random.uniform(
                    jax.random.fold_in(key, i),
                    (n_steps, R_draw, step.n_uniforms),
                    dtype=jnp.float32, minval=1e-12, maxval=1.0)
                if R_draw != R:
                    us = us[:, :R]
            state, (words, recs) = jax.lax.scan(scan_body, state, us)
            if has_hist:
                with jax.named_scope(tracing.HIST):
                    hist = _hist_flush(hist, words)
                flushes += 1
            if shocks is not None:
                with jax.named_scope(tracing.SHOCK):
                    shocks = _shock_flush(shocks, recs)
            return flushes, hist, shocks, state

        def chunk_body(carry):
            i, active_rows, flushes, hist, shocks, state = carry
            # rows still running as the chunk starts (padding rows start
            # DONE and never count)
            active = state["phase"] != DONE
            if active.ndim > 1:
                active = jnp.any(active, axis=-1)
            active_rows += jnp.sum(active, dtype=jnp.int32)
            return (i + 1, active_rows,
                    *run_chunk(flushes, hist, shocks, state, i, chunk))

        def cond(carry):
            i, *_, state = carry
            not_done = i < n_chunks
            if early_exit:
                not_done &= jnp.any(state["phase"] != DONE)
            return not_done

        n_run, active_rows, flushes, hist, shocks, state = \
            jax.lax.while_loop(cond, chunk_body,
                               (jnp.int32(0), jnp.int32(0), jnp.int32(0),
                                hist, shocks, state))
        steps = n_run * chunk
        if rem:
            # partial final chunk so an explicit max_steps is honored exactly.
            # Finished replicas are inert, so under early_exit skipping the
            # remainder once everything is DONE is bit-identical and free.
            def do_rem(*carry):
                return run_chunk(*carry, n_chunks, rem)

            carry = (flushes, hist, shocks, state)
            if early_exit:
                rem_runs = jnp.any(state["phase"] != DONE)
                carry = jax.lax.cond(rem_runs, do_rem, lambda *c: c, *carry)
                steps += jnp.where(rem_runs, rem, 0)
            else:
                carry = do_rem(*carry)
                steps += rem
            flushes, hist, shocks, state = carry
        if has_hist:
            state.pop("hist_bins")
            state["hist"] = hist
        if shocks is not None:
            state.pop("shock_dom")
            state["domain_shocks"] = shocks
        state["completed"] = (state["phase"] == DONE).astype(jnp.float32)
        t = state["t"] if state["phase"].ndim == 1 else state["t"][:, None]
        state["total_time"] = jnp.where(state["phase"] == DONE,
                                        state["total_time"], t)
        # the counters (tracing.COUNTERS), never part of the simulated
        # outputs: full chunks the early-exit loop executed (the remainder
        # chunk not counted), the sum over those chunks of the rows active
        # as each started, every step run, remainder included, the
        # histogram adds (one per chunk run, remainder included; 0 without
        # a histogram), and the shocks and the servers they struck, summed
        # once over the final state's rows (0 without a scenario)
        state["chunks_run"] = n_run
        state["active_row_chunks"] = active_rows
        state["steps_run"] = steps
        state["hist_flushes"] = flushes
        for counter, metric in (("domain_shocks_total", "n_domain_shocks"),
                                ("shock_killed_total", "n_shock_killed")):
            state[counter] = (jnp.int32(0) if step.scen is None else jnp.sum(
                state[metric].astype(jnp.int32)))
    return state


@partial(jax.jit, static_argnames=("P", "R", "chunk", "rem", "early_exit",
                                   "step"))
def _run_chunked(pv: jnp.ndarray, key: jax.Array, P: int, R: int,
                 chunk: int, n_chunks, rem: int, early_exit: bool, step,
                 init_state: Dict[str, jnp.ndarray]):
    """Single-device jit entry over :func:`_chunk_loop` (see there)."""
    return _chunk_loop(pv, key, P, R, chunk, n_chunks, rem, early_exit,
                       step, init_state)


@partial(jax.jit, static_argnames=("mesh", "P", "R", "chunk", "rem",
                                   "early_exit", "step"))
def _run_chunked_sharded(pv: jnp.ndarray, keys: jax.Array, P: int, R: int,
                         chunk: int, n_chunks, rem: int, early_exit: bool,
                         step, init_state: Dict[str, jnp.ndarray], *, mesh):
    """Replica-sharded twin of :func:`_run_chunked` via ``shard_map``.

    Reshapes every batched state leaf ``(P*R, ...) -> (P, R, ...)``,
    shards the replica axis over the 1-D device mesh
    (:func:`repro.core.replicas.replica_mesh`), and runs
    :func:`_chunk_loop` independently per shard with that shard's folded
    key (``keys`` is the :func:`repro.core.replicas.shard_keys`
    stack, one row per device).  There are no collectives inside the
    body — shards early-exit independently — and the ``out_specs``
    concatenation IS the cross-device merge: every output lane
    (metric scalars, histogram accumulators, run-record ring buffers)
    is per-replica, so reassembling the replica axis recovers the exact
    flat ``(P*R, ...)`` layout.  Unbatched leaves (``hist_edges``) ride
    along replicated; the counters (:data:`tracing.COUNTERS`) come back
    as ``(n_shards,)`` arrays, one value per shard.

    With a 1-device mesh ``keys[0]`` is the unsplit base key and the
    body sees exactly the arguments :func:`_run_chunked` would, so the
    output is bit-identical to the unsharded engine (pinned by
    tests/test_replica_sharding.py).
    """
    n_shards = mesh.shape[replicas.REPLICA_AXIS]
    R_loc = R // n_shards
    unbatched = {k: init_state[k] for k in _UNBATCHED_STATE
                 if k in init_state}
    state = {k: v.reshape((P, R) + v.shape[1:])
             for k, v in init_state.items() if k not in unbatched}
    rspec = PartitionSpec(None, replicas.REPLICA_AXIS)
    # simulate_ctmc passes one shared (n_cols,) parameter vector
    # (replicated); the sweep path passes per-row (P*R, n_cols) columns
    # (sharded like the state)
    pv_batched = pv.ndim == 2
    pv2 = pv.reshape((P, R, pv.shape[-1])) if pv_batched else pv
    pv_spec = rspec if pv_batched else PartitionSpec()
    out_specs = {k: rspec for k in list(state) + ["completed"]}
    # the counters come back one per shard: shards exit independently
    out_specs.update({k: PartitionSpec(replicas.REPLICA_AXIS)
                      for k in tracing.COUNTERS})

    def body(keys_s, pv_s, n_chunks_s, unbatched_s, state_s):
        flat = {k: v.reshape((P * R_loc,) + v.shape[2:])
                for k, v in state_s.items()}
        flat.update(unbatched_s)
        pv_flat = (pv_s.reshape(P * R_loc, pv_s.shape[-1])
                   if pv_batched else pv_s)
        out = _chunk_loop(pv_flat, keys_s[0], P, R_loc, chunk, n_chunks_s,
                          rem, early_exit, step, flat)
        for k in unbatched_s:
            out.pop(k)
        counters = {k: out.pop(k).reshape(1) for k in tracing.COUNTERS}
        return {**{k: v.reshape((P, R_loc) + v.shape[1:])
                   for k, v in out.items()}, **counters}

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(replicas.REPLICA_AXIS), pv_spec,
                  PartitionSpec(),
                  {k: PartitionSpec() for k in unbatched},
                  replicas.replica_state_specs(state)),
        out_specs=out_specs, check_vma=False,
    )(keys, pv2, n_chunks, unbatched, state)
    out = {k: v if k in tracing.COUNTERS
           else v.reshape((P * R,) + v.shape[2:]) for k, v in out.items()}
    out.update(unbatched)
    return out


def compile_cache_size() -> int:
    """Compiled-program cache entries of the chunked-scan driver (both
    engines).

    One entry per distinct static signature = one XLA compilation; the
    compile-count tests diff this around a sweep to assert the one-compile
    invariant.  Reads jax's ``PjitFunction._cache_size``: a jax without
    it fails here, loudly, rather than letting a compile guard pass
    unchecked.
    """
    return _run_chunked._cache_size()


def shard_compile_cache_size() -> int:
    """Compiled-program cache entries of the *sharded* chunked driver.

    The mesh object is part of the static signature, so re-running at
    the same device count reuses one program.
    """
    return _run_chunked_sharded._cache_size()


def _resolve_shards(shards, pts) -> int:
    """Effective shard count: the explicit argument, else the (single)
    ``Params.engine_shards`` value of the batch — a mixed grid raises
    (sharding is batch-level state; silently de-sharding part of a grid
    is exactly the failure mode docs/scaling.md promises never happens).
    """
    if shards is not None:
        return shards
    vals = {p.engine_shards for p in pts}
    if len(vals) > 1:
        raise ValueError(
            f"all points of a batched CTMC sweep must agree on "
            f"Params.engine_shards (got {sorted(vals)}); the batch axis "
            f"shards as one unit — split the grid or pass shards= "
            f"explicitly")
    return vals.pop()


def _shard_mesh(n_shards: int, R: int):
    """Validated replica mesh for ``n_shards`` shards over R replicas.

    Raises — never silently de-shards — when the shard count does not
    divide the replica count or exceeds the visible devices.
    """
    if R % n_shards:
        raise ValueError(
            f"engine_shards={n_shards} does not divide the replica "
            f"count {R}: the batch axis shards by whole replica "
            f"columns.  Choose a divisor; bucketed sweeps round R up to "
            f"a power of two, so any power-of-two shard count <= R "
            f"divides it (docs/scaling.md)")
    return replicas.replica_mesh(n_shards)


def _unsupported_error(params: Params) -> ValueError:
    reasons = unsupported_reasons(params) \
        or ["unknown reason — please report"]
    return ValueError(
        "these Params are outside the CTMC envelope: "
        + "; ".join(reasons)
        + "; use core.simulation.simulate (or engine='auto') instead")


#: non-_METRICS outputs worth returning: completion flag + the exact
#: run-duration records (ring buffer, attempt count, in-flight interval)
#: + the per-domain shock counts of scenario runs (absent otherwise)
_EXTRA_OUTPUTS = ("completed", "run_durations", "n_runs", "cur_run",
                  "domain_shocks")


def _wait(run, args, kw):
    """Run a compiled chunk loop until its outputs are ready; returns
    the state and the counters fetched to the host, as span arguments
    (:func:`tracing.counter_args`)."""
    with tracing.span(tracing.WAIT):
        out = jax.block_until_ready(run(*args, **kw))
        counters = {k: np.asarray(out.pop(k)) for k in tracing.COUNTERS}
    return out, tracing.counter_args(counters)


def _extract(state, sl=slice(None), channels=()) -> Dict[str, np.ndarray]:
    out = {k: np.asarray(v[sl]) for k, v in state.items()
           if k in _METRICS + _EXTRA_OUTPUTS}
    if "hist" in state and channels:
        # the in-scan accumulator carries exactly the selected channels,
        # in HIST_CHANNELS order
        hist = np.asarray(state["hist"][sl], np.float64)
        for ci, ch in enumerate(channels):
            out[f"hist_{ch}"] = hist[:, ci]
        out["hist_edges"] = np.asarray(state["hist_edges"], np.float64)
    return out


def _hist_channels(pts) -> tuple:
    return _selected_channels(pts[0].histogram)


def _build_programs(groups, n_replicas: int, seed, max_steps, chunk_steps,
                    early_exit: bool, bucketed: bool, shards: int, budget,
                    params_vector, initial_state) -> list:
    """The compiled chunk-loop calls of a sweep, unrun — both engines'.

    ``groups`` lists ``(idxs, step, pts)``: the points ``pts`` (at
    indices ``idxs`` of the sweep) that run as one batch through the
    chunk loop's static ``step``.  The engine brings a point's step
    budget (``budget(point)``), its parameter vector
    (``params_vector(point)``) and a group's initial state
    (``initial_state(pts, R, step)``, point-major ``(P*R, ...)``).

    A derived budget (``max_steps`` None) is the largest of the group's,
    rounded up to whole chunks when ``bucketed``; ``bucketed`` also
    rounds P and R up to powers of two with inert rows (the parameter
    rows edge-padded, the state by :func:`_bucket_pad_state`).  A
    non-zero ``shards`` runs the group replica-sharded.

    One ``(idxs, R_run, run, args, kwargs)`` entry per group:
    ``run(*args, **kwargs)`` is the jitted chunk driver call, at
    ``R_run`` replica rows per point; ``args`` are ``(pv, key, P, R,
    chunk, n_chunks, rem, early_exit, step, init_state)``.
    """
    key = (seed if isinstance(seed, jax.Array)
           else jax.random.PRNGKey(seed))
    programs = []
    for idxs, step, pts in groups:
        P, R = len(pts), n_replicas
        steps = max_steps or max(budget(p) for p in pts)
        chunk = min(chunk_steps or DEFAULT_CHUNK_STEPS, steps)
        P_run, R_run = (_next_pow2(P), _next_pow2(R)) if bucketed else (P, R)
        if bucketed and max_steps is None:
            # derived default budgets round up to whole chunks (rem
            # statically 0 -> every such sweep shares one program); an
            # *explicit* max_steps is still honored exactly — its
            # remainder stays a static part of the signature, so pass a
            # chunk multiple (or omit max_steps) for maximal sharing
            steps = -(-steps // chunk) * chunk
        pv = jnp.stack([params_vector(p) for p in pts])        # (P, n_cols)
        if P_run != P:
            # padding rows are inert (phase DONE); replicating the last
            # real row keeps every hazard column benign (a zero
            # bathtub tau would evaluate g(t) to NaN — masked out, but
            # edge-padding avoids NaNs entering the race at all)
            pv = jnp.pad(pv, ((0, P_run - P), (0, 0)), mode="edge")
        pv_flat = jnp.repeat(pv, R_run, axis=0)       # (P_run*R_run, n_cols)
        init_state = initial_state(pts, R, step)
        if (P_run, R_run) != (P, R):
            init_state = _bucket_pad_state(init_state, P, R, P_run, R_run)
        run_args = (P_run, R_run, chunk, jnp.int32(steps // chunk),
                    steps % chunk, early_exit, step, init_state)
        if shards:
            programs.append((
                idxs, R_run, _run_chunked_sharded,
                (pv_flat, replicas.shard_keys(key, shards)) + run_args,
                {"mesh": _shard_mesh(shards, R_run)}))
        else:
            programs.append((idxs, R_run, _run_chunked,
                             (pv_flat, key) + run_args, {}))
    return programs


def _run_programs(programs, n_points: int, n_replicas: int) -> list:
    """Run what :func:`_build_programs` built, one program at a time;
    returns each point's outputs in point order, as its step extracts
    them from the point's real rows."""
    results: list = [None] * n_points
    for idxs, R_run, run, args, kw in programs:
        out, counters = _wait(run, args, kw)
        step = args[8]
        # args: (pv, key, P, R, chunk, ...)
        with tracing.span(tracing.TRANSFER, chunk=args[4],
                          real_rows=len(idxs) * n_replicas, **counters):
            for j, i in enumerate(idxs):
                rows = (slice(j * R_run, j * R_run + n_replicas)
                        if R_run == n_replicas
                        else np.arange(n_replicas) + j * R_run)
                results[i] = step.extract(out, rows)
    return results


def simulate_ctmc(params: Params, n_replicas: int = 1024, seed: int = 0,
                  max_steps: Optional[int] = None,
                  impl: Optional[str] = None,
                  chunk_steps: Optional[int] = None,
                  early_exit: bool = True,
                  max_runs: Optional[int] = None,
                  shards: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Vectorized replication study. Returns {metric: np.ndarray (R,)}.

    jit-compiled once per (pool-structure, R, step-budget); parameter
    values are traced inputs, so repeated calls over rates/times/
    probabilities reuse the compiled program.  The scan runs in
    ``chunk_steps``-sized pieces and stops at the first chunk boundary
    where every replica is DONE; ``early_exit=False`` forces the full
    ``max_steps`` budget (bit-identical results — finished replicas are
    inert — which tests/test_backend.py asserts).

    ``max_runs`` (default ``params.max_run_records``) sizes the exact
    per-run duration ring buffer returned as ``run_durations`` (R,
    max_runs) alongside ``n_runs`` and ``cur_run``.  ``max_runs=0``
    compiles the buffer out of the scan entirely for callers that only
    need scalar metrics: ``mean_run_duration`` stays exact via the
    interval-sum identity over ``n_runs``/``cur_run``, but pooled
    run-duration percentiles degrade to pooling per-replica means.

    ``shards`` (default ``params.engine_shards``; 0 = unsharded) splits
    the replica axis over that many local devices via shard_map —
    exact-in-law with per-shard folded keys, bit-identical to the
    unsharded run at ``shards=1``, loud errors (never a silent de-shard)
    on indivisible replica counts or missing devices.  ``impl`` (default
    ``params.event_race_impl``) selects the event-race kernel backend.
    See docs/scaling.md for both knobs.
    """
    with tracing.span(tracing.PREPARE, rows=n_replicas,
                      real_rows=n_replicas):
        if not supports(params):
            raise _unsupported_error(params)
        params.validate()
        shards = _resolve_shards(shards, [params])
        max_steps = max_steps or default_max_steps(params)
        chunk = min(chunk_steps or DEFAULT_CHUNK_STEPS, max_steps)
        init_state = _initial_state(params, n_replicas, max_runs)
        step = _single_job_step(params, impl)
        args = (1, n_replicas, chunk, jnp.int32(max_steps // chunk),
                max_steps % chunk, early_exit, step, init_state)
        pv, key = _params_vector(params), jax.random.PRNGKey(seed)
        if shards:
            run, args, kw = (_run_chunked_sharded,
                             (pv, replicas.shard_keys(key, shards)) + args,
                             {"mesh": _shard_mesh(shards, n_replicas)})
        else:
            run, args, kw = _run_chunked, (pv, key) + args, {}
    out, counters = _wait(run, args, kw)
    with tracing.span(tracing.TRANSFER, chunk=chunk, real_rows=n_replicas,
                      **counters):
        return _extract(out, channels=step.hist_channels)


def simulate_ctmc_sweep(params_list, n_replicas: int = 1024, seed=0,
                        max_steps: Optional[int] = None,
                        impl: Optional[str] = None,
                        chunk_steps: Optional[int] = None,
                        early_exit: bool = True,
                        bucketed: bool = True,
                        max_runs: Optional[int] = None,
                        shards: Optional[int] = None):
    """Batched sweep: one compiled program for the whole grid.

    ``params_list`` is a sequence of :class:`Params` (the sweep grid, any
    order).  The entire grid — even when points differ *structurally*
    (job_size, pool sizes, warm_standbys, systematic fraction,
    job_length) — is stacked into one (P, 16) parameter array plus
    per-point padded initial states, expanded to one row per replica,
    and the whole (P * R,) batch runs through the same chunked scan as
    :func:`simulate_ctmc` in a single XLA compilation — the
    ``event_race`` kernel sees a single flat batch axis, so Pallas block
    sizes stay aligned.  The step budget is the max over points;
    replicas of cheaper points finish early and sit inert, so the shared
    head-room costs only chunks the early-exit check cannot skip.
    Per-point results are bit-identical to running each point alone
    whenever both step budgets suffice (common random numbers are drawn
    per replica column either way).

    ``bucketed=True`` (the default) additionally buckets the *shape*
    signature: P and R round up to powers of two with inert phase-DONE
    padding rows, and the chunk count is traced, so repeated sweeps of
    any size inside one bucket reuse a single XLA program.  A *derived*
    default budget rounds up to a whole number of chunks (remainder
    statically 0); an explicit ``max_steps`` is honored exactly.  Real
    rows are bit-identical to ``bucketed=False`` for any explicit
    ``max_steps``, and under the default budget whenever every replica
    finishes (early exit skips the rounded-up head-room); padding rows
    never reach the caller.

    Uniforms are shared across points (the batched analogue of the event
    engine's same-seed-per-replication policy), giving common random
    numbers across the grid.

    The hazard family (exponential / weibull / bathtub — see
    :mod:`repro.core.hazards`) is a static compile switch, so a grid
    mixing families runs one batch per family; hazard *parameters*
    (rates, ``k``, taus) are traced and share programs freely.

    ``shards`` (default: the grid's shared ``Params.engine_shards``
    value; a mixed grid raises) splits the replica axis of every batch
    over that many local devices — see :func:`simulate_ctmc` and
    docs/scaling.md.  The shard count must divide the *run* replica
    count (after pow2 bucketing), checked loudly.  ``impl`` defaults to
    each point's ``Params.event_race_impl``; since the kernel backend is
    a static compile switch, a grid mixing backends splits into one
    batch per backend.

    ``seed`` is an int or a PRNG key array — a row of
    :func:`repro.core.replicas.shard_keys` reproduces one shard of
    a sharded sweep on its own.

    Returns a list of ``{metric: np.ndarray (R,)}`` dicts in input order.
    """
    params_list = list(params_list)
    with tracing.span(tracing.PREPARE) as prepare:
        programs = sweep_programs(
            params_list, n_replicas, seed, max_steps, impl, chunk_steps,
            early_exit, bucketed, max_runs, shards)
        prepare.set_metadata(
            rows=sum(args[0].shape[0] for _, _, _, args, _ in programs),
            real_rows=len(params_list) * n_replicas)
    return _run_programs(programs, len(params_list), n_replicas)


def sweep_programs(params_list, n_replicas: int = 1024, seed=0,
                   max_steps: Optional[int] = None,
                   impl: Optional[str] = None,
                   chunk_steps: Optional[int] = None,
                   early_exit: bool = True, bucketed: bool = True,
                   max_runs: Optional[int] = None,
                   shards: Optional[int] = None) -> list:
    """The compiled programs :func:`simulate_ctmc_sweep` runs, unrun.

    One ``(idxs, R_run, run, args, kwargs)`` entry per compile group
    (:func:`_build_programs`): ``run(*args, **kwargs)`` is the jitted
    chunk driver call for the points ``idxs`` of ``params_list``, at
    ``R_run`` replica rows per point.  ``run.lower(*args,
    **kwargs).compile()`` gives the program itself — its text, its
    memory analysis — without running it.
    """
    params_list = list(params_list)
    for p in params_list:
        if not supports(p):
            raise _unsupported_error(p)
        p.validate()
    if not params_list:
        return []
    shards = _resolve_shards(shards, params_list)
    if len({p.histogram for p in params_list}) > 1:
        # the batch shares one in-scan accumulator layout (bin edges +
        # channel set are part of the compiled state), so a mixed-spec
        # grid cannot be honored point by point — reject it instead of
        # silently applying the first point's spec to every point
        raise ValueError(
            "all points of a batched CTMC sweep must share the same "
            "Params.histogram spec (the in-scan accumulator layout is "
            "per-batch); split the grid or unify the spec")

    groups: Dict[tuple, list] = {}
    for i, p in enumerate(params_list):
        # the step's static arguments split the grid into one batch
        # each: the hazard and repair families (they change the step
        # program and the uniform-stream width), the scenario key (rack
        # and pod counts + campaign codes: it sizes the race, the
        # per-domain counts and the trailing parameter columns), the
        # empirical family's segment *counts* (they size the column
        # blocks) and the event-race kernel backend (an explicit impl=
        # argument overrides every point's Params knob); so does the age
        # dtype.  Within a batch, structure padding keeps the whole
        # sub-grid one compilation: hazard and repair *parameters*, shock
        # rates, campaign times and fractions and empirical edge
        # positions and rates stay traced, so a repair-rate, shock-rate
        # or fitted-hazard grid compiles exactly once.
        step = _single_job_step(p, impl)
        groups.setdefault((p.age_dtype, step), []).append(i)
    mr = _max_runs_for(params_list) if max_runs is None else max_runs

    def initial_state(pts, R, step):
        return _initial_state_batch(pts, R, mr, step.rkind,
                                    _repair_slots_for(pts, step.rkind),
                                    step.scen)

    return _build_programs(
        [(idxs, step, [params_list[i] for i in idxs])
         for (_, step), idxs in groups.items()],
        n_replicas, seed, max_steps, chunk_steps, early_exit, bucketed,
        shards, default_max_steps, _params_vector, initial_state)
