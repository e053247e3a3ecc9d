"""Correlated failure domains and scripted fault-injection campaigns.

Every failure the base simulator produces is i.i.d. per server.  Real
clusters die by *domain*: a rack PDU trips and the whole rack goes dark,
a pod-level network event partitions dozens of hosts at once.  This
module adds both the stochastic and the scripted version of that story:

* :class:`FaultTopology` — assigns every server (workers and spares
  alike) to a rack, and racks to pods.  Each rack and each pod is a
  *fault domain* with its own exponential shock rate; a shock atomically
  fails every server in the domain — running, standby, free, spare, and
  in-repair alike.
* :class:`Campaign` / :class:`CampaignEvent` — a validated schedule of
  deterministic injections: ``kill domain d at t`` and ``maintenance
  window disabling the repair shop over [t0, t0+duration]``.  Both
  engines honor the schedule exactly; on the CTMC fast path the entries
  race as deterministic residuals the same way repair-slot completions
  do.
* :class:`ShockInjector` — the event-engine driver: merges the random
  per-domain shock processes and the campaign schedule into one ordered
  stream of injections for the coordinator to race against compute.

Semantics shared by both engines (see docs/scenarios.md):

* A shock/kill does **not** flip a server's health class — it models an
  environmental outage (power, network), not a latent hardware fault.
  Struck servers are sent through the normal repair pipeline and return
  with whatever class they had.
* Struck servers already in the repair shop are "re-broken": their
  current repair stage restarts.  Under exponential repairs this is
  exact-in-law a no-op (memorylessness) — the CTMC engine counts it
  without touching state; the event engine redraws the stage.
* Shock kills are not recorded as server failures (``n_failures`` and
  the retirement window see only organic failures); they are surfaced
  through ``n_domain_shocks`` / ``n_shock_killed`` /
  ``n_campaign_events`` and the per-domain ``domain_shocks`` counts.

Server→rack assignment is round-robin (``rack = sid % n_racks``), which
stripes both the worker and the spare pool across racks — the worst
case for correlated loss of a job plus its spares, and the natural
default when nothing is known about placement.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "FaultTopology", "CampaignEvent", "Campaign", "ShockInjector",
    "Injection", "scenario_key", "scenario_columns", "scenario_budget",
    "KILL", "MAINT_START", "MAINT_END",
]

#: campaign schedule entry codes (static on the CTMC fast path)
KILL, MAINT_START, MAINT_END = 0, 1, 2


# ---------------------------------------------------------------------------
# topology
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FaultTopology:
    """Rack → pod fault-domain hierarchy with per-level shock rates.

    Domains are indexed ``0..n_racks-1`` (racks) followed by
    ``n_racks..n_racks+n_pods-1`` (pods).  ``racks_per_pod == 0``
    disables the pod level entirely.

    >>> t = FaultTopology(n_racks=4, racks_per_pod=2,
    ...                   rack_shock_rate=1e-4)
    >>> t.n_pods, t.n_domains
    (2, 6)
    >>> [t.rack_of(s) for s in range(6)]
    [0, 1, 2, 3, 0, 1]
    >>> t.domain_members(4, total=8)     # pod 0 = racks {0, 1}
    [0, 1, 4, 5]
    """

    n_racks: int
    racks_per_pod: int = 0
    rack_shock_rate: float = 0.0
    pod_shock_rate: float = 0.0

    def validate(self, total_servers: int) -> None:
        if self.n_racks < 1:
            raise ValueError(f"n_racks must be >= 1, got {self.n_racks}")
        if self.racks_per_pod < 0:
            raise ValueError("racks_per_pod must be >= 0")
        if self.rack_shock_rate < 0 or self.pod_shock_rate < 0:
            raise ValueError("shock rates must be >= 0")
        if self.pod_shock_rate > 0 and self.racks_per_pod == 0:
            raise ValueError(
                "pod_shock_rate > 0 requires racks_per_pod >= 1")
        if self.n_racks > total_servers:
            raise ValueError(
                f"n_racks={self.n_racks} exceeds the fleet size "
                f"{total_servers}: every rack must hold a server")

    @property
    def n_pods(self) -> int:
        if not self.racks_per_pod:
            return 0
        return math.ceil(self.n_racks / self.racks_per_pod)

    @property
    def n_domains(self) -> int:
        return self.n_racks + self.n_pods

    def rack_of(self, sid: int) -> int:
        return sid % self.n_racks

    def pod_of_rack(self, rack: int) -> int:
        return rack // self.racks_per_pod

    def domain_members(self, domain: int, total: int) -> List[int]:
        """Server ids (workers + spares) belonging to ``domain``, in
        ascending order: rack ``r`` holds ``r, r + n_racks, ...``, and a
        pod the servers of its ``racks_per_pod`` racks in a row (fewer
        in the last pod when ``racks_per_pod`` does not divide
        ``n_racks``)."""
        if domain < self.n_racks:
            return list(range(domain, total, self.n_racks))
        lo = (domain - self.n_racks) * self.racks_per_pod
        hi = min(lo + self.racks_per_pod, self.n_racks)
        return [s for base in range(0, total, self.n_racks)
                for s in range(base + lo, min(base + hi, total))]

    def domain_size(self, domain: int, total: int) -> int:
        """``len(domain_members(domain, total))``, in closed form."""
        return int(domain_sizes(domain, total, self.n_racks,
                                self.racks_per_pod))

    def domain_rates(self) -> np.ndarray:
        """Per-domain shock rates, racks first then pods — shape (D,)."""
        return np.concatenate([
            np.full(self.n_racks, self.rack_shock_rate, np.float64),
            np.full(self.n_pods, self.pod_shock_rate, np.float64)])

    def domain_fractions(self, total: int) -> np.ndarray:
        """Fraction of the fleet in each domain — shape (D,).

        The CTMC engine carries compartment *counts*, not identities, so
        a shock removes ``fraction * count`` servers from every pool
        (stochastically rounded).  With round-robin assignment the
        striping is uniform, so the per-domain fraction is the exact
        expectation of the event engine's member count in every pool.
        """
        sizes = domain_sizes(np.arange(self.n_domains), total,
                             self.n_racks, self.racks_per_pod)
        return sizes.astype(np.float64) / max(total, 1)


def domain_sizes(domain, total, n_racks: int, racks_per_pod, xp=np):
    """Servers in each ``domain`` index of a fleet of ``total`` under
    round-robin striping (``rack = sid % n_racks``), in closed form.

    With ``q, rem = divmod(total, n_racks)`` rack ``r`` holds
    ``q + (r < rem)`` servers; pod ``p`` holds its ``nr`` racks from
    ``lo = p * racks_per_pod``, that is ``nr * q`` servers plus those of
    its racks below ``rem``.  ``xp`` is ``numpy`` on the host or
    ``jax.numpy`` inside the compiled step, where ``domain``, ``total``
    and ``racks_per_pod`` are integer arrays and ``n_racks`` is static.

    >>> t = FaultTopology(n_racks=5, racks_per_pod=2)
    >>> domain_sizes(np.arange(t.n_domains), 12, 5, 2).tolist()
    [3, 3, 2, 2, 2, 6, 4, 2]
    >>> [len(t.domain_members(d, 12)) for d in range(t.n_domains)]
    [3, 3, 2, 2, 2, 6, 4, 2]
    """
    q, rem = total // n_racks, total % n_racks
    rack = q + (domain < rem)
    lo = (domain - n_racks) * racks_per_pod
    nr = xp.minimum(racks_per_pod, n_racks - lo)
    pod = nr * q + xp.clip(rem - lo, 0, nr)
    return xp.where(domain < n_racks, rack, pod)


# ---------------------------------------------------------------------------
# campaigns
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CampaignEvent:
    """One scripted injection.

    ``kind="kill"``: fail every server in ``domain`` at ``time``.
    ``kind="maintenance"``: disable the repair shop over
    ``[time, time + duration]`` — in-flight repairs pause and resume
    with their remaining stage time.
    """

    time: float
    kind: str = "kill"
    domain: int = 0
    duration: float = 0.0

    def validate(self, topology: Optional[FaultTopology]) -> None:
        if self.kind not in ("kill", "maintenance"):
            raise ValueError(f"unknown campaign event kind {self.kind!r}")
        if self.time < 0:
            raise ValueError("campaign event time must be >= 0")
        if self.kind == "maintenance" and self.duration <= 0:
            raise ValueError("maintenance windows need duration > 0")
        if self.kind == "kill":
            if topology is None:
                raise ValueError(
                    "campaign kills require Params.fault_domains")
            if not 0 <= self.domain < topology.n_domains:
                raise ValueError(
                    f"kill domain {self.domain} out of range "
                    f"[0, {topology.n_domains})")


@dataclass(frozen=True)
class Campaign:
    """An ordered, validated schedule of :class:`CampaignEvent`.

    >>> c = Campaign(events=({"time": 10.0, "kind": "maintenance",
    ...                       "duration": 5.0},
    ...              CampaignEvent(time=2.0, kind="kill", domain=1)))
    >>> c.schedule()
    [(2.0, 0, 1), (10.0, 1, 0), (15.0, 2, 0)]
    """

    events: Tuple[CampaignEvent, ...] = field(default_factory=tuple)

    def __post_init__(self):
        norm = tuple(CampaignEvent(**e) if isinstance(e, dict) else e
                     for e in self.events)
        object.__setattr__(self, "events", norm)

    def validate(self, topology: Optional[FaultTopology]) -> None:
        for e in self.events:
            e.validate(topology)

    def schedule(self) -> List[Tuple[float, int, int]]:
        """Flatten to a time-sorted list of ``(time, code, domain)``.

        Maintenance windows become two entries (start/end).  The sort is
        stable, so simultaneous entries fire in declaration order on
        both engines.
        """
        flat: List[Tuple[float, int, int]] = []
        for e in self.events:
            if e.kind == "kill":
                flat.append((float(e.time), KILL, e.domain))
            else:
                flat.append((float(e.time), MAINT_START, 0))
                flat.append((float(e.time + e.duration), MAINT_END, 0))
        flat.sort(key=lambda x: x[0])
        return flat


# ---------------------------------------------------------------------------
# CTMC fast-path builders
# ---------------------------------------------------------------------------
# The scan treats the scenario as (static structure, traced numbers):
# the *shape* — the rack and pod counts and the tuple of schedule codes
# — is a static compile key, while every rate, the fleet size, and each
# campaign entry's time, fraction and target domain ride in trailing
# params-vector columns.  A shock-rate grid or a campaign-timing grid
# therefore shares one compiled program.  The race holds one lane per
# level (racks, pods), not one per domain: every domain of a level has
# the same rate, so the struck domain is uniform within its level.

def scenario_key(p) -> Optional[Tuple[int, int, Tuple[int, ...]]]:
    """Static compile key ``(n_racks, n_pods, codes)`` — or None when
    no scenario.  A campaign without a topology has ``n_racks == 0``."""
    if p.fault_domains is None and p.campaign is None:
        return None
    topo = p.fault_domains
    codes = tuple(code for _, code, _ in p.campaign.schedule()) \
        if p.campaign is not None else ()
    if topo is None:
        return (0, 0, codes)
    return (topo.n_racks, topo.n_pods, codes)


def scenario_columns(p) -> np.ndarray:
    """Traced trailing params-vector columns for the scenario.

    Layout: ``[fleet, racks_per_pod, rack_rate, pod_rate]`` when a
    topology is set, then ``[times (L), fracs (L), domains (L)]`` where
    L is the flattened schedule length.  Kill entries carry the struck
    domain's fleet fraction; maintenance entries carry zeros.
    """
    topo, camp = p.fault_domains, p.campaign
    total = p.working_pool_size + p.spare_pool_size
    cols: List[float] = []
    if topo is not None:
        cols += [total, topo.racks_per_pod, topo.rack_shock_rate,
                 topo.pod_shock_rate]
    times: List[float] = []
    efracs: List[float] = []
    edoms: List[float] = []
    if camp is not None:
        for t, code, dom in camp.schedule():
            times.append(t)
            efracs.append(topo.domain_size(dom, total) / max(total, 1)
                          if code == KILL else 0.0)
            edoms.append(float(dom))
    return np.asarray(cols + times + efracs + edoms, np.float64)


def scenario_budget(p, horizon: float) -> Tuple[float, float]:
    """``(extra_steps, extra_horizon)`` for the CTMC step budget.

    Each shock consumes one scan step plus the repair traffic of the
    block it kills (~4 steps per killed server: auto completion,
    escalation, manual completion, return/unstall).  The racks, and the
    pods, each partition the fleet, so a level's shocks kill ``total``
    servers per unit of its per-domain rate.  Maintenance windows
    stretch the horizon by their duration (repairs pause) and campaign
    entries each take a step of their own.
    """
    topo, camp = p.fault_domains, p.campaign
    total = p.working_pool_size + p.spare_pool_size
    extra_steps = 0.0
    extra_horizon = 0.0
    if topo is not None:
        lam = (topo.n_racks * topo.rack_shock_rate
               + topo.n_pods * topo.pod_shock_rate)
        if lam > 0:
            n_shocks = lam * horizon
            mean_kill = (topo.rack_shock_rate
                         + (topo.pod_shock_rate if topo.n_pods else 0.0)
                         ) * total / lam
            extra_steps += n_shocks * (2.0 + 4.0 * mean_kill)
            extra_horizon += n_shocks * (
                p.recovery_time + p.host_selection_time + p.waiting_time)
    if camp is not None:
        for _, code, dom in camp.schedule():
            extra_steps += 2.0
            if code == KILL and topo is not None:
                extra_steps += 4.0 * topo.domain_size(dom, total)
        extra_horizon += sum(e.duration for e in camp.events
                             if e.kind == "maintenance")
    return extra_steps, extra_horizon


# ---------------------------------------------------------------------------
# event-engine injector
# ---------------------------------------------------------------------------

@dataclass
class Injection:
    """One injection popped from the merged stream."""

    time: float
    kind: str                      # "shock" | "kill" | "maint_start" | "maint_end"
    domain: int
    members: Sequence[int]         # struck server ids ([] for maintenance)


class ShockInjector:
    """Merged random-shock + campaign stream for the event engine.

    Per-domain shock arrivals are drawn lazily (one exponential gap per
    pop) from the simulation RNG; the campaign schedule is a pointer
    walk.  ``peek()`` returns the next injection time (inf when
    exhausted), ``pop()`` consumes it.  Ties between a shock and a
    campaign entry resolve campaign-first, matching the CTMC race where
    deterministic residual ties break on the first (campaign) column.
    """

    def __init__(self, topology: Optional[FaultTopology],
                 campaign: Optional[Campaign], total: int, rng) -> None:
        self.topology = topology
        self._rng = rng
        self._total = total
        if topology is not None:
            self._rates = topology.domain_rates()
            self._next = np.array(
                [rng.exponential(1.0 / r) if r > 0 else math.inf
                 for r in self._rates])
        else:
            self._rates = np.zeros(0)
            self._next = np.zeros(0)
        self._schedule = campaign.schedule() if campaign is not None else []
        self._ptr = 0

    def _next_campaign_time(self) -> float:
        if self._ptr >= len(self._schedule):
            return math.inf
        return self._schedule[self._ptr][0]

    def peek(self) -> float:
        t = self._next_campaign_time()
        if len(self._next):
            t = min(t, float(self._next.min()))
        return t

    def pop(self) -> Injection:
        t_camp = self._next_campaign_time()
        t_shock = float(self._next.min()) if len(self._next) else math.inf
        if t_camp <= t_shock:            # campaign wins ties (see class doc)
            t, code, dom = self._schedule[self._ptr]
            self._ptr += 1
            if code == KILL:
                return Injection(t, "kill", dom,
                                 self.topology.domain_members(dom,
                                                              self._total))
            kind = "maint_start" if code == MAINT_START else "maint_end"
            return Injection(t, kind, 0, [])
        d = int(self._next.argmin())
        t = self._next[d]
        self._next[d] = t + self._rng.exponential(1.0 / self._rates[d])
        return Injection(float(t), "shock", d,
                         self.topology.domain_members(d, self._total))
