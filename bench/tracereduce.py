"""Reduction of a JAX profiler trace to what the per-layer readers need.

``load`` reads the ``.xplane.pb`` the profiler wrote and keeps, for each
chip the cell uses, the device operations and the executions of XLA
modules, and from the host the benchmark's study span with the host
events around it.  All times are nanoseconds on the trace's one
timeline, and everything is clipped to the study span.
"""

from __future__ import annotations

import gzip
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

#: a Pallas kernel's device events carry the name given to pallas_call
RACE_KERNEL = "event_race"

Interval = Tuple[float, float]


def find_xplane(directory) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def union_ns(intervals: List[Interval]) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Stretches of [lo, hi) that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


@dataclass
class Chip:
    """One device's events inside the study span."""

    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)

    def busy_ns(self) -> float:
        return union_ns([(s, e) for _, s, e in self.ops])

    def program(self) -> Optional[Tuple[str, float, float]]:
        """The longest module execution: the study's sweep program."""
        if not self.modules:
            return None
        return max(self.modules, key=lambda m: m[2] - m[1])


@dataclass
class TraceView:
    span: Interval
    chips: List[Chip]
    host: List[Tuple[str, float, float]]

    @property
    def span_s(self) -> float:
        return (self.span[1] - self.span[0]) * 1e-9

    def busy_s(self) -> float:
        """Device busy seconds in the span, mean over the chips."""
        return float(np.mean([c.busy_ns() for c in self.chips])) * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        """Device ops that took most time (mean over chips) and the
        longest idle gaps of the first chip, each named by the innermost
        host event under its middle."""
        per_name: Dict[str, float] = defaultdict(float)
        for c in self.chips:
            for name, s, e in c.ops:
                per_name[name] += (e - s) * 1e-9 / len(self.chips)
        ops = sorted(per_name.items(), key=lambda kv: -kv[1])[:top]
        idle = gaps([(s, e) for _, s, e in self.chips[0].ops], *self.span)
        idle = sorted(idle, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, v] for n, v in ops],
                "idle_gaps": [[self.host_label((s + e) / 2), (e - s) * 1e-9]
                              for s, e in idle]}

    def host_label(self, t: float) -> str:
        under = [(e - s, n) for n, s, e in self.host if s <= t < e]
        return min(under)[1] if under else "no host event"


def leaves(events: List[Tuple[str, float, float]]
           ) -> List[Tuple[str, float, float]]:
    """The events that hold no other event of their line: a loop or a
    call that the trace shows around the operations it runs is left out,
    so that only the operations themselves count as busy time."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    parent = [False] * len(order)
    stack: List[int] = []
    for i, (_, s, e) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= order[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [ev for ev, p in zip(order, parent) if not p]


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def load(path, span_name: str, chips: int) -> TraceView:
    """Reduce one trace file (``.xplane.pb``, or gzipped) to a
    :class:`TraceView` of ``chips`` chips."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(str(path))
    host, devices = [], {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.end_ns)
                         for ev in line.events]
        elif plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            chip = devices.setdefault(idx, Chip())
            for line in plane.lines:
                # an op's name is its HLO instruction; keep the name alone
                evs = [(ev.name.split(" = ", 1)[0], ev.start_ns, ev.end_ns)
                       for ev in line.events]
                if line.name == "XLA Ops":
                    chip.ops += leaves(evs)
                elif line.name == "XLA Modules":
                    chip.modules += evs
    spans = [(s, e) for n, s, e in host if n == span_name]
    if len(spans) != 1:
        raise ValueError(f"expected one {span_name!r} span, found "
                         f"{len(spans)}")
    lo, hi = spans[0]
    used = [devices[i] for i in sorted(devices)[:chips]]
    if len(used) < chips:
        raise ValueError(f"the trace holds {len(used)} TPU planes, the "
                         f"cell uses {chips} chips")
    for c in used:
        c.ops = _clip(c.ops, lo, hi)
        c.modules = _clip(c.modules, lo, hi)
    host = _clip(host, lo, hi)
    return TraceView((lo, hi), used, host)
