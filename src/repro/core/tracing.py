"""Profiler spans, device scopes and counters of the compiled CTMC engine.

Everything here rides on JAX's own profiler, so it costs next to nothing
until a trace is taken.  Run any CTMC study under ``jax.profiler.trace``
(or ``start_trace``/``stop_trace``) and the trace holds:

* host spans (``jax.profiler.TraceAnnotation``), one set per study:
  ``aires.study`` around the whole study, and under it ``aires.prepare``
  (validation, grouping, parameter and initial-state build),
  ``aires.wait`` (the compiled call until its outputs are ready, and the
  fetch of the counters), ``aires.transfer`` (device-to-host copy and
  per-point slicing) and one ``aires.aggregate`` per point (the host
  statistics).  Inside ``aires.prepare``, ``aires.scenario`` covers the
  host-side set-up of fault domains and campaigns: one span per point
  around its parameter columns (kill fractions among them) and one
  around its step budget; a study without a scenario has none.  Each span's keyword arguments
  come back as stats of its trace event; every child carries the
  ``study`` number of its root.
* device scopes (``jax.named_scope``): ``aires.chunk`` encloses the whole
  chunk loop, and under it ``aires.draw`` (the threefry uniforms),
  ``aires.crn_tile`` (common random numbers tiled over the points),
  ``aires.race`` (the event race), ``aires.repair_lane`` (the
  repair-slot countdown, entry, and the decode of a slot's completion),
  ``aires.ring`` (run-duration ring buffer), ``aires.hist`` (streaming
  histograms) and ``aires.shock`` (fault domains and campaigns: the
  struck domain's pick within its level, the bulk kill, the waterfall
  and deficit lanes, and the per-domain shock record and its add).  A
  scope names the ops it holds in their HLO metadata, which the
  profiler reports as each op's ``tf_op`` path.
* program counters (:data:`COUNTERS`), returned beside the state by the
  chunk loop and attached to ``aires.transfer``: full chunks run, the
  sum over those chunks of the rows still active when each started, the
  steps run, the histogram adds (one per chunk the loop ran, remainder
  included; ``steps_run / hist_flushes`` steps share an add), and the
  shocks that fired and the servers they and campaign kills struck
  (``domain_shocks_total``, ``shock_killed_total``: the sums over rows
  of ``n_domain_shocks`` and ``n_shock_killed``, taken once from the
  final state; 0 without a scenario).  They never enter the simulated
  outputs.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools

import jax

#: every span and scope name: the five host spans of every study, the
#: device scopes, then the scenario's host span and its device scope
NAMES = ("aires.study", "aires.prepare", "aires.wait", "aires.transfer",
         "aires.aggregate", "aires.chunk", "aires.draw", "aires.crn_tile",
         "aires.race", "aires.repair_lane", "aires.ring", "aires.hist",
         "aires.scenario", "aires.shock")
(STUDY, PREPARE, WAIT, TRANSFER, AGGREGATE, CHUNK, DRAW, CRN_TILE, RACE,
 REPAIR_LANE, RING, HIST, SCENARIO, SHOCK) = NAMES

#: scalars the chunk loop returns beside the state (one per shard on the
#: sharded path)
COUNTERS = ("chunks_run", "active_row_chunks", "steps_run",
            "hist_flushes", "domain_shocks_total", "shock_killed_total")
#: counters whose shards add up; the others' largest shard bounds the
#: program's time
SUMMED = ("active_row_chunks", "domain_shocks_total", "shock_killed_total")

_numbers = itertools.count()
_current: contextvars.ContextVar = contextvars.ContextVar(
    "aires_study", default=None)


@contextlib.contextmanager
def study(points: int, replicas: int):
    """The root span of one study; spans opened inside carry its number."""
    number = next(_numbers)
    token = _current.set(number)
    try:
        with jax.profiler.TraceAnnotation(STUDY, points=points,
                                          replicas=replicas, study=number):
            yield
    finally:
        _current.reset(token)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span inside the current study (if any); more arguments can
    be attached before it closes with ``set_metadata``."""
    number = _current.get()
    if number is not None:
        args["study"] = number
    return jax.profiler.TraceAnnotation(name, **args)


def counter_args(counters) -> dict:
    """Span arguments from the fetched counters: one value per counter,
    the largest over shards (steps, chunks and histogram adds, which
    bound the program's time) or their sum (:data:`SUMMED`); a sharded
    run adds the per-shard values as one string, ``name:v0/v1/...`` per
    counter."""
    shards = {k: [int(x) for x in v.reshape(-1)] for k, v in counters.items()}
    args = {k: (sum(v) if k in SUMMED else max(v))
            for k, v in shards.items()}
    if len(shards["steps_run"]) > 1:
        args["per_shard"] = " ".join(
            f"{k}:" + "/".join(map(str, v)) for k, v in shards.items())
    return args
