"""The CTMC engine's own instrumentation (``repro.core.tracing``): host
spans with their arguments, device name scopes, and the program counters
— and that none of it changes a simulated number.

CPU, tiny sizes.  Spans are read back from a real profiler trace; the
sharded counters run in a child process on four forced host devices.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import FaultTopology, JobSpec, Params
from repro.core import run_multijob_batch, run_replications
from repro.core import run_replications_batch, tracing
from repro.core import vectorized as vz
from repro.core import vectorized_multijob as vmj

BASE = Params(job_size=48, working_pool_size=56, spare_pool_size=8,
              warm_standbys=2, job_length=2000.0)
GRID = [BASE, BASE.replace(recovery_time=20.0),
        BASE.replace(working_pool_size=60)]
#: the spans every study records
HOST_SPANS = tracing.NAMES[:5]
#: the scopes every program family holds; a scenario adds aires.shock
SCOPES = tuple(n for n in tracing.NAMES[5:]
               if n not in (tracing.SCENARIO, tracing.SHOCK))
#: racks and pods shocking often enough to strike every replica
TOPO = FaultTopology(n_racks=16, racks_per_pod=4, rack_shock_rate=4e-4,
                     pod_shock_rate=2e-4)
SCEN_GRID = [p.replace(fault_domains=TOPO) for p in GRID]
#: two mixed-size jobs sharing one pool and a finite shop, on two pools
MJ_CLUSTER = Params(working_pool_size=110, spare_pool_size=16, job_size=16,
                    job_length=4000.0, random_failure_rate=0.001,
                    systematic_failure_rate=0.005, auto_repair_time=180.0,
                    manual_repair_time=480.0, repair_servers=6)
MJ_JOBS = (JobSpec(32, 4000.0, warm_standbys=2),
           JobSpec(16, 6000.0, warm_standbys=1))
MJ_GRID = [(MJ_CLUSTER, MJ_JOBS),
           (MJ_CLUSTER.replace(spare_pool_size=8), MJ_JOBS)]


def profiled(tmp_path, fn):
    """``fn()`` under the profiler: its result and the ``aires.*`` host
    spans of the trace as (name, start, end, args), in start order."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    [path] = list(Path(tmp_path).rglob("*.xplane.pb"))
    spans = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("aires.")]
    return result, sorted(spans, key=lambda sp: sp[1])


def counted(spans):
    return {n: sum(1 for sp in spans if sp[0] == n) for n in HOST_SPANS}


def assert_nested(spans):
    [(_, lo, hi, root)] = [sp for sp in spans if sp[0] == tracing.STUDY]
    for name, s, e, args in spans:
        assert lo <= s <= e <= hi, name
        assert args["study"] == root["study"], name


def test_names_are_one_prefixed_tuple():
    assert len(set(tracing.NAMES)) == len(tracing.NAMES) == 14
    assert all(n.startswith("aires.") for n in tracing.NAMES)
    assert tracing.COUNTERS == ("chunks_run", "active_row_chunks",
                                "steps_run", "hist_flushes",
                                "domain_shocks_total", "shock_killed_total")
    assert set(tracing.SUMMED) < set(tracing.COUNTERS)


def test_run_replications_records_each_span_once(tmp_path):
    rep, spans = profiled(tmp_path, lambda: run_replications(
        BASE, 16, engine="ctmc", base_seed=3))
    assert counted(spans) == dict.fromkeys(HOST_SPANS, 1)
    assert_nested(spans)
    args = {n: a for n, _, _, a in spans}
    assert args[tracing.STUDY]["points"] == 1
    assert args[tracing.STUDY]["replicas"] == 16
    assert args[tracing.PREPARE]["rows"] == 16
    assert args[tracing.PREPARE]["real_rows"] == 16
    assert args[tracing.AGGREGATE]["point"] == 0
    tr = args[tracing.TRANSFER]
    assert tr["real_rows"] == 16 and tr["chunk"] == vz.DEFAULT_CHUNK_STEPS
    assert tr["steps_run"] >= tr["chunks_run"] * tr["chunk"] > 0
    assert 0 < tr["active_row_chunks"] <= tr["chunks_run"] * 16
    assert tr["steps_run"] // tr["hist_flushes"] == tr["chunk"]
    assert "per_shard" not in tr
    # the spans follow one another in the study's order
    order = [n for n, _, _, _ in spans]
    assert order == list(HOST_SPANS)
    assert rep.arrays.keys().isdisjoint(tracing.COUNTERS)


def test_batch_records_one_aggregate_per_point(tmp_path):
    reps, spans = profiled(tmp_path, lambda: run_replications_batch(
        GRID, 12, engine="ctmc", base_seed=4))
    assert counted(spans) == {tracing.STUDY: 1, tracing.PREPARE: 1,
                              tracing.WAIT: 1, tracing.TRANSFER: 1,
                              tracing.AGGREGATE: 3}
    assert_nested(spans)
    args = {n: a for n, _, _, a in spans}
    # bucketing pads 3 points x 12 replicas to 4 x 16 rows
    assert args[tracing.PREPARE]["rows"] == 64
    assert args[tracing.PREPARE]["real_rows"] == 36
    assert args[tracing.TRANSFER]["real_rows"] == 36
    assert args[tracing.STUDY]["points"] == 3
    points = [a["point"] for n, _, _, a in spans if n == tracing.AGGREGATE]
    assert points == [0, 1, 2]
    assert len(reps) == 3
    # each point's span counts the run records its stats pooled
    pooled = [a["pooled"] for n, _, _, a in spans if n == tracing.AGGREGATE]
    ring = reps[0].arrays["run_durations"].shape[1]
    assert pooled == [int(np.minimum(r.arrays["n_runs"], ring).sum())
                      for r in reps]
    assert min(pooled) > 0


def test_multijob_batch_records_each_span(tmp_path):
    reps, spans = profiled(tmp_path, lambda: run_multijob_batch(
        MJ_GRID, 12, engine="ctmc", base_seed=4))
    assert counted(spans) == {tracing.STUDY: 1, tracing.PREPARE: 1,
                              tracing.WAIT: 1, tracing.TRANSFER: 1,
                              tracing.AGGREGATE: 2}
    assert_nested(spans)
    args = {n: a for n, _, _, a in spans}
    assert args[tracing.STUDY]["points"] == 2
    # bucketing pads 12 replicas a point to 16 rows
    assert args[tracing.PREPARE]["rows"] == 32
    assert args[tracing.PREPARE]["real_rows"] == 24
    assert args[tracing.TRANSFER]["real_rows"] == 24
    assert [n for n, _, _, _ in spans][:4] == list(HOST_SPANS[:4])
    assert [r.engine for r in reps] == ["ctmc", "ctmc"]


def test_multijob_batch_adds_its_histogram_once_per_chunk(tmp_path):
    _, spans = profiled(tmp_path, lambda: run_multijob_batch(
        MJ_GRID, 12, engine="ctmc", base_seed=5))
    [tr] = [a for n, _, _, a in spans if n == tracing.TRANSFER]
    assert tr["chunk"] == vz.DEFAULT_CHUNK_STEPS
    assert tr["hist_flushes"] > 0
    assert tr["steps_run"] / tr["hist_flushes"] == 64
    # a row is active while any of its jobs runs; never a padding row
    assert 0 < tr["active_row_chunks"] <= tr["chunks_run"] * 24
    assert tr["domain_shocks_total"] == tr["shock_killed_total"] == 0


def test_each_study_gets_its_own_number(tmp_path):
    def two():
        run_replications(BASE, 8, engine="ctmc", base_seed=1)
        run_replications(BASE, 8, engine="ctmc", base_seed=2)

    _, spans = profiled(tmp_path, two)
    roots = [a["study"] for n, _, _, a in spans if n == tracing.STUDY]
    assert len(roots) == 2 and roots[1] == roots[0] + 1
    for number in roots:
        assert counted([sp for sp in spans
                        if sp[3]["study"] == number]) == dict.fromkeys(
                            HOST_SPANS, 1)


def test_event_engine_studies_have_no_spans(tmp_path):
    _, spans = profiled(tmp_path, lambda: run_replications(
        BASE, 2, engine="event", base_seed=1))
    assert spans == []


def _counters(max_steps, early_exit, R=16, p=BASE):
    """The counters of one direct call of the compiled chunk loop."""
    out = vz._run_chunked(*_loop_args(max_steps, early_exit, R, p))
    return {k: int(out[k]) for k in tracing.COUNTERS}, out


def _loop_args(max_steps, early_exit, R, p):
    chunk = min(vz.DEFAULT_CHUNK_STEPS, max_steps)
    return (vz._params_vector(p), jax.random.PRNGKey(5), 1, R, chunk,
            np.int32(max_steps // chunk), max_steps % chunk, early_exit,
            vz._single_job_step(p), vz._initial_state(p, R, None))


@pytest.mark.parametrize("max_steps", [150, 128, 40])
def test_steps_run_is_the_full_budget_without_early_exit(max_steps):
    c, _ = _counters(max_steps, early_exit=False)
    chunk = min(vz.DEFAULT_CHUNK_STEPS, max_steps)
    assert c["chunks_run"] == max_steps // chunk
    assert c["steps_run"] == c["chunks_run"] * chunk + max_steps % chunk
    assert c["steps_run"] == max_steps


def test_steps_run_counts_chunks_and_remainder_under_early_exit():
    budget = 20 * vz.DEFAULT_CHUNK_STEPS + 9
    c, out = _counters(budget, early_exit=True)
    chunk = vz.DEFAULT_CHUNK_STEPS
    assert bool(np.all(np.asarray(out["completed"]) == 1.0))
    assert c["chunks_run"] < 20     # every replica finished early
    # the remainder chunk runs only while some row is unfinished
    assert c["steps_run"] == c["chunks_run"] * chunk


def test_steps_run_includes_a_remainder_that_ran():
    # a 64-day job takes dozens of failures: no replica finishes in the
    # budget, so the remainder chunk runs
    long_job = BASE.replace(job_length=64 * 1440.0)
    c, out = _counters(vz.DEFAULT_CHUNK_STEPS + 9, early_exit=True,
                       p=long_job)
    assert not np.any(np.asarray(out["completed"]) == 1.0)
    assert c["chunks_run"] == 1
    assert c["active_row_chunks"] == 16
    assert c["steps_run"] == vz.DEFAULT_CHUNK_STEPS + 9


@pytest.mark.parametrize("max_steps,early_exit,p", [
    (150, False, BASE),                     # remainder runs
    (128, False, BASE),                     # no remainder
    (20 * vz.DEFAULT_CHUNK_STEPS + 9, True, BASE),  # remainder skipped
    (vz.DEFAULT_CHUNK_STEPS + 9, True,      # remainder runs under early exit
     BASE.replace(job_length=64 * 1440.0)),
], ids=["remainder", "whole_chunks", "early_exit", "early_exit_remainder"])
def test_hist_flushes_count_chunks_and_a_remainder_that_ran(max_steps,
                                                            early_exit, p):
    c, _ = _counters(max_steps, early_exit, p=p)
    rem_ran = c["steps_run"] > c["chunks_run"] * vz.DEFAULT_CHUNK_STEPS
    assert c["hist_flushes"] == c["chunks_run"] + rem_ran
    assert rem_ran == (max_steps % vz.DEFAULT_CHUNK_STEPS > 0
                       and c["chunks_run"] == max_steps
                       // vz.DEFAULT_CHUNK_STEPS)


def test_no_histogram_no_flushes():
    c, out = _counters(150, early_exit=False, p=BASE.replace(histogram=None))
    assert c["hist_flushes"] == 0 and c["steps_run"] == 150
    assert "hist" not in out and "hist_bins" not in out


@pytest.mark.parametrize("early_exit", [True, False])
def test_active_row_share_is_a_share(early_exit):
    c, _ = _counters(12 * vz.DEFAULT_CHUNK_STEPS, early_exit, R=16)
    share = 100.0 * c["active_row_chunks"] / (c["chunks_run"] * 16)
    assert 0.0 < share <= 100.0
    if not early_exit:
        # finished rows ride along until the budget runs out
        assert share < 100.0


def test_padding_rows_never_count_as_active(tmp_path):
    _, spans = profiled(tmp_path, lambda: run_replications_batch(
        GRID, 12, engine="ctmc", base_seed=6, max_steps=64))
    [tr] = [a for n, _, _, a in spans if n == tracing.TRANSFER]
    # one full chunk: at most every real row, never the 28 padding rows
    assert tr["chunks_run"] == 1
    assert 0 < tr["active_row_chunks"] <= 36


def test_outputs_bit_identical_with_the_profiler_on(tmp_path):
    off = run_replications_batch(GRID, 12, engine="ctmc", base_seed=8)
    on, _ = profiled(tmp_path, lambda: run_replications_batch(
        GRID, 12, engine="ctmc", base_seed=8))
    for a, b in zip(off, on):
        assert a.arrays.keys() == b.arrays.keys()
        for k in a.arrays:
            np.testing.assert_array_equal(a.arrays[k], b.arrays[k])
        np.testing.assert_equal(
            {k: dataclasses.asdict(s) for k, s in a.stats.items()},
            {k: dataclasses.asdict(s) for k, s in b.stats.items()})


def test_every_scope_reaches_the_compiled_program():
    grid = [p.replace(repair_distribution="lognormal",
                      distribution_kwargs={"sigma": 1.2}) for p in GRID]
    [(_, _, run, args, kw)] = vz.sweep_programs(grid, 8, seed=0)
    text = run.lower(*args, **kw).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, scope
    assert tracing.SHOCK not in text


def test_multijob_scopes_reach_the_compiled_program():
    step = vmj._MultiJobStep(2, None, vz._hist_channels([MJ_CLUSTER]))
    [(_, _, run, args, kw)] = vz._build_programs(
        [([0, 1], step, MJ_GRID)], 8, 0, None, None, True, True, 0,
        lambda pt: vmj.default_max_steps_multijob(*pt),
        lambda pt: vmj._mj_params_vector(*pt),
        lambda pts, R, _: vmj._mj_initial_state_batch(pts, R, 8))
    text = run.lower(*args, **kw).as_text(debug_info=True)
    # no repair-slot lane and no fault domains on the multi-job engine
    for scope in set(SCOPES) - {tracing.REPAIR_LANE}:
        assert scope in text, scope
    assert tracing.SHOCK not in text


def test_shock_scope_reaches_a_scenario_program():
    [(_, _, run, args, kw)] = vz.sweep_programs(SCEN_GRID, 8, seed=0)
    text = run.lower(*args, **kw).as_text(debug_info=True)
    # exponential repairs: no repair-slot lane
    for scope in set(SCOPES) - {tracing.REPAIR_LANE} | {tracing.SHOCK}:
        assert scope in text, scope


def test_scenario_spans_sit_inside_prepare(tmp_path):
    reps, spans = profiled(tmp_path, lambda: run_replications_batch(
        SCEN_GRID, 12, engine="ctmc", base_seed=4))
    assert counted(spans) == {tracing.STUDY: 1, tracing.PREPARE: 1,
                              tracing.WAIT: 1, tracing.TRANSFER: 1,
                              tracing.AGGREGATE: 3}
    assert_nested(spans)
    [(_, lo, hi, _)] = [sp for sp in spans if sp[0] == tracing.PREPARE]
    scen = [sp for sp in spans if sp[0] == tracing.SCENARIO]
    # each point's parameter columns and step budget
    assert len(scen) >= len(SCEN_GRID)
    assert all(lo <= s <= e <= hi for _, s, e, _ in scen)
    # the counters on the transfer span are the sums of the outputs
    [tr] = [a for n, _, _, a in spans if n == tracing.TRANSFER]
    shocks = sum(r.arrays["n_domain_shocks"].sum() for r in reps)
    killed = sum(r.arrays["n_shock_killed"].sum() for r in reps)
    assert tr["domain_shocks_total"] == int(shocks) > 0
    assert tr["shock_killed_total"] == int(killed) > 0


@pytest.mark.parametrize("p", [BASE, SCEN_GRID[0]],
                         ids=["no_scenario", "scenario"])
def test_shock_counters_equal_the_outputs(p):
    c, out = _counters(20 * vz.DEFAULT_CHUNK_STEPS, early_exit=True, p=p)
    assert c["domain_shocks_total"] == int(np.sum(out["n_domain_shocks"]))
    assert c["shock_killed_total"] == int(np.sum(out["n_shock_killed"]))
    assert (c["domain_shocks_total"] > 0) == (p.fault_domains is not None)


SHARDED = textwrap.dedent("""
    import json
    import jax
    import numpy as np
    from repro.core import FaultTopology, Params, tracing
    from repro.core import vectorized as vz

    assert jax.device_count() == 4
    p = Params(job_size=48, working_pool_size=56, spare_pool_size=8,
               warm_standbys=2, job_length=2000.0)
    grid = [p, p.replace(recovery_time=20.0)]
    # shocks on four shards: the counters against the outputs' sums
    topo = FaultTopology(n_racks=16, racks_per_pod=4,
                         rack_shock_rate=4e-4, pod_shock_rate=2e-4)
    [(_, _, run, args, kw)] = vz.sweep_programs(
        [q.replace(fault_domains=topo) for q in grid], 32, seed=7, shards=4)
    out = run(*args, **kw)
    scen = {k: out[k].tolist() for k in tracing.COUNTERS}
    scen_args = tracing.counter_args({k: out[k] for k in tracing.COUNTERS})
    sums = {m: int(np.asarray(out[m]).sum())
            for m in ("n_domain_shocks", "n_shock_killed")}
    [(_, _, run, args, kw)] = vz.sweep_programs(grid, 32, seed=7, shards=4)
    out = run(*args, **kw)
    shards = {k: out[k].tolist() for k in tracing.COUNTERS}
    span_args = tracing.counter_args({k: out[k] for k in tracing.COUNTERS})
    # an explicit budget keeps its remainder chunk; no early exit runs it
    [(_, _, run, args, kw)] = vz.sweep_programs(
        grid, 32, seed=7, shards=4, max_steps=2 * vz.DEFAULT_CHUNK_STEPS + 9,
        early_exit=False)
    out = run(*args, **kw)
    assert "hist_bins" not in out
    rem = {k: out[k].tolist() for k in tracing.COUNTERS}
    print(json.dumps({"shards": shards, "rem": rem, "args": span_args,
                      "scen": scen, "scen_args": scen_args,
                      "sums": sums}))
""")


@pytest.fixture(scope="module")
def sharded_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + [x for x in [env.get("PYTHONPATH")] if x])
    done = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_sharded_shock_counters_add_up_to_the_outputs(sharded_run):
    scen, args, sums = (sharded_run["scen"], sharded_run["scen_args"],
                        sharded_run["sums"])
    assert all(len(v) == 4 for v in scen.values())
    # one value per shard, each shard's own sum
    assert all(v > 0 for v in scen["domain_shocks_total"])
    assert sum(scen["domain_shocks_total"]) == sums["n_domain_shocks"]
    assert sum(scen["shock_killed_total"]) == sums["n_shock_killed"]
    assert args["domain_shocks_total"] == sums["n_domain_shocks"]
    assert args["shock_killed_total"] == sums["n_shock_killed"]
    # the plain grid has no scenario: every shard reads 0
    assert sharded_run["shards"]["domain_shocks_total"] == [0] * 4


def test_sharded_counters_survive_one_per_shard(sharded_run):
    got = sharded_run
    shards, args = got["shards"], got["args"]
    chunk = vz.DEFAULT_CHUNK_STEPS
    assert all(len(v) == 4 for v in shards.values())
    for c, a, s, h in zip(shards["chunks_run"], shards["active_row_chunks"],
                          shards["steps_run"], shards["hist_flushes"]):
        assert s == c * chunk
        # each shard carries 2 points x 8 replica columns
        assert 0 < a <= c * 16
        # the default budget is whole chunks: one flush per chunk
        assert h == c
    rem = got["rem"]
    assert rem["chunks_run"] == [2] * 4
    assert rem["steps_run"] == [2 * chunk + 9] * 4
    assert rem["hist_flushes"] == [3] * 4
    assert args["steps_run"] == max(shards["steps_run"])
    assert args["chunks_run"] == max(shards["chunks_run"])
    assert args["hist_flushes"] == max(shards["hist_flushes"])
    assert args["active_row_chunks"] == sum(shards["active_row_chunks"])
    assert args["per_shard"] == " ".join(
        f"{k}:" + "/".join(map(str, shards[k])) for k in tracing.COUNTERS)
