"""The chip benchmark's harness: one cell, one run, one result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by name:

* ``BENCHMARK.json`` at the root names the cells (config, traffic,
  chips) and the metrics;
* ``bench/configs/<config>.json`` holds the deployment as it is run
  (``params``: the simulator's Params fields) and names its plain
  reference, ``bench/references/<reference>.py``;
* ``bench/workloads/<traffic>.json`` is the traffic: the entry point,
  the replicas per point, the grid of knob values crossed over the
  config, fixed overrides (``set``), the reference's replicas per point
  and the limits of the comparison;
* ``bench/metrics/<metric>.py`` reads one per-layer metric from a
  reduced profiler trace (``read(view)``, None when there is nothing to
  read).

A run enables the persistent compile cache, builds the cell's study,
warms it up through the same entry with a short step budget that keeps
the compiled program's signature, and then either runs studies back to
back for ``--seconds`` (a closed loop of one caller; the window closes
when the last started study has returned) or traces one study.  After
the window the plain reference runs on the host and decides ``correct``.
"""

from __future__ import annotations

import gc
import importlib.util
import itertools
import json
import math
import shutil
import sys
import tempfile
import time
import warnings
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from . import compare

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's event for every executable built in the process (a compile or
#: a persistent-cache read); none may happen inside the window
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
STUDY_SPAN = "bench.study"


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, bad files, ...)."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(man: dict, name: str) -> dict:
    for cell in man["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str, bench: Path = BENCH) -> dict:
    return load_json(bench / "configs" / f"{name}.json")


def load_traffic(name: str, bench: Path = BENCH) -> dict:
    return load_json(bench / "workloads" / f"{name}.json")


def load_module(kind: str, name: str, bench: Path = BENCH):
    """``bench/<kind>/<name>.py`` as a module (metrics, references)."""
    path = bench / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path)
    if spec is None or not path.is_file():
        raise BenchError(f"no {kind} file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def grid_points(config: dict, traffic: dict) -> List[dict]:
    """The study's points: the config's params, the traffic's fixed
    overrides, crossed over the traffic's grid (first key outermost)."""
    base = dict(config["params"], **traffic.get("set", {}))
    grid = traffic.get("grid", {})
    keys = list(grid)
    return [dict(base, **dict(zip(keys, vals)))
            for vals in itertools.product(*(grid[k] for k in keys))]


def derived_seed(seed: int, *path: int) -> int:
    """A 31-bit seed drawn from ``seed`` and a path of indices."""
    seq = np.random.SeedSequence([int(seed) % 2 ** 64, *path])
    return int(seq.generate_state(1, np.uint32)[0] >> 1)


#: index paths of the derived seeds (first element of the path)
WARM, STUDY, REFERENCE = 0, 1, 2


class Study:
    """One study of a cell through the simulator's public entry."""

    def __init__(self, config: dict, traffic: dict):
        from repro.core import Params

        self.points = grid_points(config, traffic)
        self.params = [Params.from_dict(json.loads(json.dumps(p)))
                       for p in self.points]
        self.replicas = int(traffic["replicas"])
        self.entry = traffic["entry"]
        if self.entry not in ("run_replications", "run_replications_batch"):
            raise BenchError(f"unknown entry {self.entry!r}")
        if self.entry == "run_replications" and len(self.params) != 1:
            raise BenchError("run_replications takes exactly one point")

    def __call__(self, seed: int, max_steps: Optional[int] = None) -> list:
        from repro.core import run_replications, run_replications_batch

        if self.entry == "run_replications":
            return [run_replications(self.params[0], self.replicas,
                                     engine="ctmc", base_seed=seed,
                                     max_steps=max_steps)]
        return run_replications_batch(self.params, self.replicas,
                                      engine="ctmc", base_seed=seed,
                                      max_steps=max_steps)

    def warm_steps(self) -> int:
        """A short step budget with the window's static signature: one
        chunk, and on ``run_replications`` the default budget's
        remainder (the batch path rounds its budget to whole chunks)."""
        from repro.core import vectorized as vz

        chunk = vz.DEFAULT_CHUNK_STEPS
        if self.entry == "run_replications":
            return chunk + vz.default_max_steps(self.params[0]) % chunk
        return chunk

    @property
    def trajectories(self) -> int:
        return len(self.params) * self.replicas


class CompileCounter:
    """Counts executables JAX builds in this process."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def device_info(devices, chips: int) -> dict:
    import jax

    used = devices[:chips]
    peaks = []
    for d in used:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": max(peaks)}


def metric_entries(man: dict, section: str, cell: str) -> List[dict]:
    return [m for m in man[section]
            if "workloads" not in m or cell in m["workloads"]]


def reference_check(config: dict, traffic: dict, study: Study,
                    outputs: List[list], seed: int, bench: Path,
                    log: Callable[[str], None]) -> Dict[str, float]:
    """Numbers compared against the plain reference, by name.

    ``outputs`` holds, per study of the window and per point, the
    per-replica arrays and the aggregated numbers users read
    (:func:`compare.reported`)."""
    ref = load_module("references", config["reference"], bench)
    n_ref = int(traffic["reference_replicas"])
    values = {"unfinished": 0.0}
    zs, stat_zs = [], []
    t0 = time.perf_counter()
    for j, point in enumerate(study.points):
        edges = ref.edges(point["histogram"])
        edges = None if edges is None else np.asarray(edges)
        ref_arrays = ref.simulate_point(point, n_ref,
                                        derived_seed(seed, REFERENCE, j))
        ref_feat = compare.features(ref_arrays, edges)
        for reps in outputs:
            arrays, rep = reps[j]
            values["unfinished"] += compare.unfinished(arrays,
                                                       study.replicas)
            zs.append(compare.z_scores(compare.features(arrays, edges),
                                       ref_feat))
            stat_zs.append(compare.stat_zs(rep, ref_arrays, edges,
                                           study.replicas))
    values["z_max"] = compare.worst_z(zs)
    values["stats_z_max"] = compare.worst_z(stat_zs)
    log(f"reference: {len(study.points)} points x {n_ref} replicas against "
        f"{len(outputs)} studies in {time.perf_counter() - t0:.3f} s; "
        f"worst z {compare.worst_name(zs)}, worst stat z "
        f"{compare.worst_name(stat_zs)}")
    return values


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: Path = ROOT, bench: Path = BENCH,
             require_chip: bool = True, log: Callable[[str], None] = print,
             make_study: Callable[[dict, dict], "Study"] = None) -> dict:
    """One run of one cell; returns the result object.

    ``make_study`` builds what the window drives from the config and the
    traffic (default :class:`Study`); the control and the fault tests
    put other things in the program's place through it.
    """
    import jax

    man = manifest(root)
    cell = find_cell(man, workload)
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devices[0].platform!r} "
                         f"({devices[0].device_kind}); there is no CPU path")
    if len(devices) < cell["chips"]:
        raise BenchError(f"cell {workload!r} needs {cell['chips']} chips, "
                         f"JAX found {len(devices)}")
    config = load_config(cell["config"], bench)
    traffic = load_traffic(cell["traffic"], bench)

    from repro import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    # cache every executable, however quick, so that a run's set-up
    # reads what the first run of the checkout compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()
    try:
        return _measure(man, cell, config, traffic, seed, seconds, trace,
                        t_start, bench, devices, compiles, log,
                        (make_study or Study)(config, traffic))
    finally:
        compiles.close()


def _measure(man, cell, config, traffic, seed, seconds, trace, t_start,
             bench, devices, compiles, log, study) -> dict:
    import jax

    workload, limits = cell["name"], traffic["limits"]

    with warnings.catch_warnings():
        # the warm-up budget leaves replicas unfinished on purpose
        warnings.simplefilter("ignore", RuntimeWarning)
        with jax.profiler.TraceAnnotation("bench.warmup"):
            study(derived_seed(seed, WARM), max_steps=study.warm_steps())
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f} s, {compiles.n} executables built")

    outputs: List[list] = []
    times: List[float] = []
    c0 = compiles.n
    result: dict = {}
    if trace:
        view, window_s = traced_study(study, seed, outputs, times, cell,
                                      log)
        metrics = {}
        for m in metric_entries(man, "per_layer", workload):
            v = load_module("metrics", m["name"], bench).read(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = view.busy_s()
        result["breakdown"] = view.breakdown()
    else:
        window_s = closed_loop(study, seed, seconds, outputs, times)
        e2e = {"setup_s": setup_s,
               "trajectories_per_s": len(times) * study.trajectories
               / window_s,
               "study_p95_s": float(np.percentile(times, 95))}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in metric_entries(man, "end_to_end", workload)}
    window_compiles = compiles.n - c0
    device = device_info(devices, cell["chips"])
    if trace:
        device["busy_s"] = busy
        device["window_s"] = window_s
    log(f"window: {len(times)} studies in {window_s:.3f} s "
        f"(study times {[round(t, 4) for t in times]}), "
        f"{window_compiles} executables built in the window, "
        f"peak HBM {device['memory_peak_bytes']} B")
    if window_compiles:
        raise BenchError(f"the window built {window_compiles} executables: "
                         "the warm-up did not cover its programs")

    # the studies' outputs are host arrays already; keep of each
    # Replications its arrays and the numbers users read from it
    outputs = [[(rep.arrays, compare.reported(rep)) for rep in reps]
               for reps in outputs]
    gc.collect()
    values = reference_check(config, traffic, study, outputs, seed, bench,
                             log)
    failed = sum(1 for reps in outputs
                 if any(compare.unfinished(a, study.replicas)
                        for a, _ in reps))
    result = {"correct": compare.judge(values, limits),
              "attempted": len(outputs), "failed": failed,
              "metrics": metrics, "device": device, **result,
              # the compared numbers come last
              "checks": {k: {"value": finite(values[k]),
                             "limit": limits[k]} for k in limits},
              "_check_lines": compare.check_lines(values, limits)}
    return result


def closed_loop(study: Study, seed: int, seconds: float,
                outputs: list, times: list) -> float:
    """Studies back to back until ``seconds`` have passed; the window
    closes when the last started study has returned."""
    import jax

    t0 = time.perf_counter()
    i = 0
    end = t0
    while end - t0 < seconds:
        ts = time.perf_counter()
        with jax.profiler.TraceAnnotation(STUDY_SPAN):
            outputs.append(study(derived_seed(seed, STUDY, i)))
        end = time.perf_counter()
        times.append(end - ts)
        i += 1
    return end - t0


def traced_study(study: Study, seed: int, outputs: list, times: list,
                 cell: dict, log):
    """One study under the profiler; returns the reduced trace view."""
    import jax

    from . import tracereduce

    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        ts = time.perf_counter()
        with jax.profiler.TraceAnnotation(STUDY_SPAN):
            outputs.append(study(derived_seed(seed, STUDY, 0)))
        times.append(time.perf_counter() - ts)
        jax.profiler.stop_trace()
        path = tracereduce.find_xplane(tmp)
        log(f"trace: {path.stat().st_size} B")
        view = tracereduce.load(path, STUDY_SPAN, cell["chips"])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return view, view.span_s


def emit(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """The result line last on stdout, the compared numbers last on
    stderr."""
    lines = result.pop("_check_lines")
    print(json.dumps(result), file=out, flush=True)
    for line in lines:
        print(line, file=err, flush=True)


def finite(x: float) -> float:
    """JSON has no infinity: an infinite reading prints as 1e300."""
    return x if math.isfinite(x) else 1e300
